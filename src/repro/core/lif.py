"""Leaky Integrate-and-Fire neuron models.

Two parallel implementations, mirroring the paper's evaluation methodology:

* :func:`lif_step_float` — the *software reference* (float32, arbitrary
  decay beta, soft or hard reset). This plays the role of the paper's
  PyTorch/snnTorch reference models.
* :func:`lif_step_fixed` — the *hardware model* (bit-exact int32 Q16.16,
  shift-based decay restricted to the four hardware rates, three reset
  modes). This plays the role of the RTL simulation.

A neuron with current-based synapses (``LIFParams.syn_decay_rate`` set)
carries a second state, the synaptic current ``i``, with a leak of its
own: :func:`cuba_step_fixed` and :func:`cuba_step_float` are its hardware
model and software reference. The current enters the membrane in the step
it is accumulated, so the epilogue stays "accumulate -> decay -> add ->
compare"::

    i_t        = decay_syn(i_{t-1}) + acc_t
    u_t        = decay_mem(v_{t-1}) + i_t
    (v_t, s_t) = fire_reset(u_t, threshold, reset)

Both are pure functions over explicit state so they compose with
``jax.lax.scan`` over timesteps and with ``vmap``/``pjit`` over batch and
population axes.

Hardware semantics (paper §IV-B, §V-A):
  - Accumulator integrates incoming weighted events over a timestep.
  - Potential Decay Unit decays the *previous* membrane potential.
  - Potential Adder combines decayed potential + accumulated input, compares
    against threshold, emits spike, applies reset mode:
      * ``hold``        — membrane unchanged on spike,
      * ``zero``        — reset to 0,
      * ``subtract``    — subtract threshold.
"""

from __future__ import annotations

import dataclasses
from typing import Literal

import jax
import jax.numpy as jnp

from repro.core import fixedpoint as fxp

__all__ = [
    "LIFParams",
    "LIFState",
    "lif_init",
    "fire_reset",
    "lif_step_float",
    "lif_step_fixed",
    "cuba_step_float",
    "cuba_step_fixed",
    "surrogate_spike",
]

ResetMode = Literal["hold", "zero", "subtract"]
RESET_MODES: tuple[str, ...] = ("hold", "zero", "subtract")


@dataclasses.dataclass(frozen=True)
class LIFParams:
    """Static LIF configuration (compile-time constants for the kernels)."""

    decay_rate: float = 0.25          # fraction of potential removed / step
    threshold: float = 1.0
    reset_mode: ResetMode = "zero"
    fmt: fxp.FixedPointFormat = fxp.Q16_16
    # fraction of the synaptic current removed / step; None is the
    # one-state LIF (no current: the accumulate adds to the membrane)
    syn_decay_rate: float | None = None

    @property
    def beta(self) -> float:
        """Retain factor (snnTorch convention)."""
        return 1.0 - self.decay_rate

    @property
    def has_current(self) -> bool:
        return self.syn_decay_rate is not None

    @property
    def threshold_raw(self) -> int:
        return int(round(self.threshold * self.fmt.scale))


class LIFState:
    """Namespace marker; state is a plain dict pytree: {'v': array}."""


def lif_init(shape, *, fixed: bool = False):
    dtype = jnp.int32 if fixed else jnp.float32
    return {"v": jnp.zeros(shape, dtype)}


def fire_reset(v_new, threshold, reset_mode: str):
    """The hardware Potential-Adder epilogue: threshold compare + reset.

    This is THE single definition of fire/reset semantics. Every datapath
    (float software reference, int32 hardware model, the SpikeEngine
    backends, and the Pallas kernel bodies) calls this function, so the
    three reset modes can never drift apart between implementations.

    Args:
      v_new: (..., N) decayed-and-integrated membrane potential; float32
        for the software path, int32 raw fixed point for the hardware path.
      threshold: scalar of matching dtype (float threshold or raw Q-format
        int32 threshold).
    Returns:
      (v_out, spikes) with spikes in {0,1} of ``v_new``'s dtype.
    """
    spikes = (v_new >= threshold).astype(v_new.dtype)
    if reset_mode == "zero":
        v_out = jnp.where(spikes > 0, jnp.zeros_like(v_new), v_new)
    elif reset_mode == "subtract":
        v_out = v_new - spikes * threshold
    elif reset_mode == "hold":
        v_out = v_new
    else:
        raise ValueError(f"unknown reset mode {reset_mode!r}; "
                         f"expected one of {RESET_MODES}")
    return v_out, spikes


def lif_step_float(state, syn_input, params: LIFParams):
    """Software-reference LIF step (float32).

    Args:
      state: {'v': (..., N) float32} membrane potential from prev step.
      syn_input: (..., N) float32 accumulated synaptic current this step.
      params: LIFParams.
    Returns:
      (new_state, spikes float32 in {0,1})
    """
    v = state["v"]
    v_decayed = v * params.beta
    v_new = v_decayed + syn_input
    v_out, spikes = fire_reset(v_new, jnp.float32(params.threshold),
                               params.reset_mode)
    return {"v": v_out}, spikes


def lif_step_fixed(state, syn_input_raw, params: LIFParams):
    """Hardware-model LIF step (bit-exact int32, shift decay).

    Args:
      state: {'v': (..., N) int32 raw fixed point}.
      syn_input_raw: (..., N) int32 raw accumulated weights (the
        accumulator-unit output for this timestep).
      params: LIFParams. ``decay_rate`` must be one of the hardware rates.
    Returns:
      (new_state, spikes int32 in {0,1})
    """
    v = state["v"]
    v_decayed = fxp.shift_decay(v, params.decay_rate)
    # Hardware adders wrap; jnp int32 add wraps too.
    v_new = v_decayed + syn_input_raw
    v_out, spikes = fire_reset(v_new, jnp.int32(params.threshold_raw),
                               params.reset_mode)
    return {"v": v_out}, spikes


def cuba_step_float(state, syn_input, params: LIFParams):
    """Software-reference current-based LIF step (float32).

    Args:
      state: {'v': (..., N), 'i': (..., N)} float32 membrane potential and
        synaptic current from the previous step.
      syn_input: (..., N) float32 accumulated synaptic input this step.
    Returns:
      (new_state, spikes float32 in {0,1})
    """
    i = state["i"] * (1.0 - params.syn_decay_rate) + syn_input
    v_out, spikes = fire_reset(state["v"] * params.beta + i,
                               jnp.float32(params.threshold),
                               params.reset_mode)
    return {"v": v_out, "i": i}, spikes


def cuba_step_fixed(state, syn_input_raw, params: LIFParams):
    """Hardware-model current-based LIF step (bit-exact int32, shift decays).

    The plain reference of the two-state neuron: straight int32 ``jnp``,
    no kernel, no masking, no batching of its own.

    Args:
      state: {'v': (..., N), 'i': (..., N)} int32 raw fixed point.
      syn_input_raw: (..., N) int32 accumulated weights this step.
      params: LIFParams; ``decay_rate`` and ``syn_decay_rate`` must be
        hardware rates.
    Returns:
      (new_state, spikes int32 in {0,1})
    """
    # hardware adders wrap; jnp int32 adds wrap too
    i = fxp.shift_decay(state["i"], params.syn_decay_rate) + syn_input_raw
    u = fxp.shift_decay(state["v"], params.decay_rate) + i
    v_out, spikes = fire_reset(u, jnp.int32(params.threshold_raw),
                               params.reset_mode)
    return {"v": v_out, "i": i}, spikes


# --------------------------------------------------------------------------
# Surrogate gradient (training substrate; paper trains offline in snnTorch —
# we train offline in JAX with the fast-sigmoid surrogate of Zenke & Ganguli)
# --------------------------------------------------------------------------

@jax.custom_vjp
def surrogate_spike(v_minus_thr, slope: float = 25.0):
    """Heaviside spike with fast-sigmoid surrogate gradient."""
    del slope
    return (v_minus_thr >= 0.0).astype(jnp.float32)


def _surrogate_fwd(v_minus_thr, slope=25.0):
    return surrogate_spike(v_minus_thr, slope), (v_minus_thr, slope)


def _surrogate_bwd(res, g):
    v, slope = res
    denom = (1.0 + slope * jnp.abs(v)) ** 2
    return (g / denom, None)


surrogate_spike.defvjp(_surrogate_fwd, _surrogate_bwd)


def lif_step_train(state, syn_input, params: LIFParams, slope: float = 25.0):
    """Differentiable LIF step used for BPTT surrogate-gradient training."""
    v = state["v"]
    v_new = v * params.beta + syn_input
    spikes = surrogate_spike(v_new - params.threshold, slope)
    if params.reset_mode == "zero":
        # straight-through on reset: detach the reset gate
        gate = jax.lax.stop_gradient(spikes)
        v_out = v_new * (1.0 - gate)
    elif params.reset_mode == "subtract":
        v_out = v_new - jax.lax.stop_gradient(spikes) * params.threshold
    else:  # hold
        v_out = v_new
    return {"v": v_out}, spikes
