"""Shared PDU + Potential-Adder epilogue for the Pallas kernel bodies.

Both fused kernels (``lif_step`` and ``spike_timestep``) end a timestep the
same way the ASIC does: decay the previous membrane potential, add the
accumulated synaptic input, compare against the threshold, apply the reset
mode. The fire/reset semantics live in ONE place —
:func:`repro.core.lif.fire_reset` — and the decay dispatch lives here, so
the kernels, the SpikeEngine reference backend, and the float software
reference can never drift apart.

The ``repro.core`` imports are deliberately deferred to trace time: the
kernels package must stay importable without triggering the core package
(core's engine imports the kernels, and eager imports here would close an
import cycle).
"""

from __future__ import annotations

import jax.numpy as jnp

__all__ = ["DECAY_KINDS", "SHIFT_RATES", "validate_decay", "apply_decay",
           "decay_and_fire"]

# "shift" — Cerebra-H arithmetic-shift decay, rate in {.125,.25,.5,.75}.
# "mul"   — Cerebra-S truncating fixed-point multiply by a raw Q16.16
#           retain factor (the S generation kept the multiplier).
DECAY_KINDS: tuple[str, ...] = ("shift", "mul")

# mirror of repro.core.fixedpoint.SHIFT_DECAY_RATES (kept literal so the
# kernels package needs no eager core import)
SHIFT_RATES: tuple[float, ...] = (0.125, 0.25, 0.5, 0.75)


def validate_decay(decay_kind: str, decay_rate: float, decay_raw: int):
    """Fail at the kernel-build call site, not from inside a traced body.

    Without this, a missing/mismatched decay parameter (e.g. the default
    ``decay_rate=0.0`` with ``decay_kind='shift'``) would only surface as
    a ValueError deep inside fixedpoint.py during kernel tracing.
    """
    if decay_kind == "shift":
        if decay_rate not in SHIFT_RATES:
            raise ValueError(
                f"decay_kind='shift' needs decay_rate in {SHIFT_RATES}, "
                f"got {decay_rate} (did you forget to pass decay_rate?)"
            )
    elif decay_kind == "mul":
        if not 0 <= decay_raw <= (1 << 16):
            raise ValueError(
                f"decay_kind='mul' needs decay_raw in [0, 2^16], got "
                f"{decay_raw} (did you forget to pass decay_raw?)"
            )
    else:
        raise ValueError(
            f"unknown decay kind {decay_kind!r}; expected one of "
            f"{DECAY_KINDS}"
        )


def apply_decay(x, decay_kind: str, decay_rate: float, decay_raw: int):
    """One Potential-Decay Unit on raw int32 state: the shift PDU at
    ``decay_rate`` (``'shift'``) or the truncating multiply by the Q16.16
    retain factor ``decay_raw`` (``'mul'``)."""
    from repro.core import fixedpoint as fxp

    if decay_kind == "shift":
        return fxp.shift_decay(x, decay_rate)
    if decay_kind == "mul":
        return fxp.fx_mul(x, jnp.int32(decay_raw))
    raise ValueError(
        f"unknown decay kind {decay_kind!r}; expected one of {DECAY_KINDS}"
    )


def decay_and_fire(v, acc, *, decay_kind: str, decay_rate: float,
                   decay_raw: int, threshold_raw: int, reset_mode: str,
                   i=None, syn_decay: tuple | None = None):
    """Decay previous potential, integrate, fire, reset. All int32.

    Pure jnp ops only (shifts, bitwise, wrapping adds) so it traces inside
    Pallas kernel bodies and inside plain jitted scan bodies alike.
    Returns (v_out, spikes) int32.

    With a synaptic current ``i`` and its decay ``syn_decay`` (a ``(kind,
    rate, raw)`` triple, as for the membrane), the accumulate adds to the
    decayed current and the membrane integrates the new current:
    returns (v_out, spikes, i_out).
    """
    from repro.core.lif import fire_reset

    if i is not None:
        acc = apply_decay(i, *syn_decay) + acc
    v_out, spikes = fire_reset(
        apply_decay(v, decay_kind, decay_rate, decay_raw) + acc,
        jnp.int32(threshold_raw), reset_mode)
    if i is None:
        return v_out, spikes
    return v_out, spikes, acc
