"""SpikeEngine — the single timestep core every accelerator model runs on.

The paper's central claim is that ONE fused accelerator timestep
(event-gated weight fetch + accumulate + LIF fire/reset) serves both
Cerebra generations and multiple co-resident models. This module is that
timestep, in software: it owns the scan loop over time, the carries
(membrane potential + previous-boundary spikes), and per-program jit
caching, and dispatches the inner accumulate+fire to a pluggable backend:

  ``"reference"``   pure-jnp int32 matmul + shared LIF epilogue. Bit-exact
                    oracle semantics; fastest on CPU.
  ``"pallas"``      the event-gated Pallas kernel
                    (:func:`repro.kernels.ops.spike_timestep`): silent
                    source blocks skip both compute and weight traffic.
                    Bit-exact vs ``"reference"`` for any int32 image (the
                    MXU accumulates the image's four byte planes, each
                    exact in bf16). Interpreted on CPU, compiled Mosaic
                    on TPU.
  ``"pallas-mxu"``  same kernel with one fp32 MXU accumulate. Exact only
                    while per-output partial sums stay below 2^24; the
                    bound is enforced AT ENGINE BUILD TIME from the weight
                    image (worst-case per-block column sums), so a program
                    that could ever produce an inexact sum refuses to
                    compile instead of silently mis-accumulating.

Frontends (``cerebra_s``, ``cerebra_h``, ``session``) contribute only a
compile step (placement + quantized weight image + decay spec) and a pure
cost-model pass over the resulting spike raster; the functional semantics
live here, once.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import fixedpoint as fxp
from repro.core.lif import lif_init

__all__ = [
    "BACKENDS",
    "GATES",
    "MXU_EXACT_BOUND",
    "DecaySpec",
    "SpikeEngine",
    "mxu_partial_sum_bound",
    "sources_raster",
]

BACKENDS: tuple[str, ...] = ("reference", "pallas", "pallas-mxu")

# Event-gate granularity of the Pallas kernels (the Incoming Forwarder):
#   "batch-tile"   one activity scalar per (8-example batch tile, source
#                  block) — a fetch is skipped only when the WHOLE tile is
#                  silent on that block (high-throughput batch inference).
#   "per-example"  batch tile = 1: one activity scalar per (example,
#                  source block), so each stream's silence skips its own
#                  weight traffic — the serving mode, where slot batches
#                  are mostly idle. Bit-identical outputs either way; the
#                  gate only changes which already-zero work is skipped.
GATES: tuple[str, ...] = ("batch-tile", "per-example")

_GATE_TILE_BATCH = 8  # batch rows per activity scalar under "batch-tile"

# f32 has a 24-bit significand: integer-valued accumulation stays exact
# while every partial sum's magnitude is < 2^24.
MXU_EXACT_BOUND: int = 1 << 24

_MXU_BLOCK_SRC = 128  # source-block size the MXU accumulate reduces over


@dataclasses.dataclass(frozen=True)
class DecaySpec:
    """Which Potential-Decay Unit the program compiled for.

    ``kind='shift'`` — Cerebra-H arithmetic-shift decay; ``rate`` must be a
    hardware-supported rate. ``kind='mul'`` — Cerebra-S truncating
    fixed-point multiply; ``raw`` is the Q16.16 retain factor.
    """

    kind: str
    rate: float = 0.0
    raw: int = 0

    @classmethod
    def shift(cls, rate: float) -> "DecaySpec":
        if rate not in fxp.SHIFT_DECAY_RATES:
            raise ValueError(
                f"shift decay rate {rate} not in {fxp.SHIFT_DECAY_RATES}"
            )
        return cls(kind="shift", rate=float(rate))

    @classmethod
    def mul(cls, raw: int) -> "DecaySpec":
        # raw == 2^16 is beta = 1.0: fx_mul's hi/lo split is the exact
        # identity there (a_hi*2^16 + a_lo == a), so leak-free IF neurons
        # (decay_rate = 0.0) are a valid Cerebra-S configuration.
        if not 0 <= raw <= (1 << 16):
            raise ValueError(
                f"mul retain factor {raw} outside [0, 2^16]"
            )
        return cls(kind="mul", raw=int(raw))

    @property
    def triple(self) -> tuple:
        """``(kind, rate, raw)``: the form the kernels take it in."""
        return (self.kind, self.rate, self.raw)


def mxu_partial_sum_bound(weights_raw: np.ndarray,
                          block_src: int = _MXU_BLOCK_SRC, *,
                          fuse_steps: int = 1) -> int:
    """Worst-case f32 partial-sum magnitude of the MXU accumulate.

    Both kernels reduce over source blocks of ``block_src`` rows; sources
    are {0,1}, so the worst case for an output column is the sum of |w|
    over one block. Inter-block accumulation happens in int32 and is
    always exact, so only the intra-block bound matters.

    ``fuse_steps`` is accepted so callers state the K they validate for:
    the bound is K-INVARIANT by construction. The K-step fused kernel
    stacks the window along the dot's BATCH axis (K*Bb rows of {0,1}
    sources against one block), and its per-step recurrent accumulate is
    chunked at ``block_src`` rows with int32 inter-chunk adds — no f32
    reduction ever spans more than one ``block_src`` block, for any K.
    """
    if fuse_steps < 1:
        raise ValueError(f"fuse_steps must be >= 1, got {fuse_steps}")
    w = np.abs(np.asarray(weights_raw, np.int64))
    S = w.shape[0]
    pad = (-S) % block_src
    if pad:
        w = np.pad(w, ((0, pad), (0, 0)))
    blocks = w.reshape(-1, block_src, w.shape[1]).sum(axis=1)
    return int(blocks.max()) if blocks.size else 0


def sources_raster(ext_spikes, spikes):
    """(T, B, S) source activity: external spikes + PREVIOUS-step spikes.

    The accelerator captures array spikes at the timestep boundary, so the
    sources of step t are the spikes of step t-1 (none before step 0).
    The cost models consume this instead of re-running the scan.
    """
    ext = jnp.asarray(ext_spikes).astype(jnp.int32)
    spk = jnp.asarray(spikes, jnp.int32)
    prev = jnp.concatenate([jnp.zeros_like(spk[:1]), spk[:-1]], axis=0)
    return jnp.concatenate([ext, prev], axis=-1)


class SpikeEngine:
    """One physical neuron array stepping under a fixed LIF configuration.

    The engine is the only owner of the functional timestep:

        sources_t = concat(external_t, spikes_{t-1})          # (B, S)
        syn_t     = sources_t @ W_raw                         # backend
        v_t, spikes_t = fire_reset(decay(v_{t-1}) + syn_t)    # shared LIF

    With ``syn_decay`` the neurons are current-based: each carries a
    synaptic current ``i`` beside ``v``, and the epilogue becomes

        i_t = syn_decay(i_{t-1}) + syn_t
        v_t, spikes_t = fire_reset(decay(v_{t-1}) + i_t)

    (:func:`repro.core.lif.cuba_step_fixed`). The carry then holds
    ``{'v', 'i', 'spikes'}``; without it, ``{'v', 'spikes'}`` as ever.

    Construction validates the backend (including the pallas-mxu 2^24
    exactness bound); :meth:`run` jit-compiles the whole scan once per
    engine and reuses it across calls (per-program jit caching).
    """

    def __init__(
        self,
        weights_raw,
        n_inputs: int,
        *,
        decay: DecaySpec,
        threshold_raw: int,
        reset_mode: str,
        backend: str = "reference",
        interpret: bool | None = None,
        gate: str = "batch-tile",
        fuse_steps: int = 1,
        syn_decay: DecaySpec | None = None,
    ):
        if backend not in BACKENDS:
            raise ValueError(
                f"unknown backend {backend!r}; expected one of {BACKENDS}"
            )
        if gate not in GATES:
            raise ValueError(
                f"unknown event gate {gate!r}; expected one of {GATES}"
            )
        fuse_steps = int(fuse_steps)
        if fuse_steps < 1:
            raise ValueError(f"fuse_steps must be >= 1, got {fuse_steps}")
        weights_raw = jnp.asarray(weights_raw, jnp.int32)
        if weights_raw.ndim != 2:
            raise ValueError(
                f"weights must be a flat (n_sources, n_phys) SRAM image, "
                f"got shape {weights_raw.shape}"
            )
        n_sources, n_phys = weights_raw.shape
        if not 0 <= n_inputs <= n_sources:
            raise ValueError(
                f"n_inputs={n_inputs} outside [0, {n_sources}]"
            )
        if n_inputs + n_phys != n_sources:
            raise ValueError(
                f"source axis {n_sources} != n_inputs {n_inputs} + "
                f"n_phys {n_phys}: recurrent spikes could not be fed back"
            )
        if backend == "pallas-mxu":
            worst = mxu_partial_sum_bound(np.asarray(weights_raw),
                                          fuse_steps=fuse_steps)
            if worst >= MXU_EXACT_BOUND:
                w_max = int(np.abs(np.asarray(weights_raw)).max())
                raise ValueError(
                    f"pallas-mxu backend rejected at compile time: "
                    f"worst-case f32 partial sum {worst} >= 2^24 "
                    f"({MXU_EXACT_BOUND}) for max |w| = {w_max} raw Q16.16, "
                    f"per-block source fan-in {_MXU_BLOCK_SRC}, "
                    f"fuse_steps K = {fuse_steps} (the bound is "
                    f"K-invariant: the fused window stacks along the dot's "
                    f"batch axis, never its reduction axis); the MXU "
                    f"accumulate would not be bit-exact for this weight "
                    f"image. Reduce fan-in or weight magnitudes, or use "
                    f"backend='pallas'."
                )
        self.weights_raw = weights_raw
        self.n_inputs = int(n_inputs)
        self.n_phys = int(n_phys)
        self.n_sources = int(n_sources)
        self.decay = decay
        self.syn_decay = syn_decay
        self.threshold_raw = int(threshold_raw)
        self.reset_mode = str(reset_mode)
        self.backend = backend
        self.interpret = interpret
        self.gate = gate
        # K timesteps per kernel invocation (the fused Pallas window);
        # part of the engine identity, so the lazily-built jit caches
        # below are keyed by it structurally — one compiled program per
        # (engine, K). fuse_steps == 1 keeps the single-step kernels.
        self.fuse_steps = fuse_steps
        self._run_jit = None  # compiled scan, built lazily once per engine
        self._chunk_jit = None  # compiled masked chunk step (streaming path)

    # ------------------------------------------------------------------
    def _scan_weights(self):
        """The weight image :meth:`run`/:meth:`step_chunk` dispatch with.

        Subclasses may substitute an equivalent re-hosted image (the mesh
        engine hands back its padded, device-sharded SRAM slices); the
        logical program — and therefore the numbers — must not change.
        """
        return self.weights_raw

    def to_mesh(self, mesh):
        """Drop-in scale-out: this engine's program re-hosted on a device
        mesh (:class:`repro.distributed.spike_mesh.MeshSpikeEngine`), with
        bit-identical ``run``/``step_chunk`` semantics."""
        from repro.distributed.spike_mesh import MeshSpikeEngine

        return MeshSpikeEngine.from_engine(self, mesh)

    @property
    def has_current(self) -> bool:
        """True for current-based neurons (a synaptic current ``i`` in
        the carry)."""
        return self.syn_decay is not None

    @property
    def carry_keys(self) -> tuple[str, ...]:
        """The states of a slot's carry, in :meth:`init_carry`'s order."""
        return ("v", "spikes", "i") if self.has_current else ("v", "spikes")

    def _program(self) -> dict:
        """The constructor arguments that fix this engine's program."""
        return dict(decay=self.decay, syn_decay=self.syn_decay,
                    threshold_raw=self.threshold_raw,
                    reset_mode=self.reset_mode, backend=self.backend,
                    interpret=self.interpret, gate=self.gate,
                    fuse_steps=self.fuse_steps)

    def with_gate(self, gate: str) -> "SpikeEngine":
        """This engine's program re-hosted under another event-gate
        granularity (bit-identical outputs; only skipped-zero work
        differs). Returns ``self`` when the gate already matches."""
        if gate == self.gate:
            return self
        return SpikeEngine(self.weights_raw, self.n_inputs,
                           **dict(self._program(), gate=gate))

    def with_fuse_steps(self, fuse_steps: int) -> "SpikeEngine":
        """This engine's program re-hosted under another K-step fusion
        window (bit-identical outputs; only kernel granularity and weight
        traffic differ). Returns ``self`` when K already matches."""
        if int(fuse_steps) == self.fuse_steps:
            return self
        return SpikeEngine(self.weights_raw, self.n_inputs,
                           **dict(self._program(), fuse_steps=fuse_steps))

    # ------------------------------------------------------------------
    def init_carry(self, batch: int) -> dict:
        """The unified initial accelerator state: V = 0, no prior spikes.

        Both Cerebra generations power up with cleared membrane SRAM; this
        is the single definition (via :func:`repro.core.lif.lif_init`)
        that ``cerebra_s.run`` and ``cerebra_h.run`` previously duplicated
        inconsistently. A current-based engine adds the synaptic current
        ``'i'``, zero at power-on too.
        """
        carry = {
            "v": lif_init((batch, self.n_phys), fixed=True)["v"],
            "spikes": jnp.zeros((batch, self.n_phys), jnp.int32),
        }
        if self.has_current:
            carry["i"] = jnp.zeros((batch, self.n_phys), jnp.int32)
        return carry

    # ------------------------------------------------------------------
    def _step(self, weights, carry, ext_t):
        """One fused timestep for a batch of external spike vectors."""
        sources = jnp.concatenate(
            [ext_t.astype(jnp.int32), carry["spikes"]], axis=-1
        )  # (B, S)
        # deferred: breaks the core <-> kernels import cycle
        from repro.kernels import ops
        from repro.kernels.epilogue import decay_and_fire

        currents = (carry["i"],) if self.has_current else ()
        if self.backend == "reference":
            syn = jax.lax.dot_general(
                sources,
                weights,
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.int32,
            )
            v_out, spikes, *currents = decay_and_fire(
                carry["v"], syn, i=carry.get("i"), **self._epilogue)
        else:
            v_out, spikes, *currents = ops.spike_timestep(
                sources,
                weights,
                carry["v"],
                *currents,
                **self._epilogue,
                use_mxu=(self.backend == "pallas-mxu"),
                block_batch=(1 if self.gate == "per-example"
                             else _GATE_TILE_BATCH),
                interpret=self.interpret,
            )
        return dict(zip(self.carry_keys, (v_out, spikes, *currents))), spikes

    @property
    def _epilogue(self) -> dict:
        """The LIF epilogue's static arguments, as every backend takes
        them (``syn_decay`` None for one-state neurons)."""
        return dict(
            decay_kind=self.decay.kind,
            decay_rate=self.decay.rate,
            decay_raw=self.decay.raw,
            threshold_raw=self.threshold_raw,
            reset_mode=self.reset_mode,
            syn_decay=(None if self.syn_decay is None
                       else self.syn_decay.triple),
        )

    def step(self, carry, ext_t):
        """Public single-step entry (closed-loop / streaming callers).

        One timestep of the batch scan body, un-jitted: ``(carry,
        ext_t (B, n_inputs)) -> (carry', spikes_t)``. Chaining ``step`` T
        times is bit-identical to one :meth:`run` over the stacked train
        (same backend dispatch, same shared epilogue).
        """
        return self._step(self.weights_raw, carry, ext_t)

    # ------------------------------------------------------------------
    # Streaming path: a fixed slot batch advanced T steps under a
    # per-(step, slot) activity mask. Inactive slots keep their carry
    # bit-for-bit (a paused stream must resume exactly where it stopped),
    # which is what lets one compiled program serve churning traffic:
    # the serving layer pins (chunk_steps, n_slots) and pads with
    # active = 0 instead of recompiling per request shape.
    # ------------------------------------------------------------------
    def _masked_chunk_scan(self, step_fn, carry, ext, active):
        """THE masked-slot scan: advance via ``step_fn`` where active,
        keep the carry bit-for-bit (and report zero spikes) where not.
        Single definition — the mesh engine scans the same body with its
        spike-exchange step, so the paused-stream contract cannot drift
        between the single-device and sharded paths."""

        def body(c, xs):
            ext_t, act_t = xs
            new, spikes = step_fn(c, ext_t)
            keep = act_t[:, None] != 0
            c_out = {k: jnp.where(keep, new[k], c[k]) for k in c}
            return c_out, jnp.where(keep, spikes, 0)

        return jax.lax.scan(body, carry, (ext, active))

    # ------------------------------------------------------------------
    # K-step fused path: with fuse_steps > 1 on a Pallas backend, run /
    # step_chunk scan over K-step WINDOWS, each one fused kernel call
    # (weight blocks fetched once per window instead of once per step).
    # A ragged T pads up to a K multiple with active = 0 — the kernel's
    # in-body masked-slot contract makes the remainder byte-identical to
    # the unfused masked scan, so no separate remainder program exists.
    # ------------------------------------------------------------------
    @property
    def _use_fused(self) -> bool:
        return self.fuse_steps > 1 and self.backend != "reference"

    def _window(self, weights, carry, ext_w, act_w):
        """One fused K-step window: (carry, (K,B,*) inputs) -> (carry',
        (K,B,P) emitted raster)."""
        from repro.kernels import ops  # deferred: breaks import cycle

        currents = (carry["i"],) if self.has_current else ()
        v_out, spk_carry, raster, *currents = ops.spike_timestep_fused(
            ext_w, carry["spikes"], weights, carry["v"], act_w, *currents,
            n_inputs=self.n_inputs,
            **self._epilogue,
            use_mxu=(self.backend == "pallas-mxu"),
            block_batch=(1 if self.gate == "per-example"
                         else _GATE_TILE_BATCH),
            interpret=self.interpret,
        )
        new = dict(zip(self.carry_keys, (v_out, spk_carry, *currents)))
        return new, raster

    def _fused_scan(self, weights, carry, ext, active):
        K = self.fuse_steps
        T, B = ext.shape[0], ext.shape[1]
        pad = (-T) % K
        if pad:
            ext = jnp.pad(ext, ((0, pad), (0, 0), (0, 0)))
            active = jnp.pad(active, ((0, pad), (0, 0)))
        nw = (T + pad) // K
        ext_w = ext.reshape(nw, K, B, self.n_inputs)
        act_w = active.reshape(nw, K, B)
        body = lambda c, xs: self._window(weights, c, xs[0], xs[1])
        final, raster = jax.lax.scan(body, carry, (ext_w, act_w))
        return final, raster.reshape(nw * K, B, self.n_phys)[:T]

    def _chunk_impl(self, weights, carry, ext, active):
        # the scope prefixes every op's ``op_name`` in the HLO metadata a
        # profile carries, telling the chunk step's ops from eager ones
        with jax.named_scope("snn.step_chunk"):
            if self._use_fused:
                return self._fused_scan(weights, carry, ext, active)
            step = lambda c, x: self._step(weights, c, x)
            return self._masked_chunk_scan(step, carry, ext, active)

    def step_chunk(self, carry, ext, active=None):
        """Advance a slot batch over a chunk of timesteps, with masking.

        Args:
          carry: {'v': (B, n_phys), 'spikes': (B, n_phys)} int32 slot state
            (and 'i', the synaptic current, for a current-based engine).
          ext: (T, B, n_inputs) external spikes; rows of inactive slots are
            ignored (conventionally zero).
          active: (T, B) mask; slot b consumes step t iff active[t, b] != 0.
            None means all slots active every step (the batch semantics).
        Returns:
          (carry', spikes (T, B, n_phys)): active slots advance exactly as
          :meth:`run`'s scan body would; inactive slots keep their carry
          unchanged and report zero spikes.

        The jitted chunk step is cached on the engine; XLA reuses one
        compiled program per (T, B) shape, so a serving layer that fixes
        its slot-batch shape compiles exactly once.
        """
        ext = jnp.asarray(ext).astype(jnp.int32)
        if ext.ndim != 3 or ext.shape[2] != self.n_inputs:
            raise ValueError(
                f"ext must be (T, B, {self.n_inputs}), got {ext.shape}"
            )
        if active is None:
            active = jnp.ones(ext.shape[:2], jnp.int32)
        active = jnp.asarray(active, jnp.int32)
        if active.shape != ext.shape[:2]:
            raise ValueError(
                f"active mask must be {ext.shape[:2]}, got {active.shape}"
            )
        if self._chunk_jit is None:
            self._chunk_jit = jax.jit(self._chunk_impl)
        return self._chunk_jit(self._scan_weights(), carry, ext, active)

    # ------------------------------------------------------------------
    def _run_impl(self, weights, ext_spikes):
        carry = self.init_carry(ext_spikes.shape[1])
        if self._use_fused:
            active = jnp.ones(ext_spikes.shape[:2], jnp.int32)
            final, spikes = self._fused_scan(
                weights, carry, ext_spikes, active)
        else:
            step = lambda c, x: self._step(weights, c, x)
            final, spikes = jax.lax.scan(step, carry, ext_spikes)
        out = {"spikes": spikes, "v_final": final["v"]}
        if self.has_current:
            out["i_final"] = final["i"]
        return out

    def run(self, ext_spikes, *, events_capacity: int | None = None,
            events_policy: str = "error") -> dict:
        """Scan the engine over a spike train.

        Args:
          ext_spikes: (T, B, n_inputs) in {0,1} (any int/float dtype), or
            an :class:`~repro.events.aer.AERStream` addressing that shape
            (the sparse external-input path; decoded by one jitted op).
          events_capacity: when set, the output raster is ALSO returned as
            an AER stream of at most this many events under
            ``events_policy`` ("error" refuses a lossy encode, "drop"
            keeps the earliest events and flags overflow).
        Returns:
          {'spikes': (T, B, n_phys) int32 raster,
           'v_final': (B, n_phys) int32 membrane state after step T,
           'i_final': (B, n_phys) int32 synaptic current after step T
             (current-based engines only),
           'events': AERStream of 'spikes' (only with events_capacity)}.

        Exactness: every backend returns bit-identical rasters (the
        pallas-mxu 2^24 bound is enforced at engine build, so an engine
        that constructs cannot mis-accumulate), under any ``gate`` and
        any ``fuse_steps`` (the K-step fused window applies the same
        int32 accumulate + LIF epilogue per step inside the kernel).
        Static shapes: the whole scan is jitted once per engine and
        reused across calls; one XLA program serves every call of the
        same ``(T, B)`` shape (AER inputs decode through one jitted op at
        the stream's fixed capacity — no retrace per spike count).
        """
        from repro.events.aer import AERStream, aer_to_dense, dense_to_aer

        if isinstance(ext_spikes, AERStream):
            if ext_spikes.shape[2] != self.n_inputs:
                raise ValueError(
                    f"AER stream addresses {ext_spikes.shape[2]} sources; "
                    f"engine expects {self.n_inputs} inputs"
                )
            ext_spikes = aer_to_dense(ext_spikes)
        ext_spikes = jnp.asarray(ext_spikes).astype(jnp.int32)
        if ext_spikes.ndim != 3 or ext_spikes.shape[2] != self.n_inputs:
            raise ValueError(
                f"ext_spikes must be (T, B, {self.n_inputs}), "
                f"got {ext_spikes.shape}"
            )
        if self._run_jit is None:
            self._run_jit = jax.jit(self._run_impl)
        out = self._run_jit(self._scan_weights(), ext_spikes)
        if events_capacity is not None:
            out["events"] = dense_to_aer(
                out["spikes"], events_capacity, policy=events_policy)
        return out
