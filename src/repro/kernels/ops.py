"""Public jitted wrappers around the Pallas kernels.

Responsibilities: shape padding to hardware-aligned blocks, activity-bitmap
computation for the event gate, platform dispatch (interpret=True on CPU so
the kernel bodies are validated everywhere; compiled Mosaic on TPU), and
un-padding of results. These are the functions the rest of the framework
calls; nothing else should touch pallas_call directly.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import bitpack
from repro.kernels import lif_step as _lif
from repro.kernels import poisson_encode as _enc
from repro.kernels import spike_timestep as _ts

__all__ = [
    "lif_step",
    "spike_timestep",
    "spike_timestep_fused",
    "ext_gate_activity",
    "poisson_encode",
    "on_cpu",
]


def on_cpu() -> bool:
    return jax.default_backend() == "cpu"


def _pad_to(x, axis: int, multiple: int, value=0):
    size = x.shape[axis]
    rem = (-size) % multiple
    if rem == 0:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, rem)
    return jnp.pad(x, pad, constant_values=value)


# --------------------------------------------------------------------------
@functools.partial(
    jax.jit,
    static_argnames=("decay_rate", "threshold_raw", "reset_mode",
                     "interpret"),
)
def lif_step(v, syn, *, decay_rate: float, threshold_raw: int,
             reset_mode: str = "zero", interpret: bool | None = None):
    """Fused LIF update. v, syn: (B, N) int32 -> (v_out, spikes)."""
    interpret = on_cpu() if interpret is None else interpret
    B, N = v.shape
    vp = _pad_to(_pad_to(v, 0, 8), 1, 128)
    sp = _pad_to(_pad_to(syn, 0, 8), 1, 128)
    rows, cols = vp.shape
    fn = _lif.build_lif_step(
        (rows, cols),
        decay_rate=decay_rate,
        threshold_raw=threshold_raw,
        reset_mode=reset_mode,
        block_rows=min(256, rows),
        block_cols=min(1024, cols),
        interpret=interpret,
    )
    v_out, spikes = fn(vp, sp)
    return v_out[:B, :N], spikes[:B, :N]


# --------------------------------------------------------------------------
@functools.partial(
    jax.jit,
    static_argnames=("decay_rate", "threshold_raw", "reset_mode",
                     "decay_kind", "decay_raw",
                     "use_mxu", "block_batch", "block_src", "interpret"),
)
def spike_timestep(sources, weights, v, *, decay_rate: float = 0.0,
                   threshold_raw: int, reset_mode: str = "zero",
                   decay_kind: str = "shift", decay_raw: int = 0,
                   use_mxu: bool = False, block_batch: int = 8,
                   block_src: int = 128, interpret: bool | None = None):
    """One fused, event-gated accelerator timestep.

    sources: (B, S) int/bool spikes; weights: (S, P) int32 raw Q16.16;
    v: (B, P) int32. Returns (v_out, spikes_out), each (B, P) int32.

    ``decay_kind='shift'`` (default) applies the Cerebra-H shift decay of
    ``decay_rate``; ``decay_kind='mul'`` applies the Cerebra-S fixed-point
    multiply by the raw Q16.16 retain factor ``decay_raw``.

    ``use_mxu=False`` (default) is bit-exact. ``use_mxu=True`` runs the
    accumulate on the MXU in f32 — exact only while per-output partial sums
    stay below 2^24 (fine for |w| <~ 1.0 Q16.16 and fan-in <= 256; the SNN
    trainer's weight clip guarantees it). The SpikeEngine enforces this
    bound from weight stats before selecting the mode.
    """
    interpret = on_cpu() if interpret is None else interpret
    B, S = sources.shape
    P = weights.shape[1]
    sources = sources.astype(jnp.int32)
    src_p = _pad_to(_pad_to(sources, 0, block_batch), 1, block_src)
    w_p = _pad_to(_pad_to(weights, 0, block_src), 1, 128)
    v_p = _pad_to(_pad_to(v, 0, block_batch), 1, 128)
    Bp, Sp = src_p.shape
    Pp = w_p.shape[1]
    nb, ns = Bp // block_batch, Sp // block_src
    # Per-(example, source-block) activity scalars — the Incoming
    # Forwarder's event ledger, popcounted over bitpacked lanes (4 u32
    # lanes per 128-source block instead of a 128-wide integer sum). The
    # kernel gate consumes one scalar per (batch tile, source block): with
    # block_batch == 1 (the per-example gate, SpikeEngine
    # gate="per-example") the tile map IS the per-example map and every
    # silent (example, block) pair skips its weight fetch; larger tiles OR
    # their examples' rows together.
    if block_src % bitpack.LANE_BITS == 0:
        per_example = bitpack.block_activity(
            bitpack.pack_spikes(src_p), block_src
        )  # (Bp, ns)
    else:  # non-lane-aligned block (never the kernels' default 128)
        per_example = (
            src_p.reshape(Bp, ns, block_src).sum(axis=2).astype(jnp.int32)
        )
    activity = per_example.reshape(nb, block_batch, ns).sum(axis=1)
    fn = _ts.build_spike_timestep(
        Bp, Sp, Pp,
        decay_rate=decay_rate,
        threshold_raw=threshold_raw,
        reset_mode=reset_mode,
        decay_kind=decay_kind,
        decay_raw=decay_raw,
        block_batch=block_batch,
        block_src=block_src,
        use_mxu=use_mxu,
        interpret=interpret,
    )
    v_out, spikes = fn(activity, src_p, w_p, v_p)
    return v_out[:B, :P], spikes[:B, :P]


# --------------------------------------------------------------------------
def _fused_pad(ext, spikes_prev, weights, v, active, *, n_inputs,
               block_batch, block_src):
    """Pad every fused-kernel operand to its block multiples.

    Returns the padded operands plus the original (B, P) for un-padding.
    The weight image splits at ``n_inputs``: external rows pad to
    ``block_src`` multiples (the DMA'd blocks), recurrent rows/columns and
    the carries pad together to the 128/block_src-aligned physical axis so
    feedback stays square.
    """
    K, B, _ = ext.shape
    P = weights.shape[1]
    w_ext = weights[:n_inputs]
    w_rec = weights[n_inputs:]
    ext_p = _pad_to(_pad_to(ext.astype(jnp.int32), 1, block_batch),
                    2, block_src)
    v_p = _pad_to(_pad_to(v, 0, block_batch), 1, 128)
    v_p = _pad_to(v_p, 1, block_src)
    spk_p = _pad_to(_pad_to(spikes_prev, 0, block_batch), 1, 128)
    spk_p = _pad_to(spk_p, 1, block_src)
    act_p = _pad_to(active.astype(jnp.int32), 1, block_batch)
    Pp = v_p.shape[1]
    w_ext_p = _pad_to(_pad_to(w_ext, 0, block_src), 1, 128)
    w_ext_p = _pad_to(w_ext_p, 1, block_src)
    # recurrent rows and columns pad together to (Pp, Pp) with zeros —
    # pad neurons have no fan-in and no fan-out, so feedback stays square
    w_rec_p = jnp.zeros((Pp, Pp), jnp.int32).at[:P, :P].set(w_rec)
    if ext_p.shape[2] == 0:  # n_inputs == 0: keep one silent block
        ext_p = jnp.zeros((K, ext_p.shape[1], block_src), jnp.int32)
        w_ext_p = jnp.zeros((block_src, Pp), jnp.int32)
    return ext_p, spk_p, w_ext_p, w_rec_p, v_p, act_p, B, P


@functools.partial(
    jax.jit,
    static_argnames=("n_inputs", "decay_rate", "threshold_raw",
                     "reset_mode", "decay_kind", "decay_raw",
                     "use_mxu", "block_batch", "block_src", "interpret"),
)
def spike_timestep_fused(ext, spikes_prev, weights, v, active, *,
                         n_inputs: int, decay_rate: float = 0.0,
                         threshold_raw: int, reset_mode: str = "zero",
                         decay_kind: str = "shift", decay_raw: int = 0,
                         use_mxu: bool = False, block_batch: int = 8,
                         block_src: int = 128,
                         interpret: bool | None = None):
    """K fused, event-gated accelerator timesteps in ONE kernel call.

    ext: (K, B, n_inputs) external spikes for the whole window;
    spikes_prev, v: (B, P) carries at window entry; weights: (S, P) int32
    raw Q16.16 with S = n_inputs + P; active: (K, B) advance mask.
    Returns ``(v_out, spikes_carry, raster)`` with raster (K, B, P).

    Byte-identical to K chained :func:`spike_timestep` calls under the
    masked-slot contract (inactive (step, example) pairs keep their carry
    and emit zero spikes). External spikes travel bitpacked (one bit per
    source block, see :func:`repro.kernels.bitpack.pack_block_planes`);
    each active external weight block is DMA'd ONCE for the whole window
    behind the accumulate, and the recurrent image is fetched once per
    window and applied per step — per-step weight traffic ~1/K of the
    single-step kernel. The ``use_mxu`` 2^24 exactness bound is unchanged
    by K (the window stacks along the dot's batch axis, never its
    reduction axis); see :func:`repro.core.engine.mxu_partial_sum_bound`.
    """
    interpret = on_cpu() if interpret is None else interpret
    K = ext.shape[0]
    (ext_p, spk_p, w_ext_p, w_rec_p, v_p, act_p, B, P) = _fused_pad(
        ext, spikes_prev, weights, v, active,
        n_inputs=n_inputs, block_batch=block_batch, block_src=block_src)
    Bp, Pp = v_p.shape
    nb = Bp // block_batch
    ns_ext = ext_p.shape[2] // block_src
    packed = bitpack.pack_spikes(ext_p)  # (K, Bp, lanes)
    # window-OR gate scalars: a block is fetched iff ANY step of the
    # window spikes on it for this batch tile (popcounts are counts, so
    # summing over steps and tile rows preserves "nonzero iff any").
    per_example = bitpack.block_activity(packed, block_src)  # (K, Bp, ns)
    activity = (per_example.sum(axis=0)
                .reshape(nb, block_batch, ns_ext).sum(axis=1))
    fn = _ts.build_spike_timestep_fused(
        Bp, ns_ext * block_src, Pp, K,
        decay_rate=decay_rate,
        threshold_raw=threshold_raw,
        reset_mode=reset_mode,
        decay_kind=decay_kind,
        decay_raw=decay_raw,
        block_batch=block_batch,
        block_src=block_src,
        use_mxu=use_mxu,
        interpret=interpret,
    )
    v_out, spk_carry, raster = fn(
        activity, ext_p, w_ext_p, w_rec_p, v_p, spk_p, act_p)
    return v_out[:B, :P], spk_carry[:B, :P], raster[:, :B, :P]


def ext_gate_activity(ext, *, block_batch: int = 8, block_src: int = 128,
                      fuse_steps: int = 1):
    """The external gate scalars the fused datapath acts on (host view).

    ext: (T, B, n_inputs) external raster. Returns an int32 array of shape
    ``(ceil(T / fuse_steps), B // block_batch (ceil), n_ext_blocks)``:
    window-OR spike counts per (window, batch tile, external source
    block), computed through the SAME bitpack/popcount pipeline the
    kernel wrapper uses. ``(activity > 0).sum()`` is therefore the exact
    number of external weight-block DMAs the fused kernel issues — the
    counter BENCH_pr6.json cross-checks against the
    :func:`repro.events.trace.block_traffic` model.
    """
    ext = jnp.asarray(ext).astype(jnp.int32)
    T, B, _ = ext.shape
    K = int(fuse_steps)
    pad_t = (-T) % K
    if pad_t:
        ext = jnp.pad(ext, ((0, pad_t), (0, 0), (0, 0)))
    ext_p = _pad_to(_pad_to(ext, 1, block_batch), 2, block_src)
    Tp, Bp, Sp = ext_p.shape
    if Sp == 0:
        return jnp.zeros((Tp // K, Bp // block_batch, 0), jnp.int32)
    packed = bitpack.pack_spikes(ext_p)
    per_example = bitpack.block_activity(packed, block_src)  # (Tp, Bp, ns)
    ns = per_example.shape[2]
    windows = per_example.reshape(Tp // K, K, Bp, ns).sum(axis=1)
    return (windows.reshape(Tp // K, Bp // block_batch, block_batch, ns)
            .sum(axis=2).astype(jnp.int32))


# --------------------------------------------------------------------------
@functools.partial(
    jax.jit, static_argnames=("num_steps", "block_batch", "interpret")
)
def poisson_encode(seed, intensities, num_steps: int, *,
                   block_batch: int = 8, interpret: bool | None = None):
    """Hardware rate encoder. intensities: (B, D) f32 -> (T, B, D) i32."""
    interpret = on_cpu() if interpret is None else interpret
    B, D = intensities.shape
    x = _pad_to(_pad_to(intensities.astype(jnp.float32), 0, block_batch),
                1, 128)
    Bp, Dp = x.shape
    fn = _enc.build_poisson_encode(
        Bp, Dp, num_steps, block_batch=block_batch, interpret=interpret
    )
    seed_arr = jnp.asarray([seed], jnp.int32).reshape(1)
    out = fn(seed_arr, x)
    return out[:, :B, :D]
