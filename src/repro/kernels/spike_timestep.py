"""Pallas TPU kernel: fused, cluster-gated Cerebra-H timestep.

This is the paper's core mechanism re-architected for TPU (DESIGN.md §2):

  ASIC                              TPU kernel
  ----                              ----------
  per-group weight SRAM row fetch   VMEM weight block (Sb x P), streamed
  incoming-forwarder event gating   @pl.when on a prefetched per-(batch-
                                    tile, source-block) activity scalar —
                                    silent source blocks are SKIPPED
  accumulator unit (32-wide row)    exact int32 accumulate on the MXU: the
                                    {0,1} sources against the image's four
                                    byte planes (each exact in bf16), or
                                    one f32 dot in high-throughput mode
  PDU + potential adder             fused shift-decay LIF epilogue on the
                                    final source block

Grid: (batch_tiles, source_tiles); source innermost so the int32
accumulator scratch completes before the LIF epilogue fires. The physical
neuron axis P (default 1024 = 8x128) stays whole inside a block — the
entire neuron array is one VPU tile set, mirroring "all clusters step in
parallel".

The event gate is the load-bearing adaptation: like Cerebra-H's resolver
only fetching rows for spiking sources, the kernel skips both the compute
and the DMA of weight blocks whose source block carries no spike in this
batch tile. The compute skip is the ``@pl.when``; the DMA skip is the
weight index map, which points a silent grid step at the block the
previous step already holds, so the pipeline issues no copy for it.
Sparse SNN activity (the paper's workloads are <10% active) turns directly
into skipped HBM traffic.

Layout: every batch-indexed operand is viewed as ``(batch_tiles, Bb,
...)``, so a block's last two dimensions are ``(Bb, lanes)`` and equal the
array's own for ANY tile height. The per-example gate (Bb = 1) and the
batch-tile gate (Bb = 8) therefore satisfy the same Mosaic tiling rule
(last two block dims divisible by (8, 128) or equal to the array's).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.bitpack import LANE_BITS, pack_block_planes
from repro.kernels.epilogue import decay_and_fire, validate_decay

__all__ = [
    "spike_timestep_kernel",
    "build_spike_timestep",
    "spike_timestep_syn_kernel",
    "spike_timestep_fused_kernel",
    "spike_timestep_fused_syn_kernel",
    "build_spike_timestep_fused",
]

_PLANE_BITS = 8  # bf16 holds every integer in [-256, 256] exactly
_N_PLANES = 32 // _PLANE_BITS


def _exact_dot(src, w, use_mxu: bool):
    """``(R, n) {0,1} int32 @ (n, P) int32 -> (R, P) int32``, exact.

    ``use_mxu=True``: one f32 dot at fp32 contract precision — exact while
    every per-column partial sum stays below 2^24, the bound the engine
    checks at build time for one ``block_src`` reduction (so callers
    reduce over one block at a time and add blocks in int32).

    ``use_mxu=False``: the weights split into four byte planes
    (``w = sum_i plane_i << 8i``; low planes in [0, 255], the top one in
    [-128, 127]), each exact in bf16. A plane's partial sums are at most
    ``n * 255 < 2^24`` and the planes recombine by wrapping int32 shifts
    and adds — the same modular arithmetic as the reference backend's
    int32 dot, for ANY int32 image.
    """
    if use_mxu:
        return jax.lax.dot(
            src.astype(jnp.float32), w.astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32,
        ).astype(jnp.int32)
    s = src.astype(jnp.float32).astype(jnp.bfloat16)
    acc = None
    for i in range(_N_PLANES):
        plane = w >> (_PLANE_BITS * i)
        if i < _N_PLANES - 1:
            plane = plane & ((1 << _PLANE_BITS) - 1)
        part = jax.lax.dot(
            s, plane.astype(jnp.float32).astype(jnp.bfloat16),
            preferred_element_type=jnp.float32,
        ).astype(jnp.int32)
        if i:
            part = part << (_PLANE_BITS * i)
        acc = part if acc is None else acc + part
    return acc


def _timestep_body(act_ref, src_ref, w_ref, v_ref, i_ref, vout_ref,
                   spk_ref, iout_ref, acc_ref, *, decay_kind: str,
                   decay_rate: float, decay_raw: int, threshold_raw: int,
                   reset_mode: str, use_mxu: bool, syn_decay):
    """The single-step body; ``i_ref``/``iout_ref`` are the synaptic
    current's carry (None for the one-state neuron)."""
    b = pl.program_id(0)
    s = pl.program_id(1)
    ns = pl.num_programs(1)

    @pl.when(s == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(act_ref[b, s] > 0)  # event gate: skip silent source blocks
    def _accumulate():
        acc_ref[...] += _exact_dot(src_ref[0], w_ref[...], use_mxu)

    @pl.when(s == ns - 1)  # LIF epilogue once accumulation is complete
    def _fire():
        out = decay_and_fire(
            v_ref[0], acc_ref[...],
            decay_kind=decay_kind, decay_rate=decay_rate,
            decay_raw=decay_raw, threshold_raw=threshold_raw,
            reset_mode=reset_mode,
            i=None if i_ref is None else i_ref[0], syn_decay=syn_decay,
        )
        vout_ref[0] = out[0]
        spk_ref[0] = out[1]
        if iout_ref is not None:
            iout_ref[0] = out[2]


def spike_timestep_kernel(
    act_ref,      # scalar-prefetch: (nb, ns) int32 block activity
    fetch_ref,    # scalar-prefetch: (nb, ns) int32 weight block to hold
    src_ref,      # (1, Bb, Sb) int32 spikes
    w_ref,        # (Sb, P) int32 SRAM image block
    v_ref,        # (1, Bb, P) int32 membrane potential
    vout_ref,     # (1, Bb, P) int32
    spk_ref,      # (1, Bb, P) int32
    acc_ref,      # scratch (Bb, P) int32
    **params,
):
    del fetch_ref  # consumed by the weight index map only
    _timestep_body(act_ref, src_ref, w_ref, v_ref, None, vout_ref, spk_ref,
                   None, acc_ref, syn_decay=None, **params)


def spike_timestep_syn_kernel(
    act_ref, fetch_ref, src_ref, w_ref, v_ref,
    i_ref,        # (1, Bb, P) int32 synaptic current
    vout_ref, spk_ref,
    iout_ref,     # (1, Bb, P) int32
    acc_ref, *, syn_decay, **params,
):
    """:func:`spike_timestep_kernel` for the current-based neuron."""
    del fetch_ref
    _timestep_body(act_ref, src_ref, w_ref, v_ref, i_ref, vout_ref, spk_ref,
                   iout_ref, acc_ref, syn_decay=syn_decay, **params)


def build_spike_timestep(
    batch: int,
    n_sources: int,
    n_phys: int,
    *,
    decay_rate: float = 0.0,
    threshold_raw: int,
    reset_mode: str,
    decay_kind: str = "shift",
    decay_raw: int = 0,
    block_batch: int = 8,
    block_src: int = 128,
    use_mxu: bool = False,
    interpret: bool = False,
    syn_decay: tuple | None = None,
):
    """Build fn(activity, sources, weights, v) -> (v_out, spikes).

    ``decay_kind='shift'`` uses the Cerebra-H shift decay (``decay_rate``);
    ``decay_kind='mul'`` uses the Cerebra-S fixed-point multiply by the raw
    Q16.16 retain factor ``decay_raw``.

    ``syn_decay`` (a ``(kind, rate, raw)`` triple) builds the
    current-based neuron's variant instead, named
    ``spike_timestep_syn``: fn(activity, sources, weights, v, i)
    -> (v_out, spikes, i_out).

    Shapes (pre-padded by ops.py):
      activity: (batch//block_batch, n_sources//block_src) int32
      sources:  (batch, n_sources) int32 {0,1}
      weights:  (n_sources, n_phys) int32
      v:        (batch, n_phys) int32
    """
    validate_decay(decay_kind, decay_rate, decay_raw)
    syn = syn_decay is not None
    if syn:
        validate_decay(*syn_decay)
    if batch % block_batch or n_sources % block_src:
        raise ValueError("shapes must be pre-padded to block multiples")
    if n_phys % 128:
        raise ValueError("n_phys must be a multiple of 128 (VPU lanes)")
    nb = batch // block_batch
    ns = n_sources // block_src
    kernel = functools.partial(
        spike_timestep_syn_kernel if syn else spike_timestep_kernel,
        **({"syn_decay": syn_decay} if syn else {}),
        decay_kind=decay_kind,
        decay_rate=decay_rate,
        decay_raw=decay_raw,
        threshold_raw=threshold_raw,
        reset_mode=reset_mode,
        use_mxu=use_mxu,
    )
    tile = (1, block_batch, n_phys)
    tile_spec = pl.BlockSpec(tile, lambda b, s, act, fetch: (b, 0, 0))
    n_carry = 2 if syn else 1  # v, and the current's carry
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(nb, ns),
        in_specs=[
            pl.BlockSpec((1, block_batch, block_src),
                         lambda b, s, act, fetch: (b, 0, s)),
            pl.BlockSpec((block_src, n_phys),
                         lambda b, s, act, fetch: (fetch[b, s], 0)),
        ] + [tile_spec] * n_carry,
        out_specs=[tile_spec] * (n_carry + 1),
        scratch_shapes=[pltpu.VMEM((block_batch, n_phys), jnp.int32)],
    )
    out = jax.ShapeDtypeStruct((nb, block_batch, n_phys), jnp.int32)
    call = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[out] * (n_carry + 1),
        interpret=interpret,
        name="spike_timestep_syn" if syn else "spike_timestep",
    )

    def fn(activity, sources, weights, v, *i):
        outs = call(
            activity, _fetch_blocks(activity),
            sources.reshape(nb, block_batch, n_sources), weights,
            *(x.reshape(nb, block_batch, n_phys) for x in (v, *i)))
        return tuple(x.reshape(batch, n_phys) for x in outs)

    return fn


def _fetch_blocks(activity):
    """The weight block each grid step holds: its own when active, else
    the one the previous step (row-major over (tile, block)) already
    fetched. An unchanged block index is what makes the Pallas pipeline
    skip the copy, so a silent block costs no weight DMA."""
    nb, ns = activity.shape
    flat = jnp.arange(nb * ns, dtype=jnp.int32)
    last = jax.lax.cummax(jnp.where(activity.reshape(-1) > 0, flat, -1))
    return jnp.where(last >= 0, last % ns, 0).reshape(nb, ns)


# ==========================================================================
# K-step fused variant: bitpacked sources + double-buffered gated weight DMA
# ==========================================================================
#
# Recurrent feedback splits the weight image's fusion behaviour in two.
# Spikes of step t feed step t+1, so the RECURRENT rows (W_rec, the last
# n_phys rows) cannot be gated ahead of time — but they CAN be fetched once
# per K-step window and kept VMEM-resident while an in-kernel loop applies
# them per step. The EXTERNAL rows (W_ext, the first n_inputs rows) face
# known inputs for all K steps, so their gate scalars are ORed over the
# window and each active block is fetched ONCE for all K steps. Both halves
# therefore move ~1/K of the per-step weight traffic of the single-step
# kernel (events/trace.py's fused model counts exactly this).
#
# The external fetch is a MANUAL double-buffered DMA: the weight image
# stays in HBM (memory_space=ANY), the kernel compacts the active block
# ids into an SMEM schedule, then ping-pongs two VMEM slots — start the
# copy of block i+1, wait on block i, accumulate. Silent blocks never
# appear in the schedule, so they skip the DMA itself, not just the
# compute.
#
# External spikes arrive BITPACKED as block-plane words
# (repro.kernels.bitpack.pack_block_planes): bit j of word [g, ..., l] is
# source (32g + j) * block_src + l, so one int32 per lane position covers
# 32 source blocks and block ``blk``'s dense {0,1} rows come back with one
# shift and mask — no lane movement, which Mosaic cannot lower for a
# dynamic block id. Exactness: the int32 accumulator and the shared LIF
# epilogue run PER STEP inside the kernel, and inactive (step, example)
# slots keep their carry bit-for-bit and emit zero spikes — the same
# contract as SpikeEngine._masked_chunk_scan, which is what makes K-aligned
# chunking with a masked remainder byte-identical to K single steps.


def spike_timestep_fused_kernel(
    act_ref,      # scalar-prefetch: (nb, ns_ext) window-OR ext activity
    ext_ref,      # (G, 1, K*Bb, block_src) int32 block-plane words
    wext_ref,     # (n_ext, P) int32 — HBM (ANY); manually DMA'd per block
    wrec_ref,     # (P, P) int32 recurrent image, VMEM-resident per window
    v_ref,        # (1, Bb, P) int32 membrane potential at window entry
    spk0_ref,     # (1, Bb, P) int32 boundary spikes at window entry
    active_ref,   # (1, K, Bb, 1) int32 per-(step, example) advance mask
    vout_ref,     # (1, Bb, P) int32 membrane potential at window exit
    spkc_ref,     # (1, Bb, P) int32 boundary spikes at window exit
    rast_ref,     # (K, 1, Bb, P) int32 emitted spike raster
    wbuf,         # scratch VMEM (2, block_src, P) int32 — DMA ping-pong
    acc_ref,      # scratch VMEM (K, Bb, P) int32 external accumulator
    sched_ref,    # scratch SMEM (ns_ext,) int32 active-block schedule
    sem,          # DMA semaphores (2,)
    **params,
):
    _fused_body(act_ref, ext_ref, wext_ref, wrec_ref, v_ref, spk0_ref,
                active_ref, None, vout_ref, spkc_ref, rast_ref, None, wbuf,
                acc_ref, sched_ref, sem, syn_decay=None, **params)


def spike_timestep_fused_syn_kernel(
    act_ref, ext_ref, wext_ref, wrec_ref, v_ref, spk0_ref, active_ref,
    i_ref,        # (1, Bb, P) int32 synaptic current at window entry
    vout_ref, spkc_ref, rast_ref,
    iout_ref,     # (1, Bb, P) int32 synaptic current at window exit
    wbuf, acc_ref, sched_ref, sem, *, syn_decay, **params,
):
    """:func:`spike_timestep_fused_kernel` for the current-based neuron:
    the current is one more carry, held across the K steps as ``v`` is."""
    _fused_body(act_ref, ext_ref, wext_ref, wrec_ref, v_ref, spk0_ref,
                active_ref, i_ref, vout_ref, spkc_ref, rast_ref, iout_ref,
                wbuf, acc_ref, sched_ref, sem, syn_decay=syn_decay,
                **params)


def _fused_body(
    act_ref, ext_ref, wext_ref, wrec_ref, v_ref, spk0_ref, active_ref,
    i_ref, vout_ref, spkc_ref, rast_ref, iout_ref, wbuf, acc_ref,
    sched_ref, sem,
    *,
    fuse_steps: int,
    block_src: int,
    decay_kind: str,
    decay_rate: float,
    decay_raw: int,
    threshold_raw: int,
    reset_mode: str,
    use_mxu: bool,
    syn_decay,
):
    b = pl.program_id(0)
    K = fuse_steps
    Bb = v_ref.shape[1]
    P = v_ref.shape[2]
    ns_ext = act_ref.shape[1]

    acc_ref[...] = jnp.zeros_like(acc_ref)

    # ---- phase A: compact active external block ids into the schedule.
    # A block is scheduled iff ANY of the K steps spikes on it for this
    # batch tile (the window-OR the activity scalars carry).
    def _collect(s, n):
        @pl.when(act_ref[b, s] > 0)
        def _():
            sched_ref[n] = s

        return n + jnp.where(act_ref[b, s] > 0, 1, 0)

    n_active = jax.lax.fori_loop(0, ns_ext, _collect, jnp.int32(0))

    # ---- phase B: double-buffered gated DMA + K-batched accumulate.
    # Scheduled block i streams HBM -> wbuf[i % 2] while block i-1 is being
    # accumulated; unscheduled (silent) blocks are never copied at all.
    def _dma(i, slot):
        blk = sched_ref[i]
        return pltpu.make_async_copy(
            wext_ref.at[pl.ds(blk * block_src, block_src)],
            wbuf.at[slot],
            sem.at[slot],
        )

    @pl.when(n_active > 0)
    def _warmup():
        _dma(jnp.int32(0), jnp.int32(0)).start()

    def _consume(i, _):
        slot = jax.lax.rem(i, 2)

        @pl.when(i + 1 < n_active)
        def _prefetch():
            _dma(i + 1, 1 - slot).start()

        _dma(i, slot).wait()
        blk = sched_ref[i]
        # all K steps' rows for the tile, (K*Bb, block_src), step-major
        words = ext_ref[blk // LANE_BITS, 0]
        src = (words >> (blk % LANE_BITS)) & 1
        # K stacks along the BATCH axis of the dot, so each partial sum
        # still reduces over one block_src block — the single-step bound.
        part = _exact_dot(src, wbuf[slot], use_mxu)
        for k in range(K):
            acc_ref[k] += part[k * Bb:(k + 1) * Bb]
        return 0

    jax.lax.fori_loop(0, n_active, _consume, 0)

    # ---- phase C: K per-step recurrences + LIF epilogues on the resident
    # recurrent image. vout/spkc (and iout) double as the in-flight carry
    # registers.
    vout_ref[...] = v_ref[...]
    spkc_ref[...] = spk0_ref[...]
    if iout_ref is not None:
        iout_ref[...] = i_ref[...]

    def _step(k, _):
        spk_prev = spkc_ref[0]
        syn = acc_ref[k]
        # recurrent accumulate, chunked at block_src rows so each dot
        # reduces over the same span as the single-step kernel (identical
        # partial-sum bound); inter-chunk accumulation is exact int32.
        for c in range(0, P, block_src):
            syn = syn + _exact_dot(
                spk_prev[:, c:c + block_src],
                wrec_ref[c:c + block_src, :], use_mxu)
        out = decay_and_fire(
            vout_ref[0], syn,
            decay_kind=decay_kind, decay_rate=decay_rate,
            decay_raw=decay_raw, threshold_raw=threshold_raw,
            reset_mode=reset_mode,
            i=None if iout_ref is None else iout_ref[0], syn_decay=syn_decay,
        )
        v_new, s_new = out[0], out[1]
        # masked-slot contract (== SpikeEngine._masked_chunk_scan): an
        # inactive (step, example) keeps its carry and emits zero spikes.
        keep = active_ref[0, k] != 0  # (Bb, 1)
        vout_ref[0] = jnp.where(keep, v_new, vout_ref[0])
        if iout_ref is not None:
            iout_ref[0] = jnp.where(keep, out[2], iout_ref[0])
        rast_ref[k, 0] = jnp.where(keep, s_new, 0)
        spkc_ref[0] = jnp.where(keep, s_new, spk_prev)
        return 0

    jax.lax.fori_loop(0, K, _step, 0)


def build_spike_timestep_fused(
    batch: int,
    n_ext: int,
    n_phys: int,
    fuse_steps: int,
    *,
    decay_rate: float = 0.0,
    threshold_raw: int,
    reset_mode: str,
    decay_kind: str = "shift",
    decay_raw: int = 0,
    block_batch: int = 8,
    block_src: int = 128,
    use_mxu: bool = False,
    interpret: bool = False,
    syn_decay: tuple | None = None,
):
    """Build the K-step fused timestep:
    ``fn(activity, ext, w_ext, w_rec, v, spikes_prev, active)
    -> (v_out, spikes_carry, raster)``.

    Shapes (pre-padded by ops.py):
      activity:   (batch//block_batch, n_ext//block_src) int32, window-OR
      ext:        (fuse_steps, batch, n_ext) int32 {0,1} external spikes,
                  packed here into block-plane words for the kernel
      w_ext:      (n_ext, n_phys) int32 — external SRAM rows (HBM-resident)
      w_rec:      (n_phys, n_phys) int32 — recurrent SRAM rows
      v, spikes_prev: (batch, n_phys) int32 carries at window entry
      active:     (fuse_steps, batch) int32 per-(step, example) mask
    Returns v/spikes carries at window exit plus the
    (fuse_steps, batch, n_phys) emitted raster.

    ``syn_decay`` (a ``(kind, rate, raw)`` triple) builds the
    current-based neuron's variant, named ``spike_timestep_fused_syn``:
    the synaptic current ``i`` (batch, n_phys) is one more carry, operand
    after ``active`` and output after the raster.
    """
    validate_decay(decay_kind, decay_rate, decay_raw)
    syn = syn_decay is not None
    if syn:
        validate_decay(*syn_decay)
    if fuse_steps < 1:
        raise ValueError(f"fuse_steps must be >= 1, got {fuse_steps}")
    if batch % block_batch or n_ext % block_src:
        raise ValueError("shapes must be pre-padded to block multiples")
    if n_phys % 128 or n_phys % block_src:
        raise ValueError(
            "n_phys must be a multiple of 128 and of block_src "
            "(the recurrent accumulate chunks at block_src rows)"
        )
    nb = batch // block_batch
    ns_ext = n_ext // block_src
    groups = -(-ns_ext // LANE_BITS)
    kernel = functools.partial(
        spike_timestep_fused_syn_kernel if syn
        else spike_timestep_fused_kernel,
        **({"syn_decay": syn_decay} if syn else {}),
        fuse_steps=fuse_steps,
        block_src=block_src,
        decay_kind=decay_kind,
        decay_rate=decay_rate,
        decay_raw=decay_raw,
        threshold_raw=threshold_raw,
        reset_mode=reset_mode,
        use_mxu=use_mxu,
    )
    tile = (1, block_batch, n_phys)
    # the current's carry, in and out, tiled as v is
    syn_specs = ([pl.BlockSpec(tile, lambda b, act: (b, 0, 0))]
                 if syn else [])
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((groups, 1, fuse_steps * block_batch, block_src),
                         lambda b, act: (0, b, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),  # w_ext stays in HBM
            # one resident copy: the block never changes across the grid
            pl.BlockSpec((n_phys, n_phys), lambda b, act: (0, 0),
                         pipeline_mode=pl.Buffered(1)),
            pl.BlockSpec(tile, lambda b, act: (b, 0, 0)),
            pl.BlockSpec(tile, lambda b, act: (b, 0, 0)),
            pl.BlockSpec((1, fuse_steps, block_batch, 1),
                         lambda b, act: (b, 0, 0, 0)),
        ] + syn_specs,
        out_specs=[
            pl.BlockSpec(tile, lambda b, act: (b, 0, 0)),
            pl.BlockSpec(tile, lambda b, act: (b, 0, 0)),
            pl.BlockSpec((fuse_steps, 1, block_batch, n_phys),
                         lambda b, act: (0, b, 0, 0)),
        ] + syn_specs,
        scratch_shapes=[
            pltpu.VMEM((2, block_src, n_phys), jnp.int32),
            pltpu.VMEM((fuse_steps, block_batch, n_phys), jnp.int32),
            pltpu.SMEM((max(ns_ext, 1),), jnp.int32),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    carry = jax.ShapeDtypeStruct((nb, block_batch, n_phys), jnp.int32)
    call = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            carry,
            carry,
            jax.ShapeDtypeStruct((fuse_steps, nb, block_batch, n_phys),
                                 jnp.int32),
        ] + [carry] * len(syn_specs),
        interpret=interpret,
        name="spike_timestep_fused_syn" if syn else "spike_timestep_fused",
    )
    tiled = (nb, block_batch, n_phys)

    def fn(activity, ext, w_ext, w_rec, v, spikes_prev, active, *i):
        # (G, K, batch, Sb) words -> per tile, rows step-major
        planes = (pack_block_planes(ext, block_src)
                  .reshape(groups, fuse_steps, nb, block_batch, block_src)
                  .transpose(0, 2, 1, 3, 4)
                  .reshape(groups, nb, fuse_steps * block_batch, block_src))
        act = (active.reshape(fuse_steps, nb, block_batch)
               .transpose(1, 0, 2)[..., None])
        v_out, spk_carry, raster, *i_out = call(
            activity, planes, w_ext, w_rec, v.reshape(tiled),
            spikes_prev.reshape(tiled), act,
            *(x.reshape(tiled) for x in i))
        return (v_out.reshape(batch, n_phys),
                spk_carry.reshape(batch, n_phys),
                raster.reshape(fuse_steps, batch, n_phys),
                *(x.reshape(batch, n_phys) for x in i_out))

    return fn
