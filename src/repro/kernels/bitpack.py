"""u32-lane bitpacked spike rasters — move bits, not bytes.

The paper's spike packets carry single-bit events; our dense rasters spend
an int32 per possible spike. This module is the packed wire format the
kernel-side datapath uses instead: 32 sources per uint32 lane, so a
1024-source axis is 32 lanes (128 bytes per example-step instead of 4 KiB).

Lane layout (the contract ARCHITECTURE.md documents and the event gate
and AER decode depend on): source ``s`` lives in lane ``s // 32`` at bit
``s % 32``, little-endian within the lane::

    packed[..., l] = sum_{i=0}^{31} (dense[..., 32*l + i] != 0) << i

Sources past the true count (the ragged tail of the last lane) are always
zero — :func:`pack_spikes` zero-pads before packing, so popcounts over
packed lanes equal dense spike counts exactly. All ops are static-shape
and jitted; ``unpack_spikes(pack_spikes(x), x.shape[-1])`` is the identity
on {0,1} rasters (any nonzero packs to 1).

Activity reduction is ``jax.lax.population_count`` on the lanes: the
per-(example, source-block) gate scalars of :mod:`repro.kernels.ops` and
the AER ``total`` bookkeeping become popcounts over 4 lanes per 128-source
block instead of 128-element integer sums.

The fused kernel's own input is a second layout, :func:`pack_block_planes`:
a kernel cannot pick a block's 4 lanes out of a row at a dynamic block id,
so there each bit position of a 128-lane word is one source block instead.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

__all__ = [
    "LANE_BITS",
    "block_activity",
    "count_spikes",
    "pack_block_planes",
    "packed_lanes",
    "pack_spikes",
    "unpack_spikes",
]

LANE_BITS = 32  # sources per uint32 lane


def packed_lanes(n_sources: int) -> int:
    """Lanes needed for ``n_sources`` (ceil; 0 sources pack to 0 lanes)."""
    return -(-int(n_sources) // LANE_BITS)


@jax.jit
def pack_spikes(dense):
    """Pack a dense ``(..., S)`` raster into ``(..., ceil(S/32))`` uint32.

    Any nonzero packs to a set bit (rasters here are {0,1} already); the
    ragged tail of the last lane is zero-filled, so lane popcounts equal
    dense spike counts.
    """
    dense = jnp.asarray(dense)
    S = dense.shape[-1]
    L = packed_lanes(S)
    bits = (dense != 0).astype(jnp.uint32)
    pad = L * LANE_BITS - S
    if pad:
        shape = list(bits.shape)
        shape[-1] = pad
        bits = jnp.concatenate([bits, jnp.zeros(shape, jnp.uint32)], axis=-1)
    lanes = bits.reshape(*bits.shape[:-1], L, LANE_BITS)
    weights = (jnp.uint32(1) << jnp.arange(LANE_BITS, dtype=jnp.uint32))
    return (lanes * weights).sum(axis=-1).astype(jnp.uint32)


@functools.partial(jax.jit, static_argnames=("n_sources",))
def unpack_spikes(packed, n_sources: int):
    """Unpack ``(..., L)`` uint32 lanes to a ``(..., n_sources)`` {0,1}
    int32 raster. Exact inverse of :func:`pack_spikes` on binary rasters."""
    packed = jnp.asarray(packed, jnp.uint32)
    L = packed.shape[-1]
    if L < packed_lanes(n_sources):
        raise ValueError(
            f"{L} lanes hold {L * LANE_BITS} sources; {n_sources} requested"
        )
    shifts = jnp.arange(LANE_BITS, dtype=jnp.uint32)
    bits = (packed[..., None] >> shifts) & jnp.uint32(1)
    dense = bits.reshape(*packed.shape[:-1], L * LANE_BITS)
    return dense[..., :n_sources].astype(jnp.int32)


@jax.jit
def count_spikes(packed):
    """Spike count per leading index: popcount summed over the lane axis.

    ``count_spikes(pack_spikes(x)) == (x != 0).sum(-1)`` — the packed
    replacement for dense activity sums. Returns int32 of shape
    ``packed.shape[:-1]``.
    """
    packed = jnp.asarray(packed, jnp.uint32)
    counts = jax.lax.population_count(packed).astype(jnp.int32)
    return counts.sum(axis=-1).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("block_src",))
def pack_block_planes(dense, block_src: int):
    """Pack ``(..., S)`` into ``(G, ..., block_src)`` int32 block-plane words.

    The kernel-side layout of the fused timestep's external raster: bit
    ``j`` of word ``[g, ..., l]`` is source ``(32*g + j) * block_src + l``
    (``G = ceil(S / block_src / 32)``), so one word per lane position
    covers 32 source blocks and block ``blk`` comes back dense with one
    shift and mask, ``(words[blk // 32] >> blk % 32) & 1`` — no movement
    across lanes. ``S`` must be a multiple of ``block_src``; bit 31 is the
    int32 sign bit, which the ``& 1`` after an arithmetic shift discards.
    """
    dense = jnp.asarray(dense)
    lead, S = dense.shape[:-1], dense.shape[-1]
    if S % block_src:
        raise ValueError(
            f"{S} sources do not tile into {block_src}-source blocks"
        )
    n_blocks = S // block_src
    groups = max(1, -(-n_blocks // LANE_BITS))
    bits = (dense != 0).astype(jnp.uint32).reshape(*lead, n_blocks,
                                                   block_src)
    pad = groups * LANE_BITS - n_blocks
    if pad:
        bits = jnp.concatenate(
            [bits, jnp.zeros((*lead, pad, block_src), jnp.uint32)], axis=-2)
    bits = bits.reshape(*lead, groups, LANE_BITS, block_src)
    shifts = jnp.arange(LANE_BITS, dtype=jnp.uint32)[:, None]
    words = (bits << shifts).sum(axis=-2, dtype=jnp.uint32)
    return jax.lax.bitcast_convert_type(jnp.moveaxis(words, -2, 0),
                                        jnp.int32)


@functools.partial(jax.jit, static_argnames=("block_src",))
def block_activity(packed, block_src: int):
    """Per-source-block spike counts: ``(..., L) -> (..., L*32/block_src)``.

    The event gate's activity scalars, computed on packed lanes: block
    ``j`` covers sources ``[j*block_src, (j+1)*block_src)`` — exactly
    ``block_src // 32`` whole lanes, popcounted. ``block_src`` must be a
    multiple of the 32-bit lane width (the kernels' 128-source blocks are
    4 lanes).
    """
    if block_src % LANE_BITS:
        raise ValueError(
            f"block_src must be a multiple of {LANE_BITS}, got {block_src}"
        )
    packed = jnp.asarray(packed, jnp.uint32)
    L = packed.shape[-1]
    lanes_per_block = block_src // LANE_BITS
    if L % lanes_per_block:
        raise ValueError(
            f"{L} lanes do not tile into {lanes_per_block}-lane blocks"
        )
    counts = jax.lax.population_count(packed).astype(jnp.int32)
    blocks = counts.reshape(*packed.shape[:-1], L // lanes_per_block,
                            lanes_per_block)
    return blocks.sum(axis=-1).astype(jnp.int32)
