"""Bitpacked spike raster contracts (``repro.kernels.bitpack``).

The packed form is a WIRE FORMAT other subsystems build on — the fused
kernel's external raster, the event gate's activity scalars, and the AER
decode all assume the exact lane layout — so the claims pinned here are:

  * ``unpack_spikes(pack_spikes(x), S)`` is the identity on {0,1} rasters
    for ANY source count (ragged last lane, zero sources, all-zero and
    all-one lanes) — hypothesis property + deterministic companions;
  * the lane layout is exactly ``source s -> lane s // 32, bit s % 32``
    (little-endian in the lane) — pinned against hand-built words so a
    refactor cannot silently flip endianness;
  * popcount reductions (``count_spikes``, ``block_activity``) equal the
    dense sums they replace;
  * the AER event path scatters into the same layout:
    ``aer_to_packed == pack_spikes(dense)`` for any stream.
"""

import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import HealthCheck, given, settings
from hypothesis import strategies as st

from repro.events.aer import aer_to_packed, dense_to_aer
from repro.kernels import bitpack


def _raster(rng, shape, density=0.3):
    return (rng.random(shape) < density).astype(np.int32)


# --------------------------------------------------------------------------
# round trip: property test + deterministic companions
# --------------------------------------------------------------------------

@settings(max_examples=60, deadline=None,
          suppress_health_check=[HealthCheck.too_slow])
@given(B=st.integers(1, 4), S=st.integers(1, 130),
       density=st.floats(0.0, 1.0), seed=st.integers(0, 2**16))
@pytest.mark.slow
def test_pack_round_trip_property(B, S, density, seed):
    """pack -> unpack is the identity for ANY ragged source count and
    activity level (0.0 and 1.0 included: all-zero / all-one lanes)."""
    rng = np.random.default_rng(seed)
    dense = _raster(rng, (B, S), density)
    packed = bitpack.pack_spikes(dense)
    assert packed.shape == (B, bitpack.packed_lanes(S))
    assert packed.dtype == jnp.uint32
    np.testing.assert_array_equal(
        np.asarray(bitpack.unpack_spikes(packed, S)), dense)


def test_pack_round_trip_deterministic(rng):
    """The same identity on fixed corner cases (always runs)."""
    cases = [
        np.zeros((2, 5), np.int32),            # ragged, silent
        np.ones((2, 64), np.int32),            # exact lanes, saturated
        np.ones((3, 33), np.int32),            # one bit into the 2nd lane
        np.zeros((1, 32), np.int32),           # one all-zero lane
        _raster(rng, (4, 127), 0.5),           # ragged last lane
        _raster(rng, (2, 3, 40), 0.2),         # leading batch dims
    ]
    for dense in cases:
        packed = bitpack.pack_spikes(dense)
        np.testing.assert_array_equal(
            np.asarray(bitpack.unpack_spikes(packed, dense.shape[-1])),
            dense)


def test_pack_binarizes_and_pads():
    """Any nonzero packs to a set bit; the ragged tail is zero-filled so
    popcounts equal dense counts."""
    dense = np.array([[0, 3, -1, 0, 7]], np.int32)  # S=5, one lane
    packed = np.asarray(bitpack.pack_spikes(dense))
    assert packed.shape == (1, 1)
    assert packed[0, 0] == (1 << 1) | (1 << 2) | (1 << 4)
    assert int(bitpack.count_spikes(bitpack.pack_spikes(dense))[0]) == 3


def test_zero_sources():
    dense = np.zeros((3, 0), np.int32)
    packed = bitpack.pack_spikes(dense)
    assert packed.shape == (3, 0)
    assert np.asarray(bitpack.unpack_spikes(packed, 0)).shape == (3, 0)
    assert bitpack.packed_lanes(0) == 0


# --------------------------------------------------------------------------
# lane layout: the contract, pinned bit by bit
# --------------------------------------------------------------------------

def test_lane_layout_pinned():
    """source s -> lane s // 32, bit s % 32, little-endian in the lane."""
    S = 80  # 3 lanes, ragged last
    for s in (0, 1, 31, 32, 63, 64, 79):
        dense = np.zeros((1, S), np.int32)
        dense[0, s] = 1
        packed = np.asarray(bitpack.pack_spikes(dense))
        expected = np.zeros(3, np.uint32)
        expected[s // 32] = np.uint32(1) << (s % 32)
        np.testing.assert_array_equal(packed[0], expected)


def test_unpack_validates_lane_count():
    packed = jnp.zeros((2, 2), jnp.uint32)
    with pytest.raises(ValueError, match="lanes"):
        bitpack.unpack_spikes(packed, 65)  # needs 3 lanes
    # exactly enough lanes, and fewer sources than capacity, both fine
    assert bitpack.unpack_spikes(packed, 64).shape == (2, 64)
    assert bitpack.unpack_spikes(packed, 40).shape == (2, 40)


# --------------------------------------------------------------------------
# popcount reductions == the dense sums they replace
# --------------------------------------------------------------------------

def test_count_spikes_matches_dense_sum(rng):
    for S in (7, 32, 100, 256):
        dense = _raster(rng, (3, 5, S), 0.4)
        counts = np.asarray(bitpack.count_spikes(bitpack.pack_spikes(dense)))
        np.testing.assert_array_equal(counts, dense.sum(axis=-1))


def test_block_activity_matches_dense_block_sums(rng):
    B, S, block = 6, 256, 128
    dense = _raster(rng, (B, S), 0.1)
    act = np.asarray(
        bitpack.block_activity(bitpack.pack_spikes(dense), block))
    assert act.shape == (B, S // block)
    np.testing.assert_array_equal(
        act, dense.reshape(B, S // block, block).sum(axis=-1))


@pytest.mark.parametrize("n_blocks", [1, 7, 32, 33])
def test_block_planes_layout(rng, n_blocks):
    """Bit j of plane word [g, ..., l] is source (32g + j)*block + l: one
    shift and mask recovers any block (what the fused kernel does)."""
    block = 128
    dense = _raster(rng, (2, 3, n_blocks * block), 0.3)
    planes = np.asarray(bitpack.pack_block_planes(dense, block))
    assert planes.dtype == np.int32
    assert planes.shape == (-(-n_blocks // 32), 2, 3, block)
    for blk in range(n_blocks):
        got = (planes[blk // 32] >> (blk % 32)) & 1
        np.testing.assert_array_equal(
            got, dense[..., blk * block:(blk + 1) * block])
    with pytest.raises(ValueError, match="tile"):
        bitpack.pack_block_planes(dense[..., :100], block)


def test_block_activity_validation():
    packed = jnp.zeros((2, 4), jnp.uint32)
    with pytest.raises(ValueError, match="multiple"):
        bitpack.block_activity(packed, 48)   # not a lane multiple
    with pytest.raises(ValueError, match="tile"):
        bitpack.block_activity(packed, 96)   # 4 lanes / 3-lane blocks


# --------------------------------------------------------------------------
# the AER event path lands on the same layout
# --------------------------------------------------------------------------

@settings(max_examples=40, deadline=None,
          suppress_health_check=[HealthCheck.too_slow])
@given(T=st.integers(1, 4), B=st.integers(1, 3), S=st.integers(1, 70),
       density=st.floats(0.0, 0.6), seed=st.integers(0, 2**16))
@pytest.mark.slow
def test_aer_to_packed_matches_pack_property(T, B, S, density, seed):
    """Scattering events as bits == packing the dense raster."""
    rng = np.random.default_rng(seed)
    dense = _raster(rng, (T, B, S), density)
    stream = dense_to_aer(dense, int(dense.sum()) + 2)
    np.testing.assert_array_equal(
        np.asarray(aer_to_packed(stream)),
        np.asarray(bitpack.pack_spikes(dense)))


def test_aer_to_packed_matches_pack_deterministic(rng):
    cases = [
        np.zeros((2, 2, 9), np.int32),
        np.ones((2, 1, 33), np.int32),
        _raster(rng, (4, 3, 100), 0.2),
    ]
    for dense in cases:
        stream = dense_to_aer(dense, int(dense.sum()))
        np.testing.assert_array_equal(
            np.asarray(aer_to_packed(stream)),
            np.asarray(bitpack.pack_spikes(dense)))
        assert int(bitpack.count_spikes(
            aer_to_packed(stream)).sum()) == int(stream.count)
