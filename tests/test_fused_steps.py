"""K-step fused timestep contracts: fusion is a SCHEDULE, never a result.

The load-bearing claims pinned here:

  * a ``fuse_steps=K`` engine is BYTE-identical to the unfused engine on
    ``run`` for every fused backend x reset mode x gate x K — including
    T not a multiple of K (the padded trailing window) — fast leg always
    runs, the full sweep rides the ``slow`` marker;
  * the masked ``step_chunk`` semantics survive fusion: inactive slots
    keep their carry bit-for-bit and report zero spikes, with carries
    chained across ragged chunks;
  * ``to_mesh`` / ``with_gate`` / ``with_fuse_steps`` carry K through
    re-hosting, and the mesh engine's outputs stay identical;
  * the MXU exactness gate stays closed under fusion and its rejection
    names the numbers that tripped it (max |w|, per-block fan-in, K);
  * the traffic accounting is CONSISTENT: the gate scalars the fused
    kernel DMAs by (``ops.ext_gate_activity``) count exactly the blocks
    the ``events.trace`` window-OR model counts, and per-step fused
    traffic at dense activity is exactly 1/K of the unfused kernel's.

The identity, masked-chunk and re-hosting legs run for both neuron models
(``neuron``): one-state LIF, and current-based LIF through the fused
kernel's two-state variant, whose synaptic current ``i`` rides the window
beside ``v``.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.engine import (BACKENDS, GATES, MXU_EXACT_BOUND, DecaySpec,
                               SpikeEngine, mxu_partial_sum_bound,
                               sources_raster)
from repro.distributed.spike_mesh import MeshSpikeEngine, make_spike_mesh
from repro.events import trace
from repro.kernels import ops

THRESH = 1 << 16
RESET_MODES = ("zero", "subtract", "hold")
FUSED_BACKENDS = tuple(b for b in BACKENDS if b != "reference")
NEURONS = ("lif", "cuba")


def _weights(rng, n_in, n_phys, density=0.3, wmax=1 << 13):
    S = n_in + n_phys
    W = ((rng.random((S, n_phys)) < density)
         * rng.integers(-wmax, wmax, (S, n_phys)))
    return jnp.asarray(W, jnp.int32)


def _raster(rng, T, B, S, density=0.3):
    return jnp.asarray(rng.random((T, B, S)) < density, jnp.int32)


def _engine(W, n_in, *, backend="reference", gate="batch-tile",
            reset="zero", K=1, neuron="lif"):
    syn = DecaySpec.shift(0.75) if neuron == "cuba" else None
    return SpikeEngine(W, n_in, decay=DecaySpec.shift(0.25),
                       threshold_raw=THRESH, reset_mode=reset,
                       backend=backend, gate=gate, fuse_steps=K,
                       syn_decay=syn)


def _assert_run_identical(a, b):
    assert a.keys() == b.keys()
    for k in set(a) - {"events"}:
        av, bv = np.asarray(a[k]), np.asarray(b[k])
        assert av.dtype == bv.dtype == np.int32
        np.testing.assert_array_equal(av, bv)


# --------------------------------------------------------------------------
# run identity: fused == unfused, fast leg + full slow sweep
# --------------------------------------------------------------------------

@pytest.mark.parametrize("neuron", NEURONS)
def test_fused_run_identity_fast(rng, neuron):
    """One combo per fused backend x gate at K=4, T ragged (10 = 2.5
    windows) — the always-on identity check."""
    W = _weights(rng, 37, 48)
    ext = _raster(rng, 10, 3, 37)
    want = _engine(W, 37, neuron=neuron).run(ext)
    for backend in FUSED_BACKENDS:
        for gate in GATES:
            got = _engine(W, 37, backend=backend, gate=gate, K=4,
                          neuron=neuron).run(ext)
            _assert_run_identical(want, got)


@pytest.mark.parametrize("neuron", NEURONS)
def test_fused_run_identity_k1_path(rng, neuron):
    """K=1 never routes through the fused kernel but must also match."""
    W = _weights(rng, 20, 40)
    ext = _raster(rng, 5, 2, 20)
    want = _engine(W, 20, neuron=neuron).run(ext)
    for backend in FUSED_BACKENDS:
        _assert_run_identical(want, _engine(W, 20, backend=backend, K=1,
                                            neuron=neuron).run(ext))


@pytest.mark.parametrize("neuron", NEURONS)
def test_fused_run_identity_edge_shapes(rng, neuron):
    """Window edges: T < K (one padded window), T == K, B=1, and a
    source axis wider than one 128-block."""
    cases = [
        # (T, B, n_in, n_phys, K)
        (2, 2, 30, 40, 4),     # T < K
        (4, 1, 30, 40, 4),     # T == K, single example
        (7, 3, 200, 130, 3),   # multi-block source axis, ragged T
    ]
    for T, B, n_in, n_phys, K in cases:
        W = _weights(rng, n_in, n_phys)
        ext = _raster(rng, T, B, n_in)
        want = _engine(W, n_in, neuron=neuron).run(ext)
        got = _engine(W, n_in, backend="pallas", K=K,
                      neuron=neuron).run(ext)
        _assert_run_identical(want, got)


@pytest.mark.slow
def test_fused_run_identity_full_sweep(rng):
    """Every fused backend x reset mode x gate x K, ragged T."""
    W = _weights(rng, 37, 48)
    ext = _raster(rng, 9, 3, 37)
    for reset in RESET_MODES:
        want = _engine(W, 37, reset=reset).run(ext)
        for backend in FUSED_BACKENDS:
            for gate in GATES:
                for K in (2, 3, 8):
                    got = _engine(W, 37, backend=backend, gate=gate,
                                  reset=reset, K=K).run(ext)
                    _assert_run_identical(want, got)


# --------------------------------------------------------------------------
# masked step_chunk: ragged remainders inside and across windows
# --------------------------------------------------------------------------

@pytest.mark.parametrize("neuron", NEURONS)
def test_fused_step_chunk_masked_identity(rng, neuron):
    """Chunks of 5 steps under K=4 (every window ragged or masked):
    active slots advance exactly as the reference chunk, inactive slots
    keep their carry bit-for-bit, chained across chunks."""
    W = _weights(rng, 30, 40)
    ref = _engine(W, 30, neuron=neuron)
    fused = _engine(W, 30, backend="pallas", K=4, neuron=neuron)
    c1, c2 = ref.init_carry(4), fused.init_carry(4)
    for _ in range(3):
        ext = _raster(rng, 5, 4, 30, 0.35)
        act = jnp.asarray(rng.random((5, 4)) < 0.5, jnp.int32)
        c1, s1 = ref.step_chunk(c1, ext, act)
        c2, s2 = fused.step_chunk(c2, ext, act)
        np.testing.assert_array_equal(np.asarray(s1), np.asarray(s2))
        assert c1.keys() == c2.keys() == set(ref.carry_keys)
        for k in c1:
            np.testing.assert_array_equal(np.asarray(c1[k]),
                                          np.asarray(c2[k]))


@pytest.mark.slow
def test_fused_step_chunk_masked_sweep(rng):
    W = _weights(rng, 30, 40)
    for backend in FUSED_BACKENDS:
        for gate in GATES:
            for reset in RESET_MODES:
                ref = _engine(W, 30, reset=reset)
                fused = _engine(W, 30, backend=backend, gate=gate,
                                reset=reset, K=3)
                c1, c2 = ref.init_carry(3), fused.init_carry(3)
                ext = _raster(rng, 4, 3, 30, 0.35)
                act = jnp.asarray(rng.random((4, 3)) < 0.5, jnp.int32)
                c1, s1 = ref.step_chunk(c1, ext, act)
                c2, s2 = fused.step_chunk(c2, ext, act)
                np.testing.assert_array_equal(np.asarray(s1),
                                              np.asarray(s2))
                for k in ("v", "spikes"):
                    np.testing.assert_array_equal(np.asarray(c1[k]),
                                                  np.asarray(c2[k]))


# --------------------------------------------------------------------------
# re-hosting carries K: with_gate / with_fuse_steps / to_mesh
# --------------------------------------------------------------------------

@pytest.mark.parametrize("neuron", NEURONS)
def test_with_fuse_steps_rehosting(rng, neuron):
    W = _weights(rng, 20, 40)
    e = _engine(W, 20, backend="pallas", gate="per-example", K=1,
                neuron=neuron)
    assert e.with_fuse_steps(1) is e
    e4 = e.with_fuse_steps(4)
    assert (e4.fuse_steps, e4.gate, e4.backend) == (4, "per-example",
                                                    "pallas")
    # and the other re-hosts preserve K, and the neuron model with it
    assert e4.with_gate("batch-tile").fuse_steps == 4
    assert e4.with_gate("per-example") is e4
    for hosted in (e4, e4.with_gate("batch-tile")):
        assert hosted.syn_decay == e.syn_decay
        assert hosted.carry_keys == e.carry_keys


def test_fuse_steps_validation(rng):
    W = _weights(rng, 10, 20)
    with pytest.raises(ValueError, match="fuse_steps"):
        _engine(W, 10, K=0)
    with pytest.raises(ValueError, match="fuse_steps"):
        mxu_partial_sum_bound(np.asarray(W), fuse_steps=0)


@pytest.mark.parametrize("neuron", NEURONS)
def test_mesh_engine_carries_fuse_steps(rng, neuron):
    """1x1 mesh (always available): to_mesh / with_gate / with_fuse_steps
    keep K, and the sharded fused run stays byte-identical."""
    mesh = make_spike_mesh(neuron=1, batch=1)
    W = _weights(rng, 30, 40)
    ext = _raster(rng, 6, 3, 30)
    want = _engine(W, 30, neuron=neuron).run(ext)
    fused = _engine(W, 30, backend="pallas", K=4, neuron=neuron)
    sharded = fused.to_mesh(mesh)
    assert isinstance(sharded, MeshSpikeEngine)
    assert sharded.fuse_steps == 4
    assert sharded.with_gate("per-example").fuse_steps == 4
    assert sharded.with_fuse_steps(2).fuse_steps == 2
    assert isinstance(sharded.with_fuse_steps(2), MeshSpikeEngine)
    _assert_run_identical(want, sharded.run(ext))


# --------------------------------------------------------------------------
# MXU exactness gate under fusion
# --------------------------------------------------------------------------

def test_mxu_bound_k_invariant(rng):
    W = np.asarray(_weights(rng, 37, 48))
    for K in (1, 2, 8):
        assert mxu_partial_sum_bound(W, fuse_steps=K) == \
            mxu_partial_sum_bound(W)


def test_mxu_rejection_message_names_the_numbers():
    """The compile-time rejection must name max |w|, the per-block
    fan-in, and K — the three numbers a user needs to fix their image."""
    # a full 128-row block of 2^17 weights: partial sum 2^24, at the bound
    n_in, n_phys = 100, 128
    W = np.full((n_in + n_phys, n_phys), 1 << 17, np.int32)
    assert mxu_partial_sum_bound(W) >= MXU_EXACT_BOUND
    with pytest.raises(ValueError) as ei:
        _engine(jnp.asarray(W), n_in, backend="pallas-mxu", K=4)
    msg = str(ei.value)
    assert f"max |w| = {1 << 17}" in msg
    assert "fan-in 128" in msg
    assert "fuse_steps K = 4" in msg
    assert "K-invariant" in msg


# --------------------------------------------------------------------------
# traffic accounting: kernel gate scalars == trace window-OR model
# --------------------------------------------------------------------------

def test_ext_gate_activity_matches_trace_counts(rng):
    """The DMAs the fused kernel schedules (nonzero gate scalars) equal
    the trace model's window-OR gated block count, for every K."""
    ext = np.asarray(_raster(rng, 10, 5, 300, 0.05))
    for K in (1, 2, 4):
        for tile in (8, 1):
            kernel = int((np.asarray(
                ops.ext_gate_activity(ext, block_batch=tile,
                                      fuse_steps=K)) > 0).sum())
            touched, _ = trace.block_traffic(ext, fuse_steps=K,
                                             tile_batch=tile)
            assert kernel == touched, (K, tile)


def test_fused_traffic_is_one_over_k_at_dense_activity(rng):
    """At full activity the gate never skips, so the fused per-step
    traffic ratio is exactly 1/K (the weight-reuse claim, isolated)."""
    T, B, n_in, n_phys = 8, 4, 256, 128
    sources = np.ones((T, B, n_in + n_phys), np.int32)
    for K in (1, 2, 4, 8):
        touched, total = trace.fused_block_traffic(sources, n_in,
                                                   fuse_steps=K)
        assert touched * K == total


def test_fused_traffic_counted_from_real_run(rng):
    """End to end on a real run: fused traffic from the engine's actual
    rasters shrinks monotonically with K and the ext leg cross-checks
    against the kernel-side counter."""
    W = _weights(rng, 200, 130)
    engine = _engine(W, 200)
    ext = _raster(rng, 12, 4, 200, 0.1)
    out = engine.run(ext)["spikes"]
    sources = np.asarray(sources_raster(ext, out))
    ratios = []
    for K in (1, 2, 4):
        touched, total = trace.fused_block_traffic(sources, 200,
                                                   fuse_steps=K)
        ratios.append(touched / total)
        kernel = int((np.asarray(
            ops.ext_gate_activity(ext, fuse_steps=K)) > 0).sum())
        assert kernel == trace.block_traffic(np.asarray(ext),
                                             fuse_steps=K)[0]
    assert ratios[0] > ratios[1] > ratios[2]
