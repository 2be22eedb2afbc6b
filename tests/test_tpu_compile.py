"""Every kernel configuration of the served path compiles for a TPU v5e.

Interpret mode (every other test's CPU path) lowers a Pallas kernel to
plain XLA ops and accepts what Mosaic refuses: slices along lanes at a
dynamic offset, blocks below the (8, 128) tile, more VMEM than a core
has. These tests compile the real kernels for a described ``v5e:2x2``
topology at the repo's 1024-neuron width — B = 8 slots, S = 784 + 1024
sources, P = 1024 neurons — with shapes only (nothing runs), and check
that each Pallas program holds the Mosaic call (``tpu_custom_call``).

The topology is described inside a module fixture, never at import: only
one process may load the TPU library at a time, and a worker that loads
it while collecting would give the workers different tests.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.engine import DecaySpec, SpikeEngine
from repro.kernels import ops

B, N_IN, P = 8, 784, 1024
S = N_IN + P
K = 8
THRESH = 1 << 16


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means no TPU lib
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back without one:
    # keep these out of the persistent cache
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _shape(sharding, shape):
    return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=sharding)


KERNELS = [(mxu, gate_tile) for mxu in (False, True) for gate_tile in (8, 1)]
KERNEL_IDS = [f"{'pallas-mxu' if m else 'pallas'}-"
              f"{'batch-tile' if t == 8 else 'per-example'}"
              for m, t in KERNELS]


@pytest.mark.parametrize("use_mxu,block_batch", KERNELS, ids=KERNEL_IDS)
def test_spike_timestep_compiles_for_v5e(one_chip, use_mxu, block_batch):
    """K = 1: the single-step event-gated kernel."""
    def step(src, w, v):
        return ops.spike_timestep(
            src, w, v, decay_rate=0.125, threshold_raw=THRESH,
            use_mxu=use_mxu, block_batch=block_batch, interpret=False)

    compiled = jax.jit(step).lower(
        _shape(one_chip, (B, S)), _shape(one_chip, (S, P)),
        _shape(one_chip, (B, P))).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("use_mxu,block_batch", KERNELS, ids=KERNEL_IDS)
def test_spike_timestep_fused_compiles_for_v5e(one_chip, use_mxu,
                                               block_batch):
    """K = 8: the fused window with its VMEM-resident recurrent image."""
    def window(ext, spk, w, v, active):
        return ops.spike_timestep_fused(
            ext, spk, w, v, active, n_inputs=N_IN, decay_rate=0.125,
            threshold_raw=THRESH, use_mxu=use_mxu, block_batch=block_batch,
            interpret=False)

    compiled = jax.jit(window).lower(
        _shape(one_chip, (K, B, N_IN)), _shape(one_chip, (B, P)),
        _shape(one_chip, (S, P)), _shape(one_chip, (B, P)),
        _shape(one_chip, (K, B))).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_reference_chunk_step_compiles_for_v5e(one_chip):
    """The ``reference`` backend's masked chunk step (the served step of
    a session without kernels)."""
    engine = SpikeEngine(np.zeros((S, P), np.int32), N_IN,
                         decay=DecaySpec.shift(0.125), threshold_raw=THRESH,
                         reset_mode="zero", backend="reference")
    carry = {"v": _shape(one_chip, (B, P)),
             "spikes": _shape(one_chip, (B, P))}
    compiled = jax.jit(engine._chunk_impl).lower(
        _shape(one_chip, (S, P)), carry, _shape(one_chip, (K, B, N_IN)),
        _shape(one_chip, (K, B))).compile()
    assert compiled.as_text()
