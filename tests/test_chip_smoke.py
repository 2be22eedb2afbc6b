"""``chip_smoke.py`` and the compile-cache helper, on the CPU.

The smoke script's phases run here at a tiny width (128 neurons, short
rasters) with the Pallas kernels interpreted, so a wrong path, argument or
comparison fails in the suite and not on the chip. Its ``main`` must refuse
a machine without a TPU: non-zero exit, ``"ok": false`` on the last line.
"""

import importlib.util
import json
import pathlib

import jax
import numpy as np
import pytest

from repro.core.cerebra_h import CerebraHConfig
from repro.core.mapping import ClusterGeometry
from repro.launch import compile_cache

ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def tiny(smoke):
    """784-16-10 on a 4-cluster (128-neuron) array."""
    config = CerebraHConfig(geometry=ClusterGeometry(n_clusters=4))
    return smoke.build_net(0, hidden=16), config


def test_digit_stimulus_is_seeded_spikes(smoke):
    a = smoke.digit_stimulus(3, 2, steps=5)
    assert a.shape == (5, 2, 784) and a.dtype == np.int32
    assert set(np.unique(a)) <= {0, 1} and a.any()
    np.testing.assert_array_equal(a, smoke.digit_stimulus(3, 2, steps=5))


def test_engine_parity_phase_tiny(smoke, tiny):
    net, config = tiny
    out = smoke.engine_parity(net, smoke.digit_stimulus(0, 3, steps=10),
                              config=config, require_kernel=False)
    assert out["checks"] == (len(smoke.KERNEL_BACKENDS) * len(smoke.GATES)
                             * len(smoke.FUSE_STEPS))


def test_served_path_phase_tiny(smoke, tiny):
    net, config = tiny
    stim = smoke.digit_stimulus(1, 5, steps=11)
    out = smoke.served_path(net, stim, config=config, require_kernel=False,
                            n_slots=2, chunk_steps=4, fuse_steps=2)
    assert out["retired"] == 5
    assert out["steps"] == 5 * 11


def test_main_refuses_a_machine_without_tpu(smoke, capsys, monkeypatch,
                                            tmp_path):
    # an externally placed cache keeps main() from re-pointing this
    # process's cache at the checkout
    monkeypatch.setenv(compile_cache.CACHE_ENV, str(tmp_path))
    assert jax.devices()[0].platform != "tpu"
    assert smoke.main([]) != 0
    last = capsys.readouterr().out.strip().splitlines()[-1]
    verdict = json.loads(last)
    assert verdict["ok"] is False and "device" not in verdict


def test_cache_helper_leaves_a_placed_cache_to_jax(monkeypatch, tmp_path):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(compile_cache.CACHE_ENV, str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_cache_helper_default_is_one_fixed_checkout_path(monkeypatch):
    from jax.experimental.compilation_cache import compilation_cache

    monkeypatch.delenv(compile_cache.CACHE_ENV, raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        first = compile_cache.enable_compile_cache()
        assert compile_cache.enable_compile_cache() == first
        assert first == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == first
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
        compilation_cache.reset_cache()
