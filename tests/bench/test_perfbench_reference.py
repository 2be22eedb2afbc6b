"""The plain fixed-point reference against the program at a small size on
the CPU, and its bf16 control, which has to fail the comparison."""

import json
import pathlib
import sys

import numpy as np
import pytest

REPO = pathlib.Path(__file__).resolve().parents[2]
for p in (str(REPO), str(REPO / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import deploy, reference  # noqa: E402
from bench.stimuli import digits  # noqa: E402


def _config(name):
    return json.loads((REPO / "bench/configs" / f"{name}.json").read_text())


def _program_raster(net, config, ext):
    """The program's own engine over ``ext`` (B, T, n_in), reference
    backend, logical neurons only."""
    import jax.numpy as jnp

    from repro.core import cerebra_h
    from repro.core.fixedpoint import FixedPointFormat
    from repro.core.lif import LIFParams
    from repro.core.mapping import ClusterGeometry
    from repro.core.network import SNNetwork
    from repro.core.session import AcceleratorSession

    fmt = FixedPointFormat(**config["fixed_point"])
    sess = AcceleratorSession(config=cerebra_h.CerebraHConfig(
        geometry=ClusterGeometry(**config["hardware"]["geometry"]), fmt=fmt))
    model = sess.deploy("m", SNNetwork(
        n_inputs=net.n_inputs, n_neurons=net.n_neurons, weights=net.weights,
        params=LIFParams(decay_rate=net.decay_rate, threshold=net.threshold,
                         reset_mode=net.reset, fmt=fmt),
        output_slice=net.output_slice))
    out = cerebra_h.make_engine(model.program).run(
        jnp.asarray(np.swapaxes(ext, 0, 1), jnp.int32))
    raster = np.swapaxes(np.asarray(out["spikes"]), 0, 1)
    assert not raster[:, :, net.n_neurons:].any()
    return raster[:, :, :net.n_neurons]


MNIST = "snapv-mnist-784-256-10"


@pytest.mark.parametrize("seed", [2**31 + 11, 17])
def test_reference_matches_the_program(seed):
    config = _config(MNIST)
    net = deploy.network(REPO, config, seed=seed)
    ext = digits.pool(5, 6, 40, net.n_inputs)
    want = _program_raster(net, config, ext)
    got = reference.Reference(net, config).run(ext)
    assert want.sum() > 0
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("seed", [2**31 + 1, 7, 123456789, 2**31 + 2**20,
                                  99, 31337])
def test_bf16_control_fails_the_comparison(seed):
    config = _config(MNIST)
    net = deploy.network(REPO, config, seed=seed)
    ext = digits.pool(seed % 1000, 6, 40, net.n_inputs)
    exact = reference.Reference(net, config)
    served = exact.run(ext)
    checks = [reference.Check(ext=e, served=s) for e, s in zip(ext, served)]
    assert reference.mismatches(exact, checks)["mismatched_spikes"] == 0
    control = reference.Reference(net, config, precision="bf16")
    assert reference.mismatches(control, checks)["mismatched_spikes"] > 0


def test_mismatches_counts_outside_model_and_length():
    config = _config(MNIST)
    net = deploy.network(REPO, config, seed=1)
    ref = reference.Reference(net, config)
    ext = digits.pool(1, 1, 50, net.n_inputs)
    want = ref.run(ext)[0]
    served = np.zeros((50, 384), np.int32)
    served[:, :net.n_neurons] = want
    ok = reference.Check(ext=ext[0], served=served)
    assert reference.mismatches(ref, [ok]) == {"mismatched_spikes": 0,
                                               "unanswered": 0}
    outside = served.copy()
    outside[3, net.n_neurons + 2] = 1
    short = served[:45]
    res = reference.mismatches(ref, [
        reference.Check(ext=ext[0], served=outside),
        reference.Check(ext=ext[0], served=short),
        reference.Check(ext=ext[0], served=None)])
    assert res == {"mismatched_spikes": 1 + 5 * net.n_neurons,
                   "unanswered": 1}


@pytest.mark.parametrize("seed", [2**31 + 5, 2**40 + 1, 3])
def test_weights_and_inputs_follow_the_seed(seed):
    """One seed gives one network and one stimulus pool; the next seed
    gives others. Seeds past 32 bits are taken whole."""
    config = _config("snapv-mnist-784-256-10")
    net = deploy.network(REPO, config, seed)
    np.testing.assert_array_equal(
        net.weights, deploy.network(REPO, config, seed).weights)
    assert not np.array_equal(
        net.weights, deploy.network(REPO, config, seed + 1).weights)
    stim = digits.pool(seed, 4, 16, net.n_inputs)
    np.testing.assert_array_equal(stim,
                                  digits.pool(seed, 4, 16, net.n_inputs))
    assert not np.array_equal(stim,
                              digits.pool(seed + 1, 4, 16, net.n_inputs))


def test_quantize_rounds_half_even_and_saturates():
    q = reference.quantize(np.array([0.5 / 65536, 1.5 / 65536, -1e9, 1e9]),
                           15, 16)
    assert q.tolist() == [0, 2, -(1 << 31), (1 << 31) - 1]
    assert reference.hardware_decay(0.1, [0.125, 0.25, 0.5, 0.75]) == 0.125
