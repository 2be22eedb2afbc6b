"""The plain fixed-point reference against the program at a small size on
the CPU, and its bf16 control, which has to fail the comparison: on the
MNIST net's spikes, and on the pid controller's potentials, where its
spikes survive."""

import json
import pathlib
import sys

import numpy as np
import pytest

REPO = pathlib.Path(__file__).resolve().parents[2]
for p in (str(REPO), str(REPO / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import deploy, load, reference  # noqa: E402
from bench.stimuli import digits  # noqa: E402


def _config(name):
    return json.loads((REPO / "bench/configs" / f"{name}.json").read_text())


def _program_run(net, config, ext):
    """The program's own engine over ``ext`` (B, T, n_in), reference
    backend, logical neurons only: (raster (B, T, N), potentials (B, N))."""
    import jax.numpy as jnp

    from repro.core import cerebra_h
    from repro.core.fixedpoint import FixedPointFormat
    from repro.core.mapping import ClusterGeometry
    from repro.core.network import SNNetwork
    from repro.core.session import AcceleratorSession

    fmt = FixedPointFormat(**config["fixed_point"])
    sess = AcceleratorSession(config=cerebra_h.CerebraHConfig(
        geometry=ClusterGeometry(**config["hardware"]["geometry"]), fmt=fmt))
    model = sess.deploy("m", SNNetwork(
        n_inputs=net.n_inputs, n_neurons=net.n_neurons, weights=net.weights,
        params=reference.neuron_module(REPO, net).program_params(
            net.neuron, fmt),
        output_slice=net.output_slice))
    out = cerebra_h.make_engine(model.program).run(
        jnp.asarray(np.swapaxes(ext, 0, 1), jnp.int32))
    raster = np.swapaxes(np.asarray(out["spikes"]), 0, 1)
    v = np.asarray(out["v_final"])
    assert not raster[:, :, net.n_neurons:].any()
    assert not v[:, net.n_neurons:].any()
    return raster[:, :, :net.n_neurons], v[:, :net.n_neurons]


def _pid_inputs(seed, B, T):
    """(B, T, 2) Poisson error spikes, each stream at rates of its own
    that change every 24 steps, as the fleet's encoder makes them."""
    r = np.random.default_rng(seed)
    rate = np.repeat(r.uniform(0, 1, (B, -(-T // 24), 2)), 24, axis=1)[:, :T]
    rate[np.arange(B), :, r.integers(0, 2, B)] = 0   # one side silent
    return (r.random((B, T, 2)) < rate).astype(np.int32)


MNIST = "snapv-mnist-784-256-10"
PID = "snapv-pid-36"


@pytest.mark.parametrize("seed", [2**31 + 11, 17])
def test_reference_matches_the_program(seed):
    config = _config(MNIST)
    net = deploy.network(REPO, config, seed=seed)
    ext = digits.pool(5, 6, 40, net.n_inputs)
    want, want_v = _program_run(net, config, ext)
    got, got_v = reference.model(REPO, net, config).run(ext)
    assert want.sum() > 0
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(got_v, want_v)


@pytest.mark.parametrize("seed", [2**31 + 13, 5])
def test_pid_reference_matches_the_program(seed):
    """Subtract reset, inhibition and the membrane state after the last
    step agree with the program's own engine."""
    config = _config(PID)
    net = deploy.network(REPO, config, seed=seed)
    ext = _pid_inputs(seed, 6, 120)
    want, want_v = _program_run(net, config, ext)
    got, got_v = reference.model(REPO, net, config).run(ext)
    assert want.sum() > 0 and (want_v < 0).any()
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(got_v, want_v)


@pytest.mark.parametrize("seed", [2**31 + 1, 7, 123456789, 2**31 + 2**20,
                                  99, 31337])
def test_bf16_control_fails_the_comparison(seed):
    config = _config(MNIST)
    net = deploy.network(REPO, config, seed=seed)
    ext = digits.pool(seed % 1000, 6, 40, net.n_inputs)
    exact = reference.model(REPO, net, config)
    served, _ = exact.run(ext)
    checks = [reference.Check(ext=e, served=s) for e, s in zip(ext, served)]
    compare = config["checks"]
    assert reference.mismatches(exact, checks, compare) == {
        "mismatched_spikes": 0, "unanswered": 0}
    control = reference.model(REPO, net, config, precision="bf16")
    assert reference.mismatches(control, checks,
                                compare)["mismatched_spikes"] > 0


@pytest.mark.parametrize("seed", [2**31 + 3, 41, 2**33 + 9])
def test_bf16_control_keeps_pid_spikes_and_fails_potentials(seed):
    """The pid's weights round to bf16 by under 0.2%: its spikes come out
    exact, its membrane potentials do not, so only the potentials check
    catches the control."""
    config = _config(PID)
    net = deploy.network(REPO, config, seed=seed)
    ext = _pid_inputs(seed, 8, 480)
    exact = reference.model(REPO, net, config)
    control = reference.model(REPO, net, config, precision="bf16")
    spikes, v = exact.run(ext)
    c_spikes, c_v = control.run(ext)
    np.testing.assert_array_equal(c_spikes, spikes)
    checks = [reference.Check(ext=e, served=s, potentials=p)
              for e, s, p in zip(ext, spikes, v)]
    assert reference.mismatches(exact, checks, config["checks"]) == {
        "mismatched_spikes": 0, "mismatched_potentials": 0, "unanswered": 0}
    res = reference.mismatches(control, checks, config["checks"])
    assert res["mismatched_spikes"] == 0
    assert res["mismatched_potentials"] > 0


def test_mismatches_counts_outside_model_and_length():
    config = _config(MNIST)
    net = deploy.network(REPO, config, seed=1)
    ref = reference.model(REPO, net, config)
    ext = digits.pool(1, 1, 50, net.n_inputs)
    want = ref.run(ext)[0][0]
    served = np.zeros((50, 384), np.int32)
    served[:, :net.n_neurons] = want
    ok = reference.Check(ext=ext[0], served=served)
    assert reference.mismatches(ref, [ok], ["spikes"]) == {
        "mismatched_spikes": 0, "unanswered": 0}
    outside = served.copy()
    outside[3, net.n_neurons + 2] = 1
    short = served[:45]
    res = reference.mismatches(ref, [
        reference.Check(ext=ext[0], served=outside),
        reference.Check(ext=ext[0], served=short),
        reference.Check(ext=ext[0], served=None)], ["spikes"])
    assert res == {"mismatched_spikes": 1 + 5 * net.n_neurons,
                   "unanswered": 1}


def test_mismatches_counts_potentials():
    """Each potential that differs counts, so does a nonzero potential
    outside the model and every neuron of an answer without potentials;
    an unknown check is refused."""
    config = _config(PID)
    net = deploy.network(REPO, config, seed=1)
    ref = reference.model(REPO, net, config)
    ext = _pid_inputs(1, 1, 96)
    spikes, v = ref.run(ext)
    served = np.zeros((96, 64), np.int32)
    served[:, :net.n_neurons] = spikes[0]
    pot = np.zeros(1024, np.int32)
    pot[:net.n_neurons] = v[0]
    both = ["spikes", "potentials"]
    ok = reference.Check(ext=ext[0], served=served, potentials=pot)
    assert reference.mismatches(ref, [ok], both) == {
        "mismatched_spikes": 0, "mismatched_potentials": 0, "unanswered": 0}
    moved = pot.copy()
    moved[5] += 1
    outside = pot.copy()
    outside[net.n_neurons + 7] = -3
    res = reference.mismatches(ref, [
        reference.Check(ext=ext[0], served=served, potentials=moved),
        reference.Check(ext=ext[0], served=served, potentials=outside),
        reference.Check(ext=ext[0], served=served)], both)
    assert res == {"mismatched_spikes": 0,
                   "mismatched_potentials": 1 + 1 + net.n_neurons,
                   "unanswered": 0}
    assert reference.mismatches(ref, [ok], ["spikes"]) == {
        "mismatched_spikes": 0, "unanswered": 0}
    with pytest.raises(ValueError, match="unknown checks"):
        reference.mismatches(ref, [ok], ["voltage"])


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
    lif = load.module(REPO, "neurons", "lif")
    assert lif.hardware_decay(0.1, [0.125, 0.25, 0.5, 0.75]) == 0.125
