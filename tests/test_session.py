"""Multi-model co-residency (paper §V-D): address-space isolation AND
true fusion — run_all advances all resident models in one engine scan.

The fusion and isolation cases run for both neuron models (``neuron``):
one-state LIF and current-based LIF (``syn_decay_rate``)."""

import jax
import numpy as np
import pytest

from repro.core.engine import SpikeEngine
from repro.core.session import AcceleratorSession

from conftest import make_ff_net

NEURONS = ("lif", "cuba")


def _syn(neuron):
    """The current-based networks' synaptic decay rate (None: LIF)."""
    return 0.75 if neuron == "cuba" else None


@pytest.mark.parametrize("neuron", NEURONS)
def test_co_residency_isolation(rng, neuron):
    """A model's outputs are identical whether it runs alone or alongside
    other resident models — disjoint clusters + rows = no interference."""
    syn = _syn(neuron)
    netA = make_ff_net(rng, sizes=(12, 40, 10), syn_decay_rate=syn)
    netB = make_ff_net(rng, sizes=(8, 30, 5), scale=0.9, syn_decay_rate=syn)
    key = jax.random.key(0)
    xA = rng.random((6, 12)).astype(np.float32)
    xB = rng.random((6, 8)).astype(np.float32)

    solo = AcceleratorSession()
    solo.deploy("A", netA)
    outA_solo = solo.run("A", xA, 20, key)

    both = AcceleratorSession()
    both.deploy("A", netA)
    both.deploy("B", netB)
    outs = both.run_all({"A": xA, "B": xB}, 20, key)

    np.testing.assert_array_equal(
        np.asarray(outA_solo["predictions"]),
        np.asarray(outs["A"]["predictions"]))
    np.testing.assert_array_equal(
        np.asarray(outA_solo["output_counts"]),
        np.asarray(outs["A"]["output_counts"]))


@pytest.mark.parametrize("neuron", NEURONS)
def test_run_all_is_one_fused_scan(rng, monkeypatch, neuron):
    """N co-resident models with a shared LIF config advance in EXACTLY one
    SpikeEngine scan — no per-model Python loop over run()."""
    syn = _syn(neuron)
    sess = AcceleratorSession()
    sess.deploy("A", make_ff_net(rng, sizes=(12, 40, 10), syn_decay_rate=syn))
    sess.deploy("B", make_ff_net(rng, sizes=(8, 30, 5), scale=0.9,
                                 syn_decay_rate=syn))
    sess.deploy("C", make_ff_net(rng, sizes=(6, 20, 4), syn_decay_rate=syn))

    scans = []
    orig_run = SpikeEngine.run
    monkeypatch.setattr(SpikeEngine, "run",
                        lambda self, ext: scans.append(self) or
                        orig_run(self, ext))

    def no_solo_run(*a, **k):  # run_all must not fall back to solo runs
        raise AssertionError("run_all looped over per-model run()")
    monkeypatch.setattr(AcceleratorSession, "run", no_solo_run)

    xs = {"A": rng.random((4, 12)).astype(np.float32),
          "B": rng.random((4, 8)).astype(np.float32),
          "C": rng.random((4, 6)).astype(np.float32)}
    outs = sess.run_all(xs, 15, jax.random.key(1))
    assert len(scans) == 1  # one fused engine scan for all three models
    assert set(outs) == {"A", "B", "C"}
    # the fused engine covers the concatenated external sources
    assert scans[0].n_inputs == 12 + 8 + 6
    assert scans[0].has_current == (syn is not None)


@pytest.mark.parametrize("neuron", NEURONS)
def test_run_all_isolation_bit_exact_per_model(rng, neuron):
    """Every co-resident model (not just the first) decodes identically to
    its solo deployment at the same placement."""
    syn = _syn(neuron)
    nets = {"A": make_ff_net(rng, sizes=(12, 40, 10), syn_decay_rate=syn),
            "B": make_ff_net(rng, sizes=(8, 30, 5), scale=0.9,
                             syn_decay_rate=syn)}
    key = jax.random.key(3)
    xs = {"A": rng.random((5, 12)).astype(np.float32),
          "B": rng.random((5, 8)).astype(np.float32)}

    both = AcceleratorSession()
    for name, net in nets.items():
        both.deploy(name, net)
    outs = both.run_all(xs, 18, key)

    # solo reference for B at the SAME placement: deploy a dummy A first
    solo = AcceleratorSession()
    for name, net in nets.items():
        solo.deploy(name, net)
    soloB = solo.run("B", xs["B"], 18, key)
    np.testing.assert_array_equal(np.asarray(soloB["output_counts"]),
                                  np.asarray(outs["B"]["output_counts"]))
    np.testing.assert_array_equal(np.asarray(soloB["spikes"]),
                                  np.asarray(outs["B"]["spikes"]))
    for k in ("cycles", "sops", "row_fetches"):
        np.testing.assert_array_equal(np.asarray(soloB[k]),
                                      np.asarray(outs["B"][k]))


@pytest.mark.parametrize("neuron", NEURONS)
def test_run_all_mixed_lif_configs_still_fused_per_group(rng, monkeypatch,
                                                         neuron):
    """Models with different LIF configs form separate fused groups (the
    hardware's per-configuration register banks) — still no per-model
    loop, and outputs still match solo deployment."""
    syn = _syn(neuron)
    netA = make_ff_net(rng, sizes=(10, 30, 6), syn_decay_rate=syn)
    netB = make_ff_net(rng, sizes=(8, 20, 4), decay_rate=0.5,
                       syn_decay_rate=syn)
    sess = AcceleratorSession()
    sess.deploy("A", netA)
    sess.deploy("B", netB)

    scans = []
    orig_run = SpikeEngine.run
    monkeypatch.setattr(SpikeEngine, "run",
                        lambda self, ext: scans.append(self) or
                        orig_run(self, ext))

    key = jax.random.key(5)
    xs = {"A": rng.random((3, 10)).astype(np.float32),
          "B": rng.random((3, 8)).astype(np.float32)}
    outs = sess.run_all(xs, 12, key)
    assert len(scans) == 2  # one scan per LIF-config group

    monkeypatch.undo()
    solo = AcceleratorSession()
    solo.deploy("A", netA)
    soloA = solo.run("A", xs["A"], 12, key)
    np.testing.assert_array_equal(np.asarray(soloA["output_counts"]),
                                  np.asarray(outs["A"]["output_counts"]))


def test_fused_engine_cache_keyed_on_backend(rng, monkeypatch):
    """Switching sess.backend after a run_all must rebuild the fused
    engine for the new backend, not reuse the cached one."""
    sess = AcceleratorSession()
    sess.deploy("A", make_ff_net(rng, sizes=(6, 10, 4)))
    scans = []
    orig_run = SpikeEngine.run
    monkeypatch.setattr(SpikeEngine, "run",
                        lambda self, ext: scans.append(self) or
                        orig_run(self, ext))
    xs = {"A": rng.random((2, 6)).astype(np.float32)}
    key = jax.random.key(0)
    sess.run_all(xs, 5, key)
    assert scans[-1].backend == "reference"
    sess.backend = "pallas"
    sess.run_all(xs, 5, key)
    assert scans[-1].backend == "pallas"


def test_run_all_rejects_mismatched_batches(rng):
    sess = AcceleratorSession()
    sess.deploy("A", make_ff_net(rng, sizes=(6, 10, 4)))
    sess.deploy("B", make_ff_net(rng, sizes=(6, 10, 4)))
    with pytest.raises(ValueError, match="batch"):
        sess.run_all({"A": np.zeros((2, 6), np.float32),
                      "B": np.zeros((3, 6), np.float32)},
                     5, jax.random.key(0))


def test_group_boundary_isolation(rng):
    """Deployments round up to group boundaries so no two models share a
    weight SRAM (the hardware's address-space isolation guarantee)."""
    sess = AcceleratorSession()
    m1 = sess.deploy("m1", make_ff_net(rng, sizes=(6, 10, 4)))
    m2 = sess.deploy("m2", make_ff_net(rng, sizes=(6, 10, 4)))
    cpg = sess.geometry.clusters_per_group
    assert m1.cluster_range[1] % cpg == 0
    assert m2.cluster_range[0] >= m1.cluster_range[1]


def test_capacity_exhaustion(rng):
    sess = AcceleratorSession()
    sess.deploy("big", make_ff_net(rng, sizes=(10, 900, 10)))
    with pytest.raises(ValueError, match="clusters"):
        sess.deploy("more", make_ff_net(rng, sizes=(10, 200, 10)))


def test_duplicate_name_rejected(rng):
    sess = AcceleratorSession()
    sess.deploy("m", make_ff_net(rng, sizes=(4, 8, 2)))
    with pytest.raises(ValueError, match="already"):
        sess.deploy("m", make_ff_net(rng, sizes=(4, 8, 2)))


def test_utilization_accounting(rng):
    sess = AcceleratorSession()
    sess.deploy("a", make_ff_net(rng, sizes=(6, 40, 10)))
    u = sess.utilization()
    assert 0 < u["neuron_utilization"] < 1
    assert 0 < u["row_utilization"] < 1
    assert u["models"] == ["a"]
    assert u["clusters_used"] >= -(-50 // 32)  # >= ceil(neurons/32)
