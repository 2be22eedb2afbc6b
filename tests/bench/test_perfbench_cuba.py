"""The current-based configuration on the CPU: a tiny current-based cell
built from the shipped files passes the comparison, its controls (bf16
weights, the current removed) and a program that drops the current at a
chunk boundary fail it, the plain reference agrees bit for bit with the
program's own ``cuba_step_fixed``, and the stimulus has SHD's shape and
its stated density."""

import json
import os
import pathlib
import subprocess
import sys

import numpy as np
import pytest

import perfbench_tiny as tiny

REPO = tiny.REPO
for p in (str(REPO), str(REPO / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import deploy, reference  # noqa: E402
from bench.stimuli import shd  # noqa: E402

SHD = "snapv-shd-700-256-20"

# the harness with the program's chunk step broken: the synaptic current
# zeroed at every chunk boundary, every other state kept
DROP_CURRENT = """
import pathlib, sys, time
STARTED = time.perf_counter()
REPO = pathlib.Path({repo!r})
sys.path[:1] = [str(REPO / "src"), str(REPO)]
import jax.numpy as jnp
from bench import harness
from repro.core.engine import SpikeEngine

step = SpikeEngine.step_chunk


def drop_current(self, carry, ext, active=None):
    return step(self, dict(carry, i=jnp.zeros_like(carry["i"])), ext, active)


SpikeEngine.step_chunk = drop_current
sys.exit(harness.main(sys.argv[2:], root=pathlib.Path(sys.argv[1]),
                      started=STARTED, require_tpu=False))
"""


def _config(name=SHD):
    return json.loads((REPO / "bench/configs" / f"{name}.json").read_text())


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A checkout with a tiny current-based cell: 700 inputs into 24
    recurrent hidden neurons and 4 outputs, mixed request lengths."""
    root = tiny.make_root(tmp_path_factory.mktemp("cuba"))
    cfg = _config()
    cfg.update(name="tiny-cuba", engine=dict(tiny.TINY_ENGINE),
               stimulus={"kind": "shd", "pool": 8})
    cfg["network"].update(hidden=24, outputs=4)
    cfg["hardware"]["geometry"] = tiny.TINY_GEOMETRY
    (root / "bench/configs/tiny-cuba.json").write_text(json.dumps(cfg))
    man = tiny.manifest(root)
    man["configs"].append({"name": "tiny-cuba", "source": "test",
                           "file": "bench/configs/tiny-cuba.json",
                           "reduced": [], "why": "CPU test"})
    man["workloads"].append({"name": "tiny-cuba", "config": "tiny-cuba",
                             "traffic": "tiny-mixed", "chips": 1,
                             "why": "CPU test"})
    for m in man["end_to_end"]:
        if m["name"] == "timesteps_per_s":
            m["workloads"].append("tiny-cuba")
    tiny.write_manifest(root, man)
    return root


def test_sound_program_passes_the_comparison(root):
    rc, res, err = tiny.run_cell(root, "tiny-cuba")
    assert rc == 0, err[-3000:]
    assert res["correct"] is True
    assert res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == {"timesteps_per_s", "setup_s"}
    assert {k: v["value"] for k, v in res["checks"].items()} == {
        "mismatched_spikes": 0, "unanswered": 0}


def test_current_dropped_at_chunk_boundary_fails(root, tmp_path):
    script = tmp_path / "drop_current.py"
    script.write_text(DROP_CURRENT.format(repo=str(REPO)))
    cmd = [sys.executable, str(script), str(root), "--workload", "tiny-cuba",
           "--seed", str(2**31 + 19), "--seconds", "0.5", "--trace", "0"]
    p = subprocess.run(cmd, capture_output=True, text=True, timeout=600,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode == 0, p.stderr[-3000:]
    res = json.loads(p.stdout.strip().splitlines()[-1])
    assert res["correct"] is False
    assert res["checks"]["mismatched_spikes"]["value"] > 0


@pytest.mark.parametrize("seed", [2**31 + 21, 5])
def test_controls_fail_the_comparison(seed):
    """At the configuration's own widths: the exact reference's answers
    pass, the bf16 control's and the no-current control's do not."""
    config = _config()
    net = deploy.network(REPO, config, seed)
    ext = shd.pool(seed, 6, 60, net.n_inputs)
    exact = reference.model(REPO, net, config)
    served, _ = exact.run(ext)
    checks = [reference.Check(ext=e, served=s) for e, s in zip(ext, served)]
    assert reference.mismatches(exact, checks, config["checks"]) == {
        "mismatched_spikes": 0, "unanswered": 0}
    for precision in ("bf16", "no_current"):
        control = reference.model(REPO, net, config, precision)
        assert reference.mismatches(
            control, checks, config["checks"])["mismatched_spikes"] > 0


@pytest.mark.parametrize("seed", [2**31 + 23, 11])
def test_reference_matches_cuba_step_fixed(seed):
    """The benchmark's NumPy reference and the program's plain
    ``cuba_step_fixed``, scanned over the same seeded network and
    utterances, give the same raster and final potentials."""
    import jax
    import jax.numpy as jnp

    from repro.core.fixedpoint import FixedPointFormat, np_to_fixed
    from repro.core.lif import cuba_step_fixed

    config = _config()
    config["network"].update(hidden=48, outputs=6)
    net = deploy.network(REPO, config, seed)
    ext = shd.pool(seed, 4, 40, net.n_inputs)
    fmt = FixedPointFormat(**config["fixed_point"])
    params = reference.neuron_module(REPO, net).program_params(
        net.neuron, fmt)
    w = jnp.asarray(np_to_fixed(net.weights, fmt))
    B, N = ext.shape[0], net.n_neurons

    def step(carry, ext_t):
        state, prev = carry
        src = jnp.concatenate([ext_t, prev], axis=-1)
        acc = jnp.dot(src, w, preferred_element_type=jnp.int32)
        state, spikes = cuba_step_fixed(state, acc, params)
        return (state, spikes), spikes

    zeros = jnp.zeros((B, N), jnp.int32)
    (state, _), raster = jax.lax.scan(
        step, ({"v": zeros, "i": zeros}, zeros),
        jnp.asarray(np.swapaxes(ext, 0, 1), jnp.int32))
    want = np.swapaxes(np.asarray(raster), 0, 1)
    got, got_v = reference.model(REPO, net, config).run(ext)
    assert want.sum() > 0
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(got_v, np.asarray(state["v"]))


def test_stimulus_shape_and_density():
    pool = shd.pool(2**31 + 29, 32, 100, 700)
    assert pool.shape == (32, 100, 700) and pool.dtype == np.int32
    assert set(np.unique(pool)) <= {0, 1}
    assert pool.mean() == pytest.approx(shd.DENSITY, rel=0.15)
    np.testing.assert_array_equal(pool, shd.pool(2**31 + 29, 32, 100, 700))
    assert not np.array_equal(pool, shd.pool(2**31 + 30, 32, 100, 700))
    with pytest.raises(ValueError, match="700"):
        shd.pool(1, 2, 10, 784)


def test_network_is_the_stated_construction():
    """700 inputs, 256 recurrent hidden neurons (self connections
    included), 20 outputs that drive nothing: 249,856 synapses."""
    config = _config()
    net = deploy.network(REPO, config, 2**31 + 31)
    assert (net.n_inputs, net.n_neurons) == (700, 276)
    assert net.output_slice == (256, 276)
    assert net.n_synapses == 700 * 256 + 256 * 256 + 256 * 20
    assert not net.weights[700 + 256:].any()
    assert np.abs(net.weights).max() <= 1.0
