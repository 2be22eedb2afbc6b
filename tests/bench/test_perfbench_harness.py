"""The benchmark harness on the CPU: cells resolve by name, a new
configuration, traffic mix, per-layer metric and neuron model need only
new files and manifest entries, the sound program passes the comparison,
and nothing is measured without a TPU."""

import json
import os
import pathlib
import shutil
import subprocess
import sys

import pytest

import perfbench_tiny as tiny


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("bench"))


@pytest.mark.parametrize("cell,metric,numbers", [
    ("tiny-closed", "timesteps_per_s", {"mismatched_spikes", "unanswered"}),
    ("tiny-mixed", "timesteps_per_s", {"mismatched_spikes", "unanswered"}),
    ("tiny-fleet", "tick_p95_ms",
     {"mismatched_spikes", "mismatched_potentials", "unanswered"}),
])
def test_sound_program_passes_the_comparison(root, cell, metric, numbers):
    rc, res, err = tiny.run_cell(root, cell)
    assert rc == 0, err[-3000:]
    assert res["correct"] is True
    assert res["failed"] == 0 and res["attempted"] > 0
    assert metric in res["metrics"]
    assert set(res["metrics"]) == {*tiny.CELLS[cell][2], "setup_s"}
    assert list(res)[-1] == "checks"
    assert {k: v["value"] for k, v in res["checks"].items()} == dict.fromkeys(
        numbers, 0)
    assert err.strip().splitlines()[-1].startswith("[bench] check ")


def test_new_config_traffic_and_metric_resolve_by_name(root):
    """Three new files and manifest entries, no edit to any existing file."""
    before = {p: p.read_bytes() for p in (root / "bench").rglob("*")
              if p.is_file() and "__pycache__" not in p.parts}
    cfg = json.loads((root / "bench/configs/tiny-mlp.json").read_text())
    cfg.update(name="throwaway-mlp", neuron=dict(cfg["neuron"],
                                                 threshold=0.7))
    (root / "bench/configs/throwaway-mlp.json").write_text(json.dumps(cfg))
    mix = dict(tiny.TRAFFIC["tiny-closed"], clients=3, lengths=[16])
    (root / "bench/traffic/throwaway-mix.json").write_text(json.dumps(mix))
    (root / "bench/metrics/throwaway_rounds.py").write_text(
        "def read(obs):\n    return float(obs.driver.rounds)\n")
    man = tiny.manifest(root)
    man["configs"].append({"name": "throwaway-mlp", "source": "test",
                           "file": "bench/configs/throwaway-mlp.json",
                           "reduced": [], "why": "test"})
    man["workloads"].append({"name": "throwaway", "config": "throwaway-mlp",
                             "traffic": "throwaway-mix", "chips": 1,
                             "why": "test"})
    for m in man["end_to_end"]:
        if m["name"] == "timesteps_per_s":
            m["workloads"].append("throwaway")
    man["per_layer"].append({"name": "throwaway_rounds", "unit": "rounds",
                             "better": "higher", "source": "host_clock",
                             "layer": "test", "moves": "timesteps_per_s",
                             "workloads": ["throwaway"]})
    tiny.write_manifest(root, man)
    for p, data in before.items():
        assert p.read_bytes() == data

    rc, res, err = tiny.run_cell(root, "throwaway", trace=1)
    assert rc == 0, err[-3000:]
    assert res["correct"] is True
    assert res["metrics"]["throwaway_rounds"]["value"] > 0
    assert {"busy_s", "window_s"} <= set(res["device"])
    assert "breakdown" in res
    rc, res, err = tiny.run_cell(root, "throwaway")
    assert rc == 0, err[-3000:]
    assert set(res["metrics"]) == {"timesteps_per_s", "setup_s"}


# a neuron kind of the test's own: LIF with its threshold given in
# thousandths, which the shipped ``lif`` module cannot read
THOUSANDTHS = """
import dataclasses
import pathlib

from bench import load

_lif = load.module(pathlib.Path(__file__).resolve().parents[2],
                   "neurons", "lif")
PRECISIONS = _lif.PRECISIONS


def _as_lif(neuron):
    return {"kind": "lif", "decay_rate": neuron["decay_rate"],
            "threshold": neuron["threshold_milli"] / 1000,
            "reset": neuron["reset"]}


class Reference(_lif.Reference):
    def __init__(self, net, config, precision="exact"):
        super().__init__(dataclasses.replace(net, neuron=_as_lif(net.neuron)),
                         config, precision)


def program_params(neuron, fmt):
    return _lif.program_params(_as_lif(neuron), fmt)
"""


@pytest.mark.parametrize("base,traffic,metric", [
    ("tiny-mlp", "tiny-closed", "timesteps_per_s"),
    ("tiny-pid", "tiny-fleet", "tick_p95_ms"),
])
def test_new_neuron_kind_resolves_by_name(tmp_path, base, traffic, metric):
    """A neuron model, its reference and its program parameters come from
    one new file under ``bench/neurons/``: no existing file is edited,
    and the cell that names the kind passes the comparison."""
    root = tiny.make_root(tmp_path)
    before = {p: p.read_bytes() for p in (root / "bench").rglob("*")
              if p.is_file() and "__pycache__" not in p.parts}
    (root / "bench/neurons/lif_milli.py").write_text(THOUSANDTHS)
    cfg = json.loads((root / f"bench/configs/{base}.json").read_text())
    lif = cfg["neuron"]
    cfg.update(name="throwaway-neuron", neuron={
        "kind": "lif_milli", "decay_rate": lif["decay_rate"],
        "threshold_milli": round(lif["threshold"] * 1000) - 50,
        "reset": lif["reset"]})
    (root / "bench/configs/throwaway-neuron.json").write_text(json.dumps(cfg))
    man = tiny.manifest(root)
    man["configs"].append({"name": "throwaway-neuron", "source": "test",
                           "file": "bench/configs/throwaway-neuron.json",
                           "reduced": [], "why": "test"})
    man["workloads"].append({"name": "throwaway", "config": "throwaway-neuron",
                             "traffic": traffic, "chips": 1, "why": "test"})
    e2e = next(c[2] for c in tiny.CELLS.values() if c[1] == traffic)
    assert metric in e2e
    for m in man["end_to_end"]:
        if m["name"] in e2e:
            m["workloads"].append("throwaway")
    tiny.write_manifest(root, man)
    for p, data in before.items():
        assert p.read_bytes() == data

    rc, res, err = tiny.run_cell(root, "throwaway", seconds=0.5)
    assert rc == 0, err[-3000:]
    assert res["correct"] is True
    assert set(res["metrics"]) == {*e2e, "setup_s"}
    assert set(res["checks"]) == {"mismatched_" + c for c in cfg["checks"]} | {
        "unanswered"}


def test_no_tpu_no_result(root):
    rc, res, err = tiny.run_cell(root, "tiny-closed", require_tpu=True)
    assert rc != 0 and res is None
    assert "TPU" in err


def test_unknown_workload_is_refused(root):
    rc, res, _ = tiny.run_cell(root, "no-such-cell")
    assert rc != 0 and res is None


def test_entry_point_alone_gives_no_result(tmp_path):
    """A directory holding only the manifest and the benchmark's paths,
    started the way the benchmark is run, prints no result."""
    shutil.copy(tiny.REPO / "BENCHMARK.json", tmp_path)
    man = json.loads((tmp_path / "BENCHMARK.json").read_text())
    for path in man["paths"]:
        shutil.copytree(tiny.REPO / path, tmp_path / path,
                        ignore=shutil.ignore_patterns("__pycache__"))
    cmd = man["command"] + ["--workload", man["workloads"][0]["name"],
                            "--seed", str(2**31 + 3), "--seconds", "1",
                            "--trace", "0"]
    cmd[0] = sys.executable if cmd[0].startswith("python") else cmd[0]
    p = subprocess.run(cmd, cwd=tmp_path, capture_output=True, text=True,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"),
                       timeout=300)
    assert p.returncode != 0
    assert not p.stdout.strip()


def test_manifest_names_every_file(root):
    """Every configuration, traffic mix and metric the manifest names has
    its file, and every cell's metrics are reported somewhere."""
    man = json.loads((tiny.REPO / "BENCHMARK.json").read_text())
    for c in man["configs"]:
        assert (tiny.REPO / c["file"]).is_file()
    for w in man["workloads"]:
        assert (tiny.REPO / "bench/traffic" / f"{w['traffic']}.json").is_file()
    for m in man["per_layer"]:
        assert (tiny.REPO / "bench/metrics" / f"{m['name']}.py").is_file()
    cells = {w["name"] for w in man["workloads"]}
    for m in man["end_to_end"] + man["per_layer"]:
        assert set(m.get("workloads", cells)) <= cells
    assert pathlib.Path(tiny.REPO / man["command"][1]).is_file()
