"""The benchmark harness on the CPU: cells resolve by name, a new
configuration, traffic mix and per-layer metric need only new files and
manifest entries, the sound program passes the comparison, and nothing is
measured without a TPU."""

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
])
def test_sound_program_passes_the_comparison(root, cell, metric, numbers):
    rc, res, err = tiny.run_cell(root, cell)
    assert rc == 0, err[-3000:]
    assert res["correct"] is True
    assert res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == {metric, "setup_s"}
    assert list(res)[-1] == "checks"
    assert {k: v["value"] for k, v in res["checks"].items()} == dict.fromkeys(
        numbers, 0)
    assert err.strip().splitlines()[-1].startswith("[bench] check ")


def test_new_config_traffic_and_metric_resolve_by_name(root):
    """Three new files and manifest entries, no edit to any existing file."""
    before = {p: p.read_bytes() for p in (root / "bench").rglob("*")
              if p.is_file() and "__pycache__" not in p.parts}
    cfg = json.loads((root / "bench/configs/tiny-mlp.json").read_text())
    cfg.update(name="throwaway-mlp", lif=dict(cfg["lif"], threshold=0.7))
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
