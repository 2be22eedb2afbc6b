"""Tiny cells for running the benchmark harness on the CPU.

``make_root`` copies the benchmark (``BENCHMARK.json`` and ``bench/``) into
a scratch directory and adds throwaway configurations, traffic mixes and
cells at sizes the CPU runs in seconds; ``run_cell`` runs one cell there
in a child process through ``perfbench_cli.py``.
"""

import json
import os
import pathlib
import shutil
import subprocess
import sys

REPO = pathlib.Path(__file__).resolve().parents[2]
CLI = pathlib.Path(__file__).resolve().parent / "perfbench_cli.py"

TINY_GEOMETRY = {"n_clusters": 4, "neurons_per_cluster": 32,
                 "clusters_per_group": 4, "rows_per_group": 2048}
TINY_ENGINE = {"backend": "reference", "fuse_steps": 1, "gate": "per-example",
               "chunk_steps": 8, "n_slots": 4}
FRONTEND = {"queue_capacity": 64, "backpressure": "reject", "deadline_ms": None}

CONFIGS = {
    "tiny-mlp": ("snapv-mnist-784-256-10",
                 {"network": {"kind": "mlp", "layer_sizes": [784, 16, 4],
                              "std_scale": 3.0, "weight_clip": 1.0},
                  "stimulus": {"kind": "digits", "pool": 8}}),
    "tiny-pid": ("snapv-pid-36", {}),
}
TRAFFIC = {
    "tiny-closed": {"driver": "requests", "clients": 6, "lengths": [8, 16],
                    "sample_rate": 1.0, "frontend": FRONTEND},
    "tiny-mixed": {"driver": "requests", "clients": 9, "lengths": [8, 24],
                   "sample_rate": 1.0, "frontend": FRONTEND},
    # every plant sampled, so a fault in any slot shows
    "tiny-fleet": {"driver": "fleet", "plants": 4, "period_ms": 10,
                   "tick_steps": 24, "warm_ticks": 2, "sample_plants": 4,
                   "plant": {"gain": 0.8, "dt": 1.0, "u_max": 0.25},
                   "encoder": {"err_scale": 0.5},
                   "setpoints": {"every_ticks": 6, "low": -1.0,
                                 "high": 1.0}},
}
# tiny cell -> (config, traffic, its end-to-end metrics); the cell also
# reports every per-layer metric that moves one of them
CELLS = {
    "tiny-closed": ("tiny-mlp", "tiny-closed", ("timesteps_per_s",)),
    "tiny-mixed": ("tiny-mlp", "tiny-mixed", ("timesteps_per_s",)),
    "tiny-fleet": ("tiny-pid", "tiny-fleet", ("tick_p95_ms", "loop_rate_hz")),
}


def make_root(tmp) -> pathlib.Path:
    root = pathlib.Path(tmp) / "checkout"
    shutil.copytree(REPO / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    man = json.loads((REPO / "BENCHMARK.json").read_text())
    for name, (base, changes) in CONFIGS.items():
        cfg = json.loads((root / "bench" / "configs" / f"{base}.json")
                         .read_text())
        cfg.update(changes, name=name)
        cfg["hardware"]["geometry"] = TINY_GEOMETRY
        cfg["engine"] = dict(TINY_ENGINE)
        path = f"bench/configs/{name}.json"
        (root / path).write_text(json.dumps(cfg))
        man["configs"].append({"name": name, "source": "test", "file": path,
                               "reduced": [], "why": "CPU test"})
    for name, mix in TRAFFIC.items():
        (root / "bench" / "traffic" / f"{name}.json").write_text(
            json.dumps(mix))
    for name, (cfg, traffic, e2e) in CELLS.items():
        man["workloads"].append({"name": name, "config": cfg,
                                 "traffic": traffic, "chips": 1,
                                 "why": "CPU test"})
        for m in man["end_to_end"]:
            if m["name"] in e2e:
                m["workloads"].append(name)
        for m in man["per_layer"]:
            if m["moves"] in e2e:
                m["workloads"].append(name)
    write_manifest(root, man)
    return root


def manifest(root) -> dict:
    return json.loads((pathlib.Path(root) / "BENCHMARK.json").read_text())


def write_manifest(root, man) -> None:
    (pathlib.Path(root) / "BENCHMARK.json").write_text(json.dumps(man))


def run_cell(root, workload, *, seconds=1.0, trace=0, seed=2**31 + 7,
             fault=None, require_tpu=False, timeout=600):
    """(exit code, parsed last stdout line or None, stderr)."""
    cmd = [sys.executable, str(CLI), "--root", str(root),
           "--workload", workload, "--seed", str(seed),
           "--seconds", str(seconds), "--trace", str(trace)]
    if fault:
        cmd += ["--fault", fault]
    if require_tpu:
        cmd += ["--require-tpu"]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(cmd, capture_output=True, text=True, env=env,
                       timeout=timeout)
    lines = p.stdout.strip().splitlines()
    result = None
    if lines:
        try:
            result = json.loads(lines[-1])
        except json.JSONDecodeError:
            result = None
    return p.returncode, result, p.stderr
