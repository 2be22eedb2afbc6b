#!/usr/bin/env python3
"""Read the comparison's two numbers on the chip: the program's, over
many seeds, and the control's, the bf16 reference put in the program's
place on the same inputs.

    python3 bench/control.py --workload mnist256-backlog --seconds 30 \\
        --seeds 1,2,3

One process runs every seed: set-up, warm-up and a window at the cell's
own load, then the program's served streams and the control's streams of
the same inputs are compared with the exact reference, on every check the
cell's configuration lists (raster bits, and final potentials where
listed). Not part of a benchmark run;
``tests/bench/test_perfbench_reference.py`` keeps the control at a size a
test run holds.
"""

import time

STARTED = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:1] = [str(ROOT / "src"), str(ROOT)]

import numpy as np  # noqa: E402

from bench import deploy, harness, load, reference  # noqa: E402


def readings(cell, seed: int, seconds: float) -> dict:
    dep = deploy.deploy(ROOT, cell.config, seed, cell.traffic.get("frontend"))
    drivers = load.module(ROOT, "drivers", cell.traffic["driver"])
    driver = drivers.Driver(ROOT, dep, cell.traffic, seed, seconds, False)
    driver.warm()
    driver.window()
    checks = [c for c in driver.checks() if c.served is not None]
    net = dep.net
    del dep, driver
    gc.collect()
    compare = cell.config["checks"]
    exact = reference.model(ROOT, net, cell.config)
    control = reference.model(ROOT, net, cell.config, "bf16")
    as_control = []
    for c, (spikes, v) in zip(checks, reference.answers(control, checks)):
        raster = np.zeros_like(np.asarray(c.served))
        raster[:, :control.n_neurons] = spikes
        pot = None
        if c.potentials is not None:
            pot = np.zeros_like(np.asarray(c.potentials))
            pot[:control.n_neurons] = v
        as_control.append(reference.Check(ext=c.ext, served=raster,
                                          potentials=pot))
    return {"streams": len(checks),
            "timesteps": int(sum(c.ext.shape[0] for c in checks)),
            "program": reference.mismatches(exact, checks, compare),
            "control": reference.mismatches(exact, as_control, compare)}


def main(argv) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    import jax

    harness.enable_cache(ROOT)
    if jax.devices()[0].platform != "tpu":
        print("needs a TPU", file=sys.stderr)
        return 3
    cell = harness.Cell(ROOT, args.workload)
    for seed in [int(s) for s in args.seeds.split(",")]:
        r = readings(cell, seed, args.seconds)
        print(f"{args.workload} seed {seed}: {r['streams']} streams, "
              f"{r['timesteps']} timesteps; program {r['program']}, "
              f"control (bf16) {r['control']}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
