"""Benchmark orchestrator — one section per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--fast] [--full-grid]

Emits ``name,us_per_call,derived`` CSV lines per benchmark plus the
formatted tables. Sections:

  table_iv   — HW-vs-SW accuracy grid (paper Table IV)
  table_v    — power breakdown on an MNIST workload (paper Table V)
  table_iii  — systems comparison (paper Table III)
  speedup    — Cerebra-S vs Cerebra-H cycles + wall time (paper §VII-B)
  kernels    — Pallas kernel micro-benchmarks + event-gating accounting
"""

from __future__ import annotations

import argparse
import time


def _section(title: str) -> None:
    print(f"\n===== {title} =====", flush=True)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="reduced training budgets (CI-sized)")
    ap.add_argument("--full-grid", action="store_true",
                    help="run the paper's full 80-experiment Table IV grid")
    ap.add_argument("--only", default=None,
                    help="comma-separated subset of sections")
    ap.add_argument("--backend", default=None,
                    help="SpikeEngine backend for the kernels/speedup "
                         "sections (reference | pallas | pallas-mxu)")
    args = ap.parse_args()

    only = set(args.only.split(",")) if args.only else None
    t0 = time.time()

    def want(name: str) -> bool:
        return only is None or name in only

    backend_args = ["--backend", args.backend] if args.backend else []

    if want("kernels"):
        _section("kernels")
        from benchmarks import kernel_bench
        kernel_bench.main(backend_args)

    if want("table_v"):
        _section("table_v (power breakdown)")
        from benchmarks import table_v_power
        table_v_power.main(["--steps", "50"] if args.fast else [])

    if want("table_iii"):
        _section("table_iii (systems comparison)")
        from benchmarks import table_iii_comparison
        table_iii_comparison.main([])

    if want("speedup"):
        _section("speedup (Cerebra-S vs Cerebra-H)")
        from benchmarks import speedup_s_vs_h
        speedup_s_vs_h.main(
            (["--steps", "25"] if args.fast else []) + backend_args)

    if want("table_iv"):
        _section("table_iv (accuracy grid)")
        from benchmarks import table_iv_accuracy
        grid_args = []
        if args.full_grid:
            grid_args.append("--full")
        if args.fast:
            grid_args += ["--train-steps", "60", "--eval-n", "256"]
        table_iv_accuracy.main(grid_args)

    print(f"\n[benchmarks] done in {time.time() - t0:.0f}s", flush=True)


if __name__ == "__main__":
    main()
