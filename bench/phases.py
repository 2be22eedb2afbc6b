#!/usr/bin/env python3
"""The program's own phases of a served round, read from a profiler trace.

The program names each phase of a served round with a catalogued
``jax.profiler`` annotation (``repro.obs.tracing.HOT_SPANS``): ``snn.pump``
around one round, ``snn.feed`` around the slot server's batched step, and
seven leaves that do not overlap. The two dispatch phases carry their byte
counts as event arguments (``h2d_bytes``, ``d2h_bytes``). This module reads
those host events and splits a round's host time into them, by the rule
``round_host_ms`` uses for the harness's own ``bench.pump`` span: the
summed wall time of a span's instances in the window, less the device
busy time inside them, over the window's rounds.

Run alone, it runs one cell once with the profiler on and prints the split
as one JSON line:

    python3 bench/phases.py --workload mnist256-backlog --seed <n> \\
        --seconds 30 [--keep DIR]

``--keep`` copies the trace's ``.xplane.pb`` and the printed line into
DIR. Not part of a benchmark run.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import pathlib
import shutil
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
if __name__ == "__main__":
    sys.path[:1] = [str(ROOT / "src"), str(ROOT)]

from bench import xtrace  # noqa: E402

PREFIX = "snn."
ROUND = "snn.pump"
# the phases of a round that do not overlap, in the order a round runs them
LEAVES = ("snn.pump.admit", "snn.pump.gather", "snn.feed.assemble",
          "snn.feed.dispatch", "snn.feed.readback", "snn.feed.split",
          "snn.pump.retire")
# leaf -> the per-layer metric that would read it
METRICS = {
    "snn.pump.admit": "pump_admit_ms.throughput",
    "snn.pump.gather": "pump_gather_ms.throughput",
    "snn.feed.assemble": "feed_assemble_ms.throughput",
    "snn.feed.dispatch": "feed_dispatch_ms.throughput",
    "snn.feed.readback": "feed_readback_ms.throughput",
    "snn.feed.split": "feed_split_ms.throughput",
    "snn.pump.retire": "pump_retire_ms.throughput",
}
BYTES = ("h2d_bytes", "d2h_bytes")


def load(path: str) -> dict[str, list[tuple[float, float, dict]]]:
    """The program's ``snn.*`` host events of an ``.xplane.pb`` file (or
    the one under a trace directory): name -> sorted (start, end, args)
    in ns on the device ops' clock."""
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        path = xtrace.find_xplane(path)
    data = ProfileData.from_file(path)
    program: dict = {}
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PREFIX):
                    program.setdefault(e.name, []).append(
                        (e.start_ns, e.start_ns + e.duration_ns,
                         dict(e.stats)))
    for v in program.values():
        v.sort(key=lambda x: x[:2])
    return program


def spans_in(program, name: str, lo: float, hi: float):
    return [sp for sp in program.get(name, [])
            if sp[0] >= lo and sp[1] <= hi]


def host_ms_per_round(trace: xtrace.Trace, program, name: str,
                      lo: float, hi: float) -> float | None:
    """Wall time of the window's ``name`` spans less the device busy time
    inside them, over the window's ``snn.pump`` rounds, in ms."""
    rounds = len(spans_in(program, ROUND, lo, hi))
    spans = spans_in(program, name, lo, hi)
    if not rounds or not spans:
        return None
    wall = sum(e - s for s, e, _ in spans)
    busy = sum(trace.busy_ns(s, e) for s, e, _ in spans)
    return (wall - busy) / rounds / 1e6


def metrics(trace: xtrace.Trace, program, lo: float, hi: float) -> dict:
    """The round's split: each leaf's host ms per round, the host time in
    ``snn.pump`` that no leaf covers, and the MB moved between host and
    device per round. Empty where the trace holds no ``snn.pump``."""
    rounds = len(spans_in(program, ROUND, lo, hi))
    if not rounds:
        return {}
    out = {}
    for leaf in LEAVES:
        v = host_ms_per_round(trace, program, leaf, lo, hi)
        if v is not None:
            out[METRICS[leaf]] = v
    whole = host_ms_per_round(trace, program, ROUND, lo, hi)
    out["pump_untraced_ms.throughput"] = whole - sum(out.values())
    moved = sum(args.get(k, 0) for leaf in ("snn.feed.dispatch",
                                            "snn.feed.readback")
                for _, _, args in spans_in(program, leaf, lo, hi)
                for k in BYTES)
    out["host_device_mb.throughput"] = moved / rounds / 1e6
    return out


def idle_gaps(trace: xtrace.Trace, program, lo: float, hi: float,
              top: int = 10):
    """[[phase, seconds], ...]: the longest gaps with no device op, each
    named by the leaf phase that overlaps it most (``host`` where none
    does)."""
    leaves = {n: [(s, e) for s, e, _ in program[n]]
              for n in LEAVES if n in program}
    return xtrace.Trace(ops=trace.ops, spans=leaves).idle_gaps(lo, hi, top)


# -- one traced run of a cell ---------------------------------------------

def run(root, workload: str, seed: int, seconds: float, keep=None,
        require_tpu: bool = True) -> dict | None:
    import jax

    from bench import deploy, harness
    from bench import load as find

    harness.enable_cache(root)
    dev = jax.devices()[0]
    if require_tpu and dev.platform != "tpu":
        print("needs a TPU", file=sys.stderr)
        return None
    cell = harness.Cell(root, workload)
    dep = deploy.deploy(root, cell.config, seed, cell.traffic.get("frontend"))
    drivers = find.module(root, "drivers", cell.traffic["driver"])
    driver = drivers.Driver(root, dep, cell.traffic, seed, seconds, True)
    driver.warm()
    trace_dir = pathlib.Path(root) / ".bench_trace" / f"phases-{workload}"
    shutil.rmtree(trace_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    # the trace ends with the driver's window span: the drain that
    # follows it is read by nothing, and would only swell the file
    annotate = driver._annotate

    @contextlib.contextmanager
    def until_window_ends(name, **args):
        with annotate(name, **args):
            yield
        if name == "bench.window":
            jax.profiler.stop_trace()

    driver._annotate = until_window_ends
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    driver.window()
    path = xtrace.find_xplane(str(trace_dir))
    tr, program = xtrace.load(path), load(path)
    lo, hi = tr.window()
    dispatch = spans_in(program, "snn.feed.dispatch", lo, hi)
    readback = spans_in(program, "snn.feed.readback", lo, hi)
    # the harness's own readings of the same window, for comparison
    obs = harness.Observation(root=root, cell=cell, net=dep.net,
                              driver=driver, trace=tr, work=None, lo=lo,
                              hi=hi, device_kind=dev.device_kind)
    out = {
        "workload": workload, "seed": seed, "device": dev.device_kind,
        "rounds": driver.rounds, "window_s": obs.window_s,
        "busy_s": obs.busy_s,
        "timesteps_per_s": driver.values()["timesteps_per_s"],
        **{m: find.module(root, "metrics", m).read(obs)
           for m in ("round_host_ms.throughput",
                     "round_device_ms.throughput",
                     "device_idle_share.throughput")},
        "phases": metrics(tr, program, lo, hi),
        "spans": {n: len(spans_in(program, n, lo, hi)) for n in program},
        "dispatch_args": dispatch[0][2] if dispatch else None,
        "readback_args": readback[0][2] if readback else None,
        "idle_gaps": idle_gaps(tr, program, lo, hi),
        "harness_idle_gaps": tr.idle_gaps(lo, hi),
        "device_ops": tr.op_breakdown(lo, hi, top=5),
    }
    if keep is not None:
        keep = pathlib.Path(keep)
        keep.mkdir(parents=True, exist_ok=True)
        shutil.copy(path, keep / f"{workload}.xplane.pb")
        (keep / f"{workload}.phases.json").write_text(json.dumps(out))
    shutil.rmtree(trace_dir, ignore_errors=True)
    return out


def main(argv, root=ROOT, require_tpu: bool = True) -> int:
    ap = argparse.ArgumentParser(prog="bench/phases.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--keep", default=None)
    args = ap.parse_args(argv)
    out = run(root, args.workload, args.seed, args.seconds, args.keep,
              require_tpu)
    if out is None:
        return 3
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
