"""Resolve a cell by name, set it up, time its window, check what the
window served against the plain reference, and print the result line.

Everything a cell is made of is found by name under ``bench/``
(see ``bench/__init__.py``); this module holds nothing of any one
configuration, traffic mix or metric.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import pathlib
import shutil
import sys
import time

from bench import deploy, load, peaks, reference, xtrace

# the numbers compared, each with its limit: an exact comparison
LIMITS = {"mismatched_spikes": 0, "mismatched_potentials": 0,
          "unanswered": 0}


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def parse(argv):
    ap = argparse.ArgumentParser(prog="bench/run.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


class Cell:
    """One ``workloads`` entry with its configuration, traffic and the
    metrics the manifest asks of it."""

    def __init__(self, root, name: str):
        man = load.manifest(root)
        by_name = {w["name"]: w for w in man["workloads"]}
        if name not in by_name:
            raise ValueError(f"no workload {name!r} in BENCHMARK.json")
        self.name, self.entry = name, by_name[name]
        conf = {c["name"]: c for c in man["configs"]}[self.entry["config"]]
        self.config = json.loads((pathlib.Path(root) / conf["file"]).read_text())
        self.traffic = load.data(root, "traffic", self.entry["traffic"])
        self.chips = int(self.entry["chips"])
        self.end_to_end = [m for m in man["end_to_end"]
                           if name in m.get("workloads", [name])]
        e2e = {m["name"] for m in self.end_to_end}
        self.per_layer = [m for m in man["per_layer"]
                          if (name in m["workloads"] if "workloads" in m
                              else m["moves"] in e2e)]


@dataclasses.dataclass
class Observation:
    """What a per-layer metric reader may read: the cell, its driver's
    records, and the traced window's reduction."""

    root: pathlib.Path         # the checkout, to find other readers by name
    cell: Cell
    net: reference.Network
    driver: object
    trace: xtrace.Trace
    work: tuple | None         # the driver's per-round source events
    lo: float                  # traced window, ns on the profiler clock
    hi: float
    device_kind: str

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) / 1e9

    @property
    def busy_s(self) -> float:
        return self.trace.busy_ns(self.lo, self.hi) / 1e9

    @property
    def peaks(self) -> dict:
        return peaks.peaks_for(self.device_kind)

    def host_ms_per_span(self, name: str) -> float | None:
        """Mean over the window's ``name`` spans of wall time minus the
        device busy time inside the span."""
        spans = self.trace.spans_in(name, self.lo, self.hi)
        if not spans:
            return None
        wall = sum(e - s for s, e in spans)
        busy = self.trace.busy_in_spans_ns(name, self.lo, self.hi)
        return (wall - busy) / len(spans) / 1e6

    def device_ms_per(self, count: int) -> float | None:
        return self.busy_s * 1e3 / count if count else None


class Compiles:
    """Counts lowerings (one per new jit specialization, cache hit or
    not) while armed."""

    KEY = "/jax/core/compile/jaxpr_to_mlir_module_duration"

    def __init__(self):
        import jax

        self.armed, self.count = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, key, duration, **kw):
        if self.armed and key == self.KEY:
            self.count += 1


def enable_cache(root) -> None:
    """JAX's persistent compilation cache: where ``JAX_COMPILATION_CACHE_DIR``
    puts it, else at one fixed path inside the checkout. Sub-second
    programs are cached too."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(pathlib.Path(root) / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def _timed(phases: dict, name: str, fn, *a, **kw):
    t = time.perf_counter()
    out = fn(*a, **kw)
    phases[name] = time.perf_counter() - t
    return out


def main(argv, *, root, started: float, require_tpu: bool = True) -> int:
    """Run one cell once. Returns the exit code."""
    args = parse(argv)
    root = pathlib.Path(root)
    cell = Cell(root, args.workload)
    t = time.perf_counter()
    phases = {"start": t - started}

    import jax

    enable_cache(root)
    phases["import jax"] = time.perf_counter() - t
    t = time.perf_counter()
    devices = jax.devices()
    phases["devices"] = time.perf_counter() - t
    dev = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(devices)}
    if require_tpu and (dev["platform"] != "tpu" or dev["count"] < cell.chips):
        log(f"needs {cell.chips} TPU chip(s); JAX found {dev['count']} "
            f"{dev['platform']} device(s)")
        return 3

    dep = deploy.deploy(root, cell.config, args.seed,
                        cell.traffic.get("frontend"), phases)
    drivers = load.module(root, "drivers", cell.traffic["driver"])
    driver = _timed(phases, "inputs", drivers.Driver, root, dep, cell.traffic,
                    args.seed, args.seconds, bool(args.trace))
    _timed(phases, "warm-up", driver.warm)
    compiles = Compiles()
    trace_dir = root / ".bench_trace" / args.workload
    if args.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    setup_s = time.perf_counter() - started
    compiles.armed = True
    driver.window()
    compiles.armed = False
    if args.trace:
        jax.profiler.stop_trace()
    dev["memory_peak_bytes"] = max(
        int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
        for d in devices[:cell.chips])

    values = driver.values()
    checks = driver.checks()
    work = driver.round_work()
    unanswered = driver.unanswered()
    for line in driver.info():
        log(line)
    log(f"set-up {setup_s:.4f} s: " + ", ".join(
        f"{k} {v:.4f} s" for k, v in phases.items()))
    log(f"compiles inside the window: {compiles.count}")

    # the program's state goes before the reference runs
    net, attempted = dep.net, driver.attempted
    failed = driver.failed + unanswered
    for obj in ("view", "session"):
        setattr(dep, obj, None)
    driver.view = driver.fe = None
    gc.collect()

    t = time.perf_counter()
    numbers = reference.mismatches(
        reference.model(root, net, cell.config), checks,
        cell.config["checks"])
    numbers["unanswered"] = unanswered
    log(f"reference over {len(checks)} streams, "
        f"{sum(c.ext.shape[0] for c in checks)} timesteps: "
        f"{time.perf_counter() - t:.4f} s")
    limits = {k: lim for k, lim in LIMITS.items() if k in numbers}
    correct = all(numbers[k] <= lim for k, lim in limits.items())

    metrics, breakdown = {}, None
    if args.trace:
        tr = xtrace.load(str(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        lo, hi = tr.window()
        obs = Observation(root=root, cell=cell, net=net, driver=driver,
                          trace=tr, work=work, lo=lo, hi=hi,
                          device_kind=dev["kind"])
        dev["busy_s"] = obs.busy_s
        dev["window_s"] = obs.window_s
        for m in cell.per_layer:
            v = load.module(root, "metrics", m["name"]).read(obs)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        breakdown = {"device_ops": tr.op_breakdown(lo, hi),
                     "idle_gaps": tr.idle_gaps(lo, hi)}
    else:
        values["setup_s"] = setup_s
        for m in cell.end_to_end:
            if m["name"] in values:
                metrics[m["name"]] = {"value": float(values[m["name"]]),
                                      "unit": m["unit"]}
    for k, v in metrics.items():
        log(f"{k} = {v['value']!r} {v['unit']}")

    result = {"correct": bool(correct), "attempted": int(attempted),
              "failed": int(failed), "metrics": metrics, "device": dev}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = {k: {"value": int(numbers[k]), "limit": lim}
                        for k, lim in limits.items()}
    for k, lim in limits.items():
        log(f"check {k}: {numbers[k]} (limit {lim})")
    print(json.dumps(result), flush=True)
    return 0
