"""The program's round phases read from a profiler trace (``bench/phases.py``):
the split's arithmetic on made-up intervals, the ``snn.*`` events and their
arguments from a trace recorded on the CPU, nothing at all from a trace of
a program without them, and one traced run of a tiny cell."""

import json
import os
import pathlib
import subprocess
import sys

import pytest

REPO = pathlib.Path(__file__).resolve().parents[2]
for p in (str(REPO), str(REPO / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import phases, xtrace  # noqa: E402

import perfbench_tiny as tiny  # noqa: E402

DATA = pathlib.Path(__file__).resolve().parent / "data"


def _made_up():
    """Two rounds in a window of 0..200 ns; device busy 30..50 (inside
    the first round's read-back) and 150..160 (between rounds)."""
    ops = {"/device:TPU:0": [("%spike_timestep_fused.2 = k", 30, 50),
                             ("%copy = c", 150, 160)]}
    tr = xtrace.Trace(ops=ops, spans={"bench.window": [(0, 200)]})
    program = {
        # two rounds in the window, a third after it that counts for nothing
        "snn.pump": [(0, 100, {}), (100, 140, {}), (300, 400, {})],
        "snn.pump.admit": [(0, 5, {}), (100, 102, {})],
        "snn.pump.gather": [(5, 10, {}), (102, 104, {})],
        "snn.feed": [(10, 80, {})],
        "snn.feed.assemble": [(10, 20, {})],
        "snn.feed.dispatch": [(20, 25, {"h2d_bytes": 3_000_000})],
        "snn.feed.readback": [(25, 60, {"d2h_bytes": 1_000_000})],
        "snn.feed.split": [(60, 75, {})],
        "snn.pump.retire": [(85, 95, {}), (104, 130, {}), (380, 390, {})],
    }
    return tr, program


def test_split_adds_up_to_the_round():
    tr, program = _made_up()
    m = phases.metrics(tr, program, 0, 200)
    per_round = {   # ns of host time over 2 rounds, in ms
        "pump_admit_ms.throughput": 7,
        "pump_gather_ms.throughput": 7,
        "feed_assemble_ms.throughput": 10,
        "feed_dispatch_ms.throughput": 5,
        "feed_readback_ms.throughput": 35 - 20,   # busy 30..50 left out
        "feed_split_ms.throughput": 15,
        "pump_retire_ms.throughput": 36,
    }
    for k, ns in per_round.items():
        assert m[k] == pytest.approx(ns / 2 / 1e6), k
    round_host = (100 + 40 - 20) / 2 / 1e6
    assert m["pump_untraced_ms.throughput"] == pytest.approx(
        round_host - sum(ns for ns in per_round.values()) / 2 / 1e6)
    assert m["host_device_mb.throughput"] == pytest.approx(2.0)
    assert phases.host_ms_per_round(tr, program, "snn.pump", 0, 200) == \
        pytest.approx(round_host)
    # each gap goes to the leaf that overlaps it most, else to "host"
    assert phases.idle_gaps(tr, program, 0, 200) == [
        ["snn.pump.retire", 100e-9], ["host", 40e-9],
        ["snn.feed.assemble", 30e-9]]


def test_no_program_spans_no_split():
    """A trace with no ``snn.pump`` in the window splits nothing, so a
    reader built on it returns nothing rather than raising."""
    tr, program = _made_up()
    assert phases.metrics(tr, program, 150, 200) == {}
    assert phases.metrics(tr, {}, 0, 200) == {}
    assert phases.host_ms_per_round(tr, program, "snn.feed", 0, 200) \
        is not None
    assert phases.host_ms_per_round(tr, {}, "snn.feed", 0, 200) is None


def test_cpu_trace_carries_the_phases_and_their_bytes(tmp_path):
    """A round served under a CPU profiler: ``load`` finds every phase
    with its byte arguments, and the harness's own reduction still sees
    only its ``bench.*`` spans."""
    import jax
    import numpy as np

    from repro.obs.tracing import HOT_SPANS
    from repro.serving.frontend import AsyncSpikeFrontend
    from repro.serving.snn import SpikeServer
    from repro.core.engine import DecaySpec, SpikeEngine

    rng = np.random.default_rng(0)
    n_in, n_phys, slots, chunk = 6, 8, 2, 4
    w = rng.integers(-(1 << 14), 1 << 14, (n_in + n_phys, n_phys))
    engine = SpikeEngine(w.astype(np.int32), n_in,
                         decay=DecaySpec.shift(0.25), threshold_raw=1 << 16,
                         reset_mode="zero", backend="reference")
    fe = AsyncSpikeFrontend(SpikeServer(engine, n_slots=slots,
                                        chunk_steps=chunk), queue_capacity=4)
    for _ in range(slots):
        fe.submit((rng.random((chunk, n_in)) < 0.5).astype(np.int32))
    fe.pump()                       # compiles outside the trace
    for _ in range(slots):
        fe.submit((rng.random((chunk, n_in)) < 0.5).astype(np.int32))
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        fe.pump()
    jax.profiler.stop_trace()

    program = phases.load(str(tmp_path))
    assert set(program) == set(HOT_SPANS)
    assert all(len(v) == 1 for v in program.values())
    (_, _, dispatch), = program["snn.feed.dispatch"]
    (_, _, readback), = program["snn.feed.readback"]
    assert dispatch == {"h2d_bytes": (chunk * slots * n_in
                                      + chunk * slots) * 4}
    assert readback == {"d2h_bytes": chunk * slots * n_phys * 4}
    (ps, pe, _), = program["snn.pump"]
    for name, ((s, e, _),) in program.items():
        assert ps <= s <= e <= pe, name
    tr = xtrace.load(str(tmp_path))
    assert set(tr.spans) == {"bench.window"}
    lo, hi = tr.window()
    m = phases.metrics(tr, program, lo, hi)
    assert set(m) == set(phases.METRICS.values()) | {
        "pump_untraced_ms.throughput", "host_device_mb.throughput"}
    assert m["host_device_mb.throughput"] == pytest.approx(
        (dispatch["h2d_bytes"] + readback["d2h_bytes"]) / 1e6)


def test_parent_chip_trace_has_no_program_spans():
    """The committed chip trace predates the program's spans: nothing to
    split, and the harness's reduction of it reads as it printed."""
    want = json.loads((DATA / "pid64-tick.result.json").read_text())
    program = phases.load(str(DATA / "pid64-tick.xplane.pb"))
    assert program == {}
    tr = xtrace.load(str(DATA / "pid64-tick.xplane.pb"))
    lo, hi = tr.window()
    assert phases.metrics(tr, program, lo, hi) == {}
    assert tr.idle_gaps(lo, hi) == want["breakdown"]["idle_gaps"]


def test_traced_run_of_a_tiny_cell(tmp_path):
    """``run`` on the CPU: one window under the profiler, every phase
    read, the split adding up to the harness's own round host time."""
    root = tiny.make_root(tmp_path)
    keep = tmp_path / "keep"
    code = ("import sys; from bench import phases; sys.exit(phases.main("
            f"sys.argv[1:], root={str(root)!r}, require_tpu=False))")
    p = subprocess.run(
        [sys.executable, "-c", code, "--workload", "tiny-closed",
         "--seed", str(2**31 + 11), "--seconds", "0.5", "--keep", str(keep)],
        capture_output=True, text=True, timeout=600,
        env=dict(os.environ, JAX_PLATFORMS="cpu",
                 PYTHONPATH=os.pathsep.join([str(REPO / "src"), str(REPO)])))
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["rounds"] > 0
    assert out["spans"]["snn.pump"] == out["rounds"]
    split = out["phases"]
    assert len(split) == 9
    # the harness's span encloses the program's, so it reads a little more
    assert 0 <= (out["round_host_ms.throughput"]
                 - sum(v for k, v in split.items()
                       if k.endswith("_ms.throughput"))) < 0.5
    assert json.loads((keep / "tiny-closed.phases.json").read_text()) == out
    assert (keep / "tiny-closed.xplane.pb").is_file()


def test_recorded_backlog_trace():
    """A 0.16 s trace of ``mnist256-backlog`` recorded on a TPU v5e with
    ``bench/phases.py``: the split reads as that run printed, every
    phase reads a number, the bytes a round moves are exact by shape,
    and the phases account for the harness's round within 1 ms."""
    want = json.loads((DATA / "mnist256-backlog-phases.json").read_text())
    path = str(DATA / "mnist256-backlog-phases.xplane.pb")
    tr, program = xtrace.load(path), phases.load(path)
    lo, hi = tr.window()
    m = phases.metrics(tr, program, lo, hi)
    assert m == pytest.approx(want["phases"])
    assert len(m) == 9 and all(v > 0 for v in m.values())
    # (8 x 128 x 784 + 8 x 128) int32 in, 8 x 128 x 1024 int32 out
    assert m["host_device_mb.throughput"] == (
        (8 * 128 * 784 + 8 * 128) * 4 + 8 * 128 * 1024 * 4) / 1e6
    total = sum(v for k, v in m.items() if k.endswith("_ms.throughput"))
    # the harness's round span holds the program's, and reads ~0.1 ms more
    rounds = tr.spans_in("bench.pump", lo, hi)
    assert len(rounds) == want["rounds"] == len(
        phases.spans_in(program, "snn.pump", lo, hi))
    bench_pump = ((sum(e - s for s, e in rounds)
                   - tr.busy_in_spans_ns("bench.pump", lo, hi))
                  / len(rounds) / 1e6)
    assert bench_pump == pytest.approx(want["round_host_ms.throughput"])
    assert 0 < bench_pump - total < 1.0
    assert m["pump_untraced_ms.throughput"] < 0.1 * bench_pump
    assert phases.idle_gaps(tr, program, lo, hi) == want["idle_gaps"]
    assert tr.idle_gaps(lo, hi) == want["harness_idle_gaps"]
    # the kernel keeps its device name under pallas_call(name=...)
    assert want["device_ops"][0][0] == "%spike_timestep_fused.2"
    assert tr.op_time_ns(r"^%spike_timestep_fused(\.\d+)? = ", lo, hi) > 0
