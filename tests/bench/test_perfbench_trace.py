"""The trace reduction and the peak table: interval arithmetic on made-up
intervals, and the whole reduction on a short trace of a 64-plant control
fleet recorded on a TPU v5e, against the numbers that run printed, by the
reduction's own calls and by the control fleet's metric readers."""

import json
import pathlib
import sys

import pytest

REPO = pathlib.Path(__file__).resolve().parents[2]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from bench import harness, load, peaks, xtrace  # noqa: E402

DATA = pathlib.Path(__file__).resolve().parent / "data"


def _trace():
    ops = {"/device:TPU:0": [("%a = x", 10, 20), ("%b = y", 15, 30),
                             ("%spike_timestep_fused.2 = k", 50, 60),
                             ("%a = x", 80, 85)]}
    spans = {"bench.window": [(0, 100)], "bench.pump": [(5, 40), (45, 90)],
             "bench.plant": [(30, 50)]}
    return xtrace.Trace(ops=ops, spans=spans)


def test_merge_and_cover():
    m = xtrace.merge([(5, 7), (1, 3), (2, 4), (7, 8)])
    assert m == [(1, 4), (5, 8)]
    assert xtrace.covered(m, 0, 10) == 6
    assert xtrace.covered(m, 3, 6) == 2
    assert xtrace.covered(m, 3, 6, ends=[4, 8]) == 2


def test_busy_kernel_and_host_time():
    tr = _trace()
    assert tr.window() == (0, 100)
    assert tr.busy_ns(0, 100) == 20 + 10 + 5          # overlap counted once
    assert tr.busy_in_spans_ns("bench.pump", 0, 100) == 20 + 15
    assert tr.op_time_ns(r"^%spike_timestep_fused(\.\d+)? = ", 0, 100) == 10
    assert tr.op_time_ns(r"^%a = ", 0, 82) == 10 + 2  # clipped to the window
    assert tr.op_breakdown(0, 100) == [["%a", 15e-9], ["%b", 15e-9],
                                       ["%spike_timestep_fused.2", 10e-9]]
    # gaps 30..50 (plant), 60..80 (pump), 85..100 (pump), 0..10 (pump)
    assert tr.idle_gaps(0, 100) == [["bench.plant", 20e-9],
                                    ["bench.pump", 20e-9],
                                    ["bench.pump", 15e-9],
                                    ["bench.pump", 10e-9]]


def test_peak_table():
    v5e = peaks.peaks_for("TPU v5 lite")
    assert v5e["int8_ops_per_s"] == 393e12
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(ValueError, match="no published peaks"):
        peaks.peaks_for("cpu")


def test_recorded_chip_trace():
    """The reduction of the committed trace gives the busy time, window and
    per-tick device time that the run on the chip printed."""
    want = json.loads((DATA / "pid64-tick.result.json").read_text())
    tr = xtrace.load(str(DATA / "pid64-tick.xplane.pb"))
    lo, hi = tr.window()
    assert tr.chips == 1
    assert (hi - lo) / 1e9 == pytest.approx(want["device"]["window_s"])
    assert tr.busy_ns(lo, hi) / 1e9 == pytest.approx(want["device"]["busy_s"])
    ticks = len(tr.spans_in("bench.feed", lo, hi))
    assert ticks == want["attempted"]
    per_tick = tr.busy_ns(lo, hi) / 1e6 / ticks
    assert per_tick == pytest.approx(want["metrics"]["tick_device_ms"]["value"])
    kernel = tr.op_time_ns(r"^%spike_timestep_fused(\.\d+)? = ", lo, hi)
    assert 0 < kernel <= tr.busy_ns(lo, hi)
    assert want["breakdown"]["device_ops"] == tr.op_breakdown(lo, hi)


@pytest.mark.parametrize("metric", ["tick_host_ms", "tick_device_ms",
                                    "device_idle_share.tick"])
def test_tick_readers_on_recorded_chip_trace(metric):
    """The fleet's per-layer readers, on the committed trace, give what
    the run on the chip printed (13.2527 ms, 3.8281 ms, 78.223%)."""
    want = json.loads((DATA / "pid64-tick.result.json").read_text())
    tr = xtrace.load(str(DATA / "pid64-tick.xplane.pb"))
    lo, hi = tr.window()

    class Ticks:
        ticks = want["attempted"]

    obs = harness.Observation(root=REPO, cell=None, net=None, driver=Ticks,
                              trace=tr, work=None, lo=lo, hi=hi,
                              device_kind=want["device"]["kind"])
    got = load.module(REPO, "metrics", metric).read(obs)
    assert got == pytest.approx(want["metrics"][metric]["value"])
