"""The control fleet's driver in one process on the CPU, at a tiny size:
the loop is closed and steers every plant to its setpoint, the same seed
gives the same inputs, and the per-chunk work it hands the roofline
reader is what its plants were sent and served."""

import json
import pathlib
import sys

import numpy as np
import pytest

REPO = pathlib.Path(__file__).resolve().parents[2]
for p in (str(REPO), str(REPO / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import deploy, load  # noqa: E402

import perfbench_tiny as tiny  # noqa: E402


def _driver(seed, *, trace=False, plants=4, seconds=0.4, period_ms=10):
    config = json.loads(
        (REPO / "bench/configs/snapv-pid-36.json").read_text())
    config["hardware"]["geometry"] = tiny.TINY_GEOMETRY
    config["engine"] = dict(tiny.TINY_ENGINE, n_slots=plants)
    traffic = dict(tiny.TRAFFIC["tiny-fleet"], plants=plants,
                   sample_plants=plants, period_ms=period_ms)
    dep = deploy.deploy(REPO, config, seed, None)
    drv = load.module(REPO, "drivers", "fleet").Driver(
        REPO, dep, traffic, seed, seconds, trace)
    drv.warm()
    return drv


def test_loop_steers_every_plant():
    """The decoded command moves each plant towards its setpoint: over
    every setpoint's period the error falls."""
    drv = _driver(2**31 + 21)
    every = drv.sp["every_ticks"]
    first, last = [], []
    for _ in range(10 * every):
        t = drv.tick
        drv._step(True)
        err = np.abs(drv.setpoint - drv.x)
        new = (t + drv.phase) % every == 0
        ends = (t + 1 + drv.phase) % every == 0
        first += err[new].tolist()
        last += err[ends].tolist()
    assert len(first) >= 9 * len(drv.uids)
    assert np.mean(last) < 0.5 * np.mean(first)


def test_same_seed_same_inputs():
    a, b = _driver(2**31 + 5), _driver(2**31 + 5)
    c = _driver(2**31 + 6)
    np.testing.assert_array_equal(np.concatenate(a._ext, 1),
                                  np.concatenate(b._ext, 1))
    assert not np.array_equal(np.concatenate(a._ext, 1),
                              np.concatenate(c._ext, 1))


@pytest.mark.parametrize("plants", [3, 4])
def test_round_work_counts_every_plant(plants):
    drv = _driver(2**31 + 9, trace=True, plants=plants)
    drv.window()
    ext_ev, rec_ev, streams = drv.round_work()
    cs, T, w0 = drv.view.server.chunk_steps, drv.T, drv.warm_ticks * drv.T
    ext = np.concatenate(drv._ext, 1).astype(np.int64)
    spk = np.concatenate(drv._spk, 1).astype(np.int64)
    prev = np.concatenate([np.zeros_like(spk[:, :1]), spk[:, :-1]], 1)
    assert drv.ticks > 0 and ext.shape[1] == w0 + drv.ticks * T
    np.testing.assert_array_equal(
        ext_ev, ext[:, w0:].reshape(plants, -1, cs, 2).sum((0, 2)))
    np.testing.assert_array_equal(
        rec_ev, prev[:, w0:].reshape(plants, -1, cs, spk.shape[2])
        .sum((0, 2)))
    assert (streams == plants).all() and len(streams) == drv.ticks * T // cs
    assert rec_ev.sum() > 0


def test_ticks_start_at_most_once_a_period():
    """A tick that ends before the loop period waits for it, so the loop
    rate never passes the deployment's; the rate is every tick of the
    window over its seconds."""
    drv = _driver(2**31 + 13, seconds=0.5, period_ms=200)
    drv.window()
    assert 1 <= drv.ticks <= 3
    assert drv.values()["loop_rate_hz"] == drv.ticks / drv.window_s
    assert len(drv.lat) == drv.ticks
    assert any(line.startswith("garbage collections") for line in drv.info())
