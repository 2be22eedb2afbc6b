"""Hot-path span contracts: the served round's phases are the catalogued
``HOT_SPANS``, opened in the order and nesting the catalogue gives, with
the host-device byte counts as arguments; an uncatalogued name is refused;
and a running profiler changes no served spike.

The annotation factory is swapped for a recorder, so no profiler runs
except in the byte-identity test, which runs a real one.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.engine import DecaySpec, SpikeEngine
from repro.obs import tracing
from repro.obs.tracing import HOT_SPANS, SpanTracer, hot_span
from repro.serving.frontend import AsyncSpikeFrontend
from repro.serving.snn import SpikeServer

N_IN, N_PHYS, SLOTS, CHUNK = 10, 16, 2, 3

PARENT = {
    "snn.pump": None,
    "snn.pump.admit": "snn.pump",
    "snn.pump.gather": "snn.pump",
    "snn.feed": "snn.pump",
    "snn.feed.assemble": "snn.feed",
    "snn.feed.dispatch": "snn.feed",
    "snn.feed.readback": "snn.feed",
    "snn.feed.split": "snn.feed",
    "snn.pump.retire": "snn.pump",
}


class Recorder:
    """Stands in for ``jax.profiler.TraceAnnotation``: records each span
    as (name, enclosing span, arguments) in the order opened."""

    def __init__(self):
        self.spans, self._open = [], []

    def __call__(self, name, **args):
        rec = self

        class _Span:
            def __enter__(self):
                rec.spans.append((name, rec._open[-1] if rec._open else None,
                                  args))
                rec._open.append(name)

            def __exit__(self, *exc):
                rec._open.pop()

        return _Span()


@pytest.fixture
def recorder(monkeypatch):
    rec = Recorder()
    monkeypatch.setattr(tracing, "_annotation", rec)
    return rec


def _engine(seed=0):
    rng = np.random.default_rng(seed)
    S = N_IN + N_PHYS
    W = (rng.random((S, N_PHYS)) < 0.4) * rng.integers(-(1 << 13), 1 << 13,
                                                       (S, N_PHYS))
    return SpikeEngine(jnp.asarray(W, jnp.int32), N_IN,
                       decay=DecaySpec.shift(0.25), threshold_raw=1 << 16,
                       reset_mode="subtract", backend="reference")


def _rasters(lengths, seed=1):
    rng = np.random.default_rng(seed)
    return [(rng.random((T, N_IN)) < 0.35).astype(np.int32) for T in lengths]


def test_catalogue_names_the_served_round():
    assert set(HOT_SPANS) == set(PARENT)
    assert len(HOT_SPANS) == len(set(HOT_SPANS))
    assert all(n.startswith("snn.") for n in HOT_SPANS)


@pytest.mark.parametrize("name", ["snn.pumpp", "bench.pump", "chunk_step"])
def test_uncatalogued_hot_span_raises(recorder, name):
    with pytest.raises(ValueError, match="unknown hot span"):
        hot_span(name)
    assert recorder.spans == []


def test_pump_round_emits_every_hot_span_nested(recorder):
    """One round that admits, serves one chunk and retires: every phase
    once, in catalogue order, under the span the catalogue gives, with
    the chunk's host-device bytes and the number of slots retired."""
    server = SpikeServer(_engine(), n_slots=SLOTS, chunk_steps=CHUNK)
    fe = AsyncSpikeFrontend(server, queue_capacity=4)
    for r in _rasters([CHUNK, CHUNK]):
        fe.submit(r)
    summary = fe.pump()
    assert summary["admitted"] == summary["retired"] == SLOTS
    assert [n for n, _, _ in recorder.spans] == list(HOT_SPANS)
    for name, parent, _ in recorder.spans:
        assert parent == PARENT[name], name
    args = {n: a for n, _, a in recorder.spans}
    ext = np.zeros((CHUNK, SLOTS, N_IN), np.int32)
    active = np.zeros((CHUNK, SLOTS), np.int32)
    raster = np.zeros((CHUNK, SLOTS, N_PHYS), np.int32)
    assert args["snn.feed.dispatch"] == {
        "h2d_bytes": ext.nbytes + active.nbytes}
    assert args["snn.feed.readback"] == {"d2h_bytes": raster.nbytes}
    assert args["snn.feed.split"] == {"streams": SLOTS, "chunks": 1}
    assert args["snn.pump.retire"] == {"zeroed": SLOTS}
    assert all(not a for n, a in args.items()
               if n not in ("snn.feed.dispatch", "snn.feed.readback",
                            "snn.feed.split", "snn.pump.retire"))


def test_feed_phases_repeat_per_chunk(recorder):
    """A feed longer than one chunk assembles, dispatches and reads back
    once per chunk, one chunk deep (chunk n+1 dispatched, marked
    ``ahead``, before chunk n is read back), then splits once."""
    server = SpikeServer(_engine(), n_slots=SLOTS, chunk_steps=CHUNK)
    uids = [server.attach(), server.attach()]
    raster_a, raster_b = _rasters([7, 4])
    out = server.feed({uids[0]: raster_a, uids[1]: raster_b})
    assert out[uids[0]]["spikes"].shape == (7, N_PHYS)
    names = [n for n, _, _ in recorder.spans]
    assert names == (["snn.feed", "snn.feed.assemble", "snn.feed.dispatch"]
                     + ["snn.feed.assemble", "snn.feed.dispatch",
                        "snn.feed.readback"] * 2
                     + ["snn.feed.readback", "snn.feed.split"])
    h2d = (CHUNK * SLOTS * N_IN + CHUNK * SLOTS) * 4
    dispatches = [a for n, _, a in recorder.spans if n == "snn.feed.dispatch"]
    assert dispatches == [{"h2d_bytes": h2d}] + [
        {"h2d_bytes": h2d, "ahead": 1}] * 2


@pytest.mark.parametrize("lengths,streams,chunks", [
    ((7, 4), 2, 3),
    ((3, 2), 2, 1),
    ((0, 5), 1, 2),
])
def test_feed_split_counts_streams_and_chunks(recorder, lengths, streams,
                                              chunks):
    """The split says how many streams it served (a zero-length chunk is
    served before it) and over how many read-back chunks."""
    server = SpikeServer(_engine(), n_slots=SLOTS, chunk_steps=CHUNK)
    uids = [server.attach() for _ in lengths]
    server.feed(dict(zip(uids, _rasters(lengths))))
    split = [a for n, _, a in recorder.spans if n == "snn.feed.split"]
    assert split == [{"streams": streams, "chunks": chunks}]


def _served(lengths):
    server = SpikeServer(_engine(), n_slots=SLOTS, chunk_steps=CHUNK)
    fe = AsyncSpikeFrontend(server, queue_capacity=len(lengths))
    handles = [fe.submit(r) for r in _rasters(lengths)]
    fe.drain()
    return [h.result()["spikes"] for h in handles]


def test_rasters_identical_with_profiler_running(tmp_path):
    lengths = (7, 4, 1, 9, 5)
    plain = _served(lengths)
    jax.profiler.start_trace(str(tmp_path / "trace"))
    try:
        traced = _served(lengths)
    finally:
        jax.profiler.stop_trace()
    assert list(tmp_path.rglob("*.xplane.pb"))
    engine = _engine()
    for a, b, r in zip(plain, traced, _rasters(lengths)):
        assert a.dtype == b.dtype and a.tobytes() == b.tobytes()
        want = np.asarray(engine.run(r[:, None, :])["spikes"])[:, 0]
        np.testing.assert_array_equal(a, want)


def test_span_tracer_has_no_profiler_path():
    """Lifecycle spans stay in memory; profiler spans are the catalogue's."""
    with pytest.raises(TypeError):
        SpanTracer(annotate=True)
