"""Streaming serving parity + lifecycle contracts.

The acceptance criterion of PR 2: for every backend and reset mode,
chunked ``SpikeServer.feed`` over ragged timestep boundaries is
BYTE-for-byte identical to one-shot ``SpikeEngine.run`` on the same
raster — streaming must be a pure re-chunking of the batch semantics,
never a different numerical path. Plus the stream-lifecycle contract:
attach/evict/re-attach churn in some slots leaves co-resident slots'
state bit-for-bit untouched.

The parity and lifecycle cases run for both neuron models (``neuron``):
one-state LIF and current-based LIF, whose synaptic current ``i`` the
slot carries, zeroes and feeds back beside ``v``.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import coding
from repro.core.engine import BACKENDS, DecaySpec, SpikeEngine
from repro.core.lif import LIFParams
from repro.core.network import SNNetwork
from repro.core.session import AcceleratorSession
from repro.serving.snn import SpikeServer

from conftest import assert_slot_state

THRESH = 1 << 16
RESET_MODES = ("zero", "subtract", "hold")
NEURONS = ("lif", "cuba")


def _engine(rng, *, backend="reference", n_in=10, n_phys=16,
            reset="subtract", decay=None, wmax=1 << 13, neuron="lif"):
    S = n_in + n_phys
    W = (rng.random((S, n_phys)) < 0.4) * rng.integers(-wmax, wmax, (S, n_phys))
    syn = DecaySpec.shift(0.75) if neuron == "cuba" else None
    return SpikeEngine(jnp.asarray(W, jnp.int32), n_in,
                       decay=decay or DecaySpec.shift(0.25),
                       threshold_raw=THRESH, reset_mode=reset,
                       backend=backend, syn_decay=syn)


def _raster(rng, T, n_in, p=0.35):
    return (rng.random((T, 1, n_in)) < p).astype(np.int32)


def _feed_ragged(server, uid, raster, sizes):
    """Feed raster (T, n_in) in ragged pieces; return concatenated spikes."""
    assert sum(sizes) == raster.shape[0]
    out, t0 = [], 0
    for n in sizes:
        out.append(server.feed({uid: raster[t0:t0 + n]})[uid]["spikes"])
        t0 += n
    return np.concatenate(out, axis=0)


def _assert_stream_equals_batch(engine, rng, *, sizes=(2, 3, 1, 3),
                                chunk_steps=3, n_slots=3):
    T = sum(sizes)
    raster = _raster(rng, T, engine.n_inputs)
    ref = engine.run(raster)
    want = np.asarray(ref["spikes"])[:, 0]
    server = SpikeServer(engine, n_slots=n_slots, chunk_steps=chunk_steps)
    uid = server.attach()
    got = _feed_ragged(server, uid, raster[:, 0], sizes)
    assert got.dtype == want.dtype == np.int32  # byte-for-byte, not just ==
    np.testing.assert_array_equal(got, want)
    assert_slot_state(server, uid, ref)


# --------------------------------------------------------------------------
# Parity: fast leg (reference backend; every reset mode; ragged chunking)
# --------------------------------------------------------------------------

@pytest.mark.parametrize("neuron", NEURONS)
@pytest.mark.parametrize("reset", RESET_MODES)
def test_feed_chunked_parity_reference(rng, reset, neuron):
    engine = _engine(rng, reset=reset, neuron=neuron)
    _assert_stream_equals_batch(engine, rng)


@pytest.mark.parametrize("neuron", NEURONS)
@pytest.mark.parametrize("sizes", [(9,), (1,) * 9, (4, 5), (1, 6, 2)])
def test_feed_ragged_boundaries(rng, sizes, neuron):
    """Chunk boundaries anywhere — including chunk > chunk_steps (internal
    re-chunking) and T=1 dribble — never change a bit."""
    engine = _engine(rng, neuron=neuron)
    _assert_stream_equals_batch(engine, rng, sizes=sizes)


def test_feed_mul_decay_parity(rng):
    """The Cerebra-S truncating-multiply PDU streams exactly too."""
    engine = _engine(rng, decay=DecaySpec.mul(int(round(0.7 * 65536))))
    _assert_stream_equals_batch(engine, rng)


# --------------------------------------------------------------------------
# Parity: the full sweep — every backend x every reset mode (CI slow leg;
# the driver's tier-1 run executes it unconditionally)
# --------------------------------------------------------------------------

@pytest.mark.slow
@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("reset", RESET_MODES)
def test_feed_parity_sweep(rng, backend, reset):
    engine = _engine(rng, backend=backend, reset=reset)
    _assert_stream_equals_batch(engine, rng)


# --------------------------------------------------------------------------
# Lifecycle: churn isolation, eviction zeroing, admission queue
# --------------------------------------------------------------------------

@pytest.mark.parametrize("neuron", NEURONS)
def test_interleaved_streams_match_solo(rng, neuron):
    """Two streams fed interleaved, ragged, and staggered: each equals its
    solo batch run (slots are independent lanes)."""
    engine = _engine(rng, neuron=neuron)
    ra, rb = _raster(rng, 11, 10), _raster(rng, 11, 10, p=0.5)
    server = SpikeServer(engine, n_slots=4, chunk_steps=3)
    a, b = server.attach(), server.attach()
    ga, gb = [], []
    o = server.feed({a: ra[0:4, 0]})
    ga.append(o[a]["spikes"])
    o = server.feed({a: ra[4:5, 0], b: rb[0:7, 0]})
    ga.append(o[a]["spikes"]); gb.append(o[b]["spikes"])
    o = server.feed({b: rb[7:11, 0], a: ra[5:11, 0]})
    ga.append(o[a]["spikes"]); gb.append(o[b]["spikes"])
    for uid, got, raster in ((a, ga, ra), (b, gb, rb)):
        ref = engine.run(raster)
        np.testing.assert_array_equal(np.concatenate(got, 0),
                                      np.asarray(ref["spikes"])[:, 0])
        assert_slot_state(server, uid, ref)


@pytest.mark.parametrize("neuron", NEURONS)
def test_churn_leaves_coresident_slots_untouched(rng, neuron):
    """attach/evict/re-attach churn around a long-lived stream must not
    perturb it by a single bit."""
    engine = _engine(rng, neuron=neuron)
    T = 12
    keeper_r = _raster(rng, T, 10)
    ref = engine.run(keeper_r)
    want = np.asarray(ref["spikes"])[:, 0]
    server = SpikeServer(engine, n_slots=3, chunk_steps=4)
    keeper = server.attach()
    got = []
    for t in range(T):
        # churn: a transient stream attaches, feeds noise, and is evicted
        # every step while the keeper streams on
        trans = server.attach()
        noise = (rng.random((2, 10)) < 0.6).astype(np.int32)
        server.feed({trans: noise})
        got.append(server.feed({keeper: keeper_r[t:t + 1, 0]})[keeper]["spikes"])
        server.detach(trans)
    np.testing.assert_array_equal(np.concatenate(got, 0), want)
    assert_slot_state(server, keeper, ref)


@pytest.mark.parametrize("neuron", NEURONS)
def test_eviction_zeroes_carry_and_reattach_is_fresh(rng, neuron):
    """Detach zeroes the slot; the next occupant of the SAME slot powers
    up from the unified initial state (bit-identical to a fresh server)."""
    engine = _engine(rng, neuron=neuron)
    raster = _raster(rng, 9, 10)
    want = np.asarray(engine.run(raster)["spikes"])[:, 0]
    server = SpikeServer(engine, n_slots=1, chunk_steps=4)
    a = server.attach()
    server.feed({a: (rng.random((7, 10)) < 0.5).astype(np.int32)})
    server.detach(a)
    assert set(server.carry) == set(engine.carry_keys)
    for k in server.carry:
        np.testing.assert_array_equal(np.asarray(server.carry[k]), 0)
    b = server.attach()
    assert server.slot_of(b) == 0  # same physical slot, recycled
    got = _feed_ragged(server, b, raster[:, 0], (4, 5))
    np.testing.assert_array_equal(got, want)


def test_admission_queue_fifo_and_feed_guard(rng):
    engine = _engine(rng)
    server = SpikeServer(engine, n_slots=1, chunk_steps=2)
    a = server.attach()
    b = server.attach()
    c = server.attach()
    assert server.slot_of(a) == 0
    assert server.slot_of(b) is None and server.slot_of(c) is None
    with pytest.raises(ValueError, match="waiting"):
        server.feed({b: np.zeros((1, 10), np.int32)})
    server.detach(a)
    assert server.slot_of(b) == 0      # FIFO: b before c
    assert server.slot_of(c) is None
    server.detach(b)
    assert server.slot_of(c) == 0


# one call of detach_many on a server whose slots 0-3 hold a-d (fed, so
# their carries are live) while e then f wait: the uids it evicts, in order
DETACH_MANY_CASES = {
    "empty": [],
    "one": ["b"],
    "slotted": ["c", "a"],                # e -> c's slot, f -> a's
    "waiting_mix": ["d", "f", "b"],       # f only waits: withdrawn
    "admitted_then_evicted": ["a", "e"],  # e takes a's slot, then leaves
    "all": ["a", "b", "c", "d", "e", "f"],
    "unknown_uid_raises": ["b", "zz", "c"],  # b's slot still zeroed
}


def _busy_server(engine, rng):
    server = SpikeServer(engine, n_slots=4, chunk_steps=3)
    for uid in "abcdef":
        server.attach(uid)
    server.feed({uid: (rng.random((5, 10)) < 0.5).astype(np.int32)
                 for uid in "abcd"})
    return server


@pytest.mark.parametrize("neuron", NEURONS)
@pytest.mark.parametrize("case", list(DETACH_MANY_CASES))
def test_detach_many_equals_sequential_detach(rng, monkeypatch, case,
                                              neuron):
    """One detach_many is a detach loop with one zeroing dispatch: the
    same carry bytes, slots, FIFO admissions and stats; freed slots read
    zero, the rest are untouched, and the streams admitted into freed
    slots run exactly as from power-on. An empty call dispatches nothing."""
    from repro.serving import snn

    uids = DETACH_MANY_CASES[case]
    # an unknown uid raises where a detach loop would: after those before it
    done = uids[:uids.index("zz")] if "zz" in uids else uids
    engine = _engine(rng, neuron=neuron)
    seq, one = _busy_server(engine, np.random.default_rng(7)), \
        _busy_server(engine, np.random.default_rng(7))
    before = {k: np.asarray(x) for k, x in one.carry.items()}
    freed = {one.slot_of(u) for u in done} - {None}
    want_stats = [seq.detach(u) for u in done]
    calls = []
    zero = snn._zero_slots
    monkeypatch.setattr(snn, "_zero_slots",
                        lambda c, m: calls.append(m.sum()) or zero(c, m))
    if done is uids:
        got_stats = one.detach_many(uids)
        assert [st.uid for st in got_stats] == [st.uid for st in want_stats]
    else:
        with pytest.raises(KeyError):
            one.detach_many(uids)
    assert calls == ([len(freed)] if freed else [])
    assert one.scheduler.active == seq.scheduler.active
    assert one.scheduler.waiting == seq.scheduler.waiting
    assert one.scheduler.free_slot_ids == seq.scheduler.free_slot_ids
    kept = [s for s in range(4) if s not in freed]
    assert set(one.carry) == set(engine.carry_keys)
    for k in one.carry:
        got = np.asarray(one.carry[k])
        assert got.dtype == before[k].dtype == np.int32
        assert got.tobytes() == np.asarray(seq.carry[k]).tobytes()
        np.testing.assert_array_equal(got[sorted(freed)], 0)
        assert got[kept].tobytes() == before[k][kept].tobytes()
    # every slot now holding a stream that was never fed powers up clean
    while one.scheduler.free_slots:
        one.attach()
    fresh = {uid: _raster(rng, 7, 10)[:, 0]
             for uid, slot in one.scheduler.active.items() if slot in freed}
    if fresh:
        out = one.feed(fresh)
    for uid, raster in fresh.items():
        want = np.asarray(engine.run(raster[:, None, :])["spikes"])[:, 0]
        np.testing.assert_array_equal(out[uid]["spikes"], want)


@pytest.mark.parametrize("neuron", NEURONS)
def test_zero_length_chunk_is_per_stream_noop(rng, neuron):
    """T=0 chunks (an idle stream this round) return an empty raster and
    leave the carry untouched — mixed calls still serve the live streams."""
    engine = _engine(rng, neuron=neuron)
    raster = _raster(rng, 8, 10)
    ref = engine.run(raster)
    want = np.asarray(ref["spikes"])[:, 0]
    server = SpikeServer(engine, n_slots=2, chunk_steps=4)
    a, b = server.attach(), server.attach()
    empty = np.zeros((0, 10), np.int32)
    o = server.feed({a: empty})
    assert o[a]["spikes"].shape == (0, 16)
    got = []
    for t0, t1 in ((0, 3), (3, 8)):
        o = server.feed({a: raster[t0:t1, 0], b: empty})
        got.append(o[a]["spikes"])
        assert o[b]["spikes"].shape == (0, 16)
    np.testing.assert_array_equal(np.concatenate(got, 0), want)
    assert server.streams[b].steps == 0
    assert_slot_state(server, a, ref)
    for k in server.carry:          # the idle stream's slot never moved
        assert not np.asarray(server.carry[k])[server.slot_of(b)].any(), k


def _split_per_stream(rasters, streams, chunk_steps, n_phys):
    """The reference split: each stream's slices concatenated out of the
    read-back chunk rasters, then summed on their own, stream by stream."""
    out = {}
    for uid, (slot, T) in streams.items():
        if T == 0:
            out[uid] = {"spikes": np.zeros((0, n_phys), np.int32),
                        "counts": np.zeros((n_phys,), np.int32),
                        "steps": 0, "spike_count": 0}
            continue
        raster = np.concatenate(
            [r[:min(chunk_steps, T - t0), slot]
             for t0, r in zip(range(0, T, chunk_steps), rasters)], axis=0)
        out[uid] = {"spikes": raster, "counts": raster.sum(axis=0),
                    "steps": raster.shape[0],
                    "spike_count": int(raster.sum())}
    return out


@pytest.mark.parametrize("neuron", ["lif", "current-based"])
@pytest.mark.parametrize("chunk_steps", [32, 8], ids=["one-chunk",
                                                       "three-chunks"])
def test_feed_split_matches_per_stream_loop(rng, monkeypatch, neuron,
                                            chunk_steps):
    """``feed``'s one counting pass over all slots returns exactly what
    the per-stream loop returns: rasters, counts and stream stats, values
    and dtypes, for ragged T on non-contiguous slots; and every returned
    raster owns its memory, apart from the read-back buffer and from
    every other stream's raster."""
    n_in, n_phys = 10, 16
    S = n_in + n_phys
    W = (rng.random((S, n_phys)) < 0.4) * rng.integers(-(1 << 15), 1 << 16,
                                                       (S, n_phys))
    syn = {"syn_decay": DecaySpec.shift(0.75)} if neuron != "lif" else {}
    engine = SpikeEngine(jnp.asarray(W, jnp.int32), n_in,
                         decay=DecaySpec.shift(0.25), threshold_raw=THRESH,
                         reset_mode="zero", backend="reference", **syn)
    server = SpikeServer(engine, n_slots=8, chunk_steps=chunk_steps)
    uids = [server.attach() for _ in range(8)]
    for i in (0, 3, 5):
        server.detach(uids[i])
    live = [uids[i] for i in (7, 2, 4, 1, 6)]   # slots out of order

    read_back = []
    step_chunk = engine.step_chunk

    def recording(carry, ext, active):
        carry, spikes = step_chunk(carry, ext, active)
        read_back.append(np.asarray(spikes))
        return carry, spikes

    monkeypatch.setattr(engine, "step_chunk", recording)
    for lengths in ((0, 3, 8, 13, 24), (24, 13, 0, 8, 3)):
        read_back.clear()
        inputs = {uid: _raster(rng, T, n_in)[:, 0]
                  for uid, T in zip(live, lengths)}
        before = {uid: (server.streams[uid].steps,
                        server.streams[uid].spike_count) for uid in live}
        out = server.feed(inputs)
        want = _split_per_stream(
            read_back, {uid: (server.slot_of(uid), x.shape[0])
                        for uid, x in inputs.items()},
            chunk_steps, n_phys)
        assert len(read_back) == -(-max(lengths) // chunk_steps)
        assert sum(w["spike_count"] for w in want.values()) > 0
        assert out.keys() == want.keys()
        for uid, w in want.items():
            for key in ("spikes", "counts"):
                assert out[uid][key].dtype == w[key].dtype, (uid, key)
                assert out[uid][key].shape == w[key].shape, (uid, key)
                assert out[uid][key].tobytes() == w[key].tobytes(), (uid, key)
            st = server.streams[uid]
            assert st.steps - before[uid][0] == w["steps"]
            assert st.spike_count - before[uid][1] == w["spike_count"]
            assert type(st.spike_count) is int
        rasters = [o["spikes"] for o in out.values()]
        for i, a in enumerate(rasters):
            assert not any(np.shares_memory(a, r) for r in read_back)
            assert not any(np.shares_memory(a, b) for b in rasters[i + 1:])


# --------------------------------------------------------------------------
# The one-deep chunk pipeline: a feed of several chunks dispatches chunk
# n+1 before it reads chunk n back
# --------------------------------------------------------------------------

PIPE_CHUNK = 8
# chunk_steps 8: one, one, two, two and three chunks; 3, 11 and 21 end
# mid-chunk
PIPE_LENGTHS = (3, 8, 11, 16, 21)


def _pipe_traffic(rng, lengths=PIPE_LENGTHS, n_in=10):
    return [_raster(rng, T, n_in)[:, 0] for T in lengths]


def _serve_pipe(engine, rasters, *, whole, mesh=None, metrics=None):
    """Serve ``rasters`` on fresh slots, in one feed (``whole``) or one
    chunk of steps per feed call; each stream's raster and counts."""
    server = SpikeServer(engine, n_slots=len(rasters) + 1,
                         chunk_steps=PIPE_CHUNK, mesh=mesh, metrics=metrics)
    uids = [server.attach() for _ in rasters]
    if whole:
        out = server.feed(dict(zip(uids, rasters)))
        return server, {u: (out[u]["spikes"], out[u]["counts"])
                        for u in uids}
    parts = {u: [] for u in uids}
    for t0 in range(0, max(len(r) for r in rasters), PIPE_CHUNK):
        out = server.feed({u: r[t0:t0 + PIPE_CHUNK]
                           for u, r in zip(uids, rasters) if len(r) > t0})
        for u, o in out.items():
            parts[u].append((o["spikes"], o["counts"]))
    return server, {u: (np.concatenate([sp for sp, _ in p]),
                        sum(c for _, c in p)) for u, p in parts.items()}


def _assert_pipe_equivalent(engine, rng, mesh=None):
    rasters = _pipe_traffic(rng)
    whole, got = _serve_pipe(engine, rasters, whole=True, mesh=mesh)
    per_call, want = _serve_pipe(engine, rasters, whole=False, mesh=mesh)
    assert whole.total_steps == per_call.total_steps == sum(PIPE_LENGTHS)
    for (uid, (spikes, counts)), raster in zip(got.items(), rasters):
        w_spikes, w_counts = want[uid]
        for a, b in ((spikes, w_spikes), (counts, w_counts)):
            assert a.dtype == b.dtype and a.shape == b.shape
            assert a.tobytes() == b.tobytes()
        assert spikes.dtype == np.int32 and spikes.any()
        ref = engine.run(raster[:, None, :])
        assert spikes.tobytes() == np.asarray(ref["spikes"])[:, 0].tobytes()
        assert_slot_state(whole, uid, ref)
        assert_slot_state(per_call, uid, ref)
        st, w_st = whole.streams[uid], per_call.streams[uid]
        assert (st.steps, st.spike_count) == (w_st.steps, w_st.spike_count)
    for k in whole.carry:
        assert (np.asarray(whole.carry[k]).tobytes()
                == np.asarray(per_call.carry[k]).tobytes()), k


@pytest.mark.parametrize("neuron", NEURONS)
@pytest.mark.parametrize("backend", ["reference", "pallas"])
def test_pipelined_feed_equals_chunk_per_call(rng, backend, neuron):
    """Ragged streams of one, two and three chunks, some ending
    mid-chunk, fed in one call: byte-identical rasters, counts, stream
    stats and final carries to the same traffic fed one chunk per call,
    and to the one-shot reference run of each stream."""
    engine = _engine(rng, backend=backend, neuron=neuron, wmax=1 << 16)
    _assert_pipe_equivalent(engine, rng)


@pytest.mark.parametrize("neuron", NEURONS)
def test_pipelined_feed_equals_chunk_per_call_mesh(rng, neuron):
    """The mesh server runs the same pipelined loop (degenerate 1x1 mesh,
    so the sharded path runs on one device)."""
    from repro.distributed.spike_mesh import make_spike_mesh

    engine = _engine(rng, neuron=neuron, wmax=1 << 16)
    _assert_pipe_equivalent(engine, rng,
                            mesh=make_spike_mesh(neuron=1, batch=1))


class _Unread:
    """A dispatched raster that logs when the host reads it back."""

    def __init__(self, spikes, i, log):
        self._spikes, self._i, self._log = spikes, i, log
        self.nbytes = spikes.nbytes

    def copy_to_host_async(self):
        self._spikes.copy_to_host_async()

    def __array__(self, dtype=None, copy=None):
        self._log.append(("read", self._i))
        return np.asarray(self._spikes)


def _logged(engine, monkeypatch):
    log = []
    step_chunk = engine.step_chunk

    def dispatch(carry, ext, active):
        carry, spikes = step_chunk(carry, ext, active)
        i = sum(1 for op, _ in log if op == "dispatch")
        log.append(("dispatch", i))
        return carry, _Unread(spikes, i, log)

    monkeypatch.setattr(engine, "step_chunk", dispatch)
    return log


@pytest.mark.parametrize("T_max,n_chunks", [(1, 1), (8, 1), (9, 2),
                                            (24, 3), (25, 4)])
def test_feed_dispatches_next_chunk_before_reading_back(rng, monkeypatch,
                                                        T_max, n_chunks):
    """Chunk n+1 is dispatched before chunk n is read back, chunks are
    read in order, never more than two rasters are unread, and the
    counter of chunks dispatched ahead reads n_chunks - 1."""
    from repro.obs import MetricsRegistry

    engine = _engine(rng)
    reg = MetricsRegistry()
    server = SpikeServer(engine, n_slots=3, chunk_steps=PIPE_CHUNK,
                         metrics=reg)
    a, b = server.attach(), server.attach()
    log = _logged(engine, monkeypatch)
    out = server.feed({a: _raster(rng, T_max, 10)[:, 0],
                       b: _raster(rng, max(1, T_max - 9), 10)[:, 0]})
    assert out[a]["spikes"].shape == (T_max, 16)
    want = [("dispatch", 0)]
    for i in range(1, n_chunks):
        want += [("dispatch", i), ("read", i - 1)]
    assert log == want + [("read", n_chunks - 1)]
    unread = peak = 0
    for op, _ in log:
        unread += 1 if op == "dispatch" else -1
        peak = max(peak, unread)
    assert peak == min(2, n_chunks)
    c = reg.counter
    assert c("snn_server_chunks_total").value == n_chunks
    assert c("snn_server_chunks_ahead_total").value == n_chunks - 1


def test_pump_round_dispatches_nothing_ahead(rng, monkeypatch):
    """A front-door round feeds one chunk: no chunk runs ahead, in a
    round that admits and in one that continues its requests."""
    from repro.obs import MetricsRegistry
    from repro.serving.frontend import AsyncSpikeFrontend

    engine = _engine(rng)
    reg = MetricsRegistry()
    server = SpikeServer(engine, n_slots=2, chunk_steps=PIPE_CHUNK,
                         metrics=reg)
    fe = AsyncSpikeFrontend(server, queue_capacity=4, metrics=reg)
    handles = [fe.submit(_raster(rng, T, 10)[:, 0]) for T in (20, 9, 3)]
    log = _logged(engine, monkeypatch)
    fe.drain()
    for h, T in zip(handles, (20, 9, 3)):
        assert h.result()["spikes"].shape == (T, 16)
    rounds = reg.counter("snn_frontend_rounds_total").value
    assert rounds >= 3
    assert log == [(op, i) for i in range(rounds)
                   for op in ("dispatch", "read")]
    assert reg.counter("snn_server_chunks_total").value == rounds
    assert reg.counter("snn_server_chunks_ahead_total").value == 0


@pytest.mark.parametrize("neuron", NEURONS)
def test_pipelined_feed_telemetry_equals_chunk_per_call(rng, neuron):
    """With metrics on, a feed of three chunks counts the same SOPs,
    spikes, steps, chunks and every other measured-work counter as the
    same traffic fed one chunk per call; only the ahead counter differs,
    and it appears in the live exposition."""
    from repro.obs import MetricsRegistry

    engine = _engine(rng, neuron=neuron, wmax=1 << 16)
    rasters = _pipe_traffic(rng, lengths=(24, 13, 5))
    regs = {whole: MetricsRegistry() for whole in (True, False)}
    for whole, reg in regs.items():
        _serve_pipe(engine, rasters, whole=whole, metrics=reg)
    snaps = {whole: reg.snapshot() for whole, reg in regs.items()}
    for name in ("snn_server_sops_total", "snn_server_spikes_total"):
        assert snaps[True][name]["samples"][0]["value"] > 0, name
    for name, spec in snaps[True].items():
        if spec["type"] != "counter" or name.startswith("snn_frontend"):
            continue
        if name == "snn_server_chunks_ahead_total":
            continue
        assert snaps[True][name] == snaps[False][name], name
    c = {w: reg.counter for w, reg in regs.items()}
    assert c[True]("snn_server_chunks_total").value == 3
    assert c[True]("snn_server_chunks_ahead_total").value == 2
    assert c[False]("snn_server_chunks_ahead_total").value == 0
    text = regs[True].to_prometheus()
    assert "# TYPE snn_server_chunks_ahead_total counter" in text
    assert "\nsnn_server_chunks_ahead_total 2" in text


def test_auto_uid_skips_caller_chosen_ids(rng):
    """Explicit and auto-generated uids coexist on one server."""
    engine = _engine(rng)
    server = SpikeServer(engine, n_slots=4, chunk_steps=2)
    server.attach(0)
    server.attach(2)
    auto1 = server.attach()
    auto2 = server.attach()
    assert len({0, 2, auto1, auto2}) == 4


@pytest.mark.parametrize("neuron", NEURONS)
def test_closed_loop_replay_matches_batch(rng, neuron):
    """Closed-loop stepping with a controller that replays a fixed raster
    is the identity case: byte-identical to the batch scan."""
    engine = _engine(rng, neuron=neuron)
    raster = _raster(rng, 8, 10)
    want = np.asarray(engine.run(raster)["spikes"])[:, 0]
    server = SpikeServer(engine, n_slots=2, chunk_steps=4)
    uid = server.attach()
    step = {"t": 0}

    def controller(spikes_t):
        step["t"] += 1
        return raster[step["t"], 0]

    out = server.run_closed_loop(uid, controller, 8, raster[0, 0])
    np.testing.assert_array_equal(out["spikes"], want)


def test_closed_loop_feedback_depends_on_output(rng):
    """The loop is actually closed: a controller keyed off the spike count
    produces a different input stream than open-loop replay would."""
    engine = _engine(rng, wmax=1 << 15)
    server = SpikeServer(engine, n_slots=1, chunk_steps=2)
    uid = server.attach()
    seen = []

    def controller(spikes_t):
        seen.append(int(spikes_t.sum()))
        # fire the encoder only when the array was quiet at step t
        return np.full((10,), int(spikes_t.sum() == 0), np.int32)

    out = server.run_closed_loop(uid, controller, 10, np.ones(10, np.int32))
    assert out["spikes"].shape == (10, 16)
    assert len(seen) == 9  # output of step t consumed at t+1, none after T


# --------------------------------------------------------------------------
# Session entry: co-resident models stream together over the fused engine
# --------------------------------------------------------------------------

def _net(rng, n_in=6, n_neurons=12, decay_rate=0.25, reset="zero",
         neuron="lif"):
    W = ((rng.random((n_in + n_neurons, n_neurons)) < 0.4)
         * rng.normal(0.0, 0.5, (n_in + n_neurons, n_neurons)))
    return SNNetwork(
        n_inputs=n_in, n_neurons=n_neurons, weights=W.astype(np.float32),
        params=LIFParams(decay_rate=decay_rate, threshold=1.0,
                         reset_mode=reset,
                         syn_decay_rate=0.75 if neuron == "cuba" else None),
        output_slice=(n_neurons - 4, n_neurons))


@pytest.mark.parametrize("neuron", NEURONS)
def test_session_serve_matches_batch_run(rng, neuron):
    """session.serve streaming output == session.run (same key, same
    encoder) for a resident model — counts and predictions identical."""
    sess = AcceleratorSession()
    sess.deploy("m", _net(rng, neuron=neuron))
    import jax
    key = jax.random.key(7)
    intensities = rng.random((1, 6)).astype(np.float32)
    T = 12
    want = sess.run("m", intensities, T, key)

    stream = sess.serve("m", n_slots=2, chunk_steps=5)
    uid = stream.attach()
    ext = np.asarray(coding.poisson_encode(key, intensities, T,
                                           dtype=np.int32))[:, 0]
    got = [stream.feed(uid, ext[0:4]), stream.feed(uid, ext[4:12])]
    counts = got[0]["output_counts"] + got[1]["output_counts"]
    np.testing.assert_array_equal(counts,
                                  np.asarray(want["output_counts"])[0])
    spikes = np.concatenate([g["spikes"] for g in got], axis=0)
    np.testing.assert_array_equal(spikes, np.asarray(want["spikes"])[:, 0])


@pytest.mark.parametrize("neuron", NEURONS)
def test_coresident_models_share_one_server(rng, neuron):
    """Models with one LIF config stream through ONE fused-engine server;
    each stream's decode equals its solo deployment, concurrently."""
    netA = _net(rng, neuron=neuron)
    netB = _net(rng, n_in=5, n_neurons=10, neuron=neuron)
    sess = AcceleratorSession()
    sess.deploy("A", netA)
    sess.deploy("B", netB)
    sA = sess.serve("A", n_slots=3, chunk_steps=4)
    sB = sess.serve("B", n_slots=3, chunk_steps=4)
    assert sA.server is sB.server  # one compiled step for the group

    rA = (rng.random((9, 6)) < 0.4).astype(np.int32)
    rB = (rng.random((9, 5)) < 0.4).astype(np.int32)

    a, b = sA.attach(), sB.attach()
    outA = [sA.feed(a, rA[:4]), sA.feed(a, rA[4:])]
    outB = [sB.feed(b, rB[:6]), sB.feed(b, rB[6:])]

    from repro.core import cerebra_h
    for name, net, raster, outs, view in (("A", netA, rA, outA, sA),
                                          ("B", netB, rB, outB, sB)):
        solo = AcceleratorSession()
        model = solo.deploy(name, net)
        want = cerebra_h.run(model.program, raster[:, None, :])
        counts = sum(o["output_counts"] for o in outs)
        np.testing.assert_array_equal(
            counts, np.asarray(want["output_counts"])[0])
        # physical placement differs (solo deploys at cluster 0; the fused
        # layout offsets later models) but the model's own cluster-range
        # slice must be bit-identical
        lo, hi = view.phys_slice
        slo, shi = (model.cluster_range[0] * 32, model.cluster_range[1] * 32)
        spikes = np.concatenate([o["spikes"] for o in outs], axis=0)
        np.testing.assert_array_equal(
            spikes[:, lo:hi], np.asarray(want["spikes"])[:, 0, slo:shi])


def test_serve_rejects_waiting_and_unknown(rng):
    sess = AcceleratorSession()
    sess.deploy("m", _net(rng))
    stream = sess.serve("m", n_slots=1)
    with pytest.raises(KeyError):
        stream.slot_of("nope")
    with pytest.raises(KeyError):
        sess.serve("ghost")


def test_serve_rejects_mismatched_slot_params(rng):
    """One server per co-resident group: a second serve() with different
    slot parameters must raise, not silently split the carries."""
    sess = AcceleratorSession()
    sess.deploy("a", _net(rng))
    sess.deploy("b", _net(rng, n_in=5, n_neurons=10))
    sess.serve("a", n_slots=2, chunk_steps=4)
    with pytest.raises(ValueError, match="already served"):
        sess.serve("b", n_slots=4, chunk_steps=4)
    assert sess.serve("b", n_slots=2, chunk_steps=4) is not None


def test_closed_loop_rejects_malformed_controller_output(rng):
    """A controller returning the wrong shape fails loudly instead of
    broadcasting across all input lines."""
    engine = _engine(rng)
    server = SpikeServer(engine, n_slots=1, chunk_steps=2)
    uid = server.attach()
    with pytest.raises(ValueError, match="controller must return"):
        server.run_closed_loop(uid, lambda s: 1, 3, np.zeros(10, np.int32))
    sess = AcceleratorSession()
    sess.deploy("m", _net(rng))
    stream = sess.serve("m")
    u2 = stream.attach()
    with pytest.raises(ValueError, match="controller must return"):
        stream.run_closed_loop(u2, lambda s: 1, 3, np.zeros(6, np.int32))


def test_stale_view_raises_after_deploy(rng):
    """deploy() changes the fused layout: an outstanding ModelStream view
    must fail loudly, not stream against the pre-deploy engine."""
    sess = AcceleratorSession()
    sess.deploy("m", _net(rng))
    stream = sess.serve("m", n_slots=2, chunk_steps=4)
    uid = stream.attach()
    stream.feed(uid, np.zeros((2, 6), np.int32))  # fresh view works
    sess.deploy("n", _net(rng, n_in=5, n_neurons=10))
    with pytest.raises(RuntimeError, match="stale"):
        stream.feed(uid, np.zeros((2, 6), np.int32))
    with pytest.raises(RuntimeError, match="stale"):
        stream.attach()
    with pytest.raises(RuntimeError, match="stale"):
        stream.run_closed_loop(uid, lambda s: np.zeros(6, np.int32), 2,
                               np.zeros(6, np.int32))
    fresh = sess.serve("m")  # re-serving after the deploy is the fix
    uid2 = fresh.attach()
    fresh.feed(uid2, np.zeros((2, 6), np.int32))


@pytest.mark.parametrize("neuron", NEURONS)
def test_feed_many_single_dispatch_matches_per_stream(rng, neuron):
    """Batched feed_many over several of a model's streams equals the
    per-stream feed results (one slot-batch dispatch, same bits)."""
    net = _net(rng, neuron=neuron)
    sess_a = AcceleratorSession()
    sess_a.deploy("m", net)
    sess_b = AcceleratorSession()
    sess_b.deploy("m", net)
    va = sess_a.serve("m", n_slots=3, chunk_steps=4)
    vb = sess_b.serve("m", n_slots=3, chunk_steps=4)
    r1 = (rng.random((7, 6)) < 0.4).astype(np.int32)
    r2 = (rng.random((7, 6)) < 0.5).astype(np.int32)
    a1, a2 = va.attach(), va.attach()
    b1, b2 = vb.attach(), vb.attach()
    batched = va.feed_many({a1: r1, a2: r2})
    solo = {b1: vb.feed(b1, r1), b2: vb.feed(b2, r2)}
    np.testing.assert_array_equal(batched[a1]["spikes"], solo[b1]["spikes"])
    np.testing.assert_array_equal(batched[a2]["spikes"], solo[b2]["spikes"])
    np.testing.assert_array_equal(batched[a1]["output_counts"],
                                  solo[b1]["output_counts"])


@pytest.mark.parametrize("neuron", NEURONS)
def test_model_stream_closed_loop_replay(rng, neuron):
    """ModelStream.run_closed_loop (session-level closed loop): replaying
    a fixed encoder stream equals the batch run of the same raster."""
    sess = AcceleratorSession()
    model = sess.deploy("m", _net(rng, neuron=neuron))
    stream = sess.serve("m", n_slots=2, chunk_steps=4)
    uid = stream.attach()
    raster = (rng.random((6, 6)) < 0.4).astype(np.int32)
    step = {"t": 0}

    def controller(local_spikes):
        step["t"] += 1
        return raster[step["t"]]

    got = stream.run_closed_loop(uid, controller, 6, raster[0])
    from repro.core import cerebra_h
    want = cerebra_h.run(model.program, raster[:, None, :])
    np.testing.assert_array_equal(got["output_counts"],
                                  np.asarray(want["output_counts"])[0])
    lo, hi = stream.phys_slice
    np.testing.assert_array_equal(got["spikes"][:, lo:hi],
                                  np.asarray(want["spikes"])[:, 0, lo:hi])


# --------------------------------------------------------------------------
# Engine chunk-step contract details
# --------------------------------------------------------------------------

def test_step_chunk_shape_validation(rng):
    engine = _engine(rng)
    carry = engine.init_carry(2)
    with pytest.raises(ValueError, match="ext must be"):
        engine.step_chunk(carry, np.zeros((3, 2, 7), np.int32))
    with pytest.raises(ValueError, match="active mask"):
        engine.step_chunk(carry, np.zeros((3, 2, 10), np.int32),
                          np.zeros((3, 3), np.int32))


@pytest.mark.parametrize("neuron", NEURONS)
def test_step_chunk_all_active_equals_run(rng, neuron):
    """active=None (or all-ones) is exactly the batch scan."""
    engine = _engine(rng, neuron=neuron)
    ext = (rng.random((6, 4, 10)) < 0.4).astype(np.int32)
    want = engine.run(ext)
    carry, spikes = engine.step_chunk(engine.init_carry(4), ext)
    np.testing.assert_array_equal(np.asarray(spikes),
                                  np.asarray(want["spikes"]))
    for k in set(carry) - {"spikes"}:
        np.testing.assert_array_equal(np.asarray(carry[k]),
                                      np.asarray(want[f"{k}_final"]))


def test_step_chunk_jit_cache_reused(rng):
    engine = _engine(rng)
    ext = (rng.random((4, 2, 10)) < 0.4).astype(np.int32)
    engine.step_chunk(engine.init_carry(2), ext)
    compiled = engine._chunk_jit
    assert compiled is not None
    engine.step_chunk(engine.init_carry(2), ext)
    assert engine._chunk_jit is compiled
