"""Async front-door contracts: the queue changes WHEN, never WHAT.

The acceptance criterion of the frontend: for the same realized admission
order, ``AsyncSpikeFrontend``-served rasters are byte-identical to direct
synchronous ``SpikeServer.feed`` / one-shot ``SpikeEngine.run`` of each
request's full raster, for every backend x reset mode x gate (full sweep
under ``slow``; the mesh cross is in tests/test_spike_mesh.py). Plus the
front-door lifecycle contracts: cancel-while-queued never touches the
server; deadline expiry mid-stream zeroes the slot carry exactly like any
eviction; backpressure policies do what they say; and admission order +
slot assignment is a deterministic function of the submit/cancel/pump
sequence (hypothesis property with deterministic companions).

With a carry connector attached (spill-on-evict), mid-stream expiry PARKS
the stream instead of killing it: ``resume()`` must continue it
byte-identically to a never-spilled run, cancel-while-parked must never
touch the server, and the determinism property extends over the
detach/attach (spill/resume) ops.

The contracts that carry, zero or restore a slot's state run for both
neuron models (``neuron``): one-state LIF and current-based LIF, whose
synaptic current ``i`` lives in the slot beside ``v``.
"""

import hypothesis
import hypothesis.strategies as st
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.engine import BACKENDS, GATES, DecaySpec, SpikeEngine
from repro.core.session import AcceleratorSession
from repro.serving.frontend import (AsyncSpikeFrontend, FrontendConfig,
                                    latency_percentiles)
from repro.serving.snn import SpikeServer

from conftest import assert_slot_state, make_random_net

THRESH = 1 << 16
RESET_MODES = ("zero", "subtract", "hold")
NEURONS = ("lif", "cuba")


class VirtualClock:
    """Deterministic frontend clock: advances only when told to."""

    def __init__(self):
        self.t = 0.0

    def __call__(self) -> float:
        return self.t


def _engine(rng, *, backend="reference", reset="subtract", gate="batch-tile",
            n_in=10, n_phys=16, wmax=1 << 13, neuron="lif"):
    S = n_in + n_phys
    W = ((rng.random((S, n_phys)) < 0.4)
         * rng.integers(-wmax, wmax, (S, n_phys)))
    syn = DecaySpec.shift(0.75) if neuron == "cuba" else None
    return SpikeEngine(jnp.asarray(W, jnp.int32), n_in,
                       decay=DecaySpec.shift(0.25), threshold_raw=THRESH,
                       reset_mode=reset, backend=backend, gate=gate,
                       syn_decay=syn)


def _rasters(rng, lengths, n_in, p=0.35):
    return [(rng.random((T, n_in)) < p).astype(np.int32) for T in lengths]


# --------------------------------------------------------------------------
# Async-vs-synchronous bit-identity
# --------------------------------------------------------------------------

def _assert_async_equals_sync(engine, rng, *, n_slots=2, chunk_steps=3,
                              lengths=(7, 4, 1, 9, 5)):
    """Everything submitted through the frontend must come back
    byte-identical to a one-shot run of its raster (which PR 2 pinned
    equal to synchronous ``feed``)."""
    rasters = _rasters(rng, lengths, engine.n_inputs)
    server = SpikeServer(engine, n_slots=n_slots, chunk_steps=chunk_steps)
    fe = AsyncSpikeFrontend(server, queue_capacity=len(rasters))
    handles = [fe.submit(r) for r in rasters]
    m = fe.drain()
    assert m["counts"]["done"] == len(rasters)
    for h, r in zip(handles, rasters):
        want = np.asarray(engine.run(r[:, None, :])["spikes"])[:, 0]
        got = h.result()["spikes"]
        assert got.dtype == want.dtype == np.int32
        np.testing.assert_array_equal(got, want)
        assert "partial" not in h.result()


@pytest.mark.parametrize("neuron", NEURONS)
@pytest.mark.parametrize("reset", RESET_MODES)
def test_async_bit_identity_reference(rng, reset, neuron):
    _assert_async_equals_sync(_engine(rng, reset=reset, neuron=neuron), rng)


@pytest.mark.parametrize("neuron", NEURONS)
def test_async_bit_identity_per_example_gate(rng, neuron):
    _assert_async_equals_sync(
        _engine(rng, gate="per-example", neuron=neuron), rng)


@pytest.mark.slow
@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("reset", RESET_MODES)
@pytest.mark.parametrize("gate", GATES)
def test_async_bit_identity_sweep(rng, backend, reset, gate):
    engine = _engine(rng, backend=backend, reset=reset, gate=gate)
    _assert_async_equals_sync(engine, rng)


@pytest.mark.parametrize("neuron", NEURONS)
def test_async_matches_direct_feed_same_admission_order(rng, neuron):
    """The literal acceptance phrasing: replay the REALIZED admission
    order synchronously through ``SpikeServer.feed`` and compare bytes."""
    engine = _engine(rng, neuron=neuron)
    rasters = _rasters(rng, (6, 3, 5), engine.n_inputs)
    server = SpikeServer(engine, n_slots=2, chunk_steps=2)
    fe = AsyncSpikeFrontend(server, queue_capacity=8)
    handles = [fe.submit(r) for r in rasters]
    order = []          # realized admission order, by request index
    while not fe.idle:
        before = {h.rid for h in handles if h.state == "queued"}
        fe.pump()
        after = {h.rid for h in handles if h.state == "queued"}
        order += sorted(before - after)
    sync_server = SpikeServer(engine, n_slots=2, chunk_steps=2)
    for rid in order:
        uid = sync_server.attach()
        got = sync_server.feed({uid: rasters[rid]})[uid]["spikes"]
        sync_server.detach(uid)
        np.testing.assert_array_equal(handles[rid].result()["spikes"], got)


# --------------------------------------------------------------------------
# Lifecycle: cancel, deadlines, carry zeroing
# --------------------------------------------------------------------------

def test_cancel_while_queued_never_touches_server(rng):
    engine = _engine(rng)
    server = SpikeServer(engine, n_slots=1, chunk_steps=2)
    fe = AsyncSpikeFrontend(server, queue_capacity=4)
    a, b = (fe.submit(r) for r in _rasters(rng, (4, 4), engine.n_inputs))
    fe.pump()  # a admitted + fed; b still queued
    assert a.state == "running" and b.state == "queued"
    assert b.cancel() is True
    assert b.state == "cancelled" and b.result() is None
    assert fe.queue_depth == 0
    assert len(server.scheduler.active) == 1  # only a ever reached a slot
    assert b.cancel() is False  # terminal: too late
    fe.drain()
    assert a.state == "done"


@pytest.mark.parametrize("neuron", NEURONS)
def test_cancel_mid_stream_keeps_partial_and_zeroes_carry(rng, neuron):
    engine = _engine(rng, neuron=neuron)
    server = SpikeServer(engine, n_slots=1, chunk_steps=2)
    fe = AsyncSpikeFrontend(server, queue_capacity=2)
    raster = _rasters(rng, (8,), engine.n_inputs)[0]
    h = fe.submit(raster)
    fe.pump()
    assert h.state == "running" and h.poll()["steps_done"] == 2
    assert h.cancel() is True
    res = h.result()
    assert res["partial"] is True and res["spikes"].shape[0] == 2
    want = np.asarray(engine.run(raster[:2, None, :])["spikes"])[:, 0]
    np.testing.assert_array_equal(res["spikes"], want)
    # eviction semantics: the freed slot is power-on clean
    assert set(server.carry) == set(engine.carry_keys)
    for k in server.carry:
        assert int(np.abs(np.asarray(server.carry[k])).sum()) == 0, k


@pytest.mark.parametrize("neuron", NEURONS)
def test_deadline_expiry_queued_vs_mid_stream(rng, neuron):
    """A queued request past its deadline is refused; a running one is
    evicted with the slot carry zeroed like any eviction, and the next
    occupant powers up from clean state (byte-identical to a fresh run)."""
    engine = _engine(rng, neuron=neuron)
    server = SpikeServer(engine, n_slots=1, chunk_steps=2)
    clock = VirtualClock()
    fe = AsyncSpikeFrontend(server, queue_capacity=4, clock=clock)
    ra, rb, rc = _rasters(rng, (8, 8, 6), engine.n_inputs)
    a = fe.submit(ra, deadline_ms=1_000)   # will expire mid-stream
    b = fe.submit(rb, deadline_ms=1_000)   # will expire while queued
    c = fe.submit(rc)                      # no deadline: must run clean
    fe.pump()
    assert a.state == "running" and b.state == "queued"
    clock.t = 2.0  # both deadlines (t=1.0) now past
    fe.pump()
    assert a.state == "expired" and b.state == "expired"
    assert a.result()["partial"] is True   # kept what was served
    assert b.result() is None              # never consumed a timestep
    m = fe.metrics()["counts"]
    assert m["expired_running"] == 1 and m["expired_queued"] == 1
    fe.drain()
    want = np.asarray(engine.run(rc[:, None, :])["spikes"])[:, 0]
    np.testing.assert_array_equal(c.result()["spikes"], want)


@pytest.mark.parametrize("neuron", NEURONS)
@pytest.mark.parametrize("n_done", [1, 3, 4])
def test_pump_round_zeroes_its_retirees_in_one_dispatch(rng, n_done, neuron):
    """A round that retires n_done of 4 streams zeroes their slots with
    ONE dispatch (the registry's reset counters say so), and the queued
    requests that take those slots power up from zero."""
    from repro.obs import MetricsRegistry

    engine = _engine(rng, neuron=neuron)
    reg = MetricsRegistry()
    server = SpikeServer(engine, n_slots=4, chunk_steps=2, metrics=reg)
    fe = AsyncSpikeFrontend(server, queue_capacity=8)
    lengths = [2] * n_done + [6] * (4 - n_done) + [5, 3]
    rasters = _rasters(rng, lengths, engine.n_inputs)
    handles = [fe.submit(r) for r in rasters]
    resets = reg.counter("snn_server_slot_resets_total")
    dispatches = reg.counter("snn_server_slot_reset_dispatches_total")
    assert fe.pump()["retired"] == n_done
    assert (resets.value, dispatches.value) == (n_done, 1)
    rounds = 1
    while not fe.idle:
        retired = fe.pump()["retired"]
        rounds += bool(retired)
    assert (resets.value, dispatches.value) == (len(lengths), rounds)
    for h, r in zip(handles, rasters):
        want = np.asarray(engine.run(r[:, None, :])["spikes"])[:, 0]
        np.testing.assert_array_equal(h.result()["spikes"], want)


# --------------------------------------------------------------------------
# Backpressure policies
# --------------------------------------------------------------------------

def test_backpressure_reject(rng):
    engine = _engine(rng)
    server = SpikeServer(engine, n_slots=1, chunk_steps=2)
    fe = AsyncSpikeFrontend(server, queue_capacity=1, backpressure="reject")
    ra, rb = _rasters(rng, (4, 4), engine.n_inputs)
    a = fe.submit(ra)
    b = fe.submit(rb)
    assert a.state == "queued" and b.state == "rejected"
    assert b.result() is None and b.done
    fe.drain()
    assert a.state == "done"
    assert fe.metrics()["counts"]["rejected"] == 1


def test_backpressure_drop_oldest(rng):
    engine = _engine(rng)
    server = SpikeServer(engine, n_slots=1, chunk_steps=2)
    fe = AsyncSpikeFrontend(server, queue_capacity=1,
                            backpressure="drop-oldest")
    ra, rb = _rasters(rng, (4, 4), engine.n_inputs)
    a = fe.submit(ra)
    b = fe.submit(rb)
    assert a.state == "dropped" and b.state == "queued"
    fe.drain()
    assert b.state == "done"
    counts = fe.metrics()["counts"]
    assert counts["dropped"] == 1 and counts["done"] == 1


@pytest.mark.parametrize("neuron", NEURONS)
def test_backpressure_block_pumps_until_space(rng, neuron):
    engine = _engine(rng, neuron=neuron)
    server = SpikeServer(engine, n_slots=1, chunk_steps=4)
    fe = AsyncSpikeFrontend(server, queue_capacity=1, backpressure="block")
    ra, rb = _rasters(rng, (4, 4), engine.n_inputs)
    a = fe.submit(ra)
    b = fe.submit(rb)  # queue full: submit itself pumps the loop
    assert b.state == "queued"
    assert a.state in ("running", "done")  # progress was forced
    fe.drain()
    assert a.state == "done" and b.state == "done"
    want = np.asarray(engine.run(rb[:, None, :])["spikes"])[:, 0]
    np.testing.assert_array_equal(b.result()["spikes"], want)


def test_constructor_validation(rng):
    engine = _engine(rng)
    server = SpikeServer(engine, n_slots=1)
    with pytest.raises(ValueError, match="backpressure"):
        AsyncSpikeFrontend(server, backpressure="explode")
    with pytest.raises(ValueError, match="queue_capacity"):
        AsyncSpikeFrontend(server, queue_capacity=0)
    with pytest.raises(ValueError, match="deadline_ms"):
        AsyncSpikeFrontend(server, deadline_ms=0)
    fe = AsyncSpikeFrontend(server)
    with pytest.raises(ValueError, match="chunk must be"):
        fe.submit(np.zeros((3, engine.n_inputs + 1), np.int32))
    with pytest.raises(ValueError, match="at least 1 timestep"):
        fe.submit(np.zeros((0, engine.n_inputs), np.int32))


# --------------------------------------------------------------------------
# Determinism: admission order + slot assignment from the op sequence
# --------------------------------------------------------------------------

def _run_scenario(engine, lengths, cancel_at, n_slots, chunk_steps,
                  capacity, policy):
    """One full frontend run; returns the observable trace: per-round
    (admitted rid -> slot) plus every request's terminal state + bytes."""
    rng = np.random.default_rng(7)
    rasters = _rasters(rng, lengths, engine.n_inputs)
    server = SpikeServer(engine, n_slots=n_slots, chunk_steps=chunk_steps)
    fe = AsyncSpikeFrontend(server, queue_capacity=capacity,
                            backpressure=policy)
    handles, trace = [], []
    for i, r in enumerate(rasters):
        handles.append(fe.submit(r))
        if i in cancel_at:
            handles[-1].cancel()
    rid_of_uid = {}
    while not fe.idle:
        fe.pump()
        for h in handles:
            uid = h._req.uid
            if uid is not None and uid not in rid_of_uid:
                rid_of_uid[uid] = h.rid
        trace.append(sorted((rid_of_uid[u], s)
                            for u, s in server.scheduler.active.items()))
    states = [h.state for h in handles]
    bytes_out = [None if h.result() is None
                 else h.result()["spikes"].tobytes() for h in handles]
    return trace, states, bytes_out


@pytest.mark.parametrize("neuron", NEURONS)
def test_admission_determinism_deterministic_companion(rng, neuron):
    engine = _engine(rng, neuron=neuron)
    kw = dict(lengths=(5, 3, 7, 2, 6), cancel_at={2}, n_slots=2,
              chunk_steps=3, capacity=3, policy="drop-oldest")
    assert (_run_scenario(engine, **kw) == _run_scenario(engine, **kw))


@hypothesis.given(
    seed=st.integers(0, 2**32 - 1),
    n_slots=st.integers(1, 3),
    chunk_steps=st.integers(1, 4),
    capacity=st.integers(1, 5),
    policy=st.sampled_from(("reject", "drop-oldest")),
)
@hypothesis.settings(max_examples=25, deadline=None)
def test_admission_determinism_property(seed, n_slots, chunk_steps,
                                        capacity, policy):
    """Admission order and slot assignment are a pure function of the
    submit/cancel/pump sequence — replaying it reproduces the identical
    trace and identical output bytes."""
    rng = np.random.default_rng(seed)
    engine = _engine(np.random.default_rng(0))
    lengths = tuple(int(t) for t in rng.integers(1, 8, rng.integers(1, 7)))
    cancel_at = set(rng.integers(0, len(lengths),
                                 rng.integers(0, len(lengths))).tolist())
    kw = dict(lengths=lengths, cancel_at=cancel_at, n_slots=n_slots,
              chunk_steps=chunk_steps, capacity=capacity, policy=policy)
    assert (_run_scenario(engine, **kw) == _run_scenario(engine, **kw))


# --------------------------------------------------------------------------
# Spill-on-evict: deadline expiry parks the carry, resume continues it
# --------------------------------------------------------------------------

def _spill_frontend(rng, *, n_slots=1, chunk_steps=2, capacity=4,
                    neuron="lif"):
    from repro.serving.connector import InMemoryCarryConnector

    engine = _engine(rng, neuron=neuron)
    server = SpikeServer(engine, n_slots=n_slots, chunk_steps=chunk_steps)
    clock = VirtualClock()
    conn = InMemoryCarryConnector()
    fe = AsyncSpikeFrontend(server, queue_capacity=capacity, clock=clock,
                            connector=conn)
    return engine, server, clock, conn, fe


@pytest.mark.parametrize("neuron", NEURONS)
def test_spill_resume_bit_clean(rng, neuron):
    """The spill contract: a mid-stream deadline eviction with a
    connector parks the carry; resume() finishes the stream and the FULL
    raster is byte-identical to a never-spilled run — no 'partial'."""
    engine, server, clock, conn, fe = _spill_frontend(rng, neuron=neuron)
    raster = _rasters(rng, (10,), engine.n_inputs)[0]
    want = np.asarray(engine.run(raster[:, None, :])["spikes"])[:, 0]

    h = fe.submit(raster, deadline_ms=1_000)
    fe.pump()                      # 2 of 10 steps served
    assert h.state == "running"
    clock.t = 2.0                  # deadline (t=1.0) passes mid-stream
    fe.pump()
    assert h.state == "parked" and not h.done
    assert h.result() is None      # parked is NOT terminal
    assert len(conn) == 1 and server.scheduler.free_slots == 1
    # the parked carry is every state after the 2 served steps
    assert_slot_state(server, None, engine.run(raster[:2, None, :]),
                      carry=conn.select(conn.stream_ids()[0]).arrays)

    assert fe.resume(h) is True
    assert h.state == "queued"
    fe.pump()                      # resumed: 2 more steps from that carry
    assert_slot_state(server, h._req.uid, engine.run(raster[:4, None, :]))
    fe.drain()
    assert h.state == "done"
    res = h.result()
    assert "partial" not in res
    np.testing.assert_array_equal(res["spikes"], want)
    assert len(conn) == 0          # admission consumed the parked carry
    c = fe.metrics()["counts"]
    assert (c["parked"], c["resumed"], c["done"]) == (1, 1, 1)
    assert c["expired_running"] == 0  # documented keys are always present


@pytest.mark.parametrize("neuron", NEURONS)
def test_spill_interleaves_with_other_traffic(rng, neuron):
    """Another request runs in the spilled stream's slot between spill
    and resume — the resumed stream must still come back bit-clean (its
    state lived in the connector, not the slot)."""
    engine, server, clock, conn, fe = _spill_frontend(rng, neuron=neuron)
    ra, rb = _rasters(rng, (8, 4), engine.n_inputs)
    want_a = np.asarray(engine.run(ra[:, None, :])["spikes"])[:, 0]

    a = fe.submit(ra, deadline_ms=1_000)
    fe.pump()
    clock.t = 2.0
    fe.pump()                      # a parked; its slot is free
    b = fe.submit(rb)              # b claims (and dirties) that slot
    fe.drain()
    assert b.state == "done" and a.state == "parked"
    assert fe.resume(a) is True
    fe.drain()
    np.testing.assert_array_equal(a.result()["spikes"], want_a)
    assert "partial" not in a.result()


def test_cancel_while_parked_never_touches_server(rng):
    engine, server, clock, conn, fe = _spill_frontend(rng)
    h = fe.submit(_rasters(rng, (8,), engine.n_inputs)[0], deadline_ms=500)
    fe.pump()
    clock.t = 1.0
    fe.pump()
    assert h.state == "parked"
    steps_before = server.total_steps
    active_before = dict(server.scheduler.active)

    assert h.cancel() is True
    assert h.state == "cancelled" and h.done
    assert len(conn) == 0                       # spilled carry evicted
    assert server.total_steps == steps_before   # server never touched
    assert dict(server.scheduler.active) == active_before
    assert fe.resume(h) is False                # terminal: too late
    assert h.cancel() is False


@pytest.mark.parametrize("neuron", NEURONS)
def test_parked_request_requeued_past_deadline_returns_to_parked(rng,
                                                                 neuron):
    """resume() arms a fresh deadline; if THAT passes while the request
    is still queued, it falls back to 'parked' (carry stays in the
    connector, no leak) and a later resume still finishes bit-clean."""
    engine, server, clock, conn, fe = _spill_frontend(rng, neuron=neuron)
    raster = _rasters(rng, (8,), engine.n_inputs)[0]
    want = np.asarray(engine.run(raster[:, None, :])["spikes"])[:, 0]

    blocker = fe.submit(_rasters(rng, (6,), engine.n_inputs)[0])
    h = fe.submit(raster, deadline_ms=1_000)
    fe.pump()                      # blocker holds the only slot
    assert blocker.state == "running" and h.state == "queued"
    clock.t = 2.0
    fe.pump()                      # h expires while QUEUED, never parked
    assert h.state == "expired"    # no carry existed -> plain refusal

    h2 = fe.submit(raster, deadline_ms=2_000)
    fe.drain(max_rounds=2)         # blocker finishes; h2 runs a quantum
    assert h2.state == "running"
    clock.t = 5.0
    fe.pump()
    assert h2.state == "parked"
    fe.resume(h2, deadline_ms=1_000)
    clock.t = 99.0                 # fresh deadline passes while queued
    blocker2 = fe.submit(_rasters(rng, (2,), engine.n_inputs)[0])
    fe.pump()
    assert h2.state == "parked" and len(conn) == 1  # back to parked
    assert fe.resume(h2) is True   # no deadline this time
    fe.drain()
    assert blocker2.state == "done" and h2.state == "done"
    np.testing.assert_array_equal(h2.result()["spikes"], want)


@pytest.mark.parametrize("neuron", NEURONS)
def test_resume_under_reject_backpressure_stays_parked(rng, neuron):
    engine, server, clock, conn, fe = _spill_frontend(rng, capacity=1,
                                                      neuron=neuron)
    h = fe.submit(_rasters(rng, (8,), engine.n_inputs)[0], deadline_ms=500)
    fe.pump()
    clock.t = 1.0
    fe.pump()
    assert h.state == "parked"
    filler = fe.submit(_rasters(rng, (9,), engine.n_inputs)[0])
    fe.pump()                      # filler admitted -> queue has room...
    blocker = fe.submit(_rasters(rng, (9,), engine.n_inputs)[0])
    assert blocker.state == "queued"
    assert fe.resume(h) is False   # ...but now it is full again: reject
    assert h.state == "parked" and len(conn) == 1
    fe.drain()
    assert fe.resume(h) is True    # room now; the carry waited it out
    fe.drain()
    assert h.state == "done"


@pytest.mark.parametrize("neuron", NEURONS)
def test_determinism_extends_over_spill_resume_ops(rng, neuron):
    """The determinism contract extended over detach/attach: with spill
    and resume in the op sequence, replaying it reproduces identical
    states, counts, and output bytes."""
    def run():
        from repro.serving.connector import InMemoryCarryConnector

        r = np.random.default_rng(13)
        engine = _engine(np.random.default_rng(5), neuron=neuron)
        server = SpikeServer(engine, n_slots=2, chunk_steps=2)
        clock = VirtualClock()
        fe = AsyncSpikeFrontend(server, queue_capacity=6, clock=clock,
                                connector=InMemoryCarryConnector())
        lengths = (9, 7, 8, 3, 6)
        # the first two carry tight deadlines (they will spill + resume,
        # possibly repeatedly); the rest run undisturbed alongside them
        handles = [fe.submit(rr, deadline_ms=(2_000 if i < 2 else None))
                   for i, rr in
                   enumerate(_rasters(r, lengths, engine.n_inputs))]
        states = []
        for _ in range(40):
            if fe.idle and not any(h.state == "parked" for h in handles):
                break
            clock.t += 1.1          # every ~2nd quantum crosses a deadline
            fe.pump()
            for h in handles:
                if h.state == "parked":
                    fe.resume(h, deadline_ms=4_000)
            states.append(tuple(h.state for h in handles))
        outs = [None if h.result() is None
                else h.result()["spikes"].tobytes() for h in handles]
        return states, outs, dict(fe.counts)

    a, b = run(), run()
    assert a == b
    states, outs, counts = a
    assert counts.get("parked", 0) > 0      # the scenario really spilled
    assert counts["done"] == 5              # and everyone finished
    # every raster byte-identical to its never-spilled run
    r = np.random.default_rng(13)
    engine = _engine(np.random.default_rng(5), neuron=neuron)
    for raster, got in zip(_rasters(r, (9, 7, 8, 3, 6), engine.n_inputs),
                           outs):
        want = np.asarray(engine.run(raster[:, None, :])["spikes"])[:, 0]
        assert got == want.tobytes()


# --------------------------------------------------------------------------
# AER requests + session wiring
# --------------------------------------------------------------------------

@pytest.mark.parametrize("neuron", NEURONS)
def test_submit_events_round_trip(rng, neuron):
    from repro.events.aer import dense_to_aer

    engine = _engine(rng, neuron=neuron)
    server = SpikeServer(engine, n_slots=2, chunk_steps=3)
    fe = AsyncSpikeFrontend(server)
    raster = _rasters(rng, (6,), engine.n_inputs)[0]
    stream = dense_to_aer(raster[:, None, :], capacity=raster.sum())
    h = fe.submit_events(stream, events_capacity=256)
    fe.drain()
    want = engine.run(raster[:, None, :])["spikes"]
    res = h.result()
    np.testing.assert_array_equal(res["spikes"], np.asarray(want)[:, 0])
    got_events = np.asarray(res["events"].addrs[:len(res["events"])])
    from repro.events.aer import aer_to_dense
    np.testing.assert_array_equal(
        np.asarray(aer_to_dense(res["events"]))[:, 0], res["spikes"])
    assert got_events.shape[1] == 3


@pytest.mark.parametrize("neuron", NEURONS)
def test_session_serve_frontend_shared_and_bit_identical(rng, neuron):
    """Co-resident views share ONE frontend queue, and async view results
    are byte-identical to synchronous view feeds of the same rasters."""
    syn = 0.75 if neuron == "cuba" else None

    def build():
        sess = AcceleratorSession()
        r = np.random.default_rng(3)
        sess.deploy("a", make_random_net(r, syn_decay_rate=syn))
        sess.deploy("b", make_random_net(r, syn_decay_rate=syn))
        return sess

    cfg = FrontendConfig(queue_capacity=8)
    sess = build()
    va = sess.serve("a", n_slots=2, chunk_steps=3, frontend=cfg)
    vb = sess.serve("b", n_slots=2, chunk_steps=3, frontend=cfg)
    assert va.frontend is vb.frontend is not None
    # a view served later without frontend= still sees the group's queue
    assert sess.serve("a", n_slots=2, chunk_steps=3).frontend is va.frontend
    with pytest.raises(ValueError, match="one request queue"):
        sess.serve("a", n_slots=2, chunk_steps=3,
                   frontend=FrontendConfig(queue_capacity=9))

    r = np.random.default_rng(11)
    chunk_a = (r.random((7, va.n_inputs)) < 0.4).astype(np.int32)
    chunk_b = (r.random((5, vb.n_inputs)) < 0.4).astype(np.int32)
    ha = va.submit(chunk_a)
    hb = vb.submit(chunk_b)
    va.frontend.drain()

    sync = build()
    for view, chunk, h in ((sync.serve("a", n_slots=2, chunk_steps=3),
                            chunk_a, ha),
                           (sync.serve("b", n_slots=2, chunk_steps=3),
                            chunk_b, hb)):
        uid = view.attach()
        want = view.feed(uid, chunk)
        got = h.result()
        np.testing.assert_array_equal(got["spikes"], want["spikes"])
        np.testing.assert_array_equal(got["output_counts"],
                                      want["output_counts"])
        assert got["predictions"] == want["predictions"]


def test_model_stream_submit_requires_frontend(rng):
    sess = AcceleratorSession()
    sess.deploy("m", make_random_net(np.random.default_rng(0)))
    view = sess.serve("m")
    with pytest.raises(RuntimeError, match="no async frontend"):
        view.submit(np.zeros((3, view.n_inputs), np.int32))


def test_latency_percentiles_shapes():
    assert latency_percentiles([])["p50"] is None
    assert latency_percentiles([])["p99"] is None
    p = latency_percentiles([1.0, 2.0, 3.0])
    assert p["p50"] == 2.0 and p["max"] == 3.0
    assert p["p95"] <= p["p99"] <= p["max"]


# --------------------------------------------------------------------------
# metrics() shape contract: every documented key, always (PR 8 satellite)
# --------------------------------------------------------------------------

def test_metrics_shape_on_empty_run(rng):
    """A frontend that never saw a request still returns every documented
    key with well-defined zeros — no KeyErrors, no missing outcomes."""
    from repro.serving.frontend import OUTCOME_KEYS

    engine = _engine(rng)
    server = SpikeServer(engine, n_slots=1, chunk_steps=2)
    fe = AsyncSpikeFrontend(server, queue_capacity=1)
    m = fe.metrics()
    assert set(m) == {"counts", "by_class", "queue_wait", "service",
                      "total", "queue_depth", "rounds"}
    assert m["counts"] == {k: 0 for k in OUTCOME_KEYS}
    # no QoS policy + no traffic = no classes to zero-fill
    assert m["by_class"] == {}
    for section in ("queue_wait", "service", "total"):
        assert m[section] == {"mean": None, "p50": None, "p95": None,
                              "p99": None, "max": None}
    assert m["queue_depth"] == {"max": 0, "mean": 0.0}
    assert m["rounds"] == 0


def test_metrics_by_class_zero_filled_on_empty_qos_run(rng):
    """A QoS frontend that never saw a request still reports every
    policy-declared class with the FULL zero-filled outcome dict and
    all-None percentiles — dashboards index per-class keys without
    existence checks (the PR 8 contract, extended per class)."""
    from repro.serving.frontend import OUTCOME_KEYS
    from repro.serving.qos import QoSClass, QoSPolicy

    engine = _engine(rng)
    server = SpikeServer(engine, n_slots=1, chunk_steps=2)
    policy = QoSPolicy(classes={"hi": QoSClass(priority=1),
                                "bg": QoSClass()})
    fe = AsyncSpikeFrontend(server, queue_capacity=1, qos=policy)
    m = fe.metrics()
    assert set(m["by_class"]) == {"hi", "bg"}
    for cls in ("hi", "bg"):
        per = m["by_class"][cls]
        assert set(per) == {"counts", "queue_wait", "service", "total"}
        assert per["counts"] == {k: 0 for k in OUTCOME_KEYS}
        for section in ("queue_wait", "service", "total"):
            assert per[section]["p50"] is None
            assert per[section]["p99"] is None


def test_metrics_shape_on_all_expired_run(rng):
    """An all-expired run (nothing ever retired cleanly) keeps the same
    shape: zero 'done', None service/total percentiles, every key there."""
    from repro.serving.frontend import OUTCOME_KEYS

    engine = _engine(rng)
    server = SpikeServer(engine, n_slots=1, chunk_steps=2)
    clock = VirtualClock()
    fe = AsyncSpikeFrontend(server, queue_capacity=4, clock=clock)
    handles = [fe.submit(r, deadline_ms=1_000)
               for r in _rasters(rng, (4, 4), engine.n_inputs)]
    clock.t = 2.0           # every deadline passed before any admission
    fe.pump()
    assert all(h.state == "expired" for h in handles)
    m = fe.metrics()
    assert set(m["counts"]) == set(OUTCOME_KEYS)
    assert m["counts"]["done"] == 0
    assert m["counts"]["expired"] == 2
    assert m["counts"]["expired_queued"] == 2
    assert m["service"]["p50"] is None and m["total"]["p50"] is None
    assert m["rounds"] == 1
    # per-class mirror: the traffic's class appears zero-filled for
    # every outcome it never reached, latencies all-None
    assert set(m["by_class"]) == {"default"}
    per = m["by_class"]["default"]
    assert set(per["counts"]) == set(OUTCOME_KEYS)
    assert per["counts"]["expired"] == 2 and per["counts"]["done"] == 0
    assert per["total"]["p50"] is None


@pytest.mark.parametrize("neuron", NEURONS)
def test_traced_spill_flow_reconstructs_violation_free(rng, neuron):
    """Lifecycle audit over the representative front-door flow: server
    and frontend share one SpanTracer through submit / cancel-queued /
    queued-expiry / mid-stream spill / resume / drain, and the timeline
    reconstruction — which hard-errors on any illegal transition, leaked
    stream, or retire-without-admit — accepts the whole trace with the
    expected outcomes on both the request and the server domain."""
    from repro.obs import SpanTracer
    from repro.obs.timeline import reconstruct
    from repro.serving.connector import InMemoryCarryConnector

    engine = _engine(rng, neuron=neuron)
    clock = VirtualClock()
    tracer = SpanTracer(clock=clock)
    server = SpikeServer(engine, n_slots=1, chunk_steps=2, tracer=tracer)
    fe = AsyncSpikeFrontend(server, queue_capacity=8, clock=clock,
                            connector=InMemoryCarryConnector(),
                            tracer=tracer)
    spill, plain, victim, late = _rasters(rng, (10, 4, 6, 5),
                                          engine.n_inputs)
    a = fe.submit(spill, deadline_ms=1_000)   # parks mid-stream
    b = fe.submit(plain)                      # queued behind a
    c = fe.submit(victim)                     # cancelled while queued
    d = fe.submit(late, deadline_ms=1_500)    # expires while queued
    assert c.cancel() is True
    fe.pump()                                 # a runs 2 of 10 steps
    clock.t = 2.0                             # both deadlines pass
    fe.pump()
    assert a.state == "parked" and d.state == "expired"
    fe.drain()                                # b completes
    assert fe.resume(a) is True
    fe.drain()
    assert a.state == "done"

    rep = reconstruct(tracer)                 # raises on any violation
    outcomes = {h: rep.stream(h.rid, domain="request").outcome
                for h in (a, b, c, d)}
    assert outcomes == {a: "done", b: "done",
                        c: "cancelled", d: "expired"}
    spilled = rep.stream(a.rid, domain="request")
    assert spilled.n_parks == 1 and spilled.n_admissions == 2
    # every timeline closed legally: all four requests retired, plus
    # three server streams — b's, a's resumed incarnation (resume mints
    # a fresh server uid off the snapshot), and a's FIRST incarnation,
    # which legally ends 'parked' (its carry continued under the new
    # uid; the request domain is the continuous thread)
    assert rep.by_state() == {"retired": 6, "parked": 1}
