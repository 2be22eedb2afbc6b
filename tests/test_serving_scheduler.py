"""Slot-scheduler invariants, property-tested.

Hypothesis drives random attach/detach sequences against
:class:`repro.serving.snn.SlotScheduler` (pure bookkeeping — fast) and a
tiny :class:`SpikeServer` (array state), checking the invariants the
streaming layer's exactness proof rests on:

  * no slot is ever double-assigned;
  * eviction always zeroes the evicted slot's carry;
  * admission is FIFO-fair: waiters are granted slots in submission order.

When ``hypothesis`` is not installed the conftest stub makes every
``@given`` test skip cleanly; the deterministic companions below still
run everywhere, so the invariants are never fully untested.
"""

import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import HealthCheck, example, given, settings
from hypothesis import strategies as st

from repro.core.engine import DecaySpec, SpikeEngine
from repro.serving.snn import SlotScheduler, SpikeServer

# op stream: (True, uid) = attach uid; (False, k) = detach the k-th oldest
# currently-submitted uid (mapped onto live uids at replay time)
_OPS = st.lists(
    st.tuples(st.booleans(), st.integers(0, 31)), min_size=1, max_size=60)


def _replay(n_slots, ops):
    """Drive a SlotScheduler; return the trace of (event, uid, slot)."""
    sched = SlotScheduler(n_slots)
    live: list = []   # uids submitted and not yet released/cancelled
    trace = []
    next_uid = 0
    for is_attach, k in ops:
        if is_attach:
            uid = next_uid
            next_uid += 1
            slot = sched.submit(uid)
            live.append(uid)
            trace.append(("submit", uid, slot))
        elif live:
            uid = live.pop(k % len(live))
            if sched.slot_of(uid) is None:
                sched.cancel(uid)
                trace.append(("cancel", uid, None))
            else:
                slot, admitted = sched.release(uid)
                trace.append(("release", uid, slot))
                if admitted is not None:
                    trace.append(("admit", admitted, slot))
        _check_consistency(sched)
    return sched, trace


def _check_consistency(sched):
    slots = list(sched.active.values())
    assert len(slots) == len(set(slots)), "slot double-assignment"
    assert all(0 <= s < sched.n_slots for s in slots)
    assert len(slots) <= sched.n_slots
    # a waiter while a slot is free is a scheduling bug
    if sched.waiting:
        assert len(slots) == sched.n_slots
    # active and waiting are disjoint
    assert not set(sched.active) & set(sched.waiting)


@settings(max_examples=60, deadline=None,
          suppress_health_check=[HealthCheck.too_slow])
@given(n_slots=st.integers(1, 5), ops=_OPS)
@pytest.mark.slow
def test_scheduler_no_double_assignment(n_slots, ops):
    """At every point of every attach/detach sequence, each slot holds at
    most one stream (checked inside the replay after every op)."""
    _replay(n_slots, ops)


@settings(max_examples=60, deadline=None,
          suppress_health_check=[HealthCheck.too_slow])
@given(n_slots=st.integers(1, 4), ops=_OPS)
# a later waiter once got a smaller stamp than an earlier one: stamping by
# len(waiting_since) repeats values after admissions shrink the dict
@example(n_slots=1, ops=[(True, 0)] * 4 + [(False, 0), (False, 0),
                                           (True, 0), (False, 0)])
@pytest.mark.slow
def test_scheduler_fifo_fairness(n_slots, ops):
    """Streams are admitted in submission order: the sequence of admitted
    uids (immediate grants + queue promotions) is monotone in submit
    order among those that ever waited, and a promotion always picks the
    longest-waiting uid."""
    sched, trace = _replay(n_slots, ops)
    waiting_since: dict = {}
    for ev, uid, slot in trace:
        if ev == "submit" and slot is None:
            # _replay hands out uids in submit order: the uid is the stamp
            waiting_since[uid] = uid
        elif ev == "admit":
            # the admitted uid must be the oldest waiter at that moment
            assert uid in waiting_since
            oldest = min(waiting_since, key=waiting_since.get)
            assert uid == oldest, (uid, waiting_since)
            del waiting_since[uid]
        elif ev == "cancel":
            waiting_since.pop(uid, None)


_ENGINE_CACHE: dict = {}


def _shared_engine():
    """One engine (and one compiled chunk step) across all examples."""
    if "engine" not in _ENGINE_CACHE:
        rng = np.random.default_rng(0)
        W = jnp.asarray((rng.random((6 + 4, 4)) < 0.6)
                        * rng.integers(1 << 14, 1 << 17, (10, 4)), jnp.int32)
        _ENGINE_CACHE["engine"] = SpikeEngine(
            W, 6, decay=DecaySpec.shift(0.25), threshold_raw=1 << 20,
            reset_mode="hold")
    return _ENGINE_CACHE["engine"]


@settings(max_examples=30, deadline=None,
          suppress_health_check=[HealthCheck.too_slow])
@given(ops=st.lists(st.tuples(st.booleans(), st.integers(0, 15)),
                    min_size=1, max_size=24))
@pytest.mark.slow
def test_server_eviction_always_zeroes_carry(ops):
    """Whatever the attach/feed/detach sequence, a detached stream's slot
    carry is zero immediately after eviction, and unoccupied slots stay
    zero (the exactness precondition for slot reuse)."""
    server = SpikeServer(_shared_engine(), n_slots=2, chunk_steps=2)
    live = []
    for is_attach, k in ops:
        if is_attach:
            uid = server.attach()
            live.append(uid)
            if server.slot_of(uid) is not None:
                server.feed({uid: np.ones((3, 6), np.int32)})
        elif live:
            uid = live.pop(k % len(live))
            had_slot = server.slot_of(uid)
            server.detach(uid)
            if had_slot is not None:
                occupied = set(server.scheduler.active.values())
                for s in range(server.n_slots):
                    if s not in occupied:
                        assert not np.asarray(server.carry["v"][s]).any()
                        assert not np.asarray(
                            server.carry["spikes"][s]).any()


# --------------------------------------------------------------------------
# Deterministic companions: the same invariants on fixed sequences, so the
# contracts run even where hypothesis is unavailable.
# --------------------------------------------------------------------------

def test_scheduler_invariants_deterministic():
    sched = SlotScheduler(2)
    assert sched.submit("a") == 0
    assert sched.submit("b") == 1
    assert sched.submit("c") is None and sched.submit("d") is None
    _check_consistency(sched)
    slot, admitted = sched.release("a")
    assert (slot, admitted) == (0, "c")       # FIFO: c before d
    _check_consistency(sched)
    sched.cancel("d")                          # withdraw a waiter
    slot, admitted = sched.release("b")
    assert (slot, admitted) == (1, None)
    assert sched.submit("e") == 1              # FIFO slot reuse
    with pytest.raises(ValueError, match="already"):
        sched.submit("e")
    with pytest.raises(KeyError):
        sched.release("ghost")
    with pytest.raises(KeyError):
        sched.cancel("e")                      # active, not waiting


def test_scheduler_rejects_bad_sizes():
    with pytest.raises(ValueError):
        SlotScheduler(0)
    W = jnp.zeros((4, 2), jnp.int32)
    eng = SpikeEngine(W, 2, decay=DecaySpec.shift(0.25),
                      threshold_raw=1, reset_mode="zero")
    with pytest.raises(ValueError, match="chunk_steps"):
        SpikeServer(eng, n_slots=1, chunk_steps=0)


def test_server_detach_of_waiting_stream(rng):
    W = jnp.zeros((8 + 4, 4), jnp.int32)
    engine = SpikeEngine(W, 8, decay=DecaySpec.shift(0.25),
                         threshold_raw=1, reset_mode="zero")
    server = SpikeServer(engine, n_slots=1, chunk_steps=2)
    a = server.attach()
    b = server.attach()
    server.detach(b)                # cancel from the waiting queue
    assert server.slot_of(a) == 0
    server.detach(a)
    c = server.attach()
    assert server.slot_of(c) == 0   # queue empty, slot recycled
