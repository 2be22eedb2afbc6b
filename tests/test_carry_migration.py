"""Live-migration byte-identity: moving a stream must never change a bit.

The governing contract of the stream-state connector: a stream whose
carry is snapshotted, parked, and restored — onto another server, another
slot, another backend/gate/fuse hosting, a mesh-sharded server, or a
fresh process after a crash — produces a spike raster BYTE-identical to
the same stream never migrated. Pinned here:

  * cross-server migration (detach_stream -> connector -> attach_stream)
    for every backend x reset mode x gate x fuse_steps re-hosting — fast
    reference legs always run, the full sweep rides the ``slow`` marker
    (same tiering as ``test_fused_steps.py``);
  * migration into mesh-sharded servers (1x1 always; 2x2 when devices
    allow — CI fakes 8 via XLA_FLAGS=--xla_force_host_platform_device_count=8);
  * intra-server ``migrate_stream`` (a slot index is an address, not a
    parameter) and straggler-driven ``rebalance_streams`` (flagged shards
    drained onto donors, deterministically, every moved stream bit-clean);
  * session-level rolling redeploy: ``deploy`` mid-stream parks live
    carries, the next ``serve`` restores them, and the spliced raster +
    decoded outputs equal an uninterrupted run;
  * crash recovery: ``checkpoint_streams`` to a file-backed connector,
    drop the server, rebuild on a NEW connector instance over the same
    directory — resumed streams continue bit-clean;
  * restore-side safety: incompatible engines, full servers, and missing
    snapshots are refused BEFORE any server state mutates.

The fast legs run for both neuron models (``neuron``): one-state LIF and
current-based LIF, whose synaptic current ``i`` must ride every hop
beside ``v``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.engine import BACKENDS, GATES, DecaySpec, SpikeEngine
from repro.core.lif import LIFParams
from repro.core.network import SNNetwork
from repro.core.session import AcceleratorSession
from repro.distributed.spike_mesh import make_spike_mesh
from repro.serving.connector import (FileCarryConnector,
                                     InMemoryCarryConnector, migrate_stream,
                                     rebalance_streams)
from repro.serving.snn import SpikeServer

from conftest import assert_slot_state

THRESH = 1 << 16
RESET_MODES = ("zero", "subtract", "hold")
NEURONS = ("lif", "cuba")


def _engine(rng, *, backend="reference", gate="batch-tile", reset="subtract",
            K=1, n_in=10, n_phys=16, wmax=1 << 13, neuron="lif"):
    S = n_in + n_phys
    W = ((rng.random((S, n_phys)) < 0.4)
         * rng.integers(-wmax, wmax, (S, n_phys)))
    syn = DecaySpec.shift(0.75) if neuron == "cuba" else None
    return SpikeEngine(jnp.asarray(W, jnp.int32), n_in,
                       decay=DecaySpec.shift(0.25), threshold_raw=THRESH,
                       reset_mode=reset, backend=backend, gate=gate,
                       fuse_steps=K, syn_decay=syn)


def _raster(rng, T, n_in, p=0.35):
    return (rng.random((T, n_in)) < p).astype(np.int32)


def _migrated_vs_reference(engine_a, engine_b, rng, *, T=14, t_mid=6,
                           connector=None, chunk_a=5, chunk_b=3,
                           mesh_b=None):
    """THE contract check: stream T steps with a mid-flight hop from
    server A to server B through a connector; the stitched raster must
    equal the one-shot never-migrated ``run`` on engine A, and so must
    the slot's final state (``v``, and ``i`` for current-based neurons)."""
    ext = _raster(rng, T, engine_a.n_inputs)
    ref = engine_a.run(ext[:, None, :])
    want = np.asarray(ref["spikes"])[:, 0]

    conn = connector if connector is not None else InMemoryCarryConnector()
    a = SpikeServer(engine_a, n_slots=3, chunk_steps=chunk_a)
    b = SpikeServer(engine_b, n_slots=4, chunk_steps=chunk_b, mesh=mesh_b)
    uid = a.attach("mig")
    first = a.feed({uid: ext[:t_mid]})[uid]["spikes"]

    a.detach_stream(uid, conn)
    assert uid not in a.streams and uid in conn
    b.attach_stream(conn, uid)
    assert uid not in conn  # the hop consumed the parked carry

    second = b.feed({uid: ext[t_mid:]})[uid]["spikes"]
    got = np.concatenate([np.asarray(first), np.asarray(second)], axis=0)
    assert got.dtype == want.dtype == np.int32
    np.testing.assert_array_equal(got, want)
    assert b.streams[uid].steps == T  # counters rode along
    assert_slot_state(b, uid, ref)


# --------------------------------------------------------------------------
# cross-server migration: fast legs + full slow sweep
# --------------------------------------------------------------------------

@pytest.mark.parametrize("neuron", NEURONS)
def test_cross_server_migration_fast(rng, neuron):
    """Reference engine, ragged chunking on both sides, in-memory hop —
    the always-on leg of the contract."""
    e = _engine(rng, neuron=neuron)
    _migrated_vs_reference(e, e, rng)


@pytest.mark.parametrize("neuron", NEURONS)
def test_cross_server_migration_through_file_fast(rng, tmp_path, neuron):
    """Same hop through the file-backed connector: the bytes take the
    disk detour and still land identical."""
    e = _engine(rng, reset="zero", neuron=neuron)
    _migrated_vs_reference(
        e, e, rng, connector=FileCarryConnector(str(tmp_path / "c")))


@pytest.mark.parametrize("neuron", NEURONS)
def test_migration_across_hostings_fast(rng, neuron):
    """A carry is portable across backend/gate/fuse re-hostings: park on
    the reference server, resume on a fused per-example pallas server."""
    src = _engine(rng, neuron=neuron)
    # same weights so the slot params (and the future) agree
    dst = SpikeEngine(src.weights_raw, src.n_inputs,
                      decay=DecaySpec.shift(0.25), threshold_raw=THRESH,
                      reset_mode="subtract", backend="pallas",
                      gate="per-example", fuse_steps=4,
                      syn_decay=src.syn_decay)
    _migrated_vs_reference(src, dst, rng)


@pytest.mark.slow
@pytest.mark.parametrize("reset", RESET_MODES)
@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("gate", GATES)
@pytest.mark.parametrize("K", [1, 4])
def test_cross_server_migration_sweep(rng, backend, reset, gate, K):
    """Full hosting matrix: migrate FROM a reference server INTO every
    backend x reset x gate x fuse_steps hosting. t_mid=7 lands mid-window
    for K=4 — the restored carry starts a fresh window, which must not
    show in the bits."""
    src = _engine(rng, reset=reset)
    dst = SpikeEngine(src.weights_raw, src.n_inputs,
                      decay=DecaySpec.shift(0.25), threshold_raw=THRESH,
                      reset_mode=reset, backend=backend, gate=gate,
                      fuse_steps=K)
    _migrated_vs_reference(src, dst, rng, t_mid=7)


# --------------------------------------------------------------------------
# mesh: migrate into (and out of) a sharded server
# --------------------------------------------------------------------------

def _mesh(neuron, batch):
    need = neuron * batch
    if len(jax.devices()) < need:
        pytest.skip(
            f"needs {need} devices; run under "
            f"XLA_FLAGS=--xla_force_host_platform_device_count=8")
    return make_spike_mesh(neuron=neuron, batch=batch)


@pytest.mark.parametrize("neuron", NEURONS)
@pytest.mark.parametrize("shape", [(1, 1), (2, 2)])
def test_migration_into_mesh_server(rng, shape, neuron):
    """A carry parked on a single-device server resumes bit-clean on a
    mesh-sharded one (and 1x1 exercises the shard_map path everywhere)."""
    mesh = _mesh(*shape)
    e = _engine(rng, n_in=11, n_phys=24, neuron=neuron)
    _migrated_vs_reference(e, e, rng, mesh_b=mesh)


@pytest.mark.parametrize("neuron", NEURONS)
def test_migration_out_of_mesh_server(rng, neuron):
    """And back: a stream born sharded hops to a plain server."""
    mesh = _mesh(1, 1)
    e = _engine(rng, reset="hold", neuron=neuron)
    ext = _raster(rng, 12, e.n_inputs)
    ref = e.run(ext[:, None, :])
    want = np.asarray(ref["spikes"])[:, 0]

    conn = InMemoryCarryConnector()
    a = SpikeServer(e, n_slots=2, chunk_steps=4, mesh=mesh)
    b = SpikeServer(e, n_slots=2, chunk_steps=5)
    uid = a.attach()
    first = a.feed({uid: ext[:5]})[uid]["spikes"]
    a.detach_stream(uid, conn)
    b.attach_stream(conn, uid)
    second = b.feed({uid: ext[5:]})[uid]["spikes"]
    np.testing.assert_array_equal(
        np.concatenate([np.asarray(first), np.asarray(second)]), want)
    assert_slot_state(b, uid, ref)


# --------------------------------------------------------------------------
# intra-server: migrate_stream + straggler rebalance
# --------------------------------------------------------------------------

@pytest.mark.parametrize("neuron", NEURONS)
def test_migrate_stream_changes_address_not_future(rng, neuron):
    """Mid-stream slot move: the raster continues byte-identically, the
    old slot is powered down, counters survive."""
    e = _engine(rng, neuron=neuron)
    ext = _raster(rng, 12, e.n_inputs)
    ref = e.run(ext[:, None, :])
    want = np.asarray(ref["spikes"])[:, 0]

    server = SpikeServer(e, n_slots=4, chunk_steps=4)
    uid = server.attach()
    first = server.feed({uid: ext[:7]})[uid]["spikes"]

    old = migrate_stream(server, uid, slot=3)
    assert (old, server.slot_of(uid)) == (0, 3)
    for k in server.carry:                                # zeroed behind
        assert not np.asarray(server.carry[k][old]).any(), k

    second = server.feed({uid: ext[7:]})[uid]["spikes"]
    np.testing.assert_array_equal(
        np.concatenate([np.asarray(first), np.asarray(second)]), want)
    assert server.streams[uid].steps == 12
    assert_slot_state(server, uid, ref)


@pytest.mark.parametrize("neuron", NEURONS)
def test_migrate_stream_same_slot_is_noop(rng, neuron):
    e = _engine(rng, neuron=neuron)
    server = SpikeServer(e, n_slots=2, chunk_steps=4)
    uid = server.attach()
    server.feed({uid: _raster(rng, 5, e.n_inputs)})
    before = {k: np.asarray(x) for k, x in server.carry.items()}
    assert migrate_stream(server, uid, slot=0) == 0
    for k, x in server.carry.items():
        np.testing.assert_array_equal(np.asarray(x), before[k])


@pytest.mark.parametrize("neuron", NEURONS)
def test_rebalance_drains_flagged_shards_bit_clean(rng, neuron):
    """8 slots / 4 shards (slots_per_shard=2), shard 0 flagged: its
    streams walk onto donor shards' free slots, lowest ids first, and a
    twin server that never rebalanced proves every stream's raster is
    untouched by the move."""
    e = _engine(rng, neuron=neuron)
    moved = SpikeServer(e, n_slots=8, chunk_steps=4)
    still = SpikeServer(e, n_slots=8, chunk_steps=4)
    uids = ["s0", "s1", "s2"]
    for server in (moved, still):
        for u in uids:
            server.attach(u)
    # slots 0,1 (shard 0, flagged) + slot 2 (shard 1)
    rasters = {u: _raster(rng, 16, e.n_inputs) for u in uids}
    for server in (moved, still):
        server.feed({u: r[:6] for u, r in rasters.items()})

    flagged = [True, False, False, False]
    moves = rebalance_streams(moved, flagged, slots_per_shard=2)
    # deterministic: busiest flagged shard's lowest live slot -> the
    # emptiest donor shard's lowest free slot (shard 2, slot 4); a second
    # move would only relocate the imbalance, so exactly one happens
    assert moves == [("s0", 0, 4)]
    assert moved.slot_of("s0") == 4 and moved.slot_of("s1") == 1

    got = moved.feed({u: r[6:] for u, r in rasters.items()})
    want = still.feed({u: r[6:] for u, r in rasters.items()})
    for u in uids:
        np.testing.assert_array_equal(np.asarray(got[u]["spikes"]),
                                      np.asarray(want[u]["spikes"]))
        for k in moved.carry:
            np.testing.assert_array_equal(
                np.asarray(moved.carry[k])[moved.slot_of(u)],
                np.asarray(still.carry[k])[still.slot_of(u)], err_msg=k)


def test_rebalance_noop_cases(rng):
    server = SpikeServer(_engine(rng), n_slots=4, chunk_steps=4)
    server.attach("a")
    # nothing flagged / everything flagged (no donors): no moves
    assert rebalance_streams(server, [False, False],
                             slots_per_shard=2) == []
    assert rebalance_streams(server, [True, True],
                             slots_per_shard=2) == []
    assert server.slot_of("a") == 0


# --------------------------------------------------------------------------
# session: rolling redeploy parks and restores live streams
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
def test_session_redeploy_preserves_live_streams(rng, neuron):
    """deploy() mid-stream is a rolling redeploy: the live stream's carry
    rides the session connector across the fused-layout change and the
    spliced outputs equal an uninterrupted single-model run."""
    netA = _net(rng, neuron=neuron)
    netB = _net(rng, n_in=5, n_neurons=10, neuron=neuron)
    ext = (rng.random((12, 6)) < 0.4).astype(np.int32)

    solo = AcceleratorSession()
    solo.deploy("A", netA)
    sv = solo.serve("A", n_slots=2, chunk_steps=4)
    u = sv.attach("live")
    want = [sv.feed(u, ext[:5]), sv.feed(u, ext[5:])]

    sess = AcceleratorSession()
    sess.deploy("A", netA)
    view = sess.serve("A", n_slots=2, chunk_steps=4)
    uid = view.attach("live")
    got_first = view.feed(uid, ext[:5])

    sess.deploy("B", netB)          # invalidates the view, parks "live"
    with pytest.raises(RuntimeError):
        view.feed(uid, ext[5:6])
    view2 = sess.serve("A", n_slots=2, chunk_steps=4)
    got_second = view2.feed(uid, ext[5:])

    for got, ref in ((got_first, want[0]), (got_second, want[1])):
        np.testing.assert_array_equal(np.asarray(got["spikes"]),
                                      np.asarray(ref["spikes"]))
        np.testing.assert_array_equal(got["output_counts"],
                                      ref["output_counts"])
    assert view2.server.streams[uid].steps == 12


@pytest.mark.parametrize("neuron", NEURONS)
def test_session_redeploy_keeps_waiting_streams_waiting(rng, neuron):
    """A stream still queued for a slot has no carry; the redeploy must
    re-queue it (not drop it, not fabricate state)."""
    sess = AcceleratorSession()
    sess.deploy("A", _net(rng, neuron=neuron))
    view = sess.serve("A", n_slots=1, chunk_steps=4)
    view.attach("holder")
    view.attach("waiter")           # n_slots=1: this one queues
    sess.deploy("B", _net(rng, n_in=5, n_neurons=10, neuron=neuron))
    view2 = sess.serve("A", n_slots=1, chunk_steps=4)
    srv = view2.server
    assert srv.slot_of("holder") == 0
    assert srv.slot_of("waiter") is None and "waiter" in srv.streams


# --------------------------------------------------------------------------
# crash recovery: file-backed checkpoints outlive the server
# --------------------------------------------------------------------------

@pytest.mark.parametrize("neuron", NEURONS)
def test_crash_recovery_from_file_checkpoint(rng, tmp_path, neuron):
    """Kill the server after a checkpoint barrier; a NEW connector
    instance over the same directory rebuilds every stream on a fresh
    server, bit-clean — including counters."""
    e = _engine(rng, reset="subtract", neuron=neuron)
    ext = {u: _raster(rng, 15, e.n_inputs) for u in ("x", "y")}
    ref = {u: e.run(r[:, None, :]) for u, r in ext.items()}
    want = {u: np.asarray(r["spikes"])[:, 0] for u, r in ref.items()}

    root = str(tmp_path / "wal")
    server = SpikeServer(e, n_slots=3, chunk_steps=5)
    for u in ext:
        server.attach(u)
    first = server.feed({u: r[:8] for u, r in ext.items()})
    assert server.checkpoint_streams(FileCarryConnector(root)) == ["x", "y"]
    steps_before = {u: server.streams[u].steps for u in ext}
    del server                      # the crash

    recovered = SpikeServer(e, n_slots=3, chunk_steps=5)
    restored = recovered.restore_streams(FileCarryConnector(root))
    assert sorted(restored, key=repr) == ["x", "y"]
    second = recovered.feed({u: r[8:] for u, r in ext.items()})
    for u in ext:
        got = np.concatenate([np.asarray(first[u]["spikes"]),
                              np.asarray(second[u]["spikes"])])
        np.testing.assert_array_equal(got, want[u])
        assert recovered.streams[u].steps == steps_before[u] + 7
        assert_slot_state(recovered, u, ref[u])


@pytest.mark.parametrize("neuron", NEURONS)
def test_checkpoint_is_nondestructive(rng, tmp_path, neuron):
    """checkpoint_streams is a write barrier, not a drain: the source
    server keeps streaming identically afterwards."""
    e = _engine(rng, neuron=neuron)
    ext = _raster(rng, 10, e.n_inputs)
    ref = e.run(ext[:, None, :])
    want = np.asarray(ref["spikes"])[:, 0]
    server = SpikeServer(e, n_slots=2, chunk_steps=4)
    uid = server.attach()
    first = server.feed({uid: ext[:4]})[uid]["spikes"]
    server.checkpoint_streams(FileCarryConnector(str(tmp_path / "c")))
    second = server.feed({uid: ext[4:]})[uid]["spikes"]
    np.testing.assert_array_equal(
        np.concatenate([np.asarray(first), np.asarray(second)]), want)
    assert_slot_state(server, uid, ref)


@pytest.mark.parametrize("neuron", NEURONS)
def test_restore_streams_restores_what_fits(rng, tmp_path, neuron):
    conn = FileCarryConnector(str(tmp_path / "c"))
    e = _engine(rng, neuron=neuron)
    src = SpikeServer(e, n_slots=4, chunk_steps=4)
    for u in ("a", "b", "c"):
        src.attach(u)
    src.feed({u: _raster(rng, 4, e.n_inputs) for u in ("a", "b", "c")})
    src.checkpoint_streams(conn)

    tiny = SpikeServer(e, n_slots=2, chunk_steps=4)
    restored = tiny.restore_streams(conn)
    assert len(restored) == 2 and tiny.scheduler.free_slots == 0
    leftover = set(conn.stream_ids())
    assert leftover == {"a", "b", "c"} - set(restored)  # still parked


# --------------------------------------------------------------------------
# restore-side safety: refused before any state mutates
# --------------------------------------------------------------------------

@pytest.mark.parametrize("neuron", NEURONS)
def test_attach_stream_rejects_incompatible_server(rng, neuron):
    """A snapshot from a 16-neuron subtract engine must not land on a
    32-neuron or zero-reset server — and the refusal leaves the target
    completely untouched."""
    src = SpikeServer(_engine(rng, neuron=neuron), n_slots=2, chunk_steps=4)
    uid = src.attach()
    src.feed({uid: _raster(rng, 4, src.engine.n_inputs)})
    snap = src.snapshot_stream(uid)

    for bad_engine, field in (
            (_engine(rng, n_phys=32, neuron=neuron), "n_phys"),
            (_engine(rng, reset="zero", neuron=neuron), "reset_mode")):
        dst = SpikeServer(bad_engine, n_slots=2, chunk_steps=4)
        with pytest.raises(ValueError, match=field):
            dst.attach_stream(snap)
        assert not dst.streams and dst.scheduler.free_slots == 2
        for k in dst.carry:
            assert not np.asarray(dst.carry[k]).any(), k


@pytest.mark.parametrize("neuron", NEURONS)
def test_attach_stream_requires_free_slot(rng, neuron):
    e = _engine(rng, neuron=neuron)
    src = SpikeServer(e, n_slots=2, chunk_steps=4)
    uid = src.attach()
    src.feed({uid: _raster(rng, 3, e.n_inputs)})
    snap = src.snapshot_stream(uid)

    full = SpikeServer(e, n_slots=1, chunk_steps=4)
    full.attach()
    with pytest.raises(RuntimeError, match="free slot"):
        full.attach_stream(snap)
    assert len(full.streams) == 1   # no phantom half-attached stream


def test_attach_stream_connector_misuse(rng):
    e = _engine(rng)
    server = SpikeServer(e, n_slots=2, chunk_steps=4)
    conn = InMemoryCarryConnector()
    with pytest.raises(ValueError, match="stream id"):
        server.attach_stream(conn)            # connector source needs uid
    with pytest.raises(KeyError):
        server.attach_stream(conn, uid="ghost")


def test_snapshot_waiting_stream_raises(rng):
    server = SpikeServer(_engine(rng), n_slots=1, chunk_steps=4)
    server.attach("holder")
    server.attach("waiter")
    with pytest.raises(ValueError, match="waiting"):
        server.snapshot_stream("waiter")


@pytest.mark.parametrize("neuron", NEURONS)
def test_traced_migration_hop_reconstructs_violation_free(rng, neuron):
    """Lifecycle audit riding the migration contract: both servers share
    one SpanTracer through attach / feed / cross-server hop /
    intra-server migrate_stream / retire, and the timeline
    reconstruction — which hard-errors on illegal transitions, leaks, or
    retire-without-admit — accepts the trace with the expected
    park/admission/migration counts on the single stream identity."""
    from repro.obs import SpanTracer
    from repro.obs.timeline import reconstruct

    tracer = SpanTracer()
    e = _engine(rng, neuron=neuron)
    conn = InMemoryCarryConnector()
    a = SpikeServer(e, n_slots=3, chunk_steps=5, tracer=tracer)
    b = SpikeServer(e, n_slots=4, chunk_steps=3, tracer=tracer)
    ext = _raster(rng, 14, e.n_inputs)

    uid = a.attach("mig")
    first = a.feed({uid: ext[:6]})[uid]["spikes"]
    a.detach_stream(uid, conn)                # park on A
    b.attach_stream(conn, uid)                # resumed admit on B
    migrate_stream(b, uid, slot=3)            # address change on B
    second = b.feed({uid: ext[6:]})[uid]["spikes"]
    ref = e.run(ext[:, None, :])
    assert_slot_state(b, uid, ref)
    b.detach(uid, reason="done")

    want = np.asarray(ref["spikes"])[:, 0]
    got = np.concatenate([np.asarray(first), np.asarray(second)])
    np.testing.assert_array_equal(got, want)  # audit never bends bytes

    rep = reconstruct(tracer)                 # raises on any violation
    st = rep.stream("mig")
    assert st.state == "retired" and st.outcome == "done"
    assert st.n_parks == 2                    # hop + intra-server move
    assert st.n_admissions == 3
    assert st.n_migrations == 1
    assert rep.by_state() == {"retired": 1}
