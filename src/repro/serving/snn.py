"""Streaming SNN serving — stateful spike streams over one compiled step.

SNAP-V's accelerator is a *stateful* device: membrane potentials persist
across timesteps and spike events are consumed as they arrive, not as
pre-materialized rasters. This module is the host-runtime analogue of that
contract, built with a fixed-slot discipline (one jitted step of a
pinned batch shape, reused for all traffic — the continuous-batching
idiom):

  * :class:`SlotScheduler` — admission of stream ids into a fixed set of
    batch slots: FIFO waiting queue, FIFO slot reuse, no double
    assignment. Pure bookkeeping; property-tested.
  * :class:`SpikeServer` — owns the persistent slot carry
    ``{v, spikes}``, plus the synaptic current ``i`` of a current-based
    engine (via ``SpikeEngine.init_carry``), chunked
    :meth:`~SpikeServer.feed` (push N timesteps of external spikes per
    stream, get the spike raster / counts back), carry zeroing on
    eviction, and a closed-loop mode where the decoded output of step t
    drives the encoder at step t+1.
  * :class:`ModelStream` — a per-model view over a server running the
    *fused multi-model* engine: co-resident models stream together
    through one physical-array step, each seeing only its own input
    columns and cluster range (``AcceleratorSession.serve``).

Exactness contract (pinned by tests/test_serving_snn.py): for any chunking
of a spike raster — including ragged chunk boundaries and co-resident
traffic in other slots — the concatenated ``feed`` outputs are
byte-for-byte identical to one-shot ``SpikeEngine.run`` on that raster,
for every backend and reset mode. This falls out of the masked step: an
active slot advances exactly as the batch scan body would; an inactive
slot's carry is untouched.
"""

from __future__ import annotations

import collections
import dataclasses
import itertools
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.engine import SpikeEngine
from repro.obs.tracing import hot_span

__all__ = ["SlotScheduler", "SpikeServer", "ModelStream", "StreamStats"]

# Source-block granularity the measured-traffic counters account at —
# the kernels' block_src (one weight block per 128 source rows).
_OBS_BLOCK_SRC = 128


@jax.jit
def _zero_slots(carry: dict, mask) -> dict:
    """Every carry row whose ``mask`` entry is set, zeroed; the others
    untouched. One device dispatch however many slots are freed; the
    mask's shape is the slot count, so a server compiles this once."""
    return {k: jnp.where(mask[:, None], 0, x) for k, x in carry.items()}


class SlotScheduler:
    """Fixed-slot admission bookkeeping (no array state).

    Invariants (property-tested in tests/test_serving_scheduler.py):
      * an active uid occupies exactly one slot; no two share one;
      * a freed slot is handed to the LONGEST-waiting uid (FIFO fairness);
      * freed slots are reused in FIFO order, so slot assignment is a
        deterministic function of the attach/detach sequence.
    """

    def __init__(self, n_slots: int):
        if n_slots <= 0:
            raise ValueError(f"n_slots must be positive, got {n_slots}")
        self.n_slots = int(n_slots)
        self._slot_of: dict = {}                      # uid -> slot
        self._free = collections.deque(range(n_slots))
        self._waiting: collections.deque = collections.deque()

    # -- queries ----------------------------------------------------------
    @property
    def active(self) -> dict:
        """{uid: slot} of admitted streams (copy)."""
        return dict(self._slot_of)

    @property
    def free_slots(self) -> int:
        """Slots with no resident stream (an admission front door checks
        this before attaching, so its own queue — not the scheduler's
        waiting list — is the only place requests ever wait)."""
        return len(self._free)

    @property
    def free_slot_ids(self) -> list:
        """Free slot indices in FIFO-reuse order (copy) — migration
        passes pick targeted destinations from this."""
        return list(self._free)

    @property
    def waiting(self) -> list:
        """uids queued for admission, FIFO order (copy)."""
        return list(self._waiting)

    def slot_of(self, uid) -> int | None:
        """The uid's slot, or None while it waits."""
        if uid in self._slot_of:
            return self._slot_of[uid]
        if uid in self._waiting:
            return None
        raise KeyError(f"unknown stream {uid!r}")

    # -- transitions ------------------------------------------------------
    def submit(self, uid) -> int | None:
        """Admit uid into a free slot, or queue it. Returns the slot or
        None (queued)."""
        if uid in self._slot_of or uid in self._waiting:
            raise ValueError(f"stream {uid!r} already submitted")
        if self._free:
            slot = self._free.popleft()
            self._slot_of[uid] = slot
            return slot
        self._waiting.append(uid)
        return None

    def submit_at(self, uid, slot: int) -> int:
        """Admit uid into a SPECIFIC free slot (migration / rebalance
        placement). Unlike :meth:`submit`, never queues: a targeted
        restore must land now or fail loudly."""
        if uid in self._slot_of or uid in self._waiting:
            raise ValueError(f"stream {uid!r} already submitted")
        if slot not in self._free:
            raise ValueError(f"slot {slot} is not free")
        self._free.remove(slot)
        self._slot_of[uid] = slot
        return slot

    def release(self, uid) -> tuple[int, object | None]:
        """Free uid's slot; the FIFO-head waiter (if any) is admitted into
        it. Returns (freed_slot, admitted_uid_or_None). The caller MUST
        zero the slot's carry before the admitted stream is stepped."""
        if uid not in self._slot_of:
            raise KeyError(f"stream {uid!r} is not active")
        slot = self._slot_of.pop(uid)
        if self._waiting:
            nxt = self._waiting.popleft()
            self._slot_of[nxt] = slot
            return slot, nxt
        self._free.append(slot)
        return slot, None

    def cancel(self, uid) -> None:
        """Withdraw a WAITING uid (never touches slots)."""
        try:
            self._waiting.remove(uid)
        except ValueError:
            raise KeyError(f"stream {uid!r} is not waiting") from None


def decode_aer_chunk(stream, n_inputs: int, label: str = "AER chunk"
                     ) -> np.ndarray:
    """Validate + decode a single-lane ``(T, 1, n_inputs)`` AER chunk to
    its dense ``(T, n_inputs)`` raster — THE entry-point contract shared
    by :meth:`SpikeServer.feed_events` and
    :meth:`~repro.serving.frontend.AsyncSpikeFrontend.submit_events`
    (one lane per stream: the slot address inside the server is the
    server's business, not the caller's)."""
    from repro.events.aer import aer_to_dense

    T, lanes, n_src = stream.shape
    if lanes != 1 or n_src != n_inputs:
        raise ValueError(
            f"{label}: AER chunk must address (T, 1, {n_inputs}), "
            f"got {stream.shape}")
    return np.asarray(aer_to_dense(stream))[:, 0, :]


@dataclasses.dataclass
class StreamStats:
    """Per-stream accounting the server keeps while a stream lives."""

    uid: object
    steps: int = 0               # timesteps consumed so far
    spike_count: int = 0         # total output spikes emitted
    attached_at: float = 0.0     # wall clock at submit()
    admitted_at: float | None = None  # wall clock at slot grant


class SpikeServer:
    """Stateful streaming server: churning spike streams, one compiled step.

    The server pins the slot-batch shape ``(chunk_steps, n_slots)``: every
    :meth:`feed` call is processed as full chunks of ``chunk_steps``
    timesteps padded with inactive steps, so ONE XLA program (per engine)
    serves arbitrary ragged traffic. A feed of several chunks runs one
    chunk deep: chunk n+1 is dispatched before chunk n is read back, so
    its kernel overlaps that read-back. Slot carries persist across calls;
    :meth:`detach` zeroes the evicted slot so re-attachment starts from
    the unified power-on state (V = 0, no prior spikes);
    :meth:`detach_many` zeroes every slot one call frees in a single
    jitted dispatch.

    ``mesh`` scales the server out over devices: the engine is re-hosted
    as a :class:`~repro.distributed.spike_mesh.MeshSpikeEngine` (neuron
    shards hold their SRAM slice, slot batch sharded over the ``batch``
    axis) with byte-identical ``feed`` semantics — streaming slot-batches
    run sharded with no change to any caller.

    ``gate`` re-hosts the engine under another event-gate granularity
    (see :data:`repro.core.engine.GATES`): serving slot batches are mostly
    idle, so ``gate="per-example"`` — the batch-tile=1 mode — lets every
    silent slot skip its own weight traffic instead of riding along with
    the tile OR. Outputs are bit-identical under either gate.

    ``fuse_steps`` re-hosts the engine under a K-step fused kernel window
    (``SpikeEngine.with_fuse_steps``): each ``feed`` chunk scans K-step
    windows, fetching every weight block once per window instead of once
    per step. ``chunk_steps`` need NOT be K-aligned — the engine pads the
    window remainder with inactive steps under the same masked-slot
    contract that pads ragged chunks, so outputs stay byte-identical.

    ``metrics`` / ``tracer`` (a
    :class:`~repro.obs.metrics.MetricsRegistry` / an
    :class:`~repro.obs.tracing.SpanTracer`) opt the server into
    telemetry: per-chunk latency, slot occupancy, and measured
    SOP/weight-traffic counters (docs/observability.md tables the
    names). Instrumentation is a pure host-side read of arrays ``feed``
    already materializes — it NEVER runs inside the scan, so the
    byte-exactness contract is untouched; with both left ``None`` the
    datapath does zero extra work.
    """

    def __init__(self, engine: SpikeEngine, *, n_slots: int = 8,
                 chunk_steps: int = 8, mesh=None, gate: str | None = None,
                 fuse_steps: int | None = None, metrics=None, tracer=None):
        if chunk_steps <= 0:
            raise ValueError(f"chunk_steps must be positive, got {chunk_steps}")
        if gate is not None:
            engine = engine.with_gate(gate)
        if fuse_steps is not None:
            engine = engine.with_fuse_steps(fuse_steps)
        if mesh is not None and getattr(engine, "mesh", None) is not mesh:
            engine = engine.to_mesh(mesh)
        self.engine = engine
        self.n_slots = int(n_slots)
        self.chunk_steps = int(chunk_steps)
        self.scheduler = SlotScheduler(n_slots)
        self.carry = engine.init_carry(self.n_slots)
        self.streams: dict = {}      # uid -> StreamStats (active + waiting)
        self._auto_uid = itertools.count()
        self.total_steps = 0         # slot-timesteps consumed (all streams)
        self.metrics = metrics
        self.tracer = tracer
        self._prev_host = None       # (n_slots, n_phys) recurrent mirror
        if metrics is not None:
            from repro.core.energy import SOPS_PER_ROW

            w = np.asarray(engine.weights_raw)
            # per-source accounting vectors (trace.py semantics): real
            # nonzero fanout, and nonzero SOPS_PER_ROW-wide row segments
            self._fanout = np.count_nonzero(w, axis=1).astype(np.int64)
            pad = (-w.shape[1]) % SOPS_PER_ROW
            wp = np.pad(w, ((0, 0), (0, pad))) if pad else w
            self._rowseg = (
                (wp.reshape(w.shape[0], -1, SOPS_PER_ROW) != 0)
                .any(axis=2).sum(axis=1).astype(np.int64))
            self._n_src_blocks = -(-engine.n_sources // _OBS_BLOCK_SRC)
            self._prev_host = np.zeros(
                (self.n_slots, engine.n_phys), np.int32)
            metrics.gauge("snn_server_slots_total").set(self.n_slots)
            metrics.gauge("snn_server_slots_occupied").set(0)
            for state, x in self.carry.items():
                metrics.gauge("snn_server_carry_bytes").labels(
                    state=state).set(int(x.size) * x.dtype.itemsize)

    # -- observability ----------------------------------------------------
    def _obs_clock(self):
        if self.metrics is not None:
            return self.metrics.clock
        return self.tracer.clock

    def _obs_occupancy(self) -> None:
        if self.metrics is not None:
            self.metrics.gauge("snn_server_slots_occupied").set(
                len(self.scheduler.active))

    def _obs_count_chunk(self, ext_u: np.ndarray, out_u: np.ndarray,
                         prev_row: np.ndarray) -> np.ndarray:
        """Measured-event accounting for ONE stream's (n, ...) raster
        slice (the closed-loop single-step path; batch dispatches use the
        vectorized pass in :meth:`_obs_feed_chunk`): count source events,
        SOPs (events x real fanout), row fetches, and per-example-gate
        weight-block traffic, exactly as
        :func:`repro.events.trace.trace_run` would measure the same
        rasters. Returns the stream's new recurrent row. Host-side only."""
        m = self.metrics
        prev_u = np.concatenate([prev_row[None, :], out_u[:-1]], axis=0)
        src = np.concatenate([ext_u, prev_u], axis=1) != 0  # (n, S)
        m.counter("snn_server_source_events_total").labels(
            kind="external").inc(int(np.count_nonzero(ext_u)))
        m.counter("snn_server_source_events_total").labels(
            kind="recurrent").inc(int(np.count_nonzero(prev_u)))
        per_src = src.sum(axis=0, dtype=np.int64)  # (S,) event counts
        m.counter("snn_server_sops_total").inc(int(per_src @ self._fanout))
        m.counter("snn_server_row_fetches_total").inc(
            int(per_src @ self._rowseg))
        n, S = src.shape
        pad = self._n_src_blocks * _OBS_BLOCK_SRC - S
        if pad:
            src = np.pad(src, ((0, 0), (0, pad)))
        touched = int(src.reshape(n, self._n_src_blocks, _OBS_BLOCK_SRC)
                      .any(axis=2).sum())
        m.counter("snn_server_weight_blocks_fetched_total").inc(touched)
        m.counter("snn_server_weight_blocks_dense_total").inc(
            n * self._n_src_blocks)
        return out_u[-1]

    def _obs_feed_chunk(self, t_start: float, active: np.ndarray,
                        spikes: np.ndarray, ext: np.ndarray,
                        chunks: dict, t0: int) -> None:
        """Record one chunk dispatch: latency + step/spike counters, a
        chunk_step span, and measured-event accounting.

        The accounting — source events, SOPs (events x real fanout), row
        fetches, per-example-gate weight-block traffic, exactly as
        :func:`repro.events.trace.trace_run` would measure the same
        rasters — runs ONE vectorized pass over the whole ``(T, n_slots,
        ...)`` dispatch rather than per stream: the per-stream loop's
        numpy-call overhead was the single biggest telemetry cost
        (benchmarks/kernel_bench.py --obs-overhead gates the budget).
        Inactive (slot, step) rows are masked out, so the counters match
        the per-stream slicing bit-for-bit on ragged chunks."""
        from repro.obs.tracing import Span

        dt = self._obs_clock()() - t_start
        n_active = int(active.sum())
        if self.tracer is not None:
            now = self.tracer.clock()
            # participating stream uids (slot order), so timeline
            # reconstruction can attribute the chunk to its streams —
            # and audit that each one was admitted at dispatch time
            uids = [uid for uid, (slot, arr) in
                    sorted(chunks.items(), key=lambda kv: kv[1][0])
                    if arr.shape[0] - t0 > 0]
            # duration span timed by the caller (clock read bracketed the
            # dispatch; recording it here keeps the hot loop branch-free)
            self.tracer._record(Span(
                "chunk_step", None, now - dt, now,
                {"steps": n_active, "streams": len(chunks), "uids": uids}))
        if self.metrics is None:
            return
        m = self.metrics
        m.histogram("snn_server_chunk_latency_seconds").observe(dt)
        m.counter("snn_server_chunks_total").inc()
        m.counter("snn_server_steps_total").inc(n_active)
        m.counter("snn_server_spikes_total").inc(int(spikes.sum()))
        mask = active.astype(bool)                      # (T, n_slots)
        if n_active == 0:
            return
        # recurrent source rows: each stream's previous output (its
        # carried row for step 0), masked to the steps it actually ran;
        # the full-chunk case (every slot active every step — the steady
        # state) skips the masking copies entirely
        full = bool(mask.all())
        sp = spikes if full else np.where(mask[:, :, None], spikes, 0)
        prev = np.concatenate([self._prev_host[None], sp[:-1]], axis=0)
        ext_b = ext != 0                                # pre-masked zeros
        prev_b = prev != 0
        if not full:
            prev_b &= mask[:, :, None]
        m.counter("snn_server_source_events_total").labels(
            kind="external").inc(int(ext_b.sum()))
        m.counter("snn_server_source_events_total").labels(
            kind="recurrent").inc(int(prev_b.sum()))
        per_src = np.concatenate(
            [ext_b.sum(axis=(0, 1)), prev_b.sum(axis=(0, 1))]
        ).astype(np.int64)                              # (S,) event counts
        m.counter("snn_server_sops_total").inc(int(per_src @ self._fanout))
        m.counter("snn_server_row_fetches_total").inc(
            int(per_src @ self._rowseg))
        src = np.concatenate([ext_b, prev_b], axis=2)   # (T, n_slots, S)
        T, n_slots, S = src.shape
        pad = self._n_src_blocks * _OBS_BLOCK_SRC - S
        if pad:
            src = np.pad(src, ((0, 0), (0, 0), (0, pad)))
        touched = int(src.reshape(T, n_slots, self._n_src_blocks,
                                  _OBS_BLOCK_SRC).any(axis=3).sum())
        m.counter("snn_server_weight_blocks_fetched_total").inc(touched)
        m.counter("snn_server_weight_blocks_dense_total").inc(
            n_active * self._n_src_blocks)
        # roll each served stream's recurrent row forward to its LAST
        # active step's output (ragged streams end mid-chunk)
        n_per = mask.sum(axis=0)
        served = n_per > 0
        self._prev_host[served] = sp[n_per[served] - 1, served]

    # -- lifecycle --------------------------------------------------------
    def attach(self, uid=None):
        """Register a stream. Returns its uid; ``slot_of(uid)`` is None
        while it waits for a slot (FIFO admission on the next detach)."""
        if uid is None:
            uid = next(self._auto_uid)
            while uid in self.streams:  # caller-chosen uids may collide
                uid = next(self._auto_uid)
        now = time.perf_counter()
        slot = self.scheduler.submit(uid)
        st = StreamStats(uid=uid, attached_at=now)
        if slot is not None:
            st.admitted_at = now
        self.streams[uid] = st
        self._obs_occupancy()
        if self.tracer is not None:
            if slot is None:
                self.tracer.event("queued", uid)
            else:
                self.tracer.event("admitted", uid, slot=slot)
        return uid

    def detach(self, uid, *, reason: str = "detached") -> StreamStats:
        """Evict a stream. Frees + ZEROES its slot (the next occupant must
        power up from clean state); the longest-waiting stream, if any, is
        admitted into the freed slot.

        ``reason`` is observational only (the datapath is identical for
        every reason): it becomes the stream's terminal ``retired`` span
        outcome — or, with ``reason="parked"``, a ``parked`` span
        instead, for callers that park the carry in a connector (spill,
        migration, rolling drain) so the timeline continues through the
        later restore instead of ending here."""
        return self.detach_many([uid], reason=reason)[0]

    def detach_many(self, uids, *, reason: str = "detached"
                    ) -> list[StreamStats]:
        """Evict several streams: :meth:`detach` for each uid in order,
        with every slot they free zeroed by ONE jitted dispatch before
        this returns (so before any later feed, snapshot or restore reads
        the carry). Waiters are admitted into the freed slots in the same
        FIFO order a detach loop would give; a uid still waiting for a
        slot is just withdrawn. An empty list dispatches nothing."""
        stats = []
        freed = np.zeros(self.n_slots, bool)
        try:
            for uid in uids:
                st = self.streams.pop(uid)
                self._obs_detached(uid, st, reason)
                stats.append(st)
                if self.scheduler.slot_of(uid) is None:
                    self.scheduler.cancel(uid)
                    continue
                slot, admitted = self.scheduler.release(uid)
                freed[slot] = True
                if self._prev_host is not None:
                    self._prev_host[slot] = 0
                if admitted is not None:
                    self.streams[admitted].admitted_at = time.perf_counter()
                    if self.tracer is not None:
                        self.tracer.event("admitted", admitted, slot=slot)
        finally:
            # a uid that raises leaves the slots freed before it zeroed
            if freed.any():
                self.carry = _zero_slots(self.carry, freed)
                if self.metrics is not None:
                    self.metrics.counter(
                        "snn_server_slot_resets_total").inc(int(freed.sum()))
                    self.metrics.counter(
                        "snn_server_slot_reset_dispatches_total").inc()
            if stats:
                self._obs_occupancy()
        return stats

    def _obs_detached(self, uid, st: "StreamStats", reason: str) -> None:
        if self.tracer is None:
            return
        if reason == "parked":
            self.tracer.event("parked", uid, steps_done=int(st.steps))
        else:
            self.tracer.event("retired", uid, outcome=reason,
                              steps_done=int(st.steps))

    def slot_of(self, uid) -> int | None:
        return self.scheduler.slot_of(uid)

    # -- carry migration (the stream-state connector) ---------------------
    def slot_params(self) -> dict:
        """This server's carry-compatibility identity (see
        :func:`repro.serving.connector.slot_params_of`)."""
        from repro.serving.connector import slot_params_of

        return slot_params_of(self.engine)

    def snapshot_stream(self, uid) -> "CarrySnapshot":
        """A stream's portable state — carry rows + counters — WITHOUT
        disturbing it (the stream keeps running; checkpointing uses
        this). The stream must hold a slot."""
        from repro.serving.connector import CarrySnapshot

        slot = self.scheduler.slot_of(uid)
        if slot is None:
            raise ValueError(
                f"stream {uid!r} is waiting for a slot; nothing to "
                f"snapshot (its carry does not exist yet)")
        st = self.streams[uid]
        return CarrySnapshot(
            stream_id=uid,
            slot_params=self.slot_params(),
            # every state of the carry: v and spikes, and the synaptic
            # current i of a current-based engine
            arrays={k: np.asarray(x[slot], np.int32)
                    for k, x in self.carry.items()},
            meta={"steps": int(st.steps),
                  "spike_count": int(st.spike_count)},
        )

    def detach_stream(self, uid, connector) -> "CarrySnapshot":
        """Drain a stream to ``connector``: snapshot, park, then detach
        (the slot is zeroed and handed on exactly like :meth:`detach`).
        The stream is gone from this server but not from the world —
        :meth:`attach_stream` restores it anywhere compatible."""
        snap = self.snapshot_stream(uid)
        connector.insert(uid, snap)
        self.detach(uid, reason="parked")
        return snap

    def attach_stream(self, source, uid=None, *, slot: int | None = None):
        """Admit a stream whose carry starts from a snapshot instead of
        power-on zero — the restore half of live migration.

        Args:
          source: a :class:`~repro.serving.connector.CarrySnapshot`, or a
            connector to ``select`` (and, on success, ``evict``) the
            snapshot from under ``uid``.
          uid: the restored stream's id on THIS server (defaults to the
            snapshot's recorded id when restoring from a connector, else
            a fresh auto id). Must not collide with a live stream.
          slot: targeted placement (rebalance); default = FIFO free slot.

        The snapshot is slot-params / dtype / shape checked before one
        byte lands; a restored stream needs a slot NOW (its state cannot
        wait in a queue), so no free slot raises ``RuntimeError``.
        """
        from repro.serving.connector import CarrySnapshot

        connector = None
        if isinstance(source, CarrySnapshot):
            snap = source
            if uid is None:
                uid = next(self._auto_uid)
                while uid in self.streams:
                    uid = next(self._auto_uid)
        else:
            connector = source
            if uid is None:
                raise ValueError(
                    "attach_stream from a connector needs the stream id")
            snap = connector.select(uid)
            if snap is None:
                raise KeyError(f"no parked carry for stream {uid!r}")
        snap.check_compatible(self.slot_params())
        if self.scheduler.free_slots == 0:
            raise RuntimeError(
                f"cannot restore stream {uid!r}: no free slot (a restored "
                f"carry cannot wait in the admission queue)")
        now = time.perf_counter()
        if slot is None:
            slot = self.scheduler.submit(uid)
        else:
            slot = self.scheduler.submit_at(uid, slot)
        self.carry = {k: x.at[slot].set(jnp.asarray(snap.arrays[k]))
                      for k, x in self.carry.items()}
        self.streams[uid] = StreamStats(
            uid=uid,
            steps=int(snap.meta.get("steps", 0)),
            spike_count=int(snap.meta.get("spike_count", 0)),
            attached_at=now, admitted_at=now,
        )
        if self._prev_host is not None:
            self._prev_host[slot] = np.asarray(
                snap.arrays["spikes"], np.int32)
        self._obs_occupancy()
        if self.tracer is not None:
            self.tracer.event("admitted", uid, slot=slot, resumed=True)
        if connector is not None:
            connector.evict(uid)
        return uid

    def checkpoint_streams(self, connector) -> list:
        """Park a snapshot of EVERY live stream in ``connector`` without
        disturbing any of them — the crash-recovery write barrier. With a
        :class:`~repro.serving.connector.FileCarryConnector` this is what
        lets a dead server's streams resume bit-clean on a fresh one.
        Returns the checkpointed uids."""
        uids = sorted(self.scheduler.active, key=repr)
        for uid in uids:
            connector.insert(uid, self.snapshot_stream(uid))
        return uids

    def restore_streams(self, connector, uids=None) -> list:
        """Re-admit parked streams (all of ``connector``'s, or ``uids``)
        into free slots, consuming their snapshots; restores what fits
        and leaves the rest parked. Returns the restored uids."""
        if uids is None:
            uids = connector.stream_ids()
        restored = []
        for uid in uids:
            if self.scheduler.free_slots == 0:
                break
            self.attach_stream(connector, uid)
            restored.append(uid)
        return restored

    # -- streaming --------------------------------------------------------
    def feed(self, inputs: dict) -> dict:
        """Push timesteps of external spikes for one or more streams.

        Args:
          inputs: {uid: (T_uid, n_inputs) array in {0,1}} — ragged T per
            stream is fine; every uid must hold a slot.
        Returns:
          {uid: {'spikes': (T_uid, n_phys) int32 raster,
                 'counts': (n_phys,) int32 spike counts over the chunk}}.

        Slots not mentioned (or past their stream's T) are masked
        inactive: their carries are bit-for-bit untouched. A zero-length
        chunk is a per-stream no-op (empty raster back, carry untouched)
        so front-ends can feed "whatever arrived this round".
        """
        if not inputs:
            return {}
        with hot_span("snn.feed"):
            return self._feed(inputs)

    def _feed(self, inputs: dict) -> dict:
        out: dict = {}
        chunks: dict = {}
        n_phys = self.engine.n_phys
        for uid, arr in inputs.items():
            slot = self.scheduler.slot_of(uid)
            if slot is None:
                raise ValueError(
                    f"stream {uid!r} is waiting for a slot; cannot feed"
                )
            arr = np.asarray(arr)
            if arr.ndim != 2 or arr.shape[1] != self.engine.n_inputs:
                raise ValueError(
                    f"stream {uid!r}: chunk must be "
                    f"(T, {self.engine.n_inputs}), got {arr.shape}"
                )
            if arr.shape[0] == 0:
                out[uid] = {"spikes": np.zeros((0, n_phys), np.int32),
                            "counts": np.zeros((n_phys,), np.int32)}
                continue
            chunks[uid] = (slot, arr.astype(np.int32))
        if not chunks:
            return out

        T_max = max(arr.shape[0] for _, arr in chunks.values())
        rasters = []                                  # (T, n_slots, n_phys)
        # One chunk deep: chunk n+1 is dispatched, its carry chained on
        # the device, before chunk n is read back, so its kernel runs
        # while chunk n's raster comes home. At most two are unread.
        pending = None
        for t0 in range(0, T_max, self.chunk_steps):
            ahead = pending is not None
            dispatched = self._dispatch_chunk(chunks, t0, ahead)
            if ahead:
                rasters.append(self._read_back(chunks, *pending))
            pending = dispatched
        rasters.append(self._read_back(chunks, *pending))

        cs = self.chunk_steps
        with hot_span("snn.feed.split", streams=len(chunks),
                      chunks=len(rasters)):
            # One counting pass for every slot: inactive slot-steps report
            # zero spikes, so whole chunks sum to each stream's own T
            # steps, and int32 is exact (at most T_max steps a slot).
            acc = rasters[0].sum(axis=0, dtype=np.int32)
            for r in rasters[1:]:
                acc += r.sum(axis=0, dtype=np.int32)
            slots = [slot for slot, _ in chunks.values()]
            counts = acc[slots].astype(np.int_)   # numpy's sum of int32
            totals = counts.sum(axis=1).tolist()
            for (uid, (slot, arr)), row, total in zip(chunks.items(),
                                                      counts, totals):
                T = arr.shape[0]
                # a copy per stream: no result may pin the round's buffer
                if len(rasters) == 1:
                    raster = rasters[0][:T, slot].copy()
                else:
                    raster = np.concatenate(
                        [r[:min(cs, T - t0), slot]
                         for t0, r in zip(range(0, T, cs), rasters)], axis=0)
                st = self.streams[uid]
                st.steps += T
                st.spike_count += total
                out[uid] = {"spikes": raster, "counts": row}
        return out

    def _dispatch_chunk(self, chunks: dict, t0: int, ahead: bool) -> tuple:
        """Assemble and dispatch the chunk of steps ``t0 ..`` of every
        stream, and start its raster's copy to the host. ``ahead``: the
        feed's previous chunk is still unread."""
        n_in = self.engine.n_inputs
        with hot_span("snn.feed.assemble"):
            ext = np.zeros((self.chunk_steps, self.n_slots, n_in), np.int32)
            active = np.zeros((self.chunk_steps, self.n_slots), np.int32)
            for uid, (slot, arr) in chunks.items():
                n = min(self.chunk_steps, arr.shape[0] - t0)
                if n <= 0:
                    continue
                ext[:n, slot] = arr[t0:t0 + n]
                active[:n, slot] = 1
        obs = self.metrics is not None or self.tracer is not None
        t_chunk = self._obs_clock()() if obs else None
        # ``ahead`` appears only where set: a lone chunk's span is as before
        with hot_span("snn.feed.dispatch",
                      h2d_bytes=ext.nbytes + active.nbytes,
                      **({"ahead": 1} if ahead else {})):
            self.carry, spikes = self.engine.step_chunk(
                self.carry, jnp.asarray(ext), jnp.asarray(active))
            spikes.copy_to_host_async()
        if ahead and self.metrics is not None:
            self.metrics.counter("snn_server_chunks_ahead_total").inc()
        return spikes, ext, active, t0, t_chunk

    def _read_back(self, chunks: dict, spikes, ext: np.ndarray,
                   active: np.ndarray, t0: int, t_chunk) -> np.ndarray:
        """Wait for a dispatched chunk's raster and account for it."""
        with hot_span("snn.feed.readback", d2h_bytes=spikes.nbytes):
            spikes = np.asarray(spikes)
        self.total_steps += int(active.sum())
        # telemetry stays outside the phases: its cost is its own
        if t_chunk is not None:
            self._obs_feed_chunk(t_chunk, active, spikes, ext, chunks, t0)
        return spikes

    def feed_events(self, inputs: dict, *, out_capacity: int | None = None,
                    out_policy: str = "error") -> dict:
        """Event-driven :meth:`feed`: AER streams in, optionally AER out.

        The sparse front door of the server — what arrives from an event
        source (sensor, upstream model) is a stream of ``(t, slot,
        source)`` addresses, not a raster. Each stream is decoded by one
        jitted op, pushed through the SAME masked chunk step ``feed``
        uses (so the byte-exactness contract carries over verbatim), and
        the spike raster comes back — optionally re-encoded as AER.

        Args:
          inputs: {uid: AERStream} — each stream addresses a dense
            ``(T_uid, 1, n_inputs)`` chunk (slot axis 1: a stream is one
            lane; the slot address inside the server is the server's
            business, not the caller's).
          out_capacity: when set, each stream's result also carries
            ``'events'``: its output raster as an AER stream of at most
            this many events under ``out_policy``.
        Returns:
          {uid: {'spikes', 'counts'[, 'events']}} exactly as :meth:`feed`.
        """
        from repro.events.aer import dense_to_aer

        dense_inputs = {
            uid: decode_aer_chunk(stream, self.engine.n_inputs,
                                  f"stream {uid!r}")
            for uid, stream in inputs.items()
        }
        out = self.feed(dense_inputs)
        if out_capacity is not None:
            for uid, res in out.items():
                res["events"] = dense_to_aer(
                    res["spikes"][:, None, :], out_capacity,
                    policy=out_policy)
        return out

    def run_closed_loop(self, uid, controller, num_steps: int, ext0) -> dict:
        """Closed-loop mode: output of step t feeds the encoder at t+1.

        Args:
          uid: an admitted stream.
          controller: ``spikes_t (n_phys,) int32 -> ext_{t+1} (n_inputs,)``
            — decode + environment + encode, the perception->action loop.
          num_steps: timesteps to run.
          ext0: (n_inputs,) external spikes for step 0.
        Returns:
          {'spikes': (num_steps, n_phys) int32, 'counts': (n_phys,)}.

        Uses a T=1 slot-batch step (its own cached XLA program) so other
        streams' slots stay untouched between iterations.
        """
        slot = self.scheduler.slot_of(uid)
        if slot is None:
            raise ValueError(f"stream {uid!r} is waiting for a slot")
        ext_t = np.asarray(ext0, np.int32)
        if ext_t.shape != (self.engine.n_inputs,):
            raise ValueError(
                f"ext0 must be ({self.engine.n_inputs},), got {ext_t.shape}"
            )
        n_in = self.engine.n_inputs
        rows = []
        active = np.zeros((1, self.n_slots), np.int32)
        active[0, slot] = 1
        active = jnp.asarray(active)
        for t in range(num_steps):
            ext = np.zeros((1, self.n_slots, n_in), np.int32)
            ext[0, slot] = ext_t
            self.carry, spikes = self.engine.step_chunk(
                self.carry, jnp.asarray(ext), active)
            self.total_steps += 1
            spikes_t = np.asarray(spikes)[0, slot]
            if self.metrics is not None:
                m = self.metrics
                m.counter("snn_server_chunks_total").inc()
                m.counter("snn_server_steps_total").inc(1)
                m.counter("snn_server_spikes_total").inc(
                    int(spikes_t.sum()))
                self._prev_host[slot] = self._obs_count_chunk(
                    ext_t[None, :], spikes_t[None, :],
                    self._prev_host[slot])
            rows.append(spikes_t)
            if t + 1 < num_steps:
                ext_t = np.asarray(controller(spikes_t), np.int32)
                if ext_t.shape != (n_in,):
                    raise ValueError(
                        f"controller must return ({n_in},) external "
                        f"spikes, got shape {ext_t.shape} at step {t}"
                    )
        raster = np.stack(rows, axis=0)
        st = self.streams[uid]
        st.steps += num_steps
        st.spike_count += int(raster.sum())
        return {"spikes": raster, "counts": raster.sum(axis=0)}


class ModelStream:
    """Per-model streaming view over a (possibly fused multi-model) server.

    ``AcceleratorSession.serve`` hands these out: all models sharing a LIF
    configuration stream through ONE fused-engine :class:`SpikeServer`
    (one compiled step for the whole co-resident set); each view embeds
    its model's external spikes at the model's column offset and decodes
    only its own cluster range — the same address-space isolation the
    fused batch path (``run_all``) provides.
    """

    def __init__(self, server: SpikeServer, *, name: str, n_inputs: int,
                 ext_offset: int, phys_slice: tuple[int, int],
                 output_map: np.ndarray, stale_check=None, frontend=None):
        self.server = server
        self.name = name
        self.n_inputs = int(n_inputs)
        self.ext_offset = int(ext_offset)
        self.phys_slice = (int(phys_slice[0]), int(phys_slice[1]))
        self.output_map = np.asarray(output_map)
        self._stale_check = stale_check
        #: the group's shared AsyncSpikeFrontend when this view was served
        #: with ``session.serve(..., frontend=)`` (None otherwise).
        self.frontend = frontend

    def _check_fresh(self) -> None:
        if self._stale_check is not None and self._stale_check():
            raise RuntimeError(
                f"stale ModelStream view for {self.name!r}: a later deploy "
                f"changed the fused layout; call session.serve() again"
            )

    # lifecycle passes straight through to the shared server
    def attach(self, uid=None):
        self._check_fresh()
        return self.server.attach(uid)

    def detach(self, uid, *, reason: str = "detached") -> StreamStats:
        return self.server.detach(uid, reason=reason)

    def slot_of(self, uid):
        return self.server.slot_of(uid)

    def embed(self, chunk: np.ndarray) -> np.ndarray:
        """Model-local (T, n_inputs) spikes -> fused-layout external rows
        (zero everywhere but this model's input columns)."""
        chunk = np.asarray(chunk, np.int32)
        fused = np.zeros((chunk.shape[0], self.server.engine.n_inputs),
                         np.int32)
        fused[:, self.ext_offset:self.ext_offset + self.n_inputs] = chunk
        return fused

    def decode(self, raster: np.ndarray) -> dict:
        """Fused physical raster -> this model's masked spikes + decoded
        output counts / prediction (its cluster range only)."""
        lo, hi = self.phys_slice
        spikes = np.zeros_like(raster)
        spikes[:, lo:hi] = raster[:, lo:hi]  # mask to the model's clusters
        counts = spikes.sum(axis=0)
        return {
            "spikes": spikes,
            "output_counts": counts[self.output_map],
            "predictions": int(np.argmax(counts[self.output_map])),
        }

    def submit(self, chunk, **kwargs):
        """Async entry: enqueue a full model-local ``(T, n_inputs)``
        raster on the group's shared request queue and return a
        :class:`~repro.serving.frontend.RequestHandle` (the frontend's
        pump admits + serves it between chunk steps; the decoded result
        is byte-identical to a synchronous :meth:`feed` of the same
        raster). Requires the view to have been served with
        ``session.serve(..., frontend=)``."""
        self._check_fresh()
        if self.frontend is None:
            raise RuntimeError(
                f"view {self.name!r} has no async frontend; pass "
                f"frontend=FrontendConfig(...) to session.serve()")
        return self.frontend.submit(chunk, view=self, **kwargs)

    def submit_events(self, stream, **kwargs):
        """AER-native :meth:`submit`: a ``(T, 1, n_inputs)`` model-local
        AER stream in, same async handle back."""
        self._check_fresh()
        if self.frontend is None:
            raise RuntimeError(
                f"view {self.name!r} has no async frontend; pass "
                f"frontend=FrontendConfig(...) to session.serve()")
        return self.frontend.submit_events(stream, view=self, **kwargs)

    def feed(self, uid, chunk) -> dict:
        """Push (T, n_inputs) model-local external spikes; get the model's
        masked raster + decoded output counts for the chunk back."""
        return self.feed_many({uid: chunk})[uid]

    def feed_many(self, inputs: dict) -> dict:
        """Batched feed: {uid: (T_uid, n_inputs) chunk} for several of
        this model's streams in ONE slot-batch dispatch (the same
        multi-stream call :meth:`SpikeServer.feed` takes; front-ends
        should prefer this per round over per-stream ``feed`` loops)."""
        self._check_fresh()
        fused: dict = {}
        for uid, chunk in inputs.items():
            chunk = np.asarray(chunk, np.int32)
            if chunk.ndim != 2 or chunk.shape[1] != self.n_inputs:
                raise ValueError(
                    f"stream {uid!r}: chunk must be (T, {self.n_inputs}), "
                    f"got {chunk.shape}"
                )
            fused[uid] = self.embed(chunk)
        out = self.server.feed(fused)
        return {uid: self.decode(o["spikes"]) for uid, o in out.items()}

    def run_closed_loop(self, uid, controller, num_steps: int, ext0) -> dict:
        """Closed loop at timestep granularity: ``controller`` sees the
        model's masked spike vector and returns the next model-local
        external spike vector."""
        self._check_fresh()
        lo, hi = self.phys_slice

        def fused_controller(spikes_t):
            local = np.zeros_like(spikes_t)
            local[lo:hi] = spikes_t[lo:hi]
            nxt = np.asarray(controller(local), np.int32)
            if nxt.shape != (self.n_inputs,):
                raise ValueError(
                    f"controller must return ({self.n_inputs},) "
                    f"model-local external spikes, got shape {nxt.shape}"
                )
            full = np.zeros((self.server.engine.n_inputs,), np.int32)
            full[self.ext_offset:self.ext_offset + self.n_inputs] = nxt
            return full

        ext0 = np.asarray(ext0, np.int32)
        if ext0.shape != (self.n_inputs,):
            raise ValueError(
                f"ext0 must be ({self.n_inputs},), got {ext0.shape}"
            )
        full0 = np.zeros((self.server.engine.n_inputs,), np.int32)
        full0[self.ext_offset:self.ext_offset + self.n_inputs] = ext0
        out = self.server.run_closed_loop(uid, fused_controller, num_steps,
                                          full0)
        return self.decode(out["spikes"])
