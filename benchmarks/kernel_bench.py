"""Kernel micro-benchmarks: the fused accelerator timestep vs its unfused
reference, at the paper's 1024-neuron scale (CPU wall time is NOT the
deliverable — the structural claim is the event-gated kernel touches fewer
weight blocks; timings are still printed for regression tracking).

``--backend`` additionally benchmarks the full SpikeEngine scan per
backend, so the Pallas-vs-reference speedup is measurable on real
inference timesteps (one engine, carries included) rather than only on
the isolated kernel call.

``--devices N`` (optionally with ``--mesh KNxKB``) adds the scale-out
axis: every engine-scan and streaming bench also runs on a mesh-sharded
``MeshSpikeEngine`` (N faked host devices on CPU; real devices on TPU),
so the per-timestep cost of the neuron-shard spike exchange is tracked
next to the single-device numbers.

``--sparsity S1,S2,...`` adds the event-gating axis: gated-vs-dense
weight-block traffic and SOP reduction (measured from real rasters via
``events.trace``) per gate granularity (batch-tile vs per-example, the
batch-tile=1 serving mode) x backend x serving occupancy.

``--async`` adds the front-door axis: the ``AsyncSpikeFrontend`` request
queue driven open-loop at under/overload on a virtual clock — outcome
counts (done/rejected/dropped/expired), queue depth, and queue-wait vs
service percentiles per backpressure policy (BENCH_pr5.json).

``--fuse-steps K1,K2,...`` adds the K-step fusion axis (BENCH_pr6.json):
engine-scan steps/s and weight-block traffic per K x backend x sparsity x
serving occupancy. Traffic is counted twice and cross-checked — the
kernel-side gate scalars (``ops.ext_gate_activity``, the DMAs the fused
kernel actually issues) against the ``events.trace`` window-OR model —
so the ~1/K per-step traffic claim is measured, not estimated.

``--json out.json`` writes all results as machine-readable records per
(backend, batch, occupancy, sparsity, gate, devices, fuse_steps) — the
repo's ``BENCH_*.json`` perf trajectory (schema versioned in
``benchmarks/common.py``; every record carries every axis).
"""

from __future__ import annotations

import argparse
import time

import jax.numpy as jnp
import numpy as np

from benchmarks import common
from benchmarks.common import emit, time_call
from repro.core.engine import (BACKENDS, GATES, DecaySpec, SpikeEngine,
                               sources_raster)
from repro.distributed.spike_mesh import (ensure_host_devices,
                                          make_spike_mesh, parse_mesh_spec)
from repro.events import trace
from repro.launch.compile_cache import enable_compile_cache
from repro.serving.frontend import AsyncSpikeFrontend
from repro.serving.snn import SpikeServer

# NOTE: repro.kernels.ops/ref import the Pallas TPU machinery, which
# INITIALIZES the XLA backend at import time — on CPU that would lock in
# the device count before --devices can force faked host devices (on a
# TPU host --devices counts real chips and nothing is faked). They are
# imported inside main(), after ensure_host_devices().


def bench_engine_backends(backends, *, batch: int, activity: float,
                          steps: int = 4, mesh=None) -> None:
    """Per-backend engine-scan throughput at the 1024-neuron scale."""
    devices = 1 if mesh is None else mesh.size
    rng = np.random.default_rng(0)
    n_in, P = 784, 1024
    W = jnp.asarray(rng.integers(-2**13, 2**13, (n_in + P, P)), jnp.int32)
    ext = jnp.asarray(
        rng.random((steps, batch, n_in)) < activity, jnp.int32)
    for backend in backends:
        engine = SpikeEngine(W, n_in, decay=DecaySpec.shift(0.25),
                             threshold_raw=1 << 16, reset_mode="zero",
                             backend=backend)
        if mesh is not None:
            engine = engine.to_mesh(mesh)
        t_run = time_call(lambda e=engine: e.run(ext)["spikes"])
        per_step = t_run / steps
        emit(f"engine/timestep_{backend}_d{devices}", per_step,
             f"us/timestep B={batch} S={n_in + P} P={P} "
             f"activity={activity} T={steps} devices={devices}",
             kind="engine_scan", backend=backend, gate=engine.gate,
             batch=batch, activity=activity, devices=devices,
             per_timestep=True)


def bench_streaming(backends, *, n_slots: int, activity: float,
                    chunk_steps: int = 8, rounds: int = 3,
                    mesh=None) -> None:
    """The serving axis: masked slot-batch chunk step (SpikeServer.feed)
    vs the one-shot batch scan on the same raster, plus the cost of a
    partially occupied slot batch (the serving occupancy regime)."""
    devices = 1 if mesh is None else mesh.size
    rng = np.random.default_rng(0)
    n_in, P = 784, 1024
    W = jnp.asarray(rng.integers(-2**13, 2**13, (n_in + P, P)), jnp.int32)
    T = chunk_steps * rounds
    rasters = [
        (rng.random((T, n_in)) < activity).astype(np.int32)
        for _ in range(n_slots)
    ]
    batch = jnp.asarray(np.stack(rasters, axis=1))  # (T, n_slots, n_in)
    for backend in backends:
        engine = SpikeEngine(W, n_in, decay=DecaySpec.shift(0.25),
                             threshold_raw=1 << 16, reset_mode="zero",
                             backend=backend)
        if mesh is not None:
            engine = engine.to_mesh(mesh)
        t_batch = time_call(lambda e=engine: e.run(batch)["spikes"])
        emit(f"streaming/batch_scan_{backend}_d{devices}", t_batch / T,
             f"us/timestep B={n_slots} T={T} devices={devices} "
             f"(one-shot run)",
             kind="streaming_batch_scan", backend=backend,
             gate=engine.gate, batch=n_slots, activity=activity,
             devices=devices, per_timestep=True)

        for occupancy in (1.0, 0.25):
            n_live = max(1, int(round(occupancy * n_slots)))

            def serve(e=engine, n_live=n_live):
                srv = SpikeServer(e, n_slots=n_slots,
                                  chunk_steps=chunk_steps)
                uids = [srv.attach() for _ in range(n_live)]
                for t0 in range(0, T, chunk_steps):
                    srv.feed({u: rasters[i][t0:t0 + chunk_steps]
                              for i, u in enumerate(uids)})
                return srv.total_steps

            t_srv = time_call(serve)
            emit(f"streaming/feed_{backend}_occ{occupancy:g}_d{devices}",
                 t_srv / T,
                 f"us/timestep {n_live}/{n_slots} slots live, "
                 f"chunk={chunk_steps} devices={devices} "
                 f"(masked step, per-chunk host hop)",
                 kind="streaming_feed", backend=backend, gate=engine.gate,
                 batch=n_slots, occupancy=occupancy, activity=activity,
                 devices=devices, per_timestep=True)


def bench_event_gating(backends, sparsities, *, batch: int,
                       n_slots: int = 8, steps: int = 4) -> None:
    """The sparsity axis: event-gated vs dense work, from real rasters.

    For each source-activity level this records (a) the gated-vs-dense
    weight-block traffic and SOP reduction under both gate granularities
    (accounting via ``events.trace`` — the structural claim), (b) the
    engine-scan time per backend x gate, and (c) the serving occupancy
    regime: a slot batch with idle slots, where the batch-tile=1
    (per-example) gate skips the idle slots' weight traffic entirely
    while the batch-tile OR cannot.
    """
    rng = np.random.default_rng(0)
    n_in, P = 784, 1024
    W = jnp.asarray(rng.integers(-2**13, 2**13, (n_in + P, P)), jnp.int32)
    ref = SpikeEngine(W, n_in, decay=DecaySpec.shift(0.25),
                      threshold_raw=1 << 16, reset_mode="zero")
    for sparsity in sparsities:
        ext = jnp.asarray(
            rng.random((steps, batch, n_in)) < sparsity, jnp.int32)
        rep = trace.trace_run(ref, ext, ref.run(ext)["spikes"])
        for gate in GATES:
            touched, total = rep.blocks[gate]
            emit(f"gating/traffic_{gate}_s{sparsity:g}", None,
                 f"{touched}/{total} weight blocks "
                 f"({100 * rep.traffic_ratio(gate):.1f}% of dense), "
                 f"SOPs {100 * rep.sop_ratio:.1f}% of dense, B={batch}",
                 kind="event_gating", gate=gate, sparsity=sparsity,
                 batch=batch, blocks_touched=touched, blocks_total=total,
                 traffic_ratio=round(rep.traffic_ratio(gate), 4),
                 measured_sops=rep.measured_sops,
                 dense_sops=rep.dense_sops,
                 sop_ratio=round(rep.sop_ratio, 4))
        for backend in backends:
            # the gate is a kernel concept: the reference matmul ignores
            # it, so timing reference x per-example would record pure jit
            # noise as a gate effect — one row there.
            for gate in (GATES if backend != "reference"
                         else ("batch-tile",)):
                engine = SpikeEngine(
                    W, n_in, decay=DecaySpec.shift(0.25),
                    threshold_raw=1 << 16, reset_mode="zero",
                    backend=backend, gate=gate)
                t = time_call(lambda e=engine: e.run(ext)["spikes"])
                emit(f"gating/timestep_{backend}_{gate}_s{sparsity:g}",
                     t / steps,
                     f"us/timestep B={batch} sparsity={sparsity} "
                     f"gate={gate}",
                     kind="event_gating_time", backend=backend, gate=gate,
                     sparsity=sparsity, batch=batch, per_timestep=True)
        # serving occupancy: only a fraction of slots carry a live stream
        # (idle slots are silent end-to-end — no input, no spikes)
        for occupancy in (1.0, 0.25, 0.125):
            n_live = max(1, int(round(occupancy * n_slots)))
            slot_ext = np.zeros((steps, n_slots, n_in), np.int32)
            slot_ext[:, :n_live] = np.asarray(
                rng.random((steps, n_live, n_in)) < sparsity, np.int32)
            srep = trace.trace_run(
                ref, slot_ext, ref.run(jnp.asarray(slot_ext))["spikes"])
            for gate in GATES:
                touched, total = srep.blocks[gate]
                emit(f"gating/serving_{gate}_occ{occupancy:g}"
                     f"_s{sparsity:g}", None,
                     f"{n_live}/{n_slots} slots live: {touched}/{total} "
                     f"weight blocks "
                     f"({100 * srep.traffic_ratio(gate):.1f}% of dense)",
                     kind="event_gating_serving", gate=gate,
                     occupancy=occupancy, sparsity=sparsity,
                     n_slots=n_slots, blocks_touched=touched,
                     blocks_total=total,
                     traffic_ratio=round(srep.traffic_ratio(gate), 4))


def bench_fuse_steps(backends, fuse_list, sparsities, *, batch: int,
                     n_slots: int = 8, steps: int = 8) -> None:
    """The K-step fusion axis: per-step weight traffic shrinking ~1/K.

    For each sparsity level this records (a) the fused kernel's
    weight-block traffic per K from the ``events.trace`` window-OR model,
    CROSS-CHECKED against the gate scalars the kernel actually DMAs by
    (``ops.ext_gate_activity`` — the two counters must agree exactly, or
    this bench raises), (b) engine-scan time per backend x K (the
    reference backend has no fused path — ``SpikeEngine`` carries K but
    executes per step — so it is timed once at K=1 as the baseline), and
    (c) the serving occupancy regime: fused per-example (tile_batch=1)
    traffic on a slot batch with idle slots.
    """
    from repro.kernels import ops  # deferred: see NOTE at module top

    rng = np.random.default_rng(0)
    n_in, P = 784, 1024
    W = jnp.asarray(rng.integers(-2**13, 2**13, (n_in + P, P)), jnp.int32)
    ref_engine = SpikeEngine(W, n_in, decay=DecaySpec.shift(0.25),
                             threshold_raw=1 << 16, reset_mode="zero")
    for sparsity in sparsities:
        ext = jnp.asarray(
            rng.random((steps, batch, n_in)) < sparsity, jnp.int32)
        out = ref_engine.run(ext)["spikes"]
        sources = np.asarray(sources_raster(ext, out))
        for K in fuse_list:
            touched, total = trace.fused_block_traffic(
                sources, n_in, fuse_steps=K)
            # counter cross-check: the trace model's window-OR count of
            # EXT blocks must equal the number of nonzero gate scalars
            # the fused kernel schedules DMAs from
            ext_trace = trace.block_traffic(
                np.asarray(ext), fuse_steps=K)[0]
            ext_kernel = int(
                (np.asarray(ops.ext_gate_activity(ext, fuse_steps=K))
                 > 0).sum())
            if ext_kernel != ext_trace:
                raise AssertionError(
                    f"fused traffic counters disagree at K={K}: kernel "
                    f"gate scalars say {ext_kernel} ext-block DMAs, "
                    f"trace window-OR says {ext_trace}")
            emit(f"fusion/traffic_K{K}_s{sparsity:g}", None,
                 f"{touched}/{total} weight blocks "
                 f"({100 * touched / max(total, 1):.1f}% of per-step "
                 f"dense), {ext_kernel} gated ext DMAs "
                 f"(counter-checked), B={batch} T={steps}",
                 kind="fusion_traffic", fuse_steps=K, sparsity=sparsity,
                 batch=batch, blocks_touched=touched, blocks_total=total,
                 traffic_ratio=round(touched / max(total, 1), 4),
                 ext_gate_dmas=ext_kernel, counter_consistent=True)
        for backend in backends:
            for K in (fuse_list if backend != "reference" else [1]):
                engine = SpikeEngine(
                    W, n_in, decay=DecaySpec.shift(0.25),
                    threshold_raw=1 << 16, reset_mode="zero",
                    backend=backend, fuse_steps=K)
                t = time_call(lambda e=engine: e.run(ext)["spikes"])
                emit(f"fusion/timestep_{backend}_K{K}_s{sparsity:g}",
                     t / steps,
                     f"us/timestep B={batch} sparsity={sparsity} K={K} "
                     f"gate={engine.gate}",
                     kind="fusion_time", backend=backend, fuse_steps=K,
                     gate=engine.gate, sparsity=sparsity, batch=batch,
                     per_timestep=True)
        # serving occupancy: idle slots under the per-example fused gate
        # (tile_batch=1 — a silent slot's ext blocks never DMA)
        for occupancy in (1.0, 0.25):
            n_live = max(1, int(round(occupancy * n_slots)))
            slot_ext = np.zeros((steps, n_slots, n_in), np.int32)
            slot_ext[:, :n_live] = np.asarray(
                rng.random((steps, n_live, n_in)) < sparsity, np.int32)
            slot_out = ref_engine.run(jnp.asarray(slot_ext))["spikes"]
            slot_src = np.asarray(sources_raster(slot_ext, slot_out))
            for K in fuse_list:
                touched, total = trace.fused_block_traffic(
                    slot_src, n_in, fuse_steps=K, tile_batch=1)
                emit(f"fusion/serving_K{K}_occ{occupancy:g}"
                     f"_s{sparsity:g}", None,
                     f"{n_live}/{n_slots} slots live: {touched}/{total} "
                     f"weight blocks "
                     f"({100 * touched / max(total, 1):.1f}% of per-step "
                     f"dense)",
                     kind="fusion_serving", fuse_steps=K,
                     gate="per-example", occupancy=occupancy,
                     sparsity=sparsity, n_slots=n_slots,
                     blocks_touched=touched, blocks_total=total,
                     traffic_ratio=round(touched / max(total, 1), 4))


def bench_async_frontend(backends, *, n_slots: int = 8,
                         chunk_steps: int = 8, n_requests: int = 24,
                         T: int = 32, activity: float = 0.05,
                         queue_capacity: int = 6) -> None:
    """The async front-door axis: admission queue vs the step loop.

    Drives :class:`AsyncSpikeFrontend` on a VIRTUAL clock (1 unit per
    pump round) so the queue dynamics are deterministic: requests arrive
    open-loop at ``load_factor`` x the slot service rate (``n_slots *
    chunk_steps / T`` streams per round at full occupancy). Underload
    (0.5x) shows the queue staying shallow; overload (2x) shows depth
    growth until backpressure (reject / drop-oldest) or a deadline sheds
    load. Wall time over the whole run gives the served steps/s next to
    the per-regime outcome counts and queue-wait / service percentiles
    (in pump rounds — the virtual clock's unit).
    """
    rng = np.random.default_rng(0)
    n_in, P = 784, 1024
    W = jnp.asarray(rng.integers(-2**13, 2**13, (n_in + P, P)), jnp.int32)
    rasters = [(rng.random((T, n_in)) < activity).astype(np.int32)
               for _ in range(n_requests)]
    service_rate = n_slots * chunk_steps / T  # streams retired per round
    regimes = [(0.5, "reject", None), (2.0, "reject", None),
               (2.0, "drop-oldest", None), (2.0, "reject", 3.0)]
    for backend in backends:
        engine = SpikeEngine(W, n_in, decay=DecaySpec.shift(0.25),
                             threshold_raw=1 << 16, reset_mode="zero",
                             backend=backend)
        for load, policy, deadline_rounds in regimes:
            server = SpikeServer(engine, n_slots=n_slots,
                                 chunk_steps=chunk_steps)
            t_virtual = [0.0]
            fe = AsyncSpikeFrontend(
                server, queue_capacity=queue_capacity, backpressure=policy,
                deadline_ms=(None if deadline_rounds is None
                             else deadline_rounds * 1e3),
                clock=lambda t=t_virtual: t[0])
            arrive_at = [i / (load * service_rate)
                         for i in range(n_requests)]
            i = 0
            t0 = time.perf_counter()
            while i < n_requests or not fe.idle:
                while i < n_requests and arrive_at[i] <= t_virtual[0]:
                    fe.submit(rasters[i])
                    i += 1
                fe.pump()
                t_virtual[0] += 1.0
            wall = time.perf_counter() - t0
            m = fe.metrics()
            c = m["counts"]
            dl = ("" if deadline_rounds is None
                  else f"_dl{deadline_rounds:g}")
            emit(f"async/frontend_{backend}_load{load:g}_{policy}{dl}",
                 wall * 1e6 / max(server.total_steps, 1),
                 f"{c.get('done', 0)}/{n_requests} done, "
                 f"{c.get('rejected', 0)} rej, {c.get('dropped', 0)} drop, "
                 f"{c.get('expired', 0)} exp, queue depth max "
                 f"{m['queue_depth']['max']}/{queue_capacity}, "
                 f"offered {load:g}x service rate",
                 kind="async_frontend", backend=backend, load_factor=load,
                 policy=policy, deadline_rounds=deadline_rounds,
                 n_requests=n_requests, n_slots=n_slots,
                 queue_capacity=queue_capacity,
                 done=c.get("done", 0), rejected=c.get("rejected", 0),
                 dropped=c.get("dropped", 0), expired=c.get("expired", 0),
                 queue_depth_max=m["queue_depth"]["max"],
                 queue_wait_p50_rounds=m["queue_wait"]["p50"],
                 queue_wait_p95_rounds=m["queue_wait"]["p95"],
                 service_p50_rounds=m["service"]["p50"],
                 per_timestep=True)


def bench_qos_frontend(backends, *, n_slots: int = 4, chunk_steps: int = 8,
                       T: int = 32, n_bg: int = 16, n_hi: int = 8,
                       activity: float = 0.05) -> None:
    """The multi-tenant QoS axis: per-class latency isolation.

    Drives the SAME adversarial 2-class traffic plan through three front
    doors on a virtual clock (1 unit per pump round): a background class
    trickling in at the slot service rate while a bursty class lands all
    its requests at once mid-run. ``fifo`` ignores the classes (the
    PR 5 baseline — the burst waits behind the backlog), ``wfq`` ranks
    the burst class into a higher priority stratum with a 4x weight, and
    ``preempt`` additionally sheds running background streams through
    the connector. Per-class p99 total latency (in rounds) is the
    deliverable: the QoS claim — high-priority p99 strictly below the
    FIFO baseline at the SAME offered load — is ENFORCED on the
    reference backend (deterministic virtual-clock schedule), not just
    recorded.
    """
    from repro.serving.connector import InMemoryCarryConnector
    from repro.serving.qos import QoSClass, QoSPolicy

    rng = np.random.default_rng(0)
    n_in, P = 784, 1024
    W = jnp.asarray(rng.integers(-2**13, 2**13, (n_in + P, P)), jnp.int32)
    rasters = [(rng.random((T, n_in)) < activity).astype(np.int32)
               for _ in range(n_bg + n_hi)]
    # deterministic plan: the background class arrives at 2x the slot
    # service rate (n_slots*chunk_steps/T = 1 stream per round here), so
    # a backlog is already deep when every hi request lands at once at
    # round 6 — FIFO makes the burst wait behind that backlog; QoS must
    # not
    plan = sorted([(0.5 * i, "bg", rasters[i]) for i in range(n_bg)]
                  + [(6.0, "hi", rasters[n_bg + i])
                     for i in range(n_hi)], key=lambda e: e[0])
    scenarios = [
        ("fifo", None),
        ("wfq", QoSPolicy(classes={"hi": QoSClass(priority=1, weight=4),
                                   "bg": QoSClass(priority=0, weight=1)})),
        ("preempt", QoSPolicy(
            classes={"hi": QoSClass(priority=1, weight=4),
                     "bg": QoSClass(priority=0, weight=1)},
            preempt=True)),
    ]
    for backend in backends:
        engine = SpikeEngine(W, n_in, decay=DecaySpec.shift(0.25),
                             threshold_raw=1 << 16, reset_mode="zero",
                             backend=backend)
        fifo_hi_p99 = None
        for scenario, policy in scenarios:
            server = SpikeServer(engine, n_slots=n_slots,
                                 chunk_steps=chunk_steps)
            t_virtual = [0.0]
            fe = AsyncSpikeFrontend(
                server, queue_capacity=n_bg + n_hi + 1,
                clock=lambda t=t_virtual: t[0], qos=policy,
                connector=(InMemoryCarryConnector()
                           if policy is not None and policy.preempt
                           else None))
            i = 0
            t0 = time.perf_counter()
            while i < len(plan) or not fe.idle:
                while i < len(plan) and plan[i][0] <= t_virtual[0]:
                    fe.submit(plan[i][2], tenant=plan[i][1])
                    i += 1
                fe.pump()
                t_virtual[0] += 1.0
            wall = time.perf_counter() - t0
            m = fe.metrics()
            hi, bg = m["by_class"]["hi"], m["by_class"]["bg"]
            hi_p99, bg_p99 = hi["total"]["p99"], bg["total"]["p99"]
            if scenario == "fifo":
                fifo_hi_p99 = hi_p99
            emit(f"qos/frontend_{backend}_{scenario}",
                 wall * 1e6 / max(server.total_steps, 1),
                 f"hi p99 {hi_p99:g} rounds vs bg {bg_p99:g} (fifo hi "
                 f"{fifo_hi_p99:g}); {m['counts']['done']}/{len(plan)} "
                 f"done, {m['counts']['evicted']} preempted",
                 kind="qos_frontend", backend=backend, scenario=scenario,
                 n_requests=len(plan), n_slots=n_slots,
                 chunk_steps=chunk_steps,
                 hi_p99_rounds=hi_p99, bg_p99_rounds=bg_p99,
                 hi_p50_rounds=hi["total"]["p50"],
                 bg_p50_rounds=bg["total"]["p50"],
                 fifo_hi_p99_rounds=fifo_hi_p99,
                 done=m["counts"]["done"],
                 evicted=m["counts"]["evicted"],
                 parked=m["counts"]["parked"],
                 per_timestep=True)
            if (backend == "reference" and scenario != "fifo"
                    and not hi_p99 < fifo_hi_p99):
                raise SystemExit(
                    f"QoS isolation claim failed: {scenario} hi-class "
                    f"p99 {hi_p99:g} rounds is not strictly below the "
                    f"FIFO baseline {fifo_hi_p99:g} at the same offered "
                    f"load")


def bench_migration(backends, *, n_slots: int = 8, chunk_steps: int = 8,
                    activity: float = 0.05) -> None:
    """The migration-overhead axis: what a stream-state move costs.

    The connector's contract is exactness (a migrated raster is
    byte-identical); this bench records its PRICE next to the work a
    migration displaces: per-stream snapshot latency, a full in-memory
    detach->attach round trip, the same round trip through a file-backed
    connector (one fsync-less atomic write + read), and the serialized
    blob size — against the cost of the ``chunk_steps`` feed quantum the
    slot would have run in that time. Spill/restore being cheap relative
    to a service quantum is what makes slot count stop bounding
    concurrent streams.
    """
    import tempfile

    from repro.serving.connector import (FileCarryConnector,
                                         InMemoryCarryConnector)

    rng = np.random.default_rng(0)
    n_in, P = 784, 1024
    W = jnp.asarray(rng.integers(-2**13, 2**13, (n_in + P, P)), jnp.int32)
    chunk = (rng.random((chunk_steps, n_in)) < activity).astype(np.int32)
    for backend in backends:
        engine = SpikeEngine(W, n_in, decay=DecaySpec.shift(0.25),
                             threshold_raw=1 << 16, reset_mode="zero",
                             backend=backend)
        server = SpikeServer(engine, n_slots=n_slots,
                             chunk_steps=chunk_steps)
        uids = [server.attach(f"s{i}") for i in range(n_slots - 1)]
        server.feed({uid: chunk for uid in uids})  # warm carries + XLA
        uid = uids[0]
        t_feed = time_call(
            lambda: server.feed({u: chunk for u in uids})[uid]["spikes"])
        snap = server.snapshot_stream(uid)
        blob_bytes = len(snap.to_bytes())
        t_snap = time_call(lambda: server.snapshot_stream(uid).to_bytes())

        mem = InMemoryCarryConnector()

        def roundtrip(conn):
            server.detach_stream(uid, conn)
            server.attach_stream(conn, uid)
            return server.carry["v"]

        t_mem = time_call(lambda: roundtrip(mem))
        with tempfile.TemporaryDirectory() as d:
            disk = FileCarryConnector(d)
            t_disk = time_call(lambda: roundtrip(disk))
        emit(f"migration/roundtrip_{backend}", t_mem,
             f"snapshot {t_snap:.0f} us, mem move {t_mem:.0f} us, file "
             f"move {t_disk:.0f} us, blob {blob_bytes} B vs "
             f"{chunk_steps}-step feed quantum {t_feed:.0f} us",
             kind="migration", backend=backend, n_slots=n_slots,
             snapshot_us=round(t_snap, 2), roundtrip_mem_us=round(t_mem, 2),
             roundtrip_file_us=round(t_disk, 2), blob_bytes=blob_bytes,
             feed_quantum_us=round(t_feed, 2),
             migration_vs_quantum=round(t_mem / max(t_feed, 1e-9), 4))


def bench_obs_overhead(backends, *, n_slots: int = 8, chunk_steps: int = 8,
                       rounds: int = 6, activity: float = 0.05,
                       budget: float = 0.05) -> None:
    """The observability-overhead axis: telemetry must be ~free.

    Times the SAME serving feed loop twice — bare vs fully instrumented
    (MetricsRegistry + SpanTracer injected into ``SpikeServer``) — and
    records the relative overhead. The telemetry layer's hard contract is
    read-only observation of the datapath (byte-identity is pinned by
    tests/test_obs_server.py); this bench pins the PRICE and ENFORCES it:
    on the reference backend (the contract backend — interpreted Pallas
    timings are too noisy to gate) an overhead beyond ``budget`` is a
    ``SystemExit``, not a printout.
    """
    from repro.obs import MetricsRegistry, SpanTracer

    rng = np.random.default_rng(0)
    n_in, P = 784, 1024
    W = jnp.asarray(rng.integers(-2**13, 2**13, (n_in + P, P)), jnp.int32)
    T = chunk_steps * rounds
    rasters = [(rng.random((T, n_in)) < activity).astype(np.int32)
               for _ in range(n_slots)]
    for backend in backends:
        engine = SpikeEngine(W, n_in, decay=DecaySpec.shift(0.25),
                             threshold_raw=1 << 16, reset_mode="zero",
                             backend=backend)

        def make_server(telemetry: bool):
            srv = SpikeServer(
                engine, n_slots=n_slots, chunk_steps=chunk_steps,
                metrics=MetricsRegistry() if telemetry else None,
                tracer=SpanTracer() if telemetry else None)
            uids = [srv.attach() for _ in range(n_slots)]
            return srv, uids

        def chunk_at(uids, t0):
            return {u: rasters[i][t0:t0 + chunk_steps]
                    for i, u in enumerate(uids)}

        bare, bare_uids = make_server(False)
        inst, inst_uids = make_server(True)
        # time bare/instrumented back-to-back PER CHUNK (alternating which
        # goes first) and take the MEDIAN of the paired differences: two
        # sequential time_call() blocks let background-load drift
        # masquerade as telemetry overhead (a 10%+ phantom on busy CI
        # runners), and even independent per-side minima drift apart by
        # several percent on a shared machine. Pairing cancels the drift
        # (both halves of a pair see the same instant), alternation
        # cancels any first-vs-second bias, and the median discards load
        # spikes that land inside one half. Scheduling noise is still
        # several times the true telemetry cost per pair, and it only
        # INFLATES an estimate — so the gate takes the floor over three
        # independent trials: a real budget regression lifts all three,
        # a load spike lifts at most one or two.
        for t0 in range(0, T, chunk_steps):  # warmup (jit + first feed)
            bare.feed(chunk_at(bare_uids, t0))
            inst.feed(chunk_at(inst_uids, t0))
        estimates = []  # (overhead, median bare, median diff) per trial
        for trial in range(3):
            bare_s, diffs = [], []
            for it in range(7):
                for t0 in range(0, T, chunk_steps):
                    cb = chunk_at(bare_uids, t0)
                    ci = chunk_at(inst_uids, t0)
                    if (it + t0 // chunk_steps) % 2:
                        t = time.perf_counter()
                        inst.feed(ci)
                        ti = time.perf_counter() - t
                        t = time.perf_counter()
                        bare.feed(cb)
                        tb = time.perf_counter() - t
                    else:
                        t = time.perf_counter()
                        bare.feed(cb)
                        tb = time.perf_counter() - t
                        t = time.perf_counter()
                        inst.feed(ci)
                        ti = time.perf_counter() - t
                    bare_s.append(tb)
                    diffs.append(ti - tb)
            bare_s.sort()
            diffs.sort()
            med_bare = bare_s[len(bare_s) // 2]
            med_diff = diffs[len(diffs) // 2]
            estimates.append((med_diff / med_bare, med_bare, med_diff))
        overhead, med_bare, med_diff = min(estimates)
        t_bare = med_bare * rounds * 1e6  # per feed-loop, as before
        t_obs = (med_bare + med_diff) * rounds * 1e6
        emit(f"obs/overhead_{backend}", t_obs / T,
             f"instrumented {t_obs / T:.1f} vs bare {t_bare / T:.1f} "
             f"us/timestep ({100 * overhead:+.2f}% with metrics+tracer on, "
             f"{n_slots} slots x {chunk_steps}-step chunks)",
             kind="obs_overhead", backend=backend, batch=n_slots,
             activity=activity,
             bare_us_per_step=round(t_bare / T, 3),
             instrumented_us_per_step=round(t_obs / T, 3),
             overhead_frac=round(overhead, 4),
             per_timestep=True)
        if backend == "reference" and overhead > budget:
            raise SystemExit(
                f"observability overhead {overhead:.1%} exceeds the "
                f"{budget:.0%} budget on the reference backend "
                f"(instrumented {t_obs / T:.1f} vs bare {t_bare / T:.1f} "
                f"us/timestep)")


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--activity", type=float, default=0.05,
                    help="fraction of sources spiking (paper: sparse)")
    ap.add_argument("--backend", choices=list(BACKENDS) + ["all"],
                    default="all",
                    help="SpikeEngine backend(s) to benchmark")
    ap.add_argument("--streaming", action="store_true",
                    help="also benchmark the SpikeServer slot-batch path "
                         "(masked chunk step vs one-shot batch scan)")
    ap.add_argument("--async", dest="async_mode", action="store_true",
                    help="also benchmark the AsyncSpikeFrontend request "
                         "queue: outcome counts + queue-wait/service "
                         "percentiles per backpressure policy x offered "
                         "load (under/overload on a virtual clock)")
    ap.add_argument("--sparsity", default=None, metavar="S1,S2,...",
                    help="comma list of source-activity levels for the "
                         "event-gating sweep: gated-vs-dense weight "
                         "traffic / SOP reduction per gate x backend x "
                         "serving occupancy (e.g. 0.02,0.05,0.2)")
    ap.add_argument("--fuse-steps", default=None, metavar="K1,K2,...",
                    help="comma list of K values for the K-step fusion "
                         "sweep: engine steps/s and weight-block traffic "
                         "per K x backend x sparsity x occupancy, with "
                         "the trace window-OR count cross-checked "
                         "against the kernel's gate scalars (e.g. 1,4,8)")
    ap.add_argument("--qos", action="store_true",
                    help="also benchmark the multi-tenant QoS front door: "
                         "the same adversarial burst-over-background "
                         "traffic through FIFO vs WFQ vs preemptive "
                         "admission on a virtual clock, recording "
                         "per-class p99 total latency — the isolation "
                         "claim (high-priority p99 strictly below the "
                         "FIFO baseline) is ENFORCED on the reference "
                         "backend")
    ap.add_argument("--migrate", action="store_true",
                    help="also benchmark stream-state migration overhead: "
                         "per-stream carry snapshot latency, in-memory and "
                         "file-backed detach->attach round trips, and blob "
                         "size vs the feed quantum a slot runs in that "
                         "time (the byte-identity itself is pinned by "
                         "tests/test_carry_migration.py)")
    ap.add_argument("--obs-overhead", action="store_true",
                    help="also benchmark the telemetry layer's cost: the "
                         "same SpikeServer feed loop bare vs instrumented "
                         "(MetricsRegistry + SpanTracer), recording the "
                         "relative overhead — the observability contract "
                         "is byte-identical outputs and < 5% overhead on "
                         "the reference backend, ENFORCED: exceeding the "
                         "budget there exits nonzero")
    ap.add_argument("--devices", type=int, default=1,
                    help="also run the engine/streaming benches on a mesh "
                         "over N devices (faked host devices on CPU)")
    ap.add_argument("--mesh", default=None, metavar="KNxKB",
                    help="neuron x batch mesh split for --devices "
                         "(default: 2 x N/2 when N allows)")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write machine-readable results (BENCH_*.json)")
    return ap


def main(argv=None) -> None:
    args = build_parser().parse_args(argv)
    if args.mesh and args.devices <= 1:
        raise SystemExit("--mesh requires --devices N (N > 1); without it "
                         "the sharded benches would silently not run")

    enable_compile_cache()
    # on CPU, force the faked device count BEFORE the first jax backend
    # touch (the Pallas kernel import below initializes it)
    if args.devices > 1:
        ensure_host_devices(args.devices)
    from repro.kernels import ops, ref

    backends = list(BACKENDS) if args.backend == "all" else [args.backend]
    if args.json:
        common.start_recording()

    mesh = None
    if args.devices > 1:
        try:
            kn, kb = parse_mesh_spec(args.devices, args.mesh)
        except ValueError as e:
            raise SystemExit(str(e))
        mesh = make_spike_mesh(neuron=kn, batch=kb)
        print(f"[bench] mesh axis: {kn} neuron shards x {kb} batch shards "
              f"({args.devices} devices)", flush=True)

    sparsities = None
    if args.sparsity:
        try:
            sparsities = [float(s) for s in args.sparsity.split(",") if s]
        except ValueError:
            raise SystemExit(
                f"--sparsity must be comma-separated floats, "
                f"got {args.sparsity!r}")
        bench_event_gating(backends, sparsities, batch=args.batch,
                           n_slots=max(args.batch, 8))

    if args.fuse_steps:
        try:
            fuse_list = [int(k) for k in args.fuse_steps.split(",") if k]
        except ValueError:
            raise SystemExit(
                f"--fuse-steps must be comma-separated ints, "
                f"got {args.fuse_steps!r}")
        if not fuse_list or any(k < 1 for k in fuse_list):
            raise SystemExit(
                f"--fuse-steps values must be >= 1, got {args.fuse_steps!r}")
        bench_fuse_steps(backends, fuse_list,
                         sparsities if sparsities else [args.activity],
                         batch=args.batch, n_slots=max(args.batch, 8))

    bench_engine_backends(backends, batch=args.batch,
                          activity=args.activity)
    if mesh is not None:
        bench_engine_backends(backends, batch=args.batch,
                              activity=args.activity, mesh=mesh)
    if args.streaming:
        bench_streaming(backends, n_slots=args.batch,
                        activity=args.activity)
        if mesh is not None:
            bench_streaming(backends, n_slots=args.batch,
                            activity=args.activity, mesh=mesh)
    if args.async_mode:
        bench_async_frontend(backends, activity=args.activity)
    if args.qos:
        bench_qos_frontend(backends, activity=args.activity)
    if args.migrate:
        bench_migration(backends, activity=args.activity)
    if args.obs_overhead:
        bench_obs_overhead(backends, activity=args.activity)

    rng = np.random.default_rng(0)
    B, S, P = args.batch, 784 + 1024, 1024
    src = jnp.asarray(rng.random((B, S)) < args.activity, jnp.int32)
    W = jnp.asarray(rng.integers(-2**14, 2**14, (S, P)), jnp.int32)
    v = jnp.asarray(rng.integers(-2**18, 2**18, (B, P)), jnp.int32)

    fused = lambda: ops.spike_timestep(src, W, v, decay_rate=0.25,
                                       threshold_raw=1 << 16)
    unfused = lambda: ref.spike_timestep_ref(
        src, W, v, decay_rate=0.25, threshold_raw=1 << 16,
        reset_mode="zero")

    t_fused = time_call(lambda: fused())
    t_ref = time_call(lambda: unfused())
    emit("kernel/spike_timestep_fused", t_fused,
         f"B={B} S={S} P={P} activity={args.activity}",
         kind="kernel", batch=B, activity=args.activity, devices=1)
    emit("kernel/spike_timestep_ref", t_ref, "pure-jnp oracle",
         kind="kernel", batch=B, activity=args.activity, devices=1)

    # event-gating accounting: active source blocks out of total
    blk = 128
    nblk = -(-S // blk)
    act = np.asarray(src).reshape(B, -1)
    padded = np.zeros((B, nblk * blk), np.int32)
    padded[:, :S] = act
    active_blocks = int(
        (padded.reshape(B, nblk, blk).sum(axis=(0, 2)) > 0).sum())
    emit("kernel/active_source_blocks", None,
         f"{active_blocks}/{nblk} touched -> "
         f"{100 * (1 - active_blocks / nblk):.0f}% weight traffic skipped",
         kind="accounting", active_blocks=active_blocks, total_blocks=nblk)

    # LIF + encoder micro-latencies
    vv = jnp.asarray(rng.integers(-2**20, 2**20, (B, P)), jnp.int32)
    syn = jnp.asarray(rng.integers(-2**16, 2**16, (B, P)), jnp.int32)
    t_lif = time_call(
        lambda: ops.lif_step(vv, syn, decay_rate=0.25,
                             threshold_raw=1 << 16))
    emit("kernel/lif_step", t_lif, f"B={B} N={P}",
         kind="kernel", batch=B, devices=1)
    x = jnp.asarray(rng.random((B, 784)), jnp.float32)
    t_enc = time_call(lambda: ops.poisson_encode(0, x, 25))
    emit("kernel/poisson_encode", t_enc, f"B={B} D=784 T=25",
         kind="kernel", batch=B, devices=1)

    if args.json:
        common.write_json(
            args.json,
            bench="kernel_bench",
            # devices=1 records in a --devices N run still execute on the
            # N-way faked host topology; flag it so trajectory comparisons
            # against plain single-device runs don't conflate the two.
            host_devices_forced=args.devices if args.devices > 1 else None,
            args={"batch": args.batch, "activity": args.activity,
                  "backend": args.backend, "streaming": args.streaming,
                  "async": args.async_mode, "qos": args.qos,
                  "sparsity": args.sparsity,
                  "fuse_steps": args.fuse_steps, "migrate": args.migrate,
                  "obs_overhead": args.obs_overhead,
                  "devices": args.devices, "mesh": args.mesh},
        )


if __name__ == "__main__":
    main()
