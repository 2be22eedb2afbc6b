"""AcceleratorSession — the SoC orchestration layer (SpikeCore's role).

The paper's SpikeCore configures the accelerator over the RoCC interface
(8-bit config packets), injects encoded stimulus spikes (11-bit spike
packets), synchronizes timesteps, and reads decoded outputs. This module is
the host-runtime analogue: it owns accelerator state, supports **multi-model
co-residency** (paper §V-D: disjoint cluster subsets + address-space
isolation), and exposes encode -> step -> decode as a closed loop.

Co-residency is implemented exactly as the hardware does it: each deployed
model occupies a contiguous physical cluster range; weights of different
models occupy disjoint SRAM rows; and ``run_all`` advances every resident
model in ONE fused SpikeEngine scan over the shared physical array —
external sources concatenated, one weight image, per-model decoded outputs.
Models sharing a LIF configuration (decay / threshold / reset, and the
synaptic current's decay of a current-based neuron — the hardware's
global config registers) fuse into a single scan; models with
different configurations form separate fused groups, mirroring the ASIC's
per-configuration register banks. Isolation (a model's outputs are
bit-identical to a solo deployment) is verified by tests/test_session.py.
"""

from __future__ import annotations

import dataclasses

import jax.numpy as jnp
import numpy as np

from repro.core import cerebra_h, coding
from repro.core.engine import DecaySpec, SpikeEngine
from repro.core.mapping import ClusterGeometry, Placement
from repro.core.network import SNNetwork

__all__ = ["AcceleratorSession", "DeployedModel"]


@dataclasses.dataclass
class DeployedModel:
    name: str
    program: cerebra_h.CerebraHProgram
    cluster_range: tuple[int, int]   # [lo, hi) physical clusters
    input_offset: int                # external-source base address


class AcceleratorSession:
    """Host-side runtime for one Cerebra-H accelerator instance.

    ``backend`` selects the SpikeEngine backend for every inference run on
    this session ("reference" | "pallas" | "pallas-mxu"). ``mesh`` (a
    ``jax.sharding.Mesh`` with ``neuron``/``batch`` axes, see
    ``repro.distributed.spike_mesh.make_spike_mesh``) scales the fused
    paths out over devices: ``run_all`` and the streaming servers behind
    :meth:`serve` step a mesh-sharded engine — neuron shards close to
    their SRAM slice, spike exchange per timestep — with outputs
    bit-identical to the single-device session.
    """

    def __init__(self, config: cerebra_h.CerebraHConfig | None = None,
                 backend: str = "reference", mesh=None,
                 fuse_steps: int = 1, connector=None,
                 metrics=None, tracer=None):
        from repro.serving.connector import InMemoryCarryConnector

        self.config = config or cerebra_h.CerebraHConfig()
        self.backend = backend
        self.mesh = mesh
        # optional telemetry, threaded into every server / frontend /
        # connector this session builds (deploy + redeploy spans recorded
        # here). Purely observational — see repro.obs.
        self.metrics = metrics
        self.tracer = tracer
        # the session's stream-state connector: rolling-redeploy drain
        # parks in-flight carries here (and spill-enabled frontends share
        # it); file-backed connectors survive the process.
        self.connector = (connector if connector is not None
                          else InMemoryCarryConnector())
        if (metrics is not None or tracer is not None) and hasattr(
                self.connector, "instrument"):
            self.connector.instrument(metrics, tracer)
        # {lif signature: [(uid, connector key | None), ...]} — streams
        # parked by deploy(), FIFO restore order, consumed by serve().
        # A None key is a stream that was still waiting for a slot (no
        # carry exists yet; it is simply re-queued).
        self._parked_groups: dict = {}
        # K timesteps per fused kernel window for every engine this
        # session builds (1 = single-step kernels); outputs are
        # byte-identical for any K, only weight traffic changes.
        self.fuse_steps = int(fuse_steps)
        self.models: dict[str, DeployedModel] = {}
        self._next_cluster = 0
        self._next_input = 0
        # fused-engine cache: {(model names, lif signature): SpikeEngine};
        # invalidated whenever the resident set changes.
        self._fused_engines: dict = {}
        # streaming-server cache: {(group names, sig, slots, chunk):
        # SpikeServer} — co-resident models with a shared LIF config
        # stream through ONE server (and one compiled step).
        self._stream_servers: dict = {}
        # async front doors, keyed like the servers they queue for: all
        # views over one server submit into ONE bounded request queue.
        self._frontends: dict = {}
        # bumped on every deploy; outstanding ModelStream views check it
        # so a stale view fails loudly instead of streaming against a
        # pre-deploy fused layout.
        self._serve_epoch = 0

    # ------------------------------------------------------------------
    @property
    def geometry(self) -> ClusterGeometry:
        return self.config.geometry

    def free_clusters(self) -> int:
        return self.geometry.n_clusters - self._next_cluster

    def deploy(self, name: str, net: SNNetwork) -> DeployedModel:
        """Deploy a model into the next free cluster range (config path).

        A ROLLING redeploy when streams are in flight: every live stream
        of every cached server is drained to the session connector first
        (:meth:`_drain_streams`), and the next :meth:`serve` of its LIF
        group restores it into the new fused server — the stream's raster
        continues byte-identically across the deploy."""
        if name in self.models:
            raise ValueError(f"model {name!r} already deployed")
        geom = self.geometry
        npc = geom.neurons_per_cluster
        need = -(-net.n_neurons // npc)  # ceil clusters
        # co-residency isolation: round up to a group boundary so no two
        # models share a weight SRAM (address-space isolation).
        cpg = geom.clusters_per_group
        need = -(-need // cpg) * cpg
        if need > self.free_clusters():
            raise ValueError(
                f"model {name!r} needs {need} clusters; only "
                f"{self.free_clusters()} free"
            )
        lo = self._next_cluster
        base_slot = lo * npc
        placement = Placement(
            geom, base_slot + np.arange(net.n_neurons)
        )
        program = cerebra_h.compile_network(net, self.config, placement)
        model = DeployedModel(
            name=name,
            program=program,
            cluster_range=(lo, lo + need),
            input_offset=self._next_input,
        )
        self.models[name] = model
        self._next_cluster += need
        self._next_input += net.n_inputs
        parked = self._drain_streams()  # park in-flight carries first —
        self._fused_engines.clear()   # resident set changed
        self._stream_servers.clear()  # fused layout changed with it
        self._frontends.clear()       # queues die with their servers
        self._serve_epoch += 1        # invalidate outstanding stream views
        if self.metrics is not None:
            self.metrics.counter("snn_session_deploys_total").inc()
            if parked:
                self.metrics.counter("snn_session_redeploys_total").inc()
        if self.tracer is not None:
            self.tracer.event("deploy", name, models=len(self.models),
                              parked_streams=parked)
        return model

    def _drain_streams(self) -> int:
        """Rolling-redeploy drain: park every in-flight stream of every
        cached server in the session connector, so :meth:`deploy` migrates
        live traffic instead of dropping it. The next :meth:`serve` of the
        same LIF group restores the parked streams — FIFO, what fits the
        new server's slots — and their rasters continue byte-identically:
        the physical array size is fixed across deploys, existing models
        keep their cluster ranges and input offsets, and a freshly
        deployed model's clusters stay silent for other streams (the
        co-residency isolation ``run_all`` is pinned on). Returns the
        number of carries parked."""
        parked = 0
        for key, server in self._stream_servers.items():
            sig = key[1]
            group = self._parked_groups.setdefault(sig, [])
            epoch = self._serve_epoch
            # admitted streams first (dict order = admission order), so
            # FIFO restore preserves the pre-deploy service order
            for uid in server.scheduler.active:
                ckey = ("deploy", epoch, sig, uid)
                self.connector.insert(ckey, server.snapshot_stream(uid))
                group.append((uid, ckey))
                parked += 1
                if self.tracer is not None:
                    self.tracer.event("redeployed", uid, epoch=epoch)
            for uid in server.scheduler.waiting:
                group.append((uid, None))
        return parked

    # ------------------------------------------------------------------
    def run(self, name: str, intensities, num_steps: int, key) -> dict:
        """Encode -> infer -> decode for one resident model.

        intensities: (B, n_inputs) in [0,1]. Returns cerebra_h.run() result
        plus 'predictions'.
        """
        model = self.models[name]
        spikes = coding.poisson_encode(key, intensities, num_steps,
                                       dtype=jnp.int32)
        result = cerebra_h.run(model.program, spikes, backend=self.backend)
        result["predictions"] = jnp.argmax(result["output_counts"], axis=-1)
        return result

    # ------------------------------------------------------------------
    @staticmethod
    def _lif_signature(program: cerebra_h.CerebraHProgram):
        """The global accelerator config a fused step must share: a LIF
        model (no synaptic current, ``None`` last) and a current-based
        one never fuse into one engine."""
        return (program.decay_rate, program.params.threshold_raw,
                program.params.reset_mode, program.syn_decay_rate)

    def _fused_engine(self, members: list[DeployedModel]) -> SpikeEngine:
        """One physical-array engine over the union of members' programs.

        External sources are concatenated in deployment order; the
        neuron-to-neuron rows of all members are summed — disjoint cluster
        ranges guarantee the nonzero patterns cannot overlap, so the sum
        IS the union SRAM image the hardware holds.
        """
        sig = self._lif_signature(members[0].program)
        decay_rate, threshold_raw, reset_mode, _ = sig
        key = (tuple(m.name for m in members), sig, self.backend, self.mesh,
               self.fuse_steps)
        engine = self._fused_engines.get(key)
        if engine is not None:
            return engine
        n_phys = self.geometry.n_physical
        n_ext = sum(m.program.n_inputs for m in members)
        W = jnp.zeros((n_ext + n_phys, n_phys), jnp.int32)
        off = 0
        for m in members:
            flat = m.program.weights_raw.reshape(
                m.program.n_sources, -1)  # (n_in_m + P, P)
            n_in = m.program.n_inputs
            W = W.at[off:off + n_in].set(flat[:n_in])
            W = W.at[n_ext:].add(flat[n_in:])
            off += n_in
        engine = SpikeEngine(
            W,
            n_ext,
            decay=DecaySpec.shift(decay_rate),
            syn_decay=cerebra_h.syn_decay_spec(members[0].program),
            threshold_raw=threshold_raw,
            reset_mode=reset_mode,
            backend=self.backend,
            fuse_steps=self.fuse_steps,
        )
        if self.mesh is not None:
            engine = engine.to_mesh(self.mesh)
        self._fused_engines[key] = engine
        return engine

    def run_all(self, inputs: dict, num_steps: int, key) -> dict:
        """Advance every resident model concurrently (shared array step).

        inputs: {name: (B, n_inputs) intensities}; all batches must match.
        Functionally each model is independent (disjoint clusters/rows);
        we exploit that to fuse them into one physical-array SpikeEngine
        scan per LIF configuration — the same way the hardware timestep
        advances all clusters at once. Each model is encoded with the SAME
        key it would get from :meth:`run`, and its decoded outputs (and
        cost-model accounting) are bit-identical to a solo deployment.
        """
        members = [self.models[name] for name in inputs]
        batches = {np.shape(inputs[m.name])[0] for m in members}
        if len(batches) > 1:
            raise ValueError(f"batch sizes differ across models: {batches}")

        # encode per model with the same key run() uses -> solo-identical
        ext = {
            m.name: coding.poisson_encode(
                key, inputs[m.name], num_steps, dtype=jnp.int32)
            for m in members
        }

        # group by shared accelerator configuration (hardware config regs)
        groups: dict = {}
        for m in members:
            groups.setdefault(self._lif_signature(m.program), []).append(m)

        npc = self.geometry.neurons_per_cluster
        results: dict = {}
        for group in groups.values():
            engine = self._fused_engine(group)
            fused_ext = jnp.concatenate([ext[m.name] for m in group], axis=-1)
            raster = engine.run(fused_ext)["spikes"]  # (T, B, P) one scan
            for m in group:
                lo, hi = m.cluster_range
                # mask to the model's cluster range: bit-identical to the
                # raster a solo deployment produces (other slots silent)
                mask = jnp.zeros((raster.shape[-1],), jnp.int32)
                mask = mask.at[lo * npc:hi * npc].set(1)
                spikes = raster * mask[None, None, :]
                prog = m.program
                cost = cerebra_h.cost_model(prog, ext[m.name], spikes)
                out_counts = jnp.sum(
                    spikes[:, :, jnp.asarray(prog.output_map)], axis=0)
                results[m.name] = {
                    "spikes": spikes,
                    "output_counts": out_counts,
                    "cycles": cost["cycles"],
                    "sops": cost["sops"],
                    "row_fetches": cost["row_fetches"],
                    "predictions": jnp.argmax(out_counts, axis=-1),
                }
        return results

    # ------------------------------------------------------------------
    def serve(self, name: str, *, n_slots: int = 4, chunk_steps: int = 8,
              gate: str | None = None, frontend=None):
        """Streaming entry: a :class:`~repro.serving.snn.ModelStream` view
        for one resident model.

        All resident models sharing ``name``'s LIF configuration stream
        through ONE fused-engine :class:`~repro.serving.snn.SpikeServer`
        (the same union SRAM image ``run_all`` scans), so co-resident
        models' streams share slots of one compiled step. Repeated
        ``serve`` calls reuse the cached server — views over the same
        group see (and compete for) the same slots, exactly like
        co-resident workloads on the physical array.

        ``gate`` selects the event-gate granularity of the server's
        engine (``"per-example"`` is the batch-tile=1 serving mode, where
        idle slots skip their own weight traffic); outputs are
        bit-identical under any gate.

        ``frontend`` (a :class:`~repro.serving.frontend.FrontendConfig`)
        makes the returned view async-capable: ONE
        :class:`~repro.serving.frontend.AsyncSpikeFrontend` is hung off
        the group's shared server (co-resident views share its bounded
        request queue like they share slots), and the view grows
        ``submit``/``submit_events`` that enqueue model-local rasters
        against it. The frontend changes only WHEN work runs — async
        outputs stay byte-identical to synchronous ``feed``. Views served
        later without ``frontend=`` still see the group's existing
        frontend; a conflicting config raises.

        A later :meth:`deploy` changes the fused layout and invalidates
        outstanding views: using one afterwards raises (epoch check);
        call ``serve`` again after deploying. In-flight streams are NOT
        lost: deploy parks their carries in the session connector and the
        re-``serve`` restores them (byte-identical continuation).
        """
        from repro.serving.frontend import AsyncSpikeFrontend
        from repro.serving.snn import ModelStream, SpikeServer

        model = self.models[name]
        sig = self._lif_signature(model.program)
        group = [m for m in self.models.values()
                 if self._lif_signature(m.program) == sig]
        group_key = (tuple(m.name for m in group), sig, self.backend,
                     self.fuse_steps)
        # normalize gate=None to the engine's effective gate so a default
        # serve and an explicit-default serve alias to ONE server key
        gate = gate if gate is not None else self._fused_engine(group).gate
        key = group_key + (int(n_slots), int(chunk_steps), gate)
        server = self._stream_servers.get(key)
        if server is None:
            # one server per group: mismatched slot parameters would
            # silently split co-resident streams into independent carries
            for other in self._stream_servers:
                if other[: len(group_key)] == group_key:
                    n_slots_o, chunk_o, gate_o = other[len(group_key):]
                    raise ValueError(
                        f"group {group_key[0]} is already served with "
                        f"n_slots={n_slots_o}, chunk_steps={chunk_o}, "
                        f"gate={gate_o}; co-resident views must share "
                        f"one server"
                    )
            server = SpikeServer(self._fused_engine(group),
                                 n_slots=n_slots, chunk_steps=chunk_steps,
                                 gate=gate, metrics=self.metrics,
                                 tracer=self.tracer)
            self._stream_servers[key] = server
            self._restore_parked(sig, server)
        fe = self._frontends.get(key)
        if frontend is not None:
            cfg = frontend
            if fe is None:
                fe = AsyncSpikeFrontend(
                    server, queue_capacity=cfg.queue_capacity,
                    backpressure=cfg.backpressure,
                    deadline_ms=cfg.deadline_ms,
                    connector=(self.connector
                               if cfg.spill or (cfg.qos is not None
                                                and cfg.qos.preempt)
                               else None),
                    metrics=self.metrics, tracer=self.tracer,
                    slo=cfg.slo, qos=cfg.qos)
                self._frontends[key] = fe
            elif (fe.queue_capacity, fe.backpressure,
                  fe.default_deadline_ms, fe.qos,
                  fe.connector is not None) != (
                      cfg.queue_capacity, cfg.backpressure,
                      cfg.deadline_ms, cfg.qos,
                      cfg.spill or (cfg.qos is not None
                                    and cfg.qos.preempt)):
                raise ValueError(
                    f"group {group_key[0]} already has a frontend with "
                    f"queue_capacity={fe.queue_capacity}, "
                    f"backpressure={fe.backpressure!r}, "
                    f"deadline_ms={fe.default_deadline_ms}, "
                    f"spill={fe.connector is not None}, "
                    f"qos={fe.qos}; co-resident "
                    f"views must share one request queue")
        ext_offset = 0
        for m in group:
            if m.name == name:
                break
            ext_offset += m.program.n_inputs
        npc = self.geometry.neurons_per_cluster
        lo, hi = model.cluster_range
        epoch = self._serve_epoch
        return ModelStream(
            server,
            name=name,
            n_inputs=model.program.n_inputs,
            ext_offset=ext_offset,
            phys_slice=(lo * npc, hi * npc),
            output_map=model.program.output_map,
            stale_check=lambda: self._serve_epoch != epoch,
            frontend=fe,
        )

    def _restore_parked(self, sig, server) -> list:
        """Restore streams :meth:`_drain_streams` parked for this LIF
        group into the (new) server: FIFO, carries first-class via
        ``attach_stream``, still-waiting uids simply re-queued. Restores
        what fits the server's free slots; the rest stay parked for a
        later ``serve`` (or manual ``attach_stream``). Returns restored
        uids."""
        parked = self._parked_groups.pop(sig, [])
        restored, leftovers = [], []
        for uid, ckey in parked:
            if ckey is None:
                server.attach(uid)
                restored.append(uid)
            elif server.scheduler.free_slots > 0:
                snap = self.connector.select(ckey)
                server.attach_stream(snap, uid=uid)
                self.connector.evict(ckey)
                restored.append(uid)
            else:
                leftovers.append((uid, ckey))
        if leftovers:
            self._parked_groups[sig] = leftovers
        return restored

    def utilization(self) -> dict:
        geom = self.geometry
        used_neurons = sum(
            m.program.n_neurons for m in self.models.values()
        )
        used_rows = sum(
            int(np.sum(m.program.capacity_report["rows_per_group"]))
            for m in self.models.values()
        )
        return {
            "clusters_used": self._next_cluster,
            "clusters_total": geom.n_clusters,
            "neuron_utilization": used_neurons / geom.n_physical,
            "row_utilization": used_rows
            / (geom.n_groups * geom.rows_per_group),
            "models": list(self.models),
        }
