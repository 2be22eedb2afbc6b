"""Cerebra-H — the clustered, hierarchical-NoC accelerator (paper §V).

Functional model (bit-exact int32) + cycle cost model + energy hooks.

Hardware semantics modeled:
  * 32 clusters x 32 neurons; cluster groups of 4 share a single-port
    weight SRAM (2048 rows x 1024 b). The Weight Resolver arbitrates four
    per-cluster request queues at one grant per cycle.
  * Incoming Forwarder looks up (src cluster-ID, src neuron-ID) -> row
    address, fetches the 32-wide weight row and delivers weights to its
    cluster's neurons.
  * Neurons: accumulator + SHIFT-based decay (rates {.125,.25,.5,.75}) +
    configurable reset (hold / zero / subtract).
  * Two-layer NoC: L1 router per 4 clusters, central L2 over 8 L1s; spike
    path is pipelined/buffered, config path is bufferless.
  * Multi-model co-residency via disjoint cluster subsets.

TPU adaptation: the blocked weight layout (source, dst_cluster, 32) is the
SRAM row structure; the functional timestep runs on the shared
:class:`~repro.core.engine.SpikeEngine` — whose ``"pallas"`` backend is the
event-gated kernel in ``repro.kernels.spike_timestep`` (cluster-gated block
skipping ON the inference path) and whose ``"reference"`` backend is the
pure-jnp blocked matmul. This module contributes the compile step and the
cycle/energy cost model, applied as a pure pass over the spike raster.
"""

from __future__ import annotations

import dataclasses

import jax.numpy as jnp
import numpy as np

from repro.core import fixedpoint as fxp
from repro.core.engine import DecaySpec, SpikeEngine, sources_raster
from repro.core.lif import LIFParams
from repro.core.mapping import (
    ClusterGeometry,
    Placement,
    check_capacity,
    communication_profile,
    place_contiguous,
)
from repro.core.network import SNNetwork

__all__ = [
    "CerebraHConfig",
    "CerebraHProgram",
    "compile_network",
    "make_engine",
    "syn_decay_spec",
    "cost_model",
    "run",
]

MAX_FREQ_MHZ = 96.24  # paper §VII-B: Cerebra-H critical path 10.3904 ns


@dataclasses.dataclass(frozen=True)
class CerebraHConfig:
    geometry: ClusterGeometry = dataclasses.field(default_factory=ClusterGeometry)
    fmt: fxp.FixedPointFormat = fxp.Q16_16
    row_mode: str = "external_broadcast"
    # NoC micro-timing (paper Table II + §V-D): spike path is pipelined —
    # throughput 1 packet/cycle/link after `spike_pipeline_depth` cycles.
    spike_pipeline_depth: int = 2
    l2_hop_cycles: int = 2
    sync_overhead_cycles: int = 4  # timestep-boundary completion handshake


@dataclasses.dataclass
class CerebraHProgram:
    config: CerebraHConfig
    params: LIFParams
    placement: Placement
    n_inputs: int
    n_neurons: int
    # blocked SRAM image: (n_sources, n_clusters, neurons_per_cluster) int32
    weights_raw: jnp.ndarray
    # row incidence: (n_sources, n_clusters) bool — a row exists for this
    # (source, dst cluster) pair (drives resolver cost + gated kernel)
    row_exists: np.ndarray
    # per-source nonzero synapse count (SOPs per spike of that source)
    fanout: np.ndarray
    output_map: np.ndarray        # physical slots of output neurons, ordered
    decay_rate: float             # snapped to hardware-supported rate
    capacity_report: dict
    comm_profile: dict
    # synaptic-current decay, snapped like decay_rate; None = one-state LIF
    syn_decay_rate: float | None = None
    # per-program engine cache: one compiled scan per backend
    _engines: dict = dataclasses.field(
        default_factory=dict, repr=False, compare=False)

    @property
    def n_sources(self) -> int:
        return self.n_inputs + self.config.geometry.n_physical


def compile_network(
    net: SNNetwork,
    config: CerebraHConfig | None = None,
    placement: Placement | None = None,
) -> CerebraHProgram:
    """Place, check capacity, quantize and block a logical network."""
    config = config or CerebraHConfig()
    geom = config.geometry
    net.validate()
    placement = placement or place_contiguous(net, geom)
    capacity = check_capacity(net, placement, config.row_mode)
    comm = communication_profile(net, placement)

    n_phys = geom.n_physical
    n_in = net.n_inputs
    # scatter logical weights into the physical array layout
    W = np.zeros((n_in + n_phys, n_phys), np.float32)
    phys = placement.neuron_to_physical
    W[:n_in, phys] = net.weights[:n_in]
    # neuron-to-neuron: source neuron i lives at phys[i]
    W[n_in + phys[:, None], phys[None, :]] = net.weights[n_in:]
    w_raw = fxp.np_to_fixed(W, config.fmt)
    blocked = w_raw.reshape(
        n_in + n_phys, geom.n_clusters, geom.neurons_per_cluster
    )
    row_exists = (blocked != 0).any(axis=-1)

    # deployment-time snapping of the trained decay to a hardware rate —
    # one of the two quantization effects the accuracy study measures.
    decay_rate = fxp.nearest_shift_decay(net.params.decay_rate)
    syn_decay_rate = (None if not net.params.has_current
                      else fxp.nearest_shift_decay(net.params.syn_decay_rate))

    lo, hi = net.output_slice
    return CerebraHProgram(
        config=config,
        params=net.params,
        placement=placement,
        n_inputs=n_in,
        n_neurons=net.n_neurons,
        weights_raw=jnp.asarray(blocked),
        row_exists=np.asarray(row_exists),
        fanout=np.count_nonzero(W, axis=1),
        output_map=phys[lo:hi],
        decay_rate=decay_rate,
        capacity_report=capacity,
        comm_profile=comm,
        syn_decay_rate=syn_decay_rate,
    )


def make_engine(program: CerebraHProgram,
                backend: str = "reference") -> SpikeEngine:
    """The program's SpikeEngine for ``backend`` (built once, then cached).

    The blocked SRAM image (S, C, n) flattens to the engine's (S, P) weight
    matrix; the H generation decays with the arithmetic-shift PDU (the
    synaptic current too, for current-based neurons).
    """
    engine = program._engines.get(backend)
    if engine is None:
        Wb = program.weights_raw
        engine = SpikeEngine(
            Wb.reshape(Wb.shape[0], -1),
            program.n_inputs,
            decay=DecaySpec.shift(program.decay_rate),
            syn_decay=syn_decay_spec(program),
            threshold_raw=program.params.threshold_raw,
            reset_mode=program.params.reset_mode,
            backend=backend,
        )
        program._engines[backend] = engine
    return engine


def syn_decay_spec(program: CerebraHProgram) -> DecaySpec | None:
    """The shift PDU of the program's synaptic current (None: no current)."""
    if program.syn_decay_rate is None:
        return None
    return DecaySpec.shift(program.syn_decay_rate)


def cost_model(program: CerebraHProgram, ext_spikes, spikes) -> dict:
    """Pure cycle/SOP/row-fetch accounting from a spike raster.

    Mirrors the hardware, as a vectorized pass over all T steps at once:

    * Weight Resolver: every spiking source requests one SRAM row per
      destination cluster it connects to; the single-port SRAM serves one
      row/cycle per group (arbitration), groups run in parallel.
    * NoC spike path: each spiking neuron emits one packet per destination
      cluster (Outgoing Encoder serializes one per cycle); L1 routers run
      in parallel; crossing L2 adds hop latency. Packets of step t come
      from the previous timestep boundary.

    Args:
      ext_spikes: (T, B, n_inputs) external stimulus in {0,1}.
      spikes: (T, B, n_physical) raster produced by the engine.
    Returns:
      {'cycles', 'sops', 'row_fetches'}: each (T, B) int32.
    """
    cfg = program.config
    geom = cfg.geometry
    sources = sources_raster(ext_spikes, spikes)  # (T, B, S)
    T, B = sources.shape[0], sources.shape[1]

    row_exists = jnp.asarray(program.row_exists, jnp.int32)  # (S, C)
    rows_active = jnp.einsum(
        "tbs,sc->tbc", sources, row_exists,
        preferred_element_type=jnp.int32,
    )  # (T, B, C) row fetches destined to each cluster
    rows_per_group = rows_active.reshape(
        T, B, geom.n_groups, geom.clusters_per_group
    ).sum(-1)
    group_cycles = rows_per_group.max(axis=-1)  # (T, B) parallel groups

    neuron_rows = row_exists[program.n_inputs:]  # (P, C)
    pkt_per_neuron = neuron_rows.sum(-1)  # (P,) packets a spike generates
    prev = sources[:, :, program.n_inputs:]  # spikes of the prev boundary
    pkts_by_cluster = (
        (prev * pkt_per_neuron[None, None, :])
        .reshape(T, B, geom.n_clusters, geom.neurons_per_cluster)
        .sum(-1)
    )  # (T, B, C)
    l1_cycles = pkts_by_cluster.reshape(
        T, B, geom.n_l1_routers, geom.clusters_per_l1
    ).sum(-1).max(-1)  # serialize per L1 router, routers in parallel
    noc_cycles = l1_cycles + cfg.spike_pipeline_depth + cfg.l2_hop_cycles

    cycles = jnp.maximum(group_cycles, noc_cycles) + cfg.sync_overhead_cycles
    fanout = jnp.asarray(program.fanout, jnp.int32)
    sops = jnp.sum(sources * fanout[None, None, :], axis=-1)
    row_fetches = rows_active.sum(-1)  # (T, B) SRAM row reads per step
    return {"cycles": cycles, "sops": sops, "row_fetches": row_fetches}


def run(program: CerebraHProgram, ext_spikes, backend: str = "reference"):
    """Run inference. ext_spikes: (T, B, n_inputs) in {0,1}.

    ``backend`` selects the SpikeEngine backend ("reference" | "pallas" |
    "pallas-mxu"); all are bit-exact (the mxu bound is checked at engine
    build). Returns dict with spike raster (physical layout), logical
    output counts, and per-step cycles / SOPs / SRAM row fetches.
    """
    engine = make_engine(program, backend)
    out = engine.run(ext_spikes)
    spikes = out["spikes"]
    cost = cost_model(program, ext_spikes, spikes)
    out_counts = jnp.sum(spikes[:, :, jnp.asarray(program.output_map)], axis=0)
    return {
        "spikes": spikes,
        "output_counts": out_counts,
        "cycles": cost["cycles"],
        "sops": cost["sops"],
        "row_fetches": cost["row_fetches"],
    }
