"""Mesh-sharded spike engine — multi-device scale-out for fused SNN inference.

SNAP-V's Cerebra-H breaks the memory–processor bottleneck by distributing
neurons and their weight SRAM across parallel nodes and exchanging spikes
over a hierarchical NoC. This module is the software analogue over a
``jax.sharding.Mesh``:

  Cerebra-H hardware                    mesh engine
  ------------------                    -----------
  node-local weight SRAM slice          weight image partitioned COLUMN-wise
                                        over the ``neuron`` mesh axis — each
                                        device holds only its neurons' rows
                                        of the SRAM image
  neurons assigned to nodes             physical-neuron axis (cluster
                                        ranges) sharded over ``neuron``
  L2 NoC spike broadcast                per-timestep ``all_gather`` of the
                                        boundary spike raster inside the
                                        ``shard_map``-ped scan body
  independent stimulus streams          batch axis sharded over ``batch``
                                        (no communication)

:class:`MeshSpikeEngine` implements the exact timestep contract of
:class:`~repro.core.engine.SpikeEngine` (same ``fire_reset`` epilogue, same
``init_carry`` semantics, same backend set) and is a drop-in replacement:
``run``/``step``/``step_chunk`` take and return the same logical shapes.
Bit-exactness falls out of the partitioning: every output column's int32
accumulate happens entirely on the device that owns the column, over the
FULL all-gathered source vector, so no sum is ever split across devices.

Non-divisible shapes are handled by zero-padding (pad neurons have
all-zero weight rows *and* columns, so they can never perturb a real
neuron even if a degenerate threshold makes them fire; pad batch rows are
sliced off). Padding and un-padding live inside the jitted call, so XLA
fuses them with the scan.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from repro.core.engine import SpikeEngine
from repro.distributed.partition import PartitionRules, spec_for

__all__ = [
    "BATCH_AXIS",
    "NEURON_AXIS",
    "SNN_RULES",
    "MeshSpikeEngine",
    "ensure_host_devices",
    "make_spike_mesh",
    "parse_mesh_spec",
]

NEURON_AXIS = "neuron"
BATCH_AXIS = "batch"

# Logical-axis -> mesh-axis rules for SNN arrays, resolved through the same
# spec machinery the LM stack uses (divisibility fallbacks included):
#   neuron -> "neuron"  (physical-neuron / cluster-range axis; weight
#                        columns + carries + rasters)
#   batch  -> "batch"   (independent streams / examples)
# Source and time axes are never sharded: every device consumes the full
# all-gathered source vector, mirroring the NoC broadcast.
SNN_RULES = PartitionRules(
    rules={"neuron": NEURON_AXIS, "batch": BATCH_AXIS},
    batch_axes=(BATCH_AXIS,),
)


def ensure_host_devices(n: int) -> None:
    """Make ``n`` devices available: faked host devices on CPU, real
    chips anywhere else.

    On the CPU platform this forces ``n`` host-platform devices (CPU
    scale-out testing); it must run before JAX initializes its backends,
    and an existing device-count flag with a smaller count is rewritten.
    On an accelerator the flag is withdrawn again (it would only fake CPU
    devices) and ``n`` means ``n`` real chips. Raises when fewer than
    ``n`` devices are up — on CPU because the backend initialized before
    the flag could take effect.
    """
    import os
    import re

    if n <= 1:
        return
    flag = f"--xla_force_host_platform_device_count={n}"
    before = os.environ.get("XLA_FLAGS")
    flags = before or ""
    if "xla_force_host_platform_device_count" in flags:
        flags = re.sub(r"--xla_force_host_platform_device_count=\d+",
                       flag, flags)
    else:
        flags = (flags + " " + flag).strip()
    os.environ["XLA_FLAGS"] = flags
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "cpu":
        if before is None:
            del os.environ["XLA_FLAGS"]
        else:
            os.environ["XLA_FLAGS"] = before
        if len(devices) < n:
            raise RuntimeError(
                f"requested {n} devices but this host has {len(devices)} "
                f"{platform} device(s); on an accelerator --devices counts "
                f"real chips"
            )
        return
    if len(devices) < n:
        raise RuntimeError(
            f"requested {n} devices but JAX is running with "
            f"{len(devices)}; the backend initialized before "
            f"XLA_FLAGS={flag!r} could take effect — call "
            f"ensure_host_devices() before the first jax device use"
        )


def parse_mesh_spec(devices: int, spec: str | None) -> tuple[int, int]:
    """``'KNxKB'`` -> (neuron, batch) shard counts covering ``devices``.

    ``spec=None`` picks a default split: a 2-way neuron axis when the
    device count allows (e.g. 2x4 on 8), else all-batch. Shared by every
    launcher/bench ``--devices/--mesh`` flag pair.
    """
    if spec:
        kn_s, sep, kb_s = spec.lower().partition("x")
        try:
            if not sep:
                raise ValueError
            kn, kb = int(kn_s), int(kb_s)
            if kn < 1 or kb < 1:
                raise ValueError
        except ValueError:
            raise ValueError(
                f"--mesh must look like 'KNxKB' (e.g. 2x4), got {spec!r}"
            ) from None
    else:
        kn = 2 if devices % 2 == 0 and devices >= 4 else 1
        kb = devices // kn
    if kn * kb != devices:
        raise ValueError(
            f"--mesh {kn}x{kb} does not cover --devices {devices}")
    return kn, kb


def make_spike_mesh(neuron: int = 1, batch: int | None = None,
                    devices=None) -> Mesh:
    """A ``(neuron, batch)`` mesh over ``devices`` (default: all).

    ``batch=None`` spreads every remaining device over the batch axis.
    """
    devices = list(jax.devices() if devices is None else devices)
    if neuron < 1:
        raise ValueError(f"neuron axis must be >= 1, got {neuron}")
    if batch is None:
        batch = max(1, len(devices) // neuron)
    if batch < 1:
        raise ValueError(f"batch axis must be >= 1, got {batch}")
    if neuron * batch > len(devices):
        raise ValueError(
            f"mesh {neuron}x{batch} needs {neuron * batch} devices; "
            f"only {len(devices)} available"
        )
    devs = np.asarray(devices[: neuron * batch]).reshape(neuron, batch)
    return Mesh(devs, (NEURON_AXIS, BATCH_AXIS))


def _pad_up(n: int, k: int) -> int:
    return -(-n // k) * k


class MeshSpikeEngine(SpikeEngine):
    """A :class:`SpikeEngine` sharded over a ``(neuron, batch)`` mesh.

    Each device holds the weight-image columns of its neuron shard (the
    node-local SRAM slice); the scan body all-gathers the previous step's
    boundary spikes across the ``neuron`` axis — the only per-timestep
    communication — and the batch axis shards streams with no communication
    at all. Outputs, carries, and the ``step_chunk`` masked-slot semantics
    are byte-identical to the single-device engine (pinned by
    tests/test_spike_mesh.py).

    ``fuse_steps`` is carried (and preserved by ``from_engine`` /
    ``with_gate``, so to_mesh round-trips keep K), but the mesh scan
    EXECUTES per step regardless: the cross-device boundary-spike exchange
    is mandatory every timestep, so a K-step window cannot be fused across
    the NoC. Outputs stay byte-identical to the fused single-device engine
    by the fusion exactness contract.
    """

    def __init__(self, weights_raw, n_inputs: int, *, mesh: Mesh,
                 decay, threshold_raw: int, reset_mode: str,
                 backend: str = "reference", interpret: bool | None = None,
                 gate: str = "batch-tile", fuse_steps: int = 1):
        super().__init__(
            weights_raw, n_inputs, decay=decay, threshold_raw=threshold_raw,
            reset_mode=reset_mode, backend=backend, interpret=interpret,
            gate=gate, fuse_steps=fuse_steps,
        )
        missing = {NEURON_AXIS, BATCH_AXIS} - set(mesh.axis_names)
        if missing:
            raise ValueError(
                f"mesh must name axes {NEURON_AXIS!r} and {BATCH_AXIS!r} "
                f"(got {mesh.axis_names}); use make_spike_mesh()"
            )
        self.mesh = mesh
        self._kn = int(mesh.shape[NEURON_AXIS])
        self._kb = int(mesh.shape[BATCH_AXIS])
        # pad the physical axis so each device owns an equal neuron shard;
        # the source axis grows with it (recurrent feedback stays square).
        self._pp = _pad_up(self.n_phys, self._kn)
        sp = self.n_inputs + self._pp
        w = np.zeros((sp, self._pp), np.int32)
        w[: self.n_inputs, : self.n_phys] = np.asarray(
            self.weights_raw[: self.n_inputs])
        w[self.n_inputs: self.n_inputs + self.n_phys, : self.n_phys] = (
            np.asarray(self.weights_raw[self.n_inputs:]))
        self._w_spec = spec_for(("source", "neuron"), (sp, self._pp),
                                mesh, SNN_RULES)
        # column-wise: each device materializes only its SRAM image slice
        self.weights_sharded = jax.device_put(
            jnp.asarray(w), NamedSharding(mesh, self._w_spec))

    @classmethod
    def from_engine(cls, engine: SpikeEngine, mesh: Mesh
                    ) -> "MeshSpikeEngine":
        """Re-host an existing engine's program on a mesh (same semantics)."""
        return cls(
            engine.weights_raw, engine.n_inputs, mesh=mesh,
            decay=engine.decay, threshold_raw=engine.threshold_raw,
            reset_mode=engine.reset_mode, backend=engine.backend,
            interpret=engine.interpret, gate=engine.gate,
            fuse_steps=engine.fuse_steps,
        )

    def with_gate(self, gate: str) -> "MeshSpikeEngine":
        """Gate re-host that KEEPS the mesh (the base implementation would
        silently fall back to a single-device engine)."""
        if gate == self.gate:
            return self
        return MeshSpikeEngine(
            self.weights_raw, self.n_inputs, mesh=self.mesh,
            decay=self.decay, threshold_raw=self.threshold_raw,
            reset_mode=self.reset_mode, backend=self.backend,
            interpret=self.interpret, gate=gate,
            fuse_steps=self.fuse_steps,
        )

    def with_fuse_steps(self, fuse_steps: int) -> "MeshSpikeEngine":
        """Fusion re-host that KEEPS the mesh (the base implementation
        would silently fall back to a single-device engine)."""
        if int(fuse_steps) == self.fuse_steps:
            return self
        return MeshSpikeEngine(
            self.weights_raw, self.n_inputs, mesh=self.mesh,
            decay=self.decay, threshold_raw=self.threshold_raw,
            reset_mode=self.reset_mode, backend=self.backend,
            interpret=self.interpret, gate=self.gate,
            fuse_steps=fuse_steps,
        )

    @property
    def device_count(self) -> int:
        return self._kn * self._kb

    # ------------------------------------------------------------------
    def _scan_weights(self):
        return self.weights_sharded

    def _specs(self, batch_padded: int, steps: int):
        """PartitionSpecs for one padded (T, B, ...) dispatch."""
        carry = spec_for(("batch", "neuron"), (batch_padded, self._pp),
                         self.mesh, SNN_RULES)
        ext = spec_for(("time", "batch", "source"),
                       (steps, batch_padded, self.n_inputs),
                       self.mesh, SNN_RULES)
        raster = spec_for(("time", "batch", "neuron"),
                          (steps, batch_padded, self._pp),
                          self.mesh, SNN_RULES)
        active = spec_for(("time", "batch"), (steps, batch_padded),
                          self.mesh, SNN_RULES)
        cdict = {"v": carry, "spikes": carry}
        return cdict, ext, raster, active

    def step(self, carry, ext_t):
        """Sharded single step (closed-loop callers): a T=1 chunk through
        the mesh path, so the column-sharded SRAM image and spike exchange
        are used — the inherited ``step`` would silently compute on the
        full replicated weights."""
        final, spikes = self.step_chunk(carry, ext_t[None])
        return final, spikes[0]

    def _exchange_step(self, weights_local, carry_local, ext_t):
        """One timestep on a neuron shard: NoC exchange + local step.

        The all-gather reassembles the full previous-boundary spike raster
        (the L2 broadcast); everything after it is the unmodified
        single-device step on this device's weight columns, so the shared
        LIF epilogue (and any backend kernel) runs untouched.
        """
        spikes_full = jax.lax.all_gather(
            carry_local["spikes"], NEURON_AXIS, axis=1, tiled=True)
        return self._step(
            weights_local,
            {"v": carry_local["v"], "spikes": spikes_full},
            ext_t,
        )

    # ------------------------------------------------------------------
    def _run_impl(self, weights, ext_spikes):
        T, B = ext_spikes.shape[0], ext_spikes.shape[1]
        bp = _pad_up(B, self._kb)
        ext_p = jnp.pad(ext_spikes, ((0, 0), (0, bp - B), (0, 0)))
        carry = {
            "v": jnp.zeros((bp, self._pp), jnp.int32),
            "spikes": jnp.zeros((bp, self._pp), jnp.int32),
        }
        cspec, espec, rspec, _ = self._specs(bp, T)

        def local(weights_l, carry_l, ext_l):
            step = lambda c, x: self._exchange_step(weights_l, c, x)
            return jax.lax.scan(step, carry_l, ext_l)

        final, spikes = jax.shard_map(
            local, mesh=self.mesh,
            in_specs=(self._w_spec, cspec, espec),
            out_specs=(cspec, rspec),
            check_vma=False,
        )(weights, carry, ext_p)
        return {
            "spikes": spikes[:, :B, : self.n_phys],
            "v_final": final["v"][:B, : self.n_phys],
        }

    # ------------------------------------------------------------------
    def _chunk_impl(self, weights, carry, ext, active):
        T, B = ext.shape[0], ext.shape[1]
        bp = _pad_up(B, self._kb)
        ext_p = jnp.pad(ext, ((0, 0), (0, bp - B), (0, 0)))
        active_p = jnp.pad(active, ((0, 0), (0, bp - B)))  # pad slots idle
        pad2 = ((0, bp - B), (0, self._pp - self.n_phys))
        carry_p = {
            "v": jnp.pad(carry["v"], pad2),
            "spikes": jnp.pad(carry["spikes"], pad2),
        }
        cspec, espec, rspec, aspec = self._specs(bp, T)

        def local(weights_l, carry_l, ext_l, active_l):
            step = lambda c, x: self._exchange_step(weights_l, c, x)
            return self._masked_chunk_scan(step, carry_l, ext_l, active_l)

        final, spikes = jax.shard_map(
            local, mesh=self.mesh,
            in_specs=(self._w_spec, cspec, espec, aspec),
            out_specs=(cspec, rspec),
            check_vma=False,
        )(weights, carry_p, ext_p, active_p)
        final = {
            "v": final["v"][:B, : self.n_phys],
            "spikes": final["spikes"][:B, : self.n_phys],
        }
        return final, spikes[:, :B, : self.n_phys]
