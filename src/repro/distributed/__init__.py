"""Distribution: partition rules, mesh-sharded spike engine, straggler
detection."""

from repro.distributed import partition, spike_mesh, straggler  # noqa: F401
