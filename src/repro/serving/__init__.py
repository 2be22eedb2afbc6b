"""Stateful SNN serving: spike streams over one compiled masked step.

A stream is a resident of the engine, not a request: its membrane state
(and, for current-based neurons, its synaptic current) stays in a fixed
slot of one slot-batch carry across chunk steps.

  * :mod:`repro.serving.snn` — :class:`SpikeServer` (fixed-slot admission
    by :class:`SlotScheduler`, chunked ``feed``/``feed_many``, closed-loop
    mode, mesh scale-out) and the per-model :class:`ModelStream` views
    ``AcceleratorSession.serve`` hands out;
  * :mod:`repro.serving.frontend` — :class:`AsyncSpikeFrontend`, the
    bounded request queue decoupled from the step loop (deadlines,
    backpressure, spill-on-evict);
  * :mod:`repro.serving.qos` — QoS classes and the weighted fair queue
    the frontend admits by;
  * :mod:`repro.serving.connector` — the carry snapshot wire format and
    the stores live migration, rolling redeploy and crash recovery ride.

Every name below is re-exported from those modules.
"""

from __future__ import annotations

from repro.serving.connector import (  # noqa: E402  (re-export)
    CarryConnectorBase,
    CarrySnapshot,
    FileCarryConnector,
    InMemoryCarryConnector,
    migrate_stream,
    rebalance_streams,
)
from repro.serving.frontend import (  # noqa: E402  (re-export)
    AsyncSpikeFrontend,
    FrontendConfig,
    RequestHandle,
)
from repro.serving.qos import (  # noqa: E402  (re-export)
    QoSClass,
    QoSPolicy,
    WeightedFairQueue,
)
from repro.serving.snn import (  # noqa: E402  (re-export)
    ModelStream,
    SlotScheduler,
    SpikeServer,
    StreamStats,
)

__all__ = ["SpikeServer", "SlotScheduler", "ModelStream", "StreamStats",
           "AsyncSpikeFrontend", "FrontendConfig", "RequestHandle",
           "QoSClass", "QoSPolicy", "WeightedFairQueue",
           "CarryConnectorBase", "CarrySnapshot", "InMemoryCarryConnector",
           "FileCarryConnector", "migrate_stream", "rebalance_streams"]
