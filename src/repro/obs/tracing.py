"""Stream-lifecycle tracing and the hot path's profiler spans.

A :class:`SpanTracer` records what happened to each request/stream as a
sequence of typed spans::

    queued -> admitted(slot) -> chunk_step x N
           -> parked | migrated | redeployed | resumed ...
           -> retired(outcome)

Span kinds are catalogued in ``SPAN_KINDS`` (docs/observability.md
tables the same schema). Spans are either *events* (a point in time,
``t1 == t0``) or *durations* (opened as a context manager). Every span
carries the stream/request uid it belongs to (or ``None`` for
process-level spans like session deploys) plus free-form attributes.

Export is JSONL — one span per line, stable keys — so traces stream to
a file during a run and load with one ``json.loads`` per line.

Like the metrics registry, the tracer is injectable and clocked by an
injectable callable; components take ``tracer=None`` (no tracing, no
work) by default. Tracing reads the datapath and never changes it.

Separately, the served round's phases are named on the profiler's own
clock: :func:`hot_span` opens a ``jax.profiler.TraceAnnotation`` for one
of the names catalogued in ``HOT_SPANS``. These are always on — with no
profiler running each costs one annotation object — and a trace captured
with :func:`profile_trace` (``serve_snn --profile DIR``) shows them on
the same timeline as the device ops.
"""

from __future__ import annotations

import contextlib
import dataclasses
import json
import threading
import time

from jax.profiler import TraceAnnotation

__all__ = ["HOT_SPANS", "SPAN_KINDS", "Span", "SpanTracer", "hot_span",
           "profile_trace"]

# The lifecycle vocabulary. Tracers accept only these kinds, so a typo
# in an instrumentation site fails loudly instead of minting a new
# span type the docs don't know about.
SPAN_KINDS: tuple[str, ...] = (
    "queued",      # request entered the admission queue
    "admitted",    # bound to a slot (attrs: slot; resumed=True if from park)
    "chunk_step",  # one masked step_chunk dispatch (attrs: steps, slots)
    "parked",      # spilled to the connector mid-flight
    "resumed",     # re-admitted from a parked snapshot
    "migrated",    # carry moved between servers/slots via the connector
    "redeployed",  # drained + restored across a session redeploy
    "retired",     # terminal (attrs: outcome = done|cancelled|expired|...)
    "deploy",      # session (re)deploy of compiled programs
    "snapshot",    # connector snapshot write (attrs: nbytes)
    "restore",     # connector snapshot read (attrs: nbytes)
    "shard_step",  # one sharded dispatch (attrs: per-shard times, flags)
)

# The served round's phases, in the order one round opens them. Nesting:
# snn.pump > {admit, gather, snn.feed > {assemble, dispatch, readback,
# split}, retire}; assemble, dispatch and readback repeat per chunk of
# a long feed, split runs once. A long feed runs one chunk deep: chunk
# n+1's assemble and dispatch come before chunk n's readback.
HOT_SPANS: tuple[str, ...] = (
    "snn.pump",           # AsyncSpikeFrontend.pump: one whole round
    "snn.pump.admit",     # expiry, preemption, admission (attach)
    "snn.pump.gather",    # each running request's next chunk, embedded
    "snn.feed",           # SpikeServer.feed: one whole call
    "snn.feed.assemble",  # one chunk's dense ext and active arrays
    "snn.feed.dispatch",  # host-to-device copies + step_chunk (h2d_bytes;
                          # ahead=1 where the previous chunk is unread)
    "snn.feed.readback",  # the chunk's raster back to the host (d2h_bytes)
    "snn.feed.split",     # one count over all slots, a raster copy per
                          # stream (streams, chunks)
    "snn.pump.retire",    # detach_many, one zeroing dispatch (zeroed)
)
_HOT = frozenset(HOT_SPANS)

# the annotation factory; tests swap in a recorder
_annotation = TraceAnnotation


def hot_span(name: str, **counts):
    """A profiler annotation around one catalogued phase of the served
    round. ``counts`` (byte or slot counts) become the event's
    arguments."""
    if name not in _HOT:
        raise ValueError(
            f"unknown hot span {name!r}; expected one of {HOT_SPANS}")
    return _annotation(name, **counts)


@dataclasses.dataclass
class Span:
    """One recorded span. ``t1 == t0`` for instantaneous events."""

    kind: str
    uid: int | str | None
    t0: float
    t1: float
    attrs: dict

    @property
    def duration(self) -> float:
        return self.t1 - self.t0

    def to_dict(self) -> dict:
        return {
            "kind": self.kind,
            "uid": self.uid,
            "t0": self.t0,
            "t1": self.t1,
            "dur": self.t1 - self.t0,
            "attrs": self.attrs,
        }


class SpanTracer:
    """Record typed lifecycle spans; export as JSONL.

    Args:
      clock: monotonic-seconds callable (injectable for determinism).
      sink: optional open text file; when set, each completed span is
        written through immediately (one JSON line) as well as kept in
        memory. Lets ``--trace FILE`` stream during long runs.
    """

    def __init__(self, clock=time.perf_counter, *, sink=None):
        self.clock = clock
        self._sink = sink
        self._lock = threading.Lock()
        self._spans: list[Span] = []

    # -- recording ----------------------------------------------------
    def _record(self, span: Span) -> Span:
        with self._lock:
            self._spans.append(span)
            if self._sink is not None:
                self._sink.write(json.dumps(span.to_dict()) + "\n")
        return span

    def event(self, kind: str, uid=None, **attrs) -> Span:
        """An instantaneous lifecycle event (t1 == t0)."""
        self._check(kind)
        now = self.clock()
        return self._record(Span(kind, uid, now, now, attrs))

    @contextlib.contextmanager
    def span(self, kind: str, uid=None, **attrs):
        """A duration span around the ``with`` body.

        Attributes added to the yielded dict inside the body are kept
        (e.g. ``s["steps"] = n`` once known).
        """
        self._check(kind)
        t0 = self.clock()
        try:
            yield attrs
        finally:
            self._record(Span(kind, uid, t0, self.clock(), attrs))

    def _check(self, kind: str) -> None:
        if kind not in SPAN_KINDS:
            raise ValueError(
                f"unknown span kind {kind!r}; expected one of {SPAN_KINDS}"
            )

    # -- reading / export ---------------------------------------------
    @property
    def spans(self) -> list[Span]:
        with self._lock:
            return list(self._spans)

    def spans_for(self, uid) -> list[Span]:
        return [s for s in self.spans if s.uid == uid]

    def to_dicts(self) -> list[dict]:
        return [s.to_dict() for s in self.spans]

    def export_jsonl(self, path) -> int:
        """Write every span as one JSON line; returns the span count.

        ``path`` may be a filesystem path or an open text file.
        """
        spans = self.to_dicts()
        if hasattr(path, "write"):
            for d in spans:
                path.write(json.dumps(d) + "\n")
        else:
            with open(path, "w") as fh:
                for d in spans:
                    fh.write(json.dumps(d) + "\n")
        return len(spans)


@contextlib.contextmanager
def profile_trace(log_dir: str | None):
    """``jax.profiler`` capture around a block (no-op when dir is None).

    The ``serve_snn --profile DIR`` path: the captured trace shows the
    ``HOT_SPANS`` phases of every served round on the device ops' clock.
    """
    if log_dir is None:
        yield
        return
    import jax

    jax.profiler.start_trace(log_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()
