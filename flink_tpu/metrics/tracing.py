"""Job-wide causal tracing + failure flight recorder.

Analog of the reference trace API (flink-metrics-core
traces/{Span.java, SpanBuilder.java:27, reporter/TraceReporter.java:31},
wired by TraceReporterSetup.java:63; checkpoint/recovery durations emitted
from CheckpointStatsTracker.java:267), grown into a causal tracing
subsystem: every span carries ``trace_id``/``span_id``/``parent_id`` so
related work — a checkpoint's trigger → per-subtask barrier alignment →
snapshot → artifact store → ack → complete fan-out — forms one tree even
when the pieces run on different hosts. A :class:`TraceContext` is the
wire-portable (trace_id, span_id) pair; it crosses process boundaries on
``CheckpointBarrier.trace`` and the distributed control messages, and
crosses thread boundaries via an explicit ``parent=`` argument or the
thread-local ambient context pushed by ``with tracer.span(...)``.

Clocks: span timestamps are epoch NANOSECONDS (``start_ns``/``end_ns``;
``start_ms``/``end_ms``/``duration_ms`` are derived, the reference Span
contract) *measured* on the monotonic clock — the epoch offset is
sampled once at import and added to ``time.monotonic_ns()`` — so a
wall-clock step (NTP slew, manual date change) can never produce a
negative duration, and a 300 us dispatch has a duration.

Stage spans (:meth:`Tracer.stage` / :meth:`Tracer.open_stage`) are the
per-batch / per-watermark / per-fire intervals of the mailbox loop. Each
one is ALSO a ``jax.profiler.TraceAnnotation`` named ``<scope>.<Name>``
with the span's attributes as its arguments, so under a profiler session
the same interval sits in the ``.xplane.pb`` on the device trace's clock
(a reader pairs the two by ``(name, task, seq)`` and checks the clocks
against each other). With no session the annotation is a flag test.

Reporters are pluggable (:class:`TraceReporter`): a bounded in-memory
ring for REST/CLI inspection, a Chrome trace-event (Perfetto-loadable)
exporter (:func:`chrome_trace_events`), and the always-on
:class:`FlightRecorder` — a process-global bounded ring of recent
spans/events dumped to a timestamped JSON file whenever a fault
chokepoint fires (StallError, region restart, CorruptArtifactError,
zombie fence), turning every fault-injection drill into a readable
post-mortem.

The process-global :data:`TRACER` follows the same singleton +
``configure(config)`` pattern as ``FAULTS``/``WATCHDOG`` and is wired on
by every deploy path (local ``run_job``, ``JobSupervisor``, distributed
coordinator/worker).
"""

from __future__ import annotations

import json
import os
import re
import tempfile
import threading
import time
import uuid
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Dict, Iterable, List, Optional

__all__ = [
    "Span", "SpanBuilder", "TraceContext", "TraceReporter",
    "InMemoryTraceReporter", "FlightRecorder", "Tracer",
    "TRACER", "FLIGHT_RECORDER", "chrome_trace_events",
    "current_context", "use_context", "now_ms", "now_ns", "thread_cpu_ns",
    "Stage",
    "record_flight_event", "dump_flight_recorder", "SPAN_INVENTORY",
]

# Epoch offset sampled once at import: now_ns() is monotonic-derived but
# reports epoch nanoseconds, so durations are immune to wall-clock steps
# while start times still line up with log timestamps.
_EPOCH_OFFSET_NS = time.time_ns() - time.monotonic_ns()  # lint: wall-clock-ok sampled ONCE at import to anchor the monotonic clock
_NS_PER_MS = 1_000_000


def now_ns() -> int:
    """Epoch nanoseconds measured on the monotonic clock."""
    return time.monotonic_ns() + _EPOCH_OFFSET_NS


def now_ms() -> int:
    """Epoch milliseconds measured on the monotonic clock."""
    return now_ns() // _NS_PER_MS


#: CPU nanoseconds the CALLING thread has run (user + system). Beside the
#: wall clock it tells a thread that computes from one that stands still:
#: waiting for the device, for the GIL, or for the machine.
thread_cpu_ns = time.thread_time_ns


def _new_id() -> str:
    return uuid.uuid4().hex[:16]


@dataclass(frozen=True)
class TraceContext:
    """Wire-portable causal context: the (trace_id, span_id) a child span
    parents itself on. ``to_wire()`` produces a plain dict safe to embed
    in pickled control messages and ``CheckpointBarrier.trace``."""

    trace_id: str
    span_id: str

    def to_wire(self) -> dict:
        return {"trace_id": self.trace_id, "span_id": self.span_id}

    @staticmethod
    def from_wire(d: Optional[dict]) -> Optional["TraceContext"]:
        if not d:
            return None
        try:
            return TraceContext(str(d["trace_id"]), str(d["span_id"]))
        except Exception:
            return None


@dataclass(frozen=True)
class Span:
    scope: str
    name: str
    start_ns: int
    end_ns: int
    attributes: dict = field(default_factory=dict)
    trace_id: str = ""
    span_id: str = ""
    parent_id: str = ""

    @property
    def start_ms(self) -> int:
        return self.start_ns // _NS_PER_MS

    @property
    def end_ms(self) -> int:
        return self.end_ns // _NS_PER_MS

    @property
    def duration_ms(self) -> int:
        return self.end_ms - self.start_ms

    @property
    def duration_ns(self) -> int:
        return self.end_ns - self.start_ns

    def to_dict(self) -> dict:
        return {
            "scope": self.scope, "name": self.name,
            "start_ms": self.start_ms, "end_ms": self.end_ms,
            "duration_ms": self.duration_ms,
            "start_ns": self.start_ns, "end_ns": self.end_ns,
            "trace_id": self.trace_id, "span_id": self.span_id,
            "parent_id": self.parent_id,
            "attributes": dict(self.attributes),
        }

    @staticmethod
    def from_dict(d: dict) -> "Span":
        """Inverse of ``to_dict`` (REST / CLI); dicts written before the
        ns fields existed carry milliseconds only."""
        start_ns = d.get("start_ns", int(d["start_ms"]) * _NS_PER_MS)
        end_ns = d.get("end_ns", int(d["end_ms"]) * _NS_PER_MS)
        return Span(d["scope"], d["name"], int(start_ns), int(end_ns),
                    dict(d.get("attributes") or {}), d.get("trace_id", ""),
                    d.get("span_id", ""), d.get("parent_id", ""))


# ---------------------------------------------------------------------------
# Ambient context: a thread-local stack so nested ``with tracer.span(...)``
# blocks parent automatically without threading a context argument through
# every call. Cross-thread/cross-host propagation stays explicit (parent=).
# ---------------------------------------------------------------------------

_TLS = threading.local()


def current_context() -> Optional[TraceContext]:
    stack = getattr(_TLS, "stack", None)
    return stack[-1] if stack else None


class use_context:
    """Pin ``ctx`` as the ambient parent for spans started on this thread
    inside the block (mailbox threads adopt the coordinator's checkpoint
    context carried on a barrier this way)."""

    def __init__(self, ctx: Optional[TraceContext]):
        self._ctx = ctx

    def __enter__(self) -> Optional[TraceContext]:
        stack = getattr(_TLS, "stack", None)
        if stack is None:
            stack = _TLS.stack = []
        stack.append(self._ctx)
        return self._ctx

    def __exit__(self, exc_type, exc, tb) -> None:
        _TLS.stack.pop()


class SpanBuilder:
    """Fluent builder (reference SpanBuilder). Usable imperatively
    (``b = tracer.span(...); ...; b.finish()``) or as a context manager —
    entering resets the start timestamp and pushes this span's context as
    the ambient parent for children started inside the block."""

    def __init__(self, tracer: "Tracer", scope: str, name: str,
                 parent: Optional[TraceContext] = None):
        self._tracer = tracer
        self._scope = scope
        self._name = name
        self._start_ns = now_ns()
        self._attrs: dict = {}
        if parent is None:
            parent = current_context()
        self._trace_id = parent.trace_id if parent else _new_id()
        self._span_id = _new_id()
        self._parent_id = parent.span_id if parent else ""
        self._finished = False
        self._ctx_cm: Optional[use_context] = None

    @property
    def context(self) -> TraceContext:
        """This span's identity, for parenting children (possibly on
        another host) before the span itself finishes."""
        return TraceContext(self._trace_id, self._span_id)

    def set_parent(self, ctx: Optional[TraceContext]) -> "SpanBuilder":
        if ctx is not None:
            self._trace_id = ctx.trace_id
            self._parent_id = ctx.span_id
        return self

    def set_attribute(self, key: str, value: Any) -> "SpanBuilder":
        self._attrs[key] = value
        return self

    def set_start_ts(self, start_ms: int) -> "SpanBuilder":
        self._start_ns = int(start_ms) * _NS_PER_MS
        return self

    def finish(self, end_ms: Optional[int] = None) -> Span:
        end = now_ns() if end_ms is None else int(end_ms) * _NS_PER_MS
        if end < self._start_ns:        # wall-clock step / caller skew
            end = self._start_ns
        span = Span(self._scope, self._name, self._start_ns, end,
                    dict(self._attrs), self._trace_id, self._span_id,
                    self._parent_id)
        if not self._finished:
            self._finished = True
            self._tracer._report(span)
        return span

    def __enter__(self) -> "SpanBuilder":
        self._start_ns = now_ns()
        self._ctx_cm = use_context(self.context)
        self._ctx_cm.__enter__()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        if self._ctx_cm is not None:
            self._ctx_cm.__exit__(exc_type, exc, tb)
            self._ctx_cm = None
        self.set_attribute("error", exc_type is not None)
        self.finish()


_ANNOTATION = None  # jax.profiler.TraceAnnotation, imported on first use


def _annotation_cls():
    """``jax.profiler.TraceAnnotation``, imported lazily: this module
    stays importable (tpu-lint, docs tooling) without jax."""
    global _ANNOTATION
    if _ANNOTATION is None:
        try:
            from jax.profiler import TraceAnnotation
            _ANNOTATION = TraceAnnotation
        except Exception:  # noqa: BLE001 - no profiler: ring spans only
            _ANNOTATION = False
    return _ANNOTATION


def _annotation_args(attrs: dict) -> dict:
    """The profiler encodes an annotation's arguments into its name as
    ``#k=v,k=v#``, so a string value may hold neither ``#`` nor ``,``: a
    task id ``v3#0`` rides as ``v3/0`` (the span keeps the real one)."""
    return {k: v.replace("#", "/").replace(",", ";")
            if isinstance(v, str) else v for k, v in attrs.items()}


class Stage:
    """One stage interval of a mailbox loop (a batch, a watermark, a
    fire, a wait), recorded on both clocks from ONE pair of timestamps:

    * a ``jax.profiler.TraceAnnotation`` named ``<scope>.<Name>`` whose
      arguments are the attributes, so that under a profiler session the
      interval is in the ``.xplane.pb`` beside the device's events;
    * when ``traces.enabled``, a :class:`Span` with the same attributes,
      parented on ``parent`` or the ambient context.

    ``with tracer.stage(...)`` pushes the stage as the ambient parent of
    spans started inside the block. ``tracer.open_stage(...)`` returns an
    open stage that a LATER mailbox turn closes (``close``); it neither
    reads nor pushes the ambient stack: with no ``parent`` it is the root
    of its own trace. ``total`` names a ``(dict, key)`` the duration in
    seconds is added to on close, so a stage total and its spans come
    from one timing site.

    From the same two sites the stage reads the opening thread's CPU
    clock (``thread_cpu_ns``), inside the wall stamps, and closes with
    ``cpu_ms``: the part of its duration the thread was computing. The
    rest it stood still (a device wait, the GIL in another thread's
    hands, a machine that did not run it). A stage closed by another
    thread than opened it (a reclaim's) has no ``cpu_ms``. The clock is
    as fine as the kernel keeps it: nanoseconds on a plain Linux host; a
    kernel that samples CPU time by ticks (10 ms on some sandboxed hosts)
    gives multiples of a tick, good only added up over many stages.

    Every stage carries ``task`` (the mailbox thread's name, which is the
    task id) and the caller's ``seq``; ``(scope.Name, task, seq)``
    identifies the interval in both records. Stages run per batch, per
    watermark and per fire, never per record or per poll, and a stage
    that is opened is reported: a caller that cannot know beforehand
    whether an attempt is an interval at all (a source read that may
    return nothing) stamps ``now_ns()`` before it and opens the stage
    afterwards with ``start_ns=`` that stamp (and ``start_cpu_ns=`` the
    CPU stamp beside it). The span then starts at the stamp; the
    annotation, which cannot be backdated, starts where the stage was
    opened (a source's ``read_ms`` later)."""

    __slots__ = ("_tracer", "scope", "name", "attrs", "_parent", "_total",
                 "_ann", "_late", "_ctx", "_ctx_cm", "_open", "start_ns",
                 "end_ns", "_thread", "_cpu_ns")

    def __init__(self, tracer: "Tracer", scope: str, name: str,
                 parent: Optional[TraceContext], total: Optional[tuple],
                 attrs: dict, start_ns: Optional[int] = None,
                 start_cpu_ns: Optional[int] = None):
        self._tracer = tracer
        self.scope = scope
        self.name = name
        thread = threading.current_thread()
        attrs.setdefault("task", thread.name)
        self.attrs = attrs
        self._thread = thread.ident
        self._parent = parent
        self._total = total
        self._ctx: Optional[TraceContext] = None
        self._ctx_cm: Optional[use_context] = None
        self._open = True
        self.end_ns = 0
        ann = _annotation_cls()
        if ann and ann.is_enabled():
            self._ann = ann(f"{scope}.{name}", **_annotation_args(attrs))
            self._ann.__enter__()
            self._late: Optional[dict] = {}
        else:
            self._ann = self._late = None
        self.start_ns = now_ns() if start_ns is None else start_ns
        self._cpu_ns = (thread_cpu_ns() if start_cpu_ns is None
                        else start_cpu_ns)

    @property
    def context(self) -> Optional[TraceContext]:
        """This stage's identity, for parenting children from a later
        mailbox turn; None while tracing is off (children then start
        their own trace, which is discarded all the same)."""
        if self._ctx is None and self._tracer.enabled:
            parent = self._parent
            self._ctx = TraceContext(
                parent.trace_id if parent else _new_id(), _new_id())
        return self._ctx

    def set(self, key: str, value: Any) -> "Stage":
        """Attribute known only once the stage runs (rows, fires); the
        annotation receives it when the stage closes."""
        self.attrs[key] = value
        if self._late is not None:
            self._late[key] = value
        return self

    def count(self, key: str) -> None:
        """Add one to a counting attribute (a fire's ``unready_polls``)."""
        self.set(key, self.attrs.get(key, 0) + 1)

    @property
    def duration_ns(self) -> int:
        return (self.end_ns if not self._open else now_ns()) - self.start_ns

    @property
    def duration_s(self) -> float:
        return self.duration_ns / 1e9

    @property
    def duration_ms(self) -> float:
        return self.duration_ns / 1e6

    def close(self, end_ns: Optional[int] = None, **attrs: Any) -> None:
        """``end_ns``: a stamp the caller took to compute an attribute
        from the stage's own duration (a source's ``emit_ms``)."""
        if not self._open:
            return
        for k, v in attrs.items():
            self.set(k, v)
        if self._thread == threading.get_ident():
            self.set("cpu_ms",
                     round((thread_cpu_ns() - self._cpu_ns) / 1e6, 3))
        self.end_ns = now_ns() if end_ns is None else end_ns
        self._open = False
        if self._ann is not None:
            if self._late:
                self._ann.set_metadata(**_annotation_args(self._late))
            self._ann.__exit__(None, None, None)
            self._ann = None
        if self._total is not None:
            d, key = self._total
            d[key] = d.get(key, 0.0) + (self.end_ns - self.start_ns) / 1e9
        if self._tracer.enabled:
            ctx = self.context
            parent = self._parent
            self._tracer._report(Span(
                self.scope, self.name, self.start_ns, self.end_ns,
                dict(self.attrs), ctx.trace_id, ctx.span_id,
                parent.span_id if parent else ""), stage=True)

    def __enter__(self) -> "Stage":
        ctx = self.context
        if ctx is not None:
            self._ctx_cm = use_context(ctx)
            self._ctx_cm.__enter__()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        if self._ctx_cm is not None:
            self._ctx_cm.__exit__(exc_type, exc, tb)
            self._ctx_cm = None
        if self._open:
            if exc_type is not None:
                self.attrs["error"] = True
            self.close()


class TraceReporter:
    """Receives completed spans (reference TraceReporter.addSpan)."""

    def add_span(self, span: Span) -> None:
        raise NotImplementedError

    def add_stage_span(self, span: Span) -> None:
        """A completed stage span (:class:`Stage`): a dozen per batch
        where the other spans come per checkpoint or per fault. The two
        built-in reporters keep them in a ring of their own, so that the
        steady stream cannot evict the rare spans."""
        self.add_span(span)


class InMemoryTraceReporter(TraceReporter):
    """Bounded in-memory span rings for tests, REST and the CLI: the most
    recent ``max_retained`` spans (``traces.max-retained``) and, apart
    from them, ``STAGE_FACTOR`` times as many stage spans, which come a
    dozen per batch where the others come per checkpoint or per fault.
    Evictions from either are counted into DEVICE_STATS as
    ``spans_dropped_total``."""

    STAGE_FACTOR = 16

    def __init__(self, max_retained: int = 4096):
        self.spans: list[Span] = []
        self.stage_spans: list[Span] = []
        self.max_retained = int(max_retained)
        self.dropped = 0
        self._lock = threading.Lock()

    def _add(self, ring: list, limit: int, span: Span) -> None:
        excess = 0
        with self._lock:
            ring.append(span)
            if len(ring) > limit:
                excess = len(ring) - limit
                del ring[:excess]
                self.dropped += excess
        if excess:
            _note_spans_dropped(excess)

    def add_span(self, span: Span) -> None:
        self._add(self.spans, self.max_retained, span)

    def add_stage_span(self, span: Span) -> None:
        self._add(self.stage_spans, self.STAGE_FACTOR * self.max_retained,
                  span)

    def by_name(self, name: str) -> list[Span]:
        return [s for s in self.snapshot() if s.name == name]

    def snapshot(self) -> list[Span]:
        """Both rings as one list, in the order the spans ended."""
        with self._lock:
            if not self.stage_spans:
                return list(self.spans)
            return sorted(self.spans + self.stage_spans,
                          key=lambda s: s.end_ns)

    def clear(self) -> None:
        with self._lock:
            self.spans.clear()
            self.stage_spans.clear()
            self.dropped = 0


def _note_spans_dropped(n: int) -> None:
    try:
        from .device import DEVICE_STATS
        DEVICE_STATS.note_spans_dropped(n)
    except Exception:  # noqa: BLE001 - metrics must not kill reporting
        pass


class FlightRecorder(TraceReporter):
    """Always-on, low-overhead post-mortem buffer: a bounded ring of the
    most recent spans and discrete events. ``dump(reason)`` writes the
    ring to a timestamped JSON file (rate-limited per reason) and is
    invoked automatically from the fault chokepoints — watchdog stall,
    region/job restart, corrupt-artifact detection, zombie fence — so
    the seconds *before* a failure are preserved, not just counters."""

    KEEP_DUMPS = 16

    def __init__(self, capacity: int = 512, dump_dir: Optional[str] = None,
                 min_dump_interval_s: float = 1.0):
        self.dump_dir = dump_dir
        self.min_dump_interval_s = min_dump_interval_s
        self.dumps: list[dict] = []
        self._ring: deque = deque(maxlen=int(capacity))
        # stage spans apart, as in the in-memory reporter: a dump holds
        # the last ``capacity`` of each
        self._stages: deque = deque(maxlen=int(capacity))
        self._last_dump_ms: Dict[str, int] = {}
        self._lock = threading.Lock()

    @property
    def capacity(self) -> int:
        return self._ring.maxlen or 0

    def set_capacity(self, capacity: int) -> None:
        with self._lock:
            self._ring = deque(self._ring, maxlen=max(1, int(capacity)))
            self._stages = deque(self._stages, maxlen=max(1, int(capacity)))

    def _add(self, ring: deque, span: Span) -> None:
        entry = {"type": "span", "ts_ms": span.end_ms}
        entry.update(span.to_dict())
        with self._lock:
            ring.append(entry)

    def add_span(self, span: Span) -> None:
        self._add(self._ring, span)

    def add_stage_span(self, span: Span) -> None:
        self._add(self._stages, span)

    def record_event(self, kind: str, **fields: Any) -> None:
        entry = {"type": "event", "kind": kind, "ts_ms": now_ms()}
        entry.update(fields)
        with self._lock:
            self._ring.append(entry)

    def snapshot(self) -> list[dict]:
        with self._lock:
            return list(self._ring)

    def stage_snapshot(self) -> list[dict]:
        with self._lock:
            return list(self._stages)

    def dump(self, reason: str, **fields: Any) -> Optional[str]:
        """Write the current ring to a timestamped file; returns the path,
        or None when rate-limited (same reason within
        ``min_dump_interval_s``) or the write fails."""
        ts = now_ms()
        with self._lock:
            last = self._last_dump_ms.get(reason, 0)
            if ts - last < self.min_dump_interval_s * 1000.0:
                return None
            self._last_dump_ms[reason] = ts
            entries = list(self._ring)
            stages = list(self._stages)
        directory = self.dump_dir or os.path.join(
            tempfile.gettempdir(), "flink_tpu_flight")
        safe = re.sub(r"[^A-Za-z0-9._-]", "_", reason) or "fault"
        path = os.path.join(directory, f"flight-{safe}-{ts}.json")
        payload = {"reason": reason, "dumped_at_ms": ts,
                   "pid": os.getpid(), "entry_count": len(entries),
                   "context": dict(fields), "entries": entries,
                   "stages": stages}
        try:
            os.makedirs(directory, exist_ok=True)
            tmp = path + ".tmp"
            with open(tmp, "w") as f:
                json.dump(payload, f, default=str)
            os.replace(tmp, path)
        except OSError:
            return None
        record = {"reason": reason, "path": path, "ts_ms": ts,
                  "entry_count": len(entries)}
        record.update(fields)
        with self._lock:
            self.dumps.append(record)
            del self.dumps[:-self.KEEP_DUMPS]
        return path

    def reset(self) -> None:
        with self._lock:
            self._ring.clear()
            self._stages.clear()
            self.dumps.clear()
            self._last_dump_ms.clear()


class Tracer:
    """Span factory + reporter fan-out (reference TraceReporterSetup)."""

    def __init__(self, reporters: Optional[list[TraceReporter]] = None):
        self._reporters = list(reporters or [])
        self.enabled = True

    def add_reporter(self, reporter: TraceReporter) -> None:
        self._reporters.append(reporter)

    def span(self, scope: str, name: str,
             parent: Optional[TraceContext] = None) -> SpanBuilder:
        return SpanBuilder(self, scope, name, parent=parent)

    def stage(self, scope: str, name: str,
              parent: Optional[TraceContext] = None,
              total: Optional[tuple] = None,
              start_ns: Optional[int] = None,
              start_cpu_ns: Optional[int] = None, **attrs: Any) -> Stage:
        """A stage interval as a context manager (see :class:`Stage`)."""
        return Stage(self, scope, name, parent or current_context(), total,
                     attrs, start_ns, start_cpu_ns)

    def open_stage(self, scope: str, name: str,
                   parent: Optional[TraceContext] = None,
                   total: Optional[tuple] = None, **attrs: Any) -> Stage:
        """A stage interval that is not a ``with`` block: opened at one
        mailbox turn, closed at a later one."""
        return Stage(self, scope, name, parent, total, attrs)

    def _report(self, span: Span, stage: bool = False) -> None:
        if not self.enabled:
            return
        for r in self._reporters:
            try:
                if stage:
                    r.add_stage_span(span)
                else:
                    r.add_span(span)
            except Exception:  # noqa: BLE001 - reporters must not kill jobs
                pass

    def retained_spans(self) -> list[Span]:
        """Spans held by the first attached in-memory reporter, stage
        spans included (the REST / CLI inspection surface)."""
        for r in self._reporters:
            if isinstance(r, InMemoryTraceReporter):
                return r.snapshot()
        return []

    def configure(self, config) -> None:
        """Apply ``traces.*`` options (same pattern as FAULTS/WATCHDOG)."""
        from ..core.config import TraceOptions
        self.enabled = bool(config.get(TraceOptions.ENABLED))
        for r in self._reporters:
            if isinstance(r, InMemoryTraceReporter):
                r.max_retained = int(config.get(TraceOptions.MAX_RETAINED))
            elif isinstance(r, FlightRecorder):
                cap = int(config.get(TraceOptions.FLIGHT_CAPACITY))
                if cap != r.capacity:
                    r.set_capacity(cap)
                r.dump_dir = config.get(TraceOptions.FLIGHT_DIR) or None
                r.min_dump_interval_s = float(
                    config.get(TraceOptions.FLIGHT_MIN_INTERVAL))

    def reset(self) -> None:
        """Test hook: clear retained spans and any attached recorder."""
        self.enabled = True
        for r in self._reporters:
            if isinstance(r, InMemoryTraceReporter):
                r.clear()
            elif isinstance(r, FlightRecorder):
                r.reset()


# ---------------------------------------------------------------------------
# Chrome trace-event (Perfetto-loadable) export
# ---------------------------------------------------------------------------

def chrome_trace_events(spans: Iterable[Span], pid: int = 0,
                        counters: Optional[Iterable[dict]] = None) -> dict:
    """Render spans as a Chrome trace-event JSON object (the ``ph: "X"``
    complete-event form, microseconds from the spans' ns clock) loadable
    in Perfetto / chrome://tracing. Scopes
    map to tids so each subsystem gets its own track; causal ids ride in
    ``args`` for tree reconstruction.

    ``counters`` takes ledger samples
    (``DEVICE_LEDGER.trace_counters()``: dicts with ``ts_ms``/``site``/
    ``ms``) and renders them as ``ph: "C"`` counter tracks — one
    ``dispatch_ms:<site>`` series per dispatch site, alongside the span
    tracks. The ledger times the HOST's dispatch call (the enqueue, which
    returns before the device runs the program), so the track is named
    for that; device time comes from a ``jax.profiler`` trace."""
    tids: Dict[str, int] = {}
    events: List[dict] = []
    for c in counters or ():
        events.append({
            "name": f"dispatch_ms:{c['site']}", "cat": "profiler",
            "ph": "C", "ts": int(c["ts_ms"]) * 1000, "pid": pid,
            "args": {"ms": round(float(c["ms"]), 4)},
        })
    for span in spans:
        tid = tids.setdefault(span.scope, len(tids))
        args: Dict[str, Any] = {
            "trace_id": span.trace_id, "span_id": span.span_id,
        }
        if span.parent_id:
            args["parent_id"] = span.parent_id
        for k, v in span.attributes.items():
            args[k] = v if isinstance(v, (int, float, bool, str)) else str(v)
        events.append({
            "name": span.name, "cat": span.scope, "ph": "X",
            "ts": span.start_ns // 1000,
            "dur": max(span.duration_ns, 0) // 1000,
            "pid": pid, "tid": tid, "args": args,
        })
    for scope, tid in sorted(tids.items(), key=lambda kv: kv[1]):
        events.append({"name": "thread_name", "ph": "M", "pid": pid,
                       "tid": tid, "args": {"name": scope}})
    return {"traceEvents": events, "displayTimeUnit": "ms"}


# ---------------------------------------------------------------------------
# Process-global tracer + flight recorder (singleton pattern of FAULTS /
# WATCHDOG / DEVICE_STATS; configured by every deploy path).
# ---------------------------------------------------------------------------

FLIGHT_RECORDER = FlightRecorder()

TRACER = Tracer()
TRACER.add_reporter(InMemoryTraceReporter())
TRACER.add_reporter(FLIGHT_RECORDER)


def _owning_job(fields: dict) -> dict:
    """Ensure every flight event/dump names its owning job: callers that
    know it pass ``job=...`` explicitly; for the rest the thread-local
    dispatch context (pinned at task-thread start) fills it in, so
    multi-tenant post-mortems can split one ring by failure domain."""
    if not fields.get("job"):
        from .profiler import dispatch_context
        job = dispatch_context()[0]
        if job:
            fields = dict(fields, job=job)
    return fields


def record_flight_event(kind: str, **fields: Any) -> None:
    """Append a discrete (non-span) event to the flight-recorder ring."""
    try:
        FLIGHT_RECORDER.record_event(kind, **_owning_job(fields))
    except Exception:  # noqa: BLE001 - observability must not kill jobs
        pass


def dump_flight_recorder(reason: str, **fields: Any) -> Optional[str]:
    """Record ``reason`` as an event, then dump the ring to a file.
    Called from the fault chokepoints; never raises."""
    try:
        fields = _owning_job(fields)
        FLIGHT_RECORDER.record_event(reason, **fields)
        return FLIGHT_RECORDER.dump(reason, **fields)
    except Exception:  # noqa: BLE001 - observability must not kill jobs
        return None


# Every (scope, name) pair the runtime emits, with its emitting site.
# docs/OBSERVABILITY.md renders this inventory as a table and
# tests/test_tracing.py asserts the two stay identical, so the doc
# cannot rot. Keep entries sorted by (scope, name).
SPAN_INVENTORY: tuple = (
    ("checkpoint", "Align",
     "runtime/stream_task.py — barrier arrival → alignment per subtask"),
    ("checkpoint", "Checkpoint",
     "checkpoint/coordinator.py + cluster/distributed.py — root span, "
     "trigger → complete"),
    ("checkpoint", "Notify",
     "checkpoint/coordinator.py + cluster/distributed.py — completion "
     "fan-out to tasks"),
    ("checkpoint", "Snapshot",
     "runtime/stream_task.py — per-subtask barrier broadcast + state "
     "snapshot + ack"),
    ("checkpoint", "Store",
     "checkpoint/coordinator.py + cluster/distributed.py — artifact "
     "store of the completed checkpoint"),
    ("device", "Compile",
     "metrics/device.py instrumented_program_cache — XLA compile of a "
     "device segment"),
    ("device", "D2H",
     "metrics/device.py note_d2h — device→host transfer"),
    ("device", "Execute",
     "runtime/faults.py DeviceGuard.run — guarded device dispatch "
     "(retries/degrade included)"),
    ("device", "H2D",
     "metrics/device.py note_h2d — host→device transfer"),
    ("ha", "Takeover",
     "cluster/distributed.py CoordinatorContender._on_grant — standby "
     "promoted over a running job: grant → hot resume or fenced restore"),
    ("net", "Fence",
     "cluster/transport.py — zombie producer fenced by epoch check"),
    ("net", "Reconnect",
     "cluster/transport.py — severed data channel redial + replay"),
    ("rescale", "Migrate",
     "runtime/operators/mesh_window.py rescale_live — page ownership "
     "diff + digest-verified key-group transfer"),
    ("rescale", "Rebuild",
     "runtime/operators/mesh_window.py rescale_live — state install on "
     "the new mesh"),
    ("rescale", "Rescale",
     "cluster/local.py live_rescale + mesh_window rescale_live — root "
     "span, barrier-aligned worker-set change without restart"),
    ("restart", "JobRestart",
     "cluster/scheduler.py + cluster/distributed.py _do_restart — "
     "full-job restart from last verified checkpoint"),
    ("restart", "RegionRestart",
     "cluster/local.py restart_region — failover-region restart"),
    ("restore", "Fallback",
     "checkpoint/coordinator.py — corrupt candidate skipped, older "
     "checkpoint selected"),
    ("restore", "Restore",
     "checkpoint/coordinator.py latest_verified_checkpoint — verified "
     "restore-candidate selection"),
    ("sched", "Admit",
     "runtime/stream_task.py _admission_gate — quota-throttled "
     "micro-batch admission (span covers the gate wait)"),
    ("sched", "Shed",
     "runtime/stream_task.py _admission_gate — overloaded micro-batch "
     "quarantined to the dead-letter output"),
    ("task", "ProcessBatch",
     "runtime/stream_task.py OneInput/TwoInputStreamTask.invoke — one "
     "dequeued batch through the operator chain (stage span: rows, "
     "queued_ms, queue_depth)"),
    ("task", "SourceBatch",
     "runtime/stream_task.py — one source read→emit mailbox cycle "
     "(stage span: records, read_ms, emit_ms, blocked_ms: the part of "
     "emit_ms its writers stood in a full channel)"),
    ("task", "WaitInput",
     "runtime/stream_task.py OneInput/TwoInputStreamTask.invoke — first "
     "empty input poll → the next event, one span per wait (stage span: "
     "polls, busy_ms: the processing-time turns worked through inside "
     "it); its durations less busy_ms are the task's idle time"),
    ("tier", "Evict",
     "state/tpu_backend.py _evict_cold_groups — cold key groups paged "
     "to the host-warm tier + device table rebuild"),
    ("tier", "Prefetch",
     "state/tiering/prefetch.py PrefetchPipeline — warm key groups "
     "gathered + staged for promotion at a batch boundary"),
    ("watchdog", "Stall",
     "runtime/watchdog.py _note_trip — deadline expiry at a guarded "
     "site"),
    ("window", "Drain",
     "runtime/operators/slice_control.py AsyncFireQueue._drain_stage, "
     "used by device_window / mesh_window _materialize — device_get of a "
     "fire's outputs + host selection/sort; child of Fire (stage span: "
     "turn — timer, batch or blocking: the kind of mailbox turn that took "
     "the fire off the queue; count_plane — presence32, count32 or "
     "count64: the form of the operator's hidden plane); "
     "runtime/operators/device_session.py _materialize — device_get of "
     "one fire ROUND's counters and rows, copied since the round's "
     "dispatch (stage span: round, turn, fired, left: the ripe sessions "
     "the round left on their lanes)"),
    ("window", "Emit",
     "runtime/operators/slice_control.py AsyncFireQueue._emit_stage — "
     "building the window's rows + output.emit; child of Fire (stage "
     "span: rows); runtime/operators/device_session.py _materialize — "
     "a fire round's session rows built and emitted"),
    ("window", "Fire",
     "runtime/operators/slice_control.py — root of one span tree per "
     "fired window: _fire entry → its rows emitted, closed from a later "
     "mailbox turn when fires are async: the first processing-time turn "
     "after its copy has landed (stage span: window_end_ms, rows, "
     "d2h_bytes, unready_polls); "
     "runtime/operators/device_session.py _maybe_fire — root of one "
     "span tree per session fire (one boundary, at the operator's "
     "cadence): its first round's dispatch → its last round's rows "
     "emitted and its watermark forwarded (stage span: boundary_ms, "
     "rounds, unready_polls; seq: the boundary)"),
    ("window", "FireDispatch",
     "runtime/operators/slice_control.py _fire_window — the host's "
     "dispatch of one fire: guarded fire program(s) + ring-row reset; "
     "child of Fire (stage span); "
     "runtime/operators/device_session.py _dispatch_round — the "
     "dispatch of one round of a session fire and the start of its "
     "device→host copy (stage span: round)"),
    ("window", "HostSort",
     "runtime/operators/device_session.py _ingest — the host's sort of "
     "one batch by (key, ts) (a stable argsort by key where the "
     "timestamps are in order, else a lexsort) and the padding of its "
     "columns, on the task's thread before the upload (stage span: "
     "rows; seq: the batch's ordinal)"),
    ("window", "IngestDispatch",
     "runtime/operators/device_window.py + "
     "runtime/operators/device_session.py — host time to enqueue one "
     "batch's ingest programs (stage span: programs); "
     "runtime/operators/mesh_window.py _flush — one [D, B] block's step "
     "and the look at the pressure probe (seq: the block's ordinal; "
     "reading_wait_ms on a block that waited for a reading)"),
    ("window", "Reclaim",
     "runtime/operators/device_window.py _apply_health — the backend's "
     "reclaim (state/tpu_backend.py reclaim: the table rebuilt at its own "
     "capacity from the keys that still hold data in a ring row, every "
     "plane re-seated, ONE device program), from its dispatch in the "
     "mailbox turn that found the table past load 0.6 until its two "
     "counts have landed (the mailbox does not wait for it); child of "
     "the Drain whose health reading found it (stage span: kept, freed, "
     "capacity; seq: that window's); runtime/operators/mesh_window.py "
     "_reclaim — the sharded reclaim (all shards, one dispatch), until "
     "its [D, 2] counts have landed: kept and freed summed over the "
     "shards, capacity a shard's; child of the Drain whose reading found "
     "the fullest shard past the limit or, found by the pressure probe, "
     "a root whose seq is the operator's watermark"),
    ("window", "RingSort",
     "runtime/operators/device_window.py _fold — out-of-order input: a "
     "host-born batch that holds rows of more than two ring rows is "
     "put in ring-row order (a stable argsort of its ring indices and "
     "a take of every column) on the task's thread, before its Upload "
     "(stage span: rows, ring_rows; seq: the batch's ordinal); an "
     "in-order batch opens none"),
    ("window", "Upload",
     "runtime/operators/device_window.py _fold_packed / "
     "_to_device_batch — pack + the one host→device copy; device/H2D "
     "nests under it (stage span: bytes); "
     "runtime/operators/mesh_window.py _flush — concatenating the "
     "staged batches, cutting one [D, B] block and its host→device "
     "copies (seq: the block's ordinal); "
     "runtime/operators/device_session.py _ingest — the sorted batch's "
     "key, timestamp and aggregate columns to the device (stage span: "
     "rows)"),
    ("window", "Watermark",
     "runtime/operators/slice_control.py "
     "SliceControlPlane.process_watermark — one watermark through the "
     "window operator (stage span: watermark_ms, fires, since_batch_ms); "
     "runtime/operators/device_session.py process_watermark — the "
     "settled segments' flush, a look at the round in flight and, at "
     "the cadence, a fire's first dispatch"),
)
