"""Metrics: counters/gauges/meters/histograms in scoped groups.

Analog of flink-metrics-core (MetricGroup.java:36, Counter/Gauge/Histogram/
Meter) and the runtime registry (MetricRegistryImpl.java:74) with scoped
groups per job/task/operator. Reporters (metrics/reporters.py) poll the
registry on an interval, like the reference's reporter setup.
"""

from __future__ import annotations

import threading
import time
from collections import deque
from typing import Any, Callable, Optional

__all__ = ["Counter", "Gauge", "Meter", "Histogram", "MetricGroup",
           "MetricRegistry", "TaskMetrics"]


class Counter:
    """Thread-safe counter: reporters poll from their own thread while the
    mailbox loop mutates, and ``_value += n`` is a read-modify-write the
    GIL does not make atomic (reference SimpleCounter is single-writer;
    here the lock keeps multi-writer updates lossless too)."""

    __slots__ = ("_value", "_lock")

    def __init__(self):
        self._value = 0
        self._lock = threading.Lock()

    def inc(self, n: int = 1) -> None:
        with self._lock:
            self._value += n

    def dec(self, n: int = 1) -> None:
        with self._lock:
            self._value -= n

    @property
    def count(self) -> int:
        return self._value


class Gauge:
    def __init__(self, fn: Callable[[], Any]):
        self._fn = fn

    @property
    def value(self) -> Any:
        return self._fn()


class Meter:
    """Rate over a sliding minute (reference MeterView). Locked: the
    reporter thread iterates the event window while the task thread
    appends/evicts — unsynchronized, that's a lost update on ``_count``
    and a RuntimeError-free but torn read of the deque."""

    def __init__(self):
        self._events: deque[tuple[float, int]] = deque()
        self._count = 0
        self._lock = threading.Lock()

    def mark(self, n: int = 1) -> None:
        now = time.time()
        with self._lock:
            self._count += n
            self._events.append((now, n))
            cutoff = now - 60.0
            while self._events and self._events[0][0] < cutoff:
                self._events.popleft()

    @property
    def rate(self) -> float:
        now = time.time()
        with self._lock:
            recent = sum(n for t, n in self._events if t >= now - 60.0)
        return recent / 60.0

    @property
    def count(self) -> int:
        return self._count


class Histogram:
    """Reservoir histogram with quantiles. Locked for the same reason as
    Meter: ``sorted()`` over the deque while the owning thread appends
    past ``maxlen`` raises 'deque mutated during iteration'."""

    def __init__(self, window: int = 1024):
        self._values: deque[float] = deque(maxlen=window)
        self._lock = threading.Lock()

    def update(self, value: float) -> None:
        with self._lock:
            self._values.append(float(value))

    def quantile(self, q: float) -> float:
        with self._lock:
            vals = sorted(self._values)
        if not vals:
            return 0.0
        idx = min(int(q * len(vals)), len(vals) - 1)
        return vals[idx]

    # Default le-bounds for the cumulative exposition buckets: latency
    # histograms here are milliseconds, so a 1ms..10s log-ish ladder.
    BUCKET_BOUNDS = (1.0, 2.5, 5.0, 10.0, 25.0, 50.0, 100.0, 250.0,
                     500.0, 1000.0, 2500.0, 5000.0, 10000.0)

    def bucket_counts(self, bounds: Optional[tuple] = None) \
            -> list[tuple[str, int]]:
        """Cumulative ``le``-labeled bucket counts over the reservoir
        window, ending with ``("+Inf", count)`` — what the Prometheus
        histogram exposition needs so external scrapers can aggregate
        across processes (summary quantiles cannot be aggregated)."""
        use = self.BUCKET_BOUNDS if bounds is None else tuple(bounds)
        with self._lock:
            vals = list(self._values)
        out: list[tuple[str, int]] = []
        for b in use:
            out.append((repr(float(b)), sum(1 for v in vals if v <= b)))
        out.append(("+Inf", len(vals)))
        return out

    @property
    def count(self) -> int:
        return len(self._values)

    @property
    def mean(self) -> float:
        with self._lock:
            vals = list(self._values)
        return sum(vals) / len(vals) if vals else 0.0

    @property
    def sum(self) -> float:
        with self._lock:
            return float(sum(self._values))


class MetricGroup:
    """Hierarchical scope: registry.group('job').group('task')..."""

    def __init__(self, registry: "MetricRegistry", scope: tuple[str, ...]):
        self._registry = registry
        self.scope = scope

    def group(self, name: str) -> "MetricGroup":
        return MetricGroup(self._registry, self.scope + (name,))

    def _register(self, name: str, metric) -> Any:
        self._registry.register(self.scope + (name,), metric)
        return metric

    def counter(self, name: str) -> Counter:
        return self._register(name, Counter())

    def gauge(self, name: str, fn: Callable[[], Any]) -> Gauge:
        return self._register(name, Gauge(fn))

    def meter(self, name: str) -> Meter:
        return self._register(name, Meter())

    def histogram(self, name: str, window: int = 1024) -> Histogram:
        return self._register(name, Histogram(window))


class MetricRegistry:
    def __init__(self):
        self._metrics: dict[tuple[str, ...], Any] = {}
        self._lock = threading.Lock()

    def register(self, scope: tuple[str, ...], metric) -> None:
        with self._lock:
            self._metrics[scope] = metric

    def root(self) -> MetricGroup:
        return MetricGroup(self, ())

    def all_metrics(self) -> dict[tuple[str, ...], Any]:
        with self._lock:
            return dict(self._metrics)

    def snapshot(self) -> dict[str, Any]:
        """Flat name -> numeric value view for reporters."""
        out: dict[str, Any] = {}
        for scope, m in self.all_metrics().items():
            name = ".".join(scope)
            if isinstance(m, Counter):
                out[name] = m.count
            elif isinstance(m, Gauge):
                try:
                    out[name] = m.value
                except Exception:  # noqa: BLE001 - gauge fn may race shutdown
                    out[name] = None
            elif isinstance(m, Meter):
                out[name + ".rate"] = m.rate
                out[name + ".count"] = m.count
            elif isinstance(m, Histogram):
                out[name + ".p50"] = m.quantile(0.50)
                out[name + ".p99"] = m.quantile(0.99)
                out[name + ".mean"] = m.mean
        return out


class TaskMetrics:
    """Standard per-task IO metrics (reference numRecordsIn/Out,
    busy/backpressure gauges)."""

    def __init__(self, registry: MetricRegistry, job: str, vertex: str,
                 subtask: int):
        g = registry.root().group(job).group(vertex).group(str(subtask))
        self.records_in = g.counter("numRecordsIn")
        self.records_out = g.counter("numRecordsOut")
        self.watermark_lag = g.histogram("watermarkLag")
        self.batch_size = g.histogram("batchSize")
        self.group = g
        self.io_timers = None

    def bind_io_timers(self, timers) -> None:
        """Expose a task's busy/idle/backpressured/cpu accounting as gauges
        (reference TaskIOMetricGroup busyTimeMsPerSecond family). The
        timers object outlives the task thread, so reporters keep a
        stable terminal reading after the job finishes."""
        self.io_timers = timers
        g = self.group
        g.gauge("busyTimeMsPerSecond", lambda: timers.busy_ms_per_s)
        g.gauge("idleTimeMsPerSecond", lambda: timers.idle_ms_per_s)
        g.gauge("backPressuredTimeMsPerSecond",
                lambda: timers.backpressured_ms_per_s)
        g.gauge("busyTimeRatio", lambda: timers.busy_ratio)
        # what the mailbox thread computed; busy - cpu is the time it
        # stood still inside a turn (device, GIL, machine)
        g.gauge("cpuTimeMsPerSecond", lambda: timers.cpu_ms_per_s)
        g.gauge("cpuTimeRatio", lambda: timers.cpu_ratio)

    def bind_input_gates(self, gates) -> None:
        """Expose, per input gate, how long the element polled last sat
        in its channel (``inputQueueResidenceMs``; ``input1...`` /
        ``input2...`` on a two-input task). The channel stamps each
        element once at ``put``; a growing reading is a consumer that
        falls behind its producer."""
        for i, gate in enumerate(gates):
            name = ("inputQueueResidenceMs" if len(gates) == 1
                    else f"input{i + 1}QueueResidenceMs")
            self.group.gauge(name,
                             lambda g=gate: g.last_residence_ns / 1e6)

    def bind_progress(self, progress) -> None:
        """Expose the task's progress-epoch age as a gauge
        (``lastProgressAgeMs``) — the per-task stall-supervision surface
        the detector, REST snapshot, and dashboards all read."""
        g = self.group
        g.gauge("lastProgressAgeMs", lambda: progress.age_ms)
        g.gauge("progressEpoch", lambda: progress.epoch)

    def operator_group(self, op_key: str) -> MetricGroup:
        """Per-operator scope under this task (WatermarkGauge / operator
        latency live here)."""
        return self.group.group(op_key)
