"""The benchmark's open-loop source and stamping sink.

``ScheduledSource`` is a ``Source`` / ``SourceReader`` pair handed to
``env.from_source()`` — the API a user's connector uses — so nothing inside
the program is touched. The reader emits batches of exactly
``batch_rows`` rows: in a paced phase when the batch's last row is due,
and back to back when it is behind, so the offered load never slows with
the system; in the unthrottled timed phase as fast as the job takes them.
It reports how late it ran (emit time - due time per batch).

``StampingSink`` stamps ``perf_counter()`` on each batch it is given and
keeps the rows. Both wrap their own work in ``jax.profiler.
TraceAnnotation`` so a traced run can name idle gaps after them.
"""

from __future__ import annotations

import time
from typing import Any, Callable, Optional

import numpy as np

from flink_tpu.connectors.core import Source, SourceReader, SourceSplit
from flink_tpu.core.functions import SinkFunction
from flink_tpu.core.records import RecordBatch, Schema

from .schedule import Schedule

__all__ = ["ScheduledSource", "StampingSink"]


def _annotation(name: str):
    import jax.profiler

    return jax.profiler.TraceAnnotation(name)


class ScheduledSource(Source):
    """One split, one reader (the generator is one process-wide stream)."""

    bounded = True

    def __init__(self, schedule: Schedule,
                 columns: Callable[[np.ndarray], dict[str, np.ndarray]],
                 schema: Schema, ts_column: str,
                 on_timed_start: Optional[Callable[[], None]] = None,
                 newest_result_ms: Optional[Callable[[], tuple]] = None,
                 pane_ms: int = 0, lead_panes: int = 2,
                 quiet_s: float = 5.0):
        self.schedule = schedule
        self.schema = schema
        self._columns = columns
        self._ts_column = ts_column
        self._on_timed_start = on_timed_start
        # set-up flow control: how far (event time) the prefill may run
        # ahead of the newest window end seen at the sink
        self._newest_result_ms = newest_result_ms
        self._lead_ms = int(lead_panes) * int(pane_ms)
        # the operator hands a fire's rows on only when a further batch
        # reaches it, so "the sink has seen nothing for quiet_s" is the
        # one sign that nothing is queued any more; it also un-sticks the
        # flow control (one batch per quiet_s) while the job compiles
        self._quiet_s = float(quiet_s)
        self.reader: Optional["_ScheduledReader"] = None

    def create_splits(self, parallelism: int) -> list[SourceSplit]:
        if parallelism != 1:
            raise ValueError("the benchmark's source is one stream; run it "
                             "with parallelism=1")
        return [SourceSplit("scheduled-0", 0)]

    def create_reader(self, split: SourceSplit) -> SourceReader:
        self.reader = _ScheduledReader(self)
        return self.reader


class _ScheduledReader(SourceReader):
    def __init__(self, source: ScheduledSource):
        self._s = source
        self._sched = source.schedule
        self._next = 0
        self._ready: Optional[RecordBatch] = None  # generated ahead of due
        self._empty = RecordBatch.empty(source.schema)
        self._timed_first = self._sched.phase("timed").first_batch
        self._warm_first = self._sched.phase("warm").first_batch
        self._settle_from: Optional[float] = None
        self.settle_wait_s = 0.0
        self.flow_waits = 0          # prefill reads refused by flow control
        self.started_s: Optional[float] = None  # first read
        self.origin_s: Optional[float] = None   # host clock at event time 0
                                                # of the PACED phases
        self.t0_s: Optional[float] = None       # first timed batch emitted
        self.done_s: Optional[float] = None     # last batch emitted
        self.emit_s: list[float] = []           # per batch, host clock
        self.lag_ms: list[Optional[float]] = []  # per batch; None unpaced
        self.generate_s = 0.0

    def _generate(self, batch: int) -> RecordBatch:
        t = time.perf_counter()
        with _annotation("source_generate"):
            cols = dict(self._s._columns(self._sched.batch_index(batch)))
            ts = self._sched.batch_ts(batch)
            cols[self._s._ts_column] = ts
            out = RecordBatch(self._s.schema, cols, ts)
        self.generate_s += time.perf_counter() - t
        return out

    def read_batch(self, max_records: int) -> Optional[RecordBatch]:
        now = time.perf_counter()
        if self.started_s is None:
            self.started_s = now
        b = self._next
        if b >= self._sched.n_batches:
            if self.done_s is None:
                self.done_s = now
            return None
        phase = self._sched.phase_of(b)
        if phase.name == "prefill" and not self._flow_allows(b, now):
            self.flow_waits += 1
            return self._empty
        if b == self._warm_first and self.origin_s is None:
            if not self._settled(now):
                return self._empty
            # the paced clock starts here: event time is wall time from
            # the warm phase's first event on
            self.origin_s = now - phase.start_ms / 1000.0
        due = None
        if phase.paced:
            due = self.origin_s + self._sched.due_s(b)
            if now < due:
                # not due: make the batch ahead of time so that it leaves
                # the moment it is due, then tell the task "nothing yet"
                # (it sleeps 1 ms and asks again)
                if self._ready is None:
                    self._ready = self._generate(b)
                    return self._empty
                if due - now > 0.0015:
                    return self._empty
                while time.perf_counter() < due:
                    pass
        batch = self._ready if self._ready is not None else self._generate(b)
        self._ready = None
        if b == self._timed_first and self._s._on_timed_start is not None:
            self._s._on_timed_start()
        emit = time.perf_counter()
        if b == self._timed_first:
            self.t0_s = emit
        self.emit_s.append(emit)
        self.lag_ms.append((emit - due) * 1e3 if phase.paced else None)
        self._next += 1
        return batch

    # -- set-up flow control ----------------------------------------------
    def _flow_allows(self, batch: int, now: float) -> bool:
        """The prefill runs back to back but at most ``lead`` of event time
        ahead of the newest window end the sink has seen, so that a stall
        of the job (a cold compile takes a minute) queues a few batches
        and not sixty. Where the sink stays quiet for ``quiet_s`` one
        batch goes out anyway: it is what makes the operator hand on the
        fires it holds."""
        src = self._s
        if src._newest_result_ms is None or not src._lead_ms:
            return True
        first_ts = self._sched.row_ts(batch, 0)
        newest, seen_at, _gap = src._newest_result_ms()
        if newest is None:
            newest = self._sched.phase("prefill").start_ms
        if first_ts - newest <= src._lead_ms:
            return True
        last = max(seen_at or 0.0, self.emit_s[-1] if self.emit_s else 0.0)
        return now - last >= src._quiet_s

    def _settled(self, now: float) -> bool:
        """After the last prefill batch: wait until the sink has been quiet
        for ``quiet_s``. The operator hands a fire's rows on only when a
        further batch reaches it, so the sink sees rows for as long as
        queued batches are being consumed, and a silence longer than the
        time between two fires means the queue is empty. (Waiting for a
        particular late window instead leaves up to a pane of batches
        behind it: measured, PERF.md section 6.)"""
        src = self._s
        if src._newest_result_ms is None:
            return True
        if self._settle_from is None:
            self._settle_from = now
        _newest, seen_at, gap = src._newest_result_ms()
        # "longer than the time between two fires": the last gap the sink
        # saw, with half as much again, where that is more than quiet_s
        quiet = max(src._quiet_s, 1.5 * gap)
        if now - max(seen_at or 0.0, self.emit_s[-1]) < quiet:
            return False
        self.settle_wait_s = now - self._settle_from
        return True

    def snapshot(self) -> Any:
        return self._next

    def restore(self, state: Any) -> None:
        self._next = int(state)
        self._ready = None


class StampingSink(SinkFunction):
    def __init__(self):
        self.batches: list[dict[str, np.ndarray]] = []
        self.stamps_s: list[float] = []
        self.invoke_s = 0.0
        self.newest_end_ms: Optional[int] = None

    def newest(self) -> tuple[Optional[int], Optional[float], float]:
        """(newest window end seen, host clock of the last batch seen,
        seconds between the last two batches seen)."""
        stamps = self.stamps_s
        return (self.newest_end_ms, stamps[-1] if stamps else None,
                stamps[-1] - stamps[-2] if len(stamps) > 1 else 0.0)

    def invoke_batch(self, batch) -> bool:
        t = time.perf_counter()
        with _annotation("sink_invoke"):
            self.batches.append({f.name: np.asarray(batch.column(f.name))
                                 for f in batch.schema.fields})
            self.stamps_s.append(t)
            end = int(self.batches[-1]["window_end"].max())
            if self.newest_end_ms is None or end > self.newest_end_ms:
                self.newest_end_ms = end
        self.invoke_s += time.perf_counter() - t
        return True

    def rows(self) -> dict[str, np.ndarray]:
        if not self.batches:
            return {}
        return {name: np.concatenate([b[name] for b in self.batches])
                for name in self.batches[0]}

    def window_stamps(self) -> dict[int, float]:
        """window end (event-time ms) -> stamp of the LAST batch that
        carried rows of that window (a window is complete at the sink only
        when its last row is)."""
        out: dict[int, float] = {}
        for b, t in zip(self.batches, self.stamps_s):
            for end in np.unique(b["window_end"]).tolist():
                out[end] = max(t, out.get(end, t))
        return out
