"""Stateless and keyed-process operators.

Analogs of StreamMap/StreamFilter/StreamFlatMap
(flink-streaming-java api/operators/Stream{Map,Filter,FlatMap}.java) and
KeyedProcessOperator (api/operators/KeyedProcessOperator). Each prefers the
function's vectorized batch path and falls back to a row loop — chained
vectorized operators later fuse into one XLA program (runtime/compiled.py).
"""

from __future__ import annotations

from typing import Any, Callable, Optional

import numpy as np

from ...core.elements import Watermark
from ...core.functions import (
    Collector, FilterFunction, FlatMapFunction, MapFunction, ProcessFunction,
    RuntimeContext,
)
from ...core.records import MAX_TIMESTAMP, MIN_TIMESTAMP, RecordBatch, \
    Schema
from ..timers import InternalTimerService, Timer
from .base import OneInputOperator, OperatorContext, Output

__all__ = ["MapOperator", "FilterOperator", "FlatMapOperator",
           "KeyedProcessOperator", "BatchFnOperator", "KeyExtractor",
           "TimestampsAndWatermarksOperator"]

# KeyExtractor: RecordBatch -> np.ndarray of keys (one per row)
KeyExtractor = Callable[[RecordBatch], np.ndarray]


def _runtime_context(op: OneInputOperator, state_backend=None) -> RuntimeContext:
    ctx = op.ctx
    return RuntimeContext(ctx.task_name, ctx.subtask_index, ctx.parallelism,
                          ctx.max_parallelism, metrics=ctx.metrics,
                          state_backend=state_backend)


class MapOperator(OneInputOperator):
    def __init__(self, fn: MapFunction, out_schema: Optional[Schema] = None,
                 name: str = "Map"):
        super().__init__(name)
        self._fn = fn
        self._out_schema = out_schema

    def open(self) -> None:
        self._fn.open(_runtime_context(self))

    def process_batch(self, batch: RecordBatch) -> None:
        vec = self._fn.map_batch(batch)
        if vec is not None:
            self.output.emit(vec)
            return
        rows = [self._fn.map(r) for r in batch.iter_rows()]
        if not rows:
            return
        schema = self._out_schema
        if schema is None and isinstance(rows[0], tuple) \
                and len(rows[0]) == len(batch.schema) > 1:
            # same-arity tuple output: keep the input's column names so
            # downstream column references (key_by("col")) keep working —
            # from_rows_infer still promotes dtypes per column as needed
            schema = batch.schema
        out, self._out_schema = RecordBatch.from_rows_infer(
            schema, rows, batch.timestamps)
        self.output.emit(out)

    def close(self) -> None:
        self._fn.close()


class FilterOperator(OneInputOperator):
    def __init__(self, fn: FilterFunction, name: str = "Filter"):
        super().__init__(name)
        self._fn = fn

    def open(self) -> None:
        self._fn.open(_runtime_context(self))

    def process_batch(self, batch: RecordBatch) -> None:
        mask = self._fn.filter_batch(batch)
        if mask is None:
            mask = np.fromiter((bool(self._fn.filter(r))
                                for r in batch.iter_rows()),
                               dtype=bool, count=batch.n)
        self.output.emit(batch.filter(mask))

    def close(self) -> None:
        self._fn.close()


class FlatMapOperator(OneInputOperator):
    def __init__(self, fn: FlatMapFunction, out_schema: Optional[Schema] = None,
                 name: str = "FlatMap"):
        super().__init__(name)
        self._fn = fn
        self._out_schema = out_schema

    def open(self) -> None:
        self._fn.open(_runtime_context(self))

    def process_batch(self, batch: RecordBatch) -> None:
        rows: list = []
        ts: list[int] = []
        for i, r in enumerate(batch.iter_rows()):
            t = int(batch.timestamps[i])
            for out in self._fn.flat_map(r):
                rows.append(out)
                ts.append(t)
        if not rows:
            return
        out, self._out_schema = RecordBatch.from_rows_infer(
            self._out_schema, rows, ts)
        self.output.emit(out)

    def close(self) -> None:
        self._fn.close()


class BatchFnOperator(OneInputOperator):
    """Operator over a raw batch->batch callable — the escape hatch the SQL
    layer and compiled segments use."""

    def __init__(self, fn: Callable[[RecordBatch], Optional[RecordBatch]],
                 name: str = "BatchFn", traceable: bool = False):
        super().__init__(name)
        self._fn = fn
        self.traceable = traceable  # True => jax-traceable columnwise fn

    def process_batch(self, batch: RecordBatch) -> None:
        out = self._fn(batch)
        if out is not None and out.n:
            self.output.emit(out)


class TimestampsAndWatermarksOperator(OneInputOperator):
    """Mid-stream timestamp and watermark assignment (reference
    TimestampsAndWatermarksOperator): every batch gets the strategy's
    timestamps and is followed by the generator's watermark where that
    moved. Watermarks from upstream are replaced by the generated ones,
    all but the end-of-input one, which is forwarded as the reference
    forwards ``Long.MAX_VALUE``: without it a bounded job's last windows,
    those the generator's holdback keeps open, would never fire.

    ``numRecordsOutOfOrder`` (operator scope of the job's registry)
    counts the rows whose timestamp lies under the largest timestamp of
    the batches BEFORE theirs: 0 for a stream in order. A device batch
    is not counted (its timestamps are not on the host)."""

    def __init__(self, strategy, name: str = "TimestampsWatermarks"):
        super().__init__(name)
        self._strategy = strategy
        self._gen = strategy.create_generator()
        self._max_ts = MIN_TIMESTAMP
        self.records_out_of_order = 0

    def setup(self, ctx: OperatorContext, output: Output) -> None:
        super().setup(ctx, output)
        if self._metric_group is not None:
            self._metric_group.gauge("numRecordsOutOfOrder",
                                     lambda: self.records_out_of_order)

    def process_batch(self, batch: RecordBatch) -> None:
        batch = self._strategy.assign_timestamps(batch)
        if batch.n and not getattr(batch, "is_device", False):
            ts = batch.timestamps
            self.records_out_of_order += int(
                np.count_nonzero(ts < self._max_ts))
            self._max_ts = max(self._max_ts, int(ts.max()))
        self._gen.on_batch(batch)
        self.output.emit(batch)
        wm = self._gen.current_watermark()
        if wm > self.current_watermark:
            self.current_watermark = wm
            self.output.emit_watermark(Watermark(wm))

    def process_watermark(self, watermark: Watermark) -> None:
        if watermark.timestamp >= MAX_TIMESTAMP:
            self.current_watermark = watermark.timestamp
            self.output.emit_watermark(watermark)


class KeyedProcessOperator(OneInputOperator):
    """Keyed per-record processing with timers + keyed state
    (reference KeyedProcessOperator). Row-oriented by nature — the user
    function sees one element at a time."""

    def __init__(self, fn: ProcessFunction, key_extractor: KeyExtractor,
                 out_schema: Optional[Schema] = None, name: str = "KeyedProcess"):
        super().__init__(name)
        # per-subtask copy: a shared instance would cross-wire state handles
        # cached in open() across subtasks (reference: functions are
        # serialized per task, RichFunction pattern)
        from ...core.functions import copy_per_subtask
        self._fn = copy_per_subtask(fn)
        self._key_extractor = key_extractor
        self._out_schema = out_schema
        self._backend = None
        self._timers: Optional[InternalTimerService] = None
        self._pending_rows: list = []
        self._pending_ts: list[int] = []

    # -- lifecycle ---------------------------------------------------------
    def setup(self, ctx: OperatorContext, output: Output) -> None:
        super().setup(ctx, output)
        self._backend = ctx.create_keyed_backend()
        self._timers = InternalTimerService(
            ctx.key_group_range, ctx.max_parallelism,
            on_event_time=self._fire_timer_event,
            on_processing_time=self._fire_timer_proc)

    def initialize_state(self, keyed_snapshots: list, operator_snapshot) -> None:
        if keyed_snapshots:
            self._backend.restore([s["backend"] for s in keyed_snapshots])
            self._timers.restore([s["timers"] for s in keyed_snapshots])

    def open(self) -> None:
        self._fn.open(_runtime_context(self, self._backend))

    # -- helpers -----------------------------------------------------------
    def _collector(self) -> Collector:
        def sink(value, timestamp):
            self._pending_rows.append(value)
            self._pending_ts.append(
                MIN_TIMESTAMP if timestamp is None else int(timestamp))
        return Collector(sink)

    def _side_collector(self, tag: str, value: Any, timestamp) -> None:
        schema = Schema.infer(value)
        self.output.emit_side(tag, RecordBatch.from_rows(
            schema, [value], [MIN_TIMESTAMP if timestamp is None else timestamp]))

    def _flush_pending(self) -> None:
        if not self._pending_rows:
            return
        out, self._out_schema = RecordBatch.from_rows_infer(
            self._out_schema, self._pending_rows, self._pending_ts)
        self.output.emit(out)
        self._pending_rows, self._pending_ts = [], []

    # -- data path ---------------------------------------------------------
    def process_batch(self, batch: RecordBatch) -> None:
        keys = self._key_extractor(batch)
        out = self._collector()
        for i, row in enumerate(batch.iter_rows()):
            key = keys[i]
            key = key.item() if isinstance(key, np.generic) else key
            self._backend.set_current_key(key)
            ts = int(batch.timestamps[i])
            ctx = ProcessFunction.Context(
                None if ts == MIN_TIMESTAMP else ts, self._timer_api(key),
                current_key=key, side_collector=self._side_collector)
            self._fn.process_element(batch.row(i), ctx, out)
        self._flush_pending()

    def _timer_api(self, key):
        op = self

        class _TimerApi:
            current_watermark = property(
                lambda s: op._timers.current_watermark)

            def register_event_time_timer(self, ts, namespace=None):
                op._timers.register_event_time_timer(key, ts, namespace)

            def register_processing_time_timer(self, ts, namespace=None):
                op._timers.register_processing_time_timer(key, ts, namespace)

            def delete_event_time_timer(self, ts, namespace=None):
                op._timers.delete_event_time_timer(key, ts, namespace)

            def delete_processing_time_timer(self, ts, namespace=None):
                op._timers.delete_processing_time_timer(key, ts, namespace)

        return _TimerApi()

    def _fire_timer_event(self, timer: Timer) -> None:
        self._fire_timer(timer, "event")

    def _fire_timer_proc(self, timer: Timer) -> None:
        self._fire_timer(timer, "processing")

    def _fire_timer(self, timer: Timer, domain: str) -> None:
        self._backend.set_current_key(timer.key)
        ctx = ProcessFunction.OnTimerContext(
            timer.timestamp, self._timer_api(timer.key), domain, timer.key,
            side_collector=self._side_collector)
        self._fn.on_timer(timer.timestamp, ctx, self._collector())

    def process_watermark(self, watermark: Watermark) -> None:
        self._timers.advance_watermark(watermark.timestamp)
        self._flush_pending()
        super().process_watermark(watermark)

    def advance_processing_time(self, now_ms: int) -> None:
        self._timers.advance_processing_time(now_ms)
        self._flush_pending()

    # -- checkpointing -----------------------------------------------------
    def snapshot_state(self, checkpoint_id: int) -> dict:
        return {"keyed": {"backend": self._backend.snapshot(checkpoint_id),
                          "timers": self._timers.snapshot()}}

    def close(self) -> None:
        self._fn.close()
