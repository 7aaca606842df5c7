"""Stream operator base: the unit of computation inside a task.

Analog of flink-streaming-java's operator layer
(api/operators/AbstractStreamOperator.java:93, StreamOperator, Output,
OperatorChain.java:108). Operators are batch-oriented: ``process_batch``
receives a whole RecordBatch; control elements (watermarks, barriers, latency
markers) arrive through dedicated methods in channel order. Chained operators
are fused by direct method calls (the ChainingOutput analog) — and when every
operator in a chain exposes a jax-traceable batch function the whole chain
compiles into ONE XLA program (see runtime/compiled.py).
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Callable, Iterable, Optional

import numpy as np

from ...core.config import Configuration, PipelineOptions, StateOptions
from ...core.elements import LatencyMarker, Watermark
from ...core.keygroups import KeyGroupRange, key_group_range_for_operator
from ...core.records import RecordBatch, Schema
from ...metrics.profiler import DEVICE_LEDGER, set_dispatch_context
from ...state.backend import KeyedStateBackend, OperatorStateBackend, \
    create_backend
from ..timers import InternalTimerService

__all__ = ["OperatorContext", "Output", "CollectingOutput", "StreamOperator",
           "OneInputOperator", "TwoInputOperator", "OperatorChain"]


@dataclass
class OperatorContext:
    """Everything an operator needs from its task (reference
    StreamingRuntimeContext + StreamConfig)."""

    task_name: str
    subtask_index: int
    parallelism: int
    max_parallelism: int
    config: Configuration = field(default_factory=Configuration)
    metrics: Any = None
    processing_time: Callable[[], int] = lambda: int(time.time() * 1000)
    operator_id: str = ""
    kv_registry: Any = None  # queryable-state registry (local job scope)

    @property
    def key_group_range(self) -> KeyGroupRange:
        return key_group_range_for_operator(
            self.max_parallelism, self.parallelism, self.subtask_index)

    def create_keyed_backend(self, name: str = None,
                             **kwargs) -> KeyedStateBackend:
        """``name`` overrides the configured backend — operators whose
        state shapes a partial backend cannot hold (e.g. the host
        WindowOperator's per-window aggregating state on the tpu value
        plane) pin the backend that can."""
        if name is None:
            name = self.config.get(StateOptions.BACKEND)
        backend = create_backend(name, self.key_group_range,
                                 self.max_parallelism, config=self.config,
                                 **kwargs)
        backend.kv_registry = self.kv_registry
        return backend


class Output:
    """Downstream edge of an operator (reference Output<StreamRecord>)."""

    def emit(self, batch: RecordBatch) -> None:
        raise NotImplementedError

    def emit_watermark(self, watermark: Watermark) -> None:
        raise NotImplementedError

    def emit_latency_marker(self, marker: LatencyMarker) -> None:
        pass

    def emit_side(self, tag: str, batch: RecordBatch) -> None:
        raise NotImplementedError(f"no side output wired for tag {tag!r}")


class CollectingOutput(Output):
    """Buffers everything — tail of test harnesses and of compiled segments."""

    def __init__(self):
        self.batches: list[RecordBatch] = []
        self.watermarks: list[Watermark] = []
        self.latency_markers: list[LatencyMarker] = []
        self.side: dict[str, list[RecordBatch]] = {}

    def emit(self, batch: RecordBatch) -> None:
        if batch.n:
            self.batches.append(batch)

    def emit_watermark(self, watermark: Watermark) -> None:
        self.watermarks.append(watermark)

    def emit_latency_marker(self, marker: LatencyMarker) -> None:
        self.latency_markers.append(marker)

    def emit_side(self, tag: str, batch: RecordBatch) -> None:
        self.side.setdefault(tag, []).append(batch)

    def rows(self) -> list:
        return [r for b in self.batches for r in b.iter_rows()]

    def clear(self) -> None:
        self.batches.clear()
        self.watermarks.clear()
        self.side.clear()


class StreamOperator:
    """Lifecycle mirrors AbstractStreamOperator: setup -> initialize_state ->
    open -> (process loop) -> finish -> close."""

    def __init__(self, name: str = ""):
        self.name = name or type(self).__name__
        self.ctx: Optional[OperatorContext] = None
        self.output: Output = None  # type: ignore[assignment]
        self.current_watermark: int = -(1 << 62)
        self._latency_hist = None
        self._metric_group = None    # the operator's scope, once set up
        self.latency_markers_seen = 0
        self._ledger_job = ""
        self._ledger_ident = self.name

    # -- lifecycle ---------------------------------------------------------
    def setup(self, ctx: OperatorContext, output: Output) -> None:
        self.ctx = ctx
        self.output = output
        # device-time ledger attribution identity: the owning job's name
        # plus the chain-stable operator key (see OperatorChain)
        self._ledger_job = str(ctx.config.get(PipelineOptions.NAME))
        self._ledger_ident = getattr(self, "_op_key", self.name)
        metrics = getattr(ctx, "metrics", None)
        if metrics is not None and hasattr(metrics, "operator_group"):
            # per-operator scope (reference AbstractStreamOperator's
            # WatermarkGauge + latency histogram under the operator group)
            g = self._metric_group = metrics.operator_group(
                getattr(self, "_op_key", self.name))
            g.gauge("currentInputWatermark", lambda: self.current_watermark)
            g.gauge("watermarkLag", self._watermark_lag_ms)
            self._latency_hist = g.histogram("latency")

    def _watermark_lag_ms(self):
        """Wall-clock lag behind the operator's event-time watermark; NaN
        until the first real watermark (MIN would read as astronomic)."""
        if self.current_watermark <= -(1 << 61):
            return float("nan")
        return max(0, int(time.time() * 1000) - self.current_watermark)

    def _enter_dispatch(self) -> None:
        """Pin this operator as the (job, operator) owner of device-time
        ledger samples recorded on the current thread — called at every
        batch/watermark entry into the operator. One attribute read when
        the ledger is disabled."""
        if DEVICE_LEDGER.enabled:
            set_dispatch_context(self._ledger_job, self._ledger_ident)

    def initialize_state(self, keyed_snapshots: list, operator_snapshot) -> None:
        pass

    def open(self) -> None:
        pass

    def finish(self) -> None:
        """End of input: flush buffers (reference StreamOperator.finish)."""

    def close(self) -> None:
        pass

    # -- data path ---------------------------------------------------------
    def process_watermark(self, watermark: Watermark) -> None:
        self.current_watermark = watermark.timestamp
        self.output.emit_watermark(watermark)

    def process_latency_marker(self, marker: LatencyMarker) -> None:
        # record source->here latency at EVERY hop, then forward (the
        # reference records into the operator's latency histogram keyed
        # by source; one histogram per operator suffices here)
        self.latency_markers_seen += 1
        if self._latency_hist is not None:
            self._latency_hist.update(
                (time.time() - marker.marked_time) * 1e3)
        self.output.emit_latency_marker(marker)

    def advance_processing_time(self, now_ms: int) -> None:
        """Driven by the task's step loop for processing-time timers."""

    # -- checkpointing -----------------------------------------------------
    def snapshot_state(self, checkpoint_id: int) -> dict:
        """Return {'keyed': <per-kg snapshot>|None, 'operator': dict|None,
        'timers': dict|None} — serializable."""
        return {}

    def notify_checkpoint_complete(self, checkpoint_id: int,
                                   is_savepoint: bool = False) -> None:
        # operators owning a keyed backend (convention: self._backend)
        # forward completions so backends with deferred artifact cleanup
        # (changelog generations) can prune on SUBSUMPTION, not snapshots
        backend = getattr(self, "_backend", None)
        if backend is not None and hasattr(backend,
                                           "notify_checkpoint_complete"):
            backend.notify_checkpoint_complete(checkpoint_id,
                                               is_savepoint=is_savepoint)

    def notify_checkpoint_aborted(self, checkpoint_id: int) -> None:
        backend = getattr(self, "_backend", None)
        if backend is not None and hasattr(backend,
                                           "notify_checkpoint_aborted"):
            backend.notify_checkpoint_aborted(checkpoint_id)


class OneInputOperator(StreamOperator):
    def process_batch(self, batch: RecordBatch) -> None:
        raise NotImplementedError


class TwoInputOperator(StreamOperator):
    """Two-input operator (reference TwoInputStreamOperator): watermark is the
    min across inputs (handled by the task's valve per input, then min here)."""

    def __init__(self, name: str = ""):
        super().__init__(name)
        self._input_watermarks = [-(1 << 62), -(1 << 62)]

    def process_batch1(self, batch: RecordBatch) -> None:
        raise NotImplementedError

    def process_batch2(self, batch: RecordBatch) -> None:
        raise NotImplementedError

    def process_watermark_n(self, input_index: int, watermark: Watermark) -> None:
        self._input_watermarks[input_index] = watermark.timestamp
        combined = min(self._input_watermarks)
        if combined > self.current_watermark:
            self.process_watermark(Watermark(combined))


class _ChainingOutput(Output):
    """Direct-call edge between chained operators (reference ChainingOutput)."""

    def __init__(self, downstream: OneInputOperator,
                 side_router: Optional[dict[str, Output]] = None):
        self._op = downstream
        self._side = side_router or {}

    def emit(self, batch: RecordBatch) -> None:
        if batch.n:
            self._op._enter_dispatch()
            self._op.process_batch(batch)

    def emit_watermark(self, watermark: Watermark) -> None:
        self._op._enter_dispatch()
        self._op.process_watermark(watermark)

    def emit_latency_marker(self, marker: LatencyMarker) -> None:
        self._op.process_latency_marker(marker)

    def emit_side(self, tag: str, batch: RecordBatch) -> None:
        out = self._side.get(tag)
        if out is not None:
            out.emit(batch)


class OperatorChain:
    """A fused sequence of operators executed by one task
    (reference OperatorChain.java:108). Head receives task input; tail writes
    the task's record writer."""

    def __init__(self, operators: list[StreamOperator], ctx: OperatorContext,
                 tail_output: Output,
                 side_outputs: Optional[dict[str, Output]] = None):
        self.operators = operators
        self.ctx = ctx
        for i, op in enumerate(operators):
            # stable per-operator id for state snapshots (unique in the chain)
            op._op_key = f"{i}:{op.name}"
        # wire back-to-front
        next_output = tail_output
        for op in reversed(operators):
            op.setup(ctx, next_output)
            next_output = _ChainingOutput(op, side_outputs)
        self.head: StreamOperator = operators[0]

    @property
    def head_one_input(self) -> OneInputOperator:
        return self.head  # type: ignore[return-value]

    def initialize_state(self, per_operator_snapshots: Optional[dict]) -> None:
        for op in self.operators:
            snaps = (per_operator_snapshots or {}).get(_op_key(op), None)
            op.initialize_state(
                snaps.get("keyed_list", []) if snaps else [],
                snaps.get("operator") if snaps else None)

    def open(self) -> None:
        for op in reversed(self.operators):  # downstream first, like reference
            op.open()

    def process_batch(self, batch: RecordBatch) -> None:
        self.head._enter_dispatch()
        self.head_one_input.process_batch(batch)

    def process_batch_n(self, input_index: int, batch: RecordBatch) -> None:
        """Route a batch to input 0/1 of a two-input head."""
        head: TwoInputOperator = self.head  # type: ignore[assignment]
        head._enter_dispatch()
        if input_index == 0:
            head.process_batch1(batch)
        else:
            head.process_batch2(batch)

    def process_watermark(self, watermark: Watermark) -> None:
        self.head._enter_dispatch()
        self.head.process_watermark(watermark)

    def process_watermark_n(self, input_index: int,
                            watermark: Watermark) -> None:
        self.head._enter_dispatch()
        if isinstance(self.head, TwoInputOperator):
            self.head.process_watermark_n(input_index, watermark)
        else:
            self.head.process_watermark(watermark)

    def process_latency_marker(self, marker: LatencyMarker) -> None:
        """Route a latency probe through every chained operator (each
        records its source->operator latency) out to the tail writers."""
        self.head.process_latency_marker(marker)

    def advance_processing_time(self, now_ms: int) -> None:
        for op in self.operators:
            op.advance_processing_time(now_ms)

    def snapshot_state(self, checkpoint_id: int) -> dict:
        return {_op_key(op): op.snapshot_state(checkpoint_id)
                for op in self.operators}

    def notify_checkpoint_complete(self, checkpoint_id: int,
                                   is_savepoint: bool = False) -> None:
        for op in self.operators:
            op.notify_checkpoint_complete(checkpoint_id,
                                          is_savepoint=is_savepoint)

    def notify_checkpoint_aborted(self, checkpoint_id: int) -> None:
        for op in self.operators:
            op.notify_checkpoint_aborted(checkpoint_id)

    def finish(self) -> None:
        for op in self.operators:
            op.finish()

    def close(self) -> None:
        for op in self.operators:
            op.close()


def _op_key(op: StreamOperator) -> str:
    return getattr(op, "_op_key", op.name)
