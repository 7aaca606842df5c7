"""DataStream API: the fluent stream-building surface.

Analog of flink-streaming-java's DataStream family
(api/datastream/DataStream.java — map:591, keyBy:291, transform:1178;
KeyedStream, WindowedStream, ConnectedStreams, side outputs). Builds a lazy
Transformation DAG; ``StreamExecutionEnvironment.execute`` compiles and runs
it.

Key selectors may be a column name (vectorized hashing — preferred) or a
row callable.
"""

from __future__ import annotations

from typing import Any, Callable, Iterable, Optional, Sequence, Union

import numpy as np

from ..core.functions import (
    AggregateFunction, BuiltinAggregate, ProcessFunction, SinkFunction,
    as_filter, as_flat_map, as_map, as_reduce,
)
from ..core.records import RecordBatch, Schema
from ..graph.transformations import (
    OneInputTransformation, PartitionTransformation, SideOutputTransformation,
    SinkTransformation, SourceTransformation, Transformation,
    TwoInputTransformation, UnionTransformation,
)
from ..window.assigners import (
    EventTimeSessionWindows, GlobalWindows, SlidingEventTimeWindows,
    TumblingEventTimeWindows, WindowAssigner,
)
from ..window.triggers import CountTrigger, Evictor, PurgingTrigger, Trigger

__all__ = ["DataStream", "KeyedStream", "WindowedStream", "ConnectedStreams",
           "BroadcastStream", "BroadcastConnectedStream",
           "make_key_extractor"]

KeySpec = Union[str, Callable[[Any], Any]]


def make_key_extractor(key: KeySpec):
    """RecordBatch -> np.ndarray of per-row keys."""
    if isinstance(key, str):
        def extract_col(batch: RecordBatch) -> np.ndarray:
            return batch.column(key)
        extract_col.column = key  # vectorizable marker
        return extract_col

    fn = key
    if getattr(fn, "vectorized", False):
        # already a batch-level extractor (RecordBatch -> ndarray); routing
        # then hashes the array it returns, so a caller that needs exchange
        # routing to agree with a backend's own key hashing (the device
        # GROUP BY combined-word keys) can guarantee it by returning the
        # exact key array the backend stores
        return fn

    def extract_fn(batch: RecordBatch) -> np.ndarray:
        return np.array([fn(r) for r in batch.iter_rows()], dtype=object)
    return extract_fn


class DataStream:
    def __init__(self, env, transformation: Transformation):
        self.env = env
        self.transformation = transformation

    # -- basic transforms --------------------------------------------------
    def _one_input(self, name: str, factory, parallelism=None,
                   key_extractor=None, schema=None, traceable=False,
                   chaining_allowed=True) -> "DataStream":
        t = OneInputTransformation(
            name=name, operator_factory=factory,
            parallelism=parallelism,
            schema=schema, inputs=[self.transformation],
            key_extractor=key_extractor, traceable=traceable,
            chaining_allowed=chaining_allowed)
        self.env._transformations.append(t)
        return DataStream(self.env, t)

    def map(self, fn, name: str = "Map", out_schema: Optional[Schema] = None,
            parallelism: Optional[int] = None) -> "DataStream":
        """Per-row transform. When the function returns tuples with the SAME
        arity as the input, output columns inherit the input's column names
        (so key_by("col") keeps working across enrichment-style maps); a map
        that reorders/replaces fields should pass ``out_schema`` to name the
        outputs correctly."""
        mf = as_map(fn)
        from ..runtime.operators.simple import MapOperator
        return self._one_input(
            name, lambda: MapOperator(mf, out_schema, name), parallelism)

    def flat_map(self, fn, name: str = "FlatMap",
                 out_schema: Optional[Schema] = None,
                 parallelism: Optional[int] = None) -> "DataStream":
        ff = as_flat_map(fn)
        from ..runtime.operators.simple import FlatMapOperator
        return self._one_input(
            name, lambda: FlatMapOperator(ff, out_schema, name), parallelism)

    def filter(self, fn, name: str = "Filter",
               parallelism: Optional[int] = None) -> "DataStream":
        pf = as_filter(fn)
        from ..runtime.operators.simple import FilterOperator
        return self._one_input(name, lambda: FilterOperator(pf, name),
                               parallelism)

    def transform(self, name: str, operator_factory,
                  parallelism: Optional[int] = None,
                  traceable: bool = False) -> "DataStream":
        """Escape hatch: attach a custom operator (reference transform:1178)."""
        return self._one_input(name, operator_factory, parallelism,
                               traceable=traceable)

    def process(self, fn: ProcessFunction, name: str = "Process",
                parallelism: Optional[int] = None) -> "DataStream":
        """Non-keyed process function (no keyed state access)."""
        from ..runtime.operators.simple import KeyedProcessOperator

        def extract(batch: RecordBatch) -> np.ndarray:
            return np.zeros(batch.n, dtype=np.int64)  # single pseudo-key

        return self._one_input(name, lambda: KeyedProcessOperator(fn, extract,
                                                                  name=name),
                               parallelism)

    def async_io(self, fn, capacity: int = 100,
                 timeout_ms: Optional[int] = None, mode: str = "ordered",
                 retry=None, on_timeout: str = "fail",
                 out_schema: Optional[Schema] = None,
                 parallelism: Optional[int] = None,
                 name: str = "AsyncIO") -> "DataStream":
        """Asynchronous external lookups (reference AsyncDataStream
        .orderedWait/unorderedWait -> AsyncWaitOperator). ``fn`` is an
        AsyncFunction (runtime/operators/async_io.py); each subtask gets
        its own copy, so open resources (thread pools, clients) in
        ``open()``, not ``__init__`` — the reference RichFunction
        pattern."""
        from ..core.functions import copy_per_subtask as make_fn_base
        from ..runtime.operators.async_io import AsyncWaitOperator

        def make_fn():
            return make_fn_base(fn)

        return self._one_input(
            name, lambda: AsyncWaitOperator(
                make_fn(), capacity=capacity, timeout_ms=timeout_ms,
                mode=mode, retry=retry, on_timeout=on_timeout,
                out_schema=out_schema, name=name),
            parallelism=parallelism)

    # -- keying / partitioning --------------------------------------------
    def key_by(self, key: KeySpec) -> "KeyedStream":
        from ..runtime.writer import KeyGroupPartitioner
        extractor = make_key_extractor(key)
        maxp = self.env.max_parallelism
        t = PartitionTransformation(
            name="keyed-exchange",
            partitioner_factory=lambda: KeyGroupPartitioner(extractor, maxp),
            partitioner_name="hash",
            inputs=[self.transformation])
        self.env._transformations.append(t)
        return KeyedStream(self.env, t, extractor, key)

    def _repartition(self, name: str, factory) -> "DataStream":
        t = PartitionTransformation(
            name=name, partitioner_factory=factory, partitioner_name=name,
            inputs=[self.transformation])
        self.env._transformations.append(t)
        return DataStream(self.env, t)

    def rebalance(self) -> "DataStream":
        from ..runtime.writer import RebalancePartitioner
        return self._repartition("rebalance", RebalancePartitioner)

    def rescale(self) -> "DataStream":
        from ..runtime.writer import RescalePartitioner
        return self._repartition("rescale", RescalePartitioner)

    def broadcast(self, *descriptors) -> "DataStream":
        """Replicate every record to every downstream subtask. With
        MapStateDescriptors the result is a BroadcastStream for the
        broadcast state pattern: ``keyed.connect(rules.broadcast(desc))
        .process(KeyedBroadcastProcessFunction)`` (reference
        DataStream.broadcast(MapStateDescriptor...) ->
        BroadcastConnectedStream.java:55)."""
        from ..runtime.writer import BroadcastPartitioner
        replicated = self._repartition("broadcast", BroadcastPartitioner)
        if descriptors:
            return BroadcastStream(self.env, replicated.transformation,
                                   descriptors)
        return replicated

    def shuffle(self) -> "DataStream":
        from ..runtime.writer import ShufflePartitioner
        return self._repartition("shuffle", ShufflePartitioner)

    def global_(self) -> "DataStream":
        from ..runtime.writer import GlobalPartitioner
        return self._repartition("global", GlobalPartitioner)

    def forward(self) -> "DataStream":
        from ..runtime.writer import ForwardPartitioner
        return self._repartition("forward", ForwardPartitioner)

    def partition_custom(self, fn: Callable[[Any, int], int],
                         key: KeySpec) -> "DataStream":
        from ..runtime.writer import CustomPartitioner
        extractor = make_key_extractor(key)
        return self._repartition(
            "custom", lambda: CustomPartitioner(fn, extractor))

    # -- unions / connect --------------------------------------------------
    def union(self, *others: "DataStream") -> "DataStream":
        t = UnionTransformation(
            name="union",
            inputs=[self.transformation] + [o.transformation for o in others])
        self.env._transformations.append(t)
        return DataStream(self.env, t)

    def connect(self, other: "DataStream") -> "ConnectedStreams":
        if isinstance(other, BroadcastStream):
            raise NotImplementedError(
                "broadcast state requires a KEYED stream: use "
                "ds.key_by(...).connect(rules.broadcast(desc)) with a "
                "KeyedBroadcastProcessFunction (the non-keyed "
                "BroadcastProcessFunction variant is not implemented; "
                "silently dropping the state descriptors would run the "
                "job with no broadcast state at all)")
        return ConnectedStreams(self.env, self, other)

    def iterate(self, max_wait_s: float = 2.0) -> "IterativeStream":
        """Open a feedback loop (reference DataStream.iterate +
        StreamIterationHead/Tail): build the loop body on the returned
        stream, then ``close_with(feedback_stream)`` to route records back
        into the head. The head terminates once this stream's regular
        input finished and the loop stayed quiet for ``max_wait_s``.
        ``max_wait_s`` must exceed the body's worst-case per-batch latency
        — records still being processed inside the body when the window
        expires are lost (the reference iteration head has the same
        timeout semantics). Iterations are not checkpointable (deploy
        rejects the combination with periodic checkpointing, matching the
        reference's exclusion of loop state from exactly-once
        guarantees)."""
        from ..graph.transformations import FeedbackTransformation
        t = FeedbackTransformation(name="iteration",
                                   inputs=[self.transformation],
                                   max_wait_s=max_wait_s)
        self.env._transformations.append(t)
        return IterativeStream(self.env, t)

    # -- side outputs ------------------------------------------------------
    def get_side_output(self, tag: str) -> "DataStream":
        t = SideOutputTransformation(name=f"side-{tag}", tag=tag,
                                     inputs=[self.transformation])
        self.env._transformations.append(t)
        return DataStream(self.env, t)

    # -- windows (non-keyed) ----------------------------------------------
    def window_all(self, assigner: WindowAssigner) -> "WindowedStream":
        """All-windows: single pseudo-key, parallelism forced to 1."""
        keyed = self.global_().key_by(lambda _row: 0)
        return WindowedStream(keyed, assigner, all_windows=True)

    # -- sinks -------------------------------------------------------------
    def add_sink(self, sink, name: str = "Sink",
                 parallelism: Optional[int] = None) -> "DataStream":
        from ..connectors.core import Sink
        from ..runtime.operators.sink import FunctionSinkOperator, SinkOperator
        if isinstance(sink, Sink):
            factory = lambda: SinkOperator(sink, name)  # noqa: E731
        elif isinstance(sink, SinkFunction):
            factory = lambda: FunctionSinkOperator(sink, name)  # noqa: E731
        else:
            raise TypeError("add_sink expects a Sink or SinkFunction")
        t = SinkTransformation(name=name, operator_factory=factory,
                               parallelism=parallelism,
                               inputs=[self.transformation])
        self.env._transformations.append(t)
        self.env._sinks.append(t)
        return self

    def sink_to(self, sink, name: str = "Sink",
                parallelism: Optional[int] = None) -> "DataStream":
        return self.add_sink(sink, name, parallelism)

    def print(self, prefix: str = "") -> "DataStream":
        from ..connectors.core import PrintSink
        return self.add_sink(PrintSink(prefix), "Print")

    def execute_and_collect(self, job_name: str = "collect") -> list:
        from ..connectors.core import CollectSink
        sink = CollectSink()
        self.add_sink(sink, "Collect")
        self.env.execute(job_name)
        return sink.rows

    # -- misc --------------------------------------------------------------
    def set_parallelism(self, parallelism: int) -> "DataStream":
        self.transformation.parallelism = parallelism
        return self

    def uid(self, uid: str) -> "DataStream":
        self.transformation.uid = uid
        return self

    def name(self, name: str) -> "DataStream":
        self.transformation.name = name
        return self

    def disable_chaining(self) -> "DataStream":
        self.transformation.chaining_allowed = False
        return self

    def slot_sharing_group(self, group: str) -> "DataStream":
        self.transformation.slot_sharing_group = group
        return self

    def assign_timestamps_and_watermarks(self, ws) -> "DataStream":
        """Mid-stream watermark assignment (reference
        assignTimestampsAndWatermarks)."""
        from ..runtime.operators.simple import \
            TimestampsAndWatermarksOperator
        return self._one_input(
            "TimestampsWatermarks",
            lambda: TimestampsAndWatermarksOperator(ws))


class IterativeStream(DataStream):
    """Head of a feedback loop; ``close_with`` registers the back edge."""

    def close_with(self, feedback: "DataStream") -> "DataStream":
        """Route ``feedback``'s records back into the loop head; returns
        ``feedback`` so the terminating/output branch can continue from it
        (reference IterativeStream.closeWith)."""
        self.transformation.feedback_inputs.append(feedback.transformation)
        return feedback


class BroadcastStream:
    """A broadcast-partitioned stream bound to the MapStateDescriptors of
    the broadcast state it will feed (reference BroadcastStream)."""

    def __init__(self, env, transformation: Transformation, descriptors):
        self.env = env
        self.transformation = transformation
        self.descriptors = list(descriptors)


class BroadcastConnectedStream:
    """Keyed stream + broadcast stream awaiting a
    KeyedBroadcastProcessFunction (reference
    BroadcastConnectedStream.java:55)."""

    def __init__(self, env, keyed: "KeyedStream",
                 broadcast: BroadcastStream):
        self.env = env
        self.keyed = keyed
        self.broadcast = broadcast

    def process(self, fn, name: str = "CoBroadcastWithKeyed",
                out_schema: Optional[Schema] = None,
                parallelism: Optional[int] = None) -> "DataStream":
        from ..runtime.operators.co_broadcast import (
            CoBroadcastWithKeyedOperator,
        )

        ke = self.keyed.key_extractor
        descs = tuple(self.broadcast.descriptors)
        t = TwoInputTransformation(
            name=name,
            operator_factory=lambda: CoBroadcastWithKeyedOperator(
                fn, ke, descs, out_schema=out_schema, name=name),
            parallelism=parallelism,
            inputs=[self.keyed.transformation,
                    self.broadcast.transformation],
            key_extractor1=ke)
        self.env._transformations.append(t)
        return DataStream(self.env, t)


class KeyedStream(DataStream):
    def __init__(self, env, transformation: Transformation, key_extractor,
                 key_spec: KeySpec):
        super().__init__(env, transformation)
        self.key_extractor = key_extractor
        self.key_spec = key_spec

    def connect(self, other) -> "ConnectedStreams":
        if isinstance(other, BroadcastStream):
            return BroadcastConnectedStream(self.env, self, other)
        return ConnectedStreams(self.env, self, other)

    def process(self, fn: ProcessFunction, name: str = "KeyedProcess",
                parallelism: Optional[int] = None) -> "DataStream":
        from ..runtime.operators.simple import KeyedProcessOperator
        ke = self.key_extractor
        return self._one_input(
            name, lambda: KeyedProcessOperator(fn, ke, name=name),
            parallelism, key_extractor=ke)

    # -- rolling (non-windowed) aggregation -------------------------------
    def reduce(self, fn, name: str = "KeyedReduce") -> "DataStream":
        rf = as_reduce(fn)
        ke = self.key_extractor

        from ..core.functions import ProcessFunction as PF
        from ..runtime.operators.simple import KeyedProcessOperator
        from ..state.descriptors import ReducingStateDescriptor

        class _RollingReduce(PF):
            def open(self, ctx):
                self._desc = ReducingStateDescriptor("rolling-reduce", rf)
                self._ctx = ctx

            def process_element(self, value, ctx, out):
                state = self._ctx.get_state(self._desc)
                state.add(value)
                out.collect(state.get(), ctx.timestamp)

        return self._one_input(
            name, lambda: KeyedProcessOperator(_RollingReduce(), ke, name=name),
            key_extractor=ke)

    def sum(self, field: Union[str, int], name: str = "KeyedSum") -> "DataStream":
        return self._rolling_builtin("sum", field, name)

    def min(self, field: Union[str, int], name: str = "KeyedMin") -> "DataStream":
        return self._rolling_builtin("min", field, name)

    def max(self, field: Union[str, int], name: str = "KeyedMax") -> "DataStream":
        return self._rolling_builtin("max", field, name)

    def _rolling_builtin(self, kind: str, field, name: str) -> "DataStream":
        import operator as _op
        pick = (_op.itemgetter(field) if isinstance(field, int)
                else _op.itemgetter(field))

        def combine(a, b):
            va, vb = pick(a), pick(b)
            if kind == "sum":
                v = va + vb
            elif kind == "min":
                v = min(va, vb)
            else:
                v = max(va, vb)
            # keep latest record's other fields, replace aggregated field
            if isinstance(b, tuple):
                out = list(b)
                out[field if isinstance(field, int) else 0] = v
                return tuple(out)
            return v

        if isinstance(field, str):
            raise NotImplementedError(
                "string fields on rolling agg need tuple index; use window "
                "aggregation or pass an int index")
        return self.reduce(combine, name)

    # -- windows -----------------------------------------------------------
    def window(self, assigner: WindowAssigner) -> "WindowedStream":
        return WindowedStream(self, assigner)

    def count_window(self, size: int) -> "WindowedStream":
        return WindowedStream(self, GlobalWindows.create(),
                              trigger=PurgingTrigger.of(CountTrigger.of(size)))


class WindowedStream:
    """(reference WindowedStream): keyed stream + assigner + trigger/evictor
    builder, terminating in reduce/aggregate/apply."""

    def __init__(self, keyed: KeyedStream, assigner: WindowAssigner,
                 trigger: Optional[Trigger] = None,
                 evictor: Optional[Evictor] = None, all_windows: bool = False):
        self.keyed = keyed
        self.assigner = assigner
        self._trigger = trigger
        self._evictor = evictor
        self._lateness = 0
        self._late_tag: Optional[str] = None
        self._all = all_windows

    def trigger(self, trigger: Trigger) -> "WindowedStream":
        self._trigger = trigger
        return self

    def evictor(self, evictor: Evictor) -> "WindowedStream":
        self._evictor = evictor
        return self

    def allowed_lateness(self, ms: int) -> "WindowedStream":
        self._lateness = int(ms)
        return self

    def side_output_late_data(self, tag: str = "late-data") -> "WindowedStream":
        self._late_tag = tag
        return self

    def _build(self, name, aggregate=None, reduce=None, window_fn=None,
               out_schema=None) -> DataStream:
        from ..runtime.operators.window import WindowOperator
        assigner, trigger, evictor = self.assigner, self._trigger, self._evictor
        lateness, late = self._lateness, self._late_tag
        ke = self.keyed.key_extractor

        def factory():
            return WindowOperator(
                assigner, ke, aggregate=aggregate, reduce=reduce,
                window_fn=window_fn, trigger=trigger, evictor=evictor,
                allowed_lateness=lateness, emit_late_data=late is not None,
                out_schema=out_schema, name=name)

        par = 1 if self._all else None
        return self.keyed._one_input(name, factory, parallelism=par,
                                     key_extractor=ke)

    def reduce(self, fn, name: str = "WindowReduce",
               window_fn=None) -> DataStream:
        return self._build(name, reduce=as_reduce(fn), window_fn=window_fn)

    def aggregate(self, fn: AggregateFunction, name: str = "WindowAggregate",
                  window_fn=None) -> DataStream:
        return self._build(name, aggregate=fn, window_fn=window_fn)

    def apply(self, window_fn, name: str = "WindowApply") -> DataStream:
        """window_fn(key, window, elements:list) -> iterable of rows."""
        return self._build(name, window_fn=window_fn)

    def sum(self, field: Union[str, int], name: str = "WindowSum") -> DataStream:
        return self._builtin_agg("sum", field, name)

    def min(self, field: Union[str, int], name: str = "WindowMin") -> DataStream:
        return self._builtin_agg("min", field, name)

    def max(self, field: Union[str, int], name: str = "WindowMax") -> DataStream:
        return self._builtin_agg("max", field, name)

    def count(self, name: str = "WindowCount") -> DataStream:
        return self._builtin_agg("count", None, name)

    def _builtin_agg(self, kind: str, field, name: str) -> DataStream:
        device = self._try_device_agg(kind, field, name)
        if device is not None:
            return device
        import operator as _op

        class _Builtin(AggregateFunction):
            """Field-wise builtin aggregate. ``bind_schema`` resolves a
            string field to the tuple index of the actual batch schema at
            runtime (the operator calls it per batch); the device window
            operator recognizes ``kind``/``field`` and lowers this to a
            segment-reduce instead of calling add() per row."""

            builtin_kind = kind
            builtin_field = field

            def __init__(self):
                if field is None:
                    self._pick = None          # count
                elif isinstance(field, int):
                    self._pick = _op.itemgetter(field)
                else:
                    self._pick = None          # resolved via bind_schema

            def bind_schema(self, schema):
                if isinstance(field, str):
                    if len(schema) == 1:
                        self._pick = lambda v: v
                    else:
                        self._pick = _op.itemgetter(schema.index_of(field))

            def create_accumulator(self):
                return None

            def add(self, value, acc):
                pick = self._pick
                v = 1 if pick is None and field is None else pick(value)
                if acc is None:
                    return v
                if kind in ("sum", "count"):
                    return acc + v
                return min(acc, v) if kind == "min" else max(acc, v)

            def merge(self, a, b):
                if a is None:
                    return b
                if b is None:
                    return a
                if kind in ("sum", "count"):
                    return a + b
                return min(a, b) if kind == "min" else max(a, b)

            def get_result(self, acc):
                return acc

        return self._build(name, aggregate=_Builtin())


    def _try_device_agg(self, kind: str, field, name: str
                        ) -> Optional[DataStream]:
        """Planner rule: lower a builtin window aggregate to the device
        slice-window operator when the configured backend is 'tpu', the key
        is a numeric column, the assigner decomposes into panes, and no
        custom trigger/evictor/lateness is attached. Falls back to the host
        WindowOperator otherwise — outputs are identical (parity-tested)."""
        from ..core.config import StateOptions
        from ..window.assigners import CumulateWindows
        cfg = self.keyed.env.config
        if (cfg.get(StateOptions.BACKEND) != "tpu"
                or not isinstance(self.keyed.key_spec, str)
                or not isinstance(field, (str, type(None)))
                or self.assigner.pane_size is None
                # cumulate panes exist but windows span a VARIABLE number
                # of them — the device/mesh fire programs assume fixed
                # panes-per-window; host WindowOperator handles cumulate
                or isinstance(self.assigner, CumulateWindows)
                or self._trigger is not None or self._evictor is not None
                or self._lateness != 0 or self._late_tag is not None):
            return None
        from ..runtime.operators.device_window import (
            AggSpec, DeviceWindowAggOperator,
        )
        assigner = self.assigner
        key_col = self.keyed.key_spec
        capacity = cfg.get(StateOptions.TPU_CAPACITY) or (1 << 16)
        mesh_devices = cfg.get(StateOptions.MESH_DEVICES)
        spec = AggSpec(kind, field, out_name="result")

        if mesh_devices and mesh_devices >= 2:
            from ..runtime.operators.mesh_window import MeshWindowAggOperator

            def factory():
                return MeshWindowAggOperator(
                    assigner, key_col, [spec], n_devices=mesh_devices,
                    capacity=capacity, emit_window_bounds=False, name=name)

            # the mesh IS the parallelism: one SPMD vertex owns all devices
            return self.keyed._one_input(
                name, factory, parallelism=1,
                key_extractor=self.keyed.key_extractor)

        def factory():
            return DeviceWindowAggOperator(
                assigner, key_col, [spec], capacity=capacity,
                emit_window_bounds=False, name=name)

        par = 1 if self._all else None
        return self.keyed._one_input(name, factory, parallelism=par,
                                     key_extractor=self.keyed.key_extractor)

    def _reject_variable_pane_assigner(self, which: str) -> None:
        from ..window.assigners import reject_variable_pane_assigner
        reject_variable_pane_assigner(self.assigner, which)

    def device_aggregate(self, aggs, capacity: int = 1 << 16,
                         ring_size: int = 64,
                         emit_window_bounds: bool = True,
                         emit_topk: Optional[int] = None,
                         defer_overflow: bool = False,
                         async_fire: bool = False,
                         hbm_budget_slots: int = 0,
                         spill_staging_slots: int = 1 << 16,
                         name: str = "DeviceWindowAgg") -> DataStream:
        """Explicit device window aggregation with multiple AggSpecs
        (key, [window_start, window_end], *agg columns). ``emit_topk=k``
        emits only the top-k keys by the first aggregate per window (the
        Nexmark Q5 hot-items fire shape, ranked on device).
        ``defer_overflow``/``async_fire`` remove all host syncs from the
        hot path (see DeviceWindowAggOperator). ``hbm_budget_slots`` caps
        device state and pages cold key groups to host RAM — composable
        with the deferred fast path (device-side split + staging)."""
        from ..runtime.operators.device_window import DeviceWindowAggOperator
        if not isinstance(self.keyed.key_spec, str):
            raise ValueError("device aggregation needs a column key")
        assigner = self.assigner
        key_col = self.keyed.key_spec

        from ..window.assigners import EventTimeSessionWindows
        if type(assigner) is EventTimeSessionWindows:
            # merging session windows: device lanes operator (VERDICT r3
            # #5) — host gap protocol, device-resident accumulators
            if emit_topk is not None:
                raise ValueError(
                    "emit_topk is not supported for session windows")
            if hbm_budget_slots:
                raise ValueError(
                    "hbm_budget_slots is not supported by the session "
                    "operator yet; drop it or use the host WindowOperator "
                    "path")
            # defer_overflow is how the session operator always works: a
            # table or lane overflow is counted on the device and raises
            # at the next fire's drain
            from ..runtime.operators.device_session import (
                DeviceSessionWindowOperator,
            )
            gap = assigner.gap

            def sess_factory():
                return DeviceSessionWindowOperator(
                    gap, key_col, aggs, capacity=capacity,
                    lanes=max(4, min(ring_size, 16)),
                    emit_window_bounds=emit_window_bounds,
                    async_fire=async_fire, name=name)

            par = 1 if self._all else None
            return self.keyed._one_input(
                name, sess_factory, parallelism=par,
                key_extractor=self.keyed.key_extractor)

        self._reject_variable_pane_assigner("device")

        def factory():
            return DeviceWindowAggOperator(
                assigner, key_col, aggs, capacity=capacity,
                ring_size=ring_size, emit_window_bounds=emit_window_bounds,
                emit_topk=emit_topk, defer_overflow=defer_overflow,
                async_fire=async_fire, hbm_budget_slots=hbm_budget_slots,
                spill_staging_slots=spill_staging_slots, name=name)

        par = 1 if self._all else None
        return self.keyed._one_input(name, factory, parallelism=par,
                                     key_extractor=self.keyed.key_extractor)

    def mesh_aggregate(self, aggs, n_devices: Optional[int] = None,
                       capacity: int = 1 << 16, ring_size: int = 64,
                       device_batch: int = 1 << 12,
                       emit_window_bounds: bool = True,
                       emit_topk: Optional[int] = None,
                       async_fire: bool = False,
                       parallelism: int = 1,
                       name: str = "MeshWindowAgg") -> DataStream:
        """Window aggregation as a mesh-sharded SPMD vertex: keyBy is the
        on-device all_to_all exchange, state is sharded by key-group range
        across the mesh (parallel/sharded_window.py). With
        ``parallelism=1`` (default) the vertex is ONE subtask whose real
        parallelism is the device mesh. ``parallelism=H`` composes DCN
        with ICI for multi-host jobs: H subtasks each own a key-group
        range (the keyed exchange crosses hosts over TCP) and re-shard it
        across their host's local devices (all_to_all over ICI) —
        SURVEY §5.8's two-level plan. ``emit_topk``/``async_fire`` match
        device_aggregate: two-phase global top-k ranked on the first
        aggregate, fires emitting asynchronously with watermarks held
        behind them. The rank aggregate's ``AggSpec.value_bits`` is the
        job's promise to every shard's select, as on one chip: under the
        plane's width (Q7's 43-bit packed word in an int64 MAX) the
        select compiles no guard against a negative rank; a COUNT rank
        is promised 63 bits whatever is declared (the mesh keeps COUNT
        in int64), and a rank with no promise (64) walks guarded. It is
        a word of the fire program's cache key, never of a shard's
        signature; no other aggregate's ``value_bits`` is read here."""
        from ..runtime.operators.mesh_window import MeshWindowAggOperator
        if not isinstance(self.keyed.key_spec, str):
            raise ValueError("mesh aggregation needs a column key")
        if emit_topk is not None and parallelism > 1:
            raise ValueError(
                "emit_topk with parallelism > 1 would rank each subtask's "
                "key range separately, not globally; run the mesh top-k "
                "at parallelism=1 or add a downstream global TopN")
        self._reject_variable_pane_assigner("mesh")
        assigner = self.assigner
        key_col = self.keyed.key_spec

        def factory():
            return MeshWindowAggOperator(
                assigner, key_col, aggs, n_devices=n_devices,
                capacity=capacity, ring_size=ring_size,
                device_batch=device_batch,
                emit_window_bounds=emit_window_bounds,
                emit_topk=emit_topk, async_fire=async_fire, name=name)

        return self.keyed._one_input(name, factory, parallelism=parallelism,
                                     key_extractor=self.keyed.key_extractor)


class ConnectedStreams:
    """Two streams into one two-input operator (reference ConnectedStreams)."""

    def __init__(self, env, first: DataStream, second: DataStream):
        self.env = env
        self.first = first
        self.second = second

    def transform(self, name: str, operator_factory,
                  parallelism: Optional[int] = None) -> DataStream:
        t = TwoInputTransformation(
            name=name, operator_factory=operator_factory,
            parallelism=parallelism,
            inputs=[self.first.transformation, self.second.transformation])
        self.env._transformations.append(t)
        return DataStream(self.env, t)
