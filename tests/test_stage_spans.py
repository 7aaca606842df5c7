"""Stage spans of the mailbox loops (metrics/tracing.py ``Stage``): the
window task's WaitInput / ProcessBatch, the window operator's Upload /
IngestDispatch / Watermark, and one Fire tree per emitted window whose
Drain and Emit arrive from a later mailbox turn. Counts, parentage and
ordering only: no wall-clock threshold anywhere."""

import glob
import os

import numpy as np
import pytest

from flink_tpu.api.environment import StreamExecutionEnvironment
from flink_tpu.connectors.core import DataGenSource, Source, SourceReader
from flink_tpu.core import WatermarkStrategy
from flink_tpu.core.config import (
    Configuration, PipelineOptions, TraceOptions,
)
from flink_tpu.core.functions import SinkFunction
from flink_tpu.core.records import RecordBatch, Schema
from flink_tpu.metrics.device import DEVICE_STATS
from flink_tpu.metrics.tracing import (
    TRACER, FlightRecorder, InMemoryTraceReporter, Span, Tracer,
    chrome_trace_events, now_ns,
)
from flink_tpu.runtime.operators.device_window import (
    AggSpec, DeviceWindowAggOperator,
)
from flink_tpu.runtime.stream_task import OneInputStreamTask
from flink_tpu.window import SlidingEventTimeWindows

pytestmark = pytest.mark.tracing

SCHEMA = Schema([("k", np.int64), ("v", np.int64), ("ts", np.int64)])
N = 12_000
BATCH = 1024


def _gen(idx):
    return {"k": idx % 97, "v": idx % 13, "ts": idx * 2}


class _Collect(SinkFunction):
    def __init__(self):
        self.batches = []

    def invoke_batch(self, batch):
        self.batches.append(batch)
        return True

    def rows(self):
        out = []
        for b in self.batches:
            out += list(zip(b.column("k").tolist(),
                            b.column("window_end").tolist(),
                            b.column("bids").tolist(),
                            b.column("vol").tolist()))
        return out


class _Hesitant(Source):
    """A source whose reader has nothing on two reads of three (what a
    paced or unbounded source looks like to its task)."""

    bounded = True

    def __init__(self, inner):
        self._inner = inner
        self.schema = inner.schema
        self.reads = 0

    def create_splits(self, parallelism):
        return self._inner.create_splits(parallelism)

    def create_reader(self, split):
        outer, inner = self, self._inner.create_reader(split)
        empty = RecordBatch.empty(self.schema)

        class Reader(SourceReader):
            def read_batch(self, max_records):
                outer.reads += 1
                if outer.reads % 3:
                    return empty
                return inner.read_batch(max_records)

        return Reader()


def _run(async_fire=True, defer=True, traces=True, hesitant=False):
    env = StreamExecutionEnvironment.get_execution_environment()
    env.set_state_backend("tpu")
    env.config.set(PipelineOptions.BATCH_SIZE, BATCH)
    env.config.set(TraceOptions.ENABLED, traces)
    ws = WatermarkStrategy.for_monotonous_timestamps() \
        .with_timestamp_column("ts")
    sink = _Collect()
    if hesitant:
        source = _Hesitant(DataGenSource(_gen, SCHEMA, count=N,
                                         timestamp_column="ts"))
        stream = env.from_source(source, ws, "hesitant")
        sink.source = source
    else:
        stream = env.datagen(_gen, SCHEMA, count=N, timestamp_column="ts",
                             watermark_strategy=ws)
    (stream
        .key_by("k")
        .window(SlidingEventTimeWindows.of(4000, 2000))
        .device_aggregate([AggSpec("count", out_name="bids"),
                           AggSpec("sum", "v", out_name="vol")],
                          capacity=1 << 10, ring_size=32,
                          defer_overflow=defer, async_fire=async_fire)
        .add_sink(sink, "collect"))
    env.execute("stage-spans", timeout=300.0)
    return env.last_job, sink


def _window_task(job):
    for task in job.tasks.values():
        for op in getattr(getattr(task, "chain", None), "operators", ()):
            if isinstance(op, DeviceWindowAggOperator):
                return task, op
    raise AssertionError("no window task in the job")


@pytest.fixture(autouse=True)
def _clean():
    TRACER.reset()
    yield
    TRACER.reset()


def _named(spans, scope, name, task=None):
    return [s for s in spans if (s.scope, s.name) == (scope, name)
            and (task is None or s.attributes.get("task") == task)]


@pytest.fixture(scope="module")
def traced_run():
    """One async, deferred job (the benchmark's operator settings)."""
    TRACER.reset()
    job, sink = _run()
    spans = TRACER.retained_spans()
    return job, sink, spans


@pytest.mark.parametrize("async_fire", [True, False])
def test_one_fire_tree_per_emitted_window(async_fire):
    _job, sink = _run(async_fire=async_fire, defer=async_fire)
    spans = TRACER.retained_spans()
    fires = _named(spans, "window", "Fire")
    ends = sorted({w for _k, w, _c, _s in sink.rows()})
    assert sorted(f.attributes["window_end_ms"] for f in fires) == ends
    by_id = {s.span_id: s for s in spans}
    for fire in fires:
        assert fire.parent_id == ""          # a root of its own
        tree = [s for s in spans if s.trace_id == fire.trace_id
                and s.scope == "window" and s is not fire]
        names = sorted(s.name for s in tree)
        assert names == ["Drain", "Emit", "FireDispatch"], names
        for child in tree:
            assert child.parent_id == fire.span_id
            assert child.attributes["seq"] == fire.attributes["seq"]
            assert fire.start_ns <= child.start_ns
            assert child.end_ns <= fire.end_ns
        drain = next(s for s in tree if s.name == "Drain")
        emit = next(s for s in tree if s.name == "Emit")
        assert drain.end_ns <= emit.start_ns
        # which kind of mailbox turn took the fire off the queue: a
        # synchronous fire waits for its own rows
        assert drain.attributes["turn"] in (
            ("timer", "batch", "blocking") if async_fire else ("blocking",))
        assert emit.attributes["rows"] == fire.attributes["rows"] > 0
        assert fire.attributes["unready_polls"] >= 0
        assert fire.attributes["d2h_bytes"] > 0
        # the transfer marker nests under the stage that made it
        d2h = [s for s in _named(spans, "device", "D2H")
               if s.parent_id == drain.span_id]
        assert len(d2h) == 1 and by_id[d2h[0].parent_id] is drain


def test_wait_spans_idle_time_and_batches(traced_run):
    job, _sink, spans = traced_run
    task, _op = _window_task(job)
    assert isinstance(task, OneInputStreamTask)
    waits = _named(spans, "task", "WaitInput", task.task_id)
    batches = _named(spans, "task", "ProcessBatch", task.task_id)
    n_batches = -(-N // BATCH)
    assert len(batches) == n_batches
    assert [b.attributes["seq"] for b in batches] == \
        list(range(1, n_batches + 1))
    assert sum(b.attributes["rows"] for b in batches) == N
    for b in batches:
        assert b.attributes["queued_ms"] >= 0.0
        assert b.attributes["queue_depth"] >= 0
    # one span per wait, not per poll: at most one before each event
    # (batch, watermark, end of input) and one at the end
    events = n_batches * 2 + 2
    assert 0 < len(waits) <= events + 1
    assert all(w.attributes["polls"] >= 1 for w in waits)
    # idle time IS the waits (same timing site), less the processing-time
    # turns the chain worked through inside them, which are busy time
    idle_ns = sum(w.duration_ns for w in waits)
    busy_in_waits_s = sum(w.attributes["busy_ms"] for w in waits) / 1e3
    assert all(0 <= w.attributes["busy_ms"] <= w.duration_ns / 1e6
               for w in waits)
    assert task.io_timers.idle_s == pytest.approx(
        idle_ns / 1e9 - busy_in_waits_s, abs=1e-6 * len(waits))
    # waits and batches of one task never overlap, and alternate in time
    turns = sorted(waits + batches, key=lambda s: s.start_ns)
    for a, b in zip(turns, turns[1:]):
        assert a.end_ns <= b.start_ns


def test_stage_totals_equal_their_spans(traced_run):
    job, _sink, spans = traced_run
    task, op = _window_task(job)

    def total_s(*names):
        return sum(s.duration_ns for n in names
                   for s in _named(spans, "window", n, task.task_id)) / 1e9

    assert op.stage_s["ingest"] == pytest.approx(
        total_s("Upload", "IngestDispatch"), abs=1e-9)
    assert op.stage_s["fire"] == pytest.approx(
        total_s("FireDispatch"), abs=1e-9)
    assert op.stage_s["drain"] == pytest.approx(
        total_s("Drain", "Emit"), abs=1e-9)
    assert op.stage_s["ingest"] > 0 and op.stage_s["fire"] > 0
    # the source task: read + emit of every non-empty cycle
    src = next(t for t in job.tasks.values() if hasattr(t, "reader"))
    cycles = _named(spans, "task", "SourceBatch", src.task_id)
    assert len(cycles) == -(-N // BATCH)
    assert sum(c.attributes["records"] for c in cycles) == N
    assert src.stage_s["emit"] == pytest.approx(
        sum(c.attributes["emit_ms"] for c in cycles) / 1e3, abs=1e-3)
    # read and emit are cut from the span's own two stamps (the reads
    # that returned nothing are no span and take time too)
    assert src.stage_s["read"] + src.stage_s["emit"] >= sum(
        c.duration_ns for c in cycles) / 1e9 - 1e-9
    assert all(c.attributes["emit_ms"] + c.attributes["read_ms"]
               == pytest.approx(c.duration_ns / 1e6, abs=2e-3)
               for c in cycles)


def test_batch_and_watermark_stage_attributes(traced_run):
    job, _sink, spans = traced_run
    task, _op = _window_task(job)
    uploads = _named(spans, "window", "Upload", task.task_id)
    dispatches = _named(spans, "window", "IngestDispatch", task.task_id)
    batches = {b.attributes["seq"]: b
               for b in _named(spans, "task", "ProcessBatch", task.task_id)}
    assert len(uploads) == len(dispatches) == len(batches)
    for up, disp in zip(uploads, dispatches):
        turn = batches[up.attributes["seq"]]
        assert up.attributes["seq"] == disp.attributes["seq"]
        assert up.parent_id == disp.parent_id == turn.span_id
        assert turn.start_ns <= up.start_ns <= up.end_ns \
            <= disp.start_ns <= disp.end_ns <= turn.end_ns
        assert up.attributes["bytes"] > 0
        assert disp.attributes["programs"] == 2   # the probe + one fold
        assert 1 <= disp.attributes["ring_rows"] <= 2
    h2d = _named(spans, "device", "H2D")
    upload_ids = {u.span_id for u in uploads}
    assert len(h2d) == len(uploads)
    assert all(s.parent_id in upload_ids for s in h2d)
    marks = _named(spans, "window", "Watermark", task.task_id)
    fires = _named(spans, "window", "Fire", task.task_id)
    assert marks and sum(m.attributes["fires"] for m in marks) == len(fires)
    assert [m.attributes["seq"] for m in marks] == \
        list(range(1, len(marks) + 1))
    assert all(m.attributes["since_batch_ms"] >= 0 for m in marks)
    mark_ids = {m.span_id for m in marks}
    # a fire's dispatch happens inside the watermark turn that caused it
    for fd in _named(spans, "window", "FireDispatch", task.task_id):
        assert any(m.start_ns <= fd.start_ns and fd.end_ns <= m.end_ns
                   for m in marks)
    assert not mark_ids & {f.parent_id for f in fires}


def test_sub_millisecond_span_has_a_duration():
    with TRACER.stage("window", "Upload", seq=1) as st:
        pass
    (span,) = TRACER.retained_spans()
    assert span.duration_ns > 0 and span.duration_ns == st.duration_ns
    assert span.duration_ms in (0, 1)
    assert span.start_ms == span.start_ns // 1_000_000
    # the ms view and the dict round trip read what they read before
    again = Span.from_dict(span.to_dict())
    assert again == span
    legacy = Span.from_dict({"scope": "a", "name": "b", "start_ms": 5,
                             "end_ms": 9})
    assert (legacy.start_ns, legacy.duration_ms) == (5_000_000, 4)
    (ev,) = [e for e in chrome_trace_events([span])["traceEvents"]
             if e["ph"] == "X"]
    assert ev["ts"] == span.start_ns // 1000
    assert ev["dur"] == span.duration_ns // 1000


def test_open_stage_is_a_root_and_a_backdated_stage_keeps_its_stamp():
    totals = {}
    with TRACER.stage("window", "Watermark", seq=1):
        fire = TRACER.open_stage("window", "Fire", seq=7)
    with TRACER.stage("window", "Emit", parent=fire.context, seq=7,
                      total=(totals, "drain")):
        pass
    fire.count("unready_polls")
    fire.close(rows=3)
    spans = TRACER.retained_spans()
    assert sorted(s.name for s in spans) == ["Emit", "Fire", "Watermark"]
    root = next(s for s in spans if s.name == "Fire")
    assert root.parent_id == ""
    # closed by the thread that opened it: the thread's CPU time between
    cpu_ms = root.attributes.pop("cpu_ms")
    assert cpu_ms >= 0
    assert root.attributes == {"seq": 7, "task": "MainThread",
                               "unready_polls": 1, "rows": 3}
    emit = next(s for s in spans if s.name == "Emit")
    assert (emit.trace_id, emit.parent_id) == (root.trace_id, root.span_id)
    assert set(totals) == {"drain"}
    # an attempt that may turn out to be no interval is stamped first and
    # becomes a stage afterwards: the span starts at the stamp
    stamp = now_ns()
    with TRACER.stage("task", "SourceBatch", start_ns=stamp, seq=1) as st:
        pass
    assert st.start_ns == stamp
    assert TRACER.retained_spans()[-1].start_ns == stamp


def test_stage_spans_have_a_ring_of_their_own():
    """A dozen stage spans per batch must not evict the rare spans (a
    checkpoint's, a failover's), in the reporter or the flight recorder,
    and every eviction is counted."""
    mem = InMemoryTraceReporter(max_retained=3)
    mem.STAGE_FACTOR = 2                    # 6 stage spans
    flight = FlightRecorder(capacity=5)
    tracer = Tracer([mem, flight])
    with tracer.span("checkpoint", "Checkpoint"):
        pass
    before = DEVICE_STATS.snapshot()["spans_dropped_total"]
    for seq in range(20):
        with tracer.stage("window", "Upload", seq=seq):
            pass
    assert [s.name for s in mem.spans] == ["Checkpoint"]
    assert [s.attributes["seq"] for s in mem.stage_spans] == list(
        range(14, 20))
    assert mem.dropped == 14
    assert DEVICE_STATS.snapshot()["spans_dropped_total"] - before == 14
    # one list for REST / CLI, in the order the spans ended
    assert [s.name for s in tracer.retained_spans()] == (
        ["Checkpoint"] + ["Upload"] * 6)
    assert [e["name"] for e in flight.snapshot()] == ["Checkpoint"]
    assert [e["attributes"]["seq"] for e in flight.stage_snapshot()] == list(
        range(15, 20))
    # traces.max-retained sizes both rings
    cfg = Configuration()
    cfg.set(TraceOptions.MAX_RETAINED, 2)
    tracer.configure(cfg)
    with tracer.stage("window", "Upload", seq=20):
        pass
    assert len(mem.stage_spans) == 4


def test_traces_disabled_retains_nothing_and_emits_the_same_rows(
        traced_run):
    _job, sink_on, _spans = traced_run
    job, sink_off = _run(traces=False)
    assert TRACER.retained_spans() == []
    assert sorted(sink_off.rows()) == sorted(sink_on.rows())
    # the totals and the idle time are still measured
    task, op = _window_task(job)
    assert op.stage_s["ingest"] > 0 and task.io_timers.idle_s > 0


def test_unready_polls_counter_matches_the_spans(traced_run):
    before = DEVICE_STATS.snapshot()["fire_unready_polls_total"]
    _run()
    spans = TRACER.retained_spans()
    moved = DEVICE_STATS.snapshot()["fire_unready_polls_total"] - before
    assert moved == sum(f.attributes["unready_polls"]
                        for f in _named(spans, "window", "Fire"))


def test_stage_annotations_land_in_a_profiler_trace(tmp_path):
    """Under a jax.profiler session every stage span is also an event of
    the .xplane.pb, named <scope>.<Name>, with its attributes (late ones
    included) as the event's stats, on the profiler's clock."""
    import jax.profiler

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        job, sink = _run(hesitant=True)
    finally:
        jax.profiler.stop_trace()
    task, _op = _window_task(job)
    (path,) = glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(path)
    seen: dict[str, list[dict]] = {}
    for plane in data.planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(("window.", "task.")):
                    stats = dict(ev.stats)
                    stats["_start_ns"] = ev.start_ns
                    stats["_dur_ns"] = ev.duration_ns
                    seen.setdefault(ev.name, []).append(stats)
    ring = TRACER.retained_spans()
    # an annotation's string arguments may hold no '#': v3#0 rides as v3/0
    xtask = task.task_id.replace("#", "/")
    for scope, name in (("task", "WaitInput"), ("task", "ProcessBatch"),
                        ("window", "Upload"), ("window", "IngestDispatch"),
                        ("window", "Watermark"), ("window", "Fire"),
                        ("window", "FireDispatch"), ("window", "Drain"),
                        ("window", "Emit")):
        events = [e for e in seen.get(f"{scope}.{name}", ())
                  if e.get("task") == xtask]
        spans = _named(ring, scope, name, task.task_id)
        assert len(events) == len(spans) > 0, (scope, name)
        by_seq = {e["seq"]: e for e in events}
        assert len(by_seq) == len(events)       # (name, task, seq) is a key
        for s in spans:
            ev = by_seq[s.attributes["seq"]]
            for key, value in s.attributes.items():
                if key != "task":
                    assert ev[key] == value, (scope, name, key)
    # a read that returned nothing is in neither record: an annotation is
    # entered only for an interval that is reported
    src = next(t for t in job.tasks.values() if hasattr(t, "reader"))
    cycles = _named(ring, "task", "SourceBatch", src.task_id)
    assert len(cycles) == -(-N // BATCH) < sink.source.reads // 2
    assert len(seen["task.SourceBatch"]) == len(cycles)
    assert ({e["seq"] for e in seen["task.SourceBatch"]}
            == {c.attributes["seq"] for c in cycles})
    # the annotation opens before the span's first timestamp and closes
    # after its second, and both keep the order of the batches
    turns = _named(ring, "task", "ProcessBatch", task.task_id)
    events = sorted((e for e in seen["task.ProcessBatch"]
                     if e.get("task") == xtask),
                    key=lambda e: e["_start_ns"])
    assert [e["seq"] for e in events] == [s.attributes["seq"] for s in turns]
    for s, ev in zip(turns, events):
        assert ev["_dur_ns"] >= s.duration_ns
