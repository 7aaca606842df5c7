"""The async fire queue drains on the mailbox's processing-time turn
(slice_control.AsyncFireQueue.advance_processing_time): a fired window's
rows leave on the first turn after their device->host copy has landed,
whether or not a further batch arrives. Both window stacks (the one-chip
operator and the mesh operator over 4 virtual devices), through
``env.execute()`` and through the operator harness. Order, counts and
parentage only: the one clock in here is a deadline that turns a drain
that never comes into a failure and not a hang."""

import contextlib
import threading
import time

import jax
import numpy as np
import pytest

from flink_tpu.api.environment import StreamExecutionEnvironment
from flink_tpu.connectors.core import DataGenSource, Source, SourceReader
from flink_tpu.core import WatermarkStrategy
from flink_tpu.core.config import PipelineOptions, TraceOptions
from flink_tpu.core.functions import SinkFunction
from flink_tpu.core.records import RecordBatch, Schema
from flink_tpu.metrics.device import DEVICE_STATS
from flink_tpu.metrics.tracing import TRACER
from flink_tpu.runtime.harness import OneInputOperatorTestHarness
from flink_tpu.runtime.operators.device_window import (
    AggSpec, DeviceWindowAggOperator,
)
from flink_tpu.runtime.operators.mesh_window import MeshWindowAggOperator
from flink_tpu.runtime.operators.slice_control import AsyncFireQueue
from flink_tpu.window import SlidingEventTimeWindows

pytestmark = pytest.mark.tracing

SCHEMA = Schema([("k", np.int64), ("v", np.int64), ("ts", np.int64)])
N = 8_192
BATCH = 1024
SIZE, SLIDE = 4000, 2000
KINDS = ["one-chip", "mesh4"]
AGGS = [AggSpec("count", out_name="bids"), AggSpec("sum", "v", out_name="vol")]
#: how long the quiet source waits for rows that a working drain hands over
#: within milliseconds, before it gives up waiting and the test fails
DEADLINE_S = 20.0


def _gen(idx):
    return {"k": idx % 97, "v": idx % 13, "ts": idx * 2}


def _reference(n=N):
    """Per record: every sliding window a record falls into counts it."""
    out = {}
    for i in range(n):
        k, v, ts = i % 97, i % 13, i * 2
        first = (ts // SLIDE) * SLIDE + SLIDE
        for end in range(first, first + SIZE, SLIDE):
            bids, vol = out.get((k, end), (0, 0))
            out[(k, end)] = (bids + 1, vol + v)
    return sorted((k, end, b, s) for (k, end), (b, s) in out.items())


def _rows_of(batches):
    out = []
    for b in batches:
        out += list(zip(b.column("k").tolist(),
                        b.column("window_end").tolist(),
                        b.column("bids").tolist(),
                        b.column("vol").tolist()))
    return out


class _Collect(SinkFunction):
    def __init__(self):
        self.batches = []
        self.ends = set()

    def invoke_batch(self, batch):
        self.batches.append(batch)
        self.ends.update(batch.column("window_end").tolist())
        return True

    def rows(self):
        return _rows_of(self.batches)


class _WaitsForItsWindows(Source):
    """Hands over a batch, lets its watermark follow, and then has
    nothing until every window that the batch completed has reached the
    sink: the window task sits in task/WaitInput while the fire lands, and
    only a drain that needs no further batch lets the source go on."""

    bounded = True

    def __init__(self, inner, sink):
        self._inner = inner
        self._sink = sink
        self.schema = inner.schema
        self.timed_out = False
        self.completed = {}      # batch ordinal -> window ends it completed

    def create_splits(self, parallelism):
        return self._inner.create_splits(parallelism)

    def create_reader(self, split):
        outer, inner = self, self._inner.create_reader(split)
        empty = RecordBatch.empty(self.schema)
        state = {"owed": set(), "since": 0.0, "batches": 0}

        class Reader(SourceReader):
            def read_batch(self, max_records):
                if (state["owed"] - outer._sink.ends
                        and not outer.timed_out):
                    if time.monotonic() - state["since"] < DEADLINE_S:
                        return empty
                    outer.timed_out = True
                batch = inner.read_batch(max_records)
                if batch is not None and batch.n:
                    state["batches"] += 1
                    last = int(batch.column("ts").max())
                    ends = set(range(SLIDE, last + 1, SLIDE))
                    outer.completed[state["batches"]] = ends - state["owed"]
                    state["owed"] = ends
                    state["since"] = time.monotonic()
                return batch

        return Reader()


def _aggregate(windowed, kind, async_fire):
    if kind == "mesh4":
        return windowed.mesh_aggregate(
            AGGS, n_devices=4, capacity=1 << 10, ring_size=32,
            device_batch=BATCH // 4, emit_window_bounds=True,
            async_fire=async_fire)
    return windowed.device_aggregate(
        AGGS, capacity=1 << 10, ring_size=32, emit_window_bounds=True,
        defer_overflow=async_fire, async_fire=async_fire)


def _run(kind, async_fire=True, gated=True):
    env = StreamExecutionEnvironment.get_execution_environment()
    env.set_state_backend("tpu")
    env.config.set(PipelineOptions.BATCH_SIZE, BATCH)
    env.config.set(PipelineOptions.AUTO_WATERMARK_INTERVAL, 0.005)
    env.config.set(TraceOptions.ENABLED, True)
    ws = WatermarkStrategy.for_monotonous_timestamps() \
        .with_timestamp_column("ts")
    sink = _Collect()
    source = DataGenSource(_gen, SCHEMA, count=N, timestamp_column="ts")
    if gated:
        source = _WaitsForItsWindows(source, sink)
    stream = env.from_source(source, ws, "bids")
    windowed = stream.key_by("k").window(
        SlidingEventTimeWindows.of(SIZE, SLIDE))
    _aggregate(windowed, kind, async_fire).add_sink(sink, "collect")
    env.execute(f"timer-drain-{kind}", timeout=300.0)
    return env.last_job, sink, source


def _window_task(job):
    for task in job.tasks.values():
        for op in getattr(getattr(task, "chain", None), "operators", ()):
            if isinstance(op, AsyncFireQueue):
                return task, op
    raise AssertionError("no window task in the job")


def _named(spans, scope, name, task=None):
    return [s for s in spans if (s.scope, s.name) == (scope, name)
            and (task is None or s.attributes.get("task") == task)]


@contextlib.contextmanager
def _drain_notes():
    """Every ``DEVICE_STATS.note_fire_drained`` call while open, as
    (thread name, timer). The counters are process-wide, and a job that an
    earlier test file left winding down on this worker may move them too:
    a test counts the calls of its own thread (a task's thread is named
    after the task) and holds the counters to at least that."""
    notes = []
    real = DEVICE_STATS.note_fire_drained

    def note(timer):
        notes.append((threading.current_thread().name, bool(timer)))
        real(timer)

    DEVICE_STATS.note_fire_drained = note
    try:
        yield notes
    finally:
        del DEVICE_STATS.note_fire_drained


def _moved(before, after, own, thread):
    """The (drained, on a timer turn) calls of ``thread``, after the
    check that the counters moved by at least as much."""
    mine = [timer for name, timer in own if name == thread]
    assert (after["fires_drained_total"] - before["fires_drained_total"]
            >= len(mine))
    assert (after["fires_drained_timer_total"]
            - before["fires_drained_timer_total"] >= sum(mine))
    return len(mine), sum(mine)


@pytest.fixture(autouse=True)
def _clean():
    TRACER.reset()
    yield
    TRACER.reset()


@pytest.fixture(scope="module", params=KINDS)
def idle_run(request):
    """One job whose source goes quiet after every batch until that
    batch's windows are at the sink."""
    TRACER.reset()
    before = DEVICE_STATS.snapshot()
    with _drain_notes() as notes:
        job, sink, source = _run(request.param)
    after = DEVICE_STATS.snapshot()
    spans = TRACER.retained_spans()
    TRACER.reset()
    moved = _moved(before, after, notes, _window_task(job)[0].task_id)
    return job, sink, source, spans, moved


def test_rows_leave_while_the_source_is_quiet(idle_run):
    job, sink, source, spans, moved = idle_run
    assert not source.timed_out, (
        "a fired window's rows waited for a batch that was waiting for them")
    assert sorted(sink.rows()) == _reference()
    task, _op = _window_task(job)
    batches = {b.attributes["seq"]: b for b in
               _named(spans, "task", "ProcessBatch", task.task_id)}
    emits = {e.attributes["seq"]: e for e in
             _named(spans, "window", "Emit", task.task_id)}
    drains = {d.attributes["seq"]: d for d in
              _named(spans, "window", "Drain", task.task_id)}
    mid_stream = set()
    for seq, ends in source.completed.items():
        mid_stream |= ends
        nxt = batches.get(seq + 1)
        for end in ends:
            # the window's rows were out before the next batch began ...
            if nxt is not None:
                assert emits[end].end_ns <= nxt.start_ns
            # ... taken off the queue by a processing-time turn (behind
            # the watermark's event or inside the wait for the next
            # batch), never by a batch's turn
            assert drains[end].attributes["turn"] == "timer"
            assert not any(b.start_ns <= drains[end].start_ns < b.end_ns
                           for b in batches.values())
    assert mid_stream
    # the windows still open at the end of input leave behind the final
    # watermark: on the turn that follows it, or with finish()
    flushed = set(drains) - mid_stream
    assert flushed and all(
        drains[end].attributes["turn"] in ("timer", "blocking")
        for end in flushed)
    on_timer = [d for d in drains.values() if d.attributes["turn"] == "timer"]
    assert moved == (len(drains), len(on_timer))
    assert len(drains) == len(sink.ends)


def test_a_timer_turn_drain_is_busy_time_not_idle_time(idle_run):
    job, _sink, _source, spans, _moved = idle_run
    task, _op = _window_task(job)
    waits = _named(spans, "task", "WaitInput", task.task_id)
    turns = _named(spans, "task", "ProcessBatch", task.task_id)
    timer_drains = {d.attributes["seq"] for d in
                    _named(spans, "window", "Drain", task.task_id)
                    if d.attributes["turn"] == "timer"}
    work = [s for name in ("Drain", "Emit")
            for s in _named(spans, "window", name, task.task_id)
            if s.attributes["seq"] in timer_drains]
    assert work
    # on top of the batches' turns, which hold none of it
    assert task.io_timers.busy_s >= sum(
        s.duration_ns for s in turns + work) / 1e9
    # and off the idle time, where it fell inside a wait (0.5 us of
    # rounding a wait)
    in_waits_ms = sum(s.duration_ns for s in work
                      if any(w.start_ns <= s.start_ns and s.end_ns <= w.end_ns
                             for w in waits)) / 1e6
    busy_in_waits_ms = sum(w.attributes["busy_ms"] for w in waits)
    assert busy_in_waits_ms >= in_waits_ms - 1e-3 * len(waits)
    assert task.io_timers.idle_s == pytest.approx(
        sum(w.duration_ns for w in waits) / 1e9 - busy_in_waits_ms / 1e3,
        abs=1e-6 * len(waits))


def test_a_turn_inside_a_wait_comes_off_the_idle_time():
    """The task's own accounting, with a chain that works 2 ms in its
    processing-time turn: inside an open task/WaitInput that time is
    busy, and the wait's idle time is its length less that."""
    from flink_tpu.runtime.operators.base import OperatorContext
    from flink_tpu.runtime.stream_task import StreamTask, TaskReporter

    clock = iter(range(1, 100))
    ctx = OperatorContext(task_name="t", subtask_index=0, parallelism=1,
                          max_parallelism=128,
                          processing_time=lambda: next(clock))
    task = StreamTask("t#0", ctx, [], TaskReporter())

    class Chain:
        turns = 0

        def advance_processing_time(self, now_ms):
            self.turns += 1
            time.sleep(0.002)

    chain = Chain()
    TRACER.reset()
    task._advance_processing_time(chain)        # behind an event: busy only
    assert task.io_timers.busy_s >= 0.002 and task.io_timers.idle_s == 0.0
    task._note_empty_poll()
    task._advance_processing_time(chain)
    task._note_empty_poll()
    task._advance_processing_time(chain)
    wait = task._wait
    task._end_wait()
    assert chain.turns == 3 and task.io_timers.busy_s >= 0.006
    assert wait.attrs["polls"] == 2 and wait.attrs["busy_ms"] >= 4.0
    assert task.io_timers.idle_s == pytest.approx(
        wait.duration_s - wait.attrs["busy_ms"] / 1e3, abs=1e-6)
    assert 0 <= task.io_timers.idle_s <= wait.duration_s - 0.004


@pytest.mark.parametrize("kind", KINDS)
def test_back_to_back_batches_give_the_same_rows(kind):
    before = DEVICE_STATS.snapshot()
    with _drain_notes() as notes:
        job, sink, _source = _run(kind, gated=False)
    assert sorted(sink.rows()) == _reference()
    task, _op = _window_task(job)
    drains = _named(TRACER.retained_spans(), "window", "Drain", task.task_id)
    # every window once, whichever kind of turn found its fire landed
    assert sorted(d.attributes["seq"] for d in drains) == sorted(sink.ends)
    assert {d.attributes["turn"] for d in drains} <= {
        "timer", "batch", "blocking"}
    on_timer = [d for d in drains if d.attributes["turn"] == "timer"]
    assert _moved(before, DEVICE_STATS.snapshot(), notes,
                  task.task_id) == (len(drains), len(on_timer))


# -- the operator alone, under the harness's manual clock -------------------

def _operator(kind, async_fire=True):
    window = SlidingEventTimeWindows.of(SIZE, SLIDE)
    if kind == "mesh4":
        return MeshWindowAggOperator(
            window, "k", AGGS, n_devices=4, capacity=1 << 10, ring_size=32,
            device_batch=BATCH // 4, async_fire=async_fire)
    return DeviceWindowAggOperator(
        window, "k", AGGS, capacity=1 << 10, ring_size=32,
        defer_overflow=async_fire, async_fire=async_fire)


def _batch(i):
    idx = np.arange(i * BATCH, (i + 1) * BATCH, dtype=np.int64)
    cols = {name: np.asarray(col, np.int64)
            for name, col in _gen(idx).items()}
    return RecordBatch(SCHEMA, cols, cols["ts"])


def _feed(h, i):
    """Batch ``i`` and the watermark behind it."""
    h.process_batch(_batch(i))
    h.process_watermark(int(_batch(i).timestamps.max()) - 1)


def _in_order(output):
    """Everything the harness's output receives from now on, in order."""
    events = []
    emit, emit_watermark = output.emit, output.emit_watermark

    def rows(batch):
        events.append(("rows", sorted(set(
            batch.column("window_end").tolist()))))
        emit(batch)

    def mark(watermark):
        events.append(("watermark", watermark.timestamp))
        emit_watermark(watermark)

    output.emit, output.emit_watermark = rows, mark
    return events


def _land(op):
    """Wait for the device to finish every queued fire (not a drain)."""
    for item in op._pending:
        if isinstance(item, tuple):
            jax.block_until_ready(item[1])


@pytest.mark.parametrize("kind", KINDS)
def test_rows_leave_before_the_watermark_held_behind_them(kind):
    op = _operator(kind)
    h = OneInputOperatorTestHarness(op, schema=SCHEMA)
    h.open()
    events = _in_order(h.output)
    _feed(h, 0)                     # ts 0..2046: the window ending 2000
    wm = int(_batch(0).timestamps.max()) - 1
    # dispatched, nothing out yet: the watermark waits behind the fire
    assert events == [] and len(op._pending) == 2
    before = DEVICE_STATS.snapshot()
    _land(op)
    with _drain_notes() as notes:
        h.set_processing_time(1)
        assert events == [("rows", [2000]), ("watermark", wm)]
        assert not op._pending
        # once: no later turn, and no blocking drain, finds it again
        h.set_processing_time(2)
        h.snapshot(1)
    assert events == [("rows", [2000]), ("watermark", wm)]
    assert _moved(before, DEVICE_STATS.snapshot(), notes,
                  threading.current_thread().name) == (1, 1)


@pytest.mark.parametrize("kind", KINDS)
def test_a_fire_that_has_not_landed_stays_queued(kind, monkeypatch):
    """A processing-time turn never blocks: a head fire whose outputs are
    not ready is counted as an unready poll and left where it is, with
    the watermark behind it."""
    op = _operator(kind)
    h = OneInputOperatorTestHarness(op, schema=SCHEMA)
    h.open()
    events = _in_order(h.output)
    _feed(h, 0)
    leaves = jax.tree_util.tree_leaves(op._pending[0][1])
    _land(op)
    monkeypatch.setattr(type(leaves[0]), "is_ready", lambda self: False)
    polls = DEVICE_STATS.snapshot()["fire_unready_polls_total"]
    fire = op._pending[0][-1]
    h.set_processing_time(1)
    h.set_processing_time(2)
    assert events == [] and len(op._pending) == 2
    assert DEVICE_STATS.snapshot()["fire_unready_polls_total"] - polls >= 2
    monkeypatch.undo()
    h.set_processing_time(3)
    assert [e[0] for e in events] == ["rows", "watermark"]
    assert fire.attrs["unready_polls"] == 2


@pytest.mark.parametrize("timer_first", [False, True],
                         ids=["barrier-first", "timer-first"])
@pytest.mark.parametrize("kind", KINDS)
def test_barrier_between_dispatch_and_drain_then_restore(kind, timer_first):
    """A checkpoint barrier that arrives while a fire is queued takes
    whatever the timer turn has not taken; the restored operator goes on
    from there: the per-record reference's rows, no window twice, none
    missing."""
    n_batches, cut = N // BATCH, 3
    h1 = OneInputOperatorTestHarness(_operator(kind), schema=SCHEMA)
    h1.open()
    for i in range(cut):
        _feed(h1, i)
        _land(h1.operator)
        h1.set_processing_time(i + 1)
    _feed(h1, cut)                  # fires the windows ending 6000, 8000
    assert h1.operator._pending
    if timer_first:
        _land(h1.operator)
        h1.set_processing_time(cut + 1)
        assert not h1.operator._pending
    snap = h1.snapshot(1)
    assert not h1.operator._pending
    first = _rows_of(h1.output.batches)
    h2 = OneInputOperatorTestHarness.restored(
        lambda: _operator(kind), snap, schema=SCHEMA)
    for i in range(cut + 1, n_batches):
        _feed(h2, i)
        _land(h2.operator)
        h2.set_processing_time(i + 1)
    h2.process_watermark(1 << 60)
    h2.close()
    rows = first + _rows_of(h2.output.batches)
    assert len(rows) == len(set((k, end) for k, end, _b, _s in rows))
    assert sorted(rows) == _reference()
    # each watermark went downstream once, in order
    marks = h1.get_watermarks() + h2.get_watermarks()
    assert marks == sorted(set(marks))


@pytest.mark.parametrize("kind", KINDS)
def test_synchronous_fires_are_untouched(kind):
    op = _operator(kind, async_fire=False)
    h = OneInputOperatorTestHarness(op, schema=SCHEMA)
    h.open()
    events = _in_order(h.output)
    before = DEVICE_STATS.snapshot()
    with _drain_notes() as notes:
        for i in range(N // BATCH):
            _feed(h, i)
            # the rows and the watermark are out when process_watermark
            # returns
            assert not op._pending and events[-1][0] == "watermark"
            seen = list(events)
            h.set_processing_time(i + 1)
            assert events == seen
        h.process_watermark(1 << 60)
        h.close()
    assert sorted(_rows_of(h.output.batches)) == _reference()
    windows = len({end for _k, end, _b, _s in _reference()})
    assert _moved(before, DEVICE_STATS.snapshot(), notes,
                  threading.current_thread().name) == (windows, 0)
