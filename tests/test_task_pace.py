"""Who sets the pace of a job (PR 53): every stage span closes with the
CPU time of the thread that ran it (``cpu_ms``), a task's mailbox thread
has a CPU clock any thread can read (``TaskIOTimers.cpu_s``), the source
task says how long its writers stood in a full channel (``blocked_ms``),
and the mesh operator counts its one host wait (``reading_wait_ms``,
``mesh_reading_waits_total``). The benchmark's readers of them run here
too, because tier-1 runs ``tests/`` only."""

import threading
import time

import numpy as np
import pytest

from benchmarks.tests import test_task_pace_readers as _cases
from flink_tpu.api.environment import StreamExecutionEnvironment
from flink_tpu.core import WatermarkStrategy
from flink_tpu.core.config import PipelineOptions
from flink_tpu.core.functions import SinkFunction
from flink_tpu.core.records import RecordBatch, Schema
from flink_tpu.metrics import tracing
from flink_tpu.metrics.core import MetricRegistry
from flink_tpu.metrics.device import DEVICE_STATS
from flink_tpu.metrics.tracing import TRACER
from flink_tpu.runtime import OneInputOperatorTestHarness
from flink_tpu.runtime.channels import LocalChannel
from flink_tpu.runtime.operators.device_window import AggSpec
from flink_tpu.runtime.operators.mesh_window import MeshWindowAggOperator
from flink_tpu.runtime.stream_task import TaskIOTimers
from flink_tpu.window import SlidingEventTimeWindows

pytestmark = pytest.mark.tracing

globals().update({name: getattr(_cases, name) for name in dir(_cases)
                  if name.startswith("test_pace_")
                  or name in ("pace_spec", "ring")})


@pytest.fixture(autouse=True)
def _clean():
    TRACER.reset()
    yield
    TRACER.reset()


def _cpu_tick_ms() -> float:
    """How coarse this kernel's thread CPU clock is: nanoseconds where
    the scheduler keeps the time, a 10 ms tick where it samples."""
    first = time.thread_time_ns()
    while (now := time.thread_time_ns()) == first:
        pass
    return (now - first) / 1e6


#: a span's cpu_ms may pass its duration by rounding, or by one tick
SLACK_MS = 0.05 + (t if (t := _cpu_tick_ms()) > 0.5 else 0.0)


def _spin_cpu(ms: float) -> None:
    """Compute until this thread's CPU clock has moved ``ms``."""
    end = time.thread_time_ns() + int(ms * 1e6)
    while time.thread_time_ns() < end:
        pass


# -- Stage.cpu_ms ------------------------------------------------------------

def test_a_sleeping_stage_has_no_cpu_time_and_a_spinning_one_is_all_cpu():
    with TRACER.stage("window", "Upload", seq=1):
        time.sleep(0.05)
    slept = TRACER.retained_spans()[-1]
    assert slept.duration_ns >= 50e6
    assert 0 <= slept.attributes["cpu_ms"] < 5 + SLACK_MS
    # a loaded host may take the core away from a spinning thread: the
    # best of three is within a fifth of its duration
    ratios = []
    for seq in range(3):
        with TRACER.stage("window", "Upload", seq=2 + seq):
            _spin_cpu(50)
        spun = TRACER.retained_spans()[-1]
        cpu, ms = spun.attributes["cpu_ms"], spun.duration_ns / 1e6
        assert 50 <= cpu <= ms + SLACK_MS
        ratios.append(cpu / ms)
    assert max(ratios) >= 0.8
    assert isinstance(spun.attributes["cpu_ms"], float)
    assert round(spun.attributes["cpu_ms"], 3) == spun.attributes["cpu_ms"]


def test_a_stage_closed_by_another_thread_has_no_cpu_ms():
    reclaim = TRACER.open_stage("window", "Reclaim", seq=7)
    closer = threading.Thread(target=lambda: reclaim.close(kept=3))
    closer.start()
    closer.join()
    wait = TRACER.open_stage("task", "WaitInput", seq=1)
    wait.close(polls=2)
    by_name = {s.name: s for s in TRACER.retained_spans()}
    assert by_name["Reclaim"].attributes == {"seq": 7, "kept": 3,
                                             "task": "MainThread"}
    # opened and closed by one thread, turns apart: it has
    assert "cpu_ms" in by_name["WaitInput"].attributes


def test_a_backdated_stage_counts_cpu_from_the_callers_stamp():
    stamp, cpu_stamp = tracing.now_ns(), tracing.thread_cpu_ns()
    _spin_cpu(20)                                # a source's read
    with TRACER.stage("task", "SourceBatch", start_ns=stamp,
                      start_cpu_ns=cpu_stamp, seq=1):
        pass
    span = TRACER.retained_spans()[-1]
    assert span.start_ns == stamp and span.attributes["cpu_ms"] >= 20
    # an end stamp the caller took is the span's end
    st = TRACER.stage("task", "SourceBatch", seq=2)
    end = tracing.now_ns()
    st.close(end, emit_ms=1.0)
    assert TRACER.retained_spans()[-1].end_ns == end == st.end_ns


def test_cpu_ms_reaches_the_annotation_as_late_metadata(monkeypatch):
    seen = []

    class Annotation:
        def __init__(self, name, **args):
            self.name, self.args, self.late = name, args, {}

        @staticmethod
        def is_enabled():
            return True

        def __enter__(self):
            return self

        def set_metadata(self, **late):
            self.late.update(late)

        def __exit__(self, *exc):
            seen.append(self)

    monkeypatch.setattr(tracing, "_ANNOTATION", Annotation)
    with TRACER.stage("window", "IngestDispatch", seq=4) as st:
        st.set("programs", 2)
    (ann,) = seen
    span = TRACER.retained_spans()[-1]
    assert ann.name == "window.IngestDispatch" and "cpu_ms" not in ann.args
    assert ann.late == {"programs": 2,
                        "cpu_ms": span.attributes["cpu_ms"]}


# -- TaskIOTimers.cpu_s ------------------------------------------------------

def test_a_tasks_cpu_clock_is_read_from_another_thread_and_freezes():
    timers = TaskIOTimers()
    assert timers.cpu_s is None and timers.cpu_ratio is None
    work, worked, rest, done = (threading.Event() for _ in range(4))

    def mailbox():
        timers.start()
        work.wait()
        _spin_cpu(60)
        worked.set()
        rest.wait()
        timers.stop()
        done.set()
        time.sleep(0.05)        # the thread lives on; the clock does not

    thread = threading.Thread(target=mailbox)
    thread.start()
    while timers.cpu_s is None:
        time.sleep(0.001)
    waiting = timers.cpu_s
    time.sleep(0.05)
    assert timers.cpu_s - waiting < 0.005 + SLACK_MS / 1e3   # waiting
    work.set()
    worked.wait()
    computed = timers.cpu_s
    assert computed - waiting >= 0.06            # a working one
    rest.set()
    done.wait()
    frozen = timers.cpu_s
    assert frozen >= computed
    thread.join()
    _spin_cpu(5)
    assert timers.cpu_s == frozen                # after stop(), for good
    assert 0 < timers.cpu_ratio <= 1
    assert timers.cpu_ms_per_s == timers.cpu_ratio * 1000.0


# -- a job: the gauges, and the source's blocked time ------------------------

SCHEMA = Schema([("k", np.int64), ("ts", np.int64)])
N, BATCH = 6_000, 100


class _Sink(SinkFunction):
    def __init__(self, sleep_s: float = 0.0):
        self.sleep_s, self.rows = sleep_s, 0

    def invoke_batch(self, batch):
        if self.sleep_s:
            time.sleep(self.sleep_s)
        self.rows += batch.n
        return True


def _job(sink):
    """A source task and, across a channel, a sink task."""
    env = StreamExecutionEnvironment.get_execution_environment()
    env.config.set(PipelineOptions.BATCH_SIZE, BATCH)
    # only the end-of-input watermark crosses the channel
    env.config.set(PipelineOptions.AUTO_WATERMARK_INTERVAL, 3600.0)
    ws = WatermarkStrategy.for_monotonous_timestamps() \
        .with_timestamp_column("ts")
    stream = env.datagen(lambda i: {"k": i % 7, "ts": i}, SCHEMA, count=N,
                         timestamp_column="ts", watermark_strategy=ws)
    stream.rebalance().add_sink(sink, "sink")
    registry = MetricRegistry()
    env.execute("pace", timeout=120.0, metrics_registry=registry)
    job = env.last_job
    src = next(t for t in job.tasks.values() if hasattr(t, "reader"))
    cycles = [s for s in TRACER.retained_spans()
              if (s.scope, s.name) == ("task", "SourceBatch")]
    assert sink.rows == N and len(cycles) == N // BATCH
    return job, src, cycles, registry


def test_a_slow_consumer_behind_one_slot_shows_as_blocked_ms(monkeypatch):
    monkeypatch.setattr(LocalChannel.__init__, "__defaults__", (1,))
    _job_, src, cycles, _reg = _job(_Sink(sleep_s=0.01))
    blocked_ms = sum(c.attributes["blocked_ms"] for c in cycles)
    assert blocked_ms > 0.8 * (N // BATCH - 2) * 10
    # the writers' own account; outside the cycles only the last
    # watermark and the end of input stood in the channel
    assert blocked_ms / 1e3 == pytest.approx(src.io_timers.backpressured_s,
                                             rel=0.1)
    # what is left of the emit is the source's own: little
    own_ms = sorted(c.attributes["emit_ms"] - c.attributes["blocked_ms"]
                    for c in cycles)
    assert all(o >= -0.002 for o in own_ms)
    assert own_ms[len(own_ms) // 2] < 2.0
    assert all(c.attributes["cpu_ms"] <= c.duration_ns / 1e6 + SLACK_MS
               for c in cycles)
    # standing in the channel is no work
    assert sum(c.attributes["cpu_ms"] for c in cycles) < 0.5 * blocked_ms


def test_a_fast_consumer_blocks_nothing_and_the_gauges_are_in_the_registry():
    job, src, cycles, registry = _job(_Sink())
    assert sum(c.attributes["blocked_ms"] for c in cycles) == 0
    assert src.io_timers.backpressured_s == 0
    snap = registry.snapshot()
    for task in job.tasks.values():
        scope = ".".join(task.ctx.metrics.group.scope)
        assert scope.startswith("pace.")
        timers = task.io_timers
        assert snap[f"{scope}.cpuTimeRatio"] == timers.cpu_ratio
        assert snap[f"{scope}.cpuTimeMsPerSecond"] == timers.cpu_ms_per_s
        assert f"{scope}.busyTimeRatio" in snap
        # the threads have ended: the reading stays
        assert 0 < timers.cpu_s == timers.cpu_s <= timers.elapsed_s


# -- the mesh operator's one host wait ---------------------------------------

MESH_SCHEMA = Schema([("key", np.int64), ("v", np.int64)])
PANE = 250
WAITS = ("mesh_reading_waits_total", "mesh_reading_wait_us_total")


def _mesh(capacity: int, blocks: int, device_batch: int = 64):
    """``blocks`` full [4, device_batch] blocks over 40 resident keys,
    one call a block; (operator, counters' growth, its dispatch spans)."""
    op = MeshWindowAggOperator(
        SlidingEventTimeWindows.of(4 * PANE, PANE), "key",
        [AggSpec("sum", "v", out_name="result")], n_devices=4,
        capacity=capacity, ring_size=16, device_batch=device_batch,
        async_fire=True)
    h = OneInputOperatorTestHarness(op, schema=MESH_SCHEMA)
    rows = 4 * device_batch
    rng = np.random.default_rng(capacity)
    before = DEVICE_STATS.snapshot()
    TRACER.reset()
    for b in range(blocks):
        keys = rng.integers(0, 40, rows).astype(np.int64)
        h.process_batch(RecordBatch(
            MESH_SCHEMA, {"key": keys, "v": np.ones_like(keys)},
            np.full(rows, b, np.int64)))
    h.process_watermark(10**9)
    op.finish()
    after = DEVICE_STATS.snapshot()
    spans = [s for s in TRACER.retained_spans()
             if (s.scope, s.name) == ("window", "IngestDispatch")]
    assert len(spans) == blocks
    return op, {k: after[k] - before[k] for k in WAITS}, spans


def test_a_job_far_from_its_headroom_never_waits_for_a_reading():
    # 2^12 slots a shard: 41 blocks of headroom, a wait after 20
    _op, grew, spans = _mesh(1 << 12, blocks=10)
    assert grew == {WAITS[0]: 0, WAITS[1]: 0}
    assert not any("reading_wait_ms" in s.attributes for s in spans)
    assert DEVICE_STATS.mesh_reading_wait_counts[0] \
        == DEVICE_STATS.snapshot()[WAITS[0]]


def test_a_table_at_its_headroom_waits_and_the_block_says_so():
    """2^8 slots a shard, every row assumed a new key until two readings
    say otherwise: two blocks of headroom, so blocks 2 and 3 each wait
    for the probe sent behind the block before, unless its copy has
    landed by then (the CPU is quick: the best of three)."""
    for _attempt in range(3):
        op, grew, spans = _mesh(1 << 8, blocks=6)
        if grew[WAITS[0]]:
            break
    waited = [s for s in spans if "reading_wait_ms" in s.attributes]
    assert 1 <= grew[WAITS[0]] == len(waited) <= 2
    assert {s.attributes["seq"] for s in waited} <= {2, 3}
    assert sum(s.attributes["reading_wait_ms"] for s in waited) \
        == pytest.approx(grew[WAITS[1]] / 1e3, abs=2e-3 * len(waited))
    assert all(0 < s.attributes["reading_wait_ms"] <= s.duration_ns / 1e6
               for s in waited)
    assert op._reading_wait_ns == pytest.approx(grew[WAITS[1]] * 1e3,
                                                abs=200)


def test_a_reading_that_has_landed_costs_no_wait():
    op, _grew, _spans = _mesh(1 << 12, blocks=1)
    before = DEVICE_STATS.snapshot()
    import jax.numpy as jnp
    outs = (jnp.int32(3), jnp.int32(0))
    for leaf in outs:
        leaf.block_until_ready()
    assert [int(x) for x in op._await_reading(outs, landed=True)] == [3, 0]
    mid = DEVICE_STATS.snapshot()
    assert [mid[k] - before[k] for k in WAITS] == [0, 0]
    assert [int(x) for x in op._await_reading(outs, landed=False)] == [3, 0]
    after = DEVICE_STATS.snapshot()
    assert after[WAITS[0]] - mid[WAITS[0]] == 1
    assert after[WAITS[1]] > mid[WAITS[1]]
