"""Stall watchdog: deadline-bounded supervised calls, `!hang@MS` fault
injection, the writer backpressure cap, task-progress supervision, and
the REST/metrics/bench stall surfaces. All hang injections use tiny
delays; the `stall` marker arms the conftest SIGALRM wall-clock guard so
a watchdog regression fails the suite instead of hanging it."""

import threading
import time
from collections import deque
from types import SimpleNamespace

import numpy as np
import pytest

from flink_tpu.core.config import Configuration, WatchdogOptions
from flink_tpu.metrics.device import DEVICE_STATS
from flink_tpu.runtime import faults as faults_mod
from flink_tpu.runtime.channels import LocalChannel
from flink_tpu.runtime.faults import FaultRule
from flink_tpu.runtime.watchdog import (
    PROGRESS, StallError, TaskProgress, TaskStallDetector, WATCHDOG,
    stall_bounded,
)
from flink_tpu.runtime.writer import ForwardPartitioner, RecordWriter

pytestmark = pytest.mark.stall


@pytest.fixture(autouse=True)
def _clean_state():
    faults_mod.FAULTS.reset()
    WATCHDOG.reset()
    yield
    faults_mod.FAULTS.reset()
    WATCHDOG.reset()


# ---------------------------------------------------------------------------
# the supervised call
# ---------------------------------------------------------------------------

def test_fast_call_passes_through_value_and_exception():
    assert WATCHDOG.run("device.execute", lambda: 42) == 42
    with pytest.raises(ValueError, match="boom"):
        WATCHDOG.run("device.execute",
                     lambda: (_ for _ in ()).throw(ValueError("boom")))
    assert WATCHDOG.trips_total() == 0


def test_deadline_expiry_raises_typed_stall_error():
    wd0 = DEVICE_STATS.watchdog_trips
    with pytest.raises(StallError) as ei:
        WATCHDOG.run("device.execute", lambda: time.sleep(2.0),
                     deadline=0.02, scope="unit")
    assert ei.value.site == "device.execute"
    assert ei.value.deadline_s == 0.02
    assert WATCHDOG.trips["device.execute"] == 1
    assert DEVICE_STATS.watchdog_trips == wd0 + 1
    # the trip is in the bounded event log (REST exceptions surface)
    assert any(e["kind"] == "watchdog-stall"
               and e["site"] == "device.execute"
               for e in WATCHDOG.events)


def test_disabled_watchdog_and_zero_deadline_call_directly():
    WATCHDOG.enabled = False
    assert WATCHDOG.run("device.execute", lambda: "x", deadline=0.001) == "x"
    WATCHDOG.enabled = True
    # deadline 0 = unbounded: direct call on the caller's thread
    tid = WATCHDOG.run("rpc.send", lambda: threading.get_ident(),
                       deadline=0)
    assert tid == threading.get_ident()


def test_configure_adopts_per_site_deadlines():
    cfg = Configuration()
    cfg.set(WatchdogOptions.EXECUTE_TIMEOUT, 1.5)
    cfg.set(WatchdogOptions.TRANSFER_TIMEOUT, "250ms")
    cfg.set(WatchdogOptions.ENABLED, False)
    WATCHDOG.configure(cfg)
    assert WATCHDOG.deadline_for("device.execute") == 1.5
    assert WATCHDOG.deadline_for("transfer.h2d") == 0.25
    assert WATCHDOG.deadline_for("transfer.d2h") == 0.25
    assert not WATCHDOG.enabled
    WATCHDOG.reset()
    assert WATCHDOG.enabled
    assert WATCHDOG.deadline_for("device.execute") == 300.0


def test_on_stall_hook_runs_on_expiry():
    killed = []
    with pytest.raises(StallError):
        WATCHDOG.run("rpc.send", lambda: time.sleep(2.0),
                     deadline=0.02, on_stall=lambda: killed.append(1))
    assert killed == [1]


# ---------------------------------------------------------------------------
# !hang@MS fault injection
# ---------------------------------------------------------------------------

def test_hang_flag_parses_and_rejects_bad_values():
    r = FaultRule.parse("device.execute=once@2!hang@50")
    assert r.mode == "once" and r.at == 2 and r.hang_ms == 50
    r = FaultRule.parse("transfer.d2h=every@3!hang@10!persistent")
    assert r.hang_ms == 10 and not r.transient
    with pytest.raises(ValueError):
        FaultRule.parse("device.execute=always!hang@0")
    with pytest.raises(ValueError):
        FaultRule.parse("device.execute=always!hangup")


def test_hang_trip_sleeps_inline_without_watchdog():
    faults_mod.FAULTS.configure_spec("device.execute=once@1!hang@50")
    t0 = time.perf_counter()
    faults_mod.FAULTS.fire("device.execute")   # visit 1: sleeps, no raise
    dt = time.perf_counter() - t0
    assert dt >= 0.045
    t0 = time.perf_counter()
    faults_mod.FAULTS.fire("device.execute")   # visit 2: rule spent
    assert time.perf_counter() - t0 < 0.02
    snap = faults_mod.FAULTS.snapshot()
    assert snap["trips"]["device.execute"] == 1


def test_drop_site_hang_sleeps_and_reports_not_tripped():
    faults_mod.FAULTS.configure_spec("rpc.heartbeat=once@1!hang@40")
    t0 = time.perf_counter()
    assert faults_mod.FAULTS.check("rpc.heartbeat") is False
    assert time.perf_counter() - t0 >= 0.035


def test_abandoned_worker_never_executes_the_real_operation():
    """Exactly-once under stall-retry: after the watchdog abandons a
    hung attempt, the worker waking from its injected hang must NOT run
    the real (state-mutating) operation."""
    faults_mod.FAULTS.configure_spec("device.execute=always!hang@150")
    ran = []

    def op():
        faults_mod.FAULTS.fire("device.execute")
        ran.append(1)

    with pytest.raises(StallError):
        WATCHDOG.run("device.execute", op, deadline=0.02)
    time.sleep(0.35)  # let the abandoned worker wake and unwind
    assert ran == [], "abandoned worker executed the real operation"


def test_stall_bounded_retries_once_then_succeeds():
    faults_mod.FAULTS.configure_spec("transfer.h2d=once@1!hang@200")
    WATCHDOG.deadlines["transfer.h2d"] = 0.02
    r0 = DEVICE_STATS.retries
    out = stall_bounded("transfer.h2d", lambda: "ok", scope="unit")
    assert out == "ok"
    assert WATCHDOG.trips["transfer.h2d"] == 1
    assert DEVICE_STATS.retries == r0 + 1


def test_stall_bounded_persistent_hang_escalates():
    faults_mod.FAULTS.configure_spec("transfer.d2h=always!hang@200")
    WATCHDOG.deadlines["transfer.d2h"] = 0.02
    with pytest.raises(StallError):
        stall_bounded("transfer.d2h", lambda: "never", scope="unit")
    assert WATCHDOG.trips["transfer.d2h"] == 2  # attempt + one retry


# ---------------------------------------------------------------------------
# writer backpressure cap (satellite: writer.py unbounded spin)
# ---------------------------------------------------------------------------

def test_backpressure_stall_raises_instead_of_spinning_forever():
    from flink_tpu.core.records import RecordBatch, Schema

    ch = LocalChannel(capacity=1)
    w = RecordWriter([ch], ForwardPartitioner(), 0, put_timeout=0.02,
                     stall_timeout=0.08)
    schema = Schema([("x", np.int64)])
    batch = RecordBatch(schema, {"x": np.arange(3, dtype=np.int64)},
                        np.zeros(3, np.int64))
    w.emit(batch)  # fills the only slot; nothing drains it
    s0 = DEVICE_STATS.stall_detections
    t0 = time.perf_counter()
    with pytest.raises(StallError) as ei:
        w.emit(batch)
    assert ei.value.site == "channel.backpressure"
    assert 0.05 < time.perf_counter() - t0 < 5.0
    assert DEVICE_STATS.stall_detections == s0 + 1
    # never dropped: the blocked element was not silently discarded —
    # the queue still holds exactly the first batch
    assert ch.size() == 1


def test_backpressure_zero_timeout_keeps_unbounded_wait():
    ch = LocalChannel(capacity=1)
    w = RecordWriter([ch], ForwardPartitioner(), 0, put_timeout=0.01,
                     stall_timeout=0.0)
    ch.put("fill")
    cancel = threading.Event()
    w.cancel_event = cancel
    t = threading.Thread(target=lambda: (time.sleep(0.1), cancel.set()),
                         daemon=True)
    t.start()
    from flink_tpu.runtime.writer import WriterCancelled
    with pytest.raises(WriterCancelled):
        w._put_blocking(ch, "second")


# ---------------------------------------------------------------------------
# task-progress supervision
# ---------------------------------------------------------------------------

class _FakeTask:
    def __init__(self, pending=True):
        self.progress = TaskProgress()
        self.is_alive = True
        self._pending = pending
        self.cancelled = False

    def input_pending(self):
        return self._pending

    def cancel(self):
        self.cancelled = True


class _FakeJob:
    def __init__(self, tasks):
        self.tasks = tasks
        self.failure_history = deque(maxlen=64)
        self.failed_with = {}
        self._done = threading.Event()

    def task_failed(self, task_id, err):
        self.failed_with[task_id] = err


def test_detector_flags_stalled_task_with_queued_input():
    job = _FakeJob({"v1#0": _FakeTask(pending=True)})
    det = TaskStallDetector(job, stall_timeout=0.05)
    assert det.scan() == []            # first pass: baseline epoch
    time.sleep(0.07)
    assert det.scan() == ["v1#0"]      # stale epoch + queued input
    assert job.tasks["v1#0"].cancelled
    assert isinstance(job.failed_with["v1#0"], StallError)
    assert job.failure_history[-1]["kind"] == "stall-detected"
    # re-armed: the same stall is not spammed every pass
    assert det.scan() == []


def test_detector_ignores_progressing_and_idle_tasks():
    progressing = _FakeTask(pending=True)
    idle = _FakeTask(pending=False)
    job = _FakeJob({"p#0": progressing, "i#0": idle})
    det = TaskStallDetector(job, stall_timeout=0.05)
    det.scan()
    time.sleep(0.07)
    progressing.progress.bump()        # made progress: never flagged
    assert det.scan() == []            # idle one has no queued input
    time.sleep(0.07)
    assert det.scan() == ["p#0"]       # now genuinely stalled


def test_detector_disabled_by_zero_timeout():
    job = _FakeJob({"v#0": _FakeTask()})
    det = TaskStallDetector(job, stall_timeout=0.0).start()
    assert det._thread is None
    det.stop()


def test_progress_registry_reports_ages():
    p = TaskProgress()
    PROGRESS.register("unit#0", p)
    try:
        time.sleep(0.03)
        ages = PROGRESS.ages_ms()
        assert ages["unit#0"] >= 25.0
        p.bump()
        assert PROGRESS.ages_ms()["unit#0"] < 25.0
    finally:
        PROGRESS.unregister("unit#0")
    assert "unit#0" not in PROGRESS.ages_ms()


def test_stalled_pipeline_recovers_through_supervisor_restart():
    """End-to-end progress supervision: with the per-site watchdog OFF, a
    persistent-hang trip wedges the window task inline; the detector
    flags it (queued input, stale epoch), the supervisor restarts, the
    spent once@1 rule stays spent across the redeploy (injector
    fingerprint), and the job finishes exactly-once vs the oracle."""
    from flink_tpu.api.environment import StreamExecutionEnvironment
    from flink_tpu.core.config import (
        FaultOptions, PipelineOptions,
    )
    from flink_tpu.core.functions import SinkFunction
    from flink_tpu.core.records import Schema
    from flink_tpu.core.watermarks import WatermarkStrategy
    from flink_tpu.runtime.operators.device_window import AggSpec
    from flink_tpu.window import TumblingEventTimeWindows

    n, n_keys, pane = 1 << 11, 17, 1000

    class _RowSink(SinkFunction):
        def __init__(self):
            self.rows = []

        def invoke_batch(self, batch):
            self.rows.extend(batch.iter_rows())
            return True

    def gen(idx):
        return {"k": (idx * 5) % n_keys, "v": (idx % 11) + 1,
                "ts": (idx * 4 * pane) // n}

    schema = Schema([("k", np.int64), ("v", np.int64), ("ts", np.int64)])

    def run(armed: bool):
        env = StreamExecutionEnvironment()
        env.set_state_backend("tpu")
        env.config.set(PipelineOptions.BATCH_SIZE, 256)
        if armed:
            env.config.set(FaultOptions.ENABLED, True)
            env.config.set(FaultOptions.SEED, 0)
            env.config.set(FaultOptions.SPEC,
                           "device.execute=once@1!hang@1500")
            env.config.set(WatchdogOptions.ENABLED, False)   # inline hang
            env.config.set(WatchdogOptions.TASK_STALL_TIMEOUT, 0.15)
        ws = WatermarkStrategy.for_monotonous_timestamps() \
            .with_timestamp_column("ts")
        sink = _RowSink()
        (env.datagen(gen, schema, count=n, timestamp_column="ts",
                     watermark_strategy=ws)
            .key_by("k")
            .window(TumblingEventTimeWindows.of(pane))
            .device_aggregate(
                [AggSpec("count", out_name="cnt", value_bits=31),
                 AggSpec("sum", "v", out_name="total")],
                capacity=1 << 12, ring_size=8,
                emit_window_bounds=True, defer_overflow=True)
            .add_sink(sink, "sink"))
        env.execute("stall-recovery", timeout=60.0, recover=True)
        return env, sink

    # the armed run compiles nothing: the restarted job must finish
    # inside the first attempt's 1.5 s hang, because the wedged task of
    # the abandoned attempt is not fenced from the sink once it wakes
    # (ROADMAP "Tests")
    run(armed=False)
    env, sink = run(armed=True)

    kinds = [e.get("kind") for e in env.last_job.failure_history]
    assert "stall-detected" in kinds, kinds
    assert DEVICE_STATS.stall_detections > 0

    idx = np.arange(n)
    keys, vals, ts = (idx * 5) % n_keys, (idx % 11) + 1, (idx * 4 * pane) // n
    expect = {}
    for k, v, t in zip(keys, vals, ts):
        end = (int(t) // pane + 1) * pane
        c, s = expect.get((int(k), end), (0, 0))
        expect[(int(k), end)] = (c + 1, s + int(v))
    got = {}
    for k, _ws, we, cnt, total in sink.rows:
        assert (int(k), int(we)) not in got, "duplicate window emission"
        got[(int(k), int(we))] = (int(cnt), int(total))
    assert got == expect


# ---------------------------------------------------------------------------
# surfaces: REST exceptions, /metrics, checkpoint storage, bench probe
# ---------------------------------------------------------------------------

def test_watchdog_stall_events_reach_rest_exceptions():
    from flink_tpu.cluster.rest import RestEndpoint

    with pytest.raises(StallError):
        WATCHDOG.run("transfer.d2h", lambda: time.sleep(1.0),
                     deadline=0.02, scope="device_window")
    ep = RestEndpoint()
    job = SimpleNamespace(failure_history=[
        {"timestamp": time.time(), "kind": "task-failure", "error": "x"}])
    ep.register_job("j", job)
    entries = ep._exceptions("j")["entries"]
    kinds = [e["kind"] for e in entries]
    assert "watchdog-stall" in kinds and "task-failure" in kinds
    stall = next(e for e in entries if e["kind"] == "watchdog-stall")
    assert stall["site"] == "transfer.d2h"
    assert stall["scope"] == "device_window"


def test_stall_counters_reach_prometheus_and_snapshot():
    from flink_tpu.metrics.core import MetricRegistry
    from flink_tpu.metrics.device import bind_device_metrics
    from flink_tpu.metrics.reporters import prometheus_text

    reg = MetricRegistry()
    bind_device_metrics(reg)
    text = prometheus_text(reg)
    assert "flink_tpu_device_watchdog_trips_total" in text
    assert "flink_tpu_device_stall_detections_total" in text
    snap = DEVICE_STATS.snapshot()
    assert "watchdog_trips_total" in snap
    assert "stall_detections_total" in snap


def test_rest_metrics_snapshot_exposes_task_progress_age():
    from flink_tpu.cluster.rest import RestEndpoint

    PROGRESS.register("vx#0", TaskProgress())
    try:
        snap = RestEndpoint()._metrics_snapshot()
        assert "task.vx#0.last_progress_age_ms" in snap
    finally:
        PROGRESS.unregister("vx#0")


def test_checkpoint_store_stall_retries_then_tolerated():
    from flink_tpu.checkpoint.storage import (
        CompletedCheckpoint, MemoryCheckpointStorage,
    )

    storage = MemoryCheckpointStorage()
    cp = CompletedCheckpoint(1, time.time(), {})
    faults_mod.FAULTS.configure_spec("checkpoint.write=once@1!hang@200")
    WATCHDOG.deadlines["checkpoint.write"] = 0.02
    # one stall, one in-place retry, then the write lands
    assert storage.store(cp) is cp
    assert storage.load(1) is cp
    assert WATCHDOG.trips["checkpoint.write"] == 1
    # persistent hang: the store raises StallError, which the
    # coordinators tolerate exactly like any failed write
    faults_mod.FAULTS.configure_spec("checkpoint.write=always!hang@200")
    with pytest.raises(StallError):
        storage.store(CompletedCheckpoint(2, time.time(), {}))


def test_fs_checkpoint_load_is_stall_bounded(tmp_path):
    from flink_tpu.checkpoint.storage import (
        CompletedCheckpoint, FsCheckpointStorage,
    )

    storage = FsCheckpointStorage(str(tmp_path))
    cp = storage.store(CompletedCheckpoint(1, time.time(), {}))
    faults_mod.FAULTS.configure_spec("checkpoint.load=always!hang@200")
    WATCHDOG.deadlines["checkpoint.load"] = 0.02
    with pytest.raises(StallError):
        storage.load(cp.external_path)
    faults_mod.FAULTS.reset()
    assert storage.load(cp.external_path).checkpoint_id == 1
