"""Randomized crash-recovery property: exactly-once keyed state under
crashes injected at random points, across several seeds (the fault-
injection analog of the reference's process-kill ITCases, SURVEY §5.3 —
every trial exercises a different checkpoint/restore interleaving).

Extended (PR 2) with DETERMINISTIC injector-driven trials: faults
scheduled through runtime/faults.py at sink.invoke / channel.send /
checkpoint.write and at the device-path sites (transfer.h2d /
device.execute / transfer.d2h), asserting the same exactly-once keyed
results."""

import numpy as np
import pytest

from flink_tpu.api.environment import StreamExecutionEnvironment
from flink_tpu.cluster.scheduler import JobSupervisor
from flink_tpu.core.config import (
    CheckpointingOptions, FaultOptions, PipelineOptions, RuntimeOptions,
    StateOptions,
)
from flink_tpu.core.functions import SinkFunction
from flink_tpu.core.records import Schema
from flink_tpu.runtime import faults as faults_mod

SCHEMA = Schema([("k", np.int64), ("v", np.int64)])


@pytest.fixture(autouse=True)
def _clean_injector():
    faults_mod.FAULTS.reset()
    yield
    faults_mod.FAULTS.reset()


class _CrashingSink(SinkFunction):
    """Collects rows; raises ONCE when the configured threshold passes."""

    def __init__(self, crash_after: int):
        self.rows = []
        self.crash_after = crash_after
        self.tripped = False

    def invoke_batch(self, batch):
        self.rows.extend(batch.iter_rows())
        if not self.tripped and len(self.rows) > self.crash_after:
            self.tripped = True
            raise RuntimeError(f"injected crash at {len(self.rows)}")
        return True


@pytest.mark.parametrize("seed", range(5))
@pytest.mark.parametrize("backend", ["hashmap", "changelog"])
def test_exactly_once_across_random_crash_points(seed, backend):
    rng = np.random.default_rng(seed)
    n = int(rng.integers(1500, 4000))
    n_keys = int(rng.integers(3, 12))
    crash_after = int(rng.integers(50, max(100, n - 200)))
    interval = float(rng.choice([0.02, 0.05, 0.1]))
    batch = int(rng.choice([8, 32, 128]))

    keys = rng.integers(0, n_keys, size=n)
    vals = rng.integers(1, 100, size=n)

    env = StreamExecutionEnvironment()
    env.set_parallelism(int(rng.integers(1, 3)))
    env.config.set(PipelineOptions.BATCH_SIZE, batch)
    env.config.set(StateOptions.BACKEND, backend)
    env.config.set(CheckpointingOptions.INTERVAL, interval)
    env.config.set(RuntimeOptions.RESTART_STRATEGY, "fixed-delay")
    env.config.set(RuntimeOptions.RESTART_ATTEMPTS, 10)
    env.config.set(RuntimeOptions.RESTART_DELAY, 0.02)

    sink = _CrashingSink(crash_after)
    rows = [(int(k), int(v)) for k, v in zip(keys, vals)]
    ds = env.from_collection(rows, SCHEMA, timestamps=list(range(n)))
    ds.key_by("k").sum(1).add_sink(sink, "sink")
    jg = env.get_job_graph(f"crash-{backend}-{seed}")
    sup = JobSupervisor(jg, env.config)
    sup.run(timeout=120.0)
    assert sup.attempt >= 2, "crash never triggered a restart"

    totals: dict[int, int] = {}
    for k, v in sink.rows:
        totals[k] = max(totals.get(k, 0), v)
    expect: dict[int, int] = {}
    for k, v in zip(keys, vals):
        expect[int(k)] = expect.get(int(k), 0) + int(v)
    assert totals == expect, (seed, backend, n, crash_after, interval,
                              batch)


class _CollectingSink(SinkFunction):
    def __init__(self):
        self.rows = []

    def invoke_batch(self, batch):
        self.rows.extend(batch.iter_rows())
        return True


def _run_keyed_sum_with_faults(seed: int, spec: str) -> JobSupervisor:
    """Keyed running-sum pipeline under an injector schedule; asserts
    exactly-once totals (max-dedup absorbs restart replays) and returns
    the supervisor for trial-specific assertions."""
    rng = np.random.default_rng(seed)
    n = 1500
    keys = rng.integers(0, 7, n)
    vals = rng.integers(1, 100, n)
    env = StreamExecutionEnvironment()
    env.config.set(PipelineOptions.BATCH_SIZE, 64)
    env.config.set(CheckpointingOptions.INTERVAL, 0.05)
    env.config.set(RuntimeOptions.RESTART_STRATEGY, "fixed-delay")
    env.config.set(RuntimeOptions.RESTART_ATTEMPTS, 10)
    env.config.set(RuntimeOptions.RESTART_DELAY, 0.02)
    env.config.set(FaultOptions.ENABLED, True)
    env.config.set(FaultOptions.SEED, seed)
    env.config.set(FaultOptions.SPEC, spec)
    sink = _CollectingSink()
    rows = [(int(k), int(v)) for k, v in zip(keys, vals)]
    ds = env.from_collection(rows, SCHEMA, timestamps=list(range(n)))
    ds.key_by("k").sum(1).add_sink(sink, "sink")
    sup = JobSupervisor(env.get_job_graph(f"inj-{seed}"), env.config)
    sup.run(timeout=120.0)
    totals = {}
    for k, v in sink.rows:
        totals[k] = max(totals.get(k, 0), v)
    expect: dict[int, int] = {}
    for k, v in zip(keys, vals):
        expect[int(k)] = expect.get(int(k), 0) + int(v)
    assert totals == expect, (seed, spec)
    return sup


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_exactly_once_with_injected_sink_fault(seed):
    """A persistent sink.invoke fault fails the task once; the supervisor
    restores from the latest checkpoint and keyed results stay exact."""
    sup = _run_keyed_sum_with_faults(
        seed, f"sink.invoke=once@{3 + seed}!persistent")
    assert sup.attempt >= 2, "injected sink fault never caused a restart"
    assert any(e["kind"] == "restart" for e in sup.failure_history)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_exactly_once_with_injected_channel_fault(seed):
    sup = _run_keyed_sum_with_faults(
        seed, f"channel.send=once@{4 + seed}!persistent")
    assert sup.attempt >= 2


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_checkpoint_write_fault_is_tolerated(seed):
    """A failed checkpoint WRITE aborts that checkpoint but must not fail
    the job: the run completes in one attempt with exact results and the
    coordinator records the failed store."""
    sup = _run_keyed_sum_with_faults(
        seed, f"checkpoint.write=once@{1 + seed}!persistent")
    assert sup.attempt == 1
    trips = faults_mod.FAULTS.snapshot()["trips"]
    if trips.get("checkpoint.write"):  # the schedule reached a store
        assert any(s.get("failed") for s in sup.coordinator.stats)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_device_pipeline_exactly_once_with_transfer_and_execute_faults(seed):
    """Device window pipeline with transient faults at transfer.h2d,
    device.execute, transfer.d2h and a tolerated checkpoint.write trip:
    every retry is absorbed in place, emitted windows match the oracle
    exactly, and no restart is needed."""
    from flink_tpu.core.watermarks import WatermarkStrategy
    from flink_tpu.runtime.operators.device_window import AggSpec
    from flink_tpu.window import TumblingEventTimeWindows

    n, n_keys, pane = 1 << 12, 23, 1000
    env = StreamExecutionEnvironment()
    env.set_state_backend("tpu")
    env.config.set(PipelineOptions.BATCH_SIZE, 512)
    env.config.set(CheckpointingOptions.INTERVAL, 0.05)
    env.config.set(FaultOptions.ENABLED, True)
    env.config.set(FaultOptions.SEED, seed)
    env.config.set(FaultOptions.SPEC,
                   "transfer.h2d=p0.05,device.execute=p0.05,"
                   "transfer.d2h=p0.05,checkpoint.write=once@1")

    def gen(idx):
        return {"k": (idx * 11) % n_keys, "v": (idx % 13) + 1,
                "ts": (idx * 6 * pane) // n}

    schema = Schema([("k", np.int64), ("v", np.int64), ("ts", np.int64)])
    ws = WatermarkStrategy.for_monotonous_timestamps() \
        .with_timestamp_column("ts")
    sink = _CollectingSink()
    (env.datagen(gen, schema, count=n, timestamp_column="ts",
                 watermark_strategy=ws)
        .key_by("k")
        .window(TumblingEventTimeWindows.of(pane))
        .device_aggregate([AggSpec("count", out_name="cnt",
                                   value_bits=31),
                           AggSpec("sum", "v", out_name="total")],
                          capacity=1 << 12, ring_size=8,
                          emit_window_bounds=True, defer_overflow=True)
        .add_sink(sink, "sink"))
    env.execute(f"device-faults-{seed}", timeout=120.0)

    idx = np.arange(n)
    keys, vals = (idx * 11) % n_keys, (idx % 13) + 1
    ts = (idx * 6 * pane) // n
    expect: dict = {}
    for k, v, t in zip(keys, vals, ts):
        end = (int(t) // pane + 1) * pane
        c, s = expect.get((int(k), end), (0, 0))
        expect[(int(k), end)] = (c + 1, s + int(v))
    got = {}
    for k, _ws, we, cnt, total in sink.rows:
        assert (int(k), int(we)) not in got, "duplicate window emission"
        got[(int(k), int(we))] = (int(cnt), int(total))
    assert got == expect, seed
