"""Deterministic chaos trials: fault injection at every device-path site
with exactly-once results asserted against a numpy oracle, forced
mid-stream degradation vs a clean run, and dead-letter quarantine
accounting. All fast enough for tier-1 (the `chaos` marker selects them
for dedicated runs; `python bench.py --chaos SEED` drives the same
schedule through the full tiny-Q5 stage)."""

import time

import jax.numpy as jnp
import numpy as np
import pytest

from flink_tpu.core.config import (
    CheckpointingOptions, Configuration, FaultOptions, PipelineOptions,
)
from flink_tpu.core.device_records import DeviceRecordBatch
from flink_tpu.core.functions import SinkFunction
from flink_tpu.core.records import RecordBatch, Schema
from flink_tpu.metrics.device import DEVICE_STATS
from flink_tpu.runtime import faults as faults_mod
from flink_tpu.runtime.harness import OneInputOperatorTestHarness
from flink_tpu.runtime.operators.device_window import (
    AggSpec, DeviceWindowAggOperator,
)

pytestmark = pytest.mark.chaos

SCHEMA = Schema([("k", np.int64), ("v", np.int64)])
PANE = 1000


@pytest.fixture(autouse=True)
def _clean_injector():
    from flink_tpu.runtime.watchdog import WATCHDOG

    faults_mod.FAULTS.reset()
    WATCHDOG.reset()
    yield
    faults_mod.FAULTS.reset()
    WATCHDOG.reset()


def _chaos_config(spec: str, seed: int = 0) -> Configuration:
    cfg = Configuration()
    if spec:
        cfg.set(FaultOptions.ENABLED, True)
        cfg.set(FaultOptions.SEED, seed)
        cfg.set(FaultOptions.SPEC, spec)
    return cfg


def _make_op(**kw) -> DeviceWindowAggOperator:
    from flink_tpu.window import TumblingEventTimeWindows

    return DeviceWindowAggOperator(
        TumblingEventTimeWindows.of(PANE), "k",
        [AggSpec("count", out_name="cnt", value_bits=31),
         AggSpec("sum", "v", out_name="total")],
        capacity=1 << 12, ring_size=8, emit_window_bounds=True, **kw)


def _device_batch(keys, vals, ts) -> DeviceRecordBatch:
    cols = {"k": jnp.asarray(keys), "v": jnp.asarray(vals)}
    return DeviceRecordBatch(SCHEMA, cols, jnp.asarray(ts),
                             int(ts.min()), int(ts.max()))


def _gen(seed: int, n: int, n_keys: int = 13):
    rng = np.random.default_rng(seed)
    keys = rng.integers(0, n_keys, n).astype(np.int64)
    vals = rng.integers(1, 50, n).astype(np.int64)
    ts = np.sort(rng.integers(0, 6 * PANE, n)).astype(np.int64)
    return keys, vals, ts


def _expected(keys, vals, ts, skip=()) -> dict:
    """Oracle: per (key, window_end) count/sum; ``skip`` masks rows that
    the run quarantined on purpose."""
    out: dict = {}
    for i, (k, v, t) in enumerate(zip(keys, vals, ts)):
        if i in skip:
            continue
        end = (int(t) // PANE + 1) * PANE
        c, s = out.get((int(k), end), (0, 0))
        out[(int(k), end)] = (c + 1, s + int(v))
    return out


def _run_device_trial(spec: str, seed: int, data_seed: int = 0,
                      batches: int = 6, batch_n: int = 256,
                      config: Configuration = None,
                      device_batches: bool = True, defer: bool = None):
    """Drive the device window operator through the harness; returns
    (emitted dict, operator, raw data)."""
    from flink_tpu.runtime.watchdog import WATCHDOG

    cfg = config if config is not None else _chaos_config(spec, seed)
    op = _make_op(defer_overflow=device_batches if defer is None else defer)
    h = OneInputOperatorTestHarness(op, SCHEMA, config=cfg)
    faults_mod.FAULTS.configure(cfg)
    WATCHDOG.configure(cfg)  # harness path: adopt deadlines like deploy does
    keys, vals, ts = _gen(data_seed, batches * batch_n)
    for b in range(batches):
        sl = slice(b * batch_n, (b + 1) * batch_n)
        if device_batches:
            h.process_batch(_device_batch(keys[sl], vals[sl], ts[sl]))
        else:
            h.process_batch(RecordBatch(
                SCHEMA, {"k": keys[sl], "v": vals[sl]}, ts[sl]))
        h.process_watermark(int(ts[sl][-1]) - PANE)
    h.process_watermark(1 << 40)
    h.close()
    got = {}
    for row in h.get_output():
        k, ws, we, cnt, total = row
        assert (k, we) not in got, "window emitted twice (not exactly-once)"
        got[(k, we)] = (int(cnt), int(total))
    return got, op, h, (keys, vals, ts)


# ---------------------------------------------------------------------------
# chaos smoke: every device-path site armed, exactly-once results
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", [0, 1, 2])
def test_exactly_once_with_all_device_sites_armed(seed):
    """Transient/bounded faults at device.compile, device.execute,
    transfer.h2d, transfer.d2h: results must match the oracle exactly —
    every trip is absorbed by retry, never by dropping or double-folding
    data."""
    spec = ("device.compile=once@1,device.execute=p0.1,"
            "transfer.h2d=p0.1,transfer.d2h=p0.1")
    got, op, h, (keys, vals, ts) = _run_device_trial(spec, seed)
    assert got == _expected(keys, vals, ts)
    assert not op._degraded
    snap = faults_mod.FAULTS.snapshot()
    assert sum(snap["trips"].values()) > 0, "chaos run injected nothing"


def test_chaos_counters_reach_prometheus():
    """The acceptance surface: device_retries_total /
    device_degraded_total / dead_letter_records_total appear in the
    /metrics exposition and move under injection."""
    from flink_tpu.metrics.core import MetricRegistry
    from flink_tpu.metrics.device import bind_device_metrics
    from flink_tpu.metrics.reporters import prometheus_text

    before = DEVICE_STATS.retries
    _run_device_trial("device.execute=p0.2,transfer.d2h=p0.2", seed=5)
    assert DEVICE_STATS.retries > before
    reg = MetricRegistry()
    bind_device_metrics(reg)
    text = prometheus_text(reg)
    for name in ("device_retries_total", "device_degraded_total",
                 "dead_letter_records_total", "injected_faults_total"):
        assert name in text, f"{name} missing from /metrics"
    snap = DEVICE_STATS.snapshot()
    assert snap["device_retries_total"] == DEVICE_STATS.retries


# ---------------------------------------------------------------------------
# degradation ladder: persistent failure -> evacuate -> synchronous fallback
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("device_batches", [True, False])
def test_forced_degradation_matches_clean_run(device_batches):
    """Mid-stream persistent device.execute failure: the operator
    evacuates state through the snapshot path and finishes on the CPU
    fallback — emitted windows must be IDENTICAL to a fault-free run
    (no lost keyed state, no duplicate fires)."""
    clean, op0, _h0, data = _run_device_trial(
        "", seed=0, device_batches=device_batches)
    assert not op0._degraded
    faults_mod.FAULTS.reset()
    d0 = DEVICE_STATS.degraded
    got, op, _h, _ = _run_device_trial(
        "device.execute=once@2!persistent", seed=0,
        device_batches=device_batches)
    assert op._degraded, "persistent fault never degraded the operator"
    assert DEVICE_STATS.degraded == d0 + 1
    assert got == clean
    keys, vals, ts = data
    assert got == _expected(keys, vals, ts)


def test_degradation_disabled_propagates():
    cfg = _chaos_config("device.execute=once@1!persistent", seed=0)
    cfg.set(FaultOptions.DEGRADATION, False)
    with pytest.raises(Exception) as ei:
        _run_device_trial("", seed=0, config=cfg)
    assert "device segment" in str(ei.value)


# ---------------------------------------------------------------------------
# dead-letter quarantine
# ---------------------------------------------------------------------------

def test_poison_fault_quarantines_batch_not_state():
    """A poison trip on the 3rd step dispatch: that batch rides the
    dead-letter counter, every other batch folds normally, and state is
    never poisoned (results match the oracle minus the quarantined
    rows)."""
    dl0 = DEVICE_STATS.dead_letter_records
    batches, batch_n = 6, 256
    got, op, h, (keys, vals, ts) = _run_device_trial(
        "device.execute=once@3!poison", seed=0,
        batches=batches, batch_n=batch_n)
    assert op.quarantined_batches == 1
    assert DEVICE_STATS.dead_letter_records == dl0 + batch_n
    skip = set(range(2 * batch_n, 3 * batch_n))  # the 3rd batch
    assert got == _expected(keys, vals, ts, skip=skip)
    assert not op._degraded


def test_validate_batches_quarantines_nonfinite_rows():
    """faults.validate-batches: NaN rows in a float aggregate column are
    diverted to the dead-letter side output instead of poisoning the sum
    plane."""
    schema = Schema([("k", np.int64), ("x", np.float64)])
    from flink_tpu.window import TumblingEventTimeWindows

    cfg = Configuration()
    cfg.set(FaultOptions.VALIDATE_BATCHES, True)
    op = DeviceWindowAggOperator(
        TumblingEventTimeWindows.of(PANE), "k",
        [AggSpec("sum", "x", out_name="sx")],
        capacity=1 << 10, ring_size=8, emit_window_bounds=False)
    h = OneInputOperatorTestHarness(op, schema, config=cfg)
    dl0 = DEVICE_STATS.dead_letter_records
    keys = np.array([1, 1, 2, 2], np.int64)
    xs = np.array([1.0, np.nan, 2.0, np.inf], np.float64)
    ts = np.array([10, 20, 30, 40], np.int64)
    h.process_batch(RecordBatch(schema, {"k": keys, "x": xs}, ts))
    h.process_watermark(1 << 40)
    h.close()
    assert DEVICE_STATS.dead_letter_records == dl0 + 2
    rows = {r[0]: r[1] for r in h.get_output()}
    assert rows == {1: 1.0, 2: 2.0}
    # the poisoned rows surface on the dead-letter side output
    assert len(h.get_side_output("dead-letter")) == 2


# ---------------------------------------------------------------------------
# stall chaos: !hang injection at every watchdog site (PR 3)
# ---------------------------------------------------------------------------

#: which WatchdogOptions deadline guards each injected site — the test
#: tightens ONLY the site under trial: real work at the other sites (XLA
#: compiles inside a first dispatch, bulk restore captures) must keep
#: their generous defaults or it would stall spuriously
_SITE_DEADLINE_KEY = {
    "device.compile": "watchdog.device.compile-timeout",
    "device.execute": "watchdog.device.execute-timeout",
    "transfer.h2d": "watchdog.transfer-timeout",
    "transfer.d2h": "watchdog.transfer-timeout",
}


def _tight_watchdog(cfg: Configuration, site: str,
                    deadline: float = 0.015) -> Configuration:
    """Tiny deadline for the site under trial so <=50ms injected hangs
    trip the watchdog (tier-1 fast: a stall costs one deadline, not a
    wall-clock hang)."""
    cfg.set(_SITE_DEADLINE_KEY[site], deadline)
    return cfg


@pytest.mark.stall
@pytest.mark.parametrize("site,device_batches,defer", [
    ("device.compile", True, True),
    ("device.execute", True, True),
    # host batches + deferred fold: the ONE packed upload is the h2d site
    ("transfer.h2d", False, True),
    ("transfer.d2h", True, True),
])
@pytest.mark.parametrize("seed", [0, 1])
def test_single_hang_at_each_watchdog_site_is_absorbed(site, device_batches,
                                                       defer, seed):
    """One injected hang at each supervised device-path site: the
    watchdog abandons the stalled attempt, the stall retries in place
    (transient rung of the ladder), and results stay exactly-once —
    deterministic across seeds (once@N schedules are seed-independent;
    the seed exercises the replay contract)."""
    if site != "device.compile":
        # warm the program caches: a tight per-site deadline must see ONLY
        # the injected hang, not a real first-dispatch XLA compile (the
        # compile trial needs cold caches — its site IS the builder)
        _run_device_trial("", seed=seed, device_batches=device_batches,
                          defer=defer)
        faults_mod.FAULTS.reset()
        from flink_tpu.runtime.watchdog import WATCHDOG
        WATCHDOG.reset()
    else:
        # cold caches regardless of test order: the builder IS the site
        from flink_tpu.runtime.operators import device_window as dw
        for builder in (dw._step_program, dw._fire_program):
            builder.cache_clear()
    wd0 = DEVICE_STATS.watchdog_trips
    cfg = _tight_watchdog(_chaos_config(f"{site}=once@2!hang@40", seed),
                          site)
    got, op, h, (keys, vals, ts) = _run_device_trial(
        "", seed=seed, config=cfg, device_batches=device_batches,
        defer=defer)
    assert got == _expected(keys, vals, ts)
    assert not op._degraded, "a single stall must retry, not degrade"
    assert DEVICE_STATS.watchdog_trips > wd0, "hang never tripped watchdog"
    snap = faults_mod.FAULTS.snapshot()
    assert snap["trips"].get(site) == 1


@pytest.mark.stall
def test_persistent_execute_hang_degrades_to_cpu_fallback():
    """The acceptance trial: with !hang injected persistently at
    device.execute, repeated stalls exhaust the guard's retries and the
    operator degrades to the CPU fallback within the configured deadline
    budget — producing byte-identical exactly-once results vs a clean
    run, with watchdog_trips_total > 0 and a stall event on the REST
    exceptions surface."""
    from flink_tpu.cluster.rest import RestEndpoint
    from flink_tpu.core.config import FaultOptions
    from flink_tpu.runtime.watchdog import WATCHDOG
    from types import SimpleNamespace

    clean, op0, _h0, data = _run_device_trial("", seed=0)
    assert not op0._degraded
    faults_mod.FAULTS.reset()
    WATCHDOG.reset()
    d0 = DEVICE_STATS.degraded
    wd0 = DEVICE_STATS.watchdog_trips
    cfg = _tight_watchdog(_chaos_config("device.execute=always!hang@40", 0),
                          "device.execute")
    cfg.set(FaultOptions.DEVICE_MAX_RETRIES, 2)
    t0 = time.perf_counter()
    got, op, _h, _ = _run_device_trial("", seed=0, config=cfg)
    wall = time.perf_counter() - t0
    assert op._degraded, "persistent stalls never degraded the operator"
    assert op._guard.stalls >= 3          # initial attempt + 2 retries
    assert DEVICE_STATS.degraded == d0 + 1
    assert DEVICE_STATS.watchdog_trips > wd0
    assert got == clean
    keys, vals, ts = data
    assert got == _expected(keys, vals, ts)
    # deadline budget: 3 attempts x 15ms deadlines + backoff, not the
    # 40ms-per-visit hang schedule run to completion
    assert wall < 30.0
    # the stall events ride /jobs/<id>/exceptions
    ep = RestEndpoint()
    ep.register_job("chaos", SimpleNamespace(failure_history=[]))
    kinds = [e["kind"] for e in ep._exceptions("chaos")["entries"]]
    assert "watchdog-stall" in kinds


@pytest.mark.stall
@pytest.mark.parametrize("seed", [3, 5])
def test_tiny_q5_pipeline_exactly_once_with_hang_injection(seed):
    """Whole-pipeline stall chaos (what `bench.py --chaos` drives): a
    bounded d2h hang schedule under a tight transfer deadline — every
    stall is absorbed by the watchdog retry and the emitted stream stays
    exactly-once, deterministically per seed."""
    from flink_tpu.api.environment import StreamExecutionEnvironment
    from flink_tpu.core.config import WatchdogOptions
    from flink_tpu.core.watermarks import WatermarkStrategy
    from flink_tpu.window import TumblingEventTimeWindows

    n, n_keys = 1 << 11, 23
    spec = ("device.execute=once@3!hang@40,transfer.d2h=every@4!hang@40,"
            "channel.send=once@2")

    def gen(idx):
        return {"k": (idx * 3) % n_keys, "v": (idx % 13) + 1,
                "ts": (idx * 5 * PANE) // n}

    schema = Schema([("k", np.int64), ("v", np.int64), ("ts", np.int64)])
    env = StreamExecutionEnvironment()
    env.set_state_backend("tpu")
    env.config.set(PipelineOptions.BATCH_SIZE, 256)
    env.config.set(FaultOptions.ENABLED, True)
    env.config.set(FaultOptions.SEED, seed)
    env.config.set(FaultOptions.SPEC, spec)
    env.config.set(WatchdogOptions.EXECUTE_TIMEOUT, 0.015)
    env.config.set(WatchdogOptions.TRANSFER_TIMEOUT, 0.015)
    ws = WatermarkStrategy.for_monotonous_timestamps() \
        .with_timestamp_column("ts")
    sink = _RowSink()
    (env.datagen(gen, schema, count=n, timestamp_column="ts",
                 watermark_strategy=ws)
        .key_by("k")
        .window(TumblingEventTimeWindows.of(PANE))
        .device_aggregate([AggSpec("count", out_name="cnt", value_bits=31),
                           AggSpec("sum", "v", out_name="total")],
                          capacity=1 << 12, ring_size=8,
                          emit_window_bounds=True, defer_overflow=True)
        .add_sink(sink, "sink"))
    env.execute(f"tiny-q5-stall-{seed}", timeout=60.0)

    idx = np.arange(n)
    expect = _expected((idx * 3) % n_keys, (idx % 13) + 1,
                       (idx * 5 * PANE) // n)
    got = {}
    for k, _ws, we, cnt, total in sink.rows:
        assert (int(k), int(we)) not in got, "duplicate window emission"
        got[(int(k), int(we))] = (int(cnt), int(total))
    assert got == expect, f"seed {seed}: results diverged under stalls"
    assert DEVICE_STATS.watchdog_trips > 0


# ---------------------------------------------------------------------------
# whole-pipeline chaos: tiny Q5-shaped job, every site armed, 3 seeds
# ---------------------------------------------------------------------------

class _RowSink(SinkFunction):
    def __init__(self):
        self.rows = []

    def invoke_batch(self, batch):
        self.rows.extend(batch.iter_rows())
        return True


_Q5_N, _Q5_KEYS = 1 << 12, 37
_EVERY_SITE = ("device.compile=once@1,device.execute=p0.03,"
               "transfer.h2d=p0.03,transfer.d2h=p0.03,"
               "channel.send=once@2,channel.backpressure=every@13,"
               "checkpoint.write=once@1,sink.invoke=once@2,"
               "rpc.heartbeat=every@5")


def _tiny_q5_job(name: str, spec: str, seed: int, device: bool) -> dict:
    """One env.execute() of the tiny Q5-shaped pipeline (datagen ->
    keyBy -> device tumbling aggregate -> sink) with ``spec`` armed;
    returns {(key, window_end): (count, sum)}."""
    from flink_tpu.api.environment import StreamExecutionEnvironment
    from flink_tpu.core.watermarks import WatermarkStrategy
    from flink_tpu.window import TumblingEventTimeWindows

    def gen(idx):
        return {"k": (idx * 7) % _Q5_KEYS,
                "v": (idx % 19) + 1,
                "ts": (idx * 6 * PANE) // _Q5_N}

    schema = Schema([("k", np.int64), ("v", np.int64), ("ts", np.int64)])
    env = StreamExecutionEnvironment()
    env.set_state_backend("tpu")
    env.config.set(PipelineOptions.BATCH_SIZE, 512)
    env.config.set(CheckpointingOptions.INTERVAL, 0.05)
    if spec:
        env.config.set(FaultOptions.ENABLED, True)
        env.config.set(FaultOptions.SEED, seed)
        env.config.set(FaultOptions.SPEC, spec)
    ws = WatermarkStrategy.for_monotonous_timestamps() \
        .with_timestamp_column("ts")
    sink = _RowSink()
    (env.datagen(gen, schema, count=_Q5_N, timestamp_column="ts",
                 watermark_strategy=ws, device=device)
        .key_by("k")
        .window(TumblingEventTimeWindows.of(PANE))
        .device_aggregate([AggSpec("count", out_name="cnt", value_bits=31),
                           AggSpec("sum", "v", out_name="total")],
                          capacity=1 << 12, ring_size=8,
                          emit_window_bounds=True, defer_overflow=True)
        .add_sink(sink, "sink"))
    env.execute(name, timeout=120.0)
    got = {}
    for k, _ws, we, cnt, total in sink.rows:
        assert (int(k), int(we)) not in got, "duplicate window emission"
        got[(int(k), int(we))] = (int(cnt), int(total))
    return got


@pytest.mark.parametrize("seed,spec,device", [
    (7, _EVERY_SITE, False), (11, _EVERY_SITE, False),
    (13, _EVERY_SITE, False),
    (0, "device.execute=once@2!persistent", True)],
    ids=["7", "11", "13", "degraded"])
def test_tiny_q5_pipeline_exactly_once_under_chaos(seed, spec, device):
    """The acceptance trial: the tiny Q5-shaped pipeline completes with
    exactly-once results with faults armed at every named site. Those
    schedules are transient/bounded so recovery happens IN PLACE (retry
    / injected backpressure / tolerated checkpoint-write failure), which
    keeps the emitted stream free of restart replays. The ``degraded``
    case injects ONE persistent fault into the second step of a
    device-born job: the operator evacuates its state mid-stream and
    finishes on the synchronous fallback (device batches read back as
    host columns), with the rows of the job that never degraded."""
    degraded0 = DEVICE_STATS.degraded
    got = _tiny_q5_job(f"tiny-q5-chaos-{seed}", spec, seed, device)
    idx = np.arange(_Q5_N)
    expect = _expected((idx * 7) % _Q5_KEYS, (idx % 19) + 1,
                       (idx * 6 * PANE) // _Q5_N)
    assert got == expect, f"seed {seed}: results diverged under chaos"
    assert DEVICE_STATS.injected_faults > 0
    if "!persistent" in spec:
        assert DEVICE_STATS.degraded == degraded0 + 1
        faults_mod.FAULTS.reset()
        assert got == _tiny_q5_job("tiny-q5-clean", "", 0, device)
        assert DEVICE_STATS.degraded == degraded0 + 1
    else:
        assert DEVICE_STATS.degraded == degraded0


# ---------------------------------------------------------------------------
# network partition drills: severed cross-host edges (PR 6)
# ---------------------------------------------------------------------------

def _two_host_sever_trial(spec: str, reconnect_timeout: float,
                          checkpoint_interval: float = 0.0):
    """Two DistributedHosts in-process with net.* faults armed; returns
    (sink rows, coordinator) after both run loops exit."""
    import threading

    from flink_tpu.api.environment import StreamExecutionEnvironment
    from flink_tpu.cluster.distributed import DistributedHost
    from flink_tpu.connectors.core import CollectSink
    from flink_tpu.core.config import NetworkOptions, RuntimeOptions

    sinks = [CollectSink(), CollectSink()]
    graphs = []
    for h in range(2):
        env = StreamExecutionEnvironment()
        env.set_parallelism(2)
        env.config.set(PipelineOptions.BATCH_SIZE, 16)
        env.config.set(FaultOptions.ENABLED, True)
        env.config.set(FaultOptions.SEED, 0)
        env.config.set(FaultOptions.SPEC, spec)
        env.config.set(NetworkOptions.RECONNECT_TIMEOUT, reconnect_timeout)
        env.config.set(NetworkOptions.RECONNECT_BACKOFF, 0.01)
        # small heartbeat -> small restart grace window (the coordinator
        # waits out hb_timeout before redeploying)
        env.config.set(RuntimeOptions.HEARTBEAT_INTERVAL, 0.05)
        if checkpoint_interval:
            env.config.set(CheckpointingOptions.INTERVAL,
                           checkpoint_interval)
            env.config.set(RuntimeOptions.RESTART_STRATEGY, "fixed-delay")
            env.config.set(RuntimeOptions.RESTART_ATTEMPTS, 3)
            env.config.set(RuntimeOptions.RESTART_DELAY, 0.05)
        n = 200
        rows = [(i % 10, i) for i in range(n)]
        ds = env.from_collection(rows, SCHEMA, timestamps=list(range(n)))
        ds.key_by("k").sum(1).add_sink(sinks[h], "sink")
        graphs.append(env.get_job_graph("net-chaos"))

    h0 = DistributedHost(graphs[0], graphs[0].config, 0, 2)
    h1 = DistributedHost(graphs[1], graphs[1].config, 1, 2,
                         coordinator_addr=f"127.0.0.1:"
                         f"{h0.coordinator.port}")
    peers = {0: h0.data_address, 1: h1.data_address}
    threads = [threading.Thread(target=h.run, args=(peers,),
                                kwargs={"timeout": 90}, daemon=True)
               for h in (h1, h0)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(110)
        assert not t.is_alive(), "host wedged under network chaos"
    coord = h0.coordinator
    h0.close()
    h1.close()
    return sinks[0].rows + sinks[1].rows, coord


@pytest.mark.netfault
def test_severed_data_channels_heal_without_restart():
    """The acceptance drill: net.sever kills every cross-host connection
    repeatedly mid-stream — the channels reconnect and replay under the
    deadline, results stay exactly-once, network_reconnects_total moves,
    and the restart counter NEVER does (a healed partition is not a
    failover)."""
    r0 = DEVICE_STATS.net_reconnects
    rows, coord = _two_host_sever_trial("net.sever=every@7",
                                        reconnect_timeout=10.0)
    assert coord.restarts == 0, "a healed sever must not restart regions"
    assert coord.failed is None
    assert DEVICE_STATS.net_reconnects > r0
    assert len(rows) == 200
    finals = {}
    for k, v in rows:
        finals[k] = max(finals.get(k, 0), v)
    assert finals == {k: sum(i for i in range(200) if i % 10 == k)
                      for k in range(10)}


@pytest.mark.netfault
def test_sever_with_zero_deadline_escalates_to_one_restart():
    """Forcing net.reconnect-timeout to 0 turns the SAME sever into a
    StallError that rides the existing ladder: exactly one region
    restart, and the job still completes exactly-once."""
    rows, coord = _two_host_sever_trial("net.sever=once@9",
                                        reconnect_timeout=0.0,
                                        checkpoint_interval=0.1)
    assert coord.restarts == 1, "deadline-0 sever must restart exactly once"
    assert coord.failed is None
    finals = {}
    for k, v in rows:
        finals[k] = max(finals.get(k, 0), v)
    assert finals == {k: sum(i for i in range(200) if i % 10 == k)
                      for k in range(10)}
