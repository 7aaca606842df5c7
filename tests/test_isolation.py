"""Multi-tenant isolation drills (cluster/isolation.py): weighted
deficit-round-robin admission, per-job bulkheads and circuit breakers,
and overload shedding — capped by the acceptance drill: a poisoned AND
hung tenant runs concurrently with a healthy one, and the healthy
tenant's output is byte-identical to its solo run with zero restarts,
zero recompiles, and none of the hostile tenant's damage on its
job-scoped surfaces. All count-based (TPU501): the breaker/shed
counters replay identically across fault seeds."""

import threading

import numpy as np
import pytest

from flink_tpu.core.config import (
    Configuration, FaultOptions, IsolationOptions, PipelineOptions,
    ProfilerOptions, WatchdogOptions,
)
from flink_tpu.core.functions import SinkFunction
from flink_tpu.core.records import Schema
from flink_tpu.cluster.isolation import ISOLATION
from flink_tpu.metrics.profiler import (
    DEVICE_LEDGER, dispatch_context, set_dispatch_context,
)
from flink_tpu.metrics.tracing import FLIGHT_RECORDER
from flink_tpu.runtime import faults as faults_mod
from flink_tpu.runtime.watchdog import WATCHDOG

pytestmark = pytest.mark.isolation

PANE = 1000


@pytest.fixture(autouse=True)
def _clean_singletons():
    for s in (faults_mod.FAULTS, WATCHDOG, ISOLATION, DEVICE_LEDGER,
              FLIGHT_RECORDER):
        s.reset()
    set_dispatch_context("", "")
    yield
    for s in (faults_mod.FAULTS, WATCHDOG, ISOLATION, DEVICE_LEDGER,
              FLIGHT_RECORDER):
        s.reset()
    set_dispatch_context("", "")


def _iso_config(**overrides) -> Configuration:
    cfg = Configuration()
    cfg.set(IsolationOptions.ENABLED, True)
    for opt, value in overrides.items():
        cfg.set(getattr(IsolationOptions, opt.upper()), value)
    return cfg


# ---------------------------------------------------------------------------
# scheduler unit drills: DRR fairness, bulkhead bounds, breaker ladder
# ---------------------------------------------------------------------------

def _poll_alternating(jobs: list, rounds: int) -> dict:
    """Alternate try_admit polls across ``jobs`` (each retry is one
    poll, like the real 1ms-backoff gate) and count admissions."""
    admitted = {j: 0 for j in jobs}
    for i in range(rounds):
        job = jobs[i % len(jobs)]
        if ISOLATION.try_admit(job) == "admit":
            admitted[job] += 1
    return admitted


def test_weighted_drr_admission_tracks_weights():
    """Two contending tenants at 3:1 weights admit ~3:1 (within one
    quantum of slack), and a re-run of the identical poll sequence
    reproduces the counters exactly — no wall clock, no RNG."""
    def run() -> dict:
        ISOLATION.reset()
        ISOLATION.configure(_iso_config(job_weights="a=3;b=1"))
        ISOLATION.register_job("a")
        ISOLATION.register_job("b")
        _poll_alternating(["a", "b"], 400)
        snap = ISOLATION.snapshot()["jobs"]
        for row in snap.values():
            row.pop("device_time_share")
        return snap

    first = run()
    assert first["a"]["admitted_total"] > 0
    assert first["b"]["admitted_total"] > 0
    ratio = first["a"]["admitted_total"] / first["b"]["admitted_total"]
    assert 2.0 <= ratio <= 4.5, f"3:1 weights gave {ratio:.2f}:1 admits"
    assert run() == first, "identical poll sequence diverged"


def test_solo_tenant_admission_is_free():
    """Quotas shape contention only: a lone job never spends credit,
    never retries, never sheds."""
    ISOLATION.configure(_iso_config(job_weights="only=1"))
    ISOLATION.register_job("only")
    admitted = _poll_alternating(["only"], 300)
    assert admitted["only"] == 300
    row = ISOLATION.snapshot()["jobs"]["only"]
    assert row["admissions_rejected_total"] == 0


def test_bulkhead_bound_and_gate_timeout_shed():
    ISOLATION.configure(_iso_config(queue_bound=2, shed_after=0.05))
    ISOLATION.register_job("a")
    for _ in range(4):
        ISOLATION.note_waiting("a", +1)
    assert ISOLATION.try_admit("a") == "shed:bulkhead-full"
    for _ in range(4):
        ISOLATION.note_waiting("a", -1)
    assert ISOLATION.try_admit("a", waited_s=0.06) == "shed:gate-timeout"
    row = ISOLATION.snapshot()["jobs"]["a"]
    assert row["bulkhead_trips_total"] == 1
    assert row["admissions_rejected_total"] == 2


def test_breaker_opens_probes_and_closes():
    """The full breaker ladder: consecutive failures open it, a
    count-based cooldown later one probe is admitted, a failed probe
    re-opens, a successful probe closes."""
    ISOLATION.configure(_iso_config(breaker_failures=3,
                                    breaker_cooldown=5))
    ISOLATION.register_job("a")
    for _ in range(3):
        ISOLATION.note_failure("a")
    assert ISOLATION.snapshot()["jobs"]["a"]["breaker"] == "open"
    # shed until the cooldown (admission attempts, not wall time) elapses
    verdicts = [ISOLATION.try_admit("a") for _ in range(5)]
    assert verdicts[0] == "shed:breaker-open"
    assert verdicts[-1] == "admit", "cooldown never produced a probe"
    assert ISOLATION.snapshot()["jobs"]["a"]["breaker"] == "half-open"
    ISOLATION.note_failure("a")  # probe failed: re-open, new cooldown
    assert ISOLATION.snapshot()["jobs"]["a"]["breaker"] == "open"
    verdicts = [ISOLATION.try_admit("a") for _ in range(6)]
    assert "admit" in verdicts, "re-opened breaker never half-opened"
    ISOLATION.note_success("a")  # probe succeeded: close
    row = ISOLATION.snapshot()["jobs"]["a"]
    assert row["breaker"] == "closed"
    assert row["breaker_opens_total"] == 1  # re-open is not a new open
    assert ISOLATION.try_admit("a") == "admit"


def test_breaker_and_shed_counters_deterministic_across_seeds():
    """TPU501 for the overload path: with job-filtered chaos rules at
    sched.shed and device.execute, the full admit/shed/breaker history
    is a pure function of the visit sequence — identical counters for
    every fault seed (count-based schedules never consult the RNG)."""
    def drive(seed: int):
        faults_mod.FAULTS.reset()
        ISOLATION.reset()
        cfg = _iso_config(breaker_failures=3, breaker_cooldown=8)
        cfg.set(FaultOptions.ENABLED, True)
        cfg.set(FaultOptions.SEED, seed)
        cfg.set(FaultOptions.SPEC,
                "sched.shed=every@5!job@job-a,"
                "device.execute=always!poison!job@job-a")
        faults_mod.FAULTS.configure(cfg)
        ISOLATION.configure(cfg)
        ISOLATION.register_job("job-a")
        set_dispatch_context("job-a", "src")
        try:
            for _ in range(64):
                if faults_mod.FAULTS.check("sched.shed"):
                    ISOLATION.note_shed("job-a", 256, "injected")
                    continue
                verdict = ISOLATION.try_admit("job-a")
                if verdict == "admit":
                    with pytest.raises(faults_mod.InjectedFault):
                        faults_mod.FAULTS.fire("device.execute")
                    ISOLATION.note_failure("job-a")
                elif verdict.startswith("shed:"):
                    ISOLATION.note_shed("job-a", 256,
                                        verdict.partition(":")[2])
        finally:
            set_dispatch_context("", "")
        row = ISOLATION.snapshot()["jobs"]["job-a"]
        row.pop("device_time_share")
        return row, faults_mod.FAULTS.snapshot()["trips"]

    runs = {seed: drive(seed) for seed in (0, 1, 7)}
    assert runs[0] == runs[1] == runs[7], \
        "breaker/shed history diverged across fault seeds"
    row, trips = runs[0]
    assert row["breaker_opens_total"] >= 1
    assert row["shed_batches_total"] > 0
    assert trips.get("sched.shed", 0) > 0


# ---------------------------------------------------------------------------
# pipeline drills: the tiny Q5 stage under the admission gate
# ---------------------------------------------------------------------------

class _RowSink(SinkFunction):
    def __init__(self):
        self.rows = []

    def invoke_batch(self, batch):
        self.rows.extend(batch.iter_rows())
        return True


def _expected(keys, vals, ts, skip=()) -> dict:
    out: dict = {}
    for i, (k, v, t) in enumerate(zip(keys, vals, ts)):
        if i in skip:
            continue
        end = (int(t) // PANE + 1) * PANE
        c, s = out.get((int(k), end), (0, 0))
        out[(int(k), end)] = (c + 1, s + int(v))
    return out


def _build_env(options, sink, n=1 << 11, n_keys=23, batch=256):
    """The tiny Q5-shaped pipeline from the chaos suite: datagen ->
    keyBy -> device tumbling aggregate -> sink."""
    from flink_tpu.api.environment import StreamExecutionEnvironment
    from flink_tpu.core.watermarks import WatermarkStrategy
    from flink_tpu.runtime.operators.device_window import AggSpec
    from flink_tpu.window import TumblingEventTimeWindows

    def gen(idx):
        return {"k": (idx * 3) % n_keys, "v": (idx % 13) + 1,
                "ts": (idx * 5 * PANE) // n}

    schema = Schema([("k", np.int64), ("v", np.int64), ("ts", np.int64)])
    env = StreamExecutionEnvironment()
    env.set_state_backend("tpu")
    env.config.set(PipelineOptions.BATCH_SIZE, batch)
    for opt, value in options:
        env.config.set(opt, value)
    ws = WatermarkStrategy.for_monotonous_timestamps() \
        .with_timestamp_column("ts")
    (env.datagen(gen, schema, count=n, timestamp_column="ts",
                 watermark_strategy=ws)
        .key_by("k")
        .window(TumblingEventTimeWindows.of(PANE))
        .device_aggregate([AggSpec("count", out_name="cnt", value_bits=31),
                           AggSpec("sum", "v", out_name="total")],
                          capacity=1 << 12, ring_size=8,
                          emit_window_bounds=True, defer_overflow=True)
        .add_sink(sink, "sink"))
    idx = np.arange(n)
    data = ((idx * 3) % n_keys, (idx % 13) + 1, (idx * 5 * PANE) // n)
    return env, data


def _rows_dict(sink) -> dict:
    got = {}
    for k, _ws, we, cnt, total in sink.rows:
        assert (int(k), int(we)) not in got, "duplicate window emission"
        got[(int(k), int(we))] = (int(cnt), int(total))
    return got


def test_injected_shed_quarantines_one_batch_with_accounting():
    """A sched.shed chaos trip sheds exactly one micro-batch: the rows
    land in the quarantine counters (never a silent drop) and every
    OTHER window stays exactly-once."""
    n, batch = 1 << 10, 256
    sink = _RowSink()
    env, (keys, vals, ts) = _build_env(
        [(IsolationOptions.ENABLED, True),
         (FaultOptions.ENABLED, True),
         (FaultOptions.SEED, 0),
         (FaultOptions.SPEC, "sched.shed=once@2")],
        sink, n=n, batch=batch)
    env.execute("shed-drill", timeout=60.0)
    # the 2nd gate poll shed the 2nd batch: rows 256..511 quarantined
    skip = set(range(batch, 2 * batch))
    assert _rows_dict(sink) == _expected(keys, vals, ts, skip=skip)
    row = ISOLATION.snapshot()["jobs"]["shed-drill"]
    assert row["shed_batches_total"] == 1
    assert row["shed_records_total"] == batch
    assert faults_mod.FAULTS.snapshot()["trips"].get("sched.shed") == 1


def test_hostile_tenant_cannot_harm_healthy_tenant():
    """THE acceptance drill (ISSUE): tenant-hostile runs with poison
    AND hang injected at device.execute (job-filtered), concurrently
    with tenant-healthy. The healthy tenant's output must be
    byte-identical to its solo run, with zero failures, zero restarts,
    zero recompiles — and the hostile tenant's damage must surface ONLY
    under its own job-scoped surfaces."""
    from types import SimpleNamespace

    from flink_tpu.cluster.rest import RestEndpoint

    hostile, healthy = "tenant-hostile", "tenant-healthy"
    iso = [(IsolationOptions.ENABLED, True),
           (IsolationOptions.JOB_WEIGHTS,
            f"{hostile}=1;{healthy}=1"),
           (ProfilerOptions.ENABLED, True)]

    # -- solo baseline (also warms the program caches for both tenants:
    # the pipelines are shape-identical, so the concurrent phase must
    # not compile anything)
    solo_sink = _RowSink()
    env, data = _build_env(iso, solo_sink)
    env.execute(healthy, timeout=60.0)
    solo = _rows_dict(solo_sink)
    keys, vals, ts = data
    assert solo == _expected(keys, vals, ts)

    for s in (faults_mod.FAULTS, WATCHDOG, ISOLATION, DEVICE_LEDGER,
              FLIGHT_RECORDER):
        s.reset()

    # -- concurrent phase: identical configs (the singletons adopt one
    # fingerprint), all damage job-filtered to the hostile tenant
    chaos = iso + [
        (FaultOptions.ENABLED, True),
        (FaultOptions.SEED, 0),
        (FaultOptions.SPEC,
         f"device.execute=every@2!poison!job@{hostile},"
         f"device.execute=every@5!hang@30!job@{hostile}"),
        (WatchdogOptions.EXECUTE_TIMEOUT, 0.015)]
    sinks = {hostile: _RowSink(), healthy: _RowSink()}
    envs = {name: _build_env(chaos, sinks[name])[0]
            for name in (hostile, healthy)}
    errors: dict = {}

    def run(name):
        try:
            envs[name].execute(name, timeout=90.0)
        except Exception as e:  # noqa: BLE001 - surfaced below
            errors[name] = e

    threads = [threading.Thread(target=run, args=(n,), daemon=True)
               for n in (hostile, healthy)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(110)
        assert not t.is_alive(), "a tenant wedged under the drill"
    assert healthy not in errors, f"healthy tenant failed: {errors}"

    # healthy tenant: byte-identical output, zero damage
    assert _rows_dict(sinks[healthy]) == solo, \
        "healthy tenant's results changed under a hostile neighbor"
    iso_jobs = ISOLATION.snapshot()["jobs"]
    assert iso_jobs[healthy]["failures_total"] == 0
    assert iso_jobs[healthy]["shed_batches_total"] == 0
    assert iso_jobs[healthy]["breaker"] == "closed"
    # zero recompiles: every program was warmed by the solo pass
    led = DEVICE_LEDGER.snapshot()["jobs"]
    assert led.get(healthy, {}).get("compile_ms", 0.0) == 0.0
    # zero restarts: no failover chokepoint ever dumped in the healthy
    # tenant's failure domain
    assert all(d.get("job") != healthy for d in FLIGHT_RECORDER.dumps)

    # hostile tenant: the damage is real and it is job-tagged
    assert faults_mod.FAULTS.snapshot()["trips"] \
        .get("device.execute", 0) > 0
    assert iso_jobs[hostile]["failures_total"] > 0
    for event in faults_mod.FAULTS.events:
        if event.get("site") == "device.execute":
            assert event.get("job") == hostile
    # the job-scoped REST exception surface never shows the neighbor's
    # stalls/poisons to the healthy tenant
    ep = RestEndpoint()
    ep.register_job(healthy, SimpleNamespace(failure_history=[]))
    for entry in ep._exceptions(healthy)["entries"]:
        assert entry.get("job") != hostile, \
            f"hostile damage leaked into {healthy}'s surface: {entry}"
