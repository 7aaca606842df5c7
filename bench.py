"""Count probes of the tiny Nexmark-Q5 job: what the tests, pytest.ini and
the documents name. Nothing here measures speed: the measurement is
``python -m benchmarks.run --workload <cell>`` on the chip (BENCHMARK.json,
PERF.md, PERF_LEDGER.jsonl), and the correctness check on the chip is
``chip_smoke.py``. Every mode runs on whatever backend JAX has and prints the
device first; a wall time or a rate in a report from the CPU is not a speed.

  --tiny [--audit]   the tiny Q5 stage report: compiles, recompiles (0 after
                 the warmup), transfer bytes, coalesce / tier counters
  --audit        --tiny with the tpu-lint jaxpr and certificate audit
  --fused [--audit]   the same pipeline unfused and fused: recompiles and
                 chain dispatches per micro-batch
  --chaos SEED   --tiny with every fault site armed (CHAOS_SPEC)
  --two-jobs     two tiny tenants under the isolation scheduler
  --trace [PREFIX] / --profile [PREFIX]   with any of the above: the run's
                 spans as Chrome trace-event JSON / the dispatch-time ledger

``run_tiny_q5`` and ``write_trace`` are what the tests import.
"""

from __future__ import annotations

import json
import sys
import time

import numpy as np

def _device() -> dict:
    """The device this process runs on, as JAX reports it. The platform
    comes from JAX alone (``JAX_PLATFORMS`` in the environment selects the
    CPU); nothing here probes for a chip or falls back to another."""
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "device_kind": devs[0].device_kind,
            "count": len(devs)}


def _start() -> None:
    """First line of every mode: the device. Also places JAX's persistent
    compile cache before the first compile."""
    from flink_tpu.utils.compile_cache import place_compile_cache

    print(json.dumps({"metric": "device", "unit": "", **_device(),
                      "compile_cache_dir": place_compile_cache()}))
    sys.stdout.flush()


RING = 16
WINDOW_PANES = 5            # Q5's HOP 10 s / 2 s
MULT = 0x9E3779B97F4A7C15   # odd 64-bit mixer: idx -> pseudo-uniform key


def _p99(xs) -> float:
    if not xs:
        return 0.0
    xs = sorted(xs)
    return xs[min(len(xs) - 1, int(0.99 * len(xs)))]


class _CountSink:
    """Vectorized discard sink that counts rows."""

    def __init__(self):
        from flink_tpu.core.functions import SinkFunction

        class _S(SinkFunction):
            def __init__(s):
                s.rows = 0

            def invoke_batch(s, batch):
                s.rows += batch.n
                return True

        self.fn = _S()

    @property
    def rows(self):
        return self.fn.rows


def _find_ops(env, cls):
    ops = []
    for task in env.last_job.tasks.values():
        chain = getattr(task, "chain", None)
        if chain is not None:
            ops += [o for o in chain.operators if isinstance(o, cls)]
    return ops


def _n_panes(n_events: int, batch: int, max_panes: int) -> int:
    """Panes sized so the WHOLE stream's event-time span plus the sliding
    window's W-1-pane tail fits inside the ring-slot accumulator ring
    with headroom: worst-case open span = n_panes + W - 1 must stay
    <= ring - 3 even if fire retirement lags ingest completely, so the
    caller passes RING - W - 2."""
    return max(4, min(max_panes, n_events // batch))


def _collect_stages(env) -> dict:
    """Per-stage wall-clock breakdown: source read/emit (SourceStreamTask
    counters) + window ingest/fire/drain (operator counters)."""
    from flink_tpu.runtime.operators.device_window import (
        DeviceWindowAggOperator,
    )
    from flink_tpu.runtime.stream_task import SourceStreamTask

    stages: dict[str, float] = {}
    for task in env.last_job.tasks.values():
        if isinstance(task, SourceStreamTask):
            for k, v in task.stage_s.items():
                stages[f"source_{k}"] = stages.get(f"source_{k}", 0.0) + v
    for op in _find_ops(env, DeviceWindowAggOperator):
        for k, v in op.stage_s.items():
            stages[f"window_{k}"] = stages.get(f"window_{k}", 0.0) + v
    return stages


def _collect_metrics(env, before: dict) -> dict:
    """Device-path observability snapshot embedded in every stage report:
    compile accounting from the process-global program caches (cumulative
    — the same series prometheus_text exposes), this run's recompile
    delta, transfer totals, and the job's busy/backpressure ratios from
    the per-subtask mailbox timers."""
    from flink_tpu.metrics import DEVICE_STATS

    snap = DEVICE_STATS.snapshot()
    out = {k: snap[k] for k in ("compiles", "compile_cache_hits",
                                "compile_ms", "h2d_bytes", "h2d_records",
                                "d2h_bytes", "d2h_records")}
    out["recompiles"] = snap["compiles"] - before.get("compiles", 0)
    # degradation-ladder + stall counters (deltas for this run): nonzero
    # only under injection or a genuinely failing/hanging device path
    # coalesced ingest + fused chain counters (deltas)
    for k in ("batches_coalesced_total", "chain_fused_dispatches_total"):
        out[k] = snap.get(k, 0) - before.get(k, 0)
    # tiered-state counters: eviction/prefetch deltas for this run plus
    # the hit-ratio and HBM-footprint gauges (point-in-time readings)
    for k in ("tier_evictions_total", "tier_evicted_keys_total",
              "tier_prefetches_total", "tier_promoted_keys_total"):
        out[k] = snap.get(k, 0) - before.get(k, 0)
    for k in ("tier_hot_hit_ratio", "tier_hbm_bytes_in_use"):
        out[k] = snap.get(k, 0)
    for k in ("device_retries_total", "device_degraded_total",
              "dead_letter_records_total", "injected_faults_total",
              "watchdog_trips_total", "stall_detections_total",
              "checkpoint_verify_failures_total", "restore_fallbacks_total",
              "network_reconnects_total", "frames_deduped_total",
              "zombies_fenced_total", "network_errors_total",
              "leader_elections_total", "coordinator_failovers_total",
              "takeover_duration_ms_count"):
        out[k] = snap.get(k, 0) - before.get(k, 0)
    # takeover-duration histogram readings (point-in-time; nonzero only
    # after a standby coordinator took over a running job)
    for k in ("takeover_duration_ms_p50", "takeover_duration_ms_max"):
        out[k] = snap.get(k, 0)
    # AOT executable-cache counters (deltas): persistent-cache hit/miss
    # accounting, store/fallback events, in-memory LRU evictions, and
    # live XLA compiles taken while the persistent cache was active
    # (compile storms — 0 on a properly warmed process)
    for k in ("aot_hits_total", "aot_misses_total", "aot_stores_total",
              "aot_fallbacks_total", "aot_in_memory_evictions_total",
              "compile_storms_total"):
        out[k] = snap.get(k, 0) - before.get(k, 0)
    # cold-start readings (point-in-time): ms from AOT-enabled process
    # start to the first device->host transfer (first fired window)
    for k in ("cold_start_ms_count", "cold_start_ms_p50",
              "cold_start_ms_max"):
        out[k] = snap.get(k, 0)
    busy = bp = elapsed = 0.0
    for task in env.last_job.tasks.values():
        t = getattr(task, "io_timers", None)
        if t is None:
            continue
        busy += max(0.0, t.busy_s - t.backpressured_s)
        bp += t.backpressured_s
        elapsed += t.elapsed_s
    out["busy_time_ratio"] = round(busy / elapsed, 4) if elapsed else 0.0
    out["backpressured_time_ratio"] = (round(bp / elapsed, 4)
                                       if elapsed else 0.0)
    return out


def _ledger_before() -> dict:
    from flink_tpu.metrics.profiler import DEVICE_LEDGER
    return DEVICE_LEDGER.snapshot()


def _device_time_block(before: dict) -> dict:
    """This run's device-time attribution from the process-global
    ledger: per-site and per-operator device-ms deltas with shares of
    the stage total (shares partition the same sum, so they add up to
    1.0 up to rounding — the report's consistency check)."""
    from flink_tpu.metrics.profiler import DEVICE_LEDGER

    after = DEVICE_LEDGER.snapshot()
    total = after["device_ms_total"] - before.get("device_ms_total", 0.0)
    compile_ms = (after["compile_ms_total"]
                  - before.get("compile_ms_total", 0.0))

    def deltas(field: str) -> dict:
        out = {}
        for name, row in after.get(field, {}).items():
            prev = before.get(field, {}).get(name, {})
            ms = row["device_ms"] - prev.get("device_ms", 0.0)
            n = row["count"] - prev.get("count", 0)
            if ms > 0.0 or n > 0:
                out[name] = {"ms": round(ms, 3), "count": n,
                             "share": (round(ms / total, 4)
                                       if total > 0.0 else 0.0)}
        return out

    return {"enabled": after["enabled"],
            "total_ms": round(total, 3),
            "compile_ms": round(compile_ms, 3),
            "dispatches": (after["dispatches_total"]
                           - before.get("dispatches_total", 0)),
            "by_site": deltas("sites"),
            "by_operator": deltas("operators")}


def _tiny_q5_pass(n_keys: int, n_events: int, batch: int,
                  metrics_registry=None, extra_config: dict = None,
                  job_name: str = "nexmark-q5"):
    """One env.execute() of the tiny Q5 pipeline (device-born batches,
    2 s panes, top 1000 by bid count, 2^14 slots); returns (wall_seconds,
    fire_latencies_ms, emitted_rows, stage_breakdown). The stage
    breakdown embeds the device-path metrics snapshot (compiles, cache
    hits, transfer bytes, busy/backpressure ratios)."""
    from flink_tpu.api import StreamExecutionEnvironment
    from flink_tpu.core import WatermarkStrategy
    from flink_tpu.core.config import PipelineOptions
    from flink_tpu.core.records import Schema
    from flink_tpu.runtime.operators.device_window import (
        AggSpec, DeviceWindowAggOperator,
    )
    from flink_tpu.window import SlidingEventTimeWindows

    schema = Schema([("auction", np.int64), ("price", np.int64),
                     ("ts", np.int64)])
    pane_ms = 2000
    n_panes = _n_panes(n_events, batch, max_panes=RING - WINDOW_PANES - 2)
    span = n_panes * pane_ms

    def gen(idx):
        u = idx.astype(np.uint64)
        auction = ((u * np.uint64(MULT)) % np.uint64(n_keys)).astype(np.int64)
        return {"auction": auction,
                "price": (idx % 997) + 1,
                "ts": (idx * span) // n_events}

    from flink_tpu.metrics import DEVICE_STATS

    stats_before = DEVICE_STATS.snapshot()
    led_before = _ledger_before()
    env = StreamExecutionEnvironment.get_execution_environment()
    env.set_state_backend("tpu")
    env.config.set(PipelineOptions.BATCH_SIZE, batch)
    # device-time ledger on by default so every stage report carries its
    # device_time block; extra_config may still override it off (the
    # overhead A/B measures exactly that)
    env.config.set("profiler.enabled", True)
    for k, v in (extra_config or {}).items():
        env.config.set(k, v)
    ws = WatermarkStrategy.for_monotonous_timestamps() \
        .with_timestamp_column("ts")
    sink = _CountSink()
    (env.datagen(gen, schema, count=n_events, timestamp_column="ts",
                 watermark_strategy=ws, device=True)
        .key_by("auction")
        .window(SlidingEventTimeWindows.of(WINDOW_PANES * pane_ms,
                                           pane_ms))
        # rank hot items by bid COUNT (value_bits=31: exact to 2.1e9 events/key/window, and
        # <= 31 selects the int32 count plane + uint32 radix select) and
        # carry the revenue SUM alongside
        .device_aggregate([AggSpec("count", out_name="bids",
                                   value_bits=31),
                           AggSpec("sum", "price", out_name="revenue")],
                          capacity=1 << 14, ring_size=RING,
                          emit_window_bounds=False, emit_topk=1000,
                          defer_overflow=True, async_fire=True)
        .add_sink(sink.fn, "count"))
    t0 = time.perf_counter()
    env.execute(job_name, timeout=1800.0,
                metrics_registry=metrics_registry)
    wall = time.perf_counter() - t0
    ops = _find_ops(env, DeviceWindowAggOperator)
    lat = [ms for o in ops for ms in o.fire_latencies_ms]
    stages = _collect_stages(env)
    stages.update(_collect_metrics(env, stats_before))
    stages["device_time"] = _device_time_block(led_before)
    stages["max_inflight"] = max((o._max_inflight for o in ops), default=0)
    return wall, lat, sink.rows, stages


def run_tiny_q5(n_keys: int = 1000, batch: int = 1 << 12,
                n_batches: int = 8, metrics_registry=None,
                chaos_seed=None, extra_config: dict = None,
                job_name: str = "nexmark-q5") -> dict:
    """Tiny Q5 acceptance probe (tier-1 safe): warmup + timed run on
    whatever backend jax already has;
    returns the timed run's stage report with the embedded metrics
    snapshot — ``recompiles`` == 0 is the no-recompile invariant.

    ``chaos_seed``: run the timed pass with deterministic fault injection
    armed at every device-path site (transient/bounded schedules — see
    CHAOS_SPEC); the report then embeds the retry/degradation/dead-letter
    counters the run produced. The recompile invariant is NOT asserted
    under chaos (retried compiles legitimately recount)."""
    n_events = n_batches * batch
    extra = dict(extra_config) if extra_config else None
    # warmup must compile the TIMED run's programs (e.g. the HBM-budget
    # capacity cap changes table/plane shapes), so it runs under the
    # caller's config — but never under the chaos schedule
    warm_extra = dict(extra) if extra else None
    if chaos_seed is not None:
        extra = dict(extra or {})
        extra.update(
                {"faults.enabled": True, "faults.seed": int(chaos_seed),
                 "faults.spec": CHAOS_SPEC,
                 # tighten the transfer deadline under the injected d2h
                 # hangs so the chaos run exercises the watchdog
                 # stall->retry path (watchdog_trips_total > 0)
                 "watchdog.transfer-timeout": 0.012,
                 # the admission gate only visits its sched.* sites when
                 # isolation is on; a solo job is never throttled, so the
                 # gate adds the CHAOS_SPEC sched trips and nothing else
                 "isolation.enabled": True})
        from flink_tpu.cluster.isolation import ISOLATION
        from flink_tpu.runtime.faults import FAULTS
        from flink_tpu.runtime.watchdog import WATCHDOG
        FAULTS.reset()  # arm fresh: visit counters start at zero
        WATCHDOG.reset()
        ISOLATION.reset()  # per-job shed/reject counters start at zero
    _tiny_q5_pass(n_keys, 4 * batch, batch,
                  metrics_registry=metrics_registry, extra_config=warm_extra,
                  job_name=job_name)                        # compile warmup
    wall, lat, rows, stages = _tiny_q5_pass(
        n_keys, n_events, batch, metrics_registry=metrics_registry,
        extra_config=extra, job_name=job_name)
    stages["wall"] = wall
    stages["events_per_sec"] = round(n_events / wall, 2)
    stages["p99_fire_latency_ms"] = round(_p99(lat), 3)
    stages["emitted_rows"] = rows
    if chaos_seed is not None:
        from flink_tpu.runtime.faults import FAULTS
        from flink_tpu.runtime.watchdog import WATCHDOG
        stages["chaos_seed"] = int(chaos_seed)
        stages["chaos_trips"] = FAULTS.snapshot()["trips"]
        stages["watchdog_trips"] = dict(WATCHDOG.trips)
        # per-job bulkhead deltas (counters started at zero above): what
        # the admission gate rejected, tripped, and shed this run
        from flink_tpu.cluster.isolation import ISOLATION
        stages["isolation"] = {
            job: {"admissions_rejected_total":
                  row["admissions_rejected_total"],
                  "bulkhead_trips_total": row["bulkhead_trips_total"],
                  "shed_records_total": row["shed_records_total"]}
            for job, row in ISOLATION.snapshot()["jobs"].items()}
        FAULTS.reset()
        WATCHDOG.reset()
        ISOLATION.reset()
    return stages


#: The --chaos schedule: every device-path site armed with a bounded or
#: probabilistic transient schedule, so the run completes while still
#: exercising retry, injected backpressure, quarantine-free recovery, and
#: the failed-checkpoint-write tolerance. transfer.d2h injects HANGS on a
#: bounded schedule (never two consecutive visits) so the watchdog
#: stall->abandon->retry path runs too, under the tightened transfer
#: deadline run_tiny_q5 sets for chaos runs. (Persistent-degradation and
#: stall-to-degrade trials live in tests/test_chaos.py where results are
#: asserted exactly.)
CHAOS_SPEC = ("device.compile=once@2,device.execute=p0.05,"
              "transfer.h2d=p0.05,transfer.d2h=every@5!hang@30,"
              "channel.send=once@3,channel.backpressure=every@17,"
              "checkpoint.write=once@1,sink.invoke=once@2,"
              "rpc.heartbeat=every@5,net.sever=every@23,"
              # tiered-state sites: no-ops unless the run sets an HBM
              # budget (mid-window evict/prefetch parity is asserted
              # exactly in tests/test_tiering.py)
              "tier.evict=once@2,tier.prefetch=once@2,"
              # admission-gate sites (visited when isolation.enabled,
              # which the chaos config sets): a bounded hang at the gate
              # plus one forced shed to the dead-letter output — the
              # two-tenant starvation drills are asserted exactly in
              # tests/test_isolation.py
              "sched.admit=every@7!hang@5,sched.shed=once@4,"
              # AOT executable-cache sites: no-ops unless the run sets
              # aot.dir (the corrupt-artifact and store-failure drills
              # are asserted exactly in tests/test_aot.py)
              "aot.load=once@1,aot.store=once@1,"
              # coordinator-failover site: a no-op here (only the
              # distributed leader's monitor loop visits it — a local run
              # has no elected coordinator); the kill-the-leader drills
              # are asserted exactly in tests/test_failover.py
              "coord.crash=once@2")


#: Set by ``--trace [PREFIX]``: each stage writes its retained spans to
#: ``<PREFIX>.<stage>.trace.json`` as Chrome trace-event JSON (load the
#: file in Perfetto / chrome://tracing).
TRACE_PREFIX = ""


def _trace_extra_config() -> dict:
    """Under --trace, run with periodic checkpointing on so the trace
    carries full checkpoint trees alongside device/mailbox spans. The
    interval must undercut even the tiny stage's sub-second wall clock,
    or the traced run would end before the first trigger fires."""
    if not TRACE_PREFIX:
        return {}
    return {"execution.checkpointing.interval": 0.05}


def write_trace(stage: str, prefix: str = None) -> str:
    """Export the global tracer's retained spans for one bench stage as
    Perfetto-loadable trace-event JSON (plus the device-time ledger's
    dispatch samples as per-site counter tracks); returns the path."""
    from flink_tpu.metrics.profiler import DEVICE_LEDGER
    from flink_tpu.metrics.tracing import TRACER, chrome_trace_events

    spans = TRACER.retained_spans()
    path = f"{prefix or TRACE_PREFIX or 'bench'}.{stage}.trace.json"
    with open(path, "w") as f:
        json.dump(chrome_trace_events(
            spans, counters=DEVICE_LEDGER.trace_counters()), f)
    print(json.dumps({"metric": "trace_file", "unit": "path",
                      "stage": stage, "path": path, "spans": len(spans)}))
    return path


def _maybe_write_trace(stage: str) -> None:
    if TRACE_PREFIX:
        write_trace(stage)


#: Set by ``--profile [PREFIX]``: each stage prints its top-10
#: hot-program table and writes the full ledger profile to
#: ``<PREFIX>.<stage>.profile.json`` (next to the --trace output).
PROFILE_PREFIX = ""


def write_profile(stage: str, prefix: str = None, top: int = 10) -> str:
    """Dump the device-time ledger's full attribution report for one
    bench stage as JSON and print the top-``top`` hot-program table;
    returns the path written."""
    from flink_tpu.metrics.profiler import DEVICE_LEDGER

    prof = DEVICE_LEDGER.profile(top=top)
    path = f"{prefix or PROFILE_PREFIX or 'bench'}.{stage}.profile.json"
    with open(path, "w") as f:
        json.dump(prof, f, indent=2, sort_keys=True)
        f.write("\n")
    print(json.dumps({"metric": "profile_file", "unit": "path",
                      "stage": stage, "path": path,
                      "programs": len(prof["programs"]),
                      "total_device_ms": round(prof["total_device_ms"],
                                               3)}))
    header = (f"{'site':<28} {'operator':<22} {'n':>7} {'self_ms':>10} "
              f"{'p95_ms':>8} {'share':>6}")
    print(header)
    print("-" * len(header))
    for p in prof["programs"]:
        print(f"{p['site']:<28} {(p['operator'] or '-'):<22} "
              f"{p['count']:>7} {p['self_ms']:>10.2f} "
              f"{p['p95_ms']:>8.3f} {p['share'] * 100:>5.1f}%")
    sys.stdout.flush()
    return path


def _maybe_write_profile(stage: str) -> None:
    if PROFILE_PREFIX:
        write_profile(stage)


def _audit_report() -> dict:
    """tpu-lint Tier-B jaxpr audit over every compiled program the run
    just registered (metrics.device PROGRAM_AUDIT) plus the Tier-P
    fusion-certificate audit over every chain the run certified
    (graph.fusion CERTIFICATE_LOG): per-rule finding counts plus the
    count not covered by the committed baseline.  The tiny Q5 report
    must show audit_new == 0 — a scatter on the fire path, an f64 leak,
    or a rejected fusion boundary fails the acceptance probe, not a
    code review."""
    from flink_tpu.analysis import (AnalysisContext, all_rules,
                                    diff_against_baseline, run_rules)
    from flink_tpu.graph.fusion import CERTIFICATE_LOG
    from flink_tpu.metrics.device import PROGRAM_AUDIT

    audited = sorted(r for r, rr in all_rules().items()
                     if rr.tier in ("B", "P"))
    skipped: list = []
    findings = run_rules(AnalysisContext(), audited, skipped)
    new, _stale = diff_against_baseline(findings)
    counts = {r: 0 for r in audited}
    for f in findings:
        counts[f.rule] += 1
    report = {f"audit_{r}": n for r, n in counts.items()}
    report["audit_programs"] = len(PROGRAM_AUDIT)
    report["audit_certificates"] = len(CERTIFICATE_LOG)
    report["audit_new"] = len(new)
    if skipped:
        report["audit_skipped"] = skipped
    return report


def tiny(audit: bool = False) -> None:
    """`python bench.py --tiny [--audit]`: the acceptance probe — one
    JSON line, the tiny Q5 stage report with the metrics snapshot
    embedded. ``--audit`` runs the tpu-lint Tier-B jaxpr audit over the
    programs the run compiled and embeds per-rule finding counts."""
    _start()
    stages = run_tiny_q5(extra_config=_trace_extra_config())
    rec = {"metric": "nexmark_q5_tiny_stage_report", "unit": "report"}
    rec.update({k: (round(v, 3) if isinstance(v, float) else v)
                for k, v in stages.items()})
    if audit:
        rec.update(_audit_report())
    print(json.dumps(rec))
    _maybe_write_trace("tiny_q5")
    _maybe_write_profile("tiny_q5")
    sys.stdout.flush()


#: The --fused stage's generator is MODULE-LEVEL on purpose: the fused
#: chain's program cache (runtime/compiled._PROGRAM_CACHE) keys on the
#: gen function object, so warmup and timed runs share one compiled
#: chain exactly as a long-running job would — a closure per run
#: (what _tiny_q5_pass builds) would recompile the chain every execute().
_FUSED_KEYS = 257
_FUSED_SPAN = 8000


def _fused_gen(idx):
    u = idx.astype(np.uint64)
    auction = ((u * np.uint64(MULT)) % np.uint64(_FUSED_KEYS)) \
        .astype(np.int64)
    return {"auction": auction, "price": (idx % 997) + 1,
            "ts": (idx * _FUSED_SPAN) // (1 << 15)}


def _run_fused_stage(fusion_on: bool, batch: int, n_events: int):
    """One execute() of the ingest-isolating Q5 variant: count-only
    aggregate, a handful of panes (fires are rare — the fire path is
    identical fused/unfused, so the stage measures what fusion changes:
    per-micro-batch ingest dispatches). Returns (wall, rows, stages)."""
    from flink_tpu.api import StreamExecutionEnvironment
    from flink_tpu.core import WatermarkStrategy
    from flink_tpu.core.config import PipelineOptions
    from flink_tpu.core.records import Schema
    from flink_tpu.metrics import DEVICE_STATS
    from flink_tpu.runtime.operators.device_window import AggSpec
    from flink_tpu.window import SlidingEventTimeWindows

    schema = Schema([("auction", np.int64), ("price", np.int64),
                     ("ts", np.int64)])
    stats_before = DEVICE_STATS.snapshot()
    led_before = _ledger_before()
    env = StreamExecutionEnvironment.get_execution_environment()
    env.set_state_backend("tpu")
    env.config.set(PipelineOptions.BATCH_SIZE, batch)
    env.config.set("profiler.enabled", True)
    env.config.set(PipelineOptions.FUSION, fusion_on)
    ws = WatermarkStrategy.for_monotonous_timestamps() \
        .with_timestamp_column("ts")
    sink = _CountSink()
    (env.datagen(_fused_gen, schema, count=n_events, timestamp_column="ts",
                 watermark_strategy=ws, device=True)
        .key_by("auction")
        .window(SlidingEventTimeWindows.of(10_000, 2000))
        .device_aggregate([AggSpec("count", out_name="bids",
                                   value_bits=31)],
                          capacity=1 << 12, ring_size=32,
                          defer_overflow=True)
        .add_sink(sink.fn, "count"))
    t0 = time.perf_counter()
    env.execute("nexmark-q5-fused", timeout=1800.0)
    wall = time.perf_counter() - t0
    stages = _collect_metrics(env, stats_before)
    stages["device_time"] = _device_time_block(led_before)
    return wall, sink.rows, stages


def fused(batch: int = 64, n_batches: int = 512) -> None:
    """`python bench.py --fused [--audit]`: the fusion-certifier
    acceptance stage — the same device-source -> window pipeline run
    twice at a small micro-batch size (the dispatch-overhead regime the
    fused chain targets), once unfused and once with
    `pipeline.fusion.enabled`, each after a compile warmup. One JSON
    line with both runs inline. The fused timed
    run must show `recompiles == 0` and exactly one
    `chain_fused_dispatches_total` per micro-batch."""
    _start()
    n_events = n_batches * batch
    rec = {"metric": "nexmark_q5_fused_report", "unit": "report",
           "batch": batch, "n_events": n_events}
    for label, on in (("unfused", False), ("fused", True)):
        _run_fused_stage(on, batch, 4 * batch)              # compile warmup
        wall, rows, stages = _run_fused_stage(on, batch, n_events)
        rec[f"{label}_events_per_sec"] = round(n_events / wall, 2)
        rec[f"{label}_recompiles"] = stages["recompiles"]
        rec[f"{label}_chain_dispatches"] = stages[
            "chain_fused_dispatches_total"]
        rec[f"{label}_emitted_rows"] = rows
    if "--audit" in sys.argv:
        rec.update(_audit_report())
    print(json.dumps(rec))
    _maybe_write_profile("fused_q5")
    sys.stdout.flush()


def chaos(seed: int) -> None:
    """`python bench.py --chaos SEED`: the tiny Q5 stage with
    deterministic fault injection armed at every site (CHAOS_SPEC, seeded
    by SEED); one JSON line embedding the run's retry / degradation /
    dead-letter / injected-fault counters alongside throughput. Same
    seed => byte-identical trip schedule."""
    _start()
    stages = run_tiny_q5(chaos_seed=seed,
                         extra_config=_trace_extra_config())
    from flink_tpu.metrics.tracing import FLIGHT_RECORDER
    rec = {"metric": "nexmark_q5_tiny_chaos_report", "unit": "report",
           "chaos_spec": CHAOS_SPEC,
           # post-mortem surface: flight-recorder dumps the chaos run's
           # fault chokepoints (stalls, fences, restarts) wrote to disk
           "flight_dumps": [d["path"] for d in FLIGHT_RECORDER.dumps],
           # verified-recovery surface: restore fallbacks taken and
           # artifact verification failures seen during the chaos run
           "restore_fallbacks": stages.get("restore_fallbacks_total", 0),
           "verify_failures": stages.get(
               "checkpoint_verify_failures_total", 0),
           # partition-tolerance surface: severed connections healed by
           # replay, duplicate frames dropped, stale-epoch peers fenced
           "net_reconnects": stages.get("network_reconnects_total", 0),
           "frames_deduped": stages.get("frames_deduped_total", 0),
           "zombies_fenced": stages.get("zombies_fenced_total", 0),
           "net_errors": stages.get("network_errors_total", 0),
           # coordinator-failover surface: elections won, takeovers
           # completed (hot + restore) and the takeover-duration
           # histogram — all zero here (no elected coordinator in a
           # local run); nonzero in the distributed failover drills
           "leader_elections": stages.get("leader_elections_total", 0),
           "coordinator_failovers": stages.get(
               "coordinator_failovers_total", 0),
           "takeover_ms": {
               "count": stages.get("takeover_duration_ms_count", 0),
               "p50": stages.get("takeover_duration_ms_p50", 0.0),
               "max": stages.get("takeover_duration_ms_max", 0.0)}}
    rec.update({k: (round(v, 3) if isinstance(v, float) else v)
                for k, v in stages.items()})
    print(json.dumps(rec))
    _maybe_write_trace("tiny_q5_chaos")
    _maybe_write_profile("tiny_q5_chaos")
    sys.stdout.flush()


def two_jobs(batch: int = 1 << 12, n_batches: int = 8) -> None:
    """`python bench.py --two-jobs`: two tiny Q5 jobs run CONCURRENTLY
    under the isolation scheduler (equal weights), after a solo baseline
    pass of each; one JSON line reporting per-job events/sec, the
    concurrent/solo ratio, and each tenant's quota/bulkhead counters.
    The fairness surface: with equal weights both ratios should land
    near each other (each tenant pays for sharing, neither starves)."""
    import threading as _threading

    from flink_tpu.cluster.isolation import ISOLATION

    _start()
    iso_cfg = {"isolation.enabled": True}
    names = ("tenant-a", "tenant-b")
    solo = {}
    for name in names:
        ISOLATION.reset()
        st = run_tiny_q5(batch=batch, n_batches=n_batches,
                         extra_config=dict(iso_cfg), job_name=name)
        solo[name] = st["events_per_sec"]
    ISOLATION.reset()
    results: dict = {}

    def _run(name: str) -> None:
        results[name] = run_tiny_q5(batch=batch, n_batches=n_batches,
                                    extra_config=dict(iso_cfg),
                                    job_name=name)

    threads = [_threading.Thread(target=_run, args=(n,), daemon=True)
               for n in names]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    quotas = ISOLATION.snapshot()["jobs"]
    ISOLATION.reset()
    rec = {"metric": "nexmark_q5_two_jobs", "unit": "report", "jobs": {}}
    for name in names:
        eps = results[name]["events_per_sec"]
        rec["jobs"][name] = {
            "events_per_sec": eps,
            "solo_events_per_sec": solo[name],
            "vs_solo": (round(eps / solo[name], 3) if solo[name] else 0.0),
            "recompiles": results[name].get("recompiles", 0),
            "quota": quotas.get(name, {})}
    print(json.dumps(rec))
    sys.stdout.flush()


if __name__ == "__main__":
    if "--trace" in sys.argv:
        i = sys.argv.index("--trace")
        TRACE_PREFIX = (sys.argv[i + 1]
                        if (len(sys.argv) > i + 1
                            and not sys.argv[i + 1].startswith("--"))
                        else "bench")
    if "--profile" in sys.argv:
        i = sys.argv.index("--profile")
        PROFILE_PREFIX = (sys.argv[i + 1]
                          if (len(sys.argv) > i + 1
                              and not sys.argv[i + 1].startswith("--"))
                          else "bench")
    if "--tiny" in sys.argv:
        tiny(audit="--audit" in sys.argv)
    elif "--fused" in sys.argv:
        fused()
    elif "--audit" in sys.argv:
        # audit alone: the tiny acceptance probe with the jaxpr audit on
        tiny(audit=True)
    elif "--chaos" in sys.argv:
        i = sys.argv.index("--chaos")
        chaos(int(sys.argv[i + 1]) if len(sys.argv) > i + 1 else 0)
    elif "--two-jobs" in sys.argv:
        two_jobs()
    else:
        raise SystemExit(__doc__)
