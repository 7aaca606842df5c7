"""Headline benchmark: Nexmark Q5 through the FRAMEWORK, not the kernels.

The default run drives a Nexmark-Q5-shaped job through ``env.execute()``:
datagen source -> keyBy -> sliding-window aggregate on the device
slice-window operator (hash-table lookup-or-insert + scatter-fold pane
accumulation + device top-k fire) -> sink, at 1M active keys — the whole
StreamTask/channel/watermark/operator path, measured end to end on
whatever chip jax.devices()[0] is (BASELINE.md config #3; reference hot
loop WindowOperator.java:278). ``vs_baseline`` compares against an
in-process per-record host dict loop (the heap-backend analog, itself
faster per-core than the RocksDB backend the target is defined against).

``--suite`` prints one JSON line per metric:
  * framework Q5 @1M and @10M keys (events/sec + p99 window-fire latency)
  * framework Q7 @10M keys — windowed max with the join lowered TPU-first:
    the winning bid's payload rides a packed (price<<20|bidder) word
    through the max lattice, so the join-with-max collapses into an argmax
    (reference Q7 join: MAX(price) subquery join; StreamExecLocal/Global
    two-phase shape)
  * framework Q7-join variant — device windowed max joined back against
    the bid stream through the host IntervalJoinOperator (a REAL two-input
    join in the job), smaller scale
  * raw kernel ceiling (the hand-inlined jitted step), for the honest gap
    between kernel and framework path

Each line: {"metric", "value", "unit", "vs_baseline"}.
"""

from __future__ import annotations

import json
import os
import subprocess
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


def _start(require_tpu: bool = False) -> None:
    """First line of every mode: the device. The modes that print
    per-chip rates (the default run and --suite) refuse to run without a
    TPU — a CPU timing is never written under a device metric's name. The
    acceptance probes (--tiny, --fused, --chaos, ...) report counts and
    run on whatever backend JAX has. Also places JAX's persistent compile
    cache before the first compile."""
    from flink_tpu.utils.compile_cache import place_compile_cache

    dev = _device()
    if require_tpu and dev["platform"] != "tpu":
        raise SystemExit(
            f"bench.py: this mode measures the chip and needs a TPU; "
            f"jax.devices()[0] is {dev['platform']!r}")
    print(json.dumps({"metric": "device", "unit": "", **dev,
                      "compile_cache_dir": place_compile_cache()}))
    sys.stdout.flush()


N_KEYS = 1_000_000
CAPACITY = 1 << 21          # 2x keys, power of two
RING = 16
BATCH = 1 << 19
N_BATCHES = 8               # distinct pre-generated batches, cycled
WARMUP = 3
WINDOW_ITERS = 8            # steps per timed window
N_WINDOWS = 6               # report the median window (a one-chip host
                            # shares its CPU cores; medians shrug off
                            # contention spikes that a single window can't)
HOST_EVENTS = 400_000

MULT = 0x9E3779B97F4A7C15   # odd 64-bit mixer: idx -> pseudo-uniform key


def _median(xs):
    xs = sorted(xs)
    mid = len(xs) // 2
    return xs[mid] if len(xs) % 2 else 0.5 * (xs[mid - 1] + xs[mid])


def _median_window_eps(run_window) -> float:
    """Run N_WINDOWS timed windows; each returns events/sec; report the
    median."""
    return _median([run_window(w) for w in range(N_WINDOWS)])


def _p99(xs) -> float:
    if not xs:
        return 0.0
    xs = sorted(xs)
    return xs[min(len(xs) - 1, int(0.99 * len(xs)))]


# ----------------------------------------------------------------------
# framework path (env.execute)
# ----------------------------------------------------------------------

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


def _n_panes(n_events: int, batch: int = BATCH,
             max_panes: int = RING - 7) -> int:
    """Panes sized so the WHOLE stream's event-time span plus the sliding
    window's W-1-pane tail fits inside the ring-slot accumulator ring
    with headroom: worst-case open span = n_panes + W - 1 must stay
    <= ring - 3 even if fire retirement lags ingest completely. The
    default max_panes of
    RING-7 is exactly that bound for the default RING ring and W=5; a
    --window-panes sweep passes ring - W - 2 for the grown _ring_for()
    ring so wide windows still see enough data panes to fill the full
    merge width."""
    return max(4, min(max_panes, n_events // batch))


def _ring_for(window_panes: int) -> int:
    """Ring size for a given window width: the default RING covers the
    default W=5; wider windows (--window-panes sweep) grow the ring to
    2W + 6 so W + 4 data panes fit under the open-span bound
    (n_panes + W - 1 <= ring - 3) — a fire near the end of the stream
    genuinely merges W live rows instead of being starved. Depends ONLY
    on the width (never the event count) so a short warmup run compiles
    the same shapes as the timed run; at W=5 this is byte-identical to
    the seed RING."""
    return max(RING, 2 * window_panes + 6)


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
    # incremental fire engine + coalesced ingest counters (deltas)
    for k in ("panes_sealed_total", "batches_coalesced_total",
              "fire_merge_rows_read", "chain_fused_dispatches_total"):
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


def _run_q5(n_keys: int, n_events: int, capacity: int,
            pane_ms: int = 2000, topk: int = 1000, device: bool = True,
            batch: int = BATCH, metrics_registry=None,
            extra_config: dict = None, fire_mode: str = "full",
            window_panes: int = 5, job_name: str = "nexmark-q5"):
    """One env.execute() of the Q5 pipeline; returns (wall_seconds,
    fire_latencies_ms, emitted_rows, stage_breakdown). The stage
    breakdown embeds the device-path metrics snapshot (compiles, cache
    hits, transfer bytes, busy/backpressure ratios).

    ``device=True`` is the TPU-native ingest: batches are born in HBM
    (DataGenSource(device=True)) and the whole per-batch hot loop is one
    compiled dispatch — zero host->device transfers. ``device=False``
    measures the same pipeline with host-generated batches uploaded per
    batch (what any host-resident source pays)."""
    import jax
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
    ring = _ring_for(window_panes)
    n_panes = _n_panes(n_events, batch, max_panes=ring - window_panes - 2)
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
    env.config.set("window.fire.incremental", fire_mode == "incremental")
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
                 watermark_strategy=ws, device=device)
        .key_by("auction")
        .window(SlidingEventTimeWindows.of(window_panes * pane_ms,
                                           pane_ms))
        # BASELINE config #3 is a SUM/COUNT aggregate: rank hot items by
        # bid COUNT (value_bits=31: exact to 2.1e9 events/key/window, and
        # <= 31 selects the int32 count plane + uint32 radix select) and
        # carry the revenue SUM alongside
        .device_aggregate([AggSpec("count", out_name="bids",
                                   value_bits=31),
                           AggSpec("sum", "price", out_name="revenue")],
                          capacity=capacity, ring_size=ring,
                          emit_window_bounds=False, emit_topk=topk,
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
    stages["fire_mode"] = fire_mode
    stages["window_panes"] = window_panes
    stages["max_inflight"] = max((o._max_inflight for o in ops), default=0)
    return wall, lat, sink.rows, stages


def bench_framework_q5(n_keys: int, n_events: int, capacity: int,
                       device: bool = True, fire_mode: str = "full",
                       window_panes: int = 5):
    """Warmup run (compile) + timed run; returns (events/sec, p99 ms,
    stage breakdown). The timed run's ``recompiles`` must be 0: identical
    shapes after warmup hit the program caches, never the compiler."""
    _run_q5(n_keys, min(n_events, 4 * BATCH), capacity, device=device,
            fire_mode=fire_mode,
            window_panes=window_panes)                      # compile warmup
    wall, lat, _rows, stages = _run_q5(n_keys, n_events, capacity,
                                       device=device, fire_mode=fire_mode,
                                       window_panes=window_panes)
    stages["wall"] = wall
    return n_events / wall, _p99(lat), stages


def run_tiny_q5(n_keys: int = 1000, batch: int = 1 << 12,
                n_batches: int = 8, metrics_registry=None,
                chaos_seed=None, extra_config: dict = None,
                fire_mode: str = "full", window_panes: int = 5,
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
                 "isolation.enabled": True,
                 "state.backend.tpu.host-index": False})
        from flink_tpu.cluster.isolation import ISOLATION
        from flink_tpu.runtime.faults import FAULTS
        from flink_tpu.runtime.watchdog import WATCHDOG
        FAULTS.reset()  # arm fresh: visit counters start at zero
        WATCHDOG.reset()
        ISOLATION.reset()  # per-job shed/reject counters start at zero
    _run_q5(n_keys, max(4 * batch, batch), 1 << 14, batch=batch,
            metrics_registry=metrics_registry, extra_config=warm_extra,
            fire_mode=fire_mode, window_panes=window_panes,
            job_name=job_name)                              # compile warmup
    wall, lat, rows, stages = _run_q5(n_keys, n_events, 1 << 14,
                                      batch=batch,
                                      metrics_registry=metrics_registry,
                                      extra_config=extra,
                                      fire_mode=fire_mode,
                                      window_panes=window_panes,
                                      job_name=job_name)
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
              # budget (--tiered does; mid-window evict/prefetch parity
              # is asserted exactly in tests/test_tiering.py)
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


def _run_q7(n_keys: int, n_events: int, capacity: int,
            pane_ms: int = 10_000):
    """Q7 TPU-first: per-window winning bid via packed argmax. The packed
    (price<<20 | bidder) word makes MAX carry the winner's payload, so the
    reference's join-with-MAX-subquery collapses into one keyed max +
    top-1 fire."""
    import jax
    from flink_tpu.api import StreamExecutionEnvironment
    from flink_tpu.core import WatermarkStrategy
    from flink_tpu.core.config import PipelineOptions
    from flink_tpu.core.records import Schema
    from flink_tpu.runtime.operators.device_window import (
        AggSpec, DeviceWindowAggOperator,
    )
    from flink_tpu.window import TumblingEventTimeWindows

    schema = Schema([("auction", np.int64), ("packed", np.int64),
                     ("ts", np.int64)])
    span = _n_panes(n_events) * pane_ms

    def gen(idx):
        u = idx.astype(np.uint64)
        auction = ((u * np.uint64(MULT)) % np.uint64(n_keys)).astype(np.int64)
        price = (idx % 9973) + 1
        bidder = idx % (1 << 20)
        return {"auction": auction,
                "packed": (price << 20) | bidder,
                "ts": (idx * span) // n_events}

    env = StreamExecutionEnvironment.get_execution_environment()
    env.set_state_backend("tpu")
    env.config.set(PipelineOptions.BATCH_SIZE, BATCH)
    ws = WatermarkStrategy.for_monotonous_timestamps() \
        .with_timestamp_column("ts")
    sink = _CountSink()
    (env.datagen(gen, schema, count=n_events, timestamp_column="ts",
                 watermark_strategy=ws, device=True)
        .key_by("auction")
        .window(TumblingEventTimeWindows.of(pane_ms))
        # packed word = (price<<20)|bidder < 2^34: value_bits tightens the
        # fire-time radix top-k to 3 histogram passes
        .device_aggregate([AggSpec("max", "packed", out_name="best",
                                   value_bits=34)],
                          capacity=capacity, ring_size=RING,
                          emit_window_bounds=True, emit_topk=1,
                          defer_overflow=True, async_fire=True)
        .add_sink(sink.fn, "count"))
    t0 = time.perf_counter()
    env.execute("nexmark-q7", timeout=1800.0)
    wall = time.perf_counter() - t0
    ops = _find_ops(env, DeviceWindowAggOperator)
    lat = [ms for o in ops for ms in o.fire_latencies_ms]
    return wall, lat, sink.rows


def bench_framework_q7(n_keys: int, n_events: int, capacity: int):
    _run_q7(n_keys, min(n_events, 4 * BATCH), capacity)     # compile warmup
    wall, lat, _rows = _run_q7(n_keys, n_events, capacity)
    return n_events / wall, _p99(lat)


def bench_framework_q7_join(n_keys: int = 100_000, n_events: int = 1 << 18,
                            pane_ms: int = 10_000, n_panes: int = 8):
    """Q7 with a REAL two-input join in the job: device windowed max per
    auction, joined back against the bid stream through the host
    IntervalJoinOperator (sql/join.py), filtered to price == window max —
    the reference's bids JOIN (SELECT MAX...) shape with the join executed
    as an operator, at host-join scale."""
    from flink_tpu.api import StreamExecutionEnvironment
    from flink_tpu.core import WatermarkStrategy
    from flink_tpu.core.config import PipelineOptions
    from flink_tpu.core.records import Schema
    from flink_tpu.runtime.operators.device_window import AggSpec
    from flink_tpu.sql.join import IntervalJoinOperator
    from flink_tpu.window import TumblingEventTimeWindows

    schema = Schema([("auction", np.int64), ("price", np.int64),
                     ("ts", np.int64)])
    span = n_panes * pane_ms

    def make_gen(count: int):
        def gen(idx):
            u = idx.astype(np.uint64)
            auction = ((u * np.uint64(MULT))
                       % np.uint64(n_keys)).astype(np.int64)
            return {"auction": auction, "price": (idx % 9973) + 1,
                    "ts": (idx * span) // count}
        return gen

    def build(env, count: int):
        ws = WatermarkStrategy.for_monotonous_timestamps() \
            .with_timestamp_column("ts")
        bids = env.datagen(make_gen(count), schema, count=count,
                           timestamp_column="ts", watermark_strategy=ws)
        maxes = (bids.key_by("auction")
                 .window(TumblingEventTimeWindows.of(pane_ms))
                 .device_aggregate([AggSpec("max", "price",
                                            out_name="maxprice")],
                                   capacity=1 << 18, ring_size=RING,
                                   emit_window_bounds=False))
        out_schema = Schema([("m_auction", np.int64),
                             ("maxprice", np.int64),
                             ("auction", np.int64), ("price", np.int64),
                             ("ts", np.int64)])

        def join_factory():
            # max row ts = window_end - 1; matching bids lie within
            # [end - pane, end - 1] -> offsets [-(pane-1), 0].
            # rows_per_key sized to the retention window (~3 bids per
            # auction per pane at this key/event ratio; 32 = 10x slack):
            # the [capacity, rows_per_key, C] block is the state the
            # per-batch scatter and per-watermark prune touch
            return IntervalJoinOperator(0, 0, -(pane_ms - 1), 0,
                                        out_schema, rows_per_key=32,
                                        store_capacity=1 << 18,
                                        name="q7-join")

        joined = maxes.connect(bids).transform("q7-join", join_factory)
        sink = _CountSink()
        from flink_tpu.runtime.operators.simple import BatchFnOperator

        def is_winner(batch):
            mask = (np.asarray(batch.column("price"))
                    == np.asarray(batch.column("maxprice")))
            return batch.take(np.flatnonzero(mask))

        (joined.transform("is-winner",
                          lambda: BatchFnOperator(is_winner, "is-winner"))
               .add_sink(sink.fn, "count"))
        return sink

    def run(count: int) -> float:
        env = StreamExecutionEnvironment.get_execution_environment()
        env.set_state_backend("tpu")
        env.config.set(PipelineOptions.BATCH_SIZE, 1 << 15)
        sink = build(env, count)
        t0 = time.perf_counter()
        env.execute("nexmark-q7-join", timeout=1800.0)
        wall = time.perf_counter() - t0
        if sink.rows == 0:
            raise RuntimeError("q7 join produced no winners")
        return count / wall

    run(min(1 << 16, n_events))                         # compile warmup
    return run(n_events)


# ----------------------------------------------------------------------
# kernel ceiling (raw jitted step, no framework)
# ----------------------------------------------------------------------

def bench_device() -> float:
    import jax
    import jax.numpy as jnp
    from flink_tpu.ops.hash_table import ensure_x64, lookup_or_insert, \
        make_table
    from flink_tpu.ops.segment_ops import make_accumulator, scatter_fold

    ensure_x64()

    @jax.jit
    def step(table, count_acc, sum_acc, keys, values, panes):
        table, slots, ok = lookup_or_insert(table, keys)
        ring_idx = jnp.where(ok, panes % RING, 0).astype(jnp.int32)
        flat = ring_idx * CAPACITY + jnp.maximum(slots, 0)
        count_acc = scatter_fold(
            "count", count_acc.reshape(-1), flat,
            jnp.ones(keys.shape[0], jnp.int64), ok).reshape(RING, CAPACITY)
        sum_acc = scatter_fold(
            "sum", sum_acc.reshape(-1), flat, values,
            ok).reshape(RING, CAPACITY)
        return table, count_acc, sum_acc

    rng = np.random.default_rng(42)
    # zipf-ish hot-key skew like Nexmark auction bids
    raw = rng.zipf(1.1, size=(N_BATCHES, BATCH)).astype(np.int64)
    keys_h = raw % N_KEYS
    vals_h = rng.random((N_BATCHES, BATCH), np.float32)
    panes_h = rng.integers(0, RING, (N_BATCHES, BATCH), np.int64)
    dev = jax.devices()[0]
    keys = [jax.device_put(jnp.asarray(k), dev) for k in keys_h]
    vals = [jax.device_put(jnp.asarray(v), dev) for v in vals_h]
    panes = [jax.device_put(jnp.asarray(p), dev) for p in panes_h]

    table = jax.device_put(make_table(CAPACITY), dev)
    count_acc = jax.device_put(
        make_accumulator("count", (RING, CAPACITY), jnp.int64), dev)
    sum_acc = jax.device_put(
        make_accumulator("sum", (RING, CAPACITY), jnp.float32), dev)

    state = [table, count_acc, sum_acc]
    for i in range(WARMUP):
        j = i % N_BATCHES
        state = list(step(*state, keys[j], vals[j], panes[j]))
    jax.block_until_ready(state[0])

    def window(w: int) -> float:
        t0 = time.perf_counter()
        for i in range(WINDOW_ITERS):
            j = (w * WINDOW_ITERS + i) % N_BATCHES
            state[:] = step(*state, keys[j], vals[j], panes[j])
        jax.block_until_ready(tuple(state))
        return WINDOW_ITERS * BATCH / (time.perf_counter() - t0)

    return _median_window_eps(window)


# ----------------------------------------------------------------------
# host baselines (per-record dict loops; heap-backend analog)
# ----------------------------------------------------------------------

def bench_host() -> float:
    rng = np.random.default_rng(42)
    keys = (rng.zipf(1.1, size=HOST_EVENTS).astype(np.int64)
            % N_KEYS).tolist()
    vals = rng.random(HOST_EVENTS).tolist()
    panes = rng.integers(0, RING, HOST_EVENTS).tolist()
    state: dict = {}
    t0 = time.perf_counter()
    for k, v, p in zip(keys, vals, panes):
        acc = state.get((k, p))
        if acc is None:
            state[(k, p)] = [1, v]
        else:
            acc[0] += 1
            acc[1] += v
    dt = time.perf_counter() - t0
    return HOST_EVENTS / dt


def bench_host_q7() -> float:
    rng = np.random.default_rng(7)
    prices = rng.integers(0, 1 << 40, HOST_EVENTS).tolist()
    bidders = rng.integers(0, 1 << 20, HOST_EVENTS).tolist()
    panes = rng.integers(0, RING, HOST_EVENTS).tolist()
    best: dict = {}
    t0 = time.perf_counter()
    for p, b, w in zip(prices, bidders, panes):
        cur = best.get(w)
        if cur is None or p > cur[0]:
            best[w] = (p, b)
    dt = time.perf_counter() - t0
    return HOST_EVENTS / dt


def bench_wordcount(n_events: int = 500_000) -> float:
    """BASELINE config #1: streaming WordCount, 5s tumbling event-time
    window, one task manager, HOST (CPU) operator path — the reference's
    flink-examples WordCount.java shape. Words are strings (object
    columns) through the hashmap backend: this measures the per-row host
    fallback path that session windows / CEP / non-integer keys take."""
    from flink_tpu.api import StreamExecutionEnvironment
    from flink_tpu.core import WatermarkStrategy
    from flink_tpu.core.config import PipelineOptions
    from flink_tpu.core.records import Schema
    from flink_tpu.window import TumblingEventTimeWindows

    vocab = np.array([f"word{i:04d}" for i in range(5000)], dtype=object)
    schema = Schema([("word", object), ("one", np.int64),
                     ("ts", np.int64)])
    span_ms = 40_000   # 8 windows of 5s

    def gen(idx):
        u = (idx.astype(np.uint64) * np.uint64(MULT))
        return {"word": vocab[(u % np.uint64(5000)).astype(np.int64)],
                "one": np.ones(len(idx), np.int64),
                "ts": (idx * span_ms) // n_events}

    env = StreamExecutionEnvironment.get_execution_environment()
    env.set_state_backend("hashmap")
    env.config.set(PipelineOptions.BATCH_SIZE, 1 << 15)
    ws = WatermarkStrategy.for_monotonous_timestamps() \
        .with_timestamp_column("ts")
    sink = _CountSink()
    (env.datagen(gen, schema, count=n_events, timestamp_column="ts",
                 watermark_strategy=ws)
        .key_by("word")
        .window(TumblingEventTimeWindows.of(5000))
        .sum("one")
        .add_sink(sink.fn, "count"))
    t0 = time.perf_counter()
    env.execute("wordcount", timeout=1800.0)
    wall = time.perf_counter() - t0
    if sink.rows == 0:
        raise RuntimeError("wordcount produced no windows")
    return n_events / wall


def bench_session(n_events: int = 1 << 21, n_keys: int = 100_000,
                  device: bool = True) -> float:
    """Session windows at 100K keys (VERDICT r3 #5 'done' criterion):
    device session-lane operator vs the host merging WindowOperator.
    ``device=False`` runs the host path on a smaller stream (it is
    per-record Python); both report raw events/sec."""
    from flink_tpu.core.functions import AggregateFunction
    from flink_tpu.core.records import RecordBatch, Schema
    from flink_tpu.runtime import OneInputOperatorTestHarness
    from flink_tpu.window import EventTimeSessionWindows

    schema = Schema([("k", np.int64), ("v", np.int64)])
    rng = np.random.default_rng(0)
    n = n_events if device else min(n_events, 1 << 17)
    keys = rng.integers(0, n_keys, n).astype(np.int64)
    vals = rng.integers(1, 100, n).astype(np.int64)
    ts = np.sort(rng.integers(0, 200_000, n)).astype(np.int64)
    gap, B = 5000, 1 << 16
    if device:
        from flink_tpu.runtime.operators.device_session import (
            DeviceSessionWindowOperator,
        )
        from flink_tpu.runtime.operators.device_window import AggSpec

        op = DeviceSessionWindowOperator(
            gap, "k", [AggSpec("sum", "v", out_name="total")],
            capacity=1 << 18, lanes=4)
    else:
        from flink_tpu.runtime.operators import WindowOperator

        class _Sum(AggregateFunction):
            def create_accumulator(self): return 0
            def add(self, value, acc): return acc + value[1]
            def merge(self, a, b): return a + b
            def get_result(self, acc): return acc

        op = WindowOperator(
            EventTimeSessionWindows.with_gap(gap),
            lambda b: np.asarray(b.column("k")), aggregate=_Sum())
    h = OneInputOperatorTestHarness(op, schema)
    t0 = time.perf_counter()
    for i in range(0, n, B):
        h.process_batch(RecordBatch(
            schema, {"k": keys[i:i + B], "v": vals[i:i + B]},
            ts[i:i + B]))
        h.process_watermark(int(ts[min(i + B, n) - 1]) - 1000)
    h.process_watermark(1 << 40)
    return n / (time.perf_counter() - t0)


def bench_tpch_q1(n_rows: int = 1 << 22, backend: str = "tpu",
                  warmup: bool = True) -> float:
    """BASELINE config #5: TPC-H Q1 streaming GROUP BY through the SQL
    layer. ``backend="tpu"`` routes the changelog aggregation onto device
    accumulator planes (sql/device_group_agg.py — one fused scatter-fold
    program per micro-batch); ``backend=""`` measures the host two-phase
    local/global path (StreamExecLocalGroupAggregate shape)."""
    from flink_tpu.api import StreamExecutionEnvironment
    from flink_tpu.core.config import PipelineOptions
    from flink_tpu.core.records import Schema
    from flink_tpu.sql import TableEnvironment

    if warmup:
        bench_tpch_q1(4 * BATCH, backend=backend, warmup=False)

    schema = Schema([("l_returnflag", np.int64), ("l_linestatus", np.int64),
                     ("l_quantity", np.float64),
                     ("l_extendedprice", np.float64),
                     ("l_discount", np.float64), ("l_tax", np.float64),
                     ("l_shipdate", np.int64)])

    def gen(idx):
        u = idx.astype(np.uint64) * np.uint64(MULT)
        return {"l_returnflag": (u % np.uint64(3)).astype(np.int64),
                "l_linestatus": ((u >> np.uint64(8)) % np.uint64(2)).astype(
                    np.int64),
                "l_quantity": ((idx % 50) + 1).astype(np.float64),
                "l_extendedprice": ((idx % 9973) + 1).astype(np.float64),
                "l_discount": (idx % 11).astype(np.float64) / 100.0,
                "l_tax": (idx % 9).astype(np.float64) / 100.0,
                "l_shipdate": 19980101 + (idx % 1400)}

    env = StreamExecutionEnvironment.get_execution_environment()
    if backend:
        env.set_state_backend(backend)
    env.config.set(PipelineOptions.BATCH_SIZE, BATCH)
    t_env = TableEnvironment(env)
    ds = env.datagen(gen, schema, count=n_rows)
    t_env.create_temporary_view("lineitem", ds, schema)
    t0 = time.perf_counter()
    res = t_env.execute_sql(
        "SELECT l_returnflag, l_linestatus, SUM(l_quantity) sq, "
        "SUM(l_extendedprice) sp, "
        "SUM(l_extendedprice * (1 - l_discount)) sd, "
        "SUM(l_extendedprice * (1 - l_discount) * (1 + l_tax)) sc, "
        "AVG(l_quantity) aq, AVG(l_extendedprice) ap, AVG(l_discount) ad, "
        "COUNT(*) co FROM lineitem WHERE l_shipdate <= 19980902 "
        "GROUP BY l_returnflag, l_linestatus")
    final = res.collect_final()
    wall = time.perf_counter() - t0
    if len(final) != 6:
        raise RuntimeError(f"tpch q1 produced {len(final)} groups")
    return n_rows / wall


def _line(metric, value, unit, vs, **extra):
    rec = {"metric": metric, "value": round(value, 2), "unit": unit,
           "vs_baseline": round(vs, 2)}
    rec.update(extra)
    print(json.dumps(rec))
    sys.stdout.flush()


def _print_breakdown(stages: dict, prefix: str) -> None:
    wall = stages.get("wall", 0.0)
    for k in ("source_read", "source_emit", "window_ingest", "window_fire",
              "window_drain"):
        if k in stages:
            _line(f"{prefix}_stage_{k}_ms", stages[k] * 1e3, "ms",
                  stages[k] / wall if wall else 0.0)
    # device-path observability snapshot (cumulative; same series as the
    # prometheus exposition) + this run's recompile delta
    for k, unit in (("compiles", "programs"), ("compile_cache_hits", ""),
                    ("recompiles", "programs"), ("compile_ms", "ms"),
                    ("h2d_bytes", "bytes"), ("d2h_bytes", "bytes"),
                    ("busy_time_ratio", "ratio"),
                    ("backpressured_time_ratio", "ratio"),
                    ("watchdog_trips_total", ""),
                    ("stall_detections_total", "")):
        if k in stages:
            _line(f"{prefix}_{k}", float(stages[k]), unit, 1.0)


def main(breakdown: bool = False):
    """Every line is one JSON object; the LAST line is the headline Q5
    metric. Exits non-zero without a TPU."""
    _start(require_tpu=True)
    host_eps = bench_host()
    eps, p99, stages = bench_framework_q5(N_KEYS, 1 << 23, CAPACITY)
    if breakdown:
        _print_breakdown(stages, "q5_1M")
        _line("nexmark_q5_framework_p99_fire_latency_1M_keys", p99,
              "ms", 1.0)
    _line("nexmark_q5_framework_events_per_sec_1M_keys", eps,
          "events/sec/chip", eps / host_eps)
    _maybe_write_trace("q5")
    _maybe_write_profile("q5")
    return eps, p99, stages, host_eps


def suite() -> None:
    """Extended matrix (one JSON line per metric) — `python bench.py
    --suite`. Exits non-zero without a TPU."""
    _start(require_tpu=True)
    host_eps = bench_host()

    wc_eps = bench_wordcount()
    _line("wordcount_host_events_per_sec", wc_eps, "events/sec", 1.0)

    eps, p99, stages = bench_framework_q5(N_KEYS, 1 << 23, CAPACITY)
    _line("nexmark_q5_framework_events_per_sec_1M_keys", eps,
          "events/sec/chip", eps / host_eps)
    _line("nexmark_q5_framework_p99_fire_latency_1M_keys", p99, "ms", 1.0)
    _print_breakdown(stages, "q5_1M")

    # host-resident ingest variant: what a source whose data is born on
    # host pays in per-batch uploads
    host_in_eps, _p, _s = bench_framework_q5(N_KEYS, 1 << 22, CAPACITY,
                                             device=False)
    _line("nexmark_q5_framework_host_ingest_events_per_sec_1M_keys",
          host_in_eps, "events/sec/chip", host_in_eps / host_eps)

    eps10, p99_10, stages10 = bench_framework_q5(10_000_000, 1 << 25,
                                                 1 << 24)
    _line("nexmark_q5_framework_events_per_sec_10M_keys", eps10,
          "events/sec/chip", eps10 / host_eps)
    _line("nexmark_q5_framework_p99_fire_latency_10M_keys", p99_10,
          "ms", 1.0)
    _print_breakdown(stages10, "q5_10M")

    q7_host = bench_host_q7()
    q7eps, q7p99 = bench_framework_q7(10_000_000, 1 << 25, 1 << 24)
    _line("nexmark_q7_framework_events_per_sec_10M_keys", q7eps,
          "events/sec/chip", q7eps / q7_host)
    _line("nexmark_q7_framework_p99_fire_latency_10M_keys", q7p99,
          "ms", 1.0)

    join_eps = bench_framework_q7_join()
    _line("nexmark_q7_interval_join_events_per_sec", join_eps,
          "events/sec", join_eps / q7_host)

    sess_host = bench_session(device=False)
    sess_dev = bench_session()
    _line("session_window_host_events_per_sec_100K_keys", sess_host,
          "events/sec", 1.0)
    _line("session_window_device_events_per_sec_100K_keys", sess_dev,
          "events/sec/chip", sess_dev / sess_host)

    q1_host = bench_tpch_q1(1 << 21, backend="")
    q1_eps = bench_tpch_q1()
    _line("tpch_q1_streaming_rows_per_sec_host", q1_host, "rows/sec", 1.0)
    _line("tpch_q1_streaming_rows_per_sec", q1_eps, "rows/sec",
          q1_eps / q1_host)

    kernel = bench_device()
    _line("q5_kernel_ceiling_events_per_sec_1M_keys", kernel,
          "events/sec/chip", kernel / host_eps)
    bench_topk_ab()


def bench_topk_ab() -> None:
    """A/B the fire-path top-k: XLA radix select (16-bit digits,
    scatter-add histograms) vs the Pallas kernel (8-bit digits,
    compare-and-count VPU histograms) on identical shapes. Runs under
    --suite only, so on a TPU: the Pallas kernel is compiled, and a
    failure to compile it raises."""
    import jax
    import jax.numpy as jnp

    from flink_tpu.ops.pallas_topk import masked_topk_pallas
    from flink_tpu.ops.topk import masked_topk

    rng = np.random.default_rng(0)
    for cap, label in ((1 << 21, "2M"), (1 << 24, "16M")):
        vals = jnp.asarray(rng.integers(0, 1 << 31, cap).astype(np.int64))
        valid = jnp.asarray(rng.random(cap) < 0.5)

        def timed(fn):
            out = fn(vals, valid, 1000, value_bits=32)
            jax.block_until_ready(out)
            t0 = time.perf_counter()
            for _ in range(5):
                out = fn(vals, valid, 1000, value_bits=32)
            jax.block_until_ready(out)
            return (time.perf_counter() - t0) / 5 * 1e3

        xla_ms = timed(masked_topk)
        _line(f"topk_ab_xla_ms_{label}", xla_ms, "ms", 1.0)
        pl_ms = timed(masked_topk_pallas)
        _line(f"topk_ab_pallas_ms_{label}", pl_ms, "ms",
              xla_ms / pl_ms if pl_ms else 0.0)


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


def tiny(fire_mode: str = "full", window_panes_list=(5,),
         audit: bool = False) -> None:
    """`python bench.py --tiny [--fire-mode full|incremental]
    [--window-panes N[,N...]] [--audit]`: the acceptance probe — one
    JSON line per window width, the tiny Q5 stage report with the
    metrics snapshot embedded. Passing several widths sweeps them
    (seal/fire programs are shared across widths, so only the first
    width compiles). ``--audit`` runs the tpu-lint Tier-B jaxpr audit
    over the programs the run compiled and embeds per-rule finding
    counts."""
    _start()
    for wp in window_panes_list:
        stages = run_tiny_q5(extra_config=_trace_extra_config(),
                             fire_mode=fire_mode, window_panes=wp)
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
#: (what _run_q5 builds) would recompile the chain every execute().
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
    line with both runs inline plus the speedup ratio. The fused timed
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
    rec["fused_speedup"] = round(rec["fused_events_per_sec"]
                                 / rec["unfused_events_per_sec"], 3)
    if "--audit" in sys.argv:
        rec.update(_audit_report())
    print(json.dumps(rec))
    _maybe_write_profile("fused_q5")
    sys.stdout.flush()


def _multichip_worker(n_devices: int, batch: int, steps: int) -> None:
    """Runs in a SUBPROCESS whose XLA_FLAGS pinned the host-platform
    device count before jax initialized (the count is process-start
    fixed): one weak-scaling sharded-window run — constant per-device
    batch, so total work grows with the mesh — printing one JSON line."""
    import jax
    import jax.numpy as jnp

    from flink_tpu.metrics.device import DEVICE_STATS
    from flink_tpu.parallel.mesh import make_mesh
    from flink_tpu.parallel.sharded_window import AggDef, ShardedWindowAgg

    D = n_devices
    if len(jax.devices()) < D:
        print(json.dumps({"n_devices": D, "error":
                          f"only {len(jax.devices())} devices"}))
        return
    agg = ShardedWindowAgg(make_mesh(D),
                           [AggDef("price", "sum", jnp.int64)],
                           capacity=1 << 12, ring=16, max_parallelism=128)
    state = agg.init_state()
    rng = np.random.default_rng(11)
    keys = jnp.asarray(rng.integers(1, 50_000, size=(D, batch)), jnp.int64)
    cols = {"price": jnp.asarray(
        rng.integers(1, 100, size=(D, batch)), jnp.int64)}
    panes = jnp.asarray(rng.integers(0, 16, size=(D, batch)), jnp.int32)
    valid = jnp.ones((D, batch), bool)
    for _ in range(2):                                     # compile warmup
        state, _p, _r = agg.step(state, keys, cols, panes, valid)
    jax.block_until_ready(state)
    before = DEVICE_STATS.snapshot()
    t0 = time.perf_counter()
    for _ in range(steps):
        state, _p, _r = agg.step(state, keys, cols, panes, valid)
    jax.block_until_ready(state)
    wall = time.perf_counter() - t0
    after = DEVICE_STATS.snapshot()
    print(json.dumps({
        "n_devices": D,
        "events_per_sec": round(D * batch * steps / wall, 2),
        "wall_s": round(wall, 4),
        "recompiles": after["compiles"] - before["compiles"]}))


def multichip(device_counts=(1, 2, 4, 8), batch: int = 4096,
              steps: int = 48) -> None:
    """`python bench.py --multichip`: device-count sweep for the sharded
    window path. Each count runs in its own subprocess (the XLA
    host-platform device count is fixed at process start, so a sweep
    cannot reuse one process), always on the CPU backend with simulated
    devices: it pins counts (recompiles, exchange rounds), and its
    events/sec are CPU timings, not speeds. Real chips are driven by ONE
    process over a mesh of all of them (chip_smoke.py's q5-mesh leg).

    Weak scaling, honestly labeled: the per-device batch is constant, so
    ideal behavior is aggregate events/sec equal to the 1-device run
    times the device count divided by the host cores actually available
    — on a single-core CI box every simulated device timeshares one
    core, so the printed ``scaling_efficiency`` is
    eps_total[D] / eps_total[1]: the fraction of throughput SURVIVING
    the exchange + psum collectives as the mesh grows (1.0 = collective
    overhead is invisible). Writes MULTICHIP_r<NN>.json next to the
    other round artifacts, keeping the legacy driver keys."""
    import glob
    import re

    rec = {"n_devices": max(device_counts), "rc": 0, "ok": True,
           "skipped": False, "tail": "",
           "mode": "weak-scaling", "per_device_batch": batch,
           "steps": steps, "device_counts": list(device_counts),
           "events_per_sec": {}, "scaling_efficiency": {},
           "recompiles": {}}
    for n in device_counts:
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        flags = [f for f in env.get("XLA_FLAGS", "").split()
                 if not f.startswith(
                     "--xla_force_host_platform_device_count")]
        flags.append(f"--xla_force_host_platform_device_count={n}")
        env["XLA_FLAGS"] = " ".join(flags)
        cmd = [sys.executable, os.path.abspath(__file__),
               "--multichip-worker", str(n), "--batch", str(batch),
               "--steps", str(steps)]
        # one process per chip: this parent has not initialised a JAX
        # backend (`--multichip` reaches here without importing jax), and
        # the child is pinned to the CPU above, so neither holds a chip
        try:
            p = subprocess.run(cmd, capture_output=True, text=True,
                               timeout=900, env=env)
        except subprocess.TimeoutExpired:
            rec.update(ok=False, rc=124,
                       tail=f"{n}-device worker timed out")
            continue
        line = (p.stdout.strip().splitlines() or [""])[-1]
        try:
            out = json.loads(line)
        except ValueError:
            out = {}
        if p.returncode != 0 or "events_per_sec" not in out:
            rec.update(ok=False, rc=p.returncode or 1,
                       tail=(p.stderr or line)[-400:])
            continue
        rec["events_per_sec"][str(n)] = out["events_per_sec"]
        rec["recompiles"][str(n)] = out.get("recompiles", -1)
    base = rec["events_per_sec"].get(str(device_counts[0]))
    if base:
        for n in device_counts:
            eps = rec["events_per_sec"].get(str(n))
            if eps:
                rec["scaling_efficiency"][str(n)] = round(eps / base, 4)
    rounds = [int(m.group(1)) for f in glob.glob("MULTICHIP_r*.json")
              for m in [re.search(r"_r(\d+)\.json$", f)] if m]
    path = f"MULTICHIP_r{max(rounds, default=0) + 1:02d}.json"
    with open(path, "w") as f:
        json.dump(rec, f, indent=2)
        f.write("\n")
    print(json.dumps({"metric": "multichip_scaling_report",
                      "unit": "report", "path": path, **rec}))
    sys.stdout.flush()


def _coldstart_worker(aot_dir: str, batch: int, n_batches: int) -> None:
    """Runs in a SUBPROCESS (XLA compile caches are process-scoped, so
    cold vs warmed must be separate processes): ONE tiny-Q5 pass — no
    in-process warmup — with the persistent AOT cache pointed at
    ``aot_dir``; prints one JSON line with the time-to-first-fired-window
    and the AOT hit/storm accounting. The first invocation against an
    empty dir is the COLD run (it compiles, and populates the cache);
    the second is the WARMED run (it must not compile at all)."""
    wall, _lat, rows, stages = _run_q5(
        1000, n_batches * batch, 1 << 14, batch=batch,
        extra_config={"aot.enabled": True, "aot.dir": aot_dir})
    first_fire_ms = (stages.get("cold_start_ms_max")
                     or round(wall * 1e3, 1))
    print(json.dumps({
        "first_fire_ms": round(first_fire_ms, 1),
        "wall_s": round(wall, 4),
        "emitted_rows": rows,
        "recompiles": stages.get("recompiles", -1),
        "compile_storms": stages.get("compile_storms_total", -1),
        "aot_hits": stages.get("aot_hits_total", 0),
        "aot_misses": stages.get("aot_misses_total", 0),
        "aot_stores": stages.get("aot_stores_total", 0),
        "aot_fallbacks": stages.get("aot_fallbacks_total", 0)}))


def coldstart(batch: int = 1 << 12, n_batches: int = 8) -> None:
    """`python bench.py --coldstart`: the compile-storm-free recovery
    acceptance drill. Two subprocesses share one persistent AOT cache
    directory: the COLD run starts with an empty cache (every program is
    a live XLA compile, each counted as a compile storm, and each stored
    as a verified artifact); the WARMED run starts a fresh process
    against the populated cache and must reach its first fired window
    with ZERO live compiles (recompiles == 0, compile_storms == 0,
    aot_hits == the cold run's program count). The report's
    ``first_fire_speedup`` is cold/warmed time-to-first-fired-window —
    the acceptance bar is >= 3x on the CPU backend. Results land in
    COLDSTART_rXX.json."""
    import glob
    import re
    import shutil
    import tempfile

    rec = {"metric": "coldstart_report", "unit": "report", "rc": 0,
           "ok": True, "tail": "", "batch": batch, "n_batches": n_batches,
           "runs": {}}
    aot_dir = tempfile.mkdtemp(prefix="flink_tpu_aot_")
    try:
        for label in ("cold", "warmed"):
            env = dict(os.environ)
            env.setdefault("JAX_PLATFORMS", "cpu")
            cmd = [sys.executable, os.path.abspath(__file__),
                   "--coldstart-worker", aot_dir, "--batch", str(batch),
                   "--n-batches", str(n_batches)]
            # one process per chip: this parent has not initialised a JAX
            # backend (`--coldstart` reaches here without importing jax);
            # the two children run one after the other, on the CPU unless
            # the environment names another platform
            try:
                p = subprocess.run(cmd, capture_output=True, text=True,
                                   timeout=900, env=env)
            except subprocess.TimeoutExpired:
                rec.update(ok=False, rc=124,
                           tail=f"{label} worker timed out")
                break
            line = (p.stdout.strip().splitlines() or [""])[-1]
            try:
                out = json.loads(line)
            except ValueError:
                out = {}
            if p.returncode != 0 or "first_fire_ms" not in out:
                rec.update(ok=False, rc=p.returncode or 1,
                           tail=(p.stderr or line)[-400:])
                break
            rec["runs"][label] = out
    finally:
        shutil.rmtree(aot_dir, ignore_errors=True)
    cold, warm = rec["runs"].get("cold"), rec["runs"].get("warmed")
    if cold and warm:
        rec["first_fire_speedup"] = round(
            cold["first_fire_ms"] / max(warm["first_fire_ms"], 1e-9), 2)
        rec["warmed_recompiles"] = warm["recompiles"]
        rec["warmed_compile_storms"] = warm["compile_storms"]
        rec["warmed_aot_hits"] = warm["aot_hits"]
        rec["cold_programs_stored"] = cold["aot_stores"]
        rec["ok"] = bool(rec["ok"]
                         and warm["recompiles"] == 0
                         and warm["compile_storms"] == 0
                         and warm["aot_hits"] > 0
                         and rec["first_fire_speedup"] >= 3.0)
    else:
        rec["ok"] = False
    rounds = [int(m.group(1)) for f in glob.glob("COLDSTART_r*.json")
              for m in [re.search(r"_r(\d+)\.json$", f)] if m]
    path = f"COLDSTART_r{max(rounds, default=0) + 1:02d}.json"
    with open(path, "w") as f:
        json.dump(rec, f, indent=2)
        f.write("\n")
    print(json.dumps({"path": path, **rec}))
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


def tiered(budget_slots: int = 1 << 10, batch: int = 1 << 12,
           n_batches: int = 8) -> None:
    """`python bench.py --tiered`: key-cardinality sweep of the tiny Q5
    stage under a FIXED HBM budget (`state.backend.tpu.hbm-budget-slots`
    = 1024): 1x / 10x / 100x the budget-resident key count, so the 100x
    point runs with ~99% of keys host-warm. One JSON line per point with
    events/sec, the recompile count (must stay 0 — residency changes
    never retrace), and the tier counters (evictions, prefetches, hot
    hit ratio, HBM bytes). The acceptance bar: the 100x point holds
    within 2x of the ALL-RESIDENT baseline at the same cardinality.
    Results land in TIERED_rXX.json."""
    _start()
    base_keys = budget_slots // 2  # resident working set incl. headroom
    rec = {"metric": "nexmark_q5_tiered_sweep", "unit": "report",
           "budget_slots": budget_slots, "base_keys": base_keys,
           "points": {}}
    for mult in (1, 10, 100):
        n_keys = base_keys * mult
        stages = run_tiny_q5(
            n_keys=n_keys, batch=batch, n_batches=n_batches,
            extra_config={
                "state.backend.tpu.hbm-budget-slots": budget_slots,
                # residency changes apply at watermark boundaries; the
                # tiny stage finishes in well under the default 200ms
                # watermark interval, so tighten it to give the prefetch
                # pipeline boundaries to stage + apply promotions at
                "pipeline.auto-watermark-interval": 0.005})
        point = {"n_keys": n_keys,
                 "events_per_sec": stages["events_per_sec"],
                 "recompiles": stages.get("recompiles", 0),
                 "tier_evictions": stages.get("tier_evictions_total", 0),
                 "tier_prefetches": stages.get("tier_prefetches_total", 0),
                 "tier_hot_hit_ratio": stages.get("tier_hot_hit_ratio", 0),
                 "tier_hbm_bytes": stages.get("tier_hbm_bytes_in_use", 0)}
        rec["points"][f"{mult}x"] = point
        print(json.dumps({"metric": "nexmark_q5_tiered_point",
                          "unit": "events/sec", **point}))
        sys.stdout.flush()
    # all-resident baseline at the 100x cardinality (no budget): the
    # tiered run must hold >= 0.5x of this rate
    baseline = run_tiny_q5(n_keys=base_keys * 100, batch=batch,
                           n_batches=n_batches)
    rec["baseline_events_per_sec"] = baseline["events_per_sec"]
    eps100 = rec["points"]["100x"]["events_per_sec"]
    rec["ratio_100x_vs_all_resident"] = round(
        eps100 / baseline["events_per_sec"], 4)
    rec["within_2x"] = rec["ratio_100x_vs_all_resident"] >= 0.5
    import glob
    import re
    rounds = [int(m.group(1)) for f in glob.glob("TIERED_r*.json")
              for m in [re.search(r"_r(\d+)\.json$", f)] if m]
    path = f"TIERED_r{max(rounds, default=0) + 1:02d}.json"
    with open(path, "w") as f:
        json.dump(rec, f, indent=2)
        f.write("\n")
    print(json.dumps({"metric": "nexmark_q5_tiered_report",
                      "unit": "report", "path": path,
                      "baseline_events_per_sec":
                          rec["baseline_events_per_sec"],
                      "ratio_100x_vs_all_resident":
                          rec["ratio_100x_vs_all_resident"],
                      "within_2x": rec["within_2x"]}))
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
    _fire_mode = "full"
    if "--fire-mode" in sys.argv:
        i = sys.argv.index("--fire-mode")
        _fire_mode = sys.argv[i + 1]
        if _fire_mode not in ("full", "incremental"):
            raise SystemExit(f"--fire-mode must be full|incremental, "
                             f"got {_fire_mode!r}")
    _window_panes = (5,)
    if "--window-panes" in sys.argv:
        i = sys.argv.index("--window-panes")
        _window_panes = tuple(int(w) for w in sys.argv[i + 1].split(","))
    if "--multichip-worker" in sys.argv:
        i = sys.argv.index("--multichip-worker")
        _n = int(sys.argv[i + 1])
        _b = (int(sys.argv[sys.argv.index("--batch") + 1])
              if "--batch" in sys.argv else 4096)
        _s = (int(sys.argv[sys.argv.index("--steps") + 1])
              if "--steps" in sys.argv else 48)
        _multichip_worker(_n, _b, _s)
    elif "--multichip" in sys.argv:
        multichip()
    elif "--coldstart-worker" in sys.argv:
        i = sys.argv.index("--coldstart-worker")
        _d = sys.argv[i + 1]
        _b = (int(sys.argv[sys.argv.index("--batch") + 1])
              if "--batch" in sys.argv else 1 << 12)
        _nb = (int(sys.argv[sys.argv.index("--n-batches") + 1])
               if "--n-batches" in sys.argv else 8)
        _coldstart_worker(_d, _b, _nb)
    elif "--coldstart" in sys.argv:
        coldstart()
    elif "--suite" in sys.argv:
        suite()
    elif "--tiny" in sys.argv:
        tiny(fire_mode=_fire_mode, window_panes_list=_window_panes,
             audit="--audit" in sys.argv)
    elif "--fused" in sys.argv:
        fused()
    elif "--audit" in sys.argv:
        # audit alone: the tiny acceptance probe with the jaxpr audit on
        tiny(fire_mode=_fire_mode, window_panes_list=_window_panes,
             audit=True)
    elif "--tiered" in sys.argv:
        tiered()
    elif "--chaos" in sys.argv:
        i = sys.argv.index("--chaos")
        chaos(int(sys.argv[i + 1]) if len(sys.argv) > i + 1 else 0)
    elif "--two-jobs" in sys.argv:
        two_jobs()
    else:
        main(breakdown="--breakdown" in sys.argv)
