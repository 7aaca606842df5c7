"""Device-time ledger tests (metrics/profiler.py):

* rollup math — per-job / per-site / per-operator attribution, shares,
  percentile windows, EWMA rate, compile-vs-dispatch charging,
* the seeded concurrent record/scrape drill — N writer threads against
  scrape threads, deterministic totals, no torn reads (every snapshot's
  job rollups must sum to its own total),
* forced-recompile attribution through instrumented_program_cache —
  the record names the exact changed shape dimension,
* the scrape surfaces — prometheus _bucket histogram lines, per-job
  ledger gauges, bind_ledger_metrics, chrome-trace counter tracks, the
  profile CLI — and the tier_hot_hit_ratio ring (state residency).
"""

import json
import random
import threading

import numpy as np
import pytest

from flink_tpu.metrics.core import MetricRegistry
from flink_tpu.metrics.profiler import (
    DEVICE_LEDGER,
    DeviceLedger,
    LEDGER_SITE_INVENTORY,
    ProgramKey,
    bind_ledger_metrics,
    clear_dispatch_context,
    dispatch_context,
    set_dispatch_context,
)
from flink_tpu.metrics.reporters import prometheus_text
from flink_tpu.metrics.tracing import chrome_trace_events


@pytest.fixture
def ledger():
    """A fresh, enabled, process-local ledger."""
    led = DeviceLedger()
    led.enabled = True
    return led


@pytest.fixture
def global_ledger():
    """The process-global ledger, enabled and isolated for one test."""
    was = DEVICE_LEDGER.enabled
    DEVICE_LEDGER.reset()
    DEVICE_LEDGER.enabled = True
    clear_dispatch_context()
    yield DEVICE_LEDGER
    DEVICE_LEDGER.enabled = was
    DEVICE_LEDGER.reset()
    clear_dispatch_context()


# ---------------------------------------------------------------------------
# Recording + rollups
# ---------------------------------------------------------------------------


def test_disabled_ledger_records_nothing():
    led = DeviceLedger()
    assert not led.enabled
    led.record("device_window.step", 5.0, job="j", operator="op")
    led.note_build("device_window.step", "k", lambda n: n, (1,), {})
    snap = led.snapshot()
    assert snap["entries"] == 0
    assert snap["device_ms_total"] == 0.0
    assert led.profile()["programs"] == []


def test_rollups_by_job_site_and_operator(ledger):
    ledger.record("device_window.step", 2.0, shape_sig="a",
                  job="j1", operator="win")
    ledger.record("device_window.step", 3.0, shape_sig="a",
                  job="j1", operator="win")
    ledger.record("device_window.fire", 5.0, shape_sig="b",
                  job="j1", operator="win")
    ledger.record("mesh.step", 7.0, shape_sig="c", job="j2", operator="mesh")
    ledger.record("device_window.step", 11.0, shape_sig="a", kind="compile",
                  job="j1", operator="win")
    snap = ledger.snapshot()
    assert snap["entries"] == 3
    assert snap["dispatches_total"] == 4
    assert snap["device_ms_total"] == pytest.approx(17.0)
    assert snap["compile_ms_total"] == pytest.approx(11.0)
    assert snap["jobs"]["j1"]["device_ms"] == pytest.approx(10.0)
    assert snap["jobs"]["j1"]["compile_ms"] == pytest.approx(11.0)
    assert snap["jobs"]["j1"]["dispatches"] == 3
    assert snap["jobs"]["j2"]["device_ms"] == pytest.approx(7.0)
    assert snap["sites"]["device_window.step"]["device_ms"] \
        == pytest.approx(5.0)
    assert snap["sites"]["device_window.step"]["count"] == 2
    assert snap["operators"]["win"]["device_ms"] == pytest.approx(10.0)
    assert snap["operators"]["mesh"]["count"] == 1


def test_profile_shares_ordering_and_job_filter(ledger):
    ledger.record("device_window.step", 1.0, shape_sig="a",
                  job="j1", operator="win")
    ledger.record("device_window.fire", 9.0, shape_sig="b",
                  job="j1", operator="win")
    ledger.record("mesh.step", 4.0, shape_sig="c", job="j2", operator="mesh")
    prof = ledger.profile(top=10)
    assert prof["total_device_ms"] == pytest.approx(14.0)
    sites = [p["site"] for p in prof["programs"]]
    assert sites[0] == "device_window.fire"  # hottest first
    assert sum(p["share"] for p in prof["programs"]) == pytest.approx(1.0)
    assert sum(o["share"] for o in prof["operators"]) == pytest.approx(1.0)
    # top-K truncates the program table, not the totals
    top1 = ledger.profile(top=1)
    assert len(top1["programs"]) == 1
    assert top1["total_device_ms"] == pytest.approx(14.0)
    # job filter keeps only that job's programs and shares re-normalise
    j2 = ledger.profile(job="j2")
    assert [p["site"] for p in j2["programs"]] == ["mesh.step"]
    assert j2["programs"][0]["share"] == pytest.approx(1.0)


def test_percentiles_max_and_clamping(ledger):
    for ms in range(1, 101):
        ledger.record("ops.pallas_topk", float(ms), shape_sig="s",
                      job="j", operator="topk")
    ledger.record("ops.pallas_topk", -5.0, shape_sig="s",
                  job="j", operator="topk")  # clock skew clamps to 0
    row = ledger.profile(top=1)["programs"][0]
    assert row["max_ms"] == pytest.approx(100.0)
    assert 45.0 <= row["p50_ms"] <= 55.0
    assert 90.0 <= row["p95_ms"] <= 100.0
    assert row["self_ms"] == pytest.approx(sum(range(1, 101)))
    assert row["count"] == 101
    assert row["ewma_ms"] >= 0.0


def test_reservoir_is_bounded(ledger):
    ledger.reservoir = 4
    for ms in (1.0, 2.0, 3.0, 4.0, 100.0):
        ledger.record("mesh.fire", ms, shape_sig="s", job="j", operator="m")
    row = ledger.profile(top=1)["programs"][0]
    # window kept the last 4 samples; lifetime max is still exact
    assert row["p50_ms"] >= 2.0
    assert row["max_ms"] == pytest.approx(100.0)


def test_dispatch_context_is_thread_local(ledger):
    set_dispatch_context("jobA", "opA")
    try:
        assert dispatch_context() == ("jobA", "opA")
        seen = {}

        def other():
            seen["ctx"] = dispatch_context()

        t = threading.Thread(target=other)
        t.start()
        t.join()
        assert seen["ctx"] == ("", "")  # context never leaks across threads
        ledger.record("transfer.h2d", 1.0, nbytes=64)
        key = ProgramKey("jobA", "opA", "transfer.h2d", "")
        assert key in ledger._entries
    finally:
        clear_dispatch_context()


def test_transfer_cost_model_byte_fallback(ledger):
    ledger.cost_gbps = 10.0
    ledger.record("transfer.h2d", 2.0, nbytes=10 * 1000 * 1000,
                  job="j", operator="src")
    row = ledger.profile(top=1)["programs"][0]
    # transfers have no jaxpr: bytes/gbps IS the estimate (1.0 ms here)
    assert row["est_ms"] == pytest.approx(1.0)
    assert row["achieved_vs_estimated"] == pytest.approx(2.0)


def test_configure_applies_profiler_options():
    from flink_tpu.core.config import Configuration, ProfilerOptions

    config = Configuration()
    config.set(ProfilerOptions.ENABLED, True)
    config.set(ProfilerOptions.RESERVOIR, 8)
    config.set(ProfilerOptions.RECOMPILE_HISTORY, 5)
    config.set(ProfilerOptions.EWMA_ALPHA, 0.5)
    config.set(ProfilerOptions.TRACE_SAMPLES, 16)
    config.set(ProfilerOptions.COST_GFLOPS, 123.0)
    config.set(ProfilerOptions.COST_GBPS, 45.0)
    led = DeviceLedger()
    led.configure(config)
    assert led.enabled
    assert led.reservoir == 8
    assert led._recompiles.maxlen == 5
    assert led.ewma_alpha == 0.5
    assert led._samples.maxlen == 16
    assert led.cost_gflops == 123.0
    assert led.cost_gbps == 45.0


# ---------------------------------------------------------------------------
# Seeded concurrent record/scrape drill (satellite: no torn reads)
# ---------------------------------------------------------------------------


def test_concurrent_record_scrape_deterministic_totals(ledger):
    rng = random.Random(20260806)
    writers, per_writer = 4, 250
    plans = [[round(rng.uniform(0.1, 5.0), 3) for _ in range(per_writer)]
             for _ in range(writers)]
    sites = ["device_window.step", "mesh.step",
             "chain.fused_step", "transfer.d2h"]
    start = threading.Barrier(writers + 2)
    done = threading.Event()
    torn = []

    def write(i):
        start.wait()
        for ms in plans[i]:
            ledger.record(sites[i], ms, shape_sig=f"sig{i}",
                          job=f"job{i % 2}", operator=f"op{i}")

    def scrape():
        start.wait()
        while not done.is_set():
            snap = ledger.snapshot()
            jobs_sum = sum(j["device_ms"] for j in snap["jobs"].values())
            # every scrape copies under the ledger lock: its own rollups
            # must always agree with its own total
            if abs(jobs_sum - snap["device_ms_total"]) > 1e-9:
                torn.append((jobs_sum, snap["device_ms_total"]))
            ledger.profile(top=3)
            ledger.trace_counters()

    threads = [threading.Thread(target=write, args=(i,))
               for i in range(writers)]
    scrapers = [threading.Thread(target=scrape) for _ in range(2)]
    for t in threads + scrapers:
        t.start()
    for t in threads:
        t.join()
    done.set()
    for t in scrapers:
        t.join()
    assert torn == []
    snap = ledger.snapshot()
    assert snap["dispatches_total"] == writers * per_writer
    expected = sum(sum(p) for p in plans)
    assert snap["device_ms_total"] == pytest.approx(expected)
    assert snap["entries"] == writers
    for i in range(writers):
        assert snap["sites"][sites[i]]["device_ms"] \
            == pytest.approx(sum(plans[i]))


# ---------------------------------------------------------------------------
# Recompile attribution (acceptance: names the exact changed dimension)
# ---------------------------------------------------------------------------


def test_forced_recompile_names_exact_changed_dimension(global_ledger):
    from flink_tpu.metrics.device import instrumented_program_cache

    built = []

    # not a string literal at the call site: this throwaway scope must
    # stay invisible to the TPU305 ledger-site inventory lock
    scope = "test." + "recompile_drill"

    def builder(shape, fill):
        built.append(shape)
        return lambda: np.full(shape, fill)

    cache = instrumented_program_cache(scope)(builder)
    cache((8, 64), 0)
    cache((8, 64), 0)          # cache hit: no build, no attribution
    cache((8, 128), 0)         # forced recompile: one dim changed
    assert built == [(8, 64), (8, 128)]

    recs = global_ledger.profile()["recompiles"]
    assert len(recs) == 1
    rec = recs[0]
    assert rec["site"] == scope
    # the record names the exact changed tuple element, nothing else
    assert rec["changed"] == ["shape[1]: 64 -> 128"]
    assert rec["prior_key"] != rec["key"]

    # attribution never spends the DEVICE_STATS recompile budget twice:
    # the ledger keeps its own count out of snapshot()'s compile totals
    assert global_ledger.snapshot()["recompiles_attributed"] == 1


def test_first_dispatch_charged_as_compile(global_ledger):
    from flink_tpu.metrics.device import instrumented_program_cache

    scope = "test." + "compile_charge"
    cache = instrumented_program_cache(scope)(
        lambda n: (lambda: np.zeros(n)))
    prog = cache(4)
    prog()       # first dispatch: trace/lower/compile charge
    prog()       # steady state dispatch
    prog()
    rows = [r for r in global_ledger.profile(top=20)["programs"]
            if r["site"] == scope]
    assert len(rows) == 1
    assert rows[0]["compiles"] == 1
    assert rows[0]["count"] == 2
    assert rows[0]["compile_ms"] >= 0.0


def test_recompile_diff_handles_absent_and_scalar_args(ledger):
    def builder(n, mode="sum"):
        return n

    ledger.note_build("mesh.fire", "k1", builder, (64,), {})
    ledger.note_build("mesh.fire", "k2", builder, (64,), {"mode": "max"})
    recs = ledger.profile()["recompiles"]
    assert len(recs) == 1
    assert recs[0]["changed"] == ["mode: 'sum' -> 'max'"]


# ---------------------------------------------------------------------------
# Scrape surfaces: prometheus, registry gauges, chrome-trace counters, CLI
# ---------------------------------------------------------------------------


def test_prometheus_histogram_bucket_lines():
    reg = MetricRegistry()
    h = reg.root().group("job").histogram("latency")
    for v in (0.5, 5.0, 50.0, 5000.0):
        h.update(v)
    text = prometheus_text(reg)
    bucket_lines = [ln for ln in text.splitlines()
                    if ln.startswith("flink_tpu_job_latency_bucket{")]
    assert bucket_lines, text
    counts = [float(ln.rpartition(" ")[2]) for ln in bucket_lines]
    # cumulative: monotone non-decreasing, +Inf bucket == observation count
    assert counts == sorted(counts)
    assert 'le="+Inf"' in bucket_lines[-1]
    assert counts[-1] == 4.0
    assert "# TYPE flink_tpu_job_latency_bucket histogram" in text
    # hardening contract: every sample line still float-parses
    for ln in text.splitlines():
        if ln and not ln.startswith("#"):
            float(ln.rpartition(" ")[2])


def test_prometheus_ledger_job_rollups(global_ledger):
    reg = MetricRegistry()
    assert "flink_tpu_profiler_job_device_ms" not in prometheus_text(reg)
    global_ledger.record("mesh.step", 3.5, shape_sig="s",
                         job='job"q5\n', operator="win")
    text = prometheus_text(reg)
    # label values are escaped, never raw (quote + newline in the name)
    assert 'flink_tpu_profiler_job_device_ms{job="job\\"q5\\n"} 3.5' in text
    assert 'flink_tpu_profiler_job_dispatches{job="job\\"q5\\n"} 1' in text
    global_ledger.enabled = False
    assert "flink_tpu_profiler_job_device_ms" not in prometheus_text(reg)


def test_bind_ledger_metrics_gauges(global_ledger):
    reg = MetricRegistry()
    bind_ledger_metrics(reg)
    global_ledger.record("mesh.step", 2.0, shape_sig="s",
                         job="j", operator="o")
    text = prometheus_text(reg)
    assert "flink_tpu_profiler_enabled 1" in text
    assert "flink_tpu_profiler_entries 1" in text
    assert "flink_tpu_profiler_device_ms_total 2" in text
    assert "flink_tpu_profiler_dispatches_total 1" in text
    bind_ledger_metrics(reg)  # idempotent re-bind


def test_trace_counters_render_as_chrome_counter_tracks(ledger):
    ledger.record("mesh.step", 1.25, shape_sig="s", job="j", operator="o")
    ledger.record("mesh.fire", 2.5, shape_sig="s", job="j", operator="o")
    counters = ledger.trace_counters()
    assert [c["site"] for c in counters] == ["mesh.step", "mesh.fire"]
    trace = chrome_trace_events([], counters=counters)
    tracks = [e for e in trace["traceEvents"] if e["ph"] == "C"]
    assert {e["name"] for e in tracks} \
        == {"dispatch_ms:mesh.step", "dispatch_ms:mesh.fire"}
    assert tracks[0]["args"]["ms"] == pytest.approx(1.25)
    json.dumps(trace)  # must stay serialisable


def test_cli_profile_json_and_table(global_ledger, capsys):
    from flink_tpu.cli import main

    global_ledger.record("device_window.step", 4.0, shape_sig="sig",
                         job="q5", operator="TumblingSum")
    assert main(["profile", "q5", "--json"]) == 0
    payload = json.loads(capsys.readouterr().out)
    assert payload["job"] == "q5"
    assert payload["programs"][0]["site"] == "device_window.step"
    assert main(["profile", "q5"]) == 0
    out = capsys.readouterr().out
    assert "device_window.step" in out and "TumblingSum" in out


def test_ledger_site_inventory_is_sorted_and_unique():
    sites = [s for s, _ in LEDGER_SITE_INVENTORY]
    assert sites == sorted(sites)
    assert len(sites) == len(set(sites))


# ---------------------------------------------------------------------------
# tier_hot_hit_ratio ring (state residency satellite)
# ---------------------------------------------------------------------------


def test_hit_ratio_series_ring():
    from flink_tpu.state.tiering.residency import (
        HIT_RATIO_WINDOW, ResidencyManager)

    mgr = ResidencyManager(max_parallelism=8, budget_slots=4)
    spilled = np.zeros(8, bool)
    spilled[4:] = True
    groups_hot = np.array([0, 1, 2, 3], np.int64)
    groups_cold = np.array([4, 5, 6, 7], np.int64)
    # boundary 1: all-hot batch -> ratio 1.0
    mgr.observe(groups_hot, 0, spilled)
    mgr.on_boundary()
    # boundary 2: half the touches land on spilled groups -> 0.5
    mgr.observe(np.concatenate([groups_hot, groups_cold]), 1, spilled)
    mgr.on_boundary()
    # boundary with no touches seals no sample
    mgr.on_boundary()
    assert mgr.hit_ratio_series() == [1.0, 0.5]
    # bounded ring: only the last HIT_RATIO_WINDOW boundaries survive
    for b in range(HIT_RATIO_WINDOW + 5):
        mgr.observe(groups_hot, 2 + b, spilled)
        mgr.on_boundary()
    series = mgr.hit_ratio_series()
    assert len(series) == HIT_RATIO_WINDOW
    assert all(v == 1.0 for v in series)


def test_hit_ratio_series_module_lookup():
    from flink_tpu.state.tiering import (
        hit_ratio_series, register_residency, unregister_residency)
    from flink_tpu.state.tiering.residency import ResidencyManager

    mgr = ResidencyManager(max_parallelism=4, budget_slots=2)
    register_residency("profiler-test-op", mgr)
    try:
        mgr.observe(np.array([0, 1], np.int64), 0, None)
        mgr.on_boundary()
        series = hit_ratio_series("profiler-test")
        assert series == {"profiler-test-op": [1.0]}
    finally:
        unregister_residency("profiler-test-op")
