"""The readers of the program's stage spans (PR 25), on a recording from
the chip and on hand-built spans.

data/stage_trace_v5e_q5_steady.json is the `--trace 1` run of
q5-10m-steady on a TPU v5 lite (PR 25, chip call 1, seed 662607015), six
seconds, reduced: the device plane's `XLA Modules` events of the ingest
and fire programs, its `XLA Ops` line merged into busy intervals (gaps
under 2 us closed), the benchmark's host spans, the program's stage
annotations with their arguments as harness/stage_trace reads them, the
ring's spans that pair with them, and the operator's own
`fire_latencies_ms` samples of the three windows fired inside. Times are
ns from the traced window's start (the ring's are epoch ns)."""

import json
import os
from types import SimpleNamespace

import pytest

from benchmarks.harness import stage_trace as S
from benchmarks.harness import trace as T
from benchmarks.harness.spec import load_module, load_spec

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "data", "stage_trace_v5e_q5_steady.json"),
              encoding="utf-8") as f:
        return json.load(f)


@pytest.fixture(scope="module")
def spec():
    return load_spec()


def _params(spec, metric):
    return spec.layer_metric(metric)["params"]


def _partition(recorded, params):
    trace = recorded["trace"]
    lo, hi = T.traced_window(trace)
    plane = T.device_planes(trace)[0]
    busy = [(a, b) for _n, a, b in T.clip(T._busy_events(plane), lo, hi)]
    program = set(params["program_spans"])
    spans = {n: [(e["start"], e["end"]) for e in S.stage_events(
        recorded["stages"], n,
        recorded["task_id"] if n in program else None)]
        for n in params["order"]}
    parts = S.idle_partition(busy, lo, hi, spans, params["order"])
    return parts, (hi - lo) / 1e9, T.busy_s(plane, lo, hi)


# -- interval arithmetic -----------------------------------------------------

def test_interval_arithmetic():
    assert S.union([(5, 7), (0, 2), (1, 3), (7, 8), (9, 9)]) \
        == [(0, 3), (5, 8)]
    assert S.intersect([(0, 10), (20, 30)], [(5, 25), (28, 40)]) \
        == [(5, 10), (20, 25), (28, 30)]
    assert S.subtract([(0, 10), (20, 30)], [(2, 3), (8, 22), (29, 35)]) \
        == [(0, 2), (3, 8), (22, 29)]
    assert S.subtract([(0, 10)], []) == [(0, 10)]
    assert S.subtract([(0, 10)], [(0, 10)]) == []


def test_partition_gives_each_idle_instant_to_the_first_cover():
    s = 1e9
    busy = [(0, 2 * s), (6 * s, 7 * s)]
    spans = {"inner": [(3 * s, 4 * s), (6.5 * s, 9 * s)],
             "outer": [(1 * s, 5 * s)],
             "wait": [(4.5 * s, 10 * s)]}
    parts = S.idle_partition(busy, 0, 10 * s, spans,
                             ["inner", "outer", "wait"])
    # idle is [2, 6) and [7, 10): inner takes [3,4) and [7,9), outer what
    # is left of [2,5), wait [5,6) and [9,10); nothing stays unnamed
    assert parts == {"inner": 3.0, "outer": 2.0, "wait": 2.0,
                     "unattributed": 0.0}
    # the order decides who gets an instant two spans cover
    swapped = S.idle_partition(busy, 0, 10 * s, spans,
                               ["wait", "outer", "inner"])
    assert swapped["wait"] == 4.5 and swapped["inner"] == 0.0
    assert sum(swapped.values()) == sum(parts.values()) == 7.0
    assert S.idle_partition(busy, 0, 10 * s, {}, ["inner"]) \
        == {"inner": 0.0, "unattributed": 7.0}


# -- the recording -----------------------------------------------------------

def test_recorded_idle_time_is_partitioned_and_named(recorded, spec):
    params = _params(spec, "idle_unattributed_share")
    parts, window_s, busy_s = _partition(recorded, params)
    assert window_s == pytest.approx(6.0004, abs=1e-4)
    # a partition: the shares add up to the device's idle time exactly
    assert sum(parts.values()) == pytest.approx(window_s - busy_s, abs=1e-9)
    share = {k: 100 * v / window_s for k, v in parts.items()}
    assert 100 * (1 - busy_s / window_s) == pytest.approx(23.5, abs=0.1)
    assert share["task.WaitInput"] == pytest.approx(20.7, abs=0.1)
    assert share["window.IngestDispatch"] == pytest.approx(1.48, abs=0.05)
    assert share["window.Upload"] == pytest.approx(0.44, abs=0.05)
    assert share["unattributed"] < 0.5          # it read 21.8 before
    # every metric file of the family names the same order, and their
    # groups cover every span of it once
    names = ["idle_wait_input_share", "idle_upload_share",
             "idle_dispatch_share", "idle_drain_share",
             "idle_source_generate_share", "idle_sink_invoke_share"]
    grouped = []
    for n in names:
        p = _params(spec, n)
        assert p["order"] == params["order"]
        grouped += p["group"]
    assert sorted(grouped) == sorted(params["order"])
    assert "group" not in params


def test_recorded_fires_join_host_stages_and_device(recorded, spec):
    params = _params(spec, "fire_device_queue_ms")
    trace = recorded["trace"]
    lo, hi = T.traced_window(trace)
    mods = T.line_events(T.device_planes(trace)[0], T.MODULE_LINE)
    lives = S.fire_lives(recorded["stages"], mods, recorded["task_id"],
                         params["fire_module"], lo, hi, params["stages"])
    assert [life["seq"] for life in lives] == [36000, 38000, 40000]
    for life in lives:
        parts = (life["device_queue"] + life["fire_device"]
                 + life["ready_to_drain"] + life["drain"] + life["emit"])
        # the parts are the operator's own dispatch -> rows sample
        assert parts == pytest.approx(life["dispatch_to_rows"], abs=0.1)
        assert parts == pytest.approx(
            recorded["fire_latencies_ms"][str(life["seq"])], abs=5.0)
        assert life["device_queue"] == pytest.approx(215, abs=3)
        assert life["fire_device"] == pytest.approx(26.2, abs=0.2)
        assert 50 < life["ready_to_drain"] < 300
    # a window whose drain lies outside the traced window is left out
    assert S.fire_lives(recorded["stages"], mods, recorded["task_id"],
                        params["fire_module"], lo, lo + 2.0e9,
                        params["stages"])[0]["seq"] == 36000
    assert len(S.fire_lives(recorded["stages"], mods, recorded["task_id"],
                            params["fire_module"], lo, lo + 1.5e9,
                            params["stages"])) == 0
    assert S.fire_lives(recorded["stages"], mods, "another#0",
                        params["fire_module"], lo, hi,
                        params["stages"]) == []


def test_recorded_clocks_agree_and_a_skewed_one_does_not(recorded, spec):
    names = _params(spec, "stage_clock_disagreement_us")["program_spans"]
    ring = [SimpleNamespace(**d) for d in recorded["ring"]]
    assert len(ring) >= 30
    off = S.clock_disagreement_ns(recorded["stages"], ring, names)
    assert off is not None and off < 5_000 < S.CLOCK_LIMIT_NS
    # the largest is reported beside the 90th percentile and refuses
    # nothing: one span stamped 1 ms late moves it alone
    worst = S.clock_disagreement_ns(recorded["stages"], ring, names, 100)
    assert off <= worst < S.CLOCK_LIMIT_NS
    late = [SimpleNamespace(**{**d, "start_ns": d["start_ns"] + (
        1_000_000 if i == 3 else 0)}) for i, d in enumerate(recorded["ring"])]
    assert S.clock_disagreement_ns(recorded["stages"], late, names) \
        == pytest.approx(off, abs=2_000)
    assert S.clock_disagreement_ns(recorded["stages"], late, names, 100) \
        > 900_000
    # a ring on a clock that runs 0.1% fast drifts 6 ms over the window
    t0 = min(s.start_ns for s in ring)
    fast = [SimpleNamespace(**{**d, "start_ns": t0 + int(
        (d["start_ns"] - t0) * 1.001)}) for d in recorded["ring"]]
    assert S.clock_disagreement_ns(recorded["stages"], fast, names) \
        > S.CLOCK_LIMIT_NS
    assert S.clock_disagreement_ns(recorded["stages"], [], names) is None
    assert S.xplane_task("v3#0") == "v3/0"


# -- the ring readers on hand-built spans -------------------------------------

def _span(scope, name, start, end, **attrs):
    attrs.setdefault("task", "v3#0")
    return SimpleNamespace(scope=scope, name=name, start_ns=start,
                           end_ns=end, duration_ns=end - start,
                           attributes=attrs)


def _fake_run(spans, dropped=0):
    timed = SimpleNamespace(first_batch=2, end_batch=5)
    schedule = SimpleNamespace(
        n_batches=5, phase=lambda name: timed,
        windows_ending_in=lambda phase, pane: [4000, 6000, 8000])
    return SimpleNamespace(
        schedule=schedule, config={"query": {}},
        query=SimpleNamespace(pane_ms=lambda q: 2000),
        window_task=SimpleNamespace(task_id="v3#0"),
        at_end={"stats_before": {"spans_dropped_total": 7},
                "device_stats": {"spans_dropped_total": 7 + dropped}})


def test_ring_readers_on_hand_built_spans(monkeypatch):
    reader = load_module(load_spec().bench_dir, "readers", "stage_ring")
    ms = 1_000_000
    spans = []
    for i, q in enumerate([900.0, 800.0, 3.0, 1.0, 2.0]):      # 5 batches
        spans.append(_span("task", "ProcessBatch", i * 1000 * ms,
                           (i * 1000 + 500) * ms, seq=i + 1, queued_ms=q))
    spans.append(_span("task", "ProcessBatch", 0, 1, seq=1, queued_ms=9e9,
                       task="other#0"))
    for i, end in enumerate([2000, 4000, 6000, 8000]):
        t = (i * 1000 + 600) * ms
        spans.append(_span("window", "Watermark", t, t + 5 * ms,
                           seq=i + 1, since_batch_ms=600.0 + i, fires=1))
        spans.append(_span("window", "FireDispatch", t + ms, t + 4 * ms,
                           seq=end))
        spans.append(_span("window", "Drain", t + 400 * ms,
                           t + (401 + i) * ms, seq=end))
    from flink_tpu.metrics import tracing
    monkeypatch.setattr(tracing.TRACER, "retained_spans", lambda: spans)
    run = _fake_run(spans)
    assert reader.read(run, {"value": "batch_queue"}) == 2.0
    assert reader.samples(run, {"value": "batch_queue"}) == [3.0, 1.0, 2.0]
    assert reader.samples(run, {"value": "since_batch"}) \
        == [601.0, 602.0, 603.0]
    assert reader.read(run, {"value": "drain"}) == 3.0
    # a window without its span, a batch count that does not match, a ring
    # that dropped spans: no reading, never one over part of the run
    short = [s for s in spans
             if not (s.name == "Drain" and s.attributes["seq"] == 6000)]
    monkeypatch.setattr(tracing.TRACER, "retained_spans", lambda: short)
    assert reader.read(run, {"value": "drain"}) is None
    assert reader.read(run, {"value": "since_batch"}) == 602.0
    monkeypatch.setattr(tracing.TRACER, "retained_spans",
                        lambda: spans[1:])
    assert reader.read(run, {"value": "batch_queue"}) is None
    monkeypatch.setattr(tracing.TRACER, "retained_spans", lambda: spans)
    assert reader.read(_fake_run(spans, dropped=1),
                       {"value": "drain"}) is None
    monkeypatch.setattr(tracing.TRACER, "retained_spans", lambda: [])
    assert reader.read(run, {"value": "drain"}) is None    # the parent


def test_trace_readers_return_nothing_without_stage_annotations(spec):
    """What the parent of PR 25 gives: a trace with device planes and the
    benchmark's own spans, and no stage annotation."""
    idle = spec.module("readers", "stage_idle")
    run = SimpleNamespace(trace=None)
    assert idle.read(run, _params(spec, "idle_wait_input_share")) is None
    run = SimpleNamespace(trace={"planes": [
        {"name": "/host:CPU", "lines": []}]})
    for reader, metric in (("stage_idle", "idle_unattributed_share"),
                           ("stage_fire", "fire_ready_to_drain_ms"),
                           ("stage_clock", "stage_clock_disagreement_us"),
                           ("stage_clock",
                            "stage_clock_disagreement_max_us")):
        assert spec.module("readers", reader).read(
            run, _params(spec, metric)) is None


def test_every_new_metric_has_its_file_and_its_cells(spec):
    steady = {m["name"] for m in spec.cell("q5-10m-steady").per_layer}
    saturated = {m["name"] for m in spec.cell("q5-10m-saturated").per_layer}
    new = {"idle_wait_input_share", "idle_upload_share",
           "idle_dispatch_share", "idle_drain_share",
           "idle_source_generate_share", "idle_sink_invoke_share",
           "idle_unattributed_share", "fire_since_batch_ms",
           "fire_device_queue_ms", "fire_ready_to_drain_ms", "drain_ms",
           "batch_queue_ms.steady", "stage_clock_disagreement_us",
           "stage_clock_disagreement_max_us"}
    assert new <= steady and not new & saturated
    assert "batch_queue_ms.saturated" in saturated - steady
    for name in new | {"batch_queue_ms.saturated"}:
        body = spec.layer_metric(name)
        entry = next(m for m in spec.benchmark["per_layer"]
                     if m["name"] == name)
        assert (body["unit"], body["layer"], body["moves"]) \
            == (entry["unit"], entry["layer"], entry["moves"])
        assert hasattr(spec.module("readers", body["reader"]), "read")


def test_rehearsal_can_compute_the_ring_metrics(spec, tmp_path):
    """On the CPU there is no device plane, so the trace readers return
    nothing, as the existing ones do; the ring is the program's own and
    is read all the same. (What `run.py --rehearse --trace 1` lists as
    `metrics_computable`, with the trace in a directory of the test's
    own.)"""
    from benchmarks.harness.cell import run_cell
    from benchmarks.run import _metrics

    cell = spec.cell("q5-10m-steady")
    run = run_cell(spec, cell, seed=2_147_483_659, seconds=5.0, trace=True,
                   rehearse=True, trace_dir=str(tmp_path))
    assert run.correct and run.trace is not None
    computable = set(_metrics(spec, run, cell, 1, 0.0, True)[0])
    assert {"fire_since_batch_ms", "drain_ms", "batch_queue_ms.steady",
            "fire_to_rows_ms"} <= computable
    assert not {m for m in computable if m.startswith("idle_")}
    assert "fire_device_queue_ms" not in computable
