"""The readers and data files of PR 53 (who sets the pace of a saturated
cell): ``readers/stage_ring_part.py`` and ``readers/task_cpu.py`` over
hand-built run objects, and the twelve metric files. Membership only:
no position in a list and no total is pinned here.
``tests/test_task_pace.py`` runs the same cases under tier-1."""

from types import SimpleNamespace

import pytest

from benchmarks.harness import stage_trace as S
from benchmarks.harness.spec import load_spec

SAT9 = {"q5-10m-saturated", "q5-10m-uniform", "q7-10m-saturated",
        "q5-inflight-saturated", "q5-10m-disorder-saturated",
        "q11-sessions-saturated", "q5-16m-mesh4-saturated",
        "q5-inflight-mesh4-saturated", "q7-16m-mesh4-saturated"}
MESH3 = {"q5-16m-mesh4-saturated", "q5-inflight-mesh4-saturated",
         "q7-16m-mesh4-saturated"}
CELLS = {
    "source_batch_own_ms": SAT9, "source_blocked_share": SAT9,
    "source_task_cpu_share": SAT9, "window_task_cpu_share": SAT9,
    "window_turn_cpu_ms": SAT9, "window_turn_wait_ms": SAT9,
    "upload_wait_ms": SAT9, "dispatch_wait_ms": SAT9,
    "mesh_reading_wait_us_per_step": MESH3,
    # not in q11-sessions-saturated, where ISSUE 53 listed them too: the
    # session operator's per-round Drain / FireDispatch / Emit share one
    # (name, task, seq), which harness/stage_trace's clock check pairs by
    "sat_idle_window_turn_share": MESH3, "sat_idle_wait_input_share": MESH3,
    "sat_idle_unattributed_share": MESH3,
}


@pytest.fixture(scope="module")
def pace_spec():
    return load_spec()


def _span(scope, name, seq, ms, task, **attrs):
    return SimpleNamespace(scope=scope, name=name, duration_ns=ms * 1e6,
                           attributes={"seq": seq, "task": task, **attrs})


def _task(task_id, scope, reader=None, cpu_s=None):
    timers = SimpleNamespace(cpu_s=cpu_s) if cpu_s is not None \
        else SimpleNamespace()              # the parent's: no clock
    return SimpleNamespace(
        task_id=task_id, reader=reader, io_timers=timers,
        ctx=SimpleNamespace(metrics=SimpleNamespace(
            group=SimpleNamespace(scope=scope))))


def _pace_run(spans, window_cpu=None, source_cpu=None, gauges=None):
    """Batches 5..9 are timed (``first_batch`` 4, ``end_batch`` 9)."""
    window = _task("v3#0", ("job", "v3", "0"), cpu_s=window_cpu)
    source = _task("v1#0", ("job", "v1", "0"), reader=object(),
                   cpu_s=source_cpu)
    return SimpleNamespace(
        window_task=window,
        job=SimpleNamespace(tasks={"v1#0": source, "v3#0": window}),
        schedule=SimpleNamespace(phase=lambda name: SimpleNamespace(
            first_batch=4, end_batch=9)),
        at_t0={"time_s": 140.0, "metrics": dict(gauges or {})},
        at_end={"job_started_s": 100.0}, window_s=10.0, _spans=spans)


@pytest.fixture()
def ring(monkeypatch):
    monkeypatch.setattr(S, "ring_spans", lambda run: run._spans)


def _turns(cpu=True):
    """ProcessBatch 1..9 of the window task: 40 ms a turn, cpu = seq ms;
    another task's turns beside them."""
    spans = [_span("task", "ProcessBatch", seq, 40.0, "v3#0",
                   **({"cpu_ms": float(seq)} if cpu else {}))
             for seq in range(1, 10)]
    spans += [_span("task", "ProcessBatch", seq, 40.0, "v9#0", cpu_ms=39.0)
              for seq in range(1, 10)]
    return spans


def test_pace_the_cpu_parts_of_a_timed_turn_are_sums_by_seq(pace_spec, ring):
    read = pace_spec.module("readers", "stage_ring_part").read
    cpu = pace_spec.layer_metric("window_turn_cpu_ms")["params"]
    wait = pace_spec.layer_metric("window_turn_wait_ms")["params"]
    run = _pace_run(_turns())
    assert read(run, cpu) == 7.0                 # mean of 5..9
    assert read(run, wait) == 33.0               # 40 - 7
    # a clock that ticks every 10 ms: 3 ms spans that read 0, 0, 10, 0, 0
    # worked 2 ms a batch and waited 1, whatever the one span says
    ticks = [_span("task", "ProcessBatch", seq, 3.0, "v3#0",
                   cpu_ms=10.0 if seq == 7 else 0.0) for seq in range(1, 10)]
    assert read(_pace_run(ticks), cpu) == pytest.approx(2.0)
    assert read(_pace_run(ticks), wait) == pytest.approx(1.0)
    # more CPU than wall over the whole phase is no negative wait
    run = _pace_run([_span("task", "ProcessBatch", seq, 1.0, "v3#0",
                           cpu_ms=1.004) for seq in range(1, 10)])
    assert read(run, wait) == 0.0


@pytest.mark.parametrize("metric", ["window_turn_cpu_ms", "upload_wait_ms",
                                    "dispatch_wait_ms",
                                    "source_batch_own_ms",
                                    "source_blocked_share"])
def test_pace_a_program_without_the_attribute_reads_nothing(
        pace_spec, ring, metric):
    """The parent of PR 53 writes the spans without ``cpu_ms`` and
    ``blocked_ms``: every reader returns None and none raises."""
    read = pace_spec.module("readers", "stage_ring_part").read
    params = pace_spec.layer_metric(metric)["params"]
    spans = _turns(cpu=False) + [
        _span("window", name, seq, 5.0, "v3#0")
        for name in ("Upload", "IngestDispatch") for seq in range(1, 10)]
    spans += [_span("task", "SourceBatch", seq, 30.0, "v1#0", read_ms=3.0,
                    emit_ms=27.0) for seq in range(1, 10)]
    assert read(_pace_run(spans), params) is None
    # a timed batch without its span; no ring at all; dropped spans
    holed = [s for s in _turns() if s.attributes["seq"] != 7]
    assert read(_pace_run(holed), params) is None
    assert read(_pace_run([]), params) is None
    assert read(_pace_run(None), params) is None


def test_pace_the_sources_own_time_leaves_the_full_channel_out(
        pace_spec, ring):
    read = pace_spec.module("readers", "stage_ring_part").read
    own = pace_spec.layer_metric("source_batch_own_ms")["params"]
    share = pace_spec.layer_metric("source_blocked_share")["params"]
    # 30 ms a cycle of which 24 blocked, but the warm batches (1..4),
    # which stood 29 ms, and a window task's span of the same name
    spans = [_span("task", "SourceBatch", seq, 30.0, "v1#0",
                   blocked_ms=29.0 if seq <= 4 else 24.0, cpu_ms=5.0)
             for seq in range(1, 10)]
    spans += [_span("task", "SourceBatch", seq, 99.0, "v3#0",
                    blocked_ms=0.0) for seq in range(1, 10)]
    run = _pace_run(spans)
    assert read(run, own) == pytest.approx(6.0)
    assert read(run, share) == pytest.approx(80.0)
    # a job with two sources (or none) has no THE source task
    run.job.tasks["v2#0"] = _task("v2#0", ("job", "v2", "0"),
                                  reader=object())
    assert read(run, own) is None
    with pytest.raises(ValueError):
        read(run, {**own, "task": "sink"})
    with pytest.raises(ValueError):
        read(_pace_run(spans), {**own, "part": "idle"})


def test_pace_a_tasks_cpu_share_is_its_clock_over_the_timed_phase(
        pace_spec):
    read = pace_spec.module("readers", "task_cpu").read
    window = pace_spec.layer_metric("window_task_cpu_share")["params"]
    source = pace_spec.layer_metric("source_task_cpu_share")["params"]
    gauges = {"job.v3.0.cpuTimeRatio": 0.5, "job.v1.0.cpuTimeRatio": 0.1,
              "job.v3.0.busyTimeRatio": 0.9}
    run = _pace_run([], window_cpu=23.0, source_cpu=6.5, gauges=gauges)
    # 40 s of job at t0: 20 s and 4 s of CPU by then; 10 s of window
    assert read(run, window) == pytest.approx(30.0)
    assert read(run, source) == pytest.approx(25.0)
    # the parent: timers without the clock, a registry without the gauge
    assert read(_pace_run([], gauges=gauges), window) is None
    assert read(_pace_run([], window_cpu=23.0, gauges={
        "job.v3.0.busyTimeRatio": 0.9}), window) is None
    # a platform without a per-thread CPU clock: cpu_s is None
    none = _pace_run([], gauges=gauges)
    none.window_task.io_timers.cpu_s = None
    assert read(none, window) is None


def test_pace_the_reading_wait_a_step_is_a_ratio_of_growths(pace_spec):
    metric = pace_spec.layer_metric("mesh_reading_wait_us_per_step")
    read = pace_spec.module("readers", metric["reader"]).read
    k_w, k_s = "mesh_reading_wait_us_total", "mesh_steps_total"

    def run(first, last):
        return SimpleNamespace(at_t0={"device_stats": first},
                               at_end={"device_stats": last})

    assert read(run({k_w: 900.0, k_s: 70}, {k_w: 2_400.0, k_s: 170}),
                metric["params"]) == pytest.approx(15.0)
    # no block waited in the timed phase: 0 is a reading
    assert read(run({k_w: 900.0, k_s: 70}, {k_w: 900.0, k_s: 170}),
                metric["params"]) == 0.0
    # the parent keeps no such counter
    assert read(run({k_s: 70}, {k_s: 170}), metric["params"]) is None


def test_pace_every_metric_has_its_file_its_entry_and_its_cells(pace_spec):
    for name, cells in CELLS.items():
        body = pace_spec.layer_metric(name)
        entry = next(m for m in pace_spec.benchmark["per_layer"]
                     if m["name"] == name)
        assert set(entry["workloads"]) == cells, name
        assert (body["unit"], body["layer"], body["moves"]) \
            == (entry["unit"], entry["layer"], entry["moves"])
        assert body["moves"] == "events_per_s"
        assert entry["better"] == (
            "higher" if name == "source_blocked_share" else "lower")
        assert hasattr(pace_spec.module("readers", body["reader"]), "read")
        for cell in cells:
            assert name in {m["name"]
                            for m in pace_spec.cell(cell).per_layer}
    assert "q5-10m-steady" not in set().union(*CELLS.values())


def test_pace_the_saturated_idle_shares_are_one_partition(pace_spec):
    """The three ``sat_idle_*`` files hold ``idle_upload_share.json``'s
    blocks letter for letter, and their groups cover every span of the
    order once: with the rest they add up to the device's idle time."""
    model = pace_spec.layer_metric("idle_upload_share")["params"]
    names = ["sat_idle_window_turn_share", "sat_idle_wait_input_share",
             "sat_idle_unattributed_share"]
    grouped = []
    for name in names:
        body = pace_spec.layer_metric(name)
        assert body["reader"] == "stage_idle"
        for block in ("order", "program_spans", "benchmark_spans"):
            assert body["params"][block] == model[block], (name, block)
        grouped += body["params"].get("group", [])
    assert sorted(grouped) == sorted(model["order"])
    assert "group" not in pace_spec.layer_metric(names[2])["params"]
    assert set(pace_spec.layer_metric(names[1])["params"]["group"]) \
        == {"source_generate", "task.WaitInput"}
    # on hand-made intervals the three groups and the rest are the idle
    # time exactly, whatever the spans' overlaps
    s = 1e9
    busy = [(0, 2 * s), (6 * s, 7 * s)]
    spans = {"window.IngestDispatch": [(1 * s, 3 * s)],
             "task.ProcessBatch": [(0.5 * s, 3.5 * s), (6.5 * s, 8 * s)],
             "sink_invoke": [(7.5 * s, 7.75 * s)],
             "source_generate": [(3 * s, 4.5 * s)],
             "task.WaitInput": [(3.5 * s, 5 * s), (8 * s, 9 * s)]}
    parts = S.idle_partition(busy, 0, 10 * s, spans, model["order"])
    share = {name: sum(parts[g] for g in pace_spec.layer_metric(name)[
        "params"].get("group", ["unattributed"])) for name in names}
    # idle: [2, 6) and [7, 10); a turn holds [2, 3.5) and [7, 8)
    assert share == {names[0]: 2.5, names[1]: 2.5, names[2]: 2.0}
    assert sum(share.values()) == 7.0
