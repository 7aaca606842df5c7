"""The partition of a program's device time over the named regions of its
compiled code (PR 37: ``harness/region_map.py``, reader
``region_partition``, the fourteen ``step_*`` / ``fire_*`` / ``reclaim_*``
metrics), on a synthetic op line and on a reduced recording of this
PR's own chip run of the four-chip cell."""

import json
import os
from types import SimpleNamespace

import pytest

from benchmarks.harness import region_map as R
from benchmarks.harness.spec import BENCH_DIR, load_spec

MS = 1_000_000      # ns
DATA = os.path.join(BENCH_DIR, "tests", "data")
STEP_METRICS = ("step_x64_ms", "step_probe_window0_ms",
                "step_probe_tail_ms", "step_fold_value_ms",
                "step_fold_count_ms", "step_fold_rows_ms",
                "step_unnamed_share")
MESH_METRICS = ("step_exchange_pack_ms", "step_plan_sync_ms")
FIRE_METRICS = ("fire_x64_ms", "fire_select_ms", "fire_unnamed_share")
RECLAIM_METRICS = ("reclaim_rehome_ms", "reclaim_remap_ms")
SATURATED = ["q5-10m-saturated", "q5-10m-uniform", "q5-inflight-saturated",
             "q7-10m-saturated", "q5-16m-mesh4-saturated"]


@pytest.fixture(scope="module")
def spec():
    return load_spec()


def _op(name, start, end, path=""):
    return (name, path, start * MS, end * MS)


def test_every_instant_goes_to_the_shortest_event_that_covers_it():
    """A ``while`` event over three leaves with a gap between two of
    them, a leaf the execution's end cuts, and an instant nothing
    covers."""
    ops = [_op("custom-call.1", 0, 10),
           _op("while.2", 10, 70),
           _op("fusion.3", 10, 30), _op("fusion.4", 30, 45),
           _op("fusion.3", 50, 70),
           _op("custom-call.5", 80, 120)]
    parts = R.leaf_partition(ops, 0, 100 * MS)
    assert parts == {"custom-call.1": 10 * MS, "fusion.3": 40 * MS,
                     "fusion.4": 15 * MS, "while.2": 5 * MS,
                     "custom-call.5": 20 * MS}
    assert sum(parts.values()) == 90 * MS        # 70-80: no operation


def _recording():
    """Two whole steps (a probe, an eager slice, a fold) between the
    recording's first program and a fire, as ``region_map`` reads a
    plane (the loops' own events left out: the probe's loop covers
    30-50 of it, the fold's 70-140); the last probe is the recording's
    last program."""
    modules, ops = [("jit_fold(22)", -90, -10)], []
    for at in (0, 200):
        modules += [("jit_lookup_or_insert(11)", at, at + 50),
                    ("jit__multi_slice(5)", at + 51, at + 52),
                    ("jit_greater_equal(6)", at + 53, at + 54),
                    ("jit_fold(22)", at + 60, at + 160)]
        ops += [_op("gather_fusion", at, at + 30),
                _op("claim_fusion", at + 31, at + 49),
                _op("slice.1", at + 51, at + 52),
                _op("compare.1", at + 53, at + 54),
                _op("custom-call.lo", at + 60, at + 70),
                _op("sum_fusion", at + 72, at + 100),
                _op("count_fusion", at + 100, at + 105),
                _op("add.77", at + 105, at + 138),
                _op("custom-call.join", at + 140, at + 160)]
    modules += [("jit_fire_fn(33)", 165, 195), ("jit_reset(34)", 195, 199),
                ("jit_lookup_or_insert(11)", 400, 450)]
    ops += [_op("select_fusion", 165, 190), _op("custom-call.reset", 195, 199)]
    return ([(n, a * MS, b * MS) for n, a, b in sorted(
        modules, key=lambda m: m[1])], sorted(ops, key=lambda o: o[2]))


# keyed as program_regions keys them: the module's name and the
# executable's own fingerprint, which is not the trace's number
MAPS = {
    "jit_lookup_or_insert(11)": {"gather_fusion": "probe.window0",
                                 "claim_fusion": "probe.tail"},
    "jit_fold(22)": {"custom-call.lo": "x64.split",
                     "custom-call.join": "x64.join", "sum_fusion": "fold.sum",
                     "count_fusion": "fold.count", "add.77": "unnamed"},
    "jit_fire_fn(33)": {"select_fusion": "fire.topk"},
    "jit_reset(34)": {"custom-call.reset": "x64.join"},
}


def _params(spec, name):
    return spec.layer_metric(name)["params"]


def test_the_parts_of_a_step_add_up_to_its_device_time(spec):
    modules, ops = _recording()
    p = _params(spec, "step_x64_ms")
    groups = R.step_groups(modules, -100 * MS, 460 * MS, p["anchor"],
                           p["modules"], p["exclude"])
    assert [[m[0] for m in g] for g in groups] == [
        ["jit_lookup_or_insert(11)", "jit__multi_slice(5)",
         "jit_greater_equal(6)", "jit_fold(22)"]] * 2
    totals, seconds, n = R.group_regions(groups, ops, MAPS, p["eager"])
    assert n == 2 and seconds == pytest.approx(2 * 0.152)
    assert sum(totals.values()) == pytest.approx(seconds)
    per_step = {region: 1e3 * s / n for region, s in totals.items()}
    assert per_step == pytest.approx({
        "probe.window0": 30, "probe.tail": 18,
        "x64.split": 10, "x64.join": 20, "fold.sum": 28, "fold.count": 5,
        "upload.slice": 1,
        # what no operation covers in the probe, 2 ms, and in the fold,
        # 4; the operation the fold's map calls unnamed, 33; the eager
        # program that is no slice
        R.UNNAMED: 2 + 4 + 33 + 1})


def _run(monkeypatch, maps):
    modules, ops = _recording()
    trace = {"planes": [
        {"name": "/host:CPU", "lines": [{"name": "t", "events": [[
            "bench_traced_window", -100 * MS, 560 * MS]]}]},
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Modules",
                                             "events": [
            [n, a, b - a] for n, a, b in modules]}]}]}
    monkeypatch.setattr(R, "load", lambda plane: {"modules": modules,
                                                  "ops": ops})
    monkeypatch.setattr(R, "program_maps", lambda: maps)
    monkeypatch.setattr(R, "_CACHE", {})
    return SimpleNamespace(trace=trace)


def test_the_metrics_read_the_partition(spec, monkeypatch):
    run = _run(monkeypatch, MAPS)
    read = spec.module("readers", "region_partition").read
    value = {name: read(run, _params(spec, name))
             for name in STEP_METRICS + FIRE_METRICS}
    assert value == pytest.approx({
        "step_x64_ms": 30, "step_probe_window0_ms": 30,
        "step_probe_tail_ms": 18, "step_fold_value_ms": 28,
        "step_fold_count_ms": 5, "step_fold_rows_ms": 0,
        "step_unnamed_share": 100 * 40 / 152,
        "fire_x64_ms": 4, "fire_select_ms": 25,
        "fire_unnamed_share": 100 * 5 / 34})
    # no reclaim in the recording
    assert read(run, _params(spec, "reclaim_remap_ms")) is None


def test_a_program_the_maps_cannot_be_paired_with_reads_nothing(
        spec, monkeypatch):
    """The probe ran as another executable than the one the map was made
    from (its fingerprint differs): nothing of the step is read, rather
    than something wrong; the fire, whose programs pair, still is. And a
    program without ``program_regions`` at all reads nothing anywhere."""
    stale = dict(MAPS)
    stale["jit_lookup_or_insert(ab12)"] = {"gather_fusion": "probe.window0",
                                           "claim_fusion.1": "probe.tail"}
    del stale["jit_lookup_or_insert(11)"]
    run = _run(monkeypatch, stale)
    read = spec.module("readers", "region_partition").read
    assert read(run, _params(spec, "step_x64_ms")) is None
    assert read(run, _params(spec, "fire_select_ms")) == pytest.approx(25)
    run = _run(monkeypatch, None)
    for name in STEP_METRICS + FIRE_METRICS + RECLAIM_METRICS:
        assert read(run, _params(spec, name)) is None
    assert read(SimpleNamespace(trace=None),
                _params(spec, "step_x64_ms")) is None


def test_a_traced_program_is_paired_with_the_map_that_holds_its_operations():
    """Two programs share a module name (the probe with and without the
    hand-over; a fire and its incremental twin): the map that holds every
    operation seen is the executable's; two maps that fit and disagree
    are no pairing. (Loops and plumbing have no entry in a map, and
    ``region_map`` reads no event of theirs.)"""
    assert R._opcode("%while.9 = (u32[]{:T(128)}, (u32[8]{0}, u32[8]{0})) "
                     "while(%tuple.3), condition=%c, body=%b") == "while"
    assert R._opcode("%fusion.17 = u32[8]{0:T(1024)S(1)} fusion(u32[8]{0} "
                     "%p), kind=kLoop") == "fusion"
    assert R._opcode("%copy-start = (u32[8]{0}, u32[8]{0}, u32[]{:S(2)}) "
                     "copy-start(u32[8]{0} %x)") == "copy-start"
    assert R._opcode("jit_step(123)") == ""
    narrow = {"gather_fusion": "probe.window0", "claim_fusion": "probe.tail"}
    wide = {"gather_fusion": "probe.window0", "claim_fusion": "probe.tail",
            "handover_fusion": "probe.tail"}
    assert R.pair({"gather_fusion", "handover_fusion"}, [narrow, wide]) is wide
    assert R.pair({"gather_fusion", "claim_fusion"}, [narrow, wide]) is narrow
    other = dict(narrow, **{"claim_fusion": "probe.window0"})
    assert R.pair({"gather_fusion", "claim_fusion"}, [narrow, other]) is None
    assert R.pair({"gather_fusion"}, [narrow, other]) is narrow
    assert R.pair({"other_fusion"}, [narrow, wide]) is None


def test_the_fourteen_are_listed_where_the_issue_put_them(spec):
    entries = {m["name"]: m for m in spec.benchmark["per_layer"]}
    for names, cells in [(STEP_METRICS + FIRE_METRICS, SATURATED),
                         (MESH_METRICS, ["q5-16m-mesh4-saturated"]),
                         (RECLAIM_METRICS, ["q5-inflight-saturated"])]:
        for name in names:
            entry, body = entries[name], spec.layer_metric(name)
            assert entry["workloads"] == cells, name
            assert (entry["source"], entry["moves"], entry["better"]) \
                == ("device_trace", "events_per_s", "lower")
            assert (entry["layer"], entry["unit"]) \
                == (body["layer"], body["unit"])
            assert body["reader"] == "region_partition"
    # one partition a group: every metric of a group describes it alike
    for names in (STEP_METRICS + MESH_METRICS, FIRE_METRICS,
                  RECLAIM_METRICS):
        groups = {json.dumps({k: v for k, v in _params(spec, n).items()
                              if k not in ("regions", "as")},
                             sort_keys=True) for n in names}
        assert len(groups) == 1, names
    # the step group is ingest_step_ms's, less the reclaim
    step, ingest = _params(spec, "step_x64_ms"), _params(spec,
                                                         "ingest_step_ms")
    assert (step["anchor"], step["modules"]) \
        == (ingest["anchor"], ingest["modules"])
    assert step["exclude"] == ingest["exclude"] + ["^jit_reclaim\\("]
    # no metric file names an instruction by the compiler's number
    import re
    for name in entries:
        if name.startswith(("step_", "fire_x", "fire_s", "fire_u",
                            "reclaim_re")):
            text = json.dumps(spec.layer_metric(name))
            assert not re.search(r"\b(fusion|custom-call)\.\d", text), name


def test_a_whole_step_of_the_chip_run_partitions_by_the_programs_own_map(
        spec):
    """One whole ``jit_step`` of this PR's traced run of the four-chip
    cell, with the map the program gave of itself: the parts add up to
    the group's device time; the exchange's int64 column scatters, which
    the trace shows under an EMPTY path (the x64 rewriter made them anew,
    without metadata), are ``exchange.pack`` by the map; the split and
    the join of the planes are found by their target; and what no region
    names stays under 5% of the step."""
    with open(os.path.join(DATA, "op_names_v5e_q5_mesh4.json")) as f:
        rec = json.load(f)
    ops = [(name, rec["paths"][at], a, b) for name, at, a, b in rec["ops"]]
    modules = [tuple(m) for m in rec["modules"]]
    p = _params(spec, "step_x64_ms")
    groups = R.step_groups(modules, rec["lo"], rec["hi"], p["anchor"],
                           p["modules"], p["exclude"])
    assert [R._label(m[0]) for m in groups[0]] \
        == ["jit_step"] + ["jit__multi_slice"] * 4 and len(groups) == 1
    totals, seconds, n = R.group_regions(groups, ops, rec["maps"],
                                         p["eager"])
    assert n == 1 and sum(totals.values()) == pytest.approx(seconds)
    ms = {region: 1e3 * s for region, s in totals.items()}
    assert 1e3 * seconds == pytest.approx(93.18, abs=0.01)
    assert ms == pytest.approx({
        "probe.window0": 16.35, "exchange.pack": 14.51, "x64.split": 12.72,
        "x64.join": 12.48, "fold.sum": 10.00, "fold.count": 10.00,
        "fold.row": 5.51, "mesh.sync": 4.79, "mesh.plan": 3.63,
        "probe.tail": 1.11, "exchange.collective": 0.04,
        "upload.slice": 0.04, R.UNNAMED: 2.00}, abs=0.01)
    assert ms[R.UNNAMED] < 0.05 * 1e3 * seconds
    (regions,) = rec["maps"].values()
    pathless: dict = {}
    for name, path, a, b in ops:
        if not path and name in regions:
            pathless[regions[name]] = pathless.get(regions[name], 0.0) \
                + (b - a) / 1e6
    assert pathless["exchange.pack"] == pytest.approx(14.50, abs=0.01)
    assert sum(pathless.values()) - pathless["exchange.pack"] < 0.2
    # an executable the map was not made from (one operation seen is
    # none of its instructions) reads nothing
    (key,) = rec["maps"]
    stale = {key: {k: v for k, v in regions.items() if k != ops[40][0]}}
    assert R.group_regions(groups, ops, stale, p["eager"]) is None
