"""The max fold read from its named scope inside ``jit_fold`` (PR 34):
``fold_max_share`` (reader ``op_region_time``), ``fold_max_ms`` and
``fold_max_roofline`` (reader ``fold_region``), on hand-built module and
operation lists. PR 34 moved the fold into one donated program, so the
eager module ``jit_scatter-max`` that ``max_fold_ms`` and
``max_fold_roofline_share`` anchor on is gone; the scope is what finds the
same work."""

from types import SimpleNamespace

import numpy as np
import pytest

from benchmarks.harness.spec import load_spec

CELL = "q7-10m-saturated"
NEW = ("fold_max_share", "fold_max_ms", "fold_max_roofline")
MS = 1_000_000      # ns
SCOPE = "jit(fold)/jit(main)/while/body/cond/branch_1_fun/while/body/" \
        "cond/branch_1_fun/fold.scatter/fold.max/scatter-max"


@pytest.fixture(scope="module")
def spec():
    return load_spec()


def _recording():
    """Two whole executions of jit_fold between a probe, a fire and the
    recording's first and last program: in each, two scatters under
    fold.max (10 + 5 ms), one under fold.count, and a split and a join of
    the plane that carry no scope."""
    modules, ops = [("jit_lookup_or_insert(1)", 0, 40 * MS)], []
    for at in (40 * MS, 200 * MS):
        modules.append(("jit_fold(2)", at, at + 100 * MS))
        ops += [("", at, at + 10 * MS),
                (SCOPE, at + 10 * MS, at + 20 * MS),
                (SCOPE.replace("fold.max", "fold.count").replace(
                    "scatter-max", "scatter-add"), at + 20 * MS,
                 at + 50 * MS),
                (SCOPE, at + 50 * MS, at + 55 * MS),
                ("", at + 55 * MS, at + 100 * MS)]
    modules += [("jit_fire_fn(3)", 140 * MS, 200 * MS),
                ("jit_lookup_or_insert(1)", 300 * MS, 340 * MS)]
    return sorted(modules, key=lambda m: m[1]), ops


def test_the_three_are_listed_in_q7s_cell_and_read_one_region(spec):
    reported = [m["name"] for m in spec.cell(CELL).per_layer]
    for name in NEW:
        assert name in reported
        entry = next(m for m in spec.benchmark["per_layer"]
                     if m["name"] == name)
        body = spec.layer_metric(name)
        assert entry["workloads"] == [CELL]
        assert (entry["layer"], entry["unit"], entry["moves"]) \
            == (body["layer"], body["unit"], "events_per_s")
        assert (body["params"]["module"], body["params"]["region"]) \
            == ("^jit_fold\\(", "/fold\\.max/")
    assert spec.layer_metric("fold_max_share")["reader"] == "op_region_time"
    # the model's bytes are those of the metric that fell silent
    old = spec.layer_metric("max_fold_roofline_share")["params"]
    new = spec.layer_metric("fold_max_roofline")["params"]["roofline"]
    assert new == {k: old[k] for k in new}


def test_the_scope_finds_the_max_fold_and_not_the_count(spec):
    modules, ops = _recording()
    p = spec.layer_metric("fold_max_share")["params"]
    reader = spec.module("readers", "op_region_time")
    region_s, module_s, n = reader.region_time(
        modules, ops, p["module"], p["region"], 0, 340 * MS)
    assert (region_s, module_s, n) == pytest.approx((0.030, 0.200, 2))
    # a program from before PR 34: the eager modules, no jit_fold
    eager = [(n.replace("jit_fold", "jit_scatter-max"), a, b)
             for n, a, b in modules]
    assert reader.region_time(eager, ops, p["module"], p["region"], 0,
                              340 * MS)[2] == 0


@pytest.mark.parametrize("found,ms", [((0.030, 0.200, 2), 15.0),
                                      (None, None)])
def test_ms_and_roofline_are_the_regions_time_an_execution(
        spec, monkeypatch, found, ms):
    reader = spec.module("readers", "fold_region")
    keys = np.r_[np.arange(1000), np.zeros(24, np.int64)]   # 1000 cells
    run = SimpleNamespace(
        schedule=SimpleNamespace(
            batch_rows=len(keys), batch_index=lambda b: b,
            phase=lambda name: SimpleNamespace(first_batch=3)),
        generator=SimpleNamespace(columns=lambda b: {"auction": keys}),
        query=SimpleNamespace(KEY_COLUMN="auction"), trace=object())
    monkeypatch.setattr(reader, "device_block",
                        lambda: {"kind": "TPU v5 lite"})
    monkeypatch.setattr(reader._region, "measured",
                        lambda run, params: found)
    got_ms = reader.read(run, spec.layer_metric("fold_max_ms")["params"])
    got = reader.read(run, spec.layer_metric("fold_max_roofline")["params"])
    if ms is None:      # nothing to read: nothing, not an error
        assert got_ms is None and got is None
        return
    assert got_ms == pytest.approx(ms)
    nbytes = 1024 * 12 + 2 * 1000 * 8
    assert got == pytest.approx(100.0 * (nbytes / 819e9) / 0.015)
