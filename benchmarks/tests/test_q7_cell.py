"""The cell ``q7-10m-saturated`` (PR 33) as files: its configuration, its
query module and reference, its four per-layer metrics on hand-built
module lines and snapshots, and its place in BENCHMARK.json. The job
itself, through ``run_cell`` at rehearsal size, is held to the reference
in tier-1 (``tests/test_nexmark_q7.py``); one case of it is here too, so
that this directory's own run sees the cell."""

import json
import re
from types import SimpleNamespace

import numpy as np
import pytest

from benchmarks.harness import trace as T
from benchmarks.harness.cell import run_cell
from benchmarks.harness.fold_bytes import scatter_fold_bytes
from benchmarks.harness.spec import BENCH_DIR, load_module, load_spec

CELL = "q7-10m-saturated"
CONFIG = "nexmark-q7-10m"
NEW = ("max_fold_ms", "max_fold_roofline_share", "q7_fire_device_ms",
       "q7_fire_select_passes")
SHARED = ("window_task_busy_share", "ingest_step_ms",
          "ingest_roofline_share", "peak_hbm_gb",
          "batch_queue_ms.saturated", "probe_rounds_p50",
          "probe_tail_share")


@pytest.fixture(scope="module")
def spec():
    return load_spec()


def _plane(modules):
    return {"name": "/device:TPU:0", "lines": [
        {"name": T.MODULE_LINE, "events": [[n, a, d] for n, a, d in modules]}]}


def test_the_cell_is_one_chip_q7_under_the_saturated_mix(spec):
    cell = spec.cell(CELL)
    assert (cell.chips, cell.config_name, cell.traffic_name) \
        == (1, CONFIG, "bids-saturated")
    assert [m["name"] for m in cell.end_to_end] == ["events_per_s",
                                                    "setup_s"]
    reported = [m["name"] for m in cell.per_layer]
    assert set(reported) == set(NEW) | set(SHARED)
    # the four new metrics are the last of per_layer, in this cell alone
    assert [m["name"] for m in spec.benchmark["per_layer"][-4:]] == list(NEW)
    for m in spec.benchmark["per_layer"][-4:]:
        assert m["workloads"] == [CELL] and m["moves"] == "events_per_s"
        body = spec.layer_metric(m["name"])
        assert (body["layer"], body["unit"]) == (m["layer"], m["unit"])
    for name in SHARED:
        entry = next(m for m in spec.benchmark["per_layer"]
                     if m["name"] == name)
        assert entry["workloads"][-1] == CELL
    chips = [w["chips"] for w in spec.benchmark["workloads"]]
    assert len(chips) == 4 and chips.count(4) == 1


def test_the_configuration_states_what_it_is_and_what_it_assumes(spec):
    entry = next(c for c in spec.benchmark["configs"] if c["name"] == CONFIG)
    cfg, q5 = spec.cell(CELL).config, spec.cell("q5-10m-saturated").config
    assert cfg["source"] == entry["source"] and len(entry["source"]) <= 200
    assert cfg["architecture"] is None
    assert cfg["reduced"] == entry["reduced"] == q5["reduced"]
    assert set(cfg["reduced_notes"]) == set(cfg["reduced"])
    for key in ("deployment", "guarantees", "assumed", "rehearse"):
        assert cfg[key]
    # the four departures from q7.sql, and the sizes set here
    assert {"join", "keying", "ring_size", "word"} <= set(cfg["assumed"])
    # the SAME data set as Q5's cell, letter for letter
    assert cfg["data"] == q5["data"]
    assert (cfg["batch_rows"], cfg["warm_s"], cfg["trace_s"]) \
        == (q5["batch_rows"], q5["warm_s"], q5["trace_s"])
    q = cfg["query"]
    assert (q["module"], q["window_size_ms"], q["topk"], q["capacity"],
            q["ring_size"]) == ("q7", 10_000, 1, 1 << 24, 8)
    assert "word_dtype" not in q             # the control's hook, unset
    assert cfg["prefill_panes"] == q["ring_size"] - 1 - 2
    assert cfg["state"]["cell_bytes"] == [8, 8]
    # the rehearsal leaves the prices alone: the word keeps its 43 bits
    assert "price_max" not in cfg["rehearse"].get("data", {})


def test_the_words_width_is_derived_and_held_to_the_data(spec):
    q7 = load_module(BENCH_DIR, "queries", "q7")
    cfg = spec.cell(CELL).config
    q, data = cfg["query"], cfg["data"]
    assert q["price_bits"] == int(data["price_max"]).bit_length() == 23
    assert q7.word_bits(q) == 43
    assert q7.window_panes(q) == 1 and q7.pane_ms(q) == 10_000
    assert q7.KEY_COLUMN == "auction"
    src = open(f"{BENCH_DIR}/queries/q7.py").read()
    assert not re.search(r"\b4[23]\b", src.split('"""', 2)[2])
    q7.make_reference(q, data, lambda *_: None)
    for block, key, value in (("query", "price_bits", 22),
                              ("data", "price_max", (1 << 22) - 1),
                              ("data", "n_bidders", (1 << 20) + 1)):
        broken = json.loads(json.dumps(cfg))
        broken[block][key] = value
        with pytest.raises(ValueError):
            q7.make_reference(broken["query"], broken["data"],
                              lambda *_: None)


def test_a_rehearsal_of_the_cell_is_correct_and_its_control_is_not(spec):
    cell = spec.cell(CELL)
    run = run_cell(spec, cell, seed=2_147_483_659, seconds=5.0,
                   trace=False, rehearse=True)
    assert run.correct and run.failed == 0
    assert all(c["ok"] for c in run.checks if "ok" in c)
    tally = next(c for c in run.checks if c["check"] == "_tally")
    assert tally["rows_compared"] == tally["windows_emitted"] >= 9
    control = load_spec().cell(CELL)
    control.config["query"]["word_dtype"] = "int32"   # control_q7's step
    run = run_cell(spec, control, seed=2_147_483_659, seconds=5.0,
                   trace=False, rehearse=True)
    assert not run.correct
    assert [c["check"] for c in run.checks if c.get("ok") is False] \
        == ["rows_differ"]
    assert run.operator._aggs[0].dtype == np.int32


def test_the_max_fold_is_its_own_module_and_nothing_elses(spec):
    p = spec.layer_metric("max_fold_ms")["params"]
    ms = 1e6
    modules = [("jit_lookup_or_insert(1)", 0, 47 * ms),
               ("jit_reshape(2)", 47 * ms, 20 * ms),
               ("jit_scatter-add(3)", 67 * ms, 25 * ms),
               ("jit_reshape(2)", 92 * ms, 20 * ms),
               ("jit_scatter-max(4)", 112 * ms, 60 * ms),
               ("jit_reshape(2)", 172 * ms, 20 * ms),
               ("jit_lookup_or_insert(1)", 192 * ms, 47 * ms),
               ("jit_scatter-max(4)", 239 * ms, 64 * ms),
               ("jit_fire_fn(5)", 303 * ms, 40 * ms),
               ("jit_scatter-max(4)", 343 * ms, 62 * ms),
               ("jit_reshape(2)", 405 * ms, 20 * ms)]
    groups = T.module_groups(_plane(modules), 0, 500 * ms, p["modules"],
                             p["anchor"], p.get("exclude", ()))
    assert groups == pytest.approx([0.060, 0.064, 0.062])
    roof = spec.layer_metric("max_fold_roofline_share")
    assert roof["reader"] == "fold_roofline"
    assert {k: roof["params"][k] for k in ("anchor", "modules")} == p


def test_the_folds_bytes_are_the_models_not_the_implementations():
    # 2^18 rows of an 8-byte value and a 4-byte flat index, and each of
    # the cells they touch read and written once: whatever the plane's
    # size, and whatever splits, joins or copies it
    rows, touched = 1 << 18, 131_172
    assert scatter_fold_bytes(rows, 8, 4, touched, 8) \
        == rows * 12 + 2 * touched * 8 == 5_244_480
    assert scatter_fold_bytes(rows, 8, 4, 0, 8) == rows * 12


def test_the_folds_roofline_share_is_least_time_over_measured(
        spec, monkeypatch):
    metric = spec.layer_metric("max_fold_roofline_share")
    reader = spec.module("readers", metric["reader"])
    keys = np.r_[np.arange(1000), np.zeros(24, np.int64)]   # 1000 cells
    run = SimpleNamespace(
        schedule=SimpleNamespace(
            batch_rows=len(keys), batch_index=lambda b: b,
            phase=lambda name: SimpleNamespace(first_batch=3)),
        generator=SimpleNamespace(columns=lambda b: {"auction": keys}),
        query=SimpleNamespace(KEY_COLUMN="auction"), trace=object())
    monkeypatch.setattr(reader, "device_block",
                        lambda: {"kind": "TPU v5 lite"})
    monkeypatch.setattr(reader._module_time, "step_seconds",
                        lambda run, params: 0.050)
    nbytes = 1024 * 12 + 2 * 1000 * 8
    assert reader.read(run, metric["params"]) == pytest.approx(
        100.0 * (nbytes / 819e9) / 0.050)
    # no such program in the trace (or no trace): nothing, not an error
    monkeypatch.setattr(reader._module_time, "step_seconds",
                        lambda run, params: None)
    assert reader.read(run, metric["params"]) is None


def test_a_one_chip_fire_is_jit_fire_fn_and_the_reset_behind_it(spec):
    p = spec.layer_metric("q7_fire_device_ms")["params"]
    ms = 1e6
    modules = [("jit_scatter-max(4)", 0, 60 * ms),
               ("jit_fire_fn(5)", 60 * ms, 40 * ms),
               ("jit_reset(6)", 100 * ms, 21 * ms),
               ("jit_lookup_or_insert(1)", 121 * ms, 47 * ms),
               ("jit_fire_fn(5)", 168 * ms, 42 * ms),
               ("jit_reset(6)", 210 * ms, 20 * ms),
               ("jit_reshape(2)", 230 * ms, 20 * ms)]
    groups = T.module_groups(_plane(modules), 0, 300 * ms, p["modules"],
                             p["anchor"], p.get("exclude", ()))
    assert groups == pytest.approx([0.061, 0.062])
    one = spec.layer_metric("fire_device_ms")
    assert one["reader"] == spec.layer_metric(
        "q7_fire_device_ms")["reader"] == "trace_module_time"


@pytest.mark.parametrize("first,last,expected", [
    # six fires between the first timed batch and the end of the run:
    # three windows hold a bid at price_max (43 bits), three do not
    ({"fire_selects_total": 7, "fire_select_passes_total": 7 * 42},
     {"fire_selects_total": 13,
      "fire_select_passes_total": 7 * 42 + 3 * 43 + 3 * 42}, 42.5),
    # the parent of PR 33: the one-chip fire keeps no such counter, so
    # the totals do not grow and the metric is left out of the line
    ({"fire_selects_total": 0, "fire_select_passes_total": 0},
     {"fire_selects_total": 0, "fire_select_passes_total": 0}, None),
    ({}, {}, None),
])
def test_passes_a_fire_reads_nothing_on_a_program_without_the_counter(
        spec, first, last, expected):
    metric = spec.layer_metric("q7_fire_select_passes")
    reader = spec.module("readers", metric["reader"])
    run = SimpleNamespace(at_t0={"device_stats": first},
                          at_end={"device_stats": last})
    got = reader.read(run, metric["params"])
    assert got == expected if expected is None else \
        got == pytest.approx(expected)


def test_every_pattern_of_the_new_metric_files_compiles(spec):
    for name in NEW:
        params = spec.layer_metric(name).get("params", {})
        for pat in [params.get("anchor"), *params.get("modules", ()),
                    *params.get("exclude", ())]:
            if pat is not None:
                re.compile(pat)
    assert re.search(spec.layer_metric("max_fold_ms")["params"]["anchor"],
                     "jit_scatter-max(1234567)")
    assert not re.search(
        spec.layer_metric("max_fold_ms")["params"]["anchor"],
        "jit_scatter-add(1234567)")
