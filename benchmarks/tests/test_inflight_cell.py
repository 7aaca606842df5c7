"""The cells ``q5-inflight-saturated`` and ``q5-10m-uniform`` (PR 35) as
files: the generator whose auction ids advance, the uniform control, the
configuration, the five per-layer metrics on hand-built inputs, their
place in BENCHMARK.json, and the rehearsal with its control (the reclaim
patched out). One rehearsal case is mirrored in tier-1
(``tests/test_state_reclaim.py``)."""

import json
from types import SimpleNamespace

import numpy as np
import pytest

from benchmarks.harness import trace as T
from benchmarks.harness.cell import run_cell
from benchmarks.harness.reclaim_bytes import reclaim_bytes
from benchmarks.harness.spec import BENCH_DIR, load_module, load_spec

CELL, CONTROL = "q5-inflight-saturated", "q5-10m-uniform"
CONFIG = "nexmark-q5-inflight"
SEED = 3_000_000_019
NEW = ("reclaim_device_ms", "reclaim_roofline_share", "reclaim_stage_ms",
       "reclaim_freed_share", "probe_wide_batch_share")
SHARED = ("window_task_busy_share", "ingest_step_ms",
          "ingest_roofline_share", "peak_hbm_gb",
          "batch_queue_ms.saturated", "probe_rounds_p50",
          "probe_tail_share", "fold_rows_per_batch")
DATA = dict(in_flight=4000, new_auctions_per_bid=[3, 46], hot_share=0.5,
            hot_every_auctions=100, id_lead=10, id_space=50_000,
            price_max=1 << 22, n_bidders=1000, layout_seed=24)
PREFILL = 4096


@pytest.fixture(scope="module")
def spec():
    return load_spec()


def _generator(seed=0, **over):
    return load_module(BENCH_DIR, "generators", "bids_inflight") \
        .make_generator({**DATA, **over}, PREFILL, seed)


# -- the generator ---------------------------------------------------------

def test_an_event_is_a_pure_function_of_its_index():
    g = np.arange(PREFILL - 100, PREFILL + 20_000, dtype=np.int64)
    whole = _generator().columns(g)
    rng = np.random.default_rng(1)
    for _ in range(3):
        part = rng.permutation(g)[:5000]
        again = _generator().columns(part)
        for name, col in whole.items():
            assert (again[name] == col[part - g[0]]).all(), name


def test_the_prefill_is_the_in_flight_set_each_id_once():
    ids = _generator().columns(np.arange(PREFILL))["auction"]
    assert sorted(ids[:4000].tolist()) == list(range(4000))
    assert ((ids[4000:] >= 0) & (ids[4000:] < 4000)).all()


def test_ids_advance_three_per_46_bids_and_only_in_flight_ones_are_bid_on():
    gen = _generator()
    bid = np.arange(0, 46_000, dtype=np.int64)
    last = gen.last_auction(bid)
    assert last[0] == 3999 and last[-1] == 3999 + 45_999 * 3 // 46
    assert set(np.diff(last).tolist()) == {0, 1}
    assert (np.diff(last[::46]) == 3).all()
    ids = gen.columns(PREFILL + bid)["auction"]
    assert (ids <= last + DATA["id_lead"]).all()
    assert (ids >= np.maximum(last - DATA["in_flight"], 0)).all()
    # the key space moves: the last bids reach ids no early bid could
    assert ids[-4600:].min() > ids[:4600].max() - DATA["in_flight"] - 11
    assert ids[-4600:].max() > DATA["in_flight"] + 2900


def test_the_hot_id_moves_every_100_auctions_and_takes_half_the_bids():
    gen = _generator()
    bid = np.arange(0, 46_000, dtype=np.int64)
    ids = gen.columns(PREFILL + bid)["auction"]
    hot = gen.last_auction(bid) // 100 * 100
    is_hot = ids == hot
    assert 0.48 < is_hot.mean() < 0.52
    # 100 auctions are 1,533 bids: a hot id holds about 767 of them
    per_hot = np.bincount(hot[is_hot] // 100)
    full = per_hot[per_hot > 0][1:-1]
    assert len(full) >= 28 and 650 < full.mean() < 880
    # and is new when it becomes hot: at most 99 auctions old
    assert ((gen.last_auction(bid) - hot) < 100).all()


def test_the_grouped_shuffle_keeps_every_row_once_inside_its_group():
    mod = load_module(BENCH_DIR, "generators", "bids_inflight")
    rows, n = 64, 21
    plain, shuffled = _generator(seed=5), _generator(seed=5)
    shuffled.shuffle_batches(PREFILL, n, rows)
    g = np.arange(PREFILL, PREFILL + n * rows, dtype=np.int64)
    src = shuffled._source_rows(g)
    assert sorted(src.tolist()) == g.tolist()
    batch_of = (src - PREFILL) // rows
    for b in range(n):
        took = batch_of[b * rows:(b + 1) * rows]
        assert len(set(took.tolist())) == 1            # whole batches
        assert took[0] // mod.GROUP == b // mod.GROUP  # inside the group
        assert (src[b * rows:(b + 1) * rows] % rows
                == g[b * rows:(b + 1) * rows] % rows).all()
    assert (batch_of[::rows] != np.arange(n)).any()
    other = _generator(seed=6)
    other.shuffle_batches(PREFILL, n, rows)
    assert (other._source_rows(g) != src).any()
    a, b = plain.columns(src), shuffled.columns(g)
    assert all((a[k] == b[k]).all() for k in a)


def test_an_id_past_the_reference_raises():
    with pytest.raises(ValueError, match="id_space"):
        _generator(id_space=4100).columns(
            np.arange(PREFILL + 40_000, PREFILL + 41_000))


def test_bids_uniform_draws_no_key_more_often_than_chance_allows(spec):
    data = spec.cell(CONTROL).config["data"]
    small = {**data, "n_keys": 20_000}
    uniform = load_module(BENCH_DIR, "generators", "bids_uniform") \
        .make_generator(small, 20_480, 0)
    skewed = load_module(BENCH_DIR, "generators", "bids") \
        .make_generator(small, 20_480, 0)
    g = np.arange(20_480, 20_480 + 400_000, dtype=np.int64)
    counts = np.bincount(uniform.columns(g)["auction"], minlength=20_000)
    # 20 bids a key on average: Poisson(20) passes 50 once in 10^8 keys
    assert counts.max() < 50 and counts.min() > 0
    assert np.bincount(skewed.columns(g)["auction"]).max() > 1500
    # the prefill is bids.py's, letter for letter
    pre = np.arange(20_480)
    assert (uniform.columns(pre)["auction"]
            == skewed.columns(pre)["auction"]).all()
    assert data["hot_share"] == 0.5          # the file is q5-10m's own


# -- the files -------------------------------------------------------------

def test_the_cells_are_one_chip_and_listed_where_the_issue_says(spec):
    cell, control = spec.cell(CELL), spec.cell(CONTROL)
    assert (cell.chips, cell.config_name, cell.traffic_name) \
        == (1, CONFIG, "bids-inflight-780k")
    assert (control.chips, control.config_name, control.traffic_name) \
        == (1, "nexmark-q5-10m", "bids-saturated-uniform")
    for c in (cell, control):
        assert [m["name"] for m in c.end_to_end] == ["events_per_s",
                                                     "setup_s"]
    assert {m["name"] for m in cell.per_layer} == set(NEW) | set(SHARED)
    assert {m["name"] for m in control.per_layer} \
        == set(SHARED) | {"probe_wide_batch_share"}
    by_name = {m["name"]: m for m in spec.benchmark["per_layer"]}
    for name in NEW:
        m = by_name[name]
        assert CELL in m["workloads"] and m["moves"] == "events_per_s"
        body = spec.layer_metric(name)
        assert (body["layer"], body["unit"]) == (m["layer"], m["unit"])
    assert by_name["probe_wide_batch_share"]["workloads"] \
        == [CELL, CONTROL, "q5-10m-saturated"]
    for name in SHARED:
        assert by_name[name]["workloads"][-2:] == [CELL, CONTROL]
    chips = [w["chips"] for w in spec.benchmark["workloads"]]
    assert chips.count(4) == 1


def test_the_configuration_states_what_it_is_and_what_it_assumes(spec):
    entry = next(c for c in spec.benchmark["configs"] if c["name"] == CONFIG)
    cfg, q5 = spec.cell(CELL).config, spec.cell("q5-10m-saturated").config
    assert cfg["source"] == entry["source"] and len(entry["source"]) <= 200
    assert cfg["reduced"] == entry["reduced"] == q5["reduced"]
    assert set(cfg["reduced_notes"]) == set(cfg["reduced"])
    for key in ("deployment", "guarantees", "assumed", "rehearse"):
        assert cfg[key]
    assert "capacity unchanged" in cfg["guarantees"]["path"]
    assert {"in_flight", "generator_constants", "id_space",
            "reinsert"} <= set(cfg["assumed"])
    q = {**cfg["query"]}
    assert (q.pop("module"), q.pop("capacity")) == ("q5_inflight", 1 << 23)
    want = {**q5["query"]}
    del want["module"], want["capacity"]
    assert q == want
    d = cfg["data"]
    assert d["in_flight"] == d["n_keys"] == 4_500_000
    assert (d["new_auctions_per_bid"], d["hot_every_auctions"],
            d["id_lead"], d["hot_share"]) == ([3, 46], 100, 10, 0.5)
    assert (cfg["batch_rows"], cfg["state"]) == (q5["batch_rows"],
                                                q5["state"])
    assert cfg["trace_s"] <= 16 and cfg["timeout_s"] == 600
    # every id a run of run_seconds can make fits the reference
    rows = 18 + 12 + 148
    assert d["id_space"] > d["in_flight"] + (rows - 18) * cfg[
        "batch_rows"] * 3 // 46 + d["id_lead"]
    assert load_module(BENCH_DIR, "queries", "q5_inflight").build \
        is not None


# -- the metrics, on hand-built inputs -------------------------------------

def _plane(modules):
    return {"name": "/device:TPU:0", "lines": [
        {"name": T.MODULE_LINE, "events": [[n, a, d] for n, a, d in modules]}]}


def _traced(spec, modules):
    cell = spec.cell(CELL)
    lo, hi = 0.0, 100e9
    host = {"name": "/host:CPU", "lines": [{"name": "bench-tracer", "events": [
        [T.WINDOW_ANNOTATION, lo, hi - lo]]}]}
    return SimpleNamespace(trace={"planes": [_plane(modules), host]},
                           config=cell.config)


def test_reclaim_bytes_from_shapes_alone():
    # table in and out, the count plane once more for the mask, both
    # planes in and out
    assert reclaim_bytes(1 << 23, 16, 8, [4, 8]) == (
        2 * 8 * (1 << 23) + 16 * 4 * (1 << 23) + 2 * 16 * 12 * (1 << 23))


def test_reclaim_device_ms_and_its_roofline_on_a_module_line(spec,
                                                             monkeypatch):
    modules = [("jit_lookup_or_insert(1)", 1e9, 1e8),
               ("jit_reclaim(7)", 2e9, 2.0e9),
               ("jit_fold(2)", 5e9, 1e8),
               ("jit_reclaim(7)", 20e9, 3.0e9),
               ("jit_fold(2)", 24e9, 1e8)]
    run = _traced(spec, modules)
    params = spec.layer_metric("reclaim_device_ms")["params"]
    reader = load_module(BENCH_DIR, "readers", "trace_module_time")
    assert reader.read(run, params) == pytest.approx(2500.0)
    roof = load_module(BENCH_DIR, "readers", "reclaim_roofline")
    monkeypatch.setattr(roof, "device_block",
                        lambda: {"kind": "TPU v5 lite"})
    share = roof.read(run, spec.layer_metric(
        "reclaim_roofline_share")["params"])
    least = reclaim_bytes(1 << 23, 16, 8, [4, 8]) / 819e9
    assert share == pytest.approx(100 * least / 2.5)
    assert 0 < share < 100
    # a recording without a reclaim reads nothing
    none = _traced(spec, [m for m in modules if "reclaim" not in m[0]])
    assert reader.read(none, params) is None
    assert roof.read(none, spec.layer_metric(
        "reclaim_roofline_share")["params"]) is None


def test_the_counter_metrics_read_their_shares(spec):
    rows = 1 << 18
    first = {"state_reclaim_keys_kept_total": 10,
             "state_reclaim_keys_freed_total": 30,
             "probe_wide_batches_total": 2, "probe_rows_total": 28 * rows,
             "fold_batches_total": 30}
    # the probe's counters trail the host's by two batches at t0
    last = {"state_reclaim_keys_kept_total": 10 + 4_600_000,
            "state_reclaim_keys_freed_total": 30 + 5_500_000,
            "probe_wide_batches_total": 2 + 90,
            "probe_rows_total": 178 * rows, "fold_batches_total": 178}
    schedule = SimpleNamespace(batch_rows=rows)
    run = SimpleNamespace(at_t0={"device_stats": first},
                          at_end={"device_stats": last}, schedule=schedule)

    def read(name, r=run):
        body = spec.layer_metric(name)
        return load_module(BENCH_DIR, "readers", body["reader"]).read(
            r, body["params"])

    assert read("reclaim_freed_share") == pytest.approx(100 * 5.5 / 10.1)
    assert read("probe_wide_batch_share") == pytest.approx(100 * 90 / 150)
    # a program without the counters (the parent) reads nothing
    old = SimpleNamespace(at_t0={"device_stats": {"fold_batches_total": 1}},
                          at_end={"device_stats": {"fold_batches_total": 9}},
                          schedule=schedule)
    assert read("reclaim_freed_share", old) is None
    assert read("probe_wide_batch_share", old) is None
    # and a run without a reclaim reads nothing, and raises nothing
    still = SimpleNamespace(at_t0={"device_stats": first},
                            at_end={"device_stats": {**first,
                                                     "probe_rows_total":
                                                     178 * rows}},
                            schedule=schedule)
    assert read("reclaim_freed_share", still) is None
    assert read("probe_wide_batch_share", still) == 0.0


# -- the rehearsal and its control -----------------------------------------

def _run(spec):
    return run_cell(spec, spec.cell(CELL), seed=SEED, seconds=4.0,
                    trace=False, rehearse=True)


def _check(run, name):
    return next(c for c in run.checks if c["check"] == name)


def test_a_rehearsal_of_the_cell_is_correct_and_reads_its_stage(spec):
    from flink_tpu.metrics import DEVICE_STATS
    from flink_tpu.metrics.tracing import TRACER

    TRACER.reset()
    before = DEVICE_STATS.snapshot()
    try:
        run = _run(spec)
        assert run.correct and run.failed == 0, [
            c for c in run.checks if not c.get("ok", True)]
        assert _check(run, "capacity_grown_by")["value"] == 0
        body = spec.layer_metric("reclaim_stage_ms")
        stage = load_module(BENCH_DIR, "readers", body["reader"])
        values = stage.samples(run, body["params"])
        assert len(values) >= 1 and stage.read(run, body["params"]) > 0
    finally:
        TRACER.reset()
    after = DEVICE_STATS.snapshot()
    assert after["state_reclaim_sweeps_total"] \
        - before["state_reclaim_sweeps_total"] >= len(values)
    assert json.dumps(run.checks)


def test_the_control_without_the_reclaim_fails_on_capacity(spec,
                                                           monkeypatch):
    from flink_tpu.state.tpu_backend import TpuKeyedStateBackend

    monkeypatch.setattr(TpuKeyedStateBackend, "_reclaimable",
                        lambda self: False)
    run = _run(spec)
    assert not run.correct
    assert _check(run, "capacity_grown_by")["value"] == 1 << 13
    assert _check(run, "rows_differ")["value"] == 0
    assert _check(run, "windows_missing")["value"] == 0
