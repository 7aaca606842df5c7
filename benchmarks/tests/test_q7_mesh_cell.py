"""The cell ``q7-16m-mesh4-saturated`` (PR 48) as files and at rehearsal
size: its configuration, its query module (``q7.py``'s maps and aggregate
LOADED through ``_Wiring``), the cell through ``run_cell`` on four
virtual CPU devices (q7_mesh_cell_driver.py, a process of its own) sound
and against a reference fed another bidder, its three new per-layer
metrics on hand-built recordings, and its place in BENCHMARK.json. The
job itself is held to the reference in tier-1 (``tests/test_mesh_q7.py``).
"""

import json
import os
import subprocess
import sys
from types import SimpleNamespace

import numpy as np
import pytest

from benchmarks.harness.cell import effective_config
from benchmarks.harness.fold_bytes import scatter_fold_bytes
from benchmarks.harness.spec import BENCH_DIR, REPO_ROOT, load_module, \
    load_spec

CELL = "q7-16m-mesh4-saturated"
CONFIG = "nexmark-q7-16m-mesh4"
NEW = ("mesh_max_fold_roofline_share", "mesh_wide_select_roofline_share",
       "mesh_fire_guarded_share")
#: the accepted metrics ISSUE 48 appends the cell to
APPENDED = (
    "window_task_busy_share", "ingest_step_ms", "peak_hbm_gb",
    "batch_queue_ms.saturated", "exchange_collective_share",
    "exchange_roofline_share", "exchange_rounds_per_step",
    "mesh_probe_fold_share", "mesh_upload_ms", "mesh_fire_device_ms",
    "mesh_fire_select_passes", "mesh_fold_rows_per_step", "step_x64_ms",
    "step_probe_window0_ms", "step_probe_tail_ms", "step_fold_value_ms",
    "step_fold_count_ms", "step_fold_rows_ms", "step_exchange_pack_ms",
    "step_plan_sync_ms", "step_unnamed_share", "fire_x64_ms",
    "fire_select_ms", "fire_unnamed_share")
DRIVER = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "q7_mesh_cell_driver.py")


def _drive(mode, seed=2_147_483_659):
    proc = subprocess.run([sys.executable, DRIVER, mode, str(seed)],
                          cwd=REPO_ROOT, capture_output=True, text=True,
                          timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.fixture(scope="module")
def spec():
    return load_spec()


def test_the_cell_is_four_chip_q7_under_the_mesh_cells_mix(spec):
    cell = spec.cell(CELL)
    assert (cell.chips, cell.config_name, cell.traffic_name) \
        == (4, CONFIG, "bids-saturated-x4")
    assert spec.benchmark["workloads"][-1]["name"] == CELL
    assert spec.benchmark["configs"][-1]["name"] == CONFIG
    assert [m["name"] for m in cell.end_to_end] == ["events_per_s",
                                                    "setup_s"]
    assert {m["name"] for m in cell.per_layer} == set(NEW) | set(APPENDED)
    by_name = {m["name"]: m for m in spec.benchmark["per_layer"]}
    for name in APPENDED:
        assert by_name[name]["workloads"][-1] == CELL
    for name in NEW:
        entry, body = by_name[name], spec.layer_metric(name)
        assert entry["workloads"] == [CELL]
        assert (entry["moves"], entry["unit"]) == ("events_per_s", "%")
        assert (body["layer"], body["unit"]) == (entry["layer"], "%")
    # the one-chip programs' byte counts and patterns stay off the cell,
    # and the two metrics that read nothing since PR 34 are not fed
    for name in ("ingest_roofline_share", "probe_rounds_p50",
                 "probe_tail_share", "fold_max_ms", "fold_max_roofline",
                 "q7_fire_device_ms", "q7_fire_select_passes",
                 "max_fold_ms", "max_fold_roofline_share"):
        assert CELL not in by_name[name]["workloads"], name
    chips = [w["chips"] for w in spec.benchmark["workloads"]]
    assert len(chips) == 9 and chips.count(4) == 3


def test_the_configuration_is_q7s_query_over_the_mesh_cells_data(spec):
    entry = spec.benchmark["configs"][-1]
    cfg = spec.cell(CELL).config
    q7 = spec.cell("q7-10m-saturated").config
    mesh = spec.cell("q5-16m-mesh4-saturated").config
    assert cfg["source"] == entry["source"] and len(entry["source"]) <= 200
    assert cfg["source"] not in (q7["source"], mesh["source"])
    assert cfg["architecture"] is None and cfg["chips"] == 4
    assert cfg["reduced"] == entry["reduced"] == q7["reduced"]
    assert cfg["reduced_notes"] == q7["reduced_notes"]
    # the SAME data set as M, letter for letter; the query's widths Q's
    assert cfg["data"] == mesh["data"]
    q = cfg["query"]
    for key in ("window_size_ms", "price_bits", "word_shift", "topk",
                "ring_size", "async_fire", "aggregates"):
        assert q[key] == q7["query"][key], key
    for key in ("operator", "n_devices", "capacity", "device_batch"):
        assert q[key] == mesh["query"][key], key
    assert q["module"] == "q7_mesh" and "word_dtype" not in q
    assert q["device_batch"] * q["n_devices"] == cfg["batch_rows"] == 1 << 18
    # Q's four stated departures from q7.sql, with their reasons
    assert {"join", "keying", "ring_size", "word"} <= set(cfg["assumed"])
    assert cfg["assumed"]["join"] == q7["assumed"]["join"]
    assert cfg["assumed"]["word"] == q7["assumed"]["word"]
    assert cfg["guarantees"]["results"] == q7["guarantees"]["results"]
    assert cfg["guarantees"]["delivery"] == q7["guarantees"]["delivery"]
    assert "every shard" in cfg["guarantees"]["path"]
    assert cfg["prefill_panes"] == q["ring_size"] - 1 - 2
    for key in ("setup_lead_panes", "quiet_s", "warm_s", "trace_s"):
        assert cfg[key] == q7[key], key
    assert cfg["state"]["cell_bytes"] == [8, 8]
    # bytes a chip, as the deployment states them: 7% of 16 GB
    chip = q["capacity"] * 8 * (1 + 2 * q["ring_size"])
    assert round(chip / 1e9, 2) == 1.14 and "1.14 GB a chip" in \
        cfg["deployment"] and "7%" in cfg["deployment"]
    assert 4_001_857 < 0.6 * q["capacity"]
    tiny, _traffic = effective_config(spec.cell(CELL), rehearse=True)
    tq = tiny["query"]
    assert tq["device_batch"] * tq["n_devices"] == tiny["batch_rows"]
    assert tiny["data"]["n_keys"] / 4 < 0.5 * tq["capacity"]
    # the rehearsal leaves the prices alone: the word keeps its 43 bits
    assert "price_max" not in cfg["rehearse"].get("data", {})


def test_the_query_module_loads_q7s_and_wires_the_mesh_operator(spec):
    q7 = load_module(BENCH_DIR, "queries", "q7")
    q7_mesh = load_module(BENCH_DIR, "queries", "q7_mesh")
    for name in ("SCHEMA_FIELDS", "TS_COLUMN", "KEY_COLUMN"):
        assert getattr(q7_mesh, name) == getattr(q7, name)
    for name in ("pane_ms", "window_panes", "word_bits", "make_reference",
                 "window_holds_data", "compare_window"):
        assert getattr(q7_mesh, name).__module__ == q7.__name__, name
    cfg = spec.cell(CELL).config
    q, data = cfg["query"], cfg["data"]
    assert q7_mesh.word_bits(q) == 43
    q7_mesh.make_reference(q, data, lambda *_: None)
    broken = dict(data, price_max=(1 << 22) - 1)     # a promise not kept
    with pytest.raises(ValueError):
        q7_mesh.make_reference(q, broken, lambda *_: None)
    # what q7.build wires is taken down, not copied: its two maps by
    # name and its one aggregate with the promise
    wired = q7_mesh._Wiring()
    q7.build(wired, dict(q, operator="device_aggregate",
                         defer_overflow=True), None)
    assert [name for _fn, name, _schema in wired.maps] \
        == ["PackBid", "UnpackWinner"]
    (agg,) = wired.aggs
    assert (agg.kind, agg.field, agg.out_name, agg.value_bits) \
        == ("max", "word", "best", 43)
    assert q7_mesh.operator_class(q).__name__ == "MeshWindowAggOperator"
    with pytest.raises(ValueError):
        q7_mesh.build(None, dict(q, operator="device_aggregate"), None)


def test_sound_rehearsal_is_correct_on_four_devices():
    out = _drive("sound")
    assert out["devices"] == 4
    assert out["query_file"] == f"{BENCH_DIR}/queries/q7_mesh.py"
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert all(v == 0 for v in out["checks"].values()), out["checks"]
    tally = out["tally"]
    assert tally["rows_compared"] == tally["windows_emitted"] \
        == tally["windows_expected"] >= 9
    assert out["capacity"] == [16384, 16384]     # per shard, nothing grown
    assert min(out["occupied"]) > 4000 and sum(out["occupied"]) == 20000
    assert out["steps_job"] == out["batches"]
    # the promise reached every fire's select: 43 bits, no guard
    assert out["value_bits"] == [43] and out["guarded_timed"] == 0
    assert set(out["select_passes"]) <= {42, 43}
    readers = out["readers"]
    assert readers["mesh_fire_guarded_share"] == 0.0
    assert 42 <= readers["mesh_fire_select_passes"] <= 43
    assert 1.0 <= readers["exchange_rounds_per_step"] < 1.5
    assert 1.0 <= readers["mesh_fold_rows_per_step"] <= 2.0
    assert readers["mesh_upload_ms"] > 0
    # no device trace on the CPU: the roofline shares read nothing
    assert readers["mesh_max_fold_roofline_share"] is None
    assert readers["mesh_wide_select_roofline_share"] is None


def test_a_reference_fed_another_bidder_makes_the_cell_not_correct():
    out = _drive("other_bidder")
    assert not out["correct"]
    tally = out["tally"]
    assert out["checks"]["rows_differ"] == tally["windows_emitted"] >= 9
    assert out["checks"]["windows_missing"] == 0
    assert out["checks"]["capacity_grown_by"] == 0


# -- the three new metric files on recorded runs ---------------------------

def _run(keys, first_guarded=0, last_guarded=0, passes=43 * 5):
    """A run's face to the readers: a schedule whose first timed block is
    ``keys``, a four-device operator, counters before and after."""
    return SimpleNamespace(
        trace=object(),
        config={"query": {"n_devices": 4, "device_batch": len(keys) // 4,
                          "capacity": 1 << 23}},
        schedule=SimpleNamespace(
            batch_rows=len(keys), batch_index=lambda b: b,
            phase=lambda name: SimpleNamespace(first_batch=3)),
        generator=SimpleNamespace(columns=lambda b: {"auction": keys}),
        query=SimpleNamespace(KEY_COLUMN="auction"),
        operator=SimpleNamespace(_max_parallelism=128),
        at_t0={"device_stats": {"fire_selects_total": 2,
                                "fire_select_passes_total": 85,
                                "fire_select_guarded_total": first_guarded}},
        at_end={"device_stats": {"fire_selects_total": 7,
                                 "fire_select_passes_total": 85 + passes,
                                 "fire_select_guarded_total": last_guarded}})


def test_the_max_folds_roofline_counts_the_busiest_shards_rows(
        spec, monkeypatch):
    from flink_tpu.core.keygroups import hash_batch, \
        key_groups_for_hash_batch

    body = spec.layer_metric("mesh_max_fold_roofline_share")
    assert body["reader"] == "mesh_fold_roofline"
    assert (body["params"]["module"], body["params"]["region"]) \
        == ("^jit_step\\(", "/fold\\.max/")
    # fold_max_roofline's model of the bytes, letter for letter
    assert body["params"]["roofline"] \
        == spec.layer_metric("fold_max_roofline")["params"]["roofline"]
    reader = spec.module("readers", body["reader"])
    rng = np.random.default_rng(5)
    keys = np.r_[rng.integers(0, 5000, 3072), np.full(1024, 17)]
    rng.shuffle(keys)
    keys = keys.astype(np.int64)
    shard = key_groups_for_hash_batch(hash_batch(keys), 128) // 32
    rows = np.bincount(shard, minlength=4)
    hot = int(shard[keys == 17][0])
    assert rows.argmax() == hot and rows[hot] > 1024 + 600
    touched = len(np.unique(keys[shard == hot]))
    run = _run(keys)
    assert reader.shard_folds(run)[hot] == (int(rows[hot]), touched)
    assert [r for r, _t in reader.shard_folds(run)] == rows.tolist()
    monkeypatch.setattr(reader, "device_block",
                        lambda: {"kind": "TPU v5 lite"})
    monkeypatch.setattr(reader._region, "measured",
                        lambda run, params: (0.030, 0.200, 2))
    nbytes = scatter_fold_bytes(int(rows[hot]), 8, 4, touched, 8)
    assert reader.read(run, body["params"]) \
        == pytest.approx(100.0 * (nbytes / 819e9) / 0.015)
    # a program without the scope, or no trace: nothing, not an error
    monkeypatch.setattr(reader._region, "measured", lambda run, params: None)
    assert reader.read(run, body["params"]) is None


def test_the_wide_selects_roofline_is_passes_times_slots_over_the_region(
        spec, monkeypatch):
    body = spec.layer_metric("mesh_wide_select_roofline_share")
    assert body["reader"] == "select_roofline"
    fire = body["params"]["fire"]
    # the fire group and region of fire_select_ms, on the mesh
    accepted = spec.layer_metric("fire_select_ms")["params"]
    assert fire["regions"] == ["fire.global"]
    assert set(fire["regions"]) < set(accepted["regions"])
    assert (fire["anchor"], fire["modules"]) \
        == (accepted["anchor"], accepted["modules"])
    reader = spec.module("readers", body["reader"])
    monkeypatch.setattr(reader, "device_block",
                        lambda: {"kind": "TPU v5 lite"})
    asked = []

    def partition(run, params):
        asked.append(params)
        return 6.0                                  # ms a fire

    monkeypatch.setattr(reader._partition, "read", partition)
    run = _run(np.arange(1024))
    least = 43 * (1 << 23) * 8 / 819e9
    assert reader.read(run, body["params"]) \
        == pytest.approx(100.0 * least / 0.006)
    assert asked == [{**fire, "as": "ms"}]
    # no region time (a program from before PR 37, no trace): nothing
    monkeypatch.setattr(reader._partition, "read", lambda run, params: None)
    assert reader.read(run, body["params"]) is None
    # no counters (a program from before PR 31): nothing
    monkeypatch.setattr(reader._partition, "read", partition)
    run.at_t0 = {"device_stats": {}}
    assert reader.read(run, body["params"]) is None


@pytest.mark.parametrize("first, last, share", [(0, 0, 0.0), (2, 7, 100.0),
                                                (0, 2, 40.0)])
def test_the_guarded_share_is_guarded_fires_over_ranked_fires(
        spec, first, last, share):
    body = spec.layer_metric("mesh_fire_guarded_share")
    assert body["reader"] == "device_stats_share"
    assert body["params"] == {"part": "fire_select_guarded_total",
                              "whole": "fire_selects_total"}
    reader = spec.module("readers", body["reader"])
    run = _run(np.arange(1024), first, last)
    assert reader.read(run, body["params"]) == pytest.approx(share)
    # a program without the counter (the parent of PR 48): nothing
    for stats in (run.at_t0, run.at_end):
        del stats["device_stats"]["fire_select_guarded_total"]
    assert reader.read(run, body["params"]) is None
