"""The cell ``q5-inflight-mesh4-saturated`` (PR 41) as files: the
configuration, its place in BENCHMARK.json, the metric files that read
the mesh reclaim on hand-built inputs, and the rehearsal on four virtual
CPU devices (inflight_mesh_cell_driver.py, a process of its own) with its
control: the reclaim switched off underneath. The rehearsal is mirrored in
tier-1 (``tests/test_mesh_reclaim.py``)."""

import json
import os
import subprocess
import sys
from types import SimpleNamespace

import pytest

from benchmarks.harness import trace as T
from benchmarks.harness.cell import effective_config
from benchmarks.harness.reclaim_bytes import reclaim_bytes
from benchmarks.harness.spec import BENCH_DIR, REPO_ROOT, load_module, \
    load_spec

CELL = "q5-inflight-mesh4-saturated"
CONFIG = "nexmark-q5-inflight-mesh4"
ONE_CHIP, MESH = "q5-inflight-saturated", "q5-16m-mesh4-saturated"
DRIVER = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "inflight_mesh_cell_driver.py")
#: the metrics of the mesh cell and the reclaim's of the one-chip
#: in-flight cell, which read the same counters, stage and program name
RECLAIM = ("reclaim_device_ms", "reclaim_roofline_share",
           "reclaim_stage_ms", "reclaim_freed_share", "reclaim_rehome_ms",
           "reclaim_remap_ms")


def _drive(mode, seed=3_000_000_019):
    proc = subprocess.run([sys.executable, DRIVER, mode, str(seed)],
                          cwd=REPO_ROOT, capture_output=True, text=True,
                          timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.fixture(scope="module")
def spec():
    return load_spec()


# -- the files -------------------------------------------------------------

def test_the_cell_is_four_chips_and_listed_where_the_issue_says(spec):
    cell = spec.cell(CELL)
    assert (cell.chips, cell.config_name, cell.traffic_name) \
        == (4, CONFIG, "bids-inflight-780k")
    assert spec.benchmark["workloads"][-1]["name"] == CELL
    assert len(cell.why) <= 200
    assert [m["name"] for m in cell.end_to_end] == ["events_per_s",
                                                    "setup_s"]
    mesh = {m["name"] for m in spec.cell(MESH).per_layer}
    assert {m["name"] for m in cell.per_layer} \
        == mesh | set(RECLAIM) | {"mesh_insert_share"}
    by_name = {m["name"]: m for m in spec.benchmark["per_layer"]}
    for name in (*mesh, *RECLAIM):
        assert by_name[name]["workloads"][-1] == CELL, name
    assert by_name["mesh_insert_share"]["workloads"] == [CELL]
    assert spec.benchmark["per_layer"][-1]["name"] == "mesh_insert_share"
    body = spec.layer_metric("mesh_insert_share")
    assert (body["layer"], body["unit"], body["moves"]) == (
        by_name["mesh_insert_share"]["layer"], "%", "events_per_s")
    # the one-chip step's byte count and patterns stay off the mesh cells
    for name in ("ingest_roofline_share", "probe_rounds_p50",
                 "probe_tail_share", "probe_wide_batch_share",
                 "fold_rows_per_batch"):
        assert CELL not in by_name[name]["workloads"]
    chips = [w["chips"] for w in spec.benchmark["workloads"]]
    assert (len(chips), chips.count(4)) == (7, 2)


def test_the_configuration_is_the_two_it_combines(spec):
    entry = spec.benchmark["configs"][-1]
    cfg = spec.cell(CELL).config
    one, mesh = spec.cell(ONE_CHIP).config, spec.cell(MESH).config
    assert entry["name"] == CONFIG and entry["file"].endswith(
        f"configs/{CONFIG}.json")
    assert cfg["source"] == entry["source"] and len(entry["source"]) <= 200
    assert cfg["source"] not in (one["source"], mesh["source"])
    assert cfg["reduced"] == entry["reduced"] == one["reduced"]
    assert set(cfg["reduced_notes"]) == set(cfg["reduced"])
    q = {**cfg["query"]}
    assert q.pop("module") == "q5_inflight_mesh"
    want = {**mesh["query"]}
    del want["module"]
    assert q == want and q["capacity"] == 1 << 23 and cfg["chips"] == 4
    d, od = cfg["data"], one["data"]
    assert 19_000_000 <= d["in_flight"] == d["n_keys"] <= 19_700_000
    for key in ("new_auctions_per_bid", "hot_share", "hot_every_auctions",
                "id_lead", "price_max", "n_bidders", "layout_seed",
                "record_bytes", "columns", "born"):
        assert d[key] == od[key], key
    # every id a run of run_seconds can make fits the reference
    rows = 12 + 148
    assert d["id_space"] > d["in_flight"] + rows * cfg["batch_rows"] \
        * 3 // 46 + d["id_lead"]
    # the fullest shard starts under the limit it will pass
    assert 0.5 < d["in_flight"] / 4 / q["capacity"] < 0.6
    assert (cfg["batch_rows"], cfg["state"]) == (mesh["batch_rows"],
                                                mesh["state"])
    assert cfg["state"]["cell_bytes"] == [8, 8]
    assert (cfg["warm_s"], cfg["prefill_panes"], cfg["setup_lead_panes"],
            cfg["quiet_s"], cfg["timeout_s"]) == (4.0, 9, 2, 5.0, 600)
    assert cfg["guarantees"]["results"] == one["guarantees"]["results"]
    assert cfg["guarantees"]["delivery"] == one["guarantees"]["delivery"]
    path = cfg["guarantees"]["path"]
    assert "per shard" in path and "no program is built" in path
    assert {"in_flight", "layout", "count_cell", "generator_constants",
            "id_space", "reinsert"} <= set(cfg["assumed"])
    tiny, _traffic = effective_config(spec.cell(CELL), rehearse=True)
    tq = tiny["query"]
    assert tq["device_batch"] * tq["n_devices"] == tiny["batch_rows"]
    assert 0.5 < tiny["data"]["in_flight"] / 4 / tq["capacity"] < 0.6
    # the job is q5_mesh's and the reference q5_inflight's: loaded, not
    # copied
    mod = load_module(BENCH_DIR, "queries", "q5_inflight_mesh")
    assert mod.build.__code__.co_filename.endswith("queries/q5_mesh.py")
    assert mod.make_reference.__code__.co_filename.endswith(
        "queries/q5_inflight.py")


# -- the metric files, on hand-built inputs ---------------------------------

def _traced(spec, modules):
    cell = spec.cell(CELL)
    plane = {"name": "/device:TPU:0", "lines": [
        {"name": T.MODULE_LINE, "events": [[n, a, d] for n, a, d in modules]}]}
    host = {"name": "/host:CPU", "lines": [{"name": "bench-tracer", "events": [
        [T.WINDOW_ANNOTATION, 0.0, 100e9]]}]}
    return SimpleNamespace(trace={"planes": [plane, host]},
                           config=cell.config)


def test_the_reclaim_metrics_read_the_mesh_program_by_its_name(spec,
                                                               monkeypatch):
    """The mesh reclaim is ``jit_reclaim`` in a trace, as the one-chip
    backend's is, and the roofline's bytes are a SHARD's: the
    configuration's per-chip capacity under two int64 planes."""
    modules = [("jit_step(1)", 1e9, 1e8), ("jit_reclaim(7)", 2e9, 2.0e9),
               ("jit_step(1)", 5e9, 1e8)]
    run = _traced(spec, modules)
    reader = load_module(BENCH_DIR, "readers", "trace_module_time")
    assert reader.read(run, spec.layer_metric(
        "reclaim_device_ms")["params"]) == pytest.approx(2000.0)
    roof = load_module(BENCH_DIR, "readers", "reclaim_roofline")
    monkeypatch.setattr(roof, "device_block",
                        lambda: {"kind": "TPU v5 lite"})
    share = roof.read(run, spec.layer_metric(
        "reclaim_roofline_share")["params"])
    nbytes = reclaim_bytes(1 << 23, 16, 8, [8, 8])
    assert nbytes == (2 * 8 + 16 * 8 + 2 * 16 * 16) * (1 << 23)
    assert share == pytest.approx(100 * nbytes / 819e9 / 2.0)
    assert 0 < share < 100
    # the step's partition leaves a reclaim out by that name
    for name in ("step_probe_tail_ms", "step_x64_ms", "step_unnamed_share"):
        assert "^jit_reclaim\\(" in spec.layer_metric(name)[
            "params"]["exclude"], name


def test_mesh_insert_share_reads_its_two_counters(spec):
    first = {"mesh_inserted_rows_total": 19_600_000,
             "mesh_stepped_rows_total": 22_000_000}
    last = {"mesh_inserted_rows_total": 19_600_000 + 3_000_000,
            "mesh_stepped_rows_total": 22_000_000 + 38_000_000}
    run = SimpleNamespace(at_t0={"device_stats": first},
                          at_end={"device_stats": last})
    body = spec.layer_metric("mesh_insert_share")
    reader = load_module(BENCH_DIR, "readers", body["reader"])
    assert reader.read(run, body["params"]) == pytest.approx(100 * 3 / 38)
    # a program without the counters (the parent) reads nothing
    old = SimpleNamespace(at_t0={"device_stats": {"mesh_steps_total": 1}},
                          at_end={"device_stats": {"mesh_steps_total": 9}})
    assert reader.read(old, body["params"]) is None


# -- the rehearsal and its control ------------------------------------------

def test_sound_rehearsal_reclaims_on_every_shard_and_is_correct():
    out = _drive("sound")
    assert out["devices"] == 4
    assert out["correct"] and out["failed"] == 0 and out["attempted"] > 0
    assert all(v == 0 for v in out["checks"].values()), out["checks"]
    tally = out["tally"]
    assert tally["windows_expected"] == tally["windows_emitted"] >= 20
    want, got = out["capacity"]
    assert want == got == 1 << 13           # per shard, nothing grown
    job = out["job"]
    # one dispatch sweeps all four shards
    assert job["state_reclaim_sweeps_total"] == len(out["reclaims"]) >= 1
    for a in out["reclaims"]:
        assert a["capacity"] == 1 << 13 and a["freed"] > a["kept"] // 4 > 0
        assert a["kept"] + a["freed"] > 0.6 * (1 << 13) * 3
    # the moving hot id overfills a bucket of most slices: 256 hot rows
    # of a 512-row slice on one shard against a round capacity of 160
    assert job["mesh_exchange_rounds_total"] > 1.5 * job["mesh_steps_total"]
    assert 0 < job["mesh_inserted_rows_total"] \
        < job["mesh_stepped_rows_total"]
    readers = out["readers"]
    assert readers["exchange_rounds_per_step"] > 1.5
    assert 0 < readers["mesh_insert_share"] < 100
    assert readers["mesh_upload_ms"] > 0
    # the device trace is not there on a CPU run
    assert readers["reclaim_device_ms"] is None


def test_without_the_reclaim_the_rehearsal_fails_on_capacity():
    out = _drive("no_reclaim")
    assert not out["correct"]
    assert out["checks"]["capacity_grown_by"] >= 1 << 13
    assert out["checks"]["rows_differ"] == 0
    assert out["checks"]["windows_missing"] == 0
    assert out["job"]["state_reclaim_sweeps_total"] == 0
