"""The four-chip cell at rehearsal size: ``q5-16m-mesh4-saturated``
through ``run_cell`` on four virtual CPU devices (mesh_cell_driver.py, a
process of its own), sound and broken on one shard, and what the data
files of the cell have to say."""

import json
import os
import subprocess
import sys

import pytest

from benchmarks.harness.cell import effective_config
from benchmarks.harness.spec import REPO_ROOT, load_spec

CELL = "q5-16m-mesh4-saturated"
DRIVER = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "mesh_cell_driver.py")


def _drive(mode, seed=2_147_483_659):
    proc = subprocess.run([sys.executable, DRIVER, mode, str(seed)],
                          cwd=REPO_ROOT, capture_output=True, text=True,
                          timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.fixture(scope="module")
def spec():
    return load_spec()


def test_sound_rehearsal_is_correct_on_four_devices():
    out = _drive("sound")
    assert out["devices"] == 4
    assert out["correct"] and out["failed"] == 0 and out["attempted"] > 0
    assert all(v == 0 for v in out["checks"].values()), out["checks"]
    tally = out["tally"]
    assert tally["windows_expected"] == tally["windows_emitted"] > 8
    assert tally["rows_compared"] == 50 * tally["windows_emitted"]
    want, got = out["capacity"]
    assert want == got                      # per shard, nothing grown
    # one step a batch; every block's four columns and its mask uploaded
    assert out["steps_job"] == out["batches"]
    assert out["h2d_bytes_job"] == out["batches"] * 2048 * (3 * 8 + 1)
    # what the new readers find on a CPU run: the counters and the ring
    # are there, the device trace is not. At rehearsal size a slice is
    # 512 rows and its round capacity 160: the fullest shard's bucket
    # (0.265 of a slice, 136 +- 10 rows) passes it now and then
    assert 1.0 <= out["readers"]["exchange_rounds_per_step"] < 1.5
    assert out["readers"]["mesh_upload_ms"] > 0
    assert out["readers"]["exchange_collective_share"] is None
    # the first timed reading may lack the steps still in flight
    assert 0 <= out["steps_timed"] - out["timed_batches"] <= 8


def test_an_answer_altered_after_the_merge_of_the_shards_is_not_correct():
    out = _drive("altered")
    assert not out["correct"]
    assert out["checks"]["rows_differ"] == 1
    assert out["checks"]["windows_missing"] == 0


def test_a_slice_one_device_never_exchanged_is_not_correct():
    out = _drive("slice_lost")
    assert not out["correct"]
    assert out["checks"]["rows_differ"] > 0
    assert out["checks"]["capacity_grown_by"] == 0


def test_the_mesh_configuration_says_what_the_issue_asks(spec):
    cell = spec.cell(CELL)
    one = spec.cell("q5-10m-saturated").config
    cfg, q = cell.config, cell.config["query"]
    assert cell.chips == cfg["chips"] == q["n_devices"] == 4
    assert cfg["data"]["n_keys"] == 16_000_000
    assert q["module"] == "q5_mesh" and q["operator"] == "mesh_aggregate"
    assert q["capacity"] == 1 << 23
    assert q["device_batch"] * q["n_devices"] == cfg["batch_rows"] == 1 << 18
    # no shard over 4,001,857 keys (PR 24): under the growth threshold
    assert 4_001_857 < 0.6 * q["capacity"]
    assert cfg["state"]["cell_bytes"] == [8, 8]
    for key in ("ring_size", "topk", "async_fire", "window_size_ms",
                "window_slide_ms"):
        assert q[key] == one["query"][key], key
    for key in ("hot_keys", "hot_share", "price_max", "n_bidders",
                "record_bytes", "layout_seed", "columns", "born"):
        assert cfg["data"][key] == one["data"][key], key
    assert cfg["reduced"] == one["reduced"]
    assert cfg["guarantees"]["results"] == one["guarantees"]["results"]
    assert cfg["guarantees"]["delivery"] == one["guarantees"]["delivery"]
    assert "per shard" in cfg["guarantees"]["path"]
    assert cfg["source"] != one["source"]
    tiny, _traffic = effective_config(cell, rehearse=True)
    tq = tiny["query"]
    assert tq["device_batch"] * tq["n_devices"] == tiny["batch_rows"]
    assert tiny["data"]["n_keys"] / 4 < 0.5 * tq["capacity"]


def test_the_mesh_traffic_is_a_rate_and_nothing_that_throttles(spec):
    traffic = spec.cell(CELL).traffic
    assert set(traffic) == {"name", "generator", "pacing", "event_rate",
                            "what", "rehearse"}
    assert traffic["generator"] == "bids"
    assert traffic["pacing"] == "unthrottled"
    assert traffic["event_rate"] >= 260_000
    assert traffic["event_rate"] % 10_000 == 0


def test_the_cell_reports_the_metrics_the_issue_lists(spec):
    cell = spec.cell(CELL)
    assert {m["name"] for m in cell.end_to_end} == {"events_per_s",
                                                    "setup_s"}
    assert {m["name"] for m in cell.per_layer} == {
        "window_task_busy_share", "ingest_step_ms", "peak_hbm_gb",
        "batch_queue_ms.saturated", "exchange_collective_share",
        "exchange_roofline_share", "exchange_rounds_per_step",
        "mesh_probe_fold_share", "mesh_upload_ms"}
    by_name = {m["name"]: m for m in spec.benchmark["per_layer"]}
    for name in ("exchange_collective_share", "exchange_roofline_share",
                 "exchange_rounds_per_step"):
        assert by_name[name]["layer"] == "exchange"
        assert by_name[name]["workloads"] == [CELL]
    # the one-chip step's byte count and patterns stay off the mesh cell
    for name in ("ingest_roofline_share", "probe_rounds_p50",
                 "probe_tail_share"):
        assert CELL not in by_name[name]["workloads"]
