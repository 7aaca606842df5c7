"""BENCHMARK.json resolves to files that exist, metrics point at metrics
their cells report, and a second benchmark made only of data files loads
without touching harness code."""

import json
import os
import re

import pytest

from benchmarks.harness import device
from benchmarks.harness.cell import effective_config
from benchmarks.harness.schedule import build_schedule
from benchmarks.harness.spec import REPO_ROOT, load_spec

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def spec():
    return load_spec()


def test_every_workload_resolves_to_files_that_exist(spec):
    assert spec.workload_names()
    for name in spec.workload_names():
        cell = spec.cell(name)
        assert os.path.isfile(spec.config_path(cell.config_name))
        assert os.path.isfile(spec.traffic_path(cell.traffic_name))
        assert cell.config["name"] == cell.config_name
        assert cell.config["chips"] == cell.chips
        q = cell.config["query"]
        for fn in ("build", "operator_class", "Q5Reference", "check_window",
                   "pane_ms", "window_panes"):
            assert hasattr(spec.module("queries", q["module"]), fn)
        assert hasattr(spec.module("generators", cell.traffic["generator"]),
                       "make_generator")
        assert cell.end_to_end and cell.per_layer
        assert "setup_s" in {m["name"] for m in cell.end_to_end}
        assert len(cell.end_to_end) >= 2
        for m in cell.end_to_end:
            assert hasattr(spec.module("end_to_end", m["name"]), "measure")
        for m in cell.per_layer:
            params = spec.layer_metric(m["name"])
            assert hasattr(spec.module("readers", params["reader"]), "read")


def test_layer_metrics_move_a_metric_their_cells_report(spec):
    bench = spec.benchmark
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    all_cells = set(spec.workload_names())
    for m in bench["per_layer"]:
        data = spec.layer_metric(m["name"])
        assert data["moves"] == m["moves"] and data["unit"] == m["unit"]
        assert data["layer"] == m["layer"]
        target = e2e[m["moves"]]
        reported_in = set(target.get("workloads", all_cells))
        for cell in m.get("workloads", all_cells):
            assert cell in reported_in, (m["name"], cell)


def test_no_latency_in_a_saturated_cell_and_no_rate_in_a_paced_one(spec):
    for name in spec.workload_names():
        cell = spec.cell(name)
        names = {m["name"] for m in cell.end_to_end}
        if cell.traffic["pacing"] == "unthrottled":
            assert "events_per_s" in names
            assert not any("latency" in n for n in names)
        else:
            assert "events_per_s" not in names
            assert "window_source_to_sink_p50_ms" in names


def test_contract_shape_of_benchmark_json(spec):
    b = spec.benchmark
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert 1 <= b["run_seconds"] <= 51 and b["paths"] == ["benchmarks"]
    assert os.path.getsize(os.path.join(REPO_ROOT, "BENCHMARK.json")) < 65536
    four = [w for w in b["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(b["workloads"]) // 2)
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert 1 <= len(w["why"]) <= 200 and w["chips"] in (1, 4)
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and 1 <= len(c["source"]) <= 200
        assert c["file"].startswith("benchmarks/") and len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])
        with open(os.path.join(REPO_ROOT, c["file"]), encoding="utf-8") as f:
            data = json.load(f)
        assert data["source"] == c["source"]
        assert data["reduced"] == c["reduced"]
        assert any(w["config"] == c["name"] for w in b["workloads"])
    for m in b["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in b["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "source", "layer", "moves"}
        assert m["source"] in SOURCES
    for m in b["end_to_end"] + b["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    names = [m["name"] for m in b["end_to_end"] + b["per_layer"]]
    assert len(names) == len(set(names))


def test_configuration_files_carry_what_the_issue_asks(spec):
    one = spec.cell("q5-10m-saturated").config
    assert one["data"]["n_keys"] == 10_000_000
    assert one["query"]["capacity"] == 1 << 24
    assert one["data"]["n_keys"] < 0.6 * one["query"]["capacity"]
    assert one["query"]["ring_size"] == 16 and one["query"]["topk"] == 1000
    assert one["data"]["record_bytes"] == 32
    assert one["batch_rows"] in (1 << 17, 1 << 18, 1 << 19)
    for key in ("reduced", "assumed", "guarantees", "rehearse"):
        assert one[key]
    sat = spec.cell("q5-10m-saturated").traffic
    steady = spec.cell("q5-10m-steady").traffic
    assert sat["event_rate"] == steady["event_rate"]
    assert sat["event_rate"] % 10_000 == 0
    # the saturated mix is {pacing, event_rate} and nothing that throttles
    assert set(sat) == {"name", "generator", "pacing", "event_rate", "what",
                        "rehearse"}


def test_peak_table_errors_on_an_unknown_device_kind():
    assert device.peak("TPU v5 lite", "hbm_bytes_per_s") == 819e9
    for kind in ("TPU v9 imaginary", "cpu", "_source"):
        with pytest.raises(KeyError):
            device.peak(kind, "hbm_bytes_per_s")


def test_a_second_benchmark_is_only_new_files(tmp_path):
    """A tiny configuration, a traffic mix, a per-layer metric and the
    BENCHMARK.json entries for them, in a directory of their own: the
    harness finds them by name, and its code is not touched (queries,
    generators, readers are found in the benchmark's own directory)."""
    bench = tmp_path / "benchmarks"
    for d in ("configs", "traffic", "layer_metrics"):
        (bench / d).mkdir(parents=True)
    base = load_spec()
    cfg = dict(base.cell("q5-10m-saturated").config)
    cfg["name"] = "tiny-q5"
    cfg["data"] = {**cfg["data"], "n_keys": 2000}
    cfg["batch_rows"] = 256
    (bench / "configs" / "tiny-q5.json").write_text(json.dumps(cfg))
    (bench / "traffic" / "bids-slow.json").write_text(json.dumps(
        {"name": "bids-slow", "generator": "bids", "pacing": "scheduled",
         "event_rate": 1000}))
    (bench / "layer_metrics" / "lag_p50_ms.json").write_text(json.dumps(
        {"layer": "source", "unit": "ms", "moves": "window_source_to_sink_p50_ms",
         "reader": "generator", "params": {"percentile": 50}}))
    doc = {
        "command": ["python", "-m", "benchmarks.run"],
        "paths": ["benchmarks"], "run_seconds": 10,
        "configs": [{"name": "tiny-q5", "source": "test", "reduced": [],
                     "file": "benchmarks/configs/tiny-q5.json",
                     "why": "test"}],
        "workloads": [{"name": "tiny.slow", "config": "tiny-q5",
                       "traffic": "bids-slow", "chips": 1, "why": "test"}],
        "end_to_end": [
            {"name": "window_source_to_sink_p50_ms", "unit": "ms",
             "better": "lower", "bound": 0.05, "source": "host_clock"},
            {"name": "setup_s", "unit": "s", "better": "lower",
             "bound": 0.25, "source": "host_clock"}],
        "per_layer": [
            {"name": "lag_p50_ms", "unit": "ms", "better": "lower",
             "source": "program_span", "layer": "source",
             "moves": "window_source_to_sink_p50_ms"}]}
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(doc))
    spec = load_spec(str(path), str(bench))
    cell = spec.cell("tiny.slow")
    assert cell.config["data"]["n_keys"] == 2000
    assert cell.traffic["event_rate"] == 1000
    assert [m["name"] for m in cell.per_layer] == ["lag_p50_ms"]
    params = spec.layer_metric("lag_p50_ms")
    assert hasattr(spec.module("readers", params["reader"]), "read")
    assert hasattr(spec.module("queries", cell.config["query"]["module"]),
                   "build")
    config, traffic = effective_config(cell, rehearse=False)
    sched = build_schedule(
        n_keys=config["data"]["n_keys"], batch_rows=config["batch_rows"],
        prefill_panes=config["prefill_panes"], pane_ms=2000,
        warm_s=config["warm_s"], event_rate=traffic["event_rate"],
        pacing=traffic["pacing"], seconds=10)
    assert sched.phase("prefill").n_batches == 8
    assert sched.phase("timed").n_batches == 39 and sched.phase("timed").paced
    with pytest.raises(KeyError):
        spec.cell("q5-10m-saturated")     # not in this benchmark


def test_rehearse_overlay_leaves_the_files_sizes_alone(spec):
    cell = spec.cell("q5-10m-saturated")
    full, _ = effective_config(cell, rehearse=False)
    tiny, traffic = effective_config(cell, rehearse=True)
    assert full["data"]["n_keys"] == 10_000_000 and "rehearse" not in full
    assert tiny["data"]["n_keys"] < 100_000
    assert tiny["data"]["hot_keys"] == full["data"]["hot_keys"]
    assert traffic["event_rate"] < cell.traffic["event_rate"]
