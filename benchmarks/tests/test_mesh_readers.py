"""The readers the four-chip cell brought (PR 27), on a recording from the
chip and on hand-built inputs.

data/op_regions_v5e_q5_mesh4.json is the `--trace 1` run of
q5-16m-mesh4-saturated on four TPU v5 lite chips (PR 27, chip call 2,
seed 3735928559), reduced: the busiest device's `XLA Modules` events
around four consecutive executions of `jit_step` and every `XLA Ops`
event that starts inside those four, each with the name path JAX gave it
(the `tf_op` stat of its metadata, as harness/op_paths reads it). Times
are ns from the start of the kept window."""

import json
import os
import re
from types import SimpleNamespace

import numpy as np
import pytest

from benchmarks.harness.exchange_bytes import exchange_step_bytes, \
    off_chip_rows
from benchmarks.harness.spec import load_spec

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "q5-16m-mesh4-saturated"


@pytest.fixture(scope="module")
def spec():
    return load_spec()


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "data", "op_regions_v5e_q5_mesh4.json"),
              encoding="utf-8") as f:
        data = json.load(f)
    data["ops"] = [(data["paths"][i], a, b) for i, a, b in data["ops"]]
    data["modules"] = [tuple(m) for m in data["modules"]]
    return data


def _region(spec, metric):
    return spec.layer_metric(metric)["params"]


def _time(spec, recorded, metric):
    reader = spec.module("readers", "op_region_time")
    p = _region(spec, metric)
    lo, hi = recorded["window"]
    return reader.region_time(recorded["modules"], recorded["ops"],
                              p["module"], p["region"], lo, hi)


# -- op_region_time on the recording ------------------------------------------

def test_the_recording_holds_four_whole_steps(spec, recorded):
    _region_s, module_s, n = _time(spec, recorded,
                                   "exchange_collective_share")
    assert n == 4
    assert 0.155 < module_s / n < 0.170          # 161 ms a step


def test_collectives_are_found_by_scope_and_primitive(spec, recorded):
    pattern = re.compile(_region(spec, "exchange_collective_share")
                         ["region"])
    found = [p for p in recorded["paths"] if pattern.search(p)]
    assert sorted(p.rsplit("/", 2)[-2] + "/" + p.rsplit("/", 1)[-1]
                  .rstrip(":") for p in found) == [
        "mesh.exchange/all_to_all", "mesh.sync/pmax", "mesh.sync/psum"]
    only_a2a = re.compile(_region(spec, "exchange_roofline_share")["region"])
    assert [p for p in recorded["paths"] if only_a2a.search(p)] == [
        p for p in found if "all_to_all" in p]
    # at least seven all-to-alls a step (three int64 columns as two
    # halves each, and the valid mask), the same number in every step
    starts = [a for p, a, _b in recorded["ops"] if only_a2a.search(p)]
    assert len(starts) % 4 == 0 and len(starts) // 4 >= 7


def test_shares_of_the_step_on_the_recording(spec, recorded):
    coll_s, module_s, n = _time(spec, recorded, "exchange_collective_share")
    a2a_s, _m, _n = _time(spec, recorded, "exchange_roofline_share")
    work_s, _m, _n = _time(spec, recorded, "mesh_probe_fold_share")
    assert 0.01 < coll_s / module_s < 0.05       # mostly the wait in pmax
    assert a2a_s < coll_s and a2a_s / n < 200e-6  # under 0.2 ms a step
    assert 0.18 < work_s / module_s < 0.30       # probe 17 + fold 20 ms


def test_region_time_cuts_to_whole_executions_and_unions_overlaps(spec):
    reader = spec.module("readers", "op_region_time")
    s = 1e9
    modules = [("jit_other(1)", 0, 1 * s), ("jit_step(7)", 1 * s, 3 * s),
               ("jit_fire(2)", 3 * s, 4 * s), ("jit_step(7)", 4 * s, 6 * s),
               ("jit_step(7)", 7 * s, 9 * s), ("jit_other(1)", 9 * s, 10 * s)]
    ops = [("a/mesh.x/while:", 1.2 * s, 2.2 * s),      # a container and
           ("a/mesh.x/while/body/add:", 1.4 * s, 1.6 * s),   # its child
           ("a/mesh.x/mul:", 2.5 * s, 3.5 * s),   # runs past its program
           ("a/mesh.x/mul:", 3.2 * s, 3.4 * s),   # inside jit_fire
           ("a/other/mul:", 4.1 * s, 4.2 * s),
           ("a/mesh.x/mul:", 5.0 * s, 5.5 * s),
           ("a/mesh.x/mul:", 7.5 * s, 8.0 * s)]   # third step: outside hi
    got = reader.region_time(modules, ops, r"^jit_step\(", r"/mesh\.x/",
                             0.5 * s, 6.5 * s)
    assert got == (pytest.approx(1.0 + 0.5 + 0.5), 4.0, 2)
    # the first and the last program of a recording may be cut short
    assert reader.region_time(modules, ops, r"^jit_step\(", r"/mesh\.x/",
                              0, 10 * s)[2] == 3
    assert reader.region_time(modules[1:], ops, r"^jit_step\(",
                              r"/mesh\.x/", 0, 10 * s)[2] == 2
    assert reader.region_time(modules[1:5], ops, r"^jit_step\(",
                              r"/mesh\.x/", 0, 10 * s)[2] == 1


# -- exchange bytes -------------------------------------------------------------

def test_off_chip_rows_counts_both_ends_of_the_link():
    # 3 devices x 4 rows; row i of slice d starts on device d
    dest = np.array([0, 0, 1, 2,   1, 1, 1, 1,   0, 0, 0, 2])
    sent, received = off_chip_rows(dest, 3, 4)
    assert sent.tolist() == [2, 0, 3]
    assert received.tolist() == [3, 1, 1]
    assert sent.sum() == received.sum()
    assert exchange_step_bytes(49_920, 24, 1) == 1_248_000


def test_the_roofline_metric_states_its_bytes_and_its_peak(spec):
    p = _region(spec, "exchange_roofline_share")
    assert p["roofline"] == {"peak": "ici_bits_per_s", "row_bytes": 24,
                             "flag_bytes": 1}
    what = spec.layer_metric("exchange_roofline_share")["what"]
    assert "1,600 Gbit/s" in what and "four ICI ports" in what
    # 1.25 MB at 200 GB/s is 6 us: under the 80 us the all-to-alls of the
    # recorded step take, so the share stays far under 100
    from benchmarks.harness.device import peak
    least = 8 * exchange_step_bytes(49_920, 24, 1) / peak(
        "TPU v5 lite", "ici_bits_per_s")
    assert 6e-6 < least < 7e-6


# -- counters and ring ---------------------------------------------------------

def _run(first, last):
    return SimpleNamespace(at_t0={"device_stats": first},
                           at_end={"device_stats": last})


def test_rounds_per_step_is_a_ratio_of_growths(spec):
    reader = spec.module("readers", "device_stats_ratio")
    p = _region(spec, "exchange_rounds_per_step")
    k_r, k_s = "mesh_exchange_rounds_total", "mesh_steps_total"
    assert reader.read(_run({k_r: 70, k_s: 68}, {k_r: 175, k_s: 138}),
                       p) == 1.5
    assert reader.read(_run({k_r: 68, k_s: 68}, {k_r: 138, k_s: 138}),
                       p) == 1.0
    # a program without the counters (the parent), or no step in the
    # timed phase: nothing to read, and no error
    assert reader.read(_run({}, {}), p) is None
    assert reader.read(_run({"h2d_bytes": 1}, {"h2d_bytes": 2}), p) is None
    assert reader.read(_run({k_r: 5, k_s: 5}, {k_r: 5, k_s: 5}), p) is None


def test_upload_ms_reads_the_timed_batches_spans(spec, monkeypatch):
    from benchmarks.harness import stage_trace as S

    reader = spec.module("readers", "stage_ring_span")
    p = _region(spec, "mesh_upload_ms")

    def span(name, seq, ms, task="v3#0", scope="window"):
        return SimpleNamespace(scope=scope, name=name, duration_ns=ms * 1e6,
                               attributes={"seq": seq, "task": task})

    spans = [span("Upload", seq, 10.0 if seq <= 4 else float(seq))
             for seq in range(1, 10)]
    spans += [span("IngestDispatch", seq, 99.0) for seq in range(1, 10)]
    spans += [span("Upload", seq, 500.0, task="v9#0") for seq in range(5, 10)]
    run = SimpleNamespace(
        window_task=SimpleNamespace(task_id="v3#0"),
        schedule=SimpleNamespace(phase=lambda name: SimpleNamespace(
            first_batch=4, end_batch=9)))
    monkeypatch.setattr(S, "ring_spans", lambda _run: spans)
    assert reader.read(run, p) == 7.0            # median of 5..9
    # a timed batch without its span, or no ring at all: nothing
    holed = [x for x in spans if not (
        x.name == "Upload" and x.attributes == {"seq": 7, "task": "v3#0"})]
    monkeypatch.setattr(S, "ring_spans", lambda _run: holed)
    assert reader.read(run, p) is None
    monkeypatch.setattr(S, "ring_spans", lambda _run: None)
    assert reader.read(run, p) is None
