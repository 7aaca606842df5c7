"""The mesh fire's two metrics (PR 31), data files only:
``mesh_fire_device_ms`` (``readers/trace_module_time.py`` over ``jit_fire``
and ``jit_retire``) and ``mesh_fire_select_passes``
(``readers/device_stats_ratio.py`` over two ``DEVICE_STATS`` counters), on
hand-built module lines and snapshots; and their place in BENCHMARK.json."""

from types import SimpleNamespace

import pytest

from benchmarks.harness import trace as T
from benchmarks.harness.spec import load_spec

CELL = "q5-16m-mesh4-saturated"
NAMES = ("mesh_fire_device_ms", "mesh_fire_select_passes")


@pytest.fixture(scope="module")
def spec():
    return load_spec()


def _plane(modules):
    return {"name": "/device:TPU:0", "lines": [
        {"name": T.MODULE_LINE, "events": [[n, a, d] for n, a, d in modules]}]}


def test_a_fire_is_jit_fire_and_the_retire_behind_it(spec):
    p = spec.layer_metric("mesh_fire_device_ms")["params"]
    ms = 1e6
    # two windows fired by one watermark, steps around and between them;
    # the one-chip fire's programs and the pressure probe are not the
    # mesh fire's
    modules = [("jit_step(7)", 0, 160 * ms),
               ("jit_fire(11)", 160 * ms, 30 * ms),
               ("jit_retire(12)", 190 * ms, 31 * ms),
               ("jit_fire(11)", 221 * ms, 32 * ms),
               ("jit_retire(12)", 253 * ms, 31 * ms),
               ("jit__probe_program(3)", 284 * ms, 1 * ms),
               ("jit_step(7)", 285 * ms, 160 * ms),
               ("jit_fire_fn(5)", 445 * ms, 26 * ms),
               ("jit_reset(6)", 471 * ms, 29 * ms),
               ("jit_fire(11)", 500 * ms, 30 * ms),
               ("jit_step(7)", 530 * ms, 160 * ms)]
    groups = T.module_groups(_plane(modules), 0, 700 * ms, p["modules"],
                             p["anchor"], p.get("exclude", ()))
    # the third fire's retire lies outside what was recorded: its group
    # still counts what ran, as fire_device_ms does for the one-chip cells
    assert groups == pytest.approx([0.061, 0.063, 0.030])
    # cut to whole groups: a fire that begins before the window is out
    assert T.module_groups(_plane(modules), 200 * ms, 700 * ms,
                           p["modules"], p["anchor"]) == pytest.approx(
        [0.063, 0.030])
    assert "roofline" not in p


def test_it_reads_what_fire_device_ms_reads_for_the_one_chip_cells(spec):
    mesh = spec.layer_metric("mesh_fire_device_ms")
    one = spec.layer_metric("fire_device_ms")
    assert mesh["reader"] == one["reader"] == "trace_module_time"
    assert mesh["layer"] == one["layer"] == "seal / fire"
    assert mesh["unit"] == one["unit"] == "ms"
    assert mesh["moves"] == "events_per_s"
    assert one["moves"] == "window_source_to_sink_p50_ms"


def _run(first, last):
    return SimpleNamespace(at_t0={"device_stats": first},
                           at_end={"device_stats": last})


@pytest.mark.parametrize("first,last,expected", [
    # nine timed fires of 15 passes behind the prefill's and the warm
    # phase's
    ({"fire_selects_total": 7, "fire_select_passes_total": 7 * 14},
     {"fire_selects_total": 16, "fire_select_passes_total": 7 * 14 + 135},
     15.0),
    # one of four fires took the sort (a float or a negative rank): 0 passes
    ({"fire_selects_total": 0, "fire_select_passes_total": 0},
     {"fire_selects_total": 4, "fire_select_passes_total": 45}, 11.25),
    # a program from before PR 31, other counters only, no fire in the phase
    ({}, {}, None),
    ({"mesh_steps_total": 1}, {"mesh_steps_total": 9}, None),
    ({"fire_selects_total": 5, "fire_select_passes_total": 75},
     {"fire_selects_total": 5, "fire_select_passes_total": 75}, None),
])
def test_passes_a_fire_is_a_ratio_of_growths(spec, first, last, expected):
    metric = spec.layer_metric("mesh_fire_select_passes")
    reader = spec.module("readers", metric["reader"])
    got = reader.read(_run(first, last), metric["params"])
    assert got == expected if expected is None else \
        got == pytest.approx(expected)


def test_the_counters_the_metric_names_are_the_programs(spec):
    from flink_tpu.metrics.device import DEVICE_STATS

    p = spec.layer_metric("mesh_fire_select_passes")["params"]
    snap = DEVICE_STATS.snapshot()
    assert p["part"] in snap and p["whole"] in snap
    assert "fire_select_sort_total" in snap


def test_benchmark_lists_both_in_the_mesh_cell_and_nowhere_else(spec):
    entries = spec.benchmark["per_layer"][-2:]
    assert [m["name"] for m in entries] == list(NAMES)
    for m, source in zip(entries, ("device_trace", "program_counter")):
        assert m["workloads"] == [CELL]
        assert m["moves"] == "events_per_s" and m["better"] == "lower"
        assert m["source"] == source
        assert spec.layer_metric(m["name"])["layer"] == m["layer"] \
            == "seal / fire"
        assert spec.layer_metric(m["name"])["unit"] == m["unit"]
    reported = [m["name"] for m in spec.cell(CELL).per_layer]
    assert set(NAMES) <= set(reported)
    for other in ("q5-10m-saturated", "q5-10m-steady"):
        assert not set(NAMES) & {m["name"] for m in
                                 spec.cell(other).per_layer}
