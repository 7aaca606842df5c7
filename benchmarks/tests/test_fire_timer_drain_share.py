"""``fire_timer_drain_share`` (PR 29): the share of the timed phase's
fired windows whose rows left on the window task's processing-time turn,
read by ``readers/device_stats_share.py`` from two ``DEVICE_STATS``
counters; on hand-built snapshots."""

from types import SimpleNamespace

import pytest

from benchmarks.harness.spec import load_module, load_spec

NAME = "fire_timer_drain_share"


@pytest.fixture(scope="module")
def reader():
    spec = load_spec()
    metric = spec.layer_metric(NAME)
    return load_module(spec.bench_dir, "readers", metric["reader"]), \
        metric["params"]


def _run(first, last):
    return SimpleNamespace(at_t0={"device_stats": first},
                           at_end={"device_stats": last})


@pytest.mark.parametrize("timer,expected", [(24, 100.0), (23, 100 * 23 / 24),
                                            (0, 0.0)])
def test_share_of_the_timed_windows_only(reader, timer, expected):
    module, params = reader
    # the prefill's and the warm phase's windows are not the timed phase's
    first = {"fires_drained_total": 9, "fires_drained_timer_total": 7}
    last = {"fires_drained_total": 9 + 24,
            "fires_drained_timer_total": 7 + timer}
    assert module.read(_run(first, last), params) == pytest.approx(expected)


@pytest.mark.parametrize("first,last", [
    ({}, {}),                                   # a program from before PR 29
    ({"fire_unready_polls_total": 3}, {"fire_unready_polls_total": 9}),
    ({"fires_drained_total": 5, "fires_drained_timer_total": 5},
     {"fires_drained_total": 5, "fires_drained_timer_total": 5}),  # no fire
])
def test_nothing_to_read_is_none_not_an_error(reader, first, last):
    module, params = reader
    assert module.read(_run(first, last), params) is None


def test_benchmark_lists_the_metric_in_the_steady_cell():
    spec = load_spec()
    entry = next(m for m in spec.benchmark["per_layer"] if m["name"] == NAME)
    assert spec.benchmark["per_layer"][-1] is entry
    assert entry["workloads"] == ["q5-10m-steady"]
    assert entry["moves"] == "window_source_to_sink_p50_ms"
    assert entry["source"] == "program_counter"
    assert spec.layer_metric(NAME)["layer"] == entry["layer"] == "drain"
    assert NAME in [m["name"] for m in spec.cell("q5-10m-steady").per_layer]
    for other in ("q5-10m-saturated", "q5-16m-mesh4-saturated"):
        assert NAME not in [m["name"] for m in spec.cell(other).per_layer]
