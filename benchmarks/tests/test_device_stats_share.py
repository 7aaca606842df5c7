"""The reader of a share of two ``DEVICE_STATS`` counters over the timed
phase (``probe_tail_share``, PR 26), on hand-built snapshots."""

from types import SimpleNamespace

import pytest

from benchmarks.harness.spec import load_module, load_spec


@pytest.fixture(scope="module")
def reader():
    spec = load_spec()
    metric = spec.layer_metric("probe_tail_share")
    return load_module(spec.bench_dir, "readers", metric["reader"]), \
        metric["params"]


def _run(first, last):
    return SimpleNamespace(at_t0={"device_stats": first},
                           at_end={"device_stats": last})


def test_share_of_the_timed_phase_only(reader):
    module, params = reader
    # the prefill's 10M new keys are all tail; the timed phase's are not
    first = {"probe_rows_total": 11_272_192, "probe_tail_rows_total":
             10_015_000, "probe_wide_batches_total": 39}
    last = {"probe_rows_total": 11_272_192 + 50 * 262_144,
            "probe_tail_rows_total": 10_015_000 + 50 * 1_900,
            "probe_wide_batches_total": 39}
    assert module.read(_run(first, last), params) == pytest.approx(
        100 * 1_900 / 262_144)


@pytest.mark.parametrize("first,last", [
    ({}, {}),                                   # a program without them
    ({"h2d_bytes": 1}, {"h2d_bytes": 2}),
    ({"probe_rows_total": 5, "probe_tail_rows_total": 1},
     {"probe_rows_total": 5, "probe_tail_rows_total": 1}),   # nothing probed
])
def test_nothing_to_read_is_none_not_an_error(reader, first, last):
    module, params = reader
    assert module.read(_run(first, last), params) is None
    assert module.read(SimpleNamespace(at_t0={}, at_end={}), params) is None


def test_benchmark_lists_the_metric_in_the_saturated_cell():
    spec = load_spec()
    entry = next(m for m in spec.benchmark["per_layer"]
                 if m["name"] == "probe_tail_share")
    assert entry["workloads"] == ["q5-10m-saturated"]
    assert entry["moves"] == "events_per_s"
    assert spec.layer_metric("probe_tail_share")["layer"] == entry["layer"]
    cell = spec.cell("q5-10m-saturated")
    assert "probe_tail_share" in [m["name"] for m in cell.per_layer]
