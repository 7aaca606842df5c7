"""The trace reduction on a small trace recorded on the chip.

data/trace_v5e_q5_steady.json is 2.4 s of the `--trace 1` run of
q5-10m-steady on a TPU v5 lite (PR 24, chip call 3, seed 67867967): the
device plane's `XLA Modules` and `XLA Ops` lines and the benchmark's host
spans, as harness/trace.load_xplane writes them, cut around one fire; op
names shortened to their labels and ops under 2 us dropped to keep the
file small. Start times are ns from the original window's start."""

import json
import os

import pytest

from benchmarks.harness import trace as T

HERE = os.path.dirname(os.path.abspath(__file__))
HOST = ("source_generate", "sink_invoke")


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "data", "trace_v5e_q5_steady.json"),
              encoding="utf-8") as f:
        return json.load(f)


def test_union_of_intervals():
    assert T.union_s([(0, 1e9), (0.5e9, 2e9), (3e9, 4e9)]) == 3.0
    assert T.union_s([]) == 0.0
    assert T.union_s([(5e9, 6e9), (1e9, 2e9), (1.2e9, 1.4e9)]) == 2.0


def test_clip_cuts_events_to_the_window():
    ev = [["a", 0.0, 10.0], ["b", 8.0, 10.0], ["c", 30.0, 5.0]]
    assert T.clip(ev, 5.0, 12.0) == [("a", 5.0, 10.0), ("b", 8.0, 12.0)]


def test_labels():
    hlo = ("%fusion.17 = (u32[16777216]{0:T(1024)}, u32[16777216]) "
           "fusion(u32[16777216]{0:T(1024)} %copy-done), kind=kCustom")
    assert T.op_label(hlo) == "fusion.17"
    assert T.op_label("fusion.17") == "fusion.17"
    assert T.module_label("jit_fire_fn(14313520015654782885)") \
        == "jit_fire_fn"


def test_recorded_trace_window_and_busy(recorded):
    lo, hi = T.traced_window(recorded)
    assert (hi - lo) / 1e9 == pytest.approx(2.4)
    planes = T.device_planes(recorded)
    assert [p["name"] for p in planes] == ["/device:TPU:0"]
    busy = T.busy_s(planes[0], lo, hi)
    # 3 ingest steps of ~0.6-0.74 s less what the cut dropped, one fire
    assert busy == pytest.approx(1.782, abs=0.002)
    assert 0 < busy < (hi - lo) / 1e9


def test_recorded_trace_step_and_fire_groups(recorded):
    lo, hi = T.traced_window(recorded)
    plane = T.device_planes(recorded)[0]
    step = T.module_groups(
        plane, lo, hi, [".*"], r"^jit_(lookup_or_insert|step)\(",
        [r"^jit_fire", r"^jit_reset\("])
    assert step == pytest.approx([0.7374], abs=1e-3)
    fire = T.module_groups(
        plane, lo, hi, [r"^jit_fire(_fn)?\(", r"^jit_reset\("],
        r"^jit_fire(_fn)?\(")
    # fire_fn 26 ms + reset 29 ms
    assert fire == pytest.approx([0.0557], abs=1e-3)
    # the fire's programs are not counted into the step around them
    with_fire = T.module_groups(plane, lo, hi, [".*"],
                                r"^jit_(lookup_or_insert|step)\(")
    assert with_fire[0] == pytest.approx(step[0] + fire[0], abs=2e-3)


def test_recorded_trace_breakdown(recorded):
    lo, hi = T.traced_window(recorded)
    plane = T.device_planes(recorded)[0]
    ops = T.top_ops(plane, lo, hi, 10)
    assert len(ops) == 10
    assert ops[0][0] == "jit_lookup_or_insert/fusion.17"
    assert ops == sorted(ops, key=lambda kv: -kv[1])
    assert not any(name.split("/")[1].startswith("while") for name, _ in ops)
    gaps = dict(T.idle_gaps(recorded, plane, lo, hi, HOST))
    idle = (hi - lo) / 1e9 - T.busy_s(plane, lo, hi)
    assert sum(gaps.values()) == pytest.approx(idle, abs=1e-6)
    assert gaps["host_unattributed"] > gaps["source_generate"] > 0


def test_busy_time_is_the_union_of_overlapping_ops():
    plane = {"name": "/device:TPU:1", "lines": [
        {"name": T.OPS_LINE, "events": [
            ["%fusion.1 = s64[4] fusion(...)", 0.0, 2e8],
            ["%fusion.2 = s32[] fusion(...)", 1e8, 2e8],
            ["fusion.9", 5e8, 1e8]]}]}
    assert T.busy_s(plane, 0.0, 1e9) == pytest.approx(0.4)


def test_a_trace_without_the_window_annotation_is_an_error():
    with pytest.raises(ValueError):
        T.traced_window({"planes": []})
