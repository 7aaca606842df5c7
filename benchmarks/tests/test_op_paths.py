"""The reader of device operations by their JAX name path (PR 25).

data/op_paths_v5e_q5_saturated.json is the `--trace 1` run of
q5-10m-saturated on a TPU v5 lite (PR 25, chip call 1, seed 662607015),
six seconds, reduced: every `XLA Modules` event of the busiest device as
harness/op_paths reads it (name, start, end) and, of its `XLA Ops` line,
the events whose path (the `tf_op` stat of their metadata) is the probe
loop's scatter-min or its table gather. Times are ns from the traced
window's start. The executable of that run came from a compile cache
older than the `probe.claim` scope, so its paths lack the scope."""

import json
import os
import struct
from types import SimpleNamespace

import pytest

from benchmarks.harness import op_paths as P
from benchmarks.harness.spec import load_spec

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "data", "op_paths_v5e_q5_saturated.json"),
              encoding="utf-8") as f:
        return json.load(f)


@pytest.fixture(scope="module")
def params():
    return load_spec().layer_metric("probe_rounds_p50")["params"]


# -- a protobuf written by hand ---------------------------------------------

def _varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _field(number, value):
    if isinstance(value, int):
        return _varint(number << 3) + _varint(value)
    if isinstance(value, float):                      # fixed64
        return _varint(number << 3 | 1) + struct.pack("<d", value)
    if isinstance(value, str):
        value = value.encode()
    return _varint(number << 3 | 2) + _varint(len(value)) + value


def _entry(key, message):
    return _field(1, key) + _field(2, message)


def _event(meta, offset_ps, duration_ps):
    # a per-event stat (a double) stands where the device's offsets stand
    return (_field(1, meta) + _field(2, offset_ps) + _field(3, duration_ps)
            + _field(4, _field(1, 9) + _field(2, 1.0)))


def _line(name, timestamp_ns, events):
    return (_field(1, 7) + _field(2, name) + _field(3, timestamp_ns)
            + b"".join(_field(4, e) for e in events))


def _space():
    claim = "jit(f)/while/body/probe.claim/scatter-min:"
    device = (
        _field(1, 1) + _field(2, "/device:TPU:0")
        + _field(5, _entry(26, _field(1, 26) + _field(2, "tf_op")))
        + _field(5, _entry(27, _field(1, 27) + _field(2, "jit(f)/mul:")))
        + _field(4, _entry(1, _field(1, 1) + _field(2, "jit_f(123)")))
        + _field(4, _entry(2, _field(1, 2) + _field(2, "%fusion.17 = x")
                           + _field(5, _field(1, 3) + _field(3, 5))
                           + _field(5, _field(1, 26) + _field(5, claim))))
        + _field(4, _entry(3, _field(1, 3) + _field(2, "%fusion.2 = y")
                           + _field(5, _field(1, 26) + _field(7, 27))))
        + _field(3, _line("XLA Ops", 1000, [
            _event(3, 6_000_000, 500_000), _event(2, 5_500_000, 250_000),
            _event(2, 300_000_000, 1_000_000)]))
        + _field(3, _line("XLA Modules", 1000, [
            _event(1, 5_000_000, 2_000_000)]))
        + _field(3, _line("Steps", 1000, [_event(1, 0, 1)])))
    host = _field(2, "/host:CPU") + _field(3, _line("XLA Ops", 0, [
        _event(1, 0, 1)]))
    return _field(1, host) + _field(1, device) + _field(2, "an error")


def test_the_wire_format_reader_on_a_file_written_by_hand(tmp_path):
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_space())
    planes = P.read_device_ops(str(path))
    assert list(planes) == ["/device:TPU:0"]        # host planes are not read
    plane = planes["/device:TPU:0"]
    # ns = the line's timestamp + the event's offset in ps, in order of start
    assert plane["modules"] == [("jit_f(123)", 6000.0, 8000.0)]
    assert plane["ops"] == [
        ("jit(f)/while/body/probe.claim/scatter-min:", 6500.0, 6750.0),
        ("jit(f)/mul:", 7000.0, 7500.0),            # a path given by reference
        ("jit(f)/while/body/probe.claim/scatter-min:", 301000.0, 302000.0)]


def test_varints_longer_than_one_byte():
    assert P._varint(_varint(300_000_000), 0) == (300_000_000, 5)
    assert dict(P._fields(memoryview(_field(3, 1 << 40)))) == {3: 1 << 40}


# -- counting a region's runs -------------------------------------------------

def test_region_runs_counts_inside_whole_executions_only(params):
    modules = [("jit_reshape(1)", 0, 5), ("jit_lookup_or_insert(9)", 10, 40),
               ("jit_scatter-add(2)", 41, 45),
               ("jit_lookup_or_insert(9)", 50, 90),
               ("jit_lookup_or_insert(9)", 100, 130)]
    path = "jit(lookup_or_insert)/while/body/probe.claim/scatter-min:"
    old = "jit(lookup_or_insert)/while/body/scatter-min:"
    ops = [(path, 12, 13), (path, 20, 21), ("jit(lookup_or_insert)/and:", 30,
                                            31),
           (old, 55, 56), (old, 60, 61), (path, 70, 71), (path, 95, 96),
           ("jit(fire_fn)/scatter-min:", 57, 58),
           (path, 101, 102)]
    run = lambda lo, hi: P.region_runs(modules, ops, params["module"],
                                       params["region"], lo, hi)
    # the last program of a recording may be cut short: it never counts;
    # the scope may or may not be in the path (an executable from a cache
    # older than it); another program's scatter-min is not the probe's
    assert run(0, 200) == [2, 3]
    assert run(11, 200) == [3]                  # not whole inside the window
    assert run(0, 80) == [2]
    # nor does the first program of the recording
    assert P.region_runs(modules[1:], ops, params["module"],
                         params["region"], 0, 200) == [3]
    assert P.region_runs([], ops, params["module"], params["region"],
                         0, 200) == []


def test_recorded_probe_rounds(recorded, params):
    """Call 1's six seconds hold nine probes; the first and the last are
    cut by the recording's ends. The seven between ran 6-8 rounds of
    72.5 ms: a batch's step time moves in whole rounds."""
    mods, ops = recorded["modules"], recorded["ops"]
    runs = P.region_runs(mods, ops, params["module"], params["region"],
                         recorded["lo"], recorded["hi"])
    assert runs == [7, 7, 8, 6, 6, 7, 7]
    probes = [(s, e) for n, s, e in mods
              if n.startswith("jit_lookup_or_insert(")][1:-1]
    for (start, end), n in zip(probes, runs):
        assert (end - start) / 1e6 / n == pytest.approx(72.5, abs=0.2)


def test_reader_returns_nothing_without_a_trace(monkeypatch):
    spec = load_spec()
    reader = spec.module("readers", "op_region_runs")
    params = spec.layer_metric("probe_rounds_p50")["params"]
    assert reader.read(SimpleNamespace(trace=None), params) is None
    # a rehearsal's trace has no device plane
    run = SimpleNamespace(trace={"planes": [
        {"name": "/host:CPU", "lines": [{"name": "x", "events": [
            ["bench_traced_window", 0.0, 6e9]]}]}]})
    assert reader.read(run, params) is None
    # a device plane in the result, and no file to read the paths from
    run.trace["planes"].append({"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": [["jit_f(1)", 1.0, 2.0]]}]})
    monkeypatch.setattr(P, "BENCH_DIR", "/nonexistent")
    assert reader.read(run, params) is None


def test_the_metric_has_its_file_and_its_cell():
    spec = load_spec()
    names = {m["name"] for m in spec.cell("q5-10m-saturated").per_layer}
    assert "probe_rounds_p50" in names
    assert "probe_rounds_p50" not in {
        m["name"] for m in spec.cell("q5-10m-steady").per_layer}
    body = spec.layer_metric("probe_rounds_p50")
    entry = next(m for m in spec.benchmark["per_layer"]
                 if m["name"] == "probe_rounds_p50")
    assert (body["unit"], body["layer"], body["moves"]) \
        == (entry["unit"], entry["layer"], entry["moves"])
