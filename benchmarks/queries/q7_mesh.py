"""NEXmark Q7 (highest bid) as ONE keyed vertex sharded over a device mesh.

The same query, schema, maps' arithmetic, reference and row check as
``q7.py`` (loaded from there and from ``q7_reference.py``, not copied:
the two maps and the aggregate through ``_Wiring``); ``build`` wires

    source -> map(pack) -> key_by(auction) -> TUMBLE -> mesh_aggregate
           -> map(unpack) -> sink

the keyBy is the on-device all-to-all, the MAX plane is sharded by
key-group range over ``query.n_devices`` devices, every shard selects its
own highest word and the fire merges the shards' candidates;
``query.capacity`` / ``query.device_batch`` are PER DEVICE. The promise
``price_bits + word_shift`` goes to the aggregate as on one chip
(``AggSpec.value_bits``), and ``make_reference`` refuses data that break
it.
"""

from __future__ import annotations

from benchmarks.harness.spec import BENCH_DIR, load_module

_q7 = load_module(BENCH_DIR, "queries", "q7")
SCHEMA_FIELDS = _q7.SCHEMA_FIELDS
TS_COLUMN = _q7.TS_COLUMN
KEY_COLUMN = _q7.KEY_COLUMN
pane_ms = _q7.pane_ms
window_panes = _q7.window_panes
word_bits = _q7.word_bits
make_reference = _q7.make_reference
window_holds_data = _q7.window_holds_data
compare_window = _q7.compare_window

__all__ = ["SCHEMA_FIELDS", "TS_COLUMN", "KEY_COLUMN", "build",
           "operator_class", "operator_capacity", "pane_ms", "window_panes",
           "word_bits", "make_reference", "window_holds_data",
           "compare_window"]


class _Wiring:
    """What ``q7.build`` wires, taken down and not built: its two map
    stages, its window and the aggregates it hands the window operator
    (with their ``value_bits``). ``q7.py`` defines them inside ``build``,
    so this is how they are LOADED from there and not copied: whatever
    the one-chip job packs, keeps and unpacks, the mesh job does."""

    def __init__(self):
        self.maps: list[tuple] = []
        self.assigner = self.aggs = None

    def map(self, fn, name, out_schema):
        self.maps.append((fn, name, out_schema))
        return self

    def key_by(self, column):
        return self

    def window(self, assigner):
        self.assigner = assigner
        return self

    def device_aggregate(self, aggs, **_one_chip_arguments):
        self.aggs = aggs
        return self

    def add_sink(self, sink, name):
        return self


def build(stream, query: dict, sink):
    if query["operator"] != "mesh_aggregate":
        raise ValueError(f"unknown operator {query['operator']!r}")
    q7 = _Wiring()
    _q7.build(q7, dict(query, operator="device_aggregate",
                       defer_overflow=True), None)
    (pack, pack_name, packed), (unpack, unpack_name, winner) = q7.maps
    stream.map(pack, name=pack_name, out_schema=packed) \
        .key_by(KEY_COLUMN) \
        .window(q7.assigner) \
        .mesh_aggregate(
            q7.aggs, n_devices=int(query["n_devices"]),
            capacity=int(query["capacity"]),
            ring_size=int(query["ring_size"]),
            device_batch=int(query["device_batch"]),
            emit_window_bounds=True, emit_topk=int(query["topk"]),
            async_fire=bool(query["async_fire"])) \
        .map(unpack, name=unpack_name, out_schema=winner) \
        .add_sink(sink, "stamp")


def operator_class(query: dict):
    from flink_tpu.runtime.operators.mesh_window import \
        MeshWindowAggOperator
    return MeshWindowAggOperator


def operator_capacity(op, query: dict) -> tuple[int, int]:
    """(slots a shard was configured with, slots a shard ended with): they
    must be equal, or a growth / rebuild ran inside the run. Every shard
    has the same capacity."""
    return int(query["capacity"]), int(op._agg.capacity)
