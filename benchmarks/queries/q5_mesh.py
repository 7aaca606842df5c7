"""NEXmark Q5 (hot items) as ONE keyed vertex sharded over a device mesh.

The same query, schema, reference and row check as ``q5.py`` (loaded from
there and from ``q5_reference.py``, not copied); ``build`` wires key_by ->
window -> mesh_aggregate -> sink: the keyBy is the on-device all-to-all,
the state is sharded by key-group range over ``query.n_devices`` devices,
and ``query.capacity`` / ``query.device_batch`` are PER DEVICE.
"""

from __future__ import annotations

from benchmarks.harness.spec import BENCH_DIR, load_module

_q5 = load_module(BENCH_DIR, "queries", "q5")
SCHEMA_FIELDS = _q5.SCHEMA_FIELDS
TS_COLUMN = _q5.TS_COLUMN
KEY_COLUMN = _q5.KEY_COLUMN
Q5Reference = _q5.Q5Reference
check_window = _q5.check_window
pane_ms = _q5.pane_ms
window_panes = _q5.window_panes

__all__ = ["SCHEMA_FIELDS", "TS_COLUMN", "KEY_COLUMN", "build",
           "operator_class", "operator_capacity", "Q5Reference",
           "check_window", "pane_ms", "window_panes", "host_index_active"]


def build(stream, query: dict, sink):
    from flink_tpu.runtime.operators.device_window import AggSpec
    from flink_tpu.window import SlidingEventTimeWindows

    if query["operator"] != "mesh_aggregate":
        raise ValueError(f"unknown operator {query['operator']!r}")
    windowed = stream.key_by(KEY_COLUMN).window(SlidingEventTimeWindows.of(
        int(query["window_size_ms"]), int(query["window_slide_ms"])))
    aggs = [AggSpec("count", out_name="bids"),
            AggSpec("sum", "price", out_name="revenue")]
    windowed.mesh_aggregate(
        aggs, n_devices=int(query["n_devices"]),
        capacity=int(query["capacity"]), ring_size=int(query["ring_size"]),
        device_batch=int(query["device_batch"]), emit_window_bounds=True,
        emit_topk=int(query["topk"]),
        async_fire=bool(query["async_fire"])).add_sink(sink, "stamp")


def operator_class(query: dict):
    from flink_tpu.runtime.operators.mesh_window import \
        MeshWindowAggOperator
    return MeshWindowAggOperator


def operator_capacity(op, query: dict) -> tuple[int, int]:
    """(slots a shard was configured with, slots a shard ended with): they
    must be equal, or a growth / rebuild ran inside the run. Every shard
    has the same capacity."""
    return int(query["capacity"]), int(op._agg.capacity)


def host_index_active(op) -> bool:
    return False          # the mesh vertex has no host index to fall to
