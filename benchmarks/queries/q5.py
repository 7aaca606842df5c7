"""NEXmark Q5 (hot items) built from the program's public entry points.

    SELECT auction, COUNT(*) AS bids, SUM(price) AS revenue
    FROM bid GROUP BY auction, HOP(ts, slide, size)   -- top-k by bids

``build`` wires key_by -> window -> device_aggregate -> sink onto a
stream; the configuration file's ``query`` block carries every
argument. The plain reference lives beside it (q5_reference.py).
"""

from __future__ import annotations

import numpy as np

from benchmarks.harness.spec import BENCH_DIR, load_module

_reference = load_module(BENCH_DIR, "queries", "q5_reference")
Q5Reference = _reference.Q5Reference
check_window = _reference.check_window

__all__ = ["SCHEMA_FIELDS", "TS_COLUMN", "KEY_COLUMN", "build",
           "operator_class", "operator_capacity", "Q5Reference",
           "check_window", "pane_ms", "window_panes", "host_index_active"]

#: the bid as Q5 reads it: 4 x int64 = 32 B a row (channel / url / extra
#: strings are projected away at the source)
SCHEMA_FIELDS = [("auction", np.int64), ("bidder", np.int64),
                 ("price", np.int64), ("ts", np.int64)]
TS_COLUMN = "ts"
KEY_COLUMN = "auction"


def pane_ms(query: dict) -> int:
    return int(query["window_slide_ms"])


def window_panes(query: dict) -> int:
    size, slide = int(query["window_size_ms"]), int(query["window_slide_ms"])
    if size % slide:
        raise ValueError("HOP size must be a multiple of its slide")
    return size // slide


def build(stream, query: dict, sink):
    from flink_tpu.runtime.operators.device_window import AggSpec
    from flink_tpu.window import SlidingEventTimeWindows

    windowed = stream.key_by(KEY_COLUMN).window(SlidingEventTimeWindows.of(
        int(query["window_size_ms"]), int(query["window_slide_ms"])))
    aggs = [AggSpec("count", out_name="bids",
                    value_bits=int(query["count_value_bits"])),
            AggSpec("sum", "price", out_name="revenue")]
    if query["operator"] != "device_aggregate":
        raise ValueError(f"unknown operator {query['operator']!r}")
    windowed.device_aggregate(
        aggs, capacity=int(query["capacity"]),
        ring_size=int(query["ring_size"]), emit_window_bounds=True,
        emit_topk=int(query["topk"]),
        defer_overflow=bool(query["defer_overflow"]),
        async_fire=bool(query["async_fire"])).add_sink(sink, "stamp")


def operator_class(query: dict):
    from flink_tpu.runtime.operators.device_window import \
        DeviceWindowAggOperator
    return DeviceWindowAggOperator


def operator_capacity(op, query: dict) -> tuple[int, int]:
    """(capacity configured, capacity the operator ended with): they must
    be equal, or a growth / rebuild ran inside the run."""
    return int(query["capacity"]), int(op._backend.capacity)


def host_index_active(op) -> bool:
    return bool(getattr(getattr(op, "_backend", None),
                        "host_index_active", False))
