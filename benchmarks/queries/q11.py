"""NEXmark Q11 (user sessions) built from the program's public entry
points.

    SELECT bidder, COUNT(*) AS bid_count,                   -- q11.sql
           SESSION_START(ts, gap), SESSION_END(ts, gap)
    FROM bid GROUP BY bidder, SESSION(ts, gap)

    source -> key_by(bidder) -> window(EventTimeSessionWindows)
           -> device_aggregate([count]) -> map(report pane) -> sink

The configuration's ``query`` block carries every argument. The harness
(harness/cell.py) groups a job's rows by ``window_end`` and is written
for fixed panes; a session has no pane, so the job's last map gives each
session row a REPORT PANE: ``window_end`` is the end of the
``report_pane_ms`` bucket that holds the session's end, ``window_start``
one bucket earlier. The bucket is a function of the session alone, so it
does not depend on when a watermark came or a fire ran; the session's own
bounds travel on as ``session_start`` / ``session_end``. The plain
reference lives beside it (q11_reference.py) and buckets its sessions
the same way, by its own arithmetic; ``make_reference`` /
``window_holds_data`` / ``compare_window`` are what the harness asks for
(harness/spec.py), thin adapters over it.
"""

from __future__ import annotations

import numpy as np

from benchmarks.harness.spec import BENCH_DIR, load_module

_reference = load_module(BENCH_DIR, "queries", "q11_reference")
Q11Reference = _reference.Q11Reference
check_window = _reference.check_window

__all__ = ["SCHEMA_FIELDS", "TS_COLUMN", "KEY_COLUMN", "build",
           "operator_class", "operator_capacity", "pane_ms", "window_panes",
           "make_reference", "window_holds_data", "compare_window"]

#: the bid as the source emits it: 4 x int64 = 32 B a row (channel / url /
#: extra strings are projected away at the source)
SCHEMA_FIELDS = [("auction", np.int64), ("bidder", np.int64),
                 ("price", np.int64), ("ts", np.int64)]
TS_COLUMN = "ts"
KEY_COLUMN = "bidder"


def pane_ms(query: dict) -> int:
    return int(query["report_pane_ms"])


def window_panes(query: dict) -> int:
    return 1                        # a report pane is one bucket


def build(stream, query: dict, sink):
    from flink_tpu.core.functions import MapFunction
    from flink_tpu.core.records import RecordBatch, Schema
    from flink_tpu.runtime.operators.device_window import AggSpec
    from flink_tpu.window import EventTimeSessionWindows

    pane = pane_ms(query)
    reported = Schema([(KEY_COLUMN, np.int64), ("session_start", np.int64),
                       ("session_end", np.int64), ("bid_count", np.int64),
                       ("window_start", np.int64),
                       ("window_end", np.int64)])

    class ReportPane(MapFunction):
        def map_batch(self, batch):
            end = np.asarray(batch.column("window_end"))
            pane_end = end // pane * pane + pane
            return RecordBatch(
                reported, {KEY_COLUMN: batch.column(KEY_COLUMN),
                           "session_start": batch.column("window_start"),
                           "session_end": end,
                           "bid_count": batch.column("bid_count"),
                           "window_start": pane_end - pane,
                           "window_end": pane_end},
                batch.timestamps)

    if query["operator"] != "device_aggregate":
        raise ValueError(f"unknown operator {query['operator']!r}")
    stream.key_by(KEY_COLUMN) \
        .window(EventTimeSessionWindows.with_gap(int(query["gap_ms"]))) \
        .device_aggregate(
            [AggSpec("count", out_name="bid_count")],
            capacity=int(query["capacity"]), ring_size=int(query["lanes"]),
            emit_window_bounds=True,
            async_fire=bool(query["async_fire"])) \
        .map(ReportPane(), name="ReportPane", out_schema=reported) \
        .add_sink(sink, "stamp")


def operator_class(query: dict):
    from flink_tpu.runtime.operators.device_session import \
        DeviceSessionWindowOperator
    return DeviceSessionWindowOperator


def operator_capacity(op, query: dict) -> tuple[int, int]:
    """(capacity configured, capacity the operator ended with): they must
    be equal, or a growth / rebuild ran inside the run."""
    return int(query["capacity"]), int(op._backend.capacity)


class _Reference:
    """``Q11Reference`` behind the harness's ``feed(columns, ts)``; a
    window is a report pane's sessions, four columns."""

    def __init__(self, query: dict, data: dict, on_window):
        self._ref = Q11Reference(int(data["id_space"]),
                                 int(query["gap_ms"]), pane_ms(query),
                                 on_window)
        self.pane_events = self._ref.pane_events
        self.close = self._ref.close

    def feed(self, columns: dict, ts: np.ndarray) -> None:
        self._ref.feed(columns[KEY_COLUMN], ts)


make_reference = _Reference


def window_holds_data(window) -> bool:
    return len(window[0]) > 0


def compare_window(rows: dict, window, query: dict):
    return check_window(rows[KEY_COLUMN], rows["session_start"],
                        rows["session_end"], rows["bid_count"], window)
