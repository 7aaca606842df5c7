"""NEXmark Q7 (highest bid) built from the program's public entry points.

    SELECT auction, price, bidder FROM bid                 -- as q7.sql,
    WHERE price = MAX(price) OVER TUMBLE(ts, size)         -- one row

as this repo runs it (configs/nexmark-q7-10m.json, ``assumed``): the join
back to the bid is folded into the aggregate. A map packs the word
``price << word_shift | bidder``, the window keeps its MAX per auction,
the fire emits the top-1 across auctions, and a second map unpacks the
winner:

    source -> map(pack) -> key_by(auction) -> TUMBLE -> device_aggregate
           -> map(unpack) -> sink

The configuration's ``query`` block carries every argument. The word's
width is never typed: ``build`` promises ``price_bits + word_shift`` bits
to the aggregate, and ``make_reference``, which also sees the ``data``
block, refuses to run unless the data keep that promise. The plain
reference lives beside it (q7_reference.py); ``make_reference`` /
``window_holds_data`` / ``compare_window`` are what the harness asks for
(harness/spec.py), thin adapters over it.

``query.word_dtype`` is the control's hook (benchmarks/control_q7.py):
the packing map's output column declared narrower, which makes the
program keep its MAX in fewer bits. No configuration file sets it.
"""

from __future__ import annotations

import numpy as np

from benchmarks.harness.spec import BENCH_DIR, load_module

_reference = load_module(BENCH_DIR, "queries", "q7_reference")
Q7Reference = _reference.Q7Reference
check_window = _reference.check_window

__all__ = ["SCHEMA_FIELDS", "TS_COLUMN", "KEY_COLUMN", "build",
           "operator_class", "operator_capacity", "pane_ms", "window_panes",
           "word_bits", "make_reference", "window_holds_data",
           "compare_window"]

#: the bid as the source emits it: 4 x int64 = 32 B a row (channel / url /
#: extra strings are projected away at the source)
SCHEMA_FIELDS = [("auction", np.int64), ("bidder", np.int64),
                 ("price", np.int64), ("ts", np.int64)]
TS_COLUMN = "ts"
KEY_COLUMN = "auction"


def pane_ms(query: dict) -> int:
    return int(query["window_size_ms"])


def window_panes(query: dict) -> int:
    return 1                                    # tumbling


def word_bits(query: dict) -> int:
    """Bits the packed word has: the price's above the bidder's."""
    return int(query["price_bits"]) + int(query["word_shift"])


def build(stream, query: dict, sink):
    from flink_tpu.core.functions import MapFunction
    from flink_tpu.core.records import RecordBatch, Schema
    from flink_tpu.runtime.operators.device_window import AggSpec
    from flink_tpu.window import TumblingEventTimeWindows

    shift = int(query["word_shift"])
    word_dtype = np.dtype(query.get("word_dtype", "int64"))
    packed = Schema([(KEY_COLUMN, np.int64), ("word", word_dtype),
                     (TS_COLUMN, np.int64)])
    winner = Schema([(KEY_COLUMN, np.int64), ("window_start", np.int64),
                     ("window_end", np.int64), ("price", np.int64),
                     ("bidder", np.int64)])

    class Pack(MapFunction):
        def map_batch(self, batch):
            word = (np.asarray(batch.column("price")) << shift) \
                | np.asarray(batch.column("bidder"))
            return RecordBatch(
                packed, {KEY_COLUMN: batch.column(KEY_COLUMN),
                         "word": word.astype(word_dtype, copy=False),
                         TS_COLUMN: batch.column(TS_COLUMN)},
                batch.timestamps)

    class Unpack(MapFunction):
        def map_batch(self, batch):
            best = np.asarray(batch.column("best")).astype(np.int64)
            return RecordBatch(
                winner, {KEY_COLUMN: batch.column(KEY_COLUMN),
                         "window_start": batch.column("window_start"),
                         "window_end": batch.column("window_end"),
                         "price": best >> shift,
                         "bidder": best & ((1 << shift) - 1)},
                batch.timestamps)

    if query["operator"] != "device_aggregate":
        raise ValueError(f"unknown operator {query['operator']!r}")
    stream.map(Pack(), name="PackBid", out_schema=packed) \
        .key_by(KEY_COLUMN) \
        .window(TumblingEventTimeWindows.of(pane_ms(query))) \
        .device_aggregate(
            [AggSpec("max", "word", out_name="best",
                     value_bits=word_bits(query))],
            capacity=int(query["capacity"]),
            ring_size=int(query["ring_size"]), emit_window_bounds=True,
            emit_topk=int(query["topk"]),
            defer_overflow=bool(query["defer_overflow"]),
            async_fire=bool(query["async_fire"])) \
        .map(Unpack(), name="UnpackWinner", out_schema=winner) \
        .add_sink(sink, "stamp")


def operator_class(query: dict):
    from flink_tpu.runtime.operators.device_window import \
        DeviceWindowAggOperator
    return DeviceWindowAggOperator


def operator_capacity(op, query: dict) -> tuple[int, int]:
    """(capacity configured, capacity the operator ended with): they must
    be equal, or a growth / rebuild ran inside the run."""
    return int(query["capacity"]), int(op._backend.capacity)


class _Reference:
    """``Q7Reference`` behind the harness's ``feed(columns, ts)``; a
    window is the best word per auction. Refuses data that break the
    promise ``build`` makes to the aggregate."""

    def __init__(self, query: dict, data: dict, on_window):
        if int(data["price_max"]).bit_length() != int(query["price_bits"]):
            raise ValueError(
                f"query.price_bits {query['price_bits']} is not the bit "
                f"length of data.price_max {data['price_max']} "
                f"({int(data['price_max']).bit_length()}): the job would "
                "promise its select a width the prices do not keep")
        if int(data["n_bidders"]) > 1 << int(query["word_shift"]):
            raise ValueError(
                f"data.n_bidders {data['n_bidders']} does not fit the "
                f"{query['word_shift']} bits under the price")
        if int(query["topk"]) != 1:
            raise ValueError("Q7 emits the highest bid: query.topk is 1")
        self._ref = Q7Reference(int(data["n_keys"]), pane_ms(query),
                                int(query["word_shift"]), on_window)
        self.pane_events = self._ref.pane_events
        self.close = self._ref.close

    def feed(self, columns: dict, ts: np.ndarray) -> None:
        self._ref.feed(columns[KEY_COLUMN], columns["price"],
                       columns["bidder"], ts)


make_reference = _Reference


def window_holds_data(best) -> bool:
    return bool(best.any())


def compare_window(rows: dict, best, query: dict):
    return check_window(rows[KEY_COLUMN], rows["price"], rows["bidder"],
                        best, int(query["word_shift"]))
