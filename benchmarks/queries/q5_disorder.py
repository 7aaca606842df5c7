"""NEXmark Q5 over a bid stream that arrives out of event-time order,
under a bounded-out-of-orderness watermark (configuration
``nexmark-q5-10m-disorder``): ``q5.py``'s job behind two operators of
the public API, and a reference that takes events in arrival order.

The harness stamps the time column by a row's place in the stream, so
the stamped time is the row's ARRIVAL time. The job's first operator, a
``map``, turns it into the event's own time, ``ts - lag`` (the lag is a
hash of the row: ``q5_disorder_reference.lag_ms``, the one definition
the reference uses too); it stands for the source that would have sent
the event late. Behind it
``assign_timestamps_and_watermarks(for_bounded_out_of_orderness(
watermark_holdback_ms))`` reads event time from that column and holds
the watermark back, as nexmark-flink's DDL does (``WATERMARK FOR
dateTime AS dateTime - INTERVAL '4' SECOND``); then ``q5.build`` as it
is. Both operators chain to the source, on the source's thread.

``query.delayed_share`` / ``query.delay_max_ms`` repeat
``data.delayed_share`` / ``data.delay_max_ms``: ``build`` is handed the
``query`` block alone and the reference defines the data from ``data``;
``make_reference`` refuses a configuration in which they differ.
"""

from __future__ import annotations

from benchmarks.harness.spec import BENCH_DIR, load_module

_q5 = load_module(BENCH_DIR, "queries", "q5")
_reference = load_module(BENCH_DIR, "queries", "q5_disorder_reference")
# the schema, the pane arithmetic, the operator and the comparison are
# q5.py's; build and make_reference below take their places
globals().update({name: getattr(_q5, name) for name in _q5.__all__})

__all__ = list(_q5.__all__)

_DISORDER = ("delayed_share", "delay_max_ms")


def build(stream, query: dict, sink):
    from flink_tpu.core import MapFunction, WatermarkStrategy

    share, delay_max = (query[k] for k in _DISORDER)

    class EventTime(MapFunction):
        def map_batch(self, batch):
            cols = dict(batch.columns)
            cols[_q5.TS_COLUMN] = _reference.event_time(
                cols, cols[_q5.TS_COLUMN], share, delay_max)
            return batch.with_columns(batch.schema, cols)

    timed = stream.map(EventTime(), name="EventTime") \
        .assign_timestamps_and_watermarks(
            WatermarkStrategy.for_bounded_out_of_orderness(
                int(query["watermark_holdback_ms"]))
            .with_timestamp_column(_q5.TS_COLUMN))
    _q5.build(timed, query, sink)


def make_reference(query: dict, data: dict, on_window):
    """``Q5DisorderReference`` is fed the harness's ``(columns, ts)`` as
    it is; a window is the pair (bids, revenue) per auction, as
    ``q5.py``'s is, so the comparison is ``q5.py``'s unchanged."""
    for k in _DISORDER:
        if query[k] != data[k]:
            raise ValueError(
                f"query.{k} = {query[k]!r} but data.{k} = {data[k]!r}: "
                "the job would derive another event time than the "
                "reference")
    return _reference.Q5DisorderReference(
        int(data["n_keys"]), _q5.pane_ms(query), _q5.window_panes(query),
        data["delayed_share"], data["delay_max_ms"],
        lambda end_ms, bids, rev: on_window(end_ms, (bids, rev)))
