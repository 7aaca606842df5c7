"""Out-of-order input in front of the window operators (PR 51).

* ``DataStream.assign_timestamps_and_watermarks`` mid-stream: the
  operator (``runtime/operators/simple.py``
  ``TimestampsAndWatermarksOperator``) replaces upstream watermarks by its
  generator's and forwards ONE of them, the end-of-input watermark, so a
  bounded job's last windows fire behind a holdback: host
  ``WindowOperator`` and ``device_aggregate``, tumbling, sliding and
  session.
* NEXmark Q5 as the benchmark's ``queries/q5_disorder.py`` builds it
  (event-time map, assigner, ``device_aggregate``) through
  ``env.execute()`` over a seeded stream whose batches hold rows of k
  panes, against ``queries/q5_disorder_reference.py`` row for row: k = 2
  (the sort branch not taken), 3, 5 and ring - W - 1, HOP and TUMBLE, the
  last windows of the bounded job included, with the fold's counters.
"""

import numpy as np
import pytest

from benchmarks.harness.spec import BENCH_DIR, load_module
from flink_tpu.api import StreamExecutionEnvironment
from flink_tpu.connectors.core import CollectSink
from flink_tpu.core import MAX_WATERMARK, PipelineOptions, Watermark, \
    WatermarkStrategy
from flink_tpu.core.config import TraceOptions
from flink_tpu.core.functions import SinkFunction
from flink_tpu.core.records import RecordBatch, Schema
from flink_tpu.metrics import DEVICE_STATS
from flink_tpu.metrics.core import MetricRegistry
from flink_tpu.metrics.tracing import TRACER
from flink_tpu.runtime.operators.base import CollectingOutput, \
    OperatorContext
from flink_tpu.runtime.operators.device_window import AggSpec
from flink_tpu.runtime.operators.simple import \
    TimestampsAndWatermarksOperator
from flink_tpu.state.tpu_backend import TpuKeyedStateBackend
from flink_tpu.window import EventTimeSessionWindows, \
    SlidingEventTimeWindows, TumblingEventTimeWindows

# -- the assigner ----------------------------------------------------------

KV = Schema([("k", np.int64), ("v", np.int64), ("ts", np.int64)])


def _assigner(holdback=100):
    op = TimestampsAndWatermarksOperator(
        WatermarkStrategy.for_bounded_out_of_orderness(holdback)
        .with_timestamp_column("ts"))
    out = CollectingOutput()
    op.setup(OperatorContext("t", 0, 1, 128), out)
    return op, out


def _kv(ts):
    ts = np.asarray(ts, np.int64)
    return RecordBatch(KV, {"k": ts * 0, "v": ts * 0 + 1, "ts": ts})


def test_the_assigner_swallows_upstream_watermarks_but_the_last():
    op, out = _assigner()
    op.process_batch(_kv([500, 400, 700]))
    assert [w.timestamp for w in out.watermarks] == [700 - 100 - 1]
    assert (out.batches[0].timestamps == [500, 400, 700]).all()
    op.process_watermark(Watermark(10_000))          # the source's own
    assert len(out.watermarks) == 1
    op.process_watermark(MAX_WATERMARK)              # end of input
    assert out.watermarks[-1] == MAX_WATERMARK and len(out.watermarks) == 2
    assert op.current_watermark == MAX_WATERMARK.timestamp


def test_the_assigner_counts_rows_behind_the_batches_before():
    op, _out = _assigner()
    op.process_batch(_kv([10, 5, 30]))       # disorder inside a batch
    assert op.records_out_of_order == 0      # is not behind a batch before
    op.process_batch(_kv([29, 30, 31, 3]))
    assert op.records_out_of_order == 2
    op.process_batch(_kv([31, 32]))
    assert op.records_out_of_order == 2


def _jittered(idx):
    """Event times up to 300 ms behind a row's place in the stream."""
    u = idx.astype(np.uint64) * np.uint64(0x9E3779B97F4A7C15)
    lag = ((u >> np.uint64(13)) % np.uint64(300)).astype(np.int64)
    return {"k": ((u >> np.uint64(7)) % np.uint64(7)).astype(np.int64),
            "v": np.ones(len(idx), np.int64),
            "ts": 1000 + idx * 5 - lag}


N_TAIL = 2000
WINDOWS = {
    "tumbling": (TumblingEventTimeWindows.of(1000), 1),
    "sliding": (SlidingEventTimeWindows.of(1000, 250), 4),
    # gap over the disorder: the device session operator is exact there
    # (its docstring: an event bridging two open sessions needs per-key
    # disorder past the gap)
    "session": (EventTimeSessionWindows.with_gap(400), 1),
}


@pytest.mark.parametrize("window", list(WINDOWS))
@pytest.mark.parametrize("operator", ["host", "device"])
def test_a_bounded_job_behind_the_assigner_emits_its_last_windows(
        operator, window):
    """The watermark trails the newest event by 400 ms, so without the
    end-of-input watermark the windows of the last 400 ms and more never
    fire (the parent of PR 51: the assigner's ``process_watermark`` was
    ``pass``). Every record must be counted in each window that holds
    it."""
    assigner, windows_a_record = WINDOWS[window]
    env = StreamExecutionEnvironment.get_execution_environment()
    env.config.set(PipelineOptions.BATCH_SIZE, 100)
    if operator == "device":
        env.set_state_backend("tpu")
    registry = MetricRegistry()
    windowed = env.datagen(_jittered, KV, count=N_TAIL) \
        .assign_timestamps_and_watermarks(
            WatermarkStrategy.for_bounded_out_of_orderness(400)
            .with_timestamp_column("ts")) \
        .key_by("k").window(assigner)
    sink = CollectSink()
    if operator == "device":
        windowed.device_aggregate([AggSpec("count", out_name="n")],
                                  capacity=1 << 8, ring_size=16) \
            .add_sink(sink, "rows")
    else:
        windowed.count().add_sink(sink, "rows")
    env.execute(f"tail-{operator}-{window}", timeout=120.0,
                metrics_registry=registry)
    assert sum(r[-1] for r in sink.rows) == N_TAIL * windows_a_record
    if operator == "device":
        # (key, start, end, n): the window of the stream's last event
        last_ts = int(_jittered(np.arange(N_TAIL))["ts"].max())
        assert max(r[2] for r in sink.rows) > last_ts
    names = {type(op).__name__ for task in env.last_job.tasks.values()
             for op in getattr(getattr(task, "chain", None),
                               "operators", ())}
    assert "TimestampsAndWatermarksOperator" in names
    behind = [v for k, v in registry.snapshot().items()
              if k.endswith("TimestampsWatermarks.numRecordsOutOfOrder")]
    assert len(behind) == 1 and 0 < behind[0] < N_TAIL


# -- Q5 over a disordered stream, against the benchmark's reference --------

q5_disorder = load_module(BENCH_DIR, "queries", "q5_disorder")
BIDS = Schema(q5_disorder.SCHEMA_FIELDS)
PANE, RING, ROWS, N, KEYS = 1000, 16, 512, 1 << 15, 97
#: a batch is a quarter of a pane of the stream
SPAN = PANE // 4
SHARE = 0.5


def _bids(idx):
    u = idx.astype(np.uint64) * np.uint64(0x9E3779B97F4A7C15)
    return {"auction": ((u >> np.uint64(7)) % np.uint64(KEYS)).astype(
                np.int64),
            "bidder": ((u >> np.uint64(29)) % np.uint64(1000)).astype(
                np.int64),
            "price": ((u >> np.uint64(23)) % np.uint64(1 << 40)).astype(
                np.int64) + 1,
            "ts": 20_000 + (idx * SPAN) // ROWS}


class _Rows(SinkFunction):
    def __init__(self):
        self.batches = []

    def invoke_batch(self, batch):
        self.batches.append({f.name: np.asarray(batch.column(f.name))
                             for f in batch.schema.fields})
        return True

    def rows(self):
        return {name: np.concatenate([b[name] for b in self.batches])
                for name in self.batches[0]}


def _query(panes_a_window, k, holdback=None):
    """A batch's rows lie in at most ``k`` panes: its own quarter of a
    pane and lags of up to ``(k - 1)`` panes less that quarter."""
    delay_max = (k - 1) * PANE - SPAN
    return dict(window_size_ms=panes_a_window * PANE, window_slide_ms=PANE,
                count_value_bits=31, topk=16, operator="device_aggregate",
                capacity=1 << 10, ring_size=RING, defer_overflow=True,
                async_fire=True, delayed_share=SHARE,
                delay_max_ms=delay_max,
                watermark_holdback_ms=delay_max if holdback is None
                else holdback)


def _run(query, monkeypatch, n=N, traces=False):
    env = StreamExecutionEnvironment.get_execution_environment()
    env.set_state_backend("tpu")
    env.config.set(TraceOptions.ENABLED, traces)
    env.config.set(PipelineOptions.BATCH_SIZE, ROWS)
    stream = env.datagen(
        _bids, BIDS, count=n, timestamp_column="ts",
        watermark_strategy=WatermarkStrategy.for_monotonous_timestamps()
        .with_timestamp_column("ts"))
    sink = _Rows()
    q5_disorder.build(stream, query, sink)
    touched = []
    fold_rings = TpuKeyedStateBackend.fold_rings

    def spy(self, slots, ring_idx, valid, values):
        touched.append(np.asarray(ring_idx))
        return fold_rings(self, slots, ring_idx, valid, values)

    monkeypatch.setattr(TpuKeyedStateBackend, "fold_rings", spy)
    before = DEVICE_STATS.snapshot()
    env.execute("q5-disorder", timeout=300.0)
    after = DEVICE_STATS.snapshot()
    op = next(op for task in env.last_job.tasks.values()
              for op in getattr(getattr(task, "chain", None),
                                "operators", ())
              if isinstance(op, q5_disorder.operator_class(query)))
    return sink.rows(), touched, op, {k: after[k] - before[k]
                                      for k in after if k.startswith(
                                          ("fold_", "h2d_records"))}


def _reference(query, n=N):
    windows = {}
    ref = q5_disorder.make_reference(
        query, {"n_keys": KEYS, "delayed_share": query["delayed_share"],
                "delay_max_ms": query["delay_max_ms"]},
        lambda end, w: windows.__setitem__(end, (w[0].copy(), w[1].copy())))
    for first in range(0, n, ROWS):
        cols = _bids(np.arange(first, first + ROWS))
        ref.feed(cols, cols.pop("ts"))
    ref.close()
    return ref, windows


@pytest.mark.parametrize("panes_a_window", [5, 1], ids=["hop", "tumble"])
@pytest.mark.parametrize("k", [2, 3, 5, "ring-W-1"])
def test_q5_over_k_panes_a_batch_equals_the_reference(k, panes_a_window,
                                                      monkeypatch):
    W = panes_a_window
    k = RING - W - 1 if k == "ring-W-1" else k
    query = _query(W, k)
    rows, touched, op, grew = _run(query, monkeypatch)
    ref, windows = _reference(query)
    assert op.late_dropped == 0
    # the fold met what the test set out to send
    per_batch = [len(np.unique(r)) for r in touched]
    assert len(per_batch) == N // ROWS and max(per_batch) == k
    assert grew["fold_batches_total"] == N // ROWS
    assert grew["fold_ring_rows_total"] == sum(per_batch)
    assert grew["fold_sorted_batches_total"] == sum(
        n > 2 for n in per_batch)
    assert all((np.diff(r) >= 0).all() for r in touched
               if len(np.unique(r)) > 2)
    if k == 2:
        assert grew["fold_sorted_batches_total"] == 0
    else:       # most batches, not all: one late in its pane reaches
        assert grew["fold_sorted_batches_total"] > N // ROWS // 2  # k - 1
    # rows that reached back behind the newest pane of the batches
    # before: the operator counts what the reference counts
    assert grew["fold_back_rows_total"] == ref.back_rows > 0
    assert grew["h2d_records"] == N
    # every window that holds data, the last ones included, row for row
    expected = {end for end, w in windows.items()
                if q5_disorder.window_holds_data(w)}
    assert set(rows["window_end"].tolist()) == expected
    last_pane = int(_bids(np.arange(N))["ts"].max()) // PANE
    assert max(expected) == (last_pane + W) * PANE
    assert (rows["window_start"] == rows["window_end"] - W * PANE).all()
    for end in sorted(expected):
        at = rows["window_end"] == end
        verdict = q5_disorder.compare_window(
            {name: col[at] for name, col in rows.items()}, windows[end],
            query)
        assert (verdict.rows_differ, verdict.topk_wrong) == (0, 0), (
            end, verdict.detail)
        assert verdict.rows == min(16, int(np.count_nonzero(
            windows[end][0])))


def test_the_sort_is_a_stage_of_its_own_before_the_upload(monkeypatch):
    """``window/RingSort``: one span a sorted batch, with the batch's
    rows and ring rows, ahead of the same batch's ``window/Upload`` on
    the same task; a batch of two ring rows or fewer opens none."""
    TRACER.reset()
    try:
        _rows, touched, _op, grew = _run(_query(5, 3), monkeypatch,
                                         traces=True)
        spans = TRACER.retained_spans()
    finally:
        TRACER.reset()
    named = {name: {s.attributes["seq"]: s for s in spans
                    if (s.scope, s.name) == ("window", name)}
             for name in ("RingSort", "Upload", "IngestDispatch")}
    per_batch = [len(np.unique(r)) for r in touched]
    assert len(named["Upload"]) == len(per_batch)
    assert sorted(named["RingSort"]) == [
        i + 1 for i, n in enumerate(per_batch) if n > 2]
    assert len(named["RingSort"]) == grew["fold_sorted_batches_total"] \
        < len(per_batch)
    for seq, sort in named["RingSort"].items():
        up = named["Upload"][seq]
        assert (sort.attributes["rows"], sort.attributes["ring_rows"]) \
            == (ROWS, per_batch[seq - 1]) == (
                ROWS, named["IngestDispatch"][seq].attributes["ring_rows"])
        assert sort.attributes["task"] == up.attributes["task"]
        assert sort.end_ns <= up.start_ns and sort.parent_id == up.parent_id


@pytest.mark.parametrize("panes_a_window", [5, 1], ids=["hop", "tumble"])
def test_q5_with_no_holdback_differs(panes_a_window, monkeypatch):
    """The control: the watermark on the newest event's heels. Under
    TUMBLE a delayed row of a fired pane is late, dropped and counted.
    Under HOP it is late for the windows that have fired and in time for
    those that have not (W = 5 panes, the delay under 2): the operator,
    like Flink's, drops and counts a row only when EVERY window of it
    has fired, so ``late_dropped`` stays 0 and the fired windows lack
    the row all the same."""
    query = _query(panes_a_window, 3, holdback=0)
    # (a batch that loses late rows changes shape and compiles its
    # programs anew: the TUMBLE case runs four batches)
    n = N if panes_a_window > 1 else 4 * ROWS
    rows, _touched, op, _grew = _run(query, monkeypatch, n)
    _ref, windows = _reference(query, n)
    assert (op.late_dropped > 0) == (panes_a_window == 1)
    differ = 0
    for end, window in windows.items():
        at = rows["window_end"] == end
        differ += q5_disorder.compare_window(
            {name: col[at] for name, col in rows.items()}, window,
            query).rows_differ
    assert differ > 0
