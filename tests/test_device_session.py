"""Device session windows (VERDICT r3 #5): parity against the host
merging WindowOperator (MergingWindowSet semantics) for in-order and
gap-bounded-disorder streams, lateness, multi-session lanes, and
checkpoint/restore."""

import numpy as np
import pytest

from flink_tpu.core import Schema
from flink_tpu.core.functions import AggregateFunction
from flink_tpu.runtime import OneInputOperatorTestHarness
from flink_tpu.runtime.operators import WindowOperator
from flink_tpu.runtime.operators.device_session import (
    DeviceSessionWindowOperator,
)
from flink_tpu.runtime.operators.device_window import AggSpec
from flink_tpu.window import EventTimeSessionWindows

SCHEMA = Schema([("k", np.int64), ("v", np.int64)])


class SumCount(AggregateFunction):
    def create_accumulator(self): return [0, 0]
    def add(self, value, acc): return [acc[0] + value[1], acc[1] + 1]
    def merge(self, a, b): return [a[0] + b[0], a[1] + b[1]]
    def get_result(self, acc): return tuple(acc)


def _host(gap, batches, wms):
    def extract(batch):
        return np.asarray(batch.column("k"))

    op = WindowOperator(
        EventTimeSessionWindows.with_gap(gap), extract,
        aggregate=SumCount(),
        window_fn=lambda key, window, result:
        [(key, window.start, window.end, result[0], result[1])])
    h = OneInputOperatorTestHarness(op, schema=SCHEMA)
    out = []
    for (rows, ts), wm in zip(batches, wms):
        h.process_elements(rows, ts)
        h.process_watermark(wm)
        for r in h.get_output():
            out.append(r)
        h.clear_output()
    h.process_watermark(1 << 40)
    out += h.get_output()
    return {(int(k), int(s), int(e), int(sm), int(c))
            for k, s, e, sm, c in out}


def _device(gap, batches, wms, capacity=1 << 10, lanes=4):
    from flink_tpu.core.records import RecordBatch

    op = DeviceSessionWindowOperator(
        gap, "k", [AggSpec("sum", "v", out_name="total"),
                   AggSpec("count", out_name="cnt")],
        capacity=capacity, lanes=lanes)
    h = OneInputOperatorTestHarness(op, schema=SCHEMA)
    for (rows, ts), wm in zip(batches, wms):
        h.process_batch(RecordBatch.from_rows(SCHEMA, rows, ts))
        h.process_watermark(wm)
    h.process_watermark(1 << 40)
    norm = set()
    for b in h.output.batches:
        for i in range(b.n):
            norm.add((int(b.column("k")[i]),
                      int(b.column("window_start")[i]),
                      int(b.column("window_end")[i]),
                      int(b.column("total")[i]),
                      int(b.column("cnt")[i])))
    return norm, op


class TestParity:
    def test_basic_sessions(self):
        batches = [([(1, 10), (1, 20), (2, 5)], [100, 150, 120]),
                   ([(1, 7)], [400]),                 # new session for 1
                   ([(2, 3)], [180])]                 # extends 2's session
        wms = [200, 500, 1000]
        gap = 100
        host = _host(gap, batches, wms)
        dev, _ = _device(gap, batches, wms)
        assert dev == host
        assert len(dev) >= 3

    @pytest.mark.parametrize("seed", [0, 1, 2, 3])
    def test_random_inorder_stream(self, seed):
        """Random keys/timestamps with per-batch watermarks. Lane count
        must cover the max concurrently-open sessions per key (batch
        span + watermark lag over gap) — the operator's documented
        capacity contract, enforced loudly on overflow."""
        rng = np.random.default_rng(seed)
        n = 600
        ts = np.cumsum(rng.integers(0, 40, n)).tolist()  # gaps up to 39
        keys = rng.integers(0, 12, n).tolist()
        vals = rng.integers(1, 10, n).tolist()
        rows = list(zip(keys, vals))
        # split into batches with watermarks trailing by a fixed lag
        batches, wms = [], []
        for i in range(0, n, 49):
            chunk = rows[i:i + 49]
            cts = ts[i:i + 49]
            batches.append((chunk, cts))
            wms.append(max(cts) - 25)                 # bounded lag
        gap = 250
        host = _host(gap, batches, wms)
        dev, _ = _device(gap, batches, wms, lanes=8)
        assert dev == host

    def test_gap_bounded_disorder(self):
        """Disorder within the gap across batch boundaries still merges
        (min-fold start extension)."""
        gap = 100
        batches = [([(7, 1)], [1000]),
                   ([(7, 2)], [950]),   # earlier, within gap: merges
                   ([(7, 4)], [1080])]
        wms = [500, 500, 500]
        host = _host(gap, batches, wms)
        dev, _ = _device(gap, batches, wms)
        assert dev == host
        assert dev == {(7, 950, 1180, 7, 3)}

    def test_late_events_dropped_like_host(self):
        gap = 50
        batches = [([(3, 1)], [100]),
                   ([(3, 9)], [10])]    # window [10,60) <= fired 201
        wms = [200, 300]
        host = _host(gap, batches, wms)
        dev, op = _device(gap, batches, wms)
        assert dev == host
        assert op.late_dropped == 1


class TestLanes:
    def test_multiple_open_sessions_one_key(self):
        """Watermark lags so two sessions of one key are open at once —
        they occupy different lanes and both fire correctly."""
        gap = 10
        batches = [([(5, 1), (5, 2)], [100, 101]),
                   ([(5, 4), (5, 8)], [200, 201])]    # second session
        wms = [50, 50]                                # nothing fires yet
        host = _host(gap, batches, wms)
        dev, _ = _device(gap, batches, wms)
        assert dev == host
        assert len(dev) == 2

    def test_lane_overflow_raises(self):
        gap = 10
        # 6 concurrently-open sessions for one key with lanes=2
        batches = [([(9, 1)], [i * 1000]) for i in range(6)]
        wms = [1] * 6                                  # watermark stuck
        with pytest.raises(RuntimeError, match="session"):
            _device(gap, batches, wms, lanes=2)


class TestCheckpoint:
    def test_snapshot_restore_midstream(self):
        from flink_tpu.core.records import RecordBatch

        gap = 100
        rows1 = ([(1, 5), (2, 6)], [100, 110])
        rows2 = ([(1, 7), (2, 8)], [150, 400])
        op = DeviceSessionWindowOperator(
            gap, "k", [AggSpec("sum", "v", out_name="total"),
                       AggSpec("count", out_name="cnt")], capacity=64)
        h = OneInputOperatorTestHarness(op, SCHEMA)
        h.process_batch(RecordBatch.from_rows(SCHEMA, *rows1))
        snap = op.snapshot_state(1)
        op2 = DeviceSessionWindowOperator(
            gap, "k", [AggSpec("sum", "v", out_name="total"),
                       AggSpec("count", out_name="cnt")], capacity=64)
        h2 = OneInputOperatorTestHarness(op2, SCHEMA)
        h2.open(keyed_snapshots=[snap["keyed"]])
        h2.process_batch(RecordBatch.from_rows(SCHEMA, *rows2))
        h2.process_watermark(1 << 40)
        got = set()
        for b in h2.output.batches:
            for i in range(b.n):
                got.add((int(b.column("k")[i]),
                         int(b.column("window_start")[i]),
                         int(b.column("window_end")[i]),
                         int(b.column("total")[i]),
                         int(b.column("cnt")[i])))
        # key 1: 100..150 merge -> [100, 250) sum 12; key 2: two sessions
        assert got == {(1, 100, 250, 12, 2), (2, 110, 210, 6, 1),
                       (2, 400, 500, 8, 1)}


class TestOutOfOrderNonLateMerge:
    """ADVICE r4 medium: an out-of-order but NON-late event overlapping a
    segment that closed inside an earlier batch must merge into it (the
    old eager finalization parked such segments in the host pending
    buffer where nothing could reach them, emitting split sessions)."""

    def test_event_merges_into_in_batch_closed_segment(self):
        gap = 50
        # batch 1: key 7 forms TWO in-batch segments [100,110], [200,210]
        batches = [
            ([(7, 1), (7, 1), (7, 1), (7, 1)], [100, 110, 200, 210]),
            # batch 2: t=130 is out of order (behind 210) but NOT late
            # (watermark is still 0) and overlaps [100,110]'s gap window
            ([(7, 1)], [130]),
        ]
        wms = [0, 0]
        host = _host(gap, batches, wms)
        dev, _op = _device(gap, batches, wms)
        assert dev == host
        # the merged first session spans [100, 130 + gap)
        assert (7, 100, 130 + gap, 3, 3) in dev

    def test_random_gap_bounded_disorder_parity(self):
        rng = np.random.default_rng(17)
        gap = 40
        n = 400
        keys = rng.integers(0, 12, n).astype(np.int64)
        base = np.sort(rng.integers(0, 4000, n)).astype(np.int64)
        ts = base + rng.integers(-35, 35, n)   # disorder < gap
        ts = np.maximum(ts, 0)
        rows = [(int(k), 1) for k in keys]
        # two batches with a mid-stream watermark far enough back that
        # nothing is late
        half = n // 2
        batches = [(rows[:half], ts[:half].tolist()),
                   (rows[half:], ts[half:].tolist())]
        wms = [int(ts[:half].max()) - 200, int(ts.max())]
        host = _host(gap, batches, wms)
        # unsettled segments occupy lanes until the watermark settles
        # them, so lane budget must cover a batch's worth of per-key
        # sessions (the operator raises loudly when it cannot)
        dev, _op = _device(gap, batches, wms, lanes=64)
        assert dev == host


AGGS = [("sum", "v", "total"), ("count", None, "cnt")]


def _op(gap, **kwargs):
    return DeviceSessionWindowOperator(
        gap, "k", [AggSpec(kind, field, out_name=out) if field
                   else AggSpec(kind, out_name=out)
                   for kind, field, out in AGGS], **kwargs)


def _rows_of(batches):
    return [(int(b.column("k")[i]), int(b.column("window_start")[i]),
             int(b.column("window_end")[i]), int(b.column("total")[i]),
             int(b.column("cnt")[i]))
            for b in batches for i in range(b.n)]


def _many_keys(n_keys, ts0=100):
    """One bid a key: ``n_keys`` sessions that all close together."""
    rows = [(k, 1) for k in range(n_keys)]
    return rows, [ts0 + (k % 7) for k in range(n_keys)]


class TestFireRounds:
    """The fire compacts at most ``fire_rows`` closed sessions a round
    (PR 43): what does not fit stays on its lanes, closed, for the next
    round of the same fire."""

    def test_a_fire_that_overflows_its_buffer_loses_nothing(self):
        from flink_tpu.core.records import RecordBatch
        from flink_tpu.metrics import DEVICE_STATS

        gap, n_keys = 50, 37
        rows, ts = _many_keys(n_keys)
        op = _op(gap, capacity=256, fire_rows=5)
        h = OneInputOperatorTestHarness(op, schema=SCHEMA)
        before = DEVICE_STATS.session_counts
        h.process_batch(RecordBatch.from_rows(SCHEMA, rows, ts))
        h.process_watermark(1000)        # synchronous: every round, now
        got = _rows_of(h.output.batches)
        assert len(got) == n_keys and len(set(got)) == n_keys
        assert set(got) == {(k, t, t + gap, 1, 1) for (k, _v), t
                            in zip(rows, ts)}
        after = DEVICE_STATS.session_counts
        grown = {k: after[k] - before[k] for k in after}
        assert grown["session_fires_total"] == 1
        assert grown["session_fire_rounds_total"] == -(-n_keys // 5)
        assert grown["session_fired_total"] == n_keys
        assert grown["session_rows_drained_total"] == n_keys
        assert grown["session_lanes_allocated_total"] == n_keys
        assert grown["session_lane_overflow_total"] == 0
        # the watermark follows its fire's last row, once
        assert h.get_watermarks() == [1000]

    @pytest.mark.parametrize("fire_rows", [1, 3, 64])
    def test_random_stream_equals_host_at_any_buffer(self, fire_rows):
        rng = np.random.default_rng(5)
        n = 300
        ts = np.cumsum(rng.integers(0, 30, n)).tolist()
        rows = list(zip(rng.integers(0, 25, n).tolist(),
                        rng.integers(1, 9, n).tolist()))
        batches = [(rows[i:i + 60], ts[i:i + 60]) for i in range(0, n, 60)]
        wms = [max(t) - 10 for _r, t in batches]
        gap = 120
        host = _host(gap, batches, wms)
        from flink_tpu.core.records import RecordBatch

        op = _op(gap, capacity=256, lanes=8, fire_rows=fire_rows)
        h = OneInputOperatorTestHarness(op, schema=SCHEMA)
        for (r, t), wm in zip(batches, wms):
            h.process_batch(RecordBatch.from_rows(SCHEMA, r, t))
            h.process_watermark(wm)
        h.process_watermark(1 << 40)
        assert set(_rows_of(h.output.batches)) == host

    def test_async_fire_leaves_over_later_turns_in_rounds(self):
        """``async_fire``: the watermark's turn only dispatches the first
        round; each later turn (here the processing-time turn) takes a
        landed round in and sends the next; the watermark follows the
        last one."""
        from flink_tpu.core.records import RecordBatch

        gap, n_keys = 50, 20
        rows, ts = _many_keys(n_keys)
        op = _op(gap, capacity=256, fire_rows=8, async_fire=True)
        h = OneInputOperatorTestHarness(op, schema=SCHEMA)
        h.process_batch(RecordBatch.from_rows(SCHEMA, rows, ts))
        h.process_watermark(1000)
        assert h.get_watermarks() == []          # held behind its fire
        for turn in range(200):
            if h.get_watermarks():
                break
            import jax
            jax.block_until_ready(op._round_inflight)
            h.set_processing_time(turn)
        assert h.get_watermarks() == [1000]
        got = _rows_of(h.output.batches)
        assert len(got) == n_keys == len(set(got))
        assert [b.n for b in h.output.batches] == [8, 8, 4]

    def test_snapshot_between_two_rounds_restores_the_rest(self):
        """A checkpoint between a fire's rounds: the round in flight
        lands before the barrier, the sessions still ripe on their lanes
        are in the snapshot, and the job that restores it fires them: no
        session lost, none twice."""
        from flink_tpu.core.records import RecordBatch

        gap, n_keys = 50, 20
        rows, ts = _many_keys(n_keys)
        op = _op(gap, capacity=256, fire_rows=8, async_fire=True)
        h = OneInputOperatorTestHarness(op, schema=SCHEMA)
        h.process_batch(RecordBatch.from_rows(SCHEMA, rows, ts))
        h.process_watermark(1000)                # round 1 dispatched
        snap = op.snapshot_state(1)              # ... and taken in
        first = _rows_of(h.output.batches)
        assert len(first) == 8
        assert h.get_watermarks() == []          # the fire is not over
        # the original goes on where it was
        op.finish()
        assert sorted(_rows_of(h.output.batches)) == sorted(
            (k, t, t + gap, 1, 1) for (k, _v), t in zip(rows, ts))
        # the restored one fires what the snapshot still held
        op2 = _op(gap, capacity=256, fire_rows=8, async_fire=True)
        h2 = OneInputOperatorTestHarness(op2, schema=SCHEMA)
        h2.open(keyed_snapshots=[snap["keyed"]])
        h2.process_batch(RecordBatch.from_rows(SCHEMA, [(99, 5)], [2000]))
        h2.process_watermark(1 << 40)
        op2.finish()                             # asynchronous: wait
        rest = _rows_of(h2.output.batches)
        assert (99, 2000, 2050, 5, 1) in rest
        rest.remove((99, 2000, 2050, 5, 1))
        assert len(rest) == n_keys - 8
        assert sorted(first + rest) == sorted(
            (k, t, t + gap, 1, 1) for (k, _v), t in zip(rows, ts))


class TestCadence:
    def test_fires_run_at_a_cadence_and_hold_the_watermark(self):
        """A fire scans every lane, so it runs when the watermark has
        moved on by ``fire_interval_ms`` (a fifth of the gap unless
        given), not at every watermark; the operator forwards a watermark
        only behind the fire that covers it."""
        from flink_tpu.core.records import RecordBatch

        gap = 100                                  # cadence 20
        op = _op(gap, capacity=64)
        h = OneInputOperatorTestHarness(op, schema=SCHEMA)
        h.process_batch(RecordBatch.from_rows(SCHEMA, [(1, 1)], [10]))
        h.process_watermark(50)                    # the first: fires
        h.process_watermark(60)                    # +10: held
        h.process_batch(RecordBatch.from_rows(SCHEMA, [(2, 1)], [65]))
        h.process_watermark(69)                    # +19: held
        assert h.get_watermarks() == [50]
        h.process_watermark(115)                   # past the cadence
        assert h.get_watermarks() == [50, 115]
        assert _rows_of(h.output.batches) == [(1, 10, 110, 1, 1)]
        h.process_watermark(120)                   # held; finish flushes
        op.finish()
        assert h.get_watermarks() == [50, 115, 120]

    def test_a_session_the_watermark_closed_absorbs_nothing(self):
        """Between two fires a lane may hold a session the watermark has
        closed already. An event that would have joined it (a late-ish
        one, within the gap of its end) must not: the host operator has
        cleared that window, and so has this one, in effect."""
        gap = 100
        batches = [([(4, 1)], [10]),               # session [10, 110)
                   ([(9, 1)], [113]),              # carries watermark 112
                   ([(4, 2)], [105]),              # [105, 205): apart
                   ([(4, 4)], [300])]
        wms = [50, 112, 115, 500]
        host = _host(gap, batches, wms)
        assert (4, 10, 110, 1, 1) in host and (4, 105, 205, 2, 1) in host
        from flink_tpu.core.records import RecordBatch

        # a cadence of 200: watermark 112 closes [10, 110) and no fire
        # takes it off its lane before the bid at 105 arrives
        op = _op(gap, capacity=64, fire_interval_ms=200)
        h = OneInputOperatorTestHarness(op, schema=SCHEMA)
        for (rows, ts), wm in zip(batches, wms):
            h.process_batch(RecordBatch.from_rows(SCHEMA, rows, ts))
            h.process_watermark(wm)
            if wm == 112:
                assert h.get_watermarks() == [50]  # no fire at 112
        h.process_watermark(1 << 40)
        assert set(_rows_of(h.output.batches)) == host

    @pytest.mark.parametrize("apart,sessions", [(99, 1), (100, 2), (101, 2)])
    def test_a_bid_at_last_plus_gap_opens_a_new_session(self, apart,
                                                        sessions):
        """``ts - last == gap`` splits, in one batch and across two, here
        and in the host operator (a function of the data alone: see the
        module's docstring for Flink's rule and why it is not taken)."""
        gap = 100
        one = [([(6, 1), (6, 1)], [1000, 1000 + apart])]
        two = [([(6, 1)], [1000]), ([(6, 1)], [1000 + apart])]
        for batches in (one, two):
            wms = [0] * len(batches)
            host = _host(gap, batches, wms)
            dev, _ = _device(gap, batches, wms)
            assert dev == host and len(dev) == sessions


@pytest.mark.parametrize("shape,share,rows", [
    ((4, 1 << 12), 0.01, 64), ((4, 1 << 12), 0.3, 5000), ((3, 100), 0.2, 17),
    ((64, 64), 0.5, 4096), ((2, 64), 0.0, 8), ((2, 64), 1.0, 200)])
def test_the_fires_select_finds_set_bits_by_what_is_set(shape, share, rows):
    """``_select_set_bits``: the count of set elements of a mask of any
    shape, and ``rows`` of them (all, where they fit), each once: the
    packed words' bit ``b`` of word ``w`` is flat element
    ``reverse5(b) * words + w``, padding included."""
    import jax
    import jax.numpy as jnp

    from flink_tpu.runtime.operators.device_session import _select_set_bits

    mask = np.random.default_rng(0).random(shape) < share
    index, total = jax.jit(lambda m: _select_set_bits(m, rows))(
        jnp.asarray(mask))
    want = set(np.flatnonzero(mask.reshape(-1)).tolist())
    assert int(total) == len(want)
    got = np.asarray(index)[:min(len(want), rows)].tolist()
    assert len(set(got)) == len(got) and set(got) <= want
    if len(want) <= rows:
        assert set(got) == want
