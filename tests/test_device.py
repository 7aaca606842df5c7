"""Device-path tests: hash table kernels, TPU state backend, device window
operator parity with the host WindowOperator (runs on the virtual CPU
platform; same code path compiles for TPU)."""

import glob
import os
import time

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from flink_tpu.api import StreamExecutionEnvironment  # noqa: E402
from flink_tpu.core import (  # noqa: E402
    KeyGroupRange, Schema, WatermarkStrategy,
)
from flink_tpu.core.config import (  # noqa: E402
    CheckpointingOptions, PipelineOptions, RuntimeOptions,
)
from flink_tpu.core.functions import MapFunction, SinkFunction  # noqa: E402
from flink_tpu.metrics import DEVICE_STATS  # noqa: E402
from flink_tpu.ops.hash_table import (  # noqa: E402
    EMPTY_KEY, ensure_x64, lookup, lookup_or_insert, make_table,
)
from flink_tpu.ops.segment_ops import (  # noqa: E402
    AGG_FOLDS, make_accumulator, pane_window_merge, ring_fold, scatter_fold,
    segment_topk,
)
from flink_tpu.runtime.operators.device_window import AggSpec  # noqa: E402
from flink_tpu.state.tpu_backend import TpuKeyedStateBackend  # noqa: E402
from flink_tpu.window import SlidingEventTimeWindows  # noqa: E402


class TestHashTable:
    def test_insert_and_lookup(self):
        t = make_table(64)
        keys = jnp.array([5, 17, 5, 99, 17], dtype=jnp.int64)
        t, slots, ok = lookup_or_insert(t, keys)
        s = np.asarray(slots)
        assert bool(np.asarray(ok).all())
        assert s[0] == s[2] and s[1] == s[4]  # duplicates share slots
        assert len({s[0], s[1], s[3]}) == 3   # distinct keys distinct slots
        # lookup finds the same slots
        s2 = np.asarray(lookup(t, jnp.array([99, 5], dtype=jnp.int64)))
        assert s2[0] == s[3] and s2[1] == s[0]

    def test_lookup_missing(self):
        t = make_table(64)
        t, _, _ = lookup_or_insert(t, jnp.array([1, 2], dtype=jnp.int64))
        assert np.asarray(lookup(t, jnp.array([42], dtype=jnp.int64)))[0] == -1

    def test_collision_heavy(self):
        """Many keys into a small table: all inserted, slots unique."""
        t = make_table(256)
        keys = jnp.arange(128, dtype=jnp.int64) * 256  # same low bits
        t, slots, ok = lookup_or_insert(t, keys)
        s = np.asarray(slots)
        assert bool(np.asarray(ok).all())
        assert len(set(s.tolist())) == 128

    def test_incremental_batches(self):
        t = make_table(1024)
        rng = np.random.default_rng(0)
        all_keys = rng.choice(10_000, size=500, replace=False).astype(np.int64)
        slots_by_key = {}
        for i in range(0, 500, 100):
            batch = jnp.asarray(all_keys[i:i + 100])
            t, slots, ok = lookup_or_insert(t, batch)
            assert bool(np.asarray(ok).all())
            for k, s in zip(all_keys[i:i + 100], np.asarray(slots)):
                slots_by_key[int(k)] = int(s)
        # re-lookup everything: stable slots
        s2 = np.asarray(lookup(t, jnp.asarray(all_keys)))
        for k, s in zip(all_keys, s2):
            assert slots_by_key[int(k)] == int(s)


class TestSegmentOps:
    def test_scatter_fold_kinds(self):
        acc = make_accumulator("sum", (8,), jnp.float32)
        idx = jnp.array([1, 1, 3], jnp.int32)
        vals = jnp.array([2.0, 3.0, 7.0])
        valid = jnp.array([True, True, False])
        out = np.asarray(scatter_fold("sum", acc, idx, vals, valid))
        assert out[1] == 5.0 and out[3] == 0.0

        accm = make_accumulator("min", (4,), jnp.int64)
        out = np.asarray(scatter_fold(
            "min", accm, jnp.array([0, 0], jnp.int32),
            jnp.array([7, 3], jnp.int64), jnp.array([True, True])))
        assert out[0] == 3

    _RING, _CAP, _ROWS = 4, 32, 100

    @pytest.mark.parametrize("masked", [False, True],
                             ids=["all_valid", "invalid_and_padding"])
    @pytest.mark.parametrize("touched", [0, 1, 2, 4],
                             ids=["no_row", "one_row", "two_rows",
                                  "every_row"])
    @pytest.mark.parametrize("dtype", [jnp.int32, jnp.int64, jnp.float32],
                             ids=["int32", "int64", "float32"])
    @pytest.mark.parametrize("kind", ["count", "sum", "min", "max"])
    def test_ring_fold_is_the_flat_fold(self, kind, dtype, touched, masked,
                                        monkeypatch):
        """`ring_fold` over a [ring, capacity] plane against the flat
        scatter it replaces, `plane.reshape(-1).at[ring_idx * cap +
        slot].<op>(v)`, bit for bit, over two consecutive batches: for
        0, 1, 2 and all ring rows touched, with every row valid and with
        invalid rows (a failed insert: a live slot, `valid` false) and
        padding rows (slot -1) among them. A ring row's run of the batch
        is folded a chunk at a time: here 32 updates, so that a run takes
        several chunks and the last one backs up."""
        ensure_x64()   # the regime every job runs in
        monkeypatch.setattr("flink_tpu.ops.segment_ops._FOLD_CHUNK", 32)
        ring, cap, n = self._RING, self._CAP, self._ROWS
        rng = np.random.default_rng(
            [touched, masked, ["count", "sum", "min", "max"].index(kind)])
        fold = jax.jit(lambda *batch: ring_fold(kind, *batch))
        plane = flat = make_accumulator(kind, (ring, cap), dtype)
        for _batch in range(2):
            first = int(rng.integers(ring))
            ring_idx = (first + rng.integers(max(touched, 1), size=n)) % ring
            slots = rng.integers(cap, size=n).astype(np.int32)
            values = rng.integers(-50, 50, size=n).astype(np.dtype(dtype))
            valid = np.full(n, touched > 0)
            if masked:
                valid &= rng.random(n) < 0.7
                slots[-8:], valid[-8:] = -1, False
            if touched:    # the batch does hold a row for each of them
                ring_idx[:touched] = (first + np.arange(touched)) % ring
                valid[:touched], slots[:touched] = True, 0
            plane = fold(plane, jnp.asarray(ring_idx),
                         jnp.asarray(slots), jnp.asarray(values),
                         jnp.asarray(valid))
            idx = np.where(valid, ring_idx * cap + slots, ring * cap)
            flat = AGG_FOLDS[kind](
                flat.reshape(-1), jnp.asarray(idx),
                jnp.asarray(values)).reshape(ring, cap)
            assert plane.dtype == flat.dtype and plane.shape == flat.shape
            assert np.asarray(plane).tobytes() == np.asarray(flat).tobytes()
        untouched = np.asarray(plane) == np.asarray(
            make_accumulator(kind, (ring, cap), dtype))
        assert untouched.all() == (touched == 0)

    def test_ring_fold_of_an_empty_batch_is_the_plane(self):
        ensure_x64()
        plane = make_accumulator("sum", (self._RING, self._CAP), jnp.int64)
        none = jnp.zeros(0, jnp.int32)
        out = ring_fold("sum", plane, none, none, jnp.zeros(0, jnp.int64),
                        jnp.zeros(0, bool))
        assert np.asarray(out).tobytes() == np.asarray(plane).tobytes()

    def test_pane_window_merge(self):
        acc = jnp.asarray(np.arange(12, dtype=np.float32).reshape(3, 4))
        out = np.asarray(pane_window_merge("sum", acc, jnp.array([0, 2])))
        assert out.tolist() == [8.0, 10.0, 12.0, 14.0]

    def test_topk(self):
        vals = jnp.array([5.0, 1.0, 9.0, 3.0])
        valid = jnp.array([True, True, False, True])
        v, i = segment_topk(vals, valid, 2)
        assert np.asarray(v).tolist() == [5.0, 3.0]
        assert np.asarray(i).tolist() == [0, 3]


class TestTpuBackend:
    def test_fold_and_rehash_growth(self):
        b = TpuKeyedStateBackend(KeyGroupRange(0, 127), 128, capacity=64)
        b.register_array_state("acc", "sum", jnp.float32)
        rng = np.random.default_rng(1)
        keys = rng.choice(100_000, size=200, replace=False).astype(np.int64)
        for i in range(0, 200, 50):
            k = keys[i:i + 50]
            slots = b.slots_for_batch(k)
            b.fold_batch("acc", slots, jnp.ones(len(k), jnp.float32),
                         slots >= 0)
        assert b.capacity >= 256  # grew past initial 64
        # every key has exactly 1.0 despite rehashes
        slots = np.asarray(jax.device_get(
            b.slots_for_batch(keys)))
        acc = np.asarray(jax.device_get(b.get_array("acc")))
        assert np.allclose(acc[slots], 1.0)

    def test_snapshot_restore_rescale(self):
        b = TpuKeyedStateBackend(KeyGroupRange(0, 127), 128, capacity=128)
        b.register_array_state("acc", "sum", jnp.float32)
        keys = np.arange(50, dtype=np.int64)
        slots = b.slots_for_batch(keys)
        b.fold_batch("acc", slots, jnp.asarray(keys.astype(np.float32)),
                     slots >= 0)
        snap = b.snapshot(1)

        b1 = TpuKeyedStateBackend(KeyGroupRange(0, 63), 128, capacity=128)
        b2 = TpuKeyedStateBackend(KeyGroupRange(64, 127), 128, capacity=128)
        b1.restore([snap])
        b2.restore([snap])
        k1 = set(b1.keys("acc"))
        k2 = set(b2.keys("acc"))
        assert k1.isdisjoint(k2)
        assert k1 | k2 == set(range(50))
        # values preserved
        got = {}
        for bb in (b1, b2):
            t = np.asarray(jax.device_get(bb.table))
            occ = np.flatnonzero(t != EMPTY_KEY)
            acc = np.asarray(jax.device_get(bb.get_array("acc")))
            for s in occ:
                got[int(t[s])] = float(acc[s])
        assert got == {int(k): float(k) for k in keys}


def _host_window_result(elements, ts, window, kind="sum"):
    """Run the host WindowOperator for parity reference."""
    from flink_tpu.core.functions import AggregateFunction
    from flink_tpu.runtime import OneInputOperatorTestHarness
    from flink_tpu.runtime.operators import WindowOperator

    class Agg(AggregateFunction):
        def create_accumulator(self): return 0
        def add(self, v, acc): return acc + v[1]
        def merge(self, a, b): return a + b
        def get_result(self, acc): return acc

    def extract(batch):
        return np.array([r[0] for r in batch.iter_rows()], dtype=object)

    op = WindowOperator(window, extract, aggregate=Agg())
    h = OneInputOperatorTestHarness(
        op, schema=Schema([("key", np.int64), ("v", np.int64)]))
    h.process_elements(elements, ts)
    h.process_watermark(10**9)
    return sorted((int(k), int(v)) for k, v in h.get_output())


class TestDeviceWindowOperator:
    def _device_result(self, elements, ts, assigner, watermarks=None):
        from flink_tpu.runtime import OneInputOperatorTestHarness
        from flink_tpu.runtime.operators.device_window import (
            AggSpec, DeviceWindowAggOperator,
        )
        op = DeviceWindowAggOperator(
            assigner, "key", [AggSpec("sum", "v", out_name="result")],
            capacity=1 << 10, emit_window_bounds=False)
        h = OneInputOperatorTestHarness(
            op, schema=Schema([("key", np.int64), ("v", np.int64)]))
        if watermarks is None:
            h.process_elements(elements, ts)
            h.process_watermark(10**9)
        else:
            for step in watermarks:
                if step[0] == "batch":
                    h.process_elements(step[1], step[2])
                else:
                    h.process_watermark(step[1])
        return h, sorted((int(k), int(v)) for k, v in h.get_output())

    def test_tumbling_parity_with_host(self):
        from flink_tpu.window import TumblingEventTimeWindows
        rng = np.random.default_rng(2)
        n = 500
        elements = [(int(k), int(v)) for k, v in
                    zip(rng.integers(0, 20, n), rng.integers(1, 10, n))]
        ts = sorted(rng.integers(0, 10_000, n).tolist())
        w = TumblingEventTimeWindows.of(1000)
        _h, device = self._device_result(elements, ts, w)
        host = _host_window_result(elements, ts, w)
        assert device == host

    def test_sliding_parity_with_host(self):
        from flink_tpu.window import SlidingEventTimeWindows
        rng = np.random.default_rng(3)
        n = 300
        elements = [(int(k), int(v)) for k, v in
                    zip(rng.integers(0, 10, n), rng.integers(1, 5, n))]
        ts = sorted(rng.integers(0, 5_000, n).tolist())
        w = SlidingEventTimeWindows.of(1000, 250)
        _h, device = self._device_result(elements, ts, w)
        host = _host_window_result(elements, ts, w)
        assert device == host

    def test_incremental_watermarks_fire_incrementally(self):
        from flink_tpu.window import TumblingEventTimeWindows
        w = TumblingEventTimeWindows.of(100)
        h, out = self._device_result(
            None, None, w,
            watermarks=[
                ("batch", [(1, 5), (2, 7)], [10, 20]),
                ("wm", 99),                       # fires window [0,100)
                ("batch", [(1, 3)], [150]),
                ("wm", 199),                      # fires window [100,200)
            ])
        assert out == [(1, 3), (1, 5), (2, 7)]

    def test_late_drop_counted(self):
        from flink_tpu.window import TumblingEventTimeWindows
        w = TumblingEventTimeWindows.of(100)
        h, out = self._device_result(
            None, None, w,
            watermarks=[
                ("batch", [(1, 5)], [10]),
                ("wm", 299),
                ("batch", [(1, 9)], [20]),   # late: window fired
                ("wm", 399),
            ])
        assert out == [(1, 5)]
        assert h.operator.late_dropped == 1

    def test_snapshot_restore_continues(self):
        from flink_tpu.runtime import OneInputOperatorTestHarness
        from flink_tpu.runtime.operators.device_window import (
            AggSpec, DeviceWindowAggOperator,
        )
        from flink_tpu.window import TumblingEventTimeWindows
        w = TumblingEventTimeWindows.of(100)

        def make_op():
            return DeviceWindowAggOperator(
                w, "key", [AggSpec("sum", "v", out_name="result")],
                capacity=1 << 10, emit_window_bounds=False)

        schema = Schema([("key", np.int64), ("v", np.int64)])
        h = OneInputOperatorTestHarness(make_op(), schema=schema)
        h.process_elements([(1, 5), (2, 7)], [10, 20])
        snap = h.snapshot()

        h2 = OneInputOperatorTestHarness.restored(
            lambda: make_op(), snap, schema=schema)
        h2.process_elements([(1, 3)], [30])
        h2.process_watermark(99)
        assert sorted((int(k), int(v)) for k, v in h2.get_output()) == \
            [(1, 8), (2, 7)]

    def test_pipeline_auto_device_selection(self):
        """env with tpu backend: WindowedStream.sum lowers to device op."""
        from flink_tpu.api import StreamExecutionEnvironment
        from flink_tpu.core import Schema as S, WatermarkStrategy
        from flink_tpu.window import TumblingEventTimeWindows
        env = StreamExecutionEnvironment.get_execution_environment()
        env.set_state_backend("tpu")
        schema = S([("key", np.int64), ("v", np.int64), ("ts", np.int64)])

        def gen(idx):
            return {"key": idx % 7, "v": np.ones_like(idx), "ts": idx * 10}

        ws = WatermarkStrategy.for_monotonous_timestamps() \
            .with_timestamp_column("ts")
        out = (env.datagen(gen, schema, count=700, timestamp_column="ts",
                           watermark_strategy=ws)
               .key_by("key")
               .window(TumblingEventTimeWindows.of(1000))
               .sum("v")
               .execute_and_collect())
        total = sum(int(v) for _k, v in out)
        assert total == 700


class TestDeviceWindowRegressions:
    """Regressions from review: ring aliasing, pre-data lateness, empty
    restore, non-integer keys."""

    SCHEMA = Schema([("k", np.int64), ("v", np.int64)])

    def _op(self, assigner, **kw):
        from flink_tpu.runtime.operators.device_window import (
            AggSpec, DeviceWindowAggOperator,
        )
        return DeviceWindowAggOperator(
            assigner, "k", [AggSpec("sum", "v", out_name="result")],
            emit_window_bounds=False, **kw)

    def test_sparse_panes_no_ring_aliasing(self):
        from flink_tpu.runtime import OneInputOperatorTestHarness
        from flink_tpu.window import SlidingEventTimeWindows
        op = self._op(SlidingEventTimeWindows.of(4000, 1000), ring_size=64)
        h = OneInputOperatorTestHarness(op, schema=self.SCHEMA)
        h.process_elements([(1, 10)], [500])
        h.process_elements([(1, 100)], [61500])  # pane 61 aliases row of pane -3
        h.process_watermark(10**9)
        out = sorted(int(v) for _k, v in h.get_output())
        assert out == [10, 10, 10, 10, 100, 100, 100, 100]

    def test_pre_data_watermark_drops_late(self):
        from flink_tpu.runtime import OneInputOperatorTestHarness
        from flink_tpu.window import TumblingEventTimeWindows
        op = self._op(TumblingEventTimeWindows.of(100))
        h = OneInputOperatorTestHarness(op, schema=self.SCHEMA)
        h.process_watermark(999)
        h.process_elements([(1, 5)], [10])
        h.process_watermark(1999)
        assert h.get_output() == []
        assert op.late_dropped == 1

    def test_empty_snapshot_restore(self):
        from flink_tpu.core import KeyGroupRange
        b = TpuKeyedStateBackend(KeyGroupRange(0, 127), 128, capacity=64)
        b.register_array_state("a", "sum", jnp.float32)
        snap = b.snapshot(1)
        b2 = TpuKeyedStateBackend(KeyGroupRange(0, 127), 128, capacity=64)
        b2.restore([snap])  # must not raise
        assert b2.num_keys == 0

    def test_non_integer_key_rejected(self):
        from flink_tpu.runtime import OneInputOperatorTestHarness
        from flink_tpu.window import TumblingEventTimeWindows
        op = self._op(TumblingEventTimeWindows.of(100))
        h = OneInputOperatorTestHarness(
            op, schema=Schema([("k", np.float64), ("v", np.int64)]))
        with pytest.raises(TypeError, match="integer key column"):
            h.process_elements([(2.3, 1)], [10])


# ---------------------------------------------------------------------------
# The one-chip host-born fold through `env.execute()`: one donated program
# folds a batch into every ring plane, ring row by ring row
# (`ops/segment_ops.ring_fold`, `TpuKeyedStateBackend.fold_rings`), and
# skips the rows a batch does not touch. The two jobs in which a skipped
# ring row or a plane reference held across a fold would show: batches
# whose event time is shuffled over more ring rows than two, and a
# checkpoint taken between two batches and restored. Both against a
# per-record reference.
# ---------------------------------------------------------------------------

FOLD_SCHEMA = Schema([("k", np.int64), ("v", np.int64), ("ts", np.int64)])
N, KEYS, PANE, PANES, WINDOW = 1 << 13, 61, 1000, 16, 4
#: the whole stream is 16 + 4 panes and a window 4: no watermark cadence,
#: however slow, lets two open panes share a ring row
RING = 32
#: how far a record's event time is shuffled ahead of its place in the
#: stream, and the out-of-orderness the watermark allows: four panes
JITTER = 4 * PANE

#: Q5's shape (a count that fits 32 bits beside an int64 sum) and Q7's (an
#: int64 max over the hidden int64 count)
AGGS = {
    "count32_sum64": [AggSpec("count", out_name="n", value_bits=31),
                      AggSpec("sum", "v", out_name="total")],
    "max64": [AggSpec("max", "v", out_name="best")],
}


def _shuffled_gen(idx):
    u = idx.astype(np.uint64) * np.uint64(0x9E3779B97F4A7C15)
    return {"k": ((u >> np.uint64(7)) % np.uint64(KEYS)).astype(np.int64),
            "v": ((u >> np.uint64(23)) % np.uint64(1 << 40)).astype(
                np.int64) + 1,
            "ts": (idx * PANES * PANE) // N
            + ((u >> np.uint64(13)) % np.uint64(JITTER)).astype(np.int64)}


def _fold_reference(names):
    """Per record: every window of HOP 4 s / 1 s that holds it."""
    cols = _shuffled_gen(np.arange(N))
    want = {}
    for k, v, ts in zip(*(cols[c].tolist() for c in ("k", "v", "ts"))):
        first_end = (ts // PANE + 1) * PANE
        for end in range(first_end, first_end + WINDOW * PANE, PANE):
            n, total, best = want.get((k, end), (0, 0, 0))
            want[(k, end)] = (n + 1, total + v, max(best, v))
    pick = [("n", "total", "best").index(c) for c in names]
    return {key: tuple(row[i] for i in pick) for key, row in want.items()}


class _Rows(SinkFunction):
    """Collects (key, window_end) -> aggregates; a replayed window has to
    repeat what it said."""

    def __init__(self, names, crash_once_checkpointed_in=None):
        self.names = names
        self.got = {}
        self.batches = 0
        self._dir = crash_once_checkpointed_in
        self.crashed = False

    def invoke_batch(self, batch):
        cols = [batch.column(c).tolist()
                for c in ("k", "window_end", *self.names)]
        for k, end, *row in zip(*cols):
            assert self.got.setdefault((k, end), tuple(row)) == tuple(row)
        self.batches += 1
        if self._dir is not None and not self.crashed:
            # leave the checkpoint coordinator its turns, and fail once a
            # checkpoint is complete: the restart restores it
            time.sleep(0.01)
            if self.batches >= 3 and glob.glob(
                    os.path.join(self._dir, "chk-*", "_metadata")):
                self.crashed = True
                raise RuntimeError("injected sink failure")
        return True


class _Pace(MapFunction):
    """Holds every batch back a little, so that a run of sixteen batches
    lasts long enough for checkpoints to fall between them."""

    def map_batch(self, batch):
        time.sleep(0.03)
        return batch


def _fold_job(env, aggs, defer, sink, paced=False):
    env.set_state_backend("tpu")
    env.config.set(PipelineOptions.BATCH_SIZE, 512)
    ws = WatermarkStrategy.for_bounded_out_of_orderness(JITTER) \
        .with_timestamp_column("ts")
    stream = env.datagen(_shuffled_gen, FOLD_SCHEMA, count=N, timestamp_column="ts",
                         watermark_strategy=ws)
    if paced:
        stream = stream.map(_Pace(), name="Pace", out_schema=FOLD_SCHEMA)
    (stream.key_by("k")
        .window(SlidingEventTimeWindows.of(WINDOW * PANE, PANE))
        .device_aggregate(aggs, capacity=1 << 10, ring_size=RING,
                          defer_overflow=defer, async_fire=defer)
        .add_sink(sink, "rows"))


@pytest.mark.parametrize("defer", [True, False],
                         ids=["deferred", "synchronous"])
@pytest.mark.parametrize("aggs", list(AGGS))
def test_batches_shuffled_over_many_ring_rows(aggs, defer, monkeypatch):
    """A batch of 512 rows is one pane of the stream shuffled over the
    four panes ahead of it, so its fold touches five ring rows, not one
    or two; the fold's own counter says so. The fold pays a batch per
    ring row whose updates lie scattered over it, so the operator hands
    such a batch over sorted by ring row."""
    names = [a.out_name for a in AGGS[aggs]]
    sink = _Rows(names)
    env = StreamExecutionEnvironment.get_execution_environment()
    _fold_job(env, AGGS[aggs], defer, sink)
    handed_over = []
    fold_rings = TpuKeyedStateBackend.fold_rings

    def spy(self, slots, ring_idx, valid, values):
        handed_over.append(np.asarray(ring_idx))
        return fold_rings(self, slots, ring_idx, valid, values)

    monkeypatch.setattr(TpuKeyedStateBackend, "fold_rings", spy)
    before = DEVICE_STATS.snapshot()
    env.execute(f"shuffled-{aggs}", timeout=300.0)
    after = DEVICE_STATS.snapshot()
    assert sink.got == _fold_reference(names)
    assert len(handed_over) == N // 512
    assert all((np.diff(r) >= 0).all() for r in handed_over
               if len(np.unique(r)) > 2)
    batches = after["fold_batches_total"] - before["fold_batches_total"]
    rows = after["fold_ring_rows_total"] - before["fold_ring_rows_total"]
    assert batches == N // 512
    assert rows > 4 * batches


#: X's batch: 2^18 rows, so the additive fold cuts a value into limbs of
#: 32 - 18 = 14 bits (`ops/segment_ops.ring_fold`)
LIMB_ROWS = 1 << 18


def _limb_gen(sign):
    """Two batches of 2^18 rows in event-time order over four panes (two
    ring rows a batch), prices under 2^22 as Q5's, of either sign."""
    def gen(idx):
        u = idx.astype(np.uint64) * np.uint64(0x9E3779B97F4A7C15)
        return {"k": ((u >> np.uint64(7)) % np.uint64(KEYS)).astype(np.int64),
                "v": sign * (((u >> np.uint64(23)) % np.uint64(1 << 22))
                             .astype(np.int64) + 1),
                "ts": (idx * 4 * PANE) // (2 * LIMB_ROWS)}
    return gen


@pytest.mark.parametrize("column, live_limbs", [("prices", 2),
                                                ("negative", 5)])
def test_a_q5_job_reports_the_limb_scatters_its_folds_ran(column,
                                                          live_limbs):
    """Q5 through `env.execute()` at X's batch size: the int64 SUM takes a
    batch limb by limb, and `DEVICE_STATS` `fold_limb_scatters_total`,
    counted by the fold program on the device, reads the live limbs a
    touched ring row: 2 for 22-bit prices (limbs of 14 bits), 5 where the
    column holds negative values (every high limb is set). The int32
    COUNT beside it folds with its one scatter and counts nothing."""
    gen = _limb_gen(1 if column == "prices" else -1)
    sink = _Rows(["n", "total"])
    env = StreamExecutionEnvironment.get_execution_environment()
    env.set_state_backend("tpu")
    env.config.set(PipelineOptions.BATCH_SIZE, LIMB_ROWS)
    ws = WatermarkStrategy.for_monotonous_timestamps() \
        .with_timestamp_column("ts")
    (env.datagen(gen, FOLD_SCHEMA, count=2 * LIMB_ROWS,
                 timestamp_column="ts", watermark_strategy=ws)
        .key_by("k")
        .window(SlidingEventTimeWindows.of(WINDOW * PANE, PANE))
        .device_aggregate(AGGS["count32_sum64"], capacity=1 << 10,
                          ring_size=RING, defer_overflow=True,
                          async_fire=True)
        .add_sink(sink, "rows"))
    before = DEVICE_STATS.snapshot()
    env.execute(f"limbs-{column}", timeout=300.0)
    grew = {k: v - before[k] for k, v in DEVICE_STATS.snapshot().items()
            if k.startswith("fold_")}
    assert grew["fold_batches_total"] == 2
    assert grew["fold_ring_rows_total"] == 4
    assert grew["fold_limb_scatters_total"] \
        == live_limbs * grew["fold_ring_rows_total"]
    # and the windows are the per-record sums, to the unit
    cols = gen(np.arange(2 * LIMB_ROWS))
    pane = cols["ts"] // PANE
    per_pane = np.zeros((2, KEYS, 4 + WINDOW), np.int64)
    np.add.at(per_pane[0], (cols["k"], pane), 1)
    np.add.at(per_pane[1], (cols["k"], pane), cols["v"])
    want = {}
    for last in range(4 + WINDOW - 1):              # window end = last + 1
        n, total = per_pane[:, :, max(0, last - WINDOW + 1):last + 1].sum(2)
        want.update({(k, (last + 1) * PANE): (int(n[k]), int(total[k]))
                     for k in range(KEYS) if n[k]})
    assert sink.got == want


@pytest.mark.parametrize("defer", [True, False],
                         ids=["deferred", "synchronous"])
def test_checkpoint_between_two_batches_restores_the_planes(tmp_path,
                                                            defer):
    """The sink fails once a checkpoint is complete; the job restarts
    from it and every window, replayed or new, is the reference's."""
    aggs = AGGS["count32_sum64"] + AGGS["max64"]
    names = [a.out_name for a in aggs]
    sink = _Rows(names, crash_once_checkpointed_in=str(tmp_path))
    env = StreamExecutionEnvironment()
    env.config.set(CheckpointingOptions.DIRECTORY, str(tmp_path))
    env.config.set(CheckpointingOptions.INTERVAL, 0.02)
    env.config.set(RuntimeOptions.RESTART_STRATEGY, "fixed-delay")
    env.config.set(RuntimeOptions.RESTART_ATTEMPTS, 3)
    env.config.set(RuntimeOptions.RESTART_DELAY, 0.02)
    _fold_job(env, aggs, defer, sink, paced=True)
    job = env.execute("checkpointed", timeout=300.0, recover=True)
    assert sink.crashed
    restart, = (h for h in job.supervisor.failure_history
                if h["kind"] == "restart")
    assert restart["restored_checkpoint"] is not None
    assert sink.got == _fold_reference(names)
