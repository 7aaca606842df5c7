"""Device state planes for SQL operators (round 3, VERDICT r2 #7):
typed row plane with TTL on the tpu backend (dedup keep-first runs as one
fused admission program per batch) and the HBM list plane (interval join
probes are one lookup+gather). Parity oracle = the same operators on the
host plane.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from flink_tpu.core import KeyGroupRange  # noqa: E402
from flink_tpu.core.config import Configuration, StateOptions  # noqa: E402
from flink_tpu.core.records import RecordBatch, Schema  # noqa: E402
from flink_tpu.runtime.harness import (  # noqa: E402
    OneInputOperatorTestHarness, TwoInputOperatorTestHarness,
)
from flink_tpu.sql.dedup import DeduplicateOperator  # noqa: E402
from flink_tpu.sql.join import IntervalJoinOperator  # noqa: E402
from flink_tpu.state.device_lists import DeviceListStore  # noqa: E402
from flink_tpu.state.tpu_backend import TpuKeyedStateBackend  # noqa: E402

SCHEMA = Schema([("k", np.int64), ("v", np.int64)])


def _cfg(backend):
    c = Configuration()
    c.set(StateOptions.BACKEND, backend)
    return c


class TestTypedRowPlane:
    @pytest.mark.parametrize("grow", [False, True])
    def test_typed_value_roundtrip_int64(self, grow):
        """``grow``: the table starts at 8 slots and 600 more keys arrive
        between the writes and the reads, so every plane is remapped by
        rehash several times over and the rows move to other slots."""
        b = TpuKeyedStateBackend(KeyGroupRange(0, 127), 128,
                                 capacity=8 if grow else 256)
        b.register_row_state("s", np.int64)
        b.register_row_state("seen", np.int8)
        keys = np.array([5, 9, 5, 7], np.int64)     # duplicate: last wins
        b.rows_upsert("s", keys, np.array([10, 20, 30, 1 << 40]))
        assert b.dedup_first_batch("seen", keys, np.zeros(4, np.int64)) \
            .tolist() == [True, True, False, True]
        if grow:
            more = np.arange(100, 700, dtype=np.int64)
            b.rows_upsert("s", more[:300], more[:300] * 2)
            assert b.dedup_first_batch(
                "seen", more[300:], np.zeros(300, np.int64)).all()
            assert b.capacity >= 1024
            vals, present = b.rows_lookup("s", more)
            assert present.tolist() == [True] * 300 + [False] * 300
            assert vals[:300].tolist() == (more[:300] * 2).tolist()
        assert not b.dedup_first_batch(
            "seen", np.array([7, 9], np.int64), np.ones(2, np.int64)).any()
        vals, present = b.rows_lookup("s", np.array([5, 7, 9, 11], np.int64))
        assert present.tolist() == [True, True, True, False]
        assert vals[:3].tolist() == [30, 1 << 40, 20]
        assert vals.dtype == np.int64
        b.rows_clear("s", np.array([7], np.int64))
        _v, p = b.rows_lookup("s", np.array([7, 5], np.int64))
        assert p.tolist() == [False, True]

    def test_ttl_expires_and_readmits(self):
        b = TpuKeyedStateBackend(KeyGroupRange(0, 127), 128, capacity=256)
        b.register_row_state("s", np.float64, ttl_ms=100)
        b.rows_upsert("s", np.array([1], np.int64), np.array([2.5]),
                      now_ms=1000)
        _v, p = b.rows_lookup("s", np.array([1], np.int64), now_ms=1050)
        assert p[0]
        _v, p = b.rows_lookup("s", np.array([1], np.int64), now_ms=1201)
        assert not p[0]

    def test_dedup_first_batch_semantics(self):
        b = TpuKeyedStateBackend(KeyGroupRange(0, 127), 128, capacity=256)
        b.register_row_state("seen", np.int8, ttl_ms=1000)
        # in-batch duplicates: only the first occurrence admits
        fresh = b.dedup_first_batch(
            "seen", np.array([1, 2, 1, 3, 2], np.int64),
            np.array([10, 10, 11, 12, 13], np.int64))
        assert fresh.tolist() == [True, True, False, True, False]
        # across batches: nothing re-admits inside the TTL
        fresh = b.dedup_first_batch(
            "seen", np.array([1, 4], np.int64),
            np.array([500, 500], np.int64))
        assert fresh.tolist() == [False, True]
        # after the TTL, the key re-admits
        fresh = b.dedup_first_batch(
            "seen", np.array([1], np.int64), np.array([1500], np.int64))
        assert fresh.tolist() == [True]

    def test_dedup_first_grows_table(self):
        b = TpuKeyedStateBackend(KeyGroupRange(0, 127), 128, capacity=64)
        b.register_row_state("seen", np.int8)
        keys = np.arange(500, dtype=np.int64)
        fresh = b.dedup_first_batch("seen", keys,
                                    np.zeros(500, np.int64))
        assert fresh.all()
        assert b.capacity >= 512
        again = b.dedup_first_batch("seen", keys, np.ones(500, np.int64))
        assert not again.any()


class TestDeviceDedupOperator:
    def _run(self, backend, rows, ts, keep="first", ttl_ms=None):
        op = DeduplicateOperator(0, keep=keep, ttl_ms=ttl_ms)
        h = OneInputOperatorTestHarness(op, schema=SCHEMA,
                                        config=_cfg(backend))
        for lo in range(0, len(rows), 7):
            h.process_elements(rows[lo:lo + 7], ts[lo:lo + 7])
        return [tuple(r) for r in h.get_output()], op

    def test_keep_first_parity_and_device_routing(self):
        rng = np.random.default_rng(5)
        rows = [(int(k), i) for i, k in
                enumerate(rng.integers(0, 40, 300))]
        ts = list(range(300))
        dev, op_d = self._run("tpu", rows, ts)
        host, op_h = self._run("hashmap", rows, ts)
        assert dev == host and len(dev) <= 40
        assert op_d._backend is not None     # really ran on device
        assert op_h._backend is None

    def test_keep_first_ttl_parity(self):
        # rows fed one per batch: TTL re-admission is evaluated against
        # STATE (device TTL is batch-granular — duplicates within a single
        # micro-batch always deduplicate, which a per-record feed sidesteps)
        rows = [(1, 0), (1, 1), (2, 2), (1, 3), (2, 4)]
        ts = [0, 50, 60, 500, 520]

        def run(backend):
            op = DeduplicateOperator(0, keep="first", ttl_ms=200)
            h = OneInputOperatorTestHarness(op, schema=SCHEMA,
                                            config=_cfg(backend))
            for r, t in zip(rows, ts):
                h.process_element(r, t)
            return [tuple(x) for x in h.get_output()]

        dev = run("tpu")
        host = run("hashmap")
        assert dev == host == [(1, 0), (2, 2), (1, 3), (2, 4)]

    def test_device_dedup_checkpoint_restore(self):
        rows = [(int(k), int(k)) for k in range(50)]
        op1 = DeduplicateOperator(0)
        h1 = OneInputOperatorTestHarness(op1, schema=SCHEMA,
                                         config=_cfg("tpu"))
        h1.process_elements(rows, list(range(50)))
        snap = op1.snapshot_state(1)
        assert snap["keyed"]["backend"].get("kind") == "tpu"

        op2 = DeduplicateOperator(0)
        h2 = OneInputOperatorTestHarness(op2, schema=SCHEMA,
                                         config=_cfg("tpu"))
        h2.open(keyed_snapshots=[snap["keyed"]])
        h2.process_elements(rows + [(99, 99)], list(range(51)))
        out = [tuple(r) for r in h2.get_output()]
        assert out == [(99, 99)]  # everything else already seen


L_SCHEMA = Schema([("k", np.int64), ("a", np.int64)])
R_SCHEMA = Schema([("k", np.int64), ("b", np.float64)])
OUT_SCHEMA = Schema([("lk", np.int64), ("a", np.int64),
                     ("rk", np.int64), ("b", np.float64)])


class TestDeviceIntervalJoin:
    def _drive(self, backend, left, right, lower=-100, upper=100,
               interleave=True, prune_at=None):
        op = IntervalJoinOperator(0, 0, lower, upper, OUT_SCHEMA,
                                  rows_per_key=64)
        h = TwoInputOperatorTestHarness(op, schema1=L_SCHEMA,
                                        schema2=R_SCHEMA,
                                        config=_cfg(backend))
        seq = []
        for i, (row, ts) in enumerate(left):
            seq.append((1, row, ts))
        for i, (row, ts) in enumerate(right):
            seq.append((2, row, ts))
        if interleave:
            seq.sort(key=lambda e: (e[2], e[0]))
        for side, row, ts in seq:
            if side == 1:
                h.process_element1(row, ts)
            else:
                h.process_element2(row, ts)
            if prune_at is not None and ts >= prune_at:
                h.process_watermark1(ts)
                h.process_watermark2(ts)
                prune_at = None
        return sorted(tuple(r) for r in h.get_output()), op

    def _data(self, seed=3, n=200, n_keys=20):
        rng = np.random.default_rng(seed)
        left = [((int(k), int(a)), int(t)) for k, a, t in
                zip(rng.integers(0, n_keys, n), rng.integers(0, 100, n),
                    np.sort(rng.integers(0, 2000, n)))]
        right = [((int(k), float(b)), int(t)) for k, b, t in
                 zip(rng.integers(0, n_keys, n),
                     rng.random(n) * 10,
                     np.sort(rng.integers(0, 2000, n)))]
        return left, right

    def test_parity_device_vs_host(self):
        left, right = self._data()
        dev, op_d = self._drive("tpu", left, right)
        host, op_h = self._drive("hashmap", left, right)
        assert dev == host and len(dev) > 50
        assert op_d._stores[0] is not None   # really ran on device
        assert op_h._stores[0] is None

    def test_parity_with_pruning_watermarks(self):
        left, right = self._data(seed=8)
        dev, _ = self._drive("tpu", left, right, prune_at=1000)
        host, _ = self._drive("hashmap", left, right, prune_at=1000)
        assert dev == host

    def test_device_join_checkpoint_restore(self):
        left, right = self._data(seed=11, n=100)
        # full run oracle
        full, _ = self._drive("tpu", left, right, interleave=False)
        # split run with snapshot/restore between the halves
        op1 = IntervalJoinOperator(0, 0, -100, 100, OUT_SCHEMA,
                                   rows_per_key=64)
        h1 = TwoInputOperatorTestHarness(op1, schema1=L_SCHEMA,
                                         schema2=R_SCHEMA,
                                         config=_cfg("tpu"))
        for row, ts in left:
            h1.process_element1(row, ts)
        snap = op1.snapshot_state(1)
        op2 = IntervalJoinOperator(0, 0, -100, 100, OUT_SCHEMA,
                                   rows_per_key=64)
        h2 = TwoInputOperatorTestHarness(op2, schema1=L_SCHEMA,
                                         schema2=R_SCHEMA,
                                         config=_cfg("tpu"))
        h2.open(keyed_snapshots=[snap["keyed"]])
        for row, ts in right:
            h2.process_element2(row, ts)
        got = sorted(tuple(r) for r in h2.get_output())
        assert got == full


class TestDeviceListStore:
    def test_append_probe_roundtrip_with_in_batch_duplicates(self):
        st = DeviceListStore(KeyGroupRange(0, 127), 128,
                             [np.dtype(np.int64), np.dtype(np.float64)],
                             capacity=64, rows_per_key=8)
        keys = np.array([3, 3, 4, 3], np.int64)
        st.append_batch(keys, np.array([10, 11, 12, 13], np.int64),
                        [np.array([1, 2, 3, 4], np.int64),
                         np.array([0.5, 1.5, 2.5, 3.5])])
        rows, counts = st.probe_batch(np.array([3, 4, 9], np.int64))
        assert counts.tolist() == [3, 1, 0]
        assert rows[0, :3, 0].tolist() == [10, 11, 13]   # insertion order
        assert st._unpack_col(rows[0, :3], 1).tolist() == [0.5, 1.5, 3.5]

    def test_prune_compacts(self):
        st = DeviceListStore(KeyGroupRange(0, 127), 128,
                             [np.dtype(np.int64)], capacity=64,
                             rows_per_key=8)
        st.append_batch(np.array([1] * 5, np.int64),
                        np.array([10, 20, 30, 40, 50], np.int64),
                        [np.arange(5, dtype=np.int64)])
        st.prune(30)
        rows, counts = st.probe_batch(np.array([1], np.int64))
        assert counts[0] == 3
        assert rows[0, :3, 0].tolist() == [30, 40, 50]

    def test_overflow_fails_loudly(self):
        st = DeviceListStore(KeyGroupRange(0, 127), 128,
                             [np.dtype(np.int64)], capacity=64,
                             rows_per_key=4)
        with pytest.raises(RuntimeError, match="list overflow"):
            st.append_batch(np.array([1] * 5, np.int64),
                            np.arange(5, dtype=np.int64),
                            [np.arange(5, dtype=np.int64)])

    def test_rehash_growth_preserves_lists(self):
        st = DeviceListStore(KeyGroupRange(0, 127), 128,
                             [np.dtype(np.int64)], capacity=64,
                             rows_per_key=4)
        keys = np.arange(200, dtype=np.int64)
        st.append_batch(keys, keys * 10, [keys * 100])
        assert st.capacity >= 256
        rows, counts = st.probe_batch(np.array([7, 150], np.int64))
        assert counts.tolist() == [1, 1]
        assert rows[0, 0].tolist() == [70, 700]
        assert rows[1, 0].tolist() == [1500, 15000]


class TestDeviceStateLifecycle:
    """Review-found lifecycle holes: checkpoints before the first batch,
    TTL upgrades over no-TTL snapshots, host->device plane migration."""

    def test_checkpoint_before_first_batch_keeps_restored_dedup_state(self):
        rows = [(int(k), int(k)) for k in range(30)]
        op1 = DeduplicateOperator(0)
        h1 = OneInputOperatorTestHarness(op1, schema=SCHEMA,
                                         config=_cfg("tpu"))
        h1.process_elements(rows, list(range(30)))
        snap1 = op1.snapshot_state(1)

        # restore, snapshot again WITHOUT processing anything
        op2 = DeduplicateOperator(0)
        h2 = OneInputOperatorTestHarness(op2, schema=SCHEMA,
                                         config=_cfg("tpu"))
        h2.open(keyed_snapshots=[snap1["keyed"]])
        snap2 = op2.snapshot_state(2)
        assert len(snap2["keyed"]["backend"]["keys"]) == 30

        op3 = DeduplicateOperator(0)
        h3 = OneInputOperatorTestHarness(op3, schema=SCHEMA,
                                         config=_cfg("tpu"))
        h3.open(keyed_snapshots=[snap2["keyed"]])
        h3.process_elements(rows, list(range(30)))
        assert h3.get_output() == []     # all still deduplicated

    def test_ttl_upgrade_over_no_ttl_snapshot(self):
        rows = [(int(k), int(k)) for k in range(10)]
        op1 = DeduplicateOperator(0)    # no TTL
        h1 = OneInputOperatorTestHarness(op1, schema=SCHEMA,
                                         config=_cfg("tpu"))
        h1.process_elements(rows, list(range(10)))
        snap = op1.snapshot_state(1)

        op2 = DeduplicateOperator(0, ttl_ms=100)   # TTL enabled on restore
        h2 = OneInputOperatorTestHarness(op2, schema=SCHEMA,
                                         config=_cfg("tpu"))
        h2.open(keyed_snapshots=[snap["keyed"]])
        # pre-TTL entries never expire (conservative upgrade: no duplicate
        # re-emission); new keys honor the TTL
        h2.process_elements(rows + [(50, 50)], [10**6] * 11)
        assert [tuple(r) for r in h2.get_output()] == [(50, 50)]

    def test_host_to_device_migration(self):
        rows = [(int(k), int(k)) for k in range(20)]
        op1 = DeduplicateOperator(0)
        h1 = OneInputOperatorTestHarness(op1, schema=SCHEMA,
                                         config=_cfg("hashmap"))
        h1.process_elements(rows, list(range(20)))
        snap = op1.snapshot_state(1)
        assert "dedup2" in snap["keyed"]["backend"]

        op2 = DeduplicateOperator(0)
        h2 = OneInputOperatorTestHarness(op2, schema=SCHEMA,
                                         config=_cfg("tpu"))
        h2.open(keyed_snapshots=[snap["keyed"]])
        h2.process_elements(rows + [(77, 77)], list(range(21)))
        out = [tuple(r) for r in h2.get_output()]
        assert out == [(77, 77)]
        assert op2._backend is not None  # migrated onto the device plane

    def test_join_checkpoint_before_first_batch_keeps_state(self):
        left, right = TestDeviceIntervalJoin()._data(seed=13, n=60)
        op1 = IntervalJoinOperator(0, 0, -100, 100, OUT_SCHEMA)
        h1 = TwoInputOperatorTestHarness(op1, schema1=L_SCHEMA,
                                         schema2=R_SCHEMA,
                                         config=_cfg("tpu"))
        for row, ts in left:
            h1.process_element1(row, ts)
        snap1 = op1.snapshot_state(1)

        op2 = IntervalJoinOperator(0, 0, -100, 100, OUT_SCHEMA)
        h2 = TwoInputOperatorTestHarness(op2, schema1=L_SCHEMA,
                                         schema2=R_SCHEMA,
                                         config=_cfg("tpu"))
        h2.open(keyed_snapshots=[snap1["keyed"]])
        snap2 = op2.snapshot_state(2)   # before ANY batch
        assert snap2["keyed"]["backend"]["list-left"] is not None
        assert len(snap2["keyed"]["backend"]["list-left"]["keys"]) > 0

        op3 = IntervalJoinOperator(0, 0, -100, 100, OUT_SCHEMA)
        h3 = TwoInputOperatorTestHarness(op3, schema1=L_SCHEMA,
                                         schema2=R_SCHEMA,
                                         config=_cfg("tpu"))
        h3.open(keyed_snapshots=[snap2["keyed"]])
        for row, ts in right:
            h3.process_element2(row, ts)
        assert len(h3.get_output()) > 0   # buffered left rows still join
