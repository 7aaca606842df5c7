"""Versioned checkpoint format (round 3, VERDICT r2 #9): metadata is a
tagged plain-structure encoding under a format-version magic, so the
on-disk format survives refactors of the framework's classes — the
TypeSerializerSnapshot / StatefulJobSnapshotMigrationITCase analog. The
committed fixture in tests/fixtures/checkpoint_v2 pins the format: if a
change breaks reading it, that change needs a new format version and a
legacy path, not a fixture update.
"""

import os
import pickle

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from flink_tpu.checkpoint.storage import (  # noqa: E402
    _COMPRESSED_MAGIC, _VERSIONED_MAGIC, CompletedCheckpoint,
    FsCheckpointStorage,
)
from flink_tpu.core import KeyGroupRange  # noqa: E402
from flink_tpu.state.tpu_backend import TpuKeyedStateBackend  # noqa: E402

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "checkpoint_v2")


class TestVersionedFormat:
    def test_metadata_is_versioned_and_class_pickle_free(self, tmp_path):
        """The stored metadata must not reference framework classes by
        module path (that is what made format v1 fragile)."""
        st = FsCheckpointStorage(str(tmp_path))
        b = TpuKeyedStateBackend(KeyGroupRange(0, 127), 128, capacity=256)
        b.register_array_state("acc", "sum", np.float64)
        keys = np.arange(50, dtype=np.int64)
        slots = b.slots_for_batch(keys)
        b.fold_batch("acc", slots, np.ones(50), slots >= 0)
        cp = st.store(CompletedCheckpoint(
            1, 0.0, {"t#0": {"keyed": b.snapshot(1)}}))
        raw = open(os.path.join(cp.external_path, "_metadata"),
                   "rb").read()
        assert raw.startswith(_VERSIONED_MAGIC)
        from flink_tpu.native import decompress
        blob = decompress(raw[len(_VERSIONED_MAGIC):])
        # no framework class paths inside the payload
        assert b"flink_tpu.checkpoint" not in blob
        assert b"CompletedCheckpoint" not in blob
        assert b"_PagedState" not in blob

    def test_roundtrip_preserves_everything(self, tmp_path):
        st = FsCheckpointStorage(str(tmp_path))
        snap = {"kind": "host", "rows": [(1, "a"), (2, "b")],
                "nested": {"t": (3, 4.5)}}
        cp = st.store(CompletedCheckpoint(
            7, 123.0, {"x#0": {"keyed": snap}},
            vertex_parallelism={"x": 2}, vertex_uids={"x": "u"}))
        loaded = st.load(cp.external_path)
        assert loaded.checkpoint_id == 7
        assert loaded.vertex_parallelism == {"x": 2}
        assert loaded.vertex_uids == {"x": "u"}
        got = loaded.task_snapshots["x#0"]["keyed"]
        assert got["rows"] == [(1, "a"), (2, "b")]
        assert got["nested"]["t"] == (3, 4.5)

    def test_reserved_tag_key_in_user_state_roundtrips(self, tmp_path):
        st = FsCheckpointStorage(str(tmp_path))
        tricky = {"__ftck__": "tuple", "items": [1, 2]}
        cp = st.store(CompletedCheckpoint(
            9, 0.0, {"t#0": {"keyed": {"user": tricky}}}))
        loaded = st.load(cp.external_path)
        assert loaded.task_snapshots["t#0"]["keyed"]["user"] == tricky

    def test_legacy_v1_class_pickle_still_loads(self, tmp_path):
        """Pre-upgrade checkpoints (FTCK compressed class-pickle) keep
        loading."""
        st = FsCheckpointStorage(str(tmp_path))
        cp = CompletedCheckpoint(3, 0.0, {"t#0": {"keyed": {"n": 1}}})
        d = os.path.join(str(tmp_path), "chk-3")
        os.makedirs(d)
        from flink_tpu.native import compress
        with open(os.path.join(d, "_metadata"), "wb") as f:
            f.write(_COMPRESSED_MAGIC)
            f.write(compress(pickle.dumps(cp)))
        loaded = st.load(d)
        assert loaded.task_snapshots["t#0"]["keyed"] == {"n": 1}


class TestCommittedFixtureMigration:
    """Restore the checkpoint committed at a fixed point in history
    (reference StatefulJobSnapshotMigrationITCase)."""

    def test_fixture_restores_exactly(self):
        st = FsCheckpointStorage(FIXTURE)
        cp = st.load(os.path.join(FIXTURE, "chk-1"))
        assert cp.checkpoint_id == 1
        assert cp.vertex_uids == {"v1": "uid-source", "v2": "uid-agg"}
        assert cp.vertex_parallelism == {"v1": 1, "v2": 1}
        v1 = cp.task_snapshots["v1#0"]
        assert v1["reader"] == 4242
        meta = v1["chain"]["op"]["keyed"]["meta"]
        assert meta == {"fired_boundary": 3, "min_seen_pane": 0,
                        "max_seen_pane": 2, "watermark": 2999}
        # device keyed state restores into a live backend with exact values
        b = TpuKeyedStateBackend(KeyGroupRange(0, 127), 128)
        b.restore([v1["chain"]["op"]["keyed"]["backend"]])
        from flink_tpu.ops.hash_table import EMPTY_KEY
        t = np.asarray(jax.device_get(b.table))
        occ = np.flatnonzero(t != np.int64(EMPTY_KEY))
        acc = np.asarray(jax.device_get(b.get_array("acc")))
        got = {int(t[s]): float(acc[int(t[s]) % 4, s]) for s in occ}
        assert got == {k: float(k % 7) for k in range(200)}
        # host-plane operator state (tuple keys, numpy values) intact
        ga = cp.task_snapshots["v2#0"]["chain"]["sum"]["keyed"]["backend"]
        entry = ga["group-agg"][5][(1, "x")]
        np.testing.assert_array_equal(entry, np.array([2.0, 9.0]))


# -- a 64-bit ring plane's stored layout is not a snapshot's format ---------

INT64_PLANES = os.path.join(os.path.dirname(__file__), "fixtures",
                            "int64_planes")


def _int64_planes_job():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "int64_planes_make", os.path.join(INT64_PLANES, "make.py"))
    make = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(make)
    return make


@pytest.fixture(scope="module")
def parent_snapshots():
    """What the parent of PR 42 wrote, whose backend kept a 64-bit ring
    plane as ONE int64 array: {flat name: array}, two snapshots."""
    with np.load(os.path.join(INT64_PLANES, "snapshots.npz")) as z:
        return {name: z[name] for name in z.files}


class TestInt64PlanesSnapshotCompatibility:
    """Since PR 42 the one-chip backend stores a pane-role ring plane of
    a 64-bit integer as its two 32-bit words (`ops/segment_ops.Halves`).
    That is a layout on the device, not a format: a snapshot holds int64
    arrays as it always did, byte for byte."""

    @pytest.mark.parametrize("between", ["nothing", "a_reclaim"])
    def test_todays_snapshots_are_byte_equal_to_the_parents(
            self, parent_snapshots, between):
        """The same job, run on the backend as it is now: a full snapshot
        and an incremental one (dirty blocks, a retired ring row replayed
        on the host) hold the parent's bytes, names, dtypes and shapes; a
        reclaim between the two moves every slot and none of the bytes."""
        make = _int64_planes_job()

        def reclaim(be):
            be.reclaim()

        snaps = make.run_job(TpuKeyedStateBackend,
                             reclaim if between == "a_reclaim" else None)
        for snap in snaps:
            for name, _kind, dtype in make.PLANES:
                st = snap["states"][name]
                assert (st["dtype"], st["ring"]) == (np.dtype(dtype).name,
                                                     make.RING)
        mine = make.flatten(snaps)
        assert list(mine) == list(parent_snapshots)
        for name, theirs in parent_snapshots.items():
            assert mine[name].dtype == theirs.dtype, name
            assert mine[name].shape == theirs.shape, name
            assert mine[name].tobytes() == theirs.tobytes(), name

    @pytest.mark.parametrize("which", [0, 1], ids=["full", "incremental"])
    def test_a_parents_snapshot_restores_into_halves(self, parent_snapshots,
                                                     which):
        """int64 arrays on the wire, two uint32 words a plane on the
        device: every key's every cell as the parent wrote it, and the
        snapshot taken straight back is the one that went in."""
        from flink_tpu.ops.hash_table import lookup
        from flink_tpu.ops.segment_ops import Halves

        make = _int64_planes_job()
        snap = {"kind": "tpu", "max_parallelism": 128,
                "keys": parent_snapshots[f"{which}/keys"],
                "key_groups": parent_snapshots[f"{which}/key_groups"],
                "states": {name: {
                    "kind": kind, "dtype": np.dtype(dtype).name,
                    "ring": make.RING,
                    "values": parent_snapshots[f"{which}/states/{name}"]}
                    for name, kind, dtype in make.PLANES}}
        b = TpuKeyedStateBackend(KeyGroupRange(0, 127), 128, capacity=512)
        b.restore([snap])
        slots = np.asarray(lookup(b.table, jax.numpy.asarray(snap["keys"])))
        assert (slots >= 0).all()
        for name, _kind, dtype in make.PLANES:
            plane = b.get_array(name)
            wide = np.dtype(dtype).itemsize == 8
            assert isinstance(plane, Halves) == wide, name
            if wide:
                assert plane.hi.dtype == plane.lo.dtype == np.uint32
                assert plane.hi.shape == plane.lo.shape == plane.shape
            assert (plane.dtype, plane.shape) == (np.dtype(dtype),
                                                  (make.RING, b.capacity))
            np.testing.assert_array_equal(
                np.asarray(plane)[:, slots], snap["states"][name]["values"])
        again = b.snapshot(3)
        assert again["keys"].tobytes() == snap["keys"].tobytes()
        for name, st in snap["states"].items():
            got = again["states"][name]
            assert got["values"].dtype == st["values"].dtype
            assert got["values"].tobytes() == st["values"].tobytes(), name

    def test_a_snapshot_of_halves_restores_into_a_smaller_ring(
            self, parent_snapshots):
        """`conform_ring` re-seats the live panes of a restored plane
        word by word."""
        make = _int64_planes_job()
        b = TpuKeyedStateBackend(KeyGroupRange(0, 127), 128)
        snap = make.run_job(TpuKeyedStateBackend)[1]
        b.restore([snap])
        b.conform_ring(3, [2, 3])
        plane = np.asarray(b.get_array("revenue"))
        assert plane.shape == (3, b.capacity)
        from flink_tpu.ops.hash_table import lookup
        slots = np.asarray(lookup(b.table, jax.numpy.asarray(snap["keys"])))
        vals = snap["states"]["revenue"]["values"]
        np.testing.assert_array_equal(plane[2 % 3, slots], vals[2])
        np.testing.assert_array_equal(plane[3 % 3, slots], vals[3])
        assert (plane[1] == 0).all()
