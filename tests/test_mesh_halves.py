"""The mesh stack keeps a 64-bit integer ring plane as its two 32-bit
words (PR 44: ``ShardedWindowState.accs`` holds ``ops/segment_ops.Halves``,
as the one-chip backend's planes since PR 42), and nothing a user sees
moves: a mesh job with an int64 SUM that carries across 2^32 in both
directions, an int64 MAX over negative values and the hidden count,
beside a float32 MIN whose plane stays one array, gives the rows of the
per-record reference through step, fire (at the fixture's width and at
the widest window the ring holds), retire, reclaim, grow, live rescale
and snapshot -> restore;
its snapshots are the PARENT's byte for byte, and a snapshot the parent
wrote restores into the words. Runs on the virtual CPU devices of
``conftest.py``.
"""

import importlib.util
import os

import jax
import numpy as np
import pytest

from flink_tpu.core.records import RecordBatch
from flink_tpu.metrics import DEVICE_STATS
from flink_tpu.ops.hash_table import ensure_x64
from flink_tpu.ops.segment_ops import AGG_INITS, Halves
from flink_tpu.runtime import OneInputOperatorTestHarness

ensure_x64()

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "int64_planes_mesh")


def _load_make():
    spec = importlib.util.spec_from_file_location(
        "int64_planes_mesh_make", os.path.join(FIXTURE, "make.py"))
    make = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(make)
    return make


make = _load_make()
SCHEMA = make.schema()

#: the job's planes as the operator builds them NOW: the job declares no
#: COUNT and no AVG, so since PR 49 its hidden plane is a 32-bit presence
#: plane (1 where the parent's int64 count was positive), one array. The
#: parent's snapshots (``make.PLANES``) still restore, and keep the int64
#: count they were written with
PLANES_NOW = make.PLANES[:-1] + (("__count__", "presence", np.int32),)


@pytest.fixture(scope="module")
def parent_snapshots():
    """What the parent of PR 44 wrote, whose mesh state kept a 64-bit
    ring plane as ONE int64 array: {flat name: array}, two snapshots."""
    with np.load(os.path.join(FIXTURE, "snapshots.npz")) as z:
        return {name: z[name] for name in z.files}


def _rows(*harnesses) -> list:
    """(key, start, end, total, high, low) of everything emitted."""
    return sorted((int(k), int(s), int(e), int(t), int(hi), float(lo))
                  for h in harnesses for k, s, e, t, hi, lo in h.get_output())


#: the window's width in panes: the fixture's, and the widest its ring
#: holds (the fire then gathers all but one ring row of every word)
WIDTHS = pytest.mark.parametrize(
    "window_panes", [make.SIZE // make.PANE, make.RING - 1],
    ids=["hop4", "widest"])


def _reference(batches: list, size: int = make.SIZE) -> list:
    """Record by record: every sliding window's SUM, MAX and MIN a key."""
    cols = {n: np.concatenate([c[n] for c, _ts in batches])
            for n, _dt in make.FIELDS}
    ts = np.concatenate([t for _c, t in batches])
    out = []
    for end in range(make.PANE, int(ts.max()) + size + 1, make.PANE):
        sel = (ts >= end - size) & (ts < end)
        for k in np.unique(cols["key"][sel]).tolist():
            mine = sel & (cols["key"] == k)
            out.append((k, end - size, end, int(cols["v"][mine].sum()),
                        int(cols["w"][mine].max()),
                        float(cols["f"][mine].min())))
    return sorted(out)


def _assert_layout(op, planes=PLANES_NOW) -> None:
    """The planes of 64-bit integers as their words, sharded as a plane
    is; the float plane and the 32-bit presence plane one array each."""
    accs = op._state.accs
    assert set(accs) == {name for name, _k, _dt in planes}
    assert {a.name: a.kind for a in op._agg.aggs} \
        == {name: kind for name, kind, _dt in planes}
    shape = (op._n_devices, make.RING, op._agg.capacity)
    for name, _kind, dtype in planes:
        plane = accs[name]
        wide = np.dtype(dtype).itemsize == 8
        assert isinstance(plane, Halves) == wide, name
        assert (plane.shape, plane.dtype) == (shape, np.dtype(dtype)), name
        for word in jax.tree.leaves(plane):
            assert word.dtype == (np.uint32 if wide else dtype), name
            assert word.sharding.is_equivalent_to(
                op._agg.plan.state_sharding, word.ndim), name


def _finish(*harnesses) -> None:
    for h in harnesses:
        h.process_watermark(10**9)
        h.operator.finish()


@WIDTHS
@pytest.mark.parametrize("async_fire", [False, True], ids=["sync", "async"])
def test_step_fire_retire_and_reclaim_give_the_reference_rows(window_panes,
                                                              async_fire):
    """40 panes of advancing keys through 4 tables of 256 slots: every
    block steps, every pane fires, the oldest pane retires, the tables
    reclaim at their capacity; every window's rows are the reference's."""
    batches = make.batches(40)
    size = window_panes * make.PANE
    before = DEVICE_STATS.snapshot()
    h = OneInputOperatorTestHarness(
        make.make_op(async_fire=async_fire, size=size), schema=SCHEMA)
    make.feed(h, batches)
    _assert_layout(h.operator)
    _finish(h)
    after = DEVICE_STATS.snapshot()
    assert _rows(h) == _reference(batches, size)
    assert h.operator._agg.capacity == 1 << 8
    assert h.operator.late_dropped == 0
    sweeps = "state_reclaim_sweeps_total"
    assert after[sweeps] - before[sweeps] >= 2


def test_a_late_write_into_an_open_pane_is_in_every_window_still_open():
    """Out-of-order rows land in a pane whose first windows have fired:
    the fire reads the planes' words as they are, so every window still
    open holds them, exactly."""
    batches = make.batches(12, seed=7)
    # the third batch's rows again, two panes late but inside the
    # windows still open
    cols, ts = batches[2]
    batches.insert(5, (cols, ts + 2 * make.PANE))
    h = OneInputOperatorTestHarness(
        make.make_op(capacity=1 << 9), schema=SCHEMA)
    for cols, ts in batches:
        h.process_batch(RecordBatch(SCHEMA, cols, ts))
        h.process_watermark(int(ts.max()) - 3 * make.PANE)
    _finish(h)
    assert _rows(h) == _reference(batches)
    assert h.operator.late_dropped == 0


@WIDTHS
def test_grow_rescale_and_restore_keep_every_cell(window_panes):
    """The host's side of the layout: a growth, a live rescale 4 -> 2 -> 4
    and a restore onto another mesh size split the planes with numpy and
    put the words on their shards; the job goes on exactly."""
    batches = make.batches(30, seed=5)
    kw = dict(async_fire=True, size=window_panes * make.PANE)
    h1 = OneInputOperatorTestHarness(make.make_op(**kw), schema=SCHEMA)
    make.feed(h1, batches[:6])
    op = h1.operator
    op._grow(2 * op._agg.capacity)
    _assert_layout(op)
    make.feed(h1, batches[6:11], first=6)
    assert op.rescale_live(2)["new_devices"] == 2
    _assert_layout(op)
    make.feed(h1, batches[11:16], first=11)
    assert op.rescale_live(4)["new_devices"] == 4
    make.feed(h1, batches[16:21], first=16)
    snap = h1.snapshot(1)
    h2 = OneInputOperatorTestHarness.restored(
        lambda: make.make_op(2, capacity=1 << 10, **kw), snap, schema=SCHEMA)
    _assert_layout(h2.operator)
    make.feed(h2, batches[21:], first=21)
    _finish(h2)
    h1.operator.finish()
    assert _rows(h1, h2) == _reference(batches, kw["size"])
    assert h2.operator._n_devices == 2
    assert h2.operator.late_dropped == 0


def _assert_holds_the_open_panes(snap: dict, fed: list,
                                 window_panes: int) -> None:
    """A snapshot's cells, record by record: a key's cell in the ring row
    of a pane some window still to fire covers is that pane's SUM, MAX,
    MIN and count of the key's rows; every other cell is the identity."""
    meta, backend = snap["keyed"]["meta"], snap["keyed"]["backend"]
    cols = {n: np.concatenate([c[n] for c, _ts in fed])
            for n, _dt in make.FIELDS}
    pane_of = np.concatenate([t for _c, t in fed]) // make.PANE
    fold = {"total": ("v", np.sum), "high": ("w", np.max),
            "low": ("f", np.min), "__count__": ("key", lambda mine: 1)}
    keys = backend["keys"]
    first_open = meta["fired_boundary"] - window_panes
    for name, kind, dtype in PLANES_NOW:
        want = np.full((make.RING, len(keys)),
                       np.asarray(AGG_INITS[kind](np.dtype(dtype))))
        field, fn = fold[name]
        for pane in range(max(first_open, 0), int(pane_of.max()) + 1):
            for j, k in enumerate(keys.tolist()):
                mine = cols[field][(pane_of == pane) & (cols["key"] == k)]
                if len(mine):
                    want[pane % make.RING, j] = fn(mine)
        got = backend["states"][name]["values"]
        assert got.dtype == want.dtype and (got == want).all(), name


@WIDTHS
def test_todays_snapshots_are_byte_equal_to_the_parents(parent_snapshots,
                                                        window_panes):
    """The same job on the operator as it is now: both snapshots (the
    second across two reclaims) hold names, dtypes and shapes as the
    parent's did and, cell for cell, what the records say of the panes
    still open; at the width the parent ran, the parent's bytes. The
    stored layout is the device's, not a format. One plane differs, and
    says so in the snapshot's own ``kind`` / ``dtype``: the hidden plane
    of this COUNT-less job is a presence plane since PR 49, 1 in int32
    exactly where the parent's int64 count is positive."""
    _h, snaps = make.run_job(size=window_panes * make.PANE)
    for snap, fed in zip(snaps, make.CUTS):
        states = snap["keyed"]["backend"]["states"]
        assert {n: (st["kind"], st["dtype"], st["ring"])
                for n, st in states.items()} \
            == {n: (kind, str(np.dtype(dt)), make.RING)
                for n, kind, dt in PLANES_NOW}
        _assert_holds_the_open_panes(snap, make.batches(fed), window_panes)
    if window_panes * make.PANE != make.SIZE:
        return
    mine = make.flatten(snaps)
    written = {name: theirs for name, theirs in parent_snapshots.items()
               if name[0] in "01"}
    assert list(mine) == list(written)
    for name, theirs in written.items():
        if name.endswith("states/__count__"):
            assert theirs.dtype == np.int64 and (theirs >= 0).all()
            theirs = (theirs > 0).astype(np.int32)
        assert mine[name].dtype == theirs.dtype, name
        assert mine[name].shape == theirs.shape, name
        assert mine[name].tobytes() == theirs.tobytes(), name


@pytest.mark.parametrize("n_devices", [4, 2])
@pytest.mark.parametrize("which", [0, 1], ids=["first", "second"])
def test_a_parents_snapshot_restores_into_the_words(parent_snapshots, which,
                                                    n_devices):
    """int64 arrays on the wire, two uint32 words a plane on the devices.
    The snapshot taken straight back holds the keys that went in with the
    cells that went in, whatever the mesh size; on the writer's mesh size
    it is, byte for byte, the one the PARENT took straight after ITS
    restore of the same snapshot (a restore re-inserts the keys, so the
    order is the restored tables' on both sides). The job then goes on
    from it to the reference's rows."""
    snap = make.unflatten(parent_snapshots, which)
    h, again = make.restored_snapshot(
        snap, n_devices, capacity=1 << (8 if n_devices == 4 else 9))
    # the planes the snapshot holds, its int64 count among them
    _assert_layout(h.operator, make.PLANES)
    assert again["keyed"]["meta"] == snap["keyed"]["meta"]
    theirs, mine = snap["keyed"]["backend"], again["keyed"]["backend"]
    order = np.argsort(theirs["keys"], kind="stable")
    back = np.argsort(mine["keys"], kind="stable")
    assert (mine["keys"][back] == theirs["keys"][order]).all()
    for name, st in theirs["states"].items():
        got = mine["states"][name]["values"]
        assert got.dtype == st["values"].dtype, name
        assert got[:, back].tobytes() == st["values"][:, order].tobytes()
    if n_devices == make.D:
        for name, values in make.flatten([again]).items():
            parents = parent_snapshots[f"{2 + which}{name[1:]}"]
            assert values.dtype == parents.dtype, name
            assert values.tobytes() == parents.tobytes(), name
    # the rest of the stream on top of it: every window the writer had
    # not fired yet is the reference's
    fed = make.CUTS[which]
    batches = make.batches(fed + 8)
    make.feed(h, batches[fed:], first=fed)
    _finish(h)
    next_end = snap["keyed"]["meta"]["fired_boundary"] * make.PANE
    want = [r for r in _reference(batches) if r[2] >= next_end]
    assert _rows(h) == want and want
