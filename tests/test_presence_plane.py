"""The hidden plane of a job that reads no count (PR 49): a window job
with no COUNT and no AVG keeps ``__count__`` as a 32-bit PRESENCE plane
(``ops/segment_ops`` kind ``presence``: 1 where a record of the key fell
in the pane, folded as a saturating mark), on one chip and on the mesh;
a job that reads the count (a COUNT emits it, an AVG divides by it) keeps
the count plane it had, bit for bit.

Held here, against a per-record reference: MAX / MIN / SUM-only jobs on
both stacks over a stream with a key whose only value IS the aggregate's
identity, a key that is only ever negative, a pane no record fell in,
windows that span several ring rows and wrap the ring; the host tier and
the deferred-spill replay; that no number of records of one key in one
pane can hide its window (where an int32 COUNT's add would wrap); the
parent's int64 plane in a savepoint restoring into such a job; and the
form of the plane on ``window/Drain`` and in ``DEVICE_STATS``.

Integer values throughout: every comparison is ``==`` on raw tuples."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from flink_tpu.core.records import Schema  # noqa: E402
from flink_tpu.metrics import DEVICE_STATS  # noqa: E402
from flink_tpu.metrics.tracing import TRACER  # noqa: E402
from flink_tpu.ops.hash_table import ensure_x64  # noqa: E402
from flink_tpu.ops.segment_ops import (  # noqa: E402
    AGG_MERGES, Halves, ring_fold,
)
from flink_tpu.runtime import OneInputOperatorTestHarness  # noqa: E402
from flink_tpu.runtime.operators.device_window import (  # noqa: E402
    AggSpec, DeviceWindowAggOperator,
)
from flink_tpu.runtime.operators.mesh_window import (  # noqa: E402
    MeshWindowAggOperator,
)
from flink_tpu.window import SlidingEventTimeWindows  # noqa: E402

ensure_x64()
pytestmark = pytest.mark.perf

SCHEMA = Schema([("k", np.int64), ("v", np.int64)])
PANE, RING, PANES = 1000, 8, 20
EMPTY_PANE = 5           # no record falls in it
I64 = np.iinfo(np.int64)
#: a value that IS the aggregate's identity (so "the plane still holds the
#: identity" must not be read as "no data": what the presence plane is for)
IDENTITY = {"max": I64.min, "min": I64.max, "sum": 0}
FOLD = {"max": max, "min": min, "sum": sum}
ON_IDENTITY, NEGATIVE = 100, 101

STACKS = pytest.mark.parametrize("stack", ["one_chip", "mesh"])
KINDS = pytest.mark.parametrize("kind", ["max", "min", "sum"])


def _op(stack: str, aggs, window_panes: int, **kw):
    assigner = SlidingEventTimeWindows.of(window_panes * PANE, PANE)
    if stack == "mesh":
        return MeshWindowAggOperator(assigner, "k", list(aggs), n_devices=4,
                                     capacity=64, ring_size=RING,
                                     device_batch=8, **kw)
    kw.setdefault("capacity", 128)
    return DeviceWindowAggOperator(assigner, "k", list(aggs),
                                   ring_size=RING, **kw)


def _stream(kind: str, seed: int = 49, keys: int = 9) -> list:
    """A step a pane over enough panes to wrap the ring twice, a watermark
    behind every step: [(keys, values, timestamps, watermark)]. Pane
    ``EMPTY_PANE`` holds nothing; key ``ON_IDENTITY`` has one record ever,
    whose value is the aggregate's identity; key ``NEGATIVE`` only ever
    has negative values."""
    rng = np.random.default_rng(seed)
    out = []
    for pane in range(PANES):
        t = pane * PANE
        n = 0 if pane == EMPTY_PANE else int(rng.integers(2, 20))
        ks = rng.integers(0, keys, n)
        vs = rng.integers(-50, 50, n)
        if pane in (2, 3, 11):
            ks = np.append(ks, NEGATIVE)
            vs = np.append(vs, -7 - pane)
        if pane == 9:
            ks = np.append(ks, ON_IDENTITY)
            vs = np.append(vs, IDENTITY[kind])
        ts = np.sort(rng.integers(t, t + PANE, len(ks)))
        out.append((ks.astype(np.int64), vs.astype(np.int64), ts,
                    t + PANE - 1))
    return out + [(np.zeros(0, np.int64),) * 3 + (10 ** 9,)]


def _feed(h, steps) -> None:
    for ks, vs, ts, wm in steps:
        h.process_elements(list(zip(ks.tolist(), vs.tolist())), ts.tolist())
        h.process_watermark(wm)


def _rows(*harnesses) -> list:
    return sorted(tuple(int(x) for x in r)
                  for h in harnesses for r in h.get_output())


def _reference(steps, window_panes: int, kinds) -> list:
    """Record by record (no record of these streams is late): every
    sliding window a record's pane belongs to, a row a (window, key)
    that holds at least one record, and none besides."""
    windows = {}
    for ks, vs, ts, _wm in steps:
        for k, v, t in zip(ks.tolist(), vs.tolist(), ts.tolist()):
            for end in range(t // PANE + 1, t // PANE + 1 + window_panes):
                windows.setdefault((end, k), []).append(v)
    return sorted((k, (end - window_panes) * PANE, end * PANE)
                  + tuple(FOLD[kind](vals) for kind in kinds)
                  for (end, k), vals in windows.items())


def _count_plane(op):
    """(kind, numpy plane, whether it is stored as words) of the
    operator's hidden plane, whichever stack."""
    if isinstance(op, MeshWindowAggOperator):
        kind = next(a.kind for a in op._agg.aggs if a.name == "__count__")
        plane = op._state.accs["__count__"]
    else:
        kind = op._backend.array_kind("__count__")
        plane = op._backend.get_array("__count__")
    return kind, np.asarray(plane), isinstance(plane, Halves)


def _assert_presence(op) -> None:
    kind, plane, halves = _count_plane(op)
    assert (kind, plane.dtype, halves) == ("presence", np.int32, False)
    assert set(np.unique(plane).tolist()) <= {0, 1}
    assert op._count_form() == "presence32"


# -- (a) results -----------------------------------------------------------

@STACKS
@KINDS
@pytest.mark.parametrize("window_panes", [1, 3, RING - 1],
                         ids=["tumbling", "hop3", "widest"])
def test_a_job_that_reads_no_count_equals_the_reference(stack, kind,
                                                        window_panes):
    """One aggregate, no COUNT: every (window, key) that holds a record
    is emitted with the reference's value, the key whose only value is
    the identity and the negative-only key among them, and nothing is
    emitted for the windows that hold only the empty pane."""
    steps = _stream(kind)
    h = OneInputOperatorTestHarness(
        _op(stack, [AggSpec(kind, "v", dtype=jnp.int64)], window_panes),
        schema=SCHEMA)
    _feed(h, steps[:PANES // 2])
    _assert_presence(h.operator)
    _feed(h, steps[PANES // 2:])
    h.close()
    want = _reference(steps, window_panes, (kind,))
    got = _rows(h)
    assert got == want
    assert (ON_IDENTITY, (10 - window_panes) * PANE, 10 * PANE,
            IDENTITY[kind]) in got
    assert any(r[0] == NEGATIVE and r[3] < 0 for r in got)
    if window_panes == 1:
        assert not any(r[2] == (EMPTY_PANE + 1) * PANE for r in got)
    assert h.operator.late_dropped == 0


@STACKS
def test_two_aggregates_and_topk_rank_without_a_count(stack):
    """MAX ranked top-2 beside a MIN gathered at the winners: the emit
    mask the select ranks under comes from the presence plane."""
    steps = _stream("max")
    h = OneInputOperatorTestHarness(
        _op(stack, [AggSpec("max", "v", out_name="hi", dtype=jnp.int64),
                    AggSpec("min", "v", out_name="lo", dtype=jnp.int64)],
            2, emit_topk=2), schema=SCHEMA)
    _feed(h, steps)
    h.close()
    _assert_presence(h.operator)
    want = {}
    for row in _reference(steps, 2, ("max", "min")):
        want.setdefault(row[2], []).append(row)
    got = _rows(h)
    assert {r[2] for r in got} == set(want)
    for end, rows in want.items():
        mine = [r for r in got if r[2] == end]
        assert len(mine) == min(2, len(rows)) and set(mine) <= set(rows)
        assert min(r[3] for r in mine) >= max(
            [r[3] for r in rows if r not in mine], default=I64.min)


@KINDS
@pytest.mark.parametrize("path", ["sync", "deferred"])
def test_the_host_tier_and_the_deferred_spill_replay(kind, path):
    """Beyond the HBM budget on one chip: evicted key groups fold on the
    host tier (a saturating mark there too), the deferred path stages
    their rows on the device and replays them with a one a row; fires
    merge both tiers. 600 keys against 64 resident slots."""
    rng = np.random.default_rng(3)
    steps = []
    for pane in range(8):
        n = 300
        ks = rng.integers(0, 600, n).astype(np.int64)
        vs = rng.integers(-1000, 1000, n).astype(np.int64)
        if pane == 4:
            ks, vs = np.append(ks, 10_000), np.append(vs, IDENTITY[kind])
        ts = np.sort(rng.integers(pane * PANE, (pane + 1) * PANE, len(ks)))
        steps.append((ks, vs, ts, (pane + 1) * PANE - 1))
    steps.append((np.zeros(0, np.int64),) * 3 + (10 ** 9,))
    deferred = path == "deferred"
    op = _op("one_chip", [AggSpec(kind, "v", dtype=jnp.int64)], 2,
             capacity=64, hbm_budget_slots=64, defer_overflow=deferred,
             async_fire=deferred)
    h = OneInputOperatorTestHarness(op, schema=SCHEMA)
    _feed(h, steps)
    h.close()
    assert _rows(h) == _reference(steps, 2, (kind,))
    _assert_presence(op)
    tier = op._backend.host_tier
    assert op._backend.spill_active and tier.evicted_keys > 0
    host = tier.arrays["__count__"]
    assert (host.kind, host.dtype) == ("presence", np.int32)
    assert set(np.unique(host.array).tolist()) <= {0, 1}
    assert tier.host_folds > 0


# -- (b) no volume of records can hide a window ------------------------------

def test_the_presence_fold_saturates_where_an_int32_count_wraps():
    """The fold itself: a presence cell at the largest value a fold can
    leave there (1), folded into once more, stays positive; so would one
    forced to INT32_MAX. The int32 COUNT form (a declared COUNT promises
    its own 31 bits; a hidden plane has nobody to promise) from 2^31 - 1
    wraps negative, and the fire's ``merge > 0`` would drop the window."""
    ring, cap = 2, 8
    slots = jnp.asarray([3, 3, 3, 5], jnp.int32)
    rows = jnp.zeros(4, jnp.int32)
    valid = jnp.asarray([True, True, True, False])
    ones = jnp.ones(4, jnp.int32)
    top = np.iinfo(np.int32).max

    def folded(kind, at):
        plane = jnp.zeros((ring, cap), jnp.int32).at[0, 3].set(at)
        plane = ring_fold(kind, plane, rows, slots, ones, valid)
        merged = AGG_MERGES[kind](plane, axis=0)
        return np.asarray(plane), np.asarray(merged > 0)

    for at in (0, 1, top):
        plane, emits = folded("presence", at)
        assert plane[0, 3] == max(at, 1) and emits[3]
        assert plane[0, 5] == 0 and not emits[5]     # the masked row
        assert plane.sum() == max(at, 1)
    plane, emits = folded("count", top)
    assert plane[0, 3] < 0 and not emits[3]          # the window is lost


@STACKS
def test_a_window_emits_whatever_its_keys_record_count(stack):
    """A hot key's thousands of records in one pane, over several
    batches, leave its cell at 1, the largest value it can hold; folding
    once more changes nothing, and the window emits with the right MAX."""
    h = OneInputOperatorTestHarness(
        _op(stack, [AggSpec("max", "v", dtype=jnp.int64)], 1),
        schema=SCHEMA)
    hot = 7
    for lot in range(4):
        n = 2000
        h.process_elements([(hot, lot * n + i) for i in range(n)]
                           + [(lot, -lot)], [10 + lot] * (n + 1))
        if stack == "mesh":
            h.operator._flush(pad=True)
        _kind, plane, _h = _count_plane(h.operator)
        assert plane.max() == 1 and plane.sum() == lot + 2
    h.process_watermark(10 ** 9)
    h.close()
    assert _rows(h) == [(0, 0, PANE, 0), (1, 0, PANE, -1), (2, 0, PANE, -2),
                        (3, 0, PANE, -3), (hot, 0, PANE, 7999)]


# -- (c) jobs that read the count keep the plane they had ---------------------

COUNT_JOBS = pytest.mark.parametrize("aggs, one_chip, mesh", [
    ([("count", None, None)], "count64", "count64"),
    ([("count", None, 31)], "count32", "count64"),
    ([("avg", "v", None)], "count64", "count64"),
    ([("max", "v", None), ("count", None, 31)], "count32", "count64"),
    ([("sum", "v", None), ("avg", "v", None)], "count64", "count64"),
], ids=["count", "count31", "avg", "max+count31", "sum+avg"])


@STACKS
@COUNT_JOBS
def test_count_and_avg_jobs_keep_their_count_plane(stack, aggs, one_chip,
                                                   mesh):
    """A COUNT (int32 under a 31-bit promise on one chip, int64 without;
    always int64 on the mesh) and an AVG (the int64 count it divides by)
    fold a count as the parent did: the plane's kind and dtype, every
    emitted count and average, the form on every window/Drain and the
    operator's one tick of ``count_plane_<form>_total``."""
    specs = [AggSpec(kind, field, dtype=jnp.int64, value_bits=bits)
             for kind, field, bits in aggs]
    steps = _stream("sum")
    form = one_chip if stack == "one_chip" else mesh
    TRACER.reset()
    before = DEVICE_STATS.snapshot()
    h = OneInputOperatorTestHarness(_op(stack, specs, 3), schema=SCHEMA)
    _feed(h, steps)
    h.close()
    after = DEVICE_STATS.snapshot()
    drains = [s for s in TRACER.retained_spans()
              if (s.scope, s.name) == ("window", "Drain")]
    TRACER.reset()
    op = h.operator
    if stack == "mesh":
        plane = next(a for a in op._agg.aggs if a.kind == "count")
        assert not any(a.kind == "presence" for a in op._agg.aggs)
        kind, dtype = plane.kind, np.dtype(plane.dtype)
        cells = np.asarray(op._state.accs[plane.name])
    else:
        kind, cells, _h = _count_plane(op)
        dtype = cells.dtype
    assert (kind, f"count{8 * dtype.itemsize}") == ("count", form)
    assert cells.dtype == dtype
    windows = {}
    for ks, vs, ts, _wm in steps:
        for k, v, t in zip(ks.tolist(), vs.tolist(), ts.tolist()):
            for end in range(t // PANE + 1, t // PANE + 4):
                windows.setdefault((end, k), []).append(v)
    value = {"count": len, "max": max, "sum": sum,
             "avg": lambda vals: float(np.float32(sum(vals))
                                       / np.float32(len(vals)))}
    want = sorted((k, (end - 3) * PANE, end * PANE)
                  + tuple(value[kind](vals) for kind, _f, _b in aggs)
                  for (end, k), vals in windows.items())
    got = sorted(tuple(x.item() for x in r)
                 for b in h.output.batches if not hasattr(b, "timestamp")
                 for r in zip(*[b.column(f.name) for f in b.schema.fields]))
    assert got == want
    assert drains and {d.attributes["count_plane"] for d in drains} == {form}
    ticks = {f: after[f"count_plane_{f}_total"]
             - before[f"count_plane_{f}_total"]
             for f in ("presence32", "count32", "count64")}
    assert ticks == {f: int(f == form)
                     for f in ("presence32", "count32", "count64")}


@STACKS
def test_the_drain_and_the_counter_say_presence(stack):
    TRACER.reset()
    before = DEVICE_STATS.snapshot()["count_plane_presence32_total"]
    h = OneInputOperatorTestHarness(
        _op(stack, [AggSpec("min", "v", dtype=jnp.int64)], 2), schema=SCHEMA)
    _feed(h, _stream("min"))
    h.close()
    drains = [s for s in TRACER.retained_spans()
              if (s.scope, s.name) == ("window", "Drain")]
    TRACER.reset()
    assert len(drains) >= PANES
    assert {d.attributes["count_plane"] for d in drains} == {"presence32"}
    assert DEVICE_STATS.snapshot()["count_plane_presence32_total"] \
        == before + 1


# -- (d) snapshots ------------------------------------------------------------

def _max_job(stack, with_count: bool, **kw):
    aggs = [AggSpec("max", "v", out_name="best", dtype=jnp.int64)]
    if with_count:
        # the plane the PARENT kept for the MAX-only job: an int64 count
        # under the name ``__count__`` (one chip keeps a declared COUNT
        # there anyway; on the mesh the out_name names the plane)
        aggs.append(AggSpec("count", out_name="__count__", dtype=jnp.int64))
    return _op(stack, aggs, 3, **kw)


@STACKS
@pytest.mark.parametrize("async_fire", [False, True], ids=["sync", "async"])
def test_a_savepoint_with_the_parents_int64_count_restores_and_runs(
        stack, async_fire):
    """A savepoint written in the parent's layout (``__count__``: kind
    count, int64, the records counted) restores into the MAX-only job:
    the plane is rebuilt from the snapshot's own kind and dtype, keeps
    counting, and every window, those open across the savepoint among
    them, is the reference's."""
    steps = _stream("max")
    half = PANES // 2
    h1 = OneInputOperatorTestHarness(
        _max_job(stack, True, async_fire=async_fire), schema=SCHEMA)
    _feed(h1, steps[:half])
    snap = h1.snapshot(1)
    state = snap["keyed"]["backend"]["states"]["__count__"]
    assert (state["kind"], state["dtype"]) == ("count", "int64")
    assert state["values"].max() > 1
    h2 = OneInputOperatorTestHarness.restored(
        lambda: _max_job(stack, False, async_fire=async_fire), snap,
        schema=SCHEMA)
    _feed(h2, steps[half:])
    h2.close()
    kind, plane, halves = _count_plane(h2.operator)
    assert (kind, plane.dtype, halves) == ("count", np.int64, True)
    assert h2.operator._count_form() == "count64"
    want = _reference(steps, 3, ("max",))
    before = sorted(r[:4] for r in _rows(h1))       # (k, start, end, best)
    assert sorted(before + _rows(h2)) == want
    h1.close()


@STACKS
@pytest.mark.parametrize("async_fire", [False, True], ids=["sync", "async"])
def test_the_presence_plane_round_trips_a_snapshot(stack, async_fire):
    """Snapshot mid-stream and restore: the snapshot says ``presence`` /
    ``int32`` of the plane, holds marks and nothing else, and the
    restored job goes on to the reference's rows on a presence plane."""
    steps = _stream("max")
    half = PANES // 2
    h1 = OneInputOperatorTestHarness(
        _max_job(stack, False, async_fire=async_fire), schema=SCHEMA)
    _feed(h1, steps[:half])
    snap = h1.snapshot(1)
    state = snap["keyed"]["backend"]["states"]["__count__"]
    assert (state["kind"], state["dtype"], state["ring"]) \
        == ("presence", "int32", RING)
    assert state["values"].dtype == np.int32
    assert set(np.unique(state["values"]).tolist()) == {0, 1}
    h2 = OneInputOperatorTestHarness.restored(
        lambda: _max_job(stack, False, async_fire=async_fire), snap,
        schema=SCHEMA)
    _assert_presence(h2.operator)
    _feed(h2, steps[half:])
    h2.close()
    _assert_presence(h2.operator)
    assert sorted(_rows(h1) + _rows(h2)) == _reference(steps, 3, ("max",))
    h1.close()


def test_a_one_chip_presence_snapshot_restores_onto_the_mesh_and_back():
    """The two stacks read each other's snapshots: the plane's kind and
    dtype ride in the snapshot, so a re-shard keeps them."""
    steps = _stream("max")
    third = PANES // 3
    h1 = OneInputOperatorTestHarness(_max_job("one_chip", False),
                                     schema=SCHEMA)
    _feed(h1, steps[:third])
    h2 = OneInputOperatorTestHarness.restored(
        lambda: _max_job("mesh", False), h1.snapshot(1), schema=SCHEMA)
    _assert_presence(h2.operator)
    _feed(h2, steps[third:2 * third])
    assert h2.operator.rescale_live(2)["new_devices"] == 2
    _assert_presence(h2.operator)
    h3 = OneInputOperatorTestHarness.restored(
        lambda: _max_job("one_chip", False), h2.snapshot(2), schema=SCHEMA)
    _assert_presence(h3.operator)
    _feed(h3, steps[2 * third:])
    h3.close()
    assert sorted(_rows(h1) + _rows(h2) + _rows(h3)) \
        == _reference(steps, 3, ("max",))


def test_the_hbm_budget_counts_the_plane_at_its_own_width():
    """``state.backend.tpu.hbm-budget-bytes`` admits the slots the memory holds:
    a MAX-only job's slot is 8 B of key + ring x (4 + 8) B, a COUNT-less
    job's hidden plane no longer counted at 8 B a cell."""
    from flink_tpu.core.config import Configuration

    def budget(aggs) -> int:
        cfg = Configuration().set("state.backend.tpu.hbm-budget-bytes",
                                  1 << 20)
        h = OneInputOperatorTestHarness(_op("one_chip", aggs, 1),
                                        schema=SCHEMA, config=cfg)
        return h.operator._backend.hbm_budget

    def slots(cell_bytes: int) -> int:
        # the backend takes the largest power of two the bytes admit
        return 1 << (((1 << 20) // (8 + RING * cell_bytes)).bit_length() - 1)

    assert budget([AggSpec("max", "v")]) == slots(4 + 8) == 8192
    assert budget([AggSpec("max", "v"), AggSpec("count")]) \
        == slots(8 + 8) == 4096          # what the MAX-only job got before
    assert budget([AggSpec("count", value_bits=31)]) == slots(4) == 16384
    assert budget([AggSpec("avg", "v")]) == slots(8 + 8)
