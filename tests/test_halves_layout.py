"""The STORED layout of a 64-bit ring plane of the one-chip backend (PR 42).

A ring plane whose dtype is a 64-bit integer is kept on the
device as two ``uint32`` arrays of the plane's shape, its high and its low
words (``ops/segment_ops.Halves``), on every platform: the fold, the reset,
the fire, the device-born step and the reclaim take the
words and hand them back, and a 64-bit value exists only inside a program,
joined from the rows or cells it has sliced or gathered. Held here to
numpy's int64 arithmetic: the words themselves, every program that takes
them, the job through ``env.execute()``. What a snapshot holds is in
``tests/test_checkpoint_format.py``; what the v5e's compiler makes of the
programs, in ``tests/test_tpu_lowering.py``.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from flink_tpu.core import KeyGroupRange, Schema  # noqa: E402
from flink_tpu.core.device_records import DeviceRecordBatch  # noqa: E402
from flink_tpu.core.records import RecordBatch  # noqa: E402
from flink_tpu.ops.hash_table import EMPTY_KEY, ensure_x64  # noqa: E402
from flink_tpu.ops.segment_ops import (  # noqa: E402
    AGG_INITS, Halves, identity_words, make_plane, ring_fold, stores_halves,
)
from flink_tpu.runtime.harness import OneInputOperatorTestHarness  # noqa: E402
from flink_tpu.runtime.operators.device_window import (  # noqa: E402
    AggSpec, DeviceWindowAggOperator, _fire_program,
)
from flink_tpu.state.tpu_backend import (  # noqa: E402
    TpuKeyedStateBackend, reclaim_shard,
)
from flink_tpu.window import SlidingEventTimeWindows  # noqa: E402

ensure_x64()   # the regime every job runs in

I64 = np.iinfo(np.int64)
EDGES = {"zero": 0, "minus_one": -1, "max_identity": I64.min,
         "low_word_full": 2**32 - 1, "first_carry": 2**32,
         "min_identity": I64.max}


# -- (a) the words ----------------------------------------------------------

@pytest.mark.parametrize("side", ["numpy", "in_program"])
@pytest.mark.parametrize("edge", list(EDGES))
def test_split_and_join_round_trip(edge, side):
    """hi = bits 63..32, lo = bits 31..0, both uint32; the join gives the
    value back, and an add inside a program carries from the low word
    into the high one (and wraps at 2^63) as int64 does."""
    value = EDGES[edge]
    x = np.array([value, value + 1 if value < I64.max else 7], np.int64)
    if side == "numpy":
        words = Halves.split(x)
        back, bumped = words.join(), Halves.split(x + np.int64(1)).join()
    else:
        words, back, bumped = jax.jit(lambda v: (
            Halves.split(v), Halves.split(v).join(),
            Halves.split(Halves.split(v).join() + 1).join()))(jnp.asarray(x))
    assert words.hi.dtype == words.lo.dtype == np.uint32
    assert words.dtype == np.int64 and words.shape == (2,)
    bits = x.view(np.uint64)
    np.testing.assert_array_equal(np.asarray(words.hi), bits >> np.uint64(32))
    np.testing.assert_array_equal(np.asarray(words.lo),
                                  bits & np.uint64(0xFFFFFFFF))
    np.testing.assert_array_equal(np.asarray(back), x)
    with np.errstate(over="ignore"):
        np.testing.assert_array_equal(np.asarray(bumped), x + np.int64(1))
    np.testing.assert_array_equal(np.asarray(words), x)   # __array__ joins


def test_unsigned_words_and_the_identities():
    u = np.array([0, 2**64 - 1, 2**63, 2**32], np.uint64)
    assert (Halves.split(u).join() == u).all()
    assert Halves.split(u).join().dtype == np.uint64
    for kind in ("sum", "count", "min", "max"):
        for dtype in (np.int64, np.uint64):
            want = np.asarray(AGG_INITS[kind](jnp.dtype(dtype)))
            assert identity_words(kind, dtype).join() == want, (kind, dtype)
            plane = make_plane(kind, (3, 5), dtype, True)
            assert isinstance(plane, Halves) and plane.nbytes == 3 * 5 * 8
            assert (np.asarray(plane) == want).all()


@pytest.mark.parametrize("dtype,ring,halves", [
    (np.int64, 8, True), (np.uint64, 8, True),
    (np.int32, 8, False), (np.float64, 8, False),
    (np.int64, None, False), (np.uint32, 8, False)])
def test_which_planes_are_stored_as_halves(dtype, ring, halves):
    """A layout, not an option: decided by the plane's own dtype and
    shape, the same on every platform."""
    assert stores_halves(dtype, ring) == halves
    be = TpuKeyedStateBackend(KeyGroupRange(0, 127), 128, capacity=64)
    be.register_array_state("p", "sum", dtype, ring=ring)
    plane = be.get_array("p")
    assert isinstance(plane, Halves) == halves
    assert plane.dtype == np.dtype(dtype)
    assert plane.shape == ((ring, 64) if ring else (64,))


# -- (b) fold, reset and fire against numpy ---------------------------------

RING, CAP, ROWS = 6, 256, 200
NP_FOLD = {"sum": np.add, "min": np.minimum, "max": np.maximum}


def _prices(rng, n):
    """Either sign, and wide enough that a few of them carry a sum across
    2^32 in both directions."""
    return rng.integers(-(1 << 44), 1 << 44, size=n)


@pytest.mark.parametrize("touched", [0, 1, 2, RING],
                         ids=["no_row", "one_row", "two_rows", "every_row"])
@pytest.mark.parametrize("kind", ["sum", "max", "min"])
def test_fold_reset_and_fire_equal_numpy(kind, touched, monkeypatch):
    """`fold_rings`, `reset_ring_row` and the full-merge fire over halves
    planes (the aggregate's, and an int64 count beside it, as Q7 keeps)
    against numpy int64 planes, cell for cell, for batches that touch 0,
    1, 2 and all ring rows."""
    monkeypatch.setattr("flink_tpu.ops.segment_ops._FOLD_CHUNK", 64)
    rng = np.random.default_rng([touched, "sum max min".split().index(kind)])
    be = TpuKeyedStateBackend(KeyGroupRange(0, 127), 128, capacity=CAP,
                              defer_overflow=True)
    be.register_array_state("__count__", "count", jnp.int64, ring=RING)
    be.register_array_state("agg", kind, jnp.int64, ring=RING)
    ident = int(np.asarray(AGG_INITS[kind](jnp.dtype(jnp.int64))))
    want = {"__count__": np.zeros((RING, CAP), np.int64),
            "agg": np.full((RING, CAP), ident, np.int64)}
    keys = rng.choice(1 << 50, size=100, replace=False).astype(np.int64)
    for _ in range(3):
        k = rng.choice(keys, size=ROWS)
        price = _prices(rng, ROWS)
        rows = (rng.integers(0, RING, size=ROWS) if touched == RING
                else rng.integers(2, 2 + max(touched, 1), size=ROWS))
        ok = rng.random(ROWS) < 0.9 if touched else np.zeros(ROWS, bool)
        slots = be.slots_for_batch_device(jnp.asarray(k))
        be.fold_rings(slots, rows, (slots >= 0) & jnp.asarray(ok),
                      {"__count__": None, "agg": price})
        s = np.asarray(slots)
        assert (s >= 0).all()
        np.add.at(want["__count__"], (rows[ok], s[ok]), 1)
        NP_FOLD[kind].at(want["agg"], (rows[ok], s[ok]), price[ok])
    for name in want:
        plane = be.get_array(name)
        assert isinstance(plane, Halves)
        np.testing.assert_array_equal(np.asarray(plane), want[name])
    if touched:
        assert np.abs(want["agg"][want["__count__"] > 0]).max() > 2**32
    # the fire over a window of three ring rows, one of them masked out
    pane_rows = np.array([2, 3, 5, 0], np.int32)
    rows_valid = np.array([True, True, True, False])
    fire = _fire_program((("count", "n"), (kind, "agg")), None)
    table, emit, results, _dropped, occ = fire(
        be.table, {n: be.get_array(n) for n in want},
        jnp.asarray(pane_rows), jnp.asarray(rows_valid), be.dropped_device)
    live = pane_rows[rows_valid]
    merged = {"sum": np.sum, "min": np.min, "max": np.max}[kind](
        want["agg"][live], axis=0)
    np.testing.assert_array_equal(np.asarray(results["n"]),
                                  want["__count__"][live].sum(axis=0))
    np.testing.assert_array_equal(np.asarray(results["agg"]), merged)
    np.testing.assert_array_equal(
        np.asarray(emit), (np.asarray(table) != EMPTY_KEY)
        & (want["__count__"][live].sum(axis=0) > 0))
    assert int(occ) == len(np.unique(np.asarray(table))) - 1
    # retiring a row writes the identity's two words into that row alone
    be.reset_ring_row(3)
    want["__count__"][3], want["agg"][3] = 0, ident
    for name in want:
        np.testing.assert_array_equal(np.asarray(be.get_array(name)),
                                      want[name])


def test_ring_fold_is_one_algorithm_for_both_layouts():
    """Handed the words it folds what it folds handed the array, bit for
    bit (the mesh hands arrays, the one-chip backend words)."""
    rng = np.random.default_rng(5)
    plane = rng.integers(I64.min, I64.max, size=(4, 64))
    rows, slots = rng.integers(0, 4, 90), rng.integers(0, 64, 90)
    vals = _prices(rng, 90)
    valid = rng.random(90) < 0.8
    for kind in ("sum", "min", "max"):
        args = (jnp.asarray(rows), jnp.asarray(slots, jnp.int32),
                jnp.asarray(vals), jnp.asarray(valid))
        whole = jax.jit(lambda p, *b, kind=kind: ring_fold(kind, p, *b))(
            jnp.asarray(plane), *args)
        words = jax.jit(lambda p, *b, kind=kind: ring_fold(kind, p, *b))(
            Halves.split(jnp.asarray(plane)), *args)
        assert isinstance(words, Halves) and not isinstance(whole, Halves)
        np.testing.assert_array_equal(np.asarray(words), np.asarray(whole))


def _limb_batch(rng, n, ring=4, cap=64, touched=None, shuffled=False,
                one_slot=False, masked=0.0):
    """(ring rows, slots, valid) of ``n`` rows over ``touched`` ring
    rows, in ring-row order or shuffled."""
    touched = ring if touched is None else touched
    rows = np.sort(rng.integers(0, touched, n)) * (ring // touched) \
        if n else np.zeros(0, np.int64)
    if shuffled:
        rng.shuffle(rows)
    slots = np.full(n, 5) if one_slot else rng.integers(0, cap, n)
    return rows, slots.astype(np.int32), rng.random(n) >= masked


def _full(rng, n):
    return rng.integers(I64.min, I64.max, n, endpoint=True)


#: name -> (kind, rows of the batch, its values, what shapes the batch)
_LIMB_CASES = {
    "q5_prices": ("sum", 90, lambda rng, n: rng.integers(1, 1 << 22, n), {}),
    "negative": ("sum", 90, lambda rng, n: -rng.integers(1, 1 << 22, n), {}),
    "either_sign_across_2_32": ("sum", 90, _prices, {}),
    "int64_min_and_max": ("sum", 90, lambda rng, n: rng.choice(
        np.array([I64.min, I64.max, -1, 0, 1]), n), {}),
    "sums_that_wrap": ("sum", 300, lambda rng, n: rng.integers(
        2**61, 2**63 - 1, n), {"cap": 8}),
    "all_64_bits": ("sum", 300, _full, {}),
    "masked_rows": ("sum", 300, _full, {"masked": 0.5}),
    "count": ("count", 300, None, {}),
    "count_masked": ("count", 300, None, {"masked": 0.5}),
    # n rows on ONE slot with every limb at its largest: n * (2^w - 1) is
    # the most a delta cell can be asked to hold, 2^32 - n
    "one_slot_all_ones_256": ("sum", 256, lambda rng, n: np.full(n, -1),
                              {"one_slot": True, "touched": 1}),
    "one_slot_all_ones_2_chunks": ("sum", 1 << 15,
                                   lambda rng, n: np.full(n, -1),
                                   {"one_slot": True, "touched": 1}),
    "count_one_slot": ("count", 1 << 15, None,
                       {"one_slot": True, "touched": 1}),
    "one_touched_row": ("sum", 200, _full, {"touched": 1}),
    "two_touched_rows": ("sum", 200, _full, {"touched": 2}),
    "two_touched_rows_shuffled": ("sum", 200, _full,
                                  {"touched": 2, "shuffled": True}),
    "every_row_shuffled": ("sum", 200, _full, {"shuffled": True}),
    "not_a_multiple_of_the_chunk": ("sum", (1 << 14) + 777, _full,
                                    {"shuffled": True}),
    "one_row": ("sum", 1, _full, {}),
    "two_rows": ("sum", 2, _full, {"one_slot": True, "touched": 1}),
    "three_rows": ("sum", 3, _full, {"one_slot": True, "touched": 1}),
    "no_row": ("sum", 0, _full, {}),
    "the_mesh_shards_row_count": ("sum", 81920, _full, {"cap": 512}),
    "the_mesh_shards_count": ("count", 81920, None, {"cap": 512}),
}


@pytest.mark.parametrize("case", list(_LIMB_CASES))
def test_the_limb_fold_is_the_wrapping_int64_add(case):
    """An additive kind into a ``Halves`` plane goes limb by limb through
    32-bit scatters (``ring_fold``): whatever the values, the plane is what
    numpy's wrapping int64 add makes of it, bit for bit, and the fold
    counts one limb scatter a touched ring row and limb that some valid
    row of it holds a non-zero value in (a count: one)."""
    from flink_tpu.ops.segment_ops import _limb_width

    kind, n, values, shape = _LIMB_CASES[case]
    rng = np.random.default_rng(len(case))
    ring, cap = 4, shape.pop("cap", 64)
    rows, slots, valid = _limb_batch(rng, n, ring, cap, **shape)
    vals = (np.ones(n, np.int64) if values is None
            else np.asarray(values(rng, n), np.int64))
    plane = _full(rng, (ring, cap))
    plane[:, 5] = [-1, 2**32 - 1, I64.max, I64.min]   # carries and wraps
    got, ran = jax.jit(lambda p, *b: ring_fold(kind, p, *b, counted=True))(
        Halves.split(jnp.asarray(plane)), jnp.asarray(rows),
        jnp.asarray(slots), jnp.asarray(vals), jnp.asarray(valid))
    assert isinstance(got, Halves)
    want = plane.view(np.uint64).copy()
    np.add.at(want, (rows[valid], slots[valid]), vals[valid].view(np.uint64))
    np.testing.assert_array_equal(np.asarray(got), want.view(np.int64))
    # the limbs that ran: by row, the limbs some valid value is not 0 in
    w = _limb_width(n) if n else 32
    limbs = [(vals.view(np.uint64) >> np.uint64(w * j))
             & np.uint64((1 << w) - 1) for j in range(-(-64 // w))]
    assert int(ran) == sum(
        1 if kind == "count" else sum(
            bool(limb[valid & (rows == r)].any()) for limb in limbs)
        for r in range(ring) if (valid & (rows == r)).any())
    if case == "q5_prices":
        assert w == 25 and int(ran) == ring          # 22-bit values: one limb
    if case in ("negative", "one_slot_all_ones_256"):
        assert int(ran) == len(limbs) * len(np.unique(rows[valid]))


def test_the_limbs_width_holds_any_number_of_the_batchs_rows():
    """w = 32 - ceil(log2 n): n rows at a limb's largest value stay under
    2^32, and one bit more would not (14 bits at X's 2^18 rows, 15 at the
    mesh shard's 81,920 routed row slots)."""
    from flink_tpu.ops.segment_ops import _limb_width

    assert _limb_width(1 << 18) == 14 and _limb_width(81920) == 15
    assert _limb_width(1) == 32 and _limb_width(2) == 31
    for n in (1, 2, 3, 255, 256, 257, 81920, 1 << 18, (1 << 18) + 1):
        w = _limb_width(n)
        assert n * ((1 << w) - 1) < 1 << 32 <= n * ((1 << (w + 1)) - 1) + n


PANE, RING = 1000, 8
SCHEMA = Schema([("k", np.int64), ("v", np.int64)])


def _stream(seed: int, n: int = 1024, batches: int = 8,
            a_pane_each: bool = False):
    """``batches`` equal cuts of 9 panes of rows in event-time order (a
    batch then straddles a pane's edge), or one batch a pane (a window of
    ring - 1 panes leaves the ring one open pane)."""
    rng = np.random.default_rng(seed)
    keys = rng.integers(0, 29, n).astype(np.int64) * 1_000_003 - 7
    vals = _prices(rng, n).astype(np.int64)
    ts = np.sort(rng.integers(0, 9 * PANE, n)).astype(np.int64)
    edges = (np.searchsorted(ts, np.arange(10) * PANE) if a_pane_each
             else np.arange(0, n + 1, n // batches))
    return [(keys[i:j], vals[i:j], ts[i:j])
            for i, j in zip(edges[:-1], edges[1:])]


def _windows(batches, window_panes: int) -> dict:
    """(key, window end) -> (count, sum, max, min), in Python integers."""
    want = {}
    for keys, vals, ts in batches:
        for k, v, t in zip(keys.tolist(), vals.tolist(), ts.tolist()):
            first = (t // PANE + 1) * PANE
            for end in range(first, first + window_panes * PANE, PANE):
                n, s, hi, lo = want.get((k, end), (0, 0, v, v))
                want[(k, end)] = (n + 1, s + v, max(hi, v), min(lo, v))
    return want


@pytest.mark.parametrize("born", ["host_born", "device_born"])
@pytest.mark.parametrize("window_panes", [3, RING - 1],
                         ids=["hop3", "widest"])
def test_the_operator_over_halves_equals_python_integers(window_panes, born):
    """COUNT (int64), SUM, MAX and MIN of an int64 column through the
    window operator, HOP 3 s / 1 s and the widest window the ring holds
    (7 s / 1 s: the fire gathers seven rows of every word), batches
    uploaded from the host (probe, then `jit_fold`) and batches born on
    the device (one `jit_step`), row for row against Python's integers;
    SUMs pass 2^32 in both directions."""
    op = DeviceWindowAggOperator(
        SlidingEventTimeWindows.of(window_panes * PANE, PANE), "k",
        [AggSpec("count", out_name="n"), AggSpec("sum", "v", out_name="s"),
         AggSpec("max", "v", out_name="hi"),
         AggSpec("min", "v", out_name="lo")],
        capacity=1 << 8, ring_size=RING, emit_window_bounds=True,
        defer_overflow=True, async_fire=True)
    h = OneInputOperatorTestHarness(op, SCHEMA)
    batches = _stream(11, a_pane_each=window_panes == RING - 1)
    for keys, vals, ts in batches:
        if born == "device_born":
            h.process_batch(DeviceRecordBatch(
                SCHEMA, {"k": jnp.asarray(keys), "v": jnp.asarray(vals)},
                jnp.asarray(ts), int(ts.min()), int(ts.max())))
        else:
            h.process_batch(RecordBatch(SCHEMA, {"k": keys, "v": vals}, ts))
        h.process_watermark(int(ts[-1]) - 1)
    h.process_watermark(1 << 40)
    h.close()
    for name in ("__count__", "s", "hi", "lo"):
        assert isinstance(op._backend.get_array(name), Halves), name
    got = {(k, end): tuple(int(x) for x in row)
           for k, _start, end, *row in h.get_output()}
    want = _windows(batches, window_panes)
    assert got == want
    assert max(abs(row[1]) for row in want.values()) > 2**32


# -- (c) the reclaim: words against the int64 form --------------------------

PLANES = (("__count__", "count", jnp.int32), ("revenue", "sum", jnp.int64),
          ("best", "max", jnp.int64))


@pytest.mark.parametrize("capacity", [1 << 9, 1 << 10])
def test_reclaim_of_halves_equals_the_int64_form_slot_for_slot(capacity):
    """`reclaim_shard` is one algorithm for both layouts: handed the
    planes the backend stores (an int32 array, two `Halves`) it returns
    the table and the cells that it returns handed the same planes as
    int64 arrays (what the mesh hands it), slot for slot; `reclaim.live`
    tests the words against the identity's words, `reclaim.remap` moves
    both words of a row in one sort."""
    rng = np.random.default_rng(capacity)
    be = TpuKeyedStateBackend(KeyGroupRange(0, 127), 128, capacity=capacity,
                              defer_overflow=True)
    for name, kind, dtype in PLANES:
        be.register_array_state(name, kind, dtype, ring=4)
    n = int(0.62 * capacity)
    keys = rng.choice(1 << 40, size=n, replace=False).astype(np.int64) \
        - (1 << 39)
    for start in range(0, n, 64):
        k = np.resize(keys[start:start + 64], 64)
        price = _prices(rng, 64)
        # a third of the keys hold data only in the rows that retire
        rows = np.where(rng.random(64) < 0.33, rng.integers(0, 2, 64),
                        rng.integers(0, 4, 64))
        slots = be.slots_for_batch_device(jnp.asarray(k))
        be.fold_rings(slots, rows, slots >= 0,
                      {"__count__": None, "revenue": price, "best": price})
    be.reset_ring_row(2)
    be.reset_ring_row(3)
    table = jnp.asarray(np.asarray(be.table))
    stored = [be.get_array(name) for name, _k, _d in PLANES]
    assert [isinstance(p, Halves) for p in stored] == [False, True, True]
    whole = tuple(jnp.asarray(np.asarray(p)) for p in stored)
    sig = tuple((kind, str(np.dtype(dtype)), (4, capacity))
                for _n, kind, dtype in PLANES)
    want_table, want_planes, _d, want_counts = jax.jit(
        lambda t, a, d: reclaim_shard(sig, t, a, d))(
        table, whole, jnp.zeros((), jnp.int64))
    kept, freed = be.reclaim()
    assert (kept, freed) == tuple(int(x) for x in want_counts)
    assert 0 < kept and 0 < freed and be.capacity == capacity
    np.testing.assert_array_equal(np.asarray(be.table),
                                  np.asarray(want_table))
    for (name, _k, _d), was, want in zip(PLANES, stored, want_planes):
        now = be.get_array(name)
        assert isinstance(now, Halves) == isinstance(was, Halves), name
        np.testing.assert_array_equal(np.asarray(now), np.asarray(want))
    # and the program is cached by a signature that says which are halves
    from flink_tpu.state.tpu_backend import _plane_sig
    assert [dt for _k, dt, _s in _plane_sig(be._array_states.values())] \
        == ["int32", "halves:int64", "halves:int64"]


def test_growth_and_the_spill_tier_move_the_words():
    """A rehash moves every cell word by word; a key group paged out to
    the host tier and promoted back arrives as it left."""
    be = TpuKeyedStateBackend(KeyGroupRange(0, 127), 128, capacity=64)
    be.register_array_state("s", "sum", jnp.int64, ring=2)
    keys = np.arange(1, 201, dtype=np.int64) * 7919
    vals = (np.arange(200, dtype=np.int64) - 100) * (1 << 33) - 5
    for i in range(0, 200, 50):
        slots = be.slots_for_batch(keys[i:i + 50])
        be.fold_rings(slots, np.full(50, 1), slots >= 0,
                      {"s": vals[i:i + 50]})
    assert be.capacity > 64 and isinstance(be.get_array("s"), Halves)
    from flink_tpu.ops.hash_table import lookup
    slots = np.asarray(lookup(be.table, jnp.asarray(keys)))
    plane = np.asarray(be.get_array("s"))
    np.testing.assert_array_equal(plane[1, slots], vals)
    assert (plane[0] == 0).all()
    # a budgeted backend: the same stream evicts cold groups to the host
    # tier, and a snapshot (device rows + host rows) holds every value
    tiered = TpuKeyedStateBackend(KeyGroupRange(0, 127), 128, capacity=64,
                                  hbm_budget_slots=128)
    tiered.register_array_state("s", "sum", jnp.int64, ring=2)
    for i in range(0, 200, 50):
        slots = tiered.slots_for_batch(keys[i:i + 50])
        tiered.fold_rings(slots, np.full(50, 1), slots >= 0,
                          {"s": vals[i:i + 50]})
    assert tiered.spill_active and isinstance(tiered.get_array("s"), Halves)
    snap = tiered.snapshot(1)
    order = np.argsort(snap["keys"])
    np.testing.assert_array_equal(snap["keys"][order], np.sort(keys))
    np.testing.assert_array_equal(
        snap["states"]["s"]["values"][1][order], vals[np.argsort(keys)])


# -- (d) the queries at rehearsal size --------------------------------------

@pytest.mark.parametrize("cell,halves", [
    ("q5-10m-saturated", {"revenue"}),
    ("q7-10m-saturated", {"best"})])
def test_the_benchmark_queries_equal_their_references_over_halves(cell,
                                                                  halves):
    """Q5 (int32 COUNT beside an int64 SUM) and Q7 (an int64 MAX over a
    packed 43-bit word; its hidden plane is a 32-bit presence plane since
    PR 49, one array) through
    `env.execute()` at rehearsal size: every row the reference's, from
    planes that are stored as words."""
    from benchmarks.harness.cell import run_cell
    from benchmarks.harness.spec import load_spec

    spec = load_spec()
    run = run_cell(spec, spec.cell(cell), seed=3_000_000_019, seconds=3.0,
                   trace=False, rehearse=True)
    # (whether a program's build ended inside a CPU rehearsal's window is
    # this host's speed of the hour, and other tests' to judge)
    wrong = [c for c in run.checks if not c.get("ok", True)
             and c["check"] != "programs_built_in_window"]
    assert not wrong and run.failed == 0 and run.attempted > 0, wrong
    tally = next(c for c in run.checks if c["check"] == "_tally")
    assert tally["windows_expected"] == tally["windows_emitted"] > 0
    assert tally["rows_compared"] > 0
    states = run.operator._backend._array_states
    assert {n for n, st in states.items()
            if isinstance(st.array, Halves)} == halves
