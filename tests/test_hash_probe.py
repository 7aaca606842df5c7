"""Property test of ``ops/hash_table.lookup_or_insert`` against a plain
dict: the read-only first window, the compacted (narrow) loop, the
full-width loop it falls back to, and the small-batch path, each held to
the table's invariants and to the four counters ``stats=True`` reports;
and the half-width first window (PR 52: one 32-bit word a slot) against a
reference that reads that window in full."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from flink_tpu.ops import hash_table as H  # noqa: E402
from flink_tpu.ops.hash_table import (  # noqa: E402
    CHUNK, EMPTY_KEY, MAX_PROBES, hash_keys_device, lookup,
    lookup_or_insert, make_table,
)

CAP = 1 << 15
N = H._COMPACT_MIN_ROWS                 # the smallest batch that compacts
WIDTHS = H._tail_widths(N)              # of the narrow loop, ascending
T = WIDTHS[-1]


def _homes(keys: np.ndarray, cap: int) -> np.ndarray:
    return np.asarray(hash_keys_device(jnp.asarray(keys, jnp.int64))
                      ).astype(np.int64) & (cap - 1)


def _resident(table: np.ndarray) -> dict[int, int]:
    occ = np.flatnonzero(table != EMPTY_KEY)
    return dict(zip(table[occ].tolist(), occ.tolist()))


def _failed_candidates(table: np.ndarray, keys: np.ndarray) -> np.ndarray:
    """Per row, whether the half-width first window cannot decide it: its
    first slot whose LOW word is the key's or EMPTY's holds a high word
    that is neither the key's nor EMPTY's (another key with that low
    word). NumPy on the table alone."""
    cap = len(table)
    idx = (_homes(keys, cap)[:, None] + np.arange(CHUNK)) % cap
    lo, hi = table[idx] & 0xFFFFFFFF, table[idx] >> 32
    cand = (lo == (keys & 0xFFFFFFFF)[:, None]) | (lo == 0xFFFFFFFF)
    first = cand.argmax(axis=1)
    at = np.arange(len(keys))
    chi = hi[at, first]
    is_key = (lo[at, first] == keys & 0xFFFFFFFF) & (chi == keys >> 32)
    is_empty = (lo[at, first] == 0xFFFFFFFF) & (chi == EMPTY_KEY >> 32)
    return cand.any(axis=1) & ~is_key & ~is_empty


def _fill(cap: int, keys: np.ndarray, batch: int = 512) -> np.ndarray:
    """A table holding ``keys``, built by the small-batch path (the loop
    every earlier version ran: what a restored checkpoint looks like)."""
    t = make_table(cap)
    for i in range(0, len(keys), batch):
        part = np.resize(keys[i:i + batch], batch)   # pad with repeats
        t, _, ok = lookup_or_insert(t, jnp.asarray(part))
        assert bool(np.asarray(ok).all())
    return np.asarray(t)


def _check(before: np.ndarray, keys: np.ndarray, valid, expect_wide=None):
    """Run one batch; hold the result to the dict model. Returns
    (table_after, slots, ok, stats)."""
    cap = len(before)
    n = len(keys)
    dvalid = None if valid is None else jnp.asarray(valid)
    table, slots, ok, stats = lookup_or_insert(
        jnp.asarray(before), jnp.asarray(keys), dvalid, stats=True)
    three = lookup_or_insert(jnp.asarray(before), jnp.asarray(keys), dvalid)
    assert len(three) == 3              # callers inside programs: 3 values
    after, slots, ok = np.asarray(table), np.asarray(slots), np.asarray(ok)
    np.testing.assert_array_equal(after, np.asarray(three[0]))
    np.testing.assert_array_equal(slots, np.asarray(three[1]))
    v = np.ones(n, bool) if valid is None else np.asarray(valid, bool)
    # rows that do not take part: never a slot, never ok, never inserted
    assert (slots[~v] == -1).all() and not ok[~v].any()
    assert ((slots >= 0) == ok).all()
    # every placed row holds the slot that holds its key
    assert (after[slots[ok]] == keys[ok]).all()
    was = _resident(before)
    now = _resident(after)
    # one slot per distinct key, duplicates share it
    for k, s in zip(keys[ok].tolist(), slots[ok].tolist()):
        assert now[k] == s
    # untouched entries unchanged; exactly the new keys were added
    occupied = before != EMPTY_KEY
    np.testing.assert_array_equal(after[occupied], before[occupied])
    new_keys = {k for k in keys[ok].tolist() if k not in was}
    assert set(now) - set(was) == new_keys
    assert not (set(keys[v & ~ok].tolist()) & set(now))
    # no key sits behind an EMPTY of its own probe sequence
    placed = np.array(sorted(now), np.int64)
    home = _homes(placed, cap)
    at = np.array([now[k] for k in placed.tolist()], np.int64)
    dist = (at - home) % cap
    for d in range(int(dist.max(initial=0))):
        on_path = dist > d
        assert (after[(home[on_path] + d) % cap] != EMPTY_KEY).all()
    # rows that gave up had nowhere to go within MAX_PROBES
    assert (dist < MAX_PROBES + CHUNK).all()
    # the four counters, from the dict and the table alone: a row leaves
    # the first window resolved if its key sits there, unless that window,
    # read from the slots' low words, left it undecided (and the batch had
    # too few such rows to read the window again in full)
    rows = int(v.sum())
    widths = H._tail_widths(n)
    if widths:
        khome = _homes(keys, cap)
        in_first_window = np.array(
            [k in was and (was[k] - h) % cap < CHUNK
             for k, h in zip(keys.tolist(), khome.tolist())])
        failed = _failed_candidates(before, keys) & v
        undecided = int(failed.sum())
        if undecided <= widths[0]:
            in_first_window &= ~failed
        tail = int((v & ~in_first_window).sum())
        wide = int(tail > widths[-1])
    else:
        tail, wide, undecided = rows, 1, 0
    assert np.asarray(stats).tolist() == [rows, tail, wide, undecided]
    if expect_wide is not None:
        assert wide == expect_wide
    return after, slots, ok, np.asarray(stats)


@pytest.fixture(scope="module")
def half_full():
    """(table at load 0.5, its keys, those found in their first window)."""
    rng = np.random.default_rng(7)
    keys = rng.choice(1 << 40, CAP // 2, replace=False).astype(np.int64)
    table = _fill(CAP, keys)
    was = _resident(table)
    home = _homes(keys, CAP)
    near = np.array([(was[k] - h) % CAP < CHUNK
                     for k, h in zip(keys.tolist(), home.tolist())])
    assert (~near).sum() > 0            # some keys are displaced >= CHUNK
    return table, keys, keys[near]


def test_all_resident_takes_the_narrow_loop(half_full):
    table, keys, _near = half_full
    rng = np.random.default_rng(1)
    batch = rng.choice(keys, N)
    after, slots, ok, stats = _check(table, batch, None, expect_wide=0)
    assert ok.all() and 0 < stats[1] <= T
    np.testing.assert_array_equal(after, table)
    np.testing.assert_array_equal(
        slots, np.asarray(lookup(jnp.asarray(table), jnp.asarray(batch))))


def test_all_new_takes_the_wide_loop(half_full):
    table, _keys, _near = half_full
    batch = np.arange(N, dtype=np.int64) + (1 << 50)
    _after, _slots, ok, _stats = _check(table, batch, None, expect_wide=1)
    assert ok.all()


@pytest.mark.parametrize("unresolved", sorted(
    {0, 1} | {w + d for w in WIDTHS for d in (-1, 0, 1)}))
def test_tail_at_the_loop_width(half_full, unresolved):
    """Exactly ``unresolved`` rows are left after the first window: new
    keys, which that window can never find."""
    table, _keys, near = half_full
    rng = np.random.default_rng(unresolved)
    batch = rng.choice(near, N)
    batch[rng.choice(N, unresolved, replace=False)] = (
        np.arange(unresolved, dtype=np.int64) + (1 << 51))
    _after, _slots, ok, stats = _check(table, batch, None,
                                       expect_wide=int(unresolved > T))
    assert ok.all() and stats[1] == unresolved


def test_hot_keys_one_of_them_displaced():
    """Half the batch on sixteen hot keys, one of which sits CHUNK or
    more slots from its home (its home window was filled first): its rows
    are the tail, and the narrow loop still holds them."""
    cand = np.arange(1, 400_000, dtype=np.int64)
    home = _homes(cand, CAP)
    h = int(np.bincount(home).argmax())
    same = cand[home == h]
    assert len(same) > CHUNK
    rng = np.random.default_rng(3)
    cold = rng.choice(cand[home != h], CAP // 2, replace=False)
    table = _fill(CAP, np.concatenate([same[:CHUNK + 1], cold]))
    displaced = int(same[CHUNK])
    was = _resident(table)
    assert (was[displaced] - h) % CAP >= CHUNK
    hot = np.concatenate([[displaced], cold[:15]]).astype(np.int64)
    batch = np.where(rng.random(N) < 0.5, rng.choice(hot, N),
                     rng.choice(cold, N))
    _after, slots, ok, stats = _check(table, batch, None, expect_wide=0)
    assert ok.all() and stats[1] >= (batch == displaced).sum() > N // 64
    assert (slots[batch == displaced] == was[displaced]).all()


@pytest.mark.parametrize("n", [8, 100, N])
def test_valid_mask(half_full, n):
    table, keys, _near = half_full
    rng = np.random.default_rng(n)
    batch = np.where(rng.random(n) < 0.5, rng.choice(keys, n),
                     rng.integers(1 << 52, 1 << 53, n)).astype(np.int64)
    valid = rng.random(n) < 0.7
    _check(table, batch, valid)


@pytest.mark.parametrize("n", [64, N])
def test_overflow_reports_not_ok(n):
    """More distinct keys than a tiny table holds: the rows that run out
    of probes report ok False and slot -1, the rest are placed."""
    cap = 256
    first = np.arange(400, dtype=np.int64) * 7919 + 11   # fill it to the brim
    table = np.asarray(lookup_or_insert(
        make_table(cap), jnp.asarray(np.resize(first, 512)))[0])
    assert (table != EMPTY_KEY).sum() > cap - 32
    more = np.arange(n, dtype=np.int64) * 104729 + 5
    _after, slots, ok, _stats = _check(table, more, None)
    assert (~ok).any() and (slots[~ok] == -1).all()


def test_table_built_by_itself_at_load_06():
    """Batch by batch through the function itself to load 0.6 (wide
    batches first, mixed ones later), then read back with ``lookup``."""
    rng = np.random.default_rng(11)
    keys = rng.choice(1 << 40, int(0.6 * CAP), replace=False
                      ).astype(np.int64)
    table = np.asarray(make_table(CAP))
    seen = 0
    for i in range(0, len(keys), N // 2):
        fresh = keys[i:i + N // 2]
        old = rng.choice(keys[:max(seen, 1)], N - len(fresh))
        table, _s, ok, _st = _check(table, np.concatenate([fresh, old]),
                                    None)
        assert ok.all()
        seen += len(fresh)
    now = _resident(table)
    assert len(now) == len(keys)
    got = np.asarray(lookup(jnp.asarray(table), jnp.asarray(keys)))
    assert got.tolist() == [now[k] for k in keys.tolist()]
    assert np.asarray(lookup(jnp.asarray(table), jnp.asarray(
        np.arange(64, dtype=np.int64) + (1 << 55)))).tolist() == [-1] * 64


def test_small_batches_run_the_full_width_loop(half_full):
    table, keys, _near = half_full
    assert H._tail_widths(N - 1) == ()
    batch = np.concatenate([keys[:5], np.array([1 << 54, 1 << 54, 9],
                                               np.int64)])
    _after, slots, ok, stats = _check(table, batch, None, expect_wide=1)
    assert ok.all() and slots[5] == slots[6]
    assert stats.tolist() == [8, 8, 1, 0]


def test_backend_hands_the_counters_to_device_stats():
    """The deferred ingest path accumulates the probe's counters on the
    device; DEVICE_STATS gets them without a sync once a copy has landed
    (a batch or two late) and exactly at check_health."""
    from flink_tpu.core import KeyGroupRange
    from flink_tpu.metrics import DEVICE_STATS
    from flink_tpu.state.tpu_backend import TpuKeyedStateBackend

    keys = ("probe_rows_total", "probe_tail_rows_total",
            "probe_wide_batches_total")

    def moved(since):
        now = DEVICE_STATS.snapshot()
        return [now[k] - since[k] for k in keys]

    be = TpuKeyedStateBackend(KeyGroupRange(0, 127), 128, capacity=CAP,
                              defer_overflow=True)
    start = DEVICE_STATS.snapshot()
    resident = jnp.arange(N, dtype=jnp.int64) * 3 + 1
    be.slots_for_batch_device(resident)                        # all new: wide
    assert moved(start) == [0, 0, 0]            # nothing has been fetched yet
    be._probe_sent[0].block_until_ready()       # the copy taken at batch one
    be.slots_for_batch_device(resident).block_until_ready()   # all resident
    assert moved(start) == [N, N, 1]            # the first batch's, landed
    be.slots_for_batch_device(jnp.arange(8, dtype=jnp.int64))  # the plain loop
    be.check_health()
    rows, tail, wide = moved(start)
    assert (rows, wide) == (2 * N + 8, 2) and N + 8 <= tail <= N + 8 + T
    be.check_health()
    assert moved(start) == [rows, tail, wide]   # a flush adds nothing twice


@pytest.mark.parametrize("load", [0.0, 0.3, 0.58])
def test_the_handover_program_places_every_row_where_the_plain_one_does(load):
    """``handover=True``: the full-width rounds stop once the rows left
    fit the widest narrow loop, which takes them over; the same rounds
    over the same rows at another width, so the same table and slots, on
    a batch of new keys, on one half of whose rows are a few new hot keys
    (NEXmark's moving hot auction), with a valid mask, and on a batch
    that never goes wide."""
    rng = np.random.default_rng(int(load * 100))
    table = _fill(CAP, rng.choice(1 << 40, int(load * CAP),
                                  replace=False).astype(np.int64))
    fresh = rng.choice(1 << 41, N, replace=False).astype(np.int64) + (1 << 41)
    hot = fresh[:40][rng.integers(0, 40, N)]
    resident = table[table != EMPTY_KEY]
    batches = [fresh, np.where(rng.random(N) < 0.5, hot, fresh)]
    if len(resident):
        batches.append(rng.choice(resident, N))
    for keys in batches:
        for valid in (None, jnp.asarray(rng.random(N) < 0.9)):
            plain = lookup_or_insert(jnp.asarray(table), jnp.asarray(keys),
                                     valid, stats=True)
            handed = lookup_or_insert(jnp.asarray(table), jnp.asarray(keys),
                                      valid, stats=True, handover=True)
            for a, b in zip(plain, handed):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    _check(table, batches[1], None, expect_wide=1)


def _elected_by_the_dict(table, keys, valid) -> tuple[int, int]:
    """(rows the election must put behind a representative, rows it could
    at most): of the rows the first window leaves, every key that keeps
    its scratch cell (the cell's smallest lane is one of its own) sends
    all but that lane; a key that loses its cell sends none."""
    cap = len(table)
    was = _resident(table)
    home = _homes(keys, cap)
    left = np.array([v and not (k in was and (was[k] - h) % cap < CHUNK)
                     for k, h, v in zip(keys.tolist(), home.tolist(),
                                        valid.tolist())])
    cells = np.asarray(H._elect_cells(jnp.asarray(keys))[0])
    winner: dict[int, int] = {}
    for lane in np.flatnonzero(left).tolist():
        winner.setdefault(int(cells[lane]), lane)
    rows: dict[int, int] = {}
    for k in keys[left].tolist():
        rows[k] = rows.get(k, 0) + 1
    kept = {int(keys[lane]) for lane in winner.values()}
    return (sum(rows[k] - 1 for k in kept),
            int(left.sum()) - len(rows))


#: name -> (new hot keys, rows of each, new distinct keys, share of valid
#: rows); the rest of a batch are resident keys. By the representatives
#: left: one key (the 64-lane loop), 8 + 150 (256 lanes, handing over), 8 +
#: 600 (1,024 lanes, handing over), 8 + 3,000 (more than the widest elected
#: loop: full width first)
_ELECTIONS = {
    "hot_ids_and_cold_inserts": (8, 300, 150, 1.0),
    "hot_ids_and_many_cold_inserts": (8, 300, 600, 1.0),
    "one_new_key": (1, N, 0, 1.0),
    "no_duplicates": (0, 0, N // 2, 1.0),
    "invalid_rows_among_the_duplicates": (8, 300, 150, 0.8),
    "more_new_keys_than_the_widest_elected_loop": (4, 200, 3000, 1.0),
}


@pytest.mark.parametrize("load", [0.3, 0.58])
@pytest.mark.parametrize("case", list(_ELECTIONS))
def test_the_electing_program_places_every_row_where_the_plain_one_does(
        case, load):
    """``handover=True`` from a caller whose keys may repeat: a wide batch
    sends ONE lane a distinct unresolved key through the rounds and the
    others take its slot. Table, slots, ``ok`` and the four counters are
    the plain program's, bit for bit, and the elected rows are those of
    the keys that kept their scratch cell: all but one lane of each."""
    hot, per, cold, valid_share = _ELECTIONS[case]
    rng = np.random.default_rng(len(case) + int(load * 100))
    table = _fill(CAP, rng.choice(1 << 40, int(load * CAP),
                                  replace=False).astype(np.int64))
    resident = table[table != EMPTY_KEY]
    new = rng.choice(1 << 41, hot + cold, replace=False).astype(
        np.int64) + (1 << 41)
    keys = rng.choice(resident, N, replace=bool(hot))
    at = rng.permutation(N)
    for j in range(hot):
        keys[at[j * per:(j + 1) * per]] = new[j]
    keys[at[hot * per:hot * per + cold]] = new[hot:]
    valid = rng.random(N) < valid_share
    dvalid = None if valid.all() else jnp.asarray(valid)
    plain = lookup_or_insert(jnp.asarray(table), jnp.asarray(keys), dvalid,
                             stats=True)
    *elect, elected = lookup_or_insert(
        jnp.asarray(table), jnp.asarray(keys), dvalid, stats=True,
        handover=True)
    for a, b in zip(plain, elect, strict=True):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert int(plain[3][2]) == 1                # a wide batch: it elected
    must, at_most = _elected_by_the_dict(table, keys, valid)
    assert np.asarray(elected).tolist() == [must, 1]
    assert must <= at_most
    if case == "one_new_key":
        assert must == at_most == int(valid.sum()) - 1
    if case == "no_duplicates":
        assert must == at_most == 0
    if hot > 1:
        # 4 cells a lane: a hot id loses its cell to an earlier key now
        # and then, most keep it
        assert must >= at_most // 2
    # a batch whose tail fits a narrow loop elects nothing, and says so
    out = lookup_or_insert(jnp.asarray(table),
                           jnp.asarray(rng.choice(resident, N)),
                           stats=True, handover=True)
    assert np.asarray(out[3])[2] == 0 and np.asarray(out[4]).tolist() == [0, 0]
    # keys a caller promises distinct: the program without an election
    assert len(lookup_or_insert(
        jnp.asarray(table), jnp.asarray(np.resize(new, N)),
        jnp.arange(N) < len(new), stats=True, handover=True,
        distinct=True)) == 4


def test_backend_picks_the_handover_program_while_batches_start_wide():
    """The backend reads the probe's counters a batch or two late and
    picks the next batch's program by them: new keys batch after batch
    take the hand-over, resident keys the plain program again."""
    from flink_tpu.core.keygroups import KeyGroupRange
    from flink_tpu.state.tpu_backend import TpuKeyedStateBackend

    be = TpuKeyedStateBackend(KeyGroupRange(0, 127), 128, capacity=CAP,
                              defer_overflow=True)
    rng = np.random.default_rng(5)
    keys = rng.choice(1 << 40, 6 * N, replace=False).astype(np.int64)
    assert not be._probe_wide
    for i in range(6):                                  # all new: wide
        be.slots_for_batch_device(jnp.asarray(keys[i * N:(i + 1) * N]))
    be.note_probe_stats(block=True)
    assert be._probe_wide
    for _ in range(3):                                  # all resident
        slots = be.slots_for_batch_device(jnp.asarray(keys[:N]))
        assert (np.asarray(slots) >= 0).all()
    be.note_probe_stats(block=True)
    assert not be._probe_wide
    # a small batch runs at full width whatever is picked
    be.slots_for_batch_device(jnp.asarray(keys[:64] + 1))
    be.note_probe_stats(block=True)
    # wide batches whose keys repeat (half of every batch on eight ids
    # that are new with it: NEXmark's hot auction of the moment): the
    # pick holds from the second batch on, for the counters it reads are
    # of the rows BEFORE the election, and every batch after the first
    # sent one lane a key through the rounds
    from flink_tpu.metrics import DEVICE_STATS

    be = TpuKeyedStateBackend(KeyGroupRange(0, 127), 128, capacity=CAP,
                              defer_overflow=True)
    start = DEVICE_STATS.snapshot()
    more = rng.choice(1 << 40, 5 * N, replace=False).astype(np.int64) \
        + (1 << 41)
    picked = []
    for i in range(5):
        fresh = more[i * N:(i + 1) * N]
        batch = np.where(rng.random(N) < 0.5,
                         fresh[rng.integers(0, 8, N)], fresh)
        slots = be.slots_for_batch_device(jnp.asarray(batch))
        np.testing.assert_array_equal(
            np.asarray(slots),
            np.asarray(lookup(be.table, jnp.asarray(batch))))
        be.note_probe_stats(block=True)
        picked.append(be._probe_wide)
    assert picked == [True] * 5
    now = DEVICE_STATS.snapshot()
    moved = {k: now[k] - start[k] for k in now if k.startswith("probe_")}
    assert moved["probe_wide_batches_total"] == 5
    assert moved["probe_elected_batches_total"] == 4
    assert 4 * 0.4 * N < moved["probe_elected_rows_total"] < 4 * 0.5 * N


# ---------------------------------------------------------------------------
# PR 52: the first window reads ONE 32-bit word a slot
# ---------------------------------------------------------------------------

_FORMS = {"plain": {}, "electing": {"handover": True},
          "distinct": {"handover": True, "distinct": True}}
_REFERENCES: dict = {}


def _window0_in_full(table, keys, h0, mask, done):
    """What ``_window0`` stands in for: the first window read in full."""
    hit, fslot, pos_empty, _ = H._window(table, keys, h0,
                                         jnp.zeros_like(h0), mask)
    return hit, fslot, pos_empty, jnp.int32(0)


def _reference(form: str, *args):
    """``lookup_or_insert(stats=True)`` in ``form`` with its first window
    read by ``_window``: the program as it was before the half-width
    window, traced from the same source with that one function swapped."""
    from unittest import mock

    if form not in _REFERENCES:
        fn = lookup_or_insert.__wrapped__
        _REFERENCES[form] = jax.jit(
            lambda *a: fn(*a, stats=True, **_FORMS[form]))
    with mock.patch.object(H, "_window0", _window0_in_full):
        return _REFERENCES[form](*args)


def _colliding_with_residents(table: np.ndarray, want: int, rng):
    """``want`` new keys ``a << 32 | low`` whose HOME slot holds a resident
    key with the same low word (and another high word): the half-width
    window's candidate is that slot, at offset 0, and fails."""
    cap = len(table)
    out = []
    highs = np.arange(1, 1 << 17, dtype=np.int64)
    for slot in rng.permutation(np.flatnonzero(table != EMPTY_KEY)):
        cand = (highs << 32) | (int(table[slot]) & 0xFFFFFFFF)
        at_home = cand[(_homes(cand, cap) == slot) & (cand != table[slot])]
        out.extend(at_home[:1].tolist())
        if len(out) == want:
            return np.array(out, np.int64)
    raise AssertionError("no colliding keys found")


def _under_2_32(rng, _table):
    return rng.integers(0, 1 << 32, N).astype(np.int64)


def _dense_ids(rng, _table):
    """Resident from the start (the test fills them in): batches of these
    leave a tail that the narrow loops hold."""
    return rng.integers(0, 2 * N, N).astype(np.int64)


def _shifted_left_32(rng, _table):
    return rng.integers(0, 3 * N, N).astype(np.int64) << 32


def _composite_of_few_low_words(rng, _table):
    return (rng.integers(0, 1 << 20, N).astype(np.int64) << 32
            | rng.integers(0, 4, N))


def _low_words_all_ones(rng, _table):
    return (rng.integers(0, 3 * N, N).astype(np.int64) << 32) | 0xFFFFFFFF


def _few_shared_low_words(rng, table):
    """Ids under 2^32, and on 40 rows 20 keys that share a resident
    key's low word at their home slot."""
    keys = _dense_ids(rng, table)
    if (table != EMPTY_KEY).any():
        keys[rng.choice(N, 40, replace=False)] = np.repeat(
            _colliding_with_residents(table, 20, rng), 2)
    return keys


def _few_low_words_all_ones(rng, table):
    """Resident ids, a quarter new ids under 2^32, and 256 keys whose low
    word is EMPTY's: a row whose window meets one of those before its own
    key or its first EMPTY takes it for a candidate EMPTY, and the high
    word says no."""
    keys = np.where(rng.random(N) < 0.25, _under_2_32(rng, table),
                    _dense_ids(rng, table))
    keys[:256] = (np.arange(256, dtype=np.int64) << 32) | 0xFFFFFFFF
    return keys


#: name -> (a batch's keys from (rng, the table so far), what the batches'
#: undecided counts must show: none / some batch with a few, read once /
#: some batch with more than N >> 6, read again in full)
_KEY_FAMILIES = {
    "ids_under_2_32": (_under_2_32, "none"),
    "dense_ids": (_dense_ids, "none"),
    "shifted_left_32": (_shifted_left_32, "reread"),
    "composite_of_few_low_words": (_composite_of_few_low_words, "reread"),
    "low_words_all_ones": (_low_words_all_ones, "reread"),
    "few_shared_low_words": (_few_shared_low_words, "few"),
    "few_low_words_all_ones": (_few_low_words_all_ones, "few"),
}


@pytest.mark.parametrize("form", list(_FORMS))
@pytest.mark.parametrize("family", list(_KEY_FAMILIES))
def test_half_width_first_window(family, form):
    """Three batches a table, with a valid mask, through every program
    form. Every batch: the table's invariants and the four counters
    (``_check``: the undecided count is NumPy's count of failed
    candidates), and the form's table, slots, ``ok`` and counters are the
    plain program's. A batch with no undecided row, or with more than
    ``N >> 6`` (which reads the window again in full), is the reference's
    to the bit, its tail count too; a batch with a few may place a new
    key elsewhere than the reference does, invariants intact."""
    gen, shows = _KEY_FAMILIES[family]
    rng = np.random.default_rng(len(family))
    # (keys under 2^32 at load 0.45, the dense ids among them)
    table = _fill(CAP, np.unique(np.concatenate([
        np.arange(2 * N), rng.choice(1 << 32, int(0.2 * CAP))])
        ).astype(np.int64))
    seen = []
    for _ in range(3):
        keys = gen(rng, table)
        valid = rng.random(N) < 0.9
        if form == "distinct":
            uniq = np.unique(keys[valid])
            keys = np.resize(uniq, N)
            valid = np.arange(N) < len(uniq)
        after, slots, ok, stats = _check(table, keys, valid)
        args = (jnp.asarray(table), jnp.asarray(keys), jnp.asarray(valid))
        out = [np.asarray(x) for x in lookup_or_insert(
            *args, stats=True, **_FORMS[form])]
        for a, b in zip((after, slots, ok, stats), out):
            np.testing.assert_array_equal(a, b)
        undecided = int(stats[3])
        if undecided == 0 or undecided > N >> 6:
            ref = [np.asarray(x) for x in _reference(form, *args)]
            assert len(ref) == len(out)
            for a, b in zip(out[:3], ref[:3]):
                np.testing.assert_array_equal(a, b)
            assert out[3][:3].tolist() == ref[3][:3].tolist()
        seen.append(undecided)
        table = after
    if shows == "none":
        assert seen == [0, 0, 0]
    elif shows == "few":
        assert any(0 < u <= N >> 6 for u in seen), seen
    else:
        assert any(u > N >> 6 for u in seen), seen


def test_backend_counts_the_rows_the_first_window_left_undecided():
    """``probe_undecided_rows_total``: 0 for ids under 2^32, and what
    NumPy counts on the table for keys ``x << 32`` (one low word)."""
    from flink_tpu.core import KeyGroupRange
    from flink_tpu.metrics import DEVICE_STATS
    from flink_tpu.state.tpu_backend import TpuKeyedStateBackend

    def undecided_moved(keys) -> int:
        before = DEVICE_STATS.snapshot()["probe_undecided_rows_total"]
        be.slots_for_batch_device(jnp.asarray(keys))
        be.check_health()
        return DEVICE_STATS.snapshot()["probe_undecided_rows_total"] - before

    be = TpuKeyedStateBackend(KeyGroupRange(0, 127), 128, capacity=CAP,
                              defer_overflow=True)
    ids = np.arange(N, dtype=np.int64) * 5 + 1
    assert undecided_moved(ids) == 0 and undecided_moved(ids) == 0
    shifted = ids << 32
    assert undecided_moved(shifted) == 0        # nothing shares a low word 0
    expect = int(_failed_candidates(np.asarray(be.table), shifted).sum())
    assert undecided_moved(shifted) == expect > N >> 6
