"""Device-resident open-addressing hash table: int64 key -> dense slot.

The core of the TPU keyed-state backend (SURVEY.md §7 step 3, the
FRocksDB-replacement): keyed state lives in dense device arrays indexed by
slot; this table maps unbounded keys onto those static-shape arrays entirely
on device, so the per-batch hot path never touches the host.

Algorithm: linear probing over a power-of-two table with a vectorized
parallel insert, probing in CHUNK-slot windows. A probe round gathers, for
every row it carries, the next CHUNK consecutive probe slots in one
[rows, CHUNK] read and resolves the window at once: the first match wins;
otherwise rows that see EMPTY race to claim the window's FIRST empty slot
with a single ``scatter-min`` (deterministic winner = smallest key); losers
resume from the contested slot. Claims only target slots read as EMPTY in
the same round, so occupied slots are never corrupted; duplicate keys
follow identical probe sequences and claim the same first-empty slot (the
loser sees its own key and resolves). The insert-only invariant (empties
never reappear IN A TABLE: a slot is freed only by building a new table
from the keys that stay, at twice the capacity or, since the state
backend reclaims, at the same one; state/tpu_backend.py ``reclaim``)
guarantees a present key can never sit behind an empty slot in its probe
sequence, so first-match-before-first-empty decides containment, from a
table's first key to its replacement. Bounded probe count returns an
``ok`` mask instead of looping forever (the host reclaims or rehashes
before the load bites, and fails the job on a dropped insert).

What a round costs on the chip (TPU v5e, 2^24 slots, PERF.md sections 5
and 6): the int64 table is two 32-bit words a slot (the compiler keeps
it as two ``uint32`` halves), a gather costs by the element it fetches
and not by the byte (about 6.3 ns), so per row carried a claiming round
pays about 110 ns for its window's two gathers of CHUNK words and about
100 ns for the claim, whatever the width, and the row with the longest
probe chain sets the round count for every row beside it. At load 0.6
that is 5 to 9 rounds while 98.6% of resident keys sit within CHUNK
slots of their home. So ``lookup_or_insert`` carries a row only while it
is unresolved: one read-only window at full width, then the rounds over
the compacted tail (1-2% of a batch of resident keys), at full width
only when the tail does not fit (mostly new keys: cold start, prefill,
growth, and, where keys come and go, every batch: a key whose slot was
reclaimed and that is seen again is an insert, and a key that is new and
hot has ALL its rows unresolved, so a job over NEXmark's advancing
auction ids leaves 58% of a 2^18-row batch to the claiming rounds:
PERF.md section 5, q5-inflight-saturated). That read-only window is the
one every row pays every batch, and it reads ONE word a slot
(``_window0``, PR 52): the eight LOW words say where the key or the
first EMPTY can be, one HIGH word at that slot says which it is: one
``[n, CHUNK]`` gather (13.2 ms at 2^18 rows) and one ``[n]`` gather (3.2)
where whole keys take two ``[n, CHUNK]`` gathers, and the window with
its hashes and bookkeeping reads 22.9 ms a batch where it read 34.5
(PERF.md section 6, PR 52); the few rows that leaves undecided (none for
keys under 2^32) go with the tail. A caller that sees wide batches one after another asks
for the program that runs the full-width rounds only until what is left
fits the narrow loop (``handover``), and in which, unless the caller's
keys cannot repeat, a key's rows send ONE lane into the rounds
(``_elect``). CHUNK = 8 is the window at which those shares were
measured; it was first sized for a CPU cache line (2.3x over one-slot
probing at 50% load on the CPU), which is no argument here: on the chip
a window costs what its CHUNK gathered elements cost.

Keys are int64 with EMPTY = int64 max as the sentinel (a real key equal to
the sentinel is remapped by the caller — see state/tpu_backend.py).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

_X64_READY = False


def ensure_x64() -> None:
    """Keyed state uses full 64-bit keys on device (XLA emulates i64 on TPU
    with i32 pairs — fine for the compare/scatter ops the table needs).
    Flipped at first *use* of the device state path, not at import, so merely
    importing the library never changes a user program's default dtypes."""
    global _X64_READY
    if not _X64_READY:
        jax.config.update("jax_enable_x64", True)
        _X64_READY = True

__all__ = ["EMPTY_KEY", "make_table", "lookup", "lookup_or_insert",
           "hash_keys_device", "sanitize_keys_device", "ensure_x64",
           "compacts", "MAX_PROBES"]

EMPTY_KEY = np.int64(np.iinfo(np.int64).max)
MAX_PROBES = 128
CHUNK = 8  # probe-window width, in slots (see the module docstring)
# Batches of at least this many rows compact their unresolved rows after the
# first window. Measured on the v5e against a 2^24-slot table at load 0.6,
# resident keys (PERF.md section 6, PR 26): 2^12 rows 3.6 ms for 9.4, 2^14
# 6.4 for 27.6, 2^16 26 for 150; at 2^10 within a tenth (2.7 for 3.1), at
# 2^8 the plain loop wins (2.2 for 2.3): fixed per-op costs.
_COMPACT_MIN_ROWS = 1 << 12
# The narrow loop's widths are n >> s, narrowest first. n / 64 holds the tail of a batch of
# resident keys at load 0.6 (1.4% of its distinct keys' rows) with one hot
# key displaced; n / 16 holds what a dozen displaced hot keys add. Two
# widths because a round costs what its lanes cost: 48.5 ms a 2^18-row
# batch with both against 73.0 with n / 16 alone (508 before).
_TAIL_SHIFTS = (6, 4)
# The loops of a wide batch whose rows elected one lane a key (``_elect``),
# narrowest first: what is left after the election is distinct keys,
# mostly cold inserts. n / 4 too because a job whose keys come and go
# inserts up to a quarter of a batch for some tens of batches after a
# reclaim (PERF.md section 5, q5-inflight-saturated); more than that
# starts at full width, as before. A round resolves all but a few per cent
# of the new keys it carries, so every loop but the narrowest runs only
# until the narrowest holds what is left.
_ELECT_SHIFTS = (6, 4, 2)
# cells of the election's scratch a lane of the batch: a key that shares
# its cell with another key of a smaller lane stays unelected (every row
# of it its own representative: correct, only wider), and with 4 cells a
# lane a hot key among n / 8 distinct keys loses one time in 64
_ELECT_CELLS = 4


def sanitize_keys_device(keys: jax.Array) -> jax.Array:
    """Remap the EMPTY sentinel (int64 max) to int64 max - 1 — THE sentinel
    rule, shared by every device ingest path (host twin:
    state/tpu_backend._sanitize_keys)."""
    keys = keys.astype(jnp.int64)
    return jnp.where(keys == jnp.int64(EMPTY_KEY), jnp.int64(EMPTY_KEY) - 1,
                     keys)


def make_table(capacity: int) -> jax.Array:
    """capacity must be a power of two."""
    ensure_x64()
    if capacity & (capacity - 1):
        raise ValueError(f"capacity {capacity} not a power of two")
    return jnp.full((capacity,), EMPTY_KEY, dtype=jnp.int64)


def hash_keys_device(keys: jax.Array) -> jax.Array:
    """Murmur-style finalizer over int64 keys -> uint32 hash, matching the
    host path's spread (keygroups.murmur_mix over Long.hashCode-folded keys)
    closely enough for probing (exact parity is only required for key-group
    routing, which happens before this table)."""
    u = keys.astype(jnp.uint64)
    h = (u ^ (u >> 32)).astype(jnp.uint32)
    h = h * jnp.uint32(0xCC9E2D51)
    h = (h << 15) | (h >> 17)
    h = h * jnp.uint32(0x1B873593)
    h = h ^ (h >> 16)
    h = h * jnp.uint32(0x85EBCA6B)
    h = h ^ (h >> 13)
    return h


def _window(table: jax.Array, keys: jax.Array, h0: jax.Array,
            base: jax.Array, mask: jax.Array):
    """One CHUNK-slot window of every row's probe sequence, read and matched:
    (hit, fslot, pos_empty, eslot). ``hit``: the row's key sits in the window
    before its first EMPTY (``fslot`` is where); ``pos_empty``: offset of the
    window's first EMPTY (CHUNK if none) and ``eslot`` its slot. The
    full-width read (both 32-bit words of every slot) that serves
    ``lookup`` and every claiming round of ``lookup_or_insert``, which
    must see whole keys to claim; the read-only first window of a wide
    batch reads less (``_window0``) and falls back on this one. Its ops
    carry probe.gather in their name path (HLO op_name; the tf_op stat of
    an op's metadata in a TPU trace), whatever fusion numbers the compiler
    assigns."""
    offs = jnp.arange(CHUNK, dtype=jnp.uint32)
    rng = jnp.arange(CHUNK, dtype=jnp.int32)
    C = jnp.int32(CHUNK)
    with jax.named_scope("probe.gather"):
        idx = (((h0 + base)[:, None] + offs[None, :]) & mask).astype(
            jnp.int32)
        entry = table[idx]                                   # [n, CHUNK]
        is_key = entry == keys[:, None]
        is_empty = entry == jnp.int64(EMPTY_KEY)
        pos_found = jnp.min(jnp.where(is_key, rng[None], C), axis=1)
        pos_empty = jnp.min(jnp.where(is_empty, rng[None], C), axis=1)
        # (the slots by take_along_axis, not by arithmetic on the offsets:
        # 3.7 ms of a 34 ms window at n = 2^18 on the v5e, but with the
        # arithmetic the compiler kept only one half of the 64-bit table in
        # its fast memory space and the window's two gathers took 48 ms
        # for 30: PERF.md section 6, PR 26)
        fslot = jnp.take_along_axis(
            idx, jnp.minimum(pos_found, C - 1)[:, None], axis=1)[:, 0]
        eslot = jnp.take_along_axis(
            idx, jnp.minimum(pos_empty, C - 1)[:, None], axis=1)[:, 0]
    return pos_found < pos_empty, fslot, pos_empty, eslot


def _window0(table: jax.Array, keys: jax.Array, h0: jax.Array,
             mask: jax.Array, done: jax.Array):
    """The read-only first window of a wide batch, from ONE 32-bit word a
    slot: (hit, fslot, pos_empty, undecided), the first three as
    ``_window`` gives them at ``base`` 0 for every row it DECIDES.

    The int64 table is two 32-bit words a slot on the chip and a gather
    costs by the element it fetches, so the window's eight LOW words find
    where the row's key or the first EMPTY can be, and ONE high word, at
    the first such candidate, says which it is. Decided, and exactly as
    ``_window`` decides it: the candidate is the key (a hit), it is an
    EMPTY (the row resumes there to claim), or the window holds no
    candidate at all (a whole match implies a low-word match, so it holds
    neither). UNDECIDED: the candidate failed its high-word check
    (another key with the key's low word, or a key whose low word is
    EMPTY's, all ones). Such a row reads as not hit with ``pos_empty`` 0:
    it resumes where it stands, and whoever reads next reads the same
    slots in full. Keys under 2^32 against keys under 2^32 (every NEXmark
    id) leave none. ``undecided`` counts such rows among those not
    ``done``."""
    offs = jnp.arange(CHUNK, dtype=jnp.uint32)
    rng = jnp.arange(CHUNK, dtype=jnp.int32)
    C = jnp.int32(CHUNK)
    empty_lo = jnp.uint32(np.uint64(EMPTY_KEY) & np.uint64(0xFFFFFFFF))
    empty_hi = jnp.int32(EMPTY_KEY >> 32)
    with jax.named_scope("probe.gather"):
        idx = ((h0[:, None] + offs[None, :]) & mask).astype(jnp.int32)
        # (the compiler's own low half of the table, X64SplitLow: no copy)
        low = table.astype(jnp.uint32)[idx]                  # [n, CHUNK]
        c_key = jnp.min(jnp.where(low == keys.astype(jnp.uint32)[:, None],
                                  rng[None], C), axis=1)
        c_emp = jnp.min(jnp.where(low == empty_lo, rng[None], C), axis=1)
        first = jnp.minimum(c_key, c_emp)
        # (by take_along_axis, as in _window and for its reason)
        cslot = jnp.take_along_axis(
            idx, jnp.minimum(first, C - 1)[:, None], axis=1)[:, 0]
        high = (table >> 32).astype(jnp.int32)[cslot]        # [n]
        cand = first < C
        # (an EMPTY before a match, as _window's pos_found < pos_empty)
        empty = cand & (c_emp == first) & (high == empty_hi)
        hit = cand & (c_key == first) & ~empty \
            & (high == (keys >> 32).astype(jnp.int32))
        failed = cand & ~hit & ~empty
        pos_empty = jnp.where(empty, first, jnp.where(failed, 0, C))
        undecided = jnp.sum(failed & ~done, dtype=jnp.int32)
    return hit, cslot, pos_empty, undecided


def _unfinished(base: jax.Array, done: jax.Array) -> jax.Array:
    return ((~done) & (base < MAX_PROBES)).any()


@jax.jit
def lookup(table_keys: jax.Array, keys: jax.Array) -> jax.Array:
    """Find slots for keys; -1 where absent. Vectorized bounded probing in
    CHUNK-slot windows (first empty before first match => absent)."""
    mask = jnp.uint32(table_keys.shape[0] - 1)
    h0 = hash_keys_device(keys) & mask
    n = keys.shape[0]

    def body(state):
        base, slot, done = state
        hit, fslot, pos_empty, _ = _window(table_keys, keys, h0, base, mask)
        slot = jnp.where((~done) & hit, fslot, slot)
        done = done | hit | (pos_empty < CHUNK)  # empty first => absent
        base = jnp.where(done, base, base + jnp.uint32(CHUNK))
        return base, slot, done

    init = (jnp.zeros(n, jnp.uint32), jnp.full(n, -1, jnp.int32),
            jnp.zeros(n, bool))
    _, slot, _ = jax.lax.while_loop(lambda s: _unfinished(s[0], s[2]), body,
                                    init)
    return slot


def _advance(base: jax.Array, done: jax.Array,
             pos_empty: jax.Array) -> jax.Array:
    """Where an unresolved row reads next: from the window's first EMPTY
    (which it wanted, and lost or has yet to claim), else the next window."""
    step = jnp.where(pos_empty < CHUNK, pos_empty, CHUNK).astype(jnp.uint32)
    return jnp.where(done, base, base + step)


def _claim_loop(table: jax.Array, keys: jax.Array, h0: jax.Array,
                mask: jax.Array, base: jax.Array, slot: jax.Array,
                done: jax.Array, until: int = 0):
    """Probe rounds at the width of ``keys``, until every row is resolved
    or out of probes, or, with ``until``, until no more than that many
    rows are unresolved (for a narrower loop to take them over): read a
    window, take a match, else claim the window's first EMPTY with one
    ``scatter-min`` (smallest key wins; losers resume from the contested
    slot). Returns (table, base, slot, done)."""

    def unfinished(state):
        _table, base, _slot, done = state
        more = _unfinished(base, done)
        if until:
            more = more & (jnp.sum(~done, dtype=jnp.int32) > until)
        return more

    def body(state):
        table, base, slot, done = state
        hit, fslot, pos_empty, eslot = _window(table, keys, h0, base, mask)
        found = (~done) & hit
        # the scatter-min stays DIRECTLY in the loop body under probe.claim:
        # the benchmark's probe_rounds_p50 counts the loop's rounds by the
        # name path /while/body/probe.claim/scatter-min
        with jax.named_scope("probe.claim"):
            want = (~done) & ~hit & (pos_empty < CHUNK)
            claim_idx = jnp.where(want, eslot, jnp.int32(0))
            claim_val = jnp.where(want, keys, jnp.int64(EMPTY_KEY))
            table = table.at[claim_idx].min(claim_val)
            won = want & (table[eslot] == keys)
        slot = jnp.where(found, fslot, jnp.where(won, eslot, slot))
        done = done | found | won
        return table, _advance(base, done, pos_empty), slot, done

    return jax.lax.while_loop(unfinished, body, (table, base, slot, done))


def _elect_cells(keys: jax.Array) -> tuple[jax.Array, int]:
    """(the cell of the election's scratch each key hashes to, cells):
    ``_ELECT_CELLS`` cells a lane, rounded up to a power of two, by the
    top bits of a second mix of the probe's hash."""
    bits = (_ELECT_CELLS * keys.shape[0] - 1).bit_length()
    cell = (hash_keys_device(keys) * jnp.uint32(0x9E3779B1)) >> jnp.uint32(
        32 - bits)
    return cell.astype(jnp.int32), 1 << bits


def _elect(keys: jax.Array, done: jax.Array):
    """One representative lane for every distinct key among the rows not
    ``done``: (rep, follows). The unresolved lanes ``scatter-min`` their
    lane number into an int32 scratch at a second hash of their key and
    read their cell back; a lane FOLLOWS the lane it finds there when
    that lane's key is its own, compared as 64-bit keys (``rep`` is then
    that lane, which follows nobody: it found itself). A lane whose cell
    went to another key follows nobody either. Rows of one key read the
    same windows, claim the same slot with the same value and read back
    the same winner, so the followers take their representative's slot
    when the rounds are over and the table, every slot and every ``ok``
    are what they are with every row in the rounds. An int32 scatter and
    gather, a 64-bit gather for the compare and (the caller's) an int32
    gather of the slots: the price of sparing a key's other rows the
    rounds, where one new hot key owns hundreds of a batch's rows."""
    n = keys.shape[0]
    lane = jnp.arange(n, dtype=jnp.int32)
    cell, cells = _elect_cells(keys)
    # (a resolved lane writes nothing: the identity into cell 0, as the
    # claim does)
    first = jnp.full(cells, n, jnp.int32).at[
        jnp.where(done, 0, cell)].min(jnp.where(done, n, lane))
    rep = jnp.where(done, lane, first[cell])
    return rep, (rep != lane) & (keys[rep] == keys)


def compacts(n: int) -> bool:
    """Whether a batch of ``n`` rows is wide enough for the probe to
    compact its unresolved rows (else every round runs at full width and
    ``handover`` changes nothing)."""
    return n >= _COMPACT_MIN_ROWS


def _tail_widths(n: int) -> tuple[int, ...]:
    """Widths of the narrow loop for a batch of ``n`` rows, ascending;
    none where the batch is too small for compaction to pay (it then
    probes at full width from the start, claiming in every round)."""
    if n < _COMPACT_MIN_ROWS:
        return ()
    return tuple(n >> s for s in _TAIL_SHIFTS)


@partial(jax.jit, static_argnames=("stats", "handover", "distinct"))
def lookup_or_insert(table_keys: jax.Array, keys: jax.Array,
                     valid: jax.Array | None = None, stats: bool = False,
                     handover: bool = False, distinct: bool = False):
    """Find-or-claim slots for a batch of keys.

    Returns (new_table_keys, slots int32, ok bool). Records that exhaust
    MAX_PROBES report ok=False with slot=-1 (host should rehash bigger).
    Rows where ``valid`` is False never probe or claim (slot=-1, ok=False) —
    the sharded exchange feeds padded batches through here.

    A batch of ``_COMPACT_MIN_ROWS`` or more reads its first window at full
    width WITHOUT claiming (in steady state nearly every key is resident
    there and nobody wants a slot) and from the slots' low 32-bit words
    alone, with one high word a row to tell a key from its low word's
    namesakes (``_window0``: one ``[n, CHUNK]`` gather where whole keys
    take two), then compacts the rows still unresolved into the narrowest
    of ``_tail_widths(n)`` that holds them and runs the probe rounds over
    those lanes alone; with more unresolved
    rows than the widest (cold start, prefill, growth, the re-homing of
    the live keys inside a reclaim, and any batch of a job whose keys
    come and go: a reclaimed key seen again is an insert, and every row
    of a brand-new hot key is unresolved) the rounds run at full width
    from where the first window left off. One program either way (a
    ``lax.switch`` on a device scalar, nothing for the host to sync on);
    smaller batches run the rounds at full width from the start, as every
    batch did before. The result is a pure function of (table, keys,
    valid) on every path.

    A row the half-width window cannot decide (its candidate slot holds
    another key with the same low word, or a key whose low word is all
    ones) counts as unresolved and the rounds' first window reads its
    slots in full: it claims, where it must, one round later than a row
    that window decided, so among NEW keys that contend for a slot the
    one each gets may differ from what whole-key reads would give; the
    table's invariants do not. The worst case is bounded by what the
    program sees: a batch with more undecided rows than its narrowest
    loop holds (``n >> 6``: keys that share few low words, ``x << 32``, a
    composite ``a << 32 | b`` with a handful of ``b``) reads the window
    once more in full (``_window`` under a ``lax.cond`` on that device
    count; no option, no host decision) and goes on exactly as if it had
    read it so in the first place: it pays both reads (73 ms a 2^18-row
    batch of resident keys ``x << 32`` against 2^24 slots where whole-key
    reads took 49 and ids under 2^32 now take 37; the ``cond`` itself
    costs 0.2 ms: PERF.md section 6, PR 52), not full-width claiming
    rounds.

    ``handover=True`` (static: a second program) is for a caller that
    expects such batches, one after another: the full-width rounds then
    run only until the rows left fit the widest narrow loop, which takes
    them over. A round costs what its lanes cost, and after one or two
    rounds of a batch of new keys all but a few per cent hold their slot:
    6 to 10 full-width rounds of 83 ms at 2^18 rows and load 0.6 become
    one or two and a few narrow ones (PERF.md section 6, PR 35). The
    rounds are the same rounds over the same rows at either width, so the
    result is the same. It is a program of its own, and not the only one,
    because the v5e's compiler keeps both 32-bit halves of a 2^24-slot
    table in its fast memory space for the program below and only one of
    them for every form of the hand-over tried (the first window's two
    gathers then take longer; measured on the chip with this program
    pinned on a job of 10M resident keys: 15.4 ms a batch, 8.8% of its
    events a second: PERF.md section 6, PRs 26 and 35), so a job whose
    batches resolve in their first window keeps the program it had.
    ``state/tpu_backend.py`` picks by the probe's own counters.

    Such a caller's wide batch also ELECTS: where the tail does not fit
    the widest narrow loop, and only there, every distinct unresolved key
    sends one representative lane into the rounds (``_elect``), the
    representatives go to the narrowest of the election's loops that
    holds them, and every other lane takes its representative's slot
    when the rounds are over. With NEXmark's advancing ids 131 thousand
    of a batch's 150 thousand unresolved rows are the rows of 171 new hot
    ids, and all 767 rows of one read the same window, write the same
    key to the same slot and read back the same winner (PERF.md section
    6, PR 46). The claim lets the smallest KEY win a slot, so with one
    writer a key the table, every slot and every ``ok`` are what they are
    with all of them. ``distinct=True`` (static) is a caller's word that
    no key comes twice among the rows it sends (keys taken from a table:
    the reclaim's re-homing; one lane a key run: the session step): it
    gets the hand-over program without an election, and nothing of the
    above is traced for it. Without ``handover`` it means nothing.

    ``stats=True`` (static) appends an int32[4]: rows probed, rows that
    entered a claiming loop (unresolved after the read-only window; every
    probed row of a batch below the compaction width), 1 if that loop
    started at full width else 0, and rows the half-width window left
    undecided (counted before any re-read; 0 below the compaction
    width); all BEFORE any election, so that a caller who picks its
    program by them keeps its pick. The electing
    program (``handover`` without ``distinct``) appends an int32[2]
    behind it: rows that stood behind a representative, and 1 if the
    batch elected else 0.
    """
    n = keys.shape[0]
    widths = _tail_widths(n)
    elects = handover and not distinct
    # the hashes and the rows' start state belong to whatever reads the
    # first window: the read-only one, or the claiming loop's
    with jax.named_scope("probe.window0" if widths else "probe.tail"):
        mask = jnp.uint32(table_keys.shape[0] - 1)
        h0 = hash_keys_device(keys) & mask
        done = (jnp.zeros(n, bool) if valid is None
                else ~valid.astype(bool))
        base = jnp.zeros(n, jnp.uint32)
        slot = jnp.full(n, -1, jnp.int32)
        rows = jnp.sum(~done, dtype=jnp.int32)
    if not widths:
        n_tail, wide, undecided = rows, jnp.int32(1), jnp.int32(0)
        with jax.named_scope("probe.tail"):
            table, _base, slot, _done = _claim_loop(
                table_keys, keys, h0, mask, base, slot, done)
    else:
        with jax.named_scope("probe.window0"):
            hit, fslot, pos_empty, undecided = _window0(
                table_keys, keys, h0, mask, done)
        # the bounded worst case: more undecided rows than the narrowest
        # loop holds, and the window is read once more, in full. By the
        # tail's name: it is what window 0 could not do, and in the mesh
        # step the compiler feeds this branch and the tail's loops from
        # ONE copy of a table half, which a trace gives to one region
        with jax.named_scope("probe.tail"):
            hit, fslot, pos_empty = jax.lax.cond(
                undecided > widths[0],
                lambda: _window(table_keys, keys, h0, base, mask)[:3],
                lambda: (hit, fslot, pos_empty))
        with jax.named_scope("probe.window0"):
            slot = jnp.where((~done) & hit, fslot, slot)
            done = done | hit
            base = _advance(base, done, pos_empty)
            n_tail = jnp.sum(~done, dtype=jnp.int32)

        def narrow_loop(T, n_left, table, base, slot, done, cols=(keys, h0),
                        then=0):
            """The rounds over the unresolved rows of ``cols``, compacted
            into ``T`` lanes; with ``then``, only until no more than that
            many are left, which a loop of ``then`` lanes, compacted
            again, finishes."""
            ckeys, ch0 = cols
            m = ckeys.shape[0]
            with jax.named_scope("probe.compact"):
                # a stable sort on `done` puts the unresolved rows first, in
                # row order: 0.24 ms at n = 2^18 on the v5e, where cumsum +
                # searchsorted took 3.3 and jnp.nonzero(size=T) did not fit
                # the compiler's vmem at all
                _, order = jax.lax.sort_key_val(
                    done.astype(jnp.int32), jnp.arange(m, dtype=jnp.int32))
                src = order[:T]
                live = jnp.arange(T, dtype=jnp.int32) < n_left
            tkeys, th0 = ckeys[src], ch0[src]
            table, tbase, tslot, tdone = _claim_loop(
                table, tkeys, th0, mask, base[src],
                jnp.full(T, -1, jnp.int32), ~live, until=then)
            if then:
                table, tslot = narrow_loop(
                    then, jnp.sum(~tdone, dtype=jnp.int32), table, tbase,
                    tslot, tdone, cols=(tkeys, th0))
            with jax.named_scope("probe.compact"):
                slot = slot.at[jnp.where(live, src, m)].set(
                    tslot, mode="drop")
            return table, slot

        def wide_loop(table, base, slot, done):
            if not handover:
                table, _base, slot, _done = _claim_loop(
                    table, keys, h0, mask, base, slot, done)
                return table, slot
            table, base, slot, done = _claim_loop(
                table, keys, h0, mask, base, slot, done, until=widths[-1])
            return narrow_loop(widths[-1], jnp.sum(~done, dtype=jnp.int32),
                               table, base, slot, done)

        def elected_loop(table, base, slot, done):
            """A wide batch of keys that may repeat: one lane a distinct
            key goes through the rounds (``_elect``), in the narrowest of
            the election's loops that holds the representatives (at full
            width until the widest does), each handing over to the
            narrowest once that holds what is left; the other lanes take
            their representative's slot. The same rounds over the same
            keys, so the same table, slots and ``ok``."""
            with jax.named_scope("probe.elect"):
                rep, follows = _elect(keys, done)
                done = done | follows
                n_rep = jnp.sum(~done, dtype=jnp.int32)
            ewidths = tuple(n >> s for s in _ELECT_SHIFTS)
            last = ewidths[0]

            def full_width(table, base, slot, done):
                table, base, slot, done = _claim_loop(
                    table, keys, h0, mask, base, slot, done,
                    until=ewidths[-1])
                return narrow_loop(
                    ewidths[-1], jnp.sum(~done, dtype=jnp.int32), table,
                    base, slot, done, then=last)

            table, slot = jax.lax.switch(
                sum((n_rep > T).astype(jnp.int32) for T in ewidths),
                [partial(narrow_loop, T, n_rep, then=last if T > last else 0)
                 for T in ewidths] + [full_width], table, base, slot, done)
            with jax.named_scope("probe.elect"):
                slot = jnp.where(follows, slot[rep], slot)
                elected = jnp.stack([jnp.sum(follows, dtype=jnp.int32),
                                     jnp.int32(1)])
            return table, slot, elected

        # the narrowest loop that holds the tail, else the full width
        with jax.named_scope("probe.tail"):
            level = sum((n_tail > T).astype(jnp.int32) for T in widths)
            wide = (level == len(widths)).astype(jnp.int32)
            if elects:
                def unelected(T, *state):
                    return (*narrow_loop(T, n_tail, *state),
                            jnp.zeros(2, jnp.int32))

                table, slot, elected = jax.lax.switch(
                    level, [partial(unelected, T) for T in widths]
                    + [elected_loop], table_keys, base, slot, done)
            else:
                table, slot = jax.lax.switch(
                    level, [partial(narrow_loop, T, n_tail) for T in widths]
                    + [wide_loop], table_keys, base, slot, done)
    out = (table, slot, slot >= 0)
    if not stats:
        return out
    out = (*out, jnp.stack([rows, n_tail, wide, undecided]))
    if elects:
        out = (*out, elected if widths else jnp.zeros(2, jnp.int32))
    return out
