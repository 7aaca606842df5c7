"""Device-resident open-addressing hash table: int64 key -> dense slot.

The core of the TPU keyed-state backend (SURVEY.md §7 step 3, the
FRocksDB-replacement): keyed state lives in dense device arrays indexed by
slot; this table maps unbounded keys onto those static-shape arrays entirely
on device, so the per-batch hot path never touches the host.

Algorithm: linear probing over a power-of-two table with a vectorized
parallel insert, probing in CHUNK-slot windows. Each iteration, every
unresolved record gathers its next CHUNK consecutive probe slots in one
[B, CHUNK] read (consecutive slots share cache lines / vector lanes, so a
window costs little more than a single slot — measured 2.3x over one-slot
probing at 50% load on CPU) and resolves the window at once: the first
match wins; otherwise records that see EMPTY race to claim the window's
FIRST empty slot with a single ``scatter-min`` (deterministic winner =
smallest key); losers resume from the contested slot. Claims only target
slots read as EMPTY in the same iteration, so occupied slots are never
corrupted; duplicate keys follow identical probe sequences and claim the
same first-empty slot (the loser sees its own key and resolves). The
insert-only invariant (empties never reappear) guarantees a present key
can never sit behind an empty slot in its probe sequence, so
first-match-before-first-empty decides containment. Bounded probe count
returns an ``ok`` mask instead of looping forever (host rehashes on
overflow).

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
           "MAX_PROBES"]

EMPTY_KEY = np.int64(np.iinfo(np.int64).max)
MAX_PROBES = 128
CHUNK = 8  # probe-window width: one 64-byte cache line of int64 slots


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


@jax.jit
def lookup(table_keys: jax.Array, keys: jax.Array) -> jax.Array:
    """Find slots for keys; -1 where absent. Vectorized bounded probing in
    CHUNK-slot windows (first empty before first match => absent)."""
    cap = table_keys.shape[0]
    mask = jnp.uint32(cap - 1)
    h0 = hash_keys_device(keys) & mask
    n = keys.shape[0]
    offs = jnp.arange(CHUNK, dtype=jnp.uint32)
    rng = jnp.arange(CHUNK, dtype=jnp.int32)
    C = jnp.int32(CHUNK)

    def body(state):
        base, slot, done = state
        with jax.named_scope("probe.gather"):
            idx = (((h0 + base)[:, None] + offs[None, :]) & mask).astype(
                jnp.int32)
            entry = table_keys[idx]                          # [n, CHUNK]
            is_key = entry == keys[:, None]
            is_empty = entry == jnp.int64(EMPTY_KEY)
            pos_found = jnp.min(jnp.where(is_key, rng[None], C), axis=1)
            pos_empty = jnp.min(jnp.where(is_empty, rng[None], C), axis=1)
            found = (~done) & (pos_found < pos_empty)
            fslot = jnp.take_along_axis(
                idx, jnp.minimum(pos_found, C - 1)[:, None], axis=1)[:, 0]
        slot = jnp.where(found, fslot, slot)
        done = done | found | (pos_empty < C)  # empty first => absent
        base = jnp.where(done, base, base + jnp.uint32(CHUNK))
        return base, slot, done

    def cond(state):
        base, _slot, done = state
        return ((~done) & (base < MAX_PROBES)).any()

    init = (jnp.zeros(n, jnp.uint32), jnp.full(n, -1, jnp.int32),
            jnp.zeros(n, bool))
    _, slot, _ = jax.lax.while_loop(cond, body, init)
    return slot


@jax.jit
def lookup_or_insert(table_keys: jax.Array, keys: jax.Array,
                     valid: jax.Array | None = None
                     ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Find-or-claim slots for a batch of keys.

    Returns (new_table_keys, slots int32, ok bool). Records that exhaust
    MAX_PROBES report ok=False with slot=-1 (host should rehash bigger).
    Rows where ``valid`` is False never probe or claim (slot=-1, ok=False) —
    the sharded exchange feeds padded batches through here.
    """
    cap = table_keys.shape[0]
    mask = jnp.uint32(cap - 1)
    h0 = hash_keys_device(keys) & mask
    n = keys.shape[0]
    offs = jnp.arange(CHUNK, dtype=jnp.uint32)
    rng = jnp.arange(CHUNK, dtype=jnp.int32)
    C = jnp.int32(CHUNK)

    def body(state):
        table, base, slot, done = state
        # named regions: one probe round's ops carry probe.gather (the
        # [n, CHUNK] window read + match) or probe.claim (the scatter-min
        # that claims empties + its read-back) in their name path (HLO
        # op_name; the tf_op stat of an op's metadata in a TPU trace),
        # whatever fusion numbers the compiler assigns
        with jax.named_scope("probe.gather"):
            idx = (((h0 + base)[:, None] + offs[None, :]) & mask).astype(
                jnp.int32)
            entry = table[idx]                               # [n, CHUNK]
            is_key = entry == keys[:, None]
            is_empty = entry == jnp.int64(EMPTY_KEY)
            pos_found = jnp.min(jnp.where(is_key, rng[None], C), axis=1)
            pos_empty = jnp.min(jnp.where(is_empty, rng[None], C), axis=1)
            found = (~done) & (pos_found < pos_empty)
            fslot = jnp.take_along_axis(
                idx, jnp.minimum(pos_found, C - 1)[:, None], axis=1)[:, 0]
        # claim the window's first empty; losers of the scatter-min resume
        # from the contested slot next iteration
        with jax.named_scope("probe.claim"):
            want = (~done) & ~found & (pos_empty < C)
            cslot = jnp.take_along_axis(
                idx, jnp.minimum(pos_empty, C - 1)[:, None], axis=1)[:, 0]
            claim_idx = jnp.where(want, cslot, jnp.int32(0))
            claim_val = jnp.where(want, keys, jnp.int64(EMPTY_KEY))
            table = table.at[claim_idx].min(claim_val)
            entry2 = table[cslot]
            won = want & (entry2 == keys)
        slot = jnp.where(found, fslot, slot)
        slot = jnp.where(won, cslot, slot)
        done = done | found | won
        base = jnp.where(
            done, base,
            base + jnp.where(want, pos_empty.astype(jnp.uint32),
                             jnp.uint32(CHUNK)))
        return table, base, slot, done

    def cond(state):
        _table, base, _slot, done = state
        return ((~done) & (base < MAX_PROBES)).any()

    start_done = (jnp.zeros(n, bool) if valid is None
                  else ~valid.astype(bool))
    init = (table_keys, jnp.zeros(n, jnp.uint32),
            jnp.full(n, -1, jnp.int32), start_done)
    table, _base, slot, done = jax.lax.while_loop(cond, body, init)
    return table, slot, done & (slot >= 0)
