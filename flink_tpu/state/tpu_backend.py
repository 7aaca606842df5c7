"""TpuKeyedStateBackend: device-resident keyed state.

The framework's answer to the reference's RocksDB backend
(flink-state-backends RocksDBKeyedStateBackend.java:114,
EmbeddedRocksDBStateBackend.java:100): instead of an LSM tree behind JNI,
keyed state for one subtask's key-group range lives in HBM as dense arrays
indexed by a device hash table (ops/hash_table.py). Registered under name
"tpu" in the backend registry (the StateBackendLoader seam).

Two access planes:
* **array states** — the hot path: named [capacity] or [ring, capacity]
  accumulator arrays updated by whole-batch scatter folds; used by the device
  window/aggregate operators. Rehash (growth) remaps every array on device;
  so does the reclaim, which comes first: when the table passes load 0.6
  the slots of keys that hold no data in any ring row any more (all their
  windows fired and retired) are freed at the SAME capacity, in one
  device program, and the table grows only if the live keys alone fill it
  (``reclaim``: a job's state is what its live windows hold, as in
  Flink's WindowOperator.clearAllState).
* **row states** — API-compatibility plane (ValueState etc.) with host-side
  gather/scatter per access; correct but slow, for small/irregular state.

Snapshots materialize (keys, key_groups, arrays) to host numpy, partitioned
by key group for rescaling restore — the device analog of key-group-ordered
snapshot streams.

Device keys must be int64 (Nexmark-style ids). Non-integer keys belong on
the host backend — the graph planner routes accordingly.
"""

from __future__ import annotations

import time
from concurrent.futures import ThreadPoolExecutor
from functools import partial
from typing import Any, Iterable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core.keygroups import KeyGroupRange, hash_batch, \
    key_groups_for_hash_batch
from ..metrics.device import DEVICE_STATS, _record_program_audit, \
    instrumented_program_cache
from ..ops.hash_table import (
    EMPTY_KEY, compacts, hash_keys_device, lookup, lookup_or_insert,
    make_table, sanitize_keys_device,
)
from ..ops.segment_ops import AGG_INITS, Halves, make_plane, \
    folds_by_limbs, plane_identity, plane_map, ring_fold, scatter_fold, \
    stores_halves
from .backend import KeyedStateBackend, State, ValueState, register_backend
from .descriptors import StateDescriptor
from .spill import HostTier
from .tiering import PrefetchPipeline, ResidencyManager

__all__ = ["TpuKeyedStateBackend", "reclaim_shard"]


def _sanitize_keys(keys: np.ndarray) -> np.ndarray:
    """Remap the EMPTY sentinel (int64 max) to int64 max - 1."""
    return np.where(keys == np.int64(EMPTY_KEY), np.int64(EMPTY_KEY) - 1,
                    keys.astype(np.int64))


def _tiering_params(config) -> dict:
    """Resolve state.tiering.* knobs (option defaults when the backend is
    constructed without a Configuration, e.g. directly in tests)."""
    from ..core.config import TieringOptions as T
    if config is None:
        return {"seed": T.SEED.default,
                "decay_interval": T.DECAY_INTERVAL.default,
                "decay_factor": T.DECAY_FACTOR.default,
                "promote_headroom": T.PROMOTE_HEADROOM.default,
                "promote_min_heat": T.PROMOTE_MIN_HEAT.default,
                "async_prefetch": T.ASYNC_PREFETCH.default}
    return {"seed": int(config.get(T.SEED)),
            "decay_interval": int(config.get(T.DECAY_INTERVAL)),
            "decay_factor": float(config.get(T.DECAY_FACTOR)),
            "promote_headroom": float(config.get(T.PROMOTE_HEADROOM)),
            "promote_min_heat": float(config.get(T.PROMOTE_MIN_HEAT)),
            "async_prefetch": bool(config.get(T.ASYNC_PREFETCH))}


# ----------------------------------------------------------------------
# typed row-plane programs (batched per-key value access; see
# TpuKeyedStateBackend.rows_* below). All scatters resolve duplicate keys
# within a batch DETERMINISTICALLY (last occurrence wins for writes,
# first occurrence admits for dedup) via first/last-position scatters.
# ----------------------------------------------------------------------

@instrumented_program_cache("state.reset_row")
def _reset_row_program(sig: tuple):
    """One jitted pane-retirement program per ring-plane signature: zero
    ring row ``row`` of every plane to its aggregate identity in a single
    dispatch. ``sig`` = tuple of (kind, dtype_str, shape); the row index is
    a traced scalar so one executable serves every row. State planes are
    donated off-CPU so XLA updates them in place."""
    donate = (0,)

    @partial(jax.jit, donate_argnums=donate)
    def reset(arrays: tuple, row):
        out = []
        with jax.named_scope("fire.reset"):
            for (kind, _dt, _shape), a in zip(sig, arrays):
                # the identity (its two words, for halves) into ONE row
                # of a donated buffer: no pass over the plane
                fill = make_plane(kind, (1,) + a.shape[1:], a.dtype,
                                  isinstance(a, Halves))
                out.append(plane_map(
                    lambda words, ident: jax.lax.dynamic_update_slice_in_dim(
                        words, ident, row, 0), a, fill))
        return tuple(out)

    return reset


@instrumented_program_cache("state.fold")
def _fold_program(sig: tuple):
    """One jitted fold per ring-plane signature: a batch into EVERY ring
    plane of a job in a single dispatch, ring row by ring row
    (``ops/segment_ops.ring_fold``). ``sig`` = tuple of (kind, dtype_str,
    shape), as ``_reset_row_program``'s; ``cols`` holds one value column
    a plane, or None where every row counts one. The planes are donated:
    the arrays passed in are deleted buffers afterwards, and only the
    ones returned are live. Where a plane folds by limbs
    (``_folds_by_limbs``: an additive 64-bit one) the program also takes
    ``limb_scatters``, the backend's running count, and returns (the
    planes, the count plus the limb scatters this fold ran): the count
    rides through the program, so that keeping it costs no dispatch of
    its own. Every other signature's program is what it was."""
    limbed = _folds_by_limbs(sig)

    @partial(jax.jit, donate_argnums=(0,))
    def fold(arrays: tuple, slots, ring_idx, valid, cols: tuple,
             limb_scatters=None):
        outs = []
        for (kind, _dt, _shape), a, c in zip(sig, arrays, cols):
            plane, ran = ring_fold(
                kind, a, ring_idx, slots,
                jnp.ones(slots.shape, a.dtype) if c is None else c,
                valid, counted=True)
            outs.append(plane)
            if folds_by_limbs(kind, isinstance(a, Halves)):
                limb_scatters = limb_scatters + ran
        return (tuple(outs), limb_scatters) if limbed else tuple(outs)

    return fold


def _folds_by_limbs(sig: tuple) -> bool:
    """Whether the fold of a plane signature runs limb scatters (and so
    carries the backend's count of them)."""
    return any(folds_by_limbs(kind, dt.startswith("halves:"))
               for kind, dt, _shape in sig)


#: live keys the reclaim re-homes at a time (one ``lookup_or_insert`` a
#: chunk, inside the program; the last chunk costs what a full one does,
#: so a quarter of a Q5 batch, which keeps a reclaim's time in step with
#: the keys it moves)
_RECLAIM_CHUNK = 1 << 16


def _sorted_slots(rank: jax.Array, ranks: int) -> jax.Array:
    """The slots ``0..C-1`` ordered by ``rank`` (an int32 in
    ``[0, ranks)``) and, within a rank, by slot: one single-operand sort
    of the distinct words ``rank * C + slot`` (so it need not be stable:
    on the v5e a stable sort of 2^23 words takes three times as long to
    compile as an unstable one)."""
    C = rank.shape[0]
    word = jnp.int32 if ranks * C <= 1 << 31 else jnp.int64
    keyed = jax.lax.sort(rank.astype(word) * C
                         + jnp.arange(C, dtype=word), is_stable=False)
    return (keyed % C).astype(jnp.int32)


def _permute(where: jax.Array, values):
    """``out[where[i]] = values[i]`` for a permutation ``where`` of
    ``0..C-1``, as a sort by it (distinct keys: no stability needed);
    the two words of a ``Halves`` row ride ONE sort as its two payloads
    (what the compiler makes of a 64-bit payload anyway)."""
    words, row = jax.tree_util.tree_flatten(values)
    return row.unflatten(jax.lax.sort((where, *words), num_keys=1,
                                      is_stable=False)[1:])


def _differs(plane, ident) -> jax.Array:
    """Where a plane (or a row of one) is not ``ident`` (``plane_identity``):
    a ``Halves`` plane is tested word against word, never joined."""
    if isinstance(plane, Halves):
        return (plane.hi != ident.hi) | (plane.lo != ident.lo)
    return plane != ident


def reclaim_shard(sig: tuple, table, arrays: tuple, dropped):
    """The reclaim of ONE table and its planes, traceable and not jitted:
    the one-chip backend jits it as it is (``_reclaim_program``) and the
    mesh runs it on every shard under ``shard_map`` (``parallel/
    sharded_window._make_reclaim``), so the three steps exist once. The
    table rebuilt AT ITS OWN CAPACITY from the keys that still hold data,
    every plane re-seated onto the new slots, at fixed shapes. ``sig`` =
    tuple of (kind, dtype_str, shape) over ALL of the table's array
    states, as ``_reset_row_program``'s: ring planes every one, and each
    says what lives. A plane is the ``Halves`` of a 64-bit integer one
    or one array (a float or 32-bit plane): told apart by what is handed
    in, tested and moved word by word, and handed back as it came.

    * ``reclaim.live``: a slot lives iff it is occupied and some ring row
      of some plane differs from its aggregate's identity there (the
      hidden plane alone would do: every fold counts into it, or marks
      it where it is a presence plane). One sort
      of the slots puts first the live keys that must move (they sit
      past their home slot, and a freed slot before them would hide them
      from the probe), then the live keys AT their home slot, then the
      rest.
    * ``reclaim.rehome``: the new table starts from the live keys at
      home, where they are; the live keys that must move go through
      ``lookup_or_insert`` into it, ``_RECLAIM_CHUNK`` at a time, only as
      many chunks as hold one. A freed slot is never set to EMPTY under a
      key that stays behind it (``lookup_or_insert`` decides containment
      by first match before first empty): every key of the new table has
      no EMPTY between its home and its slot, and the table is
      insert-only from there on. The slots no key landed in take the
      freed and empty old slots, in order, so that old slot -> new slot
      (``dest``) is a permutation.
    * ``reclaim.remap``: every plane, row by row in place: the row
      sorted by ``dest`` IS the row re-seated (a sort moves
      a ring row of 2^23 cells in tens of milliseconds where a gather by
      index costs half a second a 32-bit word on the v5e), identity where
      no key landed; a row that holds nothing but identities (a retired
      pane) is left as it is.

    Returns (table, planes, dropped, [kept, freed]); ``dropped`` is the
    caller's counter passed through, plus the live keys that found no
    slot within MAX_PROBES (0 short of a pathological key set; the next
    health check then fails the job as for any dropped insert). What is
    donated is the caller's."""
    C = table.shape[0]
    B = min(C, _RECLAIM_CHUNK)
    slot = jnp.arange(C, dtype=jnp.int32)
    lane = jnp.arange(B, dtype=jnp.int32)
    empty = jnp.int64(EMPTY_KEY)
    with jax.named_scope("reclaim.live"):
        occupied = table != empty
        holds = jnp.zeros(C, bool)
        for (kind, _dt, _shape), a in zip(sig, arrays):
            holds = holds | _differs(
                a, plane_identity(kind, a)).any(axis=0)
        live = occupied & holds
        home = (hash_keys_device(table) & jnp.uint32(C - 1)).astype(
            jnp.int32) == slot
        moves = live & ~home
        kept = jnp.sum(live, dtype=jnp.int32)
        n_moves = jnp.sum(moves, dtype=jnp.int32)
        freed = jnp.sum(occupied, dtype=jnp.int32) - kept
        order = _sorted_slots(
            jnp.where(moves, 0, jnp.where(live, 1, 2)), 3)

    with jax.named_scope("reclaim.rehome"):
        def rehome(i, carry):
            new_table, new_of, lost = carry
            src = jax.lax.dynamic_slice(order, (i * B,), (B,))
            valid = i * B + lane < n_moves
            new_table, slots, ok = lookup_or_insert(
                new_table, table[src], valid, handover=compacts(B),
                distinct=True)     # keys of a table
            new_of = jax.lax.dynamic_update_slice(
                new_of, jnp.where(ok, slots, src), (i * B,))
            return (new_table, new_of,
                    lost + jnp.sum(valid & ~ok, dtype=jnp.int32))

        # new_of[p]: where the p-th slot of ``order`` goes; a key at
        # home stays where it is
        new_table, new_of, lost = jax.lax.fori_loop(
            0, (n_moves + B - 1) // B, rehome,
            (jnp.where(live & home, table, empty), order,
             jnp.int32(0)))
        landed = new_table != empty
        free = _sorted_slots(landed.astype(jnp.int32), 2)
        new_of = jnp.where(slot < kept, new_of, jnp.roll(free, kept))
        dest = _permute(order, new_of)

    with jax.named_scope("reclaim.remap"):
        out = []
        for (kind, _dt, _shape), a in zip(sig, arrays):
            ident = plane_identity(kind, a)

            def reseat(row, ident=ident):
                return jax.lax.cond(
                    _differs(row, ident).any(),
                    lambda r: plane_map(
                        lambda moved, i: jnp.where(landed, moved, i),
                        _permute(dest, r), ident),
                    lambda r: r, row)

            def body(r, plane, reseat=reseat):
                row = plane_map(
                    lambda words: jax.lax.dynamic_index_in_dim(
                        words, r, 0, keepdims=False), plane)
                return plane_map(
                    lambda words, new: jax.lax.dynamic_update_index_in_dim(
                        words, new, r, 0), plane, reseat(row))

            out.append(jax.lax.fori_loop(0, a.shape[0], body, a))
    return (new_table, tuple(out), dropped + lost.astype(dropped.dtype),
            jnp.stack([kept, freed]))


@instrumented_program_cache("state.reclaim")
def _reclaim_program(sig: tuple):
    """One jitted reclaim per plane signature: ``reclaim_shard`` over the
    backend's table and ALL of its array states in a single fixed-shape
    dispatch. The planes are donated; the old table is not (a fire still
    in the drain queue may hold it as an output)."""

    @partial(jax.jit, donate_argnums=(1,))
    def reclaim(table, arrays: tuple, dropped):
        return reclaim_shard(sig, table, arrays, dropped)

    return reclaim


@jax.jit
def _rows_set(vals, present, last_ts, slots, new_vals, now):
    B = slots.shape[0]
    cap = vals.shape[0]
    widx = jnp.where(slots >= 0, slots, cap).astype(jnp.int32)
    lastpos = jnp.full(cap + 1, -1, jnp.int32).at[widx].max(
        jnp.arange(B, dtype=jnp.int32))
    widx = jnp.where(jnp.arange(B, dtype=jnp.int32) == lastpos[widx],
                     widx, cap)
    vals = vals.at[widx].set(new_vals.astype(vals.dtype), mode="drop")
    present = present.at[widx].set(jnp.int8(1), mode="drop")
    if last_ts is not None:
        last_ts = last_ts.at[widx].set(now, mode="drop")
    return vals, present, last_ts


@jax.jit
def _rows_get(table, vals, present, last_ts, keys, now, ttl_ms):
    slots = lookup(table, keys)
    found = slots >= 0
    sc = jnp.maximum(slots, 0)
    p = (present[sc] > 0) & found
    if last_ts is not None:
        p = p & ((now - last_ts[sc]) <= ttl_ms)
    return vals[sc], p


@jax.jit
def _rows_unset(table, present, keys):
    slots = lookup(table, keys)
    cap = present.shape[0]
    widx = jnp.where(slots >= 0, slots, cap).astype(jnp.int32)
    return present.at[widx].set(jnp.int8(0), mode="drop"), \
        jnp.maximum(slots, 0)


@jax.jit
def _dedup_first(table, present, last_ts, keys, valid, ts, ttl_ms):
    """Keep-first admission: fresh[i] iff row i is valid, its key admits
    (absent / cleared / TTL-expired in state), and i is the key's first
    occurrence in this batch. Presence is claimed for admitted keys; the
    TTL clock refreshes on admission only (keep-first write semantics)."""
    B = keys.shape[0]
    cap = present.shape[0]
    table, slots, ok = lookup_or_insert(table, keys, valid)
    widx = jnp.where(ok, slots, cap).astype(jnp.int32)
    firstpos = jnp.full(cap + 1, B, jnp.int32).at[widx].min(
        jnp.arange(B, dtype=jnp.int32))
    is_first = jnp.arange(B, dtype=jnp.int32) == firstpos[widx]
    sc = jnp.maximum(slots, 0)
    was = (present[sc] > 0) & ok
    if last_ts is not None:
        was = was & ((ts - last_ts[sc]) <= ttl_ms)
    fresh = ok & ~was & is_first
    present = present.at[widx].set(jnp.int8(1), mode="drop")
    if last_ts is not None:
        fidx = jnp.where(fresh, slots, cap).astype(jnp.int32)
        last_ts = last_ts.at[fidx].set(ts, mode="drop")
    overflow = jnp.any(valid & ~ok)
    occ = (table != jnp.int64(EMPTY_KEY)).sum()
    return table, present, last_ts, fresh, sc, overflow, occ


class _ArrayState:
    __slots__ = ("name", "kind", "dtype", "ring", "array")

    def __init__(self, name: str, kind: str, dtype, ring: Optional[int],
                 capacity: int):
        self.name = name
        self.kind = kind
        self.dtype = dtype
        self.ring = ring
        # a ring plane of a 64-bit integer is STORED as its two
        # 32-bit words (ops/segment_ops.Halves), on every platform: every
        # program takes and returns the words, and 64-bit values exist
        # only inside a program, of the rows or cells it has sliced
        shape = (ring, capacity) if ring else (capacity,)
        self.array = make_plane(kind, shape, dtype,
                                stores_halves(dtype, ring))


def _plane_sig(states) -> tuple:
    """What the plane programs (reset, fold, reclaim) are cached by:
    (kind, dtype_str, shape) of each plane they take; a plane stored as
    ``Halves`` says so in its dtype_str (``halves:int64``)."""
    return tuple((st.kind,
                  ("halves:" if isinstance(st.array, Halves) else "")
                  + str(st.array.dtype), st.array.shape)
                 for st in states)


def _to_host(plane) -> np.ndarray:
    """A plane (or a part of one) on the host, in its own dtype and
    writable: a ``Halves`` joined with numpy."""
    # lint: sync-ok the snapshot, spill and rebuild paths' one transfer a plane
    host = jax.device_get(plane)
    return host.join() if isinstance(host, Halves) else np.array(host)


def _in_layout(plane, values):
    """``values`` (the plane's own dtype, numpy or device) in ``plane``'s
    stored layout: split into words for a ``Halves`` plane."""
    if not isinstance(plane, Halves):
        return jnp.asarray(values)
    if isinstance(values, np.ndarray):
        return Halves.split(values.astype(plane.dtype, copy=False)).map(
            jnp.asarray)
    return Halves.split(jnp.asarray(values, plane.dtype))


class TpuKeyedStateBackend(KeyedStateBackend):
    # the row plane is ValueState-only: operators needing namespaced list/
    # aggregating state (host WindowOperator) must fall back to hashmap
    SUPPORTS_GENERAL_STATE = False

    def __init__(self, key_group_range: KeyGroupRange, max_parallelism: int,
                 capacity: int = 1 << 16, config=None,
                 defer_overflow: bool = False,
                 hbm_budget_slots: int = 0, **_kw):
        super().__init__(key_group_range, max_parallelism)
        cap = 1
        while cap < capacity:
            cap <<= 1
        self.capacity = cap
        self.table = make_table(cap)
        self._array_states: dict[str, _ArrayState] = {}
        self._row_states: dict[str, State] = {}
        self._row_meta: dict[str, int] = {}  # row-plane name -> ttl_ms
        # host-tracked occupancy, as of the last health reading, sync-mode
        # batch or rebuild (the table is insert-only BETWEEN two rebuilds:
        # growth, eviction, reclaim)
        self._num_keys = 0
        # deferred mode: the hot path never syncs with the host; overflow
        # accumulates in a device counter checked at watermark boundaries
        self._defer = bool(defer_overflow)
        self._dropped = jnp.zeros((), jnp.int64)
        # the probe's counters (rows, tail rows, wide batches, rows its
        # half-width first window left undecided) accumulate on the
        # device like _dropped; note_probe_stats hands them to
        # DEVICE_STATS once a copy taken at an earlier batch has landed
        self._probe = jnp.zeros(4, jnp.int64)
        # beside them what the wide-batch program's election counts (rows
        # that stood behind a representative, batches that elected); only
        # a batch through that program adds to it
        self._elected = jnp.zeros(2, jnp.int64)
        # and the limb scatters the folds ran (ops/segment_ops.ring_fold:
        # an additive 64-bit plane takes a batch limb by limb), which
        # ride THROUGH the fold program: it takes the count and returns
        # it moved on
        self._limb_scatters = jnp.zeros(1, jnp.int64)
        self._probe_sent: Optional[tuple[jax.Array, ...]] = None
        self._probe_noted = np.zeros(7, np.int64)
        # probes dispatched, and how many of them the counters last sent
        # and last noted had seen (note_probe_stats)
        self._probe_calls = self._probe_sent_calls = 0
        self._probe_noted_calls = 0
        # whether EVERY batch last noted left more rows unresolved after
        # its first window than the probe's narrow loops hold: the next
        # batch then takes the program in which one lane a distinct key
        # enters the rounds and the full-width rounds hand over to the
        # narrow loops (ops/hash_table.lookup_or_insert)
        self._probe_wide = False
        # the probe programs (by ``handover``) this backend has put in
        # the program audit
        self._probe_audited: set[bool] = set()
        # the wide-batch program built ahead for a new table:
        # (table shape, batch shape, executable) (prepare)
        self._wide_probe: Optional[tuple] = None
        # the table's generation: every rebuild (growth, eviction,
        # reclaim, restore) moves the slots and starts a new one; a
        # health reading taken under an older one says nothing of this
        # table (table_generation, apply_health)
        self._generation = 0
        # plane signature the reclaim's program was last built ahead for
        # (prepare_reclaim)
        self._reclaim_built: Optional[tuple] = None
        # a reclaim dispatched whose counts have not landed yet:
        # (device [kept, freed], the caller's open stage span)
        self._reclaiming: Optional[tuple] = None
        # spill tier: device capacity is capped at the HBM budget; cold key
        # groups page out to host RAM (state/spill.py). 0 = unlimited.
        # With defer_overflow the split is computed ON DEVICE (spilled-group
        # mask + staging compaction in the fused step; see
        # runtime/operators/device_window._step_program) so the hot path
        # still never syncs — round-3 unification of VERDICT r2 weak #4.
        budget = 0
        if hbm_budget_slots:
            budget = 1
            while budget * 2 <= hbm_budget_slots:
                budget <<= 1
            if cap > budget:
                # the budget wins: start at the cap the device may use
                cap = budget
                self.capacity = cap
                self.table = make_table(cap)
        self._budget = budget
        self._host: Optional[HostTier] = None
        self._batch_no = 0
        # tiered residency (state/tiering/): the manager owns the decayed
        # 2Q heat policy deciding WHICH groups evict/promote; the pipeline
        # stages warm->hot promotions off the mailbox thread. Both exist
        # only under a budget; decisions apply at batch boundaries
        # (tier_boundary) and on overflow pressure (_evict_cold_groups).
        self._residency: Optional[ResidencyManager] = None
        self._prefetch: Optional[PrefetchPipeline] = None
        if budget:
            params = _tiering_params(config)
            self._residency = ResidencyManager(
                max_parallelism, budget,
                seed=params["seed"],
                decay_interval=params["decay_interval"],
                decay_factor=params["decay_factor"],
                promote_headroom=params["promote_headroom"],
                promote_min_heat=params["promote_min_heat"])
            self._prefetch = PrefetchPipeline(
                self._stage_promotion,
                asynchronous=params["async_prefetch"])
        self._pending_host: Optional[tuple[np.ndarray, np.ndarray]] = None
        # -- incremental snapshot capture (delta CAPTURE, the analog of
        # RocksIncrementalSnapshotStrategy.java:70's SST diff): a device
        # dirty bitmap over slot blocks + a host mirror of the last
        # snapshot. A snapshot transfers only dirty blocks and patches the
        # mirror; ring-row retirements replay host-side (no device work).
        self._block = min(512, self.capacity)    # slots per dirty block
        self._n_blocks = self.capacity // self._block
        self._dirty = jnp.zeros(self._n_blocks, bool)
        self._mirror: Optional[dict] = None
        self._retired_rows: set[int] = set()
        self.last_snapshot_dma_bytes = 0
        # deferred-spill device mirrors: spilled-group mask (read by the
        # fused step) and per-group last-touch (device LRU clock)
        self._spilled_dev: Optional[jax.Array] = None
        self._touch_dev: Optional[jax.Array] = None

    # ------------------------------------------------------------------
    # hot path: batched slot resolution + scatter folds
    # ------------------------------------------------------------------
    def slots_for_batch(self, keys: np.ndarray) -> jax.Array:
        """Lookup-or-insert a batch of int64 keys. In the default
        (synchronous) mode the table grows by rehash on overflow, at the
        cost of one host sync per batch. In deferred mode (the pipelined
        bench/production path) there is NO sync: failed inserts return
        negative slots (the fold skips them), a device drop counter
        accumulates, and ``check_health`` at the next watermark raises /
        grows. Returns device int32 slots."""
        keys = _sanitize_keys(np.asarray(keys))
        if self._defer:
            return self.slots_for_batch_device(jnp.asarray(keys))
        self._pending_host = None
        groups = None
        if self._budget:
            self._batch_no += 1
            groups = key_groups_for_hash_batch(hash_batch(keys),
                                               self.max_parallelism)
            self._residency.observe(
                groups, self._batch_no,
                self._host.spilled_mask if self._host is not None else None)
        dkeys = jnp.asarray(keys)
        while True:
            # keep the device call's shapes CONSTANT across batches (one
            # compiled executable): spilled rows ride along masked invalid
            # instead of being sliced out
            if (self._host is not None and self._host.active
                    and groups is not None):
                sp = self._host.spilled_mask[groups]
                if not sp.any():
                    sp = None
            else:
                sp = None
            dvalid = None if sp is None else jnp.asarray(~sp)
            new_table, slots, ok = lookup_or_insert(self.table, dkeys,
                                                    dvalid)
            ok_all = ok.all() if sp is None else (ok | jnp.asarray(sp)).all()
            all_ok, occupancy = jax.device_get(
                (ok_all, (new_table != EMPTY_KEY).sum()))
            if bool(all_ok):
                self.table = new_table
                self._num_keys = int(occupancy)
                if self._num_keys > 0.6 * self.capacity:
                    if not self._budget or 2 * self.capacity <= self._budget:
                        self._rehash(self.capacity * 2)
                        # slots against the pre-rehash table are stale
                        slots = lookup(self.table, dkeys)
                    else:
                        self._evict_cold_groups(batch_groups=groups)
                        continue  # spilled set changed; re-split the batch
                break
            if not self._budget or 2 * self.capacity <= self._budget:
                self._rehash(self.capacity * 2)
            else:
                self._evict_cold_groups(batch_groups=groups)
        if sp is not None:
            host_pos = np.flatnonzero(sp)
            hslots = self._host.slots_for(keys[host_pos])
            self._host.host_folds += 1
            self._pending_host = (host_pos, hslots)
        self.mark_dirty(slots)
        return slots

    # -- incremental snapshot capture ----------------------------------
    @property
    def dirty_block_size(self) -> int:
        return self._block

    def mark_dirty(self, slots) -> None:
        """Mark the dirty blocks containing ``slots`` (device or numpy).
        Invalid slots (<0) conservatively mark block 0."""
        idx = jnp.maximum(jnp.asarray(slots), 0) // self._block
        self._dirty = self._dirty.at[idx].set(True)

    def set_dirty_mask(self, dirty: jax.Array) -> None:
        """Adopt a dirty mask updated inside a fused step program."""
        self._dirty = dirty

    @property
    def dirty_mask(self) -> jax.Array:
        return self._dirty

    def _invalidate_mirror(self) -> None:
        """Structural change (rehash/evict/restore/ring conform): the next
        snapshot re-captures everything."""
        self._mirror = None
        self._block = min(512, self.capacity)
        self._n_blocks = self.capacity // self._block
        self._dirty = jnp.zeros(self._n_blocks, bool)
        self._retired_rows.clear()

    def _sync_mirror(self) -> None:
        """Bring the host mirror up to date with device state, transferring
        only dirty blocks (plus any state registered since the mirror was
        built). Tracks the DMA bytes of this capture.

        Deadline-bounded (fault site transfer.d2h; the deadline is the
        CHECKPOINT timeout — this is a bulk snapshot-path capture, not a
        per-batch transfer — and there is no in-place retry: the mirror
        update mutates self, so a stall propagates as StallError — a
        wedged snapshot capture then fails the checkpoint/evacuation
        instead of freezing it, and recovery rides the restart path)."""
        from ..runtime.watchdog import WATCHDOG

        def _capture():
            from ..runtime.faults import fire_with_retries
            fire_with_retries("transfer.d2h", scope="tpu_backend.snapshot")
            self._sync_mirror_inner()

        WATCHDOG.run("transfer.d2h", _capture, scope="tpu_backend.snapshot",
                     deadline=WATCHDOG.deadline_for("checkpoint.write"))

    def _sync_mirror_inner(self) -> None:
        nb, bs = self._n_blocks, self._block
        self.last_snapshot_dma_bytes = 0
        snap_states = self._array_states.items()
        if self._mirror is None:
            # writable copies: device_get may return read-only views
            t = np.array(jax.device_get(self.table))
            arrs = {n: _to_host(st.array) for n, st in snap_states}
            self._mirror = {"table": t, "arrays": arrs}
            self.last_snapshot_dma_bytes = t.nbytes + sum(
                a.nbytes for a in arrs.values())
        else:
            arrs = self._mirror["arrays"]
            for n, st in snap_states:
                if n not in arrs:
                    a = _to_host(st.array)
                    arrs[n] = a
                    self.last_snapshot_dma_bytes += a.nbytes
            # ① replay ring-row retirements host-side (no DMA)
            for row in self._retired_rows:
                for n, st in snap_states:
                    if st.ring:
                        arrs[n][row, :] = np.asarray(
                            AGG_INITS[st.kind](st.dtype))
            # ② patch dirty blocks: gather on device, ONE transfer
            d = np.asarray(jax.device_get(self._dirty))
            self.last_snapshot_dma_bytes += d.nbytes
            blocks = np.flatnonzero(d)
            if len(blocks):
                bidx = jnp.asarray(blocks)
                parts = {"__table__": self.table.reshape(nb, bs)[bidx]}
                for n, st in snap_states:
                    # the blocks of each word of a halves plane: the same
                    # bytes cross, and the host joins them
                    parts[n] = plane_map(
                        lambda a: a.reshape(a.shape[:-1] + (nb, bs))[
                            ..., bidx, :], st.array)
                host = jax.device_get(parts)
                self.last_snapshot_dma_bytes += sum(
                    v.nbytes for v in host.values())
                self._mirror["table"].reshape(nb, bs)[blocks] = \
                    np.asarray(host["__table__"])
                for n, st in snap_states:
                    a, p = arrs[n], np.asarray(host[n])
                    if st.ring:
                        a.reshape(a.shape[0], nb, bs)[:, blocks] = p
                    else:
                        a.reshape(nb, bs)[blocks] = p
        self._retired_rows.clear()
        self._dirty = jnp.zeros(nb, bool)

    def _rehash(self, new_capacity: int) -> None:
        """Grow the table and remap every array state on device."""
        old_table = self.table
        occupied = jax.device_get(old_table != EMPTY_KEY)
        old_keys = jax.device_get(old_table)[occupied]
        old_slots = np.flatnonzero(occupied).astype(np.int32)
        self._rebuild_device(old_keys, old_slots, new_capacity)

    def _rebuild_device(self, keep_keys: np.ndarray,
                        old_slots: np.ndarray, new_capacity: int) -> None:
        """Re-key the device table to ``keep_keys`` only (rehash growth or
        post-eviction shrink of the resident set), remapping every array
        state's rows on device."""
        self._finish_reclaim(block=True)   # its counts are of the old table
        old_arrays = {n: st.array for n, st in self._array_states.items()}
        new_table = make_table(new_capacity)
        if len(keep_keys):
            new_table, new_slots, ok = lookup_or_insert(
                new_table, jnp.asarray(keep_keys))
            if not bool(jax.device_get(ok.all())):  # pragma: no cover
                raise RuntimeError(
                    "rebuild failed: pathological key distribution")
        self.table = new_table
        self.capacity = new_capacity
        self._num_keys = len(keep_keys)
        for name, st in self._array_states.items():
            shape = ((st.ring, new_capacity) if st.ring else (new_capacity,))
            new_arr = make_plane(st.kind, shape, st.dtype,
                                 isinstance(st.array, Halves))
            if len(keep_keys):
                # cells move as they are: word by word for a halves plane
                new_arr = plane_map(
                    lambda new, old: new.at[..., new_slots].set(
                        old[..., jnp.asarray(old_slots)]),
                    new_arr, old_arrays[name])
            st.array = new_arr
        self._invalidate_mirror()
        self._new_generation()

    # ------------------------------------------------------------------
    # spill tier (HBM budget; state/spill.py)
    # ------------------------------------------------------------------
    @property
    def spill_active(self) -> bool:
        return self._host is not None and self._host.active

    @property
    def host_tier(self) -> Optional[HostTier]:
        return self._host

    def _evict_cold_groups(self, rebuild_capacity: Optional[int] = None,
                           batch_groups: Optional[np.ndarray] = None
                           ) -> None:
        """Page the coldest resident key groups to the host tier —
        deadline-bounded under site ``tier.evict`` (the d2h pull plus the
        device-table rebuild used to run unbounded inline on the mailbox
        thread; a wedged DMA now raises StallError into the restart path
        instead of freezing ingest). The fault site fires BEFORE any
        state moves: a transient trip retries with nothing mutated, a
        persistent one fails the batch."""
        from ..runtime.faults import fire_with_retries
        from ..runtime.watchdog import WATCHDOG
        fire_with_retries("tier.evict", scope="tpu_backend.tier")
        WATCHDOG.run(
            "tier.evict",
            lambda: self._evict_cold_groups_inner(rebuild_capacity,
                                                  batch_groups),
            scope="tpu_backend.tier")

    def _evict_cold_groups_inner(self,
                                 rebuild_capacity: Optional[int] = None,
                                 batch_groups: Optional[np.ndarray] = None
                                 ) -> None:
        """Eviction body: the unit of movement is the key group
        (KeyGroupRangeAssignment.java:63), coldest first by the residency
        policy's decayed 2Q order (probationary by recency, then
        protected by heat). When the resident set alone cannot make room
        (e.g. one batch introduces more new keys than the whole budget),
        groups OF THE INCOMING BATCH are marked spilled too — each call
        spills at least one, so the caller's retry loop always
        terminates."""
        from ..metrics.tracing import TRACER
        self._ensure_host_tier()
        cap = rebuild_capacity or self.capacity
        with TRACER.span("tier", "Evict") as sp:
            keys_dev, slots_dev, groups_dev = self._device_resident()
            counts = np.bincount(groups_dev,
                                 minlength=self.max_parallelism)
            resident = np.flatnonzero(counts > 0)
            order = self._residency.eviction_order(resident)
            target = int(0.4 * cap)
            need = max(len(keys_dev) - target, max(1, len(keys_dev) // 4))
            evict_groups, acc = [], 0
            for g in order:
                evict_groups.append(int(g))
                acc += int(counts[g])
                if acc >= need:
                    break
            if acc < need and batch_groups is not None:
                # resident set can't make room: spill half the incoming
                # batch's (not yet spilled) groups as well
                fresh = np.unique(batch_groups)
                fresh = fresh[~self._host.spilled_mask[fresh]]
                fresh = [int(g) for g in fresh
                         if g not in set(evict_groups)]
                evict_groups.extend(fresh[:max(1, len(fresh) // 2)])
            if not evict_groups:
                raise RuntimeError(
                    "spill eviction made no progress; raise the HBM "
                    "budget")
            gmask = np.zeros(self.max_parallelism, bool)
            gmask[evict_groups] = True
            sel = gmask[groups_dev]
            self._absorb_and_rebuild(keys_dev, slots_dev, sel,
                                     evict_groups, cap)
            self._residency.note_demoted(np.asarray(evict_groups, np.int64))
            DEVICE_STATS.note_tier_eviction(len(evict_groups),
                                            int(sel.sum()))
            sp.set_attribute("groups", len(evict_groups))
            sp.set_attribute("keys", int(sel.sum()))

    # -- deferred spill (device-side split; see device_window) ----------
    @property
    def is_deferred(self) -> bool:
        return self._defer

    @property
    def hbm_budget(self) -> int:
        return self._budget

    @property
    def spilled_mask_device(self) -> jax.Array:
        if self._spilled_dev is None:
            self._spilled_dev = jnp.zeros(self.max_parallelism, bool)
        return self._spilled_dev

    @property
    def touch_device(self) -> jax.Array:
        if self._touch_dev is None:
            self._touch_dev = jnp.zeros(self.max_parallelism, jnp.int64)
        return self._touch_dev

    def set_touch_device(self, touch: jax.Array) -> None:
        self._touch_dev = touch

    def note_batch(self) -> int:
        """Monotone batch clock for the device LRU."""
        self._batch_no += 1
        return self._batch_no

    def _sync_spilled_dev(self) -> None:
        if self._host is not None:
            self._spilled_dev = jnp.asarray(self._host.spilled_mask)

    def _sync_touch_from_device(self) -> None:
        """Merge the on-device per-group touch clock into the residency
        policy (deferred spill path: the fused step maintains the clock,
        the policy only sees it at boundaries / eviction time)."""
        if self._touch_dev is not None and self._residency is not None:
            self._residency.adopt_clock(
                np.asarray(jax.device_get(self._touch_dev)),
                self._host.spilled_mask if self._host is not None else None)

    def _ensure_host_tier(self) -> HostTier:
        if self._host is None:
            self._host = HostTier(self.max_parallelism)
        for name, st in self._array_states.items():
            self._host.register(name, st.kind, np.dtype(jnp.dtype(st.dtype)),
                                st.ring)
        return self._host

    def _device_resident(self) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        """(keys, slots, key_groups) of every device-resident entry."""
        t = np.asarray(jax.device_get(self.table))
        occupied = t != np.int64(EMPTY_KEY)
        keys_dev = t[occupied]
        slots_dev = np.flatnonzero(occupied).astype(np.int32)
        g_dev = key_groups_for_hash_batch(hash_batch(keys_dev),
                                          self.max_parallelism)
        return keys_dev, slots_dev, g_dev

    def _absorb_and_rebuild(self, keys_dev: np.ndarray,
                            slots_dev: np.ndarray, sel: np.ndarray,
                            groups, cap: int) -> None:
        """Shared spill tail: move the selected device rows into the host
        tier, mark their groups spilled, rebuild the device table without
        them (used by LRU eviction AND the deferred-drain force-spill so
        the two paths cannot diverge)."""
        host = self._ensure_host_tier()
        if sel.any():
            values = {}
            for name, st in self._array_states.items():
                values[name] = _to_host(st.array)[..., slots_dev[sel]]
            host.absorb(keys_dev[sel], values)
        host.spilled_mask[np.asarray(groups, np.int64)] = True
        if sel.any() or cap != self.capacity:
            self._rebuild_device(keys_dev[~sel], slots_dev[~sel], cap)
        self._sync_spilled_dev()

    def _force_spill_groups(self, groups: np.ndarray) -> None:
        """Page the given key groups to the host tier NOW (deferred-spill
        drain: a group touched by staging overflow becomes host-resident
        so no key is ever split across tiers). Same guarded demotion as
        `_evict_cold_groups`: the `tier.evict` fault site fires BEFORE
        anything moves, the move runs under the watchdog deadline, and
        the residency manager accounts the demotion."""
        groups = np.asarray(groups, np.int64)
        from ..runtime.faults import fire_with_retries
        from ..runtime.watchdog import WATCHDOG
        fire_with_retries("tier.evict", scope="tpu_backend.tier")
        WATCHDOG.run("tier.evict",
                     lambda: self._force_spill_groups_inner(groups),
                     scope="tpu_backend.tier")

    def _force_spill_groups_inner(self, groups: np.ndarray) -> None:
        from ..metrics.tracing import TRACER
        with TRACER.span("tier", "Evict") as sp:
            keys_dev, slots_dev, g_dev = self._device_resident()
            gmask = np.zeros(self.max_parallelism, bool)
            gmask[groups] = True
            sel = gmask[g_dev]
            self._absorb_and_rebuild(keys_dev, slots_dev, sel, groups,
                                     self.capacity)
            if self._residency is not None:
                self._residency.note_demoted(groups)
            DEVICE_STATS.note_tier_eviction(len(groups), int(sel.sum()))
            sp.set_attribute("groups", int(len(groups)))
            sp.set_attribute("keys", int(sel.sum()))
            sp.set_attribute("forced", True)

    def drain_staged(self, keys: np.ndarray, ring_idx: np.ndarray,
                     values: dict[str, np.ndarray]) -> None:
        """Fold rows the fused step staged for the host (spilled-group
        records + failed inserts) into the host tier. Groups seen here for
        the first time are force-spilled first, so their device rows merge
        before the fold and future records route host-side on device."""
        if len(keys) == 0:
            return
        keys = _sanitize_keys(np.asarray(keys))
        host = self._ensure_host_tier()
        groups = key_groups_for_hash_batch(hash_batch(keys),
                                           self.max_parallelism)
        fresh = np.unique(groups[~host.spilled_mask[groups]])
        if len(fresh):
            self._force_spill_groups(fresh)
        hslots = host.slots_for(keys)
        host.host_folds += 1
        for name, vals in values.items():
            st = self._array_states[name]
            host.fold(name, hslots, np.asarray(vals),
                      np.asarray(ring_idx) if st.ring else None)

    # ------------------------------------------------------------------
    # tiered residency (state/tiering/): promotion pipeline + boundary hook
    # ------------------------------------------------------------------
    @property
    def tiering_active(self) -> bool:
        return self._residency is not None

    @property
    def residency(self) -> Optional[ResidencyManager]:
        return self._residency

    @property
    def prefetch_pipeline(self) -> Optional[PrefetchPipeline]:
        return self._prefetch

    def _hbm_bytes_in_use(self) -> int:
        """Device bytes held by the keyed-state planes (table + every
        array state). Shape metadata only — never a device sync."""
        total = int(self.table.nbytes)
        for st in self._array_states.values():
            total += int(st.array.nbytes)
        return total

    def tier_boundary(self) -> None:
        """Batch-boundary tiering step, called by the operator after the
        staged-spill drain (so nothing is in flight for any group):
        advance the decay cadence, queue promotion candidates on the
        prefetch pipeline, and apply at most one staged payload."""
        if self._residency is None:
            return
        self._sync_touch_from_device()
        self._residency.on_boundary()
        host = self._host
        if host is not None and host.active and self._prefetch is not None:
            cands = self._residency.promotion_candidates(
                host.spilled_mask, host.group_counts(), self._num_keys,
                self.capacity)
            if len(cands):
                self._prefetch.request(cands)
            payload = self._prefetch.poll()
            if payload is not None:
                self.apply_promotion(payload)
            self._residency.update_view(host.spilled_mask,
                                        host.group_counts())
        DEVICE_STATS.set_tier_hbm_bytes(self._hbm_bytes_in_use())

    def _stage_promotion(self, groups: np.ndarray) -> Optional[dict]:
        """Gather ``groups``' warm rows and upload the staged device
        arrays (runs on the prefetch thread in async mode). The gather is
        read-only and versioned: apply_promotion re-validates against
        the host tier's mutation counter, so a payload raced by a
        concurrent fold is re-gathered, never applied stale. Keys pad to
        the next power of two (valid-masked) so the insert and scatters
        reuse a bounded set of executables — residency changes stay
        recompile-free."""
        host = self._host
        if host is None:
            return None
        version = host.version
        groups = np.asarray(groups, np.int64)
        groups = groups[host.spilled_mask[groups]]
        if len(groups) == 0:
            return None
        keys, vals = host.peek_groups(groups)
        n = len(keys)
        if n == 0:
            return None
        from ..ops.segment_ops import pow2_ceil
        P = pow2_ceil(max(n, 1))
        pkeys = np.zeros(P, np.int64)
        pkeys[:n] = keys
        valid = np.zeros(P, bool)
        valid[:n] = True
        dvals = {}
        for name, v in vals.items():
            pad = P - n
            if pad:
                v = np.concatenate(
                    [v, np.zeros(v.shape[:-1] + (pad,), v.dtype)], axis=-1)
            # staged in the plane's stored layout (the words of a halves
            # plane are split here, with numpy, off the mailbox thread)
            dvals[name] = _in_layout(self._array_states[name].array, v)
        return {"groups": groups, "version": version, "n": n,
                "dkeys": jnp.asarray(pkeys), "valid": jnp.asarray(valid),
                "values": dvals}

    def apply_promotion(self, payload: dict) -> bool:
        """Install a staged promotion at a batch boundary (mailbox
        thread): insert the keys into the device table at FIXED capacity,
        scatter the staged rows into every snapshot-state plane, then —
        only after the insert fully succeeded — drop the groups from the
        host tier and clear their spilled flags. Ordering guarantees a
        key is never split across (or lost between) tiers."""
        host = self._host
        groups = np.asarray(payload["groups"], np.int64)
        if host is None:
            return False
        if payload["version"] != host.version:
            # raced by a host-tier mutation since staging: re-gather
            # synchronously (small, boundary-amortized) and fall through
            payload = self._stage_promotion(groups)
            if payload is None:
                return False
        n = int(payload["n"])
        if self._num_keys + n > int(0.6 * self.capacity):
            self._prefetch.forget(groups)
            return False  # headroom gone since staging; stay warm
        new_table, slots, ok = lookup_or_insert(
            self.table, payload["dkeys"], payload["valid"])
        if not bool(jax.device_get((ok | ~payload["valid"]).all())):
            self._prefetch.forget(groups)
            return False  # table could not admit; discard, keys stay warm
        self.table = new_table
        self._num_keys += n
        widx = jnp.where(payload["valid"], slots, self.capacity)
        for name, st in self._array_states.items():
            st.array = plane_map(
                lambda a, v: a.at[..., widx].set(v, mode="drop"),
                st.array, payload["values"][name])
        host.drop_groups(groups)
        self._sync_spilled_dev()
        self.mark_dirty(slots)
        self._residency.note_promoted(groups)
        DEVICE_STATS.note_tier_prefetch(len(groups), n)
        return True

    def register_array_state(self, name: str, kind: str, dtype,
                             ring: Optional[int] = None) -> None:
        if name not in self._array_states:
            self._array_states[name] = _ArrayState(name, kind, dtype, ring,
                                                   self.capacity)
            if self._host is not None:
                self._host.register(name, kind,
                                    np.dtype(jnp.dtype(dtype)), ring)

    def get_array(self, name: str):
        """The plane as it is stored: one array, or the ``Halves`` (two
        ``uint32`` arrays, high and low words) of a ring plane of a
        64-bit integer. ``shape`` and ``dtype`` read as the plane's
        either way; ``np.asarray`` of a ``Halves`` joins on the host.
        A program takes what this hands out and ``set_array`` takes back
        what the program returns."""
        return self._array_states[name].array

    def set_array(self, name: str, array) -> None:
        self._array_states[name].array = array

    def array_kind(self, name: str) -> str:
        """The aggregate kind plane ``name`` folds, merges and retires
        by: what it was registered with, or after a restore what the
        snapshot says it was written with."""
        return self._array_states[name].kind

    def fold_batch(self, name: str, slots: jax.Array, values,
                   valid: jax.Array,
                   ring_idx=None) -> None:
        """acc[(ring_idx,) slot] op= values — one scatter per aggregate.
        ``values``/``ring_idx`` may be numpy (preferred when a spill tier
        is configured: the host-side rows of the batch fold into the host
        mirror without a device round-trip). A ring plane goes through
        ``fold_rings``, as a job's planes do together."""
        st = self._array_states[name]
        if st.ring:
            self.fold_rings(slots, ring_idx, valid, {name: values})
            return
        st.array = scatter_fold(st.kind, st.array, slots,
                                jnp.asarray(values), valid)
        self._fold_host(name, values, None)

    def fold_rings(self, slots: jax.Array, ring_idx, valid: jax.Array,
                   values: dict) -> None:
        """acc[ring_idx, slot] op= values for every ring plane named in
        ``values`` (state name -> value column, numpy or device; None
        counts one a row), in ONE donated program (``_fold_program``):
        arrays taken from ``get_array`` before this call are deleted
        buffers after it."""
        states = [self._array_states[n] for n in values]
        cols = tuple(None if v is None else jnp.asarray(v)
                     for v in values.values())
        sig = _plane_sig(states)
        args = (tuple(st.array for st in states), slots,
                jnp.asarray(ring_idx), valid, cols)
        if _folds_by_limbs(sig):
            outs, self._limb_scatters = _fold_program(sig)(
                *args, self._limb_scatters)
        else:
            outs = _fold_program(sig)(*args)
        for st, arr in zip(states, outs):
            st.array = arr
        for name, vals in values.items():
            self._fold_host(name, vals, ring_idx)

    def _fold_host(self, name: str, values, ring_idx) -> None:
        """The spill tier's leg of a fold: the batch's rows of spilled
        key groups (``slots_for_batch`` left their positions) fold into
        the host tier's plane ``name``."""
        if self._pending_host is None:
            return
        pos, hslots = self._pending_host
        vals = (np.ones(len(pos), np.int64) if values is None
                else np.asarray(jax.device_get(values))[pos])
        ring = (None if ring_idx is None
                else np.asarray(jax.device_get(ring_idx))[pos])
        self._host.fold(name, hslots, vals, ring)

    def reset_ring_row(self, row: int) -> None:
        """Zero one ring row of every ring-shaped array state back to its
        aggregate identity — pane retirement for the window operators.
        ONE cached jitted program over all ring planes with the row as a
        traced scalar (eager per-plane .at[].set ran un-jitted: each call
        re-dispatched a full-plane scatter and dominated the whole fire
        stage — measured 7.7s of an 8.4s Q5@1M fire budget on CPU).
        The host knows the retired row, so the snapshot mirror replays it
        without marking anything dirty on device."""
        ring_states = [st for st in self._array_states.values()
                       if st.ring]
        if ring_states:
            outs = _reset_row_program(_plane_sig(ring_states))(
                tuple(st.array for st in ring_states), np.int32(row))
            for st, arr in zip(ring_states, outs):
                st.array = arr
        self._retired_rows.add(int(row))
        if self._host is not None:
            self._host.reset_ring_row(row)

    def slots_for_batch_device(self, dkeys: jax.Array) -> jax.Array:
        """Deferred-mode hot path for keys ALREADY on device (one packed
        upload per batch; see DeviceWindowAggOperator._fold_packed): pure
        dispatch, no host sync, sentinel keys remapped on device."""
        if not self._defer:
            raise RuntimeError("device-resident slot resolution requires "
                               "defer_overflow mode")
        if self._reclaiming is not None:
            self._finish_reclaim()       # before the probe: it may grow
        dkeys = sanitize_keys_device(dkeys)
        self._probe_calls += 1
        handover = self._probe_wide and compacts(dkeys.shape[0])
        if handover not in self._probe_audited:
            # a module-level jit that no program cache instruments: its
            # first dispatch here puts it in the audit, as
            # _TimedProgram._note_live_compile does for the others
            self._probe_audited.add(handover)
            _record_program_audit(
                "state.probe",  # lint: key-ok audit scope, not a config key
                lookup_or_insert, (self.table, dkeys),
                {"stats": True, "handover": handover}, repr(handover))
        self.table, slots, ok, probe, *elected = self._probe_program(
            dkeys, handover)(self.table, dkeys)
        self._dropped = self._dropped + jnp.sum(~ok).astype(jnp.int64)
        if elected:
            self._elected = self._elected + elected[0]
        self._probe = self._probe + probe
        self.note_probe_stats()
        self.mark_dirty(slots)
        return slots

    def _probe_program(self, dkeys: jax.Array, handover: bool):
        """The probe as a callable of (table, keys): the wide-batch
        program built ahead (``prepare``) where it is of these shapes,
        else the jitted function, which builds at its first use."""
        if handover and self._wide_probe is not None \
                and self._wide_probe[:2] == (self.table.shape, dkeys.shape):
            return self._wide_probe[2]
        return partial(lookup_or_insert, stats=True, handover=handover)

    def note_probe_stats(self, block: bool = False) -> None:
        """Hand the probe's device counters to DEVICE_STATS. Per batch this
        never waits: it reads a copy taken at an earlier batch only once
        that has landed, then takes the next (so the gauges trail the
        device by a batch or two); ``block=True`` (check_health, operator
        close: places that sync anyway) reads the counters as they are.
        The same reading picks the next batch's probe program: where
        every batch noted left more rows unresolved than the narrow loops
        hold (a prefill, or a stream whose keys come and go), the one
        in which one lane a distinct key enters the rounds and the
        full-width rounds hand over to the narrow loops; else the one
        every resident-key job has always run. Both give the same
        slots, so a late or wrong pick costs time only; and the counters
        the pick reads are of the rows before that election, so a job
        whose every batch is wide does not flip between the two."""
        if block:
            self._finish_reclaim(block=True)
        sent = self._probe_counters() if block else self._probe_sent
        # (the probe's own four are the youngest of them: a batch adds to
        # the election's first, and the limb scatters are the fold's
        # before)
        if sent is not None and (block or sent[0].is_ready()):
            # lint: sync-ok the copy has landed (or the caller syncs anyway)
            now = np.concatenate(jax.device_get(sent))
            calls = self._probe_calls if block else self._probe_sent_calls
            rows, tail, wide, undecided, elected, elections, limbs = \
                now - self._probe_noted
            DEVICE_STATS.note_probe(rows, tail, wide, elected, elections,
                                    undecided)
            DEVICE_STATS.note_limb_scatters(limbs)
            if calls > self._probe_noted_calls:
                # every probe noted started its claiming rounds at full
                # width
                self._probe_wide = bool(
                    wide == calls - self._probe_noted_calls)
            self._probe_noted, self._probe_noted_calls = now, calls
            self._probe_sent = None
        if self._probe_sent is None and not block:
            self._probe_sent = self._probe_counters()
            self._probe_sent_calls = self._probe_calls

    def _probe_counters(self) -> tuple[jax.Array, ...]:
        """The device counters ``note_probe_stats`` reads, as they are."""
        return self._probe, self._elected, self._limb_scatters

    # ------------------------------------------------------------------
    # deferred-mode health (device scalars; ride along with fire programs)
    # ------------------------------------------------------------------
    @property
    def dropped_device(self) -> jax.Array:
        return self._dropped

    @property
    def table_generation(self) -> int:
        """Which table a health reading is of: a caller that takes a
        reading now and hands it to ``apply_health`` later (a fire's
        ``occ``, drained turns after its dispatch) hands this back with
        it, and a reading of a table since rebuilt is passed over."""
        return self._generation

    def _new_generation(self) -> None:
        self._generation += 1

    def apply_health(self, dropped: int, occupancy: int,
                     generation: Optional[int] = None, stage=None) -> None:
        """Consume host-materialized health scalars (fetched in the same
        device_get as a fire's results): hard-error on any dropped insert;
        before the load factor bites, RECLAIM (free every slot whose key
        holds no data in any ring row, at the same capacity) and grow only
        if that is not enough — or, under an HBM budget, page cold key
        groups to the host tier instead. ``generation``: the
        ``table_generation`` the reading was taken under; the occupancy of
        a table that has been rebuilt since says nothing of this one and
        is passed over (None: the reading is of the table as it is).
        ``stage`` opens the caller's span around a reclaim (``reclaim``).
        The reclaim's program was built before the job's first input and
        after each growth (``prepare_reclaim``): no reading builds it."""
        if int(dropped) > 0:
            if self._budget:
                raise RuntimeError(
                    f"spill staging overflow: {int(dropped)} records could "
                    "not be staged for the host tier in one watermark "
                    "interval; raise spill_staging_slots or the HBM budget")
            raise RuntimeError(
                f"device hash table overflow: {int(dropped)} records "
                f"dropped (capacity {self.capacity}: neither a reclaim nor "
                "growth came in time); raise "
                "state.backend.tpu.slots-per-key-group or disable "
                "deferred overflow checking")
        self._finish_reclaim()
        if self._reclaiming is not None or (
                generation is not None and generation != self._generation):
            return
        self._num_keys = int(occupancy)
        if self._num_keys <= 0.6 * self.capacity:
            return
        if self._reclaimable():
            self.reclaim(stage, wait=False)
        else:
            self._grow()

    def _grow(self) -> None:
        if not self._budget or 2 * self.capacity <= self._budget:
            self._rehash(self.capacity * 2)
            # the planes have a new shape: their reclaim is built now,
            # in the turn that grew, before the next input
            self.prepare_reclaim()
        else:
            self._sync_touch_from_device()
            self._evict_cold_groups()

    def _reclaimable(self) -> bool:
        """Whether a reclaim can tell what lives: no HBM budget (cold
        groups page out instead), and every state a ring plane (a
        row-state plane has no pane that retires)."""
        states = self._array_states.values()
        return (not self._budget and bool(states)
                and all(st.ring for st in states))

    def _reclaim_call(self) -> tuple:
        """(the reclaim program of the current planes, its arguments)."""
        states = list(self._array_states.values())
        return (_reclaim_program(_plane_sig(states)),
                (self.table, tuple(st.array for st in states),
                 self._dropped))

    def prepare(self, batch_rows: int) -> None:
        """Build ahead, at a job's first batch and before it is taken in,
        what the job will run for certain beside the programs that batch
        builds by running them: the reclaim (``prepare_reclaim``) and,
        for a NEW table fed batches wide enough to compact, the probe's
        wide-batch program (every key of the first batches is new, so
        they start wide and the backend picks it from the third on), on a
        thread of its own while this one builds the reclaim, and waited
        for: nothing outlives the call. From an empty compile cache the
        job then builds that program in the reclaim's time (21 s beside
        26 at 2^23 slots on the chip's host) where it built it at its
        third batch, with the device idle and the sink silent for 16 s
        more; a harness that takes a silent sink for a drained job then
        timed the fire's programs, which were still to be built (PERF.md
        section 6, PR 46: what voided PR 45's run). A table restored or
        grown, and batches that differ from the first in size, build the
        program at its first use, as before."""
        if not (self._defer and self._num_keys == 0
                and compacts(batch_rows)):
            self.prepare_reclaim()
            return
        table, keys = (jax.ShapeDtypeStruct(shape, jnp.int64)
                       for shape in ((self.capacity,), (batch_rows,)))
        with ThreadPoolExecutor(1, "probe-build") as pool:
            wide = pool.submit(lambda: lookup_or_insert.lower(
                table, keys, stats=True, handover=True).compile())
            self.prepare_reclaim()
        self._wide_probe = (table.shape, keys.shape, wide.result())

    def prepare_reclaim(self) -> None:
        """Build the reclaim's program for the planes as they are now, so
        that the reclaim itself compiles nothing (a job may promise to
        build nothing once it is warm): called by the operator when its
        planes are registered, before its first input, and by ``_grow``
        after each growth, as the mesh operator prepares its own in
        ``_build``. WHEN a table will first pass its load limit no
        reading can tell in time (two windows that fire back to back read
        one occupancy; a build started at the prefill's last fire ends in
        the timed phase: ROADMAP D14), so it is not guessed. Only a
        backend that decides by deferred health readings and can tell
        what lives builds one; the build blocks the turn it runs in, as
        every program's first use does: a build that ENDS once the job
        has promised to build nothing breaks that promise from any
        thread (PERF.md section 7)."""
        sig = _plane_sig(self._array_states.values())
        if sig == self._reclaim_built or not self._defer \
                or not self._reclaimable():
            return
        program, args = self._reclaim_call()
        program.prepare(*jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), args))
        self._reclaim_built = sig

    def reclaim(self, stage=None, wait: bool = True):
        """Free every slot whose key holds no data in any ring row of any
        plane, keeping the capacity: what Flink's window
        operator does at a window's cleanup time (``clearAllState``), done
        for all keys at once when the table fills. ONE device program
        (``_reclaim_program``), dispatched here and not waited for: the
        batches that follow queue behind it on the device and probe the
        new table, and the mailbox does not stop. Only the two counts
        cross to the host, when they have landed (``_finish_reclaim``: at
        a later batch or health reading); then the table grows after all
        if fewer than a quarter of the occupied slots came free (the
        job's live set really is that large). Every slot moves, so a
        new table generation begins, the snapshot mirror is invalidated
        and the planes taken from ``get_array`` before this call are
        deleted buffers. ``stage`` is
        a zero-argument factory of the caller's OPEN stage span
        (``window/Reclaim``), closed with the attributes ``kept``,
        ``freed`` and ``capacity`` when the counts are in. ``wait=True``
        waits for them and returns (keys kept, keys freed)."""
        program, args = self._reclaim_call()
        span = stage() if stage is not None else None
        self.table, outs, self._dropped, counts = program(*args)
        for st, arr in zip(self._array_states.values(), outs):
            st.array = arr
        counts.copy_to_host_async()
        self._invalidate_mirror()
        self._new_generation()
        self._reclaiming = (counts, span)
        return self._finish_reclaim(block=True) if wait else None

    def _finish_reclaim(self, block: bool = False):
        """Take in a dispatched reclaim's counts once their copy has
        landed (``block``: wait for it): the stage span, the counters,
        ``num_keys``, and the growth that a reclaim which freed too
        little still calls for. Returns (kept, freed), or None while the
        counts are not in."""
        if self._reclaiming is None:
            return None
        counts, span = self._reclaiming
        if not (block or counts.is_ready()):
            return None
        self._reclaiming = None
        # lint: sync-ok the reclaim's two counts, landed (or the caller syncs anyway)
        kept, freed = (int(x) for x in jax.device_get(counts))
        self._num_keys = kept
        DEVICE_STATS.note_reclaim(kept, freed)
        if span is not None:
            span.close(kept=kept, freed=freed, capacity=self.capacity)
        if 4 * freed < kept + freed and kept + freed > 0.6 * self.capacity:
            self._grow()
        return kept, freed

    def check_health(self) -> None:
        """Standalone (blocking) variant of apply_health."""
        d, occ = jax.device_get((self._dropped,
                                 (self.table != EMPTY_KEY).sum()))
        self.note_probe_stats(block=True)
        self.apply_health(int(d), int(occ))
        self._finish_reclaim(block=True)

    def conform_ring(self, ring: int, live_panes: Iterable[int]) -> None:
        """Re-seat ring-shaped array states restored under a DIFFERENT ring
        size onto ``ring`` rows: each live pane's row moves from
        (p % old_ring) to (p % ring); every other row is the aggregate
        identity (retired). No-op when sizes already match."""
        live = list(live_panes)
        for st in self._array_states.values():
            if not st.ring or st.ring == ring:
                continue
            if len(live) > ring:
                raise RuntimeError(
                    f"cannot conform ring {st.ring} -> {ring}: "
                    f"{len(live)} panes are live; increase ring_size")
            old = st.array
            new = make_plane(st.kind, (ring, self.capacity), st.dtype,
                             isinstance(old, Halves))
            for p in live:
                new = plane_map(
                    lambda new, old, p=p: new.at[p % ring].set(
                        old[p % st.ring]), new, old)
            st.array = new
            st.ring = ring
            self._invalidate_mirror()

    def occupied_mask(self) -> jax.Array:
        return self.table != EMPTY_KEY

    @property
    def num_keys(self) -> int:
        return self._num_keys

    # ------------------------------------------------------------------
    # typed row plane: per-key values of ANY numeric dtype with presence
    # bits and optional TTL, accessed in BATCHES (one lookup + one gather
    # or scatter per batch — the per-key State handles below wrap this).
    # ------------------------------------------------------------------
    def register_row_state(self, name: str, dtype,
                           ttl_ms: Optional[int] = None) -> None:
        """Value plane [capacity] of ``dtype`` + presence int8 plane
        (+ last-update int64 plane when a TTL is set: entries expire
        ttl_ms after last update, checked lazily at read — the relaxed
        cleanup of the reference's StateTtlConfig)."""
        if self._budget:
            raise NotImplementedError(
                "the typed row plane does not page to the host tier; "
                "configure this backend without hbm_budget_slots (the "
                "budget applies to the array/window plane)")
        if name in self._row_meta:
            return
        self._row_meta[name] = (int(ttl_ms or 0),
                                jnp.dtype(np.dtype(dtype)))
        self._ensure_row_planes(name)

    def _ensure_row_planes(self, name: str) -> None:
        """(Re-)materialize a row state's planes; a restore() rebuilds
        _array_states from the snapshot alone, so planes the snapshot
        lacked (e.g. the TTL clock of a job upgraded from no-TTL) come
        back here. A fresh TTL clock next to RESTORED presence fills with
        int64 max: existing entries never expire rather than all expiring
        at once."""
        ttl, dtype = self._row_meta[name]
        restored_presence = f"{name}.__set__" in self._array_states
        self.register_array_state(name, "sum", dtype)
        self.register_array_state(f"{name}.__set__", "sum", jnp.int8)
        if ttl and f"{name}.__ts__" not in self._array_states:
            self.register_array_state(f"{name}.__ts__", "sum", jnp.int64)
            if restored_presence:
                self.set_array(f"{name}.__ts__", jnp.full(
                    self.capacity, np.iinfo(np.int64).max, jnp.int64))

    def _row_planes(self, name: str):
        ttl, _dtype = self._row_meta[name]
        self._ensure_row_planes(name)
        last = self.get_array(f"{name}.__ts__") if ttl else None
        return (self.get_array(name), self.get_array(f"{name}.__set__"),
                last, ttl)

    def rows_upsert(self, name: str, keys: np.ndarray, values: np.ndarray,
                    now_ms=0) -> None:
        """Set values for a batch of keys (last occurrence wins for
        duplicate keys, deterministically). One slot resolution + one
        scatter program. ``now_ms`` may be a scalar or a per-row array
        (TTL clock)."""
        slots = self.slots_for_batch(np.asarray(keys))
        vals, present, last, ttl = self._row_planes(name)
        arrs = _rows_set(vals, present, last, slots,
                         jnp.asarray(np.asarray(values)),
                         jnp.asarray(np.asarray(now_ms, np.int64)))
        self.set_array(name, arrs[0])
        self.set_array(f"{name}.__set__", arrs[1])
        if last is not None:
            self.set_array(f"{name}.__ts__", arrs[2])

    def rows_lookup(self, name: str, keys: np.ndarray,
                    now_ms: int = 0) -> tuple[np.ndarray, np.ndarray]:
        """(values, present) for a batch of keys — absent, cleared, or
        TTL-expired keys report present=False. One lookup + one gather +
        one transfer."""
        vals, present, last, ttl = self._row_planes(name)
        v, p = _rows_get(self.table, vals, present, last,
                         jnp.asarray(_sanitize_keys(np.asarray(keys))),
                         np.int64(now_ms), np.int64(ttl))
        v, p = jax.device_get((v, p))
        return np.asarray(v), np.asarray(p)

    def rows_clear(self, name: str, keys: np.ndarray) -> None:
        vals, present, last, _ttl = self._row_planes(name)
        new_present, slots = _rows_unset(
            self.table, present,
            jnp.asarray(_sanitize_keys(np.asarray(keys))))
        self.set_array(f"{name}.__set__", new_present)
        self.mark_dirty(slots)

    def dedup_first_batch(self, name: str, keys: np.ndarray,
                          ts: np.ndarray,
                          valid: Optional[np.ndarray] = None) -> np.ndarray:
        """Keep-first admission for a batch: returns a bool mask of the
        rows seen for the FIRST time (within the batch, against state, and
        — under a TTL — since expiry). The whole batch is one fused
        program; overflow grows the table and retries (sync-mode
        semantics)."""
        if name not in self._row_meta:
            raise RuntimeError(f"row state {name!r} not registered")
        keys = _sanitize_keys(np.asarray(keys))
        dvalid = (jnp.asarray(np.asarray(valid, bool)) if valid is not None
                  else jnp.ones(len(keys), bool))
        dts = jnp.asarray(np.asarray(ts, np.int64))
        while True:
            _vals, present, last, ttl = self._row_planes(name)
            table, new_present, new_last, fresh, slots, overflow, occ = \
                _dedup_first(self.table, present, last, jnp.asarray(keys),
                             dvalid, dts, np.int64(ttl))
            fresh_h, overflow_h, occ_h = jax.device_get(
                (fresh, overflow, occ))
            if bool(overflow_h):
                self._rehash(self.capacity * 2)
                continue
            self.table = table
            self.set_array(f"{name}.__set__", new_present)
            if new_last is not None:
                self.set_array(f"{name}.__ts__", new_last)
            self.mark_dirty(slots)
            self._num_keys = int(occ_h)
            if self._num_keys > 0.6 * self.capacity:
                self._rehash(self.capacity * 2)
            return np.asarray(fresh_h)

    # ------------------------------------------------------------------
    # row-access compatibility plane (slow; host roundtrip per call)
    # ------------------------------------------------------------------
    def get_partitioned_state(self, descriptor: StateDescriptor) -> State:
        if descriptor.kind != "value":
            raise NotImplementedError(
                "TPU backend row plane supports ValueState only; use array "
                "states (device operators), the device list plane "
                "(state/device_lists.py), or the hashmap backend")
        handle = self._row_states.get(descriptor.name)
        if handle is None:
            default = descriptor.default
            # float64 unless the user EXPLICITLY typed the default with a
            # numpy integer (a plain python-int default must not make
            # later float updates truncate)
            if isinstance(default, (np.integer, np.ndarray)) and \
                    np.asarray(default).dtype.kind in "iu":
                dtype = np.asarray(default).dtype
            else:
                dtype = np.float64
            ttl_ms = (int(descriptor.ttl.ttl * 1000)
                      if descriptor.ttl is not None else None)
            self.register_row_state(descriptor.name, dtype, ttl_ms)
            handle = _TpuValueState(self, descriptor)
            self._row_states[descriptor.name] = handle
        return handle

    def keys(self, state_name: str, namespace=None) -> Iterable[Any]:
        t = jax.device_get(self.table)
        return t[t != EMPTY_KEY].tolist()

    def namespaces(self, state_name: str) -> Iterable[Any]:
        return [None]

    # ------------------------------------------------------------------
    # checkpointing
    # ------------------------------------------------------------------
    def snapshot(self, checkpoint_id: int) -> dict:
        # delta capture: only dirty blocks cross the device boundary; the
        # snapshot itself is assembled from the host mirror
        self._finish_reclaim(block=True)   # num_keys is of this table
        self._sync_mirror()
        t = self._mirror["table"]
        occupied = t != EMPTY_KEY
        keys = t[occupied]
        slots = np.flatnonzero(occupied)
        # same hash as record routing (hash_batch), so restored keys filter
        # into exactly the key-group ranges the exchange routes them to
        groups = key_groups_for_hash_batch(hash_batch(keys),
                                           self.max_parallelism)
        host_keys = host_vals = None
        if self._host is not None and len(self._host.index):
            host_keys, host_vals = self._host.snapshot_parts()
            keys = np.concatenate([keys, host_keys])
            groups = np.concatenate([groups, key_groups_for_hash_batch(
                hash_batch(host_keys), self.max_parallelism)])
        # canonical (group, key) order: the snapshot is residency-AGNOSTIC
        # — byte-identical whether a key group is device-hot or host-warm
        # (raw order would leak slot/eviction history into the artifact)
        order = np.lexsort((keys, groups))
        keys = np.ascontiguousarray(keys[order])
        groups = np.ascontiguousarray(groups[order])
        states = {}
        for name, st in self._array_states.items():
            arr = self._mirror["arrays"][name]
            vals = arr[:, slots] if st.ring else arr[slots]
            if host_vals is not None:
                vals = np.concatenate(
                    [vals, host_vals[name].astype(vals.dtype)], axis=-1)
            vals = np.ascontiguousarray(vals[..., order])
            states[name] = {"kind": st.kind, "dtype": str(np.dtype(st.dtype)),
                            "ring": st.ring, "values": vals}
        return {"kind": "tpu", "keys": keys, "key_groups": groups,
                "max_parallelism": self.max_parallelism, "states": states}

    def restore(self, snapshots: Iterable[dict]) -> None:
        """Deadline-bounded (fault site transfer.h2d; the deadline is the
        CHECKPOINT timeout — a restore is a bulk state rebuild, not a
        per-batch transfer — and there is no in-place retry: the rebuild
        mutates self in stages, so a stalled restore upload raises
        StallError into the restart path rather than freezing recovery
        mid-rebuild)."""
        from ..runtime.watchdog import WATCHDOG

        if self._prefetch is not None:
            # restart/restore boundary: in-flight promotion stagings were
            # gathered against pre-restore state — cancel, never apply
            self._prefetch.cancel()
        snapshots = list(snapshots)
        self._finish_reclaim(block=True)   # before the state is replaced
        WATCHDOG.run("transfer.h2d",
                     lambda: self._restore_inner(snapshots),
                     scope="tpu_backend.restore",
                     deadline=WATCHDOG.deadline_for("checkpoint.load"))

    def _restore_inner(self, snapshots: Iterable[dict]) -> None:
        all_keys, per_state_vals = [], {}
        state_meta: dict[str, dict] = {}
        for snap in snapshots:
            groups = np.asarray(snap["key_groups"])
            sel = np.array([g in self.key_group_range for g in groups],
                           dtype=bool)
            keys = np.asarray(snap["keys"])[sel]
            all_keys.append(keys)
            for name, sdata in snap["states"].items():
                state_meta[name] = sdata
                vals = np.asarray(sdata["values"])
                vals = vals[:, sel] if sdata["ring"] else vals[sel]
                per_state_vals.setdefault(name, []).append(vals)
        keys = (np.concatenate(all_keys) if all_keys
                else np.empty(0, np.int64))
        from ..runtime.faults import fire_with_retries
        fire_with_retries("transfer.h2d", scope="tpu_backend.restore")
        while self.capacity < 2 * max(len(keys), 1):
            self.capacity *= 2  # may exceed the budget; evicted back below
        self.table = make_table(self.capacity)
        self._num_keys = len(keys)
        if len(keys):
            self.table, slots, ok = lookup_or_insert(self.table,
                                                     jnp.asarray(keys))
            assert bool(jax.device_get(ok.all()))
        else:
            slots = jnp.zeros(0, jnp.int32)
        self._array_states.clear()
        for name, meta in state_meta.items():
            dtype = jnp.dtype(meta["dtype"])
            st = _ArrayState(name, meta["kind"], dtype, meta["ring"],
                             self.capacity)
            if len(keys):
                # the snapshot's int64 values into the stored layout (the
                # words split with numpy: a snapshot's bytes are the same
                # whatever layout wrote or reads it)
                st.array = plane_map(
                    lambda a, v: a.at[..., slots].set(v), st.array,
                    _in_layout(st.array, np.concatenate(
                        per_state_vals[name], axis=-1)))
            self._array_states[name] = st
        # restored state may exceed the HBM budget: page the overflow out
        # immediately (fresh LRU; group order decides coldness)
        self._host = None
        self._spilled_dev = None
        self._touch_dev = None
        self._invalidate_mirror()
        self._new_generation()
        if self._budget and self.capacity > self._budget:
            self._evict_cold_groups(rebuild_capacity=self._budget)


class _TpuValueState(ValueState):
    """Row plane per-key API handle over the typed batched plane below
    (API completeness; each call is a host round-trip — batched access via
    ``rows_lookup``/``rows_upsert`` and the array plane are the hot
    paths)."""

    def __init__(self, backend: TpuKeyedStateBackend, desc: StateDescriptor):
        self._b, self._d = backend, desc

    def value(self):
        key = np.asarray([self._b._current_key], np.int64)
        vals, present = self._b.rows_lookup(
            self._d.name, key, now_ms=int(time.time() * 1000))
        if not present[0]:
            return self._d.default
        v = vals[0]
        return v.item() if isinstance(v, np.generic) else v

    def update(self, value) -> None:
        key = np.asarray([self._b._current_key], np.int64)
        self._b.rows_upsert(self._d.name, key, np.asarray([value]),
                            now_ms=int(time.time() * 1000))

    def clear(self) -> None:
        key = np.asarray([self._b._current_key], np.int64)
        self._b.rows_clear(self._d.name, key)


register_backend("tpu", TpuKeyedStateBackend)
