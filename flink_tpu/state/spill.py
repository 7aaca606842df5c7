"""Host-RAM spill tier for device keyed state.

The RocksDB-replacement risk item (SURVEY.md §7): keyed state larger than
the HBM budget pages out of the device. Where the reference pushes every
access through an LSM tree (RocksDBKeyedStateBackend.java:114), this tier
keeps the device hash table + accumulator arrays as the HOT set and moves
whole COLD KEY GROUPS to host RAM: a native open-addressing index
(native/HostHashIndex, the C++ layer built for exactly this) maps spilled
keys to dense slots in numpy mirror arrays, and every operation stays
batched — a record batch is split by key group into a device scatter-fold
and a vectorized numpy fold (np.add.at / minimum.at / maximum.at), never a
per-record loop. Fires merge pane rows from both tiers.

Eviction is LRU at key-group granularity (the reference's unit of state
movement, KeyGroupRangeAssignment.java:63): when the device table can no
longer grow within the budget, the coldest groups' keys and accumulator
rows are pulled to host in one DMA and the device table is rebuilt
without them.
"""

from __future__ import annotations

import threading
from typing import Iterable, Optional

import numpy as np

from ..core.keygroups import hash_batch, key_groups_for_hash_batch
from ..native import HostHashIndex

__all__ = ["HostTier", "HOST_IDENT"]


def _ident(kind: str, dtype: np.dtype):
    if kind in ("sum", "count", "presence"):
        return dtype.type(0)
    if kind == "min":
        return (np.finfo(dtype).max if np.issubdtype(dtype, np.floating)
                else np.iinfo(dtype).max)
    return (np.finfo(dtype).min if np.issubdtype(dtype, np.floating)
            else np.iinfo(dtype).min)


HOST_IDENT = _ident

_FOLDS = {
    "sum": np.add.at,
    "count": np.add.at,
    # the mark of a job that reads no count (ops/segment_ops.AGG_FOLDS):
    # saturating here as on the device
    "presence": np.maximum.at,
    "min": np.minimum.at,
    "max": np.maximum.at,
}

_MERGES = {
    "sum": lambda v: v.sum(axis=0),
    "count": lambda v: v.sum(axis=0),
    "presence": lambda v: v.max(axis=0),
    "min": lambda v: v.min(axis=0),
    "max": lambda v: v.max(axis=0),
}


class _HostArray:
    __slots__ = ("kind", "dtype", "ring", "array")

    def __init__(self, kind: str, dtype, ring: Optional[int], cap: int):
        self.kind = kind
        self.dtype = np.dtype(dtype)
        self.ring = ring
        shape = (ring, cap) if ring else (cap,)
        self.array = np.full(shape, _ident(kind, self.dtype), self.dtype)

    def grow(self, cap: int) -> None:
        old = self.array
        shape = (self.ring, cap) if self.ring else (cap,)
        self.array = np.full(shape, _ident(self.kind, self.dtype),
                             self.dtype)
        if self.ring:
            self.array[:, :old.shape[1]] = old
        else:
            self.array[:old.shape[0]] = old


class HostTier:
    """Spilled key groups: key index + accumulator mirrors + LRU stats."""

    def __init__(self, max_parallelism: int):
        self.max_parallelism = max_parallelism
        self.index = HostHashIndex(1 << 12)
        self.cap = 1 << 12
        self.arrays: dict[str, _HostArray] = {}
        # True where the key group lives on host
        self.spilled_mask = np.zeros(max_parallelism, bool)
        self.evicted_keys = 0      # cumulative keys moved HBM -> host
        self.promoted_keys = 0     # cumulative keys moved host -> HBM
        self.host_folds = 0        # batches (partially) folded on host
        # Monotone mutation counter: the prefetch pipeline stages gathers
        # on a background thread and validates against this at apply time,
        # so a payload raced by a concurrent fold/absorb is discarded (or
        # re-gathered synchronously) instead of applied stale.
        self.version = 0
        # Guards mutation vs the prefetch thread's multi-read gather: the
        # version check makes a raced payload harmless, but peek_groups
        # reads the index and the shadow list at different times and a
        # fold landing in between tears the gather (mismatched lengths).
        # RLock because absorb -> slots_for nests.
        self._mtx = threading.RLock()

    @property
    def active(self) -> bool:
        return bool(self.spilled_mask.any())

    def register(self, name: str, kind: str, dtype,
                 ring: Optional[int]) -> None:
        if name not in self.arrays:
            self.arrays[name] = _HostArray(kind, dtype, ring, self.cap)

    def _ensure(self, n: int) -> None:
        while self.cap < n:
            self.cap *= 2
        for a in self.arrays.values():
            if (a.array.shape[-1]) < self.cap:
                a.grow(self.cap)

    def slots_for(self, keys: np.ndarray) -> np.ndarray:
        """Upsert spilled-side keys -> dense host slots."""
        with self._mtx:
            self.version += 1
            slots = self.index.upsert(keys)
            self._ensure(len(self.index) + 1)
            self.record_new_keys(keys, slots)
            return slots

    def absorb(self, keys: np.ndarray,
               values: dict[str, np.ndarray]) -> None:
        """Fold evicted device rows into the host tier (values[name]:
        [ring?, n] rows aligned with keys)."""
        if len(keys) == 0:
            return
        with self._mtx:
            slots = self.slots_for(keys)
            for name, vals in values.items():
                a = self.arrays[name]
                if a.ring:
                    _FOLDS[a.kind](a.array, (slice(None), slots), vals)
                else:
                    _FOLDS[a.kind](a.array, slots, vals)
            self.evicted_keys += len(keys)

    def fold(self, name: str, slots: np.ndarray, values: np.ndarray,
             ring_idx: Optional[np.ndarray]) -> None:
        with self._mtx:
            self.version += 1
            a = self.arrays[name]
            if a.ring:
                _FOLDS[a.kind](a.array, (ring_idx, slots),
                               values.astype(a.dtype, copy=False))
            else:
                _FOLDS[a.kind](a.array, slots,
                               values.astype(a.dtype, copy=False))

    def keys(self) -> np.ndarray:
        """All spilled keys, in dense-slot order (shadow list: the index
        only maps key -> slot)."""
        return self._shadow[:len(self.index)]

    # -- shadow key list (dense-slot order) -----------------------------
    # HostHashIndex gives key -> slot; fires and snapshots need slot ->
    # key, so mirror inserted keys in insertion order.
    @property
    def _shadow(self) -> np.ndarray:
        if not hasattr(self, "_shadow_arr"):
            self._shadow_arr = np.empty(0, np.int64)
        return self._shadow_arr

    def record_new_keys(self, keys: np.ndarray, slots: np.ndarray) -> None:
        """Track insertion-ordered keys for slot->key reverse lookup."""
        n = len(self.index)
        cur = self._shadow
        if len(cur) < n:
            new = np.empty(n, np.int64)
            new[:len(cur)] = cur
            fresh = slots >= len(cur)
            new[slots[fresh]] = keys[fresh]
            self._shadow_arr = new

    def fire(self, name: str, pane_rows: np.ndarray) -> np.ndarray:
        """Merge the given ring rows -> per-key window results
        [n_spilled_keys]."""
        a = self.arrays[name]
        n = len(self.index)
        if a.ring is None:
            return a.array[:n].copy()
        return _MERGES[a.kind](a.array[pane_rows][:, :n])

    def reset_ring_row(self, row: int) -> None:
        with self._mtx:
            self.version += 1
            for a in self.arrays.values():
                if a.ring:
                    a.array[row] = _ident(a.kind, a.dtype)

    # -- promotion support (warm -> hot paging) -------------------------
    def key_groups(self) -> np.ndarray:
        """Key group of every spilled key, in dense-slot order."""
        return key_groups_for_hash_batch(hash_batch(self.keys()),
                                         self.max_parallelism)

    def group_counts(self) -> np.ndarray:
        """Spilled-key histogram over key groups [max_parallelism]."""
        return np.bincount(self.key_groups(),
                           minlength=self.max_parallelism)

    def peek_groups(self, groups: np.ndarray
                    ) -> tuple[np.ndarray, dict[str, np.ndarray]]:
        """Read-only gather of ``groups``' keys and accumulator rows.

        Does NOT remove anything: promotion inserts on device first and
        only then calls :meth:`drop_groups`, so a failed insert can never
        strand keys between tiers.  Safe to call from the prefetch thread;
        the caller validates ``version`` before applying the result.
        """
        sel = np.zeros(self.max_parallelism, bool)
        sel[np.asarray(groups, np.int64)] = True
        with self._mtx:
            pick = sel[self.key_groups()]
            keys = self.keys()[pick].copy()
            vals = {}
            n = len(self.index)
            for name, a in self.arrays.items():
                vals[name] = (a.array[:, :n][:, pick].copy() if a.ring
                              else a.array[:n][pick].copy())
        return keys, vals

    def drop_groups(self, groups: np.ndarray) -> int:
        """Remove ``groups`` from the tier, rebuilding the dense index.

        HostHashIndex has no delete, so the surviving keys re-upsert into
        a fresh index (dense slots in insertion order) and the arrays are
        compacted to match.  Returns how many keys were dropped.
        """
        with self._mtx:
            return self._drop_groups_locked(groups)

    def _drop_groups_locked(self, groups: np.ndarray) -> int:
        self.version += 1
        groups = np.asarray(groups, np.int64)
        sel = np.zeros(self.max_parallelism, bool)
        sel[groups] = True
        pick = sel[self.key_groups()]
        dropped = int(pick.sum())
        if dropped:
            keep_keys = self.keys()[~pick]
            n = len(self.index)
            keep_vals = {
                name: (a.array[:, :n][:, ~pick] if a.ring
                       else a.array[:n][~pick])
                for name, a in self.arrays.items()}
            self.index = HostHashIndex(self.cap)
            self._shadow_arr = np.empty(0, np.int64)
            for a in self.arrays.values():
                shape = ((a.ring, self.cap) if a.ring else (self.cap,))
                a.array = np.full(shape, _ident(a.kind, a.dtype), a.dtype)
            if len(keep_keys):
                slots = self.index.upsert(keep_keys)
                self.record_new_keys(keep_keys, slots)
                for name, a in self.arrays.items():
                    if a.ring:
                        a.array[:, slots] = keep_vals[name]
                    else:
                        a.array[slots] = keep_vals[name]
            self.promoted_keys += dropped
        self.spilled_mask[groups] = False
        return dropped

    def snapshot_parts(self) -> tuple[np.ndarray, dict[str, np.ndarray]]:
        """(keys, {name: [ring?, n] values}) for checkpointing."""
        n = len(self.index)
        keys = self._shadow[:n]
        vals = {}
        for name, a in self.arrays.items():
            vals[name] = (a.array[:, :n].copy() if a.ring
                          else a.array[:n].copy())
        return keys, vals
