"""Segment-reduce kernels: the device aggregation primitives.

These are the lowering targets for the framework's aggregate contract
(core AggregateFunction add/merge — reference AggregateFunction.java:114) and
for the window/group aggregations (reference WindowOperator + table-runtime
GroupAggFunction): each micro-batch folds into per-(pane, slot) accumulators
with ONE scatter op per aggregate, and window fire merges pane accumulators
with one reduction — no per-record work anywhere.

All functions are jax-traceable and shard_map-compatible (accumulators are
per-shard; cross-shard merge is the caller's psum/all_gather).
"""

from __future__ import annotations

from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["scatter_fold", "ring_fold", "pane_window_merge", "AGG_INITS",
           "Halves", "plane_map", "plane_take", "plane_row", "planes_joined",
           "planes_stored_like", "stores_halves", "folds_by_limbs",
           "identity_words",
           "plane_identity", "make_plane", "AGG_FOLDS", "AGG_MERGES",
           "COUNT_KINDS",
           "make_accumulator", "segment_topk", "pow2_ceil"]


def _scatter_add(acc, idx, vals):
    return acc.at[idx].add(vals)


def _scatter_min(acc, idx, vals):
    return acc.at[idx].min(vals)


def _scatter_max(acc, idx, vals):
    return acc.at[idx].max(vals)


#: kind -> (identity element factory, scatter fold, pane merge).
#: ``presence`` is the hidden plane of a job that reads no count (no COUNT,
#: no AVG): one 32-bit word a cell, 1 where a record of the key fell in
#: the pane and 0 where none did. Every row folds a one into it as into a
#: count, but as a saturating mark (``max``), so no number of records of
#: one key in one pane can wrap it and hide the key's window.
AGG_INITS = {
    "sum": lambda dtype: jnp.array(0, dtype),
    "count": lambda dtype: jnp.array(0, dtype),
    "presence": lambda dtype: jnp.array(0, dtype),
    "min": lambda dtype: jnp.array(jnp.finfo(dtype).max
                                   if jnp.issubdtype(dtype, jnp.floating)
                                   else jnp.iinfo(dtype).max, dtype),
    "max": lambda dtype: jnp.array(jnp.finfo(dtype).min
                                   if jnp.issubdtype(dtype, jnp.floating)
                                   else jnp.iinfo(dtype).min, dtype),
}

AGG_FOLDS = {
    "sum": _scatter_add,
    "count": _scatter_add,
    "presence": _scatter_max,
    "min": _scatter_min,
    "max": _scatter_max,
}

#: the kinds whose plane takes no input column: every row folds a one
COUNT_KINDS = ("count", "presence")

#: kind -> the region its scatter is named for where that is not the kind
#: itself: the presence plane's fold is the step's ``fold.count`` work
#: whatever its arithmetic (a trace must not find it under ``fold.max``,
#: beside the job's own MAX)
_FOLD_REGIONS = {"presence": "count"}

#: kind -> pane-merge reduction (callable(x, axis=...))
AGG_MERGES = {
    "sum": jnp.sum,
    "count": jnp.sum,
    "presence": lambda x, axis: jnp.max(x, axis=axis),
    "min": lambda x, axis: jnp.min(x, axis=axis),
    "max": lambda x, axis: jnp.max(x, axis=axis),
}
_MERGES = AGG_MERGES


def make_accumulator(kind: str, shape: tuple[int, ...], dtype) -> jax.Array:
    return jnp.full(shape, AGG_INITS[kind](dtype), dtype=dtype)


@jax.tree_util.register_pytree_node_class
class Halves:
    """A 64-bit integer plane STORED as its two 32-bit words: ``hi`` and
    ``lo``, two ``uint32`` arrays of the plane's shape. Both stacks keep
    their ring planes so (``state/tpu_backend``; the mesh's
    ``ShardedWindowState.accs``, ``[D, ring, capacity]`` a word): the
    TPU has no 64-bit registers, so a program whose parameter or result
    is an ``s64`` array splits ALL of it into words at its entry and
    joins ALL of it at its exit, whatever it touches (a quarter of the
    one-chip step and two thirds of its fire until PR 42; 25 of the mesh
    step's 93 ms and 37 of its fire's 58 until PR 44). Handed the
    words, a program slices or gathers what it needs of each, ``join``s
    that, computes in 64 bits as before (the compiler keeps an ``s64`` as
    its pair of words anyway, so the join of a sliced row is no work) and
    ``split``s what it writes back.

    A pytree of its two words (``dtype``, the plane's own, rides as static
    data), so it passes through ``jit``, donation, ``cond``, loops and
    ``device_get`` as an array does; ``shape`` / ``dtype`` / ``ndim`` /
    ``nbytes`` read as the plane's. ``split`` and ``join`` work alike on
    device and on numpy words; ``np.asarray`` of one joins on the host."""

    __slots__ = ("hi", "lo", "dtype")

    def __init__(self, hi, lo, dtype):
        self.hi, self.lo, self.dtype = hi, lo, dtype

    def tree_flatten(self):
        return (self.hi, self.lo), self.dtype

    @classmethod
    def tree_unflatten(cls, dtype, words):
        return cls(*words, dtype)

    @classmethod
    def split(cls, values) -> "Halves":
        """The words of a 64-bit integer array (device or numpy)."""
        dtype = values.dtype
        return cls((values >> dtype.type(32)).astype(np.uint32),
                   values.astype(np.uint32), dtype)

    def join(self):
        """The 64-bit array of the words (device or numpy)."""
        wide = self.dtype.type
        if isinstance(self.hi, np.ndarray):
            # the snapshot mirror joins whole planes: one new array,
            # shifted and filled in place
            out = self.hi.astype(wide)
            out <<= wide(32)
            out |= self.lo
            return out
        return (self.hi.astype(wide) << wide(32)) | self.lo.astype(wide)

    def map(self, fn, *more: "Halves") -> "Halves":
        """``fn`` over the high words, then over the low words, of this
        plane and of ``more`` in step: a slice, a gather, a row write."""
        return Halves(fn(self.hi, *(m.hi for m in more)),
                      fn(self.lo, *(m.lo for m in more)), self.dtype)

    @property
    def shape(self) -> tuple:
        return self.hi.shape

    @property
    def ndim(self) -> int:
        return self.hi.ndim

    @property
    def nbytes(self) -> int:
        return self.hi.nbytes + self.lo.nbytes

    def __array__(self, dtype=None, copy=None):
        host = Halves(np.asarray(self.hi), np.asarray(self.lo),
                      self.dtype).join()
        return host if dtype is None else host.astype(dtype)

    def __repr__(self) -> str:
        shape = getattr(self.hi, "shape", None)   # a mapped tree's words
        at = list(shape) if shape is not None else f"hi={self.hi!r}, " \
            f"lo={self.lo!r}"
        return f"Halves({self.dtype}{at})"


def plane_map(fn, plane, *more):
    """``fn(plane, *more)`` for whole arrays, word by word for ``Halves``
    (``more`` in the plane's own layout): what moves cells without
    reading them needs no 64-bit value."""
    if isinstance(plane, Halves):
        return plane.map(fn, *more)
    return fn(plane, *more)


def plane_take(plane, take):
    """``take(plane)`` (a row slice, a gather of rows or of cells) as
    values of the plane's own dtype: the words of a ``Halves`` plane are
    each taken FIRST and joined after, so only what was taken is ever
    64 bits wide."""
    if isinstance(plane, Halves):
        return plane.map(take).join()
    return take(plane)


def plane_row(plane, row):
    """Ring row ``row`` (a traced scalar) of a ``[ring, capacity]`` plane
    as a 1-D array of the plane's own dtype."""
    return plane_take(plane, lambda a: jax.lax.dynamic_index_in_dim(
        a, row, 0, keepdims=False))


def planes_joined(planes: dict) -> dict:
    """``planes`` with every ``Halves`` one joined: for a program that
    reads and rewrites WHOLE planes (the session operator's scans), at
    its entry. Inside a program the join is no work (the compiler keeps
    a 64-bit array as its pair of words), where a 64-bit PARAMETER is a
    pass over the plane."""
    return {name: plane.join() if isinstance(plane, Halves) else plane
            for name, plane in planes.items()}


def planes_stored_like(stored: dict, planes: dict) -> dict:
    """``planes`` back in the layouts of ``stored``: at such a program's
    exit, the twin of ``planes_joined``."""
    return {name: Halves.split(plane) if isinstance(stored[name], Halves)
            else plane for name, plane in planes.items()}


def stores_halves(dtype, ring) -> bool:
    """Whether a plane is stored as ``Halves`` (the one-chip backend's
    and the mesh state's one layout rule): a ring plane of a 64-bit
    integer."""
    dtype = np.dtype(dtype)
    return bool(ring) and dtype.kind in "iu" and dtype.itemsize == 8


def identity_words(kind: str, dtype) -> Halves:
    """The two words of a 64-bit integer aggregate's identity, as numpy
    scalars (a constant in a trace)."""
    dtype = np.dtype(dtype)
    info = np.iinfo(dtype)
    ident = {"min": info.max, "max": info.min}.get(kind, 0)
    return Halves.split(np.asarray(ident, dtype))


def plane_identity(kind: str, plane):
    """The aggregate's identity in ``plane``'s layout: the two words for
    a ``Halves`` plane."""
    return (identity_words(kind, plane.dtype) if isinstance(plane, Halves)
            else AGG_INITS[kind](plane.dtype))


def make_plane(kind: str, shape: tuple[int, ...], dtype, halves: bool):
    """An accumulator of identities in its stored layout."""
    if not halves:
        return make_accumulator(kind, shape, dtype)
    return identity_words(kind, dtype).map(
        lambda word: jnp.full(shape, word, jnp.uint32))


def _fold_scope(kind: str):
    """The scope a kind's scatter is named by (``_FOLD_REGIONS``)."""
    kind = _FOLD_REGIONS.get(kind, kind)
    return jax.named_scope(f"fold.{kind}")


def scatter_fold(kind: str, acc: jax.Array, flat_idx: jax.Array,
                 values: jax.Array, valid: jax.Array) -> jax.Array:
    """Fold a batch into a flat accumulator: acc[flat_idx] op= values,
    masked by ``valid`` (invalid rows fold the identity into slot 0).
    The scatter and its masking sit in a scope named after the kind
    (``fold.max`` under ``fold.scatter``; a presence plane's is
    ``fold.count``), so a trace tells a max fold from an add fold
    whatever program holds them."""
    with jax.named_scope("fold.scatter"), _fold_scope(kind):
        identity = AGG_INITS[kind](acc.dtype)
        idx = jnp.where(valid, flat_idx, 0)
        vals = jnp.where(valid, values.astype(acc.dtype), identity)
        return AGG_FOLDS[kind](acc, idx, vals)


#: rows of a batch that ``ring_fold`` scatters at a time
_FOLD_CHUNK = 1 << 14

#: the kinds whose fold is an addition: into a ``Halves`` plane they go
#: limb by limb (``ring_fold``)
_ADDITIVE = ("sum", "count")


def folds_by_limbs(kind: str, halves: bool) -> bool:
    """Whether ``ring_fold`` adds a batch into the plane limb by limb
    with 32-bit scatters: an additive kind into a ``Halves`` plane."""
    return halves and kind in _ADDITIVE


def _limb_width(n: int) -> int:
    """The widest limb that ``n`` rows of one key cannot carry out of 32
    bits: ``n * (2^w - 1) < 2^32``."""
    return 32 - (n - 1).bit_length()


def _limb(words: Halves, w: int, j: int) -> jax.Array:
    """Bits ``[w * j, w * j + w)`` of 64-bit values given as their words:
    32-bit shifts by constants, no 64-bit value."""
    s, mask = w * j, np.uint32((1 << w) - 1)
    if s + w <= 32:
        bits = words.lo >> np.uint32(s)
    elif s >= 32:
        bits = words.hi >> np.uint32(s - 32)
    else:
        bits = (words.lo >> np.uint32(s)) | (words.hi << np.uint32(32 - s))
    return bits & mask


def _limb_cuts(kind: str, values: jax.Array, w: int) -> list:
    """A batch's 64-bit values as ``uint32`` limbs of ``w`` bits, lowest
    first: ``ceil(64 / w)`` of them, or the one limb of ones a ``count``
    folds (``COUNT_KINDS``: every row counts one)."""
    if kind == "count":
        return [jnp.ones(values.shape, jnp.uint32)]
    words = Halves.split(values)
    return [_limb(words, w, j) for j in range(-(-64 // w))]


def _add_shifted(row: Halves, delta: jax.Array, s: int) -> Halves:
    """``row + (delta << s)`` modulo 2^64 over a ring row's two words,
    ``delta`` a ``uint32`` row and ``s`` a constant under 64: dense
    32-bit arithmetic, the carry out of ``lo`` taken from the wrap."""
    hi, lo = row.hi, row.lo
    if s < 32:
        low = delta << np.uint32(s) if s else delta
        lo = lo + low
        hi = hi + (lo < low).astype(jnp.uint32)
        if s:
            hi = hi + (delta >> np.uint32(32 - s))
    else:
        hi = hi + (delta << np.uint32(s - 32) if s > 32 else delta)
    return Halves(hi, lo, row.dtype)


def ring_fold(kind: str, plane, ring_idx: jax.Array,
              slots: jax.Array, values: jax.Array,
              valid: jax.Array, counted: bool = False):
    """Fold a batch into a ``[ring, capacity]`` plane, ring row by ring
    row: plane[ring_idx, slots] op= values, masked by ``valid``. The
    plane is the ``Halves`` of a 64-bit integer one or one array (a
    float or 32-bit plane), on one chip and on a mesh's shard alike, and
    comes back as it came. No flat
    view of the plane is taken: the TPU keeps a 2-D plane tiled, and
    ``plane.reshape(-1)`` around a scatter copies all of it into a flat
    buffer and back (three quarters of the one-chip ingest step until
    PR 34, 100 of the mesh step's 162 ms until PR 36). Every fold of a
    batch into ring planes goes through here: the backend's ``jit_fold``,
    the device-born step, and the mesh step on each shard's own plane
    under ``shard_map``, inside its exchange rounds' ``while_loop``.
    Each ring row the batch holds a valid row for is sliced out,
    folded as the 1-D accumulator it is, and written
    back; the rows the batch does not touch are skipped on the device.
    A scatter costs the TPU by the update, masked or not, so a touched
    row takes the batch ``_FOLD_CHUNK`` rows at a time and skips the
    chunks that hold nothing for it: a batch in event-time order pays
    for each of its rows once, plus one chunk where it crosses a pane's
    edge; a batch shuffled over k ring rows pays k times, which is why
    the host-born operator sorts such a batch by ring row first
    (``DeviceWindowAggOperator._fold``). Any number of touched rows is
    right, 0 to ``ring``. What a ring row costs beside its scatters (the
    mask, the slice, the chunk walk, the write-back) is the region
    ``fold.row``.

    And a scatter costs by the WIDTH of the update: a 64-bit one six
    times a 32-bit one (PERF.md section 6, PR 34 and PR 54). An additive
    kind (``sum``, ``count``) therefore never scatters into a ``Halves``
    row's two words. The batch's values, read as unsigned 64-bit words,
    are cut into limbs of ``w = 32 - ceil(log2 n)`` bits, so that no
    number of the batch's n rows on one slot can carry out of 32 bits;
    each limb that some valid row of the ring row holds a non-zero value
    in (decided on the device; a ``count`` folds ones and has one) is
    scatter-added into a zeroed ``uint32`` row, one 32-bit scatter a
    chunk, and that row, shifted to the limb's place, is added into the
    ring row's two words with the carry from ``lo`` into ``hi``. That is
    the 64-bit add modulo 2^64 whatever the values are: Q5's prices run
    two limbs, a negative value all of them, and no 64-bit value of a
    ring row exists. All of it lies under the kind's own scope
    (``fold.scatter/fold.sum``: the scatters beneath ``fold.limb``, the
    dense add beneath ``fold.carry``). ``min`` / ``max`` / ``presence``
    and every float or 32-bit plane fold with the one scatter of their
    kind (``scatter_fold``).

    ``counted``: also return the limb scatters the fold ran, one a live
    limb a touched ring row (an int32 scalar; the integer 0 for a plane
    that does not fold by limbs)."""
    limbs = folds_by_limbs(kind, isinstance(plane, Halves))
    n = slots.shape[0]
    if n == 0:
        return (plane, 0) if counted else plane
    chunk = min(_FOLD_CHUNK, n)
    with jax.named_scope("fold.row"):
        ring_idx = ring_idx.astype(jnp.int32)
        lane = jnp.arange(chunk, dtype=jnp.int32)
    if limbs:
        w = _limb_width(n)
        with jax.named_scope("fold.scatter"), _fold_scope(kind), \
                jax.named_scope("fold.limb"):
            cuts = _limb_cuts(kind, values.astype(plane.dtype), w)

    def chunk_walk(mine, fold_hit, row):
        """``row`` after ``fold_hit(row, at, hit)`` for every chunk of
        the batch that holds a row of ``mine``."""
        def fold_chunk(c, row):
            at = jnp.minimum(c * chunk, n - chunk)   # the last one backs up
            hit = jax.lax.dynamic_slice(mine, (at,), (chunk,)) \
                & (at + lane >= c * chunk)
            return jax.lax.cond(
                hit.any(), lambda row: fold_hit(row, at, hit),
                lambda row: row, row)

        return jax.lax.fori_loop(0, -(-n // chunk), fold_chunk, row)

    def fold_whole(mine, row):
        return chunk_walk(mine, lambda row, at, hit: scatter_fold(
            kind, row,
            jax.lax.dynamic_slice(slots, (at,), (chunk,)),
            jax.lax.dynamic_slice(values, (at,), (chunk,)), hit), row)

    def fold_limbs(mine, row: Halves, ran):
        for j, cut in enumerate(cuts):
            with jax.named_scope("fold.limb"):
                live = mine & (cut != 0) if len(cuts) > 1 else mine

            def add_limb(carry, j=j, cut=cut, live=live):
                row, ran = carry
                with jax.named_scope("fold.limb"):
                    # the zeroed row as the fill of a TRACED zero (the
                    # count's sign bit): the fill of a constant the v5e's
                    # compiler makes anew without its name path, and a
                    # trace then books 64 MB of writes under no region
                    zero = (ran >> 31).astype(jnp.uint32)
                    delta = chunk_walk(
                        live, lambda delta, at, hit: delta.at[jnp.where(
                            hit, jax.lax.dynamic_slice(
                                slots, (at,), (chunk,)), 0)].add(jnp.where(
                                    hit, jax.lax.dynamic_slice(
                                        cut, (at,), (chunk,)), 0)),
                        jnp.full(row.shape, zero))
                with jax.named_scope("fold.carry"):
                    return _add_shifted(row, delta, w * j), ran + 1

            if len(cuts) > 1:
                row, ran = jax.lax.cond(live.any(), add_limb,
                                        lambda carry: carry, (row, ran))
            else:
                row, ran = add_limb((row, ran))
        return row, ran

    @jax.named_scope("fold.row")
    def fold_row(r, carry):
        plane, ran = carry
        mine = valid & (ring_idx == r)

        def fold(carry):
            plane, ran = carry
            if limbs:
                # the row's two words as they lie: no 64-bit value of it
                row = plane.map(lambda a: jax.lax.dynamic_index_in_dim(
                    a, r, 0, keepdims=False))
                with jax.named_scope("fold.scatter"), _fold_scope(kind):
                    row, ran = fold_limbs(mine, row, ran)
            else:
                row = fold_whole(mine, plane_row(plane, r))
                if isinstance(plane, Halves):
                    # a min / max: the words of ring row r joined, folded
                    # as the 64-bit row they are, and split again
                    row = Halves.split(row)
            return plane_map(
                lambda words, new: jax.lax.dynamic_update_index_in_dim(
                    words, new, r, 0), plane, row), ran

        return jax.lax.cond(mine.any(), fold, lambda carry: carry, carry)

    plane, ran = jax.lax.fori_loop(
        0, plane.shape[0], fold_row,
        (plane, jnp.int32(0) if limbs else ()))
    if not counted:
        return plane
    return plane, ran if limbs else 0


def pane_window_merge(kind: str, acc: jax.Array,
                      pane_rows: jax.Array) -> jax.Array:
    """Merge selected pane rows of a [ring, capacity] accumulator into one
    [capacity] result — the slice-shared window fire
    (reference SliceSharedWindowAggProcessor)."""
    return _MERGES[kind](acc[pane_rows], 0)


@partial(jax.jit, static_argnames=("k",))
def segment_topk(values: jax.Array, valid: jax.Array, k: int
                 ) -> tuple[jax.Array, jax.Array]:
    """Top-k over a slot-indexed value array (Nexmark Q5 'hot items'):
    returns (topk values, topk slot indices)."""
    neg_inf = (jnp.finfo(values.dtype).min
               if jnp.issubdtype(values.dtype, jnp.floating)
               else jnp.iinfo(values.dtype).min)
    masked = jnp.where(valid, values, neg_inf)
    return jax.lax.top_k(masked, k)


def pow2_ceil(n: int) -> int:
    """Next power of two >= n (n >= 1). Batches pad to power-of-two
    lengths so one compiled executable serves every upstream batch size —
    variable lengths (e.g. behind a WHERE filter) otherwise force an XLA
    recompile per distinct shape (measured 15x slower than the fold
    itself on the device GROUP BY path)."""
    return 1 << (n - 1).bit_length() if n > 1 else 1
