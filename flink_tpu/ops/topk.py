"""Exact masked top-k without a sort over the slots.

``lax.top_k`` over a full [capacity] accumulator is the single most
expensive op a window fire can hold (reference fire loop:
WindowOperator.onEventTime:437 emitting ORDER BY ... LIMIT k results): XLA
lowers it to a variant of a full sort, 138 ms for k = 1000 over a v5e's
2^23 int64 slots (PERF.md section 5, PR 27). The fire only needs the k
largest values and their slots, so this module finds the exact k-th
largest value T and compacts the survivors. Two walks find T:

* ``threshold_topk``, for integers: T is built bit by bit from the top,
  one vectorized compare-and-count over the slots per bit, starting at the
  highest bit the largest valid value HAS (a window's COUNTs have 15 bits,
  whatever their plane declares), on a 32-bit view wherever that maximum
  is under 2^32; the winners (every slot above T, seats left over filled
  from the slots equal to T in index order: ties are interchangeable by
  definition) compact through cumsum + searchsorted. No scatter, no sort:
  6 ms for the same 2^23 int64 slots, 15 passes (my chip run, PR 31). Both
  window stacks fire through it (one chip: ``device_window._select_topk``;
  the mesh: ``parallel/sharded_window.global_topk``, per shard under
  shard_map). An integer rank that may be negative (no ``value_bits``
  promise) walks its sign-flipped view: the same loop, all of its bits.
* the radix walk (``_masked_topk_radix``), for floats: 4 passes of 16-bit
  histograms over a monotone uint64 image of the values (the
  sign-magnitude trick for floats), each one elementwise extract + one
  scatter-add into 65536 bins; the survivors compact with a two-ended
  scatter (ties from the back of a [k] buffer, strict from the front and
  written last, so a collision keeps the strict element). A TPU runs a
  scatter-add of 2^23 updates slowly, so the mesh hands these ranks to
  ``masked_topk_sort`` instead.

Contract matches lax.top_k + validity: ``(values[k], indices[k], ok[k])``
sorted descending; ``ok[i]`` False marks padding when fewer than k valid
slots exist.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

__all__ = ["TopK", "threshold_topk", "select_guarded", "masked_topk_radix",
           "masked_topk_sort", "masked_topk"]


def _to_uint64(v: jax.Array) -> jax.Array:
    """Monotone map of any ordered dtype into uint64 (order-preserving:
    a < b  <=>  map(a) < map(b))."""
    dt = v.dtype
    if jnp.issubdtype(dt, jnp.floating):
        bits = jax.lax.bitcast_convert_type(
            v, jnp.int32 if dt == jnp.float32 else jnp.int64)
        bits = bits.astype(jnp.int64)
        width = 32 if dt == jnp.float32 else 64
        sign = jnp.int64(1) << (width - 1)
        # positive floats: set sign bit; negative: flip all bits
        u = jnp.where(bits >= 0, bits | sign,
                      ~bits & ((sign << 1) - 1) if width == 32 else ~bits)
        u = u.astype(jnp.uint64)
        if width == 32:
            u = u << 32  # widen keeping order
        return u
    # signed ints: flip the sign bit after widening
    return (v.astype(jnp.int64).astype(jnp.uint64)
            ^ jnp.uint64(1) << jnp.uint64(63))


def select_guarded(dtype, value_bits: int) -> bool:
    """Whether ``threshold_topk`` compiles its guard against a negative
    rank for values of ``dtype`` under the promise ``value_bits``: a
    signed integer that nothing promises to fit under its own width. The
    host asks the same question of a fire it drains (``DEVICE_STATS.
    fire_select_guarded_total``), so it is one definition."""
    dt = jnp.dtype(dtype)
    return bool(jnp.issubdtype(dt, jnp.signedinteger)
                and value_bits >= 8 * dt.itemsize)


class TopK(NamedTuple):
    """What a select hands back: the ``lax.top_k`` + validity contract
    (sorted descending, ``ok`` False on padding) and how it got there."""
    values: jax.Array           # [k]
    indices: jax.Array          # [k] int32 slots
    ok: jax.Array               # [k] bool
    passes: jax.Array           # int32: compare-and-count passes walked
    fell_back: jax.Array        # bool: ``otherwise`` ran, not the walk


def threshold_topk(values: jax.Array, valid: jax.Array, k: int,
                   value_bits: int = 64, otherwise=None) -> TopK:
    """Exact top-k among the valid slots of a 1-D ``values`` by threshold
    select: the k-th largest valid value T is built bit by bit from the
    top, one compare-and-count pass over the slots per bit, then the
    winners (every slot above T, seats left over filled from the slots
    equal to T in index order) compact through cumsum + searchsorted. No
    scatter and no sort over the slots.

    The walk adapts to the data, not to a declared width: it starts at the
    highest bit the largest valid value HAS (one max-reduce; the loop's
    trip count is that bit length), and where that maximum is under 2^32
    the compares run on a 32-bit view. ``value_bits`` is the caller's
    promise that the values are non-negative and under 2^value_bits; it
    decides only what need not be compiled: at most 32, the 64-bit view;
    under the dtype's width, the guard. Without the promise a negative
    valid value (the same max-reduce sees it, as the unsigned view's top
    bit) turns the view into the values' monotone image, sign bit
    flipped, and the same walk runs over all of the dtype's bits: one
    algorithm, and nothing but compares and counts over the slots (the
    radix walk that once took these ranks does not compile inside a fire
    over 2^24 slots on a v5e: its 64-bit scans overflow vmem). Floats
    (their order is not their bit order) go whole to
    ``otherwise(values, valid, k) -> (values, indices, ok)``, which
    defaults to the radix walk; a caller whose backend lowers scatter
    badly passes ``masked_topk_sort``.

    Traceable, not jitted: it runs inside the caller's program (under x64
    wherever a 64-bit rank may come)."""
    if otherwise is None:
        otherwise = _masked_topk_radix
    dt = values.dtype
    k = min(k, values.shape[0])
    if jnp.issubdtype(dt, jnp.floating):
        v, i, ok = otherwise(values, valid, k)
        return TopK(v, i.astype(jnp.int32), ok, jnp.int32(0),
                    jnp.bool_(True))
    kk = jnp.minimum(jnp.int32(k), jnp.sum(valid, dtype=jnp.int32))
    width = 8 * dt.itemsize
    guarded = select_guarded(dt, value_bits)
    wide = width > 32 and value_bits > 32

    def view(unsigned, flip=None):
        if flip is None:
            return jnp.where(valid, values, 0).astype(unsigned)
        return jnp.where(valid, values ^ flip, 0).astype(
            f"uint{width}").astype(unsigned)

    # a negative value of a signed dtype sets its unsigned view's top bit
    top = _bit_length(jnp.max(view(jnp.uint64 if wide else jnp.uint32)))
    flip = None
    if guarded:
        negative = top >= (64 if wide else 32)
        # x ^ min is monotone from the signed order onto the unsigned one;
        # an invalid slot still reads 0, under every valid slot but one
        # that holds the dtype's minimum, and the ties' mask tells those
        # apart
        flip = jnp.where(negative, jnp.iinfo(dt).min, 0).astype(dt)
        top = jnp.where(negative, width, top)

    def select(unsigned):
        idx, filled = _threshold_select(view(unsigned, flip), valid, k, kk,
                                        top)
        return (jnp.where(filled, values[idx], _sentinel(dt)), idx, filled,
                top)

    if not wide:
        return TopK(*select(jnp.uint32), jnp.bool_(False))
    return TopK(*jax.lax.switch(
        (top > 32).astype(jnp.int32),
        [lambda: select(jnp.uint32), lambda: select(jnp.uint64)]),
        jnp.bool_(False))


def _bit_length(m: jax.Array) -> jax.Array:
    """Bits an unsigned scalar has (0 for 0), int32; 32-bit ops only."""
    if m.dtype.itemsize <= 4:
        return (32 - jax.lax.clz(m.astype(jnp.uint32))).astype(jnp.int32)
    hi = (m >> 32).astype(jnp.uint32)
    lo = m.astype(jnp.uint32)
    return jnp.where(hi > 0, 64 - jax.lax.clz(hi),
                     32 - jax.lax.clz(lo)).astype(jnp.int32)


def _threshold_select(u: jax.Array, valid: jax.Array, k: int,
                      kk: jax.Array, nbits: jax.Array):
    """The walk and the compaction over an unsigned view ``u`` whose
    invalid slots read 0: (slots [k] int32, filled [k]); unordered."""
    n = u.shape[0]
    one = jnp.ones((), u.dtype)

    def bit(i, thr):
        # bit b joins T iff at least kk slots sit at or above T | 1 << b;
        # cand >= 1, so an invalid slot (0) never counts
        cand = thr | (one << (nbits - 1 - i).astype(u.dtype))
        cnt = jnp.sum(u >= cand, dtype=jnp.int32)
        return jnp.where(cnt >= kk, cand, thr)

    thr = jax.lax.fori_loop(0, nbits, bit, jnp.zeros((), u.dtype))
    strict = u > thr                     # provably fewer than kk of them
    tie = valid & (u == thr)
    # int32 on purpose: under x64 a bare cumsum widens to int64, which a
    # TPU emulates
    cum_s = jnp.cumsum(strict, dtype=jnp.int32)
    cum_t = jnp.cumsum(tie, dtype=jnp.int32)
    n_s = cum_s[-1]
    # seat t (1-based): the t-th strict slot while they last, then the
    # (t - n_s)-th tie slot; searchsorted on the monotone prefix sums
    # finds the slot that holds each rank
    targets = jnp.arange(1, k + 1, dtype=jnp.int32)
    pos_s = jnp.searchsorted(cum_s, targets)
    pos_t = jnp.searchsorted(cum_t, jnp.maximum(targets - n_s, 1))
    idx = jnp.minimum(jnp.where(targets <= n_s, pos_s, pos_t), n - 1)
    filled = targets <= kk
    order = jnp.lexsort((jnp.where(filled, u[idx], 0), filled))[::-1]
    return idx[order].astype(jnp.int32), filled[order]


def masked_topk(values: jax.Array, valid: jax.Array, k: int,
                value_bits: int = 64):
    """The exact masked top-k a one-chip fire calls:
    (values [k], slots [k], ok [k]). Integers take the threshold select
    (``threshold_topk``); floats, and integers with a negative valid
    value, the radix walk of 16-bit histograms. ``value_bits``: the
    caller's promise that the values are non-negative and under
    2^value_bits; 64 is always safe. Consumers needing the sort-based
    lowering call ``masked_topk_sort`` directly."""
    from .hash_table import ensure_x64

    ensure_x64()  # the wide view and the radix walk are uint64
    return _masked_topk(values, valid, k, value_bits)


#: the older name, kept for its callers
masked_topk_radix = masked_topk


@partial(jax.jit, static_argnames=("k", "value_bits"))
def _masked_topk(values, valid, k: int, value_bits: int):
    top = threshold_topk(values, valid, k, value_bits)
    return top.values, top.indices.astype(jnp.int64), top.ok


@partial(jax.jit, static_argnames=("k",))
def _masked_topk_radix(values: jax.Array, valid: jax.Array, k: int):
    """The radix walk: four 16-bit histogram passes over the monotone
    uint64 image pin the exact k-th largest value, any ordered dtype."""
    n = values.shape[0]
    k = min(k, n)
    u = _to_uint64(values)
    nvalid = jnp.sum(valid, dtype=jnp.int64)
    kk = jnp.minimum(jnp.int64(k), nvalid)          # effective k
    cand = valid
    above = jnp.int64(0)                             # strictly above prefix
    prefix = jnp.uint64(0)
    bins = jnp.arange(65536, dtype=jnp.int64)
    for shift in (48, 32, 16, 0):
        field = ((u >> shift) & jnp.uint64(0xFFFF)).astype(jnp.int32)
        hist = jnp.zeros(65536, jnp.int64).at[field].add(
            cand.astype(jnp.int64))
        # count of candidates at-or-above each bin (descending cumulative)
        revcum = jnp.cumsum(hist[::-1])[::-1]
        # above + revcum[0] >= kk always holds (revcum[0] counts every
        # candidate), so bstar is a real bin; when kk == 0 the condition
        # is all-True and bstar saturates at 65535 (downstream masks are
        # empty because valid is all-False in that case)
        cond = (above + revcum) >= kk
        bstar = jnp.max(jnp.where(cond, bins, -1))
        above = above + jnp.where(bins > bstar, hist, 0).sum()
        prefix = prefix | (bstar.astype(jnp.uint64) << shift)
        cand = cand & (field.astype(jnp.int64) == bstar)
    thr = prefix                                     # exact k-th largest
    strict = valid & (u > thr)                       # provably < kk of them
    tie = valid & (u == thr)
    # two independent 1-D scans (a stacked [2, n] cumsum hits a slow XLA
    # path: measured 72 ms vs 2x16 ms at n=2M on CPU)
    cum_s = jnp.cumsum(strict.astype(jnp.int64))
    cum_t = jnp.cumsum(tie.astype(jnp.int64))
    # strict compacts from the front, ties from the back; strict written
    # last so a collision keeps the strict element (ties all equal thr, so
    # dropping any particular tie is exact)
    tie_pos = jnp.clip(jnp.int64(k) - cum_t, 0, k - 1)
    strict_pos = cum_s - 1
    idx = jnp.arange(n, dtype=jnp.int64)
    # compact only the INDEX (2 scatter passes); values gather back from
    # the k winners — scatters over [n] are the cost that scales
    buf_i = jnp.full(k, -1, jnp.int64)
    buf_i = buf_i.at[jnp.where(tie, tie_pos, k)].set(idx, mode="drop")
    buf_i = buf_i.at[jnp.where(strict, strict_pos, k)].set(idx, mode="drop")
    filled = buf_i >= 0
    sent = _sentinel(values.dtype)
    buf_v = jnp.where(filled, values[jnp.maximum(buf_i, 0)], sent)
    # order filled-first then by value descending (filled slots with the
    # sentinel value are real data; unfilled sort behind via the flag)
    order = jnp.lexsort((jnp.where(filled, _to_uint64(buf_v),
                                   jnp.uint64(0)),
                         filled))[::-1]
    return buf_v[order], jnp.maximum(buf_i, 0)[order], filled[order]


def _sentinel(dtype):
    return (jnp.finfo(dtype).min if jnp.issubdtype(dtype, jnp.floating)
            else jnp.iinfo(dtype).min)


@partial(jax.jit, static_argnames=("k",))
def masked_topk_sort(values: jax.Array, valid: jax.Array, k: int):
    """lax.top_k reference implementation (XLA sort-based)."""
    sent = _sentinel(values.dtype)
    masked = jnp.where(valid, values, sent)
    kk = min(k, values.shape[0])
    vals, idx = jax.lax.top_k(masked, kk)
    return vals, idx, jnp.take(valid, idx)
