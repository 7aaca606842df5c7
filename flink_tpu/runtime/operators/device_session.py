"""Device session windows: merging windows on per-key session LANES.

The reference runs sessions through the generic WindowOperator with a
MergingWindowSet (flink-streaming-java runtime/operators/windowing/
MergingWindowSet.java, WindowOperator.java:98): one state namespace per
window, merged pairwise as elements arrive. That design is per-record and
per-window-object — the opposite of what a TPU wants.

This operator keeps the SURVEY §7 split: the host runs only the watermark
protocol; gap/merge logic AND the per-session accumulators live on device
in dense planes. The layout mirrors the slice-window pane ring: every key
slot owns L session *lanes* ([L, capacity] planes for start/end/open +
one per aggregate), and a key's live sessions rotate through its lanes
the way panes rotate through ring rows.

Per micro-batch, ONE fused program (``_sess_step``):
  * events arrive sorted by (key, ts) (host numpy sort), so a key's rows
    are one run and its in-batch sessions are contiguous segments;
  * hash-table lookup-or-insert of each run's FIRST row -> key slot,
    handed on to the run's other rows;
  * session segmentation: an event merges into a lane it overlaps within
    ``gap`` (all L lanes are checked) unless the watermark has closed
    that lane's session, successive in-batch events split where ts gaps
    reach ``gap``; new segments allocate the next free lane;
  * one scatter-fold per aggregate into (lane, slot), start folds MIN,
    end folds MAX — so a merging event EXTENDS its session in place;
  * the key's current-lane pointer updates to its last event's lane.

A session window [start, last_ts + gap) is CLOSED once the watermark
passes its end: no later event merges into it, whether or not its rows
have left yet. The rows leave in a FIRE (``_sess_fire``): one compiled
scan over the [L, capacity] planes finds the closed sessions, packs the
mask into 32-bit words and selects at most ``fire_rows`` of them through
the words' population counts (what it gathers, emits and resets costs by
what fired, never by what exists), and resets their lanes. A fire whose
closed sessions outnumber ``fire_rows`` takes further ROUNDS, one program
each. Fires run at a cadence in event time (``fire_interval_ms``, a fifth
of the gap unless given), not on every watermark; the operator forwards a
watermark only when a fire at or past it has handed on all its rows.
With ``async_fire`` a round's outputs start copying to the host at its
dispatch and the rows leave on a later mailbox turn (a batch, a
watermark, the processing-time turn), so the task's thread never waits
for a fire except at the end of input and at a checkpoint.

Segments only bypass the lanes into the host pending buffer once they
are SETTLED — no event that is still non-late could merge into them
(end + 2*gap behind the watermark); anything fresher keeps a lane,
where out-of-order events find it through the all-lanes merge probe.

Semantics vs the host operator (exact for in-order input and for
arbitrary NON-late disorder, except the bridge case below):
  * an event at exactly ``last_ts + gap`` starts a NEW session, here and
    in the host operator (``window.TimeWindow.intersects`` is strict).
    Flink's ``TimeWindow.intersects`` also merges windows that merely
    touch, but only while the earlier one is still in state, which it
    is until the watermark fires it: whether such an event joins then
    turns on when a periodic watermark was cut, not on the data. The
    strict rule is a function of the data alone;
  * allowed_lateness = 0: an event whose merged window would end at or
    behind the watermark is dropped and counted, like the device
    pane operator;
  * an event bridging TWO open sessions of one key joins one of them;
    the host MergingWindowSet would fuse both into a single window. This
    needs per-key disorder > gap to arise; such streams belong on the
    host operator (the planner default for merging windows).
  * more than L sessions of one key that have not FIRED yet (watermark
    lag + fire cadence > ~L * gap) raises at the next fire instead of
    corrupting state.
"""

from __future__ import annotations

import time
from functools import partial
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ...core.elements import Watermark
from ...core.records import MAX_TIMESTAMP, RecordBatch, Schema
from ...metrics.tracing import TRACER
from ...metrics.device import DEVICE_STATS, instrumented_program_cache, \
    pytree_nbytes
from ...ops.hash_table import EMPTY_KEY, lookup_or_insert, \
    sanitize_keys_device
from ...ops.segment_ops import AGG_INITS, Halves, identity_words, \
    plane_map, plane_take, planes_joined, planes_stored_like, pow2_ceil
from ...state.tpu_backend import TpuKeyedStateBackend
from .base import OneInputOperator, OperatorContext, Output
from .device_window import AggSpec

__all__ = ["DeviceSessionWindowOperator"]

_NEG = np.int64(-(1 << 62))
_POS = np.int64(1 << 62)
#: rows one fire round compacts at most, unless the operator is told
_FIRE_ROWS = 1 << 18
#: bits of a packed mask word, and words of a group of the select
_WORD = 32
#: the device counters a step adds to and a fire round hands back
_STEP_STATS = ("lanes_allocated", "lane_overflow", "settled")


def _next_after(mask, idx, n):
    """Per row, the index of the first row AFTER it where ``mask`` holds
    (``n`` where none does)."""
    at = jax.lax.cummin(jnp.where(mask, idx, n), reverse=True)
    return jnp.concatenate([at[1:], jnp.full(1, n, at.dtype)])


def _fold_cells(kind: str, plane, lane, slot, values, sel):
    """``plane[lane, slot] op= values`` where ``sel``: the two-index twin
    of ``segment_ops.scatter_fold`` (an unselected row folds the identity
    into cell (0, 0)), on the [L, capacity] plane as it is stored (the
    v5e's compiler still re-tiles each plane around its scatter: the
    region map books that under ``session.fold``)."""
    identity = AGG_INITS[kind](plane.dtype)
    at = plane.at[jnp.where(sel, lane, 0), jnp.where(sel, slot, 0)]
    vals = jnp.where(sel, values.astype(plane.dtype), identity)
    if kind == "min":
        return at.min(vals)
    if kind == "max":
        return at.max(vals)
    return at.add(vals)


@instrumented_program_cache("device_session.step", maxsize=64)
def _sess_step(fold_sig: tuple, lanes: int, gap: int, dirty_block: int):
    """One fused program per batch. ``fold_sig``: (kind, name, field)."""
    L = lanes
    donate = (0, 1, 2, 3, 4, 5, 6)

    @partial(jax.jit, donate_argnums=donate)
    def step(table, planes, cur_lane, dropped, late, stats, dirty, keys, ts,
             cols, n_valid, boundary):
        # the backend stores a 64-bit lanes plane as its two 32-bit words
        # (ops/segment_ops.Halves). What the step reads of a plane it
        # gathers word by word; what it folds into, it joins (inside a
        # program a 64-bit array is its pair of words anyway) and splits
        # at its exit
        stored = planes
        B = keys.shape[0]
        idx = jnp.arange(B, dtype=jnp.int32)
        in_batch = idx < n_valid
        with jax.named_scope("session.probe"):
            keys = sanitize_keys_device(keys)
            # sorted by (key, ts): a key's rows are one run. Only the
            # run's first row probes; the others take its slot
            same_key = jnp.concatenate(
                [jnp.zeros(1, bool), keys[1:] == keys[:-1]])
            run_start = in_batch & ~same_key
            table, fslot, fok = lookup_or_insert(table, keys, run_start,
                                                 handover=True,
                                                 distinct=True)
            head = jax.lax.cummax(jnp.where(run_start, idx, 0))
            kslot, ok = fslot[head], fok[head]
        valid = ok & in_batch
        dropped = dropped + jnp.sum(in_batch & ~ok).astype(jnp.int64)
        gs = jnp.maximum(kslot, 0)
        first = valid & run_start
        with jax.named_scope("session.segment"):
            # merge check against ALL open lanes of the key (L gathers a
            # plane). A lane whose session the watermark has closed
            # (end + gap <= boundary) absorbs nothing: its rows are as
            # good as emitted, whenever the fire takes them
            open_l, mergeable = [], []
            for lane in range(L):
                s = plane_take(stored["__start__"], lambda a: a[lane][gs])
                e = plane_take(stored["__end__"], lambda a: a[lane][gs])
                o = stored["__open__"][lane][gs] > 0
                open_l.append(o)
                # strict overlap, like window.TimeWindow.intersects:
                # [ts, ts+gap) meets [s, e+gap) iff ts < e+gap, s < ts+gap
                mergeable.append(o & (ts > s - gap) & (ts < e + gap)
                                 & (e + gap > boundary))
            mg = jnp.stack(mergeable, axis=1)              # [B, L]
            open_bl = jnp.stack(open_l, axis=1)            # [B, L]
            can_merge = mg.any(axis=1)
            merge_lane = jnp.argmax(mg, axis=1).astype(jnp.int32)
            # late (allowed_lateness=0, like the host operator): the
            # event's own window [ts, ts+gap) closed already and no open
            # session can absorb it. Segment followers of a LIVE anchor
            # are never late (sorted order: their ts >= the anchor's,
            # whose window is open).
            is_late = valid & ~can_merge & (ts + gap <= boundary)
            late = late + jnp.sum(is_late).astype(jnp.int64)
            valid = valid & ~is_late
            # anchors: key-first, an in-batch ts jump >= gap (sorted by
            # (key, ts), prev row is the predecessor), or the first
            # survivor after a late-dropped predecessor (it must
            # re-decide its lane)
            prev_ts = jnp.concatenate([ts[:1], ts[:-1]])
            prev_same = same_key & ~first
            prev_late = jnp.concatenate([jnp.zeros(1, bool), is_late[:-1]])
            in_jump = prev_same & ((ts - prev_ts >= gap) | prev_late)
            is_anchor = valid & (first | in_jump)
            # ---- two-level fold: events -> per-SEGMENT accumulators ----
            # every anchor opens a batch-local segment; events fold into
            # [B] segment buffers first. Only SETTLED segments (no
            # non-late event can still merge into them; see the
            # classification below) bypass the lanes into the
            # pending-emission buffers — every other segment takes a
            # lane, so a key may allocate SEVERAL lanes per batch and
            # `lanes` must cover its maximum concurrently-unfired
            # sessions.
            last_anchor = jax.lax.cummax(jnp.where(is_anchor, idx, -1))
            seg_ok = valid & (last_anchor >= 0)
            seg_id = jnp.where(seg_ok, last_anchor, B).astype(jnp.int32)
            sstart = jnp.full(B + 1, jnp.iinfo(jnp.int64).max,
                              jnp.int64).at[seg_id].min(ts, mode="drop")[:B]
            send = jnp.full(B + 1, jnp.iinfo(jnp.int64).min,
                            jnp.int64).at[seg_id].max(ts, mode="drop")[:B]
            scount = jnp.zeros(B + 1, jnp.int64).at[seg_id].add(
                1, mode="drop")[:B]
            svals = {}
            for kind, name, field in fold_sig:
                v = cols[field].astype(stored[name].dtype)
                buf = jnp.full(B + 1, AGG_INITS[kind](v.dtype), v.dtype)
                at = buf.at[seg_id]
                buf = (at.add(v, mode="drop") if kind == "sum"
                       else at.min(v, mode="drop") if kind == "min"
                       else at.max(v, mode="drop"))
                svals[name] = buf[:B]
            # segment metadata lives at the anchor's row index
            seg_here = is_anchor                    # this row IS a segment
            smerge = can_merge & seg_here
            # is this segment its key's LAST in the batch? (no further
            # anchor before the key's run ends)
            seg_is_last = seg_here & (_next_after(is_anchor, idx, B)
                                      >= _next_after(~same_key, idx, B))
            # classify: a segment bypasses the lanes ONLY when it is
            # SETTLED — every event that could still merge into it (ts <
            # end + gap and within gap of it) is already late (ts + gap
            # <= boundary), i.e. end + 2*gap <= boundary. Eagerly
            # finalizing merely gap-closed-IN-BATCH segments (the old
            # rule) split sessions for out-of-order but NON-late events:
            # the segment sat in the host pending buffer where no later
            # event could reach it (ADVICE r4 medium). Unsettled middle
            # segments now take lanes too.
            settled = send + jnp.int64(2 * gap) <= boundary
            seg_to_lane = seg_here & (smerge | seg_is_last | ~settled)
            seg_emit = seg_here & ~smerge & ~seg_is_last & settled
        with jax.named_scope("session.lanes"):
            # lane allocation, j-th free lane for a key's j-th new segment
            # (sorted batch => a key's segments are contiguous; their
            # ordinals index into the key's free-lane rotation, so
            # several unsettled segments of one key land on distinct
            # lanes in one batch)
            need_alloc = seg_to_lane & ~smerge
            need = need_alloc.astype(jnp.int32)
            cs = jnp.cumsum(need, dtype=jnp.int32)
            # allocations before the key's run began (cs - need never
            # falls, so the newest run start's value is the largest)
            base = jax.lax.cummax(jnp.where(first, cs - need, 0))
            ordn = jnp.where(need_alloc, cs - base - 1, 0)
            cl = cur_lane[gs]
            rot = (cl[:, None] + 1
                   + jnp.arange(L, dtype=jnp.int32)[None, :]) % L
            rot_free = ~jnp.take_along_axis(open_bl, rot, axis=1)
            free_rank = jnp.cumsum(rot_free.astype(jnp.int32), axis=1,
                                   dtype=jnp.int32)
            pick = rot_free & (free_rank == (ordn + 1)[:, None])
            alloc_lane = jnp.take_along_axis(
                rot, jnp.argmax(pick, axis=1)[:, None], axis=1)[:, 0]
            no_free = need_alloc & ~pick.any(axis=1)
            overflow = jnp.sum(no_free).astype(jnp.int64)
            dropped = dropped + overflow
            seg_to_lane = seg_to_lane & ~no_free
            lane_t = jnp.where(smerge, merge_lane,
                               alloc_lane).astype(jnp.int32)
        with jax.named_scope("session.fold"):
            # ---- fold surviving segment TOTALS into lanes --------------
            sel = seg_to_lane
            slot_t = gs.astype(jnp.int32)
            out = planes_joined(stored)
            out["__start__"] = _fold_cells(
                "min", out["__start__"], lane_t, slot_t, sstart, sel)
            out["__end__"] = _fold_cells(
                "max", out["__end__"], lane_t, slot_t, send, sel)
            out["__open__"] = _fold_cells(
                "max", out["__open__"], lane_t, slot_t,
                jnp.ones(B, jnp.int8), sel)
            out["__count__"] = _fold_cells(
                "count", out["__count__"], lane_t, slot_t, scount, sel)
            for kind, name, _field in fold_sig:
                out[name] = _fold_cells(kind, out[name], lane_t, slot_t,
                                        svals[name], sel)
            # cur_lane := lane of the key's last segment (when it got a
            # lane)
            cur_lane = cur_lane.at[
                jnp.where(seg_is_last & seg_to_lane, kslot,
                          cur_lane.shape[0]).astype(jnp.int32)].set(
                lane_t, mode="drop")
            dirty = dirty.at[gs // dirty_block].set(True)
        n_emit = jnp.sum(seg_emit, dtype=jnp.int32)
        stats = stats + jnp.stack([
            jnp.sum(need_alloc & ~no_free).astype(jnp.int64), overflow,
            n_emit.astype(jnp.int64)])
        with jax.named_scope("session.emit"):
            # ---- compact settled segments for host-side pending emit ---
            # (only a batch that holds one pays for it: an in-order
            # stream settles nothing inside a batch)
            seg_cols = {"k": keys, "s": sstart, "e": send, "c": scount,
                        **svals}

            def compact(_):
                pos = jnp.cumsum(seg_emit.astype(jnp.int32),
                                 dtype=jnp.int32) - 1
                tgt = jnp.where(seg_emit, pos, B)
                return {n: jnp.zeros(B, v.dtype).at[tgt].set(
                    v, mode="drop") for n, v in seg_cols.items()}

            emit = jax.lax.cond(
                n_emit > 0, compact,
                lambda _: {n: jnp.zeros(B, v.dtype)
                           for n, v in seg_cols.items()}, None)
        return (table, planes_stored_like(stored, out), cur_lane, dropped,
                late, stats, dirty, n_emit, emit)

    return step


def _pack_mask(mask):
    """The boolean array ``mask`` packed into 32-bit words, for
    ``_select_packed``: ``(packed uint32[words], by_group int32[groups,
    32], group_at int32[groups], total int32)``: the words, their
    population counts by groups of 32 words, the rank of each group's
    first set bit among all, and how many bits are set. The mask is
    folded flat in halves five times (bit ``p`` of a word takes the upper
    half of fold ``p``, so no element moves and bit ``b`` of word ``w``
    is element ``reverse5(b) * words + w``); ``words`` is a multiple of
    32, the mask padded with zeros to it."""
    n = mask.size
    words = -(-n // (_WORD * _WORD)) * _WORD
    # as 32-bit values BEFORE it is flattened: re-tiling a 2^26-element
    # boolean plane took the v5e's compiler a minute, this a second
    packed = mask.astype(jnp.uint32).reshape(-1)
    if words * _WORD != n:
        packed = jnp.concatenate(
            [packed, jnp.zeros(words * _WORD - n, jnp.uint32)])
    for fold in range(5):
        half = packed.shape[0] // 2
        packed = packed[:half] | (packed[half:] << jnp.uint32(1 << fold))
    by_group = jax.lax.population_count(packed).astype(
        jnp.int32).reshape(words // _WORD, _WORD)
    group_n = jnp.sum(by_group, axis=1, dtype=jnp.int32)
    group_end = jnp.cumsum(group_n, dtype=jnp.int32)
    return packed, by_group, group_end - group_n, group_end[-1]


def _select_packed(packed, by_group, group_at, rows: int):
    """Flat indices (int32[rows]) of the first ``rows`` set bits of a
    mask ``_pack_mask`` packed, in no order a caller may rely on; entries
    past the mask's count are garbage. Costs by ``rows``, whatever the
    mask's size: the j-th output finds its group by a running count over
    the groups' starts, its word by the group's 32 counts and its bit by
    the word's 32 bits."""
    words = packed.shape[0]
    groups = words // _WORD
    bit = jnp.arange(_WORD, dtype=jnp.uint32)
    j = jnp.arange(rows, dtype=jnp.int32)
    # the group of output j is the last one that starts at or before j
    # (an empty group shares its start with the next one that is not)
    starts = jnp.zeros(rows, jnp.int32).at[
        jnp.where(group_at < rows, group_at, rows)].add(1, mode="drop")
    g = jnp.clip(jnp.cumsum(starts, dtype=jnp.int32) - 1, 0, groups - 1)
    r = j - jax.lax.cummax(jnp.where(starts > 0, j, 0))   # rank in group
    word_n = by_group[g]                            # [rows, 32]
    word_end = jnp.cumsum(word_n, axis=1, dtype=jnp.int32)
    wi = jnp.minimum(jnp.sum(word_end <= r[:, None], axis=1,
                             dtype=jnp.int32), _WORD - 1)
    lane32 = jnp.arange(_WORD, dtype=jnp.int32)[None, :]
    r = r - jnp.sum(jnp.where(lane32 < wi[:, None], word_n, 0), axis=1,
                    dtype=jnp.int32)                # rank in word
    word = jnp.sum(jnp.where(lane32 == wi[:, None],
                             packed.reshape(groups, _WORD)[g],
                             jnp.uint32(0)), axis=1, dtype=jnp.uint32)
    bits = ((word[:, None] >> bit[None, :]) & jnp.uint32(1)).astype(
        jnp.int32)
    b = jnp.minimum(jnp.sum(jnp.cumsum(bits, axis=1, dtype=jnp.int32)
                            <= r[:, None], axis=1, dtype=jnp.int32),
                    _WORD - 1)
    half_of = (((b & 1) << 4) | ((b & 2) << 2) | (b & 4)
               | ((b & 8) >> 2) | ((b & 16) >> 4))   # reverse5(b)
    return half_of * words + g * _WORD + wi


def _select_set_bits(mask, rows: int):
    """``(index int32[rows], total int32)``: flat indices of at most
    ``rows`` set elements of ``mask`` (the first ``min(total, rows)``
    entries are valid, each element once) and how many it holds. A pass
    over the mask, then by ``rows``."""
    packed, by_group, group_at, total = _pack_mask(mask)
    return _select_packed(packed, by_group, group_at, rows), total


#: chunks a fire round takes its sessions off the lanes in. Each chunk's
#: sixteen gathers and scatters cost the v5e about half a millisecond
#: apiece before their first row (sixteen chunks made a full round 227 ms
#: where one made it 108: my chip runs, PR 43), and a round that stops
#: only at its end makes a run's time turn on how full each fire's last
#: round happens to be (0.8% between runs of one tree)
_ROUND_CHUNKS = 4
#: a round's columns beside the key and the aggregates: of which plane
_ROUND_COLUMNS = (("s", "__start__"), ("e", "__end__"), ("c", "__count__"))


@instrumented_program_cache("device_session.fire", maxsize=64)
def _sess_fire(fold_sig: tuple, gap: int, rows: int, dirty_block: int):
    """One round of a fire: take at most ``rows`` open sessions with
    end + gap <= boundary off the lanes, ``rows / _ROUND_CHUNKS`` at a
    time and no further than there are. Returns the new planes and dirty
    mask, the round's rows (``k``, ``s``, ``e``, ``c`` and the aggregates'
    planes, ``rows`` long) and an int64 vector: sessions that were ripe
    before the round, sessions it took, then the device counters that
    ride along (dropped, late, the step's ``_STEP_STATS``). Ripe sessions
    beyond ``rows`` stay on their lanes, closed to every event, for the
    next round — the host loops."""
    reset_to = {"__start__": "min", "__end__": "max", "__open__": "max",
                "__count__": "count",
                **{name: kind for kind, name, _f in fold_sig}}

    @partial(jax.jit, donate_argnums=(1, 5))
    def fire(table, planes, dropped, late, stats, dirty, boundary):
        L, cap = planes["__open__"].shape
        with jax.named_scope("session.fire.scan"):
            end = planes_joined({"e": planes["__end__"]})["e"]
            ripe = (planes["__open__"] > 0) & (end + gap <= boundary)
        with jax.named_scope("session.fire.compact"):
            flat, total = _select_set_bits(ripe, rows)
            took = jnp.minimum(total, rows)
        with jax.named_scope("session.fire.reset"):
            # the fired sessions' cells leave their lanes: read, then set
            # back to the identities register_array_state starts with.
            # A gather or a scatter costs by the rows it is GIVEN, so the
            # round walks its ``took`` sessions a chunk at a time and
            # stops behind the last (a fire's last round, a quiet
            # stream's only one, does not pay for ``rows``); the planes
            # are flat for as long as it walks, so that the compiler
            # re-tiles each around the loop and not around every chunk
            chunk = max(1, rows // _ROUND_CHUNKS)
            padded = -(-rows // chunk) * chunk
            flat = jnp.pad(flat, (0, padded - rows))
            lanes_flat = {name: plane_map(lambda a: a.reshape(-1), plane)
                          for name, plane in planes.items()}
            taken = {"k": jnp.zeros(padded, table.dtype),
                     **{col: jnp.zeros(padded, planes[name].dtype)
                        for col, name in _ROUND_COLUMNS},
                     **{name: jnp.zeros(padded, planes[name].dtype)
                        for _kind, name, _f in fold_sig}}
            at = jnp.arange(chunk, dtype=jnp.int32)

            def walk(i, carry):
                lanes_flat, dirty, taken = carry
                live = i * chunk + at < took
                cell = jnp.where(live, jax.lax.dynamic_slice(
                    flat, (i * chunk,), (chunk,)), 0)
                slot = cell % cap
                got = {"k": table[slot],
                       **{col: plane_take(lanes_flat[name],
                                          lambda a: a[cell])
                          for col, name in _ROUND_COLUMNS},
                       **{name: plane_take(lanes_flat[name],
                                           lambda a: a[cell])
                          for _kind, name, _f in fold_sig}}
                taken = {n: jax.lax.dynamic_update_slice(
                    taken[n], got[n], (i * chunk,)) for n in taken}
                # a row past the round's sessions aims outside the plane
                # and is dropped
                gone = jnp.where(live, cell, L * cap)
                reset = {}
                for name, plane in lanes_flat.items():
                    kind = reset_to[name]
                    if isinstance(plane, Halves):
                        reset[name] = plane.map(
                            lambda a, w: a.at[gone].set(w, mode="drop"),
                            identity_words(kind, plane.dtype))
                    else:
                        reset[name] = plane.at[gone].set(
                            AGG_INITS[kind](plane.dtype), mode="drop")
                dirty = dirty.at[jnp.where(
                    live, slot // dirty_block, dirty.shape[0])].set(
                    True, mode="drop")
                return reset, dirty, taken

            lanes_flat, dirty, taken = jax.lax.fori_loop(
                0, (took + chunk - 1) // chunk, walk,
                (lanes_flat, dirty, taken))
            new = {name: plane_map(lambda a: a.reshape(L, cap), plane)
                   for name, plane in lanes_flat.items()}
            out = {n: v[:rows] for n, v in taken.items()}
        counts = jnp.concatenate([
            jnp.stack([total.astype(jnp.int64), took.astype(jnp.int64),
                       dropped, late]), stats])
        return new, dirty, out, counts

    return fire


class DeviceSessionWindowOperator(OneInputOperator):
    def __init__(self, gap_ms: int, key_column: str,
                 aggs: Sequence[AggSpec],
                 capacity: int = 1 << 16,
                 lanes: int = 4,
                 emit_window_bounds: bool = True,
                 async_fire: bool = False,
                 fire_interval_ms: Optional[int] = None,
                 fire_rows: Optional[int] = None,
                 name: str = "DeviceSessionWindowAgg"):
        super().__init__(name)
        self._gap = int(gap_ms)
        self._lanes = int(lanes)
        self._key_column = key_column
        self._aggs = list(aggs)
        self._capacity = capacity
        self._emit_bounds = emit_window_bounds
        self._async = bool(async_fire)
        # event time between two fires: a fire scans every lane, so it is
        # not run for every watermark that advances
        self._fire_interval = max(1, int(
            fire_interval_ms if fire_interval_ms is not None
            else self._gap // 5))
        self._fire_rows = int(fire_rows or min(
            _FIRE_ROWS, pow2_ceil(self._lanes * int(capacity))))
        self._backend: Optional[TpuKeyedStateBackend] = None
        self._registered = False
        self._late_dropped = 0
        self._late_cached = 0
        # watermark + 1 as the steps see it (what closes a session) and
        # as the last COMPLETED fire saw it (what has left the lanes)
        self._boundary = _NEG
        self._fired_boundary = _NEG
        # the fire under way: its boundary, its window/Fire stage, its
        # rounds so far, and the round whose copy has not been taken in
        self._fire_target: Optional[int] = None
        self._fire_stage = None
        self._fire_round = 0
        self._fire_t0 = 0.0
        self._round_inflight: Optional[tuple] = None
        # the last step's settled segments, not read back yet
        self._emit_inflight: Optional[tuple] = None
        self._stats_seen = np.zeros(len(_STEP_STATS), np.int64)
        self.fire_latencies_ms: list[float] = []
        self.stage_s = {"sort": 0.0, "upload": 0.0, "ingest": 0.0,
                        "fire": 0.0, "drain": 0.0}
        self._batch_seq = 0  # ordinal of the batch being ingested (spans)
        # gap-closed sessions awaiting their watermark, as columnar numpy
        # chunks {"k","s","e","c", aggs...} (filled by the step's eager
        # in-batch finalization; emitted once the watermark passes)
        self._pending: list[dict] = []

    # -- lifecycle ---------------------------------------------------------
    def setup(self, ctx: OperatorContext, output: Output) -> None:
        super().setup(ctx, output)
        self._backend = TpuKeyedStateBackend(
            ctx.key_group_range, ctx.max_parallelism,
            capacity=self._capacity)
        L = self._lanes
        self._backend.register_array_state("__start__", "min", jnp.int64,
                                           ring=L)
        self._backend.register_array_state("__end__", "max", jnp.int64,
                                           ring=L)
        self._backend.register_array_state("__open__", "max", jnp.int8,
                                           ring=L)
        self._backend.register_array_state("__count__", "count", jnp.int64,
                                           ring=L)
        self._backend.register_array_state("__cur_lane__", "sum", jnp.int32)
        self._late_dev = jnp.zeros((), jnp.int64)
        self._stats_dev = jnp.zeros(len(_STEP_STATS), jnp.int64)

    def _register_aggs(self, schema: Schema) -> None:
        for a in self._aggs:
            if a.field is not None and a.field in schema:
                col_dtype = np.dtype(schema.field(a.field).dtype)
                a.dtype = (jnp.float32 if a.kind == "avg"
                           else jnp.dtype(col_dtype))
            if a.kind == "avg":
                self._backend.register_array_state(
                    f"{a.out_name}.sum", "sum", a.dtype, ring=self._lanes)
            elif a.kind != "count":
                self._backend.register_array_state(
                    a.out_name, a.kind, a.dtype, ring=self._lanes)
        self._registered = True

    def _fold_sig(self) -> tuple:
        sig = []
        for a in self._aggs:
            if a.kind == "count":
                continue
            name = f"{a.out_name}.sum" if a.kind == "avg" else a.out_name
            sig.append(("sum" if a.kind == "avg" else a.kind, name,
                        a.field))
        return tuple(sig)

    def _plane_names(self) -> list[str]:
        names = ["__start__", "__end__", "__open__", "__count__"]
        names += [n for _k, n, _f in self._fold_sig()]
        return names

    def _planes(self) -> dict:
        return {n_: self._backend.get_array(n_)
                for n_ in self._plane_names()}

    # -- data path ---------------------------------------------------------
    def process_batch(self, batch: RecordBatch) -> None:
        if self._async:
            self._poll_fire(turn="batch")
        if batch.n == 0:
            return
        if not self._registered:
            key_dtype = batch.schema.field(self._key_column).dtype
            if key_dtype is object or not np.issubdtype(
                    np.dtype(key_dtype), np.integer):
                raise TypeError(
                    "device session windows need an integer key column; "
                    f"{self._key_column!r} is {key_dtype}")
            self._register_aggs(batch.schema)
        self._batch_seq += 1
        self._ingest(batch)

    def _ingest(self, batch: RecordBatch) -> None:
        from ..watchdog import stall_bounded

        n = batch.n
        P = pow2_ceil(n)
        sig = self._fold_sig()
        with TRACER.stage("window", "HostSort", seq=self._batch_seq,
                          total=(self.stage_s, "sort"), rows=n):
            keys = np.asarray(batch.column(self._key_column)).astype(
                np.int64)
            # the sentinel's stand-in, BEFORE the sort: the step tells a
            # key's run by its neighbours
            keys = np.where(keys == np.int64(EMPTY_KEY),
                            np.int64(EMPTY_KEY) - 1, keys)
            ts = np.asarray(batch.timestamps, np.int64)
            if n < 2 or bool((ts[1:] >= ts[:-1]).all()):
                # in order: a stable sort by key keeps each key's rows
                # in timestamp order
                order = np.argsort(keys, kind="stable")
            else:
                order = np.lexsort((ts, keys))

            def pad(a, fill=0):
                a = a[order]
                if P == n:
                    return a
                return np.concatenate([a, np.full(P - n, fill, a.dtype)])

            host = ({f: pad(np.asarray(batch.column(f)))
                     for _k, _n, f in sig}, pad(keys), pad(ts, _NEG))

        # deadline-bounded sites (docs/ROBUSTNESS.md): the upload and the
        # materialization are idempotent (stall-retried in place); the
        # step dispatch visits its fault site INSIDE the supervised call,
        # so an injected hang abandoned by the watchdog never reaches the
        # donating program (exactly-once under stall-retry)
        with TRACER.stage("window", "Upload", seq=self._batch_seq,
                          total=(self.stage_s, "upload"), rows=n):
            cols, dkeys, dts = stall_bounded(
                "transfer.h2d",
                lambda: jax.tree_util.tree_map(jnp.asarray, host),
                scope="device_session")
            DEVICE_STATS.note_h2d(
                pytree_nbytes(cols) + dkeys.nbytes + dts.nbytes, n)
        # the step before this one has run by now, or this waits for it:
        # at most one step is ever in flight
        self._take_settled()

        def dispatch():
            step = _sess_step(sig, self._lanes, self._gap,
                              self._backend.dirty_block_size)
            return step(
                self._backend.table, self._planes(),
                self._backend.get_array("__cur_lane__"),
                self._backend.dropped_device, self._late_dev,
                self._stats_dev, self._backend.dirty_mask,
                dkeys, dts, cols,
                np.int64(n), np.int64(self._boundary))

        with TRACER.stage("window", "IngestDispatch", seq=self._batch_seq,
                          total=(self.stage_s, "ingest"), rows=n):
            (table, out, cur_lane, dropped, late, stats, dirty,
             n_emit, emit) = stall_bounded(
                "device.execute", dispatch, scope="device_session")
        self._backend.table = table
        for n_, arr in out.items():
            self._backend.set_array(n_, arr)
        self._backend.set_array("__cur_lane__", cur_lane)
        self._backend._dropped = dropped
        self._late_dev = late
        self._stats_dev = stats
        self._backend.set_dirty_mask(dirty)
        self._emit_inflight = (n_emit, emit, P)

    def _take_settled(self) -> None:
        """Read the last step's settled segments into the host pending
        buffer: its count first (this waits for that step), the rows only
        where there are any."""
        item, self._emit_inflight = self._emit_inflight, None
        if item is None:
            return
        from ..watchdog import stall_bounded

        n_emit, emit, P = item
        # lint: sync-ok settled-count gate of the step BEFORE the one being dispatched; bounds the d2h slice
        g = int(jax.device_get(n_emit))
        if not g:
            return
        span = min(pow2_ceil(g), P)
        host = stall_bounded(
            "transfer.d2h",
            # lint: sync-ok session settled-segment drain, one d2h per batch that settled any
            lambda: jax.device_get({n_: v[:span] for n_, v in emit.items()}),
            scope="device_session")
        DEVICE_STATS.note_d2h(pytree_nbytes(host), g)
        self._pending.append({n_: np.asarray(v)[:g]
                              for n_, v in host.items()})

    def process_watermark(self, watermark: Watermark) -> None:
        self.current_watermark = watermark.timestamp
        final = watermark.timestamp >= MAX_TIMESTAMP
        boundary = watermark.timestamp + 1
        with TRACER.stage("window", "Watermark", seq=watermark.timestamp):
            if boundary > self._boundary:
                self._boundary = boundary
                self._take_settled()
                self._flush_pending(boundary)
            self._poll_fire(turn="watermark")
            self._maybe_fire(force=final)
            if final:
                self._finish_fires()
            elif not self._async:
                self._poll_fire(turn="blocking", block=True)

    def advance_processing_time(self, now_ms: int) -> None:
        """The mailbox's processing-time turn: a round whose copy has
        landed leaves now, not when the next batch arrives."""
        super().advance_processing_time(now_ms)
        if self._async:
            self._poll_fire(turn="timer")

    def _flush_pending(self, boundary: int) -> None:
        """Emit eagerly-finalized (gap-closed in batch) sessions whose
        window end passed the watermark; keep the rest."""
        if not self._pending:
            return
        merged: dict = {}
        for key in self._pending[0]:
            merged[key] = np.concatenate([c[key] for c in self._pending])
        ripe = merged["e"] + self._gap <= boundary
        if ripe.any():
            self._emit({k: v[ripe] for k, v in merged.items()},
                       int(ripe.sum()))
        rest = ~ripe
        if rest.any():
            self._pending = [{k: v[rest] for k, v in merged.items()}]
        else:
            self._pending = []

    # -- fires -------------------------------------------------------------
    def _maybe_fire(self, force: bool = False) -> None:
        """Start a fire at the current boundary when none is under way and
        the boundary has moved on by the cadence (or at all, ``force``)."""
        if not self._registered or self._fire_target is not None:
            return
        ahead = int(self._boundary) - int(self._fired_boundary)
        if ahead <= 0 or (ahead < self._fire_interval and not force):
            return
        self._fire_target = int(self._boundary)
        self._fire_round = 0
        self._fire_t0 = time.perf_counter()
        self._fire_stage = TRACER.open_stage(
            "window", "Fire", seq=self._fire_target,
            boundary_ms=self._fire_target)
        self._dispatch_round()

    def _fire_call(self, boundary: int) -> tuple:
        """(the fire program of the current planes, its arguments)."""
        return (_sess_fire(self._fold_sig(), self._gap, self._fire_rows,
                           self._backend.dirty_block_size),
                (self._backend.table, self._planes(),
                 self._backend.dropped_device, self._late_dev,
                 self._stats_dev, self._backend.dirty_mask,
                 np.int64(boundary)))

    def _dispatch_round(self) -> None:
        from ..watchdog import stall_bounded

        self._fire_round += 1
        fire, args = self._fire_call(self._fire_target)
        with TRACER.stage("window", "FireDispatch",
                          parent=self._fire_stage.context,
                          seq=self._fire_target, round=self._fire_round,
                          total=(self.stage_s, "fire")):
            # each round is a deadline-bounded device.execute visit (hang
            # trips abandoned by the watchdog never reach the program; a
            # stalled dispatch retries once, then fails the task into
            # restart-from-checkpoint)
            new, dirty, out, counts = stall_bounded(
                "device.execute",
                lambda: fire(*args), scope="device_session")
            for n_, arr in new.items():
                self._backend.set_array(n_, arr)
            self._backend.set_dirty_mask(dirty)
            for leaf in jax.tree_util.tree_leaves((out, counts)):
                leaf.copy_to_host_async()
        self._round_inflight = (out, counts)

    def _poll_fire(self, turn: str, block: bool = False,
                   advance: bool = True) -> None:
        """Take in the round in flight once its copy has landed (``block``
        waits for it), hand its rows on, and go on with the fire: the
        next round where ripe sessions are left (unless ``advance`` is
        off: a checkpoint takes the state between two rounds), else the
        fire's watermark."""
        while self._fire_target is not None:
            if self._round_inflight is None:
                if not advance:
                    return
                self._dispatch_round()     # a fire a checkpoint paused
            out, counts = self._round_inflight
            if not block and not all(
                    leaf.is_ready() for leaf in
                    jax.tree_util.tree_leaves((out, counts))):
                self._fire_stage.count("unready_polls")
                DEVICE_STATS.note_fire_unready_poll()
                return
            self._round_inflight = None
            left = self._materialize(out, counts,
                                     "blocking" if block else turn)
            if left:
                continue
            # the fire is complete: everything that ends at or before its
            # boundary has been handed on, so its watermark may follow
            boundary, self._fire_target = self._fire_target, None
            self._fired_boundary = boundary
            ms = (time.perf_counter() - self._fire_t0) * 1e3
            if len(self.fire_latencies_ms) < 65536:
                self.fire_latencies_ms.append(ms)
            self._fire_stage.close(rounds=self._fire_round)
            self._fire_stage = None
            self.output.emit_watermark(Watermark(boundary - 1))

    def _finish_fires(self) -> None:
        """Run fires, blocking, until the lanes hold nothing the
        watermark has closed: the end of input."""
        self._poll_fire(turn="blocking", block=True)
        self._maybe_fire(force=True)
        self._poll_fire(turn="blocking", block=True)

    def _materialize(self, out: dict, counts, turn: str) -> int:
        """One landed round on the host: its counters, its rows. Returns
        how many ripe sessions the round left on the lanes."""
        from ..watchdog import stall_bounded

        with TRACER.stage("window", "Drain",
                          parent=self._fire_stage.context,
                          seq=self._fire_target, round=self._fire_round,
                          turn=turn, total=(self.stage_s, "drain")) as drain:
            host_counts, host = stall_bounded(
                "transfer.d2h",
                # lint: sync-ok session fire drain: one d2h per fire round, copied since its dispatch
                lambda: jax.device_get((counts, out)),
                scope="device_session")
            total, took, dropped, late = (int(x) for x in host_counts[:4])
            stats = np.asarray(host_counts[4:], np.int64)
            drain.set("fired", took)
            drain.set("left", total - took)
        DEVICE_STATS.note_session_steps(*(stats - self._stats_seen))
        self._stats_seen = stats
        DEVICE_STATS.note_session_round(took, last=total == took)
        DEVICE_STATS.note_fire_drained(timer=turn == "timer")
        # deferred health: table overflow / lane collisions raise here
        self._late_cached = late
        if dropped:
            raise RuntimeError(
                f"device session state overflow: {dropped} records hit "
                f"hash-table or session-lane limits; raise capacity/"
                f"lanes (lanes={self._lanes})")
        if took:
            DEVICE_STATS.note_d2h(pytree_nbytes(host), took)
            with TRACER.stage("window", "Emit",
                              parent=self._fire_stage.context,
                              seq=self._fire_target, rows=took,
                              total=(self.stage_s, "drain")):
                self._emit(host, took)
        return total - took

    def _emit(self, host: dict, n: int) -> None:
        """Hand on ``n`` sessions given as columns ``k``, ``s``, ``e``,
        ``c`` and the aggregates' planes (a round's buffers, or the
        pending buffer's ripe rows)."""
        keys = np.asarray(host["k"])[:n]
        start = np.asarray(host["s"])[:n]
        end = np.asarray(host["e"])[:n] + self._gap
        count = np.asarray(host["c"])[:n]
        cols: dict[str, np.ndarray] = {self._key_column: keys}
        fields: list = [(self._key_column, np.int64)]
        if self._emit_bounds:
            cols["window_start"] = start
            cols["window_end"] = end
            fields += [("window_start", np.int64),
                       ("window_end", np.int64)]
        for a in self._aggs:
            if a.kind == "count":
                v = count
            elif a.kind == "avg":
                s = np.asarray(host[f"{a.out_name}.sum"])[:n]
                v = s / np.maximum(count, 1).astype(s.dtype)
            else:
                v = np.asarray(host[a.out_name])[:n]
            cols[a.out_name] = v
            fields.append((a.out_name, v.dtype.type))
        DEVICE_STATS.note_session_rows(n)
        self.output.emit(RecordBatch(Schema(fields), cols, end - 1))

    def _refresh_late(self) -> None:
        """Refresh the host cache of the device late-drop counter at
        checkpoint boundaries (a drained fire round refreshes it too) —
        a /metrics scrape reads the cache alone and never forces a device
        sync mid-pipeline (the PR 8 late_dropped lesson, applied to
        sessions too)."""
        # lint: sync-ok boundary-amortized refresh; scrapes read the cache
        self._late_cached = int(jax.device_get(self._late_dev))

    @property
    def late_dropped(self) -> int:
        return self._late_dropped + self._late_cached

    def finish(self) -> None:
        """End of input: whatever the watermark has closed leaves now."""
        self._take_settled()
        self._flush_pending(int(self._boundary))
        self._finish_fires()

    # -- checkpointing -----------------------------------------------------
    def snapshot_state(self, checkpoint_id: int) -> dict:
        # the state between two rounds of a fire is a state like any
        # other: the round in flight lands (its rows belong before the
        # barrier), the rounds still to come run after it, here or in
        # the job that restores this snapshot
        self._take_settled()
        self._poll_fire(turn="blocking", block=True, advance=False)
        self._refresh_late()
        return {"keyed": {
            "backend": self._backend.snapshot(checkpoint_id),
            "pending": [dict(c) for c in self._pending],
            "meta": {"fired_boundary": int(self._fired_boundary),
                     "boundary": int(self._boundary),
                     "watermark": self.current_watermark}}}

    def initialize_state(self, keyed_snapshots: list,
                         operator_snapshot) -> None:
        if keyed_snapshots:
            self._backend.restore(
                [s["backend"] for s in keyed_snapshots])
            # pending sessions re-filter by key group on rescale
            from ...core.keygroups import hash_batch, \
                key_groups_for_hash_batch
            for s in keyed_snapshots:
                for chunk in s.get("pending", []):
                    kg = key_groups_for_hash_batch(
                        hash_batch(chunk["k"]),
                        self._backend.max_parallelism)
                    mine = np.isin(
                        kg, np.arange(
                            self._backend.key_group_range.start,
                            self._backend.key_group_range.end + 1))
                    if mine.any():
                        self._pending.append(
                            {k: np.asarray(v)[mine]
                             for k, v in chunk.items()})
            metas = [s["meta"] for s in keyed_snapshots]
            # the least any of them had fired up to: what one of them
            # still holds ripe fires again here (a snapshot from before
            # the two boundaries were told apart fired at every
            # watermark: its one boundary is both)
            self._fired_boundary = min(
                int(m["fired_boundary"]) for m in metas)
            self._boundary = max(
                int(m.get("boundary", m["fired_boundary"])) for m in metas)
            self.current_watermark = max(m["watermark"] for m in metas)
            self._registered = False  # re-register agg planes lazily
