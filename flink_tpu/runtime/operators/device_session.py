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

Per micro-batch, ONE fused program:
  * events arrive sorted by (key, ts) (host numpy lexsort);
  * hash-table lookup-or-insert -> key slot;
  * session segmentation: an event merges into a lane it overlaps within
    ``gap`` (all L lanes are checked), successive in-batch events split
    where ts gaps exceed ``gap``; new segments allocate the next lane;
  * one scatter-fold per aggregate into (lane, slot), start folds MIN,
    end folds MAX — so a merging event EXTENDS its session in place;
  * the key's current-lane pointer updates to its last event's lane.

A session window [start, last_ts + gap) fires when the watermark passes
its end, as one compiled scan over the [L, capacity] planes that
compacts (key, start, end, aggregates) and resets fired lanes.

Segments only bypass the lanes into the host pending buffer once they
are SETTLED — no event that is still non-late could merge into them
(end + 2*gap behind the fired boundary); anything fresher keeps a lane,
where out-of-order events find it through the all-lanes merge probe.

Semantics vs the host operator (exact for in-order input and for
arbitrary NON-late disorder, except the bridge case below):
  * allowed_lateness = 0: an event whose merged window would end at or
    behind the fired boundary is dropped and counted, like the device
    pane operator;
  * an event bridging TWO open sessions of one key joins one of them;
    the host MergingWindowSet would fuse both into a single window. This
    needs per-key disorder > gap to arise; such streams belong on the
    host operator (the planner default for merging windows).
  * more than L concurrently-open sessions per key (watermark lag >
    ~L * gap) raises at the next watermark instead of corrupting state.
"""

from __future__ import annotations

import time
from functools import partial
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ...core.elements import Watermark
from ...core.records import RecordBatch, Schema
from ...metrics.tracing import TRACER
from ...metrics.device import DEVICE_STATS, instrumented_program_cache, \
    pytree_nbytes
from ...ops.hash_table import EMPTY_KEY, lookup_or_insert, \
    sanitize_keys_device
from ...ops.segment_ops import pow2_ceil
from ...state.tpu_backend import TpuKeyedStateBackend
from .base import OneInputOperator, OperatorContext, Output
from .device_window import AggSpec

__all__ = ["DeviceSessionWindowOperator"]

_NEG = np.int64(-(1 << 62))
_POS = np.int64(1 << 62)


@instrumented_program_cache("device_session.step", maxsize=64)
def _sess_step(fold_sig: tuple, lanes: int, gap: int, dirty_block: int):
    """One fused program per batch. ``fold_sig``: (kind, name, field)."""
    from ...ops.segment_ops import planes_joined, planes_stored_like, \
        scatter_fold

    L = lanes
    donate = (0, 1, 2, 3, 4, 5)

    @partial(jax.jit, donate_argnums=donate)
    def step(table, planes, cur_lane, dropped, late, dirty, keys, ts, cols,
             n_valid, fired_boundary):
        # the backend stores a 64-bit lanes plane as its two 32-bit words
        # (ops/segment_ops.Halves); this program reads and rewrites whole
        # planes, so it joins them here and splits them at its exit
        stored, planes = planes, planes_joined(planes)
        B = keys.shape[0]
        cap = cur_lane.shape[0]
        in_batch = jnp.arange(B) < n_valid
        keys = sanitize_keys_device(keys)
        table, kslot, ok = lookup_or_insert(table, keys, in_batch)
        valid = ok & in_batch
        dropped = dropped + jnp.sum(in_batch & ~ok).astype(jnp.int64)
        gs = jnp.maximum(kslot, 0)
        # first occurrence per key slot in this (sorted) batch
        widx0 = jnp.where(valid, kslot, cap).astype(jnp.int32)
        firstpos = jnp.full(cap + 1, B, jnp.int32).at[widx0].min(
            jnp.arange(B, dtype=jnp.int32))
        first = valid & (jnp.arange(B, dtype=jnp.int32) == firstpos[widx0])
        # merge check against ALL open lanes of the key (L gathers)
        mergeable = []
        for lane in range(L):
            s = planes["__start__"][lane, gs]
            e = planes["__end__"][lane, gs]
            o = planes["__open__"][lane, gs] > 0
            # strict overlap, like TimeWindow.intersects: [ts, ts+gap)
            # meets [s, e+gap) iff ts < e+gap and s < ts+gap
            mergeable.append(o & (ts > s - gap) & (ts < e + gap))
        mg = jnp.stack(mergeable, axis=1)              # [B, L]
        can_merge = mg.any(axis=1)
        merge_lane = jnp.argmax(mg, axis=1).astype(jnp.int32)
        # late (allowed_lateness=0, like the host operator): the event's
        # own window [ts, ts+gap) closed already and no open session can
        # absorb it. Segment followers of a LIVE anchor are never late
        # (sorted order: their ts >= the anchor's, whose window is open).
        is_late = valid & ~can_merge & (ts + gap <= fired_boundary)
        late = late + jnp.sum(is_late).astype(jnp.int64)
        valid = valid & ~is_late
        # anchors: key-first, an in-batch ts jump > gap (sorted by
        # (key, ts), prev row is the predecessor), or the first survivor
        # after a late-dropped predecessor (it must re-decide its lane)
        prev_ts = jnp.concatenate([ts[:1], ts[:-1]])
        prev_same = jnp.concatenate(
            [jnp.zeros(1, bool), (keys[1:] == keys[:-1])]) & ~first
        prev_late = jnp.concatenate([jnp.zeros(1, bool), is_late[:-1]])
        in_jump = prev_same & ((ts - prev_ts >= gap) | prev_late)
        is_anchor = valid & (first | in_jump)
        # ---- two-level fold: events -> per-SEGMENT accumulators --------
        # every anchor opens a batch-local segment; events fold into [B]
        # segment buffers first. Only SETTLED segments (no non-late event
        # can still merge into them; see the classification below) bypass
        # the lanes into the pending-emission buffers — every other
        # segment takes a lane, so a key may allocate SEVERAL lanes per
        # batch and `lanes` must cover its maximum concurrently-open
        # (unsettled) sessions.
        idx = jnp.arange(B, dtype=jnp.int32)
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
            v = cols[field].astype(planes[name].dtype)
            if kind == "sum":
                buf = jnp.zeros(B + 1, v.dtype).at[seg_id].add(
                    v, mode="drop")
            elif kind == "min":
                buf = jnp.full(B + 1, AGG_IDENT_MAX(v.dtype),
                               v.dtype).at[seg_id].min(v, mode="drop")
            else:
                buf = jnp.full(B + 1, AGG_IDENT_MIN(v.dtype),
                               v.dtype).at[seg_id].max(v, mode="drop")
            svals[name] = buf[:B]
        # segment metadata lives at the anchor's row index
        seg_here = is_anchor                        # this row IS a segment
        skslot = kslot                              # at anchor rows
        skey = keys
        smerge = can_merge & seg_here
        smlane = merge_lane
        # is this segment its key's LAST in the batch?
        lastseg = jnp.full(cap + 1, -1, jnp.int32).at[
            jnp.where(seg_here, kslot, cap).astype(jnp.int32)].max(idx)
        seg_is_last = jnp.asarray(seg_here & (idx == lastseg[widx0]))
        # classify: a segment bypasses the lanes ONLY when it is SETTLED —
        # every event that could still merge into it (ts < end + gap and
        # within gap of it) is already late (ts + gap <= fired_boundary),
        # i.e. end + 2*gap <= fired_boundary. Eagerly finalizing merely
        # gap-closed-IN-BATCH segments (the old rule) split sessions for
        # out-of-order but NON-late events: the segment sat in the host
        # pending buffer where no later event could reach it (ADVICE r4
        # medium). Unsettled middle segments now take lanes too.
        settled = send + jnp.int64(2 * gap) <= fired_boundary
        seg_to_lane = seg_here & (smerge | seg_is_last | ~settled)
        seg_emit = seg_here & ~smerge & ~seg_is_last & settled
        # lane allocation, j-th free lane for a key's j-th new segment
        # (sorted batch => a key's segments are contiguous; their ordinals
        # index into the key's free-lane rotation, so several unsettled
        # segments of one key land on distinct lanes in one batch)
        need_alloc = seg_to_lane & ~smerge
        cs = jnp.cumsum(need_alloc.astype(jnp.int32))
        base = jnp.zeros(cap + 1, jnp.int32).at[
            jnp.where(first, kslot, cap).astype(jnp.int32)].max(
            cs - need_alloc.astype(jnp.int32), mode="drop")
        ordn = jnp.where(need_alloc, cs - base[widx0] - 1, 0)
        cl = cur_lane[gs]
        open_bl = jnp.stack([planes["__open__"][ln, gs] > 0
                             for ln in range(L)], axis=1)     # [B, L]
        rot = (cl[:, None] + 1
               + jnp.arange(L, dtype=jnp.int32)[None, :]) % L
        rot_free = ~jnp.take_along_axis(open_bl, rot, axis=1)
        free_rank = jnp.cumsum(rot_free.astype(jnp.int32), axis=1)
        pick = rot_free & (free_rank == (ordn + 1)[:, None])
        alloc_lane = jnp.take_along_axis(
            rot, jnp.argmax(pick, axis=1)[:, None], axis=1)[:, 0]
        no_free = need_alloc & ~pick.any(axis=1)
        overflow = jnp.sum(no_free).astype(jnp.int64)
        dropped = dropped + overflow
        seg_to_lane = seg_to_lane & ~no_free
        lane_t = jnp.where(smerge, smlane, alloc_lane).astype(jnp.int32)
        # ---- fold surviving segment TOTALS into lanes ------------------
        flat = lane_t * cap + gs.astype(jnp.int32)
        sel = seg_to_lane
        out = dict(planes)
        out["__start__"] = scatter_fold(
            "min", planes["__start__"].reshape(-1), flat, sstart,
            sel).reshape(L, cap)
        out["__end__"] = scatter_fold(
            "max", planes["__end__"].reshape(-1), flat, send,
            sel).reshape(L, cap)
        out["__open__"] = planes["__open__"].reshape(-1).at[
            jnp.where(sel, flat, L * cap)].max(
            jnp.int8(1), mode="drop").reshape(L, cap)
        out["__count__"] = scatter_fold(
            "count", planes["__count__"].reshape(-1), flat, scount,
            sel).reshape(L, cap)
        for kind, name, _field in fold_sig:
            out[name] = scatter_fold(
                kind, planes[name].reshape(-1), flat, svals[name],
                sel).reshape(L, cap)
        # cur_lane := lane of the key's last segment (when it got a lane)
        cur_lane = cur_lane.at[
            jnp.where(seg_is_last & seg_to_lane, kslot, cap)
            .astype(jnp.int32)].set(lane_t, mode="drop")
        dirty = dirty.at[gs // dirty_block].set(True)
        # ---- compact gap-closed segments for host-side pending emit ----
        pos = jnp.cumsum(seg_emit.astype(jnp.int32)) - 1
        tgt = jnp.where(seg_emit, pos, B)
        n_emit = jnp.sum(seg_emit.astype(jnp.int64))
        ekey = jnp.zeros(B, jnp.int64).at[tgt].set(skey, mode="drop")
        estart = jnp.zeros(B, jnp.int64).at[tgt].set(sstart, mode="drop")
        eend = jnp.zeros(B, jnp.int64).at[tgt].set(send, mode="drop")
        ecount = jnp.zeros(B, jnp.int64).at[tgt].set(scount, mode="drop")
        evals = {name: jnp.zeros(B, svals[name].dtype).at[tgt].set(
            svals[name], mode="drop") for name in svals}
        return (table, planes_stored_like(stored, out), cur_lane, dropped,
                late, dirty, n_emit, ekey, estart, eend, ecount, evals)

    return step


def AGG_IDENT_MAX(dtype):
    return (jnp.inf if jnp.issubdtype(dtype, jnp.floating)
            else jnp.iinfo(dtype).max)


def AGG_IDENT_MIN(dtype):
    return (-jnp.inf if jnp.issubdtype(dtype, jnp.floating)
            else jnp.iinfo(dtype).min)


@instrumented_program_cache("device_session.fire", maxsize=64)
def _sess_fire(agg_sig: tuple, lanes: int, gap: int):
    """Fire scan: compact every open session with end + gap <= boundary
    into [capacity]-bounded buffers and reset its lane. Returns the new
    planes, the fired count, and an overflow count (fired sessions beyond
    the buffer stay open for the next scan — the host loops)."""

    from ...ops.segment_ops import planes_joined, planes_stored_like

    @jax.jit
    def fire(table, planes, boundary):
        stored, planes = planes, planes_joined(planes)   # as in the step
        L, cap = planes["__open__"].shape
        end = planes["__end__"]
        fire_mask = ((planes["__open__"] > 0)
                     & (end + gap <= boundary)).reshape(-1)
        flat_slot = jnp.tile(jnp.arange(cap), L)
        keys_flat = jnp.tile(table, L)
        pos = jnp.cumsum(fire_mask.astype(jnp.int32)) - 1
        n_fired = jnp.sum(fire_mask.astype(jnp.int64))
        can = fire_mask & (pos < cap)
        overflow = n_fired - jnp.sum(can.astype(jnp.int64))
        tgt = jnp.where(can, pos, cap)
        out_keys = jnp.zeros(cap, jnp.int64).at[tgt].set(
            keys_flat, mode="drop")
        out_start = jnp.zeros(cap, jnp.int64).at[tgt].set(
            planes["__start__"].reshape(-1), mode="drop")
        out_end = jnp.zeros(cap, jnp.int64).at[tgt].set(
            planes["__end__"].reshape(-1), mode="drop")
        outs = {}
        count_flat = planes["__count__"].reshape(-1)
        out_count = jnp.zeros(cap, jnp.int64).at[tgt].set(
            count_flat, mode="drop")
        for kind, out_name, plane in agg_sig:
            if kind == "count":
                outs[out_name] = out_count
            elif kind == "avg":
                s = jnp.zeros(cap, planes[plane].dtype).at[tgt].set(
                    planes[plane].reshape(-1), mode="drop")
                outs[out_name] = s / jnp.maximum(out_count, 1).astype(
                    s.dtype)
            else:
                outs[out_name] = jnp.zeros(
                    cap, planes[plane].dtype).at[tgt].set(
                    planes[plane].reshape(-1), mode="drop")
        # reset fired lanes (only those that fit the buffer this pass)
        new = dict(planes)
        rs = can.reshape(L, cap)
        new["__open__"] = jnp.where(rs, jnp.int8(0), planes["__open__"])
        # reset to the SAME identities register_array_state starts with
        new["__start__"] = jnp.where(rs, jnp.iinfo(jnp.int64).max,
                                     planes["__start__"])
        new["__end__"] = jnp.where(rs, jnp.iinfo(jnp.int64).min,
                                   planes["__end__"])
        new["__count__"] = jnp.where(rs, 0, planes["__count__"])
        for kind, _o, plane in agg_sig:
            if kind == "count":
                continue
            arr = planes[plane]
            if kind == "min":
                ident = (jnp.inf if jnp.issubdtype(arr.dtype, jnp.floating)
                         else jnp.iinfo(arr.dtype).max)
            elif kind == "max":
                ident = (-jnp.inf
                         if jnp.issubdtype(arr.dtype, jnp.floating)
                         else jnp.iinfo(arr.dtype).min)
            else:
                ident = 0
            new[plane] = jnp.where(rs, jnp.asarray(ident, arr.dtype), arr)
        fired = jnp.minimum(n_fired, jnp.int64(cap))
        return (planes_stored_like(stored, new), out_keys, out_start,
                out_end, outs, fired, overflow)

    return fire


class DeviceSessionWindowOperator(OneInputOperator):
    def __init__(self, gap_ms: int, key_column: str,
                 aggs: Sequence[AggSpec],
                 capacity: int = 1 << 16,
                 lanes: int = 4,
                 emit_window_bounds: bool = True,
                 name: str = "DeviceSessionWindowAgg"):
        super().__init__(name)
        self._gap = int(gap_ms)
        self._lanes = int(lanes)
        self._key_column = key_column
        self._aggs = list(aggs)
        self._capacity = capacity
        self._emit_bounds = emit_window_bounds
        self._backend: Optional[TpuKeyedStateBackend] = None
        self._registered = False
        self._late_dropped = 0
        self._late_cached = 0
        self._fired_boundary = _NEG
        self.fire_latencies_ms: list[float] = []
        self.stage_s = {"ingest": 0.0, "fire": 0.0, "drain": 0.0}
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

    def _agg_sig(self) -> tuple:
        sig = []
        for a in self._aggs:
            plane = (f"{a.out_name}.sum" if a.kind == "avg"
                     else "__count__" if a.kind == "count" else a.out_name)
            sig.append((a.kind, a.out_name, plane))
        return tuple(sig)

    def _plane_names(self) -> list[str]:
        names = ["__start__", "__end__", "__open__", "__count__"]
        names += [n for _k, n, _f in self._fold_sig()]
        return names

    # -- data path ---------------------------------------------------------
    def process_batch(self, batch: RecordBatch) -> None:
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
        with TRACER.stage("window", "IngestDispatch", seq=self._batch_seq,
                          total=(self.stage_s, "ingest"), rows=batch.n):
            self._ingest(batch)

    def _ingest(self, batch: RecordBatch) -> None:
        keys = np.asarray(batch.column(self._key_column)).astype(np.int64)
        ts = np.asarray(batch.timestamps, np.int64)
        order = np.lexsort((ts, keys))
        n = batch.n
        P = pow2_ceil(n)

        def pad(a, fill=0):
            a = a[order]
            if P == n:
                return a
            return np.concatenate([a, np.full(P - n, fill, a.dtype)])

        sig = self._fold_sig()
        from ..watchdog import stall_bounded

        def upload():
            return ({f: jnp.asarray(pad(np.asarray(batch.column(f))))
                     for _k, _n, f in sig},
                    jnp.asarray(pad(keys)), jnp.asarray(pad(ts, _NEG)))

        # deadline-bounded sites (docs/ROBUSTNESS.md): the upload and the
        # materialization are idempotent (stall-retried in place); the
        # step dispatch visits its fault site INSIDE the supervised call,
        # so an injected hang abandoned by the watchdog never reaches the
        # donating program (exactly-once under stall-retry)
        cols, dkeys, dts = stall_bounded("transfer.h2d", upload,
                                         scope="device_session")
        DEVICE_STATS.note_h2d(
            pytree_nbytes(cols) + dkeys.nbytes + dts.nbytes, n)

        def dispatch():
            step = _sess_step(sig, self._lanes, self._gap,
                              self._backend.dirty_block_size)
            planes = {n_: self._backend.get_array(n_)
                      for n_ in self._plane_names()}
            return step(
                self._backend.table, planes,
                self._backend.get_array("__cur_lane__"),
                self._backend.dropped_device, self._late_dev,
                self._backend.dirty_mask,
                dkeys, dts, cols,
                np.int64(n), np.int64(self._fired_boundary))

        (table, out, cur_lane, dropped, late, dirty,
         n_emit, ekey, estart, eend, ecount, evals) = stall_bounded(
            "device.execute", dispatch, scope="device_session")
        self._backend.table = table
        for n_, arr in out.items():
            self._backend.set_array(n_, arr)
        self._backend.set_array("__cur_lane__", cur_lane)
        self._backend._dropped = dropped
        # lint: sync-ok emitted-count gate per batch; bounds the d2h slice
        g = int(jax.device_get(n_emit))
        if g:
            span = min(pow2_ceil(g), P)
            host = stall_bounded(
                "transfer.d2h",
                # lint: sync-ok session emit drain, one d2h per emitting batch
                lambda: jax.device_get(
                    {"k": ekey[:span], "s": estart[:span],
                     "e": eend[:span], "c": ecount[:span],
                     "v": {n_: v[:span] for n_, v in evals.items()}}),
                scope="device_session")
            DEVICE_STATS.note_d2h(pytree_nbytes(host), g)
            chunk = {kk: np.asarray(vv)[:g] for kk, vv in host.items()
                     if kk != "v"}
            for n_, v in host["v"].items():
                chunk[n_] = np.asarray(v)[:g]
            self._pending.append(chunk)
        self._late_dev = late
        self._backend.set_dirty_mask(dirty)

    def process_watermark(self, watermark: Watermark) -> None:
        self.current_watermark = watermark.timestamp
        boundary = watermark.timestamp + 1
        if boundary > self._fired_boundary:
            self._fired_boundary = boundary
            self._fire(boundary)
            self._flush_pending(boundary)
        self.output.emit_watermark(watermark)

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
            sel = {k: v[ripe] for k, v in merged.items()}
            outs = {}
            for a in self._aggs:
                if a.kind == "count":
                    outs[a.out_name] = sel["c"]
                elif a.kind == "avg":
                    s = sel[f"{a.out_name}.sum"]
                    outs[a.out_name] = s / np.maximum(
                        sel["c"], 1).astype(s.dtype)
                else:
                    outs[a.out_name] = sel[a.out_name]
            self._emit({"k": sel["k"], "s": sel["s"], "e": sel["e"],
                        "o": outs}, int(ripe.sum()))
        rest = ~ripe
        if rest.any():
            self._pending = [{k: v[rest] for k, v in merged.items()}]
        else:
            self._pending = []

    def _fire(self, boundary: int) -> None:
        if not self._registered:
            return
        t0 = time.perf_counter()
        from ..watchdog import stall_bounded
        fire = _sess_fire(self._agg_sig(), self._lanes, self._gap)
        while True:
            planes = {n_: self._backend.get_array(n_)
                      for n_ in self._plane_names()}
            # each fire dispatch is a deadline-bounded device.execute
            # visit (hang trips abandoned by the watchdog never reach
            # the program; a stalled dispatch retries once, then fails
            # the task into restart-from-checkpoint)
            new, keys, start, end, outs, fired, overflow = stall_bounded(
                "device.execute",
                lambda: fire(self._backend.table, planes,
                             np.int64(boundary)),
                scope="device_session")
            # lint: sync-ok fire loop control (fired/overflow counts)
            fired_h, overflow_h = map(int, jax.device_get(
                (fired, overflow)))
            if fired_h == 0:
                break
            for n_, arr in new.items():
                self._backend.set_array(n_, arr)
            span = min(pow2_ceil(fired_h), self._backend.capacity)
            host = stall_bounded(
                "transfer.d2h",
                # lint: sync-ok session fire drain, one d2h per fire round
                lambda: jax.device_get(
                    {"k": keys[:span], "s": start[:span], "e": end[:span],
                     "o": {n_: v[:span] for n_, v in outs.items()}}),
                scope="device_session")
            DEVICE_STATS.note_d2h(pytree_nbytes(host), fired_h)
            self._emit(host, fired_h)
            if overflow_h == 0:
                break
        # deferred health: table overflow / lane collisions raise here
        self._refresh_late()
        # lint: sync-ok deferred overflow health check, once per fire
        dropped = int(jax.device_get(self._backend.dropped_device))
        if dropped:
            raise RuntimeError(
                f"device session state overflow: {dropped} records hit "
                f"hash-table or session-lane limits; raise capacity/"
                f"lanes (lanes={self._lanes})")
        ms = (time.perf_counter() - t0) * 1e3
        if len(self.fire_latencies_ms) < 65536:
            self.fire_latencies_ms.append(ms)
        self.stage_s["fire"] += ms / 1e3

    def _emit(self, host: dict, n: int) -> None:
        keys = np.asarray(host["k"])[:n]
        start = np.asarray(host["s"])[:n]
        end = np.asarray(host["e"])[:n] + self._gap
        cols: dict[str, np.ndarray] = {self._key_column: keys}
        fields: list = [(self._key_column, np.int64)]
        if self._emit_bounds:
            cols["window_start"] = start
            cols["window_end"] = end
            fields += [("window_start", np.int64),
                       ("window_end", np.int64)]
        # iterate AggSpec order, not dict order: device_get round-trips
        # JAX pytree dicts in SORTED-key order
        for a in self._aggs:
            v = np.asarray(host["o"][a.out_name])[:n]
            cols[a.out_name] = v
            fields.append((a.out_name, v.dtype.type))
        schema = Schema(fields)
        self.output.emit(RecordBatch(schema, cols, end - 1))

    def _refresh_late(self) -> None:
        """Refresh the host cache of the device late-drop counter at
        fire/checkpoint boundaries — a /metrics scrape reads the cache
        alone and never forces a device sync mid-pipeline (the PR 8
        late_dropped lesson, applied to sessions too)."""
        # lint: sync-ok boundary-amortized refresh; scrapes read the cache
        self._late_cached = int(jax.device_get(self._late_dev))

    @property
    def late_dropped(self) -> int:
        return self._late_dropped + self._late_cached

    def finish(self) -> None:
        pass

    # -- checkpointing -----------------------------------------------------
    def snapshot_state(self, checkpoint_id: int) -> dict:
        self._refresh_late()
        return {"keyed": {
            "backend": self._backend.snapshot(checkpoint_id),
            "pending": [dict(c) for c in self._pending],
            "meta": {"fired_boundary": int(self._fired_boundary),
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
            self._fired_boundary = max(
                int(s["meta"]["fired_boundary"]) for s in keyed_snapshots)
            self.current_watermark = max(
                s["meta"]["watermark"] for s in keyed_snapshots)
            self._registered = False  # re-register agg planes lazily
