"""Shared host control plane for slice-window device operators.

Both the single-chip DeviceWindowAggOperator and the mesh
MeshWindowAggOperator run the same scalar protocol around their compiled
steps: pane arithmetic, late-record filtering, the watermark-driven fire
loop, and the fired/seen-pane metadata that rides along with keyed
snapshots. This mixin holds that protocol once (the analog of the logic in
the reference's WindowOperator.processElement:278 / onEventTime:437 that
is independent of the state backend), so a fix to the boundary math lands
in every device operator.

Subclasses provide:
  _fold(batch, keys, panes)   — accumulate one filtered batch
  _fire(p_end)                — merge + emit the window ending at pane
                                boundary p_end, then retire its oldest row
  _pre_fire_flush()           — drain any staged input (mesh buffering);
                                default no-op
"""

from __future__ import annotations

import time
from collections import deque
from typing import Optional

import numpy as np

from ...core.elements import Watermark
from ...core.records import RecordBatch
from ...metrics.tracing import TRACER, Stage

__all__ = ["SliceControlPlane", "AsyncFireQueue", "CoalescingIngest",
           "IN_ORDER_RING_ROWS"]

_MAX_FIRE_SAMPLES = 65536
#: ring rows a batch in event-time order can hold a row for (two where
#: it straddles a pane's edge); a batch that holds more is out of order
#: and goes up sorted by ring row (``SliceControlPlane._note_fold``)
IN_ORDER_RING_ROWS = 2


class CoalescingIngest:
    """Coalesced ingest dispatch: consecutive same-schema micro-batches
    accumulate host-side up to a configurable record target, so ONE
    compiled step dispatch amortizes its fixed cost (program launch,
    pane bookkeeping) over several upstream batches. The buffer
    flushes when the record target is reached, when an incompatible batch
    arrives, when a configured age deadline has passed (checked at the
    next admit — no timer thread), and unconditionally before fires,
    snapshots and finish (watermark/barrier semantics are unchanged: a
    record admitted before a watermark is folded before that watermark's
    fires). Subclasses implement ``_process_batch_now(batch)``."""

    def _init_coalescer(self) -> None:
        self._coalesce_target = 0     # records; <= 1 disables
        self._coalesce_timeout_s = 0.0
        self._co_buf: list = []
        self._co_records = 0
        self._co_deadline: Optional[float] = None

    @staticmethod
    def _co_signature(batch) -> tuple:
        return (type(batch).__name__,
                tuple((f.name, np.dtype(f.dtype).str if f.dtype is not object
                       else "object") for f in batch.schema.fields))

    def _coalesce_admit(self, batch) -> None:
        if self._co_buf and \
                self._co_signature(self._co_buf[0]) != \
                self._co_signature(batch):
            self._coalesce_flush()
        self._co_buf.append(batch)
        self._co_records += batch.n
        now = time.monotonic()
        if self._co_deadline is None and self._coalesce_timeout_s > 0:
            self._co_deadline = now + self._coalesce_timeout_s
        if self._co_records >= self._coalesce_target or (
                self._co_deadline is not None and now >= self._co_deadline):
            self._coalesce_flush()

    def _coalesce_flush(self) -> None:
        buf, self._co_buf = self._co_buf, []
        self._co_records = 0
        self._co_deadline = None
        if not buf:
            return
        if len(buf) == 1:
            self._process_batch_now(buf[0])
            return
        from ...metrics.device import DEVICE_STATS
        DEVICE_STATS.note_batches_coalesced(len(buf))
        self._process_batch_now(self._co_merge(buf))

    @staticmethod
    def _co_merge(buf: list):
        from ...core.device_records import DeviceRecordBatch

        first = buf[0]
        if isinstance(first, DeviceRecordBatch):
            import jax.numpy as jnp

            cols = {f.name: jnp.concatenate(
                        [b.device_column(f.name) for b in buf])
                    for f in first.schema.fields}
            dts = (jnp.concatenate([b.dtimestamps for b in buf])
                   if first.dtimestamps is not None else None)
            return DeviceRecordBatch(
                first.schema, cols, dts,
                min(b.ts_min for b in buf), max(b.ts_max for b in buf),
                ts_column=first.ts_column)
        cols = {f.name: np.concatenate([b.column(f.name) for b in buf])
                for f in first.schema.fields}
        ts = np.concatenate([b.timestamps for b in buf])
        return RecordBatch(first.schema, cols, ts)

    def _process_batch_now(self, batch) -> None:
        raise NotImplementedError


class AsyncFireQueue:
    """Asynchronous fire emission, shared by the single-chip and mesh
    device operators: a fire's compiled outputs start copying device->host
    at dispatch (copy_to_host_async); emission is queued and drained once
    the copy lands, and watermarks are held behind their fires so they
    never overtake results downstream. The hot loop never blocks on a
    fire. The queue is looked at on every batch AND on the mailbox's
    processing-time turn (``advance_processing_time``, at most once a
    millisecond, on empty input polls too), so a window's rows leave on
    the first turn after their copy has landed whether or not a further
    batch arrives. Subclasses implement ``_materialize(item, turn)``; an
    item is a tuple (pane boundary, device-output pytree, ..., the
    window's open ``window/Fire`` stage): the stage is the root of one
    span tree per fired window, which ``_materialize`` parents its
    ``window/Drain`` and ``window/Emit`` on, from a later mailbox turn,
    and closes once the window's rows are emitted; ``turn`` says which
    kind of turn took the fire off the queue (``timer``, ``batch``, or
    ``blocking``: a barrier, finish, growth, rescale or synchronous
    fire that waits for it)."""

    _async: bool

    def _init_async_fires(self) -> None:
        self._pending: deque = deque()
        # the window/Fire stage of the fire being dispatched right now,
        # until _enqueue_fire takes it into the pending item
        self._cur_fire: Optional[Stage] = None

    def _enqueue_fire(self, item: tuple) -> None:
        import jax

        for leaf in jax.tree_util.tree_leaves(item[1]):
            leaf.copy_to_host_async()
        # synchronous fires queue too: _fire_window drains them as soon
        # as the dispatch stage is over, so Drain and Emit follow
        # FireDispatch inside the window's Fire in both modes
        fire, self._cur_fire = self._cur_fire, None
        self._pending.append((*item, fire))

    def _open_fire_stage(self, p_end: int) -> Stage:
        end_ms = p_end * self._pane + self._offset
        return TRACER.open_stage("window", "Fire", seq=end_ms,
                                 window_end_ms=end_ms)

    def _drain_stage(self, fire: Stage, turn: str) -> Stage:
        """window/Drain: the device_get of a fire's outputs + the host
        selection / sort, a child of the window's Fire."""
        return TRACER.stage("window", "Drain", parent=fire.context,
                            seq=fire.attrs["seq"], turn=turn,
                            count_plane=self._count_form(),
                            total=(self.stage_s, "drain"))

    def _count_form(self) -> str:
        """The form of the operator's hidden plane ``__count__``
        (``metrics/device.COUNT_PLANE_FORMS``): an attribute of every
        window/Drain, so a trace says whether the job's fires read a
        presence plane or a count."""
        raise NotImplementedError

    @staticmethod
    def _note_fire_select(drain: Stage, select, value_bits: int,
                          rank_dtype) -> None:
        """A ranked fire's ``[passes, fell_back]``, as it came back in the
        drain's one copy: counted, and an attribute of its window/Drain,
        beside the promise the fire's select was compiled under
        (``value_bits``; whether that left the guard against a negative
        rank of ``rank_dtype`` in the program is counted too)."""
        from ...metrics.device import DEVICE_STATS
        from ...ops.topk import select_guarded

        passes, fell_back = (int(x) for x in select)
        DEVICE_STATS.note_fire_select(
            passes, bool(fell_back), select_guarded(rank_dtype, value_bits))
        drain.set("select_passes", passes)
        drain.set("value_bits", int(value_bits))

    def _emit_stage(self, fire: Stage, rows: int) -> Stage:
        """window/Emit: building the window's rows + output.emit."""
        return TRACER.stage("window", "Emit", parent=fire.context,
                            seq=fire.attrs["seq"], rows=rows,
                            total=(self.stage_s, "drain"))

    def _close_fire(self, fire: Stage, rows: int, d2h_bytes: int) -> None:
        fire.close(rows=rows, d2h_bytes=d2h_bytes,
                   unready_polls=fire.attrs.get("unready_polls", 0))

    def advance_processing_time(self, now_ms: int) -> None:
        """The mailbox's processing-time turn: a fire whose copy has
        landed leaves now, not when the next batch arrives."""
        super().advance_processing_time(now_ms)
        if self._async and self._pending:
            self._drain(turn="timer")

    def _drain(self, block: bool = False, turn: str = "batch") -> None:
        import jax

        from ...metrics.device import DEVICE_STATS

        if block:
            turn = "blocking"
        while self._pending:
            head = self._pending[0]
            if isinstance(head, Watermark):
                self.output.emit_watermark(head)
                self._pending.popleft()
                continue
            if not block and not all(
                    leaf.is_ready()
                    for leaf in jax.tree_util.tree_leaves(head[1])):
                head[-1].count("unready_polls")
                DEVICE_STATS.note_fire_unready_poll()
                return
            self._pending.popleft()
            self._materialize(head, turn)
            DEVICE_STATS.note_fire_drained(timer=turn == "timer")

    def _emit_watermark_out(self, watermark: Watermark) -> None:
        if self._async and self._pending:
            self._pending.append(watermark)
        else:
            self.output.emit_watermark(watermark)

    def _note_latency(self, t0: float) -> None:
        if self._async and len(self.fire_latencies_ms) < _MAX_FIRE_SAMPLES:
            self.fire_latencies_ms.append((time.perf_counter() - t0) * 1e3)

    def _materialize(self, item: tuple, turn: str) -> None:
        raise NotImplementedError


class SliceControlPlane:
    # set by subclass __init__
    _pane: int
    _offset: int
    _window_panes: int
    _ring: int

    def _init_control_plane(self) -> None:
        # windows ending at pane boundary p_end for all p_end <
        # _fired_boundary have fired; panes < _fired_boundary - W are
        # retired (ring rows reusable, records late)
        self._fired_boundary: Optional[int] = None
        self._min_seen_pane: Optional[int] = None
        self._max_seen_pane: Optional[int] = None
        self._late_dropped = 0
        # wall-clock of each window fire (merge + emit), for the p99
        # window-fire latency metric (BASELINE.md); bounded reservoir.
        # Async-firing operators set _record_fire_latency False and record
        # dispatch->drain themselves.
        self.fire_latencies_ms: list[float] = []
        self._record_fire_latency = True
        # host clock at the start of this operator's last batch and the
        # ordinal of its watermarks (window/Watermark since_batch_ms, seq)
        self._last_batch_ns = 0
        self._watermarks = 0

    # -- metadata ----------------------------------------------------------
    def _control_meta(self) -> dict:
        return {"fired_boundary": self._fired_boundary,
                "min_seen_pane": self._min_seen_pane,
                "max_seen_pane": self._max_seen_pane,
                "watermark": self.current_watermark}

    def _restore_control_meta(self, metas: list[dict]) -> None:
        fires = [m["fired_boundary"] for m in metas
                 if m.get("fired_boundary") is not None]
        seens = [m["max_seen_pane"] for m in metas
                 if m.get("max_seen_pane") is not None]
        mins = [m["min_seen_pane"] for m in metas
                if m.get("min_seen_pane") is not None]
        self._fired_boundary = min(fires) if fires else None
        self._max_seen_pane = max(seens) if seens else None
        self._min_seen_pane = min(mins) if mins else None
        self.current_watermark = max(m["watermark"] for m in metas)

    # -- data path ---------------------------------------------------------
    def _ingest(self, batch: RecordBatch, keys: np.ndarray) -> None:
        """Late-filter + pane-span bookkeeping, then hand the surviving
        records to the subclass's _fold."""
        panes = ((batch.timestamps - self._offset) // self._pane).astype(
            np.int64)
        if self._fired_boundary is not None:
            # late = every window containing the pane has fired (its ring
            # row may already be retired/reused)
            first_open = self._fired_boundary - self._window_panes
            late = panes < first_open
            n_late = int(late.sum())
            if n_late:
                self._late_dropped += n_late
                keep = ~late
                keys, panes = keys[keep], panes[keep]
                batch = batch.filter(keep)
                if batch.n == 0:
                    return
        max_pane = int(panes.max())
        min_pane = int(panes.min())
        if (self._max_seen_pane is not None
                and min_pane < self._max_seen_pane):
            # rows that reach back behind the newest pane seen BEFORE
            # this batch (none where the stream is in order, so an
            # in-order batch pays the comparison above and no pass)
            from ...metrics.device import DEVICE_STATS

            DEVICE_STATS.note_fold_back(
                int(np.count_nonzero(panes < self._max_seen_pane)))
        self._max_seen_pane = (max_pane if self._max_seen_pane is None
                               else max(self._max_seen_pane, max_pane))
        self._min_seen_pane = (min_pane if self._min_seen_pane is None
                               else min(self._min_seen_pane, min_pane))
        # ring overflow check: two open panes must never share a ring row
        low = (self._fired_boundary - self._window_panes
               if self._fired_boundary is not None else self._min_seen_pane)
        if max_pane - low >= self._ring:
            raise RuntimeError(
                f"pane ring overflow: open span [{low},{max_pane}] exceeds "
                f"ring {self._ring}; increase ring_size or reduce "
                "watermark lag")
        self._fold(batch, keys, panes)

    # -- firing ------------------------------------------------------------
    def process_watermark(self, watermark: Watermark) -> None:
        self._watermarks += 1
        # how long after this operator's last batch began the watermark
        # reached it (-1 before any batch)
        since_batch_ms = (
            round((time.monotonic_ns() - self._last_batch_ns) / 1e6, 3)
            if self._last_batch_ns else -1.0)
        with TRACER.stage("window", "Watermark", seq=self._watermarks,
                          watermark_ms=watermark.timestamp,
                          since_batch_ms=since_batch_ms) as turn:
            turn.set("fires", self._process_watermark(watermark))

    def _process_watermark(self, watermark: Watermark) -> int:
        """Returns how many windows the watermark fired."""
        fires = 0
        self.current_watermark = watermark.timestamp
        self._pre_fire_flush()
        # a window ending at pane boundary p_end fires when
        # wm >= p_end*pane + offset - 1
        wm_pane_end = (watermark.timestamp - self._offset + 1) // self._pane
        if self._max_seen_pane is not None:
            # windows ending at or below min_seen contain no data; never
            # reach below that (their ring rows may alias future panes)
            start = self._min_seen_pane + 1
            if self._fired_boundary is not None:
                start = max(start, self._fired_boundary)
            last = min(wm_pane_end, self._max_seen_pane + self._window_panes)
            for p_end in range(start, last + 1):
                fires += self._fire_window(p_end)
        # the boundary tracks the watermark even when no data has arrived
        # yet or no window fired, so records behind the watermark are
        # dropped as late exactly like the host operator
        if (self._fired_boundary is None
                or wm_pane_end + 1 > self._fired_boundary):
            self._fired_boundary = wm_pane_end + 1
        self._emit_watermark_out(watermark)
        return fires

    def _window_holds_data(self, p_end: int) -> bool:
        """Whether the window ending at pane boundary ``p_end`` has a
        pane to read: never one below min_seen (those hold no data and
        their ring rows may alias live FUTURE panes)."""
        return max(p_end - self._window_panes, self._min_seen_pane) < p_end

    def _fire_window(self, p_end: int) -> bool:
        """One window's fire as the start of its span tree: the root
        window/Fire stays open in the pending item until the window's
        rows are emitted (a later mailbox turn when fires are async);
        window/FireDispatch, its first child, is the host's part now. A
        window that holds no data opens no stage."""
        if not self._window_holds_data(p_end):
            return False
        fire = self._cur_fire = self._open_fire_stage(p_end)
        with TRACER.stage("window", "FireDispatch", parent=fire.context,
                          seq=fire.attrs["seq"],
                          total=(self.stage_s, "fire")):
            self._fire(p_end)
        fired = self._cur_fire is None       # _enqueue_fire took it
        if not fired:
            # _fire enqueued nothing after all: the tree ends here
            self._cur_fire = None
            self._close_fire(fire, 0, 0)
        elif not self._async:
            self._drain(block=True)
        if (self._record_fire_latency
                and len(self.fire_latencies_ms) < _MAX_FIRE_SAMPLES):
            self.fire_latencies_ms.append(fire.duration_ms)
        return fired

    def _emit_watermark_out(self, watermark: Watermark) -> None:
        """Hook: async-firing operators hold the watermark behind its
        fires' pending emissions so it never overtakes them downstream."""
        self.output.emit_watermark(watermark)

    def _pre_fire_flush(self) -> None:
        pass

    def _fold(self, batch: RecordBatch, keys: np.ndarray,
              panes: np.ndarray) -> None:
        raise NotImplementedError

    def _fire(self, p_end: int) -> None:
        raise NotImplementedError

    def _note_fold(self, ring_idx: np.ndarray) -> int:
        """Ring rows a host-born batch (a mesh block's valid rows) holds
        a row for, which are the rows of each plane its fold slices,
        scatters into and writes back (ops/segment_ops.ring_fold):
        counted (DEVICE_STATS ``fold_ring_rows_total`` /
        ``fold_batches_total``, from the batch's own ring indices, no
        device sync) and returned for the dispatch span's
        ``ring_rows``. Past ``IN_ORDER_RING_ROWS`` the input is out of
        order and the caller sends the batch up sorted by ring row
        (``fold_sorted_batches_total``)."""
        from ...metrics.device import DEVICE_STATS

        rows = int(np.count_nonzero(
            np.bincount(ring_idx, minlength=self._ring)))
        DEVICE_STATS.note_fold(rows, rows > IN_ORDER_RING_ROWS)
        return rows

    @property
    def late_dropped(self) -> int:
        return self._late_dropped
