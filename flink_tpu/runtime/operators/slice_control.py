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

__all__ = ["SliceControlPlane", "AsyncFireQueue", "CoalescingIngest"]

_MAX_FIRE_SAMPLES = 65536


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
    fire. Subclasses implement ``_materialize(item)``; an item is a tuple
    whose second element is the fire's device-output pytree."""

    _async: bool

    def _init_async_fires(self) -> None:
        self._pending: deque = deque()

    def _enqueue_fire(self, item: tuple) -> None:
        import jax

        for leaf in jax.tree_util.tree_leaves(item[1]):
            leaf.copy_to_host_async()
        if self._async:
            self._pending.append(item)
        else:
            self._materialize(item)

    def _drain(self, block: bool = False) -> None:
        import jax

        while self._pending:
            head = self._pending[0]
            if isinstance(head, Watermark):
                self.output.emit_watermark(head)
                self._pending.popleft()
                continue
            if not block and not all(
                    leaf.is_ready()
                    for leaf in jax.tree_util.tree_leaves(head[1])):
                return
            self._pending.popleft()
            self._materialize(head)

    def _emit_watermark_out(self, watermark: Watermark) -> None:
        if self._async and self._pending:
            self._pending.append(watermark)
        else:
            self.output.emit_watermark(watermark)

    def _note_latency(self, t0: float) -> None:
        if self._async and len(self.fire_latencies_ms) < _MAX_FIRE_SAMPLES:
            self.fire_latencies_ms.append((time.perf_counter() - t0) * 1e3)

    def _materialize(self, item: tuple) -> None:
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
        self._note_open_ingest(min_pane)
        self._fold(batch, keys, panes)

    def _note_open_ingest(self, min_pane: int) -> None:
        """Hook: the incremental fire engine invalidates its running
        window accumulators when a batch writes into an already-sealed
        pane (late-but-not-dropped records, or a min-pane decrease)."""
        pass

    # -- firing ------------------------------------------------------------
    def process_watermark(self, watermark: Watermark) -> None:
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
                t0 = time.perf_counter()
                self._fire(p_end)
                if (self._record_fire_latency
                        and len(self.fire_latencies_ms) < _MAX_FIRE_SAMPLES):
                    self.fire_latencies_ms.append(
                        (time.perf_counter() - t0) * 1e3)
        # the boundary tracks the watermark even when no data has arrived
        # yet or no window fired, so records behind the watermark are
        # dropped as late exactly like the host operator
        if (self._fired_boundary is None
                or wm_pane_end + 1 > self._fired_boundary):
            self._fired_boundary = wm_pane_end + 1
        self._emit_watermark_out(watermark)

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

    @property
    def late_dropped(self) -> int:
        return self._late_dropped
