"""In-process channels, input gates, barrier alignment, watermark valve.

Local-exchange analog of the reference's network stack + input processing:
bounded queues stand in for credit-based Netty channels (a full queue IS
backpressure, like credit exhaustion in RemoteInputChannel.java:68);
``InputGate`` merges channels like SingleInputGate; barrier alignment follows
SingleCheckpointBarrierHandler.java:64 (block a channel once its barrier
arrives until all channels' barriers arrive — blocking here is simply not
polling, the queue itself buffers); watermark min-combine with idleness
follows StatusWatermarkValve.java:40. Inter-host transport plugs in behind
the same Channel interface (cluster/transport.py).
"""

from __future__ import annotations

import queue
import threading
import time
from dataclasses import dataclass
from typing import Any, Iterable, Optional

from ..core.elements import (
    CheckpointBarrier, EndOfInput, LatencyMarker, Watermark, WatermarkStatus,
)
from ..core.records import MIN_TIMESTAMP, RecordBatch
from .faults import FAULTS

__all__ = ["Channel", "LocalChannel", "InputGate", "IterationGate",
           "GateEvent"]

DEFAULT_CAPACITY = 64  # queued elements per channel before backpressure


class Channel:
    """One logical edge subtask->subtask."""

    #: how long the element returned by the last ``poll`` sat in the
    #: channel (0 where an implementation does not stamp its elements)
    last_residence_ns = 0

    def put(self, element: Any, timeout: Optional[float] = None) -> bool:
        raise NotImplementedError

    def poll(self) -> Optional[Any]:
        raise NotImplementedError

    def size(self) -> int:
        raise NotImplementedError


class LocalChannel(Channel):
    def __init__(self, capacity: int = DEFAULT_CAPACITY):
        self._q: queue.Queue = queue.Queue(maxsize=capacity)

    def put(self, element: Any, timeout: Optional[float] = None) -> bool:
        if FAULTS.enabled and FAULTS.check("channel.backpressure"):
            # drop-style site: report "queue full" once — the writer's
            # bounded-queue spin treats it exactly like real credit
            # exhaustion and retries, so chaos runs exercise the
            # backpressure path deterministically without losing data
            return False
        try:
            # stamped once per element (a batch, a watermark): the reader
            # learns how long it queued (task/ProcessBatch.queued_ms)
            self._q.put((element, time.monotonic_ns()), timeout=timeout)
            return True
        except queue.Full:
            return False

    def poll(self) -> Optional[Any]:
        try:
            element, put_ns = self._q.get_nowait()
        except queue.Empty:
            return None
        self.last_residence_ns = time.monotonic_ns() - put_ns
        return element

    def size(self) -> int:
        return self._q.qsize()

    def drain(self) -> list:
        out = []
        while True:
            e = self.poll()
            if e is None:
                return out
            out.append(e)


class ReplayableChannel(Channel):
    """Blocking-partition channel for bounded (batch) execution: writes
    append to a persistent list (the SortMergeResultPartition analog —
    in-memory here), reads advance a per-reader cursor WITHOUT consuming,
    so a speculative attempt of the consumer can re-read from the start
    via ``clone_reader``. Unbounded by design: a blocking exchange
    materializes the producer's whole output before the consumer starts.
    """

    def __init__(self, items: Optional[list] = None,
                 lock: Optional[threading.Lock] = None):
        self._items: list = items if items is not None else []
        self._lock = lock or threading.Lock()
        self._cursor = 0
        self._sealed = False

    def put(self, element: Any, timeout: Optional[float] = None) -> bool:
        with self._lock:
            if self._sealed:
                # a speculation loser may wake from a blocking call after
                # its race was settled; its late writes must not corrupt
                # the adopted partition
                return True
            self._items.append(element)
        return True

    def poll(self) -> Optional[Any]:
        with self._lock:
            if self._cursor >= len(self._items):
                return None
            e = self._items[self._cursor]
            self._cursor += 1
            return e

    def size(self) -> int:
        with self._lock:
            return len(self._items) - self._cursor

    def drain(self) -> list:
        with self._lock:
            out = self._items[self._cursor:]
            self._cursor = len(self._items)
            return out

    # -- batch-mode extensions ------------------------------------------
    def clone_reader(self) -> "ReplayableChannel":
        """A fresh cursor over the SAME partition (speculative re-read)."""
        return ReplayableChannel(self._items, self._lock)

    def adopt_items(self, other: "ReplayableChannel") -> None:
        """Replace this partition's contents with another attempt's output
        and SEAL it against the losing attempt's late writes (the
        speculation winner's partition becomes THE partition)."""
        with self._lock:
            self._sealed = True
            self._items[:] = list(other._items)
            self._cursor = 0


@dataclass
class GateEvent:
    """What the gate hands the task: either data/watermark to process, a fully
    aligned barrier (snapshot now), or end-of-input."""

    kind: str  # "batch" | "watermark" | "barrier" | "end" | "latency" | "idle"
    value: Any = None
    channel: int = -1
    queued_ns: int = 0  # residence of the element in its channel


class InputGate:
    """Merges N input channels with barrier alignment + watermark valve.

    Barrier modes (reference SingleCheckpointBarrierHandler.java:64 /
    CheckpointBarrierTracker / alternating aligned-unaligned):
    * aligned exactly-once (default): a channel that delivered its barrier
      is blocked until every channel's barrier arrived;
    * at-least-once (aligned=False): barriers counted, nothing blocks;
    * unaligned (unaligned=True): the FIRST barrier fires immediately and
      pre-barrier batches still queued on the other channels are captured
      into the checkpoint as in-flight data while processing continues;
    * alignment timeout (alignment_timeout_s > 0): an aligned checkpoint
      escalates to unaligned when alignment stalls longer than the timeout
      (reference BarrierAlignmentUtil timeout escalation).
    """

    def __init__(self, channels: list[Channel], aligned: bool = True,
                 unaligned: bool = False, alignment_timeout_s: float = 0.0):
        self.channels = channels
        self.aligned = aligned
        self.unaligned = unaligned
        self.alignment_timeout_s = alignment_timeout_s
        n = len(channels)
        self._blocked = [False] * n          # barrier-aligned channels
        self._ended = [False] * n
        self._wm = [MIN_TIMESTAMP] * n       # per-channel watermark
        self._active = [True] * n            # idleness per channel
        self._pending_barrier: Optional[CheckpointBarrier] = None
        self._barrier_seen: set[int] = set()
        self._combined_wm = MIN_TIMESTAMP
        self._rr = 0                         # fair round-robin pointer
        self.alignment_start: float = 0.0
        # channel residence of the last element polled (the per-gate
        # inputQueueResidenceMs gauge reads it)
        self.last_residence_ns = 0
        # unaligned capture state
        self._capturing: set[int] = set()    # channels still pre-barrier
        self._capture_barrier: Optional[CheckpointBarrier] = None
        self.captured: list = []             # in-flight elements

    # -- unaligned capture -------------------------------------------------
    @property
    def capture_active(self) -> bool:
        return self._capture_barrier is not None

    @property
    def capture_complete(self) -> bool:
        return self._capture_barrier is not None and not self._capturing

    def take_captured(self) -> list:
        out = self.captured
        self.captured = []
        self._capture_barrier = None
        self._capturing = set()
        return out

    def _start_capture(self, b: CheckpointBarrier) -> GateEvent:
        """Barrier overtakes: fire now, capture the other channels'
        pre-barrier data as it arrives."""
        self.captured = []  # an aborted older capture's data is discarded
        self._capture_barrier = b
        self._capturing = {i for i in range(len(self.channels))
                           if i not in self._barrier_seen
                           and not self._ended[i]}
        self._pending_barrier = None
        self._barrier_seen.clear()
        self._blocked = [False] * len(self.channels)
        return GateEvent("barrier", b)

    def begin_capture(self, b: CheckpointBarrier) -> None:
        """Externally start capture for a barrier that arrived on a SIBLING
        gate (two-input unaligned checkpoints): every live channel of this
        gate is pre-barrier until its own barrier shows up."""
        if self._capture_barrier is not None \
                and self._capture_barrier.checkpoint_id >= b.checkpoint_id:
            return
        self.captured = []
        self._capture_barrier = b
        self._capturing = {i for i in range(len(self.channels))
                           if not self._ended[i]}
        self._pending_barrier = None
        self._barrier_seen.clear()
        self._blocked = [False] * len(self.channels)

    def convert_to_unaligned(self) -> Optional[GateEvent]:
        """Escalate a stalled aligned checkpoint (alignment timeout)."""
        if self._pending_barrier is None or self.capture_active:
            return None
        return self._start_capture(self._pending_barrier)

    # -- watermark valve (reference StatusWatermarkValve) ------------------
    def _recompute_watermark(self) -> Optional[Watermark]:
        live = [self._wm[i] for i in range(len(self.channels))
                if self._active[i] and not self._ended[i]]
        if not live:
            # all idle/ended: watermark driven by ended channels' final marks
            live = [self._wm[i] for i in range(len(self.channels))]
        combined = min(live) if live else MIN_TIMESTAMP
        if combined > self._combined_wm:
            self._combined_wm = combined
            return Watermark(combined)
        return None

    def all_ended(self) -> bool:
        return all(self._ended)

    @property
    def aligning(self) -> bool:
        return self._pending_barrier is not None

    def unblock_all(self) -> None:
        self._blocked = [False] * len(self.channels)
        self._pending_barrier = None
        self._barrier_seen.clear()

    def poll(self) -> Optional[GateEvent]:
        """Poll one event, fair round-robin over non-blocked channels.
        Returns None when nothing is available right now."""
        if (self.alignment_timeout_s > 0 and not self.unaligned
                and self._pending_barrier is not None
                and not self.capture_active
                and time.time() - self.alignment_start
                > self.alignment_timeout_s):
            ev = self.convert_to_unaligned()
            if ev is not None:
                return ev
        n = len(self.channels)
        for off in range(n):
            i = (self._rr + off) % n
            if self._blocked[i] or self._ended[i]:
                continue
            ch = self.channels[i]
            e = ch.poll()
            if e is None:
                continue
            self._rr = (i + 1) % n
            ev = self._classify(i, e)
            self.last_residence_ns = ch.last_residence_ns
            if ev is not None:
                ev.queued_ns = ch.last_residence_ns
            return ev
        return None

    def queue_depth(self) -> int:
        """Elements queued over all channels right now."""
        return sum(ch.size() for ch in self.channels)

    def _classify(self, i: int, e: Any) -> Optional[GateEvent]:
        if isinstance(e, RecordBatch):
            if self._capture_barrier is not None and i in self._capturing:
                # pre-barrier in-flight data rides with the checkpoint AND
                # is processed normally (reference ChannelStateWriter)
                self.captured.append(e)
            return GateEvent("batch", e, i)
        if isinstance(e, Watermark):
            self._wm[i] = max(self._wm[i], e.timestamp)
            self._active[i] = True
            wm = self._recompute_watermark()
            return GateEvent("watermark", wm, i) if wm else None
        if isinstance(e, WatermarkStatus):
            self._active[i] = e.active
            wm = self._recompute_watermark()
            return GateEvent("watermark", wm, i) if wm else \
                GateEvent("idle", e, i)
        if isinstance(e, CheckpointBarrier):
            return self._on_barrier(i, e)
        if isinstance(e, LatencyMarker):
            return GateEvent("latency", e, i)
        if isinstance(e, EndOfInput):
            self._ended[i] = True
            self._capturing.discard(i)  # nothing more to capture from it
            # an ended channel no longer holds back alignment
            if self._pending_barrier is not None:
                return self._check_alignment_complete()
            wm = self._recompute_watermark()
            return GateEvent("watermark", wm, i) if wm else None
        raise TypeError(f"Unknown stream element {type(e)}")

    def _on_barrier(self, i: int, b: CheckpointBarrier) -> Optional[GateEvent]:
        if self._capture_barrier is not None:
            if b.checkpoint_id <= self._capture_barrier.checkpoint_id:
                # this channel caught up to the overtaking barrier
                self._capturing.discard(i)
                return None
            # a newer checkpoint while capturing (max_concurrent > 1):
            # finish the old capture forcibly and overtake again
            self._capturing.clear()
            self._barrier_seen = {i}
            return self._start_capture(b)
        if self.unaligned:
            self._barrier_seen.add(i)
            return self._start_capture(b)
        if not self.aligned:
            # at-least-once: CheckpointBarrierTracker — count, never block
            self._barrier_seen.add(i)
            if self._pending_barrier is None:
                self._pending_barrier = b
                self.alignment_start = time.time()
            return self._check_alignment_complete()
        if self._pending_barrier is None:
            self._pending_barrier = b
            self.alignment_start = time.time()
        elif b.checkpoint_id != self._pending_barrier.checkpoint_id:
            # new checkpoint overtakes: abort old alignment (reference
            # handles via abort; we adopt the newer barrier)
            self.unblock_all()
            self._pending_barrier = b
            self.alignment_start = time.time()
        self._blocked[i] = True
        self._barrier_seen.add(i)
        return self._check_alignment_complete()

    def _check_alignment_complete(self) -> Optional[GateEvent]:
        needed = {i for i in range(len(self.channels)) if not self._ended[i]}
        if self._pending_barrier is not None and needed <= self._barrier_seen:
            b = self._pending_barrier
            self.unblock_all()
            return GateEvent("barrier", b)
        return None


class IterationGate(InputGate):
    """Gate for an iteration head (reference StreamIterationHead): some
    channels are FEEDBACK edges from the loop body. Termination cannot wait
    for their EndOfInput — the body only ends after the head does — so the
    head ends once every regular channel ended AND the loop has been quiet
    (no event polled, no feedback data queued) for ``max_wait_s``. Feedback
    channels start inactive so the loop's (filtered-out) watermarks never
    hold back event time; only record batches flow on them."""

    def __init__(self, channels: list[Channel], feedback: set[int],
                 max_wait_s: float, **kwargs):
        super().__init__(channels, **kwargs)
        self.feedback = set(feedback)
        self.max_wait_s = max_wait_s
        self._quiet_since: Optional[float] = None
        self._regular = [i for i in range(len(channels))
                         if i not in self.feedback]
        for i in self.feedback:
            self._active[i] = False

    def poll(self) -> Optional[GateEvent]:
        ev = super().poll()
        if ev is not None:
            self._quiet_since = None     # any activity resets quiescence
        return ev

    def all_ended(self) -> bool:
        if not all(self._ended[i] for i in self._regular):
            self._quiet_since = None
            return False
        if all(self._ended):
            return True
        if any(self.channels[i].size() > 0 for i in self.feedback
               if not self._ended[i]):
            self._quiet_since = None     # queued feedback: not quiet
            return False
        now = time.time()
        if self._quiet_since is None:
            self._quiet_since = now
            return False
        return now - self._quiet_since >= self.max_wait_s
