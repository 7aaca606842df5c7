"""Plain numpy reference for NEXmark Q5 over a stream that arrives OUT of
event-time order (configuration ``nexmark-q5-10m-disorder``), and the
function that defines that disorder.

Independent of the code under test: it imports nothing of ``flink_tpu``
and takes nothing the program has made. Events are fed in ARRIVAL order,
a batch at a time, with the time the harness stamped on each (its place
in the stream); nothing here assumes that event times are sorted
(``q5_reference.Q5Reference.feed`` raises on such input).

**The data.** ``lag_ms`` is the one definition of the disorder, used by
the job's first map (``q5_disorder.build``) and by this reference: one
event in ``1 / delayed_share`` is held back by a whole number of
milliseconds uniform in ``[0, delay_max_ms)``, i.e. it ARRIVES at the
stamped time and carries the event time ``stamp - lag`` (Beam's
unbounded Nexmark source: ``probDelayedEvent`` / ``occasionalDelaySec``;
the event keeps its own ``dateTime``). The lag is a hash of the row's
four columns, so it is a function of the data alone: the same for the
job and for the reference, for every seed, on every machine. Event time
is clamped at 0 (the stream starts there).

**The windows.** What the data's own bound settles, and nothing of the
program's watermark: arrival times do not decrease, so once an event
stamped ``t`` has arrived no later event can carry an event time under
``t - delay_max_ms + 1``, and every pane that ends at or under it is
complete. A complete pane is counted once (``np.bincount`` over its
collected rows) and kept while a window still needs it; a window is the
plain sum of its ``W`` panes' arrays, no rolling difference.
"""

from __future__ import annotations

from typing import Callable, Optional

import numpy as np

__all__ = ["lag_ms", "event_time", "Q5DisorderReference"]

_K = tuple(np.uint64(k) for k in (
    0x9E3779B97F4A7C15, 0xBF58476D1CE4E5B9, 0x94D049BB133111EB,
    0xD6E8FEB86659FD93))
_M = np.uint64(0xFF51AFD7ED558CCD)
_U32 = np.uint64(0xFFFFFFFF)


def lag_ms(auction: np.ndarray, bidder: np.ndarray, price: np.ndarray,
           ts: np.ndarray, delayed_share: float,
           delay_max_ms: int) -> np.ndarray:
    """How long each row was held back, whole ms in ``[0, delay_max_ms)``
    and 0 for the rows that were not: a multiply-add of the four columns,
    one xor-shift-multiply round, then the top 16 bits against the
    share's cut and 32 further bits scaled to the range (multiply-shift,
    no division): some twenty passes over the batch, in place."""
    def words(col):     # an int64 column's bits, no copy
        return np.asarray(col, np.int64).view(np.uint64)

    with np.errstate(over="ignore"):
        h = words(auction) * _K[0]
        tmp = np.empty_like(h)
        for col, k in zip((bidder, price, ts), _K[1:]):
            h += np.multiply(words(col), k, out=tmp)
        h ^= np.right_shift(h, np.uint64(32), out=tmp)
        h *= _M
        h ^= np.right_shift(h, np.uint64(29), out=tmp)
        delayed = np.right_shift(h, np.uint64(48), out=tmp) < np.uint64(
            round(float(delayed_share) * 65536))
        h >>= np.uint64(16)
        h &= _U32
        h *= np.uint64(int(delay_max_ms))
        h >>= np.uint64(32)
    h *= delayed
    return h.view(np.int64)


def event_time(columns: dict, ts: np.ndarray, delayed_share: float,
               delay_max_ms: int) -> np.ndarray:
    """The event time of each row of one batch: the stamped arrival time
    less the row's lag, not under 0."""
    return np.maximum(ts - lag_ms(
        columns["auction"], columns["bidder"], columns["price"], ts,
        delayed_share, delay_max_ms), 0)


class Q5DisorderReference:
    """``feed(columns, ts)`` in arrival order, ``close()`` at the end;
    ``on_window(end_ms, bids, revenue)`` for every window end from the
    first pane's to the last pane's ``+ W - 1``, in order (a window that
    holds no data arrives as zeros)."""

    def __init__(self, n_keys: int, pane_ms: int, window_panes: int,
                 delayed_share: float, delay_max_ms: int,
                 on_window: Callable[[int, np.ndarray, np.ndarray], None]):
        self.n_keys = int(n_keys)
        self.pane_ms = int(pane_ms)
        self.W = int(window_panes)
        self.delayed_share = float(delayed_share)
        self.delay_max_ms = int(delay_max_ms)
        self._on_window = on_window
        self._last_arrival: Optional[int] = None
        # pane -> the (auction, price) chunks that arrived for it so far
        self._open: dict[int, list[tuple[np.ndarray, np.ndarray]]] = {}
        # pane -> (bids, revenue) of a complete pane some window needs
        self._done: dict[int, tuple[np.ndarray, np.ndarray]] = {}
        self._next_end: Optional[int] = None    # next window end, in panes
        self.pane_events: dict[int, int] = {}
        self.back_rows = 0      # rows of a pane older than the newest
                                # pane of the batches before theirs
        self._newest_pane: Optional[int] = None

    def feed(self, columns: dict, ts: np.ndarray) -> None:
        first, last = int(ts.min()), int(ts.max())
        if self._last_arrival is not None and first < self._last_arrival:
            raise ValueError("batches must be fed in arrival order")
        self._last_arrival = last
        panes = event_time(columns, ts, self.delayed_share,
                           self.delay_max_ms) // self.pane_ms
        if self._newest_pane is not None:
            self.back_rows += int(np.count_nonzero(
                panes < self._newest_pane))
        auction, price = columns["auction"], columns["price"]
        for pane in np.unique(panes).tolist():
            rows = panes == pane
            self._open.setdefault(pane, []).append(
                (auction[rows], price[rows]))
        self._newest_pane = max(int(panes.max()), self._newest_pane or 0)
        # no later arrival carries an event time under this
        self._settle(max(last - self.delay_max_ms + 1, 0) // self.pane_ms)

    def _settle(self, pane: int) -> None:
        """Every pane under ``pane`` is complete: count those that hold
        rows, then emit the windows that end at or under it."""
        for p in sorted(p for p in self._open if p < pane):
            chunks = self._open.pop(p)
            keys = np.concatenate([c[0] for c in chunks])
            prices = np.concatenate([c[1] for c in chunks])
            self.pane_events[p] = len(keys)
            bids = np.bincount(keys, minlength=self.n_keys)
            # float64 weights are exact here: a pane's revenue per key
            # stays far below 2^53
            rev = np.bincount(keys, weights=prices,
                              minlength=self.n_keys).astype(np.int64)
            self._done[p] = (bids, rev)
            if self._next_end is None:
                self._next_end = p + 1
        while self._next_end is not None and self._next_end <= pane:
            self._emit(self._next_end)

    def _emit(self, end: int) -> None:
        bids = np.zeros(self.n_keys, np.int64)
        rev = np.zeros(self.n_keys, np.int64)
        for p in range(end - self.W, end):
            if p in self._done:
                bids += self._done[p][0]
                rev += self._done[p][1]
        self._on_window(end * self.pane_ms, bids, rev)
        self._done.pop(end - self.W, None)    # no later window reads it
        self._next_end = end + 1

    def close(self) -> None:
        """End of stream: every pane is complete, and the W - 1 windows
        that still overlap the last one are flushed."""
        if self._newest_pane is None:
            return
        last = self._newest_pane
        self._settle(last + 1)
        for end in range(self._next_end, last + self.W + 1):
            self._emit(end)
