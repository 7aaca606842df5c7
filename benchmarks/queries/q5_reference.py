"""Plain numpy reference for NEXmark Q5 (hot items) as this repo runs it:
HOP size / slide, COUNT(*) and SUM(price) per auction, top-k by count.

Independent of the code under test: it imports nothing of ``flink_tpu``
and takes nothing the program has made. Events are fed in stream order
(timestamps non-decreasing); per pane it keeps ``np.bincount`` of the
auction column and of the price-weighted auction column, and it rolls the
last ``W`` panes into the window sum, so it holds ``W + 1`` pairs of
``n_keys`` arrays and not the whole run.

``sum_dtype`` exists for the control only: the reference computed with
SUM(price) kept in 32 bits, put in the program's place, has to FAIL the
comparison (benchmarks/control.py, benchmarks/tests).
"""

from __future__ import annotations

from collections import deque
from typing import Callable, Optional

import numpy as np

__all__ = ["Q5Reference", "check_window", "WindowVerdict"]


class Q5Reference:
    def __init__(self, n_keys: int, pane_ms: int, window_panes: int,
                 on_window: Callable[[int, np.ndarray, np.ndarray], None],
                 sum_dtype=np.int64):
        self.n_keys = int(n_keys)
        self.pane_ms = int(pane_ms)
        self.W = int(window_panes)
        self._on_window = on_window
        self._sum_dtype = np.dtype(sum_dtype)
        self._pane: Optional[int] = None        # pane being collected
        self._keys: list[np.ndarray] = []
        self._prices: list[np.ndarray] = []
        self._ring: deque = deque()             # (pane, bids, revenue) x W
        self._win_bids = np.zeros(self.n_keys, np.int64)
        self._win_rev = np.zeros(self.n_keys, self._sum_dtype)
        self._first_pane: Optional[int] = None
        self._next_end: Optional[int] = None    # next window end (in panes)
        self.pane_events: dict[int, int] = {}

    def feed(self, auction: np.ndarray, price: np.ndarray,
             ts: np.ndarray) -> None:
        panes = ts // self.pane_ms
        lo, hi = int(panes[0]), int(panes[-1])
        if self._pane is not None and lo < self._pane:
            raise ValueError("events must be fed in timestamp order")
        if lo == hi:
            self._collect(lo, auction, price)
            return
        cuts = np.flatnonzero(np.diff(panes)) + 1
        for a, b in zip(np.r_[0, cuts], np.r_[cuts, len(panes)]):
            self._collect(int(panes[a]), auction[a:b], price[a:b])

    def _collect(self, pane: int, auction, price) -> None:
        if self._pane is not None and pane != self._pane:
            self._seal()
        self._pane = pane
        self._keys.append(auction)
        self._prices.append(price)

    def _seal(self) -> None:
        """The collected pane is complete: fold it into the rolling
        window and emit every window that ends at or before it."""
        pane = self._pane
        keys = np.concatenate(self._keys)
        prices = np.concatenate(self._prices)
        self._keys, self._prices = [], []
        self.pane_events[pane] = len(keys)
        bids = np.bincount(keys, minlength=self.n_keys)
        with np.errstate(over="ignore"):
            if self._sum_dtype == np.int64:
                # float64 weights are exact here: a pane's revenue per key
                # stays far below 2^53
                rev = np.bincount(keys, weights=prices,
                                  minlength=self.n_keys).astype(np.int64)
            else:
                rev = np.zeros(self.n_keys, self._sum_dtype)
                np.add.at(rev, keys, prices.astype(self._sum_dtype))
        if self._first_pane is None:
            self._first_pane = pane
            self._next_end = pane + 1
        # windows ending before this pane's end hold only sealed panes
        self._emit_until(pane)
        self._ring.append((pane, bids, rev))
        self._win_bids += bids
        with np.errstate(over="ignore"):
            self._win_rev += rev
        self._expire(pane + 1)
        self._emit(pane + 1)

    def _expire(self, end: int) -> None:
        """Drop panes below ``end - W`` from the rolling sum."""
        while self._ring and self._ring[0][0] < end - self.W:
            _p, bids, rev = self._ring.popleft()
            self._win_bids -= bids
            with np.errstate(over="ignore"):
                self._win_rev -= rev

    def _emit(self, end: int) -> None:
        self._on_window(end * self.pane_ms, self._win_bids, self._win_rev)
        self._next_end = end + 1

    def _emit_until(self, pane: int) -> None:
        """Windows whose end lies at or before the START of ``pane`` and
        that were not emitted yet (event-time gaps leave empty panes)."""
        while self._next_end is not None and self._next_end <= pane:
            self._expire(self._next_end)
            self._emit(self._next_end)

    def close(self) -> None:
        """End of stream: seal the last pane and flush the W-1 windows
        that still overlap it."""
        if self._pane is None:
            return
        self._seal()
        last = self._pane
        for end in range(last + 2, last + self.W + 1):
            self._expire(end)
            self._emit(end)
        self._pane = None


class WindowVerdict:
    __slots__ = ("rows", "rows_differ", "topk_wrong", "detail")

    def __init__(self, rows: int, rows_differ: int, topk_wrong: int,
                 detail: str = ""):
        self.rows = rows
        self.rows_differ = rows_differ
        self.topk_wrong = topk_wrong
        self.detail = detail


def check_window(auction: np.ndarray, bids: np.ndarray,
                 revenue: np.ndarray, ref_bids: np.ndarray,
                 ref_rev: np.ndarray, topk: int) -> WindowVerdict:
    """Hold one window's emitted rows to the reference. Exact integer
    equality per row; and the emitted keys must be a correct top-k: all
    keys strictly above the k-th count, the rest tied AT it (which tied
    keys fill the last seats is free), so the emitted bids multiset equals
    the reference's."""
    n = len(auction)
    in_range = (auction >= 0) & (auction < len(ref_bids))
    safe = np.where(in_range, auction, 0)
    bad = ~in_range | (ref_bids[safe] != bids) \
        | (ref_rev[safe].astype(np.int64) != revenue.astype(np.int64))
    _u, first = np.unique(auction, return_index=True)
    dup = np.ones(n, bool)
    dup[first] = False
    bad |= dup
    rows_differ = int(bad.sum())
    k = min(int(topk), int(np.count_nonzero(ref_bids)))
    detail = ""
    topk_wrong = 0
    if n != k:
        topk_wrong, detail = 1, f"{n} rows emitted, top-{k} expected"
    elif k:
        want = np.partition(ref_bids, len(ref_bids) - k)[len(ref_bids) - k:]
        thr = want.min()
        if not np.array_equal(np.sort(bids), np.sort(want)):
            topk_wrong, detail = 1, "bids multiset differs from the top-k"
        elif not np.array_equal(np.sort(auction[bids > thr]),
                                np.flatnonzero(ref_bids > thr)):
            topk_wrong, detail = 1, "keys above the k-th count differ"
    if rows_differ and not detail:
        detail = f"{rows_differ} rows differ"
    return WindowVerdict(n, rows_differ, topk_wrong, detail)
