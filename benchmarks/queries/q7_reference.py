"""Plain numpy reference for NEXmark Q7 (highest bid) as this repo runs it:
TUMBLE size, per auction the bid with the largest (price, bidder), and of
those the largest of the window.

Independent of the code under test: it imports nothing of ``flink_tpu``
and takes nothing the program has made. It reads the generator's own
``auction``, ``price`` and ``bidder`` columns and packs the word
``price << shift | bidder`` ITSELF (with ``bidder < 2^shift`` the word's
order is the lexicographic order of (price, bidder)). Events are fed in
stream order (timestamps non-decreasing); per window it keeps one
``n_keys`` array of the best word per auction, 0 where an auction had no
bid (a bid's word is at least ``1 << shift``: prices start at 1).
"""

from __future__ import annotations

from typing import Callable, Optional

import numpy as np

__all__ = ["Q7Reference", "check_window", "WindowVerdict"]


class Q7Reference:
    def __init__(self, n_keys: int, window_ms: int, shift: int,
                 on_window: Callable[[int, np.ndarray], None]):
        self.n_keys = int(n_keys)
        self.window_ms = int(window_ms)
        self.shift = int(shift)
        self._on_window = on_window
        self._pane: Optional[int] = None         # window being collected
        self._best = np.zeros(self.n_keys, np.int64)
        self.pane_events: dict[int, int] = {}

    def feed(self, auction: np.ndarray, price: np.ndarray,
             bidder: np.ndarray, ts: np.ndarray) -> None:
        if bidder.min() < 0 or bidder.max() >> self.shift:
            raise ValueError(f"a bidder id outside [0, 2^{self.shift})")
        panes = ts // self.window_ms
        if self._pane is not None and int(panes[0]) < self._pane:
            raise ValueError("events must be fed in timestamp order")
        word = (price.astype(np.int64) << self.shift) | bidder
        cuts = np.flatnonzero(np.diff(panes)) + 1
        for a, b in zip(np.r_[0, cuts], np.r_[cuts, len(panes)]):
            self._collect(int(panes[a]), auction[a:b], word[a:b])

    def _collect(self, pane: int, auction: np.ndarray,
                 word: np.ndarray) -> None:
        if self._pane is not None and pane != self._pane:
            self.close()
        self._pane = pane
        self.pane_events[pane] = self.pane_events.get(pane, 0) + len(word)
        # the largest word of each auction in this slice: sort by
        # (auction, word), keep the last row of every auction
        order = np.lexsort((word, auction))
        a, w = auction[order], word[order]
        last = np.r_[np.flatnonzero(np.diff(a)), len(a) - 1]
        self._best[a[last]] = np.maximum(self._best[a[last]], w[last])

    def close(self) -> None:
        """The collected window is complete (the next one began, or the
        stream ended): hand it over and start afresh."""
        if self._pane is None:
            return
        self._on_window((self._pane + 1) * self.window_ms, self._best)
        self._pane, self._best = None, np.zeros(self.n_keys, np.int64)


class WindowVerdict:
    __slots__ = ("rows", "rows_differ", "topk_wrong", "detail")

    def __init__(self, rows: int, rows_differ: int, topk_wrong: int,
                 detail: str = ""):
        self.rows = rows
        self.rows_differ = rows_differ
        self.topk_wrong = topk_wrong
        self.detail = detail


def check_window(auction: np.ndarray, price: np.ndarray, bidder: np.ndarray,
                 best: np.ndarray, shift: int) -> WindowVerdict:
    """Hold one window's emitted rows to the reference's ``best``. A row
    differs unless its (price, bidder) are exactly its auction's best bid
    unpacked. The top-1 is wrong unless exactly one row came and, where
    that row is exact, its bid is the window's largest (two auctions that
    hold the same word are both right); a row that differs is counted
    once, as a row, and not again as a wrong winner."""
    n = len(auction)
    in_range = (auction >= 0) & (auction < len(best))
    want = best[np.where(in_range, auction, 0)]
    bad = ~in_range | (want == 0) \
        | (price.astype(np.int64) != want >> shift) \
        | (bidder.astype(np.int64) != want & ((1 << shift) - 1))
    rows_differ = int(bad.sum())
    topk_wrong, detail = 0, ""
    if n != 1:
        topk_wrong, detail = 1, f"{n} rows emitted, one expected"
    elif not rows_differ and want[0] != best.max():
        topk_wrong = 1
        detail = (f"auction {int(auction[0])} holds {int(want[0])}, the "
                  f"window's highest bid is {int(best.max())}")
    if rows_differ and not detail:
        detail = f"{rows_differ} rows differ"
    return WindowVerdict(n, rows_differ, topk_wrong, detail)
