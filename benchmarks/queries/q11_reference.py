"""Plain numpy reference for NEXmark Q11 (user sessions): per bidder,
SESSION(gap) over the bid stream, COUNT(*) per session; a session is
``[first_ts, last_ts + gap)``.

Independent of the code under test: it imports nothing of ``flink_tpu``
and takes nothing the program has made. Events are fed in stream order
(timestamps non-decreasing); per bidder it keeps three dense arrays over
``id_space`` ids: the open session's ``last_ts`` (-1: none), ``start``
and ``count``. A bid joins its bidder's open session when
``ts - last_ts < gap`` and otherwise closes it and opens the next: at
``ts - last_ts == gap`` the bid's window ``[ts, ts + gap)`` only TOUCHES
the session's ``[start, last_ts + gap)``. Flink's ``TimeWindow.
intersects`` merges windows that touch, but only while the earlier one
is still in state, i.e. until a watermark has fired it: whether the bid
joins would turn on when a periodic watermark was cut. The rule here is
a function of the data alone, and it is the program's (configs/
nexmark-q11-sessions.json, ``assumed``).

A batch is taken at once: a stable sort by bidder keeps each bidder's
bids in stream order, a bid opens a session where its distance to the
bidder's bid before it (in the batch, or the state's ``last_ts``)
reaches the gap, and the sessions a bidder closes in the batch are read
off the cuts. Closed sessions are kept; ``close()`` closes the open
ones, buckets all by the REPORT PANE that holds their end
(``end // pane * pane + pane``) and calls ``on_window(pane_end,
(bidder, start, end, count))`` in order.
"""

from __future__ import annotations

from typing import Callable

import numpy as np

__all__ = ["Q11Reference", "check_window", "WindowVerdict", "report_pane"]


def report_pane(session_end: np.ndarray, pane_ms: int) -> np.ndarray:
    """End of the ``pane_ms`` bucket that holds ``session_end``."""
    return session_end // pane_ms * pane_ms + pane_ms


class Q11Reference:
    def __init__(self, id_space: int, gap_ms: int, pane_ms: int,
                 on_window: Callable[[int, tuple], None]):
        self.gap = int(gap_ms)
        self.pane_ms = int(pane_ms)
        self._on_window = on_window
        self.last_ts = np.full(int(id_space), -1, np.int64)
        self.start = np.zeros(int(id_space), np.int64)
        self.count = np.zeros(int(id_space), np.int64)
        self._closed: list[tuple] = []     # (bidder, start, end, count)
        self._newest = -1
        self.pane_events: dict[int, int] = {}

    def feed(self, bidder: np.ndarray, ts: np.ndarray) -> None:
        if len(ts) == 0:
            return
        if int(ts[0]) < self._newest or (np.diff(ts) < 0).any():
            raise ValueError("events must be fed in timestamp order")
        if bidder.min() < 0 or bidder.max() >= len(self.last_ts):
            raise ValueError(f"a bidder id outside [0, {len(self.last_ts)})")
        self._newest = int(ts[-1])
        panes, n = np.unique(ts // self.pane_ms, return_counts=True)
        for p, c in zip(panes.tolist(), n.tolist()):
            self.pane_events[p] = self.pane_events.get(p, 0) + c
        order = np.argsort(bidder, kind="stable")
        b, t = bidder[order], ts[order]
        head = np.r_[True, b[1:] != b[:-1]]        # a bidder's first bid
        had = self.last_ts[b] >= 0                 # (read at heads only)
        before = np.where(head, self.last_ts[b], np.r_[0, t[:-1]])
        known = ~head | had
        opens = ~known | (t - before >= self.gap)
        # (a) the state's session, where the bidder's first bid closes it
        shut = head & had & opens
        self._keep(b[shut], self.start[b[shut]], self.last_ts[b[shut]],
                   self.count[b[shut]])
        # in-batch segments: from each head or opening bid to the bid
        # before the next one
        cuts = np.flatnonzero(head | opens)
        ends = np.r_[cuts[1:], len(b)] - 1
        seg_b, joins = b[cuts], ~opens[cuts]       # joins the state's
        seg_start = np.where(joins, self.start[seg_b], t[cuts])
        seg_count = ends - cuts + 1 + np.where(joins, self.count[seg_b], 0)
        seg_last = t[ends]
        # (b) every segment but a bidder's last closes inside the batch
        final = np.r_[seg_b[1:] != seg_b[:-1], True]
        self._keep(seg_b[~final], seg_start[~final], seg_last[~final],
                   seg_count[~final])
        self.start[seg_b[final]] = seg_start[final]
        self.count[seg_b[final]] = seg_count[final]
        self.last_ts[seg_b[final]] = seg_last[final]

    def _keep(self, bidder, start, last, count) -> None:
        if len(bidder):
            self._closed.append((bidder.copy(), start.copy(),
                                 last + self.gap, count.copy()))

    def close(self) -> None:
        """The stream ended: every open session closes; hand all the
        sessions over, pane by pane."""
        live = np.flatnonzero(self.last_ts >= 0)
        self._keep(live, self.start[live], self.last_ts[live],
                   self.count[live])
        self.last_ts[:] = -1
        if not self._closed:
            return
        bidder, start, end, count = (np.concatenate(c)
                                     for c in zip(*self._closed))
        self._closed = []
        pane = report_pane(end, self.pane_ms)
        order = np.argsort(pane, kind="stable")
        cut = np.flatnonzero(np.diff(pane[order])) + 1
        for a, z in zip(np.r_[0, cut], np.r_[cut, len(order)]):
            i = order[a:z]
            self._on_window(int(pane[i[0]]),
                            (bidder[i], start[i], end[i], count[i]))


class WindowVerdict:
    __slots__ = ("rows", "rows_differ", "topk_wrong", "detail")

    def __init__(self, rows: int, rows_differ: int, topk_wrong: int = 0,
                 detail: str = ""):
        self.rows = rows
        self.rows_differ = rows_differ
        self.topk_wrong = topk_wrong
        self.detail = detail


def _table(bidder, start, end, count) -> np.ndarray:
    rows = np.stack([np.asarray(c, np.int64)
                     for c in (bidder, start, end, count)], axis=1)
    return rows[np.lexsort(rows.T[::-1])]


def check_window(bidder, start, end, count, want: tuple) -> WindowVerdict:
    """Hold one report pane's emitted sessions to the reference's, as
    multisets of (bidder, start, end, count): ``rows_differ`` counts the
    rows only one side has (a changed row counts twice: once as the row
    that came, once as the row that did not)."""
    got, exp = _table(bidder, start, end, count), _table(*want)
    if got.shape == exp.shape and (got == exp).all():
        return WindowVerdict(len(got), 0)
    both, inverse = np.unique(np.concatenate([got, exp]), axis=0,
                              return_inverse=True)
    net = np.bincount(inverse.reshape(-1), weights=np.r_[
        np.ones(len(got)), -np.ones(len(exp))], minlength=len(both))
    odd = np.flatnonzero(net)
    detail = (f"{len(got)} rows emitted, {len(exp)} expected; first "
              f"{'extra' if net[odd[0]] > 0 else 'missing'} "
              f"(bidder, start, end, count) {both[odd[0]].tolist()}")
    return WindowVerdict(len(got), int(np.abs(net).sum()), 0, detail)
