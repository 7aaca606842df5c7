"""The run's schedule: which batch holds which events, and when it is due.

One index stream ``g = 0, 1, ...`` cut into batches of exactly
``batch_rows`` rows (one shape, so one step program) and into phases:

  prefill  set-up   every key once, back to back but never more than a
                    few panes of event time ahead of the results seen at
                    the sink (the reader's flow control), its event time
                    squeezed into ``prefill_panes`` panes
  warm     set-up   ``warm_s`` seconds of the cell's traffic, paced, on a
                    clock that starts once the prefill has been consumed
  timed    measured ``seconds`` seconds of the cell's traffic:
                    ``floor(seconds * event_rate / batch_rows)`` batches,
                    paced ("scheduled") or back to back ("unthrottled")

Event time is the schedule: within a phase of rate ``r`` that starts at
event time ``T`` with event ``g0``, event ``g`` has
``ts = T + (g - g0) * 1000 // r`` ms, and in a paced phase it is due at
``origin + ts / 1000`` s. The program's channels queue up to 64 batches,
and a backlog carried into the timed phase would be charged to it: hence
the flow control in the prefill, the wait after it, and the paced warm
phase (below what the system sustains).

Pure arithmetic; the same object serves the reader and the reference.
"""

from __future__ import annotations

import math
from dataclasses import dataclass

import numpy as np

__all__ = ["Phase", "Schedule", "build_schedule"]


@dataclass(frozen=True)
class Phase:
    name: str
    first_batch: int     # index of the phase's first batch
    n_batches: int
    rate: int            # events per second of event time
    start_ms: int        # event time of the phase's first event
    paced: bool

    @property
    def end_batch(self) -> int:
        return self.first_batch + self.n_batches


class Schedule:
    def __init__(self, batch_rows: int, phases: list[Phase]):
        self.batch_rows = int(batch_rows)
        self.phases = phases
        self.n_batches = phases[-1].end_batch

    def phase(self, name: str) -> Phase:
        return next(p for p in self.phases if p.name == name)

    def phase_of(self, batch: int) -> Phase:
        for p in self.phases:
            if p.first_batch <= batch < p.end_batch:
                return p
        raise IndexError(batch)

    def batch_index(self, batch: int) -> np.ndarray:
        """Global event indices of one batch."""
        start = batch * self.batch_rows
        return np.arange(start, start + self.batch_rows, dtype=np.int64)

    def batch_ts(self, batch: int) -> np.ndarray:
        p = self.phase_of(batch)
        local = (self.batch_index(batch)
                 - p.first_batch * self.batch_rows)
        return p.start_ms + (local * 1000) // p.rate

    def row_ts(self, batch: int, row: int) -> int:
        """Event time of one row (``row`` may be negative, from the end):
        ``batch_ts(batch)[row]`` without making the vector."""
        p = self.phase_of(batch)
        local = ((batch - p.first_batch) * self.batch_rows
                 + row % self.batch_rows)
        return p.start_ms + (local * 1000) // p.rate

    def due_s(self, batch: int) -> float:
        """Seconds after the origin at which the batch's last row is due."""
        return self.row_ts(batch, -1) / 1000.0

    def closing_batch(self, end_ms: int) -> int:
        """The batch that holds the last event before event time
        ``end_ms``: once it has been handed over, the job holds every
        event of the window that ends there."""
        p = next(p for p in reversed(self.phases) if p.start_ms < end_ms)
        # ts < end_ms  <=>  local * 1000 < (end_ms - start_ms) * rate
        last = min(p.n_batches * self.batch_rows,
                   -(-(end_ms - p.start_ms) * p.rate // 1000)) - 1
        return p.first_batch + last // self.batch_rows

    def phase_end_ms(self, p: Phase) -> int:
        """Event time just past the phase's last event."""
        return p.start_ms + math.ceil(
            p.n_batches * self.batch_rows * 1000 / p.rate)

    def windows_ending_in(self, p: Phase, pane_ms: int) -> list[int]:
        """Window ends E (multiples of the slide) whose last contributing
        event belongs to this phase: first ts of the phase < E <= the event
        time at which the next phase starts. The stream's last phase stops
        at its last event instead: a window that only the end-of-stream
        flush closes never waited for a watermark, so it is no latency
        sample."""
        if p.n_batches == 0:
            return []
        lo = p.start_ms // pane_ms + 1
        if p is self.phases[-1]:
            hi = (self.row_ts(p.end_batch - 1, -1) + 1) // pane_ms
        else:
            hi = self.phase_end_ms(p) // pane_ms
        return [e * pane_ms for e in range(lo, hi + 1)]


def build_schedule(*, n_keys: int, batch_rows: int, prefill_panes: int,
                   pane_ms: int, warm_s: float, event_rate: int,
                   pacing: str, seconds: float) -> Schedule:
    if pacing not in ("scheduled", "unthrottled"):
        raise ValueError(f"unknown pacing {pacing!r}")
    phases: list[Phase] = []
    t_ms, b = 0, 0

    def add(name: str, n: int, rate: int, paced: bool) -> None:
        nonlocal t_ms, b
        ph = Phase(name, b, n, rate, t_ms, paced)
        phases.append(ph)
        b += n
        t_ms = ph.start_ms + math.ceil(n * batch_rows * 1000 / rate)

    n_prefill = math.ceil(n_keys / batch_rows)
    prefill_rate = math.ceil(n_prefill * batch_rows * 1000
                             / (prefill_panes * pane_ms))
    add("prefill", n_prefill, prefill_rate, False)
    add("warm", max(1, math.ceil(warm_s * event_rate / batch_rows)),
        event_rate, True)
    n_timed = int(seconds * event_rate // batch_rows)
    if n_timed < 1:
        raise ValueError(f"{seconds} s at {event_rate} events/s is less "
                         f"than one batch of {batch_rows}")
    add("timed", n_timed, event_rate, pacing == "scheduled")
    return Schedule(batch_rows, phases)
