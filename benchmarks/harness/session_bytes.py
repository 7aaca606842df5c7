"""Bytes the session operator's two programs have to move, from shapes
and counts alone (kept with the benchmark so that no PR that claims a
gain can change the yardstick).

A STEP takes a batch of bids into the lanes: it reads each bid's key and
timestamp, finds each distinct key's slot (one table key read), and
reads and writes ONE lane's cells of that key (its session's start, end,
open flag and count: a bid joins the open session or opens the next, and
either way one lane of the key changes).

A FIRE takes the closed sessions off the lanes: it reads ``__end__`` and
``__open__`` once, whole (what tells a closed session from an open one
is in no smaller place), writes one row per fired session and resets one
lane's cells per fired session.

That is the least traffic the semantics need, whatever implements them:
the probe's windows, the merge check against every lane of a key, the
segment buffers, a plane joined from its halves or split into them, a
scatter that walks updates it drops and the compaction's own passes are
the implementation's cost and not the model's; an implementation that
keeps an index of what is about to close would read less than this, and
the share would say so by passing 100.
"""

from __future__ import annotations

__all__ = ["session_step_bytes", "session_fire_bytes"]


def session_step_bytes(rows: int, row_bytes: int, touched_keys: int,
                       key_bytes: int, lane_cell_bytes: list[int]) -> int:
    """``lane_cell_bytes``: bytes of one cell of each lanes plane (the
    configuration's ``state.lane_cell_bytes``)."""
    return (int(rows) * int(row_bytes)
            + int(touched_keys) * (int(key_bytes)
                                   + 2 * int(sum(lane_cell_bytes))))


def session_fire_bytes(capacity: int, lanes: int, fired: float,
                       end_cell_bytes: int, open_cell_bytes: int,
                       out_row_bytes: int,
                       lane_cell_bytes: list[int]) -> float:
    """``fired``: sessions one fire takes off the lanes (all its rounds)."""
    scan = int(capacity) * int(lanes) * (int(end_cell_bytes)
                                         + int(open_cell_bytes))
    return scan + float(fired) * (int(out_row_bytes)
                                  + int(sum(lane_cell_bytes)))
