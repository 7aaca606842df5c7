"""Bytes an ingest step has to move, from shapes alone (kept with the
benchmark so that no PR that claims a gain can change the yardstick).

One step takes ``batch_rows`` records in and folds them into the slots
they touch: each touched slot's key and every aggregate cell is read and
written once. That is the least traffic the algorithm needs; the probe's
extra table reads and XLA's temporaries are the step's own cost and are
not counted.
"""

from __future__ import annotations

__all__ = ["ingest_step_bytes", "slot_bytes"]


def slot_bytes(key_bytes: int, cell_bytes: list[int]) -> int:
    """One slot's key plus one cell per aggregate plane."""
    return int(key_bytes) + int(sum(cell_bytes))


def ingest_step_bytes(batch_rows: int, record_bytes: int,
                      touched_slots: int, slot_bytes_: int) -> int:
    return (int(batch_rows) * int(record_bytes)
            + 2 * int(touched_slots) * int(slot_bytes_))
