"""Bytes one reclaim of the keyed state has to move, from shapes alone
(kept with the benchmark so that no PR that claims a gain can change the
yardstick).

A reclaim frees the slots of the keys that hold no data in any ring row
and rebuilds the table at the same capacity: it reads the table and
writes the new one; it reads the count plane once to tell what lives
(every fold counts, so a key with no count in any ring row holds
nothing); and it reads and writes every plane once to re-seat it. That is
the least traffic the semantics need, whatever implements them: probing
for the new slots and gathering the cells from where they were are random
accesses the implementation pays for itself, and one that skips the ring
rows that are empty moves less than this.
"""

from __future__ import annotations

__all__ = ["reclaim_bytes"]


def reclaim_bytes(capacity: int, ring: int, key_bytes: int,
                  cell_bytes: list[int]) -> int:
    """``cell_bytes``: bytes of one cell of each ring plane, the count
    plane's first (the configuration's ``state.cell_bytes``)."""
    slots = int(capacity)
    planes = int(ring) * slots * int(sum(cell_bytes))
    return (2 * slots * int(key_bytes)
            + int(ring) * slots * int(cell_bytes[0])
            + 2 * planes)
