"""Bytes one scatter-fold of a batch has to move, from shapes alone (kept
with the benchmark so that no PR that claims a gain can change the
yardstick).

The fold takes one value and one flat index per row in, and reads and
writes once each cell it touches. That is the least traffic the
algorithm needs, whatever implements it: an implementation that splits
the whole plane into 32-bit halves and joins it again, or copies it
between layouts, pays for that itself.
"""

from __future__ import annotations

__all__ = ["scatter_fold_bytes"]


def scatter_fold_bytes(batch_rows: int, value_bytes: int, index_bytes: int,
                       touched_cells: int, cell_bytes: int) -> int:
    return (int(batch_rows) * (int(value_bytes) + int(index_bytes))
            + 2 * int(touched_cells) * int(cell_bytes))
