"""Bytes the mesh step's keyBy exchange has to move between chips, from
shapes and destinations alone (kept with the benchmark so that no PR that
claims a gain can change the yardstick).

A block of ``n_devices x device_batch`` rows is cut into one slice of
``device_batch`` rows a device; every row whose key group another device
owns has to cross the interconnect once: its payload columns (key, pane
and one value column per aggregate that reads one) and its place in the
valid mask. That is the least traffic the exchange needs. The buckets the
program really sends are padded to a fixed capacity per destination, and
a skewed batch sends several rounds of them: the program's own cost, not
counted.
"""

from __future__ import annotations

import numpy as np

__all__ = ["off_chip_rows", "exchange_step_bytes"]


def off_chip_rows(dest: np.ndarray, n_devices: int, device_batch: int
                  ) -> tuple[np.ndarray, np.ndarray]:
    """(rows each device sends off chip, rows each device receives from
    other chips) for one block: ``dest[i]`` is the device that owns row
    ``i``'s key group, and rows ``[d * device_batch, (d + 1) *
    device_batch)`` start on device ``d``."""
    dest = np.asarray(dest).reshape(n_devices, device_batch)
    home = np.arange(n_devices)[:, None]
    away = dest != home
    sent = away.sum(axis=1)
    received = np.array([int((away & (dest == d)).sum())
                         for d in range(n_devices)])
    return sent, received


def exchange_step_bytes(rows: int, row_bytes: int, flag_bytes: int) -> int:
    """Bytes ``rows`` rows are on the wire: payload and valid flag."""
    return int(rows) * (int(row_bytes) + int(flag_bytes))
