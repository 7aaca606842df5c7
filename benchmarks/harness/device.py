"""The device as JAX reports it, its peak memory, and the table of peaks."""

from __future__ import annotations

import json
import os
from typing import Optional

__all__ = ["device_block", "memory_peak_bytes", "peak", "versions"]

_PEAKS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "peaks.json")


def device_block() -> dict:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes() -> Optional[int]:
    """``peak_bytes_in_use`` on the fullest device (None where the backend
    reports none, as the CPU does)."""
    import jax

    vals = []
    for d in jax.devices():
        stats = d.memory_stats()
        if stats and "peak_bytes_in_use" in stats:
            vals.append(int(stats["peak_bytes_in_use"]))
    return max(vals) if vals else None


def peak(device_kind: str, quantity: str,
         peaks_path: Optional[str] = None) -> float:
    """A published peak of ``device_kind``. A device that is not in the
    table is an error, never a default."""
    with open(peaks_path or _PEAKS, encoding="utf-8") as f:
        table = json.load(f)
    if device_kind not in table or device_kind.startswith("_"):
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{peaks_path or _PEAKS}; add its published numbers "
                       "with their source")
    return float(table[device_kind][quantity])


def versions() -> dict:
    from importlib import metadata

    import jax
    import jaxlib

    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = None
    return {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
            "libtpu": libtpu}
