"""The ``device`` block's busy / window seconds and the ``breakdown`` of a
traced run, from the reduced trace."""

from __future__ import annotations

from . import trace as T
from .cell import HOST_SPANS

__all__ = ["device_summary", "busiest_plane"]


def busiest_plane(trace: dict, lo: float, hi: float):
    """The device plane with most busy time in [lo, hi]; None where the
    trace holds no device plane (a CPU rehearsal)."""
    planes = T.device_planes(trace)
    if not planes:
        return None
    return max(planes, key=lambda p: T.busy_s(p, lo, hi))


def device_summary(run) -> tuple[dict, dict]:
    trace = run.trace
    lo, hi = T.traced_window(trace)
    planes = T.device_planes(trace)
    if not planes:
        raise ValueError("the trace holds no device plane: no operation "
                         "ran on a device inside the traced window")
    busy = [T.busy_s(p, lo, hi) for p in planes]
    top = busiest_plane(trace, lo, hi)
    device = {"busy_s": sum(busy) / len(busy), "window_s": (hi - lo) / 1e9,
              "busy_s_per_device": busy}
    breakdown = {"device_ops": T.top_ops(top, lo, hi, 10),
                 "idle_gaps": T.idle_gaps(trace, top, lo, hi, HOST_SPANS,
                                          10)}
    return device, breakdown
