"""Reads one part of a fired window's life from the traced run: the
program's ``window.FireDispatch`` / ``window.Drain`` / ``window.Emit``
annotations of that window (matched by ``seq``, its end in event-time
ms) joined with the fire program's execution on the device
(harness/stage_trace.fire_lives). The value is the median, in ms, of
``params["part"]`` over the windows whose life lies whole inside the
traced window."""

import statistics

from benchmarks.harness import stage_trace as S
from benchmarks.harness import trace as T
from benchmarks.harness.trace_summary import busiest_plane


def lives(run, params):
    traced = S.checked_trace(run, params)
    if traced is None:
        return None
    stages = traced["stages"]
    lo, hi = T.traced_window(run.trace)
    plane = busiest_plane(run.trace, lo, hi)
    return S.fire_lives(stages, T.line_events(plane, T.MODULE_LINE),
                        run.window_task.task_id, params["fire_module"],
                        lo, hi, params["stages"])


def read(run, params):
    found = lives(run, params)
    if not found:
        return None
    return statistics.median(life[params["part"]] for life in found)
