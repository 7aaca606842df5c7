"""Reads the device's idle time by the program's own mailbox stages.

Every instant of the traced window in which no operation ran on the
busiest device goes to the first span, in ``order`` (innermost stage
first, ``task.WaitInput`` last), that covers it: the program's stage
annotations of the task that holds the window operator, and the
benchmark's own two. The value is the share (in % of the traced window)
that fell to the spans in ``group``, or, with ``group`` left out, to none
of them. The shares of one ``order`` are a partition: together they are
the device's idle share (harness/stage_trace.idle_partition)."""

from benchmarks.harness import stage_trace as S
from benchmarks.harness import trace as T
from benchmarks.harness.trace_summary import busiest_plane

REST = "unattributed"


def partition(run, params):
    """{span name: idle seconds} and the window's length, or None."""
    traced = S.checked_trace(run, params)
    if traced is None:
        return None
    stages = traced["stages"]
    lo, hi = T.traced_window(run.trace)
    plane = busiest_plane(run.trace, lo, hi)
    busy = [(a, b) for _n, a, b in T.clip(T._busy_events(plane), lo, hi)]
    program = set(params["program_spans"])
    spans = {}
    for name in params["order"]:
        events = S.stage_events(
            stages, name,
            run.window_task.task_id if name in program else None)
        spans[name] = [(e["start"], e["end"]) for e in events]
    return (S.idle_partition(busy, lo, hi, spans, params["order"], REST),
            (hi - lo) / 1e9)


def read(run, params):
    found = partition(run, params)
    if found is None:
        return None
    seconds, window_s = found
    group = params.get("group", [REST])
    return 100.0 * sum(seconds[name] for name in group) / window_s
