"""Reads how long a stage span that belongs to a fired window took, from
the program's tracer's ring: the median duration, in ms, of the spans
``params["scope"]`` / ``params["name"]`` of the window task whose ``seq``
is the end (ms of event time) of a window that ends in the timed phase.
What ``stage_ring_span`` reads for the spans a batch has; a span that
only some windows have (a reclaim inside a window's drain) is read over
the windows that have it. None where the ring holds no such span (a
program that does not write it, or a run without one), or dropped spans
during the run."""

import statistics

from benchmarks.harness import stage_trace as S


def samples(run, params):
    spans = S.ring_spans(run)
    if not spans:
        return None
    task = run.window_task.task_id
    begins = run.schedule.phase("timed").start_ms
    return [s.duration_ns / 1e6 for s in spans
            if s.scope == params["scope"] and s.name == params["name"]
            and s.attributes.get("task") == task
            and s.attributes.get("seq", 0) > begins]


def read(run, params):
    values = samples(run, params)
    return statistics.median(values) if values else None
