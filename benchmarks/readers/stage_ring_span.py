"""Reads how long one of the program's per-batch stage spans took, from
its tracer's ring (``flink_tpu.metrics.tracing.TRACER``), over the whole
timed phase: the median duration, in ms, of the spans
``params["scope"]`` / ``params["name"]`` of the window task whose ``seq``
is the ordinal of a timed batch (the operator steps one block per batch
of ``batch_rows`` rows, and numbers its blocks from 1). None where the
ring holds no such span for any timed batch (a program that does not
write it), or dropped spans during the run."""

import statistics

from benchmarks.harness import stage_trace as S


def samples(run, params):
    spans = S.ring_spans(run)
    if not spans:
        return None
    task = run.window_task.task_id
    by_seq = {s.attributes.get("seq"): s for s in spans
              if s.scope == params["scope"] and s.name == params["name"]
              and s.attributes.get("task") == task}
    timed = run.schedule.phase("timed")
    wanted = range(timed.first_batch + 1, timed.end_batch + 1)
    if not all(seq in by_seq for seq in wanted):
        return None
    return [by_seq[seq].duration_ns / 1e6 for seq in wanted]


def read(run, params):
    values = samples(run, params)
    if not values:
        return None
    return statistics.median(values)
