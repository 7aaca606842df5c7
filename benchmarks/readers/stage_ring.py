"""Reads the program's stage spans from its tracer's ring (``flink_tpu.
metrics.tracing.TRACER``: on whenever ``traces.enabled``, profiler or
not), over the whole timed phase and not the traced six seconds:

  since_batch   ``since_batch_ms`` of the ``window/Watermark`` span whose
                turn dispatched the fire of each window that ends in the
                timed phase: how long after the operator's last batch
                began the watermark that fires the window reached it
  drain         duration of each such window's ``window/Drain`` span
  batch_queue   ``queued_ms`` of the ``task/ProcessBatch`` span of each
                timed batch: how long the batch sat in the window task's
                input channel

each the median, in ms. None where the ring holds no such span (a
program older than PR 25), dropped spans during the run, or does not
hold one span per window / batch."""

import statistics

from benchmarks.harness import stage_trace as S


def _named(spans, scope, name, task):
    return [s for s in spans if s.scope == scope and s.name == name
            and s.attributes.get("task") == task]


def samples(run, params):
    spans = S.ring_spans(run)
    if not spans:
        return None
    task = run.window_task.task_id
    what = params["value"]
    timed = run.schedule.phase("timed")
    if what == "batch_queue":
        turns = {s.attributes["seq"]: s
                 for s in _named(spans, "task", "ProcessBatch", task)}
        # the window task sees one batch per batch the reader emitted
        if len(turns) != run.schedule.n_batches:
            return None
        return [turns[b + 1].attributes["queued_ms"]
                for b in range(timed.first_batch, timed.end_batch)]
    pane = run.query.pane_ms(run.config["query"])
    ends = run.schedule.windows_ending_in(timed, pane)
    if what == "drain":
        drains = {s.attributes["seq"]: s
                  for s in _named(spans, "window", "Drain", task)}
        if not all(e in drains for e in ends):
            return None
        return [drains[e].duration_ns / 1e6 for e in ends]
    if what == "since_batch":
        dispatches = {s.attributes["seq"]: s
                      for s in _named(spans, "window", "FireDispatch", task)}
        marks = _named(spans, "window", "Watermark", task)
        out = []
        for e in ends:
            d = dispatches.get(e)
            turn = next((m for m in marks if d is not None
                         and m.start_ns <= d.start_ns
                         and d.end_ns <= m.end_ns), None)
            if turn is None:
                return None
            out.append(turn.attributes["since_batch_ms"])
        return out
    raise ValueError(f"unknown value {what!r}")


def read(run, params):
    values = samples(run, params)
    if not values:
        return None
    return statistics.median(values)
