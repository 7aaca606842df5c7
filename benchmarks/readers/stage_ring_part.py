"""Reads one PART of a per-batch stage span, from the tracer's ring
(``flink_tpu.metrics.tracing.TRACER``), over the whole timed phase.

Since PR 53 a stage span closes with ``cpu_ms``: the CPU time of the
thread that ran it (``time.thread_time_ns``), taken at the two sites the
span's own stamps come from. What is left of its duration the thread
stood still: it waited for the device, for the GIL, or for the machine.
``task/SourceBatch`` also carries ``blocked_ms``, the part of its emit
that its writers stood in a full channel.

``params["task"]`` is ``window`` (the task that holds the window
operator) or ``source`` (the job's one source task); ``scope`` / ``name``
the span; the spans read are those whose ``seq`` is the ordinal of a
timed batch. ``params["part"]``:

  cpu            ``cpu_ms``, MEAN a timed batch
  wait           (sum of the durations - sum of ``cpu_ms``) a timed batch
  own            duration - ``blocked_ms``, median a timed batch
  blocked_share  100 x sum of ``blocked_ms`` / sum of the durations

The two CPU parts are sums and not medians because of the clock: where
the kernel accounts a thread's CPU time by ticks (10 ms on the machines
the benchmark runs on: PERF.md section 6, PR 53) a span's ``cpu_ms`` is
0, 10 or 20, may exceed a short span's duration, and says something only
added up over the batches; their mean is right to about a tick over the
square root of the ticks counted.

None where a timed batch lacks the span or the span the attribute (a
program from before PR 53), where the ring dropped spans during the run,
or where the job has no such task."""

import statistics

from benchmarks.harness import stage_trace as S


def task_of(run, which: str):
    """The window task, or the job's one source task (the task that owns
    a reader); None where the job has not exactly one."""
    if which == "window":
        return run.window_task
    if which != "source":
        raise ValueError(f"unknown task {which!r}")
    sources = [t for t in run.job.tasks.values()
               if getattr(t, "reader", None) is not None]
    return sources[0] if len(sources) == 1 else None


def timed_spans(run, params):
    """The span of each timed batch, in order, or None."""
    task = task_of(run, params["task"])
    spans = S.ring_spans(run)
    if task is None or not spans:
        return None
    by_seq = {s.attributes.get("seq"): s for s in spans
              if s.scope == params["scope"] and s.name == params["name"]
              and s.attributes.get("task") == task.task_id}
    timed = run.schedule.phase("timed")
    wanted = range(timed.first_batch + 1, timed.end_batch + 1)
    if not all(seq in by_seq for seq in wanted):
        return None
    return [by_seq[seq] for seq in wanted]


def read(run, params):
    spans = timed_spans(run, params)
    part = params["part"]
    attr = "cpu_ms" if part in ("cpu", "wait") else "blocked_ms"
    if not spans or any(attr not in s.attributes for s in spans):
        return None
    ms = [s.duration_ns / 1e6 for s in spans]
    held = [s.attributes[attr] for s in spans]
    if part == "cpu":
        return sum(held) / len(spans)
    if part == "wait":
        return max(0.0, sum(ms) - sum(held)) / len(spans)
    if part == "own":
        return statistics.median(max(0.0, d - h) for d, h in zip(ms, held))
    if part == "blocked_share":
        whole = sum(ms)
        return 100.0 * sum(held) / whole if whole > 0 else None
    raise ValueError(f"unknown part {part!r}")
