"""Reads what a task's mailbox thread COMPUTED over the timed phase, as a
share of its length: ``TaskIOTimers.cpu_s`` (PR 53: the thread's CPU
clock, read from outside the thread) of the task ``params["task"]``
(``window`` | ``source``, as ``readers/stage_ring_part.py`` finds them).

The clock counts since the thread started, so the reading at the first
timed batch comes from the job's metric registry (the gauge
``cpuTimeRatio`` = cpu / elapsed under the task's scope, times the time
since the job started, as ``readers/io_timers.py`` takes busy), and the
reading at the end from the task itself, frozen when its thread ended.
Beside ``window_task_busy_share`` (inside a turn) it says how much of a
turn is work: busy - cpu the thread stood still. A program from before
PR 53 has neither the clock nor the gauge and reads nothing."""

from benchmarks.harness.spec import BENCH_DIR, load_module

_part = load_module(BENCH_DIR, "readers", "stage_ring_part")


def read(run, params):
    task = _part.task_of(run, params["task"])
    end_cpu = getattr(getattr(task, "io_timers", None), "cpu_s", None)
    if end_cpu is None:
        return None
    key = ".".join((*task.ctx.metrics.group.scope, "cpuTimeRatio"))
    ratio_t0 = run.at_t0["metrics"].get(key)
    if ratio_t0 is None:
        return None
    elapsed_t0 = run.at_t0["time_s"] - run.at_end["job_started_s"]
    return 100.0 * (end_cpu - ratio_t0 * elapsed_t0) / run.window_s
