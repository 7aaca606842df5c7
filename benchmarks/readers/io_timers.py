"""Reads ``TaskIOTimers`` of the task that holds the window operator:
(busy - backpressured) seconds over the timed phase / its length.

The timers are cumulative since the task started, so the reading at the
first timed batch comes from the job's metric registry (the gauge
``busyTimeRatio`` = (busy - backpressured) / elapsed, times the time since
the job started), and the reading at the end from the task itself."""


def read(run, params):
    timers = run.window_task.io_timers
    end_busy = max(0.0, timers.busy_s - timers.backpressured_s)
    ratio_key = next((k for k in run.at_t0["metrics"]
                      if k.endswith(".busyTimeRatio")
                      and _same_task(k, run)), None)
    if ratio_key is None:
        return None
    elapsed_t0 = run.at_t0["time_s"] - run.at_end["job_started_s"]
    busy_t0 = run.at_t0["metrics"][ratio_key] * elapsed_t0
    return 100.0 * (end_busy - busy_t0) / run.window_s


def _same_task(key: str, run) -> bool:
    """Registry keys read <job>.<vertex>.<subtask>.<gauge>; the window
    task is the one whose own final ratio the key reproduces."""
    final = run.at_end["metrics"].get(key)
    return final is not None and abs(
        final - run.window_task.io_timers.busy_ratio) < 1e-9
