"""Reads the event-time latencies of the windows that end in the timed
phase (harness/latency.py: stamp of a window's rows at the sink - the time
its last event was due) and reduces them to the mean or a nearest-rank
percentile. Observed beside the judged source-to-sink median, never
judged: over 24 windows they move in steps (PERF.md section 6)."""

import statistics

from benchmarks.harness import latency


def read(run, params):
    sample = latency.timed_event_time_latencies_ms(run)
    if not sample:
        return None
    if params["statistic"] == "mean":
        return statistics.fmean(sample)
    if params["statistic"] == "percentile":
        return latency.nearest_rank(sample, float(params["percentile"]))
    raise ValueError(f"unknown statistic {params['statistic']!r}")
