"""Reads the benchmark's own reader: how late each paced batch left
(emit time - due time), over the timed phase."""

from benchmarks.harness.latency import nearest_rank


def read(run, params):
    timed = run.schedule.phase("timed")
    lag = [v for v in run.reader.lag_ms[timed.first_batch:timed.end_batch]
           if v is not None]
    if not lag:
        return None
    return nearest_rank(lag, float(params.get("percentile", 95)))
