"""Nearest-rank median, over the windows that end in the timed phase, of
(stamp of the window's rows at the sink - the time the reader handed over
the batch that holds the window's last event): harness/latency.py says
what it holds and why the event-time percentiles are not judged."""

from benchmarks.harness.latency import nearest_rank, timed_source_to_sink_ms


def measure(run):
    sample = timed_source_to_sink_ms(run)
    return None if not sample else nearest_rank(sample, 50)
