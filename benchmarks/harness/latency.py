"""Window latency arithmetic and nearest-rank percentiles.

A window ending at event time ``E`` (ms) has its last contributing event
due at ``origin + E / 1000`` on the host clock, because in a paced phase
event time IS the schedule. Two latencies are taken from the sink's stamp
of the window's rows:

  event-time latency   stamp - the time that last event was DUE. It holds
                       the event's wait for its batch to fill (the
                       reader hands a batch over when its last row is
                       due), the watermark, the step, the fire, the drain
                       and the sink, and excludes the window's length.
  source-to-sink       stamp - the time the reader HANDED OVER the batch
                       that holds that last event (the batch's due time):
                       the same without the wait for the batch to fill,
                       which is the schedule's arithmetic and not the
                       job's doing. What Flink's latency markers measure.

The first is a fixed ramp (the batch boundary drifts against the window
ends by the same step every slide) plus the second, so its percentiles
over a few dozen windows move in steps of that drift; the second is the
part the job decides (PERF.md section 6).
"""

from __future__ import annotations

import math
from typing import Iterable, Sequence

__all__ = ["nearest_rank", "window_latencies_ms", "timed_windows",
           "timed_event_time_latencies_ms", "timed_source_to_sink_ms"]


def nearest_rank(values: Sequence[float], pct: float) -> float:
    """Nearest-rank percentile: the smallest value with at least ``pct``
    percent of the sample at or below it. No interpolation, so the
    answer is always a measured value."""
    if not values:
        raise ValueError("percentile of an empty sample")
    if not 0 < pct <= 100:
        raise ValueError(f"percentile {pct} outside (0, 100]")
    ordered = sorted(values)
    rank = max(1, math.ceil(pct / 100.0 * len(ordered)))
    return ordered[rank - 1]


def window_latencies_ms(origin_s: float, stamps: dict[int, float],
                        window_ends_ms: Iterable[int]) -> list[float]:
    """Latency of each window in ``window_ends_ms`` (event-time ms) given
    the sink's host-clock stamp (seconds) of the batch that carried its
    rows. A window with no stamp never reached the sink and raises: a
    latency sample may not silently go missing."""
    out = []
    for end in window_ends_ms:
        if end not in stamps:
            raise KeyError(f"window ending at {end} ms never reached "
                           "the sink")
        out.append((stamps[end] - (origin_s + end / 1000.0)) * 1000.0)
    return out


def timed_windows(run) -> list[int]:
    """Ends (event-time ms) of the windows that end in the timed phase of
    a paced run; None where the timed phase is unthrottled (event time is
    not wall time there, so no latency exists)."""
    if run.traffic["pacing"] != "scheduled":
        return None
    return run.schedule.windows_ending_in(
        run.schedule.phase("timed"), run.query.pane_ms(run.config["query"]))


def timed_event_time_latencies_ms(run):
    ends = timed_windows(run)
    if not ends:
        return None
    return window_latencies_ms(run.origin_s, run.sink.window_stamps(), ends)


def timed_source_to_sink_ms(run):
    """Per window that ends in the timed phase: stamp of its rows at the
    sink - due time of the batch that holds its last event."""
    ends = timed_windows(run)
    if not ends:
        return None
    event_time = window_latencies_ms(run.origin_s, run.sink.window_stamps(),
                                     ends)
    sched = run.schedule
    return [lat - (sched.due_s(sched.closing_batch(end)) * 1000.0 - end)
            for lat, end in zip(event_time, ends)]
