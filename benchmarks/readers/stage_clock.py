"""Reads how far the program's two records of its stage spans disagree
about time: a percentile (nearest rank; ``params["rank"]``, 100 for the
largest), in us, of the paired spans' distance from their median offset
(harness/stage_trace.clock_disagreement_ns). Over 200 us at the 90th the
readers of the stage spans refuse the trace."""

from benchmarks.harness import stage_trace as S


def read(run, params):
    traced = S.checked_trace(run, params)
    if traced is None or traced["clock_ns"] is None:
        return None
    return traced["clock_ns"][params["rank"]] / 1e3
