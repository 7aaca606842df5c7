"""Reads how often a named region of a device program ran per execution
of that program, from the traced run: the operations whose JAX name path
(harness/op_paths: jit names, control flow, ``jax.named_scope``s)
matches ``params["region"]`` are counted inside each execution of the
program matching ``params["module"]``. With the region a loop body's one
defining operation, that is the loop's trip count. The value is the
median over the executions that lie whole inside the traced window, on
the busiest device."""

import statistics

from benchmarks.harness import op_paths as P
from benchmarks.harness import trace as T
from benchmarks.harness.trace_summary import busiest_plane


def runs(run, params):
    if run.trace is None:
        return None
    lo, hi = T.traced_window(run.trace)
    plane = busiest_plane(run.trace, lo, hi)
    if plane is None:
        return None
    found = P.load(plane["name"])
    if found is None:
        return None
    return P.region_runs(found["modules"], found["ops"], params["module"],
                         params["region"], lo, hi)


def read(run, params):
    counts = runs(run, params)
    if not counts:
        return None
    return statistics.median(counts)
