"""Reads the device time of the session operator's fires from the traced
run. A fire is one or more ROUNDS, each an execution of the fire program
(``params["module"]``), with steps between them; the trace shows rounds,
the program's counters (``DEVICE_STATS`` ``session_fire_rounds_total``
over ``session_fires_total``, growth over the timed phase) say how many
make a fire.

  as "ms"      device time of one fire, all its rounds: the mean round
               that lies whole inside the traced window, busiest device,
               times the rounds a fire took
  as "share"   100 x the rounds' device time / the traced window

``fire_seconds`` and ``fired_per_fire`` serve the roofline reader. A
program without the fire program's name or the counters (every commit
before PR 43), and a recording that holds no whole round, read nothing.
"""

from benchmarks.harness import trace as T
from benchmarks.harness.spec import BENCH_DIR, load_module
from benchmarks.harness.trace_summary import busiest_plane

_ratio = load_module(BENCH_DIR, "readers", "device_stats_ratio")


def rounds(run, params):
    """(device seconds of each whole round, traced window seconds)."""
    if run.trace is None:
        return None
    lo, hi = T.traced_window(run.trace)
    plane = busiest_plane(run.trace, lo, hi)
    if plane is None:
        return None
    found = T.module_groups(plane, lo, hi, [params["module"]],
                            params["module"])
    return (found, (hi - lo) / 1e9) if found else None


def rounds_per_fire(run):
    return _ratio.read(run, {"part": "session_fire_rounds_total",
                             "whole": "session_fires_total"})


def fired_per_fire(run):
    return _ratio.read(run, {"part": "session_fired_total",
                             "whole": "session_fires_total"})


def fire_seconds(run, params):
    found, per_fire = rounds(run, params), rounds_per_fire(run)
    if found is None or not per_fire:
        return None
    return sum(found[0]) / len(found[0]) * per_fire


def read(run, params):
    if params["as"] == "ms":
        seconds = fire_seconds(run, params)
        return None if seconds is None else 1e3 * seconds
    if params["as"] == "share":
        found = rounds(run, params)
        return None if found is None else 100.0 * sum(found[0]) / found[1]
    raise ValueError(f"unknown reading {params['as']!r}")
