"""Reads the session operator's share of its roofline from the traced
run: 100 x the least time the chip could take for the bytes the
SEMANTICS need (harness/session_bytes.py, from shapes and counts alone,
over the published peak, peaks.json) over the measured device time.

  of "step"   one batch's step (what ``trace_module_time`` reads with the
              same ``anchor`` / ``modules``); the distinct keys are
              counted from the first timed batch's key column
  of "fire"   one fire, all its rounds (what ``session_fire`` reads as
              "ms"); the sessions a fire takes are the program's counter
              (``session_fired_total`` over ``session_fires_total``)

The shapes are the configuration's: ``query.capacity`` slots, ``query.
lanes`` lanes, ``state.*``. A trace that holds no such program reads
nothing."""

import numpy as np

from benchmarks.harness.device import device_block, peak
from benchmarks.harness.session_bytes import session_fire_bytes, \
    session_step_bytes
from benchmarks.harness.spec import BENCH_DIR, load_module

_module_time = load_module(BENCH_DIR, "readers", "trace_module_time")
_fire = load_module(BENCH_DIR, "readers", "session_fire")


def read(run, params):
    query, state = run.config["query"], run.config["state"]
    cells = state["lane_cell_bytes"]
    if params["of"] == "step":
        seconds = _module_time.step_seconds(run, params)
        if not seconds:
            return None
        timed = run.schedule.phase("timed")
        sample = run.generator.columns(
            run.schedule.batch_index(timed.first_batch))
        nbytes = session_step_bytes(
            run.schedule.batch_rows, state["row_bytes"],
            len(np.unique(sample[run.query.KEY_COLUMN])),
            state["key_bytes"], cells)
    elif params["of"] == "fire":
        seconds = _fire.fire_seconds(run, params)
        fired = _fire.fired_per_fire(run)
        if not seconds or fired is None:
            return None
        nbytes = session_fire_bytes(
            query["capacity"], query["lanes"], fired, cells[1], cells[2],
            state["out_row_bytes"], cells)
    else:
        raise ValueError(f"unknown program {params['of']!r}")
    least = nbytes / peak(device_block()["kind"], params["peak"])
    return 100.0 * least / seconds
