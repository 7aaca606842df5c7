"""Reads the device time of XLA modules from the traced run.

A step (or a fire) may be several programs; ``anchor`` is the pattern of
the program that runs once per step, ``modules`` the patterns of all the
programs that belong to it. The value is the median over the executions
that lie whole inside the traced window, in ms, on the busiest device.

With ``roofline`` set the value is instead the share (in %) of the least
time the chip could take for the step's bytes (harness/bytes_model.py
over the published peak, peaks.json) in that median."""

import statistics

import numpy as np

from benchmarks.harness import trace as T
from benchmarks.harness.bytes_model import ingest_step_bytes, slot_bytes
from benchmarks.harness.device import device_block, peak
from benchmarks.harness.trace_summary import busiest_plane


def step_seconds(run, params):
    if run.trace is None:
        return None
    lo, hi = T.traced_window(run.trace)
    plane = busiest_plane(run.trace, lo, hi)
    if plane is None:
        return None
    groups = T.module_groups(plane, lo, hi, params["modules"],
                             params["anchor"], params.get("exclude", ()))
    return statistics.median(groups) if groups else None


def read(run, params):
    seconds = step_seconds(run, params)
    if seconds is None:
        return None
    if "roofline" not in params:
        return seconds * 1e3
    model = params["roofline"]
    data, state = run.config["data"], run.config["state"]
    timed = run.schedule.phase("timed")
    sample = run.generator.columns(
        run.schedule.batch_index(timed.first_batch))
    touched = len(np.unique(sample[run.query.KEY_COLUMN]))
    nbytes = ingest_step_bytes(
        run.schedule.batch_rows, int(data["record_bytes"]), touched,
        slot_bytes(state["key_bytes"], state["cell_bytes"]))
    least = nbytes / peak(device_block()["kind"], model["peak"])
    return 100.0 * least / seconds
