"""Reads one scatter-fold's share of its roofline from the traced run:
100 x the least time the chip could take for the fold's bytes
(harness/fold_bytes.py over the published peak, peaks.json) over the
device time of the fold's program a batch (what ``trace_module_time``
reads with the same ``anchor`` / ``modules``). The cells touched are
counted from the first timed batch's key column, as
``trace_module_time`` counts them for the step's roofline. A trace that
holds no such program reads nothing."""

import numpy as np

from benchmarks.harness.device import device_block, peak
from benchmarks.harness.fold_bytes import scatter_fold_bytes
from benchmarks.harness.spec import BENCH_DIR, load_module

_module_time = load_module(BENCH_DIR, "readers", "trace_module_time")


def read(run, params):
    seconds = _module_time.step_seconds(run, params)
    if not seconds:
        return None
    timed = run.schedule.phase("timed")
    sample = run.generator.columns(
        run.schedule.batch_index(timed.first_batch))
    touched = len(np.unique(sample[run.query.KEY_COLUMN]))
    nbytes = scatter_fold_bytes(
        run.schedule.batch_rows, params["value_bytes"],
        params["index_bytes"], touched, params["cell_bytes"])
    least = nbytes / peak(device_block()["kind"], params["peak"])
    return 100.0 * least / seconds
