"""Reads one scatter-fold from the named region it runs under, whatever
program holds it: the device time of the operations whose JAX name path
matches ``params["region"]`` inside the executions of the program
matching ``params["module"]`` (what ``op_region_time`` finds: executions
whole inside the traced window, busiest device, the union of the matching
operations' intervals), as a mean over those executions.

  as "ms"         the region's device time an execution
  as "roofline"   100 x the least time the chip could take for the fold's
                  bytes (harness/fold_bytes.py over the published peak,
                  peaks.json; the cells touched counted from the first
                  timed batch's key column, as ``fold_roofline`` counts
                  them) / the region's device time an execution

A trace that holds no such program, or a program without the scope, reads
nothing."""

import numpy as np

from benchmarks.harness.device import device_block, peak
from benchmarks.harness.fold_bytes import scatter_fold_bytes
from benchmarks.harness.spec import BENCH_DIR, load_module

_region = load_module(BENCH_DIR, "readers", "op_region_time")


def read(run, params):
    found = _region.measured(run, params)
    if found is None:
        return None
    region_s, _module_s, executions = found
    seconds = region_s / executions
    if params["as"] == "ms":
        return seconds * 1e3
    if params["as"] == "roofline":
        model = params["roofline"]
        timed = run.schedule.phase("timed")
        sample = run.generator.columns(
            run.schedule.batch_index(timed.first_batch))
        touched = len(np.unique(sample[run.query.KEY_COLUMN]))
        nbytes = scatter_fold_bytes(
            run.schedule.batch_rows, model["value_bytes"],
            model["index_bytes"], touched, model["cell_bytes"])
        least = nbytes / peak(device_block()["kind"], model["peak"])
        return 100.0 * least / seconds
    raise ValueError(f"unknown reading {params['as']!r}")
