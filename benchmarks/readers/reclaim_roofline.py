"""Reads a reclaim's share of its roofline from the traced run: 100 x the
least time the chip could take for the bytes a reclaim of the cell's
shapes must move (harness/reclaim_bytes.py over the published peak,
peaks.json) over the device time of one reclaim (what
``trace_module_time`` reads with the same ``anchor`` / ``modules``). The
shapes are the configuration's: ``query.capacity`` slots, ``query.
ring_size`` ring rows, ``state.key_bytes`` and ``state.cell_bytes``. A
trace that holds no reclaim reads nothing."""

from benchmarks.harness.device import device_block, peak
from benchmarks.harness.reclaim_bytes import reclaim_bytes
from benchmarks.harness.spec import BENCH_DIR, load_module

_module_time = load_module(BENCH_DIR, "readers", "trace_module_time")


def read(run, params):
    seconds = _module_time.step_seconds(run, params)
    if not seconds:
        return None
    query, state = run.config["query"], run.config["state"]
    nbytes = reclaim_bytes(query["capacity"], query["ring_size"],
                           state["key_bytes"], state["cell_bytes"])
    least = nbytes / peak(device_block()["kind"], params["peak"])
    return 100.0 * least / seconds
