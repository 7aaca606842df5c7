"""Reads a sharded scatter-fold's share of its roofline from the traced
run: 100 x the least time ONE chip could take for the bytes its shard's
fold has to move in a step (harness/fold_bytes.py over the published
peak, peaks.json) over the device time a step of the operations under
``params["region"]`` inside the program ``params["module"]`` (what
``op_region_time`` finds: executions whole inside the traced window,
busiest device, the union of the matching operations' intervals).

The rows a shard folds are the rows of a block whose key group it owns,
wherever they started: the first timed batch's first ``n_devices x
device_batch`` rows, routed as the program routes them (key group of the
key's hash in the job's max-parallelism space, contiguous ranges of
groups a device, by its public functions); the cells a shard touches are
its distinct keys among them. The shard whose fold has the most bytes is
the one counted. A trace that holds no such program, or a program without
the scope, reads nothing."""

import numpy as np

from benchmarks.harness.device import device_block, peak
from benchmarks.harness.fold_bytes import scatter_fold_bytes
from benchmarks.harness.spec import BENCH_DIR, load_module

_region = load_module(BENCH_DIR, "readers", "op_region_time")


def shard_folds(run) -> list[tuple[int, int]]:
    """(rows, distinct keys) each shard folds of the first timed block."""
    from flink_tpu.core.keygroups import hash_batch, \
        key_groups_for_hash_batch
    from flink_tpu.parallel.mesh import shard_ranges

    q = run.config["query"]
    n_dev, device_batch = int(q["n_devices"]), int(q["device_batch"])
    timed = run.schedule.phase("timed")
    keys = run.generator.columns(run.schedule.batch_index(
        timed.first_batch))[run.query.KEY_COLUMN][:n_dev * device_batch]
    max_par = int(run.operator._max_parallelism)
    groups = key_groups_for_hash_batch(hash_batch(keys), max_par)
    starts = np.array([r.start for r in shard_ranges(max_par, n_dev)])
    dest = np.searchsorted(starts, groups, side="right") - 1
    return [(int((dest == d).sum()), len(np.unique(keys[dest == d])))
            for d in range(n_dev)]


def read(run, params):
    found = _region.measured(run, params)
    if found is None:
        return None
    region_s, _module_s, executions = found
    model = params["roofline"]
    nbytes = max(scatter_fold_bytes(rows, model["value_bytes"],
                                    model["index_bytes"], touched,
                                    model["cell_bytes"])
                 for rows, touched in shard_folds(run))
    least = nbytes / peak(device_block()["kind"], model["peak"])
    return 100.0 * least / (region_s / executions)
