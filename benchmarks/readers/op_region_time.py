"""Reads the device time of named regions of a device program from the
traced run: the operations whose JAX name path (harness/op_paths: jit
names, control flow, ``jax.named_scope``s) matches ``params["region"]``,
inside the executions of the program matching ``params["module"]`` that
lie whole inside the traced window, on the busiest device. Time is the
UNION of the matching operations' intervals, so an operation that only
wraps others under the same scope (a ``while``, a ``conditional``) does
not count twice.

  as "module_share"   100 x region time / the executions' own time
  as "roofline"       100 x the least time the chip could take for the
                      bytes one device has on the interconnect in a step
                      (harness/exchange_bytes.py over the published peak,
                      peaks.json) / region time per execution

A program without the scopes (every commit before they were written, or
an executable from a compile cache older than they are) reads nothing.
"""

import bisect
import re

import numpy as np

from benchmarks.harness import op_paths as P
from benchmarks.harness import trace as T
from benchmarks.harness.device import device_block, peak
from benchmarks.harness.exchange_bytes import exchange_step_bytes, \
    off_chip_rows
from benchmarks.harness.trace_summary import busiest_plane


def region_time(modules, ops, module: str, region: str, lo: float,
                hi: float) -> tuple[float, float, int]:
    """(seconds of the region, seconds of the program, executions) over
    the executions of the program matching ``module`` that lie whole
    inside [lo, hi] and are neither the first nor the last program of the
    recording. Programs run one at a time on a device, so an operation
    belongs to the execution it starts in; its interval is cut to it."""
    module_re, region_re = re.compile(module), re.compile(region)
    first_start = min((start for _n, start, _e in modules), default=0.0)
    last_end = max((end for _n, _s, end in modules), default=0.0)
    runs = sorted((start, end) for name, start, end in modules
                  if module_re.search(name) and lo <= start and end <= hi
                  and first_start < start and end < last_end)
    starts = [r[0] for r in runs]
    inside: list[tuple[float, float]] = []
    for path, a, b in ops:
        if not region_re.search(path):
            continue
        i = bisect.bisect_right(starts, a) - 1
        if i >= 0 and a < runs[i][1]:
            inside.append((a, min(b, runs[i][1])))
    return (T.union_s(inside), sum(b - a for a, b in runs) / 1e9,
            len(runs))


def measured(run, params):
    if run.trace is None:
        return None
    lo, hi = T.traced_window(run.trace)
    plane = busiest_plane(run.trace, lo, hi)
    if plane is None:
        return None
    found = P.load(plane["name"])
    if found is None:
        return None
    region_s, module_s, n = region_time(
        found["modules"], found["ops"], params["module"], params["region"],
        lo, hi)
    if n == 0 or region_s <= 0.0:
        return None
    return region_s, module_s, n


def interconnect_rows(run) -> int:
    """Rows the busiest link end of one block has on the interconnect:
    the most any device sends to, or receives from, the other devices, in
    the first timed batch. Where a row goes is the program's own routing
    (key group of the key's hash in the job's max-parallelism space,
    contiguous ranges of groups a device), as its public functions
    compute it."""
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
    sent, received = off_chip_rows(dest, n_dev, device_batch)
    return int(max(sent.max(), received.max()))


def read(run, params):
    found = measured(run, params)
    if found is None:
        return None
    region_s, module_s, n = found
    if params["as"] == "module_share":
        return 100.0 * region_s / module_s
    if params["as"] == "roofline":
        model = params["roofline"]
        nbytes = exchange_step_bytes(interconnect_rows(run),
                                     model["row_bytes"], model["flag_bytes"])
        least = 8.0 * nbytes / peak(device_block()["kind"], model["peak"])
        return 100.0 * least / (region_s / n)
    raise ValueError(f"unknown reading {params['as']!r}")
