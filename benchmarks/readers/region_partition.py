"""Reads the device time of named REGIONS of a step's (a fire's, a
reclaim's) programs from the traced run, as parts of a partition
(harness/region_map.py): inside the executions of the group's programs
that lie whole in the traced window, busiest device, every instant
belongs to the shortest operation that covers it, and the program's own
map (``flink_tpu.metrics.device.program_regions``, paired by module name
and fingerprint) says what region that operation is; what no operation
covers, and what no map names, is ``unnamed``. A group is described as
``trace_module_time`` describes it (``anchor``, ``modules``, ``exclude``);
``eager`` gives the programs that no map holds (the eager slices of the
packed upload) a region by module name.

  as "ms"      the regions' device time, MEAN over the groups (not a
               median: the parts of one partition must add up)
  as "share"   100 x the regions' device time / the groups' device time

A program without ``program_regions`` (every commit before PR 37), a
trace whose programs the maps cannot be paired with, and a recording
that holds no whole group read nothing.
"""

from benchmarks.harness import region_map as R


def read(run, params):
    found = R.measured(run.trace, params)
    if found is None:
        return None
    totals, seconds, groups = found
    region_s = sum(totals.get(region, 0.0) for region in params["regions"])
    if params["as"] == "ms":
        return 1e3 * region_s / groups
    if params["as"] == "share":
        return 100.0 * region_s / seconds
    raise ValueError(f"unknown reading {params['as']!r}")
