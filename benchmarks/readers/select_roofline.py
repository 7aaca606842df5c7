"""Reads the threshold select's share of its roofline from the traced
run: 100 x the least time one chip could take to read its shard's slots
once a compare-and-count pass (``query.capacity`` slots x ``params[
"slot_bytes"]`` x the passes a fire's select walked, over the published
peak, peaks.json) over the device time a fire of the regions ``params[
"fire"]["regions"]`` (what ``region_partition`` reads as "ms": mean over
the fires whole inside the traced window, busiest device).

The passes are the program's own count (``DEVICE_STATS`` ``fire_select_
passes_total`` over ``fire_selects_total``, the longest shard's a fire,
over the timed phase). The region holds more than the passes (the max
that finds the top bit, the winners' compaction, the merge of the shards'
candidates and the gathers of the winners' rows), so the share reads
low, never high. A program without the counters or the regions reads
nothing."""

from benchmarks.harness.device import device_block, peak
from benchmarks.harness.spec import BENCH_DIR, load_module

_ratio = load_module(BENCH_DIR, "readers", "device_stats_ratio")
_partition = load_module(BENCH_DIR, "readers", "region_partition")


def read(run, params):
    passes = _ratio.read(run, {"part": "fire_select_passes_total",
                               "whole": "fire_selects_total"})
    fire_ms = _partition.read(run, {**params["fire"], "as": "ms"})
    if not passes or not fire_ms:
        return None
    nbytes = (passes * int(run.config["query"]["capacity"])
              * int(params["slot_bytes"]))
    least = nbytes / peak(device_block()["kind"], params["peak"])
    return 100.0 * least / (fire_ms / 1e3)
