"""Reads one of the program's ``DEVICE_STATS`` counters per unit of
another, over the timed phase: the growth of ``params["part"]`` between
the first timed batch and the end of the run, over the growth of
``params["whole"]``: what ``device_stats_share`` reads, without the
percent. A program that does not keep the counters, as every commit
before their PR, reads nothing."""

from benchmarks.harness.spec import BENCH_DIR, load_module

_share = load_module(BENCH_DIR, "readers", "device_stats_share")


def read(run, params):
    percent = _share.read(run, params)
    return None if percent is None else percent / 100.0
