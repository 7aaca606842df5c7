"""The bid stream of ``bids.py`` with no hot set: every bid uniform over
all keys (``hot_share`` forced to 0, whatever the configuration says; the
hot auctions are still drawn, so the prefill and the cold bids are those
of ``bids.py`` letter for letter). The control for every skew, de-dup or
partitioner change: a tenant whose keys have no favourites."""

from __future__ import annotations

from benchmarks.harness.spec import BENCH_DIR, load_module

_bids = load_module(BENCH_DIR, "generators", "bids")

__all__ = ["make_generator"]


def make_generator(data: dict, prefill_rows: int, seed: int):
    return _bids.make_generator({**data, "hot_share": 0.0}, prefill_rows,
                                seed)
