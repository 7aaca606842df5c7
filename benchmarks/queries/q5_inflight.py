"""NEXmark Q5 over auction ids that advance (generators/bids_inflight.py):
the job, the comparison and the reference are ``q5.py``'s and
``q5_reference.py``'s, unchanged. Only the size of the reference's dense
per-auction arrays differs: the ids of a run reach far past the keys that
are ever in flight at once, so they are sized by ``data.id_space``."""

from __future__ import annotations

from benchmarks.harness.spec import BENCH_DIR, load_module

_q5 = load_module(BENCH_DIR, "queries", "q5")
globals().update({name: getattr(_q5, name) for name in _q5.__all__})

__all__ = list(_q5.__all__)


def make_reference(query: dict, data: dict, on_window):
    return _q5.make_reference(
        query, {**data, "n_keys": int(data["id_space"])}, on_window)
