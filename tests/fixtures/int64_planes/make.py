"""The job behind ``snapshots.npz``: two snapshots of a one-chip backend
whose 64-bit ring planes the PARENT of PR 42 (commit 1f66f4f) stored as
int64 arrays. ``tests/test_checkpoint_format.py`` restores them into the
backend as it is now, and runs ``run_job`` again to hold today's
snapshots to the same bytes.

The file was written ONCE, from a checkout of that commit:

    JAX_PLATFORMS=cpu PYTHONPATH=<checkout of 1f66f4f> \\
        python tests/fixtures/int64_planes/make.py

It is not to be written again from a later tree: a layout change that
alters a snapshot's bytes needs a format version, not a new fixture.
"""

import os

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
RING = 4
#: (state name, kind, dtype): one narrow plane beside three 64-bit ones
PLANES = (("__count__", "count", np.int32), ("revenue", "sum", np.int64),
          ("best", "max", np.int64), ("least", "min", np.int64))


def run_job(backend_cls, between=None) -> list:
    """A full snapshot, then (after more folds and a retired ring row)
    an incremental one over the dirty blocks. ``between(backend)`` runs
    between the two."""
    from flink_tpu.core.keygroups import KeyGroupRange

    rng = np.random.default_rng(42)
    be = backend_cls(KeyGroupRange(0, 127), 128, capacity=2048)
    for name, kind, dtype in PLANES:
        be.register_array_state(name, kind, dtype, ring=RING)
    keys = rng.choice(1 << 40, size=600, replace=False).astype(np.int64) \
        - (1 << 39)

    def bid(sel, rows):
        k = keys[sel]
        # sums carry across 2^32, in both directions; values of either sign
        price = rng.integers(-(1 << 45), 1 << 45, size=len(k))
        slots = be.slots_for_batch(k)
        be.fold_rings(slots, rng.integers(*rows, size=len(k)), slots >= 0,
                      {"__count__": None, "revenue": price, "best": price,
                       "least": price})

    for _ in range(3):
        bid(slice(0, 400), (0, RING))
    snaps = [be.snapshot(1)]
    if between is not None:
        between(be)
    be.reset_ring_row(1)
    bid(slice(350, 600), (2, RING))
    snaps.append(be.snapshot(2))
    return snaps


def flatten(snaps: list) -> dict:
    """The snapshots' arrays under flat names, for one ``.npz``."""
    out = {}
    for i, snap in enumerate(snaps):
        out[f"{i}/keys"] = snap["keys"]
        out[f"{i}/key_groups"] = snap["key_groups"]
        for name, st in snap["states"].items():
            out[f"{i}/states/{name}"] = st["values"]
    return out


if __name__ == "__main__":
    from flink_tpu.ops.hash_table import ensure_x64
    from flink_tpu.state.tpu_backend import TpuKeyedStateBackend

    ensure_x64()
    np.savez_compressed(os.path.join(HERE, "snapshots.npz"),
                        **flatten(run_job(TpuKeyedStateBackend)))
