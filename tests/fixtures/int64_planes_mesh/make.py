"""The job behind ``snapshots.npz``: two snapshots of a mesh window
operator on four (virtual) devices whose 64-bit ring planes the PARENT of
PR 44 (commit 4081619) kept as int64 arrays ``[D, ring, capacity]``
(entries ``0/`` and ``1/``), and what the parent snapshotted straight
after restoring each of them onto four devices (``2/`` and ``3/``: a
restore re-inserts the keys, so their order is the restored tables').
``tests/test_mesh_halves.py`` restores them into the operator as it is
now (the planes kept as their two uint32 words) and runs ``run_job``
and ``restored_snapshot`` again to hold today's snapshots to the same
bytes.

The file was written ONCE, from a checkout of that commit:

    JAX_PLATFORMS=cpu PYTHONPATH=<checkout of 4081619> \\
        python tests/fixtures/int64_planes_mesh/make.py

It is not to be written again from a later tree: a layout change that
alters a snapshot's bytes needs a format version, not a new fixture.
"""

import json
import os

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
D, RING, PANE, SIZE = 4, 8, 250, 1000
FIELDS = (("key", np.int64), ("v", np.int64), ("w", np.int64),
          ("f", np.float32))
#: (plane, kind, dtype): an int64 SUM that carries across 2^32 both ways,
#: an int64 MAX over negative values and the hidden count the operator
#: adds (three planes of 64-bit integers), beside a float32 MIN
PLANES = (("total", "sum", np.int64), ("high", "max", np.int64),
          ("low", "min", np.float32), ("__count__", "count", np.int64))
#: the batches fed before the first snapshot and before the second
CUTS = (9, 14)


def schema():
    from flink_tpu.core.records import Schema

    return Schema(list(FIELDS))


def make_op(n_devices: int = D, capacity: int = 1 << 8, size: int = SIZE,
            **kw):
    from flink_tpu.runtime.operators.device_window import AggSpec
    from flink_tpu.runtime.operators.mesh_window import \
        MeshWindowAggOperator
    from flink_tpu.window import SlidingEventTimeWindows

    kw.setdefault("device_batch", 32)
    return MeshWindowAggOperator(
        SlidingEventTimeWindows.of(size, PANE), "key",
        [AggSpec("sum", "v", out_name="total"),
         AggSpec("max", "w", out_name="high"),
         AggSpec("min", "f", out_name="low")],
        n_devices=n_devices, capacity=capacity, ring_size=RING,
        emit_window_bounds=True, **kw)


def batches(n: int, rows: int = 128, in_flight: int = 400, born: int = 32,
            seed: int = 44) -> list:
    """One batch a pane, in event-time order: half its rows on the newest
    key, half uniform over the ``in_flight`` newest, which advance
    ``born`` a batch (so old keys retire and the tables reclaim). Keys of
    either sign; ``v`` of either sign and up to 2^45, ``w`` always
    negative, ``f`` float32."""
    rng = np.random.default_rng(seed)
    out = []
    for b in range(n):
        last = in_flight + b * born
        keys = np.where(rng.random(rows) < 0.5, last,
                        rng.integers(last - in_flight, last + 1, rows)) - 500
        ts = b * PANE + np.sort(rng.integers(0, PANE, rows))
        out.append(({"key": keys.astype(np.int64),
                     "v": rng.integers(-(1 << 45), 1 << 45, rows),
                     "w": -rng.integers(1, 1 << 50, rows),
                     "f": rng.normal(0, 1e3, rows).astype(np.float32)},
                    ts.astype(np.int64)))
    return out


def feed(h, some: list, first: int = 0) -> None:
    from flink_tpu.core.records import RecordBatch

    for i, (cols, ts) in enumerate(some, first):
        h.process_batch(RecordBatch(schema(), cols, ts))
        h.process_watermark((i + 1) * PANE - 1)


def run_job(**kw) -> tuple:
    """(the harness, [snapshot after CUTS[0] batches, after CUTS[1]])."""
    from flink_tpu.runtime import OneInputOperatorTestHarness

    data = batches(CUTS[1])
    h = OneInputOperatorTestHarness(make_op(**kw), schema=schema())
    feed(h, data[:CUTS[0]])
    snaps = [h.snapshot(1)]
    feed(h, data[CUTS[0]:], first=CUTS[0])
    snaps.append(h.snapshot(2))
    return h, snaps


def restored_snapshot(snap: dict, n_devices: int = D,
                      capacity: int = 1 << 8) -> tuple:
    """(a harness whose operator starts from ``snap``, the snapshot it
    takes before any input)."""
    from flink_tpu.runtime import OneInputOperatorTestHarness

    h = OneInputOperatorTestHarness.restored(
        lambda: make_op(n_devices, capacity=capacity), snap, schema=schema())
    return h, h.snapshot(7)


def flatten(snaps: list) -> dict:
    """The snapshots' arrays under flat names, for one ``.npz``; the
    control plane's four scalars as JSON."""
    out = {}
    for i, snap in enumerate(snaps):
        backend = snap["keyed"]["backend"]
        out[f"{i}/meta"] = np.array(json.dumps(snap["keyed"]["meta"]))
        out[f"{i}/keys"] = backend["keys"]
        out[f"{i}/key_groups"] = backend["key_groups"]
        for name, st in backend["states"].items():
            out[f"{i}/states/{name}"] = st["values"]
    return out


def unflatten(flat: dict, which: int) -> dict:
    """Snapshot ``which`` of a flattened file, as the operator wrote it."""
    return {"keyed": {
        "meta": json.loads(str(flat[f"{which}/meta"])),
        "backend": {
            "kind": "tpu", "max_parallelism": 128,
            "keys": flat[f"{which}/keys"],
            "key_groups": flat[f"{which}/key_groups"],
            "states": {name: {
                "kind": kind, "dtype": str(np.dtype(dtype)), "ring": RING,
                "values": flat[f"{which}/states/{name}"]}
                for name, kind, dtype in PLANES}}}}


if __name__ == "__main__":
    os.environ.setdefault("XLA_FLAGS",
                          "--xla_force_host_platform_device_count=8")
    from flink_tpu.ops.hash_table import ensure_x64

    ensure_x64()
    written = flatten(run_job()[1])
    again = [restored_snapshot(unflatten(written, which))[1]
             for which in (0, 1)]
    written.update({f"{2 + int(name[0])}{name[1:]}": values
                    for name, values in flatten(again).items()})
    np.savez_compressed(os.path.join(HERE, "snapshots.npz"), **written)
