"""The window fire against a per-record reference, at every width a ring
holds: a window fires ONE way a stack (the merge of its W ring rows), so
what has to hold is that the merge is exact at W = 1 (tumbling: one row,
no neighbour), W = 2 and W = ring - 1 (the widest: the fire gathers all
but one ring row, and the ring has one open pane) — over every aggregate
kind, top-k and full emission, ring wrap, late-but-open and late rows,
checkpoint and restore mid-window (synchronous and asynchronous fires),
the degraded CPU rung, and the mesh stack against one chip.

The streams use integer values on purpose: every aggregate is then exact
and the comparison is `==` on raw tuples."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from flink_tpu.core.config import Configuration  # noqa: E402
from flink_tpu.core.records import Schema  # noqa: E402
from flink_tpu.metrics import DEVICE_STATS  # noqa: E402
from flink_tpu.runtime import OneInputOperatorTestHarness  # noqa: E402
from flink_tpu.runtime.operators.device_window import (  # noqa: E402
    AggSpec, DeviceWindowAggOperator,
)
from flink_tpu.runtime.operators.mesh_window import (  # noqa: E402
    MeshWindowAggOperator,
)
from flink_tpu.window import SlidingEventTimeWindows  # noqa: E402

pytestmark = pytest.mark.perf

SCHEMA = Schema([("k", np.int64), ("v", np.int64)])
PANE, RING, STEPS = 1000, 8, 40

WIDTHS = pytest.mark.parametrize("window_panes", [1, 2, RING - 1],
                                 ids=["tumbling", "hop2", "widest"])


def _all_aggs():
    return [AggSpec("sum", "v", dtype=jnp.int64),
            AggSpec("count", dtype=jnp.int64),
            AggSpec("min", "v", dtype=jnp.int64),
            AggSpec("max", "v", dtype=jnp.int64),
            AggSpec("avg", "v", dtype=jnp.int64)]


def _make_op(window_panes, aggs=None, topk=None, **kw):
    return DeviceWindowAggOperator(
        SlidingEventTimeWindows.of(window_panes * PANE, PANE), "k",
        list(aggs if aggs is not None else _all_aggs()),
        capacity=128, ring_size=RING, emit_topk=topk, **kw)


def _stream(seed=7, keys=9):
    """A step a pane, a watermark behind every step (the widest window
    leaves the ring ONE open pane): rows that dip up to 1.5 panes behind
    the watermark (late-but-open at the widest, some late and dropped at
    W <= 2) and
    never pass the open pane, over enough panes to wrap the ring five
    times. [(keys, values, timestamps, watermark)]."""
    rng = np.random.default_rng(seed)
    out = []
    for step in range(STEPS):
        t = step * PANE
        n = int(rng.integers(1, 20))
        out.append((rng.integers(0, keys, n), rng.integers(-50, 50, n),
                    rng.integers(max(0, t - 1500), t + PANE, n),
                    t + PANE - 1))
    return out


def _feed(h, steps):
    for ks, vs, ts, wm in steps:
        h.process_elements(list(zip(ks.tolist(), vs.tolist())), ts.tolist())
        h.process_watermark(wm)


def _rows(*harnesses):
    """Everything emitted, as tuples of Python numbers, in order."""
    return [tuple(x.item() for x in r)
            for h in harnesses
            for b in h.output.batches if not hasattr(b, "timestamp")
            for r in zip(*[b.column(f.name) for f in b.schema.fields])]


def _reference(steps, window_panes, kinds=("sum", "count", "min", "max",
                                           "avg")):
    """Record by record, as Flink's WindowOperator at allowed lateness 0:
    a record joins every window of its pane that has not fired, and is
    late when all of them have. Returns (rows in the operator's order: by
    window end, then key; late records)."""
    open_windows, rows, late, fired = {}, [], 0, 0
    for ks, vs, ts, wm in steps:
        for k, v, t in zip(ks.tolist(), vs.tolist(), ts.tolist()):
            ends = [e for e in range(t // PANE + 1,
                                     t // PANE + 1 + window_panes)
                    if e >= fired]
            late += not ends
            for e in ends:
                open_windows.setdefault(e, {}).setdefault(k, []).append(v)
        fired = (wm + 1) // PANE + 1
        for e in sorted(e for e in open_windows if e < fired):
            for k, vals in sorted(open_windows.pop(e).items()):
                agg = {"sum": sum(vals), "count": len(vals),
                       "min": min(vals), "max": max(vals),
                       "avg": float(np.float32(sum(vals))
                                    / np.float32(len(vals)))}
                rows.append((k, (e - window_panes) * PANE, e * PANE)
                            + tuple(agg[kind] for kind in kinds))
    assert not open_windows     # the last watermark closes the stream
    return rows, late


def _closing(steps, window_panes):
    """``steps`` and a last watermark past every window."""
    return steps + [(np.zeros(0, np.int64),) * 3
                    + ((STEPS + window_panes) * PANE,)]


def _run(window_panes, **op_kw):
    h = OneInputOperatorTestHarness(_make_op(window_panes, **op_kw),
                                    schema=SCHEMA)
    _feed(h, _closing(_stream(), window_panes))
    h.close()
    return _rows(h), h.operator.late_dropped


@WIDTHS
def test_all_aggregates_equal_the_reference(window_panes):
    """sum/count/min/max/avg over a wrap-heavy stream with late records:
    rows, their order and the late count are the reference's."""
    want, late = _reference(_closing(_stream(), window_panes), window_panes)
    got, dropped = _run(window_panes)
    assert got == want and len(want) > STEPS
    assert dropped == late
    assert (late > 0) == (window_panes <= 2)


@WIDTHS
def test_topk_equals_the_reference(window_panes):
    """emit_topk ranks on the first aggregate and gathers the rest at the
    winners: every window emits min(k, its keys) rows, each the
    reference's, and no key left out outranks one that is in."""
    aggs = [AggSpec("count", dtype=jnp.int64, value_bits=31),
            AggSpec("sum", "v", dtype=jnp.int64)]
    got, _late = _run(window_panes, aggs=aggs, topk=3)
    want, _late = _reference(_closing(_stream(), window_panes),
                             window_panes, ("count", "sum"))
    by_window = {}
    for row in want:
        by_window.setdefault(row[2], []).append(row)
    assert {row[2] for row in got} == set(by_window)
    for end, rows in by_window.items():
        mine = [row for row in got if row[2] == end]
        assert len(mine) == min(3, len(rows)) and set(mine) <= set(rows)
        counts = [row[3] for row in mine]
        assert counts == sorted(counts, reverse=True)      # rank order
        assert min(counts) >= max(
            [row[3] for row in rows if row not in mine], default=0)


@WIDTHS
def test_min_and_max_alone_equal_the_reference(window_panes):
    """No invertible aggregate but the hidden count: the merge of min and
    max planes over identity-padded rows."""
    aggs = [AggSpec("min", "v", dtype=jnp.int64),
            AggSpec("max", "v", dtype=jnp.int64)]
    want, _late = _reference(_closing(_stream(), window_panes),
                             window_panes, ("min", "max"))
    assert _run(window_panes, aggs=aggs)[0] == want


@WIDTHS
@pytest.mark.parametrize("async_fire", [False, True], ids=["sync", "async"])
def test_checkpoint_and_restore_mid_window(window_panes, async_fire):
    """Snapshot mid-stream (open windows, a wrapped ring) and restore:
    the rows before the snapshot and the restored operator's are the
    uninterrupted reference's."""
    steps = _closing(_stream(), window_panes)
    want, late = _reference(steps, window_panes)
    h1 = OneInputOperatorTestHarness(
        _make_op(window_panes, async_fire=async_fire), schema=SCHEMA)
    _feed(h1, steps[:STEPS // 2])
    snap = h1.snapshot(1)
    h2 = OneInputOperatorTestHarness.restored(
        lambda: _make_op(window_panes, async_fire=async_fire), snap,
        schema=SCHEMA)
    _feed(h2, steps[STEPS // 2:])
    h2.close()
    assert _rows(h1, h2) == want
    # the count is a metric of each operator, not state
    assert h1.operator.late_dropped + h2.operator.late_dropped == late
    h1.close()


@WIDTHS
def test_the_degraded_cpu_rung_equals_the_reference(window_panes):
    """Mid-stream degradation evacuates the planes to the host rung's
    backend; the fire reads them there, and the rows stay exact."""
    steps = _closing(_stream(), window_panes)
    h = OneInputOperatorTestHarness(_make_op(window_panes), schema=SCHEMA)
    _feed(h, steps[:STEPS // 2])
    h.operator._degrade(RuntimeError("injected for test"))
    assert h.operator._degraded
    _feed(h, steps[STEPS // 2:])
    h.close()
    assert _rows(h) == _reference(steps, window_panes)[0]


@WIDTHS
def test_the_mesh_stack_equals_one_chip_and_the_reference(window_panes):
    """The same job through the mesh operator on four (virtual) devices:
    its fire merges the same W rows of every shard's planes."""
    steps = _closing(_stream(), window_panes)
    h = OneInputOperatorTestHarness(
        MeshWindowAggOperator(
            SlidingEventTimeWindows.of(window_panes * PANE, PANE), "k",
            _all_aggs(), n_devices=4, capacity=64, ring_size=RING,
            device_batch=8), schema=SCHEMA)
    _feed(h, steps)
    h.close()
    want, late = _reference(steps, window_panes)
    assert sorted(_rows(h)) == sorted(want) == sorted(_run(window_panes)[0])
    assert h.operator.late_dropped == late


def _drive(h, seed=7, steps=40, keys=9):
    """The stream of the coalescing test: out-of-order timestamps that dip
    up to 1.5 panes behind the watermark, a watermark every third batch
    (so that there is something to coalesce), HOP 5 s / 1 s."""
    rng = np.random.default_rng(seed)
    t = 0
    for step in range(steps):
        n = int(rng.integers(1, 20))
        ks = rng.integers(0, keys, n)
        vs = rng.integers(-50, 50, n)
        ts = rng.integers(max(0, t - 1500), t + 900, n)
        h.process_elements(list(zip(ks, vs)), list(ts))
        t += 700
        if step % 3 == 2:
            h.process_watermark(t)
    h.process_watermark(t + 20000)
    h.close()
    return _rows(h)


def test_coalesced_ingest_equivalence():
    """Coalescing merges consecutive same-schema batches host-side; the
    watermark flush keeps fire semantics exact, so output is identical
    and the merge counter moves. The job still sets the fire option the
    parent had (`Configuration.set` takes any key): nothing reads it."""
    ref = _drive(OneInputOperatorTestHarness(_make_op(5), schema=SCHEMA))
    cfg = (Configuration()
           .set("window.fire.incremental", True)
           .set("task.coalesce.target-records", 4096))
    before = DEVICE_STATS.snapshot().get("batches_coalesced_total", 0)
    out = _drive(OneInputOperatorTestHarness(_make_op(5), schema=SCHEMA,
                                             config=cfg))
    assert out == ref and len(ref) > 0
    assert DEVICE_STATS.snapshot().get("batches_coalesced_total", 0) > before
