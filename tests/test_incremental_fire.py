"""Incremental fire engine equivalence: `window.fire.incremental` must be
byte-identical to the full pane merge — same rows, same order — across
every aggregate kind (invertible running-window accumulators AND the
min/max merge trees), top-k and full emission, ring wrap, late-but-open
panes, checkpoint/restore mid-window (including a full-merge checkpoint
restored into an incremental operator: the derived planes are never
checkpointed, so the formats are identical), and the degraded CPU rung.

The streams below use integer aggregates and exactly-representable
values on purpose: for them the incremental subtraction is exact, so the
comparison is `==` on raw tuples, not approximate (float sum/avg is not
bit-stable across fire modes in general — see docs/PERFORMANCE.md)."""

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
from flink_tpu.window import SlidingEventTimeWindows  # noqa: E402

pytestmark = pytest.mark.perf

SCHEMA = Schema([("k", np.int64), ("v", np.int64)])

ALL_AGGS = [AggSpec("sum", "v", dtype=jnp.int64),
            AggSpec("count", dtype=jnp.int64),
            AggSpec("min", "v", dtype=jnp.int64),
            AggSpec("max", "v", dtype=jnp.int64),
            AggSpec("avg", "v", dtype=jnp.int64)]


def _make_op(inc, aggs=None, topk=None, ring=8, capacity=128,
             window=(5000, 1000)):
    return DeviceWindowAggOperator(
        SlidingEventTimeWindows.of(*window), "k",
        list(aggs if aggs is not None else ALL_AGGS),
        capacity=capacity, ring_size=ring, emit_topk=topk,
        fire_incremental=inc)


def _drive(h, seed=7, steps=40, keys=9, close=True):
    """Deterministic randomized stream: out-of-order timestamps that dip
    up to 1.5 panes behind the watermark (late-but-open panes writing
    into already-sealed panes — the `_note_open_ingest` rebuild trigger)
    and enough panes to wrap the 8-row ring several times."""
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
    if close:
        h.process_watermark(t + 20000)
    return t


def _rows(h):
    return [tuple(int(x) for x in r)
            for b in h.output.batches if not hasattr(b, "timestamp")
            for r in zip(*[b.column(f.name) for f in b.schema.fields])]


def _run(inc, config=None, **op_kw):
    h = OneInputOperatorTestHarness(_make_op(inc, **op_kw), schema=SCHEMA,
                                    config=config)
    _drive(h)
    out = _rows(h)
    h.close()
    return out


def test_equivalence_all_aggs():
    """sum/count/min/max/avg over a wrap-heavy late-record stream: both
    the invertible accumulators and the merge trees must reproduce the
    full merge byte for byte, and the incremental run must actually run
    incrementally (panes sealed, fewer pane rows read)."""
    full = _run(False)
    before = DEVICE_STATS.snapshot()
    inc = _run(True)
    after = DEVICE_STATS.snapshot()
    assert full == inc
    assert len(full) > 0
    assert after.get("panes_sealed_total", 0) > before.get(
        "panes_sealed_total", 0)


def test_equivalence_topk():
    """emit_topk fires rank on the first aggregate and gather the rest at
    the winners; the select is shared between modes, so tie handling
    cancels and rows must match exactly."""
    aggs = [AggSpec("count", dtype=jnp.int64, value_bits=31),
            AggSpec("sum", "v", dtype=jnp.int64)]
    full = _run(False, aggs=aggs, topk=3)
    inc = _run(True, aggs=aggs, topk=3)
    assert full == inc and len(full) > 0


def test_equivalence_minmax_only_tree_path():
    """A signature with no invertible aggregate but count: the fire view
    comes entirely from merge-tree roots."""
    aggs = [AggSpec("min", "v", dtype=jnp.int64),
            AggSpec("max", "v", dtype=jnp.int64)]
    assert _run(False, aggs=aggs) == _run(True, aggs=aggs)


@pytest.mark.parametrize("restore_inc", [True, False])
def test_checkpoint_restore_mid_window(restore_inc):
    """Snapshot mid-stream (open windows, sealed panes) and restore into
    EITHER fire mode: checkpoints carry only the authoritative pane
    planes (window-role derived state is excluded), so a full-merge
    checkpoint restores into an incremental operator — which marks
    itself dirty and rebuilds — and both continuations emit the same
    rows as the uninterrupted full-merge run."""
    ref = OneInputOperatorTestHarness(_make_op(False), schema=SCHEMA)
    _drive(ref)
    expect = _rows(ref)
    ref.close()

    h1 = OneInputOperatorTestHarness(_make_op(False), schema=SCHEMA)
    t_mid = _drive(h1, steps=20, close=False)
    head = _rows(h1)
    snap = h1.snapshot(1)
    h1.close()

    h2 = OneInputOperatorTestHarness.restored(
        lambda: _make_op(restore_inc), snap, schema=SCHEMA)
    # replay the tail of the same deterministic stream
    rng = np.random.default_rng(7)
    t = 0
    for step in range(40):
        n = int(rng.integers(1, 20))
        ks = rng.integers(0, 9, n)
        vs = rng.integers(-50, 50, n)
        ts = rng.integers(max(0, t - 1500), t + 900, n)
        if step >= 20:
            h2.process_elements(list(zip(ks, vs)), list(ts))
        t += 700
        if step % 3 == 2 and step >= 20:
            h2.process_watermark(t)
    h2.process_watermark(t + 20000)
    assert head + _rows(h2) == expect
    h2.close()


def test_incremental_checkpoint_restores_into_full():
    """The reverse direction: an incremental-mode snapshot restores into
    a full-merge operator with identical results."""
    ref = _run(False)
    h1 = OneInputOperatorTestHarness(_make_op(True), schema=SCHEMA)
    _drive(h1, steps=20, close=False)
    head = _rows(h1)
    snap = h1.snapshot(1)
    h1.close()
    h2 = OneInputOperatorTestHarness.restored(
        lambda: _make_op(False), snap, schema=SCHEMA)
    rng = np.random.default_rng(7)
    t = 0
    for step in range(40):
        n = int(rng.integers(1, 20))
        ks = rng.integers(0, 9, n)
        vs = rng.integers(-50, 50, n)
        ts = rng.integers(max(0, t - 1500), t + 900, n)
        if step >= 20:
            h2.process_elements(list(zip(ks, vs)), list(ts))
        t += 700
        if step % 3 == 2 and step >= 20:
            h2.process_watermark(t)
    h2.process_watermark(t + 20000)
    assert head + _rows(h2) == ref
    h2.close()


def test_degraded_cpu_rung_equivalence():
    """Mid-stream degradation to the host rung drops the derived planes
    with the rest of device state; the incremental engine rebuilds from
    the evacuated pane planes and the output stays byte-identical."""
    ref = _run(False)
    h = OneInputOperatorTestHarness(_make_op(True), schema=SCHEMA)
    rng = np.random.default_rng(7)
    t = 0
    for step in range(40):
        n = int(rng.integers(1, 20))
        ks = rng.integers(0, 9, n)
        vs = rng.integers(-50, 50, n)
        ts = rng.integers(max(0, t - 1500), t + 900, n)
        h.process_elements(list(zip(ks, vs)), list(ts))
        t += 700
        if step == 19:
            h.operator._degrade(RuntimeError("injected for test"))
            assert h.operator._degraded
        if step % 3 == 2:
            h.process_watermark(t)
    h.process_watermark(t + 20000)
    assert _rows(h) == ref
    h.close()


def test_config_enables_incremental():
    """fire_incremental=None defers to `window.fire.incremental`; the
    engine must actually engage (panes sealed) and stay equivalent."""
    cfg = Configuration().set("window.fire.incremental", True)
    h = OneInputOperatorTestHarness(_make_op(None), schema=SCHEMA,
                                    config=cfg)
    before = DEVICE_STATS.snapshot().get("panes_sealed_total", 0)
    _drive(h)
    out = _rows(h)
    h.close()
    assert h.operator._inc_enabled
    assert DEVICE_STATS.snapshot().get("panes_sealed_total", 0) > before
    assert out == _run(False)


def test_coalesced_ingest_equivalence():
    """Coalescing merges consecutive same-schema batches host-side; the
    watermark flush keeps fire semantics exact, so output is identical
    and the merge counter moves."""
    ref = _run(False)
    cfg = (Configuration()
           .set("window.fire.incremental", True)
           .set("task.coalesce.target-records", 4096))
    before = DEVICE_STATS.snapshot().get("batches_coalesced_total", 0)
    h = OneInputOperatorTestHarness(_make_op(None), schema=SCHEMA,
                                    config=cfg)
    _drive(h)
    out = _rows(h)
    h.close()
    assert out == ref
    assert DEVICE_STATS.snapshot().get("batches_coalesced_total", 0) > before


def test_mesh_inc_programs_match_full_merge():
    """Mesh-layer seal/rebuild/fire programs (jit+vmap only — no
    collectives) reproduce the full [D, rows, cap] pane merge exactly;
    runnable without a multi-chip runtime."""
    from flink_tpu.ops.hash_table import EMPTY_KEY, ensure_x64
    from flink_tpu.ops.segment_ops import (
        AGG_MERGES, INVERTIBLE_KINDS, make_accumulator, pow2_ceil,
    )
    from flink_tpu.parallel.mesh import make_mesh
    from flink_tpu.parallel.plan import MESH_RUNTIME
    from flink_tpu.parallel.sharded_window import (
        AggDef, ShardedWindowAgg, ShardedWindowState,
    )

    ensure_x64()
    agg = ShardedWindowAgg.__new__(ShardedWindowAgg)
    aggs = [AggDef("s", "sum", jnp.int64), AggDef("mn", "min", jnp.int64),
            AggDef("mx", "max", jnp.int64),
            AggDef("__count__", "count", jnp.int64)]
    D, cap, ring, W = 2, 16, 8, 5
    agg.aggs = aggs
    agg.capacity = cap
    agg.ring = ring
    agg.n_dev = D
    agg.mesh = make_mesh(D)          # the ranked fire selects per shard
    agg.plan = MESH_RUNTIME.plan(agg.mesh)
    agg._fire_variants = {}
    agg.tree_size = pow2_ceil(ring)
    agg.inv_sig = tuple((a.kind, a.name) for a in aggs
                        if a.kind in INVERTIBLE_KINDS)
    agg.tree_sig = tuple((a.kind, a.name) for a in aggs
                         if a.kind not in INVERTIBLE_KINDS)

    rng = np.random.default_rng(3)
    table = np.full((D, cap), EMPTY_KEY, np.int64)
    table[:, :6] = rng.integers(1, 1000, (D, 6))
    accs = {}
    for a in aggs:
        base = np.array(make_accumulator(a.kind, (D, ring, cap), a.dtype))
        base[:, :, :6] = rng.integers(0, 50, (D, ring, 6))
        accs[a.name] = jnp.asarray(base)
    state = ShardedWindowState(jnp.asarray(table), accs,
                               jnp.zeros(D, jnp.int64))

    def full_view(p_end, first):
        rows = [(p % ring) for p in range(first, p_end)]
        return {a.name: np.asarray(
            AGG_MERGES[a.kind](accs[a.name][:, rows, :], axis=1))
            for a in aggs}

    p_end, min_seen = 6, 1
    first = max(p_end - W, min_seen)
    rows = [(p % ring) for p in range(first, p_end)]
    L = agg.tree_size
    pane_rows = np.zeros(ring, np.int32)
    pane_rows[:len(rows)] = rows
    rows_valid = np.zeros(ring, bool)
    rows_valid[:len(rows)] = True
    pane_leaves = np.full(ring, L, np.int32)
    pane_leaves[:len(rows)] = [p % L for p in range(first, p_end)]
    view, wins, trees = agg.rebuild_inc(
        state, pane_rows, rows_valid, pane_leaves,
        np.int32((p_end - W) % ring), np.bool_(p_end - W >= min_seen))
    for name, ref in full_view(p_end, first).items():
        np.testing.assert_array_equal(np.asarray(view[name]), ref)

    for p_end in (7, 8):
        view, wins, trees = agg.seal_inc(
            state, wins, trees, np.int32((p_end - 1) % ring),
            np.int32((p_end - W) % ring), np.bool_(p_end - W >= min_seen),
            np.int32((p_end - 1) % L), np.int32((p_end - 1 - W) % L))
        for name, ref in full_view(p_end, max(p_end - W, min_seen)).items():
            np.testing.assert_array_equal(np.asarray(view[name]), ref)

    # the incremental fire consumes the view in both emit shapes
    agg.fire_inc(state, view, None, None)
    agg.fire_inc(state, view, "s", 4)
