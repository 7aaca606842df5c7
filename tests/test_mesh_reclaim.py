"""State that follows the windows, on a mesh: the sharded state RECLAIMS
before it grows (``ShardedWindowAgg.reclaim``, ``MeshWindowAggOperator``;
PR 41).

ONE donated program under ``shard_map`` in which every shard runs the
one-chip backend's reclaim (``state/tpu_backend.reclaim_shard``) over its
own table and planes. The program is held to the one-chip program shard
by shard, bit for bit, and to a numpy model; the operator, on a stream
whose keys advance, to a numpy reference of the sliding windows; the job,
through ``env.execute()`` on NEXmark's own key distribution at rehearsal
size (``nexmark-q5-inflight-mesh4``), to ``Q5Reference`` row for row.
Runs on the virtual CPU devices of ``conftest.py``.
"""

import json
import shutil

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness.cell import run_cell
from benchmarks.harness.spec import BENCH_DIR, REPO_ROOT, load_spec
from flink_tpu.core.records import RecordBatch, Schema
from flink_tpu.metrics import DEVICE_STATS
from flink_tpu.metrics.tracing import TRACER
from flink_tpu.ops.hash_table import EMPTY_KEY, lookup
from flink_tpu.ops.segment_ops import AGG_INITS, Halves
from flink_tpu.parallel.mesh import make_mesh
from flink_tpu.parallel.sharded_window import AggDef, ShardedWindowAgg
from flink_tpu.runtime import OneInputOperatorTestHarness
from flink_tpu.runtime.operators.device_window import AggSpec
from flink_tpu.runtime.operators.mesh_window import MeshWindowAggOperator
from flink_tpu.state.tpu_backend import _reclaim_program
from flink_tpu.window import SlidingEventTimeWindows

CELL = "q5-inflight-mesh4-saturated"
CONFIG = "nexmark-q5-inflight-mesh4"
SEED = 3_000_000_019          # over 2^31, as the driver's are
RECLAIM = ("state_reclaim_sweeps_total", "state_reclaim_keys_kept_total",
           "state_reclaim_keys_freed_total")
D, RING = 4, 8
AGGS = (AggDef("bids", "count", jnp.int64), AggDef("revenue", "sum",
                                                   jnp.int64),
        AggDef("best", "max", jnp.int64))


# -- (a) the program, shard by shard ---------------------------------------

def _seeded_state(capacity: int, seed: int):
    """A sharded state past load 0.6 whose keys hold data in panes 0-5,
    most of them only in panes that have since been retired (0-3)."""
    agg = ShardedWindowAgg(make_mesh(D), list(AGGS), capacity=capacity,
                           ring=RING)
    state = agg.init_state()
    rng = np.random.default_rng(seed)
    B = 32
    n_keys = int(0.66 * capacity * D) // (D * B) * (D * B)
    keys = rng.permutation(n_keys).astype(np.int64) - n_keys // 3
    # every key once in panes 0-3, then a third of them again in 4-5
    for lo, n, panes in ((0, n_keys, (0, 4)), (0, n_keys // 3, (4, 6))):
        for start in range(lo, lo + n - D * B + 1, D * B):
            price = rng.integers(1, 1 << 40, (D, B))
            state, _n, _r, _limbs = agg.step(
                state, jnp.asarray(keys[start:start + D * B].reshape(D, B)),
                {"revenue": jnp.asarray(price), "best": jnp.asarray(price)},
                jnp.asarray(rng.integers(*panes, (D, B))),
                jnp.ones((D, B), bool))
    for row in range(4):
        state = agg.retire_row(state, row)
    return agg, state


@pytest.fixture(scope="module", params=[1 << 9, 1 << 11])
def reclaimed(request):
    agg, state = _seeded_state(request.param, seed=request.param)
    # every plane here is a 64-bit integer's: the state keeps its words
    assert all(isinstance(p, Halves) for p in state.accs.values())
    before = _on_host(state)
    new, counts = agg.reclaim(state)        # ``state`` is donated
    assert all(isinstance(p, Halves) for p in new.accs.values())
    return agg, before, _on_host(new), np.asarray(counts)


def _on_host(state):
    """``state`` as numpy, each plane joined to the int64 values it holds."""
    host = jax.device_get(state)
    return host._replace(accs={n: np.asarray(p)
                               for n, p in host.accs.items()})


def test_the_sharded_reclaim_equals_the_one_chip_reclaim_on_each_shard(
        reclaimed):
    agg, before, after, counts = reclaimed
    # the one-chip backend's own program and layout: a 64-bit plane as
    # its two words (signed ``halves:int64``)
    sig = tuple((a.kind, "halves:" + np.dtype(a.dtype).name,
                 (RING, agg.capacity)) for a in AGGS)
    one_chip = _reclaim_program(sig)
    assert counts.shape == (D, 2) and counts.dtype == np.int32
    for d in range(D):
        table, planes, dropped, kept_freed = one_chip(
            jnp.asarray(before.table[d]),
            tuple(Halves.split(jnp.asarray(before.accs[a.name][d]))
                  for a in AGGS),
            jnp.asarray(before.dropped[d]))
        assert (np.asarray(table) == after.table[d]).all(), d
        for a, plane in zip(AGGS, planes):
            assert (np.asarray(plane) == after.accs[a.name][d]).all(), \
                (d, a.name)
        assert int(dropped) == int(after.dropped[d]) == 0
        assert (np.asarray(kept_freed) == counts[d]).all()


def test_every_live_key_keeps_every_cell_and_no_dead_key_keeps_a_slot(
        reclaimed):
    agg, before, after, counts = reclaimed
    idents = {a.name: np.asarray(AGG_INITS[a.kind](jnp.dtype(a.dtype)))
              for a in AGGS}
    for d in range(D):
        occupied = before.table[d] != EMPTY_KEY
        live = occupied & (before.accs["bids"][d] != 0).any(axis=0)
        assert 0 < live.sum() < 0.3 * agg.capacity < 0.55 * agg.capacity \
            < occupied.sum()
        assert counts[d].tolist() == [live.sum(), (occupied & ~live).sum()]
        keys = before.table[d][occupied]
        slots = np.asarray(lookup(jnp.asarray(after.table[d]),
                                  jnp.asarray(keys)))
        was = np.flatnonzero(occupied)
        assert ((slots >= 0) == live[was]).all()
        for name, ident in idents.items():
            old, new = before.accs[name][d], after.accs[name][d]
            assert (new[:, slots[slots >= 0]]
                    == old[:, was[slots >= 0]]).all(), (d, name)
            # a freed slot holds the identity in every plane
            assert (new[:, after.table[d] == EMPTY_KEY] == ident).all()
        assert int((after.table[d] != EMPTY_KEY).sum()) == live.sum()


# -- (b) the operator on a stream whose keys advance ------------------------

SCHEMA = Schema([("key", np.int64), ("v", np.int64)])
PANE, SIZE, OP_RING = 250, 1000, 16


def _advancing(n_batches: int, rows: int = 128, in_flight: int = 400,
               born: int = 32, seed: int = 0):
    """One batch a pane: half its rows on the newest id, half uniform
    over the ``in_flight`` newest ids, which advance ``born`` a batch."""
    rng = np.random.default_rng(seed)
    out = []
    for b in range(n_batches):
        last = in_flight + b * born
        keys = np.where(rng.random(rows) < 0.5, last,
                        rng.integers(last - in_flight, last + 1, rows))
        ts = b * PANE + np.sort(rng.integers(0, PANE, rows))
        out.append((keys.astype(np.int64),
                    rng.integers(1, 1 << 40, rows), ts.astype(np.int64)))
    return out


def _op(n_devices: int = D, capacity: int = 1 << 8, size: int = SIZE,
        **kw):
    kw.setdefault("device_batch", 64)
    return MeshWindowAggOperator(
        SlidingEventTimeWindows.of(size, PANE), "key",
        [AggSpec("sum", "v", out_name="result")], n_devices=n_devices,
        capacity=capacity, ring_size=OP_RING, emit_window_bounds=True, **kw)


def _feed(h, batches, first: int = 0):
    for i, (keys, vals, ts) in enumerate(batches, first):
        h.process_batch(RecordBatch(SCHEMA, {"key": keys, "v": vals}, ts))
        h.process_watermark((i + 1) * PANE - 1)


def _rows(*harnesses):
    return sorted((int(k), int(s), int(e), int(v))
                  for h in harnesses for k, s, e, v in h.get_output())


def _reference(batches, size: int = SIZE):
    keys, vals, ts = (np.concatenate(c) for c in zip(*batches))
    out = []
    for end in range(PANE, int(ts.max()) + size + 1, PANE):
        sel = (ts >= end - size) & (ts < end)
        for k in np.unique(keys[sel]).tolist():
            out.append((k, end - size, end,
                        int(vals[sel & (keys == k)].sum())))
    return sorted(out)


def _sweeps() -> int:
    return DEVICE_STATS.snapshot()["state_reclaim_sweeps_total"]


@pytest.mark.parametrize("window_panes,capacity,n_batches", [
    (SIZE // PANE, 1 << 8, 40), (OP_RING - 1, 1 << 9, 80)],
    ids=["hop4", "widest"])
def test_the_operator_reclaims_and_every_window_is_still_exact(
        window_panes, capacity, n_batches):
    """HOP 4 panes, and the widest window the ring holds (15 panes: a
    key lives four times as long, so the tables are twice the size and
    the stream twice as long)."""
    batches = _advancing(n_batches)
    size = window_panes * PANE
    before = DEVICE_STATS.snapshot()
    h = OneInputOperatorTestHarness(
        _op(async_fire=True, size=size, capacity=capacity), schema=SCHEMA)
    _feed(h, batches)
    h.process_watermark(10**9)
    h.operator.finish()
    after = DEVICE_STATS.snapshot()
    assert _rows(h) == _reference(batches, size)
    op = h.operator
    assert op._agg.capacity == capacity and op.late_dropped == 0
    assert after[RECLAIM[0]] - before[RECLAIM[0]] >= 2
    assert after[RECLAIM[2]] - before[RECLAIM[2]] > 0
    # more ids were bid on than the four tables hold under their limit
    assert len(np.unique(np.concatenate([b[0] for b in batches]))) \
        > 0.6 * D * capacity


@pytest.mark.parametrize("n_after", [4, 2])
def test_a_snapshot_across_a_reclaim_restores_exactly(n_after):
    """The barrier meets a reclaim in flight: the snapshot settles it
    (stage, counters) and holds the reclaimed state; the restore, also
    onto another mesh size, goes on from it exactly."""
    batches = _advancing(30, seed=1)
    h1 = OneInputOperatorTestHarness(_op(async_fire=True), schema=SCHEMA)
    _feed(h1, batches[:14])
    op = h1.operator
    sweeps = _sweeps()
    op._reclaim()                            # dispatched, not waited for
    assert op._reclaiming is not None and _sweeps() == sweeps
    snap = h1.snapshot(1)
    assert op._reclaiming is None and _sweeps() == sweeps + 1
    table = np.asarray(op._state.table)
    assert len(snap["keyed"]["backend"]["keys"]) \
        == int((table != EMPTY_KEY).sum())
    h2 = OneInputOperatorTestHarness.restored(
        lambda: _op(n_after, capacity=1 << (8 if n_after == 4 else 9),
                    async_fire=True), snap, schema=SCHEMA)
    _feed(h2, batches[14:], first=14)
    h2.process_watermark(10**9)
    h2.operator.finish()
    assert _rows(h1, h2) == _reference(batches)
    assert h2.operator._n_devices == n_after


def test_nothing_is_compiled_once_the_first_reclaim_has_been_prepared():
    """The reclaim's program is built with the state (``_build``), before
    any input; the reclaims of the run then build nothing."""
    from jax._src import monitoring

    builds = []

    def on_duration(event, _seconds, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            builds.append(event)

    batches = _advancing(36, seed=2)
    h = OneInputOperatorTestHarness(_op(device_batch=32), schema=SCHEMA)
    _feed(h, batches[:8])                    # step, fire, retire, probe
    program = h.operator._agg._reclaim
    assert program._compiled and _sweeps() is not None
    sweeps = _sweeps()
    monitoring.register_event_duration_secs_listener(on_duration)
    try:
        _feed(h, batches[8:], first=8)
    finally:
        monitoring.unregister_event_duration_listener(on_duration)
    assert _sweeps() - sweeps >= 2 and builds == []
    h.process_watermark(10**9)
    h.operator.finish()
    assert _rows(h) == _reference(batches)


def test_the_counts_arrive_without_a_host_wait_in_the_step_loop():
    """A reclaim is dispatched and the operator goes on stepping; its
    counts are taken in by a later turn that finds them landed, never by
    one that waits (the headroom to the load ceiling is dozens of blocks
    once two readings have shown the table's pace)."""
    cap = 1 << 12
    in_flight = int(0.57 * cap * D)
    batches = _advancing(80, rows=256, in_flight=in_flight, born=64, seed=3)
    op = _op(capacity=cap, async_fire=True)
    waited, took = [], []
    finish = op._finish_reclaim

    def spy(block=False, grow=True):
        pending = op._reclaiming is not None
        if pending and block:
            waited.append(op._block_seq)
        finish(block, grow)
        if pending and op._reclaiming is None:
            took.append(op._block_seq)

    op._finish_reclaim = spy
    h = OneInputOperatorTestHarness(op, schema=SCHEMA)
    prefill = np.arange(in_flight, dtype=np.int64)
    h.process_batch(RecordBatch(SCHEMA, {"key": prefill,
                                         "v": np.ones_like(prefill)},
                                np.zeros_like(prefill)))
    sweeps = _sweeps()
    _feed(h, batches)
    assert _sweeps() - sweeps >= 1 and took and not waited
    assert op._agg.capacity == cap
    assert 0 < op._pace < op._device_batch      # two readings of one table
    h.process_watermark(10**9)
    op.finish()


def test_a_reading_of_a_table_since_reclaimed_is_passed_over():
    """A fire takes its reading at dispatch and hands it over turns
    later; the operator knows by the generation whether the table it was
    taken of is the one it holds."""
    h = OneInputOperatorTestHarness(_op(), schema=SCHEMA)
    _feed(h, _advancing(6, seed=4))
    op = h.operator
    old = op._taken()
    op._reclaim()
    op._finish_reclaim(block=True)
    sweeps, cap = _sweeps(), op._agg.capacity
    op._reading(0, cap, old)                 # of the table that went
    assert _sweeps() == sweeps and op._reclaiming is None
    op._reading(0, cap, op._taken())         # of this one: past the limit
    assert op._reclaiming is not None
    op._finish_reclaim(block=True)
    assert _sweeps() == sweeps + 1
    with pytest.raises(RuntimeError, match="overflow"):
        op._reading(1, 0, op._taken())       # drops stay a hard error


# -- (c) the job on NEXmark's in-flight stream ------------------------------

@pytest.fixture(scope="module")
def spec():
    return load_spec()


def _bench_with(tmp_path, in_flight=None, **query):
    """A bench_dir whose only file is the real configuration with keys of
    its rehearsal's ``query`` block (and the keys in flight) replaced;
    everything else the harness finds in the real directory."""
    bench = tmp_path / "benchmarks"
    (bench / "configs").mkdir(parents=True)
    (bench / "traffic").mkdir()
    with open(f"{BENCH_DIR}/configs/{CONFIG}.json") as f:
        config = json.load(f)
    config["rehearse"]["query"].update(query)
    if in_flight is not None:
        config["rehearse"]["data"].update(in_flight=in_flight,
                                          n_keys=in_flight)
    (bench / "configs" / f"{CONFIG}.json").write_text(json.dumps(config))
    shutil.copy(f"{BENCH_DIR}/traffic/bids-inflight-780k.json",
                bench / "traffic")
    shutil.copy(f"{REPO_ROOT}/BENCHMARK.json", tmp_path / "BENCHMARK.json")
    return load_spec(str(tmp_path / "BENCHMARK.json"), str(bench))


def _run(spec, seconds):
    TRACER.reset()
    before = DEVICE_STATS.snapshot()
    try:
        run = run_cell(spec, spec.cell(CELL), seed=SEED, seconds=seconds,
                       trace=False, rehearse=True)
        spans = TRACER.retained_spans()
    finally:
        TRACER.reset()
    after = DEVICE_STATS.snapshot()
    return run, spans, {k: after[k] - before[k] for k in (
        *RECLAIM, "mesh_steps_total", "mesh_exchange_rounds_total",
        "mesh_inserted_rows_total", "mesh_stepped_rows_total")}


def _check(run, name):
    return next(c for c in run.checks if c["check"] == name)


@pytest.fixture(scope="module")
def sound(spec):
    return _run(spec, 3.0)


def test_the_inflight_job_on_a_mesh_equals_its_reference_at_its_capacity(
        sound):
    run, _spans, grew = sound
    assert run.correct and run.failed == 0 and run.attempted > 0, [
        c for c in run.checks if not c.get("ok", True)]
    assert all(c["ok"] for c in run.checks if "ok" in c)
    q = run.config["query"]
    assert (q["module"], q["operator"], q["n_devices"], q["capacity"]) \
        == ("q5_inflight_mesh", "mesh_aggregate", 4, 1 << 13)
    op = run.operator
    assert op._agg.capacity == 1 << 13       # every shard, as it began
    assert {s.data.shape for s in op._state.table.addressable_shards} \
        == {(1, 1 << 13)}
    assert _check(run, "capacity_grown_by")["value"] == 0
    assert _check(run, "programs_built_in_window")["value"] == 0
    tally = _check(run, "_tally")
    assert tally["windows_expected"] == tally["windows_emitted"] >= 20
    assert tally["rows_compared"] == len(run.sink.rows()["auction"])
    assert grew["state_reclaim_sweeps_total"] >= 1
    # the moving hot id overfills a destination bucket of most slices
    assert grew["mesh_exchange_rounds_total"] \
        > 1.5 * grew["mesh_steps_total"] > 0
    # inserts are counted between readings of one table: the prefill's
    # rows are (nearly) all inserts, the stream's a few in a hundred
    assert 0.9 * run.config["data"]["in_flight"] \
        < grew["mesh_inserted_rows_total"] \
        < 0.6 * grew["mesh_stepped_rows_total"]


def test_each_mesh_reclaim_is_a_stage_with_the_counts_of_all_shards(sound):
    run, spans, grew = sound
    reclaims = [s for s in spans if (s.scope, s.name) == ("window",
                                                          "Reclaim")]
    assert len(reclaims) == grew["state_reclaim_sweeps_total"]
    kept = freed = 0
    for s in reclaims:
        a = s.attributes
        assert a["task"] == run.window_task.task_id
        assert a["capacity"] == 1 << 13 and a["freed"] > 0 < a["kept"]
        # the fullest of four shards was past its limit: the four
        # together hold at least 0.6 x 2^13 and nearly four times that
        assert 0.6 * (1 << 13) < a["kept"] + a["freed"] < 4 * (1 << 13)
        assert 4 * a["freed"] > a["kept"] + a["freed"]
        assert s.end_ns > s.start_ns
        kept, freed = kept + a["kept"], freed + a["freed"]
    assert (kept, freed) == (grew["state_reclaim_keys_kept_total"],
                             grew["state_reclaim_keys_freed_total"])


def test_a_live_set_over_the_load_limit_still_grows_and_is_still_exact(
        tmp_path):
    """40,000 keys in flight over four 2^13-slot tables (load 1.2 if they
    were prefilled whole): the fullest shard passes its limit in the
    prefill's fourth pane, when no pane has retired yet, so the first
    reclaim frees next to nothing, the tables double as they always did,
    and every row still equals the reference."""
    run, _spans, grew = _run(_bench_with(tmp_path, in_flight=40_000), 1.5)
    assert run.operator._agg.capacity > 1 << 13
    assert _check(run, "capacity_grown_by")["value"] > 0
    assert not run.correct
    assert all(c["ok"] for c in run.checks if "ok" in c and c["check"]
               not in ("capacity_grown_by", "programs_built_in_window"))
    assert _check(run, "_tally")["windows_expected"] >= 10
    assert grew["state_reclaim_sweeps_total"] >= 1


def test_a_mesh_job_under_the_load_limit_never_sweeps(tmp_path):
    run, spans, grew = _run(_bench_with(tmp_path, capacity=1 << 15), 1.5)
    assert run.correct, [c for c in run.checks if not c.get("ok", True)]
    assert [grew[k] for k in RECLAIM] == [0, 0, 0]
    assert not [s for s in spans if s.name == "Reclaim"]
    assert run.operator._agg.capacity == 1 << 15
