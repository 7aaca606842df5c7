"""State that follows the windows: the one-chip backend RECLAIMS before
it grows (``TpuKeyedStateBackend.reclaim``; PR 35).

A key whose windows have all fired and retired holds no data in any ring
row; when the table passes load 0.6 its slot is freed, at the same
capacity, by ONE device program that rebuilds the table from the live
keys and re-seats every plane. The mechanism is held to a dict-based
numpy model; the job, through ``env.execute()`` on NEXmark's own key
distribution (auction ids that advance: ``benchmarks/generators/
bids_inflight.py``) at rehearsal size, to ``Q5Reference`` row for row.
"""

import json
import shutil

import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness.cell import run_cell
from benchmarks.harness.spec import BENCH_DIR, REPO_ROOT, load_spec
from flink_tpu.core.keygroups import KeyGroupRange
from flink_tpu.metrics import DEVICE_STATS
from flink_tpu.metrics.tracing import TRACER
from flink_tpu.ops.hash_table import EMPTY_KEY, compacts, lookup, \
    lookup_or_insert
from flink_tpu.ops.segment_ops import AGG_INITS
from flink_tpu.state.tpu_backend import TpuKeyedStateBackend

CELL = "q5-inflight-saturated"
CONFIG = "nexmark-q5-inflight"
SEED = 3_000_000_019          # over 2^31, as the driver's are
RECLAIM = ("state_reclaim_sweeps_total", "state_reclaim_keys_kept_total",
           "state_reclaim_keys_freed_total")
RING = 8
PLANES = (("__count__", "count", jnp.int32), ("revenue", "sum", jnp.int64),
          ("best", "max", jnp.int64))


# -- (a) the program against a dict-based model ----------------------------

def _planes(wide):
    """PLANES with the two value planes at ``wide``: int64 planes are
    stored as their 32-bit words (``ops/segment_ops.Halves``), int32 ones
    as the arrays they are; the reclaim is one algorithm for both."""
    return tuple((name, kind, dtype if name == "__count__" else wide)
                 for name, kind, dtype in PLANES)


def _seeded_backend(capacity: int, seed: int, wide=jnp.int64):
    """A backend at load 0.62 whose keys hold data in random ring rows,
    most of them only in rows that have since been retired; and the
    model: key -> {plane: the key's ring column}."""
    PLANES = _planes(wide)
    rng = np.random.default_rng(seed)
    be = TpuKeyedStateBackend(KeyGroupRange(0, 127), 128, capacity=capacity,
                              defer_overflow=True)
    for name, kind, dtype in PLANES:
        be.register_array_state(name, kind, dtype, ring=RING)
    n = int(0.62 * capacity)
    keys = rng.choice(1 << 40, size=n, replace=False).astype(np.int64) \
        - (1 << 39)
    batch = 256
    model = {int(k): {name: np.full(RING, np.asarray(
        AGG_INITS[kind](jnp.dtype(dtype)))) for name, kind, dtype in PLANES}
        for k in keys}
    # a third of the keys are bid on in the rows that stay; all of them
    # in the rows that retire
    late = set(rng.choice(n, size=n // 3, replace=False).tolist())
    for start in range(0, n, batch):
        k = keys[start:start + batch]
        pos = np.arange(start, start + len(k))
        if len(k) < batch:
            k, pos = np.resize(k, batch), np.resize(pos, batch)
        for rows in ((0, 5), (5, RING)):
            sel = np.ones(batch, bool) if rows[0] == 0 \
                else np.array([p in late for p in pos])
            ring = rng.integers(*rows, size=batch)
            price = rng.integers(
                1, 1 << (40 if wide == jnp.int64 else 20), size=batch)
            slots = be.slots_for_batch_device(jnp.asarray(k))
            be.fold_rings(slots, ring, (slots >= 0) & jnp.asarray(sel),
                          {"__count__": None, "revenue": price,
                           "best": price})
            for key, r, p, s in zip(k.tolist(), ring, price, sel):
                if s:
                    m = model[key]
                    m["__count__"][r] += 1
                    m["revenue"][r] += p
                    m["best"][r] = max(m["best"][r], p)
    for row in range(5):
        be.reset_ring_row(row)
        for m in model.values():
            for name, kind, dtype in PLANES:
                m[name][row] = np.asarray(AGG_INITS[kind](jnp.dtype(dtype)))
    return be, model


@pytest.mark.parametrize("wide", [jnp.int64, jnp.int32],
                         ids=["halves", "arrays"])
@pytest.mark.parametrize("capacity", [1 << 10, 1 << 11, 1 << 12])
def test_reclaim_keeps_every_live_key_bit_equal_and_no_dead_one(capacity,
                                                                wide):
    from flink_tpu.ops.segment_ops import Halves

    PLANES = _planes(wide)
    be, model = _seeded_backend(capacity, seed=capacity, wide=wide)
    assert [isinstance(be.get_array(name), Halves)
            for name, _k, _d in PLANES] == [False] + 2 * [wide == jnp.int64]
    live = {k for k, m in model.items() if m["__count__"].any()}
    assert 0 < len(live) < 0.45 * capacity < len(model)
    before = DEVICE_STATS.snapshot()
    be.check_health()            # load 0.62: reclaims, and does not grow
    after = DEVICE_STATS.snapshot()
    assert [after[k] - before[k] for k in RECLAIM] \
        == [1, len(live), len(model) - len(live)]
    assert be.capacity == capacity and be.num_keys == len(live)
    table = np.asarray(be.table)
    assert int((table != EMPTY_KEY).sum()) == len(live)
    assert set(table[table != EMPTY_KEY].tolist()) == live
    planes = {name: np.asarray(be.get_array(name))
              for name, _k, _d in PLANES}
    keys = np.array(sorted(model), np.int64)
    slots = np.asarray(lookup(be.table, jnp.asarray(keys)))
    for k, s in zip(keys.tolist(), slots.tolist()):
        if k not in live:
            assert s == -1, k
            continue
        assert s >= 0, k
        for name, col in model[k].items():
            assert (planes[name][:, s] == col).all(), (k, name)
    # a freed slot holds the identity in every plane
    empty = table == EMPTY_KEY
    for name, kind, dtype in PLANES:
        assert (planes[name][:, empty]
                == np.asarray(AGG_INITS[kind](jnp.dtype(dtype)))).all()
    # a second reclaim finds nothing to free and moves nothing
    assert be.reclaim() == (len(live), 0)
    assert (np.asarray(be.table) == table).all()
    for name, arr in planes.items():
        assert (np.asarray(be.get_array(name)) == arr).all()


def test_a_table_whose_keys_all_live_grows_as_it_did():
    """Fewer than a quarter of the occupied slots come free: the job's
    live set really is that large, and the answer is the old one."""
    be, model = _seeded_backend(1 << 10, seed=7)
    slots = be.slots_for_batch_device(jnp.asarray(
        np.array(sorted(model), np.int64)))
    be.fold_rings(slots, np.full(len(model), RING - 1), slots >= 0,
                  {"__count__": None})
    before = DEVICE_STATS.snapshot()
    be.check_health()
    after = DEVICE_STATS.snapshot()
    assert [after[k] - before[k] for k in RECLAIM] == [1, len(model), 0]
    assert be.capacity == 1 << 11 and be.num_keys == len(model)
    found = np.asarray(lookup(be.table, jnp.asarray(
        np.array(sorted(model), np.int64))))
    assert (found >= 0).all()


def test_row_states_and_a_budget_keep_growing_or_paging():
    """A plane with no ring has no pane that retires, and under an HBM
    budget cold groups page out instead: neither reclaims."""
    be = TpuKeyedStateBackend(KeyGroupRange(0, 127), 128, capacity=1 << 10)
    be.register_row_state("v", np.int64)
    assert not be._reclaimable()
    be.rows_upsert("v", np.arange(700, dtype=np.int64),
                   np.arange(700, dtype=np.int64))
    assert be.capacity == 1 << 11
    assert (be.rows_lookup("v", np.arange(700, dtype=np.int64))[0]
            == np.arange(700)).all()
    tiered = TpuKeyedStateBackend(KeyGroupRange(0, 127), 128,
                                  capacity=1 << 10, hbm_budget_slots=1 << 10)
    tiered.register_array_state("__count__", "count", jnp.int32, ring=RING)
    assert not tiered._reclaimable()


def _counting_backend(capacity: int, ring: int = 3):
    """(a backend with one count plane, a function that bids once on
    every key of a range). The reclaim program is cached by the planes'
    shapes: a test that asks whether it was built brings its own ring."""
    be = TpuKeyedStateBackend(KeyGroupRange(0, 127), 128, capacity=capacity,
                              defer_overflow=True)
    be.register_array_state("__count__", "count", jnp.int32, ring=ring)

    def bid(lo: int, hi: int) -> None:
        slots = be.slots_for_batch_device(jnp.arange(lo, hi, dtype=jnp.int64))
        be.fold_rings(slots, np.zeros(hi - lo, np.int64), slots >= 0,
                      {"__count__": None})

    return be, bid


def test_the_reclaim_program_is_built_when_asked_and_by_no_reading():
    """`prepare_reclaim` builds the program for the planes as they are
    (the operator asks once its planes are registered, before its first
    input; `_grow` after each growth), so that the reclaim compiles
    nothing where a job has promised to build nothing. No health reading
    builds it, whatever trend the readings show: the readings of two
    windows that fire back to back are one reading (ROADMAP D14)."""
    be, bid = _counting_backend(1 << 10, ring=5)
    program = be._reclaim_call()[0]
    assert program._prepared is None and not program._compiled
    be.prepare_reclaim()
    assert program._prepared is not None and program._compiled
    prepared = program._prepared
    bid(0, 30)                               # the probe's and the fold's
    before = DEVICE_STATS.snapshot()         # programs are built
    for lo, hi in ((30, 60), (60, 60), (60, 160), (160, 230)):
        if hi > lo:
            bid(lo, hi)
        be.check_health()                    # (60, 60): the same reading
    be.prepare_reclaim()                     # the same planes: nothing
    assert be.reclaim() == (230, 0)
    after = DEVICE_STATS.snapshot()
    assert after["compiles"] == before["compiles"]
    assert program._prepared is prepared
    assert int((np.asarray(be.table) != EMPTY_KEY).sum()) == 230


def test_a_backend_that_was_not_asked_builds_nothing_from_its_readings():
    """Growing readings that head straight for the load limit (what the
    trend rule of PRs 35-41 built the program from) build nothing; a
    backend that cannot reclaim, or that does not decide by deferred
    health readings, builds nothing even when asked."""
    be, bid = _counting_backend(1 << 10, ring=6)
    program = be._reclaim_call()[0]
    for n in (100, 200, 300, 400, 500):
        bid(n - 100, n)
        be.check_health()
    assert program._prepared is None and not program._compiled
    sync = TpuKeyedStateBackend(KeyGroupRange(0, 127), 128, capacity=1 << 10)
    sync.register_array_state("__count__", "count", jnp.int32, ring=7)
    sync.prepare_reclaim()
    tiered = TpuKeyedStateBackend(KeyGroupRange(0, 127), 128,
                                  capacity=1 << 10, defer_overflow=True,
                                  hbm_budget_slots=1 << 10)
    tiered.register_array_state("__count__", "count", jnp.int32, ring=7)
    tiered.prepare_reclaim()
    for other in (sync, tiered):
        assert other._reclaim_built is None
        built = other._reclaim_call()[0]
        assert built._prepared is None and not built._compiled


def test_a_growth_builds_the_reclaim_of_the_grown_planes():
    """A table whose live keys alone fill it grows, and the turn that
    grew it builds the reclaim of the new shape before the next input."""
    be, bid = _counting_backend(1 << 10, ring=9)
    be.prepare_reclaim()
    bid(0, 700)
    be.check_health()                        # all live: reclaims, grows
    assert be.capacity == 1 << 11
    program = be._reclaim_call()[0]
    assert program._prepared is not None and program._compiled
    before = DEVICE_STATS.snapshot()["compiles"]
    assert be.reclaim() == (700, 0)
    assert DEVICE_STATS.snapshot()["compiles"] == before


@pytest.mark.parametrize("capacity, per_pane, copies", [
    (1 << 10, 250, 1), (1 << 14, 4000, 2)],
    ids=["narrow_batches", "wide_batches_with_duplicates"])
def test_a_job_builds_nothing_once_its_first_windows_have_fired(
        capacity, per_pane, copies):
    """ROADMAP D14's guard, in the idiom of `tests/test_mesh_reclaim.py::
    test_nothing_is_compiled_once_the_first_reclaim_has_been_prepared`:
    the operator builds the reclaim of its planes before its first input,
    so a job whose first two windows fire back to back and hand
    `apply_health` ONE occupancy twice (no trend to read), and whose next
    reading is already past the load limit, reclaims without a single
    compile. Until PR 42 that reclaim compiled where it ran: on an empty
    compile cache at 2^24 slots a 20 s build, ending in q7-10m-saturated's
    timed phase and voiding the run (PR 39's verdict in the ledger).

    With batches wide enough to compact whose every key comes twice
    (PR 46): the backend picks its wide-batch program from the second
    batch's counters and keeps it (every batch is all new keys: no
    flipping back to the plain program, which would build nothing new
    but pay the full-width rounds), that program is built beside the
    plain one at the new table's first batch and nothing after the third,
    the reclaim included, and one lane a key went through the rounds: the
    other stood behind it."""
    from jax._src import monitoring

    from flink_tpu.core import Schema
    from flink_tpu.core.records import RecordBatch
    from flink_tpu.runtime.harness import OneInputOperatorTestHarness
    from flink_tpu.runtime.operators.device_window import (
        AggSpec, DeviceWindowAggOperator)
    from flink_tpu.window import TumblingEventTimeWindows

    schema = Schema([("k", np.int64), ("v", np.int64)])
    op = DeviceWindowAggOperator(
        TumblingEventTimeWindows.of(1000), "k",
        [AggSpec("count", out_name="n", value_bits=31),
         AggSpec("sum", "v", out_name="s")],
        capacity=capacity, ring_size=11, defer_overflow=True)
    h = OneInputOperatorTestHarness(op, schema)
    readings = []
    apply_health = TpuKeyedStateBackend.apply_health

    def feed(pane: int) -> None:
        keys = np.tile(np.arange(per_pane * pane, per_pane * (pane + 1),
                                 dtype=np.int64), copies)
        h.process_batch(RecordBatch(
            schema, {"k": keys, "v": keys + (1 << 33)},
            np.full(len(keys), 1000 * pane + 5, np.int64)))

    def spy(self, dropped, occupancy, *a, **kw):
        readings.append(int(occupancy))
        return apply_health(self, dropped, occupancy, *a, **kw)

    builds = []

    def on_duration(event, _seconds, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            builds.append(event)

    wide = compacts(per_pane * copies)
    before = DEVICE_STATS.snapshot()
    TpuKeyedStateBackend.apply_health = spy
    try:
        feed(0)
        be = op._backend
        program = be._reclaim_call()[0]
        assert program._compiled             # built before the first input
        feed(1)
        h.process_watermark(1999)            # two windows, back to back
        assert readings == [2 * per_pane, 2 * per_pane]
        be.note_probe_stats(block=True)
        assert be._probe_wide                # all new keys, batch on batch
        if wide:
            # built beside the plain program, at the first batch of a new
            # table: the third batch, the first to run it, traces nothing
            assert be._wide_probe is not None
            traced = lookup_or_insert._cache_size()
            feed(2)
            assert be._probe_audited == {False, True}
            assert lookup_or_insert._cache_size() == traced
        monitoring.register_event_duration_secs_listener(on_duration)
        try:
            if not wide:
                feed(2)
            # reads three quarters of the table: reclaims
            h.process_watermark(2999)
            for pane in (3, 4):
                feed(pane)
                be.note_probe_stats(block=True)
                assert be._probe_wide
                h.process_watermark(1000 * pane + 999)
        finally:
            monitoring.unregister_event_duration_listener(on_duration)
    finally:
        TpuKeyedStateBackend.apply_health = apply_health
    assert readings[2] == 3 * per_pane > 0.6 * capacity
    assert be.capacity == capacity
    # (a batch too narrow to compact has one program whatever is picked)
    assert be._probe_audited == ({False, True} if wide else {False})
    h.process_watermark(1 << 40)
    h.close()
    moved = {k: v - before[k] for k, v in DEVICE_STATS.snapshot().items()
             if k.startswith(("probe_", "state_reclaim_sweeps"))}
    assert moved["state_reclaim_sweeps_total"] > 0
    assert builds == []
    assert moved["probe_rows_total"] == 5 * per_pane * copies
    assert moved["probe_wide_batches_total"] == 5
    # batches 3 to 5 elected, and every key's second row stood behind
    # its first (4 cells a lane: a key loses its cell to another's now
    # and then, and both its rows then go through the rounds)
    assert moved["probe_elected_batches_total"] == (3 if wide else 0)
    if wide:
        assert 0.8 * 3 * per_pane \
            < moved["probe_elected_rows_total"] <= 3 * per_pane
    else:
        assert moved["probe_elected_rows_total"] == 0
    rows = sorted((int(k), int(n), int(s))
                  for k, _start, _end, n, s in h.get_output())
    assert rows == [(k, copies, copies * (k + (1 << 33)))
                    for k in range(5 * per_pane)]


def test_a_reading_of_a_table_rebuilt_since_is_passed_over():
    """A fire takes its reading at dispatch and hands it over turns
    later: the backend knows by the generation whether the table it was
    taken of is still the one it holds, and no caller keeps count."""
    be, bid = _counting_backend(1 << 10)
    bid(0, 700)
    old = be.table_generation
    be.reset_ring_row(0)
    assert be.reclaim() == (0, 700)
    assert be.table_generation == old + 1 and be.num_keys == 0
    sweeps = DEVICE_STATS.snapshot()["state_reclaim_sweeps_total"]
    be.apply_health(0, 700, generation=old)  # of the table that went
    assert be.num_keys == 0 and be.capacity == 1 << 10
    assert DEVICE_STATS.snapshot()["state_reclaim_sweeps_total"] == sweeps
    be.apply_health(0, 0, generation=be.table_generation)
    assert be.num_keys == 0
    snap = be.snapshot(1)                    # settles a pending reclaim
    assert len(snap["keys"]) == 0


# -- (b)-(d) the job on the in-flight stream -------------------------------

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
    return run, spans, {k: after[k] - before[k] for k in RECLAIM}


def _check(run, name):
    return next(c for c in run.checks if c["check"] == name)


@pytest.fixture(scope="module")
def sound(spec):
    return _run(spec, 4.0)


def test_the_inflight_job_equals_its_reference_at_the_capacity_it_began_with(
        sound):
    run, _spans, grew = sound
    assert run.correct and run.failed == 0 and run.attempted > 0, [
        c for c in run.checks if not c.get("ok", True)]
    assert all(c["ok"] for c in run.checks if "ok" in c)
    q = run.config["query"]
    assert (q["module"], q["capacity"]) == ("q5_inflight", 1 << 13)
    assert run.operator._backend.capacity == 1 << 13
    assert _check(run, "capacity_grown_by")["value"] == 0
    assert _check(run, "programs_built_in_window")["value"] == 0
    tally = _check(run, "_tally")
    assert tally["windows_expected"] == tally["windows_emitted"] >= 20
    assert tally["rows_compared"] == len(run.sink.rows()["auction"])
    # the key space moved: more ids were bid on than the table has slots
    # under its load limit, and still it holds only the live ones
    ids = np.unique(np.concatenate([
        run.generator.columns(run.schedule.batch_index(b))["auction"]
        for b in range(run.schedule.n_batches)]))
    assert len(ids) > 0.6 * (1 << 13) > run.operator._backend.num_keys
    assert grew["state_reclaim_sweeps_total"] >= 2
    assert grew["state_reclaim_keys_freed_total"] \
        > grew["state_reclaim_keys_kept_total"] // 2 > 0


def test_each_reclaim_is_a_stage_opened_in_the_drain_that_found_the_pressure(
        sound):
    """The drain dispatches the reclaim and goes on (the mailbox does not
    wait for it); the stage closes when the two counts have landed."""
    run, spans, grew = sound
    reclaims = [s for s in spans if (s.scope, s.name) == ("window",
                                                          "Reclaim")]
    assert len(reclaims) == grew["state_reclaim_sweeps_total"]
    drains = {s.span_id: s for s in spans
              if (s.scope, s.name) == ("window", "Drain")}
    kept = freed = 0
    for s in reclaims:
        a = s.attributes
        assert a["task"] == run.window_task.task_id
        assert a["capacity"] == 1 << 13 and a["freed"] > 0 < a["kept"]
        assert a["kept"] + a["freed"] > 0.6 * (1 << 13)
        drain = drains[s.parent_id]
        assert drain.attributes["seq"] == a["seq"]
        assert drain.start_ns <= s.start_ns <= drain.end_ns <= s.end_ns
        kept, freed = kept + a["kept"], freed + a["freed"]
    assert (kept, freed) == (grew["state_reclaim_keys_kept_total"],
                             grew["state_reclaim_keys_freed_total"])


def test_a_live_set_over_the_load_limit_still_grows_and_is_still_exact(
        tmp_path):
    """2^12 slots for 3,000 keys in flight (load 0.73 once prefilled): the
    first reclaim frees next to nothing, the table doubles as it always
    did, and every row still equals the reference."""
    run, _spans, grew = _run(
        _bench_with(tmp_path, in_flight=3000, capacity=1 << 12), 1.5)
    assert run.operator._backend.capacity > 1 << 12
    assert _check(run, "capacity_grown_by")["value"] > 0
    assert not run.correct
    assert all(c["ok"] for c in run.checks if "ok" in c and c["check"]
               not in ("capacity_grown_by", "programs_built_in_window"))
    assert _check(run, "_tally")["windows_expected"] >= 10
    assert grew["state_reclaim_sweeps_total"] >= 1


def test_a_job_under_the_load_limit_never_sweeps(tmp_path):
    run, spans, grew = _run(_bench_with(tmp_path, capacity=1 << 16), 1.5)
    assert run.correct, [c for c in run.checks if not c.get("ok", True)]
    assert grew == dict.fromkeys(RECLAIM, 0)
    assert not [s for s in spans if s.name == "Reclaim"]
    assert run.operator._backend.capacity == 1 << 16


def test_without_the_reclaim_the_rehearsal_fails_on_capacity(spec,
                                                             monkeypatch):
    """The control of the cell's guarantee (mirrors benchmarks/tests/
    test_inflight_cell.py): the same rehearsal on a backend that cannot
    reclaim doubles its table, and the harness says so."""
    monkeypatch.setattr(TpuKeyedStateBackend, "_reclaimable",
                        lambda self: False)
    run, _spans, grew = _run(spec, 4.0)
    assert not run.correct
    assert _check(run, "capacity_grown_by")["value"] == 1 << 13
    # (growth builds its programs when it happens: inside the window)
    assert all(c["ok"] for c in run.checks if "ok" in c and c["check"]
               not in ("capacity_grown_by", "programs_built_in_window"))
    assert grew["state_reclaim_sweeps_total"] == 0
