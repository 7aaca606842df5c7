"""NEXmark Q7 (highest bid) as ONE keyed vertex on four devices, at test
size on the CPU: the job of ``benchmarks/queries/q7_mesh.py`` through
``env.execute()`` against ``q7_reference.py``, and the promise
``AggSpec.value_bits`` on its way to every shard's select.

The query is a tumbling int64 MAX over ``price << 20 | bidder`` per
auction, sharded by key group behind the on-device all-to-all, with a
top-1 a window across keys AND shards, a packing map in front of the
``key_by`` and an unpacking map behind the aggregate. The last case is
the rehearsal of the cell ``q7-16m-mesh4-saturated`` from the REAL
``benchmarks/`` directory (``benchmarks/tests/test_q7_mesh_cell.py``
drives the same in a process of its own), here where tier-1 sees it.
"""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from benchmarks.harness.cell import run_cell
from benchmarks.harness.spec import BENCH_DIR, load_module, load_spec
from flink_tpu.core.keygroups import hash_batch, key_groups_for_hash_batch
from flink_tpu.core.records import Schema
from flink_tpu.metrics import DEVICE_STATS
from flink_tpu.ops.hash_table import EMPTY_KEY, ensure_x64
from flink_tpu.ops.segment_ops import Halves
from flink_tpu.parallel.mesh import shard_ranges
from flink_tpu.parallel.sharded_window import AggDef, ShardedWindowAgg, \
    ShardedWindowState
from flink_tpu.runtime.operators.device_window import AggSpec

CELL = "q7-16m-mesh4-saturated"
D, MAX_PAR = 4, 128
WINDOW_MS = 1000
#: the query block of the cell's configuration at test size
QUERY = {"module": "q7_mesh", "window_size_ms": WINDOW_MS, "price_bits": 23,
         "word_shift": 20, "topk": 1, "operator": "mesh_aggregate",
         "capacity": 1 << 10, "ring_size": 8, "async_fire": True,
         "n_devices": D, "device_batch": 64}
PRICE_MAX = 1 << 22           # a price of 23 bits: the word has 43

q7_mesh = load_module(BENCH_DIR, "queries", "q7_mesh")
_reference = load_module(BENCH_DIR, "queries", "q7_reference")


def _shard_of(keys: np.ndarray) -> np.ndarray:
    """The shard that owns each key, as the program routes it."""
    groups = key_groups_for_hash_batch(hash_batch(np.asarray(keys, np.int64)),
                                       MAX_PAR)
    starts = np.array([r.start for r in shard_ranges(MAX_PAR, D)])
    return np.searchsorted(starts, groups, side="right") - 1


def _bids(seed: int, n: int = 1500, n_keys: int = 400, windows: int = 3):
    """``n`` bids in event-time order over ``windows`` windows, prices
    under 2^20 (no bid of these wins against a planted one)."""
    rng = np.random.default_rng(seed)
    return {"auction": rng.integers(0, n_keys, n).astype(np.int64),
            "bidder": rng.integers(0, 1000, n).astype(np.int64),
            "price": rng.integers(1, 1 << 20, n).astype(np.int64),
            "ts": np.sort(rng.integers(0, windows * WINDOW_MS, n)
                          ).astype(np.int64)}


def _plant(bids: dict, ts: int, auction: int, price: int, bidder: int):
    """``bids`` with one more bid, at its place in event time."""
    at = int(np.searchsorted(bids["ts"], ts))
    new = {"auction": auction, "bidder": bidder, "price": price, "ts": ts}
    return {c: np.insert(v, at, new[c]) for c, v in bids.items()}


def _run_job(bids: dict, query: dict = QUERY) -> dict:
    """The job ``q7_mesh.build`` wires, through ``env.execute()``:
    {window_end: (auction, price, bidder)} of every emitted row."""
    from flink_tpu.api import StreamExecutionEnvironment
    from flink_tpu.connectors.core import CollectSink
    from flink_tpu.core import WatermarkStrategy

    env = StreamExecutionEnvironment.get_execution_environment()
    ws = WatermarkStrategy.for_monotonous_timestamps() \
        .with_timestamp_column(q7_mesh.TS_COLUMN)
    stream = env.datagen(lambda idx: {c: v[idx] for c, v in bids.items()},
                         Schema(q7_mesh.SCHEMA_FIELDS),
                         count=len(bids["ts"]),
                         timestamp_column=q7_mesh.TS_COLUMN,
                         watermark_strategy=ws)
    sink = CollectSink()
    q7_mesh.build(stream, query, sink)
    env.execute()
    out = {}
    for auction, start, end, price, bidder in sink.rows:
        assert end - start == WINDOW_MS and int(end) not in out
        out[int(end)] = (int(auction), int(price), int(bidder))
    return out


def _expected(bids: dict) -> dict:
    """{window_end: (auction, price, bidder)} by ``Q7Reference``."""
    shift, out = QUERY["word_shift"], {}

    def on_window(end_ms, best):
        if best.any():
            a = int(np.argmax(best))
            word = int(best[a])
            assert (best == word).sum() == 1    # planted winners are unique
            out[int(end_ms)] = (a, word >> shift, word & ((1 << shift) - 1))

    ref = _reference.Q7Reference(int(bids["auction"].max()) + 1, WINDOW_MS,
                                 shift, on_window)
    ref.feed(bids["auction"], bids["price"], bids["bidder"], bids["ts"])
    ref.close()
    return out


def test_the_job_equals_its_reference_with_keys_on_every_shard():
    bids = _bids(seed=7)
    assert set(_shard_of(bids["auction"])) == set(range(D))
    got = _run_job(bids)
    assert got == _expected(bids) and len(got) == 3


@pytest.mark.parametrize("shard", range(D))
def test_the_winner_may_live_on_any_shard(shard):
    """The window's highest bid on a key of each shard in turn, a word of
    43 bits (price 2^22): the merge of the shards' candidates must find
    it wherever it is."""
    bids = _bids(seed=11 + shard)
    keys = np.arange(400)
    auction = int(keys[_shard_of(keys) == shard][5])
    bids = _plant(bids, 1500, auction, PRICE_MAX, 777)
    word = PRICE_MAX << QUERY["word_shift"] | 777
    assert word.bit_length() == 43
    got = _run_job(bids)
    assert got[2000] == (auction, PRICE_MAX, 777)
    assert got == _expected(bids)


def test_a_tie_on_price_across_shards_goes_to_the_larger_bidder():
    bids = _bids(seed=23)
    keys = np.arange(400)
    on0 = int(keys[_shard_of(keys) == 0][3])
    on3 = int(keys[_shard_of(keys) == 3][3])
    bids = _plant(bids, 300, on3, PRICE_MAX - 1, 41)
    bids = _plant(bids, 700, on0, PRICE_MAX - 1, 42)
    got = _run_job(bids)
    assert got[1000] == (on0, PRICE_MAX - 1, 42)
    assert got == _expected(bids)


def test_a_window_with_no_bid_emits_nothing():
    bids = _bids(seed=31, windows=4)
    hole = (bids["ts"] >= 2000) & (bids["ts"] < 3000)
    bids = {c: v[~hole] for c, v in bids.items()}
    got = _run_job(bids)
    assert sorted(got) == [1000, 2000, 4000]
    assert got == _expected(bids)


# -- the promise on its way to the select ----------------------------------

def _agg(defs):
    ensure_x64()
    mesh = Mesh(np.array(jax.devices()[:D]), ("data",))
    return ShardedWindowAgg(mesh, defs, capacity=1 << 10, ring=8,
                            max_parallelism=MAX_PAR)


def _fire_text(agg, rank, k, value_bits=None, panes=1) -> str:
    """The lowered (StableHLO) text of the ranked fire for ``agg``'s
    shapes, the planes handed over as the state keeps them."""
    sharded = NamedSharding(agg.mesh, P("data"))
    rep = NamedSharding(agg.mesh, P())

    def spec(shape, dtype, sharding=sharded):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    shape = (D, agg.ring, agg.capacity)
    state = ShardedWindowState(
        spec((D, agg.capacity), jnp.int64),
        {a.name: Halves(spec(shape, jnp.uint32), spec(shape, jnp.uint32),
                        np.dtype("int64")) for a in agg.aggs},
        spec((D,), jnp.int64))
    return agg.fire_program(rank, k, value_bits).lower(
        state, spec((panes,), jnp.int32, rep),
        spec((panes,), jnp.bool_, rep)).as_text()


@pytest.mark.parametrize("value_bits, guarded", [(43, False), (63, False),
                                                 (64, True), (None, True)])
def test_a_promise_under_the_planes_width_compiles_no_guard(value_bits,
                                                            guarded):
    """The guard is the sign test and the xor of every slot with a flip
    word in front of the walk: the only xor a fire holds."""
    agg = _agg([AggDef("best", "max", jnp.int64)])
    text = _fire_text(agg, "best", 1, value_bits)
    assert ("stablehlo.xor" in text) == guarded
    assert agg.rank_bits("best", value_bits) == (value_bits or 64)


#: sha256 (16 hex digits) of the lowered text of Q5's mesh fire on four
#: CPU devices (COUNT + SUM, both int64, 2^10 slots, ring 8, five pane
#: rows), by rank, written from the parent of PR 48 (47c33d1): a COUNT
#: rank, a rank with no promise and an unranked fire lower to what they
#: lowered to before the promise was carried. A PR that MEANS to change
#: the mesh fire writes new digests
_FIRE_DIGESTS_AT_47C33D1 = {
    ("bids", 50): "0dbd8495c7fc664d",
    ("revenue", 50): "30e4e6b7498f25fa",
    (None, None): "aa16517f19e9e339",
}


@pytest.mark.parametrize("rank, k", list(_FIRE_DIGESTS_AT_47C33D1))
def test_a_fire_without_a_promise_lowers_to_what_it_lowered_to(rank, k):
    agg = _agg([AggDef("bids", "count", jnp.int64),
                AggDef("revenue", "sum", jnp.int64)])
    text = _fire_text(agg, rank, k, panes=5)
    assert hashlib.sha256(text.encode()).hexdigest()[:16] \
        == _FIRE_DIGESTS_AT_47C33D1[(rank, k)]
    # whatever is declared for a COUNT, it is promised 63 bits and no more
    assert agg.rank_bits("bids", 48) == agg.rank_bits("bids") == 63
    assert agg.rank_bits("revenue") == 64 and agg.rank_bits(None, 43) == 64


def _harness(aggs, **kw):
    from flink_tpu.runtime import OneInputOperatorTestHarness
    from flink_tpu.runtime.operators.mesh_window import \
        MeshWindowAggOperator
    from flink_tpu.window import TumblingEventTimeWindows

    op = MeshWindowAggOperator(
        TumblingEventTimeWindows.of(WINDOW_MS), "auction", aggs,
        n_devices=D, capacity=1 << 10, ring_size=8, device_batch=64,
        emit_topk=1, **kw)
    schema = Schema([("auction", np.int64), ("word", np.int64)])
    return op, OneInputOperatorTestHarness(op, schema=schema)


@pytest.mark.parametrize("value_bits, guarded", [(43, 0), (None, 1)])
def test_the_operator_hands_the_ranks_promise_to_its_fire(value_bits,
                                                          guarded):
    """``_aggdefs`` makes plane shapes and nothing else; the rank's
    ``value_bits`` goes from the ``AggSpec`` to ``fire_compact``, is an
    attribute of the window's ``window/Drain`` and decides whether
    ``fire_select_guarded_total`` moves."""
    from flink_tpu.metrics.tracing import TRACER

    op, h = _harness([AggSpec("max", "word", out_name="best",
                              value_bits=value_bits)])
    TRACER.reset()
    before = DEVICE_STATS.snapshot()
    h.process_elements([(k, (k + 1) << 20) for k in range(200)],
                       [10 * k for k in range(200)])
    h.process_watermark(10 ** 9)
    after = DEVICE_STATS.snapshot()
    drains = [s for s in TRACER.retained_spans()
              if (s.scope, s.name) == ("window", "Drain")]
    TRACER.reset()
    rows = sorted((int(r[2]), int(r[0]), int(r[3])) for r in h.get_output())
    assert rows == [(1000, 99, 100 << 20), (2000, 199, 200 << 20)]
    fires = after["fire_selects_total"] - before["fire_selects_total"]
    assert fires == len(drains) == 2
    assert after["fire_select_guarded_total"] \
        - before["fire_select_guarded_total"] == guarded * fires
    bits = value_bits or 64
    assert [d.attributes["value_bits"] for d in drains] == [bits, bits]
    assert all(d.attributes["select_passes"] == ((k + 1) << 20).bit_length()
               for d, k in zip(drains, (99, 199)))
    # the promise is no word of the shard's signature (JX505)
    assert "43" not in repr(op._agg.sig)
    # a MAX-only job reads no count: the hidden plane is a 32-bit presence
    # plane, and every window/Drain says so
    assert [(a.name, a.kind, np.dtype(a.dtype).name) for a in op._agg.aggs] \
        == [("best", "max", "int64"), ("__count__", "presence", "int32")]
    assert {d.attributes["count_plane"] for d in drains} == {"presence32"}


# -- the cell's rehearsal, where the driver's run sees it ------------------

@pytest.fixture(scope="module")
def rehearsal():
    """One rehearsal of the cell from the REAL ``benchmarks/`` directory
    on four of this platform's devices, with what the program counted
    and its stage spans."""
    from flink_tpu.metrics.tracing import TRACER

    spec = load_spec()
    TRACER.reset()
    before = DEVICE_STATS.snapshot()
    run = run_cell(spec, spec.cell(CELL), seed=3_000_000_019, seconds=5.0,
                   trace=False, rehearse=True)
    spans = TRACER.retained_spans()
    TRACER.reset()
    after = DEVICE_STATS.snapshot()
    return spec, run, spans, {k: after[k] - before[k] for k in (
        "fire_selects_total", "fire_select_passes_total",
        "fire_select_sort_total", "fire_select_guarded_total",
        "mesh_steps_total")}


def test_the_cells_rehearsal_is_correct_on_every_shard(rehearsal):
    _spec, run, _spans, counted = rehearsal
    assert run.query.__file__ == f"{BENCH_DIR}/queries/q7_mesh.py"
    assert run.correct and run.failed == 0 and run.attempted > 0
    assert all(c["ok"] for c in run.checks if "ok" in c)
    tally = next(c for c in run.checks if c["check"] == "_tally")
    assert tally["rows_compared"] == tally["windows_emitted"] \
        == tally["windows_expected"] >= 9
    op = run.operator
    assert type(op).__name__ == "MeshWindowAggOperator"
    assert run.query.operator_capacity(op, run.config["query"]) \
        == (16384, 16384)
    table = np.asarray(op._state.table)
    occupied = (table != np.int64(EMPTY_KEY)).sum(axis=1)
    assert table.shape == (D, 16384) and (occupied > 3000).all()
    assert counted["mesh_steps_total"] == run.schedule.n_batches


def test_the_cells_rehearsal_fires_unguarded_at_43_bits(rehearsal):
    _spec, _run, spans, counted = rehearsal
    fires = counted["fire_selects_total"]
    assert fires >= 9
    assert counted["fire_select_guarded_total"] == 0
    assert counted["fire_select_sort_total"] == 0
    # every window's highest word has the price's top bits: 42 or 43
    assert 42 * fires <= counted["fire_select_passes_total"] <= 43 * fires
    drains = [s for s in spans if (s.scope, s.name) == ("window", "Drain")]
    assert len(drains) == fires
    assert {d.attributes["value_bits"] for d in drains} == {43}


def test_the_cells_new_metrics_read_what_the_program_counted(rehearsal):
    spec, run, _spans, _counted = rehearsal
    read = {}
    for name in ("mesh_fire_guarded_share", "mesh_fire_select_passes",
                 "mesh_max_fold_roofline_share",
                 "mesh_wide_select_roofline_share"):
        body = spec.layer_metric(name)
        read[name] = spec.module("readers", body["reader"]).read(
            run, body.get("params", {}))
    assert read["mesh_fire_guarded_share"] == 0.0
    assert 42 <= read["mesh_fire_select_passes"] <= 43
    # no device trace on the CPU: the two roofline shares read nothing
    assert read["mesh_max_fold_roofline_share"] is None
    assert read["mesh_wide_select_roofline_share"] is None
