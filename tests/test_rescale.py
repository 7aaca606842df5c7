"""Elastic multi-host SPMD contracts (PR 12): shard-range partition
properties, keyBy-exchange permutation properties (full-width and the
capacity-bounded round form), and live rescale — barrier-aligned,
exactly-once, recompile-free — at operator, subtask (two-host drill) and
driver (coordinator) level. Runs on the 8-device virtual CPU mesh
(conftest)."""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from flink_tpu.core.keygroups import (KeyGroupRange, assign_to_key_group,
                                      operator_index_for_key_group)
from flink_tpu.core.records import Schema
from flink_tpu.ops.hash_table import ensure_x64
from flink_tpu.parallel.exchange import (bucket_capacity, exchange_round,
                                         keyby_exchange, order_payload,
                                         plan_exchange)
from flink_tpu.parallel.mesh import (DATA_AXIS, device_index_for_key_groups,
                                     make_mesh, shard_ranges)
from flink_tpu.parallel.plan import shard_map_unchecked

ensure_x64()

pytestmark = pytest.mark.mesh

SCHEMA = Schema([("key", np.int64), ("v", np.int64)])
K = 64  # key universe for exchange histograms


# ---------------------------------------------------------------------------
# satellite: shard_ranges partition properties (incl. remainders)


def _assert_partition(ranges, lo, hi):
    assert ranges[0].start == lo and ranges[-1].end == hi
    for prev, cur in zip(ranges, ranges[1:]):
        assert cur.start == prev.end + 1  # contiguous, no gap/overlap
    sizes = [r.end - r.start + 1 for r in ranges]
    assert min(sizes) >= 1
    assert max(sizes) - min(sizes) <= 1  # balanced to within one group


@pytest.mark.parametrize("maxp", [7, 8, 101, 128, 130])
@pytest.mark.parametrize("n", [1, 2, 3, 5, 7])
def test_shard_ranges_partition_properties(maxp, n):
    ranges = shard_ranges(maxp, n)
    assert len(ranges) == n
    _assert_partition(ranges, 0, maxp - 1)
    # routing parity: the device each group is ROUTED to owns it, and both
    # the host reference and the device twin agree
    kg = jnp.arange(maxp, dtype=jnp.int32)
    dev = np.asarray(jax.device_get(device_index_for_key_groups(kg, n, maxp)))
    for g in range(maxp):
        assert g in ranges[dev[g]]
        assert dev[g] == operator_index_for_key_group(maxp, n, g)


@pytest.mark.parametrize("n", [1, 3, 7, 40])
def test_shard_ranges_base_range_two_level_split(n):
    base = KeyGroupRange(40, 79)  # one subtask's 40 groups of maxp=128
    ranges = shard_ranges(128, n, base)
    assert len(ranges) == n
    _assert_partition(ranges, 40, 79)
    kg = jnp.arange(40, 80, dtype=jnp.int32)
    dev = np.asarray(jax.device_get(device_index_for_key_groups(
        kg, n, 128, base_start=40, base_len=40)))
    for g, d in zip(range(40, 80), dev):
        assert g in ranges[d]


def test_shard_ranges_rejects_empty_shards():
    with pytest.raises(ValueError, match="max-parallelism"):
        shard_ranges(4, 8)
    with pytest.raises(ValueError, match="max-parallelism"):
        shard_ranges(128, 64, KeyGroupRange(0, 9))


def test_sharded_agg_rejects_undersized_parallelism():
    from flink_tpu.parallel import AggDef, ShardedWindowAgg
    with pytest.raises(ValueError, match="max_parallelism"):
        ShardedWindowAgg(make_mesh(8), [AggDef("v", "sum", jnp.int64)],
                         capacity=64, ring=2, max_parallelism=4)


# ---------------------------------------------------------------------------
# satellite: the keyBy exchange is a permutation of the valid records


def _exchange_hists(D, dest, keys, valid, cap=None):
    """Run the exchange inside shard_map; returns [D, K] per-device key
    histograms of the routed+valid rows (and the round count for the
    bounded form)."""
    mesh = make_mesh(D)

    def body(dest, keys, valid):
        d, k, v = dest[0], keys[0], valid[0]
        if cap is None:
            routed, rvalid = keyby_exchange(DATA_AXIS, D, d, {"k": k}, v)
            hist = jnp.zeros(K, jnp.int32).at[routed["k"]].add(
                jnp.where(rvalid, 1, 0), mode="drop")
            return hist[None], jnp.ones(1, jnp.int32)
        plan = plan_exchange(d, v, D, cap)
        ordered = order_payload(plan, {"k": k}, cap)
        n_rounds = jax.lax.pmax(plan.n_rounds, DATA_AXIS)

        def rnd(carry):
            r, hist = carry
            routed, rvalid = exchange_round(DATA_AXIS, D, cap, plan,
                                            ordered, r)
            return (r + 1, hist.at[routed["k"]].add(
                jnp.where(rvalid, 1, 0), mode="drop"))

        _, hist = jax.lax.while_loop(
            lambda c: c[0] < n_rounds, rnd,
            (jnp.int32(0), jnp.zeros(K, jnp.int32)))
        return hist[None], n_rounds[None].astype(jnp.int32)

    fn = shard_map_unchecked(
        body, mesh,
        in_specs=(P(DATA_AXIS), P(DATA_AXIS), P(DATA_AXIS)),
        out_specs=(P(DATA_AXIS), P(DATA_AXIS)))
    hist, rounds = jax.jit(fn)(dest, keys, valid)
    return np.asarray(jax.device_get(hist)), int(np.asarray(rounds).max())


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("D", [2, 4, 8])
@pytest.mark.parametrize("bounded", [False, True])
def test_exchange_is_a_permutation_of_valid_records(seed, D, bounded):
    """No valid record is lost or duplicated, and every routed record
    lands on the device its destination named — for both exchange forms."""
    rng = np.random.default_rng(seed)
    B = 128
    keys = rng.integers(0, K, size=(D, B)).astype(np.int32)
    dest = (keys % D).astype(np.int32)
    valid = rng.random((D, B)) < 0.8
    cap = bucket_capacity(B, D) if bounded else None
    hist, _rounds = _exchange_hists(D, jnp.asarray(dest), jnp.asarray(keys),
                                    jnp.asarray(valid), cap)
    want = np.bincount(keys[valid], minlength=K)
    np.testing.assert_array_equal(hist.sum(axis=0), want)
    for d in range(D):
        present = np.flatnonzero(hist[d])
        assert all(k % D == d for k in present), (d, present)


def test_bounded_exchange_skew_takes_extra_rounds_losslessly():
    """Full skew (every record to shard 0) with a small round capacity:
    the loop runs ceil(bucket/cap) rounds and still delivers every
    record exactly once."""
    D, B, cap = 4, 96, 16
    keys = np.tile(np.arange(B, dtype=np.int32) % K, (D, 1))
    dest = np.zeros((D, B), np.int32)
    valid = np.ones((D, B), bool)
    hist, rounds = _exchange_hists(D, jnp.asarray(dest), jnp.asarray(keys),
                                   jnp.asarray(valid), cap)
    assert rounds == -(-B // cap)  # 6 rounds for the 96-deep bucket
    assert hist[1:].sum() == 0  # only shard 0 received anything
    np.testing.assert_array_equal(
        hist[0], np.bincount(keys[valid], minlength=K))


def _route_numpy(D, cap, dest, valid, cols, r):
    """Round `r` as a plain loop: what device j holds after the exchange
    is, source by source, the r-th `cap` rows of the source's valid rows
    bound for j in their batch order, zeros behind them."""
    flags = np.zeros((D, D * cap), bool)
    out = {n: np.zeros((D, D * cap) + c.shape[2:], c.dtype)
           for n, c in cols.items()}
    for src in range(D):
        for dst in range(D):
            rows = np.flatnonzero(valid[src] & (dest[src] == dst))[
                r * cap:(r + 1) * cap]
            at = slice(src * cap, src * cap + len(rows))
            flags[dst, at] = True
            for n, c in cols.items():
                out[n][dst, at] = c[src, rows]
    return out, flags


def _packing_case(case, D, B, cap, rng):
    """(dest, valid) [D, B] for one shape of batch; every source slice of
    a case has the same shape, with its own rows."""
    dest = rng.integers(0, D, (D, B)).astype(np.int32)
    valid = np.ones((D, B), bool)
    if case == "two_rounds":        # dest 0 holds cap + 1 rows of a slice
        dest[:, :cap + 1] = 0
        dest[:, cap + 1:] = np.maximum(dest[:, cap + 1:], min(1, D - 1))
    elif case == "full_skew":       # ceil(B / cap) rounds
        dest[:] = D - 1
    elif case == "partly_invalid":
        valid = rng.random((D, B)) < 0.6
    elif case == "wholly_invalid":
        valid[:] = False
    elif case == "ends_at_B":       # the last run is short and ends at B:
        dest = np.sort(dest, axis=1)   # its slice overhangs the batch
        dest[:, -3:] = D - 1
        dest[:, :-3] = np.minimum(dest[:, :-3], max(D - 2, 0))
    else:
        assert case == "uniform"
        dest = np.tile(np.arange(B, dtype=np.int32) % D, (D, 1))
    return dest, valid


@pytest.mark.parametrize("case", ["uniform", "two_rounds", "full_skew",
                                  "partly_invalid", "wholly_invalid",
                                  "ends_at_B"])
@pytest.mark.parametrize("D", [1, 2, 4, 8])
def test_exchange_round_packs_what_a_plain_routing_packs(D, case):
    """Every round's buffers and flags, byte for byte, through the
    collective: the rounds a batch needs and the one past the last, which
    is all-invalid and all-zero."""
    B = 64
    cap = B if D == 1 else B // D + 4
    rng = np.random.default_rng(D * 100 + len(case))
    dest, valid = _packing_case(case, D, B, cap, rng)
    cols = {"k": rng.integers(1, 1 << 62, (D, B)).astype(np.int64),
            "v": rng.integers(1, 1 << 30, (D, B, 2)).astype(np.int32)}
    deepest = max(int(np.sum(valid[s] & (dest[s] == d)))
                  for s in range(D) for d in range(D))
    want_rounds = -(-deepest // cap)
    assert want_rounds == {"uniform": 1, "two_rounds": 1 if D == 1 else 2,
                           "full_skew": -(-B // cap),
                           "wholly_invalid": 0}.get(case, want_rounds)
    R = want_rounds + 1

    def body(dest, valid, k, v):
        plan = plan_exchange(dest[0], valid[0], D, cap)
        ordered = order_payload(plan, {"k": k[0], "v": v[0]}, cap)
        rounds = [exchange_round(DATA_AXIS, D, cap, plan, ordered,
                                 jnp.int32(r)) for r in range(R)]
        return (jnp.stack([o["k"] for o, _ in rounds])[None],
                jnp.stack([o["v"] for o, _ in rounds])[None],
                jnp.stack([f for _, f in rounds])[None],
                jax.lax.pmax(plan.n_rounds, DATA_AXIS)[None])

    fn = shard_map_unchecked(
        body, make_mesh(D), in_specs=(P(DATA_AXIS),) * 4,
        out_specs=(P(DATA_AXIS),) * 4)
    k, v, flags, n_rounds = jax.device_get(jax.jit(fn)(
        jnp.asarray(dest), jnp.asarray(valid), jnp.asarray(cols["k"]),
        jnp.asarray(cols["v"])))
    assert n_rounds.tolist() == [want_rounds] * D
    assert k.dtype == np.int64 and v.dtype == np.int32
    for r in range(R):
        want, want_flags = _route_numpy(D, cap, dest, valid, cols, r)
        np.testing.assert_array_equal(flags[:, r], want_flags)
        np.testing.assert_array_equal(k[:, r], want["k"])
        np.testing.assert_array_equal(v[:, r], want["v"])
    assert not flags[:, -1].any() and not k[:, -1].any()


def test_exchange_round_refuses_columns_padded_for_another_cap():
    """The three calls share one `cap`: columns padded for another would
    let a round's slice clamp back into a neighbour's bucket."""
    dest = jnp.arange(64, dtype=jnp.int32) % 1
    plan = plan_exchange(dest, jnp.ones(64, bool), 1, 16)
    ordered = order_payload(plan, {"k": jnp.arange(64)}, 8)
    with pytest.raises(AssertionError):
        exchange_round(DATA_AXIS, 1, 16, plan, ordered, jnp.int32(0))


def test_bucket_capacity_bounds():
    for B in (32, 256, 4096):
        for D in (1, 2, 8, 64):
            cap = bucket_capacity(B, D)
            assert -(-B // D) <= cap <= B  # covers the mean bucket


# ---------------------------------------------------------------------------
# live rescale: barrier-aligned, exactly-once, recompile-free


def _mesh_op(assigner, n_devices, **kw):
    from flink_tpu.runtime.operators.device_window import AggSpec
    from flink_tpu.runtime.operators.mesh_window import MeshWindowAggOperator
    kw.setdefault("capacity", 1 << 10)
    kw.setdefault("device_batch", 64)
    return MeshWindowAggOperator(
        assigner, "key", [AggSpec("sum", "v", out_name="result")],
        n_devices=n_devices, emit_window_bounds=False, **kw)


def _gen(seed, n, n_keys=40, t_max=10_000):
    rng = np.random.default_rng(seed)
    elements = [(int(k), int(v)) for k, v in
                zip(rng.integers(0, n_keys, n), rng.integers(1, 10, n))]
    ts = sorted(rng.integers(0, t_max, n).tolist())
    return elements, ts


def _drain(h):
    h.process_watermark(10**9)
    h.operator.finish()
    return sorted((int(k), int(v)) for k, v in h.get_output())


@pytest.mark.parametrize("n_before,n_after", [(4, 8), (8, 4)])
def test_live_rescale_exactly_once_and_recompile_free(n_before, n_after):
    """Mid-stream worker-set change at the aligned barrier: output parity
    with an unrescaled run (nothing lost, nothing double-counted) and ZERO
    program-cache misses across the switch — the local-shape cache-key
    contract (JX505) paying off."""
    from flink_tpu.metrics.device import DEVICE_STATS
    from flink_tpu.runtime import OneInputOperatorTestHarness
    from flink_tpu.window import TumblingEventTimeWindows
    w = TumblingEventTimeWindows.of(1000)
    elements, ts = _gen(31, 600)

    h0 = OneInputOperatorTestHarness(_mesh_op(w, n_before), schema=SCHEMA)
    h0.process_elements(elements, ts)
    oracle = _drain(h0)

    op = _mesh_op(w, n_before)
    h = OneInputOperatorTestHarness(op, schema=SCHEMA)
    h.process_elements(elements[:300], ts[:300])
    epoch0 = op._rescale_epoch
    compiles0 = DEVICE_STATS.compiles
    op.request_rescale(n_after)
    snap = op.snapshot_state(7)  # the barrier: rescale applies HERE
    assert snap["keyed"] is not None
    assert op._n_devices == n_after
    assert op._rescale_epoch == epoch0 + 1
    stats = op._last_rescale_stats
    assert stats["new_devices"] == n_after
    assert stats["keygroups_migrated"] > 0
    assert stats["bytes_moved"] > 0
    # the rescale itself compiled nothing: every sharded program was a
    # cache hit (keys carry local shard shapes, never the device count)
    assert DEVICE_STATS.compiles == compiles0
    h.process_elements(elements[300:], ts[300:])
    assert _drain(h) == oracle


def test_live_rescale_two_host_drill():
    """Two subtasks (the two-host split: each owns a key-group range over
    DCN), each live-rescaling its LOCAL device mesh 2 -> 4 mid-stream;
    combined output matches a host-free parity run."""
    from flink_tpu.runtime import OneInputOperatorTestHarness
    from flink_tpu.window import TumblingEventTimeWindows
    w = TumblingEventTimeWindows.of(1000)
    elements, ts = _gen(32, 500, n_keys=30)

    def subtask_rows(h):
        rng = h.ctx.key_group_range if hasattr(h, "ctx") else None
        return [(e, t) for e, t in zip(elements, ts)
                if assign_to_key_group(e[0], 128) in rng]

    outs = []
    for sub in (0, 1):
        op = _mesh_op(w, 2)
        h = OneInputOperatorTestHarness(op, SCHEMA, subtask_index=sub,
                                        parallelism=2, max_parallelism=128)
        own = subtask_rows(h)
        cut = len(own) // 2
        h.process_elements([e for e, _ in own[:cut]],
                           [t for _, t in own[:cut]])
        stats = op.rescale_live(4)
        assert op._n_devices == 4
        assert stats["epoch"] == 1
        # the rescaled shards stay inside this subtask's key-group range
        base = h.ctx.key_group_range
        for r in op._agg.shard_ranges:
            assert r.start >= base.start and r.end <= base.end
        h.process_elements([e for e, _ in own[cut:]],
                           [t for _, t in own[cut:]])
        outs.extend(_drain(h))

    h0 = OneInputOperatorTestHarness(_mesh_op(w, 8), schema=SCHEMA)
    h0.process_elements(elements, ts)
    assert sorted(outs) == _drain(h0)


def test_rescale_disabled_by_config(monkeypatch):
    from flink_tpu.parallel.plan import MESH_RUNTIME
    from flink_tpu.window import TumblingEventTimeWindows
    monkeypatch.setattr(MESH_RUNTIME, "rescale_enabled", False)
    op = _mesh_op(TumblingEventTimeWindows.of(1000), 4)
    with pytest.raises(RuntimeError, match="mesh.rescale.enabled"):
        op.request_rescale(8)


def test_rescale_rejects_mesh_larger_than_range():
    from flink_tpu.runtime import OneInputOperatorTestHarness
    from flink_tpu.window import TumblingEventTimeWindows
    op = _mesh_op(TumblingEventTimeWindows.of(1000), 2)
    h = OneInputOperatorTestHarness(op, SCHEMA, max_parallelism=4)
    h.process_elements([(1, 1)], [10])
    with pytest.raises(ValueError, match="max-parallelism"):
        op.rescale_live(8)


# ---------------------------------------------------------------------------
# driver level: coordinator-driven live rescale of a RUNNING job


def _mesh_env(count=None, rate=50_000, n_devices=4):
    from flink_tpu.api.environment import StreamExecutionEnvironment
    from flink_tpu.connectors.core import CollectSink
    from flink_tpu.core import WatermarkStrategy
    from flink_tpu.runtime.operators.device_window import AggSpec
    from flink_tpu.window import TumblingEventTimeWindows

    env = StreamExecutionEnvironment()
    env.enable_checkpointing(600.0)  # aligned mode on; periodic ~never
    ws = WatermarkStrategy.for_monotonous_timestamps() \
        .with_timestamp_column("ts")
    schema = Schema([("key", np.int64), ("v", np.int64), ("ts", np.int64)])
    sink = CollectSink()

    def gen(idx):
        return {"key": idx % 40, "v": np.ones_like(idx), "ts": idx * 3}

    (env.datagen(gen, schema, count=count, rate_per_sec=rate,
                 timestamp_column="ts", watermark_strategy=ws)
        .key_by("key")
        .window(TumblingEventTimeWindows.of(1000))
        .mesh_aggregate([AggSpec("sum", "v", out_name="total")],
                        n_devices=n_devices, capacity=1 << 10,
                        device_batch=64)
        .add_sink(sink, "collect"))
    return env, sink


def test_live_rescale_driver_on_running_job():
    from flink_tpu.cluster.local import live_rescale
    env, _sink = _mesh_env()
    job = env.execute_async("live-rescale-drill")
    try:
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            ops = [op for t in job.tasks.values()
                   for op in getattr(t.chain, "operators", ())
                   if hasattr(op, "request_rescale")]
            if ops and ops[0]._agg is not None:
                break
            time.sleep(0.05)
        stats = live_rescale(job, 8, timeout=60)
        assert stats["new_devices"] == 8
        assert stats["epoch"] >= 1
        assert all(op._n_devices == 8 for op in ops)
        time.sleep(0.2)  # keep folding on the new worker set
    finally:
        job.cancel()
        for t in job.tasks.values():
            t.join(30)  # let XLA dispatches drain before interpreter exit


def test_live_rescale_driver_requires_mesh_operators():
    from flink_tpu.api.environment import StreamExecutionEnvironment
    from flink_tpu.cluster.local import deploy_local, live_rescale
    from flink_tpu.connectors.core import CollectSink
    env = StreamExecutionEnvironment()
    schema = Schema([("key", np.int64)])
    env.datagen(lambda i: {"key": i}, schema, count=10) \
       .add_sink(CollectSink(), "s")
    job = deploy_local(env.get_job_graph("no-mesh"), env.config)
    with pytest.raises(ValueError, match="no mesh operators"):
        live_rescale(job, 8)
