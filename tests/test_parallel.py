"""Multi-chip sharded execution on the 8-device virtual CPU mesh — the
MiniCluster-analog tier (SURVEY.md §4 tier 3): real collectives, real
sharding, one process."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flink_tpu.core.keygroups import (assign_to_key_group, hash_batch,
                                      key_groups_for_hash_batch,
                                      operator_index_for_key_group)
from flink_tpu.parallel import (AggDef, ShardedWindowAgg, global_topk,
                                key_groups_device, make_mesh, shard_ranges)
from flink_tpu.parallel.mesh import device_index_for_key_groups

from flink_tpu.ops.hash_table import ensure_x64
from flink_tpu.ops.segment_ops import Halves

ensure_x64()  # int64 keys on device (flipped before any test array exists)

MP = 128


def test_device_key_groups_match_host():
    keys = np.concatenate([
        np.arange(-50, 50, dtype=np.int64),
        np.random.RandomState(0).randint(-2**62, 2**62, 500, dtype=np.int64),
    ])
    host = key_groups_for_hash_batch(hash_batch(keys), MP)
    dev = np.asarray(jax.device_get(key_groups_device(jnp.asarray(keys), MP)))
    np.testing.assert_array_equal(host, dev)
    # spot-check the scalar path too
    for k in [0, 1, -1, 2**40, -(2**40)]:
        assert assign_to_key_group(int(k), MP) == int(
            jax.device_get(key_groups_device(jnp.asarray([k]), MP))[0])


def test_device_index_matches_host():
    kg = jnp.arange(MP, dtype=jnp.int32)
    dev = np.asarray(jax.device_get(device_index_for_key_groups(kg, 8, MP)))
    host = np.array([operator_index_for_key_group(MP, 8, g)
                     for g in range(MP)])
    np.testing.assert_array_equal(host, dev)


def _host_window_sums(keys, vals, panes):
    out = {}
    for k, v, p in zip(keys, vals, panes):
        out.setdefault((int(k), int(p)), [0, 0.0])
        out[(int(k), int(p))][0] += 1
        out[(int(k), int(p))][1] += float(v)
    return out


@pytest.fixture
def agg8():
    mesh = make_mesh(8)
    # these tests read the count, so they declare it (a caller that
    # declares none gets a 32-bit presence plane: test_presence_plane.py)
    return mesh, ShardedWindowAgg(
        mesh, [AggDef("price", "sum", jnp.float64),
               AggDef("__count__", "count", jnp.int64)],
        capacity=1 << 12, ring=8, max_parallelism=MP)


def test_sharded_step_matches_host(agg8):
    mesh, agg = agg8
    rng = np.random.RandomState(42)
    D, B = 8, 64
    state = agg.init_state()
    all_k, all_v, all_p = [], [], []
    for _ in range(5):
        keys = rng.randint(0, 1000, (D, B)).astype(np.int64)
        vals = rng.rand(D, B)
        panes = rng.randint(0, 4, (D, B)).astype(np.int64)
        valid = rng.rand(D, B) < 0.9
        all_k.append(keys[valid]); all_v.append(vals[valid])
        all_p.append(panes[valid])
        state, processed, _rounds, _limbs = agg.step(
            state, jnp.asarray(keys), {"price": jnp.asarray(vals)},
            jnp.asarray(panes), jnp.asarray(valid))
        assert int(processed) == int(valid.sum())
    assert int(jax.device_get(state.dropped).sum()) == 0

    keys = np.concatenate(all_k); vals = np.concatenate(all_v)
    panes = np.concatenate(all_p)
    expected = _host_window_sums(keys, vals, panes)

    # every key must live on the shard owning its key group
    table = np.asarray(jax.device_get(state.table))
    ranges = shard_ranges(MP, 8)
    for d in range(8):
        present = table[d][table[d] != np.iinfo(np.int64).max]
        for k in present:
            assert assign_to_key_group(int(k), MP) in ranges[d]

    # single-pane fire: pane p alone -> per (key, pane) sums
    for p in range(4):
        out, emit = agg.fire(state, np.array([p % agg.ring], np.int32))
        emit_np = np.asarray(jax.device_get(emit))
        counts = np.asarray(jax.device_get(out["__count__"]))
        sums = np.asarray(jax.device_get(out["price"]))
        got = {}
        for d in range(8):
            for s in np.flatnonzero(emit_np[d]):
                got[int(table[d, s])] = (int(counts[d, s]),
                                         float(sums[d, s]))
        want = {k: tuple(v) for (k, pp), v in expected.items() if pp == p}
        assert set(got) == set(want)
        for k in want:
            assert got[k][0] == want[k][0]
            np.testing.assert_allclose(got[k][1], want[k][1], rtol=1e-9)


def test_fire_merges_panes_and_retire(agg8):
    mesh, agg = agg8
    state = agg.init_state()
    D, B = 8, 16
    keys = np.tile(np.arange(B, dtype=np.int64), (D, 1))
    vals = np.ones((D, B))
    for pane in (0, 1, 2):
        panes = np.full((D, B), pane, np.int64)
        state, *_ = agg.step(state, jnp.asarray(keys),
                            {"price": jnp.asarray(vals)},
                            jnp.asarray(panes),
                            jnp.ones((D, B), bool))
    # window = panes {0,1}: each key appears D times per pane
    out, emit = agg.fire(state, np.array([0, 1], np.int32))
    counts = np.asarray(jax.device_get(out["__count__"]))
    assert counts[np.asarray(jax.device_get(emit))].sum() == 2 * D * B
    # retire pane 0 -> only pane 1 remains in a {0,1} fire
    state = agg.retire_row(state, 0)
    out, emit = agg.fire(state, np.array([0, 1], np.int32))
    counts = np.asarray(jax.device_get(out["__count__"]))
    assert counts[np.asarray(jax.device_get(emit))].sum() == D * B


def test_overflow_reports_dropped():
    mesh = make_mesh(8)
    agg = ShardedWindowAgg(mesh, [AggDef("v", "sum", jnp.float64)],
                           capacity=8, ring=2, max_parallelism=MP)
    state = agg.init_state()
    D, B = 8, 64
    rng = np.random.RandomState(1)
    keys = rng.randint(0, 10**9, (D, B)).astype(np.int64)
    state, processed, _rounds, _limbs = agg.step(
        state, jnp.asarray(keys), {"v": jnp.ones((D, B))},
        jnp.zeros((D, B), np.int64), jnp.ones((D, B), bool))
    dropped = int(jax.device_get(state.dropped).sum())
    assert dropped > 0
    assert int(processed) + dropped == D * B


# -- the state is built where it lives and donated through the step (PR 27) --

@pytest.mark.parametrize("n_dev", [4, 8])
def test_init_state_is_built_shard_by_shard(n_dev):
    """Same values, shardings and pytree as a tiled state put on the mesh,
    and no buffer beyond a device's own shards while it is built: the
    initialiser's outputs ARE the shards and it has no temporaries."""
    from flink_tpu.ops.hash_table import EMPTY_KEY
    from flink_tpu.parallel.sharded_window import ShardedWindowState

    cap, ring = 1 << 10, 8
    agg = ShardedWindowAgg(
        make_mesh(n_dev), [AggDef("bids", "count", jnp.int64),
                           AggDef("lo", "min", jnp.int32),
                           AggDef("hi", "max", jnp.float32)],
        capacity=cap, ring=ring, max_parallelism=MP)
    state = agg.init_state()
    assert isinstance(state, ShardedWindowState)
    assert set(state.accs) == {"bids", "lo", "hi"}
    want = {"table": ((n_dev, cap), np.int64, EMPTY_KEY),
            "bids": ((n_dev, ring, cap), np.int64, 0),
            "lo": ((n_dev, ring, cap), np.int32, np.iinfo(np.int32).max),
            "hi": ((n_dev, ring, cap), np.float32,
                   np.finfo(np.float32).min),
            "dropped": ((n_dev,), np.int64, 0)}
    leaves = {"table": state.table, "dropped": state.dropped, **state.accs}
    # a 64-bit integer plane is kept as its two uint32 words; a narrower
    # or a float plane stays one array
    assert {n: isinstance(p, Halves) for n, p in state.accs.items()} \
        == {"bids": True, "lo": False, "hi": False}
    for name, (shape, dtype, value) in want.items():
        leaf = leaves[name]
        assert leaf.shape == shape and leaf.dtype == dtype, name
        assert (np.asarray(jax.device_get(leaf)) == value).all(), name
        for word in jax.tree.leaves(leaf):
            assert word.dtype == (np.uint32 if isinstance(leaf, Halves)
                                  else dtype), name
            assert word.sharding == agg.plan.state_sharding, name
            shards = word.addressable_shards
            assert sorted(s.device.id for s in shards) == sorted(
                d.id for d in agg.mesh.devices.flat), name
            assert all(s.data.shape == (1,) + shape[1:]
                       for s in shards), name
    compiled = agg.init_program().lower().compile()
    for sharding in jax.tree.leaves(compiled.output_shardings):
        assert sharding == agg.plan.state_sharding
    mem = compiled.memory_analysis()
    shard_bytes = sum(int(np.prod(shape[1:])) * np.dtype(dtype).itemsize
                      for shape, dtype, _v in want.values())
    assert mem.temp_size_in_bytes == 0
    assert mem.output_size_in_bytes <= shard_bytes + 1024


def _one_step(agg, state, keys, panes=None):
    D, B = keys.shape
    return agg.step(
        state, jnp.asarray(keys), {"price": jnp.ones((D, B))},
        jnp.zeros((D, B), np.int64) if panes is None else panes,
        jnp.ones((D, B), bool))


def test_step_and_retire_donate_the_state(agg8):
    _mesh, agg = agg8
    keys = np.arange(8 * 32, dtype=np.int64).reshape(8, 32)
    old = agg.init_state()
    new, processed, rounds, _limbs = _one_step(agg, old, keys)
    assert int(processed) == keys.size and int(rounds) >= 1
    for leaf in jax.tree.leaves(old):
        assert leaf.is_deleted()
    with pytest.raises(RuntimeError, match="deleted"):
        np.asarray(old.table)
    # a fire enqueued on a state stays readable after that state has been
    # stepped on (donated) and retired: programs already enqueued on the
    # old buffers stay valid, only Python handles on them do not
    out, emit = agg.fire(new, np.array([0], np.int32))
    newer, _p, _r, _limbs = _one_step(agg, new, keys)
    retired = agg.retire_row(newer, 0)
    assert all(leaf.is_deleted() for leaf in jax.tree.leaves(newer.accs))
    assert not retired.table.is_deleted()
    counts = np.asarray(jax.device_get(out["__count__"]))
    assert counts[np.asarray(jax.device_get(emit))].sum() == keys.size
    out, emit = agg.fire(retired, np.array([0], np.int32))
    assert not np.asarray(jax.device_get(emit)).any()


def test_unranked_fire_hands_back_a_table_of_its_own(agg8):
    """Without a top-k the fused fire returns the key table; it must be a
    copy, or the next step would donate the buffer under the pending
    fire."""
    _mesh, agg = agg8
    keys = np.arange(8 * 16, dtype=np.int64).reshape(8, 16)
    state, _p, _r, _limbs = _one_step(agg, agg.init_state(), keys)
    table, emit, _res, _dropped, _occ = agg.fire_compact(
        state, np.array([0], np.int32), np.array([True]), None, None)
    state, _p, _r, _limbs = _one_step(agg, state, keys)
    got = np.asarray(jax.device_get(table))[np.asarray(
        jax.device_get(emit))]
    assert sorted(got.tolist()) == list(range(keys.size))


@pytest.mark.parametrize("n_dev", [4, 8])
def test_skewed_batch_takes_more_rounds_and_loses_nothing(n_dev):
    """Every row of a [D, B] block bound for ONE shard: the exchange takes
    ceil(B / round capacity) rounds (the deepest bucket is a whole slice),
    a spread block takes one, and both fold every row."""
    from flink_tpu.parallel import bucket_capacity

    B = 256
    agg = ShardedWindowAgg(
        make_mesh(n_dev), [AggDef("price", "sum", jnp.float64),
                           AggDef("__count__", "count", jnp.int64)],
        capacity=1 << 12, ring=4, max_parallelism=MP)
    pool = np.arange(20_000, dtype=np.int64)
    groups = key_groups_for_hash_batch(hash_batch(pool), MP)
    mine = pool[(groups >= agg.shard_ranges[1].start)
                & (groups <= agg.shard_ranges[1].end)][:97]
    skewed = np.resize(mine, (n_dev, B))
    state, processed, rounds, _limbs = _one_step(agg, agg.init_state(), skewed)
    cap_x = bucket_capacity(B, n_dev)
    assert int(rounds) == -(-B // cap_x) > 1
    assert int(processed) == n_dev * B
    assert int(jax.device_get(state.dropped).sum()) == 0
    table = np.asarray(jax.device_get(state.table))
    assert sorted(table[1][table[1] != np.iinfo(np.int64).max]) \
        == sorted(mine)
    out, emit = agg.fire(state, np.array([0], np.int32))
    counts = np.asarray(jax.device_get(out["__count__"]))
    emit = np.asarray(jax.device_get(emit))
    assert counts[emit].sum() == n_dev * B and not emit[0].any()
    # the same rows spread round-robin over their owners take one round
    spread = pool[:n_dev * B].reshape(n_dev, B)
    state, processed, rounds, _limbs = _one_step(agg, state, spread)
    assert int(rounds) == 1 and int(processed) == n_dev * B


def test_global_topk():
    vals = jnp.asarray(np.arange(64, dtype=np.float32).reshape(8, 8))
    valid = jnp.ones((8, 8), bool).at[7, 7].set(False)  # mask the max
    v, idx, ok, _passes, _sort = global_topk(vals, valid, 3)
    np.testing.assert_array_equal(np.asarray(jax.device_get(v)),
                                  [62.0, 61.0, 60.0])
    np.testing.assert_array_equal(np.asarray(jax.device_get(idx)),
                                  [62, 61, 60])
    assert np.asarray(jax.device_get(ok)).all()


def test_global_topk_fewer_valid_than_k():
    vals = jnp.asarray(np.arange(16, dtype=np.int64).reshape(4, 4))
    valid = jnp.zeros((4, 4), bool).at[1, 2].set(True).at[2, 3].set(True)
    v, idx, ok, _passes, _sort = global_topk(vals, valid, 5)
    ok_h = np.asarray(jax.device_get(ok))
    assert ok_h.sum() == 2
    kept = np.asarray(jax.device_get(idx))[ok_h]
    np.testing.assert_array_equal(sorted(kept), [6, 11])


# -- the per-shard threshold select behind global_topk (PR 31) ---------------

def _topk_case(name, D, cap=512):
    """([D, cap] int64 ranks, valid, k): COUNT-like data with the named
    feature. The last shard of a mesh is the odd one out."""
    rng = np.random.default_rng(31 + D)
    vals = rng.integers(0, 30, (D, cap)).astype(np.int64)
    valid = rng.random((D, cap)) < 0.7
    k = 40
    if name == "ties_at_kth":
        vals[:, :5] = 900 + np.arange(5)
        vals[:, 5:200] = 77
        valid[:, :200] = True
    elif name == "fewer_valid_than_k":
        valid[:] = False
        valid[:, 3::97] = True
    elif name == "an_all_zero_shard":
        vals[-1] = 0
    elif name == "one_shard_holds_every_winner":
        vals[:] = vals % 7
        vals[-1, :k + 9] = 5000 + np.arange(k + 9)
        valid[-1, :k + 9] = True
    elif name == "max_at_2p32":
        vals[-1, 17] = 1 << 32
        valid[-1, 17] = True
    elif name == "max_above_2p32":
        vals[-1, 17:30] = (1 << 35) + np.arange(13)
        valid[-1, 17:30] = True
    elif name == "a_negative_valid_value":
        vals[-1, 17] = -4
        valid[-1, 17] = True
    elif name == "a_float_rank":
        vals = (vals + rng.random((D, cap))).astype(np.float32)
    else:
        raise ValueError(name)
    return vals, valid, k


TOPK_CASES = ["ties_at_kth", "fewer_valid_than_k", "an_all_zero_shard",
              "one_shard_holds_every_winner", "max_at_2p32",
              "max_above_2p32", "a_negative_valid_value", "a_float_rank"]


@pytest.mark.parametrize("D", [1, 4])
@pytest.mark.parametrize("name", TOPK_CASES)
def test_global_topk_selects_by_threshold_on_every_shard(name, D):
    """Against numpy, under the mesh's shard_map: the values are the k
    largest valid ones (ties at the k-th free), each index carries its
    value, and the passes handed back are the bit length of the largest
    valid rank: the select walks the bits the data has, not the 64 its
    dtype declares (a shard that holds a negative valid value walks all
    64 of its sign-flipped view, PR 33). Only a float rank takes the
    sort, and DEVICE_STATS counts exactly those."""
    from flink_tpu.metrics import DEVICE_STATS

    vals, valid, k = _topk_case(name, D)
    v, idx, ok, passes, sort = jax.device_get(global_topk(
        jnp.asarray(vals), jnp.asarray(valid), k, make_mesh(D)))
    want = np.sort(vals[valid])[::-1][:k]
    np.testing.assert_array_equal(v[ok], want)
    assert ok.sum() == len(want) and ok[:len(want)].all()
    assert len(np.unique(idx[ok])) == ok.sum()
    np.testing.assert_array_equal(vals.reshape(-1)[idx[ok]], v[ok])
    assert valid.reshape(-1)[idx[ok]].all()
    takes_sort = name == "a_float_rank"
    assert bool(sort) == takes_sort
    if takes_sort:
        assert passes == 0
    else:
        walked = [64 if vals[d][valid[d]].min() < 0
                  else int(vals[d][valid[d]].max()).bit_length()
                  for d in range(D) if valid[d].any()]
        assert passes == max(walked, default=0)
    before = DEVICE_STATS.snapshot()
    DEVICE_STATS.note_fire_select(passes, sort)
    after = DEVICE_STATS.snapshot()
    assert after["fire_selects_total"] - before["fire_selects_total"] == 1
    assert after["fire_select_passes_total"] \
        - before["fire_select_passes_total"] == passes
    assert after["fire_select_sort_total"] \
        - before["fire_select_sort_total"] == int(takes_sort)


def test_global_topk_without_a_mesh_is_the_same_select():
    vals, valid, k = _topk_case("ties_at_kth", 4)
    with_mesh = jax.device_get(global_topk(
        jnp.asarray(vals), jnp.asarray(valid), k, make_mesh(4)))
    without = jax.device_get(global_topk(
        jnp.asarray(vals), jnp.asarray(valid), k))
    for a, b in zip(with_mesh, without):
        np.testing.assert_array_equal(a, b)


def test_a_count_rank_compiles_no_sort_over_a_shards_slots():
    """An integer rank compiles no sort over the slots, with the promise
    a COUNT keeps (never negative: value_bits under the dtype's width) or
    without it (the guard is the same walk over the sign-flipped view,
    PR 33)."""
    import re

    D, cap, k = 4, 4096, 16

    args = (jnp.zeros((D, cap), jnp.int64), jnp.zeros((D, cap), bool))

    def widest_sort(value_bits):
        hlo = global_topk.lower(*args, k, make_mesh(D), "data",
                                value_bits).compile().as_text()
        return max((int(m.group(1)) for m in re.finditer(
            r"\[(\d+)\][^\n]* sort\(", hlo)), default=0)

    assert widest_sort(63) <= D * k
    assert widest_sort(64) <= D * k


# -- what a v5e refuses to lower (found on the chip, PR 22) ------------------
# Every job runs with x64 on, and the TPU all-reduces a 64-bit value only
# for sum: `lax.pmax` of an int64 failed with "UNIMPLEMENTED: Supported
# lowering only of Sum all reduce", on a mesh of ONE device already.

def test_plan_exchange_is_int32_under_x64():
    from flink_tpu.parallel.exchange import plan_exchange

    assert jax.config.jax_enable_x64
    dest = jnp.arange(64, dtype=jnp.int32) % 3
    plan = plan_exchange(dest, jnp.arange(64) % 7 != 0, 3, 16)
    assert {f: getattr(plan, f).dtype.name for f in plan._fields} == {
        "order": "int32", "counts": "int32", "offsets": "int32",
        "n_rounds": "int32"}
    assert plan.counts.tolist() == [18, 18, 18]    # the valid rows a shard
    assert plan.offsets.tolist() == [0, 18, 36]
    assert int(plan.n_rounds) == 2          # ceil(18 / 16)


@pytest.mark.parametrize("n_dev", [1, 2])
def test_mesh_step_has_no_64bit_nonsum_collective(n_dev):
    from flink_tpu.analysis.jaxpr_rules import _iter_eqns

    agg = ShardedWindowAgg(
        make_mesh(n_dev), [AggDef("bids", "count", jnp.int64),
                           AggDef("revenue", "sum", jnp.int64)],
        capacity=1 << 8, ring=8, max_parallelism=MP)
    D, B = n_dev, 32
    jaxpr = jax.make_jaxpr(agg.step)(
        agg.init_state(), jnp.zeros((D, B), jnp.int64),
        {"revenue": jnp.zeros((D, B), jnp.int64)},
        jnp.zeros((D, B), jnp.int64), jnp.ones((D, B), bool))
    collectives = [e for e in _iter_eqns(jaxpr.jaxpr)
                   if e.primitive.name in ("pmax", "pmin", "all_gather")]
    assert any(e.primitive.name == "pmax" for e in collectives)
    wide = [(e.primitive.name, v.aval.dtype.name) for e in collectives
            for v in e.invars if v.aval.dtype.itemsize > 4]
    assert not wide, wide


#: the ring of the fold's parity test
_FOLD_RING = 4


def _fold_block(name, rng, D, B, hot_shard_keys):
    """(keys, panes, valid) of the [D, B] block the parity test calls
    ``name``; ``hot_shard_keys`` are keys one shard owns."""
    keys = rng.randint(0, 300, (D, B)).astype(np.int64)
    panes = np.full((D, B), 9, np.int64)
    valid = np.ones((D, B), bool)
    if name == "no_ring_row":
        valid[:] = False
    elif name == "two_ring_rows":
        # event-time order: the later pane at the tail of every slice
        panes[:, B - B // 3:] = 10
    elif name == "every_ring_row":
        panes = rng.randint(8, 8 + 2 * _FOLD_RING, (D, B)).astype(np.int64)
    elif name == "padded_tail":
        panes[:, B // 2:] = 10
        valid.reshape(-1)[D * B - (D * B) // 3:] = False
    elif name == "one_key":
        keys[:] = 77
        panes = rng.randint(8, 8 + _FOLD_RING, (D, B)).astype(np.int64)
    elif name == "hot_shard":
        keys = np.resize(hot_shard_keys, (D, B))
        panes[:, B // 2:] = 10
    else:
        assert name == "one_ring_row"
    return keys, panes, valid


_FOLD_KINDS = {"total": "sum", "n": "count", "low": "min", "high": "max"}


def _fold_per_record(folded: dict, keys, panes, vals, valid) -> None:
    """The reference: one record at a time into {(key, ring row): [sum,
    count, min, max]}."""
    for k, p, v, ok in zip(keys.ravel(), panes.ravel(), vals.ravel(),
                           valid.ravel()):
        if not ok:
            continue
        cell = folded.setdefault((int(k), int(p) % _FOLD_RING),
                                 [0, 0, int(v), int(v)])
        cell[0] += int(v)
        cell[1] += 1
        cell[2] = min(cell[2], int(v))
        cell[3] = max(cell[3], int(v))


@pytest.mark.parametrize("n_dev", [1, 4])
@pytest.mark.parametrize("block", [
    "no_ring_row", "one_ring_row", "two_ring_rows", "every_ring_row",
    "padded_tail", "one_key", "hot_shard"])
def test_mesh_step_folds_a_block_like_a_per_record_fold(block, n_dev):
    """The step's fold (`ring_fold` on every shard's [ring, capacity]
    planes, inside the exchange rounds' `while_loop`) against a per-record
    numpy fold, exact, for sum, count, min and max at once: a block that
    touches 0, 1, 2 and all ring rows, a padded tail, every row on one
    key, and a hot shard whose rows need a second exchange round, so that
    the fold runs again on the planes round one left. Each case folds
    into planes an earlier block has already written."""
    from flink_tpu.parallel import bucket_capacity

    D, B, cap = n_dev, 64, 1 << 10
    agg = ShardedWindowAgg(
        make_mesh(n_dev),
        [AggDef(name, kind, jnp.int64) for name, kind in _FOLD_KINDS.items()],
        capacity=cap, ring=_FOLD_RING, max_parallelism=MP)
    pool = np.arange(20_000, dtype=np.int64)
    groups = key_groups_for_hash_batch(hash_batch(pool), MP)
    owner = agg.shard_ranges[-1]
    hot_shard_keys = pool[(groups >= owner.start)
                          & (groups <= owner.end)][:41]
    rng = np.random.RandomState(36)
    folded: dict = {}
    state = agg.init_state()
    for name in ("every_ring_row", block):
        keys, panes, valid = _fold_block(name, rng, D, B, hot_shard_keys)
        vals = rng.randint(-1000, 1000, (D, B)).astype(np.int64)
        _fold_per_record(folded, keys, panes, vals, valid)
        cols = {n: jnp.asarray(vals) for n, kind in _FOLD_KINDS.items()
                if kind != "count"}
        state, processed, rounds, _limbs = agg.step(
            state, jnp.asarray(keys), cols, jnp.asarray(panes),
            jnp.asarray(valid))
        assert int(processed) == int(valid.sum())
    if block in ("hot_shard", "one_key") and n_dev > 1:
        # every slice's rows are bound for one shard
        assert int(rounds) == -(-B // bucket_capacity(B, n_dev)) > 1
    else:
        assert int(rounds) == (block != "no_ring_row")
    assert int(jax.device_get(state.dropped).sum()) == 0

    table = np.asarray(jax.device_get(state.table))
    planes = {n: np.asarray(jax.device_get(state.accs[n]))
              for n in _FOLD_KINDS}
    got = {}
    for d, s in zip(*np.nonzero(table != np.iinfo(np.int64).max)):
        for r in range(_FOLD_RING):
            if planes["n"][d, r, s]:
                got[(int(table[d, s]), r)] = [
                    int(planes[n][d, r, s]) for n in _FOLD_KINDS]
    assert got == folded
    # every cell no record fell in still holds its identity
    assert planes["n"].sum() == sum(c[1] for c in folded.values())
    untouched = planes["n"] == 0
    assert (planes["total"][untouched] == 0).all()
    assert (planes["low"][untouched] == np.iinfo(np.int64).max).all()
    assert (planes["high"][untouched] == np.iinfo(np.int64).min).all()
