"""Sharded slice-window aggregation: the multi-chip north-star path.

One compiled step per micro-batch over the WHOLE mesh (SURVEY.md §2.10
data-parallelism row + §5.8): every device holds the keyed state for its
contiguous key-group range (mesh.shard_ranges); a step is

    key-group routing (murmur parity with the host)   ->
    capacity-bounded `all_to_all` keyBy exchange over ICI
    (one round for a uniform batch; skew adds rounds)  ->
    device hash-table lookup-or-insert per shard      ->
    one fold per aggregate into the shard's [ring, cap] pane accumulators,
    ring row by ring row (ops/segment_ops.ring_fold: the plane stays
    tiled, no flat view of it is taken)

which replaces the reference's per-record WindowOperator.processElement:278 /
KeyGroupStreamPartitioner / Netty channel pipeline. Window fire is one pane
merge over all keys of every shard (SliceSharedWindowAggProcessor semantics);
cross-shard post-aggregations (Nexmark Q5 global hot items) are two-phase:
per-shard top-k then a tiny gather — the
StreamExecLocal/GlobalGroupAggregate split.

Everything here is functional: state is a pytree whose leaves carry a leading
device axis sharded per the ShardingPlan's partition rules, steps compile
through shard_map/pjit, and the host only touches scalars (watermarks, pane
boundaries) — the control plane of the DeviceWindowAggOperator, lifted to N
chips.

Program caching (the rescale-critical invariant, JX505): every builder below
is a module-level `instrumented_program_cache` keyed by
``local_signature(aggs, capacity, ring)`` — the per-device shard shapes and
dtypes, NEVER the device count or a global ``[D, ...]`` shape. All devices
run the same SPMD program, so two meshes with equal local shards share one
cache entry; a live rescale that preserves local shapes recompiles nothing
(the step's key-group ownership bounds are traced arguments, not baked
constants, so even re-pointing a mesh at a different subtask range is free).
The step's shard_map program additionally binds per concrete Mesh inside its
cache entry — changing the axis SIZE lowers new collectives once per size,
while changing device identities or ownership at a fixed size re-dispatches
the already-built program.
"""

from __future__ import annotations

import functools
from typing import Any, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..metrics.device import instrumented_program_cache
from ..ops.hash_table import EMPTY_KEY, ensure_x64, lookup_or_insert
from ..ops.segment_ops import AGG_INITS, AGG_MERGES, COUNT_KINDS, \
    folds_by_limbs, make_plane, plane_identity, plane_map, plane_take, \
    ring_fold, stores_halves
from ..ops.topk import masked_topk_sort, threshold_topk
from ..state.tpu_backend import reclaim_shard
from .exchange import (bucket_capacity, exchange_round, order_payload,
                       plan_exchange)
from .mesh import DATA_AXIS, device_index_for_key_groups, \
    key_groups_device, shard_ranges
from .plan import ShardingPlan, match_partition_rules, \
    shard_map_unchecked

__all__ = ["AggDef", "ShardedWindowState", "ShardedWindowAgg",
           "global_topk", "local_signature"]


class AggDef(NamedTuple):
    """One aggregate accumulator: kind in sum|count|presence|min|max.

    ``count`` and ``presence`` (``ops/segment_ops.COUNT_KINDS``: every row
    folds a one) need no input column; others fold the column named
    ``name`` from the step's value dict. (avg = sum + count at fire, like
    the reference's AggregateFunction.getResult —
    AggregateFunction.java:114.)
    """
    name: str
    kind: str
    dtype: Any = jnp.float32


class ShardedWindowState(NamedTuple):
    """Pytree of device arrays; leading axis = mesh position ("data")."""
    table: jax.Array            # [D, capacity] int64 key table
    accs: dict                  # name -> [D, ring, capacity]; a 64-bit
    #                             integer plane as its two uint32 words
    #                             (ops/segment_ops.Halves)
    dropped: jax.Array          # [D] int64 records lost to table overflow


def _sanitize(keys: jax.Array) -> jax.Array:
    return jnp.where(keys == jnp.int64(EMPTY_KEY),
                     jnp.int64(EMPTY_KEY) - 1, keys.astype(jnp.int64))


# ----------------------------------------------------------------------
# local-shard program-cache keys
# ----------------------------------------------------------------------

def local_signature(aggs: Sequence[AggDef], capacity: int, ring: int
                    ) -> tuple:
    """The canonical program-cache key: aggregate schema + per-device
    shard dims. Fully determines every local leaf — table [1, capacity]
    int64, accs [1, ring, capacity] per dtype, a 64-bit integer one as
    two uint32 words (``stores_halves``: decided by the dtype, so the key
    needs no word for it), dropped [1] int64 — and is invariant under
    device count and mesh identity, which is what lets a rescale hit
    every cached program (JX505 pins this contract)."""
    return ("local",
            tuple((a.name, a.kind, np.dtype(a.dtype).name) for a in aggs),
            int(capacity), int(ring))


def _aggs_from_sig(agg_sig) -> list[AggDef]:
    return [AggDef(name, kind, np.dtype(dt)) for name, kind, dt in agg_sig]


def _count_name(agg_sig) -> str:
    """The plane a fire's emit mask reads (a key emits iff its merge is
    positive): the first count, or the presence plane that stands in
    where the caller declared none."""
    return next(name for name, kind, _ in agg_sig if kind in COUNT_KINDS)


# ----------------------------------------------------------------------
# module-level program builders (shared across instances and meshes)
# ----------------------------------------------------------------------

def _make_init(sig, rules: tuple, mesh: Mesh):
    """The jitted initialiser of the empty state on ``mesh``: its
    ``out_shardings`` are the plan's, so every device fills only its own
    ``[1, ...]`` shard and no device ever holds a global-sized array (a
    [4, 16, 2^23] int64 plane tiled on one device and then cut is 4.3 GB
    and a second copy while it is cut; the shard is 1.07 GB). A 64-bit
    integer plane is built as its two ``uint32`` words (a sharding per
    plane is a prefix of its two leaves): no device ever holds an int64
    plane."""
    _, agg_sig, cap, ring = sig
    aggs = _aggs_from_sig(agg_sig)
    # lint: sync-ok mesh.devices is a host numpy array of Device objects
    D = int(mesh.devices.size)
    skel = {"table": 0, "accs": {a.name: 0 for a in aggs}, "dropped": 0}
    sp = match_partition_rules(rules, skel)
    shardings = jax.tree.map(
        lambda spec: NamedSharding(mesh, spec),
        ShardedWindowState(sp["table"], sp["accs"], sp["dropped"]),
        is_leaf=lambda x: isinstance(x, P))

    def init() -> ShardedWindowState:
        return ShardedWindowState(
            jnp.full((D, cap), EMPTY_KEY, jnp.int64),
            {a.name: make_plane(a.kind, (D, ring, cap), a.dtype,
                                stores_halves(a.dtype, ring))
             for a in aggs},
            jnp.zeros(D, jnp.int64))

    return jax.jit(init, out_shardings=shardings)


def _shard_plane(block):
    """A shard's own ``[ring, capacity]`` plane out of the ``[1, ring,
    capacity]`` block ``shard_map`` hands its body: each word of a
    ``Halves`` plane, which stays the two words it is stored as."""
    return plane_map(lambda words: words[0], block)


def _mesh_plane(plane):
    """``_shard_plane`` undone, for the body's result."""
    return plane_map(lambda words: words[None], plane)


def _per_mesh(make):
    """``dispatch(mesh, *args)`` over programs built once per concrete
    Mesh by ``make(mesh)``: how a builder whose cache key is local-shape-
    only holds the executables that close over a mesh."""
    bound: dict = {}
    prepared: dict = {}

    def program(mesh: Mesh):
        prog = bound.get(mesh)
        if prog is None:
            prog = bound[mesh] = make(mesh)
        return prog

    def dispatch(mesh: Mesh, *args):
        return (prepared.get(mesh) or program(mesh))(*args)

    def prepare(mesh: Mesh, *args) -> None:
        """Compile ``mesh``'s program now for ``args`` (the arrays it
        will be called with, or their ``ShapeDtypeStruct``s with the
        shardings); its dispatches then run that executable. What
        ``metrics/device._TimedProgram.prepare`` asks of a program that
        keeps its executables itself."""
        if mesh not in prepared:
            prepared[mesh] = program(mesh).lower(*args).compile()

    # what `metrics/device.program_regions` asks of an audited program
    dispatch.lower = lambda mesh, *args: program(mesh).lower(*args)
    dispatch.prepare = prepare
    return dispatch


@instrumented_program_cache("mesh.init")
def _init_program(sig, rules: tuple):
    """Builds the empty state; binds per concrete Mesh inside this one
    cache entry, like the step."""
    return _per_mesh(lambda mesh: _make_init(sig, rules, mesh))


def _make_step(sig, max_parallelism: int, axis_name: str, rules: tuple,
               mesh: Mesh):
    """The jitted sharded fold step on ``mesh`` (see _step_program)."""
    _, agg_sig, _cap, ring = sig
    aggs = _aggs_from_sig(agg_sig)
    MP = max_parallelism
    # lint: sync-ok mesh.devices is a host numpy array of Device objects
    D = int(mesh.devices.size)
    # whether a plane folds limb by limb (an additive 64-bit one): each
    # shard then counts the limb scatters it ran, through the rounds'
    # loop, and the step hands the [D] counts back beside the rounds
    limbed = {a.name for a in aggs
              if folds_by_limbs(a.kind, stores_halves(a.dtype, ring))}

    def shard_body(table, accs, dropped, keys, cols, panes, valid,
                   base_start, base_len):
        table, keys = table[0], keys[0]
        accs = {k: _shard_plane(v) for k, v in accs.items()}
        cols = {k: v[0] for k, v in cols.items()}
        panes, valid = panes[0], valid[0]

        with jax.named_scope("mesh.plan"):
            kg = key_groups_device(keys, MP)
            # ownership bounds are TRACED scalars: a rescale that
            # re-points this mesh at a different subtask range changes
            # only argument values, never the program
            dest = device_index_for_key_groups(kg, D, MP, base_start,
                                               base_len)
            # rows outside this subtask's range never fold (they
            # belong to a peer host; a correct upstream exchange never
            # sends them)
            valid = valid & (dest >= 0) & (dest < D)
            payload = {"__key__": _sanitize(keys), "__pane__": panes,
                       **cols}

            # capacity-bounded exchange: rounds of `cap_x` rows per
            # destination keep the per-device fold width O(B) as the
            # mesh grows (the worst-case-width keyby_exchange folds
            # D*B rows per device — anti-scaling). The trip count is
            # pmax-uniform across the axis so the collectives inside
            # the loop line up; a skewed batch takes more rounds but
            # never loses a record.
            B = keys.shape[0]
            cap_x = bucket_capacity(B, D)
            xplan = plan_exchange(dest, valid, D, cap_x)
            ordered = order_payload(xplan, payload, cap_x)
        with jax.named_scope("mesh.sync"):
            n_rounds = jax.lax.pmax(xplan.n_rounds, axis_name)

        def fold_round(carry):
            r, table, accs, dropped, ok_count, limbs = carry
            accs = dict(accs)
            with jax.named_scope("mesh.exchange"):
                routed, rvalid = exchange_round(axis_name, D, cap_x,
                                                xplan, ordered, r)
            with jax.named_scope("mesh.probe"):
                table, slots, ok = lookup_or_insert(
                    table, routed["__key__"], rvalid)
            with jax.named_scope("mesh.fold"):
                n_dropped = jnp.sum(rvalid & ~ok).astype(jnp.int64)
                # rows that found no slot are masked by `ok` in the fold.
                # The routed rows are D segments in the order of their
                # source slices, and the operator cuts a block into
                # slices of consecutive rows: input in event-time order
                # reaches the fold in event-time order, so a second ring
                # row costs it one more chunk (ring_fold), not D.
                ring_idx = (routed["__pane__"] % ring).astype(jnp.int32)
                for a in aggs:
                    vals = (jnp.ones(slots.shape[0], a.dtype)
                            if a.kind in COUNT_KINDS else routed[a.name])
                    accs[a.name], ran = ring_fold(
                        a.kind, accs[a.name], ring_idx, slots, vals, ok,
                        counted=True)
                    if a.name in limbed:
                        limbs = limbs + ran
            return (r + 1, table, accs, dropped + n_dropped,
                    ok_count + jnp.sum(ok).astype(jnp.int64), limbs)

        carry = (jnp.int32(0), table, accs, dropped,
                 jnp.zeros((), jnp.int64),
                 jnp.zeros(1, jnp.int32) if limbed else None)
        _, table, accs, dropped, ok_count, limbs = jax.lax.while_loop(
            lambda c: c[0] < n_rounds, fold_round, carry)
        with jax.named_scope("mesh.sync"):
            processed = jax.lax.psum(ok_count, axis_name)
        return (table[None], {k: _mesh_plane(v) for k, v in accs.items()},
                dropped, processed, n_rounds, limbs)

    skel = {"table": 0, "accs": {a.name: 0 for a in aggs},
            "dropped": 0, "keys": 0,
            "cols": {a.name: 0 for a in aggs if a.kind not in COUNT_KINDS},
            "panes": 0, "valid": 0}
    sp = match_partition_rules(rules, skel)
    state_specs = (sp["table"], sp["accs"], sp["dropped"])
    mapped = shard_map_unchecked(
        shard_body, mesh,
        in_specs=state_specs + (sp["keys"], sp["cols"], sp["panes"],
                                sp["valid"], P(), P()),
        out_specs=state_specs + (P(), P(),
                                 sp["dropped"] if limbed else None))

    # the state is DONATED: the loop folds into the planes in place.
    # Without it every step allocates a second state (2.2 GB a chip
    # at [16, 2^23] x 2 planes) and copies the planes it did not
    # touch. Programs already enqueued on the old buffers stay valid;
    # a Python handle on the old state does not. A 64-bit plane rides
    # the rounds' loop as its two uint32 words, and ``ring_fold`` joins
    # only the ring row it folds into.
    @functools.partial(jax.jit, donate_argnums=(0,))
    def step(state: ShardedWindowState, keys, cols, panes, valid,
             base_start, base_len):
        table, accs, dropped, processed, n_rounds, limbs = mapped(
            state.table, state.accs, state.dropped, keys, cols, panes,
            valid, base_start, base_len)
        return (ShardedWindowState(table, accs, dropped), processed,
                n_rounds, limbs)

    return step


@instrumented_program_cache("mesh.step")
def _step_program(sig, max_parallelism: int, axis_name: str,
                  rules: tuple):
    """The sharded fold step. The returned dispatcher takes the concrete
    Mesh as its first argument and binds the shard_map program per mesh
    inside this one cache entry: the cache key stays local-shape-only
    while the executable closes over the mesh shard_map needs. It returns
    (new state, rows folded, exchange rounds taken, limb scatters run),
    the first two counts replicated scalars, the last an int32 [D], one
    a shard, or None where no plane folds by limbs
    (``ops/segment_ops.ring_fold``); the state argument is donated."""
    return _per_mesh(lambda mesh: _make_step(sig, max_parallelism,
                                             axis_name, rules, mesh))


def _ring_rows(plane, rows: jax.Array) -> jax.Array:
    """Ring rows ``rows`` ([W] int32) of every shard's plane as values of
    the plane's own dtype, [D, W, cap]: the words of a ``Halves`` plane
    are gathered first and joined after (``plane_take``), so only what a
    fire reads is ever 64 bits wide."""
    return plane_take(plane, lambda a: a[:, rows, :])


@instrumented_program_cache("mesh.fire")
def _fire_program(sig):
    _, agg_sig, _cap, _ring = sig
    aggs = _aggs_from_sig(agg_sig)
    count_name = _count_name(agg_sig)

    @jax.jit
    def fire(state: ShardedWindowState, pane_rows: jax.Array,
             rows_valid: jax.Array):
        def merge(kind, arr):
            sub = _ring_rows(arr, pane_rows)        # [D, W, cap]
            ident = AGG_INITS[kind](arr.dtype)
            sub = jnp.where(rows_valid[None, :, None], sub, ident)
            return AGG_MERGES[kind](sub, axis=1)

        with jax.named_scope("fire.merge"):
            out = {a.name: merge(a.kind, state.accs[a.name]) for a in aggs}
            count = out[count_name]
            emit = (state.table != jnp.int64(EMPTY_KEY)) & (count > 0)
        return out, emit

    return fire


def _top_rows(state: ShardedWindowState, planes: dict, emit: jax.Array,
              rank_name: str, topk: int, axis_name: str, mesh: Mesh,
              value_bits: int):
    """A ranked fire's tail: the keys and plane values of the global
    top-k by ``planes[rank_name]``, and how the select got there (int32
    [2]: the passes the longest shard's select walked, whether any shard
    took the sort), to ride to the host in the fire's one copy."""
    # ``value_bits`` is the rank's promise as ``ShardedWindowAgg.
    # rank_bits`` settled it: under the plane's width every shard's
    # select compiles no guard against a negative rank (a COUNT, 63; a
    # job's own promise for its MAX, Q7's 43), at the width it does
    with jax.named_scope("fire.global"):
        _vals, flat_idx, ok, passes, fell_back = global_topk(
            planes[rank_name], emit, topk, mesh, axis_name, value_bits)
        keys = jnp.take(state.table.reshape(-1), flat_idx)
        res = {n: jnp.take(v.reshape(-1), flat_idx)
               for n, v in planes.items()}
        select = jnp.stack([passes, fell_back.astype(jnp.int32)])
    return keys, ok, res, select


def _make_fire_full(sig, rank_name: Optional[str], topk: Optional[int],
                    axis_name: str, mesh: Mesh, value_bits: int = 64):
    """The jitted full fire on ``mesh`` (see _fire_full_program)."""
    _, agg_sig, _cap, _ring = sig
    aggs = _aggs_from_sig(agg_sig)
    count_name = _count_name(agg_sig)

    @jax.jit
    def fire(state: ShardedWindowState, pane_rows, rows_valid):
        def merge(kind, arr):
            sub = _ring_rows(arr, pane_rows)        # [D, W, cap]
            ident = AGG_INITS[kind](arr.dtype)
            sub = jnp.where(rows_valid[None, :, None], sub, ident)
            return AGG_MERGES[kind](sub, axis=1)

        # the pane merge with the emit mask and the health scalars it
        # feeds is the region fire.merge; the select, fire.global
        with jax.named_scope("fire.merge"):
            out = {a.name: merge(a.kind, state.accs[a.name]) for a in aggs}
            count = out[count_name]
            emit = (state.table != jnp.int64(EMPTY_KEY)) & (count > 0)
            occ = (state.table != jnp.int64(EMPTY_KEY)).sum(axis=1).max()
            dropped = state.dropped.sum()
        if topk is None:
            # a copy: an input handed back as it is would share the
            # table's buffer, which the next step donates
            return jnp.copy(state.table), emit, out, dropped, occ
        keys, ok, res, select = _top_rows(state, out, emit, rank_name,
                                          topk, axis_name, mesh, value_bits)
        return keys, ok, res, dropped, occ, select

    return fire


@instrumented_program_cache("mesh.fire_full")
def _fire_full_program(sig, rank_name: Optional[str], topk: Optional[int],
                       axis_name: str = DATA_AXIS, value_bits: int = 64):
    """ONE compiled program for the whole fire (the mesh twin of
    device_window._fire_program): pane merge for every aggregate + emit
    mask + optional two-phase global top-k (``global_topk``: a threshold
    select on every shard, merge of D*k candidates) + health scalars (max
    shard occupancy, total drops) and the select's pass count riding in
    the same outputs, so the hot loop never pays a separate sync for
    pressure checks. Everything it returns is materialized with ONE
    async device->host copy — never the full [D, capacity] table when a
    top-k is requested. Like the step, the returned dispatcher takes the
    concrete Mesh as its first argument and binds per mesh inside this
    one cache entry (the select runs under that mesh's shard_map).
    ``value_bits``, the rank's promise to the select, is a word of this
    program's key beside the rank and k, never of ``sig``: it changes
    what the select compiles, not a shard's shapes."""
    return _per_mesh(lambda mesh: _make_fire_full(sig, rank_name, topk,
                                                  axis_name, mesh,
                                                  value_bits))


@instrumented_program_cache("mesh.retire")
def _retire_program(sig):
    _, agg_sig, _cap, _ring = sig
    aggs = _aggs_from_sig(agg_sig)

    # donated like the step's state, so no second copy of the planes is
    # allocated, and nothing but the row is written: the identity (its
    # two words, for a 64-bit plane) into ONE ring row of each buffer.
    # While the planes were int64 arrays this was a pass over both
    # (31 ms at [16, 2^23] on a v5e, 26 of them the split into 32-bit
    # halves and the join: PERF.md section 6, PR 36; ROADMAP S5c)
    @functools.partial(jax.jit, donate_argnums=(0,))
    def retire(accs: dict, row: jax.Array):
        with jax.named_scope("fire.retire"):
            return {a.name: plane_map(
                        lambda words, ident: words.at[:, row].set(ident),
                        accs[a.name], plane_identity(a.kind, accs[a.name]))
                    for a in aggs}

    return retire


def _make_reclaim(sig, axis_name: str, rules: tuple, mesh: Mesh):
    """The jitted reclaim of the sharded state on ``mesh`` (see
    _reclaim_program)."""
    _, agg_sig, cap, ring = sig
    names = [name for name, _kind, _dt in agg_sig]
    plane_sig = tuple((kind, dt, (ring, cap)) for _n, kind, dt in agg_sig)

    def shard_body(table, accs, dropped):
        # a shard's plane in the layout the state keeps it in: the words
        # of a 64-bit one, which the reclaim tests and moves as words
        table, planes, dropped, counts = reclaim_shard(
            plane_sig, table[0],
            tuple(_shard_plane(accs[n]) for n in names), dropped[0])
        return (table[None],
                {n: _mesh_plane(p) for n, p in zip(names, planes)},
                dropped[None], counts[None])

    skel = {"table": 0, "accs": dict.fromkeys(names, 0), "dropped": 0}
    sp = match_partition_rules(rules, skel)
    state_specs = (sp["table"], sp["accs"], sp["dropped"])
    mapped = shard_map_unchecked(shard_body, mesh, in_specs=state_specs,
                                 out_specs=state_specs + (P(axis_name),))

    # donated like the step's state: the planes are re-seated in place.
    # Named as the one-chip backend's program is (``jit_reclaim`` in a
    # trace): a reader that anchors on a reclaim, or leaves it out of a
    # step, finds either; a job runs one of the two, never both
    @functools.partial(jax.jit, donate_argnums=(0,))
    def reclaim(state: ShardedWindowState):
        table, accs, dropped, counts = mapped(state.table, state.accs,
                                              state.dropped)
        return ShardedWindowState(table, accs, dropped), counts

    return reclaim


@instrumented_program_cache("mesh.reclaim")
def _reclaim_program(sig, axis_name: str, rules: tuple):
    """The reclaim of the sharded state: ONE program (``jit_reclaim`` in a
    trace, over a mesh) in which every shard frees, at its own capacity, the
    slots of the keys that hold no data in any ring row of its planes,
    re-homes its live keys and re-seats its planes. The body is the
    one-chip backend's (``state/tpu_backend.reclaim_shard``: the regions
    ``reclaim.live`` / ``reclaim.rehome`` / ``reclaim.remap``) under
    ``shard_map``; no shard waits for another (no collective). Bound per
    mesh like the step; the state is donated. Returns (new state, int32
    [D, 2]: the keys each shard kept and freed)."""
    return _per_mesh(lambda mesh: _make_reclaim(sig, axis_name, rules,
                                                mesh))


class ShardedWindowAgg:
    """Facade over the cached sharded programs for one (mesh, schema).

    Static schema (aggregates, capacity, ring) forms the local-shard
    signature the module-level program caches key on; the mesh and the
    key-group ownership are PER-INSTANCE runtime state — rebuilding an
    instance on a new mesh (grow, restore, live rescale) with the same
    signature reuses every already-compiled program.
    """

    def __init__(self, mesh: Mesh, aggs: Sequence[AggDef],
                 capacity: int = 1 << 16, ring: int = 64,
                 max_parallelism: int = 128, base_range=None,
                 plan: Optional[ShardingPlan] = None):
        """``base_range``: restrict this mesh to one SUBTASK's key-group
        range (multi-host deployment: the vertex is parallelized across
        hosts over DCN, each host's mesh owns its subtask range and
        re-shards it across local devices over ICI). None = full space
        (single-host mesh vertex). ``plan``: partition rules + axis; by
        default the configured MESH_RUNTIME rules over ``mesh``."""
        ensure_x64()
        if capacity & (capacity - 1):
            raise ValueError("capacity must be a power of two")
        if plan is None:
            from .plan import MESH_RUNTIME
            plan = MESH_RUNTIME.plan(mesh)
        self.plan = plan
        self.mesh = mesh
        self.n_dev = mesh.devices.size
        if max_parallelism < self.n_dev:
            raise ValueError("max_parallelism must be >= mesh size")
        self.aggs = list(aggs)
        if not any(a.kind in COUNT_KINDS for a in self.aggs):
            # nothing declared reads a count (who reads one declares it:
            # a COUNT, or the count an AVG divides by), so all the fire
            # needs is whether a record of the key fell in the window: a
            # 32-bit presence plane, folded as a saturating mark
            self.aggs.append(AggDef("__count__", "presence", jnp.int32))
        names = [a.name for a in self.aggs]
        if len(set(names)) != len(names):
            raise ValueError(f"duplicate aggregate names: {names}")
        self.capacity = capacity
        self.ring = ring
        self.max_parallelism = max_parallelism
        self._sharding = plan.state_sharding
        self._init = _init_program(self.sig, plan.rules)
        self._step = _step_program(self.sig, max_parallelism,
                                   plan.axis_name, plan.rules)
        self._fire = _fire_program(self.sig)
        self._retire = _retire_program(self.sig)
        self._reclaim = _reclaim_program(self.sig, plan.axis_name,
                                         plan.rules)
        self.set_base_range(base_range)

    # ------------------------------------------------------------------
    def set_base_range(self, base_range) -> None:
        """Re-point this mesh at a (new) subtask key-group range WITHOUT
        recompiling: ownership bounds are traced step arguments, so a live
        ownership change (key-group redistribution across an unchanged
        worker set) only changes argument values."""
        self.base_range = base_range
        self.shard_ranges = shard_ranges(self.max_parallelism, self.n_dev,
                                         base_range)
        start = self.shard_ranges[0].start
        self._base_start = np.int32(start)
        self._base_len = np.int32(self.shard_ranges[-1].end - start + 1)

    # ------------------------------------------------------------------
    def init_program(self):
        """The jitted initialiser ``init_state`` runs (no arguments; its
        output shardings are the plan's)."""
        return _make_init(self.sig, self.plan.rules, self.mesh)

    def rank_bits(self, rank_name: Optional[str],
                  value_bits: Optional[int] = None) -> int:
        """The promise a ranked fire makes its select (``threshold_topk``:
        the rank is non-negative and under 2^bits). A COUNT cannot be
        negative whatever its width: 63, whatever is declared for it (a
        declared COUNT's plane is int64 here; its 48-bit default is the
        one-chip packing's).
        Any other rank keeps what the job declared (``AggSpec.value_bits``),
        and 64, no promise, where it declared nothing."""
        if rank_name is None:
            return 64
        kind = next(a.kind for a in self.aggs if a.name == rank_name)
        if kind == "count":
            return 63
        return 64 if value_bits is None else int(value_bits)

    def fire_program(self, rank_name: Optional[str], topk: Optional[int],
                     value_bits: Optional[int] = None):
        """The jitted full fire ``fire_compact`` dispatches."""
        return _make_fire_full(self.sig, rank_name, topk,
                               self.plan.axis_name, self.mesh,
                               self.rank_bits(rank_name, value_bits))

    def step_program(self):
        """The jitted step ``step`` dispatches, with its two ownership
        bounds as trailing arguments."""
        return _make_step(self.sig, self.max_parallelism,
                          self.plan.axis_name, self.plan.rules, self.mesh)

    def reclaim_program(self):
        """The jitted reclaim ``reclaim`` dispatches."""
        return _make_reclaim(self.sig, self.plan.axis_name, self.plan.rules,
                             self.mesh)

    def init_state(self) -> ShardedWindowState:
        """The empty state, each leaf built on the device that holds it."""
        return self._init(self.mesh)

    # ------------------------------------------------------------------
    @property
    def sig(self):
        """Local-shape program-cache key (JX505): per-device shard shapes
        only — derived, so partially-constructed test doubles get it too."""
        return local_signature(self.aggs, self.capacity, self.ring)

    # ------------------------------------------------------------------
    def step(self, state: ShardedWindowState, keys: jax.Array, cols: dict,
             panes: jax.Array, valid: jax.Array
             ) -> tuple[ShardedWindowState, jax.Array, jax.Array,
                        Optional[jax.Array]]:
        """Fold one micro-batch. keys/panes/valid: [D, B]; cols: dict of
        [D, B] value columns (one per aggregate that takes a column: not a
        count, not the presence plane). Returns (new
        state, rows folded, exchange rounds taken, the limb scatters each
        shard's folds ran: int32 [D], None where no plane is an additive
        64-bit one). ``state`` is DONATED:
        its buffers are deleted, only the returned state is live."""
        return self._step(self.mesh, state, keys, cols, panes, valid,
                          self._base_start, self._base_len)

    # ------------------------------------------------------------------
    def reclaim(self, state: ShardedWindowState
                ) -> tuple[ShardedWindowState, jax.Array]:
        """Free, on every shard and at its own capacity, the slots of
        the keys that hold no data in any ring row (all their windows
        fired and retired): one dispatch, nothing waited for. Returns
        (new state, int32 [D, 2] keys kept and freed a shard). ``state``
        is DONATED, like the step's; every slot may move."""
        return self._reclaim(self.mesh, state)

    def prepare_reclaim(self, state: ShardedWindowState) -> None:
        """Compile the reclaim for ``state``'s shapes and shardings now
        (``state`` is not consumed), so that the reclaim itself builds
        nothing where a job has promised to build nothing."""
        self._reclaim.prepare(self.mesh, state)

    # ------------------------------------------------------------------
    def fire(self, state: ShardedWindowState, pane_rows: np.ndarray,
             rows_valid: Optional[np.ndarray] = None
             ) -> tuple[dict, jax.Array]:
        """Merge the given ring rows into per-key window results
        ([D, capacity] per aggregate) + emit mask. Keys = state.table.
        Callers firing at a fixed cadence should pad ``pane_rows`` to a
        constant width and mask with ``rows_valid`` so the program
        compiles once."""
        if rows_valid is None:
            rows_valid = np.ones(len(pane_rows), bool)
        return self._fire(state, jnp.asarray(pane_rows, jnp.int32),
                          jnp.asarray(rows_valid))

    # ------------------------------------------------------------------
    def _fire_full_program(self, rank_name: Optional[str],
                           topk: Optional[int],
                           value_bits: Optional[int] = None):
        return _fire_full_program(self.sig, rank_name, topk,
                                  self.plan.axis_name,
                                  self.rank_bits(rank_name, value_bits))

    def fire_compact(self, state: ShardedWindowState, pane_rows: np.ndarray,
                     rows_valid: np.ndarray, rank_name: Optional[str],
                     topk: Optional[int], value_bits: Optional[int] = None):
        """Dispatch the fused fire; returns device outputs (see
        _fire_full_program) without synchronizing. ``value_bits``: what
        the job promised of the rank aggregate (``rank_bits``)."""
        return self._fire_full_program(rank_name, topk, value_bits)(
            self.mesh, state, jnp.asarray(pane_rows, jnp.int32),
            jnp.asarray(rows_valid))

    # ------------------------------------------------------------------
    def retire_row(self, state: ShardedWindowState,
                   row: int) -> ShardedWindowState:
        """Reset one ring row across all shards (pane retirement). The
        planes of ``state`` are DONATED, like the step's state."""
        return state._replace(accs=self._retire(state.accs, jnp.int32(row)))


@functools.partial(jax.jit, static_argnames=("k", "mesh", "axis_name",
                                             "value_bits"))
def global_topk(values: jax.Array, valid: jax.Array, k: int,
                mesh: Optional[Mesh] = None, axis_name: str = DATA_AXIS,
                value_bits: int = 64
                ) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array,
                           jax.Array]:
    """Two-phase global top-k over sharded [D, capacity] per-key values
    (Nexmark Q5 hot items): each shard's exact top-k by the threshold
    select of ``ops/topk.py`` (no sort over the slots; a float rank takes
    ``lax.top_k`` there), then a ``lax.top_k`` over the D*k candidates.
    With ``mesh`` phase one runs under ``shard_map`` over ``axis_name``:
    every device selects among its own slots and only its k candidates
    leave it. ``value_bits`` is the
    caller's promise of ``threshold_topk``: under the dtype's width it
    says no value is negative, and the guard is not compiled at all.

    Returns (values [k], flat indices [k] into the [D*capacity] layout,
    ok [k] bool, the compare-and-count passes the longest shard's select
    walked, whether any shard took the sort). Entries with ok=False are
    padding (fewer than k valid slots existed); their values/indices must
    be ignored — for integer dtypes the sentinel is indistinguishable
    from a real minimum, so always filter on ``ok``, not on the values."""
    D, cap = values.shape
    kk = min(k, cap)

    def shards(vals, ok):
        tops = [threshold_topk(vals[d], ok[d], kk, value_bits,
                               otherwise=masked_topk_sort)
                for d in range(vals.shape[0])]
        return tuple(jnp.stack(leaf) for leaf in zip(*tops))

    if mesh is not None:
        spec = P(axis_name)
        shards = shard_map_unchecked(shards, mesh, in_specs=(spec, spec),
                                     out_specs=spec)
    local_v, local_i, local_ok, passes, fell_back = shards(values, valid)
    flat_i = local_i + (jnp.arange(D, dtype=jnp.int32)[:, None] * cap)
    merged_v, sel = jax.lax.top_k(local_v.reshape(-1), min(k, D * kk))
    return (merged_v, flat_i.reshape(-1)[sel], local_ok.reshape(-1)[sel],
            passes.max(), fell_back.any())
