"""Mesh slice-window operator: multi-chip execution inside a JobGraph.

This is the deploy seam the reference crosses at Execution.deploy
(flink-runtime executiongraph/Execution.java:511) ->
TaskExecutor.submitTask (taskexecutor/TaskExecutor.java:634), re-thought
for a TPU mesh: instead of N parallel subtasks connected by a hash
repartition over the network, ONE JobGraph vertex executes as an SPMD
program over an n-device `jax.sharding.Mesh`. The keyBy edge into the
vertex is the on-device `all_to_all` exchange (parallel/exchange.py) —
upstream host vertices just hand raw batches to this operator; key-group
routing happens inside the compiled step, riding ICI instead of TCP.

The host side of the operator is only a control plane: it buffers incoming
batches into fixed [D, B] device blocks (static shapes so the step jits
once) and runs the shared pane/watermark protocol (slice_control.py);
fires are one pane-merge program over every shard's key-group range
(WindowOperator.onEventTime:437 / SliceSharedWindowAggProcessor semantics,
vectorized over all keys and all devices).

State checkpointing (VERDICT #2): snapshots materialize per-shard hash
tables + pane accumulators into the SAME key-group-partitioned format the
single-chip TpuKeyedStateBackend emits ({"kind": "tpu", keys, key_groups,
states}), so restore re-filters by the new mesh's shard ranges — a mesh
job can rescale 8->4->8 devices, or hand its state to a single-chip run,
the StateAssignmentOperation/KeyGroupRangeAssignment.java:63 contract.
Key groups are always computed in the job's max-parallelism space, so
mesh and host subtasks agree on ownership.
"""

from __future__ import annotations

import time
from collections import deque
from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ...core.keygroups import hash_batch, key_groups_for_hash_batch
from ...core.records import RecordBatch, Schema
from ...ops.hash_table import EMPTY_KEY, lookup_or_insert, make_table
from ...ops.segment_ops import AGG_INITS, COUNT_KINDS, Halves, plane_map, \
    stores_halves
from ...metrics.device import DEVICE_STATS, count_plane_form, \
    pytree_nbytes
from ...metrics.tracing import TRACER
from ...parallel.mesh import make_mesh, shard_ranges
from ...parallel.sharded_window import (
    AggDef, ShardedWindowAgg, ShardedWindowState,
)
from ...window.assigners import WindowAssigner
from .base import OneInputOperator, OperatorContext, Output
from .device_window import AggSpec
from .slice_control import IN_ORDER_RING_ROWS, AsyncFireQueue, \
    SliceControlPlane

__all__ = ["MeshWindowAggOperator"]


#: load of the fullest shard past which the operator acts: it reclaims,
#: and grows if that frees too little (the one-chip backend's threshold)
_LOAD_LIMIT = 0.6
#: load the fullest shard must not pass unseen: the host steps no
#: further beyond its last reading than HALF the blocks that would take
#: the shard there at the assumed pace (``_pace``). A reclaim needs no
#: lead (one dispatch, the steps queue behind it), so the limit itself
#: is no place to wait at; past 0.65 the probe's 128-slot bound starts
#: to bite (a linear-probing cluster over 128 slots: about e^-10 a key)
_LOAD_CEILING = 0.65


def _identity(kind: str, dtype) -> np.ndarray:
    """An aggregate's identity as a host scalar of ``dtype``."""
    # lint: sync-ok a scalar constant, at restore, growth and rescale only
    return np.asarray(jax.device_get(AGG_INITS[kind](jnp.dtype(dtype))))


@jax.jit
def _probe_program(table: jax.Array, dropped: jax.Array):
    """Pressure scalars: (max per-shard occupancy, total drops, occupied
    slots of all shards)."""
    occ = (table != jnp.int64(EMPTY_KEY)).sum(axis=1)
    return occ.max(), dropped.sum(), occ.sum()


class MeshWindowAggOperator(AsyncFireQueue, SliceControlPlane,
                            OneInputOperator):
    """Keyed slice-window aggregation executed over a device mesh.

    Round 3 (VERDICT r2 weak #5): the fire path matches the single-chip
    operator's standards — ONE fused fire program per window (pane merge +
    emit mask + optional two-phase global top-k + health scalars), results
    materialized with one asynchronous device->host copy instead of
    pulling the full [D, capacity] table, ``async_fire`` holding
    watermarks behind their fires, and pressure checks riding the fire
    outputs instead of a separate sync.
    """

    def __init__(self, assigner: WindowAssigner, key_column: str,
                 aggs: Sequence[AggSpec],
                 n_devices: Optional[int] = None,
                 capacity: int = 1 << 16,
                 ring_size: int = 64,
                 device_batch: int = 1 << 12,
                 emit_window_bounds: bool = True,
                 emit_topk: Optional[int] = None,
                 async_fire: bool = False,
                 name: str = "MeshWindowAgg"):
        super().__init__(name)
        pane = assigner.pane_size
        if pane is None:
            raise ValueError(
                "Mesh window operator needs a pane-decomposable assigner "
                "(tumbling, or sliding with size % slide == 0)")
        from ...window.assigners import reject_variable_pane_assigner
        reject_variable_pane_assigner(assigner, "mesh")
        self._assigner = assigner
        self._pane = int(pane)
        self._offset = int(getattr(assigner, "offset", 0))
        size = getattr(assigner, "size", self._pane)
        self._window_panes = int(size) // self._pane
        self._ring = int(ring_size)
        if self._ring < self._window_panes + 1:
            raise ValueError("ring_size must exceed panes per window")
        self._key_column = key_column
        self._aggs = list(aggs)
        self._capacity = capacity
        self._device_batch = int(device_batch)
        self._emit_bounds = emit_window_bounds
        self._topk = emit_topk
        self._async = bool(async_fire)
        self._n_devices = n_devices

        self._agg: Optional[ShardedWindowAgg] = None
        self._state: Optional[ShardedWindowState] = None
        # live rescale (PR 12): a pending worker-set change applied at the
        # next barrier-aligned quiescent point; the epoch fences the mesh
        # generation the way the coordinator's execution epoch fences
        # restarts
        self._rescale_request: Optional[int] = None
        self._rescale_epoch = 0
        self._last_rescale_stats: Optional[dict] = None
        self._init_control_plane()
        self._init_async_fires()
        if self._async:
            self._record_fire_latency = False
        self._dropped_seen = 0
        self.stage_s: dict[str, float] = {}
        # pressure probe (occupancy of the fullest shard + drops): an
        # async scalar read, dispatched at watermark cadence and, between
        # watermarks, once the blocks stepped since the last one pass a
        # quarter of the headroom to the load ceiling; consumed when
        # its copy has landed (_take_readings): (outputs, table
        # generation, block ordinal and rows stepped at its dispatch)
        self._probe: Optional[tuple] = None
        self._probed_at = 0
        self._occ_known = 0
        # the table's generation: a reclaim moves every slot and a
        # rebuild (grow, restore, rescale) replaces the table; a reading
        # taken under an older one says nothing of this table
        self._generation = 0
        # a reclaim dispatched whose counts have not landed yet: (device
        # [D, 2] kept / freed, its open window/Reclaim stage, block
        # ordinal and rows stepped at its dispatch)
        self._reclaiming: Optional[tuple] = None
        # the last reading of this table (block ordinal it was taken at,
        # occupancy of the fullest shard) and the slots a block the
        # fullest shard is ASSUMED to gain: every row of its slice a new
        # key until two readings of one table say otherwise, then twice
        # what they showed, never under an eighth of a slice (_reading)
        self._last_reading: Optional[tuple] = None
        self._pace = float(device_batch)
        # valid rows stepped, and the last reading of all shards'
        # occupied slots (generation, rows stepped by then, slots): what
        # a table gained between two readings are the rows that claimed
        # a new slot (mesh_inserted_rows_total)
        self._rows_stepped = 0
        self._occ_total: Optional[tuple] = None
        # ordinal of the [D, B] block being stepped (seq of its stage
        # spans) and the steps' round counts still on their way to the
        # host (handed to DEVICE_STATS once landed, never waited for)
        self._block_seq = 0
        self._rounds_sent: deque = deque()
        # ns the task's thread has WAITED for a reading that had not
        # landed (_await_reading), all of this operator's
        self._reading_wait_ns = 0
        # host-side staging buffers for [D, B] blocks
        self._buf_keys: list[np.ndarray] = []
        self._buf_panes: list[np.ndarray] = []
        self._buf_cols: dict[str, list[np.ndarray]] = {}
        self._buf_n = 0

    # -- lifecycle ---------------------------------------------------------
    def setup(self, ctx: OperatorContext, output: Output) -> None:
        super().setup(ctx, output)
        # DCN x ICI composition (VERDICT r3 #3): with vertex parallelism
        # P > 1 this subtask owns ctx.key_group_range (the standard keyed
        # exchange delivers only its rows, over TCP when hosts differ) and
        # its LOCAL mesh re-shards that range across this host's devices —
        # DCN between hosts, ICI within the host, per SURVEY §5.8. With
        # P == 1 (single-host mesh vertex) the base is the full key space
        # and behavior is unchanged.
        P = ctx.parallelism
        local = jax.devices()
        n = self._n_devices or (len(local) if P == 1
                                else max(1, len(local) // P))
        self._n_devices = n
        # key groups must live in the job's max-parallelism space so mesh
        # checkpoints interoperate with host subtasks and other mesh sizes
        self._max_parallelism = ctx.max_parallelism
        self._base_range = ctx.key_group_range if P > 1 else None
        base_len = (self._max_parallelism if self._base_range is None
                    else self._base_range.end - self._base_range.start + 1)
        if base_len < n:
            raise ValueError(
                f"subtask key-group range ({base_len} groups) must be >= "
                f"mesh size ({n}); raise pipeline.max-parallelism")
        # single-process multi-host emulation (tests / one-host dev box):
        # when the process sees every host's devices, subtasks take
        # deterministic disjoint slices. On a real multi-host slice each
        # process only sees its own chips and takes them all.
        sub = ctx.subtask_index
        self._parallelism = P
        self._sub_index = sub
        if P > 1 and len(local) >= (sub + 1) * n:
            devs = local[sub * n:(sub + 1) * n]
        else:
            devs = local[:n]
        self._mesh = make_mesh(n, devices=devs)

    def initialize_state(self, keyed_snapshots: list, operator_snapshot) -> None:
        if not keyed_snapshots:
            return
        self._restore_control_meta([s["meta"] for s in keyed_snapshots])
        self._restore_backends([s["backend"] for s in keyed_snapshots])

    # -- agg program construction ------------------------------------------
    def _aggdefs(self, schema: Schema) -> list[AggDef]:
        """AggSpec -> AggDef list. Accumulator dtype follows the input
        column (sum over int64 stays int64, matching the host operator);
        avg accumulates a float sum plane and divides by count at emit.
        An ``AggDef`` is a plane's shape and nothing else (it is a word of
        ``local_signature``): the RANK aggregate's ``value_bits`` goes to
        the fire beside the rank's name (``_fire``), and no other
        aggregate's promise is read by anything on the mesh."""
        defs = []
        for a in self._aggs:
            if a.kind == "count":
                defs.append(AggDef(a.out_name, "count", jnp.int64))
            elif a.kind == "avg":
                defs.append(AggDef(f"{a.out_name}.sum", "sum", jnp.float32))
            else:
                dt = (jnp.dtype(np.dtype(schema.field(a.field).dtype))
                      if a.field in schema else jnp.dtype(a.dtype))
                defs.append(AggDef(a.out_name, a.kind, dt))
        kinds = {a.kind for a in self._aggs}
        if "avg" in kinds and "count" not in kinds:
            # an AVG divides by the count: who reads one declares it (a
            # job that reads none gets ``ShardedWindowAgg``'s 32-bit
            # presence plane in its place)
            defs.append(AggDef("__count__", "count", jnp.int64))
        return defs

    @staticmethod
    def _plane_name(a: AggSpec) -> str:
        return f"{a.out_name}.sum" if a.kind == "avg" else a.out_name

    def _build(self, defs: list[AggDef], capacity: Optional[int] = None
               ) -> None:
        # a reclaim in flight is of the state that goes: settle it first
        self._finish_reclaim(block=True, grow=False)
        first = self._agg is None    # of this operator: not a growth
        self._agg = ShardedWindowAgg(
            self._mesh, defs, capacity=capacity or self._capacity,
            ring=self._ring, max_parallelism=self._max_parallelism,
            base_range=self._base_range)
        # the state that is replaced (grow, restore, rescale) goes before
        # the new one is built, and with it what was known of it
        self._state = None
        self._new_generation()
        self._occ_known = 0
        self._pace = float(self._device_batch)
        self._state = self._agg.init_state()
        if first:
            DEVICE_STATS.note_count_plane(self._count_form())
        # with the step's and the fire's programs, before any input: a
        # reclaim then compiles nothing, wherever in the job it falls (a
        # job may have promised to build nothing once it is warm)
        self._agg.prepare_reclaim(self._state)

    def _count_form(self) -> str:
        plane = next(a for a in self._agg.aggs if a.kind in COUNT_KINDS)
        return count_plane_form(plane.kind, plane.dtype)

    def _new_generation(self) -> None:
        self._generation += 1
        self._probe = None
        self._last_reading = None
        self._occ_total = None

    # -- data path ---------------------------------------------------------
    def process_batch(self, batch: RecordBatch) -> None:
        self._last_batch_ns = time.monotonic_ns()
        if self._pending:
            self._drain(block=False)
        if batch.n == 0:
            return
        if self._agg is None:
            key_dtype = batch.schema.field(self._key_column).dtype
            if key_dtype is object or not np.issubdtype(np.dtype(key_dtype),
                                                        np.integer):
                raise TypeError(
                    f"mesh window aggregation needs an integer key column; "
                    f"{self._key_column!r} is {key_dtype}")
            self._build(self._aggdefs(batch.schema))
        keys = batch.column(self._key_column).astype(np.int64)
        self._ingest(batch, keys)

    def _fold(self, batch: RecordBatch, keys: np.ndarray,
              panes: np.ndarray) -> None:
        self._buf_keys.append(keys)
        self._buf_panes.append(panes)
        for a in self._aggs:
            if a.kind == "count":
                continue
            self._buf_cols.setdefault(self._plane_name(a), []).append(
                np.asarray(batch.column(a.field)))
        self._buf_n += batch.n
        if self._buf_n >= self._n_devices * self._device_batch:
            self._flush(pad=False)

    def _flush(self, pad: bool) -> None:
        """Drain staged records into [D, B] device steps. With pad=False
        only full D*B blocks run; with pad=True a final padded block
        (valid mask) drains the remainder."""
        if self._agg is None or self._buf_n == 0:
            return
        full = self._n_devices * self._device_batch
        # a block's ring rows are counted before its window/Upload
        # starts, as the one-chip operator counts a batch's before its
        # stages: the span stays the concatenation, the cut and the copies
        ring_idx = np.concatenate(self._buf_panes) % self._ring
        staged = None
        pos, total = 0, self._buf_n
        while total - pos >= full or (pad and total > pos):
            n_valid = min(full, total - pos)
            self._block_seq += 1
            self._rows_stepped += n_valid
            ring_rows = self._note_fold(ring_idx[pos:pos + n_valid])
            with self._upload_stage() as up:
                if staged is None:
                    staged = self._concat_staged()
                rows = slice(pos, pos + n_valid)
                if ring_rows > IN_ORDER_RING_ROWS:
                    # out-of-order input goes up sorted by ring row, as
                    # on one chip (DeviceWindowAggOperator._fold): the
                    # routed rows keep a slice's order, so each ring
                    # row's updates then lie together on every shard
                    rows = pos + np.argsort(ring_idx[rows], kind="stable")
                block = self._upload_block(staged, rows, n_valid)
                nbytes = pytree_nbytes(block)
                up.set("bytes", nbytes)
                DEVICE_STATS.note_h2d(nbytes, n_valid)
            with self._dispatch_stage(ring_rows=ring_rows) as disp:
                waited = self._reading_wait_ns
                self._step_block(*block)
                if self._reading_wait_ns > waited:
                    disp.set("reading_wait_ms", round(
                        (self._reading_wait_ns - waited) / 1e6, 3))
            pos += n_valid
        if pos == 0:
            return
        keys, panes, cols = staged
        self._buf_keys = [keys[pos:]] if pos < total else []
        self._buf_panes = [panes[pos:]] if pos < total else []
        self._buf_cols = ({n: [c[pos:]] for n, c in cols.items()}
                          if pos < total else {})
        self._buf_n = total - pos

    # -- stage spans of one block (metrics/tracing.py Stage) ---------------
    def _upload_stage(self):
        """window/Upload: concatenating the staged host batches, cutting
        one [D, B] block and its host->device copies (device/H2D nests
        under it); the caller sets ``bytes``."""
        return TRACER.stage("window", "Upload", seq=self._block_seq,
                            total=(self.stage_s, "ingest"))

    def _dispatch_stage(self, **attrs):
        """window/IngestDispatch: the host's time to enqueue the block's
        step (the devices run it later) and to look at the pressure
        probe; ``ring_rows`` from the caller, ``reading_wait_ms`` on a
        block that waited for a reading (``_await_reading``)."""
        return TRACER.stage("window", "IngestDispatch", seq=self._block_seq,
                            total=(self.stage_s, "ingest"), **attrs)

    def _concat_staged(self) -> tuple:
        return (np.concatenate(self._buf_keys),
                np.concatenate(self._buf_panes),
                {n: np.concatenate(vs) for n, vs in self._buf_cols.items()})

    def _upload_block(self, staged: tuple, rows, n_valid: int) -> tuple:
        """The ``n_valid`` ``rows`` of the staged columns (a slice, or
        the indices of an out-of-order block in the order it goes up in)
        as device arrays of shape [D, B] (keys, cols, panes, valid),
        zero-padded where the block is not full."""
        D, B = self._n_devices, self._device_batch
        full = D * B

        def cut(col: np.ndarray) -> jax.Array:
            picked = col[rows]
            if n_valid < full:
                buf = np.zeros(full, col.dtype)
                buf[:n_valid] = picked
            else:
                buf = picked
            return jnp.asarray(buf.reshape(D, B))

        keys, panes, cols = staged
        valid = np.zeros(full, bool)
        valid[:n_valid] = True
        return (cut(keys), {n: cut(c) for n, c in cols.items()},
                cut(panes), jnp.asarray(valid.reshape(D, B)))

    def _step_block(self, dkeys: jax.Array, dcols: dict, dpanes: jax.Array,
                    dvalid: jax.Array) -> None:
        # what is due of the readings sent out is taken in BEFORE the
        # step (a reclaim, a growth come before the block's inserts),
        # the next probe goes out right behind it
        self._take_readings(at_block=True)
        # the old state is donated to the step: nothing may keep a handle
        # on it (fires and probes enqueued on it earlier stay valid)
        self._state, _processed, n_rounds, limbs = self._agg.step(
            self._state, dkeys, dcols, dpanes, dvalid)
        for count in (n_rounds, limbs):
            if count is not None:
                count.copy_to_host_async()
        self._rounds_sent.append((n_rounds, limbs))
        self._note_rounds()
        self._send_probe(at_block=True)

    def _note_rounds(self, block: bool = False) -> None:
        """Hand the steps' exchange-round counts to DEVICE_STATS, and
        with them the limb scatters the busiest shard's folds ran (a
        step's time is its slowest shard's; None where no plane folds by
        limbs): those whose copy has landed (all of them with ``block``:
        finish and snapshot sync anyway). Never waits in the hot loop, so
        the counters trail the devices by the steps in flight."""
        sent = self._rounds_sent
        steps = rounds = limb_scatters = 0
        while sent and (block or sent[0][0].is_ready()):
            n_rounds, limbs = sent.popleft()
            # lint: sync-ok the copy has landed (or the caller syncs anyway)
            rounds += int(np.asarray(n_rounds))
            if limbs is not None:
                # lint: sync-ok one program's outputs: landed together
                limb_scatters += int(np.asarray(limbs).max())
            steps += 1
        if steps:
            DEVICE_STATS.note_mesh_steps(steps, rounds)
            DEVICE_STATS.note_limb_scatters(limb_scatters)

    # -- firing (fire loop lives in SliceControlPlane) ----------------------
    def _pre_fire_flush(self) -> None:
        self._flush(pad=True)
        self._take_readings()
        self._send_probe()

    def _headroom(self) -> int:
        """Blocks that would take the fullest shard from its last known
        occupancy to the load ceiling at the assumed pace."""
        gap = _LOAD_CEILING * self._agg.capacity - self._occ_known
        return int(max(0.0, gap) // self._pace)

    def _take_readings(self, at_block: bool = False) -> None:
        """Pressure handling WITHOUT stalling the pipeline: an async
        scalar probe (max shard occupancy, total drops, occupied slots)
        goes out at watermark cadence and behind the steps
        (``_send_probe``) and is consumed here whenever its copy has
        landed, as one more reading of the table (``_reading``); so are
        the counts of a reclaim in flight. Drops are a hard error (also
        checked on every fire's health scalars).

        With ``at_block`` (before every step) the host also bounds how far
        it runs ahead of what it knows of the devices: it WAITS for a
        probe, or for a reclaim's counts, once half the headroom
        (``_headroom``: blocks to the load ceiling at the assumed pace)
        has been stepped since it went out, this block counted. The wait
        ends when the devices reach it, with the blocks stepped since
        still queued behind it; what it calls for, a reclaim and then a
        growth, comes before this block's inserts. The headroom is to the
        CEILING, not to the limit the operator acts at: near the limit a
        reading is due every few dozen blocks, not a wait at every
        block."""
        if self._agg is None:
            return
        self._settle_reclaim(at_block)
        if self._probe is not None:
            outs, generation, at, rows = self._probe
            wait = at_block and (self._block_seq - at
                                 >= self._headroom() // 2)
            landed = all(leaf.is_ready()
                         for leaf in jax.tree_util.tree_leaves(outs))
            if wait or landed:
                occ, dropped, occupied = self._await_reading(outs, landed)
                self._probe = None
                self._note_inserts(generation, rows, int(occupied))
                self._reading(dropped, occ, (generation, at))
                self._settle_reclaim(at_block)   # one it has just sent

    def _await_reading(self, outs, landed: bool):
        """``jax.device_get`` of a reading. One that has ``landed`` costs
        a copy already made; for any other the task's thread WAITS until
        the devices reach it, and the wait is counted
        (``mesh_reading_waits_total`` / ``mesh_reading_wait_us_total``;
        ``reading_wait_ms`` on the block's window/IngestDispatch)."""
        if landed:
            # lint: sync-ok the reading's copy has landed
            return jax.device_get(outs)
        t0 = time.perf_counter_ns()
        # lint: sync-ok the one wait of the hot loop, by _take_readings' rule
        host = jax.device_get(outs)
        waited = time.perf_counter_ns() - t0
        self._reading_wait_ns += waited
        DEVICE_STATS.note_mesh_reading_wait(waited / 1e3)
        return host

    def _settle_reclaim(self, at_block: bool) -> None:
        """Take in the counts of a reclaim in flight if they have landed;
        before a step, wait for them by the rule of ``_take_readings``."""
        if self._reclaiming is not None:
            self._finish_reclaim(block=at_block and (
                self._block_seq - self._reclaiming[2]
                >= self._headroom() // 2))

    def _send_probe(self, at_block: bool = False) -> None:
        """Dispatch the pressure probe if none is out: behind a step once
        a quarter of the headroom has been stepped since the last one
        went out, at a watermark once any block has."""
        if self._agg is None or self._probe is not None:
            return
        due = max(1, self._headroom() // 4) if at_block else 1
        if self._block_seq - self._probed_at >= due:
            outs = _probe_program(self._state.table, self._state.dropped)
            for leaf in jax.tree_util.tree_leaves(outs):
                leaf.copy_to_host_async()
            self._probe = (outs, self._generation, self._block_seq,
                           self._rows_stepped)
            self._probed_at = self._block_seq

    def _note_inserts(self, generation: int, rows: int,
                      occupied: int) -> None:
        """One reading of all shards' occupied slots, taken when ``rows``
        rows had been stepped: what one table gained since the reading
        before are the rows in between that claimed a new slot."""
        last = self._occ_total
        if last is not None and last[0] == generation and rows > last[1]:
            DEVICE_STATS.note_mesh_inserts(max(0, occupied - last[2]),
                                           rows - last[1])
        self._occ_total = (generation, rows, occupied)

    def _reading(self, dropped: int, occ_max: int, taken: tuple,
                 drain=None) -> None:
        """One reading of the table's health: scalars that rode a fire's
        outputs or a landed probe's, so the hot loop itself never syncs.
        ``taken``: (table generation, block ordinal) at the dispatch of
        what read them; a reading of a table since reclaimed or rebuilt
        says nothing of this one and is passed over, as is any reading
        while a reclaim is in flight.

        Past the load limit on the fullest shard the operator RECLAIMS
        (all shards, one dispatch, at the same capacity) and goes on
        stepping behind it; it grows only if that frees too little
        (``_finish_reclaim``). The reading is taken as it is, as the
        one-chip backend takes a fire's: a reclaim needs no lead, so
        nothing is added for the blocks stepped since (counting every row
        of them as a new key on the fullest shard makes a job near the
        limit wait for the devices at every block). How far the host may
        run ahead of its readings is ``_take_readings``', by the pace
        two readings of one table show: the slots a block the fullest
        shard gained, doubled, at least an eighth of a slice and at most
        a whole one (every row a new key there: what is assumed while two
        readings of this table have not shown otherwise, a prefill)."""
        self._check_dropped(dropped)
        self._finish_reclaim()
        generation, at = taken
        if self._reclaiming is not None or generation != self._generation:
            return
        occ, B = int(occ_max), self._device_batch
        last = self._last_reading
        if last is None or at >= last[0]:
            if last is not None and at > last[0]:
                gained = max(0, occ - last[1]) / (at - last[0])
                self._pace = min(float(B), max(2.0 * gained, B / 8))
            self._last_reading = (at, occ)
            self._occ_known = occ
        if occ > _LOAD_LIMIT * self._agg.capacity:
            self._reclaim(drain)

    def _check_dropped(self, dropped: int) -> None:
        if int(dropped) > self._dropped_seen:
            raise RuntimeError(
                f"mesh hash table overflow: {int(dropped)} records dropped "
                f"(capacity {self._agg.capacity} per shard: neither a "
                "reclaim nor growth came in time); raise "
                "state.backend.tpu.slots-per-key-group")

    def _reclaim(self, drain=None) -> None:
        """Dispatch the reclaim of every shard (``ShardedWindowAgg.
        reclaim``: one donated program, not waited for). window/Reclaim
        opens here, under the window/Drain whose reading found the
        pressure (``seq``: that window's end) or, found by a probe, as a
        root (``seq``: the operator's watermark), and closes when the
        counts have landed. Every slot may move: a new table generation
        begins."""
        at = ({"parent": drain.context, "seq": drain.attrs["seq"]}
              if drain is not None else {"seq": self.current_watermark})
        span = TRACER.open_stage("window", "Reclaim", **at)
        self._state, counts = self._agg.reclaim(self._state)
        counts.copy_to_host_async()
        self._new_generation()
        self._reclaiming = (counts, span, self._block_seq,
                            self._rows_stepped)

    def _finish_reclaim(self, block: bool = False,
                        grow: bool = True) -> None:
        """Take in a dispatched reclaim's counts once their copy has
        landed (``block``: wait for it): the stage span, the counters
        (summed over the shards), the fullest shard's occupancy, and the
        growth that a reclaim which freed too little still calls for, by
        the backend's rule read per shard: fewer than a quarter of the
        slots a shard held past the load limit came free (the job's live
        set really is that large)."""
        if self._reclaiming is None:
            return
        counts, span, at, rows = self._reclaiming
        landed = counts.is_ready()
        if not (block or landed):
            return
        self._reclaiming = None
        kept, freed = np.asarray(
            self._await_reading(counts, landed)).T.astype(np.int64)
        cap = self._agg.capacity
        DEVICE_STATS.note_reclaim(int(kept.sum()), int(freed.sum()))
        span.close(kept=int(kept.sum()), freed=int(freed.sum()),
                   capacity=cap)
        self._occ_known = int(kept.max())
        self._last_reading = (at, self._occ_known)
        self._occ_total = (self._generation, rows, int(kept.sum()))
        held = kept + freed
        if grow and ((4 * freed < held) & (held > _LOAD_LIMIT * cap)).any():
            target = 2 * cap
            while kept.max() > _LOAD_LIMIT * target:
                target *= 2
            self._grow(target)

    def _grow(self, new_capacity: int) -> None:
        self._drain(block=True)  # pending fires read the pre-grow state
        # the new state's drop counters start at zero: a row lost before
        # the growth came is still the hard error it must be
        self._check_dropped(np.asarray(
            jax.device_get(self._state.dropped)).sum())
        snap = self._snapshot_backend()
        defs = list(self._agg.aggs)
        self._build(defs, capacity=new_capacity)
        self._load_snapshot_into_state([snap])

    # -- fire/emit ---------------------------------------------------------
    def _rank_name(self) -> Optional[str]:
        if self._topk is None:
            return None
        return self._plane_name(self._aggs[0])

    def _rank_bits(self) -> int:
        """The promise a ranked fire's select is compiled under: what the
        job declared for the rank aggregate (``AggSpec.value_bits``), as
        ``ShardedWindowAgg.rank_bits`` settles it (a COUNT rank: 63)."""
        if self._topk is None:
            return 64
        return self._agg.rank_bits(self._rank_name(),
                                   self._aggs[0].value_bits)

    def _window_holds_data(self, p_end: int) -> bool:
        return self._agg is not None and super()._window_holds_data(p_end)

    def _fire(self, p_end: int) -> None:
        W = self._window_panes
        # never read panes below min_seen: they hold no data and their ring
        # rows may be occupied by live FUTURE panes (row aliasing); that
        # the window has a pane at all, _fire_window has checked
        first = max(p_end - W, self._min_seen_pane)
        rows = [(p % self._ring) for p in range(first, p_end)]
        # constant [W] shape so the fire program compiles once
        pane_rows = np.zeros(W, np.int32)
        pane_rows[:len(rows)] = rows
        rows_valid = np.zeros(W, bool)
        rows_valid[:len(rows)] = True
        outs = self._agg.fire_compact(self._state, pane_rows, rows_valid,
                                      self._rank_name(), self._topk,
                                      self._rank_bits())
        self._enqueue_fire((p_end, outs, self._taken(),
                            time.perf_counter()))
        # retire the oldest pane of this window: no future window needs it
        if p_end - W >= self._min_seen_pane:
            self._state = self._agg.retire_row(self._state,
                                               (p_end - W) % self._ring)

    def _taken(self) -> tuple:
        """(table generation, block ordinal) a fire's health scalars are
        read under: carried from its dispatch to its drain."""
        return self._generation, self._block_seq

    def _materialize(self, item: tuple, turn: str) -> None:
        p_end, outs, taken, t0, fire = item
        with self._drain_stage(fire, turn) as drain:
            host = jax.device_get(outs)   # ONE transfer for everything
            d2h_bytes = pytree_nbytes(host)
            if self._topk is not None:
                keys_k, ok, results, dropped, occ, select = host
                self._reading(dropped, occ, taken, drain)
                self._note_fire_select(drain, select, self._rank_bits(),
                                       results[self._rank_name()].dtype)
                sel = np.asarray(ok)
                keys = np.asarray(keys_k)[sel]
                res = {n: np.asarray(v)[sel] for n, v in results.items()}
            else:
                table, emit, results, dropped, occ = host
                self._reading(dropped, occ, taken, drain)
                mask = np.asarray(emit).reshape(-1)
                idx = np.flatnonzero(mask)
                keys = np.asarray(table).reshape(-1)[idx]
                res = {n: np.asarray(v).reshape(-1)[idx]
                       for n, v in results.items()}
            DEVICE_STATS.note_d2h(d2h_bytes, len(keys))
        if len(keys):
            with self._emit_stage(fire, len(keys)):
                self._emit_rows(p_end, keys, res)
        self._note_latency(t0)
        self._close_fire(fire, len(keys), d2h_bytes)

    def _emit_rows(self, p_end: int, keys: np.ndarray, host: dict) -> None:
        # the count an AVG divides by (a job that reads none has none)
        count_name = next((d.name for d in self._agg.aggs
                           if d.kind == "count"), None)
        n = len(keys)
        start = (p_end - self._window_panes) * self._pane + self._offset
        end = p_end * self._pane + self._offset
        cols: dict[str, np.ndarray] = {self._key_column: keys}
        fields: list[tuple[str, Any]] = [(self._key_column, np.int64)]
        if self._emit_bounds:
            cols["window_start"] = np.full(n, start, np.int64)
            cols["window_end"] = np.full(n, end, np.int64)
            fields += [("window_start", np.int64), ("window_end", np.int64)]
        for a in self._aggs:
            if a.kind == "avg":
                s = host[f"{a.out_name}.sum"]
                c = np.maximum(host[count_name], 1).astype(s.dtype)
                vals = s / c
            else:
                vals = host[a.out_name]
            cols[a.out_name] = vals
            fields.append((a.out_name, vals.dtype.type))
        schema = Schema(fields)
        ts = np.full(n, end - 1, np.int64)
        self.output.emit(RecordBatch(schema, cols, ts))

    # -- checkpointing ------------------------------------------------------
    def _snapshot_backend(self) -> dict:
        """Key-group-partitioned snapshot, format-compatible with
        TpuKeyedStateBackend.snapshot (state/tpu_backend.py) so mesh and
        single-chip runs restore each other's checkpoints."""
        if self._agg is None:
            return {"kind": "tpu", "keys": np.empty(0, np.int64),
                    "key_groups": np.empty(0, np.int32), "states": {}}
        # a reclaim in flight is settled first (its stage and counts; the
        # state read below is the reclaimed one either way): a snapshot
        # taken for a growth is of the table as it is now
        self._finish_reclaim(block=True, grow=False)
        table = np.asarray(jax.device_get(self._state.table))  # [D, cap]
        # a plane kept as its two words is joined HERE, on the host (numpy:
        # `np.asarray` of a `Halves`): the snapshot holds the values, and
        # its bytes are what they were when the planes were int64 arrays
        host_accs = {n: np.asarray(jax.device_get(v))
                     for n, v in self._state.accs.items()}  # [D, ring, cap]
        keys_parts, group_parts = [], []
        vals_parts: dict[str, list[np.ndarray]] = {
            n: [] for n in host_accs}
        for d in range(self._n_devices):
            occupied = table[d] != np.int64(EMPTY_KEY)
            keys_d = table[d][occupied]
            keys_parts.append(keys_d)
            group_parts.append(key_groups_for_hash_batch(
                hash_batch(keys_d), self._max_parallelism))
            slots = np.flatnonzero(occupied)
            for n, acc in host_accs.items():
                vals_parts[n].append(acc[d][:, slots])
        keys = np.concatenate(keys_parts) if keys_parts else np.empty(
            0, np.int64)
        groups = (np.concatenate(group_parts) if group_parts
                  else np.empty(0, np.int32))
        states = {}
        for a in self._agg.aggs:
            vals = (np.concatenate(vals_parts[a.name], axis=-1)
                    if vals_parts[a.name]
                    else np.empty((self._ring, 0)))
            states[a.name] = {"kind": a.kind,
                              "dtype": str(np.dtype(a.dtype)),
                              "ring": self._ring, "values": vals}
        return {"kind": "tpu", "keys": keys, "key_groups": groups,
                "max_parallelism": self._max_parallelism, "states": states}

    def snapshot_state(self, checkpoint_id: int) -> dict:
        self._flush(pad=True)
        self._drain(block=True)
        self._note_rounds(block=True)
        snap = {"keyed": {"backend": self._snapshot_backend(),
                          "meta": self._control_meta()}}
        # coordinator-driven live rescale rides the aligned-barrier
        # protocol: the snapshot above IS the consistent point (exactly
        # the reference's savepoint-then-redistribute, minus the restart),
        # so a pending worker-set change applies here, on the mailbox
        # thread, with every buffered row folded and every fire drained
        if self._rescale_request is not None:
            req, self._rescale_request = self._rescale_request, None
            self.rescale_live(req)
        return snap

    # -- live rescale -------------------------------------------------------
    def request_rescale(self, n_devices: int) -> None:
        """Stage a worker-set change; it applies at the next aligned
        barrier (snapshot_state). Thread-safe: a single reference store,
        read once on the mailbox thread."""
        from ...parallel.plan import MESH_RUNTIME
        if not MESH_RUNTIME.rescale_enabled:
            raise RuntimeError(
                "live rescale is disabled (mesh.rescale.enabled=false)")
        self._rescale_request = int(n_devices)

    def rescale_live(self, n_devices: Optional[int] = None,
                     devices: Optional[Sequence] = None) -> dict:
        """Re-shard device-resident key-group state across a new mesh
        WITHOUT restarting the job: snapshot at the quiescent point, diff
        key-group ownership old->new, ship only the pages whose groups
        change owner (checkpoint page format, digest-verified), and install
        on the new mesh. Emits one causal trace tree under the
        ``rescale/`` scope and feeds the migration counters.

        Because every sharded program is cache-keyed by local shard shape
        only (sharded_window.local_signature), a rescale that preserves
        per-device capacity/ring recompiles nothing."""
        from ...metrics.tracing import TRACER
        from ...parallel.rescale import plan_migration, reassemble_pages
        t0 = time.perf_counter()
        old_n = self._n_devices
        local = list(devices) if devices is not None else jax.devices()
        n = int(n_devices) if n_devices else len(local)
        base_len = (self._max_parallelism if self._base_range is None
                    else self._base_range.end - self._base_range.start + 1)
        if base_len < n:
            raise ValueError(
                f"subtask key-group range ({base_len} groups) must be >= "
                f"new mesh size ({n}); raise pipeline.max-parallelism")
        P = getattr(self, "_parallelism", 1)
        sub = getattr(self, "_sub_index", 0)
        if P > 1 and len(local) >= (sub + 1) * n:
            devs = local[sub * n:(sub + 1) * n]
        else:
            devs = local[:n]
        if self._agg is None:
            # nothing materialized yet: adopt the new worker set directly
            self._n_devices = n
            self._mesh = make_mesh(n, devices=devs)
            self._rescale_epoch += 1
            self._last_rescale_stats = {
                "old_devices": old_n, "new_devices": n,
                "keygroups_migrated": 0, "bytes_moved": 0,
                "epoch": self._rescale_epoch, "duration_ms": 0.0}
            return self._last_rescale_stats
        with TRACER.span("rescale", "Rescale") as root:
            root.set_attribute("old_devices", old_n)
            root.set_attribute("new_devices", n)
            # quiescent point: every buffered row folded, every async fire
            # drained — the operator-local equivalent of barrier alignment
            self._flush(pad=True)
            self._drain(block=True)
            old_sig = self._agg.sig
            old_ranges = tuple(self._agg.shard_ranges)
            new_ranges = tuple(shard_ranges(self._max_parallelism, n,
                                            self._base_range))
            snap = self._snapshot_backend()
            with TRACER.span("rescale", "Migrate") as mig:
                plan = plan_migration(snap, old_ranges, new_ranges)
                verified = reassemble_pages(plan.pages, snap)
                mig.set_attribute("keygroups_migrated",
                                  plan.keygroups_migrated)
                mig.set_attribute("bytes_moved", plan.bytes_moved)
                mig.set_attribute("pages_moved", len(plan.moved_pages))
            with TRACER.span("rescale", "Rebuild") as reb:
                self._n_devices = n
                self._mesh = make_mesh(n, devices=devs)
                # never shrink per-shard capacity on rescale: keeping the
                # local shard signature stable is what lets the program
                # caches hit (recompiles == 0 across the switch)
                self._capacity = max(self._capacity, self._agg.capacity)
                if len(verified["keys"]) or verified["states"]:
                    self._restore_backends([verified])
                else:
                    self._build(list(self._agg.aggs),
                                capacity=self._agg.capacity)
                reb.set_attribute("local_shapes_changed",
                                  self._agg.sig != old_sig)
            self._rescale_epoch += 1
            root.set_attribute("epoch", self._rescale_epoch)
        duration_ms = (time.perf_counter() - t0) * 1e3
        DEVICE_STATS.note_rescale(plan.keygroups_migrated,
                                  plan.bytes_moved, duration_ms)
        self._last_rescale_stats = {
            "old_devices": old_n, "new_devices": n,
            "keygroups_migrated": plan.keygroups_migrated,
            "bytes_moved": plan.bytes_moved,
            "epoch": self._rescale_epoch,
            "duration_ms": duration_ms}
        return self._last_rescale_stats

    def _live_pane_span(self) -> range:
        """Panes whose ring rows may hold live data (everything below has
        been retired/zeroed)."""
        if self._max_seen_pane is None:
            return range(0)
        first = self._min_seen_pane
        if self._fired_boundary is not None:
            first = max(first, self._fired_boundary - self._window_panes)
        return range(first, self._max_seen_pane + 1)

    def _remap_ring_rows(self, vals: np.ndarray, old_ring: int,
                         kind: str, dtype) -> np.ndarray:
        """Re-seat restored [old_ring, N] accumulator rows onto this
        operator's ring: live panes move row (p % old_ring) ->
        (p % new_ring); retired rows are the aggregate identity."""
        if old_ring == self._ring:
            return vals
        span = self._live_pane_span()
        if len(span) > self._ring:
            raise RuntimeError(
                f"cannot restore onto ring {self._ring}: {len(span)} panes "
                "are live; increase ring_size")
        out = np.full((self._ring, vals.shape[1]), _identity(kind, dtype),
                      dtype=vals.dtype)
        for p in span:
            out[p % self._ring] = vals[p % old_ring]
        return out

    def _restore_backends(self, snaps: list[dict]) -> None:
        snaps = [s for s in snaps if len(s.get("keys", ()))
                 or s.get("states")]
        if not snaps:
            return
        # agg program config comes from the snapshot itself (schema not yet
        # seen at restore time), like the reference rebuilding serializers
        # from their snapshots
        meta = {}
        for s in snaps:
            meta.update(s["states"])
        defs = [AggDef(n, m["kind"], jnp.dtype(m["dtype"]))
                for n, m in meta.items()]
        # capacity: smallest power of two giving every shard 2x headroom
        n_keys = sum(len(s["keys"]) for s in snaps)
        per_shard = max(1, (2 * n_keys) // self._n_devices)
        cap = self._capacity
        while cap < per_shard:
            cap <<= 1
        self._build(defs, capacity=cap)
        self._load_snapshot_into_state(snaps)

    def _load_snapshot_into_state(self, snaps: list[dict]) -> None:
        """Filter restored keys into each shard's key-group range and
        rebuild per-shard tables + accumulators (the
        StateAssignmentOperation re-distribution step)."""
        all_keys = np.concatenate(
            [np.asarray(s["keys"], np.int64) for s in snaps])
        all_groups = np.concatenate(
            [np.asarray(s["key_groups"], np.int32) for s in snaps])
        vals: dict[str, np.ndarray] = {}
        for a in self._agg.aggs:
            parts = []
            for s in snaps:
                sd = s.get("states", {}).get(a.name)
                if sd is None:
                    continue
                parts.append(self._remap_ring_rows(
                    np.asarray(sd["values"]), int(sd["ring"]),
                    a.kind, a.dtype))
            vals[a.name] = (np.concatenate(parts, axis=-1) if parts
                            else np.empty((self._ring, 0)))
        D, cap, ring = self._n_devices, self._agg.capacity, self._ring
        tables = np.empty((D, cap), np.int64)
        accs = {a.name: np.full((D, ring, cap), _identity(a.kind, a.dtype))
                for a in self._agg.aggs}
        for d, rng in enumerate(self._agg.shard_ranges):
            sel = (all_groups >= rng.start) & (all_groups <= rng.end)
            keys_d = all_keys[sel]
            table_d = make_table(cap)
            if len(keys_d):
                table_d, slots, ok = lookup_or_insert(
                    table_d, jnp.asarray(keys_d))
                if not bool(jax.device_get(ok.all())):
                    raise RuntimeError(
                        "mesh restore overflow: raise capacity")
            tables[d] = np.asarray(jax.device_get(table_d))
            if len(keys_d):
                at = np.asarray(jax.device_get(slots))
                for a in self._agg.aggs:
                    accs[a.name][d][:, at] = vals[a.name][:, sel]
        # host arrays go to their shards directly: through jnp.asarray the
        # whole [D, ...] array would sit on one device first. A plane the
        # state keeps as its two words is split HERE, with numpy, and the
        # words go up: no device splits or joins a plane, here or anywhere
        sharding = self._agg._sharding

        def put(host: np.ndarray):
            return jax.device_put(host, sharding)

        self._state = None
        self._state = ShardedWindowState(
            table=put(tables),
            accs={n: plane_map(put, Halves.split(v)
                               if stores_halves(v.dtype, ring) else v)
                  for n, v in accs.items()},
            dropped=put(np.zeros(D, np.int64)))
        self._occ_known = int((tables != np.int64(EMPTY_KEY)).sum(1).max())

    # -- teardown ----------------------------------------------------------
    def finish(self) -> None:
        self._flush(pad=True)
        self._drain(block=True)
        self._note_rounds(block=True)
        self._finish_reclaim(block=True, grow=False)
