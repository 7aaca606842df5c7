"""Device slice-window operator: the north-star TPU execution path.

Replaces the reference's per-record window hot loop
(WindowOperator.processElement:278 + slice-shared table path
SliceSharedWindowAggProcessor) with whole-batch device execution:

* each micro-batch runs ONE compiled step — hash keys -> device hash-table
  slot resolution -> pane index -> one scatter-fold per aggregate into a
  [ring, capacity] pane accumulator (the slice decomposition of §5.7b:
  sliding windows never aggregate a record twice);
* there are NO per-key timers: a window ending at pane boundary ``p_end``
  fires when the (host-scalar) watermark passes ``p_end*pane - 1``, and the
  fire is one pane-merge reduction over all keys in the subtask's key-group
  range (BASELINE north star), after which the retired pane's ring row is
  zeroed for reuse;
* under shard_map the identical step runs per device on its key-group shard
  (keys are partitioned, so keyed aggregation needs no collective; global
  post-aggregations psum — see parallel/).

Late records (pane already fired) are dropped and counted, matching the host
operator at allowed_lateness=0; use the host WindowOperator for lateness
re-firing or merging windows.
"""

from __future__ import annotations

import time
from collections import deque
from functools import partial
from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ...core.device_records import DeviceRecordBatch
from ...core.elements import Watermark
from ...core.records import MIN_TIMESTAMP, RecordBatch, Schema
from ...metrics.device import DEVICE_STATS, count_plane_form, \
    instrumented_program_cache, pytree_nbytes
from ...metrics.tracing import TRACER
from ..faults import DeviceGuard, DeviceSegmentError, FAULTS, \
    fire_with_retries
from ..watchdog import WATCHDOG, stall_bounded
from ...ops.hash_table import EMPTY_KEY, lookup_or_insert, \
    sanitize_keys_device
from ...state.tpu_backend import TpuKeyedStateBackend
from ...window.assigners import WindowAssigner
from .base import OneInputOperator, OperatorContext, Output
from .slice_control import IN_ORDER_RING_ROWS, AsyncFireQueue, \
    CoalescingIngest, SliceControlPlane

__all__ = ["DeviceWindowAggOperator", "AggSpec"]


class AggSpec:
    """One aggregate column: kind in sum|count|min|max|avg over field.

    ``value_bits``: the promise that the aggregate's RESULT is non-
    negative and below 2^value_bits. The fire's top-k select (ops/topk.py
    ``threshold_topk``) walks the bits the data has whatever is declared;
    the promise only spares it what it then need not compile: at most 32
    bits, the 64-bit view; under the plane's width, the guard against a
    negative rank. Defaults: 48 for count (exact up to 2.8e14 events per
    key per window), 64 (no promise) otherwise."""

    def __init__(self, kind: str, field: Optional[str] = None,
                 out_name: Optional[str] = None, dtype=jnp.float32,
                 value_bits: Optional[int] = None):
        if kind not in ("sum", "count", "min", "max", "avg"):
            raise ValueError(f"unsupported device aggregate {kind}")
        self.kind = kind
        self.field = field
        self.out_name = out_name or (f"{kind}_{field}" if field else kind)
        self.dtype = dtype
        self.value_bits = (value_bits if value_bits is not None
                           else 48 if kind == "count" else 64)


from ...ops.topk import threshold_topk  # noqa: E402
# exact top-k by threshold select, not by sort: lax.top_k over a
# [capacity] accumulator is a variant of a full sort (see ops/topk.py)


def _select_topk(ranked, emit, topk: int, value_bits: int):
    """The ranked fire's select: (slots [k], ok [k], int32 [passes,
    fell_back]); the last leaf rides to the host in the fire's one copy,
    as the mesh fire's does."""
    top = threshold_topk(ranked, emit, topk, value_bits)
    return (top.indices.astype(jnp.int64), top.ok,
            jnp.stack([top.passes, top.fell_back.astype(jnp.int32)]))


def _step_body(fold_sig: tuple, ring: int, pane: int, offset: int,
               dirty_block: int, spill_maxp: int = 0,
               count_kind: str = "count"):
    """The UNJITTED ingest-step body — pane assignment + late masking +
    hash-table lookup-or-insert + every scatter-fold. ``_step_program``
    wraps it in a donated jit (the standalone per-batch dispatch); the
    fused-chain lowering (runtime/compiled.py) composes it with the
    source decode under ONE jit instead, so the certified
    source→window prefix is a single XLA dispatch. ``count_kind``: what
    the hidden plane folds by (``_count_plane``)."""
    from ...ops.segment_ops import ring_fold

    spill = spill_maxp > 0

    def step_fn(table, arrays, dropped, late, dirty, stage, touch, keys, ts,
                cols, spilled, batch_no, first_open, n_valid):
        panes = (ts.astype(jnp.int64) - offset) // pane
        # rows at/after n_valid are power-of-two padding (constant shapes
        # keep ONE executable across variable upstream batch lengths, e.g.
        # behind a WHERE filter); they fold nothing and count nothing
        in_batch = jnp.arange(keys.shape[0]) < n_valid
        fresh = (panes >= first_open) & in_batch
        late = late + jnp.sum(~fresh & in_batch).astype(jnp.int64)
        keys = sanitize_keys_device(keys)
        if spill:
            from ...parallel.mesh import key_groups_device

            groups = key_groups_device(keys, spill_maxp)
            # padding rows must not touch the LRU clock (their zero key's
            # group would read permanently hot and pin residency)
            touch = touch.at[jnp.where(in_batch, groups, spill_maxp)].max(
                batch_no, mode="drop")
            sp = spilled[groups]
            table, slots, ok = lookup_or_insert(table, keys, fresh & ~sp)
            to_host = fresh & (sp | ~ok)
            S = stage["keys"].shape[0]
            base = stage["count"]
            pos = base + jnp.cumsum(to_host) - 1
            can = to_host & (pos < S)
            dropped = dropped + jnp.sum(to_host & ~can).astype(jnp.int64)
            widx = jnp.where(can, pos, S).astype(jnp.int64)
            stage = dict(stage)
            stage["keys"] = stage["keys"].at[widx].set(keys, mode="drop")
            stage["ring"] = stage["ring"].at[widx].set(
                (panes % ring).astype(jnp.int32), mode="drop")
            for _kind, name, field in fold_sig:
                stage[name] = stage[name].at[widx].set(
                    cols[field].astype(stage[name].dtype), mode="drop")
            stage["count"] = base + jnp.sum(to_host).astype(jnp.int64)
        else:
            table, slots, ok = lookup_or_insert(table, keys, fresh)
            dropped = dropped + jnp.sum(~ok & fresh).astype(jnp.int64)
        ring_idx = (panes % ring).astype(jnp.int32)
        count = arrays["__count__"]
        out = dict(arrays)
        out["__count__"] = ring_fold(
            count_kind, count, ring_idx, slots,
            jnp.ones(keys.shape[0], count.dtype), ok)
        for kind, name, field in fold_sig:
            out[name] = ring_fold(kind, arrays[name], ring_idx, slots,
                                  cols[field], ok)
        # incremental-snapshot capture: mark touched dirty blocks
        dirty = dirty.at[jnp.maximum(slots, 0) // dirty_block].set(True)
        # completion token: a fresh scalar buffer that is NEVER fed back
        # into a donated argument, so the host can block on it to bound
        # the in-flight backlog (every other output becomes a donated
        # input of the next step and would be a deleted buffer by then)
        token = late + dropped
        return table, out, dropped, late, dirty, stage, touch, token

    return step_fn


@instrumented_program_cache("device_window.step")
def _step_program(fold_sig: tuple, ring: int, pane: int, offset: int,
                  dirty_block: int, spill_maxp: int = 0,
                  count_kind: str = "count"):
    """ONE compiled program per batch for the device-resident ingest path
    (see ``_step_body`` for what runs inside), over columns that are
    ALREADY in HBM (DeviceRecordBatch). This is the whole per-batch hot
    loop in a single dispatch — the analog of the reference's record loop
    StreamTask.processInput:588 → WindowOperator.processElement:278,
    executed once per micro-batch with zero host<->device transfers.
    State buffers are donated so XLA updates them in place instead of
    copying [ring, capacity] arrays every batch.

    ``fold_sig`` is a tuple of (fold_kind, state_name, field). The hidden
    plane ("__count__") folds implicitly, a one a row, by ``count_kind``.

    ``spill_maxp`` > 0 enables the deferred-spill split (HBM budget +
    defer_overflow): records of spilled key groups — and failed inserts —
    are excluded from the device fold and compacted into the ``stage``
    buffers for the host tier, still with zero host syncs; the per-group
    LRU clock updates on device. Stage overflow (more rows than the
    staging capacity between watermarks) counts into ``dropped`` and
    fails loudly at the next health check.
    """
    donate = (0, 1, 2, 3, 4, 5, 6) if spill_maxp > 0 else (0, 1, 2, 3, 4)
    return partial(jax.jit, donate_argnums=donate)(
        _step_body(fold_sig, ring, pane, offset, dirty_block, spill_maxp,
                   count_kind))


@instrumented_program_cache("device_window.fire")
def _fire_program(agg_sig: tuple, topk: Optional[int],
                  topk_value_bits: int = 64, count_kind: str = "count"):
    """ONE compiled program per (aggregate signature, top-k) covering the
    whole fire: masked pane-row merge for every aggregate + emit mask +
    optional device top-k + health scalars. Module-level and cached so
    every operator instance with the same shape shares the executable —
    fire programs must never recompile per instance or per pane count
    (a compile costs seconds to a minute on the chip). ``pane_rows`` is
    therefore PADDED to the window width with a
    validity mask instead of varying in shape. ``count_kind``: what the
    hidden plane's W rows merge by (``_count_plane``); whatever it is, a
    key emits iff the merge is positive."""
    from ...ops.segment_ops import AGG_INITS, AGG_MERGES, plane_take

    @jax.jit
    def fire_fn(table, arrays, pane_rows, rows_valid, dropped):
        # named regions (module names stay, the benchmark matches them):
        # an op's name path (HLO op_name; tf_op in a TPU trace's op
        # metadata) says fire.merge / fire.topk, whatever fusion number
        # the compiler gave it. A plane stored as its 32-bit words
        # (ops/segment_ops.Halves) has the window's rows, or the
        # winners' cells, gathered of each word and joined after the
        # gather: the fire reads W ring rows of a plane, never all of it
        def merge(kind, arr):
            with jax.named_scope("fire.merge"):
                sub = plane_take(arr, lambda a: a[pane_rows])  # [W, cap]
                ident = AGG_INITS[kind](arr.dtype)
                sub = jnp.where(rows_valid[:, None], sub, ident)
                return AGG_MERGES[kind](sub, axis=0)

        def merge_at(kind, arr, idx):
            # winner-only merge: ONE [W, k] two-axis gather instead of a
            # full [W, capacity] pane merge — with emit_topk only k slots
            # ever emit, so secondary aggregates never pay the
            # full-capacity read. (NOT arr[pane_rows][:, idx]: the
            # chained form materializes the [W, cap] intermediate.)
            with jax.named_scope("fire.merge"):
                sub = plane_take(
                    arr, lambda a: a[pane_rows[:, None], idx[None, :]])
                ident = AGG_INITS[kind](arr.dtype)
                sub = jnp.where(rows_valid[:, None], sub, ident)
                return AGG_MERGES[kind](sub, axis=0)

        count = merge(count_kind, arrays["__count__"])
        with jax.named_scope("fire.merge"):
            emit = (table != jnp.int64(EMPTY_KEY)) & (count > 0)
            occ = (table != jnp.int64(EMPTY_KEY)).sum()
        if topk is not None:
            # rank on the FIRST aggregate; everything else gathers at the
            # k winners only
            rk_kind, rk_name = agg_sig[0]
            if rk_kind == "count":
                ranked = count
            elif rk_kind == "avg":
                s = merge("sum", arrays[f"{rk_name}.sum"])
                ranked = s / jnp.maximum(count, 1).astype(s.dtype)
            else:
                ranked = merge(rk_kind, arrays[rk_name])
            # the select and the winners' rows (merge_at's gathers stay
            # fire.merge: the innermost scope names the region)
            with jax.named_scope("fire.topk"):
                idx, ok, select = _select_topk(ranked, emit, topk,
                                               topk_value_bits)
                keys = jnp.take(table, idx)
                count_k = jnp.take(count, idx)
                out = {}
                for kind, out_name in agg_sig:
                    if out_name == rk_name:
                        out[out_name] = jnp.take(ranked, idx)
                    elif kind == "count":
                        out[out_name] = count_k
                    elif kind == "avg":
                        s = merge_at("sum", arrays[f"{out_name}.sum"], idx)
                        out[out_name] = s / jnp.maximum(
                            count_k, 1).astype(s.dtype)
                    else:
                        out[out_name] = merge_at(kind, arrays[out_name],
                                                 idx)
            return keys, ok, out, dropped, occ, select
        results = {}
        for kind, out_name in agg_sig:
            if kind == "count":
                results[out_name] = count
            elif kind == "avg":
                s = merge("sum", arrays[f"{out_name}.sum"])
                results[out_name] = s / jnp.maximum(count, 1).astype(s.dtype)
            else:
                results[out_name] = merge(kind, arrays[out_name])
        return table, emit, results, dropped, occ

    return fire_fn


class DeviceWindowAggOperator(AsyncFireQueue, CoalescingIngest,
                              SliceControlPlane, OneInputOperator):
    def __init__(self, assigner: WindowAssigner, key_column: str,
                 aggs: Sequence[AggSpec],
                 capacity: int = 1 << 16,
                 ring_size: int = 64,
                 emit_window_bounds: bool = True,
                 emit_topk: Optional[int] = None,
                 defer_overflow: bool = False,
                 async_fire: bool = False,
                 hbm_budget_slots: int = 0,
                 spill_staging_slots: int = 1 << 16,
                 name: str = "DeviceWindowAgg"):
        """``emit_topk``: emit only the k keys with the largest value of the
        FIRST aggregate per window (one device lax.top_k instead of a full
        [capacity] host materialization) — the Nexmark Q5 hot-items /
        ORDER BY ... LIMIT k fire shape.

        ``defer_overflow``: never sync the hot path with the host; hash
        overflow accumulates in a device counter checked at fire time.
        ``async_fire``: fire programs emit asynchronously — results are
        drained once their device->host copy lands, and watermarks are
        held behind their fires. Both default off (fully synchronous
        semantics); the benchmark/production path enables both."""
        super().__init__(name)
        pane = assigner.pane_size
        if pane is None:
            raise ValueError(
                "Device window operator needs a pane-decomposable assigner "
                "(tumbling, or sliding with size % slide == 0)")
        from ...window.assigners import reject_variable_pane_assigner
        reject_variable_pane_assigner(assigner, "device")
        self._assigner = assigner
        self._pane = int(pane)
        self._offset = int(getattr(assigner, "offset", 0))
        size = getattr(assigner, "size", self._pane)
        self._window_panes = int(size) // self._pane  # W panes per window
        self._ring = int(ring_size)
        if self._ring < self._window_panes + 1:
            raise ValueError("ring_size must exceed panes per window")
        self._key_column = key_column
        self._aggs = list(aggs)
        self._capacity = capacity
        self._emit_bounds = emit_window_bounds
        self._topk = emit_topk
        self._defer = bool(defer_overflow)
        self._async = bool(async_fire)
        self._hbm_budget = int(hbm_budget_slots)
        self._stage_slots = int(spill_staging_slots)
        self._stage = None  # deferred-spill staging buffers (device)

        self._backend: Optional[TpuKeyedStateBackend] = None
        self._init_control_plane()
        if self._async:
            self._record_fire_latency = False
        self._init_async_fires()
        # bounded in-flight window: the host thread can dispatch an entire
        # bounded stream into the device queue before the first program
        # retires, which pushes every queued fire's completion (and its
        # latency) to the end of the run. Holding a small deque of step
        # outputs and blocking on the (k-2)th before admitting batch k
        # keeps the device fed while capping the backlog — p99 fire
        # latency then tracks the per-batch service time instead of the
        # job tail.
        self._inflight: deque = deque()
        self._max_inflight = 2  # overridable via task.max-inflight (setup)
        self._fire_fn = None
        self._out_schema: Optional[Schema] = None
        self._late_dev = None  # device late-drop counter (device ingest)
        self._late_cached = 0  # host cache of _late_dev (metrics scrapes
        # must never force a device sync; refreshed at fire/checkpoint
        # boundaries)
        self._init_coalescer()
        # degradation ladder (docs/ROBUSTNESS.md): once a persistent
        # compiled-segment failure evacuates state to host, this operator
        # is pinned to the synchronous host-view ingest for its lifetime
        self._degraded = False
        self._degrade_enabled = True
        self._validate_batches = False
        self._guard: Optional[DeviceGuard] = None
        self.quarantined_batches = 0
        # certified fused-chain lowering (graph/fusion.py lowered_prefix):
        # armed by the deployer via enable_fused_chain, built lazily once
        # aggregate dtypes are known
        self._fused_spec = None     # (source, subtask, parallelism)
        self._fused_chain = None    # runtime.compiled.FusedChain
        # wall-clock per hot-path stage (bench breakdown), each the sum of
        # its stage spans' durations (one timing site, metrics/tracing.py
        # Stage): ingest = window/Upload + window/IngestDispatch, fire =
        # window/FireDispatch, drain = window/Drain + window/Emit
        self.stage_s: dict[str, float] = {"ingest": 0.0, "fire": 0.0,
                                          "drain": 0.0}
        self._batch_seq = 0  # ordinal of the batch being ingested (spans)

    # -- lifecycle ---------------------------------------------------------
    def setup(self, ctx: OperatorContext, output: Output) -> None:
        super().setup(ctx, output)
        from ...core.config import FaultOptions, StateOptions, TaskOptions
        budget = self._hbm_budget or ctx.config.get(
            StateOptions.TPU_HBM_BUDGET)
        if not budget:
            # byte-denominated budget: convert to slots from the per-slot
            # footprint this operator allocates — the 8-byte table key
            # plus every [ring, capacity] accumulator plane row: the
            # hidden plane at its own width, and one value plane per
            # non-count aggregate (avg's sum plane included) at 8 bytes
            # per cell (the widest dtype the value planes use; narrower
            # dtypes just land under budget)
            budget_bytes = int(ctx.config.get(
                StateOptions.TPU_HBM_BUDGET_BYTES) or 0)
            if budget_bytes:
                value_planes = sum(1 for a in self._aggs
                                   if a.kind != "count")
                slot_bytes = 8 + (self._ring or 1) * (
                    np.dtype(self._count_plane()[1]).itemsize
                    + 8 * value_planes)
                budget = max(1, budget_bytes // slot_bytes)
        self._max_inflight = max(1, int(
            ctx.config.get(TaskOptions.MAX_INFLIGHT)))
        self._coalesce_target = int(
            ctx.config.get(TaskOptions.COALESCE_TARGET_RECORDS))
        self._coalesce_timeout_s = float(
            ctx.config.get(TaskOptions.COALESCE_TIMEOUT_MS)) / 1e3
        self._guard = DeviceGuard("device_window", ctx.config)
        self._degrade_enabled = bool(
            ctx.config.get(FaultOptions.DEGRADATION))
        self._validate_batches = bool(
            ctx.config.get(FaultOptions.VALIDATE_BATCHES))
        self._backend = TpuKeyedStateBackend(
            ctx.key_group_range, ctx.max_parallelism,
            capacity=self._capacity, config=ctx.config,
            defer_overflow=self._defer,
            hbm_budget_slots=budget)
        if self._backend.tiering_active:
            from ...state.tiering import register_residency
            register_residency(
                f"{ctx.task_name}/{ctx.subtask_index}",
                self._backend.residency)
        self._backend.register_array_state(
            "__count__", *self._count_plane(), ring=self._ring)
        self._registered = False

    def _count_plane(self) -> tuple:
        """(kind, dtype) the hidden plane ``__count__`` is registered
        with: what the job needs of it, decided by its aggregate kinds
        alone. A job that READS the count (a COUNT emits it, an AVG
        divides by it) keeps a count, and its width follows the declared
        result bound: a COUNT with value_bits <= 31 promises every
        per-window count fits int32, which halves the fold scatter + fire
        merge traffic on the [ring, capacity] plane and feeds the uint32
        threshold select directly; no such promise, int64. A job that
        reads no count needs the plane for one thing, the fire's "did a
        record of this key fall in this window": a 32-bit presence plane
        (``ops/segment_ops.AGG_INITS``), whose fold is a saturating mark
        and cannot wrap where an int32 count of 2^31 records would. (A
        restored backend keeps the plane its snapshot holds, whatever
        this says: ``_count_form``.)"""
        if not any(a.kind in ("count", "avg") for a in self._aggs):
            return "presence", jnp.int32
        cvb = min((a.value_bits for a in self._aggs if a.kind == "count"),
                  default=64)
        return "count", jnp.int32 if cvb <= 31 else jnp.int64

    def _count_kind(self) -> str:
        """What the hidden plane folds and merges by, as the backend
        holds it (a savepoint written with an int64 count restores into
        a job that would register a presence plane, and runs on)."""
        return self._backend.array_kind("__count__")

    def _count_args(self) -> tuple:
        """The trailing ``count_kind`` argument of the step's and the
        fire's builders: none for a count, so a job that reads the count
        keeps the program keys (in memory and in the persistent AOT
        cache) it had before the presence plane existed."""
        kind = self._count_kind()
        return () if kind == "count" else (kind,)

    def _count_form(self) -> str:
        """The hidden plane's form, for the window/Drain span and the
        operator's gauge: ``presence32`` | ``count32`` | ``count64``."""
        return count_plane_form(
            self._count_kind(), self._backend.get_array("__count__").dtype)

    def enable_fused_chain(self, source, subtask: int,
                           parallelism: int) -> bool:
        """Arm the certified source→window lowering (called by the
        deployer when the job's FusionCertificate carries a
        ``lowered_prefix`` for this vertex, BEFORE setup). The upstream
        reader then emits ``LazyDeviceBatch`` handles and this operator
        folds each with one composed decode+step dispatch. Only legal
        under deferred-overflow semantics — the composed program checks
        nothing synchronously, exactly like ``_ingest_device``."""
        if not self._defer:
            return False
        self._fused_spec = (source, int(subtask), int(parallelism))
        return True

    def _register_aggs(self, schema: Schema, batch_rows: int) -> None:
        """Accumulator dtypes follow the input columns (sum over int64
        accumulates int64, matching the host operator's Python arithmetic);
        avg always accumulates float."""
        for a in self._aggs:
            if a.field is not None and a.field in schema:
                col_dtype = np.dtype(schema.field(a.field).dtype)
                a.dtype = (jnp.float32 if a.kind == "avg"
                           else jnp.dtype(col_dtype))
            if a.kind == "avg":
                self._backend.register_array_state(
                    f"{a.out_name}.sum", "sum", a.dtype, ring=self._ring)
            elif a.kind != "count":
                self._backend.register_array_state(
                    a.out_name, a.kind, a.dtype, ring=self._ring)
        self._registered = True
        DEVICE_STATS.note_count_plane(self._count_form())
        # every plane of the job exists now and no input has been taken:
        # the reclaim of these planes is built here, not when a reading
        # finds the table full (ROADMAP D14), and beside it the probe
        # program a new table's wide batches will run
        self._backend.prepare(batch_rows)

    def initialize_state(self, keyed_snapshots: list, operator_snapshot) -> None:
        if keyed_snapshots:
            self._backend.restore([s["backend"] for s in keyed_snapshots])
            self._restore_control_meta([s["meta"] for s in keyed_snapshots])
            # checkpoints taken under a different ring size re-seat their
            # live pane rows onto this operator's ring
            first = self._min_seen_pane
            if first is not None and self._fired_boundary is not None:
                first = max(first, self._fired_boundary - self._window_panes)
            live = (range(first, self._max_seen_pane + 1)
                    if first is not None else range(0))
            self._backend.conform_ring(self._ring, live)

    # -- data path ---------------------------------------------------------
    def process_batch(self, batch: RecordBatch) -> None:
        self._last_batch_ns = time.monotonic_ns()
        if batch.n == 0:
            return
        if self._coalesce_target > 1:
            from ...core.device_records import LazyDeviceBatch
            if isinstance(batch, LazyDeviceBatch):
                # a lazy chain batch is already a full micro-batch; admit
                # it directly (flushing buffered host batches first keeps
                # arrival order)
                self._coalesce_flush()
            else:
                self._coalesce_admit(batch)
                return
        self._process_batch_now(batch)

    def _process_batch_now(self, batch: RecordBatch) -> None:
        if self._pending:
            self._drain(block=False)
        if batch.n == 0:
            return
        if not self._registered:
            key_dtype = batch.schema.field(self._key_column).dtype
            if key_dtype is object or not np.issubdtype(np.dtype(key_dtype),
                                                        np.integer):
                raise TypeError(
                    f"device window aggregation needs an integer key column; "
                    f"{self._key_column!r} is {key_dtype} — use the hashmap "
                    "state backend for float/string keys")
            self._register_aggs(batch.schema, batch.n)
        if self._validate_batches:
            batch = self._screen_nonfinite(batch)
            if batch.n == 0:
                return
        self._batch_seq += 1
        from ...core.device_records import LazyDeviceBatch
        if (self._fused_spec is not None
                and isinstance(batch, LazyDeviceBatch)
                and batch._realized is None
                and not self._degraded
                and not self._spill_deferred):
            # certified fused chain: decode + fold in ONE dispatch; any
            # condition above failing lets the lazy batch realize through
            # the ordinary ladder below (graceful unfusing)
            self._ingest_chain(batch)
        elif self._degraded:
            # degradation ladder, last rung: slot resolution through the
            # synchronous backend path; device batches are read back as
            # host columns
            self._ingest_degraded(batch)
        elif (isinstance(batch, DeviceRecordBatch) and self._defer
                and batch.dtimestamps is not None):
            self._ingest_device(batch)
        elif self._spill_deferred:
            # deferred spill runs the fused device split for host batches
            # too: upload the needed columns and go through the one-dispatch
            # path (the staging compaction needs the device key groups)
            self._ingest_device(self._to_device_batch(batch))
        else:
            keys = batch.column(self._key_column).astype(np.int64)
            self._ingest(batch, keys)

    @property
    def _spill_deferred(self) -> bool:
        return (self._defer and self._backend is not None
                and self._backend.hbm_budget > 0)

    # -- stage spans of one batch (metrics/tracing.py Stage) ---------------
    def _upload_stage(self):
        """window/Upload: packing a host batch + its host->device copy
        (device/H2D nests under it); the caller sets ``bytes``."""
        return TRACER.stage("window", "Upload", seq=self._batch_seq,
                            total=(self.stage_s, "ingest"))

    def _dispatch_stage(self, **attrs):
        """window/IngestDispatch: the host's time to enqueue the batch's
        ingest programs (the device runs them later); ``programs`` and
        ``ring_rows`` where the caller knows them."""
        return TRACER.stage("window", "IngestDispatch",
                            seq=self._batch_seq,
                            total=(self.stage_s, "ingest"), **attrs)

    def _to_device_batch(self, batch: RecordBatch) -> DeviceRecordBatch:
        ts = batch.timestamps

        def upload():
            cols = {self._key_column: jnp.asarray(
                batch.column(self._key_column).astype(np.int64))}
            for a in self._aggs:
                if a.field is not None and a.field not in cols:
                    cols[a.field] = jnp.asarray(batch.column(a.field))
            return cols, jnp.asarray(ts)

        # deadline-bounded idempotent upload (pure function of host data:
        # a stall-abandoned attempt re-runs safely)
        with self._upload_stage() as up:
            cols, dts = stall_bounded("transfer.h2d", upload,
                                      scope="device_window")
            nbytes = pytree_nbytes(cols) + dts.nbytes
            up.set("bytes", nbytes)
            DEVICE_STATS.note_h2d(nbytes, batch.n)
        schema = Schema([(f.name, f.dtype) for f in batch.schema.fields
                         if f.name in cols])
        return DeviceRecordBatch(schema, cols, dts,
                                 int(ts.min()), int(ts.max()))

    # -- degradation ladder / dead-letter quarantine ------------------------
    def _screen_nonfinite(self, batch: RecordBatch) -> RecordBatch:
        """faults.validate-batches: rows carrying NaN/Inf in any
        aggregated float column are quarantined to the dead-letter output
        BEFORE folding — a NaN folded into a sum/avg plane poisons every
        later window of that key."""
        bad = None
        for a in self._aggs:
            if a.field is None:
                continue
            col = np.asarray(self._host_view(batch).column(a.field))
            if not np.issubdtype(col.dtype, np.floating):
                continue
            mask = ~np.isfinite(col)
            bad = mask if bad is None else (bad | mask)
        if bad is None or not bad.any():
            return batch
        hb = self._host_view(batch)
        self._dead_letter(hb.filter(bad))
        return hb.filter(~bad)

    def _dead_letter(self, batch: RecordBatch) -> None:
        """Quarantine a (host-viewed) batch: counted, side-emitted under
        the 'dead-letter' tag when a side output is wired, never folded."""
        DEVICE_STATS.note_dead_letter(batch.n)
        self.quarantined_batches += 1
        try:
            self.output.emit_side("dead-letter", batch)
        except NotImplementedError:
            pass  # no side output wired: the counter is the record

    def _degrade(self, cause: BaseException) -> None:
        """Persistent compiled-segment failure: evacuate device state to
        host through the existing snapshot path, rebuild the backend in
        its synchronous host-fallback configuration, and pin this
        operator to the CPU ingest path. Keyed state and the pane/fire
        metadata survive verbatim, so exactly-once results are preserved;
        the fault-injection sites stop firing for this operator (the
        fallback of last resort is never chaos-injected)."""
        if self._degraded:
            raise cause
        with FAULTS.suppressed():
            self._drain(block=True)
            while self._inflight:
                jax.block_until_ready(self._inflight.popleft())
            self._pre_fire_flush()
            snap = self._backend.snapshot(-1)
            if self._late_dev is not None:
                # lint: sync-ok degrade path: final drain of the device counter, once per degrade
                self._late_dropped += int(jax.device_get(self._late_dev))
                self._late_dev = None
                self._late_cached = 0
            new_backend = TpuKeyedStateBackend(
                self.ctx.key_group_range, self.ctx.max_parallelism,
                capacity=self._capacity, defer_overflow=False,
                hbm_budget_slots=0)
            new_backend.restore([snap])
        if self._backend.tiering_active:
            # the fallback backend is unbudgeted: retire the residency
            # registry entry (and any queued prefetch staging) with it
            self._backend.prefetch_pipeline.cancel()
            from ...state.tiering import unregister_residency
            unregister_residency(
                f"{self.ctx.task_name}/{self.ctx.subtask_index}")
        self._backend = new_backend
        self._defer = False
        self._stage = None
        self._degraded = True
        self._guard.active = False
        DEVICE_STATS.note_degraded("device_window")

    def _on_segment_failure(self, err: DeviceSegmentError,
                            batch=None) -> bool:
        """Shared escalation: poison faults quarantine the batch (returns
        True: handled, nothing folded); anything else degrades when
        allowed (returns False: caller re-runs through the fallback) or
        re-raises into task failover."""
        if err.poison and batch is not None:
            self._dead_letter(self._host_view(batch))
            return True
        if self._degrade_enabled and not self._degraded:
            self._degrade(err)
            return False
        raise err

    # -- device-resident ingest (zero-transfer hot path) --------------------
    def _fold_sig(self) -> tuple:
        sig = []
        for a in self._aggs:
            if a.kind == "count":
                continue
            name = f"{a.out_name}.sum" if a.kind == "avg" else a.out_name
            sig.append(("sum" if a.kind == "avg" else a.kind, name, a.field))
        return tuple(sig)

    def _ingest_device(self, batch: DeviceRecordBatch) -> None:
        """Whole-batch ingest of device-born columns: host does only pane
        bookkeeping on the batch's event-time BOUNDS; the data plane is one
        compiled dispatch (see _step_program). Late records are masked and
        counted on device; a batch wholly behind the fired boundary is
        dropped without any device work at all."""
        pane_lo = (batch.ts_min - self._offset) // self._pane
        pane_hi = (batch.ts_max - self._offset) // self._pane
        first_open = (self._fired_boundary - self._window_panes
                      if self._fired_boundary is not None else None)
        if first_open is not None and pane_hi < first_open:
            self._late_dropped += batch.n
            return
        eff_lo = pane_lo if first_open is None else max(pane_lo, first_open)
        self._max_seen_pane = (pane_hi if self._max_seen_pane is None
                               else max(self._max_seen_pane, pane_hi))
        self._min_seen_pane = (eff_lo if self._min_seen_pane is None
                               else min(self._min_seen_pane, eff_lo))
        low = (first_open if self._fired_boundary is not None
               else self._min_seen_pane)
        if pane_hi - low >= self._ring:
            raise RuntimeError(
                f"pane ring overflow: open span [{low},{pane_hi}] exceeds "
                f"ring {self._ring}; increase ring_size or reduce "
                "watermark lag")
        if self._late_dev is None:
            self._late_dev = jnp.zeros((), jnp.int64)
        spill = self._spill_deferred
        if spill and self._stage is None:
            self._alloc_stage()
        sig = self._fold_sig()
        fo = np.int64(first_open if first_open is not None else MIN_TIMESTAMP)

        def dispatch():
            step = _step_program(sig, self._ring, self._pane, self._offset,
                                 self._backend.dirty_block_size,
                                 self._backend.max_parallelism if spill
                                 else 0, *self._count_args())
            arrays = {n: self._backend.get_array(n)
                      for n in self._fire_array_names()}
            from ...ops.segment_ops import pow2_ceil

            n = batch.n
            P = pow2_ceil(n)

            def _pad(a):
                return (a if P == n
                        else jnp.concatenate([a, jnp.zeros(P - n, a.dtype)]))

            cols = {f: _pad(batch.device_column(f)) for _k, _n, f in sig}
            return step(
                self._backend.table, arrays, self._backend.dropped_device,
                self._late_dev, self._backend.dirty_mask,
                self._stage if spill else None,
                self._backend.touch_device if spill else None,
                _pad(batch.device_column(self._key_column)),
                _pad(batch.dtimestamps), cols,
                self._backend.spilled_mask_device if spill else None,
                np.int64(self._backend.note_batch()) if spill
                else np.int64(0),
                fo, np.int64(n))

        try:
            with self._dispatch_stage(programs=1):
                table, new_arrays, dropped, late, dirty, stage, touch, \
                    token = self._guard.run(dispatch)
        except DeviceSegmentError as e:
            if self._on_segment_failure(e, batch):
                return  # poisoned batch quarantined; state untouched
            # degraded mid-stream: this batch re-runs through the host
            # ingest path against the evacuated state (nothing folded
            # device-side — the fault fired before dispatch)
            self._ingest_degraded(batch)
            return
        self._backend.table = table
        for n, a in new_arrays.items():
            self._backend.set_array(n, a)
        self._backend._dropped = dropped
        self._backend.set_dirty_mask(dirty)
        self._late_dev = late
        if spill:
            self._stage = stage
            self._backend.set_touch_device(touch)
        self._admit_token(token)

    def _ingest_chain(self, batch) -> None:
        """Certified-chain ingest: the batch is a ``LazyDeviceBatch`` —
        no columns exist yet. ONE composed program (runtime/compiled.py)
        decodes the batch from its start index and folds it into the
        donated window state; pane bookkeeping on the analytic bounds is
        identical to ``_ingest_device``."""
        pane_lo = (batch.ts_min - self._offset) // self._pane
        pane_hi = (batch.ts_max - self._offset) // self._pane
        first_open = (self._fired_boundary - self._window_panes
                      if self._fired_boundary is not None else None)
        if first_open is not None and pane_hi < first_open:
            # wholly late (contradicts the monotonic-source contract, so
            # effectively unreachable): realize so the reader's deferred
            # contract check still sees this batch's outputs
            batch.realize()
            self._late_dropped += batch.n
            return
        eff_lo = pane_lo if first_open is None else max(pane_lo, first_open)
        self._max_seen_pane = (pane_hi if self._max_seen_pane is None
                               else max(self._max_seen_pane, pane_hi))
        self._min_seen_pane = (eff_lo if self._min_seen_pane is None
                               else min(self._min_seen_pane, eff_lo))
        low = (first_open if self._fired_boundary is not None
               else self._min_seen_pane)
        if pane_hi - low >= self._ring:
            raise RuntimeError(
                f"pane ring overflow: open span [{low},{pane_hi}] exceeds "
                f"ring {self._ring}; increase ring_size or reduce "
                "watermark lag")
        if self._late_dev is None:
            self._late_dev = jnp.zeros((), jnp.int64)
        if self._fused_chain is None:
            from ..compiled import FusedChain
            source, subtask, parallelism = self._fused_spec
            self._fused_chain = FusedChain(
                source, subtask, parallelism, self._key_column,
                self._fold_sig(), self._ring, self._pane, self._offset,
                self._backend.dirty_block_size, *self._count_args())
        chain = self._fused_chain
        fo = np.int64(first_open if first_open is not None else MIN_TIMESTAMP)

        def dispatch():
            arrays = {n: self._backend.get_array(n)
                      for n in self._fire_array_names()}
            return chain.run(batch.n, batch.start, batch.prev_last,
                             self._backend.table, arrays,
                             self._backend.dropped_device, self._late_dev,
                             self._backend.dirty_mask, fo)

        try:
            with self._dispatch_stage(programs=1):
                table, new_arrays, dropped, late, dirty, viol, last, \
                    token = self._guard.run(dispatch)
        except DeviceSegmentError as e:
            if self._on_segment_failure(e, batch):
                return  # poisoned batch quarantined; state untouched
            # degraded mid-stream: re-run through the host path (realizes
            # the batch — nothing folded device-side, the fault fired
            # before dispatch)
            self._ingest_degraded(batch)
            return
        self._backend.table = table
        for n, a in new_arrays.items():
            self._backend.set_array(n, a)
        self._backend._dropped = dropped
        self._backend.set_dirty_mask(dirty)
        self._late_dev = late
        batch.deliver(viol, last)
        self._admit_token(token)

    def _alloc_stage(self) -> None:
        S = self._stage_slots
        st = {"keys": jnp.zeros(S, jnp.int64),
              "ring": jnp.zeros(S, jnp.int32),
              "count": jnp.zeros((), jnp.int64)}
        for _k, name, _f in self._fold_sig():
            st[name] = jnp.zeros(S, self._backend.get_array(name).dtype)
        self._stage = st

    def _pre_fire_flush(self) -> None:
        """Coalesced batches fold before any fire (watermark/barrier
        semantics are unchanged by buffering), then deferred spill: staged
        host-tier rows must land before any fire merges host parts
        (exactly-once per window). One tiny scalar sync per watermark, a
        buffer transfer only when something was staged. Once nothing is
        in flight for any group, the tiering boundary hook runs: heat
        decay advances and at most one staged warm->hot promotion lands
        (batch-boundary-only residency changes keep the fire path's
        scatter-free invariants and exactly-once intact)."""
        self._coalesce_flush()
        self._drain_spill_stage()
        if self._backend is not None and self._backend.tiering_active:
            self._backend.tier_boundary()

    def _drain_spill_stage(self) -> None:
        if self._stage is None:
            return
        # lint: sync-ok spill-stage drain gate, once per fire boundary
        cnt = int(jax.device_get(self._stage["count"]))
        if cnt == 0:
            return
        take = min(cnt, self._stage_slots)
        # transfer only the written prefix, rounded up to a power of two so
        # the slice program compiles O(log S) times, not once per count
        span = min(1 << (take - 1).bit_length() if take > 1 else 1,
                   self._stage_slots)
        host = stall_bounded(
            "transfer.d2h",
            # lint: sync-ok spill-stage drain, one bounded d2h per fire boundary
            lambda: jax.device_get({k: v[:span]
                                    for k, v in self._stage.items()
                                    if k != "count"}),
            scope="device_window")
        DEVICE_STATS.note_d2h(pytree_nbytes(host), take)
        keys = np.asarray(host["keys"])[:take]
        ring = np.asarray(host["ring"])[:take]
        vals = {"__count__": np.ones(take, np.int64)}
        for _k, name, _f in self._fold_sig():
            vals[name] = np.asarray(host[name])[:take]
        self._backend.drain_staged(keys, ring, vals)
        # buffers are reusable (only [0:count) is ever read): reset the
        # write position alone
        self._stage["count"] = jnp.zeros((), jnp.int64)

    def _host_view(self, batch) -> RecordBatch:
        """A batch as host columns, for the rungs that work host-side: the
        degraded ingest, the dead-letter output and the non-finite
        screen. A device batch is read back (one transfer per column)."""
        if isinstance(batch, DeviceRecordBatch):
            # lint: sync-ok off the hot path: only the degraded rung, dead letters and the non-finite screen read a device batch back
            cols = {f.name: np.asarray(batch.device_column(f.name))
                    for f in batch.schema.fields}
            ts = np.asarray(batch.dtimestamps
                            if batch.dtimestamps is not None
                            else batch.timestamps)
            return RecordBatch(batch.schema, cols, ts)
        return batch

    def _ingest_degraded(self, batch) -> None:
        """The degraded rung's ingest: the batch as host columns through
        the shared control plane and the synchronous backend."""
        hb = self._host_view(batch)
        keys = np.asarray(hb.column(self._key_column)).astype(
            np.int64, copy=False)
        self._ingest(hb, keys)

    def _admit_token(self, token) -> None:
        """Bounded in-flight window of the one-dispatch ingest paths: block
        on the (k - max_inflight)th step's completion token before
        admitting more work, then drain any landed fires. The wait
        is deadline-bounded: a dispatch that never retires (wedged chip)
        raises StallError into task failover instead of blocking the
        mailbox loop forever — its state futures are unresolvable, so
        restart-from-checkpoint is the only sound rung for this stall."""
        self._inflight.append(token)
        if len(self._inflight) > self._max_inflight:
            tok = self._inflight.popleft()
            if self._guard is not None and self._guard.active:
                WATCHDOG.run("device.execute",
                             lambda: jax.block_until_ready(tok),
                             scope="device_window.inflight")
            else:
                jax.block_until_ready(tok)
            if self._pending:
                self._drain(block=False)

    def _fold(self, batch: RecordBatch, keys: np.ndarray,
              panes: np.ndarray) -> None:
        ring_idx = panes % self._ring
        ring_rows = self._note_fold(ring_idx)
        if ring_rows > IN_ORDER_RING_ROWS:
            # out-of-order input. The fold takes a touched ring row's
            # updates a chunk of the batch at a time and skips the chunks
            # that hold none (ops/segment_ops.ring_fold), so a batch
            # shuffled over k ring rows would pay k whole batches; sorted
            # by ring row it pays one. Stable, so the updates of any one
            # cell keep their order.
            with TRACER.stage("window", "RingSort", seq=self._batch_seq,
                              total=(self.stage_s, "ingest"),
                              rows=batch.n, ring_rows=ring_rows):
                order = np.argsort(ring_idx, kind="stable")
                batch, keys, ring_idx = (batch.take(order), keys[order],
                                         ring_idx[order])
        if self._defer:
            # pipelined path: host<->device calls have a fixed cost, so
            # the whole batch rides ONE upload and nothing syncs back
            self._fold_packed(batch, keys, ring_idx, ring_rows)
            return
        with self._dispatch_stage(ring_rows=ring_rows):
            slots = self._backend.slots_for_batch(keys)
            values = {"__count__": None}
            for _kind, name, field in self._fold_sig():
                values[name] = batch.column(field)
            self._backend.fold_rings(slots, ring_idx, slots >= 0, values)

    def _fold_packed(self, batch: RecordBatch, keys: np.ndarray,
                     ring_idx: np.ndarray, ring_rows: int) -> None:
        """Pack keys + ring rows + every aggregate column into one [C, B]
        int64 buffer (floats bit-cast via float64), upload once, slice on
        device. Zero host round-trips per batch: the probe, then ONE fold
        program over all of the job's planes."""
        with self._upload_stage() as up:
            rows = [keys, ring_idx]
            col_meta: list[tuple[str, bool]] = []
            for _kind, name, field in self._fold_sig():
                col = np.asarray(batch.column(field))
                if np.issubdtype(col.dtype, np.floating):
                    rows.append(np.ascontiguousarray(
                        col.astype(np.float64)).view(np.int64))
                    col_meta.append((name, True))
                else:
                    rows.append(col.astype(np.int64))
                    col_meta.append((name, False))
            packed = np.stack(rows)
            up.set("bytes", packed.nbytes)
            buf = stall_bounded("transfer.h2d",
                                lambda: jnp.asarray(packed),  # ONE upload
                                scope="device_window")
            DEVICE_STATS.note_h2d(buf.nbytes, batch.n)
        with self._dispatch_stage(programs=2, ring_rows=ring_rows):
            slots = self._backend.slots_for_batch_device(buf[0])
            values = {"__count__": None}
            for i, (name, is_float) in enumerate(col_meta):
                vals = buf[2 + i]
                if is_float:
                    vals = jax.lax.bitcast_convert_type(vals, jnp.float64)
                values[name] = vals
            self._backend.fold_rings(slots, buf[1], slots >= 0, values)

    # -- firing (fire loop lives in SliceControlPlane) ----------------------
    # A fire is ONE compiled program (pane merge for every aggregate +
    # emit mask + optional device top-k + health scalars) whose outputs
    # start copying device->host asynchronously at dispatch. In async mode
    # the emission is queued and drained once the copy lands — fires cost
    # no synchronous round-trip, and the watermark is held behind its
    # fires so it never overtakes them downstream.

    def _fire(self, p_end: int) -> None:
        W = self._window_panes
        # never read panes below min_seen: they hold no data and their ring
        # rows may be occupied by live FUTURE panes (row aliasing); that
        # the window has a pane at all, _fire_window has checked
        first = max(p_end - W, self._min_seen_pane)
        rows = [(p % self._ring) for p in range(first, p_end)]
        # constant [W] shape: pad + mask so every fire shares one program
        pane_rows = np.zeros(W, np.int32)
        pane_rows[:len(rows)] = rows
        rows_valid = np.zeros(W, bool)
        rows_valid[:len(rows)] = True
        def dispatch():
            fire_fn = _fire_program(
                tuple((a.kind, a.out_name) for a in self._aggs), self._topk,
                self._aggs[0].value_bits
                if self._topk is not None and self._aggs else 64,
                *self._count_args())
            arrays = {n: self._backend.get_array(n)
                      for n in self._fire_array_names()}
            return fire_fn(self._backend.table, arrays,
                           jnp.asarray(pane_rows), jnp.asarray(rows_valid),
                           self._backend.dropped_device)

        try:
            outs = self._guard.run(dispatch)
        except DeviceSegmentError as e:
            # a fire has no batch to quarantine: persistent failure walks
            # the degradation ladder (state evacuates; the re-dispatch
            # reads the rebuilt backend), or re-raises into task failover
            self._on_segment_failure(e)
            outs = dispatch()
        # the host spill tier's rows merge at materialization; take them
        # NOW (before this fire retires the pane row below)
        host_part = (self._host_fire_part(np.array(rows, np.int32))
                     if self._backend.spill_active else None)
        self._enqueue_fire((p_end, outs, host_part, time.perf_counter(),
                            self._backend.table_generation))
        # retire the oldest pane of this window: no future window needs it
        # (skip panes below min_seen — their ring rows belong to live panes)
        if p_end - W >= self._min_seen_pane:
            self._backend.reset_ring_row((p_end - W) % self._ring)
        self._refresh_late(block=True)

    def _fire_array_names(self) -> list[str]:
        names = ["__count__"]
        for a in self._aggs:
            if a.kind == "count":
                continue
            names.append(f"{a.out_name}.sum" if a.kind == "avg"
                         else a.out_name)
        return names

    def _host_fire_part(self, pane_rows: np.ndarray):
        """Window results for spilled keys (numpy merges over the host
        tier's ring rows)."""
        ht = self._backend.host_tier
        hcount = ht.fire("__count__", pane_rows)
        mask = hcount > 0
        if not mask.any():
            return None
        keys = ht.keys()[mask]
        res: dict[str, np.ndarray] = {}
        for a in self._aggs:
            if a.kind == "count":
                res[a.out_name] = hcount[mask]
            elif a.kind == "avg":
                s = ht.fire(f"{a.out_name}.sum", pane_rows)[mask]
                res[a.out_name] = s / np.maximum(hcount[mask],
                                                 1).astype(s.dtype)
            else:
                res[a.out_name] = ht.fire(a.out_name, pane_rows)[mask]
        return keys, res

    def _materialize(self, item, turn: str) -> None:
        p_end, outs, host_part, t0, generation, fire = item
        with self._drain_stage(fire, turn) as drain:
            keys, results, d2h_bytes = self._drain_rows(
                outs, host_part, drain, generation)
        if len(keys):
            with self._emit_stage(fire, len(keys)):
                self._emit_rows(p_end, keys, results)
        self._note_latency(t0)
        self._close_fire(fire, len(keys), d2h_bytes)

    def _drain_rows(self, outs, host_part, drain, generation):
        """One fire's rows on the host: the ONE device_get + selection /
        canonical order. Returns (keys, results, d2h bytes); a ranked
        fire's select is noted on ``drain``, its window/Drain stage.
        ``generation``: the backend's table generation at the fire's
        dispatch, which its health reading is of."""
        if self._guard is None or self._guard.active:
            # ONE deadline-bounded transfer for everything (device_get is
            # idempotent: a stall-abandoned read re-runs safely)
            host = stall_bounded("transfer.d2h",
                                 # lint: sync-ok fire materialization: the one amortized d2h per pane fire
                                 lambda: jax.device_get(outs),
                                 scope="device_window")
        else:
            # lint: sync-ok degraded-mode fire materialization (host buffers, a view)
            host = jax.device_get(outs)   # degraded: host buffers, a view
        d2h_bytes = pytree_nbytes(host)
        if self._topk is not None:
            keys_k, ok, results, dropped, occ, select = host
            self._apply_health(dropped, occ, generation, drain)
            self._note_fire_select(
                drain, select, self._aggs[0].value_bits,
                results[self._aggs[0].out_name].dtype)
            sel = np.asarray(ok)
            keys = np.asarray(keys_k)[sel]
            results = {n: np.asarray(v)[sel] for n, v in results.items()}
        else:
            table, emit, results, dropped, occ = host
            self._apply_health(dropped, occ, generation, drain)
            mask = np.asarray(emit)
            idx = np.flatnonzero(mask)
            keys = np.asarray(table)[idx]
            results = {n: np.asarray(v)[idx] for n, v in results.items()}
        if host_part is not None:
            hkeys, hres = host_part
            keys = np.concatenate([keys, hkeys])
            results = {n: np.concatenate(
                [v, hres[n].astype(v.dtype, copy=False)])
                for n, v in results.items()}
            if self._topk is not None and len(keys) > self._topk:
                order = np.argsort(
                    -results[self._aggs[0].out_name],
                    kind="stable")[:self._topk]
                keys = keys[order]
                results = {n: v[order] for n, v in results.items()}
        if self._topk is None and len(keys) > 1:
            # canonical emission order: raw slot order leaks table-insert
            # history, so a restored (or degraded, or tiered) run would
            # emit the same rows in a different order than the run it
            # replaces; host-side sort on the drain stage, off the device
            # path (top-k already emits in rank order)
            order = np.argsort(keys, kind="stable")
            keys = keys[order]
            results = {n: v[order] for n, v in results.items()}
        DEVICE_STATS.note_d2h(d2h_bytes, len(keys))
        return keys, results, d2h_bytes

    def _apply_health(self, dropped, occ, generation, drain) -> None:
        """A drained fire's health scalars to the backend, which frees
        the slots of keys that hold no data any more (window/Reclaim:
        opened inside this window/Drain turn, which dispatches the
        reclaim, and closed by the backend when its counts have landed)
        or grows when the table fills. The backend passes over a reading
        of a table it has rebuilt since the fire's dispatch."""
        at = {"parent": drain.context, "seq": drain.attrs["seq"]}
        self._backend.apply_health(
            dropped, occ, generation,
            stage=lambda: TRACER.open_stage("window", "Reclaim", **at))

    def _emit_rows(self, p_end: int, keys: np.ndarray,
                   results: dict[str, np.ndarray]) -> None:
        if self._validate_batches and len(keys):
            # screen fire RESULTS too: a non-finite aggregate (however it
            # got into the plane) rides the dead-letter output, not the
            # main stream
            bad = np.zeros(len(keys), bool)
            for v in results.values():
                if np.issubdtype(np.asarray(v).dtype, np.floating):
                    bad |= ~np.isfinite(v)
            if bad.any():
                DEVICE_STATS.note_dead_letter(int(bad.sum()))
                keep = ~bad
                keys = keys[keep]
                results = {n_: v[keep] for n_, v in results.items()}
                if not len(keys):
                    return
        n = len(keys)
        start = (p_end - self._window_panes) * self._pane + self._offset
        end = p_end * self._pane + self._offset
        cols: dict[str, np.ndarray] = {self._key_column: keys}
        fields: list[tuple[str, Any]] = [(self._key_column, np.int64)]
        if self._emit_bounds:
            cols["window_start"] = np.full(n, start, np.int64)
            cols["window_end"] = np.full(n, end, np.int64)
            fields += [("window_start", np.int64), ("window_end", np.int64)]
        # emit in AggSpec declaration order — the fire program's results
        # ride a jax pytree, which canonicalizes dict keys to SORTED
        # order, so iterating `results` directly would emit columns
        # alphabetically instead of as the user declared them
        for a in self._aggs:
            vals = results[a.out_name]
            cols[a.out_name] = vals
            fields.append((a.out_name, vals.dtype.type))
        schema = Schema(fields)
        ts = np.full(n, end - 1, np.int64)
        self.output.emit(RecordBatch(schema, cols, ts))

    def finish(self) -> None:
        self._coalesce_flush()
        self._drain(block=True)
        self._refresh_late(block=True)
        if self._backend is not None:
            self._backend.note_probe_stats(block=True)
            if self._backend.tiering_active:
                self._backend.prefetch_pipeline.close()

    def _refresh_late(self, block: bool = False) -> None:
        """Sync the host cache of the device late-drop counter. Non-
        blocking by default (only reads a counter whose value has already
        landed); fire and checkpoint boundaries pass block=True. Metrics
        scrapes read the cache alone and can never stall the hot loop."""
        if self._late_dev is None:
            return
        ready = getattr(self._late_dev, "is_ready", None)
        if block or ready is None or ready():
            # lint: sync-ok boundary-amortized refresh; scrapes read the cache (ISSUE 8)
            self._late_cached = int(jax.device_get(self._late_dev))

    @property
    def late_dropped(self) -> int:
        # cached device counter: a /metrics scrape must not force a
        # device sync mid-pipeline (satellite of ISSUE 8); the cache is
        # refreshed at fire and checkpoint boundaries
        return self._late_dropped + self._late_cached

    # -- checkpointing -----------------------------------------------------
    def snapshot_state(self, checkpoint_id: int) -> dict:
        self._drain(block=True)
        self._pre_fire_flush()  # staged spill rows belong in the snapshot
        self._refresh_late(block=True)
        return {"keyed": {"backend": self._backend.snapshot(checkpoint_id),
                          "meta": self._control_meta()}}
