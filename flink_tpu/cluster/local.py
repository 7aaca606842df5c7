"""Local deployment: run a JobGraph as threads in one process.

MiniCluster analog (flink-runtime minicluster/MiniCluster.java:153): real
channels, real barrier alignment, real state backends — multi-subtask
semantics without a cluster. Also the execution engine behind
``env.execute()`` locally (reference LocalExecutor), and the substrate the
failover/cluster layer drives (cluster/scheduler.py restarts these tasks).
"""

from __future__ import annotations

import threading
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Optional

from ..core.config import CheckpointingOptions, Configuration, PipelineOptions
from ..graph.stream_graph import JobGraph, JobVertex
from ..runtime.channels import InputGate, LocalChannel
from ..runtime.operators.base import OperatorChain, OperatorContext, Output
from ..runtime.stream_task import (
    OneInputStreamTask, SourceStreamTask, StreamTask, TaskReporter,
    TwoInputStreamTask,
)
from ..runtime.writer import RecordWriter

__all__ = ["LocalJob", "deploy_local", "run_job"]


@dataclass
class _Deployment:
    """Wiring for one execution attempt."""

    tasks: dict[str, StreamTask] = field(default_factory=dict)
    source_tasks: dict[str, SourceStreamTask] = field(default_factory=dict)


class LocalJob(TaskReporter):
    """One running local job: tasks + reporter + (optional) checkpoint hook."""

    def __init__(self, job_graph: JobGraph, config: Configuration):
        self.job_graph = job_graph
        self.config = config
        self.tasks: dict[str, StreamTask] = {}
        self.source_tasks: dict[str, SourceStreamTask] = {}
        self._finished: set[str] = set()
        self._failed: list[tuple[str, BaseException]] = []
        # a cancelled job's tasks unwind cleanly through task_finished;
        # this flag is how callers tell cancellation from real completion
        self.cancelled = False
        self._lock = threading.Lock()
        self._done = threading.Event()
        self.checkpoint_listener: Optional[Callable] = None  # coordinator hook
        self.metrics_registry = None
        from ..state.queryable import KvStateRegistry
        self.kv_registry = KvStateRegistry()
        from ..runtime.alignment import WatermarkAlignmentCoordinator
        self.watermark_alignment = WatermarkAlignmentCoordinator()
        # bounded per-job failure history (the FailureHandlingResult
        # analog, reference ExceptionHistoryEntry): every task failure,
        # degradation, and restart decision lands here; REST exposes it
        # at /jobs/<name>/exceptions. The supervisor shares ONE deque
        # across restart attempts so history survives redeploys.
        from collections import deque
        self.failure_history: deque = deque(maxlen=64)
        # per-attempt Execution records (reference ExecutionGraph's
        # Execution/ExecutionAttemptID): every deployment of a task id
        # appends one attempt with its state transitions
        self.executions: dict[str, list[dict]] = {}

    # -- execution-attempt tracking ----------------------------------------
    def _exec_new(self, task_id: str) -> None:
        with self._lock:
            attempts = self.executions.setdefault(task_id, [])
            attempts.append({"attempt": len(attempts) + 1,
                             "state": "DEPLOYING", "start": time.time(),
                             "end": None, "failure": None})

    def _exec_set(self, task_id: str, state: str,
                  failure: Optional[str] = None) -> None:
        attempts = self.executions.get(task_id)
        if not attempts:
            return
        rec = attempts[-1]
        if rec["state"] in ("FINISHED", "FAILED", "CANCELED"):
            return                      # terminal states never regress
        rec["state"] = state
        if state in ("FINISHED", "FAILED", "CANCELED"):
            rec["end"] = time.time()
        if failure is not None:
            rec["failure"] = failure

    # -- TaskReporter ------------------------------------------------------
    def acknowledge_checkpoint(self, task_id: str, checkpoint_id: int,
                               snapshot: dict) -> None:
        if self.checkpoint_listener is not None:
            self.checkpoint_listener("ack", task_id, checkpoint_id, snapshot)

    def declined_checkpoint(self, task_id: str, checkpoint_id: int,
                            reason: str) -> None:
        if self.checkpoint_listener is not None:
            self.checkpoint_listener("decline", task_id, checkpoint_id, reason)

    def task_finished(self, task_id: str) -> None:
        with self._lock:
            self._exec_set(task_id,
                           "CANCELED" if self.cancelled else "FINISHED")
            self._finished.add(task_id)
            if len(self._finished) == len(self.tasks):
                self._done.set()

    def task_failed(self, task_id: str, error: BaseException) -> None:
        with self._lock:
            self._exec_set(task_id, "FAILED", failure=repr(error))
            self._failed.append((task_id, error))
            self.failure_history.append({
                "timestamp": time.time(), "task": task_id,
                "job": self.job_graph.name, "kind": "task-failure",
                "error": f"{type(error).__name__}: {error}"})
            self._done.set()
        # feed the owning job's circuit breaker — a task failure is one
        # consecutive-failure step toward its bulkhead shedding instead
        # of restarting forever (cluster/isolation.py)
        from .isolation import ISOLATION
        ISOLATION.note_failure(self.job_graph.name)

    # -- control -----------------------------------------------------------
    def start(self) -> None:
        if not self.tasks:
            # a host can legitimately hold zero subtasks (slot-weighted
            # placement, parallelism < host count): it is trivially done
            self._done.set()
            return
        for tid, t in self.tasks.items():
            t.start()
            with self._lock:
                self._exec_set(tid, "RUNNING")

    def cancel(self) -> None:
        self.cancelled = True
        for t in self.tasks.values():
            t.cancel()
        self._done.set()

    def wait_event(self, timeout: Optional[float] = None) -> bool:
        """Wait for completion OR failure WITHOUT cancelling — the
        supervisor uses this to attempt a region-scoped restart before
        giving up on the whole job."""
        return self._done.wait(timeout)

    def current_failures(self) -> list:
        with self._lock:
            return list(self._failed)

    def wait(self, timeout: Optional[float] = None) -> None:
        if not self._done.wait(timeout):
            self.cancel()
            raise TimeoutError(f"Job did not finish within {timeout}s")
        if self._failed:
            task_id, err = self._failed[0]
            self.cancel()
            raise RuntimeError(f"Task {task_id} failed: {err!r}") from err

    @property
    def failed(self) -> bool:
        return bool(self._failed)


def deploy_local(job_graph: JobGraph, config: Configuration,
                 restored_state: Optional[dict] = None,
                 metrics_registry=None) -> LocalJob:
    """Instantiate channels, gates, writers, chains, and tasks for every
    (vertex, subtask) — the Execution.deploy analog
    (flink-runtime executiongraph/Execution.java:511)."""
    job = LocalJob(job_graph, config)
    job.metrics_registry = metrics_registry
    # arm (or disarm) the process-global fault injector from THIS job's
    # config — idempotent on an unchanged spec, so failover redeploys
    # keep their visit counters (a once@N fault must not re-arm) — and
    # the stall watchdog's per-site deadlines
    from ..runtime.faults import FAULTS
    from ..runtime.watchdog import WATCHDOG
    FAULTS.configure(config)
    WATCHDOG.configure(config)
    # job-wide causal tracing is on by default: the global tracer picks up
    # traces.* limits from this job's config and the compile cache reports
    # device spans into the same trace trees
    from ..metrics.device import set_compile_tracer
    from ..metrics.tracing import TRACER
    TRACER.configure(config)
    set_compile_tracer(TRACER if TRACER.enabled else None)
    # the mesh runtime (axis rules + live-rescale policy) is process-global
    # for the same reason the fault injector is: sharded programs compiled
    # by ANY task must agree on the partition rules
    from ..parallel.plan import MESH_RUNTIME
    MESH_RUNTIME.configure(config)
    # device-time ledger: per-program dispatch profiling + recompile
    # attribution (off by default — profiler.enabled)
    from ..metrics.profiler import DEVICE_LEDGER
    DEVICE_LEDGER.configure(config)
    # multi-tenant isolation: per-job admission quotas + bulkheads are
    # process-global for the same reason — every job sharing the device
    # pool must meter against the same scheduler (off by default)
    from .isolation import ISOLATION
    ISOLATION.configure(config)
    ISOLATION.register_job(job_graph.name)
    # persistent AOT executable cache: warm-start this process's program
    # caches (watchdog-bounded aot.warmup) before the first batch, so a
    # restart/replacement pays zero compile storm (off by default)
    from ..runtime.aot import AOT
    AOT.configure(config)
    AOT.warmup()
    if metrics_registry is not None:
        # process-global compile/transfer accounting surfaces through the
        # same registry the reporters/REST endpoint scrape
        from ..metrics.device import bind_device_metrics
        from ..metrics.profiler import bind_ledger_metrics
        bind_device_metrics(metrics_registry)
        bind_ledger_metrics(metrics_registry)

    # channels[edge_key][src_sub][dst_sub]; feedback channels are UNBOUNDED:
    # a bounded back edge would wedge the body forever once the head exits
    # on quiescence (nothing drains a dead loop), and a live loop blocking
    # on its own output is the classic iteration deadlock the reference
    # documents — growth under a slow head is the accepted tradeoff
    channels: dict[int, list[list[LocalChannel]]] = {}
    for ei, e in enumerate(job_graph.edges):
        src = job_graph.vertices[e.source_vertex]
        dst = job_graph.vertices[e.target_vertex]
        channels[ei] = [
            [LocalChannel(0) if e.feedback else LocalChannel()  # 0=unbounded
             for _ in range(dst.parallelism)]
            for _ in range(src.parallelism)]

    aligned = config.get(CheckpointingOptions.MODE) == "exactly-once"
    unaligned = config.get(CheckpointingOptions.UNALIGNED)
    alignment_timeout = config.get(CheckpointingOptions.ALIGNMENT_TIMEOUT)

    has_feedback = any(e.feedback for e in job_graph.edges)
    if has_feedback and config.get(CheckpointingOptions.INTERVAL) > 0:
        # a barrier circulating a feedback loop would re-align the head
        # forever; the reference's iterations likewise exclude loop state
        # from exactly-once guarantees — reject loudly instead of hanging
        raise ValueError(
            "iterations (feedback edges) cannot run with periodic "
            "checkpointing enabled; disable execution.checkpointing."
            "interval for this job")

    _deploy_vertices(job, job_graph, config, channels, restored_state,
                     metrics_registry, set(job_graph.vertices))
    return job


def restart_region(job: "LocalJob", job_graph: JobGraph,
                   config: Configuration, vids: set,
                   restored_state: Optional[dict] = None) -> list[str]:
    """Pipelined-region failover (reference
    RestartPipelinedRegionFailoverStrategy.java:110): tear down and
    rebuild ONLY the tasks of the given region's vertices inside a live
    job — regions share no channels, so the rest of the job keeps
    running untouched. Returns the restarted task ids."""
    affected = [tid for tid in list(job.tasks)
                if tid.rsplit("#", 1)[0] in vids]
    from ..metrics.tracing import TRACER, dump_flight_recorder
    restart_sb = (TRACER.span("restart", "RegionRestart")
                  .set_attribute("job", job_graph.name)
                  .set_attribute("vertices", sorted(vids))
                  .set_attribute("tasks", len(affected)))
    dump_flight_recorder("region-restart", job=job_graph.name,
                         vertices=sorted(vids), tasks=affected)
    old = []
    for tid in affected:
        t = job.tasks.pop(tid)
        job.source_tasks.pop(tid, None)
        t.cancel()
        with job._lock:
            # region teardown cancels the healthy region-mates of the
            # failed task; their attempt ends CANCELED, not FINISHED
            job._exec_set(tid, "CANCELED")
        old.append(t)
    for t in old:
        # the old attempt must fully unwind BEFORE the new one deploys:
        # its unwind path reports task_finished, which would otherwise
        # mark the restarted task id as already finished
        t.join(10)
    # fresh channels for the region's (internal) edges
    channels: dict[int, list[list[LocalChannel]]] = {}
    for ei, e in enumerate(job_graph.edges):
        if e.source_vertex not in vids:
            continue
        src = job_graph.vertices[e.source_vertex]
        dst = job_graph.vertices[e.target_vertex]
        channels[ei] = [
            [LocalChannel(0) if e.feedback else LocalChannel()
             for _ in range(dst.parallelism)]
            for _ in range(src.parallelism)]
    _deploy_vertices(job, job_graph, config, channels, restored_state,
                     job.metrics_registry, vids)
    with job._lock:
        job._failed = [(tid, err) for tid, err in job._failed
                       if tid.rsplit("#", 1)[0] not in vids]
        # the cancelled attempt's tasks unwound through task_finished;
        # their ids must count again for the NEW attempt
        job._finished -= set(affected)
        job._done.clear()
        if job._failed:
            # a DIFFERENT region failed during this restart window: its
            # wake-up signal must survive the clear
            job._done.set()
    for tid in affected:
        job.tasks[tid].start()
        with job._lock:
            job._exec_set(tid, "RUNNING")
    restart_sb.finish()
    return affected


def live_rescale(job: "LocalJob", n_devices: int,
                 timeout: Optional[float] = None) -> dict:
    """Coordinator-driven live rescale: change every mesh operator's
    worker set (device count) inside a RUNNING job, barrier-aligned and
    exactly-once, without a restart.

    Protocol (the elastic counterpart of restart_region): stage the new
    device count on every mesh operator (request_rescale), then trigger
    ONE aligned checkpoint — each operator applies the staged change on
    its mailbox thread at its snapshot point, where every buffered row is
    folded and every in-flight fire drained, so the barrier that makes
    the checkpoint consistent is the same event that makes the worker-set
    switch consistent. State moves via the checkpoint page format
    (digest-verified; see parallel/rescale.py). Returns the merged migration
    stats ({keygroups_migrated, bytes_moved, epoch, ...} summed/maxed
    over operators).
    """
    from ..metrics.tracing import TRACER
    from ..parallel.plan import MESH_RUNTIME
    if not MESH_RUNTIME.rescale_enabled:
        raise RuntimeError(
            "live rescale is disabled (mesh.rescale.enabled=false)")
    if timeout is None:
        timeout = MESH_RUNTIME.rescale_timeout_ms / 1000.0
    targets = []
    for tid in list(job.tasks):
        chain = getattr(job.tasks[tid], "chain", None)
        for op in (chain.operators if chain is not None else ()):
            if hasattr(op, "request_rescale"):
                targets.append((tid, op))
    if not targets:
        raise ValueError("live_rescale: job has no mesh operators")
    sb = (TRACER.span("rescale", "Rescale")
          .set_attribute("job", job.job_graph.name)
          .set_attribute("operators", len(targets))
          .set_attribute("new_devices", int(n_devices)))
    try:
        # rescale-up warm start: programs for the NEW mesh shape compile
        # on the first post-switch batch unless their executables are
        # already warm — re-scan the persistent AOT cache (artifacts a
        # prior run at the target scale stored) before the barrier
        from ..runtime.aot import AOT
        if AOT.enabled:
            AOT.warmup()
        old_epochs = {tid: op._rescale_epoch for tid, op in targets}
        for _, op in targets:
            op.request_rescale(n_devices)
        coordinator = getattr(job, "coordinator", None)
        ephemeral = None
        if coordinator is None:
            # no periodic checkpointing on this job: stand up a one-shot
            # coordinator purely to circulate the alignment barrier
            from ..checkpoint.coordinator import CheckpointCoordinator
            ephemeral = coordinator = CheckpointCoordinator(
                job, job.config, tracer=TRACER if TRACER.enabled else None)
        try:
            pending = coordinator.trigger_checkpoint()
            if not pending.done.wait(timeout):
                raise TimeoutError(
                    f"live rescale to {n_devices} devices timed out after "
                    f"{timeout:.1f}s (mesh.rescale.timeout) waiting for the "
                    f"alignment barrier")
            if pending.completed is None:
                raise RuntimeError(
                    f"live rescale checkpoint {pending.checkpoint_id} was "
                    f"declined; worker set unchanged")
        finally:
            if ephemeral is not None:
                job.checkpoint_listener = None
        stale = [tid for tid, op in targets
                 if op._rescale_epoch <= old_epochs[tid]]
        if stale:
            raise RuntimeError(
                f"live rescale barrier completed but operators {stale} did "
                f"not bump their mesh epoch")
        merged = {"new_devices": int(n_devices), "operators": len(targets),
                  "keygroups_migrated": 0, "bytes_moved": 0,
                  "duration_ms": 0.0, "epoch": 0}
        for _, op in targets:
            st = op._last_rescale_stats or {}
            merged["keygroups_migrated"] += st.get("keygroups_migrated", 0)
            merged["bytes_moved"] += st.get("bytes_moved", 0)
            merged["duration_ms"] = max(merged["duration_ms"],
                                        st.get("duration_ms", 0.0))
            merged["epoch"] = max(merged["epoch"], st.get("epoch", 0))
        sb.set_attribute("keygroups_migrated", merged["keygroups_migrated"])
        sb.set_attribute("bytes_moved", merged["bytes_moved"])
        sb.set_attribute("epoch", merged["epoch"])
        return merged
    except BaseException as e:
        sb.set_attribute("error", repr(e))
        raise
    finally:
        sb.finish()


def _deploy_vertices(job: "LocalJob", job_graph: JobGraph,
                     config: Configuration, channels: dict,
                     restored_state: Optional[dict],
                     metrics_registry, vids: set) -> None:
    from ..metrics.core import TaskMetrics

    aligned = config.get(CheckpointingOptions.MODE) == "exactly-once"
    unaligned = config.get(CheckpointingOptions.UNALIGNED)
    alignment_timeout = config.get(CheckpointingOptions.ALIGNMENT_TIMEOUT)

    for vid, vertex in job_graph.vertices.items():
        if vid not in vids:
            continue
        out_edges = [(ei, e) for ei, e in enumerate(job_graph.edges)
                     if e.source_vertex == vid]
        in_edges = [(ei, e) for ei, e in enumerate(job_graph.edges)
                    if e.target_vertex == vid]
        for sub in range(vertex.parallelism):
            task_id = f"{vid}#{sub}"
            metrics = (TaskMetrics(metrics_registry, job_graph.name, vid, sub)
                       if metrics_registry is not None else None)
            ctx = OperatorContext(
                task_name=vertex.name, subtask_index=sub,
                parallelism=vertex.parallelism,
                max_parallelism=vertex.max_parallelism,
                config=config, metrics=metrics, operator_id=vertex.id,
                kv_registry=job.kv_registry)

            # writers: one per (non-side) out edge; side writers by tag;
            # feedback edges get the filtering writer (records only).
            # Backpressure waits are capped (task.backpressure.stall-
            # timeout) so a stuck-but-alive downstream peer raises
            # StallError into the supervisor instead of wedging the task
            from ..core.config import WatchdogOptions
            from ..runtime.writer import FeedbackRecordWriter
            bp_stall = float(config.get(
                WatchdogOptions.BACKPRESSURE_STALL_TIMEOUT))
            writers, side_writers = [], {}
            for ei, e in out_edges:
                cls = FeedbackRecordWriter if e.feedback else RecordWriter
                w = cls([channels[ei][sub][d]
                         for d in range(len(channels[ei][sub]))],
                        e.partitioner_factory(), sub,
                        stall_timeout=bp_stall)
                if e.side_tag is None:
                    writers.append(w)
                else:
                    side_writers.setdefault(e.side_tag, []).append(w)

            snapshot = (restored_state or {}).get(task_id)

            if vertex.kind == "source":
                src_node = vertex.chained_nodes[0]
                chain_ops = [n.operator_factory()
                             for n in vertex.chained_nodes[1:]]
                reader = _make_reader(src_node, sub, vertex.parallelism)
                # certified fused-chain lowering: the fusion certificate
                # (graph/fusion.py) proved this vertex's source→window
                # prefix collapses to one dispatch — arm both ends. Runtime
                # gates (deferred overflow on the operator, a timestamp
                # column on the reader) can still decline, in which case
                # the chain runs exactly as before.
                cert = getattr(job_graph, "certificate", None)
                rep = (cert.chain_for_vertex(vid)
                       if cert is not None else None)
                if (rep is not None and rep.lowered_prefix and chain_ops
                        and hasattr(reader, "enable_fused")
                        and hasattr(chain_ops[0], "enable_fused_chain")
                        and chain_ops[0].enable_fused_chain(
                            src_node.source, sub, vertex.parallelism)):
                    if not reader.enable_fused():
                        chain_ops[0]._fused_spec = None
                task = SourceStreamTask(
                    task_id, ctx, src_node.source, reader,
                    src_node.watermark_strategy,
                    None, writers, job, config)
                task.side_writers = side_writers
                if chain_ops:
                    task.chain = OperatorChain(
                        chain_ops, ctx, task.make_tail_output(),
                        side_outputs=_side_outputs_map(side_writers, metrics))
                if snapshot:
                    task.restore_state(snapshot)
                job.source_tasks[task_id] = task
            elif vertex.kind == "two_input":
                # one gate per logical input (reference TwoInputStreamTask)
                per_input: list[list] = [[], []]
                for ei, e in in_edges:
                    for s in range(len(channels[ei])):
                        per_input[e.target_input].append(channels[ei][s][sub])
                ops = [n.operator_factory() for n in vertex.chained_nodes]
                task = TwoInputStreamTask.__new__(TwoInputStreamTask)
                StreamTask.__init__(task, task_id, ctx, writers, job, config,
                                    side_writers=side_writers)
                task.gates = [
                    InputGate(per_input[0], aligned=aligned,
                              unaligned=unaligned and aligned,
                              alignment_timeout_s=alignment_timeout),
                    InputGate(per_input[1], aligned=aligned,
                              unaligned=unaligned and aligned,
                              alignment_timeout_s=alignment_timeout)]
                task._gate_barrier = [None, None]
                task._unaligned_pending = None
                task._restored_inflight = [[], []]
                task.chain = OperatorChain(
                    ops, ctx, task.make_tail_output(),
                    side_outputs=_side_outputs_map(side_writers, metrics))
                if snapshot:
                    task.restore_state(snapshot)
            else:
                # input gate over all in-edges' channels for this subtask
                in_channels, feedback_idx = [], set()
                for ei, e in in_edges:
                    for s in range(len(channels[ei])):
                        if e.feedback:
                            feedback_idx.add(len(in_channels))
                        in_channels.append(channels[ei][s][sub])
                head_node = vertex.chained_nodes[0]
                if getattr(head_node, "iteration_head", False):
                    from ..runtime.channels import IterationGate
                    gate = IterationGate(
                        in_channels, feedback_idx,
                        head_node.iteration_wait_s, aligned=aligned)
                else:
                    gate = InputGate(in_channels, aligned=aligned,
                                     unaligned=unaligned and aligned,
                                     alignment_timeout_s=alignment_timeout)
                ops = [n.operator_factory() for n in vertex.chained_nodes]
                task = OneInputStreamTask.__new__(OneInputStreamTask)
                StreamTask.__init__(task, task_id, ctx, writers, job, config,
                                    side_writers=side_writers)
                task.gate = gate
                task._restored_inflight = []
                task._unaligned_pending = None
                task.chain = OperatorChain(
                    ops, ctx, task.make_tail_output(),
                    side_outputs=_side_outputs_map(side_writers, metrics))
                if snapshot:
                    task.restore_state(snapshot)
            job.tasks[task_id] = task
            job._exec_new(task_id)


def _side_outputs_map(side_writers, metrics) -> Optional[dict[str, Output]]:
    if not side_writers:
        return None
    from ..runtime.stream_task import _WriterFanout
    return {tag: _WriterFanout(ws, metrics) for tag, ws in side_writers.items()}


def _make_reader(src_node, subtask: int, parallelism: int):
    source = src_node.source
    splits = source.create_splits(parallelism)
    reader = source.create_reader(splits[subtask])
    reader._parallelism = parallelism
    return reader


def run_job(job_graph: JobGraph, config: Configuration,
            timeout: Optional[float] = 120.0,
            metrics_registry=None,
            restored_state: Optional[dict] = None) -> LocalJob:
    """Deploy, optionally attach periodic checkpointing, run to completion."""
    job = deploy_local(job_graph, config, restored_state=restored_state,
                       metrics_registry=metrics_registry)
    coordinator = None
    interval = config.get(CheckpointingOptions.INTERVAL)
    if interval and interval > 0:
        from ..checkpoint.coordinator import CheckpointCoordinator
        from ..metrics.tracing import TRACER
        coordinator = CheckpointCoordinator(
            job, config, tracer=TRACER if TRACER.enabled else None)
        coordinator.start_periodic()
    job.coordinator = coordinator
    # task-progress supervision: without a supervisor there is no restart
    # path, but a stalled subtask still FAILS the job with a typed
    # StallError instead of blocking job.wait until its timeout with
    # zero signal
    from ..core.config import WatchdogOptions
    from ..runtime.watchdog import TaskStallDetector
    detector = TaskStallDetector(
        job, float(config.get(WatchdogOptions.TASK_STALL_TIMEOUT))).start()
    job.start()
    try:
        job.wait(timeout)
    finally:
        detector.stop()
        if coordinator is not None:
            coordinator.stop()
    return job
