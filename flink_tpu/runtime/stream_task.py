"""Stream tasks: the per-subtask execution loop.

Analog of the reference's StreamTask family
(flink-streaming-java runtime/tasks/: StreamTask.java:192 invoke():821 /
processInput:588, SourceStreamTask, OneInputStreamTask) and its mailbox
(mailbox/MailboxProcessor.java:67): a single thread per subtask alternates
between the default action (process one input event) and 'mails' (checkpoint
triggers, coordinator commands) — operators never see concurrency.

Differences from the reference, by design:
* input is batch-granular; micro-batch coalescing happens at sources;
* backpressure is bounded-queue blocking (credit analog);
* processing time advances from the loop between events, keeping tests
  deterministic (a harness can inject a manual clock via OperatorContext).
"""

from __future__ import annotations

import queue
import threading
import time
import traceback
from dataclasses import dataclass, field
from typing import Any, Callable, Optional

from ..core.config import (
    CheckpointingOptions, Configuration, MetricOptions, PipelineOptions,
)
from ..core.elements import (
    MAX_WATERMARK, CheckpointBarrier, EndOfInput, LatencyMarker, Watermark,
    WatermarkStatus,
)
from ..core.records import MIN_TIMESTAMP, RecordBatch
from ..core.watermarks import WatermarkStrategy
from ..connectors.core import SinkWriter, Source, SourceReader
from ..metrics.tracing import (
    TRACER, TraceContext, now_ms, now_ns, thread_cpu_ns,
)
from ..state.backend import OperatorStateBackend
from .channels import GateEvent, InputGate
from .operators.base import OperatorChain, OperatorContext, Output
from .writer import RecordWriter

__all__ = ["StreamTask", "SourceStreamTask", "OneInputStreamTask",
           "TwoInputStreamTask", "TaskReporter", "TaskIOTimers"]


class TaskIOTimers:
    """Cumulative busy/idle/backpressured wall-clock for one subtask's
    mailbox loop (reference TaskIOMetricGroup's busyTimeMsPerSecond /
    idleTimeMsPerSecond / backPressuredTimeMsPerSecond TimerGauges, run-
    cumulative here instead of last-second-windowed). ``busy_s`` is raw
    processing time and INCLUDES time blocked inside emits; the writer
    accounts that blocked time into ``backpressured_s`` separately, so
    the derived ratios subtract it — busy means 'making progress'.

    ``cpu_s`` is what the mailbox thread COMPUTED: its CPU clock, which
    any thread may read and which costs the task nothing per batch. The
    three together: busy = inside a turn; cpu = computing; busy - cpu =
    standing still inside a turn (a device the thread waits for, the GIL
    in another thread's hands, a machine that does not run it)."""

    __slots__ = ("busy_s", "idle_s", "backpressured_s",
                 "_started_at", "_ended_at", "_cpu_clock", "_cpu_s")

    def __init__(self):
        self.busy_s = 0.0
        self.idle_s = 0.0
        self.backpressured_s = 0.0
        self._started_at: Optional[float] = None
        self._ended_at: Optional[float] = None
        self._cpu_clock: Optional[int] = None   # of the thread that started
        self._cpu_s: Optional[float] = None     # frozen at stop()

    def start(self) -> None:
        """Called by the mailbox thread itself: its CPU clock is the one
        ``cpu_s`` reads."""
        if self._started_at is None:
            self._started_at = time.time()
            if hasattr(time, "pthread_getcpuclockid"):
                self._cpu_clock = time.pthread_getcpuclockid(
                    threading.get_ident())

    def stop(self) -> None:
        # freeze elapsed at task exit so post-run gauge reads are stable
        # (and the CPU clock while its thread still is)
        if self._ended_at is None:
            self._cpu_s, self._cpu_clock = self.cpu_s, None
            self._ended_at = time.time()

    @property
    def elapsed_s(self) -> float:
        if self._started_at is None:
            return 0.0
        return max((self._ended_at or time.time()) - self._started_at,
                   1e-9)

    @property
    def cpu_s(self) -> Optional[float]:
        """CPU seconds of the mailbox thread since it started, readable
        from any thread; None where the platform has no per-thread CPU
        clock (or the task never started)."""
        clock = self._cpu_clock
        if clock is not None:
            try:
                return time.clock_gettime(clock)
            except OSError:     # the thread went between the two lines
                pass
        return self._cpu_s

    @property
    def busy_ratio(self) -> float:
        return min(1.0, max(0.0, self.busy_s - self.backpressured_s)
                   / self.elapsed_s)

    @property
    def busy_ms_per_s(self) -> float:
        return self.busy_ratio * 1000.0

    @property
    def cpu_ratio(self) -> Optional[float]:
        cpu = self.cpu_s
        return None if cpu is None else min(1.0, cpu / self.elapsed_s)

    @property
    def cpu_ms_per_s(self) -> Optional[float]:
        ratio = self.cpu_ratio
        return None if ratio is None else ratio * 1000.0

    @property
    def idle_ms_per_s(self) -> float:
        return min(1.0, self.idle_s / self.elapsed_s) * 1000.0

    @property
    def backpressured_ms_per_s(self) -> float:
        return min(1.0, self.backpressured_s / self.elapsed_s) * 1000.0


class TaskReporter:
    """Callbacks from tasks to the control plane (analog of the
    TaskExecutor->JobMaster RPC surface)."""

    def acknowledge_checkpoint(self, task_id: str, checkpoint_id: int,
                               snapshot: dict) -> None:
        pass

    def declined_checkpoint(self, task_id: str, checkpoint_id: int,
                            reason: str) -> None:
        pass

    def task_finished(self, task_id: str) -> None:
        pass

    def task_failed(self, task_id: str, error: BaseException) -> None:
        pass


class _WriterFanout(Output):
    """Chain tail output -> this task's RecordWriters. Control elements
    (watermarks, latency markers) broadcast over side-output writers too —
    downstream of a side edge still needs event time to advance."""

    def __init__(self, writers: list[RecordWriter], metrics=None,
                 side_writers: Optional[dict[str, list[RecordWriter]]] = None):
        self._writers = writers
        self._metrics = metrics
        self._side = side_writers or {}

    def _all_writers(self):
        yield from self._writers
        for ws in self._side.values():
            yield from ws

    def emit(self, batch: RecordBatch) -> None:
        if self._metrics is not None:
            self._metrics.records_out.inc(batch.n)
        for w in self._writers:
            w.emit(batch)

    def emit_watermark(self, watermark: Watermark) -> None:
        for w in self._all_writers():
            w.emit_watermark(watermark)

    def emit_latency_marker(self, marker: LatencyMarker) -> None:
        for w in self._all_writers():
            w.broadcast(marker)

    def emit_side(self, tag: str, batch: RecordBatch) -> None:
        for w in self._side.get(tag, ()):
            w.emit(batch)


def _barrier_spans(task_id: str, barrier: CheckpointBarrier,
                   align: bool = True):
    """Task-side checkpoint spans, parented on the coordinator context
    riding the barrier so the whole checkpoint forms one trace tree:
    emits the Align span (trigger → aligned at this subtask) and returns
    an open Snapshot builder the caller finishes at ack time."""
    parent = TraceContext.from_wire(barrier.trace)
    if align:
        (TRACER.span("checkpoint", "Align", parent=parent)
         .set_attribute("task", task_id)
         .set_attribute("checkpointId", barrier.checkpoint_id)
         .set_start_ts(int(barrier.timestamp * 1000))
         .finish())
    return (TRACER.span("checkpoint", "Snapshot", parent=parent)
            .set_attribute("task", task_id)
            .set_attribute("checkpointId", barrier.checkpoint_id))


class StreamTask:
    """Base: mailbox + lifecycle + checkpoint plumbing."""

    def __init__(self, task_id: str, ctx: OperatorContext,
                 writers: list[RecordWriter], reporter: TaskReporter,
                 config: Optional[Configuration] = None,
                 side_writers: Optional[dict[str, list[RecordWriter]]] = None):
        self.task_id = task_id
        self.ctx = ctx
        self.writers = writers
        self.side_writers = side_writers or {}
        self.reporter = reporter
        self.config = config or ctx.config
        self._mailbox: queue.Queue = queue.Queue()
        self._cancelled = threading.Event()
        self._thread: Optional[threading.Thread] = None
        # owning-job identity (multi-tenant attribution): every event
        # this task emits — watchdog trips, fault events, flight dumps,
        # ledger samples — is tagged with this via the thread-local
        # dispatch context pinned at thread start (_run_safely)
        self.job_name = str(self.config.get(PipelineOptions.NAME) or "")
        self.operator_state = OperatorStateBackend()
        self._last_proc_time = 0
        self.io_timers = TaskIOTimers()
        # per-subtask progress epoch (stall supervision, runtime/
        # watchdog.py): the loop bumps it once per processed event; the
        # job-level TaskStallDetector flags a stale epoch with queued
        # input and routes the task into the restart path
        from .watchdog import TaskProgress
        self.progress = TaskProgress()
        # the open task/WaitInput stage (first empty poll -> next event),
        # how many polls it has seen, and the ordinals its spans carry
        self._wait = None
        self._wait_polls = 0
        self._wait_busy_s = 0.0  # processing-time turns inside the wait
        self._waits = 0
        self._batches = 0
        metrics = getattr(ctx, "metrics", None)
        if metrics is not None and hasattr(metrics, "bind_io_timers"):
            metrics.bind_io_timers(self.io_timers)
        if metrics is not None and hasattr(metrics, "bind_progress"):
            metrics.bind_progress(self.progress)

    def _bind_gate_metrics(self, gates: list) -> None:
        metrics = getattr(self.ctx, "metrics", None)
        if metrics is not None and hasattr(metrics, "bind_input_gates"):
            metrics.bind_input_gates(gates)

    def all_writers(self):
        yield from self.writers
        for ws in self.side_writers.values():
            yield from ws

    def broadcast_all(self, element) -> None:
        for w in self.all_writers():
            w.broadcast(element)

    def make_tail_output(self) -> "_WriterFanout":
        return _WriterFanout(self.writers, self.ctx.metrics, self.side_writers)

    # -- mailbox (reference MailboxProcessor) ------------------------------
    def execute_in_mailbox(self, fn: Callable[[], None]) -> None:
        self._mailbox.put(fn)

    def _drain_mailbox(self) -> None:
        while True:
            try:
                self._mailbox.get_nowait()()
            except queue.Empty:
                return

    # -- control -----------------------------------------------------------
    def start(self) -> threading.Thread:
        # a cancelled task must unwind out of backpressured emits (failover
        # teardown toward a dead peer)
        for w in self.all_writers():
            w.cancel_event = self._cancelled
            w.io_timers = self.io_timers  # backpressured-time accounting
        self._thread = threading.Thread(target=self._run_safely,
                                        name=self.task_id, daemon=True)
        self._thread.start()
        return self._thread

    def cancel(self) -> None:
        self._cancelled.set()

    def join(self, timeout: Optional[float] = None) -> None:
        if self._thread:
            self._thread.join(timeout)

    @property
    def is_alive(self) -> bool:
        return self._thread is not None and self._thread.is_alive()

    def _run_safely(self) -> None:
        from .watchdog import PROGRESS
        from ..metrics.profiler import set_dispatch_context
        # pin the owning job for the whole task thread so watchdog/fault/
        # flight events are job-attributable even with the ledger off;
        # the operator chain narrows the operator part per dispatch
        set_dispatch_context(self.job_name, self.task_id)
        self.io_timers.start()
        self.progress.bump()  # deploy->start latency never reads as a stall
        PROGRESS.register(self.task_id, self.progress)
        try:
            self.invoke()
            self.reporter.task_finished(self.task_id)
        except BaseException as e:  # noqa: BLE001 - report everything
            if not self._cancelled.is_set():
                self.reporter.task_failed(self.task_id, e)
        finally:
            self.io_timers.stop()
            PROGRESS.unregister(self.task_id)

    def invoke(self) -> None:
        raise NotImplementedError

    def input_pending(self) -> bool:
        """Queued input this task COULD be processing right now — the
        stall detector's 'stalled, not idle' discriminator. Sources have
        no gate and are never flagged (a quiet source is idle by
        definition; its blocking sites are watchdogged individually)."""
        return False

    # -- input wait (task/WaitInput) ---------------------------------------
    def _note_empty_poll(self) -> None:
        """An input poll found nothing: the first one of a run opens the
        task/WaitInput stage, the rest only count."""
        if self._wait is None:
            self._waits += 1
            self._wait = TRACER.open_stage("task", "WaitInput",
                                           seq=self._waits)
            self._wait_polls = 0
            self._wait_busy_s = 0.0
        self._wait_polls += 1

    def _end_wait(self) -> None:
        """The next event arrived (or the input ended): close the wait;
        its measured length, less the processing-time turns the chain
        worked through inside it (already busy time), is the task's idle
        time."""
        wait = self._wait
        if wait is not None:
            self._wait = None
            wait.close(polls=self._wait_polls,
                       busy_ms=round(self._wait_busy_s * 1e3, 3))
            self.io_timers.idle_s += wait.duration_s - self._wait_busy_s

    def _process_batch_stage(self, ev: GateEvent, gate: InputGate):
        """The task/ProcessBatch stage around one dequeued batch."""
        self._batches += 1
        return TRACER.stage("task", "ProcessBatch", seq=self._batches,
                            rows=ev.value.n,
                            queued_ms=round(ev.queued_ns / 1e6, 3),
                            queue_depth=gate.queue_depth())

    # -- helpers -----------------------------------------------------------
    def _advance_processing_time(self, chain: Optional[OperatorChain]) -> None:
        """The mailbox's processing-time turn, at most once a millisecond.
        What the chain does in it (timers, completed async requests, a
        landed fire's drain and emit) is busy time, also when the turn
        falls inside an open task/WaitInput."""
        now = self.ctx.processing_time()
        if now > self._last_proc_time:
            self._last_proc_time = now
            if chain is not None:
                t0 = time.perf_counter()
                chain.advance_processing_time(now)
                spent = time.perf_counter() - t0
                self.io_timers.busy_s += spent
                if self._wait is not None:
                    self._wait_busy_s += spent


class SourceStreamTask(StreamTask):
    """Runs one source reader; checkpoints are injected here by the
    coordinator through the mailbox (reference triggerCheckpointAsync)."""

    def __init__(self, task_id: str, ctx: OperatorContext, source: Source,
                 reader: SourceReader, watermark_strategy: WatermarkStrategy,
                 chain: Optional[OperatorChain], writers: list[RecordWriter],
                 reporter: TaskReporter,
                 config: Optional[Configuration] = None):
        super().__init__(task_id, ctx, writers, reporter, config)
        self.source = source
        self.reader = reader
        self.ws = watermark_strategy
        self.chain = chain  # chained operators after the source, may be None
        self._restored_reader_state: Any = None
        # wall-clock spent per stage of the source loop (observability /
        # bench breakdown): read = generator/IO, emit = chain + backpressure
        self.stage_s: dict[str, float] = {"read": 0.0, "emit": 0.0}
        # watermark-alignment + admission-control observability
        self.alignment_pauses = 0
        self.alignment_max_overshoot_ms = 0
        # multi-tenant admission gate observability (cluster/isolation.py)
        self.sched_pauses = 0      # 1ms quota waits at the gate
        self.sched_sheds = 0       # micro-batches quarantined by overload
        self.current_batch_size = 0
        from collections import deque
        self.batch_size_history: deque = deque(maxlen=1024)
        # register in the alignment group at DEPLOY time with MIN, so no
        # group-mate can run ahead during the start-up window before this
        # source's first own report (all tasks are constructed before any
        # is started)
        align = getattr(reporter, "watermark_alignment", None)
        if (align is not None and watermark_strategy is not None
                and watermark_strategy.alignment_group):
            align.report(watermark_strategy.alignment_group, task_id,
                         MIN_TIMESTAMP,
                         watermark_strategy.alignment_max_drift_ms)

    def restore_state(self, snapshot: Optional[dict]) -> None:
        if not snapshot:
            return
        if snapshot.get("reader") is not None:
            self._restored_reader_state = snapshot["reader"]
        if self.chain is not None and snapshot.get("chain"):
            self.chain.initialize_state(snapshot["chain"])

    def _snapshot(self, barrier: CheckpointBarrier) -> None:
        sb = _barrier_spans(self.task_id, barrier, align=False)
        # ① emit barrier downstream first (source is the barrier origin)
        self.broadcast_all(barrier)
        # ② snapshot reader position + chained operators
        snap = {"reader": self.reader.snapshot(),
                "chain": (self.chain.snapshot_state(barrier.checkpoint_id)
                          if self.chain else None)}
        self.reporter.acknowledge_checkpoint(
            self.task_id, barrier.checkpoint_id, snap)
        sb.finish()

    def trigger_checkpoint(self, barrier: CheckpointBarrier) -> None:
        self.execute_in_mailbox(lambda: self._snapshot(barrier))

    def _admission_gate(self, out: Output) -> str:
        """Per-job micro-batch admission (cluster/isolation.py).

        Polls ``ISOLATION.try_admit`` before each read. ``"retry"``
        waits ~1ms per poll with the mailbox live and the wait counted
        as backpressure (the alignment-pause idiom); a shed verdict
        reads the batch anyway and quarantines it to the dead-letter
        side output under a typed ``OverloadShedError`` — counted and
        flight-recorded against THIS job only, never surfaced as a task
        failure (shedding is the bulkhead working, not the job dying).
        Returns ``"admitted"``, ``"shed"`` (caller continues its loop),
        or ``"stop"`` (cancelled / reader exhausted mid-shed)."""
        from ..cluster.isolation import ISOLATION, OverloadShedError
        from ..metrics.tracing import record_flight_event
        from .faults import FAULTS

        job = self.job_name
        waited = 0.0
        ISOLATION.note_waiting(job, +1)
        try:
            while True:
                # chaos sites: a sched.admit trip fails/hangs the gate
                # itself; a sched.shed trip forces a shed without overload
                FAULTS.fire("sched.admit")
                verdict = ("shed:injected" if FAULTS.check("sched.shed")
                           else ISOLATION.try_admit(job, waited))
                if verdict == "admit":
                    if waited > 0.0:
                        # throttle wait is attributed device-side so the
                        # ledger's per-job view shows quota pressure
                        from ..metrics.profiler import DEVICE_LEDGER
                        DEVICE_LEDGER.record(
                            "sched.throttle", waited * 1e3, job=job,
                            operator=self.task_id, kind="dispatch")
                        if TRACER.enabled:
                            end = now_ms()
                            (TRACER.span("sched", "Admit")
                             .set_attribute("job", job)
                             .set_attribute("task", self.task_id)
                             .set_attribute("waited_ms",
                                            round(waited * 1e3, 3))
                             .set_start_ts(end - int(waited * 1e3))
                             .finish(end))
                    return "admitted"
                if verdict == "retry":
                    if self._cancelled.is_set():
                        return "stop"
                    self.sched_pauses += 1
                    time.sleep(0.001)  # gated: mailbox stays live below
                    waited += 0.001
                    # quota-paused counts as backpressured, not idle: a
                    # competing tenant's consumption is what we wait on
                    self.io_timers.backpressured_s += 0.001
                    self._drain_mailbox()
                    self._advance_processing_time(self.chain)
                    continue
                # shed:* — quarantine the next batch to dead-letter
                reason = verdict.partition(":")[2] or "gate-timeout"
                batch = self.reader.read_batch(self.current_batch_size)
                if batch is None:
                    return "stop"
                if not batch.n:
                    time.sleep(0.001)  # nothing to shed; no tight spin
                    self.io_timers.idle_s += 0.001
                    return "shed"
                err = OverloadShedError(job, reason, waited)
                ISOLATION.note_shed(job, batch.n, reason)
                from ..metrics.device import DEVICE_STATS
                DEVICE_STATS.note_dead_letter(batch.n)
                # side-emitted when a dead-letter edge is wired on this
                # vertex; otherwise the counters + flight event are the
                # record (device_window._dead_letter semantics)
                try:
                    out.emit_side("dead-letter", batch)
                except NotImplementedError:
                    pass
                record_flight_event(
                    "overload-shed", job=job, task=self.task_id,
                    reason=reason, records=batch.n, error=repr(err))
                if TRACER.enabled:
                    (TRACER.span("sched", "Shed")
                     .set_attribute("job", job)
                     .set_attribute("task", self.task_id)
                     .set_attribute("reason", reason)
                     .set_attribute("records", batch.n)
                     .finish())
                self.sched_sheds += 1
                self.progress.bump()  # shedding IS progress, not a stall
                return "shed"
        finally:
            ISOLATION.note_waiting(job, -1)

    def invoke(self) -> None:
        from ..cluster.isolation import ISOLATION
        batch_size = self.config.get(PipelineOptions.BATCH_SIZE)
        wm_interval = self.config.get(PipelineOptions.AUTO_WATERMARK_INTERVAL)
        latency_interval = self.config.get(MetricOptions.LATENCY_INTERVAL)
        last_marker_emit = 0.0
        idle_timeout = self.ws.idle_timeout
        if self._restored_reader_state is not None:
            self.reader.restore(self._restored_reader_state)
        gen = self.ws.create_generator()
        out: Output = self.make_tail_output()
        if self.chain is not None:
            self.chain.open()
        last_wm_emit = 0.0
        last_wm = MIN_TIMESTAMP
        last_data_time = time.time()
        idle = False

        # watermark alignment (reference SourceCoordinator announceCombined-
        # Watermark): sources in the strategy's group pause when ahead of
        # group-min + drift; idle sources report MAX and don't hold it back
        align = getattr(self.reporter, "watermark_alignment", None)
        align_group = self.ws.alignment_group if align is not None else None
        align_drift = self.ws.alignment_max_drift_ms
        from .alignment import MAX_WATERMARK as _ALIGN_MAX

        # admission control (reference BufferDebloater): batch size tracks
        # throughput x target-latency so in-flight bytes stay bounded
        adaptive = self.config.get(PipelineOptions.ADAPTIVE_BATCH)
        if adaptive:
            target_s = self.config.get(PipelineOptions.ADAPTIVE_TARGET_LATENCY)
            min_batch = self.config.get(PipelineOptions.ADAPTIVE_MIN_BATCH)
            max_batch = self.config.get(PipelineOptions.ADAPTIVE_MAX_BATCH)
        self.current_batch_size = batch_size

        while not self._cancelled.is_set():
            self._drain_mailbox()
            if align_group is not None:
                cur = gen.current_watermark()
                allowed = align.report(align_group, self.task_id,
                                       _ALIGN_MAX if idle else cur,
                                       align_drift)
                if not idle and cur > allowed:
                    self.alignment_pauses += 1
                    if allowed - align_drift > MIN_TIMESTAMP:
                        # overshoot is only meaningful once the group min
                        # reflects a real report, not deploy-time MIN
                        self.alignment_max_overshoot_ms = max(
                            self.alignment_max_overshoot_ms, cur - allowed)
                    time.sleep(0.001)  # paused: mailbox stays live above
                    # paused-by-group counts as backpressured, not idle:
                    # downstream consumption is what the pause waits on
                    self.io_timers.backpressured_s += 0.001
                    # pausing stops READING only — processing-time timers
                    # in the chained operators must keep firing
                    self._advance_processing_time(self.chain)
                    continue
            # multi-tenant admission gate (cluster/isolation.py): under
            # contention this job spends one quota credit per micro-batch;
            # sustained overload or an open breaker sheds instead
            if ISOLATION.enabled:
                verdict = self._admission_gate(out)
                if verdict == "stop":
                    break
                if verdict == "shed":
                    continue
            # a read is a task/SourceBatch stage only once it returns rows:
            # an unbounded or paced source is asked about 1 kHz while it
            # has nothing, and stages never run per poll
            read_ns, read_cpu_ns = now_ns(), thread_cpu_ns()
            batch = self.reader.read_batch(self.current_batch_size)
            read_dt = (now_ns() - read_ns) / 1e9
            self.stage_s["read"] += read_dt
            self.io_timers.busy_s += read_dt
            if batch is None:  # exhausted (bounded)
                break
            if batch.n:
                cycle = TRACER.stage("task", "SourceBatch",
                                     start_ns=read_ns,
                                     start_cpu_ns=read_cpu_ns,
                                     seq=self._batches + 1, records=batch.n,
                                     read_ms=round(read_dt * 1e3, 3))
                # what the writers stand in a full channel they account
                # themselves (RecordWriter._put_blocking)
                blocked_s = self.io_timers.backpressured_s
                if self.ctx.metrics is not None:
                    self.ctx.metrics.records_in.inc(batch.n)
                batch = self.ws.assign_timestamps(batch)
                gen.on_batch(batch)
                last_data_time = time.time()
                if idle:
                    idle = False
                    self.broadcast_all(WatermarkStatus(True))
                if self.chain is not None:
                    self.chain.process_batch(batch)
                else:
                    out.emit(batch)
                self._batches += 1
                # read and emit are the span's own two stamps
                end_ns = now_ns()
                emit_dt = (end_ns - read_ns) / 1e9 - read_dt
                blocked_s = self.io_timers.backpressured_s - blocked_s
                cycle.close(end_ns, emit_ms=round(emit_dt * 1e3, 3),
                            blocked_ms=round(blocked_s * 1e3, 3))
                self.stage_s["emit"] += emit_dt
                self.io_timers.busy_s += emit_dt
                self.progress.bump()
                if adaptive:
                    # desired = throughput x target; EMA toward it. At the
                    # fixpoint one batch takes exactly target seconds.
                    tput = batch.n / max(read_dt + emit_dt, 1e-9)
                    desired = tput * target_s
                    self.current_batch_size = int(min(max(
                        0.5 * self.current_batch_size + 0.5 * desired,
                        min_batch), max_batch))
                    self.batch_size_history.append(self.current_batch_size)
            else:
                time.sleep(0.001)  # unbounded source, nothing available
                self.io_timers.idle_s += 0.001
                if (idle_timeout is not None and not idle
                        and time.time() - last_data_time > idle_timeout):
                    idle = True
                    self.broadcast_all(WatermarkStatus(False))
            now = time.time()
            if now - last_wm_emit >= wm_interval:
                last_wm_emit = now
                wm = gen.current_watermark()
                if wm > last_wm and not idle:
                    last_wm = wm
                    if self.chain is not None:
                        self.chain.process_watermark(Watermark(wm))
                    else:
                        out.emit_watermark(Watermark(wm))
            if (latency_interval > 0
                    and now - last_marker_emit >= latency_interval):
                # end-to-end latency probe (reference latencyTrackingInterval
                # in StreamSource): rides the chain so every operator
                # records source->here latency before forwarding
                last_marker_emit = now
                marker = LatencyMarker(now, self.task_id,
                                       self.ctx.subtask_index)
                if self.chain is not None:
                    self.chain.process_latency_marker(marker)
                else:
                    out.emit_latency_marker(marker)
            self._advance_processing_time(self.chain)

        if align_group is not None:
            # finished/cancelled source must not hold its group back
            align.unregister(align_group, self.task_id)
        if not self._cancelled.is_set():
            self._drain_mailbox()
            # bounded source done: flush event time, finish chain, close edges
            final_wm = MAX_WATERMARK
            if self.chain is not None:
                self.chain.process_watermark(final_wm)
                self.chain.finish()
                self.chain.close()
            else:
                out.emit_watermark(final_wm)
            self.broadcast_all(EndOfInput())
        self.reader.close()


class TwoInputStreamTask(StreamTask):
    """Two gates -> two-input head operator chain -> writers (reference
    TwoInputStreamTask + StreamTwoInputProcessor). Each gate aligns barriers
    over its own channels; the task snapshot fires only once BOTH gates have
    delivered the barrier for the same checkpoint (the two-gate alignment of
    SingleCheckpointBarrierHandler), holding back the already-aligned gate."""

    def __init__(self, task_id: str, ctx: OperatorContext, gate1: InputGate,
                 gate2: InputGate, chain: OperatorChain,
                 writers: list[RecordWriter], reporter: TaskReporter,
                 config: Optional[Configuration] = None):
        super().__init__(task_id, ctx, writers, reporter, config)
        self.gates = [gate1, gate2]
        self.chain = chain
        self._bind_gate_metrics(self.gates)
        self._gate_barrier: list = [None, None]
        self._unaligned_pending = None
        self._restored_inflight: list[list] = [[], []]

    def restore_state(self, snapshot: Optional[dict]) -> None:
        if not snapshot:
            return
        if snapshot.get("chain"):
            self.chain.initialize_state(snapshot["chain"])
        self._restored_inflight = [list(snapshot.get("inflight1", ())),
                                   list(snapshot.get("inflight2", ()))]

    def _complete_barrier(self, barrier: CheckpointBarrier) -> None:
        sb = _barrier_spans(self.task_id, barrier)
        self._gate_barrier = [None, None]
        self.broadcast_all(barrier)
        snap = {"chain": self.chain.snapshot_state(barrier.checkpoint_id)}
        self.reporter.acknowledge_checkpoint(
            self.task_id, barrier.checkpoint_id, snap)
        sb.finish()

    def _on_barrier(self, gi: int, barrier: CheckpointBarrier) -> None:
        if self.gates[gi].capture_active:
            # unaligned: barrier overtook on gate gi — snapshot now, start
            # capturing the sibling gate too, ack when both drained
            if self._unaligned_pending is not None:
                old_b, _ = self._unaligned_pending
                self._unaligned_pending = None
                self.reporter.declined_checkpoint(
                    self.task_id, old_b.checkpoint_id,
                    "overtaken by a newer unaligned checkpoint")
            self.broadcast_all(barrier)
            snap = {"chain": self.chain.snapshot_state(barrier.checkpoint_id)}
            self.gates[1 - gi].begin_capture(barrier)
            self._unaligned_pending = (barrier, snap)
            self._maybe_finish_unaligned()
            return
        self._gate_barrier[gi] = barrier
        self._maybe_complete_barrier()

    def _maybe_finish_unaligned(self) -> None:
        if self._unaligned_pending is None:
            return
        if not all(g.capture_complete for g in self.gates):
            return
        barrier, snap = self._unaligned_pending
        self._unaligned_pending = None
        snap["inflight1"] = self.gates[0].take_captured()
        snap["inflight2"] = self.gates[1].take_captured()
        self.reporter.acknowledge_checkpoint(
            self.task_id, barrier.checkpoint_id, snap)

    def _maybe_complete_barrier(self) -> None:
        b0, b1 = self._gate_barrier
        # an exhausted input never delivers barriers: don't wait on it
        if b0 is not None and b1 is None and self.gates[1].all_ended():
            b1 = b0
        if b1 is not None and b0 is None and self.gates[0].all_ended():
            b0 = b1
        if b0 is None or b1 is None:
            return  # hold the aligned gate (skipped in the poll loop)
        if b0.checkpoint_id != b1.checkpoint_id:
            # a newer checkpoint overtook on one side: adopt the newer one
            newer = max(b0, b1, key=lambda b: b.checkpoint_id)
            held = self._gate_barrier
            self._gate_barrier = [None, None]
            for g in (0, 1):
                if held[g] is newer:
                    self._gate_barrier[g] = newer
            return
        self._complete_barrier(b0)

    def invoke(self) -> None:
        self.chain.open()
        for gi in (0, 1):
            for b in self._restored_inflight[gi]:
                self.chain.process_batch_n(gi, b)
        self._restored_inflight = [[], []]
        rr = 0
        while not self._cancelled.is_set():
            self._drain_mailbox()
            self._maybe_finish_unaligned()
            if any(b is not None for b in self._gate_barrier):
                # the other input may have ended while a barrier was held
                self._maybe_complete_barrier()
            ev = gi = None
            for off in range(2):
                g = (rr + off) % 2
                if self._gate_barrier[g] is not None:
                    continue  # aligned, waiting for the other gate
                ev = self.gates[g].poll()
                if ev is not None:
                    gi = g
                    rr = 1 - g
                    break
            if ev is None:
                if all(g.all_ended() for g in self.gates):
                    break
                self._note_empty_poll()
                self._advance_processing_time(self.chain)
                time.sleep(0.0005)
                continue
            self._end_wait()
            if ev.kind == "batch":
                if self.ctx.metrics is not None:
                    self.ctx.metrics.records_in.inc(ev.value.n)
                with self._process_batch_stage(ev, self.gates[gi]) as turn:
                    self.chain.process_batch_n(gi, ev.value)
                self.io_timers.busy_s += turn.duration_s
            else:
                t0 = time.perf_counter()
                if ev.kind == "watermark":
                    self.chain.process_watermark_n(gi, ev.value)
                elif ev.kind == "barrier":
                    self._on_barrier(gi, ev.value)
                elif ev.kind == "latency":
                    self.chain.process_latency_marker(ev.value)
                elif ev.kind == "idle":
                    self.broadcast_all(ev.value)
                self.io_timers.busy_s += time.perf_counter() - t0
            self.progress.bump()
            self._advance_processing_time(self.chain)

        self._end_wait()
        if not self._cancelled.is_set():
            self._maybe_finish_unaligned()
            self.chain.finish()
            self.chain.close()
            self.broadcast_all(EndOfInput())

    def input_pending(self) -> bool:
        return any(ch.size() > 0 for g in self.gates for ch in g.channels)


class OneInputStreamTask(StreamTask):
    """Gate -> operator chain -> writers (reference OneInputStreamTask)."""

    def __init__(self, task_id: str, ctx: OperatorContext, gate: InputGate,
                 chain: OperatorChain, writers: list[RecordWriter],
                 reporter: TaskReporter,
                 config: Optional[Configuration] = None):
        super().__init__(task_id, ctx, writers, reporter, config)
        self.gate = gate
        self.chain = chain
        self._bind_gate_metrics([gate])
        self._restored_inflight: list = []
        self._unaligned_pending = None  # (barrier, snapshot) awaiting capture

    def restore_state(self, snapshot: Optional[dict]) -> None:
        if not snapshot:
            return
        if snapshot.get("chain"):
            self.chain.initialize_state(snapshot["chain"])
        # unaligned checkpoint: in-flight pre-barrier batches replay first
        self._restored_inflight = list(snapshot.get("inflight", ()))

    def _on_barrier(self, barrier: CheckpointBarrier) -> None:
        """Broadcast downstream first, then snapshot (reference
        SubtaskCheckpointCoordinatorImpl.checkpointState). Aligned: ack
        immediately. Unaligned (barrier overtook): the state snapshot is
        taken NOW but the ack waits until the other channels' pre-barrier
        in-flight data has been captured (reference ChannelStateWriter
        completing the channel state future)."""
        if self._unaligned_pending is not None:
            # a newer checkpoint overtook before capture finished: the older
            # one can no longer complete on this task
            old_b, _ = self._unaligned_pending
            self._unaligned_pending = None
            self.reporter.declined_checkpoint(
                self.task_id, old_b.checkpoint_id,
                "overtaken by a newer unaligned checkpoint")
        sb = _barrier_spans(self.task_id, barrier)
        self.broadcast_all(barrier)
        snap = {"chain": self.chain.snapshot_state(barrier.checkpoint_id)}
        if self.gate.capture_active and not self.gate.capture_complete:
            self._unaligned_pending = (barrier, snap)
            sb.set_attribute("unaligned", True).finish()
            return
        if self.gate.capture_active:  # capture already complete (1 channel)
            snap["inflight"] = self.gate.take_captured()
        self.reporter.acknowledge_checkpoint(
            self.task_id, barrier.checkpoint_id, snap)
        sb.finish()

    def _maybe_finish_unaligned(self) -> None:
        if self._unaligned_pending is None:
            return
        if not self.gate.capture_complete:
            return
        barrier, snap = self._unaligned_pending
        self._unaligned_pending = None
        snap["inflight"] = self.gate.take_captured()
        self.reporter.acknowledge_checkpoint(
            self.task_id, barrier.checkpoint_id, snap)

    def invoke(self) -> None:
        self.chain.open()
        for batch in self._restored_inflight:
            # replayed in-flight data precedes any new input
            self.chain.process_batch(batch)
        self._restored_inflight = []
        while not self._cancelled.is_set():
            self._drain_mailbox()
            ev = self.gate.poll()
            if ev is None:
                self._maybe_finish_unaligned()
                if self.gate.all_ended():
                    break
                self._note_empty_poll()
                self._advance_processing_time(self.chain)
                time.sleep(0.0005)
                continue
            self._end_wait()
            if ev.kind == "batch":
                if self.ctx.metrics is not None:
                    self.ctx.metrics.records_in.inc(ev.value.n)
                with self._process_batch_stage(ev, self.gate) as turn:
                    self.chain.process_batch(ev.value)
                self.io_timers.busy_s += turn.duration_s
            else:
                t0 = time.perf_counter()
                if ev.kind == "watermark":
                    self.chain.process_watermark(ev.value)
                elif ev.kind == "barrier":
                    self._on_barrier(ev.value)
                elif ev.kind == "latency":
                    # through the chain, not past it: every operator
                    # records its source->here latency before forwarding
                    self.chain.process_latency_marker(ev.value)
                elif ev.kind == "idle":
                    self.broadcast_all(ev.value)
                self.io_timers.busy_s += time.perf_counter() - t0
            self.progress.bump()
            self._maybe_finish_unaligned()
            self._advance_processing_time(self.chain)

        self._end_wait()
        if not self._cancelled.is_set():
            self._maybe_finish_unaligned()
            self.chain.finish()
            self.chain.close()
            self.broadcast_all(EndOfInput())

    def input_pending(self) -> bool:
        return any(ch.size() > 0 for ch in self.gate.channels)
