"""Stall watchdog: deadline-bounded blocking operations + task-progress
supervision.

PR 2 made device *failures* survivable; this module makes *hangs*
survivable — a hung XLA execute, host<->device transfer, checkpoint
write, or control-plane send can no longer freeze a mailbox loop forever
with zero signal (the reference's liveness story: heartbeat + checkpoint
timeouts; SURVEY L3/L4 control plane treats liveness as a first-class
recovery input).

Two mechanisms:

* **Deadline-bounded calls** (``WATCHDOG.run`` / ``stall_bounded``):
  every blocking site — ``device.compile``, ``device.execute``,
  ``transfer.h2d/d2h``, ``checkpoint.write/load``, ``rpc.send`` —
  runs on a supervised worker thread with a per-site
  configurable deadline (``watchdog.*`` config keys). Expiry abandons
  the worker and raises a typed :class:`StallError` to the caller, which
  feeds the PR-2 degradation ladder: a stall is transient (backoff-
  retry), repeated stalls at one site are persistent (state evacuation +
  synchronous fallback pin under ``DeviceGuard``, task failover elsewhere).
  Exactly-once is preserved because abandoned workers never execute the
  real operation after an injected hang (the hang sleep checks the
  abandonment flag), and the non-guarded wrapped regions are idempotent
  (pure uploads/materializations) so in-place retries are safe.

* **Task-progress supervision** (``TaskProgress`` +
  ``TaskStallDetector``): every mailbox loop bumps a per-subtask
  progress epoch; a job-level detector (started by ``run_job``, the
  ``JobSupervisor``, and each ``DistributedHost`` attempt) flags any
  subtask whose epoch has not advanced within ``task.stall-timeout``
  while its input gates hold queued data, and routes it into the
  existing failure->region-restart path by failing the task with a
  ``StallError``. This is the backstop for hangs the per-site deadlines
  cannot see (a wedged operator, an unwrapped third-party call).

Determinism: ``FaultInjector`` rules accept a ``!hang@MS`` flag — a
tripped hang rule *sleeps* MS milliseconds at the site instead of
raising, so every stall path is testable with tiny delays and replays
byte-identically by seed (same visit-order guarantee as every other
fault mode).
"""

from __future__ import annotations

import threading
import time
from typing import Callable, Optional

__all__ = ["StallError", "Watchdog", "WATCHDOG", "stall_bounded",
           "TaskProgress", "TaskStallDetector", "current_call_abandoned"]


class StallError(RuntimeError):
    """A supervised operation exceeded its deadline (or a task's progress
    epoch stalled). Transient for the degradation ladder: retry first,
    escalate on repetition."""

    def __init__(self, site: str, deadline_s: float,
                 scope: Optional[str] = None):
        where = f"{site}[{scope}]" if scope else site
        super().__init__(
            f"operation at {where} stalled past its "
            f"{deadline_s:.3g}s deadline")
        self.site = site
        self.deadline_s = deadline_s
        self.scope = scope


#: Thread-local marker for the watchdog worker running the current call,
#: consulted by the fault injector's hang sleep so an abandoned worker
#: never executes the real operation after its injected hang ends.
_TLS = threading.local()


def current_call_abandoned() -> bool:
    call = getattr(_TLS, "call", None)
    return call is not None and call.abandoned


class _Call:
    """One supervised invocation: result/exception slot + abandon flag."""

    __slots__ = ("fn", "done", "result", "exc", "abandoned")

    def __init__(self, fn: Callable):
        self.fn = fn
        self.done = threading.Event()
        self.result = None
        self.exc: Optional[BaseException] = None
        self.abandoned = False

    def execute(self) -> None:
        _TLS.call = self
        try:
            self.result = self.fn()
        except BaseException as e:  # noqa: BLE001 - relayed to the caller
            self.exc = e
        finally:
            _TLS.call = None
            self.done.set()


class Watchdog:
    """Per-site deadline supervisor. One instance per process
    (``WATCHDOG``), configured from the job ``Configuration`` by the
    deploy paths exactly like ``FAULTS``."""

    #: site -> the WatchdogOptions attribute its deadline reads from
    _SITE_OPTIONS = {
        "device.compile": "COMPILE_TIMEOUT",
        "device.execute": "EXECUTE_TIMEOUT",
        "transfer.h2d": "TRANSFER_TIMEOUT",
        "transfer.d2h": "TRANSFER_TIMEOUT",
        "checkpoint.write": "CHECKPOINT_TIMEOUT",
        "checkpoint.load": "CHECKPOINT_TIMEOUT",
        "rpc.send": "RPC_TIMEOUT",
        "tier.evict": "TIER_TIMEOUT",
        "tier.prefetch": "TIER_TIMEOUT",
        "aot.warmup": "AOT_WARMUP_TIMEOUT",  # lint: key-ok watchdog site label, not a config key
    }

    #: sites whose deadline reads from NetworkOptions instead (net.*
    #: keys live beside the other networking options; note the inverted
    #: zero convention — net.reconnect-timeout=0 DISABLES reconnection
    #: rather than unbounding it, enforced by the transport itself)
    _NET_SITE_OPTIONS = {
        "net.reconnect": "RECONNECT_TIMEOUT",
    }

    def __init__(self):
        self._lock = threading.Lock()
        self.enabled = True
        self.deadlines: dict[str, float] = self._default_deadlines()
        self.stall_retries = 1
        self.trips: dict[str, int] = {}
        #: bounded stall-event log, merged into REST
        #: ``/jobs/<id>/exceptions`` (the JobExceptionsHandler analog for
        #: stalls that never reach a task failure — e.g. a stall absorbed
        #: by retry or by the degradation ladder)
        self.events: list[dict] = []

    @staticmethod
    def _default_deadlines() -> dict[str, float]:
        from ..core.config import NetworkOptions, WatchdogOptions

        out = {site: getattr(WatchdogOptions, attr).default
               for site, attr in Watchdog._SITE_OPTIONS.items()}
        out.update({site: getattr(NetworkOptions, attr).default
                    for site, attr in Watchdog._NET_SITE_OPTIONS.items()})
        return out

    # -- configuration ---------------------------------------------------
    def configure(self, config) -> None:
        """Adopt ``watchdog.*`` (and the ``net.reconnect`` site's
        ``net.*``) keys from a job Configuration."""
        from ..core.config import NetworkOptions, WatchdogOptions

        with self._lock:
            self.enabled = bool(config.get(WatchdogOptions.ENABLED))
            self.stall_retries = int(
                config.get(WatchdogOptions.STALL_RETRIES))
            for site, attr in self._SITE_OPTIONS.items():
                self.deadlines[site] = float(
                    config.get(getattr(WatchdogOptions, attr)))
            for site, attr in self._NET_SITE_OPTIONS.items():
                self.deadlines[site] = float(
                    config.get(getattr(NetworkOptions, attr)))

    def reset(self) -> None:
        """Back to defaults and clear trip accounting (test isolation)."""
        with self._lock:
            self.enabled = True
            self.deadlines = self._default_deadlines()
            self.stall_retries = 1
            self.trips.clear()
            self.events.clear()

    def deadline_for(self, site: str) -> float:
        return self.deadlines.get(site, 0.0)

    def trips_total(self) -> int:
        with self._lock:
            return sum(self.trips.values())

    # -- the supervised call ---------------------------------------------
    def run(self, site: str, fn: Callable, deadline: Optional[float] = None,
            scope: Optional[str] = None,
            on_stall: Optional[Callable] = None):
        """Run ``fn`` under ``site``'s deadline on a supervised worker;
        raise :class:`StallError` on expiry. Disabled watchdog or a
        zero/negative deadline calls through directly (no worker thread,
        no supervision)."""
        d = self.deadline_for(site) if deadline is None else deadline
        if not self.enabled or d is None or d <= 0:
            return fn()
        # the supervised worker is a fresh thread: re-pin the caller's
        # (job, operator) dispatch context so device-time ledger samples
        # recorded inside fn keep their attribution across the hop
        from ..metrics.profiler import dispatch_context, set_dispatch_context
        job, operator = dispatch_context()
        if job or operator:
            inner = fn

            def fn():
                set_dispatch_context(job, operator)
                return inner()

        call = _Call(fn)
        worker = threading.Thread(target=call.execute,
                                  name=f"watchdog:{site}", daemon=True)
        worker.start()
        if call.done.wait(d):
            if call.exc is not None:
                raise call.exc
            return call.result
        call.abandoned = True
        self._note_trip(site, scope, d)
        if on_stall is not None:
            try:
                on_stall()
            except Exception:  # noqa: BLE001 - best-effort cleanup hook
                pass
        raise StallError(site, d, scope)

    def note_stall(self, site: str, deadline: float,
                   scope: Optional[str] = None) -> StallError:
        """Record a deadline expiry observed by a caller that runs its
        own bounded retry loop instead of a supervised worker (the
        transport's reconnect path owns the socket lifecycle, so it
        cannot run under ``run``): counts the trip into the same
        events/metrics surface and returns the typed error for the
        caller to raise."""
        self._note_trip(site, scope, deadline)
        return StallError(site, deadline, scope)

    def _note_trip(self, site: str, scope: Optional[str],
                   deadline: float) -> None:
        # the owning job (thread-local dispatch context, pinned at task-
        # thread start): multi-job stall events/dumps must be attributable
        # to ONE tenant's failure domain
        from ..metrics.profiler import dispatch_context
        job = dispatch_context()[0]
        with self._lock:
            self.trips[site] = self.trips.get(site, 0) + 1
            if len(self.events) < 1024:
                self.events.append({
                    "timestamp": time.time(), "kind": "watchdog-stall",
                    "site": site, "scope": scope, "job": job,
                    "deadline_s": deadline})
        from ..metrics.device import DEVICE_STATS
        DEVICE_STATS.note_watchdog_trip(site)
        # the post-mortem moment: the stall span lands in the flight
        # recorder's ring FIRST, then the dump snapshots the ring — the
        # dump's tail always contains the stall site that triggered it
        from ..metrics.tracing import TRACER, dump_flight_recorder
        (TRACER.span("watchdog", "Stall")
         .set_attribute("site", site)
         .set_attribute("scope", scope)
         .set_attribute("job", job)
         .set_attribute("deadline_s", deadline)
         .finish())
        dump_flight_recorder("stall", site=site, scope=scope, job=job,
                             deadline_s=deadline)


#: The process-global watchdog every wrapped site consults.
#: ``deploy_local`` / ``DistributedHost.deploy`` / bench configure it
#: from the job Configuration.
WATCHDOG = Watchdog()


def stall_bounded(site: str, fn: Callable, scope: Optional[str] = None,
                  deadline: Optional[float] = None,
                  retries: Optional[int] = None):
    """The shared idiom for watchdogging an IDEMPOTENT blocking region
    (uploads, materializations, checkpoint writes): visit ``site``'s
    fault rule (raising trips keep their transient-retry semantics; hang
    trips sleep on the supervised worker) and run ``fn`` under the
    site's deadline. A stall abandons the worker and retries in place up
    to ``watchdog.stall-retries`` times — retrying is safe precisely
    because the region is idempotent — then propagates ``StallError``
    into task failover. Compiled-segment dispatches use ``DeviceGuard``
    (which owns its own retry/degrade ladder) instead of this helper."""
    from .faults import FAULTS, fire_with_retries

    def _body():
        if FAULTS.enabled:
            fire_with_retries(site, scope=scope)
        return fn()

    max_retries = WATCHDOG.stall_retries if retries is None else retries
    attempt = 0
    while True:
        try:
            return WATCHDOG.run(site, _body, deadline=deadline, scope=scope)
        except StallError:
            if attempt >= max_retries:
                raise
            attempt += 1
            from ..metrics.device import DEVICE_STATS
            DEVICE_STATS.note_retry(scope or site)


# ---------------------------------------------------------------------------
# task-progress supervision
# ---------------------------------------------------------------------------

class TaskProgress:
    """Per-subtask progress epoch: the mailbox loop bumps it once per
    processed event/batch; age is wall-clock since the last bump. Cheap
    enough for the hot loop (one int increment + one clock read)."""

    __slots__ = ("epoch", "last_ts")

    def __init__(self):
        self.epoch = 0
        self.last_ts = time.time()

    def bump(self) -> None:
        self.epoch += 1
        self.last_ts = time.time()

    @property
    def age_ms(self) -> float:
        return (time.time() - self.last_ts) * 1000.0


class _ProgressRegistry:
    """Process-global task_id -> TaskProgress view, feeding the per-task
    ``last_progress_age_ms`` surface (REST /metrics/snapshot, bench)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._tasks: dict[str, TaskProgress] = {}

    def register(self, task_id: str, progress: TaskProgress) -> None:
        with self._lock:
            self._tasks[task_id] = progress

    def unregister(self, task_id: str) -> None:
        with self._lock:
            self._tasks.pop(task_id, None)

    def ages_ms(self) -> dict[str, float]:
        with self._lock:
            items = list(self._tasks.items())
        return {tid: round(p.age_ms, 1) for tid, p in items}


PROGRESS = _ProgressRegistry()


class TaskStallDetector:
    """Job-level stall detector: flags any subtask whose progress epoch
    has not advanced within ``task.stall-timeout`` while its input gates
    are non-empty, and routes it into the existing restart path by
    failing the task with a ``StallError`` (the local supervisor then
    performs a region restart or full restart-from-checkpoint; a
    distributed worker's failure report reaches the coordinator's
    redeploy logic — both exactly as for any other task failure)."""

    def __init__(self, job, stall_timeout: float,
                 interval: Optional[float] = None):
        self.job = job
        self.stall_timeout = stall_timeout
        self.interval = interval or max(stall_timeout / 4.0, 0.01)
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._last_epoch: dict[str, tuple[int, float]] = {}
        self.detections = 0

    def start(self) -> "TaskStallDetector":
        if self.stall_timeout and self.stall_timeout > 0:
            self._thread = threading.Thread(
                target=self._loop, name="task-stall-detector", daemon=True)
            self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()

    def _loop(self) -> None:
        while not self._stop.wait(self.interval):
            if self.job._done.is_set():
                return
            self.scan()

    def scan(self) -> list[str]:
        """One detection pass; returns the task ids flagged (tests drive
        this directly for determinism)."""
        now = time.time()
        flagged = []
        for task_id, task in list(self.job.tasks.items()):
            progress = getattr(task, "progress", None)
            if progress is None or not task.is_alive:
                self._last_epoch.pop(task_id, None)
                continue
            epoch = progress.epoch
            seen, since = self._last_epoch.get(task_id, (None, now))
            if epoch != seen:
                self._last_epoch[task_id] = (epoch, now)
                continue
            if now - since < self.stall_timeout:
                continue
            if not task.input_pending():
                # no queued input: idle, not stalled (a source waiting on
                # data, a task whose upstream is quiet)
                continue
            self._last_epoch[task_id] = (epoch, now)  # re-arm, don't spam
            flagged.append(task_id)
            self._flag(task_id, task, now - since)
        return flagged

    def _flag(self, task_id: str, task, age_s: float) -> None:
        self.detections += 1
        from ..metrics.device import DEVICE_STATS
        DEVICE_STATS.note_stall(task_id)
        err = StallError("task.progress", self.stall_timeout, scope=task_id)
        history = getattr(self.job, "failure_history", None)
        if history is not None:
            history.append({
                "timestamp": time.time(), "task": task_id,
                "kind": "stall-detected",
                "error": (f"no progress for {age_s:.3g}s with queued "
                          f"input (task.stall-timeout="
                          f"{self.stall_timeout:.3g}s)")})
        # cancel FIRST: when the wedged thread eventually unwinds it must
        # not report a second failure for the already-failed attempt
        task.cancel()
        self.job.task_failed(task_id, err)
