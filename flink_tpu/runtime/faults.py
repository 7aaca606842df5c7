"""Deterministic fault injection + the device-path retry/degrade guard.

The chaos substrate for the whole stack (the injectable analog of the
reference's process-kill ITCases, SURVEY §5.3, generalized from "kill the
JVM" to "fail THIS site on THIS visit"): a process-wide registry of named
fault sites threaded through the device operators, the transfer points,
channels, the sink, checkpoint storage, and the cluster heartbeat. Every
site is seeded and schedulable through ``Configuration`` keys
(``faults.enabled`` / ``faults.seed`` / ``faults.spec``), so a chaos run
replays byte-identically: same seed + same spec + same visit order =>
the same trips, down to the visit number recorded in each event.

Sites (see docs/ROBUSTNESS.md for where each is threaded):

    device.compile    building a compiled program (XLA compile)
    device.execute    dispatching a compiled segment (step/fire/fold)
    transfer.h2d      host->device upload of a batch/column
    transfer.d2h      device->host materialization (fires, snapshots)
    channel.send      writing into a downstream channel
    channel.backpressure  drop-style: a put reports "queue full" once
    checkpoint.write  persisting a completed checkpoint
    checkpoint.load   reading a checkpoint back for restore
    checkpoint.corrupt   mutation-style: bit-flip a stored chunk file
    checkpoint.truncate  mutation-style: truncate a stored chunk file
    rpc.heartbeat     drop-style: a worker heartbeat frame is lost
    rpc.send          a worker<->coordinator control frame send
    sink.invoke       delivering a batch to a sink function/writer
    net.connect       establishing (or re-establishing) a data-plane
                      TCP connection — a trip is one failed attempt,
                      absorbed by the reconnect loop's deadline
    net.sever         drop-style: kill the established socket out from
                      under a data-plane send (simulated TCP RST)
    net.delay         drop-style: data-plane send latency — use !hang@MS
                      (a trip without the hang flag is a no-op)
    net.zombie        drop-style: suppress a worker's heartbeats AND its
                      control-reconnect reflex while tasks and data keep
                      flowing (the partitioned-but-alive split-brain)
    sched.admit       the per-job admission gate sources poll before
                      reading a micro-batch (cluster/isolation.py)
    sched.shed        drop-style: force the admission gate to shed the
                      next micro-batch to the dead-letter output even
                      without real overload
    coord.crash       drop-style: the LEADER coordinator crashes — drops
                      its server plus every worker control socket and
                      stops renewing its lease, so a standby can steal
                      leadership and take the running job over
    ha.lease          a leader-lease renew or steal attempt fails (or,
                      with !hang@MS, stalls — the GC-pause analog that
                      lets the lease expire under a live leader)
    aot.load          reading a persisted AOT executable artifact back
                      (warm-start scan); a !poison trip corrupt-mutates
                      the read bytes so digest verification — not luck —
                      must catch it (the checkpoint.corrupt analog)
    aot.store         persisting a freshly-compiled executable; a trip
                      skips persistence (compile-on-miss next process),
                      a !poison trip commits a corrupt-mutated artifact
                      for the verified load path to quarantine

Every rule also accepts a ``!hang@MS`` flag: the trip SLEEPS MS
milliseconds at the site instead of raising — the deterministic stand-in
for a wedged call, surfaced by the stall watchdog's per-site deadline
(runtime/watchdog.py) rather than by an exception.

A ``!job@NAME`` flag scopes a rule to one tenant: it only trips when the
thread-local dispatch context (metrics/profiler.py) attributes the visit
to job NAME, and it counts visits per ``site!job@NAME`` stream — the
multi-job isolation drill poisons or hangs job A's dispatches without
touching job B's. A site may carry several comma-separated rules (e.g.
one per job); unfiltered single-rule specs behave exactly as before.

``DeviceGuard`` is the reflex around every compiled-segment call:
transient failures retry with exponential backoff (reusing the
cluster/failover.py strategy math); persistent failures surface as
``DeviceSegmentError`` so the operator can evacuate state and degrade to
its synchronous fallback path, and data-poison faults skip retry entirely (the
same batch cannot stop being poisoned) so the operator quarantines the
batch to a dead-letter output instead of folding it into state.
"""

from __future__ import annotations

import random
import threading
import time
from dataclasses import dataclass
from typing import Callable, Optional

__all__ = ["FAULT_SITES", "InjectedFault", "HangAbandoned",
           "DeviceSegmentError", "FaultInjector", "FAULTS",
           "fire_with_retries", "DeviceGuard"]

#: Every site the runtime threads. ``configure`` rejects unknown sites so a
#: typo in a chaos spec fails loudly instead of silently injecting nothing.
FAULT_SITES = (
    "device.compile", "device.execute",
    "transfer.h2d", "transfer.d2h",
    "channel.send", "channel.backpressure",
    "checkpoint.write", "checkpoint.load",
    "checkpoint.corrupt", "checkpoint.truncate",
    "rpc.heartbeat", "rpc.send", "sink.invoke",
    "tier.evict", "tier.prefetch",
    "net.connect", "net.sever", "net.delay", "net.zombie",
    "sched.admit", "sched.shed",
    "coord.crash", "ha.lease",
    "aot.load", "aot.store",
)


class InjectedFault(RuntimeError):
    """Raised (or reported, for drop-style sites) by a tripped fault rule.
    ``hang_ms > 0`` marks a hang fault: the site SLEEPS instead of
    raising (the deterministic stand-in for a wedged device call — the
    stall watchdog's deadline, not this exception, is what surfaces)."""

    def __init__(self, site: str, visit: int, transient: bool = True,
                 poison: bool = False, hang_ms: int = 0):
        super().__init__(
            f"injected fault at {site} (visit {visit}, "
            f"{'transient' if transient else 'persistent'}"
            f"{', poison' if poison else ''}"
            f"{f', hang {hang_ms}ms' if hang_ms else ''})")
        self.site = site
        self.visit = visit
        self.transient = transient
        self.poison = poison
        self.hang_ms = hang_ms


class HangAbandoned(RuntimeError):
    """An injected hang outlived its watchdog deadline: the caller was
    already handed a StallError, so the abandoned worker unwinds through
    this WITHOUT executing the real operation (exactly-once: nothing the
    caller will retry can also run to completion here)."""


class DeviceSegmentError(RuntimeError):
    """A compiled-segment call failed beyond what retries can absorb.
    ``poison`` marks a data fault (quarantine the batch); otherwise the
    operator should degrade to its synchronous fallback path or fail over."""

    def __init__(self, scope: str, cause: BaseException,
                 poison: bool = False):
        super().__init__(f"device segment {scope!r} failed: {cause}")
        self.scope = scope
        self.cause = cause
        self.poison = poison


@dataclass
class FaultRule:
    """One parsed ``site=mode[!flags]`` entry of ``faults.spec``."""

    site: str
    mode: str            # "once" | "every" | "prob" | "always" | "off"
    at: int = 1          # once: trip ON this visit; every: period
    p: float = 0.0       # prob mode: per-visit trip probability
    transient: bool = True
    poison: bool = False
    hang_ms: int = 0     # >0: the trip SLEEPS this long instead of raising
    job: str = ""        # non-empty: only trips for this dispatch-context job

    @staticmethod
    def parse(entry: str) -> "FaultRule":
        entry = entry.strip()
        if "=" not in entry:
            raise ValueError(f"fault rule {entry!r}: expected 'site=mode'")
        site, _, mode = entry.partition("=")
        site = site.strip()
        if site not in FAULT_SITES:
            raise ValueError(f"unknown fault site {site!r} "
                             f"(known: {', '.join(FAULT_SITES)})")
        parts = mode.strip().split("!")
        mode, flags = parts[0].strip(), {f.strip() for f in parts[1:]}
        hang_ms = 0
        job = ""
        for f in list(flags):
            if f.startswith("hang@"):
                flags.discard(f)
                hang_ms = int(f[5:])
                if hang_ms < 1:
                    raise ValueError(
                        f"fault rule {entry!r}: hang@MS needs MS>=1")
            elif f.startswith("job@"):
                flags.discard(f)
                job = f[4:]
                if not job:
                    raise ValueError(
                        f"fault rule {entry!r}: job@NAME needs a name")
        bad = flags - {"persistent", "transient", "poison"}
        if bad:
            raise ValueError(f"fault rule {entry!r}: unknown flags {bad}")
        rule = FaultRule(site, "off",
                         transient="persistent" not in flags,
                         poison="poison" in flags, hang_ms=hang_ms,
                         job=job)
        if mode in ("off", ""):
            rule.mode = "off"
        elif mode == "always":
            rule.mode = "always"
        elif mode.startswith("once"):
            rule.mode = "once"
            rule.at = int(mode[5:]) if mode.startswith("once@") else 1
        elif mode.startswith("every@"):
            rule.mode = "every"
            rule.at = int(mode[6:])
            if rule.at < 1:
                raise ValueError(f"fault rule {entry!r}: every@N needs N>=1")
        elif mode.startswith("p"):
            rule.mode = "prob"
            rule.p = float(mode[1:])
            if not 0.0 <= rule.p <= 1.0:
                raise ValueError(f"fault rule {entry!r}: p out of [0,1]")
        else:
            raise ValueError(f"fault rule {entry!r}: unknown mode {mode!r}")
        return rule


class FaultInjector:
    """Process-wide registry of schedulable fault sites.

    Disabled (the default) every check is one attribute read. Enabled,
    each visit to a site increments a per-site counter under a lock and
    evaluates that site's rule; probability rules draw from a per-site
    ``random.Random((seed, site))`` stream, so determinism needs only the
    visit ORDER to be stable — which single-threaded mailbox loops give
    per subtask, and tests give globally.
    """

    def __init__(self):
        self._lock = threading.Lock()
        self.enabled = False
        self.seed = 0
        self._rules: dict[str, list[FaultRule]] = {}
        self._visits: dict[str, int] = {}
        self._trips: dict[str, int] = {}
        self._rngs: dict[str, random.Random] = {}
        self._fingerprint: Optional[tuple] = None
        self._suppress = 0  # >0: sites never trip (degrade/evacuate paths)
        self.events: list[dict] = []  # bounded trip log (site, visit)

    # -- configuration ---------------------------------------------------
    def configure(self, config) -> None:
        """Adopt ``faults.*`` keys from a job Configuration. Idempotent on
        an unchanged (enabled, seed, spec) fingerprint so failover
        redeploys of the SAME job keep their visit counters — a once@N
        fault must not re-arm on every restart attempt."""
        from ..core.config import FaultOptions

        enabled = bool(config.get(FaultOptions.ENABLED))
        seed = int(config.get(FaultOptions.SEED))
        spec = str(config.get(FaultOptions.SPEC) or "")
        fingerprint = (enabled, seed, spec)
        with self._lock:
            if fingerprint == self._fingerprint:
                return
        self.configure_spec(spec, seed=seed, enabled=enabled)
        with self._lock:
            self._fingerprint = fingerprint

    def configure_spec(self, spec: str, seed: int = 0,
                       enabled: bool = True) -> None:
        rules: dict[str, list[FaultRule]] = {}
        for entry in (spec or "").split(","):
            if not entry.strip():
                continue
            rule = FaultRule.parse(entry)
            rules.setdefault(rule.site, []).append(rule)
        with self._lock:
            self._rules = rules
            self.seed = seed
            self.enabled = enabled and bool(rules)
            self._visits.clear()
            self._trips.clear()
            self._rngs.clear()
            self.events.clear()
            self._fingerprint = None

    def reset(self) -> None:
        """Disarm and clear all schedules/counters (test isolation)."""
        with self._lock:
            self.enabled = False
            self._rules = {}
            self._visits.clear()
            self._trips.clear()
            self._rngs.clear()
            self.events.clear()
            self._fingerprint = None

    # -- suppression (degrade/evacuate paths must not re-trip) -----------
    class _Suppressed:
        def __init__(self, inj): self._inj = inj

        def __enter__(self):
            with self._inj._lock:
                self._inj._suppress += 1

        def __exit__(self, *exc):
            with self._inj._lock:
                self._inj._suppress -= 1
            return False

    def suppressed(self) -> "_Suppressed":
        """Context manager: sites never trip inside (the evacuation /
        fallback path of last resort must not be chaos-injected)."""
        return self._Suppressed(self)

    # -- the hot check ---------------------------------------------------
    def _trip(self, site: str) -> Optional[InjectedFault]:
        from ..metrics.profiler import dispatch_context

        ctx_job = dispatch_context()[0]
        with self._lock:
            if self._suppress:
                return None
            rules = self._rules.get(site)
            if not rules:
                return None
            visit = self._visits.get(site, 0) + 1
            self._visits[site] = visit
            # job-filtered rules count visits on their own per-tenant
            # stream (site!job@NAME) so every@N means "every Nth visit
            # BY that job"; at most one bump per stream per visit even
            # with several rules on it
            bumped: dict[str, int] = {site: visit}

            def stream_visit(key: str) -> int:
                if key not in bumped:
                    bumped[key] = self._visits.get(key, 0) + 1
                    # lint: lock-ok closure only called in the locked block
                    self._visits[key] = bumped[key]
                return bumped[key]

            hit_rule, hit_visit = None, visit
            for rule in rules:
                if rule.mode == "off":
                    continue
                if rule.job:
                    if ctx_job != rule.job:
                        continue
                    key = f"{site}!job@{rule.job}"
                    rvisit = stream_visit(key)
                else:
                    key, rvisit = site, visit
                if rule.mode == "once":
                    hit = rvisit == rule.at
                elif rule.mode == "every":
                    hit = rvisit % rule.at == 0
                elif rule.mode == "always":
                    hit = True
                else:  # prob
                    rng = self._rngs.get(key)
                    if rng is None:
                        rng = self._rngs[key] = random.Random(
                            f"{self.seed}:{key}")
                    hit = rng.random() < rule.p
                if hit:
                    hit_rule, hit_visit = rule, rvisit
                    break
            if hit_rule is None:
                return None
            rule = hit_rule
            self._trips[site] = self._trips.get(site, 0) + 1
            if len(self.events) < 4096:
                self.events.append({"site": site, "visit": hit_visit,
                                    "transient": rule.transient,
                                    "poison": rule.poison,
                                    "hang_ms": rule.hang_ms,
                                    "job": rule.job or ctx_job})
        from ..metrics.device import DEVICE_STATS
        DEVICE_STATS.note_injected(site)
        return InjectedFault(site, hit_visit, transient=rule.transient,
                             poison=rule.poison, hang_ms=rule.hang_ms)

    def _hang(self, fault: InjectedFault) -> None:
        """Sleep out a hang trip OUTSIDE the injector lock, in small
        slices that watch the watchdog abandonment flag: once the caller
        gave up on this worker, the real operation behind the site must
        never run (exactly-once under stall-retry)."""
        from .watchdog import current_call_abandoned

        end = time.monotonic() + fault.hang_ms / 1000.0
        while True:
            if current_call_abandoned():
                raise HangAbandoned(
                    f"hang at {fault.site} abandoned by the watchdog")
            remaining = end - time.monotonic()
            if remaining <= 0:
                return
            time.sleep(min(remaining, 0.005))

    def fire(self, site: str) -> None:
        """Visit a raising site; raises InjectedFault when its rule trips.
        A hang trip sleeps instead (the stall, not an exception, IS the
        fault — the watchdog deadline is what surfaces it)."""
        if not self.enabled:
            return
        fault = self._trip(site)
        if fault is None:
            return
        if fault.hang_ms:
            self._hang(fault)
            return
        raise fault

    def check(self, site: str) -> bool:
        """Visit a drop-style site (lost heartbeat, full queue): returns
        True when the rule trips — the caller drops/declines instead of
        raising. Hang trips sleep and report not-tripped (the delay is
        the fault)."""
        if not self.enabled:
            return False
        fault = self._trip(site)
        if fault is None:
            return False
        if fault.hang_ms:
            self._hang(fault)
            return False
        return True

    # -- views -----------------------------------------------------------
    def snapshot(self) -> dict:
        with self._lock:
            return {"enabled": self.enabled, "seed": self.seed,
                    "visits": dict(self._visits),
                    "trips": dict(self._trips)}


#: The process-global injector every site consults. ``deploy_local`` /
#: ``DistributedHost.deploy`` configure it from the job Configuration.
FAULTS = FaultInjector()


def fire_with_retries(site: str, scope: Optional[str] = None,
                      max_attempts: int = 5) -> int:
    """Visit a raising site with transient-retry semantics: a transient
    trip counts one retry (``DEVICE_STATS``) and re-visits; persistent or
    poison trips — and retry exhaustion — propagate. Returns the number of
    retries spent. The shared idiom for transfer/channel/sink sites whose
    'retry' IS simply attempting the operation again."""
    if not FAULTS.enabled:
        return 0
    from ..metrics.device import DEVICE_STATS
    for attempt in range(max_attempts + 1):
        try:
            FAULTS.fire(site)
            return attempt
        except InjectedFault as e:
            if not e.transient or e.poison or attempt >= max_attempts:
                raise
            DEVICE_STATS.note_retry(scope or site)
    return max_attempts  # pragma: no cover - loop always returns/raises


def _is_device_error(e: BaseException) -> bool:
    """Real accelerator-runtime failures (as opposed to programming
    errors, which must propagate untouched): anything out of the XLA
    runtime / PJRT client surfaces as XlaRuntimeError or JaxRuntimeError
    depending on the jaxlib vintage."""
    for t in type(e).__mro__:
        if t.__name__ in ("XlaRuntimeError", "JaxRuntimeError"):
            return True
    return False


class DeviceGuard:
    """Retry/escalate wrapper around compiled-segment calls.

    * transient faults (injected-transient, or real XLA runtime errors)
      retry up to ``device.failover.max-retries`` with exponential
      backoff, counted in ``DEVICE_STATS`` (``device_retries_total``);
    * poison faults skip retry — re-running identical data cannot
      unpoison it — and surface as ``DeviceSegmentError(poison=True)``
      so the operator quarantines the batch;
    * persistent faults / exhausted retries surface as
      ``DeviceSegmentError`` for the operator's degradation ladder.

    ``active=False`` (set when an operator has degraded to its CPU
    fallback) turns the guard into a passthrough: the fallback path of
    last resort is never chaos-injected.
    """

    def __init__(self, scope: str, config=None):
        from ..cluster.failover import ExponentialDelayRestartStrategy
        from ..core.config import FaultOptions

        self.scope = scope
        self.active = True
        if config is not None:
            self.max_retries = int(config.get(FaultOptions.DEVICE_MAX_RETRIES))
            initial = float(config.get(FaultOptions.DEVICE_RETRY_BACKOFF))
            maximum = float(config.get(
                FaultOptions.DEVICE_RETRY_BACKOFF_MAX))
        else:
            self.max_retries, initial, maximum = 3, 0.005, 0.25
        # reuse the failover escalation math: consecutive failures back off
        # exponentially, a healthy call resets the ladder
        self._strategy = ExponentialDelayRestartStrategy(
            initial=initial, maximum=maximum, reset_after=60.0)
        self.retries = 0      # per-guard observability (bench/tests)
        self.failures = 0
        self.stalls = 0       # watchdog deadline expiries seen here

    @staticmethod
    def _note_breaker(success: bool) -> None:
        """Feed the owning job's circuit breaker (cluster/isolation.py):
        a surfaced DeviceSegmentError counts one failure toward tripping
        it open, a healthy guarded call resets the ladder. No-op unless
        isolation is enabled."""
        from ..cluster.isolation import ISOLATION
        if not ISOLATION.enabled:
            return
        from ..metrics.profiler import dispatch_context
        job = dispatch_context()[0]
        if success:
            ISOLATION.note_success(job)
        else:
            ISOLATION.note_failure(job)

    def _sites_ok(self, sites: tuple) -> None:
        for s in sites:
            FAULTS.fire(s)

    def run(self, fn: Callable, sites: tuple = ("device.execute",)):
        """Call ``fn`` (which performs the guarded upload+dispatch) after
        visiting ``sites``, the whole attempt deadline-bounded by the
        stall watchdog (site ``device.execute``). Retries transient
        failures AND stalls; raises DeviceSegmentError beyond that — so
        repeated stalls at one segment walk the same degradation ladder
        as repeated failures (evacuate + synchronous fallback pin)."""
        if not self.active:
            return fn()
        from ..metrics.tracing import TRACER
        from .watchdog import WATCHDOG, StallError

        def attempt_call():
            self._sites_ok(sites)
            return fn()

        attempt = 0
        while True:
            try:
                with (TRACER.span("device", "Execute")
                      .set_attribute("scope", self.scope)
                      .set_attribute("attempt", attempt)):
                    out = WATCHDOG.run("device.execute", attempt_call,
                                       scope=self.scope)
                if attempt:
                    self._strategy.notify_recovered()
                self._note_breaker(success=True)
                return out
            except StallError as e:
                # a stall is transient first: the abandoned worker never
                # ran the real dispatch (hang sleeps check abandonment),
                # so re-running it cannot double-fold
                self.stalls += 1
                err, retryable = e, True
            except InjectedFault as e:
                if e.poison:
                    self.failures += 1
                    self._note_breaker(success=False)
                    raise DeviceSegmentError(self.scope, e, poison=True) \
                        from e
                err, retryable = e, e.transient
            except Exception as e:  # noqa: BLE001 - classify, re-raise rest
                if not _is_device_error(e):
                    raise
                err, retryable = e, True
            if not retryable or attempt >= self.max_retries:
                self.failures += 1
                self._note_breaker(success=False)
                raise DeviceSegmentError(self.scope, err) from err
            attempt += 1
            self.retries += 1
            from ..metrics.device import DEVICE_STATS
            DEVICE_STATS.note_retry(self.scope)
            self._strategy.notify_failure()
            time.sleep(self._strategy.backoff_seconds())
