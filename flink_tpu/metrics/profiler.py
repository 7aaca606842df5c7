"""Device-time ledger: per-program dispatch profiling, recompile
attribution, and static cost-model accounting.

``DEVICE_STATS`` counts *events* (compiles, cache hits, transfer bytes)
but attributes no wall-clock device time to anything — the multi-query
device-time scheduler and the self-tuning controller on the ROADMAP both
need to know *which program, owned by which operator and job, is burning
the device*.  The process-global :data:`DEVICE_LEDGER` (same singleton +
``configure(config)`` pattern as ``DEVICE_STATS``/``TRACER``/``FAULTS``,
wired by every deploy path via ``profiler.*`` options) is that
measurement substrate.  It is OFF by default: a disabled ledger costs
one attribute read per dispatch site.

Every sample is attributed to a stable :class:`ProgramKey`::

    (job, operator, site, shape_signature)

* ``job``/``operator`` ride a thread-local dispatch context pushed by
  the operator chain at batch/watermark entry (``set_dispatch_context``)
  — dispatch sites themselves never know which job they serve.
* ``site`` is a dotted dispatch-site name from the doc-locked
  :data:`LEDGER_SITE_INVENTORY` (TPU305 keeps code, this inventory, and
  docs/OBSERVABILITY.md identical).
* ``shape_signature`` is the builder cache key of the dispatched
  program (``_TimedProgram._build_key`` / ``runtime.compiled.shape_key``)
  — already computed by the caches, so attribution adds no per-dispatch
  tree walk.

Each entry carries exact ``count``/``self_ms``/``compile_ms`` totals, a
bounded duration reservoir (p50/p95 percentile window; ``max`` is exact
over the entry's lifetime), EWMA duration + dispatch-rate estimates, and
— resolved lazily from PROGRAM_AUDIT at read time, never on the dispatch
path — a static roofline cost estimate traced from the program's jaxpr
(flop + byte counts, the Tier-B analyzer's walk):

    estimated_ms = max(flops / gflops, bytes / gbps)

with ``profiler.cost-model.gflops`` / ``profiler.cost-model.gbps`` as
the assumed rates; ``achieved_vs_estimated`` is measured/estimated.

Recompile attribution: on every instrumented-cache miss after a scope's
first build, the new builder arguments are diffed against the nearest
prior build (most shared parameters) and the record names exactly which
parameter — down to the tuple element, e.g. ``shape[1]: 64 -> 128`` —
changed.  ``recompiles != 0`` regressions become one CLI table
(``python -m flink_tpu.cli profile <job>``) instead of a grep hunt.

What is timed is the HOST'S DISPATCH CALL of each program: JAX returns
from it before the device has run the program, so ``self_ms`` /
``device_ms`` here are dispatch time, not time on the device (that comes
from a ``jax.profiler`` trace; PERF.md section 3). The Perfetto counter
track says so: ``dispatch_ms:<site>``.

Durations are measured with ``time.perf_counter()`` and clamped to be
non-negative; timestamps come from the monotonic-anchored ``now_ms()``
(TPU501: no wall clock in span paths).  All mutation happens under one
ledger lock (TPU401); scrape paths copy under the same lock, so a
concurrent record/scrape drill sees no torn reads.
"""

from __future__ import annotations

import inspect
import threading
import time
from collections import deque
from typing import Any, Dict, List, NamedTuple, Optional, Tuple

from .tracing import now_ms

__all__ = [
    "ProgramKey", "DeviceLedger", "DEVICE_LEDGER",
    "LEDGER_SITE_INVENTORY", "bind_ledger_metrics",
    "set_dispatch_context", "clear_dispatch_context", "dispatch_context",
]


class ProgramKey(NamedTuple):
    """Stable attribution key for one profiled program."""

    job: str
    operator: str
    site: str
    shape_signature: str


# ---------------------------------------------------------------------------
# Thread-local dispatch context: the operator chain pushes (job, operator)
# at batch/watermark entry so device dispatch sites — which know only
# their site name — can attribute time to the owning job and operator.
# ---------------------------------------------------------------------------

_CTX = threading.local()


def set_dispatch_context(job: str, operator: str) -> None:
    """Pin the (job, operator) owner for ledger samples recorded on this
    thread until the next ``set_dispatch_context``/``clear``."""
    _CTX.job = job
    _CTX.operator = operator


def clear_dispatch_context() -> None:
    _CTX.job = ""
    _CTX.operator = ""


def dispatch_context() -> Tuple[str, str]:
    return (getattr(_CTX, "job", ""), getattr(_CTX, "operator", ""))


# ---------------------------------------------------------------------------
# Per-key ledger entries
# ---------------------------------------------------------------------------


class _Entry:
    """Mutable accumulator for one ProgramKey.  Mutated only under the
    owning ledger's lock — it carries no lock of its own."""

    __slots__ = ("count", "compiles", "self_ms", "compile_ms", "max_ms",
                 "ewma_ms", "ewma_interval_ms", "last_ts_ms", "nbytes",
                 "reservoir")

    def __init__(self, reservoir: int):
        self.count = 0              # dispatches (compile calls excluded)
        self.compiles = 0
        self.self_ms = 0.0          # device dispatch time
        self.compile_ms = 0.0       # trace/lower/compile time
        self.max_ms = 0.0           # exact lifetime max dispatch duration
        self.ewma_ms = 0.0
        self.ewma_interval_ms = 0.0
        self.last_ts_ms = 0
        self.nbytes = 0             # payload bytes (transfer sites)
        self.reservoir: deque = deque(maxlen=max(1, int(reservoir)))


def _percentile(sorted_vals: List[float], q: float) -> float:
    if not sorted_vals:
        return 0.0
    idx = min(len(sorted_vals) - 1, int(q * (len(sorted_vals) - 1) + 0.5))
    return sorted_vals[idx]


# ---------------------------------------------------------------------------
# Static cost model: flop/byte counts traced from the program's jaxpr at
# its audited abstract signature (the Tier-B analyzer's recursive walk),
# folded through a two-term roofline.  Resolved lazily at READ time and
# cached per (site, shape_signature) — never on the dispatch path.
# ---------------------------------------------------------------------------


def _iter_eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for val in eqn.params.values():
            for sub in _sub_jaxprs(val):
                yield from _iter_eqns(sub)


def _sub_jaxprs(val):
    inner = getattr(val, "jaxpr", None)
    if inner is not None and hasattr(inner, "eqns"):
        yield inner
    elif hasattr(val, "eqns"):
        yield val
    elif isinstance(val, (tuple, list)):
        for v in val:
            yield from _sub_jaxprs(v)


def _aval_elems(aval) -> int:
    n = 1
    for d in getattr(aval, "shape", ()) or ():
        n *= int(d)
    return n


def _aval_bytes(aval) -> int:
    try:
        import numpy as np
        return _aval_elems(aval) * np.dtype(aval.dtype).itemsize
    except Exception:
        return 0


def _estimate_flops_bytes(closed) -> Tuple[int, int]:
    """(flops, bytes) of a ClosedJaxpr: one flop per output element per
    equation (elementwise model), 2*M*N*K for dot_general; bytes are the
    program's top-level input + output buffer footprint (what the
    dispatch actually moves through HBM at minimum)."""
    jaxpr = closed.jaxpr if hasattr(closed, "jaxpr") else closed
    flops = 0
    for eqn in _iter_eqns(jaxpr):
        out_elems = sum(_aval_elems(getattr(v, "aval", None) or ())
                        for v in eqn.outvars)
        if eqn.primitive.name == "dot_general":
            k = 1
            try:
                (contract, _batch) = eqn.params["dimension_numbers"]
                lhs = eqn.invars[0].aval
                for d in contract[0]:
                    k *= int(lhs.shape[d])
            except Exception:
                pass
            flops += 2 * out_elems * k
        else:
            flops += out_elems
    nbytes = 0
    for v in list(jaxpr.invars) + list(jaxpr.outvars):
        aval = getattr(v, "aval", None)
        if aval is not None:
            nbytes += _aval_bytes(aval)
    return flops, nbytes


def _trace_cost(site: str, shape_signature: str) -> Optional[Tuple[int, int]]:
    """Resolve (flops, bytes) for a profiled program by re-tracing its
    PROGRAM_AUDIT entry abstractly; None when no audit entry matches or
    the program cannot be abstractly re-traced."""
    try:
        import jax

        from .device import PROGRAM_AUDIT
    except Exception:
        return None
    for entry in list(PROGRAM_AUDIT):
        if entry.scope != site or entry.build_key != shape_signature:
            continue
        try:
            closed = jax.make_jaxpr(entry.fn)(*entry.abstract_args,
                                              **entry.abstract_kwargs)
        except Exception:
            return None
        return _estimate_flops_bytes(closed)
    return None


# ---------------------------------------------------------------------------
# Recompile attribution
# ---------------------------------------------------------------------------


def _bind_builder_args(builder, args: tuple, kwargs: dict) -> Dict[str, Any]:
    """Builder arguments by parameter name (repr-compared); positional
    fallback ``arg0``/``arg1``… when the signature cannot be bound."""
    try:
        bound = inspect.signature(builder).bind(*args, **kwargs)
        bound.apply_defaults()
        return dict(bound.arguments)
    except (TypeError, ValueError):
        named = {f"arg{i}": a for i, a in enumerate(args)}
        named.update(kwargs)
        return named


def _describe_changes(prev: Dict[str, Any],
                      cur: Dict[str, Any]) -> List[str]:
    """Human-readable per-parameter diff; tuples of equal length diff to
    the exact changed element (``shape[1]: 64 -> 128``)."""
    changed: List[str] = []
    for name in sorted(set(prev) | set(cur)):
        if name not in prev:
            changed.append(f"{name}: <absent> -> {cur[name]!r}")
            continue
        if name not in cur:
            changed.append(f"{name}: {prev[name]!r} -> <absent>")
            continue
        old, new = prev[name], cur[name]
        if repr(old) == repr(new):
            continue
        if (isinstance(old, tuple) and isinstance(new, tuple)
                and len(old) == len(new)):
            for i, (a, b) in enumerate(zip(old, new)):
                if repr(a) != repr(b):
                    changed.append(f"{name}[{i}]: {a!r} -> {b!r}")
        else:
            changed.append(f"{name}: {old!r} -> {new!r}")
    return changed


# ---------------------------------------------------------------------------
# The ledger
# ---------------------------------------------------------------------------


class DeviceLedger:
    """Process-global device-time ledger.  All mutation under one lock;
    every read surface copies under the same lock (no torn reads on the
    scrape path).  Disabled, every site pays one attribute read."""

    # Priors retained per site for nearest-prior recompile diffing.
    _PRIORS_PER_SITE = 8

    def __init__(self):
        self._lock = threading.Lock()
        self.enabled = False
        self.reservoir = 256
        self.recompile_history = 64
        self.ewma_alpha = 0.2
        self.trace_samples = 2048
        self.cost_gflops = 50.0
        self.cost_gbps = 10.0
        self._entries: Dict[ProgramKey, _Entry] = {}
        self._builds: Dict[str, deque] = {}       # site -> prior builds
        self._recompiles: deque = deque(maxlen=64)
        self._samples: deque = deque(maxlen=2048)  # (ts_ms, site, ms)
        self._cost_cache: Dict[Tuple[str, str], Optional[Tuple[int, int]]] \
            = {}

    # -- wiring ------------------------------------------------------------

    def configure(self, config) -> None:
        """Apply ``profiler.*`` options (same pattern as FAULTS /
        WATCHDOG / TRACER); called by every deploy path."""
        from ..core.config import ProfilerOptions
        with self._lock:
            self.enabled = bool(config.get(ProfilerOptions.ENABLED))
            self.reservoir = int(config.get(ProfilerOptions.RESERVOIR))
            self.recompile_history = int(
                config.get(ProfilerOptions.RECOMPILE_HISTORY))
            self.ewma_alpha = float(config.get(ProfilerOptions.EWMA_ALPHA))
            self.trace_samples = int(
                config.get(ProfilerOptions.TRACE_SAMPLES))
            self.cost_gflops = float(
                config.get(ProfilerOptions.COST_GFLOPS))
            self.cost_gbps = float(config.get(ProfilerOptions.COST_GBPS))
            if self._recompiles.maxlen != self.recompile_history:
                self._recompiles = deque(
                    self._recompiles, maxlen=max(1, self.recompile_history))
            if self._samples.maxlen != self.trace_samples:
                self._samples = deque(
                    self._samples, maxlen=max(1, self.trace_samples))

    # -- recording (the dispatch path) -------------------------------------

    def record(self, site: str, ms: float, *, shape_sig: str = "",
               kind: str = "dispatch", nbytes: int = 0,
               job: Optional[str] = None,
               operator: Optional[str] = None) -> None:
        """Account one timed event at ``site``.  ``kind="compile"``
        charges trace/lower/compile time (a program's first dispatch);
        ``kind="dispatch"`` charges steady-state device time.  Durations
        are clamped non-negative (caller clock skew must never produce a
        negative total)."""
        if not self.enabled:
            return
        ms = max(float(ms), 0.0)
        if job is None or operator is None:
            cj, co = dispatch_context()
            job = cj if job is None else job
            operator = co if operator is None else operator
        key = ProgramKey(job, operator, site, shape_sig)
        ts = now_ms()
        with self._lock:
            e = self._entries.get(key)
            if e is None:
                e = self._entries[key] = _Entry(self.reservoir)
            if kind == "compile":
                e.compiles += 1
                e.compile_ms += ms
            else:
                e.count += 1
                e.self_ms += ms
                e.nbytes += int(nbytes)
                if ms > e.max_ms:
                    e.max_ms = ms
                e.reservoir.append(ms)
                a = self.ewma_alpha
                e.ewma_ms = ms if e.count == 1 \
                    else (1.0 - a) * e.ewma_ms + a * ms
                if e.last_ts_ms:
                    dt = max(ts - e.last_ts_ms, 0)
                    e.ewma_interval_ms = dt if e.ewma_interval_ms == 0.0 \
                        else (1.0 - a) * e.ewma_interval_ms + a * dt
                e.last_ts_ms = ts
            self._samples.append((ts, site, ms))

    def note_build(self, site: str, build_key: str, builder,
                   args: tuple, kwargs: dict) -> None:
        """Recompile attribution: called on every instrumented-cache
        MISS.  The first build of a site is the expected compile; each
        later build is diffed parameter-by-parameter against the nearest
        prior build (most shared arguments) and the record names exactly
        which dimension changed.  Never counted into
        ``DEVICE_STATS.compiles`` — the bench recompile budget is not
        this ledger's to spend."""
        if not self.enabled:
            return
        named = _bind_builder_args(builder, args, kwargs)
        job, operator = dispatch_context()
        with self._lock:
            priors = self._builds.get(site)
            if priors is None:
                priors = self._builds[site] = deque(
                    maxlen=self._PRIORS_PER_SITE)
            record = None
            if priors:
                def shared(p):
                    return sum(1 for k, v in p[1].items()
                               if k in named and repr(named[k]) == repr(v))
                nearest = max(priors, key=shared)
                record = {
                    "site": site, "job": job, "operator": operator,
                    "key": build_key, "prior_key": nearest[0],
                    "changed": _describe_changes(nearest[1], named),
                    "ts_ms": now_ms(),
                }
            priors.append((build_key, named))
            if record is not None:
                self._recompiles.append(record)

    # -- read surfaces -----------------------------------------------------

    def _cost_for(self, site: str,
                  shape_signature: str) -> Optional[Tuple[int, int]]:
        # lazy + cached: jaxpr re-tracing is read-path work only
        ck = (site, shape_signature)
        with self._lock:
            if ck in self._cost_cache:
                return self._cost_cache[ck]
        cost = _trace_cost(site, shape_signature) if shape_signature else None
        with self._lock:
            self._cost_cache[ck] = cost
        return cost

    def _entry_dict(self, key: ProgramKey, e: _Entry,
                    window: List[float]) -> dict:
        window.sort()
        mean = e.self_ms / e.count if e.count else 0.0
        rate = (1000.0 / e.ewma_interval_ms
                if e.ewma_interval_ms > 0.0 else 0.0)
        return {
            "job": key.job, "operator": key.operator, "site": key.site,
            "shape_signature": key.shape_signature,
            "count": e.count, "compiles": e.compiles,
            "self_ms": e.self_ms, "compile_ms": e.compile_ms,
            "total_ms": e.self_ms + e.compile_ms,
            "mean_ms": mean, "p50_ms": _percentile(window, 0.50),
            "p95_ms": _percentile(window, 0.95), "max_ms": e.max_ms,
            "ewma_ms": e.ewma_ms, "rate_hz": rate, "bytes": e.nbytes,
        }

    def _with_cost(self, d: dict) -> dict:
        """Attach the static cost estimate to an entry dict."""
        cost = self._cost_for(d["site"], d["shape_signature"])
        if cost is None and d["site"].startswith("transfer.") and d["bytes"]:
            # transfers have no jaxpr; the byte term IS the model
            cost = (0, d["bytes"] // max(d["count"], 1))
        if cost is None:
            d.update(est_flops=None, est_bytes=None, est_ms=None,
                     achieved_vs_estimated=None)
            return d
        flops, nbytes = cost
        est_ms = max(flops / (self.cost_gflops * 1e6),
                     nbytes / (self.cost_gbps * 1e6))
        d.update(est_flops=flops, est_bytes=nbytes, est_ms=est_ms,
                 achieved_vs_estimated=(
                     d["mean_ms"] / est_ms if est_ms > 0.0 else None))
        return d

    def snapshot(self) -> dict:
        """Cheap rollups for /metrics and prometheus: totals plus
        per-job and per-site device-time shares.  No jaxpr work."""
        with self._lock:
            items = [(k, e, list(e.reservoir))
                     for k, e in self._entries.items()]
            recompiles = len(self._recompiles)
        jobs: Dict[str, dict] = {}
        sites: Dict[str, dict] = {}
        operators: Dict[str, dict] = {}
        tot_self = tot_compile = 0.0
        tot_count = 0
        for key, e, _w in items:
            tot_self += e.self_ms
            tot_compile += e.compile_ms
            tot_count += e.count
            j = jobs.setdefault(key.job or "<unattributed>",
                                {"device_ms": 0.0, "compile_ms": 0.0,
                                 "dispatches": 0})
            j["device_ms"] += e.self_ms
            j["compile_ms"] += e.compile_ms
            j["dispatches"] += e.count
            s = sites.setdefault(key.site, {"device_ms": 0.0, "count": 0})
            s["device_ms"] += e.self_ms
            s["count"] += e.count
            o = operators.setdefault(key.operator or "<unattributed>",
                                     {"device_ms": 0.0, "count": 0})
            o["device_ms"] += e.self_ms
            o["count"] += e.count
        return {
            "enabled": self.enabled, "entries": len(items),
            "device_ms_total": tot_self, "compile_ms_total": tot_compile,
            "dispatches_total": tot_count,
            "recompiles_attributed": recompiles,
            "jobs": jobs, "sites": sites, "operators": operators,
        }

    def profile(self, job: Optional[str] = None, top: int = 10) -> dict:
        """The full attribution report: top-``top`` hot programs (cost
        model attached), per-operator device-time shares, and the
        recompile-attribution records.  ``job`` filters by exact job
        name; None aggregates every job."""
        with self._lock:
            items = [(k, e, list(e.reservoir))
                     for k, e in self._entries.items()]
            recompiles = [dict(r) for r in self._recompiles]
        if job is not None:
            items = [(k, e, w) for k, e, w in items if k.job == job]
            recompiles = [r for r in recompiles if r.get("job") == job]
        rows = [self._entry_dict(k, e, w) for k, e, w in items]
        total_self = sum(r["self_ms"] for r in rows)
        total_compile = sum(r["compile_ms"] for r in rows)
        for r in rows:
            r["share"] = (r["self_ms"] / total_self) if total_self else 0.0
        rows.sort(key=lambda r: (-r["total_ms"], r["site"],
                                 r["shape_signature"]))
        operators: Dict[str, float] = {}
        for r in rows:
            op = r["operator"] or "<unattributed>"
            operators[op] = operators.get(op, 0.0) + r["self_ms"]
        op_rows = [{"operator": op, "device_ms": ms,
                    "share": (ms / total_self) if total_self else 0.0}
                   for op, ms in sorted(operators.items(),
                                        key=lambda kv: -kv[1])]
        return {
            "job": job, "enabled": self.enabled,
            "total_device_ms": total_self,
            "total_compile_ms": total_compile,
            "programs": [self._with_cost(r) for r in rows[:max(0, top)]],
            "operators": op_rows,
            "recompiles": recompiles,
        }

    def trace_counters(self) -> List[dict]:
        """Recent (ts_ms, site, ms) samples for the Perfetto counter
        tracks (``chrome_trace_events(counters=...)``, rendered as
        ``dispatch_ms:<site>``: what is timed is the host's dispatch
        call, not the program on the device)."""
        with self._lock:
            return [{"ts_ms": ts, "site": site, "ms": ms}
                    for ts, site, ms in self._samples]

    def reset(self) -> None:
        """Test hook: drop every entry, prior build, and sample."""
        with self._lock:
            self._entries.clear()
            self._builds.clear()
            self._recompiles.clear()
            self._samples.clear()
            self._cost_cache.clear()


DEVICE_LEDGER = DeviceLedger()


def bind_ledger_metrics(registry) -> None:
    """Register ledger rollups as gauges under the ``profiler`` scope of
    a MetricRegistry (prometheus: ``flink_tpu_profiler_*``).  Idempotent:
    re-binding overwrites the same scope entries."""
    g = registry.root().group("profiler")
    led = DEVICE_LEDGER
    g.gauge("enabled", lambda: 1 if led.enabled else 0)
    g.gauge("entries", lambda: led.snapshot()["entries"])
    g.gauge("device_ms_total",
            lambda: led.snapshot()["device_ms_total"])
    g.gauge("compile_ms_total",
            lambda: led.snapshot()["compile_ms_total"])
    g.gauge("dispatches_total",
            lambda: led.snapshot()["dispatches_total"])
    g.gauge("recompiles_attributed_total",
            lambda: led.snapshot()["recompiles_attributed"])


# Every ledger dispatch site, with its recording location.  The
# "Device-time ledger" section of docs/OBSERVABILITY.md renders this
# inventory as a table and TPU305 asserts code literals (every
# ``instrumented_program_cache("<site>")`` builder and every literal
# ``DEVICE_LEDGER.record("<site>", ...)`` call), this tuple, and the doc
# table stay identical.  Keep entries sorted by site.
LEDGER_SITE_INVENTORY: tuple = (
    ("chain.fused_prelude",
     "runtime/compiled.py FusedChain.run — certified decode prelude "
     "registration (compile marker; its time is charged to the fused "
     "step that contains it)"),
    ("chain.fused_step",
     "runtime/compiled.py FusedChain.run — one fused decode+step "
     "dispatch per certified micro-batch"),
    ("device_session.fire",
     "runtime/operators/device_session.py — session-window fire "
     "(merge + emit) program"),
    ("device_session.step",
     "runtime/operators/device_session.py — per-batch session ingest "
     "program"),
    ("device_window.fire",
     "runtime/operators/device_window.py — full pane fire program"),
    ("device_window.step",
     "runtime/operators/device_window.py — per-batch window ingest "
     "program"),
    ("mesh.fire",  # lint: key-ok ledger site, not a config key
     "parallel/sharded_window.py — sharded fire (compact) program"),
    ("mesh.fire_full",
     "parallel/sharded_window.py — sharded full-fire program"),
    ("mesh.init",  # lint: key-ok ledger site, not a config key
     "parallel/sharded_window.py — the empty sharded state, built shard "
     "by shard"),
    ("mesh.reclaim",  # lint: key-ok ledger site, not a config key
     "parallel/sharded_window.py — sharded reclaim program (every "
     "shard's table rebuilt at its own capacity from its live keys, its "
     "planes re-seated; built with the state, before any input)"),
    ("mesh.retire",  # lint: key-ok ledger site, not a config key
     "parallel/sharded_window.py — retired-pane cleanup program"),
    ("mesh.step",  # lint: key-ok ledger site, not a config key
     "parallel/sharded_window.py — sharded per-batch ingest program"),
    ("ops.pallas_topk",
     "ops/pallas_topk.py — top-k selection kernel"),
    ("sched.throttle",  # lint: key-ok ledger site, not a config key
     "runtime/stream_task.py _admission_gate — wall time a micro-batch "
     "waited at the per-job admission gate before dispatch (quota "
     "pressure, charged to the throttled job)"),
    ("sql.device_group_agg",
     "sql/device_group_agg.py — SQL grouped-aggregation program"),
    ("state.fold",  # lint: key-ok ledger site, not a config key
     "state/tpu_backend.py — ring-plane fold program (one batch into "
     "every ring plane, ring row by ring row)"),
    ("state.reclaim",  # lint: key-ok ledger site, not a config key
     "state/tpu_backend.py — reclaim program (the table rebuilt at its "
     "own capacity from the live keys, every plane re-seated)"),
    ("state.reset_row",
     "state/tpu_backend.py — keyed-state row reset program"),
    ("transfer.d2h",
     "metrics/device.py note_d2h — device→host transfer"),
    ("transfer.h2d",
     "metrics/device.py note_h2d — host→device transfer"),
)
