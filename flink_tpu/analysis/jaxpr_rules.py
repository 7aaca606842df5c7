"""Tier-B jaxpr program audit (JX5xx): abstractly re-trace every
compiled-segment builder that registered through
``instrumented_program_cache`` (metrics/device.py PROGRAM_AUDIT) and
lint the program IR itself.

The audit needs a populated registry: either a pipeline already ran in
this process (bench.py --audit) or ``exercise_programs()`` runs a tiny
Q5-shaped job first (the cli lint path).  Without jax the rules report
themselves as skipped — Tier A never depends on them.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

from .core import AnalysisContext, Finding, rule, skip_rule

# --------------------------------------------------------------------------
# Registry access + shared tracing helpers


def _entries():
    try:
        from flink_tpu.metrics.device import PROGRAM_AUDIT
    except Exception as e:  # pragma: no cover - import failure only
        skip_rule(f"metrics.device unavailable: {e}")
    if not PROGRAM_AUDIT:
        skip_rule("no programs registered — run exercise_programs() or a "
                  "pipeline first")
    return list(PROGRAM_AUDIT)


def _require_jax():
    try:
        import jax  # noqa: F401
        return jax
    except Exception as e:
        skip_rule(f"jax unavailable: {e}")


def _entry_location(ctx: AnalysisContext, entry) -> Tuple[str, int]:
    if entry.source:
        fname, lineno = entry.source
        try:
            from pathlib import Path
            rel = Path(fname).resolve().relative_to(ctx.root.resolve())
            return rel.as_posix(), lineno
        except ValueError:
            return fname, lineno
    return f"program:{entry.scope}", 0


def _trace_jaxpr(jax, entry):
    """ClosedJaxpr of the program at its recorded abstract signature, or
    None when the program cannot be abstractly re-traced (e.g. it closes
    over concrete device buffers)."""
    try:
        return jax.make_jaxpr(entry.fn)(*entry.abstract_args,
                                        **entry.abstract_kwargs)
    except Exception:
        return None


def _iter_eqns(jaxpr):
    """All equations, recursing into nested (pjit / scan / cond / …)
    sub-jaxprs via eqn params."""
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


def _all_avals(jaxpr):
    seen = []

    def collect(j):
        for v in list(j.invars) + list(j.outvars):
            aval = getattr(v, "aval", None)
            if aval is not None:
                seen.append(aval)
        for eqn in j.eqns:
            for v in list(eqn.invars) + list(eqn.outvars):
                aval = getattr(v, "aval", None)
                if aval is not None:
                    seen.append(aval)
            for val in eqn.params.values():
                for sub in _sub_jaxprs(val):
                    collect(sub)

    collect(jaxpr.jaxpr if hasattr(jaxpr, "jaxpr") else jaxpr)
    return seen


# --------------------------------------------------------------------------
# JX501 — scatter lowering in a fire-path program


@rule("JX501", "scatter lowering on the fire path", "B",
      "scatter/scatter-add primitives lower to a serial loop on the CPU "
      "fallback rung and a slow DUS cascade on TPU; per-fire programs "
      "(latency-critical, once per pane) must stay scatter-free — the "
      "PR 8 top-k regression class")
def scatter_rule(ctx: AnalysisContext) -> List[Finding]:
    jax = _require_jax()
    findings: List[Finding] = []
    for entry in _entries():
        if not any(tok in entry.scope
                   for tok in ctx.settings.fire_path_scopes):
            continue
        closed = _trace_jaxpr(jax, entry)
        if closed is None:
            continue
        prims = sorted({eqn.primitive.name
                        for eqn in _iter_eqns(closed.jaxpr)
                        if eqn.primitive.name.startswith("scatter")})
        if not prims:
            continue
        file, line = _entry_location(ctx, entry)
        findings.append(Finding(
            rule="JX501", file=file, line=line,
            symbol=f"{entry.scope}:{'+'.join(prims)}",
            message=f"fire-path program '{entry.scope}' lowers "
                    f"{', '.join(prims)}",
            hint="rank/permute with sort- or bisection-based selection "
                 "(ops/topk.py threshold_topk) instead of scatter; "
                 "if the scatter is provably amortized, baseline the "
                 "finding with a reason"))
    return findings


# --------------------------------------------------------------------------
# JX502 — float64 leak


@rule("JX502", "float64 leak in a compiled segment", "B",
      "f64 halves vector throughput on TPU (and silently doubles "
      "buffer bytes); device programs are int/f32 by contract — an f64 "
      "aval usually means a Python float or np.float64 leaked into the "
      "trace")
def f64_rule(ctx: AnalysisContext) -> List[Finding]:
    jax = _require_jax()
    import numpy as np
    findings: List[Finding] = []
    for entry in _entries():
        closed = _trace_jaxpr(jax, entry)
        if closed is None:
            continue
        hit = sorted({str(getattr(a, "dtype", ""))
                      for a in _all_avals(closed)
                      if getattr(a, "dtype", None) == np.float64})
        if not hit:
            continue
        file, line = _entry_location(ctx, entry)
        findings.append(Finding(
            rule="JX502", file=file, line=line,
            symbol=f"{entry.scope}:float64",
            message=f"program '{entry.scope}' carries float64 values",
            hint="pin the accumulator dtype (jnp.float32 / int64) at "
                 "the leak site; if f64 is required for exactness, "
                 "baseline with a reason"))
    return findings


# --------------------------------------------------------------------------
# JX503 — large outputs without donation aliasing


def _aval_bytes(aval) -> int:
    try:
        import numpy as np
        n = 1
        for d in aval.shape:
            n *= int(d)
        return n * np.dtype(aval.dtype).itemsize
    except Exception:
        return 0


@rule("JX503", "large output buffer without donation", "B",
      "a program whose outputs are large and shape-match an input "
      "should donate (donate_argnums) so XLA reuses the input buffer "
      "in place of a fresh HBM allocation per dispatch")
def donation_rule(ctx: AnalysisContext) -> List[Finding]:
    jax = _require_jax()
    findings: List[Finding] = []
    for entry in _entries():
        lower = getattr(entry.fn, "lower", None)
        if lower is None:
            continue
        try:
            lowered = lower(*entry.abstract_args, **entry.abstract_kwargs)
            text = lowered.as_text()
        except Exception:
            continue
        # donation shows as input_output_alias once compiled, or as the
        # tf.aliasing_output arg attribute in StableHLO (what lower()
        # emits on the CPU rung, where XLA ignores the donation but the
        # intent is still declared)
        if "input_output_alias" in text or "aliasing_output" in text:
            continue
        closed = _trace_jaxpr(jax, entry)
        if closed is None:
            continue
        out_avals = [getattr(v, "aval", None)
                     for v in closed.jaxpr.outvars]
        out_bytes = sum(_aval_bytes(a) for a in out_avals if a is not None)
        if out_bytes < ctx.settings.donation_min_bytes:
            continue
        in_sigs = {(tuple(a.shape), str(a.dtype))
                   for a in (getattr(v, "aval", None)
                             for v in closed.jaxpr.invars)
                   if a is not None and getattr(a, "shape", None)
                   is not None}
        matched = any(
            a is not None and getattr(a, "shape", None) is not None
            and (tuple(a.shape), str(a.dtype)) in in_sigs
            for a in out_avals)
        if not matched:
            continue
        file, line = _entry_location(ctx, entry)
        findings.append(Finding(
            rule="JX503", file=file, line=line,
            symbol=f"{entry.scope}:no-donation",
            message=f"program '{entry.scope}' returns "
                    f"{out_bytes >> 20} MiB with a shape-matched input "
                    "but no input_output_alias",
            hint="add donate_argnums for the state buffers the program "
                 "consumes-and-replaces; baseline with a reason if the "
                 "input must stay live"))
    return findings


# --------------------------------------------------------------------------
# JX504 — value-derived cache keys (recompile hazard)


def _array_signature(jax, entry) -> str:
    """Shape/dtype-only signature of the recorded dispatch: non-array
    leaves are EXCLUDED so that two builds differing only in a scalar
    value (or in builder args) but identical in buffer shapes collide —
    which is exactly the recompile hazard."""
    leaves = jax.tree_util.tree_leaves((entry.abstract_args,
                                        entry.abstract_kwargs))
    sig = []
    for leaf in leaves:
        shape = getattr(leaf, "shape", None)
        dtype = getattr(leaf, "dtype", None)
        if shape is not None and dtype is not None:
            sig.append((tuple(shape), str(dtype)))
    return repr(sig)


@rule("JX504", "cache key derived from values, not shapes", "B",
      "two builds of the same scope with identical buffer shapes/dtypes "
      "mean the builder's cache key varies with a VALUE — every new "
      "value pays a fresh compile (seconds to a minute on the chip) "
      "instead of a cache hit; recompiles==0 in steady state is the "
      "core perf contract")
def recompile_hazard_rule(ctx: AnalysisContext) -> List[Finding]:
    jax = _require_jax()
    findings: List[Finding] = []
    by_scope_sig: Dict[Tuple[str, str], list] = {}
    for entry in _entries():
        by_scope_sig.setdefault(
            (entry.scope, _array_signature(jax, entry)), []).append(entry)
    for (scope, _sig), group in sorted(by_scope_sig.items()):
        keys = {e.build_key for e in group}
        if len(group) < 2 or len(keys) < 2:
            continue
        file, line = _entry_location(ctx, group[0])
        findings.append(Finding(
            rule="JX504", file=file, line=line,
            symbol=f"{scope}:value-keyed",
            message=f"scope '{scope}' compiled {len(group)} programs "
                    "with identical array shapes/dtypes but different "
                    "builder keys — the cache key depends on values",
            hint="key the builder on shape/dtype/config only; pass "
                 "per-batch values as traced arguments"))
    return findings


# --------------------------------------------------------------------------
# JX505 — sharded (mesh) programs must be keyed by LOCAL shard shapes


_MESH_SCOPE_PREFIX = "mesh."
# repr((args, kwargs)) of a builder whose first argument is the canonical
# local_signature tuple — see parallel/sharded_window.local_signature
_LOCAL_KEY_PREFIX = "((('local',"


@rule("JX505", "sharded program keyed by non-local shapes", "B",
      "every 'mesh.*' program builder must be keyed by the local-shard "
      "signature (parallel/sharded_window.local_signature: schema + "
      "per-device dims; the local leaves it determines are table "
      "[1, capacity] int64, accs [1, ring, capacity] per dtype, a 64-bit "
      "integer one as two uint32 words, dropped [1] int64) and NEVER by "
      "the device count or a global "
      "[D, ...] shape — a global-keyed builder compiles a different "
      "program per mesh size, so a live rescale that preserves local "
      "shard shapes pays a recompile instead of a cache hit "
      "(recompiles==0 across rescale is the PR 12 contract)")
def mesh_local_key_rule(ctx: AnalysisContext) -> List[Finding]:
    jax = _require_jax()
    entries = [e for e in _entries()
               if e.scope.startswith(_MESH_SCOPE_PREFIX)]
    if not entries:
        skip_rule("no 'mesh.*' programs registered — run a sharded "
                  "pipeline or exercise_programs() first")
    findings: List[Finding] = []
    for entry in entries:
        file, line = _entry_location(ctx, entry)
        if not entry.build_key.startswith(_LOCAL_KEY_PREFIX):
            findings.append(Finding(
                rule="JX505", file=file, line=line,
                symbol=f"{entry.scope}:not-local-keyed",
                message=f"mesh program '{entry.scope}' build key "
                        f"{entry.build_key[:80]!r} is not derived from "
                        "local_signature (missing the 'local' marker as "
                        "its first builder argument)",
                hint="key the builder on local_signature(aggs, capacity, "
                     "ring) + static config; bind the concrete Mesh "
                     "inside the cache entry (see _step_program)"))
            continue
        # a global dispatch shape leaking into the key: any [D, ...] aval
        # of the recorded dispatch appearing verbatim means the key varies
        # with the mesh size (local keys carry dims, never shape tuples)
        leaked = set()
        for leaf in jax.tree_util.tree_leaves((entry.abstract_args,
                                               entry.abstract_kwargs)):
            shape = getattr(leaf, "shape", None)
            if (shape is not None and getattr(leaf, "dtype", None)
                    is not None and len(shape) >= 2):
                if repr(tuple(int(d) for d in shape)) in entry.build_key:
                    leaked.add(tuple(int(d) for d in shape))
        if leaked:
            findings.append(Finding(
                rule="JX505", file=file, line=line,
                symbol=f"{entry.scope}:global-shape-keyed",
                message=f"mesh program '{entry.scope}' build key embeds "
                        f"global dispatch shape(s) "
                        f"{sorted(leaked)} — the key varies with the "
                        "device count",
                hint="derive the key from per-device shard dims only; "
                     "global [D, ...] shapes belong to the traced "
                     "arguments, not the cache key"))
    return findings


# --------------------------------------------------------------------------
# JX6xx — fused-chain program audit (the fusion certifier's runtime half:
# graph/fusion.py certifies the plan, these rules lock the programs the
# lowering actually built; scopes are "chain.fused_prelude" — the
# source-decode + pure stages — and "chain.fused_step" — prelude + the
# donated window step, the one dispatch per micro-batch)


_CHAIN_PRELUDE_SCOPE = "chain.fused_prelude"
_CHAIN_STEP_SCOPE = "chain.fused_step"


def _chain_entries(prefix: str):
    entries = [e for e in _entries() if e.scope.startswith(prefix)]
    if not entries:
        skip_rule(f"no '{prefix}' programs registered — run a fused "
                  "pipeline (pipeline.fusion.enabled) first")
    return entries


@rule("JX601", "fused chain prelude must be scatter-free", "B",
      "the certified source-decode -> filter/map stages of a fused "
      "chain run once per micro-batch ahead of the window step; a "
      "scatter there lowers to a serial loop on the CPU rung and "
      "forfeits the fusion win (the window fold's own scatters are "
      "governed separately by the fire-path rule)")
def chain_scatter_rule(ctx: AnalysisContext) -> List[Finding]:
    jax = _require_jax()
    findings: List[Finding] = []
    for entry in _chain_entries(_CHAIN_PRELUDE_SCOPE):
        closed = _trace_jaxpr(jax, entry)
        if closed is None:
            continue
        prims = sorted({eqn.primitive.name
                        for eqn in _iter_eqns(closed.jaxpr)
                        if eqn.primitive.name.startswith("scatter")})
        if not prims:
            continue
        file, line = _entry_location(ctx, entry)
        findings.append(Finding(
            rule="JX601", file=file, line=line,
            symbol=f"{entry.scope}:{'+'.join(prims)}",
            message=f"fused chain prelude '{entry.scope}' lowers "
                    f"{', '.join(prims)}",
            hint="express the stage with gathers/masks/segment ops; a "
                 "stage that genuinely needs scatter is not certifiable "
                 "as part of the prelude"))
    return findings


@rule("JX602", "donation must thread through the fused chain", "B",
      "the fused step consumes-and-replaces the window state planes; "
      "without input_output_alias every micro-batch allocates a fresh "
      "copy of the whole table, so donation is mandatory for chain "
      "step programs regardless of size")
def chain_donation_rule(ctx: AnalysisContext) -> List[Finding]:
    _require_jax()
    findings: List[Finding] = []
    for entry in _chain_entries(_CHAIN_STEP_SCOPE):
        lower = getattr(entry.fn, "lower", None)
        text = ""
        if lower is not None:
            try:
                text = lower(*entry.abstract_args,
                             **entry.abstract_kwargs).as_text()
            except Exception:
                continue
        if "input_output_alias" in text or "aliasing_output" in text:
            continue
        file, line = _entry_location(ctx, entry)
        findings.append(Finding(
            rule="JX602", file=file, line=line,
            symbol=f"{entry.scope}:no-donation",
            message=f"fused chain step '{entry.scope}' declares no "
                    "buffer donation: state planes are copied every "
                    "micro-batch",
            hint="thread donate_argnums through the composed program for "
                 "the table and every accumulator plane"))
    return findings


@rule("JX603", "fused chain cache key must be shape-only", "B",
      "a fused chain program is rebuilt per (shapes, dtypes) bucket "
      "only; any value or identity (closure id, start index, batch "
      "number) in the cache key means a recompile per micro-batch — "
      "the exact failure the certifier exists to prevent")
def chain_cache_key_rule(ctx: AnalysisContext) -> List[Finding]:
    jax = _require_jax()
    findings: List[Finding] = []
    entries = _chain_entries("chain.")
    for entry in entries:
        expected = _array_signature(jax, entry)
        if entry.build_key == expected:
            continue
        file, line = _entry_location(ctx, entry)
        findings.append(Finding(
            rule="JX603", file=file, line=line,
            symbol=f"{entry.scope}:value-keyed",
            message=f"chain program '{entry.scope}' build key "
                    f"{entry.build_key!r} is not the canonical "
                    "shape/dtype signature of its dispatch",
            hint="derive the key with runtime.compiled.shape_key(...) "
                 "from the traced arguments only"))
    by_scope_sig: Dict[Tuple[str, str], list] = {}
    for entry in entries:
        by_scope_sig.setdefault(
            (entry.scope, _array_signature(jax, entry)), []).append(entry)
    for (scope, _sig), group in sorted(by_scope_sig.items()):
        if len(group) < 2 or len({e.build_key for e in group}) < 2:
            continue
        file, line = _entry_location(ctx, group[0])
        findings.append(Finding(
            rule="JX603", file=file, line=line,
            symbol=f"{scope}:key-collision",
            message=f"chain scope '{scope}' compiled {len(group)} "
                    "programs with identical array signatures but "
                    "different build keys",
            hint="derive the key with runtime.compiled.shape_key(...) "
                 "from the traced arguments only"))
    return findings


# --------------------------------------------------------------------------
# Exercise: populate PROGRAM_AUDIT with a tiny Q5-shaped pipeline


def exercise_programs(n_events: int = 4096, batch: int = 1024,
                      capacity: int = 2048) -> List[str]:
    """Run a tiny Q5 sliding-window job (per ingest path) so every
    window-path builder registers its compiled programs in
    PROGRAM_AUDIT; returns the registered scopes.  Mirrors bench.py
    _run_q5 at toy scale — same operators, same program builders.

    The device-time ledger records through the same runs (restored to
    its prior enablement on return), so the audit doubles as a drill of
    every ledger-wrapped dispatch site: the TPU305 inventory can be
    checked against scopes that actually fired, not just grep hits."""
    import numpy as np

    from flink_tpu.api import StreamExecutionEnvironment
    from flink_tpu.core import WatermarkStrategy
    from flink_tpu.core.config import PipelineOptions
    from flink_tpu.metrics.device import PROGRAM_AUDIT
    from flink_tpu.metrics.profiler import DEVICE_LEDGER
    from flink_tpu.core.records import Schema
    from flink_tpu.runtime.operators.device_window import AggSpec
    from flink_tpu.window import SlidingEventTimeWindows

    ledger_was_enabled = DEVICE_LEDGER.enabled
    DEVICE_LEDGER.enabled = True
    try:
        schema = Schema([("auction", np.int64), ("price", np.int64),
                         ("ts", np.int64)])
        pane_ms = 2000
        n_panes = max(2, n_events // batch)
        span = n_panes * pane_ms

        def gen(idx):
            u = idx.astype(np.uint64)
            return {"auction": ((u * np.uint64(2654435761)) % np.uint64(64))
                    .astype(np.int64),
                    "price": (idx % 97) + 1,
                    "ts": (idx * span) // n_events}

        from flink_tpu.core.functions import SinkFunction

        class _DiscardSink(SinkFunction):
            def invoke_batch(self, batch):
                return True

        # (device_ingest, fused): device ingest exercises the
        # one-dispatch step program, host ingest the packed upload with
        # its probe and fold programs, and the fused run registers the
        # certified chain programs
        # (chain.fused_prelude / chain.fused_step) for JX601-603.
        for device_ingest, fused in ((True, False), (False, False),
                                     (True, True)):
            env = StreamExecutionEnvironment.get_execution_environment()
            env.set_state_backend("tpu")
            env.config.set(PipelineOptions.BATCH_SIZE, batch)
            env.config.set(PipelineOptions.FUSION, fused)
            ws = WatermarkStrategy.for_monotonous_timestamps() \
                .with_timestamp_column("ts")
            (env.datagen(gen, schema, count=n_events, timestamp_column="ts",
                         watermark_strategy=ws, device=device_ingest)
                .key_by("auction")
                .window(SlidingEventTimeWindows.of(3 * pane_ms, pane_ms))
                .device_aggregate(
                    [AggSpec("count", out_name="bids", value_bits=31),
                     AggSpec("sum", "price", out_name="revenue")],
                    capacity=capacity, ring_size=16, emit_window_bounds=False,
                    emit_topk=32, defer_overflow=True)
                .add_sink(_DiscardSink(), "audit-sink"))
            env.execute("tpu-lint-audit", timeout=600.0)

        # the session operator's two programs (device_session.step /
        # device_session.fire): the same bids keyed into per-auction
        # SESSION windows of one pane's gap (a session a key: the host
        # runs ahead of the periodic watermark, and a key has eight lanes)
        from flink_tpu.window import EventTimeSessionWindows

        env = StreamExecutionEnvironment.get_execution_environment()
        env.set_state_backend("tpu")
        env.config.set(PipelineOptions.BATCH_SIZE, batch)
        ws = WatermarkStrategy.for_monotonous_timestamps() \
            .with_timestamp_column("ts")
        (env.datagen(gen, schema, count=n_events, timestamp_column="ts",
                     watermark_strategy=ws, device=False)
            .key_by("auction")
            .window(EventTimeSessionWindows.with_gap(pane_ms))
            .device_aggregate([AggSpec("count", out_name="bids")],
                              capacity=capacity, ring_size=8)
            .add_sink(_DiscardSink(), "audit-sink"))
        env.execute("tpu-lint-audit-sessions", timeout=600.0)

        # sharded (mesh.*) programs: one direct step + fused fire on a tiny
        # ShardedWindowAgg so the JX505 local-key audit has entries to lint
        import jax
        import jax.numpy as jnp

        from flink_tpu.parallel.mesh import make_mesh
        from flink_tpu.parallel.sharded_window import AggDef, ShardedWindowAgg

        D = max(1, min(4, len(jax.devices())))
        agg = ShardedWindowAgg(make_mesh(D),
                               [AggDef("price", "sum", jnp.int64)],
                               capacity=256, ring=8, max_parallelism=128)
        state = agg.init_state()
        B = 64
        keys = (jnp.arange(D * B, dtype=jnp.int64) % 37).reshape(D, B) + 1
        state, *_ = agg.step(state, keys,
                            {"price": jnp.ones((D, B), jnp.int64)},
                            jnp.zeros((D, B), jnp.int32),
                            jnp.ones((D, B), bool))
        agg.fire_compact(state, np.arange(4), np.ones(4, bool),
                         "price", 8)
        return sorted({e.scope for e in PROGRAM_AUDIT})
    finally:
        DEVICE_LEDGER.enabled = ledger_was_enabled
