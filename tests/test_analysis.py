"""tpu-lint: the tier-1 lint gate plus seeded regression proofs that
every rule actually fires.

Two families:

* Gate tests (``pytest -m lint``): run the full rule suite against THIS
  tree and fail on any finding the committed baseline does not cover —
  the mechanical form of "the device-path invariants hold".
* Seeded tests: synthetic mini-packages (tmp_path) with one injected
  violation each — a ``float(device_val)`` in a hot path, a deploy path
  missing a singleton, a config-key typo, an un-locked mutation, a
  scatter-bearing / f64 / undonated / value-keyed program — proving
  each rule detects its violation with the right rule id and file:line.
"""

import json
import textwrap

import pytest

from flink_tpu.analysis import (
    AnalysisContext,
    all_rules,
    diff_against_baseline,
    load_baseline,
    run_rules,
)
from flink_tpu.analysis.core import AnalysisSettings, Finding

pytestmark = pytest.mark.lint

TIER_A = sorted(r for r, rr in all_rules().items() if rr.tier == "A")
TIER_B = sorted(r for r, rr in all_rules().items() if rr.tier == "B")


def _fmt(findings):
    return "\n".join(f"{f.rule} {f.location()} {f.symbol}: {f.message}"
                     for f in findings)


# ---------------------------------------------------------------------------
# The gate: this tree must lint clean against the committed baseline


def test_tier_a_clean_against_baseline():
    """Any unbaselined Tier-A finding (host-sync, wiring, inventory
    drift, lock discipline, determinism) fails tier-1 right here."""
    findings = run_rules(AnalysisContext(), TIER_A)
    new, stale = diff_against_baseline(findings, rules=TIER_A)
    assert not new, f"unbaselined findings:\n{_fmt(new)}"
    assert not stale, f"stale baseline entries (fixed? shrink the " \
                      f"baseline): {stale}"


def test_baseline_entries_carry_reviewed_reasons():
    """The committed baseline may hold only justified exceptions."""
    for e in load_baseline():
        assert e.get("reason") and "TODO" not in e["reason"], (
            f"baseline entry without a reviewed reason: {e}")


def _lint_in_a_fresh_process(*args):
    """`python -m flink_tpu.cli lint` in a process of its own. The
    program audit is process-wide, so in this one it also holds whatever
    the test files that ran earlier on this worker built (two mesh
    aggregates under different builder keys, a float64 job), and which
    files those are changes with the suite's order; the gate is about
    the programs of the tiny Q5 the lint itself exercises."""
    import os
    import subprocess
    import sys

    return subprocess.run(
        [sys.executable, "-m", "flink_tpu.cli", "lint", *args],
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        capture_output=True, text=True, timeout=600)


def test_tier_b_clean_on_tiny_q5():
    """Exercise a tiny Q5-shaped pipeline and audit every compiled
    program it registered: scatter on the fire path, f64 leaks, missing
    donation, and value-derived cache keys must all be absent (or
    baselined)."""
    pytest.importorskip("jax")
    done = _lint_in_a_fresh_process("--rules", ",".join(TIER_B), "--json")
    report = json.loads(done.stdout[done.stdout.index("{"):])
    assert not report["skipped"], f"tier-B rules skipped: {report['skipped']}"
    new = [f for f in report["findings"]
           if f["fingerprint"] in report["new"]]
    assert not new, f"unbaselined program findings:\n{new}"


def test_cli_lint_exits_zero_on_committed_tree():
    """Acceptance: `python -m flink_tpu.cli lint` (all rules) exits 0."""
    pytest.importorskip("jax")
    done = _lint_in_a_fresh_process()
    assert done.returncode == 0, f"cli lint failed:\n{done.stdout}"
    assert "0 new" in done.stdout


def test_cli_lint_unknown_rule_is_usage_error(capsys):
    from flink_tpu.cli import main
    assert main(["lint", "--rules", "TPU999"]) == 2


def test_cli_lint_json_shape(capsys):
    from flink_tpu.cli import main
    rc = main(["lint", "--rules", "TPU501", "--json"])
    data = json.loads(capsys.readouterr().out)
    assert rc == 0
    assert set(data) == {"findings", "new", "stale_baseline", "skipped"}


# ---------------------------------------------------------------------------
# Seeded regressions: each rule fires on an injected violation


def _mini_pkg(tmp_path, files: dict, **settings_overrides):
    """Build a throwaway package tree and a context pointing at it."""
    root = tmp_path / "repo"
    pkg = root / "pkg"
    pkg.mkdir(parents=True)
    (pkg / "__init__.py").write_text("")
    for rel, src in files.items():
        p = pkg / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        if not (p.parent / "__init__.py").exists():
            (p.parent / "__init__.py").write_text("")
        p.write_text(textwrap.dedent(src))
    settings = AnalysisSettings(**settings_overrides)
    return AnalysisContext(package_root=root, package_name="pkg",
                           settings=settings, extra_files=())


def test_seeded_host_sync_detected(tmp_path):
    """An injected float(device_val) in a hot-path module is flagged
    with rule TPU101 at the exact line; the same call under a reasoned
    sync-ok annotation is not."""
    ctx = _mini_pkg(tmp_path, {
        "hot.py": """\
            import jax

            def bad(self):
                return float(self._acc_dev)           # line 4

            def also_bad(x_dev):
                return x_dev.item()

            def fine(x_dev):
                # lint: sync-ok amortized once per fire
                return float(x_dev)

            def host_only(ts):
                return int(ts.min())
            """,
    }, hot_path_modules=("hot.py",))
    findings = run_rules(ctx, ["TPU101"])
    assert [(f.rule, f.file, f.line) for f in findings] == [
        ("TPU101", "pkg/hot.py", 4), ("TPU101", "pkg/hot.py", 7)]
    assert "float()" in findings[0].message


def test_seeded_missing_singleton_detected(tmp_path):
    """A deploy entry point that wires FAULTS but never TRACER is
    flagged with rule TPU201 naming the missing singleton — including
    when the configure call hides one level down the call graph."""
    ctx = _mini_pkg(tmp_path, {
        "deploy.py": """\
            from .wiring import wire_faults

            def launch(config):
                wire_faults(config)
                return object()
            """,
        "wiring.py": """\
            FAULTS = object()
            TRACER = object()

            def wire_faults(config):
                FAULTS.configure(config)
            """,
    }, entry_points=(("deploy.py", "launch"),),
       singletons=(("FAULTS", ("FAULTS",)), ("TRACER", ("TRACER",))))
    findings = run_rules(ctx, ["TPU201"])
    assert len(findings) == 1
    f = findings[0]
    assert (f.rule, f.file, f.symbol) == (
        "TPU201", "pkg/deploy.py", "launch:TRACER")
    assert f.line == 3  # anchored at the entry point def


def test_seeded_config_key_typo_detected(tmp_path):
    """A literal that looks like a config key of a real family but is
    not declared (a typo) is flagged with rule TPU304."""
    ctx = _mini_pkg(tmp_path, {
        "uses.py": """\
            def f(config):
                return config.get("checkpoint.intervall")  # typo, line 2
            """,
    })
    findings = run_rules(ctx, ["TPU304"])
    assert [(f.rule, f.file, f.line) for f in findings] == [
        ("TPU304", "pkg/uses.py", 2)]
    assert "checkpoint.intervall" in findings[0].message


def test_seeded_rogue_ledger_site_detected(tmp_path):
    """A DEVICE_LEDGER.record / instrumented_program_cache site literal
    that is not in LEDGER_SITE_INVENTORY is flagged with rule TPU305 at
    the recording line; inventoried sites are not (the mini package
    still yields inventoried-not-in-code noise for the real inventory,
    so assert membership, not the exact finding list)."""
    ctx = _mini_pkg(tmp_path, {
        "disp.py": """\
            from .led import DEVICE_LEDGER, instrumented_program_cache

            def fire(ms):
                DEVICE_LEDGER.record("mesh.rogue_site", ms)   # line 4

            build = instrumented_program_cache(
                "device_window.step")
            """,
    })
    findings = run_rules(ctx, ["TPU305"])
    flagged = {(f.symbol, f.file, f.line) for f in findings}
    assert ("code-not-inventoried:mesh.rogue_site",
            "pkg/disp.py", 4) in flagged
    # the inventoried site used by the mini package is clean, and every
    # other inventory row is reported as missing from this package
    symbols = {f.symbol for f in findings}
    assert "code-not-inventoried:device_window.step" not in symbols
    assert "inventoried-not-in-code:mesh.step" in symbols


def test_sched_inventory_rows_locked(tmp_path):
    """The isolation scheduler's observability contract is inventoried:
    its chaos sites (sched.admit / sched.shed) are declared FAULT_SITES
    members, its spans (sched/Admit, sched/Shed) are in SPAN_INVENTORY,
    and its ledger site (sched.throttle) is in LEDGER_SITE_INVENTORY — a
    mini package exercising all of them draws no undeclared/rogue
    findings, while lookalike rogues at the same scopes still do."""
    ctx = _mini_pkg(tmp_path, {
        "gate.py": """\
            from .wiring import DEVICE_LEDGER, FAULTS, TRACER

            def gate(job, waited):
                FAULTS.fire("sched.admit")
                if FAULTS.check("sched.shed"):
                    TRACER.span("sched", "Shed").finish()
                    return "shed"
                DEVICE_LEDGER.record("sched.throttle", waited * 1e3)
                TRACER.span("sched", "Admit").finish()
                return "admit"

            def rogue(ms):
                FAULTS.fire("sched.evict")                # line 13
                TRACER.span("sched", "Starve").finish()
                DEVICE_LEDGER.record("sched.rogue", ms)
            """,
    })
    f301 = {f.symbol for f in run_rules(ctx, ["TPU301"])}
    f302 = {f.symbol for f in run_rules(ctx, ["TPU302"])}
    f305 = {f.symbol for f in run_rules(ctx, ["TPU305"])}
    for sym in ("code-not-inventoried:sched.Admit",
                "code-not-inventoried:sched.Shed"):
        assert sym not in f301, f"{sym}: SPAN_INVENTORY row went missing"
    for sym in ("undeclared-site:sched.admit",
                "undeclared-site:sched.shed"):
        assert sym not in f302, f"{sym}: FAULT_SITES member went missing"
    assert "code-not-inventoried:sched.throttle" not in f305, \
        "sched.throttle: LEDGER_SITE_INVENTORY row went missing"
    # the lock still bites on undeclared lookalikes
    assert "code-not-inventoried:sched.Starve" in f301
    assert "undeclared-site:sched.evict" in f302
    assert "code-not-inventoried:sched.rogue" in f305


def test_failover_inventory_rows_locked(tmp_path):
    """The coordinator-failover observability contract is inventoried:
    its chaos sites (coord.crash / ha.lease) are declared FAULT_SITES
    members and its span (ha/Takeover) is in SPAN_INVENTORY — a mini
    package exercising them draws no undeclared/rogue findings, while
    lookalike rogues at the same scopes still do."""
    ctx = _mini_pkg(tmp_path, {
        "coord.py": """\
            from .wiring import FAULTS, TRACER

            def monitor(self):
                if FAULTS.check("coord.crash"):
                    return "crashed"
                TRACER.span("ha", "Takeover").finish()
                return "leading"

            def renew(self):
                if FAULTS.check("ha.lease"):
                    return False
                return True

            def rogue(self):
                FAULTS.fire("coord.split-brain")          # line 15
                TRACER.span("ha", "Abdicate").finish()
            """,
    })
    f301 = {f.symbol for f in run_rules(ctx, ["TPU301"])}
    f302 = {f.symbol for f in run_rules(ctx, ["TPU302"])}
    assert "code-not-inventoried:ha.Takeover" not in f301, \
        "ha/Takeover: SPAN_INVENTORY row went missing"
    for sym in ("undeclared-site:coord.crash",
                "undeclared-site:ha.lease"):
        assert sym not in f302, f"{sym}: FAULT_SITES member went missing"
    # the lock still bites on undeclared lookalikes
    assert "code-not-inventoried:ha.Abdicate" in f301
    assert "undeclared-site:coord.split-brain" in f302


def test_seeded_unlocked_mutation_detected(tmp_path):
    """A class that guards an attribute under self._lock in one method
    but mutates it bare in another is flagged with rule TPU401."""
    ctx = _mini_pkg(tmp_path, {
        "locked.py": """\
            import threading

            class Counter:
                def __init__(self):
                    self._lock = threading.Lock()
                    self.total = 0

                def add(self, n):
                    with self._lock:
                        self.total += n

                def reset(self):
                    self.total = 0                    # line 13: un-locked

                def _drain_locked(self):
                    self.total = 0                    # _locked convention
            """,
    })
    findings = run_rules(ctx, ["TPU401"])
    assert [(f.rule, f.file, f.line) for f in findings] == [
        ("TPU401", "pkg/locked.py", 13)]
    assert findings[0].symbol == "Counter.reset:total"


def test_seeded_unguarded_global_detected(tmp_path):
    ctx = _mini_pkg(tmp_path, {
        "events.py": """\
            EVENTS = []
            GUARDED = []  # lint: guarded-by appended under EV_LOCK only

            def record(e):
                EVENTS.append(e)
                GUARDED.append(e)

            def clear():
                EVENTS.clear()
                GUARDED.clear()
            """,
    })
    findings = run_rules(ctx, ["TPU402"])
    assert [(f.rule, f.symbol, f.line) for f in findings] == [
        ("TPU402", "EVENTS", 1)]


def test_seeded_wall_clock_and_rng_detected(tmp_path):
    ctx = _mini_pkg(tmp_path, {
        "metrics/tracing.py": """\
            import time

            def stamp():
                return time.time()                    # line 4
            """,
        "runtime/jitter.py": """\
            import random

            def backoff():
                return random.random()                # line 4
            """,
    }, span_clock_modules=("metrics/tracing.py",),
       runtime_rng_prefixes=("runtime/",))
    clock = run_rules(ctx, ["TPU501"])
    rng = run_rules(ctx, ["TPU502"])
    assert [(f.rule, f.file, f.line) for f in clock] == [
        ("TPU501", "pkg/metrics/tracing.py", 4)]
    assert [(f.rule, f.file, f.line) for f in rng] == [
        ("TPU502", "pkg/runtime/jitter.py", 4)]


# ---------------------------------------------------------------------------
# Seeded Tier-B regressions: scatter / f64 / donation / value-keyed


@pytest.fixture
def _audit_registry():
    """Snapshot + restore the process-global program-audit registry so
    seeded entries never leak into the gate tests (and vice versa)."""
    pytest.importorskip("jax")
    from flink_tpu.metrics.device import PROGRAM_AUDIT
    saved = list(PROGRAM_AUDIT)
    PROGRAM_AUDIT.clear()
    yield PROGRAM_AUDIT
    PROGRAM_AUDIT[:] = saved


def _seed_program(registry, scope, fn, *abstract_args, build_key="k"):
    from flink_tpu.metrics.device import ProgramAuditEntry
    registry.append(ProgramAuditEntry(
        scope, fn, tuple(abstract_args), {}, build_key,
        ("/nowhere/seeded.py", 1)))


def test_seeded_scatter_and_f64_programs_detected(_audit_registry):
    """A scatter-bearing fire-path program and an f64-carrying program
    are each detected with the right rule id (JX501 / JX502)."""
    import jax
    import jax.numpy as jnp
    jax.config.update("jax_enable_x64", True)

    scatterer = jax.jit(lambda x, i: x.at[i].add(1.0))
    _seed_program(_audit_registry, "seeded.fire", scatterer,
                  jax.ShapeDtypeStruct((128,), jnp.float32),
                  jax.ShapeDtypeStruct((8,), jnp.int32))
    doubler64 = jax.jit(lambda x: x * 2)
    _seed_program(_audit_registry, "seeded.step", doubler64,
                  jax.ShapeDtypeStruct((16,), jnp.float64))

    scatter = run_rules(AnalysisContext(), ["JX501"])
    f64 = run_rules(AnalysisContext(), ["JX502"])
    assert [(f.rule, f.symbol.split(":")[0]) for f in scatter] == [
        ("JX501", "seeded.fire")]
    assert "scatter" in scatter[0].symbol
    assert [(f.rule, f.symbol) for f in f64] == [
        ("JX502", "seeded.step:float64")]


def test_seeded_undonated_large_output_detected(_audit_registry):
    import jax
    import jax.numpy as jnp

    grow = jax.jit(lambda state, d: (state + d, state.sum()))
    big = jax.ShapeDtypeStruct((1024, 1024), jnp.float32)  # 4 MiB
    _seed_program(_audit_registry, "seeded.step", grow, big, big)
    findings = run_rules(AnalysisContext(), ["JX503"])
    assert [(f.rule, f.symbol) for f in findings] == [
        ("JX503", "seeded.step:no-donation")]

    # the donated twin is clean
    _audit_registry.clear()
    donated = jax.jit(lambda state, d: (state + d, state.sum()),
                      donate_argnums=(0,))
    _seed_program(_audit_registry, "seeded.step", donated, big, big)
    assert run_rules(AnalysisContext(), ["JX503"]) == []


def test_seeded_value_keyed_cache_detected(_audit_registry):
    """Two builds of one scope with identical array signatures but
    different builder keys = a cache key derived from values (JX504)."""
    import jax
    import jax.numpy as jnp

    sds = jax.ShapeDtypeStruct((64,), jnp.float32)
    for key in ("boundary=1000", "boundary=2000"):
        _seed_program(_audit_registry, "seeded.step",
                      jax.jit(lambda x: x + 1), sds, build_key=key)
    findings = run_rules(AnalysisContext(), ["JX504"])
    assert [(f.rule, f.symbol) for f in findings] == [
        ("JX504", "seeded.step:value-keyed")]


def test_seeded_mesh_nonlocal_keys_detected(_audit_registry):
    """JX505: a mesh-scoped program whose build key is not the local
    signature, and one whose key embeds a global [D, ...] dispatch shape;
    the local-signature-keyed twin is clean."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: x + 1)
    sds = jax.ShapeDtypeStruct((8, 64), jnp.float32)  # a [D, B] dispatch
    _seed_program(_audit_registry, "mesh.badkey", f, sds,
                  build_key="((8, 64), 128)")
    _seed_program(_audit_registry, "mesh.badshape", f, sds,
                  build_key="((('local', ()), '(8, 64)'), ())")
    findings = run_rules(AnalysisContext(), ["JX505"])
    assert {(x.rule, x.symbol) for x in findings} == {
        ("JX505", "mesh.badkey:not-local-keyed"),
        ("JX505", "mesh.badshape:global-shape-keyed")}

    _audit_registry.clear()
    _seed_program(
        _audit_registry, "mesh.step", f, sds,
        build_key="((('local', (('price', 'sum', 'int64'),), 256, 8), "
                  "128, 'data'), ())")
    assert run_rules(AnalysisContext(), ["JX505"]) == []


def test_real_mesh_programs_are_local_keyed(_audit_registry):
    """The shipped sharded-window builders pass JX505 when exercised on a
    real (virtual) mesh — the contract live rescale depends on."""
    import jax
    import jax.numpy as jnp
    from flink_tpu.parallel import AggDef, ShardedWindowAgg, make_mesh
    jax.config.update("jax_enable_x64", True)

    D = max(1, min(4, len(jax.devices())))
    # a signature no other test builds, so the program caches MISS and
    # fresh audit entries land in the cleared registry
    agg = ShardedWindowAgg(make_mesh(D), [AggDef("price", "sum", jnp.int64)],
                           capacity=512, ring=4, max_parallelism=128)
    state = agg.init_state()
    B = 64
    keys = (jnp.arange(D * B, dtype=jnp.int64) % 37).reshape(D, B) + 1
    agg.step(state, keys, {"price": jnp.ones((D, B), jnp.int64)},
             jnp.zeros((D, B), jnp.int32), jnp.ones((D, B), bool))
    assert any(e.scope.startswith("mesh.") for e in _audit_registry)
    assert run_rules(AnalysisContext(), ["JX505"]) == []


def test_seeded_undeclared_collective_axis_detected(tmp_path):
    """TPU102: collectives naming an axis outside DECLARED_AXES are
    flagged; the declared-axis and threaded-axis_name forms, plus a
    reasoned 'axis-ok' suppression, are clean."""
    ctx = _mini_pkg(tmp_path, {
        "parallel/mesh.py": 'DATA_AXIS = "data"\n',
        "parallel/plan.py": ('from .mesh import DATA_AXIS\n'
                             'DECLARED_AXES = (DATA_AXIS,)\n'),
        "hot.py": '''
            import jax
            from jax import lax

            def good(x, axis_name):
                a = jax.lax.psum(x, "data")
                b = lax.all_to_all(x, axis_name, split_axis=0,
                                   concat_axis=0)
                return a + b

            def waived(x):
                return jax.lax.pmax(x, "adhoc")  # lint: axis-ok seeded

            def bad(x):
                y = jax.lax.psum(x, "rows")
                i = jax.lax.axis_index("cols")
                return y + i
        ''',
    })
    findings = run_rules(ctx, ["TPU102"])
    assert sorted(f.symbol.split(":")[0] for f in findings) == ["bad", "bad"]
    assert {f.rule for f in findings} == {"TPU102"}


# ---------------------------------------------------------------------------
# Framework mechanics: fingerprints, baseline diff, suppression hygiene


def test_fingerprint_survives_line_shifts():
    a = Finding(rule="TPU101", file="pkg/hot.py", line=10, symbol="f:x",
                message="m")
    b = Finding(rule="TPU101", file="pkg/hot.py", line=99, symbol="f:x",
                message="m")
    assert a.fingerprint == b.fingerprint
    assert a.fingerprint != Finding(rule="TPU102", file="pkg/hot.py",
                                    line=10, symbol="f:x",
                                    message="m").fingerprint


def test_baseline_diff_reports_new_and_stale():
    f_known = Finding(rule="R", file="a.py", line=1, symbol="s1",
                      message="m")
    f_new = Finding(rule="R", file="a.py", line=2, symbol="s2",
                    message="m")
    baseline = [
        {"rule": "R", "file": "a.py", "symbol": "s1",
         "fingerprint": f_known.fingerprint, "reason": "ok"},
        {"rule": "R", "file": "gone.py", "symbol": "dead",
         "fingerprint": "feedfeedfeedfeed", "reason": "ok"},
    ]
    new, stale = diff_against_baseline([f_known, f_new], baseline)
    assert [f.symbol for f in new] == ["s2"]
    assert [e["symbol"] for e in stale] == ["dead"]
    # an entry of a rule that did not run has not gone stale
    _new, stale = diff_against_baseline([f_known, f_new], baseline,
                                        rules=("OTHER",))
    assert stale == []
    _new, stale = diff_against_baseline([f_known, f_new], baseline,
                                        rules=("R",))
    assert [e["symbol"] for e in stale] == ["dead"]


def test_suppression_without_reason_does_not_suppress(tmp_path):
    """`# lint: sync-ok` with no reason is not a suppression — the
    reason is the reviewable record."""
    ctx = _mini_pkg(tmp_path, {
        "hot.py": """\
            def bad(x_dev):
                # lint: sync-ok
                return float(x_dev)
            """,
    }, hot_path_modules=("hot.py",))
    findings = run_rules(ctx, ["TPU101"])
    assert len(findings) == 1 and findings[0].line == 3


def test_every_registered_rule_has_catalogue_entry():
    """docs/ANALYSIS.md documents every rule id (and no phantom ids)."""
    import pathlib
    doc = (pathlib.Path(__file__).parent.parent / "docs" /
           "ANALYSIS.md").read_text()
    for rule_id in all_rules():
        assert f"`{rule_id}`" in doc, f"{rule_id} missing from " \
                                      "docs/ANALYSIS.md"
