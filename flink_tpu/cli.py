"""CLI: run pipelines and inspect savepoints from the command line.

Analog of the reference CliFrontend (flink-clients CliFrontend.java:92):

    python -m flink_tpu.cli run <script.py> [--parallelism N]
                                            [--state-backend NAME]
                                            [--checkpoint-dir DIR]
                                            [--checkpoint-interval SECS]
                                            [--from-savepoint PATH]
    python -m flink_tpu.cli savepoint-info <path>
    python -m flink_tpu.cli version

``run`` executes a user script that builds a pipeline on
StreamExecutionEnvironment.get_default() — the CLI pre-configures that
environment from the flags (parallelism, backend, checkpointing, savepoint
restore), mirroring how the reference CLI injects configuration into the
user program's environment.
"""

from __future__ import annotations

import argparse
import runpy
import sys
from typing import Optional

__all__ = ["main"]


def _cmd_run(args) -> int:
    from .api.environment import StreamExecutionEnvironment
    from .core.config import CheckpointingOptions, StateOptions

    env = StreamExecutionEnvironment.get_default()
    if args.parallelism:
        env.set_parallelism(args.parallelism)
    if args.state_backend:
        env.config.set(StateOptions.BACKEND, args.state_backend)
    if args.checkpoint_dir:
        env.config.set(CheckpointingOptions.DIRECTORY, args.checkpoint_dir)
    if args.checkpoint_interval:
        env.config.set(CheckpointingOptions.INTERVAL,
                       args.checkpoint_interval)
    if args.from_savepoint:
        env.restore_from_savepoint(args.from_savepoint)
    if args.target:
        # submit to a running session cluster instead of running in-process
        env.set_remote_target(args.target)
    try:
        runpy.run_path(args.script, run_name="__main__")
    except SystemExit as e:
        if e.code is None:
            return 0
        if isinstance(e.code, int):
            return e.code
        print(e.code, file=sys.stderr)  # sys.exit("message") idiom
        return 1
    return 0


def _cmd_savepoint_info(args) -> int:
    from .checkpoint.storage import (
        CheckpointNotFoundError, CorruptArtifactError,
    )
    from .state_processor import SavepointReader

    try:
        reader = SavepointReader.read(args.path)
    except CorruptArtifactError as e:
        print(f"savepoint-info: corrupt savepoint artifact at "
              f"{args.path}: {e}", file=sys.stderr)
        return 1
    except (CheckpointNotFoundError, FileNotFoundError, NotADirectoryError):
        print(f"savepoint-info: no savepoint at {args.path}",
              file=sys.stderr)
        return 1
    cp = reader.checkpoint
    print(f"savepoint id={cp.checkpoint_id} "
          f"savepoint={cp.is_savepoint} path={cp.external_path}")
    for vertex in reader.vertices():
        par = cp.vertex_parallelism.get(vertex, "?")
        uid = (cp.vertex_uids or {}).get(vertex, "")
        print(f"  vertex {vertex} parallelism={par} uid={uid}")
        for op_key in reader.operators(vertex).get(vertex, []):
            names = reader.state_names(vertex, op_key)
            print(f"    operator {op_key!r} keyed-states={names}")
    return 0


def _cmd_checkpoint_verify(args) -> int:
    """Offline artifact verification of every retained checkpoint under a
    storage directory (the restore-time verification, runnable before an
    incident): per-checkpoint OK/CORRUPT table from the manifest's chunk
    digests + metadata checksum. Exit code reflects the worst result —
    0 all OK, 1 any CORRUPT, 2 nothing to verify."""
    import os

    from .checkpoint.storage import (
        CheckpointNotFoundError, CorruptArtifactError, FsCheckpointStorage,
        retained_checkpoint_dirs,
    )

    if not os.path.isdir(args.dir):
        print(f"checkpoint-verify: no such directory: {args.dir}",
              file=sys.stderr)
        return 2
    storage = FsCheckpointStorage(args.dir)
    rows, worst = [], 0
    for _cid, path in retained_checkpoint_dirs(args.dir):
        name = os.path.basename(path)
        try:
            info = storage.verify_checkpoint(path)
            detail = f"{info['chunks']} chunks, {info['bytes']} bytes"
            if not info["manifest"]:
                detail += " (legacy: no manifest, deep-verified)"
            rows.append([name, "OK", detail])
        except (CorruptArtifactError, CheckpointNotFoundError) as e:
            rows.append([name, "CORRUPT", str(e)])
            worst = 1
    for name in sorted(os.listdir(args.dir)):
        if ".corrupt" in name and os.path.isdir(
                os.path.join(args.dir, name)):
            rows.append([name, "QUARANTINED", "previously failed "
                                              "verification"])
    # a co-located AOT executable cache (aot.dir pointed under the
    # checkpoint root) is verified in the same sweep
    aot_sub = os.path.join(args.dir, "aot")
    if os.path.isdir(aot_sub):
        from .runtime.aot import verify_aot_cache
        for name, status, detail in verify_aot_cache(aot_sub):
            rows.append([f"aot/{name}", status, detail])
            if status == "CORRUPT":
                worst = 1
    if not rows:
        print(f"no retained checkpoints under {args.dir}")
        return 2
    _print_table(["checkpoint", "status", "detail"], rows, max_rows=10_000)
    return worst


def _cmd_aot_cache(args) -> int:
    """Offline verification of a persistent AOT executable cache
    directory (``aot.dir``): per-artifact OK/CORRUPT/QUARANTINED table
    from the embedded header digests + environment fingerprint. Exit
    code reflects the worst result — 0 all OK, 1 any CORRUPT, 2 nothing
    to verify."""
    import os

    from .runtime.aot import verify_aot_cache

    if not os.path.isdir(args.dir):
        print(f"aot-cache: no such directory: {args.dir}", file=sys.stderr)
        return 2
    rows = [list(r) for r in verify_aot_cache(args.dir)]
    if not rows:
        print(f"no AOT artifacts under {args.dir}")
        return 2
    _print_table(["artifact", "status", "detail"], rows, max_rows=10_000)
    return 1 if any(r[1] == "CORRUPT" for r in rows) else 0


def _cmd_list(args) -> int:
    from .cluster.dispatcher import ClusterClient

    for job in ClusterClient(args.target).list_jobs():
        print(f"{job['job_id']}  {job['state']:<10} {job['name']}")
    return 0


def _cmd_cancel(args) -> int:
    from .cluster.dispatcher import ClusterClient

    ClusterClient(args.target).cancel(args.job_id)
    print(f"cancelled {args.job_id}")
    return 0


def _cmd_savepoint(args) -> int:
    from .cluster.dispatcher import ClusterClient

    sp = ClusterClient(args.target).trigger_savepoint(args.job_id)
    print(f"savepoint {sp['id']} path={sp.get('external_path')}")
    return 0


def _split_statements(text: str) -> list[str]:
    """Split on ';' OUTSIDE single-quoted SQL string literals ('' escapes
    a quote inside a literal). Returns complete statements; a trailing
    unterminated fragment is returned last un-split."""
    out, buf, in_str = [], [], False
    i = 0
    while i < len(text):
        ch = text[i]
        if in_str:
            if ch == "'":
                if i + 1 < len(text) and text[i + 1] == "'":
                    buf.append("''")
                    i += 2
                    continue
                in_str = False
            buf.append(ch)
        elif ch == "'":
            in_str = True
            buf.append(ch)
        elif ch == ";":
            out.append("".join(buf))
            buf = []
        else:
            buf.append(ch)
        i += 1
    if "".join(buf).strip():
        out.append("".join(buf))
    return [s for s in out if s.strip()]


def _read_statements(args):
    """Yield complete ';'-terminated SQL statements from -e, -f, or an
    interactive prompt (reference SqlClient's statement splitter);
    semicolons inside quoted literals do not split."""
    if args.execute:
        yield from _split_statements(args.execute)
        return
    if args.file:
        with open(args.file) as f:
            yield from _split_statements(f.read())
        return
    try:
        import readline  # noqa: F401 - line editing when available
    except ImportError:
        pass
    print("Flink-TPU SQL client. Statements end with ';' — "
          "'quit;' exits.", flush=True)
    buf: list[str] = []
    while True:
        try:
            line = input("sql> " if not buf else "   > ")
        except (EOFError, KeyboardInterrupt):
            print()
            return
        buf.append(line)
        joined = "\n".join(buf)
        if ";" not in joined:
            continue
        parts = _split_statements(joined)
        complete = (joined.rstrip().endswith(";")
                    and (not parts or parts[-1].count("'") % 2 == 0))
        tail = None if complete else (parts.pop() if parts else None)
        for stmt in parts:
            if stmt.strip().lower() in ("quit", "exit"):
                return
            yield stmt
        buf = [tail] if tail else []


def _print_table(schema_names, rows, max_rows: int) -> None:
    shown = rows[:max_rows]
    cells = [[str(v) for v in r] for r in shown]
    widths = [max([len(n)] + [len(c[i]) for c in cells])
              for i, n in enumerate(schema_names)]

    def line(vals):
        return "| " + " | ".join(v.ljust(w)
                                 for v, w in zip(vals, widths)) + " |"

    sep = "+-" + "-+-".join("-" * w for w in widths) + "-+"
    print(sep)
    print(line(schema_names))
    print(sep)
    for c in cells:
        print(line(c))
    print(sep)
    extra = len(rows) - len(shown)
    tail = f" ({extra} more)" if extra > 0 else ""
    print(f"{len(rows)} row(s){tail}", flush=True)


def _cmd_trace_dump(args) -> int:
    """Fetch retained spans from a running endpoint's
    ``/jobs/<name>/traces`` and either write them as Chrome trace-event
    JSON (``-o`` — load the file in Perfetto / chrome://tracing) or
    print a span table. Falls back to THIS process's tracer when no
    ``--target`` is given (useful right after an in-process run)."""
    import json as _json
    import urllib.request

    from .metrics.tracing import Span, TRACER, chrome_trace_events

    if args.target:
        url = f"http://{args.target}/jobs/{args.job}/traces"
        try:
            with urllib.request.urlopen(url, timeout=10.0) as resp:
                payload = _json.loads(resp.read().decode())
        except OSError as e:
            print(f"trace-dump: cannot fetch {url}: {e}", file=sys.stderr)
            return 1
        spans = [Span.from_dict(d) for d in payload.get("spans", [])]
    else:
        spans = TRACER.retained_spans()
    if args.output:
        with open(args.output, "w") as f:
            _json.dump(chrome_trace_events(spans), f)
        print(f"wrote {len(spans)} span(s) to {args.output}")
        return 0
    rows = [[s.scope, s.name, s.start_ms, s.duration_ms, s.trace_id,
             s.parent_id or "-"] for s in spans]
    _print_table(["scope", "name", "start_ms", "dur_ms", "trace", "parent"],
                 rows, max_rows=args.max_rows)
    return 0


def _cmd_state_residency(args) -> int:
    """Print the per-key-group residency/heat table of a job's tiered
    keyed state: which key groups are device-hot vs host-warm, their 2Q
    stage, decayed heat, and last-touch batch. Fetches
    ``/jobs/<name>/state-residency`` from a running endpoint, or falls
    back to THIS process's residency registry when no ``--target`` is
    given (useful right after an in-process run)."""
    import json as _json
    import urllib.request

    if args.target:
        url = f"http://{args.target}/jobs/{args.job}/state-residency"
        try:
            with urllib.request.urlopen(url, timeout=10.0) as resp:
                payload = _json.loads(resp.read().decode())
        except OSError as e:
            print(f"state-residency: cannot fetch {url}: {e}",
                  file=sys.stderr)
            return 1
        rows = payload.get("rows", [])
        series = payload.get("hit_ratio_series", {})
    else:
        from .state.tiering import hit_ratio_series, residency_table
        rows = residency_table(args.job)
        series = hit_ratio_series(args.job)
    if not rows:
        print("no tiered state registered (is the job running under "
              "state.backend.tpu.hbm-budget-bytes / -slots?)")
        return 0
    warm = sum(1 for r in rows if r["tier"] == "warm")
    cells = [[r["operator"], r["key_group"], r["tier"], r["stage"],
              r["warm_keys"], r["heat"], r["last_touch"]] for r in rows]
    _print_table(["operator", "key_group", "tier", "stage", "warm_keys",
                  "heat", "last_touch"], cells, max_rows=args.max_rows)
    print(f"{warm} warm / {len(rows) - warm} hot key group(s)")
    # per-boundary hot-hit-ratio trajectory (last boundaries, oldest
    # first): the cumulative tier_hot_hit_ratio gauge hides phase
    # changes — a paging storm shows up here as a dip
    for op, vals in sorted(series.items()):
        if vals:
            print(f"hit_ratio[{op}] last {len(vals)} boundar(y/ies): "
                  + " ".join(f"{v:.2f}" for v in vals))
    return 0


def _cmd_profile(args) -> int:
    """Print a job's device-time ledger profile: top-K hot programs
    (device-time share, percentiles, cost-model achieved-vs-estimated),
    per-operator device-time shares, and recompile-attribution records
    naming the argument that changed. Fetches ``/jobs/<name>/profile``
    from a running endpoint, or falls back to THIS process's ledger when
    no ``--target`` is given (useful right after an in-process run with
    profiler.enabled)."""
    import json as _json
    import urllib.request

    if args.target:
        url = (f"http://{args.target}/jobs/{args.job}/profile"
               f"?top={args.top}")
        try:
            with urllib.request.urlopen(url, timeout=10.0) as resp:
                payload = _json.loads(resp.read().decode())
        except OSError as e:
            print(f"profile: cannot fetch {url}: {e}", file=sys.stderr)
            return 1
    else:
        from .metrics.profiler import DEVICE_LEDGER
        payload = DEVICE_LEDGER.profile(job=args.job or None, top=args.top)
    if args.json:
        print(_json.dumps(payload, indent=2, sort_keys=True))
        return 0
    if not payload.get("enabled"):
        print("device-time ledger is disabled (run with "
              "profiler.enabled: true)")
    progs = payload.get("programs", [])
    if not progs:
        print("no attributed device time recorded")
        return 0
    print(f"job {payload.get('job') or '<all>'}: "
          f"{payload.get('total_device_ms', 0.0):.2f} ms device, "
          f"{payload.get('total_compile_ms', 0.0):.2f} ms compile")

    def _fmt(v, spec=".3f"):
        return format(v, spec) if isinstance(v, (int, float)) else "-"

    rows = [[p["site"], p["operator"] or "-", p["count"],
             _fmt(p["self_ms"], ".2f"), _fmt(p["p50_ms"]),
             _fmt(p["p95_ms"]), _fmt(p["max_ms"]),
             f"{p['share'] * 100:.1f}%", _fmt(p.get("est_ms")),
             _fmt(p.get("achieved_vs_estimated"), ".2f")] for p in progs]
    _print_table(["site", "operator", "n", "self_ms", "p50", "p95",
                  "max", "share", "est_ms", "ach/est"], rows,
                 max_rows=args.top)
    ops = payload.get("operators", [])
    if ops:
        _print_table(["operator", "device_ms", "share"],
                     [[o["operator"] or "-", _fmt(o["device_ms"], ".2f"),
                       f"{o['share'] * 100:.1f}%"] for o in ops],
                     max_rows=args.top)
    for r in payload.get("recompiles", []):
        changed = "; ".join(r.get("changed") or ()) or "<no arg diff>"
        print(f"recompile {r['site']}: {changed}")
    return 0


def _cmd_jobs(args) -> int:
    """Print the per-job quota/bulkhead table (``--quotas``): fair-share
    weight, remaining deficit, device-time share from the ledger,
    breaker state, and the rejected/shed counters. Fetches
    ``/jobs/<name>/quota`` for each job on a running endpoint, or falls
    back to THIS process's isolation scheduler when no ``--target`` is
    given (useful right after an in-process multi-job run)."""
    import json as _json
    import urllib.request

    if args.target:
        base = f"http://{args.target}"
        try:
            with urllib.request.urlopen(f"{base}/jobs",
                                        timeout=10.0) as resp:
                overview = _json.loads(resp.read().decode())
        except OSError as e:
            print(f"jobs: cannot fetch {base}/jobs: {e}", file=sys.stderr)
            return 1
        if isinstance(overview, dict):
            overview = overview.get("jobs", [])
        names = [j.get("name") for j in overview if j.get("name")]
        views = []
        for name in names:
            try:
                with urllib.request.urlopen(f"{base}/jobs/{name}/quota",
                                            timeout=10.0) as resp:
                    views.append(_json.loads(resp.read().decode()))
            except OSError as e:
                print(f"jobs: cannot fetch quota for {name}: {e}",
                      file=sys.stderr)
                return 1
        enabled = any(v.get("enabled") for v in views)
        views = [v for v in views if v.get("job")]
    else:
        from .cluster.isolation import ISOLATION
        snap = ISOLATION.snapshot()
        enabled = snap["enabled"]
        views = list(snap["jobs"].values())
    if not args.quotas:
        _print_table(["job"], [[v["job"]] for v in views],
                     max_rows=args.max_rows)
        return 0
    if not enabled:
        print("isolation is disabled (run with isolation.enabled: true)")
    if not views:
        print("no jobs registered with the isolation scheduler")
        return 0
    rows = [[v["job"], v["weight"], v["deficit"],
             f"{v['device_time_share'] * 100:.1f}%", v["breaker"],
             v["admitted_total"], v["admissions_rejected_total"],
             v["shed_records_total"], v["bulkhead_trips_total"]]
            for v in views]
    _print_table(["job", "weight", "deficit", "device_share", "breaker",
                  "admitted", "rejected", "shed_records", "trips"],
                 rows, max_rows=args.max_rows)
    return 0


def _cmd_sql(args) -> int:
    """Interactive SQL client against a TableEnvironment (reference
    flink-table/flink-sql-client SqlClient.java:67): DDL mutates the
    session catalog; queries run and render their FINAL table (changelog
    folded). ``--target`` submits query jobs to a session cluster."""
    from .api.environment import StreamExecutionEnvironment
    from .core.config import StateOptions
    from .sql import TableEnvironment
    from .sql import rowkind as rk

    env = StreamExecutionEnvironment()
    if args.parallelism:
        env.set_parallelism(args.parallelism)
    if args.state_backend:
        env.config.set(StateOptions.BACKEND, args.state_backend)
    if args.target:
        env.set_remote_target(args.target)
    t_env = TableEnvironment(env)
    rc = 0
    for stmt in _read_statements(args):
        try:
            res = t_env.execute_sql(stmt)
        except Exception as e:  # the REPL survives bad statements
            print(f"[ERROR] {e}", file=sys.stderr, flush=True)
            if args.execute or args.file:
                return 1       # script mode: fail fast, fail loudly
            continue           # interactive: keep the session alive
        names = [n for n in res.schema.names if n != rk.ROWKIND_COLUMN]
        rows = res.collect_final()
        if names == ["result"] and rows in ([("OK",)], [["OK"]]):
            print("[INFO] OK", flush=True)
        else:
            _print_table(names, rows, args.max_rows)
    return rc


def _cmd_sql_gateway(args) -> int:
    """Serve the REST SQL gateway (reference SqlGatewayRestEndpoint)."""
    import time

    from .sql.gateway import SqlGateway

    gw = SqlGateway(port=args.port, host=args.host,
                    state_backend=args.state_backend)
    gw.start()
    print(f"sql gateway listening on {args.host}:{gw.port}", flush=True)
    try:
        while True:
            time.sleep(3600)
    except KeyboardInterrupt:
        gw.stop()
        return 0


def _cmd_deploy(args) -> int:
    """Launch one SPMD script across N supervised worker processes
    (reference start-cluster.sh + active resource manager drivers; see
    cluster/deployment.py — a Kubernetes driver swaps the process
    launcher for pod creation)."""
    from .cluster.deployment import ProcessDeploymentDriver, SpmdDeployment

    dep = SpmdDeployment(
        args.script, n_hosts=args.hosts,
        driver=ProcessDeploymentDriver(stdout_dir=args.log_dir or None),
        max_worker_restarts=args.max_restarts)
    dep.start()
    print(f"deployed {args.hosts} workers; supervising", flush=True)
    try:
        codes = dep.wait(timeout=args.timeout)
    except KeyboardInterrupt:
        dep.stop()          # never orphan worker processes on Ctrl-C
        print("interrupted; workers stopped", flush=True)
        return 130
    for hid in sorted(codes):
        print(f"worker {hid}: exit {codes[hid]}")
    return 0 if all(c == 0 for c in codes.values()) else 1


def _cmd_cluster(args) -> int:
    import time

    from .cluster.dispatcher import Dispatcher

    d = Dispatcher(port=args.port, host=args.host,
                   archive_dir=args.archive_dir or None)
    port = d.start()
    print(f"session cluster listening on {args.host}:{port}", flush=True)
    try:
        while True:
            time.sleep(3600)
    except KeyboardInterrupt:
        d.stop()
        return 0


def _cmd_plan(args) -> int:
    """Print the fusion certificate for a pipeline script or .sql file:
    per chained vertex the operator chain, its verdict (CERTIFIED /
    PARTIAL / REJECTED), whether the runtime lowers the prefix to one
    dispatch, and every rejecting PLAN6xx finding with file:line (the
    catalogue lives in docs/ANALYSIS.md). Execution is stubbed — the
    script's graphs compile and certify but never run."""
    import json as _json

    from .graph.fusion import capture_certificates

    certs, err = capture_certificates(args.script, argv=args.args)
    if err:
        print(f"plan: script error after capture: {err}", file=sys.stderr)
    if not certs:
        print("plan: the script built no pipeline (nothing to certify)",
              file=sys.stderr)
        return 2
    if args.json:
        print(_json.dumps([c.to_dict() for c in certs], indent=2,
                          sort_keys=True))
        return 0
    for cert in certs:
        print(f"job {cert.job_name!r} "
              f"fusion_enabled={cert.fusion_enabled}")
        rows = []
        for ch in cert.chains:
            ops = " -> ".join(f"{o.name}[{o.category}]" for o in ch.ops)
            lowered = "one-dispatch" if ch.lowered_prefix else "-"
            if ch.findings:
                rejects = "; ".join(f"{f.rule} {f.file}:{f.line}"
                                    for f in ch.findings)
            else:
                rejects = "-"
            rows.append([ch.vertex_id, ops, ch.verdict, lowered, rejects])
        _print_table(["chain", "operators", "verdict", "lowered",
                      "rejected by"], rows, max_rows=1000)
        for ch in cert.chains:
            for f in ch.findings:
                print(f"  {f.rule} {f.file}:{f.line} [{f.symbol}] "
                      f"{f.message}")
    return 0


def _cmd_lint(args) -> int:
    """tpu-lint driver: Tier-A AST rules + Tier-B jaxpr program audit,
    diffed against the committed baseline (flink_tpu/analysis/
    baseline.json).  Exit 0 clean, 1 unbaselined/stale findings, 2
    usage error."""
    import json as _json

    from .analysis import (AnalysisContext, all_rules,
                           diff_against_baseline, run_rules,
                           save_baseline)

    known = all_rules()
    if args.rules:
        selected = [r.strip() for r in args.rules.split(",") if r.strip()]
        unknown = [r for r in selected if r not in known]
        if unknown:
            print(f"unknown rule id(s): {', '.join(unknown)} "
                  f"(known: {', '.join(sorted(known))})", file=sys.stderr)
            return 2
    else:
        selected = sorted(known)

    skipped: list[str] = []
    if any(known[r].tier == "B" for r in selected):
        # The jaxpr audit lints programs a pipeline actually built:
        # exercise a tiny Q5-shaped job to populate the registry.
        from .metrics.device import PROGRAM_AUDIT
        if not PROGRAM_AUDIT:
            try:
                from .analysis.jaxpr_rules import exercise_programs
                exercise_programs()
            except Exception as e:
                skipped.append(f"tier-B program exercise failed: {e}")

    ctx = AnalysisContext()
    findings = run_rules(ctx, selected, skipped)
    new, stale = diff_against_baseline(findings, rules=selected)

    if args.update_baseline:
        save_baseline(findings, default_reason=args.reason or None)
        if args.reason:
            print(f"baseline updated: {len(findings)} entries "
                  f"({len(new)} stamped with the given reason)")
        else:
            print(f"baseline updated: {len(findings)} entries "
                  f"({len(new)} need a reviewed reason)")
        return 0

    if args.json:
        print(_json.dumps({
            "findings": [f.to_dict() for f in findings],
            "new": [f.fingerprint for f in new],
            "stale_baseline": stale,
            "skipped": skipped}, indent=2, sort_keys=True))
    else:
        new_fps = {f.fingerprint for f in new}
        if findings:
            rows = [[f.rule,
                     "NEW" if f.fingerprint in new_fps else "baselined",
                     f.location(), f.message] for f in findings]
            _print_table(["rule", "status", "location", "finding"],
                         rows, max_rows=200)
            for f in new:
                if f.hint:
                    print(f"  {f.rule} {f.location()}: hint: {f.hint}")
        for s in skipped:
            print(f"skipped: {s}")
        for e in stale:
            print(f"stale baseline entry (fixed? run --update-baseline): "
                  f"{e['rule']} {e['file']} {e['symbol']}")
        print(f"{len(findings)} finding(s), {len(new)} new, "
              f"{len(stale)} stale baseline entr(y/ies)")
    return 1 if (new or stale) else 0


def _cmd_leader(args) -> int:
    """Who currently leads the coordinator election over an HA dir
    (cluster/ha.py leader_info): leader owner, fencing epoch, lease age,
    published address, standby roster."""
    import json as _json

    from .cluster.ha import leader_info

    info = leader_info(args.ha_dir)
    if args.json:
        print(_json.dumps(info, indent=2, sort_keys=True))
        return 0 if info.get("leader") else 1
    leader = info.get("leader")
    if not leader:
        print(f"no leader for {args.ha_dir}")
        if info.get("standbys"):
            print(f"standbys ({info['standby_count']}): "
                  + ", ".join(info["standbys"]))
        return 1
    age = info.get("lease_age")
    print(f"leader:   {leader}")
    print(f"epoch:    {info.get('epoch')}")
    print(f"lease age: {age:.3f}s" if age is not None else "lease age: ?")
    if info.get("address"):
        print(f"address:  {info['address']}")
    print(f"standbys: {info.get('standby_count', 0)}"
          + (f" ({', '.join(info['standbys'])})"
             if info.get("standbys") else ""))
    return 0


def main(argv: Optional[list[str]] = None) -> int:
    parser = argparse.ArgumentParser(
        prog="flink-tpu", description="flink-tpu command line client")
    sub = parser.add_subparsers(dest="command", required=True)

    run = sub.add_parser("run", help="run a pipeline script")
    run.add_argument("script")
    run.add_argument("--parallelism", "-p", type=int, default=0)
    run.add_argument("--state-backend", default="")
    run.add_argument("--checkpoint-dir", default="")
    run.add_argument("--checkpoint-interval", type=float, default=0.0)
    run.add_argument("--from-savepoint", default="")
    run.add_argument("--target", default="",
                     help="host:port of a running session cluster "
                          "(flink-tpu cluster); empty = run locally")
    run.set_defaults(fn=_cmd_run)

    cluster = sub.add_parser(
        "cluster", help="start a standing session cluster (Dispatcher)")
    cluster.add_argument("--port", type=int, default=8081)
    cluster.add_argument("--host", default="127.0.0.1")
    cluster.add_argument("--archive-dir", default="")
    cluster.set_defaults(fn=_cmd_cluster)

    lst = sub.add_parser("list", help="list jobs on a session cluster")
    lst.add_argument("--target", required=True, help="host:port")
    lst.set_defaults(fn=_cmd_list)

    cancel = sub.add_parser("cancel", help="cancel a job on a cluster")
    cancel.add_argument("job_id")
    cancel.add_argument("--target", required=True)
    cancel.set_defaults(fn=_cmd_cancel)

    sp = sub.add_parser("savepoint",
                        help="trigger a savepoint on a running job")
    sp.add_argument("job_id")
    sp.add_argument("--target", required=True)
    sp.set_defaults(fn=_cmd_savepoint)

    spi = sub.add_parser("savepoint-info", help="inspect a savepoint")
    spi.add_argument("path")
    spi.set_defaults(fn=_cmd_savepoint_info)

    cvf = sub.add_parser(
        "checkpoint-verify",
        help="verify every retained checkpoint's artifact integrity "
             "offline (chunk digests + metadata checksum)")
    cvf.add_argument("dir", help="checkpoint storage directory "
                                 "(execution.checkpointing.dir)")
    cvf.set_defaults(fn=_cmd_checkpoint_verify)

    aotc = sub.add_parser(
        "aot-cache",
        help="verify a persistent AOT executable cache directory "
             "offline (artifact digests + environment fingerprint)")
    aotc.add_argument("dir", help="the cache directory (config key "
                                  "aot.dir)")
    aotc.set_defaults(fn=_cmd_aot_cache)

    trd = sub.add_parser(
        "trace-dump",
        help="dump causal-trace spans from a running job (or this "
             "process) as a table or Perfetto-loadable JSON")
    trd.add_argument("--target", default="",
                     help="host:port of a REST endpoint; empty = the "
                          "current process's tracer")
    trd.add_argument("--job", default="job",
                     help="job name on the endpoint (default: job)")
    trd.add_argument("-o", "--output", default="",
                     help="write Chrome trace-event JSON here instead of "
                          "printing a table")
    trd.add_argument("--max-rows", type=int, default=200)
    trd.set_defaults(fn=_cmd_trace_dump)

    srr = sub.add_parser(
        "state-residency",
        help="print the per-key-group residency/heat table of a job's "
             "tiered keyed state (device-hot vs host-warm)")
    srr.add_argument("job", nargs="?", default="",
                     help="job (or job/operator) name; empty = every "
                          "registered operator")
    srr.add_argument("--target", default="",
                     help="host:port of a REST endpoint; empty = the "
                          "current process's residency registry")
    srr.add_argument("--max-rows", type=int, default=200)
    srr.set_defaults(fn=_cmd_state_residency)

    prf = sub.add_parser(
        "profile",
        help="print a job's device-time ledger profile (hot programs, "
             "per-operator shares, recompile attribution)")
    prf.add_argument("job", nargs="?", default="",
                     help="job name; empty = every attributed job "
                          "(local fallback only)")
    prf.add_argument("--target", default="",
                     help="host:port of a REST endpoint; empty = the "
                          "current process's ledger")
    prf.add_argument("--top", type=int, default=10,
                     help="programs to show (default 10)")
    prf.add_argument("--json", action="store_true",
                     help="machine-readable payload")
    prf.set_defaults(fn=_cmd_profile)

    jbs = sub.add_parser(
        "jobs",
        help="list jobs; --quotas adds the per-job admission-quota / "
             "bulkhead table (weight, deficit, device share, breaker)")
    jbs.add_argument("--quotas", action="store_true",
                     help="show the isolation scheduler's quota columns")
    jbs.add_argument("--target", default="",
                     help="host:port of a REST endpoint; empty = the "
                          "current process's isolation scheduler")
    jbs.add_argument("--max-rows", type=int, default=50)
    jbs.set_defaults(fn=_cmd_jobs)

    gwp = sub.add_parser("sql-gateway",
                         help="serve the REST SQL gateway")
    gwp.add_argument("--port", type=int, default=8083)
    gwp.add_argument("--host", default="127.0.0.1")
    gwp.add_argument("--state-backend", default="")
    gwp.set_defaults(fn=_cmd_sql_gateway)

    dep = sub.add_parser(
        "deploy", help="run an SPMD script across N supervised workers")
    dep.add_argument("script")
    dep.add_argument("--hosts", type=int, default=2)
    dep.add_argument("--log-dir", default="")
    dep.add_argument("--max-restarts", type=int, default=2)
    dep.add_argument("--timeout", type=float, default=3600.0)
    dep.set_defaults(fn=_cmd_deploy)

    sql = sub.add_parser(
        "sql", help="interactive SQL client (reference sql-client.sh)")
    sql.add_argument("-e", "--execute", help="run statements and exit")
    sql.add_argument("-f", "--file", help="run a .sql script and exit")
    sql.add_argument("--target", help="session cluster host:port")
    sql.add_argument("--state-backend", default="")
    sql.add_argument("--parallelism", type=int, default=0)
    sql.add_argument("--max-rows", type=int, default=100)
    sql.set_defaults(fn=_cmd_sql)

    lint = sub.add_parser(
        "lint", help="tpu-lint: device-path static analysis "
                     "(AST rules + jaxpr program audit)")
    lint.add_argument("--rules", help="comma-separated rule ids "
                                      "(default: all)")
    lint.add_argument("--json", action="store_true",
                      help="machine-readable output")
    lint.add_argument("--update-baseline", action="store_true",
                      help="rewrite flink_tpu/analysis/baseline.json "
                           "from the current findings")
    lint.add_argument("--reason", default="",
                      help="with --update-baseline: stamp NEW baseline "
                           "entries with this reviewed reason instead of "
                           "the TODO placeholder (BASE601 flags entries "
                           "whose reason is still the TODO)")
    lint.set_defaults(fn=_cmd_lint)

    plan = sub.add_parser(
        "plan", help="print the fusion certificate for an example "
                     "pipeline or .sql script (PLAN6xx rejections with "
                     "file:line; see docs/ANALYSIS.md)")
    plan.add_argument("script", help="a pipeline .py script or a .sql file")
    plan.add_argument("--json", action="store_true",
                      help="machine-readable certificate")
    plan.add_argument("args", nargs="*",
                      help="argv passed through to the script")
    plan.set_defaults(fn=_cmd_plan)

    ldr = sub.add_parser(
        "leader", help="print the current coordinator-election leader "
                       "of an HA dir (owner, fencing epoch, lease age, "
                       "standby count)")
    ldr.add_argument("ha_dir", help="the job's ha.dir")
    ldr.add_argument("--json", action="store_true",
                     help="machine-readable payload")
    ldr.set_defaults(fn=_cmd_leader)

    ver = sub.add_parser("version", help="print version")
    ver.set_defaults(fn=lambda a: (print("flink-tpu 0.1"), 0)[1])

    args = parser.parse_args(argv)
    return args.fn(args)


if __name__ == "__main__":
    # the process entry, not main(): tests call main() in-process and must
    # not switch their interpreter's compile cache on
    from .utils.compile_cache import place_compile_cache
    place_compile_cache()
    sys.exit(main())
