"""Run one benchmark cell once.

    python -m benchmarks.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, one stream through one ``env.execute()``. It exits non-zero,
before building anything, unless ``jax.devices()[0].platform == "tpu"``
and the device count equals the cell's ``chips``; it spawns nothing that
touches JAX. The last stdout line is the result object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` and, traced,
``breakdown``); the lines before it carry what that object has no key
for. A run in which a program was compiled (or loaded) inside the timed
phase is a failed run: exit 3, no result line.

``--rehearse`` drives the same code at the tiny sizes of the data files'
``rehearse`` blocks on the CPU (four virtual devices for a four-chip
cell). It prints ``correct`` and counts and NO metric and no result
object, so a CPU number can never stand under a device metric's name.
"""

from __future__ import annotations

import time

_T_IMPORT = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)


def _process_age_s() -> float:
    """Seconds since this process started (from /proc, so interpreter
    start-up counts); falls back to the time since this module loaded."""
    try:
        with open("/proc/self/stat", encoding="ascii") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime", encoding="ascii") as f:
            uptime = float(f.read().split()[0])
        return uptime - ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return time.perf_counter() - _T_IMPORT


def _say(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


def _series(values, n: int = 24) -> list:
    """At most ``n`` evenly spaced points of a series, rounded."""
    vals = [v for v in values if v is not None]
    if len(vals) > n:
        step = len(vals) / n
        vals = [vals[int(i * step)] for i in range(n)]
    return [round(v, 3) for v in vals]


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--seconds", type=float, default=None)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--rehearse", action="store_true",
                        help="tiny sizes on the CPU; prints no metric")
    parser.add_argument("--keep-trace", default=None, metavar="FILE",
                        help="with --trace 1: also write the reduced trace "
                             "(device planes + the benchmark's host spans) "
                             "as JSON, for a look by hand")
    args = parser.parse_args(argv)
    if args.seed < 0:
        parser.error("--seed must be a non-negative whole number")

    from benchmarks.harness.spec import load_spec

    spec = load_spec()
    cell = spec.cell(args.workload)
    seconds = float(args.seconds if args.seconds is not None
                    else spec.run_seconds)

    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        if cell.chips > 1:
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + f" --xla_force_host_platform_device_count={cell.chips}")
    import jax

    from benchmarks.harness import device as device_mod

    device = device_mod.device_block()
    if not args.rehearse and (device["platform"] != "tpu"
                              or device["count"] != cell.chips):
        print(f"benchmarks.run: cell {cell.name!r} needs {cell.chips} TPU "
              f"chip(s); JAX reports {device['count']} x "
              f"{device['platform']!r}. Nothing was run.", file=sys.stderr)
        return 2

    cache_dir = None
    if not args.rehearse:
        from flink_tpu.utils.compile_cache import place_compile_cache

        cache_dir = place_compile_cache()
        # every program, however quick to compile, so that a second run
        # in the same checkout compiles nothing
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    _say({"benchmark": "start", "workload": cell.name, "seed": args.seed,
          "seconds": seconds, "trace": args.trace,
          "rehearse": args.rehearse, "device": device,
          **device_mod.versions(), "compile_cache_dir": cache_dir})

    from benchmarks.harness.cell import run_cell

    age_at_import = _process_age_s() - (time.perf_counter() - _T_IMPORT)
    run = run_cell(spec, cell, seed=args.seed, seconds=seconds,
                   trace=bool(args.trace), rehearse=args.rehearse)
    # process start -> first timed event, on one clock
    setup_s = age_at_import + (run.t0_s - _T_IMPORT)

    if args.keep_trace and run.trace is not None:
        with open(args.keep_trace, "w", encoding="utf-8") as f:
            json.dump(run.trace, f)
    for c in run.checks:
        _say(c)
    reader, watch = run.reader, run.compile_watch
    stats0 = run.at_end["stats_before"]
    stats_t0 = run.at_t0["device_stats"]
    stats1 = run.at_end["device_stats"]
    lag = reader.lag_ms
    _say({"info": "run", "batches": len(reader.emit_s),
          "batch_rows": run.schedule.batch_rows,
          "phases": {p.name: {"batches": p.n_batches, "rate": p.rate,
                              "paced": p.paced, "start_ms": p.start_ms}
                     for p in run.schedule.phases},
          "windows_emitted": len(run.sink.window_stamps()),
          "rows_emitted": int(sum(len(b["auction"])
                                  for b in run.sink.batches)),
          "timed_events": run.timed_events,
          "timed_wall_s": round(run.window_s, 4),
          "compile_s_setup": round(run.at_t0["compile_s"], 3),
          "compile_s_total": round(watch.seconds, 3),
          "compile_cache_hits": watch.cache_hits,
          "compile_cache_misses": watch.cache_misses,
          "programs_built_in_window": run.builds_in_window,
          "generator_lag_ms": _series(lag),
          "generator_lag_max_ms": round(max(
              (v for v in lag if v is not None), default=0.0), 3),
          "source_generate_s": round(reader.generate_s, 3),
          "prefill_flow_waits": reader.flow_waits,
          "prefill_wall_s": round(
              reader.emit_s[run.schedule.phase("warm").first_batch - 1]
              - reader.started_s, 3),
          "settle_wait_s": round(reader.settle_wait_s, 3),
          "sink_invoke_s": round(run.sink.invoke_s, 4),
          "h2d_bytes_timed": stats1["h2d_bytes"] - stats_t0["h2d_bytes"],
          "d2h_bytes_timed": stats1["d2h_bytes"] - stats_t0["d2h_bytes"],
          "h2d_bytes_job": stats1["h2d_bytes"] - stats0["h2d_bytes"],
          "reference_s": round(run.reference_s, 3),
          "setup_s": round(setup_s, 3)})
    from benchmarks.harness import latency

    pane = run.query.pane_ms(run.config["query"])
    stamps = run.sink.window_stamps()
    ends = [e for e in run.schedule.windows_ending_in(
        run.schedule.phase("warm"), pane) if e in stamps]
    timed_ends = run.schedule.windows_ending_in(
        run.schedule.phase("timed"), pane)
    _say({"info": "timed_window_stamps_s", "what": "sink stamp of each "
          "window that ends in the timed phase, seconds after its start "
          "(a stall shows as a gap)",
          "values": _series([stamps[e] - run.t0_s for e in timed_ends
                             if e in stamps], 64)})
    _say({"info": "window_latency_ms", "batch_period_ms": round(
              1e3 * run.schedule.batch_rows / run.schedule.phase(
                  "timed").rate, 3),
          "warm_event_time": _series(latency.window_latencies_ms(
              run.origin_s, stamps, ends)),
          "timed_event_time": _series(
              latency.timed_event_time_latencies_ms(run) or (), 64),
          "timed_source_to_sink": _series(
              latency.timed_source_to_sink_ms(run) or (), 64)})

    if args.rehearse:
        _say({"rehearsal": True, "correct": run.correct,
              "attempted": run.attempted, "failed": run.failed,
              "metrics_computable": sorted(
                  _metrics(spec, run, cell, args.trace, setup_s, True)[0])})
        return 0 if run.correct else 1
    if args.trace and run.trace is None:
        print("benchmarks.run: the timed phase ended before the traced "
              "window began; nothing was traced.", file=sys.stderr)
        return 4
    if run.builds_in_window:
        print(f"benchmarks.run: {run.builds_in_window} program(s) were "
              "compiled or loaded inside the timed phase; the run is "
              "void.", file=sys.stderr)
        return 3

    metrics, device_extra, breakdown = _metrics(spec, run, cell, args.trace,
                                                setup_s)
    device["memory_peak_bytes"] = run.memory_peak_bytes
    device.update(device_extra)
    line = {"correct": run.correct, "attempted": run.attempted,
            "failed": run.failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    _say(line)
    return 0


def _metrics(spec, run, cell, traced: int, setup_s: float,
             rehearse: bool = False):
    """The cell's end-to-end metrics (untraced) or per-layer metrics
    (traced), each from its own file; a reader that finds nothing to read
    returns None and the metric is left out of the line."""
    metrics: dict[str, dict] = {}
    device_extra: dict = {}
    breakdown = None
    if not traced:
        run.setup_s = setup_s
        for m in cell.end_to_end:
            value = spec.module("end_to_end", m["name"]).measure(run)
            if value is not None:
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}
        return metrics, device_extra, breakdown
    for m in cell.per_layer:
        params = spec.layer_metric(m["name"])
        reader = spec.module("readers", params["reader"])
        value = reader.read(run, params.get("params", {}))
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    if run.trace is not None and not rehearse:
        from benchmarks.harness.trace_summary import device_summary

        device_extra, breakdown = device_summary(run)
    return metrics, device_extra, breakdown


if __name__ == "__main__":
    sys.exit(main())
