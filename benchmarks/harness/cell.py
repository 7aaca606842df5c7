"""One run of one cell: build the job from public entry points, drive it
through ONE ``env.execute()``, hold every emitted row to the plain
reference, and hand back everything the metric files read.

``run_cell`` does not look for a chip (run.py does, before calling it), so
the benchmark's own tests can drive a whole run at rehearsal size on the
CPU, also with the timed path broken underneath.
"""

from __future__ import annotations

import os
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Optional

import numpy as np

from . import trace as trace_mod
from .compile_watch import CompileWatch
from .schedule import Schedule, build_schedule
from .spec import Cell, Spec

__all__ = ["RunResult", "run_cell", "FALLBACK_COUNTERS", "HOST_SPANS",
           "effective_config"]

#: counters that must not move: each one is a fallback or a recovery taken
#: (copied from chip_smoke.FALLBACK_COUNTERS)
FALLBACK_COUNTERS = ("device_degraded_total", "device_retries_total",
                     "dead_letter_records_total", "watchdog_trips_total",
                     "stall_detections_total")
#: the benchmark's own host annotations, in the order idle gaps are
#: attributed to them
HOST_SPANS = ("source_generate", "sink_invoke")


@dataclass
class RunResult:
    cell: Cell
    seed: int
    seconds: float
    config: dict
    traffic: dict
    schedule: Schedule
    generator: Any
    query: Any                      # the query module
    reader: Any                     # the benchmark's SourceReader
    sink: Any                       # the benchmark's sink
    operator: Any                   # the window operator of the job
    window_task: Any                # the task that holds it
    job: Any
    origin_s: float
    t0_s: float
    t_end_s: float
    at_t0: dict                     # snapshots taken at the first timed batch
    at_end: dict
    compile_watch: CompileWatch
    builds_in_window: int
    memory_peak_bytes: Optional[int]
    trace: Optional[dict] = None    # reduced jax.profiler trace
    checks: list[dict] = field(default_factory=list)
    correct: bool = False
    attempted: int = 0
    failed: int = 0
    reference_s: float = 0.0
    setup_s: float = 0.0            # process start -> t0; set by run.py

    @property
    def window_s(self) -> float:
        return self.t_end_s - self.t0_s

    @property
    def timed_events(self) -> int:
        return (self.schedule.phase("timed").n_batches
                * self.schedule.batch_rows)

    def check(self, name: str, value, limit) -> bool:
        """Record one number compared beside its limit (printed by every
        run); returns whether it holds."""
        ok = bool(value <= limit)
        self.checks.append({"check": name, "value": value, "limit": limit,
                            "ok": ok})
        return ok


def effective_config(cell: Cell, rehearse: bool) -> tuple[dict, dict]:
    """The configuration and traffic as run: the files as they are, or
    with their ``rehearse`` blocks laid over them (tiny sizes, CPU)."""
    def overlay(base: dict) -> dict:
        out = {k: v for k, v in base.items() if k != "rehearse"}
        if rehearse:
            for k, v in base.get("rehearse", {}).items():
                if isinstance(v, dict) and isinstance(out.get(k), dict):
                    out[k] = {**out[k], **v}
                else:
                    out[k] = v
        return out
    return overlay(cell.config), overlay(cell.traffic)


def _window_task(job, operator_cls):
    found = []
    for task in job.tasks.values():
        for op in getattr(getattr(task, "chain", None), "operators", ()):
            if isinstance(op, operator_cls):
                found.append((task, op))
    if len(found) != 1:
        raise RuntimeError(f"expected one {operator_cls.__name__} in the "
                           f"job, found {len(found)}")
    return found[0]


class _Tracer:
    """Records ``length_s`` of the timed phase, starting ``delay_s`` after
    its first batch, from a helper thread (so that neither the source nor
    the window task waits for the profiler)."""

    def __init__(self, log_dir: str, delay_s: float, length_s: float):
        self.log_dir = log_dir
        self._delay = delay_s
        self._length = length_s
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self.error: Optional[BaseException] = None
        self.recorded = False

    def start(self) -> None:
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="bench-tracer")
        self._thread.start()

    def _run(self) -> None:
        import jax.profiler

        try:
            if self._stop.wait(self._delay):
                return
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(self.log_dir, profiler_options=opts)
            self.recorded = True
            try:
                with jax.profiler.TraceAnnotation(
                        trace_mod.WINDOW_ANNOTATION):
                    self._stop.wait(self._length)
            finally:
                jax.profiler.stop_trace()
        except BaseException as e:  # noqa: BLE001 - reported by finish()
            self.error = e

    def finish(self) -> Optional[dict]:
        if self._thread is None:
            return None
        self._stop.set()
        self._thread.join()
        if self.error is not None:
            raise self.error
        if not self.recorded:
            return None      # the job ended before the traced window began
        return trace_mod.load_xplane(
            trace_mod.find_xplane(self.log_dir),
            host_names=(*HOST_SPANS, trace_mod.WINDOW_ANNOTATION))


def run_cell(spec: Spec, cell: Cell, *, seed: int, seconds: float,
             trace: bool, rehearse: bool = False,
             trace_dir: Optional[str] = None,
             schema_fields: Optional[list] = None) -> RunResult:
    """``schema_fields`` replaces the query's bid schema: the control
    (benchmarks/control.py) declares ``price`` int32, which makes the
    program keep SUM(price) in 32 bits."""
    import shutil

    from flink_tpu.api import StreamExecutionEnvironment
    from flink_tpu.core import WatermarkStrategy
    from flink_tpu.core.config import PipelineOptions
    from flink_tpu.core.records import Schema
    from flink_tpu.metrics import DEVICE_STATS
    from flink_tpu.metrics.core import MetricRegistry

    from .stream import ScheduledSource, StampingSink

    config, traffic = effective_config(cell, rehearse)
    query_cfg = config["query"]
    data = config["data"]
    batch_rows = int(config["batch_rows"])

    query = spec.module("queries", query_cfg["module"])
    schedule = build_schedule(
        n_keys=int(data["n_keys"]), batch_rows=batch_rows,
        prefill_panes=int(config["prefill_panes"]),
        pane_ms=query.pane_ms(query_cfg), warm_s=float(config["warm_s"]),
        event_rate=int(traffic["event_rate"]), pacing=traffic["pacing"],
        seconds=seconds)
    prefill_rows = schedule.phase("prefill").n_batches * batch_rows
    generator = spec.module("generators", traffic["generator"]) \
        .make_generator(data, prefill_rows, seed)
    for name in ("warm", "timed"):
        # every seed gets the same batches in another order
        ph = schedule.phase(name)
        generator.shuffle_batches(ph.first_batch * batch_rows, ph.n_batches,
                                  batch_rows)

    watch = CompileWatch().install()
    registry = MetricRegistry()
    at_t0: dict = {}
    tracer: Optional[_Tracer] = None
    if trace:
        log_dir = trace_dir or os.path.join(spec.bench_dir, ".trace")
        shutil.rmtree(log_dir, ignore_errors=True)
        timed_s = (schedule.phase("timed").n_batches * batch_rows
                   / int(traffic["event_rate"]))
        tracer = _Tracer(log_dir, delay_s=0.25 * timed_s,
                         length_s=min(float(config.get("trace_s", 6.0)),
                                      0.4 * timed_s))

    def on_timed_start() -> None:
        at_t0["time_s"] = time.perf_counter()
        at_t0["device_stats"] = DEVICE_STATS.snapshot()
        at_t0["metrics"] = registry.snapshot()
        at_t0["compile_s"] = watch.seconds
        if tracer is not None:
            tracer.start()

    schema = Schema(list(schema_fields or query.SCHEMA_FIELDS))
    sink = StampingSink()
    source = ScheduledSource(
        schedule, generator.columns, schema, query.TS_COLUMN,
        on_timed_start=on_timed_start,
        newest_result_ms=sink.newest,
        pane_ms=query.pane_ms(query_cfg),
        lead_panes=int(config["setup_lead_panes"]),
        quiet_s=float(config["quiet_s"]))

    env = StreamExecutionEnvironment.get_execution_environment()
    env.set_state_backend("tpu")
    env.config.set(PipelineOptions.BATCH_SIZE, batch_rows)
    # the chip resolves slots with the XLA probe; the native host index is
    # the CPU backend's rung (as in chip_smoke.py)
    env.config.set("state.backend.tpu.host-index", False)
    ws = WatermarkStrategy.for_monotonous_timestamps() \
        .with_timestamp_column(query.TS_COLUMN)
    stream = env.from_source(source, ws, name="bids", parallelism=1)
    query.build(stream, query_cfg, sink)

    stats_before = DEVICE_STATS.snapshot()
    job_started = time.perf_counter()
    try:
        env.execute(cell.name, timeout=float(config.get("timeout_s", 1100)),
                    metrics_registry=registry)
        t_end = time.perf_counter()
    finally:
        traced = tracer.finish() if tracer is not None else None
    job = env.last_job
    task, op = _window_task(job, query.operator_class(query_cfg))
    reader = source.reader
    at_end = {"time_s": t_end, "device_stats": DEVICE_STATS.snapshot(),
              "metrics": registry.snapshot(), "compile_s": watch.seconds,
              "job_started_s": job_started,
              "stats_before": stats_before}
    from .device import memory_peak_bytes

    result = RunResult(
        cell=cell, seed=seed, seconds=seconds, config=config,
        traffic=traffic, schedule=schedule, generator=generator,
        query=query, reader=reader, sink=sink, operator=op,
        window_task=task, job=job, origin_s=reader.origin_s,
        t0_s=reader.t0_s, t_end_s=t_end, at_t0=at_t0, at_end=at_end,
        compile_watch=watch,
        builds_in_window=watch.builds_between(reader.t0_s, t_end),
        memory_peak_bytes=memory_peak_bytes(), trace=traced)
    _verify(result)
    return result


def _verify(run: RunResult) -> None:
    """Exact equality with the plain reference on every window of the run
    (prefill included), and the guarantees the configuration states: no
    event dropped, no fallback rung taken, no growth of the table."""
    t_ref = time.perf_counter()
    query, q = run.query, run.config["query"]
    rows = run.sink.rows()
    pane_ms = query.pane_ms(q)
    W = query.window_panes(q)
    topk = int(q["topk"])
    by_end: dict[int, np.ndarray] = {}
    if rows:
        order = np.argsort(rows["window_end"], kind="stable")
        ends, starts = np.unique(rows["window_end"][order],
                                 return_index=True)
        bounds = np.r_[starts, len(order)]
        for i, end in enumerate(ends.tolist()):
            by_end[int(end)] = order[bounds[i]:bounds[i + 1]]
    tally = {"windows_expected": 0, "windows_missing": 0, "rows_compared": 0,
             "rows_differ": 0, "topk_wrong": 0, "bounds_wrong": 0}
    missing_ends: list[int] = []
    seen: set[int] = set()
    first_detail: list[str] = []

    def on_window(end_ms: int, bids: np.ndarray, rev: np.ndarray) -> None:
        if not bids.any():
            return                       # no data: nothing may be emitted
        tally["windows_expected"] += 1
        idx = by_end.get(end_ms)
        if idx is None:
            tally["windows_missing"] += 1
            missing_ends.append(end_ms)
            return
        seen.add(end_ms)
        v = query.check_window(rows["auction"][idx], rows["bids"][idx],
                               rows["revenue"][idx], bids, rev, topk)
        tally["rows_compared"] += v.rows
        tally["rows_differ"] += v.rows_differ
        tally["topk_wrong"] += v.topk_wrong
        tally["bounds_wrong"] += int(
            (rows["window_start"][idx] != end_ms - W * pane_ms).sum())
        if v.detail and len(first_detail) < 3:
            first_detail.append(f"window {end_ms}: {v.detail}")

    ref = query.Q5Reference(int(run.config["data"]["n_keys"]), pane_ms, W,
                            on_window)
    for b in range(run.schedule.n_batches):
        cols = run.generator.columns(run.schedule.batch_index(b))
        ref.feed(cols["auction"], cols["price"], run.schedule.batch_ts(b))
    ref.close()
    run.reference_s = time.perf_counter() - t_ref
    unexpected = len(set(by_end) - seen)

    stats0, stats1 = run.at_end["stats_before"], run.at_end["device_stats"]
    ok = True
    ok &= run.check("windows_missing", tally["windows_missing"], 0)
    ok &= run.check("windows_unexpected", unexpected, 0)
    ok &= run.check("rows_differ", tally["rows_differ"], 0)
    ok &= run.check("topk_windows_wrong", tally["topk_wrong"], 0)
    ok &= run.check("window_bounds_wrong", tally["bounds_wrong"], 0)
    dead = 0
    for k in FALLBACK_COUNTERS:
        moved = stats1.get(k, 0) - stats0.get(k, 0)
        ok &= run.check(k, moved, 0)
        if k == "dead_letter_records_total":
            dead = moved
    late = int(run.operator.late_dropped)
    ok &= run.check("late_dropped", late, 0)
    ok &= run.check("host_index_active",
                    int(query.host_index_active(run.operator)), 0)
    want_cap, got_cap = query.operator_capacity(run.operator, q)
    ok &= run.check("capacity_grown_by", got_cap - want_cap, 0)
    ok &= run.check("programs_built_in_window", run.builds_in_window, 0)
    run.checks.append({"check": "_tally", **tally,
                       "windows_emitted": len(by_end),
                       "detail": first_detail})
    # events of the timed phase that no emitted window reflects
    timed = run.schedule.phase("timed")
    lost_panes = {p for end in missing_ends
                  for p in range(end // pane_ms - W, end // pane_ms)}
    t_lo = timed.start_ms // pane_ms
    lost = sum(n for p, n in ref.pane_events.items()
               if p in lost_panes and p >= t_lo)
    run.attempted = run.timed_events
    run.failed = min(run.attempted, late + dead + lost)
    run.correct = bool(ok) and tally["windows_expected"] > 0
