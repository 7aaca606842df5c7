"""The program's own stage spans, read from both of its records.

Since PR 25 the program annotates its mailbox stages (``flink_tpu/metrics/
tracing.py`` ``Stage``): every stage interval is a span in the tracer's
ring AND, under a profiler session, a ``TraceAnnotation`` named
``<scope>.<Name>`` in the ``.xplane.pb``, with the span's attributes as
the event's arguments; ``(name, task, seq)`` identifies an interval in
both. ``harness/trace.load_xplane`` keeps only the benchmark's own two
host annotations, so the readers of the stage spans load the raw file
here, once per process, keeping the host events whose names the metric
files list.

Everything below ``load`` is a pure function over plain lists, checked on
a reduced recording (``tests/data/stage_trace_v5e_q5_steady.json``). A
program that lacks the annotations (the parent of PR 25) gives an empty
list, and every reader built on it returns None.
"""

from __future__ import annotations

import os
import re
import statistics
from typing import Iterable, Optional, Sequence

from . import trace as T
from .latency import nearest_rank
from .spec import BENCH_DIR

__all__ = ["load", "checked_trace", "ring_spans", "stage_events",
           "xplane_task", "idle_partition", "fire_lives",
           "clock_disagreement_ns", "subtract", "intersect", "union",
           "CLOCK_LIMIT_NS", "CLOCK_RANK"]

#: the ring's clock and the trace's clock may disagree by this much, at
#: this percentile of their paired spans (the largest is reported beside
#: it and refuses nothing: one thread descheduled between two stamps is
#: not a second clock)
CLOCK_LIMIT_NS = 200_000
CLOCK_RANK = 90

Interval = tuple[float, float]

_CACHE: dict[tuple, dict] = {}


def xplane_task(task_id: str) -> str:
    """The profiler encodes an annotation's arguments into its name as
    ``#k=v,k=v#``; the program therefore writes a task id ``v3#0`` as
    ``v3/0`` (``metrics/tracing._annotation_args``)."""
    return task_id.replace("#", "/").replace(",", ";")


def load(names: Iterable[str]) -> Optional[dict]:
    """``{"stages": [{"name", "start", "end", "args"}]}`` from the
    ``.xplane.pb`` of this process's traced run (``harness/cell.run_cell``
    empties ``<bench_dir>/.trace`` and has the profiler write there), or
    None where no trace was written. ``stages`` holds the host events
    named in ``names``; start and end are ns on the trace's own clock (the
    one the device events of ``run.trace`` are on)."""
    try:
        path = T.find_xplane(os.path.join(BENCH_DIR, ".trace"))
    except FileNotFoundError:
        return None
    key = (path, os.path.getmtime(path))
    cached = _CACHE.get(key)
    if cached is None:
        cached = _CACHE[key] = {"names": set(), "stages": []}
    want = set(names) - cached["names"]
    if want:
        cached["stages"] += _read_host_events(path, want)
        cached["names"] |= want
    return cached


def ring_spans(run) -> Optional[list]:
    """The spans the program's tracer retained, or None where the ring
    dropped any during the run: a reading over part of the run is not
    one."""
    from flink_tpu.metrics.tracing import TRACER

    before = run.at_end["stats_before"].get("spans_dropped_total", 0)
    after = run.at_end["device_stats"].get("spans_dropped_total", 0)
    if after != before:
        return None
    return TRACER.retained_spans()


def checked_trace(run, params: dict) -> Optional[dict]:
    """``{"stages": [...], "clock_ns": {90: ..., 100: ...} | None}`` of
    the traced run: the program's stage events named in
    ``params["program_spans"]`` and the benchmark's own in
    ``params["benchmark_spans"]``, after the check that the ring and the
    trace tell the same time. None where there is no trace, no device
    plane, or no stage annotation in it (a program older than PR 25)."""
    if run.trace is None or not T.device_planes(run.trace):
        return None
    program = list(params["program_spans"])
    loaded = load(program + list(params.get("benchmark_spans", ())))
    if loaded is None or not any(s["name"] in program
                                 for s in loaded["stages"]):
        return None
    if "clock_ns" not in loaded:
        ring = ring_spans(run)
        loaded["clock_ns"] = None if ring is None else {
            rank: clock_disagreement_ns(loaded["stages"], ring, program,
                                        rank)
            for rank in (CLOCK_RANK, 100)}
    off = loaded["clock_ns"] and loaded["clock_ns"][CLOCK_RANK]
    if off is not None and off > CLOCK_LIMIT_NS:
        raise RuntimeError(
            f"the tracer's ring and the profiler's trace disagree by "
            f"{off / 1e3:.1f} us over their paired stage spans (limit "
            f"{CLOCK_LIMIT_NS / 1e3:.0f} us): they are not on one clock")
    return loaded


def _read_host_events(path: str, names: set) -> list[dict]:
    import jax.profiler

    out = []
    data = jax.profiler.ProfileData.from_file(path)
    for plane in data.planes:
        if T.DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name in names:
                    out.append({"name": e.name, "start": float(e.start_ns),
                                "end": float(e.start_ns + e.duration_ns),
                                "args": dict(e.stats)})
    return out


def stage_events(stages: Sequence[dict], name: str,
                 task: Optional[str] = None) -> list[dict]:
    """Events called ``name``, of one task where ``task`` (the task id as
    the program knows it) is given, in order of start."""
    want = None if task is None else xplane_task(task)
    return sorted((s for s in stages if s["name"] == name
                   and (want is None or s["args"].get("task") == want)),
                  key=lambda s: s["start"])


# -- interval arithmetic on sorted, disjoint lists --------------------------

def union(intervals: Iterable[Interval]) -> list[Interval]:
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def intersect(xs: Sequence[Interval], ys: Sequence[Interval]
              ) -> list[Interval]:
    out, i, j = [], 0, 0
    while i < len(xs) and j < len(ys):
        a, b = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        if b > a:
            out.append((a, b))
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return out


def subtract(xs: Sequence[Interval], ys: Sequence[Interval]
             ) -> list[Interval]:
    out, j = [], 0
    for a, b in xs:
        cur = a
        while j < len(ys) and ys[j][1] <= cur:
            j += 1
        k = j
        while k < len(ys) and ys[k][0] < b:
            if ys[k][0] > cur:
                out.append((cur, ys[k][0]))
            cur = max(cur, ys[k][1])
            k += 1
        if cur < b:
            out.append((cur, b))
    return out


def _length_s(xs: Iterable[Interval]) -> float:
    return sum(b - a for a, b in xs) / 1e9


def idle_partition(busy: Iterable[Interval], lo: float, hi: float,
                   spans: dict[str, Sequence[Interval]],
                   order: Sequence[str], rest: str = "unattributed"
                   ) -> dict[str, float]:
    """Seconds of the device's idle time in [lo, hi] by what the host was
    doing: each idle instant goes to the FIRST name in ``order`` one of
    whose intervals covers it (innermost stage first), what none covers
    to ``rest``. A partition: the values add up to the idle time exactly,
    whatever overlaps the spans have among themselves."""
    left = subtract([(lo, hi)], union(busy))
    out: dict[str, float] = {}
    for name in order:
        mine = intersect(left, union(spans.get(name, ())))
        out[name] = _length_s(mine)
        left = subtract(left, mine)
    out[rest] = _length_s(left)
    return out


def fire_lives(stages: Sequence[dict], modules: Sequence[Sequence],
               task: str, fire_module: str, lo: float, hi: float,
               names: dict[str, str]) -> list[dict]:
    """One record per fired window whose life lies inside [lo, hi]: the
    window's ``FireDispatch`` on the host, the first execution of the
    fire program on the device that starts after that dispatch began
    (fires run in order and one at a time, so that is this fire's), and
    the window's ``Drain`` and ``Emit``. All in ms::

        device_queue     end of FireDispatch -> the fire starts on device
        fire_device      the fire program on the device
        ready_to_drain   the fire program ended -> Drain starts
        drain, emit      the two host stages
        dispatch_to_rows end of FireDispatch -> end of Emit (their sum,
                         with the hand-over from Drain to Emit)
    """
    pat = re.compile(fire_module)
    runs = sorted((start, start + dur) for name, start, dur in modules
                  if pat.search(name))
    by_seq: dict[str, dict] = {}
    for key in ("dispatch", "drain", "emit"):
        by_seq[key] = {s["args"].get("seq"): s
                       for s in stage_events(stages, names[key], task)}
    out = []
    for seq, disp in sorted(by_seq["dispatch"].items(),
                            key=lambda kv: kv[1]["start"]):
        drain, emit = by_seq["drain"].get(seq), by_seq["emit"].get(seq)
        if drain is None or emit is None:
            continue
        if disp["start"] < lo or emit["end"] > hi:
            continue
        run = next((r for r in runs if r[0] >= disp["start"]), None)
        if run is None or run[1] > drain["start"]:
            continue            # the fire's execution is not in the trace
        out.append({
            "seq": seq,
            "device_queue": (run[0] - disp["end"]) / 1e6,
            "fire_device": (run[1] - run[0]) / 1e6,
            "ready_to_drain": (drain["start"] - run[1]) / 1e6,
            "drain": (drain["end"] - drain["start"]) / 1e6,
            "emit": (emit["end"] - emit["start"]) / 1e6,
            "dispatch_to_rows": (emit["end"] - disp["end"]) / 1e6,
        })
    return out


def clock_disagreement_ns(stages: Sequence[dict], ring: Sequence,
                          names: Iterable[str], rank: int = CLOCK_RANK
                          ) -> Optional[float]:
    """How far the ring's clock and the trace's clock disagree. Every
    stage interval is in both records, cut from one pair of timestamps,
    so ``ring start - trace start`` is one constant (the trace counts from
    its own origin) plus the few hundred ns between the two stamps. The
    value is the ``rank``-th percentile (nearest rank; 100 is the largest)
    of the pairs' distance from their median offset: a clock that ticks
    differently, or a second clock behind either record, shows as a
    spread at the 90th; one descheduled thread between two stamps shows
    at the largest alone. None without pairs."""
    names = set(names)
    by_key = {}
    for s in stages:
        if s["name"] in names:
            by_key[(s["name"], s["args"].get("task"),
                    s["args"].get("seq"))] = s["start"]
    offsets = []
    for span in ring:
        key = (f"{span.scope}.{span.name}",
               xplane_task(str(span.attributes.get("task", ""))),
               span.attributes.get("seq"))
        start = by_key.get(key)
        if start is not None:
            offsets.append(span.start_ns - start)
    if not offsets:
        return None
    mid = statistics.median(offsets)
    return nearest_rank([abs(o - mid) for o in offsets], rank)
