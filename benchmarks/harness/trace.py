"""From a ``jax.profiler`` trace to numbers: the benchmark's only reading
of device time.

Two stages, so that the arithmetic can be checked on a small recorded
trace (benchmarks/tests/data/) without a profiler:

1. ``load_xplane`` reads the ``.xplane.pb`` the profiler wrote (with
   nothing but ``jax.profiler.ProfileData``) into a plain dict:
   ``{"planes": [{"name", "lines": [{"name", "events": [[name, start_ns,
   dur_ns], ...]}]}]}``. Device planes are kept whole; host planes keep
   only the events whose name is in ``host_names`` (the benchmark's own
   annotations), or a trace of a busy host would not come back whole.
2. Pure functions over that dict: the union of busy intervals, per-module
   durations, idle gaps named after what the host was doing.

On a TPU each chip is a plane ``/device:TPU:<n>`` whose line
``XLA Modules`` has one event per executed program, named
``<jit name>(<fingerprint>)``, and whose line ``XLA Ops`` has one event
per HLO operation. ``flink_tpu`` sets no ``named_scope`` yet, so module
names are the only stable names; the patterns that map them to layers are
data (layer_metrics/*.json).
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from typing import Iterable, Sequence

__all__ = ["load_xplane", "find_xplane", "device_planes", "line_events",
           "clip", "union_s", "busy_s", "module_groups",
           "idle_gaps", "top_ops", "op_label", "module_label",
           "traced_window", "WINDOW_ANNOTATION",
           "MODULE_LINE", "OPS_LINE"]

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
MODULE_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
#: the annotation the harness holds open for exactly the traced window
WINDOW_ANNOTATION = "bench_traced_window"


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"the profiler wrote no .xplane.pb under "
                                f"{log_dir}")
    return paths[-1]


def load_xplane(path: str, host_names: Iterable[str]) -> dict:
    import jax.profiler

    keep = set(host_names)
    data = jax.profiler.ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        lines = []
        for line in plane.lines:
            events = [[e.name, float(e.start_ns), float(e.duration_ns)]
                      for e in line.events
                      if device or e.name in keep]
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def device_planes(trace: dict) -> list[dict]:
    out = [p for p in trace["planes"] if DEVICE_PLANE.match(p["name"])]
    return sorted(out, key=lambda p: int(DEVICE_PLANE.match(
        p["name"]).group(1)))


def line_events(plane: dict, line_name: str) -> list[list]:
    for line in plane["lines"]:
        if line["name"] == line_name:
            return line["events"]
    return []


def traced_window(trace: dict) -> tuple[float, float]:
    """[start_ns, end_ns] of the harness's window annotation."""
    for plane in trace["planes"]:
        if DEVICE_PLANE.match(plane["name"]):
            continue
        for line in plane["lines"]:
            for name, start, dur in line["events"]:
                if name == WINDOW_ANNOTATION:
                    return start, start + dur
    raise ValueError(f"the trace holds no {WINDOW_ANNOTATION!r} annotation")


def clip(events: Sequence[Sequence], lo: float, hi: float
         ) -> list[tuple[str, float, float]]:
    """Events cut to [lo, hi] as (name, start, end)."""
    out = []
    for name, start, dur in events:
        a, b = max(start, lo), min(start + dur, hi)
        if b > a:
            out.append((name, a, b))
    return out


def union_s(intervals: Iterable[tuple[float, float]]) -> float:
    """Total length, in seconds, of the union of [start_ns, end_ns]."""
    total, cur_a, cur_b = 0.0, None, None
    for a, b in sorted(intervals):
        if cur_b is None or a > cur_b:
            if cur_b is not None:
                total += cur_b - cur_a
            cur_a, cur_b = a, b
        else:
            cur_b = max(cur_b, b)
    if cur_b is not None:
        total += cur_b - cur_a
    return total / 1e9


def _busy_events(plane: dict) -> list[list]:
    """What counts as 'an operation ran on the device': the HLO-op line,
    or the module line where a backend writes no op line."""
    return line_events(plane, OPS_LINE) or line_events(plane, MODULE_LINE)


def busy_s(plane: dict, lo: float, hi: float) -> float:
    return union_s((a, b) for _n, a, b in clip(_busy_events(plane), lo, hi))


def module_groups(plane: dict, lo: float, hi: float, modules: Sequence[str],
                  anchor: str, exclude: Sequence[str] = ()) -> list[float]:
    """Device seconds per execution of a step that is several programs.

    Programs run in order on a device, so every execution of the module
    matching ``anchor`` opens a group that lasts until the next one; the
    group's value is the summed duration of the modules matching any of
    ``modules`` and none of ``exclude`` in it. Only groups that lie whole
    inside [lo, hi] count."""
    pats = [re.compile(m) for m in modules]
    skip = [re.compile(m) for m in exclude]
    anchor_re = re.compile(anchor)
    events = sorted((start, start + dur, name) for name, start, dur
                    in line_events(plane, MODULE_LINE))
    groups: list[list[float]] = []   # [start, end, seconds]
    for a, b, name in events:
        if anchor_re.search(name):
            groups.append([a, b, 0.0])
        if groups and any(p.search(name) for p in pats) \
                and not any(p.search(name) for p in skip):
            groups[-1][1] = max(groups[-1][1], b)
            groups[-1][2] += (b - a) / 1e9
    # a group is whole only if something ran after it: the trace's last
    # program may have been cut short by the end of the recording
    last_end = events[-1][1] if events else 0.0
    return [g[2] for g in groups
            if g[0] >= lo and g[1] <= hi and g[1] < last_end]


def op_label(name: str) -> str:
    """A short label for an ``XLA Ops`` event: on a TPU the event's name is
    the whole HLO instruction (``%fusion.17 = (u32[...]...) fusion(...)``);
    the label is the instruction's own name, ``fusion.17``."""
    head = name.split(" = ", 1)[0].strip()
    return head.lstrip("%")[:48]


def module_label(name: str) -> str:
    """``jit_lookup_or_insert(1431352...)`` -> ``jit_lookup_or_insert``."""
    return re.sub(r"\(\d+\)$", "", name)


_CONTAINER = re.compile(r"^(while|conditional|call)(\.\d+)?$")


def top_ops(plane: dict, lo: float, hi: float, n: int = 10
            ) -> list[list]:
    """The device operations that took most time, as
    ``[["<module>/<op>", seconds], ...]``. An op belongs to the module
    whose execution encloses it (one op name means different things in
    different programs); ops that only wrap others (while, conditional,
    call) are left out, or their children would count twice. Where a
    backend writes no op line the modules themselves are ranked."""
    modules = sorted((start, start + dur, module_label(name))
                     for name, start, dur in line_events(plane, MODULE_LINE))
    ops = line_events(plane, OPS_LINE)
    totals: dict[str, float] = {}
    if not ops:
        for a, b, label in modules:
            a, b = max(a, lo), min(b, hi)
            if b > a:
                totals[label] = totals.get(label, 0.0) + (b - a) / 1e9
    else:
        starts = [m[0] for m in modules]
        for name, a, b in clip(ops, lo, hi):
            label = op_label(name)
            if _CONTAINER.match(label):
                continue
            i = bisect.bisect_right(starts, a) - 1
            owner = (modules[i][2] if i >= 0 and a < modules[i][1]
                     else "no_module")
            key = f"{owner}/{label}"
            totals[key] = totals.get(key, 0.0) + (b - a) / 1e9
    ranked = sorted(totals.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v] for k, v in ranked]


def idle_gaps(trace: dict, plane: dict, lo: float, hi: float,
              host_names: Sequence[str], n: int = 10,
              unattributed: str = "host_unattributed") -> list[list]:
    """Idle time of one device by what the host was doing: every gap
    between busy intervals is split over the host annotations that
    overlap it (in the order of ``host_names``, first match wins per
    stretch is approximated by overlap length), the rest is
    ``unattributed``. Returns [[name, seconds], ...], largest first."""
    busy = sorted((a, b) for _n, a, b in clip(_busy_events(plane), lo, hi))
    gaps, cursor = [], lo
    for a, b in busy:
        if a > cursor:
            gaps.append((cursor, a))
        cursor = max(cursor, b)
    if hi > cursor:
        gaps.append((cursor, hi))
    host: dict[str, list[tuple[float, float]]] = {h: [] for h in host_names}
    for p in trace["planes"]:
        if DEVICE_PLANE.match(p["name"]):
            continue
        for line in p["lines"]:
            for name, start, dur in line["events"]:
                if name in host:
                    host[name].append((start, start + dur))
    totals = {h: 0.0 for h in host_names}
    totals[unattributed] = 0.0
    for ga, gb in gaps:
        covered = 0.0
        for h in host_names:
            part = union_s((max(a, ga), min(b, gb)) for a, b in host[h]
                           if min(b, gb) > max(a, ga))
            totals[h] += part
            covered += part
        totals[unattributed] += max(0.0, (gb - ga) / 1e9 - covered)
    ranked = sorted(((k, v) for k, v in totals.items() if v > 0),
                    key=lambda kv: -kv[1])[:n]
    return [[k, v] for k, v in ranked]
