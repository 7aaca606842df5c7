"""A program's device time, PARTITIONED over the named regions of its
compiled code.

``harness/op_paths`` keeps the JAX name path of every ``XLA Ops`` event
and drops the event's own name; a path cannot name what the compiler's
x64 rewriter makes (the int64 planes' split and join, the halves of a
64-bit scatter carry none). This module keeps both: the event's name is
the HLO instruction as the executable holds it (``%fusion.17 = ...``),
and the PROGRAM says what region each of its instructions belongs to
(``flink_tpu.metrics.device.program_regions``: the map is made from the
compiled HLO, by scope, by custom-call target and by data flow). It
reuses ``op_paths``' reading of the protobuf wire format.

The partition: inside one execution of a program, every instant belongs
to the SHORTEST operation event that covers it (the leaf: the TPU's op
line nests a ``while`` event around its body's operations, which a union
over a name pattern tolerates and a partition cannot); the leaf's
instruction looks up the region in the map of the program that ran. A
trace names an executable by module name and a number that no Python API
of the runtime yields (``pair``), so a traced program is paired with a
map by content: the map, among those of its module name, that holds
every operation the trace shows of it. An instant no operation covers,
an operation of a loop or branch itself and a program that has no map at
all are ``unnamed``; a program whose NAME the maps know and that fits
none of them is not an executable a map was made from, and reads
nothing.

``leaf_partition`` and ``group_regions`` are pure functions over plain
lists, checked on a synthetic op line and on a reduced recording
(``tests/data/op_names_v5e_q5_mesh4.json``).
"""

from __future__ import annotations

import bisect
import heapq
import json
import os
import re
import time
from typing import Mapping, Optional, Sequence

from . import op_paths as P
from . import trace as T
from .spec import BENCH_DIR

__all__ = ["UNNAMED", "read_device_ops", "load", "program_maps",
           "leaf_partition", "step_groups", "pair", "group_regions",
           "measured"]

UNNAMED = "unnamed"
_CACHE: dict = {}


#: opcodes a program's map has no entry for
_NO_ENTRY = frozenset({"while", "conditional", "call", "tuple",
                       "get-tuple-element", "bitcast", "constant",
                       "parameter"})


def _opcode(event_name: str) -> str:
    """The opcode of an ``XLA Ops`` event's name, which is the whole HLO
    instruction: ``%fusion.17 = (u32[8]{0}, u32[8]{0}) fusion(...)``."""
    _head, sep, rest = event_name.partition(" = ")
    if not sep:
        return ""
    if rest.startswith("("):                      # a tuple type
        depth = 0
        for i, c in enumerate(rest):
            depth += (c == "(") - (c == ")")
            if depth == 0:
                rest = rest[i + 1:]
                break
    else:
        rest = rest[rest.find(" "):]
    m = re.match(r"\s*([\w\-]+)\(", rest)
    return m.group(1) if m else ""


def _plane(buf) -> dict:
    """``{"modules": [(name, start_ns, end_ns)], "ops": [(instruction,
    path, start_ns, end_ns)]}`` of one device plane, in order of start:
    ``op_paths._plane`` with an operation's own name kept (as the
    instruction's name, ``fusion.17``) beside its path."""
    stat_names: dict[int, str] = {}
    metas: dict[int, object] = {}
    lines = []
    for number, v in P._fields(buf):
        if number == P._PLANE_STAT_META:
            key, meta = P._map_entry(v)
            stat_names[key] = next(
                (P._text(x) for n, x in P._fields(meta) if n == 2), "")
        elif number == P._PLANE_EVENT_META:
            key, meta = P._map_entry(v)
            metas[key] = meta
        elif number == P._PLANE_LINES:
            lines.append(v)
    path_stat = next((k for k, n in stat_names.items() if n == "tf_op"), None)
    names: dict[int, str] = {}
    paths: dict[int, str] = {}
    for key, meta in metas.items():
        for number, v in P._fields(meta):
            if number == P._META_NAME:
                names[key] = P._text(v)
            elif number == P._META_STATS and path_stat is not None:
                stat = dict(P._fields(v))
                if stat.get(P._STAT_META_ID) == path_stat:
                    if P._STAT_STR in stat:
                        paths[key] = P._text(stat[P._STAT_STR])
                    elif P._STAT_REF in stat:
                        paths[key] = stat_names.get(stat[P._STAT_REF], "")
    # what only holds other computations (its body's operations have
    # events of their own) and the plumbing that runs nothing have no
    # entry in a program's map: they are left out here, and the instants
    # only they cover are covered by nothing
    instructions = {key: name.split(" = ", 1)[0].strip().lstrip("%")
                    for key, name in names.items()
                    if _opcode(name) not in _NO_ENTRY}
    out = {"modules": [], "ops": []}
    for line in lines:
        head = dict((n, v) for n, v in P._fields(line)
                    if n != P._LINE_EVENTS)
        which = {T.MODULE_LINE: "modules", T.OPS_LINE: "ops"}.get(
            P._text(head.get(P._LINE_NAME, b"")))
        if which is None:
            continue
        origin = head.get(P._LINE_TIMESTAMP_NS, 0)
        for number, v in P._fields(line):
            if number != P._LINE_EVENTS:
                continue
            event = dict(P._fields(v))
            meta = event.get(P._EVENT_META_ID, 0)
            start = origin + event.get(P._EVENT_OFFSET_PS, 0) / 1e3
            end = start + event.get(P._EVENT_DURATION_PS, 0) / 1e3
            if which == "modules":
                out[which].append((names.get(meta, ""), start, end))
            elif meta in instructions:
                out[which].append((instructions[meta],
                                   paths.get(meta, ""), start, end))
        out[which].sort(key=lambda e: e[-2])
    return out


def read_device_ops(path: str) -> dict[str, dict]:
    """``{plane name: {"modules", "ops"}}`` of the device planes of one
    ``.xplane.pb``; times are ns on the clock ``harness/trace.load_xplane``
    reports."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out = {}
    for number, plane in P._fields(space):
        if number != P._SPACE_PLANES:
            continue
        name = next((P._text(v) for n, v in P._fields(plane)
                     if n == P._PLANE_NAME), "")
        if T.DEVICE_PLANE.match(name):
            out[name] = _plane(plane)
    return out


def load(plane_name: str) -> Optional[dict]:
    """The named device plane of this process's traced run, read once;
    None where there is none."""
    try:
        path = T.find_xplane(os.path.join(BENCH_DIR, ".trace"))
    except FileNotFoundError:
        return None
    key = (path, os.path.getmtime(path))
    if key not in _CACHE:
        _CACHE.clear()
        _CACHE[key] = read_device_ops(path)
    return _CACHE[key].get(plane_name)


def program_maps() -> Optional[dict[str, dict[str, str]]]:
    """The region maps of the programs this process ran, made once, by
    the program (``flink_tpu.metrics.device.program_regions``), with one
    info line on stdout that says how long the making took; None where
    the program has no such function (every commit before it was
    written)."""
    if "maps" not in _CACHE:
        try:
            from flink_tpu.metrics.device import program_regions
        except ImportError:
            _CACHE["maps"] = None
        else:
            t0 = time.perf_counter()
            maps = _CACHE["maps"] = program_regions()
            print(json.dumps({
                "info": "region_maps", "programs": len(maps),
                "seconds": round(time.perf_counter() - t0, 3),
                "instructions": sum(len(m) for m in maps.values())}),
                flush=True)
    return _CACHE["maps"]


def leaf_partition(ops: Sequence[Sequence], lo: float, hi: float
                   ) -> dict[str, float]:
    """``{instruction: ns}`` of [lo, hi]: every instant goes to the
    shortest of the operation events ``(instruction, path, start, end)``
    that cover it, cut to the interval; instants none covers go to no
    one. ``ops`` in order of start."""
    out: dict[str, float] = {}
    active: list[tuple[float, float, str]] = []   # (length, end, name)
    points = sorted({lo, hi}
                    | {t for _n, _p, a, b in ops for t in (a, b)
                       if lo < t < hi})
    i = 0
    for a, b in zip(points, points[1:]):
        while i < len(ops) and ops[i][2] <= a:
            name, _path, start, end = ops[i]
            if end > a:
                heapq.heappush(active, (end - start, end, name))
            i += 1
        while active and active[0][1] <= a:
            heapq.heappop(active)
        if active:
            name = active[0][2]
            out[name] = out.get(name, 0.0) + (b - a)
    return out


def step_groups(modules: Sequence[Sequence], lo: float, hi: float,
                anchor: str, include: Sequence[str],
                exclude: Sequence[str] = ()) -> list[list[tuple]]:
    """The executions ``(name, start, end)`` of every step that is
    several programs, as ``harness/trace.module_groups`` groups them:
    an execution of the program matching ``anchor`` opens a group that
    lasts until the next one and holds the programs matching any of
    ``include`` and none of ``exclude``; only groups whole inside
    [lo, hi] that are not the recording's last count."""
    pats = [re.compile(m) for m in include]
    skip = [re.compile(m) for m in exclude]
    anchor_re = re.compile(anchor)
    events = sorted(modules, key=lambda m: m[1])
    groups: list[list[tuple]] = []
    for name, a, b in events:
        if anchor_re.search(name):
            groups.append([])
        if groups and any(p.search(name) for p in pats) \
                and not any(p.search(name) for p in skip):
            groups[-1].append((name, a, b))
    last_end = max((b for _n, _a, b in events), default=0.0)
    return [g for g in groups
            if g and g[0][1] >= lo and g[-1][2] <= hi
            and g[-1][2] < last_end]


def _label(name: str) -> str:
    """``jit_step(<whatever names the executable>)`` -> ``jit_step``."""
    return re.sub(r"\([^()]*\)$", "", name)


def pair(seen: set, candidates: Sequence[Mapping[str, str]]
         ) -> Optional[Mapping[str, str]]:
    """The map of the executable whose operations a trace shows as
    ``seen`` (instruction names), among the maps of the programs that
    share its module name: a map FITS when every operation seen is one
    of its instructions. The trace names an executable by a number no
    Python API of the runtime yields (the executable's own fingerprint
    is another), so the pairing is by content: one map that fits is the
    executable's; several that fit and give every operation seen the
    same region are as good as one; none, or several that disagree, is
    no pairing."""
    fits = [m for m in candidates if all(name in m for name in seen)]
    if not fits:
        return None
    first = fits[0]
    if any(m[name] != first[name] for m in fits[1:] for name in seen):
        return None
    return first


def group_regions(groups: Sequence[Sequence[tuple]], ops: Sequence[Sequence],
                  maps: Mapping[str, Mapping[str, str]],
                  eager: Optional[Mapping[str, str]] = None
                  ) -> Optional[tuple[dict[str, float], float, int]]:
    """(``{region: seconds}``, seconds, groups): the device time of the
    groups' executions, partitioned. An execution of a program whose
    module name the maps know goes leaf by leaf to the regions of the
    map ``pair`` finds for it; one of a program no map knows by name
    (the eager modules around the audited programs) goes whole to the
    region of ``eager`` (region -> module pattern) whose pattern matches
    its name, else to ``unnamed``. The parts add up to the executions'
    time exactly. None where a program's name is in the maps and no map
    can be paired with what the trace shows of it: that is not an
    executable a map was made from."""
    by_label: dict[str, list] = {}
    for key, regions in maps.items():
        by_label.setdefault(_label(key), []).append(regions)
    eager_res = [(region, re.compile(pat))
                 for region, pat in (eager or {}).items()]
    starts = [op[2] for op in ops]

    def inside(a: float, b: float) -> Sequence:
        # programs run one at a time on a device: an operation belongs
        # to the execution it starts in
        return ops[bisect.bisect_left(starts, a):bisect.bisect_left(starts, b)]

    seen: dict[str, set] = {}         # traced program -> operations seen
    for group in groups:
        for name, a, b in group:
            if _label(name) in by_label:
                seen.setdefault(name, set()).update(
                    op[0] for op in inside(a, b))
    paired = {name: pair(names, by_label[_label(name)])
              for name, names in seen.items()}
    if any(regions is None for regions in paired.values()):
        return None
    totals: dict[str, float] = {}
    seconds = 0.0
    for group in groups:
        for name, a, b in group:
            seconds += (b - a) / 1e9
            regions = paired.get(name)
            if regions is None:
                region = next((r for r, pat in eager_res
                               if pat.search(name)), UNNAMED)
                totals[region] = totals.get(region, 0.0) + (b - a) / 1e9
                continue
            named = 0.0
            for instruction, ns in leaf_partition(inside(a, b), a,
                                                  b).items():
                region = regions.get(instruction, UNNAMED)
                totals[region] = totals.get(region, 0.0) + ns / 1e9
                named += ns
            totals[UNNAMED] = totals.get(UNNAMED, 0.0) \
                + ((b - a) - named) / 1e9
    return totals, seconds, len(groups)


def measured(trace: Optional[dict], params: Mapping
             ) -> Optional[tuple[dict[str, float], float, int]]:
    """``group_regions`` of this process's traced run for the groups
    ``params`` describes (``anchor``, ``modules``, ``exclude``, ``eager``),
    on the busiest device, inside the traced window; computed once for
    each such description (a dozen metrics read one partition). None
    where there is nothing to read: no trace, no device plane, no maps,
    no whole group, or a program the maps cannot be paired with."""
    from .trace_summary import busiest_plane

    if trace is None:
        return None
    lo, hi = T.traced_window(trace)
    plane = busiest_plane(trace, lo, hi)
    if plane is None:
        return None
    found = load(plane["name"])
    maps = program_maps()
    if found is None or maps is None:
        return None
    key = ("groups", json.dumps(
        {k: params.get(k) for k in ("anchor", "modules", "exclude",
                                    "eager")}, sort_keys=True))
    if key not in _CACHE:
        groups = step_groups(found["modules"], lo, hi, params["anchor"],
                             params["modules"], params.get("exclude", ()))
        _CACHE[key] = group_regions(groups, found["ops"], maps,
                                    params.get("eager")) if groups else None
    return _CACHE[key]
