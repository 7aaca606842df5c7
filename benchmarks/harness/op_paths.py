"""The name JAX gave each operation the device ran, from the raw
``.xplane.pb``.

On a TPU every ``XLA Ops`` event points at an event METADATA whose stat
``tf_op`` is the operation's name path as JAX wrote it into the HLO: the
jit names, the control flow and every ``jax.named_scope`` around it, e.g.
``jit(lookup_or_insert)/while/body/probe.claim/scatter-min:``. That path
does not change when the compiler renumbers its fusions, which the
event's own name (the HLO instruction, ``%fusion.17 = ...``) does.
``jax.profiler.ProfileData`` shows an event's own stats (offsets and
durations) and not its metadata's, so this module reads the file's
protobuf wire format itself, and only what it needs of it: the device
planes' ``XLA Modules`` and ``XLA Ops`` lines.

A scope is in the path only if the executable was compiled from a source
that has it. JAX's persistent compile cache leaves such metadata out of
its key, so a cache filled before a scope was written hands back an
executable whose paths lack it (PERF.md section 6, PR 25): patterns over
these paths name the JAX primitive as well as the scope.

``region_runs`` is a pure function over plain lists, checked on a reduced
recording (``tests/data/op_paths_v5e_q5_saturated.json``).
"""

from __future__ import annotations

import bisect
import os
import re
from typing import Iterator, Optional, Sequence

from . import trace as T
from .spec import BENCH_DIR

__all__ = ["load", "read_device_ops", "region_runs"]

_CACHE: dict[tuple, dict] = {}

# field numbers of tsl/profiler/protobuf/xplane.proto
_SPACE_PLANES = 1
_PLANE_NAME, _PLANE_LINES, _PLANE_EVENT_META, _PLANE_STAT_META = 2, 3, 4, 5
_LINE_NAME, _LINE_TIMESTAMP_NS, _LINE_EVENTS = 2, 3, 4
_EVENT_META_ID, _EVENT_OFFSET_PS, _EVENT_DURATION_PS = 1, 2, 3
_META_NAME, _META_STATS = 2, 5
_STAT_META_ID, _STAT_STR, _STAT_REF = 1, 5, 7
_MAP_KEY, _MAP_VALUE = 1, 2


def _varint(buf, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf) -> Iterator[tuple[int, object]]:
    """(field number, value) of one message: an int for a varint, a
    memoryview for a length-delimited field; fixed-width fields, which
    nothing here reads, are skipped."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
            yield number, value
        elif wire == 2:
            size, i = _varint(buf, i)
            yield number, buf[i:i + size]
            i += size
        elif wire == 1:
            i += 8
        elif wire == 5:
            i += 4
        else:
            raise ValueError(f"wire type {wire} in an xplane file")


def _text(view) -> str:
    return bytes(view).decode("utf-8", "replace")


def _map_entry(buf) -> tuple[int, object]:
    key, value = 0, b""
    for number, v in _fields(buf):
        if number == _MAP_KEY:
            key = v
        elif number == _MAP_VALUE:
            value = v
    return key, value


def _plane(buf) -> dict:
    """``{"modules": [(name, start_ns, end_ns)], "ops": [(path, start_ns,
    end_ns)]}`` of one device plane, in order of start."""
    stat_names: dict[int, str] = {}
    metas: dict[int, object] = {}
    lines = []
    for number, v in _fields(buf):
        if number == _PLANE_STAT_META:
            key, meta = _map_entry(v)
            stat_names[key] = next(
                (_text(x) for n, x in _fields(meta) if n == 2), "")
        elif number == _PLANE_EVENT_META:
            key, meta = _map_entry(v)
            metas[key] = meta
        elif number == _PLANE_LINES:
            lines.append(v)
    path_stat = next((k for k, n in stat_names.items() if n == "tf_op"), None)
    names: dict[int, str] = {}
    paths: dict[int, str] = {}
    for key, meta in metas.items():
        for number, v in _fields(meta):
            if number == _META_NAME:
                names[key] = _text(v)
            elif number == _META_STATS and path_stat is not None:
                stat = dict(_fields(v))
                if stat.get(_STAT_META_ID) == path_stat:
                    if _STAT_STR in stat:
                        paths[key] = _text(stat[_STAT_STR])
                    elif _STAT_REF in stat:
                        paths[key] = stat_names.get(stat[_STAT_REF], "")
    out = {"modules": [], "ops": []}
    for line in lines:
        head = dict((n, v) for n, v in _fields(line) if n != _LINE_EVENTS)
        which = {T.MODULE_LINE: "modules", T.OPS_LINE: "ops"}.get(
            _text(head.get(_LINE_NAME, b"")))
        if which is None:
            continue
        label = names if which == "modules" else paths
        origin = head.get(_LINE_TIMESTAMP_NS, 0)
        for number, v in _fields(line):
            if number != _LINE_EVENTS:
                continue
            event = dict(_fields(v))
            start = origin + event.get(_EVENT_OFFSET_PS, 0) / 1e3
            out[which].append((
                label.get(event.get(_EVENT_META_ID, 0), ""), start,
                start + event.get(_EVENT_DURATION_PS, 0) / 1e3))
        out[which].sort(key=lambda e: e[1])
    return out


def read_device_ops(path: str) -> dict[str, dict]:
    """``{plane name: {"modules", "ops"}}`` of the device planes of one
    ``.xplane.pb``; times are ns on the clock ``harness/trace.load_xplane``
    reports."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out = {}
    for number, plane in _fields(space):
        if number != _SPACE_PLANES:
            continue
        name = next((_text(v) for n, v in _fields(plane)
                     if n == _PLANE_NAME), "")
        if T.DEVICE_PLANE.match(name):
            out[name] = _plane(plane)
    return out


def load(plane_name: str) -> Optional[dict]:
    """The named device plane of this process's traced run (the trace
    ``harness/cell.run_cell`` had the profiler write under
    ``<bench_dir>/.trace``), read once; None where there is none."""
    try:
        path = T.find_xplane(os.path.join(BENCH_DIR, ".trace"))
    except FileNotFoundError:
        return None
    key = (path, os.path.getmtime(path))
    if key not in _CACHE:
        _CACHE.clear()
        _CACHE[key] = read_device_ops(path)
    return _CACHE[key].get(plane_name)


def region_runs(modules: Sequence[Sequence], ops: Sequence[Sequence],
                module: str, region: str, lo: float, hi: float
                ) -> list[int]:
    """For every execution of the program matching ``module`` that lies
    whole inside [lo, hi] and is neither the first nor the last program
    of the recording (which its two ends may have cut short): how many
    operations whose path matches ``region`` started inside it. Programs
    run one at a time on a device, so an operation belongs to the program
    it starts in."""
    module_re, region_re = re.compile(module), re.compile(region)
    starts = sorted(start for path, start, _end in ops
                    if region_re.search(path))
    first_start = min((start for _n, start, _e in modules), default=0.0)
    last_end = max((end for _n, _s, end in modules), default=0.0)
    return [bisect.bisect_left(starts, end) - bisect.bisect_left(starts, start)
            for name, start, end in modules
            if module_re.search(name) and lo <= start and end <= hi
            and first_start < start and end < last_end]
