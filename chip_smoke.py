"""On-chip smoke: NEXmark Q5 at 10M keys through env.execute() on a TPU.

    python chip_smoke.py [--seed N]

The quickest proof that the system still starts on the chip. ONE process
(a chip belongs to one process; nothing is spawned) drives the entry
points a user calls — StreamExecutionEnvironment -> datagen -> key_by ->
window -> device_aggregate / mesh_aggregate -> sink -> env.execute() — and
holds every answer to a plain numpy reference built from the same
generator and seed. Legs:

  q5-10M-device  HOP 10 s / 2 s, COUNT + SUM(price), top-1000, 10M keys,
                 capacity 2^24 (~3.4 GB of keyed state in HBM), 2^24 events
                 born on the device
  q5-10M-host    the same job fed host-born batches (2^22 events, h2d > 0)
  q5-mesh        the same query through the mesh vertex over ALL visible
                 devices (D=1 on one chip, D=4 on four); shards must sit
                 on D distinct devices and rows must equal q5-10M-host's
  q5-mesh-inflight  a short run of the mesh vertex over auction ids that
                 ADVANCE (24 panes, 13 x the ids in flight at once): the
                 shards reclaim the slots of dead ids several times and
                 every one ends at the capacity it began with
  pallas-topk    masked_topk_pallas compiled (not interpreted) under x64,
                 equal to ops.topk.masked_topk and to numpy

It exits non-zero, before any leg, unless jax.devices()[0] is a TPU, and
non-zero if any leg fails: no exception is caught. Every leg asserts that
the degradation ladder, retries, dead-letter output, watchdog and stall
detector were NOT used. Walls and compile seconds are set-up facts printed
for the record; none of them is a speed. The last stdout line is
{"ok": true, "device": {...}} with the device as JAX reports it.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from importlib import metadata
from typing import Callable, Optional

import numpy as np

MULT = 0x9E3779B97F4A7C15   # odd 64-bit mixer: idx -> pseudo-uniform key
PANE_MS = 2_000             # HOP slide
WINDOW_PANES = 5            # HOP size 10 s = 5 panes
RING = 16
TOPK = 1000
N_KEYS = 10_000_000
BATCH = 1 << 19

#: counters that must not move: each one is a fallback or a recovery taken
FALLBACK_COUNTERS = ("device_degraded_total", "device_retries_total",
                     "dead_letter_records_total", "watchdog_trips_total",
                     "stall_detections_total")

_COMPILE = {"seconds": 0.0, "cache_hits": 0, "cache_misses": 0,
            "installed": False}


def _watch_compiles() -> None:
    """Sum XLA backend-compile time (a persistent-cache hit costs only its
    retrieval) and count persistent-cache hits/misses, from JAX's own
    monitoring events."""
    if _COMPILE["installed"]:
        return
    import jax.monitoring

    def on_duration(event: str, seconds: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            _COMPILE["seconds"] += seconds

    def on_event(event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            _COMPILE["cache_hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            _COMPILE["cache_misses"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)
    _COMPILE["installed"] = True


def _compile_since(before: dict) -> dict:
    return {"compile_s": round(_COMPILE["seconds"] - before["seconds"], 3),
            "compile_cache_hits": (_COMPILE["cache_hits"]
                                   - before["cache_hits"]),
            "compile_cache_misses": (_COMPILE["cache_misses"]
                                     - before["cache_misses"])}


def _device_block() -> dict:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def _leg_header(leg: str) -> dict:
    """What every leg's JSON line starts with: where it ran, on what."""
    import jax
    import jaxlib
    from flink_tpu import native

    dev = _device_block()
    return {"leg": leg, "platform": dev["platform"],
            "device_kind": dev["kind"], "n_devices": dev["count"],
            "jax": jax.__version__, "jaxlib": jaxlib.__version__,
            "native": native.NATIVE_AVAILABLE}


def _memory(field: str) -> Optional[int]:
    """Largest ``field`` of memory_stats() over the devices (None where
    the backend reports none, as the CPU does)."""
    import jax

    stats = [d.memory_stats() for d in jax.devices()]
    vals = [s[field] for s in stats if s and field in s]
    return max(vals) if vals else None


def _n_panes(n_events: int, batch: int) -> int:
    """Event-time span in panes: the whole stream plus the sliding
    window's W-1-pane tail must fit in the ring with 3 rows of headroom
    even if fire retirement lags ingest completely."""
    return max(4, min(RING - WINDOW_PANES - 2, n_events // batch))


def _make_inflight_gen(in_flight: int, n_events: int, span_ms: int,
                       seed: int):
    """Bids whose auction ids advance: event ``idx`` sees the newest id
    ``in_flight + idx * 12 * in_flight // n_events`` (half the ids in
    flight are new every pane of a 24-pane run), bids on it one time in
    four and otherwise on one of the ``in_flight`` ids before it; no id
    reaches ``13 * in_flight + 1``."""

    def gen(idx):
        u = ((idx + seed).astype(np.uint64) * np.uint64(MULT))
        newest = in_flight + (idx * (12 * in_flight)) // n_events
        cold = newest - 1 - (u % np.uint64(in_flight)).astype(np.int64)
        return {"auction": np.where(idx % 4 == 0, newest, cold),
                "price": (idx % 997) + 1,
                "ts": (idx * span_ms) // n_events}

    return gen


def _make_gen(n_keys: int, n_events: int, span_ms: int, seed: int):
    """Bid generator: numpy on the host, traced under jit on the device —
    identical values either way (uint64 wrap-around is the same)."""

    def gen(idx):
        u = (idx + seed).astype(np.uint64)
        auction = ((u * np.uint64(MULT)) % np.uint64(n_keys)).astype(np.int64)
        return {"auction": auction, "price": (idx % 997) + 1,
                "ts": (idx * span_ms) // n_events}

    return gen


# ----------------------------------------------------------------------
# plain numpy reference
# ----------------------------------------------------------------------

def q5_reference(n_keys: int, n_events: int, batch: int, seed: int,
                 make_gen: Callable = _make_gen,
                 n_panes: Optional[int] = None,
                 id_space: Optional[int] = None) -> dict:
    """window_end_ms -> (bids[ids], revenue[ids]) for every window
    holding data: np.bincount of auction and of price per (pane, key),
    summed over each window's W panes. Independent of the code under test.
    ``make_gen`` / ``n_panes``: another generator of this module and its
    span; ``id_space``: the ids it makes stay under this (``n_keys``
    where every key exists from the start)."""
    if n_panes is None:
        n_panes = _n_panes(n_events, batch)
    cols = make_gen(n_keys, n_events, n_panes * PANE_MS, seed)(
        np.arange(n_events, dtype=np.int64))
    n_keys = id_space or n_keys
    cell = (cols["ts"] // PANE_MS) * n_keys + cols["auction"]
    bids = np.bincount(cell, minlength=n_panes * n_keys) \
        .reshape(n_panes, n_keys)
    revenue = np.bincount(cell, weights=cols["price"],
                          minlength=n_panes * n_keys) \
        .astype(np.int64).reshape(n_panes, n_keys)    # exact: sums << 2^53
    out = {}
    for p_end in range(1, n_panes + WINDOW_PANES):
        panes = slice(max(0, p_end - WINDOW_PANES), p_end)
        out[p_end * PANE_MS] = (bids[panes].sum(axis=0),
                                revenue[panes].sum(axis=0))
    return out


def check_rows(rows: dict, reference: dict, topk: int) -> None:
    """Every emitted (window, auction, bids, revenue) row equals the
    reference's for that key, and each window's emitted keys are a correct
    top-k: all keys strictly above the k-th count, the rest tied AT it
    (uniform keys tie heavily, so which tied keys fill the last seats is
    free) — hence the emitted bids multiset equals the reference's."""
    ends = np.unique(rows["window_end"])
    assert ends.tolist() == sorted(reference), (ends.tolist(),
                                                sorted(reference))
    assert (rows["window_start"]
            == rows["window_end"] - WINDOW_PANES * PANE_MS).all()
    for end in ends.tolist():
        ref_bids, ref_rev = reference[end]
        sel = rows["window_end"] == end
        auction, bids = rows["auction"][sel], rows["bids"][sel]
        assert len(np.unique(auction)) == len(auction), f"dup keys @{end}"
        assert (ref_bids[auction] == bids).all(), f"bids differ @{end}"
        assert (ref_rev[auction] == rows["revenue"][sel]).all(), \
            f"revenue differs @{end}"
        k = min(topk, int(np.count_nonzero(ref_bids)))
        assert len(auction) == k, (end, len(auction), k)
        want = np.partition(ref_bids, len(ref_bids) - k)[len(ref_bids) - k:]
        assert np.array_equal(np.sort(bids), np.sort(want)), \
            f"top-{k} multiset differs @{end}"
        thr = want.min()
        assert np.array_equal(np.sort(auction[bids > thr]),
                              np.flatnonzero(ref_bids > thr)), \
            f"keys above the threshold differ @{end}"


def check_same_answer(rows: dict, other: dict) -> None:
    """Two runs of the same query agree: per window the same bids
    multiset, and the same keys wherever ties leave no freedom."""
    for end in np.unique(other["window_end"]).tolist():
        a, b = rows["window_end"] == end, other["window_end"] == end
        assert np.array_equal(np.sort(rows["bids"][a]),
                              np.sort(other["bids"][b])), end
        thr = other["bids"][b].min()
        for col in ("auction", "revenue"):
            assert np.array_equal(
                np.sort(rows[col][a][rows["bids"][a] > thr]),
                np.sort(other[col][b][other["bids"][b] > thr])), (col, end)


# ----------------------------------------------------------------------
# the job
# ----------------------------------------------------------------------

def _collecting_sink():
    from flink_tpu.core.functions import SinkFunction

    class Collect(SinkFunction):
        def __init__(self):
            self.batches = []

        def invoke_batch(self, batch):
            self.batches.append({f.name: np.asarray(batch.column(f.name))
                                 for f in batch.schema.fields})
            return True

    return Collect()


def run_q5(leg: str, aggregate: Callable, operator_cls, *, n_keys: int,
           n_events: int, batch: int, device: bool, seed: int,
           reference: dict, topk: int, make_gen: Callable = _make_gen,
           n_panes: Optional[int] = None,
           watermark_interval_s: Optional[float] = None
           ) -> tuple[dict, dict, list]:
    """One env.execute() of Q5. ``aggregate(windowed_stream, aggs)``
    picks the vertex (device_aggregate / mesh_aggregate). Returns (report,
    rows, the job's window operators) after checking the rows against the
    reference and the fallback counters against zero. ``make_gen`` /
    ``n_panes`` as ``q5_reference`` takes them; ``watermark_interval_s``
    sets ``pipeline.auto-watermark-interval`` (0: a watermark a batch)."""
    from flink_tpu.api import StreamExecutionEnvironment
    from flink_tpu.core import WatermarkStrategy
    from flink_tpu.core.config import PipelineOptions
    from flink_tpu.core.records import Schema
    from flink_tpu.metrics import DEVICE_STATS
    from flink_tpu.runtime.operators.device_window import AggSpec
    from flink_tpu.window import SlidingEventTimeWindows

    _watch_compiles()
    schema = Schema([("auction", np.int64), ("price", np.int64),
                     ("ts", np.int64)])
    span_ms = (n_panes or _n_panes(n_events, batch)) * PANE_MS
    env = StreamExecutionEnvironment.get_execution_environment()
    env.set_state_backend("tpu")
    env.config.set(PipelineOptions.BATCH_SIZE, batch)
    if watermark_interval_s is not None:
        env.config.set(PipelineOptions.AUTO_WATERMARK_INTERVAL,
                       watermark_interval_s)
    ws = WatermarkStrategy.for_monotonous_timestamps() \
        .with_timestamp_column("ts")
    sink = _collecting_sink()
    windowed = (env.datagen(make_gen(n_keys, n_events, span_ms, seed),
                            schema, count=n_events, timestamp_column="ts",
                            watermark_strategy=ws, device=device)
                .key_by("auction")
                .window(SlidingEventTimeWindows.of(WINDOW_PANES * PANE_MS,
                                                   PANE_MS)))
    aggregate(windowed, [AggSpec("count", out_name="bids", value_bits=31),
                         AggSpec("sum", "price", out_name="revenue")]
              ).add_sink(sink, "collect")

    before = DEVICE_STATS.snapshot()
    compile_before = dict(_COMPILE)
    t0 = time.perf_counter()
    env.execute(leg, timeout=1100.0)
    wall = time.perf_counter() - t0
    after = DEVICE_STATS.snapshot()

    rows = {name: np.concatenate([b[name] for b in sink.batches])
            for name in sink.batches[0]}
    check_rows(rows, reference, topk)
    ops = [o for task in env.last_job.tasks.values()
           for o in getattr(getattr(task, "chain", None), "operators", ())
           if isinstance(o, operator_cls)]
    assert len(ops) == 1, ops
    report = {**_leg_header(leg), "n_keys": n_keys, "batch": batch,
              "events": n_events, "device_born": device,
              "windows": len(np.unique(rows["window_end"])),
              "rows": len(rows["auction"]),
              "wall_s": round(wall, 3), **_compile_since(compile_before),
              "h2d_bytes": after["h2d_bytes"] - before["h2d_bytes"],
              "d2h_bytes": after["d2h_bytes"] - before["d2h_bytes"],
              "late_dropped": ops[0].late_dropped,
              "peak_bytes_in_use": _memory("peak_bytes_in_use")}
    for k in FALLBACK_COUNTERS:
        report[k] = after.get(k, 0) - before.get(k, 0)
        assert report[k] == 0, (k, report[k])
    assert report["late_dropped"] == 0, report["late_dropped"]
    return report, rows, ops


def leg_q5_single(leg: str, *, n_keys: int, capacity: int, batch: int,
                  n_events: int, device: bool, seed: int, reference: dict,
                  topk: int = TOPK) -> tuple[dict, dict]:
    """Q5 on the single-chip window operator (the shape of bench._run_q5,
    window bounds emitted)."""
    from flink_tpu.runtime.operators.device_window import \
        DeviceWindowAggOperator

    def aggregate(windowed, aggs):
        return windowed.device_aggregate(
            aggs, capacity=capacity, ring_size=RING,
            emit_window_bounds=True, emit_topk=topk, defer_overflow=True,
            async_fire=True)

    report, rows, _ops = run_q5(
        leg, aggregate, DeviceWindowAggOperator, n_keys=n_keys,
        n_events=n_events, batch=batch, device=device, seed=seed,
        reference=reference, topk=topk)
    report["capacity"] = capacity
    if not device:
        assert report["h2d_bytes"] > 0, "host-born leg uploaded nothing"
    return report, rows


def leg_q5_mesh(*, n_keys: int, capacity_per_device: int, batch: int,
                n_events: int, seed: int, reference: dict,
                single_chip_rows: dict, topk: int = TOPK
                ) -> tuple[dict, dict]:
    """Q5 through the mesh vertex over every visible device, host-born
    batches; the answer must equal the single-chip leg's."""
    import jax
    from flink_tpu.runtime.operators.mesh_window import \
        MeshWindowAggOperator

    n_dev = len(jax.devices())

    def aggregate(windowed, aggs):
        return windowed.mesh_aggregate(
            aggs, n_devices=n_dev, capacity=capacity_per_device,
            ring_size=RING, device_batch=batch // n_dev,
            emit_window_bounds=True, emit_topk=topk, async_fire=True)

    report, rows, ops = run_q5(
        "q5-mesh", aggregate, MeshWindowAggOperator, n_keys=n_keys,
        n_events=n_events, batch=batch, device=False, seed=seed,
        reference=reference, topk=topk)
    check_same_answer(rows, single_chip_rows)
    state = ops[0]._state
    shard_ids = sorted(s.device.id for s in state.table.addressable_shards)
    assert len(set(shard_ids)) == n_dev, shard_ids
    report.update(capacity_per_device=ops[0]._agg.capacity,
                  state_device_ids=shard_ids,
                  equals_single_chip=True)
    # the capacity asked for must have held: a grown table means the leg
    # ran a rebuild it did not size for
    assert ops[0]._agg.capacity == capacity_per_device, ops[0]._agg.capacity
    return report, rows


def leg_q5_mesh_inflight(*, capacity_per_device: int, batch: int, seed: int,
                         topk: int = TOPK, n_panes: int = 24) -> dict:
    """The mesh vertex over auction ids that advance, one batch a pane:
    a tenth of all slots in flight at once and thirteen times that many
    ids in the run, so the tables fill to their load limit again and
    again and only the reclaim of dead ids' slots keeps them at their
    capacity. Rows equal numpy's; every shard ends as large as it began
    and has been swept."""
    import jax
    from flink_tpu.metrics import DEVICE_STATS
    from flink_tpu.runtime.operators.mesh_window import \
        MeshWindowAggOperator

    n_dev = len(jax.devices())
    in_flight = n_dev * capacity_per_device // 10
    shape = dict(n_keys=in_flight, n_events=n_panes * batch, batch=batch,
                 seed=seed, make_gen=_make_inflight_gen, n_panes=n_panes)

    def aggregate(windowed, aggs):
        return windowed.mesh_aggregate(
            aggs, n_devices=n_dev, capacity=capacity_per_device,
            ring_size=RING, device_batch=batch // n_dev,
            emit_window_bounds=True, emit_topk=topk, async_fire=True)

    before = DEVICE_STATS.snapshot()
    # a watermark a batch: the panes retire as the ids advance, however
    # fast the host runs ahead of the 0.2 s the default leaves between two
    report, _rows, ops = run_q5(
        "q5-mesh-inflight", aggregate, MeshWindowAggOperator, device=False,
        reference=q5_reference(id_space=14 * in_flight, **shape),
        topk=topk, watermark_interval_s=0.0, **shape)
    after = DEVICE_STATS.snapshot()
    report.update(
        capacity_per_device=ops[0]._agg.capacity, in_flight=in_flight,
        **{k: after[k] - before[k] for k in (
            "state_reclaim_sweeps_total", "state_reclaim_keys_kept_total",
            "state_reclaim_keys_freed_total")})
    assert ops[0]._agg.capacity == capacity_per_device, ops[0]._agg.capacity
    assert report["state_reclaim_sweeps_total"] >= 2, report
    assert report["state_reclaim_keys_freed_total"] > in_flight, report
    return report


def q11_reference(gen: Callable, n_events: int, gap_ms: int) -> set:
    """Per-bidder sessions of the whole stream, in plain numpy: sort by
    (bidder, ts), cut where a bidder changes or two of its bids lie the
    gap or more apart; a session is (bidder, first, last + gap, bids)."""
    cols = gen(np.arange(n_events, dtype=np.int64))
    order = np.lexsort((cols["ts"], cols["bidder"]))
    b, t = cols["bidder"][order], cols["ts"][order]
    cut = np.flatnonzero(np.r_[True, (b[1:] != b[:-1])
                               | (t[1:] - t[:-1] >= gap_ms)])
    end = np.r_[cut[1:], n_events] - 1
    return set(zip(b[cut].tolist(), t[cut].tolist(),
                   (t[end] + gap_ms).tolist(), (end - cut + 1).tolist()))


def leg_q11_sessions(*, n_keys: int, capacity: int, batch: int,
                     n_events: int, seed: int, gap_ms: int = 2000,
                     span_ms: int = 16000) -> dict:
    """NEXmark Q11 in small: per-bidder SESSION(gap) COUNT(*) through
    ``env.execute()`` and ``DeviceSessionWindowOperator`` with
    ``async_fire``, three bids in four on a hot bidder that moves every
    batch, the rest uniform over ``n_keys``; every session equals
    numpy's, the fires ran at their cadence, and no lane overflowed."""
    from flink_tpu.api import StreamExecutionEnvironment
    from flink_tpu.core import WatermarkStrategy
    from flink_tpu.core.config import PipelineOptions
    from flink_tpu.core.records import Schema
    from flink_tpu.metrics import DEVICE_STATS
    from flink_tpu.runtime.operators.device_session import \
        DeviceSessionWindowOperator
    from flink_tpu.runtime.operators.device_window import AggSpec
    from flink_tpu.window import EventTimeSessionWindows

    def gen(idx):
        u = ((idx + seed).astype(np.uint64) * np.uint64(MULT))
        cold = (u % np.uint64(n_keys)).astype(np.int64)
        return {"bidder": np.where(idx % 4 == 0, cold,
                                   n_keys + idx // batch),
                "ts": (idx * span_ms) // n_events}

    _watch_compiles()
    leg = "q11-sessions"
    schema = Schema([("bidder", np.int64), ("ts", np.int64)])
    env = StreamExecutionEnvironment.get_execution_environment()
    env.set_state_backend("tpu")
    env.config.set(PipelineOptions.BATCH_SIZE, batch)
    # a watermark a batch: a bidder's sessions close as the stream moves
    # on, however fast the host runs ahead of the default's 0.2 s (four
    # lanes hold the sessions of one bidder that have not fired yet)
    env.config.set(PipelineOptions.AUTO_WATERMARK_INTERVAL, 0.0)
    ws = WatermarkStrategy.for_monotonous_timestamps() \
        .with_timestamp_column("ts")
    sink = _collecting_sink()
    env.datagen(gen, schema, count=n_events, timestamp_column="ts",
                watermark_strategy=ws, device=False) \
        .key_by("bidder") \
        .window(EventTimeSessionWindows.with_gap(gap_ms)) \
        .device_aggregate([AggSpec("count", out_name="bid_count")],
                          capacity=capacity, ring_size=4,
                          emit_window_bounds=True, async_fire=True) \
        .add_sink(sink, "collect")
    before = DEVICE_STATS.snapshot()
    compile_before = dict(_COMPILE)
    t0 = time.perf_counter()
    env.execute(leg, timeout=1100.0)
    wall = time.perf_counter() - t0
    after = DEVICE_STATS.snapshot()
    rows = {name: np.concatenate([b[name] for b in sink.batches])
            for name in sink.batches[0]}
    got = list(zip(rows["bidder"].tolist(), rows["window_start"].tolist(),
                   rows["window_end"].tolist(), rows["bid_count"].tolist()))
    want = q11_reference(gen, n_events, gap_ms)
    assert len(got) == len(set(got)), "a session was emitted twice"
    assert set(got) == want, (len(got), len(want),
                              sorted(set(got) ^ want)[:5])
    ops = [o for task in env.last_job.tasks.values()
           for o in getattr(getattr(task, "chain", None), "operators", ())
           if isinstance(o, DeviceSessionWindowOperator)]
    assert len(ops) == 1, ops
    report = {**_leg_header(leg), "n_keys": n_keys, "batch": batch,
              "events": n_events, "sessions": len(got),
              "wall_s": round(wall, 3), **_compile_since(compile_before),
              "late_dropped": ops[0].late_dropped,
              "capacity": int(ops[0]._backend.capacity),
              "peak_bytes_in_use": _memory("peak_bytes_in_use"),
              **{k: after[k] - before[k] for k in after
                 if k.startswith("session_")}}
    for k in FALLBACK_COUNTERS:
        report[k] = after.get(k, 0) - before.get(k, 0)
        assert report[k] == 0, (k, report[k])
    assert report["late_dropped"] == 0, report["late_dropped"]
    assert report["capacity"] == capacity, report["capacity"]
    assert report["session_fired_total"] == len(got), report
    assert report["session_lane_overflow_total"] == 0, report
    assert report["session_fires_total"] >= 2, report
    return report


def leg_pallas_topk(sizes=(1 << 21, 1 << 24), k: int = TOPK,
                    value_bits: int = 31, interpret: bool = False,
                    seed: int = 0) -> dict:
    """masked_topk_pallas under x64 — compiled unless ``interpret`` —
    against ops.topk.masked_topk and numpy. Values are drawn from a narrow
    range so the k-th value is heavily tied, like a window's counts."""
    import jax.numpy as jnp
    from flink_tpu.ops.hash_table import ensure_x64
    from flink_tpu.ops.pallas_topk import masked_topk_pallas
    from flink_tpu.ops.topk import masked_topk

    ensure_x64()
    _watch_compiles()
    compile_before = dict(_COMPILE)
    t0 = time.perf_counter()
    for n in sizes:
        rng = np.random.default_rng(seed + n)
        vals = rng.integers(0, 50_000, n, dtype=np.int64)
        valid = rng.random(n) < 0.5
        want = np.sort(vals[valid])[::-1][:k]
        assert len(want) == k, "size too small for k"
        got_v, got_i, got_ok = (np.asarray(x) for x in masked_topk_pallas(
            jnp.asarray(vals), jnp.asarray(valid), k,
            value_bits=value_bits, interpret=interpret))
        xla_v, _xla_i, xla_ok = (np.asarray(x) for x in masked_topk(
            jnp.asarray(vals), jnp.asarray(valid), k, value_bits))
        assert got_ok.all() and xla_ok.all()
        assert np.array_equal(got_v, want), f"pallas != numpy at n={n}"
        assert np.array_equal(got_v, xla_v), f"pallas != xla at n={n}"
        assert len(np.unique(got_i)) == k and valid[got_i].all()
        assert np.array_equal(vals[got_i], got_v)
    return {**_leg_header("pallas-topk"), "sizes": list(sizes), "k": k,
            "value_bits": value_bits, "interpret": interpret, "x64": True,
            "wall_s": round(time.perf_counter() - t0, 3),
            **_compile_since(compile_before),
            "peak_bytes_in_use": _memory("peak_bytes_in_use")}


def _emit(report: dict) -> None:
    """One JSON line per leg, after the leg's job and state are released:
    the next leg needs the HBM (the host-born leg alone peaks at 12.2 of
    16 GB), and the line shows what is still held."""
    gc.collect()
    report["bytes_in_use_after_release"] = _memory("bytes_in_use")
    print(json.dumps(report), flush=True)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--seed", type=int, default=0,
                        help="offsets the bid generator's index stream")
    args = parser.parse_args(argv)

    import jax

    device = _device_block()
    if device["platform"] != "tpu":
        print(f"chip_smoke: needs a TPU, jax.devices()[0] is "
              f"{device['platform']!r}; no leg was run", file=sys.stderr)
        return 2
    from flink_tpu.utils.compile_cache import place_compile_cache

    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = None
    print(json.dumps({"smoke": "start", "device": device,
                      "jax": jax.__version__, "libtpu": libtpu,
                      "compile_cache_dir": place_compile_cache(),
                      "seed": args.seed}), flush=True)

    n_dev = device["count"]
    ref_24 = q5_reference(N_KEYS, 1 << 24, BATCH, args.seed)
    report, _rows = leg_q5_single(
        "q5-10M-device", n_keys=N_KEYS, capacity=1 << 24, batch=BATCH,
        n_events=1 << 24, device=True, seed=args.seed, reference=ref_24)
    del ref_24, _rows
    _emit(report)

    ref_22 = q5_reference(N_KEYS, 1 << 22, BATCH, args.seed)
    report, host_rows = leg_q5_single(
        "q5-10M-host", n_keys=N_KEYS, capacity=1 << 24, batch=BATCH,
        n_events=1 << 22, device=False, seed=args.seed, reference=ref_22)
    _emit(report)

    # sized by the operator's growth threshold alone: 2^22 events touch
    # ~3.4M distinct keys, so 2^23 slots a device on one or two chips and
    # 2^22 on four keep every shard under 0.6. Memory no longer decides:
    # the mesh step donates its state (2^23 slots are 2.2 GB of table and
    # planes, built shard by shard) and needs beside it the flat copy of
    # each plane, 3.3 GB (PERF.md section 5); undonated it held the state
    # three times
    report, _rows = leg_q5_mesh(
        n_keys=N_KEYS, capacity_per_device=(1 << 24) // max(n_dev, 2),
        batch=BATCH, n_events=1 << 22, seed=args.seed, reference=ref_22,
        single_chip_rows=host_rows)
    del ref_22, host_rows, _rows
    _emit(report)

    # short: 24 batches of 2^18 bids (2.5 a pane for each id in flight,
    # so that nearly every id is bid on) over 2^20 slots in all
    _emit(leg_q5_mesh_inflight(capacity_per_device=(1 << 20) // n_dev,
                               batch=1 << 18, seed=args.seed))

    # sessions: 2^20 bidders bid over 16 s, gap 2 s: about 3M sessions
    # through the lanes of a 2^22-slot table, fired every 0.4 s of event
    # time in rounds of 2^18
    _emit(leg_q11_sessions(n_keys=1 << 20, capacity=1 << 22, batch=BATCH,
                           n_events=1 << 22, seed=args.seed))

    _emit(leg_pallas_topk(seed=args.seed))

    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
