"""Device-path accounting: compiles, program-cache hits, transfers.

The compiled fire/step programs are process-global ``lru_cache``-backed
builders (one executable shared by every operator instance with the same
shape signature — see runtime/operators/device_window.py), so their
accounting is process-global too: one ``DeviceStats`` singleton that the
instrumented builders and the explicit transfer sites feed, readable from
any ``MetricRegistry`` through ``bind_device_metrics`` (gauges under the
``device`` scope) and as a flat dict through ``snapshot()`` (what
bench.py embeds in its stage reports).

Analog of the reference's compile/IO visibility split: Flink counts
bytes/records per task (TaskIOMetricGroup) and DrJAX-style JAX pipelines
treat compiled-program reuse as a measured resource — a recompile in the
hot path costs seconds to a minute on the chip, so
``compiles`` staying flat across identical-shape fires is the invariant
this module exists to watch.
"""

from __future__ import annotations

import collections
import functools
import re
import threading
import time
from typing import Any, Callable, Optional

from .profiler import DEVICE_LEDGER

__all__ = ["DeviceStats", "DEVICE_STATS", "instrumented_program_cache",
           "bind_device_metrics", "set_compile_tracer", "pytree_nbytes",
           "PROGRAM_AUDIT", "ProgramAuditEntry", "clear_program_audit",
           "REGION_SCOPES", "PATH_SCOPES", "UNNAMED", "classify_hlo",
           "program_regions"]


#: the forms a window operator's hidden plane ``__count__`` takes: kind
#: and width (``DeviceWindowAggOperator._count_plane``, ``ShardedWindowAgg``)
COUNT_PLANE_FORMS = ("presence32", "count32", "count64")


def count_plane_form(kind: str, dtype) -> str:
    """A hidden plane's form, of ``COUNT_PLANE_FORMS``: its kind and its
    width in bits."""
    import numpy as np

    return f"{kind}{8 * np.dtype(dtype).itemsize}"


class DeviceStats:
    """Process-global compile + transfer counters (thread-safe)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._compiles: dict[str, int] = {}
        self._cache_hits: dict[str, int] = {}
        self._compile_ms: dict[str, float] = {}
        self.h2d_bytes = 0
        self.h2d_records = 0
        self.h2d_batches = 0
        self.d2h_bytes = 0
        self.d2h_records = 0
        self.d2h_fires = 0
        # robustness accounting (PR 2): retries/degradations per scope,
        # dead-letter quarantines, and injected-fault trips per site
        self._retries: dict[str, int] = {}
        self._degraded: dict[str, int] = {}
        self._injected: dict[str, int] = {}
        self.dead_letter_records = 0
        self.dead_letter_batches = 0
        # stall accounting (PR 3): watchdog deadline expiries per site,
        # task-progress / backpressure stall detections per scope
        self._watchdog_trips: dict[str, int] = {}
        self._stalls: dict[str, int] = {}
        # verified-recovery accounting (PR 4): restore-candidate artifact
        # verification failures and restore fallbacks per scope
        self._verify_failures: dict[str, int] = {}
        self._restore_fallbacks: dict[str, int] = {}
        # partition-tolerance accounting (PR 5): channel reconnects per
        # scope (data/control), replayed frames deduped at the receiver,
        # stale-epoch peers fenced, and swallowed-no-longer socket
        # errors per direction (accept/receive/credit/send)
        self._net_reconnects: dict[str, int] = {}
        self._frames_deduped: dict[str, int] = {}
        self._zombies_fenced: dict[str, int] = {}
        self._net_errors: dict[str, int] = {}
        # tracing accounting (PR 7): spans evicted from the bounded
        # in-memory trace reporter (traces.max-retained)
        self._spans_dropped = 0
        # coalesced-ingest accounting (PR 8): upstream micro-batches
        # merged into coalesced dispatches
        self._batches_coalesced = 0
        # drain accounting (PR 25): non-blocking drains that found the
        # oldest queued fire's device->host copy not landed yet
        self._fire_unready_polls = 0
        # fires taken off the async fire queue and their rows emitted, and
        # those of them that left on the mailbox's processing-time turn
        # (PR 29) and not on a batch's or a blocking drain
        self._fires_drained = 0
        self._fires_drained_timer = 0
        # hash-probe accounting (PR 26): rows probed by the deferred ingest
        # path, rows still unresolved after the probe's read-only first
        # window (the tail its claiming loop carries), and batches whose
        # tail did not fit the narrow loop and looped at full width. Device
        # counters, handed over without a sync (state/tpu_backend.py), so
        # they trail the device by a batch or two until a blocking flush
        self._probe_rows = 0
        self._probe_tail_rows = 0
        self._probe_wide_batches = 0
        # of the wide batches, those whose unresolved rows elected one
        # lane a distinct key for the rounds, and the rows that stood
        # behind a representative (the same device vector's other half)
        self._probe_elected_rows = 0
        self._probe_elected_batches = 0
        # rows whose first window, read from the slots' low 32-bit words
        # (PR 52), could not say what it holds: the one candidate's high
        # word was neither the key's nor EMPTY's. 0 for keys under 2^32
        self._probe_undecided_rows = 0
        # mesh step accounting (PR 27): steps of the sharded window
        # program and the keyBy exchange rounds they took (one for a
        # batch spread evenly over the shards, more under skew). Read
        # from each step's own output once its copy has landed
        # (runtime/operators/mesh_window.py), so they trail the devices
        # by the steps in flight
        self._mesh_steps = 0
        self._mesh_exchange_rounds = 0
        # the mesh operator's one host wait (PR 53): times its task's
        # thread WAITED for a reading that had not landed (a pressure
        # probe, a reclaim's counts: half the headroom stepped since it
        # went out, or a snapshot / rebuild / finish settling a reclaim)
        # and the microseconds it stood there
        self._mesh_reading_waits = 0
        self._mesh_reading_wait_us = 0.0
        # mesh insert accounting (PR 41): rows of the mesh step that
        # claimed a new slot (all shards), over the rows stepped between
        # the two readings of the shards' occupied slots that say so
        # (the pressure probe's, or a reclaim's kept keys): how
        # insert-heavy the blocks were, read and not inferred
        self._mesh_inserted_rows = 0
        self._mesh_stepped_rows = 0
        # fire select accounting (PR 31): ranked mesh fires, the
        # compare-and-count passes their threshold selects walked (the
        # longest shard's; the bit length of the largest rank) and how
        # many handed a shard to the sort (a float rank, or a negative
        # one). Read from the fire's own outputs in the copy its drain
        # makes anyway
        self._fire_selects = 0
        self._fire_select_passes = 0
        self._fire_select_sort = 0
        self._fire_select_guarded = 0
        # the form of a window operator's hidden plane ``__count__``,
        # counted once an operator when its planes are built: a job that
        # reads no count (no COUNT, no AVG) keeps a 32-bit presence
        # plane, one that reads it a count of 32 or 64 bits
        self._count_planes = dict.fromkeys(COUNT_PLANE_FORMS, 0)
        # ring fold accounting (PR 34; the mesh operator's blocks since
        # PR 36): host-born batches folded and the ring rows they held a
        # row for, which are the rows of each plane the fold slices,
        # scatters into and writes back (ops/segment_ops.ring_fold);
        # counted on the host from the batch's own ring indices
        self._fold_batches = 0
        self._fold_ring_rows = 0
        # out-of-order input (PR 51): the batches (mesh blocks) that held
        # rows of more than two ring rows and so went up sorted by ring
        # row, and the rows whose pane lay under the newest pane the
        # operator had seen before their batch (0 for a stream in order)
        self._fold_sorted_batches = 0
        self._fold_back_rows = 0
        # the limb scatters those folds ran (PR 54): an additive 64-bit
        # plane takes a batch limb by limb, one 32-bit scatter-add pass a
        # limb that some row of a touched ring row holds a non-zero value
        # in (ops/segment_ops.ring_fold). Counted BY the device program,
        # whose count rides to the host with the probe's counters (one
        # chip) or the step's round count (mesh: the busiest shard's)
        self._fold_limb_scatters = 0
        # state reclaim accounting (PR 35; the mesh operator's since
        # PR 41, one sweep a dispatch, keys summed over the shards):
        # sweeps of the reclaim (state/tpu_backend.py reclaim_shard: a
        # table rebuilt at its own capacity from the keys that still hold
        # data in a ring row) and the keys they kept and freed
        self._reclaim_sweeps = 0
        self._reclaim_kept = 0
        self._reclaim_freed = 0
        # session operator accounting (PR 43): fires (one boundary each),
        # the rounds they took (a round compacts at most the operator's
        # ``fire_rows`` ripe sessions), the sessions they emitted and
        # the rows their drains handed on; lanes the steps allocated,
        # segments that found no free lane, segments settled inside a
        # batch (they bypass the lanes). The step's three are device
        # counters that ride back with a fire round's copy, so they
        # trail the device by the batches since the last drained round
        self._session_fires = 0
        self._session_fire_rounds = 0
        self._session_fired = 0
        self._session_rows_drained = 0
        self._session_lanes_allocated = 0
        self._session_lane_overflow = 0
        self._session_settled = 0
        # whole-chain fusion accounting (PR 11): micro-batches ingested
        # through a certified fused chain program — ONE dispatch covering
        # source-decode + window step (graph/fusion.py certificate)
        self._chain_dispatches = 0
        # live-rescale accounting (PR 12): worker-set changes applied
        # without a restart, key groups whose owner changed, page bytes
        # shipped through the checkpoint transfer format, and total time
        # spent inside the barrier-aligned switch
        self._rescales = 0
        self._keygroups_migrated = 0
        self._rescale_bytes_moved = 0
        self._rescale_ms = 0.0
        # tiered-state accounting (PR 15): key groups demoted to the
        # host-warm tier / promoted back, hot-tier touch ratio (accesses
        # landing on device-resident groups over all accesses), and the
        # latest HBM bytes held by the keyed-state planes
        self._tier_evictions = 0
        self._tier_evicted_keys = 0
        self._tier_prefetches = 0
        self._tier_promoted_keys = 0
        self._tier_hot_touches = 0
        self._tier_touches = 0
        self._tier_hbm_bytes = 0
        # coordinator-failover accounting (PR 18): leader elections won
        # per scope, takeovers completed per mode (hot/restore), and a
        # bounded list of takeover durations for the failover histogram
        self._leader_elections: dict[str, int] = {}
        self._failovers: dict[str, int] = {}
        self._takeover_ms: list[float] = []
        # AOT executable cache accounting (PR 19): persistent-cache hits
        # and misses per scope, executables persisted, dispatch-time
        # fallbacks from a loaded executable to the live jit path,
        # in-memory program-cache LRU evictions, live XLA compiles paid
        # while the persistent cache was active (the compile storm a
        # warmed process must not see), and the process cold-start clock:
        # configure-time mark -> first fired window (d2h fire)
        self._aot_hits: dict[str, int] = {}
        self._aot_misses: dict[str, int] = {}
        self._aot_stores: dict[str, int] = {}
        self._aot_fallbacks: dict[str, int] = {}
        self._aot_evictions = 0
        self._compile_storms: dict[str, int] = {}
        self._cold_start_ms: list[float] = []
        self._cold_start_t0: Optional[float] = None
        self._tracer = None  # optional Tracer receiving device spans

    # -- compile accounting ------------------------------------------------
    def note_build(self, scope: str) -> None:
        with self._lock:
            self._compiles[scope] = self._compiles.get(scope, 0) + 1

    def note_cache_hit(self, scope: str) -> None:
        with self._lock:
            self._cache_hits[scope] = self._cache_hits.get(scope, 0) + 1

    def note_compile_done(self, scope: str, ms: float,
                          start_ms: Optional[int] = None) -> None:
        with self._lock:
            self._compile_ms[scope] = self._compile_ms.get(scope, 0.0) + ms
            tracer = self._tracer
        if tracer is not None:
            sb = tracer.span("device", "Compile").set_attribute(
                "scope", scope).set_attribute("ms", round(ms, 3))
            if start_ms is not None:
                sb.set_start_ts(start_ms)
            sb.finish()

    # -- AOT executable-cache accounting -------------------------------------
    def note_aot_hit(self, scope: str) -> None:
        with self._lock:
            self._aot_hits[scope] = self._aot_hits.get(scope, 0) + 1

    def note_aot_miss(self, scope: str) -> None:
        with self._lock:
            self._aot_misses[scope] = self._aot_misses.get(scope, 0) + 1

    def note_aot_store(self, scope: str) -> None:
        with self._lock:
            self._aot_stores[scope] = self._aot_stores.get(scope, 0) + 1

    def note_aot_fallback(self, scope: str) -> None:
        with self._lock:
            self._aot_fallbacks[scope] = self._aot_fallbacks.get(scope, 0) + 1

    def note_aot_eviction(self, n: int = 1) -> None:
        with self._lock:
            self._aot_evictions += int(n)

    def note_compile_storm(self, scope: str) -> None:
        """A live XLA compile paid while the persistent AOT cache was
        active — zero on a properly warmed process is the recovery
        contract."""
        with self._lock:
            self._compile_storms[scope] = \
                self._compile_storms.get(scope, 0) + 1

    def mark_cold_start(self) -> None:
        """Start the cold-start clock (idempotent until the first fired
        window records it): called when an AOT-enabled deploy configures
        this process."""
        with self._lock:
            if self._cold_start_t0 is None and not self._cold_start_ms:
                self._cold_start_t0 = time.perf_counter()

    # -- transfer accounting -----------------------------------------------
    def note_h2d(self, nbytes: int, records: int = 0,
                 ms: Optional[float] = None) -> None:
        with self._lock:
            self.h2d_bytes += int(nbytes)
            self.h2d_records += int(records)
            self.h2d_batches += 1
            tracer = self._tracer
        if tracer is not None:
            self._finish_transfer(tracer.span("device", "H2D"),
                                  nbytes, records, ms)
        DEVICE_LEDGER.record("transfer.h2d", ms or 0.0, nbytes=nbytes)

    def note_d2h(self, nbytes: int, records: int = 0,
                 ms: Optional[float] = None) -> None:
        with self._lock:
            self.d2h_bytes += int(nbytes)
            self.d2h_records += int(records)
            self.d2h_fires += 1
            if self._cold_start_t0 is not None:
                # first materialized result since the AOT-enabled deploy
                # marked this process cold: a time-to-first-fired-window
                # sample
                self._cold_start_ms.append(
                    (time.perf_counter() - self._cold_start_t0) * 1e3)
                del self._cold_start_ms[:-256]
                self._cold_start_t0 = None
            tracer = self._tracer
        if tracer is not None:
            self._finish_transfer(tracer.span("device", "D2H"),
                                  nbytes, records, ms)
        DEVICE_LEDGER.record("transfer.d2h", ms or 0.0, nbytes=nbytes)

    @staticmethod
    def _finish_transfer(sb, nbytes: int, records: int,
                         ms: Optional[float]) -> None:
        from .tracing import now_ms
        end = now_ms()
        sb.set_attribute("bytes", int(nbytes))
        sb.set_attribute("records", int(records))
        sb.set_start_ts(end - int(ms) if ms else end)
        sb.finish(end)

    # -- robustness accounting ---------------------------------------------
    def note_retry(self, scope: str, n: int = 1) -> None:
        with self._lock:
            self._retries[scope] = self._retries.get(scope, 0) + n

    def note_degraded(self, scope: str) -> None:
        with self._lock:
            self._degraded[scope] = self._degraded.get(scope, 0) + 1

    def note_injected(self, site: str) -> None:
        with self._lock:
            self._injected[site] = self._injected.get(site, 0) + 1

    def note_dead_letter(self, records: int, batches: int = 1) -> None:
        with self._lock:
            self.dead_letter_records += int(records)
            self.dead_letter_batches += int(batches)

    def note_watchdog_trip(self, site: str) -> None:
        with self._lock:
            self._watchdog_trips[site] = \
                self._watchdog_trips.get(site, 0) + 1

    def note_stall(self, scope: str) -> None:
        with self._lock:
            self._stalls[scope] = self._stalls.get(scope, 0) + 1

    def note_verify_failure(self, scope: str) -> None:
        with self._lock:
            self._verify_failures[scope] = \
                self._verify_failures.get(scope, 0) + 1
        from .tracing import dump_flight_recorder
        dump_flight_recorder("corrupt-artifact", scope=scope)

    def note_restore_fallback(self, scope: str) -> None:
        with self._lock:
            self._restore_fallbacks[scope] = \
                self._restore_fallbacks.get(scope, 0) + 1

    # -- partition-tolerance accounting --------------------------------------
    def note_net_reconnect(self, scope: str) -> None:
        with self._lock:
            self._net_reconnects[scope] = \
                self._net_reconnects.get(scope, 0) + 1

    def note_frame_deduped(self, scope: str, n: int = 1) -> None:
        with self._lock:
            self._frames_deduped[scope] = \
                self._frames_deduped.get(scope, 0) + n

    def note_zombie_fenced(self, scope: str) -> None:
        with self._lock:
            self._zombies_fenced[scope] = \
                self._zombies_fenced.get(scope, 0) + 1
        from .tracing import dump_flight_recorder
        dump_flight_recorder("zombie-fenced", scope=scope)

    def note_net_error(self, direction: str) -> None:
        with self._lock:
            self._net_errors[direction] = \
                self._net_errors.get(direction, 0) + 1

    # -- coordinator-failover accounting -------------------------------------
    def note_leader_election(self, scope: str) -> None:
        with self._lock:
            self._leader_elections[scope] = \
                self._leader_elections.get(scope, 0) + 1

    def note_coordinator_failover(self, took_ms: float, mode: str) -> None:
        """A standby finished taking over a running job: ``mode`` is
        'hot' (all workers re-registered, no restart) or 'restore'
        (fenced global restore from the latest verified checkpoint)."""
        with self._lock:
            self._failovers[mode] = self._failovers.get(mode, 0) + 1
            self._takeover_ms.append(float(took_ms))
            del self._takeover_ms[:-256]

    # -- coalescing accounting -----------------------------------------------
    def note_batches_coalesced(self, n: int) -> None:
        with self._lock:
            self._batches_coalesced += int(n)

    def note_fire_unready_poll(self) -> None:
        with self._lock:
            self._fire_unready_polls += 1

    @property
    def fire_unready_polls(self) -> int:
        with self._lock:
            return self._fire_unready_polls

    def note_fire_drained(self, timer: bool) -> None:
        with self._lock:
            self._fires_drained += 1
            self._fires_drained_timer += bool(timer)

    @property
    def fires_drained(self) -> tuple[int, int]:
        """(fires drained, those drained on a processing-time turn)."""
        with self._lock:
            return self._fires_drained, self._fires_drained_timer

    def note_probe(self, rows: int, tail_rows: int, wide_batches: int,
                   elected_rows: int = 0, elected_batches: int = 0,
                   undecided_rows: int = 0) -> None:
        with self._lock:
            self._probe_rows += int(rows)
            self._probe_tail_rows += int(tail_rows)
            self._probe_wide_batches += int(wide_batches)
            self._probe_elected_rows += int(elected_rows)
            self._probe_elected_batches += int(elected_batches)
            self._probe_undecided_rows += int(undecided_rows)

    @property
    def probe_counts(self) -> tuple[int, int, int, int, int, int]:
        """(rows probed, tail rows, wide batches, rows that stood behind
        an elected representative, batches that elected, rows the first
        window left undecided)."""
        with self._lock:
            return (self._probe_rows, self._probe_tail_rows,
                    self._probe_wide_batches, self._probe_elected_rows,
                    self._probe_elected_batches, self._probe_undecided_rows)

    def note_mesh_steps(self, steps: int, rounds: int) -> None:
        with self._lock:
            self._mesh_steps += int(steps)
            self._mesh_exchange_rounds += int(rounds)

    @property
    def mesh_step_counts(self) -> tuple[int, int]:
        """(mesh steps, exchange rounds they took)."""
        with self._lock:
            return self._mesh_steps, self._mesh_exchange_rounds

    def note_mesh_reading_wait(self, us: float) -> None:
        with self._lock:
            self._mesh_reading_waits += 1
            self._mesh_reading_wait_us += float(us)

    @property
    def mesh_reading_wait_counts(self) -> tuple[int, float]:
        """(host waits for a mesh reading, microseconds waited)."""
        with self._lock:
            return self._mesh_reading_waits, self._mesh_reading_wait_us

    def note_mesh_inserts(self, inserted: int, stepped: int) -> None:
        with self._lock:
            self._mesh_inserted_rows += int(inserted)
            self._mesh_stepped_rows += int(stepped)

    @property
    def mesh_insert_counts(self) -> tuple[int, int]:
        """(rows that claimed a new slot, rows stepped between the
        readings that say so), all shards of the mesh step."""
        with self._lock:
            return self._mesh_inserted_rows, self._mesh_stepped_rows

    def note_fire_select(self, passes: int, sort: bool,
                         guarded: bool = False) -> None:
        with self._lock:
            self._fire_selects += 1
            self._fire_select_passes += int(passes)
            self._fire_select_sort += bool(sort)
            self._fire_select_guarded += bool(guarded)

    @property
    def fire_select_counts(self) -> tuple[int, int, int, int]:
        """(ranked fires of either window operator, select passes they
        walked, those that took a fallback: a float rank, those whose
        select was compiled with the guard against a negative rank: a
        signed integer rank with no ``value_bits`` promise under its
        width)."""
        with self._lock:
            return (self._fire_selects, self._fire_select_passes,
                    self._fire_select_sort, self._fire_select_guarded)

    def note_count_plane(self, form: str) -> None:
        with self._lock:
            self._count_planes[form] += 1

    @property
    def count_plane_counts(self) -> dict[str, int]:
        """Window operators of either stack by the form of their hidden
        plane (``COUNT_PLANE_FORMS``)."""
        with self._lock:
            return dict(self._count_planes)

    def note_fold(self, ring_rows: int, sorted_: bool = False) -> None:
        with self._lock:
            self._fold_batches += 1
            self._fold_ring_rows += int(ring_rows)
            self._fold_sorted_batches += bool(sorted_)

    def note_limb_scatters(self, scatters: int) -> None:
        with self._lock:
            self._fold_limb_scatters += int(scatters)

    @property
    def fold_limb_scatters(self) -> int:
        """Limb scatters the ring folds ran (``ring_fold``): over
        ``fold_counts[1]``, the live limbs a touched ring row (Q5's
        23-bit prices 2, its int64 COUNT on the mesh 1 more, a column
        with negative values 5)."""
        with self._lock:
            return self._fold_limb_scatters

    def note_fold_back(self, rows: int) -> None:
        with self._lock:
            self._fold_back_rows += int(rows)

    @property
    def fold_counts(self) -> tuple[int, int, int, int]:
        """(host-born batches or mesh blocks folded, ring rows they
        touched, those of them that went up sorted by ring row, rows
        whose pane was older than the newest pane seen before their
        batch)."""
        with self._lock:
            return (self._fold_batches, self._fold_ring_rows,
                    self._fold_sorted_batches, self._fold_back_rows)

    def note_reclaim(self, kept: int, freed: int) -> None:
        with self._lock:
            self._reclaim_sweeps += 1
            self._reclaim_kept += int(kept)
            self._reclaim_freed += int(freed)

    @property
    def reclaim_counts(self) -> tuple[int, int, int]:
        """(reclaim sweeps, keys they kept, keys they freed)."""
        with self._lock:
            return (self._reclaim_sweeps, self._reclaim_kept,
                    self._reclaim_freed)

    def note_session_round(self, fired: int, last: bool) -> None:
        """One drained round of a session fire: the sessions it took off
        the lanes, and whether it ended its fire."""
        with self._lock:
            self._session_fire_rounds += 1
            self._session_fired += int(fired)
            self._session_fires += bool(last)

    def note_session_rows(self, rows: int) -> None:
        """Session rows handed on: a round's, or settled ones' that were
        ripe at a watermark."""
        with self._lock:
            self._session_rows_drained += int(rows)

    def note_session_steps(self, lanes_allocated: int, lane_overflow: int,
                           settled: int) -> None:
        """What the session steps counted since the last reading."""
        with self._lock:
            self._session_lanes_allocated += int(lanes_allocated)
            self._session_lane_overflow += int(lane_overflow)
            self._session_settled += int(settled)

    def _session_counts(self) -> dict[str, int]:
        return {
            "session_fires_total": self._session_fires,
            "session_fire_rounds_total": self._session_fire_rounds,
            "session_fired_total": self._session_fired,
            "session_rows_drained_total": self._session_rows_drained,
            "session_lanes_allocated_total": self._session_lanes_allocated,
            "session_lane_overflow_total": self._session_lane_overflow,
            "session_settled_in_batch_total": self._session_settled}

    @property
    def session_counts(self) -> dict[str, int]:
        """The session operator's counters, under their snapshot names."""
        with self._lock:
            return self._session_counts()

    def note_chain_dispatch(self, n: int = 1) -> None:
        with self._lock:
            self._chain_dispatches += int(n)

    @property
    def chain_dispatches(self) -> int:
        with self._lock:
            return self._chain_dispatches

    @property
    def batches_coalesced(self) -> int:
        with self._lock:
            return self._batches_coalesced

    # -- live-rescale accounting ---------------------------------------------
    def note_rescale(self, keygroups_migrated: int, bytes_moved: int,
                     duration_ms: float) -> None:
        with self._lock:
            self._rescales += 1
            self._keygroups_migrated += int(keygroups_migrated)
            self._rescale_bytes_moved += int(bytes_moved)
            self._rescale_ms += float(duration_ms)

    @property
    def rescales(self) -> int:
        with self._lock:
            return self._rescales

    @property
    def keygroups_migrated(self) -> int:
        with self._lock:
            return self._keygroups_migrated

    @property
    def rescale_bytes_moved(self) -> int:
        with self._lock:
            return self._rescale_bytes_moved

    @property
    def rescale_ms(self) -> float:
        with self._lock:
            return self._rescale_ms

    # -- tiered-state accounting ---------------------------------------------
    def note_tier_eviction(self, groups: int, keys: int) -> None:
        with self._lock:
            self._tier_evictions += int(groups)
            self._tier_evicted_keys += int(keys)

    def note_tier_prefetch(self, groups: int, keys: int) -> None:
        with self._lock:
            self._tier_prefetches += int(groups)
            self._tier_promoted_keys += int(keys)

    def note_tier_touches(self, hot: int, total: int) -> None:
        with self._lock:
            self._tier_hot_touches += int(hot)
            self._tier_touches += int(total)

    def set_tier_hbm_bytes(self, nbytes: int) -> None:
        with self._lock:
            self._tier_hbm_bytes = int(nbytes)

    @property
    def tier_evictions(self) -> int:
        with self._lock:
            return self._tier_evictions

    @property
    def tier_prefetches(self) -> int:
        with self._lock:
            return self._tier_prefetches

    @property
    def tier_hot_hit_ratio(self) -> float:
        with self._lock:
            return self._tier_hot_touches / max(self._tier_touches, 1)

    @property
    def tier_hbm_bytes_in_use(self) -> int:
        with self._lock:
            return self._tier_hbm_bytes

    # -- tracing accounting --------------------------------------------------
    def note_spans_dropped(self, n: int = 1) -> None:
        with self._lock:
            self._spans_dropped += int(n)

    @property
    def spans_dropped(self) -> int:
        with self._lock:
            return self._spans_dropped

    @property
    def net_reconnects(self) -> int:
        with self._lock:
            return sum(self._net_reconnects.values())

    @property
    def frames_deduped(self) -> int:
        with self._lock:
            return sum(self._frames_deduped.values())

    @property
    def zombies_fenced(self) -> int:
        with self._lock:
            return sum(self._zombies_fenced.values())

    @property
    def net_errors(self) -> int:
        with self._lock:
            return sum(self._net_errors.values())

    @property
    def leader_elections(self) -> int:
        with self._lock:
            return sum(self._leader_elections.values())

    @property
    def coordinator_failovers(self) -> int:
        with self._lock:
            return sum(self._failovers.values())

    @property
    def verify_failures(self) -> int:
        with self._lock:
            return sum(self._verify_failures.values())

    @property
    def restore_fallbacks(self) -> int:
        with self._lock:
            return sum(self._restore_fallbacks.values())

    @property
    def watchdog_trips(self) -> int:
        with self._lock:
            return sum(self._watchdog_trips.values())

    @property
    def stall_detections(self) -> int:
        with self._lock:
            return sum(self._stalls.values())

    @property
    def retries(self) -> int:
        with self._lock:
            return sum(self._retries.values())

    @property
    def degraded(self) -> int:
        with self._lock:
            return sum(self._degraded.values())

    @property
    def injected_faults(self) -> int:
        with self._lock:
            return sum(self._injected.values())

    @property
    def aot_hits(self) -> int:
        with self._lock:
            return sum(self._aot_hits.values())

    @property
    def aot_misses(self) -> int:
        with self._lock:
            return sum(self._aot_misses.values())

    @property
    def aot_stores(self) -> int:
        with self._lock:
            return sum(self._aot_stores.values())

    @property
    def aot_fallbacks(self) -> int:
        with self._lock:
            return sum(self._aot_fallbacks.values())

    @property
    def aot_in_memory_evictions(self) -> int:
        with self._lock:
            return self._aot_evictions

    @property
    def compile_storms(self) -> int:
        with self._lock:
            return sum(self._compile_storms.values())

    # -- views -------------------------------------------------------------
    @property
    def compiles(self) -> int:
        with self._lock:
            return sum(self._compiles.values())

    @property
    def compile_cache_hits(self) -> int:
        with self._lock:
            return sum(self._cache_hits.values())

    @property
    def compile_ms(self) -> float:
        with self._lock:
            return sum(self._compile_ms.values())

    def snapshot(self) -> dict[str, Any]:
        """Flat cumulative view — the shape bench.py embeds per stage
        report and tests compare against the prometheus exposition."""
        with self._lock:
            out: dict[str, Any] = {
                "compiles": sum(self._compiles.values()),
                "compile_cache_hits": sum(self._cache_hits.values()),
                "compile_ms": round(sum(self._compile_ms.values()), 3),
                "h2d_bytes": self.h2d_bytes,
                "h2d_records": self.h2d_records,
                "h2d_batches": self.h2d_batches,
                "d2h_bytes": self.d2h_bytes,
                "d2h_records": self.d2h_records,
                "d2h_fires": self.d2h_fires,
                "device_retries_total": sum(self._retries.values()),
                "device_degraded_total": sum(self._degraded.values()),
                "dead_letter_records_total": self.dead_letter_records,
                "dead_letter_batches_total": self.dead_letter_batches,
                "injected_faults_total": sum(self._injected.values()),
                "watchdog_trips_total": sum(self._watchdog_trips.values()),
                "stall_detections_total": sum(self._stalls.values()),
                "checkpoint_verify_failures_total":
                    sum(self._verify_failures.values()),
                "restore_fallbacks_total":
                    sum(self._restore_fallbacks.values()),
                "network_reconnects_total":
                    sum(self._net_reconnects.values()),
                "frames_deduped_total":
                    sum(self._frames_deduped.values()),
                "zombies_fenced_total":
                    sum(self._zombies_fenced.values()),
                "network_errors_total": sum(self._net_errors.values()),
                "leader_elections_total":
                    sum(self._leader_elections.values()),
                "coordinator_failovers_total":
                    sum(self._failovers.values()),
                "spans_dropped_total": self._spans_dropped,
                "batches_coalesced_total": self._batches_coalesced,
                "fire_unready_polls_total": self._fire_unready_polls,
                "fires_drained_total": self._fires_drained,
                "fires_drained_timer_total": self._fires_drained_timer,
                "probe_rows_total": self._probe_rows,
                "probe_tail_rows_total": self._probe_tail_rows,
                "probe_undecided_rows_total": self._probe_undecided_rows,
                "probe_wide_batches_total": self._probe_wide_batches,
                "probe_elected_rows_total": self._probe_elected_rows,
                "probe_elected_batches_total": self._probe_elected_batches,
                "mesh_steps_total": self._mesh_steps,
                "mesh_exchange_rounds_total": self._mesh_exchange_rounds,
                "mesh_reading_waits_total": self._mesh_reading_waits,
                "mesh_reading_wait_us_total": round(
                    self._mesh_reading_wait_us, 1),
                "mesh_inserted_rows_total": self._mesh_inserted_rows,
                "mesh_stepped_rows_total": self._mesh_stepped_rows,
                "fire_selects_total": self._fire_selects,
                "fire_select_passes_total": self._fire_select_passes,
                "fire_select_sort_total": self._fire_select_sort,
                "fire_select_guarded_total": self._fire_select_guarded,
                **{f"count_plane_{form}_total": n
                   for form, n in self._count_planes.items()},
                "fold_batches_total": self._fold_batches,
                "fold_ring_rows_total": self._fold_ring_rows,
                "fold_sorted_batches_total": self._fold_sorted_batches,
                "fold_back_rows_total": self._fold_back_rows,
                "fold_limb_scatters_total": self._fold_limb_scatters,
                "state_reclaim_sweeps_total": self._reclaim_sweeps,
                "state_reclaim_keys_kept_total": self._reclaim_kept,
                "state_reclaim_keys_freed_total": self._reclaim_freed,
                **self._session_counts(),
                "chain_fused_dispatches_total": self._chain_dispatches,
                "rescales_total": self._rescales,
                "keygroups_migrated_total": self._keygroups_migrated,
                "rescale_bytes_moved_total": self._rescale_bytes_moved,
                "rescale_ms": round(self._rescale_ms, 3),
                "tier_evictions_total": self._tier_evictions,
                "tier_evicted_keys_total": self._tier_evicted_keys,
                "tier_prefetches_total": self._tier_prefetches,
                "tier_promoted_keys_total": self._tier_promoted_keys,
                "tier_hot_hit_ratio": round(
                    self._tier_hot_touches / max(self._tier_touches, 1), 6),
                "tier_hbm_bytes_in_use": self._tier_hbm_bytes,
            }
            tk = sorted(self._takeover_ms)
            out["takeover_duration_ms_count"] = len(tk)
            out["takeover_duration_ms_p50"] = (
                round(tk[len(tk) // 2], 3) if tk else 0.0)
            out["takeover_duration_ms_max"] = (
                round(tk[-1], 3) if tk else 0.0)
            out["aot_hits_total"] = sum(self._aot_hits.values())
            out["aot_misses_total"] = sum(self._aot_misses.values())
            out["aot_stores_total"] = sum(self._aot_stores.values())
            out["aot_fallbacks_total"] = sum(self._aot_fallbacks.values())
            out["aot_in_memory_evictions_total"] = self._aot_evictions
            out["compile_storms_total"] = \
                sum(self._compile_storms.values())
            cs = sorted(self._cold_start_ms)
            out["cold_start_ms_count"] = len(cs)
            out["cold_start_ms_p50"] = (
                round(cs[len(cs) // 2], 3) if cs else 0.0)
            out["cold_start_ms_max"] = (
                round(cs[-1], 3) if cs else 0.0)
            for scope, n in sorted(self._compiles.items()):
                out[f"compiles.{scope}"] = n
            for scope, n in sorted(self._retries.items()):
                out[f"retries.{scope}"] = n
            for scope, n in sorted(self._degraded.items()):
                out[f"degraded.{scope}"] = n
            for site, n in sorted(self._injected.items()):
                out[f"injected.{site}"] = n
            for site, n in sorted(self._watchdog_trips.items()):
                out[f"watchdog.{site}"] = n
            for scope, n in sorted(self._stalls.items()):
                out[f"stalls.{scope}"] = n
            for scope, n in sorted(self._verify_failures.items()):
                out[f"verify_failures.{scope}"] = n
            for scope, n in sorted(self._restore_fallbacks.items()):
                out[f"restore_fallbacks.{scope}"] = n
            for scope, n in sorted(self._net_reconnects.items()):
                out[f"net_reconnects.{scope}"] = n
            for scope, n in sorted(self._frames_deduped.items()):
                out[f"frames_deduped.{scope}"] = n
            for scope, n in sorted(self._zombies_fenced.items()):
                out[f"zombies_fenced.{scope}"] = n
            for direction, n in sorted(self._net_errors.items()):
                out[f"net_errors.{direction}"] = n
            for scope, n in sorted(self._leader_elections.items()):
                out[f"leader_elections.{scope}"] = n
            for mode, n in sorted(self._failovers.items()):
                out[f"coordinator_failovers.{mode}"] = n
            for scope, n in sorted(self._aot_hits.items()):
                out[f"aot_hits.{scope}"] = n
            for scope, n in sorted(self._aot_fallbacks.items()):
                out[f"aot_fallbacks.{scope}"] = n
            for scope, n in sorted(self._compile_storms.items()):
                out[f"compile_storms.{scope}"] = n
            return out

    def reset(self) -> None:
        """Test/bench isolation only — counters are otherwise cumulative
        for the process lifetime (prometheus counter semantics)."""
        with self._lock:
            self._compiles.clear()
            self._cache_hits.clear()
            self._compile_ms.clear()
            self._retries.clear()
            self._degraded.clear()
            self._injected.clear()
            self._watchdog_trips.clear()
            self._stalls.clear()
            self._verify_failures.clear()
            self._restore_fallbacks.clear()
            self._net_reconnects.clear()
            self._frames_deduped.clear()
            self._zombies_fenced.clear()
            self._net_errors.clear()
            self._leader_elections.clear()
            self._failovers.clear()
            self._takeover_ms.clear()
            self._aot_hits.clear()
            self._aot_misses.clear()
            self._aot_stores.clear()
            self._aot_fallbacks.clear()
            self._aot_evictions = 0
            self._compile_storms.clear()
            self._cold_start_ms.clear()
            self._cold_start_t0 = None
            self._spans_dropped = 0
            self._batches_coalesced = 0
            self._fire_unready_polls = 0
            self._fires_drained = self._fires_drained_timer = 0
            self._probe_rows = self._probe_tail_rows = 0
            self._probe_wide_batches = 0
            self._probe_elected_rows = self._probe_elected_batches = 0
            self._probe_undecided_rows = 0
            self._mesh_steps = self._mesh_exchange_rounds = 0
            self._mesh_reading_waits, self._mesh_reading_wait_us = 0, 0.0
            self._mesh_inserted_rows = self._mesh_stepped_rows = 0
            self._fire_selects = self._fire_select_passes = 0
            self._fire_select_sort = self._fire_select_guarded = 0
            self._count_planes = dict.fromkeys(COUNT_PLANE_FORMS, 0)
            self._fold_batches = self._fold_ring_rows = 0
            self._fold_sorted_batches = self._fold_back_rows = 0
            self._fold_limb_scatters = 0
            self._reclaim_sweeps = 0
            self._reclaim_kept = self._reclaim_freed = 0
            self._session_fires = self._session_fire_rounds = 0
            self._session_fired = self._session_rows_drained = 0
            self._session_lanes_allocated = 0
            self._session_lane_overflow = self._session_settled = 0
            self._chain_dispatches = 0
            self._rescales = 0
            self._keygroups_migrated = 0
            self._rescale_bytes_moved = 0
            self._rescale_ms = 0.0
            self._tier_evictions = self._tier_evicted_keys = 0
            self._tier_prefetches = self._tier_promoted_keys = 0
            self._tier_hot_touches = self._tier_touches = 0
            self._tier_hbm_bytes = 0
            self.dead_letter_records = self.dead_letter_batches = 0
            self.h2d_bytes = self.h2d_records = self.h2d_batches = 0
            self.d2h_bytes = self.d2h_records = self.d2h_fires = 0


DEVICE_STATS = DeviceStats()


def set_compile_tracer(tracer) -> None:
    """Route compile-duration spans into a Tracer (scope 'device',
    name 'Compile', attributes scope/ms)."""
    DEVICE_STATS._tracer = tracer


def pytree_nbytes(tree) -> int:
    """Total buffer bytes across a pytree of arrays (host or device)."""
    import jax

    total = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        nb = getattr(leaf, "nbytes", None)
        if nb is not None:
            total += int(nb)
    return total


class ProgramAuditEntry:
    """One compiled program captured for the tpu-lint Tier-B jaxpr audit
    (flink_tpu/analysis/jaxpr_rules.py): the jitted callable plus the
    abstract (shape/dtype) signature of its first dispatch, so the audit
    can re-trace it without real buffers, and the builder-arg key so
    value-derived cache keys are detectable."""

    __slots__ = ("scope", "fn", "abstract_args", "abstract_kwargs",
                 "build_key", "source")

    def __init__(self, scope, fn, abstract_args, abstract_kwargs,
                 build_key, source):
        self.scope = scope
        self.fn = fn
        self.abstract_args = abstract_args
        self.abstract_kwargs = abstract_kwargs
        self.build_key = build_key
        self.source = source  # (filename, lineno) of the underlying fn


# Every instrumented program's first dispatch appends its audit entry
# here; `python -m flink_tpu.cli lint` / `bench.py --audit` read it after
# exercising a pipeline.  Bounded so a pathological builder loop cannot
# grow it without limit.
PROGRAM_AUDIT: list = []  # lint: guarded-by GIL-atomic append/clear; read offline by the Tier-B audit
_PROGRAM_AUDIT_LIMIT = 512


def clear_program_audit() -> None:
    PROGRAM_AUDIT.clear()


def _program_source(fn):
    inner = getattr(fn, "__wrapped__", fn)
    code = getattr(inner, "__code__", None)
    if code is None:
        return None
    return (code.co_filename, code.co_firstlineno)


def _record_program_audit(scope, fn, args, kwargs, build_key) -> None:
    """Capture the abstract signature of a program's first dispatch.
    Non-fatal by design: the audit is an observer, never a reason for a
    dispatch to fail."""
    if len(PROGRAM_AUDIT) >= _PROGRAM_AUDIT_LIMIT:
        return
    try:
        import jax

        def _abs(x):
            shape = getattr(x, "shape", None)
            dtype = getattr(x, "dtype", None)
            if shape is not None and dtype is not None:
                # with the sharding of an array that is committed to its
                # devices, or a program over a mesh lowers again to
                # another module than the one that ran
                return jax.ShapeDtypeStruct(
                    tuple(shape), dtype,
                    sharding=(x.sharding if getattr(x, "committed", False)
                              else None))
            return x

        PROGRAM_AUDIT.append(ProgramAuditEntry(
            scope, fn,
            jax.tree_util.tree_map(_abs, args),
            jax.tree_util.tree_map(_abs, kwargs),
            build_key, _program_source(fn)))
    except Exception:
        pass


# --------------------------------------------------------------------------
# Named regions of the device programs.
#
# Every ``jax.named_scope`` in flink_tpu/ is a name of this vocabulary. A
# REGION is what a device operation's time is booked under: the innermost
# region scope on the operation's name path (HLO ``op_name``), whatever
# program holds it and whatever number the compiler gave its fusion. The
# int64 planes' split into 32-bit halves and their join carry no path (the
# x64 rewriter makes them) and go by their custom-call target.

#: scope -> the region it names
REGION_SCOPES = {
    "probe.window0": "probe.window0", "probe.tail": "probe.tail",
    "fold.count": "fold.count", "fold.sum": "fold.sum",
    "fold.max": "fold.max", "fold.min": "fold.min", "fold.row": "fold.row",
    "exchange.pack": "exchange.pack",
    # the all-to-alls sit directly under it; what else does is packing
    "mesh.exchange": "exchange.collective",  # lint: key-ok a region scope
    "mesh.plan": "mesh.plan",  # lint: key-ok a region scope
    "mesh.sync": "mesh.sync",  # lint: key-ok a region scope
    "fire.merge": "fire.merge", "fire.topk": "fire.topk",
    "fire.global": "fire.global", "fire.reset": "fire.reset",
    "fire.retire": "fire.retire",
    "reclaim.live": "reclaim.live", "reclaim.rehome": "reclaim.rehome",
    "reclaim.remap": "reclaim.remap",
    # the session operator's two programs (runtime/operators/
    # device_session.py); its probe's own windows stay probe.*
    "session.probe": "session.probe", "session.segment": "session.segment",
    "session.lanes": "session.lanes", "session.fold": "session.fold",
    "session.emit": "session.emit",
    "session.fire.scan": "session.fire.scan",
    "session.fire.compact": "session.fire.compact",
    "session.fire.reset": "session.fire.reset",
}
#: scopes that name no region of their own: they lie around or beneath
#: the region scopes, for the path patterns that count the probe's rounds
#: (``probe.claim``) and time the mesh step's shared part (``mesh.probe``,
#: ``mesh.fold``), and for the lowering tests
PATH_SCOPES = frozenset({
    "probe.gather", "probe.claim", "probe.compact", "probe.elect",
    "fold.scatter", "fold.limb", "fold.carry",
    "mesh.probe", "mesh.fold"})  # lint: key-ok scopes, not config keys
UNNAMED = "unnamed"

_X64_TARGETS = {"X64SplitLow": "x64.split", "X64SplitHigh": "x64.split",
                "X64Combine": "x64.join"}
_X64_REGIONS = frozenset(_X64_TARGETS.values())
#: what the compiler adds to move an operand between memory spaces
_MOVES = frozenset({"copy", "copy-start", "copy-done"})
#: instructions that only hold other computations: their bodies'
#: instructions take regions, they take none
_WRAPPERS = frozenset({"while", "conditional", "call"})
_COLLECTIVES = ("all-to-all", "all-reduce", "all-gather",
                "collective-permute", "reduce-scatter")

_HLO_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s+\(.*\{\s*$")
_HLO_INSTRUCTION = re.compile(r"^\s+(?:ROOT\s+)?%?([\w.\-]+)\s+=\s+(.*)$")
_HLO_OPCODE = re.compile(r"\s*([\w\-]+)\(")
_HLO_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
_HLO_TARGET = re.compile(r'custom_call_target="([^"]*)"')
_HLO_METADATA = re.compile(r"metadata=\{[^}]*\}")
_HLO_REF = re.compile(r"%([\w.\-]+)")
#: instructions that run nothing
_SILENT = frozenset({"parameter", "constant", "tuple", "get-tuple-element",
                     "bitcast"})


def _closing(text: str, start: int) -> int:
    """Index just past the parenthesis that closes the one at ``start``."""
    depth = 0
    for i in range(start, len(text)):
        c = text[i]
        if c == "(":
            depth += 1
        elif c == ")":
            depth -= 1
            if depth == 0:
                return i + 1
    return len(text)


def _scope_region(op_name: str, opcode: str) -> Optional[str]:
    """The region of the innermost region scope on a name path."""
    for part in reversed(op_name.split("/")):
        region = REGION_SCOPES.get(part)
        if region is None:
            continue
        if region == "exchange.collective" \
                and not opcode.startswith(_COLLECTIVES):
            return "exchange.pack"
        return region
    return None


def classify_hlo(text: str) -> dict[str, str]:
    """``{instruction name: region}`` of one compiled program, from its
    HLO text (``compiled.as_text()``). Pure: text in, map out. The map
    holds the instructions the device runs as operations of their own:
    those of the entry computation and of the loop bodies, conditions and
    branches under it, not the insides of a fusion.

    1. A custom-call the x64 rewriter made (``X64SplitLow`` /
       ``X64SplitHigh`` / ``X64Combine``) is ``x64.split`` / ``x64.join``.
    2. An instruction whose ``op_name`` holds a region scope takes the
       innermost one's region.
    3. A fusion without one takes the region the instructions of its
       fused computation agree on.
    4. An instruction without a name PATH (no ``op_name``, or the bare
       primitive the x64 rewriter leaves on the halves of a 64-bit
       scatter, and what the compiler adds to move an operand between
       memory spaces) takes the region of the instructions its result
       reaches, when they are of one region, else of the instructions
       that feed it, when they are, else of the loop or branch that
       holds it. A split's or a join's region goes on to such moves
       only: what computes between a split and a join (the retire's row
       writes) takes the scope's region beside it. A LOOP without a name
       path (the compiler re-tiling a plane around a scatter, row by
       row) is placed the same way, before the rest, and what it holds
       goes with it.
    5. Everything else is ``unnamed``.

    ``while`` / ``conditional`` / ``call`` hold other computations and get
    no entry: their bodies' instructions do. Parameters, constants and
    the tuple plumbing run nothing and get none either."""
    comps: dict[str, list[tuple]] = {}
    entry = current = None
    for line in text.splitlines():
        if not line or line[0] == "}":
            continue
        if not line[0].isspace():
            m = _HLO_COMPUTATION.match(line)
            current = comps.setdefault(m.group(1), []) if m else None
            if m and line.startswith("ENTRY"):
                entry = m.group(1)
            continue
        m = _HLO_INSTRUCTION.match(line)
        if m is None or current is None:
            continue
        name, rest = m.groups()
        # the result type (a tuple type holds spaces), then the opcode
        at = _closing(rest, 0) if rest.startswith("(") else rest.find(" ")
        op = _HLO_OPCODE.match(rest, at)
        if op is None:
            continue
        end = _closing(rest, op.end() - 1)
        attrs = rest[end:]
        path = _HLO_OP_NAME.search(attrs)
        target = _HLO_TARGET.search(attrs)
        current.append((name, op.group(1),
                        _HLO_REF.findall(rest, op.end(), end),
                        path.group(1) if path else "",
                        target.group(1) if target else "",
                        _HLO_REF.findall(_HLO_METADATA.sub("", attrs))))

    def own(opcode, path, target) -> Optional[str]:
        return _X64_TARGETS.get(target) or _scope_region(path, opcode)

    def agreed(comp: str, seen: frozenset) -> Optional[str]:
        """What the instructions of a fused computation agree on."""
        found = set()
        for _name, opcode, _refs, path, target, called in comps.get(comp, ()):
            r = own(opcode, path, target)
            if r is None:
                for c in called:
                    if c in comps and c not in seen:
                        r = r or agreed(c, seen | {c})
            if r is not None:
                found.add(r)
        return found.pop() if len(found) == 1 else None

    regions: dict[str, str] = {}
    # a loop or a branch has no entry, but what feeds it is for it
    wrappers: dict[str, str] = {}
    held_by: dict[str, str] = {entry: UNNAMED}    # computation -> region
    pathless: dict[str, list[str]] = {}           # computation -> names
    # loops the compiler made itself (no name path: a plane re-tiled
    # around a scatter, row by row), with the computations they hold
    bare_loops: list[tuple[str, str, list[str]]] = []
    todo = [entry]
    while todo:
        comp = todo.pop()
        for name, opcode, _refs, path, target, called in comps.get(comp, ()):
            if opcode in _WRAPPERS:
                wrappers[name] = _scope_region(path, opcode) or held_by[comp]
                mine = [c for c in called if c not in held_by]
                if "/" not in path and wrappers[name] == UNNAMED:
                    bare_loops.append((comp, name, mine))
                for c in mine:
                    held_by[c] = wrappers[name]
                    todo.append(c)
                continue
            if opcode in _SILENT:
                continue
            r = own(opcode, path, target)
            if r is None and opcode == "fusion":
                r = agreed(called[0], frozenset(called)) if called else None
            if r is None and "/" not in path:
                pathless.setdefault(comp, []).append(name)
            regions[name] = r or UNNAMED

    # rule 4, over the data flow of each computation that holds a
    # pathless instruction: through other pathless instructions and the
    # tuple plumbing, to the first instructions that have a region
    def flows(comp: str, names) -> tuple:
        operands = {i[0]: i[2] for i in comps[comp]}
        users: dict[str, list[str]] = {}
        for name, refs in operands.items():
            for ref in refs:
                users.setdefault(ref, []).append(name)
        through = set(names) | {i[0] for i in comps[comp]
                                if i[1] in _SILENT}
        # a split's or a join's region goes on to the moves between
        # memory spaces beside it and to nothing that computes: the
        # retire's row writes sit between a split and a join and are
        # neither
        moves = {i[0] for i in comps[comp] if i[1] in _MOVES}
        return operands, users, through, moves

    # rule 4 for a loop without a name path, first (what lies beside it
    # then sees its region): the loop, and through it every computation
    # it holds, takes the region its result reaches, else the one that
    # feeds it
    for comp, loop, held in bare_loops:
        operands, users, through, _moves = flows(
            comp, pathless.get(comp, ()))

        def near(edges) -> set:
            found, seen, todo = set(), {loop}, list(edges.get(loop, ()))
            while todo:
                n = todo.pop()
                if n in seen:
                    continue
                seen.add(n)
                r = regions.get(n) or wrappers.get(n, UNNAMED)
                if r in _X64_REGIONS:
                    continue
                if r != UNNAMED:
                    found.add(r)
                elif n in through:
                    todo.extend(edges.get(n, ()))
            return found

        for found in (near(users), near(operands)):
            if len(found) == 1:
                wrappers[loop] = found.pop()
                todo = list(held)
                while todo:
                    c = todo.pop()
                    held_by[c] = wrappers[loop]
                    todo.extend(k for k, r in held_by.items()
                                if r == UNNAMED and any(
                                    k in i[5] for i in comps.get(c, ())))
                break

    for comp, names in pathless.items():
        operands, users, through, moves = flows(comp, names)

        def reach(start: str, edges) -> set:
            found, seen, todo = set(), {start}, list(edges.get(start, ()))
            while todo:
                n = todo.pop()
                if n in seen:
                    continue
                seen.add(n)
                r = regions.get(n) or wrappers.get(n, UNNAMED)
                if r in _X64_REGIONS and start not in moves:
                    continue
                if r != UNNAMED:
                    found.add(r)
                elif n in through:
                    todo.extend(edges.get(n, ()))
            return found

        for name in names:
            for found in (reach(name, users), reach(name, operands),
                          {held_by[comp]}):
                if len(found) == 1:     # none, or two that disagree: on
                    r = found.pop()
                    # what feeds a collective is not the collective
                    regions[name] = ("exchange.pack"
                                     if r == "exchange.collective" else r)
                    break
    return regions


def _executable_fingerprint(compiled) -> str:
    """What the runtime calls the executable's fingerprint, as text (32
    bytes in hex on a TPU, a decimal number on the CPU); "" where it gives
    none. It tells two programs of one module name apart; it is NOT the
    number a device trace prints after a module's name
    (``jit_step(8252...)``), which no Python API yields."""
    try:
        fp = compiled.runtime_executable().fingerprint
    except Exception:  # noqa: BLE001 - an observer never fails its caller
        return ""
    if isinstance(fp, bytes):
        return fp.decode("ascii") if fp.isdigit() else fp.hex()
    return str(fp or "")


def program_regions() -> dict[str, dict[str, str]]:
    """``{"<hlo module name>(<fingerprint>)": {instruction: region}}`` of
    every program in ``PROGRAM_AUDIT``: from an xprof op name (the head
    of an ``XLA Ops`` event's name, ``custom-call.15``) to the region its
    time belongs to. The module name is the one a device trace shows;
    the fingerprint is the executable's own (``_executable_fingerprint``)
    and keeps apart the programs that share a module name (the probe
    with and without its hand-over, a fire and its incremental twin).

    Computed WHEN ASKED (the end of a traced run, a test, an operator's
    shell), never in a dispatch: each audited program is lowered again
    for the abstract arguments of its first dispatch and compiled, which
    the persistent compile cache serves in a process that has run it,
    and its HLO text goes through ``classify_hlo``. A map is valid only
    for the executable it was made from: a reader of a trace takes, of
    the maps of a module name, the one that holds every operation the
    trace shows of that program (``benchmarks/harness/region_map.pair``).
    A program that cannot be lowered this way (a plain Python builder,
    buffers closed over) has no map."""
    out: dict[str, dict[str, str]] = {}
    for entry in list(PROGRAM_AUDIT):
        lower = getattr(entry.fn, "lower", None)
        if lower is None:
            continue
        try:
            compiled = lower(*entry.abstract_args,
                             **entry.abstract_kwargs).compile()
            text = compiled.as_text()
        except Exception:  # noqa: BLE001 - an observer never fails
            continue
        module = re.match(r"HloModule\s+([\w.\-]+)", text)
        if module is None:
            continue
        key = f"{module.group(1)}({_executable_fingerprint(compiled)})"
        if key not in out:
            out[key] = classify_hlo(text)
    return out


class _TimedProgram:
    """Times the FIRST dispatch of a freshly-built program — jax.jit
    traces/lowers/compiles synchronously inside that call, so its wall
    clock IS the compile cost; later calls pay one extra branch.

    When the persistent AOT cache is active, dispatches route through an
    explicitly-compiled executable per call signature: a warm-loaded one
    (no compile at all) or a live ``lower().compile()`` whose result is
    persisted for the next cold process. Any failure on that path falls
    back to the plain jit call — the cache never fails a dispatch.

    ``prepare`` compiles the program for its arguments' shapes AHEAD of
    its first dispatch (``lower().compile()``, timed as the compile), for
    a program whose first dispatch falls where nothing may compile: the
    state backend's reclaim, built when two health readings in a row
    show the table heading for the load that triggers it. Dispatches
    then run that executable."""

    __slots__ = ("_fn", "_scope", "_compiled", "_build_key",
                 "_build_counted", "_aot_execs", "_aot_bad", "_prepared")

    def __init__(self, fn, scope: str, build_key: str = "",
                 build_counted: bool = True):
        self._fn = fn
        self._scope = scope
        self._compiled = False
        self._build_key = build_key
        self._build_counted = build_counted
        self._aot_execs = None  # call_sig -> compiled executable
        self._aot_bad = None    # call_sigs pinned to the plain jit path
        self._prepared = None   # the executable prepare() compiled

    def __call__(self, *args, **kwargs):
        from ..runtime.aot import AOT
        if self._prepared is None and AOT.dispatch_active():
            return self._call_aot(AOT, args, kwargs)
        return self._call_plain(args, kwargs)

    def prepare(self, *args, **kwargs) -> None:
        """Compile now for ``args`` (arrays or ``ShapeDtypeStruct``s of
        the one shape the program will be called with); a no-op once the
        program has compiled either way (a second mesh of a per-mesh
        program compiles uncounted, as its first dispatch would)."""
        # a program that keeps its executables itself (one a mesh:
        # parallel/sharded_window._per_mesh) is asked every time: it
        # knows whether THIS mesh's is built
        own = getattr(self._fn, "prepare", None)
        if self._compiled and own is None:
            return
        from .tracing import now_ms
        start_ms = now_ms()
        t0 = time.perf_counter()
        if own is not None:
            own(*args, **kwargs)
        else:
            self._prepared = self._fn.lower(*args, **kwargs).compile()
        if not self._compiled:
            self._note_live_compile((time.perf_counter() - t0) * 1e3,
                                    start_ms, args, kwargs)

    def _call_plain(self, args, kwargs):
        if self._compiled:
            fn = self._prepared or self._fn
            if not DEVICE_LEDGER.enabled:
                return fn(*args, **kwargs)
            t0 = time.perf_counter()
            out = fn(*args, **kwargs)
            DEVICE_LEDGER.record(self._scope,
                                 (time.perf_counter() - t0) * 1e3,
                                 shape_sig=self._build_key)
            return out
        from .tracing import now_ms
        start_ms = now_ms()
        t0 = time.perf_counter()
        out = self._fn(*args, **kwargs)
        self._note_live_compile((time.perf_counter() - t0) * 1e3,
                                start_ms, args, kwargs)
        return out

    def _note_live_compile(self, ms, start_ms, args, kwargs) -> None:
        self._compiled = True
        if not self._build_counted:
            # the builder skipped compile accounting expecting a warm
            # executable; this dispatch compiled after all, so it counts
            self._build_counted = True
            DEVICE_STATS.note_build(self._scope)
        DEVICE_STATS.note_compile_done(self._scope, ms, start_ms)
        # first dispatch = trace/lower/compile: charged to the ledger as
        # compile time, never as a steady-state dispatch sample
        DEVICE_LEDGER.record(self._scope, ms, shape_sig=self._build_key,
                             kind="compile")
        _record_program_audit(self._scope, self._fn, args, kwargs,
                              self._build_key)

    def _call_aot(self, aot, args, kwargs):
        sig = aot.call_signature(args, kwargs)
        lower = getattr(self._fn, "lower", None)
        if sig is None or lower is None or \
                (self._aot_bad and sig in self._aot_bad):
            # not an AOT-able dispatch (non-array leaves, a plain python
            # builder, or a signature already pinned to the jit path)
            return self._call_plain(args, kwargs)
        execs = self._aot_execs
        if execs is None:
            execs = self._aot_execs = {}
        compiled = execs.get(sig)
        fresh = False
        if compiled is None:
            compiled = aot.lookup(self._scope, self._build_key, sig)
            if compiled is not None:
                # warm hit: the executable was pre-loaded by warmup — no
                # compile happens, no compile is counted
                execs[sig] = compiled
                self._compiled = True
            else:
                # persistent-cache miss while the cache is active: pay
                # the live compile (the compile storm a warmed process
                # must not see) and persist the result for the next one
                from .tracing import now_ms
                start_ms = now_ms()
                t0 = time.perf_counter()
                try:
                    compiled = lower(*args, **kwargs).compile()
                except Exception:  # noqa: BLE001 - degrade to jit
                    self._pin_bad(sig)
                    return self._call_plain(args, kwargs)
                execs[sig] = compiled
                fresh = True
                ms = (time.perf_counter() - t0) * 1e3
                DEVICE_STATS.note_compile_storm(self._scope)
                if not self._compiled:
                    self._note_live_compile(ms, start_ms, args, kwargs)
                else:
                    # an additional specialization of an already-compiled
                    # program: still compile time, never a dispatch sample
                    DEVICE_STATS.note_compile_done(self._scope, ms,
                                                   start_ms)
                    DEVICE_LEDGER.record(self._scope, ms,
                                         shape_sig=self._build_key,
                                         kind="compile")
        try:
            if not DEVICE_LEDGER.enabled:
                out = compiled(*args, **kwargs)
            else:
                t0 = time.perf_counter()
                out = compiled(*args, **kwargs)
                DEVICE_LEDGER.record(self._scope,
                                     (time.perf_counter() - t0) * 1e3,
                                     shape_sig=self._build_key)
        except Exception as e:  # noqa: BLE001 - degrade to jit
            execs.pop(sig, None)
            self._pin_bad(sig)
            aot.note_dispatch_fallback(self._scope, e)
            return self._call_plain(args, kwargs)
        if fresh:
            aot.store(self._scope, self._build_key, sig, compiled)
        return out

    def _pin_bad(self, sig) -> None:
        if self._aot_bad is None:
            self._aot_bad = set()
        self._aot_bad.add(sig)


#: ``functools.lru_cache``-compatible statistics tuple, preserved so the
#: ``wrapper.cache_info()`` API survives the switch to the config-capped
#: LRU below.
_CacheInfo = collections.namedtuple(
    "CacheInfo", ["hits", "misses", "maxsize", "currsize"])


def instrumented_program_cache(scope: str, maxsize: int = 128):
    """Drop-in replacement for ``functools.lru_cache`` on a compiled-
    program BUILDER: a cache miss counts one compile (the returned
    program's first dispatch is timed as its compile span); a hit counts
    one cache hit. The cached object is shared exactly as before, so
    donation/in-place semantics of the jitted programs are untouched.

    The cache is a config-capped LRU (``aot.in-memory-max-programs``;
    0 = unbounded): evictions count into
    ``aot_in_memory_evictions_total``, and an evicted program rebuilt
    while its executable is warm in the persistent AOT cache skips the
    compile counters entirely — eviction + AOT reload is never a
    recompile. A miss while a warm executable exists likewise bypasses
    the compile accounting, the recompile-attribution ledger, and the
    ``device.compile`` fault/watchdog sites: building the lazy jit
    wrapper is not a compile."""

    def deco(builder: Callable):
        lock = threading.Lock()
        cache = collections.OrderedDict()
        stats = {"hits": 0, "misses": 0}

        def _build_program(args, kwargs):
            key = repr((args, tuple(sorted(kwargs.items()))))
            from ..runtime.aot import AOT
            if AOT.has_program(scope, key):
                # warm start: executables for this program were
                # pre-loaded from the persistent cache, so no compile is
                # decided here — the dispatch path serves them directly
                return _TimedProgram(builder(*args, **kwargs), scope,
                                     build_key=key, build_counted=False)
            # the device.compile fault site + watchdog deadline cover
            # EVERY instrumented builder (device_window/device_session/
            # device_group_agg/pallas_topk/tpu_backend) at the one place
            # a compile is decided; transient trips retry, hang trips
            # stall into the watchdog's deadline, persistent failures
            # surface to the caller's DeviceGuard / failover
            from ..runtime.watchdog import WATCHDOG

            def _build():
                from ..runtime.faults import fire_with_retries
                fire_with_retries("device.compile", scope=scope)
                DEVICE_STATS.note_build(scope)
                # recompile attribution only — the ledger never touches
                # DEVICE_STATS.compiles (the bench recompile budget)
                DEVICE_LEDGER.note_build(scope, key, builder, args,
                                         kwargs)
                return _TimedProgram(builder(*args, **kwargs), scope,
                                     build_key=key)

            return WATCHDOG.run("device.compile", _build, scope=scope)

        def _cap() -> int:
            from ..runtime.aot import AOT
            return AOT.in_memory_max_programs

        @functools.wraps(builder)
        def wrapper(*args, **kwargs):
            ck = (args, tuple(sorted(kwargs.items())))
            with lock:
                prog = cache.get(ck)
                if prog is not None:
                    cache.move_to_end(ck)
                    stats["hits"] += 1
            if prog is not None:
                DEVICE_STATS.note_cache_hit(scope)
                return prog
            # build outside the lock: compiles are slow and must not
            # serialize unrelated builders' cache hits
            prog = _build_program(args, kwargs)
            evicted = 0
            with lock:
                prog = cache.setdefault(ck, prog)
                cache.move_to_end(ck)
                stats["misses"] += 1
                cap = _cap()
                while cap and len(cache) > cap:
                    cache.popitem(last=False)
                    evicted += 1
            if evicted:
                DEVICE_STATS.note_aot_eviction(evicted)
            return prog

        def cache_info() -> _CacheInfo:
            with lock:
                return _CacheInfo(stats["hits"], stats["misses"],
                                  _cap() or None, len(cache))

        def cache_clear() -> None:
            with lock:
                cache.clear()
                stats["hits"] = stats["misses"] = 0

        wrapper.cache_clear = cache_clear
        wrapper.cache_info = cache_info
        return wrapper

    return deco


def bind_device_metrics(registry) -> None:
    """Register the global device stats as gauges under the ``device``
    scope of a MetricRegistry, so prometheus_text / the REST endpoint /
    reporters expose the same series bench.py reads via snapshot().
    Idempotent: re-binding overwrites the same scope entries."""
    g = registry.root().group("device")
    s = DEVICE_STATS
    g.gauge("compiles", lambda: s.compiles)
    g.gauge("compile_cache_hits", lambda: s.compile_cache_hits)
    g.gauge("compile_ms", lambda: s.compile_ms)
    g.gauge("h2d_bytes", lambda: s.h2d_bytes)
    g.gauge("h2d_records", lambda: s.h2d_records)
    g.gauge("h2d_batches", lambda: s.h2d_batches)
    g.gauge("d2h_bytes", lambda: s.d2h_bytes)
    g.gauge("d2h_records", lambda: s.d2h_records)
    g.gauge("d2h_fires", lambda: s.d2h_fires)
    # degradation-ladder counters (prometheus: flink_tpu_device_*)
    g.gauge("retries_total", lambda: s.retries)
    g.gauge("degraded_total", lambda: s.degraded)
    g.gauge("dead_letter_records_total", lambda: s.dead_letter_records)
    g.gauge("dead_letter_batches_total", lambda: s.dead_letter_batches)
    g.gauge("injected_faults_total", lambda: s.injected_faults)
    # stall supervision (prometheus: flink_tpu_device_watchdog_trips_total
    # / flink_tpu_device_stall_detections_total)
    g.gauge("watchdog_trips_total", lambda: s.watchdog_trips)
    g.gauge("stall_detections_total", lambda: s.stall_detections)
    # verified recovery (prometheus:
    # flink_tpu_device_checkpoint_verify_failures_total /
    # flink_tpu_device_restore_fallbacks_total)
    g.gauge("checkpoint_verify_failures_total", lambda: s.verify_failures)
    g.gauge("restore_fallbacks_total", lambda: s.restore_fallbacks)
    # partition tolerance (prometheus:
    # flink_tpu_device_network_reconnects_total /
    # flink_tpu_device_frames_deduped_total /
    # flink_tpu_device_zombies_fenced_total /
    # flink_tpu_device_network_errors_total)
    g.gauge("network_reconnects_total", lambda: s.net_reconnects)
    g.gauge("frames_deduped_total", lambda: s.frames_deduped)
    g.gauge("zombies_fenced_total", lambda: s.zombies_fenced)
    g.gauge("network_errors_total", lambda: s.net_errors)
    # coordinator failover (prometheus:
    # flink_tpu_device_leader_elections_total /
    # flink_tpu_device_coordinator_failovers_total)
    g.gauge("leader_elections_total", lambda: s.leader_elections)
    g.gauge("coordinator_failovers_total", lambda: s.coordinator_failovers)
    # AOT executable cache (prometheus: flink_tpu_device_aot_hits_total /
    # flink_tpu_device_aot_misses_total /
    # flink_tpu_device_aot_stores_total /
    # flink_tpu_device_aot_fallbacks_total /
    # flink_tpu_device_aot_in_memory_evictions_total /
    # flink_tpu_device_compile_storms_total)
    g.gauge("aot_hits_total", lambda: s.aot_hits)
    g.gauge("aot_misses_total", lambda: s.aot_misses)
    g.gauge("aot_stores_total", lambda: s.aot_stores)
    g.gauge("aot_fallbacks_total", lambda: s.aot_fallbacks)
    g.gauge("aot_in_memory_evictions_total",
            lambda: s.aot_in_memory_evictions)
    g.gauge("compile_storms_total", lambda: s.compile_storms)
    # tracing (prometheus: flink_tpu_device_spans_dropped_total)
    g.gauge("spans_dropped_total", lambda: s.spans_dropped)
    # coalesced ingest (prometheus:
    # flink_tpu_device_batches_coalesced_total)
    g.gauge("batches_coalesced_total", lambda: s.batches_coalesced)
    # async fire drain (prometheus:
    # flink_tpu_device_fire_unready_polls_total /
    # flink_tpu_device_fires_drained_total /
    # flink_tpu_device_fires_drained_timer_total)
    g.gauge("fire_unready_polls_total", lambda: s.fire_unready_polls)
    g.gauge("fires_drained_total", lambda: s.fires_drained[0])
    g.gauge("fires_drained_timer_total", lambda: s.fires_drained[1])
    # hash probe (prometheus: flink_tpu_device_probe_rows_total /
    # flink_tpu_device_probe_tail_rows_total /
    # flink_tpu_device_probe_undecided_rows_total /
    # flink_tpu_device_probe_wide_batches_total /
    # flink_tpu_device_probe_elected_rows_total /
    # flink_tpu_device_probe_elected_batches_total)
    g.gauge("probe_rows_total", lambda: s.probe_counts[0])
    g.gauge("probe_tail_rows_total", lambda: s.probe_counts[1])
    g.gauge("probe_undecided_rows_total", lambda: s.probe_counts[5])
    g.gauge("probe_wide_batches_total", lambda: s.probe_counts[2])
    g.gauge("probe_elected_rows_total", lambda: s.probe_counts[3])
    g.gauge("probe_elected_batches_total", lambda: s.probe_counts[4])
    # mesh step (prometheus: flink_tpu_device_mesh_steps_total /
    # flink_tpu_device_mesh_exchange_rounds_total)
    g.gauge("mesh_steps_total", lambda: s.mesh_step_counts[0])
    g.gauge("mesh_exchange_rounds_total", lambda: s.mesh_step_counts[1])
    # host waits for a mesh reading and the microseconds waited
    # (prometheus: flink_tpu_device_mesh_reading_waits_total /
    # flink_tpu_device_mesh_reading_wait_us_total)
    g.gauge("mesh_reading_waits_total",
            lambda: s.mesh_reading_wait_counts[0])
    g.gauge("mesh_reading_wait_us_total",
            lambda: s.mesh_reading_wait_counts[1])
    # rows of the mesh step that claimed a new slot, over the rows
    # stepped between the readings that say so (prometheus:
    # flink_tpu_device_mesh_inserted_rows_total /
    # flink_tpu_device_mesh_stepped_rows_total)
    g.gauge("mesh_inserted_rows_total", lambda: s.mesh_insert_counts[0])
    g.gauge("mesh_stepped_rows_total", lambda: s.mesh_insert_counts[1])
    # ranked fire select, both window operators (prometheus:
    # flink_tpu_device_fire_selects_total /
    # flink_tpu_device_fire_select_passes_total /
    # flink_tpu_device_fire_select_sort_total /
    # flink_tpu_device_fire_select_guarded_total)
    g.gauge("fire_selects_total", lambda: s.fire_select_counts[0])
    g.gauge("fire_select_passes_total", lambda: s.fire_select_counts[1])
    g.gauge("fire_select_sort_total", lambda: s.fire_select_counts[2])
    g.gauge("fire_select_guarded_total", lambda: s.fire_select_counts[3])
    # the hidden plane's form, one count a window operator (prometheus:
    # flink_tpu_device_count_plane_presence32_total / _count32_total /
    # _count64_total)
    for form in COUNT_PLANE_FORMS:
        g.gauge(f"count_plane_{form}_total",
                lambda form=form: s.count_plane_counts[form])
    # ring fold of the host-born ingest, one chip or mesh (prometheus:
    # flink_tpu_device_fold_batches_total /
    # flink_tpu_device_fold_ring_rows_total /
    # flink_tpu_device_fold_sorted_batches_total /
    # flink_tpu_device_fold_back_rows_total /
    # flink_tpu_device_fold_limb_scatters_total)
    g.gauge("fold_batches_total", lambda: s.fold_counts[0])
    g.gauge("fold_ring_rows_total", lambda: s.fold_counts[1])
    g.gauge("fold_sorted_batches_total", lambda: s.fold_counts[2])
    g.gauge("fold_back_rows_total", lambda: s.fold_counts[3])
    g.gauge("fold_limb_scatters_total", lambda: s.fold_limb_scatters)
    # state reclaim, one chip or mesh (prometheus:
    # flink_tpu_device_state_reclaim_sweeps_total /
    # flink_tpu_device_state_reclaim_keys_kept_total /
    # flink_tpu_device_state_reclaim_keys_freed_total)
    g.gauge("state_reclaim_sweeps_total", lambda: s.reclaim_counts[0])
    g.gauge("state_reclaim_keys_kept_total", lambda: s.reclaim_counts[1])
    g.gauge("state_reclaim_keys_freed_total", lambda: s.reclaim_counts[2])
    # session operator (prometheus: flink_tpu_device_session_*_total)
    for name in s.session_counts:
        g.gauge(name, lambda name=name: s.session_counts[name])
    # whole-chain fusion (prometheus:
    # flink_tpu_device_chain_fused_dispatches_total)
    g.gauge("chain_fused_dispatches_total", lambda: s.chain_dispatches)
    # live rescale (prometheus: flink_tpu_device_rescales_total /
    # flink_tpu_device_keygroups_migrated_total /
    # flink_tpu_device_rescale_bytes_moved_total /
    # flink_tpu_device_rescale_ms)
    g.gauge("rescales_total", lambda: s.rescales)
    g.gauge("keygroups_migrated_total", lambda: s.keygroups_migrated)
    g.gauge("rescale_bytes_moved_total", lambda: s.rescale_bytes_moved)
    g.gauge("rescale_ms", lambda: s.rescale_ms)
    # tiered state (prometheus: flink_tpu_device_tier_evictions_total /
    # flink_tpu_device_tier_prefetches_total /
    # flink_tpu_device_tier_hot_hit_ratio /
    # flink_tpu_device_tier_hbm_bytes_in_use)
    g.gauge("tier_evictions_total", lambda: s.tier_evictions)
    g.gauge("tier_prefetches_total", lambda: s.tier_prefetches)
    g.gauge("tier_hot_hit_ratio", lambda: s.tier_hot_hit_ratio)
    g.gauge("tier_hbm_bytes_in_use", lambda: s.tier_hbm_bytes_in_use)
