"""Typed configuration system.

TPU-native analog of the reference's config layer
(flink-core/src/main/java/org/apache/flink/configuration/Configuration.java:53,
ConfigOption.java:42, ConfigOptions.java:70): typed, documented key/value options
with fallback keys, Duration / MemorySize parsing, and per-job overrides that are
serialized into each stage's compiled step config.

Unlike the reference (JVM properties + YAML), options here are plain Python
descriptors; documentation is generated by ``flink_tpu.utils.docs``.
"""

from __future__ import annotations

import copy
import json
import re
from dataclasses import dataclass, field
from typing import Any, Callable, Generic, Iterator, Mapping, Optional, TypeVar

T = TypeVar("T")

_DURATION_RE = re.compile(r"^\s*(\d+(?:\.\d+)?)\s*(ns|us|ms|s|m|min|h|d)?\s*$")
_DURATION_UNITS = {
    "ns": 1e-9, "us": 1e-6, "ms": 1e-3, "s": 1.0,
    "m": 60.0, "min": 60.0, "h": 3600.0, "d": 86400.0,
}

_MEMSIZE_RE = re.compile(r"^\s*(\d+(?:\.\d+)?)\s*(b|kb|k|mb|m|gb|g|tb|t)?\s*$", re.I)
_MEMSIZE_UNITS = {
    "b": 1, "k": 1024, "kb": 1024, "m": 1024**2, "mb": 1024**2,
    "g": 1024**3, "gb": 1024**3, "t": 1024**4, "tb": 1024**4,
}


def parse_duration(value: Any) -> float:
    """Parse a duration into seconds. Accepts numbers (seconds) or '500ms', '1 min'."""
    if isinstance(value, (int, float)):
        return float(value)
    m = _DURATION_RE.match(str(value))
    if not m:
        raise ValueError(f"Cannot parse duration: {value!r}")
    return float(m.group(1)) * _DURATION_UNITS.get(m.group(2) or "s", 1.0)


def parse_memory_size(value: Any) -> int:
    """Parse a memory size into bytes. Accepts ints (bytes) or '32kb', '1g'."""
    if isinstance(value, int):
        return value
    m = _MEMSIZE_RE.match(str(value))
    if not m:
        raise ValueError(f"Cannot parse memory size: {value!r}")
    return int(float(m.group(1)) * _MEMSIZE_UNITS[(m.group(2) or "b").lower()])


_PARSERS: dict[type, Callable[[Any], Any]] = {
    bool: lambda v: v if isinstance(v, bool) else str(v).lower() in ("true", "1", "yes"),
    int: int,
    float: float,
    str: str,
}


@dataclass(frozen=True)
class ConfigOption(Generic[T]):
    """A typed, documented configuration key (reference ConfigOption.java:42)."""

    key: str
    type: type
    default: Optional[T] = None
    description: str = ""
    fallback_keys: tuple[str, ...] = ()
    # 'duration' and 'memory' get special parsing on top of the base type.
    semantic: Optional[str] = None

    def parse(self, raw: Any) -> T:
        if raw is None:
            return raw
        if self.semantic == "duration":
            return parse_duration(raw)  # type: ignore[return-value]
        if self.semantic == "memory":
            return parse_memory_size(raw)  # type: ignore[return-value]
        parser = _PARSERS.get(self.type)
        if parser is not None:
            return parser(raw)
        return raw

    def with_default(self, default: T) -> "ConfigOption[T]":
        return ConfigOption(self.key, self.type, default, self.description,
                            self.fallback_keys, self.semantic)


class _OptionBuilder:
    """Fluent builder mirroring ConfigOptions.key(...).xType().defaultValue(...)."""

    def __init__(self, key: str):
        self._key = key

    def _make(self, typ: type, semantic: Optional[str] = None) -> "_TypedBuilder":
        return _TypedBuilder(self._key, typ, semantic)

    def bool_type(self): return self._make(bool)
    def int_type(self): return self._make(int)
    def float_type(self): return self._make(float)
    def string_type(self): return self._make(str)
    def duration_type(self): return self._make(float, "duration")
    def memory_type(self): return self._make(int, "memory")
    def any_type(self): return self._make(object)


class _TypedBuilder(Generic[T]):
    def __init__(self, key: str, typ: type, semantic: Optional[str]):
        self._key, self._type, self._semantic = key, typ, semantic
        self._fallbacks: tuple[str, ...] = ()

    def with_fallback_keys(self, *keys: str) -> "_TypedBuilder[T]":
        self._fallbacks = tuple(keys)
        return self

    def default_value(self, default: T, description: str = "") -> ConfigOption[T]:
        opt = ConfigOption(self._key, self._type, default, description,
                           self._fallbacks, self._semantic)
        _ALL_OPTIONS[self._key] = opt
        return opt

    def no_default(self, description: str = "") -> ConfigOption[T]:
        opt = ConfigOption(self._key, self._type, None, description,
                           self._fallbacks, self._semantic)
        _ALL_OPTIONS[self._key] = opt
        return opt


#: Registry of every declared option, for doc generation
#: (reference ConfigOptionsDocGenerator.java:69).
_ALL_OPTIONS: dict[str, ConfigOption] = {}


def key(name: str) -> _OptionBuilder:
    """Entry point: ``key("pipeline.batch-size").int_type().default_value(4096)``."""
    return _OptionBuilder(name)


def all_options() -> Mapping[str, ConfigOption]:
    return dict(_ALL_OPTIONS)


class Configuration:
    """Mutable typed key/value store (reference Configuration.java:53).

    Values are stored raw and parsed through the option's type on read, so a
    config loaded from JSON/YAML-ish dicts behaves identically to one built
    programmatically.
    """

    def __init__(self, data: Optional[Mapping[str, Any]] = None):
        self._data: dict[str, Any] = dict(data or {})

    # -- typed access ------------------------------------------------------
    def get(self, option: ConfigOption[T], default: Optional[T] = None) -> T:
        for k in (option.key, *option.fallback_keys):
            if k in self._data:
                return option.parse(self._data[k])
        if default is not None:
            return default
        return option.default  # type: ignore[return-value]

    def set(self, option: "ConfigOption[T] | str", value: T) -> "Configuration":
        k = option.key if isinstance(option, ConfigOption) else option
        self._data[k] = value
        return self

    def contains(self, option: "ConfigOption | str") -> bool:
        k = option.key if isinstance(option, ConfigOption) else option
        return k in self._data

    def remove(self, option: "ConfigOption | str") -> None:
        k = option.key if isinstance(option, ConfigOption) else option
        self._data.pop(k, None)

    # -- raw access --------------------------------------------------------
    def get_raw(self, key: str, default: Any = None) -> Any:
        return self._data.get(key, default)

    def keys(self) -> Iterator[str]:
        return iter(self._data)

    def to_dict(self) -> dict[str, Any]:
        return dict(self._data)

    # -- merging -----------------------------------------------------------
    def merge(self, other: "Configuration | Mapping[str, Any]") -> "Configuration":
        """Return a new Configuration; ``other`` wins on conflicts."""
        merged = dict(self._data)
        merged.update(other.to_dict() if isinstance(other, Configuration) else other)
        return Configuration(merged)

    def clone(self) -> "Configuration":
        return Configuration(copy.deepcopy(self._data))

    # -- (de)serialization: configs travel inside the StageDeploymentDescriptor
    def to_json(self) -> str:
        return json.dumps(self._data, sort_keys=True, default=str)

    @classmethod
    def from_json(cls, s: str) -> "Configuration":
        return cls(json.loads(s))

    def __eq__(self, other: object) -> bool:
        return isinstance(other, Configuration) and self._data == other._data

    def __repr__(self) -> str:
        return f"Configuration({self._data!r})"


# ---------------------------------------------------------------------------
# Core option sets (analogs of the reference's *Options classes)
# ---------------------------------------------------------------------------

class PipelineOptions:
    """Job-level pipeline knobs (reference PipelineOptions / ExecutionOptions)."""

    NAME = key("pipeline.name").string_type().default_value(
        "flink-tpu-job", "Human-readable job name.")
    MAX_PARALLELISM = key("pipeline.max-parallelism").int_type().default_value(
        128, "Number of key groups; the hard ceiling on keyed-operator "
             "parallelism (reference KeyGroupRangeAssignment).")
    DEFAULT_PARALLELISM = key("pipeline.parallelism").int_type().default_value(
        1, "Default operator parallelism (subtask count).")
    BATCH_SIZE = key("pipeline.micro-batch-size").int_type().default_value(
        4096, "Records per micro-batch fed into one compiled step. Larger "
              "batches amortize dispatch; smaller batches cut latency.")
    BATCH_TIMEOUT = key("pipeline.micro-batch-timeout").duration_type().default_value(
        0.05, "Max time to wait filling a micro-batch before running a "
              "partial batch (the BufferDebloater analog: bounds latency).")
    CHAINING_ENABLED = key("pipeline.operator-chaining").bool_type().default_value(
        True, "Fuse compatible adjacent operators into one compiled stage "
              "(reference OperatorChain.java:108 -> XLA fusion).")
    AUTO_WATERMARK_INTERVAL = key("pipeline.auto-watermark-interval").duration_type() \
        .default_value(0.2, "Periodic watermark emission interval.")
    ADAPTIVE_BATCH = key("pipeline.adaptive-batch-size").bool_type() \
        .default_value(False, "Auto-tune the source micro-batch size so one "
                              "batch's read+emit time tracks the target "
                              "latency (reference BufferDebloater.java:33: "
                              "size = throughput x target, EMA-smoothed).")
    ADAPTIVE_TARGET_LATENCY = key(
        "pipeline.adaptive-batch-size.target-latency").duration_type() \
        .default_value(0.1, "Per-batch latency the admission controller "
                            "steers toward (reference buffer-debloat.target).")
    ADAPTIVE_MIN_BATCH = key("pipeline.adaptive-batch-size.min").int_type() \
        .default_value(64, "Lower clamp for the adaptive batch size.")
    ADAPTIVE_MAX_BATCH = key("pipeline.adaptive-batch-size.max").int_type() \
        .default_value(1 << 20, "Upper clamp for the adaptive batch size.")
    FUSION = key("pipeline.fusion.enabled").bool_type().default_value(
        False, "Whole-chain fusion: certify operator chains with the fusion "
               "certifier (graph/fusion.py) and lower certified "
               "source -> window prefixes into ONE donated XLA dispatch per "
               "micro-batch. Also allows chaining across a hash edge at "
               "parallelism 1 when the downstream head is a device window "
               "aggregate (hash at parallelism 1 is forward-equivalent).")


class SqlOptions:
    """Table/SQL planner knobs (reference OptimizerConfigOptions /
    ExecutionConfigOptions)."""

    TWO_PHASE_AGG = key("sql.optimizer.agg-phase-strategy.two-phase") \
        .bool_type().default_value(
            True, "Split plain GROUP BY into a local combine before the "
                  "keyed exchange and a global merge after it (reference "
                  "StreamExecLocalGroupAggregate/StreamExecGlobal"
                  "GroupAggregate): the exchange ships one partial row per "
                  "distinct key per micro-batch instead of every record.")


class CheckpointingOptions:
    """Checkpointing knobs (reference CheckpointingOptions)."""

    INTERVAL = key("execution.checkpointing.interval").duration_type().default_value(
        0.0, "Checkpoint interval in seconds; 0 disables periodic checkpoints.")
    MODE = key("execution.checkpointing.mode").string_type().default_value(
        "exactly-once", "exactly-once (aligned barriers) or at-least-once.")
    TIMEOUT = key("execution.checkpointing.timeout").duration_type().default_value(
        600.0, "Abort a pending checkpoint after this long.")
    MIN_PAUSE = key("execution.checkpointing.min-pause").duration_type().default_value(
        0.0, "Minimum pause between completed checkpoints.")
    MAX_CONCURRENT = key("execution.checkpointing.max-concurrent-checkpoints") \
        .int_type().default_value(1, "Concurrent in-flight checkpoints.")
    DIRECTORY = key("execution.checkpointing.dir").string_type().no_default(
        "Checkpoint storage directory; in-memory storage when unset.")
    RETAINED = key("execution.checkpointing.num-retained").int_type().default_value(
        1, "Completed checkpoints kept before subsumption.")
    UNALIGNED = key("execution.checkpointing.unaligned").bool_type().default_value(
        False, "Unaligned barriers: barrier overtakes queued batches, "
               "in-flight data is snapshotted with the channel.")
    ALIGNMENT_TIMEOUT = key("execution.checkpointing.aligned-checkpoint-timeout") \
        .duration_type().default_value(
            0.0, "Escalate aligned->unaligned after this alignment delay "
                 "(0 = never escalate).")
    VERIFY_ON_RESTORE = key("checkpoint.verify-on-restore").bool_type() \
        .default_value(
            True, "Verify checkpoint artifact integrity on the restore "
                  "path: chunk content digests are recomputed on every "
                  "read and the per-checkpoint manifest's whole-metadata "
                  "checksum is checked before decoding. A mismatch raises "
                  "a typed CorruptArtifactError instead of restoring "
                  "garbage state; restore paths (supervisor, distributed "
                  "coordinator, HA recovery) then fall back to the "
                  "next-oldest retained checkpoint that verifies, and "
                  "fail the job only when none does.")
    QUARANTINE_CORRUPT = key("checkpoint.quarantine-corrupt").bool_type() \
        .default_value(
            True, "Rename a checkpoint directory that failed verification "
                  "to '<dir>.corrupt' and drop its chunk references "
                  "(chunks still referenced by older retained checkpoints "
                  "survive) so a corrupt artifact never sits first in the "
                  "restore order again. Disable to leave corrupt "
                  "artifacts in place for forensics.")


class StateOptions:
    """State backend selection (reference StateBackendLoader.java:50)."""

    BACKEND = key("state.backend.type").string_type().default_value(
        "hashmap", "State backend name registered in the backend registry: "
                   "'hashmap' (host), 'tpu' (device-resident), or a "
                   "fully-qualified factory 'module:attr'.")
    TPU_CAPACITY = key("state.backend.tpu.slots-per-key-group").int_type() \
        .default_value(0, "Pre-sized device slots per key group "
                          "(0 = grow dynamically by rehash).")
    TPU_DTYPE = key("state.backend.tpu.accumulator-dtype").string_type() \
        .default_value("float32", "Accumulator dtype for device aggregation.")
    TPU_HBM_BUDGET = key("state.backend.tpu.hbm-budget-slots").int_type() \
        .default_value(0, "Max device hash-table slots; keyed state beyond "
                          "this pages to the host-RAM spill tier at "
                          "key-group granularity (the RocksDB-replacement "
                          "larger-than-HBM path). 0 = unlimited.")
    TPU_HBM_BUDGET_BYTES = key("state.backend.tpu.hbm-budget-bytes") \
        .memory_type().default_value(
            0, "HBM budget for keyed state in bytes (accepts '512mb' "
               "style sizes). Converted to hash-table slots from the "
               "operator's per-slot footprint (table key + every "
               "accumulator plane row) and applied like "
               "state.backend.tpu.hbm-budget-slots, which takes "
               "precedence when both are set. 0 = unlimited.")
    MESH_DEVICES = key("state.backend.tpu.mesh-devices").int_type() \
        .default_value(0, "Execute keyed window aggregation as one SPMD "
                          "vertex over an n-device mesh (on-device keyBy "
                          "all_to_all exchange). 0/1 = single-chip device "
                          "operator.")
    LOCAL_RECOVERY = key("state.backend.local-recovery").bool_type() \
        .default_value(False, "Keep a local snapshot copy for fast restore.")
    CHANGELOG_MATERIALIZATION_INTERVAL = key(
        "state.backend.changelog.materialization-interval").int_type() \
        .default_value(10, "Checkpoints between changelog-backend "
                           "materializations (reference periodic "
                           "materialization).")


class TieringOptions:
    """Tiered state residency (state/tiering/): decayed 2Q heat policy
    deciding which key groups stay device-hot under an HBM budget and
    which page to the host-warm tier, plus the async prefetch pipeline
    promoting warming groups back. Decay runs on batch-boundary cadence
    (never wall clock) and every tie-break is seeded, so residency
    decisions replay deterministically under chaos."""

    DECAY_INTERVAL = key("state.tiering.decay-interval").int_type() \
        .default_value(
            8, "Batch boundaries (checkpoint/fire events) between heat "
               "decay steps. Cadence is counted in boundaries, never "
               "wall clock, so chaos replay sees the same residency "
               "decisions.")
    DECAY_FACTOR = key("state.tiering.decay-factor").float_type() \
        .default_value(
            0.5, "Multiplier applied to every key group's heat at each "
                 "decay step; lower values forget the access history "
                 "faster (pure recency), 1.0 never forgets (pure "
                 "frequency).")
    SEED = key("state.tiering.seed").int_type().default_value(
        24243, "Seed for the residency policy's tie-break permutation. "
               "Key groups with identical heat and recency order by this "
               "fixed seeded permutation, keeping eviction order total "
               "and replayable.")
    ASYNC_PREFETCH = key("state.tiering.async-prefetch").bool_type() \
        .default_value(
            True, "Stage warm->hot promotions on a background thread "
                  "(double-buffered h2d staging under site "
                  "tier.prefetch). Off, staging runs synchronously at "
                  "the batch boundary — slower, but fully deterministic "
                  "for single-threaded replay.")
    PROMOTE_HEADROOM = key("state.tiering.promote-headroom").float_type() \
        .default_value(
            0.5, "Fraction of device capacity the resident key count may "
                 "reach through promotions. Promotions stop short of the "
                 "rehash/evict load factor (0.6), so paging a group in "
                 "can never itself force an eviction.")
    PROMOTE_MIN_HEAT = key("state.tiering.promote-min-heat").float_type() \
        .default_value(
            2.0, "Minimum decayed heat before a warm key group is "
                 "considered for promotion; groups below it serve from "
                 "the host tier. Raise to damp residency oscillation "
                 "under shifting working sets.")


class MeshOptions:
    """Multi-device SPMD mesh: declarative sharding plan + live rescale
    (parallel/plan.py ShardingPlan / MESH_RUNTIME)."""

    AXIS_RULES = key("mesh.axis-rules").string_type().default_value(
        "", "Partition rules for sharded state as ';'-separated "
            "'regex=axis' entries matched against '/'-joined pytree leaf "
            "paths (e.g. 'accs/.*=data;.*=*'); 'regex=*' replicates. "
            "Empty = the built-in window-state rules. Axes must be "
            "declared mesh axes (parallel/plan.py DECLARED_AXES).")
    RESCALE_ENABLED = key("mesh.rescale.enabled").bool_type() \
        .default_value(
            True, "Allow live worker-set changes: re-shard device-resident "
                  "key-group state across the new mesh at an aligned "
                  "barrier, without a job restart (exactly-once "
                  "preserved). Disable to force the restart-from-"
                  "checkpoint rescale path only.")
    RESCALE_TIMEOUT = key("mesh.rescale.timeout").duration_type() \
        .default_value(
            30.0, "Deadline for a coordinator-driven live rescale "
                  "(barrier trigger -> state re-sharded on the new "
                  "mesh); on expiry the rescale is abandoned and the "
                  "old mesh keeps running.")


class TaskOptions:
    """Per-task dispatch pipelining and ingest batching knobs."""

    MAX_INFLIGHT = key("task.max-inflight").int_type().default_value(
        2, "Compiled-step dispatches allowed in flight before the ingest "
           "path blocks on the oldest (device-operator backpressure "
           "window). Higher values overlap more host/device work at the "
           "cost of deeper donation pipelining; 1 = fully synchronous.")
    COALESCE_TARGET_RECORDS = key("task.coalesce.target-records") \
        .int_type().default_value(
            0, "Coalesce consecutive same-schema input micro-batches "
               "host-side until this many records are buffered, then "
               "dispatch ONE compiled step for the merged batch "
               "(amortizes fixed dispatch cost over small upstream "
               "batches). The buffer always flushes before fires, "
               "snapshots and finish, so watermark/barrier semantics "
               "are unchanged. 0/1 = disabled.")
    COALESCE_TIMEOUT_MS = key("task.coalesce.timeout-ms").int_type() \
        .default_value(
            0, "Age deadline in milliseconds for a non-empty coalescing "
               "buffer; checked when the next batch is admitted (no "
               "timer thread). 0 = no deadline (flush on target, "
               "incompatible batch, or watermark/barrier only).")


class RuntimeOptions:
    """Worker-side execution knobs (reference TaskManagerOptions)."""

    NUM_TASK_SLOTS = key("taskmanager.num-task-slots").int_type().default_value(
        1, "Task slots per worker.")
    SLOTS_PER_HOST = key("taskmanager.slots-per-host").string_type() \
        .default_value("", "Comma-separated slot counts per host id for SPMD "
                           "placement (e.g. '2,1'); empty = every host "
                           "contributes num-task-slots. Host h takes "
                           "slots[h] of every sum(slots) subtasks, "
                           "round-robin interleaved.")
    HEARTBEAT_INTERVAL = key("heartbeat.interval").duration_type().default_value(
        1.0, "Heartbeat period between coordinator and workers.")
    HEARTBEAT_TIMEOUT = key("heartbeat.timeout").duration_type().default_value(
        5.0, "Mark a worker dead after this silence.")
    RESTART_STRATEGY = key("restart-strategy.type").string_type().default_value(
        "exponential-delay", "none | fixed-delay | exponential-delay | failure-rate")
    RESTART_ATTEMPTS = key("restart-strategy.fixed-delay.attempts").int_type() \
        .default_value(3, "Restart attempts for fixed-delay strategy.")
    RESTART_DELAY = key("restart-strategy.fixed-delay.delay").duration_type() \
        .default_value(0.1, "Delay between restarts.")
    FAILURE_RATE_MAX = key(
        "restart-strategy.failure-rate.max-failures-per-interval").int_type() \
        .default_value(3, "Max failures per interval for failure-rate "
                       "strategy (reference RestartStrategyOptions).")
    FAILURE_RATE_INTERVAL = key(
        "restart-strategy.failure-rate.failure-rate-interval") \
        .duration_type().default_value(
            60.0, "Measurement window for the failure-rate strategy.")
    FAILURE_RATE_DELAY = key("restart-strategy.failure-rate.delay") \
        .duration_type().default_value(
            0.1, "Delay between restarts for the failure-rate strategy.")
    BACKOFF_INITIAL = key("restart-strategy.exponential-delay.initial-backoff") \
        .duration_type().default_value(0.05, "Initial restart backoff.")
    BACKOFF_MAX = key("restart-strategy.exponential-delay.max-backoff") \
        .duration_type().default_value(10.0, "Max restart backoff.")


class FaultOptions:
    """Deterministic fault injection + device-path degradation knobs
    (the chaos/ITCase analog of the reference's process-kill tests:
    failures become injectable, schedulable, and seeded so a chaos run
    replays byte-identically). See docs/ROBUSTNESS.md for the site
    catalog and the spec grammar."""

    ENABLED = key("faults.enabled").bool_type().default_value(
        False, "Master switch for the fault-injection registry. Off (the "
               "default) short-circuits every site check to one attribute "
               "read — production pays nothing.")
    SEED = key("faults.seed").int_type().default_value(
        0, "Seed for probabilistic fault schedules. Each site derives its "
           "own stream from (seed, site), so two runs with the same seed, "
           "spec, and visit order trip identically.")
    SPEC = key("faults.spec").string_type().default_value(
        "", "Comma-separated fault rules '<site>=<mode>[!flag...]'. Modes: "
            "'once@N' (trip exactly once, on the Nth visit), 'every@N' "
            "(every Nth visit), 'p<float>' (seeded per-visit probability), "
            "'always', 'off'. Flags: '!persistent' (not retryable; default "
            "is transient), '!poison' (data fault: the batch is "
            "quarantined to the dead-letter output, never retried into "
            "state), '!hang@MS' (the trip SLEEPS MS milliseconds at the "
            "site instead of raising — the deterministic stand-in for a "
            "wedged call, surfaced by the stall watchdog's deadline). "
            "Example: 'device.execute=p0.02,transfer.h2d=once@5,"
            "checkpoint.write=once@2!persistent'.")
    VALIDATE_BATCHES = key("faults.validate-batches").bool_type() \
        .default_value(
            False, "Device operators screen ingest batches for NaN/Inf in "
                   "aggregated float columns; non-finite rows are "
                   "quarantined to the dead-letter output instead of "
                   "poisoning accumulator state. Off by default (one "
                   "np.isfinite pass per float column per batch).")
    DEVICE_MAX_RETRIES = key("device.failover.max-retries").int_type() \
        .default_value(
            3, "Transient compiled-segment failures (compile, execute, "
               "transfer) retry this many times with exponential backoff "
               "before escalating to degradation / task failover.")
    DEVICE_RETRY_BACKOFF = key("device.failover.retry-backoff") \
        .duration_type().default_value(
            0.005, "Initial backoff between device-segment retries "
                   "(exponential, shared with the restart-strategy "
                   "escalation math).")
    DEVICE_RETRY_BACKOFF_MAX = key("device.failover.retry-backoff-max") \
        .duration_type().default_value(
            0.25, "Backoff ceiling for device-segment retries.")
    DEGRADATION = key("device.failover.degradation").bool_type() \
        .default_value(
            True, "Persistent compile/execute failure evacuates the "
                  "operator's device state to host through the snapshot "
                  "path and pins the operator to its synchronous fallback "
                  "ingest path (keyed state and exactly-once semantics "
                  "survive). "
                  "Off = persistent device failures fail the task and "
                  "recover through the job restart strategy instead.")


class WatchdogOptions:
    """Stall watchdog: deadline-bounded device/transfer/RPC operations
    plus task-progress supervision (docs/ROBUSTNESS.md, 'Stall
    watchdog'). Every blocking site runs on a supervised worker with a
    per-site deadline; expiry raises a typed StallError that feeds the
    degradation ladder (stall = transient -> retry; repeated stalls ->
    degrade / task failover), so no single hung operation can stall a
    job indefinitely. A deadline of 0 unbounds that site (direct call,
    no supervision)."""

    ENABLED = key("watchdog.enabled").bool_type().default_value(
        True, "Master switch for the stall watchdog. Off, every wrapped "
              "site calls through directly (no supervision thread, no "
              "deadline) — hangs are then only caught by task-progress "
              "supervision.")
    COMPILE_TIMEOUT = key("watchdog.device.compile-timeout") \
        .duration_type().default_value(
            600.0, "Deadline for building/compiling a device program "
                   "(site device.compile — every instrumented builder). "
                   "A cold XLA compile at 10M-key shapes is about a "
                   "minute on a v5e; 0 = unbounded.")
    EXECUTE_TIMEOUT = key("watchdog.device.execute-timeout") \
        .duration_type().default_value(
            300.0, "Deadline for dispatching a compiled segment (site "
                   "device.execute: step/fire/fold dispatch and the "
                   "bounded in-flight sync). The FIRST dispatch of a "
                   "fresh program compiles inside this window. "
                   "0 = unbounded.")
    TRANSFER_TIMEOUT = key("watchdog.transfer-timeout") \
        .duration_type().default_value(
            120.0, "Deadline for one host<->device transfer (sites "
                   "transfer.h2d / transfer.d2h). 0 = unbounded.")
    CHECKPOINT_TIMEOUT = key("watchdog.checkpoint-timeout") \
        .duration_type().default_value(
            300.0, "Deadline for persisting or loading a checkpoint "
                   "(sites checkpoint.write / checkpoint.load). A stalled "
                   "write aborts that checkpoint (tolerable failure); a "
                   "stalled load fails the restore. 0 = unbounded.")
    RPC_TIMEOUT = key("watchdog.rpc-timeout").duration_type() \
        .default_value(
            60.0, "Deadline for one control-plane RPC frame (site "
                  "rpc.send: worker<->coordinator control sends). A "
                  "stalled send is treated like a severed connection. "
                  "0 = unbounded.")
    TIER_TIMEOUT = key("watchdog.tier-timeout").duration_type() \
        .default_value(
            120.0, "Deadline for one tiered-state residency move (sites "
                   "tier.evict / tier.prefetch: paging cold key groups "
                   "to the host-warm tier and staging warm groups back). "
                   "A stalled eviction fails the ingest batch into the "
                   "restart path; a stalled prefetch staging surfaces at "
                   "the next batch boundary. 0 = unbounded.")
    STALL_RETRIES = key("watchdog.stall-retries").int_type() \
        .default_value(
            1, "In-place retries after a StallError at sites outside a "
               "DeviceGuard (transfers, checkpoint writes): the stalled "
               "attempt is abandoned and the idempotent operation re-"
               "runs. DeviceGuard-wrapped dispatches use "
               "device.failover.max-retries instead.")
    TASK_STALL_TIMEOUT = key("task.stall-timeout").duration_type() \
        .default_value(
            120.0, "Task-progress supervision: a subtask whose progress "
                   "epoch has not advanced for this long WHILE its input "
                   "gates hold queued data is declared stalled and "
                   "routed into the region-failover restart path. "
                   "0 disables progress supervision.")
    BACKPRESSURE_STALL_TIMEOUT = key("task.backpressure.stall-timeout") \
        .duration_type().default_value(
            300.0, "Cap on the total time one emit may spend blocked on "
                   "a full downstream channel. A stuck-but-alive peer "
                   "then raises StallError into the supervisor (task "
                   "failover; the element is never dropped — restart "
                   "replays it from the checkpoint). 0 = unbounded "
                   "(pre-watchdog behavior).")
    AOT_WARMUP_TIMEOUT = key("watchdog.aot-warmup-timeout") \
        .duration_type().default_value(
            120.0, "Deadline for the AOT warm-start scan (site "
                   "aot.warmup): pre-deserializing every persisted "
                   "executable before a cold process serves its first "
                   "batch. Expiry degrades to partial warmth — whatever "
                   "loaded in time serves, the rest compile on miss — "
                   "never a failure. 0 = unbounded.")


class AotOptions:
    """Persistent AOT executable cache (docs/ROBUSTNESS.md,
    'Compile-storm-free recovery'): every program build additionally
    persists a serialized compiled executable, content-addressed by the
    same shape-only signatures the in-process program caches use plus a
    backend/jax-version/device-kind fingerprint, verified per artifact
    with the checkpoint manifest machinery (blake2b digest,
    write-tmp/fsync/rename, typed CorruptArtifactError + quarantine on
    mismatch). Cold processes (deploy, rescale-up replicas, post-failover
    successors) pre-load the cache under the ``aot.warmup`` watchdog
    deadline before their first batch, so recovery never pays a compile
    storm; any corrupt/skewed/unserializable artifact degrades to live
    compilation — the cache can only make a process faster."""

    ENABLED = key("aot.enabled").bool_type().default_value(
        False, "Master switch for the persistent AOT executable cache. "
               "Off (the default), program builds and dispatches behave "
               "exactly as before: nothing is persisted or pre-loaded "
               "and the dispatch path pays one attribute read.")
    DIR = key("aot.dir").string_type().default_value(
        "", "Directory holding the serialized-executable artifacts "
            "('<blake2b16>.aotx'). Shared across processes of a "
            "deployment (coordinator journals it next to the checkpoint "
            "pointer so failover successors and rescale replicas find "
            "it). Empty disables the cache even when aot.enabled is "
            "set.")
    IN_MEMORY_MAX_PROGRAMS = key("aot.in-memory-max-programs") \
        .int_type().default_value(
            0, "Cap on entries in each process-global in-memory program "
               "cache (LRU eviction, counted in "
               "aot_in_memory_evictions_total). 0 = unbounded, "
               "preserving the prior growth behavior. An evicted program "
               "that is rebuilt while its executable is warm in the AOT "
               "cache never counts as a recompile.")


class NetworkOptions:
    """Partition-tolerant inter-host networking (docs/ROBUSTNESS.md,
    'Network partitions'): every cross-host data channel carries
    sequence-numbered frames backed by a bounded replay buffer, so a
    severed-and-restored TCP connection resumes exactly-once with ZERO
    region restarts — the receiver dedups replayed frames by sequence
    number. The worker's control channel reconnects to the coordinator
    inside the same deadline. Only reconnect-deadline expiry escalates
    into the StallError -> region-restart ladder."""

    RECONNECT_TIMEOUT = key("net.reconnect-timeout").duration_type() \
        .default_value(
            10.0, "Deadline for re-establishing a severed data or "
                  "control connection (site net.reconnect, watchdog-"
                  "supervised). The INITIAL connect of a data channel is "
                  "bounded by the same deadline (it always gets one "
                  "attempt). Expiry raises a typed StallError into the "
                  "existing region-restart ladder. 0 DISABLES "
                  "reconnection: a severed established connection fails "
                  "immediately (unlike watchdog.* deadlines, where 0 "
                  "means unbounded — a channel must never retry "
                  "forever).")
    RECONNECT_BACKOFF = key("net.reconnect-backoff").duration_type() \
        .default_value(
            0.05, "Sleep between reconnect attempts within the "
                  "net.reconnect-timeout deadline.")
    REPLAY_BUFFER = key("net.replay-buffer-frames").int_type() \
        .default_value(
            1024, "Max unacknowledged frames a sender retains for replay "
                  "after a reconnect. Credit flow control bounds genuine "
                  "in-flight frames far below this (the receiver acks on "
                  "delivery), so overflow means the receiver stopped "
                  "acknowledging entirely — the channel fails loudly "
                  "instead of buffering without bound.")


class HaOptions:
    """Coordinator high availability (docs/ROBUSTNESS.md, 'Coordinator
    failover'): standby coordinator processes contend on the file lease
    under the job's HA dir; the leader publishes a fenced leader record
    (address + epoch token) and journals topology, worker addresses, the
    retained-checkpoint pointer, and the attempt epoch so a successor can
    take over a RUNNING job — hot (all workers re-register, no restart)
    or via a fenced restore from the latest verified checkpoint."""

    DIR = key("ha.dir").string_type().default_value(
        "", "High-availability directory shared by all coordinator "
            "contenders and workers of a job. Empty DISABLES coordinator "
            "failover: the coordinator runs unreplicated at a fixed "
            "address, exactly as before.")
    STANDBY_COORDINATORS = key("ha.standby-coordinators").int_type() \
        .default_value(
            0, "Number of standby coordinator processes a deployment is "
               "expected to keep contending on the lease. Informational "
               "for the leader surface (cli leader / REST "
               "/jobs/<name>/leader); the election itself admits any "
               "number of contenders.")
    LEASE_TIMEOUT = key("ha.lease-timeout").duration_type().default_value(
        2.0, "Leader lease duration. The leader renews at a third of "
             "this; a standby may steal the lease once it has gone "
             "unrenewed for the full duration. Lower values detect a "
             "dead leader faster but tolerate less scheduling jitter "
             "before a live leader is deposed (site ha.lease).")
    TAKEOVER_TIMEOUT = key("ha.takeover-timeout").duration_type() \
        .default_value(
            30.0, "Deadline for a newly elected coordinator to resolve "
                  "the takeover: if every journaled worker re-registers "
                  "in time the job resumes HOT (no restart, recompiles "
                  "0); on expiry the missing workers are declared dead "
                  "and the job falls back to a fenced global restore "
                  "from the latest verified checkpoint. Workers extend "
                  "their control-reconnect deadline to at least this "
                  "value when an HA dir is set, so they outlive the "
                  "election gap.")


class MetricOptions:
    REPORTERS = key("metrics.reporters").string_type().default_value(
        "", "Comma-separated reporter names ('log', 'prometheus', 'jsonfile').")
    INTERVAL = key("metrics.reporter.interval").duration_type().default_value(
        10.0, "Reporter flush period.")
    LATENCY_INTERVAL = key("metrics.latency.interval").duration_type() \
        .default_value(
            0.0, "Interval between LatencyMarker injections at sources "
                 "(reference latencyTrackingInterval); markers ride the "
                 "operator chain and each operator records source->here "
                 "latency into its 'latency' histogram. 0 disables.")


class TraceOptions:
    """Causal tracing + flight recorder (docs/OBSERVABILITY.md,
    'Traces'): spans carry trace_id/span_id/parent_id and a TraceContext
    propagates across the control plane and transport, so a checkpoint's
    trigger -> align -> snapshot -> store -> ack -> complete fan-out
    forms one tree. The flight recorder keeps a bounded ring of recent
    spans/events and dumps it to a timestamped JSON file whenever a
    StallError, region restart, CorruptArtifactError, or zombie fence
    fires."""

    ENABLED = key("traces.enabled").bool_type().default_value(
        True, "Master switch for span reporting. Off, SpanBuilders still "
              "work (callers never branch) but completed spans are "
              "discarded before reporter fan-out.")
    MAX_RETAINED = key("traces.max-retained").int_type().default_value(
        4096, "Most recent completed spans the in-memory reporter (the "
              "REST /jobs/<name>/traces and CLI trace-dump surface) "
              "retains; stage spans (the per-batch / per-watermark / "
              "per-fire intervals of the mailbox loops, a dozen a "
              "batch) are kept apart, 16 times as many. Older spans "
              "are evicted and counted in the spans_dropped_total "
              "device-stats counter.")
    FLIGHT_CAPACITY = key("traces.flight-recorder.capacity").int_type() \
        .default_value(
            512, "Ring size of the always-on flight recorder: how many "
                 "recent spans/events a post-mortem dump preserves.")
    FLIGHT_DIR = key("traces.flight-recorder.dir").string_type() \
        .default_value(
            "", "Directory for automatic flight-recorder dump files "
                "('flight-<reason>-<ts>.json'). Empty = "
                "'<tmpdir>/flink_tpu_flight'.")
    FLIGHT_MIN_INTERVAL = key("traces.flight-recorder.min-interval") \
        .duration_type().default_value(
            1.0, "Rate limit between two automatic dumps for the SAME "
                 "reason, so a flapping fault cannot flood the disk.")


class ProfilerOptions:
    """Device-time ledger (docs/OBSERVABILITY.md, 'Device-time
    ledger'): per-program dispatch profiling attributed to
    ``(job, operator, site, shape-signature)`` keys — compile and
    dispatch durations, latency reservoirs, EWMA rates, static
    flop/byte cost estimates from the jaxpr, and recompile
    attribution (which builder dimension changed on every cache miss
    after the first). Off by default: a disabled ledger costs one
    attribute read per dispatch."""

    ENABLED = key("profiler.enabled").bool_type().default_value(
        False, "Master switch for the device-time ledger. Off, every "
               "dispatch site short-circuits to a single attribute "
               "read; nothing is recorded or retained.")
    RESERVOIR = key("profiler.reservoir").int_type().default_value(
        256, "Most recent per-dispatch durations retained per ledger "
             "entry (the p50/p95/max percentile window). Totals and "
             "counts are exact regardless of this bound.")
    RECOMPILE_HISTORY = key("profiler.recompile-history").int_type() \
        .default_value(
            64, "Recompile-attribution records retained (ring): every "
                "program-cache miss after a scope's first build stores "
                "which builder dimension changed vs the nearest prior "
                "build.")
    EWMA_ALPHA = key("profiler.ewma-alpha").float_type().default_value(
        0.2, "Smoothing factor for the per-entry EWMA duration and "
             "dispatch-rate estimates (1.0 = last sample only).")
    TRACE_SAMPLES = key("profiler.trace-samples").int_type() \
        .default_value(
            2048, "Most recent (timestamp, site, ms) samples retained "
                  "for the Perfetto counter-track export "
                  "(chrome_trace_events counters).")
    COST_GFLOPS = key("profiler.cost-model.gflops").float_type() \
        .default_value(
            50.0, "Assumed sustained compute rate (GFLOP/s) for the "
                  "static roofline estimate: estimated dispatch ms = "
                  "max(flops/gflops, bytes/gbps). Achieved-vs-"
                  "estimated is reported as measured/estimated.")
    COST_GBPS = key("profiler.cost-model.gbps").float_type() \
        .default_value(
            10.0, "Assumed memory bandwidth (GB/s) for the static "
                  "roofline estimate's byte term.")


class IsolationOptions:
    """Multi-tenant isolation (docs/ROBUSTNESS.md, 'Multi-tenant
    isolation'): the process-global device-time scheduler that meters
    each job's share from the device-time ledger, admits micro-batch
    dispatches by deficit-round-robin over ``isolation.job-weights``,
    and wraps every job in a bulkhead — bounded admission, a per-job
    circuit breaker, and an overload shedder that quarantines to the
    dead-letter side output with a typed ``OverloadShedError`` instead
    of silently dropping. Off by default: a disabled scheduler admits
    unconditionally at the cost of one attribute read."""

    ENABLED = key("isolation.enabled").bool_type().default_value(
        False, "Master switch for per-job admission quotas, bulkheads, "
               "and overload shedding. Off, every dispatch is admitted "
               "unconditionally and no per-job state is kept.")
    JOB_WEIGHTS = key("isolation.job-weights").string_type() \
        .default_value(
            "", "Per-job fair-share weights as 'jobA=3;jobB=1'. Jobs "
                "not listed (and all jobs when empty) get weight 1.0. "
                "Weights set the deficit-round-robin replenish ratio: "
                "a weight-3 job is admitted 3 micro-batches for every "
                "1 of a weight-1 competitor under contention.")
    QUANTUM = key("isolation.quantum").float_type().default_value(
        8.0, "Deficit-round-robin replenish quantum in admission "
             "credits (1 credit = 1 micro-batch dispatch) granted per "
             "unit weight each time every active demanding job has "
             "exhausted its deficit. Larger values trade fairness "
             "granularity for fewer scheduler rounds.")
    SHED_AFTER = key("isolation.shed-after").float_type().default_value(
        5.0, "Seconds a micro-batch may wait at the admission gate "
             "before the bulkhead sheds it to the dead-letter side "
             "output with OverloadShedError. 0 disables age-based "
             "shedding (the breaker can still shed).")
    BREAKER_FAILURES = key("isolation.breaker-failures").int_type() \
        .default_value(
            8, "Consecutive task failures that trip a job's circuit "
               "breaker open. While open, that job's new micro-batches "
               "are shed to the dead-letter output instead of "
               "dispatched; other jobs are unaffected.")
    BREAKER_COOLDOWN = key("isolation.breaker-cooldown").int_type() \
        .default_value(
            64, "Admission attempts (count-based, never wall-clock) a "
                "tripped breaker stays open before transitioning to "
                "half-open, where one probe batch is admitted; a "
                "success closes the breaker, a failure re-opens it.")
    QUEUE_BOUND = key("isolation.queue-bound").int_type().default_value(
        128, "Per-job bound on micro-batches concurrently waiting at "
             "the admission gate. Arrivals past the bound are shed "
             "immediately (bulkhead full) rather than queued. All "
             "admission decisions are count-based (a global attempt "
             "counter), never wall-clock or random, so schedules "
             "replay deterministically.")


class ExecutionOptions:
    """Bounded-job execution mode (reference ExecutionOptions.RUNTIME_MODE
    + BatchExecutionOptions)."""

    RUNTIME_MODE = key("execution.runtime-mode").string_type() \
        .default_value(
            "streaming", "streaming (pipelined, default) | batch: bounded "
                         "jobs run stage-by-stage over BLOCKING exchanges "
                         "— every producer materializes its whole output "
                         "before its consumer starts (reference "
                         "AdaptiveBatchScheduler/SortMergeResultPartition "
                         "shape).")
    SPECULATIVE = key("execution.batch.speculative.enabled").bool_type() \
        .default_value(
            False, "Batch mode only: re-dispatch a straggling subtask as "
                   "a second attempt once its stage's median sibling "
                   "finished; first attempt to finish wins, the loser is "
                   "cancelled (reference SpeculativeScheduler.java:89).")
    SPECULATIVE_FACTOR = key("execution.batch.speculative.multiplier") \
        .float_type().default_value(
            2.0, "A subtask is a straggler when it has run longer than "
                 "this multiple of its stage's median finished-subtask "
                 "duration.")


class SecurityOptions:
    """Cluster trust boundary (reference SecurityOptions.java). The
    pickle-bearing endpoints (dispatcher job submission, log broker,
    queryable state) authenticate connections with this shared secret; a
    non-loopback bind REFUSES to start without one (deserializing pickle
    from an unauthenticated routable socket is remote code execution)."""

    CLUSTER_SECRET = key("security.cluster-secret").string_type() \
        .default_value(
            "", "Shared secret all cluster processes present before any "
                "pickle deserialization on network endpoints. Empty (the "
                "default) is only acceptable for loopback binds; the "
                "FLINK_TPU_CLUSTER_SECRET environment variable is the "
                "fallback source.")
