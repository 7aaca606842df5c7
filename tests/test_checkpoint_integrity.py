"""Verified recovery (PR 4): checkpoint artifact integrity manifests,
restore-time digest verification, the retained-checkpoint fallback chain
with quarantine, refs-file resilience, changelog segment checksums, and
the `checkpoint.corrupt` / `checkpoint.truncate` fault sites under the
existing chaos harness.
"""

import json
import os
import pickle

import numpy as np
import pytest

from flink_tpu.checkpoint.storage import (
    MANIFEST_NAME, CheckpointNotFoundError, CompletedCheckpoint,
    CorruptArtifactError, FsCheckpointStorage, MemoryCheckpointStorage,
    retained_checkpoint_dirs,
)
from flink_tpu.metrics.device import DEVICE_STATS
from flink_tpu.runtime import faults as faults_mod

pytestmark = pytest.mark.integrity


@pytest.fixture(autouse=True)
def _clean_injector():
    faults_mod.FAULTS.reset()
    yield
    faults_mod.FAULTS.reset()


def _tpu_snap(n=200, seed=0):
    """A device-keyed snapshot shape (what gets chunked into key-group
    pages) built host-side — no device needed."""
    rng = np.random.default_rng(seed)
    keys = np.arange(n, dtype=np.int64)
    return {"kind": "tpu", "keys": keys,
            "key_groups": (keys % 128).astype(np.int64),
            "max_parallelism": 128,
            "states": {"acc": {"values": rng.integers(
                1, 100, n).astype(np.float64)}}}


def _cp(cid, snap, savepoint=False):
    return CompletedCheckpoint(cid, 0.0, {"task#0": {"keyed": snap}},
                               is_savepoint=savepoint)


def _chunks_of(st):
    return [f for f in os.listdir(st.chunk_dir) if not f.startswith("_")]


def _flip_byte(path, offset=None):
    size = os.path.getsize(path)
    pos = size // 2 if offset is None else offset
    with open(path, "r+b") as f:
        f.seek(pos)
        b = f.read(1)
        f.seek(pos)
        f.write(bytes([(b[0] if b else 0) ^ 0x40]))


# ---------------------------------------------------------------------------
# artifact format: manifest + digest round trip
# ---------------------------------------------------------------------------

class TestManifest:
    def test_store_writes_manifest_and_roundtrips(self, tmp_path):
        st = FsCheckpointStorage(str(tmp_path))
        snap = _tpu_snap()
        cp = st.store(_cp(1, snap))
        mpath = os.path.join(cp.external_path, MANIFEST_NAME)
        assert os.path.exists(mpath)
        with open(mpath) as f:
            manifest = json.load(f)
        meta = os.path.join(cp.external_path, "_metadata")
        assert manifest["metadata_size"] == os.path.getsize(meta)
        # every referenced chunk is on disk with the recorded size
        assert manifest["chunks"], "incremental store recorded no chunks"
        for name, info in manifest["chunks"].items():
            p = os.path.join(st.chunk_dir, name)
            assert os.path.getsize(p) == info["size"]
        info = st.verify_checkpoint(cp.external_path)
        assert info["manifest"] and info["chunks"] == len(manifest["chunks"])
        loaded = st.load(cp.external_path)
        got = loaded.task_snapshots["task#0"]["keyed"]
        np.testing.assert_array_equal(np.sort(np.asarray(got["keys"])),
                                      np.sort(np.asarray(snap["keys"])))

    def test_savepoint_manifest_covers_metadata(self, tmp_path):
        st = FsCheckpointStorage(str(tmp_path))
        cp = st.store(_cp(5, _tpu_snap(), savepoint=True))
        info = st.verify_checkpoint(cp.external_path)
        assert info["manifest"] and info["chunks"] == 0
        _flip_byte(os.path.join(cp.external_path, "_metadata"))
        with pytest.raises(CorruptArtifactError):
            st.verify_checkpoint(cp.external_path)

    def test_bit_flipped_chunk_is_detected_on_read_and_offline(
            self, tmp_path):
        st = FsCheckpointStorage(str(tmp_path))
        cp = st.store(_cp(1, _tpu_snap()))
        _flip_byte(os.path.join(st.chunk_dir, _chunks_of(st)[0]))
        with pytest.raises(CorruptArtifactError):
            st.verify_checkpoint(cp.external_path)
        with pytest.raises(CorruptArtifactError):
            st.load(cp.external_path)

    def test_truncated_chunk_is_detected(self, tmp_path):
        st = FsCheckpointStorage(str(tmp_path))
        cp = st.store(_cp(1, _tpu_snap()))
        name = _chunks_of(st)[0]
        p = os.path.join(st.chunk_dir, name)
        with open(p, "r+b") as f:
            f.truncate(os.path.getsize(p) // 2)
        with pytest.raises(CorruptArtifactError):
            st.verify_checkpoint(cp.external_path)
        with pytest.raises(CorruptArtifactError):
            st.load(cp.external_path)

    def test_corrupt_metadata_never_decodes_as_garbage(self, tmp_path):
        st = FsCheckpointStorage(str(tmp_path))
        cp = st.store(_cp(1, _tpu_snap()))
        _flip_byte(os.path.join(cp.external_path, "_metadata"))
        with pytest.raises(CorruptArtifactError):
            st.load(cp.external_path)

    def test_quarantine_renames_and_keeps_shared_chunks(self, tmp_path):
        st = FsCheckpointStorage(str(tmp_path))
        snap = _tpu_snap()
        cp1 = st.store(_cp(1, snap))
        cp2 = st.store(_cp(2, snap))  # same content: fully shared chunks
        n_chunks = len(_chunks_of(st))
        dest = st.quarantine(cp2)
        assert dest and dest.endswith(".corrupt") and os.path.isdir(dest)
        assert not os.path.exists(cp2.external_path)
        # cp1 still references every chunk: none was GC'd, and it loads
        assert len(_chunks_of(st)) == n_chunks
        st.verify_checkpoint(cp1.external_path)
        st.load(cp1.external_path)
        # quarantined dirs are invisible to the retained scan
        ids = [cid for cid, _ in retained_checkpoint_dirs(str(tmp_path))]
        assert ids == [1]


# ---------------------------------------------------------------------------
# atomic commit + refs resilience
# ---------------------------------------------------------------------------

class TestCrashAndRefs:
    def test_crash_between_chunk_write_and_manifest_rename(self, tmp_path):
        """Simulated kill mid-store: chunks of the dying checkpoint are on
        disk but neither manifest nor metadata was renamed — the PRIOR
        checkpoint still verifies and restores, and a fresh storage
        instance (new process) sees exactly one retained checkpoint."""
        st = FsCheckpointStorage(str(tmp_path))
        cp1 = st.store(_cp(1, _tpu_snap(seed=1)))
        # "crash": chunks written + refs mutated in memory, no commit
        st._current_chunks = set()
        st._chunk_snapshots(_cp(2, _tpu_snap(seed=2)))
        st2 = FsCheckpointStorage(str(tmp_path))  # restart
        assert [c for c, _ in retained_checkpoint_dirs(str(tmp_path))] == [1]
        st2.verify_checkpoint(cp1.external_path)
        loaded = st2.load(cp1.external_path)
        assert "task#0" in loaded.task_snapshots

    def test_corrupt_refs_file_rebuilds_from_manifests(self, tmp_path):
        st = FsCheckpointStorage(str(tmp_path))
        cp1 = st.store(_cp(1, _tpu_snap()))
        with open(st._refs_path, "wb") as f:
            f.write(b"\x80\x04definitely-not-a-pickle")
        st2 = FsCheckpointStorage(str(tmp_path))  # must not crash
        assert st2._refs, "refs not rebuilt from the surviving manifest"
        assert all(1 in refs for refs in st2._refs.values())
        st2.load(cp1.external_path)

    def test_lost_refs_file_does_not_reset_refcounts(self, tmp_path):
        """A LOST refs file used to silently reset refcounts to {},
        letting GC delete chunks still referenced by retained
        checkpoints. The rebuild scan restores them."""
        st = FsCheckpointStorage(str(tmp_path))
        snap = _tpu_snap()
        cp1 = st.store(_cp(1, snap))
        st.store(_cp(2, snap))
        os.unlink(st._refs_path)
        st2 = FsCheckpointStorage(str(tmp_path))
        # discarding cp2 must NOT delete chunks cp1 still references
        st2.discard(CompletedCheckpoint(2, 0.0, {}))
        st2.verify_checkpoint(cp1.external_path)
        st2.load(cp1.external_path)


# ---------------------------------------------------------------------------
# typed not-found errors
# ---------------------------------------------------------------------------

class TestNotFound:
    def test_memory_storage_missing_id(self):
        st = MemoryCheckpointStorage()
        with pytest.raises(CheckpointNotFoundError):
            st.load(999)
        # back-compat: pre-typed callers caught KeyError
        with pytest.raises(KeyError):
            st.load(999)

    def test_fs_storage_missing_path(self, tmp_path):
        st = FsCheckpointStorage(str(tmp_path))
        with pytest.raises(CheckpointNotFoundError):
            st.load(os.path.join(str(tmp_path), "chk-404"))
        with pytest.raises(FileNotFoundError):
            st.load(os.path.join(str(tmp_path), "chk-404"))


# ---------------------------------------------------------------------------
# changelog (DSTL) segment checksums
# ---------------------------------------------------------------------------

class TestChangelogSegments:
    def test_segment_digest_roundtrip_and_detection(self, tmp_path):
        from flink_tpu.state.dstl import (
            FsChangelogStorage, read_any_segment,
        )

        store = FsChangelogStorage(str(tmp_path))
        records = [(i, ("put", f"k{i}", i)) for i in range(1, 50)]
        h = store.write_segment(records)
        assert h.digest
        assert store.read_segment(h) == records
        assert read_any_segment(h.__dict__, str(tmp_path)) == records
        _flip_byte(os.path.join(str(tmp_path), h.location))
        with pytest.raises(CorruptArtifactError):
            store.read_segment(h)
        with pytest.raises(CorruptArtifactError):
            read_any_segment(h.__dict__, str(tmp_path))

    def test_legacy_handle_without_digest_still_reads(self, tmp_path):
        from flink_tpu.state.dstl import FsChangelogStorage, SegmentHandle

        store = FsChangelogStorage(str(tmp_path))
        records = [(1, ("put", "k", 1))]
        h = store.write_segment(records)
        legacy = SegmentHandle(h.segment_id, h.from_seq, h.to_seq,
                               "fs", h.location)  # no digest recorded
        assert store.read_segment(legacy) == records


# ---------------------------------------------------------------------------
# fallback chain: corrupt newest of 3 retained -> restore from #2
# ---------------------------------------------------------------------------

class _CheckpointAwareCrashingSink:
    """Collects rows; once `crash_after` rows passed AND >= `want`
    retained checkpoints exist on disk, raises exactly once. Never
    blocks the mailbox (barriers must keep flowing through the sink for
    checkpoints to complete) — it throttles each batch slightly so
    several checkpoint intervals elapse mid-stream."""

    def __init__(self, ckpt_dir: str, crash_after: int, want: int = 3):
        self.rows = []
        self.ckpt_dir = ckpt_dir
        self.crash_after = crash_after
        self.want = want
        self.tripped = False

    def _n_retained(self):
        return len(retained_checkpoint_dirs(self.ckpt_dir))

    def invoke_batch(self, batch):
        import time
        self.rows.extend(batch.iter_rows())
        if not self.tripped:
            time.sleep(0.002)
            if (len(self.rows) > self.crash_after
                    and self._n_retained() >= self.want):
                self.tripped = True
                raise RuntimeError(
                    f"injected crash at {len(self.rows)} rows with "
                    f"{self._n_retained()} retained checkpoints")
        return True


def _keyed_sum_supervisor(tmp_path, sink, retained=3, seed=7):
    from flink_tpu.api.environment import StreamExecutionEnvironment
    from flink_tpu.cluster.scheduler import JobSupervisor
    from flink_tpu.core.config import (
        CheckpointingOptions, PipelineOptions, RuntimeOptions,
    )
    from flink_tpu.core.functions import SinkFunction
    from flink_tpu.core.records import Schema

    class _Sink(SinkFunction):
        def invoke_batch(self, batch):
            return sink.invoke_batch(batch)

    rng = np.random.default_rng(seed)
    n = 20_000
    keys = rng.integers(0, 7, n)
    vals = rng.integers(1, 100, n)
    env = StreamExecutionEnvironment()
    env.config.set(PipelineOptions.BATCH_SIZE, 32)
    env.config.set(CheckpointingOptions.DIRECTORY, str(tmp_path))
    env.config.set(CheckpointingOptions.INTERVAL, 0.03)
    env.config.set(CheckpointingOptions.RETAINED, retained)
    env.config.set(RuntimeOptions.RESTART_STRATEGY, "fixed-delay")
    env.config.set(RuntimeOptions.RESTART_ATTEMPTS, 10)
    env.config.set(RuntimeOptions.RESTART_DELAY, 0.02)
    schema = Schema([("k", np.int64), ("v", np.int64)])
    rows = [(int(k), int(v)) for k, v in zip(keys, vals)]
    ds = env.from_collection(rows, schema, timestamps=list(range(n)))
    ds.key_by("k").sum(1).add_sink(_Sink(), "sink")
    sup = JobSupervisor(env.get_job_graph("verified-recovery"), env.config)
    expect = {}
    for k, v in zip(keys, vals):
        expect[int(k)] = expect.get(int(k), 0) + int(v)
    return sup, expect


def _install_corruption_hook(monkeypatch, ckpt_dir, corrupt_all=False):
    """Bit-flip retained checkpoint metadata at EXACTLY the restore
    decision point (deterministic: no race with in-flight checkpoint
    completions), then run the real verified-candidate walk."""
    from flink_tpu.checkpoint.coordinator import CheckpointCoordinator

    orig = CheckpointCoordinator.latest_verified_checkpoint
    state = {"corrupted": []}

    def hooked(self):
        dirs = retained_checkpoint_dirs(ckpt_dir)
        if dirs and not state["corrupted"]:
            targets = dirs if corrupt_all else dirs[-1:]
            for cid, path in targets:
                _flip_byte(os.path.join(path, "_metadata"))
                state["corrupted"].append(cid)
        return orig(self)

    monkeypatch.setattr(CheckpointCoordinator,
                        "latest_verified_checkpoint", hooked)
    return state


def test_fallback_chain_restores_next_oldest(tmp_path, monkeypatch):
    """The acceptance trial: 3 retained checkpoints, the newest one
    bit-flipped — the job restores from the next-oldest VERIFIED
    checkpoint with exactly-once output, restore_fallbacks_total >= 1, a
    corrupt-artifact event on the REST exceptions surface, and the
    corrupt artifact quarantined on disk."""
    from types import SimpleNamespace

    from flink_tpu.cluster.rest import RestEndpoint

    vf0 = DEVICE_STATS.verify_failures
    rf0 = DEVICE_STATS.restore_fallbacks
    sink = _CheckpointAwareCrashingSink(str(tmp_path), crash_after=2000)
    sup, expect = _keyed_sum_supervisor(tmp_path, sink)
    state = _install_corruption_hook(monkeypatch, str(tmp_path))
    sup.run(timeout=120.0)
    assert sup.attempt >= 2, "crash never triggered a restart"
    assert state["corrupted"], "hook never corrupted a checkpoint"
    corrupted_id = state["corrupted"][0]

    # exactly-once keyed totals (max-dedup absorbs restart replays)
    totals = {}
    for k, v in sink.rows:
        totals[k] = max(totals.get(k, 0), int(v))
    assert totals == expect

    # counters moved
    assert DEVICE_STATS.verify_failures >= vf0 + 1
    assert DEVICE_STATS.restore_fallbacks >= rf0 + 1

    # restored from an OLDER checkpoint than the corrupted one
    restarts = [e for e in sup.failure_history if e["kind"] == "restart"]
    assert restarts and restarts[0]["restored_checkpoint"] is not None
    assert restarts[0]["restored_checkpoint"] < corrupted_id
    kinds = {e["kind"] for e in sup.failure_history}
    assert "corrupt-artifact" in kinds and "restore-fallback" in kinds

    # corrupt artifact quarantined on disk, invisible to the retained scan
    assert any(".corrupt" in name for name in os.listdir(str(tmp_path)))
    assert corrupted_id not in [
        c for c, _ in retained_checkpoint_dirs(str(tmp_path))]

    # the corrupt-artifact event rides REST /jobs/<name>/exceptions
    ep = RestEndpoint()
    ep.register_job("vr", SimpleNamespace(
        failure_history=list(sup.failure_history)))
    rest_kinds = [e["kind"] for e in ep._exceptions("vr")["entries"]]
    assert "corrupt-artifact" in rest_kinds


def test_all_retained_corrupt_fails_typed_never_restores_garbage(
        tmp_path, monkeypatch):
    """With EVERY retained checkpoint corrupted, the job must fail with
    CorruptArtifactError — silently restarting from scratch would replay
    the whole stream past committed output."""
    sink = _CheckpointAwareCrashingSink(str(tmp_path), crash_after=2000,
                                        want=2)
    sup, _expect = _keyed_sum_supervisor(tmp_path, sink)
    state = _install_corruption_hook(monkeypatch, str(tmp_path),
                                     corrupt_all=True)
    with pytest.raises(CorruptArtifactError):
        sup.run(timeout=120.0)
    assert state["corrupted"], "hook never corrupted a checkpoint"
    assert len(retained_checkpoint_dirs(str(tmp_path))) == 0


def test_verify_disabled_skips_the_walk(tmp_path, monkeypatch):
    """checkpoint.verify-on-restore=false restores the pre-PR behavior:
    the newest retained checkpoint is trusted as-is (corruption of the
    ON-DISK artifact is invisible to the in-memory restore path)."""
    from flink_tpu.core.config import CheckpointingOptions

    vf0 = DEVICE_STATS.verify_failures
    sink = _CheckpointAwareCrashingSink(str(tmp_path), crash_after=2000,
                                        want=2)
    sup, expect = _keyed_sum_supervisor(tmp_path, sink)
    sup.config.set(CheckpointingOptions.VERIFY_ON_RESTORE, False)
    _install_corruption_hook(monkeypatch, str(tmp_path), corrupt_all=True)
    sup.run(timeout=120.0)
    assert sup.attempt >= 2
    assert DEVICE_STATS.verify_failures == vf0
    totals = {}
    for k, v in sink.rows:
        totals[k] = max(totals.get(k, 0), int(v))
    assert totals == expect


# ---------------------------------------------------------------------------
# chaos: checkpoint.corrupt / checkpoint.truncate fault sites
# ---------------------------------------------------------------------------

@pytest.mark.chaos
@pytest.mark.parametrize("site,seed", [
    ("checkpoint.corrupt", 0), ("checkpoint.corrupt", 1),
    ("checkpoint.truncate", 0), ("checkpoint.truncate", 1),
])
def test_corruption_fault_site_is_deterministic_and_detected(
        tmp_path, site, seed):
    """One `site=once@5` trip: the 5th chunk write of the store is
    mutated on disk, verification + load detect it (typed, never
    np.frombuffer garbage), and the same seed+spec replays the identical
    trip visit — byte-identical chaos."""
    events = []
    for trial in range(2):
        faults_mod.FAULTS.configure_spec(f"{site}=once@5", seed=seed)
        st = FsCheckpointStorage(str(tmp_path / f"t{trial}"))
        cp = st.store(_cp(1, _tpu_snap(seed=seed)))
        events.append(list(faults_mod.FAULTS.events))
        assert faults_mod.FAULTS.snapshot()["trips"][site] == 1
        with pytest.raises(CorruptArtifactError):
            st.verify_checkpoint(cp.external_path)
        with pytest.raises(CorruptArtifactError):
            st.load(cp.external_path)
        faults_mod.FAULTS.reset()
    assert events[0] == events[1], "chaos schedule did not replay"


@pytest.mark.chaos
def test_corrupting_shared_chunk_poisons_every_referent(tmp_path):
    """The dedup hazard from the issue: a `checkpoint.corrupt` trip on a
    chunk SHARED across retained checkpoints (unchanged content pages)
    fails verification of every checkpoint referencing it — which is
    exactly why the fallback chain walks until a checkpoint verifies."""
    st = FsCheckpointStorage(str(tmp_path))
    snap = _tpu_snap()
    cp1 = st.store(_cp(1, snap))
    # the second store dedups every page; arm the site so its first chunk
    # visit (a dedup hit on a shared chunk) mutates the shared file
    faults_mod.FAULTS.configure_spec("checkpoint.corrupt=once@1", seed=0)
    cp2 = st.store(_cp(2, snap))
    faults_mod.FAULTS.reset()
    with pytest.raises(CorruptArtifactError):
        st.verify_checkpoint(cp2.external_path)
    with pytest.raises(CorruptArtifactError):
        st.verify_checkpoint(cp1.external_path)


@pytest.mark.chaos
@pytest.mark.parametrize("seed", [0, 1])
def test_device_pipeline_exactly_once_under_corruption_chaos(
        tmp_path, seed):
    """End-to-end chaos: the device window pipeline with a persistent
    sink fault (forces restore-from-checkpoint) while checkpoint.corrupt
    mutates stored chunks — results stay exactly-once whether the
    restore used the newest checkpoint or fell back past a corrupt one,
    and the restore path never materializes garbage state."""
    from flink_tpu.api.environment import StreamExecutionEnvironment
    from flink_tpu.cluster.scheduler import JobSupervisor
    from flink_tpu.core.config import (
        CheckpointingOptions, FaultOptions, PipelineOptions, RuntimeOptions,
    )
    from flink_tpu.core.functions import SinkFunction
    from flink_tpu.core.records import Schema
    from flink_tpu.core.watermarks import WatermarkStrategy
    from flink_tpu.runtime.operators.device_window import AggSpec
    from flink_tpu.window import TumblingEventTimeWindows

    n, n_keys, pane = 1 << 12, 23, 1000
    env = StreamExecutionEnvironment()
    env.set_state_backend("tpu")
    env.config.set(PipelineOptions.BATCH_SIZE, 512)
    env.config.set(CheckpointingOptions.DIRECTORY, str(tmp_path))
    env.config.set(CheckpointingOptions.INTERVAL, 0.05)
    env.config.set(CheckpointingOptions.RETAINED, 3)
    env.config.set(RuntimeOptions.RESTART_STRATEGY, "fixed-delay")
    env.config.set(RuntimeOptions.RESTART_ATTEMPTS, 10)
    env.config.set(RuntimeOptions.RESTART_DELAY, 0.02)
    env.config.set(FaultOptions.ENABLED, True)
    env.config.set(FaultOptions.SEED, seed)
    env.config.set(
        FaultOptions.SPEC,
        f"checkpoint.corrupt=every@40,sink.invoke=once@{2 + seed}"
        "!persistent")

    def gen(idx):
        return {"k": (idx * 11) % n_keys, "v": (idx % 13) + 1,
                "ts": (idx * 6 * pane) // n}

    class _Sink(SinkFunction):
        def __init__(self):
            self.rows = []

        def invoke_batch(self, batch):
            self.rows.extend(batch.iter_rows())
            return True

    schema = Schema([("k", np.int64), ("v", np.int64), ("ts", np.int64)])
    ws = WatermarkStrategy.for_monotonous_timestamps() \
        .with_timestamp_column("ts")
    sink = _Sink()
    (env.datagen(gen, schema, count=n, timestamp_column="ts",
                 watermark_strategy=ws)
        .key_by("k")
        .window(TumblingEventTimeWindows.of(pane))
        .device_aggregate([AggSpec("count", out_name="cnt", value_bits=31),
                           AggSpec("sum", "v", out_name="total")],
                          capacity=1 << 12, ring_size=8,
                          emit_window_bounds=True, defer_overflow=True)
        .add_sink(sink, "sink"))
    sup = JobSupervisor(env.get_job_graph(f"corrupt-chaos-{seed}"),
                        env.config)
    sup.run(timeout=120.0)
    assert sup.attempt >= 2, "persistent sink fault never forced a restart"

    idx = np.arange(n)
    keys, vals = (idx * 11) % n_keys, (idx % 13) + 1
    ts = (idx * 6 * pane) // n
    expect = {}
    for k, v, t in zip(keys, vals, ts):
        end = (int(t) // pane + 1) * pane
        c, s = expect.get((int(k), end), (0, 0))
        expect[(int(k), end)] = (c + 1, s + int(v))
    # restart replay may re-emit windows fired after the last checkpoint
    # (the sink is not transactional), but EVERY emission — original or
    # replayed — must carry the exact oracle value: a restore from a
    # half-read/garbage artifact would emit diverging aggregates here
    got = {}
    for k, _ws, we, cnt, total in sink.rows:
        key = (int(k), int(we))
        assert key in expect, f"seed {seed}: phantom window {key}"
        assert (int(cnt), int(total)) == expect[key], \
            f"seed {seed}: window {key} diverged under corruption"
        got[key] = (int(cnt), int(total))
    assert got == expect, f"seed {seed}: windows missing under corruption"


# ---------------------------------------------------------------------------
# observability + CLI surfaces
# ---------------------------------------------------------------------------

def test_counters_reach_prometheus_and_snapshot():
    from flink_tpu.metrics.core import MetricRegistry
    from flink_tpu.metrics.device import bind_device_metrics
    from flink_tpu.metrics.reporters import prometheus_text

    reg = MetricRegistry()
    bind_device_metrics(reg)
    text = prometheus_text(reg)
    for name in ("checkpoint_verify_failures_total",
                 "restore_fallbacks_total"):
        assert name in text, f"{name} missing from /metrics"
    snap = DEVICE_STATS.snapshot()
    assert "checkpoint_verify_failures_total" in snap
    assert "restore_fallbacks_total" in snap


def test_cli_checkpoint_verify_table_and_exit_codes(tmp_path, capsys):
    from flink_tpu.cli import main

    st = FsCheckpointStorage(str(tmp_path))
    st.store(_cp(1, _tpu_snap(seed=1)))
    cp2 = st.store(_cp(2, _tpu_snap(seed=2)))
    assert main(["checkpoint-verify", str(tmp_path)]) == 0
    out = capsys.readouterr().out
    assert "chk-1" in out and "chk-2" in out and "OK" in out
    _flip_byte(os.path.join(cp2.external_path, "_metadata"))
    assert main(["checkpoint-verify", str(tmp_path)]) == 1
    out = capsys.readouterr().out
    assert "CORRUPT" in out
    assert main(["checkpoint-verify",
                 str(tmp_path / "does-not-exist")]) == 2


def test_cli_savepoint_info_missing_and_corrupt(tmp_path, capsys):
    from flink_tpu.cli import main

    assert main(["savepoint-info",
                 str(tmp_path / "sp-404")]) == 1
    assert "no savepoint" in capsys.readouterr().err
    st = FsCheckpointStorage(str(tmp_path))
    cp = st.store(_cp(3, _tpu_snap(), savepoint=True))
    _flip_byte(os.path.join(cp.external_path, "_metadata"))
    assert main(["savepoint-info", cp.external_path]) == 1
    assert "corrupt" in capsys.readouterr().err.lower()


def test_ha_record_corruption_is_unreadable_not_fatal(tmp_path):
    """Satellite: a corrupt HA checkpoint record (unpicklable bytes) no
    longer crashes get_checkpoint — it reads as missing, and the HA
    recovery path falls back to scanning retained checkpoint dirs."""
    from flink_tpu.cluster.ha import FileHaServices

    ha = FileHaServices(str(tmp_path))
    path = os.path.join(str(tmp_path), "checkpoints", "job.pkl")
    with open(path, "wb") as f:
        f.write(b"\x80\x04 this is not a pickle")
    assert ha.get_checkpoint("job") is None
