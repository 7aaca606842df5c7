"""End-to-end causal tracing + failure flight recorder: one trace tree
per checkpoint across threads/hosts (context rides control messages and
``CheckpointBarrier.trace``), net/restart episode spans, Perfetto
(Chrome trace-event) export schema, post-mortem dump files at the fault
chokepoints, and the doc-code inventory lock that keeps
docs/OBSERVABILITY.md's span table from rotting."""

import json
import os
import threading
import time
import urllib.error
import urllib.request
from types import SimpleNamespace

import numpy as np
import pytest

from flink_tpu.api.environment import StreamExecutionEnvironment
from flink_tpu.connectors.core import CollectSink
from flink_tpu.core.config import (
    CheckpointingOptions, PipelineOptions, RuntimeOptions, TraceOptions,
)
from flink_tpu.core.records import RecordBatch, Schema
from flink_tpu.metrics.device import DEVICE_STATS
from flink_tpu.metrics.tracing import (
    FLIGHT_RECORDER, InMemoryTraceReporter, TRACER,
    TraceContext, Tracer, chrome_trace_events, current_context, use_context,
)
from flink_tpu.runtime import faults as faults_mod
from flink_tpu.runtime.watchdog import WATCHDOG, StallError

pytestmark = pytest.mark.tracing

SCHEMA = Schema([("k", np.int64), ("v", np.int64)])


@pytest.fixture(autouse=True)
def _clean_tracing():
    """Process-global tracer/flight-recorder/injector state is shared;
    isolate every test and restore the recorder's dump target."""
    dump_dir = FLIGHT_RECORDER.dump_dir
    interval = FLIGHT_RECORDER.min_dump_interval_s
    TRACER.reset()
    faults_mod.FAULTS.reset()
    WATCHDOG.reset()
    yield
    TRACER.reset()
    faults_mod.FAULTS.reset()
    WATCHDOG.reset()
    FLIGHT_RECORDER.dump_dir = dump_dir
    FLIGHT_RECORDER.min_dump_interval_s = interval


def _spans():
    return TRACER.retained_spans()


def _tree(spans, trace_id):
    return [s for s in spans if s.trace_id == trace_id]


# -- span identity + context propagation ------------------------------------

def test_nested_spans_share_one_trace_tree():
    mem = InMemoryTraceReporter()
    t = Tracer([mem])
    with t.span("unit", "Outer") as outer:
        with t.span("unit", "Inner"):
            pass
    inner, = mem.by_name("Inner")
    out, = mem.by_name("Outer")
    assert inner.trace_id == out.trace_id
    assert inner.parent_id == out.span_id
    assert out.parent_id == ""
    assert current_context() is None  # the ambient stack unwound


def test_trace_context_wire_roundtrip_parents_across_boundary():
    """The cross-host path: a context serialized into a control message
    reconstructs on the far side and parents a span started there."""
    mem = InMemoryTraceReporter()
    t = Tracer([mem])
    root = t.span("unit", "Root")
    wire = root.context.to_wire()
    assert set(wire) == {"trace_id", "span_id"}
    ctx = TraceContext.from_wire(json.loads(json.dumps(wire)))
    t.span("unit", "Remote", parent=ctx).finish()
    root.finish()
    remote, = mem.by_name("Remote")
    assert remote.trace_id == root.context.trace_id
    assert remote.parent_id == root.context.span_id
    assert TraceContext.from_wire(None) is None
    assert TraceContext.from_wire({"junk": 1}) is None


def test_use_context_adopts_foreign_parent():
    mem = InMemoryTraceReporter()
    t = Tracer([mem])
    ctx = TraceContext("t" * 16, "s" * 16)
    with use_context(ctx):
        t.span("unit", "Adopted").finish()
    sp, = mem.by_name("Adopted")
    assert sp.trace_id == "t" * 16 and sp.parent_id == "s" * 16


def test_monotonic_clock_clamps_backwards_end():
    """Satellite: epoch-ms timestamps from the monotonic clock; a caller
    handing a skewed end never yields a negative duration."""
    mem = InMemoryTraceReporter()
    sb = Tracer([mem]).span("unit", "Clamp")
    sp = sb.finish(end_ms=sb._start_ns // 1_000_000 - 500)
    assert sp.end_ms == sp.start_ms and sp.duration_ms == 0
    # and now_ms tracks epoch time closely enough to line up with logs
    from flink_tpu.metrics.tracing import now_ms
    assert abs(now_ms() - time.time() * 1000.0) < 5_000


def test_bounded_reporter_evicts_and_counts_drops():
    """Satellite: the in-memory ring is bounded by traces.max-retained
    and evictions surface as the spans_dropped_total device counter."""
    d0 = DEVICE_STATS.spans_dropped
    mem = InMemoryTraceReporter(max_retained=8)
    t = Tracer([mem])
    for i in range(20):
        t.span("unit", "Evict").set_attribute("i", i).finish()
    assert len(mem.snapshot()) == 8
    assert mem.dropped == 12
    assert DEVICE_STATS.spans_dropped == d0 + 12
    # the retained window is the most recent spans
    assert [s.attributes["i"] for s in mem.snapshot()] == list(range(12, 20))


def test_tracer_configure_applies_trace_options():
    from flink_tpu.core.config import Configuration

    cfg = Configuration()
    cfg.set(TraceOptions.ENABLED, False)
    cfg.set(TraceOptions.MAX_RETAINED, 7)
    cfg.set(TraceOptions.FLIGHT_CAPACITY, 9)
    TRACER.configure(cfg)
    try:
        TRACER.span("unit", "Dark").finish()
        assert _spans() == []          # disabled: nothing reported
        assert FLIGHT_RECORDER.capacity == 9
    finally:
        TRACER.reset()
        TRACER.configure(Configuration())
    assert FLIGHT_RECORDER.capacity == 512


# -- one trace tree per checkpoint: local ------------------------------------

def test_local_checkpoint_forms_single_trace_tree():
    """Trigger → Align → Snapshot → Store → Notify all share the root's
    trace_id, and the task-side spans (emitted on mailbox threads from
    the barrier's wire context) parent directly on the root."""
    from flink_tpu.checkpoint.coordinator import CheckpointCoordinator

    env = StreamExecutionEnvironment()
    env.set_parallelism(2)
    env.config.set(PipelineOptions.BATCH_SIZE, 8)
    n = 2000
    rows = [(i % 3, i) for i in range(n)]
    ds = env.from_collection(rows, SCHEMA, timestamps=list(range(n)))
    ds.key_by("k").sum(1).add_sink(CollectSink(), "s")
    job = env.execute_async("trace-tree")
    coord = CheckpointCoordinator(job, env.config, tracer=TRACER)
    cp = None
    for _ in range(50):
        try:
            cp = coord.trigger_savepoint(timeout=2)
            break
        except Exception:
            time.sleep(0.02)
    job.wait(30)
    assert cp is not None, "no savepoint completed"
    spans = _spans()
    roots = [s for s in spans if s.name == "Checkpoint"
             and s.attributes.get("checkpointId") == cp.checkpoint_id]
    assert len(roots) == 1
    root = roots[0]
    assert root.parent_id == ""
    tree = _tree(spans, root.trace_id)
    by_name = {}
    for s in tree:
        by_name.setdefault(s.name, []).append(s)
    for name in ("Align", "Snapshot", "Store", "Notify"):
        assert by_name.get(name), f"{name} span missing from the tree"
    # every non-root span in the tree hangs directly off the root
    for s in tree:
        if s is not root:
            assert s.parent_id == root.span_id, (s.name, s.parent_id)
    # each subtask snapshotted inside this tree exactly once
    snap_tasks = [s.attributes["task"] for s in by_name["Snapshot"]]
    assert len(snap_tasks) == len(set(snap_tasks)) == len(job.tasks)


# -- one trace tree per checkpoint: two hosts over real TCP ------------------

def test_two_host_checkpoint_single_tree_across_transport():
    """Acceptance: a distributed checkpoint's coordinator-side spans
    (root/Store/Notify on host 0) and worker-side Snapshot spans (both
    hosts, context carried inside the trigger control message over a
    real socket) form ONE tree with consistent parent/child ids."""
    from flink_tpu.cluster.distributed import DistributedHost

    graphs = []
    for h in range(2):
        env = StreamExecutionEnvironment()
        env.set_parallelism(2)
        env.config.set(PipelineOptions.BATCH_SIZE, 4)
        env.config.set(CheckpointingOptions.INTERVAL, 0.02)
        n = 4000
        rows = [(i % 7, i) for i in range(n)]
        ds = env.from_collection(rows, SCHEMA, timestamps=list(range(n)))
        ds.key_by("k").sum(1).add_sink(CollectSink(), "sink")
        graphs.append(env.get_job_graph("dist-trace"))

    h0 = DistributedHost(graphs[0], graphs[0].config, 0, 2)
    h1 = DistributedHost(graphs[1], graphs[1].config, 1, 2,
                         coordinator_addr=f"127.0.0.1:"
                         f"{h0.coordinator.port}")
    peers = {0: h0.data_address, 1: h1.data_address}
    threads = [threading.Thread(target=h.run, args=(peers,),
                                kwargs={"timeout": 90}, daemon=True)
               for h in (h1, h0)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(120)
    assert not any(t.is_alive() for t in threads)
    completed = list(h0.coordinator.completed)
    h0.close()
    h1.close()
    assert completed, "no distributed checkpoint completed"

    spans = _spans()
    # pick a completed checkpoint whose fan-out finished (Notify present)
    done_cids = {s.attributes.get("checkpointId")
                 for s in spans if s.name == "Notify"}
    assert done_cids, "no completed checkpoint tree"
    cid = sorted(done_cids)[0]
    root, = [s for s in spans if s.name == "Checkpoint"
             and s.attributes.get("checkpointId") == cid]
    assert root.attributes.get("hosts") == 2
    tree = _tree(spans, root.trace_id)
    snaps = [s for s in tree if s.name == "Snapshot"]
    assert snaps, "no worker-side Snapshot spans joined the tree"
    for s in tree:
        if s is not root:
            assert s.parent_id == root.span_id
    assert any(s.name == "Store" for s in tree)
    # placement spreads subtasks round-robin (subtask_host = sub % 2):
    # the tree holds spans emitted on BOTH sides of the wire
    hosts = {int(s.attributes["task"].rsplit("#", 1)[1]) % 2
             for s in snaps}
    assert hosts == {0, 1}, f"snapshot spans from one host only: {hosts}"


# -- net episode spans -------------------------------------------------------

@pytest.mark.netfault
def test_sever_and_heal_emits_reconnect_span():
    """A net.sever heal (redial + replay, no restart) lands a net /
    Reconnect span whose attributes carry the channel and replay size."""
    from flink_tpu.cluster.transport import (
        RemoteChannelSender, TransportServer,
    )

    srv = TransportServer()
    recv = srv.channel("edge")
    snd = RemoteChannelSender(srv.host, srv.port, "edge")
    faults_mod.FAULTS.configure_spec("net.sever=every@3", seed=0)
    n = 12
    for i in range(n):
        assert snd.put(RecordBatch(SCHEMA,
                                   {"k": np.array([i], np.int64),
                                    "v": np.array([i], np.int64)},
                                   np.array([i], np.int64)), timeout=10)
    got = []
    deadline = time.time() + 15
    while len(got) < n and time.time() < deadline:
        e = recv.poll()
        if e is None:
            time.sleep(0.002)
        else:
            got.append(int(e.column("k")[0]))
    faults_mod.FAULTS.configure_spec("", enabled=False)
    assert got == list(range(n))
    reconnects = [s for s in _spans()
                  if s.scope == "net" and s.name == "Reconnect"]
    assert reconnects
    assert reconnects[0].attributes["channel"] == "edge"
    assert reconnects[0].attributes["attempts"] >= 1
    snd.close()
    srv.close()


@pytest.mark.netfault
def test_zombie_fence_emits_fence_span():
    from flink_tpu.cluster.transport import (
        FencedError, RemoteChannelSender, TransportServer,
    )

    srv = TransportServer()
    srv.set_epoch(7)
    snd = RemoteChannelSender(srv.host, srv.port, "edge", epoch=3)
    with pytest.raises(FencedError):
        for i in range(50):
            snd.put(RecordBatch(SCHEMA,
                                {"k": np.array([i], np.int64),
                                 "v": np.array([i], np.int64)},
                                np.array([i], np.int64)), timeout=0.2)
            time.sleep(0.02)
    deadline = time.time() + 5
    while time.time() < deadline:
        fences = [s for s in _spans()
                  if s.scope == "net" and s.name == "Fence"]
        if fences:
            break
        time.sleep(0.02)
    assert fences, "fence span never reported"
    assert fences[0].attributes["peer_epoch"] == 3
    assert fences[0].attributes["epoch"] == 7
    snd.close()
    srv.close()


# -- region restart: span + automatic flight dump ----------------------------

class _Bomb:
    """Map fn raising once, process-wide, at a given record value."""

    armed = True

    def __init__(self, at):
        self.at = at

    def __call__(self, row):
        if _Bomb.armed and row[1] == self.at:
            _Bomb.armed = False
            raise RuntimeError("boom")
        return row


@pytest.mark.chaos
def test_region_restart_emits_span_and_flight_dump(tmp_path):
    """A pipelined-region failover trips the restart / RegionRestart
    span AND writes a flight-recorder dump (reason region-restart) whose
    pre-failure entries are preserved on disk."""
    from flink_tpu.cluster.scheduler import JobSupervisor

    _Bomb.armed = True
    env = StreamExecutionEnvironment()
    env.config.set(PipelineOptions.BATCH_SIZE, 4)
    env.config.set(CheckpointingOptions.INTERVAL, 0.05)
    env.config.set(RuntimeOptions.RESTART_STRATEGY, "fixed-delay")
    env.config.set(TraceOptions.FLIGHT_DIR, str(tmp_path))
    n = 400
    rows = [(i % 3, i) for i in range(n)]
    sink_a, sink_b = CollectSink(), CollectSink()
    (env.from_collection(rows, SCHEMA, timestamps=list(range(n)),
                         name="src-a")
        .map(_Bomb(250), name="bomb")
        .key_by("k").sum(1).add_sink(sink_a, "sink-a"))
    (env.from_collection(rows, SCHEMA, timestamps=list(range(n)),
                         name="src-b")
        .key_by("k").sum(1).add_sink(sink_b, "sink-b"))
    jg = env.get_job_graph("trace-regions")
    sup = JobSupervisor(jg, env.config)
    sup.run(timeout=120)
    assert sup.failures, "the bomb never went off"
    restarts = [s for s in _spans()
                if s.scope == "restart" and s.name == "RegionRestart"]
    assert restarts
    assert restarts[0].attributes["job"] == "trace-regions"
    assert restarts[0].attributes["tasks"] >= 1
    dumps = [d for d in FLIGHT_RECORDER.dumps
             if d["reason"] == "region-restart"]
    assert dumps, "no automatic flight dump on region restart"
    assert dumps[0]["path"].startswith(str(tmp_path))
    with open(dumps[0]["path"]) as f:
        payload = json.load(f)
    assert payload["reason"] == "region-restart"
    assert payload["entries"], "dump preserved no pre-failure entries"


# -- stall: dump file tail contains the stall span + REST reachability -------

@pytest.mark.stall
def test_stall_dump_tail_contains_stall_span_and_rest_serves_it(tmp_path):
    """Acceptance: an injected device.execute hang (!hang@MS) produces a
    flight-recorder dump whose TAIL contains the stall site's span, and
    the dump record is reachable via GET /jobs/<name>/flight-recorder."""
    from flink_tpu.cluster.rest import RestEndpoint

    FLIGHT_RECORDER.dump_dir = str(tmp_path)
    faults_mod.FAULTS.configure_spec("device.execute=once@1!hang@200")
    with pytest.raises(StallError):
        WATCHDOG.run("device.execute",
                     lambda: faults_mod.FAULTS.fire("device.execute"),
                     deadline=0.02, scope="unit")
    dumps = [d for d in FLIGHT_RECORDER.dumps if d["reason"] == "stall"]
    assert dumps, "stall produced no flight dump"
    path = dumps[0]["path"]
    assert os.path.isfile(path)
    with open(path) as f:
        payload = json.load(f)
    tail = payload["entries"][-3:]
    stall_spans = [e for e in tail if e.get("type") == "span"
                   and e.get("scope") == "watchdog"
                   and e.get("name") == "Stall"]
    assert stall_spans, f"dump tail holds no Stall span: {tail}"
    assert stall_spans[-1]["attributes"]["site"] == "device.execute"

    endpoint = RestEndpoint(port=0)
    endpoint.register_job("stalljob", SimpleNamespace(failure_history=[]))
    port = endpoint.start()
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/jobs/stalljob/flight-recorder",
                timeout=5) as r:
            body = json.loads(r.read().decode())
        assert body["name"] == "stalljob"
        assert any(d["reason"] == "stall" for d in body["dumps"])
        assert any(e.get("name") == "Stall" for e in body["recent"])
    finally:
        endpoint.stop()


def test_dump_rate_limit_and_ring_bound():
    FLIGHT_RECORDER.min_dump_interval_s = 10.0
    FLIGHT_RECORDER.set_capacity(4)
    try:
        for i in range(10):
            FLIGHT_RECORDER.record_event("tick", i=i)
        assert len(FLIGHT_RECORDER.snapshot()) == 4
        from flink_tpu.metrics.tracing import dump_flight_recorder
        first = dump_flight_recorder("unit-reason")
        second = dump_flight_recorder("unit-reason")
        assert first is not None and second is None  # rate-limited
        assert len([d for d in FLIGHT_RECORDER.dumps
                    if d["reason"] == "unit-reason"]) == 1
    finally:
        FLIGHT_RECORDER.set_capacity(512)


# -- Perfetto (Chrome trace-event) export ------------------------------------

def _valid_trace_event_json(doc: dict) -> None:
    assert doc["displayTimeUnit"] == "ms"
    events = doc["traceEvents"]
    assert isinstance(events, list) and events
    cats = set()
    for ev in events:
        assert ev["ph"] in ("X", "M", "C"), ev
        if ev["ph"] == "M":
            assert ev["name"] == "thread_name"
            assert isinstance(ev["args"]["name"], str)
            continue
        if ev["ph"] == "C":
            # device-time ledger counter tracks (one per dispatch site)
            assert ev["name"].startswith("dispatch_ms:")
            assert ev["cat"] == "profiler"
            assert isinstance(ev["ts"], int) and ev["ts"] > 0
            assert isinstance(ev["args"]["ms"], (int, float))
            assert ev["args"]["ms"] >= 0.0
            continue
        cats.add(ev["cat"])
        assert isinstance(ev["name"], str) and ev["name"]
        assert isinstance(ev["ts"], int) and ev["ts"] > 0
        assert isinstance(ev["dur"], int) and ev["dur"] >= 0
        assert isinstance(ev["pid"], int) and isinstance(ev["tid"], int)
        assert ev["args"]["trace_id"] and ev["args"]["span_id"]
        for v in ev["args"].values():  # JSON-primitive args only
            assert isinstance(v, (int, float, bool, str))
    meta_names = {ev["args"]["name"] for ev in events if ev["ph"] == "M"}
    assert meta_names == cats  # one named track per scope


def test_chrome_trace_export_schema():
    mem = InMemoryTraceReporter()
    t = Tracer([mem])
    with t.span("checkpoint", "Checkpoint") as root:
        root.set_attribute("checkpointId", 1)
        t.span("device", "Execute").set_attribute(
            "obj", object()).finish()   # non-primitive attr → str()
    doc = json.loads(json.dumps(chrome_trace_events(mem.snapshot())))
    _valid_trace_event_json(doc)
    execute = [e for e in doc["traceEvents"]
               if e.get("ph") == "X" and e["name"] == "Execute"]
    root_ev = [e for e in doc["traceEvents"]
               if e.get("ph") == "X" and e["name"] == "Checkpoint"]
    assert execute[0]["args"]["parent_id"] == root_ev[0]["args"]["span_id"]
    assert execute[0]["args"]["trace_id"] == root_ev[0]["args"]["trace_id"]


# -- bench --trace: Perfetto file with checkpoint/device/mailbox spans -------

def test_bench_trace_writes_perfetto_file_with_consistent_trees(
        tmp_path, monkeypatch):
    """Acceptance: the tiny Q5 bench under --trace emits Perfetto-
    loadable trace-event JSON holding checkpoint, device-step, and
    mailbox spans, and the checkpoint spans form consistent trees."""
    import sys
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    import bench

    stages = bench.run_tiny_q5(
        n_keys=500, batch=1 << 11, n_batches=8,
        extra_config={"execution.checkpointing.interval": 0.05})
    assert stages["events_per_sec"] > 0
    spans = _spans()
    scopes = {s.scope for s in spans}
    assert {"checkpoint", "device", "task"} <= scopes, scopes
    roots = {s.span_id: s for s in spans if s.name == "Checkpoint"}
    assert roots, "no checkpoint completed under --trace interval"
    # a checkpoint whose completion fan-out ran has a full tree; anchor
    # there (a final in-flight checkpoint at job end legally has no root)
    done_roots = [roots[s.parent_id] for s in spans
                  if s.name == "Notify" and s.parent_id in roots]
    assert done_roots
    root = done_roots[0]
    snaps = [s for s in spans
             if s.name == "Snapshot" and s.trace_id == root.trace_id]
    assert snaps, "no task-side spans joined the completed tree"
    assert all(s.parent_id == root.span_id for s in snaps)
    # the writer path bench --trace uses, on the same retained spans
    monkeypatch.setattr(bench, "TRACE_PREFIX",
                        str(tmp_path / "bench"), raising=True)
    path = bench.write_trace("tiny_q5")
    assert path == str(tmp_path / "bench") + ".tiny_q5.trace.json"
    with open(path) as f:
        doc = json.load(f)
    _valid_trace_event_json(doc)
    cats = {e["cat"] for e in doc["traceEvents"] if e.get("ph") == "X"}
    assert {"checkpoint", "device", "task"} <= cats


# -- REST + CLI surfaces -----------------------------------------------------

def test_rest_traces_endpoint_and_cli_trace_dump(tmp_path, capsys):
    from flink_tpu.cli import main
    from flink_tpu.cluster.rest import RestEndpoint

    with TRACER.span("unit", "RestSpan") as sb:
        sb.set_attribute("n", 1)
    endpoint = RestEndpoint(port=0)
    endpoint.register_job("tjob", SimpleNamespace(failure_history=[]))
    port = endpoint.start()
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/jobs/tjob/traces",
                timeout=5) as r:
            body = json.loads(r.read().decode())
        assert body["name"] == "tjob"
        names = [s["name"] for s in body["spans"]]
        assert "RestSpan" in names
        assert all({"trace_id", "span_id", "start_ms", "end_ms"}
                   <= set(s) for s in body["spans"])
        # 404 for unknown jobs
        with pytest.raises(urllib.error.HTTPError) as exc:
            urllib.request.urlopen(
                f"http://127.0.0.1:{port}/jobs/nope/traces", timeout=5)
        assert exc.value.code == 404

        # CLI against the live endpoint: fetch + export trace-event JSON
        out = tmp_path / "remote.trace.json"
        rc = main(["trace-dump", "--target", f"127.0.0.1:{port}",
                   "--job", "tjob", "-o", str(out)])
        assert rc == 0
        with open(out) as f:
            _valid_trace_event_json(json.load(f))
    finally:
        endpoint.stop()
    # CLI against the in-process tracer: table mode
    rc = main(["trace-dump"])
    assert rc == 0
    assert "RestSpan" in capsys.readouterr().out


# -- doc-code consistency ----------------------------------------------------
# (span-inventory doc-lock moved onto the tpu-lint framework: rule TPU301
# in flink_tpu/analysis/inventory.py, exercised by tests/test_analysis.py)
