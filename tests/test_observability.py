"""Observability: prometheus reporter, spans, REST endpoint, CLI
(reference test models: PrometheusReporterTest, rest handler ITCases),
plus the device-path layer: compile/transfer accounting, mailbox
busy/idle/backpressure gauges, and bench-report <-> prometheus agreement."""

import json
import os
import sys
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from flink_tpu.api.environment import StreamExecutionEnvironment
from flink_tpu.core.config import CheckpointingOptions, PipelineOptions
from flink_tpu.core.records import Schema
from flink_tpu.metrics.core import MetricRegistry
from flink_tpu.metrics.reporters import (
    LoggingReporter, PrometheusReporter, prometheus_text,
)
from flink_tpu.metrics.tracing import InMemoryTraceReporter, Tracer

SCHEMA = Schema([("k", np.int64), ("v", np.int64)])

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))  # bench.py lives at the repo root


def _get(url):
    with urllib.request.urlopen(url, timeout=5) as r:
        return r.status, r.read().decode()


def _parse_prom(text: str) -> dict:
    """name (incl. {labels}) -> float for every sample line."""
    out = {}
    for ln in text.splitlines():
        if not ln or ln.startswith("#"):
            continue
        name, _, val = ln.rpartition(" ")
        out[name] = float(val)  # NaN/+Inf/-Inf parse fine
    return out


def test_prometheus_text_rendering():
    reg = MetricRegistry()
    g = reg.root().group("job").group("task")
    g.counter("numRecordsIn").inc(42)
    g.gauge("lag", lambda: 7.5)
    g.histogram("latency").update(10)
    text = prometheus_text(reg)
    assert "flink_tpu_job_task_numRecordsIn 42" in text
    assert "flink_tpu_job_task_lag 7.5" in text
    assert 'quantile="0.99"' in text
    assert "# TYPE flink_tpu_job_task_numRecordsIn counter" in text


def test_prometheus_reporter_serves_http():
    reg = MetricRegistry()
    reg.root().group("up").counter("c").inc(3)
    rep = PrometheusReporter(port=0)
    rep.open(reg)
    try:
        status, body = _get(f"http://127.0.0.1:{rep.port}/metrics")
        assert status == 200
        assert "flink_tpu_up_c 3" in body
        status, _ = _get(f"http://127.0.0.1:{rep.port}/metrics")
        assert status == 200
    finally:
        rep.close()


def test_logging_reporter():
    reg = MetricRegistry()
    reg.root().counter("x").inc(1)
    lines = []
    rep = LoggingReporter(interval_s=0.02, sink=lines.append)
    rep.open(reg)
    time.sleep(0.1)
    rep.close()
    assert any("x=1" in ln for ln in lines)


def test_tracer_spans():
    mem = InMemoryTraceReporter()
    tracer = Tracer([mem])
    with tracer.span("test", "Work") as sb:
        sb.set_attribute("n", 5)
        time.sleep(0.01)
    spans = mem.by_name("Work")
    assert len(spans) == 1
    assert spans[0].duration_ms >= 10
    assert spans[0].attributes["n"] == 5
    assert spans[0].attributes["error"] is False


def test_checkpoint_spans_emitted():
    from flink_tpu.checkpoint.coordinator import CheckpointCoordinator
    env = StreamExecutionEnvironment()
    env.set_parallelism(2)
    env.config.set(PipelineOptions.BATCH_SIZE, 8)
    n = 2000
    rows = [(i % 3, i) for i in range(n)]
    ds = env.from_collection(rows, SCHEMA, timestamps=list(range(n)))
    from flink_tpu.connectors.core import CollectSink
    ds.key_by("k").sum(1).add_sink(CollectSink(), "s")
    job = env.execute_async("spans")
    mem = InMemoryTraceReporter()
    coord = CheckpointCoordinator(job, env.config, tracer=Tracer([mem]))
    for _ in range(50):
        try:
            coord.trigger_savepoint(timeout=2)
            break
        except Exception:
            time.sleep(0.02)
    job.wait(30)
    spans = mem.by_name("Checkpoint")
    assert spans and spans[0].attributes["savepoint"] is True


def test_rest_endpoint():
    from flink_tpu.checkpoint.coordinator import CheckpointCoordinator
    from flink_tpu.cluster.rest import RestEndpoint
    from flink_tpu.metrics.core import MetricRegistry

    reg = MetricRegistry()
    env = StreamExecutionEnvironment()
    env.set_parallelism(2)
    env.config.set(PipelineOptions.BATCH_SIZE, 4)
    n = 4000
    rows = [(i % 3, i) for i in range(n)]
    ds = env.from_collection(rows, SCHEMA, timestamps=list(range(n)))
    from flink_tpu.connectors.core import CollectSink
    ds.key_by("k").sum(1).add_sink(CollectSink(), "s")
    job = env.execute_async("rest-job", metrics_registry=reg)
    coord = CheckpointCoordinator(job, env.config)
    endpoint = RestEndpoint(port=0, metrics_registry=reg)
    endpoint.register_job("rest-job", job, coord)
    port = endpoint.start()
    base = f"http://127.0.0.1:{port}"
    try:
        status, body = _get(f"{base}/jobs")
        jobs = json.loads(body)
        assert status == 200 and jobs[0]["name"] == "rest-job"
        assert jobs[0]["state"] in ("RUNNING", "FINISHED")

        status, body = _get(f"{base}/jobs/rest-job")
        detail = json.loads(body)
        assert status == 200
        assert any("KeyedSum" in v["name"] or "Sum" in v["name"]
                   or v["subtasks"] for v in detail["vertices"])

        # trigger a savepoint over REST while the job runs
        req = urllib.request.Request(f"{base}/jobs/rest-job/savepoints",
                                     method="POST")
        with urllib.request.urlopen(req, timeout=30) as r:
            sp = json.loads(r.read().decode())
        assert "id" in sp

        status, body = _get(f"{base}/jobs/rest-job/checkpoints")
        cps = json.loads(body)
        assert any(c["savepoint"] for c in cps)

        status, body = _get(f"{base}/metrics")
        assert status == 200 and "flink_tpu" in body

        # unknown job: narrow 404 probe (must not swallow earlier failures)
        with pytest.raises(urllib.error.HTTPError) as exc:
            _get(f"{base}/jobs/nope")
        assert exc.value.code == 404
    finally:
        endpoint.stop()
        job.wait(60)


def test_metrics_package_reexports():
    """Satellite: the package __init__ re-exports the public API."""
    from flink_tpu.metrics import (  # noqa: F401
        DEVICE_STATS, Counter, Gauge, Histogram, LoggingReporter, Meter,
        MetricGroup, MetricRegistry, PrometheusReporter, Span, TaskMetrics,
        Tracer, bind_device_metrics, instrumented_program_cache,
        prometheus_text, register_reporter, reporters_from_config,
    )
    assert callable(prometheus_text)
    assert Counter().count == 0


def test_counter_meter_thread_safe():
    """Reporter thread polls while the mailbox loop mutates: concurrent
    inc/mark must be lossless (``_value += n`` alone is not atomic)."""
    from flink_tpu.metrics import Counter, Histogram, Meter

    c, m, h = Counter(), Meter(), Histogram(window=256)
    N, T = 20_000, 8

    def work():
        for i in range(N):
            c.inc()
            m.mark()
            h.update(i)
            if i % 64 == 0:
                _ = m.rate, h.quantile(0.5), h.mean  # reader interleave

    threads = [threading.Thread(target=work) for _ in range(T)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.count == N * T
    assert m.count == N * T


def test_prometheus_text_hardening():
    """Non-numeric gauges render NaN (never raise mid-scrape), a raising
    gauge is skipped, and histogram summaries are valid exposition format
    (quantile samples + _sum + _count)."""
    reg = MetricRegistry()
    g = reg.root().group("h")
    g.gauge("bad_str", lambda: "not-a-number")
    g.gauge("none", lambda: None)
    g.gauge("nanval", lambda: float("nan"))
    g.gauge("infval", lambda: float("inf"))
    g.gauge("raises", lambda: 1 / 0)
    h = g.histogram("lat")
    h.update(5.0)
    h.update(7.0)
    text = prometheus_text(reg)
    assert "flink_tpu_h_bad_str NaN" in text
    assert "flink_tpu_h_none NaN" in text
    assert "flink_tpu_h_nanval NaN" in text
    assert "flink_tpu_h_infval +Inf" in text
    assert "raises" not in text
    assert 'flink_tpu_h_lat{quantile="0.5"} ' in text
    assert "flink_tpu_h_lat_sum 12.0" in text
    assert "flink_tpu_h_lat_count 2" in text
    # every sample line must be "<name or name{labels}> <float>"
    for ln in text.strip().splitlines():
        if ln.startswith("#"):
            continue
        name, _, val = ln.rpartition(" ")
        assert name
        float(val)  # NaN/+Inf parse; anything else would raise


def test_compile_cache_accounting():
    """instrumented_program_cache: a miss counts one compile, a hit one
    cache hit, and the first dispatch records compile duration."""
    from flink_tpu.metrics import DEVICE_STATS, instrumented_program_cache

    calls = []

    @instrumented_program_cache("test.scope", maxsize=4)
    def builder(x: int):
        calls.append(x)
        return lambda v: v + x

    before = DEVICE_STATS.snapshot()
    assert builder(1)(10) == 11
    assert builder(1)(20) == 21
    assert builder(2)(10) == 12
    after = DEVICE_STATS.snapshot()
    assert calls == [1, 2]
    assert after["compiles"] - before["compiles"] == 2
    assert after["compile_cache_hits"] - before["compile_cache_hits"] == 1
    assert after.get("compiles.test.scope", 0) == 2


def test_compile_spans_via_tracer():
    from flink_tpu.metrics import (
        InMemoryTraceReporter, Tracer, instrumented_program_cache,
        set_compile_tracer,
    )

    mem = InMemoryTraceReporter()
    set_compile_tracer(Tracer([mem]))
    try:
        @instrumented_program_cache("test.span_scope", maxsize=2)
        def builder(x: int):
            return lambda v: v * x

        builder(3)(2)
        spans = [s for s in mem.by_name("Compile")
                 if s.attributes.get("scope") == "test.span_scope"]
        assert len(spans) == 1
    finally:
        set_compile_tracer(None)


def test_tiny_q5_report_agrees_with_prometheus(host_born_upload):
    """Acceptance: the bench stage report embeds compiles /
    compile_cache_hits / h2d_bytes / d2h_bytes / busy_time_ratio, with no
    recompiles in the timed run, and prometheus_text exposes the same
    cumulative series."""
    import bench

    # the tiny Q5 is device-born and uploads nothing: the cumulative
    # h2d series is this test's own host-born job's doing
    uploaded = host_born_upload()
    reg = MetricRegistry()
    stages = bench.run_tiny_q5(n_keys=500, batch=1 << 11, n_batches=6,
                               metrics_registry=reg)
    for k in ("compiles", "compile_cache_hits", "h2d_bytes", "d2h_bytes",
              "busy_time_ratio"):
        assert k in stages, k
    assert stages["compiles"] > 0
    assert stages["compile_cache_hits"] > 0
    assert stages["h2d_bytes"] >= uploaded > 0
    assert stages["d2h_bytes"] > 0
    assert stages["recompiles"] == 0  # identical shapes after warmup
    assert 0.0 < stages["busy_time_ratio"] <= 1.0
    vals = _parse_prom(prometheus_text(reg))
    assert vals["flink_tpu_device_compiles"] == stages["compiles"]
    assert (vals["flink_tpu_device_compile_cache_hits"]
            == stages["compile_cache_hits"])
    assert vals["flink_tpu_device_h2d_bytes"] == stages["h2d_bytes"]
    assert vals["flink_tpu_device_d2h_bytes"] == stages["d2h_bytes"]
    # the aggregate busy ratio lies within the per-task gauge envelope
    ratios = [v for k, v in vals.items() if k.endswith("busyTimeRatio")]
    assert ratios
    assert (min(ratios) - 1e-9 <= stages["busy_time_ratio"]
            <= max(ratios) + 1e-9)


def test_cli_savepoint_info_and_version(tmp_path, capsys):
    from flink_tpu.cli import main
    from flink_tpu.state_processor import SavepointWriter

    assert main(["version"]) == 0
    sp = (SavepointWriter(max_parallelism=128)
          .with_keyed_state("v1", "0:KeyedProcess", "cnt",
                            [(1, 10)], parallelism=1)
          .write(str(tmp_path)))
    assert main(["savepoint-info", sp.external_path]) == 0
    out = capsys.readouterr().out
    assert "v1" in out and "cnt" in out


def test_cli_run_with_savepoint(tmp_path):
    """CLI run: pre-configured default env + restore from savepoint."""
    from flink_tpu.cli import main

    script = tmp_path / "pipeline.py"
    script.write_text(
        "import numpy as np\n"
        "from flink_tpu.api.environment import StreamExecutionEnvironment\n"
        "from flink_tpu.core.records import Schema\n"
        "from flink_tpu.connectors.core import CollectSink\n"
        "env = StreamExecutionEnvironment.get_default()\n"
        "schema = Schema([('k', np.int64), ('v', np.int64)])\n"
        "rows = [(i % 2, i) for i in range(10)]\n"
        "ds = env.from_collection(rows, schema, "
        "timestamps=list(range(10)))\n"
        "sink = CollectSink()\n"
        "ds.key_by('k').sum(1).add_sink(sink, 's')\n"
        "env.execute('cli-job')\n"
        f"open(r'{tmp_path}/done', 'w').write(str(len(sink.rows)))\n")
    rc = main(["run", str(script), "--parallelism", "2"])
    assert rc == 0
    assert (tmp_path / "done").read_text() == "10"
    # the CLI configured the default env's parallelism
    assert StreamExecutionEnvironment.get_default().parallelism == 2
