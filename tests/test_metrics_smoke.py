"""CI smoke (tier-1 safe: CPU, not slow): start a PrometheusReporter,
drive a tiny Q5-shaped pipeline through env.execute(), and assert the
HTTP scrape carries nonzero compile-count, transfer-bytes, and busy-time
series — the observability layer's end-to-end contract."""

import os
import sys
import urllib.request

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from flink_tpu.metrics import MetricRegistry, PrometheusReporter  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))  # bench.py lives at the repo root


def _scrape(port: int) -> dict:
    with urllib.request.urlopen(f"http://127.0.0.1:{port}/metrics",
                                timeout=10) as r:
        text = r.read().decode()
    out = {}
    for ln in text.splitlines():
        if not ln or ln.startswith("#"):
            continue
        name, _, val = ln.rpartition(" ")
        out[name] = float(val)
    return out


def test_prometheus_scrape_of_tiny_q5(host_born_upload):
    import bench

    # the tiny Q5 is device-born and uploads nothing: the cumulative
    # h2d series is this test's own host-born job's doing
    uploaded = host_born_upload()
    reg = MetricRegistry()
    rep = PrometheusReporter(port=0)
    rep.open(reg)
    try:
        bench.run_tiny_q5(n_keys=500, batch=1 << 11, n_batches=6,
                          metrics_registry=reg)
        vals = _scrape(rep.port)
    finally:
        rep.close()

    # compile accounting: the device programs compiled at least once and
    # repeated identical-shape batches hit the cache
    assert vals.get("flink_tpu_device_compiles", 0) > 0
    assert vals.get("flink_tpu_device_compile_cache_hits", 0) > 0
    # transfer accounting: host->device ingest and device->host fires
    assert vals.get("flink_tpu_device_h2d_bytes", 0) >= uploaded > 0
    assert vals.get("flink_tpu_device_d2h_bytes", 0) > 0
    # per-subtask mailbox busy time: at least one task reported progress
    busy = [v for k, v in vals.items()
            if k.endswith("busyTimeMsPerSecond")]
    assert busy and max(busy) > 0
    # records flowed through the instrumented task metrics
    recs = [v for k, v in vals.items() if k.endswith("numRecordsIn")]
    assert recs and max(recs) >= np.int64(1)
