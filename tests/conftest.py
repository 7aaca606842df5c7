"""Test config: force an 8-device virtual CPU platform so multi-chip sharding
paths run without TPU hardware (the MiniCluster-analog of the reference's
single-JVM multi-TaskExecutor testing, SURVEY.md §4 tier 3). The environment
variables alone select the platform; they must be set before jax is first
imported."""

import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _clean_net_events():
    """The transport-plane event log and the watchdog's stall events
    (both merged into REST /exceptions) are process-global; clear them
    per test so one test's reconnect/sever/stall events don't surface in
    another's exception-history assertions (test_failover leaves an
    unattributed coordinator stall behind, which test_webui then read
    whenever it ran next on the same worker)."""
    from flink_tpu.cluster.transport import NET_EVENTS
    from flink_tpu.runtime.watchdog import WATCHDOG
    NET_EVENTS.clear()
    del WATCHDOG.events[:]
    yield


@pytest.fixture(autouse=True)
def _stall_wall_clock_guard(request):
    """Hard per-test wall-clock guard for `stall`-, `netfault`-,
    `isolation`- and `failover`-marked tests: the stall watchdog's (or
    the reconnect, admission-gate, or leader-election path's) own
    regressions must FAIL the suite, not hang it. SIGALRM fires in the
    main thread and unwinds whatever wait the test is blocked in (hang
    injections use <=50ms delays and reconnect/lease deadlines are a
    few seconds, so 120s means a real supervision bug, not a slow
    box)."""
    if (request.node.get_closest_marker("stall") is None
            and request.node.get_closest_marker("netfault") is None
            and request.node.get_closest_marker("isolation") is None
            and request.node.get_closest_marker("failover") is None
            and request.node.get_closest_marker("aot") is None):
        yield
        return
    import signal

    def _expired(signum, frame):
        raise TimeoutError(
            "stall/netfault test exceeded its 120s wall-clock guard — "
            "a hang went unbounded by supervision or reconnect deadlines")

    old = signal.signal(signal.SIGALRM, _expired)
    signal.alarm(120)
    try:
        yield
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, old)


@pytest.fixture
def eight_device_mesh():
    from jax.sharding import Mesh
    import numpy as np
    devs = np.array(jax.devices("cpu")[:8])
    with Mesh(devs, ("data",)) as m:
        yield m


@pytest.fixture
def host_born_upload():
    """Runs a tiny HOST-born windowed job (its batches are numpy and the
    operator uploads them) and returns the ``h2d_bytes`` it added to the
    process's cumulative DEVICE_STATS: a test that reads that series has
    made its own upload, whichever tests ran before it in the process."""
    import numpy as np

    from flink_tpu.api import StreamExecutionEnvironment
    from flink_tpu.core import WatermarkStrategy
    from flink_tpu.core.config import PipelineOptions
    from flink_tpu.core.records import Schema
    from flink_tpu.metrics import DEVICE_STATS
    from flink_tpu.runtime.operators.device_window import AggSpec
    from flink_tpu.window import TumblingEventTimeWindows

    def run() -> int:
        before = DEVICE_STATS.snapshot()["h2d_bytes"]
        env = StreamExecutionEnvironment.get_execution_environment()
        env.set_state_backend("tpu")
        env.config.set(PipelineOptions.BATCH_SIZE, 256)
        ws = WatermarkStrategy.for_monotonous_timestamps() \
            .with_timestamp_column("ts")
        rows = (env.datagen(lambda i: {"k": i % 7, "ts": i * 4},
                            Schema([("k", np.int64), ("ts", np.int64)]),
                            count=1024, timestamp_column="ts",
                            watermark_strategy=ws)
                .key_by("k")
                .window(TumblingEventTimeWindows.of(2000))
                .device_aggregate([AggSpec("count", out_name="n")],
                                  capacity=1 << 8, ring_size=4,
                                  defer_overflow=True)
                .execute_and_collect("host-born-upload"))
        assert rows
        return DEVICE_STATS.snapshot()["h2d_bytes"] - before

    return run
