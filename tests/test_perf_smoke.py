"""Tier-1-safe performance contract smoke tests for the window fire: the
timed tiny-Q5 run is recompile-free, the fire's program cache is window-
width independent (one builder entry serves every W), and every fire of
a stream at one width, partial windows included, runs ONE executable.

Wall-clock ratios are NOT asserted here — they are hardware- and
load-dependent; `python -m benchmarks.run` measures on the chip (PERF.md).
These tests pin the structural facts the fire's cost rests on instead."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from flink_tpu.core.records import Schema  # noqa: E402
from flink_tpu.runtime import OneInputOperatorTestHarness  # noqa: E402
from flink_tpu.runtime.operators.device_window import (  # noqa: E402
    AggSpec, DeviceWindowAggOperator, _fire_program,
)
from flink_tpu.window import SlidingEventTimeWindows  # noqa: E402

pytestmark = pytest.mark.perf

SCHEMA = Schema([("k", np.int64), ("v", np.int64)])
AGGS = (("sum", "sum_v"), ("min", "min_v"))


def _drive(window_panes: int, steps: int = 24):
    op = DeviceWindowAggOperator(
        SlidingEventTimeWindows.of(window_panes * 1000, 1000), "k",
        [AggSpec("sum", "v", dtype=jnp.int64),
         AggSpec("min", "v", dtype=jnp.int64)],
        capacity=128, ring_size=2 * window_panes + 6)
    h = OneInputOperatorTestHarness(op, schema=SCHEMA)
    rng = np.random.default_rng(11)
    t = 0
    for _ in range(steps):
        n = int(rng.integers(4, 16))
        h.process_elements(
            list(zip(rng.integers(0, 7, n), rng.integers(0, 99, n))),
            list(rng.integers(max(0, t - 400), t + 800, n)))
        t += 1000
        h.process_watermark(t)
    h.process_watermark(t + window_panes * 2000)
    rows = len(h.get_output())
    h.close()
    return rows


def test_tiny_q5_recompile_free():
    """The acceptance invariant from ISSUE 8: after the warmup pass the
    timed tiny-Q5 run compiles NOTHING — step and fire dispatches all
    hit the program caches."""
    import bench

    report = bench.run_tiny_q5(n_keys=500, batch=1 << 11, n_batches=6)
    assert report["recompiles"] == 0
    assert report["emitted_rows"] > 0


def test_program_cache_width_independent():
    """Widening the window must NOT mint new fire builders: the program
    key carries the aggregate signature and the top-k, never W, so the
    builder cache's footprint is O(signatures)."""
    assert _drive(5) > 0
    fire0 = _fire_program.cache_info().currsize
    for w in (8, 12):
        assert _drive(w) > 0
    assert _fire_program.cache_info().currsize == fire0


def test_every_fire_of_a_width_runs_one_executable():
    """A fire's pane rows are padded to [W] under a validity mask, so the
    stream's first windows (fewer than W panes of data), its full ones
    and its tail's all dispatch the executable the first fire compiled:
    one entry a width in the jit's own cache, none a fire."""
    # the jitted fire itself, as a job that reads no count keys it (its
    # hidden plane is a presence plane: PR 49)
    fire_fn = _fire_program(AGGS, None, 64, "presence")._fn
    _drive(6)
    one_width = fire_fn._cache_size()
    _drive(6, steps=40)
    assert fire_fn._cache_size() == one_width
    _drive(7)
    assert fire_fn._cache_size() == one_width + 1
