"""Compile the device programs the chip once refused FOR a v5e, on the CPU.

libtpu can describe a TPU topology and compile against it with no chip
attached (``jax.experimental.topologies``), so a lowering the TPU compiler
or Mosaic rejects fails here, not twenty minutes into a chip run. Nothing
is executed: this proves "compiles under x64 for `TPU v5 lite`", not
results (tests/test_pallas_topk.py and tests/test_parallel.py check those
on the CPU, chip_smoke.py on the chip). Skipped where libtpu is absent.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, \
    SingleDeviceSharding

from flink_tpu.ops.hash_table import ensure_x64

ensure_x64()   # the regime every job runs in


@pytest.fixture(scope="module")
def v5e_devices():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no libtpu: nothing to test
        pytest.skip(f"no TPU topology without a chip here: {e}")
    assert topo.devices[0].device_kind == "TPU v5 lite"
    return topo.devices


def test_pallas_topk_compiles_under_x64(v5e_devices):
    from flink_tpu.ops.pallas_topk import _topk_pallas

    one = SingleDeviceSharding(v5e_devices[0])
    n = 1 << 18
    jax.jit(lambda v, m: _topk_pallas(v, m, 1000, 4, False)).lower(
        jax.ShapeDtypeStruct((n,), jnp.int64, sharding=one),
        jax.ShapeDtypeStruct((n,), jnp.bool_, sharding=one)).compile()


@pytest.mark.parametrize("n_dev", [1, 4])
def test_mesh_step_compiles(v5e_devices, n_dev):
    from flink_tpu.parallel.sharded_window import AggDef, ShardedWindowAgg, \
        ShardedWindowState

    mesh = Mesh(np.array(v5e_devices[:n_dev]), ("data",))
    agg = ShardedWindowAgg(
        mesh, [AggDef("bids", "count", jnp.int64),
               AggDef("revenue", "sum", jnp.int64)],
        capacity=1 << 10, ring=16, max_parallelism=128)
    sharded = NamedSharding(mesh, P("data"))

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharded)

    D, B, cap, ring = n_dev, 256, agg.capacity, agg.ring
    state = ShardedWindowState(
        spec((D, cap), jnp.int64),
        {a.name: spec((D, ring, cap), jnp.int64) for a in agg.aggs},
        spec((D,), jnp.int64))
    jax.jit(agg.step).lower(
        state, spec((D, B), jnp.int64), {"revenue": spec((D, B), jnp.int64)},
        spec((D, B), jnp.int64), spec((D, B), jnp.bool_)).compile()


#: the path by which the benchmark's probe_rounds_p50 finds the probe
#: loop's claim in a device trace (benchmarks/layer_metrics/
#: probe_rounds_p50.json), and the program it counts them under
_CLAIM_PATH = r'op_name="[^"]*/while/body/(probe\.claim/)?scatter-min"'
_PROBE_MODULE = "HloModule jit_lookup_or_insert"


def _assert_probe_is_countable(hlo: str) -> None:
    import re

    assert _PROBE_MODULE in hlo
    assert re.search(_CLAIM_PATH, hlo), "no scatter-min directly in a " \
        "while body: probe_rounds_p50 would read 0"


def test_hash_probe_compiles_at_the_benchmark_shape(v5e_devices):
    """[2^24] slots x [2^18] rows, with the counters: the first window, the
    compaction (a sort), both narrow loops and the wide one under one
    `lax.switch`, for the v5e's compiler."""
    from flink_tpu.ops.hash_table import lookup_or_insert

    one = SingleDeviceSharding(v5e_devices[0])
    compiled = lookup_or_insert.lower(
        jax.ShapeDtypeStruct((1 << 24,), jnp.int64, sharding=one),
        jax.ShapeDtypeStruct((1 << 18,), jnp.int64, sharding=one),
        stats=True).compile()
    _assert_probe_is_countable(compiled.as_text())


@pytest.mark.parametrize("rows", [64, 1 << 12])
def test_hash_probe_claim_stays_countable_on_any_backend(rows):
    """Small batches (the plain loop) and compacting ones alike keep the
    claim's scatter-min directly in a while body, under the module name the
    trace readers anchor on."""
    from flink_tpu.ops.hash_table import lookup_or_insert, make_table

    compiled = lookup_or_insert.lower(
        make_table(1 << 14), jnp.zeros(rows, jnp.int64)).compile()
    _assert_probe_is_countable(compiled.as_text())
