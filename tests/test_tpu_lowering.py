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
