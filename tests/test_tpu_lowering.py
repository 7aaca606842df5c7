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


def _q5_mesh(devices, capacity: int, batch: int):
    """Q5's sharded aggregate (COUNT + SUM, both int64, ring 16) on a mesh
    of described devices, and the step's arguments as shapes on it."""
    from flink_tpu.parallel.sharded_window import AggDef, ShardedWindowAgg, \
        ShardedWindowState

    mesh = Mesh(np.array(devices), ("data",))
    agg = ShardedWindowAgg(
        mesh, [AggDef("bids", "count", jnp.int64),
               AggDef("revenue", "sum", jnp.int64)],
        capacity=capacity, ring=16, max_parallelism=128)
    sharded = NamedSharding(mesh, P("data"))

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharded)

    D, B = len(devices), batch
    # an int64 ring plane is handed over as the state keeps it: its two
    # uint32 words, sharded as the plane is
    state = ShardedWindowState(
        spec((D, capacity), jnp.int64),
        {a.name: _plane_spec("halves:int64", (D, agg.ring, capacity),
                             sharded) for a in agg.aggs},
        spec((D,), jnp.int64))
    args = (state, spec((D, B), jnp.int64),
            {"revenue": spec((D, B), jnp.int64)}, spec((D, B), jnp.int64),
            spec((D, B), jnp.bool_))
    return agg, sharded, args


#: a device's shard of the benchmark's state (q5-16m-mesh4): a 2^23-slot
#: table, two [16, 2^23] int64 planes (each its two uint32 words: the
#: same bytes), the drop counter
_SHARD_BYTES = (1 << 23) * 8 * (1 + 2 * 16) + 8


#: the benchmark-shape programs compiled so far, by name: several tests
#: look at one program, and each compile takes a quarter of a minute
_COMPILED: dict = {}


def _compiled(name: str, build):
    if name not in _COMPILED:
        _COMPILED[name] = build()
    return _COMPILED[name]


def _mesh_step(devices):
    """(agg, the step of q5-16m-mesh4 as the operator dispatches it:
    [4, 65536] rows against [4, 16, 2^23] planes, donation included)."""
    agg, _sharded, args = _q5_mesh(devices[:4], 1 << 23, 1 << 16)
    return agg, _compiled("mesh.step", lambda: agg.step_program().lower(
        *args, agg._base_start, agg._base_len).compile())


@pytest.mark.parametrize("n_dev", [1, 4])
def test_mesh_step_compiles(v5e_devices, n_dev):
    agg, _sharded, args = _q5_mesh(v5e_devices[:n_dev], 1 << 10, 256)
    jax.jit(agg.step).lower(*args).compile()


def test_mesh_state_is_built_shard_by_shard_at_the_benchmark_shape(
        v5e_devices):
    """[4, 16, 2^23] int64 x 2 planes on a v5e 2x2 (q5-16m-mesh4): the
    initialiser writes each device's own 2.2 GB shard and nothing else.
    Tiled on one device and then cut, the same state wanted 4.5 GB with
    3.4 GB free and the job died building it (PR 24, chip call 8)."""
    agg, sharded, _args = _q5_mesh(v5e_devices[:4], 1 << 23, 1 << 16)
    compiled = agg.init_program().lower().compile()
    assert all(s == sharded
               for s in jax.tree.leaves(compiled.output_shardings))
    mem = compiled.memory_analysis()
    assert mem.output_size_in_bytes <= _SHARD_BYTES + 4096
    assert (mem.output_size_in_bytes + mem.temp_size_in_bytes
            + mem.argument_size_in_bytes) < 3e9


def test_mesh_step_donates_its_state_at_the_benchmark_shape(v5e_devices):
    """The step at [4, 65536] rows against [4, 16, 2^23] planes, as the
    operator dispatches it (donation included): the state is aliased into
    the outputs (no second 2.2 GB of state a step) and the program fits a
    16 GB chip with room."""
    _agg, compiled = _mesh_step(v5e_devices)
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= _SHARD_BYTES - 4096
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes) < 8e9


def test_mesh_step_compiles_without_a_plane_copy_at_the_benchmark_shape(
        v5e_devices):
    """The mesh step of q5-16m-mesh4 ([4, 65536] rows against two
    [4, 16, 2^23] int64 planes), lowered as the operator dispatches it,
    donation included: every aggregate folds through `ring_fold` on the
    shard's own tiled plane, so no `copy` holds a whole shard plane and
    the only loops that carry one are the exchange rounds' `while_loop`
    (the planes are its carry) and, inside it, the fold's one walk over
    the ring an aggregate. `plane.reshape(-1)` around the scatter was four
    more: relayout `while`s of `dynamic-update-slice` between `T(8,128)`
    and the flat `T(1024)`, out and back for each plane (100 of the
    step's 162 ms until PR 36)."""
    import re

    agg, compiled = _mesh_step(v5e_devices)
    hlo = compiled.as_text()
    assert "HloModule jit_step" in hlo        # the name the traces anchor on
    ring, cap = agg.ring, agg.capacity
    whole = re.compile(rf"\[(1,)?{ring},{cap}\]|\[{ring * cap}\]")
    rounds = 'op_name="jit(step)/shard_map/while"'
    ring_walk = 'op_name="jit(step)/shard_map/while/body/mesh.fold/while"'
    loops = []
    for line in hlo.splitlines():
        if re.search(r" (copy|while)\(", line) and whole.search(line):
            assert " while(" in line and f"[{ring * cap}]" not in line, \
                line[:300]
            loops.append(next((name for name in (rounds, ring_walk)
                               if name in line), line[:300]))
    assert sorted(loops) == sorted([rounds] + [ring_walk] * len(agg.aggs))
    for kind in ("sum", "count"):
        assert re.search(
            rf"shard_map/while/body/mesh\.fold/[^\"]*fold\.scatter/fold\.{kind}/",
            hlo), kind
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= _SHARD_BYTES - 4096
    # beside the shard's state: the table's 32-bit halves, the ring row
    # of a plane that is out being folded, the send buffers; no second
    # copy of a plane (an int64 plane PARAMETER was one: its halves)
    assert mem.temp_size_in_bytes < 32 * cap


def _mesh_fire(devices):
    """The whole ranked fire of q5-16m-mesh4 ([4, 2^23] int64 COUNT rank,
    k = 1000, five pane rows) on a described v5e 2x2."""
    def build():
        agg, sharded, args = _q5_mesh(devices[:4], 1 << 23, 1 << 16)
        rep = NamedSharding(sharded.mesh, P())
        return agg.fire_program("bids", 1000).lower(
            args[0], jax.ShapeDtypeStruct((5,), jnp.int32, sharding=rep),
            jax.ShapeDtypeStruct((5,), jnp.bool_, sharding=rep)).compile()

    return _compiled("mesh.fire", build)


def _operand_elements(hlo: str, op: str):
    """Element counts of the array operands-or-results named on every
    HLO line that applies ``op`` (``sort``, ``all-reduce``, ...)."""
    import re

    for line in hlo.splitlines():
        if re.search(rf" {op}(-start)?\(", line):
            head = line.split(f" {op}", 1)[0]
            for dims in re.findall(r"\[([\d,]+)\]", head):
                yield int(np.prod([int(d) for d in dims.split(",")]))


def test_mesh_fire_selects_on_the_shard_at_the_benchmark_shape(v5e_devices):
    """The whole mesh fire of q5-16m-mesh4 ([4, 2^23] int64 COUNT rank,
    k = 1000) for a described v5e 2x2: phase one of the top-k is the
    threshold select under shard_map, so the program holds no sort (and
    no top-k custom call) over a shard's 2^23 slots (`top_k.3` was one,
    138 of the fire's 161 ms: PERF.md section 5), and nothing wider than
    the D x k candidates crosses the interconnect."""
    import re

    D, k = 4, 1000
    hlo = _mesh_fire(v5e_devices).as_text()
    assert "HloModule jit_fire" in hlo        # the name the traces anchor on
    sorts = list(_operand_elements(hlo, "sort"))
    assert sorts and max(sorts) <= D * k, max(sorts)
    assert not re.search(r'custom_call_target="(TopK|ApproxTopK)', hlo)
    for op in ("all-reduce", "all-gather", "all-to-all",
               "collective-permute", "reduce-scatter"):
        assert max(_operand_elements(hlo, op), default=0) <= D * k, op
    # the walk is there: a compare-and-count loop over the shard's slots
    assert "shard_map" in hlo and re.search(r" while\(", hlo)


#: q7-10m-saturated's shapes: 2^24 slots, a ring of 8, batches of 2^18
_Q7_CAP, _Q7_RING, _Q7_BATCH = 1 << 24, 8, 1 << 18


def test_max_fold_compiles_at_the_benchmark_shape(v5e_devices):
    """The fold of kind `max` as `ring_fold` hands it to `scatter_fold`:
    `row.at[slots].max(vals)` over ONE int64 ring row of 2^24 slots (the
    plane is [8, 2^24]; a batch touches one or two of its rows): under
    x64 one scatter over the row's 32-bit halves, in the scope
    `fold.max` by which a trace finds it."""
    from flink_tpu.ops.segment_ops import scatter_fold

    one = SingleDeviceSharding(v5e_devices[0])
    n = _Q7_CAP
    compiled = jax.jit(
        lambda acc, idx, vals, valid: scatter_fold(
            "max", acc, idx, vals, valid)).lower(
        jax.ShapeDtypeStruct((n,), jnp.int64, sharding=one),
        jax.ShapeDtypeStruct((_Q7_BATCH,), jnp.int32, sharding=one),
        jax.ShapeDtypeStruct((_Q7_BATCH,), jnp.int64, sharding=one),
        jax.ShapeDtypeStruct((_Q7_BATCH,), jnp.bool_, sharding=one)
    ).compile()
    hlo = compiled.as_text()
    assert " scatter(" in hlo
    assert "fold.scatter/fold.max" in hlo
    assert compiled.memory_analysis().temp_size_in_bytes < 2 * n * 8


#: the ring planes of the two one-chip configurations as the backend
#: signs them (kind, dtype, shape), 2^24 slots each: Q5's int32 count
#: beside its int64 sum on a ring of 16, Q7's hidden plane beside its
#: int64 max on a ring of 8: the job reads no count, so since PR 49 that
#: plane is a 32-bit presence plane (an int64 count until then). The int64
#: ones are STORED as their two
#: 32-bit words since PR 42 (`ops/segment_ops.Halves`), and signed so
_FOLD_SIGS = {
    "q5": (("count", "int32", (16, 1 << 24)),
           ("sum", "halves:int64", (16, 1 << 24))),
    "q7": (("presence", "int32", (8, 1 << 24)),
           ("max", "halves:int64", (8, 1 << 24))),
}


def _plane_spec(dt: str, shape, sharding):
    """A plane of a backend signature as shapes: one array, or the two
    `uint32` words of a `halves:` plane."""
    from flink_tpu.ops.segment_ops import Halves

    def spec(dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    if dt.startswith("halves:"):
        return Halves(spec(jnp.uint32), spec(jnp.uint32),
                      np.dtype(dt.split(":")[1]))
    return spec(dt)


def _plane_bytes(sig) -> int:
    return sum(np.dtype(dt.split(":")[-1]).itemsize * int(np.prod(shape))
               for _k, dt, shape in sig)


def _host_born_fold(devices, query: str):
    """The backend's fold program of a one-chip cell at 2^24 slots and
    2^18 rows, every ring plane donated."""
    from flink_tpu.state.tpu_backend import _fold_program

    one = SingleDeviceSharding(devices[0])

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    sig, rows = _FOLD_SIGS[query], 1 << 18
    fold = _fold_program(sig)
    return _compiled(f"fold.{query}", lambda: getattr(fold, "_fn", fold).lower(
        tuple(_plane_spec(dt, shape, one) for _k, dt, shape in sig),
        spec((rows,), jnp.int32), spec((rows,), jnp.int64),
        spec((rows,), jnp.bool_),
        (None, spec((rows,), jnp.int64)),
        *_limb_count(sig, spec)).compile())


def _limb_count(sig, spec) -> tuple:
    """The backend's running count of limb scatters, which a fold program
    with an additive int64 plane (Q5's SUM) takes and hands back."""
    from flink_tpu.state.tpu_backend import _folds_by_limbs

    return (spec((1,), jnp.int64),) if _folds_by_limbs(sig) else ()


@pytest.mark.parametrize("query", list(_FOLD_SIGS))
def test_host_born_fold_compiles_without_a_plane_copy_at_the_benchmark_shape(
        v5e_devices, query):
    """The backend's fold program (one a batch, every ring plane of the
    job donated) at 2^24 slots and 2^18 rows: no `copy` of a whole plane
    and no loop over one but the fold's own walk over the ring rows. `plane.reshape(-1)` around the scatter was
    both: a `[ring, 2^24]` plane is tiled `T(8,128)` and its flat view
    `T(1024)`, so XLA copied all of it out and back in a `while` of
    `dynamic-update-slice`s (232 of the step's 279 ms until PR 34). What
    is left beside the planes is a ring row of each: the int64 planes
    come as their 32-bit words (PR 42), so no second copy of one is made
    at the program's entry (2.1 GB of temporaries at Q5's shapes until
    then)."""
    import re

    sig = _FOLD_SIGS[query]
    compiled = _host_born_fold(v5e_devices, query)
    hlo = compiled.as_text()
    assert "HloModule jit_fold" in hlo
    ring, cap = sig[0][2]
    # a whole plane, tiled or flat; the one loop that may carry it is the
    # fold's own walk over the ring, which updates the plane in place
    whole = re.compile(rf"\[(1,)?{ring},{cap}\]|\[{ring * cap}\]")
    ring_walks = 0
    for line in hlo.splitlines():
        if re.search(r" (copy|while)\(", line) and whole.search(line):
            assert " while(" in line and f"[{ring * cap}]" not in line \
                and f"[1,{ring},{cap}]" not in line \
                and 'op_name="jit(fold)/while"' in line, line[:300]
            ring_walks += 1
    assert ring_walks <= len(sig)
    assert "fold.scatter/fold.count" in hlo
    assert f"fold.scatter/fold.{sig[1][0]}" in hlo
    mem = compiled.memory_analysis()
    planes = _plane_bytes(sig)
    assert mem.alias_size_in_bytes >= planes     # every plane is donated
    # beside the planes: the ring row of each plane that is out being
    # folded, and nothing the size of a plane. An additive int64 plane
    # (Q5's SUM) folds limb by limb since PR 54: its row's two words, the
    # zeroed 32-bit row a limb is scattered into, and one copy of a word
    # the compiler keeps between two limbs' conditionals: four 32-bit
    # rows, where the parent's one 64-bit row was two
    row_bytes = 4 * 4 if sig[1][0] == "sum" else planes // (ring * cap)
    assert mem.temp_size_in_bytes < 1.01 * row_bytes * cap


def _reclaim(devices):
    """(program, plane signature, abstract arguments, executable) of the
    backend's reclaim at the in-flight cell's shapes: 2^23 slots under
    Q5's two ring planes."""
    from flink_tpu.state.tpu_backend import _reclaim_program

    one = SingleDeviceSharding(devices[0])

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    cap, ring = 1 << 23, 16
    sig = (("count", "int32", (ring, cap)),
           ("sum", "halves:int64", (ring, cap)))
    reclaim = _reclaim_program(sig)
    args = (spec((cap,), jnp.int64),
            tuple(_plane_spec(dt, shape, one) for _k, dt, shape in sig),
            spec((), jnp.int64))
    return reclaim, sig, args, _compiled(
        "reclaim",
        lambda: getattr(reclaim, "_fn", reclaim).lower(*args).compile())


def test_reclaim_compiles_in_place_at_the_benchmark_shape(v5e_devices):
    """The backend's reclaim (PR 35) at the in-flight cell's shapes, 2^23
    slots under Q5's two ring planes: ONE program `jit_reclaim` with the
    three scopes a trace finds its parts by, every plane donated and
    re-seated in place by a sort a ring row (no second copy of a plane:
    the int64 plane comes and goes as its 32-bit words, so beside the
    planes there are only per-slot vectors; no gather over the slots
    outside the probe), and nothing
    but the table, the planes, the dropped counter and two counts coming
    back."""
    import re

    reclaim, sig, args, compiled = _reclaim(v5e_devices)
    (ring, cap) = sig[0][2]
    hlo = compiled.as_text()
    assert "HloModule jit_reclaim" in hlo
    for scope in ("reclaim.live", "reclaim.rehome", "reclaim.remap"):
        assert f"jit(reclaim)/{scope}/" in hlo, scope
    # the live keys go through the probe the ingest step uses
    assert "reclaim.rehome/while/body/" in hlo and "probe.claim" in hlo
    # a ring row that holds nothing is skipped; one that does is sorted
    assert re.search(r"reclaim\.remap/while/body/.*cond/branch_1_fun/sort", hlo)
    assert len(re.findall(r" sort\(", hlo)) >= 5
    for line in hlo.splitlines():
        if " copy(" in line:
            assert not re.search(rf"\[{ring},{cap}\]", line), line[:300]
    table, planes_out, dropped, counts = jax.eval_shape(
        getattr(reclaim, "_fn", reclaim), *args)
    assert (table.shape, dropped.shape, counts.shape) == ((cap,), (), (2,))
    # every plane goes out in the layout it came in
    assert jax.tree.structure(planes_out) == jax.tree.structure(args[1])
    assert [(a.shape, a.dtype) for a in jax.tree.leaves(planes_out)] \
        == [(a.shape, a.dtype) for a in jax.tree.leaves(args[1])]
    mem = compiled.memory_analysis()
    planes = _plane_bytes(sig)
    assert mem.alias_size_in_bytes >= planes     # every plane is donated
    assert mem.temp_size_in_bytes < 64 * cap


def _mesh_reclaim(devices):
    """The reclaim of the sharded state at the four-chip in-flight cell's
    shapes (q5-inflight-mesh4: [4, 16, 2^23] int64 planes), as the
    operator prepares it (donation included)."""
    agg, _sharded, args = _q5_mesh(devices[:4], 1 << 23, 1 << 16)
    return _compiled("mesh.reclaim", lambda: agg.reclaim_program().lower(
        args[0]).compile())


def test_mesh_reclaim_compiles_in_place_at_the_benchmark_shape(v5e_devices):
    """ONE program `jit_reclaim` for a described v5e 2x2 in which every
    shard runs the backend's three steps over its own [16, 2^23] int64
    planes, kept as their words (PR 41, PR 44): the state donated and
    re-seated in place, no shard
    waiting for another (no collective at all), nothing but the state and
    each shard's two counts coming back, and it fits a 16 GB chip beside
    what a step leaves."""
    import re

    compiled = _mesh_reclaim(v5e_devices)
    hlo = compiled.as_text()
    assert "HloModule jit_reclaim" in hlo and "shard_map" in hlo
    for scope in ("reclaim.live", "reclaim.rehome", "reclaim.remap"):
        assert re.search(rf"jit\(reclaim\)/.*{scope}/", hlo), scope
    assert "reclaim.rehome/while/body/" in hlo and "probe.claim" in hlo
    for op in ("all-reduce", "all-gather", "all-to-all",
               "collective-permute", "reduce-scatter"):
        assert not list(_operand_elements(hlo, op)), op
    for line in hlo.splitlines():
        if " copy(" in line:
            assert not re.search(r"\[(1,)?16,8388608\]", line), line[:300]
    agg, _sharded, args = _q5_mesh(v5e_devices[:4], 1 << 23, 1 << 16)
    state, counts = jax.eval_shape(agg.reclaim_program(), args[0])
    assert (counts.shape, str(counts.dtype)) == ((4, 2), "int32")
    # every plane goes out in the layout it came in (its two words)
    assert jax.tree.structure(state) == jax.tree.structure(args[0])
    assert [(a.shape, a.dtype) for a in jax.tree.leaves(state)] \
        == [(a.shape, a.dtype) for a in jax.tree.leaves(args[0])]
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= _SHARD_BYTES - (1 << 23) * 8 - 4096
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes) < 8e9


def _one_chip_fire(devices, name: str, agg_sig, k, value_bits, cap, arrays,
                   panes: int, count_kind: str = "count"):
    from flink_tpu.runtime.operators.device_window import _fire_program

    one = SingleDeviceSharding(devices[0])

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    fire = _fire_program(agg_sig, k, value_bits, count_kind)
    return _compiled(name, lambda: getattr(fire, "_fn", fire).lower(
        spec((cap,), jnp.int64),
        # an int64 ring plane is handed over as the backend stores it
        {n: _plane_spec(
            "halves:int64" if np.dtype(dt) == np.int64 else np.dtype(dt).name,
            shape, one) for n, (shape, dt) in arrays.items()},
        spec((panes,), jnp.int32), spec((panes,), jnp.bool_),
        spec((), jnp.int64)).compile())


def _q7_fire(devices, value_bits: int, k: int):
    shape = (_Q7_RING, _Q7_CAP)
    return _one_chip_fire(
        devices, f"fire.q7.{value_bits}.{k}", (("max", "best"),), k,
        value_bits, _Q7_CAP, {"__count__": (shape, jnp.int32),
                              "best": (shape, jnp.int64)}, 1, "presence")


@pytest.mark.parametrize("k", [1, 1000])
@pytest.mark.parametrize("value_bits", [43, 64],
                         ids=["promised_43_bits", "no_promise"])
def test_max_ranked_fire_compiles_at_the_benchmark_shape(v5e_devices,
                                                         value_bits, k):
    """The one-chip fire of q7-10m-saturated (a `max` rank over a
    [8, 2^24] int64 plane beside the 32-bit presence plane, one pane row)
    with
    the 43 bits the query module promises AND with the default 64, which
    is what `AggSpec("max", "x")` gets. The no-promise cases fail at the
    parent of PR 33: the guard was the radix walk as a third `lax.switch`
    branch, and its 64-bit scans do not fit vmem inside the fire
    (`RESOURCE_EXHAUSTED: Ran out of memory in memory space vmem ...
    %reduce-window ... (u32[4,128], u32[4,128]) ... Scoped allocation
    with size 19.10M and limit 16.00M`). Now the guard is the same walk
    over the sign-flipped view: no scatter over the slots, no sort wider
    than the winners, and the select's passes ride out as an int32
    pair."""
    import re

    compiled = _q7_fire(v5e_devices, value_bits, k)
    hlo = compiled.as_text()
    assert "HloModule jit_fire_fn" in hlo     # the name the traces anchor on
    assert not re.search(r" scatter\(", hlo)
    assert max(_operand_elements(hlo, "sort"), default=0) <= k
    assert re.search(r" while\(", hlo)         # the compare-and-count walk
    select = jax.tree_util.tree_leaves(compiled.out_info)[-1]
    assert select.shape == (2,) and select.dtype == jnp.int32
    # beside 1.74 GB of state: the merged row, the views, the scans
    assert compiled.memory_analysis().temp_size_in_bytes < 1.5e9


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


def _assert_window0_reads_one_word_a_slot(hlo: str, slots: int) -> None:
    """PR 52: the first window (outside the tail's `cond` that re-reads a
    batch of colliding low words in full) holds ONE `[2^18, 8]` gather (the
    slots' low words; the high word's is `[2^18]`), and the v5e's
    compiler keeps BOTH 32-bit halves of the table in its fast memory
    space `S(1)`: a gather from a half left outside takes twice as long
    (PERF.md section 6, PRs 26 and 52)."""
    import re

    window0 = [line for line in hlo.splitlines()
               if re.search(r"= \S+ gather\(", line)
               and re.search(r'op_name="[^"]*/probe\.window0/probe\.gather/',
                             line)]
    shapes = sorted(line.split("= ")[1].split("{")[0] for line in window0)
    assert shapes == ["s32[262144]", "u32[262144,8]"], window0
    # (the re-read, `_window` in full, is the tail's: two more, in a branch)
    reread = [line for line in hlo.splitlines()
              if re.search(r"= u32\[262144,8\]\S* gather\(", line)
              and "/probe.tail/cond/branch_1_fun/probe.gather/" in line]
    assert len(reread) == 2, reread
    halves = [line.split(" custom-call(")[0] for line in hlo.splitlines()
              if re.search(r'custom_call_target="X64Split(Low|High)"', line)
              and f"u32[{slots}]" in line.split(" custom-call(")[0]]
    assert len(halves) == 2 and all("S(1)" in h for h in halves), halves


def _hash_probe(devices):
    from flink_tpu.ops.hash_table import lookup_or_insert

    one = SingleDeviceSharding(devices[0])
    return _compiled("probe", lambda: lookup_or_insert.lower(
        jax.ShapeDtypeStruct((1 << 24,), jnp.int64, sharding=one),
        jax.ShapeDtypeStruct((1 << 18,), jnp.int64, sharding=one),
        stats=True).compile())


def _electing_probe(devices):
    """The wide-batch program of `q5-inflight-saturated`: [2^23] slots x
    [2^18] rows whose keys may repeat (`handover=True`), with both
    counter vectors."""
    from flink_tpu.ops.hash_table import lookup_or_insert

    one = SingleDeviceSharding(devices[0])
    return _compiled("probe.elect", lambda: lookup_or_insert.lower(
        jax.ShapeDtypeStruct((1 << 23,), jnp.int64, sharding=one),
        jax.ShapeDtypeStruct((1 << 18,), jnp.int64, sharding=one),
        stats=True, handover=True).compile())


def test_hash_probe_compiles_at_the_benchmark_shape(v5e_devices):
    """[2^24] slots x [2^18] rows, with the counters: the first window, the
    compaction (a sort), both narrow loops and the wide one under one
    `lax.switch`, for the v5e's compiler; the first window reads one
    32-bit word a slot from a table whose halves both sit in fast
    memory."""
    hlo = _hash_probe(v5e_devices).as_text()
    _assert_probe_is_countable(hlo)
    _assert_window0_reads_one_word_a_slot(hlo, 1 << 24)


def test_electing_probe_compiles_at_the_benchmark_shape(v5e_devices):
    """The program every batch of the in-flight cell runs (PR 46): window
    0, the two narrow loops, and the election with its three loops and
    the full-width one. Its rounds stay countable, the election's own
    `scatter-min` lies outside every loop, and the v5e's compiler keeps
    BOTH 32-bit halves of the 2^23-slot table in its fast memory space
    for window 0's gathers (one `[2^18, 8]` of low words and one `[2^18]`
    of high words since PR 52), as it does for the program without an
    election (a form of the election that chained its loops through
    `lax.cond`s lost one half: PERF.md section 6, PR 46)."""
    import re

    hlo = _electing_probe(v5e_devices).as_text()
    _assert_probe_is_countable(hlo)
    elects = [line for line in hlo.splitlines()
              if re.search(r'op_name="[^"]*probe\.elect/scatter-min"', line)]
    assert elects and not any(re.search(_CLAIM_PATH, line)
                              for line in elects)
    _assert_window0_reads_one_word_a_slot(hlo, 1 << 23)


@pytest.mark.parametrize("rows", [64, 1 << 12])
def test_hash_probe_claim_stays_countable_on_any_backend(rows):
    """Small batches (the plain loop) and compacting ones alike keep the
    claim's scatter-min directly in a while body, under the module name the
    trace readers anchor on."""
    from flink_tpu.ops.hash_table import lookup_or_insert, make_table

    compiled = lookup_or_insert.lower(
        make_table(1 << 14), jnp.zeros(rows, jnp.int64)).compile()
    _assert_probe_is_countable(compiled.as_text())


def test_the_elections_scatter_is_not_counted_as_a_round():
    """The electing program has one `scatter-min` more than its loops
    hold: the election's, under `probe.elect` and in no `while` body, so
    the pattern `probe_rounds_p50` counts rounds by passes over it."""
    import re

    from flink_tpu.ops.hash_table import lookup_or_insert, make_table

    lowered = lookup_or_insert.lower(
        make_table(1 << 14), jnp.zeros(1 << 12, jnp.int64), stats=True,
        handover=True)
    hlo = lowered.compile().as_text()
    _assert_probe_is_countable(hlo)
    # (the x64 rewriter leaves the bare primitive on a 64-bit scatter's
    # halves: no path, no match)
    names = set(re.findall(r'op_name="([^"]*/scatter-min)"', hlo))
    elect = [n for n in names if "/probe.elect/" in n]
    assert elect and all("/probe.tail/" in n for n in elect)
    assert not any(re.search(_CLAIM_PATH, f'op_name="{n}"') for n in elect)
    rounds = [n for n in names if re.search(_CLAIM_PATH, f'op_name="{n}"')]
    assert rounds and len(rounds) + len(elect) == len(names)


def _assert_the_exchange_packs_without_a_scatter(hlo: str,
                                                 regions: dict) -> None:
    """A compiled mesh step at the benchmark shape ([4, 65536] rows a
    block, rounds of 4 x 20480): a round's send buffers are slices of the
    destination-ordered columns (PR 50), so no scatter lies under
    `mesh.exchange`, none is pathless (the x64 rewriter made the int64
    columns' scatters anew, without a name path), and the slices' fusions
    are the region `exchange.pack`."""
    import re

    lines = hlo.splitlines()
    scatters = [line for line in lines if re.search(r" scatter\(", line)]
    assert scatters
    assert not [line[:300] for line in scatters
                if "mesh.exchange" in line or 'op_name="' not in line]
    slices = {m.group(1) for line in lines if "exchange.pack/dynamic_slice"
              in line and " fusion(" in line
              for m in [re.match(r"\s+(?:ROOT )?%([\w.\-]+) = ", line)]}
    assert slices and {regions[name] for name in slices
                       if name in regions} == {"exchange.pack"}


# ---------------------------------------------------------------------------
# the region map of every program a benchmark cell runs, at its shapes


def _big_instructions(hlo: str, floor: int = 1 << 20) -> dict:
    """{instruction: opcode} of the instructions whose result, or whose
    operands together, hold ``floor`` bytes or more."""
    import re

    from flink_tpu.metrics.device import _closing

    item = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
            "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
            "f64": 8}

    def nbytes(type_text: str) -> int:
        return sum(item.get(dt, 0) * int(np.prod(
            [int(d) for d in dims.split(",") if d], dtype=np.int64))
            for dt, dims in re.findall(r"\b(pred|[a-z]+\d+)\[([\d,]*)\]",
                                       type_text))

    wrote, parsed = {}, []
    for line in hlo.splitlines():
        m = re.match(r"^\s+(?:ROOT\s+)?%([\w.\-]+)\s+=\s+(.*)$", line)
        if m is None:
            continue
        name, rest = m.groups()
        at = _closing(rest, 0) if rest.startswith("(") else rest.find(" ")
        op = re.match(r"\s*([\w\-]+)\(", rest[at:])
        if op is None:
            continue
        wrote[name] = nbytes(rest[:at])
        end = _closing(rest, at + op.end() - 1)
        parsed.append((name, op.group(1), re.findall(
            r"%([\w.\-]+)", rest[at + op.end():end])))
    return {name: opcode for name, opcode, refs in parsed
            if max(wrote[name], sum(wrote.get(r, 0) for r in refs)) >= floor}


def _region_programs(devices) -> dict:
    """name -> executable of every program of the benchmark's cells, at
    the cells' shapes (those the tests above compiled are not compiled
    again)."""
    from flink_tpu.parallel.sharded_window import _retire_program
    from flink_tpu.state.tpu_backend import _reset_row_program

    one = SingleDeviceSharding(devices[0])
    q5 = _FOLD_SIGS["q5"]
    reset = _reset_row_program(q5)
    agg, step = _mesh_step(devices)
    _a, sharded, args = _q5_mesh(devices[:4], 1 << 23, 1 << 16)
    retire = _retire_program(agg.sig)
    cap = 1 << 24
    return {
        "jit_lookup_or_insert": _hash_probe(devices),
        "jit_lookup_or_insert.elect": _electing_probe(devices),
        "jit_fold.q5": _host_born_fold(devices, "q5"),
        "jit_fold.q7": _host_born_fold(devices, "q7"),
        "jit_fire_fn.q5": _one_chip_fire(
            devices, "fire.q5", (("count", "bids"), ("sum", "revenue")),
            1000, 48, cap, {"__count__": ((16, cap), jnp.int32),
                            "revenue": ((16, cap), jnp.int64)}, 5),
        "jit_fire_fn.q7": _q7_fire(devices, 43, 1),
        "jit_reset": _compiled("reset", lambda: getattr(
            reset, "_fn", reset).lower(
            tuple(_plane_spec(dt, shape, one) for _k, dt, shape in q5),
            jax.ShapeDtypeStruct((), jnp.int32, sharding=one)).compile()),
        "jit_step": step,
        "jit_fire": _mesh_fire(devices),
        "jit_retire": _compiled("mesh.retire", lambda: getattr(
            retire, "_fn", retire).lower(
            args[0].accs, jax.ShapeDtypeStruct(
                (), jnp.int32,
                sharding=NamedSharding(sharded.mesh, P()))).compile()),
        "jit_reclaim": _reclaim(devices)[3],
        "jit_reclaim.mesh": _mesh_reclaim(devices),
    }


#: the programs of the mesh stack that take the sharded table
_MESH_TABLE_PROGRAMS = {"jit_step", "jit_fire", "jit_reclaim.mesh"}

#: the regions each program must hold, beside the split and the join of
#: its int64 arguments
_PROGRAM_REGIONS = {
    "jit_lookup_or_insert": {"probe.window0", "probe.tail"},
    "jit_lookup_or_insert.elect": {"probe.window0", "probe.tail"},
    "jit_fold.q5": {"fold.row", "fold.count", "fold.sum"},
    "jit_fold.q7": {"fold.row", "fold.count", "fold.max"},
    "jit_fire_fn.q5": {"fire.merge", "fire.topk"},
    "jit_fire_fn.q7": {"fire.merge", "fire.topk"},
    "jit_reset": {"fire.reset"},
    "jit_step": {"mesh.plan", "mesh.sync", "exchange.pack",
                 "exchange.collective", "probe.window0", "probe.tail",
                 "fold.row", "fold.count", "fold.sum"},
    "jit_fire": {"fire.merge", "fire.global"},
    "jit_retire": {"fire.retire"},
    "jit_reclaim": {"reclaim.live", "reclaim.rehome", "reclaim.remap",
                    "probe.window0", "probe.tail"},
    "jit_reclaim.mesh": {"reclaim.live", "reclaim.rehome", "reclaim.remap",
                         "probe.window0", "probe.tail"},
}


@pytest.mark.parametrize("program", list(_PROGRAM_REGIONS))
def test_every_big_instruction_lies_in_a_named_region(v5e_devices, program):
    """The map a program gives of itself (`metrics/device.classify_hlo`,
    what `program_regions` serves the benchmark's partition from), for
    every program of the benchmark's cells compiled FOR the v5e at the
    cells' shapes: every instruction the device runs that reads or writes
    1 MiB or more lies in a named region, the x64 rewriter's split and
    join among them (no `named_scope` can reach those: they go by their
    custom-call target), and the mesh step's send buffers are slices
    under `exchange.pack`, with no scatter."""
    import re

    from flink_tpu.metrics.device import UNNAMED, classify_hlo

    hlo = _region_programs(v5e_devices)[program].as_text()
    assert f"HloModule {program.split('.')[0]}" in hlo
    regions = classify_hlo(hlo)
    big = _big_instructions(hlo)
    assert len(big) > 4
    unnamed = sorted(name for name in big if regions.get(name) == UNNAMED)
    assert not unnamed, unnamed
    found = set(regions.values())
    assert _PROGRAM_REGIONS[program] <= found, \
        _PROGRAM_REGIONS[program] - found
    x64 = {"x64.split": 0, "x64.join": 0}
    for line in hlo.splitlines():
        m = re.match(r'\s+(?:ROOT )?%([\w.\-]+) = .*custom_call_target='
                     r'"X64(Split|Combine)', line)
        if m:
            kind = "x64.split" if m.group(2) == "Split" else "x64.join"
            assert regions[m.group(1)] == kind, line[:200]
            x64[kind] += 1
    if program in _MESH_TABLE_PROGRAMS \
            or program.startswith("jit_lookup_or_insert"):
        # the table's int64 keys are everywhere (ROADMAP S1)
        assert x64["x64.split"] >= 2 and x64["x64.join"] >= 1, x64
    if program == "jit_retire":
        # the identity's word into one ring row of each plane's two
        # words: four row writes and no split or join (PR 44)
        writes = re.findall(r"%([\w.\-]+) = [^=]* dynamic-update-slice\(",
                            hlo)
        assert len(writes) == 4 and x64 == {"x64.split": 0, "x64.join": 0}
        assert {regions[name] for name in writes} == {"fire.retire"}
    if program == "jit_step":
        _assert_the_exchange_packs_without_a_scatter(hlo, regions)


# ---------------------------------------------------------------------------
# PR 42: a 64-bit ring plane of the one-chip backend is stored as its two
# 32-bit words, so no program splits or joins a whole plane


def _q7_reclaim(devices):
    """The backend's reclaim at q7-10m-saturated's shapes: 2^24 slots
    under two `[8, 2^24]` planes, the MAX stored as words beside the
    32-bit presence plane."""
    from flink_tpu.state.tpu_backend import _reclaim_program

    one = SingleDeviceSharding(devices[0])
    sig = _FOLD_SIGS["q7"]
    reclaim = _reclaim_program(sig)
    return _compiled("reclaim.q7", lambda: getattr(
        reclaim, "_fn", reclaim).lower(
        jax.ShapeDtypeStruct((_Q7_CAP,), jnp.int64, sharding=one),
        tuple(_plane_spec(dt, shape, one) for _k, dt, shape in sig),
        jax.ShapeDtypeStruct((), jnp.int64, sharding=one)).compile())


def _q7_reset(devices):
    from flink_tpu.state.tpu_backend import _reset_row_program

    one = SingleDeviceSharding(devices[0])
    sig = _FOLD_SIGS["q7"]
    reset = _reset_row_program(sig)
    return _compiled("reset.q7", lambda: getattr(reset, "_fn", reset).lower(
        tuple(_plane_spec(dt, shape, one) for _k, dt, shape in sig),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=one)).compile())


def _x64_instructions(hlo: str) -> dict:
    """{instruction: (region, opcode, the most elements any array of its
    result holds, its operands' text)} of everything the program's own map
    (`metrics/device.classify_hlo`) books under `x64.split` / `x64.join`:
    the custom calls and the moves between memory spaces only they feed."""
    import re

    from flink_tpu.metrics.device import classify_hlo

    regions = classify_hlo(hlo)
    sized = {}
    for line in hlo.splitlines():
        m = re.match(r"\s+(?:ROOT )?%([\w.\-]+) = (.*?) ([\w\-]+)\(([^)]*)",
                     line)
        if m and regions.get(m.group(1)) in ("x64.split", "x64.join"):
            sized[m.group(1)] = (
                regions[m.group(1)], m.group(3),
                max(int(np.prod([int(d) for d in dims.split(",") if d],
                                dtype=np.int64))
                    for dims in re.findall(r"\[([\d,]*)\]", m.group(2))),
                m.group(4))
    assert set(sized) == {n for n, r in regions.items()
                          if r in ("x64.split", "x64.join")}
    return sized


#: program -> (the slots of its table, whether a table-sized split or
#: join is the table's own and so allowed: S1's, not this layout's)
_ONE_CHIP_PLANE_PROGRAMS = {
    "jit_fold.q5": (1 << 24, False), "jit_fold.q7": (1 << 24, False),
    "jit_reset": (1 << 24, False), "jit_reset.q7": (1 << 24, False),
    "jit_fire_fn.q5": (1 << 24, True), "jit_fire_fn.q7": (1 << 24, True),
    "jit_reclaim": (1 << 23, True), "jit_reclaim.q7": (1 << 24, True),
}


@pytest.mark.parametrize("program", list(_ONE_CHIP_PLANE_PROGRAMS))
def test_no_one_chip_program_splits_or_joins_a_plane(v5e_devices, program):
    """Every program that takes a ring plane of the one-chip backend, FOR
    the v5e at X's shapes (`[16, 2^24]` int64 SUM beside the int32 COUNT)
    and Q's (an `[8, 2^24]` int64 MAX beside the int32 presence plane),
    read through the map the
    program gives of itself (`metrics/device.classify_hlo`, what
    `program_regions` serves and `step_x64_ms` / `fire_x64_ms` read): the
    regions `x64.split` / `x64.join` hold no instruction over a plane.
    The fold and the reset hold none over as much as a ring ROW (the
    fold's are its batch's int64 columns, 2^18 rows; the reset has none
    at all); the fire and the reclaim hold the table's own split (and the
    reclaim the new table's join), which are the hash table's int64 keys
    and ROADMAP S1's to remove. Until PR 42 each of these programs split
    every int64 plane at its entry (`X64SplitLow` / `X64SplitHigh`) and
    the donating ones joined it at their exit (`X64Combine`), whatever
    they touched of it: 25 of the step's 121 ms and 37 of the fire's
    56 ms in q5-10m-saturated (ledger, PR 41)."""
    cap, table_allowed = _ONE_CHIP_PLANE_PROGRAMS[program]
    compiled = {**_region_programs(v5e_devices),
                "jit_reclaim.q7": _q7_reclaim(v5e_devices),
                "jit_reset.q7": _q7_reset(v5e_devices)}[program]
    hlo = compiled.as_text()
    assert f"HloModule {program.split('.')[0]}" in hlo
    sized = _x64_instructions(hlo)
    over_a_row = {n: v for n, v in sized.items()
                  if v[1] == "custom-call" and v[2] >= cap}
    assert not any(v[2] > cap for v in sized.values()), sized
    if not table_allowed:
        assert not over_a_row, over_a_row
        if program.startswith("jit_reset"):
            assert not sized, sized
        return
    splits = [v for v in over_a_row.values() if v[0] == "x64.split"]
    joins = [v for v in over_a_row.values() if v[0] == "x64.join"]
    assert len(splits) == 2 and all("%table" in v[3] for v in splits), splits
    # the reclaim hands back a new table; the fire hands back none
    assert len(joins) == (1 if program.startswith("jit_reclaim") else 0)
    # and no second copy of a plane is made inside the program: what it
    # needs beside its arguments is less than ONE ring row of each plane
    # (the fire: the merged rows and the select's views)
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 48 * cap


#: the mesh stack's four programs (ISSUE 44): each takes the sharded state
#: of M / F, a 2^23-slot table a chip under two `[4, 16, 2^23]` int64
#: planes kept as their words; the retire takes the planes alone
_MESH_PLANE_PROGRAMS = ("jit_step", "jit_fire", "jit_retire",
                        "jit_reclaim.mesh")


@pytest.mark.parametrize("program", _MESH_PLANE_PROGRAMS)
def test_no_mesh_program_splits_or_joins_a_plane(v5e_devices, program):
    """The mesh twin of the test above, on the described v5e 2x2 at M's
    shapes (`[4, 16, 2^23]`, COUNT + SUM int64): `jit_step`, `jit_fire`,
    `jit_retire` and the mesh `jit_reclaim` take the planes as their two
    `uint32` words (`ShardedWindowState.accs`: `Halves`), so the regions
    `x64.split` / `x64.join` of the program's own map hold no instruction
    over as much as a ring ROW of a plane but the table's own (the hash
    table's int64 keys, ROADMAP S1's: the step and the reclaim split it
    and join the new one, the fire splits it, the retire never sees it).
    Until PR 44 each split both planes of a shard at its entry and the
    donating ones joined them at their exit, whatever they touched: 25.2
    of the step's 93.5 ms and 37.0 of the fire's 58.2 ms in
    q5-16m-mesh4-saturated (ledger, PR 43). The state stays donated, no
    parameter or result of a program is an `s64` plane, and beside the
    state a program holds less than one ring row of each plane's words
    twice over (the fire: the window's rows, merged, and the select's
    views)."""
    cap, ring, planes = 1 << 23, 16, 2
    compiled = _region_programs(v5e_devices)[program]
    hlo = compiled.as_text()
    assert f"HloModule {program.split('.')[0]}" in hlo
    entry = next(line for line in hlo.splitlines()
                 if line.startswith("ENTRY"))
    assert f"s64[1,{ring},{cap}]" not in entry
    assert entry.split("->")[0].count(f"u32[1,{ring},{cap}]") == 2 * planes
    sized = _x64_instructions(hlo)
    assert not any(v[2] > cap for v in sized.values()), sized
    calls = [v[0] for v in sized.values()
             if v[1] == "custom-call" and v[2] >= cap]
    splits, joins = calls.count("x64.split"), calls.count("x64.join")
    mem = compiled.memory_analysis()
    plane_bytes = planes * ring * cap * 8
    if program == "jit_retire":
        # two one-row writes a plane, in place, and nothing else
        assert not sized, sized
        assert mem.alias_size_in_bytes >= plane_bytes
        assert mem.temp_size_in_bytes < 1 << 20
        return
    # the table's two words at the entry; the step and the reclaim hand
    # back a new table, the fire hands back none
    assert splits == 2 and joins == (0 if program == "jit_fire" else 1), \
        sized
    if program != "jit_fire":
        assert mem.alias_size_in_bytes >= _SHARD_BYTES - cap * 8 - 4096
    # no second copy of a plane (1.07 GB a shard): the step's send
    # buffers and the probe's, the reclaim's per-slot vectors, the fire's
    # [W, cap] rows and views
    assert mem.temp_size_in_bytes < 128 * cap, mem.temp_size_in_bytes


#: sha256 of `lower().as_text()` (StableHLO, no locations) of the ONE-CHIP
#: stack's plane programs on a described v5e at `_one_chip_digest_programs`'
#: shapes, AT THE PARENT OF PR 44 (commit 4081619). PR 44 changes the mesh
#: stack's stored layout and must not touch this stack: `ring_fold` and
#: `reclaim_shard` are shared, and handed what the one-chip backend hands
#: them they trace what they traced. A later change that MEANS to alter a
#: one-chip program writes its own digests here: PR 49 did for the three
#: `.q7` programs (the hidden plane of the COUNT-less Q7 job is a 32-bit
#: presence plane folded by a scatter-max, where it was an int64 count);
#: the Q5 ones are still the text of 4081619.
_ONE_CHIP_DIGESTS_AT_4081619 = {
    # PR 54 MEANT to change it: the int64 SUM's rows go limb by limb into
    # a zeroed 32-bit row and are carried into the plane's two words, and
    # the backend's count of limb scatters rides through the program
    "jit_fold.q5":
        "20a23f2efbf5a0c9140949e51e810cc35ba391e77e6a6ef585a70c7d47107d2f",
    "jit_fold.q7":
        "63c4c2cc153cc81669f8f51e18ee57e3f8d324a2aff7c4f2453724725dc6ca40",
    "jit_fire_fn.q5":
        "9da6a9579af24094a0730af2551359ea6214dc479836b3ed6d68c9c05bd0ca3f",
    "jit_fire_fn.q7":
        "2722b805f84a17a72eb051a26894b4ca1fab966cb29eadb99c412b3a032978c4",
    "jit_reset":
        "e8e4d0355a1a73cec64b87a924a87ab6ad0261ad0c4ec239394270940560f1f9",
    "jit_reclaim.q5":
        "ff1098ef55b64a7a2ef6b53fc5cef6690cd9be715ab677d150311275abd4a21c",
    "jit_reclaim.q7":
        "4cb23bc7fc21c2f81f6c72a0d1d056e9b406b1855964eb9695e3e06aef3ec8d5",
}


def _one_chip_digest_programs(devices) -> dict:
    """name -> sha256 of the StableHLO of the one-chip programs that take
    ring planes, at 2^10 slots and 256 rows in the backend's own layouts
    (Q5: int32 COUNT beside the int64 SUM's words; Q7: the int32 presence
    plane beside the int64 MAX's words)."""
    import hashlib

    from flink_tpu.runtime.operators.device_window import _fire_program
    from flink_tpu.state.tpu_backend import _fold_program, \
        _reclaim_program, _reset_row_program

    one = SingleDeviceSharding(devices[0])
    cap, rows = 1 << 10, 256

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    def fn(program):
        return getattr(program, "_fn", program)

    sigs = {q: tuple((kind, dt, (ring, cap)) for kind, dt, (ring, _c) in sig)
            for q, sig in _FOLD_SIGS.items()}

    def planes(sig):
        return tuple(_plane_spec(dt, shape, one) for _k, dt, shape in sig)

    lowered = {}
    for q, sig in sigs.items():
        lowered[f"jit_fold.{q}"] = fn(_fold_program(sig)).lower(
            planes(sig), spec((rows,), jnp.int32), spec((rows,), jnp.int64),
            spec((rows,), jnp.bool_), (None, spec((rows,), jnp.int64)),
            *_limb_count(sig, spec))
    q5 = sigs["q5"]
    for q, agg_sig, names, k, bits, panes in (
            ("q5", (("count", "bids"), ("sum", "revenue")),
             ("__count__", "revenue"), 1000, 48, 5),
            ("q7", (("max", "best"),), ("__count__", "best"), 1, 43, 1)):
        lowered[f"jit_fire_fn.{q}"] = fn(_fire_program(
            agg_sig, k, bits, sigs[q][0][0])).lower(
            spec((cap,), jnp.int64),
            {n: _plane_spec(dt, shape, one)
             for n, (_k, dt, shape) in zip(names, sigs[q])},
            spec((panes,), jnp.int32), spec((panes,), jnp.bool_),
            spec((), jnp.int64))
    lowered["jit_reset"] = fn(_reset_row_program(q5)).lower(
        planes(q5), spec((), jnp.int32))
    for q, sig in sigs.items():
        lowered[f"jit_reclaim.{q}"] = fn(_reclaim_program(sig)).lower(
            spec((cap,), jnp.int64), planes(sig), spec((), jnp.int64))
    return {name: hashlib.sha256(low.as_text().encode()).hexdigest()
            for name, low in lowered.items()}


@pytest.mark.parametrize("program", list(_ONE_CHIP_DIGESTS_AT_4081619))
def test_the_one_chip_programs_lower_to_what_they_lowered_to(v5e_devices,
                                                             program):
    """X, S, U, Q, I and K run another stack's programs, and PR 44 does
    not touch them: the backend's `jit_fold` (Q5's and Q7's signatures),
    `jit_fire_fn`, `jit_reset` and `jit_reclaim` lower, letter for letter,
    to the text they lowered to at the parent commit."""
    got = _compiled("one_chip.digests",
                    lambda: _one_chip_digest_programs(v5e_devices))
    assert set(got) == set(_ONE_CHIP_DIGESTS_AT_4081619)
    assert got[program] == _ONE_CHIP_DIGESTS_AT_4081619[program]


#: sha256 of the StableHLO of the hash probe as its callers get it, AT PR
#: 52, which MEANT to change all six: a batch that compacts reads its
#: first window from the slots' low 32-bit words (`_window0`), and the
#: counters are four (the fourth: rows that window left undecided), which
#: is all that moved the program below the compaction width. The plain
#: program with its counters (X, S, U, Q, D past their prefill), as the
#: mesh step calls it (a valid mask, no counters) and below the
#: compaction width; the hand-over program for keys that cannot repeat
#: (`distinct`), alone, inside a reclaim whose re-homing chunk compacts
#: (2^12 slots: the digests above are of 2^10, with no first window, and
#: did not move) and inside the session step. They were PR 46's control
#: (`_PROBE_DIGESTS_AT_F6F8D19`: the parent of the election) until this
#: PR; a PR that does not mean to change the probe leaves them as they
#: are.
_PROBE_DIGESTS_AT_PR_52 = {
    "jit_lookup_or_insert.plain":
        "2275c2415d8bd083a709ffca524c47faa9a3866f75f173f7a7ccabd940dc9404",
    "jit_lookup_or_insert.plain.mesh":
        "33bb1c7f53016cf4823585a85773b8f258703da53a77bd743db3587ef50f026f",
    "jit_lookup_or_insert.plain.small":
        "246356872ce026ac84b5e0dcd46696bd1a3fd476bd1c5dd3c9327f4b9de2d197",
    "jit_lookup_or_insert.distinct":
        "0f7925ce669381e684aeaaf9b3c012477685f591a4bd7b1ec440f4f05c37a942",
    "jit_reclaim.q5.handover":
        "ee642c6962662b969b1e150dd85d6cf754466bc455c6186196cd9343b172be32",
    "jit_step.session":
        "a3410c871a854defd1530ff7b375da9589713f0417997497bdeee8523809544c",
}


def _probe_digest_programs(devices) -> dict:
    """name -> sha256 of the StableHLO of the probe's unelecting forms:
    2^14 slots x 2^12 rows (the smallest batch that compacts) and 256
    rows; the Q5 reclaim at 2^12 slots; the session step at 2^13 slots,
    2 lanes, 2^12 rows."""
    import hashlib

    from flink_tpu.ops.hash_table import lookup_or_insert
    from flink_tpu.runtime.operators.device_session import _sess_step
    from flink_tpu.state.tpu_backend import _reclaim_program

    one = SingleDeviceSharding(devices[0])
    cap, rows = 1 << 14, 1 << 12

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    def fn(program):
        return getattr(program, "_fn", program)

    table, keys = spec((cap,), jnp.int64), spec((rows,), jnp.int64)
    valid = spec((rows,), jnp.bool_)
    lowered = {
        "jit_lookup_or_insert.plain":
            lookup_or_insert.lower(table, keys, stats=True),
        "jit_lookup_or_insert.plain.mesh":
            lookup_or_insert.lower(table, keys, valid),
        "jit_lookup_or_insert.plain.small":
            lookup_or_insert.lower(table, spec((256,), jnp.int64),
                                   stats=True),
        "jit_lookup_or_insert.distinct":
            lookup_or_insert.lower(table, keys, valid, handover=True,
                                   distinct=True),
    }
    sig = tuple((kind, dt, (ring, rows))
                for kind, dt, (ring, _c) in _FOLD_SIGS["q5"])
    lowered["jit_reclaim.q5.handover"] = fn(
        _reclaim_program(sig)).lower(
        spec((rows,), jnp.int64),
        tuple(_plane_spec(dt, shape, one) for _k, dt, shape in sig),
        spec((), jnp.int64))
    L, scap, block = 2, 1 << 13, 512
    planes = {name: _plane_spec(dt, (L, scap), one) for name, dt in (
        ("__start__", "halves:int64"), ("__end__", "halves:int64"),
        ("__open__", "int8"), ("__count__", "halves:int64"))}
    scalar = spec((), jnp.int64)
    lowered["jit_step.session"] = fn(_sess_step((), L, 10_000, block)).lower(
        spec((scap,), jnp.int64), planes, spec((scap,), jnp.int32), scalar,
        scalar, spec((3,), jnp.int64), spec((scap // block,), jnp.bool_),
        keys, keys, {}, scalar, scalar)
    return {name: hashlib.sha256(low.as_text().encode()).hexdigest()
            for name, low in lowered.items()}


@pytest.mark.parametrize("program", list(_PROBE_DIGESTS_AT_PR_52))
def test_the_unelecting_probe_lowers_to_what_it_lowered_to(v5e_devices,
                                                           program):
    """The probe's forms outside the one-chip backend's wide batches
    (the plain program of X, S, U, Q, D and of the mesh step of M, Z and
    F; the hand-over program of callers whose keys cannot repeat: the
    reclaim's re-homing on both stacks, the session step of K) lower,
    letter for letter, to the text PR 52 left: which fast memory the
    v5e's compiler gives the table's halves turns on how the rest of a
    probe program is written (ROADMAP D13), so a PR that touches one form
    shows here that it left the others alone."""
    got = _compiled("probe.digests",
                    lambda: _probe_digest_programs(v5e_devices))
    assert set(got) == set(_PROBE_DIGESTS_AT_PR_52)
    assert got[program] == _PROBE_DIGESTS_AT_PR_52[program]


# ---------------------------------------------------------------------------
# PR 43: the session operator's two programs at the Q11 cell's shapes


_SESSION_SHAPE = dict(lanes=4, cap=1 << 24, rows=1 << 18, gap=10_000,
                      fire_rows=1 << 18, dirty_block=512)


def _session_program(devices, which: str):
    """`jit_step` / `jit_fire` of `runtime/operators/device_session.py` at
    `q11-sessions-saturated`'s shapes: 2^24 slots, 4 lanes, the three
    int64 lanes planes as the backend stores them (two 32-bit words),
    `__open__` int8, COUNT(*) alone, a batch of 2^18 bids, a round of
    2^18 sessions."""
    from flink_tpu.runtime.operators.device_session import _sess_fire, \
        _sess_step

    one = SingleDeviceSharding(devices[0])
    s = _SESSION_SHAPE
    L, cap, B = s["lanes"], s["cap"], s["rows"]

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    planes = {name: _plane_spec(dt, (L, cap), one) for name, dt in (
        ("__start__", "halves:int64"), ("__end__", "halves:int64"),
        ("__open__", "int8"), ("__count__", "halves:int64"))}
    scalar, stats = spec((), jnp.int64), spec((3,), jnp.int64)
    dirty = spec((cap // s["dirty_block"],), jnp.bool_)
    if which == "step":
        step = _sess_step((), L, s["gap"], s["dirty_block"])
        return _compiled("session.step", lambda: getattr(
            step, "_fn", step).lower(
            spec((cap,), jnp.int64), planes, spec((cap,), jnp.int32),
            scalar, scalar, stats, dirty, spec((B,), jnp.int64),
            spec((B,), jnp.int64), {}, scalar, scalar).compile())
    fire = _sess_fire((), s["gap"], s["fire_rows"], s["dirty_block"])
    return _compiled("session.fire", lambda: getattr(
        fire, "_fn", fire).lower(
        spec((cap,), jnp.int64), planes, scalar, scalar, stats, dirty,
        scalar).compile())


#: the regions each session program must hold
_SESSION_REGIONS = {
    "step": {"session.probe", "session.segment", "session.lanes",
             "session.fold", "session.emit", "probe.window0", "probe.tail"},
    "fire": {"session.fire.scan", "session.fire.compact",
             "session.fire.reset"},
}


@pytest.mark.parametrize("which", ["step", "fire"])
def test_session_programs_compile_at_the_benchmark_shape(v5e_devices, which):
    """Both programs of the session operator compile for a described v5e
    at `capacity` 2^24, 4 lanes and 2^18 rows (PR 43), their planes
    donated and rewritten in place, and fit a 16 GB chip many times over
    (the live bytes are printed: a later PR that makes them not fit is
    caught here, without a chip). Every instruction that reads or writes
    1 MiB or more lies in a named region (the compiler's own re-tiling
    of a plane around its scatter among them, by what it feeds), and no
    whole 64-bit plane is an argument or a result: the planes go in and
    come out as their 32-bit words."""
    import re

    from flink_tpu.metrics.device import UNNAMED, classify_hlo

    compiled = _session_program(v5e_devices, which)
    hlo = compiled.as_text()
    assert f"HloModule jit_{which}" in hlo
    mem = compiled.memory_analysis()
    live = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    print(f"\nsession {which} at 2^24 x 4 lanes: arguments "
          f"{mem.argument_size_in_bytes / 1e9:.3f} GB, temporaries "
          f"{mem.temp_size_in_bytes / 1e9:.3f} GB, aliased "
          f"{mem.alias_size_in_bytes / 1e9:.3f} GB, live {live / 1e9:.3f} GB")
    # the table (134 MB) and six words + __open__ (1.68 GB) + cur_lane
    state = _SESSION_SHAPE["cap"] * (6 * 4 + 1) * _SESSION_SHAPE["lanes"]
    assert mem.alias_size_in_bytes >= state
    assert live < 4e9
    entry = next(line for line in hlo.splitlines()
                 if line.startswith("ENTRY"))
    assert "s64[4,16777216]" not in entry
    regions = classify_hlo(hlo)
    found = set(regions.values())
    assert _SESSION_REGIONS[which] <= found, _SESSION_REGIONS[which] - found
    big = _big_instructions(hlo)
    assert len(big) > 20
    unnamed = sorted(name for name in big if regions.get(name) == UNNAMED)
    # what is left unnamed is small: the pieces of a cumulative sum over
    # the batch that the compiler cuts loose from their scope
    sizes = {}
    for line in hlo.splitlines():
        m = re.match(r"\s+(?:ROOT\s+)?%([\w.\-]+)\s+=\s+(.*)$", line)
        if m and m.group(1) in unnamed:
            sizes[m.group(1)] = m.group(2)[:60]
    assert not [n for n, text in sizes.items()
                if "16777216" in text or "67108864" in text], sizes


# ---------------------------------------------------------------------------
# PR 48: NEXmark Q7 on the mesh (q7-16m-mesh4-saturated): a sharded int64
# MAX plane beside the hidden plane (a 32-bit presence plane since PR 49:
# the job reads no count), a ring of 8, one pane row a window, k = 1, the
# rank promised 43 bits


def _q7_mesh(devices):
    """Q7's sharded aggregate (one int64 MAX; the state appends the hidden
    plane, 32 bits of presence) at the four-chip cell's shapes on a
    described v5e 2x2, and the step's arguments as shapes on it."""
    from flink_tpu.parallel.sharded_window import AggDef, ShardedWindowAgg, \
        ShardedWindowState

    cap, batch, ring, D = 1 << 23, 1 << 16, 8, 4
    mesh = Mesh(np.array(devices[:D]), ("data",))
    agg = ShardedWindowAgg(mesh, [AggDef("best", "max", jnp.int64)],
                           capacity=cap, ring=ring, max_parallelism=128)
    sharded = NamedSharding(mesh, P("data"))

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharded)

    state = ShardedWindowState(
        spec((D, cap), jnp.int64),
        # each plane as the state stores it: the int64 MAX as its words,
        # the int32 presence plane as the one array it is
        {a.name: _plane_spec(
            ("halves:" if np.dtype(a.dtype).itemsize == 8 else "")
            + np.dtype(a.dtype).name, (D, ring, cap), sharded)
         for a in agg.aggs},
        spec((D,), jnp.int64))
    args = (state, spec((D, batch), jnp.int64),
            {"best": spec((D, batch), jnp.int64)},
            spec((D, batch), jnp.int64), spec((D, batch), jnp.bool_))
    return agg, sharded, args


def _q7_mesh_program(devices, program: str):
    """One program of the four-chip Q7 cell, compiled as the operator
    dispatches it; ``jit_fire.unpromised`` is the fire of a job that
    declared no ``value_bits``."""
    from flink_tpu.parallel.sharded_window import _retire_program

    agg, sharded, args = _q7_mesh(devices)
    rep = NamedSharding(sharded.mesh, P())

    def fire(value_bits):
        return agg.fire_program("best", 1, value_bits).lower(
            args[0], jax.ShapeDtypeStruct((1,), jnp.int32, sharding=rep),
            jax.ShapeDtypeStruct((1,), jnp.bool_, sharding=rep)).compile()

    def retire():
        program = _retire_program(agg.sig)
        return getattr(program, "_fn", program).lower(
            args[0].accs,
            jax.ShapeDtypeStruct((), jnp.int32, sharding=rep)).compile()

    build = {
        "jit_step": lambda: agg.step_program().lower(
            *args, agg._base_start, agg._base_len).compile(),
        "jit_fire": lambda: fire(43),
        "jit_fire.unpromised": lambda: fire(None),
        "jit_retire": retire,
        "jit_reclaim": lambda: agg.reclaim_program().lower(
            args[0]).compile(),
    }[program]
    return _compiled(f"q7.mesh.{program}", build)


#: a chip's shard of the Q7 mesh state: the table, the [8, 2^23] int64 MAX
#: and the [8, 2^23] int32 presence plane
_Q7_SHARD_BYTES = (1 << 23) * (8 + 8 * 8 + 8 * 4) + 8

_Q7_MESH_REGIONS = {
    "jit_step": {"mesh.plan", "mesh.sync", "exchange.pack",
                 "exchange.collective", "probe.window0", "probe.tail",
                 "fold.row", "fold.count", "fold.max"},
    "jit_fire": {"fire.merge", "fire.global"},
    "jit_fire.unpromised": {"fire.merge", "fire.global"},
    "jit_retire": {"fire.retire"},
    "jit_reclaim": {"reclaim.live", "reclaim.rehome", "reclaim.remap",
                    "probe.window0", "probe.tail"},
}


@pytest.mark.parametrize("program", list(_Q7_MESH_REGIONS))
def test_q7_mesh_programs_compile_at_the_benchmark_shape(v5e_devices,
                                                         program):
    """Step, fire, retire and reclaim of `q7-16m-mesh4-saturated` for a
    described v5e 2x2 ([4, 65536] rows against a 2^23-slot table, a
    [4, 8, 2^23] int64 MAX kept as its words and the int32 presence plane
    of a job that reads no count; the fire over ONE pane row, k = 1, a
    64-bit rank): each compiles, fits a chip beside the 0.87 GB of state
    with room, names the regions the benchmark reads
    (`fold.max`, `fire.merge`, `fire.global`, `fire.retire`) over every
    instruction that moves 1 MiB or more, and takes no `s64` plane. With
    the job's promise (43 bits) a shard's select holds no guard: the
    compiled fire has not one `xor`; with none it xors every slot with
    the flip word."""
    import re

    from flink_tpu.metrics.device import UNNAMED, classify_hlo

    compiled = _q7_mesh_program(v5e_devices, program)
    hlo = compiled.as_text()
    assert f"HloModule {program.split('.')[0]}" in hlo
    regions = classify_hlo(hlo)
    big = _big_instructions(hlo)
    unnamed = sorted(name for name in big if regions.get(name) == UNNAMED)
    assert big and not unnamed, unnamed
    assert _Q7_MESH_REGIONS[program] <= set(regions.values())
    entry = next(line for line in hlo.splitlines()
                 if line.startswith("ENTRY"))
    assert "s64[1,8,8388608]" not in entry
    # the MAX's two words and the hidden plane as ONE 32-bit operand
    assert entry.split("->")[0].count("u32[1,8,8388608]") == 2
    assert entry.split("->")[0].count("s32[1,8,8388608]") == 1
    mem = compiled.memory_analysis()
    live = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    assert live < 2e9, live
    if program == "jit_step":
        _assert_the_exchange_packs_without_a_scatter(hlo, regions)
    if program.startswith("jit_fire"):
        guarded = program.endswith("unpromised")
        assert bool(re.search(r" xor\(", hlo)) == guarded
        # the walk is there, on the shard, and nothing wider than the
        # D x k candidates crosses the interconnect or is sorted
        assert "shard_map" in hlo and re.search(r" while\(", hlo)
        assert max(_operand_elements(hlo, "sort"), default=0) <= 4
        for op in ("all-reduce", "all-gather", "all-to-all",
                   "collective-permute", "reduce-scatter"):
            assert max(_operand_elements(hlo, op), default=0) <= 128, op
        # the window's one row of each plane and the select's views
        assert mem.temp_size_in_bytes < 48 * (1 << 23)
    else:
        assert mem.alias_size_in_bytes >= {
            "jit_retire": _Q7_SHARD_BYTES - (1 << 23) * 8 - 4096,
            "jit_reclaim": _Q7_SHARD_BYTES - (1 << 23) * 8 - 4096,
            "jit_step": _Q7_SHARD_BYTES - 4096}[program]


def _fold_scatters(hlo: str, kind: str) -> list:
    """The result types of the scatters that lie under ``fold.<kind>``."""
    import re

    return [m.group(1) for line in hlo.splitlines()
            if f"fold.scatter/fold.{kind}/" in line
            for m in [re.search(r"= (\(.*?\)|\S+) scatter\(", line)] if m]


@pytest.mark.parametrize("program", ["jit_fold.q7", "jit_step.q7_mesh"])
def test_the_presence_plane_folds_as_one_32_bit_scatter(v5e_devices,
                                                        program):
    """Q's fold and Z's step at the cells' shapes: the hidden plane of the
    COUNT-less Q7 job is a 32-bit operand, and what folds it, named
    `fold.count` whatever its arithmetic, is a scatter over ONE `s32` row:
    no two-word (variadic `u32`, `u32`) scatter of an int64 count lies
    under `fold.count` any more, and the job's MAX keeps its own under
    `fold.max`."""
    import re

    compiled = (_host_born_fold(v5e_devices, "q7") if program == "jit_fold.q7"
                else _q7_mesh_program(v5e_devices, "jit_step"))
    hlo = compiled.as_text()
    rows = 1 << (24 if program == "jit_fold.q7" else 23)
    scatters = _fold_scatters(hlo, "count")
    assert scatters and all(t.startswith(f"s32[{rows}]{{")
                            for t in scatters), scatters
    # the folds' two-word scatters (the mesh step has others: the probe's
    # claim of int64 keys): the MAX's, nobody else's
    wide = [line for line in hlo.splitlines()
            if "/fold.scatter/" in line and re.search(
                r"= \(u32\[\d+\]\S*, u32\[\d+\]\S*\) scatter\(", line)]
    assert wide and all("fold.scatter/fold.max" in line for line in wide)


@pytest.mark.parametrize("program", ["jit_fold.q5", "jit_step.q5_mesh"])
def test_an_additive_int64_plane_folds_with_32_bit_scatters(v5e_devices,
                                                           program):
    """X's fold and M's step at the cells' shapes (PR 54): a SUM's and a
    COUNT's rows go into a 64-bit plane limb by limb, so every scatter
    under `fold.sum` and `fold.count` is over ONE 32-bit row (`u32`: a
    limb's zeroed delta row; `s32`: X's declared int32 COUNT) and no
    two-word (variadic `u32`, `u32`) scatter of an `s64` row lies under
    either: a 64-bit update costs the TPU six times a 32-bit one. The
    limbs' scatters lie beneath `fold.limb` and the dense add into the
    row's two words beneath `fold.carry`, both inside the kind's region.
    Q's fold and Z's step keep the MAX's own two-word scatter under
    `fold.max` (`test_the_presence_plane_folds_as_one_32_bit_scatter`)."""
    import re

    if program == "jit_fold.q5":
        hlo, rows = _host_born_fold(v5e_devices, "q5").as_text(), 1 << 24
    else:
        hlo, rows = _mesh_step(v5e_devices)[1].as_text(), 1 << 23
    for kind in ("sum", "count"):
        scatters = _fold_scatters(hlo, kind)
        assert scatters and all(
            re.match(rf"[us]32\[{rows}\]\{{", t) for t in scatters), \
            (kind, scatters)
    wide = [line for line in hlo.splitlines()
            if "/fold.scatter/" in line and re.search(
                r"= \(u32\[\d+\]\S*, u32\[\d+\]\S*\) scatter\(", line)]
    assert not wide, wide[0][:300]
    assert re.search(
        r"fold\.scatter/fold\.sum/[^\"]*fold\.limb/[^\"]*scatter", hlo)
    assert re.search(r"fold\.scatter/fold\.sum/[^\"]*fold\.carry/", hlo)
    assert bool(re.search(r"fold\.scatter/fold\.count/fold\.carry/", hlo)) \
        == (program == "jit_step.q5_mesh")


def test_the_q7_mesh_step_lowers_to_what_it_lowered_to(v5e_devices):
    """Z's step holds a MAX beside a 32-bit presence plane, neither of
    them additive 64-bit: PR 54, which folds such planes limb by limb and
    hands M's and F's step a fourth result (the shards' limb scatters),
    leaves this one the parent's text, letter for letter (sha256 of
    `lower().as_text()` at commit 2997035), so it loads the parent's
    executable: the proof that Z is that PR's control."""
    import hashlib

    agg, _sharded, args = _q7_mesh(v5e_devices)
    text = agg.step_program().lower(
        *args, agg._base_start, agg._base_len).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == \
        "3e261f8eed0c3e96a07063da9edc0f88ce3d51be8e02f40bce1544841535d561"


#: sha256 of `lower().as_text()` of the ranked mesh fire of M and F
#: (q5-16m-mesh4 / q5-inflight-mesh4: a COUNT rank over [4, 16, 2^23]
#: int64 planes, k = 1000, five pane rows) and of the same planes ranked
#: by the SUM (no promise), on a described v5e 2x2, AT THE PARENT OF PR 48
#: (commit 47c33d1). PR 48 carries the rank's `value_bits` to the fire;
#: a COUNT rank keeps 63 and a rank with no promise 64, so these fires
#: are the parent's text and load the parent's executables from the
#: compile cache. A later change that MEANS to alter them writes its own
_MESH_FIRE_DIGESTS_AT_47C33D1 = {
    "bids":
        "b273280c02a484594a1d418fcbe5a17727b7a2721732f331d99feee20397fac7",
    "revenue":
        "257da0c3fcdfd51a4c0943a523404bd48bfb8b1df370a595e495dd5b3802daf8",
}


@pytest.mark.parametrize("rank", list(_MESH_FIRE_DIGESTS_AT_47C33D1))
def test_the_mesh_fire_of_m_and_f_lowers_to_what_it_lowered_to(v5e_devices,
                                                               rank):
    import hashlib

    agg, sharded, args = _q5_mesh(v5e_devices[:4], 1 << 23, 1 << 16)
    rep = NamedSharding(sharded.mesh, P())
    text = agg.fire_program(rank, 1000).lower(
        args[0], jax.ShapeDtypeStruct((5,), jnp.int32, sharding=rep),
        jax.ShapeDtypeStruct((5,), jnp.bool_, sharding=rep)).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() \
        == _MESH_FIRE_DIGESTS_AT_47C33D1[rank]
