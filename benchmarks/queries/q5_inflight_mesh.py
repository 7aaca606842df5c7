"""NEXmark Q5 over auction ids that advance, as ONE keyed vertex sharded
over a device mesh: the job is ``q5_mesh.py``'s (``build``,
``operator_class``, ``operator_capacity``: key_by -> window ->
mesh_aggregate, ``query.capacity`` and ``query.device_batch`` PER DEVICE)
and the reference is ``q5_inflight.py``'s (``q5_reference.py`` over dense
arrays of ``data.id_space`` ids), both loaded and not copied."""

from __future__ import annotations

from benchmarks.harness.spec import BENCH_DIR, load_module

_mesh = load_module(BENCH_DIR, "queries", "q5_mesh")
_inflight = load_module(BENCH_DIR, "queries", "q5_inflight")
globals().update({name: getattr(_mesh, name) for name in _mesh.__all__})
make_reference = _inflight.make_reference

__all__ = list(_mesh.__all__)
