"""One rehearsal of the four-chip in-flight cell through ``run_cell`` on
four virtual CPU devices, in a process of its own (``mesh_cell_driver``
sets the device count before JAX starts; this driver is its twin for
``q5-inflight-mesh4-saturated`` and prints what that cell adds).

    python benchmarks/tests/inflight_mesh_cell_driver.py <mode> <seed>

``mode`` is ``sound`` or ``no_reclaim`` (the operator's reclaim switched
off underneath: a reading past the load limit grows the tables, as the
parent's did). Prints one JSON object.
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import mesh_cell_driver  # noqa: E402,F401 - four CPU devices, the repo on the path

CELL = "q5-inflight-mesh4-saturated"
READERS = ("exchange_rounds_per_step", "mesh_insert_share",
           "reclaim_freed_share", "reclaim_stage_ms", "mesh_upload_ms",
           "reclaim_device_ms")


def main(mode: str, seed: int) -> None:
    import jax

    from benchmarks.harness.cell import run_cell
    from benchmarks.harness.spec import load_spec
    from flink_tpu.metrics.tracing import TRACER
    from flink_tpu.runtime.operators.mesh_window import \
        MeshWindowAggOperator as Op

    if mode == "no_reclaim":
        def grow_at_once(self, drain=None):
            self._grow(2 * self._agg.capacity)

        Op._reclaim = grow_at_once
    elif mode != "sound":
        raise SystemExit(f"unknown mode {mode!r}")

    spec = load_spec()
    TRACER.reset()
    run = run_cell(spec, spec.cell(CELL), seed=seed, seconds=3.0,
                   trace=False, rehearse=True)
    before, last = run.at_end["stats_before"], run.at_end["device_stats"]
    readers = {}
    for name in READERS:
        params = spec.layer_metric(name)
        readers[name] = spec.module("readers", params["reader"]).read(
            run, params.get("params", {}))
    reclaims = [s.attributes for s in TRACER.retained_spans()
                if (s.scope, s.name) == ("window", "Reclaim")]
    print(json.dumps({
        "correct": run.correct, "attempted": run.attempted,
        "failed": run.failed, "devices": len(jax.devices()),
        "checks": {c["check"]: c["value"] for c in run.checks
                   if "value" in c},
        "tally": next(c for c in run.checks if c["check"] == "_tally"),
        "job": {k: last[k] - before[k] for k in (
            "state_reclaim_sweeps_total", "state_reclaim_keys_kept_total",
            "state_reclaim_keys_freed_total", "mesh_steps_total",
            "mesh_exchange_rounds_total", "mesh_inserted_rows_total",
            "mesh_stepped_rows_total")},
        "reclaims": reclaims,
        "capacity": run.query.operator_capacity(
            run.operator, run.config["query"]),
        "readers": readers}))


if __name__ == "__main__":
    main(sys.argv[1], int(sys.argv[2]))
