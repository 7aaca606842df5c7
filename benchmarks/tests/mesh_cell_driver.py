"""One rehearsal of the four-chip cell through ``run_cell`` on four
virtual CPU devices, in a process of its own (the device count is fixed
when JAX starts, and the benchmark's other tests run on one device).

    python benchmarks/tests/mesh_cell_driver.py <mode> <seed>

``mode`` is ``sound``, ``altered`` (one emitted row of one window is one
unit off) or ``slice_lost`` (one device's slice of one timed block never
reaches the exchange). Prints one JSON object.
"""

import json
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=4")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

CELL = "q5-16m-mesh4-saturated"


def main(mode: str, seed: int) -> None:
    import jax
    import jax.numpy as jnp

    from benchmarks.harness.cell import run_cell
    from benchmarks.harness.spec import load_spec
    from flink_tpu.runtime.operators.mesh_window import \
        MeshWindowAggOperator as Op

    calls = {"n": 0}
    if mode == "altered":
        real_emit = Op._emit_rows

        def off_by_one(self, p_end, keys, host):
            calls["n"] += 1
            if calls["n"] == 7:
                host = {k: v.copy() for k, v in host.items()}
                host["revenue"][0] += 1
            return real_emit(self, p_end, keys, host)

        Op._emit_rows = off_by_one
    elif mode == "slice_lost":
        real_step = Op._step_block

        def lose_a_slice(self, dkeys, dcols, dpanes, dvalid):
            calls["n"] += 1
            if calls["n"] == 30:          # a timed block, device 2's slice
                dvalid = jnp.asarray(dvalid).at[2].set(False)
            return real_step(self, dkeys, dcols, dpanes, dvalid)

        Op._step_block = lose_a_slice
    elif mode != "sound":
        raise SystemExit(f"unknown mode {mode!r}")

    spec = load_spec()
    run = run_cell(spec, spec.cell(CELL), seed=seed, seconds=5.0,
                   trace=False, rehearse=True)
    first, last = run.at_t0["device_stats"], run.at_end["device_stats"]
    readers = {}
    for name in ("exchange_rounds_per_step", "mesh_upload_ms",
                 "exchange_collective_share"):
        params = spec.layer_metric(name)
        readers[name] = spec.module("readers", params["reader"]).read(
            run, params.get("params", {}))
    print(json.dumps({
        "correct": run.correct, "attempted": run.attempted,
        "failed": run.failed, "devices": len(jax.devices()),
        "checks": {c["check"]: c["value"] for c in run.checks
                   if "value" in c},
        "tally": next(c for c in run.checks if c["check"] == "_tally"),
        "batches": run.schedule.n_batches,
        "timed_batches": run.schedule.phase("timed").n_batches,
        "steps_timed": last["mesh_steps_total"] - first["mesh_steps_total"],
        "steps_job": last["mesh_steps_total"]
        - run.at_end["stats_before"]["mesh_steps_total"],
        "h2d_bytes_job": last["h2d_bytes"]
        - run.at_end["stats_before"]["h2d_bytes"],
        "capacity": run.query.operator_capacity(
            run.operator, run.config["query"]),
        "readers": readers}))


if __name__ == "__main__":
    main(sys.argv[1], int(sys.argv[2]))
