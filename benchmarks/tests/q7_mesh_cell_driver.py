"""One rehearsal of the four-chip Q7 cell through ``run_cell`` on four
virtual CPU devices, in a process of its own (the device count is fixed
when JAX starts, and the benchmark's other tests run on one device).

    python benchmarks/tests/q7_mesh_cell_driver.py <mode> <seed>

``mode`` is ``sound`` or ``other_bidder`` (the plain reference is fed
every bid with another bidder id: the job's rows are right and the
comparison must say they differ). Prints one JSON object.
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

CELL = "q7-16m-mesh4-saturated"
READ = ("mesh_fire_guarded_share", "mesh_fire_select_passes",
        "mesh_max_fold_roofline_share", "mesh_wide_select_roofline_share",
        "exchange_rounds_per_step", "mesh_fold_rows_per_step",
        "mesh_upload_ms")


def main(mode: str, seed: int) -> None:
    import jax
    import numpy as np

    from benchmarks.harness.cell import run_cell
    from benchmarks.harness.spec import load_spec
    from flink_tpu.metrics.tracing import TRACER
    from flink_tpu.ops.hash_table import EMPTY_KEY

    spec = load_spec()
    if mode == "other_bidder":
        module = spec.module

        def with_other_bidder(kind, name):
            found = module(kind, name)
            if (kind, name) != ("queries", "q7_mesh"):
                return found
            make = found.make_reference

            def make_reference(q, data, on_window):
                ref = make(q, data, on_window)
                feed = ref.feed
                ref.feed = lambda cols, ts: feed(
                    {**cols, "bidder": cols["bidder"] ^ 1}, ts)
                return ref

            found.make_reference = make_reference
            return found

        spec.module = with_other_bidder
    elif mode != "sound":
        raise SystemExit(f"unknown mode {mode!r}")

    TRACER.reset()
    run = run_cell(spec, spec.cell(CELL), seed=seed, seconds=5.0,
                   trace=False, rehearse=True)
    drains = [s.attributes for s in TRACER.retained_spans()
              if (s.scope, s.name) == ("window", "Drain")]
    first, last = run.at_t0["device_stats"], run.at_end["device_stats"]
    readers = {}
    for name in READ:
        body = spec.layer_metric(name)
        readers[name] = spec.module("readers", body["reader"]).read(
            run, body.get("params", {}))
    table = np.asarray(run.operator._state.table)
    print(json.dumps({
        "correct": run.correct, "attempted": run.attempted,
        "failed": run.failed, "devices": len(jax.devices()),
        "query_file": run.query.__file__,
        "checks": {c["check"]: c["value"] for c in run.checks
                   if "value" in c},
        "tally": next(c for c in run.checks if c["check"] == "_tally"),
        "batches": run.schedule.n_batches,
        "steps_job": last["mesh_steps_total"]
        - run.at_end["stats_before"]["mesh_steps_total"],
        "guarded_timed": last["fire_select_guarded_total"]
        - first["fire_select_guarded_total"],
        "value_bits": sorted({d["value_bits"] for d in drains}),
        "select_passes": sorted({d["select_passes"] for d in drains}),
        "occupied": (table != np.int64(EMPTY_KEY)).sum(axis=1).tolist(),
        "capacity": run.query.operator_capacity(
            run.operator, run.config["query"]),
        "readers": readers}))


if __name__ == "__main__":
    main(sys.argv[1], int(sys.argv[2]))
