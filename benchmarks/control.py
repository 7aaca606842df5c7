"""The control of the output check: the program with SUM(price) kept in
32 bits has to come out NOT correct.

    python -m benchmarks.control --workload <cell> --seeds 1,2,3 [--seconds S]
                                 [--rehearse]

The configuration states exact results. The tempting step below it is a
narrower accumulator: the program sizes SUM's accumulator after the input
column (``_register_aggs`` / ``_aggdefs``), so declaring ``price`` int32 in
the bid schema IS the program's own 32-bit path. Prices are drawn so that
a hot auction's revenue per window passes 2^31; the wrapped sums differ
from the int64 reference and ``rows_differ`` must be far above its limit
of 0. One process, the seeds one after the other (a chip belongs to one
process). Not part of a benchmark run; exit 0 means every control failed
the comparison, as it must.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys

import numpy as np

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    from benchmarks.harness.spec import load_spec

    spec = load_spec()
    cell = spec.cell(args.workload)
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        if cell.chips > 1:
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + f" --xla_force_host_platform_device_count={cell.chips}")
    import jax

    from benchmarks.harness.cell import run_cell
    from benchmarks.harness.device import device_block

    device = device_block()
    if not args.rehearse:
        if device["platform"] != "tpu" or device["count"] != cell.chips:
            print(f"benchmarks.control: needs {cell.chips} TPU chip(s), "
                  f"found {device}", file=sys.stderr)
            return 2
        from flink_tpu.utils.compile_cache import place_compile_cache

        place_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    query = spec.module("queries", cell.config["query"]["module"])
    sum32 = [(n, np.int32 if n == "price" else t)
             for n, t in query.SCHEMA_FIELDS]
    passed_by_mistake = []
    for seed in [int(s) for s in args.seeds.split(",") if s]:
        run = run_cell(spec, cell, seed=seed, seconds=args.seconds,
                       trace=False, rehearse=args.rehearse,
                       schema_fields=sum32)
        tally = next(c for c in run.checks if c["check"] == "_tally")
        print(json.dumps({"control": "sum32", "workload": cell.name,
                          "seed": seed, "device": device,
                          "correct": run.correct,
                          "rows_compared": tally["rows_compared"],
                          "rows_differ": tally["rows_differ"],
                          "limit": 0,
                          "other_checks_failed": [
                              c["check"] for c in run.checks
                              if c.get("ok") is False
                              and c["check"] != "rows_differ"]}),
              flush=True)
        if run.correct:
            passed_by_mistake.append(seed)
        del run
        gc.collect()
    print(json.dumps({"control_failed_the_check_on_every_seed":
                      not passed_by_mistake,
                      "seeds_that_passed": passed_by_mistake}), flush=True)
    return 1 if passed_by_mistake else 0


if __name__ == "__main__":
    sys.exit(main())
