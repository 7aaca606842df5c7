"""The control of Q7's output check: the program with the packed highest
bid kept in 32 bits has to come out NOT correct.

    python -m benchmarks.control_q7 --workload <cell> --seeds 1,2,3 [--seconds S]
                                    [--rehearse]

The configuration states exact results over a word of ``price_bits +
word_shift`` = 43 bits. The tempting step below it is a narrower
accumulator: the program sizes MAX's plane after the input column
(``_register_aggs``), so declaring the packing map's output column
``word`` int32 IS the program's own 32-bit path, and the query module
gives that column's type a hook for exactly this (``query.word_dtype``,
queries/q7.py; no configuration file sets it). A price above 2^11 then
wraps out of the word, the window's winner is some bid that did not
lose its top bits, and its unpacked (price, bidder) differ from the
int64 reference: ``rows_differ`` must be above its limit of 0, and no
other check may fail (a row that differs is counted as a row, not again
as a wrong winner: q7_reference.check_window). One process, the seeds
one after the other (a chip belongs to one process). Not part of a
benchmark run; exit 0 means every control failed the comparison, as it
must. ``benchmarks/control.py`` is Q5's (SUM in 32 bits).
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys

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
    if cell.config["query"]["module"] != "q7":
        print(f"benchmarks.control_q7: cell {cell.name!r} does not run Q7",
              file=sys.stderr)
        return 2
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    from benchmarks.harness.cell import run_cell
    from benchmarks.harness.device import device_block

    device = device_block()
    if not args.rehearse:
        if device["platform"] != "tpu" or device["count"] != cell.chips:
            print(f"benchmarks.control_q7: needs {cell.chips} TPU chip(s), "
                  f"found {device}", file=sys.stderr)
            return 2
        from flink_tpu.utils.compile_cache import place_compile_cache

        place_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    # the query module's hook: the packed word's column in 32 bits
    cell.config["query"]["word_dtype"] = "int32"
    passed_by_mistake = []
    for seed in [int(s) for s in args.seeds.split(",") if s]:
        run = run_cell(spec, cell, seed=seed, seconds=args.seconds,
                       trace=False, rehearse=args.rehearse)
        tally = next(c for c in run.checks if c["check"] == "_tally")
        print(json.dumps({"control": "word32", "workload": cell.name,
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
