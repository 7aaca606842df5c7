"""Run one cell several times, one process per run, and summarise.

    python benchmarks/tools/repeat.py --workload <cell> --seeds 11,12,13 \
        [--seconds S] [--trace 0|1] [--tag NAME] [--keep-trace]

What a builder uses on the chip to take a set of runs in one call: the
parent never touches JAX (a chip belongs to one process), each run is the
benchmark's own command in a child, started only after the last has
exited. Every run's stdout goes to ``chiprun_out/<tag>.<seed>.log``, the
result lines to ``chiprun_out/<tag>.jsonl``; the summary printed at the
end gives, per metric, the values, the median and the spread (distance
between the quartiles of ``statistics.quantiles(values, n=4)`` as a share
of the median).
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def spread(values: list[float]) -> float:
    if len(values) < 2:
        return float("nan")
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated whole numbers, one run each")
    ap.add_argument("--seconds", default=None)
    ap.add_argument("--trace", default="0")
    ap.add_argument("--tag", default=None)
    ap.add_argument("--keep-trace", action="store_true")
    args = ap.parse_args(argv)
    tag = args.tag or f"{args.workload}.t{args.trace}"
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    results = []
    for seed in [s for s in args.seeds.split(",") if s]:
        cmd = [sys.executable, "-m", "benchmarks.run", "--workload",
               args.workload, "--seed", seed, "--trace", args.trace]
        if args.seconds is not None:
            cmd += ["--seconds", args.seconds]
        if args.keep_trace:
            cmd += ["--keep-trace",
                    os.path.join(out_dir, f"{tag}.{seed}.trace.json")]
        t = time.time()
        proc = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True)
        wall = time.time() - t
        with open(os.path.join(out_dir, f"{tag}.{seed}.log"), "w",
                  encoding="utf-8") as f:
            f.write(proc.stdout)
            f.write("\n--- stderr ---\n")
            f.write(proc.stderr[-20000:])
        line = None
        if proc.returncode == 0 and proc.stdout.strip():
            try:
                line = json.loads(proc.stdout.strip().splitlines()[-1])
            except json.JSONDecodeError:
                line = None
        rec = {"seed": int(seed), "rc": proc.returncode,
               "wall_s": round(wall, 1), "result": line}
        results.append(rec)
        with open(os.path.join(out_dir, f"{tag}.jsonl"), "a",
                  encoding="utf-8") as f:
            f.write(json.dumps(rec) + "\n")
        short = {k: v["value"] for k, v in (line or {}).get(
            "metrics", {}).items()}
        print(json.dumps({"seed": int(seed), "rc": proc.returncode,
                          "wall_s": round(wall, 1),
                          "correct": (line or {}).get("correct"),
                          "metrics": short,
                          "device": {k: v for k, v in (line or {}).get(
                              "device", {}).items()
                              if k in ("busy_s", "window_s",
                                       "memory_peak_bytes")}}),
              flush=True)
        if proc.returncode != 0:
            print(proc.stderr[-3000:], flush=True)
    names = sorted({k for r in results if r["result"]
                    for k in r["result"]["metrics"]})
    for name in names:
        vals = [r["result"]["metrics"][name]["value"] for r in results
                if r["result"] and name in r["result"]["metrics"]]
        print(json.dumps({"metric": name, "n": len(vals),
                          "median": statistics.median(vals),
                          "spread_iqr": spread(vals),
                          "min": min(vals), "max": max(vals)}), flush=True)
    bad = [r["seed"] for r in results
           if r["rc"] != 0 or not (r["result"] or {}).get("correct")]
    print(json.dumps({"runs": len(results), "not_correct_or_failed": bad}),
          flush=True)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
