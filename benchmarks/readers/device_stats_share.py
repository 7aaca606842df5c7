"""Reads one of the program's ``DEVICE_STATS`` counters as a share of
another, over the timed phase: 100 x the growth of ``params["part"]``
between the first timed batch and the end of the run, over the growth of
``params["whole"]``. The counters are the program's own (handed over
without a device sync, so the reading at the first timed batch may lack
the last batch or two before it; the end reading is exact). A program
that does not keep them, as every commit before the counters' PR, reads
nothing."""


def read(run, params):
    first = run.at_t0.get("device_stats") or {}
    last = run.at_end.get("device_stats") or {}
    part, whole = params["part"], params["whole"]
    if any(k not in s for k in (part, whole) for s in (first, last)):
        return None
    grown = last[whole] - first[whole]
    if grown <= 0:
        return None
    return 100.0 * (last[part] - first[part]) / grown
