"""Reads one of the program's ``DEVICE_STATS`` counters as a share of the
SUM of several, over the timed phase: what ``device_stats_share`` reads
where the whole is kept as its parts (``params["whole"]`` is a list of
counter names; ``params["part"]`` may be one of them). A program that
does not keep the counters, or a run in which the whole did not grow,
reads nothing."""


def read(run, params):
    first = run.at_t0.get("device_stats") or {}
    last = run.at_end.get("device_stats") or {}
    part, whole = params["part"], list(params["whole"])
    if any(k not in s for k in (part, *whole) for s in (first, last)):
        return None
    grown = sum(last[k] - first[k] for k in whole)
    if grown <= 0:
        return None
    return 100.0 * (last[part] - first[part]) / grown
