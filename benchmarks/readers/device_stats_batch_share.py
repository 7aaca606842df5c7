"""Reads how many batches a ``DEVICE_STATS`` counter of batches
(``params["part"]``) counted, as a share of the batches another counter
of the SAME hand-over saw, over the timed phase: 100 x the growth of
``part`` over the growth of ``params["rows"]`` / ``batch_rows``. The
probe's counters (rows probed, tail rows, wide batches) are one device
vector that the program hands over without a sync, a batch or two late;
a whole counted on the host (``fold_batches_total``) is exact at the
first timed batch while the part still lacks the last warm batches, and
their ratio then passes 100 (150 wide batches over 148 folded: PERF.md
section 6, PR 35). Rows over ``batch_rows`` lags as the part does. A
program that does not keep the counters reads nothing."""


def read(run, params):
    first = run.at_t0.get("device_stats") or {}
    last = run.at_end.get("device_stats") or {}
    part, rows = params["part"], params["rows"]
    if any(k not in s for k in (part, rows) for s in (first, last)):
        return None
    batches = (last[rows] - first[rows]) / run.schedule.batch_rows
    if batches <= 0:
        return None
    return 100.0 * (last[part] - first[part]) / batches
