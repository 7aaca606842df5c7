"""Reads a list of per-fire samples the window operator keeps (its
``fire_latencies_ms``: dispatch of a fire to its rows on the host). The
operator fires every window from the stream's first pane on, once, in
order, so sample ``i`` belongs to the window ending at pane ``i + 1``; the
value is a percentile over the windows that end in the timed phase."""

from benchmarks.harness.latency import nearest_rank


def read(run, params):
    samples = list(getattr(run.operator, params["attribute"], ()))
    pane = run.query.pane_ms(run.config["query"])
    first_pane = run.schedule.phases[0].start_ms // pane
    timed = [samples[end // pane - first_pane - 1]
             for end in run.schedule.windows_ending_in(
                 run.schedule.phase("timed"), pane)
             if 0 <= end // pane - first_pane - 1 < len(samples)]
    if not timed:
        return None
    return nearest_rank(timed, float(params.get("percentile", 50)))
