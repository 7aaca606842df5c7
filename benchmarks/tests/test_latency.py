"""Latency arithmetic and nearest-rank percentiles on a hand-made
schedule."""

from types import SimpleNamespace

import pytest

from benchmarks.harness import latency
from benchmarks.harness.latency import nearest_rank, window_latencies_ms
from benchmarks.harness.schedule import build_schedule


@pytest.mark.parametrize("values,pct,want", [
    ([10, 20, 30, 40], 50, 20),          # ceil(0.5 * 4) = 2nd
    ([10, 20, 30, 40], 90, 40),          # ceil(3.6) = 4th
    ([10, 20, 30, 40, 50], 50, 30),
    ([7], 90, 7),
    (list(range(1, 26)), 90, 23),        # 25 windows: ceil(22.5) = 23rd
    (list(range(1, 26)), 100, 25),
])
def test_nearest_rank_is_a_measured_value(values, pct, want):
    assert nearest_rank(values, pct) == want


@pytest.mark.parametrize("values,pct", [([], 50), ([1], 0), ([1], 101)])
def test_nearest_rank_rejects(values, pct):
    with pytest.raises(ValueError):
        nearest_rank(values, pct)


def test_window_latency_is_stamp_minus_due_time():
    # origin at host clock 100.0 s; the window ending at 4,000 ms of event
    # time has its last event due at 104.0; its rows reached the sink at
    # 104.75 -> 750 ms; the window's length plays no part
    stamps = {4000: 104.75, 6000: 107.5}
    got = window_latencies_ms(100.0, stamps, [4000, 6000])
    assert got == pytest.approx([750.0, 1500.0])


def test_a_window_that_never_reached_the_sink_is_an_error():
    with pytest.raises(KeyError):
        window_latencies_ms(0.0, {2000: 2.5}, [2000, 4000])


def _schedule(pacing="scheduled"):
    return build_schedule(n_keys=1000, batch_rows=100, prefill_panes=4,
                          pane_ms=1000, warm_s=2.0, event_rate=200,
                          pacing=pacing, seconds=10.0)


def test_schedule_phases_batches_and_event_time():
    s = _schedule()
    pre, warm, timed = (s.phase(n) for n in ("prefill", "warm", "timed"))
    assert (pre.n_batches, warm.n_batches, timed.n_batches) == (10, 4, 20)
    # prefill: 1000 rows squeezed into 4 panes of 1 s -> 250 events/s
    assert pre.rate == 250 and not pre.paced
    assert warm.start_ms == 4000 and warm.paced
    assert timed.start_ms == 6000 and timed.paced
    # event i of a phase: start + i * 1000 // rate
    ts = s.batch_ts(timed.first_batch)
    assert ts[0] == 6000 and ts[1] == 6005 and ts[-1] == 6000 + 99 * 5
    # a batch is due when its last row is
    assert s.due_s(timed.first_batch) == pytest.approx(6.495)
    assert s.batch_index(3).tolist() == list(range(300, 400))
    # the scalar form the reader polls equals the vector's ends
    for b in (0, 9, 10, 13, 14, 33):
        ts = s.batch_ts(b)
        assert (s.row_ts(b, 0), s.row_ts(b, -1)) == (ts[0], ts[-1])


def test_windows_ending_in_the_timed_phase():
    s = _schedule()
    # timed events span [6000, 15995]: window ends 7000 .. 15000
    ends = s.windows_ending_in(s.phase("timed"), 1000)
    assert ends == list(range(7000, 16000, 1000))
    assert s.windows_ending_in(s.phase("warm"), 1000) == [5000, 6000]


def test_unthrottled_timed_phase_is_not_paced():
    s = _schedule("unthrottled")
    assert not s.phase("timed").paced and s.phase("warm").paced
    with pytest.raises(ValueError):
        _schedule("sometimes")


def test_closing_batch_holds_the_last_event_before_the_window_end():
    s = _schedule()
    timed = s.phase("timed")
    # timed batches hold 100 events 5 ms apart: batch k spans event time
    # [6000 + 500 k, 6495 + 500 k]
    for end in s.windows_ending_in(timed, 1000) + [6001, 6500, 6501]:
        b = s.closing_batch(end)
        assert s.row_ts(b, 0) < end
        assert b + 1 == s.n_batches or s.row_ts(b + 1, 0) >= end
    assert s.closing_batch(7000) == timed.first_batch + 1
    assert s.closing_batch(6500) == timed.first_batch
    assert s.closing_batch(6501) == timed.first_batch + 1
    # a window that ends with the warm phase is closed by a warm batch
    assert s.closing_batch(6000) == timed.first_batch - 1


def _fake_run(pacing, stamps):
    s = _schedule(pacing)
    return SimpleNamespace(
        traffic={"pacing": pacing}, schedule=s, origin_s=100.0,
        config={"query": {}}, query=SimpleNamespace(pane_ms=lambda q: 1000),
        sink=SimpleNamespace(window_stamps=lambda: stamps))


def test_source_to_sink_leaves_out_the_wait_for_the_batch_to_fill():
    s = _schedule()
    ends = s.windows_ending_in(s.phase("timed"), 1000)
    # every window's rows reach the sink 300 ms after its closing batch
    # was handed over (due 495 ms after the batch's first event)
    stamps = {e: 100.0 + s.due_s(s.closing_batch(e)) + 0.3 for e in ends}
    run = _fake_run("scheduled", stamps)
    assert latency.timed_source_to_sink_ms(run) == pytest.approx(
        [300.0] * len(ends))
    # the window ending at 7,000 is closed by the batch due at 6,995
    # (wait -5: the batch's last event, ts 6,995, is the window's last);
    # the event-time latency holds that wait
    assert latency.timed_event_time_latencies_ms(run) == pytest.approx(
        [295.0] * len(ends))


def test_an_unthrottled_run_has_no_latency_sample():
    run = _fake_run("unthrottled", {})
    assert latency.timed_source_to_sink_ms(run) is None
    assert latency.timed_event_time_latencies_ms(run) is None
