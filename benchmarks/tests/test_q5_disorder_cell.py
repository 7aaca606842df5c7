"""The cell ``q5-10m-disorder-saturated`` (PR 51) as files: the lag
function that defines the disorder, the arrival-order reference against a
per-record dictionary, the configuration, its place in BENCHMARK.json
(membership only: the next PR appends behind it), and the rehearsed cell
end to end through ``run_cell`` from the REAL ``benchmarks/`` directory,
sound and wrong in four ways, one of them the watermark with no holdback.
``tests/test_nexmark_q5_disorder.py`` runs the same cases under tier-1."""

import copy

import numpy as np
import pytest

from benchmarks.harness import cell as cell_mod
from benchmarks.harness.cell import run_cell
from benchmarks.harness.spec import BENCH_DIR, Cell, load_module, load_spec

CELL, CONFIG, CONTROL = ("q5-10m-disorder-saturated",
                         "nexmark-q5-10m-disorder", "q5-10m-saturated")
SEED = 3_000_000_019          # over 2^31, as the driver's are
NEW = ("ring_sort_ms", "fold_sorted_batch_share", "fold_back_row_share")

ref_mod = load_module(BENCH_DIR, "queries", "q5_disorder_reference")


@pytest.fixture(scope="module")
def spec():
    return load_spec()


def _bids(n, seed=7, keys=200, start_ms=5000, rate=4000):
    rng = np.random.default_rng(seed)
    return ({"auction": rng.integers(0, keys, n),
             "bidder": rng.integers(0, 1000, n),
             "price": rng.integers(1, 1 << 40, n)},
            start_ms + (np.arange(n) * 1000) // rate)


# -- the lag function ------------------------------------------------------

def test_q5_disorder_one_row_in_ten_is_held_back_under_three_seconds():
    cols, ts = _bids(1 << 20, rate=260_000)
    lag = ref_mod.lag_ms(cols["auction"], cols["bidder"], cols["price"], ts,
                         0.1, 3000)
    assert lag.dtype == np.int64 and lag.min() == 0 and lag.max() < 3000
    # (one delayed row in 3,000 draws the lag 0)
    assert 0.095 < np.count_nonzero(lag) / len(lag) < 0.105
    held = lag[lag > 0]
    share = np.histogram(held, bins=6, range=(0, 3000))[0] / len(held)
    assert (np.abs(share - 1 / 6) < 0.01).all()         # uniform
    # which rows: no run of the stream is spared or struck
    per_block = (lag.reshape(64, -1) > 0).mean(axis=1)
    assert 0.08 < per_block.min() and per_block.max() < 0.12


def test_q5_disorder_the_lag_is_a_function_of_the_row_alone():
    cols, ts = _bids(1 << 14)
    args = (cols["auction"], cols["bidder"], cols["price"], ts)
    lag = ref_mod.lag_ms(*args, 0.1, 3000)
    part = np.random.default_rng(1).permutation(len(ts))[:3000]
    again = ref_mod.lag_ms(*(a[part] for a in args), 0.1, 3000)
    assert (again == lag[part]).all()
    # a narrower column of the same values (the sum32 control) changes
    # nothing, and the inputs are left as they were
    before = [a.copy() for a in args]
    assert (ref_mod.lag_ms(args[0], args[1], args[2].astype(np.int64),
                           ts.astype(np.int32), 0.1, 3000) == lag).all()
    assert all((a == b).all() for a, b in zip(args, before))
    # every column counts
    for i in range(4):
        moved = list(args)
        moved[i] = moved[i] + 1
        assert (ref_mod.lag_ms(*moved, 0.5, 3000) != ref_mod.lag_ms(
            *args, 0.5, 3000)).mean() > 0.4
    # the share and the range are the arguments'
    assert np.count_nonzero(ref_mod.lag_ms(*args, 0.0, 3000)) == 0
    assert ref_mod.lag_ms(*args, 1.0, 50).max() == 49


def test_q5_disorder_event_time_is_the_stamp_less_the_lag_never_negative():
    cols, ts = _bids(1 << 14, start_ms=0, rate=2000)
    et = ref_mod.event_time(cols, ts, 0.5, 3000)
    lag = ref_mod.lag_ms(cols["auction"], cols["bidder"], cols["price"],
                         ts, 0.5, 3000)
    assert et.min() == 0 and (et == np.maximum(ts - lag, 0)).all()
    assert (et < ts).any() and ((ts - lag) < 0).any()
    assert (np.diff(et) < 0).any()               # out of order it is


# -- the reference ---------------------------------------------------------

def test_q5_disorder_the_reference_takes_nothing_from_the_program():
    src = open(f"{BENCH_DIR}/queries/q5_disorder_reference.py").read()
    code = src.split('"""', 2)[2]
    assert "flink_tpu" not in code and "benchmarks" not in code
    assert "import numpy" in code


def _per_record(cols, ts, pane_ms, W, share, delay_max):
    """Every window of HOP W panes / 1 pane that holds each record."""
    et = ref_mod.event_time(cols, ts, share, delay_max)
    want = {}
    for a, p, t in zip(cols["auction"].tolist(), cols["price"].tolist(),
                       et.tolist()):
        first_end = (t // pane_ms + 1) * pane_ms
        for end in range(first_end, first_end + W * pane_ms, pane_ms):
            n, total = want.get((end, a), (0, 0))
            want[(end, a)] = (n + 1, total + p)
    return want


@pytest.mark.parametrize("W", [5, 1])
@pytest.mark.parametrize("batch", [256, 1000])
def test_q5_disorder_the_reference_equals_a_per_record_fold(W, batch):
    n, pane_ms, share, delay_max = 6000, 250, 0.3, 900
    cols, ts = _bids(n)
    got, ends = {}, []

    def on_window(end, bids, rev):
        ends.append(end)
        for a in np.flatnonzero(bids).tolist():
            got[(end, a)] = (int(bids[a]), int(rev[a]))

    ref = ref_mod.Q5DisorderReference(200, pane_ms, W, share, delay_max,
                                      on_window)
    for lo in range(0, n, batch):
        ref.feed({k: v[lo:lo + batch] for k, v in cols.items()},
                 ts[lo:lo + batch])
        # a window leaves only once the data's bound has settled it
        assert not ends or ends[-1] <= ts[min(lo + batch, n) - 1] \
            - delay_max + 1
    ref.close()
    assert got == _per_record(cols, ts, pane_ms, W, share, delay_max)
    et = ref_mod.event_time(cols, ts, share, delay_max)
    assert ends == list(range((int(et.min()) // pane_ms + 1) * pane_ms,
                              (int(et.max()) // pane_ms + W) * pane_ms + 1,
                              pane_ms))
    assert sum(ref.pane_events.values()) == n
    assert ref.pane_events == dict(zip(*(x.tolist() for x in np.unique(
        et // pane_ms, return_counts=True))))
    assert 0 < ref.back_rows < n * share


def test_q5_disorder_the_reference_refuses_batches_out_of_arrival_order():
    cols, ts = _bids(2000)
    ref = ref_mod.Q5DisorderReference(200, 250, 5, 0.3, 900,
                                      lambda *a: None)
    ref.feed({k: v[1000:] for k, v in cols.items()}, ts[1000:])
    with pytest.raises(ValueError, match="arrival order"):
        ref.feed({k: v[:1000] for k, v in cols.items()}, ts[:1000])


def test_q5_disorder_make_reference_refuses_a_file_that_disagrees(spec):
    cfg = spec.cell(CELL).config
    query = spec.module("queries", "q5_disorder")
    query.make_reference(cfg["query"], cfg["data"], lambda *a: None)
    with pytest.raises(ValueError, match="delay_max_ms"):
        query.make_reference({**cfg["query"], "delay_max_ms": 2000},
                             cfg["data"], lambda *a: None)


# -- the files -------------------------------------------------------------

def test_q5_disorder_the_cell_is_listed_where_the_issue_says(spec):
    cell, control = spec.cell(CELL), spec.cell(CONTROL)
    assert (cell.chips, cell.config_name, cell.traffic_name) \
        == (1, CONFIG, "bids-saturated") == (
            control.chips, CONFIG, control.traffic_name)
    assert cell.traffic == control.traffic
    assert [m["name"] for m in cell.end_to_end] == ["events_per_s",
                                                    "setup_s"]
    # every per-layer metric of its control, and three of its own
    mine = {m["name"] for m in cell.per_layer}
    assert mine == {m["name"] for m in control.per_layer} | set(NEW)
    by_name = {m["name"]: m for m in spec.benchmark["per_layer"]}
    for name in NEW:
        m = by_name[name]
        assert m["workloads"] == [CELL] and m["moves"] == "events_per_s"
        body = spec.layer_metric(name)
        assert (body["layer"], body["unit"]) == (m["layer"], m["unit"]) \
            == ("ingest step", m["unit"])
        assert spec.module("readers", body["reader"]).read
    assert CONFIG in [c["name"] for c in spec.benchmark["configs"]]
    assert len(cell.why) <= 200 and CONTROL in cell.why
    chips = [w["chips"] for w in spec.benchmark["workloads"]]
    assert chips.count(4) <= len(chips) // 2


def test_q5_disorder_the_configuration_is_its_control_s_but_for_the_order(
        spec):
    entry = next(c for c in spec.benchmark["configs"] if c["name"] == CONFIG)
    cfg, x = spec.cell(CELL).config, spec.cell(CONTROL).config
    assert cfg["source"] == entry["source"] and len(entry["source"]) <= 200
    for word in ("probDelayedEvent", "occasionalDelaySec", "WATERMARK FOR",
                 "INTERVAL '4' SECOND", "q5.sql"):
        assert word in entry["source"], word
    assert cfg["reduced"] == entry["reduced"] == [
        r for r in x["reduced"] if r != "watermark_delay"]
    assert set(cfg["reduced_notes"]) == set(cfg["reduced"])
    q, d = cfg["query"], cfg["data"]
    added = {"watermark_holdback_ms": 4000, "delayed_share": 0.1,
             "delay_max_ms": 3000}
    assert q == {**x["query"], "module": "q5_disorder", **added}
    assert d == {**x["data"], "delayed_share": 0.1, "delay_max_ms": 3000}
    # no event is later than the watermark
    assert d["delay_max_ms"] < q["watermark_holdback_ms"]
    assert "late_dropped 0" in cfg["guarantees"]["delivery"]
    for key in ("results", "path"):
        assert cfg["guarantees"][key] == x["guarantees"][key]
    assert cfg["guarantees"]["delivery"].startswith(
        x["guarantees"]["delivery"])
    # the same schedule as the control: the same batches, prefill included
    for key in ("batch_rows", "warm_s", "prefill_panes", "quiet_s", "state",
                "trace_s"):
        assert cfg[key] == x[key], key
    assert {"source_constants", "delay_distribution",
            "event_time_in_the_job", "clamp_and_prefill",
            "watermark_cadence", "ring", "prefill_panes",
            "setup_lead_panes", "quiet_s", "rehearse"} <= set(cfg["assumed"])
    # the ring holds the panes a holdback and a batch keep open
    mod = load_module(BENCH_DIR, "queries", "q5_disorder")
    pane, W = mod.pane_ms(q), mod.window_panes(q)
    batch_ms = cfg["batch_rows"] * 1000 // 260_000 + 1
    assert -(-q["watermark_holdback_ms"] // pane) + W \
        + -(-batch_ms // pane) < q["ring_size"]
    # the prefill may run ahead of the results by more than they trail
    assert cfg["setup_lead_panes"] * pane \
        >= q["watermark_holdback_ms"] + 2 * pane


# -- the rehearsed cell, end to end ----------------------------------------

def _run(spec, cell=None, seed=SEED, seconds=3.0):
    return run_cell(spec, cell or spec.cell(CELL), seed=seed,
                    seconds=seconds, trace=False, rehearse=True)


def _check(run, name):
    return next(c for c in run.checks if c.get("check") == name)


@pytest.fixture(scope="module")
def sound(spec):
    return _run(spec)


def test_q5_disorder_from_the_real_benchmark_directory_equals_its_reference(
        sound):
    run = sound
    assert run.query.__file__ == f"{BENCH_DIR}/queries/q5_disorder.py"
    assert type(run.operator).__name__ == "DeviceWindowAggOperator"
    assert run.correct and run.failed == 0 and run.attempted > 0, [
        c for c in run.checks if not c.get("ok", True)]
    assert all(c["ok"] for c in run.checks if "ok" in c)
    assert run.operator.late_dropped == 0
    tally = _check(run, "_tally")
    assert tally["windows_expected"] == tally["windows_emitted"] >= 20
    rows, q = run.sink.rows(), run.config["query"]
    assert tally["rows_compared"] == len(rows["auction"]) \
        == q["topk"] * tally["windows_emitted"]
    # the prefill's windows and the end-of-input flush's are there: the
    # last window ends W panes past the pane of the newest event
    pane, W = run.query.pane_ms(q), run.query.window_panes(q)
    last_ts = run.schedule.row_ts(run.schedule.n_batches - 1, -1)
    ends = np.unique(rows["window_end"])
    assert ends[0] == pane and ends[-1] == (last_ts // pane + W) * pane
    assert (np.diff(ends) == pane).all()
    # what the job was built from
    names = [op.name for task in run.job.tasks.values()
             for op in getattr(getattr(task, "chain", None),
                               "operators", ())]
    assert {"EventTime", "TimestampsWatermarks"} <= set(names)


def test_q5_disorder_the_program_counted_the_disorder(sound):
    run = sound
    t0, end = run.at_t0["device_stats"], run.at_end["device_stats"]
    timed = run.schedule.phase("timed").n_batches
    grew = {k: end[k] - t0[k] for k in (
        "fold_batches_total", "fold_ring_rows_total",
        "fold_sorted_batches_total", "fold_back_rows_total", "h2d_records")}
    # (the reading at t0 is the source thread's: the window task may
    # still hold the last warm batch or two)
    batches = grew["fold_batches_total"]
    assert timed <= batches == grew["fold_sorted_batches_total"] \
        <= timed + 2
    assert grew["h2d_records"] == batches * run.schedule.batch_rows
    # a rehearsal's batch is a fifth of a pane and the delay three panes
    assert 3.5 < grew["fold_ring_rows_total"] / batches <= 5
    assert 0.05 < grew["fold_back_rows_total"] / grew["h2d_records"] < 0.1
    behind = [v for k, v in run.at_end["metrics"].items()
              if k.endswith("TimestampsWatermarks.numRecordsOutOfOrder")]
    assert len(behind) == 1 and behind[0] > grew["fold_back_rows_total"]
    # the three new metrics read something (the span's only when traced)
    for name in NEW[1:]:
        body = load_spec().layer_metric(name)
        value = load_module(BENCH_DIR, "readers", body["reader"]).read(
            run, body["params"])
        assert value is not None and 0 < value <= 100, name
    assert load_module(BENCH_DIR, "readers", "device_stats_share").read(
        run, load_spec().layer_metric(NEW[1])["params"]) == 100.0


def test_q5_disorder_another_seed_sends_the_same_rows_elsewhere(spec,
                                                                 sound):
    again = _run(spec, seed=SEED + 1)
    assert again.correct
    a, b = sound.sink.rows(), again.sink.rows()
    timed = sound.schedule.phase("timed").start_ms
    assert (a["window_end"] == b["window_end"]).all()
    late = a["window_end"] > timed + sound.config["query"]["window_size_ms"]
    assert (a["bids"][late] != b["bids"][late]).any()


def _without_holdback(spec):
    cell = spec.cell(CELL)
    config = copy.deepcopy(cell.config)
    config["query"]["watermark_holdback_ms"] = 0
    return Cell(**{**cell.__dict__, "config": config})


def test_q5_disorder_with_no_holdback_it_is_not_correct(spec):
    """The control that must fail: the watermark on the newest event's
    heels. A held-back row then lands in panes whose first windows have
    fired; the fired windows lack it (``rows_differ``). ``late_dropped``
    stays 0 all the same under HOP: the operator, like Flink's, drops
    and counts a row only when EVERY window of its pane has fired, and
    with W = 4 panes and a delay under 3 one is always open."""
    run = _run(spec, _without_holdback(spec))
    assert not run.correct
    tally = _check(run, "_tally")
    assert tally["rows_differ"] > 100 and not _check(run,
                                                     "rows_differ")["ok"]
    assert tally["windows_missing"] == 0


def _changed(rows):
    rows["revenue"] = rows["revenue"].copy()
    rows["revenue"][len(rows["revenue"]) // 2] += 1
    return rows, "rows_differ"


def _tail_lost(rows):
    """What the parent of PR 51 does: the windows that only the
    end-of-input watermark fires never come."""
    ends = np.unique(rows["window_end"])
    keep = rows["window_end"] <= ends[-7]
    return {k: v[keep] for k, v in rows.items()}, "windows_missing"


def _unasked(rows):
    extra = {k: v[:1].copy() for k, v in rows.items()}
    extra["window_end"] += 1_000_000
    extra["window_start"] += 1_000_000
    return {k: np.r_[v, extra[k]] for k, v in rows.items()}, \
        "windows_unexpected"


@pytest.mark.parametrize("wrong", [_changed, _tail_lost, _unasked])
def test_q5_disorder_a_wrong_answer_makes_it_not_correct(sound, wrong):
    run = copy.copy(sound)
    run.checks, run.sink = [], copy.copy(run.sink)
    rows, check = wrong({k: v.copy() for k, v in sound.sink.rows().items()})
    run.sink.rows = lambda: rows
    cell_mod._verify(run)
    assert not run.correct and not _check(run, check)["ok"]
    assert [c["check"] for c in run.checks if c.get("ok") is False] \
        == [check]
    if wrong is _tail_lost:
        assert _check(run, check)["value"] == 6 and run.failed > 0
    assert sound.correct                     # the sound run is untouched
