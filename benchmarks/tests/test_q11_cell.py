"""The cell ``q11-sessions-saturated`` (PR 43) as files: the generator
whose BIDDER ids advance, the plain reference on a hand-made stream (the
``ts - last == gap`` case included), the configuration, the bytes model
of the two roofline shares, their place in BENCHMARK.json, and the
rehearsed cell end to end through ``run_cell`` with three ways of being
wrong. ``tests/test_nexmark_q11.py`` runs the same cases under tier-1."""

import copy

import numpy as np
import pytest

from benchmarks.harness import cell as cell_mod
from benchmarks.harness.cell import run_cell
from benchmarks.harness.session_bytes import session_fire_bytes, \
    session_step_bytes
from benchmarks.harness.spec import BENCH_DIR, load_module, load_spec

CELL, CONFIG = "q11-sessions-saturated", "nexmark-q11-sessions"
SEED = 3_000_000_019          # over 2^31, as the driver's are
NEW = ("session_step_ms", "session_step_roofline_share", "session_fire_ms",
       "session_fire_roofline_share", "session_fire_share",
       "session_host_sort_ms", "sessions_fired_per_fire",
       "session_fire_rounds", "session_unnamed_share")
DATA = dict(active_bidders=4000, new_bidders_per_bid=[1, 46],
            hot_share=0.75, hot_every_bidders=100, id_space=20_000,
            price_max=1 << 22, n_auctions=1000, layout_seed=24)
PREFILL = 4096


@pytest.fixture(scope="module")
def spec():
    return load_spec()


def _generator(seed=0, **over):
    return load_module(BENCH_DIR, "generators", "bids_sessions") \
        .make_generator({**DATA, **over}, PREFILL, seed)


# -- the generator ---------------------------------------------------------

def test_q11_an_event_is_a_pure_function_of_its_index():
    g = np.arange(PREFILL - 100, PREFILL + 20_000, dtype=np.int64)
    whole = _generator().columns(g)
    assert set(whole) == {"auction", "bidder", "price"}
    part = np.random.default_rng(1).permutation(g)[:5000]
    again = _generator().columns(part)
    for name, col in whole.items():
        assert (again[name] == col[part - g[0]]).all(), name


def test_q11_the_prefill_is_the_active_set_each_id_once():
    ids = _generator().columns(np.arange(PREFILL))["bidder"]
    assert sorted(ids[:4000].tolist()) == list(range(4000))
    assert ((ids[4000:] >= 0) & (ids[4000:] < 4000)).all()


def test_q11_three_bids_in_four_come_from_the_hot_bidder_of_the_moment():
    gen = _generator()
    bid = np.arange(0, 92_000, dtype=np.int64)
    ids = gen.columns(PREFILL + bid)["bidder"]
    last = gen.last_bidder(bid)
    # one new bidder per 46 bids, for ever
    assert last[0] == 3999 and last[-1] == 3999 + 91_999 // 46
    assert (np.diff(last[::46]) == 1).all()
    hot = last // 100 * 100 + 1
    assert (gen.hot_bidder(bid) == hot).all()
    is_hot = gen.is_hot(PREFILL + bid)
    assert 0.74 < is_hot.mean() < 0.76
    assert (ids[is_hot] == hot[is_hot]).all()
    # the hot id moves every 100 new ids (4,600 bids) and is a new one:
    # at most 99 ids behind the newest, or the one about to be born
    moves = np.flatnonzero(np.diff(hot))
    assert set(np.diff(moves).tolist()) == {4600}
    assert ((last - hot >= -1) & (last - hot < 100)).all()
    # so a hot bidder's bids are ONE burst of about 3,450
    per_hot = np.bincount(hot[is_hot] // 100)
    full = per_hot[per_hot > 0][1:-1]
    assert len(full) >= 15 and 3300 < full.mean() < 3600


def test_q11_cold_bids_lie_in_the_newest_active_bidders():
    gen = _generator()
    bid = np.arange(0, 92_000, dtype=np.int64)
    ids = gen.columns(PREFILL + bid)["bidder"]
    cold, last = ~gen.is_hot(PREFILL + bid), gen.last_bidder(bid)
    assert (ids[cold] <= last[cold]).all()
    assert (ids[cold] > last[cold] - DATA["active_bidders"]).all()
    # uniform over them: no id far more often than chance allows, and
    # the window moves (the last bids reach ids no early bid could)
    assert np.bincount(ids[cold]).max() < 30
    assert ids[cold][-2000:].max() > 3999 + 1900
    assert ids[cold][-2000:].min() >= 1800


def test_q11_an_id_past_the_reference_raises():
    with pytest.raises(ValueError, match="id_space"):
        _generator(id_space=4100).columns(
            np.arange(PREFILL + 40_000, PREFILL + 41_000))
    gen = _generator()
    ids = gen.columns(np.arange(PREFILL + 600_000))["bidder"]
    assert ids.max() < DATA["id_space"]


def test_q11_the_seed_permutes_whole_batches_inside_their_group():
    mod = load_module(BENCH_DIR, "generators", "bids_sessions")
    rows, n = 64, 21
    plain, shuffled = _generator(seed=5), _generator(seed=5)
    shuffled.shuffle_batches(PREFILL, n, rows)
    g = np.arange(PREFILL, PREFILL + n * rows, dtype=np.int64)
    src = shuffled._source_rows(g)
    assert sorted(src.tolist()) == g.tolist()
    batch_of = (src - PREFILL) // rows
    for b in range(n):
        took = batch_of[b * rows:(b + 1) * rows]
        assert len(set(took.tolist())) == 1            # whole batches
        assert took[0] // mod.GROUP == b // mod.GROUP  # inside the group
    assert (batch_of[::rows] != np.arange(n)).any()
    a, b = plain.columns(src), shuffled.columns(g)
    assert all((a[k] == b[k]).all() for k in a)


# -- the reference ---------------------------------------------------------

def _reference(gap=100, pane=50, ids=16):
    ref = load_module(BENCH_DIR, "queries", "q11_reference")
    seen = []
    return ref, ref.Q11Reference(
        ids, gap, pane, lambda end, w: seen.append(
            (end, sorted(zip(*(c.tolist() for c in w)))))), seen


def test_q11_the_reference_takes_nothing_from_the_program():
    src = open(f"{BENCH_DIR}/queries/q11_reference.py").read()
    assert "flink_tpu" not in src.split('"""', 2)[2]
    assert "import" in src and "benchmarks" not in src.split('"""', 2)[2]


def test_q11_the_reference_on_a_hand_made_stream():
    """gap 100. Bidder 3: 10, 60, 159 (one session: 99 apart), then 259
    (EXACTLY 100 after 159: a new session), 300. Bidder 5: 20, then 500.
    Bidder 7: one bid in a second batch."""
    ref, r, seen = _reference()
    r.feed(np.array([3, 5, 3, 3, 3]), np.array([10, 20, 60, 159, 259]))
    r.feed(np.array([3, 7, 5]), np.array([300, 310, 500]))
    assert not seen                      # sessions leave at close()
    r.close()
    assert seen == [
        (150, [(5, 20, 120, 1)]),        # pane [100, 150) holds end 120
        (300, [(3, 10, 259, 3)]),        # end 259 -> pane [250, 300)
        (450, [(3, 259, 400, 2), (7, 310, 410, 1)]),
        (650, [(5, 500, 600, 1)])]
    assert r.pane_events == {0: 2, 1: 1, 3: 1, 5: 1, 6: 2, 10: 1}
    assert ref.report_pane(np.array([0, 49, 50, 120]), 50).tolist() \
        == [50, 50, 100, 150]


@pytest.mark.parametrize("apart,sessions", [(99, 1), (100, 2), (101, 2)])
def test_q11_the_reference_splits_at_exactly_the_gap(apart, sessions):
    """In one batch and across two: ``ts - last == gap`` opens a new
    session (configs/nexmark-q11-sessions.json, assumed.session_boundary)."""
    for split in (False, True):
        _ref, r, seen = _reference()
        if split:
            r.feed(np.array([2]), np.array([1000]))
            r.feed(np.array([2]), np.array([1000 + apart]))
        else:
            r.feed(np.array([2, 2]), np.array([1000, 1000 + apart]))
        r.close()
        rows = [row for _end, w in seen for row in w]
        assert len(rows) == sessions
        if sessions == 1:
            assert rows == [(2, 1000, 1000 + apart + 100, 2)]


def test_q11_the_reference_refuses_a_stream_out_of_order_or_out_of_range():
    _ref, r, _seen = _reference()
    r.feed(np.array([1]), np.array([50]))
    with pytest.raises(ValueError, match="timestamp order"):
        r.feed(np.array([1]), np.array([49]))
    with pytest.raises(ValueError, match="bidder id"):
        r.feed(np.array([16]), np.array([60]))


def test_q11_the_comparison_counts_rows_only_one_side_has():
    ref, _r, _seen = _reference()
    want = (np.array([1, 2, 3]), np.array([0, 5, 9]),
            np.array([100, 150, 200]), np.array([4, 1, 2]))
    same = ref.check_window(want[0][::-1], want[1][::-1], want[2][::-1],
                            want[3][::-1], want)
    assert (same.rows, same.rows_differ, same.topk_wrong) == (3, 0, 0)
    changed = ref.check_window(want[0], want[1], want[2],
                               np.array([4, 1, 3]), want)
    assert (changed.rows, changed.rows_differ) == (3, 2)
    assert "(bidder, start, end, count)" in changed.detail
    dropped = ref.check_window(want[0][:2], want[1][:2], want[2][:2],
                               want[3][:2], want)
    assert (dropped.rows, dropped.rows_differ) == (2, 1)
    twice = ref.check_window(*(np.r_[c, c[:1]] for c in want), want)
    assert (twice.rows, twice.rows_differ) == (4, 1)


# -- the bytes model -------------------------------------------------------

def test_q11_the_bytes_model_counts_from_shapes_and_counts_alone():
    cells = [8, 8, 1, 8]
    # 2^18 bids of 16 B, 65,000 keys: a key read, one lane read + written
    assert session_step_bytes(1 << 18, 16, 65_000, 8, cells) \
        == (1 << 18) * 16 + 65_000 * (8 + 2 * 25)
    # __end__ and __open__ once, whole; a row and a reset a session
    assert session_fire_bytes(1 << 24, 4, 250_000, 8, 1, 32, cells) \
        == (1 << 24) * 4 * 9 + 250_000 * (32 + 25)
    assert session_fire_bytes(1 << 24, 4, 0, 8, 1, 32, cells) \
        == 603_979_776


# -- the files -------------------------------------------------------------

def test_q11_the_cell_is_one_chip_and_listed_where_the_issue_says(spec):
    cell = spec.cell(CELL)
    assert (cell.chips, cell.config_name, cell.traffic_name) \
        == (1, CONFIG, "bids-sessions")
    assert [m["name"] for m in cell.end_to_end] == ["events_per_s",
                                                    "setup_s"]
    assert set(NEW) <= {m["name"] for m in cell.per_layer}
    by_name = {m["name"]: m for m in spec.benchmark["per_layer"]}
    for name in NEW:
        m = by_name[name]
        assert CELL in m["workloads"] and m["moves"] == "events_per_s"
        body = spec.layer_metric(name)
        assert (body["layer"], body["unit"]) == (m["layer"], m["unit"]) \
            == ("session operator", m["unit"])
        assert spec.module("readers", body["reader"]).read
    # (no pin on WHERE in the lists they stand, nor on how many cells
    # there are: the next PR appends behind them)
    names = [m["name"] for m in spec.benchmark["per_layer"]]
    assert sorted(names.index(n) for n in NEW) == list(range(
        names.index(NEW[0]), names.index(NEW[0]) + len(NEW)))
    assert CONFIG in [c["name"] for c in spec.benchmark["configs"]]
    assert len(cell.why) <= 200
    chips = [w["chips"] for w in spec.benchmark["workloads"]]
    assert chips.count(4) <= len(chips) // 2
    assert cell.traffic["generator"] == "bids_sessions"
    assert cell.traffic["pacing"] == "unthrottled"
    assert cell.traffic["event_rate"] % 10_000 == 0


def test_q11_the_configuration_states_what_it_is_and_what_it_assumes(spec):
    entry = next(c for c in spec.benchmark["configs"] if c["name"] == CONFIG)
    cfg, q5 = spec.cell(CELL).config, spec.cell("q5-10m-saturated").config
    assert cfg["source"] == entry["source"] and len(entry["source"]) <= 200
    assert cfg["reduced"] == entry["reduced"] == q5["reduced"]
    assert set(cfg["reduced_notes"]) == set(cfg["reduced"])
    for key in ("deployment", "guarantees", "assumed", "rehearse"):
        assert cfg[key]
    assert "capacity unchanged" in cfg["guarantees"]["path"]
    assert {"active_bidders", "lanes", "capacity", "report_pane",
            "session_boundary", "key_retention", "generator_constants",
            "id_space"} <= set(cfg["assumed"])
    q, d = cfg["query"], cfg["data"]
    assert (q["module"], q["gap_ms"], q["capacity"], q["lanes"],
            q["report_pane_ms"]) == ("q11", 10_000, 1 << 24, 4, 2000)
    assert d["active_bidders"] == d["n_keys"] == 4_000_000
    assert (d["new_bidders_per_bid"], d["hot_every_bidders"],
            d["hot_share"], d["layout_seed"]) == ([1, 46], 100, 0.75, 24)
    assert (cfg["batch_rows"], cfg["timeout_s"]) == (q5["batch_rows"], 600)
    # every id a run of run_seconds can make fits the reference
    rows = 12 + 148
    assert d["id_space"] > d["active_bidders"] + rows * cfg[
        "batch_rows"] // 46 + 1
    # the prefill does not wait quiet_s a batch: the lead covers the gap
    q11 = load_module(BENCH_DIR, "queries", "q11")
    assert cfg["setup_lead_panes"] * q11.pane_ms(q) >= q["gap_ms"]
    assert cfg["prefill_panes"] * q11.pane_ms(q) >= q["gap_ms"]
    assert q11.window_panes(q) == 1 and q11.KEY_COLUMN == "bidder"


# -- the rehearsed cell, end to end ----------------------------------------

def _run(spec, seed=SEED, seconds=3.0):
    return run_cell(spec, spec.cell(CELL), seed=seed, seconds=seconds,
                    trace=False, rehearse=True)


def _check(run, name):
    return next(c for c in run.checks if c.get("check") == name)


def _table(rows):
    t = np.stack([rows[c] for c in ("bidder", "session_start",
                                    "session_end", "bid_count",
                                    "window_start", "window_end")], axis=1)
    return t[np.lexsort(t.T[::-1])]


@pytest.fixture(scope="module")
def sound(spec):
    from flink_tpu.metrics import DEVICE_STATS

    before = DEVICE_STATS.session_counts
    run = _run(spec)
    after = DEVICE_STATS.session_counts
    return run, {k: after[k] - before[k] for k in after}


def test_q11_from_the_real_benchmark_directory_equals_its_reference(sound):
    run, grew = sound
    assert run.query.__file__ == f"{BENCH_DIR}/queries/q11.py"
    assert type(run.operator).__name__ == "DeviceSessionWindowOperator"
    assert run.correct and run.failed == 0 and run.attempted > 0, [
        c for c in run.checks if not c.get("ok", True)]
    assert all(c["ok"] for c in run.checks if "ok" in c)
    rows = run.sink.rows()
    assert set(rows) == {"bidder", "session_start", "session_end",
                         "bid_count", "window_start", "window_end"}
    tally = _check(run, "_tally")
    assert tally["windows_expected"] == tally["windows_emitted"] >= 20
    # EVERY session of the run is compared, and every bid is in one
    assert tally["rows_compared"] == len(rows["bidder"]) > 4000
    assert int(rows["bid_count"].sum()) \
        == run.schedule.n_batches * run.schedule.batch_rows
    q = run.config["query"]
    assert (rows["session_end"] - rows["session_start"]
            >= q["gap_ms"]).all()
    # the prefill's sessions and the end-of-input flush's are there
    last_ts = run.schedule.row_ts(run.schedule.n_batches - 1, -1)
    assert (rows["session_start"] < run.schedule.phase(
        "warm").start_ms).sum() >= 4000
    assert (rows["session_end"] > last_ts).sum() > 100
    # a hot bidder is one long session
    assert rows["bid_count"].max() > 1000
    # the operator counted what it did
    assert grew["session_fired_total"] == grew[
        "session_rows_drained_total"] == len(rows["bidder"])
    assert grew["session_fires_total"] >= 5
    assert grew["session_fire_rounds_total"] >= grew["session_fires_total"]
    assert grew["session_lanes_allocated_total"] == len(rows["bidder"])
    assert grew["session_lane_overflow_total"] == 0
    assert grew["session_settled_in_batch_total"] == 0   # in order


def test_q11_the_report_pane_is_a_function_of_the_data_alone(spec, sound):
    """The pane a session is reported in is arithmetic on its own end;
    a job whose fires fall elsewhere (another cadence, another seed's
    batch order would change the sessions themselves) emits the very
    same rows under the very same panes."""
    run = sound[0]
    rows, pane = run.sink.rows(), run.config["query"]["report_pane_ms"]
    assert (rows["window_end"]
            == rows["session_end"] // pane * pane + pane).all()
    assert (rows["window_start"] == rows["window_end"] - pane).all()
    from flink_tpu.runtime.operators import device_session as ds

    init = ds.DeviceSessionWindowOperator.__init__

    def other_cadence(self, *a, **kw):
        init(self, *a, **{**kw, "fire_interval_ms": 37, "fire_rows": 500})

    ds.DeviceSessionWindowOperator.__init__ = other_cadence
    try:
        again = _run(spec)
    finally:
        ds.DeviceSessionWindowOperator.__init__ = init
    assert again.correct
    assert again.operator._fire_interval == 37
    assert (_table(again.sink.rows()) == _table(rows)).all()
    stamps = sorted(again.sink.window_stamps().items())
    assert stamps != sorted(run.sink.window_stamps().items())


def _changed(rows):
    i = int(np.argmax(rows["bid_count"] > 1))
    rows["bid_count"] = rows["bid_count"].copy()
    rows["bid_count"][i] += 1
    return rows


def _split(rows):
    """One session of several bids emitted as two that abut."""
    i = int(np.argmax(rows["bid_count"] > 1))
    mid = int(rows["session_start"][i] + rows["session_end"][i]) // 2
    out = {k: np.r_[v, v[i:i + 1]] for k, v in rows.items()}
    out["session_end"][i] = mid
    out["bid_count"][i] -= 1
    out["session_start"][-1] = mid
    out["bid_count"][-1] = 1
    return out


def _dropped(rows):
    return {k: v[1:] for k, v in rows.items()}


@pytest.mark.parametrize("wrong,differ", [(_changed, 2), (_split, 3),
                                          (_dropped, 1)])
def test_q11_a_wrong_row_makes_it_not_correct(sound, wrong, differ):
    run = copy.copy(sound[0])
    run.checks, run.sink = [], copy.copy(run.sink)
    rows = wrong({k: v.copy() for k, v in sound[0].sink.rows().items()})
    run.sink.rows = lambda: rows
    cell_mod._verify(run)
    assert not run.correct
    tally = _check(run, "_tally")
    assert tally["rows_differ"] == differ
    assert tally["windows_missing"] == 0 and tally["bounds_wrong"] == 0
    assert not _check(run, "rows_differ")["ok"]
    assert sound[0].correct                  # the sound run is untouched
