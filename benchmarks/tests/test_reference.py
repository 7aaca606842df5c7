"""The rolling Q5 reference against chip_smoke's whole-run reference, and
what the row check rejects."""

import sys

import numpy as np
import pytest

from benchmarks.harness.spec import BENCH_DIR, REPO_ROOT, load_module

q5 = load_module(BENCH_DIR, "queries", "q5_reference")


def _windows(n_keys, pane_ms, W, batches, sum_dtype=np.int64):
    out = {}
    ref = q5.Q5Reference(
        n_keys, pane_ms, W,
        lambda end, bids, rev: out.__setitem__(end, (bids.copy(),
                                                     rev.copy())),
        sum_dtype=sum_dtype)
    for auction, price, ts in batches:
        ref.feed(auction, price, ts)
    ref.close()
    return out


def test_rolling_reference_equals_chip_smoke_reference():
    sys.path.insert(0, REPO_ROOT)
    import chip_smoke

    n_keys, n_events, batch, seed = 500, 1 << 13, 1 << 10, 3
    want = chip_smoke.q5_reference(n_keys, n_events, batch, seed)
    n_panes = chip_smoke._n_panes(n_events, batch)
    gen = chip_smoke._make_gen(n_keys, n_events,
                               n_panes * chip_smoke.PANE_MS, seed)
    batches = []
    for lo in range(0, n_events, batch):
        cols = gen(np.arange(lo, lo + batch, dtype=np.int64))
        batches.append((cols["auction"], cols["price"], cols["ts"]))
    got = _windows(n_keys, chip_smoke.PANE_MS, chip_smoke.WINDOW_PANES,
                   batches)
    assert sorted(got) == sorted(want)
    for end in want:
        assert np.array_equal(got[end][0], want[end][0]), end
        assert np.array_equal(got[end][1], want[end][1]), end


def test_rolling_reference_over_an_event_time_gap():
    # panes 0 and 1 hold data, panes 2..5 are empty, pane 6 holds data;
    # W = 3: the windows that end inside the gap still come out
    a = np.array([1, 1, 2]), np.array([10, 20, 30]), np.array([0, 500, 1500])
    b = np.array([2]), np.array([5]), np.array([6100])
    got = _windows(4, 1000, 3, [a, b])
    assert sorted(got) == [1000, 2000, 3000, 4000, 5000, 6000, 7000, 8000,
                           9000]
    assert got[1000][0].tolist() == [0, 2, 0, 0]
    assert got[2000][1].tolist() == [0, 30, 30, 0]
    assert got[4000][0].tolist() == [0, 0, 1, 0]     # only pane 1 left
    assert got[5000][0].sum() == 0 and got[6000][0].sum() == 0
    assert got[7000][0].tolist() == [0, 0, 1, 0] and got[9000][1][2] == 5


def _one_window():
    rng = np.random.default_rng(0)
    ref_bids = rng.integers(0, 50, 2000)
    ref_rev = ref_bids * 1000 + rng.integers(0, 999, 2000)
    k = 100
    order = np.argsort(-ref_bids, kind="stable")[:k]
    return ref_bids, ref_rev, k, order


def test_check_window_accepts_a_correct_top_k_and_free_ties():
    ref_bids, ref_rev, k, order = _one_window()
    v = q5.check_window(order, ref_bids[order], ref_rev[order], ref_bids,
                        ref_rev, k)
    assert (v.rows, v.rows_differ, v.topk_wrong) == (k, 0, 0)
    # swap a key tied at the threshold for another tied key: still right
    thr = ref_bids[order].min()
    tied_out = np.setdiff1d(np.flatnonzero(ref_bids == thr), order)
    alt = order.copy()
    alt[np.flatnonzero(ref_bids[order] == thr)[0]] = tied_out[0]
    v = q5.check_window(alt, ref_bids[alt], ref_rev[alt], ref_bids,
                        ref_rev, k)
    assert (v.rows_differ, v.topk_wrong) == (0, 0)


@pytest.mark.parametrize("column,delta", [("bids", 1), ("bids", -1),
                                          ("revenue", 1), ("revenue", -1)])
def test_check_window_rejects_a_row_off_by_one(column, delta):
    ref_bids, ref_rev, k, order = _one_window()
    bids, rev = ref_bids[order].copy(), ref_rev[order].copy()
    (bids if column == "bids" else rev)[17] += delta
    v = q5.check_window(order, bids, rev, ref_bids, ref_rev, k)
    assert v.rows_differ == 1


def test_check_window_rejects_wrong_key_sets():
    ref_bids, ref_rev, k, order = _one_window()
    # a key below the threshold in place of one above it
    low = int(np.argmin(ref_bids))
    bad = order.copy()
    bad[0] = low
    v = q5.check_window(bad, ref_bids[bad], ref_rev[bad], ref_bids, ref_rev,
                        k)
    assert v.rows_differ == 0 and v.topk_wrong == 1
    # a duplicate key, a missing row
    dup = order.copy()
    dup[1] = dup[0]
    assert q5.check_window(dup, ref_bids[dup], ref_rev[dup], ref_bids,
                           ref_rev, k).rows_differ >= 1
    assert q5.check_window(order[:-1], ref_bids[order[:-1]],
                           ref_rev[order[:-1]], ref_bids, ref_rev,
                           k).topk_wrong == 1


def test_sum_kept_in_32_bits_fails_the_comparison():
    """The control at test size: the reference computed with SUM(price) in
    int32, put in the program's place, differs from the int64 reference
    wherever a hot key's revenue passes 2^31."""
    n = 4000
    auction = np.zeros(n, np.int64)            # one hot key
    auction[::4] = np.arange(1, 1001)          # and some cold ones
    price = np.full(n, 1 << 20, np.int64)
    ts = np.arange(n) // 4                      # 1 s of event time
    full = _windows(1001, 1000, 2, [(auction, price, ts)])
    narrow = _windows(1001, 1000, 2, [(auction, price, ts)],
                      sum_dtype=np.int32)
    bids, rev = full[1000]
    assert rev[0] == 3000 * (1 << 20) > 2 ** 31
    order = np.argsort(-bids, kind="stable")[:10]
    ok = q5.check_window(order, bids[order], rev[order], bids, rev, 10)
    assert ok.rows_differ == 0
    wrapped = narrow[1000][1].astype(np.int64)
    v = q5.check_window(order, bids[order], wrapped[order], bids, rev, 10)
    assert v.rows_differ >= 1
