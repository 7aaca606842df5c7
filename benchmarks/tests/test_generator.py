"""The bid generator: one function of (parameters, seed, index)."""

import numpy as np
import pytest

from benchmarks.generators.bids import BidGenerator

PARAMS = dict(n_keys=20_000, hot_keys=100, hot_share=0.5,
              price_max=1 << 22, n_bidders=1000, prefill_rows=20_480)


def _gen(seed, **over):
    return BidGenerator(seed=seed, **{**PARAMS, **over})


@pytest.mark.parametrize("seed", [0, 7, 2_147_483_659, 4_000_000_007])
def test_same_seed_same_stream_any_slice(seed):
    a, b = _gen(seed), _gen(seed)
    idx = np.arange(30_000, 34_096)
    ca, cb = a.columns(idx), b.columns(idx)
    for name in ("auction", "bidder", "price"):
        assert np.array_equal(ca[name], cb[name])
        assert ca[name].dtype == np.int64 and ca[name].shape == (4096,)
    # index-addressed: a slice equals the same rows of a larger slice
    part = a.columns(idx[1000:1100])
    assert np.array_equal(part["auction"], ca["auction"][1000:1100])


def test_every_seed_gets_the_same_batches_in_another_order():
    rows, n, first = 256, 12, 20_480
    a, b, plain = _gen(1), _gen(2), _gen(3)
    for g in (a, b):
        g.shuffle_batches(first, n, rows)
    idx = np.arange(first, first + n * rows)
    ca, cb, cp = (g.columns(idx) for g in (a, b, plain))

    def batches(cols):
        return {tuple(cols["auction"][i:i + rows]) + tuple(
            cols["price"][i:i + rows]) for i in range(0, n * rows, rows)}

    assert batches(ca) == batches(cb) == batches(cp)      # same multiset
    assert not np.array_equal(ca["auction"], cb["auction"])  # other order
    assert not np.array_equal(ca["auction"], cp["auction"])
    # rows outside the registered block, the hot set and the prefill are
    # the same for every seed
    after = np.arange(first + n * rows, first + n * rows + 512)
    assert np.array_equal(a.columns(after)["auction"],
                          plain.columns(after)["auction"])
    assert np.array_equal(a.hot_set, b.hot_set)
    pre = np.arange(0, 4096)
    assert np.array_equal(a.columns(pre)["auction"],
                          b.columns(pre)["auction"])
    assert not np.array_equal(a.hot_set, _gen(1, layout_seed=9).hot_set)
    # a slice of a shuffled stream equals the same rows of a larger slice
    part = a.columns(idx[300:900])
    assert np.array_equal(part["bidder"], ca["bidder"][300:900])


def test_hot_share_and_ranges():
    g = _gen(11)
    cols = g.columns(np.arange(100_000, 100_000 + (1 << 17)))
    hot = np.isin(cols["auction"], g.hot_set)
    # 1 bid in 2 to the hot set (cold bids hit it 100/20000 of the time)
    assert abs(hot.mean() - (0.5 + 0.5 * 100 / 20_000)) < 0.01
    assert len(g.hot_set) == 100 == len(np.unique(g.hot_set))
    assert cols["auction"].min() >= 0 and cols["auction"].max() < 20_000
    assert cols["price"].min() >= 1 and cols["price"].max() <= 1 << 22
    # all 100 hot keys are used, about evenly
    counts = np.bincount(np.searchsorted(g.hot_set,
                                         cols["auction"][hot]) % 100,
                         minlength=100)
    assert counts.min() > 0.7 * counts.mean()


def test_hot_share_zero_is_uniform():
    g = _gen(3, hot_share=0.0)
    cols = g.columns(np.arange(30_000, 30_000 + (1 << 16)))
    assert np.isin(cols["auction"], g.hot_set).mean() < 0.02


def test_prefill_holds_every_key_once():
    g = _gen(5)
    keys = g.columns(np.arange(0, 20_000))["auction"]
    assert np.array_equal(np.sort(keys), np.arange(20_000))
    # the padding rows of the last prefill batch repeat keys, never invent
    pad = g.columns(np.arange(20_000, 20_480))["auction"]
    assert pad.min() >= 0 and pad.max() < 20_000


def test_hot_revenue_per_pane_passes_int32():
    # the property the 32-bit control rests on, at the configuration's own
    # numbers: the program's fire merges panes in 64 bits, so the overflow
    # has to happen inside ONE pane's accumulator: 260 k events/s x 2 s,
    # half to 100 keys, prices up to 2^22
    from benchmarks.harness.spec import load_spec

    spec = load_spec()
    cell = spec.cell("q5-10m-steady")
    data, q = cell.config["data"], cell.config["query"]
    per_key = (cell.traffic["event_rate"] * q["window_slide_ms"] / 1000
               * data["hot_share"] / data["hot_keys"])
    assert per_key * (data["price_max"] / 2) > 1.5 * 2 ** 31


def test_rejects_a_key_count_the_prefill_stride_does_not_cover():
    with pytest.raises(ValueError):
        _gen(1, n_keys=7_368_787 * 2)
