"""Radix-select masked top-k vs a numpy oracle (exactness incl. ties,
validity padding, every accumulator dtype)."""

import numpy as np
import pytest

import jax

jax.config.update("jax_enable_x64", True)  # before any array construction:
# int64/float64 test inputs must not downcast (order-independent runs)

import jax.numpy as jnp  # noqa: E402

from flink_tpu.ops.topk import masked_topk_radix, masked_topk_sort  # noqa: E402


def _oracle(values: np.ndarray, valid: np.ndarray, k: int):
    iv = np.flatnonzero(valid)
    order = iv[np.argsort(-values[iv].astype(np.float64), kind="stable")]
    # ties at the boundary make the selected SET ambiguous only among
    # equal values; compare the multiset of values instead of indices
    return np.sort(values[order[:k]])[::-1]


@pytest.mark.parametrize("dtype", [np.int64, np.int32, np.float32,
                                   np.float64])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_matches_oracle(dtype, seed):
    rng = np.random.default_rng(seed)
    n, k = 4096, 100
    if np.issubdtype(dtype, np.integer):
        vals = rng.integers(-1_000_000, 1_000_000, n).astype(dtype)
    else:
        vals = (rng.standard_normal(n) * 1e6).astype(dtype)
    valid = rng.random(n) < 0.7
    got_v, got_i, got_ok = map(np.asarray, masked_topk_radix(
        jnp.asarray(vals), jnp.asarray(valid), k))
    exp = _oracle(vals, valid, k)
    assert got_ok[:len(exp)].all() and not got_ok[len(exp):].any()
    np.testing.assert_array_equal(got_v[: len(exp)], exp)
    # returned indices are valid and carry their own values
    sel = got_i[got_ok]
    assert valid[sel].all()
    np.testing.assert_array_equal(vals[sel], got_v[got_ok])
    assert len(np.unique(sel)) == len(sel)


def test_heavy_ties():
    n, k = 1000, 64
    vals = np.zeros(n, np.int64)
    vals[:10] = 5                     # 10 strict
    vals[10:500] = 3                  # 490 ties at the boundary
    valid = np.ones(n, bool)
    v, i, ok = map(np.asarray, masked_topk_radix(
        jnp.asarray(vals), jnp.asarray(valid), k))
    assert ok.all()
    assert (v[:10] == 5).all() and (v[10:] == 3).all()
    assert len(np.unique(i)) == k
    np.testing.assert_array_equal(vals[i], v)


def test_fewer_valid_than_k():
    vals = np.arange(50, dtype=np.int64)
    valid = vals % 10 == 0            # 5 valid
    v, i, ok = map(np.asarray, masked_topk_radix(
        jnp.asarray(vals), jnp.asarray(valid), 16))
    assert ok[:5].all() and not ok[5:].any()
    np.testing.assert_array_equal(v[:5], [40, 30, 20, 10, 0])


def test_all_invalid():
    vals = np.arange(32, dtype=np.int64)
    v, i, ok = map(np.asarray, masked_topk_radix(
        jnp.asarray(vals), jnp.zeros(32, bool), 8))
    assert not ok.any()


def test_negative_and_extreme():
    vals = np.array([np.iinfo(np.int64).min, -5, 0, 7,
                     np.iinfo(np.int64).max], np.int64)
    v, i, ok = map(np.asarray, masked_topk_radix(
        jnp.asarray(vals), jnp.ones(5, bool), 3))
    np.testing.assert_array_equal(v, [np.iinfo(np.int64).max, 7, 0])
    assert ok.all()


@pytest.mark.parametrize("bits", [16, 32, 48])
def test_value_bits_shortcut(bits):
    rng = np.random.default_rng(bits)
    n, k = 4096, 64
    vals = rng.integers(0, 1 << (bits - 1), n).astype(np.int64)
    valid = rng.random(n) < 0.8
    v, i, ok = map(np.asarray, masked_topk_radix(
        jnp.asarray(vals), jnp.asarray(valid), k, value_bits=bits))
    exp = _oracle(vals, valid, k)
    np.testing.assert_array_equal(v[: len(exp)], exp)
    np.testing.assert_array_equal(vals[i[ok]], v[ok])


def test_value_bits_ignored_for_floats():
    """A tightened value_bits must not break float selection (the float
    map packs exponents into the HIGH bits; the shortcut only fits ints).
    Goes through the public wrapper, which guards on dtype."""
    from flink_tpu.ops.topk import masked_topk

    rng = np.random.default_rng(3)
    vals = (rng.random(2048) * 1000).astype(np.float32)
    v, i, ok = map(np.asarray, masked_topk(
        jnp.asarray(vals), jnp.ones(2048, bool), 5, value_bits=16))
    np.testing.assert_array_equal(v, np.sort(vals)[::-1][:5])


def test_sort_variant_agrees():
    rng = np.random.default_rng(9)
    vals = rng.integers(0, 1000, 2048).astype(np.int64)
    valid = rng.random(2048) < 0.5
    rv, _ri, rok = map(np.asarray, masked_topk_radix(
        jnp.asarray(vals), jnp.asarray(valid), 50))
    sv, _si, sok = map(np.asarray, masked_topk_sort(
        jnp.asarray(vals), jnp.asarray(valid), 50))
    np.testing.assert_array_equal(rv[rok], sv[sok])


# -- the threshold select (PR 31): the walk starts at the bit the data has --

def _case(name):
    """(values, valid, k) whose k-th largest is what the name says."""
    rng = np.random.default_rng(31)
    n = 2048
    vals = rng.integers(0, 40, n).astype(np.int64)
    valid = rng.random(n) < 0.8
    k = 100
    if name == "ties_at_kth":
        vals[:60] = 1000 + np.arange(60)          # 60 strict
        vals[60:900] = 500                        # 840 equal at the k-th
        valid[:900] = True
    elif name == "fewer_valid_than_k":
        valid[:] = False
        valid[::64] = True                        # 32 valid
    elif name == "all_zero":
        vals[:] = 0
    elif name == "none_valid":
        valid[:] = False
    elif name == "max_at_2p32":
        vals[5] = 1 << 32
        valid[5] = True
    elif name == "max_above_2p32":
        vals[7:40] = (1 << 40) + np.arange(33) * (1 << 33)
        valid[7:40] = True
    elif name == "negative_valid":
        vals[11] = -3
        valid[11] = True
    elif name == "negative_masked_out":
        vals[11] = -3
        valid[11] = False
    elif name == "hot_counts":
        vals[:27] = 22_500 + np.arange(27)        # 15 bits
        valid[:27] = True
    else:
        raise ValueError(name)
    return vals, valid, k


CASES = ["ties_at_kth", "fewer_valid_than_k", "all_zero", "none_valid",
         "max_at_2p32", "max_above_2p32", "negative_valid",
         "negative_masked_out", "hot_counts"]


def _bit_length_of_largest_valid(vals, valid):
    return int(vals[valid].max()).bit_length() if valid.any() else 0


@pytest.mark.parametrize("otherwise", ["radix", "sort"])
@pytest.mark.parametrize("name", CASES)
def test_threshold_select_matches_oracle_and_counts_its_passes(
        name, otherwise):
    from flink_tpu.ops.topk import threshold_topk

    vals, valid, k = _case(name)
    top = jax.jit(lambda v, m: threshold_topk(
        v, m, k, otherwise=masked_topk_sort if otherwise == "sort"
        else None))(jnp.asarray(vals), jnp.asarray(valid))
    v, i, ok = (np.asarray(x) for x in (top.values, top.indices, top.ok))
    exp = _oracle(vals, valid, k)
    assert ok[:len(exp)].all() and not ok[len(exp):].any()
    np.testing.assert_array_equal(v[:len(exp)], exp)
    sel = i[ok]
    assert valid[sel].all() and len(np.unique(sel)) == len(sel)
    np.testing.assert_array_equal(vals[sel], v[ok])
    # a negative valid value is answered by the same walk over the
    # sign-flipped view, all 64 bits of it (PR 33): nothing falls back
    assert not bool(top.fell_back)
    assert int(top.passes) == (64 if name == "negative_valid" else
                               _bit_length_of_largest_valid(vals, valid))


@pytest.mark.parametrize("dtype,value_bits", [
    (np.int32, 31), (np.int32, 64), (np.uint32, 64), (np.int64, 31),
    (np.int64, 48), (np.int64, 63), (np.uint64, 64), (np.int16, 64)])
def test_threshold_select_on_every_integer_width_and_promise(dtype,
                                                             value_bits):
    """The promise decides what is compiled (the wide view, the guard),
    never the answer, as long as it is kept."""
    from flink_tpu.ops.topk import threshold_topk

    vals, valid, k = _case("hot_counts")
    vals = vals.astype(dtype)
    top = threshold_topk(jnp.asarray(vals), jnp.asarray(valid), k,
                         value_bits)
    exp = _oracle(vals, valid, k)
    np.testing.assert_array_equal(np.asarray(top.values)[:len(exp)], exp)
    assert int(top.passes) == 15 and not bool(top.fell_back)
    assert top.values.dtype == vals.dtype


@pytest.mark.parametrize("dtype", [np.int32, np.int16])
def test_a_narrow_signed_rank_with_a_negative_value_walks_its_width(dtype):
    from flink_tpu.ops.topk import threshold_topk

    vals = np.array([5, -7, 3, 9, -1, 0], dtype)
    top = threshold_topk(jnp.asarray(vals), jnp.ones(6, bool), 4)
    np.testing.assert_array_equal(np.asarray(top.values), [9, 5, 3, 0])
    assert not bool(top.fell_back)
    assert int(top.passes) == 8 * np.dtype(dtype).itemsize


@pytest.mark.parametrize("value_bits", [43, 64],
                         ids=["promised_43_bits", "no_promise"])
@pytest.mark.parametrize("top_price", [1 << 22, (1 << 22) - 1],
                         ids=["43_bit_word", "42_bit_word"])
def test_a_packed_word_takes_the_wide_view_and_walks_its_bit_length(
        value_bits, top_price):
    """NEXmark Q7's rank, `price << 20 | bidder` with prices in
    [1, 2^22]: the words pass 2^32, so the walk runs on the 64-bit view
    (on the 32-bit one the words that differ only above bit 31 would
    tie), for as many passes as the largest word has bits: 43 where a bid
    sits at 2^22, 42 where none does. With the promise or without it."""
    from flink_tpu.ops.topk import threshold_topk

    rng = np.random.default_rng(7)
    n, k = 4096, 5
    price = rng.integers(1, top_price, n)
    price[17] = top_price
    # the runners-up share their low 32 bits: only the wide view ranks them
    price[100:104] = top_price - 1 - np.arange(4) * 4096
    bidder = rng.integers(0, 1_000_000, n)
    bidder[100:104] = 77
    word = (price.astype(np.int64) << 20) | bidder
    valid = rng.random(n) < 0.9
    valid[[17, 100, 101, 102, 103]] = True
    top = jax.jit(lambda v, m: threshold_topk(v, m, k, value_bits))(
        jnp.asarray(word), jnp.asarray(valid))
    np.testing.assert_array_equal(np.asarray(top.values),
                                  np.sort(word[valid])[::-1][:k])
    assert int(top.passes) == int(word[valid].max()).bit_length() \
        == (43 if top_price == 1 << 22 else 42)
    assert not bool(top.fell_back)


@pytest.mark.parametrize("k", [1, 7, 64])
def test_a_negative_rank_without_a_promise_is_exact_at_every_k(k):
    """MAX planes start at the dtype's minimum and a SUM may go below 0:
    with no promise the select still answers exactly, by the same walk
    over the sign-flipped view (the path that replaced the radix walk's
    branch, which did not compile inside a fire over 2^24 slots)."""
    from flink_tpu.ops.topk import threshold_topk

    rng = np.random.default_rng(k)
    vals = rng.integers(-(1 << 62), 1 << 62, 2048)
    vals[:3] = [np.iinfo(np.int64).min, np.iinfo(np.int64).max, -1]
    valid = rng.random(2048) < 0.7
    valid[:3] = True
    for v, m in ((vals, valid), (-np.abs(vals) - 1, valid),
                 (vals, np.arange(2048) < min(k, 3))):
        top = threshold_topk(jnp.asarray(v), jnp.asarray(m), k)
        exp = np.sort(v[m])[::-1][:k]       # exact: no float64 in between
        got, ok = np.asarray(top.values), np.asarray(top.ok)
        assert ok[:len(exp)].all() and not ok[len(exp):].any()
        np.testing.assert_array_equal(got[:len(exp)], exp)
        np.testing.assert_array_equal(v[np.asarray(top.indices)[ok]],
                                      got[ok])
        assert int(top.passes) == 64 and not bool(top.fell_back)


def test_a_float_rank_never_walks():
    from flink_tpu.ops.topk import threshold_topk

    vals = np.array([0.5, -2.0, 8.25, 3.0], np.float32)
    top = threshold_topk(jnp.asarray(vals), jnp.ones(4, bool), 2)
    np.testing.assert_array_equal(np.asarray(top.values), [8.25, 3.0])
    assert bool(top.fell_back) and int(top.passes) == 0


def test_a_kept_promise_compiles_neither_scatter_nor_sort_over_the_slots():
    """Declared under the dtype's width (a COUNT), the select's program
    holds the walk and the compaction only: no histogram scatter of the
    radix walk, and no sort wider than the k winners."""
    from flink_tpu.ops.topk import threshold_topk

    n, k = 4096, 64
    hlo = jax.jit(lambda v, m: threshold_topk(v, m, k, 48)).lower(
        jnp.zeros(n, jnp.int64), jnp.zeros(n, bool)).compile().as_text()
    import re
    assert not re.search(r" scatter(-add)?\(", hlo)
    for m in re.finditer(r"\[(\d+)\][^\n]* sort\(", hlo):
        assert int(m.group(1)) <= k
