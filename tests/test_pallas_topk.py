"""Pallas radix-select histogram: correctness in interpreter mode on CPU
(it has not been timed against the XLA select on the chip: ROADMAP D4)."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from flink_tpu.ops.hash_table import ensure_x64  # noqa: E402
from flink_tpu.ops.pallas_topk import (  # noqa: E402
    histogram256_pallas, masked_topk_pallas,
)
from flink_tpu.ops.topk import masked_topk  # noqa: E402


def test_histogram_matches_numpy():
    ensure_x64()
    rng = np.random.default_rng(3)
    u = rng.integers(0, 1 << 31, 5000).astype(np.int32)
    valid = rng.random(5000) < 0.7
    for shift in (0, 8, 16, 24):
        got = np.asarray(histogram256_pallas(
            jnp.asarray(u), jnp.asarray(valid), shift, interpret=True))
        ids = (u[valid].astype(np.uint32) >> shift) & 0xFF
        want = np.bincount(ids, minlength=256).astype(np.int32)
        np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("seed,k,vb", [(0, 10, 16), (1, 100, 32),
                                       (2, 7, 8)])
def test_topk_parity_with_xla_path(seed, k, vb):
    ensure_x64()
    rng = np.random.default_rng(seed)
    n = 4096
    vals = rng.integers(0, 1 << min(vb, 30), n).astype(np.int64)
    valid = rng.random(n) < 0.6
    pv, pi, pok = masked_topk_pallas(jnp.asarray(vals), jnp.asarray(valid),
                                     k, value_bits=vb, interpret=True)
    xv, xi, xok = masked_topk(jnp.asarray(vals), jnp.asarray(valid), k,
                              value_bits=vb)
    assert np.asarray(pok).tolist() == np.asarray(xok).tolist()
    # values must match exactly; indices may differ among equal values
    np.testing.assert_array_equal(np.asarray(pv)[np.asarray(pok)],
                                  np.asarray(xv)[np.asarray(xok)])
    sel = np.asarray(pok)
    assert (vals[np.asarray(pi)[sel]] == np.asarray(pv)[sel]).all()


def test_fewer_valid_than_k():
    ensure_x64()
    vals = jnp.asarray(np.array([5, 3, 9, 1], np.int64))
    valid = jnp.asarray(np.array([True, False, True, False]))
    pv, pi, pok = masked_topk_pallas(vals, valid, 3, value_bits=8,
                                     interpret=True)
    assert np.asarray(pok).tolist() == [True, True, False]
    assert np.asarray(pv)[:2].tolist() == [9, 5]
