"""Pallas TPU kernel for the top-k radix-select histogram (A/B vs XLA).

The fire-path top-k (ops/topk.py) is O(n) histogram passes; under XLA
each pass lowers to a scatter-add — correct, but scatter is the op XLA
lowers most conservatively on TPU. This module implements the same
histogram as a Pallas kernel using the TPU-native formulation: per-block
compare-and-count (one vectorized ``ids == bin`` pass per bin on the VPU
at full vector width; no scatter at all), accumulated per lane across
grid steps in VMEM and lane-summed by the caller.

The kernel uses 8-bit digits (256 bins) so the per-lane accumulator stays
small in VMEM ([256, 128] int32 = 128 KB); a 32-bit walk is <= 4 passes
instead of the XLA path's <= 2 passes of 16-bit digits; which wins on
the chip has not been measured (ROADMAP D4).

``masked_topk_pallas`` matches ``ops.topk.masked_topk``'s contract for
non-negative integer domains below 2^32 (the count/packed-word fires);
other dtypes fall back to the XLA path. ``interpret=True`` runs the
kernel in the Pallas interpreter for CPU correctness tests; compiled, it
needs a TPU, and a failure to compile there raises out of the caller
(chip_smoke.py's pallas-topk leg and tests/test_tpu_lowering.py hold it
to compiling under x64).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ..metrics.device import instrumented_program_cache

__all__ = ["histogram256_pallas", "masked_topk_pallas"]

_BINS = 256
_LANES = 128
_BLOCK_ROWS = 256               # ids block = 32 int32 vregs, held in registers
_BLOCK = _BLOCK_ROWS * _LANES


def _hist_kernel(u_ref, valid_ref, out_ref, *, shift: int):
    """One grid step: per-LANE 256-bin histogram of ((u >> shift) & 0xFF)
    over a [BLOCK_ROWS, 128] block, masked by ``valid``, accumulated into
    out_ref[256, 128] (the caller sums the lanes). Every array keeps the
    native (sublane, lane) layout — no reshape, no cross-lane reduce — and
    every constant, loop bound and reduction is explicitly 32-bit: jobs run
    with x64 on, and Mosaic has no 64-bit types."""
    import jax.experimental.pallas as pl

    @pl.when(pl.program_id(0) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    digit = jax.lax.shift_right_logical(
        u_ref[...], jnp.int32(shift)) & jnp.int32(_BINS - 1)
    ids = jnp.where(valid_ref[...] != 0, digit, jnp.int32(_BINS))

    def count_bin(b, carry):
        hit = (ids == b).astype(jnp.int32)
        out_ref[pl.ds(b, 1), :] += jnp.sum(hit, axis=0, keepdims=True,
                                           dtype=jnp.int32)
        return carry

    jax.lax.fori_loop(jnp.int32(0), jnp.int32(_BINS), count_bin,
                      jnp.int32(0))


@partial(jax.jit, static_argnames=("shift", "interpret"))
def histogram256_pallas(u: jax.Array, valid: jax.Array, shift: int,
                        interpret: bool = False) -> jax.Array:
    """[256] int32 histogram of ((u >> shift) & 0xFF) where valid."""
    import jax.experimental.pallas as pl

    n = u.shape[0]
    pad = -n % _BLOCK
    u = jnp.pad(u.astype(jnp.int32), (0, pad))
    valid = jnp.pad(valid.astype(jnp.int32), (0, pad))
    # index maps return numpy int32: a Python 0 traces as int64 under x64
    zero = np.int32(0)
    out = pl.pallas_call(
        partial(_hist_kernel, shift=shift),
        grid=((n + pad) // _BLOCK,),
        in_specs=[
            pl.BlockSpec((_BLOCK_ROWS, _LANES), lambda i: (i, zero)),
            pl.BlockSpec((_BLOCK_ROWS, _LANES), lambda i: (i, zero)),
        ],
        out_specs=pl.BlockSpec((_BINS, _LANES), lambda i: (zero, zero)),
        out_shape=jax.ShapeDtypeStruct((_BINS, _LANES), jnp.int32),
        interpret=interpret,
    )(u.reshape(-1, _LANES), valid.reshape(-1, _LANES))
    return out.sum(axis=1, dtype=jnp.int32)


def masked_topk_pallas(values: jax.Array, valid: jax.Array, k: int,
                       value_bits: int = 32, interpret: bool = False):
    """Exact masked top-k via Pallas histogram radix select (8-bit
    digits). Contract identical to ops.topk.masked_topk for non-negative
    integer domains < 2^32; other inputs take the XLA path."""
    from .topk import masked_topk

    if (value_bits > 32
            or jnp.issubdtype(jnp.asarray(values).dtype, jnp.floating)):
        return masked_topk(values, valid, k, value_bits)
    passes = max(1, -(-value_bits // 8))
    from ..runtime.watchdog import stall_bounded
    return stall_bounded(
        "device.execute",
        lambda: _topk_program(int(k), int(passes),
                              bool(interpret))(values, valid),
        scope="pallas_topk")


@instrumented_program_cache("ops.pallas_topk", maxsize=32)
def _topk_program(k: int, passes: int, interpret: bool):
    """One jitted program per (k, passes, interpret); shapes re-trace
    inside jax.jit as usual, the builder cache is what the compile
    accounting watches."""

    @jax.jit
    def run(values, valid):
        return _topk_pallas(values, valid, k, passes, interpret)

    return run


def _topk_pallas(values, valid, k, passes, interpret):
    n = values.shape[0]
    k = min(k, n)
    u = values.astype(jnp.uint32)
    nvalid = jnp.sum(valid, dtype=jnp.int32)
    kk = jnp.minimum(jnp.int32(k), nvalid)
    cand = valid
    above = jnp.int32(0)
    prefix = jnp.uint32(0)
    bins = jnp.arange(256, dtype=jnp.int32)
    for shift in (24, 16, 8, 0)[4 - passes:]:
        hist = histogram256_pallas(u.view(jnp.int32)
                                   if u.dtype == jnp.uint32 else u,
                                   cand, shift, interpret=interpret)
        revcum = jnp.cumsum(hist[::-1])[::-1]
        cond = (above + revcum) >= kk
        bstar = jnp.max(jnp.where(cond, bins, -1))
        above = above + jnp.where(bins > bstar, hist, 0).sum()
        prefix = prefix | (bstar.astype(jnp.uint32) << shift)
        field = ((u >> shift) & jnp.uint32(0xFF)).astype(jnp.int32)
        cand = cand & (field == bstar)
    thr = prefix
    strict = valid & (u > thr)
    tie = valid & (u == thr)
    cum_s = jnp.cumsum(strict.astype(jnp.int32))
    cum_t = jnp.cumsum(tie.astype(jnp.int32))
    tie_pos = jnp.clip(jnp.int32(k) - cum_t, 0, k - 1)
    strict_pos = cum_s - 1
    idx = jnp.arange(n, dtype=jnp.int32)
    buf_i = jnp.full(k, -1, jnp.int32)
    buf_i = buf_i.at[jnp.where(tie, tie_pos, k)].set(idx, mode="drop")
    buf_i = buf_i.at[jnp.where(strict, strict_pos, k)].set(idx, mode="drop")
    filled = buf_i >= 0
    sent = jnp.iinfo(values.dtype).min
    buf_v = jnp.where(filled, values[jnp.maximum(buf_i, 0)], sent)
    order = jnp.lexsort((jnp.where(filled, buf_v.astype(jnp.uint32),
                                   jnp.uint32(0)), filled))[::-1]
    return (buf_v[order], jnp.maximum(buf_i, 0)[order].astype(jnp.int64),
            filled[order])
