"""keyBy exchange over ICI: the all-to-all repartition.

This replaces the reference's hash repartition between subtasks
(KeyGroupStreamPartitioner + RecordWriter.emit:104 + the Netty
credit-based channel stack, SURVEY.md §5.8) with ONE XLA collective: every
device buckets its local micro-batch by destination shard and a single
`lax.all_to_all` rides the ICI mesh. There are no credits — collectives are
synchronous, so backpressure collapses to admission control at ingestion
(SURVEY.md §7 hard-parts).

Two shapes of the same exchange live here:

* ``keyby_exchange`` — the worst-case-width form: each device sends a
  [n_dev, B] buffer (capacity B per destination — the whole local batch
  may hash to one shard), so ONE collective always suffices but every
  receiver folds n_dev*B rows. Per-device cost grows linearly with the
  mesh.
* ``plan_exchange`` + ``order_payload`` + ``exchange_round`` — the
  capacity-bounded form the sharded window step uses: buckets are cut
  into rounds of ``cap`` rows per destination and the step loops rounds
  until the DEEPEST bucket across the mesh is drained (`lax.pmax` of the
  local round counts, so every device runs the same trip count and the
  collectives stay uniform). A uniform batch takes one round of
  ~B/n_dev-deep buckets — per-device fold width stays O(B) as the mesh
  grows; a fully skewed batch degrades to ceil(B/cap) rounds, the old
  worst case, but never drops a record. A round is packed BY POSITION,
  with no scatter: the plan's stable sort leaves bucket ``d`` as the
  contiguous run ``ordered[offsets[d] : offsets[d] + counts[d]]``, so
  lane ``l`` of destination ``d`` in round ``r`` is row
  ``offsets[d] + r*cap + l`` of the ordered column — one ``cap``-row
  slice a destination a column — and is valid iff
  ``l < counts[d] - r*cap``; the lanes past a bucket's end are zeroed.

Invalid (padding) rows are routed to a virtual overflow destination and
vanish in both forms.
"""

from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

__all__ = ["keyby_exchange", "plan_exchange", "exchange_round", "ExchangePlan"]


def keyby_exchange(axis_name: str, n_dev: int, dest: jax.Array,
                   payload: Any, valid: jax.Array) -> tuple[Any, jax.Array]:
    """Route records to their destination shard. Call INSIDE shard_map.

    dest:    [B] int32 destination mesh position per record
    payload: pytree of [B, ...] column arrays
    valid:   [B] bool — padding rows are discarded

    Returns (routed payload pytree of [n_dev * B, ...], routed valid mask
    [n_dev * B]); routed rows are grouped by source device.
    """
    B = dest.shape[0]
    d = jnp.where(valid, dest, jnp.int32(n_dev))  # invalid -> overflow bucket
    order = jnp.argsort(d, stable=True)
    sd = d[order]
    counts = jnp.sum(jax.nn.one_hot(d, n_dev + 1, dtype=jnp.int32), axis=0)
    offsets = jnp.concatenate(
        [jnp.zeros(1, jnp.int32), jnp.cumsum(counts)[:-1]])
    rank = jnp.arange(B, dtype=jnp.int32) - offsets[sd]

    send_valid = jnp.zeros((n_dev, B), bool).at[sd, rank].set(
        sd < n_dev, mode="drop")

    def scatter(col):
        buf = jnp.zeros((n_dev, B) + col.shape[1:], col.dtype)
        return buf.at[sd, rank].set(col[order], mode="drop")

    send = jax.tree.map(scatter, payload)
    if n_dev == 1:
        recv, recv_valid = send, send_valid
    else:
        recv = jax.tree.map(
            lambda x: jax.lax.all_to_all(x, axis_name, split_axis=0,
                                         concat_axis=0), send)
        recv_valid = jax.lax.all_to_all(send_valid, axis_name, split_axis=0,
                                        concat_axis=0)
    routed = jax.tree.map(
        lambda x: x.reshape((n_dev * B,) + x.shape[2:]), recv)
    return routed, recv_valid.reshape(n_dev * B)


class ExchangePlan(NamedTuple):
    """Routing plan for the capacity-bounded exchange (see module doc).

    order:    [B] int32 — stable sort permutation grouping rows by dest
    counts:   [n_dev] int32 — rows of the local batch bound for each dest
    offsets:  [n_dev] int32 — where each dest's run starts in the order
    n_rounds: []  int32 — LOCAL round count; `lax.pmax` it across the
              axis before looping so every device runs the same trips
    """
    order: jax.Array
    counts: jax.Array
    offsets: jax.Array
    n_rounds: jax.Array


def bucket_capacity(batch: int, n_dev: int) -> int:
    """Static per-destination round capacity for a local batch of `batch`.

    Mean bucket depth is batch/n_dev; the +25% (floor +16) headroom keeps
    a uniformly keyed batch to one round with high probability while a
    skewed batch just takes more rounds — capacity never loses records.
    """
    per = -(-batch // n_dev)
    return int(min(batch, max(32, per + max(per // 4, 16))))


def plan_exchange(dest: jax.Array, valid: jax.Array, n_dev: int,
                  cap: int) -> ExchangePlan:
    """Bucket a local batch by destination for round-based exchange.

    Call INSIDE shard_map. `cap` must be a static int (shapes depend on
    it); `bucket_capacity` picks a good default.
    """
    d = jnp.where(valid, dest, jnp.int32(n_dev))
    # Every reduction names int32: under x64 (which the state path turns
    # on) argsort/sum/cumsum widen to int64, and the caller's pmax of
    # n_rounds would then be an s64 max all-reduce, which the TPU refuses
    # ("Supported lowering only of Sum all reduce").
    order = jnp.argsort(d, stable=True).astype(jnp.int32)
    counts = jnp.sum(jax.nn.one_hot(d, n_dev + 1, dtype=jnp.int32), axis=0,
                     dtype=jnp.int32)[:n_dev]
    offsets = jnp.cumsum(counts, dtype=jnp.int32) - counts
    n_rounds = (jnp.max(counts) + jnp.int32(cap - 1)) // jnp.int32(cap)
    return ExchangePlan(order, counts, offsets, n_rounds)


def order_payload(plan: ExchangePlan, payload: Any, cap: int) -> Any:
    """The columns `exchange_round` packs from: each [B, ...] column of
    `payload` permuted by `plan.order`, with `cap` rows of zeros behind it
    so that a round's `cap`-row slice which starts inside the batch never
    clamps back into another bucket. Once a step, outside the rounds'
    loop."""
    def ordered(col):
        pad = jnp.zeros((cap,) + col.shape[1:], col.dtype)
        return jnp.concatenate([col[plan.order], pad])

    return jax.tree.map(ordered, payload)


def exchange_round(axis_name: str, n_dev: int, cap: int, plan: ExchangePlan,
                   ordered_payload: Any, r: jax.Array) -> tuple[Any, jax.Array]:
    """Route round `r` of a planned exchange: rows with bucket rank in
    [r*cap, (r+1)*cap). `ordered_payload` is `order_payload`'s. Returns
    ([n_dev*cap, ...] routed pytree, [n_dev*cap] valid mask). Safe inside
    lax.while_loop with a pmax-uniform trip count.
    """
    # the send buffers are the region exchange.pack; the all-to-alls stay
    # directly under the caller's scope
    with jax.named_scope("exchange.pack"):
        done = r * jnp.int32(cap)
        left = jnp.clip(plan.counts - done, 0, cap)
        send_valid = jnp.arange(cap, dtype=jnp.int32)[None, :] < left[:, None]
        # A drained bucket's start may lie past the batch: dynamic_slice
        # clamps it onto the padding, and no lane of it is valid.
        starts = plan.offsets + done

        def pack(col):
            # shapes are static: a `cap` other than `order_payload`'s would
            # let a slice clamp back into another bucket
            assert col.shape[0] == plan.order.shape[0] + cap, (col.shape, cap)
            rows = jnp.stack([
                jax.lax.dynamic_slice_in_dim(col, starts[d], cap)
                for d in range(n_dev)])
            keep = send_valid.reshape(send_valid.shape + (1,) * (col.ndim - 1))
            return jnp.where(keep, rows, jnp.zeros((), col.dtype))

        send = jax.tree.map(pack, ordered_payload)
    if n_dev == 1:
        recv, recv_valid = send, send_valid
    else:
        recv = jax.tree.map(
            lambda x: jax.lax.all_to_all(x, axis_name, split_axis=0,
                                         concat_axis=0), send)
        recv_valid = jax.lax.all_to_all(send_valid, axis_name, split_axis=0,
                                        concat_axis=0)
    routed = jax.tree.map(
        lambda x: x.reshape((n_dev * cap,) + x.shape[2:]), recv)
    return routed, recv_valid.reshape(n_dev * cap)
