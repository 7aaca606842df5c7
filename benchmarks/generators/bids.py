"""NEXmark bid stream as one index-addressed function of the seed.

Event ``g`` (a global index over the whole run) is a pure function of
``(params, seed, g)``: any slice of the stream can be regenerated in any
order, which is what lets the reference recompute the run pane by pane.
Plain numpy; imports nothing of the program under test.

Stream layout (the harness's schedule decides WHEN a row is due; this
module decides only WHAT it holds):

  g <  prefill_rows   the resident-key prefill: every key of the
                      configuration once (a bijection of g), so the state
                      holds ``n_keys`` keys before anything is timed
  g >= prefill_rows   the bid traffic: ``hot_share`` of the bids go to a
                      hot set of ``hot_keys`` auctions (Beam
                      NexmarkConfiguration hotAuctionRatio 2 = 1 bid in
                      2), the rest uniform over all keys

Every seed gets the SAME batches in ANOTHER order. Which auctions are hot,
the order in which the prefill inserts the keys and the bids themselves
come from the configuration's ``layout_seed``; ``--seed`` permutes the
batches of each phase the harness registers with ``shuffle_batches`` (the
warm phase among themselves, the timed phase among themselves), so the
seed decides which bids share a pane and a window, not which bids exist.
Measured reason (PERF.md section 6): a step's device time depends on the
keys its batch happens to hold (one key with a long probe chain costs the
whole batch another round of the probe loop), so with the bids drawn from
the run's seed ``events_per_s`` differed by up to 7% from seed to seed
while two runs of one seed agreed within 0.2%: the seed was changing the
work.
"""

from __future__ import annotations

import numpy as np

__all__ = ["BidGenerator"]

_GOLD = np.uint64(0x9E3779B97F4A7C15)
_M1 = np.uint64(0xBF58476D1CE4E5B9)
_M2 = np.uint64(0x94D049BB133111EB)
#: odd and free of the factors 2 and 5, so g -> g * _STRIDE mod n_keys is a
#: bijection for every n_keys of the form 2^a * 5^b (10M, 16M, ...)
_STRIDE = 7_368_787


def _mix(x: np.ndarray) -> np.ndarray:
    """splitmix64 finalizer over a uint64 vector (wrap-around intended)."""
    with np.errstate(over="ignore"):
        x = (x ^ (x >> np.uint64(30))) * _M1
        x = (x ^ (x >> np.uint64(27))) * _M2
        return x ^ (x >> np.uint64(31))


class BidGenerator:
    """``columns(g)`` -> {"auction", "bidder", "price"} for index vector g.

    Parameters (all from data files): ``n_keys``, ``hot_keys``,
    ``hot_share``, ``price_max``, ``n_bidders``, ``layout_seed``,
    ``prefill_rows``."""

    def __init__(self, *, n_keys: int, hot_keys: int, hot_share: float,
                 price_max: int, n_bidders: int, prefill_rows: int,
                 seed: int, layout_seed: int = 0):
        if np.gcd(_STRIDE, n_keys) != 1:
            raise ValueError(f"n_keys={n_keys} shares a factor with the "
                             f"prefill stride {_STRIDE}")
        if not 0.0 <= hot_share <= 1.0:
            raise ValueError(f"hot_share {hot_share} outside [0, 1]")
        self.n_keys = int(n_keys)
        self.prefill_rows = int(prefill_rows)
        self.price_max = int(price_max)
        self.n_bidders = int(n_bidders)
        self.seed = int(seed)
        # 16-bit threshold: hot_share 0.5 -> exactly half of the hash space
        self._hot_cut = np.uint64(round(hot_share * 65536))
        rng = np.random.default_rng(int(layout_seed))
        self.hot_set = np.sort(rng.choice(self.n_keys, size=int(hot_keys),
                                          replace=False)).astype(np.int64)
        self._salt = _mix(np.array([int(layout_seed)], np.uint64) * _GOLD
                          + np.uint64(1))[0]
        self._offset = int(rng.integers(0, self.n_keys))
        self._blocks: list[tuple[int, int, np.ndarray]] = []

    def shuffle_batches(self, first_row: int, n_batches: int,
                        batch_rows: int) -> None:
        """Permute, by the run's seed, the ``n_batches`` batches of
        ``batch_rows`` rows that start at global row ``first_row``."""
        rng = np.random.default_rng([self.seed, int(first_row)])
        self._blocks.append((int(first_row), int(batch_rows),
                             rng.permutation(int(n_batches))))

    def _source_rows(self, g: np.ndarray) -> np.ndarray:
        """Row ``g`` of the run holds row ``_source_rows(g)`` of the fixed
        stream: the same row of the batch the seed's permutation put in
        this place."""
        out = g
        for first, rows, perm in self._blocks:
            inside = (g >= first) & (g < first + rows * len(perm))
            if inside.any():
                local = np.where(inside, g - first, 0)
                moved = first + perm[local // rows] * rows + local % rows
                out = np.where(inside, moved, out)
        return out

    def columns(self, g: np.ndarray) -> dict[str, np.ndarray]:
        g = self._source_rows(np.asarray(g, np.int64))
        with np.errstate(over="ignore"):
            h = _mix(g.astype(np.uint64) * _GOLD + self._salt)
            h2 = _mix(h + _GOLD)
        hot = ((h >> np.uint64(48)) < self._hot_cut)
        n_hot = np.uint64(len(self.hot_set))
        auction = np.where(
            hot,
            self.hot_set[((h >> np.uint64(8)) % n_hot).astype(np.int64)]
            if len(self.hot_set) else 0,
            (h2 % np.uint64(self.n_keys)).astype(np.int64))
        pre = g < self.prefill_rows
        if pre.any():
            # python-int arithmetic would be slow; int64 is exact here:
            # (g mod n_keys) * _STRIDE < 2^24.3 * 2^22.9 << 2^63
            auction = np.where(
                pre, ((g % self.n_keys) * _STRIDE + self._offset)
                % self.n_keys, auction)
        price = ((h2 >> np.uint64(24)) % np.uint64(self.price_max)
                 ).astype(np.int64) + 1
        bidder = ((h >> np.uint64(20)) % np.uint64(self.n_bidders)
                  ).astype(np.int64)
        return {"auction": auction.astype(np.int64), "bidder": bidder,
                "price": price}


def make_generator(data: dict, prefill_rows: int, seed: int) -> BidGenerator:
    """The entry point the harness calls: ``data`` is the configuration
    file's ``data`` block."""
    return BidGenerator(n_keys=data["n_keys"], hot_keys=data["hot_keys"],
                        hot_share=data["hot_share"],
                        price_max=data["price_max"],
                        n_bidders=data["n_bidders"],
                        layout_seed=data["layout_seed"],
                        prefill_rows=prefill_rows, seed=seed)
