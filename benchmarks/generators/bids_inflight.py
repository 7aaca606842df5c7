"""NEXmark bids whose auction ids ADVANCE, as the source's generator makes
them (Apache Beam ``BidGenerator`` / ``AuctionGenerator``, ported by
nexmark-flink): a pure function of ``(data, seed, g)``, plain numpy,
nothing of the program under test.

Beam interleaves 1 person : 3 auctions : 46 bids, auction ids only grow,
and a bid goes, 1 time in ``hotAuctionRatio`` (2), to the hot auction
``(lastAuctionId / HOT_AUCTION_RATIO) * HOT_AUCTION_RATIO`` (100: it moves
with every 100th new auction) and otherwise uniformly to one of the last
``numInFlightAuctions`` ids, ``AUCTION_ID_LEAD`` (10) ids ahead of the
newest included. This stream is the bids alone, so the ids advance by the
bid index: bid ``i`` (counted from the end of the prefill) sees

    last(i) = in_flight - 1 + i * 3 // 46        the newest auction id
    hot(i)  = last(i) // 100 * 100               half of the bids
    cold    = uniform over [max(last - in_flight, 0), last + id_lead]

so three new keys arrive per 46 bids for ever, a key is bid on only while
it is in flight, and the hot key is a new one every 100 auctions (1,533
bids). The constants are the data file's (``new_auctions_per_bid``,
``hot_every_auctions``, ``hot_share``, ``in_flight``, ``id_lead``); an id
at or over ``id_space`` (what the reference's dense arrays hold) raises.

Stream layout (the harness's schedule decides WHEN a row is due):

  g <  prefill_rows   the in-flight set at bid 0, ids [0, in_flight), each
                      once (a bijection of g while g < in_flight), then
                      ids of that set again up to the end of the batch
  g >= prefill_rows   bid ``g - prefill_rows`` of the stream above

Every seed gets the SAME batches in ANOTHER order, as ``bids.py`` does it
and for its reason, but only within consecutive groups of ``GROUP``
batches of a phase, so that the ids still advance: the seed decides which
bids share a pane, not which bids exist nor when a key is born.
"""

from __future__ import annotations

import numpy as np

from benchmarks.harness.spec import BENCH_DIR, load_module

_bids = load_module(BENCH_DIR, "generators", "bids")
_mix, _GOLD, _STRIDE = _bids._mix, _bids._GOLD, _bids._STRIDE

__all__ = ["InFlightBidGenerator", "GROUP", "make_generator"]

#: batches the run's seed permutes among themselves
GROUP = 8


class InFlightBidGenerator(_bids.BidGenerator):
    """``columns(g)`` -> {"auction", "bidder", "price"} for index vector g.
    Takes ``shuffle_batches`` / ``_source_rows`` (where a row of the run
    comes from in the fixed stream) from ``bids.BidGenerator``."""

    def __init__(self, *, in_flight: int, new_auctions_per_bid, hot_share,
                 hot_every_auctions: int, id_lead: int, id_space: int,
                 price_max: int, n_bidders: int, prefill_rows: int,
                 seed: int, layout_seed: int = 0):
        super().__init__(n_keys=in_flight, hot_keys=0, hot_share=hot_share,
                         price_max=price_max, n_bidders=n_bidders,
                         prefill_rows=prefill_rows, seed=seed,
                         layout_seed=layout_seed)
        self.in_flight = int(in_flight)
        self.new_num, self.new_den = (int(x) for x in new_auctions_per_bid)
        self.hot_every = int(hot_every_auctions)
        self.id_lead = int(id_lead)
        self.id_space = int(id_space)

    def shuffle_batches(self, first_row: int, n_batches: int,
                        batch_rows: int) -> None:
        """Permute, by the run's seed, each consecutive group of ``GROUP``
        of the ``n_batches`` batches that start at row ``first_row``."""
        rng = np.random.default_rng([self.seed, int(first_row)])
        perm = np.arange(int(n_batches))
        for a in range(0, len(perm), GROUP):
            perm[a:a + GROUP] = a + rng.permutation(len(perm[a:a + GROUP]))
        self._blocks.append((int(first_row), int(batch_rows), perm))

    def last_auction(self, bid: np.ndarray) -> np.ndarray:
        """The newest auction id when bid ``bid`` is made."""
        return self.in_flight - 1 + bid * self.new_num // self.new_den

    def columns(self, g: np.ndarray) -> dict[str, np.ndarray]:
        g = self._source_rows(np.asarray(g, np.int64))
        with np.errstate(over="ignore"):
            h = _mix(g.astype(np.uint64) * _GOLD + self._salt)
            h2 = _mix(h + _GOLD)
        last = self.last_auction(np.maximum(g - self.prefill_rows, 0))
        low = np.maximum(last - self.in_flight, 0)
        span = (last - low + 1 + self.id_lead).astype(np.uint64)
        auction = np.where(
            (h >> np.uint64(48)) < self._hot_cut,
            last // self.hot_every * self.hot_every,
            low + (h2 % span).astype(np.int64))
        # the prefill, as bids.py lays it out over n_keys = in_flight
        auction = np.where(
            g < self.prefill_rows,
            ((g % self.in_flight) * _STRIDE + self._offset) % self.in_flight,
            auction)
        if len(auction) and int(auction.max()) >= self.id_space:
            raise ValueError(
                f"auction id {int(auction.max())} at or over data.id_space "
                f"{self.id_space}: the run is longer than the "
                "configuration sized its reference for")
        price = ((h2 >> np.uint64(24)) % np.uint64(self.price_max)
                 ).astype(np.int64) + 1
        bidder = ((h >> np.uint64(20)) % np.uint64(self.n_bidders)
                  ).astype(np.int64)
        return {"auction": auction.astype(np.int64), "bidder": bidder,
                "price": price}


def make_generator(data: dict, prefill_rows: int,
                   seed: int) -> InFlightBidGenerator:
    """The entry point the harness calls: ``data`` is the configuration
    file's ``data`` block."""
    return InFlightBidGenerator(
        in_flight=data["in_flight"],
        new_auctions_per_bid=data["new_auctions_per_bid"],
        hot_share=data["hot_share"],
        hot_every_auctions=data["hot_every_auctions"],
        id_lead=data["id_lead"], id_space=data["id_space"],
        price_max=data["price_max"], n_bidders=data["n_bidders"],
        layout_seed=data["layout_seed"], prefill_rows=prefill_rows,
        seed=seed)
