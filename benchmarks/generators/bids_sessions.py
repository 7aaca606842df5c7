"""NEXmark bids whose BIDDER ids advance, as the source's generator makes
them (Apache Beam ``BidGenerator`` / ``PersonGenerator``, ported by
nexmark-flink): a pure function of ``(data, seed, g)``, plain numpy,
nothing of the program under test.

Beam interleaves 1 person : 3 auctions : 46 bids, person ids only grow,
and a bid comes, ``hotBiddersRatio - 1`` times in ``hotBiddersRatio`` (4:
3 bids in 4), from THE hot bidder of the moment,
``(lastPersonId / HOT_BIDDER_RATIO) * HOT_BIDDER_RATIO + 1`` (100: it
moves with every 100th new person), and otherwise from one of the last
``numActivePeople`` persons, uniformly. This stream is the bids alone, so
the ids advance by the bid index: bid ``i`` (counted from the end of the
prefill) sees

    last(i) = active - 1 + i * 1 // 46          the newest person id
    hot(i)  = last(i) // 100 * 100 + 1          three bids in four
    cold    = uniform over [last - active + 1, last]

so one new bidder arrives per 46 bids for ever, a bidder bids only while
among the newest ``active_bidders``, and the hot bidder is a new one
every 100 persons (4,600 bids: ONE session of about 3,450 bids). The
constants are the data file's (``new_bidders_per_bid``,
``hot_every_bidders``, ``hot_share``, ``active_bidders``); an id at or
over ``id_space`` (what the reference's dense arrays hold) raises.

Stream layout (the harness's schedule decides WHEN a row is due):

  g <  prefill_rows   the active set at bid 0, ids [0, active), each once
                      (a bijection of g while g < active), then ids of
                      that set again up to the end of the batch
  g >= prefill_rows   bid ``g - prefill_rows`` of the stream above

Every seed gets the SAME batches in ANOTHER order, within consecutive
groups of ``GROUP`` batches of a phase, as ``bids_inflight.py`` does it
and for its reason: the ids still advance, and the seed decides which
bids share a batch, not which bids exist nor when a bidder is born.
"""

from __future__ import annotations

import numpy as np

from benchmarks.harness.spec import BENCH_DIR, load_module

_bids = load_module(BENCH_DIR, "generators", "bids")
_inflight = load_module(BENCH_DIR, "generators", "bids_inflight")
_mix, _GOLD, _STRIDE = _bids._mix, _bids._GOLD, _bids._STRIDE

__all__ = ["SessionBidGenerator", "GROUP", "make_generator"]

#: batches the run's seed permutes among themselves
GROUP = _inflight.GROUP


class SessionBidGenerator(_bids.BidGenerator):
    """``columns(g)`` -> {"auction", "bidder", "price"} for index vector g.
    Takes ``_source_rows`` (where a row of the run comes from in the fixed
    stream) from ``bids.BidGenerator`` and the grouped ``shuffle_batches``
    from ``bids_inflight.InFlightBidGenerator``."""

    shuffle_batches = _inflight.InFlightBidGenerator.shuffle_batches

    def __init__(self, *, active_bidders: int, new_bidders_per_bid,
                 hot_share, hot_every_bidders: int, id_space: int,
                 price_max: int, n_auctions: int, prefill_rows: int,
                 seed: int, layout_seed: int = 0):
        super().__init__(n_keys=active_bidders, hot_keys=0,
                         hot_share=hot_share, price_max=price_max,
                         n_bidders=active_bidders,
                         prefill_rows=prefill_rows, seed=seed,
                         layout_seed=layout_seed)
        self.active = int(active_bidders)
        self.new_num, self.new_den = (int(x) for x in new_bidders_per_bid)
        self.hot_every = int(hot_every_bidders)
        self.id_space = int(id_space)
        self.n_auctions = int(n_auctions)

    def last_bidder(self, bid: np.ndarray) -> np.ndarray:
        """The newest bidder id when bid ``bid`` is made."""
        return self.active - 1 + bid * self.new_num // self.new_den

    def hot_bidder(self, bid: np.ndarray) -> np.ndarray:
        """THE hot bidder when bid ``bid`` is made."""
        return self.last_bidder(bid) // self.hot_every * self.hot_every + 1

    def is_hot(self, g: np.ndarray) -> np.ndarray:
        """Whether row ``g`` of the FIXED stream is a hot bidder's."""
        with np.errstate(over="ignore"):
            h = _mix(np.asarray(g).astype(np.uint64) * _GOLD + self._salt)
        return (h >> np.uint64(48)) < self._hot_cut

    def columns(self, g: np.ndarray) -> dict[str, np.ndarray]:
        g = self._source_rows(np.asarray(g, np.int64))
        with np.errstate(over="ignore"):
            h = _mix(g.astype(np.uint64) * _GOLD + self._salt)
            h2 = _mix(h + _GOLD)
        bid = np.maximum(g - self.prefill_rows, 0)
        last = self.last_bidder(bid)
        bidder = np.where(
            (h >> np.uint64(48)) < self._hot_cut,
            self.hot_bidder(bid),
            last - self.active + 1
            + (h2 % np.uint64(self.active)).astype(np.int64))
        # the prefill, as bids.py lays it out over n_keys = active
        bidder = np.where(
            g < self.prefill_rows,
            ((g % self.active) * _STRIDE + self._offset) % self.active,
            bidder)
        if len(bidder) and int(bidder.max()) >= self.id_space:
            raise ValueError(
                f"bidder id {int(bidder.max())} at or over data.id_space "
                f"{self.id_space}: the run is longer than the "
                "configuration sized its reference for")
        price = ((h2 >> np.uint64(24)) % np.uint64(self.price_max)
                 ).astype(np.int64) + 1
        auction = ((h >> np.uint64(20)) % np.uint64(self.n_auctions)
                   ).astype(np.int64)
        return {"auction": auction, "bidder": bidder.astype(np.int64),
                "price": price}


def make_generator(data: dict, prefill_rows: int,
                   seed: int) -> SessionBidGenerator:
    """The entry point the harness calls: ``data`` is the configuration
    file's ``data`` block."""
    return SessionBidGenerator(
        active_bidders=data["active_bidders"],
        new_bidders_per_bid=data["new_bidders_per_bid"],
        hot_share=data["hot_share"],
        hot_every_bidders=data["hot_every_bidders"],
        id_space=data["id_space"], price_max=data["price_max"],
        n_auctions=data["n_auctions"], layout_seed=data["layout_seed"],
        prefill_rows=prefill_rows, seed=seed)
