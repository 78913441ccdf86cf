"""The NEXmark bid stream, drawn in one vectorised pass from the seed.

The rules are those of Apache Beam's Nexmark generator at the default
``NexmarkConfiguration`` (the configuration file lists them, and what is
assumed): events come in cycles of 50 ids, one person, three auctions and
46 bids; event ``n`` happens at ``floor(n / 10)`` ms (10,000 events/s,
millisecond timestamps); a bid goes to the hot auction (the newest auction
id rounded down to a multiple of 100) with probability 1/2, else to one of
the auctions in flight, and comes from the hot bidder (the newest person
id rounded down to a multiple of 100, plus one) with probability 3/4, else
from one of the active people; its price is ``10**(6u)`` dollars.

Persons and auctions only create ids.  A bid updates two profiles of one
table: its bidder's row (a person's base-0 id) and its auction's row (the
person rows first, then the auction's base-0 id).
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass
class Stream:
    key: np.ndarray      # int32 [N, 2]: bidder row, auction row
    q: np.ndarray        # float32 [N]: price, dollars
    t: np.ndarray        # float32 [N]: event time, seconds
    ctr: np.ndarray      # uint32 [N]: event id
    n_rows: int          # table rows: persons, then auctions
    n_person_rows: int

    def __len__(self) -> int:
        return len(self.q)


def last_person(st: dict, ids: np.ndarray) -> np.ndarray:
    """The newest person's base-0 id at each event id."""
    total = st["person_proportion"] + st["auction_proportion"] \
        + st["bid_proportion"]
    epoch, off = np.divmod(ids, total)
    return epoch * st["person_proportion"] + np.minimum(
        off, st["person_proportion"] - 1)


def last_auction(st: dict, ids: np.ndarray) -> np.ndarray:
    """The newest auction's base-0 id at each event id."""
    pp, ap = st["person_proportion"], st["auction_proportion"]
    epoch, off = np.divmod(ids, pp + ap + st["bid_proportion"])
    before = off < pp                       # a person event: last cycle's
    epoch = epoch - before
    off = np.where(before | (off >= pp + ap), ap - 1, off - pp)
    return epoch * ap + off


def bid_ids(st: dict, j) -> np.ndarray:
    """Event ids of the bids numbered ``j`` (from 0)."""
    pp, ap, bp = (st["person_proportion"], st["auction_proportion"],
                  st["bid_proportion"])
    cycle, off = np.divmod(np.asarray(j, np.int64), bp)
    return cycle * (pp + ap + bp) + pp + ap + off


def time_ms(st: dict, ids):
    """Event time of each event id, in whole milliseconds."""
    return np.asarray(ids, np.int64) * 1000 // int(st["first_event_rate"])


def make_stream(config: dict, n_events: int, seed: int) -> Stream:
    """The first ``n_events`` bids of the stream, from ``seed``."""
    st = config["stream"]
    if n_events and time_ms(st, bid_ids(st, n_events - 1)) \
            >= 1000 * st["max_event_time_s"]:
        raise ValueError(f"{n_events} bids run past "
                         f"{st['max_event_time_s']} s of event time, "
                         f"where float32 merges milliseconds")
    ids = bid_ids(st, np.arange(n_events))
    t_ms = time_ms(st, ids)
    rng = np.random.default_rng([seed, 0x4E58])
    person, auction = last_person(st, ids), last_auction(st, ids)

    hot_a = rng.integers(0, st["hot_auction_ratio"], n_events) > 0
    lo = np.maximum(auction - st["num_in_flight_auctions"], 0)
    span = auction - lo + 1 + st["auction_id_lead"]
    cold_a = lo + (rng.random(n_events) * span).astype(np.int64)
    hot_auction = auction // st["hot_auction_id_ratio"] \
        * st["hot_auction_id_ratio"]
    a = np.where(hot_a, hot_auction, cold_a)

    hot_b = rng.integers(0, st["hot_bidders_ratio"], n_events) > 0
    people = person + 1
    active = np.minimum(people, st["num_active_people"])
    cold_b = people - active + (rng.random(n_events) * (
        active + st["person_id_lead"])).astype(np.int64)
    hot_bidder = person // st["hot_bidder_id_ratio"] \
        * st["hot_bidder_id_ratio"] + 1
    b = np.where(hot_b, hot_bidder, cold_b)

    cents = np.round(10.0 ** (rng.random(n_events) * st["price_decades"])
                     * 100.0)
    n_person_rows = int(person[-1]) + 1 + st["person_id_lead"] \
        if n_events else 0
    n_auction_rows = int(auction[-1]) + 1 + st["auction_id_lead"] \
        if n_events else 0
    key = np.stack([b, n_person_rows + a], axis=1).astype(np.int32)
    return Stream(key=key, q=(cents / 100.0).astype(np.float32),
                  t=(t_ms / 1000.0).astype(np.float32),
                  ctr=ids.astype(np.uint32),
                  n_rows=n_person_rows + n_auction_rows,
                  n_person_rows=n_person_rows)
