"""The bid stream of `nexmark-q5` has the shape the configuration
states, on the first 10^6 events of a seeded stream."""

import numpy as np
import pytest

from benchmark import run
from benchmark.flows import nexmark_q5 as q5

EVENTS = 1_000_000


@pytest.fixture(scope="module")
def stream():
    cfg = run.Cell("q5.flood").cfg
    data = q5.make_data(cfg, {}, 2147483659, "")
    bids = q5.bids_before(cfg, EVENTS)
    return cfg, data, bids, q5.columns(cfg, data, 0, bids)


def test_bids_are_46_of_every_50_events(stream):
    cfg, _data, bids, cols = stream
    assert bids == EVENTS * 46 // 50
    ids = q5.event_ids(cfg, 0, bids)
    assert ids[0] == 4 and ids[-1] == EVENTS - 1 and (ids % 50 >= 4).all()
    assert (np.diff(ids) >= 1).all() and len(np.unique(ids)) == bids
    assert (cols["ts"] == ids * 100).all()  # 10,000 events a second
    assert q5.bids_before(cfg, 4) == 0 and q5.bids_before(cfg, 5) == 1
    assert q5.bids_before(cfg, 50) == 46 and q5.bids_before(cfg, 53) == 46


def test_half_the_bids_go_to_the_hot_auction(stream):
    cfg, _data, bids, cols = stream
    ids = q5.event_ids(cfg, 0, bids)
    last = (ids // 50) * 3 + 2
    on_hot = cols["kid"] == (last // 100) * 100
    # Half by the draw, and a hundredth of the others by chance.
    assert 0.495 < on_hot.mean() < 0.515
    per_auction = np.bincount(cols["kid"])
    hot = per_auction[::100]
    # A new hot auction every 100 auctions = 1,533 bids: about 770 each.
    assert 700 < np.median(hot[1:-1]) < 840


def test_an_ordinary_auction_gets_its_bids_near_its_birth(stream):
    cfg, _data, bids, cols = stream
    ids = q5.event_ids(cfg, 0, bids)
    last = (ids // 50) * 3 + 2
    kid = cols["kid"].astype(np.int64)
    ordinary = kid != (last // 100) * 100
    behind = (last - kid)[ordinary]
    assert behind.min() == -10 and behind.max() == 100  # lead 10, 100 in flight
    per_auction = np.bincount(kid)
    plain = np.delete(per_auction, np.arange(0, len(per_auction), 100))
    assert 7.0 < plain[200:-200].mean() < 8.4  # about 7.7 bids each
    # 60,000 auctions were born; ids count on from first_auction_id.
    assert last[-1] <= kid.max() <= last[-1] + 10
    assert len(np.unique(kid)) >= 59_000


def test_keys_are_decimal_auction_ids_from_1000(stream):
    cfg, data, _bids, _cols = stream
    fresh = q5.make_data(cfg, {}, 3, "")
    first = q5.batch(cfg, fresh, 0, 5000)
    vocab = first.key_vocab
    assert vocab[0] == "1000" and vocab[-1] == str(1000 + len(vocab) - 1)
    assert int(first.numpy("key_id").max()) == len(vocab) - 1
    longer = q5.batch(cfg, fresh, 5000, 10_000).key_vocab
    assert len(longer) > len(vocab) and (longer[: len(vocab)] == vocab).all()
    # The same seed gives the same stream; another seed another.
    again = q5.columns(cfg, q5.make_data(cfg, {}, 3, ""), 0, 1000)
    other = q5.columns(cfg, q5.make_data(cfg, {}, 4, ""), 0, 1000)
    mine = q5.columns(cfg, fresh, 0, 1000)
    assert (again["kid"] == mine["kid"]).all() and (other["kid"] != mine["kid"]).any()


def test_a_bid_lies_in_two_windows(stream):
    cfg, data, _bids, _cols = stream
    want = q5.reference(cfg, data, 200_000)
    # 200,000 bids are 21.7 s of event time: windows -1 .. 4.
    assert want["wid"].tolist() == [-1, 0, 1, 2, 3, 4]
    assert want["total"].sum() == 2 * 200_000
    assert (want["top"] > 256).all() and len(want["hot"]) >= len(want["wid"])
