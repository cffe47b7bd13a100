"""The bid stream of `nexmark-q11` has the shape the configuration
states: the bidder drawn as ``BidGenerator.nextBid`` draws it, three
bids in four on the current hot bidder, and a session an ordinary
bidder at Beam's defaults."""

import numpy as np
import pytest

from benchmark import run
from benchmark.flows import nexmark_q11 as q11
from benchmark.flows import nexmark_q5 as q5

EVENTS = 1_000_000


@pytest.fixture(scope="module")
def stream():
    cfg = run.Cell("q11.flood").cfg
    data = q11.make_data(cfg, {}, 2147483659, "")
    bids = q5.bids_before(cfg, EVENTS)
    return cfg, data, bids, q11.columns(cfg, data, 0, bids)


def _next_bid_bidder(event_id: int, hot: bool, draw: int) -> int:
    """``BidGenerator.nextBid``'s bidder (base 0), written out from the
    Java: ``lastBase0PersonId``, the hot branch, and
    ``PersonGenerator.nextBase0PersonId`` with ``draw`` for
    ``nextLong(random, activePeople + PERSON_ID_LEAD)``."""
    epoch, offset = divmod(event_id, 50)  # totalProportion
    offset = min(offset, 1 - 1)  # personProportion 1
    last = epoch * 1 + offset
    if hot:
        return (last // 100) * 100 + 1  # HOT_BIDDER_RATIO
    num_people = last + 1
    active_people = min(num_people, 1000)  # numActivePeople
    return num_people - active_people + draw % (active_people + 10)  # PERSON_ID_LEAD


def test_the_bidder_draw_is_beams(stream):
    cfg, data, _bids, cols = stream
    lo, hi = 0, 4000  # events 4 .. 4,349: the first persons, under 1000 active
    ids = q5.event_ids(cfg, lo, hi)
    with np.errstate(over="ignore"):
        drawn = q5._mix(ids.astype(np.uint64) * np.uint64(0x9E3779B97F4A7C15) + data["salt"])
        again = q5._mix(drawn + np.uint64(0xD1B54A32D192ED03))
    hot = ((drawn >> np.uint64(33)) % np.uint64(4) > 0).tolist()
    draws = (again >> np.uint64(11)).tolist()
    want = [_next_bid_bidder(int(e), h, int(d)) for e, h, d in zip(ids, hot, draws)]
    assert cols["kid"][lo:hi].tolist() == want
    later = q11.columns(cfg, data, 600_000, 604_000)
    ids = q5.event_ids(cfg, 600_000, 604_000)
    with np.errstate(over="ignore"):
        drawn = q5._mix(ids.astype(np.uint64) * np.uint64(0x9E3779B97F4A7C15) + data["salt"])
        again = q5._mix(drawn + np.uint64(0xD1B54A32D192ED03))
    hot = ((drawn >> np.uint64(33)) % np.uint64(4) > 0).tolist()
    want = [
        _next_bid_bidder(int(e), h, int(d))
        for e, h, d in zip(ids, hot, (again >> np.uint64(11)).tolist())
    ]
    assert later["kid"].tolist() == want
    assert (later["ts"] == ids * 100).all()  # 10,000 events a second


def test_three_bids_in_four_go_to_the_hot_bidder(stream):
    cfg, _data, bids, cols = stream
    last = q5.event_ids(cfg, 0, bids) // 50
    on_hot = cols["kid"] == (last // 100) * 100 + 1
    # 3/4 by the draw, and about 1/1010 of the others by chance.
    assert 0.745 < on_hot.mean() < 0.755
    per_bidder = np.bincount(cols["kid"])
    # A new hot bidder every 100 persons = 4,600 bids: about 3,450 each.
    hot = per_bidder[1::100][1:-1]
    assert 3_200 < np.median(hot) < 3_700


def test_an_ordinary_bidder_has_one_session(stream):
    """At the defaults an ordinary bidder is drawn for 5.05 s of event
    time, about 11.5 bids 0.44 s apart on average: one session each,
    as the reference groups them."""
    cfg, data, bids, cols = stream
    want = q11.reference(cfg, data, bids)
    assert want["bids"].sum() == bids
    assert (want["rank"] == 0).all()  # no bidder has a second session
    plain = want["bids"][(want["kid"] % 100 != 1)]
    assert 10.5 < plain[100:-100].mean() < 12.5
    # 20,000 persons were born; ids count on from first_person_id.
    assert 19_900 <= len(want["kid"]) <= 20_010
    first = q11.batch(cfg, q11.make_data(cfg, {}, 3, ""), 0, 5000).key_vocab
    assert first[0] == "1000" and first[-1] == str(1000 + len(first) - 1)
