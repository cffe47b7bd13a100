"""The stream of `nexmark-q8` has the shape the configuration states:
one person to three auctions at their event ids and times, the seller
drawn as ``AuctionGenerator.nextAuction`` draws it (three in four the
current hot seller), and reserves past float32's exact integers."""

import numpy as np
import pytest

from benchmark import run
from benchmark.flows import nexmark_q5 as q5
from benchmark.flows import nexmark_q8 as q8

ROWS = 400_000


@pytest.fixture(scope="module")
def stream():
    cfg = run.Cell("q8.flood").cfg
    data = q8.make_data(cfg, {}, 2147483659, "")
    return cfg, data, q8.columns(cfg, data, 0, ROWS)


def test_one_person_to_three_auctions_at_their_event_ids(stream):
    cfg, _data, cols = stream
    j = np.arange(ROWS)
    ids = (j // 4) * 50 + j % 4
    assert (q8.event_ids(cfg, 0, ROWS) == ids).all()
    assert (cols["ts"] == ids * 100).all()  # 10,000 events a second
    assert (cols["side"] == np.where(j % 4 == 0, 0, 1)).all()
    # A person's key is its own base-0 id, the round it is born in.
    assert (cols["kid"][::4] == j[::4] // 4).all()
    assert 0 <= cols["value"][::4].min() and cols["value"][::4].max() < len(q8.NAMES) == 99


def _next_auction_seller(event_id: int, hot: bool, draw: int) -> int:
    """``AuctionGenerator.nextAuction``'s seller (base 0), written out
    from the Java: ``lastBase0PersonId``, the hot branch, and
    ``PersonGenerator.nextBase0PersonId`` with ``draw`` for
    ``nextLong(random, activePeople + PERSON_ID_LEAD)``."""
    epoch, offset = divmod(event_id, 50)  # totalProportion
    offset = min(offset, 1 - 1)  # personProportion 1
    last = epoch * 1 + offset
    if hot:
        return (last // 100) * 100  # HOT_SELLER_RATIO
    num_people = last + 1
    active_people = min(num_people, 1000)  # numActivePeople
    return num_people - active_people + draw % (active_people + 10)  # PERSON_ID_LEAD


def test_the_seller_draw_is_beams(stream):
    cfg, data, cols = stream
    for lo, hi in ((0, 4000), (300_000, 304_000)):
        ids = q8.event_ids(cfg, lo, hi)
        with np.errstate(over="ignore"):
            drawn = q5._mix(ids.astype(np.uint64) * np.uint64(0x9E3779B97F4A7C15) + data["salt"])
            again = q5._mix(drawn + np.uint64(0xD1B54A32D192ED03))
        hot = ((drawn >> np.uint64(33)) % np.uint64(4) > 0).tolist()
        draws = (again >> np.uint64(11)).tolist()
        auction = (cols["side"][lo:hi] == 1).tolist()
        want = [
            _next_auction_seller(int(e), h, int(d))
            for e, h, d, a in zip(ids, hot, draws, auction)
            if a
        ]
        assert cols["kid"][lo:hi][cols["side"][lo:hi] == 1].tolist() == want


def test_three_auctions_in_four_go_to_the_hot_seller(stream):
    cfg, _data, cols = stream
    auction = cols["side"] == 1
    last = q8.event_ids(cfg, 0, ROWS)[auction] // 50
    seller = cols["kid"][auction]
    on_hot = seller == (last // 100) * 100
    # 3/4 by the draw, and about 1/1010 of the others by chance.
    assert 0.745 < on_hot.mean() < 0.755
    plain = seller[~on_hot]
    people = last[~on_hot] + 1
    assert (plain >= people - np.minimum(people, 1000)).all()
    assert (plain < people + 10).all()


def test_reserves_pass_float32s_exact_integers(stream):
    _cfg, _data, cols = stream
    reserve = cols["value"][cols["side"] == 1].astype(np.int64)
    assert reserve.min() >= 200  # two prices of at least 100 cents
    assert reserve.max() < 2 * 10**8 + 1
    past = reserve > 1 << 24
    assert 0.15 < past.mean() < 0.35
    rounded = reserve.astype(np.float32).astype(np.int64) != reserve
    assert rounded.mean() > 0.1


def test_the_vocabulary_names_the_person_ids(stream):
    cfg, _data, _cols = stream
    first = q8.batch(cfg, q8.make_data(cfg, {}, 3, ""), 0, 5000)
    assert set(first.cols) == {"key_id", "ts", "value", "side"}
    assert first.key_vocab[0] == "1000"
    assert first.key_vocab[-1] == str(1000 + len(first.key_vocab) - 1)
    assert int(first.cols["key_id"].max()) < len(first.key_vocab)
