"""NEXmark Query 11, "user sessions", as Apache Beam's nexmark suite runs
it: the flow, its seeded bid stream, its plain reference and the
comparison.

Source shape: ``queries/Query11.java`` (bids keyed by bidder -> session
windows with a gap of ``windowSizeSec`` -> the bids of each session
counted, written as ``BidsPerSession(bidder, bids)``), the bid generator
of ``sources/generator/model/BidGenerator.java`` over
``GeneratorConfig.java`` and ``PersonGenerator.java``, at the defaults of
``NexmarkConfiguration.java``.  The event stream is ``nexmark-q5``'s
(event ids, event times, the splitmix64 draws): only the bidder is
drawn instead of the auction.  Nothing here imports the program except
:func:`batch` and :func:`build_flow`, which use its public operators,
set-up's walk through the slot table's sizes (nexmark-q5's
``warm_slot_programs``), and the check below that the program can hold the
configuration's guarantees at all.
"""

from datetime import timedelta
from typing import Any, Dict, List, Sequence, Tuple

import numpy as np

from benchmark.flows.nexmark_q5 import (
    ALIGN,
    _mix,
    _mix_of,
    bids_before,
    event_ids,
    warm_slot_programs,
)


def _require_key_retirement() -> None:
    """The configuration states "a bidder is held only while it has an
    open session", and no run's output can show whether that holds.
    A session tier that keeps every key it has seen grows by thousands
    of bidders a second without end and copies every key's clock on
    every delivery.  ``hasattr(DeviceSessionAggState, "let_go")`` holds
    through inheritance from the tumbling tier, so the session class
    has to define its own.  Such a program cannot run this deployment:
    say so at once, before the chip is touched."""
    from bytewax_tpu.engine.window_accel import DeviceSessionAggState

    if "let_go" not in vars(DeviceSessionAggState):
        msg = (
            "nexmark-q11 needs a session tier that lets a bidder go with "
            "its last session (DeviceSessionAggState.let_go): this "
            "program holds every bidder it has seen, against the "
            "configuration's guarantees"
        )
        raise ImportError(msg)


_require_key_retirement()

_US = 1_000_000
#: Bidders the reference groups at a time.
_BLOCK_BIDDERS = 50_000
#: The key the late rows carry to the sink.
LATE = "late"
#: The shapes under which nexmark-q5's set-up walk folds a row a bid.
_ONE_ROW_A_BID = {"shapes": {"window_seconds": 1, "window_period_seconds": 1}}


def _shape(cfg: Dict[str, Any], name: str):
    return cfg["shapes"][name]


# -- the stream ---------------------------------------------------------------


def make_data(cfg, traffic, seed: int, workdir: str) -> Dict[str, Any]:
    """What set-up makes from the seed: the stream's salt, and the key
    vocabulary (decimal strings of the person ids), which grows with
    the stream and is filled as :func:`batch` hands rows out."""
    salt = _mix(np.array([seed], dtype=np.uint64) + np.uint64(0xD6E8FEB86659FD93))
    # Set-up's walk through the slot table's sizes is nexmark-q5's (a
    # session holds a slot as a window does), at one row a bid: a
    # window as long as its period.
    warm_slot_programs(_ONE_ROW_A_BID, traffic)
    return {"salt": salt[0], "vocab": np.empty(0, dtype="U10"), "vocab_filled": 0}


def _last_person(cfg, i: np.ndarray) -> np.ndarray:
    """``lastBase0PersonId`` of event ids ``i``: the newest person made
    by then (a bid comes after its round's persons)."""
    per, _first, _bids = _mix_of(cfg)
    person = int(_shape(cfg, "person_auction_bid")[0])
    return (i // per) * person + person - 1


def columns(cfg, data, lo: int, hi: int) -> Dict[str, np.ndarray]:
    """Bids ``lo:hi`` in arrival order: ``kid`` (the bidder, a base-0
    person id) and ``ts`` (int64 us since ``ALIGN``), as
    ``BidGenerator.nextBid`` draws the bidder: the current hot bidder
    (the first of the newest hundred persons) with probability ``1 -
    1/hot_bidders_ratio``, else ``PersonGenerator.nextBase0PersonId``:
    one of the last ``active_people`` persons or of the next
    ``person_id_lead``."""
    i = event_ids(cfg, lo, hi)
    ts = i * (_US // int(_shape(cfg, "events_per_second")))
    last = _last_person(cfg, i)
    with np.errstate(over="ignore"):
        drawn = _mix(i.astype(np.uint64) * np.uint64(0x9E3779B97F4A7C15) + data["salt"])
        again = _mix(drawn + np.uint64(0xD1B54A32D192ED03))
    every = int(_shape(cfg, "hot_bidder_every"))
    is_hot = (drawn >> np.uint64(33)) % np.uint64(_shape(cfg, "hot_bidders_ratio")) > 0
    people = last + 1
    active = np.minimum(people, int(_shape(cfg, "active_people")))
    span = (active + int(_shape(cfg, "person_id_lead"))).astype(np.uint64)
    plain = people - active + ((again >> np.uint64(11)) % span).astype(np.int64)
    kid = np.where(is_hot, (last // every) * every + 1, plain)
    return {"kid": kid.astype(np.int32), "ts": ts}


def _vocab(cfg, data, upto: int) -> np.ndarray:
    """The key vocabulary up to entry ``upto`` at least, filled on from
    where the last call stopped: a view of one growing buffer, never
    shorter than the one before (the engine wants it append-only)."""
    buf, filled = data["vocab"], data["vocab_filled"]
    if upto > len(buf):
        grown = np.empty(max(2 * len(buf), upto, 1 << 16), dtype=buf.dtype)
        grown[:filled] = buf[:filled]
        buf = data["vocab"] = grown
    if upto > filled:
        first = int(_shape(cfg, "first_person_id"))
        buf[filled:upto] = np.arange(first + filled, first + upto).astype(buf.dtype)
        filled = data["vocab_filled"] = upto
    return buf[:filled]


#: The program's counters read at every poll (:func:`batch`), so that
#: a metric can say what they read when the window's last poll was
#: handed out: after end of input every session is closed.
SAMPLED = (
    "window_keys_opened",
    "window_keys_retired",
    "session_opens",
    "session_closes",
    "session_keys_remembered",
)


def batch(cfg, data, lo: int, hi: int):
    """Bids ``lo:hi`` as the columnar batch the source hands out."""
    from bytewax_tpu.engine import flight
    from bytewax_tpu.engine.arrays import ArrayBatch

    counters = flight.RECORDER.counters
    data.setdefault("counter_samples", []).append(
        (lo,) + tuple(counters.get(name) for name in SAMPLED)
    )
    cols = columns(cfg, data, lo, hi)
    base = np.datetime64(ALIGN.replace(tzinfo=None), "us")
    return ArrayBatch(
        {"key_id": cols["kid"], "ts": base + cols["ts"].astype("timedelta64[us]")},
        key_vocab=_vocab(cfg, data, int(cols["kid"].max()) + 1 if hi > lo else 0),
    )


# -- the flow -----------------------------------------------------------------


def _late_tagged(lates: List[Any]) -> List[Any]:
    return [(LATE, (-1, 0)) for _bidder in lates]


def build_flow(cfg, data, source, sink):
    """``op.input`` -> ``w.count_window`` (sessions by bidder) ->
    ``op.output``; the late rows go to the same sink, tagged.  The
    ``meta`` stream is not tapped: Q11's answer has no bounds."""
    import bytewax_tpu.operators as op
    import bytewax_tpu.operators.windowing as w
    from bytewax_tpu import xla
    from bytewax_tpu.dataflow import Dataflow

    clock = w.EventClock(
        ts_getter=xla.column_ts,
        wait_for_system_duration=timedelta(
            seconds=_shape(cfg, "wait_for_system_duration_s")
        ),
    )
    flow = Dataflow("bench_nexmark_q11")
    bids = op.input("inp", flow, source)
    sessions = w.count_window(
        "bids",
        bids,
        clock,
        w.SessionWindower(gap=timedelta(seconds=_shape(cfg, "session_gap_seconds"))),
        key=lambda row: row[0],
    )
    late = op.flat_map_batch("late_tagged", sessions.late, _late_tagged)
    op.output("out", op.merge("results", sessions.down, late), sink)
    return flow


# -- the plain reference ------------------------------------------------------


def _people(cfg, served: int) -> int:
    """Persons that can have bid among the first ``served`` bids."""
    if served <= 0:
        return 0
    last = int(_last_person(cfg, event_ids(cfg, served - 1, served))[0])
    return last + 1 + int(_shape(cfg, "person_id_lead"))


def reference(cfg, data, served: int, precision: str = "exact", twice=None, split=None):
    """Every session of the first ``served`` bids: ``kid`` (the bidder),
    ``rank`` (its place among the bidder's sessions, in time) and
    ``bids``.  Plain numpy, a block of bidders at a time: their bids
    (a bidder is drawn only while it is among the newest persons),
    grouped by bidder, sorted by time and split where two bids lie
    more than the gap apart.  ``precision`` other than exact, ``twice``
    (a bid to count twice) and ``split`` (a bid that starts a session
    of its own) make the controls."""
    per = _mix_of(cfg)[0]
    gap = int(_shape(cfg, "session_gap_seconds")) * _US
    lead = int(_shape(cfg, "person_id_lead"))
    reach = int(_shape(cfg, "active_people")) + int(_shape(cfg, "hot_bidder_every"))
    parts: Dict[str, List[np.ndarray]] = {"kid": [], "rank": [], "bids": []}
    for p0 in range(0, _people(cfg, served), _BLOCK_BIDDERS):
        p1 = p0 + _BLOCK_BIDDERS
        # A bidder of p0..p1-1 bids while the newest person lies in
        # p0 - lead .. p1 + active people (+ a hot bidder's hundred).
        lo = bids_before(cfg, max(p0 - lead - 1, 0) * per)
        hi = min(served, bids_before(cfg, (p1 + reach) * per))
        cols = columns(cfg, data, lo, hi)
        kid, ts = cols["kid"].astype(np.int64), cols["ts"]
        if twice is not None and lo <= twice < hi:
            kid = np.insert(kid, twice - lo, kid[twice - lo])
            ts = np.insert(ts, twice - lo, ts[twice - lo])
        mine = (kid >= p0) & (kid < p1)
        starts_alone = np.zeros(len(kid), dtype=bool)
        if split is not None and lo <= split < hi:
            starts_alone[split - lo] = True
        block = sessions_of(kid[mine], ts[mine], gap, starts_alone[mine])
        if precision != "exact":
            block["bids"] = _low_precision_counts(block["bids"], precision)
        for name, col in parts.items():
            col.append(block[name])
    return {
        name: np.concatenate(col) if col else np.empty(0, dtype=np.int64)
        for name, col in parts.items()
    }


def _rank(kid: np.ndarray) -> np.ndarray:
    """Each entry's place among the entries of its key (``kid``
    grouped)."""
    first = np.flatnonzero(np.diff(kid, prepend=-1))
    return np.arange(len(kid)) - np.repeat(first, np.diff(np.append(first, len(kid))))


def sessions_of(kid: np.ndarray, ts: np.ndarray, gap, starts_alone=None):
    """The sessions of rows ``kid`` (non-negative), ``ts``: each key's
    rows by time, split where two lie more than ``gap`` apart (and
    before a row of ``starts_alone``); ``kid``, ``rank`` (the place in
    time among the key's sessions) and ``bids`` a session."""
    order = np.lexsort((ts, kid))
    kid, ts = kid[order], ts[order]
    head = np.ones(len(kid), dtype=bool)
    head[1:] = (kid[1:] != kid[:-1]) | (np.diff(ts) > gap)
    if starts_alone is not None:
        head |= starts_alone[order]
    at = np.flatnonzero(head)
    return {
        "kid": kid[at],
        "rank": _rank(kid[at]),
        "bids": np.diff(np.append(at, len(kid))),
    }


def _low_precision_counts(count: np.ndarray, precision: str) -> np.ndarray:
    """What an accumulator of ``precision`` holds after ``count``
    additions of one, rounded after every addition."""
    if precision != "bfloat16":
        raise ValueError(f"no control precision {precision!r}")
    import ml_dtypes

    held = np.zeros(len(count), dtype=np.float32)
    live, done = np.nonzero(count)[0], 0
    while len(live):
        held[live] = (held[live] + 1).astype(ml_dtypes.bfloat16).astype(np.float32)
        done += 1
        live = live[count[live] > done]
    return held.astype(np.int64)


def undecided(cfg, data, polls: Sequence[Tuple[float, int, int]], ended: float):
    """Bidders the wall clock, and not the data, may have decided.
    Bids arrive in event-time order and the clock waits
    ``wait_for_system_duration_s``: while no two polls lie that far
    apart the wall clock makes no bid late and closes no session that
    a later bid would have extended (a bidder's bids span two polls at
    most); once they do, every bidder is."""
    times = [p[0] for p in polls] + [ended]
    gap = max((b - a for a, b in zip(times, times[1:])), default=0.0)
    if gap < _shape(cfg, "wait_for_system_duration_s"):
        return np.empty(0, dtype=np.int64)
    return np.arange(_people(cfg, polls[-1][2] if polls else 0), dtype=np.int64)


# -- what the sink received ---------------------------------------------------


def pack(items: List[Any]):
    """One sink write as arrays: a row ``bidder, session id, bids`` a
    session, and the number of late rows."""
    if not items:
        return np.empty((0, 3), dtype=np.int64), 0
    keys = np.array([key for key, _value in items])
    on_time = keys != LATE
    values = np.array([value for _key, value in items], dtype=np.int64)[on_time]
    rows = np.column_stack([keys[on_time].astype(np.int64), values])
    return rows, len(items) - int(on_time.sum())


def result_arrays(cfg, packs: List[Any]) -> Dict[str, np.ndarray]:
    """The sink's writes as the reference's columns: per session
    ``kid`` (from the first person id), ``rank`` (by session id among
    the bidder's), ``bids``; ``wid`` and the number of late rows."""
    rows = np.concatenate([p[0] for p in packs]) if packs else np.empty((0, 3), np.int64)
    kid = rows[:, 0] - int(_shape(cfg, "first_person_id"))
    order = np.lexsort((rows[:, 1], kid))
    kid, wid, bids = kid[order], rows[order, 1], rows[order, 2]
    return {
        "kid": kid,
        "rank": _rank(kid),
        "bids": bids,
        "wid": wid,
        "late": sum(p[1] for p in packs),
    }


def compare(cfg, got, want, open_kids=()) -> Dict[str, float]:
    """The numbers ``correct`` is decided on, all exact: per bidder as
    many sessions as the reference, each with its count (sessions in
    time order against the program's in id order), none written twice,
    every bid counted once, none late.  ``open_kids``
    (:func:`undecided`) are left out on both sides."""
    g = ~np.isin(got["kid"], open_kids)
    w = ~np.isin(want["kid"], open_kids)
    g_kid, g_rank, g_bids = got["kid"][g], got["rank"][g], got["bids"][g]
    w_kid, w_rank, w_bids = want["kid"][w], want["rank"][w], want["bids"][w]
    size = int(max(g_kid.max(initial=-1), w_kid.max(initial=-1))) + 1
    n_got = np.bincount(g_kid, minlength=size)
    n_want = np.bincount(w_kid, minlength=size)
    g_comp = (g_kid << 20) + g_rank
    w_comp = (w_kid << 20) + w_rank
    _both, gi, wi = np.intersect1d(g_comp, w_comp, assume_unique=True, return_indices=True)
    twice = len(got["wid"][g]) - len(np.unique((g_kid << 32) + got["wid"][g]))
    return {
        "sessions_missing": int(np.maximum(n_want - n_got, 0).sum()),
        "sessions_extra": int(np.maximum(n_got - n_want, 0).sum()),
        "sessions_twice": int(twice),
        "count_wrong": int((g_bids[gi] != w_bids[wi]).sum()),
        "rows_unanswered": abs(int(w_bids.sum()) - int(g_bids.sum())),
        "rows_late": int(got.get("late", 0)),
    }


def control_results(cfg, data, served: int, which: str) -> Dict[str, np.ndarray]:
    """The reference put in the program's place with one thing lowered
    or broken: ``bfloat16`` (counts held in the precision below the
    stated exact integers), ``row_twice`` (one bid folded twice),
    ``split_session`` (one bid starts a session of its own)."""
    if which == "bfloat16":
        want = reference(cfg, data, served, precision="bfloat16")
    elif which == "row_twice":
        want = reference(cfg, data, served, twice=served // 2)
    elif which == "split_session":
        want = reference(cfg, data, served, split=served // 2)
    else:
        raise ValueError(f"no control {which!r}")
    return dict(want, wid=want["rank"], late=0)


CONTROLS = ("bfloat16", "row_twice", "split_session")
