"""NEXmark Query 5, "hot items", as Apache Beam's nexmark suite runs it:
the flow, its seeded bid stream, its plain reference and the
comparison.

Source shape: ``queries/Query5.java`` (bids -> sliding windows of
``windowSizeSec`` every ``windowPeriodSec`` -> count per auction -> per
window the auctions with the most bids), the bid generator of
``sources/generator/model/BidGenerator.java`` over
``GeneratorConfig.java``, at the defaults of
``NexmarkConfiguration.java``.  Nothing here imports the program except
:func:`batch` and :func:`build_flow`, which use its public operators,
:func:`warm_slot_programs`, set-up's walk through the slot table's
sizes, and the check below that the program can hold the
configuration's guarantees at all.

The stream is a function of the row's index and the seed: row ``j`` of
the schedule is the ``j``-th bid of the full event stream (person and
auction events are not made: Query 5 filters them away first), with
the event id and the event time it has there.
"""

from datetime import datetime, timedelta, timezone
from typing import Any, Dict, List, Sequence, Tuple

import numpy as np


def _require_key_retirement() -> None:
    """The configuration states "a key is held only while it has an
    open window", and no run's output can show whether that holds.  A
    window tier that keeps every key it has seen also snapshots them
    all at each epoch close, a stall as long as the clock's wait at
    this stream's 65,000 new keys a million bids, after which the wall
    clock decides what is late.  Such a program cannot run this
    deployment: say so at once, before the chip is touched."""
    from bytewax_tpu.engine.window_accel import DeviceWindowAggState

    if not hasattr(DeviceWindowAggState, "let_go"):
        msg = (
            "nexmark-q5 needs a window tier that lets a key go with its "
            "last window (DeviceWindowAggState.let_go): this program "
            "holds every key it has seen, against the configuration's "
            "guarantees"
        )
        raise ImportError(msg)


_require_key_retirement()

#: Event time zero of every generated stream.
ALIGN = datetime(2022, 1, 1, tzinfo=timezone.utc)
_US = 1_000_000
#: Windows the reference groups at a time.
_BLOCK_WINDOWS = 16
#: The key the first stage's late rows carry to the sink.
LATE = "late"


def _shape(cfg: Dict[str, Any], name: str):
    return cfg["shapes"][name]


# -- the stream ---------------------------------------------------------------


def _mix(x: np.ndarray) -> np.ndarray:
    """splitmix64's finalizer over uint64 (wrapping arithmetic)."""
    x = (x ^ (x >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
    x = (x ^ (x >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
    return x ^ (x >> np.uint64(31))


def _mix_of(cfg) -> Tuple[int, int, int]:
    """``(events a round, first bid's place, bids a round)`` of the
    person : auction : bid proportion."""
    person, auction, bid = _shape(cfg, "person_auction_bid")
    return person + auction + bid, person + auction, bid


def make_data(cfg, traffic, seed: int, workdir: str) -> Dict[str, Any]:
    """What set-up makes from the seed: the stream's salt, and the key
    vocabulary (decimal strings of the auction ids), which grows with
    the stream and is filled as :func:`batch` hands rows out."""
    salt = _mix(np.array([seed], dtype=np.uint64) + np.uint64(0x9E3779B97F4A7C15))
    warm_slot_programs(cfg, traffic)
    return {"salt": salt[0], "vocab": np.empty(0, dtype="U10"), "vocab_filled": 0}


#: Reset lengths the slot table pads to (``2**3`` up to twice a
#: poll's closes), and the table size from which a poll can close
#: anything (the first closes come 10 s of system time into a run).
_RESET_POWS = range(3, 16)
_RESETS_FROM = 1 << 19


def warm_slot_programs(cfg, traffic) -> int:
    """Set-up's second half.  The set-up rows leave the first stage's
    slot table at a fraction of what a backlog holds open (a window is
    held ``length + wait`` of system time); it doubles on the way
    there, and each size has its own programs: the growth, the fold
    of a poll's rows, the reset of reused slots at each padded length,
    the fetch.  Walk a table of the same kind through those sizes, up
    to ``warm_windows_per_poll_row`` (key, window)s for each row of a
    poll (the traffic's: a backlog's open windows grow with the poll),
    so that the window compiles nothing; the programs land in the
    process's and the persistent compile cache, the table is dropped.
    Returns the size reached (0: the traffic asks for none)."""
    upto = int(traffic.get("warm_windows_per_poll_row", 0)) * int(
        traffic.get("poll_rows", 0)
    )
    if not upto:
        return 0
    from bytewax_tpu.engine.xla import DeviceAggState

    rows = _panes(cfg) * int(traffic["poll_rows"])
    agg = DeviceAggState("count")
    ones = np.ones(rows, dtype=np.float64)
    slots = agg.open_ids(np.empty(1 << 16))
    while True:
        agg.update_ids(np.resize(slots, rows), ones)
        agg.states_of(slots[:1])
        if agg.capacity >= _RESETS_FROM:
            for n in (1 << p for p in _RESET_POWS):
                # Freed slots are reset when they are given out again.
                agg.release_ids(slots[:n])
                agg.open_ids(np.empty(n))
                agg.states_of(slots[:1])
        if agg.capacity > upto:
            return agg.capacity
        # Just past full: one doubling.
        grown = agg.open_ids(np.empty(agg.capacity - len(slots)))
        slots = np.concatenate([slots, grown])


def event_ids(cfg, lo: int, hi: int) -> np.ndarray:
    """Event ids of bids ``lo:hi``: the bids of a round of 50 events
    follow its person and its auctions."""
    per, first, bids = _mix_of(cfg)
    j = np.arange(lo, hi, dtype=np.int64)
    return (j // bids) * per + first + j % bids


def bids_before(cfg, event_id: int) -> int:
    """How many bids have an event id below ``event_id``."""
    per, first, bids = _mix_of(cfg)
    return (event_id // per) * bids + min(max(event_id % per - first, 0), bids)


def columns(cfg, data, lo: int, hi: int) -> Dict[str, np.ndarray]:
    """Bids ``lo:hi`` in arrival order: ``kid`` (the auction, counted
    from the first auction id) and ``ts`` (int64 us since ``ALIGN``),
    as ``BidGenerator.nextBid`` draws the auction: the current hot one
    with probability ``1 - 1/hot_auction_ratio``, else one of the last
    ``in_flight_auctions`` or of the next ``auction_id_lead``."""
    per, _first, _bids = _mix_of(cfg)
    i = event_ids(cfg, lo, hi)
    ts = i * (_US // int(_shape(cfg, "events_per_second")))
    last = (i // per) * int(_shape(cfg, "person_auction_bid")[1]) + 2
    with np.errstate(over="ignore"):
        drawn = _mix(i.astype(np.uint64) * np.uint64(0x9E3779B97F4A7C15) + data["salt"])
        again = _mix(drawn + np.uint64(0xD1B54A32D192ED03))
    every = int(_shape(cfg, "hot_auction_every"))
    in_flight = int(_shape(cfg, "in_flight_auctions"))
    is_hot = (drawn >> np.uint64(33)) % np.uint64(_shape(cfg, "hot_auction_ratio")) > 0
    low = np.maximum(last - in_flight, 0)
    span = (last - low + 1 + int(_shape(cfg, "auction_id_lead"))).astype(np.uint64)
    plain = low + ((again >> np.uint64(11)) % span).astype(np.int64)
    kid = np.where(is_hot, (last // every) * every, plain)
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
        first = int(_shape(cfg, "first_auction_id"))
        buf[filled:upto] = np.arange(first + filled, first + upto).astype(buf.dtype)
        filled = data["vocab_filled"] = upto
    return buf[:filled]


#: The program's counters read at every poll (:func:`batch`), so that
#: a metric can say what they read when the window's last poll was
#: handed out: after end of input every key and window is gone.
SAMPLED = ("window_keys_opened", "window_keys_retired", "window_opens", "close_emit_rows")


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


def _by_window(counts: List[Any]) -> List[Any]:
    """``(auction, (window id, count))`` -> ``(window id, (auction,
    count))``: the first stage's results keyed for the second."""
    return [(str(wid), (auction, count)) for auction, (wid, count) in counts]


def _late_tagged(lates: List[Any]) -> List[Any]:
    return [(LATE, (auction, wid)) for auction, (wid, _ts) in lates]


def hot_items_logic(linger_s: float):
    """The second stage's logic, one per window id: the highest count,
    the auctions that have it, the number of auctions and the sum of
    counts; written once, ``linger_s`` of system time after the
    window's last count arrived or at end of input, and then
    forgotten.  A count that comes later starts a new part."""
    from bytewax_tpu.operators import StatefulBatchLogic

    linger = timedelta(seconds=linger_s)

    class _HotItems(StatefulBatchLogic):
        def __init__(self, resume):
            self.top, self.hot, self.auctions, self.total, self.due = resume or (
                0, (), 0, 0, None,
            )

        def on_batch(self, values):
            counts = [count for _auction, count in values]
            top = max(counts)
            if top >= self.top:
                kept = self.hot if top == self.top else ()
                self.hot = kept + tuple(a for a, c in values if c == top)
                self.top = top
            self.auctions += len(counts)
            self.total += sum(counts)
            self.due = datetime.now(timezone.utc) + linger
            return (), StatefulBatchLogic.RETAIN

        def _part(self):
            part = (self.top, self.hot, self.auctions, self.total)
            return (part,), StatefulBatchLogic.DISCARD

        def on_notify(self):
            return self._part()

        def on_eof(self):
            return self._part()

        def notify_at(self):
            return self.due

        def snapshot(self):
            return (self.top, self.hot, self.auctions, self.total, self.due)

    return _HotItems


def build_flow(cfg, data, source, sink):
    """``op.input`` -> ``w.count_window`` (sliding) -> re-key by window
    -> ``op.stateful_batch`` (hot items) -> ``op.output``; the first
    stage's late rows go to the same sink, tagged."""
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
    flow = Dataflow("bench_nexmark_q5")
    bids = op.input("inp", flow, source)
    counts = w.count_window(
        "bids",
        bids,
        clock,
        w.SlidingWindower(
            align_to=ALIGN,
            length=timedelta(seconds=_shape(cfg, "window_seconds")),
            offset=timedelta(seconds=_shape(cfg, "window_period_seconds")),
        ),
        key=lambda row: row[0],
    )
    by_window = op.flat_map_batch("by_window", counts.down, _by_window)
    hot = op.stateful_batch(
        "hot_items", by_window, hot_items_logic(_shape(cfg, "hot_items_linger_s"))
    )
    late = op.flat_map_batch("late_tagged", counts.late, _late_tagged)
    op.output("out", op.merge("results", hot, late), sink)
    return flow


# -- the plain reference ------------------------------------------------------


def _period_us(cfg) -> int:
    return int(_shape(cfg, "window_period_seconds")) * _US


def _panes(cfg) -> int:
    """Windows that contain an instant (the sliding fan-out)."""
    return int(_shape(cfg, "window_seconds")) // int(_shape(cfg, "window_period_seconds"))


def reference(cfg, data, served: int, precision: str = "exact", twice=None, once=None):
    """Per window the highest count of an auction, the auctions that
    have it, the number of auctions with a bid and the sum of counts,
    over the first ``served`` bids: numpy, a histogram over (window,
    auction) for a block of whole windows at a time.  Bids arrive in
    event-time order, so none is late by the data.  ``precision``
    other than exact, ``twice`` (a bid to count twice) and ``once`` (a
    bid to count in the newest of its windows only) make the
    controls."""
    names = ("wid", "top", "auctions", "total", "hot")
    parts: Dict[str, List[np.ndarray]] = {name: [] for name in names}
    if served <= 0:
        return {name: np.empty(0, dtype=np.int64) for name in names}
    period, panes = _period_us(cfg), _panes(cfg)
    per_us = _US // int(_shape(cfg, "events_per_second"))
    newest = int(event_ids(cfg, served - 1, served)[0]) * per_us // period
    for w0 in range(1 - panes, newest + 1, _BLOCK_WINDOWS):
        w1 = min(w0 + _BLOCK_WINDOWS, newest + 1)
        # Bids of the windows w0..w1-1: event times [w0, w1 - 1 + panes) periods.
        lo = bids_before(cfg, max(w0, 0) * period // per_us)
        hi = min(served, bids_before(cfg, (w1 - 1 + panes) * period // per_us))
        cols = columns(cfg, data, lo, hi)
        kid, newest_wid = cols["kid"].astype(np.int64), cols["ts"] // period
        if twice is not None and lo <= twice < hi:
            at = twice - lo
            kid = np.concatenate([kid[: at + 1], kid[at:]])
            newest_wid = np.concatenate([newest_wid[: at + 1], newest_wid[at:]])
        wids = newest_wid[:, None] - np.arange(panes, dtype=np.int64)[None, :]
        inside = (wids >= w0) & (wids < w1)
        if once is not None and lo <= once < hi:
            inside[once - lo, 1:] = False
        kids = np.broadcast_to(kid[:, None], wids.shape)
        block = _group(wids[inside], kids[inside], w0, w1, precision)
        for name in names:
            parts[name].append(block[name])
    return {name: np.concatenate(parts[name]) for name in names}


def hot_comp(wid, auction) -> np.ndarray:
    """One sortable int64 per (window id, auction), window-major."""
    return (np.asarray(wid, dtype=np.int64) << 32) + np.asarray(auction, dtype=np.int64)


def _group(wid, kid, w0: int, w1: int, precision: str) -> Dict[str, np.ndarray]:
    """The windows ``w0..w1-1`` from one (window, auction) entry a bid
    and window: counts as a dense table, a row a window."""
    k0 = int(kid.min())
    span = int(kid.max()) - k0 + 1
    counts = np.bincount((wid - w0) * span + (kid - k0), minlength=(w1 - w0) * span)
    if precision != "exact":
        counts = _low_precision_counts(counts, precision)
    counts = counts.reshape(w1 - w0, span)
    top = counts.max(axis=1)
    seen = top > 0
    at_wid, at_kid = np.nonzero((counts == top[:, None]) & seen[:, None])
    return {
        "wid": np.arange(w0, w1, dtype=np.int64)[seen],
        "top": top[seen],
        "auctions": (counts > 0).sum(axis=1)[seen],
        "total": counts.sum(axis=1)[seen],
        "hot": hot_comp(at_wid + w0, at_kid + k0),
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
    """Window ids the wall clock, and not the data, may have decided.
    Bids arrive in event-time order and the clock waits
    ``wait_for_system_duration_s``: while no two polls lie that far
    apart the wall clock makes no row late, and no window is left
    out; once they do, every window is."""
    times = [p[0] for p in polls] + [ended]
    gap = max((b - a for a, b in zip(times, times[1:])), default=0.0)
    if gap < _shape(cfg, "wait_for_system_duration_s"):
        return np.empty(0, dtype=np.int64)
    return reference(cfg, data, polls[-1][2] if polls else 0)["wid"]


# -- what the sink received ---------------------------------------------------


def pack(items: List[Any]):
    """One sink write as arrays: a row ``window id, highest count,
    auctions, sum of counts`` a part, a row ``window id, auction,
    highest count of its part`` for every auction that has it, and the
    number of late rows."""
    parts = [(int(wid), part) for wid, part in items if wid != LATE]
    rows = np.array(
        [(wid, top, auctions, total) for wid, (top, _hot, auctions, total) in parts],
        dtype=np.int64,
    ).reshape(len(parts), 4)
    hot = np.array(
        [(wid, int(a), top) for wid, (top, hot, _n, _total) in parts for a in hot],
        dtype=np.int64,
    ).reshape(-1, 3)
    return rows, hot, len(items) - len(parts)


def result_arrays(cfg, packs: List[Any]) -> Dict[str, np.ndarray]:
    """The sink's writes with a window's parts merged as the consumer
    merges them: the maximum, the union of the auctions at the
    maximum, and sums.  Auctions as the reference counts them, from
    the first auction id."""
    rows = np.concatenate([p[0] for p in packs]) if packs else np.empty((0, 4), np.int64)
    hot = np.concatenate([p[1] for p in packs]) if packs else np.empty((0, 3), np.int64)
    wid, part_of = np.unique(rows[:, 0], return_inverse=True)
    top = np.zeros(len(wid), dtype=np.int64)
    np.maximum.at(top, part_of, rows[:, 1])
    at_top = hot[:, 2] == top[np.searchsorted(wid, hot[:, 0])]
    first = int(_shape(cfg, "first_auction_id"))
    return {
        "wid": wid,
        "top": top,
        "auctions": np.bincount(part_of, rows[:, 2], len(wid)).astype(np.int64),
        "total": np.bincount(part_of, rows[:, 3], len(wid)).astype(np.int64),
        "hot": np.sort(hot_comp(hot[at_top, 0], hot[at_top, 1] - first)),
        "parts": len(rows),
        "late": sum(p[2] for p in packs),
    }


def compare(cfg, got, want, open_wids=()) -> Dict[str, float]:
    """The numbers ``correct`` is decided on, all exact: the window
    sets equal; per window the highest count, the set of auctions
    that have it and the number of auctions; the sum of all counts;
    no late row.  ``open_wids`` (:func:`undecided`) are left out on
    both sides."""
    decided_got = ~np.isin(got["wid"], open_wids)
    decided_want = ~np.isin(want["wid"], open_wids)
    g_wid, w_wid = got["wid"][decided_got], want["wid"][decided_want]
    both_g = np.isin(g_wid, w_wid)
    both_w = np.isin(w_wid, g_wid)

    def of(side, decided, both, name):
        return side[name][decided][both]

    hot_g = got["hot"][~np.isin(got["hot"] >> 32, open_wids)]
    hot_w = want["hot"][~np.isin(want["hot"] >> 32, open_wids)]
    hot_wrong = np.unique(np.setxor1d(hot_g, hot_w) >> 32)
    hot_wrong = hot_wrong[np.isin(hot_wrong, g_wid[both_g])]
    fan_out = _panes(cfg)
    answered = int(got["total"][decided_got].sum())
    asked = int(want["total"][decided_want].sum())
    return {
        "windows_missing": int((~both_w).sum()),
        "windows_extra": int((~both_g).sum()),
        "max_wrong": int(
            (of(got, decided_got, both_g, "top") != of(want, decided_want, both_w, "top")).sum()
        ),
        "hot_set_wrong": len(hot_wrong),
        "auctions_wrong": int(
            (
                of(got, decided_got, both_g, "auctions")
                != of(want, decided_want, both_w, "auctions")
            ).sum()
        ),
        "rows_unanswered": -(-abs(asked - answered) // fan_out),
        "rows_late": int(got.get("late", 0)),
        "undecided_share": len(open_wids) / max(len(want["wid"]), 1),
    }


def control_results(cfg, data, served: int, which: str) -> Dict[str, np.ndarray]:
    """The reference put in the program's place with one thing lowered
    or broken: ``bfloat16`` (counts held in the precision below the
    stated exact integers), ``row_twice`` (one bid folded twice),
    ``one_window`` (one bid counted in one of its two windows)."""
    if which == "bfloat16":
        return reference(cfg, data, served, precision="bfloat16")
    if which == "row_twice":
        return reference(cfg, data, served, twice=served // 2)
    if which == "one_window":
        return reference(cfg, data, served, once=served // 2)
    raise ValueError(f"no control {which!r}")


CONTROLS = ("bfloat16", "row_twice", "one_window")
