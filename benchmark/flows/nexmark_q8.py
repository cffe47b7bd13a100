"""NEXmark Query 8, "monitor new users", as Apache Beam's nexmark suite
runs it: the flow, its seeded stream of persons and auctions, its plain
reference and the comparison.

Source shape: ``queries/Query8.java`` (new persons keyed by id and new
auctions keyed by seller, each into ``FixedWindows(windowSizeSec)``,
joined with ``CoGroupByKey``: for every auction whose seller was
created as a person in the same window, ``IdNameReserve(person.id,
person.name, auction.reserve)``), the generators of
``sources/generator/model/PersonGenerator.java`` and
``AuctionGenerator.java`` over ``GeneratorConfig.java``, at the defaults
of ``NexmarkConfiguration.java``.  The event stream is
``nexmark-q5``'s (event ids, event times, the splitmix64 draws), with
the persons and auctions kept and the bids left out; the seller is
drawn as ``nexmark-q11`` draws a bidder.  Nothing here imports the
program except :func:`batch` and :func:`build_flow`, which use its
public operators, set-up's walk through the sizes of the slot table
(nexmark-q5's ``warm_slot_programs``) and of the join's row store, and
the check below that the program can run this deployment at all.
"""

from datetime import timedelta
from operator import itemgetter
from typing import Any, Dict, List, Sequence, Tuple

import numpy as np

from benchmark.flows.nexmark_q5 import ALIGN, _mix, _mix_of, warm_slot_programs
from benchmark.flows.nexmark_q11 import _last_person


def _require_device_join() -> None:
    """The configuration holds a window's rows on the device until it
    closes; a program whose plan leaves ``join_window`` on the host
    tier would run Query 8 as per-item Python over millions of lists.
    Say so at once, before the chip is touched."""
    from bytewax_tpu.engine import window_accel

    if not hasattr(window_accel, "JoinAccelSpec"):
        msg = (
            "nexmark-q8 needs a window tier that lowers join_window "
            "(window_accel.JoinAccelSpec): this program joins windows on "
            "the host tier"
        )
        raise ImportError(msg)


_require_device_join()

_US = 1_000_000
#: Input rows the reference takes at a time.
_BLOCK_ROWS = 4_000_000
#: The key the late rows carry to the sink.
LATE = "late"
#: ``PersonGenerator.java``'s names: a person's name is a first name
#: and a last name, each drawn uniformly.
FIRST_NAMES = (
    "Peter", "Paul", "Luke", "John", "Saul", "Vicky", "Kate", "Julie",
    "Sarah", "Deiter", "Walter",
)  # fmt: skip
LAST_NAMES = (
    "Shultz", "Abrams", "Spencer", "White", "Bartels", "Walton", "Smith",
    "Jones", "Noris",
)  # fmt: skip
#: The 99 names by index: ``first * 9 + last``.
NAMES = tuple(f"{f} {l}" for f in FIRST_NAMES for l in LAST_NAMES)
#: A name by its index, whether the index comes as an int (the device
#: tier) or a float (the host tier's ``TsValue``): one lookup a row.
_NAME_OF = dict(enumerate(NAMES))


def _shape(cfg: Dict[str, Any], name: str):
    return cfg["shapes"][name]


# -- the stream ---------------------------------------------------------------


def _persons_auctions(cfg) -> Tuple[int, int]:
    person, auction, _bid = _shape(cfg, "person_auction_bid")
    return int(person), int(auction)


def event_ids(cfg, lo: int, hi: int) -> np.ndarray:
    """Event ids of Query 8's rows ``lo:hi``: of each round of 50
    events, its person and its auctions, the first four."""
    per = _mix_of(cfg)[0]
    person, auction = _persons_auctions(cfg)
    kept = person + auction
    j = np.arange(lo, hi, dtype=np.int64)
    return (j // kept) * per + j % kept


def make_data(cfg, traffic, seed: int, workdir: str) -> Dict[str, Any]:
    """What set-up makes from the seed: the stream's salt and the key
    vocabulary (decimal strings of the person ids), which grows with
    the stream and is filled as :func:`batch` hands rows out.  First
    set-up walks the programs the window will meet."""
    salt = _mix(np.array([seed], dtype=np.uint64) + np.uint64(0x2545F4914F6CDD1D))
    warm_join_programs(traffic)
    return {"salt": salt[0], "vocab": np.empty(0, dtype="U10"), "vocab_filled": 0}


#: The shapes under which nexmark-q5's set-up walk folds a row a slot.
_ONE_ROW_A_SLOT = {"shapes": {"window_seconds": 1, "window_period_seconds": 1}}


def warm_join_programs(traffic) -> int:
    """Set-up's walk: the programs of the join tier at the sizes the
    cell reaches (the traffic's ``warm_join``), so that the window
    compiles nothing.  The slot table's (the ``count`` fold, slot
    resets) through nexmark-q5's walk, once for each side's delivery
    (``delivery_rows``: a poll's persons and its auctions) up to
    ``slots``; then the row store's and the close's on zeros: at each
    arena size, from the first a delivery makes to the largest the
    window reaches, the write of a delivery and the move of a region
    that grows (each pad no longer than the arena), the compaction
    (to the same size and to the two next: a poll's rows grow an arena
    at most sixteen times), the expansion at each
    of the output ladder's sizes; at each table size the count gather.
    The chip's compiler takes up to 10 s for a write or a compaction
    at the small arenas: met inside the stream, one such stall past
    the clock's wait closes windows by the wall clock.  Returns how
    many were run."""
    warm = traffic.get("warm_join")
    if not warm:
        return 0
    import jax.numpy as jnp

    from bytewax_tpu.ops import join

    for rows in warm["delivery_rows"]:
        warm_slot_programs(
            _ONE_ROW_A_SLOT,
            {"poll_rows": rows, "warm_windows_per_poll_row": -(-warm["slots"] // rows)},
        )
    ran = []

    def zeros(*shape, dtype=jnp.int32):
        return jnp.zeros(shape, dtype=dtype)

    for arena in warm["arena_rows"]:
        words = zeros(2, arena)
        for pad in warm["delivery_pads"]:
            if pad <= arena:
                words = join.join_store_write(words, zeros(pad), zeros(2, pad))
                ran.append(words)
        for pad in warm["move_pads"]:
            if pad <= arena:
                words = join.join_store_move(words, zeros(pad), zeros(pad))
                ran.append(words)
        for grown in (rows for rows in warm["arena_rows"] if arena <= rows <= 16 * arena):
            ran.append(join.join_store_compact(words, zeros(arena), zeros(arena), rows=grown))
        for rows in join.OUTPUT_LADDER:
            ran.append(
                join.join_expand(
                    words, zeros(2, rows), zeros(2, rows), zeros(rows), rows=rows, wide=()
                )[0]
            )
        ran[-1].block_until_ready()
    for slots in warm["slot_rows"]:
        for rows in join.OUTPUT_LADDER:
            ran.append(join.join_counts(zeros(slots, dtype=jnp.float32), zeros(2, rows)))
    ran[-1].block_until_ready()
    return len(ran)


def columns(cfg, data, lo: int, hi: int) -> Dict[str, np.ndarray]:
    """Query 8's rows ``lo:hi`` in arrival order: ``side`` (0 a person,
    1 an auction), ``kid`` (the person's id, or the auction's seller,
    as a base-0 person id), ``value`` (a person's name index into
    :data:`NAMES`, or an auction's reserve in cents) and ``ts`` (int64
    us since ``ALIGN``).  A person's name is drawn as
    ``PersonGenerator.nextPerson`` draws it; an auction's seller and
    reserve as ``AuctionGenerator.nextAuction`` draws them: the current
    hot seller (the first of the newest hundred persons) with
    probability ``1 - 1/hot_sellers_ratio``, else
    ``PersonGenerator.nextBase0PersonId``; the reserve is the initial
    bid plus another price, each ``round(10^(6u) * 100)`` cents."""
    per = _mix_of(cfg)[0]
    i = event_ids(cfg, lo, hi)
    ts = i * (_US // int(_shape(cfg, "events_per_second")))
    person = i % per < _persons_auctions(cfg)[0]
    last = _last_person(cfg, i)
    with np.errstate(over="ignore"):
        drawn = _mix(i.astype(np.uint64) * np.uint64(0x9E3779B97F4A7C15) + data["salt"])
        again = _mix(drawn + np.uint64(0xD1B54A32D192ED03))
        third = _mix(again + np.uint64(0x8CB92BA72F3D8DD7))
    every = int(_shape(cfg, "hot_seller_every"))
    is_hot = (drawn >> np.uint64(33)) % np.uint64(_shape(cfg, "hot_sellers_ratio")) > 0
    people = last + 1
    active = np.minimum(people, int(_shape(cfg, "active_people")))
    span = (active + int(_shape(cfg, "person_id_lead"))).astype(np.uint64)
    plain = people - active + ((again >> np.uint64(11)) % span).astype(np.int64)
    seller = np.where(is_hot, (last // every) * every, plain)
    name = ((drawn >> np.uint64(11)) % np.uint64(len(FIRST_NAMES))).astype(np.int64) * len(
        LAST_NAMES
    ) + ((again >> np.uint64(11)) % np.uint64(len(LAST_NAMES))).astype(np.int64)
    reserve = _price(drawn ^ third) + _price(third)
    return {
        "side": np.where(person, 0, 1).astype(np.int8),
        "kid": np.where(person, last, seller).astype(np.int32),
        "value": np.where(person, name, reserve).astype(np.int32),
        "ts": ts,
    }


def _price(draw: np.ndarray) -> np.ndarray:
    """``PriceGenerator.nextPrice``: ``Math.round(10^(6u) * 100)``
    cents, ``u`` uniform in [0, 1) from the draw's 53 high bits."""
    u = (draw >> np.uint64(11)).astype(np.float64) * (1.0 / (1 << 53))
    return np.floor(np.power(10.0, u * 6.0) * 100.0 + 0.5).astype(np.int64)


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
#: handed out.
SAMPLED = (
    "window_keys_opened",
    "window_keys_retired",
    "join_rows_stored",
    "join_store_rows",
)


def batch(cfg, data, lo: int, hi: int):
    """Rows ``lo:hi`` as the one columnar batch the source hands out."""
    from bytewax_tpu.engine import flight
    from bytewax_tpu.engine.arrays import ArrayBatch

    counters = flight.RECORDER.counters
    data.setdefault("counter_samples", []).append(
        (lo,) + tuple(counters.get(name) for name in SAMPLED)
    )
    cols = columns(cfg, data, lo, hi)
    base = np.datetime64(ALIGN.replace(tzinfo=None), "us")
    return ArrayBatch(
        {
            "key_id": cols["kid"],
            "ts": base + cols["ts"].astype("timedelta64[us]"),
            "value": cols["value"],
            "side": cols["side"],
        },
        key_vocab=_vocab(cfg, data, int(cols["kid"].max()) + 1 if hi > lo else 0),
    )


# -- the flow -----------------------------------------------------------------


def _side(which: int):
    """The rows of one side of the source's batch, as a keyed columnar
    batch (the join tags them again)."""

    def split(rows):
        mine = rows.numpy("side") == which
        from bytewax_tpu.engine.arrays import ArrayBatch

        return ArrayBatch(
            {name: rows.numpy(name)[mine] for name in ("key_id", "ts", "value")},
            key_vocab=rows.key_vocab,
        )

    return split


def _id_name_reserve(joined: List[Any]) -> List[Any]:
    """``IdNameReserve(person.id, person.name, auction.reserve)`` for
    every pair with both sides: a window's person alone, or its
    auctions with no person, write nothing."""
    return [
        (pid, (_NAME_OF[name], reserve))
        for pid, (_wid, (name, reserve)) in joined
        if name is not None and reserve is not None
    ]


def _late_tagged(lates: List[Any]) -> List[Any]:
    return [(LATE, (-1, 0)) for _row in lates]


def build_flow(cfg, data, source, sink):
    """``op.input`` -> two columnar splits (persons keyed by id,
    auctions keyed by seller) -> ``w.join_window`` (tumbling, product
    inserts) -> the pairs with both sides -> ``op.output``; the late
    rows go to the same sink, tagged."""
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
    flow = Dataflow("bench_nexmark_q8")
    rows = op.input("inp", flow, source)
    persons = op.flat_map_batch("persons", rows, _side(0))
    auctions = op.flat_map_batch("auctions", rows, _side(1))
    joined = w.join_window(
        "new_users",
        clock,
        w.TumblingWindower(
            length=timedelta(seconds=_shape(cfg, "window_seconds")), align_to=ALIGN
        ),
        persons,
        auctions,
        insert_mode="product",
    )
    q8 = op.flat_map_batch("id_name_reserve", joined.down, _id_name_reserve)
    late = op.flat_map_batch("late_tagged", joined.late, _late_tagged)
    op.output("out", op.merge("results", q8, late), sink)
    return flow


# -- the plain reference ------------------------------------------------------


def _window_us(cfg) -> int:
    return int(_shape(cfg, "window_seconds")) * _US


def _joined(cfg, data, lo: int, hi: int, served: int, precision: str = "exact"):
    """The Query 8 rows of the auctions among rows ``lo:hi`` of a
    stream served up to row ``served``: an auction joins where its
    seller's person row was served and lies in the auction's window
    (a person is one row, its birth)."""
    cols = columns(cfg, data, lo, hi)
    auction = cols["side"] == 1
    seller = cols["kid"][auction].astype(np.int64)
    wid = cols["ts"][auction] // _window_us(cfg)
    person, auctions_a_round = _persons_auctions(cfg)
    # The person of base-0 id p is row p * 4, event p * 50: its birth.
    hit = (seller * (person + auctions_a_round) < served) & (_birth_wid(cfg, seller) == wid)
    pid = seller[hit]
    reserve = cols["value"][auction][hit].astype(np.int64)
    if precision == "float32":
        reserve = reserve.astype(np.float32).astype(np.int64)
    return pid, _names_of(cfg, data, pid), reserve


def _names_of(cfg, data, pid: np.ndarray) -> np.ndarray:
    """Name indexes of the persons of base-0 ids ``pid``, drawn as
    :func:`columns` draws them at each person's event."""
    i = pid.astype(np.int64) * _mix_of(cfg)[0]
    with np.errstate(over="ignore"):
        drawn = _mix(i.astype(np.uint64) * np.uint64(0x9E3779B97F4A7C15) + data["salt"])
        again = _mix(drawn + np.uint64(0xD1B54A32D192ED03))
    return ((drawn >> np.uint64(11)) % np.uint64(len(FIRST_NAMES))).astype(np.int64) * len(
        LAST_NAMES
    ) + ((again >> np.uint64(11)) % np.uint64(len(LAST_NAMES))).astype(np.int64)


def reference(cfg, data, served: int, precision: str = "exact", twice=None, no_person=None):
    """Every Query 8 row of the first ``served`` rows: ``pid`` (base-0
    person id), ``name`` (index into :data:`NAMES`) and ``reserve``,
    sorted by person and reserve.  Plain numpy, a block of rows at a
    time.  ``precision`` other than exact (reserves through a float32
    carrier), ``twice`` (the row at that place written twice) and
    ``no_person`` (the persons of that row's window dropped) make the
    controls."""
    parts: Dict[str, List[np.ndarray]] = {"pid": [], "name": [], "reserve": []}
    for lo in range(0, served, _BLOCK_ROWS):
        pid, name, reserve = _joined(cfg, data, lo, min(served, lo + _BLOCK_ROWS), served, precision)
        for key, col in zip(parts, (pid, name, reserve)):
            parts[key].append(col)
    out = {
        key: np.concatenate(col) if col else np.empty(0, dtype=np.int64)
        for key, col in parts.items()
    }
    if no_person is not None and len(out["pid"]):
        birth = _birth_wid(cfg, out["pid"])
        gone = birth == birth[min(no_person, len(birth) - 1)]
        out = {key: col[~gone] for key, col in out.items()}
    if twice is not None and len(out["pid"]):
        at = min(twice, len(out["pid"]) - 1)
        out = {key: np.insert(col, at, col[at]) for key, col in out.items()}
    order = np.lexsort((out["reserve"], out["pid"]))
    return {key: col[order] for key, col in out.items()}


def _birth_wid(cfg, pid: np.ndarray) -> np.ndarray:
    born = pid * _mix_of(cfg)[0]
    return born * (_US // int(_shape(cfg, "events_per_second"))) // _window_us(cfg)


def output_rows(cfg, data, lo: int, hi: int, served: int) -> int:
    """How many Query 8 rows the auctions among rows ``lo:hi`` make."""
    return len(_joined(cfg, data, lo, hi, served)[0])


def undecided(cfg, data, polls: Sequence[Tuple[float, int, int]], ended: float):
    """Persons the wall clock, and not the data, may have decided.
    Rows arrive in event-time order and the clock waits
    ``wait_for_system_duration_s``: while no two polls lie that far
    apart the wall clock makes no row late and closes no window before
    its rows are in; once they do, every person is."""
    times = [p[0] for p in polls] + [ended]
    gap = max((b - a for a, b in zip(times, times[1:])), default=0.0)
    if gap < _shape(cfg, "wait_for_system_duration_s"):
        return np.empty(0, dtype=np.int64)
    served = polls[-1][2] if polls else 0
    person, auction = _persons_auctions(cfg)
    return np.arange(-(-served // (person + auction)), dtype=np.int64)


# -- what the sink received ---------------------------------------------------


_NAME_INDEX = {name: i for i, name in enumerate(NAMES)}
_FIRST, _SECOND = itemgetter(0), itemgetter(1)


def pack(items: List[Any]):
    """One sink write as arrays: a row ``person id, name index,
    reserve`` a Query 8 row, and the number of late rows.  Each column
    is one ``map`` over the write (the sink's time is the window's)."""
    keys = list(map(_FIRST, items))
    late = keys.count(LATE)
    if late:
        items = [row for row in items if row[0] != LATE]
        keys = list(map(_FIRST, items))
    n = len(items)
    rows = list(map(_SECOND, items))
    return (
        np.column_stack(
            [
                np.fromiter(map(int, keys), np.int64, n),
                np.fromiter(map(_NAME_INDEX.__getitem__, map(_FIRST, rows)), np.int64, n),
                np.fromiter(map(_SECOND, rows), np.int64, n),
            ]
        ).reshape(n, 3),
        late,
    )


def result_arrays(cfg, packs: List[Any]) -> Dict[str, np.ndarray]:
    """The sink's writes as the reference's columns, sorted the same
    way, and the number of late rows."""
    rows = np.concatenate([p[0] for p in packs]) if packs else np.empty((0, 3), np.int64)
    pid = rows[:, 0] - int(_shape(cfg, "first_person_id"))
    order = np.lexsort((rows[:, 2], pid))
    return {
        "pid": pid[order],
        "name": rows[order, 1],
        "reserve": rows[order, 2],
        "late": sum(p[1] for p in packs),
    }


def compare(cfg, got, want, open_pids=()) -> Dict[str, float]:
    """The numbers ``correct`` is decided on, all exact: per person as
    many rows as the reference (``rows_missing``, ``rows_extra``), no
    (person, reserve) written more often than the reference has it
    (``rows_twice``), the reserves of a person with the right number
    of rows the reference's (``reserve_wrong``), every name the
    person's (``name_wrong``), the total (``rows_unanswered``), none
    late.  ``open_pids`` (:func:`undecided`) are left out on both
    sides, and ``undecided_share`` says what share of the reference's
    persons they are, so that a run that left persons to the wall
    clock is not judged on the rest alone."""
    g = ~np.isin(got["pid"], open_pids)
    w = ~np.isin(want["pid"], open_pids)
    g_pid, g_name, g_res = got["pid"][g], got["name"][g], got["reserve"][g]
    w_pid, w_name, w_res = want["pid"][w], want["name"][w], want["reserve"][w]
    size = int(max(g_pid.max(initial=-1), w_pid.max(initial=-1))) + 1
    n_got = np.bincount(g_pid, minlength=size)
    n_want = np.bincount(w_pid, minlength=size)
    # (person, reserve) as one sortable number: reserves lie below 2^31.
    g_pairs, g_times = np.unique((g_pid << 31) + g_res, return_counts=True)
    w_pairs, w_times = np.unique((w_pid << 31) + w_res, return_counts=True)
    at = np.minimum(np.searchsorted(w_pairs, g_pairs), max(len(w_pairs) - 1, 0))
    known = (w_pairs[at] == g_pairs) if len(w_pairs) else np.zeros(len(g_pairs), bool)
    twice = np.maximum(g_times - w_times[at], 0)[known].sum() if len(w_pairs) else 0
    same_n = np.isin(g_pid, np.flatnonzero(n_got == n_want))
    same_w = np.isin(w_pid, np.flatnonzero(n_got == n_want))
    name_of = np.full(size, -1, dtype=np.int64)
    name_of[w_pid] = w_name
    persons = np.unique(want["pid"])
    return {
        "rows_missing": int(np.maximum(n_want - n_got, 0).sum()),
        "rows_extra": int(np.maximum(n_got - n_want, 0).sum()),
        "rows_twice": int(twice),
        "reserve_wrong": int((g_res[same_n] != w_res[same_w]).sum()),
        "name_wrong": int(((name_of[g_pid] != g_name) & (name_of[g_pid] >= 0)).sum()),
        "rows_unanswered": abs(len(w_pid) - len(g_pid)),
        "rows_late": int(got.get("late", 0)),
        "undecided_share": int(np.isin(persons, open_pids).sum()) / max(len(persons), 1),
    }


def control_results(cfg, data, served: int, which: str) -> Dict[str, np.ndarray]:
    """The reference put in the program's place with one thing lowered
    or broken: ``float32_reserve`` (reserves through a float32
    carrier, the precision below the stated exact integers),
    ``row_twice`` (one row written twice), ``no_person`` (one window's
    person side dropped)."""
    if which == "float32_reserve":
        want = reference(cfg, data, served, precision="float32")
    elif which == "row_twice":
        want = reference(cfg, data, served, twice=served // 8)
    elif which == "no_person":
        want = reference(cfg, data, served, no_person=served // 8)
    else:
        raise ValueError(f"no control {which!r}")
    return dict(want, late=0)


CONTROLS = ("float32_reserve", "row_twice", "no_person")
