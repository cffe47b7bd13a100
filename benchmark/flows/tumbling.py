"""Event-time tumbling ``fold_window`` as upstream's windowing
benchmark runs it: the flow, its seeded stream, its plain reference
and the comparison.

Upstream shape: ``examples/benchmark_windowing.py:11-39`` (items one
second of event time apart and in order, a random key of two an item,
``EventClock`` with a wait of 0, one-minute ``TumblingWindower``,
100,000 items a batch).  Nothing here imports the program except
:func:`batch` and :func:`build_flow`, which use its public operators.

The stream is a function of the row's index and the seed, so it is as
long as the window takes and nothing of it is stored: row ``i`` has
event time ``i * event_spacing_s``, the second of its minute as its
value, and a key drawn from a hash of ``(seed, i)``.
"""

from datetime import datetime, timedelta, timezone
from typing import Any, Dict, List, Sequence, Tuple

import numpy as np

#: Event time zero of every generated stream.
ALIGN = datetime(2022, 1, 1, tzinfo=timezone.utc)
_US = 1_000_000
#: (key, window)s the reference groups at a time: few enough to sort
#: rows by a 16-bit key, which numpy does in one linear pass.
_BLOCK_COMPS = 1 << 16
#: The engine has taken a batch in before it has polled this many rows
#: more (it may gather polls into one delivery: by default until that
#: holds 65,536 rows).
_TAKEN_IN_WITHIN_ROWS = 1 << 18


def _shape(cfg: Dict[str, Any], name: str):
    return cfg["shapes"][name]


# -- the stream ---------------------------------------------------------------


def _mix(x: np.ndarray) -> np.ndarray:
    """splitmix64's finalizer over uint64 (wrapping arithmetic)."""
    x = (x ^ (x >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
    x = (x ^ (x >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
    return x ^ (x >> np.uint64(31))


def make_data(cfg, traffic, seed: int, workdir: str) -> Dict[str, Any]:
    """What set-up makes from the seed: the key vocabulary and the
    stream's salt (the rows themselves come from :func:`columns`)."""
    keys = int(_shape(cfg, "keys"))
    salt = _mix(np.array([seed], dtype=np.uint64) + np.uint64(0x9E3779B97F4A7C15))
    return {
        "salt": salt[0],
        "keys": keys,
        "vocab": np.array([str(i) for i in range(keys)]),
    }


def columns(cfg, data, lo: int, hi: int) -> Dict[str, np.ndarray]:
    """Rows ``lo:hi`` of the stream in arrival order: ``kid``, ``ts``
    (int64 us since ``ALIGN``) and ``value`` (float32)."""
    i = np.arange(lo, hi, dtype=np.int64)
    ts = i * int(_shape(cfg, "event_spacing_s") * _US)
    with np.errstate(over="ignore"):
        drawn = _mix(i.astype(np.uint64) * np.uint64(0x9E3779B97F4A7C15) + data["salt"])
    kid = ((drawn >> np.uint64(33)) % np.uint64(data["keys"])).astype(np.int32)
    value = ((ts // _US) % 60).astype(np.float32)
    return {"kid": kid, "ts": ts, "value": value}


def batch(cfg, data, lo: int, hi: int):
    """Rows ``lo:hi`` as the columnar batch the source hands out."""
    from bytewax_tpu.engine.arrays import ArrayBatch

    cols = columns(cfg, data, lo, hi)
    base = np.datetime64(ALIGN.replace(tzinfo=None), "us")
    return ArrayBatch(
        {
            "key_id": cols["kid"],
            "ts": base + cols["ts"].astype("timedelta64[us]"),
            "value": cols["value"],
        },
        key_vocab=data["vocab"],
    )


def build_flow(cfg, data, source, sink):
    """``op.input`` -> ``w.fold_window(xla.STATS)`` -> ``op.output``."""
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
    flow = Dataflow("bench_tumbling")
    s = op.input("inp", flow, source)
    stats = w.fold_window(
        "stats",
        s,
        clock,
        w.TumblingWindower(
            align_to=ALIGN,
            length=timedelta(seconds=_shape(cfg, "window_seconds")),
        ),
        xla.STATS.make_acc,
        xla.STATS,
        xla.STATS.merge,
    )
    op.output("out", stats.down, sink)
    return flow


# -- the plain reference ------------------------------------------------------


def comp_of(cfg, kid, wid) -> np.ndarray:
    """One sortable int64 per (key, window id), window-major."""
    return np.asarray(wid, dtype=np.int64) * int(_shape(cfg, "keys")) + np.asarray(
        kid, dtype=np.int64
    )


def _window_of(cfg, ts: np.ndarray) -> np.ndarray:
    return ts // (int(_shape(cfg, "window_seconds")) * _US)  # floor, also < 0


def reference(cfg, data, served: int, precision: str = "float64", twice=None):
    """numpy group-by on (key, window id) over the first ``served``
    rows of the stream, in blocks of whole windows: arrays sorted by
    ``comp``.  Rows arrive in event-time order, so none is late by the
    data (what the wall clock can make late is :func:`undecided`).
    ``precision`` other than float64 makes the control: the same
    answer as a lower-precision fold would give it.  ``twice`` is the
    index of a row to fold twice (a control)."""
    spacing = int(_shape(cfg, "event_spacing_s") * _US)
    per_window = int(_shape(cfg, "window_seconds")) * _US // spacing
    names = ("comp", "min", "max", "sum", "count", "abs_sum")
    parts: Dict[str, List[np.ndarray]] = {name: [] for name in names}
    step = per_window * max(1, _BLOCK_COMPS // int(_shape(cfg, "keys")))
    for lo in range(0, served, step):
        cols = columns(cfg, data, lo, min(served, lo + step))
        if twice is not None and lo <= twice < lo + step:
            at = twice - lo
            cols = {k: np.concatenate([v[: at + 1], v[at:]]) for k, v in cols.items()}
        block = _group(cfg, cols, precision)
        for name in names:
            parts[name].append(block[name])
    return {name: np.concatenate(parts[name]) for name in names}


def _group(cfg, cols, precision: str) -> Dict[str, np.ndarray]:
    comp = comp_of(cfg, cols["kid"], _window_of(cfg, cols["ts"]))
    local = comp - comp.min()
    if int(local.max()) < _BLOCK_COMPS:
        local = local.astype(np.uint16)
    order = np.argsort(local, kind="stable")
    comp = comp[order]
    starts = np.flatnonzero(np.r_[True, comp[1:] != comp[:-1]])
    uniq, count = comp[starts], np.diff(np.r_[starts, len(comp)])
    value = cols["value"][order]
    if precision == "float64":
        v = value.astype(np.float64)
        total = np.add.reduceat(v, starts)
    else:
        v = _to_bfloat16(value, precision)
        total = _low_precision_sums(v, starts, count, precision)
    return {
        "comp": uniq,
        "min": np.minimum.reduceat(v, starts).astype(np.float64),
        "max": np.maximum.reduceat(v, starts).astype(np.float64),
        "sum": total.astype(np.float64),
        "count": count.astype(np.int64),
        "abs_sum": np.add.reduceat(np.abs(value.astype(np.float64)), starts),
    }


def _to_bfloat16(v: np.ndarray, precision: str) -> np.ndarray:
    """``v`` rounded to bfloat16 (the one control precision), kept in
    float32."""
    if precision != "bfloat16":
        raise ValueError(f"no control precision {precision!r}")
    import ml_dtypes

    return v.astype(ml_dtypes.bfloat16).astype(np.float32)


def _low_precision_sums(v, starts, count, precision) -> np.ndarray:
    """Running sums per segment, rounded to ``precision`` after every
    addition (what an accumulator of that type holds)."""
    total = np.zeros(len(starts), dtype=np.float32)
    for j in range(int(count.max())):
        live = count > j
        total[live] = _to_bfloat16(total[live] + v[starts[live] + j], precision)
    return total


def undecided(cfg, data, polls: Sequence[Tuple[float, int, int]], ended: float):
    """(key, window)s that hold a row the wall clock, and not the
    data, decides: sorted ``comp``s.

    The clock's watermark of a key is its newest event time less the
    wait, *plus the wall time since that event was taken in*; a row
    behind it is dropped as late.  ``polls`` are ``(wall time, lo,
    hi)`` of every batch handed out.  The engine takes a batch in
    after its poll and before it has polled
    ``_TAKEN_IN_WITHIN_ROWS`` more, so a row is on time for sure only
    where it is ahead of its key's newest earlier event by more than
    the wall time from that event's poll to the poll that far on.  A
    row closer than that may be kept or dropped, and its window is
    left out of the comparison."""
    wait = int(_shape(cfg, "wait_for_system_duration_s") * _US)
    times = np.array([p[0] for p in polls] + [ended])
    first_rows = np.array([p[1] for p in polls])
    last_ts = np.full(data["keys"], -(1 << 62), dtype=np.int64)
    last_at = np.zeros(data["keys"])
    out: List[np.ndarray] = []
    for i, (at, lo, hi) in enumerate(polls):
        cols = columns(cfg, data, lo, hi)
        by = times[np.searchsorted(first_rows, hi + _TAKEN_IN_WITHIN_ROWS)]
        reach = last_ts - wait + ((by - last_at) * _US).astype(np.int64)
        near = cols["ts"] < reach.max()
        if near.any():
            kid, ts = cols["kid"][near], cols["ts"][near]
            open_ = ts < reach[kid]
            out.append(comp_of(cfg, kid[open_], _window_of(cfg, ts[open_])))
        seen = np.unique(cols["kid"])
        newest = np.full(data["keys"], -(1 << 62), dtype=np.int64)
        np.maximum.at(newest, cols["kid"], cols["ts"])
        last_ts[seen] = newest[seen]
        last_at[seen] = at
    return np.unique(np.concatenate(out)) if out else np.empty(0, dtype=np.int64)


# -- what the sink received ---------------------------------------------------


def pack(items: List[Any]) -> np.ndarray:
    """One sink write's emissions ``(key, (wid, (min, max, sum,
    count)))`` as one float64 array of ``key id, wid, min, max, sum,
    count`` rows: what the sink keeps, so that the benchmark holds no
    object that the program's garbage collector would walk."""
    return np.array(
        [(int(k), v[0], *v[1]) for k, v in items], dtype=np.float64
    ).reshape(len(items), 6)


def result_arrays(cfg, packs: List[np.ndarray]) -> Dict[str, np.ndarray]:
    """The sink's writes as columns, in the order they were written."""
    rows = np.concatenate(packs) if packs else np.empty((0, 6))
    return {
        "comp": comp_of(cfg, rows[:, 0].astype(np.int64), rows[:, 1].astype(np.int64)),
        "min": rows[:, 2],
        "max": rows[:, 3],
        "sum": rows[:, 4],
        "count": rows[:, 5].astype(np.int64),
    }


def compare(cfg, got, want, open_comps=()) -> Dict[str, float]:
    """The numbers ``correct`` is decided on: (key, window) sets equal
    and each once, counts and extrema exact, sums by their forward
    error against the float64 reference (over the sum of |values|).
    ``open_comps`` (:func:`undecided`) are left out on both sides."""
    comp, first, times = np.unique(got["comp"], return_index=True, return_counts=True)
    twice = int((times > 1).sum())
    decided = ~np.isin(comp, open_comps)
    comp, first = comp[decided], first[decided]
    want_rows = np.nonzero(~np.isin(want["comp"], open_comps))[0]
    want_comp = want["comp"][want_rows]
    missing = int((~np.isin(want_comp, comp)).sum())
    extra = int((~np.isin(comp, want_comp)).sum())
    # Compare the windows both sides have, one row each.
    both = np.isin(comp, want_comp)
    g = {name: got[name][first][both] for name in ("min", "max", "sum", "count")}
    w_rows = want_rows[np.searchsorted(want_comp, comp[both])]
    w = {name: want[name][w_rows] for name in ("min", "max", "sum", "count", "abs_sum")}
    count_wrong = int((g["count"] != w["count"]).sum())
    extrema_wrong = int(((g["min"] != w["min"]) | (g["max"] != w["max"])).sum())
    if len(w_rows):
        sum_err = float(
            (np.abs(g["sum"] - w["sum"]) / np.maximum(w["abs_sum"], 1.0)).max()
        )
    else:
        sum_err = 0.0
    answered = int(got["count"][first].sum())
    return {
        "windows_missing": missing,
        "windows_extra": extra,
        "windows_twice": twice,
        "count_wrong": count_wrong,
        "extrema_wrong": extrema_wrong,
        "sum_err": sum_err,
        "rows_unanswered": abs(int(want["count"][want_rows].sum()) - answered),
        "undecided_share": len(open_comps) / max(len(want["comp"]), 1),
    }


def control_results(cfg, data, served: int, which: str) -> Dict[str, np.ndarray]:
    """The reference put in the program's place with one thing
    lowered or broken: ``bfloat16`` (the precision below the float32
    the configuration states), ``row_twice`` (one row folded twice)."""
    if which == "bfloat16":
        return reference(cfg, data, served, precision="bfloat16")
    if which == "row_twice":
        return reference(cfg, data, served, twice=served // 2)
    raise ValueError(f"no control {which!r}")


CONTROLS = ("bfloat16", "row_twice")
