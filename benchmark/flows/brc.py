"""The one-billion-row challenge as a keyed min / mean / max fold over
a measurements file: the flow, its seeded file, its plain reference
and the comparison.

Upstream shape: ``examples/1brc.py:16-100``.  File generator and
checks are adapted from ``chip_smoke.py`` (``_brc_file``,
``_brc_flow``, ``_check_brc``); nothing here imports the program
except :func:`build_flow`, which uses its public source and operator.
"""

import os
from typing import Any, Dict, List

import numpy as np


HERE = os.path.dirname(os.path.abspath(__file__))


def stations(cfg) -> List[tuple]:
    """``(name, mean)`` of every weather station, from the file the
    configuration names: the 1BRC generator's list."""
    path = os.path.join(os.path.dirname(HERE), cfg["shapes"]["stations_file"])
    with open(path, encoding="utf-8") as f:
        rows = [line.rstrip("\n").rsplit(";", 1) for line in f if line.strip()]
    return [(name, float(mean)) for name, mean in rows]


def make_data(cfg, traffic, seed: int, workdir: str) -> Dict[str, Any]:
    """Write the measurements file as the 1BRC generator does (a
    station drawn uniformly a row, its reading normal(the station's
    mean, 10) rounded to one decimal) and keep the per-station
    reference ``(min, max, sum, count)`` in deci-degrees, both from the
    same generated columns."""
    shapes = cfg["shapes"]
    rows = int(shapes["rows_per_job"])
    known = stations(cfg)
    names = [name for name, _mean in known]
    means = np.array([mean for _name, mean in known]) * 10.0
    n = len(names)
    rng = np.random.default_rng([seed, 2])
    decis = np.arange(-999, 1000)
    lines = np.array(
        [f"{name};{d / 10:.1f}\n" for name in names for d in decis],
        dtype=object,
    )
    mn = np.full(n, 999, dtype=np.int64)
    mx = np.full(n, -999, dtype=np.int64)
    total = np.zeros(n, dtype=np.int64)
    count = np.zeros(n, dtype=np.int64)
    path = os.path.join(workdir, "measurements.txt")
    with open(path, "w", encoding="utf-8") as f:
        for start in range(0, rows, 1 << 20):
            m = min(1 << 20, rows - start)
            ids = rng.integers(0, n, size=m)
            deci = np.clip(
                np.round(rng.normal(means[ids], 100.0)), -999, 999
            ).astype(np.int64)
            f.write("".join(lines[ids * len(decis) + deci + 999].tolist()))
            np.minimum.at(mn, ids, deci)
            np.maximum.at(mx, ids, deci)
            total += np.bincount(ids, weights=deci, minlength=n).astype(np.int64)
            count += np.bincount(ids, minlength=n)
    return {
        "path": path,
        "rows": rows,
        "names": names,
        "want": {"min": mn, "max": mx, "sum": total, "count": count},
    }


def build_flow(cfg, data, source, sink):
    """``BrcFileSource`` -> ``xla.stats_final`` -> ``op.output``; the
    source is the program's own (``source`` is unused: a job reads its
    file as fast as the engine polls it)."""
    import bytewax_tpu.operators as op
    from bytewax_tpu import xla
    from bytewax_tpu.dataflow import Dataflow
    from bytewax_tpu.models.brc import BrcFileSource

    flow = Dataflow("bench_brc")
    s = op.input(
        "inp",
        flow,
        BrcFileSource(data["path"], part_count=int(cfg["shapes"]["part_count"])),
    )
    stats = xla.stats_final("stats", s)
    op.output("out", stats, sink)
    return flow


def reference(cfg, data, precision: str = "exact") -> Dict[str, np.ndarray]:
    """Per-station ``min, mean, max, count`` in degrees from the exact
    integer columns.  ``bfloat16`` makes the control: readings rounded
    to bfloat16 and the mean taken from a bfloat16 running sum's
    error model (the sum rounded once: the mildest such fold)."""
    w = data["want"]
    live = w["count"] > 0
    mn, mx = w["min"] / 10.0, w["max"] / 10.0
    mean = w["sum"] / np.maximum(w["count"], 1) / 10.0
    if precision == "bfloat16":
        import ml_dtypes

        def low(a):
            return a.astype(ml_dtypes.bfloat16).astype(np.float64)

        mn, mx = low(mn), low(mx)
        mean = low(w["sum"] / 10.0) / np.maximum(w["count"], 1)
    elif precision != "exact":
        raise ValueError(f"no control precision {precision!r}")
    names = np.array(data["names"])
    order = np.argsort(names)  # the comparison searches them sorted
    keep = order[live[order]]
    return {
        "names": names[keep],
        "min": mn[keep],
        "mean": mean[keep],
        "max": mx[keep],
        "count": w["count"][keep],
    }


def pack(items: List[Any]) -> Dict[str, np.ndarray]:
    """One sink write's emissions ``(station, (min, mean, max,
    count))`` as arrays: what the sink keeps, so that the benchmark
    holds no object that the program's garbage collector would walk."""
    n = len(items)
    return {
        "names": np.array([k for k, _ in items], dtype=str),
        "acc": np.array([v for _, v in items], dtype=np.float64).reshape(n, 4),
    }


def result_arrays(cfg, packs: List[Dict[str, np.ndarray]]) -> Dict[str, np.ndarray]:
    """One job's sink writes as the columns the comparison takes."""
    names = np.concatenate([p["names"] for p in packs])
    acc = np.concatenate([p["acc"] for p in packs])
    return {
        "names": names,
        "min": acc[:, 0],
        "mean": acc[:, 1],
        "max": acc[:, 2],
        "count": acc[:, 3].astype(np.int64),
    }


def compare(cfg, got, want) -> Dict[str, float]:
    """One job against the reference: station sets equal and each
    once, counts exact, extrema and mean by their largest absolute
    gap in degrees."""
    names, first, times = np.unique(
        got["names"], return_index=True, return_counts=True
    )
    both = np.isin(names, want["names"])
    g = {k: got[k][first][both] for k in ("min", "mean", "max", "count")}
    rows = np.searchsorted(want["names"], names[both])
    w = {k: want[k][rows] for k in ("min", "mean", "max", "count")}
    have = len(rows) > 0
    return {
        "stations_missing": int((~np.isin(want["names"], names)).sum()),
        "stations_extra": int((~both).sum()),
        "stations_twice": int((times > 1).sum()),
        "count_wrong": int((g["count"] != w["count"]).sum()),
        "extrema_err": float(
            max(
                np.abs(g["min"] - w["min"]).max(),
                np.abs(g["max"] - w["max"]).max(),
            )
        )
        if have
        else 0.0,
        "mean_err": float(np.abs(g["mean"] - w["mean"]).max()) if have else 0.0,
        "rows_unanswered": abs(int(want["count"].sum()) - int(got["count"][first].sum())),
    }


def control_results(cfg, data, which: str) -> Dict[str, np.ndarray]:
    """The reference in the program's place with one thing lowered or
    broken: ``bfloat16``, or ``row_twice`` (one row counted twice)."""
    if which == "bfloat16":
        return reference(cfg, data, precision="bfloat16")
    if which == "row_twice":
        out = reference(cfg, data)
        out["count"] = out["count"].copy()
        out["count"][0] += 1
        return out
    raise ValueError(f"no control {which!r}")


CONTROLS = ("bfloat16", "row_twice")
