"""Prove that the system still starts on the chip.

Drives the device tier once through the entry point a user calls
(``bytewax_tpu.run.cli_main``, what ``python -m bytewax_tpu.run
mod:flow`` calls) at sizes a user of a stream processor would call
real, and checks every answer against a plain numpy (or host-tier)
reference.  Data is made from ``--seed``; nothing is read that git
would not commit; the native data plane is rebuilt from its sources
on the machine that runs this.  One process holds the chip: every
stage runs in this process.

Stages: ``brc`` (generated 1BRC file, native parser, packed int16
fold), ``windows`` (event-time tumbling stats + sliding counts with a
recovery store), ``resume`` (the same store stopped mid-stream and
resumed, exactly once), ``scan_infer`` (z-score scan, ``op.infer`` and
session windows against the host tier), ``kernels`` (the fold's dense
form, the Pallas kernel compiled, and its scatter through the entry
points on both sides of the threshold) and, with more than one device,
``mesh`` (``brc`` and ``windows`` again over the local mesh).

Prints one JSON object per stage and, as the last line of standard
output, ``{"ok": true, "device": {...}}``.  Any failed check, any
exception, or a platform other than ``tpu`` makes the exit code
non-zero; without an accelerator nothing is printed on standard
output at all.  The wall times it prints are smoke times, not a
benchmark.
"""

import argparse
import glob
import json
import os
import shutil
import sys
import tempfile
import time
import urllib.request
from dataclasses import asdict, dataclass, replace
from datetime import datetime, timedelta, timezone
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))


@dataclass(frozen=True)
class Sizes:
    """What each stage runs at.  The defaults are the floors a user
    would call real; ``scaled`` cuts rows, and rows a poll with them
    so a run keeps its number of polls (never keys or stations), and
    the output says by how much."""

    #: 1BRC: the standard generator's station count; rows cut from 1B.
    brc_rows: int = 10_000_000
    brc_stations: int = 413
    #: Windows: live keys, events, minutes of event time, rows a poll.
    win_keys: int = 100_000
    win_events: int = 8_000_000
    win_minutes: int = 8
    win_batch_rows: int = 1 << 21
    #: Scan / infer / session: keys, rows, rows a poll, and the keys
    #: the host-tier oracle re-runs (off the chip's clock).
    scan_keys: int = 100_000
    scan_rows: int = 2_000_000
    scan_batch_rows: int = 1 << 18
    oracle_keys: int = 2_000
    #: The fold's two forms: rows a fold (a 16 MiB 1BRC chunk, padded)
    #: and the slot-table capacities they are held to (the engine's
    #: smallest table, the largest the dense form takes, and the next).
    kernel_rows: int = 1 << 21
    kernel_caps: Tuple[int, ...] = (1024, 8192, 16384)

    def scaled(self, factor: float) -> "Sizes":
        def cut(n: int, floor: int) -> int:
            return max(floor, int(n * factor))

        return replace(
            self,
            brc_rows=cut(self.brc_rows, 1000),
            win_events=cut(self.win_events, 2000),
            win_batch_rows=cut(self.win_batch_rows, 256),
            scan_rows=cut(self.scan_rows, 2000),
            scan_batch_rows=cut(self.scan_batch_rows, 256),
            kernel_rows=cut(self.kernel_rows, 1024),
        )


FLOORS = Sizes()

#: Event time zero of every generated stream.
ALIGN = datetime(2022, 1, 1, tzinfo=timezone.utc)
#: EventClock slack: rows up to this far behind their key's newest
#: event are on time.
WAIT_S = 240
#: The watermark also advances with the wall clock while a key is
#: quiet, so a row closer than this to its lateness boundary would be
#: judged by how long the run stalled, not by the data.  The
#: generator makes no such row, and the run fails if the source ever
#: waited half of this between two polls (a key can sit out a poll).
CLOCK_MARGIN_S = 180
#: Share of events that arrive out of order (half of them still
#: inside ``WAIT_S``, half late).
OUT_OF_ORDER_SHARE = 0.02
SESSION_GAP_S = 30
ZSCORE_THRESHOLD = 3.0

_COUNTERS = (
    "xla_compile_count",
    "xla_compile_seconds",
    "device_transfer_bytes_h2d",
    "device_transfer_bytes_d2h",
    "demotion_count",
    "epoch_close_count",
)


def _counter_deltas(before: Dict[str, float]) -> Dict[str, float]:
    """What the flight recorder's counters gained since ``before``."""
    from bytewax_tpu.engine import flight

    after = flight.RECORDER.counters
    return {
        name: round(after.get(name, 0) - before.get(name, 0), 6)
        for name in _COUNTERS
    }


class SmokeFailure(Exception):
    """A check of the smoke did not hold."""


def require(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


# -- observing a run from outside -------------------------------------------


class Probe:
    """Samples the engine's own API plane (``GET /graph``, ``GET
    /status``) while a flow runs.  Sampling is driven by the flows'
    sinks, on the engine's main thread, so the last sample is taken
    when the last output is written."""

    def __init__(self, port: int):
        self.port = port
        self.graph: Optional[dict] = None
        self.status: Optional[dict] = None
        self._last = 0.0

    def _get(self, path: str) -> dict:
        url = f"http://127.0.0.1:{self.port}{path}"
        with urllib.request.urlopen(url, timeout=10) as resp:
            return json.loads(resp.read())

    def sample(self) -> None:
        now = time.monotonic()
        if now - self._last < 0.25:
            return
        self._last = now
        self.graph = self._get("/graph")
        if self.status is None:
            self.status = self._get("/status")

    def reset(self) -> None:
        self.graph = None
        self.status = None
        self._last = 0.0


def _free_port() -> int:
    import socket

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _sink(out: list, probe: Probe):
    """A list-collecting sink that samples ``probe`` as it writes."""
    from bytewax_tpu.outputs import DynamicSink, StatelessSinkPartition

    class _Part(StatelessSinkPartition):
        def write_batch(self, items) -> None:
            out.extend(items)
            probe.sample()

    class _ListSink(DynamicSink):
        def build(self, step_id, worker_index, worker_count):
            return _Part()

    return _ListSink()


@dataclass
class Ctx:
    """What every stage needs: sizes, seed, a scratch directory, the
    API probe, whether a CPU backend is tolerated, and what the
    device and the native build looked like at start-up."""

    sizes: Sizes
    seed: int
    workdir: str
    probe: Probe
    allow_cpu: bool
    device: Dict[str, Any]
    native: Dict[str, bool]


def run_flow(
    ctx: Ctx,
    flow,
    *,
    epoch_interval: Optional[timedelta] = None,
    recovery_config: Optional[Any] = None,
) -> Dict[str, Any]:
    """One execution through ``bytewax_tpu.run.cli_main``.  Returns
    what the run showed from outside: completion status, wall time,
    flight-counter deltas, and the tier of every step the plan
    lowered to the device as ``GET /graph`` last reported it."""
    from bytewax_tpu.engine import flight
    from bytewax_tpu.engine.flatten import flatten
    from bytewax_tpu.run import cli_main

    lowered = [
        op.step_id
        for op in flatten(flow).ops
        if op.conf.get("_accel") is not None
    ]
    require(bool(lowered), f"{flow.flow_id}: no step lowered to the device")
    before = dict(flight.RECORDER.counters)
    ctx.probe.reset()
    t0 = time.perf_counter()
    status = cli_main(
        flow,
        epoch_interval=epoch_interval,
        recovery_config=recovery_config,
    )
    wall_s = time.perf_counter() - t0
    counters = _counter_deltas(before)
    require(
        counters["demotion_count"] == 0,
        f"{flow.flow_id}: {counters['demotion_count']} step(s) left "
        "the device tier",
    )
    graph = ctx.probe.graph
    require(graph is not None, f"{flow.flow_id}: GET /graph never sampled")
    tiers = {n["step_id"]: n["tier"] for n in graph["steps"]}
    for step_id in lowered:
        require(
            tiers.get(step_id) == "device",
            f"{step_id}: tier {tiers.get(step_id)!r}, expected 'device'",
        )
    seen = ctx.probe.status["device"]
    require(
        seen == ctx.device, f"/status device {seen} != jax's {ctx.device}"
    )
    return {
        "status": status,
        "wall_s": round(wall_s, 3),
        "counters": counters,
        "device_steps": lowered,
        "compile_cache_dir": ctx.probe.status["compile_cache_dir"],
    }


def _merge_counters(runs: List[Dict[str, Any]]) -> Dict[str, float]:
    return {
        name: round(sum(r["counters"][name] for r in runs), 6)
        for name in _COUNTERS
    }


def _report(ctx: Ctx, stage: str, runs, **fields) -> Dict[str, Any]:
    """The one JSON object a stage prints."""
    doc = {
        "stage": stage,
        "ok": True,
        "device": ctx.device,
        "native": ctx.native,
        "counters": _merge_counters(runs),
        "compile_cache_dir": runs[0].get("compile_cache_dir"),
        "wall_s_not_a_benchmark": round(
            sum(r["wall_s"] for r in runs), 3
        ),
        **fields,
    }
    print(json.dumps(doc), flush=True)
    return doc


# -- stage: brc ---------------------------------------------------------------


def _brc_file(path: str, rows: int, stations: int, seed: int):
    """Write a 1BRC measurements file and return the per-station
    reference ``(names, min, max, sum, count)`` in deci-degrees, both
    from the same generated columns."""
    rng = np.random.default_rng(seed)
    names = [f"station_{i:04d}" for i in range(stations)]
    decis = np.arange(-999, 1000)
    lines = np.array(
        [f"{name};{d / 10:.1f}\n" for name in names for d in decis],
        dtype=object,
    )
    mn = np.full(stations, 999, dtype=np.int64)
    mx = np.full(stations, -999, dtype=np.int64)
    total = np.zeros(stations, dtype=np.int64)
    count = np.zeros(stations, dtype=np.int64)
    with open(path, "w") as f:
        for start in range(0, rows, 1 << 20):
            m = min(1 << 20, rows - start)
            ids = rng.integers(0, stations, size=m)
            deci = np.clip(
                np.round(rng.normal(120, 100, size=m)), -999, 999
            ).astype(np.int64)
            f.write("".join(lines[ids * len(decis) + deci + 999].tolist()))
            np.minimum.at(mn, ids, deci)
            np.maximum.at(mx, ids, deci)
            total += np.bincount(ids, weights=deci, minlength=stations).astype(
                np.int64
            )
            count += np.bincount(ids, minlength=stations)
    return names, mn, mx, total, count


def _brc_flow(path: str, out: list, probe: Probe):
    """``examples/brc.py``'s flow with a checking sink."""
    import bytewax_tpu.operators as op
    from bytewax_tpu import xla
    from bytewax_tpu.dataflow import Dataflow
    from bytewax_tpu.models.brc import BrcFileSource

    flow = Dataflow("smoke_brc")
    s = op.input("inp", flow, BrcFileSource(path, part_count=4))
    stats = xla.stats_final("stats", s)
    op.output("out", stats, _sink(out, probe))
    return flow


def _check_brc(out: list, ref, rows: int) -> None:
    names, mn, mx, total, count = ref
    got = dict(out)
    require(len(out) == len(got), "brc: a station was emitted twice")
    live = [n for n, c in zip(names, count) if c]
    require(sorted(got) == sorted(live), "brc: station set differs")
    require(
        sum(v[3] for v in got.values()) == rows,
        "brc: row count in != row count out",
    )
    for i, name in enumerate(names):
        if not count[i]:
            continue
        g_mn, g_mean, g_mx, g_count = got[name]
        require(g_count == count[i], f"brc {name}: count")
        # tests/test_native.py's tolerances for the same flow.
        require(abs(g_mn - mn[i] / 10) < 1e-4, f"brc {name}: min")
        require(abs(g_mx - mx[i] / 10) < 1e-4, f"brc {name}: max")
        require(
            abs(g_mean - total[i] / count[i] / 10) < 1e-3,
            f"brc {name}: mean {g_mean} vs {total[i] / count[i] / 10}",
        )


def stage_brc(ctx: Ctx, mesh: bool = False) -> Dict[str, Any]:
    sz = ctx.sizes
    name = "mesh_brc" if mesh else "brc"
    path = os.path.join(ctx.workdir, f"{name}-measurements.txt")
    ref = _brc_file(path, sz.brc_rows, sz.brc_stations, ctx.seed)
    out: list = []
    with MeshSpy(mesh) as spy:
        run = run_flow(ctx, _brc_flow(path, out, ctx.probe))
    os.unlink(path)
    require(run["status"] is None, f"{name}: run did not reach EOF")
    _check_brc(out, ref, sz.brc_rows)
    return _report(
        ctx,
        name,
        [run],
        rows_in=sz.brc_rows,
        rows_out=len(out),
        stations=sz.brc_stations,
        **spy.checked(name),
    )


# -- stage: windows / resume ---------------------------------------------------


def _prefix_max_by_key(kid: np.ndarray, ts: np.ndarray) -> np.ndarray:
    """Per row, the largest ``ts`` among the rows of its key up to
    and including it, in arrival order."""
    order = np.argsort(kid, kind="stable")
    k, t = kid[order], ts[order]
    lo = int(t.min())
    band = int(t.max()) - lo + 1
    seg = np.cumsum(np.r_[False, k[1:] != k[:-1]])
    shifted = (t - lo) + seg * band
    out = np.empty_like(ts)
    out[order] = np.maximum.accumulate(shifted) - seg * band + lo
    return out


def _late(kid: np.ndarray, ts: np.ndarray) -> np.ndarray:
    """The EventClock's lateness rule on the data alone: a row is
    late when it is more than ``WAIT_S`` behind the newest event its
    key has shown so far (strictly)."""
    return ts < _prefix_max_by_key(kid, ts) - WAIT_S * 1_000_000


def window_events(sz: Sizes, seed: int) -> Dict[str, np.ndarray]:
    """Columns ``kid`` / ``ts`` (int64 us since ``ALIGN``) / ``value``
    in arrival order: a time-ordered stream with a seeded share of
    rows pushed back — some by less than the clock's slack (on time),
    some by more (late)."""
    rng = np.random.default_rng(seed + 1)
    # A little over the floor, so what the margin filter below drops
    # does not take the stream under it.
    n = sz.win_events + sz.win_events // 500
    span_us = sz.win_minutes * 60 * 1_000_000
    ts = np.sort(rng.integers(0, span_us, size=n))
    kid = rng.integers(0, sz.win_keys, size=n).astype(np.int32)
    value = np.round(rng.normal(20, 8, size=n), 1).astype(np.float32)
    pushed = rng.random(n) < OUT_OF_ORDER_SHARE
    far = pushed & (rng.random(n) < 0.5)
    near = pushed & ~far
    ts = ts - near * rng.integers(5_000_000, 30_000_000, size=n)
    ts = ts - far * rng.integers(270_000_000, 360_000_000, size=n)
    # No row whose lateness the wall clock would decide (see
    # CLOCK_MARGIN_S).  Such a row is behind its key's newest event,
    # so dropping it changes no other row's verdict.
    behind = _prefix_max_by_key(kid, ts) - ts
    lo = (WAIT_S - CLOCK_MARGIN_S) * 1_000_000
    keep = ~((behind > lo) & (behind <= WAIT_S * 1_000_000))
    return {"kid": kid[keep], "ts": ts[keep], "value": value[keep]}


def _column_source(
    cols: Dict[str, np.ndarray],
    vocab: np.ndarray,
    batch_rows: int,
    pace: Dict[str, Any],
    stop_after_closes: Optional[int] = None,
):
    """A resumable columnar source over in-memory columns (the resume
    state is the next row).  ``pace`` records the longest wait
    between two polls; with ``stop_after_closes`` the source asks for
    a graceful stop once that many epochs have closed."""
    from bytewax_tpu.engine import flight
    from bytewax_tpu.engine.arrays import ArrayBatch
    from bytewax_tpu.engine.driver import request_stop
    from bytewax_tpu.inputs import (
        FixedPartitionedSource,
        StatefulSourcePartition,
    )

    n = len(cols["ts"])
    base = np.datetime64(ALIGN.replace(tzinfo=None), "us")
    closes_at_start = flight.RECORDER.counters.get("epoch_close_count", 0)

    class _Part(StatefulSourcePartition):
        def __init__(self, resume_state):
            self._pos = resume_state or 0

        def next_batch(self):
            now = time.monotonic()
            if pace.get("last_poll") is not None:
                pace["max_gap_s"] = max(
                    pace.get("max_gap_s", 0.0), now - pace["last_poll"]
                )
            pace["last_poll"] = now
            if self._pos >= n:
                raise StopIteration()
            closes = (
                flight.RECORDER.counters.get("epoch_close_count", 0)
                - closes_at_start
            )
            if (
                stop_after_closes is not None
                and closes >= stop_after_closes
                and "stopped_at_row" not in pace
            ):
                pace["stopped_at_row"] = self._pos
                request_stop("chip_smoke")
            lo, hi = self._pos, min(self._pos + batch_rows, n)
            self._pos = hi
            batch = {
                "key_id": cols["kid"][lo:hi],
                "ts": base + cols["ts"][lo:hi].astype("timedelta64[us]"),
            }
            if "value" in cols:
                batch["value"] = cols["value"][lo:hi]
            return ArrayBatch(batch, key_vocab=vocab)

        def snapshot(self):
            return self._pos

    class _ColumnSource(FixedPartitionedSource):
        def list_parts(self):
            return ["columns"]

        def build_part(self, step_id, for_part, resume_state):
            return _Part(resume_state)

    return _ColumnSource()


def _key_vocab(n_keys: int) -> np.ndarray:
    return np.array([f"k{i:07d}" for i in range(n_keys)])


def _windows_flow(source, stats_out: list, counts_out: list, probe: Probe):
    import bytewax_tpu.operators as op
    import bytewax_tpu.operators.windowing as w
    from bytewax_tpu import xla
    from bytewax_tpu.dataflow import Dataflow

    clock = w.EventClock(
        ts_getter=xla.column_ts,
        wait_for_system_duration=timedelta(seconds=WAIT_S),
    )
    flow = Dataflow("smoke_windows")
    s = op.input("inp", flow, source)
    stats = w.fold_window(
        "stats",
        s,
        clock,
        w.TumblingWindower(align_to=ALIGN, length=timedelta(minutes=1)),
        xla.STATS.make_acc,
        xla.STATS,
        xla.STATS.merge,
    )
    op.output("stats_out", stats.down, _sink(stats_out, probe))
    counts = w.count_window(
        "counts",
        s,
        clock,
        w.SlidingWindower(
            align_to=ALIGN,
            length=timedelta(minutes=2),
            offset=timedelta(minutes=1),
        ),
        key=lambda row: row[0],
    )
    op.output("counts_out", counts.down, _sink(counts_out, probe))
    return flow


_WID_BIAS = 1 << 20


def _comp(kid, wid) -> np.ndarray:
    """One sortable int64 per (key, window id)."""
    return np.asarray(kid, dtype=np.int64) * (2 * _WID_BIAS) + (
        np.asarray(wid, dtype=np.int64) + _WID_BIAS
    )


def _windows_reference(cols: Dict[str, np.ndarray]):
    """numpy group-by on (key, window id), late rows dropped by the
    clock's rule.  Returns the tumbling stats and the sliding counts,
    each as arrays sorted by ``_comp``."""
    on_time = ~_late(cols["kid"], cols["ts"])
    kid = cols["kid"][on_time]
    ts = cols["ts"][on_time]
    value = cols["value"][on_time].astype(np.float64)
    minute = ts // 60_000_000  # floor, also before ALIGN

    comp = _comp(kid, minute)
    order = np.argsort(comp, kind="stable")
    uniq, starts, count = np.unique(
        comp[order], return_index=True, return_counts=True
    )
    v = value[order]
    stats = {
        "comp": uniq,
        "min": np.minimum.reduceat(v, starts),
        "max": np.maximum.reduceat(v, starts),
        "sum": np.add.reduceat(v, starts),
        "count": count,
    }
    # length 2 min, offset 1 min: a row is in its own minute's window
    # and in the one that opened a minute earlier.
    both = np.concatenate([comp, comp - 1])
    s_uniq, s_count = np.unique(both, return_counts=True)
    return stats, {"comp": s_uniq, "count": s_count}, int(on_time.sum())


def _kid_of(keys: List[str]) -> np.ndarray:
    return np.fromiter((int(k[1:]) for k in keys), dtype=np.int64, count=len(keys))


def _window_arrays(out: list) -> Dict[str, np.ndarray]:
    """Window-close emissions ``(key, (wid, acc))`` as arrays sorted
    by ``_comp``; ``acc`` is a count, or the stats fold's ``(min,
    max, sum, count)``."""
    n = len(out)
    comp = _comp(
        _kid_of([k for k, _ in out]),
        np.fromiter((v[0] for _, v in out), dtype=np.int64, count=n),
    )
    order = np.argsort(comp, kind="stable")
    acc = np.array([v[1] for _, v in out], dtype=np.float64).reshape(n, -1)
    names = ("min", "max", "sum", "count") if acc.shape[1] == 4 else ("count",)
    cols = {name: acc[order, i] for i, name in enumerate(names)}
    cols["count"] = cols["count"].astype(np.int64)
    return {"comp": comp[order], **cols}


def _same_windows(what: str, got, want) -> None:
    """Equal (key, window) sets, each exactly once; counts and
    extrema equal; sums to tests/test_window_accel.py's tolerance."""
    require(
        len(np.unique(got["comp"])) == len(got["comp"]),
        f"{what}: a (key, window) was emitted twice",
    )
    require(
        np.array_equal(got["comp"], want["comp"]),
        f"{what}: (key, window) set differs "
        f"({len(got['comp'])} vs {len(want['comp'])})",
    )
    require(
        np.array_equal(got["count"], want["count"]), f"{what}: counts differ"
    )
    for name in ("min", "max"):
        if name in want:
            require(
                np.allclose(got[name], want[name], rtol=1e-6, atol=0),
                f"{what}: {name} differs",
            )
    if "sum" in want:
        require(
            np.allclose(got["sum"], want["sum"], rtol=1e-4, atol=0),
            f"{what}: sums differ",
        )


def _run_windows(
    ctx: Ctx,
    cols,
    db_dir: str,
    pace: Dict[str, Any],
    stop_after_closes: Optional[int] = None,
):
    """One execution of the windows flow over ``cols`` against the
    recovery store at ``db_dir``; returns ``(run, stats_out,
    counts_out)``."""
    from bytewax_tpu.recovery import RecoveryConfig

    stats_out: list = []
    counts_out: list = []
    source = _column_source(
        cols,
        _key_vocab(ctx.sizes.win_keys),
        ctx.sizes.win_batch_rows,
        pace,
        stop_after_closes,
    )
    run = run_flow(
        ctx,
        _windows_flow(source, stats_out, counts_out, ctx.probe),
        # Zero-length epochs: one close (and one snapshot of every
        # touched key) per delivery, whatever the wall clock does.
        epoch_interval=timedelta(0),
        recovery_config=RecoveryConfig(
            db_dir, backup_interval=timedelta(0)
        ),
    )
    return run, stats_out, counts_out


def _new_store(ctx: Ctx, name: str) -> str:
    from bytewax_tpu.recovery import init_db_dir

    db_dir = os.path.join(ctx.workdir, name)
    os.makedirs(db_dir)
    init_db_dir(db_dir, 1)
    return db_dir


def _check_pace(what: str, pace: Dict[str, Any]) -> None:
    require(
        pace.get("max_gap_s", 0.0) * 2 < CLOCK_MARGIN_S,
        f"{what}: the source waited {pace.get('max_gap_s'):.1f}s between "
        f"polls; lateness within {CLOCK_MARGIN_S}s of the boundary is "
        "not decided by the data any more",
    )


def stage_windows(ctx: Ctx, mesh: bool = False):
    """Returns the stage's document and, for :func:`stage_resume`,
    the events with what the uninterrupted run made of them."""
    sz = ctx.sizes
    name = "mesh_windows" if mesh else "windows"
    cols = window_events(sz, ctx.seed)
    want_stats, want_counts, on_time = _windows_reference(cols)
    pace: Dict[str, Any] = {}
    with MeshSpy(mesh) as spy:
        run, stats_out, counts_out = _run_windows(
            ctx, cols, _new_store(ctx, f"{name}-db"), pace
        )
    require(run["status"] is None, f"{name}: run did not reach EOF")
    _check_pace(name, pace)
    require(
        run["counters"]["epoch_close_count"] >= 3,
        f"{name}: {run['counters']['epoch_close_count']} epoch closes, "
        "expected at least 3",
    )
    got_stats = _window_arrays(stats_out)
    got_counts = _window_arrays(counts_out)
    _same_windows(f"{name} tumbling stats", got_stats, want_stats)
    _same_windows(f"{name} sliding count", got_counts, want_counts)
    require(
        int(got_stats["count"].sum()) == on_time,
        f"{name}: on-time rows in != rows folded",
    )
    doc = _report(
        ctx,
        name,
        [run],
        rows_in=len(cols["ts"]),
        rows_late=len(cols["ts"]) - on_time,
        rows_out=len(stats_out) + len(counts_out),
        live_keys=int(len(np.unique(cols["kid"]))),
        windows_of_event_time=sz.win_minutes,
        max_poll_gap_s=round(pace.get("max_gap_s", 0.0), 3),
        **spy.checked(name),
    )
    return doc, (cols, got_stats, got_counts)


def stage_resume(ctx: Ctx, uninterrupted) -> Dict[str, Any]:
    """The windows flow again on a fresh store, stopped gracefully
    after its 2nd epoch close and resumed in this process: what the
    two executions emit together equals the uninterrupted run,
    exactly once."""
    cols, want_stats, want_counts = uninterrupted
    db_dir = _new_store(ctx, "resume-db")
    pace: Dict[str, Any] = {}
    first, stats_out, counts_out = _run_windows(
        ctx, cols, db_dir, pace, stop_after_closes=2
    )
    from bytewax_tpu.errors import GracefulStop

    require(
        isinstance(first["status"], GracefulStop),
        f"resume: first execution returned {first['status']!r}, "
        "not a graceful stop",
    )
    require(
        0 < pace["stopped_at_row"] < len(cols["ts"]),
        "resume: the stop did not land mid-stream",
    )
    emitted_before_stop = len(stats_out) + len(counts_out)
    second, stats_more, counts_more = _run_windows(ctx, cols, db_dir, pace)
    require(second["status"] is None, "resume: resumed run did not reach EOF")
    _check_pace("resume", pace)
    _same_windows(
        "resume tumbling stats",
        _window_arrays(stats_out + stats_more),
        want_stats,
    )
    _same_windows(
        "resume sliding count",
        _window_arrays(counts_out + counts_more),
        want_counts,
    )
    return _report(
        ctx,
        "resume",
        [first, second],
        rows_in=len(cols["ts"]),
        stopped_at_row=pace["stopped_at_row"],
        stopped_at_epoch=first["status"].epoch,
        rows_out_before_stop=emitted_before_stop,
        rows_out=emitted_before_stop + len(stats_more) + len(counts_more),
    )


# -- stage: scan_infer ----------------------------------------------------------


def _scan_events(sz: Sizes, seed: int) -> Dict[str, np.ndarray]:
    """A time-ordered keyed stream of zero-centred one-decimal
    readings with an occasional spike, for the z-score flows and the
    session count.  (Centred and coarse on purpose: the device keeps
    Welford state in float32, and the scan tests' absolute tolerance
    on z only means something while a key's first readings cannot
    sit arbitrarily close together around a large offset.)"""
    rng = np.random.default_rng(seed + 2)
    n = sz.scan_rows
    span_us = 10 * 60 * 1_000_000
    value = rng.normal(0, 1, size=n)
    spikes = rng.random(n) < 0.01
    value[spikes] += rng.choice([-5.0, 5.0], size=int(spikes.sum()))
    return {
        "kid": rng.integers(0, sz.scan_keys, size=n).astype(np.int32),
        "ts": np.sort(rng.integers(0, span_us, size=n)),
        "value": np.round(value, 1),
    }


def _items(cols: Dict[str, np.ndarray], vocab: np.ndarray) -> list:
    return list(zip(vocab[cols["kid"]].tolist(), cols["value"].tolist()))


def _by_key(out: list) -> Dict[str, list]:
    by: Dict[str, list] = {}
    for key, row in out:
        by.setdefault(key, []).append(row)
    return by


def _check_scored(what: str, got: list, want: list, keys: set) -> None:
    """``tests/test_scan_accel.py``'s comparison: per key, the same
    values in the same order, z within f32-vs-f64 tolerance, flags
    equal wherever the oracle's z is not within that tolerance of the
    threshold."""
    g = _by_key([kv for kv in got if kv[0] in keys])
    w = _by_key(want)
    require(g.keys() == w.keys(), f"{what}: oracle key set differs")
    for key, rows in w.items():
        require(len(g[key]) == len(rows), f"{what} {key}: row count")
        for (gv, gz, ga), (wv, wz, wa) in zip(g[key], rows):
            require(gv == wv, f"{what} {key}: value {gv} vs {wv}")
            require(abs(gz - wz) <= 1e-4, f"{what} {key}: z {gz} vs {wz}")
            if abs(abs(wz) - ZSCORE_THRESHOLD) > 1e-4:
                require(ga == wa, f"{what} {key}: flag at z={wz}")


def _run_on_host_tier(flow) -> None:
    """Run ``flow`` on the host tier (the oracle), off the chip."""
    from bytewax_tpu.testing import run_main

    prev = os.environ.get("BYTEWAX_TPU_ACCEL")
    os.environ["BYTEWAX_TPU_ACCEL"] = "0"
    try:
        run_main(flow)
    finally:
        if prev is None:
            del os.environ["BYTEWAX_TPU_ACCEL"]
        else:
            os.environ["BYTEWAX_TPU_ACCEL"] = prev


def _session_flow(source, sink):
    import bytewax_tpu.operators as op
    import bytewax_tpu.operators.windowing as w
    from bytewax_tpu import xla
    from bytewax_tpu.dataflow import Dataflow

    clock = w.EventClock(
        ts_getter=xla.column_ts,
        wait_for_system_duration=timedelta(seconds=WAIT_S),
    )
    flow = Dataflow("smoke_sessions")
    s = op.input("inp", flow, source)
    counts = w.count_window(
        "sessions",
        s,
        clock,
        w.SessionWindower(gap=timedelta(seconds=SESSION_GAP_S)),
        key=lambda row: row[0],
    )
    op.output("out", counts.down, sink)
    return flow


def stage_scan_infer(ctx: Ctx) -> Dict[str, Any]:
    from bytewax_tpu.models.anomaly import anomaly_flow, anomaly_infer_flow
    from bytewax_tpu.testing import TestingSink, TestingSource

    sz = ctx.sizes
    cols = _scan_events(sz, ctx.seed)
    vocab = _key_vocab(sz.scan_keys)
    items = _items(cols, vocab)
    in_oracle = cols["kid"] < sz.oracle_keys
    oracle_cols = {name: col[in_oracle] for name, col in cols.items()}
    oracle_items = _items(oracle_cols, vocab)
    oracle_keys = set(vocab[: sz.oracle_keys].tolist())

    runs = []
    rows_out = 0
    for what, make in (("scan", anomaly_flow), ("infer", anomaly_infer_flow)):
        out: list = []
        runs.append(
            run_flow(
                ctx,
                make(
                    TestingSource(items, batch_size=sz.scan_batch_rows),
                    _sink(out, ctx.probe),
                    threshold=ZSCORE_THRESHOLD,
                ),
            )
        )
        require(len(out) == len(items), f"{what}: rows out != rows in")
        want: list = []
        _run_on_host_tier(
            make(
                TestingSource(oracle_items, batch_size=sz.scan_batch_rows),
                TestingSink(want),
                threshold=ZSCORE_THRESHOLD,
            )
        )
        _check_scored(what, out, want, oracle_keys)
        rows_out += len(out)

    # Session count: the device tier over every key; the host tier
    # over the oracle keys' rows of the same polls.
    out = []
    pace: Dict[str, Any] = {}
    ts_cols = {"kid": cols["kid"], "ts": cols["ts"]}
    runs.append(
        run_flow(
            ctx,
            _session_flow(
                _column_source(ts_cols, vocab, sz.scan_batch_rows, pace),
                _sink(out, ctx.probe),
            ),
        )
    )
    _check_pace("sessions", pace)
    # A session per key, and one more wherever two consecutive rows
    # of a key are further apart than the gap.
    by_key = np.argsort(cols["kid"], kind="stable")
    same_key = np.diff(cols["kid"][by_key]) == 0
    gaps = np.diff(cols["ts"][by_key]) > SESSION_GAP_S * 1_000_000
    n_sessions = int((~same_key).sum() + 1 + (gaps & same_key).sum())
    require(
        sum(v[1] for _, v in out) == len(cols["ts"]),
        "sessions: rows counted != rows in",
    )
    require(
        len(out) == n_sessions,
        f"sessions: {len(out)} sessions, the gaps in the data make "
        f"{n_sessions}",
    )
    want = []
    _run_on_host_tier(
        _session_flow(
            _column_source(
                {"kid": oracle_cols["kid"], "ts": oracle_cols["ts"]},
                vocab,
                sz.scan_batch_rows,
                {},
            ),
            TestingSink(want),
        )
    )
    got_sessions = _by_key(out)
    for key, sessions in _by_key(want).items():
        require(
            sorted(got_sessions.get(key, ())) == sorted(sessions),
            f"sessions {key}: {got_sessions.get(key)} vs host {sessions}",
        )
    rows_out += len(out)
    return _report(
        ctx,
        "scan_infer",
        runs,
        flows=["scan", "infer", "sessions"],
        rows_in=3 * len(items),
        rows_out=rows_out,
        live_keys=int(len(np.unique(cols["kid"]))),
        sessions=n_sessions,
        oracle_keys=sz.oracle_keys,
        oracle_rows=len(oracle_items),
    )


# -- stage: kernels -------------------------------------------------------------


def stage_kernels(ctx: Ctx) -> Dict[str, Any]:
    """The fold's two forms through its jitted entry points, on both
    sides of the threshold: a table up to ``DENSE_MAX_SLOTS`` takes
    the dense reduce (the Pallas kernel, compiled), a larger one the
    XLA scatter, and either way the answer is the scatter's on the
    same rows.  Values are multiples of 1/8 in a small range, so
    every partial sum is exact in float32 and the two summation
    orders must agree to the tolerance tests/test_pallas_fold.py uses
    however many rows fold.  The milliseconds are smoke times."""
    import jax
    import jax.numpy as jnp

    from bytewax_tpu.engine import flight
    from bytewax_tpu.ops import pallas_fold
    from bytewax_tpu.ops.segment import (
        AGG_KINDS,
        fold_is_dense,
        init_fields,
        scatter_fields,
        update_fields,
        update_fields_packed,
    )

    interpreted = pallas_fold._interpret()
    require(
        ctx.allow_cpu or not interpreted,
        "kernels: the Pallas fold would run interpreted, not compiled",
    )
    flight.ensure_compile_listener()
    before = dict(flight.RECORDER.counters)
    kind = AGG_KINDS["stats"]
    scatter = jax.jit(scatter_fields, static_argnames=("kind",))
    rng = np.random.default_rng(ctx.seed + 3)
    n = ctx.sizes.kernel_rows

    def timed_ms(fold, cap):
        """The second call's wall time: the first compiled."""
        got = None
        for _ in range(2):
            state = init_fields(kind, cap)
            jax.block_until_ready(state)
            t0 = time.perf_counter()
            got = fold(state)
            jax.block_until_ready(got)
        return got, round((time.perf_counter() - t0) * 1e3, 3)

    def same(got, want, what):
        for name in kind.fields:
            g, w = np.asarray(got[name]), np.asarray(want[name])
            if name == "sum":
                ok = np.allclose(g, w, rtol=1e-5, atol=1e-5)
            else:
                ok = np.array_equal(g, w)
            require(ok, f"kernels: {what}, field {name} differs")

    t0 = time.perf_counter()
    forms = []
    for cap in ctx.sizes.kernel_caps:
        dense = fold_is_dense(init_fields(kind, cap))
        require(
            dense == (cap <= pallas_fold.DENSE_MAX_SLOTS),
            f"kernels: capacity {cap} takes the wrong form",
        )
        slots = jnp.asarray(rng.integers(0, cap - 1, size=n).astype(np.int32))
        values = jnp.asarray(
            (rng.integers(-512, 512, size=n) / 8).astype(np.float32)
        )
        want, scatter_ms = timed_ms(
            lambda st: scatter(kind, st, slots, values), cap
        )
        got, entry_ms = timed_ms(
            lambda st: update_fields(kind, st, slots, values), cap
        )
        same(got, want, f"capacity {cap}")
        require(
            int(np.asarray(got["count"]).sum()) == n,
            f"kernels: capacity {cap} lost rows",
        )
        forms.append(
            {
                "capacity": cap,
                "form": "dense" if dense else "scatter",
                "entry_ms": entry_ms,
                "scatter_ms": scatter_ms,
            }
        )
    # The packed entry as a 1BRC chunk calls it: int16 ids through an
    # id->slot table (the last entry is the padding's), deci-degrees.
    cap, n_ext = ctx.sizes.kernel_caps[0], ctx.sizes.brc_stations + 2
    table = np.full(n_ext, cap - 1, dtype=np.int32)
    table[: n_ext - 1] = rng.permutation(n_ext - 1)
    packed = np.stack(
        [
            rng.integers(0, n_ext - 1, size=n).astype(np.int16),
            (8 * rng.integers(-100, 100, size=n)).astype(np.int16),
        ]
    )
    table_d, packed_d = jnp.asarray(table), jnp.asarray(packed)
    scale = jnp.float32(0.125)
    slots = jnp.asarray(table[packed[0]])
    values = jnp.asarray(packed[1].astype(np.float32) * np.float32(0.125))
    want, _ms = timed_ms(lambda st: scatter(kind, st, slots, values), cap)
    got, packed_ms = timed_ms(
        lambda st: update_fields_packed(kind, st, table_d, packed_d, scale), cap
    )
    same(got, want, "the packed entry")
    forms.append(
        {"capacity": cap, "form": "dense, packed entry", "entry_ms": packed_ms}
    )
    run = {
        "wall_s": time.perf_counter() - t0,
        "counters": _counter_deltas(before),
    }
    return _report(
        ctx,
        "kernels",
        [run],
        rows_in=n * (len(ctx.sizes.kernel_caps) + 1),
        rows_a_fold=n,
        capacities=list(ctx.sizes.kernel_caps),
        forms_ms_not_a_benchmark=forms,
        pallas_interpreted=interpreted,
    )


# -- stage: mesh ----------------------------------------------------------------


class MeshSpy:
    """With ``on``, watches the aggregate states the engine builds
    through its own factory while a flow runs: their class, the mesh
    they span, and — read whenever the engine itself fetches a table
    — which devices' shards hold folded slots.  Off, it watches
    nothing and reports nothing."""

    def __init__(self, on: bool):
        self.on = on
        self.classes: List[str] = []
        self.mesh_sizes: List[int] = []
        self.occupied: Dict[int, bool] = {}

    def _watch(self, state):
        self.classes.append(type(state).__name__)
        self.mesh_sizes.append(getattr(state, "n_shards", 1))
        fetch = state._fetch

        def watched_fetch():
            fields = state._fields
            if fields is not None and not (
                self.occupied and all(self.occupied.values())
            ):
                for shard in fields["count"].addressable_shards:
                    dev = shard.device.id
                    self.occupied[dev] = self.occupied.get(dev, False) or bool(
                        np.asarray(shard.data).any()
                    )
            return fetch()

        state._fetch = watched_fetch
        return state

    def __enter__(self):
        if self.on:
            from bytewax_tpu.engine import sharded_state

            self._module = sharded_state
            self._factory = sharded_state.make_agg_state
            sharded_state.make_agg_state = lambda *a, **kw: self._watch(
                self._factory(*a, **kw)
            )
        return self

    def __exit__(self, *exc):
        if self.on:
            self._module.make_agg_state = self._factory

    def checked(self, what: str) -> Dict[str, Any]:
        """The mesh facts of the watched run, held to: every state
        sharded, over every local device, every device holding folded
        slots."""
        if not self.on:
            return {}
        import jax

        n_devices = jax.local_device_count()
        require(
            bool(self.classes) and set(self.classes) == {"ShardedAggState"},
            f"{what}: aggregate state classes {self.classes}",
        )
        require(
            set(self.mesh_sizes) == {n_devices},
            f"{what}: mesh sizes {self.mesh_sizes}, devices {n_devices}",
        )
        require(
            len(self.occupied) == n_devices and all(self.occupied.values()),
            f"{what}: devices holding folded slots: {self.occupied}",
        )
        return {
            "state_class": "ShardedAggState",
            "mesh_size": n_devices,
            "devices_holding_state": sorted(self.occupied),
        }


def stage_mesh(ctx: Ctx) -> None:
    """``brc`` and ``windows`` again with the state sharded over every
    local device."""
    os.environ["BYTEWAX_TPU_SHARD"] = "auto"
    try:
        stage_brc(ctx, mesh=True)
        stage_windows(ctx, mesh=True)
    finally:
        os.environ["BYTEWAX_TPU_SHARD"] = "0"


# -- start-up ------------------------------------------------------------------


def rebuild_native() -> Dict[str, bool]:
    """Delete any native library already in the tree and build both
    from the committed sources on this host."""
    for path in glob.glob(os.path.join(REPO, "bytewax_tpu", "native", "_*.so")):
        os.unlink(path)
    from bytewax_tpu import native

    built = {
        "io_native": native.is_available(),
        "host_ops": native._ext() is not None,
    }
    require(all(built.values()), f"native libraries did not build: {built}")
    return built


def device_or_exit(allow_cpu: bool) -> Dict[str, Any]:
    """The device as jax reports it; exits non-zero, with nothing on
    standard output, when it is not a TPU (unless waived)."""
    import jax

    devices = jax.devices()
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    if device["platform"] != "tpu" and not allow_cpu:
        sys.exit(
            f"chip_smoke: jax found no TPU (platform "
            f"{device['platform']!r}); pass --allow-cpu to rehearse on "
            "another backend"
        )
    return device


def run_stages(ctx: Ctx) -> None:
    import jax

    # The single-device path first, whatever the host has; the mesh
    # stage then shards over all of it.
    os.environ["BYTEWAX_TPU_SHARD"] = "0"
    stage_brc(ctx)
    _doc, uninterrupted = stage_windows(ctx)
    stage_resume(ctx, uninterrupted)
    del uninterrupted
    stage_scan_infer(ctx)
    stage_kernels(ctx)
    if jax.local_device_count() > 1:
        stage_mesh(ctx)


def main(argv: Optional[List[str]] = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument(
        "--scale",
        type=float,
        default=1.0,
        help="multiply every stage's row count (keys stay); below 1.0 "
        "the output says what was cut",
    )
    parser.add_argument(
        "--allow-cpu",
        action="store_true",
        help="rehearse on a backend that is not a TPU (the result line "
        "then names that backend)",
    )
    args = parser.parse_args(argv)

    sys.path.insert(0, REPO)
    device = device_or_exit(args.allow_cpu)
    sizes = FLOORS if args.scale == 1.0 else FLOORS.scaled(args.scale)
    workdir = tempfile.mkdtemp(prefix="chip_smoke-")
    os.environ["BYTEWAX_DATAFLOW_API_ENABLED"] = "1"
    port = _free_port()
    os.environ["BYTEWAX_DATAFLOW_API_PORT"] = str(port)
    cwd = os.getcwd()
    os.chdir(workdir)  # the API plane dumps dataflow.json into the cwd
    t0 = time.perf_counter()
    try:
        ctx = Ctx(
            sizes=sizes,
            seed=args.seed,
            workdir=workdir,
            probe=Probe(port),
            allow_cpu=args.allow_cpu,
            device=device,
            native=rebuild_native(),
        )
        print(
            json.dumps(
                {
                    "stage": "start",
                    "device": device,
                    "seed": args.seed,
                    "sizes": asdict(sizes),
                    "cut_from_floors": {
                        name: f"{getattr(sizes, name)}/{floor}"
                        for name, floor in asdict(FLOORS).items()
                        if getattr(sizes, name) != floor
                    },
                }
            ),
            flush=True,
        )
        run_stages(ctx)
        from bytewax_tpu.engine import flight

        print(
            json.dumps(
                {
                    "stage": "end",
                    "compile_cache_dir": ctx.probe.status["compile_cache_dir"],
                    "xla_compile_count": int(
                        flight.RECORDER.counters.get("xla_compile_count", 0)
                    ),
                    "xla_compile_seconds": round(
                        flight.RECORDER.counters.get("xla_compile_seconds", 0.0),
                        3,
                    ),
                    "wall_s_not_a_benchmark": round(
                        time.perf_counter() - t0, 1
                    ),
                }
            ),
            flush=True,
        )
    finally:
        os.chdir(cwd)
        shutil.rmtree(workdir, ignore_errors=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
