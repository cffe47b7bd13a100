"""Benchmark harness covering the full BASELINE.json metric:
1BRC + wordcount events/sec/chip and fold_window p99 window-close
latency, plus the isolated device-step time.

Prints ONE JSON line::

    {"metric", "value", "unit", "vs_baseline", "extra": {...}}

The headline value is the 1BRC XLA-tier events/sec on this chip and
``vs_baseline`` its speedup over the host tier (per-item Python — the
stand-in for the reference's per-item Timely+GIL path, since the
reference's Rust engine is not installable here).  ``extra`` carries
the windowing/wordcount/device-step sub-metrics, the device jax gave
the run, and ``failed_phases``; the exit code is non-zero when any
phase failed or when jax found no accelerator and the CPU backend
was not asked for by name.
"""

import json
import os
import subprocess
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


def _device() -> dict:
    """The backend jax gives this process, as jax reports it.  The
    bench takes what it gets — no probe, no retry — and refuses the
    CPU backend unless the caller asked for it by name
    (``BYTEWAX_TPU_PLATFORM=cpu`` / ``JAX_PLATFORMS=cpu``,
    docs/profiling.md): a CPU figure must never stand in for a chip
    figure by accident."""
    from bytewax_tpu.utils import cpu_asked_for, force_platform

    plat = os.environ.get("BYTEWAX_TPU_PLATFORM")
    if plat:
        force_platform(plat)
    import jax

    devices = jax.devices()
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    print(json.dumps({"device": device}), file=sys.stderr)
    if device["platform"] == "cpu" and not cpu_asked_for():
        sys.exit(
            "bench.py: jax found no accelerator (platform cpu); set "
            "BYTEWAX_TPU_PLATFORM=cpu or JAX_PLATFORMS=cpu to bench "
            "the CPU backend on purpose"
        )
    return device


# -- 1BRC --------------------------------------------------------------------


def _run_columnar(n_rows: int, batch_rows: int) -> float:
    from bytewax_tpu.models.brc import (
        ArrayBatchSource,
        brc_flow_columnar,
        generate_batches,
    )
    from bytewax_tpu.testing import TestingSink, run_main

    batches = generate_batches(n_rows, batch_rows)
    out = []
    flow = brc_flow_columnar(ArrayBatchSource(batches), TestingSink(out))
    t0 = time.perf_counter()
    run_main(flow)
    dt = time.perf_counter() - t0
    assert len(out) == 413, f"expected 413 stations, got {len(out)}"
    return n_rows / dt


def _run_itemized(n_rows: int, batch_rows: int) -> float:
    """The 1BRC aggregation over itemized ``(key, value)`` tuples with
    acceleration ON: measures the itemized→columnar promotion at the
    accel boundary (native grouper + value flatten) — ported-from-
    bytewax flows feed this shape, so it should track
    ``_run_columnar`` within a small factor."""
    from bytewax_tpu.models.brc import (
        ArrayBatchSource,
        brc_flow,
        generate_batches,
    )
    from bytewax_tpu.testing import TestingSink, run_main

    batches = [
        b.to_pylist() for b in generate_batches(n_rows, batch_rows)
    ]
    out = []
    flow = brc_flow(ArrayBatchSource(batches), TestingSink(out))
    t0 = time.perf_counter()
    run_main(flow)
    dt = time.perf_counter() - t0
    assert len(out) == 413
    return n_rows / dt


def _run_ingest_columnar(n_rows: int) -> float:
    """End-to-end columnar ingest (docs/performance.md "Columnar
    ingest"): a 1BRC-shaped line file read in raw chunks by
    ``FileSource(columnar=True)``, split and parsed in vectorized
    passes (ops/text), folded on the device tier — no per-row Python
    anywhere on the path.  The result is asserted against a
    host-built numpy oracle, so the rate only counts correct runs."""
    import tempfile

    import numpy as np

    import bytewax_tpu.operators as op
    from bytewax_tpu import xla
    from bytewax_tpu.connectors.files import FileSource
    from bytewax_tpu.dataflow import Dataflow
    from bytewax_tpu.engine.arrays import ArrayBatch
    from bytewax_tpu.ops.text import split_fields
    from bytewax_tpu.testing import TestingSink, run_main

    n_stations = 413
    rng = np.random.RandomState(7)
    station_ids = rng.randint(0, n_stations, size=n_rows)
    deci = np.clip(
        np.round(rng.randn(n_rows) * 100 + 120), -999, 999
    ).astype(np.int64)
    stations = np.array([f"station_{i:04d}" for i in range(n_stations)])
    temps = deci / 10.0
    lines = np.char.add(
        np.char.add(stations[station_ids], ";"),
        np.char.mod("%.1f", temps),
    )

    # Host oracle: per-station min/mean/max, rounded like the flow.
    mins = np.full(n_stations, np.inf)
    maxs = np.full(n_stations, -np.inf)
    np.minimum.at(mins, station_ids, temps)
    np.maximum.at(maxs, station_ids, temps)
    sums = np.bincount(station_ids, weights=temps, minlength=n_stations)
    counts = np.bincount(station_ids, minlength=n_stations)
    oracle = {
        str(stations[i]): (
            round(float(mins[i]), 1),
            round(float(sums[i] / counts[i]), 1),
            round(float(maxs[i]), 1),
        )
        for i in range(n_stations)
        if counts[i]
    }

    def parse(batch):
        cols = split_fields(batch.cols["line"], 2, ";")
        return ArrayBatch(
            {"key": cols[0], "value": cols[1].astype(np.float64)}
        )

    with tempfile.TemporaryDirectory() as td:
        path = os.path.join(td, "measurements.txt")
        with open(path, "w") as f:
            f.write("\n".join(lines.tolist()))
            f.write("\n")
        out = []
        flow = Dataflow("ingest_columnar")
        s = op.input(
            "inp", flow, FileSource(path, columnar=True, chunk_bytes=1 << 20)
        )
        parsed = op.flat_map_batch("parse", s, parse)
        stats = xla.stats_final("stats", parsed)
        rounded = op.map_value(
            "round",
            stats,
            lambda s4: (round(s4[0], 1), round(s4[1], 1), round(s4[2], 1)),
        )
        op.output("out", rounded, TestingSink(out))
        t0 = time.perf_counter()
        run_main(flow)
        dt = time.perf_counter() - t0
    got = dict(out)
    assert len(got) == len(oracle), (
        f"expected {len(oracle)} stations, got {len(got)}"
    )
    for k, want in oracle.items():
        have = got[k]
        assert all(
            abs(h - w) <= 0.1 + 1e-9 for h, w in zip(have, want)
        ), f"station {k}: columnar ingest {have} != oracle {want}"
    return n_rows / dt


def _run_host(n_rows: int, batch_rows: int) -> float:
    from bytewax_tpu.models.brc import (
        ArrayBatchSource,
        brc_flow,
        generate_batches,
    )
    from bytewax_tpu.testing import TestingSink, run_main

    os.environ["BYTEWAX_TPU_ACCEL"] = "0"
    try:
        batches = [
            b.to_pylist() for b in generate_batches(n_rows, batch_rows)
        ]
        out = []
        flow = brc_flow(ArrayBatchSource(batches), TestingSink(out))
        t0 = time.perf_counter()
        run_main(flow)
        dt = time.perf_counter() - t0
        assert len(out) == 413
        return n_rows / dt
    finally:
        os.environ.pop("BYTEWAX_TPU_ACCEL", None)


# -- windowing ---------------------------------------------------------------


def _run_windowing_host(batch_size: int, batch_count: int) -> float:
    """The reference benchmark shape (list-append fold_window, 2 keys,
    1-min tumbling, event time: examples/benchmark_windowing.py:11-39)
    on the host tier; returns events/sec."""
    from bytewax_tpu.models.windowing_bench import (
        make_input,
        windowing_bench_flow,
    )
    from bytewax_tpu.testing import TestingSink, TestingSource, run_main

    os.environ["BYTEWAX_TPU_ACCEL"] = "0"
    try:
        inp = make_input(batch_size, batch_count)
        out = []
        flow = windowing_bench_flow(
            TestingSource(inp, batch_size=batch_size), TestingSink(out)
        )
        t0 = time.perf_counter()
        run_main(flow)
        dt = time.perf_counter() - t0
        return len(inp) / dt
    finally:
        os.environ.pop("BYTEWAX_TPU_ACCEL", None)


def _run_windowing_columnar(
    n_rows: int,
    batch_rows: int,
    accel: bool,
    dict_keys: bool = True,
    depth: int = None,
) -> float:
    """A steady on-time event stream (10 rows per event-second — the
    reference shape's density — 2 keys, 1-min tumbling count) as
    columnar batches, on the device tier or the host tier (same
    shape, so the ratio isolates the tier); returns events/sec.

    ``dict_keys`` selects dictionary-encoded keys (the fast path) vs
    string keys — both are reported so round-over-round numbers stay
    comparable with earlier string-keyed baselines.  ``depth``
    overrides the dispatch-pipeline depth (1 = the synchronous
    lock-step engine, default = BYTEWAX_TPU_PIPELINE_DEPTH)."""
    from datetime import timedelta

    import numpy as np

    import bytewax_tpu.operators as op
    import bytewax_tpu.operators.windowing as w
    from bytewax_tpu.dataflow import Dataflow
    from bytewax_tpu.engine.arrays import ArrayBatch
    from bytewax_tpu.models.brc import ArrayBatchSource
    from bytewax_tpu.models.windowing_bench import ALIGN_TO
    from bytewax_tpu.operators.windowing import EventClock, TumblingWindower
    from bytewax_tpu.testing import TestingSink, run_main

    rng = np.random.RandomState(42)
    base = np.datetime64(ALIGN_TO.replace(tzinfo=None), "us")
    vocab = np.array(["0", "1"])  # dictionary-encoded keys: the fast path
    batches = []
    for i in range(0, n_rows, batch_rows):
        m = min(batch_rows, n_rows - i)
        secs = (np.arange(i, i + m) // 10).astype("timedelta64[s]")
        key_ids = rng.randint(0, 2, size=m)
        if dict_keys:
            cols = {"key_id": key_ids.astype(np.int32), "ts": base + secs}
            batches.append(ArrayBatch(cols, key_vocab=vocab))
        else:
            batches.append(
                ArrayBatch({"key": key_ids.astype(str), "ts": base + secs})
            )
    clock = EventClock(
        ts_getter=lambda x: x, wait_for_system_duration=timedelta(0)
    )
    windower = TumblingWindower(
        align_to=ALIGN_TO, length=timedelta(minutes=1)
    )
    out = []
    flow = Dataflow("winbench")
    s = op.input("in", flow, ArrayBatchSource(batches))
    wo = w.count_window("count", s, clock, windower, key=lambda x: x)
    op.output("out", wo.down, TestingSink(out))
    os.environ["BYTEWAX_TPU_ACCEL"] = "1" if accel else "0"
    prev_depth = os.environ.get("BYTEWAX_TPU_PIPELINE_DEPTH")
    if depth is not None:
        os.environ["BYTEWAX_TPU_PIPELINE_DEPTH"] = str(depth)
    try:
        t0 = time.perf_counter()
        run_main(flow)
        dt = time.perf_counter() - t0
    finally:
        os.environ.pop("BYTEWAX_TPU_ACCEL", None)
        if depth is not None:
            if prev_depth is None:
                os.environ.pop("BYTEWAX_TPU_PIPELINE_DEPTH", None)
            else:
                os.environ["BYTEWAX_TPU_PIPELINE_DEPTH"] = prev_depth
    return n_rows / dt


def _run_windowing_itemized(n_rows: int, accel: bool) -> float:
    """The reference benchmark's *itemized* shape — Python datetime
    items, event-time 1-minute tumbling windows, 2 keys
    (examples/benchmark_windowing.py:11-39) — through count_window.
    With ``accel`` the rows ride the native itemized→columnar
    windowing promotion (wa_encode + vectorized ingest); without, the
    host tier folds per item.  Returns events/sec."""
    from datetime import timedelta

    import bytewax_tpu.operators as op
    import bytewax_tpu.operators.windowing as w
    from bytewax_tpu.dataflow import Dataflow
    from bytewax_tpu.models.windowing_bench import ALIGN_TO
    from bytewax_tpu.operators.windowing import EventClock, TumblingWindower
    from bytewax_tpu.testing import TestingSink, TestingSource, run_main

    # 10 events per event-second, like the columnar variant.
    inp = [
        ALIGN_TO + timedelta(seconds=i // 10) for i in range(n_rows)
    ]
    clock = EventClock(
        ts_getter=lambda x: x, wait_for_system_duration=timedelta(0)
    )
    windower = TumblingWindower(
        align_to=ALIGN_TO, length=timedelta(minutes=1)
    )
    keys = ("0", "1")
    out = []
    flow = Dataflow("winbench_item")
    s = op.input("in", flow, TestingSource(inp, batch_size=65_536))
    wo = w.count_window(
        "count", s, clock, windower, key=lambda dt: keys[dt.second & 1]
    )
    op.output("out", wo.down, TestingSink(out))
    os.environ["BYTEWAX_TPU_ACCEL"] = "1" if accel else "0"
    try:
        t0 = time.perf_counter()
        run_main(flow)
        dt = time.perf_counter() - t0
    finally:
        os.environ.pop("BYTEWAX_TPU_ACCEL", None)
    return n_rows / dt


def _run_windowing_session(n_rows: int, batch_rows: int) -> float:
    """Session-windowed count on columnar batches (device gap-merge
    scan): 2 keys, ~1 event/sec per key with a >gap jump every ~1000
    events so sessions keep closing; returns events/sec."""
    from datetime import timedelta

    import numpy as np

    import bytewax_tpu.operators as op
    import bytewax_tpu.operators.windowing as w
    from bytewax_tpu.dataflow import Dataflow
    from bytewax_tpu.engine.arrays import ArrayBatch
    from bytewax_tpu.models.brc import ArrayBatchSource
    from bytewax_tpu.models.windowing_bench import ALIGN_TO
    from bytewax_tpu.operators.windowing import EventClock, SessionWindower
    from bytewax_tpu.testing import TestingSink, run_main

    rng = np.random.RandomState(42)
    base = np.datetime64(ALIGN_TO.replace(tzinfo=None), "us")
    # Mostly 1s steps with a 120s (> gap) jump every ~1000 rows.
    steps = np.ones(n_rows, dtype=np.int64)
    steps[rng.rand(n_rows) < 0.001] = 120
    secs = np.cumsum(steps)
    batches = []
    for i in range(0, n_rows, batch_rows):
        m = min(batch_rows, n_rows - i)
        batches.append(
            ArrayBatch(
                {
                    "key": rng.randint(0, 2, size=m).astype(str),
                    "ts": base + secs[i : i + m].astype("timedelta64[s]"),
                }
            )
        )
    clock = EventClock(
        ts_getter=lambda x: x, wait_for_system_duration=timedelta(0)
    )
    windower = SessionWindower(gap=timedelta(seconds=60))
    out = []
    flow = Dataflow("sessbench")
    s = op.input("in", flow, ArrayBatchSource(batches))
    wo = w.count_window("count", s, clock, windower, key=lambda x: x)
    op.output("out", wo.down, TestingSink(out))
    os.environ["BYTEWAX_TPU_ACCEL"] = "1"
    try:
        t0 = time.perf_counter()
        run_main(flow)
        dt = time.perf_counter() - t0
    finally:
        os.environ.pop("BYTEWAX_TPU_ACCEL", None)
    return n_rows / dt


def _run_flowmap_overhead():
    """Flow-map observability overhead (docs/observability.md "Flow
    map"): the pipelined windowed bench with the API server up and a
    thread polling ``GET /graph`` continuously, vs idle — the flow
    map must stay ledger-cheap (dict adds sealed per epoch), so the
    polled run is asserted within 3% of the idle run.  Returns
    ``(overhead_pct, polls, bottleneck_step)``; the bottleneck is the
    derived attribution over the run's sealed records."""
    import threading
    import urllib.request

    rows = 1 << 21
    idle = max(
        _run_windowing_columnar(rows, 1 << 19, accel=True, depth=2)
        for _ in range(2)
    )

    port = 13990
    os.environ["BYTEWAX_DATAFLOW_API_ENABLED"] = "1"
    os.environ["BYTEWAX_DATAFLOW_API_PORT"] = str(port)
    stop = threading.Event()
    seen = {"polls": 0, "bottleneck": None}

    def _poll():
        while not stop.is_set():
            try:
                with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/graph", timeout=1
                ) as resp:
                    doc = json.loads(resp.read())
                seen["polls"] += 1
                if doc.get("bottleneck"):
                    seen["bottleneck"] = doc["bottleneck"]["step"]
            except Exception:  # noqa: BLE001 - server cycles per rep
                pass
            stop.wait(0.05)

    poller = threading.Thread(target=_poll, daemon=True)
    poller.start()
    try:
        polled = max(
            _run_windowing_columnar(
                rows, 1 << 19, accel=True, depth=2
            )
            for _ in range(2)
        )
    finally:
        stop.set()
        poller.join(timeout=5)
        os.environ.pop("BYTEWAX_DATAFLOW_API_ENABLED", None)
        os.environ.pop("BYTEWAX_DATAFLOW_API_PORT", None)

    overhead_pct = (idle - polled) / idle * 100.0
    assert overhead_pct < 3.0, (
        f"flow-map polling cost {overhead_pct:.1f}% "
        f"({idle:.0f} -> {polled:.0f} events/s)"
    )

    bottleneck = seen["bottleneck"]
    if bottleneck is None:
        # Single-epoch EOF runs seal after the last poll window:
        # derive from the sealed ledger directly (same pure
        # attribution /graph uses).
        from bytewax_tpu.engine import flight, flowmap

        ledger = flight.RECORDER.last_ledger or {}
        steps = {}
        for phase_steps in ledger.get("phases", {}).values():
            for step, s in phase_steps.items():
                if step == "*":
                    continue
                ent = steps.setdefault(step, {})
                ent["busy_s"] = ent.get("busy_s", 0.0) + s
        for step, depth in ledger.get(
            "queue_depth_at_drain", {}
        ).items():
            steps.setdefault(step, {})["queue_depth"] = depth
        bn = flowmap.derive_bottleneck(steps)
        bottleneck = bn[0] if bn else None
    return overhead_pct, seen["polls"], bottleneck


def _run_window_close_p99(n_batches: int = 200, batch_size: int = 1000):
    """p99 window-close latency: wall time from the source emitting
    the batch whose events push the watermark past a window's close to
    the close (meta) event reaching the sink.  A progressive event-
    time stream (1 s per item, 2 keys, 1-min tumbling) closes ~16
    windows per batch at steady state."""
    from datetime import timedelta

    import numpy as np

    import bytewax_tpu.operators as op
    import bytewax_tpu.operators.windowing as w
    from bytewax_tpu.dataflow import Dataflow
    from bytewax_tpu.inputs import DynamicSource, StatelessSourcePartition
    from bytewax_tpu.models.windowing_bench import ALIGN_TO
    from bytewax_tpu.operators.windowing import EventClock, TumblingWindower
    from bytewax_tpu.outputs import DynamicSink, StatelessSinkPartition
    from bytewax_tpu.testing import TestingSink, run_main

    wm_log = []  # (wall, max event ts) after each emitted batch
    meta_log = []  # (wall, close_time) per window-close meta event

    class _Src(StatelessSourcePartition):
        def __init__(self):
            self._i = 0

        def next_batch(self):
            if self._i >= n_batches:
                raise StopIteration()
            lo = self._i * batch_size
            batch = [
                ALIGN_TO + timedelta(seconds=lo + j)
                for j in range(batch_size)
            ]
            self._i += 1
            wm_log.append(
                (time.perf_counter(), lo + batch_size - 1, self._i - 1)
            )
            return batch

    class _SrcSource(DynamicSource):
        def build(self, step_id, worker_index, worker_count):
            return _Src() if worker_index == 0 else _Empty()

    class _Empty(StatelessSourcePartition):
        def next_batch(self):
            raise StopIteration()

    class _MetaPart(StatelessSinkPartition):
        def write_batch(self, items):
            now = time.perf_counter()
            meta_log.extend((now, it) for it in items)

    class _MetaSink(DynamicSink):
        def build(self, step_id, worker_index, worker_count):
            return _MetaPart()

    clock = EventClock(
        ts_getter=lambda x: x, wait_for_system_duration=timedelta(0)
    )
    windower = TumblingWindower(
        align_to=ALIGN_TO, length=timedelta(minutes=1)
    )
    flow = Dataflow("close_lat")
    import random

    rand = random.Random(7)
    s = op.input("in", flow, _SrcSource())
    wo = w.count_window(
        "count", s, clock, windower, key=lambda _x: str(rand.randrange(2))
    )
    drop = op.filter("drop", wo.down, lambda _x: False)
    op.output("down", drop, TestingSink([]))
    op.output("meta", wo.meta, _MetaSink())
    run_main(flow)

    # Latency per close: sink wall minus the wall of the first batch
    # whose max event ts reached the close.  Closes crossed by the
    # first batches are excluded — they time jit compilation, not the
    # steady state a latency percentile is about.
    import bisect

    warmup_batches = max(5, n_batches // 10)
    lats = []
    walls = [wl for wl, _ts, _b in wm_log]
    maxes = [ts for _wl, ts, _b in wm_log]
    for recv_wall, item in meta_log:
        _key, (_wid, meta) = item
        close_s = (meta.close_time - ALIGN_TO).total_seconds()
        i = bisect.bisect_left(maxes, close_s)  # first max ts >= close
        if i < len(walls) and wm_log[i][2] >= warmup_batches:
            lats.append(recv_wall - walls[i])
    if not lats:
        return None, 0
    lats.sort()
    return lats[int(len(lats) * 0.99)], len(lats)


# -- wordcount ---------------------------------------------------------------


def _run_wordcount(n_lines: int, words_per_line: int = 10) -> float:
    """Wordcount (reference: examples/wordcount.py): host tokenize →
    device keyed count; returns steady-state word-events/sec.

    The per-word slot table grows by doubling, and each capacity is a
    distinct XLA shape compiled once per process — warm the full
    growth path (same vocab) before timing, like the other benches,
    so the timed run measures the engine rather than jit compiles."""
    import numpy as np

    from bytewax_tpu.models.wordcount import wordcount_flow
    from bytewax_tpu.testing import TestingSink, TestingSource, run_main

    import itertools
    import string

    rng = np.random.RandomState(0)
    # Letter-only words (the default tokenizer strips digits).
    vocab = np.array(
        [
            "w" + "".join(c)
            for c in itertools.islice(
                itertools.product(string.ascii_lowercase, repeat=3), 1000
            )
        ]
    )
    lines = [
        " ".join(vocab[rng.randint(0, 1000, size=words_per_line)])
        for _ in range(n_lines)
    ]
    # Warm run over the same vocab: replays every slot-table capacity
    # the timed run will hit, so its scatter shapes are all cached.
    warm = []
    run_main(
        wordcount_flow(
            TestingSource(lines[: max(1000, n_lines // 10)], batch_size=1000),
            TestingSink(warm),
        )
    )
    out = []
    flow = wordcount_flow(
        TestingSource(lines, batch_size=1000), TestingSink(out)
    )
    t0 = time.perf_counter()
    run_main(flow)
    dt = time.perf_counter() - t0
    assert len(out) == 1000
    return n_lines * words_per_line / dt


# -- anomaly detector --------------------------------------------------------


def _run_anomaly(n_rows: int, n_keys: int = 50):
    """Per-key rolling z-score via stateful_map (reference:
    examples/anomaly_detector.py) — the per-item stateful hot path.

    Warms the scan kernel's compiled shape first (like every other
    bench here — a streaming deployment runs warm), then times
    steady state over the full input, best of 2.  Returns
    ``(events/sec, cold_first_run_seconds)`` so the one-time jit cost
    is reported instead of silently amortized or silently included.
    """
    import numpy as np

    from bytewax_tpu.models.anomaly import anomaly_flow
    from bytewax_tpu.testing import TestingSink, TestingSource, run_main

    rng = np.random.RandomState(3)
    keys = [f"sensor_{i:02d}" for i in range(n_keys)]
    inp = list(
        zip(
            (keys[i] for i in rng.randint(0, n_keys, size=n_rows)),
            rng.randn(n_rows).tolist(),
        )
    )
    # Power-of-two batches match the device tier's padding
    # granularity (no padded-row waste in the scan kernel).
    batch_size = 16_384

    # Cold run over two batches: pays the scan kernel's compile (all
    # timed batches pad to the same shape, so two batches cover it).
    warm_rows = min(n_rows, 2 * batch_size)
    warm_out = []
    t0 = time.perf_counter()
    run_main(
        anomaly_flow(
            TestingSource(inp[:warm_rows], batch_size=batch_size),
            TestingSink(warm_out),
        )
    )
    cold_s = time.perf_counter() - t0

    rate = 0.0
    for _ in range(2):
        out = []
        flow = anomaly_flow(
            TestingSource(inp, batch_size=batch_size), TestingSink(out)
        )
        t0 = time.perf_counter()
        run_main(flow)
        dt = time.perf_counter() - t0
        assert len(out) == n_rows
        rate = max(rate, n_rows / dt)
    return rate, cold_s


_ANOMALY_COLD_SCRIPT = """
import json, os, sys, time

sys.path.insert(0, {repo!r})
import jax

jax.local_devices()  # backend up-front: time the FLOW cold start
import numpy as np

from bytewax_tpu.models.anomaly import anomaly_flow
from bytewax_tpu.testing import TestingSink, TestingSource, run_main

# Warm the GENERIC machinery (engine, jax tracing internals) with an
# unrelated keyed-sum flow, so the timed run isolates the anomaly
# scan kernel's own trace+compile — the portion the persistent
# compilation cache can (partly) eliminate.
import bytewax_tpu.operators as _op
from bytewax_tpu import xla as _xla
from bytewax_tpu.dataflow import Dataflow as _Dataflow

_wf = _Dataflow("warmup")
_ws = _op.input(
    "inp", _wf, TestingSource([("w", 1.0)] * 64, batch_size=32)
)
_op.output("out", _op.reduce_final("sum", _ws, _xla.SUM), TestingSink([]))
run_main(_wf)

rng = np.random.RandomState(3)
keys = [f"sensor_{{i:02d}}" for i in range(50)]
n = 32768
inp = list(
    zip(
        (keys[i] for i in rng.randint(0, 50, size=n)),
        rng.randn(n).tolist(),
    )
)
out = []
t0 = time.perf_counter()
run_main(
    anomaly_flow(TestingSource(inp, batch_size=16384), TestingSink(out))
)
print(json.dumps({{"cold_s": time.perf_counter() - t0}}))
"""


def _run_anomaly_cold_vs_warm():
    """Anomaly-flow cold start without vs with the persistent
    compilation cache, each in a fresh process so no in-process jit
    cache can leak in: the children get a private, initially empty
    cache directory through ``JAX_COMPILATION_CACHE_DIR``, so the
    first run is a true cold start (pays the recompile and populates
    the cache) and the second hits it.  Returns ``(cold_ms,
    warm_ms)``; a failing child raises."""
    import tempfile

    here = os.path.dirname(os.path.abspath(__file__))
    script = _ANOMALY_COLD_SCRIPT.format(repo=here)
    times = []
    with tempfile.TemporaryDirectory() as cache_dir:
        env = dict(
            os.environ,
            BYTEWAX_TPU_PLATFORM="cpu",
            JAX_PLATFORMS="cpu",
            JAX_COMPILATION_CACHE_DIR=cache_dir,
        )
        for _ in range(2):
            res = subprocess.run(
                [sys.executable, "-c", script],
                capture_output=True,
                timeout=300,
                env=env,
                check=True,
            )
            line = res.stdout.decode().strip().splitlines()[-1]
            times.append(json.loads(line)["cold_s"] * 1e3)
    return times[0], times[1]


# -- streaming inference (docs/inference.md) ---------------------------------


def _infer_bench_params(rng):
    import numpy as np

    return {
        "w1": rng.randn(4, 8).astype(np.float32),
        "b1": rng.randn(8).astype(np.float32),
        "w2": rng.randn(8).astype(np.float32),
        "b2": np.float32(0.1),
    }


def _infer_bench_apply(params, x):
    import jax.numpy as jnp

    h = jnp.tanh(x @ params["w1"] + params["b1"])
    return h @ params["w2"] + params["b2"]


def _run_infer_accel_vs_host(n_rows: int, n_keys: int = 32):
    """``op.infer`` batched device scoring vs the same model scored
    per-item on the host tier via ``op.map`` — the path a user would
    write without the inference subsystem.  The host-tier numpy
    oracle is asserted in-bench on the device outputs.  Returns
    ``(accel_events_per_sec, host_events_per_sec)``.
    """
    import numpy as np

    import bytewax_tpu.operators as op
    from bytewax_tpu.dataflow import Dataflow
    from bytewax_tpu.testing import TestingSink, TestingSource, run_main

    rng = np.random.RandomState(11)
    params = _infer_bench_params(rng)
    keys = [f"k{i:02d}" for i in range(n_keys)]
    feats = rng.randn(n_rows, 4).astype(np.float32)
    inp = [
        (keys[k], tuple(row))
        for k, row in zip(rng.randint(0, n_keys, size=n_rows), feats)
    ]
    batch_size = 8_192

    def build(tag, rows, accel):
        flow = Dataflow(f"infer_bench_{tag}")
        s = op.input(
            "inp", flow, TestingSource(inp[:rows], batch_size=batch_size)
        )
        if accel:
            s = op.infer("score", s, _infer_bench_apply, params)
        else:
            def scorer(kv):
                x = np.asarray(kv[1], dtype=np.float32)
                h = np.tanh(x @ params["w1"] + params["b1"])
                return kv[0], float(h @ params["w2"] + params["b2"])

            s = op.map("score", s, scorer)
        out = []
        op.output("out", s, TestingSink(out))
        return flow, out

    run_main(build("warm", 2 * batch_size, accel=True)[0])  # jit warm

    accel_rate = 0.0
    accel_out = []
    for _ in range(2):
        flow, out = build("accel", n_rows, accel=True)
        t0 = time.perf_counter()
        run_main(flow)
        dt = time.perf_counter() - t0
        assert len(out) == n_rows
        accel_rate = max(accel_rate, n_rows / dt)
        accel_out = out

    # In-bench oracle: the device scores must equal the vectorized
    # float32 numpy forward pass (order-free — routing interleaves).
    h = np.tanh(feats @ params["w1"] + params["b1"])
    want = np.sort(h @ params["w2"] + params["b2"])
    got = np.sort(np.asarray([v for _k, v in accel_out], dtype=np.float32))
    assert np.allclose(got, want, rtol=1e-4, atol=1e-5), (
        "op.infer diverged from the host oracle"
    )

    host_rows = min(n_rows, 64_000)
    flow, out = build("host", host_rows, accel=False)
    t0 = time.perf_counter()
    run_main(flow)
    host_rate = host_rows / (time.perf_counter() - t0)
    assert len(out) == host_rows
    return accel_rate, host_rate


def _run_infer_swap_gap(n_items: int = 300):
    """Live hot-swap latency: wall milliseconds from a mid-run
    ``update_params()`` request to the first emission scored by the
    new generation (the swap itself only commits at the next agreed
    epoch close — the gap is the user-visible staleness window).
    """
    import threading
    from datetime import timedelta

    import numpy as np

    import bytewax_tpu.operators as op
    from bytewax_tpu.dataflow import Dataflow
    from bytewax_tpu.engine import driver as engine_driver
    from bytewax_tpu.outputs import DynamicSink, StatelessSinkPartition
    from bytewax_tpu.testing import TestingSource, run_main

    inp = []
    for _ in range(n_items):
        inp.append(("k", 1.0))
        inp.append(TestingSource.PAUSE(timedelta(milliseconds=2)))

    rec = []

    class _TimedPart(StatelessSinkPartition):
        def write_batch(self, items):
            now = time.perf_counter()
            rec.extend((float(v), now) for _k, v in items)

    class _TimedSink(DynamicSink):
        def build(self, step_id, worker_index, worker_count):
            return _TimedPart()

    flow = Dataflow("infer_swap_gap_bench")
    s = op.input("inp", flow, TestingSource(inp, batch_size=1))
    s = op.infer(
        "score",
        s,
        lambda p, x: x[:, 0] * p["w"],
        {"w": np.float32(1.0)},
    )
    op.output("out", s, _TimedSink())

    swap_at = [None]

    def _swap_when_warm():
        while len(rec) < n_items // 4:
            time.sleep(0.001)
        swap_at[0] = time.perf_counter()
        engine_driver.update_params({"w": np.float32(3.0)})

    t = threading.Thread(target=_swap_when_warm, daemon=True)
    t.start()
    run_main(flow, epoch_interval=timedelta(0))
    t.join(timeout=5)

    assert len(rec) == n_items
    assert swap_at[0] is not None, "swap request never fired"
    post = [ts for v, ts in rec if v == 3.0]
    assert post, "no emission ever carried the swapped params"
    # Every item scores exactly once and the timeline splits once.
    values = [v for v, _ts in rec]
    assert values == sorted(values), "old-generation score after swap"
    return (min(post) - swap_at[0]) * 1e3


# -- isolated device step ----------------------------------------------------


def _device_step_ms(n_rows: int = 1 << 20, reps: int = 5):
    """Milliseconds per n_rows-row scatter-combine on the device
    (steady state, including the host->device transfer), plus the
    mesh-sharded all_to_all step time when >1 device is present."""
    import jax
    import numpy as np

    from bytewax_tpu.engine.xla import DeviceAggState

    rng = np.random.RandomState(0)
    slots = rng.randint(0, 413, size=n_rows).astype(np.int32)
    vals = rng.randn(n_rows).astype(np.float32)

    st = DeviceAggState("stats")
    for k in range(413):
        st.alloc(f"s{k:03d}")
    st.update_slots(slots[: 1 << 16], vals[: 1 << 16])  # warm small
    st.update_slots(slots, vals)  # warm the timed shape
    jax.block_until_ready(st._fields)
    t0 = time.perf_counter()
    for _ in range(reps):
        st.update_slots(slots, vals)
    jax.block_until_ready(st._fields)
    single_ms = (time.perf_counter() - t0) / reps * 1e3

    sharded_ms = None
    if len(jax.local_devices()) > 1:
        from bytewax_tpu.engine.sharded_state import ShardedAggState
        from bytewax_tpu.parallel.mesh import make_mesh

        sst = ShardedAggState("stats", make_mesh())
        kid_table = np.asarray(
            [sst.alloc(f"s{k:03d}") for k in range(413)], dtype=np.int32
        )
        kids = kid_table[slots]
        sst._dispatch(kids[: 1 << 16], vals[: 1 << 16])
        sst._dispatch(kids, vals)
        jax.block_until_ready(sst._fields)
        t0 = time.perf_counter()
        for _ in range(reps):
            sst._dispatch(kids, vals)
        jax.block_until_ready(sst._fields)
        sharded_ms = (time.perf_counter() - t0) / reps * 1e3
    return single_ms, sharded_ms


# -- supervised restart recovery latency -------------------------------------


def _run_restart_recovery():
    """Kill-to-first-epoch-close after resume, in seconds.

    A supervised single-process flow takes an injected crash at the
    snapshot-commit point (the torn-epoch window) mid-run; the
    supervisor restarts it from the last committed epoch.  Reported is
    the wall time from the crash to the first epoch close of the
    resumed execution — the end-to-end recovery latency a production
    fault would pay (driver teardown + resume math + state reload +
    first close), tracked round over round like ``epoch_close_p99``.
    """
    import tempfile
    from datetime import timedelta

    import bytewax_tpu.operators as op
    from bytewax_tpu import xla
    from bytewax_tpu.dataflow import Dataflow
    from bytewax_tpu.engine import faults, flight
    from bytewax_tpu.recovery import RecoveryConfig, init_db_dir
    from bytewax_tpu.testing import TestingSink, TestingSource, run_main

    env_keys = (
        "BYTEWAX_TPU_FAULTS",
        "BYTEWAX_TPU_MAX_RESTARTS",
        "BYTEWAX_TPU_RESTART_BACKOFF_S",
        "BYTEWAX_FLIGHT_RECORDER",
        "BYTEWAX_TPU_INGEST_TARGET_ROWS",
    )
    saved = {k: os.environ.get(k) for k in env_keys}
    os.environ["BYTEWAX_TPU_MAX_RESTARTS"] = "1"
    os.environ["BYTEWAX_TPU_RESTART_BACKOFF_S"] = "0"
    # The driver re-activates the ring from the env at run start; the
    # measurement needs the restart + epoch-close events.
    os.environ["BYTEWAX_FLIGHT_RECORDER"] = "1"
    # The crash spec below targets an *epoch*; ingest coalescing
    # compresses this trickle source into a couple of giant epochs,
    # which silently moved every crash point past the end of the run
    # (the probe's one-epoch-per-poll assumption predates the
    # batching knob).  Pin it off so the run really closes ~125
    # epochs and the crash lands mid-run.
    os.environ["BYTEWAX_TPU_INGEST_TARGET_ROWS"] = "0"
    main_rec = flight.RECORDER
    try:
        # The crash epoch still races the run's natural length: a
        # snapshot cadence change can leave fewer closes than the
        # target epoch, or land the crash after the final close so
        # the resumed execution closes nothing before EOF.  Either
        # way the ring simply lacks the event pair — retry at
        # earlier crash points instead of tracing back a
        # StopIteration as the probe error.
        last = "no restart/epoch_close event pair recorded"
        for crash_epoch in (40, 10, 2):
            os.environ["BYTEWAX_TPU_FAULTS"] = (
                f"snapshot.commit:crash:{crash_epoch}:x1"
            )
            # A private, larger ring so the whole run's event stream
            # (one epoch per loop at interval 0) survives for the
            # measurement and the main recorder's close-percentile
            # buffer stays untouched.
            flight.RECORDER = flight.FlightRecorder(1 << 15)
            flight.RECORDER.activate(True)
            faults.reset()
            with tempfile.TemporaryDirectory() as td:
                init_db_dir(td, 1)
                inp = [(f"k{i % 8}", float(i)) for i in range(2000)]
                out = []
                flow = Dataflow("restart_bench_df")
                s = op.input(
                    "inp", flow, TestingSource(inp, batch_size=16)
                )
                r = op.reduce_final("sum", s, xla.SUM)
                op.output("out", r, TestingSink(out))
                run_main(
                    flow,
                    epoch_interval=timedelta(0),
                    recovery_config=RecoveryConfig(td),
                )
            events = flight.RECORDER.tail(1 << 15)
            restart_t = next(
                (e["t"] for e in events if e["kind"] == "restart"),
                None,
            )
            if restart_t is None:
                last = (
                    f"no restart event at crash epoch {crash_epoch} "
                    "(crash point past the run's close count)"
                )
                continue
            first_close_t = next(
                (
                    e["t"]
                    for e in events
                    if e["kind"] == "epoch_close"
                    and e["t"] >= restart_t
                ),
                None,
            )
            if first_close_t is None:
                last = (
                    f"no epoch close after restart at crash epoch "
                    f"{crash_epoch} (crash landed after the final "
                    "close)"
                )
                continue
            return first_close_t - restart_t
        raise RuntimeError(f"restart probe: {last}")
    finally:
        flight.RECORDER = main_rec
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        faults.reset()


def _run_ckpt_async_vs_sync(
    n_rounds: int = 40,
    n_keys: int = 1024,
    batch_size: int = 8192,
    pad_bytes: int = 2048,
):
    """Epoch-close p99 with the synchronous whole-state checkpointer
    vs delta snapshots sealed at the close and committed on the
    committer lane (``BYTEWAX_TPU_CKPT_DELTA=1`` +
    ``BYTEWAX_TPU_CKPT_ASYNC=1``), same keyed flow, with output
    equality asserted in-bench.

    The flow is a saturating running-max over ``n_keys`` keys with a
    ``pad_bytes`` payload riding in each state: every key is touched
    every epoch (so the legacy close rewrites every row, every
    close), but after the first epoch the value never changes — the
    counters-that-saturate / watermark / dedup-set shape.  The delta
    digest filter drops the unchanged rows at the seal and the
    committer lane absorbs what little remains, so the measured gap
    is the snapshot write+commit the synchronous close pays per
    epoch.  Also reports the final ``snapshot_lag_epochs`` — the
    run-ending fence must have drained the lane, so a clean exit is
    always 0.  Python GC is parked for the probe (both modes) so the
    rate-limited close-time collection doesn't blur the percentile.
    """
    import tempfile
    from datetime import timedelta

    import bytewax_tpu.operators as op
    from bytewax_tpu.dataflow import Dataflow
    from bytewax_tpu.engine import flight
    from bytewax_tpu.recovery import RecoveryConfig, init_db_dir
    from bytewax_tpu.testing import TestingSink, TestingSource, run_main

    env_keys = (
        "BYTEWAX_TPU_CKPT_ASYNC",
        "BYTEWAX_TPU_CKPT_DELTA",
        "BYTEWAX_TPU_CKPT_COMPACT_EVERY",
        "BYTEWAX_TPU_GC",
    )
    saved = {k: os.environ.get(k) for k in env_keys}
    pad = "x" * pad_bytes
    # First touch of each key saturates the max; every later value
    # leaves the state byte-identical while still touching the key.
    inp = [
        (
            f"k{i % n_keys:05d}",
            1e9 if i < n_keys else float(i % 100),
        )
        for i in range(n_rounds * batch_size)
    ]

    def step(st, v):
        mx = max((st or (0.0, pad))[0], v)
        return (mx, pad), mx

    def one_mode(async_delta: bool):
        for k in env_keys:
            os.environ.pop(k, None)
        os.environ["BYTEWAX_TPU_GC"] = "off"
        if async_delta:
            os.environ["BYTEWAX_TPU_CKPT_ASYNC"] = "1"
            os.environ["BYTEWAX_TPU_CKPT_DELTA"] = "1"
        # A private recorder per mode: the close-percentile buffer is
        # the measurement, so neither mode may see the other's closes
        # (or the main recorder's).
        main_rec = flight.RECORDER
        flight.RECORDER = flight.FlightRecorder()
        try:
            with tempfile.TemporaryDirectory() as td:
                init_db_dir(td, 1)
                out = []
                flow = Dataflow("ckpt_bench_df")
                s = op.input(
                    "inp", flow, TestingSource(inp, batch_size=batch_size)
                )
                s = op.stateful_map("mx", s, step)
                op.output("out", s, TestingSink(out))
                run_main(
                    flow,
                    epoch_interval=timedelta(0),
                    recovery_config=RecoveryConfig(td),
                )
            pct = flight.RECORDER.epoch_close_percentiles()
            if pct is None:
                raise RuntimeError("no epoch closes recorded")
            lag = int(
                flight.RECORDER.counters.get("snapshot_lag_epochs", 0)
            )
            return pct[1], lag, sorted(out)
        finally:
            flight.RECORDER = main_rec
            for k, v in saved.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v

    sync_p99, _, sync_out = one_mode(False)
    async_p99, lag, async_out = one_mode(True)
    assert async_out == sync_out, "ckpt bench: async/sync outputs diverge"
    assert lag == 0, f"ckpt bench: clean exit left snapshot lag {lag}"
    return {
        "sync_p99_s": sync_p99,
        "async_p99_s": async_p99,
        "lag_epochs": lag,
    }


def _run_io_fault_soak(n_rows: int = 20000):
    """Throughput under a seeded transient-fault soak at the
    connector edge, with oracle equality asserted in-bench.

    A stateful keyed flow runs with deterministic transient faults
    fired through the REAL pinned ``source_poll``/``sink_write``
    sites (docs/recovery.md "Connector-edge resilience"); every
    fault must be absorbed by the in-place I/O retry ladder — ZERO
    supervised restarts — and the output must equal the fault-free
    host oracle.  Reported is events/sec of the faulted run: the
    throughput a flow keeps while its connector edge misbehaves.
    """
    from datetime import timedelta

    import bytewax_tpu.operators as op
    from bytewax_tpu.dataflow import Dataflow
    from bytewax_tpu.engine import faults, flight
    from bytewax_tpu.testing import TestingSink, TestingSource, run_main

    env_keys = (
        "BYTEWAX_TPU_FAULTS",
        "BYTEWAX_TPU_IO_RETRIES",
        "BYTEWAX_TPU_IO_BACKOFF_S",
        "BYTEWAX_TPU_MAX_RESTARTS",
    )
    saved = {k: os.environ.get(k) for k in env_keys}
    # Deterministic (seeded-by-spec) schedule: 6 source-poll and 4
    # sink-write transient errors spread over the run, each of which
    # the retry ladder must absorb without escalating.
    os.environ["BYTEWAX_TPU_FAULTS"] = (
        "source_poll:error:2+:x6,sink_write:error:3+:x4"
    )
    os.environ["BYTEWAX_TPU_IO_RETRIES"] = "8"
    os.environ["BYTEWAX_TPU_IO_BACKOFF_S"] = "0.002"
    os.environ["BYTEWAX_TPU_MAX_RESTARTS"] = "0"
    faults.reset()
    try:
        inp = [(f"k{i % 16}", float(i % 97)) for i in range(n_rows)]
        sums: dict = {}
        want = []
        for k, v in inp:
            sums[k] = sums.get(k, 0.0) + v
            want.append((k, sums[k]))

        out: list = []
        flow = Dataflow("io_soak_bench_df")
        s = op.input("inp", flow, TestingSource(inp, batch_size=64))
        s = op.stateful_map(
            "sum", s, lambda st, v: ((st or 0.0) + v, (st or 0.0) + v)
        )
        op.output("out", s, TestingSink(out))
        restarts_before = flight.RECORDER.counters.get(
            "worker_restart_count", 0
        )
        retries_before = flight.RECORDER.counters.get(
            "io_retries_count", 0
        )
        t0 = time.perf_counter()
        run_main(flow, epoch_interval=timedelta(0))
        dt = time.perf_counter() - t0
        # Keyed deliveries group per key within a batch, so compare
        # the multiset (every (key, running-sum) pair is unique).
        if sorted(out) != sorted(want):
            msg = (
                "io fault soak diverged from the fault-free oracle "
                f"({len(out)} rows vs {len(want)})"
            )
            raise AssertionError(msg)
        if (
            flight.RECORDER.counters.get("worker_restart_count", 0)
            != restarts_before
        ):
            msg = "io fault soak escalated to a supervised restart"
            raise AssertionError(msg)
        retries = (
            flight.RECORDER.counters.get("io_retries_count", 0)
            - retries_before
        )
        if retries < 10:
            msg = f"io fault soak only exercised {retries} retries"
            raise AssertionError(msg)
        return n_rows / dt
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        faults.reset()


_CLUSTER_SHUFFLE_CHILD = '''
import json
import os
import sys
import time

import numpy as np

import bytewax_tpu.operators as op
from bytewax_tpu import xla
from bytewax_tpu.dataflow import Dataflow
from bytewax_tpu.engine import flight
from bytewax_tpu.engine.arrays import ArrayBatch
from bytewax_tpu.engine.driver import cluster_main
from bytewax_tpu.inputs import FixedPartitionedSource, StatefulSourcePartition
from bytewax_tpu.testing import TestingSink

pid = int(sys.argv[1])
addrs = sys.argv[2].split(",")
warm_addrs = sys.argv[3].split(",")
n_parts = int(sys.argv[4])
polls = int(sys.argv[5])
batch_rows = int(sys.argv[6])
n_keys = int(sys.argv[7])
out_path = sys.argv[8]


def part_batches(idx, count):
    # Integer-valued floats: exact sums in any fold order, so the
    # parent can assert byte-identical oracle equality.
    rows = count * batch_rows
    rng = np.random.RandomState(100 + idx)
    keys = np.array(
        [f"k{k:04d}" for k in rng.randint(0, n_keys, size=rows)]
    )
    vals = rng.randint(0, 1000, size=rows).astype(np.float64)
    return [
        ArrayBatch(
            {
                "key": keys[i : i + batch_rows],
                "value": vals[i : i + batch_rows],
            }
        )
        for i in range(0, rows, batch_rows)
    ]


class Part(StatefulSourcePartition):
    """One trickle partition: a small record batch per poll — the
    Kafka-many-partitions shape whose tiny routed slices the route
    accumulator amortizes."""

    def __init__(self, idx, count):
        self._batches = part_batches(idx, count)

    def next_batch(self):
        if not self._batches:
            raise StopIteration()
        return self._batches.pop(0)

    def snapshot(self):
        return None  # no recovery store in the bench


class Src(FixedPartitionedSource):
    def __init__(self, count):
        self._count = count

    def list_parts(self):
        return [f"p{i:02d}" for i in range(n_parts)]

    def build_part(self, step_id, name, resume):
        return Part(int(name[1:]), self._count)


def flow_of(count, out):
    flow = Dataflow("cluster_shuffle_bench")
    s = op.input("inp", flow, Src(count))
    s = op.redistribute("redist", s)
    summed = op.reduce_final("sum", s, xla.SUM)
    op.output("out", summed, TestingSink(out))
    return flow


# Warmup run: compiles the fold shapes and forms/tears one mesh, so
# the timed window measures the steady-state shuffle.
cluster_main(flow_of(2, []), warm_addrs, pid)
base = dict(flight.RECORDER.counters)
out = []
t0 = time.perf_counter()
cluster_main(flow_of(polls, out), addrs, pid)
dt = time.perf_counter() - t0
c = flight.RECORDER.counters
wire = {
    k: c.get(k, 0) - base.get(k, 0)
    for k in (
        "wire_encode_bytes_columnar",
        "wire_encode_bytes_pickle",
        "wire_encode_frames_columnar",
        "wire_encode_frames_pickle",
        "wire_encode_seconds_columnar",
        "wire_encode_seconds_pickle",
        "wire_decode_seconds_columnar",
        "wire_decode_seconds_pickle",
        "comm_bytes_tx",
        "comm_frames_tx",
        "xla_compile_count",
        "xla_compile_seconds",
    )
}
with open(out_path, "w") as f:
    json.dump(
        {
            "proc": pid,
            "dt": dt,
            "wire": wire,
            "out": [[k, float(v)] for k, v in out],
        },
        f,
    )
'''


def _run_cluster_columnar_shuffle():
    """2-proc keyed columnar shuffle over the cluster wire
    (docs/performance.md "Columnar exchange"), once per wire mode.

    Two real processes form a TCP mesh; 16 trickle partitions emit
    small ``{key, value}`` record batches per poll (the Kafka-many-
    partitions shape), a redistribute re-balances them across the
    cluster, and the keyed device reduce ships every row to its home
    lane — columnar splits end to end.  On the columnar wire the
    per-poll routed slices coalesce in the route accumulator and ship
    as merged zero-copy frames; ``BYTEWAX_TPU_WIRE=pickle`` is the
    legacy wire (whole-frame pickle, one frame per slice) on the SAME
    flow.  The merged output is asserted byte-identical to a host
    numpy oracle (integer-valued floats, so fold order cannot perturb
    it).

    Returns ``{mode: {"events_per_sec", "wire_bytes_per_event",
    "wire_frames"}}``.
    """
    import socket
    import tempfile

    import numpy as np

    n_rows = int(os.environ.get("BENCH_CLUSTER_ROWS", 262_144))
    n_parts = 32
    batch_rows = 128
    n_keys = 512
    polls = max(1, n_rows // (n_parts * batch_rows))
    n_rows = n_parts * polls * batch_rows  # cluster total, exact

    def free_port():
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
        s.close()
        return port

    # Host oracle (the exact arrays each child partition generates).
    sums = {}
    for idx in range(n_parts):
        rng = np.random.RandomState(100 + idx)
        rows = polls * batch_rows
        ids = rng.randint(0, n_keys, size=rows)
        vals = rng.randint(0, 1000, size=rows).astype(np.float64)
        binned = np.bincount(ids, weights=vals, minlength=n_keys)
        seen = np.bincount(ids, minlength=n_keys) > 0
        for k in np.nonzero(seen)[0]:
            key = f"k{int(k):04d}"
            sums[key] = sums.get(key, 0.0) + float(binned[k])

    results = {}
    with tempfile.TemporaryDirectory() as td:
        child_py = os.path.join(td, "shuffle_child.py")
        with open(child_py, "w") as f:
            f.write(_CLUSTER_SHUFFLE_CHILD)
        def one_run(mode, rep_i):
            addrs = ",".join(
                f"127.0.0.1:{free_port()}" for _ in range(2)
            )
            warm = ",".join(
                f"127.0.0.1:{free_port()}" for _ in range(2)
            )
            env = dict(os.environ)
            env["PYTHONPATH"] = (
                os.path.dirname(os.path.abspath(__file__))
                + os.pathsep
                + env.get("PYTHONPATH", "")
            )
            env["BYTEWAX_TPU_PLATFORM"] = "cpu"
            env["BYTEWAX_TPU_WIRE"] = mode
            # A true trickle: the routed slices stay poll-sized (the
            # ingest coalescer would re-batch them before routing and
            # measure itself instead of the wire).
            env["BYTEWAX_TPU_INGEST_TARGET_ROWS"] = "0"
            env.pop("BYTEWAX_TPU_FAULTS", None)
            procs = [
                subprocess.Popen(
                    [
                        sys.executable,
                        child_py,
                        str(pid),
                        addrs,
                        warm,
                        str(n_parts),
                        str(polls),
                        str(batch_rows),
                        str(n_keys),
                        os.path.join(td, f"{mode}_{rep_i}_{pid}.json"),
                    ],
                    env=env,
                    stdout=subprocess.PIPE,
                    stderr=subprocess.PIPE,
                )
                for pid in (0, 1)
            ]
            for p in procs:
                try:
                    _out, err = p.communicate(timeout=600)
                except subprocess.TimeoutExpired:
                    for q in procs:
                        q.kill()
                    msg = f"{mode} shuffle bench timed out"
                    raise RuntimeError(msg) from None
                if p.returncode != 0:
                    msg = (
                        f"{mode} shuffle child failed: "
                        f"{err.decode()[-2000:]}"
                    )
                    raise RuntimeError(msg)
            reports = []
            for pid in (0, 1):
                with open(
                    os.path.join(td, f"{mode}_{rep_i}_{pid}.json")
                ) as f:
                    reports.append(json.load(f))
            merged = {}
            for rep in reports:
                for k, v in rep["out"]:
                    if k in merged:
                        msg = f"key {k} emitted on both processes"
                        raise AssertionError(msg)
                    merged[k] = v
            if merged != sums:
                msg = (
                    f"{mode} shuffle output diverged from the host "
                    f"oracle ({len(merged)} keys vs {len(sums)})"
                )
                raise AssertionError(msg)
            dt = max(rep["dt"] for rep in reports)
            wire_bytes = sum(
                rep["wire"]["wire_encode_bytes_columnar"]
                + rep["wire"]["wire_encode_bytes_pickle"]
                for rep in reports
            )
            wire_frames = sum(
                rep["wire"]["wire_encode_frames_columnar"]
                + rep["wire"]["wire_encode_frames_pickle"]
                for rep in reports
            )
            return {
                "events_per_sec": n_rows / dt,
                "wire_bytes_per_event": wire_bytes / n_rows,
                "wire_frames": wire_frames,
            }

        # The host-oracle assertion runs on EVERY rep; best-of-2 for
        # the rate (bench convention — the box is shared and bursty).
        for mode in ("columnar", "pickle"):
            reps = [one_run(mode, i) for i in range(2)]
            results[mode] = max(
                reps, key=lambda r: r["events_per_sec"]
            )
    return results


_COLLECTIVE_OVERLAP_CHILD = '''
import json
import os
import sys
import time

import numpy as np

import bytewax_tpu.operators as op
from bytewax_tpu import xla
from bytewax_tpu.dataflow import Dataflow
from bytewax_tpu.engine.arrays import ArrayBatch
from bytewax_tpu.engine.driver import cluster_main
from bytewax_tpu.inputs import DynamicSource, StatelessSourcePartition
from bytewax_tpu.testing import TestingSink

pid = int(sys.argv[1])
addrs = sys.argv[2].split(",")
warm_addrs = sys.argv[3].split(",")
polls = int(sys.argv[4])
rows_per_poll = int(sys.argv[5])
n_keys = int(sys.argv[6])
pace_s = float(sys.argv[7])
out_path = sys.argv[8]

from datetime import timedelta


def part_batches(worker_index, count):
    """Pre-built columnar batches with small integer-valued floats:
    per-key sums stay exact in the f32 accumulator, so the parent
    asserts byte-identical oracle equality in any fold order."""
    base = worker_index * 13
    rows = count * rows_per_poll
    idx = np.arange(rows)
    keys = np.array([f"k{r % n_keys:05d}" for r in idx])
    vals = ((base + idx) % 997).astype(np.float64)
    return [
        ArrayBatch(
            {
                "key": keys[i : i + rows_per_poll],
                "value": vals[i : i + rows_per_poll],
            }
        )
        for i in range(0, rows, rows_per_poll)
    ]


class _Part(StatelessSourcePartition):
    """A paced (arrival-limited) source — the realistic streaming
    shape: batches land every ``pace_s`` with idle gaps between
    them.  The lock-step tier burns those gaps blocked in the
    epoch-close collective; the overlapped tier runs the collective
    INSIDE them."""

    def __init__(self, worker_index, count, paced):
        self._batches = part_batches(worker_index, count)
        self._pace = pace_s if paced else 0.0

    def next_batch(self):
        if not self._batches:
            raise StopIteration()
        if self._pace:
            time.sleep(self._pace)
        return self._batches.pop(0)


class Src(DynamicSource):
    def __init__(self, count, paced=True):
        self._count = count
        self._paced = paced

    def build(self, step_id, worker_index, worker_count):
        return _Part(worker_index, self._count, self._paced)


def flow_of(src, out):
    flow = Dataflow("collective_overlap_bench")
    s = op.input("inp", flow, src)
    summed = op.reduce_final("sum", s, xla.SUM)
    op.output("out", summed, TestingSink(out))
    return flow


# Warmup: compiles the exchange shapes and forms/tears one mesh, so
# the timed window measures the steady-state overlap (not compiles).
cluster_main(
    flow_of(Src(2, paced=False), []), warm_addrs, pid,
    epoch_interval=timedelta(seconds=0.1),
)
out = []
t0 = time.perf_counter()
cluster_main(
    flow_of(Src(polls), out), addrs, pid,
    epoch_interval=timedelta(seconds=0.3),
)
dt = time.perf_counter() - t0
with open(out_path, "w") as f:
    json.dump({"dt": dt, "out": out}, f)
'''


def _run_collective_overlap():
    """2-proc global-mesh keyed aggregation (BYTEWAX_TPU_DISTRIBUTED
    + GlobalAggState), overlapped vs lock-step collective tier
    (docs/performance.md "Overlapped collectives").

    Each process ingests a PACED columnar stream (batches arrive
    every ``pace_s`` — the arrival-limited deployment shape) while
    every epoch close flushes the buffered rows through the
    collective exchange.  Lock-step, the close blocks the run loop
    for the whole exchange, so every epoch pays ``arrivals +
    collective``; with ``BYTEWAX_TPU_GSYNC_OVERLAP=1`` epoch N's
    exchange runs on the collective lane inside epoch N+1's arrival
    gaps, so the steady state pays ``max(arrivals, collective)`` —
    a mechanism that holds even on a single-core box (the lane's
    exchange runs while the paced source sleeps).  The overlap leg
    runs the multi-epoch ladder at ``BYTEWAX_TPU_GSYNC_DEPTH=2``:
    two sealed rounds in flight, so one slow round borrows the next
    epoch's gap instead of stalling the close.  The merged output
    is asserted equal to the host oracle on EVERY rep
    (integer-valued floats: exact in any fold order).

    Returns ``{mode: events_per_sec}`` for ``lockstep``/``overlap``.
    """
    import socket
    import tempfile

    import numpy as np

    # The shape must stay ARRIVAL-LIMITED for the mechanism to be
    # measurable: each epoch's pacing sleeps (the window the lane's
    # exchange hides in) must be comparable to one exchange round's
    # cost (~0.3s on this box — fixed rendezvous+dispatch dominated,
    # nearly row-count independent at these sizes).  The pre-ladder
    # shape (64k rows/poll at 0.05s pace, 0.1s epochs) had grown
    # compute-saturated: the gaps were fully consumed and the bench
    # measured single-core GIL contention, not overlap.
    polls = int(os.environ.get("BENCH_COLLECTIVE_POLLS", 16))
    rows_per_poll = int(
        os.environ.get("BENCH_COLLECTIVE_ROWS_PER_POLL", 8000)
    )
    pace_s = float(os.environ.get("BENCH_COLLECTIVE_PACE_S", 0.15))
    n_keys = 1024
    n_rows = 2 * polls * rows_per_poll

    def free_port():
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
        s.close()
        return port

    # Host oracle: per-key sums over both processes' rows (exactly
    # the arrays the children pre-build).
    sums = {}
    total = polls * rows_per_poll
    idx = np.arange(total)
    key_ids = idx % n_keys
    for wi in (0, 1):
        vals = ((wi * 13 + idx) % 997).astype(np.float64)
        binned = np.bincount(key_ids, weights=vals, minlength=n_keys)
        for k in range(n_keys):
            key = f"k{k:05d}"
            sums[key] = sums.get(key, 0.0) + float(binned[k])

    results = {}
    with tempfile.TemporaryDirectory() as td:
        child_py = os.path.join(td, "overlap_child.py")
        with open(child_py, "w") as f:
            f.write(_COLLECTIVE_OVERLAP_CHILD)

        def one_run(mode, rep_i):
            addrs = ",".join(
                f"127.0.0.1:{free_port()}" for _ in range(2)
            )
            warm = ",".join(
                f"127.0.0.1:{free_port()}" for _ in range(2)
            )
            env = dict(os.environ)
            env["PYTHONPATH"] = (
                os.path.dirname(os.path.abspath(__file__))
                + os.pathsep
                + env.get("PYTHONPATH", "")
            )
            env["BYTEWAX_TPU_PLATFORM"] = "cpu"
            env["BYTEWAX_TPU_ACCEL"] = "1"
            env["BYTEWAX_TPU_DISTRIBUTED"] = "1"
            env["BYTEWAX_TPU_GLOBAL_EXCHANGE"] = "1"
            env["BYTEWAX_TPU_GSYNC_OVERLAP"] = (
                "1" if mode == "overlap" else "0"
            )
            # The overlap leg runs at depth 2 (the multi-epoch fence
            # ladder, docs/performance.md "The overlap ladder") so the
            # bench measures the shipped steady state: two sealed
            # rounds in flight, retired in order.  Ignored under
            # lock-step (overlap off never enters the lane).
            env["BYTEWAX_TPU_GSYNC_DEPTH"] = "2"
            # Batch-granular ingest: the coalescer would swallow the
            # whole source in one poll and collapse the run into one
            # EOF flush — the bench needs per-epoch rounds.
            env["BYTEWAX_TPU_INGEST_TARGET_ROWS"] = "0"
            env.pop("BYTEWAX_TPU_FAULTS", None)
            procs = [
                subprocess.Popen(
                    [
                        sys.executable,
                        child_py,
                        str(pid),
                        addrs,
                        warm,
                        str(polls),
                        str(rows_per_poll),
                        str(n_keys),
                        str(pace_s),
                        os.path.join(td, f"{mode}_{rep_i}_{pid}.json"),
                    ],
                    env=env,
                    stdout=subprocess.PIPE,
                    stderr=subprocess.PIPE,
                )
                for pid in (0, 1)
            ]
            for p in procs:
                try:
                    _out, err = p.communicate(timeout=600)
                except subprocess.TimeoutExpired:
                    for q in procs:
                        q.kill()
                    msg = f"{mode} collective bench timed out"
                    raise RuntimeError(msg) from None
                if p.returncode != 0:
                    msg = (
                        f"{mode} collective child failed "
                        f"(rc {p.returncode}): {err.decode()[-2000:]}"
                    )
                    raise RuntimeError(msg)
            reports = []
            for pid in (0, 1):
                with open(
                    os.path.join(td, f"{mode}_{rep_i}_{pid}.json")
                ) as f:
                    reports.append(json.load(f))
            merged = {}
            for rep in reports:
                for k, v in rep["out"]:
                    if k in merged:
                        msg = f"key {k} emitted on both processes"
                        raise AssertionError(msg)
                    merged[k] = v
            if merged != sums:
                bad = sum(
                    1 for k in sums if merged.get(k) != sums[k]
                )
                msg = (
                    f"{mode} collective output diverged from the "
                    f"host oracle ({bad} of {len(sums)} keys differ)"
                )
                raise AssertionError(msg)
            return n_rows / max(rep["dt"] for rep in reports)

        # Oracle asserted on every rep; best-of-N for the rate (the
        # overlap leg gets one more rep: its steady state rides the
        # lane's thread schedule, noisier on a loaded 1-core box).
        for mode, n_reps in (("lockstep", 2), ("overlap", 3)):
            results[mode] = max(
                one_run(mode, i) for i in range(n_reps)
            )
    return results


def _run_gsync_bytes_per_round():
    """Bytes one gsync aggregate-exchange round puts on the wire,
    quantized vs exact (docs/performance.md "Overlapped
    collectives"): the stats-shape partial columns (key + min/max/sum
    float64 + count int64) for a representative key cardinality,
    framed by ``engine/wire.py``'s aggregate codec under each
    ``BYTEWAX_TPU_GSYNC_QUANT`` mode.  Counts are asserted byte-exact
    through the int8/bf16 round trips in-bench.

    Returns ``{mode: bytes}`` plus the int8/exact ratio.
    """
    import numpy as np

    from bytewax_tpu.engine import wire

    n_keys = int(os.environ.get("BENCH_GSYNC_KEYS", 65536))
    rng = np.random.RandomState(1711)
    cols = {
        "key": np.array([f"user-{i:08d}" for i in range(n_keys)]),
        "min": rng.randn(n_keys) * 100.0,
        "max": rng.randn(n_keys) * 100.0 + 500.0,
        "sum": rng.randn(n_keys) * 1e4,
        "count": rng.randint(1, 100_000, size=n_keys).astype(
            np.int64
        ),
    }
    out = {}
    for mode in ("off", "bf16", "int8"):
        frames = wire.encode_agg(cols, mode)
        out[mode] = sum(len(f) for f in frames)
        dec = {}
        for frame in frames:
            for name, arr in wire.decode_agg(frame).items():
                dec.setdefault(name, []).append(arr)
        count = np.concatenate(dec["count"])
        if not np.array_equal(count, cols["count"]):
            msg = f"count column not exact under {mode}"
            raise AssertionError(msg)
        keys = np.concatenate(dec["key"])
        if not np.array_equal(keys, cols["key"]):
            msg = f"key column not exact under {mode}"
            raise AssertionError(msg)
    return out


def _run_gsync_d2h_bytes_per_round():
    """Host↔device bytes one merged exchange round moves, device
    merge vs the host fold (docs/performance.md "Device-side
    dequant+merge"): the REAL seal/apply path —
    ``wire.encode_agg`` → ``GlobalAggState._seal_merge`` →
    ``_apply_merge`` — driven standalone over a stats-shape
    two-peer round, reading the flight counters the engine itself
    bumps (``gsync_merge_h2d_bytes`` / ``gsync_merge_host_bytes`` /
    ``gsync_fetch_d2h_bytes``).  The host fold materializes every
    round's dequantized f64 partials host-side; the device merge
    uploads the wire-width parts (int8 ≈ 1 byte/value + block
    scales) and pays d2h ONCE at the final fetch.  The device
    tables are asserted against the host-fold oracle in-bench
    (counts byte-exact; float fields to f32-accumulation
    tolerance).

    Returns per-round bytes ``{host_fold, off, bf16, int8}`` plus
    the one-time ``fetch_d2h`` of the int8 run.
    """
    import numpy as np

    from bytewax_tpu.engine import flight, sharded_state, wire
    from bytewax_tpu.ops.segment import AGG_KINDS

    n_keys = int(os.environ.get("BENCH_GSYNC_MERGE_KEYS", 8192))
    rounds = 8
    cap = 1
    while cap < n_keys + 1:  # +1: the exchange-scratch slot
        cap *= 2
    keys = np.array([f"k{i:05d}" for i in range(n_keys)])

    def round_cols(peer, rnd):
        rng = np.random.RandomState(7919 + 31 * peer + rnd)
        return {
            "key": keys,
            "min": rng.randn(n_keys) * 100.0,
            "max": rng.randn(n_keys) * 100.0 + 500.0,
            "sum": rng.randn(n_keys) * 1e4,
            "count": rng.randint(1, 100_000, size=n_keys).astype(
                np.int64
            ),
        }

    def one_path(mode, demoted):
        st = sharded_state.GlobalAggState.__new__(
            sharded_state.GlobalAggState
        )
        st.kind = AGG_KINDS["stats"]
        st.n_shards = 1
        st.cap_per_shard = cap
        st.key_to_kid = {k: i for i, k in enumerate(keys.tolist())}
        st._merge_demoted = demoted
        st._quant_int = False
        st._dev_fields = None
        st._host_fields = None
        names = (
            "gsync_merge_h2d_bytes",
            "gsync_merge_host_bytes",
            "gsync_fetch_d2h_bytes",
        )
        base = {
            n: flight.RECORDER.counters.get(n, 0) for n in names
        }
        for rnd in range(rounds):
            sealed = st._seal_merge(
                [
                    wire.encode_agg(round_cols(peer, rnd), mode)
                    for peer in (0, 1)
                ]
            )
            st._apply_merge(sealed)
        tables = (
            st._host_fields if demoted else st._fetch_dev_fields()
        )
        deltas = {
            n: flight.RECORDER.counters.get(n, 0) - base[n]
            for n in names
        }
        return tables, deltas

    # Host-fold oracle (the BYTEWAX_TPU_WIRE=pickle-era path) over
    # the exact wire — also the per-round host-bytes baseline.
    oracle, host_d = one_path("off", demoted=True)
    out = {
        "host_fold": round(
            host_d["gsync_merge_host_bytes"] / rounds
        )
    }
    for mode in ("off", "bf16", "int8"):
        tables, dev_d = one_path(mode, demoted=False)
        if not np.array_equal(
            tables["count"][:n_keys], oracle["count"][:n_keys]
        ):
            msg = f"device count diverged from host fold ({mode})"
            raise AssertionError(msg)
        if mode == "off":
            for name in ("min", "max", "sum"):
                # atol: f32 wire width + f32 scatter-adds over
                # zero-mean values — near-zero sums have unbounded
                # RELATIVE error but tiny absolute error.
                if not np.allclose(
                    tables[name][:n_keys],
                    oracle[name][:n_keys],
                    rtol=1e-4,
                    atol=1.0,
                ):
                    msg = f"device {name} diverged from host fold"
                    raise AssertionError(msg)
        out[mode] = round(dev_d["gsync_merge_h2d_bytes"] / rounds)
        if mode == "int8":
            out["fetch_d2h"] = dev_d["gsync_fetch_d2h_bytes"]
    return out


def _run_rescale_resume():
    """Stop-at-N → first-epoch-close-at-M wall time, in seconds.

    An in-process 2-lane cluster runs a keyed flow (5k keys through
    the device scan tier) to a mid-stream EOF, populating the
    recovery store; the relaunch at 3 lanes with
    ``BYTEWAX_TPU_RESCALE=1`` then pays driver build + resume math +
    the startup rescale migration (route rewrite over every keyed
    row) + state reload + the first epoch close — the end-to-end
    pause an operator pays to resize a running flow, the rescale
    sibling of ``restart_recovery_s``.
    """
    import tempfile
    from datetime import timedelta

    import bytewax_tpu.operators as op
    from bytewax_tpu import xla
    from bytewax_tpu.dataflow import Dataflow
    from bytewax_tpu.engine import flight
    from bytewax_tpu.engine.driver import cluster_main
    from bytewax_tpu.recovery import RecoveryConfig, init_db_dir
    from bytewax_tpu.testing import TestingSink, TestingSource

    n_keys = 5000
    env_keys = ("BYTEWAX_TPU_RESCALE", "BYTEWAX_FLIGHT_RECORDER")
    saved = {k: os.environ.get(k) for k in env_keys}
    os.environ["BYTEWAX_FLIGHT_RECORDER"] = "1"
    main_rec = flight.RECORDER
    flight.RECORDER = flight.FlightRecorder(1 << 15)
    flight.RECORDER.activate(True)

    def flow_of(items, out):
        flow = Dataflow("rescale_bench_df")
        s = op.input(
            "inp", flow, TestingSource(items, batch_size=256)
        )
        scored = op.stateful_map("ema", s, xla.ema(0.3))
        op.output("out", scored, TestingSink(out))
        return flow

    try:
        with tempfile.TemporaryDirectory() as td:
            init_db_dir(td, 2)
            inp = [
                (f"k{i % n_keys:05d}", float(i % 97))
                for i in range(2 * n_keys)
            ]
            half = len(inp) // 2
            items = inp[:half] + [TestingSource.EOF()] + inp[half:]
            cluster_main(
                flow_of(items, []),
                [],
                0,
                worker_count_per_proc=2,
                epoch_interval=timedelta(0),
                recovery_config=RecoveryConfig(td),
            )
            os.environ["BYTEWAX_TPU_RESCALE"] = "1"
            t0 = time.time()
            cluster_main(
                flow_of(items, []),
                [],
                0,
                worker_count_per_proc=3,
                epoch_interval=timedelta(0),
                recovery_config=RecoveryConfig(td),
            )
        events = flight.RECORDER.tail(1 << 15)
        if not any(e["kind"] == "rescale" for e in events):
            msg = "rescale migration did not run"
            raise RuntimeError(msg)
        first_close_t = next(
            e["t"]
            for e in events
            if e["kind"] == "epoch_close" and e["t"] >= t0
        )
        return first_close_t - t0
    finally:
        flight.RECORDER = main_rec
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def _run_graceful_stop():
    """Stop-request-to-clean-exit wall time, in seconds.

    A single-process keyed flow with a recovery store takes a
    cooperative stop request mid-stream (the in-process equivalent of
    SIGTERM / ``POST /stop``; docs/recovery.md "Graceful
    drain-to-stop"): the run loop drains to the next epoch close —
    pipelines flushed, snapshots committed — and returns a typed
    ``GracefulStop``.  Reported is request → ``run_main`` returning:
    the whole drain + teardown.  Compare ``restart_recovery_s`` (the
    crash path on the same flow shape): the graceful path commits
    instead of replaying, so a stop-and-relaunch cycle pays no
    recovery at all.
    """
    import tempfile
    from datetime import timedelta

    import bytewax_tpu.operators as op
    from bytewax_tpu import xla
    from bytewax_tpu.dataflow import Dataflow
    from bytewax_tpu.engine import driver as _driver
    from bytewax_tpu.recovery import RecoveryConfig, init_db_dir
    from bytewax_tpu.testing import TestingSink, TestingSource, run_main

    t_req = [None]

    def trig(kv):
        if t_req[0] is None and kv[1] == 1500.0:
            t_req[0] = time.perf_counter()
            _driver.request_stop()
        return kv

    with tempfile.TemporaryDirectory() as td:
        init_db_dir(td, 1)
        inp = [(f"k{i % 8}", float(i)) for i in range(20000)]
        out = []
        flow = Dataflow("graceful_stop_bench_df")
        s = op.input("inp", flow, TestingSource(inp, batch_size=16))
        s = op.map("trig", s, trig)
        r = op.reduce_final("sum", s, xla.SUM)
        op.output("out", r, TestingSink(out))
        status = run_main(
            flow,
            epoch_interval=timedelta(0),
            recovery_config=RecoveryConfig(td),
        )
        dt = (
            time.perf_counter() - t_req[0]
            if t_req[0] is not None
            else None
        )
    if status is None or dt is None:
        msg = "graceful stop did not trigger"
        raise RuntimeError(msg)
    return dt


_AUTOSCALE_FLOW = '''
import os
from datetime import datetime, timedelta, timezone

import bytewax_tpu.operators as op
from bytewax_tpu.dataflow import Dataflow
from bytewax_tpu.connectors.files import FileSink
from bytewax_tpu.inputs import FixedPartitionedSource, StatefulSourcePartition

CAP = int(os.environ["BENCH_AUTOSCALE_CAP"])
KEYS = int(os.environ["BENCH_AUTOSCALE_KEYS"])
DELAY_MS = float(os.environ["BENCH_AUTOSCALE_DELAY_MS"])
BATCH = int(os.environ["BENCH_AUTOSCALE_BATCH"])


class _Part(StatefulSourcePartition):
    def __init__(self, name, resume):
        self._name = name
        self._i = resume or 0
        self._awake = None

    def next_batch(self):
        if self._i >= CAP:
            raise StopIteration()
        out = []
        for _ in range(BATCH):
            if self._i >= CAP:
                break
            self._i += 1
            out.append(
                (
                    f"{{self._name}}-k{{self._i % KEYS:04d}}",
                    float(self._i % 97),
                )
            )
        self._awake = datetime.now(timezone.utc) + timedelta(
            milliseconds=DELAY_MS
        )
        return out

    def next_awake(self):
        return self._awake

    def snapshot(self):
        return self._i


class Source(FixedPartitionedSource):
    def list_parts(self):
        return ["p0", "p1"]

    def build_part(self, step_id, name, resume):
        return _Part(name, resume)


flow = Dataflow("autoscale_live_df")
s = op.input("inp", flow, Source())
s = op.stateful_map("ema", s, lambda st, v: (
    (v if st is None else st + 0.3 * (v - st),) * 2
))
s = op.map("fmt", s, lambda kv: (kv[0], f"{{kv[0]}}={{kv[1]:.3f}}"))
op.output("out", s, FileSink({out_path!r}))
'''


def _autoscale_oracle(cap, keys):
    want = []
    for part in ("p0", "p1"):
        emas = {}
        for i in range(1, cap + 1):
            key = f"{part}-k{i % keys:04d}"
            v = float(i % 97)
            prev = emas.get(key)
            emas[key] = v if prev is None else prev + 0.3 * (v - prev)
            want.append(f"{key}={emas[key]:.3f}")
    return sorted(want)


def _run_autoscale_move(p_from, p_to, live):
    """Service interruption of ONE autoscale move on a REAL
    multi-process supervised cluster, in seconds: the longest gap
    between observed epoch advances on process 0's status plane
    across the move window.

    ``live=True`` measures the live partial rescale (the default
    path, docs/recovery.md "Live partial rescale"): the joiner boots
    while the cluster keeps serving, the membership change rides an
    epoch close, survivors re-enter run startup in-process, and only
    changed-route keys migrate.  ``live=False`` forces the legacy
    whole-cluster drain-to-stop + relaunch (the PR-11 baseline),
    measured with the identical methodology — the interruption then
    spans the drain, full process teardown/boot, and the full-store
    migration.

    Returns ``(interruption_s, info)`` where info carries the
    completed run's oracle check inputs and — for a live grow — the
    delta-migration proof: ``migrated_keys`` (scraped from the
    surviving coordinator's /metrics counter) and
    ``expected_moved_keys`` (recomputed from the recovery store's
    distinct keys under the old→new moduli; the two must be EQUAL or
    the "live move migrates only changed-route keys" claim fails).
    The run always finishes to EOF and the FileSink output must
    equal the host oracle exactly-once — in both directions.

    Host-tier flow (``BYTEWAX_TPU_ACCEL=0``) on purpose: the metric
    isolates the move machinery (drain/boot/handshake/migration)
    from XLA compile times, which hit both paths identically and
    drown the signal on CPU.
    """
    import sqlite3
    import tempfile
    import threading
    import urllib.request
    from pathlib import Path

    from bytewax_tpu.engine.recovery_store import route_of
    from bytewax_tpu.recovery import init_db_dir
    from bytewax_tpu.supervise import ClusterSupervisor, _get_status

    # Stream pacing: the flow must outlive child boot (~5s of
    # python+jax import per process on this box) plus the move in
    # BOTH paths — the restart path boots three fresh children
    # mid-stream.  1ms/16-item polls ≈ 16k items/s nominal.
    cap = 20_000
    keys = 500
    delay_ms = 1.0
    batch = 16
    advice = "grow" if p_to > p_from else "shrink"
    knobs = {
        "BYTEWAX_TPU_AUTOSCALE_LIVE": "1" if live else "0",
        "BYTEWAX_TPU_AUTOSCALE_POLL_S": "0.2",
        "BYTEWAX_TPU_AUTOSCALE_HYSTERESIS": "1",
        "BYTEWAX_TPU_AUTOSCALE_COOLDOWN_S": "0",
        "BYTEWAX_TPU_AUTOSCALE_STOP_TIMEOUT_S": "60",
    }
    saved = {k: os.environ.get(k) for k in knobs}
    os.environ.update(knobs)
    try:
        with tempfile.TemporaryDirectory() as td:
            td = Path(td)
            out_path = td / "out.txt"
            flow_py = td / "autoscale_flow.py"
            flow_py.write_text(
                _AUTOSCALE_FLOW.format(out_path=str(out_path))
            )
            db = td / "db"
            db.mkdir()
            init_db_dir(db, 2)
            child_env = {
                # Children run with cwd=tmpdir; the package root must
                # stay importable.
                "PYTHONPATH": os.path.dirname(
                    os.path.abspath(__file__)
                )
                + os.pathsep
                + os.environ.get("PYTHONPATH", ""),
                "BYTEWAX_TPU_PLATFORM": "cpu",
                "BYTEWAX_TPU_ACCEL": "0",
                "BENCH_AUTOSCALE_CAP": str(cap),
                "BENCH_AUTOSCALE_KEYS": str(keys),
                "BENCH_AUTOSCALE_DELAY_MS": str(delay_ms),
                "BENCH_AUTOSCALE_BATCH": str(batch),
            }
            state = {"t_decide": None}

            def hint():
                # Hold until warm: EACH partition has cycled through
                # its whole key set (so every distinct key is in the
                # store — committed long before the migration, which
                # lands seconds later behind the joiner boot — and
                # the delta computation is stable), then confirm the
                # move.
                if state["t_decide"] is None:
                    try:
                        txt = out_path.read_text()
                    except OSError:
                        return "hold"
                    if (
                        txt.count("p0-") < keys
                        or txt.count("p1-") < keys
                    ):
                        return "hold"
                    state["t_decide"] = time.monotonic()
                return advice

            sup = ClusterSupervisor(
                f"{flow_py}:flow",
                min_procs=min(p_from, p_to),
                max_procs=max(p_from, p_to),
                procs=p_from,
                recovery_dir=str(db),
                snapshot_interval_s=0.05,
                backup_interval_s=0.05,
                env=child_env,
                hint_fn=hint,
                log_dir=str(td / "logs"),
                workdir=str(td),
            )
            advances = []
            stop_sampling = threading.Event()

            def sample():
                last = None
                while not stop_sampling.is_set():
                    st = _get_status(sup.api_base_port or 0)
                    now = time.monotonic()
                    if st is not None:
                        ep = st.get("epoch")
                        if ep is not None and ep != last:
                            last = ep
                            advances.append(now)
                    time.sleep(0.015)

            info = {}
            with sup:
                runner = threading.Thread(
                    target=lambda: info.__setitem__(
                        "rc", sup.run()
                    ),
                    daemon=True,
                )
                runner.start()
                deadline = time.monotonic() + 120
                while sup.api_base_port is None:
                    time.sleep(0.01)
                    if time.monotonic() > deadline:
                        msg = "cluster never launched"
                        raise RuntimeError(msg)
                sampler = threading.Thread(target=sample, daemon=True)
                sampler.start()
                # Wait for the move to complete (the supervisor
                # records the action and reaches the new size).
                while time.monotonic() < deadline:
                    if (
                        (advice, p_from, p_to) in sup.actions
                        and sup.current == p_to
                        and sup._all_ready
                    ):
                        break
                    time.sleep(0.05)
                else:
                    msg = "autoscale move never completed"
                    raise RuntimeError(msg)
                t_done = time.monotonic()
                # The interruption ENDS at the first epoch advance
                # observed after the move completed; wait for it so
                # the restart path's teardown/boot gap — which
                # stretches past the readiness flip — is inside the
                # measured window, not truncated by it.
                while time.monotonic() < deadline:
                    if advances and advances[-1] > t_done:
                        break
                    time.sleep(0.02)
                else:
                    msg = "no epoch progress after the move"
                    raise RuntimeError(msg)
                t_end = next(t for t in advances if t > t_done)
                if live:
                    if sup.last_live_move is None:
                        msg = "live move fell back to restart"
                        raise RuntimeError(msg)
                    # Delta proof (grow): the surviving coordinator's
                    # migrated-keys counter equals the recomputed
                    # changed-route key count — the migration touched
                    # ONLY the keys whose home lane moved.
                    with urllib.request.urlopen(
                        f"http://127.0.0.1:{sup.api_base_port}"
                        "/metrics",
                        timeout=5,
                    ) as rsp:
                        metrics = rsp.read().decode()
                    migrated = None
                    for line in metrics.splitlines():
                        if line.startswith(
                            "bytewax_rescale_migrated_keys_total"
                        ):
                            migrated = int(float(line.split()[-1]))
                    expected = 0
                    for part in sorted(db.glob("part-*.sqlite3")):
                        con = sqlite3.connect(part)
                        for (key,) in con.execute(
                            "SELECT DISTINCT state_key FROM snaps"
                        ):
                            if route_of(key, p_from) != route_of(
                                key, p_to
                            ):
                                expected += 1
                        con.close()
                    info["migrated_keys"] = migrated
                    info["expected_moved_keys"] = expected
                    if migrated != expected:
                        msg = (
                            f"live move migrated {migrated} keys, "
                            f"expected exactly the {expected} "
                            "changed-route keys"
                        )
                        raise RuntimeError(msg)
                # Let the flow run to EOF so the oracle covers the
                # move end to end.
                runner.join(timeout=180)
                stop_sampling.set()
                sampler.join(timeout=5)
                if runner.is_alive() or info.get("rc") != 0:
                    msg = f"cluster did not finish cleanly ({info.get('rc')})"
                    raise RuntimeError(msg)
            got = sorted(out_path.read_text().split())
            if got != _autoscale_oracle(cap, keys):
                msg = (
                    "output diverged from the host oracle across "
                    f"the {p_from}->{p_to} move"
                )
                raise RuntimeError(msg)
            t0 = state["t_decide"]
            if os.environ.get("BENCH_AUTOSCALE_DEBUG"):
                with open("/tmp/bench_autoscale_debug.json", "w") as f:
                    json.dump(
                        {
                            "t0": t0,
                            "t_done": t_done,
                            "t_end": t_end,
                            "advances": advances,
                        },
                        f,
                    )
            # Anchor the window at the last progress seen BEFORE the
            # decision: if the drain lands between two samples, the
            # interruption still starts from genuine pre-move
            # progress instead of silently shrinking to the post-move
            # tail.
            prior = [t for t in advances if t < t0]
            window = ([prior[-1]] if prior else []) + [
                t for t in advances if t0 <= t <= t_end
            ]
            if len(window) < 2:
                msg = "not enough epoch-advance samples in the move window"
                raise RuntimeError(msg)
            interruption = max(
                b - a for a, b in zip(window, window[1:])
            )
            return interruption, info
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def _run_residency_stress(
    n_rows: int = 100_000, n_keys: int = 4096, budget: int = 64
):
    """Key cardinality ≫ budget: a keyed sum over ``n_keys`` keys with
    ``BYTEWAX_TPU_STATE_BUDGET=budget`` and a disk spill dir, a 90/10
    hot/cold access mix so evictions AND restores churn throughout.

    Returns ``(events_per_sec, restore_p99_ms, evictions,
    peak_resident)`` — and ASSERTS the output equals the host oracle
    (the residency contract: budgeted runs are a memory shape, never
    a semantics change) and that the resident peak held the budget.
    """
    import tempfile
    from datetime import timedelta

    import numpy as np

    import bytewax_tpu.operators as op
    from bytewax_tpu import xla
    from bytewax_tpu.dataflow import Dataflow
    from bytewax_tpu.engine import flight
    from bytewax_tpu.testing import TestingSink, TestingSource, run_main

    n_rows = int(os.environ.get("BENCH_RESIDENCY_ROWS", n_rows))
    rng = np.random.RandomState(7)
    hot = rng.randint(0, 48, size=n_rows)
    cold = rng.randint(0, n_keys, size=n_rows)
    take_cold = rng.rand(n_rows) < 0.1
    key_ids = np.where(take_cold, cold, hot)
    # Batches far smaller than the budget keep the drain-boundary
    # budget invariant assertable (docs/state-residency.md).
    inp = [
        (f"u{int(k):05d}", int(v))
        for k, v in zip(key_ids, rng.randint(0, 100, size=n_rows))
    ]

    env_keys = (
        "BYTEWAX_TPU_STATE_BUDGET",
        "BYTEWAX_TPU_HOST_STATE_BUDGET",
        "BYTEWAX_TPU_SPILL_DIR",
    )
    saved = {k: os.environ.get(k) for k in env_keys}
    main_rec = flight.RECORDER
    flight.RECORDER = flight.FlightRecorder()
    try:
        with tempfile.TemporaryDirectory() as td:
            os.environ["BYTEWAX_TPU_STATE_BUDGET"] = str(budget)
            os.environ["BYTEWAX_TPU_HOST_STATE_BUDGET"] = str(
                budget * 4
            )
            os.environ["BYTEWAX_TPU_SPILL_DIR"] = td
            out = []
            flow = Dataflow("residency_bench_df")
            s = op.input(
                "inp", flow, TestingSource(inp, batch_size=32)
            )
            r = op.reduce_final("sum", s, xla.SUM)
            op.output("out", r, TestingSink(out))
            t0 = time.perf_counter()
            run_main(flow, epoch_interval=timedelta(seconds=10))
            dt = time.perf_counter() - t0
        sums = {}
        for k, v in inp:
            sums[k] = sums.get(k, 0) + v
        assert sorted(out) == sorted(sums.items()), (
            "residency-stress output diverged from the host oracle"
        )
        rec = flight.RECORDER
        peak = max(
            (
                v
                for k, v in rec.counters.items()
                if k.startswith("state_resident_keys_peak[")
            ),
            default=0,
        )
        assert peak <= budget, (
            f"resident peak {peak} exceeded budget {budget}"
        )
        pct = rec.restore_percentiles()
        restore_p99_ms = (
            round(pct[1] * 1e3, 3) if pct is not None else None
        )
        evictions = int(rec.counters.get("state_evictions_count", 0))
        return n_rows / dt, restore_p99_ms, evictions, int(peak)
    finally:
        flight.RECORDER = main_rec
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def main() -> None:
    device = _device()
    backend = device["platform"]

    batch_rows = 1 << 20  # 1M-row micro-batches

    # Warm up compilation with a small run so the timed run measures
    # steady state, like any streaming deployment.
    _run_columnar(batch_rows, batch_rows)

    xla_rows = int(os.environ.get("BENCH_ROWS", 32 * batch_rows))
    host_rows = int(os.environ.get("BENCH_HOST_ROWS", 2_000_000))
    reps = int(os.environ.get("BENCH_REPS", 3))

    # Take the best of a few reps as the steady-state rate.
    xla_rate = max(_run_columnar(xla_rows, batch_rows) for _ in range(reps))
    item_rows = int(os.environ.get("BENCH_ITEM_ROWS", 4_000_000))
    _run_itemized(1 << 20, 1 << 20)  # warm the promoted shapes
    item_rate = max(
        _run_itemized(item_rows, batch_rows) for _ in range(2)
    )
    ingest_rows = int(os.environ.get("BENCH_INGEST_ROWS", 2_000_000))
    _run_ingest_columnar(1 << 18)  # warm the parse + fold shapes
    ingest_rate = max(
        _run_ingest_columnar(ingest_rows) for _ in range(2)
    )
    host_rate = _run_host(host_rows, batch_rows)

    win_ref = _run_windowing_host(100_000, 10)  # the reference shape
    win_accel_rows = int(os.environ.get("BENCH_WIN_ROWS", 4_000_000))
    # Warm both key encodings at the timed batch shape so neither
    # timed number pays the other's jit compiles.
    _run_windowing_columnar(1 << 19, 1 << 19, accel=True)
    _run_windowing_columnar(1 << 19, 1 << 19, accel=True, dict_keys=False)
    win_accel = max(
        _run_windowing_columnar(win_accel_rows, 1 << 19, accel=True)
        for _ in range(2)
    )
    win_accel_str = max(
        _run_windowing_columnar(
            min(win_accel_rows, 1 << 21), 1 << 19, accel=True,
            dict_keys=False,
        )
        for _ in range(2)
    )
    win_host = _run_windowing_columnar(
        min(win_accel_rows, 1 << 21), 1 << 19, accel=False
    )
    # Dispatch-pipeline overlap: the same accelerated windowing shape
    # at depth 1 (the synchronous lock-step engine) vs depth 2
    # (double-buffered: batch N+1's host ingest overlaps batch N's
    # device phase) — the ratio is the pipeline's measured win.
    pipe_d1 = max(
        _run_windowing_columnar(
            win_accel_rows, 1 << 19, accel=True, depth=1
        )
        for _ in range(2)
    )
    pipe_d2 = max(
        _run_windowing_columnar(
            win_accel_rows, 1 << 19, accel=True, depth=2
        )
        for _ in range(2)
    )
    _run_windowing_itemized(1 << 18, accel=True)  # warm
    win_item_accel = max(
        _run_windowing_itemized(2_000_000, accel=True) for _ in range(2)
    )
    win_item_host = _run_windowing_itemized(500_000, accel=False)
    _run_windowing_session(1 << 19, 1 << 19)  # warm at the timed shape
    win_session = max(
        _run_windowing_session(min(win_accel_rows, 1 << 21), 1 << 19)
        for _ in range(2)
    )
    p99_s, n_closes = _run_window_close_p99()
    wc_rate = max(_run_wordcount(50_000) for _ in range(2))
    anomaly_rate, anomaly_cold_s = _run_anomaly(500_000)
    step_ms, sharded_ms = _device_step_ms()

    #: Phases that failed: each records its error under its own key
    #: and lands here, and the run exits non-zero after reporting.
    failed = []

    def fail(key: str, ex: Exception) -> None:
        extra[key] = str(ex)[:200]
        failed.append(key)

    extra = {
        "windowing_ref_shape_events_per_sec": round(win_ref),
        "windowing_accel_events_per_sec": round(win_accel),
        "windowing_accel_strkeys_events_per_sec": round(win_accel_str),
        "windowing_host_events_per_sec": round(win_host),
        "windowing_accel_vs_host": round(win_accel / win_host, 2),
        "pipeline_depth1_events_per_sec": round(pipe_d1),
        "pipeline_depth2_events_per_sec": round(pipe_d2),
        "pipeline_overlap": round(pipe_d2 / pipe_d1, 2),
        "windowing_itemized_accel_events_per_sec": round(win_item_accel),
        "windowing_itemized_host_events_per_sec": round(win_item_host),
        "windowing_session_events_per_sec": round(win_session),
        "window_close_p99_ms": (
            round(p99_s * 1e3, 3) if p99_s is not None else None
        ),
        "window_closes_measured": n_closes,
        "wordcount_events_per_sec": round(wc_rate),
        "anomaly_events_per_sec": round(anomaly_rate),
        "anomaly_cold_start_ms": round(anomaly_cold_s * 1e3, 1),
        "device_step_1m_rows_ms": round(step_ms, 3),
        "pipeline_depth": int(
            os.environ.get("BYTEWAX_TPU_PIPELINE_DEPTH", "2") or 2
        ),
        "brc_itemized_events_per_sec": round(item_rate),
        "brc_itemized_vs_columnar": round(item_rate / xla_rate, 2),
        "ingest_columnar_events_per_sec": round(ingest_rate),
        "host_events_per_sec": round(host_rate),
    }
    if sharded_ms is not None:
        extra["sharded_step_1m_rows_ms"] = round(sharded_ms, 3)
        extra["sharded_devices"] = len(
            __import__("jax").local_devices()
        )

    # The flight recorder's counters and close-percentile buffer are
    # always on (the ring stays off, so the measured loops are not
    # perturbed): report compile counts and epoch-close latency so
    # BENCH_* files track recompile regressions round over round.
    from bytewax_tpu.engine import flight

    rec = flight.RECORDER
    extra["xla_compile_count"] = int(
        rec.counters.get("xla_compile_count", 0)
    )
    extra["xla_compile_seconds"] = round(
        rec.counters.get("xla_compile_seconds", 0.0), 3
    )
    pct = rec.epoch_close_percentiles()
    if pct is not None:
        p50_s, p99_s_close, n_closes_rec = pct
        extra["epoch_close_p50_ms"] = round(p50_s * 1e3, 3)
        extra["epoch_close_p99_ms"] = round(p99_s_close * 1e3, 3)
        extra["epoch_closes_recorded"] = n_closes_rec
    # Epoch-ledger attribution (docs/observability.md): where this
    # round's epochs actually went — host routing vs device folds vs
    # flush stalls vs barrier/gsync/snapshot — as fractions of the
    # attributed time, so BENCH_* files track the measured bottleneck
    # round over round, not just the close latency.
    extra["epoch_phase_fractions"] = flight.ledger_fractions()

    # Flow-map observability cost (docs/observability.md "Flow
    # map"): the pipelined windowed bench with /graph polled
    # continuously vs idle (< 3% asserted in-bench), plus the
    # derived bottleneck attribution for the round.
    try:
        fm_pct, fm_polls, fm_bn = _run_flowmap_overhead()
        extra["flowmap_overhead_pct"] = round(fm_pct, 2)
        extra["flowmap_graph_polls"] = fm_polls
        extra["bottleneck_step"] = fm_bn
    except Exception as ex:  # noqa: BLE001 - bench must still report
        extra["flowmap_overhead_pct"] = None
        fail("flowmap_overhead_error", ex)

    # Streaming inference (docs/inference.md): op.infer's batched
    # device scoring vs the same model scored per-item through a
    # host-tier op.map (the pre-subsystem path), numpy-oracle
    # asserted in-bench; plus the live hot-swap staleness window
    # (update_params request -> first new-generation emission).
    try:
        infer_rows = int(os.environ.get("BENCH_INFER_ROWS", 512_000))
        _run_infer_accel_vs_host(2 * 8_192)  # warm both tiers
        infer_accel, infer_host = max(
            (_run_infer_accel_vs_host(infer_rows) for _ in range(2)),
            key=lambda r: r[0],
        )
        extra["infer_accel_events_per_sec"] = round(infer_accel)
        extra["infer_host_map_events_per_sec"] = round(infer_host)
        extra["infer_accel_vs_host_map"] = round(
            infer_accel / infer_host, 2
        )
    except Exception as ex:  # noqa: BLE001 - bench must still report
        extra["infer_accel_events_per_sec"] = None
        fail("infer_error", ex)
    try:
        extra["infer_swap_gap_ms"] = round(_run_infer_swap_gap(), 1)
    except Exception as ex:  # noqa: BLE001 - bench must still report
        extra["infer_swap_gap_ms"] = None
        fail("infer_swap_gap_error", ex)

    # Persistent-compile-cache cold vs warm start (fresh processes;
    # the warm figure is what a supervised restart or redeploy pays).
    try:
        cold_ms, warm_ms = _run_anomaly_cold_vs_warm()
        extra["anomaly_cold_start_nocache_ms"] = round(cold_ms, 1)
        extra["anomaly_warm_start_ms"] = round(warm_ms, 1)
    except Exception as ex:  # noqa: BLE001 - bench must still report
        extra["anomaly_warm_start_ms"] = None
        fail("anomaly_cold_vs_warm_error", ex)

    try:
        extra["restart_recovery_s"] = round(_run_restart_recovery(), 3)
    except Exception as ex:  # noqa: BLE001 - bench must still report
        extra["restart_recovery_s"] = None
        fail("restart_recovery_error", ex)

    # Async incremental checkpoints (docs/recovery.md): epoch-close
    # p99 with the synchronous whole-state checkpointer vs sealed
    # delta snapshots committed on the committer lane — same keyed
    # flow, output equality and a zero run-ending snapshot lag
    # asserted in-bench.
    try:
        ck = _run_ckpt_async_vs_sync()
        extra["ckpt_sync_close_p99_ms"] = round(
            ck["sync_p99_s"] * 1e3, 3
        )
        extra["ckpt_async_close_p99_ms"] = round(
            ck["async_p99_s"] * 1e3, 3
        )
        extra["snapshot_lag_epochs"] = ck["lag_epochs"]
    except Exception as ex:  # noqa: BLE001 - bench must still report
        extra["ckpt_async_close_p99_ms"] = None
        fail("ckpt_async_error", ex)

    # Connector-edge resilience (docs/recovery.md): throughput while
    # seeded transient faults fire through the source_poll/sink_write
    # sites and the in-place retry ladder absorbs every one (oracle
    # equality + zero restarts asserted in-bench).
    try:
        extra["io_fault_soak_events_per_sec"] = round(
            _run_io_fault_soak()
        )
    except Exception as ex:  # noqa: BLE001 - bench must still report
        extra["io_fault_soak_events_per_sec"] = None
        fail("io_fault_soak_error", ex)

    # Columnar frames on the wire (docs/performance.md "Columnar
    # exchange"): the 2-proc keyed columnar shuffle, host-oracle
    # asserted in-bench, against the legacy-wire baseline
    # (BYTEWAX_TPU_WIRE=pickle = whole-frame pickle AND one frame per
    # routed slice — the ratio measures codec + frame coalescing
    # together, i.e. the whole exchange subsystem vs the pre-PR
    # wire).
    try:
        shuffle = _run_cluster_columnar_shuffle()
        extra["cluster_columnar_events_per_sec"] = round(
            shuffle["columnar"]["events_per_sec"]
        )
        extra["cluster_pickle_events_per_sec"] = round(
            shuffle["pickle"]["events_per_sec"]
        )
        extra["cluster_columnar_vs_pickle"] = round(
            shuffle["columnar"]["events_per_sec"]
            / shuffle["pickle"]["events_per_sec"],
            2,
        )
        extra["wire_bytes_per_event"] = round(
            shuffle["columnar"]["wire_bytes_per_event"], 2
        )
        extra["wire_bytes_per_event_pickle"] = round(
            shuffle["pickle"]["wire_bytes_per_event"], 2
        )
        extra["wire_frames_columnar_run"] = shuffle["columnar"][
            "wire_frames"
        ]
    except Exception as ex:  # noqa: BLE001 - bench must still report
        extra["cluster_columnar_events_per_sec"] = None
        fail("cluster_columnar_error", ex)

    # Overlapped collectives (docs/performance.md "Overlapped
    # collectives"): the 2-proc global-mesh keyed aggregation with
    # the exchange double-buffered onto the collective lane vs the
    # lock-step tier — host oracle asserted in-bench on every rep.
    try:
        ovl = _run_collective_overlap()
        extra["collective_lockstep_events_per_sec"] = round(
            ovl["lockstep"]
        )
        extra["collective_overlap_events_per_sec"] = round(
            ovl["overlap"]
        )
        extra["collective_overlap"] = round(
            ovl["overlap"] / ovl["lockstep"], 2
        )
    except Exception as ex:  # noqa: BLE001 - bench must still report
        extra["collective_overlap"] = None
        fail("collective_overlap_error", ex)

    # Quantized gsync aggregate frames: bytes per exchange round,
    # quantized vs exact (counts asserted byte-exact in-bench).
    try:
        gsync_bytes = _run_gsync_bytes_per_round()
        extra["gsync_bytes_per_round"] = gsync_bytes
        extra["gsync_bytes_int8_vs_exact"] = round(
            gsync_bytes["int8"] / gsync_bytes["off"], 3
        )
    except Exception as ex:  # noqa: BLE001 - bench must still report
        extra["gsync_bytes_per_round"] = None
        fail("gsync_bytes_error", ex)

    # HBM-resident aggregate: host↔device bytes per merged exchange
    # round, device merge vs the host fold (docs/performance.md
    # "Device-side dequant+merge") — device tables asserted against
    # the host-fold oracle in-bench.
    try:
        d2h = _run_gsync_d2h_bytes_per_round()
        extra["gsync_d2h_bytes_per_round"] = d2h
        extra["gsync_d2h_int8_vs_host_fold"] = round(
            d2h["int8"] / d2h["host_fold"], 3
        )
    except Exception as ex:  # noqa: BLE001 - bench must still report
        extra["gsync_d2h_bytes_per_round"] = None
        fail("gsync_d2h_bytes_error", ex)

    # Elastic rescale-on-resume: stop a 2-lane flow, relaunch at 3
    # lanes with the store migration (docs/recovery.md) — the pause
    # an operator pays to resize a running flow.
    try:
        extra["rescale_resume_s"] = round(_run_rescale_resume(), 3)
    except Exception as ex:  # noqa: BLE001 - bench must still report
        extra["rescale_resume_s"] = None
        fail("rescale_resume_error", ex)

    # Graceful drain-to-stop (docs/recovery.md): stop request →
    # clean exit with the in-flight epoch committed — the drain the
    # autoscaler pays instead of the crash path's recovery replay.
    try:
        extra["graceful_stop_s"] = round(_run_graceful_stop(), 3)
    except Exception as ex:  # noqa: BLE001 - bench must still report
        extra["graceful_stop_s"] = None
        fail("graceful_stop_error", ex)

    # The autoscale pause, measured as SERVICE INTERRUPTION (longest
    # epoch-progress gap across the move) on a real supervised
    # multi-process cluster.  autoscale_grow_s / autoscale_shrink_s
    # are the live partial-rescale path (the default;
    # docs/recovery.md "Live partial rescale") — the grow leg also
    # asserts in-bench that the migration moved ONLY the
    # changed-route keys and that output equals the host oracle
    # exactly-once.  autoscale_grow_restart_s is the legacy
    # whole-cluster drain-to-stop + relaunch (the PR-11 path) under
    # the identical methodology, so the live-vs-restart ratio is
    # measured, not assumed.
    try:
        grow_s, grow_info = _run_autoscale_move(2, 3, live=True)
        extra["autoscale_grow_s"] = round(grow_s, 3)
        extra["autoscale_grow_migrated_keys"] = grow_info[
            "migrated_keys"
        ]
    except Exception as ex:  # noqa: BLE001 - bench must still report
        extra["autoscale_grow_s"] = None
        fail("autoscale_grow_error", ex)
    try:
        shrink_s, _info = _run_autoscale_move(3, 2, live=True)
        extra["autoscale_shrink_s"] = round(shrink_s, 3)
    except Exception as ex:  # noqa: BLE001 - bench must still report
        extra["autoscale_shrink_s"] = None
        fail("autoscale_shrink_error", ex)
    try:
        restart_s, _info = _run_autoscale_move(2, 3, live=False)
        extra["autoscale_grow_restart_s"] = round(restart_s, 3)
        if extra.get("autoscale_grow_s"):
            extra["autoscale_live_vs_restart"] = round(
                restart_s / extra["autoscale_grow_s"], 2
            )
    except Exception as ex:  # noqa: BLE001 - bench must still report
        extra["autoscale_grow_restart_s"] = None
        fail("autoscale_grow_restart_error", ex)

    # Tiered key-state residency under stress (cardinality >> budget;
    # docs/state-residency.md): throughput with continuous evict/
    # restore/spill churn, plus restore latency percentiles — the
    # price of a residency fault.
    try:
        res_rate, res_p99, res_evs, res_peak = _run_residency_stress()
        extra["residency_stress_events_per_sec"] = round(res_rate)
        extra["residency_restore_p99_ms"] = res_p99
        extra["residency_evictions"] = res_evs
        extra["residency_peak_resident"] = res_peak
    except Exception as ex:  # noqa: BLE001 - bench must still report
        extra["residency_stress_events_per_sec"] = None
        fail("residency_error", ex)

    # Static contract enforcement status: rule count, per-rule
    # finding counts, clean/dirty, and the analyzer's own wall time —
    # so the trajectory records enforcement growth AND analyzer
    # regressions round over round (pure AST — never touches jax;
    # see docs/contracts.md).
    try:
        from bytewax_tpu.analysis import ALL_RULES, analyze_tree

        rule_timings = {}
        t0 = time.perf_counter()
        diags, _suppressed, _project = analyze_tree(
            timings=rule_timings
        )
        extra["analysis_wall_s"] = round(time.perf_counter() - t0, 3)
        extra["contract_rules"] = len(ALL_RULES)
        extra["contract_findings"] = len(diags)
        by_rule = {rid: 0 for rid in ALL_RULES}
        for d in diags:
            by_rule[d.rule] = by_rule.get(d.rule, 0) + 1
        extra["contract_findings_by_rule"] = by_rule
        extra["contract_rule_wall_s"] = {
            rid: round(secs, 3)
            for rid, secs in sorted(rule_timings.items())
        }
        extra["contracts_clean"] = not diags
    except Exception as ex:  # noqa: BLE001 - bench must still report
        fail("contracts_error", ex)

    # A dirty tree is a bench-integrity failure, not a metric: every
    # number above assumes the engine honors its own lane/drain/send
    # contracts (an analyzer *error* is reported as contracts_error
    # and fails the exit code after the line is printed — a finding
    # stops the run here).
    assert extra.get("contracts_clean", True), (
        "static contracts dirty in-bench: "
        f"{extra.get('contract_findings_by_rule')}"
    )

    extra["backend"] = backend
    extra["device"] = device
    extra["failed_phases"] = failed
    print(
        json.dumps(
            {
                "metric": "1brc_keyed_stats_events_per_sec",
                "value": round(xla_rate),
                # Only a real accelerator run may claim a /chip rate.
                "unit": (
                    "events/s/chip" if backend != "cpu" else "events/s"
                ),
                "vs_baseline": round(xla_rate / host_rate, 2),
                "extra": extra,
            }
        )
    )
    if failed:
        sys.exit(f"bench.py: {len(failed)} phase(s) failed: {failed}")


if __name__ == "__main__":
    main()
