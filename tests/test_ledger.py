"""Epoch-ledger tests (tentpole of the observability PR): per-epoch
time attribution and its consumers — `/status`, Prometheus, the
Perfetto ``trace_event`` dump, the attribution-backed rescale hint —
plus the satellite surfaces (`/healthz`, `/stacks`, crash
post-mortems).

The ledger is always-on observability data on a global recorder, so
tests that assert per-run records clear the sealed-record buffer
first (never the engine's own state).
"""

import json
import os
import subprocess
import sys
import time
import urllib.error
import urllib.request
from datetime import timedelta

import pytest

import bytewax_tpu.operators as op
from bytewax_tpu.dataflow import Dataflow
from bytewax_tpu.engine import faults, flight
from bytewax_tpu.engine.driver import derive_rescale_hint
from bytewax_tpu.recovery import RecoveryConfig, init_db_dir
from bytewax_tpu.testing import TestingSink, TestingSource, run_main

ZERO_TD = timedelta(seconds=0)

#: Ledger phases measured on the main thread: disjoint exclusive
#: intervals, so their per-epoch sum may never exceed the epoch wall
#: time ("device" and its children, "device/prep", run on the
#: pipeline worker and overlap).
_MAIN_PHASES_ONLY = lambda phases: {  # noqa: E731
    p: v for p, v in phases.items() if p.split("/")[0] != "device"
}


def _reset_ledger():
    rec = flight.RECORDER
    rec._ledgers.clear()
    rec.last_ledger = None
    rec._ledger = {}
    rec._ledger_pre_close = None
    rec._epoch_t0 = time.monotonic()


def _phase_sum(phases):
    return sum(
        s for steps in phases.values() for s in steps.values()
    )


# -- phase attribution sums --------------------------------------------


def test_ledger_phase_sums_all_entry_points(entry_point):
    # Every epoch close seals a ledger record whose main-thread
    # phases are disjoint exclusive intervals: per epoch they sum to
    # no more than the epoch wall time, and over a host-work-heavy
    # run they attribute most of it.
    _reset_ledger()
    out = []
    flow = Dataflow("ledger_df")
    s = op.input("inp", flow, TestingSource(list(range(30)), batch_size=6))
    s = op.map("work", s, lambda x: (time.sleep(0.002), x * 2)[1])
    op.output("out", s, TestingSink(out))
    entry_point(flow, epoch_interval=ZERO_TD)
    assert out and len(out) == 30

    records = flight.RECORDER.ledgers()
    assert records, "no ledger records sealed"
    for rec in records:
        assert isinstance(rec["epoch"], int)
        phases = rec["phases"]
        main_sum = _phase_sum(_MAIN_PHASES_ONLY(phases))
        # Disjoint main-thread intervals: sum <= wall (small slack
        # for float rounding / clock granularity).
        assert main_sum <= rec["wall_s"] * 1.05 + 0.002, rec
        # Close-window breakdown tracks the measured close duration.
        close_sum = sum(rec["close"].values())
        assert close_sum <= rec["close_s"] * 1.1 + 0.002, rec
        assert rec["close_s"] <= rec["wall_s"] * 1.05 + 0.002
    # The sleeping mapper dominates: most wall time is attributed
    # (skip the first record — its window includes driver startup).
    tail = records[1:]
    if tail:
        wall = sum(r["wall_s"] for r in tail)
        attributed = sum(
            _phase_sum(_MAIN_PHASES_ONLY(r["phases"])) for r in tail
        )
        assert attributed >= 0.45 * wall, (attributed, wall)
    # The mapper's step shows up under the host phase somewhere.
    hosts = [r["phases"].get("host", {}) for r in records]
    assert any(
        "ledger_df.work.flat_map_batch" in h for h in hosts
    ), hosts


def _windowed_accel_flow(n_rows=200):
    """Columnar event-time count_window exercising the accelerated
    window step (device pipeline: device/readback phases, processing
    lag) with a ``ts`` column (event-time lag)."""
    from datetime import datetime, timezone

    import numpy as np

    import bytewax_tpu.operators.windowing as w
    from bytewax_tpu.engine.arrays import ArrayBatch
    from bytewax_tpu.models.brc import ArrayBatchSource
    from bytewax_tpu.operators.windowing import (
        EventClock,
        TumblingWindower,
    )

    align = datetime(2022, 1, 1, tzinfo=timezone.utc)
    base = np.datetime64(align.replace(tzinfo=None), "us")
    batches = [
        ArrayBatch(
            {
                "key_id": (np.arange(n_rows) % 2).astype(np.int32),
                "ts": base
                + (np.arange(n_rows) // 10).astype("timedelta64[s]"),
            },
            key_vocab=np.array(["0", "1"]),
        )
    ]
    clock = EventClock(
        ts_getter=lambda x: x, wait_for_system_duration=ZERO_TD
    )
    windower = TumblingWindower(
        align_to=align, length=timedelta(seconds=10)
    )
    out = []
    flow = Dataflow("lag_df")
    s = op.input("in", flow, ArrayBatchSource(batches))
    wo = w.count_window("count", s, clock, windower, key=lambda x: x)
    op.output("out", wo.down, TestingSink(out))
    return flow, out


def test_source_lag_and_device_phase(monkeypatch):
    # Source lag accounting: event_time lag sampled at ingest from
    # the batch's ts column, processing lag from the dispatch
    # pipeline's submit->finalize interval; the device fold's wall
    # time lands in the ledger's worker lane.
    from prometheus_client import REGISTRY

    monkeypatch.setenv("BYTEWAX_TPU_ACCEL", "1")
    _reset_ledger()
    flight.RECORDER._lag.clear()
    flow, out = _windowed_accel_flow()
    run_main(flow, epoch_interval=ZERO_TD)
    assert out  # windows closed on device

    lag = flight.RECORDER._lag
    # The 2022 timestamps are years behind wall clock: a big positive
    # event-time lag, sampled at the input step.
    assert lag.get(("lag_df.in", "event_time"), 0.0) > 0.0
    assert any(kind == "processing" for (_s, kind) in lag), lag
    # Prometheus mirrors of both samples.
    assert (
        REGISTRY.get_sample_value(
            "bytewax_source_lag_seconds",
            {"step_id": "lag_df.in", "kind": "event_time"},
        )
        > 0.0
    )
    # Device fold time attributed on the worker lane.
    assert flight.RECORDER.phase_totals.get("device", 0.0) > 0.0
    # And the epoch_phase_seconds family carries it.
    from bytewax_tpu._metrics import generate_python_metrics

    text = generate_python_metrics()
    assert "bytewax_epoch_phase_seconds" in text
    assert "bytewax_source_lag_seconds" in text


def test_event_lag_nat_timestamp_is_skipped(now):
    # A NaT in the ts column must yield no sample (never NaN — a NaN
    # gauge renders /status as invalid JSON cluster-wide).
    import numpy as np

    from bytewax_tpu.engine.arrays import ArrayBatch
    from bytewax_tpu.engine.driver import _batch_event_lag_s

    ts = np.array(["2022-01-01T00:00:00", "NaT"], dtype="datetime64[us]")
    batch = ArrayBatch(
        {"key_id": np.zeros(2, dtype=np.int32), "ts": ts},
        key_vocab=np.array(["0"]),
    )
    assert _batch_event_lag_s(batch, now) is None
    # Without the NaT the same batch samples a real lag.
    ok = ArrayBatch(
        {"key_id": np.zeros(2, dtype=np.int32), "ts": ts[:1].repeat(2)},
        key_vocab=np.array(["0"]),
    )
    lag = _batch_event_lag_s(ok, now)
    assert lag is not None and lag == lag and lag > 0


# -- fraction buckets and the attribution-backed rescale hint ----------


def test_ledger_fractions_buckets():
    fr = flight.ledger_fractions(
        {"host": 1.0, "ingest": 1.0, "device": 1.0, "barrier": 1.0}
    )
    assert fr["host"] == 0.5  # host + ingest fold into one bucket
    assert fr["device"] == 0.25 and fr["barrier"] == 0.25
    assert abs(sum(fr.values()) - 1.0) < 0.01
    # No attributed time yet -> no fractions (not a zero division).
    assert flight.ledger_fractions({}) is None


def test_rescale_hint_ledger_device_dominated_grows():
    advice, reasons = derive_rescale_hint(
        worker_count=1,
        epoch_interval_s=10.0,
        close_p99_s=0.1,
        stall_s_per_close=0.0,
        restores_per_close=0.0,
        phase_fractions={"device": 0.4, "flush": 0.2, "host": 0.4},
    )
    assert advice == "grow"
    assert any("ledger" in r and "device" in r for r in reasons)


def test_rescale_hint_barrier_dominated_vetoes_grow():
    # Loud close latency but barrier-dominated attribution: this
    # process is waiting for peers — growing adds waiters.
    advice, reasons = derive_rescale_hint(
        worker_count=2,
        epoch_interval_s=10.0,
        close_p99_s=6.0,
        stall_s_per_close=0.0,
        restores_per_close=0.0,
        phase_fractions={"barrier": 0.7, "host": 0.3},
    )
    assert advice == "hold"
    assert any("barrier" in r for r in reasons)


def test_rescale_hint_barrier_dominated_shrinks_when_not_loud():
    advice, reasons = derive_rescale_hint(
        worker_count=2,
        epoch_interval_s=10.0,
        close_p99_s=None,
        stall_s_per_close=0.0,
        restores_per_close=0.0,
        phase_fractions={"barrier": 0.8, "host": 0.2},
    )
    assert advice == "shrink"
    assert any("barrier" in r for r in reasons)


# -- Perfetto trace_event export ---------------------------------------


def test_perfetto_trace_dump(monkeypatch, tmp_path):
    trace_dir = tmp_path / "traces"
    monkeypatch.setenv("BYTEWAX_TPU_TRACE_DIR", str(trace_dir))
    _reset_ledger()
    out = []
    flow = Dataflow("trace_df")
    s = op.input("inp", flow, TestingSource(list(range(20)), batch_size=5))
    s = op.map("double", s, lambda x: x * 2)
    op.output("out", s, TestingSink(out))
    run_main(flow, epoch_interval=ZERO_TD)
    assert out

    files = sorted(trace_dir.glob("epoch-p00-*.json"))
    assert files, list(trace_dir.iterdir())
    saw_phase_slice = False
    saw_counter = False
    counter_ts = {}  # (path, track) -> [ts, ...]
    for path in files:
        doc = json.loads(path.read_text())
        events = doc["traceEvents"]
        assert isinstance(events, list) and events
        for ev in events:
            # Chrome trace_event required fields per phase type.
            assert isinstance(ev["name"], str)
            assert ev["ph"] in ("M", "X", "C")
            assert isinstance(ev["pid"], int)
            if ev["ph"] == "X":
                assert isinstance(ev["ts"], (int, float))
                assert isinstance(ev["dur"], (int, float))
                assert ev["dur"] >= 0
                if ev.get("args", {}).get("step_id"):
                    saw_phase_slice = True
            elif ev["ph"] == "C":
                # Flow-map counter tracks: numeric args only (Chrome
                # renders each args key as a series on the track).
                saw_counter = True
                assert isinstance(ev["ts"], (int, float))
                assert ev["args"], ev
                for v in ev["args"].values():
                    assert isinstance(v, (int, float)), ev
                counter_ts.setdefault(
                    (str(path), ev["name"]), []
                ).append(ev["ts"])
    assert saw_phase_slice, "no per-step phase slices in any dump"
    # Counter tracks ride the flow-map seal: every dump after the
    # first sealed epoch carries rows/s samples...
    assert saw_counter, "no flow-map counter tracks in any dump"
    assert any(
        name.startswith("rows/s ") for (_p, name) in counter_ts
    ), sorted(counter_ts)
    # ...and each track's samples are monotone-timestamped (Perfetto
    # silently drops out-of-order counter samples).
    for (path, name), stamps in counter_ts.items():
        assert len(stamps) >= 2, (path, name, stamps)
        assert stamps == sorted(stamps), (path, name, stamps)


_OVERLAP_TRACE_FLOW = '''
import os

import bytewax_tpu.operators as op
from bytewax_tpu import xla
from bytewax_tpu.dataflow import Dataflow
from bytewax_tpu.connectors.files import FileSink
from bytewax_tpu.inputs import DynamicSource, StatelessSourcePartition


class _Part(StatelessSourcePartition):
    """Paced batches so the run spans several epochs (several
    overlapped collective flush rounds), not one EOF burst."""

    def __init__(self, worker_index):
        import time

        self._time = time
        base = worker_index * 1000
        self._batches = [
            [(f"k{{i % 5}}", float(base + b * 100 + i)) for i in range(80)]
            for b in range(4)
        ]

    def next_batch(self):
        if not self._batches:
            raise StopIteration()
        self._time.sleep(0.12)
        return self._batches.pop(0)


class Src(DynamicSource):
    def build(self, step_id, worker_index, worker_count):
        return _Part(worker_index)


flow = Dataflow("trace_ovl_df")
s = op.input("inp", flow, Src())
st = xla.stats_final("stats", s)
fmt = op.map_value("fmt", st, str)
op.output("out", fmt, FileSink({out_path!r}))
'''


def test_perfetto_overlap_collective_lane_own_tid(tmp_path):
    # Under BYTEWAX_TPU_GSYNC_OVERLAP=1 the sealed device exchange
    # runs on the collective lane while the next epoch computes: its
    # spans must land on their OWN Perfetto tid (3; named by a
    # thread_name meta), distinct from the driver (1) and device
    # pipeline (2) tracks — sharing the device tid would render as
    # nonsense nesting — and the flow-map counter tracks must emit
    # monotone-timestamped samples in the same dumps.
    trace_dir = tmp_path / "traces"
    flow_py = tmp_path / "trace_ovl_flow.py"
    out_path = str(tmp_path / "trace_ovl_out.txt")
    flow_py.write_text(_OVERLAP_TRACE_FLOW.format(out_path=out_path))

    env = dict(os.environ)
    env["PYTHONPATH"] = "/root/repo" + os.pathsep + env.get(
        "PYTHONPATH", ""
    )
    env["BYTEWAX_TPU_PLATFORM"] = "cpu"
    env["BYTEWAX_TPU_ACCEL"] = "1"
    env["BYTEWAX_TPU_DISTRIBUTED"] = "1"
    env["BYTEWAX_TPU_GLOBAL_EXCHANGE"] = "1"
    env["BYTEWAX_TPU_GSYNC_OVERLAP"] = "1"
    env["BYTEWAX_TPU_TRACE_DIR"] = str(trace_dir)
    # Batch-granular ingest: the coalescer would collapse the paced
    # source into one EOF flush and leave nothing to overlap.
    env["BYTEWAX_TPU_INGEST_TARGET_ROWS"] = "0"
    res = subprocess.run(
        [
            sys.executable,
            "-m",
            "bytewax_tpu.testing",
            f"{flow_py}:flow",
            "-p",
            "2",
            "-s",
            "0.2",
        ],
        env=env,
        cwd=tmp_path,
        capture_output=True,
        text=True,
        timeout=240,
    )
    assert res.returncode == 0, res.stderr[-3000:]

    lane_spans = []
    other_tids = set()
    counter_ts = {}
    for proc in (0, 1):
        files = sorted(trace_dir.glob(f"epoch-p{proc:02d}-*.json"))
        assert files, list(
            trace_dir.iterdir() if trace_dir.exists() else []
        )
        for path in files:
            doc = json.loads(path.read_text())
            lane_named = [
                ev
                for ev in doc["traceEvents"]
                if ev["ph"] == "M"
                and ev["name"] == "thread_name"
                and ev["args"]["name"] == "collective lane"
            ]
            assert lane_named and all(
                ev["tid"] == 3 for ev in lane_named
            ), path
            for ev in doc["traceEvents"]:
                if ev["ph"] == "X":
                    if ev["name"] == "collective_lane":
                        lane_spans.append(ev)
                    else:
                        other_tids.add(ev["tid"])
                elif ev["ph"] == "C":
                    counter_ts.setdefault(
                        (str(path), ev["name"]), []
                    ).append(ev["ts"])
    # The sealed exchange ran (both procs flush, but dumps are
    # per-process; one proc's lane spans suffice for the rendering
    # contract) and every lane span sits on tid 3.
    assert lane_spans, "no collective_lane spans in any dump"
    assert {ev["tid"] for ev in lane_spans} == {3}
    # No other span ever shares the lane's track (the collective
    # tier bypasses the per-delivery device pipeline, so this flow
    # has no device-lane spans — only the driver track plus the
    # lane's own).
    assert 3 not in other_tids, other_tids
    assert 1 in other_tids, other_tids
    # Counter tracks emit monotone-timestamped samples under overlap.
    assert counter_ts, "no flow-map counter tracks in any dump"
    for (path, name), stamps in counter_ts.items():
        assert len(stamps) >= 2, (path, name, stamps)
        assert stamps == sorted(stamps), (path, name, stamps)


# -- /healthz and /stacks ----------------------------------------------


def test_healthz_and_stacks_during_run(monkeypatch, tmp_path):
    monkeypatch.setenv("BYTEWAX_DATAFLOW_API_ENABLED", "1")
    monkeypatch.setenv("BYTEWAX_DATAFLOW_API_PORT", "13049")
    monkeypatch.chdir(tmp_path)

    captured = {}

    class _ProbePartition:
        def write_batch(self, items):
            if "health" not in captured:
                with urllib.request.urlopen(
                    "http://127.0.0.1:13049/healthz", timeout=5
                ) as resp:
                    captured["health_code"] = resp.status
                    captured["health"] = json.loads(resp.read())
                with urllib.request.urlopen(
                    "http://127.0.0.1:13049/stacks", timeout=5
                ) as resp:
                    captured["stacks"] = resp.read().decode()

        def close(self):
            pass

    from bytewax_tpu.outputs import DynamicSink

    class _ProbeSink(DynamicSink):
        def build(self, step_id, worker_index, worker_count):
            return _ProbePartition()

    flow = Dataflow("health_df")
    s = op.input("inp", flow, TestingSource([1, 2, 3]))
    op.output("out", s, _ProbeSink())
    run_main(flow)

    # Readiness: startup (handshake, agreement round, runtime builds)
    # finished before the run loop -> 200 ready from inside the run.
    assert captured["health_code"] == 200
    health = captured["health"]
    assert health["live"] is True and health["ready"] is True
    assert health["proc_id"] == 0
    assert isinstance(health["epoch"], int)
    # /stacks names every thread with a Python stack; the probe runs
    # on the main run loop.
    assert "MainThread" in captured["stacks"]
    assert "Thread " in captured["stacks"]


def test_healthz_not_ready_is_503(monkeypatch, tmp_path):
    # k8s readiness reads the status code: an un-ready process must
    # answer 503 (liveness still true in the body).
    monkeypatch.setenv("BYTEWAX_DATAFLOW_API_ENABLED", "1")
    monkeypatch.setenv("BYTEWAX_DATAFLOW_API_PORT", "13050")
    monkeypatch.chdir(tmp_path)
    from bytewax_tpu.engine.webserver import maybe_start_server

    flow = Dataflow("unready_df")
    s = op.input("inp", flow, TestingSource([1]))
    op.output("out", s, TestingSink([]))
    srv = maybe_start_server(
        flow, health_fn=lambda: {"ready": False, "phase": "startup"}
    )
    assert srv is not None
    try:
        with pytest.raises(urllib.error.HTTPError) as exc_info:
            urllib.request.urlopen(
                "http://127.0.0.1:13050/healthz", timeout=5
            )
        err = exc_info.value
        assert err.code == 503
        body = json.loads(err.read())
        assert body["live"] is True and body["ready"] is False
    finally:
        srv.shutdown()


def test_back_to_back_runs_share_one_port_and_tear_down_at_once(
    monkeypatch, tmp_path
):
    # A job ends when its work ends: with the API plane on, a tiny
    # flow's "teardown" span no longer waits out serve_forever's poll
    # (0.0-0.5 s a run), and the next run in the same process binds
    # the same port (no "continuing without /dataflow, /metrics,
    # /status") and answers on it.
    monkeypatch.setenv("BYTEWAX_DATAFLOW_API_ENABLED", "1")
    monkeypatch.setenv("BYTEWAX_DATAFLOW_API_PORT", "13067")
    monkeypatch.chdir(tmp_path)
    from bytewax_tpu.outputs import DynamicSink

    graphs = []

    class _ProbePartition:
        def write_batch(self, items):
            with urllib.request.urlopen(
                "http://127.0.0.1:13067/graph", timeout=5
            ) as resp:
                graphs.append(json.loads(resp.read()))

        def close(self):
            pass

    class _ProbeSink(DynamicSink):
        def build(self, step_id, worker_index, worker_count):
            return _ProbePartition()

    teardowns = []
    for n in range(2):
        flow = Dataflow(f"job{n}_df")
        s = op.input("inp", flow, TestingSource([1]))
        op.output("out", s, _ProbeSink())
        before = flight.RECORDER.phase_totals.get("teardown", 0.0)
        run_main(flow)
        teardowns.append(
            flight.RECORDER.phase_totals.get("teardown", 0.0) - before
        )
    assert [g["flow_id"] for g in graphs] == ["job0_df", "job1_df"]
    assert all(0.0 < t < 0.1 for t in teardowns), teardowns


# -- crash post-mortems ------------------------------------------------


def test_postmortem_write_unit(monkeypatch, tmp_path):
    monkeypatch.delenv("BYTEWAX_TPU_POSTMORTEM_DIR", raising=False)
    assert flight.write_postmortem(0, 0, "DeviceFault") is None

    monkeypatch.setenv("BYTEWAX_TPU_POSTMORTEM_DIR", str(tmp_path))
    flight.note_phase("host", "pm_df.step", 0.01)
    path = flight.write_postmortem(3, 2, "DeviceFault", "boom")
    assert path is not None and os.path.exists(path)
    assert os.path.basename(path) == "postmortem-3-2.json"
    doc = json.loads(open(path).read())
    assert doc["proc_id"] == 3 and doc["generation"] == 2
    assert doc["cause"] == "DeviceFault" and doc["detail"] == "boom"
    assert "counters" in doc and "tail" in doc
    # The in-flight (unsealed) epoch's attribution is the evidence a
    # sealed record can't carry.
    assert doc["ledger"]["in_flight"]["host"]["pm_df.step"] > 0


def test_postmortem_on_supervised_restart(monkeypatch, tmp_path):
    # A restartable injected fault under the supervisor dumps the
    # flight state before the backoff sleep, named by the failed
    # generation.
    faults.reset()
    pm_dir = tmp_path / "pm"
    db = tmp_path / "db"
    db.mkdir()
    init_db_dir(db, 1)
    monkeypatch.setenv(
        "BYTEWAX_TPU_FAULTS", "snapshot.commit:crash:3:x1"
    )
    monkeypatch.setenv("BYTEWAX_TPU_MAX_RESTARTS", "2")
    monkeypatch.setenv("BYTEWAX_TPU_RESTART_BACKOFF_S", "0.05")
    monkeypatch.setenv("BYTEWAX_TPU_POSTMORTEM_DIR", str(pm_dir))
    try:
        out = []
        flow = Dataflow("pm_df")
        s = op.input(
            "inp", flow, TestingSource(list(range(12)), batch_size=2)
        )
        s = op.map("id", s, lambda x: x)
        op.output("out", s, TestingSink(out))
        run_main(
            flow,
            epoch_interval=ZERO_TD,
            recovery_config=RecoveryConfig(str(db)),
        )
    finally:
        faults.reset()

    path = pm_dir / "postmortem-0-0.json"
    assert path.exists(), list(pm_dir.iterdir() if pm_dir.exists() else [])
    doc = json.loads(path.read_text())
    assert doc["cause"] == "InjectedCrash"
    assert doc["generation"] == 0
    assert "ledger" in doc and "counters" in doc and "tail" in doc


# -- comm contract: the piggyback grew, the frame inventory did not ----


def test_ledger_rides_existing_telemetry_no_new_frames():
    # The cluster ledger exchange rides the existing epoch-close
    # summary (one gsync round) — the sealed record is IN the
    # summary, and the analyzer's frame/send inventories still hold
    # with zero new control-frame kinds.
    rec = flight.FlightRecorder()
    rec.ledger_add("host", "s1", 0.01)
    rec.note_epoch_close(1, 0.002)
    summary = rec.summary(1)
    assert summary["ledger"]["epoch"] == 1
    assert summary["ledger"]["phases"]["host"]["s1"] > 0

    from bytewax_tpu.analysis import analyze_tree
    from bytewax_tpu.analysis.contracts import CONTROL_FRAMES

    assert not any("ledger" in kind for kind in CONTROL_FRAMES)
    diags, _suppressed, _project = analyze_tree()
    assert not diags, [str(d) for d in diags]


# -- the acceptance check: 2-process cluster /status ledger ------------


def test_ledger_cluster_status_piggyback_2proc(tmp_path):
    # In a real 2-process cluster, any process's /status shows BOTH
    # processes' per-epoch phase breakdowns, and each breakdown's
    # close-window phases sum to within 10% of that epoch's measured
    # close duration (floored at scheduler granularity for sub-ms
    # closes).
    flow_py = tmp_path / "ledger_flow.py"
    flow_py.write_text(
        """
import time
import bytewax_tpu.operators as op
from bytewax_tpu.dataflow import Dataflow
from bytewax_tpu.inputs import DynamicSource, StatelessSourcePartition
from bytewax_tpu.outputs import DynamicSink, StatelessSinkPartition


class _Tick(StatelessSourcePartition):
    def __init__(self):
        self._i = 0

    def next_batch(self):
        if self._i >= 40:
            raise StopIteration()
        self._i += 1
        time.sleep(0.1)
        return [("k", 1)]


class TickSource(DynamicSource):
    def build(self, step_id, worker_index, worker_count):
        return _Tick()


class _Null(StatelessSinkPartition):
    def write_batch(self, items):
        pass


class NullSink(DynamicSink):
    def build(self, step_id, worker_index, worker_count):
        return _Null()


flow = Dataflow("ledger_cluster_df")
s = op.input("inp", flow, TickSource())
s = op.stateful_map("sum", s, lambda st, v: ((st or 0) + v, (st or 0) + v))
op.output("out", s, NullSink())
"""
    )
    import socket

    ports = []
    for _ in range(2):
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        ports.append(s.getsockname()[1])
        s.close()
    db = tmp_path / "db"
    db.mkdir()
    init_db_dir(db, 1)

    env = dict(os.environ)
    env["PYTHONPATH"] = "/root/repo" + os.pathsep + env.get(
        "PYTHONPATH", ""
    )
    env["BYTEWAX_TPU_PLATFORM"] = "cpu"
    env["BYTEWAX_TPU_ACCEL"] = "0"
    env["BYTEWAX_DATAFLOW_API_ENABLED"] = "1"
    env["BYTEWAX_DATAFLOW_API_PORT"] = "13051"
    env["BYTEWAX_ADDRESSES"] = ";".join(
        f"127.0.0.1:{p}" for p in ports
    )
    env["BYTEWAX_TPU_DIAL_TIMEOUT_S"] = "120"
    procs = []
    for proc_id in range(2):
        penv = dict(env)
        penv["BYTEWAX_PROCESS_ID"] = str(proc_id)
        procs.append(
            subprocess.Popen(
                [
                    sys.executable,
                    "-m",
                    "bytewax_tpu.run",
                    f"{flow_py}:flow",
                    "-s",
                    "0.3",
                    "-b",
                    "30",
                    "-r",
                    str(db),
                ],
                env=penv,
                cwd=tmp_path,
                stdout=subprocess.PIPE,
                stderr=subprocess.PIPE,
            )
        )
    status = None
    try:
        deadline = time.monotonic() + 150
        while time.monotonic() < deadline:
            try:
                with urllib.request.urlopen(
                    "http://127.0.0.1:13051/status", timeout=2
                ) as resp:
                    got = json.loads(resp.read())
            except OSError:
                time.sleep(0.2)
                continue
            cluster = got.get("cluster", {})
            # The summary ships the PREVIOUS epoch's sealed record,
            # so wait for a close where both processes have one.
            if len(cluster) == 2 and all(
                isinstance(s.get("ledger"), dict)
                and s["ledger"].get("close")
                for s in cluster.values()
            ):
                status = got
                break
            time.sleep(0.2)
    finally:
        errs = []
        for proc in procs:
            try:
                _out, err = proc.communicate(timeout=120)
            except subprocess.TimeoutExpired:
                proc.kill()
                _out, err = proc.communicate()
            errs.append(err)
    for proc, err in zip(procs, errs):
        assert proc.returncode == 0, err[-2000:].decode(errors="replace")
    assert status is not None, "cluster ledgers never reached proc 0"
    assert set(status["cluster"]) == {"0", "1"}
    for pid in ("0", "1"):
        record = status["cluster"][pid]["ledger"]
        assert isinstance(record["epoch"], int)
        assert record["phases"], record
        # The acceptance bound: close-window phase sum within 10% of
        # the measured close duration (absolute floor for clock
        # granularity on sub-ms closes).
        close_sum = sum(record["close"].values())
        close_s = record["close_s"]
        assert abs(close_sum - close_s) <= max(
            0.10 * close_s, 0.004
        ), record
        # Full-epoch main-thread phases stay within the epoch wall.
        main_sum = _phase_sum(_MAIN_PHASES_ONLY(record["phases"]))
        assert main_sum <= record["wall_s"] * 1.10 + 0.005, record
    # Local /status carries the same ledger section for this process.
    assert "ledger" in status
    assert "phase_totals" in status["ledger"]
