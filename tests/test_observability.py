"""Metrics, webserver, tracing, flight-recorder tests (model:
SURVEY.md §5.5)."""

import json
import os
import subprocess
import sys
import time
import urllib.request

import pytest

import bytewax_tpu.operators as op
from bytewax_tpu.dataflow import Dataflow
from bytewax_tpu.testing import TestingSink, TestingSource, run_main


def test_item_counters_increment():
    from prometheus_client import REGISTRY

    out = []
    flow = Dataflow("metrics_df")
    s = op.input("inp", flow, TestingSource([1, 2, 3]))
    s = op.map("double", s, lambda x: x * 2)
    op.output("out", s, TestingSink(out))
    run_main(flow)

    val = REGISTRY.get_sample_value(
        "bytewax_item_inp_count_total",
        {"step_id": "metrics_df.double.flat_map_batch", "worker_index": "0"},
    )
    assert val is not None and val >= 3


def test_dataflow_api_server(monkeypatch, tmp_path):
    monkeypatch.setenv("BYTEWAX_DATAFLOW_API_ENABLED", "1")
    monkeypatch.setenv("BYTEWAX_DATAFLOW_API_PORT", "13031")
    monkeypatch.chdir(tmp_path)

    captured = {}

    class _ProbeSinkPartition:
        def write_batch(self, items):
            # Hit the server from inside the running dataflow.
            if "flow" not in captured:
                with urllib.request.urlopen(
                    "http://127.0.0.1:13031/dataflow", timeout=5
                ) as resp:
                    captured["flow"] = json.loads(resp.read())
                with urllib.request.urlopen(
                    "http://127.0.0.1:13031/metrics", timeout=5
                ) as resp:
                    captured["metrics"] = resp.read().decode()

        def close(self):
            pass

    from bytewax_tpu.outputs import DynamicSink

    class _ProbeSink(DynamicSink):
        def build(self, step_id, worker_index, worker_count):
            return _ProbeSinkPartition()

    flow = Dataflow("api_df")
    s = op.input("inp", flow, TestingSource([1]))
    op.output("out", s, _ProbeSink())
    run_main(flow)

    assert captured["flow"]["flow_id"] == "api_df"
    assert "bytewax_item_inp_count" in captured["metrics"]
    # Graph also dumped to disk at startup.
    assert (tmp_path / "dataflow.json").exists()


def _windowed_accel_flow(n_rows=200):
    """A columnar event-time count_window flow that exercises the
    accelerated window step (device scatter-combine + transfers)."""
    from datetime import datetime, timedelta, timezone

    import numpy as np

    import bytewax_tpu.operators.windowing as w
    from bytewax_tpu.engine.arrays import ArrayBatch
    from bytewax_tpu.models.brc import ArrayBatchSource
    from bytewax_tpu.operators.windowing import EventClock, TumblingWindower

    align = datetime(2022, 1, 1, tzinfo=timezone.utc)
    base = np.datetime64(align.replace(tzinfo=None), "us")
    batches = [
        ArrayBatch(
            {
                "key_id": (np.arange(n_rows) % 2).astype(np.int32),
                "ts": base + (np.arange(n_rows) // 10).astype(
                    "timedelta64[s]"
                ),
            },
            key_vocab=np.array(["0", "1"]),
        )
    ]
    clock = EventClock(
        ts_getter=lambda x: x, wait_for_system_duration=timedelta(0)
    )
    windower = TumblingWindower(
        align_to=align, length=timedelta(seconds=10)
    )
    out = []
    flow = Dataflow("flight_df")
    s = op.input("in", flow, ArrayBatchSource(batches))
    wo = w.count_window("count", s, clock, windower, key=lambda x: x)
    op.output("out", wo.down, TestingSink(out))
    return flow, out


def test_flight_recorder_metric_families(monkeypatch):
    # The six new engine families appear in /metrics exposition, and
    # the ones a single-process accelerated-window run can exercise
    # have nonzero samples (gsync/barrier/comm need a cluster; their
    # families must still be present).
    from datetime import timedelta

    from prometheus_client import REGISTRY

    from bytewax_tpu._metrics import generate_python_metrics
    from bytewax_tpu.engine import flight

    monkeypatch.setenv("BYTEWAX_FLIGHT_RECORDER", "1")
    monkeypatch.setenv("BYTEWAX_TPU_ACCEL", "1")
    flow, out = _windowed_accel_flow()
    run_main(flow, epoch_interval=timedelta(0))
    assert out  # windows closed on device

    text = generate_python_metrics()
    for family in (
        "bytewax_epoch_close_duration_seconds",
        "bytewax_barrier_wait_seconds",
        "bytewax_gsync_round_count",
        "bytewax_xla_compile_count",
        "bytewax_xla_compile_seconds",
        "bytewax_device_transfer_bytes",
        "bytewax_comm_frames",
    ):
        assert family in text, f"{family} missing from exposition"

    assert (
        REGISTRY.get_sample_value("bytewax_epoch_close_duration_seconds_count")
        >= 1
    )
    assert (
        REGISTRY.get_sample_value(
            "bytewax_device_transfer_bytes_total", {"direction": "h2d"}
        )
        > 0
    )
    assert (
        REGISTRY.get_sample_value(
            "bytewax_device_transfer_bytes_total", {"direction": "d2h"}
        )
        > 0
    )
    # The jax.monitoring listener counts compiles process-wide; at
    # least the device window fold compiled at some point.
    assert (
        REGISTRY.get_sample_value("bytewax_xla_compile_count_total") >= 1
    )
    # Ring + percentile buffer recorded (enabled via env).
    rec = flight.RECORDER
    assert rec.counters.get("epoch_close_count", 0) >= 1
    assert rec.epoch_close_percentiles() is not None
    kinds = {e["kind"] for e in rec.tail()}
    assert "epoch_close" in kinds
    assert "device_dispatch" in kinds


def test_status_endpoint(entry_point, monkeypatch, tmp_path):
    # GET /status returns a valid JSON engine snapshot under all 3
    # entry points.
    monkeypatch.setenv("BYTEWAX_DATAFLOW_API_ENABLED", "1")
    monkeypatch.setenv("BYTEWAX_DATAFLOW_API_PORT", "13033")
    monkeypatch.chdir(tmp_path)

    captured = {}

    class _ProbeSinkPartition:
        def write_batch(self, items):
            if "status" not in captured:
                with urllib.request.urlopen(
                    "http://127.0.0.1:13033/status", timeout=5
                ) as resp:
                    captured["status"] = json.loads(resp.read())

        def close(self):
            pass

    from bytewax_tpu.outputs import DynamicSink

    class _ProbeSink(DynamicSink):
        def build(self, step_id, worker_index, worker_count):
            return _ProbeSinkPartition()

    flow = Dataflow("status_df")
    s = op.input("inp", flow, TestingSource([1, 2, 3]))
    op.output("out", s, _ProbeSink())
    entry_point(flow)

    status = captured["status"]
    assert status["flow_id"] == "status_df"
    assert status["proc_id"] == 0
    assert isinstance(status["epoch"], int)
    assert "status_df.out" in status["queue_depths"]
    assert status["recorder"]["enabled"] is True
    assert isinstance(status["recorder"]["counters"], dict)
    assert isinstance(status["cluster"], dict)
    # The rescale recommendation signal (docs/recovery.md) is always
    # present for external autoscalers to poll.
    hint = status["rescale_hint"]
    assert hint["advice"] in ("grow", "shrink", "hold")
    assert isinstance(hint["reasons"], list)
    assert hint["signals"]["worker_count"] == status["worker_count"]
    # The epoch-ledger section (docs/observability.md) is always
    # present; its records fill in as epochs seal.
    ledger = status["ledger"]
    assert set(ledger) >= {
        "last",
        "recent",
        "phase_totals",
        "phase_fractions",
        "lag",
        "collective_lane",
    }
    assert isinstance(ledger["recent"], list)
    assert isinstance(ledger["phase_totals"], dict)
    # The collective exchange-lane window is always present; single
    # process runs have no global tier, so it pins to None (never a
    # missing key).
    assert ledger["collective_lane"] is None
    # The wire section always carries the per-kind pending breakdown
    # and the vocab-session view; in-process runs have no accumulator
    # or comm layer, so both pin to None (never missing keys).
    wire = status["wire"]
    assert set(wire) >= {"mode", "pending_frames", "pending", "session"}
    assert wire["pending"] is None
    assert wire["session"] is None


def test_collective_lane_status_unit_pin():
    # Satellite pin (HBM-resident-aggregate PR): the exchange-lane
    # window /status and /graph expose.  lane_status() reports sealed
    # rounds in flight against the configured depth bound — the lane
    # is built with depth = BYTEWAX_TPU_GSYNC_DEPTH + 1 (push's
    # make_room retires round N-depth before round N seals), so the
    # reported "depth" is the knob value — and pins to None when the
    # lock-step tier runs (no lane constructed).
    import threading

    from bytewax_tpu.engine.pipeline import DevicePipeline
    from bytewax_tpu.engine.sharded_state import GlobalAggState

    st = GlobalAggState.__new__(GlobalAggState)
    st._lane = None
    assert st.lane_status() is None

    gate = threading.Event()
    lane = DevicePipeline("gsync", depth=3, phase="collective_lane")
    st._lane = lane
    try:
        assert st.lane_status() == {"in_flight": 0, "depth": 2}
        lane.push(lambda: gate.wait(10), lambda _res: None)
        assert st.lane_status()["in_flight"] == 1
        gate.set()
        lane.flush()
        assert st.lane_status() == {"in_flight": 0, "depth": 2}
    finally:
        gate.set()
        lane.flush()
        lane.shutdown()


def test_route_accumulator_pending_status_covers_both_kinds():
    # Satellite audit (PR-15 generalized accumulator): the /status
    # pending breakdown must count coalesced ship_deliver (peer, op,
    # port, lane) buckets alongside the PR-12 route (peer, stream,
    # lane) buckets.
    from bytewax_tpu.engine.wire import RouteAccumulator

    acc = RouteAccumulator()
    assert acc.pending_status() == {
        "route": {"buckets": 0, "frames": 0},
        "deliver": {"buckets": 0, "frames": 0},
    }
    acc.add(1, "df.split", 0, [("k", 1)])
    acc.add(1, "df.split", 0, [("k", 2)])  # same bucket, new run or merge
    acc.add(2, "df.split", 0, [("k", 3)])
    acc.add_deliver(1, 4, "up", 0, [("k", 4)])
    st = acc.pending_status()
    assert st["route"]["buckets"] == 2
    assert st["route"]["frames"] >= 2
    assert st["deliver"]["buckets"] == 1
    assert st["deliver"]["frames"] >= 1
    # The breakdown and the flat count agree.
    assert (
        st["route"]["frames"] + st["deliver"]["frames"]
        == acc.pending_frames()
    )
    # Drain via the flush protocol: everything returns to zero.
    while acc.peek() is not None:
        acc.pop()
    assert acc.pending_status() == {
        "route": {"buckets": 0, "frames": 0},
        "deliver": {"buckets": 0, "frames": 0},
    }


def test_wire_session_status_view():
    from bytewax_tpu.engine.wire import WireSession

    st = WireSession().status()
    assert set(st) == {"generation", "tx_streams", "rx_streams"}
    assert all(isinstance(v, int) for v in st.values())
    assert st["tx_streams"] == 0 and st["rx_streams"] == 0


def test_json_safe_round_trip():
    # Satellite: every /status // /graph payload is JSON-safe by
    # construction — the shared sweep converts numpy scalars/arrays
    # and datetime64 to native types, and non-finite floats to null
    # (a NaN gauge renders the whole document invalid cluster-wide).
    import numpy as np

    from bytewax_tpu.engine.flight import _json_safe

    doc = {
        "i": np.int64(7),
        "f": np.float32(1.5),
        "ts": np.datetime64("2024-01-02T03:04:05", "us"),
        "arr": np.arange(3, dtype=np.int32),
        "nested": {np.int64(1): [np.float64(2.5), (np.int16(3),)]},
        "nan": float("nan"),
        "inf": np.float64("inf"),
        "b": b"bytes",
    }
    text = json.dumps(_json_safe(doc))  # must not raise
    back = json.loads(text)
    assert back["i"] == 7 and back["f"] == 1.5
    assert back["ts"].startswith("2024-01-02T03:04:05")
    assert back["arr"] == [0, 1, 2]
    assert back["nested"]["1"] == [2.5, [3]]
    assert back["nan"] is None and back["inf"] is None
    assert back["b"] == "bytes"


def test_status_cluster_gsync_piggyback(tmp_path):
    # In a real 2-process cluster, each process's compact telemetry
    # summary rides a gsync round at epoch close; process 0's /status
    # then shows both processes.
    flow_py = tmp_path / "status_flow.py"
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


flow = Dataflow("status_cluster_df")
s = op.input("inp", flow, TickSource())
op.output("out", s, NullSink())
"""
    )
    import socket

    # Allocate two mesh ports up front (bind-then-close; the window
    # is tiny in an isolated test and avoids the SO_REUSEPORT holder
    # machinery of `python -m bytewax_tpu.testing`).
    ports = []
    for _ in range(2):
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        ports.append(s.getsockname()[1])
        s.close()
    addresses = ";".join(f"127.0.0.1:{p}" for p in ports)

    env = dict(os.environ)
    env["PYTHONPATH"] = "/root/repo" + os.pathsep + env.get("PYTHONPATH", "")
    env["BYTEWAX_TPU_PLATFORM"] = "cpu"
    env["BYTEWAX_TPU_ACCEL"] = "0"
    env["BYTEWAX_DATAFLOW_API_ENABLED"] = "1"
    env["BYTEWAX_DATAFLOW_API_PORT"] = "13045"
    env["BYTEWAX_ADDRESSES"] = addresses
    # A loaded CI box can take >30s just to start both interpreters;
    # don't let the mesh handshake give up before they're up.
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
                    "http://127.0.0.1:13045/status", timeout=2
                ) as resp:
                    got = json.loads(resp.read())
            except OSError:
                time.sleep(0.2)
                continue
            cluster = got.get("cluster", {})
            # The summary is snapshotted before its own sync round
            # completes, so wait for a close where every process has
            # already finished at least one earlier gsync round.
            if len(cluster) == 2 and all(
                s["counters"].get("gsync_round_count", 0) >= 1
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
    assert status is not None, "cluster summary never reached proc 0"
    assert set(status["cluster"]) == {"0", "1"}
    for pid in ("0", "1"):
        summary = status["cluster"][pid]
        assert isinstance(summary["epoch"], int)
        # The piggyback itself runs over gsync: every process must
        # have completed at least one round.
        assert summary["counters"]["gsync_round_count"] >= 1
    # Mesh traffic was metered per peer on proc 0.
    assert status["recorder"]["counters"]["comm_frames_tx"] >= 1
    assert status["recorder"]["counters"]["comm_frames_rx"] >= 1
    # Clustered wire section: the per-kind pending breakdown covers
    # BOTH accumulator bucket kinds (route AND the generalized
    # coalesced ship_deliver buckets), and the vocab-session view is
    # live — not just the PR-12 route count.
    wire = status["wire"]
    assert set(wire["pending"]) == {"route", "deliver"}
    for kind in ("route", "deliver"):
        assert set(wire["pending"][kind]) == {"buckets", "frames"}
        assert wire["pending"][kind]["buckets"] >= 0
    assert isinstance(wire["session"]["generation"], int)
    assert wire["session"]["tx_streams"] >= 0
    assert wire["session"]["rx_streams"] >= 0


def test_status_cluster_divergent_env_does_not_hang(tmp_path):
    # Only process 0 enables the API server: the startup agreement
    # round must disable the telemetry piggyback cluster-wide (not
    # leave proc 0 blocking in a sync round its peer never enters).
    import socket

    flow_py = tmp_path / "div_flow.py"
    flow_py.write_text(
        """
import bytewax_tpu.operators as op
from bytewax_tpu.dataflow import Dataflow
from bytewax_tpu.testing import TestingSink, TestingSource

flow = Dataflow("div_df")
s = op.input("inp", flow, TestingSource(list(range(20))))
op.output("out", s, TestingSink([]))
"""
    )
    ports = []
    for _ in range(2):
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        ports.append(s.getsockname()[1])
        s.close()
    base = dict(os.environ)
    base["PYTHONPATH"] = "/root/repo" + os.pathsep + base.get("PYTHONPATH", "")
    base["BYTEWAX_TPU_PLATFORM"] = "cpu"
    base["BYTEWAX_TPU_ACCEL"] = "0"
    base["BYTEWAX_ADDRESSES"] = ";".join(
        f"127.0.0.1:{p}" for p in ports
    )
    base["BYTEWAX_TPU_DIAL_TIMEOUT_S"] = "120"
    base.pop("BYTEWAX_DATAFLOW_API_ENABLED", None)
    base.pop("BYTEWAX_FLIGHT_RECORDER", None)
    procs = []
    for proc_id in range(2):
        penv = dict(base)
        penv["BYTEWAX_PROCESS_ID"] = str(proc_id)
        if proc_id == 0:
            penv["BYTEWAX_DATAFLOW_API_ENABLED"] = "1"
            penv["BYTEWAX_DATAFLOW_API_PORT"] = "13047"
        procs.append(
            subprocess.Popen(
                [
                    sys.executable,
                    "-m",
                    "bytewax_tpu.run",
                    f"{flow_py}:flow",
                    "-s",
                    "0.2",
                ],
                env=penv,
                cwd=tmp_path,
                stdout=subprocess.PIPE,
                stderr=subprocess.PIPE,
            )
        )
    for proc in procs:
        try:
            _out, err = proc.communicate(timeout=150)
        except subprocess.TimeoutExpired:
            proc.kill()
            _out, err = proc.communicate()
            raise AssertionError(
                "cluster hung with divergent telemetry env: "
                + err[-2000:].decode(errors="replace")
            )
        assert proc.returncode == 0, err[-2000:].decode(errors="replace")


def test_setup_tracing_local():
    from bytewax_tpu.tracing import setup_tracing, span

    guard = setup_tracing(None, "DEBUG")
    with span("test_span", step_id="x"):
        pass
    guard.shutdown()


def test_map_dict_value():
    from bytewax_tpu.operators.helpers import map_dict_value

    out = []
    flow = Dataflow("helpers_df")
    s = op.input("inp", flow, TestingSource([{"name": "ada", "id": 1}]))
    s = op.map("norm", s, map_dict_value("name", str.upper))
    op.output("out", s, TestingSink(out))
    run_main(flow)
    assert out == [{"name": "ADA", "id": 1}]


def test_duration_histograms_observed(monkeypatch):
    # with_timer! parity (reference src/metrics/mod.rs:8-16): every
    # user-code call site records a *_duration_seconds histogram.
    from datetime import datetime, timedelta, timezone

    from prometheus_client import REGISTRY

    import bytewax_tpu.operators.windowing as w
    from bytewax_tpu.connectors.files import FileSink
    from bytewax_tpu.operators.windowing import EventClock, TumblingWindower

    monkeypatch.setenv("BYTEWAX_TPU_ACCEL", "0")
    align = datetime(2022, 1, 1, tzinfo=timezone.utc)
    inp = [align + timedelta(seconds=i) for i in range(50)]
    clock = EventClock(
        ts_getter=lambda x: x, wait_for_system_duration=timedelta(0)
    )
    windower = TumblingWindower(length=timedelta(seconds=10), align_to=align)
    out = []
    flow = Dataflow("hist_df")
    s = op.input("inp", flow, TestingSource(inp, batch_size=10))
    s = op.map("fmt", s, lambda x: x)
    wo = w.count_window("count", s, clock, windower, key=lambda _x: "k")
    op.output("out", wo.down, TestingSink(out))
    run_main(flow, epoch_interval=timedelta(0))
    assert out  # windows closed

    def count_of(name, step):
        return REGISTRY.get_sample_value(
            f"bytewax_{name}_duration_seconds_count",
            {"step_id": step, "worker_index": "0"},
        )

    assert count_of("inp_part_next_batch", "hist_df.inp") >= 5
    assert count_of("flat_map_batch", "hist_df.fmt.flat_map_batch") >= 5
    assert (
        count_of(
            "stateful_batch_on_batch",
            "hist_df.count.fold_window.window.stateful_batch",
        )
        >= 1
    )
    assert (
        count_of(
            "stateful_batch_on_eof",
            "hist_df.count.fold_window.window.stateful_batch",
        )
        >= 1
    )
    assert (
        count_of(
            "snapshot", "hist_df.count.fold_window.window.stateful_batch"
        )
        >= 1
    )
    assert count_of("out_part_write_batch", "hist_df.out") >= 1
    # And the bucket layout matches the reference (0.0005 .. 10).
    from bytewax_tpu._metrics import DURATION_BUCKETS

    assert DURATION_BUCKETS[0] == 0.0005 and DURATION_BUCKETS[-1] == 10.0


def test_per_operator_spans_at_debug(caplog):
    # With DEBUG tracing on, every operator activation emits a span
    # (the reference's debug_span!("operator") analog).
    import logging

    from bytewax_tpu.tracing import setup_tracing

    guard = setup_tracing(None, "DEBUG")
    try:
        with caplog.at_level(logging.DEBUG, logger="bytewax_tpu"):
            out = []
            flow = Dataflow("span_df")
            s = op.input("inp", flow, TestingSource([1, 2]))
            s = op.map("double", s, lambda x: x * 2)
            op.output("out", s, TestingSink(out))
            run_main(flow)
        assert out == [2, 4]
        spans = [
            r.getMessage()
            for r in caplog.records
            if "span operator" in r.getMessage()
        ]
        assert spans, "no operator spans emitted at DEBUG"
        joined = " ".join(spans)
        assert "span_df.double.flat_map_batch" in joined
        assert "span_df.out" in joined
    finally:
        guard.shutdown()
        setup_tracing(None, "ERROR")


# -- the API plane stops on a wake-up ----------------------------------


def _api_flow(flow_id="stop_df"):
    flow = Dataflow(flow_id)
    s = op.input("inp", flow, TestingSource([1]))
    op.output("out", s, TestingSink([]))
    return flow


def _get_json(port, path):
    with urllib.request.urlopen(
        f"http://127.0.0.1:{port}{path}", timeout=5
    ) as resp:
        return json.loads(resp.read())


def _api_env(monkeypatch, tmp_path, port):
    monkeypatch.setenv("BYTEWAX_DATAFLOW_API_ENABLED", "1")
    monkeypatch.setenv("BYTEWAX_DATAFLOW_API_PORT", str(port))
    monkeypatch.chdir(tmp_path)


@pytest.mark.parametrize("idle_s", [0.05, 0.26, 0.45])
def test_api_server_stops_on_a_wake_up(idle_s, monkeypatch, tmp_path):
    # The serving loop waits for a connection or for shutdown()'s
    # wake-up, never for a clock: a stop costs a thread hand-over
    # wherever it falls in what was serve_forever's half-second poll
    # (0.45 / 0.24 / 0.05 s left at these three), and the port is
    # free for the next generation the moment shutdown() returns.
    from bytewax_tpu.engine.webserver import maybe_start_server

    _api_env(monkeypatch, tmp_path, 13064)
    flow = _api_flow()
    took = []
    for _ in range(3):  # a loaded machine may preempt one hand-over
        srv = maybe_start_server(flow, status_fn=lambda: {"gen": 0})
        assert srv is not None and srv.port == 13064
        time.sleep(idle_s)
        t0 = time.perf_counter()
        srv.shutdown()
        took.append(time.perf_counter() - t0)
        # No bind degraded to "continuing without": the second
        # generation holds the same port at once and answers.
        nxt = maybe_start_server(flow, status_fn=lambda: {"gen": 1})
        try:
            assert nxt is not None and nxt.port == 13064
            assert _get_json(13064, "/status") == {"gen": 1}
        finally:
            nxt.shutdown()
        if took[-1] < 0.05:
            break
    assert min(took) < 0.05, took


def test_api_server_shutdown_is_idempotent_and_safe_unserved(
    monkeypatch, tmp_path
):
    # The start-up unwind stops a plane whose loop may not have
    # selected yet and that no request ever reached; teardown may
    # follow it with a second shutdown().
    import socket

    from bytewax_tpu.engine.webserver import maybe_start_server

    _api_env(monkeypatch, tmp_path, 13065)
    srv = maybe_start_server(_api_flow())
    assert srv is not None
    thread = srv._thread
    srv.shutdown()
    srv.shutdown()
    assert not thread.is_alive()
    # The listening socket is closed, not merely idle.
    with socket.socket() as probe:
        probe.bind(("127.0.0.1", 13065))


def test_api_server_serves_while_another_request_blocks(
    monkeypatch, tmp_path
):
    # A thread a request, beside a select with no time-out: a slow
    # /status neither stalls /healthz nor the stop, and the loop
    # answers after sitting idle in its select.
    import threading

    from bytewax_tpu.engine.webserver import maybe_start_server

    _api_env(monkeypatch, tmp_path, 13066)
    entered, release = threading.Event(), threading.Event()

    def slow_status():
        entered.set()
        release.wait(10)
        return {"slow": True}

    srv = maybe_start_server(
        _api_flow(), status_fn=slow_status, health_fn=lambda: {"ready": True}
    )
    assert srv is not None
    got = {}
    slow = threading.Thread(
        target=lambda: got.update(_get_json(13066, "/status"))
    )
    try:
        time.sleep(0.2)
        slow.start()
        assert entered.wait(5)
        assert _get_json(13066, "/healthz") == {"live": True, "ready": True}
        assert _get_json(13066, "/dataflow")["flow_id"] == "stop_df"
        # A request in flight does not hold the stop back (request
        # threads are daemons, as they were).
        t0 = time.perf_counter()
        srv.shutdown()
        assert time.perf_counter() - t0 < 1.0
    finally:
        release.set()
        slow.join(5)
        srv.shutdown()
    assert got == {"slow": True}
