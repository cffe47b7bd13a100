"""The span primitive of the epoch ledger (``flight.span``): exclusive
time on each lane, counts at the same place, live totals, unchanged
fraction buckets, and the work spans in the profiler's trace."""

import glob
import os
import threading
import time
from datetime import datetime, timedelta, timezone

import numpy as np
import pytest

import bytewax_tpu.operators as op
import bytewax_tpu.operators.windowing as w
from bytewax_tpu import xla
from bytewax_tpu.dataflow import Dataflow
from bytewax_tpu.engine import flight
from bytewax_tpu.engine.arrays import ArrayBatch
from bytewax_tpu.models.brc import ArrayBatchSource, BrcFileSource
from bytewax_tpu.operators.windowing import EventClock, TumblingWindower
from bytewax_tpu.testing import TestingSink, run_main

ZERO_TD = timedelta(seconds=0)
REC = flight.RECORDER


@pytest.fixture
def ledger():
    """A clean epoch ledger; gives a function reading what the ledger
    and the counters gained since."""
    REC._ledger = {}
    REC._ledger_pre_close = None
    assert REC._phase_stack == []
    totals0 = dict(REC.phase_totals)
    counters0 = dict(REC.counters)

    def gained():
        return (
            {
                k: v - totals0.get(k, 0.0)
                for k, v in REC.phase_totals.items()
                if v != totals0.get(k, 0.0)
            },
            {
                k: v - counters0.get(k, 0)
                for k, v in REC.counters.items()
                if v != counters0.get(k, 0)
            },
        )

    return gained


def _seconds(phase, step="t"):
    return REC._ledger.get((phase, step), 0.0)


# -- exclusive time ----------------------------------------------------------


def test_exclusive_time_over_two_levels_on_the_main_thread(ledger):
    t0 = time.monotonic()
    with flight.span("host", "t"):
        time.sleep(0.01)
        with flight.span("fetch", rows=7):
            time.sleep(0.02)
            with flight.span("close_emit", "t", rows=3):
                time.sleep(0.03)
        time.sleep(0.01)
    whole = time.monotonic() - t0
    host, fetch, emit = _seconds("host"), _seconds("fetch"), _seconds("close_emit")
    assert emit >= 0.03 and 0.02 <= fetch < 0.03 + 0.015 and 0.02 <= host < 0.05
    # Disjoint: the three add up to the outermost interval.
    assert host + fetch + emit == pytest.approx(whole, abs=0.002)
    # A span that names no step is its parent's.
    assert ("fetch", "*") not in REC._ledger
    totals, counters = ledger()
    assert counters["fetch_spans"] == 1 and counters["fetch_rows"] == 7
    assert counters["close_emit_rows"] == 3 and counters["host_spans"] == 1
    assert "host_rows" not in counters
    assert REC._phase_stack == []


def test_lane_spans_nest_among_themselves_and_charge_no_main_frame(ledger):
    def task():
        time.sleep(0.01)
        with flight.span("prep", rows=5):
            time.sleep(0.02)
            with flight.span("encode"):
                time.sleep(0.02)
        with flight.span("dispatch"):
            time.sleep(0.01)
        return "done"

    box = {}
    worker = threading.Thread(
        target=lambda: box.update(out=flight.lane_run("device", "t", task))
    )
    with flight.span("host", "t"):
        worker.start()
        worker.join()
        # The worker touched no shared recorder state.
        assert not any(p.startswith("device") for p, _s in REC._ledger)
        assert "prep_spans" not in ledger()[1]
        spans, result = box["out"]
        flight.lane_fold(spans)
    assert result == "done"
    # Under the lane's phase and their own; the lane's own line is its
    # self time; exclusive over two levels on the lane too.
    prep, encode = _seconds("device/prep"), _seconds("device/encode")
    dispatch, self_s = _seconds("device/dispatch"), _seconds("device")
    assert encode >= 0.02 and 0.02 <= prep < 0.035 and dispatch >= 0.01
    assert 0.01 <= self_s < 0.025
    assert ("prep", "t") not in REC._ledger
    # Children plus the lane's self time are the task's gross.
    gross = [s for s in spans if s[0] == "device"][0][3]
    assert prep + encode + dispatch + self_s == pytest.approx(gross, abs=1e-6)
    # No main frame was charged: the main span kept all its time.
    assert _seconds("host") >= gross
    totals, counters = ledger()
    assert counters["prep_rows"] == 5 and counters["device_spans"] == 1
    assert REC._phase_stack == []


def test_inline_lane_records_under_the_lane_and_charges_the_enclosing_frame(ledger):
    def task():
        with flight.span("prep"):
            time.sleep(0.02)
        time.sleep(0.01)

    with flight.span("host", "t"):
        spans, _ = flight.lane_run("device", "t", task, inline=True)
    assert spans is None  # recorded at once
    assert _seconds("device/prep") >= 0.02 and _seconds("device") >= 0.01
    # On the caller's thread: the lane's time comes out of the host's.
    assert _seconds("host") < 0.01
    assert getattr(flight._tls, "lane", None) is None


def test_pipeline_worker_time_is_children_plus_self():
    from bytewax_tpu.engine.pipeline import DevicePipeline

    REC._ledger = {}
    pipe = DevicePipeline("t", depth=2)
    done = []

    def task():
        with flight.span("prep"):
            time.sleep(0.01)
        with flight.span("h2d"):
            time.sleep(0.01)
        return 1

    t0 = time.monotonic()
    pipe.push(task, done.append)
    pipe.flush()
    pipe.shutdown()
    wall = time.monotonic() - t0
    assert done == [1]
    lane = sum(s for (p, _st), s in REC._ledger.items() if p.split("/")[0] == "device")
    assert 0.02 <= lane <= wall
    assert _seconds("device/prep") >= 0.01 and _seconds("device/h2d") >= 0.01
    assert ("readback", "t") in REC._ledger


def test_begin_and_end_do_nothing_the_second_time(ledger):
    sp = flight.span("startup").begin()
    sp.begin()
    assert len(REC._phase_stack) == 1
    # Run start keeps the open span's frame, and the first epoch's
    # wall clock starts where the span did.
    REC.activate(False)
    assert len(REC._phase_stack) == 1
    assert REC._epoch_t0 == REC._phase_stack[0].t0
    with flight.span("encode"):
        time.sleep(0.005)
    sp.end()
    sp.end()
    assert REC._phase_stack == []
    totals, counters = ledger()
    assert counters["startup_spans"] == 1 and totals["encode"] >= 0.005
    never = flight.span("teardown")
    never.end()  # never begun: nothing recorded
    assert "teardown_spans" not in ledger()[1]


@pytest.mark.parametrize("on_lane", [False, True])
def test_a_dropped_span_records_nothing_and_its_children_still_come_out(ledger, on_lane):
    """``drop``: as if the span had never been opened.  Its own time
    stays the enclosing span's; what a child took inside it is still
    taken out of that one."""

    def work():
        with flight.span("ingest", "t"):
            read = flight.span("read", "t", rows=9).begin()
            time.sleep(0.01)
            with flight.span("parse", rows=4):
                time.sleep(0.02)
            read.drop()
            read.drop()
            read.end()  # dropped: nothing left to end

    prefix = ""
    if on_lane:
        flight.lane_run("device", "t", work, inline=True)
        prefix = "device/"
    else:
        work()
    totals, counters = ledger()
    assert "read_spans" not in counters and "read_rows" not in counters
    assert prefix + "read" not in totals
    assert totals[prefix + "parse"] >= 0.02 and counters["parse_rows"] == 4
    assert 0.01 <= totals[prefix + "ingest"] < 0.02
    assert REC._phase_stack == []
    unbegun = flight.span("read")
    unbegun.drop()
    assert "read_spans" not in ledger()[1]


def test_a_span_that_raises_still_ends(ledger):
    with pytest.raises(ValueError):
        with flight.span("host", "t"):
            with flight.span("prep"):
                raise ValueError("x")
    assert REC._phase_stack == []
    assert ledger()[1]["prep_spans"] == 1


# -- totals and buckets ------------------------------------------------------


def test_phase_totals_move_before_an_epoch_seals(ledger):
    sealed = len(REC.ledgers())
    with flight.span("host", "t"):
        time.sleep(0.005)
    flight.note_phase("flush", "t", 0.25)
    totals, _ = ledger()
    assert totals["host"] >= 0.005 and totals["flush"] == pytest.approx(0.25)
    assert len(REC.ledgers()) == sealed
    # Sealing moves them no further.
    before = dict(REC.phase_totals)
    REC.note_epoch_close(99991, 0.0)
    assert dict(REC.phase_totals) == before


def test_ledger_fractions_give_the_same_buckets_with_and_without_children():
    lump = {"host": 4.0, "ingest": 2.0, "readback": 1.0, "device": 6.0, "flush": 3.0}
    split = {
        "host": 1.0, "watermark": 1.5, "encode": 0.5, "emit": 0.6, "sink": 0.4,
        "ingest": 0.5, "parse": 1.5,
        "readback": 1.0,
        "device": 0.5, "device/prep": 2.5, "device/h2d": 0.5,
        "device/dispatch": 0.5, "device/close_scan": 0.2, "device/fetch": 0.8,
        "device/close_emit": 0.9, "device/encode": 0.1,
        "flush": 3.0,
        "startup": 50.0, "teardown": 50.0,  # in no bucket
    }
    assert flight.ledger_fractions(split) == flight.ledger_fractions(lump)
    fr = flight.ledger_fractions(
        {"collective_lane/fetch": 1.0, "snapshot_lane/prep": 1.0, "host": 2.0}
    )
    assert fr["gsync"] == 0.25 and fr["snapshot"] == 0.25 and fr["host"] == 0.5


def test_close_breakdown_leaves_a_lanes_children_out():
    REC._ledger = {}
    REC.mark_close()
    flight.note_phase("device/prep", "t", 1.0, lane=1)
    flight.note_phase("device", "t", 1.0, lane=1)
    flight.note_phase("snapshot", "*", 0.5)
    record = REC._seal_ledger(99992, 0.5)
    assert record["close"] == {"snapshot": 0.5}
    assert record["phases"]["device/prep"] == {"t": 1.0}


# -- whole flows -------------------------------------------------------------


def _tumbling_flow(n_rows=240, n_batches=3):
    align = datetime(2022, 1, 1, tzinfo=timezone.utc)
    base = np.datetime64(align.replace(tzinfo=None), "us")
    batches = [
        ArrayBatch(
            {
                "key_id": (np.arange(n_rows) % 2).astype(np.int32),
                "ts": base
                + (np.arange(n_rows) // 10 + 30 * b).astype("timedelta64[s]"),
            },
            key_vocab=np.array(["0", "1"]),
        )
        for b in range(n_batches)
    ]
    clock = EventClock(ts_getter=lambda x: x, wait_for_system_duration=ZERO_TD)
    windower = TumblingWindower(align_to=align, length=timedelta(seconds=10))
    out = []
    flow = Dataflow("span_df")
    s = op.input("in", flow, ArrayBatchSource(batches))
    wo = w.count_window("count", s, clock, windower, key=lambda x: x)
    op.output("out", wo.down, TestingSink(out))
    return flow, out, n_rows * n_batches


def test_tumbling_flow_counts_rows_windows_and_items(ledger, monkeypatch):
    monkeypatch.setenv("BYTEWAX_TPU_ACCEL", "1")
    flow, out, rows = _tumbling_flow()
    run_main(flow)
    totals, counters = ledger()
    assert sum(n for _k, (_wid, n) in out) == rows
    # Windows closed, items written, rows through each stage.
    assert counters["close_emit_rows"] == len(out)
    assert counters["sink_rows"] == len(out)
    assert counters["watermark_rows"] == rows == counters["encode_rows"]
    # An "E" a window and no "M": the flow reads `down` only, so the
    # `unwrap_meta` tap is pruned and the tier builds no metadata.
    assert counters["emit_rows"] == len(out) == counters["window_opens"]
    assert "window_meta_events" not in counters
    assert counters["device_spans"] == 3  # deliveries
    # At most 24 spans a delivery, none per row or per window.
    spans = sum(
        n for k, n in counters.items()
        if k.endswith("_spans") and k[: -len("_spans")] in flight.TRACED_PHASES
    )
    assert spans <= 24 * counters["device_spans"]
    # On the worker in a delivery, on the main thread at end of input.
    assert totals["device/prep"] > 0 and totals["device/close_emit"] > 0
    assert totals["eof/close_emit"] > 0 and totals["eof/emit"] > 0
    assert totals["device"] > 0 and totals["startup"] > 0
    assert counters["startup_spans"] == 1 == counters["teardown_spans"]
    assert REC._phase_stack == []


def _brc_file(path, rows, seed=3):
    rng = np.random.RandomState(seed)
    names = [f"st{i:03d}" for i in range(17)]
    with open(path, "w") as f:
        for i in rng.randint(0, len(names), size=rows):
            f.write(f"{names[i]};{rng.randint(-300, 400) / 10:.1f}\n")
    return len(names)


def test_brc_file_flow_counts_rows_parsed_and_items_written(ledger, monkeypatch, tmp_path):
    pytest.importorskip("bytewax_tpu.native")
    monkeypatch.setenv("BYTEWAX_TPU_ACCEL", "1")
    path = str(tmp_path / "m.txt")
    stations = _brc_file(path, 5000)
    out = []
    flow = Dataflow("span_brc")
    s = op.input("inp", flow, BrcFileSource(path, chunk_bytes=16384))
    stats = xla.stats_final("stats", s)
    op.output("out", stats, TestingSink(out))
    run_main(flow)
    totals, counters = ledger()
    assert len(out) == stations
    assert counters["parse_rows"] == 5000
    assert counters["sink_rows"] == stations == counters["close_emit_rows"]
    assert counters["parse_spans"] >= 2  # chunks
    assert totals["parse"] > 0 and totals["device/h2d"] > 0
    assert totals["device/encode"] > 0
    # End of input was in no phase before: its work keeps the `eof`
    # lane's name and joins no fraction bucket.
    assert totals["eof/fetch"] > 0 and totals["eof/close_emit"] > 0
    assert "fetch" not in totals and "eof" in totals
    with_eof = flight.ledger_fractions(totals)
    without = flight.ledger_fractions(
        {p: s for p, s in totals.items() if p.split("/")[0] != "eof"}
    )
    assert with_eof == without
    # `parse` comes out of `ingest`, not on top of it.
    assert totals["ingest"] >= 0


# -- the profiler's trace ----------------------------------------------------


def test_work_spans_are_in_the_profilers_trace_and_parent_frames_are_not(
    monkeypatch, tmp_path
):
    import jax
    from jax.profiler import ProfileData

    monkeypatch.setenv("BYTEWAX_TPU_ACCEL", "1")
    flow, out, _rows = _tumbling_flow()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        run_main(flow)
    finally:
        jax.profiler.stop_trace()
    assert out
    (path,) = glob.glob(
        os.path.join(str(tmp_path), "plugins", "profile", "*", "*.xplane.pb")
    )
    lines = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            names = {ev.name for ev in line.events if ev.name.startswith("btx.")}
            if names:
                lines[i] = names
    found = set().union(*lines.values())
    # Keywords (step_id) are taken out of the event's name.
    assert {"btx.close_emit", "btx.prep", "btx.watermark", "btx.sink"} <= found
    assert not found & {"btx.host", "btx.device", "btx.flush", "btx.ingest", "btx.readback"}
    assert found <= {"btx." + p for p in flight.TRACED_PHASES}
    # Each thread on a line of its own: the worker's prep is not on
    # the line that holds the main thread's watermark.
    main = [n for n in lines.values() if "btx.watermark" in n]
    assert len(main) == 1 and len(lines) >= 2
    assert any("btx.prep" in n and "btx.watermark" not in n for n in lines.values())
