"""The span reduction on a small trace kept beside
``recorded_trace.json`` (``recorded_spans.json``: a lane span and a
main span over one gap, a gap no span covers, a span that began before
the first device operation, a span nested in another) and on the
ledger's phases."""

import io
import json
import os

import pytest

from benchmark import span_reduce, trace_reduce
from benchmark.tests.tiny import run_tiny, tiny_cell

HERE = os.path.dirname(os.path.abspath(__file__))
MS = 1_000_000.0


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "recorded_spans.json")) as f:
        return json.load(f)


def test_main_thread_is_told_from_the_lane_by_what_only_it_does(recorded):
    lanes, mains = span_reduce.span_lines(recorded)
    assert len(lanes) == 1 and len(mains) == 1
    assert {e[0] for e in mains[0]} >= {"btx.watermark", "btx.sink"}
    assert {e[0] for e in lanes[0]} >= {"btx.prep", "btx.fetch"}
    # A line with no span of the engine is neither.
    assert all(e[0].startswith("btx.") for ln in lanes + mains for e in ln)


def test_idle_seconds_by_span(recorded):
    out = span_reduce.reduce(recorded)
    # Gaps of the chip: 110-150, 152-200, 210-300, 301-400 ms.
    assert out["idle_s"] == pytest.approx(0.277)
    idle = out["idle_by_span_s"]
    # 110-150: the lane's prep to 120, h2d to 130, dispatch to 135;
    # the main thread's watermark (to 130) and encode (131-135) lie
    # under the lane's spans and get nothing; 135-150 nobody's.
    assert idle["lane/prep"] == pytest.approx(0.010)
    assert idle["lane/h2d"] == pytest.approx(0.010)
    assert idle["lane/dispatch"] == pytest.approx(0.005)
    assert "watermark" not in idle and "encode" not in idle
    # 210-300: close_scan 212-220 and fetch 220-300 on the lane take
    # what emit and sink also cover; emit keeps 210-212 alone.
    assert idle["lane/close_scan"] == pytest.approx(0.008)
    assert idle["lane/fetch"] == pytest.approx(0.080)
    assert idle["emit"] == pytest.approx(0.002)
    assert "sink" not in idle
    # 301-400: close_emit 301-360 less the encode nested in it.
    assert idle["lane/close_emit"] == pytest.approx(0.054)
    assert idle["lane/encode"] == pytest.approx(0.005)
    # 135-150, all of 152-200 and 360-400: no span.
    assert out["unattributed_s"] == pytest.approx(0.015 + 0.048 + 0.040)
    assert sum(idle.values()) + out["unattributed_s"] == pytest.approx(out["idle_s"])
    # Of the unattributed, nothing lies under a span of the harness
    # here (bench_poll 95-96 ms is before the first gap, the sink's
    # write under the lane's fetch).
    assert out["harness_s"] == {}
    left = [(135 * MS, 150 * MS), (236 * MS, 240 * MS)]
    assert span_reduce.harness_cover(recorded, left) == pytest.approx(
        {"bench_sink_write": 0.003}
    )


def test_self_seconds_by_span(recorded):
    self_s = span_reduce.reduce(recorded)["self_by_span_s"]
    # prep began 40 ms before the first device operation: all of it
    # counts as its own time, none of that as idle.
    assert self_s["lane/prep"] == pytest.approx(0.060)
    assert self_s["lane/close_emit"] == pytest.approx(0.054)
    assert self_s["lane/encode"] == pytest.approx(0.005)
    assert self_s["encode"] == pytest.approx(0.004)
    assert self_s["sink"] == pytest.approx(0.030)
    assert not any(k.startswith("bench_") for k in self_s)


def test_trace_reduce_names_the_gaps_by_the_spans(recorded):
    """``trace_reduce._label_gap``, as it is, names an idle gap by the
    engine's span that covers most of it."""
    gaps = dict(trace_reduce.reduce(recorded)["breakdown"]["idle_gaps"])
    assert gaps["btx.fetch"] == pytest.approx(0.090)
    assert gaps["btx.close_emit"] == pytest.approx(0.099)
    assert "python_in_engine" in gaps  # 152-200: nobody's


def test_a_trace_without_spans_or_without_a_device_reads_nothing(recorded):
    with open(os.path.join(HERE, "recorded_trace.json")) as f:
        assert span_reduce.reduce(json.load(f)) is None  # PR 24's program
    host_only = {"planes": [p for p in recorded["planes"] if "device" not in p["name"]]}
    assert span_reduce.reduce(host_only) is None
    from benchmark.metrics import idle_unattributed_pct

    assert idle_unattributed_pct.read({"trace": None}) is None
    assert idle_unattributed_pct.read({"trace": {"busy_s": 1.0}}) is None  # no ./trace


def test_flattened_gives_each_instant_to_the_innermost_span():
    events = [["a", 0.0, 100.0], ["b", 10.0, 20.0], ["c", 15.0, 5.0], ["d", 200.0, 10.0]]
    assert span_reduce.flattened(events) == [
        (0.0, 10.0, "a"), (10.0, 15.0, "b"), (15.0, 20.0, "c"), (20.0, 30.0, "b"),
        (30.0, 100.0, "a"), (200.0, 210.0, "d"),
    ]


def test_table_is_a_line_a_span(recorded):
    out = io.StringIO()
    span_reduce.print_table(span_reduce.reduce(recorded), out)
    lines = out.getvalue().splitlines()
    assert lines[0].startswith("span_reduce: idle 0.277000 s, unattributed 0.103000 s")
    assert lines[1].split()[1] == "lane/fetch"  # most idle seconds first
    assert len(lines) == 1 + len(span_reduce.reduce(recorded)["self_by_span_s"])
    reduced = dict(span_reduce.reduce(recorded), harness_s={"bench_poll": 0.25})
    out = io.StringIO()
    span_reduce.print_table(reduced, out)
    assert out.getvalue().splitlines()[-1] == (
        "span_reduce: of the unattributed, under bench_poll 0.250000 s"
    )


def test_phase_pct_reads_a_name_on_every_lane():
    run = {
        "window_s": 10.0,
        "phases": {"fetch": 0.5, "device/fetch": 1.5, "device": 2.0, "device/prep": 1.0, "host": 3.0},
    }
    assert span_reduce.phase_pct(run, "fetch") == pytest.approx(20.0)
    assert span_reduce.phase_pct(run, "device/fetch") == pytest.approx(15.0)
    assert span_reduce.phase_pct(run, "device/*") == pytest.approx(45.0)
    assert span_reduce.phase_pct(run, "device") == pytest.approx(20.0)
    assert span_reduce.phase_pct(run, "prep", "fetch") == pytest.approx(30.0)
    assert span_reduce.phase_pct(run, "parse") is None
    assert span_reduce.phase_pct({"window_s": 10.0, "phases": {}}, "device/*") is None


@pytest.mark.parametrize("name", ["tumbling.flood", "brc.file"])
def test_every_program_span_metric_reads_a_number_in_the_cells_it_lists(name):
    from benchmark import run as bench_run

    cell = tiny_cell(name)
    line = run_tiny(cell)
    assert line["correct"], line["checks"]
    got = bench_run.read_metrics(cell, "per_layer", line["_run"])
    listed = [
        m["name"] for m in cell.metrics("per_layer") if m["source"] == "program_span"
    ]
    assert len(listed) >= 6
    for metric in listed:
        assert metric in got, metric
        # The ledger's window closes after the last job's teardown and
        # ``window_s`` at its last write: at four tiny jobs the
        # lifecycle's share can pass 100 (at 34 real ones, by 1/34).
        top = 200.0 if metric == "run_lifecycle_pct" else 100.0
        assert 0.0 <= got[metric]["value"] <= top, (metric, got[metric])
    # Shares of the window's wall time: the lane's parts add up to it.
    run = line["_run"]
    parts = sum(
        s for p, s in run["phases"].items() if p.split("/")[0] == "device"
    )
    assert got["lane_busy_pct"]["value"] == pytest.approx(100.0 * parts / run["window_s"])
    assert got["lane_busy_pct"]["value"] >= got["lane_prep_pct"]["value"]
    # Work spans a delivery, from the counters: inside the budget of 24.
    # A lane task carries every batch one poll brought.
    counters = run["counters"]
    assert 4 <= span_reduce.spans_a_delivery(counters) <= 24
    assert span_reduce.spans_a_delivery(counters, "device") >= (
        span_reduce.spans_a_delivery(counters)
    )
    assert span_reduce.spans_a_delivery({}) is None
    # Not traced: the device_trace metric is left out, and does not raise.
    assert "idle_unattributed_pct" not in got
