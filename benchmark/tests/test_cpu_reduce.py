"""The metrics that read CPU seconds beside wall seconds, the
jit-stage counters and the run's wall clock, each over a recorded
``run`` dict (the window's gains as ``benchmark.run`` hands them to a
metric file), with the case of a program that records none of it; and
a tiny run of a cell of each kind."""

import io

import pytest

from benchmark import cpu_reduce
from benchmark.metrics import (
    device_wait_pct,
    gc_pct,
    lane_offcpu_pct,
    main_offcpu_pct,
    main_unnamed_pct,
    retrace_pct,
)
from benchmark.tests.tiny import run_tiny, tiny_cell

NEW = (
    "lane_offcpu_pct", "main_offcpu_pct", "device_wait_pct", "retrace_pct",
    "main_unnamed_pct", "gc_pct",
)


def recorded():
    """Ten seconds of a window: a lane and a main thread, a wait
    recorded from a duration (``flush``: no CPU), a span whose CPU the
    clocks read a hair above its wall (``sink``)."""
    phases = {
        "device": 0.5, "device/prep": 3.0, "device/dispatch": 0.4, "device/fetch": 0.6,
        "host": 1.0, "ingest": 0.5, "encode": 1.5, "fetch": 0.2, "eof/fetch": 0.3,
        "eof": 0.1, "flush": 2.0, "gc": 0.25, "startup": 0.05, "sink": 0.1,
    }
    cpu = {
        "device": 0.4, "device/prep": 2.0, "device/dispatch": 0.1, "device/fetch": 0.1,
        "host": 0.9, "ingest": 0.5, "encode": 1.2, "fetch": 0.05, "eof/fetch": 0.05,
        "eof": 0.1, "gc": 0.25, "startup": 0.05, "sink": 0.1001,
    }
    counters = {"cpu:" + k: v for k, v in cpu.items()}
    counters.update(
        {
            "run_wall_seconds": 6.5,
            "jit_trace_count": 12, "jit_trace_seconds": 0.5, "jit_lower_seconds": 0.25,
            "xla_cache_load_count": 4, "xla_cache_load_seconds": 0.25,
            "xla_compile_count": 0,
            "jit_stage_seconds[body]": 0.9, "jit_stage_seconds[add]": 0.1,
            "jit_stage_seconds[still]": 0.0,
        }
    )
    return {"window_s": 10.0, "phases": phases, "counters": counters}


def before():
    """The same window under a program from before the counters."""
    run = recorded()
    run["phases"].pop("gc")
    run["counters"] = {"xla_compile_count": 0, "prep_spans": 7}
    return run


def test_lane_offcpu_reads_the_lane_but_its_waits_for_the_chip():
    # device 0.1 + device/prep 1.0; not dispatch, not fetch.
    assert lane_offcpu_pct.read(recorded()) == pytest.approx(11.0)


def test_main_offcpu_reads_the_main_threads_spans_and_prints_the_table(capsys):
    # host 0.1 + encode 0.3 + sink -0.0001; flush has no CPU; the
    # waits (fetch, eof/fetch) and the lane are not its own.
    assert main_offcpu_pct.read(recorded()) == pytest.approx(3.999)
    err = capsys.readouterr().err
    assert "cpu_reduce: device/prep" in err and "off 1.000000 s" in err
    assert "cpu_reduce: flush" in err and "(no cpu: a duration)" in err


def test_device_wait_reads_the_three_waits_on_every_lane():
    # device/dispatch 0.3 + device/fetch 0.5 + fetch 0.15 + eof/fetch 0.25.
    assert device_wait_pct.read(recorded()) == pytest.approx(12.0)


def test_retrace_reads_the_stages_that_are_not_a_compile(capsys):
    assert retrace_pct.read(recorded()) == pytest.approx(10.0)
    err = capsys.readouterr().err
    assert "retrace: traces 12 cache loads 4 compiles 0" in err
    assert err.index("retrace: body") < err.index("retrace: add")
    assert "still" not in err
    # Counters that stood still are a measurement, not an absence.
    still = recorded()
    for key in retrace_pct.STAGES:
        still["counters"][key] = 0.0
    assert retrace_pct.read(still) == 0.0


def test_main_unnamed_is_the_run_wall_less_every_main_phase(capsys, monkeypatch):
    monkeypatch.delenv("BYTEWAX_TPU_PIPELINE_DEPTH", raising=False)
    run = recorded()
    main = sum(s for p, s in run["phases"].items() if not p.startswith("device"))
    assert main == pytest.approx(6.0)
    assert main_unnamed_pct.read(run) == pytest.approx(5.0)
    err = capsys.readouterr().err
    assert "run_wall 6.500000 s, under a phase 6.000000 s" in err
    assert "frame host" in err and "frame eof" in err and "frame readback" not in err
    # Never below nothing (a window that opens in mid-pass).
    run["counters"]["run_wall_seconds"] = 5.9
    assert main_unnamed_pct.read(run) == 0.0
    # At depth 1 the lane's tasks are the main thread's.
    monkeypatch.setenv("BYTEWAX_TPU_PIPELINE_DEPTH", "1")
    run["counters"]["run_wall_seconds"] = 11.0
    assert main_unnamed_pct.read(run) == pytest.approx(5.0)


def test_gc_reads_the_span_and_nothing_is_a_zero():
    assert gc_pct.read(recorded()) == pytest.approx(2.5)
    none_ran = recorded()
    none_ran["phases"].pop("gc")
    assert gc_pct.read(none_ran) == 0.0


@pytest.mark.parametrize(
    "reader",
    [lane_offcpu_pct, main_offcpu_pct, device_wait_pct, retrace_pct, main_unnamed_pct, gc_pct],
    ids=lambda m: m.__name__.rpartition(".")[2],
)
def test_a_program_without_the_source_reads_none_and_does_not_raise(reader, capsys):
    assert reader.read(before()) is None
    assert capsys.readouterr().err == ""


def test_a_lane_that_never_ran_reads_none():
    run = recorded()
    run["phases"] = {p: s for p, s in run["phases"].items() if not p.startswith("device")}
    assert lane_offcpu_pct.read(run) is None
    assert main_offcpu_pct.read(run) is not None


def test_the_table_prints_a_line_a_phase():
    out = io.StringIO()
    cpu_reduce.print_table(recorded(), out)
    lines = out.getvalue().splitlines()
    assert lines[0] == "cpu_reduce: window 10.000000 s"
    assert len(lines) == 1 + len(recorded()["phases"])
    assert lines[1].startswith("cpu_reduce: device/prep")  # by wall seconds


@pytest.mark.parametrize("name", ["tumbling.flood", "brc.items"])
def test_every_new_metric_reads_a_number_in_a_cell_it_lists(name, capfd):
    from benchmark import run as bench_run

    cell = tiny_cell(name)
    line = run_tiny(cell)
    assert line["correct"], line["checks"]
    capfd.readouterr()
    got = bench_run.read_metrics(cell, "per_layer", line["_run"])
    listed = [m["name"] for m in cell.metrics("per_layer") if m["name"] in NEW]
    assert set(listed) == set(NEW) - ({"gc_pct"} if name == "tumbling.flood" else set())
    for metric in listed:
        assert metric in got, metric
        assert 0.0 <= got[metric]["value"] <= 100.0, (metric, got[metric])
    run = line["_run"]
    # No phase's CPU passes its wall seconds by more than a hundredth.
    cpu = cpu_reduce.phase_cpu(run)
    for phase, wall in run["phases"].items():
        if phase in cpu:
            assert cpu[phase] <= wall * 1.01 + 2e-3, (phase, cpu[phase], wall)
    # The main thread's phases fit in the run's wall clock.
    assert cpu_reduce.main_seconds(run) <= run["counters"]["run_wall_seconds"] * 1.01 + 0.05
    err = capfd.readouterr().err
    assert "cpu_reduce: window" in err and "main_unnamed: run_wall" in err
