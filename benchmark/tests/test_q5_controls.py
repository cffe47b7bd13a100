"""`q5.flood`: `correct` has to be able to come out false.

A sound tiny run is `correct`; each control of the flow (the reference
in the program's place with counts held in bfloat16, one bid folded
twice, one bid counted in one of its two windows) fails the cell's
comparison; and a run with the program's sliding fan-out broken
underneath reports `correct` false."""

import pytest

from benchmark import control
from benchmark.tests.tiny import run_tiny, tiny_cell


@pytest.fixture(scope="module")
def sound():
    cell = tiny_cell("q5.flood")
    return cell, run_tiny(cell)


def test_sound_run_is_correct(sound):
    _cell, line = sound
    assert line["correct"], line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert all(value == 0 for value, _limit in line["checks"].values())
    assert line["info"]["results"] >= 2  # parts written, one a window at least


@pytest.mark.parametrize("which", ["bfloat16", "row_twice", "one_window"])
def test_control_comes_out_not_correct(sound, which):
    cell, line = sound
    numbers = control.control_numbers(cell, line)[which]
    failed = control.failed_by(cell, numbers)
    assert failed, (which, numbers)
    if which == "bfloat16":
        assert "max_wrong" in failed  # a hot auction's count is past 256
    else:
        assert failed == ["rows_unanswered"] and numbers["rows_unanswered"] == 1


def test_controls_are_the_flows_own():
    cell = tiny_cell("q5.flood")
    assert cell.flow.CONTROLS == ("bfloat16", "row_twice", "one_window")
    assert set(cell.cfg["limits"].values()) == {0}


def test_the_sliding_fan_out_broken_in_the_program(monkeypatch):
    """Every bid folded into the newest of its windows only."""
    from bytewax_tpu.engine.window_accel import WindowAccelSpec

    make_state = WindowAccelSpec.make_state

    def one_window(self):
        state = make_state(self)
        assert state.expand == 2
        state.expand = 1
        return state

    monkeypatch.setattr(WindowAccelSpec, "make_state", one_window)
    line = run_tiny(tiny_cell("q5.flood"))
    assert not line["correct"]
    failed = [k for k, (v, lim) in line["checks"].items() if v > lim]
    assert "rows_unanswered" in failed and "windows_missing" in failed
    assert line["failed"] > 0


def test_half_of_a_poll_left_out(monkeypatch):
    cell = tiny_cell("q5.flood")
    whole = cell.flow.batch
    monkeypatch.setattr(
        cell.flow,
        "batch",
        lambda cfg, data, lo, hi: whole(cfg, data, lo, hi - (hi - lo) // 2),
    )
    line = run_tiny(cell)
    assert not line["correct"]
    assert line["checks"]["rows_unanswered"][0] > 0 and line["failed"] > 0


def test_new_metrics_read_the_run_and_nothing_on_a_program_without_them(monkeypatch):
    from benchmark.metrics import host_logic_pct, key_retire_pct, keys_held_pct

    # Every poll a delivery, so that the counters move while the
    # window's polls are still being handed out.
    monkeypatch.setenv("BYTEWAX_TPU_INGEST_TARGET_ROWS", "0")
    run = run_tiny(tiny_cell("q5.flood"), seconds=3.0)["_run"]
    # A tiny run's windows are all still open at its last poll.
    assert 0 < keys_held_pct.read(run) <= 100
    assert host_logic_pct.read(run) > 0 and key_retire_pct.read(run) > 0
    # The parent's program has neither the counters nor the spans.
    samples = [(s[0], None, None, s[3], s[4]) for s in run["data"]["counter_samples"]]
    bare = dict(
        run,
        data=dict(run["data"], counter_samples=samples),
        phases={k: v for k, v in run["phases"].items() if "logic" not in k and "retire" not in k},
    )
    for reader in (keys_held_pct, host_logic_pct, key_retire_pct):
        assert reader.read(bare) is None
