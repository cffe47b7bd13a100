"""`q11.flood`: `correct` has to be able to come out false, and the
three metrics of the session tier read the run.

A sound tiny run is `correct`; each control of the flow (the reference
in the program's place with counts held in bfloat16, one bid folded
twice, one bid starting a session of its own) fails the cell's
comparison; and a run with the program's session gap broken underneath
reports `correct` false."""

import pytest

from benchmark import control
from benchmark.tests.tiny import run_tiny, tiny_cell


@pytest.fixture(scope="module")
def sound():
    cell = tiny_cell("q11.flood")
    cell.traffic["warm_windows_per_poll_row"] = 0
    return cell, run_tiny(cell)


def test_sound_run_is_correct(sound):
    _cell, line = sound
    assert line["correct"], line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert all(value == 0 for value, _limit in line["checks"].values())
    assert line["info"]["results"] > 100  # a session a bidder


@pytest.mark.parametrize("which", ["bfloat16", "row_twice", "split_session"])
def test_control_comes_out_not_correct(sound, which):
    cell, line = sound
    numbers = control.control_numbers(cell, line)[which]
    failed = control.failed_by(cell, numbers)
    assert failed, (which, numbers)
    if which == "bfloat16":
        assert "count_wrong" in failed  # a hot bidder's count is past 256
    elif which == "row_twice":
        assert failed == ["count_wrong", "rows_unanswered"]
    else:
        assert failed == ["sessions_extra", "count_wrong"]


def test_a_gap_broken_in_the_program(monkeypatch):
    """Sessions cut at a tenth of the configuration's gap."""
    from bytewax_tpu.engine.window_accel import SessionAccelSpec

    make_state = SessionAccelSpec.make_state

    def narrow(self):
        self.gap_us /= 10
        return make_state(self)

    monkeypatch.setattr(SessionAccelSpec, "make_state", narrow)
    cell = tiny_cell("q11.flood")
    cell.traffic["warm_windows_per_poll_row"] = 0
    line = run_tiny(cell)
    assert not line["correct"]
    assert line["checks"]["sessions_extra"][0] > 0


def test_the_session_metrics_read_the_run(monkeypatch):
    from benchmark.metrics import (
        session_close_pct,
        session_keys_held_pct,
        session_keys_remembered_pct,
        session_place_pct,
    )

    # Every poll a delivery, so that the counters move while the
    # window's polls are still being handed out.
    monkeypatch.setenv("BYTEWAX_TPU_INGEST_TARGET_ROWS", "0")
    cell = tiny_cell("q11.flood")
    cell.traffic["warm_windows_per_poll_row"] = 0
    run = run_tiny(cell, seconds=3.0)["_run"]
    # A tiny run's sessions are all still open at its last poll.
    assert session_keys_held_pct.read(run) == 100
    assert session_keys_remembered_pct.read(run) in (None, 0.0)
    assert session_place_pct.read(run) > 0 and session_close_pct.read(run) > 0
    # A program without the spans reads nothing.
    bare = dict(run, phases={k: v for k, v in run["phases"].items() if "session" not in k})
    assert session_place_pct.read(bare) is None


@pytest.mark.parametrize(
    "remembered, want",
    [((None, None, None), None), ((None, 0, 700), 70.0), ((300, 300, 300), 0.0)],
    ids=["no_counter", "grows", "flat"],
)
def test_the_record_of_keys_let_go_reads_its_growth(remembered, want):
    """The record's growth between the window's first and last polls,
    over the keys given an id between them; nothing where the program
    has no counter."""
    from types import SimpleNamespace

    from benchmark.flows.nexmark_q11 import SAMPLED
    from benchmark.metrics import session_keys_remembered_pct

    def sample(lo, opened, held):
        row = dict.fromkeys(SAMPLED)
        row.update(window_keys_opened=opened, session_keys_remembered=held)
        return (lo,) + tuple(row[name] for name in SAMPLED)

    run = {
        "data": {
            "counter_samples": [
                sample(lo, opened, held)
                for lo, opened, held in zip((0, 100, 200), (10, 500, 1500), remembered)
            ]
        },
        "schedule": SimpleNamespace(warm_rows=100),
    }
    assert session_keys_remembered_pct.read(run) == want
