"""`q8.flood`: `correct` has to be able to come out false, the
reference's answer has to stand, and the join's three metrics read the
run.

A sound tiny run is `correct`; each control of the flow (the reference
in the program's place with reserves through float32, one row written
twice, one window's persons dropped) fails the cell's comparison; and a
run with the program's value carrier broken underneath reports
`correct` false."""

import numpy as np
import pytest

from benchmark import control
from benchmark.tests.tiny import run_tiny, tiny_cell


def _tiny():
    """The cell at a test's size, with no set-up walk (its sizes are
    the chip's)."""
    cell = tiny_cell("q8.flood")
    cell.traffic.pop("warm_join")
    return cell


@pytest.fixture(scope="module")
def sound():
    cell = _tiny()
    return cell, run_tiny(cell)


def test_sound_run_is_correct(sound):
    _cell, line = sound
    assert line["correct"], line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert all(value == 0 for value, _limit in line["checks"].values())
    assert line["info"]["results"] > 0.6 * line["attempted"]


@pytest.mark.parametrize("which", ["float32_reserve", "row_twice", "no_person"])
def test_control_comes_out_not_correct(sound, which):
    cell, line = sound
    numbers = control.control_numbers(cell, line)[which]
    failed = control.failed_by(cell, numbers)
    if which == "float32_reserve":
        assert failed == ["reserve_wrong"]
    elif which == "row_twice":
        assert failed == ["rows_extra", "rows_twice", "rows_unanswered"]
    else:
        assert failed == ["rows_missing", "rows_unanswered"]


def test_the_reference_stands_against_itself(sound):
    cell, line = sound
    flow, cfg = cell.flow, cell.cfg
    want = flow.reference(cfg, *line["_run"]["basis"])
    assert not control.failed_by(cell, flow.compare(cfg, dict(want, late=0), want))


def test_a_stalled_schedule_comes_out_not_correct(sound):
    """Two polls the clock's wait apart leave every person to the wall
    clock: with them left out nothing is compared, and the share of
    persons left out fails the run on its own."""
    cell, line = sound
    flow, cfg = cell.flow, cell.cfg
    data, served = line["_run"]["basis"]
    want = flow.reference(cfg, data, served)
    half = served // 2
    quick = [(10.0, 0, half), (10.1, half, served)]
    assert len(flow.undecided(cfg, data, quick, 10.2)) == 0
    stalled = [(10.0, 0, half), (20.1, half, served)]
    open_pids = flow.undecided(cfg, data, stalled, 20.2)
    numbers = flow.compare(cfg, dict(want, late=0), want, open_pids)
    assert numbers["undecided_share"] == 1.0
    assert control.failed_by(cell, numbers) == ["undecided_share"]


def test_a_carrier_broken_in_the_program(monkeypatch):
    """Values carried through float32 underneath: reserves past 2^24
    round, and the run is not correct."""
    from bytewax_tpu.engine.window_accel import DeviceJoinState

    carried = DeviceJoinState._carried

    def through_float32(self, side, values):
        return carried(self, side, values.astype(np.float32).astype(values.dtype))

    monkeypatch.setattr(DeviceJoinState, "_carried", through_float32)
    line = run_tiny(_tiny())
    assert not line["correct"]
    assert line["checks"]["reserve_wrong"][0] > 0


def test_the_join_metrics_read_the_run(monkeypatch):
    from benchmark.metrics import join_close_pct, join_place_pct, join_roofline

    monkeypatch.setenv("BYTEWAX_TPU_INGEST_TARGET_ROWS", "0")
    run = run_tiny(_tiny(), seconds=3.0)["_run"]
    assert join_place_pct.read(run) > 0 and join_close_pct.read(run) > 0
    # No trace: no roofline.
    assert join_roofline.read(run) is None
    # A program without the spans reads nothing.
    bare = dict(run, phases={k: v for k, v in run["phases"].items() if "join" not in k})
    assert join_place_pct.read(bare) is None and join_close_pct.read(bare) is None


def test_the_set_up_walk_runs_every_program_at_the_listed_sizes():
    """The walk at a test's sizes: each listed arena and table size, so
    that a size the window meets was compiled in set-up, and no pad
    longer than its arena, nor a compaction past sixteen times, which
    no delivery makes."""
    from benchmark.flows import nexmark_q8 as q8

    warm = {
        "delivery_rows": [100, 300],
        "slots": 2048,
        "arena_rows": [1024, 4096, 65536],
        "delivery_pads": [128, 2048],
        "move_pads": [32],
        "slot_rows": [1024, 2048],
    }
    # 1024: one write (2048 is longer), a move, compactions to 1024
    # and 4096, three expansions; 4096: two writes, a move, to 4096
    # and 65536, three; 65536: two, a move, to itself, three; each
    # table size three count gathers.
    assert q8.warm_join_programs({"warm_join": warm}) == 7 + 8 + 7 + 2 * 3
    assert q8.warm_join_programs({}) == 0
