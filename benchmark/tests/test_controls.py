"""`correct` has to be able to come out false.

Each control of a flow (the reference in the program's place, in the
precision below the stated one or with one stated guarantee broken)
fails the cell's comparison, at a size a test run can hold; and a run
driven past the harness's look for a chip, with the timed path broken
underneath, reports ``correct`` false: half of a batch left out, an
answer altered where it is produced, a fold that returns its state
unchanged.
"""

import pytest

from benchmark import control
from benchmark.tests.tiny import run_tiny, tiny_cell

CELLS = ["tumbling.flood", "brc.file"]


@pytest.fixture(scope="module")
def sound():
    """One sound tiny run per cell."""
    out = {}
    for name in CELLS:
        cell = tiny_cell(name)
        out[name] = (cell, run_tiny(cell))
    return out


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(sound, name):
    _cell, line = sound[name]
    assert line["correct"], line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert list(line)[-2] == "checks"  # last key of the printed line


@pytest.mark.parametrize("name", CELLS)
def test_every_control_comes_out_not_correct(sound, name):
    cell, line = sound[name]
    for which, numbers in control.control_numbers(cell, line).items():
        assert control.failed_by(cell, numbers), (which, numbers)


def test_half_of_a_batch_left_out(monkeypatch):
    cell = tiny_cell("tumbling.flood")
    whole = cell.flow.batch
    monkeypatch.setattr(
        cell.flow,
        "batch",
        lambda cfg, data, lo, hi: whole(cfg, data, lo, hi - (hi - lo) // 2),
    )
    line = run_tiny(cell)
    assert not line["correct"]
    assert line["checks"]["rows_unanswered"][0] > 0 and line["failed"] > 0


def test_a_window_answer_altered_where_it_is_produced(monkeypatch):
    from bytewax_tpu.engine.window_accel import DeviceWindowAggState

    finalize = DeviceWindowAggState._finalize_one
    seen = []

    def altered(self, snap):
        out = finalize(self, snap)
        seen.append(1)
        if len(seen) == 50 and out is not None:
            mn, mx, total, count = out
            return (mn, mx, total * (1 + 1e-3), count)
        return out

    monkeypatch.setattr(DeviceWindowAggState, "_finalize_one", altered)
    line = run_tiny(tiny_cell("tumbling.flood"))
    assert not line["correct"]
    failed = [k for k, (v, lim) in line["checks"].items() if v > lim]
    assert failed == ["sum_err"]


def test_a_fold_that_returns_its_state_unchanged(monkeypatch):
    from bytewax_tpu.engine.xla import DeviceAggState

    scatter = DeviceAggState._scatter
    calls = []

    def skipping(self, slot_ids, values):
        calls.append(1)
        if len(calls) == 2:  # the engine coalesces tiny polls
            return None
        return scatter(self, slot_ids, values)

    monkeypatch.setattr(DeviceAggState, "_scatter", skipping)
    line = run_tiny(tiny_cell("tumbling.flood"))
    assert len(calls) >= 2
    assert not line["correct"]
    assert line["checks"]["count_wrong"][0] > 0


def test_a_station_answer_altered_where_it_is_produced(monkeypatch):
    from bytewax_tpu.engine import xla

    final_of = xla._final_of

    def altered(kind, fields, i):
        out = final_of(kind, fields, i)
        if i == 3:
            return (out[0], out[1] + 0.01, out[2], out[3])
        return out

    monkeypatch.setattr(xla, "_final_of", altered)
    line = run_tiny(tiny_cell("brc.file"))
    assert not line["correct"]
    failed = [k for k, (v, lim) in line["checks"].items() if v > lim]
    assert failed == ["mean_err"]


def test_half_of_the_file_left_out(monkeypatch):
    """The job reads a file half as long as the reference was made
    from."""
    cell = tiny_cell("brc.file")
    make_data = cell.flow.make_data

    def halved(cfg, traffic, seed, workdir):
        data = make_data(cfg, traffic, seed, workdir)
        with open(data["path"], "rb") as f:
            lines = f.readlines()
        with open(data["path"], "wb") as f:
            f.writelines(lines[: len(lines) // 2])
        return data

    monkeypatch.setattr(cell.flow, "make_data", halved)
    line = run_tiny(cell)
    assert not line["correct"]
    assert line["checks"]["rows_unanswered"][0] > 0


def test_rows_the_wall_clock_makes_late_are_left_out_not_failed(monkeypatch):
    """A wait of 0 lets a stall of the engine's polling make in-order
    rows late: the clock drops them by its rule, the data alone cannot
    say which, and the comparison leaves those windows out."""
    import time

    cell = tiny_cell("tumbling.flood")
    # Polls over the engine's gathering target, so each is a delivery.
    cell.traffic.update(poll_rows=70_000, warmup=[{"rows": 70_000, "poll_rows": 70_000}])
    whole, polls = cell.flow.batch, []

    def stalling(cfg, data, lo, hi):
        polls.append(lo)
        if len(polls) == 3:
            time.sleep(2.6)
        return whole(cfg, data, lo, hi)

    monkeypatch.setattr(cell.flow, "batch", stalling)
    line = run_tiny(cell, seconds=3.0)
    assert line["correct"], line["checks"]
    run = line["_run"]
    assert 0 < len(run["open_comps"]) <= 8
    strict = cell.flow.compare(
        cell.cfg,
        cell.flow.result_arrays(cell.cfg, run["packs"]),
        cell.flow.reference(cell.cfg, *run["basis"]),
    )
    assert strict["rows_unanswered"] > 0  # the engine did drop rows


def test_undecided_windows_by_hand():
    """Two keys, rows 1 s apart: polls 0.1 s apart leave nothing to
    the wall clock; a 3 s stall before a poll leaves the windows of
    the rows in its first 3 s of event time."""
    cell = tiny_cell("tumbling.flood")
    flow, cfg = cell.flow, cell.cfg
    data = flow.make_data(cfg, cell.traffic, 5, "")
    within_rows = flow._TAKEN_IN_WITHIN_ROWS
    flow._TAKEN_IN_WITHIN_ROWS = 0
    try:
        quick = [(10.0 + 0.1 * i, 100 * i, 100 * i + 100) for i in range(4)]
        assert len(flow.undecided(cfg, data, quick, 10.4)) == 0
        stalled = quick[:2] + [(13.2, 200, 300), (13.3, 300, 400)]
        open_comps = flow.undecided(cfg, data, stalled, 13.4)
        # Poll 2 follows poll 1 by 3.1 s.  The engine took batch 1 in at
        # some time before poll 2, so up to 3.2 s after it took in batch
        # 0: rows 100..102 may be late (window 1, rows 60..119); and it
        # took batch 2 in up to 3.2 s after batch 1: rows 200..202
        # (window 3, rows 180..239).
        assert set(open_comps // 2) == {1, 3} and 2 <= len(open_comps) <= 4
    finally:
        flow._TAKEN_IN_WITHIN_ROWS = within_rows
