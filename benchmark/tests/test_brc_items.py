"""``brc.items`` small, on the CPU backend: the cell is ``correct``
through the harness's own ``jobs`` driver, each control fails by its
own number alone, a run whose rows came in as columns fails on
``off_items`` alone, and the cell's per-layer metrics find what they
read (and nothing, not an error, under a program without it)."""

import pytest

from benchmark import control, run
from benchmark.flows import brc
from benchmark.tests.tiny import run_tiny, tiny_cell

ITEM_METRICS = {
    "item_read_pct", "item_ops_pct", "item_promote_pct", "item_rows_per_delivery",
}


@pytest.fixture(scope="module")
def sound():
    """One sound tiny run a seed (the second over 2**31)."""
    cell = tiny_cell("brc.items")
    return cell, {seed: run_tiny(cell, seed=seed) for seed in (7, 2147483659)}


@pytest.mark.parametrize("seed", [7, 2147483659])
def test_sound_run_is_correct(sound, seed):
    cell, lines = sound
    line = lines[seed]
    assert line["correct"], line["checks"]
    assert set(line["checks"]) == set(cell.cfg["limits"])
    assert line["checks"]["off_items"] == [0, 0]
    assert line["checks"]["off_device"] == [0, 0]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["info"]["jobs"] >= 1
    assert {"events_per_s", "setup_s"} <= set(line["metrics"])


def test_every_control_fails_by_its_own_number_alone(sound):
    cell, lines = sound
    controls = control.control_numbers(cell, lines[7])
    assert {w: control.failed_by(cell, n) for w, n in controls.items()} == {
        "bfloat16": ["extrema_err", "mean_err"],
        "row_twice": ["count_wrong", "rows_unanswered"],
        "columnar_door": ["off_items"],
    }


def test_rows_that_came_in_as_columns_fail_on_off_items_alone(monkeypatch):
    """The cell's jobs driven over ``flows/brc.py``'s flow (the
    native parser, columns all the way): right answers, not
    ``correct``."""
    cell = tiny_cell("brc.items")
    noted = cell.flow.build_flow

    def columnar(cfg, data, source, sink):
        noted(cfg, data, source, sink)  # the job's starting sample
        return brc.build_flow(cfg, data, source, sink)

    monkeypatch.setattr(cell.flow, "build_flow", columnar)
    line = run_tiny(cell)
    assert not line["correct"]
    assert control.failed_by(cell, {k: v[0] for k, v in line["checks"].items()}) == [
        "off_items"
    ]


def test_per_layer_metrics_find_what_they_read(sound):
    cell, lines = sound
    internals = lines[7]["_run"]
    listed = {m["name"] for m in cell.metrics("per_layer")}
    assert ITEM_METRICS <= listed
    # Every metric that lists no cell goes with this one too.
    assert {
        m["name"] for m in cell.manifest["per_layer"] if "workloads" not in m
    } <= listed
    got = run.read_metrics(cell, "per_layer", internals)
    assert ITEM_METRICS | {"host_phase_pct", "h2d_bytes_per_event"} <= set(got)
    for name in ITEM_METRICS - {"item_rows_per_delivery"}:
        assert 0 < got[name]["value"] < 100, name
    # 50,000 rows a job in 1000-line polls: one delivery a job.
    assert got["item_rows_per_delivery"]["value"] == 50_000
    # 8 bytes a padded row, 50,000 rows padded to 65,536.
    assert got["h2d_bytes_per_event"]["value"] == pytest.approx(8 * 65536 / 50_000)


def test_a_program_without_the_spans_reads_as_nothing(sound):
    """The parent's ledger has no `read`, `item_ops` or `promote` and
    no ``ingest_deliveries_itemized``: each reader returns None and
    the line leaves the metric out."""
    cell, lines = sound
    internals = dict(lines[7]["_run"])
    internals["phases"] = {
        k: v
        for k, v in internals["phases"].items()
        if k.rpartition("/")[2] not in ("read", "item_ops", "promote")
    }
    internals["counters"] = {
        k: v
        for k, v in internals["counters"].items()
        if k != "ingest_deliveries_itemized"
    }
    got = run.read_metrics(cell, "per_layer", internals)
    assert not ITEM_METRICS & set(got)
    assert "host_phase_pct" in got
