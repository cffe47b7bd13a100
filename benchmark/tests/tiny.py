"""Cells cut to a size a test run can hold (rows and polls only; the
flow, the reference and the comparison are the cell's)."""

from benchmark import run


def tiny_cell(name: str) -> run.Cell:
    cell = run.Cell(name)
    if cell.traffic["mode"] == "stream":
        cell.traffic.update(
            poll_rows=5000, warmup=[{"rows": 10_000, "poll_rows": 5000}]
        )
    else:
        cell.cfg["shapes"].update(rows_per_job=50_000)
    return cell


def run_tiny(cell: run.Cell, seed: int = 7, seconds: float = 2.0):
    return run.run_cell(cell, seed, seconds, False, run.device_seen())
