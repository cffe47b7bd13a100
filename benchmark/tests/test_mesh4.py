"""``brc.mesh4`` small, on the CPU backend's virtual devices: the
cell is ``correct`` where jax has four devices and fails on
``off_mesh`` alone where it has one; each control fails by its own
number alone.

The device count is fixed when jax starts, so each run is a child
process of its own (the other tests of this directory run on one
device)."""

import json
import os
import subprocess
import sys

import pytest

from benchmark import run

CHILD = """
import json
from benchmark import control, run
from benchmark.tests.tiny import run_tiny, tiny_cell

cell = tiny_cell("brc.mesh4")
line = run_tiny(cell)
controls = control.control_numbers(cell, line)
print(json.dumps({
    "correct": line["correct"],
    "failed_by": control.failed_by(cell, {k: v[0] for k, v in line["checks"].items()}),
    "controls_failed_by": {w: control.failed_by(cell, n) for w, n in controls.items()},
    "counters": {k: v for k, v in line["_run"]["counters"].items() if k.startswith("exchange")},
    "metrics": sorted(run.read_metrics(cell, "per_layer", line["_run"])),
    "device_count": line["device"]["count"],
}))
"""


def tiny_run(devices: int):
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        JAX_ENABLE_COMPILATION_CACHE="0",
        XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
        PYTHONPATH=run.ROOT,
    )
    env.pop("BYTEWAX_TPU_SHARD", None)
    done = subprocess.run(
        [sys.executable, "-c", CHILD],
        env=env, capture_output=True, text=True, timeout=600,
    )
    assert done.returncode == 0, done.stderr[-2000:]
    return json.loads(done.stdout.strip().splitlines()[-1])


@pytest.fixture(scope="module")
def on_four():
    return tiny_run(4)


def test_correct_on_four_devices(on_four):
    assert on_four["device_count"] == 4
    assert on_four["correct"] and on_four["failed_by"] == []
    counters = on_four["counters"]
    assert counters["exchange_rows"] > 0
    assert counters["exchange_bucket_rows"] >= counters["exchange_rows"]
    assert {"exchange_prep_pct", "exchange_fill_pct", "shard_load_skew_pct"} <= set(
        on_four["metrics"]
    )


def test_every_control_fails_by_its_own_number_alone(on_four):
    assert on_four["controls_failed_by"] == {
        "bfloat16": ["extrema_err", "mean_err"],
        "row_twice": ["count_wrong", "rows_unanswered"],
        "one_device": ["off_mesh"],
    }


def test_one_device_fails_on_off_mesh_alone():
    on_one = tiny_run(1)
    assert on_one["device_count"] == 1
    assert not on_one["correct"] and on_one["failed_by"] == ["off_mesh"]
    assert not on_one["counters"].get("exchange_rows")
    assert not {"exchange_fill_pct", "shard_load_skew_pct"} & set(on_one["metrics"])
