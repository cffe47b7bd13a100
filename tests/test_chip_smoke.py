"""chip_smoke.py's stages at a tiny size on the CPU backend, so its
flows, references and counter checks cannot rot between chip runs —
and the proof that, without the explicit waiver, it refuses to run
anywhere but on a chip."""

import os
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

import chip_smoke  # noqa: E402

TINY = chip_smoke.Sizes(
    brc_rows=5_000,
    win_keys=64,
    win_events=4_000,
    win_batch_rows=500,
    scan_keys=50,
    scan_rows=3_000,
    scan_batch_rows=500,
    oracle_keys=10,
    kernel_rows=2_000,
)


@pytest.fixture
def ctx(tmp_path, monkeypatch):
    from bytewax_tpu import native

    port = chip_smoke._free_port()
    monkeypatch.setenv("BYTEWAX_DATAFLOW_API_ENABLED", "1")
    monkeypatch.setenv("BYTEWAX_DATAFLOW_API_PORT", str(port))
    # Tiny polls must stay polls: the ingest coalescer would merge
    # them into one delivery, and with it into one epoch close.
    monkeypatch.setenv("BYTEWAX_TPU_INGEST_TARGET_ROWS", "0")
    monkeypatch.setenv("BYTEWAX_TPU_SHARD", "0")
    monkeypatch.chdir(tmp_path)
    return chip_smoke.Ctx(
        sizes=TINY,
        seed=7,
        workdir=str(tmp_path),
        probe=chip_smoke.Probe(port),
        allow_cpu=True,
        device=chip_smoke.device_or_exit(allow_cpu=True),
        native={
            "io_native": native.is_available(),
            "host_ops": native._ext() is not None,
        },
    )


def test_stage_brc(ctx):
    doc = chip_smoke.stage_brc(ctx)
    assert doc["ok"] and doc["rows_in"] == TINY.brc_rows
    assert doc["rows_out"] == TINY.brc_stations
    assert doc["counters"]["demotion_count"] == 0
    assert doc["counters"]["device_transfer_bytes_h2d"] > 0


def test_stage_windows_then_resume(ctx):
    doc, uninterrupted = chip_smoke.stage_windows(ctx)
    assert doc["ok"] and doc["rows_late"] > 0
    assert doc["live_keys"] == TINY.win_keys
    assert doc["counters"]["epoch_close_count"] >= 3
    resumed = chip_smoke.stage_resume(ctx, uninterrupted)
    assert resumed["ok"]
    assert 0 < resumed["stopped_at_row"] < doc["rows_in"]
    assert resumed["rows_out"] == doc["rows_out"]


def test_stage_scan_infer(ctx):
    doc = chip_smoke.stage_scan_infer(ctx)
    assert doc["ok"] and doc["flows"] == ["scan", "infer", "sessions"]
    assert doc["oracle_rows"] > 0 and doc["sessions"] > TINY.scan_keys


def test_stage_kernels(ctx):
    doc = chip_smoke.stage_kernels(ctx)
    assert doc["ok"] and doc["capacities"] == [1024, 8192, 16384]
    assert [f["form"] for f in doc["forms_ms_not_a_benchmark"]] == [
        "dense", "dense", "scatter", "dense, packed entry"
    ]
    # Only the waiver lets an interpreted kernel through.
    assert doc["pallas_interpreted"]
    ctx.allow_cpu = False
    with pytest.raises(chip_smoke.SmokeFailure, match="interpreted"):
        chip_smoke.stage_kernels(ctx)


def test_stage_mesh(ctx):
    # conftest's virtual 8-device mesh stands in for the chips.
    chip_smoke.stage_mesh(ctx)
    assert os.environ["BYTEWAX_TPU_SHARD"] == "0"


def test_a_wrong_answer_fails_the_stage(ctx, monkeypatch):
    real = chip_smoke._brc_file

    def off_by_one(*args):
        names, mn, mx, total, count = real(*args)
        count[0] += 1
        return names, mn, mx, total, count

    monkeypatch.setattr(chip_smoke, "_brc_file", off_by_one)
    with pytest.raises(chip_smoke.SmokeFailure):
        chip_smoke.stage_brc(ctx)


def test_refuses_to_run_off_the_chip(tmp_path):
    res = subprocess.run(
        [sys.executable, str(REPO / "chip_smoke.py")],
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
        cwd=tmp_path,
        capture_output=True,
        text=True,
        timeout=120,
    )
    assert res.returncode != 0
    assert res.stdout == ""
    assert "no TPU" in res.stderr
