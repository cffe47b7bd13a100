"""NEXmark Query 8 as the benchmark runs it, small, on the CPU: the
windowed join of persons and auctions against its plain reference over
several seeds, the tiers alike, and the controls that must fail."""

import json
import os
import sys

import pytest

from bytewax_tpu.engine import flight
from bytewax_tpu.testing import TestingSink, run_main

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmark.flows import nexmark_q8 as q8  # noqa: E402


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(REPO, "benchmark", "configs", "nexmark-q8.json")) as f:
        return json.load(f)


def _run_q8(cfg, rows, seed, poll=4000):
    """The benchmark's flow over the first ``rows`` rows of a seeded
    stream; what the sink received."""
    from tests.test_xla import ArraySource

    data = q8.make_data(cfg, {}, seed, "")
    batches = [q8.batch(cfg, data, lo, min(rows, lo + poll)) for lo in range(0, rows, poll)]
    out = []
    run_main(q8.build_flow(cfg, data, ArraySource(batches), TestingSink(out)))
    return data, out


@pytest.mark.parametrize("seed", [3, 2147483659, 9_000_000_011])
def test_flow_matches_its_reference(monkeypatch, cfg, seed):
    """Every check 0 against the numpy reference, no row late, on the
    device tier; about nine auctions in ten find their person."""
    monkeypatch.setenv("BYTEWAX_TPU_ACCEL", "1")
    rows = 40_000
    before = dict(flight.RECORDER.counters)
    data, out = _run_q8(cfg, rows, seed)
    got = q8.result_arrays(cfg, [q8.pack(out)])
    want = q8.reference(cfg, data, rows)
    numbers = q8.compare(cfg, got, want)
    assert numbers == dict.fromkeys(numbers, 0), numbers
    assert set(numbers) | {"off_device"} == set(cfg["limits"])
    assert all(limit == 0 for limit in cfg["limits"].values())
    auctions = rows * 3 // 4
    assert 0.85 * auctions < len(want["pid"]) < auctions
    assert want["reserve"].max() > 1 << 24  # past float32's exact integers

    def gained(name):
        return flight.RECORDER.counters.get(name, 0) - before.get(name, 0)

    assert gained("join_rows_stored") == rows
    assert gained("join_rows_emitted") >= len(want["pid"])
    assert gained("join_place_spans") >= 1 and gained("join_close_spans") >= 1
    assert gained("window_keys_retired") == gained("window_keys_opened") > 0


def test_the_tail_is_an_item_pass_and_the_taps_are_none(monkeypatch, cfg):
    """The join's rows reach the flow's tail through the window's
    ``down`` tap unwalked: the tail's mapper opens an ``item_ops`` span
    over exactly the rows the join wrote, the taps open none, and
    every row is counted as handed on directly."""
    monkeypatch.setenv("BYTEWAX_TPU_ACCEL", "1")
    before = dict(flight.RECORDER.counters)
    _data, out = _run_q8(cfg, 20_000, seed=5)

    def gained(name):
        return flight.RECORDER.counters.get(name, 0) - before.get(name, 0)

    emitted = gained("join_rows_emitted")
    assert 0 < len(out) < emitted  # the tail drops the rows with no person
    assert gained("item_ops_spans") >= 1
    assert gained("item_ops_rows") == emitted
    assert gained("window_rows_direct") == emitted
    assert gained("window_rows_tapped") == 0


def test_the_tiers_write_the_same_rows(monkeypatch, cfg):
    outs = []
    for accel in ("1", "0"):
        monkeypatch.setenv("BYTEWAX_TPU_ACCEL", accel)
        _data, out = _run_q8(cfg, 8000, seed=2147483659, poll=2000)
        outs.append(sorted(out))
    assert outs[0] == outs[1]
    assert {"join_place", "join_close"} <= flight.TRACED_PHASES


def test_controls_fail_the_comparison(cfg):
    """Each control is not correct by the configuration's limits, and
    the reference against itself is."""
    data = q8.make_data(cfg, {}, 11, "")
    served = 60_000
    want = q8.reference(cfg, data, served)
    assert q8.compare(cfg, dict(want, late=0), want) == dict.fromkeys(
        set(cfg["limits"]) - {"off_device"}, 0
    )
    for which in q8.CONTROLS:
        numbers = q8.compare(cfg, q8.control_results(cfg, data, served, which), want)
        assert any(numbers[k] > cfg["limits"][k] for k in numbers), which
