"""Tiered key-state residency (engine/residency.py): budgeted HBM,
host-RAM eviction, disk spill.

The host tier (BYTEWAX_TPU_ACCEL=0 / plain Python sums) is the
oracle: a budgeted run must produce identical output however many
evictions, restores, and spills happened along the way, the resident
device key count must hold the budget at every drain boundary, and
recovery must cover evicted/spilled keys unchanged.  Faults are
injected ONLY through the engine's own injector (the pinned
``residency_restore`` site) — no monkeypatching of engine internals.
"""

import os
import pickle
import sqlite3
from datetime import timedelta
from pathlib import Path

import numpy as np
import pytest

import bytewax_tpu.operators as op
from bytewax_tpu import xla
from bytewax_tpu.dataflow import Dataflow
from bytewax_tpu.engine import faults, flight
from bytewax_tpu.testing import TestingSink, TestingSource, run_main

ZERO_TD = timedelta(seconds=0)


@pytest.fixture(autouse=True)
def _fresh_fault_plan():
    faults.reset()
    yield
    faults.reset()


def _sum_flow(flow_id, inp, out, batch_size=2):
    flow = Dataflow(flow_id)
    s = op.input("inp", flow, TestingSource(inp, batch_size=batch_size))
    r = op.reduce_final("sum", s, xla.SUM)
    op.output("out", r, TestingSink(out))
    return flow


def _sum_oracle(inp):
    sums = {}
    for k, v in inp:
        sums[k] = sums.get(k, 0) + v
    return sorted(sums.items())


def _cycling_items(n, n_keys, stride=7):
    """Every key recurs throughout the stream, so a small budget
    forces continuous evict/restore churn."""
    return [(f"k{(i * stride) % n_keys:03d}", i) for i in range(n)]


def _peak_resident(flow_id):
    return max(
        (
            v
            for k, v in flight.RECORDER.counters.items()
            if k.startswith("state_resident_keys_peak[")
            and flow_id in k
        ),
        default=0,
    )


# -- eviction/restore output equality vs the host oracle --------------------


@pytest.mark.parametrize("budget", [2, 8, None])
def test_budgeted_agg_matches_host_oracle(
    entry_point, entry_point_name, budget, monkeypatch, tmp_path
):
    """Aggregation outputs are identical to the host oracle at tight,
    loose, and unbounded budgets, under all three entry points.
    Integer values keep both tiers exact, so equality is exact."""
    flow_id = f"res_eq_{budget}_{entry_point_name}"
    if budget is not None:
        monkeypatch.setenv("BYTEWAX_TPU_STATE_BUDGET", str(budget))
        monkeypatch.setenv(
            "BYTEWAX_TPU_SPILL_DIR", str(tmp_path / "spill")
        )
        monkeypatch.setenv("BYTEWAX_TPU_HOST_STATE_BUDGET", "4")
    else:
        monkeypatch.delenv("BYTEWAX_TPU_STATE_BUDGET", raising=False)
    inp = _cycling_items(240, 24)
    out = []
    entry_point(_sum_flow(flow_id, inp, out), epoch_interval=ZERO_TD)
    assert sorted(out) == _sum_oracle(inp)
    if budget is not None:
        # deliveries carry at most 2 distinct keys <= every budget
        # tested, so the boundary invariant must hold exactly.
        assert 0 < _peak_resident(flow_id) <= budget


def test_budget_invariant_and_tier_counters(monkeypatch, tmp_path):
    """With cardinality >> budget the run evicts, restores, and
    spills — and resident keys never exceed the budget at any drain
    boundary (the ratcheting peak counter under the
    bytewax_state_resident_keys family is the audit)."""
    flow_id = "res_invariant"
    monkeypatch.setenv("BYTEWAX_TPU_STATE_BUDGET", "3")
    monkeypatch.setenv("BYTEWAX_TPU_SPILL_DIR", str(tmp_path / "spill"))
    monkeypatch.setenv("BYTEWAX_TPU_HOST_STATE_BUDGET", "4")
    c0 = dict(flight.RECORDER.counters)
    inp = _cycling_items(200, 20)
    out = []
    run_main(_sum_flow(flow_id, inp, out), epoch_interval=ZERO_TD)
    assert sorted(out) == _sum_oracle(inp)

    def delta(name):
        return flight.RECORDER.counters.get(name, 0) - c0.get(name, 0)

    assert delta("state_evictions_count") > 0
    assert delta("residency_restore_count") > 0
    assert delta("state_spill_bytes") > 0
    peak = _peak_resident(flow_id)
    assert 0 < peak <= 3
    # The Prometheus gauge tracks the same samples.
    from bytewax_tpu._metrics import state_resident_keys

    gauge_vals = [
        s.value
        for metric in state_resident_keys.collect()
        for s in metric.samples
        if flow_id in str(s.labels.get("step_id", ""))
    ]
    assert gauge_vals and max(gauge_vals) <= 3


def test_unset_budget_never_builds_a_manager(monkeypatch):
    """Depth-0 contract: without BYTEWAX_TPU_STATE_BUDGET the state
    object the driver folds into is the raw tier — no wrapper, no
    manager code on any path."""
    monkeypatch.delenv("BYTEWAX_TPU_STATE_BUDGET", raising=False)
    from bytewax_tpu.engine.residency import maybe_wrap
    from bytewax_tpu.engine.sharded_state import make_agg_state

    st = make_agg_state("sum")
    assert maybe_wrap("step", st) is st


# -- scan tier ---------------------------------------------------------------


def test_budgeted_scan_matches_host_oracle(monkeypatch, tmp_path):
    """The per-row-emitting scan tier restores evicted key state
    BEFORE folding (outputs read the state), so per-row emissions
    match the host mapper exactly under a tight budget."""
    monkeypatch.setenv("BYTEWAX_TPU_STATE_BUDGET", "2")
    monkeypatch.setenv("BYTEWAX_TPU_SPILL_DIR", str(tmp_path / "spill"))
    monkeypatch.setenv("BYTEWAX_TPU_HOST_STATE_BUDGET", "3")
    items = [
        (f"k{(i * 3) % 9}", float(np.round(np.sin(i), 3)))
        for i in range(120)
    ]

    def make():
        return xla.ema(0.5)

    states = {}
    want = []
    mapper = make()
    for k, v in items:
        st, emit = mapper(states.get(k), v)
        states[k] = st
        want.append((k, emit))

    out = []
    flow = Dataflow("res_scan")
    s = op.input("inp", flow, TestingSource(items, batch_size=2))
    s = op.stateful_map("scan", s, make())
    op.output("out", s, TestingSink(out))
    run_main(flow, epoch_interval=ZERO_TD)

    by_g, by_w = {}, {}
    for k, row in out:
        by_g.setdefault(k, []).append(row)
    for k, row in want:
        by_w.setdefault(k, []).append(row)
    assert by_g.keys() == by_w.keys()
    for k in by_w:
        for g_row, w_row in zip(by_g[k], by_w[k]):
            assert g_row[0] == pytest.approx(w_row[0])
            assert g_row[1] == pytest.approx(w_row[1], abs=1e-4)
    assert _peak_resident("res_scan") <= 2


# -- spilled-key recovery via resume_from() ----------------------------------


def test_spilled_key_recovery_resume_from(
    recovery_config, tmp_path, monkeypatch
):
    """Epoch snapshots read THROUGH the residency tiers, so a key
    sitting in the disk spill store when the run aborts resumes via
    resume_from() exactly like a resident one."""
    monkeypatch.setenv("BYTEWAX_TPU_STATE_BUDGET", "2")
    monkeypatch.setenv("BYTEWAX_TPU_HOST_STATE_BUDGET", "3")
    spill_dir = tmp_path / "spill"
    monkeypatch.setenv("BYTEWAX_TPU_SPILL_DIR", str(spill_dir))
    head = _cycling_items(90, 18)
    tail = _cycling_items(36, 18, stride=5)
    inp = head + [TestingSource.ABORT()] + tail
    out = []
    flow_id = "res_resume"
    run_main(
        _sum_flow(flow_id, inp, out),
        epoch_interval=ZERO_TD,
        recovery_config=recovery_config,
    )
    assert out == []  # reduce_final emits at EOF only

    # The spill tier engaged and its rows ARE recovery-format rows:
    # same snaps schema, pickled host-format state.
    files = list(Path(spill_dir).glob("spill-*.sqlite3"))
    assert files, "expected a spill store file"
    con = sqlite3.connect(files[0])
    try:
        rows = con.execute(
            "SELECT step_id, state_key, epoch, ser_change FROM snaps"
        ).fetchall()
    finally:
        con.close()
    assert rows, "expected spilled rows in recovery row format"
    for sid, key, _epoch, ser in rows:
        assert "stateful_batch" in sid
        assert isinstance(pickle.loads(ser), int)

    out2 = []
    run_main(
        _sum_flow(flow_id, inp, out2),
        epoch_interval=ZERO_TD,
        recovery_config=recovery_config,
    )
    assert sorted(out2) == _sum_oracle(head + tail)


# -- residency faults through the real injector ------------------------------


def test_mid_restore_device_fault_retries_in_place(
    monkeypatch, tmp_path
):
    """A DeviceFault injected at the pinned residency_restore site
    (fired BEFORE any state mutates) is retried in place by the
    driver's dispatch handling; output stays equal to the oracle."""
    flow_id = "res_fault_retry"
    monkeypatch.setenv("BYTEWAX_TPU_STATE_BUDGET", "2")
    monkeypatch.setenv("BYTEWAX_TPU_SPILL_DIR", str(tmp_path / "spill"))
    monkeypatch.setenv(
        "BYTEWAX_TPU_FAULTS", "residency_restore:error:*:x1"
    )
    c0 = flight.RECORDER.counters.get("fault_injected_count", 0)
    inp = _cycling_items(120, 12)
    out = []
    run_main(_sum_flow(flow_id, inp, out), epoch_interval=ZERO_TD)
    assert sorted(out) == _sum_oracle(inp)
    assert (
        flight.RECORDER.counters.get("fault_injected_count", 0)
        == c0 + 1
    )


def test_persistent_restore_faults_demote_with_all_tiers(
    monkeypatch, tmp_path
):
    """Restore faults past the demotion threshold demote the step to
    the host tier; demotion_snapshots drains the resident, evicted,
    AND spilled tiers, so the migrated host logics own every key and
    the output still matches the oracle."""
    flow_id = "res_fault_demote"
    monkeypatch.setenv("BYTEWAX_TPU_STATE_BUDGET", "2")
    monkeypatch.setenv("BYTEWAX_TPU_SPILL_DIR", str(tmp_path / "spill"))
    monkeypatch.setenv("BYTEWAX_TPU_HOST_STATE_BUDGET", "3")
    monkeypatch.setenv(
        "BYTEWAX_TPU_FAULTS", "residency_restore:error:*"
    )
    c0 = flight.RECORDER.counters.get("demotion_count", 0)
    inp = _cycling_items(120, 12)
    out = []
    run_main(_sum_flow(flow_id, inp, out), epoch_interval=ZERO_TD)
    assert sorted(out) == _sum_oracle(inp)
    assert (
        flight.RECORDER.counters.get("demotion_count", 0) == c0 + 1
    )


# -- the collective tier never evicts ----------------------------------------


def test_global_exchange_tier_never_evicts(monkeypatch):
    """Pin: the global-mesh exchange tier is excluded from residency
    exactly like demotion — maybe_wrap refuses global_exchange states
    even with a budget armed, and GlobalAggState implements no
    residency surface (the BTX-SNAPSHOT rule proves the same over
    the AST)."""
    monkeypatch.setenv("BYTEWAX_TPU_STATE_BUDGET", "2")
    from bytewax_tpu.engine.residency import maybe_wrap
    from bytewax_tpu.engine.sharded_state import GlobalAggState

    class _FakeGlobal:
        global_exchange = True

    fake = _FakeGlobal()
    assert maybe_wrap("step", fake) is fake
    assert not hasattr(GlobalAggState, "extract_keys")
    assert not hasattr(GlobalAggState, "inject_keys")


# -- extract/inject unit round trips -----------------------------------------


def test_agg_extract_inject_round_trip():
    from bytewax_tpu.engine.sharded_state import make_agg_state

    st = make_agg_state("sum")
    st.update(
        np.asarray(["a", "b", "c"]), np.asarray([1, 2, 3])
    )
    items = dict(st.extract_keys(["a", "b"]))
    assert items == {"a": 1, "b": 2}
    assert set(st.keys()) == {"c"}
    st.inject_keys(list(items.items()))
    st.update(np.asarray(["a"]), np.asarray([10]))
    assert sorted(st.finalize()) == [("a", 11), ("b", 2), ("c", 3)]


def test_scan_extract_inject_round_trip():
    from bytewax_tpu.engine.sharded_state import make_scan_state
    from bytewax_tpu.ops.scan import Ema

    st = make_scan_state(Ema(0.5))
    st.update(
        np.asarray(["a", "a", "b"]), np.asarray([1.0, 2.0, 3.0])
    )
    items = st.extract_keys(["a"])
    assert [k for k, _s in items] == ["a"]
    (snap,) = [s for _k, s in items]
    assert snap[0] == 2  # count field rode the snapshot
    assert "a" not in st.keys()
    st.inject_keys(items)
    (resumed,) = [s for _k, s in st.snapshots_for(["a"])]
    assert resumed == pytest.approx(snap)
