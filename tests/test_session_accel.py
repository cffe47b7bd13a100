"""Device-tier session windows: equivalence with the host tier,
gap-merge metadata, lateness, and cross-tier recovery.

Documented deviations (see ``DeviceSessionAggState``): within one
delivered batch the device assigns new session ids in timestamp order
(host: arrival order), so the equivalence tests feed ts-ordered
input, where the tiers agree exactly.
"""

from datetime import datetime, timedelta, timezone

import numpy as np
import pytest

import bytewax_tpu.operators as op
import bytewax_tpu.operators.windowing as w
from bytewax_tpu.dataflow import Dataflow
from bytewax_tpu.engine.flatten import flatten
from bytewax_tpu.engine.window_accel import SessionAccelSpec
from bytewax_tpu.operators.windowing import (
    LATE_SESSION_ID,
    EventClock,
    SessionWindower,
)
from bytewax_tpu.testing import TestingSink, TestingSource, run_main
from tests import test_window_accel as twa

ALIGN = datetime(2022, 1, 1, tzinfo=timezone.utc)


def _flow_count(inp, down, meta, late, gap_s=10, wait_s=5, batch_size=64):
    clock = EventClock(
        ts_getter=lambda item: item[0],
        wait_for_system_duration=timedelta(seconds=wait_s),
    )
    windower = SessionWindower(gap=timedelta(seconds=gap_s))
    flow = Dataflow("test_df")
    s = op.input("inp", flow, TestingSource(inp, batch_size=batch_size))
    wo = w.count_window("count", s, clock, windower, key=lambda item: item[1])
    op.output("down", wo.down, TestingSink(down))
    op.output("meta", wo.meta, TestingSink(meta))
    op.output("late", wo.late, TestingSink(late))
    return flow


def _sorted_events(n, n_keys=3, spread_s=600, seed=0):
    rng = np.random.RandomState(seed)
    base = np.sort(rng.randint(0, spread_s, size=n))
    return [
        (ALIGN + timedelta(seconds=int(s)), f"key{rng.randint(n_keys)}")
        for s in base
    ]


def test_session_count_window_is_annotated():
    flow = _flow_count([], [], [], [])
    plan = flatten(flow)
    stateful = [o for o in plan.ops if o.name == "stateful_batch"]
    assert isinstance(stateful[0].conf.get("_accel"), SessionAccelSpec)


def test_session_count_device_matches_host(monkeypatch):
    inp = _sorted_events(500, spread_s=3000)

    def run(accel):
        monkeypatch.setenv("BYTEWAX_TPU_ACCEL", accel)
        down, meta, late = [], [], []
        run_main(_flow_count(inp, down, meta, late))
        return sorted(down), sorted(meta, key=repr), sorted(late, key=repr)

    device, host = run("1"), run("0")
    assert device[0] == host[0]  # values per (key, session)
    assert device[1] == host[1]  # metadata incl. merged_ids
    assert device[2] == host[2]  # late stream


def test_session_merge_metadata(monkeypatch):
    # Two sessions per key bridged by a later value: the earlier-open
    # session wins and records the absorbed id, on both tiers.
    inp = [
        (ALIGN + timedelta(seconds=0), "a"),
        (ALIGN + timedelta(seconds=2), "a"),
        # > gap away: second session...
        (ALIGN + timedelta(seconds=30), "a"),
        # ...bridged back into the first by a value between them.
        (ALIGN + timedelta(seconds=12), "a"),
        (ALIGN + timedelta(seconds=21), "a"),
        # push the watermark far ahead so everything closes.
        (ALIGN + timedelta(seconds=500), "a"),
    ]

    def run(accel, batch_size):
        monkeypatch.setenv("BYTEWAX_TPU_ACCEL", accel)
        down, meta, late = [], [], []
        run_main(
            _flow_count(
                inp,
                down,
                meta,
                late,
                gap_s=10,
                # Large wait: the out-of-order bridging values must be
                # on time for the merge to happen.
                wait_s=60,
                batch_size=batch_size,
            )
        )
        return down, meta

    # batch_size=1: the device sees arrival order like the host.
    dev_down, dev_meta = run("1", 1)
    host_down, host_meta = run("0", 1)
    assert sorted(dev_down) == sorted(host_down)
    assert sorted(dev_meta, key=repr) == sorted(host_meta, key=repr)
    merged = [m for _k, (_wid, m) in dev_meta if m.merged_ids]
    assert merged, "expected a gap-merge to happen"
    assert merged[0].merged_ids == {1}
    assert merged[0].open_time == ALIGN
    assert merged[0].close_time == ALIGN + timedelta(seconds=30)
    # All 5 merged values in session 0; the 500s value in session 2.
    assert sorted(dev_down) == [("a", (0, 5)), ("a", (2, 1))]


def test_session_late_values_use_sentinel(monkeypatch):
    monkeypatch.setenv("BYTEWAX_TPU_ACCEL", "1")
    inp = [
        (ALIGN + timedelta(seconds=100), "a"),
        # Far behind the watermark (wait=0): late.
        (ALIGN + timedelta(seconds=1), "a"),
    ]
    down, meta, late = [], [], []
    run_main(_flow_count(inp, down, meta, late, wait_s=0, batch_size=1))
    assert late == [("a", (LATE_SESSION_ID, (ALIGN + timedelta(seconds=1), "a")))]


@pytest.mark.parametrize("direction", ["device_to_host", "host_to_device"])
def test_session_cross_tier_recovery(tmp_path, monkeypatch, direction):
    from bytewax_tpu.recovery import RecoveryConfig, init_db_dir

    init_db_dir(tmp_path, 1)
    rc = RecoveryConfig(str(tmp_path))
    inp = [
        (ALIGN + timedelta(seconds=1), "a"),
        (ALIGN + timedelta(seconds=3), "a"),
        TestingSource.ABORT(),
        # Within gap of the snapshot's open session: must extend it.
        (ALIGN + timedelta(seconds=9), "a"),
    ]
    first, second = (
        ("1", "0") if direction == "device_to_host" else ("0", "1")
    )
    down, meta, late = [], [], []
    clock = EventClock(
        ts_getter=lambda item: item[0],
        wait_for_system_duration=timedelta(days=999),
    )
    windower = SessionWindower(gap=timedelta(seconds=10))
    flow = Dataflow("test_df")
    s = op.input("inp", flow, TestingSource(inp))
    wo = w.count_window("count", s, clock, windower, key=lambda item: item[1])
    op.output("down", wo.down, TestingSink(down))
    op.output("meta", wo.meta, TestingSink(meta))
    op.output("late", wo.late, TestingSink(late))

    monkeypatch.setenv("BYTEWAX_TPU_ACCEL", first)
    run_main(flow, epoch_interval=timedelta(0), recovery_config=rc)
    assert down == []
    monkeypatch.setenv("BYTEWAX_TPU_ACCEL", second)
    run_main(flow, epoch_interval=timedelta(0), recovery_config=rc)
    assert down == [("a", (0, 3))]
    assert [m for _k, (_wid, m) in meta] == [
        w.WindowMetadata(
            ALIGN + timedelta(seconds=1), ALIGN + timedelta(seconds=9)
        )
    ]


def test_session_sum_columnar_matches_host(monkeypatch):
    # Columnar {key, ts, value} batches session-fold on device with
    # no per-row Python; equivalence against the host tier over the
    # degraded itemized view of the same batches.
    from bytewax_tpu.engine.arrays import ArrayBatch
    from bytewax_tpu.xla import SUM
    from tests.test_xla import ArraySource

    n = 4000
    rng = np.random.RandomState(5)
    secs = np.sort(rng.randint(0, 3000, size=n))
    keys = np.array([f"key{k}" for k in rng.randint(0, 3, size=n)])
    vals = rng.randint(1, 100, size=n).astype(np.float64)
    ts = (
        np.datetime64(ALIGN.replace(tzinfo=None), "us")
        + secs.astype("timedelta64[s]")
    )
    batches = [
        ArrayBatch(
            {
                "key": keys[i : i + 512],
                "ts": ts[i : i + 512],
                "value": vals[i : i + 512],
            }
        )
        for i in range(0, n, 512)
    ]

    def run(accel):
        monkeypatch.setenv("BYTEWAX_TPU_ACCEL", accel)
        from bytewax_tpu.engine.arrays import column_ts

        clock = EventClock(
            ts_getter=column_ts,
            wait_for_system_duration=timedelta(seconds=5),
        )
        windower = SessionWindower(gap=timedelta(seconds=7))
        down, meta = [], []
        flow = Dataflow("test_df")
        s = op.input("inp", flow, ArraySource(batches))
        wo = w.fold_window(
            "sum", s, clock, windower, lambda: 0, SUM, SUM
        )
        op.output("down", wo.down, TestingSink(down))
        op.output("meta", wo.meta, TestingSink(meta))
        run_main(flow)
        return sorted(down), sorted(meta, key=repr)

    device, host = run("1"), run("0")
    assert device[0] == host[0]
    assert device[1] == host[1]
    total = sum(v for _k, (_wid, v) in device[0])
    assert total == vals.sum()


def test_session_fold_custom_merger_stays_host(monkeypatch):
    # A fold whose merger is NOT the kind's combine must not lower.
    from bytewax_tpu.xla import SUM

    clock = EventClock(
        ts_getter=lambda item: item[0],
        wait_for_system_duration=timedelta(seconds=5),
    )
    flow = Dataflow("test_df")
    s = op.input("inp", flow, TestingSource([]))
    wo = w.fold_window(
        "sum",
        s,
        clock,
        SessionWindower(gap=timedelta(seconds=10)),
        lambda: 0,
        SUM,
        lambda a, b: a,  # arbitrary merger: device combine would differ
    )
    op.output("down", wo.down, TestingSink([]))
    plan = flatten(flow)
    stateful = [o for o in plan.ops if o.name == "stateful_batch"]
    assert stateful[0].conf.get("_accel") is None


# -- the clock pass under sessions -------------------------------------------


def _session_of(kind):
    from bytewax_tpu import xla

    gap = timedelta(seconds=10)
    return (
        SessionAccelSpec(kind, xla.column_ts, gap, timedelta(0)),
        SessionWindower(gap=gap),
    )


@pytest.mark.parametrize("kind", ["count", "stats"])
@pytest.mark.parametrize("case,entry", twa.clock_case_entries())
def test_session_clock_passes_agree_with_each_other_and_the_host_tier(
    monkeypatch, case, entry, kind
):
    """The session tier inherits ``_ingest``: the in-order pass, the
    sorted pass over the same rows and the per-item host tier give the
    same late events (under the sentinel id), sessions, clocks and
    touched keys (cases and harness: tests/test_window_accel.py)."""
    twa.check_clock_case(monkeypatch, *_session_of(kind), case, entry)


@pytest.mark.parametrize("entry", ["columnar", "itemized", "host_format"])
@pytest.mark.parametrize(
    "case", ["in_order", "late_by_the_carried_clock_only", "out_of_order_within_a_key"]
)
def test_session_clock_passes_agree_on_a_resumed_clock(monkeypatch, case, entry):
    """The same on session states loaded from snapshots."""
    twa.check_clock_case(
        monkeypatch, *_session_of("stats"), case, entry, resumed=True
    )
