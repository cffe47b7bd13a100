"""Device-accelerated windowed aggregation: equivalence with the host
tier, lateness, and cross-tier recovery."""

from datetime import datetime, timedelta, timezone

import numpy as np
import pytest

import bytewax_tpu.operators as op
import bytewax_tpu.operators.windowing as w
from bytewax_tpu.dataflow import Dataflow
from bytewax_tpu.engine.flatten import flatten
from bytewax_tpu.engine.window_accel import WindowAccelSpec
from bytewax_tpu.operators.windowing import (
    EventClock,
    SlidingWindower,
    TumblingWindower,
)
from bytewax_tpu.testing import TestingSink, TestingSource, run_main

ALIGN = datetime(2022, 1, 1, tzinfo=timezone.utc)


def _flow_count(inp, out, windower):
    clock = EventClock(
        ts_getter=lambda item: item[0],
        wait_for_system_duration=timedelta(seconds=5),
    )
    flow = Dataflow("test_df")
    s = op.input("inp", flow, TestingSource(inp, batch_size=64))
    wo = w.count_window("count", s, clock, windower, key=lambda item: item[1])
    op.output("out", wo.down, TestingSink(out))
    return flow


def _rand_events(n, n_keys=3, spread_s=600, seed=0):
    rng = np.random.RandomState(seed)
    # Mostly-increasing event times with jitter.
    base = np.sort(rng.randint(0, spread_s, size=n))
    return [
        (ALIGN + timedelta(seconds=int(s)), f"key{rng.randint(n_keys)}")
        for s in base
    ]


def test_count_window_is_annotated():
    flow = _flow_count(
        [], [], TumblingWindower(length=timedelta(minutes=1), align_to=ALIGN)
    )
    plan = flatten(flow)
    stateful = [o for o in plan.ops if o.name == "stateful_batch"]
    assert isinstance(stateful[0].conf.get("_accel"), WindowAccelSpec)


@pytest.mark.parametrize(
    "windower",
    [
        TumblingWindower(length=timedelta(minutes=1), align_to=ALIGN),
        SlidingWindower(
            length=timedelta(minutes=2),
            offset=timedelta(minutes=1),
            align_to=ALIGN,
        ),
    ],
    ids=["tumbling", "sliding"],
)
def test_count_window_device_matches_host(monkeypatch, windower):
    inp = _rand_events(500)

    def run(accel):
        monkeypatch.setenv("BYTEWAX_TPU_ACCEL", accel)
        out = []
        run_main(_flow_count(inp, out, windower))
        return sorted(out)

    device, host = run("1"), run("0")
    assert device == host


def test_count_window_benchmark_shape(monkeypatch):
    # The reference benchmark shape: timestamp items, 2 random keys,
    # 1-min tumbling windows — device vs host equivalence.
    monkeypatch.setenv("BYTEWAX_TPU_ACCEL", "1")
    import random

    def build(out):
        rand = random.Random(7)
        inp = [ALIGN + timedelta(seconds=i) for i in range(3000)]
        clock = EventClock(
            ts_getter=lambda x: x,
            wait_for_system_duration=timedelta(seconds=10),
        )
        windower = TumblingWindower(
            length=timedelta(minutes=1), align_to=ALIGN
        )
        flow = Dataflow("test_df")
        s = op.input("inp", flow, TestingSource(inp, batch_size=256))
        wo = w.count_window(
            "count", s, clock, windower, key=lambda _x: str(rand.randrange(2))
        )
        op.output("out", wo.down, TestingSink(out))
        return flow

    device = []
    run_main(build(device))
    monkeypatch.setenv("BYTEWAX_TPU_ACCEL", "0")
    host = []
    run_main(build(host))
    # Totals must match exactly; late-item routing may differ at batch
    # boundaries (documented), so compare window count sums.
    assert sum(c for _k, (_w, c) in device) == sum(
        c for _k, (_w, c) in host
    ) == 3000


def test_window_accel_late_items(monkeypatch):
    monkeypatch.setenv("BYTEWAX_TPU_ACCEL", "1")
    clock = EventClock(
        ts_getter=lambda item: item[0],
        wait_for_system_duration=timedelta(seconds=10),
    )
    windower = TumblingWindower(length=timedelta(minutes=1), align_to=ALIGN)
    inp = [
        (ALIGN + timedelta(seconds=120), "a"),
        (ALIGN + timedelta(seconds=1), "a"),  # far behind watermark
    ]
    down, late = [], []
    flow = Dataflow("test_df")
    s = op.input("inp", flow, TestingSource(inp))
    wo = w.count_window("count", s, clock, windower, key=lambda item: item[1])
    op.output("down", wo.down, TestingSink(down))
    op.output("late", wo.late, TestingSink(late))
    run_main(flow)
    assert len(late) == 1
    assert late[0][0] == "a"
    assert sum(c for _k, (_wid, c) in down) == 1


@pytest.mark.parametrize(
    "offsets_s, late_expected",
    [
        # Watermark jump first (wait=10s → watermark 110s), then a
        # borderline-old row IN THE SAME BATCH: late, post-item.
        ([120, 100], [100]),
        # Same rows, old one first: nothing has advanced the
        # watermark past it yet, so it is on time.
        ([100, 120], []),
        # Exactly AT the watermark (110 == 120 - 10): strict `<`
        # means on time.
        ([120, 110], []),
        # Just below: late.
        ([120, 109], [109]),
    ],
)
def test_window_accel_lateness_boundary(monkeypatch, offsets_s, late_expected):
    """Pin the in-batch lateness boundary: the device tier judges each
    row post-item against its key's running watermark, strict `<`,
    bit-identical to the host tier (`window_accel.py` semantics
    note)."""

    def run(accel):
        monkeypatch.setenv("BYTEWAX_TPU_ACCEL", accel)
        clock = EventClock(
            ts_getter=lambda item: item[0],
            wait_for_system_duration=timedelta(seconds=10),
        )
        windower = TumblingWindower(
            length=timedelta(minutes=1), align_to=ALIGN
        )
        inp = [(ALIGN + timedelta(seconds=s), "a") for s in offsets_s]
        down, late = [], []
        flow = Dataflow("test_df")
        # One delivered batch so the in-batch prefix-max path is
        # what judges the borderline row.
        s = op.input("inp", flow, TestingSource(inp, batch_size=len(inp)))
        wo = w.count_window(
            "count", s, clock, windower, key=lambda item: item[1]
        )
        op.output("down", wo.down, TestingSink(down))
        op.output("late", wo.late, TestingSink(late))
        run_main(flow)
        late_secs = sorted(
            int((v[0] - ALIGN).total_seconds()) for _k, (_wid, v) in late
        )
        counted = sum(c for _k, (_wid, c) in down)
        return late_secs, counted

    dev_late, dev_count = run("1")
    host_late, host_count = run("0")
    assert dev_late == host_late == late_expected
    assert dev_count == host_count == len(offsets_s) - len(late_expected)


def test_window_accel_cross_tier_recovery(tmp_path, monkeypatch):
    from bytewax_tpu.recovery import RecoveryConfig, init_db_dir

    init_db_dir(tmp_path, 1)
    rc = RecoveryConfig(str(tmp_path))
    clock = EventClock(
        ts_getter=lambda item: item[0],
        wait_for_system_duration=timedelta(days=999),
    )
    windower = TumblingWindower(length=timedelta(minutes=1), align_to=ALIGN)
    inp = [
        (ALIGN + timedelta(seconds=1), "a"),
        (ALIGN + timedelta(seconds=2), "a"),
        TestingSource.ABORT(),
        (ALIGN + timedelta(seconds=3), "a"),
    ]
    out = []
    flow = Dataflow("test_df")
    s = op.input("inp", flow, TestingSource(inp))
    wo = w.count_window("count", s, clock, windower, key=lambda item: item[1])
    op.output("out", wo.down, TestingSink(out))

    # Crash on the device tier, resume on the host tier.
    monkeypatch.setenv("BYTEWAX_TPU_ACCEL", "1")
    run_main(flow, epoch_interval=timedelta(0), recovery_config=rc)
    assert out == []
    monkeypatch.setenv("BYTEWAX_TPU_ACCEL", "0")
    run_main(flow, epoch_interval=timedelta(0), recovery_config=rc)
    assert out == [("a", (0, 3))]


def test_count_window_columnar(monkeypatch):
    # Columnar event batches (key + ts columns) count with no
    # per-item Python; results match the itemized device path.
    monkeypatch.setenv("BYTEWAX_TPU_ACCEL", "1")
    from bytewax_tpu.engine.arrays import ArrayBatch
    from tests.test_xla import ArraySource

    n = 5000
    rng = np.random.RandomState(3)
    secs = np.sort(rng.randint(0, 600, size=n))
    keys = np.array([f"key{k}" for k in rng.randint(0, 3, size=n)])
    ts = (
        np.datetime64(ALIGN.replace(tzinfo=None), "us")
        + secs.astype("timedelta64[s]")
    )
    batches = [
        ArrayBatch({"key": keys[i : i + 512], "ts": ts[i : i + 512]})
        for i in range(0, n, 512)
    ]

    clock = EventClock(
        ts_getter=lambda item: item,  # unused on the columnar path
        wait_for_system_duration=timedelta(seconds=5),
    )
    windower = TumblingWindower(length=timedelta(minutes=1), align_to=ALIGN)
    out = []
    flow = Dataflow("test_df")
    s = op.input("inp", flow, ArraySource(batches))
    wo = w.count_window("count", s, clock, windower, key=lambda item: item)
    op.output("out", wo.down, TestingSink(out))
    run_main(flow)

    assert sum(c for _k, (_w, c) in out) == n
    # Spot-check one window against numpy.
    k0w0 = [
        c for k, (wid, c) in out if k == "key0" and wid == 0
    ]
    expect = int(((keys == "key0") & (secs < 60)).sum())
    assert k0w0 == [expect]


def test_columnar_batches_degrade_on_host_tier(monkeypatch):
    # With accel disabled, {'key','ts'} columnar batches must still
    # key and count correctly through the host tier.
    monkeypatch.setenv("BYTEWAX_TPU_ACCEL", "0")
    from bytewax_tpu.engine.arrays import ArrayBatch
    from tests.test_xla import ArraySource

    secs = np.array([1, 2, 61])
    keys = np.array(["a", "b", "a"])
    ts = (
        np.datetime64(ALIGN.replace(tzinfo=None), "us")
        + secs.astype("timedelta64[s]")
    )
    batches = [ArrayBatch({"key": keys, "ts": ts})]
    clock = EventClock(
        ts_getter=lambda item: item,
        wait_for_system_duration=timedelta(seconds=5),
    )
    out = []
    flow = Dataflow("test_df")
    s = op.input("inp", flow, ArraySource(batches))
    wo = w.count_window(
        "count",
        s,
        clock,
        TumblingWindower(length=timedelta(minutes=1), align_to=ALIGN),
        key=lambda item: item,
    )
    op.output("out", wo.down, TestingSink(out))
    run_main(flow)
    assert sorted(out) == [("a", (0, 1)), ("a", (1, 1)), ("b", (0, 1))]


def test_windowed_sum_columnar_matches_host(monkeypatch):
    # Numeric windowed folds on columnar key/ts/value batches: device
    # result must match the host tier folding the same rows as items.
    from bytewax_tpu import xla
    from bytewax_tpu.engine.arrays import ArrayBatch
    from tests.test_xla import ArraySource

    n = 4000
    rng = np.random.RandomState(5)
    secs = np.sort(rng.randint(0, 600, size=n))
    keys = np.array([f"key{k}" for k in rng.randint(0, 3, size=n)])
    vals = rng.randn(n).astype(np.float64).round(3)
    ts = (
        np.datetime64(ALIGN.replace(tzinfo=None), "us")
        + secs.astype("timedelta64[s]")
    )
    windower = TumblingWindower(length=timedelta(minutes=1), align_to=ALIGN)

    def run_device():
        monkeypatch.setenv("BYTEWAX_TPU_ACCEL", "1")
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
        clock = EventClock(
            ts_getter=lambda item: item,
            wait_for_system_duration=timedelta(seconds=30),
        )
        out = []
        flow = Dataflow("test_df")
        s = op.input("inp", flow, ArraySource(batches))
        wo = w.reduce_window("sum", s, clock, windower, xla.SUM)
        op.output("out", wo.down, TestingSink(out))
        run_main(flow)
        return out

    # Numpy oracle: input is time-sorted so nothing is late; expected
    # is a plain groupby-sum over (key, window).
    expected = {}
    for k, s_, v in zip(keys.tolist(), secs.tolist(), vals.tolist()):
        wid = s_ // 60
        expected[(k, wid)] = expected.get((k, wid), 0.0) + v

    device = {(k, wid): v for k, (wid, v) in run_device()}
    assert set(device) == set(expected)
    for key in expected:
        assert abs(device[key] - expected[key]) < 1e-3, key


def test_windowed_sum_itemized_falls_back_to_host(monkeypatch):
    # Itemized deliveries into a numeric windowed fold run host-tier.
    from bytewax_tpu import xla

    monkeypatch.setenv("BYTEWAX_TPU_ACCEL", "1")
    windower = TumblingWindower(length=timedelta(minutes=1), align_to=ALIGN)
    inp = [
        ("k", (ALIGN + timedelta(seconds=1), 2.0)),
        ("k", (ALIGN + timedelta(seconds=2), 3.0)),
    ]
    out = []
    flow = Dataflow("test_df")
    s = op.input("inp", flow, TestingSource(inp))
    vs = op.map_value("unpack", s, lambda pair: pair[1])
    clock2 = EventClock(
        ts_getter=_TsFromPairStream(inp),
        wait_for_system_duration=timedelta(seconds=5),
    )
    wo = w.reduce_window("sum", vs, clock2, windower, xla.SUM)
    op.output("out", wo.down, TestingSink(out))
    run_main(flow)
    assert out == [("k", (0, 5.0))]


class _TsFromPairStream:
    """Host-tier ts getter for bare values in this test."""

    def __init__(self, inp):
        self._ts = {v: t for _k, (t, v) in inp}

    def __call__(self, v):
        return self._ts[v]


def test_windowed_fold_nonconforming_columnar_falls_back(monkeypatch):
    # A columnar batch with ts but no value column must fall back to
    # the host tier (degrading to keyed items), not crash.
    from bytewax_tpu import xla
    from bytewax_tpu.engine.arrays import ArrayBatch
    from tests.test_xla import ArraySource

    monkeypatch.setenv("BYTEWAX_TPU_ACCEL", "1")
    ts = (
        np.datetime64(ALIGN.replace(tzinfo=None), "us")
        + np.array([1, 2]).astype("timedelta64[s]")
    )
    batches = [ArrayBatch({"key": np.array(["k", "k"]), "ts": ts})]
    clock = EventClock(
        ts_getter=lambda v: v,  # host degrade: value IS the timestamp
        wait_for_system_duration=timedelta(seconds=5),
    )
    windower = TumblingWindower(length=timedelta(minutes=1), align_to=ALIGN)
    out = []
    flow = Dataflow("test_df")
    s = op.input("inp", flow, ArraySource(batches))
    wo = w.reduce_window("max", s, clock, windower, xla.MAX)
    op.output("out", wo.down, TestingSink(out))
    run_main(flow)
    assert out == [("k", (0, ALIGN + timedelta(seconds=2)))]


def test_high_cardinality_windowed_count(monkeypatch):
    # 20k keys with open windows: the per-batch due check must stay
    # vectorized (this is a smoke bound, not a benchmark).
    import time

    monkeypatch.setenv("BYTEWAX_TPU_ACCEL", "1")
    from bytewax_tpu.engine.arrays import ArrayBatch
    from tests.test_xla import ArraySource

    n_keys = 20_000
    rows_per_batch = n_keys
    n_batches = 5
    keys = np.array([f"key{i:05d}" for i in range(n_keys)])
    batches = []
    for b in range(n_batches):
        ts = (
            np.datetime64(ALIGN.replace(tzinfo=None), "us")
            + np.full(rows_per_batch, b, dtype=np.int64).astype(
                "timedelta64[s]"
            )
        )
        batches.append(ArrayBatch({"key": keys, "ts": ts}))

    clock = EventClock(
        ts_getter=lambda item: item,
        wait_for_system_duration=timedelta(seconds=60),
    )
    windower = TumblingWindower(length=timedelta(minutes=1), align_to=ALIGN)
    out = []
    flow = Dataflow("test_df")
    s = op.input("inp", flow, ArraySource(batches))
    wo = w.count_window("count", s, clock, windower, key=lambda item: item)
    op.output("out", wo.down, TestingSink(out))
    t0 = time.monotonic()
    run_main(flow)
    elapsed = time.monotonic() - t0
    assert len(out) == n_keys
    assert all(c == n_batches for _k, (_w, c) in out)
    assert elapsed < 30, f"high-cardinality run too slow: {elapsed:.1f}s"


def test_windowed_sum_columnar_degrades_on_host_tier(monkeypatch):
    # {'key','ts','value'} batches must degrade to (key, TsValue)
    # items so the host-tier oracle (BYTEWAX_TPU_ACCEL=0) keys, times,
    # and folds them correctly.
    monkeypatch.setenv("BYTEWAX_TPU_ACCEL", "0")
    from bytewax_tpu import xla
    from bytewax_tpu.engine.arrays import ArrayBatch
    from tests.test_xla import ArraySource

    secs = np.array([1, 2, 61])
    keys = np.array(["a", "b", "a"])
    vals = np.array([2.0, 5.0, 7.0])
    ts = (
        np.datetime64(ALIGN.replace(tzinfo=None), "us")
        + secs.astype("timedelta64[s]")
    )
    batches = [ArrayBatch({"key": keys, "ts": ts, "value": vals})]
    clock = EventClock(
        ts_getter=xla.column_ts,
        wait_for_system_duration=timedelta(seconds=5),
    )
    out = []
    flow = Dataflow("test_df")
    s = op.input("inp", flow, ArraySource(batches))
    wo = w.reduce_window(
        "sum",
        s,
        clock,
        TumblingWindower(length=timedelta(minutes=1), align_to=ALIGN),
        xla.SUM,
    )
    op.output("out", wo.down, TestingSink(out))
    run_main(flow)
    assert sorted(out) == [("a", (0, 2.0)), ("a", (1, 7.0)), ("b", (0, 5.0))]


def test_ts_value_degrade_shapes():
    # The {'key','ts','value'} to_pylist convention: (key, TsValue)
    # pairs whose payload folds as a float and carries .ts, applying
    # any fixed-point value_scale; survives pickling (cluster ship).
    import pickle

    from bytewax_tpu.engine.arrays import ArrayBatch, TsValue, column_ts

    ts = (
        np.datetime64(ALIGN.replace(tzinfo=None), "us")
        + np.array([1, 2]).astype("timedelta64[s]")
    )
    ab = ArrayBatch(
        {
            "key": np.array(["a", "b"]),
            "ts": ts,
            "value": np.array([25, -5], dtype=np.int16),
        },
        value_scale=0.1,
    )
    items = ab.to_pylist()
    assert [k for k, _v in items] == ["a", "b"]
    assert [float(v) for _k, v in items] == [2.5, -0.5]
    assert [column_ts(v) for _k, v in items] == [
        ALIGN + timedelta(seconds=1),
        ALIGN + timedelta(seconds=2),
    ]
    v2 = pickle.loads(pickle.dumps(items[0][1]))
    assert isinstance(v2, TsValue)
    assert (float(v2), v2.ts) == (2.5, ALIGN + timedelta(seconds=1))


def test_window_accel_host_to_device_recovery(tmp_path, monkeypatch):
    # An ordered=True host-tier window logic keeps on-time values
    # whose ts is ahead of the watermark in its snapshot `queue`;
    # resuming that snapshot on the device tier must replay them into
    # their windows, not drop them.
    from bytewax_tpu import xla
    from bytewax_tpu.recovery import RecoveryConfig, init_db_dir

    init_db_dir(tmp_path, 1)
    rc = RecoveryConfig(str(tmp_path))
    ts_map = {
        2.0: ALIGN + timedelta(seconds=1),
        3.0: ALIGN + timedelta(seconds=2),
        4.0: ALIGN + timedelta(seconds=3),
    }
    clock = EventClock(
        ts_getter=lambda v: ts_map[v],
        wait_for_system_duration=timedelta(days=999),
    )
    windower = TumblingWindower(length=timedelta(minutes=1), align_to=ALIGN)
    inp = [
        ("k", 2.0),
        ("k", 3.0),
        TestingSource.ABORT(),
        ("k", 4.0),
    ]
    out = []
    flow = Dataflow("test_df")
    s = op.input("inp", flow, TestingSource(inp))
    # fold_window (not reduce_window) because only ordered=True logics
    # carry a queue, and reduce_window lowers with ordered=False.
    wo = w.fold_window("sum", s, clock, windower, lambda: 0, xla.SUM, xla.SUM)
    op.output("out", wo.down, TestingSink(out))

    # Crash on the host tier (pending values live in `queue`), resume
    # on the device tier.
    monkeypatch.setenv("BYTEWAX_TPU_ACCEL", "0")
    run_main(flow, epoch_interval=timedelta(0), recovery_config=rc)
    assert out == []
    monkeypatch.setenv("BYTEWAX_TPU_ACCEL", "1")
    run_main(flow, epoch_interval=timedelta(0), recovery_config=rc)
    assert out == [("k", (0, 9))]


@pytest.mark.parametrize("kind", ["mean", "stats"])
def test_windowed_mean_stats_device_matches_host(monkeypatch, kind):
    # mean/stats windowed folds lower to the device slot table; output
    # must match the host tier folding the same columnar rows.
    import bytewax_tpu.operators.windowing as w2
    from bytewax_tpu import xla
    from bytewax_tpu.engine.arrays import ArrayBatch
    from tests.test_xla import ArraySource

    n = 3000
    rng = np.random.RandomState(11)
    secs = np.sort(rng.randint(0, 300, size=n))
    keys = np.array([f"key{k}" for k in rng.randint(0, 3, size=n)])
    vals = (rng.randn(n) * 5).round(2)
    ts = (
        np.datetime64(ALIGN.replace(tzinfo=None), "us")
        + secs.astype("timedelta64[s]")
    )
    windower = TumblingWindower(length=timedelta(minutes=1), align_to=ALIGN)
    op_fn = w2.mean_window if kind == "mean" else w2.stats_window

    def run(accel):
        monkeypatch.setenv("BYTEWAX_TPU_ACCEL", accel)
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
        clock = EventClock(
            ts_getter=xla.column_ts,
            wait_for_system_duration=timedelta(seconds=30),
        )
        out = []
        flow = Dataflow("test_df")
        s = op.input("inp", flow, ArraySource(batches))
        wo = op_fn(kind, s, clock, windower)
        op.output("out", wo.down, TestingSink(out))
        run_main(flow)
        return sorted(out)

    device, host = run("1"), run("0")
    assert [kv[0] for kv in device] == [kv[0] for kv in host]
    for (k, (wid_d, v_d)), (_k, (wid_h, v_h)) in zip(device, host):
        assert wid_d == wid_h
        np.testing.assert_allclose(v_d, v_h, rtol=1e-4, err_msg=k)

    # And against a numpy oracle (mean case).
    if kind == "mean":
        expected = {}
        for k, s_, v in zip(keys.tolist(), secs.tolist(), vals.tolist()):
            expected.setdefault((k, s_ // 60), []).append(v)
        got = {(k, wid): v for k, (wid, v) in device}
        assert set(got) == set(expected)
        for key2, rows in expected.items():
            np.testing.assert_allclose(
                got[key2], np.mean(rows), rtol=1e-4, err_msg=str(key2)
            )


def test_fold_window_with_mean_marker_is_annotated():
    # The VERDICT bar: fold_window(..., MEAN)-style flows lower.
    from bytewax_tpu import xla
    from bytewax_tpu.engine.flatten import flatten
    from bytewax_tpu.engine.window_accel import WindowAccelSpec

    clock = EventClock(
        ts_getter=lambda v: ALIGN, wait_for_system_duration=timedelta(0)
    )
    windower = TumblingWindower(length=timedelta(minutes=1), align_to=ALIGN)
    flow = Dataflow("test_df")
    s = op.input("inp", flow, TestingSource([]))
    wo = w.fold_window(
        "m", s, clock, windower, xla.MEAN.make_acc, xla.MEAN, xla.MEAN.merge
    )
    op.output("out", wo.down, TestingSink([]))
    plan = flatten(flow)
    stateful = [o for o in plan.ops if o.name == "stateful_batch"]
    spec = stateful[0].conf.get("_accel")
    assert isinstance(spec, WindowAccelSpec)
    assert spec.kind == "mean"


def test_mean_window_cross_tier_recovery(tmp_path, monkeypatch):
    # mean windows crash on the device tier and resume on the host
    # tier (and the accumulator format crosses over).
    import bytewax_tpu.operators.windowing as w2
    from bytewax_tpu.recovery import RecoveryConfig, init_db_dir

    init_db_dir(tmp_path, 1)
    rc = RecoveryConfig(str(tmp_path))
    ts_map = {
        2.0: ALIGN + timedelta(seconds=1),
        4.0: ALIGN + timedelta(seconds=2),
        9.0: ALIGN + timedelta(seconds=3),
    }
    clock = EventClock(
        ts_getter=lambda v: ts_map[v],
        wait_for_system_duration=timedelta(days=999),
    )
    windower = TumblingWindower(length=timedelta(minutes=1), align_to=ALIGN)
    inp = [
        ("k", 2.0),
        ("k", 4.0),
        TestingSource.ABORT(),
        ("k", 9.0),
    ]
    out = []
    flow = Dataflow("test_df")
    s = op.input("inp", flow, TestingSource(inp))
    wo = w2.mean_window("mean", s, clock, windower)
    op.output("out", wo.down, TestingSink(out))

    monkeypatch.setenv("BYTEWAX_TPU_ACCEL", "1")
    run_main(flow, epoch_interval=timedelta(0), recovery_config=rc)
    assert out == []
    monkeypatch.setenv("BYTEWAX_TPU_ACCEL", "0")
    run_main(flow, epoch_interval=timedelta(0), recovery_config=rc)
    assert out == [("k", (0, 5.0))]


def test_count_window_dict_encoded_columnar(monkeypatch):
    # {'key_id','ts'} + vocab batches count on device without string
    # sorting; results match the string-keyed columnar path and the
    # host tier (which degrades through the vocab).
    from bytewax_tpu.engine.arrays import ArrayBatch
    from tests.test_xla import ArraySource

    n = 4000
    rng = np.random.RandomState(9)
    secs = np.sort(rng.randint(0, 600, size=n))
    ids = rng.randint(0, 5, size=n).astype(np.int32)
    vocab = np.array([f"key{k}" for k in range(5)])
    ts = (
        np.datetime64(ALIGN.replace(tzinfo=None), "us")
        + secs.astype("timedelta64[s]")
    )

    def build(out, encoded):
        if encoded:
            batches = [
                ArrayBatch(
                    {"key_id": ids[i : i + 512], "ts": ts[i : i + 512]},
                    key_vocab=vocab,
                )
                for i in range(0, n, 512)
            ]
        else:
            batches = [
                ArrayBatch(
                    {"key": vocab[ids[i : i + 512]], "ts": ts[i : i + 512]}
                )
                for i in range(0, n, 512)
            ]
        clock = EventClock(
            ts_getter=lambda item: item,
            wait_for_system_duration=timedelta(seconds=5),
        )
        windower = TumblingWindower(
            length=timedelta(minutes=1), align_to=ALIGN
        )
        flow = Dataflow("test_df")
        s = op.input("inp", flow, ArraySource(batches))
        wo = w.count_window("count", s, clock, windower, key=lambda x: x)
        op.output("out", wo.down, TestingSink(out))
        return flow

    monkeypatch.setenv("BYTEWAX_TPU_ACCEL", "1")
    enc, strs = [], []
    run_main(build(enc, True))
    run_main(build(strs, False))
    assert sorted(enc) == sorted(strs)
    monkeypatch.setenv("BYTEWAX_TPU_ACCEL", "0")
    host = []
    run_main(build(host, True))
    assert sorted(enc) == sorted(host)
    assert sum(c for _k, (_w, c) in enc) == n


def test_windowed_sum_dict_encoded_matches_host(monkeypatch):
    # {'key_id','ts','value'} + vocab: numeric windowed folds on the
    # dict-encoded fast path match the host tier degrade.
    from bytewax_tpu import xla
    from bytewax_tpu.engine.arrays import ArrayBatch
    from tests.test_xla import ArraySource

    n = 3000
    rng = np.random.RandomState(10)
    secs = np.sort(rng.randint(0, 300, size=n))
    ids = rng.randint(0, 4, size=n).astype(np.int32)
    vocab = np.array([f"s{k}" for k in range(4)])
    vals = (rng.randn(n) * 4).round(2)
    ts = (
        np.datetime64(ALIGN.replace(tzinfo=None), "us")
        + secs.astype("timedelta64[s]")
    )

    def run(accel):
        monkeypatch.setenv("BYTEWAX_TPU_ACCEL", accel)
        batches = [
            ArrayBatch(
                {
                    "key_id": ids[i : i + 512],
                    "ts": ts[i : i + 512],
                    "value": vals[i : i + 512],
                },
                key_vocab=vocab,
            )
            for i in range(0, n, 512)
        ]
        clock = EventClock(
            ts_getter=xla.column_ts,
            wait_for_system_duration=timedelta(seconds=30),
        )
        windower = TumblingWindower(
            length=timedelta(minutes=1), align_to=ALIGN
        )
        out = []
        flow = Dataflow("test_df")
        s = op.input("inp", flow, ArraySource(batches))
        wo = w.reduce_window("sum", s, clock, windower, xla.SUM)
        op.output("out", wo.down, TestingSink(out))
        run_main(flow)
        return sorted(out)

    device, host = run("1"), run("0")
    assert [kv[0] for kv in device] == [kv[0] for kv in host]
    for (k, (wd, vd)), (_k, (wh, vh)) in zip(device, host):
        assert wd == wh
        # Device accumulates in float32.
        np.testing.assert_allclose(vd, vh, rtol=1e-4, err_msg=k)


def test_windowed_vocab_must_extend(monkeypatch):
    # Swapping in an unrelated vocabulary between batches must raise,
    # not silently remap ids.
    monkeypatch.setenv("BYTEWAX_TPU_ACCEL", "1")
    from bytewax_tpu.engine.window_accel import (
        DeviceWindowAggState,
        WindowAccelSpec,
    )
    from bytewax_tpu.engine.arrays import ArrayBatch

    spec = WindowAccelSpec(
        "count",
        lambda x: x,
        ALIGN,
        timedelta(minutes=1),
        timedelta(minutes=1),
        timedelta(seconds=5),
    )
    st = DeviceWindowAggState(spec)
    ts = (
        np.datetime64(ALIGN.replace(tzinfo=None), "us")
        + np.array([1, 2]).astype("timedelta64[s]")
    )
    v1 = np.array(["a", "b"])
    st.on_batch_columnar(
        ArrayBatch({"key_id": np.array([0, 1]), "ts": ts}, key_vocab=v1)
    )
    v2 = np.array(["x", "b"])
    with pytest.raises(TypeError, match="append-only"):
        st.on_batch_columnar(
            ArrayBatch({"key_id": np.array([0, 1]), "ts": ts}, key_vocab=v2)
        )


def test_windowed_sum_mixed_columnar_then_itemized(monkeypatch):
    # Once device state exists (from columnar batches), later
    # itemized deliveries flow through the device fold via the ts
    # getter — a mixed stream must match the host tier end to end.
    from bytewax_tpu import xla
    from bytewax_tpu.engine.arrays import ArrayBatch
    from bytewax_tpu.inputs import DynamicSource, StatelessSourcePartition

    ts0 = ALIGN + timedelta(seconds=1)
    ts1 = ALIGN + timedelta(seconds=2)
    ts2 = ALIGN + timedelta(seconds=70)
    col = ArrayBatch(
        {
            "key": np.array(["a", "b"]),
            "ts": np.array(
                [np.datetime64(ts0.replace(tzinfo=None), "us"),
                 np.datetime64(ts1.replace(tzinfo=None), "us")]
            ),
            "value": np.array([2.0, 5.0]),
        }
    )
    itemized = [
        ("a", xla.TsValue(3.0, ts1)),
        ("b", xla.TsValue(7.0, ts2)),
    ]

    class _P(StatelessSourcePartition):
        def __init__(self):
            self._batches = [col, itemized]

        def next_batch(self):
            if not self._batches:
                raise StopIteration()
            return self._batches.pop(0)

    class Src(DynamicSource):
        def build(self, step_id, wi, wc):
            p = _P()
            if wi != 0:
                p._batches = []
            return p

    def run(accel):
        monkeypatch.setenv("BYTEWAX_TPU_ACCEL", accel)
        clock = EventClock(
            ts_getter=xla.column_ts,
            wait_for_system_duration=timedelta(seconds=5),
        )
        windower = TumblingWindower(
            length=timedelta(minutes=1), align_to=ALIGN
        )
        out = []
        flow = Dataflow("test_df")
        s = op.input("inp", flow, Src())
        wo = w.reduce_window("sum", s, clock, windower, xla.SUM)
        op.output("out", wo.down, TestingSink(out))
        run_main(flow)
        return sorted(out)

    device, host = run("1"), run("0")
    assert device == host == [
        ("a", (0, 5.0)),
        ("b", (0, 5.0)),
        ("b", (1, 7.0)),
    ]


def test_windowed_fallback_boundary_then_columnar(monkeypatch):
    # Itemized rows BEFORE any device state permanently fall the step
    # back to the host tier; columnar batches arriving afterwards must
    # still fold correctly (degraded), matching an all-host run.
    from bytewax_tpu import xla
    from bytewax_tpu.engine.arrays import ArrayBatch
    from bytewax_tpu.inputs import DynamicSource, StatelessSourcePartition

    ts0 = ALIGN + timedelta(seconds=1)
    itemized = [("a", xla.TsValue(2.0, ts0))]
    col = ArrayBatch(
        {
            "key": np.array(["a"]),
            "ts": np.array([np.datetime64(ts0.replace(tzinfo=None), "us")]),
            "value": np.array([3.0]),
        }
    )

    class _P(StatelessSourcePartition):
        def __init__(self):
            self._batches = [itemized, col]

        def next_batch(self):
            if not self._batches:
                raise StopIteration()
            return self._batches.pop(0)

    class Src(DynamicSource):
        def build(self, step_id, wi, wc):
            p = _P()
            if wi != 0:
                p._batches = []
            return p

    def run(accel):
        monkeypatch.setenv("BYTEWAX_TPU_ACCEL", accel)
        clock = EventClock(
            ts_getter=xla.column_ts,
            wait_for_system_duration=timedelta(seconds=5),
        )
        windower = TumblingWindower(
            length=timedelta(minutes=1), align_to=ALIGN
        )
        out = []
        flow = Dataflow("test_df")
        s = op.input("inp", flow, Src())
        wo = w.reduce_window("sum", s, clock, windower, xla.SUM)
        op.output("out", wo.down, TestingSink(out))
        run_main(flow)
        return sorted(out)

    device, host = run("1"), run("0")
    assert device == host == [("a", (0, 5.0))]


def test_dict_encoded_window_cross_tier_recovery(tmp_path, monkeypatch):
    # Dict-encoded windowed batches crash on the device tier and
    # resume on the host tier (and the vocab re-syncs after resume on
    # the device tier).
    from bytewax_tpu import xla
    from bytewax_tpu.engine.arrays import ArrayBatch
    from bytewax_tpu.inputs import FixedPartitionedSource, StatefulSourcePartition
    from bytewax_tpu.recovery import RecoveryConfig, init_db_dir

    init_db_dir(tmp_path, 1)
    rc = RecoveryConfig(str(tmp_path))
    vocab = np.array(["a", "b"])
    base = np.datetime64(ALIGN.replace(tzinfo=None), "us")

    def batch(ids, secs, vals):
        return ArrayBatch(
            {
                "key_id": np.asarray(ids, dtype=np.int32),
                "ts": base + np.asarray(secs).astype("timedelta64[s]"),
                "value": np.asarray(vals, dtype=np.float64),
            },
            key_vocab=vocab,
        )

    crashed: list = []  # the crash marker fires once, like ABORT

    class _Part(StatefulSourcePartition):
        def __init__(self, resume):
            self._i = resume or 0
            self._batches = [
                batch([0, 1], [1, 2], [2.0, 5.0]),
                None,  # crash marker
                batch([0, 1], [3, 4], [3.0, 7.0]),
            ]

        def next_batch(self):
            while True:
                if self._i >= len(self._batches):
                    raise StopIteration()
                b = self._batches[self._i]
                self._i += 1
                if b is None:
                    if not crashed:
                        crashed.append(True)
                        from bytewax_tpu.inputs import AbortExecution

                        raise AbortExecution()
                    continue
                return b

        def snapshot(self):
            return self._i

    class Src(FixedPartitionedSource):
        def list_parts(self):
            return ["p0"]

        def build_part(self, step_id, name, resume):
            return _Part(resume)

    def build(out):
        clock = EventClock(
            ts_getter=xla.column_ts,
            wait_for_system_duration=timedelta(days=999),
        )
        windower = TumblingWindower(
            length=timedelta(minutes=1), align_to=ALIGN
        )
        flow = Dataflow("test_df")
        s = op.input("inp", flow, Src())
        wo = w.reduce_window("sum", s, clock, windower, xla.SUM)
        op.output("out", wo.down, TestingSink(out))
        return flow

    out: list = []
    monkeypatch.setenv("BYTEWAX_TPU_ACCEL", "1")
    # The crash marker only fires on the first execution: resumes skip
    # it because the partition snapshot is already past its index.
    run_main(build(out), epoch_interval=timedelta(0), recovery_config=rc)
    assert out == []
    monkeypatch.setenv("BYTEWAX_TPU_ACCEL", "0")
    run_main(build(out), epoch_interval=timedelta(0), recovery_config=rc)
    assert sorted(out) == [("a", (0, 5.0)), ("b", (0, 12.0))]


def test_key_id_without_vocab_raises_clearly():
    # A key_id column invokes the dict convention; forgetting the
    # vocab must be a clear error, not silently mis-keyed rows.
    from bytewax_tpu.engine.arrays import ArrayBatch

    ts = (
        np.datetime64(ALIGN.replace(tzinfo=None), "us")
        + np.array([1]).astype("timedelta64[s]")
    )
    for cols in (
        {"key_id": np.array([0]), "ts": ts},
        {"key_id": np.array([0]), "ts": ts, "value": np.array([1.0])},
        {"key_id": np.array([0]), "value": np.array([1.0])},
    ):
        with pytest.raises(TypeError, match="key_vocab"):
            ArrayBatch(cols).to_pylist()


def test_itemized_promotion_unit_matches_per_item_path():
    """on_batch_items (native wa_encode promotion) must produce the
    same events and snapshots as the per-item on_batch path for both
    row shapes: (key, datetime) counts and (key, TsValue) sums."""
    from bytewax_tpu import xla
    from bytewax_tpu.engine.window_accel import (
        DeviceWindowAggState,
        WindowAccelSpec,
    )

    pytest.importorskip("bytewax_tpu.native")
    from bytewax_tpu.native import wa_encode as _probe

    if _probe([], {}, np.empty(0, np.int32), np.empty(0), np.empty(0)) is None:
        pytest.skip("native toolchain unavailable")

    def specs(kind, getter):
        return WindowAccelSpec(
            kind,
            getter,
            ALIGN,
            timedelta(minutes=1),
            timedelta(minutes=1),
            timedelta(0),
        )

    def run(ingest):
        # on_batch* return (late_events, device_phase); materialize
        # the deferred phase to get the full event stream.
        late, phase = ingest
        closes, _hint, _gone = phase()
        return list(late + closes)

    # Count shape: values ARE the timestamps.
    items = [
        ("a", ALIGN + timedelta(seconds=s)) for s in (1, 2, 61, 150)
    ] + [("b", ALIGN + timedelta(seconds=5))]
    st_promo = specs("count", lambda x: x).make_state()
    st_items = specs("count", lambda x: x).make_state()
    ev_promo = st_promo.on_batch_items(list(items))
    assert ev_promo is not None
    ev_items = st_items.on_batch(
        [k for k, _ in items], [v for _, v in items]
    )
    assert run(ev_promo) == run(ev_items)
    assert dict(st_promo.snapshots_for(["a", "b"])).keys() == dict(
        st_items.snapshots_for(["a", "b"])
    ).keys()

    # TsValue shape: floats carrying their event timestamp.
    rows = [
        ("a", xla.TsValue(2.0, ALIGN + timedelta(seconds=1))),
        ("a", xla.TsValue(3.0, ALIGN + timedelta(seconds=2))),
        ("b", xla.TsValue(7.0, ALIGN + timedelta(seconds=61))),
    ]
    st2_promo = specs("sum", xla.column_ts).make_state()
    st2_items = specs("sum", xla.column_ts).make_state()
    ev2_promo = st2_promo.on_batch_items(list(rows))
    assert ev2_promo is not None
    ev2_items = st2_items.on_batch(
        [k for k, _ in rows], [v for _, v in rows]
    )
    assert run(ev2_promo) == run(ev2_items)


def test_itemized_promotion_rejects_disagreeing_getter():
    """A ts_getter that does NOT read the row's own timestamp must
    force the per-item path (NonNumericValues), not silently use the
    row timestamp."""
    from bytewax_tpu.engine.window_accel import WindowAccelSpec
    from bytewax_tpu.engine.xla import NonNumericValues
    from bytewax_tpu.native import wa_encode as _probe

    if _probe([], {}, np.empty(0, np.int32), np.empty(0), np.empty(0)) is None:
        pytest.skip("native toolchain unavailable")

    shifted = WindowAccelSpec(
        "count",
        lambda x: x + timedelta(hours=1),  # disagrees with the row ts
        ALIGN,
        timedelta(minutes=1),
        timedelta(minutes=1),
        timedelta(0),
    ).make_state()
    with pytest.raises(NonNumericValues):
        shifted.on_batch_items([("a", ALIGN + timedelta(seconds=1))])


def test_itemized_promotion_rejects_non_utc():
    """Non-UTC tzinfo rows take the per-item path (its .timestamp()
    handles any tz); the native promotion must refuse them."""
    from bytewax_tpu.engine.window_accel import WindowAccelSpec
    from bytewax_tpu.engine.xla import NonNumericValues
    from bytewax_tpu.native import wa_encode as _probe

    if _probe([], {}, np.empty(0, np.int32), np.empty(0), np.empty(0)) is None:
        pytest.skip("native toolchain unavailable")

    offset_tz = timezone(timedelta(hours=2))
    st = WindowAccelSpec(
        "count",
        lambda x: x,
        ALIGN,
        timedelta(minutes=1),
        timedelta(minutes=1),
        timedelta(0),
    ).make_state()
    with pytest.raises(NonNumericValues):
        st.on_batch_items(
            [("a", datetime(2022, 1, 1, 2, 0, 1, tzinfo=offset_tz))]
        )


def test_itemized_tsvalue_flow_device_matches_host(monkeypatch):
    """End-to-end: a TsValue itemized stream through reduce_window
    rides the promotion on the device tier and matches the host tier
    exactly."""
    from bytewax_tpu import xla

    rng = np.random.RandomState(4)
    inp = [
        (
            f"k{rng.randint(0, 3)}",
            xla.TsValue(
                float(np.round(rng.randn(), 3)),
                ALIGN + timedelta(seconds=int(s)),
            ),
        )
        for s in range(300)
    ]
    windower = TumblingWindower(length=timedelta(minutes=1), align_to=ALIGN)

    def run(accel):
        monkeypatch.setenv("BYTEWAX_TPU_ACCEL", "1" if accel else "0")
        clock = EventClock(
            ts_getter=xla.column_ts,
            wait_for_system_duration=timedelta(0),
        )
        out = []
        flow = Dataflow("test_df")
        s = op.input("inp", flow, TestingSource(list(inp), batch_size=32))
        wo = w.reduce_window("sum", s, clock, windower, xla.SUM)
        op.output("out", wo.down, TestingSink(out))
        run_main(flow)
        return out

    got = run(True)
    want = run(False)
    gd = {(k, wid): v for k, (wid, v) in got}
    wd = {(k, wid): v for k, (wid, v) in want}
    assert gd.keys() == wd.keys()
    for kw in wd:
        # Device folds in f32; host in f64.
        assert gd[kw] == pytest.approx(wd[kw], abs=1e-4)


# -- the batched open / read / release path (integer composites) -----------
#
# Shared with tests/test_sharded.py, which runs the state-level cases
# over several host devices.

TUMBLING_10S = TumblingWindower(length=timedelta(seconds=10), align_to=ALIGN)
# An offset that does not divide the length: a row is in 2 or 3 windows.
SLIDING_10S_BY_4S = SlidingWindower(
    length=timedelta(seconds=10), offset=timedelta(seconds=4), align_to=ALIGN
)


def _spec_of(kind, windower, meta_live=True):
    offset = getattr(windower, "offset", windower.length)
    spec = WindowAccelSpec(
        kind, lambda v: v.ts, ALIGN, windower.length, offset, timedelta(0)
    )
    spec.meta_live = meta_live
    return spec


def _deliver(st, keys, secs, vals):
    """One columnar delivery through the state's public entry point;
    returns the delivery's late and close events."""
    from bytewax_tpu.engine.arrays import ArrayBatch

    batch = ArrayBatch(
        {
            "key": np.asarray(keys),
            "ts": np.datetime64(ALIGN.replace(tzinfo=None), "us")
            + np.asarray(secs).astype("timedelta64[s]"),
            "value": np.asarray(vals, dtype=np.float64),
        }
    )
    late, phase = st.on_batch_columnar(batch)
    closes, _hint, gone = phase()
    st.let_go(gone)  # as the driver does where the phase is finalized
    return late + closes


def _types_of(obj):
    if isinstance(obj, (tuple, list)):
        return tuple(_types_of(x) for x in obj)
    # The host tier's min / max hand back the TsValue (a float that
    # also carries its row's timestamp) they were given.
    return float if isinstance(obj, float) else type(obj)


def _tap_flow(kind, windower, meta, inp):
    """A windowed flow over timestamped rows with its ``down`` and
    ``late`` taps read, and ``meta`` read or left to be pruned."""
    from bytewax_tpu import xla

    taps = {"down": [], "late": [], "meta": []}
    flow = Dataflow("test_df")
    s = op.input("inp", flow, TestingSource(list(inp), batch_size=50))
    clock = EventClock(
        ts_getter=(lambda kv: kv[1]) if kind == "count" else xla.column_ts,
        wait_for_system_duration=timedelta(seconds=20),
    )
    if kind == "count":
        wo = w.count_window("win", s, clock, windower, key=lambda kv: kv[0])
    else:
        wo = w.fold_window(
            "win",
            s,
            clock,
            windower,
            xla.STATS.make_acc,
            xla.STATS,
            xla.STATS.merge,
        )
    op.output("down", wo.down, TestingSink(taps["down"]))
    op.output("late", wo.late, TestingSink(taps["late"]))
    if meta:
        op.output("meta", wo.meta, TestingSink(taps["meta"]))
    return flow, taps


def _rows_with_late(n=400, seed=5):
    """Integer-valued readings a second apart in event time, jittered
    a few seconds (on time under the 20 s wait) with every 37th row
    200 s behind (late on both tiers, whatever the wall clock does)."""
    from bytewax_tpu import xla

    rng = np.random.RandomState(seed)
    rows = []
    for i in range(n):
        sec = 300 + i - int(rng.randint(0, 4))
        if i % 37 == 36:
            sec -= 200
        rows.append(
            (
                f"k{rng.randint(0, 3)}",
                xla.TsValue(
                    float(rng.randint(-50, 50)),
                    ALIGN + timedelta(seconds=sec),
                ),
            )
        )
    return rows


@pytest.mark.parametrize("meta", [False, True], ids=["meta_pruned", "meta_read"])
@pytest.mark.parametrize(
    "windower", [TUMBLING_10S, SLIDING_10S_BY_4S], ids=["tumbling", "sliding"]
)
@pytest.mark.parametrize("kind", ["count", "stats"])
@pytest.mark.parametrize("shard", ["0", "auto"], ids=["one_device", "mesh"])
def test_batched_close_matches_host_tier(
    monkeypatch, shard, kind, windower, meta
):
    """Device tier against the host tier, the oracle: the same events
    on every tap the flow reads, in the same order, with the same
    Python types (integer-valued readings fold exactly in float32)."""
    from bytewax_tpu.engine import flight

    inp = _rows_with_late()
    if kind == "count":
        inp = [(k, v.ts) for k, v in inp]
    monkeypatch.setenv("BYTEWAX_TPU_SHARD", shard)

    def run(accel):
        monkeypatch.setenv("BYTEWAX_TPU_ACCEL", accel)
        flow, taps = _tap_flow(kind, windower, meta, inp)
        before = dict(flight.RECORDER.counters)
        run_main(flow)
        gained = {
            name: flight.RECORDER.counters.get(name, 0) - before.get(name, 0)
            for name in ("window_opens", "window_meta_events")
        }
        return taps, gained

    (device, counted), (host, _) = run("1"), run("0")
    assert len(device["late"]) >= 10 and len(device["down"]) > 100
    for tap in ("down", "late", "meta"):
        # One key's events keep their order on both tiers; across
        # keys the host tier goes key by key and the device tier in
        # the order windows were opened, so those are compared sorted.
        for key in ("k0", "k1", "k2"):
            assert [e for e in device[tap] if e[0] == key] == [
                e for e in host[tap] if e[0] == key
            ], (tap, key)
        assert len(device[tap]) == len(host[tap])
        assert _types_of(sorted(device[tap], key=repr)) == _types_of(
            sorted(host[tap], key=repr)
        )
    assert counted["window_opens"] == len(device["down"])
    assert counted["window_meta_events"] == len(device["meta"])
    assert (len(device["meta"]) == len(device["down"])) is meta


@pytest.mark.parametrize(
    "kind", ["sum", "min", "max", "count", "mean", "stats"]
)
def test_reused_slot_starts_from_identity(monkeypatch, kind, shard="0"):
    """A slot released by a close and given to a later window is reset
    before it folds: nothing of the closed window's state is inherited."""
    monkeypatch.setenv("BYTEWAX_TPU_SHARD", shard)
    st = _spec_of(kind, TUMBLING_10S).make_state()
    first = _deliver(st, ["a"] * 3 + ["b"], [1, 2, 3, 4], [100, -100, 7, 9])
    assert list(first) == []
    held = sorted(st.open.ids.tolist())
    # 30 s on: two new windows open, then both old ones close (wait
    # 0) and hand their slots back.
    second = _deliver(st, ["a", "b"], [31, 32], [-3, -4])
    assert sorted(e[1][0] for e in second if e[1][1] == "E") == [0, 0]
    assert not set(st.open.ids.tolist()) & set(held)
    # 30 s on again: the next two windows take the freed slots.
    _deliver(st, ["a", "b"], [61, 62], [5, 6])
    if shard == "0":  # on a mesh a freed slot serves its own shard only
        assert sorted(st.open.ids.tolist()) == held, "freed slots are reused"
    third = [e for e in st.on_eof() if e[1][1] == "E"]
    want = {
        "sum": [5.0, 6.0],
        "min": [5.0, 6.0],
        "max": [5.0, 6.0],
        "count": [1, 1],
        "mean": [(5.0, 1), (6.0, 1)],
        "stats": [(5.0, 5.0, 5.0, 1), (6.0, 6.0, 6.0, 1)],
    }[kind]
    assert [(k, wid, v) for k, (wid, _e, v) in third] == [
        ("a", 6, want[0]),
        ("b", 6, want[1]),
    ]
    assert _types_of([e[1][2] for e in third]) == _types_of(want)
    # Every key went with its last window; both are still to snapshot.
    assert st.open_count == 0 and not st.key_ids and st.is_empty() is False


@pytest.mark.parametrize(
    "windower", [TUMBLING_10S, SLIDING_10S_BY_4S], ids=["tumbling", "sliding"]
)
@pytest.mark.parametrize("shard", ["0", "auto"], ids=["one_device", "mesh"])
def test_snapshot_resumes_to_same_results(monkeypatch, shard, windower):
    """A snapshot taken before a restart resumes to the same results,
    and is the host tier's ``_WindowSnapshot`` as it always was: wid ->
    ``WindowMetadata`` under the windower state, wid -> host-format
    fold state, Python floats and an ``int`` count."""
    from bytewax_tpu.operators.windowing import (
        WindowMetadata,
        _SlidingWindowerState,
        _WindowSnapshot,
    )

    monkeypatch.setenv("BYTEWAX_TPU_SHARD", shard)
    spec = _spec_of("stats", windower)
    rng = np.random.RandomState(9)

    def rows(lo, n=200):
        secs = lo + np.arange(n) // 4
        return (
            rng.choice(["a", "b", "c"], size=n),
            secs,
            rng.randint(-9, 9, size=n),
        )

    # 30 s of event time between the two deliveries: the watermark
    # also moves with the wall clock (wait 0), and no row may turn late.
    before, after = rows(0), rows(80)
    straight = spec.make_state()
    ev_before = _deliver(straight, *before)
    snaps = straight.snapshots_for(["a", "b", "c", "never_seen"])
    assert snaps[-1] == ("never_seen", None)
    for key, snap in snaps[:3]:
        assert isinstance(snap, _WindowSnapshot), key
        assert isinstance(snap.windower_state, _SlidingWindowerState)
        opened = snap.windower_state.opened
        assert list(opened) == list(snap.logic_states) != []
        for wid, meta in opened.items():
            assert type(wid) is int and isinstance(meta, WindowMetadata)
            assert meta.close_time - meta.open_time == windower.length
        for state in snap.logic_states.values():
            assert _types_of(state) == (float, float, float, int)
        assert snap.queue == []
    resumed = spec.make_state()
    resumed.load_many([s for s in snaps if s[1] is not None])
    assert resumed.open_count == straight.open_count
    assert dict(resumed.snapshots_for(["a", "b", "c"])) == dict(snaps[:3])
    ev_straight = _deliver(straight, *after) + straight.on_eof()
    ev_resumed = _deliver(resumed, *after) + resumed.on_eof()
    assert len(ev_before) > 0 and len(ev_straight) >= 30
    # Key ids are given anew at load (in the page's order), so keys
    # may take turns differently; one key's events keep their order.
    for key in "abc":
        assert [e for e in ev_resumed if e[0] == key] == [
            e for e in ev_straight if e[0] == key
        ]
    assert len(ev_resumed) == len(ev_straight)


#: Per tier: the spans a close records (the slot table's read of the
#: fold states times itself as ``fetch`` and ``close_emit``), and the
#: keys the tier knows once ``c`` was let go.
_CLOSE_SPAN_NAMES = {
    "tumbling": ({"close_scan", "fetch", "close_emit", "retire"}, "ab"),
    "session": ({"session_close", "fetch", "close_emit"}, "abc"),
    "join": ({"join_close"}, "ab"),
}


@pytest.mark.parametrize("tier", sorted(_CLOSE_SPAN_NAMES))
def test_a_tier_closes_and_snapshots_through_the_one_form(monkeypatch, tier):
    """Each device tier closes and snapshots through the base class's
    one form: a close records the tier's spans and no other, with the
    rows the metrics read; the snapshots of every key load into a fresh
    state that snapshots the same; and a demotion drains every key the
    tier knows, a session key let go among them."""
    from bytewax_tpu import xla
    from bytewax_tpu.engine import flight
    from bytewax_tpu.engine import window_accel as wa
    from bytewax_tpu.engine.arrays import ArrayBatch

    monkeypatch.setenv("BYTEWAX_TPU_SHARD", "0")
    t0 = ALIGN + timedelta(days=400)
    now = [t0]

    class _Datetime(datetime):
        @classmethod
        def now(cls, tz=None):
            return now[0]

    monkeypatch.setattr(wa, "datetime", _Datetime)
    ten = timedelta(seconds=10)
    if tier == "tumbling":
        spec = wa.WindowAccelSpec("sum", xla.column_ts, ALIGN, ten, ten, ten)
    elif tier == "session":
        spec = wa.SessionAccelSpec("sum", xla.column_ts, ten, ten)
    else:
        spec = wa.JoinAccelSpec(2, xla.column_ts, ALIGN, ten, ten, ten)
    st = spec.make_state()

    def deliver(at_s, rows):
        """``rows``: ``(key, second, side)``; the system clock at
        ``at_s`` seconds."""
        now[0] = t0 + timedelta(seconds=at_s)
        cols = {
            "key": np.asarray([k for k, _s, _side in rows]),
            "ts": np.datetime64(ALIGN.replace(tzinfo=None), "s")
            + np.asarray([s for _k, s, _side in rows]).astype("timedelta64[s]"),
            "value": np.arange(len(rows), dtype=np.int32 if tier == "join" else np.float64),
        }
        if tier == "join":
            cols["side"] = np.asarray([side for _k, _s, side in rows], dtype=np.int8)
        _late, phase = st.on_batch_columnar(ArrayBatch(cols))
        _closes, _hint, gone = phase()
        st.let_go(gone)

    # "a" holds two windows (sessions); at 8 s the first is due (the
    # watermark at 13 s), the second not, and no key goes.
    deliver(0, [("a", 1, 0), ("a", 2, 1), ("a", 15, 0)])
    held = len(st.open)
    before = dict(flight.RECORDER.counters)
    now[0] = t0 + timedelta(seconds=8)
    closed = st.on_notify()
    moved = {
        name[: -len("_spans")]: flight.RECORDER.counters[name] - before.get(name, 0)
        for name in flight.RECORDER.counters
        if name.endswith("_spans") and flight.RECORDER.counters[name] != before.get(name, 0)
    }
    names, known = _CLOSE_SPAN_NAMES[tier]
    assert set(moved) == names

    def gained(name):
        return flight.RECORDER.counters.get(name, 0) - before.get(name, 0)

    assert len(closed.down) == len(closed.meta) == gained("window_meta_events") == 1
    if tier == "tumbling":
        assert gained("close_scan_rows") == held == 2
        assert gained("close_emit_rows") == gained("retire_rows") == 1
    elif tier == "session":
        assert gained("session_close_rows") == held + 1 == 3
        assert gained("session_closes") == 1
    else:
        assert gained("join_close_rows") == held == 3
        assert gained("join_rows_emitted") == 1

    # "c" comes and goes (a session key one delivery later); "a" and
    # "b" keep windows open.
    deliver(10, [("c", 20, 0), ("b", 21, 1)])
    deliver(40, [("b", 50, 0), ("b", 51, 1)])
    deliver(41, [("a", 52, 0)])
    assert "c" not in st.key_ids and st.open_count
    keys = ["a", "b", "c", "never"]
    snaps = st.snapshots_for(keys)
    assert [k for k, s in snaps if s is not None] == list(known)
    fresh = spec.make_state()
    fresh.load_many([(k, s) for k, s in snaps if s is not None])
    assert fresh.snapshots_for(keys) == snaps
    assert st.demotion_snapshots() == [(k, s) for k, s in snaps if k in known]


class _CountedCalls:
    """Wraps an aggregate state and counts the calls made into it."""

    def __init__(self, inner):
        self._inner = inner
        self.calls = {}

    def __getattr__(self, name):
        attr = getattr(self._inner, name)
        if not callable(attr):
            return attr

        def counted(*args, **kwargs):
            self.calls[name] = self.calls.get(name, 0) + 1
            return attr(*args, **kwargs)

        return counted


@pytest.mark.parametrize("meta", [False, True], ids=["meta_pruned", "meta_read"])
@pytest.mark.parametrize("shard", ["0", "auto"], ids=["one_device", "mesh"])
def test_delivery_makes_constant_calls_into_agg(monkeypatch, shard, meta):
    """A delivery that opens and closes N windows makes the same few
    calls into the aggregate state whatever N is, and builds N "M"
    events when ``meta`` is read and none when it was pruned."""
    from bytewax_tpu.engine import flight

    monkeypatch.setenv("BYTEWAX_TPU_SHARD", shard)

    def drive(n_windows):
        st = _spec_of("stats", TUMBLING_10S, meta_live=meta).make_state()
        st.agg = _CountedCalls(st.agg)
        secs = np.arange(n_windows) * 10
        _deliver(st, ["a"] * n_windows, secs, np.ones(n_windows))
        st.agg.calls.clear()
        before = dict(flight.RECORDER.counters)
        # As many windows open as close (the first delivery's last,
        # then all of this one's but its own last).
        events = _deliver(
            st, ["a"] * n_windows, secs + n_windows * 10, np.ones(n_windows)
        )
        gained = {
            name: flight.RECORDER.counters.get(name, 0) - before.get(name, 0)
            for name in ("window_opens", "window_meta_events")
        }
        return events, st.agg.calls, gained

    few, calls_few, _ = drive(8)
    many, calls_many, gained = drive(1000)
    assert calls_many == calls_few
    assert calls_many == {
        "open_ids": 1,
        "update_ids": 1,
        "states_of": 1,
        "release_ids": 1,
    }
    assert gained["window_opens"] == 1000
    assert len(many.down) == 1000 and not many.late
    assert gained["window_meta_events"] == (1000 if meta else 0)
    # A metadata row for each window closed, in the same order.
    assert [(k, wid) for k, (wid, _m) in many.meta] == (
        [(k, wid) for k, (wid, _v) in many.down] if meta else []
    )
    assert len(few) == (16 if meta else 8)


def _carrier_rows(n=400):
    """``(key, second)`` rows a second apart in event time with a jump
    of 40 s every 60 rows (sessions of a 10 s gap close between them),
    every 37th row 200 s behind (late on both tiers under a wait of
    100 s, whatever the wall clock does); on-time rows in order."""
    rng = np.random.RandomState(11)
    rows = []
    for i in range(n):
        sec = 300 + i + 40 * (i // 60)
        if i % 37 == 36:
            sec -= 200
        rows.append((f"k{rng.randint(0, 3)}", sec))
    return rows


def _carrier_flow(tier, rows, late, meta):
    """The window step of ``tier`` over ``rows`` in deliveries of 50
    (a join's two sides from two inputs, a delivery each in turn: a
    side's rows lie up to 90 s behind the other's, inside the wait),
    with the taps the case reads; the lists the sinks fill."""
    from bytewax_tpu import xla
    from bytewax_tpu.engine.arrays import ArrayBatch
    from tests.test_xla import ArraySource

    taps = {"down": [], "late": [], "meta": []}
    flow = Dataflow("carrier_df")
    at = np.datetime64(ALIGN.replace(tzinfo=None), "s")
    if tier == "join":
        # Two columnar sides: even rows on the first, odd on the second.
        sides = []
        for side in (0, 1):
            batches = []
            for lo in range(0, len(rows), 50):
                part = rows[lo : lo + 50][side::2]
                batches.append(
                    ArrayBatch(
                        {
                            "key": np.asarray([k for k, _s in part]),
                            "ts": at + np.asarray([s for _k, s in part]).astype("timedelta64[s]"),
                            "value": np.arange(lo, lo + len(part), dtype=np.int32),
                        }
                    )
                )
            sides.append(op.input(f"in{side}", flow, ArraySource(batches)))
        clock = EventClock(
            ts_getter=xla.column_ts, wait_for_system_duration=timedelta(seconds=100)
        )
        wo = w.join_window("win", clock, TUMBLING_10S, *sides, insert_mode="product")
    else:
        items = [(k, ALIGN + timedelta(seconds=s)) for k, s in rows]
        s = op.input("inp", flow, TestingSource(items, batch_size=50))
        clock = EventClock(
            ts_getter=lambda kv: kv[1], wait_for_system_duration=timedelta(seconds=100)
        )
        windower = {
            "tumbling": TUMBLING_10S,
            "sliding": SLIDING_10S_BY_4S,
            "session": w.SessionWindower(gap=timedelta(seconds=10)),
        }[tier]
        wo = w.count_window("win", s, clock, windower, key=lambda kv: kv[0])
    op.output("down", wo.down, TestingSink(taps["down"]))
    if late:
        op.output("late", wo.late, TestingSink(taps["late"]))
    if meta:
        op.output("meta", wo.meta, TestingSink(taps["meta"]))
    return flow, taps


@pytest.mark.parametrize("meta", [False, True], ids=["meta_off", "meta_read"])
@pytest.mark.parametrize("late", [False, True], ids=["late_pruned", "late_read"])
@pytest.mark.parametrize("workers", [1, 2], ids=["one_worker", "two_workers"])
@pytest.mark.parametrize("tier", ["tumbling", "sliding", "session", "join"])
def test_device_tiers_hand_the_taps_their_rows_built(
    monkeypatch, tier, workers, late, meta
):
    """Each device tier hands its output on as one ``WindowEvents`` a
    delivery, and the taps pass its parts on: ``down``, ``late`` and
    ``meta`` are the host tier's, item for item (one key's rows in the
    same order; across keys the tiers take turns differently), with
    no row walked by a tap on the device tier and every row written
    counted as handed on directly; among the deliveries, one carries
    late rows and closes together."""
    from bytewax_tpu.engine import driver as drv
    from bytewax_tpu.engine import flight
    from bytewax_tpu.engine.window_accel import WindowEvents
    from bytewax_tpu.testing import cluster_main

    monkeypatch.setenv("BYTEWAX_TPU_SHARD", "0")
    # Deliveries as the sources cut them on both tiers (the device
    # tier's inputs would otherwise merge them).
    monkeypatch.setenv("BYTEWAX_TPU_INGEST_TARGET_ROWS", "0")
    rows = _carrier_rows()
    carried = []
    emit = drv._StatefulBatchRt._emit_window_events

    def noted(self, events):
        carried.append((type(events), len(events.late), len(events.down)))
        return emit(self, events)

    monkeypatch.setattr(drv._StatefulBatchRt, "_emit_window_events", noted)

    def run(accel):
        monkeypatch.setenv("BYTEWAX_TPU_ACCEL", accel)
        flow, taps = _carrier_flow(tier, rows, late, meta)
        before = dict(flight.RECORDER.counters)
        if workers == 1:
            run_main(flow)
        else:
            cluster_main(flow, [], 0, worker_count_per_proc=2)
        gained = {
            name: flight.RECORDER.counters.get(name, 0) - before.get(name, 0)
            for name in ("window_rows_direct", "window_rows_tapped")
        }
        return taps, gained

    (device, dev_counted), (host, host_counted) = run("1"), run("0")
    assert len(device["down"]) > 20
    assert (len(device["late"]) >= 5) is late
    assert (len(device["meta"]) > 0) is meta
    for tap in ("down", "late", "meta"):
        assert len(device[tap]) == len(host[tap]), tap
        for key in ("k0", "k1", "k2"):
            assert [e for e in device[tap] if e[0] == key] == [
                e for e in host[tap] if e[0] == key
            ], (tap, key)
    written = sum(len(got) for got in device.values())
    assert dev_counted == {"window_rows_direct": written, "window_rows_tapped": 0}
    assert host_counted["window_rows_direct"] == 0
    assert host_counted["window_rows_tapped"] >= written
    assert carried and all(t is WindowEvents for t, _l, _d in carried)
    assert any(n_late and n_down for _t, n_late, n_down in carried)


# -- the open-window table against a plain dict model -----------------------


class _FakeSlots:
    """``open_ids`` as the aggregate state gives slots out: freed
    ones from the end of the free list, then fresh ones."""

    def __init__(self):
        self.free, self.fresh, self.asked = [], 0, []

    def open_ids(self, place):
        self.asked.append(place.copy())
        reused = [self.free.pop() for _ in range(min(len(place), len(self.free)))]
        fresh = np.arange(self.fresh, self.fresh + len(place) - len(reused))
        self.fresh += len(fresh)
        return np.concatenate([reused, fresh]).astype(np.int32)


def _comp_of(kid, wid):
    from bytewax_tpu.engine.window_accel import _WID_BIAS

    return (int(kid) << 32) + int(wid) + _WID_BIAS


def _model_closes_of(comp):
    from bytewax_tpu.engine.window_accel import _WID_BIAS, _WID_MASK

    return ((comp & _WID_MASK) - _WID_BIAS) * 5.0 + 10.0


class _TableModel:
    """The table as a dict: composite -> [slot, due instant, open
    order], with the clock each key was last retimed from."""

    def __init__(self):
        self.rows, self.clock, self.opened = {}, {}, 0

    def in_order(self):
        return sorted(self.rows, key=lambda c: self.rows[c][2])

    def open(self, uniq, table, slots):
        before = len(slots.asked)
        got = table.ids_for(uniq, slots)
        new = [c for c in uniq.tolist() if c not in self.rows]
        if new:  # one call, in ascending composite order
            assert len(slots.asked) == before + 1
            assert slots.asked[-1].tolist() == new
        else:
            assert len(slots.asked) == before
        it = iter(got[[c not in self.rows for c in uniq.tolist()]].tolist())
        for c in new:
            self.rows[c] = [next(it), np.inf, self.opened]
            self.opened += 1
        assert got.tolist() == [self.rows[c][0] for c in uniq.tolist()]

    def retime(self, kids, base, sys_at, table):
        table.retime(kids, base, sys_at, _model_closes_of)
        of = dict(zip(kids.tolist(), zip(base.tolist(), sys_at.tolist())))
        self.clock.update(of)
        for c, row in self.rows.items():
            if c >> 32 in of:
                b, s = of[c >> 32]
                row[1] = s + (float(_model_closes_of(np.int64(c))) - b)

    def close(self, now, table, slots):
        due = table.due(now)
        comp, ids = table.read(due)
        want = [c for c in self.in_order() if self.rows[c][1] <= now]
        assert comp.tolist() == want  # in the order they were opened
        assert ids.tolist() == [self.rows[c][0] for c in want]
        table.remove(due)
        slots.free.extend(ids.tolist())
        for c in want:
            del self.rows[c]
        kids = np.unique(comp >> 32)
        held = {c >> 32 for c in self.rows}
        assert table.without_window(kids).tolist() == [
            k for k in kids.tolist() if k not in held
        ]

    def check(self, table):
        order = self.in_order()
        assert len(table) == len(order)
        assert table.comp.tolist() == order
        assert table.ids.tolist() == [self.rows[c][0] for c in order]
        assert table.at.tolist() == [self.rows[c][1] for c in order]
        ats = [row[1] for row in self.rows.values()]
        assert table.next_due() == (min(ats) if ats else np.inf)


def _rebuilds():
    from bytewax_tpu.engine import flight

    return flight.RECORDER.counters.get("window_table_rebuilds", 0)


@pytest.mark.parametrize("keys, per_step", [(6, 4), (40, 30), (400, 300), (3000, 1500)])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_open_window_table_matches_dict_model(seed, keys, per_step):
    """Random opens, retimes, due scans, closes, whole-clock shifts,
    ends of input and resumes leave the table and a plain dict model
    with the same slots, the same close order, the same keys without
    a window and the same notify minimum after every step, through
    many rebuilds (sizes swing from empty to thousands and back)."""
    from bytewax_tpu.engine.window_accel import _OpenWindows

    rng = np.random.default_rng([seed, keys])
    table, slots, model = _OpenWindows(), _FakeSlots(), _TableModel()
    started, now, wid = _rebuilds(), 0.0, 0
    for step in range(120):
        growing = (step // 15) % 2 == 0  # fill, drain, fill, ...
        what = rng.choice(
            ["open", "close", "retime", "shift", "eof", "resume"],
            p=[0.5, 0.2, 0.2, 0.04, 0.02, 0.04] if growing
            else [0.2, 0.5, 0.2, 0.04, 0.02, 0.04],
        )  # fmt: skip
        now += 1.0
        if what == "open":
            wid += int(rng.integers(0, 2))
            kids = rng.integers(0, keys, size=int(rng.integers(1, per_step + 1)))
            wids = wid - rng.integers(0, 3, size=len(kids))
            comps = [_comp_of(k, w) for k, w in zip(kids, wids)]
            model.open(np.unique(np.asarray(comps, dtype=np.int64)), table, slots)
            what = "retime" if rng.random() < 0.8 else what
        if what == "retime":
            kids = np.unique(rng.integers(0, keys, size=int(rng.integers(1, per_step + 1))))
            base = rng.integers(0, 50, size=len(kids)).astype(np.float64)
            model.retime(kids, base, np.full(len(kids), now), table)
        elif what == "close" and model.rows:
            ats = sorted(row[1] for row in model.rows.values())
            model.close(ats[int(rng.integers(0, len(ats)))], table, slots)
        elif what == "eof":
            model.close(np.inf, table, slots)
            assert len(table) == 0
        elif what == "shift":
            delta = float(rng.integers(-20, 20))
            table.shift(delta)
            for row in model.rows.values():
                row[1] += delta
            model.clock = {k: (b, s + delta) for k, (b, s) in model.clock.items()}
        elif what == "resume":
            # As ``load_many`` does: a page's windows reopen in
            # composite order, then every key is retimed.
            table, slots = _OpenWindows(), _FakeSlots()
            live, clock = sorted(model.rows), model.clock
            model = _TableModel()
            model.open(np.asarray(live, dtype=np.int64), table, slots)
            kids = np.asarray(sorted({c >> 32 for c in live} & set(clock)), dtype=np.int64)
            if len(kids):
                base, sys_at = map(np.asarray, zip(*(clock[k] for k in kids.tolist())))
                model.retime(kids, base.astype(np.float64), sys_at.astype(np.float64), table)
        model.check(table)
    assert _rebuilds() - started >= 5


def test_open_window_table_key_straddles_both_index_levels():
    """A key with windows in the large run and in the small one is
    retimed, listed and held as one key."""
    from bytewax_tpu.engine.window_accel import _OpenWindows

    table, slots, model = _OpenWindows(), _FakeSlots(), _TableModel()
    old = np.asarray([_comp_of(k, w) for k in range(500) for w in (0, 1)], dtype=np.int64)
    model.open(old, table, slots)
    # The next open merges the first into the large run and joins the
    # small one itself.
    model.open(np.asarray([_comp_of(7, 2), _comp_of(600, 2)], dtype=np.int64), table, slots)
    assert (len(table._big), len(table._small)) == (1000, 2)
    model.retime(np.asarray([7]), np.asarray([3.0]), np.asarray([100.0]), table)
    model.check(table)
    comp, _ids = table.read(table.rows_of(np.asarray([7])))
    assert comp.tolist() == [_comp_of(7, 0), _comp_of(7, 1), _comp_of(7, 2)]
    assert np.isfinite(table.at[table.comp >> 32 == 7]).all()
    # Its two old windows close: the key is still held by the new one.
    model.close(float(model.rows[_comp_of(7, 1)][1]), table, slots)
    assert _comp_of(7, 2) in model.rows and _comp_of(7, 1) not in model.rows
    assert (len(table._big), len(table._small)) == (1000, 2)  # no rebuild yet
    assert table.without_window(np.asarray([7, 8])).tolist() == []
    model.close(float(model.rows[_comp_of(7, 2)][1]), table, slots)
    assert table.without_window(np.asarray([7, 8])).tolist() == [7]
    model.check(table)


def test_open_window_table_reopens_a_composite_before_a_rebuild():
    """A window closed and opened again before the index was rebuilt
    takes a new slot and a new place in the order; the entry its
    closed self left behind finds the new one, in either run."""
    from bytewax_tpu.engine.window_accel import _OpenWindows

    table, slots, model = _OpenWindows(), _FakeSlots(), _TableModel()
    model.open(np.asarray([_comp_of(k, 0) for k in range(100)], dtype=np.int64), table, slots)
    model.open(np.asarray([_comp_of(k, 1) for k in range(10)], dtype=np.int64), table, slots)
    assert (len(table._big), len(table._small)) == (100, 10)
    in_big, in_small = _comp_of(40, 0), _comp_of(4, 1)
    kids = np.asarray([4, 40])
    model.retime(kids, np.asarray([0.0, 0.0]), np.asarray([0.0, 0.0]), table)
    # Key 4's windows fall due at 10 and 15, key 40's at 10.
    model.close(12.0, table, slots)
    assert _comp_of(4, 0) not in model.rows and in_big not in model.rows
    model.close(15.0, table, slots)
    assert in_small not in model.rows and table.without_window(kids).tolist() == [4, 40]
    started = _rebuilds()
    model.open(np.asarray(sorted([in_big, in_small, _comp_of(50, 0)]), dtype=np.int64), table, slots)
    assert _rebuilds() == started
    assert (len(table._big), len(table._small)) == (100, 10)  # entries reused
    model.check(table)
    assert table.comp[-2:].tolist() == sorted([in_big, in_small])
    assert np.isinf(table.at[-2:]).all()
    assert table.without_window(kids).tolist() == []
    model.retime(kids, np.asarray([1.0, 2.0]), np.asarray([50.0, 60.0]), table)
    model.check(table)
    # Through a rebuild and on: still one live entry a composite.
    model.open(np.asarray([_comp_of(k, 2) for k in range(60)], dtype=np.int64), table, slots)
    model.open(np.asarray([_comp_of(k, 3) for k in range(5)], dtype=np.int64), table, slots)
    assert _rebuilds() > started
    model.check(table)
    model.close(np.inf, table, slots)
    assert len(table) == 0 and table.next_due() == np.inf


@pytest.mark.parametrize(
    "held, per_delivery, deliveries, most",
    [(1_000_000, 10_000, 20, 2_000_000), (3_334, 3_334, 20, 20 * 2 * 3_334)],
    ids=["million_open", "flood_sized"],
)
def test_open_window_table_copies_are_amortised(held, per_delivery, deliveries, most):
    """What the table copies follows what deliveries open and close,
    not what it holds: with a million windows open, twenty deliveries
    that each open and close ten thousand copy under two million rows
    between them (a table rewritten at every insert and remove copies
    eight million a delivery), and at the flood's 3,334 a delivery
    copies a few thousand; both counters are on ``GET /status``."""
    from bytewax_tpu.engine import flight
    from bytewax_tpu.engine.window_accel import _OpenWindows

    table, slots, kid = _OpenWindows(), _FakeSlots(), 0

    def deliver(n):
        nonlocal kid
        kids = np.arange(kid, kid + n, dtype=np.int64)
        kid += n
        table.ids_for((kids << 32) + (1 << 31), slots)
        table.retime(kids, np.zeros(n), kids.astype(np.float64), _model_closes_of)

    while len(table) < held:
        deliver(min(100_000, held - len(table)))
    before = dict(flight.RECORDER.counters)
    for _ in range(deliveries):
        deliver(per_delivery)
        due = table.due(float(table.at[per_delivery - 1]))
        assert len(due) == per_delivery
        table.remove(due)
        assert len(table) == held
    counted = flight.RECORDER.snapshot()["counters"]
    gained = {
        name: counted[name] - before.get(name, 0)
        for name in ("window_table_rebuilds", "window_table_rebuild_rows")
    }
    assert gained["window_table_rebuild_rows"] <= most
    assert gained["window_table_rebuilds"] <= deliveries + 1
    # Further on the rebuilds come, each a copy of the table, and stay
    # far apart: under a quarter of the table a delivery.
    for _ in range(4 * deliveries):
        deliver(per_delivery)
        table.remove(table.due(float(table.at[per_delivery - 1])))
    rows = flight.RECORDER.counters["window_table_rebuild_rows"] - before.get(
        "window_table_rebuild_rows", 0
    )
    assert rows > 0
    if held > 100 * per_delivery - 1:
        assert rows / (5 * deliveries) < held / 4


def test_window_table_counters_are_on_status(monkeypatch):
    """A window step's deliveries count the table's rebuilds where
    ``window_opens`` is counted: the recorder section of ``GET
    /status``."""
    import json

    from bytewax_tpu.engine import flight

    monkeypatch.setenv("BYTEWAX_TPU_SHARD", "0")
    before = dict(flight.RECORDER.counters)
    st = _spec_of("count", TUMBLING_10S).make_state()
    for step in range(4):
        secs = step * 400 + np.arange(40) * 10
        _deliver(st, ["a"] * 40, secs, np.ones(40))
    counted = json.loads(json.dumps(flight.RECORDER.snapshot()))["counters"]
    assert counted["window_table_rebuilds"] > before.get("window_table_rebuilds", 0)
    assert counted["window_table_rebuild_rows"] > before.get("window_table_rebuild_rows", 0)
    assert counted["window_opens"] - before.get("window_opens", 0) == 160


# -- the clock pass: in order, sorted, and the host tier ---------------------
#
# ``_ingest`` computes a delivery's watermarks by one of two passes
# and chooses by the delivery alone: no timestamp below the one before
# it, and no row is sorted.  The cases below drive the same rows
# through both (the second forced by input only: a ballast key's row,
# far in the future, moved to the front) and through the per-item host
# tier, all under one scripted system clock.

_CLOCK_T0 = ALIGN + timedelta(days=400)
_ALIGN_US = int(ALIGN.timestamp()) * 1_000_000
_BALLAST = "zz"
_BALLAST_S = 9_000_000.0

#: name -> (wait in seconds, [(system seconds since the first
#: delivery, [(key, event seconds since ALIGN), ...]), ...], does the
#: delivery-by-delivery order put every delivery on the in-order pass)
CLOCK_CASES = {
    "in_order": (
        5,
        [
            (0, [("a", 1), ("b", 2), ("a", 3), ("a", 14), ("b", 15), ("a", 27)]),
            (1, [("b", 28), ("a", 29), ("c", 30), ("b", 41), ("c", 55)]),
        ],
        True,
    ),
    "in_order_with_ties": (
        0,
        [
            (0, [("a", 1), ("b", 1), ("a", 1), ("a", 12), ("b", 12), ("b", 12)]),
            (1, [("a", 12), ("b", 30), ("a", 30), ("a", 30)]),
        ],
        True,
    ),
    # base 90 after the first delivery, carried to 140 by 50 s of
    # system time: 95-97 are late by the carried clock alone.
    "late_by_the_carried_clock_only": (
        10,
        [
            (0, [("a", 100), ("b", 100)]),
            (50, [("a", 95), ("a", 96), ("b", 97), ("a", 150), ("b", 151)]),
        ],
        True,
    ),
    "one_row": (0, [(0, [("a", 5)]), (1, [("a", 3)]), (2, [("a", 27)])], True),
    "empty_delivery": (
        0,
        [(0, [("a", 5), ("b", 6)]), (1, []), (2, [("a", 8), ("b", 19)])],
        True,
    ),
    # "a" loses its windows to the notify after the second delivery:
    # where the tier lets a key go, its clock starts over at -inf and
    # 50 is on time; where it keeps the key (sessions), 50 is late.
    "key_fresh_at_minus_inf": (
        0,
        [
            (0, [("a", 1), ("b", 2)]),
            (100, [("b", 200)]),
            (101, [("a", 50), ("a", 51), ("b", 201), ("c", 202)]),
        ],
        True,
    ),
    "fractional_microseconds": (
        0,
        [
            (0, [("a", 1.00000025), ("b", 1.0000005), ("a", 2.0000015), ("b", 13.00000075)]),
            (1, [("a", 2.00000125), ("a", 14.5), ("b", 14.50000025)]),
        ],
        True,
    ),
    "in_order_for_each_key_not_across": (
        2,
        [
            (0, [("a", 1), ("a", 2), ("a", 13), ("b", 1), ("b", 5), ("b", 14)]),
            (1, [("b", 15), ("b", 26), ("a", 14), ("a", 30)]),
        ],
        False,
    ),
    "out_of_order_within_a_key": (
        3,
        [
            (0, [("a", 10), ("a", 8), ("a", 2), ("b", 5), ("a", 20), ("b", 1), ("b", 30), ("a", 18)]),
            (1, [("a", 31), ("b", 12), ("a", 19), ("b", 44), ("a", 45)]),
        ],
        False,
    ),
}


def _scripted_now(monkeypatch):
    """System time under the test's hand: the device tier reads it
    through its module's ``datetime``, the host tier through the
    clock's ``now_getter``."""
    from bytewax_tpu.engine import window_accel as wa

    at = [_CLOCK_T0]

    class _Datetime(datetime):
        @classmethod
        def now(cls, tz=None):
            return at[0]

    monkeypatch.setattr(wa, "datetime", _Datetime)
    return at


def _case_rows(deliveries):
    """A case's deliveries with each row's value: its number in the
    case (whole, so float32 folds it exactly; distinct, so a late
    event names its row)."""
    out, n = [], 0
    for elapsed, rows in deliveries:
        out.append(
            (elapsed, [(k, s, float(n + i + 1)) for i, (k, s) in enumerate(rows)])
        )
        n += len(rows)
    return out


def _with_ballast(deliveries):
    """The same rows with a ballast key's far-future row ahead of them
    and one a second older behind: each delivery now descends, which
    is all that sends it to the sorted pass; clocks are per key, so
    no other key's answers move."""
    return [
        (
            elapsed,
            [(_BALLAST, _BALLAST_S + elapsed, 0.0)]
            + rows
            + [(_BALLAST, _BALLAST_S + elapsed - 1, 0.0)],
        )
        for elapsed, rows in deliveries
    ]


def _ts_of(sec):
    return ALIGN + timedelta(microseconds=round(sec * 1_000_000))


def _enter(st, entry, rows):
    """One delivery through one of the tier's three entry points."""
    from bytewax_tpu.engine.arrays import ArrayBatch, TsValue

    keys = [k for k, _s, _v in rows]
    if entry == "columnar":
        # Microseconds since the epoch; float64 carries quarters of
        # one at this size, and whole ones go as int64.
        us = np.asarray([s * 1_000_000 for _k, s, _v in rows], dtype=np.float64)
        ts = _ALIGN_US + us
        if (us == np.floor(us)).all():
            ts = ts.astype(np.int64)
        cols = {
            "key": np.asarray(keys, dtype="U2"),
            "ts": ts,
            "value": np.asarray([v for _k, _s, v in rows], dtype=np.float64),
        }
        return st.on_batch_columnar(ArrayBatch(cols))
    if st.spec.kind == "count":
        values = [_ts_of(s) for _k, s, _v in rows]
    else:
        values = [TsValue(v, _ts_of(s)) for _k, s, v in rows]
    if entry == "itemized":
        got = st.on_batch_items(list(zip(keys, values)))
        if got is None:
            pytest.skip("native toolchain unavailable")
        return got
    return st.on_batch(keys, values)


def _norm(event):
    """A window event as plain comparable numbers."""
    key, (wid, tag, payload) = event
    if tag == "M":
        payload = (payload.open_time.timestamp(), payload.close_time.timestamp())
    elif isinstance(payload, datetime):
        payload = payload.timestamp()
    elif isinstance(payload, tuple):
        payload = tuple(float(x) for x in payload)
    elif payload is not None:
        payload = float(payload)
    return key, int(wid), tag, payload


def _passes():
    from bytewax_tpu.engine import flight

    return tuple(
        flight.RECORDER.counters.get(name, 0)
        for name in ("window_clock_inorder", "window_clock_sorted")
    )


def _device_tier(at, spec, entry, deliveries, resume_from=None):
    """Drive a device-tier state through the deliveries: its events
    (late and closed, a notify after every delivery and end of input
    last), and after each ingest the delivery's keys, their clocks
    and the pass that ran.  Every delivery is also held to the rule
    itself, item by item in plain Python."""
    st = spec.make_state()
    if resume_from is not None:
        st.load_many(resume_from)
        st.touched.clear()
    wait_us = spec.wait_us
    events, log = [], []
    for elapsed, rows in deliveries:
        at[0] = _CLOCK_T0 + timedelta(seconds=elapsed)
        now_us = at[0].timestamp() * 1_000_000
        held = {
            key: (float(st.base_us[kid]), float(st.sys_at_base[kid]))
            for key, kid in st.key_ids.items()
        }
        before = _passes()
        seg = []
        phase_clock = st._phase_clock
        st._phase_clock = lambda kids: seg.append(kids) or phase_clock(kids)
        late, phase = _enter(st, entry, rows)
        del st._phase_clock
        took = tuple(b - a for a, b in zip(before, _passes()))
        # The delivery's key ids, ascending as ``np.unique`` gives them.
        (seg_kids,) = seg
        assert (np.diff(seg_kids) > 0).all()
        assert {st.keys[kid] for kid in seg_kids.tolist()} == {
            k for k, _s, _v in rows
        }
        clocks = {
            key: (float(st.base_us[kid]), float(st.sys_at_base[kid]))
            for key, kid in st.key_ids.items()
        }
        # The rule: after each row its key's watermark is the larger
        # of the carried clock and the key's largest ts - wait so far.
        want_late, top = set(), {}
        for key, sec, value in rows:
            base, sys_at = held.get(key, (-np.inf, now_us))
            ts = _ALIGN_US + np.float64(sec * 1_000_000)
            top[key] = max(top.get(key, -np.inf), ts - wait_us)
            if ts < max(top[key], base + (now_us - sys_at)):
                want_late.add(value)
        for key, most in top.items():
            base, sys_at = held.get(key, (-np.inf, now_us))
            want = (most, now_us) if most > base else (base, sys_at)
            assert clocks[key] == want, key
        by_value = {v: (k, s) for k, s, v in rows}
        got_late = set()
        for key, (_wid, tag, payload) in late:
            assert tag == "L"
            if st.spec.kind == "count":
                got_late.update(
                    v for v, (k, s) in by_value.items()
                    if k == key and _ts_of(s) == payload
                )
            else:
                got_late.add(float(payload))
        assert got_late == want_late
        assert st.touched == {k for k, _s, _v in rows}
        log.append((took, clocks, set(st.touched)))
        st.touched.clear()
        closes, _hint, gone = phase()
        st.let_go(gone)
        events += late + closes + st.on_notify()
    events += st.on_eof()
    return sorted(map(_norm, events)), log, st


def _host_tier(at, kind, windower, wait_s, deliveries, resume_from=None):
    """The per-item host tier over the same deliveries: one
    ``_WindowLogic`` a key, discarded when empty as the driver does,
    a notify after every delivery and end of input last."""
    from bytewax_tpu import xla
    from bytewax_tpu.engine.arrays import TsValue
    from bytewax_tpu.operators.windowing import _FoldWindowLogic, _WindowLogic

    clock = EventClock(
        ts_getter=xla.column_ts,
        wait_for_system_duration=timedelta(seconds=wait_s),
        now_getter=lambda: at[0],
    )

    def builder(resume):
        if kind == "count":
            return _FoldWindowLogic(
                lambda s, _v: s + 1, lambda s, t: s + t, resume or 0
            )
        state = resume if resume is not None else xla.STATS.make_acc()
        return _FoldWindowLogic(xla.STATS, xla.STATS.merge, state)

    def logic_of(snap):
        if snap is None:
            return _WindowLogic(
                clock.build(None), windower.build(None), builder, False
            )
        return _WindowLogic(
            clock.build(snap.clock_state),
            windower.build(snap.windower_state),
            builder,
            False,
            {wid: builder(s) for wid, s in snap.logic_states.items()},
            list(snap.queue),
        )

    logics = {
        key: logic_of(snap)
        for key, snap in (resume_from or [])
        if snap is not None
    }
    events = []

    def step(key, call, *args):
        got, empty = call(*args)
        events.extend((key, ev) for ev in got)
        if empty:
            del logics[key]

    for elapsed, rows in deliveries:
        at[0] = _CLOCK_T0 + timedelta(seconds=elapsed)
        by_key = {}
        for key, sec, value in rows:
            ts = _ts_of(sec)
            by_key.setdefault(key, []).append(
                ts if kind == "count" else TsValue(value, ts)
            )
        for key, values in by_key.items():
            if key not in logics:
                logics[key] = logic_of(None)
            step(key, logics[key].on_batch, values)
        for key in list(logics):
            step(key, logics[key].on_notify)
    for key in list(logics):
        step(key, logics[key].on_eof)
    return sorted(map(_norm, events))


def _without_ballast(events):
    return [ev for ev in events if ev[0] != _BALLAST]


def check_clock_case(monkeypatch, spec, windower, case, entry, resumed=False):
    """The body of the clock-pass tests, shared with the session
    tier's (tests/test_session_accel.py)."""
    wait_s, deliveries, in_order = CLOCK_CASES[case]
    whole_us = case != "fractional_microseconds"
    monkeypatch.setenv("BYTEWAX_TPU_SHARD", "0")
    at = _scripted_now(monkeypatch)
    spec.wait_us = wait_s * 1_000_000.0
    deliveries = _case_rows(deliveries)
    resume_from = None
    if resumed:
        # Everything but the first delivery runs on states resumed
        # from the snapshots the first left: "a" comes back with its
        # clock, a key the first delivery did not hold starts at -inf.
        first, deliveries = deliveries[:1], deliveries[1:]
        _events, _log, st0 = _device_tier(at, spec, entry, [])
        at[0] = _CLOCK_T0 + timedelta(seconds=first[0][0])
        _late, phase = _enter(st0, entry, first[0][1])
        phase()
        resume_from = [
            (key, snap)
            for key, snap in st0.snapshots_for(sorted(st0.key_ids))
            if snap is not None
        ]
        assert resume_from

    events, log, _st = _device_tier(at, spec, entry, deliveries, resume_from)
    for (took, _clocks, _touched), (_e, rows) in zip(log, deliveries):
        assert took == ((1, 0) if in_order or len(rows) < 2 else (0, 1))

    if in_order:
        # The same rows on the sorted pass: every answer the same.
        twin, twin_log, _st = _device_tier(
            at, spec, entry, _with_ballast(deliveries), resume_from
        )
        assert _without_ballast(twin) == events
        for (took, clocks, touched), (took_2, clocks_2, touched_2) in zip(
            log, twin_log
        ):
            assert took_2 == (0, 1)
            assert touched_2 - {_BALLAST} == touched
            clocks_2.pop(_BALLAST)
            # Clocks of the delivery's keys; a key the delivery does
            # not hold keeps what it had in both.
            assert clocks_2 == clocks

    if whole_us:
        host = _host_tier(
            at, spec.kind, windower, wait_s, deliveries, resume_from
        )
        if isinstance(windower, w.SessionWindower) and not in_order:
            # Session ids follow timestamp order on the device tier
            # and arrival order on the host tier (documented): the
            # sessions and their values are the same.
            def anonymous(evs):
                return sorted((k, tag, p) for k, _wid, tag, p in evs)

            assert anonymous(events) == anonymous(host)
        else:
            assert events == host
    assert any(tag == "E" for _k, _wid, tag, _p in events) or not any(
        rows for _e, rows in deliveries
    )


def _window_spec(kind, windower):
    from bytewax_tpu import xla

    offset = getattr(windower, "offset", windower.length)
    return WindowAccelSpec(
        kind, xla.column_ts, ALIGN, windower.length, offset, timedelta(0)
    )


def clock_case_entries():
    """Every case through every entry point that can carry it: a
    ``datetime`` holds whole microseconds, so fractions of one reach
    the tier through a float column alone (and no host tier)."""
    return [
        pytest.param(case, entry, id=f"{case}-{entry}")
        for case in sorted(CLOCK_CASES)
        for entry in ("columnar", "itemized", "host_format")
        if case != "fractional_microseconds" or entry == "columnar"
    ]


@pytest.mark.parametrize("kind", ["count", "stats"])
@pytest.mark.parametrize(
    "windower", [TUMBLING_10S, SLIDING_10S_BY_4S], ids=["tumbling", "sliding"]
)
@pytest.mark.parametrize("case,entry", clock_case_entries())
def test_clock_passes_agree_with_each_other_and_the_host_tier(
    monkeypatch, case, windower, kind, entry
):
    """Late events, emitted windows, ``base_us`` / ``sys_at_base`` and
    ``touched`` of the in-order pass, of the sorted pass over the same
    rows, and of the per-item host tier."""
    check_clock_case(
        monkeypatch, _window_spec(kind, windower), windower, case, entry
    )


@pytest.mark.parametrize("entry", ["columnar", "itemized", "host_format"])
@pytest.mark.parametrize("kind", ["count", "stats"])
@pytest.mark.parametrize(
    "windower", [TUMBLING_10S, SLIDING_10S_BY_4S], ids=["tumbling", "sliding"]
)
@pytest.mark.parametrize(
    "case", ["in_order", "late_by_the_carried_clock_only", "out_of_order_within_a_key"]
)
def test_clock_passes_agree_on_a_resumed_clock(
    monkeypatch, case, windower, kind, entry
):
    """The same on states loaded from snapshots: a resumed key's clock
    carries, a key new to the resumed state starts at minus infinity."""
    check_clock_case(
        monkeypatch, _window_spec(kind, windower), windower, case, entry,
        resumed=True,
    )


def test_clock_pass_counters_are_on_the_recorder_and_on_status(monkeypatch, tmp_path):
    """``window_clock_inorder`` / ``window_clock_sorted`` count the
    deliveries each pass took, on the recorder and under it on ``GET
    /status``: three deliveries in order, then one that descends."""
    import json
    import urllib.request

    from bytewax_tpu.engine import flight
    from bytewax_tpu.outputs import DynamicSink, StatelessSinkPartition

    monkeypatch.setenv("BYTEWAX_TPU_SHARD", "0")
    monkeypatch.setenv("BYTEWAX_TPU_ACCEL", "1")
    monkeypatch.setenv("BYTEWAX_TPU_INGEST_TARGET_ROWS", "0")
    monkeypatch.setenv("BYTEWAX_DATAFLOW_API_ENABLED", "1")
    monkeypatch.setenv("BYTEWAX_DATAFLOW_API_PORT", "13063")
    monkeypatch.chdir(tmp_path)
    secs = list(range(0, 150, 5)) + [400, 390, 420, 410, 440, 430, 460, 450, 480, 470]
    inp = [(ALIGN + timedelta(seconds=s), f"key{s % 3}") for s in secs]
    seen = []

    class _Part(StatelessSinkPartition):
        def write_batch(self, items):
            with urllib.request.urlopen(
                "http://127.0.0.1:13063/status", timeout=5
            ) as resp:
                seen.append(json.loads(resp.read())["recorder"]["counters"])

    class _Sink(DynamicSink):
        def build(self, step_id, worker_index, worker_count):
            return _Part()

    clock = EventClock(
        ts_getter=lambda item: item[0],
        wait_for_system_duration=timedelta(seconds=30),
    )
    flow = Dataflow("test_df")
    s = op.input("inp", flow, TestingSource(inp, batch_size=10))
    wo = w.count_window("count", s, clock, TUMBLING_10S, key=lambda item: item[1])
    op.output("out", wo.down, _Sink())
    before = dict(flight.RECORDER.counters)
    run_main(flow)
    gained = {
        name: flight.RECORDER.counters.get(name, 0) - before.get(name, 0)
        for name in ("window_clock_inorder", "window_clock_sorted")
    }
    assert gained == {"window_clock_inorder": 3, "window_clock_sorted": 1}
    assert seen, "no window closed before end of input"
    last = seen[-1]
    assert last["window_clock_inorder"] == flight.RECORDER.counters["window_clock_inorder"]
    assert last["window_clock_sorted"] == flight.RECORDER.counters["window_clock_sorted"]


def _late_and_on_time(rng, n):
    """Rows of two keys in order, every seventh 500 s behind (late
    under a wait of 0 from the second row of its key on)."""
    secs = 1000 + np.arange(n) * 3
    secs[6::7] -= 500
    return rng.choice(["a", "b"], size=n), secs


@pytest.mark.parametrize("late", [False, True], ids=["none_late", "some_late"])
@pytest.mark.parametrize(
    "column", ["callers", "value_scale", "itemized"],
)
def test_lane_folds_the_on_time_rows_as_they_were_at_the_call(
    monkeypatch, column, late
):
    """The deferred phase is handed exactly the rows the mask kept,
    and a source that reuses its columns' buffers once the call has
    returned (before the lane runs the fold) changes nothing: with no
    row late the engine's own columns go on uncopied, a caller's own
    value column is copied."""
    from bytewax_tpu.engine.arrays import ArrayBatch, TsValue

    monkeypatch.setenv("BYTEWAX_TPU_SHARD", "0")
    rng = np.random.RandomState(11)
    n = 70
    keys, secs = _late_and_on_time(rng, n)
    if not late:
        secs = np.sort(secs)
    vals = rng.randint(1, 50, size=n)
    scale = 0.5 if column == "value_scale" else 1.0
    st = _spec_of("sum", TUMBLING_10S).make_state()
    absorbed = []
    absorb = st._absorb

    def spy(kids_ok, ts_ok, vals_ok):
        names = [st.keys[kid] for kid in kids_ok.tolist()]
        absorbed.append((names, ts_ok.copy(), np.array(vals_ok)))
        absorb(kids_ok, ts_ok, vals_ok)

    monkeypatch.setattr(st, "_absorb", spy)

    if column == "itemized":
        items = [
            (str(k), TsValue(float(v), ALIGN + timedelta(seconds=int(s))))
            for k, s, v in zip(keys, secs, vals)
        ]
        got = st.on_batch_items(items)
        if got is None:
            pytest.skip("native toolchain unavailable")
        late_events, phase = got
        items[:] = [("a", TsValue(-1.0, ALIGN))] * n
    else:
        cols = {
            "key": np.array(keys),
            "ts": (_ALIGN_US + secs * 1_000_000).astype(np.float64),
            "value": vals.astype(np.int16 if column == "value_scale" else np.float64),
        }
        late_events, phase = st.on_batch_columnar(
            ArrayBatch(cols, value_scale=scale if column == "value_scale" else None)
        )
        # The source fills its buffers with the next poll's rows.
        cols["key"][:] = "b"
        cols["ts"][:] = _ALIGN_US
        cols["value"][:] = -1
    closes, _hint, gone = phase()
    st.let_go(gone)
    events = closes + st.on_eof()

    top, on_time = {}, []
    for k, s in zip(keys, secs):
        top[k] = max(top.get(k, -np.inf), s)
        on_time.append(s >= top[k])
    on_time = np.asarray(on_time)
    assert (~on_time).any() == late and len(late_events) == (~on_time).sum()
    ((names_ok, ts_ok, vals_ok),) = absorbed
    assert names_ok == keys[on_time].tolist()
    np.testing.assert_array_equal(ts_ok, _ALIGN_US + secs[on_time] * 1_000_000.0)
    np.testing.assert_array_equal(vals_ok, vals[on_time] * scale)
    want = {}
    for k, s, v in zip(keys[on_time], secs[on_time], vals[on_time]):
        want[(str(k), int(s) // 10)] = want.get((str(k), int(s) // 10), 0.0) + v * scale
    got = {(k, wid): v for k, (wid, tag, v) in events if tag == "E"}
    assert got == want
