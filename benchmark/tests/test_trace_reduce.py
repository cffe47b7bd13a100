"""The trace reduction on a small recorded trace (a cut of a v5e trace
of ``brc.file``, ``recorded_trace.json``) and on hand-made
planes."""

import json
import os

import pytest

from benchmark import roofline, trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "recorded_trace.json")) as f:
        return json.load(f)


def brute_union_ns(events):
    """Union length by sweeping the sorted end points: another
    algorithm than the reducer's."""
    points = sorted(
        [(s, 1) for _n, s, d in events] + [(s + d, -1) for _n, s, d in events]
    )
    depth, since, total = 0, None, 0.0
    for at, step in points:
        if depth == 0 and step == 1:
            since = at
        depth += step
        if depth == 0:
            total += at - since
    return total


def test_recorded_trace_busy_and_programs(recorded):
    out = trace_reduce.reduce(recorded)
    device = next(p for p in recorded["planes"] if p["name"] == "/device:TPU:0")
    ops = next(ln for ln in device["lines"] if ln["name"] == "XLA Ops")["events"]
    modules = next(ln for ln in device["lines"] if ln["name"] == "XLA Modules")
    assert list(out["busy_by_device_s"]) == ["/device:TPU:0"]
    assert out["busy_s"] == pytest.approx(brute_union_ns(ops) / 1e9, rel=1e-9)
    assert 0 < out["busy_s"] < out["window_s"]
    # Program names lose their fingerprint; calls and seconds add up.
    assert "jit_update_fields_packed" in out["programs"]
    assert all("(" not in name for name in out["programs"])
    assert sum(v[0] for v in out["programs"].values()) == len(modules["events"])
    assert sum(v[1] for v in out["programs"].values()) == pytest.approx(
        sum(e[2] for e in modules["events"]) / 1e9
    )
    shapes = {"programs": ["jit_update_fields"]}
    calls, seconds = roofline.fold_time(shapes, out["programs"])
    assert calls >= 1 and seconds == pytest.approx(out["programs"]["jit_update_fields_packed"][1])
    breakdown = out["breakdown"]
    assert len(breakdown["device_ops"]) <= 10 and len(breakdown["idle_gaps"]) <= 10
    assert breakdown["device_ops"][0][1] >= breakdown["device_ops"][-1][1]
    assert breakdown["idle_gaps"]


def plane(name, **lines):
    return {
        "name": name,
        "lines": [{"name": k.replace("_", " "), "events": v} for k, v in lines.items()],
    }


def test_hand_made_planes_gaps_are_named_by_what_the_host_did():
    ms = 1_000_000.0
    device = plane(
        "/device:TPU:0",
        XLA_Modules=[["jit_update_fields(1)", 0.0, 10 * ms], ["jit_scatter(2)", 100 * ms, 5 * ms], ["jit_update_fields(1)", 400 * ms, 10 * ms]],
        XLA_Ops=[["%a", 0.0, 6 * ms], ["%b", 4 * ms, 6 * ms], ["%c", 100 * ms, 5 * ms], ["%d", 400 * ms, 10 * ms]],
    )
    host = plane(
        "/host:CPU",
        python=[
            ["bench_sink_write", 12 * ms, 80 * ms],
            ["bench_poll", 106 * ms, 1 * ms],
            ["np.asarray(jax.Array)", 200 * ms, 150 * ms],
        ],
    )
    out = trace_reduce.reduce({"planes": [device, host, plane("/device:CUSTOM:x")]})
    assert out["busy_s"] == pytest.approx(0.025)
    assert out["window_s"] == pytest.approx(0.410)
    assert out["programs"] == {"jit_update_fields": [2, 0.020], "jit_scatter": [1, 0.005]}
    assert dict(out["breakdown"]["idle_gaps"]) == pytest.approx(
        {"np.asarray(jax.Array)": 0.295, "bench_sink_write": 0.090}
    )
    assert out["breakdown"]["device_ops"][0] == ["jit_update_fields", 0.020]
    assert out["program_starts_ns"]["jit_update_fields"] == [0.0, 400 * ms]
    assert out["span_ends_ns"] == {"bench_poll": [107 * ms], "bench_sink_write": [92 * ms]}


def test_two_chips_average_and_name_the_least_busy():
    from benchmark.metrics import device_idle_pct

    ms = 1_000_000.0
    a = plane("/device:TPU:0", XLA_Ops=[["%a", 0.0, 50 * ms]], XLA_Modules=[["jit_shard_fn(1)", 0.0, 50 * ms]])
    b = plane("/device:TPU:1", XLA_Ops=[["%a", 0.0, 10 * ms], ["%z", 99 * ms, 1 * ms]], XLA_Modules=[["jit_shard_fn(1)", 0.0, 10 * ms]])
    out = trace_reduce.reduce({"planes": [a, b]})
    assert out["busy_s"] == pytest.approx(0.0305)
    assert out["programs"]["jit_shard_fn"] == [1, pytest.approx(0.030)]
    assert device_idle_pct.read({"trace": out}) == pytest.approx(89.0)


def test_a_trace_with_no_device_operation_reads_nothing():
    from benchmark.metrics import device_idle_pct, fold_roofline

    out = trace_reduce.reduce({"planes": [plane("/host:CPU", python=[["bench_poll", 0.0, 5e6]])]})
    assert out["busy_s"] == 0.0 and out["programs"] == {}
    assert device_idle_pct.read({"trace": out}) is None
    cell = type("Cell", (), {"cfg": {"fold_shapes": {"programs": ["jit_update_fields"]}}})
    assert fold_roofline.read({"trace": out, "cell": cell}) is None
    assert fold_roofline.read({"trace": None}) is None


def test_jobs_events_in_a_stretch_come_from_the_traced_fold_calls():
    """Three sink writes end three jobs; the two whole jobs between
    them hold four fold calls each, so the stretch's ten calls folded
    2.5 jobs' rows."""
    ms = 1_000_000.0
    folds = [["jit_update_fields_packed(7)", at * ms, 1 * ms] for at in
             (5, 8, 20, 22, 24, 26, 40, 42, 44, 46)]
    device = plane("/device:TPU:0", XLA_Modules=folds, XLA_Ops=[["%a", f[1], f[2]] for f in folds])
    host = plane("/host:CPU", python=[["bench_sink_write", at * ms, 1 * ms] for at in (10, 30, 50)])
    out = trace_reduce.reduce({"planes": [device, host]})
    cell = type("Cell", (), {"cfg": {"fold_shapes": {"programs": ["jit_update_fields"]}}})
    run = {"trace": out, "cell": cell, "data": {"rows": 1000}}
    assert roofline.events_in_stretch(run, 10) == pytest.approx(2500)
    out["span_ends_ns"]["bench_sink_write"] = [11 * ms]
    assert roofline.events_in_stretch(run, 10) is None


def test_fold_bytes_and_unknown_device():
    shapes = {"id_bytes": 4, "value_bytes": 4, "fields": 4, "field_bytes": 4, "live_slots": 1000}
    # 10 calls of 500 rows: rows in, and each call reads and writes
    # 500 slots of 4 fields.
    assert roofline.fold_bytes(shapes, 5000, 10) == 5000 * 8 + 10 * 2 * 16 * 500
    # More rows a call than live slots: the table bounds what is touched.
    assert roofline.fold_bytes(shapes, 50000, 10) == 50000 * 8 + 10 * 2 * 16 * 1000
    assert roofline.peak("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        roofline.peak("TPU v9")
