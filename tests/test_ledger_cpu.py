"""What the epoch ledger records beside a span's wall seconds: its
thread's CPU seconds (``cpu:<phase>`` counters, exclusive like the
wall seconds, the worker's own clock on a lane), every stage of a
program's way to the chip (the ``jit_*`` / ``xla_cache_load_*``
counters), the run's wall clock (``run_wall_seconds``), and the spans
``gc``, ``touch``, ``group`` and ``free``."""

import threading
import time
import types
from datetime import datetime, timedelta, timezone

import numpy as np
import pytest

import bytewax_tpu.operators as op
import bytewax_tpu.operators.windowing as w
from bytewax_tpu.dataflow import Dataflow
from bytewax_tpu.engine import flight
from bytewax_tpu.engine.arrays import ArrayBatch
from bytewax_tpu.models.brc import ArrayBatchSource
from bytewax_tpu.operators.windowing import EventClock, TumblingWindower
from bytewax_tpu.testing import TestingSink, TestingSource, run_main

REC = flight.RECORDER

#: Lanes with a thread of their own, whose phases overlap the main
#: thread's (``eof/...`` runs inline and is the main thread's; so is
#: ``device/...`` at depth 1).
_OFF_MAIN = ("device", "collective_lane", "snapshot_lane")


@pytest.fixture
def gained(monkeypatch):
    """What ``phase_totals`` and the counters gained since.  The
    functions named under ``jit_stage_seconds[...]`` start afresh: a
    process that ran other tests first may have named as many as the
    cap allows, and a probe's stage would read as ``other``."""
    monkeypatch.setattr(flight, "_jit_names", set())
    REC._ledger = {}
    REC._ledger_pre_close = None
    assert REC._phase_stack == []
    totals0, counters0 = dict(REC.phase_totals), dict(REC.counters)

    def read():
        return (
            {k: v - totals0.get(k, 0.0) for k, v in REC.phase_totals.items()},
            {
                k: v - counters0.get(k, 0)
                for k, v in REC.counters.items()
                if v != counters0.get(k, 0)
            },
        )

    return read


def _spin(cpu_s: float) -> None:
    """Burn ``cpu_s`` of this thread's CPU, by its own clock."""
    c0 = time.thread_time()
    while time.thread_time() - c0 < cpu_s:
        sum(range(200))


# -- CPU beside wall ---------------------------------------------------------


@pytest.mark.parametrize("how", ["sleeps", "spins"])
def test_a_span_reads_its_threads_cpu_beside_the_wall_clock(gained, how):
    with flight.span("prep", "t"):
        if how == "sleeps":
            time.sleep(0.05)
        else:
            _spin(0.05)
    totals, counters = gained()
    wall, cpu = totals["prep"], counters["cpu:prep"]
    assert cpu <= wall + 1e-3
    if how == "sleeps":
        # Off-CPU is the sleep: the thread held no processor.
        assert wall - cpu >= 0.045 and cpu < 0.02
    else:
        # All the spin's CPU is the span's: what is left of the wall
        # is pre-emption.
        assert 0.05 <= cpu < 0.08


def test_cpu_is_exclusive_under_nesting(gained):
    with flight.span("host", "t"):
        _spin(0.02)
        with flight.span("fetch"):
            time.sleep(0.03)
            with flight.span("close_emit"):
                _spin(0.04)
        time.sleep(0.02)
    totals, counters = gained()
    host, fetch, emit = (counters["cpu:" + p] for p in ("host", "fetch", "close_emit"))
    assert 0.04 <= emit < 0.06
    assert fetch < 0.01  # its child's spin is not its own; it slept
    assert 0.02 <= host < 0.04  # nor is it the host's
    for phase in ("host", "fetch", "close_emit"):
        assert counters["cpu:" + phase] <= totals[phase] + 1e-3
    # The frames gave back what they gathered.
    assert REC._phase_stack == []


@pytest.mark.parametrize("on_lane", [False, True])
def test_a_dropped_span_leaves_its_cpu_to_the_enclosing_one(gained, on_lane):
    """``drop``: the span's own CPU stays the enclosing span's, a
    child's still comes out of that one."""

    def work():
        with flight.span("ingest", "t"):
            read = flight.span("read", "t").begin()
            _spin(0.02)
            with flight.span("parse"):
                _spin(0.03)
            read.drop()

    prefix = ""
    if on_lane:
        flight.lane_run("device", "t", work, inline=True)
        prefix = "device/"
    else:
        work()
    _totals, counters = gained()
    assert "cpu:" + prefix + "read" not in counters
    assert 0.03 <= counters["cpu:" + prefix + "parse"] < 0.045
    assert 0.02 <= counters["cpu:" + prefix + "ingest"] < 0.035


def test_a_worker_lanes_spans_carry_the_workers_own_cpu(gained):
    """Two threads spinning at once: the lane's CPU is read on the
    worker's clock and folded on the main thread; no phase's CPU
    passes its wall seconds."""

    def task():
        with flight.span("prep"):
            _spin(0.05)
        time.sleep(0.02)

    box = {}
    worker = threading.Thread(
        target=lambda: box.update(out=flight.lane_run("device", "t", task))
    )
    with flight.span("host", "t"):
        worker.start()
        _spin(0.05)
        worker.join()
        assert "cpu:device/prep" not in gained()[1]  # not before the fold
        spans, _result = box["out"]
        flight.lane_fold(spans)
    totals, counters = gained()
    assert 0.05 <= counters["cpu:device/prep"] < 0.08
    assert counters["cpu:device"] < 0.01  # the lane's self time slept
    assert totals["device"] - counters["cpu:device"] >= 0.015
    # The main thread's clock saw its own spin and not the worker's.
    assert 0.05 <= counters["cpu:host"] < 0.08
    for phase in ("host", "device", "device/prep"):
        assert counters["cpu:" + phase] <= totals[phase] + 1e-3


def test_a_clock_reading_is_used_again_while_it_is_young(gained, monkeypatch):
    """Spans end and begin in clusters and the read is a system call:
    back to back they share a reading (on the reckoning that the
    thread ran meanwhile), apart they each take their own."""
    reads = []
    real = time.thread_time

    def counted():
        reads.append(1)
        return real()

    monkeypatch.setattr(flight.time, "thread_time", counted)
    monkeypatch.setattr(flight, "_CPU_REUSE_S", 10.0)
    with flight.span("host", "t"):
        for _ in range(50):
            with flight.span("encode"):
                pass
    assert len(reads) <= 1  # none, or the thread's first
    del reads[:]
    monkeypatch.setattr(flight, "_CPU_REUSE_S", 0.0)
    with flight.span("host", "t"):
        with flight.span("encode"):
            pass
    assert len(reads) == 4  # a begin and an end each
    # The window is forty reads long, within bounds on any host.
    assert 10e-6 <= flight._cpu_reuse_s() <= 500e-6
    _totals, counters = gained()
    # Reckoned or read, a span's CPU stays inside its wall seconds.
    assert counters["cpu:encode"] <= gained()[0]["encode"] + 1e-3


def test_a_duration_only_phase_records_no_cpu(gained):
    with flight.span("host", "t"):
        flight.note_pipeline_stall("t", 0.25)
    totals, counters = gained()
    assert totals["flush"] == pytest.approx(0.25)
    assert "cpu:flush" not in counters and "cpu:host" in counters


def test_status_shows_cpu_totals_from_the_counters(gained):
    with flight.span("encode", "t"):
        _spin(0.01)
    shown = flight.phase_cpu_totals()
    assert shown["encode"] == round(REC.counters["cpu:encode"], 6)
    assert set(shown) == {k[4:] for k in REC.counters if k.startswith("cpu:")}
    # Not in the wall totals: a metric that matches a bare phase name
    # by its last path part would read the CPU a second time.
    assert not any(k.startswith("cpu:") for k in REC.phase_totals)


# -- the run's wall clock and what covers it -----------------------------------


def _window_flow(n_batches=3, n_rows=4000, n_keys=50):
    align = datetime(2024, 1, 1, tzinfo=timezone.utc)
    base = np.datetime64("2024-01-01T00:00:00", "us")
    rng = np.random.RandomState(5)
    batches = []
    for b in range(n_batches):
        secs = (b * n_rows + np.arange(n_rows)) // 40
        batches.append(
            ArrayBatch(
                {
                    "key_id": rng.randint(0, n_keys, size=n_rows).astype(np.int32),
                    "ts": base + secs.astype("timedelta64[s]"),
                },
                key_vocab=np.array([f"k{i}" for i in range(n_keys)]),
            )
        )
    clock = EventClock(ts_getter=lambda x: x, wait_for_system_duration=timedelta(0))
    windower = TumblingWindower(align_to=align, length=timedelta(seconds=10))
    out = []
    flow = Dataflow("cpu_df")
    s = op.input("in", flow, ArrayBatchSource(batches))
    wo = w.count_window("count", s, clock, windower, key=lambda x: x)
    op.output("out", wo.down, TestingSink(out))
    return flow, out, n_keys


@pytest.mark.parametrize("depth", ["1", "2"])
def test_run_wall_seconds_cover_the_main_threads_phases(gained, monkeypatch, depth):
    monkeypatch.setenv("BYTEWAX_TPU_ACCEL", "1")
    monkeypatch.setenv("BYTEWAX_TPU_PIPELINE_DEPTH", depth)
    flow, out, n_keys = _window_flow()
    t0 = time.monotonic()
    run_main(flow)
    whole = time.monotonic() - t0
    assert out
    totals, counters = gained()
    run_wall = counters["run_wall_seconds"]
    assert 0 < run_wall <= whole
    assert REC._run_t is None  # the clock stopped with the run
    on_worker = _OFF_MAIN if depth == "2" else ()
    main = sum(
        s for p, s in totals.items() if p.split("/")[0] not in on_worker
    )
    assert 0 < main <= run_wall * 1.01
    # The main thread's spans cover the run: start-up to teardown.
    assert main >= 0.5 * run_wall
    for phase, seconds in totals.items():
        cpu = counters.get("cpu:" + phase)
        if cpu is not None:
            assert cpu <= seconds * 1.01 + 2e-3, (phase, cpu, seconds)
    # ``touch``: one span a delivery, a row a key of the delivery.
    assert counters["touch_spans"] == counters["watermark_spans"] == 3
    assert counters["touch_rows"] == 3 * n_keys
    assert totals["touch"] > 0 and "cpu:touch" in counters


def test_a_pass_with_nothing_to_do_is_idle(gained):
    """The loop's own wait (for input, a timer, a lane) has a name,
    so what ``run_wall_seconds`` has left over is the loop itself."""
    from bytewax_tpu.inputs import DynamicSource, StatelessSourcePartition

    class _Part(StatelessSourcePartition):
        def __init__(self):
            self.left = [1, 2, 3]
            self.awake = None

        def next_batch(self):
            if not self.left:
                raise StopIteration()
            self.awake = datetime.now(timezone.utc) + timedelta(milliseconds=40)
            return [self.left.pop()]

        def next_awake(self):
            return self.awake

    class _Paced(DynamicSource):
        def build(self, step_id, worker_index, worker_count):
            return _Part()

    out = []
    flow = Dataflow("idle_df")
    op.output("out", op.input("in", flow, _Paced()), TestingSink(out))
    run_main(flow)
    assert sorted(out) == [1, 2, 3]
    totals, counters = gained()
    assert totals["idle"] >= 0.1  # three waits of 40 ms
    assert "cpu:idle" not in counters and "idle_spans" not in counters
    main = sum(s for p, s in totals.items() if p.split("/")[0] not in _OFF_MAIN)
    assert 0.9 * counters["run_wall_seconds"] <= main <= counters["run_wall_seconds"] * 1.01
    # A wait, and in no bucket: the fractions read what they read.
    assert "idle" not in flight.TRACED_PHASES
    assert flight.ledger_fractions({"idle": 1.0}) is None


def test_run_wall_clock_advances_by_the_pass():
    before = REC.counters.get("run_wall_seconds", 0.0)
    flight.note_run_wall()  # the run's first call starts its clock
    assert REC.counters.get("run_wall_seconds", 0.0) == before
    time.sleep(0.02)
    flight.note_run_wall()  # a pass of the loop
    mid = REC.counters["run_wall_seconds"]
    assert mid - before >= 0.02
    time.sleep(0.01)
    flight.note_run_wall(stop=True)
    assert REC.counters["run_wall_seconds"] - mid >= 0.01
    assert REC._run_t is None
    time.sleep(0.01)
    flight.note_run_wall(stop=True)  # between runs: nothing to add
    assert REC.counters["run_wall_seconds"] - mid < 0.02


def test_a_collection_is_the_span_gc_and_joins_no_bucket(gained):
    from bytewax_tpu.engine.driver import _Driver

    class _Cycle:
        def __init__(self):
            self.me = self

    for _ in range(10):
        _Cycle()
    holder = types.SimpleNamespace(_last_gc=0.0)
    _Driver._collect(holder)
    totals, counters = gained()
    assert counters["gc_spans"] == 1 and counters["gc_rows"] >= 10
    assert totals["gc"] > 0 and "cpu:gc" in counters
    assert holder._last_gc > 0.0
    assert "gc" in flight.TRACED_PHASES
    # Cut out of no frame of a bucket: the fractions read what they read.
    assert flight.ledger_fractions({"gc": 1.0}) is None


def test_a_host_tier_keyed_step_groups_its_delivery_under_a_span(gained, monkeypatch):
    monkeypatch.setenv("BYTEWAX_TPU_ACCEL", "0")
    out = []
    flow = Dataflow("group_df")
    s = op.input("in", flow, TestingSource([("a", 1), ("b", 2), ("a", 3)], batch_size=3))
    s = op.stateful_map("sum", s, lambda st, v: ((st or 0) + v,) * 2)
    op.output("out", s, TestingSink(out))
    run_main(flow)
    assert sorted(out) == [("a", 1), ("a", 4), ("b", 2)]
    totals, counters = gained()
    assert counters["group_spans"] == counters["logic_spans"] == 1
    assert counters["group_rows"] == 3
    # The delivery's three items let go where the step held the last
    # reference to their list (the output step after it does too).
    assert counters["free_spans"] >= 1 and counters["free_rows"] >= 3
    assert totals["free"] > 0
    # All cut out of ``host``, so all in its bucket.
    assert {"touch", "group", "free"} <= flight.TRACED_PHASES
    fractions = flight.ledger_fractions(
        {"group": 1.0, "touch": 1.0, "free": 2.0, "device": 4.0}
    )
    assert fractions["host"] == 0.5


def test_a_drained_delivery_dies_inside_free(gained):
    """The list a step drained dies inside ``free``: nothing of the
    engine (a loop variable, a dict iterator's last pair) keeps it
    alive past the span, to be freed under no name."""
    import weakref

    from bytewax_tpu.engine.driver import _OpRt

    class _Item:
        pass

    class _Rt(_OpRt):
        def __init__(self):  # no plan behind it: the queues alone
            self.op = types.SimpleNamespace(step_id="t")
            self.driver = types.SimpleNamespace(trace_ops=False)
            self.queues = {"up": [], "side": []}

        def _count_inp(self, w, n):
            pass

        def process(self, port, entries):
            pass

    rt = _Rt()
    frames_open = []
    for port in ("up", "side"):
        items = [_Item() for _ in range(5)]
        weakref.finalize(
            items[0], lambda: frames_open.append(len(REC._phase_stack))
        )
        rt.queues[port].append((0, items))
    del items
    rt.drain()
    assert frames_open == [2, 2]  # `host` and `free` open, both times
    totals, counters = gained()
    assert counters["free_spans"] == 2 and counters["free_rows"] == 10
    assert counters["host_spans"] == 1 and totals["free"] > 0
    # Columns are let go whole: no span for them.
    rt.queues["up"].append((0, ArrayBatch({"x": np.arange(3)})))
    rt.drain()
    assert gained()[1]["free_spans"] == 2 and rt.queues == {"up": [], "side": []}


# -- every stage of a program's way to the chip --------------------------------


def _fresh_jit(name):
    import jax
    import jax.numpy as jnp

    def body(x):
        return jnp.where(x > 0, x, -x).sum()

    body.__name__ = body.__qualname__ = name
    return jax.jit(body)


def test_a_fresh_jit_is_a_trace_with_its_function_named(gained):
    import jax.numpy as jnp

    flight.ensure_compile_listener()
    x = jnp.arange(8.0)
    _fresh_jit("retrace_probe_warm")(x)  # the eager operations' first calls
    _t, before = gained()
    step = _fresh_jit("retrace_probe")
    step(x)
    _t, first = gained()
    new = {k: v - before.get(k, 0) for k, v in first.items()}
    assert new["jit_trace_count"] >= 1 and new["jit_trace_seconds"] > 0
    assert new["jit_lower_seconds"] > 0
    # The trace, the lowering and the compile, by function.
    assert new["jit_stage_seconds[retrace_probe]"] >= new["jit_lower_seconds"]
    # Tier-1 keeps the persistent cache off: a real compile.
    assert new["xla_compile_count"] == 1 and "xla_cache_load_count" not in new
    step(x)  # the same object, the same shapes: nothing
    assert gained()[1] == first


def test_a_cache_hit_is_a_load_and_not_a_compile(gained):
    from jax import monitoring

    flight.ensure_compile_listener()
    monitoring.record_event("/jax/compilation_cache/cache_hits")
    monitoring.record_event_duration_secs(
        flight._COMPILE_EVENT, 0.25, fun_name="jit(load_probe)"
    )
    _t, counters = gained()
    assert counters["xla_cache_load_count"] == 1
    assert counters["xla_cache_load_seconds"] == 0.25
    assert counters["jit_stage_seconds[load_probe]"] == 0.25
    assert "xla_compile_count" not in counters
    monitoring.record_event_duration_secs(flight._COMPILE_EVENT, 0.5)
    _t, counters = gained()
    assert counters["xla_compile_count"] == 1 and counters["xla_cache_load_count"] == 1
    assert counters["xla_compile_seconds"] == 0.5


def test_a_trace_inside_a_trace_is_not_timed_twice(gained):
    from jax import monitoring

    flight.ensure_compile_listener()
    REC.active, was = True, REC.active
    try:
        monitoring.record_scalar(flight._TRACE_EVENT, 0.0, fun_name="outer_probe")
        monitoring.record_scalar(flight._TRACE_EVENT, 0.0, fun_name="inner_probe")
        monitoring.record_event_duration_secs(
            flight._TRACE_EVENT, 0.2, fun_name="inner_probe"
        )
        monitoring.record_event_duration_secs(
            flight._TRACE_EVENT, 0.5, fun_name="outer_probe"
        )
    finally:
        REC.active = was
    _t, counters = gained()
    assert counters["jit_trace_count"] == 2
    assert counters["jit_trace_seconds"] == pytest.approx(0.5)
    assert counters["jit_stage_seconds[inner_probe]"] == pytest.approx(0.2)
    assert counters["jit_stage_seconds[outer_probe]"] == pytest.approx(0.3)
    # One ring event a program traced, not one a function inside it.
    events = [e for e in REC.tail() if e["kind"] == "jit_trace"]
    assert [e["fun"] for e in events[-1:]] == ["outer_probe"]
    assert not any(e["fun"] == "inner_probe" for e in events)


def test_the_functions_named_are_bounded(gained, monkeypatch):
    monkeypatch.setattr(flight, "_JIT_NAMES_CAP", 0)
    flight._note_jit_stage("jit(never_named_probe)", 0.125)
    _t, counters = gained()
    assert counters["jit_stage_seconds[other]"] == 0.125
    assert "jit_stage_seconds[never_named_probe]" not in REC.counters
