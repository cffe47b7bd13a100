"""``join_window`` on the device tier (``engine/window_accel.py``
:class:`DeviceJoinState`, ``ops/join.py``) against the host tier's
``_JoinWindowLogic``: random columnar streams of two and three sides
with 0-3 rows a side, key and window, sliding windows, keys let go and
back, late rows, resumes across the tiers both ways; the forms that
stay on the host tier; and the row store on its own."""

from collections import Counter
from datetime import datetime, timedelta, timezone

import numpy as np
import pytest

import bytewax_tpu.operators as op
import bytewax_tpu.operators.windowing as w
from bytewax_tpu import xla
from bytewax_tpu.dataflow import Dataflow
from bytewax_tpu.engine import flight
from bytewax_tpu.engine import window_accel as wa
from bytewax_tpu.engine.arrays import ArrayBatch, TsValue
from bytewax_tpu.engine.flatten import flatten
from bytewax_tpu.engine.window_accel import DeviceJoinState, JoinAccelSpec
from bytewax_tpu.operators import _SideTable
from bytewax_tpu.testing import TestingSink, TestingSource, run_main
from tests.test_xla import ArraySource

ALIGN = datetime(2022, 1, 1, tzinfo=timezone.utc)
_US = 1_000_000


def _gained(before, name):
    return flight.RECORDER.counters.get(name, 0) - before.get(name, 0)


def _side_batches(rng, side, n_batches, keys, span_s, start_s=0, dtype=np.int32):
    """Columnar batches of one side, in event-time order within a
    batch: ``(key, ts, value)`` rows."""
    out = []
    for b in range(n_batches):
        n = int(rng.randint(0, 40))
        ts = np.sort(rng.randint(0, span_s, n)) + start_s + b * span_s
        vals = (rng.randint(-1000, 1000, n) * (side + 1)).astype(dtype)
        out.append(
            ArrayBatch(
                {
                    "key": np.asarray([f"k{k}" for k in rng.randint(0, keys, n)]),
                    "ts": np.datetime64(ALIGN.replace(tzinfo=None), "s")
                    + ts.astype("timedelta64[s]"),
                    "value": vals,
                }
            )
        )
    return out


def _join_flow(sides, windower, wait_s=10_000, late=None, meta=None):
    flow = Dataflow("join_df")
    ups = [op.input(f"in{i}", flow, ArraySource(b)) for i, b in enumerate(sides)]
    clock = w.EventClock(
        ts_getter=xla.column_ts, wait_for_system_duration=timedelta(seconds=wait_s)
    )
    out = []
    joined = w.join_window("join", clock, windower, *ups, insert_mode="product")
    op.output("out", joined.down, TestingSink(out))
    if late is not None:
        op.output("late", joined.late, TestingSink(late))
    if meta is not None:
        op.output("meta", joined.meta, TestingSink(meta))
    return flow, out


def _both_tiers(monkeypatch, make_sides, windower, **kw):
    """The same flow on the device tier and on the host tier: the
    outputs (and whatever lists ``kw`` names), and the counters the
    device run gained."""
    got = {}
    streams = kw.pop("streams", ())
    for accel in ("1", "0"):
        monkeypatch.setenv("BYTEWAX_TPU_ACCEL", accel)
        extra = {k: [] for k in streams}
        before = dict(flight.RECORDER.counters)
        flow, out = _join_flow(make_sides(), windower, **kw, **extra)
        run_main(flow)
        got[accel] = (out, extra, before)
    (dev, dev_extra, before), (host, host_extra, _b) = got["1"], got["0"]
    return dev, host, dev_extra, host_extra, before


def _tumbling(seconds=30):
    return w.TumblingWindower(length=timedelta(seconds=seconds), align_to=ALIGN)


@pytest.mark.parametrize("n_sides", [2, 3])
@pytest.mark.parametrize("seed", [1, 2])
def test_device_tier_writes_the_host_tiers_rows(monkeypatch, n_sides, seed):
    """Random keys and multiplicities (0-3 rows a side, key and window
    on average), several windows and deliveries: the same multiset of
    rows, ``None`` where a side has no row, on the device tier."""
    counts = []

    def sides():
        rng = np.random.RandomState(seed)
        return [_side_batches(rng, s, 6, keys=12, span_s=40) for s in range(n_sides)]

    before = dict(flight.RECORDER.counters)
    dev, host, _d, _h, _b = _both_tiers(monkeypatch, sides, _tumbling())
    assert Counter(dev) == Counter(host)
    assert any(None in vals for _k, (_wid, vals) in dev)
    assert any(None not in vals for _k, (_wid, vals) in dev)
    assert _gained(before, "join_rows_emitted") == len(dev)
    counts.append(_gained(before, "join_rows_stored"))
    assert counts[0] == sum(len(b) for s in sides() for b in s)
    # Integer columns come back as integers.
    assert all(isinstance(v, int) for _k, (_w, vals) in dev for v in vals if v is not None)


def test_sliding_windows_put_a_row_in_each_of_its_windows(monkeypatch):
    def sides():
        rng = np.random.RandomState(5)
        return [_side_batches(rng, s, 5, keys=6, span_s=25) for s in range(2)]

    windower = w.SlidingWindower(
        length=timedelta(seconds=20), offset=timedelta(seconds=10), align_to=ALIGN
    )
    before = dict(flight.RECORDER.counters)
    dev, host, _d, _h, _b = _both_tiers(monkeypatch, sides, windower)
    assert Counter(dev) == Counter(host)
    rows = sum(len(b) for s in sides() for b in s)
    assert _gained(before, "join_rows_stored") == 2 * rows


def test_float_and_wide_integer_columns_come_back_exactly(monkeypatch):
    """A float64 side and an int64 side with values past 2^31 and past
    2^53's float rounding: the device tier carries both in 64 bits."""

    def sides():
        rng = np.random.RandomState(9)
        a = _side_batches(rng, 0, 3, keys=4, span_s=20, dtype=np.float64)
        b = _side_batches(rng, 1, 3, keys=4, span_s=20, dtype=np.int64)
        for batch in a:
            batch.cols["value"] = batch.cols["value"] / 7.0
        for batch in b:
            batch.cols["value"] = batch.cols["value"] * (1 << 50) + 1
        return [a, b]

    monkeypatch.setenv("BYTEWAX_TPU_ACCEL", "1")
    flow, out = _join_flow(sides(), _tumbling())
    run_main(flow)
    want = Counter()
    a, b = sides()
    rows = {}
    for s, batches in enumerate((a, b)):
        for batch in batches:
            secs = (batch.numpy("ts") - np.datetime64(ALIGN.replace(tzinfo=None), "s")).astype(int)
            for k, t, v in zip(batch.numpy("key").tolist(), secs.tolist(), batch.numpy("value").tolist()):
                rows.setdefault((k, t // 30), ([], []))[s].append(v)
    for (k, wid), (xs, ys) in rows.items():
        for x in xs or [None]:
            for y in ys or [None]:
                want[(k, (wid, (x, y)))] += 1
    assert Counter(out) == want
    assert any(isinstance(y, int) and abs(y) > 1 << 53 for _k, (_w, (_x, y)) in out)


def test_late_rows_and_the_meta_stream_match_the_host_tier(monkeypatch):
    """Under a wait of 0, rows far behind their key's newest row are
    late on both tiers (far: no stall of the wall clock decides it),
    and every closed window writes its metadata."""

    def sides():
        sec = np.datetime64(ALIGN.replace(tzinfo=None), "s")

        def batch(keys, secs, vals):
            return ArrayBatch(
                {
                    "key": np.asarray(keys),
                    "ts": sec + np.asarray(secs).astype("timedelta64[s]"),
                    "value": np.asarray(vals, dtype=np.int32),
                }
            )

        a = [batch(["a", "b"], [5000, 5001], [1, 2]), batch(["a", "b"], [10, 5002], [3, 4])]
        b = [batch(["a"], [5003], [5]), batch(["b", "a"], [20, 9000], [6, 7])]
        return [a, b]

    dev, host, dev_x, host_x, _b = _both_tiers(
        monkeypatch, sides, _tumbling(), wait_s=0, streams=("late", "meta")
    )
    assert Counter(dev) == Counter(host)
    assert Counter(dev_x["late"]) == Counter(host_x["late"])
    assert len(dev_x["late"]) == 2
    def metas(got):
        return sorted((k, wid, m.open_time, m.close_time) for k, (wid, m) in got)

    assert metas(dev_x["meta"]) == metas(host_x["meta"])


def test_a_columnar_join_over_two_workers(entry_point, monkeypatch):
    """The side column survives the routing of a columnar batch to the
    worker that owns its key."""

    def sides():
        rng = np.random.RandomState(3)
        return [_side_batches(rng, s, 4, keys=9, span_s=30) for s in range(2)]

    monkeypatch.setenv("BYTEWAX_TPU_ACCEL", "0")
    flow, host = _join_flow(sides(), _tumbling())
    run_main(flow)
    monkeypatch.setenv("BYTEWAX_TPU_ACCEL", "1")
    flow, dev = _join_flow(sides(), _tumbling())
    entry_point(flow)
    assert Counter(dev) == Counter(host)


def test_itemized_sides_run_on_the_host_tier(monkeypatch):
    """An itemized side is the host tier's: the step falls back before
    any device state exists, and its rows are the host tier's."""
    monkeypatch.setenv("BYTEWAX_TPU_ACCEL", "1")
    flow = Dataflow("join_items")
    names = op.input("names", flow, TestingSource([("1", (ALIGN, "ada"))]))
    mails = op.input("mails", flow, TestingSource([("1", (ALIGN, "a@b.c"))]))
    clock = w.EventClock(ts_getter=lambda v: v[0], wait_for_system_duration=timedelta(hours=1))
    joined = w.join_window("join", clock, _tumbling(), names, mails, insert_mode="product")
    out = []
    op.output("out", joined.down, TestingSink(out))
    run_main(flow)
    assert out == [("1", (0, ((ALIGN, "ada"), (ALIGN, "a@b.c"))))]


# -- the plan -------------------------------------------------------------------


def _spec_of(**kw):
    flow = Dataflow("plan")
    a = op.input("a", flow, TestingSource([]))
    b = op.input("b", flow, TestingSource([]))
    clock = kw.pop(
        "clock",
        w.EventClock(ts_getter=xla.column_ts, wait_for_system_duration=timedelta(0)),
    )
    windower = kw.pop("windower", _tumbling())
    joined = w.join_window("join", clock, windower, a, b, **kw)
    op.output("out", joined.down, TestingSink([]))
    specs = [o.conf.get("_accel") for o in flatten(flow).ops if o.name == "stateful_batch"]
    return specs[0]


def test_the_plan_lowers_product_inserts_with_final_emits():
    spec = _spec_of(insert_mode="product")
    assert isinstance(spec, JoinAccelSpec) and spec.sides == 2
    sliding = _spec_of(
        insert_mode="product",
        windower=w.SlidingWindower(
            length=timedelta(seconds=20), offset=timedelta(seconds=10), align_to=ALIGN
        ),
    )
    assert isinstance(sliding, JoinAccelSpec)


@pytest.mark.parametrize(
    "form",
    [
        {"insert_mode": "first"},
        {"insert_mode": "last"},
        {"insert_mode": "product", "emit_mode": "complete"},
        {"insert_mode": "product", "emit_mode": "running"},
        {"insert_mode": "product", "windower": w.SessionWindower(gap=timedelta(seconds=5))},
        {
            "insert_mode": "product",
            "clock": w.EventClock(
                ts_getter=xla.column_ts,
                wait_for_system_duration=timedelta(0),
                now_getter=lambda: ALIGN,
            ),
        },
        {"insert_mode": "product", "clock": w.SystemClock()},
    ],
    ids=["first", "last", "complete", "running", "session", "custom_clock", "system_clock"],
)
def test_other_forms_stay_on_the_host_tier(form):
    assert _spec_of(**form) is None


# -- the state, driven by hand ----------------------------------------------------


T0 = ALIGN + timedelta(days=400)


@pytest.fixture
def now(monkeypatch):
    """System time under the test's hand."""
    at = [T0]

    class _Datetime(datetime):
        @classmethod
        def now(cls, tz=None):
            return at[0]

    monkeypatch.setattr(wa, "datetime", _Datetime)
    return at


def _spec(wait_s, sides=2):
    spec = JoinAccelSpec(
        sides, xla.column_ts, ALIGN, timedelta(seconds=30), timedelta(seconds=30),
        timedelta(seconds=wait_s),
    )
    spec.meta_live = False
    return spec


def _batch(rows):
    """``rows``: ``(key, seconds since ALIGN, side, value)``."""
    return ArrayBatch(
        {
            "key": np.asarray([r[0] for r in rows]),
            "ts": np.datetime64(ALIGN.replace(tzinfo=None), "s")
            + np.asarray([r[1] for r in rows]).astype("timedelta64[s]"),
            "value": np.asarray([r[3] for r in rows], dtype=np.int32),
            "side": np.asarray([r[2] for r in rows], dtype=np.int8),
        }
    )


def _deliver(st, rows, events):
    late, phase = st.on_batch_columnar(_batch(rows))
    closes, _hint, gone = phase()
    st.let_go(gone)
    events += late + closes


def _want(rows, sides=2):
    """The product of each (key, window)'s sides, as ``Counter``."""
    tables = {}
    for key, sec, side, val in rows:
        tables.setdefault((key, sec // 30), _SideTable.empty(sides)).absorb(side, val, "product")
    return Counter(
        (key, (wid, row)) for (key, wid), t in tables.items() for row in t.rows()
    )


def test_a_key_let_go_comes_back_under_a_new_id(now):
    """A key whose last window closed goes (id, clock, slots, stored
    rows); a new key takes its id, and the key comes back with rows
    of its own."""
    st = DeviceJoinState(_spec(wait_s=0))
    events = []
    before = dict(flight.RECORDER.counters)
    first = [("a", 1, 0, 10), ("a", 2, 1, 20), ("a", 3, 1, 21), ("b", 4, 0, 30)]
    _deliver(st, first, events)
    now[0] = T0 + timedelta(seconds=100)
    _deliver(st, [("b", 400, 1, 31)], events)  # a's and b's first windows close
    assert "a" not in st.key_ids and st.store.live == 1
    _deliver(st, [("c", 500, 0, 40)], events)
    again = [("a", 900, 0, 11), ("a", 901, 1, 22)]
    _deliver(st, again, events)
    events += st.on_eof()
    assert Counter(e for e in events if e[1][1] == "E") == Counter(
        (k, (wid, "E", row)) for k, (wid, row) in _want(first + [("b", 400, 1, 31), ("c", 500, 0, 40)] + again).elements()
    )
    assert _gained(before, "window_keys_retired") >= 2
    assert st.store.live == 0 and not st.key_ids


def test_a_close_reads_back_its_output_rows_only(now):
    """The bytes read back by a close are its output rows' values (the
    padded chunk), whatever the store holds."""
    st = DeviceJoinState(_spec(wait_s=1000))
    events = []
    # A window falls due wait + (its close - its key's newest row) after
    # that row: 1,030 s for these, at the start of their windows ...
    held = [(f"h{i}", 30 * (1000 + i), i % 2, i) for i in range(3000)]
    _deliver(st, held, events)
    before = dict(flight.RECORDER.counters)
    # ... and 1,001 s for x's.
    _deliver(st, [("x", 28, 0, 1), ("x", 29, 1, 2)], events)
    now[0] = T0 + timedelta(seconds=1010)
    events += st.on_notify()
    assert ("x", (0, "E", (1, 2))) in events
    read = _gained(before, "device_transfer_bytes_d2h")
    assert 0 < read <= _gained(before, "join_expand_rows") * 2 * 4 < st.store.live * 8


def test_a_close_takes_the_earliest_due_windows_whole_up_to_its_budget(now, monkeypatch):
    """Past its budget of slots a close takes the windows due first,
    each with every side, and leaves the rest due for the next; end of
    input takes all that is left."""
    monkeypatch.setattr(wa, "_CLOSE_SLOTS", 3)
    st = DeviceJoinState(_spec(wait_s=0))
    # k{i}'s window falls due 10 + i s after the delivery: k0's first.
    rows = [(f"k{i}", 20 - i, side, 10 * i + side) for i in range(6) for side in (0, 1)]
    events = []
    _deliver(st, rows, events)
    assert not events
    now[0] = T0 + timedelta(seconds=100)
    closes = [st.on_notify() for _ in range(3)]
    assert [sorted({k for k, _e in c}) for c in closes] == [["k0"], ["k1"], ["k2"]]
    closes.append(st.on_eof())
    assert sorted({k for k, _e in closes[-1]}) == ["k3", "k4", "k5"]
    got = Counter((k, (wid, row)) for c in closes for k, (wid, _tag, row) in c)
    assert got == _want(rows)
    assert not st.key_ids and st.store.live == 0


def _host_logic(snap, wait_s, sides=2):
    """A host tier window logic of a join, built as the ``window``
    operator builds one (from a snapshot, or fresh), on the clock the
    device tier reads (``T0``)."""
    from bytewax_tpu.operators.windowing import _JoinWindowLogic, _WindowLogic

    clock = w.EventClock(
        ts_getter=lambda i_v: i_v[1].ts,
        wait_for_system_duration=timedelta(seconds=wait_s),
        now_getter=lambda: T0,
    )

    def builder(resume):
        return _JoinWindowLogic("product", "final", resume or _SideTable.empty(sides))

    windower = _tumbling()
    if snap is None:
        return _WindowLogic(clock.build(None), windower.build(None), builder, True)
    return _WindowLogic(
        clock.build(snap.clock_state),
        windower.build(snap.windower_state),
        builder,
        True,
        {wid: builder(state) for wid, state in snap.logic_states.items()},
        list(snap.queue),
    )


def _items(rows):
    """Host tier items of rows, as the tagging gives them."""
    return _batch(rows).to_pylist()


def _host_events(logics, rows, eof=True):
    out = []
    by_key = {}
    for key, value in _items(rows):
        by_key.setdefault(key, []).append(value)
    for key, values in by_key.items():
        logic = logics.setdefault(key, _host_logic(None, 1000))
        evs, _done = logic.on_batch(values)
        out += [(key, ev) for ev in evs]
    if eof:
        for key, logic in logics.items():
            evs, _done = logic.on_eof()
            out += [(key, ev) for ev in evs]
    return Counter((k, (wid, row)) for k, (wid, tag, row) in out if tag == "E")


FIRST = [("a", 1, 0, 1), ("a", 2, 1, 2), ("b", 3, 1, 3), ("a", 40, 0, 4), ("a", 41, 0, 5)]
SECOND = [("a", 5, 1, 6), ("b", 6, 0, 7), ("a", 42, 1, 8), ("c", 44, 1, 9)]


def test_a_resume_from_device_snapshots_on_the_host_tier(now):
    st = DeviceJoinState(_spec(wait_s=1000))
    _deliver(st, FIRST, [])
    snaps = dict(st.snapshots_for(["a", "b", "never"]))
    assert snaps["never"] is None
    tables = snaps["a"].logic_states
    assert tables[0] == _SideTable([[1], [2]]) and tables[1] == _SideTable([[4, 5], []])
    logics = {k: _host_logic(s, 1000) for k, s in snaps.items() if s is not None}
    assert _host_events(logics, SECOND) == _want(FIRST + SECOND)


def test_a_resume_from_host_snapshots_on_the_device_tier(now):
    logics = {}
    _host_events(logics, FIRST, eof=False)
    snaps = [(key, logic.snapshot()) for key, logic in sorted(logics.items())]
    # The host tier's ordered mode may still queue rows: both forms load.
    st = DeviceJoinState(_spec(wait_s=1000))
    st.load_many(snaps)
    events = []
    _deliver(st, SECOND, events)
    events += st.on_eof()
    got = Counter((k, (wid, row)) for k, (wid, tag, row) in events if tag == "E")
    want = _want(FIRST + SECOND)
    # The host tier's values are TsValues: equal to the device tier's ints.
    assert got == want
    assert all(isinstance(v, (int, float)) for _k, (_w, row) in got for v in row if v is not None)
    assert TsValue(1.0, ALIGN) == 1


# -- the row store ------------------------------------------------------------------


def test_the_row_store_keeps_each_slots_rows_through_growth_and_compaction():
    """Random places, releases and writes against a dict: every region
    reads back its rows in order, regions never overlap, the arena
    compacts and grows, and the moves are counted."""
    from bytewax_tpu.ops.join import RowStore

    rng = np.random.RandomState(0)
    store, ref, stamp = RowStore(), {}, [0]
    before = dict(flight.RECORDER.counters)
    for _step in range(60):
        slots = np.unique(rng.randint(0, 300, rng.randint(1, 40)))
        adds = rng.randint(1, 9, len(slots))
        base = store.place(slots.astype(np.int64), adds)
        pos, vals = [], []
        for slot, first, n in zip(slots.tolist(), base.tolist(), adds.tolist()):
            for k in range(n):
                stamp[0] += 1
                ref.setdefault(slot, []).append(stamp[0])
                pos.append(first + k)
                vals.append(stamp[0])
        bits = np.asarray(vals, dtype=np.int64)
        store.write(np.asarray(pos), bits.view(np.int32).reshape(-1, 2).T)
        gone = [s for s in ref if rng.rand() < 0.2]
        store.release(np.asarray(gone, dtype=np.int64))
        for s in gone:
            del ref[s]
        live = np.asarray(sorted(ref), dtype=np.int64)
        start, length = store.regions(live)
        ends = start + store.room[live]
        order = np.argsort(start)
        assert (start[order][1:] >= ends[order][:-1]).all()
        assert (ends <= store.n).all() and store.n < store.cap
        low, high = store.read(live)
        got = np.stack([low, high], 1).view(np.int64).ravel().tolist()
        want = [v for s in live.tolist() for v in ref[s]]
        assert got == want and store.live == len(want)
        assert length.tolist() == [len(ref[s]) for s in live.tolist()]
    assert _gained(before, "join_store_moved") > 0
    assert store.cap > 1024


# -- the expansion ------------------------------------------------------------------


def _expand_chunk(rng, rows, sides, cap, first_inside, last_past):
    """A chunk of a close's expansion as ``RowStore.expand`` hands it to
    ``join_expand``: ``(count, starts, ends)``, padded to ``rows``
    windows.  Counts 0-3 a side (a side with none reads the scratch
    row), now and then a window of many rows; ``first_inside``: the
    chunk begins inside its first window, which began in the chunk
    before; ``last_past``: the last window runs past the chunk's end,
    else the close's rows end inside the chunk."""
    counts = []
    total = 0
    while total < 2 * rows + 64:
        c = rng.randint(0, 4, sides)
        c[rng.rand(sides) < 0.3] = 0
        if rng.rand() < 0.02:
            c[rng.randint(sides)] = 40
        if not c.any():
            c[rng.randint(sides)] = 1
        counts.append(c)
        total += int(np.prod(np.maximum(c, 1)))
    count = np.array(counts, dtype=np.int64).T
    sizes = np.prod(np.maximum(count, 1), axis=0)
    ends = np.cumsum(sizes)
    begins = ends - sizes
    # The chunk's first row: inside a window of two rows or more, or at
    # a window's first; then its last row inside a window, or not.
    big = np.flatnonzero(sizes > 1)
    while True:
        w0 = int(big[rng.randint(len(big) // 4)])
        t0 = int(begins[w0]) + (rng.randint(1, sizes[w0]) if first_inside else 0)
        cut = int(np.searchsorted(ends, t0 + rows, side="left"))
        if begins[cut] < t0 + rows < ends[cut]:
            break
    if not last_past:
        keep = int(np.searchsorted(ends, t0 + rows - rng.randint(1, rows // 2), side="right"))
        count, sizes, ends, begins = count[:, :keep], sizes[:keep], ends[:keep], begins[:keep]
    d0 = int(np.searchsorted(ends, t0, side="right"))
    d1 = int(np.searchsorted(begins, t0 + rows, side="left"))
    assert (begins[d0] < t0) == first_inside and (ends[d1 - 1] > t0 + rows) == last_past
    n = d1 - d0
    starts = rng.randint(0, cap - 64, count.shape)
    out_count = np.zeros((sides, rows), dtype=np.int32)
    out_count[:, :n] = count[:, d0:d1]
    out_starts = np.zeros((sides, rows), dtype=np.int32)
    out_starts[:, :n] = starts[:, d0:d1]
    out_ends = np.full(rows, ends[d1 - 1] - t0, dtype=np.int32)
    out_ends[:n] = ends[d0:d1] - t0
    return out_count, out_starts, out_ends


def _expand_oracle(words, count, starts, ends, rows, wide):
    """``join_expand`` in numpy: each window's combinations numbered as
    ``itertools.product`` numbers them (side 0 slowest)."""
    import itertools

    sides = count.shape[0]
    scratch = words.shape[1] - 1
    pos = np.full((sides, rows), scratch, dtype=np.int64)
    last = -1
    for w in range(count.shape[1]):
        if ends[w] <= last:
            break  # the padding windows repeat the last end
        last = int(ends[w])
        shape = [max(int(c), 1) for c in count[:, w]]
        begin = last - int(np.prod(shape))
        for k, combo in enumerate(itertools.product(*map(range, shape))):
            if 0 <= begin + k < rows:
                for s in range(sides):
                    if count[s, w] > 0:
                        pos[s, begin + k] = starts[s, w] + combo[s]
    return words[0][pos], words[1][pos[list(wide)]].reshape(len(wide), rows)


@pytest.mark.parametrize("full", [False, True], ids=["narrow", "wide"])
@pytest.mark.parametrize("sides", [2, 3])
@pytest.mark.parametrize("rows", [256, 4096, 65536])
def test_the_expansion_numbers_each_windows_rows_as_the_product_does(rows, sides, full):
    """``join_expand`` against a numpy oracle at each of the output
    ladder's sizes: a first window that began in the chunk before, a
    last window that runs past the chunk (or a close that ends inside
    it, the padding windows repeating its end), sides with no row."""
    from bytewax_tpu.ops.join import OUTPUT_LADDER, join_expand

    assert rows in OUTPUT_LADDER
    rng = np.random.RandomState(rows + sides + full)
    cap = 1 << 12
    words = rng.randint(-(2**31), 2**31 - 1, (2, cap)).astype(np.int32)
    wide = tuple(range(sides)) if full else ()
    for first_inside in (True, False):
        for last_past in (True, False):
            count, starts, ends = _expand_chunk(rng, rows, sides, cap, first_inside, last_past)
            low, high = join_expand(words, count, starts, ends, rows=rows, wide=wide)
            want_low, want_high = _expand_oracle(words, count, starts, ends, rows, wide)
            assert np.array_equal(np.asarray(low), want_low)
            assert np.array_equal(np.asarray(high), want_high)
            scratch = words[:, cap - 1]
            assert (np.asarray(low) == scratch[0]).any(axis=1).all()


def test_a_close_compiles_no_expansion_the_walk_did_not(now):
    """Once ``join_expand`` ran at the set-up walk's argument shapes
    (``benchmark/flows/nexmark_q8.py`` ``warm_join_programs``) for an
    arena size and every ladder size, a real close at that arena size
    compiles no expansion of its own."""
    import jax.numpy as jnp

    from bytewax_tpu.ops import join

    st = DeviceJoinState(_spec(wait_s=0))
    # Two keys of 40 x 40 rows and 60 of 3 x 3: 3,740 output rows from
    # 520 stored, in the store's second arena; then one row alone.
    rows = [
        (f"k{k}", 3, side, 1000 * k + r)
        for k in range(62)
        for side in (0, 1)
        for r in range(40 if k < 2 else 3)
    ]
    _deliver(st, rows, [])
    arena = st.store.cap
    assert arena == 4 * join._MIN_ROWS
    words = jnp.zeros((2, arena), dtype=jnp.int32)
    for ladder in join.OUTPUT_LADDER:
        zeros = jnp.zeros((2, ladder), dtype=jnp.int32)
        join.join_expand(words, zeros, zeros, jnp.zeros(ladder, dtype=jnp.int32), rows=ladder, wide=())
    compiled = join.join_expand._cache_size()
    before = dict(flight.RECORDER.counters)
    events = []
    now[0] = T0 + timedelta(seconds=100)
    _deliver(st, [("z", 400, 0, 1)], events)
    events += st.on_eof()
    assert st.store.cap == arena
    assert _gained(before, "join_expand_rows") == 4096 + 256
    assert join.join_expand._cache_size() == compiled
    got = Counter((k, (wid, row)) for k, (wid, tag, row) in events if tag == "E")
    assert got == _want(rows + [("z", 400, 0, 1)])
