"""NEXmark Query 5 as the benchmark runs it, small, on the CPU: the
two-stage flow against its plain reference on both tiers, parts of a
window merging to one answer, and what the deployment forced in the
window tier: a key is let go with its last window, as the host tier
discards an empty window logic."""

import json
import os
import sys
from datetime import datetime, timedelta, timezone

import numpy as np
import pytest

import bytewax_tpu.operators as op
import bytewax_tpu.operators.windowing as w
from bytewax_tpu.dataflow import Dataflow
from bytewax_tpu.engine import flight
from bytewax_tpu.engine.window_accel import WindowAccelSpec
from bytewax_tpu.operators.windowing import EventClock, TumblingWindower
from bytewax_tpu.testing import TestingSink, TestingSource, run_main

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmark.flows import nexmark_q5 as q5  # noqa: E402

ALIGN = datetime(2022, 1, 1, tzinfo=timezone.utc)
SLIDING = w.SlidingWindower(
    length=timedelta(seconds=10), offset=timedelta(seconds=5), align_to=ALIGN
)
TUMBLING = TumblingWindower(length=timedelta(seconds=10), align_to=ALIGN)


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(REPO, "benchmark", "configs", "nexmark-q5.json")) as f:
        return json.load(f)


def _run_q5(cfg, bids, seed=5, poll=1500):
    """The benchmark's flow over the first ``bids`` bids of a seeded
    stream; what the sink received, as the comparison reads it."""
    from tests.test_xla import ArraySource

    data = q5.make_data(cfg, {}, seed, "")
    batches = [
        q5.batch(cfg, data, lo, min(bids, lo + poll)) for lo in range(0, bids, poll)
    ]
    out = []
    run_main(q5.build_flow(cfg, data, ArraySource(batches), TestingSink(out)))
    return data, out


def _gained(before):
    return {
        name: flight.RECORDER.counters.get(name, 0) - before.get(name, 0)
        for name in ("window_keys_opened", "window_keys_retired")
    }


def _checked(cfg, data, out, bids):
    got = q5.result_arrays(cfg, [q5.pack(out)])
    numbers = q5.compare(cfg, got, q5.reference(cfg, data, bids))
    return got, numbers


@pytest.mark.parametrize("accel", ["1", "0"], ids=["device", "host"])
def test_flow_matches_its_reference_on_both_tiers(monkeypatch, cfg, accel):
    """Every check 0 against the numpy reference, no bid late, on the
    device tier and with ``BYTEWAX_TPU_ACCEL`` off."""
    monkeypatch.setenv("BYTEWAX_TPU_ACCEL", accel)
    bids = 6000
    data, out = _run_q5(cfg, bids)
    got, numbers = _checked(cfg, data, out, bids)
    assert numbers == dict.fromkeys(numbers, 0), numbers
    assert set(numbers) | {"off_device"} == set(cfg["limits"])
    assert all(limit == 0 for limit in cfg["limits"].values())
    # 6000 bids are 0.65 s of event time: the windows -1 and 0, each
    # holding every bid, and a hot auction well past bfloat16's 256.
    assert got["wid"].tolist() == [-1, 0]
    assert got["total"].tolist() == [bids, bids] and got["top"].min() > 256


def test_tiers_write_the_same_rows(monkeypatch, cfg):
    outs = []
    for accel in ("1", "0"):
        monkeypatch.setenv("BYTEWAX_TPU_ACCEL", accel)
        _data, out = _run_q5(cfg, 4000, seed=2147483659)
        outs.append(q5.result_arrays(cfg, [q5.pack(out)]))
    device, host = outs
    for name in ("wid", "top", "auctions", "total", "hot"):
        assert device[name].tolist() == host[name].tolist(), name
    assert device["late"] == host["late"] == 0


def test_spans_and_counters_of_the_two_stages(monkeypatch, cfg):
    """The hot-items stage runs under the span ``logic`` with every
    first-stage count as a row, letting keys go under ``retire``, and
    every auction that was given a key id gives it back."""
    monkeypatch.setenv("BYTEWAX_TPU_ACCEL", "1")
    before = dict(flight.RECORDER.counters)
    phases = dict(flight.RECORDER.phase_totals)
    data, out = _run_q5(cfg, 6000)
    gained = {
        name: value - before.get(name, 0)
        for name, value in flight.RECORDER.counters.items()
    }
    got = q5.result_arrays(cfg, [q5.pack(out)])
    assert gained["logic_spans"] >= 1
    assert gained["logic_rows"] == got["auctions"].sum() == gained["window_opens"]
    auctions = len(np.unique(q5.columns(cfg, data, 0, 6000)["kid"]))
    assert gained["window_keys_opened"] == gained["window_keys_retired"] == auctions
    assert gained["retire_spans"] >= 2 and gained["retire_rows"] >= auctions
    moved = {
        name
        for name, seconds in flight.RECORDER.phase_totals.items()
        if seconds > phases.get(name, 0)
    }
    assert "logic" in moved and {n.rpartition("/")[2] for n in moved} >= {"retire"}
    assert {"logic", "retire"} <= flight.TRACED_PHASES


def test_a_straggler_after_the_linger_is_a_second_part(cfg):
    """A count that arrives after a window's part was written starts a
    new part, and the parts merge to the answer one part would give."""
    counts = [(str(1000 + a), (7, c)) for a, c in enumerate([3, 9, 9, 1])]
    late_one = [("2000", (7, 9)), ("2001", (7, 2))]
    linger = 0.2

    def run(inp):
        out = []
        flow = Dataflow("test_df")
        s = op.input("inp", flow, TestingSource(inp, batch_size=10))
        s = op.flat_map_batch("by_window", s, q5._by_window)
        s = op.stateful_batch("hot_items", s, q5.hot_items_logic(linger))
        op.output("out", s, TestingSink(out))
        run_main(flow)
        return out

    apart = run(counts + [TestingSource.PAUSE(timedelta(seconds=1.0))] + late_one)
    together = run(counts + late_one)
    assert len(apart) == 2 and len(together) == 1
    assert apart[0] == ("7", (9, ("1001", "1002"), 4, 22))
    merged = q5.result_arrays(cfg, [q5.pack(apart[:1]), q5.pack(apart[1:])])
    whole = q5.result_arrays(cfg, [q5.pack(together)])
    assert merged.pop("parts") == 2 and whole.pop("parts") == 1
    for name in whole:
        assert np.array_equal(merged[name], whole[name]), name
    assert merged["top"].tolist() == [9] and merged["total"].tolist() == [33]
    assert (merged["hot"] & 0xFFFFFFFF).tolist() == [1, 2, 1000]


# -- key retirement -------------------------------------------------------------


def _dying_keys_input():
    """Keys whose windows all close by system time during a pause and
    that come back afterwards with rows older than what they had seen:
    on time for a new clock, late for the old one.  ``stay`` gets the
    same old row behind a new one, which is late on both tiers."""

    def at(sec):
        return ALIGN + timedelta(seconds=sec)

    first = [("a", at(1.7)), ("b", at(1.8)), ("a", at(1.9))]
    back = [
        ("stay", at(1000.0)), ("a", at(0.5)), ("stay", at(0.5)),
        ("b", at(4.0)), ("a", at(0.7)),
    ]
    return first + [TestingSource.PAUSE(timedelta(seconds=2.0))] + back


def _count_flow(inp, windower, wait_s=0.1):
    taps = {"down": [], "late": []}
    flow = Dataflow("test_df")
    s = op.input("inp", flow, TestingSource(inp, batch_size=8))
    clock = EventClock(
        ts_getter=lambda kv: kv[1],
        wait_for_system_duration=timedelta(seconds=wait_s),
    )
    wo = w.count_window("win", s, clock, windower, key=lambda kv: kv[0])
    op.output("down", wo.down, TestingSink(taps["down"]))
    op.output("late", wo.late, TestingSink(taps["late"]))
    return flow, taps


@pytest.mark.parametrize(
    "windower",
    [
        TumblingWindower(length=timedelta(seconds=2), align_to=ALIGN),
        w.SlidingWindower(
            length=timedelta(seconds=2), offset=timedelta(seconds=1), align_to=ALIGN
        ),
    ],
    ids=["tumbling", "sliding"],
)
@pytest.mark.parametrize("shard", ["0", "auto"], ids=["one_device", "mesh"])
def test_tiers_agree_on_keys_that_die_and_come_back(
    monkeypatch, entry_point, shard, windower
):
    monkeypatch.setenv("BYTEWAX_TPU_SHARD", shard)
    taps, counted = {}, {}
    for accel in ("1", "0"):
        monkeypatch.setenv("BYTEWAX_TPU_ACCEL", accel)
        flow, taps[accel] = _count_flow(_dying_keys_input(), windower)
        before = dict(flight.RECORDER.counters)
        entry_point(flow)
        counted[accel] = _gained(before)
    for tap in ("down", "late"):
        assert sorted(taps["1"][tap], key=repr) == sorted(taps["0"][tap], key=repr), tap
    down, late = taps["1"]["down"], taps["1"]["late"]
    # "a" had window 0 twice: closed during the pause, opened again by
    # the rows that came back (a new clock takes them; the old one,
    # 1.8 s ahead, would have dropped them as "stay"'s does).
    assert sorted(c for k, (wid, c) in down if (k, wid) == ("a", 0)) == [2, 2]
    assert [k for k, _ in late] and {k for k, _ in late} == {"stay"}
    # a, b and stay, then a and b again; every one let go by the end.
    assert counted["1"] == {"window_keys_opened": 5, "window_keys_retired": 5}
    assert counted["0"] == {"window_keys_opened": 0, "window_keys_retired": 0}


def _state(windower, wait_s=0.0):
    offset = getattr(windower, "offset", windower.length)
    spec = WindowAccelSpec(
        "count", lambda v: v, ALIGN, windower.length, offset, timedelta(seconds=wait_s)
    )
    spec.meta_live = False
    return spec.make_state()


def _deliver(st, keys, secs):
    """One columnar delivery, finalized as the driver finalizes it."""
    from bytewax_tpu.engine.arrays import ArrayBatch

    batch = ArrayBatch(
        {
            "key": np.asarray(keys),
            "ts": np.datetime64(ALIGN.replace(tzinfo=None), "us")
            + (np.asarray(secs) * 1e6).astype("timedelta64[us]"),
        }
    )
    late, phase = st.on_batch_columnar(batch)
    closes, _hint, gone = phase()
    st.let_go(gone)
    return list(late + closes)


def _age(st, seconds):
    """As if ``seconds`` of system time had passed since every key's
    newest event was taken in."""
    st.sys_at_base -= seconds * 1e6
    st.open.shift(-seconds * 1e6)


@pytest.mark.parametrize("windower", [TUMBLING, SLIDING], ids=["tumbling", "sliding"])
@pytest.mark.parametrize("shard", ["0", "auto"], ids=["one_device", "mesh"])
def test_state_holds_only_keys_with_an_open_window(monkeypatch, shard, windower):
    """Over rounds of keys that are born and die the host arrays stay
    at the size of one round's keys, ``window_keys_opened -
    window_keys_retired`` is the number of keys with an open window
    after every delivery, and end of input lets every key go."""
    monkeypatch.setenv("BYTEWAX_TPU_SHARD", shard)
    st = _state(windower, wait_s=10.0)
    before = dict(flight.RECORDER.counters)
    rounds, born = 8, 40
    results = []
    for r in range(rounds):
        keys = [f"k{r}-{i}" for i in range(born)] + ["every"] + (
            ["other"] if r % 2 == 0 else []
        )
        results += _deliver(st, keys * 2, [30.0 * r + 1 + i % 7 for i in range(2 * len(keys))])
        alive = np.unique(st.open.comp >> 32)
        counted = _gained(before)
        held = counted["window_keys_opened"] - counted["window_keys_retired"]
        assert held == len(alive) == len(st.key_ids)
        assert sorted(st.key_ids.values()) == alive.tolist()
        assert [k for k in st.keys if k is not None] and len(st.keys) <= 2 * (born + 2)
        # The key-indexed columns grow by doubling: at most twice the ids.
        assert len(st.keys) <= len(st.base_us) == len(st.sys_at_base) <= 2 * len(st.keys)
        # 25 s on: every window of this round is due at the next
        # delivery's close (the clock waits 10 s), and that
        # delivery's rows (30 s of event time on) are on time.
        _age(st, 25)
    # "every" has a row a round and is never let go; "other" skips
    # every second round and is let go in it.
    assert counted["window_keys_opened"] == rounds * born + 1 + rounds // 2
    results += st.on_eof()
    counted = _gained(before)
    assert counted["window_keys_opened"] == counted["window_keys_retired"]
    assert not st.key_ids and st.open_count == 0 and not any(st.keys)
    # Nothing was lost on the way: every row counted once a window.
    panes = 2 if windower is SLIDING else 1
    assert sum(c for _k, (_wid, _e, c) in results) == panes * 2 * (
        rounds * (born + 1) + rounds // 2
    )


@pytest.mark.parametrize(
    "path", ["key_column", "key_id_vocab", "items"], ids=lambda p: p
)
def test_every_ingest_path_forgets_a_key_that_was_let_go(path):
    """A key that comes back through any ingest path is a new key: a
    new id (a free one is taken first), a clock at minus infinity."""
    from bytewax_tpu.engine.arrays import ArrayBatch

    st = _state(TUMBLING)
    base = np.datetime64(ALIGN.replace(tzinfo=None), "us")
    vocab = np.array(["a", "b", "c"])

    def deliver(keys, secs):
        ts = base + (np.asarray(secs) * 1e6).astype("timedelta64[us]")
        if path == "key_column":
            ingest = st.on_batch_columnar(ArrayBatch({"key": np.asarray(keys), "ts": ts}))
        elif path == "key_id_vocab":
            ids = np.array([vocab.tolist().index(k) for k in keys], dtype=np.int32)
            ingest = st.on_batch_columnar(
                ArrayBatch({"key_id": ids, "ts": ts}, key_vocab=vocab)
            )
        else:
            items = [(k, ALIGN + timedelta(seconds=s)) for k, s in zip(keys, secs)]
            ingest = st.on_batch_items(items)
            if ingest is None:
                pytest.skip("no native toolchain for itemized promotion")
        late, phase = ingest
        closes, _hint, gone = phase()
        st.let_go(gone)
        return list(late + closes)

    assert deliver(["a", "b", "a"], [1, 2, 3]) == []
    kid_b = st.key_ids["b"]
    _age(st, 30)
    # "c" arrives; the close of its delivery takes a's and b's windows.
    closed = deliver(["c"], [31])
    assert sorted(closed) == [("a", (0, "E", 2)), ("b", (0, "E", 1))]
    assert list(st.key_ids) == ["c"] and len(st._free_kids) == 2
    # "b" comes back with a row its old clock (2 s + 30 s) would drop.
    again = deliver(["b", "c"], [5, 32])
    assert again == [] and st.key_ids["b"] in (0, kid_b)
    assert st.base_us[st.key_ids["b"]] == 5e6 + st.spec.align_us
    assert sorted(k for k in st.keys if k) == ["b", "c"] and len(st.keys) == 3
    done = st.on_eof()
    assert sorted(done) == [("b", (0, "E", 1)), ("c", (3, "E", 2))]
    assert not st.key_ids


def test_a_key_with_rows_in_flight_is_not_let_go():
    """The close of one delivery finds a key without a window while a
    later delivery, already taken in, holds an on-time row of it: the
    key stays (its fold is still to come) and goes with that window."""
    from bytewax_tpu.engine.arrays import ArrayBatch

    st = _state(TUMBLING, wait_s=60.0)
    base = np.datetime64(ALIGN.replace(tzinfo=None), "us")

    def ingest(keys, secs):
        ts = base + (np.asarray(secs) * 1e6).astype("timedelta64[us]")
        return st.on_batch_columnar(ArrayBatch({"key": np.asarray(keys), "ts": ts}))

    _late, first = ingest(["a"], [1])
    closes, _hint, gone = first()
    st.let_go(gone)
    assert list(closes) == [] and list(st.key_ids) == ["a"]
    _age(st, 120)
    # Two deliveries taken in before either phase runs (a pipeline
    # deeper than 2): the first one's close takes a's window.
    _late, second = ingest(["b"], [200])
    _late, third = ingest(["a"], [150])
    closes, _hint, gone = second()
    assert list(closes) == [("a", (0, "E", 1))] and gone[1].tolist() == [st.key_ids["a"]]
    st.let_go(gone)
    assert sorted(st.key_ids) == ["a", "b"]
    closes, _hint, gone = third()
    st.let_go(gone)
    assert sorted(st.on_eof()) == [("a", (15, "E", 1)), ("b", (20, "E", 1))]
    assert not st.key_ids


@pytest.mark.parametrize("shard", ["0", "auto"], ids=["one_device", "mesh"])
def test_resume_between_a_keys_death_and_its_return(monkeypatch, shard):
    """A snapshot taken after "a"'s window has closed and the key was
    let go writes "a" as a discard, as the host tier's does for a
    logic it dropped; the state resumed from it takes the rows that
    come back as the state that went on does: a new key, on time."""
    from bytewax_tpu.operators.windowing import _WindowSnapshot

    monkeypatch.setenv("BYTEWAX_TPU_SHARD", shard)
    straight = _state(TUMBLING)
    assert _deliver(straight, ["a", "a"], [9.5, 9.7]) == []
    _age(straight, 30)
    assert _deliver(straight, ["b", "stay"], [40.0, 1000.0]) == [("a", (0, "E", 2))]
    assert sorted(straight.touched) == ["a", "b", "stay"]
    snaps = straight.snapshots_for(sorted(straight.touched))
    assert snaps[0] == ("a", None)
    assert all(isinstance(snap, _WindowSnapshot) for _key, snap in snaps[1:])
    resumed = _state(TUMBLING)
    resumed.load_many(snaps[1:])
    assert sorted(resumed.key_ids) == sorted(straight.key_ids) == ["b", "stay"]
    back = (["a", "stay", "a"], [3.0, 3.0, 4.0])
    events = [_deliver(st, *back) + list(st.on_eof()) for st in (straight, resumed)]
    assert sorted(events[0], key=repr) == sorted(events[1], key=repr)
    assert ("a", (0, "E", 2)) in events[0]
    late = [e for e in events[0] if e[1][1] == "L"]
    assert [key for key, _ in late] == ["stay"]


def test_an_epoch_close_without_a_store_reads_no_state_back(monkeypatch, recovery_config):
    """With no recovery store an epoch close forgets the keys the
    epoch touched and snapshots none of them (650,000 keys an epoch
    on NEXmark Q5: seconds a close, PERF.md); with a store it
    snapshots them as before."""
    from bytewax_tpu.engine.window_accel import DeviceWindowAggState

    monkeypatch.setenv("BYTEWAX_TPU_ACCEL", "1")
    asked = []
    snapshots_for = DeviceWindowAggState.snapshots_for

    def counted(self, keys):
        asked.append(len(keys))
        return snapshots_for(self, keys)

    monkeypatch.setattr(DeviceWindowAggState, "snapshots_for", counted)
    inp = [(f"k{i % 5}", ALIGN + timedelta(seconds=i)) for i in range(40)]
    want = None
    for store in (None, recovery_config):
        flow, taps = _count_flow(list(inp), TUMBLING, wait_s=60.0)
        asked.clear()
        run_main(flow, epoch_interval=timedelta(0), recovery_config=store)
        assert (asked == []) is (store is None)
        want = want or sorted(taps["down"])
        assert sorted(taps["down"]) == want and len(want) == 20


def _due_from_clock(st):
    """Every open window's due instant recomputed from the live clock:
    the system time at which its key's watermark reaches its close."""
    kids, _wids, closes = st._open_arrays()
    return st.sys_at_base[kids] + (closes - st.base_us[kids])


@pytest.mark.parametrize("windower", [TUMBLING, SLIDING], ids=["tumbling", "sliding"])
def test_due_instants_follow_the_keys_clocks(windower):
    """The open-window table keeps each window's due instant; it is
    the clock's after every delivery (new keys, a key whose later rows
    move its clock, rows behind the clock that move nothing), after a
    close, after phases that ran one delivery behind their ingests,
    and after a resume; the notify hint is the earliest of them and a
    close takes exactly the windows whose instant has passed."""
    from bytewax_tpu.engine.arrays import ArrayBatch

    st = _state(windower, wait_s=10.0)
    assert st.notify_at() is None
    assert _deliver(st, ["a", "b", "a", "c"], [1.0, 2.0, 12.0, 3.0]) == []
    np.testing.assert_array_equal(st.open.at, _due_from_clock(st))
    # "a" moves on (its open windows fall due sooner), "b" gets a row
    # behind its clock (on time: nothing moves), "d" is new.
    assert _deliver(st, ["a", "b", "d"], [14.0, 1.5, 2.0]) == []
    np.testing.assert_array_equal(st.open.at, _due_from_clock(st))
    assert st.notify_at().timestamp() * 1e6 == pytest.approx(st.open.at.min(), abs=1)
    # Two deliveries taken in before either phase runs: each phase
    # retimes with the clock of its own ingest, and the last one
    # leaves the table at the live clock.
    base = np.datetime64(ALIGN.replace(tzinfo=None), "us")

    def ingest(keys, secs):
        ts = base + (np.asarray(secs) * 1e6).astype("timedelta64[us]")
        return st.on_batch_columnar(ArrayBatch({"key": np.asarray(keys), "ts": ts}))

    _late, first = ingest(["b", "e"], [13.0, 4.0])
    _late, second = ingest(["b"], [14.5])
    for phase in (first, second):
        closes, _hint, gone = phase()
        st.let_go(gone)
        assert list(closes) == []
    np.testing.assert_array_equal(st.open.at, _due_from_clock(st))
    # Part of the table falls due: exactly those windows close.
    _age(st, 14)
    now_us = datetime.now(timezone.utc).timestamp() * 1e6
    kids, wids, _closes = st._open_arrays()
    due = {(st.keys[k], w) for k, w in zip(kids[st.open.at <= now_us], wids[st.open.at <= now_us])}
    assert due and len(due) < st.open_count
    closed = st.on_notify()
    assert {(k, wid) for k, (wid, _e, _c) in closed} == due
    np.testing.assert_array_equal(st.open.at, _due_from_clock(st))
    assert (st.open.at > now_us).all()
    # A resume retimes what it loads from the snapshots' clocks.
    snaps = [s for s in st.snapshots_for(sorted(st.key_ids)) if s[1] is not None]
    resumed = _state(windower, wait_s=10.0)
    resumed.load_many(snaps)
    assert resumed.open_count == st.open_count
    np.testing.assert_allclose(np.sort(resumed.open.at), np.sort(st.open.at), atol=2)
    np.testing.assert_array_equal(resumed.open.at, _due_from_clock(resumed))
