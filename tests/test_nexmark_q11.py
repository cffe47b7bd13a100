"""NEXmark Query 11 as the benchmark runs it, small, on the CPU: the
session flow against its plain reference over several seeds, and what
the deployment forced in the session tier, each against the same
reference on hand-made streams: a merge by a bridging row, rows out of
order within a delivery, a key let go with its last session that comes
back under a new id, and a resume from a snapshot taken while a key let
go had its id given to another."""

import json
import os
import sys
from datetime import datetime, timedelta, timezone

import numpy as np
import pytest

from bytewax_tpu import xla
from bytewax_tpu.engine import flight
from bytewax_tpu.engine import window_accel as wa
from bytewax_tpu.engine.arrays import ArrayBatch
from bytewax_tpu.engine.window_accel import SessionAccelSpec
from bytewax_tpu.operators.windowing import (
    LATE_SESSION_ID,
    SessionWindower,
    _SessionWindowerState,
    _WindowSnapshot,
)
from bytewax_tpu.testing import TestingSink, run_main

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmark.flows import nexmark_q11 as q11  # noqa: E402

ALIGN = datetime(2022, 1, 1, tzinfo=timezone.utc)
T0 = ALIGN + timedelta(days=400)
GAP_S = 10
_US = 1_000_000


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(REPO, "benchmark", "configs", "nexmark-q11.json")) as f:
        return json.load(f)


def _run_q11(cfg, bids, seed, poll=2500):
    """The benchmark's flow over the first ``bids`` bids of a seeded
    stream; what the sink received."""
    from tests.test_xla import ArraySource

    data = q11.make_data(cfg, {}, seed, "")
    batches = [
        q11.batch(cfg, data, lo, min(bids, lo + poll)) for lo in range(0, bids, poll)
    ]
    out = []
    run_main(q11.build_flow(cfg, data, ArraySource(batches), TestingSink(out)))
    return data, out


@pytest.mark.parametrize("seed", [3, 2147483659, 9_000_000_011])
def test_flow_matches_its_reference(monkeypatch, cfg, seed):
    """Every check 0 against the numpy reference, no bid late, on the
    device tier, and the sessions are what the shapes make: a session a
    bidder, three bids in four on a hot one."""
    monkeypatch.setenv("BYTEWAX_TPU_ACCEL", "1")
    bids = 12_000
    before = dict(flight.RECORDER.counters)
    data, out = _run_q11(cfg, bids, seed)
    got = q11.result_arrays(cfg, [q11.pack(out)])
    want = q11.reference(cfg, data, bids)
    numbers = q11.compare(cfg, got, want)
    assert numbers == dict.fromkeys(numbers, 0), numbers
    assert set(numbers) | {"off_device"} == set(cfg["limits"])
    assert all(limit == 0 for limit in cfg["limits"].values())
    assert got["bids"].sum() == bids and (got["rank"] == 0).all()
    assert got["bids"].max() > 256  # a hot bidder, past bfloat16's exact range

    def gained(name):
        return flight.RECORDER.counters.get(name, 0) - before.get(name, 0)

    assert gained("session_opens") == gained("session_closes") == len(want["kid"])
    assert gained("window_keys_opened") == len(want["kid"])
    assert gained("session_place_spans") >= 1 and gained("session_close_spans") >= 1


def test_the_tiers_write_the_same_sessions(monkeypatch, cfg):
    outs = []
    for accel in ("1", "0"):
        monkeypatch.setenv("BYTEWAX_TPU_ACCEL", accel)
        _data, out = _run_q11(cfg, 5000, seed=2147483659)
        outs.append(sorted(out))
    assert outs[0] == outs[1]
    assert {"session_place", "session_close"} <= flight.TRACED_PHASES


def test_controls_fail_the_comparison(cfg):
    """Each control is not correct by the configuration's limits."""
    data = q11.make_data(cfg, {}, 11, "")
    served = 30_000
    want = q11.reference(cfg, data, served)
    for which in q11.CONTROLS:
        numbers = q11.compare(cfg, q11.control_results(cfg, data, served, which), want)
        assert any(numbers[k] > cfg["limits"][k] for k in numbers), which


# -- the session tier on hand-made streams ------------------------------------


@pytest.fixture
def now(monkeypatch):
    """System time under the test's hand (the tier reads it through
    its module's ``datetime``)."""
    at = [T0]

    class _Datetime(datetime):
        @classmethod
        def now(cls, tz=None):
            return at[0]

    monkeypatch.setattr(wa, "datetime", _Datetime)
    monkeypatch.setenv("BYTEWAX_TPU_SHARD", "0")
    return at


class _Tier:
    """A count session state driven as the engine drives it: a
    delivery's phase, its keys let go, a notify; every event kept."""

    def __init__(self, now, wait_s, meta=True):
        spec = SessionAccelSpec(
            "count", xla.column_ts, timedelta(seconds=GAP_S), timedelta(seconds=wait_s)
        )
        spec.meta_live = meta
        self.now, self.st, self.events = now, spec.make_state(), []

    def deliver(self, rows, at_s=None):
        """``rows``: ``(key, seconds since ALIGN)``."""
        if at_s is not None:
            self.now[0] = T0 + timedelta(seconds=at_s)
        us = np.asarray([int(s * _US) for _k, s in rows], dtype=np.int64)
        batch = ArrayBatch(
            {
                "key": np.asarray([k for k, _s in rows]),
                "ts": np.datetime64(ALIGN.replace(tzinfo=None), "us")
                + us.astype("timedelta64[us]"),
            }
        )
        late, phase = self.st.on_batch_columnar(batch)
        closes, _hint, gone = phase()
        self.st.let_go(gone)
        self.events += late + closes + self.st.on_notify()

    def notify(self, at_s):
        self.now[0] = T0 + timedelta(seconds=at_s)
        self.events += self.st.on_notify()

    def eof(self):
        self.events += self.st.on_eof()
        return self.events

    def sessions(self):
        """``{(key, id): count}`` of the closed sessions."""
        out = {}
        for key, (wid, tag, value) in self.events:
            if tag == "E":
                assert (key, wid) not in out, "a session written twice"
                out[key, wid] = value
        return out


def _want(rows):
    """The reference's sessions of hand-made rows: per key, counts in
    time order."""
    keys = sorted({k for k, _s in rows})
    kid = np.asarray([keys.index(k) for k, _s in rows], dtype=np.int64)
    ts = np.asarray([int(s * _US) for _k, s in rows], dtype=np.int64)
    got = q11.sessions_of(kid, ts, GAP_S * _US)
    out = {}
    for k, bids in zip(got["kid"].tolist(), got["bids"].tolist()):
        out.setdefault(keys[k], []).append(bids)
    return out


def _by_key(sessions):
    """``{key: counts in id order}``."""
    out = {}
    for (key, _wid), count in sorted(sessions.items()):
        out.setdefault(key, []).append(count)
    return out


def test_a_late_bridge_merges_two_sessions(now):
    """Two sessions of a key, then rows in the gap between them that are
    still on time: one session, the earlier's id, the later's id among
    its merged ones, as the reference groups them."""
    tier = _Tier(now, wait_s=60)
    first = [("a", 0), ("a", 2), ("b", 1)]
    second = [("a", 30), ("b", 40)]
    bridge = [("a", 12), ("a", 21), ("b", 10), ("b", 20), ("b", 30)]
    tier.deliver(first)
    tier.deliver(second)
    assert len(tier.st.open) == 4
    tier.deliver(bridge)
    assert len(tier.st.open) == 2
    events = tier.eof()
    got = tier.sessions()
    assert got == {("a", 0): 5, ("b", 0): 5}
    assert _by_key(got) == _want(first + second + bridge)
    metas = {(k, wid): m for k, (wid, tag, m) in events if tag == "M"}
    assert metas["a", 0].merged_ids == {1} and metas["b", 0].merged_ids == {1}
    assert metas["a", 0].open_time == ALIGN
    assert metas["a", 0].close_time == ALIGN + timedelta(seconds=30)
    assert not tier.st._merged and not tier.st._extra


def test_one_delivery_bridging_two_sessions_and_opening_one(now):
    """The run-by-run path: a delivery whose runs bridge two sessions of
    a key and open another, beside keys the array path places."""
    tier = _Tier(now, wait_s=60)
    rows = [("a", 0), ("a", 20), ("c", 3), ("c", 50)]
    later = [("a", 10), ("a", 48), ("c", 4), ("d", 7)]
    tier.deliver(rows)
    tier.deliver(later)
    tier.eof()
    got = tier.sessions()
    assert _by_key(got) == _want(rows + later)
    assert got[("a", 0)] == 3 and got[("a", 2)] == 1


def test_rows_out_of_order_within_a_delivery(now):
    """A delivery that descends within and across keys, on time by the
    wait: the sessions the reference makes."""
    rng = np.random.RandomState(4)
    secs = rng.randint(0, 120, size=400)
    keys = [f"k{i}" for i in rng.randint(0, 7, size=400)]
    rows = list(zip(keys, secs.tolist()))
    tier = _Tier(now, wait_s=500, meta=False)
    tier.deliver(rows[:250])
    tier.deliver(rows[250:])
    events = tier.eof()
    assert not any(tag == "M" for _k, (_w, tag, _v) in events)
    assert _by_key(tier.sessions()) == _want(rows)


def test_a_key_let_go_comes_back_with_a_new_session_id(now):
    """A key whose last session closed is let go one delivery later (id,
    clock, encoder entries); what the host tier keeps of it, its clock
    and its next session id, stays by name, so it comes back late by
    the clock it had and with a session id it never had."""
    st_counters = dict(flight.RECORDER.counters)
    tier = _Tier(now, wait_s=0)
    tier.deliver([("a", 1), ("a", 3), ("b", 2)], at_s=0)
    tier.deliver([("b", 120)], at_s=100)  # a's session closes here: parked
    assert "a" in tier.st.key_ids
    tier.deliver([("b", 121)], at_s=101)  # ... and a is let go here
    assert "a" not in tier.st.key_ids and "a" in tier.st._retired
    assert len(tier.st.open) == 1
    assert _gained(st_counters, "session_keys_remembered") == 1
    kid_of_a = tier.st._free_kids[-1]
    # A new key takes a's id and starts at session 0.
    tier.deliver([("c", 130)], at_s=102)
    assert tier.st.key_ids["c"] == kid_of_a
    # a again: 50 is late by a's clock (3 + 103 s), 200 opens session 1.
    tier.deliver([("a", 50), ("a", 200)], at_s=103)
    assert "a" not in tier.st._retired
    assert _gained(st_counters, "session_keys_remembered") == 0
    events = tier.eof()
    got = tier.sessions()
    assert got == {("a", 0): 2, ("a", 1): 1, ("b", 0): 1, ("b", 1): 2, ("c", 0): 1}
    assert [ev for ev in events if ev[1][1] == "L"] == [
        ("a", (LATE_SESSION_ID, "L", ALIGN + timedelta(seconds=50)))
    ]
    gained = {
        name: _gained(st_counters, name)
        for name in ("window_keys_opened", "window_keys_retired")
    }
    assert gained == {"window_keys_opened": 4, "window_keys_retired": 1}


def _gained(before, name):
    return flight.RECORDER.counters.get(name, 0) - before.get(name, 0)


def test_resume_from_a_snapshot_taken_while_a_key_was_let_go(now):
    """Snapshots taken after ``a`` was let go and its id given to ``c``:
    ``a``'s is the host tier's session logic with no session; resumed
    on either tier, ``a`` comes back under session id 1, never 0."""
    tier = _Tier(now, wait_s=0)
    tier.deliver([("a", 1), ("b", 2)], at_s=0)
    tier.deliver([("b", 120)], at_s=100)
    tier.deliver([("b", 121)], at_s=101)
    tier.deliver([("c", 122)], at_s=102)
    assert "a" not in tier.st.key_ids and tier.st.key_ids["c"] == 0
    snaps = dict(tier.st.snapshots_for(["a", "b", "c", "never"]))
    assert snaps["never"] is None
    a = snaps["a"]
    assert a.windower_state.next_id == 1 and a.windower_state.sessions == {}
    assert a.logic_states == {}
    assert set(snaps["b"].windower_state.sessions) == {1}

    # Device to device (and the format the host tier loads).
    resumed = _Tier(now, wait_s=0)
    resumed.st.load_many([(k, snap) for k, snap in sorted(snaps.items()) if snap])
    resumed.deliver([("a", 300), ("b", 125)], at_s=103)
    resumed.eof()
    assert resumed.sessions() == {("a", 1): 1, ("b", 1): 3, ("c", 0): 1}

    # Host to device: a host logic of "a" with no session and next_id 3.
    host = _Tier(now, wait_s=0)
    host.st.load_many(
        [
            (
                "a",
                _WindowSnapshot(
                    wa._clock_state(1 * _US + wa._to_us(ALIGN), T0.timestamp() * _US),
                    _SessionWindowerState(next_id=3),
                    {},
                    [],
                ),
            )
        ]
    )
    host.deliver([("a", 400)], at_s=104)
    host.eof()
    assert host.sessions() == {("a", 3): 1}


def test_host_tier_resumes_a_key_let_go_where_it_stopped(now):
    """The device tier's snapshot of a key let go builds the host tier's
    logic: its next session id goes on from there."""
    tier = _Tier(now, wait_s=0)
    tier.deliver([("a", 1)], at_s=0)
    tier.notify(100)
    tier.deliver([("b", 2)], at_s=101)
    ((_key, snap),) = tier.st.snapshots_for(["a"])
    logic = SessionWindower(gap=timedelta(seconds=GAP_S)).build(snap.windower_state)
    assert list(logic.open_for(ALIGN + timedelta(seconds=500))) == [1]


def test_a_key_back_with_late_rows_alone_goes_again(now):
    """A key let go that comes back with late rows only takes its clock
    up again, opens no session and goes again one delivery later, its
    next session id unmoved."""
    tier = _Tier(now, wait_s=0)
    tier.deliver([("a", 1), ("b", 2)], at_s=0)
    tier.deliver([("b", 120)], at_s=100)
    tier.deliver([("b", 121)], at_s=101)
    assert "a" not in tier.st.key_ids
    tier.deliver([("a", 5), ("a", 6)], at_s=102)  # late by a's clock, 1 + 102 s
    assert "a" in tier.st.key_ids and len(tier.st.open) == 1
    tier.deliver([("b", 140)], at_s=103)
    assert "a" not in tier.st.key_ids and tier.st._retired["a"][2] == 1
    tier.deliver([("a", 300)], at_s=104)
    events = tier.eof()
    assert sum(tag == "L" for _k, (_w, tag, _v) in events) == 2
    assert tier.sessions() == {
        ("a", 0): 1, ("a", 1): 1, ("b", 0): 1, ("b", 1): 2, ("b", 2): 1,
    }


def test_keys_let_go_and_back_with_the_lane_racing_the_main_thread(monkeypatch):
    """At pipeline depth 2 the main thread gives keys ids (and queues
    their first session ids, a returning key's where it stopped) while
    the lane places runs and closes sessions.  With the interpreter
    switching threads every microsecond and keys let go and back all
    the time (a gap of a millisecond, a key's rows 1,000 s of event
    time apart, so that no stall of the wall clock makes one late),
    every key's session ids are 0 .. n-1, each once, one bid each: a
    lost or stale next id would write one twice."""
    from tests.test_xla import ArraySource

    import bytewax_tpu.operators as op
    import bytewax_tpu.operators.windowing as w
    from bytewax_tpu.dataflow import Dataflow

    monkeypatch.setenv("BYTEWAX_TPU_ACCEL", "1")
    monkeypatch.setenv("BYTEWAX_TPU_PIPELINE_DEPTH", "2")
    rng = np.random.RandomState(8)
    seen = {}
    batches = []
    for _ in range(300):
        keys = [f"k{k}" for k in rng.choice(40, size=12, replace=False)]
        secs = []
        for key in keys:
            seen[key] = seen.get(key, 0) + 1
            secs.append(seen[key])
        us = np.asarray(secs, dtype=np.int64) * 1000 * _US
        batches.append(
            ArrayBatch(
                {
                    "key": np.asarray(keys),
                    "ts": np.datetime64(ALIGN.replace(tzinfo=None), "us")
                    + us.astype("timedelta64[us]"),
                }
            )
        )
    clock = w.EventClock(ts_getter=xla.column_ts, wait_for_system_duration=timedelta(0))
    flow = Dataflow("test_df")
    s = op.input("inp", flow, ArraySource(batches))
    sessions = w.count_window(
        "count", s, clock, SessionWindower(gap=timedelta(milliseconds=1)),
        key=lambda row: row[0],
    )
    out = []
    op.output("out", sessions.down, TestingSink(out))
    late = []
    op.output("late", sessions.late, TestingSink(late))
    before = flight.RECORDER.counters.get("window_keys_retired", 0)
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        run_main(flow)
    finally:
        sys.setswitchinterval(interval)
    assert not late
    ids = {}
    for key, (wid, count) in out:
        assert count == 1
        ids.setdefault(key, []).append(wid)
    assert {k: sorted(v) for k, v in ids.items()} == {
        k: list(range(n)) for k, n in seen.items()
    }
    assert flight.RECORDER.counters.get("window_keys_retired", 0) > before
