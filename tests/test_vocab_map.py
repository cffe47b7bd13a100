"""``VocabMap``, the append-only map from a batch's external key ids to
engine ids, against plain references: a dict for what ``sync`` maps,
``np.isin`` over the table for what ``drop_ids`` forgets.  A batch
costs its own rows and the keys it touches, whatever the vocabulary's
length, and the window and session tiers that grow their key columns
by doubling write what the host tier writes while keys go, come back
and take freed ids."""

from datetime import datetime, timedelta, timezone

import numpy as np
import pytest

import bytewax_tpu.operators as op
import bytewax_tpu.operators.windowing as w
from bytewax_tpu import xla
from bytewax_tpu.dataflow import Dataflow
from bytewax_tpu.engine import flight
from bytewax_tpu.engine.arrays import ArrayBatch, VocabMap
from bytewax_tpu.testing import TestingSink, run_main

ALIGN = datetime(2022, 1, 1, tzinfo=timezone.utc)
_US = 1_000_000


def _gained(before, name):
    return flight.RECORDER.counters.get(name, 0) - before.get(name, 0)


class _Ids:
    """The engine's side of a sync: one internal id a key name, freed
    ids given out again (newest freed first)."""

    def __init__(self):
        self.of = {}
        self.free = []
        self.next = 0

    def alloc_many(self, names):
        out = []
        for name in names:
            if name not in self.of:
                if self.free:
                    self.of[name] = self.free.pop()
                else:
                    self.of[name] = self.next
                    self.next += 1
            out.append(self.of[name])
        return out

    def release(self, names):
        ids = [self.of.pop(name) for name in names]
        self.free.extend(ids)
        return ids


class _Vocab:
    """A vocabulary of ``n`` names handed over the three ways a source
    can: ``view`` (a new view of one growing buffer), ``reused`` (the
    same whole ndarray every time) and ``list`` (one list grown in
    place)."""

    def __init__(self, kind, names):
        self.kind, self.names = kind, names
        self.buf = np.empty(0, dtype=names.dtype)
        self.filled = 0
        self.list = []

    def upto(self, n):
        if self.kind == "reused":
            return self.names
        if self.kind == "list":
            self.list.extend(self.names[len(self.list) : n].tolist())
            return self.list
        if n > len(self.buf):
            grown = np.empty(max(2 * len(self.buf), n), dtype=self.buf.dtype)
            grown[: self.filled] = self.buf[: self.filled]
            self.buf = grown
        self.buf[self.filled : n] = self.names[self.filled : n]
        self.filled = max(self.filled, n)
        return self.buf[: self.filled]


def _delivery(rng, kind, head, rows):
    """External ids of one delivery whose vocabulary reaches ``head``:
    ``narrow`` (a span of a few thousand below the head), ``from_zero``
    (from 0 up to twice the rows) or ``sparse`` (a few rows over all of
    it)."""
    if kind == "narrow":
        return rng.randint(max(0, head - 3200), head, size=rows)
    if kind == "from_zero":
        return rng.randint(0, min(head, 2 * rows), size=rows)
    return rng.randint(0, head, size=max(1, rows // 200))


@pytest.mark.parametrize("ids_kind", ["narrow", "from_zero", "sparse"])
@pytest.mark.parametrize("vocab_kind", ["view", "reused", "list"])
def test_sync_matches_a_dict_reference(vocab_kind, ids_kind):
    """Over a seeded stream with keys dropped and their ids given out
    again: the touched ids, the table and the names are the
    reference's after every delivery, and only the sparse deliveries
    are sorted."""
    rng = np.random.RandomState(len(vocab_kind) * 10 + len(ids_kind))
    size = 60_000
    names = np.array([f"n{i % 45_000}" for i in range(size)])  # some named twice
    vocab = _Vocab(vocab_kind, names)
    vm, ids_side = VocabMap(dtype=np.int64), _Ids()
    ref = {}  # external id -> internal id
    before = dict(flight.RECORDER.counters)
    head = 0
    for step in range(40):
        head = min(size, head + rng.randint(500, 2500))
        ext = _delivery(rng, ids_kind, head, rows=4000)
        got = vm.sync(ext, vocab.upto(head), ids_side.alloc_many)
        want = np.unique(ext)
        for e in want.tolist():
            if e not in ref:
                ref[e] = ids_side.alloc_many([str(names[e])])[0]
        assert got.tolist() == want.tolist()
        assert len(vm.table) == len(vocab.upto(head))
        table = np.full(len(vm.table), -1)
        table[list(ref)] = list(ref.values())
        assert vm.table.tolist() == table.tolist()
        assert vm.vocab[want].tolist() == names[want].tolist()
        if step % 3 == 2 and ids_side.of:
            gone = rng.choice(sorted(ids_side.of), size=min(40, len(ids_side.of)), replace=False)
            dropped = ids_side.release(gone.tolist())
            n = vm.drop_ids(dropped)
            for e in [e for e, i in ref.items() if i in set(dropped)]:
                del ref[e]
            assert n == len(table) - len(ref) - int((table < 0).sum())
    sorted_ = _gained(before, "vocab_sorted")
    assert (sorted_ > 0) == (ids_kind == "sparse")
    assert _gained(before, "vocab_rows") > 0


@pytest.mark.parametrize("vocab_kind", ["ndarray", "list"])
@pytest.mark.parametrize("change", ["shrink", "rewritten_prefix", "in_place_rewrite"])
def test_a_vocabulary_that_is_not_append_only_raises(vocab_kind, change):
    vm = VocabMap()
    first = [f"k{i}" for i in range(100)]
    vocab = np.array(first) if vocab_kind == "ndarray" else list(first)
    vm.sync(np.arange(100), vocab, lambda keys: list(range(len(keys))))
    if change == "shrink":
        # Shorter, and not a prefix of the vocabulary held.
        bad = vocab[50:]
    elif change == "rewritten_prefix":
        bad = vocab.copy() if vocab_kind == "ndarray" else list(vocab)
        bad[0] = "other"
    else:
        bad = vocab
        bad[0] = "other"
    with pytest.raises(TypeError, match="key_vocab"):
        vm.sync(np.arange(3), bad, lambda keys: list(range(len(keys))))


@pytest.mark.parametrize("vocab_kind", ["ndarray", "list"])
def test_an_older_view_of_the_vocabulary_reads_through_the_one_held(vocab_kind):
    """A merge can deliver one stream's earlier batch, with a prefix of
    the vocabulary, after another's later one: its ids map as the held
    vocabulary maps them, the held vocabulary stays, and an id past the
    older view's end raises."""
    names = [f"k{i}" for i in range(100)]
    ids = _Ids()
    vm = VocabMap()
    full = np.array(names) if vocab_kind == "ndarray" else list(names)
    older = full[:40]
    vm.sync(np.array([5, 70]), full, ids.alloc_many)
    assert vm.sync(np.array([5, 30]), older, ids.alloc_many).tolist() == [5, 30]
    assert vm.table[[5, 30, 70]].tolist() == [ids.of["k5"], ids.of["k30"], ids.of["k70"]]
    assert len(vm.table) == 100
    # The longer vocabulary is still the one held: no revalidation.
    vm.sync(np.array([99]), full, ids.alloc_many)
    assert vm.table[99] == ids.of["k99"]
    with pytest.raises(TypeError, match="out of range for a 40-entry key_vocab"):
        vm.sync(np.array([40]), older, ids.alloc_many)


def _isin_drop(table, ids):
    """What ``drop_ids`` did before it kept a reverse index."""
    mask = np.isin(table, np.asarray(ids, dtype=table.dtype))
    table[mask] = -1
    return int(mask.sum())


def _check_drop(vm, ids):
    want = vm.table.copy()
    n = _isin_drop(want, ids)
    assert vm.drop_ids(ids) == n
    assert vm.table.tolist() == want.tolist()
    return n


def test_drop_forgets_a_key_named_twice():
    """One key under two external ids (in one delivery and across two):
    one internal id, and a drop forgets every entry, as ``np.isin``
    does."""
    vm, ids_side = VocabMap(), _Ids()
    vocab = np.array(["a", "b", "a", "c", "a"])
    vm.sync(np.array([0, 1, 2]), vocab, ids_side.alloc_many)
    vm.sync(np.array([4, 3]), vocab, ids_side.alloc_many)
    a = ids_side.of["a"]
    assert vm.table[[0, 2, 4]].tolist() == [a] * 3
    assert _check_drop(vm, ids_side.release(["a"])) == 3
    assert _check_drop(vm, [a]) == 0


def test_drop_leaves_the_new_owner_of_a_freed_id():
    """An id freed by a drop and given to another key: a drop of the old
    owner's other ids leaves it, the old owner comes back under a new
    id, and a drop of the reused id forgets the new owner alone."""
    vm, ids_side = VocabMap(), _Ids()
    vocab = np.array(["a", "b", "c", "d"])
    vm.sync(np.array([0, 1]), vocab, ids_side.alloc_many)
    a = ids_side.of["a"]
    _check_drop(vm, ids_side.release(["a"]))
    vm.sync(np.array([2]), vocab, ids_side.alloc_many)  # c takes a's id
    assert ids_side.of["c"] == a and vm.table[2] == a
    vm.sync(np.array([0, 3]), vocab, ids_side.alloc_many)  # a is back
    assert vm.table[0] not in (-1, a)
    assert _check_drop(vm, ids_side.release(["c"])) == 1
    assert vm.table[0] == ids_side.of["a"] and vm.table[2] == -1


def test_drop_of_an_id_given_out_again_without_a_drop():
    """A caller that gives an id to a second key without dropping the
    first: both entries map to it, and a drop forgets both."""
    vm = VocabMap()
    vocab = np.array(["a", "b"])
    vm.sync(np.array([0]), vocab, lambda keys: [7])
    vm.sync(np.array([1]), vocab, lambda keys: [7])
    assert _check_drop(vm, [7, 9, 7]) == 2


@pytest.mark.parametrize("seed", [1, 2, 3, 4])
def test_drop_matches_isin_over_seeded_streams(seed):
    """Random deliveries over names that repeat, random drops of held
    and unheld ids: every drop forgets what ``np.isin`` would."""
    rng = np.random.RandomState(seed)
    names = np.array([f"k{i}" for i in rng.randint(0, 300, size=2000)])
    vm, ids_side = VocabMap(), _Ids()
    head = 0
    for _ in range(60):
        head = min(len(names), head + rng.randint(10, 80))
        vm.sync(rng.randint(0, head, size=50), names[:head], ids_side.alloc_many)
        held = sorted(ids_side.of)
        if held:
            gone = rng.choice(held, size=rng.randint(1, min(10, len(held)) + 1), replace=False)
            dropped = ids_side.release(gone.tolist()) + [10_000]
            _check_drop(vm, dropped)


@pytest.mark.parametrize("vocab_kind", ["view", "list"])
def test_table_is_the_vocabulary_long_and_grows_by_doubling(vocab_kind):
    size = 20_000
    vocab = _Vocab(vocab_kind, np.array([f"k{i}" for i in range(size)]))
    vm, ids_side = VocabMap(), _Ids()
    before = dict(flight.RECORDER.counters)
    for head in range(7, size + 1, 7):
        vm.sync(np.arange(head - 7, head), vocab.upto(head), ids_side.alloc_many)
        assert len(vm.table) == head
    # The table and the reverse index, each doubling from 7 entries.
    assert _gained(before, "vocab_grows") <= 2 * (np.log2(size / 7) + 2)


def test_walk_stays_with_the_rows_and_the_keys_born():
    """200 deliveries of a stream whose vocabulary reaches 10^6, each
    on a few thousand ids near the head: what the syncs walk beyond
    their rows is a small multiple of the rows and the keys born, not
    the vocabulary's length a delivery."""
    size, deliveries, rows = 1_000_000, 200, 5000
    names = np.arange(size).astype("U7")
    vocab = _Vocab("view", names)
    vm, ids_side = VocabMap(dtype=np.int64), _Ids()
    before = dict(flight.RECORDER.counters)
    for d in range(1, deliveries + 1):
        head = d * (size // deliveries)
        ext = np.random.RandomState(d).randint(head - 3200, head, size=rows)
        vm.sync(ext, vocab.upto(head), ids_side.alloc_many)
    born = ids_side.next
    walked = _gained(before, "vocab_walked")
    assert _gained(before, "vocab_rows") == deliveries * rows
    assert walked <= 4 * (deliveries * rows + born)
    # A walk over the whole table each delivery: 10^8 entries.
    assert 20 * walked < sum(d * (size // deliveries) for d in range(deliveries))


# -- the window and session tiers --------------------------------------------


def _tier_batches(seed):
    """Dictionary-encoded deliveries whose keys are born, go and come
    back: a delivery touches more keys than the one before (so the key
    columns double while freed ids are reused), each key's rows of a
    delivery at one instant, 1,000 s of event time after its last, so
    that no stall of the wall clock makes one late."""
    rng = np.random.RandomState(seed)
    pool = 600
    names = np.array([f"k{i}" for i in range(pool)])
    vocab = _Vocab("view", names)
    seen = np.zeros(pool, dtype=np.int64)
    batches = []
    for d in range(90):
        size = min(3 + 2 * d, 160)
        head = min(pool, 3 * size)
        keys = rng.choice(head, size=size, replace=False)
        seen[keys] += 1
        reps = rng.randint(1, 4, size=size)
        ext = np.repeat(keys, reps)
        secs = np.repeat(seen[keys], reps) * 1000
        ts = np.datetime64(ALIGN.replace(tzinfo=None), "us") + (secs * _US).astype(
            "timedelta64[us]"
        )
        order = rng.permutation(len(ext))
        batches.append(
            ArrayBatch(
                {"key_id": ext[order].astype(np.int32), "ts": ts[order]},
                key_vocab=vocab.upto(head),
            )
        )
    return batches


def _run_tier(windower, batches):
    from tests.test_xla import ArraySource

    clock = w.EventClock(ts_getter=xla.column_ts, wait_for_system_duration=timedelta(0))
    if windower == "session":
        windower_obj = w.SessionWindower(gap=timedelta(milliseconds=1))
    else:
        windower_obj = w.TumblingWindower(length=timedelta(milliseconds=1), align_to=ALIGN)
    flow = Dataflow("test_df")
    s = op.input("inp", flow, ArraySource(batches))
    counted = w.count_window("count", s, clock, windower_obj, key=lambda row: row[0])
    out, late = [], []
    op.output("out", counted.down, TestingSink(out))
    op.output("late", counted.late, TestingSink(late))
    run_main(flow)
    return sorted(out), late


@pytest.mark.parametrize("shard", ["0", "auto"], ids=["one_device", "mesh"])
@pytest.mark.parametrize("windower", ["tumbling", "session"])
def test_tiers_agree_while_keys_go_and_come_back(monkeypatch, windower, shard):
    """The device tier, whose key columns and vocabulary table grow by
    doubling while keys are let go and take freed ids, writes the host
    tier's windows (counts, and session ids that go on where a key
    stopped)."""
    monkeypatch.setenv("BYTEWAX_TPU_SHARD", shard)
    batches = _tier_batches(seed=5)
    rows = sum(len(b) for b in batches)
    monkeypatch.setenv("BYTEWAX_TPU_ACCEL", "1")
    before = dict(flight.RECORDER.counters)
    device, device_late = _run_tier(windower, batches)
    assert _gained(before, "vocab_rows") == rows
    assert _gained(before, "vocab_grows") >= 6
    retired = _gained(before, "window_keys_retired")
    assert retired > 0
    assert _gained(before, "window_keys_opened") > len(np.unique(np.concatenate(
        [b.cols["key_id"] for b in batches]
    )))
    monkeypatch.setenv("BYTEWAX_TPU_ACCEL", "0")
    host, host_late = _run_tier(windower, batches)
    assert not device_late and not host_late
    assert device == host
    assert sum(count for _key, (_wid, count) in device) == rows
