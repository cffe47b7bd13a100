"""The keyed slot table's one surface, driven through both of its
placements: ``DeviceAggState`` (one device) and ``ShardedAggState``
(conftest's mesh of 8).  The layout's arithmetic on one device (slot
numbers, capacities, padded reset lengths) is pinned against a
sequence recorded before the two classes were merged."""

import numpy as np
import pytest

from bytewax_tpu.engine import xla
from bytewax_tpu.engine.arrays import ArrayBatch
from bytewax_tpu.engine.xla import DeviceAggState, NonNumericValues


def _summary(ids):
    ids = np.asarray(ids).tolist()
    return ids if len(ids) <= 24 else [len(ids), ids[0], ids[-1], sum(ids)]


def _recorded_sequence(monkeypatch):
    """Opens, folds, releases, keyed allocs, a resume and an eviction
    on one one-device table: what each call handed out, the capacity
    after it, and the padded length of every reset."""
    resets = []
    real = xla.reset_fields

    def counted(kind, fields, slots):
        resets.append(len(slots))
        return real(kind, fields, slots)

    monkeypatch.setattr(xla, "reset_fields", counted)
    agg = DeviceAggState("count")
    log = []

    def note(tag, ids=()):
        log.append([tag, agg.capacity, _summary(ids)])

    a = agg.open_ids(np.empty(1000))
    note("open 1000", a)
    agg.update_ids(a, np.ones(len(a)))
    b = agg.open_ids(np.empty(22))
    note("open 22", b)
    note("alloc k0", [agg.alloc("k0")])
    agg.release_ids(a[10:25])
    note("alloc k1", [agg.alloc("k1")])
    c = agg.open_ids(np.empty(20))
    note("open 20", c)
    agg.update_ids(c, np.ones(len(c)))
    note("fold 20")
    agg.release_ids(c)
    agg.release_ids(b)
    agg.discard("k0")
    d = agg.open_ids(np.empty(1100))
    note("open 1100", d)
    note("states", agg.states_of(d[:3]))
    agg.load_many([("k2", 5), ("k0", 7)])
    note("load k2 k0", [agg.alloc("k2"), agg.alloc("k0")])
    note("extract k1", [s for _k, s in agg.extract_keys(["k1"])])
    note("alloc k3", [agg.alloc("k3")])
    touched = agg.update(np.array(["k3", "k4", "k3"]), np.ones(3))
    note("update " + " ".join(touched), [agg.alloc("k4")])
    e = agg.open_ids(np.empty(3000))
    note("open 3000", e)
    return log, resets, agg.finalize()


#: Recorded at the parent of the PR that merged the two classes
#: (commit e492823): a block of ``cap`` rows holds ``cap - 2`` slots
#: (the last row is scratch and one is spare), freed slots come back
#: newest first, a growth resets what is pending at the old size.
_RECORDED_LOG = [
    ["open 1000", 1024, [1000, 0, 999, 499500]],
    ["open 22", 1024, list(range(1000, 1022))],
    ["alloc k0", 2048, [1022]],
    ["alloc k1", 2048, [24]],
    ["open 20", 2048, list(range(23, 9, -1)) + list(range(1023, 1029))],
    ["fold 20", 2048, []],
    ["open 1100", 4096, [1100, 1022, 2085, 1675386]],
    ["states", 4096, [0, 0, 0]],
    ["load k2 k0", 4096, [2086, 2087]],
    ["extract k1", 4096, [0]],
    ["alloc k3", 4096, [24]],
    ["update k3 k4", 4096, [2088]],
    ["open 3000", 8192, [3000, 2089, 5088, 10765500]],
]
_RECORDED_RESETS = [16, 64, 8]
_RECORDED_FINAL = [("k0", 7), ("k2", 5), ("k3", 2), ("k4", 1)]


def test_one_device_layout_matches_the_recorded_sequence(monkeypatch):
    log, resets, final = _recorded_sequence(monkeypatch)
    assert log == _RECORDED_LOG
    assert resets == _RECORDED_RESETS
    assert final == _RECORDED_FINAL


# -- the one surface, over both placements -----------------------------------


@pytest.fixture(params=["one_device", "mesh_of_8"])
def make_state(request):
    """``make_state(kind)``: a ``DeviceAggState``, or a
    ``ShardedAggState`` over conftest's 8 virtual devices whose
    blocks start small enough that the cases below grow them."""
    if request.param == "one_device":
        return DeviceAggState
    import jax

    from bytewax_tpu.engine.sharded_state import ShardedAggState
    from bytewax_tpu.parallel.mesh import make_mesh

    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")
    mesh = make_mesh(8)
    return lambda kind: ShardedAggState(kind, mesh, cap_per_shard=8)


def test_growth_keeps_state(make_state):
    # Keys folded before a capacity growth must keep their state after.
    st = make_state("sum")
    before = st.capacity
    st.update(np.array(["early"]), np.array([5.0]))
    n = 5000  # past the initial capacity of either placement
    many = np.array([f"k{i:05d}" for i in range(n)])
    st.update(many, np.ones(n, dtype=np.float32))
    st.update(many, np.ones(n, dtype=np.float32))
    st.update(np.array(["early"]), np.array([7.0]))
    assert st.capacity > before
    out = dict(st.finalize())
    assert len(out) == n + 1
    assert out["early"] == 12.0
    assert out["k00000"] == 2.0 and out[f"k{n - 1:05d}"] == 2.0


def _station_batches(n_keys=50, seed=3):
    vocab = np.array([f"station{i}" for i in range(n_keys)])
    rng = np.random.RandomState(seed)
    rows = []
    for _ in range(4):
        ids = rng.randint(0, n_keys, size=500).astype(np.int32)
        temps = rng.randint(-400, 400, size=500).astype(np.int16)
        rows.append((ids, temps))
    return vocab, rows


def _assert_stats(out, vocab, rows, scale):
    groups = {}
    for ids, temps in rows:
        for i, t in zip(ids.tolist(), temps.tolist()):
            groups.setdefault(str(vocab[i]), []).append(t * scale)
    assert set(out) == set(groups)
    for k, g in groups.items():
        mn, mean, mx, cnt = out[k]
        assert cnt == len(g)
        np.testing.assert_allclose(mn, min(g), atol=1e-4)
        np.testing.assert_allclose(mx, max(g), atol=1e-4)
        np.testing.assert_allclose(mean, sum(g) / len(g), atol=1e-3)


def test_dict_encoded_batches(make_state):
    st = make_state("stats")
    vocab, rows = _station_batches()
    touched = set()
    for ids, temps in rows:
        # The vocabulary grows by appending: a longer array each time.
        vocab = np.append(vocab, f"unused{len(vocab)}")
        touched.update(
            st.update_batch(
                ArrayBatch(
                    {"key_id": ids, "value": temps},
                    key_vocab=vocab,
                    value_scale=0.1,
                )
            )
        )
    out = dict(st.finalize())
    assert touched == set(out)
    _assert_stats(out, vocab, rows, 0.1)


def test_fixed_point_values_past_the_int16_id_range(make_state):
    """A vocabulary too long for the packed int16 carrier still has
    its fixed-point values scaled (the one-device table folded them
    raw before the two classes were merged)."""
    st = make_state("stats")
    vocab, rows = _station_batches()
    vocab = np.append(vocab, [f"far{i}" for i in range(1 << 15)])
    far = np.array([len(vocab) - 1], dtype=np.int32)
    rows.append((far, np.array([123], dtype=np.int16)))
    for ids, temps in rows:
        st.update_batch(
            ArrayBatch(
                {"key_id": ids, "value": temps},
                key_vocab=vocab,
                value_scale=0.1,
            )
        )
    _assert_stats(dict(st.finalize()), vocab, rows, 0.1)


def test_vocab_must_be_append_only(make_state):
    st = make_state("sum")

    def batch(vocab):
        return ArrayBatch(
            {"key_id": np.array([0], np.int16), "value": np.array([1.0])},
            key_vocab=vocab,
        )

    st.update_batch(batch(np.array(["london", "paris"])))
    with pytest.raises(TypeError, match="append-only"):
        st.update_batch(batch(np.array(["paris", "london"])))
    assert st.finalize() == [("london", 1.0)]


def test_value_scale_string_key_path(make_state):
    ab = ArrayBatch(
        {"key": np.array(["a", "a"]), "value": np.array([15, 23], np.int16)},
        value_scale=0.1,
    )
    st = make_state("sum")
    assert st.update_batch(ab) == ["a"]
    results = dict(st.finalize())
    assert abs(results["a"] - 3.8) < 1e-5
    # to_pylist honors the scale too
    assert ab.to_pylist()[0] == ("a", 1.5)
    # A carrier wider than int16 is scaled as well.
    st = make_state("sum")
    st.update_batch(
        ArrayBatch(
            {"key_id": np.array([0, 0]), "value": np.array([150_000, 23])},
            key_vocab=np.array(["a"]),
            value_scale=0.1,
        )
    )
    assert abs(dict(st.finalize())["a"] - 15002.3) < 1e-2
    # A fixed-point batch after the state locked to integers is refused.
    st = make_state("sum")
    st.update(np.array(["a"]), np.array([1], dtype=np.int64))
    with pytest.raises(TypeError, match="float accumulator"):
        st.update_batch(
            ArrayBatch(
                {"key_id": np.array([0], np.int16), "value": np.array([1], np.int16)},
                key_vocab=np.array(["a"]),
                value_scale=0.1,
            )
        )


@pytest.mark.parametrize("door", ["update", "update_items", "update_ids"])
def test_int64_overflow_raises_with_no_state_mutated(make_state, door):
    big = 1 << 40
    st = make_state("sum")
    st.update(np.array(["k"]), np.array([3], dtype=np.int64))
    with pytest.raises(NonNumericValues, match="wider than 32 bits"):
        if door == "update":
            st.update(np.array(["k", "new"]), np.array([big, 1]))
        elif door == "update_items":
            st.update_items([("k", big), ("new", 1)])
        else:
            st.update_ids(np.array([st.alloc("k")]), np.array([big]))
    assert st.keys() == ["k"] and "new" not in st._iddict
    # The itemized door works afterwards (or is absent altogether).
    assert st.update_items([("k", 4), ("new", 1)]) in (["k", "new"], None)
    assert dict(st.finalize())["k"] in (3, 7)


@pytest.mark.parametrize("kind", ["sum", "count", "mean", "stats"])
def test_load_snapshot_finalize_round_trip(make_state, kind):
    """What ``snapshots_for`` gives is what ``load_many`` takes: a
    state resumed from another's snapshots folds on to the same
    finals, across a growth and whichever placement wrote them."""
    rng = np.random.RandomState(5)
    keys = np.array([f"k{i:04d}" for i in rng.randint(0, 1500, size=4000)])
    vals = rng.randint(-40, 40, size=len(keys)).astype(np.float64)
    first = make_state(kind)
    first.update(keys[:3000], vals[:3000])
    snaps = first.snapshots_for(sorted(set(keys[:3000])) + ["never_seen"])
    assert snaps[-1] == ("never_seen", None)
    resumed = make_state(kind)
    resumed.load_many(snaps[:-1])
    assert resumed.snapshots_for([k for k, _s in snaps]) == snaps
    resumed.load("loaded_alone", snaps[0][1])
    first.update(keys[3000:], vals[3000:])
    resumed.update(keys[3000:], vals[3000:])
    straight, again = dict(first.finalize()), dict(resumed.finalize())
    assert again.pop("loaded_alone") is not None
    assert again == straight
    assert first.keys() == resumed.keys() == []


def test_extract_inject_and_id_reuse(make_state):
    """An evicted key's id goes to the next key; the evicted key's
    vocabulary entry no longer routes rows there, and the key comes
    back beside it with its state."""
    st = make_state("sum")
    vocab = np.array(["a", "b", "c"])

    def fold(ids, values, vocab=vocab):
        return st.update_batch(
            ArrayBatch(
                {"key_id": np.array(ids, np.int16), "value": np.array(values)},
                key_vocab=vocab,
            )
        )

    fold([0, 1, 2], [1.0, 2.0, 3.0])
    id_a = st.alloc("a")
    items = st.extract_keys(["a", "missing"])
    assert items == [("a", 1.0)] and st.keys() == ["b", "c"]
    # A newcomer that "a"'s shard owns (any key does, on one device).
    d = next(
        k for k in map("d{}".format, range(99))
        if st._owner(k) == st._owner("a")
    )
    longer = np.append(vocab, d)
    fold([3], [40.0], longer)
    assert st.alloc(d) == id_a  # the freed id, reset to the identity
    fold([0, 3], [10.0, 4.0], longer)  # "a" returns: a new id, not d's
    assert st.alloc("a") != id_a
    st.inject_keys([("e", 5.0)])
    st.discard("b")
    assert st.snapshots_for(["b"]) == [("b", None)]
    assert st.demotion_snapshots() == [
        ("c", 3.0), (d, 44.0), ("a", 10.0), ("e", 5.0)
    ]


def test_a_finalized_state_starts_over(make_state):
    st = make_state("count")
    st.update(np.array(["a", "b", "a"]), np.ones(3))
    st.discard("b")
    assert st.finalize() == [("a", 2)]
    assert st.finalize() == []
    st.update(np.array(["c", "b"]), np.ones(2))
    assert st.finalize() == [("b", 1), ("c", 1)]
