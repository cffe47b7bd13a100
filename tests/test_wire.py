"""Columnar wire codec + route accumulator (engine/wire.py;
docs/performance.md "Columnar exchange").

The fast single-process half of the exchange tier-1 coverage: codec
round trips for every column dtype the ingest tier produces, the
pickle fallbacks, the typed unknown-version error, and the
accumulator's merge/flush protocol.  The 2-proc exchange itself is
pinned in tests/test_cluster.py (frame counts, oracle equality,
crash/replay) and soaked in tests/test_chaos.py.
"""

from datetime import timedelta

import numpy as np
import pytest

from bytewax_tpu.engine import wire
from bytewax_tpu.engine.arrays import ArrayBatch
from bytewax_tpu.errors import WireFormatError

ZERO_TD = timedelta(seconds=0)


@pytest.fixture(autouse=True)
def _fresh_wire_mode(monkeypatch):
    """Each test reads BYTEWAX_TPU_WIRE from its own env."""
    monkeypatch.delenv("BYTEWAX_TPU_WIRE", raising=False)
    wire.reconfigure()
    yield
    wire.reconfigure()


def _batches_equal(a: ArrayBatch, b: ArrayBatch) -> None:
    assert set(a.cols) == set(b.cols)
    for name in a.cols:
        x, y = np.asarray(a.cols[name]), np.asarray(b.cols[name])
        assert x.dtype == y.dtype, name
        assert np.array_equal(x, y), name
    if a.key_vocab is None:
        assert b.key_vocab is None
    elif isinstance(a.key_vocab, np.ndarray):
        assert np.array_equal(
            np.asarray(a.key_vocab), np.asarray(b.key_vocab)
        )
        assert np.asarray(a.key_vocab).dtype == np.asarray(b.key_vocab).dtype
    else:
        assert b.key_vocab == a.key_vocab
    assert a.value_scale == b.value_scale


def _roundtrip(msg):
    data = wire.encode(msg)
    return data, wire.decode(data)


# -- codec round trips: every ingest-tier column dtype ------------------


@pytest.mark.parametrize(
    "col",
    [
        np.arange(64, dtype=np.int64),
        np.arange(64, dtype=np.int32),
        np.arange(64, dtype=np.uint16),
        np.linspace(0.0, 1.0, 64, dtype=np.float64),
        np.linspace(0.0, 1.0, 64, dtype=np.float32),
        np.arange(64, dtype=np.int16),  # fixed-point deci-values
        (np.arange(64) % 2).astype(bool),
        # event time both ways the ingest tier produces it:
        # datetime64[us] and numeric microseconds-since-epoch
        np.datetime64("2022-01-01", "us")
        + np.arange(64).astype("timedelta64[s]"),
        (1_640_995_200_000_000 + np.arange(64) * 1_000_000).astype(
            np.int64
        ),
        (1_640_995_200_000_000 + np.arange(64) * 1_000_000).astype(
            np.float64
        ),
        np.timedelta64(1, "ms") * np.arange(64),
    ],
    ids=[
        "i8",
        "i4",
        "u2",
        "f8",
        "f4",
        "i2",
        "bool",
        "dt64us",
        "ts-us-int",
        "ts-us-float",
        "td64",
    ],
)
def test_roundtrip_every_ingest_dtype(col):
    batch = ArrayBatch(
        {"key_id": np.arange(64, dtype=np.int32), "value": col}
    )
    data, out = _roundtrip(("route", "flow.s", (3, batch)))
    assert data[:1] != b"\x80"  # really the columnar framing
    kind, sid, (w, got) = out
    assert (kind, sid, w) == ("route", "flow.s", 3)
    _batches_equal(batch, got)


def test_roundtrip_bytes_columns_with_trailing_nuls():
    # The PR 8 Kafka-fallback class of bug: S cells whose raw bytes
    # end in NULs (and whose width exceeds the used bytes) must ship
    # buffer-exact — the decoded array compares equal cell for cell,
    # width preserved.
    keys = np.array([b"a\x00b", b"\x00", b"c", b""], dtype="S5")
    vals = np.array([b"x\x00\x00", b"yy", b"\x00z", b"w"], dtype="S3")
    batch = ArrayBatch({"key": keys, "value": vals})
    _data, out = _roundtrip(("deliver", 2, "up", (1, batch)))
    kind, op_idx, port, (w, got) = out
    assert (kind, op_idx, port, w) == ("deliver", 2, "up", 1)
    _batches_equal(batch, got)
    # Buffer-exact: the fixed width survives, not just the values.
    assert got.cols["key"].dtype == np.dtype("S5")
    assert got.cols["key"].tobytes() == keys.tobytes()


def test_roundtrip_unicode_keys_vocab_and_scale():
    vocab = np.array(["alpha", "beta", "gamma"])
    batch = ArrayBatch(
        {
            "key_id": np.array([0, 2, 1, 0], dtype=np.int32),
            "ts": np.datetime64("2024-06-01", "us")
            + np.arange(4).astype("timedelta64[ms]"),
            "value": np.array([10, 20, 30, 40], dtype=np.int16),
        },
        key_vocab=vocab,
        value_scale=0.1,
    )
    _data, out = _roundtrip(("deliver", 5, "up", (7, batch)))
    _batches_equal(batch, out[3][1])
    # to_pylist parity: consumers see exactly what the sender's batch
    # would have produced locally.
    assert out[3][1].to_pylist() == batch.to_pylist()


def test_decode_is_zero_copy_for_raw_columns():
    batch = ArrayBatch({"value": np.arange(1024, dtype=np.float64)})
    data = wire.encode(("route", "s", (0, batch)))
    got = wire.decode(data)[2][1].cols["value"]
    # A view over the received frame: read-only, no copy.
    assert got.flags.writeable is False
    assert got.base is not None


def test_object_columns_fall_back_per_column():
    payloads = np.array([{"a": 1}, {"b": 2}], dtype=object)
    batch = ArrayBatch(
        {"key": np.array(["x", "y"]), "value": payloads}
    )
    data, out = _roundtrip(("route", "s", (1, batch)))
    assert data[:4] == b"\xb5BXW"  # still a columnar frame
    got = out[2][1]
    assert np.array_equal(
        np.asarray(got.cols["key"]), np.asarray(batch.cols["key"])
    )
    assert got.cols["value"].dtype == object
    assert list(got.cols["value"]) == [{"a": 1}, {"b": 2}]


def test_list_vocab_and_nonbatch_payloads_fall_back():
    # List vocab: pickled inside the columnar frame.
    batch = ArrayBatch(
        {"key_id": np.array([0, 1], dtype=np.int32)},
        key_vocab=["k0", "k1"],
    )
    _data, out = _roundtrip(("route", "s", (0, batch)))
    assert out[2][1].key_vocab == ["k0", "k1"]
    # Non-batch payloads: whole-frame pickle, byte-compatible with
    # the legacy encoding.
    for msg in (
        ("gsync", 3, 1, {"stop": False}),
        ("route", "s", (1, [("k", 1.0), ("k2", 2.0)])),
        ("close_epoch", 9, False),
        ("__bytewax_tpu_hb__",),
    ):
        data = wire.encode(msg)
        assert data[:1] == b"\x80"  # a pickle
        assert wire.decode(data) == msg


def test_pickle_mode_disables_columnar(monkeypatch):
    monkeypatch.setenv("BYTEWAX_TPU_WIRE", "pickle")
    wire.reconfigure()
    assert wire.wire_mode() == "pickle"
    batch = ArrayBatch({"value": np.arange(8.0)})
    data = wire.encode(("route", "s", (0, batch)))
    assert data[:1] == b"\x80"
    got = wire.decode(data)[2][1]
    assert np.array_equal(got.cols["value"], batch.cols["value"])


def test_unknown_version_raises_typed():
    batch = ArrayBatch({"value": np.arange(4.0)})
    data = bytearray(wire.encode(("route", "s", (0, batch))))
    assert data[:4] == b"\xb5BXW"
    data[4] = 99
    with pytest.raises(WireFormatError, match="version 99"):
        wire.decode(bytes(data))


def test_truncated_frame_raises_typed():
    batch = ArrayBatch({"value": np.arange(64.0)})
    data = wire.encode(("route", "s", (0, batch)))
    with pytest.raises(WireFormatError, match="truncated"):
        wire.decode(data[: len(data) - 16])


def test_property_random_numeric_roundtrips():
    # Seeded property sweep over shapes/dtypes/scales/vocab layouts.
    rng = np.random.RandomState(7)
    dtypes = [np.int64, np.int32, np.float64, np.float32, np.uint8]
    for trial in range(25):
        n = int(rng.randint(1, 200))
        cols = {
            "key_id": rng.randint(0, 16, size=n).astype(np.int32),
            "value": rng.randint(0, 1000, size=n).astype(
                dtypes[trial % len(dtypes)]
            ),
        }
        if trial % 2:
            cols["ts"] = np.datetime64("2023-01-01", "us") + rng.randint(
                0, 10**9, size=n
            ).astype("timedelta64[us]")
        vocab = None
        if trial % 3 == 0:
            vocab = np.array(
                [f"key-{i}" for i in range(16)], dtype="S8"
            )
        batch = ArrayBatch(
            cols,
            key_vocab=vocab,
            value_scale=0.5 if trial % 5 == 0 else None,
        )
        _data, out = _roundtrip(("route", f"s{trial}", (trial, batch)))
        assert out[1] == f"s{trial}" and out[2][0] == trial
        _batches_equal(batch, out[2][1])


def test_strided_view_columns_encode_contiguous():
    # The redistribute op ships strided per-lane column views; the
    # codec must compact them, not serialize stride garbage.
    base = np.arange(100, dtype=np.float64)
    batch = ArrayBatch({"value": base[1::3]})
    _data, out = _roundtrip(("route", "s", (0, batch)))
    assert np.array_equal(out[2][1].cols["value"], base[1::3])


# -- the route accumulator ---------------------------------------------


def _vb(keys, vals, vocab=None, scale=None):
    return ArrayBatch(
        {
            "key_id": np.asarray(keys, dtype=np.int32),
            "value": np.asarray(vals, dtype=np.float64),
        },
        key_vocab=vocab,
        value_scale=scale,
    )


def test_accumulator_merges_compatible_runs():
    acc = wire.RouteAccumulator()
    vocab = np.array(["a", "b"])
    acc.add(1, "s", 4, _vb([0], [1.0], vocab))
    acc.add(1, "s", 4, _vb([1], [2.0], vocab))
    acc.add(1, "s", 4, _vb([0], [3.0], vocab))
    key, items = acc.peek()
    assert key == ("route", 1, "s", 4)
    assert len(items) == 3  # one frame for the whole run
    assert np.array_equal(items.cols["value"], [1.0, 2.0, 3.0])
    acc.pop()
    assert not acc.pending()


def test_accumulator_keeps_incompatible_slices_apart():
    acc = wire.RouteAccumulator()
    acc.add(1, "s", 4, _vb([0], [1.0]))
    acc.add(1, "s", 4, _vb([0], [2.0], scale=0.1))  # scale differs
    acc.add(1, "s", 5, _vb([0], [3.0]))  # different lane
    acc.add(2, "s", 4, _vb([0], [4.0]))  # different peer
    frames = []
    while acc.pending():
        frames.append(acc.peek())
        acc.pop()
    assert [(f[0][1], f[0][3]) for f in frames] == [
        (1, 4),
        (1, 4),
        (1, 5),
        (2, 4),
    ]
    assert frames[0][1].value_scale is None
    assert frames[1][1].value_scale == 0.1


def test_accumulator_merges_item_lists_too():
    acc = wire.RouteAccumulator()
    acc.add(0, "s", 1, [("k", 1)])
    acc.add(0, "s", 1, [("k", 2), ("j", 3)])
    assert acc.peek()[1] == [("k", 1), ("k", 2), ("j", 3)]
    acc.pop()
    assert acc.peek() is None


def test_accumulator_peek_is_stable_until_pop():
    # The flush protocol: peek -> send (may raise) -> pop.  A raise
    # between peek and pop must leave the run pending and peek must
    # keep returning it.
    acc = wire.RouteAccumulator()
    acc.add(1, "s", 4, _vb([0], [1.0]))
    first = acc.peek()
    assert acc.peek() is first  # cached, no re-merge
    assert acc.pending()
    acc.pop()
    assert not acc.pending() and acc.peek() is None


def test_accumulator_add_after_peek_invalidates_head():
    acc = wire.RouteAccumulator()
    acc.add(1, "s", 4, _vb([0], [1.0]))
    assert len(acc.peek()[1]) == 1
    acc.add(1, "s", 4, _vb([1], [2.0]))
    assert len(acc.peek()[1]) == 2  # re-merged, nothing stranded


def test_accumulator_deliver_buckets_coalesce_apart_from_route():
    """The deliver leg (keyed split slices): same-(peer, op, port,
    lane) slices coalesce into one frame, bucketed apart from route
    slices and from other ports/ops, in global first-seen order."""
    acc = wire.RouteAccumulator()
    acc.add_deliver(1, 7, "up", 3, _vb([0], [1.0]))
    acc.add(1, "s", 3, _vb([0], [2.0]))
    acc.add_deliver(1, 7, "up", 3, _vb([1], [3.0]))
    acc.add_deliver(1, 8, "up", 3, _vb([1], [4.0]))  # other op
    frames = []
    while acc.pending():
        frames.append(acc.peek())
        acc.pop()
    assert [f[0] for f in frames] == [
        ("deliver", 1, 7, "up", 3),
        ("route", 1, "s", 3),
        ("deliver", 1, 8, "up", 3),
    ]
    assert np.array_equal(frames[0][1].cols["value"], [1.0, 3.0])


# -- the vocab/schema session cache -------------------------------------


def test_vocab_session_ships_once_then_refs():
    """An unchanged key_vocab for one (peer, stream) ships its body
    once; subsequent frames carry only the generation tag and decode
    against the receiver's cache — and the ref frames are materially
    smaller than defining frames."""
    tx, rx = wire.WireSession(), wire.WireSession()
    vocab = np.array([f"key-{i:04d}" for i in range(512)])
    b1 = _vb([0, 1], [1.0, 2.0], vocab)
    b2 = _vb([2, 3], [3.0, 4.0], vocab)
    d1 = wire.encode(("route", "s", (1, b1)), tx, 9)
    d2 = wire.encode(("route", "s", (1, b2)), tx, 9)
    assert len(d2) < len(d1) - len(vocab.tobytes()) // 2
    got1 = wire.decode(d1, rx, 9)[2][1]
    got2 = wire.decode(d2, rx, 9)[2][1]
    assert np.array_equal(np.asarray(got1.key_vocab), vocab)
    assert np.array_equal(np.asarray(got2.key_vocab), vocab)
    assert got2.key_vocab is got1.key_vocab  # resolved from cache


def test_vocab_session_invalidates_on_growth_and_scopes_streams():
    """A vocab grown in place (same object, longer) re-defines under
    a fresh generation; a different stream never shares an entry."""
    tx, rx = wire.WireSession(), wire.WireSession()
    vocab = ["a", "b"]
    d1 = wire.encode(("route", "s", (0, _vb([0], [1.0], vocab))), tx, 3)
    vocab.append("c")  # append-only in-place growth
    d2 = wire.encode(("route", "s", (0, _vb([2], [2.0], vocab))), tx, 3)
    assert wire.decode(d1, rx, 3)[2][1].key_vocab == ["a", "b"]
    assert wire.decode(d2, rx, 3)[2][1].key_vocab == ["a", "b", "c"]
    # Same vocab on ANOTHER stream: defines there too (scoped cache).
    d3 = wire.encode(("route", "t", (0, _vb([0], [3.0], vocab))), tx, 3)
    assert wire.decode(d3, rx, 3)[2][1].key_vocab == ["a", "b", "c"]


def test_vocab_ref_without_defining_frame_raises_typed():
    """A ref whose defining frame the receiver never saw (fresh
    session — a restarted generation) fails typed, never resolves
    against stale state."""
    tx = wire.WireSession()
    vocab = np.array(["a", "b"])
    wire.encode(("route", "s", (0, _vb([0], [1.0], vocab))), tx, 1)
    ref = wire.encode(("route", "s", (1, _vb([1], [2.0], vocab))), tx, 1)
    with pytest.raises(WireFormatError, match="generation"):
        wire.decode(ref, wire.WireSession(), 1)
    with pytest.raises(WireFormatError, match="session"):
        wire.decode(ref)  # no session at all


def test_vocab_session_not_armed_without_session():
    """Sessionless encode (tests, tools) always ships the full vocab
    — byte-stable behavior for callers outside the comm layer."""
    vocab = np.array(["a", "b"])
    d1 = wire.encode(("route", "s", (0, _vb([0], [1.0], vocab))))
    d2 = wire.encode(("route", "s", (1, _vb([1], [2.0], vocab))))
    assert abs(len(d1) - len(d2)) <= 8  # both carry the body
    assert wire.decode(d2)[2][1].key_vocab is not None


# -- the quantized gsync aggregate codec --------------------------------


def _partial_cols(n=2000, seed=11):
    rng = np.random.RandomState(seed)
    return {
        "key": np.array([f"k{i:05d}" for i in range(n)]),
        "min": rng.randn(n) * 100.0,
        "max": rng.randn(n) * 100.0 + 500.0,
        "sum": rng.randn(n) * 1e4,
        "count": rng.randint(1, 1000, size=n).astype(np.int64),
    }


@pytest.mark.parametrize("quant", ["off", "bf16", "int8"])
def test_agg_codec_roundtrip_bounds(quant):
    """The quantized aggregate codec's accuracy contract
    (docs/performance.md "Overlapped collectives"): float columns
    round-trip within the documented bound — int8 within half a
    quantization step of the block max, bf16 within 2**-8 relative —
    and exact columns (key strings, counts) are byte-exact under
    EVERY mode."""
    cols = _partial_cols()
    frames = wire.encode_agg(cols, quant)
    dec = {}
    for frame in frames:
        for name, arr in wire.decode_agg(frame).items():
            dec.setdefault(name, []).append(arr)
    dec = {k: np.concatenate(v) for k, v in dec.items()}
    assert np.array_equal(dec["key"], cols["key"])
    # Counts are exact by VALUE under every mode (the codec may
    # narrow the integer width losslessly).
    assert dec["count"].dtype.kind == "i"
    assert np.array_equal(dec["count"], cols["count"])  # exact, always
    for name in ("min", "max", "sum"):
        orig, got = cols[name], dec[name]
        if quant == "off":
            assert np.array_equal(got, orig)
        elif quant == "int8":
            # Per 1024-value block: |err| <= max|block| / 254.
            nb = -(-len(orig) // 1024)
            padded = np.zeros(nb * 1024)
            padded[: len(orig)] = orig
            bound = np.repeat(
                np.abs(padded.reshape(nb, 1024)).max(axis=1) / 254.0,
                1024,
            )[: len(orig)]
            assert np.all(np.abs(got - orig) <= bound + 1e-9), name
        else:  # bf16
            denom = np.maximum(np.abs(orig), 1e-30)
            assert np.all(np.abs(got - orig) / denom <= 2.0**-8), name


def test_agg_codec_all_int_columns_exact_under_int8():
    """Integer partial columns (all-integer workloads) never
    quantize: int8 mode ships them byte-exact."""
    cols = {
        "key": np.array(["a", "b", "c"]),
        "sum": np.array([10**12, -(10**12), 7], dtype=np.int64),
        "count": np.array([3, 4, 5], dtype=np.int64),
    }
    (frame,) = wire.encode_agg(cols, "int8")
    dec = wire.decode_agg(frame)
    assert np.array_equal(dec["sum"], cols["sum"])
    assert np.array_equal(dec["count"], cols["count"])


def test_agg_codec_int8_shrinks_floats():
    """The bytes win of the quantized exchange: int8 frames for float-heavy
    partial columns are well under half the exact framing."""
    cols = _partial_cols(n=8192)
    exact = sum(len(f) for f in wire.encode_agg(cols, "off"))
    int8 = sum(len(f) for f in wire.encode_agg(cols, "int8"))
    bf16 = sum(len(f) for f in wire.encode_agg(cols, "bf16"))
    # The key/count columns ship exact in every mode; the three f64
    # columns shrink 8x (int8) / 4x (bf16).
    assert int8 <= 0.5 * exact
    assert bf16 < exact


def test_agg_codec_chunks_oversized_column_sets():
    n = (1 << 16) + 123  # one full chunk + a tail
    cols = {
        "key": np.array([f"k{i}" for i in range(n)]),
        "sum": np.arange(n, dtype=np.float64),
    }
    frames = wire.encode_agg(cols, "off")
    assert len(frames) == 2
    dec = np.concatenate(
        [wire.decode_agg(f)["sum"] for f in frames]
    )
    assert np.array_equal(dec, cols["sum"])


def test_agg_codec_unknown_version_raises_typed():
    (frame,) = wire.encode_agg({"sum": np.arange(4.0)}, "int8")
    bad = bytearray(frame)
    bad[4] = 99
    with pytest.raises(WireFormatError, match="version 99"):
        wire.decode_agg(bytes(bad))
    with pytest.raises(WireFormatError, match="aggregate"):
        wire.decode_agg(b"\x80nonsense")


def test_gsync_quant_knob_is_validated(monkeypatch):
    monkeypatch.setenv("BYTEWAX_TPU_GSYNC_QUANT", "int4")
    wire.reconfigure()
    with pytest.raises(ValueError, match="int4"):
        wire.gsync_quant()
    monkeypatch.setenv("BYTEWAX_TPU_GSYNC_QUANT", "bf16")
    wire.reconfigure()
    assert wire.gsync_quant() == "bf16"


# -- the driver's zero-row skip + in-process exchange parity ------------


def test_ship_route_skips_zero_row_entries():
    """A zero-row routed slice (empty list or 0-row batch) must not
    reach the accumulator or the wire; non-empty ones must."""
    from bytewax_tpu.engine.driver import _Driver

    class _Probe(_Driver):  # minimal: only what ship_route touches
        def __init__(self):
            self.wpp = 1
            self.local_lo = 0
            self.local_hi = 1
            self._ship_acc = wire.RouteAccumulator()
            self.sent = [0, 0]

    d = _Probe()
    d.ship_route("s", (1, []))
    d.ship_route(
        "s", (1, ArrayBatch({"value": np.empty(0, dtype=np.float64)}))
    )
    assert not d._ship_acc.pending()
    d.ship_route("s", (1, [("k", 1)]))
    assert d._ship_acc.pending()
    assert d.sent == [0, 0]  # counted only at ship_flush


def test_wire_status_shape():
    from bytewax_tpu.engine import flight

    wire.encode(("route", "s", (0, _vb([0], [1.0]))))
    st = flight.wire_status()
    assert set(st) == {"encode", "decode"}
    for op in st.values():
        assert set(op) == {"columnar", "pickle"}
        for c in op.values():
            assert set(c) == {"frames", "bytes", "seconds"}
    assert st["encode"]["columnar"]["frames"] >= 1


def test_cluster_entrypoints_exchange_equality(entry_point):
    """The wire-era exchange must be observationally identical across
    all 3 entry points (single lane, 1-lane cluster, 2-lane cluster)
    on a keyed columnar flow: per-key sums equal the host oracle."""
    import bytewax_tpu.operators as op
    from bytewax_tpu.dataflow import Dataflow
    from bytewax_tpu.inputs import DynamicSource, StatelessSourcePartition
    from bytewax_tpu.testing import TestingSink

    n, n_keys = 2000, 16
    rng = np.random.RandomState(3)
    key_ids = rng.randint(0, n_keys, size=n).astype(np.int32)
    vals = rng.rand(n)
    vocab = np.array([f"user-{i:03d}" for i in range(n_keys)])

    class _Part(StatelessSourcePartition):
        def __init__(self, worker_index):
            self._batches = (
                [
                    ArrayBatch(
                        {
                            "key_id": key_ids[i : i + 256],
                            "value": vals[i : i + 256],
                        },
                        key_vocab=vocab,
                    )
                    for i in range(0, n, 256)
                ]
                if worker_index == 0
                else []
            )

        def next_batch(self):
            if not self._batches:
                raise StopIteration()
            return self._batches.pop(0)

    class Src(DynamicSource):
        def build(self, step_id, worker_index, worker_count):
            return _Part(worker_index)

    out = []
    flow = Dataflow("wire_parity_df")
    s = op.input("inp", flow, Src())
    summed = op.reduce_final("sum", s, lambda a, b: a + b)
    op.output("out", summed, TestingSink(out))
    entry_point(flow, epoch_interval=ZERO_TD)

    oracle = {}
    for k, v in zip(key_ids, vals):
        key = f"user-{int(k):03d}"
        oracle[key] = oracle.get(key, 0.0) + float(v)
    got = dict(out)
    assert set(got) == set(oracle)
    for k in oracle:
        assert got[k] == pytest.approx(oracle[k])
