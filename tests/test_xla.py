"""XLA-tier tests: columnar batches, device aggregation, mesh
exchange.  Run on the virtual 8-device CPU mesh from conftest."""

import numpy as np
import pytest

import bytewax_tpu.operators as op
from bytewax_tpu import xla
from bytewax_tpu.dataflow import Dataflow
from bytewax_tpu.engine.arrays import ArrayBatch
from bytewax_tpu.inputs import DynamicSource, StatelessSourcePartition
from bytewax_tpu.testing import TestingSink, TestingSource, run_main


class _ArraySourcePartition(StatelessSourcePartition):
    def __init__(self, batches):
        self._batches = list(batches)

    def next_batch(self):
        if not self._batches:
            raise StopIteration()
        return self._batches.pop(0)


class ArraySource(DynamicSource):
    """Emit pre-built ArrayBatch columnar batches (worker 0 only)."""

    def __init__(self, batches):
        self._batches = batches

    def build(self, step_id, worker_index, worker_count):
        if worker_index == 0:
            return _ArraySourcePartition(self._batches)
        return _ArraySourcePartition([])


def test_array_batch_to_pylist_kv():
    ab = ArrayBatch({"key": np.array(["a", "b"]), "value": np.array([1, 2])})
    assert ab.to_pylist() == [("a", 1), ("b", 2)]
    assert len(ab) == 2


def test_columnar_reduce_final_sum():
    batches = [
        ArrayBatch(
            {
                "key": np.array(["a", "b", "a"]),
                "value": np.array([1.0, 10.0, 2.0]),
            }
        ),
        ArrayBatch(
            {"key": np.array(["b"]), "value": np.array([30.0])}
        ),
    ]
    out = []
    flow = Dataflow("test_df")
    s = op.input("inp", flow, ArraySource(batches))
    r = op.reduce_final("sum", s, xla.SUM)
    op.output("out", r, TestingSink(out))
    run_main(flow)
    assert sorted(out) == [("a", 3.0), ("b", 40.0)]


def test_columnar_jax_udf_map():
    batches = [
        ArrayBatch(
            {"key": np.array(["a", "a"]), "value": np.array([1.0, 2.0])}
        )
    ]
    out = []

    @xla.jit_batch
    def double(cols):
        # String columns (key) bypass the jitted fn and re-attach.
        return {"value": cols["value"] * 2}

    flow = Dataflow("test_df")
    s = op.input("inp", flow, ArraySource(batches))
    s = op.flat_map_batch("double", s, double)
    r = op.reduce_final("sum", s, xla.SUM)
    op.output("out", r, TestingSink(out))
    run_main(flow)
    assert out == [("a", 6.0)]


def test_jax_udf_rejects_python_items():
    @xla.jit_batch
    def ident(cols):
        return cols

    out = []
    flow = Dataflow("test_df")
    s = op.input("inp", flow, TestingSource([1, 2]))
    s = op.flat_map_batch("bad", s, ident)
    op.output("out", s, TestingSink(out))
    with pytest.raises(TypeError, match="ArrayBatch"):
        run_main(flow)


def test_accelerated_count_matches_host(monkeypatch):
    inp = ["apple", "banana", "apple", "banana", "banana"]

    def run(accel_env):
        monkeypatch.setenv("BYTEWAX_TPU_ACCEL", accel_env)
        out = []
        flow = Dataflow("test_df")
        s = op.input("inp", flow, TestingSource(inp))
        s = op.count_final("count", s, lambda x: x)
        op.output("out", s, TestingSink(out))
        run_main(flow)
        return sorted(out)

    assert run("1") == run("0") == [("apple", 2), ("banana", 3)]


def test_accelerated_min_max_fallback_non_numeric(monkeypatch):
    monkeypatch.setenv("BYTEWAX_TPU_ACCEL", "1")
    inp = [("k", "zebra"), ("k", "ant")]
    out = []
    flow = Dataflow("test_df")
    s = op.input("inp", flow, TestingSource(inp))
    s = op.min_final("min", s)
    op.output("out", s, TestingSink(out))
    run_main(flow)
    assert out == [("k", "ant")]


def test_stats_final():
    inp = [("k", 1.0), ("k", 2.0), ("k", 9.0), ("j", 5.0)]
    out = []
    flow = Dataflow("test_df")
    s = op.input("inp", flow, TestingSource(inp))
    s = xla.stats_final("stats", s)
    op.output("out", s, TestingSink(out))
    run_main(flow)
    assert sorted(out) == [
        ("j", (5.0, 5.0, 5.0, 1)),
        ("k", (1.0, 4.0, 9.0, 3)),
    ]


def test_accelerated_recovery_cross_tier(tmp_path, monkeypatch):
    # Crash mid-stream with the device tier, resume with the host
    # tier (and vice versa): snapshots are interchangeable.
    from bytewax_tpu.recovery import RecoveryConfig, init_db_dir
    from datetime import timedelta

    init_db_dir(tmp_path, 1)
    rc = RecoveryConfig(str(tmp_path))
    inp = [
        ("a", 5),
        ("a", 3),
        TestingSource.ABORT(),
        ("a", 40),
    ]
    out = []
    flow = Dataflow("test_df")
    s = op.input("inp", flow, TestingSource(inp))
    s = op.max_final("max", s)
    op.output("out", s, TestingSink(out))

    monkeypatch.setenv("BYTEWAX_TPU_ACCEL", "1")
    run_main(flow, epoch_interval=timedelta(0), recovery_config=rc)
    assert out == []

    monkeypatch.setenv("BYTEWAX_TPU_ACCEL", "0")
    run_main(flow, epoch_interval=timedelta(0), recovery_config=rc)
    assert out == [("a", 40)]


def test_keyed_all_to_all_mesh():
    import jax
    import jax.numpy as jnp

    from bytewax_tpu.parallel.exchange import keyed_all_to_all
    from bytewax_tpu.parallel.mesh import make_mesh

    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")
    mesh = make_mesh(8)
    n = 64  # 8 rows per device
    rng = np.random.RandomState(0)
    shard_ids = rng.randint(0, 8, size=n).astype(np.int32)
    values = np.arange(n, dtype=np.float32)
    valid = np.ones(n, dtype=bool)

    got, mask, dropped = keyed_all_to_all(
        mesh, 16, jnp.asarray(shard_ids), jnp.asarray(values), jnp.asarray(valid)
    )
    got = np.asarray(got)
    mask = np.asarray(mask)
    assert int(dropped) == 0
    # After exchange, device d's slice holds exactly the rows whose
    # shard_id == d.
    per_dev = got.reshape(8, -1)
    per_mask = mask.reshape(8, -1)
    for d in range(8):
        received = sorted(per_dev[d][per_mask[d]].tolist())
        expected = sorted(values[shard_ids == d].tolist())
        assert received == expected, f"device {d}"


def test_keyed_all_to_all_reports_drops():
    # An undersized bucket capacity must be detectable: the exchange
    # reports how many valid rows did not fit instead of silently
    # losing them.
    import jax
    import jax.numpy as jnp

    from bytewax_tpu.parallel.exchange import keyed_all_to_all
    from bytewax_tpu.parallel.mesh import make_mesh

    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")
    mesh = make_mesh(8)
    n = 64
    shard_ids = np.zeros(n, dtype=np.int32)  # every row to shard 0
    values = np.arange(n, dtype=np.float32)
    valid = np.ones(n, dtype=bool)
    got, mask, dropped = keyed_all_to_all(
        mesh, 4, jnp.asarray(shard_ids), jnp.asarray(values), jnp.asarray(valid)
    )
    # 8 rows per source device, capacity 4 -> 4 dropped per source.
    assert int(dropped) == 32
    assert int(np.asarray(mask).sum()) == 32


def test_int64_overflow_falls_back_to_host():
    big = 1 << 40
    inp = [("k", big), ("k", big)]
    out = []
    flow = Dataflow("test_df")
    s = op.input("inp", flow, TestingSource(inp))
    r = op.reduce_final("sum", s, xla.SUM)
    op.output("out", r, TestingSink(out))
    run_main(flow)
    assert out == [("k", 2 * big)]  # exact, via host fallback


def test_redistributed_columnar_batch_reaches_accel(monkeypatch):
    # Strided per-lane column views from a columnar redistribute must
    # still run the device-accelerated keyed fold (KeyEncoder compacts
    # non-contiguous key columns before its dtype view).
    monkeypatch.setenv("BYTEWAX_TPU_ACCEL", "1")
    import bytewax_tpu.operators as op
    from bytewax_tpu.dataflow import Dataflow
    from bytewax_tpu.engine.arrays import ArrayBatch
    from bytewax_tpu.xla import SUM

    keys = np.array([f"k{i % 3}" for i in range(300)])
    batch = ArrayBatch({"key": keys, "value": np.ones(300)})
    out = []
    flow = Dataflow("test_df")
    s = op.input("inp", flow, ArraySource([batch]))
    s = op.redistribute("shuffle", s)
    r = op.reduce_final("sum", s, SUM)
    op.output("out", r, TestingSink(out))
    from bytewax_tpu.testing import cluster_main

    cluster_main(flow, [], 0, worker_count_per_proc=2)
    assert sorted(out) == [("k0", 100.0), ("k1", 100.0), ("k2", 100.0)]


def test_key_encoder_empty_first_batch():
    # An empty delivery must not install its (arbitrary) dtype kind
    # as the encoder's seen-set; later real batches keep the
    # steady-state fast path.
    from bytewax_tpu.engine.arrays import KeyEncoder

    enc = KeyEncoder()
    assert len(enc.encode(np.array([], dtype=object), lambda ks: [])) == 0
    assert enc._sorted is None
    ids = enc.encode(np.array(["a", "b", "a"]), lambda ks: [10, 11])
    assert ids.tolist() == [10, 11, 10]
    assert enc._sorted is not None and enc._sorted.dtype.kind == "U"
    # Steady state: no allocs for seen keys.
    ids2 = enc.encode(np.array(["b", "a"]), lambda ks: 1 / 0)
    assert ids2.tolist() == [11, 10]


def test_key_encoder_wide_column_fast_path():
    """With few seen keys, an over-wide string column is searched
    as-is (no per-batch narrowing); prefix collisions and misses stay
    exact across widths."""
    from bytewax_tpu.engine.arrays import KeyEncoder

    enc = KeyEncoder()
    next_id = iter(range(100))
    alloc = lambda ks: [next(next_id) for _ in ks]  # noqa: E731

    ids = enc.encode(np.array(["a", "b"], dtype="U1"), alloc)
    assert ids.tolist() == [0, 1]
    assert enc._sorted.dtype.itemsize // 4 == 1  # stored narrow

    # Over-wide batch (U8): hits map to the same ids; "ab" must MISS
    # (no truncation against the narrow "a") and get a fresh id.
    wide = np.array(["b", "ab", "a"], dtype="U8")
    ids2 = enc.encode(wide, alloc)
    assert ids2.tolist() == [1, 2, 0]
    # The miss installed narrowed: the seen set stays at true width.
    assert enc._sorted.dtype.itemsize // 4 == 2
    # Steady state over wide columns: no allocs.
    ids3 = enc.encode(np.array(["ab", "a", "b"], dtype="U21"), lambda ks: 1 / 0)
    assert ids3.tolist() == [2, 0, 1]


def test_key_encoder_many_keys_still_narrow():
    """Above the wide-search threshold the narrowing path still runs
    (deep searches at full width would be slower) and stays exact."""
    from bytewax_tpu.engine.arrays import KeyEncoder

    enc = KeyEncoder()
    keys = np.array([f"k{i}" for i in range(40)])
    ids = enc.encode(keys, lambda ks: list(range(len(ks))))
    wide = keys.astype("U30")
    ids2 = enc.encode(wide, lambda ks: 1 / 0)
    assert ids2.tolist() == ids.tolist()
