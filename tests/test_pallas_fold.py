"""The fold's dense form (``ops/pallas_fold.py``; interpreted on the
CPU backend) through the public jitted entry points, against the
scatter and a numpy reference; which form a table takes, read from
the program's own counters; nothing here sets a variable to choose."""

import json
import socket
import urllib.request

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from bytewax_tpu.engine import flight
from bytewax_tpu.engine.xla import DeviceAggState
from bytewax_tpu.ops.pallas_fold import DENSE_MAX_SLOTS
from bytewax_tpu.ops.segment import (
    AGG_KINDS,
    fold_is_dense,
    init_fields,
    scatter_fields,
    update_fields,
    update_fields_packed,
    update_fields_vocab,
)

_scatter = jax.jit(scatter_fields, static_argnames=("kind",))


class _Gained:
    """What the fold's two counters gain from here."""

    def __init__(self):
        self.before = dict(flight.RECORDER.counters)

    def __call__(self, name):
        return flight.RECORDER.counters.get(name, 0) - self.before.get(name, 0)


def _assert_fields(kind, got, want, err_msg=""):
    for name in kind.fields:
        np.testing.assert_allclose(
            np.asarray(got[name]),
            np.asarray(want[name]),
            rtol=1e-5,
            atol=1e-5,
            err_msg=f"{err_msg}/{name}",
        )


@pytest.mark.parametrize("kind_name", ["sum", "count", "min", "max", "stats"])
def test_dense_matches_scatter(kind_name):
    kind = AGG_KINDS[kind_name]
    capacity = 128
    rng = np.random.RandomState(0)
    n = 1000
    padded = 1024
    slots = np.full(padded, capacity - 1, dtype=np.int32)
    slots[:n] = rng.randint(0, capacity - 1, size=n)
    vals = np.zeros(padded, dtype=np.float32)
    vals[:n] = rng.randn(n).astype(np.float32)

    assert fold_is_dense(init_fields(kind, capacity))
    ref = _scatter(
        kind, init_fields(kind, capacity), jnp.asarray(slots), jnp.asarray(vals)
    )
    got = update_fields(
        kind, init_fields(kind, capacity), jnp.asarray(slots), jnp.asarray(vals)
    )
    _assert_fields(kind, got, ref, kind_name)


def test_dense_engine_end_to_end(monkeypatch):
    monkeypatch.setenv("BYTEWAX_TPU_ACCEL", "1")
    # One device's placement (tier-1's eight virtual devices would
    # shard the table, and the mesh has its own fold).
    monkeypatch.setenv("BYTEWAX_TPU_SHARD", "0")
    import bytewax_tpu.operators as op
    from bytewax_tpu.dataflow import Dataflow
    from bytewax_tpu.testing import TestingSink, TestingSource, run_main

    inp = ["apple", "banana", "apple", "banana", "banana"]
    out = []
    flow = Dataflow("test_df")
    s = op.input("inp", flow, TestingSource(inp))
    s = op.count_final("count", s, lambda x: x)
    op.output("out", s, TestingSink(out))
    gained = _Gained()
    run_main(flow)
    assert sorted(out) == [("apple", 2), ("banana", 3)]
    assert gained("fold_dense_rows") > 0
    assert gained("fold_scatter_rows") == 0


def test_int_state_stays_exact_in_the_dense_form():
    # An int32 table reduces in int32 throughout: a float32 mask or
    # partial would round values above 2^24.
    agg = DeviceAggState("sum")
    big = 20_000_001  # not representable in f32
    gained = _Gained()
    agg.update(np.array(["k"]), np.array([big], dtype=np.int32))
    agg.update(np.array(["k"]), np.array([big], dtype=np.int32))
    assert dict(agg.finalize())["k"] == 2 * big
    assert gained("fold_dense_rows") > 0 and gained("fold_scatter_rows") == 0


# -- the three entry points at the job's shapes, small -----------------------


@pytest.fixture(scope="module")
def brc_rows():
    """Rows as a 1BRC chunk brings them: int16 external ids through a
    415-entry id->slot table into 1,024 slots, one id the table does
    not know (it routes to the scratch slot, as the sentinel the
    padding carries does), int16 deci-degrees, and the same rows as
    slots and float32 values."""
    rng = np.random.default_rng(34)
    capacity, n_ext, n, padded = 1024, 415, 5000, 8192
    table = np.full(n_ext, capacity - 1, dtype=np.int32)
    table[: n_ext - 2] = rng.permutation(n_ext - 2)
    unknown = n_ext - 2
    ext = np.full(padded, n_ext - 1, dtype=np.int16)
    ext[:n] = rng.integers(0, unknown, size=n)
    ext[17] = unknown
    quant = np.zeros(padded, dtype=np.int16)
    quant[:n] = rng.integers(-999, 1000, size=n)
    scale = np.float32(0.1)
    slots = table[ext.astype(np.int64)]
    values = quant.astype(np.float32) * scale
    return {
        "capacity": capacity, "table": table, "ext": ext, "quant": quant,
        "scale": scale, "slots": slots, "values": values,
    }


def _numpy_stats(capacity, slots, values):
    """float64 sums; the scratch slot answers nothing."""
    real = slots != capacity - 1
    want = {
        "min": np.full(capacity, np.inf),
        "max": np.full(capacity, -np.inf),
        "sum": np.zeros(capacity),
        "count": np.zeros(capacity),
    }
    np.minimum.at(want["min"], slots[real], values[real].astype(np.float64))
    np.maximum.at(want["max"], slots[real], values[real].astype(np.float64))
    np.add.at(want["sum"], slots[real], values[real].astype(np.float64))
    np.add.at(want["count"], slots[real], 1.0)
    return want


def _fold_through(path, kind, state, rows):
    if path == "plain":
        return update_fields(
            kind, state, jnp.asarray(rows["slots"]), jnp.asarray(rows["values"])
        )
    if path == "vocab":
        return update_fields_vocab(
            kind, state, jnp.asarray(rows["table"]), jnp.asarray(rows["ext"]),
            jnp.asarray(rows["values"]),
        )
    packed = np.stack([rows["ext"], rows["quant"]])
    return update_fields_packed(
        kind, state, jnp.asarray(rows["table"]), jnp.asarray(packed),
        jnp.float32(rows["scale"]),
    )


@pytest.mark.parametrize("path", ["plain", "vocab", "packed"])
def test_entry_point_against_scatter_and_numpy(brc_rows, path):
    """Two deliveries of the same rows, so the second folds into a
    table that already holds something: extrema and counts to the
    last bit, sums within float32 of the float64 reference and no
    farther from it than the scatter's are."""
    kind = AGG_KINDS["stats"]
    capacity = brc_rows["capacity"]
    slots = jnp.asarray(brc_rows["slots"])
    values = jnp.asarray(brc_rows["values"])
    got = ref = None
    for _ in range(2):
        got = _fold_through(
            path, kind, got or init_fields(kind, capacity), brc_rows
        )
        ref = _scatter(kind, ref or init_fields(kind, capacity), slots, values)
    want = _numpy_stats(
        capacity,
        np.concatenate([brc_rows["slots"]] * 2),
        np.concatenate([brc_rows["values"]] * 2),
    )
    for name in ("min", "max", "count"):
        np.testing.assert_array_equal(np.asarray(got[name]), np.asarray(ref[name]), name)
        np.testing.assert_array_equal(
            np.asarray(got[name]), want[name].astype(np.float32), name
        )
    got_err = np.abs(np.asarray(got["sum"], dtype=np.float64) - want["sum"]).max()
    ref_err = np.abs(np.asarray(ref["sum"], dtype=np.float64) - want["sum"]).max()
    assert got_err <= max(ref_err, 1e-4), (got_err, ref_err)
    # The scratch slot took padding and the unknown id, and holds the
    # identity again.
    assert np.asarray(got["count"])[-1] == 0 and np.asarray(got["sum"])[-1] == 0
    assert np.asarray(got["min"])[-1] == np.inf
    assert np.asarray(got["max"])[-1] == -np.inf
    assert int(np.asarray(got["count"]).sum()) == 2 * (5000 - 1)


def test_a_large_vocabulary_is_looked_up_a_row():
    """Past the threshold the rows' ids go through the table first;
    the table's own size then decides (here: dense)."""
    kind = AGG_KINDS["sum"]
    capacity, n_ext = 1024, DENSE_MAX_SLOTS + 2
    rng = np.random.default_rng(5)
    table = np.full(n_ext, capacity - 1, dtype=np.int32)
    table[: n_ext - 1] = rng.integers(0, capacity - 1, size=n_ext - 1)
    ext = rng.integers(0, n_ext, size=4096).astype(np.int32)
    values = rng.integers(-8, 8, size=4096).astype(np.float32)
    state = init_fields(kind, capacity)
    assert fold_is_dense(state, jnp.asarray(table))
    got = update_fields_vocab(
        kind, state, jnp.asarray(table), jnp.asarray(ext), jnp.asarray(values)
    )
    want = np.zeros(capacity)
    np.add.at(want, table[ext], values)
    want[-1] = 0
    np.testing.assert_array_equal(np.asarray(got["sum"]), want.astype(np.float32))


# -- which form a table takes ---------------------------------------------------


def _grown(kind_name, n_keys):
    agg = DeviceAggState(kind_name)
    keys = np.array([f"k{i:05d}" for i in range(n_keys)])
    agg.update(keys, np.ones(n_keys, dtype=np.float32))
    return agg, keys


def test_a_table_at_the_threshold_folds_densely():
    # A block keeps its scratch slot and one to spare.
    gained = _Gained()
    agg, keys = _grown("sum", DENSE_MAX_SLOTS - 2)
    assert agg.capacity == DENSE_MAX_SLOTS
    assert gained("fold_dense_rows") == DENSE_MAX_SLOTS
    assert gained("fold_scatter_rows") == 0
    assert dict(agg.finalize()) == dict.fromkeys(keys.tolist(), 1.0)


def test_a_table_above_the_threshold_scatters():
    gained = _Gained()
    agg, keys = _grown("sum", DENSE_MAX_SLOTS - 1)
    assert agg.capacity == 2 * DENSE_MAX_SLOTS
    assert gained("fold_scatter_rows") == DENSE_MAX_SLOTS
    assert gained("fold_dense_rows") == 0
    assert dict(agg.finalize()) == dict.fromkeys(keys.tolist(), 1.0)


def test_a_table_that_grows_across_the_threshold_keeps_its_state():
    agg, keys = _grown("stats", DENSE_MAX_SLOTS - 2)
    assert agg.capacity == DENSE_MAX_SLOTS
    gained = _Gained()
    more = np.array([f"m{i:05d}" for i in range(300)])
    both = np.concatenate([keys[:500], more])
    agg.update(both, np.full(len(both), 3.0, dtype=np.float32))
    assert agg.capacity == 2 * DENSE_MAX_SLOTS
    assert gained("fold_scatter_rows") > 0 and gained("fold_dense_rows") == 0
    got = dict(agg.finalize())
    assert len(got) == DENSE_MAX_SLOTS - 2 + 300
    # stats' final is (min, mean, max, count).
    assert got[keys[0]] == (1.0, 2.0, 3.0, 2)
    assert got[keys[-1]] == (1.0, 1.0, 1.0, 1)
    assert got[more[0]] == (3.0, 3.0, 3.0, 1)


def test_an_int32_table_folds_densely_and_exactly():
    """min, max, sum and count over int32 values no float32 holds,
    against numpy's int64, through the entry point."""
    kind = AGG_KINDS["stats"]
    capacity, n = 1024, 4096
    rng = np.random.default_rng(11)
    slots = rng.integers(0, capacity, size=n).astype(np.int32)
    values = (2**24 + rng.integers(1, 2**20, size=n)).astype(np.int32)
    state = init_fields(kind, capacity, jnp.int32)
    assert fold_is_dense(state)
    got = update_fields(kind, state, jnp.asarray(slots), jnp.asarray(values))
    real = slots != capacity - 1
    info = np.iinfo(np.int32)
    want = {
        "min": np.full(capacity, info.max, dtype=np.int64),
        "max": np.full(capacity, info.min, dtype=np.int64),
        "sum": np.zeros(capacity, dtype=np.int64),
        "count": np.zeros(capacity, dtype=np.int64),
    }
    np.minimum.at(want["min"], slots[real], values[real])
    np.maximum.at(want["max"], slots[real], values[real])
    np.add.at(want["sum"], slots[real], values[real])
    np.add.at(want["count"], slots[real], 1)
    for name in kind.fields:
        assert got[name].dtype == jnp.int32
        np.testing.assert_array_equal(np.asarray(got[name]), want[name], name)


def test_a_1brc_job_engages_the_dense_form_with_nothing_set(monkeypatch, tmp_path):
    """A measurements file through ``BrcFileSource`` and ``cli_main``:
    the packed rows fold densely, as ``GET /status`` says while the
    job's API plane is up."""
    pytest.importorskip("bytewax_tpu.native")
    from bytewax_tpu.models.brc import BrcFileSource, brc_flow
    from bytewax_tpu.outputs import DynamicSink, StatelessSinkPartition
    from bytewax_tpu.run import cli_main

    for name in ("BYTEWAX_TPU_ACCEL", "BYTEWAX_TPU_PLATFORM"):
        monkeypatch.delenv(name, raising=False)
    monkeypatch.setenv("BYTEWAX_TPU_SHARD", "0")  # the one-device placement
    rng = np.random.default_rng(1)
    stations = [f"station{i:03d}" for i in range(40)]
    rows = [
        (stations[i], t / 10)
        for i, t in zip(rng.integers(0, 40, 20_000), rng.integers(-500, 500, 20_000))
    ]
    path = tmp_path / "measurements.txt"
    path.write_text("".join(f"{s};{t:.1f}\n" for s, t in rows))

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    monkeypatch.setenv("BYTEWAX_DATAFLOW_API_ENABLED", "1")
    monkeypatch.setenv("BYTEWAX_DATAFLOW_API_PORT", str(port))
    monkeypatch.chdir(tmp_path)  # the API plane dumps dataflow.json
    out, seen = [], {}

    class _Sink(DynamicSink):
        def build(self, step_id, worker_index, worker_count):
            class _Part(StatelessSinkPartition):
                def write_batch(self, items):
                    if not out:
                        url = f"http://127.0.0.1:{port}/status"
                        with urllib.request.urlopen(url, timeout=10) as r:
                            seen.update(json.loads(r.read()))
                    out.extend(items)

            return _Part()

    before = dict(flight.RECORDER.counters)
    assert cli_main(brc_flow(BrcFileSource(str(path)), _Sink())) is None
    counters = seen["recorder"]["counters"]
    assert counters["fold_dense_rows"] - before.get("fold_dense_rows", 0) >= 20_000
    assert counters.get("fold_scatter_rows", 0) == before.get("fold_scatter_rows", 0)
    got = dict(out)
    assert len(got) == 40
    temps = [t for s, t in rows if s == stations[0]]
    want = (min(temps), round(sum(temps) / len(temps), 1), max(temps))
    assert got[stations[0]] == pytest.approx(want, abs=0.051)


# -- the chip's compiler, without the chip ------------------------------------


@pytest.fixture(scope="module")
def one_chip():
    """A described v5e chip: the TPU's compiler takes the fold at the
    job's real shapes here, and what it refuses (a misaligned slice, too
    much VMEM) costs no chip time.  Nothing runs."""
    import os

    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else logs under /tmp
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as ex:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {ex}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize(
    ("capacity", "rows", "dtype"),
    [(1024, 2**21, jnp.float32), (DENSE_MAX_SLOTS, 2**17, jnp.float32), (1024, 2**17, jnp.int32)],
)
def test_the_dense_fold_compiles_for_a_v5e(monkeypatch, one_chip, capacity, rows, dtype):
    from bytewax_tpu.ops import pallas_fold

    monkeypatch.setattr(pallas_fold, "_interpret", lambda: False)
    kind = AGG_KINDS["stats"]

    def shape(dims, dt):
        return jax.ShapeDtypeStruct(dims, dt, sharding=one_chip)

    state = {name: shape((capacity,), dtype) for name in kind.fields}
    plain = update_fields.lower(kind, state, shape((rows,), jnp.int32), shape((rows,), dtype))
    assert "tpu_custom_call" in plain.compile().as_text()
    if dtype == jnp.float32:
        packed = update_fields_packed.lower(
            kind, state, shape((415,), jnp.int32), shape((2, rows), jnp.int16),
            shape((), jnp.float32),
        )
        text = packed.compile().as_text()
        assert "tpu_custom_call" in text
        # The rows are folded by external id: no gather of them.
        assert f"[{rows}]" not in "".join(
            line for line in text.splitlines() if " gather(" in line
        )
