"""Mesh-sharded keyed aggregation: the all_to_all exchange step, the
ShardedAggState engine tier, dataflow equivalence with the host tier,
and cross-tier recovery (host <-> single-device <-> mesh)."""

import collections

import numpy as np
import pytest

import bytewax_tpu.operators as op
from bytewax_tpu import xla
from bytewax_tpu.dataflow import Dataflow
from bytewax_tpu.engine.arrays import ArrayBatch
from bytewax_tpu.testing import TestingSink, TestingSource, run_main
from tests.test_xla import ArraySource


def _mesh(n=8):
    import jax

    from bytewax_tpu.parallel.mesh import make_mesh

    if len(jax.devices()) < n:
        pytest.skip(f"needs {n} virtual devices")
    return make_mesh(n)


# -- make_sharded_step directly ---------------------------------------------


def _run_step(mesh, kind, key_ids, values, cap_per_shard=64, capacity=None,
              dtype=None):
    import jax
    import jax.numpy as jnp

    from bytewax_tpu.ops.sharded import init_sharded_fields, make_sharded_step
    from bytewax_tpu.parallel.mesh import key_sharding

    n_shards = len(mesh.devices)
    if dtype is None:
        dtype = jnp.float32
    if capacity is None:
        # true per-(source block, dest) maximum
        rows_per_shard = len(key_ids) // n_shards
        block_of = np.arange(len(key_ids)) // rows_per_shard
        dest = key_ids % n_shards
        capacity = int(
            np.bincount(
                block_of * n_shards + dest, minlength=n_shards * n_shards
            ).max()
        )
    fields = init_sharded_fields(
        xla_kind(kind), mesh, cap_per_shard, dtype=dtype
    )
    step = make_sharded_step(mesh, kind, cap_per_shard, capacity, dtype=dtype)
    sh = key_sharding(mesh)
    out = step(
        fields,
        jax.device_put(jnp.asarray(key_ids), sh),
        jax.device_put(jnp.asarray(values), sh),
        jax.device_put(jnp.ones(len(key_ids), dtype=bool), sh),
    )
    return {k: np.asarray(v) for k, v in out.items()}


def xla_kind(name):
    from bytewax_tpu.ops.segment import AGG_KINDS

    return AGG_KINDS[name]


def _oracle_index(kid, n_shards, cap_per_shard):
    shard, slot = kid % n_shards, kid // n_shards
    return shard * cap_per_shard + slot


def test_sharded_step_matches_oracle_random():
    mesh = _mesh()
    rng = np.random.RandomState(1)
    n, n_keys, cap = 512, 100, 64
    key_ids = rng.randint(0, n_keys, size=n).astype(np.int32)
    values = rng.randn(n).astype(np.float32)
    out = _run_step(mesh, "stats", key_ids, values, cap_per_shard=cap)
    for k in range(n_keys):
        idx = _oracle_index(k, 8, cap)
        rows = values[key_ids == k]
        assert out["count"][idx] == len(rows)
        if len(rows):
            np.testing.assert_allclose(out["sum"][idx], rows.sum(), rtol=1e-5)
            np.testing.assert_allclose(out["min"][idx], rows.min(), rtol=1e-6)
            np.testing.assert_allclose(out["max"][idx], rows.max(), rtol=1e-6)
    assert out["count"].sum() == n  # row conservation


def test_sharded_step_nonuniform_distribution():
    # All rows target two shards; every other bucket is empty.
    mesh = _mesh()
    n, cap = 256, 64
    key_ids = np.where(
        np.arange(n) % 2 == 0, 0, 1
    ).astype(np.int32)  # keys 0 (shard 0) and 1 (shard 1)
    values = np.ones(n, dtype=np.float32)
    out = _run_step(mesh, "sum", key_ids, values, cap_per_shard=cap)
    assert out["sum"][_oracle_index(0, 8, cap)] == n // 2
    assert out["sum"][_oracle_index(1, 8, cap)] == n // 2
    assert out["sum"].sum() == n


def test_sharded_step_float_bitcast_roundtrip():
    # Negative / subnormal-ish floats must survive the int32 bitcast
    # ride through the exchange exactly.
    mesh = _mesh()
    cap = 16
    # Smallest NORMAL float32 included; subnormals are out of scope
    # (XLA flushes them to zero on every tier).
    specials = np.array(
        [-0.0, 1.5, -2.25, 1.2e-38, -1e38, 3.14159], dtype=np.float32
    )
    n = 64
    key_ids = (np.arange(n) % len(specials)).astype(np.int32)
    values = specials[key_ids]
    out = _run_step(mesh, "max", key_ids, values, cap_per_shard=cap)
    for k, v in enumerate(specials):
        idx = _oracle_index(k, 8, cap)
        assert out["max"][idx] == np.float32(v), (k, v, out["max"][idx])


def test_sharded_step_int32_exact():
    import jax.numpy as jnp

    mesh = _mesh()
    cap = 16
    n = 64
    key_ids = np.zeros(n, dtype=np.int32)
    values = np.full(n, 2**24 + 1, dtype=np.int32)  # not f32-representable
    out = _run_step(
        mesh, "sum", key_ids, values, cap_per_shard=cap, dtype=jnp.int32
    )
    assert out["sum"][_oracle_index(0, 8, cap)] == n * (2**24 + 1)


def test_sharded_step_capacity_boundary():
    # Exactly capacity rows from one source block to one destination:
    # nothing may be lost at the boundary.
    mesh = _mesh()
    cap_per_shard, capacity = 16, 8
    n = 64  # 8 rows per source block
    key_ids = np.zeros(n, dtype=np.int32)  # all to shard 0, count==capacity
    values = np.ones(n, dtype=np.float32)
    out = _run_step(
        mesh, "sum", key_ids, values,
        cap_per_shard=cap_per_shard, capacity=capacity,
    )
    assert out["sum"][_oracle_index(0, 8, cap_per_shard)] == n


# -- ShardedAggState --------------------------------------------------------


def test_sharded_state_matches_single_device():
    from bytewax_tpu.engine.sharded_state import ShardedAggState
    from bytewax_tpu.engine.xla import DeviceAggState

    mesh = _mesh()
    rng = np.random.RandomState(2)
    n = 3000
    keys = np.array([f"k{i:03d}" for i in rng.randint(0, 413, size=n)])
    vals = (rng.randn(n) * 10).round(1).astype(np.float64)

    sharded = ShardedAggState("stats", mesh)
    single = DeviceAggState("stats")
    for i in range(0, n, 700):  # uneven batches
        sharded.update(keys[i : i + 700], vals[i : i + 700])
        single.update(keys[i : i + 700], vals[i : i + 700])
    a, b = sharded.finalize(), single.finalize()
    assert [k for k, _ in a] == [k for k, _ in b]
    # The two placements sum a key's float32 rows in different
    # orders (the mesh scatters, one device reduces densely): a mean
    # of readings of size 10 that cancel is held to their rounding,
    # not to a share of itself.
    for (ka, va), (_kb, vb) in zip(a, b):
        np.testing.assert_allclose(va, vb, rtol=1e-5, atol=1e-5, err_msg=ka)


def test_sharded_state_skewed_hot_key():
    # One key receives far more rows than any per-bucket guess would
    # allow; the host-sized exchange must not lose a single row.
    from bytewax_tpu.engine.sharded_state import ShardedAggState

    mesh = _mesh()
    st = ShardedAggState("count", mesh)
    keys = np.array(["hot"] * 9000 + [f"cold{i}" for i in range(100)])
    st.update(keys, np.zeros(len(keys)))
    out = dict(st.finalize())
    assert out["hot"] == 9000
    assert sum(out.values()) == 9100


# -- engine integration -----------------------------------------------------


def _brc_flow(batches, out):
    flow = Dataflow("sharded_df")
    s = op.input("inp", flow, ArraySource(batches))
    r = xla.stats_final("stats", s)
    op.output("out", r, TestingSink(out))
    return flow


def _brc_batches(n=4000, n_keys=200, seed=4):
    rng = np.random.RandomState(seed)
    batches = []
    for i in range(0, n, 512):
        m = min(512, n - i)
        batches.append(
            ArrayBatch(
                {
                    "key": np.array(
                        [f"s{k:03d}" for k in rng.randint(0, n_keys, size=m)]
                    ),
                    "value": (rng.randn(m) * 10).round(1),
                }
            )
        )
    return batches


def test_dataflow_sharded_matches_host_tier(monkeypatch):
    # The "Done" bar from the round-1 verdict: a dataflow on the
    # 8-device mesh produces output identical to the host tier.
    batches = _brc_batches()

    monkeypatch.setenv("BYTEWAX_TPU_ACCEL", "1")
    monkeypatch.setenv("BYTEWAX_TPU_SHARD", "8")
    sharded = []
    run_main(_brc_flow(batches, sharded))

    monkeypatch.setenv("BYTEWAX_TPU_SHARD", "0")
    single = []
    run_main(_brc_flow(batches, single))

    monkeypatch.setenv("BYTEWAX_TPU_ACCEL", "0")
    host = []
    run_main(_brc_flow(batches, host))

    assert [k for k, _ in sharded] == [k for k, _ in host]
    for (k, vs), (_k1, v1), (_k2, vh) in zip(sharded, single, host):
        np.testing.assert_allclose(vs, v1, rtol=1e-5, err_msg=k)
        np.testing.assert_allclose(vs, vh, rtol=1e-4, err_msg=k)


def test_dataflow_sharded_reduce_sum_exact(monkeypatch):
    # Integer reduce via the mesh stays exact and byte-identical to
    # the host tier.
    monkeypatch.setenv("BYTEWAX_TPU_ACCEL", "1")
    monkeypatch.setenv("BYTEWAX_TPU_SHARD", "8")
    inp = [(f"k{i % 40}", i) for i in range(2000)]

    def build(out):
        flow = Dataflow("sum_df")
        s = op.input("inp", flow, TestingSource(inp, batch_size=128))
        r = op.reduce_final("sum", s, xla.SUM)
        op.output("out", r, TestingSink(out))
        return flow

    sharded = []
    run_main(build(sharded))
    monkeypatch.setenv("BYTEWAX_TPU_ACCEL", "0")
    host = []
    run_main(build(host))
    assert sharded == host


def test_sharded_cross_tier_recovery(tmp_path, monkeypatch):
    # Crash on the host tier, resume on the mesh; crash on the mesh,
    # resume on the host tier.  Snapshots are the same format.
    from bytewax_tpu.recovery import RecoveryConfig, init_db_dir
    from datetime import timedelta

    def build(inp, out):
        flow = Dataflow("rec_df")
        s = op.input("inp", flow, TestingSource(inp))
        r = op.reduce_final("sum", s, xla.SUM)
        op.output("out", r, TestingSink(out))
        return flow

    # host -> mesh
    d1 = tmp_path / "a"
    d1.mkdir()
    init_db_dir(d1, 1)
    rc1 = RecoveryConfig(str(d1))
    inp1 = [("k", 1.0), ("k", 2.0), TestingSource.ABORT(), ("k", 4.0)]
    out1: list = []
    monkeypatch.setenv("BYTEWAX_TPU_ACCEL", "0")
    run_main(build(inp1, out1), epoch_interval=timedelta(0), recovery_config=rc1)
    assert out1 == []
    monkeypatch.setenv("BYTEWAX_TPU_ACCEL", "1")
    monkeypatch.setenv("BYTEWAX_TPU_SHARD", "8")
    run_main(build(inp1, out1), epoch_interval=timedelta(0), recovery_config=rc1)
    assert out1 == [("k", 7.0)]

    # mesh -> host
    d2 = tmp_path / "b"
    d2.mkdir()
    init_db_dir(d2, 1)
    rc2 = RecoveryConfig(str(d2))
    inp2 = [("k", 1.0), ("k", 2.0), TestingSource.ABORT(), ("k", 4.0)]
    out2: list = []
    run_main(build(inp2, out2), epoch_interval=timedelta(0), recovery_config=rc2)
    assert out2 == []
    monkeypatch.setenv("BYTEWAX_TPU_ACCEL", "0")
    run_main(build(inp2, out2), epoch_interval=timedelta(0), recovery_config=rc2)
    assert out2 == [("k", 7.0)]


def test_make_agg_state_selection(monkeypatch):
    from bytewax_tpu.engine.sharded_state import (
        ShardedAggState,
        make_agg_state,
    )
    from bytewax_tpu.engine.xla import DeviceAggState

    _mesh()  # ensure devices exist
    monkeypatch.setenv("BYTEWAX_TPU_SHARD", "0")
    assert isinstance(make_agg_state("sum"), DeviceAggState)
    monkeypatch.setenv("BYTEWAX_TPU_SHARD", "auto")
    st = make_agg_state("sum")
    assert isinstance(st, ShardedAggState)
    assert st.n_shards == 8
    monkeypatch.setenv("BYTEWAX_TPU_SHARD", "4")
    st4 = make_agg_state("sum")
    assert isinstance(st4, ShardedAggState)
    assert st4.n_shards == 4


def test_windowed_fold_sharded_matches_single_device(monkeypatch):
    # The windowed fold table shards over the mesh too: same output
    # as the single-device slot table and the host tier.
    from datetime import datetime, timedelta, timezone

    import bytewax_tpu.operators.windowing as w
    from bytewax_tpu.operators.windowing import EventClock, TumblingWindower
    from tests.test_xla import ArraySource

    _mesh()
    align = datetime(2022, 1, 1, tzinfo=timezone.utc)
    n = 4000
    rng = np.random.RandomState(12)
    secs = np.sort(rng.randint(0, 300, size=n))
    keys = np.array([f"key{k}" for k in rng.randint(0, 6, size=n)])
    vals = (rng.randn(n) * 3).round(2)
    ts = (
        np.datetime64(align.replace(tzinfo=None), "us")
        + secs.astype("timedelta64[s]")
    )

    def run(accel, shard):
        monkeypatch.setenv("BYTEWAX_TPU_ACCEL", accel)
        monkeypatch.setenv("BYTEWAX_TPU_SHARD", shard)
        batches = [
            ArrayBatch(
                {
                    "key": keys[i : i + 512],
                    "ts": ts[i : i + 512],
                    "value": vals[i : i + 512],
                }
            )
            for i in range(0, n, 512)
        ]
        clock = EventClock(
            ts_getter=xla.column_ts,
            wait_for_system_duration=timedelta(seconds=30),
        )
        windower = TumblingWindower(
            length=timedelta(minutes=1), align_to=align
        )
        out = []
        flow = Dataflow("swin_df")
        s = op.input("inp", flow, ArraySource(batches))
        wo = w.reduce_window("sum", s, clock, windower, xla.SUM)
        op.output("out", wo.down, TestingSink(out))
        run_main(flow)
        return sorted(out)

    sharded = run("1", "8")
    single = run("1", "0")
    host = run("0", "0")
    assert [kv[0] for kv in sharded] == [kv[0] for kv in host]
    for (k, (wd, vs)), (_k1, (_w1, v1)), (_k2, (_w2, vh)) in zip(
        sharded, single, host
    ):
        np.testing.assert_allclose(vs, v1, rtol=1e-5, err_msg=k)
        np.testing.assert_allclose(vs, vh, rtol=1e-4, err_msg=k)


def test_sharded_scan_matches_single_device(monkeypatch):
    """ShardedScanState (exchange + per-shard segmented scan +
    outputs home) must produce the same per-row outputs and
    host-format snapshots as DeviceScanState."""
    from bytewax_tpu.engine.scan_accel import DeviceScanState
    from bytewax_tpu.engine.sharded_state import ShardedScanState
    from bytewax_tpu.ops.scan import WelfordZScore
    from bytewax_tpu.parallel.mesh import make_mesh

    rng = np.random.RandomState(17)
    n = 500
    keys = np.array([f"k{j}" for j in rng.randint(0, 13, size=n)])
    vals = rng.randn(n).round(3)

    sh = ShardedScanState(WelfordZScore(2.0), make_mesh(8))
    sd = DeviceScanState(WelfordZScore(2.0))
    t_sh, e_sh = sh.update(keys, vals)
    t_sd, e_sd = sd.update(keys, vals)
    assert sorted(t_sh) == sorted(t_sd)
    np.testing.assert_allclose(e_sh.outs[0], e_sd.outs[0], atol=1e-3)
    np.testing.assert_array_equal(e_sh.outs[1], e_sd.outs[1])
    all_keys = sorted(set(keys.tolist()))
    snaps_sh = dict(sh.snapshots_for(all_keys))
    snaps_sd = dict(sd.snapshots_for(all_keys))
    for k in all_keys:
        (c1, m1, v1), (c2, m2, v2) = snaps_sh[k], snaps_sd[k]
        assert c1 == c2
        assert m1 == pytest.approx(m2, abs=1e-4)
        assert v1 == pytest.approx(v2, abs=1e-3)


def test_sharded_scan_multi_batch_and_growth():
    """Per-key scan order holds across batches and capacity growth:
    fold 3 batches over >cap keys and compare against the host
    mapper oracle."""
    from bytewax_tpu.engine.sharded_state import ShardedScanState
    from bytewax_tpu.ops.scan import WelfordZScore
    from bytewax_tpu.parallel.mesh import make_mesh

    rng = np.random.RandomState(23)
    # cap_per_shard=4 → forces at least one doubling with 80 keys/8 shards.
    st = ShardedScanState(WelfordZScore(2.5), make_mesh(8), cap_per_shard=4)
    mapper = xla.zscore(2.5)
    states, want = {}, collections.defaultdict(list)
    for _b in range(3):
        n = 200
        keys = np.array([f"g{j}" for j in rng.randint(0, 80, size=n)])
        vals = rng.randn(n).round(3)
        _t, emit = st.update(keys, vals)
        got = collections.defaultdict(list)
        for k, (v, z, a) in emit.items():
            got[k].append((v, z, a))
        for k, v in zip(keys.tolist(), vals.tolist()):
            s2, (vv, z, a) = mapper(states.get(k), v)
            states[k] = s2
            want[k].append((vv, z, a))
        # Per-batch per-key emission matches the oracle's tail.
        for k, rows in got.items():
            tail = want[k][-len(rows):]
            for (gv, gz, ga), (wv, wz, wa) in zip(rows, tail):
                assert gv == pytest.approx(wv)
                # f32 fold vs f64 oracle: large |z| (near-degenerate
                # variance) is relatively, not absolutely, accurate.
                assert gz == pytest.approx(wz, rel=1e-3, abs=1e-3)
                assert ga == wa


def test_sharded_scan_resume_from_device_snapshot():
    """Snapshots written by the single-device scan resume into the
    sharded scan (and back) — the cross-tier recovery contract holds
    across mesh sizes."""
    from bytewax_tpu.engine.scan_accel import DeviceScanState
    from bytewax_tpu.engine.sharded_state import ShardedScanState
    from bytewax_tpu.ops.scan import WelfordZScore
    from bytewax_tpu.parallel.mesh import make_mesh

    sd = DeviceScanState(WelfordZScore(2.0))
    sd.update(np.array(["a", "a", "b"]), np.array([1.0, 2.0, 10.0]))
    snaps = [s for s in sd.snapshots_for(["a", "b"])]

    sh = ShardedScanState(WelfordZScore(2.0), make_mesh(8))
    sh.load_many(snaps)
    _t, emit = sh.update(np.array(["a"]), np.array([3.0]))
    mapper = xla.zscore(2.0)
    _s, (_v, z, a) = mapper((2, 1.5, 0.5), 3.0)
    assert emit.outs[0][0] == pytest.approx(z, abs=1e-4)
    assert bool(emit.outs[1][0]) == a
    # And back: sharded snapshots resume on the single-device tier.
    snaps2 = sh.snapshots_for(["a", "b"])
    sd2 = DeviceScanState(WelfordZScore(2.0))
    sd2.load_many(snaps2)
    back = dict(sd2.snapshots_for(["a", "b"]))
    assert back["a"][0] == 3  # count folded the resumed row


def test_make_scan_state_selection(monkeypatch):
    from bytewax_tpu.engine.scan_accel import DeviceScanState
    from bytewax_tpu.engine.sharded_state import (
        ShardedScanState,
        make_scan_state,
    )
    from bytewax_tpu.ops.scan import WelfordZScore

    monkeypatch.setenv("BYTEWAX_TPU_SHARD", "0")
    assert isinstance(make_scan_state(WelfordZScore(2.0)), DeviceScanState)
    monkeypatch.setenv("BYTEWAX_TPU_SHARD", "auto")
    assert isinstance(make_scan_state(WelfordZScore(2.0)), ShardedScanState)


@pytest.mark.parametrize("kind_name", ["ema", "extrema"])
def test_sharded_scan_generic_kinds_match_single_device(kind_name):
    """Kinds WITHOUT a specialized kernel (Ema single-output,
    RunningExtrema multi-output) exercise generic_scan_body inside
    shard_map and the multi-lane return trip — pinned against the
    single-device tier."""
    from bytewax_tpu.engine.scan_accel import DeviceScanState
    from bytewax_tpu.engine.sharded_state import ShardedScanState
    from bytewax_tpu.ops.scan import Ema, RunningExtrema
    from bytewax_tpu.parallel.mesh import make_mesh

    make_kind = (lambda: Ema(0.3)) if kind_name == "ema" else RunningExtrema

    rng = np.random.RandomState(31)
    n = 300
    keys = np.array([f"k{j}" for j in rng.randint(0, 11, size=n)])
    vals = rng.randn(n).round(3)

    sh = ShardedScanState(make_kind(), make_mesh(8))
    sd = DeviceScanState(make_kind())
    t_sh, e_sh = sh.update(keys, vals)
    t_sd, e_sd = sd.update(keys, vals)
    assert sorted(t_sh) == sorted(t_sd)
    assert len(e_sh.outs) == len(e_sd.outs)
    for o_sh, o_sd in zip(e_sh.outs, e_sd.outs):
        np.testing.assert_allclose(o_sh, o_sd, atol=1e-4)
    all_keys = sorted(set(keys.tolist()))
    for (k1, s1), (k2, s2) in zip(
        sh.snapshots_for(all_keys), sd.snapshots_for(all_keys)
    ):
        assert k1 == k2
        np.testing.assert_allclose(s1, s2, rtol=1e-4, atol=1e-5)


# -- the id-based slot surface the window tier drives ------------------------


def _composites(n, n_keys=5, seed=3):
    """Integer (key, window) composites as window_accel makes them."""
    rng = np.random.RandomState(seed)
    kids = rng.randint(0, n_keys, size=n).astype(np.int64)
    wids = np.arange(n, dtype=np.int64) - n // 2  # negative ids too
    return np.unique((kids << 32) + (wids + (1 << 31)))


@pytest.mark.parametrize("n_shards", [4, 8])
@pytest.mark.parametrize(
    "kind", ["sum", "min", "max", "count", "mean", "stats"]
)
def test_sharded_open_read_release_matches_single_device(kind, n_shards):
    """Batched open / fold / read / release / reopen over a mesh gives
    what the single-device slot table gives for the same composites,
    across a capacity growth, and a reopened id reads the identity."""
    from bytewax_tpu.engine.sharded_state import ShardedAggState
    from bytewax_tpu.engine.xla import DeviceAggState

    mesh = _mesh(n_shards)
    sharded = ShardedAggState(kind, mesh, cap_per_shard=16)
    single = DeviceAggState(kind)
    comps = _composites(300)
    rng = np.random.RandomState(8)
    rows = rng.randint(0, len(comps), size=2000)
    vals = (
        np.ones(len(rows))
        if kind == "count"
        else rng.randint(-40, 40, size=len(rows)).astype(np.float64)
    )

    ids_m, ids_1 = sharded.open_ids(comps), single.open_ids(comps)
    assert len(set(ids_m.tolist())) == len(comps) == len(set(ids_1.tolist()))
    owners = sharded._owners(comps)
    assert (ids_m % n_shards == owners).all()
    assert len(set(owners.tolist())) == n_shards  # every shard takes some
    assert sharded.cap_per_shard > 16  # grew, in one call
    sharded.update_ids(ids_m[rows], vals)
    single.update_ids(ids_1[rows], vals)
    got, want = sharded.states_of(ids_m), single.states_of(ids_1)
    assert got == want
    assert [type(x) for x in np.ravel(got[:3])] == [
        type(x) for x in np.ravel(want[:3])
    ]

    # Release every other id; the next open takes exactly those back
    # (per shard, newest freed first) and they read the identity.
    freed_m, freed_1 = ids_m[::2], ids_1[::2]
    sharded.release_ids(freed_m)
    single.release_ids(freed_1)
    again = comps[::2] + (1 << 20)
    re_m, re_1 = sharded.open_ids(again), single.open_ids(again)
    assert sorted(re_1.tolist()) == sorted(freed_1.tolist())
    owners_again = sharded._owners(again)
    for shard in range(n_shards):
        had = sorted(freed_m[freed_m % n_shards == shard].tolist())
        now = re_m[owners_again == shard].tolist()
        reused = [k for k in now if k in set(had)]
        assert len(reused) == min(len(had), len(now))
    one = np.array([1.0])
    sharded.update_ids(re_m[:1], one)
    single.update_ids(re_1[:1], one)
    fresh = DeviceAggState(kind)
    fresh_ids = fresh.open_ids(again)
    fresh.update_ids(fresh_ids[:1], one)
    assert (
        sharded.states_of(re_m)
        == single.states_of(re_1)
        == fresh.states_of(fresh_ids)
    )
    # The ids that stayed keep their state through all of it.
    assert sharded.states_of(ids_m[1::2]) == want[1::2]

    # load_ids installs host-format states by id, as load_many by key.
    loaded = ShardedAggState(kind, mesh, cap_per_shard=16)
    loaded_ids = loaded.open_ids(comps)
    loaded.load_ids(loaded_ids, want)
    assert loaded.states_of(loaded_ids) == want


@pytest.mark.parametrize(
    "case",
    ["reused_slot", "constant_calls", "snapshot_resumes"],
)
def test_window_state_on_four_devices(monkeypatch, case):
    """The window tier's batched path over a 4-device mesh (the shape
    of ``chip_smoke.py``'s four-chip stage): the state-level cases of
    tests/test_window_accel.py with ``make_agg_state`` handing out a
    ``ShardedAggState``."""
    from bytewax_tpu.engine.sharded_state import ShardedAggState
    from tests import test_window_accel as twa

    _mesh(4)
    monkeypatch.setenv("BYTEWAX_TPU_SHARD", "4")
    st = twa._spec_of("stats", twa.TUMBLING_10S).make_state()
    assert isinstance(st.agg, ShardedAggState) and st.agg.n_shards == 4
    if case == "reused_slot":
        twa.test_reused_slot_starts_from_identity(monkeypatch, "stats", "4")
    elif case == "constant_calls":
        twa.test_delivery_makes_constant_calls_into_agg(
            monkeypatch, "4", True
        )
    else:
        twa.test_snapshot_resumes_to_same_results(
            monkeypatch, "4", twa.SLIDING_10S_BY_4S
        )


# -- GlobalAggState's durable baseline row ----------------------------------


def test_global_baseline_row_keeps_its_stored_format(monkeypatch):
    """A baseline row outlives the program that wrote it
    (docs/recovery.md "Store-composable overlap"): the payload below
    is spelled as the program at e492823 stored it, with the key map
    under ``"key_to_kid"``.  It installs, and the row this program
    writes from it is the same row."""
    import types

    from bytewax_tpu.engine.sharded_state import GlobalAggState

    _mesh(8)
    monkeypatch.delenv("BYTEWAX_TPU_GSYNC_OVERLAP", raising=False)
    monkeypatch.delenv("BYTEWAX_TPU_GSYNC_QUANT", raising=False)
    driver = types.SimpleNamespace(proc_count=1, worker_count=1, store=None)
    st = GlobalAggState("sum", driver)
    cap, n = st.cap_per_shard, st.n_shards
    # kid = slot * n + shard: "a" and "c" on shard 0, "b" on shard 1.
    kids = {"a": 0, "b": 1, "c": n}
    blocks = {
        "sum": {d * cap: np.zeros(cap, np.float32) for d in range(n)}
    }
    blocks["sum"][0][0] = 1.5
    blocks["sum"][cap][0] = 2.5
    blocks["sum"][0][1] = 4.0
    stored = {
        "round": 3,
        "key_to_kid": dict(kids),
        "shard_fill": [2, 1] + [0] * (n - 2),
        "procs": 1,
        "fmt": "exact",
        "blocks": blocks,
        "dtype": "float32",
    }
    st._install_baseline(stored)
    assert st.key_to_slot == kids
    assert st._shard_fill == stored["shard_fill"]
    assert st._data_rounds == 3

    again = st._capture_baseline()
    assert set(again) == set(stored)
    assert "key_to_slot" not in again
    assert again["key_to_kid"] == kids
    for key in ("round", "shard_fill", "procs", "fmt", "dtype"):
        assert again[key] == stored[key], key
    assert set(again["blocks"]) == {"sum"}
    assert set(again["blocks"]["sum"]) == set(blocks["sum"])
    for start, block in blocks["sum"].items():
        np.testing.assert_array_equal(again["blocks"]["sum"][start], block)
