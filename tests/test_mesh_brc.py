"""The 1BRC deployment on a mesh, small, on the CPU's virtual devices:
the benchmark's flow through ``cli_main`` against its plain reference
and against the one-device placement, the placement as ``GET /graph``
reports it, and the exchange's span and counters."""

import json
import os
import socket
import sys
import urllib.request

import numpy as np
import pytest

from bytewax_tpu.engine import flight
from bytewax_tpu.outputs import DynamicSink, StatelessSinkPartition
from bytewax_tpu.run import cli_main

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmark.flows import brc, brc_mesh  # noqa: E402

ROWS = 50_000
#: ``BYTEWAX_TPU_SHARD`` -> blocks the factory then builds, given the
#: local devices (``auto`` takes them all).
BLOCKS = {"auto": lambda n: n, "4": lambda n: 4, "0": lambda n: 1}


def _devices() -> int:
    import jax

    n = len(jax.local_devices())
    if n < 4:
        pytest.skip("needs 4 virtual devices")
    return n


@pytest.fixture(scope="module")
def cfg():
    pytest.importorskip("bytewax_tpu.native")
    with open(os.path.join(REPO, "benchmark", "configs", "brc-1b-mesh4.json")) as f:
        cfg = json.load(f)
    cfg["shapes"]["rows_per_job"] = ROWS
    return cfg


@pytest.fixture(scope="module")
def data(cfg, tmp_path_factory):
    return brc_mesh.make_data(cfg, {}, 2147483659, str(tmp_path_factory.mktemp("brc")))


class _Sink(DynamicSink):
    """Keeps what is written; ``at_write`` runs inside the first
    write, while the run's API plane is still up."""

    def __init__(self, out, at_write=None):
        self.out, self.at_write = out, at_write

    def build(self, step_id, worker_index, worker_count):
        sink = self

        class _Part(StatelessSinkPartition):
            def write_batch(self, items):
                if sink.at_write is not None and not sink.out:
                    sink.at_write()
                sink.out.extend(items)

        return _Part()


def _job(monkeypatch, cfg, data, shard, at_write=None):
    """One job of the benchmark's flow under ``BYTEWAX_TPU_SHARD``;
    what the comparison takes."""
    monkeypatch.setenv("BYTEWAX_TPU_ACCEL", "1")
    monkeypatch.setenv("BYTEWAX_TPU_SHARD", shard)
    out = []
    flow = brc_mesh.build_flow(cfg, data, None, _Sink(out, at_write))
    assert cli_main(flow) is None
    return brc_mesh.result_arrays(cfg, [brc_mesh.pack(out)])


@pytest.mark.parametrize("shard", ["4", "auto", "0"])
def test_flow_matches_its_reference_on_every_placement(monkeypatch, cfg, data, shard):
    """Counts exact, extrema to 1e-4, mean to 1e-3, every station
    once; ``off_mesh`` 0 exactly where the state was in the
    configuration's 4 blocks."""
    blocks = BLOCKS[shard](_devices())
    got = _job(monkeypatch, cfg, data, shard)
    numbers = brc_mesh.compare(cfg, got, brc.reference(cfg, data))
    assert set(numbers) | {"off_device"} == set(cfg["limits"])
    off_mesh = numbers.pop("off_mesh")
    assert off_mesh == (0 if blocks == cfg["shapes"]["shards"] else 1)
    failed = [k for k, v in numbers.items() if v > cfg["limits"][k]]
    assert not failed, numbers
    assert len(got["names"]) == len(set(got["names"])) == cfg["shapes"]["stations"]
    assert int(got["count"].sum()) == ROWS


def test_mesh_and_one_device_agree(monkeypatch, cfg, data):
    """Counts to the last bit.  Extrema to one float32 step and no
    further: the mesh dequantises deci-degrees on the host (a float64
    product, rounded once), one device on the device (a float32
    product), so a reading's last bit may differ."""
    _devices()
    mesh = _job(monkeypatch, cfg, data, "4")
    one = _job(monkeypatch, cfg, data, "0")
    assert mesh["names"].tolist() == one["names"].tolist()
    assert mesh["count"].tobytes() == one["count"].tobytes()
    ulp = float(np.spacing(np.float32(99.9)))
    for name in ("min", "max"):
        assert np.abs(mesh[name] - one[name]).max() <= ulp, name
    np.testing.assert_allclose(mesh["mean"], one["mean"], rtol=0, atol=1e-4)


@pytest.mark.parametrize("shard", ["auto", "4", "0"])
def test_graph_reports_where_the_state_lives(monkeypatch, tmp_path, cfg, data, shard):
    """``GET /graph``: the step's node keeps ``tier: device`` and says
    in how many blocks, on which devices, its state lives."""
    blocks = BLOCKS[shard](_devices())
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    monkeypatch.setenv("BYTEWAX_DATAFLOW_API_ENABLED", "1")
    monkeypatch.setenv("BYTEWAX_DATAFLOW_API_PORT", str(port))
    monkeypatch.chdir(tmp_path)  # the API plane dumps dataflow.json
    seen = {}

    def read_graph():
        with urllib.request.urlopen(f"http://127.0.0.1:{port}/graph", timeout=10) as r:
            seen["graph"] = json.loads(r.read())

    _job(monkeypatch, cfg, data, shard, at_write=read_graph)
    nodes = {n["step_id"]: n for n in seen["graph"]["steps"]}
    placed = {sid: n["placement"] for sid, n in nodes.items() if "placement" in n}
    assert len(placed) == 1, placed
    ((step_id, placement),) = placed.items()
    assert ".stats." in step_id and nodes[step_id]["tier"] == "device"
    assert placement["blocks"] == blocks
    assert len(placement["devices"]) == len(set(placement["devices"])) == blocks


def _watched(monkeypatch, cls):
    """Every exchange ``cls`` plans from here on, as ``(rows,
    total_rows, capacity)``, read where the step is looked up."""
    plans = []
    plan, step_for = cls._plan_exchange, cls._step_for

    def watched_plan(self, kids):
        plans.append([len(kids)])
        return plan(self, kids)

    def watched_step_for(self, total_rows, capacity):
        plans[-1] += [total_rows, capacity]
        return step_for(self, total_rows, capacity)

    monkeypatch.setattr(cls, "_plan_exchange", watched_plan)
    monkeypatch.setattr(cls, "_step_for", watched_step_for)
    return plans


def _agg_rows(n_shards, rng):
    from bytewax_tpu.engine.sharded_state import ShardedAggState
    from bytewax_tpu.parallel.mesh import make_mesh

    state = ShardedAggState("stats", make_mesh(n_shards))
    for n in (3000, 700):
        keys = rng.integers(0, 50, size=n).astype(str)
        state.update(keys, rng.normal(size=n))
    return 3700


def _scan_rows(n_shards, rng):
    from bytewax_tpu.engine.sharded_state import ShardedScanState
    from bytewax_tpu.ops.scan import WelfordZScore
    from bytewax_tpu.parallel.mesh import make_mesh

    state = ShardedScanState(WelfordZScore(2.0), make_mesh(n_shards))
    for n in (2000, 300):
        keys = rng.integers(0, 20, size=n).astype(str)
        state.update(keys, rng.normal(size=n))
    return 2300


@pytest.mark.parametrize("n_shards", [4, 8])
@pytest.mark.parametrize("drive", ["agg", "scan", "brc_flow"])
def test_exchange_span_and_counters(monkeypatch, request, drive, n_shards):
    """Whatever hands rows to a sharded step: ``exchange_rows`` is the
    rows handed over, ``exchange_bucket_rows`` the ``n_shards² ×
    capacity`` of every step, ``exchange_rows_max_block`` the fullest
    source block of every step, ``exchange_blocks`` the blocks of the
    last state, and the host half is one ``exchange`` span a step."""
    if _devices() < n_shards:
        pytest.skip(f"needs {n_shards} virtual devices")
    from bytewax_tpu.engine import sharded_state

    plans = []
    for cls in (sharded_state.ShardedAggState, sharded_state.ShardedScanState):
        plans.append(_watched(monkeypatch, cls))
    counters0 = dict(flight.RECORDER.counters)
    totals0 = dict(flight.RECORDER.phase_totals)
    rng = np.random.default_rng(n_shards)
    if drive == "brc_flow":
        cfg, data = (request.getfixturevalue(f) for f in ("cfg", "data"))
        _job(monkeypatch, cfg, data, str(n_shards))
        rows = ROWS
    else:
        rows = {"agg": _agg_rows, "scan": _scan_rows}[drive](n_shards, rng)
    steps = [p for ps in plans for p in ps]
    assert steps and all(len(p) == 3 for p in steps)

    def gained(name):
        return flight.RECORDER.counters.get(name, 0) - counters0.get(name, 0)

    assert gained("exchange_rows") == rows == sum(n for n, _t, _c in steps)
    assert gained("exchange_steps") == gained("exchange_spans") == len(steps)
    assert gained("exchange_bucket_rows") == sum(
        n_shards * n_shards * cap for _n, _t, cap in steps
    )
    assert gained("exchange_rows_max_block") == sum(
        min(n, total // n_shards) for n, total, _c in steps
    )
    assert flight.RECORDER.counters["exchange_blocks"] == n_shards
    spent = {
        phase: s - totals0.get(phase, 0.0)
        for phase, s in flight.RECORDER.phase_totals.items()
        if phase.rpartition("/")[2] == "exchange"
    }
    assert spent and all(s >= 0 for s in spent.values()) and sum(spent.values()) > 0
    # The host half is cut out of `prep`, into the same bucket.
    assert flight._BUCKET_OF["exchange"] == flight._BUCKET_OF["prep"] == "host"
    assert "exchange" in flight.TRACED_PHASES


def test_the_two_halves_of_the_step_are_named_in_the_compiled_program():
    """``jax.named_scope`` round the exchange and the fold: the
    operations of the compiled step carry the halves' names."""
    import jax
    import jax.numpy as jnp

    from bytewax_tpu.ops.scan import WelfordZScore
    from bytewax_tpu.ops.sharded import (
        init_sharded_fields,
        init_sharded_scan_fields,
        make_sharded_scan_step,
        make_sharded_step,
    )
    from bytewax_tpu.ops.segment import AGG_KINDS
    from bytewax_tpu.parallel.mesh import key_sharding, make_mesh

    _devices()
    mesh = make_mesh(4)
    sh = key_sharding(mesh)
    rows = (
        jax.device_put(jnp.zeros(256, jnp.int32), sh),
        jax.device_put(jnp.zeros(256, jnp.float32), sh),
        jax.device_put(jnp.zeros(256, bool), sh),
    )
    kind = WelfordZScore(2.0)
    for step, fields in (
        (make_sharded_step(mesh, "stats", 128, 64), init_sharded_fields(AGG_KINDS["stats"], mesh, 128)),
        (make_sharded_scan_step(mesh, kind, 128, 64), init_sharded_scan_fields(kind, mesh, 128)),
    ):
        text = step.lower(fields, *rows).compile().as_text()
        scoped = [ln for ln in text.splitlines() if "op_name=" in ln]
        assert any("/exchange/" in ln and "all-to-all" in ln for ln in scoped)
        assert any("/fold/" in ln for ln in scoped)
        assert not any("/fold/" in ln and "all-to-all" in ln for ln in scoped)


@pytest.mark.parametrize("shard", ["auto", "4", "0"])
@pytest.mark.parametrize("state", ["agg", "scan", "agg_by_ids"])
def test_state_objects_report_their_placement(monkeypatch, state, shard):
    """What ``GET /graph`` reads, off the objects the factories build
    (``agg_by_ids``: the slot table as the window tier drives it)."""
    from bytewax_tpu.engine.sharded_state import make_agg_state, make_scan_state
    from bytewax_tpu.ops.scan import WelfordZScore

    blocks = BLOCKS[shard](_devices())
    monkeypatch.setenv("BYTEWAX_TPU_SHARD", shard)
    made = make_scan_state(WelfordZScore(2.0)) if state == "scan" else make_agg_state("sum")
    before = made.placement()
    assert before["blocks"] == blocks
    # One device: nowhere until a table is made; a mesh: its devices.
    assert len(before["devices"]) == (0 if blocks == 1 else blocks)
    keys, values = np.array(["a", "b", "a"]), np.array([1.0, 2.0, 3.0])
    if state == "agg_by_ids":
        made.update_ids(np.array([made.alloc("a"), made.alloc("b")]), values[:2])
    else:
        made.update(keys, values)
    after = made.placement()
    assert after["blocks"] == blocks
    assert len(after["devices"]) == len(set(after["devices"])) == blocks
