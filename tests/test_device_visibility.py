"""Nothing hides the device tier: a step that leaves it says so, a
backend that fails to come up is not read as "one device", the
engine reports the backend it got, the compile cache lives where the
environment says, and launchers give each child its own chip or
refuse."""

import json
import os
import subprocess
import sys
from datetime import timedelta
from pathlib import Path

import pytest

import bytewax_tpu.operators as op
import bytewax_tpu.operators.windowing as w
from bytewax_tpu import xla
from bytewax_tpu.dataflow import Dataflow
from bytewax_tpu.engine import flight
from bytewax_tpu.testing import TestingSource, run_main

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

from chip_smoke import (  # noqa: E402
    ALIGN,
    Probe,
    _free_port,
    _sink,
    device_or_exit,
)


@pytest.fixture
def probe(monkeypatch, tmp_path):
    port = _free_port()
    monkeypatch.setenv("BYTEWAX_DATAFLOW_API_ENABLED", "1")
    monkeypatch.setenv("BYTEWAX_DATAFLOW_API_PORT", str(port))
    monkeypatch.setenv("BYTEWAX_TPU_ACCEL", "1")
    monkeypatch.chdir(tmp_path)
    return Probe(port)


def _agg_fallback_flow(out, probe):
    # Strings can't fold on device; the host tier concatenates them.
    flow = Dataflow("agg_fallback")
    s = op.input("inp", flow, TestingSource([("k", "a"), ("k", "b")]))
    s = op.reduce_final("sum", s, xla.SUM)
    op.output("out", s, _sink(out, probe))
    return flow, [("k", "ab")]


def _scan_fallback_flow(out, probe):
    # Complex values can't ride the device scan (not float-coercible);
    # the host mapper's arithmetic takes them as they are.
    flow = Dataflow("scan_fallback")
    s = op.input("inp", flow, TestingSource([("k", 2 + 0j), ("k", 4 + 0j)]))
    s = op.stateful_map("ema", s, xla.ema(0.5))
    op.output("out", s, _sink(out, probe))
    return flow, [("k", (2 + 0j, 2 + 0j)), ("k", (4 + 0j, 10 / 3 + 0j))]


@pytest.mark.parametrize(
    "make",
    [_agg_fallback_flow, _scan_fallback_flow],
    ids=["agg", "scan"],
)
def test_host_fallback_shows_as_host_tier(make, probe):
    out = []
    flow, want = make(out, probe)
    before = flight.RECORDER.counters.get("demotion_count", 0)
    run_main(flow)
    assert out == want
    assert flight.RECORDER.counters["demotion_count"] == before + 1
    tiers = {n["step_id"]: n["tier"] for n in probe.graph["steps"]}
    (stateful,) = [sid for sid in tiers if sid.endswith("stateful_batch")]
    assert tiers[stateful] == "host"


def test_window_host_fallback_shows_as_host_tier(probe, monkeypatch):
    # Without the native promotion (no toolchain) an itemized numeric
    # windowed fold has nowhere to go but the host tier.
    from bytewax_tpu.engine import window_accel

    monkeypatch.setattr(
        window_accel.DeviceWindowAggState,
        "on_batch_items",
        lambda self, items: None,
    )
    rows = [
        ("k", xla.TsValue(2.0, ALIGN + timedelta(seconds=1))),
        ("k", xla.TsValue(3.0, ALIGN + timedelta(seconds=2))),
    ]
    out = []
    flow = Dataflow("window_fallback")
    s = op.input("inp", flow, TestingSource(rows))
    wo = w.reduce_window(
        "sum",
        s,
        w.EventClock(
            ts_getter=xla.column_ts,
            wait_for_system_duration=timedelta(seconds=5),
        ),
        w.TumblingWindower(align_to=ALIGN, length=timedelta(minutes=1)),
        xla.SUM,
    )
    op.output("out", wo.down, _sink(out, probe))
    before = flight.RECORDER.counters.get("demotion_count", 0)
    run_main(flow)
    assert out == [("k", (0, 5.0))]
    assert flight.RECORDER.counters["demotion_count"] == before + 1
    tiers = {n["step_id"]: n["tier"] for n in probe.graph["steps"]}
    (stateful,) = [sid for sid in tiers if sid.endswith("stateful_batch")]
    assert tiers[stateful] == "host"


def test_status_names_the_backend(probe):
    out = []
    flow = Dataflow("status_device")
    s = op.input("inp", flow, TestingSource([("k", 1.0), ("k", 2.0)]))
    s = op.reduce_final("sum", s, xla.SUM)
    op.output("out", s, _sink(out, probe))
    run_main(flow)
    assert probe.status["device"] == device_or_exit(allow_cpu=True)
    assert probe.status["compile_cache_dir"].endswith(".jax_cache")


def test_shard_devices_propagates_a_backend_error(monkeypatch):
    import jax

    from bytewax_tpu.engine import sharded_state

    def boom():
        msg = "Unable to initialize backend 'tpu'"
        raise RuntimeError(msg)

    monkeypatch.delenv("BYTEWAX_TPU_SHARD", raising=False)
    monkeypatch.setattr(jax, "local_devices", boom)
    with pytest.raises(RuntimeError, match="Unable to initialize"):
        sharded_state._shard_devices()
    with pytest.raises(RuntimeError, match="Unable to initialize"):
        sharded_state.make_agg_state("sum")


def test_cache_loads_are_not_counted_as_compiles():
    from jax import monitoring

    flight.ensure_compile_listener()
    event = "/jax/core/compile/backend_compile_duration"
    before = flight.RECORDER.counters.get("xla_compile_count", 0)
    monitoring.record_event("/jax/compilation_cache/cache_hits")
    monitoring.record_event_duration_secs(event, 0.5)
    assert flight.RECORDER.counters.get("xla_compile_count", 0) == before
    monitoring.record_event_duration_secs(event, 0.5)
    assert flight.RECORDER.counters["xla_compile_count"] == before + 1


_CACHE_DIR_SCRIPT = """
import json, sys
sys.path.insert(0, {repo!r})
import bytewax_tpu.operators as op
from bytewax_tpu.dataflow import Dataflow
from bytewax_tpu.testing import TestingSink, TestingSource, run_main

flow = Dataflow("cache_dir")
op.output("out", op.input("inp", flow, TestingSource([1])), TestingSink([]))
run_main(flow)
# What a driver start-up leaves jax configured with (arming twice
# changes nothing).
from bytewax_tpu.engine import driver
print(json.dumps(driver._arm_compile_cache()))
"""


def _cache_dir_of_a_fresh_process(**env) -> str:
    child_env = {
        k: v
        for k, v in os.environ.items()
        if k not in ("JAX_COMPILATION_CACHE_DIR", "JAX_ENABLE_COMPILATION_CACHE")
    }
    res = subprocess.run(
        [sys.executable, "-c", _CACHE_DIR_SCRIPT.format(repo=str(REPO))],
        env=dict(child_env, JAX_PLATFORMS="cpu", **env),
        capture_output=True,
        text=True,
        timeout=120,
        check=True,
    )
    return json.loads(res.stdout.strip().splitlines()[-1])


def test_cache_dir_is_the_environments_when_set(tmp_path):
    named = str(tmp_path / "named-cache")
    assert (
        _cache_dir_of_a_fresh_process(JAX_COMPILATION_CACHE_DIR=named)
        == named
    )


def test_cache_dir_is_fixed_in_the_checkout_when_unset():
    # Cache writes off (jax's own switch): only the location is under
    # test, and tier-1 leaves nothing in the checkout.
    dirs = {
        _cache_dir_of_a_fresh_process(JAX_ENABLE_COMPILATION_CACHE="0")
        for _ in range(2)
    }
    assert dirs == {str(REPO / ".jax_cache")}


# -- one process per chip ----------------------------------------------------


def test_chip_env_gives_each_child_its_own_chip(monkeypatch):
    from bytewax_tpu import utils

    monkeypatch.setattr(utils, "local_chip_count", lambda: 4)
    envs = [utils.chip_env(i, 2, {}) for i in range(2)]
    assert [e["TPU_VISIBLE_CHIPS"] for e in envs] == ["0", "1"]
    assert all(e["TPU_PROCESS_BOUNDS"] == "1,1,1" for e in envs)
    # One process keeps every chip (it shards over them itself); CPU
    # children and explicit choices are left alone.
    assert utils.chip_env(0, 1, {}) == {}
    assert utils.chip_env(1, 2, {"BYTEWAX_TPU_PLATFORM": "cpu"}) == {}
    assert utils.chip_env(1, 2, {"TPU_VISIBLE_CHIPS": "3"}) == {}
    with pytest.raises(RuntimeError, match="5 device-tier processes"):
        utils.chip_env(0, 5, {})
    monkeypatch.setattr(utils, "local_chip_count", lambda: 0)
    assert utils.chip_env(1, 8, {}) == {}


def test_testing_launcher_refuses_before_starting_anything(monkeypatch):
    from bytewax_tpu import testing, utils

    def no_children(*_a, **_kw):
        raise AssertionError("a child was started")

    monkeypatch.setattr(utils, "local_chip_count", lambda: 1)
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    monkeypatch.delenv("BYTEWAX_TPU_PLATFORM", raising=False)
    monkeypatch.setattr(subprocess, "Popen", no_children)
    monkeypatch.setattr(sys, "argv", ["x", "some_module:flow", "-p", "2"])
    with pytest.raises(SystemExit) as exit_info:
        testing._cluster_test_main()
    assert exit_info.value.code == 2


def test_supervisor_refuses_before_starting_anything(monkeypatch):
    from bytewax_tpu import supervise, utils

    monkeypatch.setattr(utils, "local_chip_count", lambda: 2)
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    monkeypatch.delenv("BYTEWAX_TPU_PLATFORM", raising=False)
    with pytest.raises(RuntimeError, match="one process at a time"):
        supervise.ClusterSupervisor(
            "some_module:flow", min_procs=3, max_procs=3
        )
    sup = supervise.ClusterSupervisor(
        "some_module:flow", min_procs=2, max_procs=2
    )
    assert sup._child_env(1)["TPU_VISIBLE_CHIPS"] == "1"
