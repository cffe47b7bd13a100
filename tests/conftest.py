"""pytest config: fixtures mirroring the reference's test strategy
(SURVEY.md §4): every dataflow test runs under three entry points
(single lane, cluster with 1 lane, cluster with 2 lanes), and device
tests run on a virtual 8-device CPU mesh."""

# Force a deterministic virtual 8-device CPU mesh for all tests BEFORE
# jax initializes a backend (overriding any inherited platform
# setting); chip runs go through chip_smoke.py / benchmark.run / run.py.
import os

# The driver arms jax's persistent compile cache on every run.  Tier-1
# keeps it off with jax's own switch (read when jax is first imported,
# and inherited by every child the tests spawn): a cache in the
# checkout would be copied to other machines and read there, and
# concurrent cache writes from children that join jax.distributed on
# the CPU backend have corrupted the CPU client's heap.  Tests of the
# cache itself re-enable it in their own children.
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "0"

from bytewax_tpu.utils import force_cpu_mesh  # noqa: E402

force_cpu_mesh(8)

from datetime import datetime, timezone  # noqa: E402

from pytest import fixture  # noqa: E402

from bytewax_tpu.recovery import RecoveryConfig, init_db_dir  # noqa: E402
from bytewax_tpu.testing import cluster_main, run_main  # noqa: E402


@fixture(scope="session", autouse=True)
def _warm_device_tier():
    """Compile the device fold once up front: EventClock watermarks
    advance with wall-clock time, so a ~1s first-compile inside a
    windowing test can flip borderline items late (a cold-start flake
    when a single test runs alone)."""
    import numpy as np

    from bytewax_tpu.engine.xla import DeviceAggState

    st = DeviceAggState("count")
    st.update(np.array(["warm"]), np.array([1.0]))
    st.finalize()


@fixture(params=["run_main", "cluster_main-1thread", "cluster_main-2thread"])
def entry_point_name(request):
    """Run a version of the test for each execution entry point."""
    return request.param


def _wrapped_cluster_main1x2(*args, **kwargs):
    return cluster_main(*args, [], 0, worker_count_per_proc=2, **kwargs)


def _wrapped_cluster_main1x1(*args, **kwargs):
    return cluster_main(*args, [], 0, **kwargs)


@fixture
def entry_point(entry_point_name):
    """Callable for each execution entry point."""
    if entry_point_name == "run_main":
        return run_main
    elif entry_point_name == "cluster_main-1thread":
        return _wrapped_cluster_main1x1
    elif entry_point_name == "cluster_main-2thread":
        return _wrapped_cluster_main1x2
    else:
        msg = f"unknown entry point name: {entry_point_name!r}"
        raise ValueError(msg)


@fixture
def recovery_config(tmp_path):
    """A recovery config pointing at a 1-partition store."""
    init_db_dir(tmp_path, 1)
    yield RecoveryConfig(str(tmp_path))


@fixture
def now():
    """Current datetime in UTC."""
    yield datetime.now(timezone.utc)
