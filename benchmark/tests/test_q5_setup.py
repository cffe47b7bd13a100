"""What the Q5 flow does before a run: it refuses a program that cannot
hold the configuration's guarantees, and set-up walks the slot table's
sizes (``warm_slot_programs``).

After the walk, a table of the same kind driven the way a window's
deliveries drive it (slots opened a few thousand at a time, a poll's
rows folded, freed slots given out again in uneven numbers, fetches)
compiles nothing, at any size up to the one the walk reached.
"""

import sys

import numpy as np
import pytest

from benchmark import run
from benchmark.flows import nexmark_q5 as q5


@pytest.fixture
def honest_compiles():
    """The persistent compile cache off, so that a program this
    process has not compiled counts as a compile."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    from bytewax_tpu.engine import flight

    flight.ensure_compile_listener()
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield lambda: flight.RECORDER.counters.get("xla_compile_count", 0)
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def test_the_traffic_sizes_the_walk():
    cfg = run.Cell("q5.flood").cfg
    assert q5.warm_slot_programs(cfg, {}) == 0
    assert q5.warm_slot_programs(cfg, {"poll_rows": 5000}) == 0
    reached = q5.warm_slot_programs(
        cfg, {"poll_rows": 5000, "warm_windows_per_poll_row": 40}
    )
    assert reached == 1 << 18  # the first size that holds 200,000
    cell = run.Cell("q5.flood").traffic
    assert cell["warm_windows_per_poll_row"] * cell["poll_rows"] >= 1 << 21


def test_after_the_walk_a_table_compiles_nothing(honest_compiles):
    from bytewax_tpu.engine.xla import DeviceAggState

    cfg = run.Cell("q5.flood").cfg
    traffic = {"poll_rows": 20_000, "warm_windows_per_poll_row": 30}
    before = honest_compiles()
    reached = q5.warm_slot_programs(cfg, traffic)
    assert reached == 1 << 20 and honest_compiles() > before
    walked = honest_compiles()
    rows = 2 * traffic["poll_rows"]
    agg = DeviceAggState("count")
    held = agg.open_ids(np.empty(1 << 16))
    given_back = iter([9, 100, 1000, 5000, 20_000, 31_000, 700, 13_200] * 8)
    while agg.capacity < reached or len(held) < reached - 60_000:
        held = np.concatenate([held, agg.open_ids(np.empty(26_400))])
        agg.update_ids(np.resize(held, rows), np.ones(rows))
        if agg.capacity >= 1 << 19:
            n = next(given_back)
            agg.release_ids(held[:n])
            assert (agg.open_ids(np.empty(n)) == held[:n][::-1]).all()
        agg.states_of(held[:3])
    assert agg.capacity == reached
    assert honest_compiles() == walked


def test_a_program_that_keeps_every_key_is_refused_at_once(monkeypatch):
    """The parent of the PR that brought the cell has no ``let_go``:
    the flow module does not import there, and ``benchmark.run`` turns
    that into exit code 1 before it looks for the chip."""
    from bytewax_tpu.engine.window_accel import DeviceWindowAggState

    monkeypatch.delattr(DeviceWindowAggState, "let_go")
    with pytest.raises(ImportError, match="lets a key go"):
        q5._require_key_retirement()

    def no_chip_yet(chips):
        raise AssertionError("the run went on to look for the chip")

    monkeypatch.setattr(run, "device_or_fail", no_chip_yet)
    monkeypatch.delitem(sys.modules, "benchmark.flows.nexmark_q5")
    assert run.main(["--workload", "q5.flood", "--seed", "1", "--seconds", "1"]) == 1
