"""The 1BRC deployment through the itemized front door, small, on the
CPU backend: the benchmark's flow (``FileSource`` line items, a Python
parse a row, string keys promoted to the device fold) through
``cli_main`` against its plain reference, against the columnar door
and against the host tier; what ``off_items`` and the controls say;
a line that does not parse; and the door's spans and counters."""

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

from benchmark.flows import brc, brc_items  # noqa: E402

ROWS = 150_000
DOOR_PHASES = ("read", "item_ops", "promote")


@pytest.fixture(scope="module")
def cfg():
    pytest.importorskip("bytewax_tpu.native")
    with open(os.path.join(REPO, "benchmark", "configs", "brc-1b-items.json")) as f:
        cfg = json.load(f)
    cfg["shapes"]["rows_per_job"] = ROWS
    return cfg


@pytest.fixture(scope="module")
def datas(cfg, tmp_path_factory):
    """The seeded file and its reference columns, a seed."""
    made = {}

    def data(seed):
        if seed not in made:
            workdir = str(tmp_path_factory.mktemp(f"brc{len(made)}"))
            made[seed] = brc_items.make_data(cfg, {}, seed, workdir)
        return made[seed]

    return data


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


def _job(monkeypatch, cfg, data, shard="0", accel="1", module=brc_items, at_write=None):
    """One job of a benchmark flow module's flow; what this cell's
    comparison takes."""
    monkeypatch.setenv("BYTEWAX_TPU_ACCEL", accel)
    monkeypatch.setenv("BYTEWAX_TPU_SHARD", shard)
    out = []
    flow = module.build_flow(cfg, data, None, _Sink(out, at_write))
    assert cli_main(flow) is None
    return brc_items.result_arrays(cfg, [brc_items.pack(out)])


def _failed(cfg, numbers):
    return sorted(k for k, v in numbers.items() if v > cfg["limits"][k])


class _Gained:
    """What the program's counters and phase totals gain from here."""

    def __init__(self):
        self.counters0 = dict(flight.RECORDER.counters)
        self.totals0 = dict(flight.RECORDER.phase_totals)

    def count(self, name):
        return flight.RECORDER.counters.get(name, 0) - self.counters0.get(name, 0)

    def seconds(self, phase):
        """Of a phase on every lane (``promote`` + ``device/promote``)."""
        return sum(
            s - self.totals0.get(p, 0.0)
            for p, s in flight.RECORDER.phase_totals.items()
            if p.rpartition("/")[2] == phase
        )


@pytest.mark.parametrize("shard", ["0", "auto"])
@pytest.mark.parametrize("seed", [7, 2147483659])
def test_flow_matches_its_reference(monkeypatch, cfg, datas, seed, shard):
    """Every limit of the configuration held, ``off_items`` among
    them, on one device (the cell's placement) and on the mesh."""
    data = datas(seed)
    got = _job(monkeypatch, cfg, data, shard)
    numbers = brc_items.compare(cfg, got, brc.reference(cfg, data))
    assert set(numbers) | {"off_device"} == set(cfg["limits"])
    assert not _failed(cfg, numbers), numbers
    assert numbers["off_items"] == 0
    assert len(got["names"]) == len(set(got["names"])) == cfg["shapes"]["stations"]
    assert int(got["count"].sum()) == ROWS


def test_both_doors_give_the_same_answers(monkeypatch, cfg, datas):
    """The same file through ``FileSource`` items and through
    ``BrcFileSource`` columns: stations and counts to the last bit,
    extrema to one float32 step (a reading is ``float(text)`` rounded
    once on one side, deci-degrees times a float32 tenth on the
    other), means within ``mean_err``."""
    data = datas(7)
    items = _job(monkeypatch, cfg, data)
    columns = _job(monkeypatch, cfg, data, module=brc)
    assert items["names"].tolist() == columns["names"].tolist()
    assert items["count"].tobytes() == columns["count"].tobytes()
    ulp = float(np.spacing(np.float32(99.9)))
    for name in ("min", "max"):
        assert np.abs(items[name] - columns[name]).max() <= ulp, name
    assert np.abs(items["mean"] - columns["mean"]).max() <= cfg["limits"]["mean_err"]
    want = brc.reference(cfg, data)
    assert brc_items.off_items(cfg, items, want) == 0
    assert brc_items.off_items(cfg, columns, want) == 1


def test_host_tier_agrees(monkeypatch, cfg, datas):
    """``BYTEWAX_TPU_ACCEL=0``: the same flow on the host tier (the
    tests' oracle) meets the same limits, and the device tier's
    answers lie within them of it."""
    data = datas(7)
    host = _job(monkeypatch, cfg, data, accel="0")
    device = _job(monkeypatch, cfg, data)
    assert not _failed(cfg, brc_items.compare(cfg, host, brc.reference(cfg, data)))
    assert host["names"].tolist() == device["names"].tolist()
    assert host["count"].tolist() == device["count"].tolist()
    for name in ("min", "max"):
        assert np.abs(host[name] - device[name]).max() <= cfg["limits"]["extrema_err"]
    assert np.abs(host["mean"] - device["mean"]).max() <= cfg["limits"]["mean_err"]


@pytest.mark.parametrize(
    "which, by",
    [
        ("bfloat16", ["extrema_err", "mean_err"]),
        ("row_twice", ["count_wrong", "rows_unanswered"]),
        ("columnar_door", ["off_items"]),
    ],
)
def test_every_control_fails_by_its_own_number_alone(monkeypatch, cfg, datas, which, by):
    monkeypatch.setenv("BYTEWAX_TPU_SHARD", "0")
    data = datas(7)
    assert which in brc_items.CONTROLS
    got = brc_items.control_results(cfg, data, which)
    assert _failed(cfg, brc_items.compare(cfg, got, brc.reference(cfg, data))) == by


def test_a_result_without_a_sample_is_off_items(cfg, datas):
    data = datas(7)
    want = brc.reference(cfg, data)
    assert brc_items.off_items(cfg, brc.control_results(cfg, data, "row_twice"), want) == 1


@pytest.mark.parametrize("at", [0, ROWS // 2, ROWS - 1])
def test_a_line_that_does_not_parse_fails_the_job(monkeypatch, tmp_path, cfg, datas, at):
    """``float`` raises in the user's mapper: the job fails with the
    step's name, whichever delivery holds the line, and the keyed
    step is not demoted to hide it."""
    data = datas(7)
    with open(data["path"], encoding="utf-8") as f:
        lines = f.readlines()
    lines[at] = "Hamburg;12.x\n"
    bad = dict(data, path=str(tmp_path / "bad.txt"))
    with open(bad["path"], "w", encoding="utf-8") as f:
        f.writelines(lines)
    gained = _Gained()
    with pytest.raises(Exception) as raised:
        _job(monkeypatch, cfg, bad)
    text = "".join(
        str(e)
        for e in (raised.value, raised.value.__cause__, *getattr(raised.value, "__notes__", ()))
    )
    assert "bench_brc_items.parse" in text and "12.x" in text, text
    assert gained.count("demotion_count") == 0


@pytest.mark.parametrize("shard", ["0", "auto"])
@pytest.mark.parametrize("depth", ["1", "2"])
def test_the_doors_spans_and_counters(monkeypatch, cfg, datas, depth, shard):
    """One ``read``, one ``item_ops`` and one ``promote`` a delivery,
    never a row; each carries the job's rows; the counters add up.
    At depth 1 ``promote`` is the main thread's, at 2 the lane's."""
    monkeypatch.setenv("BYTEWAX_TPU_PIPELINE_DEPTH", depth)
    gained = _Gained()
    _job(monkeypatch, cfg, datas(7), shard)
    deliveries = gained.count("ingest_deliveries_itemized")
    assert gained.count("ingest_rows_itemized") == ROWS
    assert gained.count("ingest_rows_columnar") == 0
    # 1000-line polls gathered to the target: 66,000 rows a delivery.
    assert deliveries == -(-ROWS // 66_000)
    assert gained.count("ingest_coalesced_polls") == ROWS // 1000 - deliveries + 1
    for phase in DOOR_PHASES:
        assert gained.count(phase + "_spans") == deliveries, phase
        assert gained.count(phase + "_rows") == ROWS, phase
        assert gained.seconds(phase) > 0, phase
        assert flight._BUCKET_OF[phase] == "host"
        assert phase in flight.TRACED_PHASES
    assert gained.count("items_promoted_rows") == ROWS
    assert gained.count("items_fallback_rows") == 0
    assert 3 * deliveries < ROWS / 100
    # `promote` is cut out of `prep`: the itemized door books none
    # under that name before the pad.
    assert gained.count("prep_rows") == 0


def test_rows_the_fast_pass_cannot_take_are_counted_as_fallback(monkeypatch, cfg, datas):
    """Without the native ``kv_encode`` every row takes the per-item
    path: still the device tier, still right, counted apart."""
    from bytewax_tpu.engine import xla

    monkeypatch.setattr(xla._AggTable, "update_items", lambda self, items: None)
    data = datas(7)
    gained = _Gained()
    got = _job(monkeypatch, cfg, data)
    assert not _failed(cfg, brc_items.compare(cfg, got, brc.reference(cfg, data)))
    assert gained.count("items_fallback_rows") == ROWS
    assert gained.count("items_promoted_rows") == 0
    assert gained.count("items_promoted_rows") + gained.count(
        "items_fallback_rows"
    ) == gained.count("ingest_rows_itemized")


def test_a_columnar_poll_opens_no_door_span(monkeypatch, cfg, datas):
    """``BrcFileSource``'s polls bring columns: no ``read`` (the
    source's own ``parse`` times them), no ``item_ops``, no
    ``promote``, and ``parse`` still comes out of ``ingest``."""
    gained = _Gained()
    _job(monkeypatch, cfg, datas(7), module=brc)
    assert gained.count("ingest_rows_columnar") == ROWS
    assert gained.count("ingest_deliveries_itemized") == 0
    for phase in DOOR_PHASES:
        assert gained.count(phase + "_spans") == 0, phase
        assert gained.seconds(phase) == 0, phase
    assert gained.seconds("parse") > gained.seconds("ingest") >= 0


def test_graph_keeps_a_step_fed_by_items_on_the_device(monkeypatch, tmp_path, cfg, datas):
    """``GET /graph`` at the sink write: the keyed step that items
    feed reads ``tier: device``, not the host fallback."""
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    monkeypatch.setenv("BYTEWAX_DATAFLOW_API_ENABLED", "1")
    monkeypatch.setenv("BYTEWAX_DATAFLOW_API_PORT", str(port))
    monkeypatch.chdir(tmp_path)  # the API plane dumps dataflow.json
    seen = {}

    def read_plane():
        for path in ("/graph", "/status"):
            with urllib.request.urlopen(f"http://127.0.0.1:{port}{path}", timeout=10) as r:
                seen[path] = json.loads(r.read())

    _job(monkeypatch, cfg, datas(7), at_write=read_plane)
    tiers = {n["step_id"]: n["tier"] for n in seen["/graph"]["steps"]}
    stats = [sid for sid in tiers if ".stats." in sid and tiers[sid] == "device"]
    assert stats, tiers
    counters = seen["/status"]["recorder"]["counters"]
    for name in ("ingest_deliveries_itemized", "items_promoted_rows", "read_spans"):
        assert counters.get(name, 0) > 0, name
