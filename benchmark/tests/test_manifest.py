"""``BENCHMARK.json`` as the driver reads it: names, layers, units,
files, and that every name leads to its file."""

import json
import os
import re

from benchmark import run

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
MANIFEST = run.load_json(run.ROOT, "BENCHMARK.json")


def test_top_level_keys():
    assert set(MANIFEST) == {
        "command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer",
    }
    assert 1 <= MANIFEST["run_seconds"] <= 51
    assert len(json.dumps(MANIFEST)) < 64 * 1024


def test_every_name_and_layer_is_a_name():
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names += [entry["name"] for entry in MANIFEST[group]]
    names += [w["config"] for w in MANIFEST["workloads"]]
    names += [w["traffic"] for w in MANIFEST["workloads"]]
    names += [m["layer"] for m in MANIFEST["per_layer"]]
    names += [k for c in MANIFEST["configs"] for k in c["reduced"]]
    for name in names:
        assert NAME.match(name), name
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        got = [entry["name"] for entry in MANIFEST[group]]
        assert len(got) == len(set(got))
    metrics = [m["name"] for m in MANIFEST["end_to_end"] + MANIFEST["per_layer"]]
    assert len(metrics) == len(set(metrics))


def test_units_and_fixed_words():
    for m in MANIFEST["end_to_end"] + MANIFEST["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in (
            "device_trace", "program_span", "program_counter", "host_clock",
        )
    for m in MANIFEST["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in MANIFEST["per_layer"]:
        assert set(m) <= {
            "name", "unit", "better", "source", "layer", "moves", "workloads",
        }
        assert m["moves"] in [e["name"] for e in MANIFEST["end_to_end"]]
    for entry in MANIFEST["configs"] + MANIFEST["workloads"]:
        assert 1 <= len(entry["why"]) <= 200 and "\n" not in entry["why"]
    for c in MANIFEST["configs"]:
        assert 1 <= len(c["source"]) <= 200


def test_every_name_leads_to_its_file():
    for w in MANIFEST["workloads"]:
        cell = run.Cell(w["name"])
        assert cell.cfg["name"] == w["config"]
        assert cell.traffic["chips"] == w["chips"]
        assert cell.traffic["why"] == w["why"]
        e2e = [m["name"] for m in cell.metrics("end_to_end")]
        assert "setup_s" in e2e and len(e2e) >= 2
        assert cell.metrics("per_layer")
        for m in cell.metrics("per_layer"):
            assert m["moves"] in e2e
    for c in MANIFEST["configs"]:
        cfg = run.load_json(run.ROOT, c["file"])
        assert cfg["reduced"] == c["reduced"] and cfg["source"] == c["source"]
        assert set(cfg["limits"]) and cfg["assumed"] and cfg["guarantees"]
    for m in MANIFEST["end_to_end"] + MANIFEST["per_layer"]:
        assert os.path.exists(
            os.path.join(run.HERE, "metrics", m["name"] + ".py")
        ), m["name"]


def test_chips():
    four = [w for w in MANIFEST["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(MANIFEST["workloads"]) // 2)
    assert all(w["chips"] in (1, 4) for w in MANIFEST["workloads"])
