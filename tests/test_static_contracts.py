"""Tier-1 wrapper around the engine-contract analyzer: the shipped
tree must be clean (golden test), via both the API and the CLI entry
points (``python -m bytewax_tpu.analysis`` is what CI and operators
run)."""

import re
import subprocess
import sys
import time
from pathlib import Path

from bytewax_tpu.analysis import analyze_tree
from bytewax_tpu.analysis.contracts import KNOBS
from bytewax_tpu.analysis.diagnostics import format_diagnostics
from bytewax_tpu.analysis.rules import ALL_RULES

REPO = Path(__file__).resolve().parent.parent


def test_tree_is_clean():
    timings = {}
    t0 = time.perf_counter()
    diags, suppressed, project = analyze_tree(timings=timings)
    wall = time.perf_counter() - t0
    assert not diags, (
        "the shipped tree violates an engine contract (see "
        "docs/contracts.md):\n" + format_diagnostics(diags)
    )
    # The committed baseline is empty: nothing should be suppressed.
    assert suppressed == 0
    # Sanity: the scan actually covered the engine and the examples.
    assert "bytewax_tpu.engine.driver" in project.modules
    assert any(m.startswith("examples.") for m in project.modules)
    # Every rule really ran, and the full tree stays fast enough to
    # run on every CI round (budget well above the ~3s measured, far
    # below the ~5s ceiling the analyzer tooling targets).
    assert set(timings) == set(ALL_RULES) | {"<call-graph>"}
    assert wall < 30, f"analyzer took {wall:.1f}s on the tree"


def test_rule_registry_is_complete():
    assert set(ALL_RULES) == {
        "BTX-SEND",
        "BTX-GSYNC",
        "BTX-FRAMES",
        "BTX-FAULT",
        "BTX-SNAPSHOT",
        "BTX-DRAIN",
        "BTX-THREAD",
        "BTX-KNOB",
        "BTX-LANE",
        "BTX-RACE",
    }


def test_docs_rule_catalog_matches_registry():
    """docs/contracts.md's rule-catalog table lists exactly the
    analyzer's rule ids — a rule without a catalog entry (or a
    catalog row for a deleted rule) is doc drift, failed here."""
    text = (REPO / "docs" / "contracts.md").read_text()
    catalog = text.split("## Rule catalog", 1)[1].split("##", 1)[0]
    documented = set(
        re.findall(r"^\|\s*`(BTX-[A-Z]+)`", catalog, re.MULTILINE)
    )
    assert documented == set(ALL_RULES), (
        "docs/contracts.md rule catalog drifted from the registry: "
        f"doc-only {sorted(documented - set(ALL_RULES))}, "
        f"undocumented {sorted(set(ALL_RULES) - documented)}"
    )


def test_docs_knob_table_matches_catalog():
    """docs/configuration.md's reference table lists exactly the
    pinned KNOBS catalog (names AND defaults) — the table is
    generated from the catalog and must not drift."""
    text = (REPO / "docs" / "configuration.md").read_text()
    rows = dict(
        re.findall(
            r"^\|\s*`(BYTEWAX_TPU_[A-Z0-9_]+)`\s*\|\s*(?:`([^`|]*)`)?\s*\|",
            text,
            re.MULTILINE,
        )
    )
    assert set(rows) == set(KNOBS), (
        "docs/configuration.md knob table drifted from "
        "contracts.KNOBS: doc-only "
        f"{sorted(set(rows) - set(KNOBS))}, missing "
        f"{sorted(set(KNOBS) - set(rows))}"
    )
    for name, (default, _doc) in KNOBS.items():
        assert rows[name] == default, (
            f"{name}: doc default {rows[name]!r} != catalog "
            f"{default!r}"
        )


def test_docs_metrics_inventory_matches_registry():
    """docs/observability.md's metrics inventory lists exactly the
    Prometheus families ``_metrics.py`` registers — same
    update-both-together rule as KNOBS ↔ configuration.md: adding a
    family means adding its doc row in the same change (and vice
    versa)."""
    from prometheus_client import Counter, Gauge, Histogram

    import bytewax_tpu._metrics as _metrics

    registered = {
        m._name
        for m in vars(_metrics).values()
        if isinstance(m, (Counter, Gauge, Histogram))
    }
    registered |= {
        h._name for h in _metrics.DURATION_HISTOGRAMS.values()
    }

    text = (REPO / "docs" / "observability.md").read_text()
    inventory = text.split("## Metrics inventory", 1)[1].split(
        "\n## ", 1
    )[0]
    documented = set(
        re.findall(r"`(bytewax_[a-z0-9_]+)`", inventory)
    )
    assert documented == registered, (
        "docs/observability.md metrics inventory drifted from "
        "_metrics.py: doc-only "
        f"{sorted(documented - registered)}, undocumented "
        f"{sorted(registered - documented)}"
    )


def test_cli_exits_zero_on_shipped_tree():
    res = subprocess.run(
        [sys.executable, "-m", "bytewax_tpu.analysis"],
        capture_output=True,
        text=True,
        cwd=REPO,
        timeout=120,
    )
    assert res.returncode == 0, res.stdout + res.stderr
    assert "clean" in res.stderr


def test_cli_sarif_full_tree_smoke():
    """CI smoke (satellite of the HBM-resident-aggregate PR): the
    code-scanning upload path — ``--output sarif`` over the FULL
    shipped tree (fixtures only exercised it before) — emits one
    valid SARIF 2.1.0 document: all 10 rules in the driver inventory,
    zero results (the tree is clean), exit 0."""
    import json

    res = subprocess.run(
        [
            sys.executable,
            "-m",
            "bytewax_tpu.analysis",
            "--output",
            "sarif",
        ],
        capture_output=True,
        text=True,
        cwd=REPO,
        timeout=120,
    )
    assert res.returncode == 0, res.stdout + res.stderr
    doc = json.loads(res.stdout)
    assert doc["version"] == "2.1.0"
    assert doc["$schema"].endswith("sarif-schema-2.1.0.json")
    (run,) = doc["runs"]
    assert run["tool"]["driver"]["name"] == "bytewax_tpu.analysis"
    assert {r["id"] for r in run["tool"]["driver"]["rules"]} == set(
        ALL_RULES
    )
    assert run["results"] == []


def test_cli_exits_nonzero_on_positive_fixture():
    fixture = (
        REPO / "tests" / "analysis_fixtures" / "fixture_send_alias.py"
    )
    res = subprocess.run(
        [sys.executable, "-m", "bytewax_tpu.analysis", str(fixture)],
        capture_output=True,
        text=True,
        cwd=REPO,
        timeout=120,
    )
    assert res.returncode == 1, res.stdout + res.stderr
    assert "BTX-SEND" in res.stdout


def test_cli_exits_nonzero_on_each_new_rule_fixture():
    fixtures = REPO / "tests" / "analysis_fixtures"
    for name, rule in (
        ("fixture_drain_per_batch.py", "BTX-DRAIN"),
        ("fixture_thread_worker_send.py", "BTX-THREAD"),
        ("fixture_knob_uncataloged.py", "BTX-KNOB"),
        ("fixture_lane_uncataloged.py", "BTX-LANE"),
        ("fixture_lane_unfenced.py", "BTX-LANE"),
        ("fixture_lane_phase.py", "BTX-LANE"),
        ("fixture_race_alias.py", "BTX-RACE"),
    ):
        res = subprocess.run(
            [
                sys.executable,
                "-m",
                "bytewax_tpu.analysis",
                "--rule",
                rule,
                str(fixtures / name),
            ],
            capture_output=True,
            text=True,
            cwd=REPO,
            timeout=120,
        )
        assert res.returncode == 1, (name, res.stdout, res.stderr)
        assert rule in res.stdout, (name, res.stdout)


def test_cli_rule_filter_json_and_timings():
    """The CI surface: --rule filtering, --json output, --timings
    per-rule wall times."""
    fixture = (
        REPO
        / "tests"
        / "analysis_fixtures"
        / "fixture_knob_uncataloged.py"
    )
    res = subprocess.run(
        [
            sys.executable,
            "-m",
            "bytewax_tpu.analysis",
            "--rule",
            "BTX-KNOB",
            "--json",
            "--timings",
            str(fixture),
        ],
        capture_output=True,
        text=True,
        cwd=REPO,
        timeout=120,
    )
    import json

    assert res.returncode == 1, res.stdout + res.stderr
    records = [
        json.loads(line) for line in res.stdout.strip().splitlines()
    ]
    assert records and all(r["rule"] == "BTX-KNOB" for r in records)
    timing_lines = [
        json.loads(line)
        for line in res.stderr.splitlines()
        if line.startswith("{")
    ]
    assert timing_lines and "BTX-KNOB" in timing_lines[0]["timings_s"]
    # Only the requested rule ran.
    assert "BTX-SEND" not in timing_lines[0]["timings_s"]


def test_cli_sarif_output(tmp_path):
    """--output sarif emits one SARIF 2.1.0 document and composes
    with --rule (rule inventory reflects what ran) and
    --write-baseline (the document is still emitted alongside the
    baseline write)."""
    import json

    fixture = (
        REPO / "tests" / "analysis_fixtures" / "fixture_race_alias.py"
    )
    res = subprocess.run(
        [
            sys.executable,
            "-m",
            "bytewax_tpu.analysis",
            "--rule",
            "BTX-RACE",
            "--output",
            "sarif",
            str(fixture),
        ],
        capture_output=True,
        text=True,
        cwd=REPO,
        timeout=120,
    )
    assert res.returncode == 1, res.stdout + res.stderr
    doc = json.loads(res.stdout)
    assert doc["version"] == "2.1.0"
    run = doc["runs"][0]
    assert run["tool"]["driver"]["name"] == "bytewax_tpu.analysis"
    # The rule inventory is what RAN, not what fired.
    assert [r["id"] for r in run["tool"]["driver"]["rules"]] == [
        "BTX-RACE"
    ]
    (result,) = run["results"]
    assert result["ruleId"] == "BTX-RACE"
    assert result["level"] == "error"
    loc = result["locations"][0]["physicalLocation"]
    assert loc["artifactLocation"]["uri"].endswith(
        "fixture_race_alias.py"
    )
    assert loc["region"]["startLine"] > 0
    # --write-baseline still emits the document (and exits 0).
    baseline = tmp_path / "BASELINE"
    res2 = subprocess.run(
        [
            sys.executable,
            "-m",
            "bytewax_tpu.analysis",
            "--rule",
            "BTX-RACE",
            "--output",
            "sarif",
            "--write-baseline",
            "--baseline",
            str(baseline),
            str(fixture),
        ],
        capture_output=True,
        text=True,
        cwd=REPO,
        timeout=120,
    )
    assert res2.returncode == 0, res2.stdout + res2.stderr
    doc2 = json.loads(res2.stdout)
    assert len(doc2["runs"][0]["results"]) == 1
    assert baseline.exists()
