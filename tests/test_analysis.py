"""The analyzer's own machinery: positive fixtures for every rule,
the alias shapes the old regex scan provably missed, inline-waiver
and baseline-file round-trips."""

import re
from pathlib import Path

import pytest

from bytewax_tpu.analysis import analyze_paths
from bytewax_tpu.analysis.diagnostics import (
    Diagnostic,
    Waivers,
    apply_baseline,
    load_baseline,
    write_baseline,
)

FIXTURES = Path(__file__).resolve().parent / "analysis_fixtures"
REPO = FIXTURES.parent.parent


def _diags(name, rules=None):
    diags, _suppressed, _project = analyze_paths(
        [FIXTURES / name],
        rule_ids=rules,
        rel_root=REPO,
    )
    return diags


# -- one positive fixture per rule ------------------------------------------


def test_send_rule_flags_alias_smuggled_raw_send():
    diags = _diags("fixture_send_alias.py", ["BTX-SEND"])
    assert [d.rule for d in diags] == ["BTX-SEND"]
    assert "raw cluster send" in diags[0].message
    # The shape is provably invisible to the regex scan this analyzer
    # replaced: the old strict matcher required a literal `comm.`
    # receiver on the call line.
    old_regex = re.compile(
        r"(?:\bcomm\s*\.\s*(?:send|broadcast)\s*\()"
        r"|(?:self\s*\.\s*comm\s*\.\s*(?:send|broadcast)\s*\()"
    )
    source = (FIXTURES / "fixture_send_alias.py").read_text()
    assert not old_regex.search(source)


def test_gsync_rule_flags_per_batch_reachability():
    diags = _diags("fixture_gsync_per_batch.py", ["BTX-GSYNC"])
    reach = [d for d in diags if "per-batch path" in d.message]
    assert reach, diags
    assert "EagerExchange.process" in reach[0].message
    assert "_sync_now" in reach[0].message  # witness chain
    # Invisible to the old regex: no line spells `global_sync(` —
    # the primitive hides behind a bound-method alias.
    source = (FIXTURES / "fixture_gsync_per_batch.py").read_text()
    body = "\n".join(
        line
        for line in source.splitlines()
        if not line.lstrip().startswith(("#", '"', "'"))
    )
    assert not re.search(r"global_sync\s*\(", body)


def test_frames_rule_flags_rogue_kind():
    diags = _diags("fixture_frames_rogue.py", ["BTX-FRAMES"])
    msgs = "\n".join(d.message for d in diags)
    assert "rogue_frame" in msgs
    assert any("inventory drifted" in d.message for d in diags)
    assert any("sent in" in d.message for d in diags)


def test_fault_rule_flags_unknown_site_and_late_fire():
    diags = _diags("fixture_fault_site.py", ["BTX-FAULT"])
    msgs = "\n".join(d.message for d in diags)
    assert "device_dispatchx" in msgs
    assert "before firing" in msgs
    # The reachability component: a pre-fire call that only REACHES a
    # mutator through the call graph (the pipeline indirection shape)
    # is flagged with its witness chain.
    assert "_spin_helper" in msgs
    assert "_process_device" in msgs


def test_snapshot_rule_flags_missing_demotion_method():
    diags = _diags("fixture_snapshot_missing.py", ["BTX-SNAPSHOT"])
    assert [d.rule for d in diags] == ["BTX-SNAPSHOT"]
    assert "OrphanDeviceState" in diags[0].message


def test_snapshot_rule_flags_residency_pairing():
    diags = _diags("fixture_residency_missing.py", ["BTX-SNAPSHOT"])
    msgs = "\n".join(d.message for d in diags)
    # extract_keys with no inject_keys: stranded evictions.
    assert "HalfResidentState" in msgs
    assert "inject_keys" in msgs
    # The collective tier must implement NEITHER half.
    assert "EvictingGlobalState" in msgs
    assert any(
        "global_exchange" in d.message and "residency" in d.message
        for d in diags
    )


def test_snapshot_rule_flags_infer_broadcast_state():
    # The inference subsystem's broadcast-params state is ordinary
    # device-tier state to the analyzer: reachable from a make_state
    # factory, it must drain its params row via demotion_snapshots.
    diags = _diags("fixture_infer_snapshot.py", ["BTX-SNAPSHOT"])
    assert [d.rule for d in diags] == ["BTX-SNAPSHOT"]
    assert "BroadcastParamsState" in diags[0].message
    assert "EagerInferSpec.make_state" in diags[0].message
    assert "demotion_snapshots" in diags[0].message


def test_gsync_rule_flags_per_batch_swap_agreement():
    # A params-swap vote belongs in the epoch-close "fstat" round; an
    # infer runtime entering a sync round from its per-batch `update`
    # (behind a bound-method alias) is the same deadlock shape as any
    # smuggled collective.
    diags = _diags("fixture_infer_gsync.py", ["BTX-GSYNC"])
    reach = [d for d in diags if "per-batch path" in d.message]
    assert reach, diags
    assert "EagerSwapInfer.update" in reach[0].message
    assert "_agree_swap" in reach[0].message  # witness chain
    source = (FIXTURES / "fixture_infer_gsync.py").read_text()
    body = "\n".join(
        line
        for line in source.splitlines()
        if not line.lstrip().startswith(("#", '"', "'"))
    )
    assert not re.search(r"global_sync\s*\(", body)


def test_thread_rule_flags_worker_lane_alias_send():
    diags = _diags("fixture_thread_worker_send.py", ["BTX-THREAD"])
    assert [d.rule for d in diags] == ["BTX-THREAD"]
    msg = diags[0].message
    # The callable was traced INTO the thread submission (a nested
    # def is the worker-lane root)...
    assert "LeakyStep.process.<locals>.task" in msg
    # ...and the send surface was reached through a bound-method
    # alias — no line in the fixture spells `comm.send(...)`.
    assert "alias of a raw cluster send" in msg
    source = (FIXTURES / "fixture_thread_worker_send.py").read_text()
    assert not re.search(r"comm\s*\.\s*send\s*\(", source)
    # The diagnostic lands at the submit site, where a deliberate
    # exception would be waived.
    assert "self._pipe.push(task, finalize)" in source.splitlines()[
        diags[0].lineno - 1
    ]


def test_drain_rule_flags_per_batch_eviction_and_flush():
    diags = _diags("fixture_drain_per_batch.py", ["BTX-DRAIN"])
    msgs = "\n".join(d.message for d in diags)
    # Eviction reachable from a per-batch path, with a witness chain.
    assert "evict_to_budget" in msgs
    assert "EagerStep.process -> EagerStep._maybe_trim" in msgs
    # Raw pipeline drain on a per-batch path (receiver-typed seed).
    assert "DevicePipeline.flush" in msgs
    # Flush-before-sync: the gsync primitive hides behind a
    # bound-method alias and still gets flagged.
    assert "without first flushing" in msgs
    assert {d.rule for d in diags} == {"BTX-DRAIN"}


def test_knob_rule_flags_uncataloged_and_computed_reads():
    diags = _diags("fixture_knob_uncataloged.py", ["BTX-KNOB"])
    msgs = "\n".join(d.message for d in diags)
    assert "uncataloged knob BYTEWAX_TPU_TURBO" in msgs
    assert "computed BYTEWAX_TPU_* knob name" in msgs
    # Subscript loads are reads too.
    assert "BYTEWAX_TPU_SECRET_MODE" in msgs
    # A knob name bound to a variable first cannot slip the catalog.
    assert "BYTEWAX_TPU_STEALTH_MODE" in msgs
    assert len(diags) == 4


def test_lane_rule_flags_uncataloged_construction():
    diags = _diags("fixture_lane_uncataloged.py", ["BTX-LANE"])
    # The module drains its lane and uses a cataloged phase — the ONE
    # finding is catalog closure.
    assert [d.rule for d in diags] == ["BTX-LANE"]
    assert "un-cataloged lane" in diags[0].message
    assert "SneakyStep.__init__" in diags[0].message
    # The diagnostic lands on the construction line.
    source = (FIXTURES / "fixture_lane_uncataloged.py").read_text()
    assert "DevicePipeline(" in source.splitlines()[diags[0].lineno - 1]


def test_lane_rule_flags_unfenced_module():
    diags = _diags("fixture_lane_unfenced.py", ["BTX-LANE"])
    msgs = "\n".join(d.message for d in diags)
    # The module flushes but never tears down: the un-fenced finding
    # names exactly the missing half.
    unfenced = [d for d in diags if "un-fenced lane" in d.message]
    assert unfenced, diags
    assert ".shutdown()/.drop_pending()" in unfenced[0].message
    assert ".flush()" not in unfenced[0].message
    # (The un-cataloged finding fires too — the fixture lane is not
    # in contracts.LANES either.)
    assert "un-cataloged lane" in msgs


def test_lane_rule_flags_unknown_ledger_phase():
    diags = _diags("fixture_lane_phase.py", ["BTX-LANE"])
    phase = [d for d in diags if "unknown ledger phase" in d.message]
    assert phase, diags
    assert "'turbo_lane'" in phase[0].message
    # The message routes the reader to the observable consequence.
    assert "ledger bucket" in phase[0].message


def test_race_rule_flags_alias_smuggled_write():
    diags = _diags("fixture_race_alias.py", ["BTX-RACE"])
    assert [d.rule for d in diags] == ["BTX-RACE"]
    msg = diags[0].message
    assert "RacyStep._tally" in msg
    # DUAL witness chains: the worker path resolves the bound-method
    # alias into the write...
    assert "RacyStep.process.<locals>.task -> RacyStep._bump" in msg
    # ...and the main path shows the per-batch access.
    assert "(via RacyStep.process" in msg
    # No line inside the task spells a self-attribute store — only
    # alias resolution can see the worker-side write.
    source = (FIXTURES / "fixture_race_alias.py").read_text()
    task = source[source.index("def task") : source.index("def finalize")]
    assert "self._tally" not in task
    # The diagnostic lands at the worker-side write site.
    assert "def _bump" in source.splitlines()[diags[0].lineno - 1]


def test_new_rule_waiver_round_trip(tmp_path):
    """Each new rule's finding is suppressed by an inline waiver on
    the flagged line — the same escape hatch the engine's deliberate
    exceptions use — and reappears when the waiver is removed."""
    cases = {
        "fixture_thread_worker_send.py": "BTX-THREAD",
        "fixture_drain_per_batch.py": "BTX-DRAIN",
        "fixture_knob_uncataloged.py": "BTX-KNOB",
        "fixture_lane_uncataloged.py": "BTX-LANE",
        "fixture_race_alias.py": "BTX-RACE",
        "fixture_infer_snapshot.py": "BTX-SNAPSHOT",
        "fixture_infer_gsync.py": "BTX-GSYNC",
    }
    for name, rule in cases.items():
        diags = _diags(name, [rule])
        assert diags, name
        lines = (FIXTURES / name).read_text().splitlines()
        for d in diags:
            lines[d.lineno - 1] += f"  # bytewax: allow[{rule}]"
        waived = tmp_path / name
        waived.write_text("\n".join(lines) + "\n")
        after, _s, _p = analyze_paths(
            [waived], rule_ids=[rule], rel_root=tmp_path
        )
        assert not after, (name, after)


# -- waivers ----------------------------------------------------------------


def test_inline_waiver_suppresses_finding():
    diags = _diags("fixture_waived.py")
    assert not diags


def test_waiver_parsing_is_comment_token_based():
    # A '#' inside a string literal neither creates a waiver nor
    # truncates the line (the old _strip_comments bug hid real calls
    # this way).
    w = Waivers.parse(
        'x = "# bytewax: allow[BTX-SEND]"\n'
        "y = 1  # bytewax: allow[BTX-FRAMES]\n"
    )
    assert not w.waives(1, "BTX-SEND")
    assert w.waives(2, "BTX-FRAMES")
    # Multi-id waivers and the line-above form.
    w2 = Waivers.parse("# bytewax: allow[BTX-A,BTX-B]\ncall()\n")
    assert w2.waives(2, "BTX-A") and w2.waives(2, "BTX-B")
    assert not w2.waives(2, "BTX-C")


def test_string_literal_hash_does_not_hide_calls():
    # fixture_waived.tagged_flush sends a frame whose kind comes from
    # a string containing '#'; with waivers stripped the analyzer
    # must still SEE the call (the old line-split comment stripping
    # dropped everything after the '#', hiding it).
    source = (FIXTURES / "fixture_waived.py").read_text()
    unwaived = source.replace("# bytewax: allow", "# waiver removed ")
    probe = FIXTURES / "_probe_unwaived.py"
    probe.write_text(unwaived)
    try:
        diags, _s, _p = analyze_paths(
            [probe], rule_ids=["BTX-SEND"], rel_root=REPO
        )
        assert len(diags) == 2  # both sends, incl. the '#'-string one
    finally:
        probe.unlink()


# -- baseline ---------------------------------------------------------------


def test_baseline_round_trip(tmp_path):
    diags = _diags("fixture_send_alias.py", ["BTX-SEND"])
    assert diags
    baseline = tmp_path / "BASELINE"
    write_baseline(baseline, diags)
    loaded = load_baseline(baseline)
    remaining, suppressed = apply_baseline(diags, loaded)
    assert not remaining
    assert suppressed == len(diags)
    # And through the public API path.
    diags2, suppressed2, _p = analyze_paths(
        [FIXTURES / "fixture_send_alias.py"],
        rule_ids=["BTX-SEND"],
        baseline=baseline,
        rel_root=REPO,
    )
    assert not diags2
    assert suppressed2 == len(diags)


def test_baseline_is_line_number_free(tmp_path):
    d1 = Diagnostic("BTX-X", "a.py", 10, "msg")
    d2 = Diagnostic("BTX-X", "a.py", 99, "msg")
    baseline = tmp_path / "BASELINE"
    write_baseline(baseline, [d1])
    remaining, suppressed = apply_baseline(
        [d2], load_baseline(baseline)
    )
    assert not remaining and suppressed == 1


def test_missing_baseline_is_empty(tmp_path):
    assert load_baseline(tmp_path / "nope") == set()
    assert load_baseline(None) == set()


def test_unknown_rule_id_raises():
    with pytest.raises(KeyError):
        _diags("fixture_send_alias.py", ["BTX-NOPE"])
