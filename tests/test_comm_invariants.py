"""Static enforcement of the cluster comm contract (CLAUDE.md): all
data sends ride ``ship_deliver``/``ship_route`` and all control-plane
sync rides ``global_sync`` — no module outside ``engine/comm.py`` and
``engine/driver.py`` may touch the raw send primitives, or the epoch
barrier's count-matched quiescence check silently breaks.

Since the analyzer PR this file no longer greps: the checks run on
:mod:`bytewax_tpu.analysis` — an AST resolver + call graph that sees
through aliases, ``from``-imports, and method receivers (the old
regex scan missed ``c = self.comm; c.send(...)``, and its
``_strip_comments`` helper truncated any line with a ``#`` inside a
string literal, hiding real calls).  What stays here is the PINNING:
the inventories live in ``bytewax_tpu/analysis/contracts.py`` as data
tables the rules consume, and this test hardcodes their expected
values so editing contracts.py alone cannot silently relax a
contract.  Extending an inventory requires updating the table AND
this test AND re-checking the contract note in CLAUDE.md +
docs/contracts.md.
"""

import functools

from bytewax_tpu.analysis import contracts
from bytewax_tpu.analysis.api import default_roots, discover_files
from bytewax_tpu.analysis.diagnostics import (
    Waivers,
    apply_waivers,
    format_diagnostics,
)
from bytewax_tpu.analysis.resolver import Project
from bytewax_tpu.analysis.rules import run_rules


@functools.lru_cache(maxsize=1)
def _project():
    # The tree is immutable within a test run; build the call graph
    # once for all tests in this file.
    pkg_dir, examples = default_roots()
    return Project.load(
        discover_files(pkg_dir, examples), pkg_dir.parent
    )


def _check(rule_ids):
    """Run rules with the documented inline-waiver escape hatch
    honored, so this file and `python -m bytewax_tpu.analysis` agree
    on what the contract is."""
    project = _project()
    diags = run_rules(project, rule_ids)
    waivers = {
        mod.rel: Waivers.parse(mod.source)
        for mod in project.modules.values()
    }
    return apply_waivers(diags, waivers)


def test_no_raw_sends_outside_comm_and_driver():
    diags = _check(["BTX-SEND"])
    assert not diags, (
        "raw cluster-send primitives used outside the sanctioned "
        "modules (route data through ship_deliver/ship_route and "
        "control metadata through driver.global_sync):\n"
        + format_diagnostics(diags)
    )


def test_collectives_only_at_ordered_points():
    diags = _check(["BTX-GSYNC"])
    assert not diags, (
        "collective sync reachable outside the globally-ordered "
        "points (run startup, epoch close / the EOF ladder):\n"
        + format_diagnostics(diags)
    )


def test_control_frame_inventory_is_pinned():
    # The contract values, hardcoded: a drive-by edit to the
    # contracts tables cannot silently add a frame kind.  Adding one
    # REQUIRES updating contracts.CONTROL_FRAMES, this set, and the
    # contract note in CLAUDE.md: data frames must stay counted
    # (``deliver``/``route``) and everything else must be legal at
    # the protocol point it arrives at.  (The robustness PR
    # deliberately added no frame kinds: supervised-restart signaling
    # rides socket closes plus per-frame generation fencing.  The
    # residency PR added none either: eviction/restore/spill are
    # process-local tier movement — nothing rides the mesh.  The
    # live-rescale PR added none either, deliberately: the
    # membership-change proposal is a field in the EXISTING
    # epoch-close "fstat" gsync payload (like the stop vote), the
    # join/retire handshake is the existing generation-fenced mesh
    # handshake re-entered at run startup, and keyed state moves
    # through the shared recovery store — never the wire.)
    assert contracts.CONTROL_FRAMES == {
        "deliver",
        "route",
        "report_msg",
        "hold",
        "eof_step",
        "close_epoch",
        "gsync",
        "abort",
    }
    # And the driver's _handle_ctrl AST + every literal frame it
    # sends agree with that inventory.
    diags = _check(["BTX-FRAMES"])
    assert not diags, format_diagnostics(diags)


def test_fault_site_inventory_is_pinned():
    # The residency PR added exactly one site: residency_restore, the
    # restore-before-dispatch path of the tiered key-state manager
    # (engine/residency.py).  It is a retryable device-path site
    # (DeviceFault, fired before any state mutates), pinned in
    # FAULT_DEVICE_SITES alongside device_dispatch.
    # The rescale PR added exactly one more: rescale_migrate, fired
    # inside the rescale-on-resume store transaction before any row
    # moves (engine/recovery_store.py), so a mid-migration crash
    # rolls back whole and retries under the supervisor.  It is NOT a
    # device site (a plain restartable InjectedFault, not a
    # DeviceFault), and the rescale mapping agreement added no
    # control-frame kinds — it rides existing startup gsync rounds.
    # The connector-edge resilience PR added two: source_poll and
    # sink_write, fired in the driver immediately before a source
    # partition's next_batch / a sink partition's write_batch (before
    # any offset advances or byte lands — retry-safe by
    # construction).  kind=error at them raises the typed
    # TransientSourceError/TransientSinkError absorbed by the I/O
    # retry ladder; they are NOT device sites, and the whole layer is
    # process-local (no new frame kinds, no send-surface growth —
    # the inventories below are byte-identical).
    # The async-checkpoint PR added exactly one: snapshot_seal,
    # fired at the epoch-close drain point after the consistent
    # delta is sealed in memory but before it is handed to anything
    # durable (inline write or the committer lane) — an injected
    # crash there proves the crash-between-seal-and-commit window
    # replays exactly the sealed epoch.  It is NOT a device site,
    # and the whole checkpoint tier is process-local (no new frame
    # kinds, no send-surface growth).
    # The inference PR added exactly one: params_swap, fired at the
    # agreed epoch close BEFORE any runtime installs the pending
    # params and BEFORE the module-level target is consumed — an
    # injected crash there proves the swap lands exactly once across
    # a supervised restart (the target survives like the stop flag).
    assert contracts.FAULT_SITES == (
        "comm.send",
        "comm.recv",
        "device_dispatch",
        "residency_restore",
        "source_poll",
        "sink_write",
        "snapshot.write",
        "snapshot.commit",
        "snapshot_seal",
        "rescale_migrate",
        "params_swap",
        "barrier",
    )
    assert contracts.FAULT_DEVICE_SITES == {
        "device_dispatch",
        "residency_restore",
    }
    # Injector originates no traffic; every fire() site is pinned;
    # the retryable device-path sites fire before any device-state
    # mutation.
    diags = _check(["BTX-FAULT"])
    assert not diags, format_diagnostics(diags)


def test_send_surface_allowlist_is_pinned():
    assert contracts.SEND_ALLOWED == {
        "comm_construct": {
            "bytewax_tpu.engine.comm",
            "bytewax_tpu.engine.driver",
        },
        "raw_send": {
            "bytewax_tpu.engine.comm",
            "bytewax_tpu.engine.driver",
        },
        "ship": {"bytewax_tpu.engine.driver"},
    }
    # The columnar-exchange PR grew the ship surface by exactly one
    # method: ship_flush, the route-accumulator drain (frames ship
    # and count ONLY there or in the direct ship paths) — and made
    # the wire codec module part of the send surface.  The
    # overlapped-collectives PR widened the codec's callers by
    # exactly one module: engine/sharded_state.py, whose quantized
    # partial-aggregate frames (encode_agg/decode_agg) ride the
    # EXISTING gsync payload — no new frame kinds, no new ship
    # methods, nothing uncounted on the mesh.
    assert contracts.SHIP_METHODS == {
        "ship_deliver",
        "ship_route",
        "ship_flush",
    }
    assert contracts.WIRE_MODULE == "bytewax_tpu.engine.wire"
    assert contracts.WIRE_ALLOWED_MODULES == {
        "bytewax_tpu.engine.comm",
        "bytewax_tpu.engine.driver",
        "bytewax_tpu.engine.sharded_state",
        "bytewax_tpu.engine.wire",
    }
    assert contracts.GSYNC_CALLER_MODULES == {
        "bytewax_tpu.engine.driver",
        "bytewax_tpu.engine.sharded_state",
    }


def test_allowlist_is_not_stale():
    # The contract checks above are only meaningful while their
    # allowed call sites actually exist; fail loudly if a refactor
    # moves them.
    project = _project()
    driver = "bytewax_tpu.engine.driver"
    for fn in ("ship_deliver", "ship_route", "ship_flush", "global_sync"):
        assert f"{driver}:_Driver.{fn}" in project.functions
    sharded = project.modules["bytewax_tpu.engine.sharded_state"]
    flush = project.functions[
        "bytewax_tpu.engine.sharded_state:GlobalAggState.flush"
    ]
    assert any(
        call.name in contracts.GSYNC_PRIMITIVES for call in flush.calls
    ), f"GlobalAggState.flush in {sharded.rel} no longer syncs"
    # And the resolver really binds the collective chain the GSYNC
    # rule depends on: pre_close -> GlobalAggState.flush.
    pre_close = project.functions[
        f"{driver}:_StatefulBatchRt.pre_close"
    ]
    assert any(
        "GlobalAggState.flush" in t
        for call in pre_close.calls
        for t in call.targets
    ), "call graph lost the pre_close -> global flush edge"


def test_connector_edge_resilience_is_process_local():
    """The connector-edge resilience PR pin: the I/O retry ladder
    (engine/backoff.py), the dead-letter queue (engine/dlq.py), and
    partition quarantine are process-local — the frame-kind inventory
    is byte-identical, no allowlist grew, and none of their functions
    call a raw send primitive, a ship method, or a sync round (a
    quarantined partition parks via next_awake scheduling; nothing
    rides the mesh, so it can never early-exit a collective tier)."""
    modules = {"bytewax_tpu.engine.backoff", "bytewax_tpu.engine.dlq"}
    allowlisted = (
        set().union(*contracts.SEND_ALLOWED.values())
        | contracts.GSYNC_CALLER_MODULES
    )
    assert not (modules & allowlisted)

    project = _project()
    forbidden = (
        contracts.RAW_SEND_METHODS
        | contracts.SHIP_METHODS
        | contracts.GSYNC_PRIMITIVES
    )
    checked = 0
    for qual, fn in project.functions.items():
        mod = qual.split(":", 1)[0]
        if mod in modules:
            checked += 1
            comm_calls = [c.name for c in fn.calls if c.name in forbidden]
            assert not comm_calls, f"{qual} calls {comm_calls}"
    assert checked >= 8  # the scan really covered both modules


def test_drain_point_inventory_is_pinned():
    """The pipeline-era drain contract (docs/performance.md,
    docs/state-residency.md): drain-only operations are pinned by
    name, raw pipeline drains by receiver, and the drain-point set —
    window close/notify, epoch close, snapshot, the EOF ladder,
    demotion, the gsync-bearing startup paths — is hardcoded here so
    editing contracts.py alone cannot quietly bless a new per-batch
    readback.  Extending either set requires updating the table AND
    this test AND re-checking the contract note in CLAUDE.md +
    docs/contracts.md."""
    assert contracts.DRAIN_ONLY_METHODS == {
        "evict_to_budget",
        "prepare",
        "prepare_entries",
        "extract_keys",
        "inject_keys",
        "demotion_snapshots",
        "pipeline_flush",
        "pipeline_shutdown",
        "_pipe_shutdown",
        "_close_epoch",
        "_close_epoch_inner",
        # The columnar-exchange PR: the route-accumulator flush is
        # drain-only — frames ship (and count into the barrier's
        # quiescence math) only at poll boundaries / drain points.
        "ship_flush",
        # The async-checkpoint PR: the seal reads every step's
        # epoch_snaps (worker-owned between submit and finalize) and
        # the fence blocks on the committer lane — both legal only
        # at the pinned drain points.
        "_ckpt_seal",
        "_ckpt_fence",
        # The lane-contract PR: the committer lane's teardown joins
        # the worker thread — run-ending closes only, like
        # _pipe_shutdown.
        "_ckpt_shutdown",
        # The inference PR: the broadcast-params swap installs only
        # at the agreed epoch close (every dispatch pipeline
        # quiesced, so no in-flight forward pass observes a
        # half-installed tree).
        "_apply_params_swap",
        "install_params",
    }
    assert contracts.PIPELINE_DRAIN_METHODS == {
        "flush",
        "shutdown",
        "drop_pending",
    }
    assert contracts.DRAIN_POINTS == {
        ("bytewax_tpu.engine.driver", "_StatefulBatchRt.advance"),
        ("bytewax_tpu.engine.driver", "_StatefulBatchRt._demote"),
        ("bytewax_tpu.engine.driver", "_InferRt._demote"),
        ("bytewax_tpu.engine.driver", "_Driver._close_epoch"),
        ("bytewax_tpu.engine.driver", "_Driver._close_epoch_inner"),
        ("bytewax_tpu.engine.driver", "_Driver._drain_pipelines"),
        ("bytewax_tpu.engine.driver", "_Driver._apply_eof_step"),
        ("bytewax_tpu.engine.driver", "_Driver._startup_rescale"),
        ("bytewax_tpu.engine.driver", "_Driver.run"),
    }
    assert contracts.DRAIN_POINT_METHOD_NAMES == {
        "pre_close",
        "on_upstream_eof",
        "epoch_snaps",
        "on_notify",
        "on_eof",
    }
    # The flush-before-sync exemptions are exactly the startup
    # rounds (no pipeline can hold work yet) and the collective
    # flush (its one caller, pre_close, flushes first).
    assert contracts.GSYNC_PREFLUSHED == {
        ("bytewax_tpu.engine.sharded_state", "GlobalAggState.flush"),
        ("bytewax_tpu.engine.driver", "_Driver.run"),
        ("bytewax_tpu.engine.driver", "_Driver._startup_rescale"),
    }
    # And every pinned drain point still exists (staleness guard,
    # like test_allowlist_is_not_stale).
    project = _project()
    for module, qualname in contracts.DRAIN_POINTS:
        assert f"{module}:{qualname}" in project.functions, qualname
    diags = _check(["BTX-DRAIN"])
    assert not diags, format_diagnostics(diags)


def test_worker_lane_inventory_is_pinned():
    """The thread-ownership contract (docs/performance.md): the
    worker-lane roots the resolver traces out of the pipeline
    submissions, and the MAIN_ONLY surface they must never reach,
    pinned by value."""
    from bytewax_tpu.analysis.rules.thread import worker_lane_roots

    project = _project()
    roots = worker_lane_roots(project)
    driver = "bytewax_tpu.engine.driver"
    sharded = "bytewax_tpu.engine.sharded_state"
    # Exactly the three device-tier submission shapes — the window
    # task, the scan task, the keyed-aggregation fold lambda — plus
    # the overlapped-collectives PR's two sealed exchange tasks on
    # the global tier's collective lane (docs/performance.md
    # "Overlapped collectives"): the exact device exchange and the
    # quantized partial merge, both sealed at a globally-ordered
    # flush and fenced at the next close/finalize — plus the
    # async-checkpoint PR's committer task (docs/recovery.md
    # "Asynchronous incremental checkpoints"): one write_epoch over
    # a delta the main thread sealed and froze, at most one in
    # flight, fenced at the next close/finalize/run-ending close —
    # plus the inference PR's scoring task (docs/inference.md): the
    # sealed batched forward pass on the step's dispatch pipeline,
    # same lane and fences as the aggregation tiers.
    assert set(roots) == {
        f"{driver}:_StatefulBatchRt._push_window_task.<locals>.task",
        f"{driver}:_StatefulBatchRt._push_scan_task.<locals>.task",
        f"{driver}:_StatefulBatchRt._process_accel.<locals>.<lambda>",
        f"{sharded}:GlobalAggState.flush.<locals>.exchange_task",
        f"{sharded}:GlobalAggState.flush.<locals>.merge_task",
        f"{driver}:_Driver._ckpt_seal.<locals>.commit_task",
        f"{driver}:_InferRt._push_infer_task.<locals>.task",
    }
    # The committer lane's recovery-store carve-out is exactly that
    # one root, one method, one module — root-scoped, so every other
    # worker-lane root still sees the store as main-only.
    assert contracts.SNAPSHOT_LANE_ROOTS == {
        f"{driver}:_Driver._ckpt_seal.<locals>.commit_task",
    }
    assert (
        contracts.SNAPSHOT_LANE_MODULE
        == "bytewax_tpu.engine.recovery_store"
    )
    assert contracts.SNAPSHOT_LANE_SAFE == {"write_epoch"}
    # The send surface, sync rounds, emission/routing, recovery
    # store, residency movement, and pipeline drains are main-only.
    for name in (
        "ship_deliver",
        "ship_route",
        "ship_flush",
        "send",
        "broadcast",
        "global_sync",
        "next_gsync_tag",
        "emit",
        "route",
        "write_epoch",
        "evict_to_budget",
        "inject_keys",
        "demotion_snapshots",
        "pipeline_flush",
        "flush",
        "push",
        "submit",
        "_close_epoch",
        "_ckpt_shutdown",
    ):
        assert name in contracts.MAIN_ONLY, name
    assert contracts.MAIN_ONLY_MODULES == {
        "bytewax_tpu.engine.comm",
        "bytewax_tpu.engine.recovery_store",
        "bytewax_tpu.engine.residency",
        "bytewax_tpu.engine.dlq",
        "bytewax_tpu.engine.webserver",
    }
    # The deliberately-shared surface stays exactly the flight-ring/
    # ledger append paths.
    assert contracts.WORKER_SAFE == {
        "note_phase",
        "note_source_lag",
        "note_pipeline_stall",
        "note_flush_depth",
        "record",
        "count",
    }
    assert contracts.PIPELINE_SUBMIT_METHODS == {"push", "submit"}
    assert (
        contracts.PIPELINE_CLASS
        == "bytewax_tpu.engine.pipeline.DevicePipeline"
    )
    diags = _check(["BTX-THREAD"])
    assert not diags, format_diagnostics(diags)


def test_lane_catalog_is_pinned():
    """The lane contract (docs/contracts.md BTX-LANE): exactly
    today's three ordered off-main-thread lanes — the per-step
    dispatch pipeline, the collective exchange lane, the checkpoint
    committer lane — each pinned with its constructor, ledger phase,
    max-in-flight bound, and fence + shutdown functions.  Adding a
    lane requires updating contracts.LANES, this test, and the
    "adding a lane" recipe in docs/contracts.md in one change; the
    rule itself proves the catalog is not stale (every entry still
    constructs, every fence/shutdown still reachable from the pinned
    run-ending closes)."""
    driver = "bytewax_tpu.engine.driver"
    sharded = "bytewax_tpu.engine.sharded_state"
    assert contracts.LANES == {
        "dispatch": {
            "constructor": (driver, "_StatefulBatchRt.__init__"),
            "phase": "device",
            "depth": None,  # knob-driven (BYTEWAX_TPU_PIPELINE_DEPTH)
            "fence": (driver, "_StatefulBatchRt.pipeline_flush"),
            "shutdown": (driver, "_StatefulBatchRt._pipe_shutdown"),
        },
        "collective": {
            "constructor": (sharded, "GlobalAggState.__init__"),
            "phase": "collective_lane",
            # knob-driven (BYTEWAX_TPU_GSYNC_DEPTH; the site passes
            # _gsync_depth() + 1, so depth 1 = one round in flight)
            "depth": None,
            "fence": (sharded, "GlobalAggState.fence"),
            "shutdown": (sharded, "GlobalAggState.lane_shutdown"),
        },
        "checkpoint": {
            "constructor": (driver, "_Driver.__init__"),
            "phase": "snapshot_lane",
            "depth": 2,
            "fence": (driver, "_Driver._ckpt_fence"),
            "shutdown": (driver, "_Driver._ckpt_shutdown"),
        },
    }
    assert contracts.LANE_TEARDOWN_ROOTS == {
        (driver, "_Driver.run"),
        (driver, "_Driver._close_epoch_inner"),
        (driver, "_StatefulBatchRt._demote"),
        (driver, "_InferRt._demote"),
    }
    # Every cataloged ledger phase must be documented in
    # docs/observability.md's phase table — the buckets feed
    # derive_rescale_hint, and an observer can only read buckets the
    # doc names.
    import pathlib

    obs = (
        pathlib.Path(__file__).resolve().parent.parent
        / "docs"
        / "observability.md"
    ).read_text()
    for name, info in contracts.LANES.items():
        assert f"`{info['phase']}`" in obs, (
            f"lane {name!r}: phase {info['phase']!r} missing from "
            "docs/observability.md's phase table"
        )
    diags = _check(["BTX-LANE"])
    assert not diags, format_diagnostics(diags)


def test_shared_state_inventory_is_pinned():
    """The shared-state contract (docs/contracts.md BTX-RACE):
    exactly today's three worker/main shared attributes, each with a
    synchronization justification, plus the sealed-capture and
    worker-carve-out inventories.  An attribute enters SHARED_STATE
    only with its justification here AND in contracts.py AND a
    re-check of the docs — never silently.  (The HBM-resident-
    aggregate PR REMOVED wire:_Reader.off: peer frames now decode on
    main at seal time, so no lane task constructs a _Reader.  The
    batched window close REMOVED KeyEncoder._ids / ._sorted: the
    lane's closes release slot ids, and no longer drop a key from
    the aggregate state's encoder per window.)"""
    assert set(contracts.SHARED_STATE) == {
        # GIL-atomic memoization; duplicate handles are benign.
        "bytewax_tpu.engine.driver:_OpRt._m_timers",
        # the deliberately-shared lock-free telemetry surface
        # (engine/flight thread-safety note; WORKER_SAFE).
        "bytewax_tpu.engine.flight:FlightRecorder._ring",
        "bytewax_tpu.engine.flight:FlightRecorder.counters",
    }
    for key, why in contracts.SHARED_STATE.items():
        assert why.strip(), f"SHARED_STATE entry {key} lacks its " \
            "one-line synchronization justification"
    # Sealed-task purity holds on the tree with NO exceptions today:
    # every value a lane task consumes is sealed at submit.  The
    # inventory exists for the day that changes — extending it means
    # editing contracts.py AND this test.
    assert contracts.SEALED_CAPTURE_SAFE == {}
    # The three sealed device phases handed back as closures (the
    # resolver cannot trace callables through return values).
    assert contracts.RACE_WORKER_CARVEOUTS == {
        "bytewax_tpu.engine.window_accel:"
        "DeviceWindowAggState._ingest.<locals>.device_phase",
        "bytewax_tpu.engine.driver:"
        "_StatefulBatchRt._scan_batch.<locals>.batch_phase",
        "bytewax_tpu.engine.driver:"
        "_InferRt._infer_batch.<locals>.batch_phase",
    }
    # Staleness guard: every pinned carve-out root still exists.
    project = _project()
    for fid in contracts.RACE_WORKER_CARVEOUTS:
        assert fid in project.functions, fid
    diags = _check(["BTX-RACE"])
    assert not diags, format_diagnostics(diags)


def test_knob_catalog_is_pinned():
    """The knob inventory: exactly today's 57 BYTEWAX_TPU_* knobs,
    each with a default and a doc anchor.  Adding a knob requires
    updating contracts.KNOBS, this list, docs/configuration.md, and
    the anchor doc — BTX-KNOB enforces the rest (literal reads,
    staleness, doc mention).  The autoscaling-loop PR added exactly
    five: the four BYTEWAX_TPU_AUTOSCALE_* knobs read by the outer
    supervisor (bytewax_tpu/supervise.py) and
    BYTEWAX_TPU_ALLOW_REMOTE_STOP (the POST /stop non-loopback
    opt-in in engine/webserver.py), all anchored at
    docs/deployment.md.  The live-rescale PR added exactly one:
    BYTEWAX_TPU_AUTOSCALE_LIVE (default on — a scale move is an
    epoch-boundary membership change with delta-only migration; 0
    forces the legacy whole-cluster drain-to-stop + relaunch).  The
    overlapped-collectives PR added exactly two:
    BYTEWAX_TPU_GSYNC_OVERLAP (default off — 1 double-buffers the
    global tier's exchange rounds on the collective lane; 0 is the
    lock-step tier, byte-identical to the pre-overlap engine) and
    BYTEWAX_TPU_GSYNC_QUANT (default off — bf16/int8 block-scale the
    gsync partial-aggregate frames; counts stay exact), both
    anchored at docs/performance.md "Overlapped collectives".  The
    async-checkpoint PR added exactly three:
    BYTEWAX_TPU_CKPT_ASYNC (default off — 1 commits each sealed
    epoch delta on the committer lane while the next epoch
    computes), BYTEWAX_TPU_CKPT_DELTA (default off — 1 writes only
    keys whose pickled state changed since the last close), and
    BYTEWAX_TPU_CKPT_COMPACT_EVERY (unset — every K closes forces a
    commit/GC watermark so an uncompacted delta chain stays
    bounded), all anchored at docs/recovery.md "Asynchronous
    incremental checkpoints".  The HBM-resident-aggregate PR added
    exactly two: BYTEWAX_TPU_GSYNC_DEPTH (default 1 — the bounded
    in-flight window for the collective exchange lane; 1 keeps the
    original one-round-in-flight overlap, D allows D sealed rounds
    retired in order), anchored at docs/performance.md "Overlapped
    collectives", and BYTEWAX_TPU_GSYNC_BASELINE_EVERY (default 8 —
    under a recovery store the overlapped tier writes a compacting
    aggregate baseline row every K data rounds so resume replays at
    most K-1 sealed rounds), anchored at docs/recovery.md
    "Store-composable overlap".  The inference PR added exactly one:
    BYTEWAX_TPU_INFER_DEVICE (default 1 — 0 forces op.infer steps
    onto the host numpy apply without disabling any other device
    tier), anchored at docs/inference.md."""
    assert sorted(contracts.KNOBS) == [
        "BYTEWAX_TPU_ACCEL",
        "BYTEWAX_TPU_ALLOW_REMOTE_STOP",
        "BYTEWAX_TPU_AUTOSCALE_COOLDOWN_S",
        "BYTEWAX_TPU_AUTOSCALE_HYSTERESIS",
        "BYTEWAX_TPU_AUTOSCALE_LIVE",
        "BYTEWAX_TPU_AUTOSCALE_POLL_S",
        "BYTEWAX_TPU_AUTOSCALE_STOP_TIMEOUT_S",
        "BYTEWAX_TPU_CKPT_ASYNC",
        "BYTEWAX_TPU_CKPT_COMPACT_EVERY",
        "BYTEWAX_TPU_CKPT_DELTA",
        "BYTEWAX_TPU_COORDINATOR",
        "BYTEWAX_TPU_DEMOTE_AFTER",
        "BYTEWAX_TPU_DIAL_TIMEOUT_S",
        "BYTEWAX_TPU_DISTRIBUTED",
        "BYTEWAX_TPU_DLQ_DIR",
        "BYTEWAX_TPU_EPOCH_STALL_S",
        "BYTEWAX_TPU_FAULTS",
        "BYTEWAX_TPU_FAULTS_KINDS",
        "BYTEWAX_TPU_FAULTS_MIN_GAP_S",
        "BYTEWAX_TPU_FAULTS_RATE",
        "BYTEWAX_TPU_FAULTS_SEED",
        "BYTEWAX_TPU_FAULTS_SITES",
        "BYTEWAX_TPU_FAULT_DELAY_S",
        "BYTEWAX_TPU_GC",
        "BYTEWAX_TPU_GLOBAL_EXCHANGE",
        "BYTEWAX_TPU_GLOBAL_EXCHANGE_DEBUG",
        "BYTEWAX_TPU_GSYNC_BASELINE_EVERY",
        "BYTEWAX_TPU_GSYNC_DEPTH",
        "BYTEWAX_TPU_GSYNC_OVERLAP",
        "BYTEWAX_TPU_GSYNC_QUANT",
        "BYTEWAX_TPU_HB_S",
        "BYTEWAX_TPU_HEARTBEAT_S",
        "BYTEWAX_TPU_HOST_STATE_BUDGET",
        "BYTEWAX_TPU_INFER_DEVICE",
        "BYTEWAX_TPU_INGEST_TARGET_ROWS",
        "BYTEWAX_TPU_IO_BACKOFF_CAP_S",
        "BYTEWAX_TPU_IO_BACKOFF_S",
        "BYTEWAX_TPU_IO_RETRIES",
        "BYTEWAX_TPU_MAX_RESTARTS",
        "BYTEWAX_TPU_PAD_MAX_POW",
        "BYTEWAX_TPU_PAD_MIN_POW",
        "BYTEWAX_TPU_PIPELINE_DEPTH",
        "BYTEWAX_TPU_PLATFORM",
        "BYTEWAX_TPU_POSTMORTEM_DIR",
        "BYTEWAX_TPU_QUARANTINE",
        "BYTEWAX_TPU_QUARANTINE_REPROBE_S",
        "BYTEWAX_TPU_RESCALE",
        "BYTEWAX_TPU_RESTART_BACKOFF_S",
        "BYTEWAX_TPU_RESTART_RESET_S",
        "BYTEWAX_TPU_REUSEPORT",
        "BYTEWAX_TPU_RX_BUFFER_CAP",
        "BYTEWAX_TPU_SHARD",
        "BYTEWAX_TPU_SPILL_DIR",
        "BYTEWAX_TPU_STATE_BUDGET",
        "BYTEWAX_TPU_TEXT_DEVICE",
        "BYTEWAX_TPU_TRACE_DIR",
        "BYTEWAX_TPU_WIRE",
    ]
    assert len(contracts.KNOBS) == 57
    for name, (default, doc) in contracts.KNOBS.items():
        assert isinstance(default, str), name
        assert doc.startswith("docs/") and doc.endswith(".md"), name
    diags = _check(["BTX-KNOB"])
    assert not diags, format_diagnostics(diags)


def test_supervisor_is_process_local():
    """The autoscaling-loop PR pin (extended by the live-rescale PR):
    the outer cluster supervisor (bytewax_tpu/supervise.py) and the
    graceful-stop/live-reconfigure surfaces are HTTP + OS process
    management only.  The frame-kind inventory above is
    byte-identical (the stop vote AND the membership-change proposal
    ride the EXISTING epoch-close gsync round — no new kinds; the
    live move's only new supervisor surfaces are a POST /reconfigure
    and a connect-and-close listener probe, both plain sockets/HTTP,
    never mesh frames), no allowlist grew to admit the supervisor,
    and none of its functions call a raw send primitive, a ship
    method, or a sync round — so it can never reach the send surface
    or early-exit a collective tier."""
    modules = {"bytewax_tpu.supervise"}
    allowlisted = (
        set().union(*contracts.SEND_ALLOWED.values())
        | contracts.GSYNC_CALLER_MODULES
    )
    assert not (modules & allowlisted)

    project = _project()
    assert "bytewax_tpu.supervise" in project.modules
    forbidden = (
        contracts.RAW_SEND_METHODS
        | contracts.SHIP_METHODS
        | contracts.GSYNC_PRIMITIVES
    )
    checked = 0
    for qual, fn in project.functions.items():
        mod = qual.split(":", 1)[0]
        if mod in modules:
            checked += 1
            comm_calls = [c.name for c in fn.calls if c.name in forbidden]
            assert not comm_calls, f"{qual} calls {comm_calls}"
    assert checked >= 10  # the scan really covered the supervisor


def test_wire_codec_is_pure_and_allowlisted():
    """The columnar-exchange PR pin (docs/performance.md "Columnar
    exchange"): ``engine/wire.py`` is pure encode/decode plus the
    route accumulator — no sockets, no frames of its own.  The
    frame-kind inventory above is byte-identical (columnar framing
    rides INSIDE the existing deliver/route payloads), none of the
    wire module's functions touch a raw send primitive, a ship
    method, or a sync round, and it never constructs a Comm.  The
    module itself is send-surface-adjacent: BTX-SEND restricts
    resolved calls into it to the comm/driver pair plus the
    global-mesh collective tier, whose quantized aggregate frames it
    encodes (``contracts.WIRE_ALLOWED_MODULES``, pinned in
    test_send_surface_allowlist_is_pinned)."""
    project = _project()
    assert contracts.WIRE_MODULE in project.modules
    forbidden = (
        contracts.RAW_SEND_METHODS
        | contracts.SHIP_METHODS
        | contracts.GSYNC_PRIMITIVES
    )
    checked = 0
    for qual, fn in project.functions.items():
        mod = qual.split(":", 1)[0]
        if mod != contracts.WIRE_MODULE:
            continue
        checked += 1
        comm_calls = [c.name for c in fn.calls if c.name in forbidden]
        assert not comm_calls, f"{qual} calls {comm_calls}"
        constructs = [
            c.name for c in fn.calls if c.dotted == contracts.COMM_CLASS
        ]
        assert not constructs, f"{qual} constructs Comm"
    assert checked >= 10  # the scan really covered the codec

    # And the accumulator's flush counterpart really exists where
    # BTX-DRAIN pins it (staleness guard).
    driver = "bytewax_tpu.engine.driver"
    flush = project.functions[f"{driver}:_Driver.ship_flush"]
    assert any(
        c.name in contracts.RAW_SEND_METHODS for c in flush.calls
    ), "ship_flush no longer sends — the drain-only pin is stale"


def test_ingest_batching_is_process_local():
    """The columnar-ingest PR pin: batch-native sources, coalescing,
    and bucketed padding (engine/batching.py + the connectors) are
    process-local — the frame-kind inventory above is byte-identical,
    no allowlist grew to admit them, and none of their functions call
    a raw send primitive, a ship method, or a sync round."""
    ingest_modules = {"bytewax_tpu.engine.batching"}
    allowlisted = (
        set().union(*contracts.SEND_ALLOWED.values())
        | contracts.GSYNC_CALLER_MODULES
    )
    assert not (ingest_modules & allowlisted)
    assert not any(m.startswith("bytewax_tpu.connectors") for m in allowlisted)

    project = _project()
    assert "bytewax_tpu.engine.batching" in project.modules
    forbidden = (
        contracts.RAW_SEND_METHODS
        | contracts.SHIP_METHODS
        | contracts.GSYNC_PRIMITIVES
    )
    checked = 0
    for qual, fn in project.functions.items():
        mod = qual.split(":", 1)[0]
        if mod in ingest_modules or mod.startswith("bytewax_tpu.connectors"):
            checked += 1
            comm_calls = [c.name for c in fn.calls if c.name in forbidden]
            assert not comm_calls, f"{qual} calls {comm_calls}"
    assert checked > 10  # the scan really covered the ingest surface
