"""Flatten a nested operator tree into the 9-core-operator plan.

The reference does this reflectively in the engine with a build stack
(``/root/reference/src/worker.rs:255-497``); here it is a plain
recursive walk producing a topologically-ordered list of core
operators plus stream wiring tables.
"""

from typing import Dict, List, Optional, Tuple

from bytewax_tpu.dataflow import Dataflow, DataflowError, Operator

__all__ = ["Plan", "flatten"]


def _find_core_stateful(op: Operator) -> Optional[Operator]:
    for sub in op.substeps:
        if sub.core and sub.name == "stateful_batch":
            return sub
        found = _find_core_stateful(sub)
        if found is not None:
            return found
    return None


def _annotate_accel(op: Operator) -> None:
    """Lowering pass: recognize aggregation shapes and annotate their
    core ``stateful_batch`` with a device spec so the driver folds
    them on device instead of per-key Python logics."""
    from bytewax_tpu.engine.xla import AccelSpec
    from bytewax_tpu.xla import Reducer, ScanMap

    spec = None
    if op.name == "reduce_final" and isinstance(op.conf.get("reducer"), Reducer):
        spec = AccelSpec(op.conf["reducer"].kind)
    elif op.name == "stats_final":
        spec = AccelSpec("stats")
    elif op.name == "stateful_map" and isinstance(
        op.conf.get("mapper"), ScanMap
    ):
        # The mapper names its own device lowering: any ScanKind —
        # built-in or user-registered — lowers through the one
        # generic path; mappers returning None stay host-tier (they
        # are still valid plain mappers).
        kind = op.conf["mapper"].device_kind()
        if kind is not None:
            from bytewax_tpu.engine.scan_accel import ScanAccelSpec

            spec = ScanAccelSpec(kind)
    elif op.name == "infer":
        # Model scoring always lowers: the spec's batched forward
        # pass is the step's one semantics (the driver's infer
        # runtime owns both tiers, so accel-off runs the same spec's
        # host apply, not per-key Python logics).
        from bytewax_tpu.engine.infer import InferAccelSpec

        spec = InferAccelSpec(
            op.conf["apply_fn"],
            op.conf["params"],
            op.conf.get("host_apply"),
        )
    elif op.name in ("count_window", "fold_window", "reduce_window"):
        spec = _window_accel_spec(op)
    elif op.name == "join_window":
        spec = _join_accel_spec(op)
    if spec is not None:
        inner = _find_core_stateful(op)
        if inner is not None:
            inner.conf["_accel"] = spec


def _window_accel_spec(op: Operator):
    """Device lowering for windowed folds over EventClock +
    tumbling/sliding windows.

    ``count_window`` always lowers (the folded "value" is a constant
    1, so only the item's timestamp matters).  Numeric folds
    (``fold_window``/``reduce_window`` with a marked
    ``bytewax_tpu.xla`` reducer) lower too, but only columnar batches
    carrying explicit ``key``/``ts``/``value`` columns run on device
    — itemized deliveries can't statically promise numeric,
    timestamp-bearing values, so the runtime falls back to the host
    tier on first contact with them.  Session windows lower too
    (key-local gap-merge scan, ``SessionAccelSpec``) when the
    merger is the kind's own combine; custom/fake clocks always
    stay host-side.
    """
    from bytewax_tpu.engine.window_accel import (
        SessionAccelSpec,
        WindowAccelSpec,
    )
    from bytewax_tpu.operators.windowing import SessionWindower
    from bytewax_tpu.xla import Reducer, WindowFold

    from bytewax_tpu.ops.segment import AGG_KINDS

    # A Reducer is a binary combine over bare values — only these
    # kinds have that shape on the device tier (a Reducer("mean")
    # would wrongly fold (sum, count) instead of applying its fn).
    # WindowFolds carry a structured accumulator and may use any
    # implemented kind.
    reducer_identity = {"sum": 0, "min": float("inf"), "max": float("-inf")}

    folder = op.conf.get("folder")
    if op.name == "count_window":
        kind = "count"
    elif op.name == "reduce_window" and isinstance(
        op.conf.get("reducer"), Reducer
    ):
        kind = op.conf["reducer"].kind
        if kind not in reducer_identity:
            # User-constructed Reducer with a kind the device tier
            # has no binary-reduce lowering for: stay host-side.
            return None
    elif op.name == "fold_window" and isinstance(folder, (Reducer, WindowFold)):
        kind = folder.kind
        if isinstance(folder, WindowFold):
            if kind not in AGG_KINDS:
                # User-constructed WindowFold with a kind the device
                # tier has no lowering for: stay host-side.
                return None
            expected = folder.make_acc()
        else:
            if kind not in reducer_identity:
                return None
            expected = reducer_identity[kind]
        # The device fold starts from the kind's identity; a builder
        # with any other initial accumulator must stay host-side.
        # NOTE: the probe runs the user's builder at plan time — a
        # builder with side effects observes one extra call.
        try:
            if op.conf["builder"]() != expected:
                return None
        except Exception as ex:  # noqa: BLE001
            import warnings

            warnings.warn(
                f"step {op.step_id!r}: probing the window fold builder "
                f"for device lowering raised {ex!r}; the step stays on "
                "the host tier",
                RuntimeWarning,
                stacklevel=2,
            )
            return None
    else:
        return None
    clock = _system_event_clock(op)
    windower = op.conf.get("windower")
    if clock is None:
        return None
    fixed = _fixed_windows(windower)
    if fixed is not None:
        length, offset = fixed
    elif isinstance(windower, SessionWindower):
        # Sessions merge, so the device tier's slot-set combine must
        # be the kind's own merge: require the operator's merger to
        # be the marked reducer/fold's combine (count_window's merge
        # is addition by construction).
        merger = op.conf.get("merger")
        if op.name == "fold_window":
            from bytewax_tpu.xla import WindowFold

            if isinstance(folder, WindowFold):
                if merger is not folder.merge:
                    return None
            elif merger is not folder:
                return None
        elif op.name == "reduce_window" and merger not in (
            None,
            op.conf.get("reducer"),
        ):
            return None
        return SessionAccelSpec(
            kind,
            clock.ts_getter,
            windower.gap,
            clock.wait_for_system_duration,
        )
    else:
        return None
    return WindowAccelSpec(
        kind,
        clock.ts_getter,
        windower.align_to,
        length,
        offset,
        clock.wait_for_system_duration,
    )


def _system_event_clock(op: Operator):
    """The step's ``EventClock`` where it runs on the system clock, else
    None: custom and fake clocks (tests) need the host tier's exact
    per-item semantics."""
    from bytewax_tpu.operators import _get_system_utc, _identity
    from bytewax_tpu.operators.windowing import EventClock

    clock = op.conf.get("clock")
    if not isinstance(clock, EventClock):
        return None
    if clock.now_getter is not _get_system_utc or clock.to_system_utc is not _identity:
        return None
    return clock


def _fixed_windows(windower):
    """``(length, offset)`` of a tumbling or sliding windower, else None."""
    from bytewax_tpu.operators.windowing import SlidingWindower, TumblingWindower

    if isinstance(windower, TumblingWindower):
        return windower.length, windower.length
    if isinstance(windower, SlidingWindower):
        return windower.length, windower.offset
    return None


def _join_accel_spec(op: Operator):
    """Device lowering for ``join_window``: every row of every side
    kept until its window closes, then the product of the sides
    written once (``insert_mode="product"``, ``emit_mode="final"``),
    over ``EventClock`` with the system clock and tumbling or sliding
    windows.  Every other form stays on the host tier: ``first`` /
    ``last`` / ``complete`` / ``running`` decide row by row as values
    arrive, sessions merge tables, and a custom clock needs the host
    tier's per-item semantics."""
    from bytewax_tpu.engine.window_accel import JoinAccelSpec

    if op.conf.get("insert_mode") != "product" or op.conf.get("emit_mode") != "final":
        return None
    clock = _system_event_clock(op)
    fixed = _fixed_windows(op.conf.get("windower"))
    if clock is None or fixed is None:
        return None
    return JoinAccelSpec(
        len(op.ups["sides"]), clock.ts_getter, op.conf["windower"].align_to, *fixed,
        clock.wait_for_system_duration,
    )


CORE_OPS = frozenset(
    {
        "_noop",
        "branch",
        "flat_map_batch",
        "input",
        "inspect_debug",
        "merge",
        "output",
        "redistribute",
        "stateful_batch",
    }
)


class Plan:
    """Execution plan: core ops in topological order + stream wiring."""

    def __init__(self, flow: Dataflow):
        self.flow = flow
        self.ops: List[Operator] = []
        #: stream_id -> index of producing core op in ``ops``
        self.producer: Dict[str, int] = {}
        #: stream_id -> [(consumer op index, port name)]
        self.consumers: Dict[str, List[Tuple[int, str]]] = {}

    def up_stream_ids(self, op: Operator) -> List[str]:
        return [s.stream_id for s in op.up_streams()]


def _walk(op: Operator, plan: Plan) -> None:
    if op.core:
        if op.name not in CORE_OPS:
            msg = f"unknown core operator {op.name!r} at {op.step_id!r}"
            raise DataflowError(msg)
        plan.ops.append(op)
    else:
        _annotate_accel(op)
        for sub in op.substeps:
            _walk(sub, plan)


def _index(plan: Plan) -> None:
    plan.producer = {}
    plan.consumers = {}
    for idx, op in enumerate(plan.ops):
        for port, val in op.ups.items():
            streams = [val] if not isinstance(val, list) else val
            for s in streams:
                plan.consumers.setdefault(s.stream_id, []).append((idx, port))
        for s in op.down_streams():
            plan.producer[s.stream_id] = idx


#: Core ops a columnar batch passes through (possibly transformed but
#: still batch-granular) on its way to a device-tier consumer.  Used
#: by the ingest reachability pass below; branch/inspect itemize but
#: still forward, so they stay transparent for reachability.
_BATCH_TRANSPARENT = frozenset(
    {
        "_noop",
        "branch",
        "flat_map_batch",
        "inspect_debug",
        "merge",
        "redistribute",
    }
)


def _annotate_accel_bound(plan: Plan) -> None:
    """Ingest-plumbing pass: mark each core ``input`` op whose stream
    reaches a device-annotated ``stateful_batch`` through batch-
    transparent ops with ``_accel_bound``.  The driver arms adaptive
    micro-batch coalescing (engine/batching.py) for those inputs by
    default — re-batching trickle sources into device-sized
    micro-batches pays exactly when a dispatch is being amortized.
    Deterministic (plan order), so every cluster process agrees."""
    for op in plan.ops:
        if op.name != "input":
            continue
        seen: set = set()
        frontier = [s.stream_id for s in op.down_streams()]
        bound = False
        while frontier and not bound:
            sid = frontier.pop()
            if sid in seen:
                continue
            seen.add(sid)
            for ci, _port in plan.consumers.get(sid, []):
                consumer = plan.ops[ci]
                spec = (
                    consumer.conf.get("_accel")
                    if consumer.name == "stateful_batch"
                    else None
                )
                if spec is not None:
                    # Session windows merge by inter-batch arrival
                    # grouping, so re-batching would change their
                    # window metadata — they never arm coalescing.
                    if type(spec).__name__ != "SessionAccelSpec":
                        bound = True
                        break
                if consumer.name in _BATCH_TRANSPARENT:
                    frontier.extend(
                        s.stream_id for s in consumer.down_streams()
                    )
        op.conf["_accel_bound"] = bound


def _annotate_meta_live(plan: Plan) -> None:
    """Tell each device-lowered window step whether its ``meta``
    stream is read: with the ``unwrap_meta`` tap pruned (below) no
    consumer can see an "M" event, and the device tier builds none
    (two ``datetime``s and a ``WindowMetadata`` a closed window).
    Read from the plan, so every cluster process agrees."""
    for op in plan.ops:
        spec = op.conf.get("_accel") if op.name == "stateful_batch" else None
        if hasattr(spec, "meta_live"):
            spec.meta_live = any(
                plan.ops[ci].step_id.endswith(".unwrap_meta")
                for s in op.down_streams()
                for ci, _port in plan.consumers.get(s.stream_id, [])
            )


def _prune_dead_taps(plan: Plan) -> None:
    """Drop core steps marked ``_prunable`` (pure internal shims —
    the window operator's unwrap taps) whose output streams have no
    consumer: they can never affect anything observable, and a live
    tap costs a per-event Python pass.  Iterates because dropping a
    tap can orphan another prunable step upstream.  Deterministic
    (tree order), so every cluster process prunes identically."""
    while True:
        dead = [
            op
            for op in plan.ops
            if op.conf.get("_prunable")
            and all(
                not plan.consumers.get(s.stream_id)
                for s in op.down_streams()
            )
        ]
        if not dead:
            return
        drop = set(map(id, dead))
        plan.ops = [op for op in plan.ops if id(op) not in drop]
        _index(plan)


def flatten(flow: Dataflow) -> Plan:
    """Flatten the operator tree; validate ≥1 input and ≥1 output
    (reference parity: ``src/worker.rs:474-483``)."""
    plan = Plan(flow)
    for op in flow.substeps:
        _walk(op, plan)
    _index(plan)
    _prune_dead_taps(plan)
    _annotate_meta_live(plan)
    _annotate_accel_bound(plan)
    names = {op.name for op in plan.ops}
    if "input" not in names:
        msg = (
            f"dataflow {flow.flow_id!r} needs at least one input step; "
            "add an `bytewax_tpu.operators.input` step"
        )
        raise DataflowError(msg)
    if "output" not in names:
        msg = (
            f"dataflow {flow.flow_id!r} needs at least one output step; "
            "add an `bytewax_tpu.operators.output` step"
        )
        raise DataflowError(msg)
    return plan
