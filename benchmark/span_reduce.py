"""From the profiler's trace and the program's phase ledger to what
the engine's own spans say.

The engine writes each of its work spans into the profiler's trace as
``btx.<phase>`` (``bytewax_tpu/engine/flight.py`` ``span``), on the
thread that does the work and on the clock of the device's
operations.  ``reduce`` reads them from the plain structure
``trace_reduce.load_xplane`` makes of an ``.xplane.pb``:

- *idle seconds by span*: the overlap of each span with the complement
  of the first chip's ``XLA Ops`` union, over all gaps of the stretch.
  An instant that a span of a pipeline worker (a lane) and a span of
  the main thread both cover goes to the lane's: the main thread is
  then preparing the next delivery, and the device waits for the
  lane.  What no span covers is ``unattributed``.
- *self seconds by span*: how long each span ran, less what spans
  nested in it took (none nests on the hot path).

The profiler names every Python thread's line ``python3``, so the main
thread is told by what only it does: the harness's own spans, and the
engine's spans that never run on a lane.

``phase_pct`` reads the ledger's seconds (``run["phases"]``, the
window's gain of ``flight.RECORDER.phase_totals``) as a share of the
window's wall time.
"""

import sys
from typing import Any, Dict, List, Optional, Tuple

from benchmark import trace_reduce

PREFIX = "btx."
#: Engine spans that only the main thread enters.
MAIN_ONLY = frozenset(
    PREFIX + p
    for p in ("startup", "teardown", "parse", "watermark", "emit", "sink")
)
HARNESS = frozenset(trace_reduce.BENCH_SPANS + (trace_reduce.WHOLE_RUN_SPAN,))

Segment = Tuple[float, float, str]


def flattened(events: List[list]) -> List[Segment]:
    """One thread's spans as disjoint ``(start, end, name)`` segments,
    the innermost span owning each instant."""
    out: List[Segment] = []
    stack: List[list] = []  # [name, end, cursor]

    def close_until(at: float) -> None:
        while stack and stack[-1][1] <= at:
            name, end, cursor = stack.pop()
            if end > cursor:
                out.append((cursor, end, name))
            if stack:
                stack[-1][2] = max(stack[-1][2], end)

    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        close_until(start)
        if stack:
            parent = stack[-1]
            if start > parent[2]:
                out.append((parent[2], start, parent[0]))
            parent[2] = start
        stack.append([name, start + dur, start])
    close_until(float("inf"))
    return sorted(out)


def _take(
    intervals: List[Tuple[float, float]], segments: List[Segment]
) -> Tuple[Dict[str, float], List[Tuple[float, float]]]:
    """Seconds (in ns) of ``intervals`` that ``segments`` cover, by
    name, and the intervals left uncovered.  Both sorted, disjoint."""
    got: Dict[str, float] = {}
    left: List[Tuple[float, float]] = []
    i = 0
    for a, b in intervals:
        while i < len(segments) and segments[i][1] <= a:
            i += 1
        at, j = a, i
        while j < len(segments) and segments[j][0] < b:
            s, e, name = segments[j]
            s, e = max(s, a), min(e, b)
            if s > at:
                left.append((at, s))
            got[name] = got.get(name, 0.0) + (e - s)
            at = max(at, e)
            j += 1
        if at < b:
            left.append((at, b))
    return got, left


def span_lines(trace: Dict[str, Any]) -> Tuple[List[List[list]], List[List[list]]]:
    """The ``btx.*`` events of the host planes, a list a thread:
    ``(lanes, mains)``."""
    lanes, mains = [], []
    for plane in trace["planes"]:
        if trace_reduce.is_device_plane(plane["name"]):
            continue
        for line in plane["lines"]:
            names = {e[0] for e in line["events"]}
            spans = [e for e in line["events"] if e[0].startswith(PREFIX)]
            if not spans:
                continue
            is_main = bool(names & HARNESS) or bool(names & MAIN_ONLY)
            (mains if is_main else lanes).append(spans)
    return lanes, mains


def device_gaps(trace: Dict[str, Any]) -> List[Tuple[float, float]]:
    """Every gap between the operations of the first chip."""
    for plane in sorted(trace["planes"], key=lambda p: p["name"]):
        if not trace_reduce.is_device_plane(plane["name"]):
            continue
        ops = trace_reduce._line(plane, "XLA Ops", "XLA Modules")
        if ops:
            busy = trace_reduce.merged([(s, s + d) for _n, s, d in ops])
            return [(a[1], b[0]) for a, b in zip(busy, busy[1:])]
    return []


def reduce(trace: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """Idle and self seconds by span; None where the trace holds no
    span of the engine (a program without them) or no device gap."""
    lanes, mains = span_lines(trace)
    gaps = device_gaps(trace)
    if not (lanes or mains) or not gaps:
        return None
    idle: Dict[str, float] = {}
    self_s: Dict[str, float] = {}
    left = gaps
    for prefix, lines in (("lane/", lanes), ("", mains)):
        for events in lines:
            segments = flattened(events)
            for s, e, name in segments:
                key = prefix + name[len(PREFIX):]
                self_s[key] = self_s.get(key, 0.0) + (e - s) / 1e9
            got, left = _take(left, segments)
            for name, ns in got.items():
                key = prefix + name[len(PREFIX):]
                idle[key] = idle.get(key, 0.0) + ns / 1e9
    total = sum(b - a for a, b in gaps) / 1e9
    return {
        "idle_s": total,
        "unattributed_s": sum(b - a for a, b in left) / 1e9,
        "idle_by_span_s": idle,
        "self_by_span_s": self_s,
        # Of what no span of the engine covers, the part the
        # harness's own spans do (its source making a batch; between
        # two jobs nothing of it is in a span).
        "harness_s": harness_cover(trace, left),
    }


def harness_cover(
    trace: Dict[str, Any], intervals: List[Tuple[float, float]]
) -> Dict[str, float]:
    """Seconds of ``intervals`` under the harness's own spans (the
    whole-run span left out: it covers the engine too)."""
    got: Dict[str, float] = {}
    for plane in trace["planes"]:
        if trace_reduce.is_device_plane(plane["name"]):
            continue
        for line in plane["lines"]:
            events = [e for e in line["events"] if e[0] in trace_reduce.BENCH_SPANS]
            if events:
                cover, _left = _take(intervals, flattened(events))
                for name, ns in cover.items():
                    got[name] = got.get(name, 0.0) + ns / 1e9
    return got


def reduce_dir(trace_dir: str) -> Optional[Dict[str, Any]]:
    path, _size = trace_reduce.find_xplane(trace_dir)
    return reduce(trace_reduce.load_xplane(path))


def print_table(reduced: Dict[str, Any], out=None) -> None:
    """The table ``idle_unattributed_pct`` prints: a line a span."""
    out = out or sys.stderr
    idle, self_s = reduced["idle_by_span_s"], reduced["self_by_span_s"]
    print(
        f"span_reduce: idle {reduced['idle_s']:.6f} s, "
        f"unattributed {reduced['unattributed_s']:.6f} s",
        file=out,
    )
    for key in sorted(self_s, key=lambda k: -idle.get(k, 0.0)):
        print(
            f"span_reduce: {key:<20} idle {idle.get(key, 0.0):.6f} s "
            f"self {self_s[key]:.6f} s",
            file=out,
        )
    for key, seconds in sorted(reduced["harness_s"].items()):
        print(
            f"span_reduce: of the unattributed, under {key} {seconds:.6f} s",
            file=out,
        )


def spans_a_delivery(counters: Dict[str, float], per: str = "dispatch") -> Optional[float]:
    """Work spans over deliveries, from the program's
    ``<phase>_spans`` counters.  A delivery is one batch handed to the
    fold (``dispatch_spans``: one jitted call a batch); ``per="device"``
    counts tasks of the dispatch lane instead, each of which carries
    every batch one poll brought (four, on ``brc.file``)."""
    from bytewax_tpu.engine import flight

    deliveries = counters.get(per + "_spans")
    if not deliveries:
        return None
    work = sum(
        counters.get(phase + "_spans", 0)
        for phase in getattr(flight, "TRACED_PHASES", ())
    )
    return work / deliveries


def phase_seconds(phases: Dict[str, float], *names: str) -> Optional[float]:
    """Ledger seconds of the named phases.  A bare name stands for the
    phase on every lane (``fetch`` and ``device/fetch``); a name given
    with its lane (``device/fetch``) for that one; ``device/*`` for
    the lane's own time and all its children.  None where the ledger
    holds none of them."""
    total = None
    for phase, seconds in phases.items():
        lane, _, leaf = phase.rpartition("/")
        for name in names:
            if name.endswith("/*"):
                hit = phase == name[:-2] or lane == name[:-2]
            elif "/" in name:
                hit = phase == name
            else:
                hit = leaf == name
            if hit:
                total = (total or 0.0) + seconds
                break
    return total


def phase_pct(run: Dict[str, Any], *names: str) -> Optional[float]:
    """The named phases' seconds as a share of the window's wall time
    (unlike ``host_phase_pct``, a share of attributed time)."""
    seconds = phase_seconds(run["phases"], *names)
    if seconds is None or not run["window_s"]:
        return None
    return 100.0 * seconds / run["window_s"]
