"""From the profiler's trace to numbers: device busy intervals, device
time by jitted program, and the longest idle gaps with what the host
was doing in them.

The reduction works on a plain structure, so that a small recorded
trace can be kept as JSON with the tests::

    {"planes": [{"name": ..., "lines": [{"name": ...,
        "events": [[name, start_ns, duration_ns], ...]}]}]}

``load_xplane`` makes it from the ``.xplane.pb`` that
``jax.profiler.start_trace`` writes.
"""

import glob
import os
import re
from typing import Any, Dict, List, Tuple

#: Host spans the harness writes with ``jax.profiler.TraceAnnotation``.
BENCH_SPANS = ("bench_poll", "bench_sink_write")
WHOLE_RUN_SPAN = "bench_cli_main"
#: How many of the longest idle gaps are attributed.
GAPS_READ = 50
#: Host events shorter than this explain no gap worth reading.
MIN_HOST_EVENT_NS = 50_000


def find_xplane(trace_dir: str) -> Tuple[str, int]:
    """The newest ``.xplane.pb`` under ``trace_dir`` and the bytes the
    whole trace directory holds."""
    paths = glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")
    )
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    size = 0
    for base, _dirs, files in os.walk(trace_dir):
        size += sum(os.path.getsize(os.path.join(base, f)) for f in files)
    return max(paths, key=os.path.getmtime), size


def load_xplane(path: str) -> Dict[str, Any]:
    from jax.profiler import ProfileData

    planes = []
    for plane in ProfileData.from_file(path).planes:
        lines = []
        for line in plane.lines:
            events = [
                [ev.name, float(ev.start_ns), float(ev.duration_ns)]
                for ev in line.events
            ]
            lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def is_device_plane(name: str) -> bool:
    return name.startswith("/device:") and "CPU" not in name and (
        "SparseCore" not in name
    )


def merged(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Union of ``(start, end)`` intervals, sorted."""
    out: List[Tuple[float, float]] = []
    for start, end in sorted(intervals):
        if out and start <= out[-1][1]:
            if end > out[-1][1]:
                out[-1] = (out[-1][0], end)
        else:
            out.append((start, end))
    return out


def _line(plane: Dict[str, Any], *names: str) -> List[list]:
    for name in names:
        for line in plane["lines"]:
            if line["name"] == name and line["events"]:
                return line["events"]
    return []


def program_name(event_name: str) -> str:
    """``jit_update_fields(123456789)`` -> ``jit_update_fields``."""
    return re.sub(r"\(\d+\)$", "", event_name).strip()


def _overlap(a0: float, a1: float, b0: float, b1: float) -> float:
    return max(0.0, min(a1, b1) - max(a0, b0))


def _label_gap(gap: Tuple[float, float], host_events: List[list]) -> str:
    """What the host was doing in an idle gap: the harness's own span
    where one covers half of it, else the host event recorded by the
    runtime that covers most of it (a quarter or more), else Python in
    the engine (which the profiler does not see)."""
    g0, g1 = gap
    length = g1 - g0
    best: Dict[str, float] = {}
    for name, start, dur in host_events:
        if name == WHOLE_RUN_SPAN:
            continue
        cover = _overlap(g0, g1, start, start + dur)
        if cover > 0:
            best[name] = best.get(name, 0.0) + cover
    for span in BENCH_SPANS:
        if best.get(span, 0.0) >= 0.5 * length:
            return span
    others = {k: v for k, v in best.items() if k not in BENCH_SPANS}
    if others:
        name = max(others, key=others.get)
        if others[name] >= 0.25 * length:
            return program_name(name)[:60]
    # A run's span is in the trace only where the run began inside
    # it (the jobs of a cell that runs many); where there is none, the
    # one run covers the whole trace.
    runs = [e for e in host_events if e[0] == WHOLE_RUN_SPAN]
    inside = not runs or any(
        _overlap(g0, g1, start, start + dur) >= 0.5 * length
        for _name, start, dur in runs
    )
    return "python_in_engine" if inside else "between_runs"


def reduce(trace: Dict[str, Any]) -> Dict[str, Any]:
    """Busy seconds per device, device seconds by program, the traced
    window and the breakdown the result line carries."""
    starts, ends = [], []
    host_events: List[list] = []
    busy_by_device: Dict[str, float] = {}
    gaps_by_device: Dict[str, List[Tuple[float, float]]] = {}
    programs: Dict[str, List[float]] = {}
    program_starts: Dict[str, List[float]] = {}
    n_devices = 0
    for plane in trace["planes"]:
        for line in plane["lines"]:
            for _name, start, dur in line["events"]:
                starts.append(start)
                ends.append(start + dur)
        if not is_device_plane(plane["name"]):
            for line in plane["lines"]:
                host_events.extend(
                    e for e in line["events"] if e[2] >= MIN_HOST_EVENT_NS
                )
            continue
        ops = _line(plane, "XLA Ops", "XLA Modules")
        if not ops:
            continue
        n_devices += 1
        busy = merged([(s, s + d) for _n, s, d in ops])
        busy_by_device[plane["name"]] = sum(e - s for s, e in busy) / 1e9
        gaps_by_device[plane["name"]] = [
            (a[1], b[0]) for a, b in zip(busy, busy[1:])
        ]
        for name, start, dur in _line(plane, "XLA Modules"):
            entry = programs.setdefault(program_name(name), [0, 0.0])
            entry[0] += 1
            entry[1] += dur / 1e9
            if n_devices == 1:
                program_starts.setdefault(program_name(name), []).append(start)
    window_s = (max(ends) - min(starts)) / 1e9 if starts else 0.0
    # Over several chips, a program's calls and seconds are per chip.
    for entry in programs.values():
        entry[0] = entry[0] / max(n_devices, 1)
        entry[1] = entry[1] / max(n_devices, 1)
    idle: Dict[str, float] = {}
    if gaps_by_device:
        first = sorted(gaps_by_device)[0]
        longest = sorted(
            gaps_by_device[first], key=lambda g: g[0] - g[1]
        )[:GAPS_READ]
        for gap in longest:
            label = _label_gap(gap, host_events)
            idle[label] = idle.get(label, 0.0) + (gap[1] - gap[0]) / 1e9
    top = sorted(programs.items(), key=lambda kv: -kv[1][1])[:10]
    return {
        "window_s": window_s,
        "busy_s": (
            sum(busy_by_device.values()) / len(busy_by_device)
            if busy_by_device
            else 0.0
        ),
        "busy_by_device_s": busy_by_device,
        "programs": {k: [v[0], v[1]] for k, v in programs.items()},
        # When each call of a program began on the first chip, and when
        # each of the harness's spans ended, on the trace's one clock.
        "program_starts_ns": program_starts,
        "span_ends_ns": {
            span: sorted(s + d for n, s, d in host_events if n == span)
            for span in BENCH_SPANS
        },
        "breakdown": {
            "device_ops": [[name, v[1]] for name, v in top],
            "idle_gaps": sorted(
                ([k, v] for k, v in idle.items()), key=lambda kv: -kv[1]
            )[:10],
        },
    }


def reduce_dir(trace_dir: str) -> Dict[str, Any]:
    path, size = find_xplane(trace_dir)
    out = reduce(load_xplane(path))
    out["bytes"] = size
    return out
