"""What the keyed fold needs by its shapes, and the chip's peaks."""

import json
import os
from typing import Any, Dict, List, Optional, Tuple

HERE = os.path.dirname(os.path.abspath(__file__))

def peak(device_kind: str) -> Dict[str, float]:
    with open(os.path.join(HERE, "peaks.json")) as f:
        devices = json.load(f)["devices"]
    if device_kind not in devices:
        raise KeyError(f"no peaks for device kind {device_kind!r} in peaks.json")
    return devices[device_kind]


def fold_bytes(shapes: Dict[str, int], events: int, calls: int) -> float:
    """Bytes a fold of ``events`` rows in ``calls`` programs has to
    move: each row's id and value read once, and each call reads and
    writes every field of the slots it touches (at most one slot a
    row, at most the live slots)."""
    row = shapes["id_bytes"] + shapes["value_bytes"]
    touched = min(events / calls, shapes["live_slots"])
    table = 2 * shapes["fields"] * shapes["field_bytes"] * touched
    return events * row + calls * table


def fold_programs(shapes: Dict[str, Any], programs: Dict[str, Any]) -> List[str]:
    """The traced programs that are the fold: those whose name starts
    with one of the configuration's ``fold_shapes.programs``."""
    prefixes = tuple(shapes["programs"])
    return [name for name in programs if name.startswith(prefixes)]


def fold_time(shapes, programs: Dict[str, Tuple[int, float]]) -> Tuple[int, float]:
    """Calls and device seconds of the fold's programs in a reduced
    trace's ``programs`` (name -> [calls, seconds])."""
    names = fold_programs(shapes, programs)
    return (
        sum(programs[name][0] for name in names),
        sum(programs[name][1] for name in names),
    )


def events_in_stretch(run: Dict[str, Any], calls: float) -> Optional[float]:
    """Input events folded while the profiler was on.  A stream: the
    rows of the polls inside the stretch.  Jobs: the traced fold calls
    times the rows a call folds, which is a job's rows over the fold
    calls of one whole job, counted in the trace between the ends of
    two sink writes (a job ends with one); ``None`` where the stretch
    holds no whole job."""
    trace = run["trace"]
    schedule = run.get("schedule")
    if schedule is not None:
        lo, hi = trace["stretch_s"]
        return sum(b - a for at, a, b in schedule.window_polls() if lo <= at < hi)
    ends = trace["span_ends_ns"].get("bench_sink_write", [])
    if len(ends) < 2:
        return None
    shapes = run["cell"].cfg["fold_shapes"]
    starts = [
        at
        for name in fold_programs(shapes, trace["program_starts_ns"])
        for at in trace["program_starts_ns"][name]
    ]
    between = sum(ends[0] <= at < ends[-1] for at in starts)
    if not between:
        return None
    calls_per_job = between / (len(ends) - 1)
    return calls * run["data"]["rows"] / calls_per_job
