"""Wall seconds beside CPU seconds, by phase of the program's ledger.

The program's spans record their thread's CPU seconds beside their
wall seconds: ``run["phases"]`` holds the window's gain of
``flight.RECORDER.phase_totals`` and ``run["counters"]`` that of the
``cpu:<phase>`` counters, under the same phase names.  A phase's
*off-CPU* seconds are its wall less its CPU seconds: the thread waited
for the interpreter, was pre-empted, or blocked in the runtime (for
the chip, in the three spans of :data:`WAITS`).  A phase that was
recorded from a duration alone (``flush``, ``barrier``, ``gsync``) has
no CPU seconds and is in none of these sums.

Under a program without the counters every reader here returns None.
"""

import sys
from typing import Any, Callable, Dict, Optional, Tuple

#: Lanes that run on a thread of their own and overlap the main one.
OFF_MAIN_LANES = ("device", "collective_lane", "snapshot_lane")
#: The spans in which a thread hands work to the chip or takes it
#: back: what they spend off the CPU is the host held by the chip.
WAITS = ("h2d", "dispatch", "fetch")
#: The main thread's parent frames: their seconds are what no work
#: span inside them covers.
FRAMES = ("host", "ingest", "readback", "eof")


def lane_and_leaf(phase: str) -> Tuple[str, str]:
    """``device/prep`` -> ``("device", "prep")``; ``prep`` ->
    ``("prep", "prep")``: a phase with no lane is its own."""
    return phase.split("/", 1)[0], phase.rpartition("/")[2]


def on_lane(phase: str) -> bool:
    return lane_and_leaf(phase)[0] in OFF_MAIN_LANES


def is_wait(phase: str) -> bool:
    return lane_and_leaf(phase)[1] in WAITS


def phase_cpu(run: Dict[str, Any]) -> Optional[Dict[str, float]]:
    """CPU seconds by phase; None under a program without them."""
    cpu = {k[4:]: v for k, v in run["counters"].items() if k.startswith("cpu:")}
    return cpu or None


def offcpu_pct(run: Dict[str, Any], want: Callable[[str], bool]) -> Optional[float]:
    """Off-CPU seconds of the phases ``want`` picks, as a share of the
    window's wall time; None where the program records no CPU seconds
    or none of the phases ran in this process."""
    cpu = phase_cpu(run)
    if cpu is None or not run["window_s"]:
        return None
    picked = [p for p in run["phases"] if p in cpu and want(p)]
    if not picked:
        return None
    off = sum(run["phases"][p] - cpu[p] for p in picked)
    return 100.0 * max(off, 0.0) / run["window_s"]


def lanes_are_inline() -> bool:
    """At pipeline depth 1 the lane's tasks run on the main thread."""
    try:
        from bytewax_tpu.engine.pipeline import pipeline_depth

        return pipeline_depth() <= 1
    except (ImportError, ValueError):
        return False


def main_seconds(run: Dict[str, Any]) -> float:
    """Seconds of the window under any ledger phase of the main
    thread (spans, parent frames, and the waits recorded from a
    duration)."""
    inline = lanes_are_inline()
    return sum(s for p, s in run["phases"].items() if inline or not on_lane(p))


def print_table(run: Dict[str, Any], out=None) -> None:
    """A line a phase: wall, CPU and off-CPU seconds of the window."""
    out = out or sys.stderr
    cpu = phase_cpu(run) or {}
    print(f"cpu_reduce: window {run['window_s']:.6f} s", file=out)
    for phase, wall in sorted(run["phases"].items(), key=lambda kv: -kv[1]):
        if phase in cpu:
            print(
                f"cpu_reduce: {phase:<22} wall {wall:.6f} s cpu {cpu[phase]:.6f} s "
                f"off {wall - cpu[phase]:.6f} s",
                file=out,
            )
        else:
            print(f"cpu_reduce: {phase:<22} wall {wall:.6f} s (no cpu: a duration)", file=out)
