"""Seconds in the full collections the engine runs at an epoch's close
(at most one a second), as a share of the window: ledger seconds of
the span ``gc``.  None under a program without the span; 0 where no
collection fell in the window."""


def read(run):
    from benchmark import span_reduce

    if "run_wall_seconds" not in run["counters"]:
        return None  # a program from before the span
    return span_reduce.phase_pct(run, "gc") or 0.0
