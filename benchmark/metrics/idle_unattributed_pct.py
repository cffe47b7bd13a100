"""Idle seconds of the first chip that no span of the engine covers,
over all its idle seconds in the traced stretch.  Read again from the
profiler's trace (``<cwd>/trace`` while the metrics are read); prints
idle and self seconds by span, and the work spans a delivery, to
stderr."""

import os
import sys


def read(run):
    from benchmark import span_reduce

    if not run.get("trace") or not os.path.isdir("trace"):
        return None
    try:
        reduced = span_reduce.reduce_dir("trace")
    except FileNotFoundError:
        return None
    if reduced is None or reduced["idle_s"] <= 0:
        return None
    span_reduce.print_table(reduced)
    print(
        "span_reduce: work spans a delivery",
        span_reduce.spans_a_delivery(run["counters"]),
        "a lane task",
        span_reduce.spans_a_delivery(run["counters"], "device"),
        file=sys.stderr,
    )
    return 100.0 * reduced["unattributed_s"] / reduced["idle_s"]
