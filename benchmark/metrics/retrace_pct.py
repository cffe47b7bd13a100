"""Seconds spent tracing, lowering and loading programs that were not
compiled, as a share of the window: the program's counters
``jit_trace_seconds`` + ``jit_lower_seconds`` +
``xla_cache_load_seconds`` (``compiles_in_window`` sees none of them).
Prints the seconds by function (``jit_stage_seconds[...]``, a real
compile included) to stderr.  None under a program without the
counters; 0 where they stood still."""

import sys

STAGES = ("jit_trace_seconds", "jit_lower_seconds", "xla_cache_load_seconds")


def read(run):
    counters = run["counters"]
    if "jit_trace_seconds" not in counters or not run["window_s"]:
        return None
    by_fun = {
        k[len("jit_stage_seconds["):-1]: v
        for k, v in counters.items()
        if k.startswith("jit_stage_seconds[") and v > 0
    }
    print(
        "retrace: traces", counters.get("jit_trace_count", 0),
        "cache loads", counters.get("xla_cache_load_count", 0),
        "compiles", counters.get("xla_compile_count", 0),
        file=sys.stderr,
    )
    for fun, seconds in sorted(by_fun.items(), key=lambda kv: -kv[1])[:16]:
        print(f"retrace: {fun:<32} {seconds:.6f} s", file=sys.stderr)
    return 100.0 * sum(counters.get(k, 0.0) for k in STAGES) / run["window_s"]
