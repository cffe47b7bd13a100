"""Wall seconds of the program's runs that its main thread spent under
no ledger phase at all, as a share of the window: the counter
``run_wall_seconds`` (start-up's begin to teardown's end, advanced
once a pass of the run loop) less every phase of the main thread (work
spans, parent frames, ``flush``, ``idle``, ``close_flush``, ``eof/*``,
``startup``, ``teardown``).  Prints beside it what the parent frames
kept for themselves (``host``, ``ingest``, ``readback``, ``eof``:
under a frame, under no work span) to stderr.  None under a program
without the counter."""

import sys


def read(run):
    from benchmark import cpu_reduce

    run_wall = run["counters"].get("run_wall_seconds")
    if run_wall is None or not run["window_s"]:
        return None
    named = cpu_reduce.main_seconds(run)
    print(
        f"main_unnamed: run_wall {run_wall:.6f} s, under a phase {named:.6f} s",
        file=sys.stderr,
    )
    for frame in cpu_reduce.FRAMES:
        if frame in run["phases"]:
            print(
                f"main_unnamed: frame {frame:<10} self {run['phases'][frame]:.6f} s",
                file=sys.stderr,
            )
    return 100.0 * max(run_wall - named, 0.0) / run["window_s"]
