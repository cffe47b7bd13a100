"""Seconds the engine's main thread waited for its dispatch pipeline to
drain, as a share of the window."""


def read(run):
    stalled = run["counters"].get("pipeline_flush_stall_seconds")
    if stalled is None:
        return None
    return 100.0 * stalled / run["window_s"]
