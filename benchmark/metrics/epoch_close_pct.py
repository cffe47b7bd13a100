"""Seconds spent closing epochs, as a share of the window."""


def read(run):
    closing = run["counters"].get("epoch_close_seconds")
    if closing is None:
        return None
    return 100.0 * closing / run["window_s"]
