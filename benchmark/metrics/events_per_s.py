"""All input records of the window over all its time, from the
window's start to the last result written (the drain included)."""


def read(run):
    return run["events"] / run["window_s"]
