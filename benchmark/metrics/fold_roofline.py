"""The least time the chip could take for the fold's bytes, over the
device time of the fold's programs in the traced stretch.

The bytes are what the fold needs by its shapes, whatever implements
it (``benchmark/roofline.py``); the fold does a handful of operations
a row, so memory bounds it and the peak is HBM bytes a second."""


def read(run):
    from benchmark import roofline

    trace = run.get("trace")
    if not trace:
        return None
    shapes = run["cell"].cfg["fold_shapes"]
    calls, seconds = roofline.fold_time(shapes, trace["programs"])
    if not calls or seconds <= 0:
        return None
    events = roofline.events_in_stretch(run, calls)
    if not events:
        return None
    needed = roofline.fold_bytes(shapes, events, calls)
    peak = roofline.peak(run["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * (needed / peak) / seconds
