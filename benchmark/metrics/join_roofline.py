"""The least time the chip could take for the join's bytes, over the
device time of the join's programs in the traced stretch.

The bytes are what the join needs by the configuration's
``join_shapes``, whatever implements it: each input row of the polls
inside the stretch written to the row store and read back once, and
each Query 8 row those rows make (the flow's plain reference counts
them) written.  The join moves rows and does no arithmetic to speak
of, so memory bounds it and the peak is HBM bytes a second.  None
where the configuration names no join, the trace holds none of its
programs or the stretch no poll."""


def read(run):
    from benchmark import roofline

    trace = run.get("trace")
    shapes = run["cell"].cfg.get("join_shapes")
    if not trace or not shapes:
        return None
    prefixes = tuple(shapes["programs"])
    programs = trace["programs"]
    seconds = sum(v[1] for name, v in programs.items() if name.startswith(prefixes))
    schedule = run.get("schedule")
    if seconds <= 0 or schedule is None:
        return None
    lo, hi = trace["stretch_s"]
    polls = [(a, b) for at, a, b in schedule.window_polls() if lo <= at < hi]
    if not polls:
        return None
    flow, cfg = run["cell"].flow, run["cell"].cfg
    rows = roofline.events_in_stretch(run, len(polls))
    out = sum(flow.output_rows(cfg, run["data"], a, b, schedule.served_rows) for a, b in polls)
    needed = 2 * shapes["stored_row_bytes"] * rows + shapes["output_row_bytes"] * out
    peak = roofline.peak(run["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * (needed / peak) / seconds
