"""How long the dispatch pipeline's worker was busy, as a share of the
window: ledger seconds of ``device`` (the lane's own time) and every
``device/*`` (its spans) over ``window_s``."""


def read(run):
    from benchmark import span_reduce

    return span_reduce.phase_pct(run, "device/*")
