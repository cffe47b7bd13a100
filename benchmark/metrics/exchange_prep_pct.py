"""The exchange's host half (destinations, the per-(source block,
destination) counts, the bucket capacity, the step cache) as a share
of the window: ledger seconds of ``exchange`` on every lane over
``window_s``."""


def read(run):
    from benchmark import span_reduce

    return span_reduce.phase_pct(run, "exchange")
