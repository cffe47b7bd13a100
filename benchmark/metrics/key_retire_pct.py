"""Letting keys go with their last window (finding them in the close
path, then giving up their ids, clocks and encoder entries) as a share
of the window: ledger seconds of ``retire`` on every lane over
``window_s``."""


def read(run):
    from benchmark import span_reduce

    return span_reduce.phase_pct(run, "retire")
