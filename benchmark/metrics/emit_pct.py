"""Routing the window events downstream on the main thread, as a share
of the window: ledger seconds of ``emit`` over ``window_s``."""


def read(run):
    from benchmark import span_reduce

    return span_reduce.phase_pct(run, "emit")
