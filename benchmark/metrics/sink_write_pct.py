"""The sink's ``write_batch`` calls as a share of the window: ledger
seconds of ``sink`` over ``window_s``."""


def read(run):
    from benchmark import span_reduce

    return span_reduce.phase_pct(run, "sink")
