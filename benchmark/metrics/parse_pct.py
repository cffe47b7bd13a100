"""The source's `parse` span (read, split, native parse, vocabulary) as
a share of the window: ledger seconds of ``parse`` over ``window_s``."""


def read(run):
    from benchmark import span_reduce

    return span_reduce.phase_pct(run, "parse")
