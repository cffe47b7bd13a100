"""The join tier's close as a share of the window: ledger seconds, on
every lane, of ``join_close`` (the delivery's keys retimed, the due
scan over the open slots, the expansion program over the closing
windows, the read-back of the output rows' values, the events built
and the slots and store regions given back) over ``window_s``."""


def read(run):
    from benchmark import span_reduce

    return span_reduce.phase_pct(run, "join_close")
