"""The join tier's placement of a delivery's rows (each row's slot
found or opened, its region of the row store made room in, and the
rows written to the device, less the pad and `device_put` of the write,
which are the ``h2d`` span inside it) as a share of the window: ledger seconds
of ``join_place`` on every lane over ``window_s``."""


def read(run):
    from benchmark import span_reduce

    return span_reduce.phase_pct(run, "join_place")
