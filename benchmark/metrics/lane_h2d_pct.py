"""Padding, ``device_put`` and the jitted call up to its return, as a
share of the window: ledger seconds of ``h2d`` and ``dispatch`` on
every lane over ``window_s``."""


def read(run):
    from benchmark import span_reduce

    return span_reduce.phase_pct(run, "h2d", "dispatch")
