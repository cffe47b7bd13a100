"""The due scan over the open windows and the device-to-host copy of
the slot table with the wait for it, as a share of the window: ledger
seconds of ``close_scan`` and ``fetch`` on every lane over
``window_s``."""


def read(run):
    from benchmark import span_reduce

    return span_reduce.phase_pct(run, "close_scan", "fetch")
