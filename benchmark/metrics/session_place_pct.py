"""The session tier's placement of a delivery's runs (each run matched
against its key's open sessions, then the sessions created, widened
and merged, and the slot each run folds into) as a share of the
window: ledger seconds of ``session_place`` on every lane over
``window_s``."""


def read(run):
    from benchmark import span_reduce

    return span_reduce.phase_pct(run, "session_place")
