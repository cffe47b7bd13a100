"""The per-window Python of a close (fetched slots to states, finalize,
discard, two events and a metadata object a window) as a share of the
window: ledger seconds of ``close_emit`` on every lane over
``window_s``."""


def read(run):
    from benchmark import span_reduce

    return span_reduce.phase_pct(run, "close_emit")
