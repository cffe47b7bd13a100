"""Host work ahead of a fold (composite ids, the unique pass, slot
allocation, dtype checks, pending resets) as a share of the window:
ledger seconds of ``prep`` on every lane over ``window_s``."""


def read(run):
    from benchmark import span_reduce

    return span_reduce.phase_pct(run, "prep")
