"""Items to columns (`_AggTable.update_items`: native `kv_encode`,
the id dictionary's growth, the all-integer lane, the id gather) as a
share of the window: ledger seconds of ``promote`` on every lane over
``window_s``.  None under a program without the span (which books
the same work as ``prep``)."""


def read(run):
    from benchmark import span_reduce

    return span_reduce.phase_pct(run, "promote")
