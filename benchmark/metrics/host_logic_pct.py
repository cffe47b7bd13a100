"""The host tier's keyed logics (``_StatefulBatchRt``'s calls into a
step's per-key ``on_batch`` for a delivery: NEXmark Q5's hot-items
stage) as a share of the window: ledger seconds of ``logic`` over
``window_s``."""


def read(run):
    from benchmark import span_reduce

    return span_reduce.phase_pct(run, "logic")
