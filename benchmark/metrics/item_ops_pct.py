"""The per-item operators (`_FlatMapBatchRt.process`: the mapper's
Python call a row over one delivery of items; `op.map`, `filter`,
`key_on` and the rest are built on it) as a share of the window:
ledger seconds of ``item_ops`` over ``window_s``.  None under a
program without the span."""


def read(run):
    from benchmark import span_reduce

    return span_reduce.phase_pct(run, "item_ops")
