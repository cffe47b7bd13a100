"""Key encoding (`VocabMap.sync`, `KeyEncoder.encode`, key-id
allocation) as a share of the window: ledger seconds of ``encode`` on
every lane over ``window_s``."""


def read(run):
    from benchmark import span_reduce

    return span_reduce.phase_pct(run, "encode")
