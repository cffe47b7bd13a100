"""The window step's watermark pass at ingest (a stable sort by key and
a segmented prefix maximum a delivery) as a share of the window: ledger
seconds of ``watermark`` over ``window_s``."""


def read(run):
    from benchmark import span_reduce

    return span_reduce.phase_pct(run, "watermark")
