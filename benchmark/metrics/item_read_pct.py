"""The itemized reader (`_InputRt.poll`: an itemized `next_batch` and
the coalescing polls that follow it, one span a delivery) as a share
of the window: ledger seconds of ``read`` over ``window_s``.  None
under a program without the span."""


def read(run):
    from benchmark import span_reduce

    return span_reduce.phase_pct(run, "read")
