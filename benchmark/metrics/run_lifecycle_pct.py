"""A job's start-up (flatten, plan, driver, runtimes, up to the run
loop's first pass) and teardown (from the loop's exit to the return), as
a share of the window: ledger seconds of ``startup`` and ``teardown``
over ``window_s``."""


def read(run):
    from benchmark import span_reduce

    return span_reduce.phase_pct(run, "startup", "teardown")
