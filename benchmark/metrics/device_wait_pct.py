"""Seconds a host thread was held by the chip, as a share of the
window: wall less ``cpu:`` seconds of ``h2d``, ``dispatch`` and
``fetch`` on every lane (the worker's, the main thread's at a notify,
``eof/fetch`` at end of input)."""


def read(run):
    from benchmark import cpu_reduce

    return cpu_reduce.offcpu_pct(run, cpu_reduce.is_wait)
