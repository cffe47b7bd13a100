"""Seconds the dispatch pipeline's worker spent in its spans without a
processor (waiting for the interpreter, pre-empted), as a share of the
window: wall less ``cpu:`` seconds over ``device`` (the lane's own
time) and every ``device/*`` but the three spans that wait for the
chip (``device_wait_pct`` reads those)."""


def read(run):
    from benchmark import cpu_reduce

    return cpu_reduce.offcpu_pct(
        run, lambda p: cpu_reduce.on_lane(p) and not cpu_reduce.is_wait(p)
    )
