"""Seconds the main thread spent in its spans without a processor, as
a share of the window: wall less ``cpu:`` seconds over its work spans
and parent frames (``ingest``, ``host``, ``readback``, ``eof/*``,
start-up and teardown, the close phases) but the three spans that
wait for the chip.  Prints wall, CPU and off-CPU seconds by phase to
stderr."""


def read(run):
    from benchmark import cpu_reduce

    value = cpu_reduce.offcpu_pct(
        run, lambda p: not cpu_reduce.on_lane(p) and not cpu_reduce.is_wait(p)
    )
    if value is not None:
        cpu_reduce.print_table(run)
    return value
