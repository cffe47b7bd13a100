"""The general traffic generator: a seeded stream served on a schedule,
as a consumer sees a topic that already holds it (a backfill, or a
consumer that fell behind).

A traffic file gives ``poll_rows`` and ``warmup``, the stretches of the
stream served during set-up, each with its own poll size.  After them
the window starts: the whole stream is due at once, handed out in polls
of at most ``poll_rows`` for as long as the engine comes to read, and
the input ends at the deadline (``t0 + seconds``).
"""

import time
from typing import Any, Callable, Dict, List, Optional


class Schedule:
    """Which rows are handed out when, and the record of every poll.

    ``warmup`` is a list of ``{"rows": n, "poll_rows": m}``: those
    rows come first and are set-up, before the window.  The window
    starts (``t0``) at the first poll after them.
    """

    def __init__(
        self,
        warmup: List[Dict[str, int]],
        poll_rows: int,
        seconds: float,
        clock: Callable[[], float] = time.monotonic,
        on_window_start: Optional[Callable[[float], None]] = None,
        on_end: Optional[Callable[[float], None]] = None,
    ):
        self._warm_polls: List[tuple] = []
        at = 0
        for stretch in warmup:
            end = at + int(stretch["rows"])
            while at < end:
                nxt = min(end, at + int(stretch["poll_rows"]))
                self._warm_polls.append((at, nxt))
                at = nxt
        self._warm_polls.reverse()
        self.warm_rows = at
        self.poll_rows = poll_rows
        self.seconds = seconds
        self.clock = clock
        self.on_window_start = on_window_start
        self.on_end = on_end
        self.pos = 0
        self.t0: Optional[float] = None
        self.ended_at: Optional[float] = None
        #: (clock time, first row, end row) of every poll that handed
        #: out rows, set-up's included.
        self.polls: List[tuple] = []
        self.max_gap_s = 0.0

    def next_range(self) -> tuple:
        """``(lo, hi)`` of the rows to hand out now; raises
        ``StopIteration`` at the deadline."""
        now = self.clock()
        if self.polls:
            self.max_gap_s = max(self.max_gap_s, now - self.polls[-1][0])
        if self._warm_polls:
            lo, hi = self._warm_polls.pop()
        else:
            if self.t0 is None:
                self.t0 = now
                if self.on_window_start is not None:
                    self.on_window_start(now)
            if now - self.t0 >= self.seconds:
                self.ended_at = now
                if self.on_end is not None:
                    self.on_end(now)
                raise StopIteration()
            lo, hi = self.pos, self.pos + self.poll_rows
        self.polls.append((now, lo, hi))
        self.pos = hi
        return lo, hi

    @property
    def served_rows(self) -> int:
        return self.pos

    @property
    def window_rows(self) -> int:
        return max(0, self.pos - self.warm_rows)

    def window_polls(self) -> List[tuple]:
        """The polls of the window as ``(seconds since t0, lo, hi)``."""
        return [(at - self.t0, lo, hi) for at, lo, hi in self.polls if lo >= self.warm_rows]


def scheduled_source(schedule: Schedule, make_batch: Callable[[int, int], Any]):
    """A one-partition source over ``schedule``; ``make_batch(lo, hi)``
    builds what the flow's input takes for those rows."""
    import jax

    from bytewax_tpu.inputs import FixedPartitionedSource, StatefulSourcePartition

    class _Part(StatefulSourcePartition):
        def next_batch(self):
            with jax.profiler.TraceAnnotation("bench_poll"):
                return make_batch(*schedule.next_range())

        def snapshot(self):
            return schedule.pos

    class _ScheduledSource(FixedPartitionedSource):
        def list_parts(self):
            return ["stream"]

        def build_part(self, step_id, for_part, resume_state):
            return _Part()

    return _ScheduledSource()
