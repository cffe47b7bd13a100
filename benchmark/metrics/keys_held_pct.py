"""Keys the window tier gave an id in the window and still held when
the window's last poll was handed out, over the keys it gave an id in
the window: 100 where no key is ever let go, about the share that
still has an open window where a key is retired with its last one.
Read from the samples the flow takes of the program's counters
``window_keys_opened`` and ``window_keys_retired`` at every poll
(after end of input every key is gone, so the window's gain says
nothing)."""


def read(run):
    samples = run["data"].get("counter_samples")
    schedule = run.get("schedule")
    if not samples or schedule is None:
        return None
    in_window = [s for s in samples if s[0] >= schedule.warm_rows]
    if len(in_window) < 2 or in_window[-1][1] is None:
        return None  # a program without the counters
    (_lo, opened0, retired0, *_), (_hi, opened1, retired1, *_) = in_window[0], in_window[-1]
    opened = opened1 - (opened0 or 0)
    if not opened:
        return None
    return 100.0 * (opened - ((retired1 or 0) - (retired0 or 0))) / opened
