"""Keys the session tier added to its by-name record of keys let go in
the window, over the keys it gave an id in the window.  A key let go
leaves its clock and next session id behind by name, as the host
tier's never-empty session logic keeps them, so the record grows by
every key that goes and does not come back: about 100 less
``session_keys_held_pct`` where no key returns, 0 for a tier that
keeps nothing of a key let go.  Read from the samples the flow takes
of the program's counters ``window_keys_opened`` and
``session_keys_remembered`` (the record's size) at every poll."""


def read(run):
    from benchmark.flows.nexmark_q11 import SAMPLED

    samples = run["data"].get("counter_samples")
    schedule = run.get("schedule")
    if not samples or schedule is None:
        return None
    in_window = [s for s in samples if s[0] >= schedule.warm_rows]
    opened_at = 1 + SAMPLED.index("window_keys_opened")
    held_at = 1 + SAMPLED.index("session_keys_remembered")
    if len(in_window) < 2 or in_window[-1][held_at] is None:
        return None  # a program without the record or its counter
    first, last = in_window[0], in_window[-1]
    opened = (last[opened_at] or 0) - (first[opened_at] or 0)
    if not opened:
        return None
    return 100.0 * (last[held_at] - (first[held_at] or 0)) / opened
