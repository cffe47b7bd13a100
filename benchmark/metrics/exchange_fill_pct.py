"""Real rows over the bucket slots the ``all_to_all`` moved and the
scatter walked in the window: the program's ``exchange_rows`` over
``exchange_bucket_rows`` (``n_shards² × capacity`` a step)."""


def read(run):
    rows = run["counters"].get("exchange_rows")
    slots = run["counters"].get("exchange_bucket_rows")
    if not rows or not slots:
        return None
    return 100.0 * rows / slots
