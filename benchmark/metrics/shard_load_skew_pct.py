"""How far the fullest source block of a step stands over an even
share, summed over the window's steps: the program's
``exchange_rows_max_block`` times the shards over ``exchange_rows``,
less one.  0 where every chip sends the same; ``shards - 1`` (300 on
four) where one chip sends everything."""


def read(run):
    rows = run["counters"].get("exchange_rows")
    fullest = run["counters"].get("exchange_rows_max_block")
    shards = run["cell"].cfg["shapes"].get("shards")
    if not rows or fullest is None or not shards:
        return None
    return 100.0 * (fullest * shards / rows - 1.0)
