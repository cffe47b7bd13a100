"""What coalescing makes of the source's polls: item rows over the
deliveries that carried them in the window, the program's
``ingest_rows_itemized`` over ``ingest_deliveries_itemized``.  None
under a program without the second counter."""


def read(run):
    rows = run["counters"].get("ingest_rows_itemized")
    deliveries = run["counters"].get("ingest_deliveries_itemized")
    if not rows or not deliveries:
        return None
    return rows / deliveries
