"""Entries of a key-indexed array that the key vocabulary's syncs walk
a row synced, in the window: the program's counters ``vocab_walked``
(the counting pass over a delivery's id range, and the entries copied
or filled when the id table, its reverse index or a window-tier key
column grows) over ``vocab_rows`` (rows handed to a sync).  About the
delivery's id span over its rows, plus the keys born a row; a sync
that walked the whole vocabulary would read its length over the rows
a delivery.  None under a program without the counters, or where no
row was synced in the window."""


def read(run):
    counters = run["counters"]
    rows = counters.get("vocab_rows")
    if not rows:
        return None
    return counters.get("vocab_walked", 0) / rows
