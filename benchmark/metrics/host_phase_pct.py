"""The ``host`` bucket of the program's phase ledger (ingest, host,
readback phases) as a share of all time the ledger attributed in the
window.  The ledger's ``device`` bucket is host time spent waiting on
the device, so only ``host`` is read."""


def read(run):
    from bytewax_tpu.engine import flight

    fractions = flight.ledger_fractions(run["phases"])
    if fractions is None:
        return None
    return 100.0 * fractions["host"]
