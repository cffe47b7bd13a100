"""The session tier's close as a share of the window: ledger seconds,
on every lane, of ``session_close`` (the delivery's keys retimed, the
due scan over the open sessions, then the closed rows taken out, their
slots released, the events built and the keys left without a session
found), of the ``fetch`` and ``close_emit`` the slot table records
inside it (the device-to-host copy and the fetched rows turned into
states: the session tier is the only window step of its cell) and of
``retire`` (the keys let go on the main thread), over ``window_s``."""


def read(run):
    from benchmark import span_reduce

    return span_reduce.phase_pct(run, "session_close", "fetch", "close_emit", "retire")
