"""Keys the session tier gave an id in the window and still held when
the window's last poll was handed out, over the keys it gave an id in
the window: 100 where no key is ever let go, about the share that
still has an open session where a key is let go with its last one.
The same counters, sampled the same way, as ``keys_held_pct``
(``window_keys_opened`` and ``window_keys_retired``, at every poll of
the flow), which the session tier writes since it lets keys go."""

from benchmark.metrics import keys_held_pct


def read(run):
    return keys_held_pct.read(run)
