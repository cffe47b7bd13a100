"""The one-billion-row challenge on a four-chip host: ``flows/brc.py``'s
file, flow, plain reference and comparison, with the placement held
too.

The engine shards a keyed step's state over the local mesh by default
wherever jax has more than one device, and nothing the sink receives
says whether it did.  So the flow samples the program's own exchange
counters (``flight.RECORDER.counters``: ``exchange_rows``, the real
rows handed to a mesh-sharded step; ``exchange_blocks``, the blocks
of the state the last such step ran on) when a job is built and again
at its sink write, and the comparison adds ``off_mesh``: non-zero
unless the state was in ``shapes.shards`` blocks and every row of the
job went through the exchange.  Nothing of the program is patched and
no environment variable is set.
"""

from typing import Any, Dict, List

import numpy as np

from benchmark.flows import brc
from benchmark.flows.brc import make_data, reference  # noqa: F401  (the cell's own, unchanged)

#: ``exchange_rows`` when the job now running was built; ``pack``
#: takes only the sink's items, so the module holds it between the two.
_rows_at_job_start = [0]


def _counter(name: str) -> int:
    """The program's counter, 0 where it has none (a program without
    the counters; a process in which no sharded step has run yet)."""
    from bytewax_tpu.engine import flight

    return int(flight.RECORDER.counters.get(name, 0))


def build_flow(cfg, data, source, sink):
    """``flows/brc.py``'s three steps; notes where ``exchange_rows``
    stands as the job starts."""
    _rows_at_job_start[0] = _counter("exchange_rows")
    return brc.build_flow(cfg, data, source, sink)


def exchange_sample(start: int, end: int, blocks: int) -> np.ndarray:
    return np.array([start, end, blocks], dtype=np.int64)


def pack(items: List[Any]) -> Dict[str, np.ndarray]:
    """``flows/brc.py``'s arrays and the exchange counters as they
    stand at this sink write, beside the job's starting point."""
    out = brc.pack(items)
    out["exchange"] = exchange_sample(
        _rows_at_job_start[0],
        _counter("exchange_rows"),
        _counter("exchange_blocks"),
    )
    return out


def result_arrays(cfg, packs: List[Dict[str, np.ndarray]]) -> Dict[str, np.ndarray]:
    """One job's sink writes as the columns the comparison takes, with
    the last write's sample."""
    out = brc.result_arrays(cfg, packs)
    out["exchange"] = packs[-1]["exchange"]
    return out


def off_mesh(cfg, got, want) -> int:
    """0 where the job's sample shows the state in ``shapes.shards``
    blocks and ``exchange_rows`` grown by exactly the job's rows; 1
    otherwise, and for a result that carries no sample."""
    sample = got.get("exchange")
    if sample is None:
        return 1
    start, end, blocks = (int(v) for v in sample)
    rows = int(want["count"].sum())
    on_mesh = blocks == int(cfg["shapes"]["shards"]) and end - start == rows
    return 0 if on_mesh else 1


def compare(cfg, got, want) -> Dict[str, float]:
    return {**brc.compare(cfg, got, want), "off_mesh": off_mesh(cfg, got, want)}


def control_results(cfg, data, which: str) -> Dict[str, np.ndarray]:
    """``flows/brc.py``'s controls with a well-formed sample, so that
    each fails by its own number alone; and ``one_device``: the
    reference's answers from a state that was in one block."""
    shards = int(cfg["shapes"]["shards"])
    if which == "one_device":
        out = reference(cfg, data)
        shards = 1
    else:
        out = brc.control_results(cfg, data, which)
    out["exchange"] = exchange_sample(0, int(data["rows"]), shards)
    return out


CONTROLS = brc.CONTROLS + ("one_device",)
