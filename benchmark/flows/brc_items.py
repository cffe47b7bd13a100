"""The one-billion-row challenge through the itemized front door:
``flows/brc.py``'s file, plain reference and comparison, under the
flow an upstream bytewax user writes against the public API and moves
over unchanged but for the fold's name: line items from
``FileSource`` at its documented defaults, a Python ``op.map`` that
parses a row, string keys into the keyed fold.

Nothing the sink receives says which door the rows came through.  So
the flow samples the program's own ingest counters
(``flight.RECORDER.counters``: ``ingest_rows_itemized``,
``ingest_rows_columnar``) when a job is built and again at its sink
write, and the comparison adds ``off_items``: non-zero unless the
itemized count grew by exactly the job's rows and the columnar one by
none.  Nothing of the program is patched and no environment variable
is set.
"""

import inspect
from typing import Any, Dict, List, Tuple

import numpy as np

from benchmark.flows import brc
from benchmark.flows.brc import make_data, reference  # noqa: F401  (the cell's own, unchanged)

#: ``(ingest_rows_itemized, ingest_rows_columnar)`` when the job now
#: running was built; ``pack`` takes only the sink's items, so the
#: module holds it between the two.
_at_job_start = [(0, 0)]


def _ingest_rows() -> Tuple[int, int]:
    """The program's two ingest counters, 0 where it has none yet."""
    from bytewax_tpu.engine import flight

    counters = flight.RECORDER.counters
    return (
        int(counters.get("ingest_rows_itemized", 0)),
        int(counters.get("ingest_rows_columnar", 0)),
    )


def parse_line(line: str) -> Tuple[str, float]:
    station, _, temp = line.partition(";")
    return (station, float(temp))


def build_flow(cfg, data, source, sink):
    """``FileSource(path)`` -> ``op.map(parse_line)`` ->
    ``xla.stats_final`` -> ``op.output`` (``source`` is unused: a job
    reads its file as fast as the engine polls it); notes where the
    ingest counters stand as the job starts.  The connector's
    defaults are the deployment's shapes: held here, so a changed
    default fails the run and does not quietly measure another
    deployment."""
    import bytewax_tpu.operators as op
    from bytewax_tpu import xla
    from bytewax_tpu.connectors.files import FileSource
    from bytewax_tpu.dataflow import Dataflow

    shapes = cfg["shapes"]
    defaults = inspect.signature(FileSource).parameters
    assert defaults["batch_size"].default == shapes["source_batch_size"]
    assert defaults["columnar"].default is shapes["source_columnar"] is False
    lines = FileSource(data["path"])
    assert len(lines.list_parts()) == shapes["part_count"]
    _at_job_start[0] = _ingest_rows()
    flow = Dataflow("bench_brc_items")
    s = op.input("inp", flow, lines)
    s = op.map("parse", s, parse_line)
    stats = xla.stats_final("stats", s)
    op.output("out", stats, sink)
    return flow


def door_sample(start: Tuple[int, int], end: Tuple[int, int]) -> np.ndarray:
    return np.array([*start, *end], dtype=np.int64)


def pack(items: List[Any]) -> Dict[str, np.ndarray]:
    """``flows/brc.py``'s arrays and the ingest counters as they stand
    at this sink write, beside the job's starting point."""
    out = brc.pack(items)
    out["door"] = door_sample(_at_job_start[0], _ingest_rows())
    return out


def result_arrays(cfg, packs: List[Dict[str, np.ndarray]]) -> Dict[str, np.ndarray]:
    """One job's sink writes as the columns the comparison takes, with
    the last write's sample."""
    out = brc.result_arrays(cfg, packs)
    out["door"] = packs[-1]["door"]
    return out


def off_items(cfg, got, want) -> int:
    """0 where the job's sample shows ``ingest_rows_itemized`` grown
    by exactly the job's rows and ``ingest_rows_columnar`` by none; 1
    otherwise, and for a result that carries no sample."""
    sample = got.get("door")
    if sample is None:
        return 1
    items0, columns0, items1, columns1 = (int(v) for v in sample)
    rows = int(want["count"].sum())
    return 0 if items1 - items0 == rows and columns1 == columns0 else 1


def compare(cfg, got, want) -> Dict[str, float]:
    return {**brc.compare(cfg, got, want), "off_items": off_items(cfg, got, want)}


def control_results(cfg, data, which: str) -> Dict[str, np.ndarray]:
    """``flows/brc.py``'s controls with a well-formed sample, so that
    each fails by its own number alone; and ``columnar_door``: the
    reference's answers from rows that came in as columns (what the
    same job through ``BrcFileSource`` leaves in the counters;
    ``benchmark/tests/test_brc_items.py`` drives that job whole)."""
    rows = int(data["rows"])
    if which == "columnar_door":
        out = reference(cfg, data)
        out["door"] = door_sample((0, 0), (0, rows))
    else:
        out = brc.control_results(cfg, data, which)
        out["door"] = door_sample((0, 0), (rows, 0))
    return out


CONTROLS = brc.CONTROLS + ("columnar_door",)
