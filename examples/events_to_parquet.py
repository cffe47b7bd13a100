"""Stream web events into a partitioned Parquet dataset (reference:
``examples/events_to_parquet.py``).

TPU-idiomatic twist: events flow as columnar :class:`ArrayBatch`
micro-batches end-to-end, and the sink implements
``write_array_batch`` so columns convert to an Arrow table with no
per-row Python (the engine calls it whenever a columnar batch reaches
a dynamic sink).

Output goes to ``$PARQUET_DEMO_OUT`` (default: a fresh temp dir);
the sink prints the location when it closes.
"""

import os
import tempfile
from typing import Any, List, Optional

import numpy as np

import bytewax_tpu.operators as op
from bytewax_tpu.dataflow import Dataflow
from bytewax_tpu.engine.arrays import ArrayBatch
from bytewax_tpu.inputs import FixedPartitionedSource, StatefulSourcePartition
from bytewax_tpu.outputs import DynamicSink, StatelessSinkPartition

_out_dir_cache = []


def _out_dir() -> str:
    """Resolved lazily so importing the module never creates a dir."""
    if not _out_dir_cache:
        _out_dir_cache.append(
            os.environ.get("PARQUET_DEMO_OUT")
            or tempfile.mkdtemp(prefix="parquet_demo_")
        )
    return _out_dir_cache[0]

_PAGES = ["/", "/about", "/product", "/blog", "/checkout"]


class SimulatedPartition(StatefulSourcePartition):
    """Synthesizes columnar batches of fake web events (the reference
    uses the ``fake_web_events`` package; same shape, no dependency)."""

    def __init__(self):
        self._rng = np.random.RandomState(7)
        self._remaining = 10

    def next_batch(self) -> Any:
        if self._remaining == 0:
            raise StopIteration()
        self._remaining -= 1
        n = 50
        pages = self._rng.choice(_PAGES, size=n)
        days = self._rng.randint(1, 4, size=n)
        return ArrayBatch(
            {
                "page_url_path": pages,
                "year": np.full(n, 2022, dtype=np.int16),
                "month": np.full(n, 1, dtype=np.int8),
                "day": days.astype(np.int8),
                "user_id": self._rng.randint(0, 5, size=n).astype(np.int32),
                "duration_ms": self._rng.randint(10, 5000, size=n).astype(
                    np.int32
                ),
            }
        )

    def snapshot(self) -> Any:
        return None


class FakeWebEventsSource(FixedPartitionedSource):
    def list_parts(self) -> List[str]:
        return ["singleton"]

    def build_part(
        self, step_id: str, for_part: str, resume_state: Optional[Any]
    ) -> SimulatedPartition:
        return SimulatedPartition()


class ParquetPartition(StatelessSinkPartition):
    """Columnar fast path: batches land as Arrow tables straight from
    the device-friendly column dict."""

    def write_array_batch(self, batch: ArrayBatch) -> None:
        from pyarrow import Table, parquet

        table = Table.from_pydict(
            {name: np.asarray(col) for name, col in batch.cols.items()}
        )
        parquet.write_to_dataset(
            table,
            root_path=_out_dir(),
            partition_cols=["year", "month", "day"],
        )

    def close(self) -> None:
        print(f"wrote parquet dataset under {_out_dir()}")

    def write_batch(self, items: List[Any]) -> None:
        # Host-tier degrade: per-row dicts back into one table.
        from pyarrow import Table, parquet

        parquet.write_to_dataset(
            Table.from_pylist(items),
            root_path=_out_dir(),
            partition_cols=["year", "month", "day"],
        )


class ParquetSink(DynamicSink):
    def build(
        self, step_id: str, worker_index: int, worker_count: int
    ) -> ParquetPartition:
        return ParquetPartition()


flow = Dataflow("events_to_parquet")
stream = op.input("input", flow, FakeWebEventsSource())
op.output("out", stream, ParquetSink())

if __name__ == "__main__":
    from bytewax_tpu.testing import run_main

    run_main(flow)
