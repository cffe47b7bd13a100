"""Mesh-sharded keyed aggregation state.

The multi-chip placement of the keyed slot table
(:class:`bytewax_tpu.engine.xla._AggTable`, whose one-device placement
is ``DeviceAggState``): per-key state lives as a slot table sharded
over a device mesh (``n_shards * cap_per_shard`` rows, block *d* on
device *d*), and each micro-batch runs ONE compiled program that
exchanges rows to their owning shard with ``all_to_all`` over ICI and
scatter-combines them into the local block
(:func:`bytewax_tpu.ops.sharded.make_sharded_step`).

This is the keyed shuffle of the reference collapsed into the compiled
step: ``hash(key) → worker → routed_exchange → per-key callback``
(``/root/reference/src/timely.rs:806-812``,
``src/operators.rs:441-1041``) becomes ``hash(key) → shard →
all_to_all → scatter-combine``, with no host hop on the exchange.

Snapshots stay in the host tier's per-key scalar format, so recovery
is interchangeable between the host tier, the single-device tier, and
any mesh size (rescaling across tiers is just a resume).

The exchange never drops rows: the host sizes each dispatch's bucket
capacity to the batch's exact per-(source, destination) maximum before
compiling/calling the step (skew just means a larger capacity bucket,
pow2-quantized so XLA sees O(log n) shapes).
"""

import math
import os
import zlib
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from bytewax_tpu.engine import flight as _flight
from bytewax_tpu.engine import wire as _wire
from bytewax_tpu.engine.arrays import ArrayBatch, VocabMap
from bytewax_tpu.engine.scan_accel import ScanUpdates
from bytewax_tpu.engine.xla import (
    DeviceAggState,
    NonNumericValues,
    _AggTable,
    _final_of,
    _SlotLayout,
)
from bytewax_tpu.ops.segment import AGG_KINDS

__all__ = [
    "ShardedAggState",
    "ShardedScanState",
    "make_agg_state",
    "make_scan_state",
]

_MIN_CAP_PER_SHARD = 128
_MIN_ROWS_PER_SHARD = 64

#: Store row-key prefixes of the global tier's own recovery rows
#: (store-composable overlap, docs/recovery.md): NUL-prefixed so they
#: can never collide with user keys that happen to look similar.
#: Rows ride the EXISTING recovery ``snaps`` format — the keys are
#: salted per process (``_mine_local_key``) so route-scoped resume
#: reads deliver each process exactly its own rows.
_GSYNC_KEY_PREFIX = "\x00gsync-"
_GSYNC_BASE_KEY = "\x00gsync-base\x00"
_GSYNC_ROUND_KEY = "\x00gsync-round\x00"


def _discard_result(_res) -> None:
    """Collective-lane finalize: the sealed exchange task mutates the
    state it owns in place; nothing surfaces at finalize."""


def _gsync_overlap() -> bool:
    """Whether the collective tier double-buffers its exchange rounds
    (``BYTEWAX_TPU_GSYNC_OVERLAP``, default off — the lock-step tier,
    byte-identical to the pre-overlap engine; docs/performance.md
    "Overlapped collectives")."""
    return os.environ.get("BYTEWAX_TPU_GSYNC_OVERLAP", "0") not in (
        "",
        "0",
    )


def _gsync_depth() -> int:
    """How many overlapped exchange rounds may be in flight on the
    collective lane (``BYTEWAX_TPU_GSYNC_DEPTH``, default 1 — the
    double-buffered behavior the overlap shipped with; higher values
    let the sealed rounds of several epoch closes ladder behind the
    compute frontier; docs/performance.md "Overlapped collectives").
    Only read under ``BYTEWAX_TPU_GSYNC_OVERLAP=1`` — lock-step runs
    never construct the lane."""
    raw = os.environ.get("BYTEWAX_TPU_GSYNC_DEPTH", "1") or "1"
    try:
        depth = int(raw)
    except ValueError:
        msg = (
            f"BYTEWAX_TPU_GSYNC_DEPTH={raw!r} is not an integer; use "
            "the in-flight exchange-round bound (1 = double-buffered)"
        )
        raise ValueError(msg) from None
    return max(1, depth)


def _gsync_baseline_every() -> int:
    """With a recovery store under ``BYTEWAX_TPU_GSYNC_OVERLAP=1``,
    how many data-bearing exchange rounds ride between full-aggregate
    baseline snapshots (``BYTEWAX_TPU_GSYNC_BASELINE_EVERY``, default
    8): resume replays at most this many sealed rounds on top of the
    latest baseline (docs/recovery.md "Store-composable overlap")."""
    raw = (
        os.environ.get("BYTEWAX_TPU_GSYNC_BASELINE_EVERY", "8") or "8"
    )
    try:
        every = int(raw)
    except ValueError:
        msg = (
            f"BYTEWAX_TPU_GSYNC_BASELINE_EVERY={raw!r} is not an "
            "integer; use the rounds-per-baseline cadence"
        )
        raise ValueError(msg) from None
    return max(1, every)


def _shard_devices() -> Optional[list]:
    """The local devices to shard one step's state over, or None for
    single-device execution.

    ``BYTEWAX_TPU_SHARD`` overrides: ``0`` forces single-device,
    ``auto``/unset uses all local devices, an integer uses that many.
    """
    want = os.environ.get("BYTEWAX_TPU_SHARD", "auto")
    if want == "0":
        return None
    if want not in ("auto", ""):
        try:
            limit = int(want)
        except ValueError:
            limit = -1
        if limit < 0:
            msg = (
                f"BYTEWAX_TPU_SHARD={want!r} is not valid; use '0' "
                "(single device), 'auto', or a device count"
            )
            raise ValueError(msg) from None
    else:
        limit = None
    import jax

    # local_devices only: this process can only shard state over
    # devices it can address (each process of a multi-host pod
    # builds its own mesh; cross-process routing stays host-tier).
    # A backend that fails to come up raises here: a silent
    # single-device answer would leave the other chips idle.
    devices = jax.local_devices()
    if limit is not None:
        devices = devices[:limit]
    return devices if len(devices) > 1 else None


def make_agg_state(kind: str, driver=None):
    """Build aggregation state for one stateful step.

    Tier selection, most-capable first:

    - **global-mesh exchange** (``GlobalAggState``) when the jax
      distributed runtime spans the cluster's processes
      (``BYTEWAX_TPU_DISTRIBUTED=1``) and the flow has no recovery
      store — or has one AND ``BYTEWAX_TPU_GSYNC_OVERLAP=1`` is
      armed (store-composable overlap, docs/recovery.md: the tier
      snapshots its sealed rounds in recovery ``snaps`` row format):
      keyed rows stay on the process that ingested them until
      epoch close, then ONE collective ``all_to_all`` over the global
      device mesh (ICI/DCN) routes and folds them — the host TCP mesh
      carries only control-plane metadata.  Opt out with
      ``BYTEWAX_TPU_GLOBAL_EXCHANGE=0``.
    - **per-process mesh** (``ShardedAggState``) when >1 local device.
    - **single-device slot table** otherwise.
    """
    if (
        driver is not None
        and driver.comm is not None
        and (driver.store is None or _gsync_overlap())
        and os.environ.get("BYTEWAX_TPU_DISTRIBUTED") == "1"
        and os.environ.get("BYTEWAX_TPU_GLOBAL_EXCHANGE", "1") != "0"
    ):
        try:
            import jax

            eligible = (
                jax.distributed.is_initialized()
                and jax.process_count() == driver.proc_count
                and jax.process_count() > 1
            )
        except Exception as ex:  # noqa: BLE001 — probe failed HERE only
            # The tier decision must be SYMMETRIC across the cluster:
            # the values probed above (distributed init, process
            # count) are identical on every process, but an exception
            # (an unimportable or failing backend) can be
            # per-process.  Swallowing it into ``eligible = False``
            # would downgrade only this process to a non-collective
            # tier while peers that did build GlobalAggState block
            # forever in the collective flush — so under
            # BYTEWAX_TPU_DISTRIBUTED=1 a failed probe is a hard
            # error.  Opt the whole cluster out of the global tier
            # with BYTEWAX_TPU_GLOBAL_EXCHANGE=0 instead.
            msg = (
                "BYTEWAX_TPU_DISTRIBUTED=1 is set but probing the "
                f"distributed jax runtime failed on this process ({ex}); "
                "a silent per-process downgrade would deadlock the "
                "peers' collective flushes — fix the backend or run "
                "the whole cluster with BYTEWAX_TPU_GLOBAL_EXCHANGE=0"
            )
            raise RuntimeError(msg) from ex
        if eligible:
            # Construction errors must PROPAGATE: a one-process
            # downgrade to a non-collective tier would deadlock the
            # peers' collective flushes.
            return GlobalAggState(kind, driver)
    devices = _shard_devices()
    if devices is None:
        return DeviceAggState(kind)
    from bytewax_tpu.parallel.mesh import make_mesh

    return ShardedAggState(kind, make_mesh(devices=devices))


def make_scan_state(scan_kind):
    """Build ``stateful_map`` scan state for one step: mesh-sharded
    (exchange + per-shard segmented scan + outputs home) when more
    than one local device is available, single-device otherwise."""
    from bytewax_tpu.engine.scan_accel import DeviceScanState

    devices = _shard_devices()
    if devices is None:
        return DeviceScanState(scan_kind)
    from bytewax_tpu.parallel.mesh import make_mesh

    return ShardedScanState(scan_kind, make_mesh(devices=devices))


def _pow2(n: int, floor: int) -> int:
    return 1 << max(floor, math.ceil(math.log2(max(n, 1))))


class _ShardedSlots(_SlotLayout):
    """The table of a mesh-sharded state: block *d* of
    ``cap_per_shard`` rows on device *d*, made, reset and grown over
    the columns :meth:`_iter_fields` names (see ``xla._SlotLayout``
    for where an id lives).

    Hosts set ``mesh`` and ``_sharding``, call :meth:`_init_slots`,
    and implement :meth:`_iter_fields` yielding ``(name, identity,
    dtype)`` per state column and ``_step_for(total_rows, capacity)``
    returning the compiled step of that shape.
    """

    def _iter_fields(self):
        """``(name, identity, dtype)`` per state column."""
        raise NotImplementedError

    def placement(self) -> Dict[str, Any]:
        """Where this step's state lives (``GET /graph``): block *d*
        on the mesh's device *d*."""
        return {
            "blocks": self.n_shards,
            "devices": [int(d.id) for d in self.mesh.devices.flat],
        }

    def _plan_exchange(self, kids: np.ndarray) -> Tuple[int, Any]:
        """The exchange's host half for one delivery: the padded row
        count and the compiled step for it.  Rows are padded at the
        end to ``n_shards`` equal source blocks, and the bucket
        capacity is the exact per-(source block, destination shard)
        maximum, so the exchange can never drop rows, however skewed
        the key distribution."""
        n = len(kids)
        n_shards = self.n_shards
        with _flight.span("exchange", rows=n):
            rows_per_shard = _pow2(
                -(-n // n_shards), int(math.log2(_MIN_ROWS_PER_SHARD))
            )
            total = rows_per_shard * n_shards
            dest = kids % n_shards
            block_of = np.arange(n) // rows_per_shard
            pair_counts = np.bincount(
                block_of * n_shards + dest, minlength=n_shards * n_shards
            )
            capacity = _pow2(int(pair_counts.max()), 4)
            step = self._step_for(total, capacity)
            _flight.note_exchange(
                n_shards,
                capacity,
                int(pair_counts.reshape(n_shards, n_shards).sum(axis=1).max()),
            )
        return total, step

    def _make_fields(self):
        import jax
        import jax.numpy as jnp

        return {
            name: jax.device_put(
                jnp.full((self.capacity,), ident, dtype=dtype),
                self._sharding,
            )
            for name, ident, dtype in self._iter_fields()
        }

    def _reset_rows(self, ids: List[int]) -> None:
        import jax.numpy as jnp

        idxs = jnp.asarray(
            self._global_idx(np.asarray(ids, dtype=np.int32))
        )
        for name, ident, _dtype in self._iter_fields():
            self._fields[name] = self._fields[name].at[idxs].set(ident)

    def _resize(self, new_cap: int) -> None:
        """Grow every shard's block.  Ids are unchanged; only the
        per-shard scratch row (the block's last) moves, and the old
        scratch becomes a real slot (reset to identity)."""
        import jax
        import jax.numpy as jnp

        old_cap = self.cap_per_shard
        if self._fields is not None:
            grown = {}
            for name, ident, dtype in self._iter_fields():
                blocks = self._fields[name].reshape(self.n_shards, old_cap)
                blocks = blocks.at[:, old_cap - 1].set(ident)
                pad = jnp.full(
                    (self.n_shards, new_cap - old_cap), ident, dtype=dtype
                )
                arr = jnp.concatenate([blocks, pad], axis=1).reshape(-1)
                grown[name] = jax.device_put(arr, self._sharding)
            self._fields = grown
        self.cap_per_shard = new_cap


class ShardedAggState(_ShardedSlots, _AggTable):
    """Slot-table aggregation state sharded over a device mesh:
    ``xla._AggTable`` with the placement of ``n_shards`` blocks.  A
    delivery's rows go to their owning shard in ONE compiled program
    (``all_to_all`` over ICI, then scatter-combine into the local
    block: :func:`bytewax_tpu.ops.sharded.make_sharded_step`); a
    dictionary-encoded batch has its ids looked up on the host first.
    """

    def __init__(self, kind: str, mesh, cap_per_shard: int = _MIN_CAP_PER_SHARD):
        from bytewax_tpu.parallel.mesh import SHARD_AXIS, key_sharding

        super().__init__(kind, mesh.shape[SHARD_AXIS], cap_per_shard)
        self.mesh = mesh
        # Rows and state blocks use the same leading-axis split.
        self._sharding = key_sharding(mesh)
        self._steps: Dict[Tuple[int, int, int, Any], Any] = {}

    def _iter_fields(self):
        from bytewax_tpu.ops.segment import identity_for

        return [
            (name, identity_for(init, self.dtype), self.dtype)
            for name, (init, _op) in self.kind.fields.items()
        ]

    def _step_for(self, total_rows: int, capacity: int):
        from bytewax_tpu.ops.sharded import make_sharded_step

        key = (self.cap_per_shard, capacity, total_rows, self.dtype)
        step = self._steps.get(key)
        if step is None:
            step = make_sharded_step(
                self.mesh,
                self.kind_name,
                self.cap_per_shard,
                capacity,
                dtype=self.dtype,
            )
            self._steps[key] = step
        return step

    # -- placement -----------------------------------------------------------

    def _scatter(self, kids: np.ndarray, values: np.ndarray) -> None:
        """Run one compiled exchange + fold over the mesh."""
        import jax

        n = len(kids)
        if n == 0:
            return
        total, step = self._plan_exchange(kids)
        with _flight.span("h2d", rows=total):
            kids_p = np.zeros(total, dtype=np.int32)
            kids_p[:n] = kids
            vals_p = np.zeros(total, dtype=np.dtype(self.dtype))
            vals_p[:n] = values
            valid_p = np.zeros(total, dtype=bool)
            valid_p[:n] = True
            _flight.note_transfer(
                "h2d", kids_p.nbytes + vals_p.nbytes + valid_p.nbytes
            )
            kids_d = jax.device_put(kids_p, self._sharding)
            vals_d = jax.device_put(vals_p, self._sharding)
            valid_d = jax.device_put(valid_p, self._sharding)
        with _flight.span("dispatch"):
            self._fields = step(self._fields, kids_d, vals_d, valid_d)

    def _fold_encoded(self, ids, values, scale) -> None:
        with _flight.span("prep"):
            if scale is not None:
                values = (values * scale).astype(np.float32)
            self._ensure_fields()
            kids = self._vocab.table[ids]
        self._scatter(kids, values)


class ShardedScanState(_ShardedSlots, ScanUpdates):
    """Mesh-sharded per-key scan state (``stateful_map`` lowering).

    The multi-chip sibling of
    :class:`bytewax_tpu.engine.scan_accel.DeviceScanState`: per-key
    state columns (one per :class:`~bytewax_tpu.ops.scan.ScanKind`
    field) live sharded over the mesh, and each micro-batch runs ONE
    compiled program that exchanges rows to their owner shard, runs
    the kind's segmented scan against the local block, and ships each
    row's output back to its source position
    (:func:`bytewax_tpu.ops.sharded.make_sharded_scan_step`).

    Key placement and wire ids follow :class:`ShardedAggState`
    (``kid = slot * n_shards + shard``, per-shard scratch at the
    block's last slot); snapshots stay in the host tier's field-order
    tuple format, so recovery interchanges between the host tier, the
    single-device tier, and any mesh size.
    """

    def __init__(self, scan_kind, mesh, cap_per_shard: int = _MIN_CAP_PER_SHARD):
        from bytewax_tpu.parallel.mesh import SHARD_AXIS, key_sharding

        self.kind = scan_kind
        self.mesh = mesh
        self._sharding = key_sharding(mesh)
        self._init_slots(mesh.shape[SHARD_AXIS], cap_per_shard)
        self._steps: Dict[Tuple[int, int, int], Any] = {}

    def _iter_fields(self):
        return [
            (name, init, dtype)
            for name, (init, dtype) in self.kind.fields.items()
        ]

    # -- updates -------------------------------------------------------------

    def _step_for(self, total_rows: int, capacity: int):
        from bytewax_tpu.ops.sharded import make_sharded_scan_step

        key = (self.cap_per_shard, capacity, total_rows)
        step = self._steps.get(key)
        if step is None:
            step = make_sharded_scan_step(
                self.mesh, self.kind, self.cap_per_shard, capacity
            )
            self._steps[key] = step
        return step

    def _dispatch(
        self, kids: np.ndarray, values: np.ndarray
    ) -> Tuple[np.ndarray, ...]:
        """One compiled exchange + scan + return trip; outputs are
        aligned with the input rows (finished by ``kind.post``)."""
        import jax

        n = len(kids)
        if n == 0:
            return tuple()
        self._ensure_fields()
        total, step = self._plan_exchange(kids)

        kids_p = np.zeros(total, dtype=np.int32)
        kids_p[:n] = kids
        vals_p = np.zeros(total, dtype=np.float32)
        vals_p[:n] = values
        valid_p = np.zeros(total, dtype=bool)
        valid_p[:n] = True

        outs, self._fields = step(
            self._fields,
            jax.device_put(kids_p, self._sharding),
            jax.device_put(vals_p, self._sharding),
            jax.device_put(valid_p, self._sharding),
        )
        return self.kind.post(tuple(np.asarray(o)[:n] for o in outs))

    # update_grouped / update / update_batch come from ScanUpdates;
    # _dispatch is its hook (the compiled round trip returns outputs
    # in row order, which for pre-grouped rows IS the grouped
    # emission order).

    # -- recovery ------------------------------------------------------------

    def load(self, key: str, state: Any) -> None:
        self.load_many([(key, state)])

    def load_many(self, items: List[Tuple[str, Any]]) -> None:
        """Batched resume from host-format field-order tuples: one
        scatter per field per page (wire ids resolved after every
        alloc so capacity growth mid-page can't skew indices)."""
        import jax

        if not items:
            return
        field_items = list(self.kind.fields.items())
        cols = [
            np.empty(len(items), dtype=np.dtype(dtype))
            for _name, (_init, dtype) in field_items
        ]
        kids = []
        for i, (key, state) in enumerate(items):
            kids.append(self.alloc(key))
            for j, part in enumerate(state):
                cols[j][i] = part
        self._ensure_fields()
        idxs = np.fromiter(
            (self._global_idx(k) for k in kids),
            dtype=np.int64,
            count=len(kids),
        )
        for (name, _spec), col in zip(field_items, cols):
            self._fields[name] = (
                self._fields[name].at[idxs].set(jax.device_put(col))
            )

    def snapshots_for(self, keys: List[str]) -> List[Tuple[str, Any]]:
        if self._fields is None or not keys:
            return [(k, None) for k in keys]
        names = tuple(self.kind.fields)
        host = {name: np.asarray(self._fields[name]) for name in names}
        out = []
        for key in keys:
            kid = self.key_to_slot.get(key)
            if kid is None:
                out.append((key, None))
            else:
                idx = self._global_idx(kid)
                out.append(
                    (
                        key,
                        self.kind.snapshot_of(
                            tuple(host[nm][idx] for nm in names)
                        ),
                    )
                )
        return out



class GlobalAggState:
    """Cluster-spanning keyed aggregation over the GLOBAL device mesh.

    The tier that makes "the pod is the cluster" literal: instead of
    routing keyed rows between processes over the pickled host TCP
    mesh (the reference's wire: ``/root/reference/src/timely.rs:806-812``,
    ``src/pyo3_extensions.rs:94-148``), rows buffer on the process
    that ingested them and, at every epoch close — a point all
    processes reach in the same order via the close broadcast — ONE
    compiled ``all_to_all`` over a mesh of EVERY process's devices
    exchanges and folds them into key-sharded state (ICI within a
    host, DCN across hosts).  The TCP mesh carries only a small
    metadata round per flush (new keys, row counts, dtype vote)
    through ``driver.global_sync``.

    Key placement is lane-aligned: a key's owner shard lives on the
    process that owns the key's worker lane (``route_hash %
    worker_count``), spread over that process's local devices — so
    EOF emission needs no extra routing hop, exactly like the TCP
    tier.  Slot assignment is deterministic (merged new keys in
    sorted order), so every process holds an identical key→kid map
    without negotiation.

    Scope: flows without a recovery store (``make_agg_state`` falls
    back to the per-process tier when recovery is configured — resume
    pages are partitioned by worker lane, which this tier does not
    re-shuffle yet).
    """

    global_exchange = True

    #: Per-shard slot capacity; keys-per-shard beyond this raise (the
    #: global tier defers growth — blocks would have to be resized
    #: collectively).
    CAP_PER_SHARD = 4096
    #: Rows per device per exchange step: big flushes run as repeats
    #: of this fixed shape (one compiled program, bounded buffers).
    CHUNK_PER_DEV = 1 << 18

    def __init__(self, kind_name: str, driver):
        import jax

        from bytewax_tpu.parallel.mesh import key_sharding, make_mesh

        self.kind_name = kind_name
        self.kind = AGG_KINDS[kind_name]
        self.driver = driver
        devices = jax.devices()
        #: proc id -> global shard indices of its devices (the mesh
        #: is built over jax.devices() in order, so a device's shard
        #: index IS its position in that list).
        by_proc: Dict[int, List[int]] = {}
        for i, d in enumerate(devices):
            by_proc.setdefault(d.process_index, []).append(i)
        counts = {len(v) for v in by_proc.values()}
        if len(counts) != 1:
            msg = (
                "the global-mesh exchange needs the same local device "
                "count on every process; got "
                f"{ {p: len(v) for p, v in by_proc.items()} } — run "
                "with BYTEWAX_TPU_GLOBAL_EXCHANGE=0 or equalize "
                "xla_force_host_platform_device_count"
            )
            raise RuntimeError(msg)
        self._proc_shards = by_proc
        self.local_devs = counts.pop()
        self.n_shards = len(devices)
        self.cap_per_shard = self.CAP_PER_SHARD
        self.mesh = make_mesh(devices=devices)
        self._sharding = key_sharding(self.mesh)
        #: Full global key→kid map, identical on every process.
        self.key_to_slot: Dict[str, int] = {}
        self._shard_fill = [0] * self.n_shards
        #: Buffered local rows awaiting the next collective flush,
        #: dictionary-encoded: per-row DENSE local ids into
        #: ``_dense_keys`` (so kid resolution at flush is one gather
        #: over distinct keys, never a per-row Python loop).
        self._buf_ids: List[np.ndarray] = []
        self._buf_vals: List[np.ndarray] = []
        self._buf_all_int = True
        self._dense_keys: List[str] = []
        self._dense_map: Dict[str, int] = {}
        self._vocab = VocabMap(dtype=np.int32)
        self._fields = None
        self.dtype = None  # decided collectively at first flush
        self._round = 0
        self._steps: Dict[Tuple[int, int, Any], Any] = {}
        #: Quantized aggregate exchange (docs/performance.md
        #: "Overlapped collectives"): with ``BYTEWAX_TPU_GSYNC_QUANT``
        #: armed, rows pre-reduce locally per key and the flush ships
        #: block-scaled partial-aggregate columns inside the existing
        #: gsync round (EQuARX, PAPERS.md) instead of raw rows through
        #: the device all_to_all; every process merges the partials
        #: host-side.  Cluster-wide agreement on the mode is checked
        #: at every flush — a divergent knob fails typed, it can not
        #: desynchronize the round sequence.
        self._quant = _wire.gsync_quant()
        #: Host-side merged partial fields (quant mode only), indexed
        #: like the device blocks (``n_shards * cap_per_shard``).
        self._host_fields: Optional[Dict[str, np.ndarray]] = None
        #: Whether every merged flush so far was all-integer (quant
        #: mode emits ints then, matching the exact tier's int lock).
        self._quant_int = True
        #: Device-resident merge tables (quant mode, docs/performance.md
        #: "Overlapped collectives"): peer partial frames upload at
        #: wire width and dequantize+merge+scatter in HBM
        #: (engine/xla.py ``agg_merge_fn``), so the merged aggregate
        #: never leaves HBM between closes.  ``_merge_demoted`` pins
        #: the host-side ``decode_agg`` fold instead — the
        #: ``BYTEWAX_TPU_WIRE=pickle``-era fallback and the oracle in
        #: tests — and flips sticky when an exact integer part cannot
        #: ride the device's int32 tables (deterministic: every
        #: process folds identical frames).
        self._dev_fields: Optional[Dict[str, Any]] = None
        self._merge_demoted = _wire.wire_mode() == "pickle"
        #: Store-composable overlap (docs/recovery.md): with a
        #: recovery store, every data-bearing round stashes a sealed
        #: round row (and every ``BYTEWAX_TPU_GSYNC_BASELINE_EVERY``
        #: rounds, a fenced full-aggregate baseline row) in recovery
        #: ``snaps`` format; resume replays baseline + tail rounds.
        self._data_rounds = 0
        self._outstanding_rounds: List[str] = []
        self._pending_snap_rows: List[Tuple[str, Any]] = []
        self._resume_rows: List[Tuple[str, Any]] = []
        self._base_written = False
        #: Overlapped exchange lane (docs/performance.md "Overlapped
        #: collectives"): with ``BYTEWAX_TPU_GSYNC_OVERLAP=1`` the
        #: sealed exchange for an epoch's close runs on this ordered
        #: single-worker lane while the run loop computes later
        #: epochs.  The lane bounds its own in-flight window: at the
        #: configured ``BYTEWAX_TPU_GSYNC_DEPTH`` (default 1 =
        #: double-buffered), ``push``'s ``make_room`` retires the
        #: oldest sealed round before admitting a new one, so at most
        #: DEPTH rounds ride between the compute frontier and the
        #: fences (finalize, baselines, the run-ending close).  The
        #: lane is ONE per driver, shared by every global-exchange
        #: step: seal order is the agreed round order (pre_close
        #: iterates steps identically everywhere), so the collective
        #: programs still launch in an identical sequence
        #: cluster-wide — up to DEPTH epochs behind the compute
        #: frontier.  Per-step lanes would break exactly that: two
        #: steps' rounds on independent worker threads could launch
        #: their collectives in a different relative order on each
        #: process.  Off (the default) keeps the lock-step tier
        #: byte-identical: no lane is ever constructed.
        self._lane = None
        if _gsync_overlap():
            if getattr(driver, "_gsync_lane", None) is None:
                from bytewax_tpu.engine.pipeline import DevicePipeline

                driver._gsync_lane = DevicePipeline(
                    "gsync",
                    depth=_gsync_depth() + 1,
                    phase="collective_lane",
                )
            self._lane = driver._gsync_lane

    # -- placement -----------------------------------------------------------

    def _owner_shard(self, key: str) -> int:
        h = zlib.adler32(key.encode())
        w = h % self.driver.worker_count
        p = self.driver.owner_proc(w)
        shards = self._proc_shards[p]
        return shards[
            (h // max(1, self.driver.worker_count)) % len(shards)
        ]

    def _global_idx(self, kid: int) -> int:
        shard, slot = kid % self.n_shards, kid // self.n_shards
        return shard * self.cap_per_shard + slot

    # -- buffering update surface -------------------------------------------

    def _dense_alloc(self, keys: List[str]) -> List[int]:
        out = []
        for k in keys:
            did = self._dense_map.get(k)
            if did is None:
                did = len(self._dense_keys)
                self._dense_map[k] = did
                self._dense_keys.append(k)
            out.append(did)
        return out

    def _check_values(self, values: np.ndarray) -> None:
        if values.dtype == object or values.dtype.kind in "US":
            msg = (
                "device-accelerated reduction requires numeric values; "
                "pass a plain Python reducer for non-numeric data"
            )
            raise NonNumericValues(msg)
        if np.issubdtype(values.dtype, np.integer):
            if values.dtype.itemsize > 4 and len(values) and (
                values.max() > np.iinfo(np.int32).max
                or values.min() < np.iinfo(np.int32).min
            ):
                msg = (
                    "device-accelerated reduction over integers wider "
                    "than 32 bits is not exact; pass a plain Python "
                    "reducer"
                )
                raise NonNumericValues(msg)
        else:
            import jax.numpy as jnp

            if self.dtype == jnp.int32:
                # Same policy as the per-process tiers: integral
                # in-range floats after an int lock cast losslessly
                # at flush; anything else would silently truncate.
                if len(values) and (
                    np.any(values % 1)
                    or values.max() > np.iinfo(np.int32).max
                    or values.min() < np.iinfo(np.int32).min
                ):
                    msg = (
                        "non-integral float values arrived after "
                        "earlier batches locked this step's global "
                        "state to an integer dtype; pass a plain "
                        "Python reducer for mixed int/float streams"
                    )
                    raise TypeError(msg)
            else:
                self._buf_all_int = False

    def update(self, keys: np.ndarray, values: np.ndarray) -> List[str]:
        keys = np.asarray(keys)
        values = np.asarray(values)
        self._check_values(values)
        from bytewax_tpu.engine.arrays import factorize_keys

        codes, uniq = factorize_keys(keys)
        uniq_list = [str(k) for k in uniq.tolist()]
        dense_of = np.asarray(
            self._dense_alloc(uniq_list), dtype=np.int32
        )
        self._buf_ids.append(dense_of[codes])
        self._buf_vals.append(values.astype(np.float64))
        return uniq_list

    def update_items(self, items) -> Optional[List[str]]:
        # The driver promotes itemized rows itself when this returns
        # None (the buffering tier has no kv_encode cache to keep in
        # sync across the cluster).
        return None

    def update_batch(self, batch: ArrayBatch) -> List[str]:
        values = batch.numpy("value")
        if batch.value_scale is not None:
            values = values * batch.value_scale
        if "key_id" in batch.cols and batch.key_vocab is not None:
            # Dictionary-encoded fast path: map external ids to dense
            # ids through the append-only vocab table — one gather,
            # no per-row strings.
            ids = batch.numpy("key_id").astype(np.int64)
            self._check_values(values)
            uniq_ext = self._vocab.sync(
                ids, batch.key_vocab, self._dense_alloc
            )
            self._buf_ids.append(self._vocab.table[ids])
            self._buf_vals.append(values.astype(np.float64))
            return [
                str(self._vocab.vocab[e]) for e in uniq_ext.tolist()
            ]
        if "key" in batch.cols:
            return self.update(batch.numpy("key"), values)
        msg = (
            "columnar batch feeding an accelerated keyed "
            "aggregation needs a 'key' or dictionary-encoded "
            "'key_id' column"
        )
        raise TypeError(msg)

    def keys(self) -> List[str]:
        known = set(self.key_to_slot)
        known.update(self._dense_keys)
        return sorted(known)

    def discard(self, key: str) -> None:  # pragma: no cover - EOF clears
        self.key_to_slot.pop(key, None)

    # -- the collective flush -------------------------------------------------

    def _assign_kids(self, new_keys: List[str]) -> None:
        for k in new_keys:
            if k in self.key_to_slot:
                continue
            shard = self._owner_shard(k)
            slot = self._shard_fill[shard]
            if slot >= self.cap_per_shard - 1:
                msg = (
                    f"global-exchange shard {shard} is full "
                    f"({self.cap_per_shard - 1} keys; the last slot "
                    "is exchange scratch); raise "
                    "GlobalAggState.CAP_PER_SHARD"
                )
                raise RuntimeError(msg)
            self._shard_fill[shard] = slot + 1
            self.key_to_slot[k] = slot * self.n_shards + shard

    def _ensure_fields(self) -> None:
        import jax

        from bytewax_tpu.ops.segment import identity_for

        if self._fields is not None:
            return
        shape = (self.n_shards * self.cap_per_shard,)
        fields = {}
        for name, (init, _op) in self.kind.fields.items():
            ident = identity_for(init, self.dtype)

            def cb(index, _ident=ident):
                size = shape[0] // self.n_shards
                return np.full((size,), _ident, dtype=np.dtype(self.dtype))

            fields[name] = jax.make_array_from_callback(
                shape, self._sharding, cb
            )
        self._fields = fields

    def _step_for(self, rows_per_dev: int, capacity: int):
        from bytewax_tpu.ops.sharded import make_sharded_step

        # dtype is part of the key: finalize() resets self.dtype to
        # None and the next lock may pick the OTHER dtype — a stale
        # cached step would ride int values through the float32
        # bitcast lane.
        key = (rows_per_dev, capacity, self.dtype)
        step = self._steps.get(key)
        if step is None:
            step = make_sharded_step(
                self.mesh,
                self.kind_name,
                self.cap_per_shard,
                capacity,
                dtype=self.dtype,
            )
            self._steps[key] = step
        return step

    def fence(self) -> None:
        """Wait out every in-flight overlapped exchange round on the
        (driver-shared) collective lane.  The only FULL drains
        (docs/performance.md "Overlapped collectives"): any read of
        the global result (finalize/EOF), a baseline snapshot, and
        the run-ending close — nothing per-batch ever blocks here.
        A flush no longer drains the lane wholesale: ``push`` bounds
        the in-flight window itself (``make_room`` retires the
        oldest sealed round once ``BYTEWAX_TPU_GSYNC_DEPTH`` rounds
        ride the lane), so the depth ladder keeps up to DEPTH sealed
        rounds behind the compute frontier with ordered
        retirement — at the default depth 1 that is exactly the
        original fence-every-flush behavior."""
        if self._lane is not None:
            self._lane.flush()

    def lane_status(self) -> Optional[Dict[str, int]]:
        """Collective-lane introspection for /status and /graph
        (docs/observability.md): sealed rounds currently in flight
        and the configured overlap depth.  None when the lock-step
        tier runs (no lane constructed)."""
        if self._lane is None:
            return None
        return {
            "in_flight": len(self._lane),
            "depth": self._lane.depth - 1,
        }

    def lane_shutdown(self) -> None:
        """Teardown (driver ``pipeline_shutdown``, fault unwinds):
        wait for the lane worker to go quiet and stop it.  A clean
        exit has already fenced (finalize and the run-ending close
        drain the lane), so pending work here only exists on a fault
        path — dropped, matching the dispatch pipelines.  The lane is
        driver-shared: the first step's shutdown retires it for all
        (drop_pending/shutdown are idempotent on a quiet lane), and
        clearing the driver attribute makes a rebuilt driver start
        fresh."""
        lane, self._lane = self._lane, None
        if lane is not None:
            lane.drop_pending()
            lane.shutdown()
            if getattr(self.driver, "_gsync_lane", None) is lane:
                self.driver._gsync_lane = None

    def _note_flush(
        self, n_local: int, total_rows: int, n_steps: int, detail: str
    ) -> None:
        """Record one sealed-and-launched exchange round (flight ring
        + the debug marker)."""
        _flight.RECORDER.record(
            "global_flush",
            rows=n_local,
            total_rows=total_rows,
            steps=n_steps,
        )
        if os.environ.get("BYTEWAX_TPU_GLOBAL_EXCHANGE_DEBUG") == "1":
            import sys

            print(
                f"global-exchange: proc {self.driver.proc_id} flushed "
                f"{n_local}/{total_rows} rows over {self.n_shards} "
                f"shards in {n_steps} step(s), {detail}",
                file=sys.stderr,
                flush=True,
            )

    def flush(self) -> None:
        """One collective exchange+fold round.  EVERY process must
        call this the same number of times in the same global order
        (epoch close / the EOF ladder guarantee it); rounds where the
        whole cluster has nothing buffered skip the device step but
        still run the (cheap) metadata sync.

        With ``BYTEWAX_TPU_GSYNC_OVERLAP=1`` the exchange phase is
        sealed into an immutable task and launched on the ordered
        collective lane — the metadata rounds still run HERE, at the
        globally-ordered point, so every process executes the
        identical sequence of sync rounds and seals the identical
        sequence of collective programs, up to
        ``BYTEWAX_TPU_GSYNC_DEPTH`` epochs behind the compute
        frontier (``push`` itself retires the oldest round once the
        window is full — no wholesale fence per flush).  With
        ``BYTEWAX_TPU_GSYNC_QUANT`` armed, buffered rows pre-reduce
        locally per key and quantized partial-aggregate frames ride
        the metadata round (engine/wire.py) instead of raw rows
        riding the device all_to_all; the merge is sealed on the
        main thread (scatter targets resolved against the main-owned
        ``key_to_slot``) and folds on device
        (dequant+merge+scatter in HBM, engine/xla.py) — or
        host-side under the ``BYTEWAX_TPU_WIRE=pickle`` fallback."""
        import jax
        import jax.numpy as jnp

        driver = self.driver
        self._maybe_replay_resume()
        n_local = int(sum(len(a) for a in self._buf_vals))
        local_new = sorted(
            k for k in self._dense_keys if k not in self.key_to_slot
        )
        quant = self._quant
        frames = (
            self._local_partial_frames() if quant != "off" else None
        )
        # Every process performs the same global sequence of sync
        # rounds (epoch close / EOF ladder ordering), so a driver-wide
        # monotone counter names the round identically cluster-wide.
        tag = ("gagg", driver.next_gsync_tag())
        self._round += 1
        replies = driver.global_sync(
            tag, (local_new, n_local, self._buf_all_int, quant, frames)
        )
        modes = {r[3] for r in replies.values()}
        if len(modes) != 1:
            msg = (
                "cluster processes disagree on BYTEWAX_TPU_GSYNC_QUANT "
                f"({sorted(modes)}); the quantized aggregate exchange "
                "must be armed identically on every process"
            )
            raise RuntimeError(msg)
        merged_new = sorted(
            {k for new, *_rest in replies.values() for k in new}
        )
        total_rows = sum(r[1] for r in replies.values())
        all_int = all(r[2] for r in replies.values())
        self._assign_kids(merged_new)
        if total_rows == 0:
            self._buf_ids.clear()
            self._buf_vals.clear()
            return
        self._data_rounds += 1
        if quant != "off":
            # Quantized exchange: the partial frames already rode the
            # round; seal the (deterministically ordered) merge ON
            # MAIN — frame decode and scatter-target resolution
            # against the main-owned ``key_to_slot`` — and launch the
            # fold (device or host per the sealed decision).
            self._buf_ids.clear()
            self._buf_vals.clear()
            self._quant_int = self._quant_int and all_int
            peer_frames = [replies[pid][4] for pid in sorted(replies)]
            n_frames = sum(len(f or ()) for f in peer_frames)
            sealed = self._seal_merge(peer_frames)

            def merge_task():
                self._apply_merge(sealed)

            # Launch: inline (lock-step) or on the overlapped lane —
            # the direct push site is what BTX-THREAD traces.
            if self._lane is None:
                merge_task()
            else:
                self._lane.push(merge_task, _discard_result)
            where = "host" if sealed["device"] is False else "device"
            self._note_flush(
                n_local,
                total_rows,
                1,
                f"{n_frames} quantized partial frame(s) "
                f"[{quant}, {where} merge]",
            )
            self._stash_round(
                lambda: {
                    "fmt": "quant",
                    "round": self._data_rounds,
                    "frames": peer_frames,
                    "new": merged_new,
                    "all_int": all_int,
                }
            )
            return
        if self.dtype is None:
            self.dtype = jnp.int32 if all_int else jnp.float32
        elif self.dtype == jnp.int32 and not all_int:
            msg = (
                "non-integral float values arrived after earlier "
                "batches locked this step's global state to an "
                "integer dtype; pass a plain Python reducer for "
                "mixed int/float streams"
            )
            raise TypeError(msg)
        self._ensure_fields()

        # Chunk layout — identical on every process (derived from the
        # synced per-process max): big flushes run as a sequence of
        # fixed-shape steps so ONE compiled program is reused across
        # chunks, flushes, and epochs, and exchange buffers stay
        # bounded regardless of how much an epoch buffered.
        max_rows = max(n for _new, n, *_rest in replies.values())
        chunk_pd = min(
            _pow2(
                -(-max_rows // self.local_devs),
                int(math.log2(_MIN_ROWS_PER_SHARD)),
            ),
            self.CHUNK_PER_DEV,
        )
        chunk_rows = chunk_pd * self.local_devs
        n_steps = -(-max_rows // chunk_rows)
        pad_total = n_steps * chunk_rows

        ids_cat = (
            np.concatenate(self._buf_ids)
            if self._buf_ids
            else np.empty(0, dtype=np.int32)
        )
        vals_cat = (
            np.concatenate(self._buf_vals)
            if self._buf_vals
            else np.empty(0, dtype=np.float64)
        )
        self._buf_ids.clear()
        self._buf_vals.clear()
        # Kid resolution per DISTINCT key, then one gather per row.
        kid_map = self.key_to_slot
        kid_of_dense = np.fromiter(
            (kid_map[k] for k in self._dense_keys),
            dtype=np.int32,
            count=len(self._dense_keys),
        )
        kids = (
            kid_of_dense[ids_cat]
            if len(ids_cat)
            else np.empty(0, dtype=np.int32)
        )
        kids_p = np.zeros(pad_total, dtype=np.int32)
        kids_p[:n_local] = kids
        vals_p = np.zeros(pad_total, dtype=np.dtype(self.dtype))
        vals_p[:n_local] = vals_cat
        valid_p = np.zeros(pad_total, dtype=bool)
        valid_p[:n_local] = True

        # Exact exchange capacity: local per-(step, source device
        # block, destination shard) maximum, then one more metadata
        # round for the global max — the exchange ships only real
        # rows (pow2-quantized), not a worst-case n_shards-fold
        # inflation.
        idx = np.arange(n_local)
        blk = (idx // chunk_rows) * self.local_devs + (
            (idx % chunk_rows) // chunk_pd
        )
        pair_counts = np.bincount(
            blk * self.n_shards + (kids % self.n_shards),
            minlength=n_steps * self.local_devs * self.n_shards,
        )
        local_max = int(pair_counts.max()) if len(pair_counts) else 0
        cap_replies = driver.global_sync(
            ("gagg", driver.next_gsync_tag()), local_max
        )
        capacity = _pow2(max(cap_replies.values()), 4)

        _flight.note_transfer(
            "h2d", kids_p.nbytes + vals_p.nbytes + valid_p.nbytes
        )
        step = self._step_for(chunk_pd, capacity)
        global_rows = chunk_pd * self.n_shards
        val_dtype = np.dtype(self.dtype)

        def exchange_task():
            # Sealed device phase: identical program sequence on every
            # process's lane (seal order is the agreed round order).
            self._exchange_chunks(
                step,
                kids_p,
                vals_p,
                valid_p,
                chunk_rows,
                n_steps,
                global_rows,
                val_dtype,
            )

        if self._lane is None:
            exchange_task()
        else:
            self._lane.push(exchange_task, _discard_result)
        self._note_flush(
            n_local, total_rows, n_steps, f"capacity {capacity}"
        )
        self._stash_round(
            lambda: {
                "fmt": "exact",
                "round": self._data_rounds,
                "kids": kids,
                "vals": vals_cat,
                "new": merged_new,
                "chunk_pd": chunk_pd,
                "capacity": capacity,
                "n_steps": n_steps,
                "dtype": np.dtype(self.dtype).name,
            }
        )

    def _exchange_chunks(
        self,
        step,
        kids_p: np.ndarray,
        vals_p: np.ndarray,
        valid_p: np.ndarray,
        chunk_rows: int,
        n_steps: int,
        global_rows: int,
        val_dtype,
    ) -> None:
        """Run one sealed exchange round's chunk sequence (the device
        phase shared by the flush task and resume replay)."""
        import jax

        sharding = self._sharding

        def garr(local, dtype):
            return jax.make_array_from_process_local_data(
                sharding, local.astype(dtype), (global_rows,)
            )

        for c in range(n_steps):
            sl = slice(c * chunk_rows, (c + 1) * chunk_rows)
            self._fields = step(
                self._fields,
                garr(kids_p[sl], np.int32),
                garr(vals_p[sl], val_dtype),
                garr(valid_p[sl], bool),
            )

    def _local_partial_frames(self) -> List[bytes]:
        """Pre-reduce this process's buffered rows per key and frame
        the partial-aggregate columns for the gsync round: one
        ``key`` column (exact) plus one column per state field —
        ``count`` and all-integer partials exact, float partials
        block-quantized per the armed mode (engine/wire.py)."""
        if not self._dense_keys or not self._buf_ids:
            return []
        ids = np.concatenate(self._buf_ids)
        vals = np.concatenate(self._buf_vals)
        if not len(ids):
            return []
        # Remap to the TOUCHED dense ids only: work and allocation
        # scale with this flush's rows and distinct keys, never with
        # the full accumulated key history (a trickle stream over a
        # large vocabulary would otherwise pay O(total keys) per
        # epoch close).
        uniq, inv = np.unique(ids, return_inverse=True)
        n_touched = len(uniq)
        dense_keys = self._dense_keys
        cols: Dict[str, np.ndarray] = {
            "key": np.array([dense_keys[i] for i in uniq.tolist()])
        }
        counts = np.bincount(inv, minlength=n_touched)
        for name, (_init, op) in self.kind.fields.items():
            if name == "count":
                arr = counts.astype(np.int64)
            else:
                if op == "add":
                    arr = np.bincount(
                        inv, weights=vals, minlength=n_touched
                    )
                elif op == "min":
                    arr = np.full(n_touched, np.inf)
                    np.minimum.at(arr, inv, vals)
                else:
                    arr = np.full(n_touched, -np.inf)
                    np.maximum.at(arr, inv, vals)
                if self._buf_all_int:
                    # All-integer rows: partials ship as exact int64
                    # (the codec never quantizes integer columns), so
                    # integer workloads stay lossless under int8/bf16.
                    arr = np.rint(arr).astype(np.int64)
            cols[name] = arr
        return _wire.encode_agg(cols, self._quant)

    def _merge_dtype(self, name: str) -> str:
        """Device merge-table dtype for one field: ``count`` (exact
        by contract) and every field while the cluster-agreed all-int
        lock holds fold on int32 tables (bit-identical to the host
        f64 oracle); once any peer ships floats the value fields
        promote to float32 — the dequantized wire width."""
        if name == "count" or self._quant_int:
            return "int32"
        return "float32"

    def _seal_merge(self, peer_frames: List[Any]) -> Dict[str, Any]:
        """Seal one quantized round's merge ON MAIN: decode every
        peer frame's raw parts (engine/wire.py ``decode_agg_parts``)
        and resolve scatter targets against the main-owned
        ``key_to_slot`` — the sealed task never reads main state
        (BTX-RACE).  Decides device-vs-host per the sticky
        ``_merge_demoted`` flag: an exact integer part that cannot
        ride the device's int32 tables demotes the merge to the host
        fold for the rest of the run (deterministic — every process
        sees identical frames), and ``BYTEWAX_TPU_WIRE=pickle`` pins
        the host fold wholesale.  Device-bound parts pad to the
        power-of-two bucket ladder (``pad_len``) with the
        exchange-scratch slot as the padding target, so one compiled
        merge program per (op, encoding, dtype, bucket) serves every
        round via the compile cache."""
        from bytewax_tpu.engine.batching import pad_len

        decoded = []
        for frames in peer_frames:
            for frame in frames or ():
                parts = _wire.decode_agg_parts(frame)
                kp = parts.get("key")
                if kp is None or not len(kp[1]):
                    continue
                decoded.append(
                    (kp[1], {n: parts[n] for n in self.kind.fields})
                )
        if not self._merge_demoted and self._needs_host_fold(decoded):
            self._demote_merge()
        kid_map = self.key_to_slot
        if self._merge_demoted:
            sealed = []
            for keys, fields in decoded:
                gidx = np.fromiter(
                    (
                        self._global_idx(kid_map[k])
                        for k in keys.tolist()
                    ),
                    dtype=np.int64,
                    count=len(keys),
                )
                sealed.append((gidx, fields))
            return {"device": False, "frames": sealed}
        sealed = []
        h2d = 0
        for keys, fields in decoded:
            n = len(keys)
            padded = pad_len(n)
            gidx_p = np.full(
                padded, self.cap_per_shard - 1, dtype=np.int32
            )
            gidx_p[:n] = np.fromiter(
                (self._global_idx(kid_map[k]) for k in keys.tolist()),
                dtype=np.int64,
                count=n,
            )
            h2d += gidx_p.nbytes
            sealed_fields = {}
            for name in self.kind.fields:
                enc, parts = fields[name]
                want = self._merge_dtype(name)
                if enc == "int8":
                    scales, q = parts
                    nb = -(-padded // _wire.QBLOCK)
                    scales_p = np.zeros(nb, dtype=np.float32)
                    scales_p[: len(scales)] = scales
                    q_p = np.zeros(padded, dtype=np.int8)
                    q_p[:n] = q
                    sealed_fields[name] = (enc, (scales_p, q_p), want)
                    h2d += scales_p.nbytes + q_p.nbytes
                elif enc == "bf16":
                    hi_p = np.zeros(padded, dtype=np.uint16)
                    hi_p[:n] = parts
                    sealed_fields[name] = (enc, (hi_p,), want)
                    h2d += hi_p.nbytes
                else:  # raw — pre-cast to the table dtype (lossless:
                    # _needs_host_fold demoted anything that is not)
                    arr_p = np.zeros(padded, dtype=np.dtype(want))
                    arr_p[:n] = parts
                    sealed_fields[name] = ("raw", (arr_p,), want)
                    h2d += arr_p.nbytes
            sealed.append((gidx_p, n, sealed_fields))
        _flight.note_transfer("h2d", h2d)
        _flight.RECORDER.count("gsync_merge_h2d_bytes", h2d)
        return {"device": True, "frames": sealed}

    def _needs_host_fold(self, decoded: List[Any]) -> bool:
        """Whether any exact part of this round cannot fold on the
        device tables: an integer column bound for an int32 table
        whose values overflow it (the host f64 fold holds 53 exact
        bits; int32 tables hold 31)."""
        info = np.iinfo(np.int32)
        for _keys, fields in decoded:
            for name in self.kind.fields:
                enc, parts = fields[name]
                if enc != "raw" or self._merge_dtype(name) != "int32":
                    continue
                arr = np.asarray(parts)
                if arr.dtype.kind not in "iu":
                    return True
                if arr.dtype.itemsize > 4 and len(arr) and (
                    arr.max() > info.max or arr.min() < info.min
                ):
                    return True
        return False

    def _demote_merge(self) -> None:
        """Sticky demotion to the host fold (main thread): fence any
        in-flight device merges, fetch the device tables into the
        host-side f64 blocks, and fold host-side from here on."""
        self._merge_demoted = True
        if self._dev_fields is None:
            return
        self.fence()
        self._host_fields = self._fetch_dev_fields()
        self._dev_fields = None

    def _fetch_dev_fields(self) -> Dict[str, np.ndarray]:
        """One device→host fetch of the merge tables (f64 host
        blocks, the emission/baseline format).  Counted under the
        collective tier's transfer counters — this is the ONLY d2h
        the device merge pays (finalize, baselines, demotion), where
        the host fold materialized every round's dequantized
        partials host-side."""
        host = {}
        d2h = 0
        for name, table in self._dev_fields.items():
            raw = np.asarray(table)
            d2h += raw.nbytes
            host[name] = raw.astype(np.float64)
        _flight.note_transfer("d2h", d2h)
        _flight.RECORDER.count("gsync_fetch_d2h_bytes", d2h)
        return host

    def _apply_merge(self, sealed: Dict[str, Any]) -> None:
        """Fold one sealed round (runs on the collective lane under
        overlap, inline otherwise).  Every process folds identical
        frames in identical order with identical programs, so merged
        tables stay cluster-identical — same values, same addition
        order."""
        if sealed["device"]:
            self._apply_merge_device(sealed["frames"])
        else:
            self._apply_merge_host(sealed["frames"])

    def _apply_merge_host(self, sealed_frames: List[Any]) -> None:
        """The host fold (the ``BYTEWAX_TPU_WIRE=pickle``-era
        fallback and the oracle in tests): dequantize each sealed
        part to f64 and scatter into host-resident field blocks."""
        if self._host_fields is None:
            size = self.n_shards * self.cap_per_shard
            self._host_fields = {
                name: np.full(size, init, dtype=np.float64)
                for name, (init, _op) in self.kind.fields.items()
            }
        host_bytes = 0
        for gidx, fields in sealed_frames:
            for name, (_init, op) in self.kind.fields.items():
                enc, parts = fields[name]
                vals = np.asarray(
                    _wire.dequant_part(enc, parts), dtype=np.float64
                )
                host_bytes += vals.nbytes
                tgt = self._host_fields[name]
                if op == "add":
                    np.add.at(tgt, gidx, vals)
                elif op == "min":
                    np.minimum.at(tgt, gidx, vals)
                else:
                    np.maximum.at(tgt, gidx, vals)
        _flight.RECORDER.count("gsync_merge_host_bytes", host_bytes)

    def _apply_merge_device(self, sealed_frames: List[Any]) -> None:
        """The device fold: upload each sealed frame's wire-width
        parts, dequantize+merge+scatter in HBM (engine/xla.py
        ``agg_merge_fn``), and keep the merged tables device-resident
        between closes — no per-round d2h."""
        import jax
        import jax.numpy as jnp

        from bytewax_tpu.engine import xla as _xla

        size = self.n_shards * self.cap_per_shard
        if self._dev_fields is None:
            self._dev_fields = {}
        tables = self._dev_fields
        for gidx_p, n, fields in sealed_frames:
            g = jax.device_put(gidx_p)
            for name, (init, op) in self.kind.fields.items():
                enc, parts, want = fields[name]
                table = tables.get(name)
                if table is None:
                    table = _xla.agg_merge_table(size, init, want)
                elif str(table.dtype) != want:
                    # Deterministic promotion (int32 → float32) at
                    # the first non-all-int round, in round order on
                    # the lane — identical on every process.
                    table = table.astype(jnp.dtype(want))
                fn = _xla.agg_merge_fn(op, enc, want, len(gidx_p))
                tables[name] = fn(
                    table, g, n, *(jax.device_put(p) for p in parts)
                )

    # -- store-composable overlap (docs/recovery.md) -------------------------

    def _mine_local_key(self, base: str) -> str:
        """A deterministic store row key derived from ``base`` whose
        worker lane (``adler32 % worker_count`` — the route the store
        stamps and resume reads scope by) lands on THIS process, so
        the row comes back to the process that wrote it."""
        d = self.driver
        salt = 0
        while True:
            key = f"{base}{salt}"
            if d.is_local(zlib.adler32(key.encode()) % d.worker_count):
                return key
            salt += 1

    def _base_key(self) -> str:
        return self._mine_local_key(_GSYNC_BASE_KEY)

    def _round_key(self, round_no: int) -> str:
        return self._mine_local_key(
            f"{_GSYNC_ROUND_KEY}{round_no:08d}\x00"
        )

    def _stash_round(self, payload_fn) -> None:
        """With a recovery store, make this data-bearing round
        durable: stash a sealed round row for this close's snapshot —
        or, every ``BYTEWAX_TPU_GSYNC_BASELINE_EVERY`` rounds, fence
        the lane and stash a full-aggregate baseline row instead
        (same key every time, so the store's latest-row-per-key read
        supersedes), tombstoning the round rows it covers.  Round
        stash decisions derive from gsync-agreed values
        (``total_rows``), so every process stashes symmetric rows for
        the identical round sequence — resume replays deterministically
        cluster-wide."""
        if self.driver.store is None:
            return
        if self._data_rounds % _gsync_baseline_every() == 0:
            self.fence()
            self._pending_snap_rows.append(
                (self._base_key(), self._capture_baseline())
            )
            self._base_written = True
            self._pending_snap_rows.extend(
                (k, None) for k in self._outstanding_rounds
            )
            self._outstanding_rounds = []
            return
        key = self._round_key(self._data_rounds)
        self._pending_snap_rows.append((key, payload_fn()))
        self._outstanding_rounds.append(key)

    def _capture_baseline(self) -> Dict[str, Any]:
        """Snapshot the full merged aggregate (lane fenced by the
        caller) in a self-contained host format: resume installs it
        and replays only the rounds stashed after it."""
        base: Dict[str, Any] = {
            "round": self._data_rounds,
            # The stored name predates the attribute's: baseline rows
            # outlive the program that wrote them.
            "key_to_kid": dict(self.key_to_slot),
            "shard_fill": list(self._shard_fill),
            "procs": self.driver.proc_count,
        }
        if self._quant != "off":
            if self._dev_fields is not None:
                fields = self._fetch_dev_fields()
            elif self._host_fields is not None:
                fields = {
                    n: a.copy() for n, a in self._host_fields.items()
                }
            else:
                fields = None
            base.update(
                fmt="quant", fields=fields, quant_int=self._quant_int
            )
            return base
        blocks = (
            self._local_host_fields()
            if self._fields is not None
            else None
        )
        base.update(
            fmt="exact",
            blocks=blocks,
            dtype=(
                np.dtype(self.dtype).name
                if self.dtype is not None
                else None
            ),
        )
        return base

    def _install_baseline(self, base: Dict[str, Any]) -> None:
        import jax
        import jax.numpy as jnp

        if base.get("procs") != self.driver.proc_count:
            msg = (
                "the global-exchange tier cannot rescale on resume: "
                f"the store's baseline was written by {base.get('procs')} "
                f"process(es), this cluster runs {self.driver.proc_count}; "
                "resume at the original size or run with "
                "BYTEWAX_TPU_GLOBAL_EXCHANGE=0"
            )
            raise RuntimeError(msg)
        self.key_to_slot = dict(base["key_to_kid"])
        self._shard_fill = list(base["shard_fill"])
        self._data_rounds = base["round"]
        self._base_written = True
        if base["fmt"] == "quant":
            self._quant_int = base["quant_int"]
            fields = base["fields"]
            if fields is None:
                return
            if self._merge_demoted:
                self._host_fields = {
                    n: np.asarray(a, dtype=np.float64)
                    for n, a in fields.items()
                }
                return
            self._dev_fields = {}
            for name, arr in fields.items():
                want = self._merge_dtype(name)
                self._dev_fields[name] = jax.device_put(
                    np.asarray(arr).astype(np.dtype(want))
                )
            return
        if base["dtype"] is not None:
            self.dtype = (
                jnp.int32 if base["dtype"] == "int32" else jnp.float32
            )
        blocks = base["blocks"]
        if blocks is None:
            return
        shape = (self.n_shards * self.cap_per_shard,)
        fields = {}
        for name in self.kind.fields:
            per = blocks[name]

            def cb(index, _per=per):
                start = index[0].start or 0
                return np.ascontiguousarray(_per[start]).astype(
                    np.dtype(self.dtype)
                )

            fields[name] = jax.make_array_from_callback(
                shape, self._sharding, cb
            )
        self._fields = fields

    def _maybe_replay_resume(self) -> None:
        """Install deferred resume rows at the FIRST flush — a
        globally-ordered point every process reaches in lockstep, so
        the replayed collective rounds launch in the identical
        sequence cluster-wide.  The round sequence is symmetric by
        construction (stash decisions derive from gsync-agreed
        values), and rows at or before the installed baseline's
        round are superseded by it."""
        if not self._resume_rows:
            return
        rows, self._resume_rows = self._resume_rows, []
        baseline = None
        rounds = []
        for key, payload in rows:
            if key.startswith(_GSYNC_BASE_KEY):
                if (
                    baseline is None
                    or payload["round"] > baseline["round"]
                ):
                    baseline = payload
            else:
                rounds.append(payload)
        base_no = 0
        if baseline is not None:
            self._install_baseline(baseline)
            base_no = baseline["round"]
        for payload in sorted(rounds, key=lambda p: p["round"]):
            if payload["round"] <= base_no:
                continue
            self._replay_round(payload)
            self._outstanding_rounds.append(
                self._round_key(payload["round"])
            )
            self._data_rounds = max(
                self._data_rounds, payload["round"]
            )
        self._data_rounds = max(self._data_rounds, base_no)

    def _replay_round(self, payload: Dict[str, Any]) -> None:
        """Re-run one sealed-but-uncommitted round from its stashed
        row (inline — replay precedes any overlap)."""
        import jax.numpy as jnp

        self._assign_kids(payload["new"])
        if payload["fmt"] == "quant":
            self._quant_int = self._quant_int and payload["all_int"]
            self._apply_merge(self._seal_merge(payload["frames"]))
            return
        want = (
            jnp.int32 if payload["dtype"] == "int32" else jnp.float32
        )
        if self.dtype is None:
            self.dtype = want
        self._ensure_fields()
        chunk_pd = payload["chunk_pd"]
        n_steps = payload["n_steps"]
        chunk_rows = chunk_pd * self.local_devs
        pad_total = n_steps * chunk_rows
        kids = payload["kids"]
        vals = payload["vals"]
        n_local = len(kids)
        kids_p = np.zeros(pad_total, dtype=np.int32)
        kids_p[:n_local] = kids
        vals_p = np.zeros(pad_total, dtype=np.dtype(self.dtype))
        vals_p[:n_local] = vals
        valid_p = np.zeros(pad_total, dtype=bool)
        valid_p[:n_local] = True
        step = self._step_for(chunk_pd, payload["capacity"])
        self._exchange_chunks(
            step,
            kids_p,
            vals_p,
            valid_p,
            chunk_rows,
            n_steps,
            chunk_pd * self.n_shards,
            np.dtype(self.dtype),
        )

    # -- recovery / emission --------------------------------------------------

    def load(self, key: str, state: Any) -> None:
        self.load_many([(key, state)])

    def load_many(self, items) -> None:
        """Defer resumed store rows for replay at the first flush.
        Only the tier's OWN rows (sealed rounds + baselines) resume;
        a store written by a per-process tier cannot page user-key
        state into the collective tier (kid assignment is a
        collective agreement, and resume reads are route-scoped)."""
        for key, state in items:
            if not key.startswith(_GSYNC_KEY_PREFIX):
                msg = (
                    "the global-exchange tier cannot resume "
                    "user-key state written by another tier "
                    f"(got row {key!r}); resume this store with "
                    "BYTEWAX_TPU_GLOBAL_EXCHANGE=0"
                )
                raise RuntimeError(msg)
            self._resume_rows.append((key, state))

    def snapshots_for(self, keys: List[str]) -> List[Tuple[str, Any]]:
        if self.driver.store is None:
            # Only reachable with no recovery store (make_agg_state
            # gating) — the epoch snapshot pass discards these.
            return [(k, None) for k in keys]
        # Store-composable overlap: the tier's durable unit is the
        # sealed round/baseline row, never per-user-key rows (state
        # lives merged in HBM; a per-key emission would force the
        # fence the overlap exists to avoid).
        rows, self._pending_snap_rows = self._pending_snap_rows, []
        return rows

    def _local_host_fields(self) -> Dict[str, Dict[int, np.ndarray]]:
        """Per-field {global_offset: block} of this process's shards."""
        out: Dict[str, Dict[int, np.ndarray]] = {}
        d2h = 0
        for name in self.kind.fields:
            blocks: Dict[int, np.ndarray] = {}
            for shard in self._fields[name].addressable_shards:
                start = shard.index[0].start or 0
                blocks[start] = np.asarray(shard.data)
                d2h += blocks[start].nbytes
            out[name] = blocks
        _flight.note_transfer("d2h", d2h)
        _flight.RECORDER.count("gsync_fetch_d2h_bytes", d2h)
        return out

    def _exactify(self, val: Any) -> Any:
        """Re-integerize a quant-mode final value when every merged
        flush was all-integer, matching the exact tier's int lock
        (``8`` out, never ``8.0``)."""
        if not self._quant_int:
            return val
        if self.kind_name in ("sum", "min", "max"):
            return int(val)
        if self.kind_name == "stats":
            mn, mean, mx, count = val
            return (int(mn), mean, int(mx), count)
        return val

    def finalize(self) -> List[Tuple[str, Any]]:
        """Flush any tail rows (collective — the EOF ladder has every
        process in this call), fence any overlapped round (the global
        result is about to be read), then emit ``(key, final)`` for
        the keys whose owner shard lives on THIS process
        (lane-aligned placement makes those exactly this process's
        emission keys), sorted by key."""
        self.flush()
        self.fence()
        out: List[Tuple[str, Any]] = []
        if self._quant != "off":
            if self._dev_fields is not None:
                # The device merge's ONE d2h: the merged aggregate
                # leaves HBM only here (and at baselines/demotion).
                self._host_fields = self._fetch_dev_fields()
                self._dev_fields = None
            if self._host_fields is not None and self.key_to_slot:
                my_shards = set(
                    self._proc_shards[self.driver.proc_id]
                )
                for key in sorted(self.key_to_slot):
                    kid = self.key_to_slot[key]
                    if kid % self.n_shards not in my_shards:
                        continue  # another process's shard emits it
                    out.append(
                        (
                            key,
                            self._exactify(
                                _final_of(
                                    self.kind_name,
                                    self._host_fields,
                                    self._global_idx(kid),
                                )
                            ),
                        )
                    )
        elif self._fields is not None and self.key_to_slot:
            blocks = self._local_host_fields()
            first_field = next(iter(self.kind.fields))
            #: block start -> membership test happens once per key.
            starts = sorted(blocks[first_field])

            for key in sorted(self.key_to_slot):
                gidx = self._global_idx(self.key_to_slot[key])
                start = next(
                    (
                        s
                        for s in starts
                        if s <= gidx < s + len(blocks[first_field][s])
                    ),
                    None,
                )
                if start is None:
                    continue  # another process's shard emits it
                flat = {
                    name: blocks[name][start][
                        gidx - start : gidx - start + 1
                    ]
                    for name in self.kind.fields
                }
                out.append((key, _final_of(self.kind_name, flat, 0)))
        if self.driver.store is not None:
            # The aggregate just emitted and resets: this close's own
            # not-yet-written round rows drop, durable rounds and the
            # baseline tombstone (a resumed post-EOF store replays
            # nothing).
            dropped = {
                k
                for k, p in self._pending_snap_rows
                if p is not None
            }
            self._pending_snap_rows = [
                (k, p) for k, p in self._pending_snap_rows if p is None
            ]
            self._pending_snap_rows.extend(
                (k, None)
                for k in self._outstanding_rounds
                if k not in dropped
            )
            self._outstanding_rounds = []
            if self._base_written:
                self._pending_snap_rows.append((self._base_key(), None))
                self._base_written = False
        self.key_to_slot.clear()
        self._shard_fill = [0] * self.n_shards
        self._fields = None
        self._host_fields = None
        self._dev_fields = None
        self.dtype = None
        self._buf_all_int = True
        self._quant_int = True
        self._dense_keys = []
        self._dense_map = {}
        self._vocab = VocabMap(dtype=np.int32)
        return out
