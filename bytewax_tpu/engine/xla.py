"""Device-resident keyed aggregation state.

Replaces per-key Python logic objects with slot-table device arrays
for the recognized reduction kinds (see
:mod:`bytewax_tpu.ops.segment`).  The host keeps the key→slot
vocabulary; values fold in on device; snapshots `jax.device_get` only
the slots awoken in the closing epoch, preserving the recovery
contract of the host tier (states are interchangeable between tiers).
"""

import functools
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bytewax_tpu.engine import flight as _flight
from bytewax_tpu.engine.arrays import ArrayBatch, KeyEncoder, VocabMap
from bytewax_tpu.engine.batching import pad_len
from bytewax_tpu.ops.segment import (
    AGG_KINDS,
    AggKind,
    identity_for,
    init_fields,
    reset_fields,
    update_fields,
    update_fields_packed,
    update_fields_vocab,
)

__all__ = ["AccelSpec", "DeviceAggState", "NonNumericValues"]

_MIN_CAPACITY = 1024


class NonNumericValues(TypeError):
    """Values are not device-foldable; the caller should fall back to
    the host tier (distinct from malformed-batch errors, which must
    surface)."""


class AccelSpec:
    """Annotation on a core ``stateful_batch`` op: lower it to a
    device aggregation of this kind instead of per-key Python logics."""

    def __init__(self, kind: str):
        if kind not in AGG_KINDS:
            msg = f"unknown aggregation kind {kind!r}"
            raise ValueError(msg)
        self.kind = kind

    def __repr__(self) -> str:
        return f"AccelSpec({self.kind!r})"


def _final_of(kind: str, fields: Dict[str, np.ndarray], i: int):
    if kind == "sum":
        return fields["sum"][i].item()
    if kind == "count":
        return int(fields["count"][i].item())
    if kind == "min":
        return fields["min"][i].item()
    if kind == "max":
        return fields["max"][i].item()
    if kind == "mean":
        count = fields["count"][i].item()
        return fields["sum"][i].item() / count if count else 0.0
    if kind == "stats":
        count = fields["count"][i].item()
        mean = fields["sum"][i].item() / count if count else 0.0
        return (
            fields["min"][i].item(),
            mean,
            fields["max"][i].item(),
            int(count),
        )
    raise AssertionError(kind)


def _snaps_of(kind: str, fields: Dict[str, np.ndarray], idx: np.ndarray):
    """Host-format snapshots of the table rows ``idx``: one gather
    and one ``tolist()`` a field, no Python per row but the tuples
    themselves.  Single-field kinds snapshot the bare scalar (floats;
    an ``int`` count) so host-tier logics can resume from device
    snapshots and vice versa."""

    def col(name: str) -> list:
        return fields[name][idx].tolist()

    if kind in ("sum", "min", "max"):
        return col(kind)
    count = fields["count"][idx].astype(np.int64).tolist()
    if kind == "count":
        return count
    if kind == "mean":
        return list(zip(col("sum"), count))
    if kind == "stats":
        return list(zip(col("min"), col("max"), col("sum"), count))
    raise AssertionError(kind)


def _snaps_for(kind: str, fields, idx_of_key: List[Optional[int]], keys):
    """``(key, snapshot)`` per key, ``None`` where a key has no row."""
    have = [i for i in idx_of_key if i is not None]
    snaps = iter(_snaps_of(kind, fields, np.asarray(have, dtype=np.int64)))
    return [
        (key, None if i is None else next(snaps))
        for key, i in zip(keys, idx_of_key)
    ]


def _field_vals(kind: str, state: Any) -> Dict[str, float]:
    """Decompose a host-format snapshot into per-field scalars."""
    if kind in ("sum", "min", "max", "count"):
        return {kind: float(state)}
    if kind == "mean":
        total, count = state
        return {"sum": float(total), "count": float(count)}
    mn, mx, total, count = state  # stats
    return {
        "min": float(mn),
        "max": float(mx),
        "sum": float(total),
        "count": float(count),
    }


def _state_columns(kind: AggKind, dtype, states, padded: int):
    """Host-format snapshots as one column a field, padded to a
    bucket (repeating the first row — set is idempotent) so pages of
    any length share a few compiled shapes."""
    rows = [_field_vals(kind.name, state) for state in states]
    cols = {}
    for name in kind.fields:
        col = np.empty(padded, dtype=np.dtype(dtype))
        col[: len(rows)] = [row[name] for row in rows]
        col[len(rows) :] = col[0]
        cols[name] = col
    return cols


def _take_free(free: List[int], n: int) -> List[int]:
    """Up to ``n`` slots off the end of a free list, in the order as
    many ``pop()``s would give them."""
    keep = max(len(free) - n, 0)
    taken = free[keep:][::-1]
    del free[keep:]
    return taken


class DeviceAggState:
    """Slot-table aggregation state for one stateful step.

    The last slot of the table is scratch for masked (padding) rows;
    keys occupy slots ``0..capacity-2``.  Tables double when full so
    XLA recompiles only O(log n) shapes.
    """

    def __init__(self, kind: str, sharding: Optional[Any] = None):
        self.kind_name = kind
        self.kind = AGG_KINDS[kind]
        self.sharding = sharding
        self.capacity = _MIN_CAPACITY
        self.key_to_slot: Dict[str, int] = {}
        self.slot_keys: List[Optional[str]] = []
        self._free: List[int] = []
        self._pending_reset: List[int] = []
        self.dtype = jnp.float32
        self._fields = None  # lazy until first update/load
        # Dictionary-encoded fast path: external id -> slot table,
        # mirrored on device so raw (id, value) columns are all the
        # host ships per batch.
        self._vocab = VocabMap(dtype=np.int32)
        self._dev_map = None
        # Automatic encoder for plain string key columns: steady
        # state is one searchsorted per batch, no per-row hashing.
        self._enc = KeyEncoder()
        # One-pass itemized promotion (native kv_encode): dense ids
        # assigned in first-sight order, mapped to slots via one
        # gather per batch.
        self._iddict: Dict[str, int] = {}
        self._id_keys: List[str] = []
        self._id_to_slot = np.empty(0, dtype=np.int32)

    # -- slot management ---------------------------------------------------

    def _ensure_fields(self) -> None:
        if self._fields is None:
            self._fields = init_fields(self.kind, self.capacity, self.dtype)
            if self.sharding is not None:
                self._fields = {
                    k: jax.device_put(v, self.sharding)
                    for k, v in self._fields.items()
                }
            self._pending_reset.clear()
        else:
            self._apply_resets()

    def _grow_to(self, needed: int) -> None:
        new_cap = self.capacity
        while new_cap - 1 < needed:
            new_cap *= 2
        if new_cap == self.capacity:
            return
        # The scratch slot moves to the new last index; any device
        # id→slot table pointing at the old scratch is stale.
        self._dev_map = None
        if self._fields is None:
            # Nothing folded yet: the table is made at its final size
            # (and dtype) by the first update or load.
            self.capacity = new_cap
            return
        self._apply_resets()
        grown = {}
        for name, (init, _op) in self.kind.fields.items():
            old = self._fields[name]
            # Identities in the accumulator dtype (see
            # segment.update_fields): a float identity does not cast
            # safely into an integer table.
            ident = identity_for(init, old.dtype)
            # The old scratch slot becomes a real slot: clear it.
            old = old.at[self.capacity - 1].set(ident)
            pad = jnp.full((new_cap - self.capacity,), ident, dtype=old.dtype)
            arr = jnp.concatenate([old, pad])
            if self.sharding is not None:
                arr = jax.device_put(arr, self.sharding)
            grown[name] = arr
        self._fields = grown
        self.capacity = new_cap

    def alloc(self, key: str) -> int:
        """Assign (or return) the slot for a key, reusing freed slots."""
        slot = self.key_to_slot.get(key)
        if slot is not None:
            return slot
        if self._free:
            slot = self._free.pop()
            self._pending_reset.append(slot)
            self.slot_keys[slot] = key
        else:
            self._grow_to(len(self.slot_keys) + 2)
            slot = len(self.slot_keys)
            self.slot_keys.append(key)
        self.key_to_slot[key] = slot
        return slot

    def discard(self, key: str) -> None:
        """Release a key's slot for reuse (its state is reset when the
        slot is reallocated)."""
        slot = self._release(key)
        if slot is not None and self._vocab.drop_ids([slot]):
            # The on-device id→slot table still routes the dropped
            # external id to this (now reusable) slot; rebuild it
            # on the next vocab sync.
            self._dev_map = None

    def _release(self, key: str) -> Optional[int]:
        """Free a key's slot WITHOUT the vocab drop (extract_keys
        batches that into one pass); returns the freed slot."""
        slot = self.key_to_slot.pop(key, None)
        if slot is not None:
            self.slot_keys[slot] = None  # type: ignore[call-overload]
            self._free.append(slot)
            self._enc.drop(key)
            if self._iddict:
                # Dense ids must stay collision-free (kv_encode
                # assigns len(dict)), so a discard invalidates the
                # itemized cache wholesale; keys re-intern to their
                # existing slots on the next batch.  Callers that
                # discard per-close (window accel) never use this
                # cache, so the reset is effectively free.
                self._iddict = {}
                self._id_keys = []
                self._id_to_slot = np.empty(0, dtype=np.int32)
        return slot

    # The id-based slot surface for a caller that keeps its own table
    # of what a slot holds (the window tier: integer (key, window)
    # composites): a whole delivery's slots are given out, read and
    # taken back in one call each, and carry no key here.  Shared
    # with ShardedAggState, where the ids are wire ids.

    def open_ids(self, place: np.ndarray) -> np.ndarray:
        """One slot per entry of ``place`` (only its length matters
        on one device), in the order that many :meth:`alloc`s would
        take them: freed slots from the end of the free list, then
        fresh ones with one growth."""
        n = len(place)
        reused = _take_free(self._free, n)
        self._pending_reset.extend(reused)
        fresh = n - len(reused)
        start = len(self.slot_keys)
        if fresh:
            self._grow_to(start + fresh + 1)
            self.slot_keys.extend([None] * fresh)  # type: ignore[list-item]
        slots = np.empty(n, dtype=np.int32)
        slots[: len(reused)] = reused
        slots[len(reused) :] = np.arange(start, start + fresh)
        return slots

    def release_ids(self, slots: np.ndarray) -> None:
        """Take back slots :meth:`open_ids` gave out (reset when they
        are given out again), with one vocab drop for the batch."""
        freed = slots.tolist()
        self._free.extend(freed)
        if self._vocab.drop_ids(freed):
            self._dev_map = None

    def states_of(self, slots: np.ndarray) -> List[Any]:
        """Host-format snapshots of the given slots, in order (one
        device_get)."""
        self._ensure_fields()
        host = self._fetch()
        # Ledger: fetched columns to host-format states is part of
        # `close_emit` (its rows are counted by the close that asked).
        with _flight.span("close_emit"):
            return _snaps_of(self.kind_name, host, slots)

    def _apply_resets(self) -> None:
        if self._fields is None:
            self._pending_reset.clear()
            return
        if not self._pending_reset:
            return
        # Pad to a bucket (repeating the first slot — set is
        # idempotent) so XLA sees few distinct shapes.
        n = len(self._pending_reset)
        padded = pad_len(n, floor_pow=3)
        slots_np = np.full(padded, self._pending_reset[0], dtype=np.int32)
        slots_np[:n] = self._pending_reset
        self._fields = reset_fields(
            self.kind, self._fields, jax.device_put(slots_np)
        )
        self._pending_reset.clear()

    def update_slots(self, slot_ids: np.ndarray, values: np.ndarray) -> None:
        """Fold rows into pre-allocated slots (fast path for callers
        managing their own key→slot mapping via :meth:`alloc`)."""
        with _flight.span("prep", rows=len(values)):
            self._pick_dtype(values)
            self._ensure_fields()
            slot_ids = slot_ids.astype(np.int32)
        self._scatter(slot_ids, values)

    # The id-based fold surface shared with ShardedAggState: ids are
    # whatever :meth:`alloc` returned (slots here, wire kids there).
    update_ids = update_slots

    # -- updates -----------------------------------------------------------

    def _pick_dtype(self, values: np.ndarray) -> np.ndarray:
        """Choose the accumulator dtype; integer inputs that don't fit
        32 bits fall back to the exact host tier.  Per-key integer
        sums exceeding 2^31 are out of scope for the device tier —
        use a plain Python reducer for bigint arithmetic."""
        if np.issubdtype(values.dtype, np.integer):
            if values.dtype.itemsize > 4:
                if len(values) and (
                    values.max() > np.iinfo(np.int32).max
                    or values.min() < np.iinfo(np.int32).min
                ):
                    msg = (
                        "device-accelerated reduction over integers "
                        "wider than 32 bits is not exact; pass a plain "
                        "Python reducer"
                    )
                    raise NonNumericValues(msg)
                values = values.astype(np.int32)
            if self._fields is None:
                self.dtype = jnp.int32
        elif self.dtype == jnp.int32 and len(values):
            # Mirrors the value_scale guard: a float batch after the
            # accumulator locked to int32 would otherwise be silently
            # truncated by the host-side cast into the int32 carrier.
            # Integral in-range floats (e.g. the count path's ones
            # after resuming an int snapshot) cast losslessly and
            # pass through.
            if (
                np.any(values % 1)
                or values.max() > np.iinfo(np.int32).max
                or values.min() < np.iinfo(np.int32).min
            ):
                msg = (
                    "non-integral float values arrived after earlier "
                    "batches locked this step's device state to an "
                    "integer dtype; pass a plain Python reducer for "
                    "mixed int/float streams"
                )
                raise TypeError(msg)
        return values

    def update_items(self, items: List[Any]):
        """One-pass itemized fast path: native ``kv_encode`` walks
        each ``(key, value)`` tuple exactly once (dict-encode + value
        fill), then one gather maps dense ids to slots and one
        scatter folds the batch.  Returns the touched keys, or None
        when the native module is unavailable (caller falls back).
        Raises :class:`NonNumericValues` for rows the device tier
        can't take, with no state mutated."""
        from bytewax_tpu.native import kv_encode as _kv_encode

        with _flight.span("prep", rows=len(items)):
            n = len(items)
            ids = np.empty(n, dtype=np.int32)
            vals = np.empty(n, dtype=np.float64)
            ivals = np.empty(n, dtype=np.int64)
            try:
                res = _kv_encode(items, self._iddict, ids, vals, ivals)
            except TypeError as ex:
                raise NonNumericValues(str(ex)) from ex
            if res is None:
                return None
            new_keys, all_int = res
            if all_int:
                # Preserve the exact-integer accumulator the per-item
                # path would have picked: the int64 lane is filled
                # directly by the C pass (a float64 round-trip would
                # round integers past 2^53).
                vals = ivals
            try:
                vals = self._pick_dtype(vals)
            except (NonNumericValues, TypeError):
                # Undo the C pass's id assignments so a host fallback
                # (or any caller that survives the error) sees a
                # genuinely untouched state.
                for k in new_keys:
                    self._iddict.pop(k, None)
                raise
            if new_keys:
                self._id_keys.extend(new_keys)
                self._id_to_slot = np.concatenate(
                    [
                        self._id_to_slot,
                        np.fromiter(
                            (self.alloc(k) for k in new_keys),
                            dtype=np.int32,
                            count=len(new_keys),
                        ),
                    ]
                )
            self._ensure_fields()
            slots = self._id_to_slot[ids]
        self._scatter(slots, vals)
        counts = np.bincount(ids, minlength=len(self._id_keys))
        return [
            self._id_keys[i] for i in np.nonzero(counts)[0].tolist()
        ]

    def update(self, keys: np.ndarray, values: np.ndarray) -> List[str]:
        """Fold ``(key, value)`` rows in; returns the unique keys
        touched (for epoch snapshot bookkeeping)."""
        keys = np.asarray(keys)
        values = np.asarray(values)
        if values.dtype == object or values.dtype.kind in "US":
            msg = (
                "device-accelerated reduction requires numeric values; "
                "pass a plain Python reducer for non-numeric data"
            )
            raise NonNumericValues(msg)
        with _flight.span("prep", rows=len(values)):
            values = self._pick_dtype(values)
        row_slots = self._enc.encode(
            keys, lambda ks: [self.alloc(k) for k in ks]
        )
        with _flight.span("prep"):
            self._ensure_fields()
            slots = row_slots.astype(np.int32, copy=False)
        self._scatter(slots, values)
        return [
            self.slot_keys[s] for s in np.unique(row_slots).tolist()
        ]

    def _scatter(self, slot_ids: np.ndarray, values: np.ndarray) -> None:
        from bytewax_tpu.ops.pallas_fold import maybe_update_fields

        n = len(values)
        # Bucketed padding (engine/batching.py) so XLA sees few
        # distinct shapes; padding rows target the scratch slot
        # (capacity - 1).
        padded = pad_len(n)
        with _flight.span("h2d", rows=padded):
            slots_p = np.full(padded, self.capacity - 1, dtype=np.int32)
            slots_p[:n] = slot_ids
            vals_p = np.zeros(padded, dtype=np.dtype(self.dtype))
            vals_p[:n] = values
            _flight.note_transfer("h2d", slots_p.nbytes + vals_p.nbytes)
            slots_d = jax.device_put(slots_p)
            vals_d = jax.device_put(vals_p)
        with _flight.span("dispatch"):
            self._fields = maybe_update_fields(
                self.kind, self._fields, slots_d, vals_d
            )

    def _fetch(self) -> Dict[str, np.ndarray]:
        """One stacked device→host transfer for all fields (one
        round-trip instead of one per field)."""
        names = list(self.kind.fields)
        with _flight.span("fetch", rows=self.capacity):
            stacked = np.asarray(
                jnp.stack([self._fields[name] for name in names])
            )
            _flight.note_transfer("d2h", stacked.nbytes)
        return {name: stacked[i] for i, name in enumerate(names)}

    def _sync_vocab(self, ids: np.ndarray, vocab: np.ndarray) -> np.ndarray:
        """Assign slots for newly-seen external ids (alloc reuses a
        recovery-resumed slot if one exists) and refresh the on-device
        id→slot table; returns the touched unique ids."""
        had_new = []

        def alloc_many(keys):
            had_new.extend(keys)
            # alloc reuses a recovery-resumed slot if one exists.
            return [self.alloc(key) for key in keys]

        uniq = self._vocab.sync(ids, vocab, alloc_many)
        if had_new or self._dev_map is None:
            # Rebuild the device table: unseen ids and the padding
            # sentinel (index len(vocab)) route to the scratch slot.
            with _flight.span("h2d") as sp:
                table = np.append(self._vocab.table, -1)
                table = np.where(
                    table < 0, self.capacity - 1, table
                ).astype(np.int32)
                sp.rows = len(table)
                _flight.note_transfer("h2d", table.nbytes)
                self._dev_map = jax.device_put(table)
        return uniq

    def update_batch(self, batch: ArrayBatch) -> List[str]:
        if "key_id" in batch.cols and batch.key_vocab is not None:
            # Ledger: `prep` ends before the vocab sync (`encode`) and
            # begins again after it, so no work span holds another.
            with _flight.span("prep") as sp:
                ids = batch.numpy("key_id")
                values = batch.numpy("value")
                sp.rows = len(values)
                quantized = (
                    batch.value_scale is not None
                    and values.dtype == np.int16
                )
                if (
                    batch.value_scale is not None
                    and self.dtype != jnp.float32
                ):
                    msg = (
                        "fixed-point (value_scale) batches need a float "
                        "accumulator, but earlier batches locked this "
                        "step's state to an integer dtype"
                    )
                    raise TypeError(msg)
                if batch.value_scale is not None and not quantized:
                    # Fixed-point values in a non-int16 carrier:
                    # dequantize host-side into the (float)
                    # accumulator dtype.
                    values = (values * batch.value_scale).astype(
                        np.float32
                    )
                elif not quantized:
                    values = self._pick_dtype(values)
            uniq = self._sync_vocab(ids, batch.key_vocab)
            with _flight.span("prep"):
                self._ensure_fields()
                n = len(values)
                sentinel = len(self._vocab.table)
                padded = pad_len(n)
            if quantized and sentinel < 2**15:
                # Fixed-point fast path: one int16 [2, n] transfer.
                with _flight.span("h2d", rows=padded):
                    packed = np.full(
                        (2, padded), sentinel, dtype=np.int16
                    )
                    packed[0, :n] = ids
                    packed[1, :n] = values
                    packed[1, n:] = 0
                    _flight.note_transfer("h2d", packed.nbytes)
                    packed_d = jax.device_put(packed)
                    scale = jnp.float32(batch.value_scale)
                with _flight.span("dispatch"):
                    self._fields = update_fields_packed(
                        self.kind,
                        self._fields,
                        self._dev_map,
                        packed_d,
                        scale,
                    )
            else:
                with _flight.span("h2d", rows=padded):
                    id_dtype = np.int16 if sentinel < 2**15 else np.int32
                    ids_p = np.full(padded, sentinel, dtype=id_dtype)
                    ids_p[:n] = ids
                    vals_p = np.zeros(padded, dtype=np.dtype(self.dtype))
                    vals_p[:n] = values
                    _flight.note_transfer(
                        "h2d", ids_p.nbytes + vals_p.nbytes
                    )
                    ids_d = jax.device_put(ids_p)
                    vals_d = jax.device_put(vals_p)
                with _flight.span("dispatch"):
                    self._fields = update_fields_vocab(
                        self.kind,
                        self._fields,
                        self._dev_map,
                        ids_d,
                        vals_d,
                    )
            return [str(self._vocab.vocab[e]) for e in uniq.tolist()]
        if "key" in batch.cols:
            values = batch.numpy("value")
            if batch.value_scale is not None:
                values = (values * batch.value_scale).astype(np.float32)
            return self.update(batch.numpy("key"), values)
        msg = (
            "columnar batch feeding an accelerated keyed aggregation "
            "needs a 'key' or dictionary-encoded 'key_id' column"
        )
        raise TypeError(msg)

    # -- recovery ----------------------------------------------------------

    def _maybe_lock_int(self, state: Any) -> None:
        if (
            self.kind_name in ("sum", "min", "max", "count")
            and isinstance(state, int)
            and self._fields is None
        ):
            self.dtype = jnp.int32

    def load(self, key: str, state: Any) -> None:
        """Install a resumed snapshot for a key (host-tier format).
        Slot assignment goes through :meth:`alloc` so freed (evicted/
        discarded) slots are reused instead of growing the table."""
        self._maybe_lock_int(state)
        field_vals = _field_vals(self.kind_name, state)
        slot = self.alloc(key)
        self._ensure_fields()
        for name, val in field_vals.items():
            self._fields[name] = (
                self._fields[name].at[slot].set(jnp.asarray(val, self.dtype))
            )

    def load_many(self, items: List[Tuple[str, Any]]) -> None:
        """Batched resume: ONE scatter per field for a whole page of
        host-format snapshots.  A per-key :meth:`load` is a device
        dispatch per key — resuming 10^6 keys that way is 10^6 jax
        ops; this is O(fields) ops per page."""
        if not items:
            return
        self._maybe_lock_int(items[0][1])
        # alloc reuses freed (evicted/discarded) slots and grows on
        # demand.
        slots = np.fromiter(
            (self.alloc(key) for key, _state in items),
            dtype=np.int32,
            count=len(items),
        )
        self.load_ids(slots, [state for _key, state in items])

    def load_ids(self, ids: np.ndarray, states: List[Any]) -> None:
        """Install host-format snapshots into slots already given out
        (:meth:`alloc`, :meth:`open_ids`): one scatter per field."""
        n = len(states)
        if not n:
            return
        self._maybe_lock_int(states[0])
        padded = pad_len(n, floor_pow=3)
        cols = _state_columns(self.kind, self.dtype, states, padded)
        slots = np.empty(padded, dtype=np.int32)
        slots[:n] = ids
        slots[n:] = slots[0]
        # Pending resets apply here, BEFORE the scatter installs the
        # resumed values.
        self._ensure_fields()
        with _flight.span("h2d", rows=padded):
            _flight.note_transfer(
                "h2d",
                slots.nbytes + sum(c.nbytes for c in cols.values()),
            )
            dev_slots = jax.device_put(slots)
            for name, col in cols.items():
                self._fields[name] = (
                    self._fields[name].at[dev_slots].set(jax.device_put(col))
                )

    def snapshots_for(self, keys: List[str]) -> List[Tuple[str, Any]]:
        """Host-format snapshots of specific keys (one device_get)."""
        if self._fields is None or not keys:
            return [(k, None) for k in keys]
        host = self._fetch()
        # Ledger: turning the fetched slots into host-format states
        # is part of `close_emit` (its rows are counted by the close
        # that asked).
        with _flight.span("close_emit"):
            return _snaps_for(
                self.kind_name,
                host,
                [self.key_to_slot.get(key) for key in keys],
                keys,
            )

    # -- finalization ------------------------------------------------------

    def finalize(self) -> List[Tuple[str, Any]]:
        """Emit ``(key, final_value)`` for every live key, sorted by
        key (matching the host tier's EOF ordering), and clear."""
        if not self.slot_keys:
            return []
        self._ensure_fields()
        host = self._fetch()
        with _flight.span("close_emit", rows=len(self.key_to_slot)):
            out = [
                (
                    key,
                    _final_of(self.kind_name, host, self.key_to_slot[key]),
                )
                for key in sorted(self.key_to_slot)
            ]
        self.key_to_slot.clear()
        self.slot_keys.clear()
        self._fields = None
        self._vocab = VocabMap(dtype=np.int32)
        self._dev_map = None
        self._enc.clear()
        self._iddict = {}
        self._id_keys = []
        self._id_to_slot = np.empty(0, dtype=np.int32)
        return out

    def keys(self) -> List[str]:
        return [k for k in self.slot_keys if k is not None]

    def flush(self) -> None:
        """Block until every dispatched fold has materialized on
        device.  ``update*`` only enqueue under JAX async dispatch;
        the engine's pipeline (``engine/pipeline.py``) defers all host
        readbacks to drain points, and this is the state-level wait
        those drain points (snapshot, demotion, EOF) rest on."""
        if self._fields is not None:
            jax.block_until_ready(self._fields)

    def demotion_snapshots(self) -> List[Tuple[str, Any]]:
        """Every live key's host-format snapshot — the full-state
        drain the driver uses to demote this step to the host tier
        after repeated device faults (host logics rebuild from these
        exactly as a recovery resume would)."""
        return self.snapshots_for(self.keys())

    # -- residency (engine/residency.py) ------------------------------------

    def extract_keys(self, keys: List[str]) -> List[Tuple[str, Any]]:
        """Snapshot AND release the given keys (one device_get for the
        batch): the residency manager's eviction surface.  Released
        slots reset lazily on reuse; keys with no folded state release
        with no snapshot.  The vocab drop runs as ONE vectorized pass
        over the whole victim batch (a per-key drop is an O(vocab)
        scan each).  Callers own the drain-point scheduling — no fold
        referencing these slots may be in flight."""
        snaps = self.snapshots_for(keys)
        slots = [
            s for s in (self._release(key) for key in keys)
            if s is not None
        ]
        if slots and self._vocab.drop_ids(slots):
            self._dev_map = None
        return [(k, s) for k, s in snaps if s is not None]

    def inject_keys(self, items: List[Tuple[str, Any]]) -> None:
        """Reinstall previously-extracted keys (host-format snapshots,
        one scatter per field) — the residency-fault restore path."""
        self.load_many(items)


# -- global-exchange device merge (docs/performance.md "Overlapped
# -- collectives") -----------------------------------------------------------
#
# The quantized gsync exchange used to fold peer partial frames
# host-side (``GlobalAggState._merge_partials``): every round decoded
# the block-scaled columns to float64 on the host and ``np.add.at``-ed
# them into host-resident field blocks.  These kernels move that fold
# into HBM — the wire-width parts (int8 q + f32 block scales, bf16
# mantissas, narrowed exact integers) upload as-is, dequantize on
# device, and scatter into a device-resident aggregate table, so the
# merged aggregate never leaves HBM between closes (EQuARX, PAPERS.md)
# and the only per-round host traffic is the wire frames themselves.
# Rows pad to the same power-of-two bucket ladder as every other
# device dispatch (``pad_len``), so one compiled program per
# (op, encoding, dtype, bucket) serves every round via the compile
# cache; a traced ``n`` masks the padding.


@functools.lru_cache(maxsize=None)
def agg_merge_fn(
    op: str, enc: str, table_dtype: str, padded_len: int
):
    """One compiled scatter-merge: ``fn(table, gidx, n, *parts) ->
    table``.  ``enc`` is the wire encoding of the value part
    (``raw`` arrives pre-cast to the table dtype; ``int8`` arrives as
    the (scales, q) pair; ``bf16`` as the uint16 mantissas); rows past
    ``n`` fold the op identity (their gidx already targets the
    exchange-scratch slot).  Pure function of its arguments — every
    process compiles the identical program and folds the identical
    frames in the identical order, so merged tables stay
    cluster-identical (same values, same addition order)."""
    from bytewax_tpu.engine.wire import QBLOCK

    dtype = jnp.dtype(table_dtype)
    if op == "add":
        pad_ident = identity_for(0.0, dtype)
    elif op == "min":
        pad_ident = identity_for(float("inf"), dtype)
    else:
        pad_ident = identity_for(float("-inf"), dtype)

    def fn(table, gidx, n, *parts):
        if enc == "int8":
            scales, q = parts
            expanded = jnp.repeat(scales, QBLOCK)[:padded_len]
            vals = (q.astype(jnp.float32) * expanded).astype(dtype)
        elif enc == "bf16":
            (hi,) = parts
            vals = jax.lax.bitcast_convert_type(
                hi.astype(jnp.uint32) << 16, jnp.float32
            ).astype(dtype)
        else:  # raw (pre-cast host-side)
            (vals,) = parts
        valid = jnp.arange(padded_len, dtype=jnp.int32) < n
        vals = jnp.where(valid, vals, pad_ident)
        if op == "add":
            return table.at[gidx].add(vals)
        if op == "min":
            return table.at[gidx].min(vals)
        return table.at[gidx].max(vals)

    return jax.jit(fn)


def agg_merge_table(
    size: int, init: float, table_dtype: str
) -> jax.Array:
    """A fresh device-resident merge table, initialized to the
    field's fold identity (±inf saturates for integer dtypes)."""
    dtype = jnp.dtype(table_dtype)
    return jnp.full((size,), identity_for(init, dtype), dtype=dtype)
