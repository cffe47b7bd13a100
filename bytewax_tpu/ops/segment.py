"""Device kernels for keyed aggregation.

The reference's ``stateful_batch`` calls a Python logic object per key
per batch under the GIL (``/root/reference/src/operators.rs:767-808``).
Here the same aggregation is one compiled program over a slot table:
per-key state lives in device arrays indexed by a host-assigned slot
id, and a whole micro-batch of (slot, value) rows updates at once, no
per-key host roundtrips.  The program is a dense, collision-free
reduce where the table is small (``ops/pallas_fold.py``) and an XLA
scatter-combine where it is not; the table's size and dtype, static
where the fold is traced, decide (:func:`fold_is_dense`).

State arrays grow by doubling so XLA recompiles only O(log n_keys)
times per shape.
"""

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from bytewax_tpu.ops import pallas_fold

__all__ = [
    "AGG_KINDS",
    "AggKind",
    "combine_stats",
    "fold_is_dense",
    "init_fields",
    "scatter_fields",
    "update_fields",
]


class AggKind:
    """Declarative reduction: named state fields, how a batch folds
    into them, and how a final value is read out.

    ``fields`` maps field name to ``(init_value, scatter_op)`` where
    scatter_op is one of ``"add" | "min" | "max"``.
    """

    def __init__(self, name: str, fields: Dict[str, Tuple[float, str]]):
        self.name = name
        self.fields = fields

    def __repr__(self) -> str:
        return f"AggKind({self.name!r})"


AGG_KINDS: Dict[str, AggKind] = {
    "sum": AggKind("sum", {"sum": (0.0, "add")}),
    "count": AggKind("count", {"count": (0.0, "add")}),
    "min": AggKind("min", {"min": (float("inf"), "min")}),
    "max": AggKind("max", {"max": (float("-inf"), "max")}),
    "mean": AggKind("mean", {"sum": (0.0, "add"), "count": (0.0, "add")}),
    # 1BRC-style: min/mean/max in one pass.
    "stats": AggKind(
        "stats",
        {
            "min": (float("inf"), "min"),
            "max": (float("-inf"), "max"),
            "sum": (0.0, "add"),
            "count": (0.0, "add"),
        },
    ),
}


def identity_for(init: float, dtype) -> jax.Array:
    """The fold identity as a value of the accumulator dtype
    (±inf saturates to the integer min/max for integer dtypes)."""
    return jnp.asarray(pallas_fold.identity_scalar(init, dtype), dtype=dtype)


def init_fields(kind: AggKind, capacity: int, dtype=jnp.float32):
    """Fresh state arrays for ``capacity`` slots."""
    return {
        name: jnp.full((capacity,), identity_for(init, dtype), dtype=dtype)
        for name, (init, _op) in kind.fields.items()
    }


@functools.partial(jax.jit, static_argnames=("kind",), donate_argnums=(1,))
def reset_fields(
    kind: AggKind, state: Dict[str, jax.Array], slot_ids: jax.Array
) -> Dict[str, jax.Array]:
    """Set the given slots back to every field's fold identity: one
    program for a batch of released slots and all fields (an eager
    ``.at[].set`` a field is half a dozen dispatches each).  Padding
    repeats a slot; set is idempotent."""
    return {
        name: state[name]
        .at[slot_ids]
        .set(identity_for(init, state[name].dtype))
        for name, (init, _op) in kind.fields.items()
    }


def scatter_fields(
    kind: AggKind,
    state: Dict[str, jax.Array],
    slot_ids: jax.Array,
    values: jax.Array,
) -> Dict[str, jax.Array]:
    """The fold as one XLA scatter-combine a field: 30-35 ns a row on
    a v5e whatever the table's size, since rows that share a slot are
    combined one after another.  The form for large tables."""
    capacity = next(iter(state.values())).shape[0]
    valid = slot_ids != capacity - 1
    out = {}
    for name, (init, op_name) in kind.fields.items():
        arr = state[name]
        # Identities in the accumulator dtype: a weak-float identity
        # would promote integer values through f32 and round them.
        ident = identity_for(init, arr.dtype)
        if name == "count":
            contrib = jnp.ones((), dtype=arr.dtype)
        else:
            contrib = values.astype(arr.dtype)
        out[name] = _combine_at(
            arr, slot_ids, op_name, jnp.where(valid, contrib, ident)
        )
    return out


def _combine_at(arr, slot_ids, op_name: str, rows):
    ref = arr.at[slot_ids]
    if op_name == "add":
        return ref.add(rows)
    if op_name == "min":
        return ref.min(rows)
    if op_name == "max":
        return ref.max(rows)
    msg = f"unknown scatter op {op_name!r}"  # pragma: no cover
    raise ValueError(msg)  # pragma: no cover


def _clear_scratch(kind: AggKind, state: Dict[str, jax.Array]):
    """The scratch slot took the partials of the padding and of the
    unknown ids: back to the identity."""
    return {
        name: state[name]
        .at[-1]
        .set(identity_for(init, state[name].dtype))
        for name, (init, _op) in kind.fields.items()
    }


def _dense_over(state: Dict[str, jax.Array], n_ids: int) -> bool:
    return pallas_fold.fits(n_ids, next(iter(state.values())).dtype)


def fold_is_dense(state: Dict[str, jax.Array], ext_to_slot=None) -> bool:
    """Whether a fold into ``state`` (through ``ext_to_slot``, where
    the rows carry external ids) is the dense reduce
    (``ops/pallas_fold.py``) or the scatter.  The sizes and the
    dtype decide, which a trace sees and so does a caller that counts
    its rows by form: nothing else is asked."""
    if ext_to_slot is not None and _dense_over(state, ext_to_slot.shape[0]):
        return True
    return _dense_over(state, next(iter(state.values())).shape[0])


@functools.partial(jax.jit, static_argnames=("kind",), donate_argnums=(1,))
def update_fields(
    kind: AggKind,
    state: Dict[str, jax.Array],
    slot_ids: jax.Array,
    values: jax.Array,
) -> Dict[str, jax.Array]:
    """Fold a micro-batch of ``(slot, value)`` rows into the state.

    Padding rows carry ``slot_id == capacity - 1`` (the reserved
    scratch slot, which holds the identity before and after); the
    host ships only two arrays per micro-batch.  Donated state buffers
    update in place in HBM.  A small table folds by the dense reduce,
    a large one by the scatter (:func:`fold_is_dense`).
    """
    first = next(iter(state.values()))
    capacity = first.shape[0]
    if not _dense_over(state, capacity):
        return scatter_fields(kind, state, slot_ids, values)
    partials = pallas_fold.dense_partials(
        kind.fields, capacity, first.dtype, slot_ids, values
    )
    return _clear_scratch(kind, combine_stats(kind, state, partials))


@functools.partial(jax.jit, static_argnames=("kind",), donate_argnums=(1,))
def update_fields_vocab(
    kind: AggKind,
    state: Dict[str, jax.Array],
    ext_to_slot: jax.Array,
    ext_ids: jax.Array,
    values: jax.Array,
) -> Dict[str, jax.Array]:
    """Dictionary-encoded fold: rows carry external vocabulary ids;
    the id→slot mapping lives on device so the host ships only the raw
    ``(id, value)`` columns.  Padding rows carry ``ext_id ==
    len(ext_to_slot) - 1`` which must map to the scratch slot.  A
    small vocabulary folds densely by external id and the few
    partials are looked up, not the many rows (the gather of 2^21
    rows costs five dense folds of them); a large one looks every
    row up and then folds as the table's own size says."""
    n_ext = ext_to_slot.shape[0]
    if not _dense_over(state, n_ext):
        slot_ids = ext_to_slot[ext_ids.astype(jnp.int32)]
        return update_fields(kind, state, slot_ids, values)
    partials = pallas_fold.dense_partials(
        kind.fields, n_ext, next(iter(state.values())).dtype, ext_ids, values
    )
    return _clear_scratch(
        kind,
        {
            name: _combine_at(state[name], ext_to_slot, op_name, partials[name])
            for name, (_init, op_name) in kind.fields.items()
        },
    )


@functools.partial(jax.jit, static_argnames=("kind",), donate_argnums=(1,))
def update_fields_packed(
    kind: AggKind,
    state: Dict[str, jax.Array],
    ext_to_slot: jax.Array,
    packed: jax.Array,
    scale: jax.Array,
) -> Dict[str, jax.Array]:
    """Quantized single-transfer fold: ``packed`` is ``[2, n]`` int16
    with row 0 the external ids and row 1 the quantized values
    (``value = packed[1] * scale``).  Halves host→device bytes for
    fixed-point data (e.g. 1BRC deci-degree temperatures)."""
    values = packed[1].astype(jnp.float32) * scale
    return update_fields_vocab(kind, state, ext_to_slot, packed[0], values)


def combine_stats(kind: AggKind, state: Dict[str, jax.Array], other: Dict[str, jax.Array]):
    """Merge two state dicts field-wise (for shard rebalancing and
    snapshot merging)."""
    out = {}
    for name, (_init, op_name) in kind.fields.items():
        if op_name == "add":
            out[name] = state[name] + other[name]
        elif op_name == "min":
            out[name] = jnp.minimum(state[name], other[name])
        else:
            out[name] = jnp.maximum(state[name], other[name])
    return out
