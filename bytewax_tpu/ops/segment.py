"""Device kernels for keyed aggregation.

The reference's ``stateful_batch`` calls a Python logic object per key
per batch under the GIL (``/root/reference/src/operators.rs:767-808``).
Here the same aggregation is one XLA scatter-combine over a slot table:
per-key state lives in device arrays indexed by a host-assigned slot
id, and a whole micro-batch of (slot, value) rows updates in one
fused kernel — MXU/VPU-friendly, no per-key host roundtrips.

State arrays grow by doubling so XLA recompiles only O(log n_keys)
times per shape.
"""

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

__all__ = [
    "AGG_KINDS",
    "AggKind",
    "combine_stats",
    "init_fields",
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
    if jnp.issubdtype(dtype, jnp.integer):
        info = jnp.iinfo(dtype)
        if init == float("inf"):
            return jnp.asarray(info.max, dtype=dtype)
        if init == float("-inf"):
            return jnp.asarray(info.min, dtype=dtype)
        return jnp.asarray(int(init), dtype=dtype)
    return jnp.asarray(init, dtype=dtype)


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


@functools.partial(jax.jit, static_argnames=("kind",), donate_argnums=(1,))
def update_fields(
    kind: AggKind,
    state: Dict[str, jax.Array],
    slot_ids: jax.Array,
    values: jax.Array,
) -> Dict[str, jax.Array]:
    """Fold a micro-batch of ``(slot, value)`` rows into the state.

    Padding rows carry ``slot_id == capacity - 1`` (the reserved
    scratch slot); the validity mask is derived on device so the host
    ships only two arrays per micro-batch.  Donated state buffers
    update in place in HBM.
    """
    capacity = next(iter(state.values())).shape[0]
    valid = slot_ids != capacity - 1
    out = {}
    for name, (init, op_name) in kind.fields.items():
        arr = state[name]
        # Identities in the accumulator dtype: a weak-float identity
        # would promote integer values through f32 and round them.
        ident = identity_for(init, arr.dtype)
        zero = jnp.zeros((), dtype=arr.dtype)
        if name == "count":
            one = jnp.ones((), dtype=arr.dtype)
            contrib = jnp.where(valid, one, zero)
        else:
            contrib = jnp.where(valid, values.astype(arr.dtype), ident)
        ref = arr.at[slot_ids]
        if op_name == "add":
            out[name] = ref.add(jnp.where(valid, contrib, zero))
        elif op_name == "min":
            out[name] = ref.min(contrib)
        elif op_name == "max":
            out[name] = ref.max(contrib)
        else:  # pragma: no cover
            msg = f"unknown scatter op {op_name!r}"
            raise ValueError(msg)
    return out


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
    len(ext_to_slot) - 1`` which must map to the scratch slot."""
    slot_ids = ext_to_slot[ext_ids.astype(jnp.int32)]
    return update_fields(kind, state, slot_ids, values)


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
    slot_ids = ext_to_slot[packed[0].astype(jnp.int32)]
    values = packed[1].astype(jnp.float32) * scale
    return update_fields(kind, state, slot_ids, values)


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
