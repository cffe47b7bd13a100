"""Pallas TPU kernel for the keyed segment fold.

The default device fold is an XLA scatter-combine
(``ops/segment.py``), which XLA lowers well but serializes on slot
collisions.  This kernel instead reduces each row tile against the
whole slot table with a masked VPU reduction (one-hot compare +
reduce) — collision-free, VMEM-resident, and tiled to the VPU lanes —
computing every aggregation field of the kind in one pass over a
single mask, then combines tiles into the accumulator across grid
steps.  The table is reduced one ``_CAP_BLOCK`` of slots at a time so
the mask's size does not grow with capacity.

Enable with ``BYTEWAX_TPU_PALLAS=1`` (on non-TPU backends the same
kernel runs in interpret mode, so tests exercise it).  Scope: float32
accumulators with slot tables up to a few thousand keys (the work
is rows × capacity); integer states and the dictionary-encoded/packed
wire paths keep the exact XLA scatter.
"""

import functools
import os
from typing import Dict

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from bytewax_tpu.ops.segment import AggKind

__all__ = ["enabled", "fits", "maybe_update_fields", "update_fields_pallas"]

#: Rows reduced per grid step.
_TILE = 512
#: Slots reduced per grid step: the one-hot mask and each field's
#: masked copy are ``_TILE x _CAP_BLOCK`` f32 temporaries (1 MiB), so
#: a handful of them stay far inside a core's scoped VMEM whatever
#: the table's capacity.
_CAP_BLOCK = 512
#: Max slot-table size for the one-hot strategy: its work is rows x
#: capacity, so it is only ever a candidate for small tables.
_MAX_CAP = 4096


def enabled() -> bool:
    return os.environ.get("BYTEWAX_TPU_PALLAS") == "1"


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _fold_kernel(field_ops, cap_block, slots_ref, vals_ref, out_ref):
    """One ``(capacity block, row tile)`` grid step.  ``field_ops`` is
    a static tuple of (field_index, op_name, init, is_count); the
    one-hot mask is built once and reused for every field.  Rows
    arrive as ``[TILE, 1]`` columns (sublane-major, as the mask needs
    them: no relayout in the kernel) and broadcast along the lanes
    against the block's slot numbers; the row-tile axis is the inner
    grid axis, so the output block stays resident while every tile
    folds into it."""
    tile = pl.program_id(1)

    @pl.when(tile == 0)
    def _init():
        for idx, _op, init, _is_count in field_ops:
            out_ref[idx : idx + 1, :] = jnp.full(
                (1, cap_block), init, dtype=jnp.float32
            )

    slots = slots_ref[:, :]  # [TILE, 1] int32
    vals = vals_ref[:, :]  # [TILE, 1] f32
    first_slot = pl.program_id(0) * cap_block
    hit = slots == first_slot + jax.lax.broadcasted_iota(
        jnp.int32, (_TILE, cap_block), 1
    )
    for idx, op_name, _init, is_count in field_ops:
        row = out_ref[idx : idx + 1, :]
        if op_name == "add":
            c = 1.0 if is_count else vals
            part = jnp.sum(
                jnp.where(hit, c, 0.0), axis=0, keepdims=True
            )
            out_ref[idx : idx + 1, :] = row + part
        elif op_name == "min":
            part = jnp.min(
                jnp.where(hit, vals, jnp.inf), axis=0, keepdims=True
            )
            out_ref[idx : idx + 1, :] = jnp.minimum(row, part)
        else:  # max
            part = jnp.max(
                jnp.where(hit, vals, -jnp.inf), axis=0, keepdims=True
            )
            out_ref[idx : idx + 1, :] = jnp.maximum(row, part)


@functools.partial(jax.jit, static_argnames=("kind",), donate_argnums=(1,))
def update_fields_pallas(
    kind: AggKind,
    state: Dict[str, jax.Array],
    slot_ids: jax.Array,
    values: jax.Array,
) -> Dict[str, jax.Array]:
    """Drop-in alternative to ``segment.update_fields`` built on the
    Pallas fold (float32 accumulators only).  Padding rows must target
    the scratch slot (``capacity - 1``), which is reset to the
    identity afterwards."""
    capacity = next(iter(state.values())).shape[0]
    n = slot_ids.shape[0]
    pad = (-n) % _TILE
    if pad:
        scratch = jnp.full((pad,), capacity - 1, dtype=slot_ids.dtype)
        slot_ids = jnp.concatenate([slot_ids, scratch])
        values = jnp.concatenate(
            [values, jnp.zeros((pad,), dtype=values.dtype)]
        )
    n_padded = slot_ids.shape[0]
    cap_block = min(capacity, _CAP_BLOCK)
    if capacity % cap_block:
        msg = (
            f"slot-table capacity {capacity} is not a multiple of "
            f"the kernel's {cap_block}-slot block"
        )
        raise ValueError(msg)

    names = list(kind.fields)
    field_ops = tuple(
        (i, kind.fields[name][1], float(kind.fields[name][0]), name == "count")
        for i, name in enumerate(names)
    )
    partials = pl.pallas_call(
        functools.partial(_fold_kernel, field_ops, cap_block),
        out_shape=jax.ShapeDtypeStruct((len(names), capacity), jnp.float32),
        grid=(capacity // cap_block, n_padded // _TILE),
        in_specs=[
            pl.BlockSpec((_TILE, 1), lambda c, t: (t, 0)),
            pl.BlockSpec((_TILE, 1), lambda c, t: (t, 0)),
        ],
        out_specs=pl.BlockSpec(
            (len(names), cap_block), lambda c, t: (0, c)
        ),
        interpret=_interpret(),
    )(
        slot_ids.reshape(n_padded, 1).astype(jnp.int32),
        values.reshape(n_padded, 1).astype(jnp.float32),
    )

    out = {}
    for i, name in enumerate(names):
        init, op_name = kind.fields[name]
        arr = state[name]
        partial = partials[i]
        if op_name == "add":
            merged = arr + partial.astype(arr.dtype)
        elif op_name == "min":
            merged = jnp.minimum(arr, partial.astype(arr.dtype))
        else:
            merged = jnp.maximum(arr, partial.astype(arr.dtype))
        # The scratch slot absorbed padding rows; restore identity.
        out[name] = merged.at[capacity - 1].set(
            jnp.asarray(init, dtype=merged.dtype)
        )
    return out


def fits(capacity: int) -> bool:
    return capacity <= _MAX_CAP


def maybe_update_fields(kind, state, slot_ids, values):
    """Dispatch to the Pallas kernel when enabled, the table fits, and
    the accumulator is float32 (integer folds stay on the exact XLA
    scatter — the f32 mask path would round values above 2^24)."""
    from bytewax_tpu.ops.segment import update_fields

    first = next(iter(state.values()))
    if (
        enabled()
        and fits(first.shape[0])
        and first.dtype == jnp.float32
    ):
        return update_fields_pallas(kind, state, slot_ids, values)
    return update_fields(kind, state, slot_ids, values)
