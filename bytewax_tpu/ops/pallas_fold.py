"""The keyed fold for small slot tables: a dense, collision-free
reduce (Pallas, TPU).

The XLA scatter-combine (``ops/segment.py``) serializes on slot
collisions and costs 30-35 ns a row whatever it scatters into
(``PERF.md`` §6, PR 34).  For a small table the fold is instead
rows x slots compare-selects on the VPU: a block of slot numbers is
held against the rows' ids, the mask is built once and used by every
field, and each field is reduced under it.  No two rows contend for
anything, so the cost does not depend on how the keys fall.

Layout: the rows lie on the lanes, 128 a sublane row, as they lie in
HBM (a ``[n, 1]`` column would be tiled out to 128 lanes there); the
slots lie on the sublanes.  A register block of ``_GROUP`` slots keeps
one ``[_GROUP, 128]`` accumulator a field in vector registers while
every row of the tile passes under it (one sublane-broadcast load of
ids and one of values a 128 rows), so a row and a slot meet in nine
vector operations for ``stats`` and nothing is reduced across lanes
until the whole delivery is in: the ``[slots, 128]`` accumulators stay
in VMEM across the row tiles (the inner grid axis) and XLA folds the
lanes once at the end.  Work is rows x slots: 3.5 ms for 2^21 rows a
1,024 slots on a v5e, against the scatter's 62-74 ms, so
:func:`fits` stops at ``DENSE_MAX_SLOTS``.

Exact for ``count``, ``min`` and ``max``; ``sum`` accumulates in the
table's own dtype (float32 partial sums a lane, then a tree over
lanes: shorter chains than the scatter's one a slot).  int32 tables
reduce in int32 throughout.  On a backend other than the TPU the
same kernel runs interpreted.
"""

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["DENSE_MAX_SLOTS", "dense_partials", "fits", "identity_scalar"]

#: The largest table the dense reduce is chosen for: where it stops
#: being at least twice as fast as the scatter (v5e, ``stats``,
#: float32, 2^21 rows: 28.6 ms against 61.6 at 8,192 slots, 56.7
#: against 61.6 at 16,384; ``PERF.md`` §6, PR 34, has the table).
DENSE_MAX_SLOTS = 8192

_LANES = 128
#: Slots a register block: four vector registers a field.
_GROUP = 32
#: Slots a grid block: the four ``[_SLOT_BLOCK, 128]`` accumulators
#: of ``stats`` are 2 MiB of VMEM whatever the table's size.
_SLOT_BLOCK = 1024
#: Sublane rows (of 128 table rows) a grid step, and how many of them
#: the inner loop's body spells out.
_ROW_TILE = 256
_UNROLL = 16

_REDUCE = {"add": jnp.sum, "min": jnp.min, "max": jnp.max}


def fits(n_slots: int, dtype) -> bool:
    """Whether a table of ``n_slots`` accumulators of ``dtype`` takes
    the dense reduce: both are static where a fold is traced, and
    nothing else decides."""
    return n_slots <= DENSE_MAX_SLOTS and dtype in (jnp.float32, jnp.int32)


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def identity_scalar(init: float, dtype):
    """The fold identity as a Python scalar of ``dtype`` (±inf
    saturates to an integer dtype's ends); a kernel closes over no
    array, so it takes this and not ``segment.identity_for``."""
    if jnp.issubdtype(dtype, jnp.integer):
        info = np.iinfo(dtype)
        if init == float("inf"):
            return int(info.max)
        if init == float("-inf"):
            return int(info.min)
        return int(init)
    return float(init)


def _fold_kernel(field_ops, n_groups, steps, ids_ref, vals_ref, out_ref):
    """One ``(slot block, row tile)`` grid step.  ``field_ops`` is a
    static tuple of ``(op, identity, is_count)`` in the order of the
    output's leading axis.  The row-tile axis is the inner one, so
    the output block stays in VMEM while every tile folds into it."""
    dtype = out_ref.dtype

    @pl.when(pl.program_id(1) == 0)
    def _init():
        for idx, (_op, ident, _c) in enumerate(field_ops):
            out_ref[idx] = jnp.full(out_ref.shape[1:], ident, dtype)

    first = pl.program_id(0) * (n_groups * _GROUP)

    def slot_group(g, carry):
        off = pl.multiple_of(g * _GROUP, _GROUP)
        want = first + off + lax.broadcasted_iota(
            jnp.int32, (_GROUP, _LANES), 0
        )

        def fold_row(r, accs):
            ids = jnp.broadcast_to(ids_ref[pl.ds(r, 1), :], want.shape)
            vals = jnp.broadcast_to(vals_ref[pl.ds(r, 1), :], want.shape)
            hit = ids == want
            new = []
            for (op, ident, is_count), acc in zip(field_ops, accs):
                if op == "add":
                    c = jnp.ones((), dtype) if is_count else vals
                    new.append(acc + jnp.where(hit, c, jnp.zeros((), dtype)))
                elif op == "min":
                    new.append(jnp.minimum(acc, jnp.where(hit, vals, ident)))
                else:
                    new.append(jnp.maximum(acc, jnp.where(hit, vals, ident)))
            return tuple(new)

        def row_step(j, accs):
            r0 = pl.multiple_of(j * _UNROLL, _UNROLL)
            for k in range(_UNROLL):
                accs = fold_row(r0 + k, accs)
            return accs

        accs = tuple(
            out_ref[idx, pl.ds(off, _GROUP), :]
            for idx in range(len(field_ops))
        )
        accs = lax.fori_loop(0, steps, row_step, accs)
        for idx, acc in enumerate(accs):
            out_ref[idx, pl.ds(off, _GROUP), :] = acc
        return carry

    lax.fori_loop(0, n_groups, slot_group, 0)


def dense_partials(
    fields: Dict[str, Tuple[float, str]],
    n_slots: int,
    dtype,
    ids: jax.Array,
    values: jax.Array,
) -> Dict[str, jax.Array]:
    """What the rows alone fold to: for each of ``fields`` (an
    :class:`~bytewax_tpu.ops.segment.AggKind`'s) one ``[n_slots]``
    array of ``dtype``, slot ``i`` reduced over the rows whose id is
    ``i`` and the identity where no row's is.  An id outside
    ``[0, n_slots)`` meets no slot and is dropped.  Called under a
    ``jit``: shapes decide the grid."""
    # Whole register blocks, and whole grid blocks past one.
    block = _GROUP if n_slots <= _SLOT_BLOCK else _SLOT_BLOCK
    padded_slots = -(-n_slots // block) * block
    slot_block = min(padded_slots, _SLOT_BLOCK)
    # Whole unrolled steps, and whole row tiles past one.  A padding
    # row's id meets no slot.
    n = ids.shape[0]
    rows = -(-n // (_UNROLL * _LANES)) * _UNROLL
    tile = min(rows, _ROW_TILE)
    rows = -(-rows // tile) * tile
    pad = rows * _LANES - n
    ids = ids.astype(jnp.int32)
    values = values.astype(dtype)
    if pad:
        ids = jnp.concatenate([ids, jnp.full((pad,), -1, jnp.int32)])
        values = jnp.concatenate([values, jnp.zeros((pad,), dtype)])

    names = list(fields)
    field_ops = tuple(
        (fields[name][1], identity_scalar(fields[name][0], dtype), name == "count")
        for name in names
    )
    lanes = pl.pallas_call(
        functools.partial(
            _fold_kernel, field_ops, slot_block // _GROUP, tile // _UNROLL
        ),
        out_shape=jax.ShapeDtypeStruct(
            (len(names), padded_slots, _LANES), dtype
        ),
        grid=(padded_slots // slot_block, rows // tile),
        in_specs=[
            pl.BlockSpec((tile, _LANES), lambda s, t: (t, 0)),
            pl.BlockSpec((tile, _LANES), lambda s, t: (t, 0)),
        ],
        out_specs=pl.BlockSpec(
            (len(names), slot_block, _LANES), lambda s, t: (0, s, 0)
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=_interpret(),
    )(ids.reshape(rows, _LANES), values.reshape(rows, _LANES))
    return {
        name: _REDUCE[fields[name][1]](lanes[i, :n_slots], axis=-1)
        for i, name in enumerate(names)
    }
