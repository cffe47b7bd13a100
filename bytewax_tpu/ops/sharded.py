"""The sharded streaming step: keyed exchange + scatter-combine over a
device mesh.

This is the multi-chip "training step" of the framework: a micro-batch
of ``(key_id, value)`` rows, sharded over devices on the row axis, is
exchanged over ICI so each device receives the rows whose keys it
owns (``key_id % n_shards``), then folded into that device's block of
the key-sharded state table.  One compiled program per micro-batch —
no host hop, no RPC mesh — replacing the reference's
``routed_exchange`` + per-key Python callbacks
(``/root/reference/src/timely.rs:806-812``,
``src/operators.rs:767-808``).
"""

from typing import Dict

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from bytewax_tpu.ops.segment import AGG_KINDS, AggKind, identity_for
from bytewax_tpu.parallel.exchange import bucket_by_shard
from bytewax_tpu.parallel.mesh import SHARD_AXIS, shard_map

__all__ = [
    "init_sharded_fields",
    "init_sharded_scan_fields",
    "make_sharded_scan_step",
    "make_sharded_step",
]


def init_sharded_fields(
    kind: AggKind, mesh: Mesh, cap_per_shard: int, dtype=jnp.float32
) -> Dict[str, jax.Array]:
    """State table sharded over the mesh: ``n_shards * cap_per_shard``
    slots, block ``d`` living on device ``d``."""
    n_shards = mesh.shape[SHARD_AXIS]
    sharding = NamedSharding(mesh, P(SHARD_AXIS))
    return {
        name: jax.device_put(
            jnp.full(
                (n_shards * cap_per_shard,),
                identity_for(init, dtype),
                dtype=dtype,
            ),
            sharding,
        )
        for name, (init, _op) in kind.fields.items()
    }


def make_sharded_step(
    mesh: Mesh,
    kind_name: str,
    cap_per_shard: int,
    exchange_capacity: int,
    dtype=jnp.float32,
):
    """Build the jitted sharded update step.

    Returned ``step(fields, key_ids, values, valid) -> fields`` expects
    rows sharded on the leading axis over the mesh and the state
    sharded per :func:`init_sharded_fields`.  Key ownership is
    ``key_id % n_shards``; a key's slot within its owner is
    ``key_id // n_shards``, scratch slot is the block's last.

    ``exchange_capacity`` is the per-(source, destination) bucket
    size; the caller must size it to the batch's true per-bucket
    maximum (see ``engine/sharded_state.py``, which computes it
    exactly per micro-batch) — rows beyond it would be dropped.

    ``dtype`` is the accumulator dtype: float32 values ride the
    exchange bitcast to int32 (so key ids keep full precision);
    int32 values ride as-is and fold exactly.
    """
    kind = AGG_KINDS[kind_name]
    n_shards = mesh.shape[SHARD_AXIS]
    integer = jnp.issubdtype(dtype, jnp.integer)

    def body(fields, key_ids, values, valid):
        # The two halves carry a ``jax.named_scope`` each, so the
        # device trace's ``XLA Ops`` line tells them apart.
        with jax.named_scope("exchange"):
            recv_ids, recv_vals, mask = exchange(key_ids, values, valid)
        with jax.named_scope("fold"):
            return fold(fields, recv_ids, recv_vals, mask)

    def exchange(key_ids, values, valid):
        # 1. Keyed exchange over ICI: ship each row to its owner.
        # Float payloads ride bitcast to int32 (a float32 payload
        # lane would corrupt ids above 2^24).
        shard_ids = (key_ids % n_shards).astype(jnp.int32)
        if integer:
            value_bits = values.astype(jnp.int32)
        else:
            value_bits = jax.lax.bitcast_convert_type(
                values.astype(jnp.float32), jnp.int32
            )
        payload = jnp.stack(
            [key_ids.astype(jnp.int32), value_bits],
            axis=1,
        )
        buckets, counts, _dropped = bucket_by_shard(
            shard_ids, payload, valid, n_shards, exchange_capacity
        )
        got = jax.lax.all_to_all(
            buckets, SHARD_AXIS, split_axis=0, concat_axis=0, tiled=True
        )
        got_counts = jax.lax.all_to_all(
            counts, SHARD_AXIS, split_axis=0, concat_axis=0, tiled=True
        )
        mask = (
            jnp.arange(exchange_capacity)[None, :] < got_counts[:, None]
        ).reshape(-1)
        rows = got.reshape(-1, 2)
        recv_ids = rows[:, 0]
        if integer:
            recv_vals = rows[:, 1]
        else:
            recv_vals = jax.lax.bitcast_convert_type(rows[:, 1], jnp.float32)
        return recv_ids, recv_vals, mask

    def fold(fields, recv_ids, recv_vals, mask):
        # 2. Local scatter-combine into this device's state block.
        local_slot = jnp.where(
            mask, recv_ids // n_shards, cap_per_shard - 1
        )
        out = {}
        for name, (init, op_name) in kind.fields.items():
            arr = fields[name]
            ident = identity_for(init, arr.dtype)
            zero = jnp.zeros((), dtype=arr.dtype)
            if name == "count":
                one = jnp.ones((), dtype=arr.dtype)
                contrib = jnp.where(mask, one, zero)
            else:
                contrib = jnp.where(
                    mask, recv_vals.astype(arr.dtype), ident
                )
            ref = arr.at[local_slot]
            if op_name == "add":
                out[name] = ref.add(jnp.where(mask, contrib, zero))
            elif op_name == "min":
                out[name] = ref.min(contrib)
            else:
                out[name] = ref.max(contrib)
        return out

    field_specs = {name: P(SHARD_AXIS) for name in kind.fields}
    shard_fn = shard_map(
        body,
        mesh=mesh,
        in_specs=(field_specs, P(SHARD_AXIS), P(SHARD_AXIS), P(SHARD_AXIS)),
        out_specs=field_specs,
    )
    return jax.jit(shard_fn, donate_argnums=(0,))


def init_sharded_scan_fields(scan_kind, mesh: Mesh, cap_per_shard: int):
    """Scan-state table sharded over the mesh, one column per
    :class:`~bytewax_tpu.ops.scan.ScanKind` field (each with its own
    dtype and identity): ``n_shards * cap_per_shard`` slots, block
    ``d`` on device ``d``."""
    n_shards = mesh.shape[SHARD_AXIS]
    sharding = NamedSharding(mesh, P(SHARD_AXIS))
    return {
        name: jax.device_put(
            jnp.full((n_shards * cap_per_shard,), init, dtype=dtype),
            sharding,
        )
        for name, (init, dtype) in scan_kind.fields.items()
    }


def _lane_encode(col: jax.Array) -> jax.Array:
    """Encode an output column as an int32 wire lane (floats bitcast
    so the exchange can't round them; bools/ints widen/narrow)."""
    if col.dtype == jnp.bool_ or jnp.issubdtype(col.dtype, jnp.integer):
        return col.astype(jnp.int32)
    return jax.lax.bitcast_convert_type(col.astype(jnp.float32), jnp.int32)


def _lane_decode(lane: jax.Array, like: jax.Array) -> jax.Array:
    if like.dtype == jnp.bool_ or jnp.issubdtype(like.dtype, jnp.integer):
        return lane.astype(like.dtype)
    return jax.lax.bitcast_convert_type(lane, jnp.float32).astype(like.dtype)


def make_sharded_scan_step(
    mesh: Mesh,
    scan_kind,
    cap_per_shard: int,
    exchange_capacity: int,
):
    """Build the jitted sharded *scan* step: keyed exchange +
    segmented per-key scan + per-row outputs exchanged back.

    Where :func:`make_sharded_step` folds rows into state and returns
    only the state, a scan also emits one output tuple per ROW
    (``stateful_map`` semantics), so the program makes a round trip:
    rows ship to their owner shard (``key_id % n_shards``) carrying
    their source position, each shard sorts its received rows by slot
    (a stable sort, so a key's rows keep arrival order across source
    blocks) and runs the kind's segmented-scan body over its local
    state block, and the per-row outputs ride a second ``all_to_all``
    back to their source positions.

    Returned ``step(fields, key_ids, values, valid) -> (outs, fields)``
    with every array sharded on the leading axis; ``outs`` columns are
    aligned with the input rows.  ``exchange_capacity`` must be sized
    to the batch's true per-(source, destination) maximum (see
    ``engine/sharded_state.py``).  Output columns travel as 32-bit
    lanes: float64 outputs narrow to float32 and integers to int32 on
    the return trip.
    """
    n_shards = mesh.shape[SHARD_AXIS]
    cap = exchange_capacity

    def body(fields, key_ids, values, valid):
        rows = key_ids.shape[0]
        # ``exchange`` / ``fold`` scopes: see make_sharded_step.
        with jax.named_scope("exchange"):
            shard_ids = (key_ids % n_shards).astype(jnp.int32)
            vbits = jax.lax.bitcast_convert_type(
                values.astype(jnp.float32), jnp.int32
            )
            pos = jnp.arange(rows, dtype=jnp.int32)
            payload = jnp.stack(
                [key_ids.astype(jnp.int32), vbits, pos], axis=1
            )
            buckets, counts, _dropped = bucket_by_shard(
                shard_ids, payload, valid, n_shards, cap
            )
            got = jax.lax.all_to_all(
                buckets, SHARD_AXIS, split_axis=0, concat_axis=0, tiled=True
            )
            got_counts = jax.lax.all_to_all(
                counts, SHARD_AXIS, split_axis=0, concat_axis=0, tiled=True
            )
            mask = (
                jnp.arange(cap)[None, :] < got_counts[:, None]
            ).reshape(-1)
            recv = got.reshape(-1, 3)
            recv_ids = recv[:, 0]
            recv_vals = jax.lax.bitcast_convert_type(recv[:, 1], jnp.float32)
            recv_pos = recv[:, 2]

        with jax.named_scope("fold"):
            # Group by slot with ONE stable sort: received buckets are
            # ordered by source block and source order within each block,
            # so the stable sort preserves each key's global arrival
            # order.  Padding rows target the scratch slot (the block's
            # last), which sorts to the tail — the kernel's contract.
            local_slot = jnp.where(
                mask, recv_ids // n_shards, cap_per_shard - 1
            ).astype(jnp.int32)
            order = jnp.argsort(local_slot, stable=True)
            outs_s, new_fields = scan_kind.raw_run(
                fields, local_slot[order], recv_vals[order]
            )
            # Un-sort back to received order, then ship outputs home.
            outs_r = tuple(
                jnp.zeros_like(o).at[order].set(o) for o in outs_s
            )
        with jax.named_scope("exchange"):
            ret = jnp.stack(
                [*(_lane_encode(o) for o in outs_r), recv_pos], axis=1
            ).reshape(n_shards, cap, -1)
            back = jax.lax.all_to_all(
                ret, SHARD_AXIS, split_axis=0, concat_axis=0, tiled=True
            ).reshape(-1, len(outs_r) + 1)
            # This device's send counts bound each returned bucket's
            # valid prefix (bucket d of `back` holds shard d's outputs
            # for the rows we sent it, in the order we sent them).
            src_mask = (
                jnp.arange(cap)[None, :] < counts[:, None]
            ).reshape(-1)
            back_pos = jnp.where(src_mask, back[:, -1], rows)
            outs_local = []
            for j, o in enumerate(outs_r):
                buf = (
                    jnp.zeros((rows + 1,), dtype=jnp.int32)
                    .at[back_pos]
                    .set(back[:, j])
                )
                outs_local.append(_lane_decode(buf[:rows], o))
        return tuple(outs_local), new_fields

    field_specs = {name: P(SHARD_AXIS) for name in scan_kind.fields}
    shard_fn = shard_map(
        body,
        mesh=mesh,
        in_specs=(field_specs, P(SHARD_AXIS), P(SHARD_AXIS), P(SHARD_AXIS)),
        out_specs=(P(SHARD_AXIS), field_specs),
    )
    return jax.jit(shard_fn, donate_argnums=(0,))
