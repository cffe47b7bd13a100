"""Segmented per-key running scans with per-row emission.

The reference's ``stateful_map`` calls the user mapper once per item
under the GIL (``/root/reference/pysrc/bytewax/operators/__init__.py``
``stateful_map``; engine loop ``src/operators.rs:441-520``).  For
numeric state shapes the same computation is one device program per
micro-batch: the host groups rows by key into contiguous segments and
a segmented scan over the state monoid yields every row's running
state in O(log n) depth instead of n sequential Python calls.

The device contract is :class:`ScanKind` — a monoid (``lift`` /
``merge`` / ``emit`` as jax functions over per-field slot-table
columns).  Any kind expressed against it runs through ONE generic
kernel (:func:`generic_scan_kernel`, a flagged
``jax.lax.associative_scan``); a kind may override :meth:`ScanKind.run`
with a specialized kernel when a better formulation exists, as the
z-score kind does with the pivot-shifted prefix-sum program
(:func:`zscore_scan`).  Registering a new kind requires *no* engine
changes — the driver, snapshots, and native emission are all generic
over the kind's declared fields and outputs.
"""

import functools
import math
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "ScanKind",
    "WelfordZScore",
    "Ema",
    "JaxUdfScan",
    "RunningExtrema",
    "generic_scan_body",
    "generic_scan_kernel",
    "welford_merge",
    "zscore_scan",
    "zscore_scan_body",
    "WELFORD_FIELDS",
]

#: name -> (init, dtype) of the per-key Welford state row.
WELFORD_FIELDS = {
    "count": (0, jnp.int32),
    "mean": (0.0, jnp.float32),
    "m2": (0.0, jnp.float32),
}


class ScanKind:
    """Device contract for a ``stateful_map`` lowering.

    A kind is a *monoid over per-key state rows* plus a per-row
    emission:

    - :attr:`fields` — ordered ``{name: (identity, dtype)}`` of the
      slot-table columns.  The field order IS the host snapshot tuple
      order: the host-tier mapper's state tuple and the device tier's
      per-slot row must be the same tuple, so recovery snapshots
      interchange between tiers (CLAUDE.md contract).
    - :meth:`lift` — one row's state contribution (jax, elementwise).
    - :meth:`merge` — associative combine of two state tuples (jax);
      ``merge(s, identity) == s`` must hold.
    - :meth:`emit` — per-row device outputs given the row's
      *pre-update* state, *post-update* state, and value (jax).
    - :meth:`post` — optional host-side finisher over the kernel's
      numpy outputs (e.g. a float64 threshold compare).

    Subclasses carry their parameters (threshold, alpha, ...) as
    instance attributes; the generic kernel closes over them at trace
    time.  See :class:`Ema` for a minimal example — a kind defined in
    a user module (or a test file) lowers exactly like the built-ins.
    """

    #: kind name (diagnostics / reprs).
    name: str = "?"
    #: ordered {field: (identity, dtype)}; also the snapshot order.
    fields: Dict[str, Tuple[Any, Any]] = {}

    def lift(self, values: jax.Array) -> Tuple[jax.Array, ...]:
        raise NotImplementedError

    def merge(self, a: Tuple, b: Tuple) -> Tuple:
        raise NotImplementedError

    def emit(self, pre: Tuple, post: Tuple, values: jax.Array) -> Tuple:
        raise NotImplementedError

    def post(self, outs: Tuple[np.ndarray, ...]) -> Tuple[np.ndarray, ...]:
        """Host-side finisher over the kernel outputs (identity by
        default)."""
        return outs

    def raw_run(
        self,
        fields: Dict[str, jax.Array],
        slots: jax.Array,
        values: jax.Array,
    ) -> Tuple[Tuple[jax.Array, ...], Dict[str, jax.Array]]:
        """The kernel body, uncompiled — callable inside an enclosing
        jit/shard_map (the sharded tier inlines it per shard).
        Override to supply a specialized kernel."""
        body = self.__dict__.get("_raw_body")
        if body is None:
            body = generic_scan_body(self)
            self.__dict__["_raw_body"] = body
        return body(fields, slots, values)

    def run(
        self,
        fields: Dict[str, jax.Array],
        slots: jax.Array,
        values: jax.Array,
    ) -> Tuple[Tuple[jax.Array, ...], Dict[str, jax.Array]]:
        """Execute one micro-batch; compiled (once per kind instance)
        with the state donated in place."""
        kernel = self.__dict__.get("_kernel")
        if kernel is None:
            kernel = functools.partial(jax.jit, donate_argnums=(0,))(
                self.raw_run
            )
            self.__dict__["_kernel"] = kernel
        return kernel(fields, slots, values)

    # -- snapshot plumbing (generic over the field table) -----------------

    def snapshot_of(self, row: Tuple) -> Tuple:
        """Host-format state tuple from one slot row (device scalars
        → exact Python bools / ints / floats, in field order).  The
        bool branch must come first: ``jnp.bool_`` is not an integer
        subdtype, so without it a bool field snapshots as a float and
        a host-tier resume sees ``1.0`` where its mapper kept
        ``True`` — breaking the cross-tier interchange contract for
        bool state."""
        out = []
        for (name, (_i, dtype)), v in zip(self.fields.items(), row):
            if jnp.issubdtype(dtype, jnp.bool_):
                out.append(bool(v))
            elif jnp.issubdtype(dtype, jnp.integer):
                out.append(int(v))
            else:
                out.append(float(v))
        return tuple(out)

    def __repr__(self) -> str:
        return f"ScanKind({self.name!r})"


def generic_scan_body(kind: ScanKind) -> Callable:
    """Build the one generic device program for a kind: a flagged
    segmented ``associative_scan`` over the kind's state monoid.

    ``slots`` must be grouped (all rows of a key contiguous); padding
    rows carry the scratch slot ``capacity - 1`` and must form the
    trailing segment.  Returns the kind's per-row outputs and the
    updated slot tables; segment tails write ``table carry ⊕
    inclusive in-batch state`` back, every other row is redirected to
    the scratch slot.  Uncompiled — wrap in jit (``ScanKind.run``) or
    inline per shard (``ops/sharded.py``).
    """
    names = tuple(kind.fields)
    inits = tuple(init for init, _ in kind.fields.values())

    def run(fields, slots, values):
        capacity = fields[names[0]].shape[0]
        seg_start = jnp.concatenate(
            [jnp.ones((1,), dtype=bool), slots[1:] != slots[:-1]]
        )
        lifted = kind.lift(values)

        def comb(a, b):
            fa, sa = a[0], a[1:]
            fb, sb = b[0], b[1:]
            merged = kind.merge(sa, sb)
            # Segment heads restart the fold: keep b's own state.
            kept = tuple(
                jnp.where(fb, x, m) for x, m in zip(sb, merged)
            )
            return (fa | fb, *kept)

        incl = jax.lax.associative_scan(comb, (seg_start, *lifted))[1:]

        def shifted(x, ident):
            prev = jnp.concatenate(
                [jnp.full((1,), ident, x.dtype), x[:-1]]
            )
            return jnp.where(seg_start, jnp.asarray(ident, x.dtype), prev)

        excl = tuple(shifted(x, i) for x, i in zip(incl, inits))
        carry = tuple(fields[nm][slots] for nm in names)
        pre = kind.merge(carry, excl)
        post = kind.merge(carry, incl)
        outs = kind.emit(pre, post, values)
        seg_end = jnp.concatenate(
            [slots[1:] != slots[:-1], jnp.ones((1,), dtype=bool)]
        )
        dest = jnp.where(seg_end, slots, capacity - 1)
        new_fields = {
            nm: fields[nm].at[dest].set(p.astype(fields[nm].dtype))
            for nm, p in zip(names, post)
        }
        return outs, new_fields

    return run


def generic_scan_kernel(kind: ScanKind) -> Callable:
    """Compiled form of :func:`generic_scan_body` (state donated)."""
    return functools.partial(jax.jit, donate_argnums=(0,))(
        generic_scan_body(kind)
    )


def welford_merge(a, b):
    """Chan's parallel Welford merge: combine two ``(count, mean, m2)``
    summaries of disjoint samples.  Associative, identity (0, 0, 0)."""
    na, ma, m2a = a
    nb, mb, m2b = b
    n = na + nb
    f = ma.dtype
    nf = n.astype(f)
    naf = na.astype(f)
    nbf = nb.astype(f)
    safe = jnp.where(n > 0, nf, 1.0)
    delta = mb - ma
    mean = ma + delta * nbf / safe
    m2 = m2a + m2b + delta * delta * naf * nbf / safe
    return n, mean, m2


def zscore_scan_body(
    state: Dict[str, jax.Array],
    slots: jax.Array,
    values: jax.Array,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """One micro-batch of the per-key rolling z-score (the
    :class:`WelfordZScore` kind's specialized kernel; uncompiled —
    see :data:`zscore_scan`).

    ``slots`` must be grouped (all rows of a key contiguous); padding
    rows carry the scratch slot ``capacity - 1`` and must form the
    trailing segment.  Returns per-row ``z`` — computed against each
    row's pre-update state, matching the host mapper — and the
    updated slot tables (donated in place in HBM).  The threshold
    compare happens host-side on the returned column (one fewer
    device transfer).

    The per-row running Welford state is computed from segmented
    prefix sums of *pivot-shifted* values (the segment head's value is
    the pivot, so the ``sumsq - sum²/n`` form stays well-conditioned),
    then merged with each key's persistent table state via Chan's
    parallel Welford combine.  The prefix sums restart at every
    segment head (a flagged scan over plain sums): one running total
    over the whole batch, differenced at the heads, leaves each
    segment the float32 precision of the batch's total, not of its
    own rows, and at a few hundred thousand rows that is none.
    Counts ride int32
    end-to-end (an fp32 count freezes at 2^24 rows; the int path keeps
    parity with the host tier's exact-int Welford state for arbitrary
    stream lengths), cast to float only for the mean/m2 divisions.
    """
    count_t, mean_t, m2_t = state["count"], state["mean"], state["m2"]
    capacity = count_t.shape[0]
    n = slots.shape[0]
    f = mean_t.dtype
    vals = values.astype(f)

    seg_start = jnp.concatenate(
        [jnp.ones((1,), dtype=bool), slots[1:] != slots[:-1]]
    )
    idx = jnp.arange(n, dtype=jnp.int32)
    # Broadcast each segment head's index to its rows: arange is
    # monotone, so a running max of head indices does it.
    head_idx = jax.lax.cummax(jnp.where(seg_start, idx, 0))
    pivot = vals[head_idx]
    d = vals - pivot

    def restart_at_heads(a, b):
        (fa, sa, qa), (fb, sb, qb) = a, b
        return (
            fa | fb,
            jnp.where(fb, sb, sa + sb),
            jnp.where(fb, qb, qa + qb),
        )

    # Inclusive in-segment sums of d and d^2, then the same one row
    # earlier (zero at the heads) for the pre-update state.
    _f, si, qi = jax.lax.associative_scan(
        restart_at_heads, (seg_start, d, d * d)
    )

    def before_row(incl):
        prev = jnp.concatenate([jnp.zeros((1,), dtype=f), incl[:-1]])
        return jnp.where(seg_start, 0.0, prev)

    ps, pq = before_row(si), before_row(qi)
    # Prior rows of this key in the batch — exact int32 arithmetic.
    pn_i = idx - head_idx

    def around_pivot(cnt_f, s, q):
        """(mean, m2) of a shifted prefix sum triple."""
        safe = jnp.maximum(cnt_f, 1.0)
        return pivot + s / safe, q - s * s / safe

    def chan_merge(n0_i, mean0, m20, nb_i, mean_b, m2_b):
        nt_i = n0_i + nb_i
        n0f = n0_i.astype(f)
        nbf = nb_i.astype(f)
        safe = jnp.maximum(nt_i.astype(f), 1.0)
        delta = mean_b - mean0
        mean = mean0 + delta * nbf / safe
        m2 = m20 + m2_b + delta * delta * n0f * nbf / safe
        return nt_i, mean, m2

    n0_i = count_t[slots]
    mean0 = mean_t[slots]
    m20 = m2_t[slots]

    # Pre-update state per row = table carry ⊕ in-batch prefix.
    mean_b, m2_b = around_pivot(pn_i.astype(f), ps, pq)
    p_n, p_mean, p_m2 = chan_merge(n0_i, mean0, m20, pn_i, mean_b, m2_b)

    have_var = (p_n >= 2) & (p_m2 > 0)
    denom = jnp.sqrt(p_m2 / jnp.maximum(p_n.astype(f) - 1, 1.0))
    z = jnp.where(have_var, (vals - p_mean) / denom, 0.0)

    # Segment tails write table carry ⊕ inclusive in-batch state back;
    # every other row is redirected to the scratch slot (arbitrary
    # values there are fine — padding already targets it).
    mean_i, m2_i = around_pivot(pn_i.astype(f) + 1, si, qi)
    s_n, s_mean, s_m2 = chan_merge(n0_i, mean0, m20, pn_i + 1, mean_i, m2_i)
    seg_end = jnp.concatenate(
        [slots[1:] != slots[:-1], jnp.ones((1,), dtype=bool)]
    )
    dest = jnp.where(seg_end, slots, capacity - 1)
    new_state = {
        "count": count_t.at[dest].set(s_n.astype(count_t.dtype)),
        "mean": mean_t.at[dest].set(s_mean),
        "m2": m2_t.at[dest].set(s_m2),
    }
    return (z,), new_state


#: Compiled z-score kernel (state donated), shared across states.
zscore_scan = functools.partial(jax.jit, donate_argnums=(0,))(
    zscore_scan_body
)


class WelfordZScore(ScanKind):
    """Per-key rolling z-score over Welford ``(count, mean, m2)``
    state; emits ``(value, z, abs(z) > threshold)`` per row, z scored
    against the pre-update state.  Uses the specialized pivot-shifted
    kernel (:func:`zscore_scan`) rather than the generic program."""

    name = "zscore"
    fields = WELFORD_FIELDS

    def __init__(self, threshold: float):
        self.threshold = float(threshold)

    def lift(self, values):
        n = values.shape[0]
        return (
            jnp.ones((n,), dtype=jnp.int32),
            values,
            jnp.zeros((n,), dtype=values.dtype),
        )

    def merge(self, a, b):
        return welford_merge(a, b)

    def emit(self, pre, post, values):
        p_n, p_mean, p_m2 = pre
        f = p_mean.dtype
        have_var = (p_n >= 2) & (p_m2 > 0)
        denom = jnp.sqrt(p_m2 / jnp.maximum(p_n.astype(f) - 1, 1.0))
        z = jnp.where(have_var, (values - p_mean) / denom, 0.0)
        return (z,)

    def raw_run(self, fields, slots, values):
        return zscore_scan_body(fields, slots, values)

    def run(self, fields, slots, values):
        return zscore_scan(fields, slots, values)

    def post(self, outs):
        (z,) = outs
        # The flag compare runs in float64 so borderline rows classify
        # identically to the host tier (which compares in f64).
        return z, np.abs(z.astype(np.float64)) > self.threshold


class Ema(ScanKind):
    """Per-key debiased exponential moving average.

    State is ``(count, s)`` with ``s`` the biased accumulator
    ``s ← (1-alpha)·s + alpha·v``; each row emits ``(value, ema)``
    with the Adam-style debiased ``ema = s / (1 - (1-alpha)^count)``
    *after* folding the row in — so the first value of a key emits
    itself.  The merge ``(n₁+n₂, s₁·(1-alpha)^{n₂} + s₂)`` is
    associative, which is what lets the fold run as one segmented
    scan per micro-batch.
    """

    name = "ema"
    fields = {
        "count": (0, jnp.int32),
        "s": (0.0, jnp.float32),
    }

    def __init__(self, alpha: float):
        if not 0.0 < alpha <= 1.0:
            msg = f"ema alpha must be in (0, 1], got {alpha}"
            raise ValueError(msg)
        self.alpha = float(alpha)
        # (1-alpha)^n and 1-(1-alpha)^n go through exp/expm1 of
        # n·log1p(-alpha) (the log in f64 at trace time): the naive
        # power rounds 1-alpha to 1.0 in f32 for alpha < ~6e-8, which
        # freezes the decay and collapses the debias factor to 0.
        self._log_q = (
            float("-inf") if alpha == 1.0 else math.log1p(-alpha)
        )

    def lift(self, values):
        n = values.shape[0]
        return (
            jnp.ones((n,), dtype=jnp.int32),
            self.alpha * values,
        )

    def merge(self, a, b):
        n1, s1 = a
        n2, s2 = b
        f = s1.dtype
        # Guard n2 == 0: 0 · -inf is NaN for alpha == 1.
        decay = jnp.where(
            n2 > 0, jnp.exp(n2.astype(f) * self._log_q), 1.0
        )
        return n1 + n2, s1 * decay + s2

    def emit(self, pre, post, values):
        n, s = post
        f = s.dtype
        bias = -jnp.expm1(n.astype(f) * self._log_q)
        return (s / jnp.maximum(bias, jnp.finfo(f).tiny),)


class RunningExtrema(ScanKind):
    """Per-key running min/max: state ``(mn, mx)``, each row emits
    ``(value, min_so_far, max_so_far)`` including the row itself."""

    name = "extrema"
    fields = {
        "mn": (float("inf"), jnp.float32),
        "mx": (float("-inf"), jnp.float32),
    }

    def lift(self, values):
        return values, values

    def merge(self, a, b):
        return jnp.minimum(a[0], b[0]), jnp.maximum(a[1], b[1])

    def emit(self, pre, post, values):
        return post


class JaxUdfScan(ScanKind):
    """ANY jax-traceable per-key mapper at device speed — the
    traceable-UDF tier for ``stateful_map``.

    Where the monoid kinds above parallelize their fold (O(log n)
    segmented scan), an arbitrary mapper has no associative structure
    to exploit: this kind runs the rows through ONE compiled
    ``lax.scan`` instead — still one device program per micro-batch
    with per-key state in slot tables (no per-item Python, no GIL),
    just sequential in the scan dimension.  On a mesh it shards like
    every other kind (each shard scans only its own keys' rows), so
    devices divide the sequential length.

    ``fn(state_tuple, value) -> (state_tuple, outs_tuple)`` — scalar
    jax ops over a tuple of scalar state fields; ``init`` gives each
    field's initial value (and, by Python type, its dtype: float →
    f32, int → int32, bool → bool).  The emitted item per row is
    ``(value, *outs)``.  Snapshots are the plain state tuple, in
    field order, interchangeable with the host tier.
    """

    name = "jax_udf"

    def __init__(self, fn: Callable, init: Tuple):
        self.fn = fn
        self.init = tuple(init)

        def dtype_of(v):
            if isinstance(v, bool):
                return jnp.bool_
            if isinstance(v, int):
                return jnp.int32
            return jnp.float32

        self.fields = {
            f"s{i}": (v, dtype_of(v)) for i, v in enumerate(self.init)
        }

    def raw_run(self, fields, slots, values):
        names = tuple(self.fields)

        def step(tables, row):
            slot, v = row
            state = tuple(t[slot] for t in tables)
            new_state, outs = self.fn(state, v)
            if len(new_state) != len(tables):
                msg = (
                    f"jax_stateful_map fn returned {len(new_state)} "
                    f"state fields; init declared {len(tables)}"
                )
                raise TypeError(msg)
            tables = tuple(
                t.at[slot].set(jnp.asarray(ns).astype(t.dtype))
                for t, ns in zip(tables, new_state)
            )
            if not isinstance(outs, tuple):
                outs = (outs,)
            return tables, tuple(jnp.asarray(o) for o in outs)

        tables0 = tuple(fields[nm] for nm in names)
        tables_n, emits = jax.lax.scan(step, tables0, (slots, values))
        return tuple(emits), dict(zip(names, tables_n))
