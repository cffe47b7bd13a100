"""Device-tier batched model scoring (``op.infer`` lowering).

The user supplies a jax ``apply_fn(params, x)`` plus a params pytree;
the engine runs it as a bucket-padded, jit-compiled forward pass over
each micro-batch's feature rows — through the same dispatch pipeline
(:mod:`bytewax_tpu.engine.pipeline`), pad ladder
(:func:`bytewax_tpu.engine.batching.pad_len`), and persistent compile
cache every other device-tier step uses.  Scoring is stateless per
row, so unlike the keyed aggregation/scan tiers there is no slot
table: the ONE piece of state is the params pytree itself, treated as
broadcast state:

* snapshot-covered — the params (plus generation/digest bookkeeping)
  round-trip through the recovery store under the single reserved key
  :data:`PARAMS_KEY`, in a host-format dict interchangeable between
  the device and host tiers (CLAUDE.md cross-tier recovery contract);
* demotable — repeated :class:`~bytewax_tpu.errors.DeviceFault` drops
  the step to :class:`HostInferState`, a numpy apply over the same
  snapshot (``demotion_snapshots`` drains exactly the params row);
* hot-swappable — a pending update installs at an agreed epoch close
  (driver-side; see ``_Driver._apply_params_swap``), bumping the
  generation and digest recorded here.

Params shapes/dtypes are pinned at construction: a swap must match
the current tree structure and leaf shapes (leaves are cast to the
incumbent dtypes), so the jitted apply never recompiles on swap — the
new leaves slot into the existing traced signature.
"""

import hashlib
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from bytewax_tpu.engine import flight as _flight
from bytewax_tpu.engine.batching import pad_len
from bytewax_tpu.engine.xla import NonNumericValues

__all__ = [
    "PARAMS_KEY",
    "InferAccelSpec",
    "DeviceInferState",
    "HostInferState",
    "normalize_params",
    "params_digest",
]

#: The one broadcast-state snapshot key an infer step writes.  A
#: reserved name (user keys flow through infer untouched, but never
#: into its snapshots) so resume can read it route-agnostically.
PARAMS_KEY = "_params"


def _tree_map(fn: Callable[[Any], Any], tree: Any) -> Any:
    """Structure-preserving map over dict/list/tuple pytrees.  Pure
    Python (no jax import) so the host tier works on a machine whose
    accelerator just faulted."""
    if isinstance(tree, dict):
        return {k: _tree_map(fn, v) for k, v in tree.items()}
    if isinstance(tree, (list, tuple)):
        return type(tree)(_tree_map(fn, v) for v in tree)
    return fn(tree)


def _tree_leaves(tree: Any, out: Optional[List[Any]] = None) -> List[Any]:
    if out is None:
        out = []
    if isinstance(tree, dict):
        for k in sorted(tree):
            _tree_leaves(tree[k], out)
    elif isinstance(tree, (list, tuple)):
        for v in tree:
            _tree_leaves(v, out)
    else:
        out.append(tree)
    return out


def _treedef(tree: Any) -> Any:
    """Hashable structural summary (structure + leaf dtype/shape)."""
    if isinstance(tree, dict):
        return ("dict", tuple((k, _treedef(tree[k])) for k in sorted(tree)))
    if isinstance(tree, (list, tuple)):
        return (type(tree).__name__, tuple(_treedef(v) for v in tree))
    a = np.asarray(tree)
    return ("leaf", str(a.dtype), a.shape)


def _cast_like(old: Any, new: Any) -> Any:
    """Cast ``new``'s leaves to ``old``'s dtypes; raise ``ValueError``
    on any structure or leaf-shape mismatch (the swap-compatibility
    check — shapes are part of the jitted apply's traced signature)."""
    if isinstance(old, dict):
        if not isinstance(new, dict) or set(old) != set(new):
            msg = f"params tree mismatch: {sorted(old)} vs new"
            raise ValueError(msg)
        return {k: _cast_like(old[k], new[k]) for k in old}
    if isinstance(old, (list, tuple)):
        if not isinstance(new, (list, tuple)) or len(new) != len(old):
            msg = "params tree mismatch: sequence arity differs"
            raise ValueError(msg)
        return type(old)(_cast_like(o, n) for o, n in zip(old, new))
    o = np.asarray(old)
    n = np.asarray(new)
    if o.shape != n.shape:
        msg = f"params leaf shape mismatch: {n.shape} vs {o.shape}"
        raise ValueError(msg)
    return np.asarray(n, dtype=o.dtype)


def normalize_params(params: Any) -> Any:
    """Materialize every leaf as a host numpy array (snapshot form)."""
    return _tree_map(np.asarray, params)


def params_digest(params: Any) -> str:
    """Content digest of a params pytree: structure + leaf bytes.
    Deterministic across processes, so the cluster-wide swap agreement
    can compare digests instead of shipping params over the mesh."""
    h = hashlib.blake2b(digest_size=8)
    h.update(repr(_treedef(params)).encode())
    for leaf in _tree_leaves(params):
        h.update(np.ascontiguousarray(np.asarray(leaf)).tobytes())
    return h.hexdigest()


def extract_features(items: Any) -> Tuple[List[str], np.ndarray]:
    """Keys + a float32 ``[N, F]`` feature matrix from one delivery.

    Accepts a columnar :class:`~bytewax_tpu.engine.arrays.ArrayBatch`
    (the ``value`` column is one feature) or an itemized list of
    ``(key, value)`` rows where ``value`` is a numeric scalar or a
    fixed-width tuple/list of numerics.  Raises
    :class:`~bytewax_tpu.engine.xla.NonNumericValues` otherwise — an
    infer step REQUIRES numeric features, there is no host-logic
    fallback for arbitrary objects.
    """
    from bytewax_tpu.engine.arrays import ArrayBatch
    from bytewax_tpu.engine.scan_accel import _batch_keys

    if isinstance(items, ArrayBatch):
        keys = [str(k) for k in _batch_keys(items).tolist()]
        values = items._scaled_values()
        if values.dtype == object or values.dtype.kind in "USb":
            msg = "op.infer requires numeric feature values"
            raise NonNumericValues(msg)
        feats = np.asarray(values, dtype=np.float32).reshape(len(keys), -1)
        return keys, feats
    keys = []
    rows = []
    width = None
    for kv in items:
        try:
            key, value = kv
        except (TypeError, ValueError) as ex:
            msg = "op.infer requires (key, value) 2-tuples from upstream"
            raise NonNumericValues(msg) from ex
        row = (
            list(value) if isinstance(value, (tuple, list)) else [value]
        )
        if width is None:
            width = len(row)
        elif len(row) != width:
            msg = (
                "op.infer requires fixed-width feature rows; got "
                f"widths {width} and {len(row)}"
            )
            raise NonNumericValues(msg)
        keys.append(str(key))
        rows.append(row)
    try:
        feats = np.asarray(rows, dtype=np.float32)
    except (TypeError, ValueError) as ex:
        msg = "op.infer requires numeric feature values"
        raise NonNumericValues(msg) from ex
    if feats.ndim == 1:
        feats = feats.reshape(len(keys), -1)
    return keys, feats


def _out_columns(out: Any) -> Tuple[Any, ...]:
    """Normalize an apply output into per-row columns: a 1-d array is
    one column, a 2-d ``[N, K]`` array is K columns, a tuple/list is
    taken column-wise."""
    if isinstance(out, (tuple, list)):
        return tuple(out)
    if getattr(out, "ndim", 1) == 2:
        return tuple(out[:, j] for j in range(out.shape[1]))
    return (out,)


def assemble_items(
    keys: List[str], cols: Tuple[np.ndarray, ...]
) -> List[Tuple[str, Any]]:
    """Zip scored columns back into ``(key, out)`` items, in the
    incoming row order (scoring is stateless: no regrouping).  One
    output column emits bare scalars; several emit tuples."""
    if len(cols) == 1:
        return list(zip(keys, cols[0].tolist()))
    return list(zip(keys, zip(*(c.tolist() for c in cols))))


class _ParamsHolder:
    """Shared broadcast-params bookkeeping for both tiers: the host
    snapshot form, the generation counter, the content digest, and
    the epoch the last swap landed at."""

    def __init__(self, params: Any):
        self._host = normalize_params(params)
        self.generation = 0
        self.digest = params_digest(self._host)
        self.swap_epoch = 0

    def snapshot_state(self) -> Dict[str, Any]:
        """Host-format broadcast-state snapshot — the one row an
        infer step writes, interchangeable between tiers."""
        return {
            "generation": self.generation,
            "digest": self.digest,
            "swap_epoch": self.swap_epoch,
            "params": self._host,
        }

    def _load_snapshot(self, snap: Dict[str, Any]) -> None:
        self._host = normalize_params(snap["params"])
        self.generation = int(snap["generation"])
        self.digest = str(snap["digest"])
        self.swap_epoch = int(snap["swap_epoch"])

    def _swap_host(self, params: Any, digest: str, epoch: int) -> Any:
        """Validate + cast an incoming params tree against the
        incumbent; returns the cast tree or ``None`` on mismatch (the
        caller skips the swap deterministically — every process sees
        the same trees, so every process skips together)."""
        try:
            cast = _cast_like(self._host, normalize_params(params))
        except ValueError:
            return None
        self._host = cast
        self.generation += 1
        self.digest = digest
        self.swap_epoch = epoch
        return cast


class InferAccelSpec:
    """Annotation on a core ``stateful_batch``: lower the enclosing
    ``infer`` step to a device-tier batched forward pass."""

    def __init__(
        self,
        apply_fn: Callable[[Any, Any], Any],
        params: Any,
        host_apply: Optional[Callable[[Any, np.ndarray], Any]] = None,
    ):
        if not callable(apply_fn):
            msg = f"InferAccelSpec takes a callable apply_fn; got {apply_fn!r}"
            raise TypeError(msg)
        self.apply_fn = apply_fn
        self.params = normalize_params(params)
        self.host_apply = host_apply

    def make_state(self) -> "DeviceInferState":
        return DeviceInferState(self)

    def make_host_state(
        self, snap: Optional[Dict[str, Any]] = None
    ) -> "HostInferState":
        return HostInferState(self, snap)

    def __repr__(self) -> str:
        return f"InferAccelSpec({self.apply_fn!r})"


class DeviceInferState(_ParamsHolder):
    """Device-resident broadcast params + the jitted forward pass for
    one lowered ``infer`` step.

    ``score_rows`` pads each feature matrix to the power-of-two
    bucket ladder so XLA compiles O(log n) shapes per params
    signature; params ride as a traced argument, so a same-shape swap
    is a compile-cache hit, not a recompile.
    """

    def __init__(self, spec: InferAccelSpec):
        import jax

        super().__init__(spec.params)
        self.spec = spec
        self._jax = jax
        self._params = _tree_map(jax.device_put, self._host)
        self._apply = jax.jit(spec.apply_fn)

    # -- scoring -----------------------------------------------------------

    def score_rows(self, feats: np.ndarray) -> Tuple[np.ndarray, ...]:
        """Jit-applied forward pass over ``[N, F]`` float32 rows;
        returns host-numpy output columns trimmed back to N."""
        n = len(feats)
        padded = pad_len(n)
        with _flight.span("h2d", rows=padded):
            feats_p = np.zeros(
                (padded,) + feats.shape[1:], dtype=np.float32
            )
            feats_p[:n] = feats
            _flight.note_transfer("h2d", feats_p.nbytes)
            feats_d = self._jax.device_put(feats_p)
        with _flight.span("dispatch"):
            out = self._apply(self._params, feats_d)
        with _flight.span("fetch", rows=padded):
            host = tuple(
                np.asarray(col)[:n] for col in _out_columns(out)
            )
            _flight.note_transfer(
                "d2h", sum(col.nbytes for col in host)
            )
        return host

    # -- broadcast-state lifecycle -----------------------------------------

    def install(self, params: Any, digest: str, epoch: int) -> bool:
        """Hot-swap the broadcast params (epoch-close only — the
        driver's ``install_params`` drain path is the sole caller)."""
        cast = self._swap_host(params, digest, epoch)
        if cast is None:
            return False
        self._params = _tree_map(self._jax.device_put, cast)
        return True

    def load_state(self, snap: Dict[str, Any]) -> None:
        """Resume-path restore: adopt a stored snapshot wholesale
        (exact params generation, not just the values)."""
        self._load_snapshot(snap)
        self._params = _tree_map(self._jax.device_put, self._host)

    def snapshots_for(
        self, keys: List[str]
    ) -> List[Tuple[str, Any]]:
        return [
            (k, self.snapshot_state() if k == PARAMS_KEY else None)
            for k in keys
        ]

    def demotion_snapshots(self) -> List[Tuple[str, Any]]:
        """Full-state drain for device→host demotion: broadcast
        params are the entire state, one row."""
        return [(PARAMS_KEY, self.snapshot_state())]

    def flush(self) -> None:
        """Block until the resident params have materialized (scoring
        results are consumed inside their own lane task)."""
        self._jax.block_until_ready(_tree_leaves(self._params))


class HostInferState(_ParamsHolder):
    """Host-tier numpy apply over the same broadcast-state snapshot —
    the demotion target, and the whole tier when the accelerator is
    off (``BYTEWAX_TPU_ACCEL=0`` / ``BYTEWAX_TPU_INFER_DEVICE=0``).

    Scores through the user's ``host_apply`` numpy oracle when given;
    otherwise falls back to calling ``apply_fn`` eagerly on host
    arrays (fine for jnp-only fns on a healthy backend, which is the
    accel-off case; a real device fault wants ``host_apply``).
    """

    def __init__(
        self, spec: InferAccelSpec, snap: Optional[Dict[str, Any]] = None
    ):
        super().__init__(spec.params)
        self.spec = spec
        if snap is not None:
            self._load_snapshot(snap)

    def score_rows(self, feats: np.ndarray) -> Tuple[np.ndarray, ...]:
        feats = np.asarray(feats, dtype=np.float32)
        apply = self.spec.host_apply or self.spec.apply_fn
        out = apply(self._host, feats)
        return tuple(np.asarray(col) for col in _out_columns(out))

    def install(self, params: Any, digest: str, epoch: int) -> bool:
        return self._swap_host(params, digest, epoch) is not None

    def load_state(self, snap: Dict[str, Any]) -> None:
        self._load_snapshot(snap)
