"""The device programs of the windowed product join, and the row store
they work on.

A join holds every row of every side until its window closes, then
writes each combination of one row a side once (``join_window`` with
``insert_mode="product"``, ``emit_mode="final"``; the tier is
``engine/window_accel.py`` :class:`DeviceJoinState`).  The rows live
in :class:`RowStore`, an arena on the device: a row is two int32
words (a 64-bit carrier: an integer, or the bits of a float64), and
the rows of one (key, window, side) slot lie in one region of it.
Which region a slot holds is the host's to know (three numbers a
slot), so a delivery's rows are written straight to their places and
a close finds its slots' rows with no search: :func:`join_expand`
turns a close's slots into its output rows on the device and reads
back only their values.  An output row finds its window by one prefix
sum over the windows' ends (no search a row) and reads that window's
numbers with one gather from a small table.

Regions grow by doubling (a slot that gains rows past its room is
copied to a region twice its size), closed slots leave their regions
dead, and a full arena is compacted (the live regions copied to the
front, in order) or grown: counter ``join_store_moved`` counts the
rows these copies move, ``join_store_rows`` the rows held.
"""

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bytewax_tpu.engine import flight as _flight
from bytewax_tpu.engine.batching import pad_len

__all__ = ["OUTPUT_LADDER", "RowStore", "join_counts", "join_expand"]

#: The smallest arena, in rows (the last row is scratch); it grows by
#: four times.
_MIN_ROWS = 1 << 10

#: Padded lengths of a close's chunk of output rows (and of the
#: windows it spans, at most as many): few, so that few programs are
#: compiled.
OUTPUT_LADDER = (1 << 8, 1 << 12, 1 << 16)


@functools.partial(jax.jit, donate_argnums=(0,))
def join_store_write(words: jax.Array, pos: jax.Array, rows: jax.Array) -> jax.Array:
    """Rows ``[2, n]`` written at arena positions ``pos`` (padding
    writes the scratch row)."""
    return words.at[:, pos].set(rows)


@functools.partial(jax.jit, donate_argnums=(0,))
def join_store_move(words: jax.Array, src: jax.Array, dst: jax.Array) -> jax.Array:
    """The rows at ``src`` copied to ``dst`` (a region that grows)."""
    return words.at[:, dst].set(words[:, src])


@functools.partial(jax.jit, static_argnames=("rows",))
def join_store_compact(
    words: jax.Array, src: jax.Array, dst: jax.Array, rows: int
) -> jax.Array:
    """A new arena of ``rows`` rows holding the rows at ``src`` at
    ``dst``."""
    return jnp.zeros((2, rows), dtype=words.dtype).at[:, dst].set(words[:, src])


@jax.jit
def join_read(words: jax.Array, pos: jax.Array) -> jax.Array:
    """The rows at ``pos``, ``[2, n]``."""
    return words[:, pos]


@jax.jit
def join_counts(counts: jax.Array, slots: jax.Array) -> jax.Array:
    """The slot table's count of each of ``slots`` (``[sides,
    windows]``; -1 where a side has no row: 0), as int32.  A program
    of its own, so that the expansion's depend on the arena's size
    alone and not on the table's as well."""
    return jnp.where(slots >= 0, counts[jnp.maximum(slots, 0)].astype(jnp.int32), 0)


@functools.partial(jax.jit, static_argnames=("rows", "wide"))
def join_expand(
    words: jax.Array,
    count: jax.Array,
    starts: jax.Array,
    ends: jax.Array,
    rows: int,
    wide: Tuple[int, ...],
) -> Tuple[jax.Array, jax.Array]:
    """A chunk of a close's output rows, on the device.

    ``count`` and ``starts`` are ``[sides, windows]``: the rows of each
    closing window's slot a side (:func:`join_counts`) and where that
    slot's region begins.  A window's output size is the product of
    ``max(count, 1)`` over its sides; ``ends`` is the inclusive prefix
    sum of the sizes, counted from the chunk's first row (so the first
    window may begin before it), and the padding windows repeat the
    last end.  Output row ``j`` falls in the window whose end first
    passes it: the number of ends at or below ``j``, read from one
    prefix sum over a histogram of the ends (clipped to the chunk), so
    no row searches.  The row then reads its window's numbers (end,
    size, each side's count and start) with one gather, a row of a
    ``[windows, 2 + 2 * sides]`` table, and is that window's
    combination number ``j - begin``, split into one row index a side
    as ``itertools.product`` numbers them (side 0 slowest).  Returns
    each side's low word and the high words of the sides in ``wide``,
    ``rows`` entries each; entries past the chunk's end, and those of
    a side with no row, read the scratch row.  Every gather takes a
    flat index (a gather by a 2-D index takes the chip's compiler
    seconds at these sizes)."""
    scratch = words.shape[1] - 1
    n_sides, n_windows = count.shape
    total = jnp.maximum(count[0], 1)
    for s in range(1, n_sides):
        total = total * jnp.maximum(count[s], 1)
    j = jnp.arange(rows, dtype=jnp.int32)
    hist = jnp.zeros(rows + 1, dtype=jnp.int32).at[jnp.clip(ends, 0, rows)].add(1)
    at = jnp.minimum(jnp.cumsum(hist[:rows]), n_windows - 1)
    window = jnp.stack([ends, total, *count, *starts], axis=1)[at]
    live = j < ends[-1]
    loc = j - (window[:, 0] - window[:, 1])
    pos = [None] * n_sides
    for side in reversed(range(n_sides)):
        side_count = window[:, 2 + side]
        side_size = jnp.maximum(side_count, 1)
        index = loc % side_size
        loc = loc // side_size
        here = live & (side_count > 0)
        pos[side] = jnp.where(here, window[:, 2 + n_sides + side] + index, scratch)
    low = jnp.stack([words[0, p] for p in pos])
    hi = [words[1, pos[side]] for side in wide]
    high = jnp.stack(hi) if hi else jnp.zeros((0, rows), dtype=words.dtype)
    return low, high


def _expanded(starts: np.ndarray, lengths: np.ndarray) -> np.ndarray:
    """Every position of the regions ``(starts, lengths)``, region by
    region."""
    total = int(lengths.sum())
    if not total:
        return np.empty(0, dtype=np.int64)
    skip = starts - (np.cumsum(lengths) - lengths)
    return np.repeat(skip, lengths) + np.arange(total)


def _padded(values: np.ndarray, fill: int, length: Optional[int] = None) -> np.ndarray:
    """``values`` as int32, padded with ``fill`` to ``length`` (by
    default the pad ladder's length for them)."""
    out = np.full(length or pad_len(len(values)), fill, dtype=np.int32)
    out[: len(values)] = values
    return out


class RowStore:
    """The rows of open (key, window, side) slots in an arena on the
    device, with the host's record of each slot's region: where it
    starts, the rows it holds and its room (``start``, ``length``,
    ``room``, indexed by slot id).  Positions handed out hold until the
    next :meth:`place`, which may compact."""

    def __init__(self):
        self.words: Optional[jax.Array] = None
        self.cap = 0  # arena rows, the scratch row included
        self.n = 0  # rows handed out from the front of the arena
        self.live = 0  # rows held by open slots
        self.start = np.zeros(0, dtype=np.int64)
        self.length = np.zeros(0, dtype=np.int64)
        self.room = np.zeros(0, dtype=np.int64)

    def _hold_slots(self, upto: int) -> None:
        """Room in the per-slot record for slot ids below ``upto``."""
        have = len(self.start)
        if upto <= have:
            return
        size = max(upto, 2 * have, 1024)
        for name in ("start", "length", "room"):
            col = np.zeros(size, dtype=np.int64)
            col[:have] = getattr(self, name)
            setattr(self, name, col)

    def place(self, slots: np.ndarray, adds: np.ndarray) -> np.ndarray:
        """Make room for ``adds`` more rows in each of ``slots``
        (unique); the position of each slot's first new row.  A slot
        whose region is too small moves to one twice its new size (a
        new slot gets exactly its rows)."""
        self._hold_slots(int(slots.max()) + 1)
        held = self.length[slots]
        need = held + adds
        grows = need > self.room[slots]
        room = np.where(held == 0, need, 2 * need)[grows]
        total = int(room.sum())
        if self.n + total > self.cap - 1:
            self._compact(total)
        moving = slots[grows]
        old_start = self.start[moving]
        new_start = self.n + np.cumsum(room) - room
        self.n += total
        copied = held[grows]
        if copied.any():
            self._copy(_expanded(old_start, copied), _expanded(new_start, copied))
        self.start[moving] = new_start
        self.room[moving] = room
        self.length[slots] = need
        added = int(adds.sum())
        self.live += added
        _flight.RECORDER.count("join_store_rows", added)
        return self.start[slots] + held

    def write(self, pos: np.ndarray, rows: np.ndarray) -> None:
        """Rows ``[2, n]`` (int32 words) to their positions."""
        n = len(pos)
        padded = pad_len(n)
        with _flight.span("h2d", rows=padded):
            buf = np.zeros((2, padded), dtype=np.int32)
            buf[:, :n] = rows
            pos_p = _padded(pos, self.cap - 1)
            _flight.note_transfer("h2d", buf.nbytes + pos_p.nbytes)
            self.words = join_store_write(self.words, jax.device_put(pos_p), jax.device_put(buf))

    def release(self, slots: np.ndarray) -> None:
        """The regions of closed slots are dead from now on."""
        if not len(slots):
            return
        gone = int(self.length[slots].sum())
        self.live -= gone
        self.length[slots] = 0
        self.room[slots] = 0
        _flight.RECORDER.count("join_store_rows", -gone)

    def regions(self, slots: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
        """``(start, length)`` of each slot's region."""
        return self.start[slots], self.length[slots]

    def read(self, slots: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
        """The stored rows of ``slots`` region by region as ``(low
        words, high words)``."""
        pos = _expanded(*self.regions(slots))
        if not len(pos):
            empty = np.empty(0, dtype=np.int32)
            return empty, empty
        got = np.asarray(join_read(self.words, jax.device_put(_padded(pos, self.cap - 1))))
        _flight.note_transfer("d2h", got.nbytes)
        return got[0, : len(pos)], got[1, : len(pos)]

    def expand(
        self,
        counts: jax.Array,
        slots: np.ndarray,
        starts: np.ndarray,
        sizes: np.ndarray,
        wide: Tuple[int, ...],
    ) -> Tuple[np.ndarray, np.ndarray]:
        """A close's output rows: ``slots`` and ``starts`` are ``[sides,
        windows]``, ``sizes`` each window's output rows, ``counts`` the
        slot table's count field.  Chunks of the output ladder's sizes
        (the largest where more is left), all dispatched before the
        first is read back; only the output rows' words come back
        (:func:`join_expand`).  Returns the low words ``[sides, rows]``
        and the high words of the sides in ``wide``."""
        ends = np.cumsum(sizes)
        begins = ends - sizes
        total = int(ends[-1]) if len(ends) else 0
        pending = []
        t0 = 0
        while t0 < total:
            left = total - t0
            rows = next((n for n in OUTPUT_LADDER if left <= n), OUTPUT_LADDER[-1])
            d0 = int(np.searchsorted(ends, t0, side="right"))
            d1 = int(np.searchsorted(begins, t0 + rows, side="left"))
            these = np.full((len(slots), rows), -1, dtype=np.int32)
            these[:, : d1 - d0] = slots[:, d0:d1]
            at = np.zeros((len(slots), rows), dtype=np.int32)
            at[:, : d1 - d0] = starts[:, d0:d1]
            rel = np.full(rows, ends[d1 - 1] - t0, dtype=np.int32)
            rel[: d1 - d0] = ends[d0:d1] - t0
            _flight.note_transfer("h2d", these.nbytes + at.nbytes + rel.nbytes)
            count = join_counts(counts, jax.device_put(these))
            out = join_expand(
                self.words, count, jax.device_put(at), jax.device_put(rel), rows=rows, wide=wide
            )
            pending.append((min(rows, left), out))
            _flight.RECORDER.count("join_expand_rows", rows)
            t0 += rows
        low, high = [], []
        for keep, (lo, hi) in pending:
            lo, hi = np.asarray(lo), np.asarray(hi)
            _flight.note_transfer("d2h", lo.nbytes + hi.nbytes)
            low.append(lo[:, :keep])
            high.append(hi[:, :keep])
        if not pending:
            empty = np.empty((len(slots), 0), dtype=np.int32)
            return empty, empty[: len(wide)]
        return np.concatenate(low, axis=1), np.concatenate(high, axis=1)

    def _copy(self, src: np.ndarray, dst: np.ndarray) -> None:
        scratch = self.cap - 1
        self.words = join_store_move(
            self.words,
            jax.device_put(_padded(src, scratch)),
            jax.device_put(_padded(dst, scratch)),
        )
        _flight.RECORDER.count("join_store_moved", len(src))

    def _compact(self, need: int) -> None:
        """Copy the live regions to the front of an arena with room
        for ``need`` more rows, in the order they lie, each with the
        room it had: the same size where they and ``need`` fill at
        most half of it, else four times as large until they do (few
        sizes: each is a program of every kind)."""
        live_slots = np.flatnonzero(self.length > 0)
        live_slots = live_slots[np.argsort(self.start[live_slots], kind="stable")]
        lengths = self.length[live_slots]
        room = self.room[live_slots]
        held = int(room.sum())
        new_start = np.cumsum(room) - room
        src = _expanded(self.start[live_slots], lengths)
        dst = _expanded(new_start, lengths)
        rows = max(self.cap, _MIN_ROWS)
        while 2 * (held + need) + 1 > rows:
            rows *= 4
        if self.words is None:
            self.words = jnp.zeros((2, rows), dtype=jnp.int32)
        else:
            # Padded to the old arena's size: a compaction compiles
            # once an arena size, however many rows it carries over.
            self.words = join_store_compact(
                self.words,
                jax.device_put(_padded(src, self.cap - 1, self.cap)),
                jax.device_put(_padded(dst, rows - 1, self.cap)),
                rows=rows,
            )
            _flight.RECORDER.count("join_store_moved", len(src))
        self.cap = rows
        self.start[live_slots] = new_start
        self.n = held
