"""Device-accelerated windowed aggregation.

Lowers numeric ``fold_window``/``reduce_window``/``count_window`` over
``EventClock`` + tumbling/sliding windows to the device tier: window-id
assignment, per-key watermarks, and lateness are vectorized numpy on
the host (float64 time math keeps full precision); the per-(key,
window) fold is one scatter-combine into a device slot table (see
``bytewax_tpu/ops/segment.py``).  The host tier's `_WindowLogic`
(``bytewax_tpu/operators/windowing.py``) remains the oracle and
handles everything else (sessions, non-numeric folds, SystemClock).

Snapshots are emitted in the host tier's ``_WindowSnapshot`` format,
so recovery is interchangeable between tiers.

Semantics note: lateness matches the host tier exactly — each row is
judged post-item against its key's running watermark (a per-key
prefix max over the delivered batch, floored by the carried base), so
an in-batch timestamp jump marks subsequent borderline rows late on
both tiers identically, and the comparison is strict (``ts <
watermark``; a row exactly at the watermark is on time).
``tests/test_window_accel.py::test_window_accel_lateness_boundary``
pins this.

Slot note: a tumbling/sliding step keeps its open (key, window)s
under integer composites (``kid << 32 | wid + 2**31``) in
:class:`_OpenWindows` and opens, reads and releases their device
slots a delivery at a time through the aggregate state's id-based
surface (``open_ids`` / ``states_of`` / ``release_ids``): nothing runs
once per (key, window) but the construction of the result tuples and
``_finalize_one``.  The table appends opens to an arena in open
order, marks closes dead and finds composites through a two-level
sorted index; it copies itself only in a rebuild, when its own sizes
say so, so a delivery costs what it opens, closes and retimes, not
what is open.  Beside each composite the table keeps the system
time at which the window falls due under its key's clock (``at``); a
delivery's phase retimes the windows of the delivery's own keys (the
only clocks it moved), so the due scan and the notify hint are one
pass over one column each, not a recomputation of every open
window's watermark.  Metadata rows (two ``datetime``s and a
``WindowMetadata`` a window) are built only while the plan keeps the
step's ``meta`` tap (``WindowAccelSpec.meta_live``, set at flatten
time).  The session tier keeps its open sessions the same way, in
:class:`_OpenSessions` (the arena with each session's bounds beside
it), and builds metadata rows under the same rule.

Output note: every tier here hands a delivery's output on as one
:class:`WindowEvents`, its ``down``, ``late`` and ``meta`` rows each
built once in the form its stream carries, so the window operator's
unwrap taps pass them on with no pass over the rows; the host tier's
``_WindowLogic`` emits tagged ``(key, (window_id, type, obj))`` rows,
which the taps still take apart.

Key note: a tumbling/sliding step holds a key (its id, its clock,
its encoder entries) only while the key has an open window, as the
host tier discards a ``_WindowLogic`` that is empty.  The close that
takes a key's last window finds it (on whichever thread runs the
close) and hands it back with the close's events; the main thread
lets it go (:meth:`DeviceWindowAggState.let_go`) and gives its id to
the next new key, which starts a clock at minus infinity.  A key with
an on-time row in a delivery taken in after the one whose close found
it stays, clock and all (it has a window again before anything could
tell).  The session tier lets a key go one delivery after its last
session closed and keeps, by name, the clock and next session id the
host tier's never-empty session logic would still hold
(:class:`DeviceSessionAggState`).

Join note: ``join_window`` with product inserts and final emits runs
here too (:class:`DeviceJoinState`): a slot a (key, window, side) in
the tumbling/sliding tier's table, the rows themselves in a row store
on the device (``ops/join.py``), and a close that expands its windows'
products there and reads back the output rows' values alone.

Tier note: the three tiers (tumbling/sliding, session, join) share
one close (:meth:`DeviceWindowAggState._close_due`: retime, due scan,
take out, give the slots back, one :class:`WindowEvents`, the keys
left without a window) and one snapshot
(:meth:`DeviceWindowAggState.snapshots_for`: one pass and one device
read for all the keys, a ``_WindowSnapshot`` a key).  A tier gives
only what differs, through hooks: ``_close_begins``, ``_due``,
``_closing`` / ``_fetch_closed`` / ``_emit_closed`` (what a close
takes, reads back and writes down), ``_bounds``, ``_window_states``,
``_windower_of`` and ``_known_keys``; ``_CLOSE_SPANS`` names a
close's spans stage by stage.

Pipeline note (docs/performance.md): each ``on_batch*`` call returns
``(late_events, device_phase)`` — the host phase (vocab sync,
watermark math, late classification) runs on the caller's thread and
mutates only host clock state; ``device_phase()`` (the fold
scatter-combine, the due-window scan against the clocks of the
delivery's keys as its ingest left them, the close snapshot fetch,
and window-event construction)
is safe to defer onto the engine's dispatch-pipeline worker.  The
driver runs it inline at pipeline depth 1 — byte-identical to the
pre-pipeline engine.  ``on_notify``/``on_eof``/``snapshots_for``
remain synchronous and may only run with the pipeline drained.
"""

from collections import deque
from datetime import datetime, timedelta, timezone
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from bytewax_tpu.engine import flight as _flight
from bytewax_tpu.engine.arrays import KeyEncoder, VocabMap, grow_column

__all__ = [
    "DeviceJoinState",
    "DeviceWindowAggState",
    "JoinAccelSpec",
    "WindowAccelSpec",
    "WindowEvents",
]

_US = 1_000_000.0


def _to_us(dt: datetime) -> float:
    return dt.timestamp() * _US


# A (key, window) of a tumbling/sliding step as one integer:
# ``kid << 32 | wid + 2**31``.
_WID_BIAS = 1 << 31
_WID_MASK = (1 << 32) - 1
_NO_KIDS = np.empty(0, dtype=np.int64)


# When :class:`_OpenWindows` rebuilds, as ratios of the sizes it
# observes: the recent opens outgrow this share of the large sorted
# run, or the closed rows this share of the open ones.
_RECENT_SHARE = 1 / 4
_DEAD_SHARE = 1 / 2


def _find(run: np.ndarray, wanted: np.ndarray):
    """Where each of ``wanted`` lies in the sorted ``run``, and
    whether it is there."""
    pos = np.searchsorted(run, wanted)
    if not len(run):
        return pos, np.zeros(len(wanted), dtype=bool)
    return pos, run[np.minimum(pos, len(run) - 1)] == wanted


class _OpenWindows:
    """The open (key, window)s of a tumbling/sliding step.  What a
    delivery costs here follows what the delivery opens, closes and
    retimes, not what the table holds: no Python per window, and no
    copy of a whole column but in a rebuild, whose cost is spread
    over the deliveries between two of them.

    **The arena** holds a row per window in the order the windows
    were opened, which is the order closes and snapshots list them
    in: the composite, the aggregate state's slot id and ``at``, the
    system time (us) at which the window falls due under its key's
    clock (``sys_at_base + (close - base)``, the instant the key's
    watermark reaches the close time).  ``at`` moves only when the
    key's clock does (:meth:`retime`), so the due scan and the notify
    hint are one pass over one column each; a window just opened
    holds ``inf`` until the delivery that opened it retimes its key.
    Opens append (the columns are allocated with room to spare); a
    close marks its rows dead (slot id -1, ``at`` inf) and moves
    nothing.  A window's key id, window id and close time are
    arithmetic on its composite and are not stored.

    **The index** finds a composite's row: two sorted runs of
    (composite, arena row), a large one that only a rebuild writes
    and a small one of the opens since, which takes each delivery's
    new composites with one ``np.insert``.  A composite has at most
    one entry; the entry of a closed window stays until the next
    rebuild (a lookup skips it: its row is dead) and is pointed at
    the new row if the window is opened again before that.  A key's
    windows are one range of each run.

    **A rebuild** drops the dead rows from the arena, merges the two
    runs into the large one and renumbers its rows, in one pass.  It
    runs before an open that finds the small run grown past
    ``_RECENT_SHARE`` of the large one or the arena full, and after a
    close that leaves more than ``_DEAD_SHARE`` as many dead rows as
    live ones: counters
    ``window_table_rebuilds`` and ``window_table_rebuild_rows`` (the
    live rows each carried over).  Arena rows handed out
    (:meth:`due`, :meth:`rows_of`) hold until the next call that
    opens or removes.
    """

    __slots__ = (
        "_comp", "_ids", "_at", "_n", "_live",
        "_big", "_big_rows", "_small", "_small_rows",
    )  # fmt: skip

    #: The arena's columns, each carried over by a rebuild.
    _COLUMNS = ("_comp", "_ids", "_at")

    def __init__(self):
        self._comp = np.empty(0, dtype=np.int64)
        self._ids = np.empty(0, dtype=np.int32)
        self._at = np.empty(0, dtype=np.float64)
        self._n = 0  # arena rows in use, the dead among them
        self._live = 0
        self._big = self._small = np.empty(0, dtype=np.int64)
        self._big_rows = self._small_rows = np.empty(0, dtype=np.int64)

    def __len__(self) -> int:
        return self._live

    def _column(self, col: np.ndarray) -> np.ndarray:
        col = col[: self._n]
        if self._live < self._n:
            col = col[self._ids[: self._n] >= 0]
        col = col.view()
        col.flags.writeable = False
        return col

    @property
    def comp(self) -> np.ndarray:
        """Composites of the open windows in the order they were
        opened (read-only, like :attr:`ids` and :attr:`at`)."""
        return self._column(self._comp)

    @property
    def ids(self) -> np.ndarray:
        return self._column(self._ids)

    @property
    def at(self) -> np.ndarray:
        return self._column(self._at)

    def ids_for(self, uniq: np.ndarray, agg) -> np.ndarray:
        """Slot ids of sorted unique composites; those not yet open
        are given slots by ``agg`` in one call (in ascending
        composite order) and join the table."""
        return self._find_or_open(uniq, agg)[0]

    def _find_or_open(
        self, uniq: np.ndarray, agg, counter: str = "window_opens"
    ) -> Tuple[np.ndarray, np.ndarray]:
        """:meth:`ids_for`, and the arena row of each composite (they
        hold until the next call that opens or removes); the opens
        are counted under ``counter``."""
        if (
            self._n + len(uniq) > len(self._comp)
            or len(self._small) > _RECENT_SHARE * len(self._big)
        ):
            self._rebuild(room=len(uniq))
        pos_big, in_big = _find(self._big, uniq)
        pos_small, in_small = _find(self._small, uniq)
        row_of = np.full(len(uniq), -1, dtype=np.int64)
        row_of[in_big] = self._big_rows[pos_big[in_big]]
        row_of[in_small] = self._small_rows[pos_small[in_small]]
        # (A composite in neither run reads row -1: whatever that
        # row holds, ``new`` does not trust it.)
        fresh = row_of < 0
        out = self._ids[row_of]
        new = fresh | (out < 0)
        if not new.any():
            return out, row_of
        opened = agg.open_ids(uniq[new])
        _flight.RECORDER.count(counter, len(opened))
        out[new] = opened
        rows = np.arange(self._n, self._n + len(opened))
        self._comp[rows] = uniq[new]
        self._ids[rows] = opened
        self._at[rows] = np.inf
        self._n += len(opened)
        self._live += len(opened)
        row_of[new] = rows
        # An entry left by a window closed since the last rebuild is
        # pointed at the new row; a composite with no entry joins the
        # small run.
        for run_rows, pos, held in (
            (self._big_rows, pos_big, in_big),
            (self._small_rows, pos_small, in_small),
        ):
            again = new & held
            if again.any():
                run_rows[pos[again]] = row_of[again]
        self._small = np.insert(self._small, pos_small[fresh], uniq[fresh])
        self._small_rows = np.insert(
            self._small_rows, pos_small[fresh], row_of[fresh]
        )
        return out, row_of

    def _of_keys(self, kids: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
        """Arena rows of the open windows of ``kids``, and for each
        the index into ``kids`` of its key: a key's composites are
        one range of each run, the dead among them skipped."""
        rows, of_key = [], []
        for run, run_rows in (
            (self._big, self._big_rows),
            (self._small, self._small_rows),
        ):
            lo = np.searchsorted(run, kids << 32)
            n = np.searchsorted(run, (kids + 1) << 32) - lo
            total = int(n.sum())
            if not total:
                continue
            found = run_rows[
                np.arange(total) + np.repeat(lo - (np.cumsum(n) - n), n)
            ]
            live = self._ids[found] >= 0
            rows.append(found[live])
            of_key.append(np.repeat(np.arange(len(kids)), n)[live])
        if not rows:
            return _NO_KIDS, _NO_KIDS
        return np.concatenate(rows), np.concatenate(of_key)

    def retime(
        self, kids: np.ndarray, base: np.ndarray, sys_at: np.ndarray, closes_of
    ) -> None:
        """Set ``at`` of every open window of ``kids`` from the keys'
        clocks (``base``, ``sys_at``, parallel to ``kids``)."""
        rows, of_key = self._of_keys(kids)
        self._at[rows] = sys_at[of_key] + (
            self._closes(rows, closes_of) - base[of_key]
        )

    def _closes(self, rows: np.ndarray, closes_of) -> np.ndarray:
        """Event times (us) at which the windows at ``rows`` fall due:
        their close times, arithmetic on their composites."""
        return closes_of(self._comp[rows])

    def shift(self, delta_us: float) -> None:
        """Move every due instant by ``delta_us``, as if the system
        clock had (tests age a table with it)."""
        self._at[: self._n] += delta_us

    def due(self, now_us: float, most: Optional[int] = None) -> np.ndarray:
        """Arena rows of the windows due by ``now_us``, in the order
        they were opened; with ``most``, at most that many, the
        earliest due first and whole instants at a time (rows that
        share a due instant, as a key's rows of one window do, are
        all taken or all left)."""
        at = self._at[: self._n]
        rows = np.nonzero(at <= now_us)[0]
        if now_us == np.inf:  # end of input: the dead hold inf too
            rows = rows[self._ids[rows] >= 0]
        elif most is not None and len(rows) > most:
            at_due = at[rows]
            cut = np.partition(at_due, most)[most]
            first = at_due < cut
            rows = rows[first if first.any() else at_due == cut]
        return rows

    def next_due(self) -> float:
        """The earliest due instant (inf where none is known)."""
        return float(self._at[: self._n].min()) if self._n else np.inf

    def read(self, rows: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
        """Composites and slot ids at arena rows."""
        return self._comp[rows], self._ids[rows]

    def rows_of(self, kids: np.ndarray) -> np.ndarray:
        """Arena rows of the keys' open windows, in the order the
        windows were opened."""
        return np.sort(self._of_keys(kids)[0])

    def without_window(self, kids: np.ndarray) -> np.ndarray:
        """Those of the key ids that have no open window."""
        held = np.zeros(len(kids), dtype=bool)
        held[self._of_keys(kids)[1]] = True
        return kids[~held]

    def remove(self, rows: np.ndarray) -> None:
        self._ids[rows] = -1
        self._at[rows] = np.inf
        self._live -= len(rows)
        if self._n - self._live > _DEAD_SHARE * self._live:
            self._rebuild()

    def _rebuild(self, room: int = 0) -> None:
        """Drop the dead rows, merge the small run into the large one
        and leave the arena with room for as many rows again and for
        ``room`` more (and no smaller than it was filled: a table
        that turns over whole keeps its room)."""
        n, live = self._n, self._live
        keep = self._ids[:n] >= 0
        row_now = np.cumsum(keep)
        row_now -= 1
        runs = []
        for run, run_rows in (
            (self._big, self._big_rows),
            (self._small, self._small_rows),
        ):
            held = keep[run_rows]
            runs.append((run[held], row_now[run_rows[held]]))
        (big, big_rows), (small, small_rows) = runs
        pos = np.searchsorted(big, small)
        self._big = np.insert(big, pos, small)
        self._big_rows = np.insert(big_rows, pos, small_rows)
        self._small = small[:0]
        self._small_rows = small_rows[:0]
        kept = np.flatnonzero(keep)
        size = max(2 * (live + room), n)

        def carried(old: np.ndarray) -> np.ndarray:
            col = np.empty(size, dtype=old.dtype)
            # ("clip" writes straight into ``out``; the default mode
            # goes through a buffer.)
            np.take(old, kept, out=col[:live], mode="clip")
            return col

        for name in self._COLUMNS:
            setattr(self, name, carried(getattr(self, name)))
        self._n = live
        _flight.RECORDER.count("window_table_rebuilds")
        _flight.RECORDER.count("window_table_rebuild_rows", live)


class _OpenSessions(_OpenWindows):
    """The open sessions of a session step: :class:`_OpenWindows` with
    two more columns, the session's open and close times (us), which
    move as the session grows and so are stored.  The composite is
    ``kid << 32 | wid + 2**31`` as a window's; ``at`` is the system
    time at which the session falls due (its close + gap under its
    key's clock), and a session is due strictly after it, as the host
    tier closes a session once ``close < watermark - gap``."""

    __slots__ = ("_lo", "_hi", "gap_us")

    _COLUMNS = _OpenWindows._COLUMNS + ("_lo", "_hi")

    def __init__(self, gap_us: float):
        super().__init__()
        self._lo = np.empty(0, dtype=np.float64)
        self._hi = np.empty(0, dtype=np.float64)
        self.gap_us = gap_us

    def _closes(self, rows: np.ndarray, closes_of) -> np.ndarray:
        return self._hi[rows] + self.gap_us

    def due(self, now_us: float) -> np.ndarray:
        if now_us == np.inf:  # end of input: every open session
            return np.flatnonzero(self._ids[: self._n] >= 0)
        return np.flatnonzero(self._at[: self._n] < now_us)

    def bounds(self, rows: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
        """Open and close times (us) at arena rows."""
        return self._lo[rows], self._hi[rows]

    def grow(self, rows: np.ndarray, lo: np.ndarray, hi: np.ndarray) -> None:
        """Widen the sessions at ``rows`` (repeats allowed) to take in
        ``[lo, hi]``."""
        np.minimum.at(self._lo, rows, lo)
        np.maximum.at(self._hi, rows, hi)

    def set_bounds(self, rows: np.ndarray, lo: np.ndarray, hi: np.ndarray) -> None:
        self._lo[rows] = lo
        self._hi[rows] = hi

    def open_sessions(
        self, comp: np.ndarray, lo: np.ndarray, hi: np.ndarray, agg
    ) -> np.ndarray:
        """Open new sessions (sorted unique composites, none open
        now) with their bounds; their slot ids."""
        ids, rows = self._find_or_open(comp, agg, "session_opens")
        self.set_bounds(rows, lo, hi)
        return ids


def _ts_us_of(batch) -> np.ndarray:
    """A columnar batch's ``ts`` column as float64 microseconds since
    the epoch (``np.datetime64`` or int64 microseconds)."""
    ts_col = batch.numpy("ts")
    if np.issubdtype(ts_col.dtype, np.datetime64):
        return ts_col.astype("datetime64[us]").astype(np.int64).astype(np.float64)
    return ts_col.astype(np.float64)


def _clock_state(base_us: float, sys_at_us: float):
    """A key's clock as the host tier's ``_EventClockState``."""
    from bytewax_tpu.operators.windowing import _EventClockState

    return _EventClockState(
        system_time_of_max_event=datetime.fromtimestamp(
            sys_at_us / _US, tz=timezone.utc
        ),
        watermark_base=(
            datetime.fromtimestamp(base_us / _US, tz=timezone.utc)
            if np.isfinite(base_us)
            else datetime.min.replace(tzinfo=timezone.utc)
        ),
    )


class WindowEvents:
    """What one delivery of a device-tier window step writes, already
    split by the stream each row goes to: ``down`` (closed windows'
    values), ``late`` (late rows' values) and ``meta`` (closed
    windows' ``WindowMetadata``, only while the ``meta`` tap is live),
    each a list of ``(key, (window_id, obj))`` rows built once, and
    ``keys``, the keys those rows belong to (one a window or a late
    row, not one an output row).  The window operator's unwrap taps
    hand a part on as it is; the host tier's tagged
    ``(key, (window_id, type, obj))`` lists still go through their
    comprehension.  Its length is its rows, and iterating it gives the
    tagged form: late rows, then ``"E"``, then ``"M"``."""

    __slots__ = ("down", "late", "meta", "keys")

    def __init__(self, down=None, keys=None, late=None, meta=None):
        self.down: List[Any] = [] if down is None else down
        self.keys: List[str] = [] if keys is None else keys
        self.late: List[Any] = [] if late is None else late
        self.meta: List[Any] = [] if meta is None else meta

    def __len__(self) -> int:
        return len(self.down) + len(self.late) + len(self.meta)

    def __add__(self, other: "WindowEvents") -> "WindowEvents":
        """Two deliveries' output in order, part by part."""
        if not other:
            return self
        if not self:
            return other
        return WindowEvents(
            self.down + other.down,
            self.keys + other.keys,
            self.late + other.late,
            self.meta + other.meta,
        )

    def __iter__(self):
        for typ, rows in (("L", self.late), ("E", self.down), ("M", self.meta)):
            for key, (wid, obj) in rows:
                yield key, (wid, typ, obj)


class _CloseSpans:
    """The spans of one close as it goes from stage to stage (a tier's
    ``_CLOSE_SPANS``): a stage named as the span still open goes on in
    it, and ``None`` opens none."""

    __slots__ = ("_names", "_open")

    def __init__(self, names: Tuple[Optional[str], ...]):
        self._names = names
        self._open: Optional[_flight.span] = None

    def to(self, stage: int, rows: int) -> None:
        name = self._names[stage]
        if self._open is not None:
            if self._open.phase == name:
                return
            self._open.end()
            self._open = None
        if name is not None:
            self._open = _flight.span(name, rows=rows).begin()

    def end(self) -> None:
        if self._open is not None:
            self._open.end()


class _LateTs:
    """Late-value view for columnar batches: row index → timestamp."""

    def __init__(self, ts_us: np.ndarray):
        self._ts_us = ts_us

    def __getitem__(self, row: int) -> datetime:
        return datetime.fromtimestamp(
            self._ts_us[row] / _US, tz=timezone.utc
        )


class _ItemVals:
    """Late-value view for promoted itemized batches: row index →
    the row's original value object (so late events carry the same
    object the host tier would emit — a TsValue keeps its ``.ts``)."""

    __slots__ = ("_items",)

    def __init__(self, items):
        self._items = items

    def __getitem__(self, row: int):
        return self._items[row][1]


class WindowAccelSpec:
    """Flatten-time annotation: lower this windowed fold to device."""

    def __init__(
        self,
        kind: str,
        ts_getter: Callable[[Any], datetime],
        align_to: datetime,
        length: timedelta,
        offset: timedelta,
        wait: timedelta,
    ):
        self.kind = kind
        self.ts_getter = ts_getter
        self.align_us = _to_us(align_to)
        self.length_us = length.total_seconds() * _US
        self.offset_us = offset.total_seconds() * _US
        self.wait_us = wait.total_seconds() * _US

    #: Whether anything reads the step's ``meta`` stream: the flatten
    #: pass clears it when the ``unwrap_meta`` tap was pruned from the
    #: plan, and the tumbling/sliding tier then builds no "M" events.
    meta_live = True

    def make_state(self) -> "DeviceWindowAggState":
        return DeviceWindowAggState(self)

    def __repr__(self) -> str:
        return f"WindowAccelSpec({self.kind!r})"


class SessionAccelSpec(WindowAccelSpec):
    """Flatten-time annotation: lower this session-windowed fold to
    device (gap-merged sessions, reference semantics:
    ``/root/reference/pysrc/bytewax/operators/windowing.py:688-806``)."""

    def __init__(
        self,
        kind: str,
        ts_getter: Callable[[Any], datetime],
        gap: timedelta,
        wait: timedelta,
    ):
        self.kind = kind
        self.ts_getter = ts_getter
        self.gap_us = gap.total_seconds() * _US
        self.wait_us = wait.total_seconds() * _US
        # Unused sliding fields (the base __init__ computes its
        # static expansion factor from them).
        self.align_us = 0.0
        self.length_us = 1.0
        self.offset_us = 1.0

    def make_state(self) -> "DeviceSessionAggState":
        return DeviceSessionAggState(self)

    def __repr__(self) -> str:
        return f"SessionAccelSpec({self.kind!r})"


class DeviceWindowAggState:
    """All keys' open windows for one windowed-fold step.

    Host numpy state: per-key watermark bases (EventClock semantics:
    watermark = max event ts − wait + system time since that event,
    ``windowing.py:_EventClockLogic``) and the open-window table
    mapping ``(key, window_id)`` to a device slot.
    """

    def __init__(self, spec: WindowAccelSpec):
        self.spec = spec
        self.agg = self._slot_table(spec)
        # windows_per_ts is static for a sliding windower.
        self.expand = max(1, int(np.ceil(spec.length_us / spec.offset_us)))
        # Per-key clock state, indexed by key id.  A key holds an id
        # while it has an open window (:meth:`let_go`); ``keys`` is
        # None at an id that is free.  The key-indexed columns grow by
        # doubling (``grow_column``): their ``len`` is their capacity,
        # ``len(self.keys)`` the ids given out.
        self.keys: List[Optional[str]] = []
        self.key_ids: Dict[str, int] = {}
        self._free_kids: List[int] = []
        self.base_us = np.empty(0, dtype=np.float64)  # watermark base
        self.sys_at_base = np.empty(0, dtype=np.float64)
        # Deliveries taken in so far, and per key id the last one that
        # held an on-time row of the key.
        self._seq = 0
        self._seen = np.empty(0, dtype=np.int64)
        # Scratch of the in-order clock pass, one entry a key id: a
        # delivery writes its keys' entries and reads only those, so
        # nothing initialises it.
        self._last_row = np.empty(0, dtype=np.int64)
        # Open windows by integer composite, with the slot self.agg
        # gave each (the session tier keeps a table of its own).
        self.open = _OpenWindows()
        #: Keys touched since the last epoch snapshot.
        self.touched: set = set()
        # Dictionary-encoded fast path: external id -> internal kid.
        self._vocab = VocabMap(dtype=np.int64)
        # Automatic encoder for plain string key columns.
        self._enc = KeyEncoder()
        # Sticky marker: itemized promotion failed a deterministic
        # check; stop re-trying it every batch.
        self._promote_failed = False
        # Itemized promotion: the native pass numbers keys densely in
        # a dict of its own; ``_item_kids`` maps those numbers to key
        # ids.  Both start over when a key is let go.
        self._item_iddict: Optional[Dict[str, int]] = None
        self._item_kids = _NO_KIDS
        #: Keys of the delivery being taken in that a close must look
        #: at for their late rows (:meth:`_late_events` notes them
        #: where a key with no on-time row may be left with no window).
        self._late_kids = _NO_KIDS

    def _slot_table(self, spec: WindowAccelSpec):
        """The per-(key, window) fold's table: mesh-sharded when >1
        local device (the window bookkeeping, watermarks and
        open/close, stays host-side; the fold rides the same
        all_to_all exchange as keyed aggregations)."""
        from bytewax_tpu.engine.sharded_state import make_agg_state

        return make_agg_state(spec.kind)

    def _release_slots(self, ids: np.ndarray) -> None:
        """Give back the slots of windows that closed or left."""
        self.agg.release_ids(ids)

    # -- clock -------------------------------------------------------------

    def _phase_clock(self, kids: np.ndarray):
        """What a delivery's deferred phase needs of the clock as of
        its own ingest (the next ingest moves the clock on the host
        thread while the phase may still be in flight): the
        delivery's keys with their clocks, from which the phase
        retimes their open windows, and its keys with late rows that
        :meth:`_late_events` noted."""
        late, self._late_kids = self._late_kids, _NO_KIDS
        return kids, self.base_us[kids], self.sys_at_base[kids], late

    def _wids_of(self, comp: np.ndarray) -> np.ndarray:
        """Window ids of composites."""
        return (comp & _WID_MASK) - _WID_BIAS

    def _closes_of(self, comp: np.ndarray) -> np.ndarray:
        """Close times (us) of composites."""
        spec = self.spec
        return spec.align_us + self._wids_of(comp) * spec.offset_us + spec.length_us

    def _key_ids_for(self, keys: List[str]) -> np.ndarray:
        """Key ids of ``keys``; a key not held takes a free id (or a
        new one) and starts a clock at minus infinity."""
        out = np.empty(len(keys), dtype=np.int64)
        free = self._free_kids
        fresh = []
        for i, k in enumerate(keys):
            kid = self.key_ids.get(k)
            if kid is None:
                if free:
                    kid = free.pop()
                    self.keys[kid] = k
                else:
                    kid = len(self.keys)
                    self.keys.append(k)
                self.key_ids[k] = kid
                fresh.append(kid)
            out[i] = kid
        if fresh:
            _flight.RECORDER.count("window_keys_opened", len(fresh))
            n = len(self.keys)
            if n > len(self.base_us):
                self.base_us = grow_column(self.base_us, n)
                self.sys_at_base = grow_column(self.sys_at_base, n)
                self._seen = grow_column(self._seen, n, 0)
                self._last_row = np.empty(len(self._seen), dtype=np.int64)
            self._start_keys(fresh)
        return out

    def _start_keys(self, fresh: List[int]) -> None:
        """Clocks of keys just given an id: minus infinity."""
        self.base_us[fresh] = -np.inf
        self.sys_at_base[fresh] = datetime.now(timezone.utc).timestamp() * _US

    def let_go(self, gone: Tuple[int, np.ndarray]) -> None:
        """Retire the keys a close left without an open window
        (``gone``: the delivery the close belongs to and the key ids
        it found): id, clock, vocabulary and encoder entries, as the
        host tier discards an empty window logic.  Main thread only,
        in the order of the closes.  A key with an on-time row in a
        later delivery stays: its fold is still to come."""
        seq, kids = gone
        kids = kids[self._seen[kids] <= seq]
        if not len(kids):
            return
        with _flight.span("retire", rows=len(kids)):
            ids = kids.tolist()
            names = [self.keys[kid] for kid in ids]
            self._left_behind(kids, names)
            for name, kid in zip(names, ids):
                del self.key_ids[name]
                self.keys[kid] = None
            self._free_kids.extend(ids)
            self._vocab.drop_ids(ids)
            self._enc.drop_many(names)
            self._item_iddict = None
            _flight.RECORDER.count("window_keys_retired", len(ids))

    def _left_behind(self, kids: np.ndarray, names: List[str]) -> None:
        """Hook: what a key let go leaves behind (nothing here)."""

    def _watermarks(self, kids: np.ndarray, now_us: float) -> np.ndarray:
        return self.base_us[kids] + (now_us - self.sys_at_base[kids])

    # -- processing --------------------------------------------------------

    def _sync_vocab(self, ids: np.ndarray, vocab) -> np.ndarray:
        """Map dictionary-encoded external ids to internal key ids
        with one table lookup; vocabularies must be append-only
        extensions between batches (see :class:`VocabMap`)."""
        self._vocab.sync(ids, vocab, self._key_ids_for)
        return self._vocab.table[ids]

    def _key_ids_of(self, batch) -> np.ndarray:
        """Key ids of a columnar batch's rows (``key_id`` through its
        vocabulary, or ``key`` strings)."""
        if "key_id" in batch.cols and batch.key_vocab is not None:
            return self._sync_vocab(
                batch.numpy("key_id").astype(np.int64), batch.key_vocab
            )
        return self._enc.encode(batch.numpy("key"), self._key_ids_for)

    def on_batch_columnar(self, batch):
        """Columnar fast path: a batch with ``"key"`` (strings) or
        dictionary-encoded ``"key_id"`` + ``key_vocab`` and ``"ts"``
        columns (``np.datetime64`` or int64 microseconds since the
        epoch), plus a ``"value"`` column for numeric folds, runs with
        no per-row Python.  Late rows are reported with their value
        (counting: their timestamp).  Returns ``(late_events,
        device_phase)`` — see :meth:`_ingest`."""
        kids = self._key_ids_of(batch)
        ts_us = _ts_us_of(batch)
        if self.spec.kind == "count":
            return self._ingest(kids, ts_us, _LateTs(ts_us))
        # Keep the column's dtype: integer folds stay exact (the slot
        # table's _pick_dtype handles int32 and rejects wider ints).
        vals = batch.numpy("value")
        if batch.value_scale is not None:
            vals = (vals * batch.value_scale).astype(np.float32)
            return self._ingest(kids, ts_us, vals, fold_vals=vals)
        return self._ingest(kids, ts_us, vals)

    @property
    def open_count(self) -> int:
        """Open (key, window)s, each holding a slot of ``self.agg``."""
        return len(self.open)

    def is_empty(self) -> bool:
        return not self.open_count and not self.key_ids and not self.touched

    def on_batch_items(self, items: List[Any]):
        """Itemized promotion: one native pass dictionary-encodes the
        keys of timestamped ``(key, value)`` tuples and extracts
        epoch-us timestamps — ``(key, datetime)`` rows (counts) or
        ``(key, TsValue)`` rows (numeric folds) — then ingests the
        columns exactly like ``on_batch_columnar``.  Returns None when
        the native module is unavailable (caller runs the per-item
        path); raises :class:`NonNumericValues` when the rows can't
        promote (malformed/mixed shapes, non-UTC timestamps, a
        ts_getter that disagrees with the row's own timestamp) so the
        caller can fall back, matching ``_process_scan_accel``.
        """
        from bytewax_tpu.engine.xla import NonNumericValues
        from bytewax_tpu.native import wa_encode

        if getattr(self, "_promote_failed", False):
            # A previous batch failed a deterministic promotion check
            # (getter disagreement, shape/kind mismatch): don't pay
            # the full encode + rejection on every batch.
            return None
        n = len(items)
        ids = np.empty(n, dtype=np.int32)
        ts_us = np.empty(n, dtype=np.float64)
        vals = np.empty(n, dtype=np.float64)
        # The native pass numbers keys in a dict of its own (a key's
        # number is the dict's length when it is first seen);
        # ``_item_kids`` maps the numbers to key ids, which other
        # ingest paths give out too and :meth:`let_go` takes back.
        iddict = self._item_iddict
        if iddict is None:
            iddict = self._item_iddict = {}
            self._item_kids = _NO_KIDS
        try:
            with _flight.span("encode", rows=n):
                res = wa_encode(items, iddict, ids, ts_us, vals)
        except (TypeError, AttributeError) as ex:
            # AttributeError: a float-coercible value without the
            # TsValue `.ts` attribute.  (The native pass has taken
            # its new keys out of the dict again.)
            raise NonNumericValues(str(ex)) from ex
        if res is None:
            return None
        new_keys, mode = res
        try:
            self._check_promotion(items, ts_us, mode)
        except NonNumericValues:
            # The native pass numbered keys that the map will not hold.
            self._item_iddict = None
            raise
        if new_keys:
            self._item_kids = np.concatenate(
                [self._item_kids, self._key_ids_for(new_keys)]
            )
        kids = self._item_kids[ids]
        if self.spec.kind == "count":
            return self._ingest(kids, ts_us, _LateTs(ts_us))
        # Late events carry the original value objects (a TsValue
        # keeps its .ts); the fold consumes the encoded column.
        return self._ingest(kids, ts_us, _ItemVals(items), fold_vals=vals)

    def _check_promotion(self, items: List[Any], ts_us, mode: int) -> None:
        """The promotion's deterministic checks; raises
        :class:`NonNumericValues` where the rows cannot promote."""
        from bytewax_tpu.engine.xla import NonNumericValues

        n = len(items)
        if mode == 1 and self.spec.kind != "count":
            # Bare datetimes carry no foldable value; the numeric
            # fold must see the rows itemized (and will raise the
            # host tier's own error).
            self._promote_failed = True
            msg = "datetime-only rows can't feed a numeric windowed fold"
            raise NonNumericValues(msg)
        # The promotion bypasses spec.ts_getter; verify on a spread
        # sample of rows that the getter agrees with the row's own
        # timestamp.  This is the promotion contract (documented on
        # EventClock): the getter must read the row's datetime /
        # TsValue ``.ts`` — a getter transforming timestamps
        # nonuniformly within one batch can evade a finite sample and
        # must not be combined with promotable row shapes.  Sub-us
        # slack: .timestamp() arithmetic is float, the native path is
        # exact integer microseconds.
        probes = sorted(
            {int(p) for p in np.linspace(0, n - 1, min(n, 8))}
        ) if n else ()
        for probe in probes:
            try:
                got = _to_us(self.spec.ts_getter(items[probe][1]))
            except Exception as ex:  # noqa: BLE001 — getter rejects row
                raise NonNumericValues(str(ex)) from ex
            if abs(got - ts_us[probe]) > 1.0:
                self._promote_failed = True
                msg = (
                    "ts_getter disagrees with the row timestamp; "
                    "itemized windowing promotion needs a getter "
                    "reading the row's own datetime/TsValue.ts"
                )
                raise NonNumericValues(msg)

    def on_batch(self, keys: List[str], values: List[Any]):
        """Fold a batch; window events come as :class:`WindowEvents`,
        split by stream.  Returns ``(late_events, device_phase)`` —
        see :meth:`_ingest`."""
        spec = self.spec
        with _flight.span("encode", rows=len(keys)):
            kids = self._key_ids_for(keys)
        ts_us = np.fromiter(
            (_to_us(spec.ts_getter(v)) for v in values),
            dtype=np.float64,
            count=len(values),
        )
        return self._ingest(kids, ts_us, values)

    def _ingest(
        self, kids: np.ndarray, ts_us: np.ndarray, values, fold_vals=None
    ):
        """Host phase of one delivery; returns ``(late_events,
        device_phase)``.

        ``kids`` and ``ts_us`` are columns the entry point built for
        this call.  ``values`` is indexed per late row (original
        objects where available); ``fold_vals`` is the numeric fold
        column where the entry point built one of its own (a lazy
        ``values`` view, a ``value_scale`` product): it may go to the
        deferred fold uncopied, a caller's own column may not (a
        source may reuse its buffer once this call has returned).
        ``device_phase()`` — the fold, the due-window scan (against
        the clock as of THIS ingest), and window-event construction —
        returns ``(close_events, notify_hint, gone)`` and may run
        deferred on the dispatch pipeline's worker; it touches only
        the fold/open-window state the pipeline owns between submit
        and finalize.  ``gone`` (the keys its close left without a
        window) goes to :meth:`let_go` on the main thread, phases in
        order."""
        spec = self.spec
        now_us = datetime.now(timezone.utc).timestamp() * _US
        self._seq += 1
        seq = self._seq
        n = len(ts_us)

        # Per-row watermark exactly as the host tier computes it per
        # item (post-item): the running per-key prefix max of
        # (ts - wait), floored by the carried base advanced with
        # system time.  The delivery itself says which pass computes
        # it: where no timestamp falls below the one before it, a
        # key's running maximum at a row is the row's own value.
        with _flight.span("watermark", rows=n):
            eff = ts_us - spec.wait_us
            if n < 2 or bool((ts_us[1:] >= ts_us[:-1]).all()):
                _flight.RECORDER.count("window_clock_inorder")
                seg_kids, seg_max, wm_rows = self._clock_inorder(
                    kids, eff, now_us
                )
            else:
                _flight.RECORDER.count("window_clock_sorted")
                seg_kids, seg_max, wm_rows = self._clock_sorted(
                    kids, eff, now_us
                )
            advanced = seg_max > self.base_us[seg_kids]
            if advanced.any():
                moved = seg_kids[advanced]
                self.base_us[moved] = seg_max[advanced]
                self.sys_at_base[moved] = now_us
            late_mask = ts_us < wm_rows
        any_late = bool(late_mask.any())
        # Ledger: `touch` is the delivery's keys noted for the epoch's
        # close (one set update a key, by name).
        with _flight.span("touch", rows=len(seg_kids)):
            self.touched.update(
                map(self.keys.__getitem__, seg_kids.tolist())
            )

        events = WindowEvents()
        kids_ok = ts_ok = vals_ok = None
        if not any_late:
            # Nothing to drop: the engine's own columns go on as they
            # are, and every key of the delivery has an on-time row.
            if n:
                kids_ok, ts_ok = kids, ts_us
                self._seen[seg_kids] = seq
                if spec.kind == "count":
                    vals_ok = np.ones(n, dtype=np.float64)
                elif fold_vals is not None:
                    vals_ok = fold_vals
                else:
                    vals_ok = np.array(values)  # keep dtype for exact ints
        else:
            events.late = self._late_events(
                np.nonzero(late_mask)[0], kids, ts_us, values
            )
            events.keys = [key for key, _ev in events.late]
            ok = ~late_mask
            if ok.any():
                kids_ok = kids[ok]
                self._seen[kids_ok] = seq
                ts_ok = ts_us[ok]
                if spec.kind == "count":
                    vals_ok = np.ones(int(ok.sum()), dtype=np.float64)
                elif fold_vals is not None:
                    vals_ok = fold_vals[ok]
                else:
                    vals_ok = np.asarray(values)[ok]  # keep dtype for exact ints

        # The deferred phase judges window dues by the watermark as of
        # THIS ingest.
        clock = self._phase_clock(seg_kids)

        def device_phase():
            if kids_ok is not None:
                self._absorb(kids_ok, ts_ok, vals_ok)
            closes, gone = self._close_due(now_us, clock=clock)
            return closes, self.notify_at(clock=clock), (seq, gone)

        return events, device_phase

    def _clock_inorder(
        self, kids: np.ndarray, eff: np.ndarray, now_us: float
    ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """The clock pass over a delivery whose timestamps do not
        decrease: ``(seg_kids, seg_max, wm_rows)`` as
        :meth:`_clock_sorted` gives them, with no sort of the rows.
        A key's running maximum at a row is the row's own ``eff``, and
        its new base candidate the value at its last row."""
        n = len(eff)
        carry_rows = self.base_us[kids] + (
            now_us - self.sys_at_base[kids]
        )
        wm_rows = np.maximum(eff, carry_rows)
        # Each key's last row from one scatter (of repeated indices
        # the last assignment stays), then a sort of the distinct
        # keys only.
        rows = np.arange(n)
        last_row = self._last_row
        last_row[kids] = rows
        last = np.flatnonzero(last_row[kids] == rows)
        by_kid = np.argsort(kids[last])
        last = last[by_kid]
        return kids[last], eff[last], wm_rows

    def _clock_sorted(
        self, kids: np.ndarray, eff: np.ndarray, now_us: float
    ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """The clock pass over any delivery of two rows or more: the
        delivery's key ids ascending, each key's maximum of ``eff``
        and the watermark after each row (its key's running maximum
        of ``eff``, floored by the key's carried clock).  Rows are
        grouped by key with one stable sort and one accumulate runs
        over the contiguous segments — O(n log n), not O(keys × rows)."""
        n = len(eff)
        order = np.argsort(kids, kind="stable")
        kids_sorted = kids[order]
        eff_sorted = eff[order]
        seg_starts = np.concatenate(
            ([0], np.flatnonzero(kids_sorted[1:] != kids_sorted[:-1]) + 1)
        )
        seg_kids = kids_sorted[seg_starts]
        n_seg = len(seg_kids)
        carry = self.base_us[seg_kids] + (
            now_us - self.sys_at_base[seg_kids]
        )

        # Segmented prefix max with no per-key Python: shift each
        # key's rows into its own disjoint value band (band width >
        # the value span), run ONE global cummax — later bands
        # dominate earlier ones, so the running max never leaks
        # across segments — and shift back.  Exact only in integer
        # arithmetic below 2^53, which the hot columnar path
        # (datetime64[us] timestamps) always is; fractional
        # microseconds or astronomically-spread batches take the
        # per-segment loop so watermark equality stays bit-exact (a
        # value that is not finite makes the band infinite or NaN,
        # and goes there too).
        lo_val = float(eff_sorted.min())
        band = float(eff_sorted.max()) - lo_val + 1.0
        integral = band == np.floor(band) and bool(
            (eff_sorted == np.floor(eff_sorted)).all()
        )
        if integral and n_seg * band < float(1 << 53):
            seg_of_row = np.repeat(
                np.arange(n_seg, dtype=np.int64),
                np.diff(np.append(seg_starts, n)),
            )
            off = seg_of_row * band
            prefix = (
                np.maximum.accumulate((eff_sorted - lo_val) + off) - off
            ) + lo_val
            wm_sorted = np.maximum(prefix, carry[seg_of_row])
            seg_max = np.maximum.reduceat(eff_sorted, seg_starts)
        else:
            seg_ends = np.append(seg_starts[1:], n)
            wm_sorted = np.empty(n, dtype=np.float64)
            seg_max = np.empty(n_seg, dtype=np.float64)
            for j, (lo, hi) in enumerate(
                zip(seg_starts.tolist(), seg_ends.tolist())
            ):
                prefix = np.maximum.accumulate(eff_sorted[lo:hi])
                np.maximum(prefix, carry[j], out=wm_sorted[lo:hi])
                seg_max[j] = prefix[-1]
        wm_rows = np.empty(n, dtype=np.float64)
        wm_rows[order] = wm_sorted
        return seg_kids, seg_max, wm_rows

    def _late_events(
        self, late_rows: np.ndarray, kids: np.ndarray, ts_us: np.ndarray, values
    ) -> List[Tuple[str, Tuple[int, Any]]]:
        """The ``late`` rows: window-id attribution for late rows
        (sliding arithmetic; the session subclass reports the
        late-session sentinel)."""
        spec = self.spec
        events = []
        wid_hi = np.floor(
            (ts_us[late_rows] - spec.align_us) / spec.offset_us
        ).astype(np.int64)
        for i, row in zip(range(len(late_rows)), late_rows):
            key = self.keys[int(kids[row])]
            ts_row = ts_us[row]
            for wid in range(
                int(wid_hi[i]) - self.expand + 1, int(wid_hi[i]) + 1
            ):
                # Same in-window bound as the on-time path; for
                # offsets that don't divide length, not every wid
                # in the static range contains the timestamp.
                if (
                    ts_row
                    < spec.align_us
                    + wid * spec.offset_us
                    + spec.length_us
                ):
                    events.append((key, (wid, values[row])))
        return events

    def _absorb(
        self, kids_ok: np.ndarray, ts_ok: np.ndarray, vals_ok: np.ndarray
    ) -> None:
        """Route on-time rows into windows and fold them on device."""
        self._fold_rows(kids_ok, ts_ok, vals_ok)

    def _fold_rows(
        self, kids_ok: np.ndarray, ts_ok: np.ndarray, vals_ok: np.ndarray
    ) -> None:
        """Fold on-time rows into their containing windows (opening
        windows as needed) — the scatter-combine into the slot table."""
        # Ledger: `prep` is the host work up to the fold (composite
        # ids, the unique pass, a slot per new window).
        with _flight.span("prep", rows=len(kids_ok)):
            spec = self.spec
            hi = np.floor(
                (ts_ok - spec.align_us) / spec.offset_us
            ).astype(np.int64)
            if len(hi) and int(np.abs(hi).max()) >= (1 << 31) - self.expand:
                msg = (
                    "window ids exceed the composite encoding range; "
                    "move align_to closer to the event times or use a "
                    "larger window offset"
                )
                raise ValueError(msg)

            # Expand each row into the (static count of) windows that
            # contain it, all vectorized.  Tumbling windows (expand == 1)
            # skip the 2-D broadcast entirely: every row is in exactly its
            # own window (ts < align + hi*offset + length holds by
            # construction of hi when offset == length), saving five
            # row-count-sized materializations per batch on the pipeline
            # worker.
            if self.expand == 1 and spec.offset_us == spec.length_us:
                kid_rep = kids_ok
                wid_flat = hi
                val_rep = vals_ok
            else:
                e = np.arange(self.expand, dtype=np.int64)
                wids = hi[:, None] - e[None, :]  # [n, expand]
                in_window = (
                    ts_ok[:, None]
                    < spec.align_us + wids * spec.offset_us + spec.length_us
                )
                kid_rep = np.broadcast_to(kids_ok[:, None], wids.shape)[
                    in_window
                ]
                wid_flat = wids[in_window]
                val_rep = np.broadcast_to(vals_ok[:, None], wids.shape)[
                    in_window
                ]

            # Composite (key, window) ids: one unique pass, one table
            # lookup, one batched open for the windows that are new.
            comp = (kid_rep << 32) + (wid_flat + _WID_BIAS)
            if not len(comp):
                return
            uniq, inverse = np.unique(comp, return_inverse=True)
            slots_rep = self.open.ids_for(uniq, self.agg)[inverse]
            _flight.RECORDER.record(
                "device_dispatch", tier="window", rows=len(val_rep)
            )
        self.agg.update_ids(slots_rep, val_rep)

    def _open_arrays(self):
        """Parallel ``(kids, wids, closes)`` arrays over the open
        windows (table order: the order they were opened in)."""
        comp = self.open.comp
        return comp >> 32, self._wids_of(comp), self._closes_of(comp)

    # -- close ---------------------------------------------------------------

    #: The spans of a close, stage by stage: the due scan, the closed
    #: windows read back (``None``: the slot table's read times
    #: itself), the events built, the keys left without a window.
    _CLOSE_SPANS: Tuple[Optional[str], ...] = ("close_scan", None, "close_emit", "retire")

    def _close_due(
        self, now_us: float, clock=None
    ) -> Tuple[WindowEvents, np.ndarray]:
        """Close the windows that are due: their events, and the ids
        of the keys this leaves without an open window (for
        :meth:`let_go`).  ``clock`` (:meth:`_phase_clock`, from a
        delivery's own phase) first retimes the delivery's keys and
        names those with late rows, which may be left with no window
        too."""
        if not self._close_begins():
            return WindowEvents(), _NO_KIDS
        late = _NO_KIDS if clock is None else clock[3]
        spans = _CloseSpans(self._CLOSE_SPANS)
        try:
            spans.to(0, len(self.open))
            if clock is not None:
                self.open.retime(*clock[:3], self._closes_of)
            due = self._due(now_us)
            if not len(due):
                gone = self.open.without_window(late) if len(late) else _NO_KIDS
                return WindowEvents(), gone
            rows, comp, slots, held = self._closing(due, *self.open.read(due))
            spans.to(1, len(due))
            got = self._fetch_closed(held)
            spans.to(2, len(due))
            kids = comp >> 32
            keys = list(map(self.keys.__getitem__, kids.tolist()))
            wids = self._wids_of(comp)
            # (Before :meth:`_emit_closed`, which may forget what the
            # bounds read, and before the rows are removed.)
            metas = self._metas(*self._bounds(rows, comp)) if self.spec.meta_live else None
            events = WindowEvents(self._emit_closed(held, got, keys, wids), keys)
            if metas is not None:
                _flight.RECORDER.count("window_meta_events", len(metas))
                events.meta = list(zip(keys, zip(wids.tolist(), metas)))
            self.open.remove(due)
            self._release_slots(slots)
            spans.to(3, len(due))
            gone = self.open.without_window(
                np.unique(np.append(kids, late) if len(late) else kids)
            )
        finally:
            spans.end()
        return events, gone

    def _close_begins(self) -> bool:
        """Hook: whether a close looks at the table at all."""
        return bool(self.open_count)

    def _due(self, now_us: float) -> np.ndarray:
        """Hook: arena rows of the windows due by ``now_us``."""
        return self.open.due(now_us)

    def _closing(self, due: np.ndarray, comp: np.ndarray, ids: np.ndarray):
        """Hook, in the due scan: what closing the arena rows ``due``
        (composites ``comp``, slots ``ids``) takes, ``(rows, comp,
        slots, held)``: the arena row and composite of each window
        (one a window), the slots to give back, and what the next two
        hooks read.  Here a row is a window."""
        return due, comp, ids, ids

    def _fetch_closed(self, held) -> Any:
        """Hook: the closed windows' output, one read from the device;
        here their fold states."""
        # bytewax: allow[BTX-DRAIN] — the windower's .agg is its own slot table (never residency-wrapped; the driver evicts only the keyed-agg/scan tiers), and this due-window fetch runs inside the deferred device phase the pipeline worker owns
        return self.agg.states_of(held)

    def _emit_closed(self, held, got, keys: List[str], wids: np.ndarray) -> List[Any]:
        """Hook: the ``down`` rows from what :meth:`_fetch_closed` read
        (``keys`` and ``wids`` one a window); here one a window."""
        return list(zip(keys, zip(wids.tolist(), map(self._finalize_one, got))))

    def _bounds(self, rows: np.ndarray, comp: np.ndarray):
        """Hook: open and close times (us) of the windows at arena
        ``rows`` (composites ``comp``) and the ids each absorbed (None:
        none); here arithmetic on the composites."""
        closes = self._closes_of(comp)
        return closes - self.spec.length_us, closes, None

    def _metas(self, lo_us: np.ndarray, hi_us: np.ndarray, merged=None) -> List[Any]:
        """``WindowMetadata`` of windows from their open and close
        times (us), with the ids each absorbed (``merged``, a set a
        window; none where not given)."""
        from bytewax_tpu.operators.windowing import WindowMetadata

        if merged is None:
            merged = (set() for _ in range(len(lo_us)))
        return [
            WindowMetadata(
                datetime.fromtimestamp(a / _US, tz=timezone.utc),
                datetime.fromtimestamp(b / _US, tz=timezone.utc),
                m,
            )
            for a, b, m in zip(lo_us.tolist(), hi_us.tolist(), merged)
        ]

    def _finalize_one(self, snap: Any) -> Any:
        """A closed window's emitted value from its host-format fold
        state: where a window's answer is produced."""
        kind = self.spec.kind
        if snap is None:
            return 0 if kind == "count" else None
        if kind == "count":
            return int(snap)
        # mean/stats windows emit the raw accumulator ((sum, count) /
        # (min, max, sum, count)) exactly like the host-tier
        # WindowFold; finalization happens downstream (mean_window /
        # stats_window append it).
        return snap

    def on_notify(self) -> WindowEvents:
        return self._close_now(datetime.now(timezone.utc).timestamp() * _US)

    def on_eof(self) -> WindowEvents:
        return self._close_now(np.inf)

    def _close_now(self, now_us: float) -> WindowEvents:
        """A close on the main thread with the pipeline drained: the
        keys it leaves without a window go at once."""
        events, gone = self._close_due(now_us)
        self.let_go((self._seq, gone))
        return events

    def notify_at(self, clock=None) -> Optional[datetime]:
        """System time of the earliest window close: the instant the
        key's watermark reaches the close time."""
        if not self.open_count:
            return None
        at = self.open.next_due()
        if not np.isfinite(at):
            return None
        return datetime.fromtimestamp(at / _US, tz=timezone.utc)

    # -- recovery ----------------------------------------------------------

    def snapshots_for(self, keys: List[str]):
        """Host-tier ``_WindowSnapshot``-compatible snapshots: a key's
        open windows with their ``WindowMetadata`` and states
        (:meth:`_window_states`), its clock and windower state
        (:meth:`_windower_of`; None: the key snapshots as a discard)."""
        from bytewax_tpu.operators import windowing

        # One pass over the open-window table and ONE device fetch
        # for all requested keys: a scan and a fetch per key is
        # O(keys x open windows) host work plus a whole-table
        # readback per key, which an epoch close over 10^5 touched
        # keys never finishes.
        rows = self._rows_of(keys)
        rows, comp, states = self._window_states(rows, *self.open.read(rows))
        open_of: Dict[int, Tuple[dict, dict]] = {}
        for kid, wid, meta, state in zip(
            (comp >> 32).tolist(),
            self._wids_of(comp).tolist(),
            self._metas(*self._bounds(rows, comp)),
            states,
        ):
            opened, folded = open_of.setdefault(kid, ({}, {}))
            opened[wid] = meta
            folded[wid] = state
        out = []
        for key in keys:
            kid = self.key_ids.get(key)
            opened, folded = open_of.get(kid) or ({}, {})
            held = self._windower_of(windowing, key, kid, opened)
            if held is None:
                out.append((key, None))
                continue
            base_us, sys_at_us, windower = held
            out.append(
                (
                    key,
                    windowing._WindowSnapshot(
                        _clock_state(base_us, sys_at_us), windower, folded, []
                    ),
                )
            )
        return out

    def _window_states(self, rows: np.ndarray, comp: np.ndarray, ids: np.ndarray):
        """Hook: the open windows at arena ``rows`` (composites
        ``comp``, slots ``ids``) as ``(rows, comp, states)``, one a
        window, states in the host tier's form; here the folds'."""
        # bytewax: allow[BTX-DRAIN] — snapshots run with the pipeline drained (module docstring); the windower's .agg is its own slot table
        return rows, comp, self.agg.states_of(ids) if len(ids) else []

    def _windower_of(self, windowing, key: str, kid: Optional[int], opened: dict):
        """Hook: a key's ``(watermark base, system time at base,
        windower state)`` (us; a state class of the host tier's module
        ``windowing``), None where the key snapshots as a discard: here
        one with no open window, as the host tier discards an empty
        window logic."""
        if not opened:
            return None
        return (
            self.base_us[kid],
            self.sys_at_base[kid],
            windowing._SlidingWindowerState(opened=opened),
        )

    def _rows_of(self, keys: List[str]) -> np.ndarray:
        """Table rows of the given keys' open windows, in the order
        the windows were opened."""
        wanted = [
            kid for kid in map(self.key_ids.get, keys) if kid is not None
        ]
        return self.open.rows_of(np.unique(np.asarray(wanted, dtype=np.int64)))

    def demotion_snapshots(self):
        """Full-state drain for device→host demotion: host-format
        window snapshots for every key the tier knows
        (:meth:`_known_keys`); a key that snapshots as a discard
        drains as None, and the host tier rebuilds it on demand."""
        return self.snapshots_for(sorted(self._known_keys()))

    def _known_keys(self):
        """Hook: the keys a demotion drains; here those held."""
        return self.key_ids.keys()

    def _load_clock(self, kid: int, snap: Any) -> None:
        cs = snap.clock_state
        if cs is not None:
            self.base_us[kid] = _to_us(cs.watermark_base)
            self.sys_at_base[kid] = _to_us(cs.system_time_of_max_event)

    def _retime(self, kids: np.ndarray) -> None:
        """Due instants of the keys' open windows from the live clock
        (main thread, pipeline drained)."""
        self.open.retime(
            kids, self.base_us[kids], self.sys_at_base[kids], self._closes_of
        )

    def _replay_queue(self, kid: int, snap: Any) -> None:
        """A host-tier ordered=True logic keeps on-time values whose
        ts is still ahead of the watermark in ``queue``, to apply in
        timestamp order once due.  The device tier folds eagerly (its
        folds are commutative), so replay them into their windows now
        — the host never late-drops queued entries, so neither do we.
        Window closes happen on the next batch / notify via the
        restored watermark base."""
        queue = getattr(snap, "queue", None)
        if not queue:
            return
        ts_q = np.fromiter(
            (_to_us(ts) for _v, ts in queue),
            dtype=np.float64,
            count=len(queue),
        )
        if self.spec.kind == "count":
            vals_q = np.ones(len(queue), dtype=np.float64)
        else:
            vals_q = np.asarray([v for v, _ts in queue])
        self._absorb(
            np.full(len(queue), kid, dtype=np.int64), ts_q, vals_q
        )

    def load(self, key: str, snap: Any) -> None:
        """Resume from a host-tier ``_WindowSnapshot``."""
        self.load_many([(key, snap)])

    def load_many(self, items: List[Tuple[str, Any]]) -> None:
        """Batched resume: the per-key bookkeeping stays host Python,
        the fold states of the whole page install with ONE scatter
        per field (a device dispatch per window per field does not
        finish at 10^5 keys)."""
        # One id allocation for the page: the clock arrays grow once.
        with _flight.span("encode", rows=len(items)):
            kids = self._key_ids_for(
                [key for key, _snap in items]
            ).tolist()
        for kid, (_key, snap) in zip(kids, items):
            self._load_clock(kid, snap)
        self._load_windows(kids, items)
        # Queued values fold ON TOP of the installed states.
        for kid, (_key, snap) in zip(kids, items):
            self._replay_queue(kid, snap)
        self._retime(np.unique(np.asarray(kids, dtype=np.int64)))

    def _load_windows(
        self, kids: List[int], items: List[Tuple[str, Any]]
    ) -> None:
        """Reopen the page's windows (one batched open) and install
        their fold states on device."""
        comps, states = [], []
        for kid, (_key, snap) in zip(kids, items):
            folded = snap.logic_states
            for wid in snap.windower_state.opened:
                comps.append((kid << 32) + wid + _WID_BIAS)
                states.append(folded[wid])
        if not comps:
            return
        uniq, inverse = np.unique(
            np.asarray(comps, dtype=np.int64), return_inverse=True
        )
        self.agg.load_ids(self.open.ids_for(uniq, self.agg)[inverse], states)


class DeviceSessionAggState(DeviceWindowAggState):
    """Session windows on the device tier: key-local gap merges.

    A delivery's on-time rows are sorted by (key, timestamp) and cut
    into runs (consecutive timestamps of a key within ``gap``) with one
    vectorized diff; each run folds into ONE device slot through the
    same scatter-combine as sliding windows.  The open sessions are
    rows of :class:`_OpenSessions` (composite, slot id, due instant,
    open and close times), so placing a delivery's runs is array work:
    each run is matched against its key's open sessions, and a run
    that touches none opens a session, a run that touches one widens
    it.  Only the keys where a run bridges two sessions (a merge) are
    placed run by run in Python.  A merged session keeps the slots of
    the sessions it absorbed beside its own (``_extra``) and their ids
    (``_merged``); its accumulator is their combine, taken host-side
    at close or snapshot over a handful of scalars.

    Documented deviations from the host tier (cosmetic — the merged
    intervals, membership, and values are identical):

    - New session ids are assigned in timestamp order within each
      delivered batch; the host tier assigns in arrival order.
    - A merge's surviving id is the earliest-open pre-merge session;
      the host tier's can differ when a single value extends several
      sessions downward at once.

    How session ids stay unique: a key is let go with its last open
    session (:meth:`let_go`: its id, clock slot, vocabulary and
    encoder entries), as the tumbling/sliding tier lets a key go with
    its last window, one delivery later (a key that comes straight
    back keeps its id).  The host tier never discards
    a session logic (reused ids would give downstream joins wrong
    metadata), so what it would still hold of such a key, its clock
    and its next session id, stays behind in ``_retired`` by name:
    three numbers a key and nothing in the table, but the record grows
    by every key that goes and never returns (counter
    ``session_keys_remembered``; about 200 bytes of host memory a
    key), as the host tier's logics do.  A key that returns
    takes them up again, so it is judged late by the clock it had and
    its session ids go on from where they stopped.  The snapshot of a
    key let go is the host tier's snapshot of a session logic with no
    session (its clock and ``next_id``), so ids stay unique across a
    resume in either direction; a key resumed with no open session is
    let go again at the next delivery.

    Reference session semantics:
    ``/root/reference/pysrc/bytewax/operators/windowing.py:688-806``.
    """

    def __init__(self, spec: SessionAccelSpec):
        super().__init__(spec)
        self.open = _OpenSessions(spec.gap_us)
        #: Per key id, the next session id.  The placement (the
        #: dispatch lane) owns it; a key given an id on the main thread
        #: queues its first value in ``_wid_starts``, which the
        #: placement takes in before it reads the column.
        self._next_wid = np.zeros(0, dtype=np.int64)
        self._wid_starts: deque = deque()
        #: What the host tier would still hold of a key let go, by
        #: name: ``(watermark base, system time at base, next id)``.
        self._retired: Dict[str, Tuple[float, float, int]] = {}
        #: By composite, for merged sessions only: the ids they
        #: absorbed, and the slots besides their own that hold parts
        #: of their accumulator.
        self._merged: Dict[int, set] = {}
        self._extra: Dict[int, List[int]] = {}
        #: ``(delivery, key ids)`` found without an open session by
        #: the closes of that delivery, to let go at a later one's.
        self._parked: Tuple[int, np.ndarray] = (0, _NO_KIDS)

    def is_empty(self) -> bool:
        return super().is_empty() and not self._retired

    # -- keys ----------------------------------------------------------------

    def _start_keys(self, fresh: List[int]) -> None:
        """A key given an id starts at minus infinity with session id
        0, or where it stopped when it was let go."""
        super()._start_keys(fresh)
        starts = np.zeros(len(fresh), dtype=np.int64)
        retired = self._retired
        if retired:
            back = 0
            for i, kid in enumerate(fresh):
                held = retired.pop(self.keys[kid], None)
                if held is not None:
                    self.base_us[kid], self.sys_at_base[kid], starts[i] = held
                    back += 1
            _flight.RECORDER.count("session_keys_remembered", -back)
        self._wid_starts.append((np.asarray(fresh, dtype=np.int64), starts))

    def _take_wid_starts(self) -> None:
        """Take the queued first session ids into ``_next_wid``."""
        pending = self._wid_starts
        while pending:
            kids, starts = pending.popleft()
            self._next_wid = grow_column(self._next_wid, len(self.keys), 0)
            self._next_wid[kids] = starts

    def let_go(self, gone: Tuple[int, np.ndarray]) -> None:
        """Park the keys a close left without an open session; let go
        those parked by an earlier delivery's close that took in no
        on-time row since.  A key is held one delivery past its last
        session, so one that comes straight back keeps its id."""
        seq, kids = gone
        parked_seq, parked = self._parked
        if seq > parked_seq:
            super().let_go(self._parked)
            # A key found again has had late rows alone since: it went
            # just now.
            self._parked = (seq, np.setdiff1d(kids, parked))
        else:
            self._parked = (seq, np.union1d(parked, kids))

    def _left_behind(self, kids: np.ndarray, names: List[str]) -> None:
        _flight.RECORDER.count("session_keys_remembered", len(names))
        self._retired.update(
            zip(
                names,
                zip(
                    self.base_us[kids].tolist(),
                    self.sys_at_base[kids].tolist(),
                    self._next_wid[kids].tolist(),
                ),
            )
        )

    # -- hook overrides -----------------------------------------------------

    def _late_events(
        self, late_rows: np.ndarray, kids: np.ndarray, ts_us: np.ndarray, values
    ) -> List[Tuple[str, Tuple[int, Any]]]:
        # Session membership depends on other values, so a late value
        # can't name a specific session (host: late_for -> sentinel).
        from bytewax_tpu.operators.windowing import LATE_SESSION_ID

        # A key with late rows alone may be left with no session: the
        # delivery's close looks at these keys.
        self._late_kids = np.unique(kids[late_rows])
        return [
            (self.keys[int(kids[row])], (LATE_SESSION_ID, values[row]))
            for row in late_rows
        ]

    def _absorb(
        self, kids_ok: np.ndarray, ts_ok: np.ndarray, vals_ok: np.ndarray
    ) -> None:
        n = len(ts_ok)
        if not n:
            return
        self._take_wid_starts()
        with _flight.span("prep", rows=n):
            order = np.lexsort((ts_ok, kids_ok))
            k = kids_ok[order]
            t = ts_ok[order]
            v = np.asarray(vals_ok)[order]
            # Runs: maximal (key, ts-sorted) stretches with consecutive
            # gaps <= gap, in (key, time) order.
            new_run = np.empty(n, dtype=bool)
            new_run[0] = True
            np.logical_or(
                k[1:] != k[:-1],
                (t[1:] - t[:-1]) > self.spec.gap_us,
                out=new_run[1:],
            )
            run_of_row = np.cumsum(new_run) - 1
            starts = np.flatnonzero(new_run)
            ends = np.append(starts[1:], n) - 1
        with _flight.span("session_place", rows=len(starts)):
            slot_of_run = self._place(k[starts], t[starts], t[ends])
            _flight.RECORDER.record("device_dispatch", tier="session", rows=n)
            slots_rep = slot_of_run[run_of_row]
        self.agg.update_ids(slots_rep, v)

    # -- placement ------------------------------------------------------------

    def _place(
        self, r_kid: np.ndarray, r_lo: np.ndarray, r_hi: np.ndarray
    ) -> np.ndarray:
        """Create, widen and merge sessions for a delivery's runs (in
        (key, time) order); the slot each run folds into.  Two runs of
        a key lie more than ``gap`` apart, so a run touches a session
        that the delivery's other runs widened only where it touched
        it before: the runs are matched against the sessions as they
        stood, all at once."""
        table, gap = self.open, self.spec.gap_us
        n_runs = len(r_kid)
        uk, r_key = np.unique(r_kid, return_inverse=True)
        rows, of_key = table._of_keys(uk)
        # Every (run, open session of its key) pair.
        by_key = rows[np.argsort(of_key, kind="stable")]
        per_key = np.bincount(of_key, minlength=len(uk))
        n_of_run = per_key[r_key]
        ahead = np.cumsum(n_of_run) - n_of_run - (np.cumsum(per_key) - per_key)[r_key]
        pair_run = np.repeat(np.arange(n_runs), n_of_run)
        pair_row = by_key[np.arange(len(pair_run)) - np.repeat(ahead, n_of_run)]
        lo, hi = table.bounds(pair_row)
        touch = (r_hi[pair_run] >= lo - gap) & (r_lo[pair_run] <= hi + gap)
        pair_run, pair_row = pair_run[touch], pair_row[touch]
        touches = np.bincount(pair_run, minlength=n_runs)
        merging = np.isin(r_kid, r_kid[touches > 1])

        slot = np.empty(n_runs, dtype=np.int32)
        # A run that touches one session widens it.
        widen = ((touches == 1) & ~merging)[pair_run]
        runs, at = pair_run[widen], pair_row[widen]
        table.grow(at, r_lo[runs], r_hi[runs])
        slot[runs] = table.read(at)[1]
        # The keys where a run bridges two sessions, run by run.
        created, owner_of = (
            self._place_merging(np.flatnonzero(merging), r_kid, r_lo, r_hi)
            if merging.any()
            else ([], {})
        )
        # A run that touches none opens a session: ids in time order
        # a key.
        fresh = np.flatnonzero((touches == 0) & ~merging)
        kids = r_kid[fresh]
        head = np.flatnonzero(np.diff(kids, prepend=-1))
        count = np.diff(np.append(head, len(kids)))
        wids = self._next_wid[kids] + (np.arange(len(kids)) - np.repeat(head, count))
        self._next_wid[kids[head]] += count
        kid_of = np.append(kids, [kid for kid, _s in created]).astype(np.int64)
        wid_of = np.append(wids, [s[2] for _kid, s in created]).astype(np.int64)
        comp = (kid_of << 32) + (wid_of + _WID_BIAS)
        order = np.argsort(comp)
        opened = np.empty(len(comp), dtype=np.int32)
        opened[order] = table.open_sessions(
            comp[order],
            np.append(r_lo[fresh], [s[0] for _kid, s in created])[order],
            np.append(r_hi[fresh], [s[1] for _kid, s in created])[order],
            self.agg,
        )
        slot[fresh] = opened[: len(fresh)]
        for (_kid, s), id_ in zip(created, opened[len(fresh) :].tolist()):
            s[4] = id_
        for run, s in owner_of.items():
            while s[5] is not None:
                s = s[5]
            slot[run] = s[4]
        return slot

    def _place_merging(
        self, runs: np.ndarray, r_kid: np.ndarray, r_lo: np.ndarray, r_hi: np.ndarray
    ):
        """The runs of the keys where a run bridges two sessions, one
        after another in time order, as the host tier places values: a
        run that touches no session opens one, else the sessions it
        touches merge into the earliest open.  A session here is
        ``[open, close, wid, arena row or -1, slot id or -1, merged
        into]``.  Returns the sessions opened here that nothing merged
        away ``(key id, session)``, and each run's session."""
        table, gap = self.open, self.spec.gap_us
        created, owner_of, dead = [], {}, []
        for kid in np.unique(r_kid[runs]).tolist():
            rows = table.rows_of(np.array([kid], dtype=np.int64))
            lo, hi = table.bounds(rows)
            comp, ids = table.read(rows)
            sess = [
                [a, b, w, r, i, None]
                for a, b, w, r, i in zip(
                    lo.tolist(),
                    hi.tolist(),
                    ((comp & _WID_MASK) - _WID_BIAS).tolist(),
                    rows.tolist(),
                    ids.tolist(),
                )
            ]
            next_wid = int(self._next_wid[kid])
            for run in runs[r_kid[runs] == kid].tolist():
                a, b = float(r_lo[run]), float(r_hi[run])
                over = [s for s in sess if b >= s[0] - gap and a <= s[1] + gap]
                if not over:
                    s = [a, b, next_wid, -1, -1, None]
                    next_wid += 1
                    sess.append(s)
                    created.append((kid, s))
                else:
                    s = min(over, key=lambda x: x[0])
                    s[0], s[1] = min(s[0], a), max(s[1], b)
                    for other in over:
                        if other is not s:
                            sess.remove(other)
                            self._merge(kid, s, other, dead)
                owner_of[run] = s
            self._next_wid[kid] = next_wid
            kept = [s for s in sess if s[3] >= 0]
            table.set_bounds(
                np.array([s[3] for s in kept], dtype=np.int64),
                np.array([s[0] for s in kept]),
                np.array([s[1] for s in kept]),
            )
        table.remove(np.asarray(dead, dtype=np.int64))
        return [(kid, s) for kid, s in created if s[5] is None], owner_of

    def _merge(self, kid: int, keep: list, other: list, dead: List[int]) -> None:
        """Session ``other`` of key ``kid`` merges into ``keep``: its
        bounds, its id (the host records only the absorbed session's
        id; its own merged ids go), its slots and its row."""
        keep[0], keep[1] = min(keep[0], other[0]), max(keep[1], other[1])
        other[5] = keep
        into = (kid << 32) + keep[2] + _WID_BIAS
        gone = (kid << 32) + other[2] + _WID_BIAS
        self._merged.pop(gone, None)
        self._merged.setdefault(into, set()).add(other[2])
        parts = self._extra.pop(gone, [])
        if other[3] >= 0:
            parts.append(other[4])
            dead.append(other[3])
        if parts:
            self._extra.setdefault(into, []).extend(parts)
        _flight.RECORDER.count("session_merges")

    # -- close ----------------------------------------------------------------

    def _combine(self, snaps: List[Any]) -> Any:
        """Combine slot accumulators host-side (kind algebra over a
        handful of scalars)."""
        kind = self.spec.kind
        snaps = [s for s in snaps if s is not None]
        if not snaps:
            return None
        acc = snaps[0]
        for s in snaps[1:]:
            if kind in ("sum", "count"):
                acc = acc + s
            elif kind == "min":
                acc = min(acc, s)
            elif kind == "max":
                acc = max(acc, s)
            elif kind == "mean":
                acc = (acc[0] + s[0], acc[1] + s[1])
            else:  # stats
                acc = (
                    min(acc[0], s[0]),
                    max(acc[1], s[1]),
                    acc[2] + s[2],
                    acc[3] + s[3],
                )
        return acc

    def _parts(self, comp: np.ndarray, take: bool) -> Dict[int, List[int]]:
        """By position in ``comp``, the slots a merged session holds
        besides its own; ``take``: forget them."""
        parts: Dict[int, List[int]] = {}
        if self._extra:
            get = self._extra.pop if take else self._extra.get
            for i, c in enumerate(comp.tolist()):
                held = get(c, None)
                if held:
                    parts[i] = held
        return parts

    @staticmethod
    def _with_parts(ids: np.ndarray, parts: Dict[int, List[int]]) -> np.ndarray:
        """The sessions' own slots, then the parts' in order."""
        if not parts:
            return ids
        more = [s for held in parts.values() for s in held]
        return np.append(ids, np.asarray(more, dtype=ids.dtype))

    def _accs(self, ids: np.ndarray, parts: Dict[int, List[int]]) -> List[Any]:
        """Accumulators of the sessions with slots ``ids`` and
        ``parts`` (:meth:`_parts`), from ONE device fetch, a merged
        session's slots combined."""
        slots = self._with_parts(ids, parts)
        # bytewax: allow[BTX-DRAIN] — the session windower's .agg is its own slot table (never residency-wrapped), fetched inside the deferred device phase the pipeline worker owns, or with the pipeline drained
        states = list(self.agg.states_of(slots)) if len(slots) else []
        at = len(ids)
        for i, held in parts.items():
            states[i] = self._combine([states[i]] + states[at : at + len(held)])
            at += len(held)
        return states[: len(ids)]

    #: Two spans a close: the due scan, and the events with the keys
    #: left without a session (the slot table's read times itself).
    _CLOSE_SPANS = ("session_close", None, "session_close", "session_close")

    def _close_begins(self) -> bool:
        """Always: a key with late rows alone may be left with no
        session whatever is open (its first id, queued, comes in
        first)."""
        self._take_wid_starts()
        return True

    def _closing(self, due: np.ndarray, comp: np.ndarray, ids: np.ndarray):
        """A session is a row; a merged one gives back the slots of
        the sessions it absorbed too."""
        parts = self._parts(comp, take=True)
        return due, comp, self._with_parts(ids, parts), (comp, ids, parts)

    def _fetch_closed(self, held) -> List[Any]:
        _comp, ids, parts = held
        return self._accs(ids, parts)

    def _emit_closed(self, held, got, keys: List[str], wids: np.ndarray) -> List[Any]:
        """The base class's rows; the sessions' merge records go, their
        metadata built."""
        comp, ids, _parts = held
        if self._merged:
            for c in comp.tolist():
                self._merged.pop(c, None)
        _flight.RECORDER.count("session_closes", len(ids))
        return super()._emit_closed(held, got, keys, wids)

    def _bounds(self, rows: np.ndarray, comp: np.ndarray):
        """A session's bounds are stored, and it carries the ids of the
        sessions it absorbed."""
        lo, hi = self.open.bounds(rows)
        merged = self._merged
        return lo, hi, (set(merged.get(c, ())) for c in comp.tolist())

    # -- recovery -----------------------------------------------------------

    def _window_states(self, rows: np.ndarray, comp: np.ndarray, ids: np.ndarray):
        """A session's state is its parts' accumulators combined (the
        keys' next ids, queued, come in first)."""
        self._take_wid_starts()
        return rows, comp, self._accs(ids, self._parts(comp, take=False))

    def _windower_of(self, windowing, key: str, kid: Optional[int], opened: dict):
        """A held key's open sessions (none, too), a key let go as the
        host tier's logic with no session (clock and ``next_id``);
        None for a key never seen."""
        if kid is not None:
            base_us, sys_at_us, next_id = (
                self.base_us[kid], self.sys_at_base[kid], self._next_wid[kid]
            )  # fmt: skip
        else:
            held = self._retired.get(key)
            if held is None:
                return None
            base_us, sys_at_us, next_id = held
        return (
            base_us,
            sys_at_us,
            windowing._SessionWindowerState(
                next_id=int(next_id), sessions=opened, merge_queue=[]
            ),
        )

    def _known_keys(self):
        """Every key held and every key let go: the host tier keeps
        both."""
        return self.key_ids.keys() | self._retired.keys()

    def load_many(self, items: List[Tuple[str, Any]]) -> None:
        """The base class's resume; a key resumed with no open session
        is let go at once."""
        super().load_many(items)
        kids = np.unique(
            np.fromiter(
                (self.key_ids[key] for key, _snap in items),
                dtype=np.int64,
                count=len(items),
            )
        )
        self._seen[kids] = self._seq
        self.let_go((self._seq, self.open.without_window(kids)))

    def _load_windows(
        self, kids: List[int], items: List[Tuple[str, Any]]
    ) -> None:
        """Session variant: reopen the page's sessions from host-tier
        session ``_WindowSnapshot``s, one open and one install for the
        page."""
        self._take_wid_starts()
        comps, los, his, states = [], [], [], []
        for kid, (_key, snap) in zip(kids, items):
            st = snap.windower_state
            self._next_wid[kid] = st.next_id
            # A snapshot taken between a windower merge and the logic
            # merge has the sessions dict merged but logic states
            # still split per pre-merge id; resolve each state to its
            # surviving session (chasing chained merges).
            into = dict(st.merge_queue)
            parts: Dict[int, List[Any]] = {}
            for wid, state in snap.logic_states.items():
                target, seen = wid, set()
                while target in into and target not in seen:
                    seen.add(target)
                    target = into[target]
                parts.setdefault(target, []).append(state)
            for wid, meta in st.sessions.items():
                comp = (kid << 32) + wid + _WID_BIAS
                comps.append(comp)
                los.append(_to_us(meta.open_time))
                his.append(_to_us(meta.close_time))
                states.append(self._combine(parts.get(wid, [])))
                if meta.merged_ids:
                    self._merged[comp] = set(meta.merged_ids)
        if not comps:
            return
        comp = np.asarray(comps, dtype=np.int64)
        order = np.argsort(comp)
        ids = np.empty(len(comp), dtype=np.int32)
        ids[order] = self.open.open_sessions(
            comp[order], np.asarray(los)[order], np.asarray(his)[order], self.agg
        )
        held = [i for i, state in enumerate(states) if state is not None]
        self.agg.load_ids(ids[held], [states[i] for i in held])


# -- the windowed product join ----------------------------------------------


class JoinAccelSpec(WindowAccelSpec):
    """Flatten-time annotation: lower this ``join_window`` (product
    inserts, final emits) to the device tier."""

    def __init__(
        self,
        sides: int,
        ts_getter: Callable[[Any], datetime],
        align_to: datetime,
        length: timedelta,
        offset: timedelta,
        wait: timedelta,
    ):
        super().__init__("join", ts_getter, align_to, length, offset, wait)
        self.sides = sides

    def make_state(self) -> "DeviceJoinState":
        return DeviceJoinState(self)

    def __repr__(self) -> str:
        return f"JoinAccelSpec({self.sides} sides)"


class _JoinRows:
    """A delivery's join columns beside its keys and timestamps: each
    row's side and its value as 64 carrier bits."""

    __slots__ = ("side", "bits")

    def __init__(self, side: np.ndarray, bits: np.ndarray):
        self.side = side
        self.bits = bits

    def __getitem__(self, rows) -> "_JoinRows":
        return _JoinRows(self.side[rows], self.bits[rows])


class _JoinLate:
    """Late-value view of a columnar join delivery: row index → the
    ``(side, value)`` the host tier's tagging gives the row
    (``ArrayBatch.to_pylist``: the value a :class:`TsValue`)."""

    __slots__ = ("_side", "_values", "_ts")

    def __init__(self, side: np.ndarray, values: np.ndarray, ts_us: np.ndarray):
        self._side, self._values, self._ts = side, values, _LateTs(ts_us)

    def __getitem__(self, row: int):
        from bytewax_tpu.engine.arrays import TsValue

        return (int(self._side[row]), TsValue(self._values[row].item(), self._ts[row]))


#: A side's carrier: integers (and bools) as int64, floats as float64.
_CARRIERS = {"i": np.int64, "b": np.int64, "f": np.float64}

#: The most slots one close of the join takes before end of input (the
#: earliest due first; the rest stay due for the next).  A close's
#: output is Python an output row downstream, and while it runs no row
#: comes in: a backlog closed at once (a flood's first closes hold
#: millions of rows) would stall the input past the clock's wait, and
#: rows that came on time by the data would come late by the wall
#: clock.
_CLOSE_SLOTS = 1 << 16


class DeviceJoinState(DeviceWindowAggState):
    """``join_window`` with product inserts and final emits on the
    device tier: the tumbling/sliding tier's clock, keys, open-window
    table and due scan, over one slot a (key, window, side).

    A slot counts its rows in the slot table (the ``count`` fold), and
    its rows lie in one region of a row store on the device
    (:class:`~bytewax_tpu.ops.join.RowStore`): a row is its value
    alone, 64 carrier bits, an integer column exact whatever its
    width.  The composite of a slot is ``kid << 32 | wid * sides +
    side + 2**31``, so a window's sides are neighbours in the
    table's order and close together (they share a key clock and a
    close time).  A close expands its windows on the device
    (:func:`~bytewax_tpu.ops.join.join_expand`: each window's product
    of ``max(count, 1)`` over its sides, the prefix sum, the stored row
    of each side of each output row) and reads back only the output
    rows' values; a side with no row reads ``None``, as
    ``_SideTable.rows`` writes it.  The events are built from those
    columns with one ``tolist()`` a side.

    Snapshots are the host tier's (``_WindowSnapshot`` with a
    ``_SideTable`` a window), so a resume crosses tiers both ways.
    The tier takes columnar sides only (``ArrayBatch.is_keyed_ts``
    with the ``side`` column the join's tagging adds): the driver
    hands an itemized delivery to the host tier, state and all."""

    def __init__(self, spec: JoinAccelSpec):
        from bytewax_tpu.ops.join import RowStore

        super().__init__(spec)
        self.store = RowStore()
        #: Per side: its carrier (``_CARRIERS``), fixed by its first
        #: rows, and whether any of its integers needed 64 bits.
        self._carrier: List[Optional[str]] = [None] * spec.sides
        self._wide = [False] * spec.sides

    def _slot_table(self, spec):
        from bytewax_tpu.engine.xla import DeviceAggState

        # One device: the close reads the count field beside the
        # store on the device that holds both.
        return DeviceAggState("count")

    def _release_slots(self, ids: np.ndarray) -> None:
        self.agg.release_ids(ids)
        self.store.release(ids.astype(np.int64))

    # -- composites -----------------------------------------------------------

    def _windows_of(self, comp: np.ndarray) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """``(kids, wids, sides)`` of slot composites."""
        low = (comp & _WID_MASK) - _WID_BIAS
        wids = low // self.spec.sides
        return comp >> 32, wids, low - wids * self.spec.sides

    def _wids_of(self, comp: np.ndarray) -> np.ndarray:
        return ((comp & _WID_MASK) - _WID_BIAS) // self.spec.sides

    # -- values -----------------------------------------------------------------

    def _carried(self, side: np.ndarray, values: np.ndarray) -> np.ndarray:
        """The values as carrier bits (int64), each side in the
        carrier its first rows fixed; raises :class:`NonNumericValues`
        for a value the device tier cannot hold exactly, before any
        state is touched."""
        from bytewax_tpu.engine.xla import NonNumericValues

        if values.dtype == np.bool_:
            kind = "b"
        elif np.issubdtype(values.dtype, np.integer):
            kind = "i"
            if values.dtype == np.uint64 and len(values) and values.max() > np.iinfo(np.int64).max:
                raise NonNumericValues("the join's device tier holds integers of 64 bits")
        elif np.issubdtype(values.dtype, np.floating):
            kind = "f"
        else:
            msg = f"the join's device tier holds numbers, not {values.dtype}"
            raise NonNumericValues(msg)
        carriers = {}
        for s in np.unique(side).tolist():
            have = self._carrier[s] or kind
            if have != kind and not (have == "f" or {have, kind} == {"i", "b"}):
                msg = (
                    f"side {s} of the join took {have!r} values and now "
                    f"{kind!r}: the device tier keeps one carrier a side"
                )
                raise NonNumericValues(msg)
            carriers[s] = have
        bits = np.empty(len(values), dtype=np.int64)
        for s, have in carriers.items():
            mine = side == s if len(carriers) > 1 else slice(None)
            col = values[mine].astype(_CARRIERS[have])
            if have == "f":
                bits[mine] = col.view(np.int64)
            else:
                bits[mine] = col
                if len(col) and (
                    col.min() < np.iinfo(np.int32).min or col.max() > np.iinfo(np.int32).max
                ):
                    self._wide[s] = True
            self._carrier[s] = have
        return bits

    def _values_of(self, side: int, low: np.ndarray, high: Optional[np.ndarray]) -> np.ndarray:
        """A side's values back from their carrier words."""
        carrier = self._carrier[side]
        if high is None:
            return low.astype(bool) if carrier == "b" else low
        pair = np.empty((len(low), 2), dtype=np.int32)
        pair[:, 0] = low
        pair[:, 1] = high
        return pair.view(np.float64 if carrier == "f" else np.int64).ravel()

    def _fetches_high(self, side: int) -> bool:
        return self._carrier[side] == "f" or self._wide[side]

    # -- ingest -----------------------------------------------------------------

    def on_batch_columnar(self, batch):
        """A delivery of tagged columnar sides: ``key`` or ``key_id``,
        ``ts``, ``value`` and ``side``.  Returns ``(late_events,
        device_phase)`` — see :meth:`_ingest`."""
        if "side" not in batch.cols or "value" not in batch.cols:
            msg = "a columnar join delivery needs 'side' and 'value' columns"
            raise TypeError(msg)
        side = batch.numpy("side").astype(np.int64)
        values = batch.numpy("value")
        if batch.value_scale is not None:
            values = values * batch.value_scale
        bits = self._carried(side, values)
        kids = self._key_ids_of(batch)
        ts_us = _ts_us_of(batch)
        return self._ingest(
            kids, ts_us, _JoinLate(side, values, ts_us), fold_vals=_JoinRows(side, bits)
        )

    def on_batch_items(self, items: List[Any]):
        from bytewax_tpu.engine.xla import NonNumericValues

        raise NonNumericValues("the join's device tier takes columnar sides only")

    def _absorb(self, kids_ok: np.ndarray, ts_ok: np.ndarray, rows: _JoinRows) -> None:
        """Each on-time row into every window that holds it: its slot
        counts it, its region of the store keeps its value."""
        with _flight.span("prep", rows=len(kids_ok)):
            spec = self.spec
            hi = np.floor((ts_ok - spec.align_us) / spec.offset_us).astype(np.int64)
            if len(hi) and int(np.abs(hi).max()) * spec.sides >= (1 << 31) - self.expand * spec.sides:
                msg = (
                    "window ids exceed the composite encoding range; "
                    "move align_to closer to the event times or use a "
                    "larger window offset"
                )
                raise ValueError(msg)
            if self.expand == 1 and spec.offset_us == spec.length_us:
                kid_rep, wid_rep = kids_ok, hi
            else:
                e = np.arange(self.expand, dtype=np.int64)
                wids = hi[:, None] - e[None, :]
                in_window = ts_ok[:, None] < spec.align_us + wids * spec.offset_us + spec.length_us
                kid_rep = np.broadcast_to(kids_ok[:, None], wids.shape)[in_window]
                wid_rep = wids[in_window]
                rows = rows[np.nonzero(in_window)[0]]
            comp = (kid_rep << 32) + (wid_rep * spec.sides + rows.side + _WID_BIAS)
        self._place(comp, rows.bits)

    def _place(self, comp: np.ndarray, bits: np.ndarray) -> None:
        """Rows with slot composites ``comp``: their slots (opened where
        new), their places in the store in arrival order within a slot,
        the store written and the slots' counts folded."""
        n = len(comp)
        if not n:
            return
        with _flight.span("join_place", rows=n):
            order = np.argsort(comp, kind="stable")
            in_order = comp[order]
            head = np.empty(n, dtype=bool)
            head[0] = True
            np.not_equal(in_order[1:], in_order[:-1], out=head[1:])
            first = np.flatnonzero(head)
            group = np.cumsum(head) - 1
            slots = self.open.ids_for(in_order[first], self.agg)
            base = self.store.place(
                slots.astype(np.int64), np.diff(np.append(first, n))
            )
            pos = np.empty(n, dtype=np.int64)
            pos[order] = base[group] + (np.arange(n) - first[group])
            self.store.write(pos, bits.view(np.int32).reshape(n, 2).T)
            slot_of_row = np.empty(n, dtype=np.int32)
            slot_of_row[order] = slots[group]
            _flight.RECORDER.count("join_rows_stored", n)
            _flight.RECORDER.record("device_dispatch", tier="join", rows=n)
        self.agg.update_ids(slot_of_row, np.ones(n, dtype=np.float32))

    # -- close ------------------------------------------------------------------

    #: One span around the whole close.
    _CLOSE_SPANS = ("join_close",) * 4

    def _due(self, now_us: float) -> np.ndarray:
        """Before end of input, at most ``_CLOSE_SLOTS`` slots, the
        earliest due first."""
        return self.open.due(now_us, _CLOSE_SLOTS)

    def _closing(self, due: np.ndarray, comp: np.ndarray, ids: np.ndarray):
        """A window's slots (a side each) as one column of ``[sides,
        windows]`` arrays: their slots, the starts and counts of their
        regions in the store, and each window's output rows (the
        product of ``max(count, 1)`` over its sides); the window's
        first slot stands for it."""
        order = np.argsort(comp)
        comp, ids = comp[order], ids[order]
        kids, wids, sides = self._windows_of(comp)
        window = (kids << 32) + (wids + _WID_BIAS)
        head = np.empty(len(comp), dtype=bool)
        head[0] = True
        np.not_equal(window[1:], window[:-1], out=head[1:])
        at = np.cumsum(head) - 1
        n_windows = int(at[-1]) + 1
        slots = np.full((self.spec.sides, n_windows), -1, dtype=np.int64)
        slots[sides, at] = ids
        starts = np.zeros_like(slots)
        counts = np.zeros_like(slots)
        starts[sides, at], counts[sides, at] = self.store.regions(ids.astype(np.int64))
        sizes = np.maximum(counts, 1).prod(axis=0)
        return due[order[head]], comp[head], ids, (slots, starts, counts, sizes)

    def _fetch_closed(self, held) -> List[np.ndarray]:
        """Each side's values of the close's output rows, expanded on
        the device from the slot table's counts (``RowStore.expand``)."""
        slots, starts, _counts, sizes = held
        sides = self.spec.sides
        wide = tuple(s for s in range(sides) if self._fetches_high(s))
        self.agg._ensure_fields()
        low, high = self.store.expand(self.agg._fields["count"], slots, starts, sizes, wide)
        return [
            self._values_of(s, low[s], high[wide.index(s)] if s in wide else None)
            for s in range(sides)
        ]

    def _emit_closed(self, held, columns, keys: List[str], wids: np.ndarray) -> List[Any]:
        """A ``down`` row a combination, built from the columns with
        one ``tolist()`` a side; a side with no row reads ``None``."""
        _slots, _starts, counts, sizes = held
        _flight.RECORDER.count("join_rows_emitted", int(sizes.sum()))
        values = []
        for s, col in enumerate(columns):
            absent = counts[s] == 0
            if absent.any():
                col = col.astype(object)
                col[np.repeat(absent, sizes)] = None
            values.append(col.tolist())
        return list(
            zip(
                np.repeat(np.asarray(keys, dtype=object), sizes).tolist(),
                zip(np.repeat(wids, sizes).tolist(), zip(*values)),
            )
        )

    # -- recovery -----------------------------------------------------------------

    def _window_states(self, rows: np.ndarray, comp: np.ndarray, ids: np.ndarray):
        """A window's state is a ``_SideTable`` of every stored row, in
        arrival order a side; the window's first slot stands for it."""
        from bytewax_tpu.operators import _SideTable

        sides = self._windows_of(comp)[2]
        slot_ids = ids.astype(np.int64)
        low, high = self.store.read(slot_ids)
        lengths = self.store.regions(slot_ids)[1]
        side_of_row = np.repeat(sides, lengths)
        values = np.empty(len(low), dtype=object)
        for s in range(self.spec.sides):
            mine = side_of_row == s
            if mine.any():
                got = self._values_of(s, low[mine], high[mine] if self._fetches_high(s) else None)
                values[mine] = got.astype(object)
        tables: Dict[int, Any] = {}
        first = []
        at = 0
        for i, (window, side, n) in enumerate(
            zip((comp - sides).tolist(), sides.tolist(), lengths.tolist())
        ):
            table = tables.get(window)
            if table is None:
                table = tables[window] = _SideTable.empty(self.spec.sides)
                first.append(i)
            table.pools[side] = values[at : at + n].tolist()
            at += n
        first = np.asarray(first, dtype=np.int64)
        return rows[first], comp[first], list(tables.values())

    def _load_windows(self, kids: List[int], items: List[Tuple[str, Any]]) -> None:
        """Reopen the page's windows from host-tier ``_SideTable``s and
        put every pooled row in the store."""
        kid_rep, wid_rep, side_rep, values = [], [], [], []
        for kid, (_key, snap) in zip(kids, items):
            for wid, table in snap.logic_states.items():
                for side, pool in enumerate(table.pools):
                    kid_rep += [kid] * len(pool)
                    wid_rep += [wid] * len(pool)
                    side_rep += [side] * len(pool)
                    values += pool
        if not values:
            return
        side = np.asarray(side_rep, dtype=np.int64)
        bits = self._carried(side, np.asarray(values))
        comp = (np.asarray(kid_rep, dtype=np.int64) << 32) + (
            np.asarray(wid_rep, dtype=np.int64) * self.spec.sides + side + _WID_BIAS
        )
        self._place(comp, bits)

    def _replay_queue(self, kid: int, snap: Any) -> None:
        """A host-tier logic's queued ``((side, value), ts)`` entries
        (ordered mode keeps on-time values until the watermark passes
        them) go into their windows now."""
        queue = getattr(snap, "queue", None)
        if not queue:
            return
        ts_q = np.fromiter((_to_us(ts) for _v, ts in queue), dtype=np.float64, count=len(queue))
        side = np.asarray([v[0] for v, _ts in queue], dtype=np.int64)
        bits = self._carried(side, np.asarray([v[1] for v, _ts in queue]))
        self._absorb(np.full(len(queue), kid, dtype=np.int64), ts_q, _JoinRows(side, bits))
