"""Native C++ IO tests."""

import re

import numpy as np
import pytest

from bytewax_tpu import native


@pytest.fixture(scope="module")
def parser():
    if not native.is_available():
        pytest.skip("native toolchain unavailable")
    return native.BrcParser()


def test_brc_parse(parser):
    ids, temps = parser.parse(b"oslo;-3.5\nrome;18.2\noslo;0.0\n")
    assert temps.tolist() == [-35, 182, 0]
    vocab = parser.vocab()
    assert vocab[ids].tolist() == ["oslo", "rome", "oslo"]


def test_brc_vocab_stable_across_chunks(parser):
    ids1, _ = parser.parse(b"oslo;1.0\n")
    ids2, _ = parser.parse(b"oslo;2.0\n")
    assert ids1[0] == ids2[0]


def test_brc_malformed():
    if not native.is_available():
        pytest.skip("native toolchain unavailable")
    p = native.BrcParser()
    with pytest.raises(ValueError, match="malformed"):
        p.parse(b"oslo;abc\n")


def test_split_point(parser):
    assert parser.split_point(b"a;1.0\nb;2") == 6
    assert parser.split_point(b"no-newline") == 0


def _ref_parse(chunk: bytes, vocab: list):
    """The parser's rules in plain Python, as `brc_parse_chunk` had
    them before the word-at-a-time scan: a row is a name up to the
    first ``;`` (newlines and all), then a reading up to the next
    newline or the chunk's end: an optional ``-``, digits with every
    ``.`` skipped.  Any other byte, or no digit, refuses the chunk
    (``None``), the row's name already in the vocabulary.  Returns
    ``(ids, temps, fixed, general)``: readings in 1BRC's two shapes
    count as fixed."""
    ids, temps, fixed, general = [], [], 0, 0
    cur = 0
    while cur < len(chunk):
        semi = chunk.find(b";", cur)
        if semi < 0:
            break
        nl = chunk.find(b"\n", semi + 1)
        if nl < 0:
            nl = len(chunk)
        name = chunk[cur:semi]
        if name not in vocab:
            vocab.append(name)
        reading = chunk[semi + 1 : nl]
        digits = reading[1:] if reading[:1] == b"-" else reading
        digits = digits.replace(b".", b"")
        if not digits.isdigit():
            return None
        if re.fullmatch(rb"-?[0-9]{1,2}[.][0-9]", reading):
            fixed += 1
        else:
            general += 1
        value = int(digits)
        ids.append(vocab.index(name))
        temps.append(-value if reading[:1] == b"-" else value)
        cur = nl + 1
    return ids, temps, fixed, general


def _rows(names, readings=(b"12.3",), last_newline=True):
    body = b"\n".join(n + b";" + r for n in names for r in readings)
    return body + (b"\n" if last_newline else b"")


_TWINS = [b"abcdefgh-one-ijklmnop", b"abcdefgh-two-ijklmnop"]
_PARITY_CASES = {
    **{
        f"name_{n}_bytes": [_rows([b"n" * n, b"m" * n, b"n" * n])]
        for n in (1, 7, 8, 9, 15, 16, 17, 26, 100)
    },
    "names_utf8": [
        _rows(["Abéché".encode(), "Ürümqi".encode(), "札幌市".encode()] * 2)
    ],
    "names_equal_in_first_and_last_8": [_rows(_TWINS + _TWINS[::-1])],
    "names_one_a_prefix_of_the_other": [
        _rows([b"abcdefgh", b"abcdefghi", b"abcdefg", b"abcdefgh"])
    ],
    "name_empty_and_name_with_nul": [_rows([b"", b"a\x00", b"a", b"a\x00\x00"])],
    **{
        "reading_" + r.decode(): [_rows([b"Oslo", b"Abha"], [r])]
        for r in (b"0.0", b"-0.1", b"9.9", b"-99.9", b"5", b"12", b"123.4",
                  b"1.2.3", b"-5", b"1.", b".5", b"-.5", b"00.0", b"1234.5")
    },
    "no_last_newline_fixed": [_rows([b"Oslo", b"Rome"], last_newline=False)],
    "no_last_newline_general": [_rows([b"Oslo"], [b"12.3", b"7"], False)],
    "no_last_newline_long_name": [_rows([b"x" * 23], [b"-1.5"], False)],
    **{
        f"chunk_of_{n}_rows": [_rows([b"ab", b"c", b"ab"][:n], [b"-9.9"])]
        for n in (1, 2, 3)
    },
    "chunk_empty": [b""],
    "chunk_without_semicolon": [b"abc\n"],
    "chunk_ends_in_a_name": [b"Oslo;1.0\nRom"],
    "empty_line_joins_the_next_name": [b"Oslo;1.0\n\nRome;2.0\n"],
    "malformed_letters": [b"Oslo;1.0\nRome;abc\nOslo;2.0\n"],
    "malformed_empty_reading": [b"Oslo;\nRome;2.0\n"],
    "malformed_empty_reading_at_end": [b"Oslo;1.0\nRome;"],
    "malformed_sign_alone": [b"Oslo;-\n"],
    "malformed_dot_alone": [b"Oslo;.\n"],
    "malformed_crlf": [b"Oslo;1.0\r\nRome;2.0\r\n"],
    "malformed_sign_twice": [b"Oslo;--1.0\n"],
    "malformed_sign_inside": [b"Oslo;1-.0\n"],
    "malformed_byte_over_127": [b"Oslo;1\xc3\xa9.0\n"],
    "malformed_then_a_good_chunk": [b"Bonn;x\n", b"Rome;1.0\nBonn;2.0\n"],
    "vocabulary_over_three_calls": [
        _rows([b"a", b"b"]), _rows([b"c", b"a"]), _rows([b"b", b"d", b"c"])
    ],
    "ten_thousand_names_three_calls": [
        _rows([b"station-%d" % i for i in range(10_000)], [b"-12.3", b"4"])
    ] * 3,
}


@pytest.mark.parametrize("case", sorted(_PARITY_CASES))
def test_brc_parse_parity(case):
    """`BrcParser.parse` against the plain reference, chunk by chunk
    through one parser: ids, readings, row count, the vocabulary in
    first-sight order, refusals, and which way the readings went."""
    if not native.is_available():
        pytest.skip("native toolchain unavailable")
    from bytewax_tpu.engine import flight

    def counted():
        c = flight.RECORDER.counters
        return c.get("parse_rows_fixed", 0), c.get("parse_rows_general", 0)

    p = native.BrcParser()
    vocab: list = []
    for chunk in _PARITY_CASES[case]:
        want = _ref_parse(chunk, vocab)
        fixed0, general0 = counted()
        if want is None:
            with pytest.raises(ValueError, match="malformed"):
                p.parse(chunk)
            assert counted() == (fixed0, general0)
        else:
            # Once as `bytes`, once as the head of a longer reused
            # buffer: the same rows, the vocabulary unmoved by the
            # second pass.
            ids, temps = p.parse(chunk)
            assert ids.dtype == np.int32 and temps.dtype == np.int16
            assert ids.tolist() == want[0]
            assert temps.tolist() == want[1]
            fixed1, general1 = counted()
            assert (fixed1 - fixed0, general1 - general0) == want[2:]
            buf = bytearray(chunk + b"Nowhere;9.9\ntail")
            ids2, temps2 = p.parse(buf, len(chunk))
            assert ids2.tolist() == want[0] and temps2.tolist() == want[1]
        # (Through numpy on both sides: its strings drop trailing NULs.)
        want_vocab = np.array([n.decode() for n in vocab], dtype=str)
        assert p.vocab().tolist() == want_vocab.tolist()


def test_brc_parse_length_outside_buffer():
    if not native.is_available():
        pytest.skip("native toolchain unavailable")
    p = native.BrcParser()
    with pytest.raises(ValueError, match="outside"):
        p.parse(b"a;1.0\n", 7)
    assert p.split_point(bytearray(b"a;1.0\nb;2"), 6) == 6
    assert p.split_point(bytearray(b"a;1.0\nb;2"), 5) == 0


def _poll_all(part):
    """Every remaining batch of a partition as ``(names, readings)``
    rows."""
    rows = []
    while True:
        try:
            batch = part.next_batch()
        except StopIteration:
            return rows
        names = batch.key_vocab[batch.cols["key_id"]].tolist()
        rows.extend(zip(names, batch.cols["value"].tolist()))


def _measurements(tmp_path, n_rows, last_newline, seed=0):
    rng = np.random.RandomState(seed)
    rows = [
        (f"st{rng.randint(20)}", int(rng.randint(-999, 999)))
        for _ in range(n_rows)
    ]
    path = tmp_path / "measurements.txt"
    text = "\n".join(f"{k};{v / 10:.1f}" for k, v in rows)
    path.write_text(text + ("\n" if last_newline else ""))
    return path, rows


def test_brc_partition_reuses_its_buffer_not_its_columns(tmp_path):
    """Every chunk is parsed out of the source's one input buffer; a
    batch's columns are its own: later polls leave them as they were
    (the dispatch lane holds a delivery while the next poll parses)."""
    if not native.is_available():
        pytest.skip("native toolchain unavailable")
    from bytewax_tpu.models.brc import BrcFileSource

    path, rows = _measurements(tmp_path, 5000, last_newline=False)
    src = BrcFileSource(path, part_count=1, chunk_bytes=4096)
    part = src.build_part("inp", "range-0000", None)
    first = part.next_batch()
    inbuf = src._chunk_buffer.room(0)
    held = [first.cols["key_id"], first.cols["value"]]
    kept = [a.copy() for a in held]
    later = [part.next_batch(), part.next_batch()]
    assert src._chunk_buffer.room(0) is inbuf
    for arr, copy in zip(held, kept):
        assert np.array_equal(arr, copy)
        assert not np.shares_memory(arr, np.frombuffer(inbuf, dtype=np.uint8))
        for batch in later:
            for col in batch.cols.values():
                assert not np.shares_memory(arr, col)
    got = list(zip(first.key_vocab[held[0]].tolist(), held[1].tolist()))
    for batch in later:
        names = batch.key_vocab[batch.cols["key_id"]].tolist()
        got.extend(zip(names, batch.cols["value"].tolist()))
    assert got + _poll_all(part) == rows
    part.close()


def test_brc_partitions_polled_in_turn_share_one_buffer(tmp_path):
    """A source's partitions read into one buffer and each keeps its
    own carry: polled in turn, a poll each, every part serves the rows
    it serves alone."""
    if not native.is_available():
        pytest.skip("native toolchain unavailable")
    from bytewax_tpu.models.brc import BrcFileSource

    path, rows = _measurements(tmp_path, 4000, last_newline=True)
    src = BrcFileSource(path, part_count=3, chunk_bytes=4096)
    names = src.list_parts()
    alone = [_poll_all(src.build_part("inp", n, None)) for n in names]
    assert sum(alone, []) == rows
    parts = {n: src.build_part("inp", n, None) for n in names}
    got = {n: [] for n in names}
    while parts:
        for n in list(parts):
            try:
                batch = parts[n].next_batch()
            except StopIteration:
                parts.pop(n).close()
                continue
            keys = batch.key_vocab[batch.cols["key_id"]].tolist()
            got[n].extend(zip(keys, batch.cols["value"].tolist()))
    assert [got[n] for n in names] == alone


def test_brc_partition_snapshot_resumes_at_every_poll(tmp_path):
    """`snapshot()` after each poll is the byte offset of the first
    unconsumed byte: a fresh partition resumed from it yields exactly
    the rows the first has not yet served (3 parts, 4,096-byte chunks,
    a last line with no newline)."""
    if not native.is_available():
        pytest.skip("native toolchain unavailable")
    from bytewax_tpu.models.brc import BrcFileSource

    path, rows = _measurements(tmp_path, 3000, last_newline=False)
    data = path.read_bytes()
    src = BrcFileSource(path, part_count=3, chunk_bytes=4096)
    served = []
    for name in src.list_parts():
        part = src.build_part("inp", name, None)
        start = part.snapshot()
        mine = _poll_all(src.build_part("inp", name, None))
        done = 0
        while True:
            try:
                batch = part.next_batch()
            except StopIteration:
                break
            done += len(batch.cols["key_id"])
            offset = part.snapshot()
            # The offset lies on a line boundary of the file, the rows
            # served so far in front of it.
            assert data[start:offset].count(b"\n") + (
                offset == len(data)
            ) * (not data.endswith(b"\n")) == done
            resumed = src.build_part("inp", name, offset)
            assert _poll_all(resumed) == mine[done:]
            resumed.close()
        assert done == len(mine)
        served.extend(mine)
        part.close()
    assert served == rows


def test_brc_partition_carry_longer_than_a_read(tmp_path):
    """A line longer than a chunk (5,000 bytes against 4,096-byte
    reads) is carried until its newline arrives, the buffer growing to
    hold it; every row is served once and the long name comes back."""
    if not native.is_available():
        pytest.skip("native toolchain unavailable")
    from bytewax_tpu.models.brc import BrcFileSource

    long_name = "L" * 4990
    lines = [f"st{i % 7};{i % 100 / 10:.1f}" for i in range(600)]
    lines.insert(300, f"{long_name};-1.5")
    path = tmp_path / "measurements.txt"
    path.write_text("\n".join(lines) + "\n")
    src = BrcFileSource(path, part_count=1, chunk_bytes=4096)
    part = src.build_part("inp", "range-0000", None)
    got = _poll_all(part)
    part.close()
    want = [(k, round(float(v) * 10)) for k, v in (ln.split(";") for ln in lines)]
    assert got == want
    assert len(src._chunk_buffer.room(0)) >= 5000


def test_brc_file_source_end_to_end(tmp_path):
    if not native.is_available():
        pytest.skip("native toolchain unavailable")
    import bytewax_tpu.operators as op
    from bytewax_tpu import xla
    from bytewax_tpu.dataflow import Dataflow
    from bytewax_tpu.models.brc import BrcFileSource
    from bytewax_tpu.testing import TestingSink, run_main

    path = tmp_path / "measurements.txt"
    rng = np.random.RandomState(0)
    lines = []
    for _ in range(5000):
        station = f"st{rng.randint(20)}"
        temp = rng.randint(-999, 999) / 10
        lines.append(f"{station};{temp:.1f}")
    path.write_text("\n".join(lines) + "\n")

    out = []
    flow = Dataflow("brc_native")
    s = op.input(
        "inp", flow, BrcFileSource(path, part_count=3, chunk_bytes=4096)
    )
    stats = xla.stats_final("stats", s)
    op.output("out", stats, TestingSink(out))
    run_main(flow)

    # Oracle: plain Python aggregation over the same file.
    expect = {}
    for line in lines:
        k, v = line.split(";")
        v = float(v)
        mn, mx, tot, ct = expect.get(k, (float("inf"), float("-inf"), 0.0, 0))
        expect[k] = (min(mn, v), max(mx, v), tot + v, ct + 1)

    got = dict(out)
    assert set(got) == set(expect)
    for k, (mn, mx, tot, ct) in expect.items():
        gmn, gmean, gmx, gct = got[k]
        assert gct == ct, k
        assert abs(gmn - mn) < 1e-4 and abs(gmx - mx) < 1e-4
        assert abs(gmean - tot / ct) < 1e-3


def test_group_kv_fast_path():
    from bytewax_tpu.native import group_kv

    got = group_kv([("a", 1), ("b", 2), ("a", 3)])
    if got is None:
        pytest.skip("no toolchain for the host_ops extension")
    assert got == {"a": [1, 3], "b": [2]}
    # Non-tuple rows and non-str keys must raise so the driver falls
    # back to its permissive Python loop.
    with pytest.raises(TypeError):
        group_kv([("a", 1), ["b", 2]])
    with pytest.raises(TypeError):
        group_kv([(1, "a")])
    # Value identity is preserved (no copying).
    obj = object()
    assert group_kv([("k", obj)])["k"][0] is obj


def test_group_kv_matches_python_loop_in_dataflow(monkeypatch):
    # The host tier with the native grouping produces identical output
    # to a pure-Python run (grouping is forced off via a stub).
    import bytewax_tpu.engine.driver as drv
    import bytewax_tpu.operators as op
    from bytewax_tpu.dataflow import Dataflow
    from bytewax_tpu.testing import TestingSink, TestingSource, run_main

    inp = [(f"k{i % 7}", i) for i in range(500)]

    def build(out):
        flow = Dataflow("native_df")
        s = op.input("inp", flow, TestingSource(inp, batch_size=64))
        s = op.stateful_map(
            "sum", s, lambda st, v: ((st or 0) + v, (st or 0) + v)
        )
        op.output("out", s, TestingSink(out))
        return flow

    fast = []
    run_main(build(fast))
    monkeypatch.setattr(drv, "_native_group_kv", lambda items: None)
    slow = []
    run_main(build(slow))
    assert fast == slow


def test_kv_encode_basic():
    import numpy as np

    from bytewax_tpu.native import kv_encode

    items = [("a", 1), ("b", 2.5), ("a", 3)]
    iddict = {}
    ids = np.empty(3, dtype=np.int32)
    vals = np.empty(3, dtype=np.float64)
    res = kv_encode(items, iddict, ids, vals)
    if res is None:
        import pytest

        pytest.skip("no native toolchain")
    new_keys, all_int = res
    assert new_keys == ["a", "b"]
    assert all_int == 0  # 2.5 is a float
    assert iddict == {"a": 0, "b": 1}
    assert ids.tolist() == [0, 1, 0]
    assert vals.tolist() == [1.0, 2.5, 3.0]
    # Second batch: existing ids reused, only new keys reported.
    items2 = [("b", 4), ("c", 5)]
    ids2 = np.empty(2, dtype=np.int32)
    vals2 = np.empty(2, dtype=np.float64)
    new2, all_int2 = kv_encode(items2, iddict, ids2, vals2)
    assert new2 == ["c"]
    assert all_int2 == 1
    assert ids2.tolist() == [1, 2]


def test_kv_encode_rolls_back_on_error():
    import numpy as np
    import pytest

    from bytewax_tpu.native import kv_encode

    iddict = {"pre": 0}
    items = [("pre", 1), ("new1", 2), ("bad", "not-a-number")]
    ids = np.empty(3, dtype=np.int32)
    vals = np.empty(3, dtype=np.float64)
    try:
        res = kv_encode([], iddict, np.empty(0, np.int32), np.empty(0, np.float64))
    except TypeError:
        res = None
    if res is None:
        pytest.skip("no native toolchain")
    with pytest.raises(TypeError):
        kv_encode(items, iddict, ids, vals)
    # The keys added before the failure are rolled back.
    assert iddict == {"pre": 0}


def test_kv_encode_int64_exact_past_2_53():
    """Exact-int streams keep exact values beyond float64's 2^53
    integer range via the int64 lane (ADVICE r4: the float64
    round-trip silently rounded large counters/timestamps)."""
    import numpy as np

    from bytewax_tpu.native import kv_encode

    big = (1 << 53) + 1  # not representable in float64
    items = [("a", big), ("a", 1), ("b", 7)]
    ids = np.empty(3, dtype=np.int32)
    vals = np.empty(3, dtype=np.float64)
    ivals = np.empty(3, dtype=np.int64)
    res = kv_encode(items, {}, ids, vals, ivals)
    if res is None:
        import pytest

        pytest.skip("native toolchain unavailable")
    _new, all_int = res
    assert all_int
    assert ivals.tolist() == [big, 1, 7]
    assert int(vals[0]) != big  # the float lane rounds; the int lane is why


def test_kv_encode_int_overflow_falls_to_float():
    import numpy as np

    from bytewax_tpu.native import kv_encode

    over = 1 << 70
    items = [("a", over)]
    ids = np.empty(1, dtype=np.int32)
    vals = np.empty(1, dtype=np.float64)
    ivals = np.empty(1, dtype=np.int64)
    res = kv_encode(items, {}, ids, vals, ivals)
    if res is None:
        import pytest

        pytest.skip("native toolchain unavailable")
    _new, all_int = res
    assert not all_int
    assert vals[0] == float(over)
