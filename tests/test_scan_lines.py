"""A device scan's matched lines stay sorted int64 arrays from decode to
stitch: span-to-line expansion, the already-confirmed subtraction, the
offset routes' line lookup against the scan's own newline index, and the
one merge in ``stitch_lines``.  Every route must give the line numbers a
Python-set reference gives, and the per-scan counters must read what the
set-based decode read on the same input."""

from __future__ import annotations

import logging
import re

import numpy as np
import pytest

from distributed_grep_tpu.ops import engine as engine_mod
from distributed_grep_tpu.ops import lines as lines_mod
from distributed_grep_tpu.ops import sparse as sparse_mod
from distributed_grep_tpu.ops.engine import GrepEngine

SEG = 4096  # small segment_bytes: a multi-segment scan at test size
HAY = b"hay straw the a of dust".split()
STAT_KEYS = ("candidates", "end_offsets", "stitch_lines", "host_line_passes")


def filler(seed: int, n: int, words=HAY) -> list[bytes]:
    rng = np.random.default_rng(seed)
    return [b" ".join(words[i] for i in rng.integers(0, len(words),
                                                     int(rng.integers(1, 6))))
            for _ in range(n)]


def set_reference(regex: bytes, data: bytes) -> set[int]:
    """GNU grep's matched line numbers, one line at a time, as a set."""
    rx = re.compile(regex)
    lines = data.split(b"\n")
    if lines[-1] == b"":
        lines.pop()
    return {i for i, ln in enumerate(lines, 1) if rx.search(ln)}


# ------------------------------------------------------------ array helpers

def set_range_union(lo, hi) -> set[int]:
    out: set[int] = set()
    for a, b in zip(lo.tolist(), hi.tolist()):
        out.update(range(a, b + 1))
    return out


@pytest.mark.parametrize("seed", range(6))
def test_range_union_equals_set_expansion(seed):
    """Nondecreasing starts with overlaps, repeats, gaps, ranges inside
    earlier ones and empty ranges (lo > hi): the same numbers as the
    per-range set update, sorted."""
    rng = np.random.default_rng(seed)
    n = int(rng.integers(1, 400))
    lo = np.sort(rng.integers(1, 500, n))
    hi = lo + rng.integers(-2, 9, n)
    got = lines_mod.range_union(lo, hi)
    assert got.dtype == np.int64
    assert got.tolist() == sorted(set_range_union(lo, hi))


def test_range_union_empty():
    assert lines_mod.range_union(np.zeros(0, np.int64),
                                 np.zeros(0, np.int64)).size == 0
    assert lines_mod.range_union(np.array([5]), np.array([4])).size == 0


def test_stitch_merges_a_list_of_arrays():
    """Per-segment arrays in any order, a line two of them report, and
    empty parts: one sorted merge; an empty list stitches to nothing but
    the host verdict on the suspects."""
    data = b"needle\nhay\nneedle\nhay\nneedle\n"
    nl = lines_mod.newline_index(data)
    table = [GrepEngine("needle", backend="device").tables[0]]

    def matching(d, n, lns):
        return lines_mod.lines_matching(table, d, n, lns)

    parts = [np.array([5], np.int64), np.zeros(0, np.int64),
             np.array([1, 3], np.int64), np.array([3, 5], np.int64)]
    out = lines_mod.stitch_lines(parts, data, nl, [], matching)
    assert out.dtype == np.int64
    assert out.tolist() == sorted(set().union(*(p.tolist() for p in parts)))
    assert lines_mod.stitch_lines([], data, nl, [], matching).size == 0
    # line 2 holds a boundary: the host verdict (no match) is all it gets
    assert lines_mod.stitch_lines([], data, nl, [8], matching).size == 0
    assert lines_mod.stitch_lines([], data, nl, [14], matching).tolist() == [3]


@pytest.mark.parametrize("seed", range(3))
def test_word_decoder_equals_per_bit_reference(seed):
    """The packed-word decoder (FDR, NFA, approx and pairset planes)
    against the packing read one bit at a time: flat word index
    (w*S + s)*128 + l, lane (s//32)*4096 + (s%32)*128 + l, bit t the end
    offset lane*chunk + w*32 + t + 1, none past the real bytes."""
    from distributed_grep_tpu.ops import layout as layout_mod
    from distributed_grep_tpu.ops import pallas_scan

    lay = layout_mod.choose_layout(
        3_000_000, target_lanes=8192, min_chunk=512,
        lane_multiple=pallas_scan.LANES_PER_BLOCK, chunk_multiple=512,
        quantize_chunk=True,
    )
    rng = np.random.default_rng(seed)
    n_words = lay.padded // 32
    idx = np.sort(rng.choice(n_words, 3000, replace=False)).astype(np.int64)
    idx[-1] = n_words - 1  # the padded tail: offsets past n_real drop
    vals = (rng.integers(0, 2**32, idx.size, dtype=np.uint64)
            >> rng.integers(0, 33, idx.size).astype(np.uint64)).astype(np.uint32)
    vals[:3] = [0xFFFFFFFF, 1, 1 << 31]
    S = lay.lanes // 128
    want = set()
    for i, v in zip(idx.tolist(), vals.tolist()):
        w, rest = divmod(i, S * 128)
        s, l = divmod(rest, 128)
        lane = (s // 32) * 4096 + (s % 32) * 128 + l
        for t in range(32):
            off = lane * lay.chunk + w * 32 + t + 1
            if v >> t & 1 and off <= lay.n_real:
                want.add(off)
    got = sparse_mod.offsets_from_sparse_words(idx, vals, lay)
    assert got.dtype == np.int64
    assert got.tolist() == sorted(want)
    assert sparse_mod.offsets_from_sparse_words(
        idx[:0], vals[:0], lay).size == 0


# ------------------------------------------------- every route of the scan

def straddle_corpus(seed: int, hit: bytes, inject: dict[int, bytes]) -> bytes:
    """Filler lines with ``inject`` appended by line index, and a line that
    holds ``hit`` before and after the byte at offset SEG: the line
    straddles the first segment boundary and both segments report it."""
    head = filler(seed, 400)
    for i, text in inject.items():
        head[i] = head[i] + b" " + text
    data = b"\n".join(head) + b"\n"
    cut = data.rfind(b"\n", 0, SEG - len(hit) - 8) + 1
    line = hit + b" " + b"x" * (SEG - cut - len(hit) - 1) + b" y " + hit
    assert cut < SEG < cut + len(line)
    return data[:cut] + line + b"\n" + data[cut:]


ROUTES = [
    # (kind, pattern or literal set, regex for the reference, engine
    #  kwargs, the decoder the route calls)
    ("span_words", "needle", rb"needle", dict(interpret=True),
     "span_starts_from_sparse_words"),
    ("cand_words", "Carol foo$", rb"Carol foo$", dict(interpret=True),
     "offsets_from_sparse_words"),
    ("words", ["Alice", "Bob", "Erin"], rb"Alice|Bob|Erin",
     dict(interpret=True), "offsets_from_sparse_words"),
    ("lane_bytes", "needle", rb"needle", {},
     "offsets_from_sparse_lane_bytes"),
    ("bank_list", r"\w+\s+Carol\s+\w+", rb"\w+\s+Carol\s+\w+", {},
     "offsets_from_sparse_lane_bytes"),
]
HIT = b"Alice needle Carol foo"  # every route's pattern, '$' included
INJECT = {3: b"needle Bob", 77: b"Erin x Carol foo", 150: b"a Carol b",
          310: b"needle", 390: b"Carol foo"}


def engine_for(pattern, kwargs, segment_bytes):
    if isinstance(pattern, list):
        return GrepEngine(patterns=pattern, backend="device",
                          segment_bytes=segment_bytes, **kwargs)
    return GrepEngine(pattern, backend="device",
                      segment_bytes=segment_bytes, **kwargs)


@pytest.mark.parametrize("case", ["one_segment", "multi_segment", "no_match"])
@pytest.mark.parametrize("kind,pattern,regex,kwargs,decoder", ROUTES,
                         ids=[r[0] for r in ROUTES])
def test_route_lines_equal_set_reference(
    kind, pattern, regex, kwargs, decoder, case, monkeypatch
):
    called = []
    real = getattr(sparse_mod, decoder)

    def spy(*a, **k):
        called.append(decoder)
        return real(*a, **k)

    monkeypatch.setattr(sparse_mod, decoder, spy)
    if case == "no_match":
        data = b"\n".join(filler(11, 600)) + b"\n"
    else:
        data = straddle_corpus(5, HIT, INJECT)
    eng = engine_for(pattern, kwargs,
                     SEG if case == "multi_segment" else 1 << 20)
    res = eng.scan(data)
    assert called, f"the {kind} route did not run"
    if kind in ("lane_bytes", "bank_list"):
        assert eng.stats["kernel"] == "xla"
        assert eng.mode == ("shift_and" if kind == "lane_bytes" else "nfa")
    want = set_reference(regex, data)
    if case == "no_match":
        assert not want
    else:
        assert len(want) >= 3
    assert res.matched_lines.dtype == np.int64
    assert res.matched_lines.tolist() == sorted(want)
    assert res.n_matches == len(want)


def test_offset_routes_map_segment_offsets_through_the_scan_newlines():
    """A segment-local end offset o >= 1 names the same line through the
    scan's newline index (offset + segment start) as through the
    segment's own index plus the lines before the segment."""
    data = straddle_corpus(9, HIT, INJECT) * 3
    nl = lines_mod.newline_index(data)
    rng = np.random.default_rng(0)
    for seg_start in range(0, len(data), SEG):
        seg_len = min(SEG, len(data) - seg_start)
        offs = np.unique(rng.integers(1, seg_len + 1, 200)).astype(np.int64)
        seg_nl = lines_mod.newline_index(data[seg_start:seg_start + seg_len])
        base = int(np.searchsorted(nl, seg_start))
        want = lines_mod.unique_match_lines(offs, seg_nl) + base
        got = lines_mod.unique_match_lines(offs + seg_start, nl)
        assert got.tolist() == want.tolist()


# ------------------------------------------------------- per-scan counters

def dense_corpus() -> bytes:
    return b"\n".join(filler(2, 1500, HAY + [b"needle"] * 3)) + b"\n"


def sparse_corpus() -> bytes:
    lines = filler(3, 1500)
    for i in (3, 900):
        lines[i] += b" needle"
    return b"\n".join(lines) + b"\n"


def zebra_corpus() -> bytes:
    """'zebra''s rare-class device filter checks only z.b..: every other
    line starts 'zab', a false candidate; three lines truly match."""
    lines = filler(1, 1200)
    for i in range(0, 1200, 2):
        lines[i] = b"zab " + lines[i]
    for i in (7, 700, 1100):
        lines[i] = b"a zebra " + lines[i]
    return b"\n".join(lines) + b"\n"


def repeat_corpus() -> bytes:
    """x[ab]{2,40}y's relaxed NFA filter takes every 'x' + 60 'a' + 'y'
    line; the exact automaton takes two."""
    lines = filler(4, 900)
    for i in range(0, 900, 3):
        lines[i] = b"x" + b"a" * 60 + b"y"
    for i in (10, 500):
        lines[i] = b"xababy " + lines[i]
    return b"\n".join(lines) + b"\n"


# The counters the set-based decode read on these inputs, the match count,
# and whether a filter-defeat guard fired.  SPAN_CONFIRM_LINE_LIMIT is set
# at and one under the scan's candidate-line count: at it the lines take
# the host confirm batch (one more host pass); past it the native rescan,
# and with mostly false candidates the guard drops the filter.  (Across
# segments the guard's effect is timed by the dispatch loop, so the
# limit cases scan one segment.)
COUNTER_CASES = {
    # name: (pattern, corpus, segment_bytes, limit,
    #        (candidates, end_offsets, stitch_lines, host_line_passes),
    #        matches, guard fired)
    "dense": ("needle", dense_corpus, 1 << 20, None, (1448, 0, 43, 2),
              1000, False),
    "dense_segments": ("needle", dense_corpus, SEG, None, (1448, 0, 43, 7),
                       1000, False),
    "sparse": ("needle", sparse_corpus, 1 << 20, None, (6, 0, 34, 2), 2,
               False),
    "sparse_segments": ("needle", sparse_corpus, SEG, None, (6, 0, 34, 3), 2,
                        False),
    # 1180 candidate lines under the rare-class filter, 3 true
    "span_at_limit": ("zebra", zebra_corpus, 1 << 20, 1180,
                      (1180, 0, 32, 2), 3, False),
    "span_past_limit": ("zebra", zebra_corpus, 1 << 20, 1179,
                        (1180, 0, 32, 1), 3, True),
    # 268 candidate lines under the relaxed NFA filter, 2 true
    "nfa_at_limit": (r"x[ab]{2,40}y", repeat_corpus, 1 << 20, 268,
                     (268, 0, 50, 2), 2, False),
    "nfa_past_limit": (r"x[ab]{2,40}y", repeat_corpus, 1 << 20, 267,
                       (268, 0, 50, 1), 2, True),
    # the straddling line is reported by both segments: the second's
    # candidate lines leave out the one the first confirmed
    "fdr_segments": (["Alice", "Bob", "Erin"], None, SEG, None,
                     (6, 4, 9, 1), 3, False),
    "span_segments": ("needle", None, SEG, None, (12, 0, 9, 3), 3, False),
    "nfa_segments": ("Carol foo$", None, SEG, None, (4, 0, 9, 3), 3, False),
}


@pytest.mark.parametrize("name", sorted(COUNTER_CASES))
def test_scan_counters_read_as_before(name, monkeypatch):
    pattern, corpus, seg, limit, want, matches, guard = COUNTER_CASES[name]
    if limit is not None:
        monkeypatch.setattr(engine_mod, "SPAN_CONFIRM_LINE_LIMIT", limit)
    data = corpus() if corpus else straddle_corpus(5, HIT, INJECT)
    eng = engine_for(pattern, dict(interpret=True), seg)
    said: list[str] = []

    class Said(logging.Handler):  # the package logger does not propagate
        def emit(self, record):
            said.append(record.getMessage())

    handler = Said(logging.INFO)
    engine_mod.log.addHandler(handler)
    try:
        res = eng.scan(data)
    finally:
        engine_mod.log.removeHandler(handler)
    assert tuple(eng.stats[k] for k in STAT_KEYS) == want
    assert res.n_matches == matches
    assert any("mostly false on this corpus" in m for m in said) is guard
    if eng.mode == "nfa":
        assert eng.stats.get("nfa_filter_defeated", False) is guard
