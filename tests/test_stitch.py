"""The host re-match of a device scan's boundary and candidate lines: one
native pass per DFA bank over the batch of lines must give GNU grep's
per-line verdict on every line it is handed."""

import re

import numpy as np
import pytest

from distributed_grep_tpu.models.aho import compile_aho_corasick_banks
from distributed_grep_tpu.ops import lines as lines_mod
from distributed_grep_tpu.ops.engine import GrepEngine
from distributed_grep_tpu.utils import native, spans

WORDS = [b"foo", b"needle", b"Needle", b"NEEDLE", b"Smith", b"Alice", b"Bob",
         b"Carol", b"Dave", b"Erin", b"bar", b"x", b"", b"\t"]


def make_lines(seed: int, n: int = 300) -> list[bytes]:
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n):
        k = int(rng.integers(0, 6))
        out.append(b" ".join(WORDS[i] for i in rng.integers(0, len(WORDS), k)))
    # the edges: an empty first line, a bare "foo" last line
    out[0] = b""
    out[-1] = b"foo"
    return out


def pick_suspects(seed: int, n_lines: int) -> np.ndarray:
    rng = np.random.default_rng(seed + 1)
    pick = set(rng.choice(np.arange(1, n_lines + 1), n_lines // 4).tolist())
    return np.asarray(sorted(pick | {1, 2, n_lines}), dtype=np.int64)


@pytest.fixture(params=["native", "joined"])
def line_pass(request, monkeypatch):
    """Both routes of ops/lines.lines_matching: the native per-line walk,
    and the scan of the joined lines that serves without libdgrep."""
    if request.param == "joined":
        monkeypatch.setattr(native, "dfa_lines", lambda *a, **k: None)
    return request.param


def oracle(regex: bytes, flags: int, lines: list[bytes], suspects) -> list[int]:
    """GNU grep -E semantics, line by line (bytes re in the C locale)."""
    rx = re.compile(regex, flags)
    return [ln for ln in suspects.tolist() if rx.search(lines[ln - 1])]


CASES = [
    # (pattern, ignore_case, mode the engine routes to)
    ("needle", False, "shift_and"),
    ("needle", True, "shift_and"),
    ("Alice|Bob|Carol|Dave|Erin", False, "fdr"),
    (r"\w+\s+Smith\s+\w+", False, "nfa"),
    ("^foo", False, "nfa"),
    ("foo$", False, "nfa"),
    ("^$", False, "native"),
]


@pytest.mark.parametrize("seed", [3, 29])
@pytest.mark.parametrize("trailing_nl", [True, False])
@pytest.mark.parametrize("pattern,ignore_case,mode", CASES)
def test_batched_matcher_equals_per_line_oracle(
    pattern, ignore_case, mode, trailing_nl, seed, line_pass
):
    lines = make_lines(seed)
    data = b"\n".join(lines) + (b"\n" if trailing_nl else b"")
    nl = lines_mod.newline_index(data)
    suspects = pick_suspects(seed, len(lines))
    eng = GrepEngine(pattern, ignore_case=ignore_case, backend="device")
    assert eng.mode == mode and eng._host_lines_batched()
    got = eng._host_lines_matching(data, nl, suspects)
    flags = re.IGNORECASE if ignore_case else 0
    assert got.tolist() == oracle(pattern.encode(), flags, lines, suspects)
    assert eng._host_line_passes(suspects.size) == len(eng.tables)


@pytest.mark.parametrize("trailing_nl", [True, False])
def test_batched_matcher_multi_bank_aho_corasick(trailing_nl, line_pass):
    """A literal set split over several Aho-Corasick banks: the union of
    one pass per bank."""
    pats = [b"needle", b"Smith", b"Carol", b"o b", b"foo", b"Erin x"]
    tables = compile_aho_corasick_banks(pats, max_states_per_bank=12)
    assert len(tables) >= 3
    lines = make_lines(7)
    data = b"\n".join(lines) + (b"\n" if trailing_nl else b"")
    nl = lines_mod.newline_index(data)
    suspects = pick_suspects(7, len(lines))
    got = lines_mod.lines_matching(tables, data, nl, suspects)
    want = [ln for ln in suspects.tolist()
            if any(p in lines[ln - 1] for p in pats)]
    assert got.tolist() == want


def test_empty_boundary_line_matches_caret_dollar(line_pass):
    """'^$' on an empty suspect line: a scan of the line's bytes alone
    sees no byte and reports nothing; the batched re-match reports the
    match grep prints (the scan's nullable-'$' fix-up used to restore
    it)."""
    from distributed_grep_tpu.models.dfa import compile_dfa, reference_scan

    table = compile_dfa("^$")
    assert reference_scan(table, b"").size == 0
    data = b"a\n\nb\n\n"
    nl = lines_mod.newline_index(data)
    got = lines_mod.lines_matching([table], data, nl, np.arange(1, 5))
    assert got.tolist() == [2, 4]


def test_boundary_lines_and_stitch_replace_the_device_verdict():
    """Suspect lines come from one vectorised lookup (a position on the
    final '\\n' names no further line); the host verdict replaces the
    device's on them and nowhere else."""
    data = b"ab\nneedle\ncd\nxneedle\n"
    nl = lines_mod.newline_index(data)
    # positions 4 (line 2), 11 (line 3), 12 and 13 (line 4), 20 (final '\n')
    sus = lines_mod.boundary_lines([0, 4, 11, 12, 13, 20, 21], nl, len(data))
    assert sus.tolist() == [2, 3, 4]
    table = [GrepEngine("needle", backend="device").tables[0]]
    # device: a false positive on suspect 3, a miss on suspect 4, and a
    # false positive on line 1, which is no suspect and so stays
    out = lines_mod.stitch_lines(
        np.array([1, 3]), data, nl, [4, 11, 13],
        lambda d, n, lns: lines_mod.lines_matching(table, d, n, lns),
    )
    assert out.dtype == np.int64 and out.tolist() == [1, 2, 4]


def test_per_line_path_only_without_tables():
    """The approx model keeps the per-line matcher: one pass a line."""
    eng = GrepEngine("needle", max_errors=1, backend="device")
    assert not eng._host_lines_batched()
    data = b"neddle\nhay\nnoodle\nneedle\n"
    nl = lines_mod.newline_index(data)
    got = eng._host_lines_matching(data, nl, np.arange(1, 5))
    assert got.tolist() == [1, 4]
    assert eng._host_line_passes(4) == 4


def _scan_args(eng, data):
    buf = spans.SpanBuffer()
    with spans.task_context(buf, job="j", worker=0, task=1, attempt="a",
                            kind="map"):
        res = eng.scan(data)
    recs = [r for r in buf.drain() if r["name"] == f"scan:{eng.mode}"]
    assert len(recs) == 1
    return res, recs[0]["args"]


def test_device_scan_span_carries_stitch_counters():
    """A device-route scan reports its boundary lines and host passes on
    its scan:<mode> span: with no candidates to confirm, the stitch's one
    pass per bank is all; with candidates, one more per confirm batch —
    never one per line."""
    eng = GrepEngine("needle", backend="device", interpret=True)
    hay = b"hay and straw\nmore hay\n" * 400
    res, args = _scan_args(eng, hay)
    assert res.n_matches == 0
    assert 0 < args["stitch_lines"] < hay.count(b"\n")
    assert args["host_line_passes"] == len(eng.tables) == 1

    data = b"hay\nneedle here\nhay\n" * 200
    res, args = _scan_args(eng, data)
    assert res.n_matches == 200
    assert args["stitch_lines"] > 0
    assert len(eng.tables) < args["host_line_passes"] < args["stitch_lines"]


def test_explain_sums_stitch_counters_per_mode():
    """`dgrep explain`'s engine_modes rows sum the counters over a job's
    scans; a mode whose scans carry none (host scanners) shows none."""
    from distributed_grep_tpu.runtime import explain as explain_mod

    def scan(mode, **args):
        return {"t": "span", "name": f"scan:{mode}", "dur": 0.1,
                "args": {"bytes": 10, "kernel": "pallas", **args}}

    agg = explain_mod.summarize_events([
        scan("nfa", stitch_lines=4095, host_line_passes=1),
        scan("nfa", stitch_lines=4095, host_line_passes=3),
        scan("native"),
    ])
    assert agg["modes"]["nfa"]["stitch_lines"] == 8190
    assert agg["modes"]["nfa"]["host_line_passes"] == 4
    assert "stitch_lines" not in agg["modes"]["native"]
