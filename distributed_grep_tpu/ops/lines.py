"""Host-side line machinery: packed match bits -> line numbers, plus exact
stitching of lines that span stripe/segment boundaries.

The device scan starts every stripe from the start state.  By the
newline-reset property that is exact for every byte *after* the stripe's
first newline; the stripe's head partial line may have false negatives
(matches spanning the boundary) and — for '^'/'$' patterns — false
positives (the device treats the stripe start as a line start and the
stripe tail as a line end).  The fix is exact and local: every line that
contains a stripe boundary is re-matched on the host, and the host verdict
*replaces* the device verdict for that line.  The re-match is one native
DFA pass per bank over all the suspect lines (`lines_matching`, behind
the engine's `_host_lines_matching`), each line walked alone from the
start state — one call, not one per line.  Without libdgrep the pass
scans the suspect lines joined by '\\n', which is as exact: the tables
reset to the start state on every '\\n', '^' sees each line's start and
'$' the '\\n' after it.  This is the long-context analogue of carrying
block state in ring attention (SURVEY.md §5): instead of carrying, we
re-derive the tiny boundary-dependent region from its true line start.
"""

from __future__ import annotations

from typing import Callable, Iterable, Sequence

import numpy as np

from distributed_grep_tpu.models.dfa import DfaTable, reference_scan
from distributed_grep_tpu.ops.layout import Layout
from distributed_grep_tpu.utils import native

NL = 0x0A


def match_offsets_from_packed(packed: np.ndarray, layout: Layout) -> np.ndarray:
    """(chunk, lanes//8) packed bits -> sorted absolute end offsets (i+1),
    clamped to the real document length."""
    bits = np.unpackbits(packed, axis=1, bitorder="little")  # (chunk, lanes)
    c_idx, l_idx = np.nonzero(bits)
    offsets = l_idx.astype(np.int64) * layout.chunk + c_idx + 1
    offsets = offsets[offsets <= layout.n_real]
    offsets.sort()
    return offsets


def line_of_offsets(offsets: np.ndarray, nl_index: np.ndarray) -> np.ndarray:
    """1-based line number containing each match end offset (i+1 convention):
    the match's last byte is at offset-1."""
    return np.searchsorted(nl_index, offsets - 1, side="right") + 1


def unique_match_lines(offsets: np.ndarray, nl_index: np.ndarray) -> np.ndarray:
    """Sorted unique 1-based line numbers of SORTED match end offsets —
    ``np.unique(line_of_offsets(...))`` as one native linear merge when
    libdgrep is available (both arrays are sorted, so the searchsorted +
    sort-based-unique pair is two avoidable O(n log n) passes on the
    match-dense path; the fallback is bit-identical)."""
    if offsets.size == 0:
        return np.zeros(0, dtype=np.int64)
    out = native.unique_lines_native(nl_index, offsets)
    if out is not None:
        return out
    return np.unique(line_of_offsets(offsets, nl_index)).astype(np.int64)


def range_union(lo: np.ndarray, hi: np.ndarray) -> np.ndarray:
    """Sorted unique int64 numbers in the union of the closed ranges
    ``[lo[i], hi[i]]``, ``lo`` nondecreasing (a range with lo > hi is
    empty).  Each range contributes only the numbers past the furthest
    end before it, so the expansion comes out sorted and unique with no
    sort: one ``np.repeat`` + ``arange``."""
    lo = np.asarray(lo, dtype=np.int64)
    hi = np.asarray(hi, dtype=np.int64)
    if lo.size == 0:
        return np.zeros(0, dtype=np.int64)
    first = lo.copy()
    first[1:] = np.maximum(lo[1:], np.maximum.accumulate(hi)[:-1] + 1)
    n = np.maximum(hi - first + 1, 0)
    total = int(n.sum())
    # each output position minus the index its range starts at, plus the
    # range's first number
    return np.arange(total, dtype=np.int64) + np.repeat(first - (np.cumsum(n) - n), n)


def line_span(nl_index: np.ndarray, line_no: int, n_bytes: int) -> tuple[int, int]:
    """[start, end) byte range of 1-based line line_no (end excludes '\\n')."""
    start = 0 if line_no == 1 else int(nl_index[line_no - 2]) + 1
    end = int(nl_index[line_no - 1]) if line_no - 1 < len(nl_index) else n_bytes
    return start, end


def boundary_lines(
    boundaries: Iterable[int], nl_index: np.ndarray, n_bytes: int
) -> np.ndarray:
    """Sorted unique 1-based numbers of the lines containing any of the
    given byte positions (0 < p < n_bytes).  A position on the data's
    final '\\n' names no further line."""
    p = np.fromiter(boundaries, dtype=np.int64)
    p = p[(p > 0) & (p < n_bytes)]
    lns = np.unique(np.searchsorted(nl_index, p, side="right") + 1)
    ends_nl = nl_index.size > 0 and int(nl_index[-1]) == n_bytes - 1
    n_lines = nl_index.size + (0 if ends_nl else 1)
    return lns[lns <= n_lines].astype(np.int64)


def join_lines(data: bytes, nl_index: np.ndarray, line_nos: np.ndarray) -> bytes:
    """The given lines of ``data`` (sorted 1-based numbers), each followed
    by one '\\n' — line i of the result is line line_nos[i-1] verbatim."""
    from distributed_grep_tpu.runtime.columnar import gather_ranges, line_spans

    n = len(data)
    starts, ends = line_spans(line_nos, nl_index, n)
    # each range takes its line's own '\n'; only the last line of the
    # data can lack one, and sorted input puts it last
    joined, _ = gather_ranges(
        np.frombuffer(data, dtype=np.uint8), starts, np.minimum(ends + 1, n)
    )
    if int(ends[-1]) == n:
        joined += b"\n"
    return joined


def lines_matching(
    tables: list[DfaTable], data: bytes, nl_index: np.ndarray,
    line_nos: np.ndarray,
) -> np.ndarray:
    """The subset of ``line_nos`` (sorted, unique, 1-based) whose lines
    match any of the DFA ``tables``: one native call per table walks every
    line alone from the start state.  Without libdgrep, one scan per
    table over the lines joined by '\\n' (exact under the tables' newline
    reset), mapped back by position in the joined buffer."""
    line_nos = np.asarray(line_nos, dtype=np.int64)
    if line_nos.size == 0 or not tables:
        return np.zeros(0, dtype=np.int64)
    hit = np.zeros(line_nos.size, dtype=bool)
    joined = joined_nl = None
    for t in tables:
        h = native.dfa_lines(data, nl_index, line_nos, t.full_table(),
                             t.accept, t.accept_eol, t.start)
        if h is not None:
            hit |= h
            continue
        if joined is None:
            joined = join_lines(data, nl_index, line_nos)
            joined_nl = newline_index(joined)  # one '\n' per joined line
        offsets = reference_scan(t, joined)
        if offsets.size:
            idx = line_of_offsets(offsets, joined_nl) - 1
            hit[idx[idx < line_nos.size]] = True
    return line_nos[hit]


def stitch_lines(
    device_lines: np.ndarray | Sequence[np.ndarray],
    data: bytes,
    nl_index: np.ndarray,
    boundaries: Iterable[int],
    host_lines_matching: Callable[[bytes, np.ndarray, np.ndarray], np.ndarray],
) -> np.ndarray:
    """Sorted matched lines: the device's, with the host verdict replacing
    it on every line that contains a stripe/segment boundary.
    ``device_lines`` is one sorted, unique int64 array of 1-based line
    numbers or a list of them (one per segment and confirm batch, in any
    order; a line two of them report counts once).
    ``host_lines_matching(data, nl_index, suspects)`` returns the matching
    subset of the sorted suspect line numbers."""
    parts = [device_lines] if isinstance(device_lines, np.ndarray) \
        else list(device_lines)
    if len(parts) == 1:
        # one scan segment: nothing to merge.  Every numpy call here
        # hands the GIL to the other worker slot and waits to get it
        # back, so the merge is skipped where it would change nothing.
        dev = np.asarray(parts[0], dtype=np.int64)
    elif parts:
        dev = np.unique(np.concatenate(parts)).astype(np.int64, copy=False)
    else:
        dev = np.zeros(0, dtype=np.int64)
    suspects = boundary_lines(boundaries, nl_index, len(data))
    if not suspects.size:
        return dev
    kept = dev[~np.isin(dev, suspects, assume_unique=True)]
    hits = host_lines_matching(data, nl_index, suspects)
    return np.insert(kept, np.searchsorted(kept, hits), hits)


def newline_index(data: bytes) -> np.ndarray:
    """Byte offsets of every '\\n' (native fast path)."""
    return native.newline_index(data).astype(np.int64)


def empty_line_numbers(data: bytes, nl_index: np.ndarray | None = None) -> np.ndarray:
    """Sorted 1-based numbers of zero-length lines.

    A line is empty iff its '\\n' sits at the line's start offset —
    position 0 for line 1, or immediately after the previous '\\n'.  The
    fragment after the last '\\n' is a line only when non-empty
    (count_lines semantics), so it is never reported here.  Pass an
    already-computed ``newline_index(data)`` to skip the native pass."""
    nl = newline_index(data) if nl_index is None else nl_index
    if nl.size == 0:
        return np.zeros(0, dtype=np.int64)
    out = (np.nonzero(np.diff(nl) == 1)[0] + 2).astype(np.int64)
    if nl[0] == 0:
        out = np.concatenate([np.ones(1, np.int64), out])
    return out


def count_lines(data: bytes) -> int:
    """Line count with grep -n semantics: a trailing '\\n' closes the last
    line rather than opening an empty one; empty input has zero lines."""
    if not data:
        return 0
    return data.count(b"\n") + (0 if data.endswith(b"\n") else 1)
