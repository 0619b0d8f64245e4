"""Native-surface driver for the sanitizer builds (tests/test_sanitize.py).

Run as a SUBPROCESS with the sanitizer runtime LD_PRELOADed and
``DGREP_NATIVE_LIB`` pointing at ``libdgrep-asan.so`` / ``libdgrep-tsan.so``
(utils/native.py loads exactly that build, and raises instead of silently
degrading to the Python fallbacks).  Exercises every exported entry point
against independent pure-Python oracles — including the buffer-regrow
retry loops, the ignore_case fold, the short-pattern chain, and the
threaded paths (MT DFA scan, the confirm pool) — then a threaded stress
that shares one ConfirmSet / one DFA table across concurrent scans (the
race surface TSan watches; the library's scan entry points are read-only
by contract).

    python tests/_native_sanitize_driver.py surface   # full sweep
    python tests/_native_sanitize_driver.py stress    # threaded stress

Exit 0 = every check passed and no sanitizer report fired (the builds run
with halt-on-error, so a report is a nonzero exit).
"""

from __future__ import annotations

import os
import random
import sys
import threading

import numpy as np

from distributed_grep_tpu.utils import native


def check(cond: bool, what: str) -> None:
    if not cond:
        print(f"FAIL: {what}", file=sys.stderr)
        sys.exit(1)


def py_fnv32a(data: bytes) -> int:
    h = 2166136261
    for b in data:
        h ^= b
        h = (h * 16777619) & 0xFFFFFFFF
    return h & 0x7FFFFFFF


def py_dfa(data: bytes, table: np.ndarray, accept: np.ndarray,
           start: int = 0) -> list[int]:
    s = start
    out = []
    for i, b in enumerate(data):
        s = int(table[s, b])
        if accept[s]:
            out.append(i + 1)
    return out


def literal_dfa(needle: bytes) -> tuple[np.ndarray, np.ndarray]:
    """KMP-style literal DFA with the '\\n'-resets-to-start invariant."""
    m = len(needle)
    table = np.zeros((m + 1, 256), dtype=np.uint16)
    accept = np.zeros(m + 1, dtype=np.uint8)
    accept[m] = 1
    fail = [0] * (m + 1)
    for s in range(m + 1):
        for c in range(256):
            if s < m and c == needle[s]:
                table[s, c] = s + 1
            elif s == 0:
                table[s, c] = 0
            else:
                table[s, c] = table[fail[s], c]
        if s < m:
            fail[s + 1] = int(table[fail[s], needle[s]])
    table[:, 0x0A] = table[0, 0x0A]  # newline reset (the MT-scan contract)
    return table, accept


def py_trigram(blob: bytes, m: int) -> np.ndarray:
    """Pure-numpy oracle of dgrep_trigram_summary (the shard-index bloom:
    case-folded 24-bit trigram codes, one 64-bit Fibonacci mix, two bit
    probes from the low/high halves)."""
    bloom = np.zeros(m, dtype=np.uint8)
    if len(blob) < 3:
        return bloom
    fold = np.arange(256, dtype=np.uint8)
    fold[ord("A"):ord("Z") + 1] += 32
    f = fold[np.frombuffer(blob, np.uint8)].astype(np.uint64)
    v = (f[:-2] << np.uint64(16)) | (f[1:-1] << np.uint64(8)) | f[2:]
    h = v * np.uint64(0x9E3779B97F4A7C15)
    mask = np.uint64(m * 8 - 1)
    idx = np.unique(
        np.concatenate([h & mask, (h >> np.uint64(32)) & mask])
    )
    np.bitwise_or.at(
        bloom, (idx >> np.uint64(3)).astype(np.int64),
        np.uint8(1) << (idx & np.uint64(7)).astype(np.uint8),
    )
    return bloom


def surface() -> None:
    rng = random.Random(7)
    data = bytes(rng.choice(b"abcnedle\n") for _ in range(200_000))

    # --- fnv32a / partition (incl. non-UTF-8 surrogateescape keys) ---------
    for key in (b"", b"k", b"hello world", b"\xff\xfe\x00raw", "unié",
                "sur" + "\udcff"):
        kb = key.encode("utf-8", "surrogateescape") if isinstance(key, str) \
            else key
        check(native.fnv32a(key) == py_fnv32a(kb), f"fnv32a {key!r}")
        check(0 <= native.partition(key, 7) < 7, f"partition {key!r}")

    # --- newline_index -----------------------------------------------------
    nl = native.newline_index(data)
    expect = np.flatnonzero(np.frombuffer(data, np.uint8) == 0x0A)
    check(np.array_equal(nl, expect.astype(np.uint64)), "newline_index")
    check(native.newline_index(b"").size == 0, "newline_index empty")

    # --- literal_scan (overlaps + regrow: 'aa' in 'aaaa...' doubles) -------
    hay = b"aa" * 8000 + data
    ends = native.literal_scan(hay, b"aa")
    py_ends, start = [], 0
    while True:
        i = hay.find(b"aa", start)
        if i < 0:
            break
        py_ends.append(i + 2)
        start = i + 1
    check(ends.tolist() == py_ends, "literal_scan overlapping + regrow")
    check(native.literal_scan(hay, b"").size == 0, "literal_scan empty")
    check(native.literal_scan(b"ab", b"abc").size == 0, "needle > hay")

    # --- dfa_scan / dfa_scan_mt (forced threads; bit-identity) -------------
    table, accept = literal_dfa(b"nedle")
    offs, final = native.dfa_scan(data, table, accept)
    check(offs.tolist() == py_dfa(data, table, accept), "dfa_scan")
    check(0 <= final < table.shape[0], "dfa_scan final state")
    mt = native.dfa_scan_mt(data, table, accept, n_threads=4)
    check(mt.tolist() == offs.tolist(), "dfa_scan_mt == sequential")

    # --- dfa_lines: per-line verdicts, first/last/absent lines -------------
    nl64 = nl.astype(np.int64)
    n_lines = nl.size + (0 if data.endswith(b"\n") else 1)
    lns = np.array([1, 2, n_lines // 2, n_lines, n_lines + 5, 0], np.int64)
    eol = np.zeros_like(accept)
    hits = native.dfa_lines(data, nl64, lns, table, accept, eol)
    lines = data.split(b"\n")
    want = [0 < ln <= n_lines and b"nedle" in lines[ln - 1] for ln in lns]
    check(hits.tolist() == want, "dfa_lines")

    # --- ConfirmSet: folds, shorts, regrow-sized candidate sets ------------
    pats = [b"nedle", b"ab", b"z", b"needle", b"\xff\xferaw"]
    for ci in (False, True):
        norm = [p.lower() if ci else p for p in pats]
        cs = native.ConfirmSet(norm, ignore_case=ci)
        ref = native.ConfirmSet(norm, ignore_case=ci, use_native=False)
        cand = np.arange(0, len(data), 3, dtype=np.uint64)
        got = cs.confirm(data, cand, n_threads=4)
        want = ref.confirm(data, cand)
        check(np.array_equal(got, want), f"ConfirmSet ci={ci}")
        del cs, ref  # dgrep_confirm_free under the sanitizer

    # --- gather_ranges -----------------------------------------------------
    arr = np.frombuffer(data, np.uint8)
    starts = np.asarray([0, 10, 5, 199_990, 7, 7], dtype=np.int64)
    stops = np.asarray([5, 20, 5, 200_000, 6, 107], dtype=np.int64)
    lens = np.maximum(stops - starts, 0)
    offsets = np.zeros(len(lens) + 1, dtype=np.int64)
    np.cumsum(lens, out=offsets[1:])
    got_b = native.gather_ranges_native(arr, starts, stops, offsets,
                                        int(offsets[-1]))
    want_b = b"".join(data[a:b] for a, b in zip(starts.tolist(),
                                                stops.tolist()) if b > a)
    check(got_b == want_b, "gather_ranges")

    # --- format_batch (valid UTF-8 + the -2 refusal path) ------------------
    lines3 = [b"line one", b"line two!", b"\xc3\xa9 accents"]
    packed = b"".join(lines3)
    po = np.zeros(len(lines3) + 1, dtype=np.int64)
    np.cumsum([len(ln) for ln in lines3], out=po[1:])
    linenos = np.asarray([3, 11, 222], dtype=np.int64)
    prefix = "f\udcffile (line number #".encode("utf-8", "surrogateescape")
    got_f = native.format_batch(prefix, linenos, po, packed)
    want_f = b"".join(
        prefix + str(n).encode() + b")\t" + packed[po[i]:po[i + 1]] + b"\n"
        for i, n in enumerate(linenos.tolist())
    )
    check(got_f == want_f, "format_batch bytes")
    bad = native.format_batch(b"p (line number #", linenos[:1],
                              np.asarray([0, 2], dtype=np.int64), b"\xff\xff")
    check(bad is None, "format_batch refuses non-UTF-8 slab")

    # --- unique_lines / line_spans / build_records (record pipeline) -------
    nl_i = native.newline_index(data)
    ends_c = np.asarray(
        sorted(rng.sample(range(1, len(data) + 1), 4000)), dtype=np.int64
    )
    want_u = np.unique(
        np.searchsorted(nl_i.astype(np.int64), ends_c - 1, side="right") + 1
    )
    got_u = native.unique_lines_native(nl_i, ends_c)
    check(got_u is not None and np.array_equal(got_u, want_u), "unique_lines")
    check(native.unique_lines_native(nl_i, np.zeros(0, np.int64)).size == 0,
          "unique_lines empty")

    n_lines = int(nl_i.size) + (0 if data.endswith(b"\n") else 1)
    lns = np.asarray(
        sorted(rng.sample(range(1, n_lines + 1), 3000)), dtype=np.int64
    )
    sp = native.line_spans_native(nl_i, lns, len(data))
    check(sp is not None, "line_spans available")
    st, en = sp
    nl64 = nl_i.astype(np.int64)
    for i in (0, 1, len(lns) // 2, len(lns) - 1):
        ln = int(lns[i])
        w_s = 0 if ln == 1 else int(nl64[ln - 2]) + 1
        w_e = int(nl64[ln - 1]) if ln - 1 < nl64.size else len(data)
        check((int(st[i]), int(en[i])) == (w_s, w_e), f"line_spans ln={ln}")
    sp0 = native.line_spans_native(np.zeros(0, np.uint64),
                                   np.asarray([1], np.int64), 5)
    check(sp0 is not None and (int(sp0[0][0]), int(sp0[1][0])) == (0, 5),
          "line_spans no-newline chunk")

    arr_u8 = np.frombuffer(data, np.uint8)
    prefix = "f\udcffile (line number #".encode("utf-8", "surrogateescape")
    for n_reduce in (1, 7):
        parts = native.build_records(arr_u8, st, en, lns + 10**12,
                                     prefix, n_reduce)
        check(parts is not None, "build_records available")
        total = 0
        for p, (pl, po, slab) in parts.items():
            check(0 <= p < n_reduce, "build_records partition range")
            check(int(po[0]) == 0 and int(po[-1]) == len(slab),
                  "build_records offsets")
            total += int(pl.size)
            for j in range(min(5, int(pl.size))):
                key = prefix + str(int(pl[j])).encode() + b")"
                check(native.fnv32a(key) % n_reduce == p,
                      "build_records partition == fnv32a")
                line = slab[int(po[j]):int(po[j + 1])]
                ln = int(pl[j] - 10**12)
                w_s = 0 if ln == 1 else int(nl64[ln - 2]) + 1
                w_e = int(nl64[ln - 1]) if ln - 1 < nl64.size else len(data)
                check(line == data[w_s:w_e], "build_records slab bytes")
        check(total == lns.size, "build_records record count")
    check(native.build_records(
        arr_u8, np.asarray([0], np.int64),
        np.asarray([len(data) + 9], np.int64),
        np.asarray([1], np.int64), prefix, 4) is None,
        "build_records refuses out-of-bounds span")

    # --- trigram_summary (shard index: native == numpy oracle) -------------
    for blob in (b"", b"a", b"ab", b"abc", data[:100_000],
                 b"MiXeD CaSe needle\xff\xfe\n" * 50):
        for m in (1024, 16384):
            bloom = np.zeros(m, dtype=np.uint8)
            check(native.trigram_summary_into(blob, bloom),
                  "trigram_summary available")
            check(np.array_equal(bloom, py_trigram(blob, m)),
                  f"trigram_summary bits (len={len(blob)}, m={m})")

    # --- merge_display (k-way, codepoint path order, tie-break) ------------
    def rec(path: bytes, n: int, text: bytes) -> bytes:
        return path + b" (line number #" + str(n).encode() + b")\t" + text

    b1 = b"\n".join([rec(b"a.txt", 1, b"x"), rec(b"b.txt", 9, b"y")]) + b"\n"
    b2 = b"\n".join([rec(b"a.txt", 2, b"z"), rec(b"b.txt", 9, b"w")])  # no \n
    got_m = native.merge_display([b1, b2])
    want_m = (rec(b"a.txt", 1, b"x").replace(b"\t", b" ") + b"\n"
              + rec(b"a.txt", 2, b"z").replace(b"\t", b" ") + b"\n"
              + rec(b"b.txt", 9, b"y").replace(b"\t", b" ") + b"\n"
              + rec(b"b.txt", 9, b"w").replace(b"\t", b" ") + b"\n")
    check(got_m == want_m, "merge_display order + tab->space + final NL")
    # surrogateescape codepoint order: raw byte 0xFF sorts AFTER valid é
    b3 = rec(b"f\xff.t", 1, b"raw") + b"\n"
    b4 = rec(b"f\xc3\xa9.t", 1, b"acc") + b"\n"
    got_o = native.merge_display([b3, b4])
    check(got_o is not None and got_o.index(b"acc") < got_o.index(b"raw"),
          "merge_display surrogateescape codepoint order")
    check(native.merge_display([b"not a grep key\n"]) is None,
          "merge_display refuses non-grep-shaped")

    print("surface ok")


def stress() -> None:
    """Shared-state threaded stress: one DFA table + one ConfirmSet used
    by concurrent scans, plus each scan internally fanning out threads —
    the pthread surface TSan instruments."""
    rng = random.Random(11)
    data = bytes(rng.choice(b"xyneedle\n") for _ in range(400_000))
    table, accept = literal_dfa(b"needle")
    pats = [b"needle", b"ne", b"edle", b"x"]
    cs = native.ConfirmSet(pats)
    seq = native.dfa_scan_mt(data, table, accept, n_threads=1).tolist()
    cand = np.arange(0, len(data), 2, dtype=np.uint64)
    want_mask = cs.confirm(data, cand, n_threads=1)
    # shared inputs for the record-pipeline stress: concurrent worker
    # slots share one engine, so concurrent build_records over the SAME
    # data/nl arrays is the production shape (entries are read-only)
    nl_i = native.newline_index(data)
    n_lines = int(nl_i.size) + (0 if data.endswith(b"\n") else 1)
    lns = np.arange(1, n_lines + 1, 3, dtype=np.int64)
    sp = native.line_spans_native(nl_i, lns, len(data))
    arr_u8 = np.frombuffer(data, np.uint8)
    prefix = b"s (line number #"
    want_parts = native.build_records(arr_u8, sp[0], sp[1], lns, prefix, 5)
    errors: list[str] = []

    # trigram-summary stress inputs: concurrent builds over the SAME
    # shared corpus bytes into private blooms (the production shape —
    # worker threads summarize shared read-only buffers; the bloom each
    # writes is its own)
    want_tg = py_trigram(data, 4096)

    def pound(idx: int) -> None:
        for _ in range(6):
            got = native.dfa_scan_mt(data, table, accept, n_threads=4)
            if got.tolist() != seq:
                errors.append(f"thread {idx}: dfa_scan_mt diverged")
                return
            bloom = np.zeros(4096, dtype=np.uint8)
            if not native.trigram_summary_into(data, bloom) or \
                    not np.array_equal(bloom, want_tg):
                errors.append(f"thread {idx}: trigram_summary diverged")
                return
            mask = cs.confirm(data, cand, n_threads=4)
            if not np.array_equal(mask, want_mask):
                errors.append(f"thread {idx}: confirm diverged")
                return
            got_sp = native.line_spans_native(nl_i, lns, len(data))
            parts = native.build_records(
                arr_u8, got_sp[0], got_sp[1], lns, prefix, 5
            )
            if set(parts) != set(want_parts) or any(
                parts[p][2] != want_parts[p][2]
                or not np.array_equal(parts[p][0], want_parts[p][0])
                for p in parts
            ):
                errors.append(f"thread {idx}: build_records diverged")
                return

    threads = [threading.Thread(target=pound, args=(i,)) for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    check(not errors, "; ".join(errors) or "stress")
    print("stress ok")


if __name__ == "__main__":
    check(os.environ.get("DGREP_NATIVE_LIB", "") != "",
          "driver needs DGREP_NATIVE_LIB")
    check(native.native_available(), "native library failed to load")
    mode = sys.argv[1] if len(sys.argv) > 1 else "surface"
    {"surface": surface, "stress": stress}[mode]()
