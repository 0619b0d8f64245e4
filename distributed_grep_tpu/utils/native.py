"""ctypes bindings to libdgrep (native/dgrep.cpp), with Python fallbacks.

Builds the shared library on demand via ``make -C native`` when a compiler
is available; otherwise every entry point degrades to a pure-Python/numpy
implementation with identical semantics, so the framework never hard-depends
on the toolchain.  The FNV-32a hash matches the reference's ``ihash``
(map_reduce/worker.go:13-17) bit-for-bit — intermediate partition layout is
therefore compatible across the native and fallback paths.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
from pathlib import Path

import numpy as np

_NATIVE_DIR = Path(__file__).resolve().parents[2] / "native"
_LIB_PATH = _NATIVE_DIR / "libdgrep.so"
# Build key of the library on disk (gitignored, written after each build):
# a hash of the sources and of the host's compiler target.  A library
# copied from another host — or built from other sources — does not match
# and is rebuilt before it is loaded: -march=native code from a different
# CPU can die with SIGILL, and file mtimes say nothing across a copy.
_KEY_PATH = _NATIVE_DIR / "libdgrep.so.key"

_lib: ctypes.CDLL | None = None
_load_attempted = False


def _build_key() -> str:
    """sha256 over native/dgrep.cpp, native/Makefile, the machine, the
    compiler's target triple and what -march=native resolves to here."""
    import hashlib
    import platform

    h = hashlib.sha256()
    for p in (_NATIVE_DIR / "dgrep.cpp", _NATIVE_DIR / "Makefile"):
        h.update(p.read_bytes())
    h.update(platform.machine().encode())
    cxx = os.environ.get("CXX", "g++")
    for args in (["-dumpmachine"], ["-march=native", "-Q", "--help=target"]):
        try:
            out = subprocess.run([cxx, *args], capture_output=True,
                                 timeout=30).stdout
        except (subprocess.SubprocessError, OSError):
            out = b"no compiler"
        h.update(out)
    return h.hexdigest()


def _build_if_stale() -> bool:
    """Rebuild libdgrep.so unless its key matches this host and sources;
    True when a library matching the key is on disk.  Serialized across
    processes by a lock file (parallel test workers load concurrently)."""
    import fcntl

    key = _build_key()
    with open(_NATIVE_DIR / ".build.lock", "w") as lock:
        fcntl.flock(lock, fcntl.LOCK_EX)
        try:
            have = _KEY_PATH.read_text().strip()
        except OSError:
            have = ""
        if have == key and _LIB_PATH.exists():
            return True
        try:
            # -B: the library on disk was built for another key
            subprocess.run(["make", "-B", "-C", str(_NATIVE_DIR), "libdgrep.so"],
                           check=True, capture_output=True, timeout=300)
        except (subprocess.SubprocessError, OSError):
            return False
        _KEY_PATH.write_text(key + "\n")
        return True


def _try_load() -> ctypes.CDLL | None:
    global _lib, _load_attempted
    if _load_attempted:
        return _lib
    override = os.environ.get("DGREP_NATIVE_LIB")
    if override:
        # Explicit build selection (sanitizer builds: libdgrep-asan.so /
        # libdgrep-tsan.so from `make -C native sanitize|tsan`).  No make,
        # no key check — and a load failure RAISES, on this call and
        # every later one (_load_attempted stays False): a test that asked
        # for the ASan build must never silently run the Python fallbacks.
        lib = ctypes.CDLL(override)
        _bind(lib)
        _lib = lib
        _load_attempted = True
        return _lib
    _load_attempted = True
    if not _build_if_stale():
        return None  # no toolchain: the Python fallbacks serve
    lib = ctypes.CDLL(str(_LIB_PATH))  # a library built here must load
    _bind(lib)
    _lib = lib
    return _lib


def _bind(lib: ctypes.CDLL) -> None:
    lib.dgrep_fnv32a.restype = ctypes.c_uint32
    lib.dgrep_fnv32a.argtypes = [ctypes.c_char_p, ctypes.c_size_t]
    lib.dgrep_newline_index.restype = ctypes.c_size_t
    lib.dgrep_newline_index.argtypes = [
        ctypes.c_char_p,
        ctypes.c_size_t,
        ctypes.POINTER(ctypes.c_uint64),
        ctypes.c_size_t,
    ]
    lib.dgrep_literal_scan.restype = ctypes.c_size_t
    lib.dgrep_literal_scan.argtypes = [
        ctypes.c_char_p,
        ctypes.c_size_t,
        ctypes.c_char_p,
        ctypes.c_size_t,
        ctypes.POINTER(ctypes.c_uint64),
        ctypes.c_size_t,
    ]
    lib.dgrep_dfa_scan.restype = ctypes.c_size_t
    lib.dgrep_dfa_scan.argtypes = [
        ctypes.c_char_p,
        ctypes.c_size_t,
        ctypes.POINTER(ctypes.c_uint16),
        ctypes.c_char_p,
        ctypes.c_uint32,
        ctypes.POINTER(ctypes.c_uint64),
        ctypes.c_size_t,
        ctypes.POINTER(ctypes.c_uint32),
    ]
    if hasattr(lib, "dgrep_dfa_lines"):
        lib.dgrep_dfa_lines.restype = None
        lib.dgrep_dfa_lines.argtypes = [
            ctypes.c_char_p,
            ctypes.c_int64,
            ctypes.POINTER(ctypes.c_int64),
            ctypes.c_int64,
            ctypes.POINTER(ctypes.c_int64),
            ctypes.c_int64,
            ctypes.POINTER(ctypes.c_uint16),
            ctypes.c_char_p,
            ctypes.c_char_p,
            ctypes.c_uint32,
            ctypes.POINTER(ctypes.c_uint8),
        ]
    if hasattr(lib, "dgrep_dfa_scan_mt"):
        lib.dgrep_dfa_scan_mt.restype = ctypes.c_size_t
        lib.dgrep_dfa_scan_mt.argtypes = [
            ctypes.c_char_p,
            ctypes.c_size_t,
            ctypes.POINTER(ctypes.c_uint16),
            ctypes.c_char_p,
            ctypes.c_uint32,
            ctypes.POINTER(ctypes.c_uint64),
            ctypes.c_size_t,
            ctypes.c_uint32,
        ]
    if hasattr(lib, "dgrep_gather_ranges"):
        lib.dgrep_gather_ranges.restype = None
        lib.dgrep_gather_ranges.argtypes = [
            ctypes.c_char_p,
            ctypes.POINTER(ctypes.c_int64),
            ctypes.POINTER(ctypes.c_int64),
            ctypes.c_size_t,
            ctypes.POINTER(ctypes.c_uint8),
        ]
        lib.dgrep_format_batch.restype = ctypes.c_int64
        lib.dgrep_format_batch.argtypes = [
            ctypes.c_char_p,
            ctypes.c_size_t,
            ctypes.POINTER(ctypes.c_int64),
            ctypes.POINTER(ctypes.c_int64),
            ctypes.c_char_p,
            ctypes.c_size_t,
            ctypes.c_uint8,
            ctypes.POINTER(ctypes.c_uint8),
            ctypes.c_size_t,
        ]
        lib.dgrep_merge_display.restype = ctypes.c_int64
        lib.dgrep_merge_display.argtypes = [
            ctypes.c_char_p,
            ctypes.POINTER(ctypes.c_int64),
            ctypes.c_int32,
            ctypes.POINTER(ctypes.c_uint8),
        ]
    if hasattr(lib, "dgrep_build_records"):
        lib.dgrep_unique_lines.restype = ctypes.c_int64
        lib.dgrep_unique_lines.argtypes = [
            ctypes.POINTER(ctypes.c_uint64),
            ctypes.c_int64,
            ctypes.POINTER(ctypes.c_int64),
            ctypes.c_int64,
            ctypes.POINTER(ctypes.c_int64),
        ]
        lib.dgrep_line_spans.restype = None
        lib.dgrep_line_spans.argtypes = [
            ctypes.POINTER(ctypes.c_uint64),
            ctypes.c_int64,
            ctypes.POINTER(ctypes.c_int64),
            ctypes.c_int64,
            ctypes.c_int64,
            ctypes.POINTER(ctypes.c_int64),
            ctypes.POINTER(ctypes.c_int64),
        ]
        lib.dgrep_build_records.restype = ctypes.c_int64
        lib.dgrep_build_records.argtypes = [
            ctypes.c_char_p,
            ctypes.c_int64,
            ctypes.POINTER(ctypes.c_int64),
            ctypes.POINTER(ctypes.c_int64),
            ctypes.POINTER(ctypes.c_int64),
            ctypes.c_int64,
            ctypes.c_char_p,
            ctypes.c_int64,
            ctypes.c_int32,
            ctypes.POINTER(ctypes.c_int64),
            ctypes.POINTER(ctypes.c_int64),
            ctypes.POINTER(ctypes.c_uint8),
            ctypes.POINTER(ctypes.c_int64),
            ctypes.POINTER(ctypes.c_int64),
        ]
    if hasattr(lib, "dgrep_confirm_build"):
        lib.dgrep_confirm_build.restype = ctypes.c_void_p
        lib.dgrep_confirm_build.argtypes = [
            ctypes.c_char_p,
            ctypes.POINTER(ctypes.c_uint32),
            ctypes.c_uint32,
            ctypes.c_int,
        ]
        lib.dgrep_confirm_free.restype = None
        lib.dgrep_confirm_free.argtypes = [ctypes.c_void_p]
        lib.dgrep_confirm_scan.restype = None
        lib.dgrep_confirm_scan.argtypes = [
            ctypes.c_void_p,
            ctypes.c_char_p,
            ctypes.c_size_t,
            ctypes.POINTER(ctypes.c_uint64),
            ctypes.c_size_t,
            ctypes.POINTER(ctypes.c_uint8),
            ctypes.c_uint32,
        ]
    if hasattr(lib, "dgrep_trigram_summary"):
        lib.dgrep_trigram_summary.restype = None
        lib.dgrep_trigram_summary.argtypes = [
            ctypes.c_char_p,
            ctypes.c_size_t,
            ctypes.POINTER(ctypes.c_uint8),
            ctypes.c_size_t,
        ]


def native_available() -> bool:
    return _try_load() is not None


# --- FNV-32a partition hash (reference ihash, worker.go:13-17) -------------

def fnv32a(key: str | bytes) -> int:
    # surrogateescape: keys embed filenames whose non-UTF8 bytes arrive as
    # lone surrogates — hash the original bytes, don't crash
    data = key.encode("utf-8", "surrogateescape") if isinstance(key, str) else key
    lib = _try_load()
    if lib is not None:
        return lib.dgrep_fnv32a(data, len(data))
    h = 2166136261
    for b in data:
        h ^= b
        h = (h * 16777619) & 0xFFFFFFFF
    return h & 0x7FFFFFFF


def partition(key: str | bytes, n_reduce: int) -> int:
    """ihash(key) % nReduce — the shuffle partitioning (worker.go:89)."""
    return fnv32a(key) % n_reduce


# --- Newline index ---------------------------------------------------------

def newline_index(data: bytes) -> np.ndarray:
    """Byte offsets of every newline, as uint64."""
    lib = _try_load()
    if lib is None:
        return np.flatnonzero(np.frombuffer(data, dtype=np.uint8) == 0x0A).astype(np.uint64)
    cap = max(1024, len(data) // 16)
    while True:
        # np.empty, not a ctypes array: (c_uint64 * cap)() ZEROES the
        # buffer — measured as the wrapper's single biggest cost on a
        # dense 64 MB input (25 ms of memset vs a 30 ms AVX2 scan)
        buf = np.empty(cap, dtype=np.uint64)
        n = lib.dgrep_newline_index(
            data, len(data),
            buf.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)), cap,
        )
        if n <= cap:
            return buf[:n].copy()
        cap = n


# --- Literal scan (CPU engine / oracle) ------------------------------------

def literal_scan(haystack: bytes, needle: bytes) -> np.ndarray:
    """End offsets (last byte + 1) of all (overlapping) occurrences."""
    if not needle:
        return np.zeros(0, dtype=np.uint64)
    lib = _try_load()
    if lib is None:
        out = []
        start = 0
        while True:
            i = haystack.find(needle, start)
            if i < 0:
                break
            out.append(i + len(needle))
            start = i + 1
        return np.asarray(out, dtype=np.uint64)
    # size the first buffer off the data (one match per ~64 bytes): a
    # match-dense corpus must not pay a SECOND full scan just to learn
    # the count (the old fixed 4096 cap re-ran the whole 64 MB receipt)
    cap = max(4096, len(haystack) >> 6)
    while True:
        buf = np.empty(cap, dtype=np.uint64)
        n = lib.dgrep_literal_scan(
            haystack, len(haystack), needle, len(needle),
            buf.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)), cap,
        )
        if n <= cap:
            return buf[:n].copy()
        cap = n


# --- DFA scan (CPU engine / oracle for the Pallas kernel) ------------------

def dfa_scan(
    data: bytes,
    table: np.ndarray,  # [n_states, 256] uint16 (or int) next-state table
    accept: np.ndarray,  # [n_states] bool/uint8
    start_state: int = 0,
) -> tuple[np.ndarray, int]:
    """Feed every byte through the DFA; return (accept end-offsets, final state)."""
    table = np.ascontiguousarray(table, dtype=np.uint16)
    accept_u8 = np.ascontiguousarray(accept, dtype=np.uint8)
    lib = _try_load()
    if lib is None:
        tbl = table
        s = start_state
        out = []
        for i, b in enumerate(data):
            s = int(tbl[s, b])
            if accept_u8[s]:
                out.append(i + 1)
        return np.asarray(out, dtype=np.uint64), s
    final = ctypes.c_uint32(0)
    cap = 4096
    while True:
        buf = (ctypes.c_uint64 * cap)()
        n = lib.dgrep_dfa_scan(
            data,
            len(data),
            table.ctypes.data_as(ctypes.POINTER(ctypes.c_uint16)),
            accept_u8.tobytes(),
            start_state,
            buf,
            cap,
            ctypes.byref(final),
        )
        if n <= cap:
            return np.ctypeslib.as_array(buf)[:n].copy(), int(final.value)
        cap = n


def dfa_lines(
    data: bytes,
    nl: np.ndarray,  # [n] int64 '\n' offsets of data, ascending
    linenos: np.ndarray,  # 1-based lines to match
    table: np.ndarray,  # [n_states, 256] uint16 next-state table
    accept: np.ndarray,  # [n_states] bool/uint8
    accept_eol: np.ndarray,  # [n_states] bool/uint8: match iff at line end
    start_state: int = 0,
) -> np.ndarray | None:
    """Per-line match verdicts (bool per entry of ``linenos``): each line
    walked alone from ``start_state`` in one native call, or None when
    libdgrep is unavailable (caller: ops/lines.lines_matching's joined
    scan)."""
    lib = _try_load()
    if lib is None or not hasattr(lib, "dgrep_dfa_lines"):
        return None
    nl = np.ascontiguousarray(nl, dtype=np.int64)
    linenos = np.ascontiguousarray(linenos, dtype=np.int64)
    table = np.ascontiguousarray(table, dtype=np.uint16)
    hit = np.empty(linenos.size, dtype=np.uint8)
    if not isinstance(data, bytes):  # a bytearray or memoryview
        data = np.frombuffer(data, dtype=np.uint8).ctypes.data_as(ctypes.c_char_p)
    lib.dgrep_dfa_lines(
        data, len(data),
        nl.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)), nl.size,
        linenos.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)), linenos.size,
        table.ctypes.data_as(ctypes.POINTER(ctypes.c_uint16)),
        np.ascontiguousarray(accept, dtype=np.uint8).tobytes(),
        np.ascontiguousarray(accept_eol, dtype=np.uint8).tobytes(),
        start_state,
        hit.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
    )
    return hit.view(bool)


# --- Literal-set candidate confirm (FDR filter path, models/fdr.py) --------

class ConfirmSet:
    """Batch-confirm FDR candidate end-offsets against a literal set.

    Native path: an L1-resident bloom bitmap rejects absent last-4-byte
    keys, survivors take a hash-table probe + full memcmp
    (native/dgrep.cpp dgrep_confirm_*, ~4 ns/candidate on random offsets,
    ~8.6 ns on FDR-biased candidates) — the cost that lets the FDR tuner run a cheaper device
    filter and accept more candidates (models/fdr.py
    CONFIRM_PS_PER_CANDIDATE).  Fallback: a dict keyed the same way.

    ``patterns`` must be pre-normalized (lowercased when ignore_case);
    ``ignore_case`` controls folding of the *data* bytes at probe time.
    """

    def __init__(self, patterns: list[bytes], ignore_case: bool = False,
                 use_native: bool = True):
        self.ignore_case = bool(ignore_case)
        self._patterns = [bytes(p) for p in patterns]
        self._handle = None
        lib = _try_load() if use_native else None
        self._free = None
        if lib is not None and hasattr(lib, "dgrep_confirm_build"):
            blob = b"".join(self._patterns)
            offs = np.zeros(len(self._patterns) + 1, dtype=np.uint32)
            np.cumsum([len(p) for p in self._patterns], out=offs[1:])
            self._offs = offs  # keep alive
            self._blob = blob
            self._handle = lib.dgrep_confirm_build(
                blob,
                offs.ctypes.data_as(ctypes.POINTER(ctypes.c_uint32)),
                len(self._patterns),
                1 if ignore_case else 0,
            )
            self._free = lib.dgrep_confirm_free  # bound now: survives shutdown
        if self._handle is None:
            by_key: dict[bytes, list[bytes]] = {}
            shorts: list[bytes] = []
            for p in self._patterns:
                if len(p) < 4:
                    shorts.append(p)
                else:
                    by_key.setdefault(p[-4:], []).append(p)
            self._by_key, self._shorts = by_key, shorts

    def __del__(self):
        if getattr(self, "_handle", None) and getattr(self, "_free", None):
            self._free(self._handle)
            self._handle = None

    def confirm(self, data: bytes, ends: np.ndarray,
                n_threads: int | None = None) -> np.ndarray:
        """Boolean mask: does some pattern truly end at each offset?"""
        ends = np.ascontiguousarray(ends, dtype=np.uint64)
        if ends.size == 0:
            return np.zeros(0, dtype=bool)
        if self._handle is not None:
            lib = _try_load()
            out = np.zeros(ends.size, dtype=np.uint8)
            lib.dgrep_confirm_scan(
                self._handle,
                data,
                len(data),
                ends.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
                ends.size,
                out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
                n_threads if n_threads is not None else min(8, os.cpu_count() or 1),
            )
            return out.astype(bool)
        hay = data.lower() if self.ignore_case else data
        out_b = np.zeros(ends.size, dtype=bool)
        for i, e in enumerate(ends.tolist()):
            if e > len(hay) or e == 0:
                continue
            hit = False
            for p in self._by_key.get(hay[max(0, e - 4):e], ()):
                if e >= len(p) and hay[e - len(p):e] == p:
                    hit = True
                    break
            if not hit:
                for p in self._shorts:
                    if e >= len(p) and hay[e - len(p):e] == p:
                        hit = True
                        break
            out_b[i] = hit
        return out_b


# --- Columnar merge/print hot loops (round 6) ------------------------------

def gather_ranges_native(
    arr: np.ndarray, starts: np.ndarray, ends: np.ndarray,
    offsets: np.ndarray, total: int,
) -> bytes | None:
    """Native arr[starts[i]:ends[i]] concatenation (the LineBatch slab
    rebuild), or None when libdgrep is unavailable — the caller
    (runtime/columnar.gather_ranges) keeps the numpy fallback.  ``offsets``
    /``total`` are the caller's cumsum (it needs them anyway)."""
    lib = _try_load()
    if lib is None or not hasattr(lib, "dgrep_gather_ranges"):
        return None
    if arr.dtype != np.uint8 or arr.ndim != 1:
        return None  # starts/ends are ELEMENT indices; C indexes bytes
    starts = np.ascontiguousarray(starts, dtype=np.int64)
    ends = np.ascontiguousarray(ends, dtype=np.int64)
    if not arr.flags.c_contiguous:
        arr = np.ascontiguousarray(arr)
    out = np.empty(int(total), dtype=np.uint8)
    lib.dgrep_gather_ranges(
        arr.ctypes.data_as(ctypes.c_char_p),
        starts.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        ends.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        starts.size,
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
    )
    return out.tobytes()


def merge_display_available() -> bool:
    """True when the native display merge exists — callers check BEFORE
    materializing file contents, so a no-native install doesn't read the
    whole output set just to learn it must stream instead."""
    lib = _try_load()
    return lib is not None and hasattr(lib, "dgrep_merge_display")


def format_batch(
    prefix: bytes, linenos: np.ndarray, offsets: np.ndarray, slab: bytes,
    sep: bytes = b"\t",
) -> bytes | None:
    """The mr-out text form of one LineBatch as BYTES —
    ``b"<prefix><N>)<sep><line>\\n"`` per record, byte-identical to
    ``LineBatch.format_lines`` encoded utf-8/surrogateescape.  None when
    libdgrep is unavailable OR the slab is not strictly valid UTF-8 (the
    Python path's utf-8/replace decode is then not the identity; caller
    falls back)."""
    lib = _try_load()
    if lib is None or not hasattr(lib, "dgrep_format_batch"):
        return None
    if len(sep) != 1:
        return None  # C writes exactly one sep byte; fall back otherwise
    n = int(linenos.size)
    if n == 0:
        return b""
    linenos = np.ascontiguousarray(linenos, dtype=np.int64)
    offsets = np.ascontiguousarray(offsets, dtype=np.int64)
    cap = n * (len(prefix) + 23) + len(slab)
    out = np.empty(cap, dtype=np.uint8)
    wrote = lib.dgrep_format_batch(
        prefix,
        len(prefix),
        linenos.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        offsets.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        slab,
        n,
        sep[0],
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        cap,
    )
    if wrote < 0:
        return None  # -2: slab needs utf-8/replace; -1: cannot happen (cap)
    return out[:wrote].tobytes()


def merge_display(bufs: list[bytes]) -> bytes | None:
    """K-way merge of pre-sorted mr-out buffers into final display bytes
    (first '\\t' -> ' ' per record, '\\n'-terminated), ordered by
    (path, line) with paths compared as Python str (surrogateescape
    codepoints) and ties broken by buffer order — byte-identical to
    ``JobResult.iter_display_bytes_sorted``.  None when libdgrep is
    unavailable or any line is not grep-key-shaped (caller falls back)."""
    lib = _try_load()
    if lib is None or not hasattr(lib, "dgrep_merge_display"):
        return None
    data = b"".join(bufs)
    off = np.zeros(len(bufs) + 1, dtype=np.int64)
    np.cumsum([len(b) for b in bufs], out=off[1:])
    # + n_bufs: a buffer whose final line lacks '\n' gains one on output
    out = np.empty(max(1, len(data) + len(bufs)), dtype=np.uint8)
    wrote = lib.dgrep_merge_display(
        data,
        off.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        len(bufs),
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
    )
    if wrote < 0:
        return None
    return out[:wrote].tobytes()


# --- Native map-record pipeline (round 8) ----------------------------------
#
# One C pass from kernel output (matched line numbers + the newline index)
# to the partitioned per-reduce LineBatch arrays — replacing the numpy
# chain make_batch_from_lines -> partitions() -> per-partition select().
# Routed from runtime/columnar.py, which keeps bit-identical numpy
# fallbacks for every entry; DGREP_NATIVE_RECORDS=0 is the debug
# kill-switch (this module is the knob's single owner, analysis/knobs.py).

def env_native_records() -> bool:
    """False when DGREP_NATIVE_RECORDS disables the native record build
    (the numpy fallbacks then serve every call — byte-identical, slower)."""
    return os.environ.get("DGREP_NATIVE_RECORDS", "") not in ("0", "false")


def native_records_available() -> bool:
    """True when the one-pass record build can answer — callers that
    would otherwise pre-compute inputs just to feed it (DeferredBatch's
    span pass) check this FIRST so the fallback path does no wasted
    work."""
    lib = _try_load()
    return (lib is not None and hasattr(lib, "dgrep_build_records")
            and env_native_records())


def unique_lines_native(nl: np.ndarray, ends: np.ndarray) -> np.ndarray | None:
    """Unique 1-based line numbers of sorted match END offsets, or None
    when libdgrep is unavailable (caller keeps the searchsorted+unique
    fallback, ops/lines.unique_match_lines)."""
    lib = _try_load()
    if lib is None or not hasattr(lib, "dgrep_unique_lines"):
        return None
    if not env_native_records():
        return None
    nl = np.ascontiguousarray(nl)
    # newline offsets are nonnegative: an int64 index is read as uint64
    # in place instead of copied
    nl = nl.view(np.uint64) if nl.dtype == np.int64 else nl.astype(np.uint64)
    ends = np.ascontiguousarray(ends, dtype=np.int64)
    out = np.empty(ends.size, dtype=np.int64)
    n = lib.dgrep_unique_lines(
        nl.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
        nl.size,
        ends.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        ends.size,
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
    )
    return out[:n].copy()


def line_spans_native(
    nl: np.ndarray, linenos: np.ndarray, n_bytes: int
) -> tuple[np.ndarray, np.ndarray] | None:
    """[start, end) byte span per 1-based line (vectorized
    ops/lines.line_span), or None when libdgrep is unavailable."""
    lib = _try_load()
    if lib is None or not hasattr(lib, "dgrep_line_spans"):
        return None
    if not env_native_records():
        return None
    nl = np.ascontiguousarray(nl, dtype=np.uint64)
    linenos = np.ascontiguousarray(linenos, dtype=np.int64)
    starts = np.empty(linenos.size, dtype=np.int64)
    ends = np.empty(linenos.size, dtype=np.int64)
    lib.dgrep_line_spans(
        nl.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
        nl.size,
        linenos.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        linenos.size,
        int(n_bytes),
        starts.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        ends.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
    )
    return starts, ends


def build_records(
    data: np.ndarray, starts: np.ndarray, ends: np.ndarray,
    linenos: np.ndarray, prefix: bytes, n_reduce: int,
) -> dict[int, tuple[np.ndarray, np.ndarray, bytes]] | None:
    """One-pass partitioned record build: line spans of ``data`` in,
    ``{partition: (stored linenos, offsets, slab bytes)}`` out — the
    grouped arrays of each partition's LineBatch, record order preserved
    inside each partition, partition assignment bit-identical to
    ``partition(f"{prefix}{lineno})")`` per record.  None when libdgrep
    is unavailable, DGREP_NATIVE_RECORDS disables it, or the inputs are
    not the grep shape (caller keeps the numpy split path)."""
    lib = _try_load()
    if lib is None or not hasattr(lib, "dgrep_build_records"):
        return None
    if not env_native_records():
        return None
    data = np.asarray(data)
    if data.dtype != np.uint8 or data.ndim != 1:
        return None  # spans are ELEMENT indices; C indexes bytes
    if not data.flags.c_contiguous:
        data = np.ascontiguousarray(data)
    starts = np.ascontiguousarray(starts, dtype=np.int64)
    ends = np.ascontiguousarray(ends, dtype=np.int64)
    linenos = np.ascontiguousarray(linenos, dtype=np.int64)
    n = int(linenos.size)
    if n == 0:
        return {}
    total = int(np.sum(ends - starts))
    out_linenos = np.empty(n, dtype=np.int64)
    out_offsets = np.empty(n + 1, dtype=np.int64)
    out_slab = np.empty(max(1, total), dtype=np.uint8)
    counts = np.zeros(n_reduce, dtype=np.int64)
    nbytes = np.zeros(n_reduce, dtype=np.int64)
    wrote = lib.dgrep_build_records(
        data.ctypes.data_as(ctypes.c_char_p),
        data.size,
        starts.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        ends.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        linenos.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        n,
        prefix,
        len(prefix),
        int(n_reduce),
        out_linenos.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        out_offsets.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        out_slab.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        counts.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        nbytes.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
    )
    if wrote < 0:
        return None  # malformed span: let the numpy path handle it
    out: dict[int, tuple[np.ndarray, np.ndarray, bytes]] = {}
    r0 = 0
    b0 = 0
    for p in range(int(n_reduce)):
        c = int(counts[p])
        nb = int(nbytes[p])
        if c:
            out[p] = (
                out_linenos[r0 : r0 + c].copy(),
                out_offsets[r0 : r0 + c + 1] - b0,
                out_slab[b0 : b0 + nb].tobytes(),
            )
        r0 += c
        b0 += nb
    return out


# Big inputs fan the DFA scan across threads; newline-aligned chunking keeps
# output byte-identical (every state's '\n' transition is the start state —
# the table invariant the device stripes rely on too).
MT_THRESHOLD_BYTES = 1 << 22


def dfa_scan_mt(
    data: bytes,
    table: np.ndarray,
    accept: np.ndarray,
    start_state: int = 0,
    n_threads: int | None = None,
) -> np.ndarray:
    """Multithreaded DFA scan (accept end-offsets only; no final state —
    chunked scans have no single sequential final state)."""
    lib = _try_load()
    if lib is None or not hasattr(lib, "dgrep_dfa_scan_mt"):
        offsets, _ = dfa_scan(data, table, accept, start_state)
        return offsets
    if n_threads is None:
        n_threads = min(8, os.cpu_count() or 1)
    table = np.ascontiguousarray(table, dtype=np.uint16)
    accept_bytes = np.ascontiguousarray(accept, dtype=np.uint8).tobytes()
    # this path only runs on multi-MB inputs: size the first buffer off the
    # data (one match per ~64 bytes) so a match-dense corpus doesn't pay a
    # second full scan just to learn the count
    cap = max(4096, len(data) >> 6)
    while True:
        buf = np.empty(cap, dtype=np.uint64)
        n = lib.dgrep_dfa_scan_mt(
            data,
            len(data),
            table.ctypes.data_as(ctypes.POINTER(ctypes.c_uint16)),
            accept_bytes,
            start_state,
            buf.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
            cap,
            n_threads,
        )
        if n <= cap:
            return buf[:n].copy()
        cap = n


# --- Trigram shard summaries (shard-index tier) ----------------------------
#
# dgrep_trigram_summary ORs the case-folded trigram bloom of `data` into a
# caller-owned byte array — the native build half of the shard index
# (distributed_grep_tpu/index/summary.py owns the format, the bit-identical
# numpy fallback, and the DGREP_INDEX* knobs).  `bloom.size` must be a
# power of two (summary.py enforces it); returns False when libdgrep (or a
# pre-index build of it) is unavailable, and the caller falls back.

def trigram_summary_available() -> bool:
    lib = _try_load()
    return lib is not None and hasattr(lib, "dgrep_trigram_summary")


def trigram_summary_into(data: bytes, bloom: np.ndarray) -> bool:
    """OR `data`'s folded trigram bits into `bloom` (uint8, C-contiguous,
    power-of-two size) via the native pass; False = not available (the
    caller runs the numpy fallback — identical bits)."""
    lib = _try_load()
    if lib is None or not hasattr(lib, "dgrep_trigram_summary"):
        return False
    assert bloom.dtype == np.uint8 and bloom.flags["C_CONTIGUOUS"]
    lib.dgrep_trigram_summary(
        data, len(data),
        bloom.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)), bloom.size,
    )
    return True
