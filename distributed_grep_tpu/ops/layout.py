"""Stripe layout: document bytes -> (chunk, lanes) device array.

The scan is lane-parallel: the document is cut into ``lanes`` contiguous
stripes, each lane scans its stripe sequentially (lax.scan over the chunk
axis), and all lanes run as one vector op per byte step.  Because the DFA
resets to line-start on '\\n', every lane can start from the start state;
the only error is each stripe's first partial line, which lines.py
re-scans exactly on the host.

Padding uses '\\n' bytes: the pattern can never consume '\\n', so padding
can't create matches inside real lines; phantom empty padding lines are
clamped away by lines.py (they sit past the real data's last offset).

Layout is column-major for the scan: array[c, l] = byte c of stripe l, so
lax.scan iterates the leading axis with unit-stride vectors.
"""

from __future__ import annotations

from collections import OrderedDict as _OrderedDict
from dataclasses import dataclass, field

import numpy as np

from distributed_grep_tpu.utils import lockdep as _lockdep

NL = 0x0A


@dataclass(frozen=True)
class Layout:
    lanes: int
    chunk: int  # bytes per lane
    n_real: int  # real (unpadded) document length

    @property
    def padded(self) -> int:
        return self.lanes * self.chunk

    def offset_of(self, c: int, l: int) -> int:
        """Absolute byte offset of array position (chunk c, lane l)."""
        return l * self.chunk + c

    def stripe_starts(self) -> np.ndarray:
        """Absolute offsets where a lane's stripe begins (boundary fix-ups)."""
        return np.arange(1, self.lanes, dtype=np.int64) * self.chunk


def choose_layout(
    n_bytes: int,
    target_lanes: int = 1024,
    min_chunk: int = 256,
    lane_multiple: int = 8,
    chunk_multiple: int = 8,
    quantize_chunk: bool = False,
) -> Layout:
    """Pick (lanes, chunk) for a document: enough lanes to fill the VPU,
    chunks long enough that the sequential scan amortizes its step cost.
    lane_multiple/chunk_multiple let kernels impose tile shapes (the Pallas
    path needs lanes % 4096 == 0 and chunk % 512 == 0).

    ``quantize_chunk`` rounds the chunk UP to a 4-mantissa-bit grid, so a
    job over arbitrarily-sized splits produces O(log) distinct padded
    shapes instead of one per ``chunk_multiple``-byte size step — every
    distinct shape jit-specializes the scan kernel (~20-40 s through a
    cold cache), so the engine bounds compiles at the cost of <= 1/8
    extra '\\n' padding on tail segments (scanned at kernel speed, and
    full 64 MB segments land exactly on the grid unchanged)."""
    if n_bytes <= 0:
        return Layout(lanes=lane_multiple, chunk=chunk_multiple, n_real=max(0, n_bytes))
    lanes = max(lane_multiple, target_lanes // lane_multiple * lane_multiple)
    while lanes > lane_multiple and (n_bytes + lanes - 1) // lanes < min_chunk:
        lanes = max(lane_multiple, lanes // 2 // lane_multiple * lane_multiple)
    chunk = (n_bytes + lanes - 1) // lanes
    if quantize_chunk:
        q = 1 << max(0, chunk.bit_length() - 4)
        chunk = (chunk + q - 1) // q * q
    chunk = (chunk + chunk_multiple - 1) // chunk_multiple * chunk_multiple
    return Layout(lanes=lanes, chunk=chunk, n_real=n_bytes)


def to_device_array(data: bytes, layout: Layout) -> np.ndarray:
    """Pad with '\\n' and reshape column-major: result[c, l] = data[l*chunk+c]."""
    buf = np.full(layout.padded, NL, dtype=np.uint8)
    buf[: len(data)] = np.frombuffer(data, dtype=np.uint8)
    return np.ascontiguousarray(buf.reshape(layout.lanes, layout.chunk).T)


# ----------------------------------------------------- cross-file batching
#
# The many-small-files regime (grep -r over a source tree): every file
# below device_min_bytes would pay a full dispatch round-trip on its own,
# so the scan never reaches the kernels at all.  Packing many
# newline-terminated blobs into ONE buffer amortizes a single dispatch
# across all of them — exactly Hyperscan's one-database-many-payloads
# amortization and MapReduce's small-inputs-into-splits batching.
#
# Why the packed scan is exact at file granularity: every blob is
# terminated with '\n' in the packed buffer (synthesized when the file
# lacks one — which adds no line: grep -n counts the unterminated tail as
# a line already), so no line ever spans a file boundary.  Every DFA
# table's '\n' column is the start state (the invariant stripe/segment
# boundaries already rely on), '^' sees a true line start at each file's
# first byte, '$' sees a true line end at each file's last line, the
# approx recurrence resets its rows at '\n' (an errorful match can never
# span a newline), and the filter families' host confirm/stitch pass
# operates per line — lines are bit-identical to the per-file layout, so
# the per-file verdicts are too.  Demux is pure line arithmetic over the
# cumulative per-file line counts.


# The default packing window, shared by every site that opts into
# batching (GrepEngine's cap fallback, the CLI's cfg.batch_bytes): one
# constant, so the "one packed dispatch per window" contract cannot
# drift between direct engine users and CLI jobs.  32 MB ≈ half a scan
# segment: big enough to amortize dispatch across thousands of small
# files, small enough that a batch never adds a second segment compile.
DEFAULT_BATCH_BYTES = 32 << 20


DEFAULT_DEVICE_MIN_BYTES = 1 << 20


def env_device_min_bytes(fallback: int = DEFAULT_DEVICE_MIN_BYTES) -> int:
    """Parse the DGREP_DEVICE_MIN_BYTES override, ONE way for its two
    readers (GrepEngine's small-input host branch and the map-split
    planner's "small file" bound, runtime/job.plan_map_splits): unset or
    unparseable -> ``fallback``.  A divergent parse would let the planner
    batch files the engine then refuses to treat as small — same failure
    mode env_batch_bytes below guards for the packing window."""
    import os

    env = os.environ.get("DGREP_DEVICE_MIN_BYTES")
    if env is not None:
        try:
            return max(0, int(env))
        except ValueError:
            pass  # malformed override: both readers fall back identically
    return fallback


def env_batch_bytes(fallback: int = DEFAULT_BATCH_BYTES) -> int:
    """Parse the DGREP_BATCH_BYTES override, ONE way for its two readers
    (GrepEngine's packing cap and JobConfig.effective_batch_bytes — the
    map-split planner): unset or unparseable -> ``fallback``, else the
    clamped integer (0 disables).  A divergent parse would let the
    planner hand out batched splits whose worker engines then crash on
    the same env var."""
    import os

    env = os.environ.get("DGREP_BATCH_BYTES")
    if env is not None:
        try:
            return max(0, int(env))
        except ValueError:
            pass  # malformed override: both readers fall back identically
    return fallback


@dataclass
class PackedBatch:
    """One packed scan buffer plus the per-file offset tables to demux it."""

    data: bytes  # concatenated newline-terminated blobs
    names: list  # caller-supplied per-file identifiers, input order
    blobs: list | None  # the ORIGINAL blobs (no synthesized terminator);
    # None on cache-slimmed copies (without_blobs) — member bytes then
    # reconstruct on demand as slices of ``data``
    # cumulative tables, length len(names)+1 with [0] == 0:
    byte_starts: np.ndarray  # packed byte offset where each file begins
    # (demux below is pure LINE arithmetic — byte_starts exists for
    # diagnostics and future byte-addressed consumers like -o/-b)
    line_starts: np.ndarray  # packed line count before each file begins
    blob_lens: np.ndarray | None = None  # ORIGINAL member byte lengths,
    # set on slimmed copies (a packed piece is the original bytes plus a
    # possibly-synthesized '\n' — the packed span alone cannot tell
    # whether the final newline was original)

    def __len__(self) -> int:
        return len(self.names)

    def member_blobs(self) -> list:
        """The ORIGINAL member blobs: as stored, or (slimmed copies)
        reconstructed as transient slices of ``data`` — alive only for
        the scan that asked, never pinned."""
        if self.blobs is not None:
            return self.blobs
        return [
            self.data[int(s) : int(s) + int(n)]
            for s, n in zip(self.byte_starts[:-1], self.blob_lens)
        ]

    def without_blobs(self) -> "PackedBatch":
        """Copy for cache residency that does NOT pin the member blobs
        (they would double a cached window's host footprint alongside
        ``data``); records the original lengths so member_blobs() can
        slice them back out."""
        if self.blobs is None:
            return self
        return PackedBatch(
            data=self.data, names=self.names, blobs=None,
            byte_starts=self.byte_starts, line_starts=self.line_starts,
            blob_lens=np.asarray(
                [len(b) for b in self.blobs], dtype=np.int64
            ),
        )

    def demux(self, matched_lines: np.ndarray) -> list[np.ndarray]:
        """Split packed-buffer 1-based matched line numbers (sorted, as a
        ScanResult carries them) into per-file LOCAL 1-based line arrays,
        input order.  File i owns global lines
        (line_starts[i], line_starts[i+1]]."""
        matched = np.asarray(matched_lines, dtype=np.int64)
        splits = np.searchsorted(matched, self.line_starts, side="right")
        return [
            matched[splits[i] : splits[i + 1]] - self.line_starts[i]
            for i in range(len(self.names))
        ]


def packed_size(blob: bytes) -> int:
    """Bytes `blob` occupies in a packed buffer: its length plus the
    synthesized '\\n' terminator when it lacks one.  Empty blobs occupy
    zero bytes — appending a terminator would manufacture a phantom empty
    line that '^$'-style patterns would match."""
    if not blob:
        return 0
    return len(blob) + (0 if blob.endswith(b"\n") else 1)


class BatchPacker:
    """Accumulate small newline-terminated blobs for one packed dispatch.

    ``add`` never splits a blob across batches: callers check ``fits``
    first and flush (``pack``) when the next blob would overflow
    ``max_bytes``.  ``pack`` returns the PackedBatch and resets the packer
    for the next round."""

    def __init__(self, max_bytes: int):
        self.max_bytes = int(max_bytes)
        self._names: list = []
        self._blobs: list = []
        self._total = 0  # packed bytes including synthesized terminators

    def __len__(self) -> int:
        return len(self._names)

    def fits(self, blob: bytes) -> bool:
        """Whether `blob` joins the CURRENT batch: always true for the
        first blob (a blob is never split), else capacity-bounded."""
        return not self._names or self._total + packed_size(blob) <= self.max_bytes

    def add(self, name, blob: bytes) -> None:
        self._names.append(name)
        self._blobs.append(blob)
        self._total += packed_size(blob)

    def pack(self) -> PackedBatch | None:
        """Build the packed buffer + offset tables; None when empty."""
        if not self._names:
            return None
        names, blobs = self._names, self._blobs
        self._names, self._blobs, self._total = [], [], 0
        pieces: list[bytes] = []
        byte_starts = np.zeros(len(names) + 1, dtype=np.int64)
        line_starts = np.zeros(len(names) + 1, dtype=np.int64)
        pos = 0
        lines = 0
        for i, blob in enumerate(blobs):
            byte_starts[i] = pos
            line_starts[i] = lines
            if blob:
                pieces.append(blob)
                n = packed_size(blob)
                if n > len(blob):
                    pieces.append(b"\n")
                pos += n
                # grep -n line count: every packed blob ends with '\n', so
                # the count is exactly its newline count
                lines += blob.count(b"\n") + (0 if blob.endswith(b"\n") else 1)
        byte_starts[-1] = pos
        line_starts[-1] = lines
        return PackedBatch(
            data=b"".join(pieces), names=names, blobs=blobs,
            byte_starts=byte_starts, line_starts=line_starts,
        )


# ------------------------------------------------ device corpus cache
#
# The service regime (runtime/service.py: log search / code search, many
# queries over the same corpus) repeats the whole data path per query:
# read from disk, pack/pad the stripe layout on host, upload segments to
# HBM — while the scan kernel itself is ~12% of a dense job's wall
# (BASELINE round 6).  The model cache (ops/engine.cached_engine) answers
# "same pattern"; this cache answers "same data": packed/padded device
# segments stay resident across queries, keyed by content identity +
# the layout parameters they were packed under, so a warm query scans
# the resident arrays directly — no file read, no to_device_array pack,
# no upload.  The layout quantizer (choose_layout(quantize_chunk=True))
# bounds distinct padded shapes to O(log), so resident shards are
# reusable across engines and their jit keys converge.
#
# Correctness: the content key carries a FRESH os.stat of every member
# (realpath + size + mtime_ns + inode, taken by the caller in the same
# call that scans), and lookups revalidate the stored entry against it —
# an in-place modification changes size or mtime_ns, an atomic
# replacement (mv/rename, even one that preserves size AND mtime, e.g.
# `cp -p` + mv or a timestamp-preserving tar extract) changes the
# inode; either way revalidation fails and evicts the entry, so stale
# bytes can never be served.  Entries also keep the
# HOST bytes (the confirm/stitch pass and matched-line emit read them),
# so the real footprint is ~2x the device budget; DGREP_CORPUS_BYTES
# budgets the DEVICE-resident bytes and LRU-evicts whole entries beyond
# it.  Pattern-dependent state never enters an entry.

# Default device budget when jax's default backend is a real accelerator
# and neither DGREP_CORPUS_BYTES nor the engine's corpus_bytes= is set.
# On CPU backends the default is OFF (0): CI and plain host runs keep
# their exact pre-cache behavior unless a budget is asked for.
DEFAULT_CORPUS_BYTES_ACCEL = 1 << 30


def env_corpus_bytes() -> int | None:
    """Parse the DGREP_CORPUS_BYTES override, ONE way for every reader
    (the engine's budget resolution — ops/engine._corpus_budget): unset
    or unparseable -> None (the engine then sizes by backend: 0 on CPU,
    DEFAULT_CORPUS_BYTES_ACCEL on accelerators), else the clamped
    integer (0 disables)."""
    import os

    env = os.environ.get("DGREP_CORPUS_BYTES")
    if env is not None:
        try:
            return max(0, int(env))
        except ValueError:
            pass  # malformed override behaves as unset, everywhere
    return None


@dataclass(frozen=True)
class CorpusKey:
    """Content identity of one cacheable input: a file, or a packed
    batch window over several files.  ``validators`` carry the stat
    snapshot (size, mtime_ns, inode) per member, taken at key-derivation
    time — lookups compare them against the cached entry (hit-time stat
    revalidation; the inode catches a same-size, mtime-preserving atomic
    replacement that size+mtime alone would miss)."""

    identity: tuple  # ("file", realpath) | ("pack", (realpath, ...))
    validators: tuple  # ((size, mtime_ns, ino), ...), one per member

    @property
    def n_bytes(self) -> int:
        return sum(v[0] for v in self.validators)


def file_content_key(path) -> CorpusKey | None:
    """CorpusKey for a filesystem path from a FRESH stat, or None when
    the path cannot be statted (the scan then proceeds uncached)."""
    import os

    try:
        real = os.path.realpath(os.fspath(path))
        st = os.stat(real)
    except OSError:
        return None
    return CorpusKey(
        identity=("file", real),
        validators=(
            (int(st.st_size), int(st.st_mtime_ns), int(st.st_ino)),
        ),
    )


def batch_content_key(member_keys) -> CorpusKey | None:
    """CorpusKey for a packed batch window: the ordered member file
    identities, validators concatenated.  None when any member lacks a
    key (mixed bytes/path windows stay uncached)."""
    keys = list(member_keys)
    if not keys or any(k is None for k in keys):
        return None
    return CorpusKey(
        identity=("pack", tuple(k.identity for k in keys)),
        validators=tuple(v for k in keys for v in k.validators),
    )


@dataclass
class ResidentCorpus:
    """One cached input: host bytes + per-layout-sig device segments.

    ``variants`` maps a layout signature (segment size + the
    choose_layout kwargs the device scan packed under — computed in
    ops/device_scan from the SAME values its prepare step uses, so the
    key cannot drift from the layout) to the resident segment list
    [(seg_start, Layout, device_array, device)].  ``batch`` optionally
    holds the PackedBatch whose .data these bytes are (scan_batch demux
    tables + original member blobs, so a warm packed window emits
    per-file records without re-reading members)."""

    key: CorpusKey
    data: bytes
    variants: dict = field(default_factory=dict)
    batch: PackedBatch | None = None
    device_bytes: int = 0
    # Shard-index summary (distributed_grep_tpu/index): the trigram bloom
    # of ``data``, attached by the engine AFTER the scan that built it
    # succeeded — resident next to the bytes it summarizes, so a warm
    # entry answers "can this query match here?" without any store read.
    summary: bytes | None = None


def _segments_nbytes(segments) -> int:
    total = 0
    for _start, lay, arr, _dev in segments:
        total += int(getattr(arr, "nbytes", lay.padded))
    return total


class CorpusCache:
    """Process-global LRU of ResidentCorpus entries, byte-budgeted over
    their DEVICE-resident segment bytes.  Thread-safe: lookups/puts run
    under one lock (dict surgery only — no I/O, no device work; the
    stat that feeds revalidation happens at key derivation, outside)."""

    def __init__(self):
        self._lock = _lockdep.make_lock("corpus-cache")
        self._entries: "_OrderedDict[tuple, ResidentCorpus]" = _OrderedDict()
        self._bytes = 0
        # first-member file identity -> packed-window entry identity:
        # scan_batch's warm-window probe (recognize a cached window from
        # its first upcoming path item BEFORE reading any member)
        self._windows: dict = {}
        self._stats = {
            "corpus_cache_hits": 0,
            "corpus_cache_misses": 0,
            "corpus_cache_evictions": 0,
            # host-bytes serves (scan_file / scan_batch warm paths):
            # counted separately from the device-variant hits above —
            # a host-routed engine (mode "re"/"native", including a
            # device engine routed to native by the FDR pricing) serves ent.data without ever reaching
            # scan_device's resident_segments verdict, and would
            # otherwise read as an idle cache in /status while doing
            # real work; on a device engine a warm scan increments BOTH
            # (host bytes served + resident segments served)
            "corpus_cache_host_hits": 0,
        }
        # lock-free counters() fast path: False until the cache is first
        # touched (verdict counted or entry published).  engine.scan()
        # polls counters() once per scan — on hosts where the cache is
        # permanently off that poll must not take a process-global lock
        # per chunk per thread.  Plain attribute: CPython reads/writes
        # are atomic, and the worst case of a stale False is one scan's
        # telemetry reading {} at the exact moment of first touch —
        # indistinguishable from ordering the scans the other way.
        self._touched = False

    # ------------------------------------------------------------- internals
    def _evict_locked(self, identity) -> None:
        ent = self._entries.pop(identity, None)
        if ent is not None:
            self._bytes -= ent.device_bytes
            self._stats["corpus_cache_evictions"] += 1
            if ent.key.identity[0] == "pack":
                first = ent.key.identity[1][0]
                if self._windows.get(first) == identity:
                    del self._windows[first]

    def _lookup_locked(self, key: CorpusKey) -> ResidentCorpus | None:
        ent = self._entries.get(key.identity)
        if ent is None:
            return None
        if ent.key.validators != key.validators:
            # hit-time stat revalidation: the caller's key carries a
            # fresh stat — any size/mtime_ns/inode drift means the
            # content changed and the resident bytes are stale
            self._evict_locked(key.identity)
            return None
        self._entries.move_to_end(key.identity)
        return ent

    # --------------------------------------------------------------- lookups
    def lookup(self, key: CorpusKey | None) -> ResidentCorpus | None:
        """Revalidated entry for ``key`` (LRU-touched), or None.  Does
        NOT count hit/miss — the per-scan verdict is counted once, at
        the segment-variant level (resident_segments), so a warm
        scan_file's data lookup + its device-variant hit read as ONE
        hit, not two."""
        if key is None:
            return None
        with self._lock:
            return self._lookup_locked(key)

    def resident_segments(self, key: CorpusKey, sig: tuple):
        """The resident segment list for (key, layout sig) or None;
        counts the scan-level hit/miss verdict."""
        with self._lock:
            self._touched = True
            ent = self._lookup_locked(key)
            segs = None if ent is None else ent.variants.get(sig)
            if segs is None:
                self._stats["corpus_cache_misses"] += 1
            else:
                self._stats["corpus_cache_hits"] += 1
            return segs

    def count_host_hit(self) -> None:
        """Record one warm host-bytes serve (scan_file / scan_batch read
        ent.data instead of the disk).  Separate from the hit/miss
        verdict: on device engines the same scan ALSO reaches
        resident_segments, and host-routed engines never do — one
        counter per distinct event keeps both visible without
        double-counting either."""
        with self._lock:
            self._touched = True
            self._stats["corpus_cache_host_hits"] += 1

    # ------------------------------------------------------------------ puts
    def put_segments(
        self, key: CorpusKey, sig: tuple, data: bytes, segments, budget: int
    ) -> None:
        """Insert/replace the (key, sig) variant and LRU-evict whole
        entries until device bytes fit ``budget``.  A variant whose OWN
        device bytes exceed the whole budget is DECLINED outright: it
        could never stay resident, and admitting it would LRU-evict
        every smaller tenant before it evicted itself.  This is the
        authoritative check — the caller's raw-input gate (ops/
        device_scan) under-counts padding, so the raw<=budget<padded
        band lands here.  (A stale same-key entry left behind by a
        decline is caught by the next lookup's revalidation.)"""
        new_bytes = _segments_nbytes(segments)
        if new_bytes > max(0, budget):
            return
        with self._lock:
            self._touched = True
            ent = self._entries.get(key.identity)
            if ent is not None and ent.key.validators != key.validators:
                self._evict_locked(key.identity)
                ent = None
            if ent is None:
                ent = ResidentCorpus(key=key, data=data)
                self._entries[key.identity] = ent
            old = ent.variants.get(sig)
            if old is not None:  # concurrent same-key scans: last wins
                delta = _segments_nbytes(old)
                ent.device_bytes -= delta
                self._bytes -= delta
            ent.variants[sig] = list(segments)
            ent.device_bytes += new_bytes
            self._bytes += new_bytes
            self._entries.move_to_end(key.identity)
            cap = max(0, budget)
            if self._bytes > cap and len(ent.variants) > 1:
                # Over-budget with sibling variants on THIS entry (the
                # same content packed under another layout sig — e.g. a
                # Pallas family vs the DFA banks): drop the siblings
                # before any whole-entry eviction.  The LRU loop below
                # would otherwise reach this just-touched entry last
                # and wipe it INCLUDING the variant just built —
                # alternating engine families would thrash the cache to
                # a permanent miss.
                for other in [s for s in ent.variants if s != sig]:
                    delta = _segments_nbytes(ent.variants.pop(other))
                    ent.device_bytes -= delta
                    self._bytes -= delta
                    self._stats["corpus_cache_evictions"] += 1
                    if self._bytes <= cap:
                        break
            while self._bytes > cap and self._entries:
                oldest = next(iter(self._entries))
                self._evict_locked(oldest)

    def attach_batch(self, key: CorpusKey | None, batch: PackedBatch) -> None:
        """Record the PackedBatch behind an entry's bytes (scan_batch
        warm demux + member blobs) and index the window by its first
        member; no-op when the entry was not admitted (host-scanned
        window, over-budget, no key).  Stored SLIMMED (without_blobs):
        the member blobs would pin a second full host copy of the
        window alongside entry.data — warm scans slice them back out
        of the packed bytes transiently instead."""
        if key is None:
            return
        slim = batch.without_blobs()
        with self._lock:
            ent = self._entries.get(key.identity)
            if ent is not None and ent.key.validators == key.validators:
                ent.batch = slim
                if key.identity[0] == "pack":
                    # last wins on collision (same first file packed into
                    # a different window, e.g. a changed batch cap) — the
                    # probe's membership revalidation makes a stale index
                    # row a clean miss, never a wrong answer
                    self._windows[key.identity[1][0]] = key.identity

    def attach_summary(self, key: CorpusKey | None, summary: bytes) -> None:
        """Record the shard-index trigram summary behind an entry's bytes
        (same no-op-when-absent contract as attach_batch: a window that
        was never admitted simply keeps its summary in the index tier's
        own cache/store)."""
        if key is None:
            return
        with self._lock:
            ent = self._entries.get(key.identity)
            if ent is not None and ent.key.validators == key.validators:
                ent.summary = summary

    def window_for(self, member_key: CorpusKey | None) -> CorpusKey | None:
        """The STORED key of a cached packed window whose first member is
        ``member_key``'s file, or None.  The caller re-derives fresh keys
        for every member and looks the window up with those — this only
        answers "which files would I need" without touching the disk."""
        if member_key is None:
            return None
        with self._lock:
            wid = self._windows.get(member_key.identity)
            ent = self._entries.get(wid) if wid is not None else None
            if ent is None or ent.batch is None:
                return None
            return ent.key

    # ------------------------------------------------------------- telemetry
    def counters(self) -> dict:
        """Copy of the counters + the bytes_resident gauge, or {} when
        the cache was never touched (zero-activity processes never grow
        stats/piggyback keys — same contract as model_cache_counters).
        The never-touched answer is LOCK-FREE: engine.scan() polls this
        once per scan, and on hosts with the cache permanently off that
        poll must not serialize worker threads on a process-global
        mutex."""
        if not self._touched:
            return {}
        with self._lock:
            if not any(self._stats.values()) and not self._entries:
                return {}
            out = dict(self._stats)
            out["corpus_cache_bytes_resident"] = self._bytes
            return out

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()
            self._windows.clear()
            self._bytes = 0
            for k in self._stats:
                self._stats[k] = 0
            self._touched = False


_corpus_cache = CorpusCache()


def corpus_cache() -> CorpusCache:
    """The process-global corpus cache (cross-job by design, like the
    compiled-model cache — a service process WANTS shards shared)."""
    return _corpus_cache


def corpus_cache_counters() -> dict:
    return _corpus_cache.counters()


def corpus_cache_clear() -> None:
    """Drop every resident entry and zero the counters (tests)."""
    _corpus_cache.clear()
