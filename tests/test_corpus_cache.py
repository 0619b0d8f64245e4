"""Device corpus cache (round 7, ops/layout.CorpusCache): warm queries
rescan HBM-resident shards without re-read / re-pack / re-upload.

The contract under test (ISSUE 7): with a byte budget in force, a repeat
``scan_file`` / ``scan_batch`` over UNCHANGED inputs performs zero host
file reads and zero ``to_device_array`` uploads (spy-proven, ``perf``
marker), and its results are bit-identical to the cold scan for every
kernel family.  The content key is a fresh stat (realpath + size +
mtime_ns + inode) revalidated on every hit, so a modified file can never
serve stale bytes; entries LRU-evict under the DGREP_CORPUS_BYTES budget; the
service's persistent workers get cross-job hits (model cache answers
"same pattern", this cache answers "same data").

Standalone: ``python -m pytest tests/test_corpus_cache.py -q`` (CPU-only;
interpret engines drive the production device path, and the autouse
conftest fixture ``_fresh_corpus_cache`` keeps shards from leaking
across tests).
"""

from __future__ import annotations

import builtins
import os

import numpy as np
import pytest

from distributed_grep_tpu.ops import layout
from distributed_grep_tpu.ops.engine import GrepEngine

BUDGET = 1 << 28  # roomy test budget: nothing evicts unless a test asks


def _corpus_bytes_fixture() -> bytes:
    """Needles for every engine family under test, plus hay."""
    rng = np.random.default_rng(13)
    words = ["hello", "hallo", "helloo", "volcano", "needle", "ab", "zz",
             "q", "the", "quick", "brown", "fox", "of", "and"]
    out = []
    for _ in range(600):
        k = int(rng.integers(1, 8))
        out.append(" ".join(
            words[int(rng.integers(0, len(words)))] for _ in range(k)
        ).encode())
    return b"\n".join(out) + b"\n"


def _fdr_patterns() -> list[str]:
    rng = np.random.default_rng(3)
    pats = {"hello", "volcano", "needle"}
    while len(pats) < 50:
        k = int(rng.integers(4, 9))
        pats.add("".join(chr(c) for c in rng.integers(97, 123, size=k)))
    return sorted(pats)


# the five families ISSUE 7 names; labels follow tests/test_batch.py
ENGINES = [
    ("shift_and", dict(pattern="hello")),
    ("nfa", dict(pattern="h[ae]llo+")),
    ("pairset", dict(patterns=["ab", "zz", "q"])),
    ("dfa_filter", dict(pattern="hello$")),  # '$'-dropped device filter
    ("fdr", dict(patterns=_fdr_patterns())),
]


def _counters() -> dict:
    return layout.corpus_cache_counters()


def _spy_reads_and_uploads(monkeypatch):
    """Record every builtins.open target and every to_device_array call.
    The upload spy patches the layout module attribute — ops/device_scan
    resolves ``layout_mod.to_device_array`` at call time, so the patch is
    seen at the real boundary, not via engine telemetry."""
    opens: list[str] = []
    real_open = builtins.open

    def spy_open(f, *a, **k):
        opens.append(str(f))
        return real_open(f, *a, **k)

    uploads: list[int] = []
    real_tda = layout.to_device_array

    def spy_tda(data, lay, *a, **k):
        uploads.append(len(data))
        return real_tda(data, lay, *a, **k)

    monkeypatch.setattr(builtins, "open", spy_open)
    monkeypatch.setattr(layout, "to_device_array", spy_tda)
    return opens, uploads


# ------------------------------------------------------------- key / knob

def test_file_content_key_is_a_fresh_stat(tmp_path):
    p = tmp_path / "a.txt"
    p.write_bytes(b"hello\n")
    k1 = layout.file_content_key(p)
    assert k1 is not None and k1.identity == ("file", os.path.realpath(p))
    assert k1.n_bytes == 6
    p.write_bytes(b"hello!\n")
    k2 = layout.file_content_key(p)
    assert k2.validators != k1.validators  # size changed
    assert layout.file_content_key(tmp_path / "missing") is None


def test_batch_content_key_requires_every_member(tmp_path):
    p = tmp_path / "a.txt"
    p.write_bytes(b"x\n")
    k = layout.file_content_key(p)
    assert layout.batch_content_key([k, None]) is None
    assert layout.batch_content_key([]) is None
    wk = layout.batch_content_key([k, k])
    assert wk.identity[0] == "pack" and wk.n_bytes == 4


def test_env_corpus_bytes_accessor(monkeypatch):
    monkeypatch.delenv("DGREP_CORPUS_BYTES", raising=False)
    assert layout.env_corpus_bytes() is None
    monkeypatch.setenv("DGREP_CORPUS_BYTES", "notanint")
    assert layout.env_corpus_bytes() is None  # malformed == unset
    monkeypatch.setenv("DGREP_CORPUS_BYTES", "0")
    assert layout.env_corpus_bytes() == 0
    monkeypatch.setenv("DGREP_CORPUS_BYTES", str(1 << 20))
    assert layout.env_corpus_bytes() == 1 << 20


def test_budget_resolution(monkeypatch):
    monkeypatch.delenv("DGREP_CORPUS_BYTES", raising=False)
    # CPU backend default: OFF (CI and plain host runs keep their exact
    # pre-cache behavior)
    assert GrepEngine("x", interpret=True)._corpus_budget() == 0
    # explicit construction arg wins
    assert GrepEngine(
        "x", interpret=True, corpus_bytes=123
    )._corpus_budget() == 123
    # env knob beats the backend default
    monkeypatch.setenv("DGREP_CORPUS_BYTES", "456")
    assert GrepEngine("x", interpret=True)._corpus_budget() == 456


def test_mesh_engines_bypass(monkeypatch):
    """Same verdict as the model cache: a mesh engine's sharded uploads
    are tied to ITS device set — budget answers 0 regardless of knobs."""
    from distributed_grep_tpu.parallel.mesh import make_mesh

    monkeypatch.setenv("DGREP_CORPUS_BYTES", str(BUDGET))
    eng = GrepEngine("hello", interpret=True, mesh=make_mesh((2,), ("data",)))
    assert eng._corpus_budget() == 0


# ------------------------------------------- warm-vs-cold per family

@pytest.mark.parametrize("label,kw", ENGINES, ids=[e[0] for e in ENGINES])
def test_warm_scan_file_bit_identical_per_family(label, kw, tmp_path):
    p = tmp_path / "c.txt"
    p.write_bytes(_corpus_bytes_fixture())
    eng = GrepEngine(interpret=True, corpus_bytes=BUDGET, **kw)

    cold_emitted: list = []
    cold = eng.scan_file(str(p), emit=lambda ln, b: cold_emitted.append((ln, b)))
    c = _counters()
    assert c["corpus_cache_misses"] >= 1, label
    assert c["corpus_cache_bytes_resident"] > 0, label

    warm_emitted: list = []
    warm = eng.scan_file(str(p), emit=lambda ln, b: warm_emitted.append((ln, b)))
    c2 = _counters()
    assert c2["corpus_cache_hits"] >= 1, label

    assert np.array_equal(cold.matched_lines, warm.matched_lines), label
    assert cold.n_matches == warm.n_matches
    assert cold.bytes_scanned == warm.bytes_scanned == len(_corpus_bytes_fixture())
    assert cold_emitted == warm_emitted  # per-line emit, byte-identical
    assert cold.n_matches > 0  # the corpus really exercises this family


@pytest.mark.parametrize("label,kw", ENGINES, ids=[e[0] for e in ENGINES])
def test_warm_scan_batch_bit_identical_per_family(label, kw, tmp_path):
    files = []
    body = _corpus_bytes_fixture()
    for j in range(5):
        q = tmp_path / f"f{j}.txt"
        q.write_bytes(body[j * 512:] or b"hello\n")
        files.append((f"f{j}.txt", str(q)))
    eng = GrepEngine(interpret=True, corpus_bytes=BUDGET,
                     batch_bytes=1 << 22, **kw)
    cold = eng.scan_batch(list(files))
    warm = eng.scan_batch(list(files))
    assert _counters()["corpus_cache_hits"] >= 1, label
    assert [n for n, _ in cold] == [n for n, _ in warm] == [n for n, _ in files]
    for (_, a), (_, b) in zip(cold, warm):
        assert np.array_equal(a.matched_lines, b.matched_lines), label
        assert a.n_matches == b.n_matches
        assert a.bytes_scanned == b.bytes_scanned


def test_no_trailing_newline_file_populates_and_warm_hits(tmp_path):
    """A single-chunk file WITHOUT a trailing newline (common in code
    search) must still populate on the cold scan: scan_file detects
    the whole-file-in-hand case and scans it unsplit instead of
    orphaning the un-terminated tail into the carry (which left the
    key unthreaded on both pieces)."""
    body = _corpus_bytes_fixture() + b"hello tail without newline"
    p = tmp_path / "c.txt"
    p.write_bytes(body)
    eng = GrepEngine("hello", interpret=True, corpus_bytes=BUDGET)
    cold = eng.scan_file(str(p))
    c = _counters()
    assert c["corpus_cache_misses"] >= 1
    assert c["corpus_cache_bytes_resident"] > 0  # populated

    warm = eng.scan_file(str(p))
    assert _counters()["corpus_cache_hits"] >= 1
    oracle = GrepEngine("hello", interpret=True).scan(body)
    for res in (cold, warm):
        assert np.array_equal(res.matched_lines, oracle.matched_lines)
        assert res.n_matches == oracle.n_matches
        assert res.bytes_scanned == len(body)


def test_padded_band_input_is_cache_ineligible(tmp_path):
    """raw <= budget < padded: eligibility is priced on the PADDED
    device bytes UPFRONT (device_scan computes the total from the same
    hoisted lay_kwargs the prepare step uses) — the scan skips the
    cache entirely instead of retaining every built segment and having
    the publish declined, and resident tenants survive untouched."""
    body = _corpus_bytes_fixture()
    a, b = tmp_path / "a.txt", tmp_path / "b.txt"
    a.write_bytes(body)
    b.write_bytes((b"hello padded band filler\n" * 4100)[:100001])  # odd
    eng = GrepEngine("hello", interpret=True, corpus_bytes=BUDGET)
    eng.scan_file(str(a))
    c0 = _counters()

    eng.corpus_bytes = b.stat().st_size  # == raw; padded exceeds it
    res = eng.scan_file(str(b))
    assert res.n_matches > 0
    c1 = _counters()
    assert c1["corpus_cache_evictions"] == 0  # the tenant survived
    assert c1["corpus_cache_misses"] == c0["corpus_cache_misses"]
    assert c1["corpus_cache_bytes_resident"] == c0[
        "corpus_cache_bytes_resident"
    ]

    eng.corpus_bytes = BUDGET
    hits0 = c1.get("corpus_cache_hits", 0)
    eng.scan_file(str(a))  # still warm
    assert _counters()["corpus_cache_hits"] == hits0 + 1


# --------------------------------------------------- spy proofs (perf)

@pytest.mark.perf
def test_warm_scan_file_zero_reads_zero_uploads(tmp_path, monkeypatch):
    """ISSUE 7 acceptance: the repeat scan_file touches neither the
    filesystem (no open of the input) nor the upload boundary (zero
    to_device_array calls) — counted at the real boundaries, not from
    the engine's own telemetry."""
    p = tmp_path / "c.txt"
    p.write_bytes(_corpus_bytes_fixture() * 4)
    eng = GrepEngine("hello", interpret=True, corpus_bytes=BUDGET)
    cold = eng.scan_file(str(p))

    opens, uploads = _spy_reads_and_uploads(monkeypatch)
    warm = eng.scan_file(str(p))
    assert not [f for f in opens if str(tmp_path) in f]  # zero host reads
    assert uploads == []  # zero device uploads
    assert np.array_equal(cold.matched_lines, warm.matched_lines)
    assert cold.n_matches == warm.n_matches > 0


@pytest.mark.perf
def test_warm_scan_batch_window_zero_reads_zero_uploads(tmp_path, monkeypatch):
    """The packed-window variant: the warm window is recognized from its
    FIRST member's path before any member is read — the whole window
    re-scans with zero opens and zero uploads, and the demux still emits
    per-file results."""
    files = []
    for j in range(8):
        q = tmp_path / f"f{j}.txt"
        q.write_bytes(
            b"".join(
                (b"hello line %d\n" % i if i % 5 == 0 else b"hay line %d\n" % i)
                for i in range(60)
            )
        )
        files.append((f"f{j}.txt", str(q)))
    eng = GrepEngine("hello", interpret=True, corpus_bytes=BUDGET,
                     batch_bytes=1 << 20)
    cold = eng.scan_batch(list(files))
    assert dict(eng.stats)["batch_dispatches"] == 1  # one packed window

    opens, uploads = _spy_reads_and_uploads(monkeypatch)
    warm = eng.scan_batch(list(files))
    stats = dict(eng.stats)
    assert not [f for f in opens if str(tmp_path) in f]  # zero member reads
    assert uploads == []  # zero uploads
    assert stats["corpus_cache_hits"] >= 1
    for (na, a), (nb, b) in zip(cold, warm):
        assert na == nb
        assert np.array_equal(a.matched_lines, b.matched_lines)
    assert sum(r.n_matches for _, r in warm) > 0


def test_disabled_budget_never_populates(tmp_path):
    p = tmp_path / "c.txt"
    p.write_bytes(_corpus_bytes_fixture())
    eng = GrepEngine("hello", interpret=True, corpus_bytes=0)
    eng.scan_file(str(p))
    eng.scan_file(str(p))
    assert _counters() == {}  # a disabled cache is a true no-op


# ------------------------------------------------------ invalidation

def test_mtime_change_invalidates_same_size(tmp_path):
    """Same byte count, different content: the mtime_ns component of the
    validator must catch it — stale resident bytes are NEVER served."""
    p = tmp_path / "c.txt"
    body = _corpus_bytes_fixture()
    p.write_bytes(body)
    eng = GrepEngine("hello", interpret=True, corpus_bytes=BUDGET)
    cold = eng.scan_file(str(p))
    assert cold.n_matches > 0

    changed = body.replace(b"hello", b"hxllo", 5)  # same length
    assert len(changed) == len(body)
    p.write_bytes(changed)
    st = p.stat()
    os.utime(p, ns=(st.st_atime_ns, st.st_mtime_ns + 1))  # force a tick

    res = eng.scan_file(str(p))
    oracle = GrepEngine("hello", interpret=True).scan(changed)
    assert np.array_equal(res.matched_lines, oracle.matched_lines)
    assert res.n_matches == oracle.n_matches
    # the replaced needles really changed the verdict: stale resident
    # bytes would have reproduced cold's lines exactly
    assert not np.array_equal(res.matched_lines, cold.matched_lines)
    c = _counters()
    assert c["corpus_cache_evictions"] >= 1  # the stale entry died


def test_inode_change_invalidates_same_size_same_mtime(tmp_path):
    """Atomic replacement that preserves BOTH size and mtime (cp -p +
    mv, rsync -t, timestamp-preserving tar extract): the inode component
    of the validator must catch it — size+mtime alone would revalidate
    the stale entry as unchanged and serve old bytes with the file never
    opened."""
    p = tmp_path / "c.txt"
    body = _corpus_bytes_fixture()
    p.write_bytes(body)
    eng = GrepEngine("hello", interpret=True, corpus_bytes=BUDGET)
    cold = eng.scan_file(str(p))
    assert cold.n_matches > 0
    st = p.stat()

    changed = body.replace(b"hello", b"hxllo", 5)  # same length
    assert len(changed) == len(body)
    q = tmp_path / "c.txt.new"
    q.write_bytes(changed)
    os.utime(q, ns=(st.st_atime_ns, st.st_mtime_ns))  # preserve mtime
    os.replace(q, p)  # new inode, same size, same mtime_ns
    assert p.stat().st_mtime_ns == st.st_mtime_ns
    assert p.stat().st_size == st.st_size

    res = eng.scan_file(str(p))
    oracle = GrepEngine("hello", interpret=True).scan(changed)
    assert np.array_equal(res.matched_lines, oracle.matched_lines)
    assert res.n_matches == oracle.n_matches
    assert not np.array_equal(res.matched_lines, cold.matched_lines)
    assert _counters()["corpus_cache_evictions"] >= 1  # stale entry died


def test_size_change_invalidates(tmp_path):
    p = tmp_path / "c.txt"
    body = _corpus_bytes_fixture()
    p.write_bytes(body)
    eng = GrepEngine("hello", interpret=True, corpus_bytes=BUDGET)
    eng.scan_file(str(p))
    p.write_bytes(body + b"one more hello line\n")
    res = eng.scan_file(str(p))
    assert res.bytes_scanned == len(body) + 20
    oracle = GrepEngine("hello", interpret=True).scan(
        body + b"one more hello line\n"
    )
    assert np.array_equal(res.matched_lines, oracle.matched_lines)


def test_batch_member_change_invalidates_window(tmp_path):
    """One modified member breaks the whole packed window's key: fresh
    stats are taken per member on every call, so the warm-window probe
    misses and the files are re-read (correct results, counted miss)."""
    files = []
    for j in range(4):
        q = tmp_path / f"f{j}.txt"
        q.write_bytes(b"hello %d\nworld\n" % j * 30)
        files.append((f"f{j}.txt", str(q)))
    eng = GrepEngine("hello", interpret=True, corpus_bytes=BUDGET,
                     batch_bytes=1 << 20)
    eng.scan_batch(list(files))
    hits0 = _counters().get("corpus_cache_hits", 0)

    q = tmp_path / "f2.txt"
    q.write_bytes(b"no needles at all\n" * 30)
    out = eng.scan_batch(list(files))
    assert _counters().get("corpus_cache_hits", 0) == hits0  # no false hit
    assert out[2][1].n_matches == 0  # the new content, not the cached one
    assert out[0][1].n_matches > 0


def test_shrunk_batch_bytes_governs_warm_windows(tmp_path):
    """Lowering batch_bytes must take effect for already-resident
    windows too (the knob bounds per-dispatch host/device memory): a
    window packed under the old larger cap is NOT re-served — the cold
    path re-packs at the new granularity, results stay exact."""
    files = []
    for j in range(6):
        q = tmp_path / f"f{j}.txt"
        q.write_bytes(b"hello %d\nworld filler line\n" % j * 40)
        files.append((f"f{j}.txt", str(q)))
    eng = GrepEngine("hello", interpret=True, corpus_bytes=BUDGET,
                     batch_bytes=1 << 20)
    cold = eng.scan_batch(list(files))
    assert dict(eng.stats)["batch_dispatches"] == 1  # one big window

    eng.batch_bytes = 2048  # shrink below the resident window's size
    out = eng.scan_batch(list(files))
    stats = dict(eng.stats)
    # re-dispatched at the new granularity (smaller windows and/or solo
    # scans) — NOT one oversized warm window
    assert stats["batch_dispatches"] + stats["solo_dispatches"] > 1
    assert stats["batch_fill_ratio"] <= 1.0  # vs the CURRENT cap
    assert [n for n, _ in out] == [n for n, _ in cold]
    for (_, a), (_, b) in zip(cold, out):
        assert np.array_equal(a.matched_lines, b.matched_lines)


# ------------------------------------------------------ LRU eviction

def test_lru_eviction_under_tiny_budget(tmp_path):
    body = _corpus_bytes_fixture()
    a, b = tmp_path / "a.txt", tmp_path / "b.txt"
    a.write_bytes(body)
    b.write_bytes(body[7:])  # distinct content, ~same size
    eng = GrepEngine("hello", interpret=True, corpus_bytes=BUDGET)
    eng.scan_file(str(a))
    one_entry = _counters()["corpus_cache_bytes_resident"]
    assert one_entry > 0

    eng.corpus_bytes = int(one_entry * 1.5)  # fits ONE entry, not two
    eng.scan_file(str(b))  # inserting b pushes a (LRU) out
    c = _counters()
    assert c["corpus_cache_evictions"] >= 1
    assert c["corpus_cache_bytes_resident"] <= int(one_entry * 1.5)

    hits0 = c.get("corpus_cache_hits", 0)
    eng.scan_file(str(b))  # survivor is warm
    assert _counters()["corpus_cache_hits"] == hits0 + 1
    misses0 = _counters()["corpus_cache_misses"]
    eng.scan_file(str(a))  # evictee is cold again
    assert _counters()["corpus_cache_misses"] == misses0 + 1


def test_input_larger_than_budget_is_cache_ineligible(tmp_path):
    """An input bigger than the whole budget never touches the cache:
    retaining its built segments until scan end would defeat the
    double-buffer's bounded footprint, and publishing would LRU-wipe
    every smaller entry before the oversized newcomer evicts itself —
    so the scan runs exactly as if the cache were off."""
    p = tmp_path / "c.txt"
    p.write_bytes(_corpus_bytes_fixture())
    eng = GrepEngine("hello", interpret=True, corpus_bytes=1)  # 1 byte
    r1 = eng.scan_file(str(p))
    assert _counters() == {}  # no lookup, no put, no counters
    r2 = eng.scan_file(str(p))  # still correct, still uncached
    assert np.array_equal(r1.matched_lines, r2.matched_lines)
    assert _counters() == {}


def test_oversized_input_does_not_wipe_resident_entries(tmp_path):
    """The LRU-wipe scenario pinned directly: a small warm entry must
    SURVIVE a scan of an input larger than the budget."""
    body = _corpus_bytes_fixture()
    small, big = tmp_path / "small.txt", tmp_path / "big.txt"
    small.write_bytes(body)
    big.write_bytes(body * 400)  # ~6 MB, over the 4 MB budget
    # XLA device path (no interpret): same scan_device cache gate,
    # fast enough for a multi-MB corpus in CI
    eng = GrepEngine("hello", backend="device", corpus_bytes=1 << 22)
    eng.scan_file(str(small))
    resident = _counters()["corpus_cache_bytes_resident"]
    assert 0 < resident <= 1 << 22

    assert big.stat().st_size > 1 << 22
    eng.scan_file(str(big))  # cache-ineligible, must not evict anything
    c = _counters()
    assert c["corpus_cache_evictions"] == 0
    assert c["corpus_cache_bytes_resident"] == resident

    hits0 = c.get("corpus_cache_hits", 0)
    eng.scan_file(str(small))  # the small entry is still warm
    assert _counters()["corpus_cache_hits"] == hits0 + 1


def test_cached_window_is_slim_and_reconstructs_members(tmp_path):
    """A cache-resident window must NOT pin the original member blobs
    (they would double its host footprint alongside the packed data);
    member bytes reconstruct as slices of the packed blob, exactly."""
    files = []
    for j in range(4):
        q = tmp_path / f"f{j}.txt"
        # one member missing its trailing newline (synthesized in the
        # packed layout) and one empty (packs to zero bytes)
        body = (b"" if j == 2
                else b"hello %d\nworld" % j + (b"\n" if j % 2 else b""))
        q.write_bytes(body)
        files.append((f"f{j}.txt", str(q)))
    eng = GrepEngine("hello", interpret=True, corpus_bytes=BUDGET,
                     batch_bytes=1 << 20)
    eng.scan_batch(list(files))

    cache = layout.corpus_cache()
    wins = [e for e in cache._entries.values() if e.batch is not None]
    assert wins
    for ent in wins:
        assert ent.batch.blobs is None  # no second host copy pinned
        for nm, blob in zip(ent.batch.names, ent.batch.member_blobs()):
            assert blob == (tmp_path / nm).read_bytes(), nm


def test_put_segments_declines_oversized_variant():
    """The authoritative budget check is on the PADDED device bytes at
    put time (the raw-input gate in device_scan under-counts padding):
    a variant whose own bytes exceed the whole budget is declined
    outright — resident tenants survive, nothing is evicted."""
    from types import SimpleNamespace

    cache = layout.CorpusCache()
    small = layout.CorpusKey(identity=("file", "/a"), validators=((1, 1),))
    cache.put_segments(
        small, ("sig",), b"x",
        [(0, SimpleNamespace(padded=100), np.zeros(100, np.uint8), None)],
        budget=1000,
    )
    big = layout.CorpusKey(identity=("file", "/b"), validators=((2, 2),))
    cache.put_segments(
        big, ("sig",), b"y",
        [(0, SimpleNamespace(padded=2000), np.zeros(2000, np.uint8), None)],
        budget=1000,
    )
    c = cache.counters()
    assert c["corpus_cache_evictions"] == 0  # the small tenant survives
    assert c["corpus_cache_bytes_resident"] == 100
    assert cache.lookup(small) is not None
    assert cache.lookup(big) is None  # the oversized variant never landed


def test_sibling_variant_dropped_before_tenant_eviction():
    """Two layout sigs of the SAME content whose total exceeds the
    budget (alternating engine families over one corpus): the stale
    sibling variant is dropped, not the whole entry — whole-entry LRU
    would wipe the variant just built and thrash to permanent misses —
    and other tenants survive when dropping the sibling suffices."""
    from types import SimpleNamespace

    def seg(n):
        return [(0, SimpleNamespace(padded=n), np.zeros(n, np.uint8), None)]

    cache = layout.CorpusCache()
    tenant = layout.CorpusKey(identity=("file", "/t"), validators=((1, 1),))
    cache.put_segments(tenant, ("sig1",), b"t", seg(300), budget=1000)
    shared = layout.CorpusKey(identity=("file", "/s"), validators=((2, 2),))
    cache.put_segments(shared, ("sig1",), b"s", seg(600), budget=1000)
    cache.put_segments(shared, ("sig2",), b"s", seg(600), budget=1000)

    assert cache.resident_segments(shared, ("sig2",)) is not None  # kept
    assert cache.resident_segments(shared, ("sig1",)) is None  # dropped
    assert cache.lookup(tenant) is not None  # the other tenant survived
    assert cache.counters()["corpus_cache_bytes_resident"] == 900


def test_explicit_device_list_bypasses(monkeypatch):
    """Same verdict as the model cache: resident segments are committed
    to specific devices, so an engine pinned to an explicit devices=
    LIST must not share them — budget answers 0; the symbolic "all"
    stays cacheable."""
    import jax

    monkeypatch.setenv("DGREP_CORPUS_BYTES", str(BUDGET))
    dev = jax.devices("cpu")[0]
    eng = GrepEngine("hello", interpret=True, devices=[dev])
    assert eng._corpus_budget() == 0
    assert GrepEngine(
        "hello", interpret=True, devices="all"
    )._corpus_budget() == BUDGET


# ------------------------------------------------ telemetry contracts

def test_stats_stamped_nonzero_only(tmp_path):
    """Zero-activity engines keep their exact stats shape (same contract
    as compile_cache_*): no corpus_* keys before the cache is touched."""
    eng = GrepEngine("hello", interpret=True)  # budget 0 on cpu
    eng.scan(b"hello\n")
    assert not any(k.startswith("corpus_cache") for k in eng.stats)

    p = tmp_path / "c.txt"
    p.write_bytes(_corpus_bytes_fixture())
    eng2 = GrepEngine("hello", interpret=True, corpus_bytes=BUDGET)
    eng2.scan_file(str(p))
    s = dict(eng2.stats)
    assert s["corpus_cache_misses"] >= 1
    assert s["corpus_cache_bytes_resident"] > 0


def test_host_routed_warm_serve_counts_host_hit(tmp_path):
    """A host-routed engine (backend="cpu" — mode native/re, never
    reaches scan_device) serving warm host bytes must still show up in
    the counters: corpus_cache_host_hits counts the ent.data serve,
    since the resident_segments hit/miss verdict never runs for it.
    Without the counter, /status reads an actively-working cache as
    idle."""
    p = tmp_path / "c.txt"
    body = _corpus_bytes_fixture()
    p.write_bytes(body)
    # populate via a device-path engine (put_segments is the only
    # entry creator)
    GrepEngine("hello", interpret=True, corpus_bytes=BUDGET).scan_file(str(p))
    c0 = _counters()

    host_eng = GrepEngine("hello", backend="cpu", corpus_bytes=BUDGET)
    res = host_eng.scan_file(str(p))
    c1 = _counters()
    assert c1["corpus_cache_host_hits"] == c0.get("corpus_cache_host_hits", 0) + 1
    # the host serve is NOT a resident-segments verdict: neither hit
    # nor miss moved
    assert c1["corpus_cache_hits"] == c0["corpus_cache_hits"]
    assert c1["corpus_cache_misses"] == c0["corpus_cache_misses"]
    oracle = GrepEngine("hello", backend="cpu").scan(body)
    assert np.array_equal(res.matched_lines, oracle.matched_lines)
    assert res.n_matches == oracle.n_matches > 0


def test_counters_never_touched_is_lock_free():
    """engine.scan() polls corpus_cache_counters() once per scan even
    when the cache is off — the never-touched answer must not take the
    process-global lock (worker threads would serialize on it per chunk
    for a disabled feature)."""
    cache = layout.CorpusCache()

    class _Exploding:
        def __enter__(self):
            raise AssertionError("counters() took the lock before first touch")

        def __exit__(self, *a):
            return False

    real_lock = cache._lock
    cache._lock = _Exploding()
    try:
        assert cache.counters() == {}  # lock-free fast path
    finally:
        cache._lock = real_lock
    cache.count_host_hit()  # first touch
    assert cache.counters()["corpus_cache_host_hits"] == 1
    cache.clear()  # reset re-arms the fast path
    assert cache.counters() == {}


def test_worker_piggyback_merges_corpus_counters(tmp_path):
    from distributed_grep_tpu.runtime.worker import _engine_cache_counters

    p = tmp_path / "c.txt"
    p.write_bytes(_corpus_bytes_fixture())
    GrepEngine("hello", interpret=True, corpus_bytes=BUDGET).scan_file(str(p))
    counters = _engine_cache_counters()
    assert counters is not None
    assert counters["corpus_cache_misses"] >= 1
    assert "corpus_cache_bytes_resident" in counters


def test_corpus_span_instants_reach_events_jsonl(tmp_path):
    """The corpus:hit|miss verdict instants ride the span pipeline into
    events.jsonl — i.e. they are visible in trace-export, which renders
    exactly these records."""
    from distributed_grep_tpu.runtime.job import run_job
    from distributed_grep_tpu.utils import spans
    from distributed_grep_tpu.utils.config import JobConfig
    from pathlib import Path

    files = []
    for j in range(4):
        q = tmp_path / f"f{j}.txt"
        q.write_bytes(b"hello %d\nworld\n" % j * 40)
        files.append(str(q))
    base = dict(
        input_files=files,
        application="distributed_grep_tpu.apps.grep_tpu",
        app_options={"pattern": "hello", "backend": "device",
                     "interpret": True, "corpus_bytes": BUDGET},
        batch_bytes=1 << 20,
        n_reduce=2,
        spans=True,
    )
    run_job(JobConfig(work_dir=str(tmp_path / "w1"), job_id="cold",
                      **base), n_workers=1)
    cold_events = spans.EventLog.read(tmp_path / "w1" / "events.jsonl")
    assert any(e.get("name") == "corpus:miss" for e in cold_events)

    run_job(JobConfig(work_dir=str(tmp_path / "w2"), job_id="warm",
                      **base), n_workers=1)
    warm_events = spans.EventLog.read(tmp_path / "w2" / "events.jsonl")
    assert any(e.get("name") == "corpus:hit" for e in warm_events)


# ------------------------------------------- cross-job via the service

@pytest.mark.service
def test_cross_job_warm_hit_through_service(tmp_path, monkeypatch):
    """ISSUE 7 acceptance: two submits of the same query over the same
    inputs through GrepService's persistent shared workers — the second
    job's packed window comes from the resident cache (hits counted in
    the service /status corpus_cache view) and outputs are identical.
    The round-20 RESULT tier would answer the resubmit before any scan
    (no corpus lookup at all) — pin THIS tier with it off, the
    corpus_resident.py base-leg discipline."""
    from distributed_grep_tpu.runtime.service import GrepService, JobState
    from distributed_grep_tpu.utils.config import JobConfig
    from pathlib import Path

    monkeypatch.setenv("DGREP_RESULT_CACHE", "0")

    files = []
    for j in range(6):
        q = tmp_path / f"f{j}.txt"
        q.write_bytes(b"".join(
            (b"hello from f%d line %d\n" % (j, i) if i % 3 == 0
             else b"hay f%d line %d\n" % (j, i))
            for i in range(50)
        ))
        files.append(str(q))

    def cfg() -> JobConfig:
        return JobConfig(
            input_files=list(files),
            application="distributed_grep_tpu.apps.grep_tpu",
            app_options={"pattern": "hello", "backend": "device",
                         "interpret": True, "corpus_bytes": BUDGET},
            batch_bytes=1 << 20,
            n_reduce=2,
        )

    svc = GrepService(work_root=tmp_path / "svc", task_timeout_s=5.0,
                      sweep_interval_s=0.1)
    try:
        svc.start_local_workers(2)
        j1 = svc.submit(cfg())
        assert svc.wait_job(j1, timeout=120), svc.job_status(j1)
        c1 = layout.corpus_cache_counters()
        assert c1.get("corpus_cache_bytes_resident", 0) > 0

        j2 = svc.submit(cfg())
        assert svc.wait_job(j2, timeout=120), svc.job_status(j2)
        c2 = layout.corpus_cache_counters()
        assert c2["corpus_cache_hits"] >= c1.get("corpus_cache_hits", 0) + 1

        r1, r2 = svc.job_result(j1), svc.job_result(j2)
        assert r1["state"] == r2["state"] == JobState.DONE
        got1 = {Path(p).name: Path(p).read_bytes() for p in r1["outputs"]}
        got2 = {Path(p).name: Path(p).read_bytes() for p in r2["outputs"]}
        assert got1 == got2 and any(got1.values())
        # and the service-level status view carries the counters
        assert svc.status()["corpus_cache"]["corpus_cache_hits"] >= 1
    finally:
        svc.stop()
