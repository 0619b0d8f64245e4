"""Shard-index tier (distributed_grep_tpu/index): trigram summaries route
queries past shards that cannot match.

The contract under test (ISSUE 12): indexed and DGREP_INDEX=0 outputs are
byte-identical across every kernel family (the summary only ever answers
"cannot match"; a maybe always scans); pruned shards are never opened and
never dispatched (spy-pinned, ``perf`` marker); eligibility boundaries —
empty-match patterns, sub-trigram literals, ignore_case, POSIX classes,
the \\b re-fallback, approx mode — each either prune correctly or fall
through to a full scan, never under-report; summaries persist under the
work root keyed by the content-identity validator tuple, so stat drift
(the cp -p + mv inode case) is a clean miss and a daemon restart serves
them without rebuilding.

Standalone: ``python -m pytest tests/test_index.py -q`` (CPU-only; the
autouse ``_fresh_index`` fixture clears the summary cache per test).
"""

from __future__ import annotations

import builtins
import os
import shutil
import time

import numpy as np
import pytest

from distributed_grep_tpu.index import plan as index_plan
from distributed_grep_tpu.index import summary as index_summary
from distributed_grep_tpu.index.store import IndexStore
from distributed_grep_tpu.ops.engine import GrepEngine

pytestmark = pytest.mark.index


@pytest.fixture(autouse=True)
def _engine_store(tmp_path):
    """Engine-level summary BUILDS are gated on a reuse surface (an
    attached store, or corpus-cache opt-in — one-shot CLI jobs build
    nothing); the engine tests here run the store-attached shape, like
    the service's workers.  Runs after conftest's _fresh_index clear."""
    index_summary.attach_store(tmp_path / "idxstore")
    yield


def _fdr_patterns() -> list[str]:
    rng = np.random.default_rng(3)
    pats = {"hello", "volcano", "needle"}
    while len(pats) < 50:
        k = int(rng.integers(4, 9))
        pats.add("".join(chr(c) for c in rng.integers(97, 123, size=k)))
    return sorted(pats)


# the five families the corpus-cache suite pins, reused here
ENGINES = [
    ("shift_and", dict(pattern="hello")),
    ("nfa", dict(pattern="h[ae]llo+")),
    ("pairset", dict(patterns=["ab", "zz", "q"])),  # index-INELIGIBLE set
    ("dfa_filter", dict(pattern="hello$")),
    ("fdr", dict(patterns=_fdr_patterns())),
]


def _corpus_bytes() -> bytes:
    rng = np.random.default_rng(13)
    words = ["hello", "hallo", "helloo", "volcano", "needle", "ab", "zz",
             "q", "the", "quick", "brown", "fox", "of", "and"]
    out = []
    for _ in range(400):
        k = int(rng.integers(1, 8))
        out.append(" ".join(
            words[int(rng.integers(0, len(words)))] for _ in range(k)
        ).encode())
    return b"\n".join(out) + b"\n"


# ------------------------------------------------------------ summary format


def test_native_and_numpy_builds_are_bit_identical(monkeypatch):
    from distributed_grep_tpu.utils import native

    data = (b"The Quick BROWN fox\xff\xfe jumps over\n" * 500
            + b"unterminated tail")
    if native.trigram_summary_available():
        s_native = index_summary.build_summary(data)
        monkeypatch.setattr(native, "trigram_summary_into",
                            lambda d, b: False)
        s_py = index_summary.build_summary(data)
        assert s_native == s_py
    # chunked fallback == one-shot fallback (the 2-byte overlap seam)
    monkeypatch.setattr(native, "trigram_summary_into", lambda d, b: False)
    big = data * 40
    import distributed_grep_tpu.index.summary as S

    whole = S.build_summary(big)
    # shrink the chunk step so the seam logic actually runs
    monkeypatch.setattr(S, "build_summary", S.build_summary)
    bloom = np.zeros(len(whole), dtype=np.uint8)
    step = 1 << 12
    arr = np.frombuffer(big, dtype=np.uint8)
    for pos in range(0, max(len(big) - 2, 0), step):
        piece = S._FOLD[arr[pos:pos + step + 2]].astype(np.uint64)
        if piece.size < 3:
            break
        v = ((piece[:-2] << np.uint64(16)) | (piece[1:-1] << np.uint64(8))
             | piece[2:])
        idx = np.unique(S._bit_indices(v, len(whole) * 8))
        np.bitwise_or.at(
            bloom, (idx >> np.uint64(3)).astype(np.int64),
            (np.uint8(1) << (idx & np.uint64(7)).astype(np.uint8)),
        )
    assert bloom.tobytes() == whole


def test_short_data_yields_all_zero_summary():
    # < 3 bytes: no trigram — the all-zero summary correctly prunes every
    # eligible query (a 2-byte shard cannot contain a 3+-byte literal)
    for blob in (b"", b"a", b"ab"):
        s = index_summary.build_summary(blob)
        assert not any(s)
    req = index_plan.requirements_for_query(pattern="needle")
    assert not req.may_match(index_summary.build_summary(b"ab"))


def test_case_fold_is_index_time_noop():
    s = index_summary.build_summary(b"some NEEDLE text here\n")
    for q, ic in [("needle", False), ("NEEDLE", False), ("NeEdLe", True)]:
        req = index_plan.requirements_for_query(pattern=q, ignore_case=ic)
        assert req.may_match(s), q
    # and a literal genuinely absent prunes regardless of case flags
    req = index_plan.requirements_for_query(pattern="volcano",
                                            ignore_case=True)
    assert not req.may_match(s)


def test_env_summary_bytes_clamps_to_pow2(monkeypatch):
    monkeypatch.delenv("DGREP_INDEX_SUMMARY_BYTES", raising=False)
    assert index_summary.env_summary_bytes() == 16384
    monkeypatch.setenv("DGREP_INDEX_SUMMARY_BYTES", "notanint")
    assert index_summary.env_summary_bytes() == 16384
    monkeypatch.setenv("DGREP_INDEX_SUMMARY_BYTES", "5000")
    assert index_summary.env_summary_bytes() == 4096
    monkeypatch.setenv("DGREP_INDEX_SUMMARY_BYTES", "1")
    assert index_summary.env_summary_bytes() == 1024
    monkeypatch.setenv("DGREP_INDEX_SUMMARY_BYTES", str(1 << 30))
    assert index_summary.env_summary_bytes() == 1 << 20


# -------------------------------------------------------- query eligibility

ELIGIBLE = [
    ("needle", {}, [b"needle"]),
    ("(volcano|needle)", {}, [b"volcano", b"needle"]),
    ("err[0-9]+ors", {}, [b"err"]),  # required factor around a class
    (r"\berror\b", {}, [b"error"]),  # the re-fallback rescue family
    ("hello$", {}, [b"hello"]),  # '$'-dropped device-filter family
    ("^needle", {}, [b"needle"]),
    ("[[:digit:]]+needle", {}, [b"needle"]),  # POSIX class body
    ("a{3,}", {}, [b"aaa"]),  # required repeat of a singleton
    ("NEEDLE", {"ignore_case": True}, [b"NEEDLE"]),
]

INELIGIBLE = [
    ("", {}),  # empty pattern: matches everything
    ("a*", {}),  # nullable: no required bytes
    ("x?y?z?", {}),
    ("ab", {}),  # sub-trigram literal
    ("(foo|ab)", {}),  # one alternative too short unconstrains the Alt
    ("needle", {"max_errors": 1}),  # approx: edits can destroy literals
    ("[0-9]+", {}),  # classes only: no literal run
]


@pytest.mark.parametrize("pat,kw,lits", ELIGIBLE)
def test_eligible_queries_derive_required_literals(pat, kw, lits):
    req = index_plan.requirements_for_query(pattern=pat, **kw)
    assert req is not None and req.literals == lits


@pytest.mark.parametrize("pat,kw", INELIGIBLE)
def test_ineligible_queries_scan_everything(pat, kw):
    assert index_plan.requirements_for_query(pattern=pat, **kw) is None


def test_pattern_set_eligibility_boundaries():
    req = index_plan.requirements_for_query(patterns=["volcano", "needle"])
    assert req.literals == [b"volcano", b"needle"]
    # ANY sub-trigram member makes the whole set ineligible: the summary
    # could never rule that member out, so pruning would under-report
    assert index_plan.requirements_for_query(
        patterns=["volcano", "ab"]) is None
    assert index_plan.requirements_for_query(patterns=[]) is None


def test_cannot_match_verdict_is_sound_fuzz():
    """Whenever the index says "cannot match", a real scan agrees —
    random corpora x random queries, both fire (absent literal prunes)
    and silent (present literal never prunes a matching shard)."""
    import re

    rng = np.random.default_rng(42)
    letters = "abcdefgh"
    for trial in range(40):
        n = int(rng.integers(10, 400))
        corpus = bytes(
            rng.choice([ord(c) for c in letters + "\n "], size=n)
        )
        s = index_summary.build_summary(corpus)
        qlen = int(rng.integers(3, 6))
        q = "".join(letters[int(rng.integers(0, len(letters)))]
                    for _ in range(qlen))
        req = index_plan.requirements_for_query(pattern=q)
        present = q.encode() in corpus
        if not req.may_match(s):
            assert not present, (q, corpus)
        if present:
            assert req.may_match(s), (q, corpus)


# ----------------------------------------------------- engine-level routing


def _spy_opens(monkeypatch):
    opened: list = []
    real_open = builtins.open

    def spy_open(f, *a, **k):
        opened.append(os.fspath(f) if not isinstance(f, int) else f)
        return real_open(f, *a, **k)

    monkeypatch.setattr(builtins, "open", spy_open)
    return opened


@pytest.mark.perf
def test_scan_file_pruned_shard_is_never_opened(tmp_path, monkeypatch):
    p = tmp_path / "shard.txt"
    p.write_bytes(b"nothing of note\nplain filler text\n" * 200)
    eng = GrepEngine("needle", backend="cpu")
    cold = eng.scan_file(p)  # builds + publishes the summary
    assert cold.n_matches == 0
    opened = _spy_opens(monkeypatch)
    scans: list = []
    orig = GrepEngine._scan_impl
    monkeypatch.setattr(
        GrepEngine, "_scan_impl",
        lambda self, *a, **k: (scans.append(1), orig(self, *a, **k))[1],
    )
    res = eng.scan_file(p)
    assert res.n_matches == 0 and res.matched_lines.size == 0
    assert str(p) not in [str(x) for x in opened], "pruned shard was opened"
    assert not scans, "pruned shard was dispatched"
    assert eng.stats["index_shards_pruned"] >= 1
    assert eng.stats["index_bytes_skipped"] >= p.stat().st_size


def test_one_shot_engine_builds_nothing(tmp_path):
    """No store attached, no corpus opt-in (the one-shot CLI shape):
    lookups run, but no summary is ever BUILT — a process that will
    never consult them must not pay the pass."""
    index_summary.clear()  # detach the autouse store
    p = tmp_path / "shard.txt"
    p.write_bytes(b"plain filler\n" * 50)
    eng = GrepEngine("needle", backend="cpu")
    eng.scan_file(p)
    eng.scan_batch([("a", str(p))], index_prune=True)
    assert index_summary.index_counters().get(
        "index_summaries_built", 0) == 0


def test_scan_file_maybe_still_scans(tmp_path):
    p = tmp_path / "shard.txt"
    p.write_bytes(b"the needle is here\nplain filler\n" * 50)
    eng = GrepEngine("needle", backend="cpu")
    assert eng.scan_file(p).n_matches == 50
    res = eng.scan_file(p)  # summary exists, literal present: maybe
    assert res.n_matches == 50
    assert eng.stats.get("index_maybe_scans", 0) >= 1
    assert not eng.stats.get("index_shards_pruned", 0)


@pytest.mark.parametrize("label,kw", ENGINES)
def test_indexed_vs_off_byte_identity_scan_file(label, kw, tmp_path,
                                                monkeypatch):
    """Every kernel family: matched lines with the index warm equal the
    DGREP_INDEX=0 answer — on a corpus its query matches AND one it
    cannot."""
    hit = tmp_path / "hit.txt"
    hit.write_bytes(_corpus_bytes())
    miss = tmp_path / "miss.txt"
    miss.write_bytes(b"xyzzy plugh 12345\n" * 300)
    results = {}
    for mode in ("off", "indexed"):
        if mode == "off":
            monkeypatch.setenv("DGREP_INDEX", "0")
        else:
            monkeypatch.delenv("DGREP_INDEX", raising=False)
        index_summary.clear()  # detaches the store too
        index_summary.attach_store(tmp_path / "idxstore")
        eng = GrepEngine(backend="cpu", **kw)
        per = {}
        for p in (hit, miss):
            a = eng.scan_file(p)
            b = eng.scan_file(p)  # the warm (possibly pruned) pass
            assert a.matched_lines.tolist() == b.matched_lines.tolist()
            per[p.name] = a.matched_lines.tolist()
        results[mode] = per
    assert results["off"] == results["indexed"], label


@pytest.mark.perf
def test_scan_batch_pruned_members_zero_opens_zero_scans(tmp_path,
                                                         monkeypatch):
    paths = []
    for i in range(6):
        p = tmp_path / f"f{i}.txt"
        body = b"plain filler line\n" * 100
        if i == 2:
            body += b"one needle line\n"
        p.write_bytes(body)
        paths.append(p)
    eng = GrepEngine("needle", backend="cpu")
    items = [(p.name, str(p)) for p in paths]
    first = eng.scan_batch(items, index_prune=True)
    assert [r.n_matches for _, r in first] == [0, 0, 1, 0, 0, 0]
    opened = _spy_opens(monkeypatch)
    warm = eng.scan_batch(items, index_prune=True)
    assert [(n, r.n_matches) for n, r in warm] == \
        [(n, r.n_matches) for n, r in first]
    opened_names = {os.path.basename(str(x)) for x in opened}
    # only the maybe shard may be re-opened; all pruned members never are
    assert opened_names <= {"f2.txt"}, opened_names
    assert eng.stats["index_shards_pruned"] >= 5


def test_scan_batch_invert_keeps_reads_exact(tmp_path, monkeypatch):
    """grep -v: the complement needs the file's real lines, so the app
    refuses member pruning (index_prune=False) and outputs stay
    byte-identical to DGREP_INDEX=0."""
    from distributed_grep_tpu.apps import grep_tpu

    paths = []
    for i in range(3):
        p = tmp_path / f"f{i}.txt"
        p.write_bytes(b"alpha\nbeta\n" + (b"needle\n" if i == 1 else b""))
        paths.append(p)
    items = [(p.name, p.read_bytes()) for p in paths]

    def records(env_off: bool):
        if env_off:
            monkeypatch.setenv("DGREP_INDEX", "0")
        else:
            monkeypatch.delenv("DGREP_INDEX", raising=False)
        index_summary.clear()
        grep_tpu._configured_with = None
        grep_tpu.configure(pattern="needle", backend="cpu", invert=True)
        from conftest import expand_records

        out = []
        for _ in range(2):  # cold then (possibly) index-warm
            out = expand_records(grep_tpu.map_batch_fn(list(items)))
        return sorted((kv.key, kv.value) for kv in out)

    assert records(False) == records(True)


# ------------------------------------------------ content drift (cp -p + mv)


def test_stat_drift_evicts_and_never_prunes_stale(tmp_path):
    """The cp -p + mv case: an atomic same-size, mtime-preserving
    replacement changes only the inode — the summary keyed on the old
    stat must be a clean miss, and the new content's matches must
    surface."""
    p = tmp_path / "shard.txt"
    old = b"plain filler text here\n" * 40
    p.write_bytes(old)
    eng = GrepEngine("needle", backend="cpu")
    assert eng.scan_file(p).n_matches == 0  # builds the no-needle summary
    assert eng.scan_file(p).n_matches == 0  # and prunes on it
    assert eng.stats.get("index_shards_pruned", 0) >= 1
    st = p.stat()
    # same SIZE, same MTIME, new INODE, needle present
    new = (b"plain filler text here\n" * 39
           + b"x needle yz\n".ljust(23, b"!"))
    assert len(new) == len(old)
    repl = tmp_path / "shard.txt.new"
    repl.write_bytes(new)
    os.utime(repl, ns=(st.st_atime_ns, st.st_mtime_ns))
    os.replace(repl, p)
    st2 = p.stat()
    assert (st2.st_size, st2.st_mtime_ns) == (st.st_size, st.st_mtime_ns)
    res = eng.scan_file(p)
    assert res.n_matches == 1, "stale summary pruned fresh content"


def test_store_rejects_stale_validators(tmp_path):
    store = IndexStore(tmp_path / "index")
    p = tmp_path / "f.txt"
    p.write_bytes(b"some corpus bytes here\n")
    key = index_summary.file_key(p)
    s = index_summary.build_summary(p.read_bytes())
    store.save(key, s)
    assert store.load(key) == s
    # drift the validators: the stored record must evict, not serve
    time.sleep(0.01)
    p.write_bytes(b"different corpus bytes\n")
    key2 = index_summary.file_key(p)
    assert store.load(key2) is None
    assert store.load(key2) is None  # stays gone (file deleted)


# ------------------------------------------------------------- planner side


def _mk_corpus(tmp_path, n=6, needle_at=2):
    paths = []
    for i in range(n):
        p = tmp_path / f"f{i}.txt"
        body = b"plain filler line\n" * 30
        if i == needle_at:
            body += b"one needle line\n"
        p.write_bytes(body)
        paths.append(str(p))
    return paths


def _publish_all(paths):
    for f in paths:
        with open(f, "rb") as fh:
            index_summary.publish_summary(index_summary.file_key(f),
                                          fh.read())


def test_plan_map_splits_prunes_files(tmp_path):
    from distributed_grep_tpu.runtime.job import plan_map_splits

    paths = _mk_corpus(tmp_path)
    _publish_all(paths)
    req = index_plan.requirements_for_query(pattern="needle")
    pruner = index_plan.SplitPruner(req, IndexStore(tmp_path / "idx"))
    splits = plan_map_splits(paths, batch_bytes=32 << 20, pruner=pruner)
    flat = [f for s in splits for f in (s if isinstance(s, list) else [s])]
    assert flat == [paths[2]]
    assert pruner.shards_pruned == 5 and pruner.maybe_scans == 1
    assert pruner.bytes_skipped == sum(
        os.path.getsize(p) for p in paths if p != paths[2]
    )
    # no summaries -> nothing prunes (silent direction)
    index_summary.clear()
    pruner2 = index_plan.SplitPruner(req, IndexStore(tmp_path / "idx"))
    splits2 = plan_map_splits(paths, batch_bytes=32 << 20, pruner=pruner2)
    flat2 = [f for s in splits2 for f in (s if isinstance(s, list) else [s])]
    assert flat2 == paths and pruner2.shards_pruned == 0


def test_pruner_for_job_gating(tmp_path, monkeypatch):
    from distributed_grep_tpu.utils.config import JobConfig

    def cfg(**opts):
        return JobConfig(
            input_files=["x"],
            application="distributed_grep_tpu.apps.grep_tpu",
            app_options={"pattern": "needle", "backend": "cpu", **opts},
        )

    assert index_plan.pruner_for_job(cfg(), tmp_path) is not None
    # zero-match output is NOT empty for these: planner must not prune
    assert index_plan.pruner_for_job(cfg(invert=True), tmp_path) is None
    assert index_plan.pruner_for_job(cfg(count_only=True), tmp_path) is None
    assert index_plan.pruner_for_job(
        cfg(presence_only=True), tmp_path) is None
    assert index_plan.pruner_for_job(cfg(max_errors=1), tmp_path) is None
    # ineligible query / foreign app / kill-switch
    assert index_plan.pruner_for_job(cfg(pattern="ab"), tmp_path) is None
    foreign = JobConfig(input_files=["x"],
                        application="distributed_grep_tpu.apps.grep",
                        app_options={"pattern": "needle"})
    assert index_plan.pruner_for_job(foreign, tmp_path) is None
    monkeypatch.setenv("DGREP_INDEX", "0")
    assert index_plan.pruner_for_job(cfg(), tmp_path) is None


# ----------------------------------------------------- service end to end


def _run_service_job(svc, files, pattern, **opts):
    import time as _t

    from distributed_grep_tpu.utils.config import JobConfig

    cfg = JobConfig(
        input_files=files,
        application="distributed_grep_tpu.apps.grep_tpu",
        app_options={"pattern": pattern, "backend": "cpu", **opts},
        n_reduce=2, journal=False,
    )
    jid = svc.submit(cfg)
    deadline = _t.monotonic() + 60
    while _t.monotonic() < deadline:
        st = svc.job_status(jid)
        if st["state"] in ("done", "failed", "cancelled"):
            break
        _t.sleep(0.02)
    assert st["state"] == "done", st
    from pathlib import Path

    out = b"".join(
        Path(p).read_bytes() for p in sorted(st.get("outputs", []))
    )
    return st, out


@pytest.mark.service
def test_service_indexed_vs_off_byte_identity_and_restart(tmp_path,
                                                          monkeypatch):
    from distributed_grep_tpu.runtime.service import GrepService

    paths = _mk_corpus(tmp_path, n=8, needle_at=3)

    # result tier off throughout: the warm resubmits below must PLAN
    # (the index prune is what shrinks the warm plan) — the round-20
    # result cache would answer them with no plan at all
    monkeypatch.setenv("DGREP_RESULT_CACHE", "0")
    # DGREP_INDEX=0 oracle (fresh service, no summaries anywhere)
    monkeypatch.setenv("DGREP_INDEX", "0")
    svc0 = GrepService(work_root=tmp_path / "svc0", task_timeout_s=30)
    svc0.start_local_workers(1)
    try:
        _, out_off = _run_service_job(svc0, paths, "needle")
        _, out_off_miss = _run_service_job(svc0, paths, "zzqqxx")
    finally:
        svc0.stop()
    assert "index" not in svc0.status()  # true no-op: no /status key
    monkeypatch.delenv("DGREP_INDEX", raising=False)
    index_summary.clear()

    svc = GrepService(work_root=tmp_path / "svc", task_timeout_s=30)
    svc.start_local_workers(1)
    try:
        st_cold, out_cold = _run_service_job(svc, paths, "needle")
        st_warm, out_warm = _run_service_job(svc, paths, "needle")
        assert out_cold == out_warm == out_off
        assert st_warm["map"]["total"] < st_cold["map"]["total"]
        assert st_warm["metrics"]["counters"]["index_shards_pruned"] == 7
        _, out_miss = _run_service_job(svc, paths, "zzqqxx")
        assert out_miss == out_off_miss
        assert svc.status()["index"]["index_shards_pruned"] >= 7
    finally:
        svc.stop()

    # restart: a NEW daemon + a cold process-side cache must serve the
    # persisted summaries without rebuilding a single one
    index_summary.clear()
    svc2 = GrepService(work_root=tmp_path / "svc")
    svc2.start_local_workers(1)
    try:
        built0 = index_summary.index_counters().get(
            "index_summaries_built", 0)
        st2, out2 = _run_service_job(svc2, paths, "needle")
        assert out2 == out_off
        assert st2["metrics"]["counters"]["index_shards_pruned"] == 7
        assert index_summary.index_counters().get(
            "index_summaries_built", 0) == built0
    finally:
        svc2.stop()


@pytest.mark.service
def test_service_count_mode_not_planner_pruned(tmp_path):
    """grep -c emits a record per file (zero counts included): the
    planner must keep every map task, and outputs must match the
    unindexed daemon exactly."""
    from distributed_grep_tpu.runtime.service import GrepService

    paths = _mk_corpus(tmp_path, n=4, needle_at=1)
    svc = GrepService(work_root=tmp_path / "svc", task_timeout_s=30)
    svc.start_local_workers(1)
    try:
        _run_service_job(svc, paths, "needle")  # builds summaries
        st, out = _run_service_job(svc, paths, "needle", count_only=True)
        assert st["map"]["total"] == len(paths)
        # every file's count record is present, zeros included
        for p in paths:
            assert os.fspath(p).encode() in out
    finally:
        svc.stop()
