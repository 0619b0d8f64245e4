"""Cross-file device batching (round 6): packed scans are bit-identical to
per-file scans across every kernel family.

The contract under test (ops/layout.py BatchPacker + GrepEngine.scan_batch):
many small newline-terminated blobs pack into ONE scan buffer, the scan
runs once, and the demux maps packed line numbers back to per-file lines.
Exactness rides the invariants the repo already pins — every DFA '\\n'
column is the start state (file boundaries are line starts), the approx
recurrence resets at '\\n', and the filter families' host confirm/stitch
pass operates per line — so each family's per-file verdicts must equal a
plain per-file scan() exactly, anchors, missing trailing newlines, empty
files and segment-boundary-spanning batches included.

Standalone: ``python -m pytest tests/test_batch.py -q`` (CPU-only).
"""

from __future__ import annotations

import numpy as np
import pytest

from distributed_grep_tpu.ops.engine import GrepEngine, ScanResult
from distributed_grep_tpu.ops.layout import BatchPacker, packed_size


def _blobs() -> dict[str, bytes]:
    """Edge-case corpus: trailing-newline-less files, empty files, files of
    only empty lines, needles for every engine family."""
    rng = np.random.default_rng(7)
    words = ["hello", "hallo", "helloo", "world", "fox", "ab", "zz", "q",
             "volcano", "volcXno", "needle", "the", "of", "and"]

    def text(n_lines: int, seed_words=words) -> bytes:
        out = []
        for _ in range(n_lines):
            k = int(rng.integers(1, 8))
            out.append(" ".join(
                seed_words[int(rng.integers(0, len(seed_words)))]
                for _ in range(k)
            ).encode())
        return b"\n".join(out) + b"\n"

    return {
        "plain": text(40),
        "no_trailing_nl": b"first hello line\nsecond line\nlast hello",
        "empty": b"",
        "only_newlines": b"\n\nhello\n\n",
        "match_first_byte": b"hello starts this file\nand more\n",
        "match_last_line": text(10) + b"ends with hello",
        "no_match": b"nothing to see\nin this file\n",
        "dense": b"hello\n" * 200,
        "anchored": b"hello\nxhello\nhello tail\nworld hello\n",
    }


ENGINES = [
    ("shift_and", dict(pattern="hello")),
    ("nfa", dict(pattern="h[ae]llo+")),
    ("anchor_start", dict(pattern="^hello")),
    ("anchor_end", dict(pattern="hello$")),
    ("empty_line", dict(pattern="^$")),
    ("approx_k1", dict(pattern="volcano", max_errors=1)),
    ("pairset", dict(patterns=["ab", "zz", "q"])),
    ("cpu_native", dict(pattern="hello", backend="cpu")),
    ("cpu_set", dict(patterns=["hello", "needle", "volcano"], backend="cpu")),
    ("re_fallback", dict(pattern="hello(?! tail)")),
]


def _fdr_patterns() -> list[str]:
    rng = np.random.default_rng(3)
    pats = {"hello", "volcano", "needle"}
    while len(pats) < 50:
        k = int(rng.integers(4, 9))
        pats.add("".join(chr(c) for c in rng.integers(97, 123, size=k)))
    return sorted(pats)


ENGINES.append(("fdr", dict(patterns=_fdr_patterns())))


def _assert_batch_matches_per_file(eng: GrepEngine, blobs: dict[str, bytes]):
    got = eng.scan_batch(list(blobs.items()))
    stats = dict(eng.stats)  # snapshot BEFORE the verify scans reset it
    assert [name for name, _ in got] == list(blobs)  # input order kept
    for name, res in got:
        solo = eng.scan(blobs[name])
        assert np.array_equal(res.matched_lines, solo.matched_lines), (
            name, res.matched_lines, solo.matched_lines
        )
        assert res.n_matches == solo.n_matches == res.matched_lines.size
        assert res.bytes_scanned == len(blobs[name])
    return stats


@pytest.mark.parametrize("label,kw", ENGINES, ids=[e[0] for e in ENGINES])
def test_packed_batch_bit_identical_per_family(label, kw):
    kw = dict(kw)
    if kw.get("backend") != "cpu":
        kw["interpret"] = True  # CI: Pallas interpret = the device path
    eng = GrepEngine(batch_bytes=1 << 20, **kw)
    _assert_batch_matches_per_file(eng, _blobs())


def test_batch_spanning_segment_boundary():
    """A packed buffer larger than segment_bytes crosses segment (and
    stripe) boundaries mid-batch; the stitch pass must keep every file
    exact."""
    blobs = {
        f"f{i:02d}": (b"filler line with hello inside\n" * 400
                      + (b"tail hello" if i % 3 else b""))
        for i in range(12)
    }  # ~12 KB each, ~145 KB packed >> 64 KB segments
    eng = GrepEngine("hello$", interpret=True, segment_bytes=1 << 16,
                     batch_bytes=1 << 20)
    stats = _assert_batch_matches_per_file(eng, blobs)
    assert stats["batch_dispatches"] == 1
    assert stats["batched_files"] == 12


def test_large_inputs_scan_solo_order_preserved():
    big = b"hello big\n" * 2000  # 20 KB >= device_min_bytes below
    blobs = [("s1", b"small hello\n"), ("s2", b"more hello\n"), ("big", big)]
    eng = GrepEngine("hello", backend="cpu", batch_bytes=1 << 20,
                     device_min_bytes=1 << 14)
    seen = []
    got = eng.scan_batch(blobs, emit=lambda n, d, r: seen.append((n, d)))
    assert [n for n, _ in got] == ["s1", "s2", "big"]
    assert seen == [(n, d) for n, d in blobs]  # emit gets ORIGINAL blobs
    st = eng.stats
    assert st["solo_dispatches"] == 1  # the big input
    assert st["batched_files"] == 2 and st["batch_dispatches"] == 1
    assert st["dispatches_saved"] == 1

    # a large input BETWEEN smalls flushes the pending batch first (order
    # preservation): the stranded singles scan solo, never packed
    eng2 = GrepEngine("hello", backend="cpu", batch_bytes=1 << 20,
                      device_min_bytes=1 << 14)
    got2 = eng2.scan_batch(
        [("s1", b"small hello\n"), ("big", big), ("s2", b"more hello\n")]
    )
    assert [n for n, _ in got2] == ["s1", "big", "s2"]
    assert eng2.stats["solo_dispatches"] == 3
    assert eng2.stats["batched_files"] == 0


def test_scan_batch_accepts_paths(tmp_path):
    p1 = tmp_path / "a.txt"
    p1.write_bytes(b"hello from disk\nno match\n")
    p2 = tmp_path / "b.txt"
    p2.write_bytes(b"nothing")
    eng = GrepEngine("hello", backend="cpu")
    got = dict(eng.scan_batch([("a", p1), ("b", str(p2))]))
    assert got["a"].matched_lines.tolist() == [1]
    assert got["b"].n_matches == 0


def test_batch_bytes_zero_disables_packing():
    eng = GrepEngine("hello", backend="cpu", batch_bytes=0)
    got = eng.scan_batch([("a", b"hello\n"), ("b", b"hello\n")])
    assert [r.n_matches for _, r in got] == [1, 1]
    assert eng.stats["batched_files"] == 0
    assert eng.stats["solo_dispatches"] == 2


# ------------------------------------------------------------- packer unit
def test_packer_tables_and_demux():
    p = BatchPacker(1 << 20)
    blobs = [b"a\nbb\n", b"no newline", b"", b"\n\n", b"z\n"]
    for i, b in enumerate(blobs):
        assert p.fits(b)
        p.add(i, b)
    batch = p.pack()
    assert p.pack() is None  # packer reset
    # synthesized terminator only where needed; empty blob adds nothing
    assert batch.data == b"a\nbb\n" + b"no newline\n" + b"\n\n" + b"z\n"
    assert batch.byte_starts.tolist() == [0, 5, 16, 16, 18, 20]
    # grep -n line counts: 2, 1, 0, 2, 1
    assert batch.line_starts.tolist() == [0, 2, 3, 3, 5, 6]
    per = batch.demux(np.asarray([1, 3, 4, 5, 6], dtype=np.int64))
    assert [x.tolist() for x in per] == [[1], [1], [], [1, 2], [1]]


def test_packed_size():
    assert packed_size(b"") == 0
    assert packed_size(b"x") == 2
    assert packed_size(b"x\n") == 2


def test_packer_fits_respects_cap_but_never_splits():
    p = BatchPacker(8)
    assert p.fits(b"0123456789abcdef")  # first blob always joins
    p.add("big", b"0123456789abcdef")
    assert not p.fits(b"x")
    assert len(p.pack()) == 1


# -------------------------------------------------------- runtime plumbing
def test_plan_map_splits_groups_small_consecutive(tmp_path):
    from distributed_grep_tpu.runtime.job import plan_map_splits

    paths = []
    for i, size in enumerate([100, 200, 5000, 100, 100]):
        f = tmp_path / f"f{i}"
        f.write_bytes(b"x" * size)
        paths.append(str(f))
    splits = plan_map_splits(paths, batch_bytes=1 << 20, small_bytes=1000)
    assert splits == [[paths[0], paths[1]], paths[2], [paths[3], paths[4]]]
    # capacity closes groups
    splits = plan_map_splits(paths, batch_bytes=250, small_bytes=1000)
    assert splits == [paths[0], paths[1], paths[2], [paths[3], paths[4]]]
    # disabled -> identity
    assert plan_map_splits(paths, batch_bytes=0) == paths


def test_scheduler_batched_split_assignment_and_journal(tmp_path):
    from distributed_grep_tpu.runtime import rpc
    from distributed_grep_tpu.runtime.journal import TaskJournal
    from distributed_grep_tpu.runtime.scheduler import Scheduler
    from distributed_grep_tpu.runtime.types import TaskState

    jpath = tmp_path / "journal.jsonl"
    journal = TaskJournal(jpath)
    sched = Scheduler(
        files=[["a", "b"], "c"], n_reduce=1, journal=journal,
    )
    try:
        reply = sched.assign_task(rpc.AssignTaskArgs(), timeout=1.0)
        assert reply.assignment == rpc.Assignment.MAP
        assert reply.filenames == ["a", "b"]
        sched.map_finished(rpc.TaskFinishedArgs(
            task_id=reply.task_id, worker_id=reply.worker_id,
            produced_parts=[0],
        ))
        reply2 = sched.assign_task(rpc.AssignTaskArgs(), timeout=1.0)
        assert reply2.filenames == [] and reply2.filename == "c"
    finally:
        sched.stop()
        journal.close()
    entries = TaskJournal.replay(jpath)
    batch_entries = [e for e in entries if e.get("files")]
    assert batch_entries and batch_entries[0]["files"] == ["a", "b"]

    # replay: same plan resumes COMPLETED; a re-planned member list re-runs
    sched2 = Scheduler(files=[["a", "b"], "c"], n_reduce=1,
                       resume_entries=entries)
    try:
        assert sched2.map_tasks[0].state is TaskState.COMPLETED
    finally:
        sched2.stop()
    sched3 = Scheduler(files=[["a", "x"], "c"], n_reduce=1,
                       resume_entries=entries)
    try:
        assert sched3.map_tasks[0].state is TaskState.UNASSIGNED
    finally:
        sched3.stop()


def test_map_batch_fn_records_match_per_file(tmp_path):
    """grep_tpu.map_batch_fn emits the SAME records as per-file map_fn —
    per-file line numbers verified through expand_records."""
    from conftest import expand_records

    from distributed_grep_tpu.apps.loader import load_application

    app = load_application(
        "distributed_grep_tpu.apps.grep_tpu",
        pattern="hello", backend="cpu",
    )
    items = [(name, blob) for name, blob in _blobs().items()]
    batched = expand_records(app.map_batch_fn(items))
    per_file = expand_records(
        [r for name, blob in items for r in app.map_fn(name, blob)]
    )
    assert [(r.key, r.value) for r in batched] == \
        [(r.key, r.value) for r in per_file]
    assert batched  # the corpus does contain matches


def test_job_batched_output_identical_and_fewer_tasks(tmp_path, monkeypatch):
    from distributed_grep_tpu.runtime.job import run_job
    from distributed_grep_tpu.utils.config import JobConfig

    monkeypatch.delenv("DGREP_BATCH_BYTES", raising=False)
    files = []
    for i in range(12):
        f = tmp_path / f"in{i:02d}.txt"
        f.write_bytes(
            b"line one\n" + (b"hello %d\n" % i) * (i % 4)
            + (b"tail hello" if i % 2 else b"")
        )
        files.append(str(f))

    def cfg(work, batch):
        return JobConfig(
            input_files=files,
            application="distributed_grep_tpu.apps.grep_tpu",
            app_options={"pattern": "hello", "backend": "cpu"},
            work_dir=str(tmp_path / work), n_reduce=3,
            batch_bytes=batch,
        )

    res_plain = run_job(cfg("plain", 0), n_workers=2)
    res_batch = run_job(cfg("batched", 1 << 20), n_workers=2)
    assert res_batch.sorted_lines() == res_plain.sorted_lines()
    assert res_plain.metrics["counters"]["map_tasks"] == 12
    assert res_batch.metrics["counters"]["map_tasks"] < 12


def test_job_batched_app_without_map_batch_fn(tmp_path, monkeypatch):
    """Apps lacking map_batch_fn (the CPU reference-mirror grep app) get
    map_fn per member inside the one batched task — same records, fewer
    tasks."""
    from distributed_grep_tpu.runtime.job import run_job
    from distributed_grep_tpu.utils.config import JobConfig

    monkeypatch.delenv("DGREP_BATCH_BYTES", raising=False)
    files = []
    for i in range(6):
        f = tmp_path / f"in{i}.txt"
        f.write_text(f"hello {i}\nnope\n")
        files.append(str(f))

    def cfg(work, batch):
        return JobConfig(
            input_files=files,
            application="distributed_grep_tpu.apps.grep",
            app_options={"pattern": "hello"},
            work_dir=str(tmp_path / work), n_reduce=2, batch_bytes=batch,
        )

    res_plain = run_job(cfg("plain", 0), n_workers=2)
    res_batch = run_job(cfg("batched", 1 << 20), n_workers=2)
    assert res_batch.sorted_lines() == res_plain.sorted_lines()
    assert res_batch.metrics["counters"]["map_tasks"] == 1


def test_cli_recursive_batched_equals_unbatched(tmp_path, capsys, monkeypatch):
    from distributed_grep_tpu.__main__ import main

    d = tmp_path / "tree"
    (d / "sub").mkdir(parents=True)
    (d / "a.txt").write_text("hello a\nnothing\n")
    (d / "sub" / "b.txt").write_text("nothing\nhello b")
    (d / "c.txt").write_text("no match here\n")

    monkeypatch.setenv("DGREP_BATCH_BYTES", "0")
    assert main(["grep", "-r", "hello", str(d)]) == 0
    unbatched = capsys.readouterr().out
    monkeypatch.delenv("DGREP_BATCH_BYTES")
    assert main(["grep", "-r", "hello", str(d)]) == 0
    batched = capsys.readouterr().out
    assert batched == unbatched
    assert "hello a" in batched and "hello b" in batched


def test_scan_batch_emits_batch_span():
    from distributed_grep_tpu.utils import spans as spans_mod

    buf = spans_mod.SpanBuffer()
    eng = GrepEngine("hello", backend="cpu", batch_bytes=1 << 20)
    with spans_mod.task_context(buf, job="j", worker=0, task=1, attempt="a"):
        eng.scan_batch([("a", b"hello\n"), ("b", b"world\n")])
    recs = buf.drain(limit=buf.cap)
    batch_spans = [r for r in recs if r.get("name") == "scan:batch"]
    assert len(batch_spans) == 1
    args = batch_spans[0]["args"]
    assert args["files"] == 2 and args["matches"] == 1
    assert 0 < args["fill_ratio"] <= 1


def test_scan_result_type_stability():
    eng = GrepEngine("hello", backend="cpu")
    for _, res in eng.scan_batch([("a", b"hello\n"), ("e", b"")]):
        assert isinstance(res, ScanResult)
        assert res.matched_lines.dtype == np.int64
