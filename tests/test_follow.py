"""Streaming tier (round 17, runtime/follow.py): suffix-boundary
exactness vs the one-shot oracle across kernel families, durable-cursor
restart resume (no duplicate / no lost line), bounded-stream shed, the
service subscription surface, and the stale-prune pin.

Standalone: ``python -m pytest tests/test_follow.py -q`` (CPU-only).
Marker: ``follow``.
"""

from __future__ import annotations

import json
import os
import threading
import time
from pathlib import Path

import numpy as np
import pytest

from distributed_grep_tpu.ops.engine import GrepEngine
from distributed_grep_tpu.runtime.follow import (
    FollowLog,
    FollowRunner,
    FollowScanner,
    StreamRing,
    follow_counters,
)
from distributed_grep_tpu.utils.config import JobConfig

pytestmark = pytest.mark.follow


# ---------------------------------------------------------------- oracle
def _oracle(engine_kw: dict, data: bytes) -> list[tuple[int, bytes]]:
    """(line_no, line_bytes) a ONE-SHOT scan of the final file state
    selects — the exactness contract every streamed emission must equal."""
    from distributed_grep_tpu.ops import lines as lines_mod

    eng = GrepEngine(**engine_kw)
    res = eng.scan(data)
    nl = lines_mod.newline_index(data)
    out = []
    for ln in res.matched_lines.tolist():
        s, e = lines_mod.line_span(nl, int(ln), len(data))
        out.append((int(ln), data[s:e]))  # span end excludes the newline
    return out


def _streamed(groups_log: list) -> list[tuple[int, bytes]]:
    out = []
    for _path, records, _cur in groups_log:
        for rec in records:
            if "text" in rec:
                out.append((
                    rec["line"],
                    rec["text"].encode("utf-8", "surrogateescape"),
                ))
    return out


# Append stages exercising every boundary shape the issue names: catch-up
# over existing content, an append SPLITTING a line mid-byte, the append
# completing it (plus whole lines), an append of exactly one line, an
# empty append, and an unterminated tail (finalize).
STAGES = [
    b"hello start\nhallo there\nmiss\n",
    b"partial hel",
    b"lo end\nab zz q volcano needle\n",
    b"hello exactly one helloo line\n",
    b"",
    b"\nends with hello\n",
    b"tail hello no newline",
]


def _fdr_patterns() -> list[str]:
    rng = np.random.default_rng(3)
    pats = {"hello", "volcano", "needle"}
    while len(pats) < 50:
        k = int(rng.integers(4, 9))
        pats.add("".join(chr(c) for c in rng.integers(97, 123, size=k)))
    return sorted(pats)


FAMILIES = [
    ("shift_and", dict(pattern="hello")),
    ("nfa", dict(pattern="h[ae]llo+")),
    ("anchor_start", dict(pattern="^hello")),
    ("anchor_end", dict(pattern="hello$")),
    ("empty_line", dict(pattern="^$")),
    ("pairset", dict(patterns=["ab", "zz", "q"])),
    ("fdr", dict(patterns=_fdr_patterns())),
    ("cpu_native", dict(pattern="hello", backend="cpu")),
    ("cpu_set", dict(patterns=["hello", "needle"], backend="cpu")),
    ("re_fallback", dict(pattern="hello(?! tail)")),
]


@pytest.mark.parametrize("label,kw", FAMILIES, ids=[f[0] for f in FAMILIES])
def test_suffix_exactness_across_families(tmp_path, label, kw):
    """Append boundary exactness: streamed emissions across every wake ==
    the one-shot oracle over the final file bytes, per kernel family."""
    kw = dict(kw)
    if kw.get("backend") != "cpu":
        kw["interpret"] = True  # CI: Pallas interpret IS the device path
    eng = GrepEngine(**kw)
    path = tmp_path / "grow.log"
    path.write_bytes(b"")
    scanner = FollowScanner(eng, [str(path)])
    groups_log: list = []
    for stage in STAGES:
        with open(path, "ab") as f:
            f.write(stage)
        groups_log.extend(scanner.poll_once())
    groups_log.extend(scanner.poll_once(final=True))
    final = b"".join(STAGES)
    assert _streamed(groups_log) == _oracle(kw, final)


def test_line_carry_is_not_emitted_early(tmp_path):
    """The partial tail line never emits before its newline arrives —
    even when the prefix already matches (a half-written 'hello' line
    must not stream, then duplicate once completed)."""
    eng = GrepEngine("hello", backend="cpu")
    path = tmp_path / "carry.log"
    path.write_bytes(b"hello whole\n")
    sc = FollowScanner(eng, [str(path)])
    g1 = sc.poll_once()
    assert [r["line"] for _p, rs, _c in g1 for r in rs] == [1]
    with open(path, "ab") as f:
        f.write(b"hello partial")  # matches already, but incomplete
    assert sc.poll_once() == []  # no complete line: no wake output
    with open(path, "ab") as f:
        f.write(b" now complete\nx\n")
    g2 = sc.poll_once()
    assert [(r["line"], r["text"]) for _p, rs, _c in g2 for r in rs] == [
        (2, "hello partial now complete")
    ]


def test_truncation_and_replacement_full_rescan(tmp_path):
    """Validator-tuple drift: size below the cursor (truncate) and a new
    inode (cp + mv replacement) both reset the cursor — a ``reset``
    record, then emissions byte-identical to a one-shot scan of the NEW
    content."""
    eng = GrepEngine("hello", backend="cpu")
    path = tmp_path / "trunc.log"
    path.write_bytes(b"hello a\nhello b\nhello c\n")
    sc = FollowScanner(eng, [str(path)])
    assert len(_streamed(sc.poll_once())) == 3
    # truncate to SHORTER content (size below the cursor is the signal;
    # an in-place rewrite that grows is indistinguishable from an append
    # by stat alone — the tail -f blind spot, shared deliberately)
    new1 = b"hello cut\nmiss\n"
    path.write_bytes(new1)
    groups = sc.poll_once()
    recs = [r for _p, rs, _c in groups for r in rs]
    assert recs[0] == {"file": str(path), "reset": True}
    assert _streamed(groups) == _oracle(dict(pattern="hello", backend="cpu"),
                                        new1)
    # atomic replacement: same size, fresh inode
    repl = tmp_path / "repl.tmp"
    repl.write_bytes(b"hello replaced content\n")
    os.replace(repl, path)
    groups = sc.poll_once()
    recs = [r for _p, rs, _c in groups for r in rs]
    assert recs[0] == {"file": str(path), "reset": True}
    assert _streamed(groups) == [(1, b"hello replaced content")]


def test_missing_then_created_file(tmp_path):
    """A standing query over a log that does not exist yet (tail -F):
    the cursor waits; creation is just the first growth."""
    eng = GrepEngine("hello", backend="cpu")
    path = tmp_path / "later.log"
    sc = FollowScanner(eng, [str(path)])
    assert sc.poll_once() == []
    path.write_bytes(b"hello now\n")
    assert _streamed(sc.poll_once()) == [(1, b"hello now")]


def test_invert_complement_matches_oracle(tmp_path):
    eng = GrepEngine("hello", backend="cpu")
    path = tmp_path / "inv.log"
    path.write_bytes(b"")
    sc = FollowScanner(eng, [str(path)], invert=True)
    groups_log: list = []
    for stage in STAGES:
        with open(path, "ab") as f:
            f.write(stage)
        groups_log.extend(sc.poll_once())
    groups_log.extend(sc.poll_once(final=True))
    final = b"".join(STAGES)
    from distributed_grep_tpu.ops import lines as lines_mod

    matched = {ln for ln, _ in _oracle(dict(pattern="hello", backend="cpu"),
                                       final)}
    n_lines = lines_mod.count_lines(final)
    want = [ln for ln in range(1, n_lines + 1) if ln not in matched]
    assert [ln for ln, _ in _streamed(groups_log)] == want


def test_count_only_never_materializes_lines(tmp_path):
    """-c standing queries: records carry per-wake count deltas only —
    the match-dense worst case is a bandwidth-bound counter update."""
    eng = GrepEngine("hello", backend="cpu")
    path = tmp_path / "dense.log"
    path.write_bytes(b"hello\n" * 1000)
    sc = FollowScanner(eng, [str(path)], count_only=True)
    groups = sc.poll_once()
    recs = [r for _p, rs, _c in groups for r in rs]
    assert recs == [{"file": str(path), "count": 1000}]
    with open(path, "ab") as f:
        f.write(b"hello\n" * 500 + b"miss\n")
    groups = sc.poll_once()
    recs = [r for _p, rs, _c in groups for r in rs]
    assert recs == [{"file": str(path), "count": 500}]
    assert all("text" not in r and "line" not in r for r in recs)
    assert sc.poll_once() == []  # nothing new: no wake output
    assert sc.cursors[str(path)].emitted == 1500


def test_presence_only_stops_after_first_match(tmp_path):
    eng = GrepEngine("hello", backend="cpu")
    path = tmp_path / "q.log"
    path.write_bytes(b"miss\nhello yes\nhello more\n")
    sc = FollowScanner(eng, [str(path)], count_only=True,
                       presence_only=True)
    groups = sc.poll_once()
    recs = [r for _p, rs, _c in groups for r in rs]
    assert recs == [{"file": str(path), "match": True}]
    with open(path, "ab") as f:
        f.write(b"hello again\n")
    assert sc.poll_once() == []  # settled: no further scans/emits


def test_giant_line_larger_than_wake_cap_does_not_stall(tmp_path,
                                                        monkeypatch):
    """A single line larger than the per-wake read cap must not stall
    the cursor: the suffix read extends until a newline lands, and the
    streamed set still equals the one-shot oracle."""
    from distributed_grep_tpu.runtime import follow as follow_mod

    monkeypatch.setattr(follow_mod, "MAX_WAKE_BYTES", 64)
    eng = GrepEngine("hello", backend="cpu")
    path = tmp_path / "giant.log"
    giant = b"hello " + b"x" * 300  # one 306-byte line vs a 64-byte cap
    path.write_bytes(giant + b"\nhello after\n")
    sc = FollowScanner(eng, [str(path)])
    groups = sc.poll_once()
    assert _streamed(groups) == [(1, giant), (2, b"hello after")]
    # newline-free growth past the cap stays a carry (no emit) ...
    with open(path, "ab") as f:
        f.write(b"hello " + b"y" * 200)
    assert sc.poll_once() == []
    # ... until its newline arrives
    with open(path, "ab") as f:
        f.write(b"tail\n")
    assert _streamed(sc.poll_once()) == [(3, b"hello " + b"y" * 200 + b"tail")]


def test_unterminated_tail_not_rescanned_until_growth(tmp_path,
                                                      monkeypatch):
    """The carry is re-read once after the wake that consumed up to it;
    further no-growth wakes skip the disk entirely (the ``seen`` size
    gate) and the next append still scans exactly."""
    eng = GrepEngine("hello", backend="cpu")
    path = tmp_path / "tail.log"
    path.write_bytes(b"hello a\npartial hel")
    sc = FollowScanner(eng, [str(path)])
    calls = []
    real = eng.scan_file_suffix

    def spy(p, offset, **kw):
        calls.append(offset)
        return real(p, offset, **kw)

    monkeypatch.setattr(eng, "scan_file_suffix", spy)
    assert len(_streamed(sc.poll_once())) == 1  # consumes "hello a\n"
    sc.poll_once()  # tail re-read once: no progress, size remembered
    n = len(calls)
    for _ in range(5):
        assert sc.poll_once() == []
    assert len(calls) == n  # no-growth wakes never hit the disk
    with open(path, "ab") as f:
        f.write(b"lo\n")
    assert _streamed(sc.poll_once()) == [(2, b"partial hello")]


def test_one_bad_file_does_not_discard_other_groups(tmp_path,
                                                    monkeypatch):
    """Per-file fault isolation: a transient read error on one file must
    not lose the other files' already-scanned lines, and the failed
    file's cursor stays put for the next wake."""
    eng = GrepEngine("hello", backend="cpu")
    pa, pb = tmp_path / "a.log", tmp_path / "b.log"
    pa.write_bytes(b"hello A\n")
    pb.write_bytes(b"hello B\n")
    sc = FollowScanner(eng, [str(pa), str(pb)])
    real = eng.scan_file_suffix
    boom = {str(pb)}

    def flaky(p, offset, **kw):
        if str(p) in boom:
            raise OSError("transient")
        return real(p, offset, **kw)

    monkeypatch.setattr(eng, "scan_file_suffix", flaky)
    groups = sc.poll_once()
    assert _streamed(groups) == [(1, b"hello A")]
    assert sc.cursors[str(pb)].offset == 0  # untouched, retried next wake
    boom.clear()
    assert _streamed(sc.poll_once()) == [(1, b"hello B")]


# --------------------------------------------------------- durability
def _mk_cfg(path: str, work_dir: str, **opts) -> JobConfig:
    return JobConfig(
        input_files=[path],
        application="distributed_grep_tpu.apps.grep_tpu",
        app_options={"pattern": "hello", "backend": "cpu", **opts},
        work_dir=work_dir,
        follow=True,
    )


def test_runner_restart_resumes_cursors_no_dup_no_loss(tmp_path):
    """FollowRunner crash/restart (in-process): a second runner over the
    same workdir resumes from the journaled cursors — the union of
    records streamed across both lives equals the oracle exactly, with
    no duplicate and no lost line, and sequence numbers continue."""
    log_path = tmp_path / "app.log"
    log_path.write_bytes(b"hello one\nmiss\n")
    cfg = _mk_cfg(str(log_path), str(tmp_path / "wd"))
    r1 = FollowRunner("job-t", cfg, tmp_path / "wd")
    assert r1.wake_once() == 1
    with open(log_path, "ab") as f:
        f.write(b"hello two\n")
    assert r1.wake_once() == 1
    recs1, _n1, _d1 = r1.ring.read_since(0, timeout=0)
    # simulate a crash: NO close — the fsync'd journal is all that survives
    del r1
    with open(log_path, "ab") as f:
        f.write(b"hello three\nhello four\n")
    r2 = FollowRunner("job-t", cfg, tmp_path / "wd")
    assert r2.resumed
    assert r2.wake_once() == 2
    recs2, _n2, _d2 = r2.ring.read_since(recs1[-1]["seq"], timeout=0)
    seen = recs1 + recs2
    assert [(r["line"], r["text"]) for r in seen] == [
        (1, "hello one"), (3, "hello two"),
        (4, "hello three"), (5, "hello four"),
    ]
    seqs = [r["seq"] for r in seen]
    assert seqs == sorted(set(seqs))  # continuous, no duplicate seq
    r2.close()


def test_follow_log_replay_tolerates_torn_tail(tmp_path):
    """A wake line torn by a crash mid-fsync neither advances the cursor
    nor replays its records (journal-before-publish: nobody ever saw
    them) — the next runner re-scans and re-emits exactly once."""
    log_path = tmp_path / "app.log"
    log_path.write_bytes(b"hello a\nhello b\n")
    cfg = _mk_cfg(str(log_path), str(tmp_path / "wd"))
    r1 = FollowRunner("job-t", cfg, tmp_path / "wd")
    r1.wake_once()
    # tear the last journal line (crash mid-append)
    jp = tmp_path / "wd" / FollowLog.FILENAME
    raw = jp.read_bytes()
    jp.write_bytes(raw[: len(raw) - 9])  # chop inside the last record
    del r1
    r2 = FollowRunner("job-t", cfg, tmp_path / "wd")
    assert not r2.resumed  # the only wake line tore: fresh cursors
    assert r2.wake_once() == 2
    recs, _n, _d = r2.ring.read_since(0, timeout=0)
    assert [(r["line"], r["text"]) for r in recs] == [
        (1, "hello a"), (2, "hello b"),
    ]
    r2.close()


def test_journal_failure_rolls_cursor_back_no_lost_line(tmp_path,
                                                        monkeypatch):
    """A journal write failing mid-wake (disk-full blip) must not lose
    lines LIVE: the un-journaled groups' cursors roll back, nothing was
    published for them, and the next healthy wake re-emits exactly."""
    log_path = tmp_path / "app.log"
    log_path.write_bytes(b"hello one\nhello two\n")
    cfg = _mk_cfg(str(log_path), str(tmp_path / "wd"))
    r = FollowRunner("job-j", cfg, tmp_path / "wd")
    orig = r._log.record_wake

    def failing(*a, **kw):
        raise OSError("disk full")

    monkeypatch.setattr(r._log, "record_wake", failing)
    with pytest.raises(OSError):
        r.wake_once()
    recs, _n, _d = r.ring.read_since(0, timeout=0)
    assert recs == []  # nothing published for the failed journal line
    monkeypatch.setattr(r._log, "record_wake", orig)
    assert r.wake_once() == 2  # cursor rolled back: the wake re-emits
    recs, _n, _d = r.ring.read_since(0, timeout=0)
    assert [(x["line"], x["text"]) for x in recs] == [
        (1, "hello one"), (2, "hello two"),
    ]
    r.close()


def test_journal_landed_but_fsync_failed_retry_no_dup_seq(tmp_path,
                                                          monkeypatch):
    """The write-succeeded/fsync-failed variant: the rollback makes the
    retried wake re-journal the SAME records under the SAME seq0 — two
    identical wake lines on disk.  Replay dedups by seq (first wins), so
    a restarted ring keeps the contiguous-seq invariant and subscribers
    see each line exactly once."""
    log_path = tmp_path / "app.log"
    log_path.write_bytes(b"hello a\nhello b\n")
    cfg = _mk_cfg(str(log_path), str(tmp_path / "wd"))
    r1 = FollowRunner("job-f", cfg, tmp_path / "wd")
    orig = r1._log.record_wake

    def landed_then_failed(*a, **kw):
        orig(*a, **kw)  # the line IS durable ...
        raise OSError("fsync failed")  # ... but the caller must assume not

    monkeypatch.setattr(r1._log, "record_wake", landed_then_failed)
    with pytest.raises(OSError):
        r1.wake_once()
    monkeypatch.setattr(r1._log, "record_wake", orig)
    assert r1.wake_once() == 2  # retry re-journals the same seq0
    del r1
    r2 = FollowRunner("job-f", cfg, tmp_path / "wd")
    recs, _n, _d = r2.ring.read_since(0, timeout=0)
    assert [(x["seq"], x["line"], x["text"]) for x in recs] == [
        (1, 1, "hello a"), (2, 2, "hello b"),
    ]
    r2.close()


def test_torn_journal_line_reopens_before_next_append(tmp_path):
    """A failed wake may leave a torn line mid-file; the next wake must
    reopen the log (truncating the fragment) instead of gluing onto it —
    otherwise replay discards every later line."""
    log_path = tmp_path / "app.log"
    log_path.write_bytes(b"hello a\n")
    cfg = _mk_cfg(str(log_path), str(tmp_path / "wd"))
    r1 = FollowRunner("job-g", cfg, tmp_path / "wd")
    assert r1.wake_once() == 1
    # simulate the torn write the failure path leaves behind
    with open(tmp_path / "wd" / FollowLog.FILENAME, "ab") as f:
        f.write(b'{"kind": "wa')
    r1._log_dirty = True
    with open(log_path, "ab") as f:
        f.write(b"hello b\n")
    assert r1.wake_once() == 1  # reopen truncated the fragment first
    del r1
    r2 = FollowRunner("job-g", cfg, tmp_path / "wd")
    assert r2.resumed
    recs, _n, _d = r2.ring.read_since(0, timeout=0)
    assert [(x["line"], x["text"]) for x in recs] == [
        (1, "hello a"), (2, "hello b"),
    ]
    r2.close()


def test_follow_log_compaction_bounds_disk_and_replay(tmp_path,
                                                      monkeypatch):
    """A long-streaming standing query's wake log compacts at restart:
    disk shrinks to the bounded snapshot, replay memory is capped by
    REPLAY_TAIL_RECORDS, and cursors/seqs/records survive exactly —
    including across a post-compaction append and ANOTHER restart."""
    monkeypatch.setattr(FollowLog, "COMPACT_BYTES", 256)
    monkeypatch.setattr(FollowLog, "REPLAY_TAIL_RECORDS", 4)
    log_path = tmp_path / "app.log"
    log_path.write_bytes(b"")
    cfg = _mk_cfg(str(log_path), str(tmp_path / "wd"))
    r1 = FollowRunner("job-c", cfg, tmp_path / "wd")
    for i in range(10):
        with open(log_path, "ab") as f:
            f.write(b"hello %d\n" % i)
        assert r1.wake_once() == 1
    jp = tmp_path / "wd" / FollowLog.FILENAME
    big = jp.stat().st_size
    assert big > 256
    del r1
    r2 = FollowRunner("job-c", cfg, tmp_path / "wd")  # compacts at init
    assert jp.stat().st_size < big
    assert r2.resumed
    # only the bounded tail is preserved; the reader learns what it lost
    recs, _n, dropped = r2.ring.read_since(0, timeout=0)
    assert dropped == 6 and [x["seq"] for x in recs] == [7, 8, 9, 10]
    # the cursor survived compaction: a new append scans from line 11
    with open(log_path, "ab") as f:
        f.write(b"hello post\n")
    assert r2.wake_once() == 1
    del r2
    r3 = FollowRunner("job-c", cfg, tmp_path / "wd")  # replay compacted+appended
    recs3, _n3, d3 = r3.ring.read_since(7, timeout=0)  # tail cap keeps 8..11
    assert d3 == 0
    assert [(x["seq"], x["line"], x["text"]) for x in recs3] == [
        (8, 8, "hello 7"), (9, 9, "hello 8"),
        (10, 10, "hello 9"), (11, 11, "hello post"),
    ]
    r3.close()


# ------------------------------------------------------------ streaming
def test_stream_ring_sheds_oldest_with_dropped_count():
    ring = StreamRing(cap_bytes=600)
    for i in range(50):
        ring.publish([{"file": "f", "line": i + 1, "text": "x" * 40}])
    recs, nxt, dropped = ring.read_since(0, timeout=0)
    assert recs, "tail must survive"
    first = recs[0]["seq"]
    assert first > 1 and dropped == first - 1  # explicit shed count
    assert nxt == recs[-1]["seq"] == 50
    # a keeping-up consumer sees no drop marker
    recs2, _nxt2, dropped2 = ring.read_since(first, timeout=0)
    assert dropped2 == 0
    assert follow_counters()["stream_dropped_records"] == dropped


def test_stream_ring_longpoll_wakes_on_publish():
    ring = StreamRing(cap_bytes=1 << 20)
    got: list = []

    def reader():
        recs, _n, _d = ring.read_since(0, timeout=5.0)
        got.extend(recs)

    t = threading.Thread(target=reader)
    t.start()
    time.sleep(0.1)
    ring.publish([{"file": "f", "line": 1, "text": "hello"}])
    t.join(timeout=5.0)
    assert [r["seq"] for r in got] == [1]


# ------------------------------------------------------------- service
@pytest.fixture()
def follow_service(tmp_path, monkeypatch):
    monkeypatch.setenv("DGREP_FOLLOW_POLL_S", "0.05")
    from distributed_grep_tpu.runtime.service import GrepService, ServiceServer

    svc = GrepService(work_root=tmp_path / "svc")
    srv = ServiceServer(svc)
    srv.start()
    yield svc, srv, tmp_path
    srv.shutdown()
    svc.stop()


def _http(method: str, url: str, body: bytes | None = None,
          timeout: float = 10.0) -> dict:
    import urllib.request

    req = urllib.request.Request(url, data=body, method=method)
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return json.loads(r.read())


def test_service_follow_stream_and_status(follow_service):
    svc, srv, tmp_path = follow_service
    base = f"http://127.0.0.1:{srv.port}"
    # follow-off wire pin: no standing queries, no "follow" key anywhere
    assert "follow" not in _http("GET", f"{base}/status")
    log_path = tmp_path / "app.log"
    log_path.write_bytes(b"hello one\nmiss\n")
    cfg = _mk_cfg(str(log_path), "ignored")
    jid = _http("POST", f"{base}/jobs",
                cfg.to_json().encode("utf-8"))["job_id"]
    r = _http("GET", f"{base}/jobs/{jid}/stream?cursor=0&timeout=5")
    assert [(x["line"], x["text"]) for x in r["records"]] == [(1, "hello one")]
    with open(log_path, "ab") as f:
        f.write(b"hello two\n")
    r2 = _http("GET",
               f"{base}/jobs/{jid}/stream?cursor={r['next']}&timeout=5")
    assert [(x["line"], x["text"]) for x in r2["records"]] == [
        (3, "hello two")
    ]
    st = _http("GET", f"{base}/status")
    assert st["follow"]["standing"] == 1 and st["follow"]["follow_wakes"] >= 1
    js = _http("GET", f"{base}/jobs/{jid}")
    assert js["follow"]["wakes"] >= 1 and js["state"] == "running"
    # /stream on a batch job answers 409
    import urllib.error

    plain = tmp_path / "plain.txt"
    plain.write_text("hello\n")
    bcfg = JobConfig(input_files=[str(plain)],
                     application="distributed_grep_tpu.apps.grep_tpu",
                     app_options={"pattern": "hello", "backend": "cpu"})
    bjid = _http("POST", f"{base}/jobs",
                 bcfg.to_json().encode("utf-8"))["job_id"]
    with pytest.raises(urllib.error.HTTPError) as ei:
        _http("GET", f"{base}/jobs/{bjid}/stream?cursor=0&timeout=0")
    assert ei.value.code == 409
    # cancel the standing query: stream answers drain + terminal state
    _http("POST", f"{base}/jobs/{jid}/cancel", b"")
    r3 = _http("GET",
               f"{base}/jobs/{jid}/stream?cursor={r['next']}&timeout=0")
    assert r3["state"] == "cancelled"


def test_service_follow_validation(follow_service):
    import urllib.error

    svc, srv, tmp_path = follow_service
    base = f"http://127.0.0.1:{srv.port}"
    log_path = tmp_path / "v.log"
    log_path.write_bytes(b"x\n")
    bad = _mk_cfg(str(log_path), "ignored", word_regexp=True)
    with pytest.raises(urllib.error.HTTPError) as ei:
        _http("POST", f"{base}/jobs", bad.to_json().encode("utf-8"))
    assert ei.value.code == 400
    no_pat = JobConfig(input_files=[str(log_path)],
                       application="distributed_grep_tpu.apps.grep_tpu",
                       app_options={"backend": "cpu"}, follow=True)
    with pytest.raises(urllib.error.HTTPError) as ei:
        _http("POST", f"{base}/jobs", no_pat.to_json().encode("utf-8"))
    assert ei.value.code == 400


def test_stream_on_queued_follow_job_answers_empty_page(tmp_path,
                                                        monkeypatch):
    """A follow job parked in the admission queue has no runner yet:
    /stream answers an empty page with state "queued" (the subscriber
    polls again), never the misleading non-follow 409."""
    monkeypatch.setenv("DGREP_FOLLOW_POLL_S", "0.05")
    from distributed_grep_tpu.runtime.service import GrepService

    svc = GrepService(work_root=tmp_path / "svc", max_jobs=1)
    try:
        log_path = tmp_path / "q.log"
        log_path.write_bytes(b"hello\n")
        first = svc.submit(_mk_cfg(str(log_path), "ignored"))
        queued = svc.submit(_mk_cfg(str(log_path), "ignored"))
        page = svc.job_stream(queued, cursor=0, timeout=0)
        assert page["records"] == [] and page["next"] == 0
        assert str(page["state"]) == "queued"
        # the running one streams normally
        assert svc.job_status(first)["state"] == "running"
    finally:
        svc.stop()


def test_follow_engine_build_failure_fails_job(tmp_path, monkeypatch):
    """A pattern that passes submit validation but cannot compile fails
    the job from the runner thread — the on_fail path runs the close
    flush ON that thread (the current-thread join guard), the job lands
    FAILED with the error, and the stream drains terminal."""
    monkeypatch.setenv("DGREP_FOLLOW_POLL_S", "0.05")
    from distributed_grep_tpu.runtime.service import GrepService

    svc = GrepService(work_root=tmp_path / "svc")
    try:
        log_path = tmp_path / "b.log"
        log_path.write_bytes(b"x\n")
        jid = svc.submit(_mk_cfg(str(log_path), "ignored",
                                 pattern="(unbalanced"))
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline:
            st = svc.job_status(jid)
            if st["state"] == "failed":
                break
            time.sleep(0.05)
        assert st["state"] == "failed" and st["error"]
        page = svc.job_stream(jid, cursor=0, timeout=0)
        assert page["records"] == [] and str(page["state"]) == "failed"
    finally:
        svc.stop()


def test_follow_wire_shape_elides_at_defaults():
    """Round-17 wire pin: a follow-free JobConfig serializes byte-
    identically to the pre-follow dataclass (no new keys), and the
    fields round-trip when set."""
    d = json.loads(JobConfig(input_files=["/x"]).to_json())
    assert "follow" not in d and "follow_poll_s" not in d
    d2 = json.loads(JobConfig(input_files=["/x"], follow=True).to_json())
    assert d2["follow"] is True and "follow_poll_s" not in d2
    cfg = JobConfig.from_json(
        JobConfig(input_files=["/x"], follow=True,
                  follow_poll_s=0.25).to_json()
    )
    assert cfg.follow and cfg.follow_poll_s == 0.25


def test_stale_summary_never_prunes_standing_query(tmp_path):
    """Index-tier pin: a persisted trigram summary built BEFORE an append
    must not hide the appended match — the follow path never consults
    the index at all, and the batch path's fresh-stat revalidation
    treats the append as drift (clean miss)."""
    from distributed_grep_tpu.index import summary as index_summary

    path = tmp_path / "shard.txt"
    path.write_bytes(b"nothing of note here\nmore filler\n")
    store_dir = tmp_path / "index"
    index_summary.attach_store(str(store_dir))
    eng = GrepEngine("zebraword", backend="cpu", corpus_bytes=1 << 20)
    res = eng.scan_file(str(path))
    assert res.n_matches == 0
    # a second scan may now prune via the stored summary — then APPEND
    with open(path, "ab") as f:
        f.write(b"zebraword appears\n")
    sc = FollowScanner(eng, [str(path)])
    sc.cursors[str(path)].offset = 0  # standing query starting at 0
    recs = _streamed(sc.poll_once())
    assert recs == [(3, b"zebraword appears")]
    # batch path agrees after the drift (fresh-stat revalidation)
    res2 = eng.scan_file(str(path))
    assert res2.n_matches == 1


# ------------------------------------------------------------ telemetry
def test_follow_counters_ride_engine_stats_and_are_gated(tmp_path):
    eng = GrepEngine("hello", backend="cpu")
    path = tmp_path / "c.log"
    path.write_bytes(b"hello\n")
    sc = FollowScanner(eng, [str(path)])
    sc.poll_once()
    c = follow_counters()
    assert c["follow_wakes"] == 1 and c["suffix_bytes_scanned"] == 6
    # the next scan's stats tail merges the module counters (engine-stats
    # + heartbeat piggyback surface)
    eng.scan(b"hello again\n")
    assert eng.stats.get("follow_wakes") == 1


# ------------------------------------------------------------ CLI e2e
def test_cli_follow_matches_one_shot_oracle(tmp_path, monkeypatch, capsys):
    monkeypatch.setenv("DGREP_FOLLOW_POLL_S", "0.05")
    from distributed_grep_tpu.__main__ import main

    path = tmp_path / "cli.log"
    path.write_bytes(b"hello first\nmiss\n")

    def appender():
        time.sleep(0.15)
        with open(path, "ab") as f:
            f.write(b"hello sec")
        time.sleep(0.15)
        with open(path, "ab") as f:
            f.write(b"ond\nhello tail")

    t = threading.Thread(target=appender)
    t.start()
    rc = main(["grep", "--follow", "--follow-idle-s", "0.8", "hello",
               str(path)])
    t.join()
    out = capsys.readouterr().out
    want = [
        f"{path} (line number #1) hello first",
        f"{path} (line number #3) hello second",
        f"{path} (line number #4) hello tail",
    ]
    assert out.splitlines() == want
    assert rc == 0


def test_cli_follow_count_mode(tmp_path, monkeypatch, capsys):
    monkeypatch.setenv("DGREP_FOLLOW_POLL_S", "0.05")
    from distributed_grep_tpu.__main__ import main

    path = tmp_path / "cnt.log"
    path.write_bytes(b"hello\nmiss\nhello\n")
    rc = main(["grep", "--follow", "--follow-idle-s", "0.3", "-c", "hello",
               str(path)])
    out = capsys.readouterr().out
    assert out.strip() == "2"
    assert rc == 0


def test_cli_follow_relative_path_display_matches_one_shot(
        tmp_path, monkeypatch, capsys):
    """The printed filename prefix matches the one-shot run byte for
    byte on a relative-path invocation (both resolve to the absolute
    path — the repo-wide display convention)."""
    monkeypatch.setenv("DGREP_FOLLOW_POLL_S", "0.05")
    monkeypatch.chdir(tmp_path)
    from distributed_grep_tpu.__main__ import main

    Path("rel.log").write_bytes(b"hello rel\n")
    rc = main(["grep", "--follow", "--follow-idle-s", "0.2", "hello",
               "rel.log"])
    follow_out = capsys.readouterr().out
    assert rc == 0
    rc2 = main(["grep", "hello", "rel.log"])
    assert rc2 == 0
    assert follow_out == capsys.readouterr().out
    assert follow_out.startswith(str(tmp_path / "rel.log"))


def test_cli_follow_finalize_drains_past_wake_cap(tmp_path, monkeypatch,
                                                  capsys):
    """The exit finalize loops until nothing drains: a writer that raced
    more than one per-wake read window ahead of the last wake still gets
    every line printed (the one-shot oracle contract holds at exit)."""
    from distributed_grep_tpu.runtime import follow as follow_mod

    monkeypatch.setenv("DGREP_FOLLOW_POLL_S", "0.05")
    monkeypatch.setattr(follow_mod, "MAX_WAKE_BYTES", 64)
    from distributed_grep_tpu.__main__ import main

    path = tmp_path / "burst.log"
    # > 4 windows of matching lines, unterminated tail included
    body = b"".join(b"hello line %02d\n" % i for i in range(20))
    path.write_bytes(body + b"hello tail")
    rc = main(["grep", "--follow", "--follow-idle-s", "0.15", "-h",
               "hello", str(path)])
    out = capsys.readouterr().out
    assert rc == 0
    assert len(out.splitlines()) == 21  # all 20 lines + the tail


def test_cli_stream_and_follow_print_reset_notice(tmp_path, monkeypatch,
                                                  capsys):
    """Truncation mid-follow surfaces as a stderr notice (tail parity) —
    the consumer learns the line numbers restarted for a new file
    generation — while stdout keeps only match lines."""
    monkeypatch.setenv("DGREP_FOLLOW_POLL_S", "0.05")
    from distributed_grep_tpu.__main__ import main

    path = tmp_path / "rot.log"
    path.write_bytes(b"hello old\n")

    def truncator():
        time.sleep(0.2)
        path.write_bytes(b"hello x\n")  # strictly SHORTER: size < cursor

    t = threading.Thread(target=truncator)
    t.start()
    rc = main(["grep", "--follow", "--follow-idle-s", "0.6", "-h",
               "hello", str(path)])
    t.join()
    cap = capsys.readouterr()
    assert rc == 0
    assert cap.out.splitlines() == [
        "(line number #1) hello old", "(line number #1) hello x",
    ]
    assert "truncated or replaced" in cap.err


def test_cli_follow_rejects_unsupported_modes(tmp_path, capsys):
    from distributed_grep_tpu.__main__ import main

    path = tmp_path / "x.log"
    path.write_text("hello\n")
    assert main(["grep", "--follow", "-o", "hello", str(path)]) == 2
    assert main(["grep", "--follow", "-C", "1", "hello", str(path)]) == 2
    assert main(["grep", "--follow", "hello", "-"]) == 2


# ------------------------------------------------------- chaos (restart)
def test_daemon_sigkill_restart_resumes_stream(tmp_path):
    """The round-17 chaos leg: SIGKILL the daemon mid-stream, restart on
    the same work root, and the union of records collected across both
    daemon lives equals the oracle — no duplicate, no lost line."""
    import sys

    sys.path.insert(0, str(Path(__file__).parent))
    import service_proc

    log_path = tmp_path / "app.log"
    log_path.write_bytes(b"")
    proc = service_proc.ServiceProc(
        tmp_path / "root", workers=0,
        env={"DGREP_FOLLOW_POLL_S": "0.05"},
    )
    (tmp_path / "root").mkdir(parents=True, exist_ok=True)
    proc.start()
    collected: dict[int, tuple] = {}
    cursor = 0

    def drain(deadline_s: float = 8.0, want: int = 0) -> None:
        nonlocal cursor
        deadline = time.monotonic() + deadline_s
        while time.monotonic() < deadline:
            try:
                r = service_proc._http_json(
                    "GET",
                    f"{proc.base}/jobs/{jid}/stream"
                    f"?cursor={cursor}&timeout=0.5",
                )
            except OSError:
                time.sleep(0.1)
                continue
            for rec in r["records"]:
                assert rec["seq"] not in collected, "duplicate seq"
                collected[rec["seq"]] = (rec["line"], rec["text"])
            cursor = r["next"]
            if want and len(collected) >= want:
                return
            if not want:
                return
        raise TimeoutError(
            f"stream stuck at {len(collected)}/{want}: {proc.tail_log()}"
        )

    try:
        cfg = _mk_cfg(str(log_path), "ignored")
        jid = proc.submit(cfg)
        with open(log_path, "ab") as f:
            f.write(b"".join(b"hello %d\n" % i for i in range(10)))
        drain(want=10)
        proc.sigkill()
        with open(log_path, "ab") as f:  # appends land while the daemon is down
            f.write(b"".join(b"hello %d\n" % i for i in range(10, 15)))
        proc.start()  # resume: registry replays, cursors reload
        with open(log_path, "ab") as f:
            f.write(b"".join(b"hello %d\n" % i for i in range(15, 20)))
        drain(deadline_s=15.0, want=20)
    finally:
        proc.terminate()
    got = [collected[s] for s in sorted(collected)]
    assert got == [(i + 1, "hello %d" % i) for i in range(20)]


def test_stream_stale_cursor_dropped_across_daemon_restart(tmp_path,
                                                           monkeypatch):
    """Satellite pin (round 18): a subscriber reconnecting with a STALE
    cursor after a ring shed gets the explicit ``dropped`` count — and
    still gets it ACROSS a daemon restart: StreamRing.preload re-seeds
    the replayed journal tail under the same byte cap, so the restarted
    /stream page reports what the bounded ring no longer holds instead
    of silently renumbering or starting empty."""
    monkeypatch.setenv("DGREP_FOLLOW_POLL_S", "0.05")
    monkeypatch.setenv("DGREP_STREAM_BUFFER", "600")
    from distributed_grep_tpu.runtime.service import GrepService

    log_path = tmp_path / "app.log"
    log_path.write_bytes(b"".join(
        b"hello %02d %s\n" % (i, b"x" * 40) for i in range(50)
    ))
    cfg = _mk_cfg(str(log_path), "ignored")
    svc_a = GrepService(work_root=tmp_path / "svc")
    jid = svc_a.submit(cfg)
    deadline = time.monotonic() + 30
    page = {}
    while page.get("next") != 50:
        assert time.monotonic() < deadline, page
        page = svc_a.job_stream(jid, cursor=0, timeout=0.2)
    # same-life shed: the 600-byte ring kept only a tail; a stale
    # cursor=0 reader learns exactly how much it lost
    assert page["dropped"] > 0
    first_live = page["records"][0]["seq"]
    assert page["dropped"] == first_live - 1
    # daemon "crash": stop only the wake loop — no cancel record, the
    # registry row stays RUNNING (the abandoned-not-stopped idiom; a
    # graceful stop would record CANCELLED and nothing would resume)
    svc_a.record(jid).follow.request_stop()
    time.sleep(0.3)

    svc_b = GrepService(work_root=tmp_path / "svc")
    try:
        deadline = time.monotonic() + 30
        page2 = {}
        while page2.get("next") != 50:
            assert time.monotonic() < deadline, page2
            page2 = svc_b.job_stream(jid, cursor=0, timeout=0.2)
        # the stale cursor's explicit dropped count survived the restart
        assert page2["dropped"] > 0
        assert page2["records"][0]["seq"] == page2["dropped"] + 1
        # sequence numbers are the SAME stream, not a renumbering: the
        # retained tail ends at the pre-crash high-water seq
        assert page2["records"][-1]["seq"] == 50
        # a caught-up cursor sees no drop marker after the restart either
        page3 = svc_b.job_stream(jid, cursor=page2["records"][0]["seq"],
                                 timeout=0)
        assert "dropped" not in page3
    finally:
        svc_b.stop()
