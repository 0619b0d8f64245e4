"""Fused follow tier (round 21, runtime/follow.py FollowGroup*): one
suffix scan per (file, wake) serves K standing queries.  Pins fused ==
solo byte identity across query families (anchors, ^$, re-fallback,
pattern sets, ignore_case), counter flatness in K, join-mid-stream
catch-up, leave/cancel shrink, truncation demotion isolation, per-member
journal-fault demotion, the DGREP_FOLLOW_FUSE=0 true-no-op pin, the
/status group rows + dgrep top rendering, fuse:wake explain routing, and
the SIGKILL-mid-wake daemon-restart chaos leg.

Standalone: ``python -m pytest tests/test_follow_fuse.py -q`` (CPU-only).
Marker: ``follow`` (rides the round-17 tier's marker).
"""

from __future__ import annotations

import json
import time
from pathlib import Path

import pytest

from distributed_grep_tpu.ops.engine import GrepEngine
from distributed_grep_tpu.runtime.follow import (
    FollowGroupRegistry,
    FollowRunner,
    follow_counters,
    follow_counters_clear,
    follow_fused_counters,
    follow_fused_counters_clear,
)
from distributed_grep_tpu.utils.config import JobConfig

pytestmark = pytest.mark.follow


# ---------------------------------------------------------------- helpers
def _mk_cfg(path, work_dir: str, **opts) -> JobConfig:
    app_options = {"backend": "cpu", **opts}
    if "pattern" not in app_options and "patterns" not in app_options:
        app_options["pattern"] = "hello"
    files = path if isinstance(path, list) else [str(path)]
    return JobConfig(
        input_files=[str(f) for f in files],
        application="distributed_grep_tpu.apps.grep_tpu",
        app_options=app_options,
        work_dir=work_dir,
        follow=True,
    )


def _mk_runner(tmp_path, tag: str, log_path, reg=None, **opts):
    wd = tmp_path / f"wd-{tag}"
    cfg = _mk_cfg(log_path, str(wd), **opts)
    return FollowRunner(f"job-{tag}", cfg, wd, groups=reg)


def _records(runner) -> list[dict]:
    recs, _next, _dropped = runner.ring.read_since(0, timeout=0)
    return recs


def _lt(recs: list[dict]) -> list[tuple[int, str]]:
    return [(r["line"], r["text"]) for r in recs if "text" in r]


def _oracle(opts: dict, data: bytes) -> list[tuple[int, str]]:
    """(line, text) a one-shot scan over the final bytes selects — the
    contract each tenant's stream must equal regardless of routing."""
    from distributed_grep_tpu.ops import lines as lines_mod

    kw = {"backend": "cpu", "ignore_case": bool(opts.get("ignore_case"))}
    if opts.get("patterns"):
        kw["patterns"] = list(opts["patterns"])
    else:
        kw["pattern"] = opts["pattern"]
    eng = GrepEngine(**kw)
    res = eng.scan(data)
    nl = lines_mod.newline_index(data)
    out = []
    for ln in res.matched_lines.tolist():
        s, e = lines_mod.line_span(nl, int(ln), len(data))
        out.append((int(ln), data[s:e].decode("utf-8", "surrogateescape")))
    return out


# Append stages exercising the boundary shapes round 17 pinned: catch-up
# over existing content, a mid-line split + its completion, an exact-line
# append, an empty append, an empty LINE, and an unterminated tail.
STAGES = [
    b"hello start\nhallo there\nmiss\n",
    b"partial hel",
    b"lo end\nab zz q volcano needle\n",
    b"hello exactly one helloo line\n",
    b"",
    b"\nends with HELLO\n",
    b"tail hello no newline",
]

QUERIES = [
    ("literal", {"pattern": "hello"}),
    ("nfa", {"pattern": "h[ae]llo+"}),
    ("anchor_start", {"pattern": "^hello"}),
    ("anchor_end", {"pattern": "hello$"}),
    ("empty_line", {"pattern": "^$"}),
    ("pairset", {"patterns": ["ab", "zz", "q"]}),
    ("set", {"patterns": ["hello", "needle"]}),
    ("re_fallback", {"pattern": "hello(?! tail)"}),
    ("ignore_case", {"pattern": "HELLO", "ignore_case": True}),
]


@pytest.mark.parametrize("label,opts", QUERIES, ids=[q[0] for q in QUERIES])
def test_fused_equals_solo_and_oracle(tmp_path, label, opts):
    """The load-bearing identity: a tenant inside a fused group streams
    byte-identically to its own solo runner AND to the one-shot oracle,
    for every union-hostable query shape — the co-tenant's query never
    bleeds into the confirm."""
    co = {"pattern": "volcano"}
    solo_log = tmp_path / "solo.log"
    fused_log = tmp_path / "fused.log"
    solo_log.write_bytes(b"")
    fused_log.write_bytes(b"")

    solo = [_mk_runner(tmp_path, f"s{i}", solo_log, None, **o)
            for i, o in enumerate((opts, co))]
    reg = FollowGroupRegistry(start_threads=False, auto_solo=False)
    fused = [_mk_runner(tmp_path, f"f{i}", fused_log, reg, **o)
             for i, o in enumerate((opts, co))]
    for r in fused:
        assert reg.adopt(r)
    (group,) = reg._groups.values()

    for stage in STAGES:
        for p in (solo_log, fused_log):
            with open(p, "ab") as f:
                f.write(stage)
        for r in solo:
            r.wake_once()
        group.wake_once()

    final = b"".join(STAGES)
    terminated = final[: final.rfind(b"\n") + 1]
    for s, f, o in zip(solo, fused, (opts, co)):
        assert _lt(_records(f)) == _lt(_records(s)) == _oracle(o, terminated)
        assert f.fused
    for r in solo + fused:
        r.close()


def test_counters_flat_in_k(tmp_path):
    """The perf contract the benchmark receipts: K fused tenants cost ONE
    wake + one suffix read per (file, wake) — base counters flat in K,
    the saved counter pricing the (K-1) avoided re-scans — while K solo
    runners pay K of everything."""
    K = 4
    log = tmp_path / "app.log"
    log.write_bytes(b"")
    reg = FollowGroupRegistry(start_threads=False, auto_solo=False)
    runners = [
        _mk_runner(tmp_path, f"k{i}", log, reg, pattern=f"t{i}mark")
        for i in range(K)
    ]
    for r in runners:
        assert reg.adopt(r)
    (group,) = reg._groups.values()
    stages = [b"".join(b"t%dmark line %d\n" % (i, s) for i in range(K))
              for s in range(3)]
    for stage in stages:
        with open(log, "ab") as f:
            f.write(stage)
        group.wake_once()
    total = sum(len(s) for s in stages)
    base = follow_counters()
    assert base["follow_wakes"] == 3  # one per group wake, NOT K
    assert base["suffix_bytes_scanned"] == total  # each byte read ONCE
    fstats = follow_fused_counters()
    assert fstats["follow_fused_queries"] == K
    assert fstats["follow_fused_wakes"] == 3
    assert fstats["follow_suffix_bytes_saved"] == total * (K - 1)
    row = group.status()
    assert row["members"] == K and row["files"] == 1
    assert row["wakes"] == 3 and row["wake_lag_s"] >= 0.0
    for r in runners:
        assert _lt(_records(r)) == [(s * K + i + 1, f"t{i}mark line {s}")
                                    for i, s in [(int(r.job_id[5:]), st)
                                                 for st in range(3)]]
        r.close()

    # the solo control: K independent runners re-read everything K times
    follow_counters_clear()
    follow_fused_counters_clear()
    log2 = tmp_path / "solo.log"
    log2.write_bytes(b"")
    solos = [_mk_runner(tmp_path, f"q{i}", log2, None, pattern=f"t{i}mark")
             for i in range(K)]
    for stage in stages:
        with open(log2, "ab") as f:
            f.write(stage)
        for r in solos:
            r.wake_once()
    base = follow_counters()
    assert base["follow_wakes"] == 3 * K
    assert base["suffix_bytes_scanned"] == total * K
    assert follow_fused_counters() == {}
    for r in solos:
        r.close()


def test_join_mid_stream_catches_up_then_fuses(tmp_path):
    """A tenant joining a live group solo-catches-up from its durable
    cursor to the group cursor on the group thread, then fuses: its
    stream equals the oracle over everything, the incumbent sees no
    duplicate, and subsequent appends ride the shared scan."""
    log = tmp_path / "app.log"
    log.write_bytes(b"hello one\nmiss\n")
    reg = FollowGroupRegistry(start_threads=False, auto_solo=False)
    r1 = _mk_runner(tmp_path, "a", log, reg, pattern="hello")
    assert reg.adopt(r1)
    (group,) = reg._groups.values()
    group.wake_once()
    with open(log, "ab") as f:
        f.write(b"hello two\n")
    group.wake_once()
    assert _lt(_records(r1)) == [(1, "hello one"), (3, "hello two")]

    r2 = _mk_runner(tmp_path, "b", log, reg, pattern="hello")
    assert reg.adopt(r2)
    assert not r2.fused  # catching up until its cursors align
    group.wake_once()  # catch-up: r2 replays 0 -> group cursor, solo path
    assert _lt(_records(r2)) == [(1, "hello one"), (3, "hello two")]

    with open(log, "ab") as f:
        f.write(b"hello three\n")
    group.wake_once()  # aligned now: r2 fuses, then rides the shared scan
    assert r2.fused
    want = [(1, "hello one"), (3, "hello two"), (4, "hello three")]
    assert _lt(_records(r1)) == want
    assert _lt(_records(r2)) == want  # no dup from the catch-up boundary
    # the group consumed "hello three\n" once for both
    assert follow_fused_counters()["follow_suffix_bytes_saved"] == len(
        b"hello three\n")
    for r in (r1, r2):
        r.close()


def test_leave_shrinks_group_and_last_close_retires_it(tmp_path):
    log = tmp_path / "app.log"
    log.write_bytes(b"hello a\n")
    reg = FollowGroupRegistry(start_threads=False, auto_solo=False)
    rs = [_mk_runner(tmp_path, f"m{i}", log, reg, pattern="hello")
          for i in range(3)]
    for r in rs:
        assert reg.adopt(r)
    (group,) = reg._groups.values()
    group.wake_once()
    rs[1].close()  # cancel mid-stream: discard detaches under the wake lock
    assert len(group.members()) == 2
    with open(log, "ab") as f:
        f.write(b"hello b\n")
    group.wake_once()
    want = [(1, "hello a"), (2, "hello b")]
    assert _lt(_records(rs[0])) == _lt(_records(rs[2])) == want
    assert _lt(_records(rs[1])) == [(1, "hello a")]  # stopped at leave
    rs[0].close()
    rs[2].close()
    assert reg._groups == {}  # last member's discard retired the group


def test_truncation_demotes_group_and_members_stay_exact(tmp_path):
    """Truncation/replacement falls the watching group's members back to
    their solo runners — each re-detects the reset against its OWN
    durable cursor and re-emits exactly (the solo-tested reset path) —
    while an unrelated group on another file keeps fusing untouched."""
    loga = tmp_path / "a.log"
    logb = tmp_path / "b.log"
    loga.write_bytes(b"hello a1\nhello a2\n")
    logb.write_bytes(b"hello b1\n")
    reg = FollowGroupRegistry(start_threads=False, auto_solo=False)
    ra = [_mk_runner(tmp_path, f"a{i}", loga, reg, pattern="hello")
          for i in range(2)]
    rb = [_mk_runner(tmp_path, f"b{i}", logb, reg, pattern="hello")
          for i in range(2)]
    for r in ra + rb:
        assert reg.adopt(r)
    assert len(reg._groups) == 2
    ga = next(g for g in reg._groups.values()
              if str(loga) in next(iter(g.cursors)))
    gb = next(g for g in reg._groups.values() if g is not ga)
    ga.wake_once()
    gb.wake_once()

    new = b"hello cut\n"  # strictly shorter: size below the group cursor
    loga.write_bytes(new)
    ga.wake_once()  # detects truncation: demotes ALL of group A to solo
    assert all(not r.fused for r in ra)
    assert len(reg._groups) == 1 and gb.key in reg._groups
    for r in ra:  # drive the solo runners (auto_solo=False left them idle)
        r.wake_once()
        recs = _records(r)
        assert {"file": str(loga), "reset": True} in [
            {k: v for k, v in x.items() if k != "seq"} for x in recs
        ]
        assert _lt(recs) == [(1, "hello a1"), (2, "hello a2"),
                             (1, "hello cut")]
    # the OTHER group never noticed: still fused, still shared-scanning
    with open(logb, "ab") as f:
        f.write(b"hello b2\n")
    gb.wake_once()
    for r in rb:
        assert r.fused
        assert _lt(_records(r)) == [(1, "hello b1"), (2, "hello b2")]
    for r in ra + rb:
        r.close()


def test_commit_failure_demotes_only_that_member(tmp_path, monkeypatch):
    """A journal fault on ONE member's fused commit rolls that member's
    cursor back and demotes it alone; the co-tenant keeps fusing.  The
    demoted runner's next solo wake re-emits exactly once — fusion is
    never a correctness dependency."""
    log = tmp_path / "app.log"
    log.write_bytes(b"hello x\n")
    reg = FollowGroupRegistry(start_threads=False, auto_solo=False)
    r1 = _mk_runner(tmp_path, "ok", log, reg, pattern="hello")
    r2 = _mk_runner(tmp_path, "bad", log, reg, pattern="hello")
    assert reg.adopt(r1) and reg.adopt(r2)
    (group,) = reg._groups.values()

    orig = r2._log.record_wake

    def failing(*a, **kw):
        raise OSError("disk full")

    monkeypatch.setattr(r2._log, "record_wake", failing)
    group.wake_once()
    assert _lt(_records(r1)) == [(1, "hello x")]  # co-tenant unaffected
    assert _records(r2) == []  # nothing published for the failed journal
    assert r1.fused and not r2.fused
    assert [m.runner for m in group.members()] == [r1]

    monkeypatch.setattr(r2._log, "record_wake", orig)
    assert r2.wake_once() == 1  # rolled-back cursor: solo re-emits once
    assert _lt(_records(r2)) == [(1, "hello x")]
    with open(log, "ab") as f:
        f.write(b"hello y\n")
    group.wake_once()
    r2.wake_once()
    assert _lt(_records(r1)) == _lt(_records(r2)) == [
        (1, "hello x"), (2, "hello y")
    ]
    r1.close()
    r2.close()


# ------------------------------------------------------------- service
def _drain(svc, jid, want: int, deadline_s: float = 15.0) -> list[dict]:
    out: list[dict] = []
    cursor = 0
    deadline = time.monotonic() + deadline_s
    while len(out) < want:
        assert time.monotonic() < deadline, (jid, out)
        page = svc.job_stream(jid, cursor=cursor, timeout=0.5)
        out.extend(page["records"])
        cursor = page["next"]
    return out


def test_service_fuses_and_status_exposes_groups(tmp_path, monkeypatch):
    monkeypatch.setenv("DGREP_FOLLOW_POLL_S", "0.05")
    from distributed_grep_tpu.runtime.service import GrepService

    log = tmp_path / "app.log"
    log.write_bytes(b"hello t0x\nhello t1x\n")
    svc = GrepService(work_root=tmp_path / "svc")
    try:
        jids = [svc.submit(_mk_cfg(log, "ignored", pattern=f"t{k}x"))
                for k in range(2)]
        pages = [_drain(svc, jid, 1) for jid in jids]
        for k, recs in enumerate(pages):
            assert _lt(recs) == [(k + 1, f"hello t{k}x")]
        st = svc.status()
        fol = st["follow"]
        assert fol["follow_fused_queries"] == 2
        (row,) = fol["groups"]
        assert row["members"] == 2 and sorted(row["jobs"]) == sorted(jids)
        assert "wake_lag_s" in row and row["wake_lag_s"] >= 0.0
        # dgrep top renders the group row (the round-21 small fix)
        from distributed_grep_tpu.__main__ import _render_top

        text = _render_top({"x": st}, "x", {})
        assert "group [" in text and "wake_lag_s=" in text
        # runner rows carry the fused flag (a joiner flips it one wake
        # after its catch-up aligns — poll briefly)
        deadline = time.monotonic() + 10.0
        while not all(svc.job_status(j)["follow"].get("fused")
                      for j in jids):
            assert time.monotonic() < deadline
            time.sleep(0.05)
    finally:
        svc.stop()


def test_follow_fuse_off_is_true_noop(tmp_path, monkeypatch):
    """DGREP_FOLLOW_FUSE=0 pin: no group registry is ever built, runners
    ride the solo path, /status keeps the round-17 follow view byte
    shape (no fused keys, no groups key), and the streamed records equal
    the fused daemon's."""
    monkeypatch.setenv("DGREP_FOLLOW_POLL_S", "0.05")
    from distributed_grep_tpu.runtime.service import GrepService

    log = tmp_path / "app.log"
    log.write_bytes(b"hello t0x\nhello t1x\n")

    monkeypatch.setenv("DGREP_FOLLOW_FUSE", "0")
    svc = GrepService(work_root=tmp_path / "svc-off")
    try:
        jids = [svc.submit(_mk_cfg(log, "ignored", pattern=f"t{k}x"))
                for k in range(2)]
        off_pages = [_lt(_drain(svc, jid, 1)) for jid in jids]
        assert svc._follow_groups is None  # never constructed
        fol = svc.status()["follow"]
        assert "groups" not in fol
        assert not any(k.startswith("follow_fused") for k in fol)
        assert follow_fused_counters() == {}
        assert not any(svc.job_status(j)["follow"].get("fused")
                       for j in jids)
    finally:
        svc.stop()

    monkeypatch.setenv("DGREP_FOLLOW_FUSE", "1")
    svc2 = GrepService(work_root=tmp_path / "svc-on")
    try:
        jids = [svc2.submit(_mk_cfg(log, "ignored", pattern=f"t{k}x"))
                for k in range(2)]
        on_pages = [_lt(_drain(svc2, jid, 1)) for jid in jids]
        assert on_pages == off_pages  # identical streams either way
    finally:
        svc2.stop()


def test_ineligible_configs_stay_solo(tmp_path):
    """Group-ineligible shapes never adopt: count/presence modes (no
    fusion_key), approx, and two spellings of one file — each runs the
    pre-round-21 solo runner."""
    log = tmp_path / "app.log"
    log.write_bytes(b"hello\n")
    reg = FollowGroupRegistry(start_threads=False, auto_solo=False)
    shapes = [
        {"pattern": "hello", "count_only": True},
        {"pattern": "hello", "max_errors": 1},
        {"pattern": ""},
    ]
    for i, opts in enumerate(shapes):
        r = _mk_runner(tmp_path, f"i{i}", log, reg, **opts)
        assert not reg.adopt(r)
        r.close()
    alias = tmp_path / "app.log"
    dup = _mk_runner(tmp_path, "dup", [log, alias], reg, pattern="hello")
    assert not reg.adopt(dup)
    dup.close()
    assert reg._groups == {}


def test_fuse_wake_instants_feed_explain_route(tmp_path):
    """Satellite: fused wakes write ``fuse:wake`` into each member's
    events.jsonl; dgrep explain's follow section reads them into the
    fused/solo/mixed route verdict."""
    from distributed_grep_tpu.runtime.explain import summarize_events
    from distributed_grep_tpu.utils import spans as spans_mod

    log = tmp_path / "app.log"
    log.write_bytes(b"hello e\n")
    reg = FollowGroupRegistry(start_threads=False, auto_solo=False)
    runners = []
    for i in range(2):
        wd = tmp_path / f"wd-e{i}"
        wd.mkdir()
        ev = spans_mod.EventLog(wd / spans_mod.EventLog.FILENAME, fresh=True)
        cfg = _mk_cfg(log, str(wd), pattern="hello")
        r = FollowRunner(f"job-e{i}", cfg, wd, event_log=ev, groups=reg)
        assert reg.adopt(r)
        runners.append((r, ev, wd))
    (group,) = reg._groups.values()
    group.wake_once()
    for r, ev, wd in runners:
        r.close()
        ev.close()
        events = [json.loads(ln) for ln in
                  (wd / spans_mod.EventLog.FILENAME).read_text().splitlines()]
        assert any(e.get("name") == "fuse:wake" and e.get("job") == r.job_id
                   for e in events)
        rep = summarize_events(events)
        assert rep["follow"]["route"] == "fused"
        assert rep["follow"]["fused_wakes"] == 1
        assert rep["follow"]["records"] == 1


def test_fused_counters_ride_engine_stats_tail(tmp_path):
    """Telemetry contract: the fused counters merge into engine.stats
    after a scan (heartbeat piggyback surface), nonzero-only."""
    log = tmp_path / "app.log"
    log.write_bytes(b"hello s\n")
    reg = FollowGroupRegistry(start_threads=False, auto_solo=False)
    rs = [_mk_runner(tmp_path, f"t{i}", log, reg, pattern="hello")
          for i in range(2)]
    for r in rs:
        assert reg.adopt(r)
    (group,) = reg._groups.values()
    group.wake_once()
    eng = GrepEngine("hello", backend="cpu")
    eng.scan(b"hello again\n")
    assert eng.stats.get("follow_fused_queries") == 2
    assert eng.stats.get("follow_fused_wakes") == 1
    for r in rs:
        r.close()


# ------------------------------------------------------- chaos (restart)
def test_daemon_sigkill_mid_wake_resumes_every_member(tmp_path):
    """The round-21 chaos leg: SIGKILL the daemon while a fused group
    streams K tenants, append during the outage, restart on the same
    work root — every member's durable cursor resumes; the union of
    records across both daemon lives equals each tenant's oracle with
    no duplicate seq and no lost line."""
    import sys

    sys.path.insert(0, str(Path(__file__).parent))
    import service_proc

    K = 2
    log = tmp_path / "app.log"
    log.write_bytes(b"")
    proc = service_proc.ServiceProc(
        tmp_path / "root", workers=0,
        env={"DGREP_FOLLOW_POLL_S": "0.05"},
    )
    (tmp_path / "root").mkdir(parents=True, exist_ok=True)
    proc.start()
    collected: list[dict[int, tuple]] = [{} for _ in range(K)]
    cursors = [0] * K

    def drain(k: int, want: int, deadline_s: float = 15.0) -> None:
        deadline = time.monotonic() + deadline_s
        while time.monotonic() < deadline:
            try:
                r = service_proc._http_json(
                    "GET",
                    f"{proc.base}/jobs/{jids[k]}/stream"
                    f"?cursor={cursors[k]}&timeout=0.5",
                )
            except OSError:
                time.sleep(0.1)
                continue
            for rec in r["records"]:
                assert rec["seq"] not in collected[k], "duplicate seq"
                collected[k][rec["seq"]] = (rec["line"], rec["text"])
            cursors[k] = r["next"]
            if len(collected[k]) >= want:
                return
        raise TimeoutError(
            f"tenant {k} stuck at {len(collected[k])}/{want}: "
            f"{proc.tail_log()}"
        )

    def append(lo: int, hi: int) -> None:
        with open(log, "ab") as f:
            f.write(b"".join(
                b"hello t%dx line %d\n" % (i % K, i) for i in range(lo, hi)
            ))

    try:
        jids = [proc.submit(_mk_cfg(log, "ignored", pattern=f"t{k}x"))
                for k in range(K)]
        append(0, 10)
        for k in range(K):
            drain(k, 5)
        st = service_proc._http_json("GET", f"{proc.base}/status")
        assert st["follow"]["follow_fused_queries"] == K  # it WAS fused
        proc.sigkill()
        append(10, 14)  # lands while the daemon is down
        proc.start()  # resume: cursors reload per member, group re-forms
        append(14, 20)
        for k in range(K):
            drain(k, 10, deadline_s=20.0)
    finally:
        proc.terminate()
    for k in range(K):
        got = [collected[k][s] for s in sorted(collected[k])]
        want = [(i + 1, "hello t%dx line %d" % (i % K, i))
                for i in range(20) if i % K == k]
        assert got == want
