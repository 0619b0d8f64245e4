"""Crash/fault-injection matrix over the blob-store commit layer.

Exercises runtime/store.py end-to-end: for every CrashPoint × {map commit,
reduce commit} × {PosixStore, NonAtomicStore}, a worker is killed at that
exact instruction of the commit protocol and the job must still finish with
byte-identical output — via a surviving worker (sweeper re-issue) or via a
coordinator restart replaying an idempotent journal.  Also pins the
non-atomic resolution invariants directly (torn parts/records invisible,
duplicate attempts resolve to exactly one winner) and the journal's
torn-tail handling.

Standalone: ``python -m pytest tests/test_store_faults.py -q``.  CPU-only
and device-free by construction — the plain grep app never touches a
backend.
"""

import io
import threading
import time

import pytest

from distributed_grep_tpu.apps.loader import load_application
from distributed_grep_tpu.runtime.job import run_job
from distributed_grep_tpu.runtime.journal import TaskJournal
from distributed_grep_tpu.runtime.scheduler import Scheduler
from distributed_grep_tpu.runtime.store import (
    CrashPoint,
    FaultStore,
    NonAtomicStore,
    decode_record,
    encode_record,
    make_store,
)
from distributed_grep_tpu.runtime.transport import LocalTransport
from distributed_grep_tpu.runtime.worker import WorkerKilled, WorkerLoop
from distributed_grep_tpu.utils.config import JobConfig
from distributed_grep_tpu.utils.io import WorkDir

pytestmark = pytest.mark.faults

STORES = ["posix", "nonatomic"]


def make_config(tmp_path, corpus, sub="job", **kw):
    defaults = dict(
        input_files=[str(p) for p in corpus.values()],
        application="distributed_grep_tpu.apps.grep",
        app_options={"pattern": "hello"},
        n_reduce=3,
        work_dir=str(tmp_path / sub),
        task_timeout_s=0.5,
        sweep_interval_s=0.05,
    )
    defaults.update(kw)
    return JobConfig(**defaults)


def output_bytes(res) -> list[bytes]:
    return [p.read_bytes() for p in res.output_files]


def clean_output(tmp_path, corpus, store) -> list[bytes]:
    """Baseline: the byte-exact outputs of an uninjected run on this store."""
    res = run_job(make_config(tmp_path, corpus, sub=f"clean-{store}",
                              store=store), n_workers=2)
    out = output_bytes(res)
    assert out and any(b"hello" in b for b in out)
    return out


# ------------------------------------------------------------- store units

def test_config_store_names_match_registry():
    """utils/config validates store names from a literal (importing the
    runtime package per JobConfig would be absurdly heavy); this pins the
    literal to the factory registry so they cannot drift."""
    from distributed_grep_tpu.runtime.store import STORES
    from distributed_grep_tpu.utils.config import STORE_NAMES

    assert STORE_NAMES == frozenset(STORES)
    with pytest.raises(ValueError, match="store must be one of"):
        JobConfig(store="bogus")


def test_record_roundtrip_and_torn_detection():
    payload = {"parts": [0, 2], "kind": "map", "task_id": 3}
    data = encode_record(payload)
    assert decode_record(data) == payload
    # every strict prefix is detectably torn — never half-truth
    for cut in range(len(data)):
        assert decode_record(data[:cut]) is None
    assert decode_record(data[:-2] + b"x\n") is None  # bit-flipped crc


@pytest.mark.parametrize("store_name", STORES)
def test_store_put_get_visibility(tmp_path, store_name):
    store = make_store(store_name)
    p = tmp_path / "blob"
    assert not store.exists(p)
    with pytest.raises(FileNotFoundError):
        store.get(p)
    store.put(p, b"payload")
    assert store.exists(p)
    assert store.get(p) == b"payload"
    got = store.resolve(p)
    assert got is not None and got.read_bytes() == b"payload"
    assert store.list_committed(tmp_path, "blob*") == [got]
    # streaming variants commit the same way
    store.put_from_stream(tmp_path / "s", io.BytesIO(b"abcdef"), 6, chunk_bytes=2)
    assert store.get(tmp_path / "s") == b"abcdef"
    src = tmp_path / "src.local"
    src.write_bytes(b"xyz" * 100)
    store.put_from_file(tmp_path / "f", src, chunk_bytes=7)
    assert store.get(tmp_path / "f") == b"xyz" * 100


def test_nonatomic_torn_part_and_record_are_invisible(tmp_path):
    store = NonAtomicStore()
    p = tmp_path / "mr-0-0"
    # torn part: bytes staged, crash before the commit record
    (tmp_path / "mr-0-0.part.aaaa").write_bytes(b"half a blo")
    assert not store.exists(p)
    # torn commit record: marker half-written
    store.put(p, b"full contents")
    winner = store.resolve(p)
    marker = next(tmp_path.glob("mr-0-0.commit.*"))
    torn = tmp_path / "mr-0-0.commit.zzzz"
    torn.write_bytes(marker.read_bytes()[: marker.stat().st_size // 2])
    assert store.resolve(p) == winner  # torn marker never wins
    # record whose part vanished must not win either
    ghost = decode_record(marker.read_bytes())
    ghost2 = dict(ghost, attempt="0000")  # sorts before every hex uuid
    (tmp_path / "mr-0-0.commit.0000").write_bytes(encode_record(ghost2))
    assert store.resolve(p) == winner


def test_nonatomic_duplicate_attempts_one_winner(tmp_path):
    store = NonAtomicStore()
    p = tmp_path / "mr-out-1"
    store.put(p, b"attempt output\n")
    store.put(p, b"attempt output\n")  # re-executed straggler, same bytes
    assert len(list(tmp_path.glob("mr-out-1.part.*"))) == 2
    assert len(list(tmp_path.glob("mr-out-1.commit.*"))) == 2
    assert store.list_committed(tmp_path, "mr-out-*") == [store.resolve(p)]
    assert store.get(p) == b"attempt output\n"


@pytest.mark.parametrize("store_name", STORES)
def test_task_commit_winner_is_deterministic(tmp_path, store_name):
    store = make_store(store_name)
    store.commit_task(tmp_path, "map", 7, "bbbb", {"parts": [1]})
    store.commit_task(tmp_path, "map", 7, "aaaa", {"parts": [1]})
    rec = store.resolve_task_commit(tmp_path, "map", 7)
    assert rec["attempt"] == "aaaa" and rec["parts"] == [1]
    assert store.resolve_task_commit(tmp_path, "map", 77) is None


# ------------------------------------------------------------ crash matrix

def _kill_once(match):
    """A CrashPoint hook that raises WorkerKilled the first time ctx
    matches; returns (hook, fired) — fired["n"] proves injection ran."""
    fired = {"n": 0}

    def hook(ctx):
        if fired["n"] == 0 and match(ctx):
            fired["n"] += 1
            raise WorkerKilled(f"injected at {ctx}")

    return hook, fired


def _tear_once(match):
    """TORN_COMMIT_RECORD hooks signal by RETURN (FaultStore writes the
    half record and raises itself)."""
    fired = {"n": 0}

    def hook(ctx):
        if fired["n"] == 0 and match(ctx):
            fired["n"] += 1
            return True
        return False

    return hook, fired


def _phase_match(phase, point):
    if point == CrashPoint.AFTER_TEMP_WRITE:
        # ctx is the blob name: map blobs "mr-<t>-<r>", reduce "mr-out-<r>"
        if phase == "map":
            return lambda ctx: ctx.startswith("mr-") and not ctx.startswith("mr-out-")
        return lambda ctx: ctx.startswith("mr-out-")
    return lambda ctx: ctx.startswith(f"{phase}-")


@pytest.mark.parametrize("store_name", STORES)
@pytest.mark.parametrize("phase", ["map", "reduce"])
@pytest.mark.parametrize("point", CrashPoint.ALL)
def test_crash_matrix_surviving_worker(tmp_path, corpus, store_name, phase, point):
    """A worker dies at every commit-protocol instruction; the surviving
    worker (after the sweeper re-issue) completes the job with output
    byte-identical to an uninjected run — no duplicate, torn, or phantom
    mr-* content on either store."""
    expected = clean_output(tmp_path, corpus, store_name)
    maker = _tear_once if point == CrashPoint.TORN_COMMIT_RECORD else _kill_once
    hook, fired = maker(_phase_match(phase, point))
    res = run_job(
        make_config(tmp_path, corpus, store=store_name),
        n_workers=2,
        store_faults_per_worker=[{point: hook}, {}],
    )
    assert fired["n"] == 1, "injection never fired"
    assert output_bytes(res) == expected


@pytest.mark.parametrize("store_name", STORES)
@pytest.mark.parametrize("point", CrashPoint.ALL)
def test_crash_matrix_coordinator_restart(tmp_path, corpus, store_name, point):
    """The lone worker dies at each crash point, taking the job down; a
    restarted coordinator (journal replay + commit records) finishes it.
    A third run replays to a no-op — replay is idempotent."""
    expected = clean_output(tmp_path, corpus, store_name)
    maker = _tear_once if point == CrashPoint.TORN_COMMIT_RECORD else _kill_once
    hook, fired = maker(lambda ctx: True)  # first commit-path call of any task
    cfg = make_config(tmp_path, corpus, store=store_name)
    with pytest.raises(RuntimeError, match="all workers exited"):
        run_job(cfg, n_workers=1, store_faults_per_worker=[{point: hook}])
    assert fired["n"] == 1
    res = run_job(cfg, n_workers=1, resume=True)
    assert output_bytes(res) == expected
    res2 = run_job(cfg, n_workers=1, resume=True)
    assert res2.metrics["counters"].get("map_assigned", 0) == 0
    assert res2.metrics["counters"].get("reduce_assigned", 0) == 0
    assert output_bytes(res2) == expected


# -------------------------------------------- duplicate-completion races

def test_sweeper_reissue_both_attempts_commit_exactly_once(tmp_path, corpus):
    """The satellite race the old suite never covered: a straggler stalls
    mid-commit, the sweeper re-issues, BOTH attempts then commit on disk.
    The store must resolve exactly one winner per blob and per task, the
    scheduler must not double-register, and the output must equal a clean
    run's bytes."""
    workdir = WorkDir(tmp_path / "job", store=make_store("nonatomic"))
    app = load_application("distributed_grep_tpu.apps.grep", pattern="hello")
    files = [str(p) for p in corpus.values()]
    sched = Scheduler(
        files=files, n_reduce=3, task_timeout_s=0.5, sweep_interval_s=0.05,
        app_options={"pattern": "hello"},
        commit_resolver=workdir.resolve_task_commit,
    )
    stalled = {"n": 0, "ctx": None}

    def stall(ctx):
        if ctx.startswith("map-") and stalled["n"] == 0:
            stalled["n"] += 1
            stalled["ctx"] = ctx
            time.sleep(1.2)  # past the sweep window: task re-issued meanwhile

    w0 = WorkerLoop(
        LocalTransport(sched, workdir, store=FaultStore(
            workdir.store, {CrashPoint.BEFORE_COMMIT_RECORD: stall})),
        app,
    )
    w1 = WorkerLoop(LocalTransport(sched, workdir), app)
    threads = [threading.Thread(target=w.run, daemon=True) for w in (w0, w1)]
    for t in threads:
        t.start()
    assert sched.wait_done(timeout=30.0)
    sched.stop()
    for t in threads:
        t.join(timeout=10.0)

    assert stalled["n"] == 1
    tid = int(stalled["ctx"].split("-", 1)[1])
    # the race actually happened: both attempts published task records...
    assert len(list(workdir.commits_dir().glob(f"map-{tid}.*"))) == 2
    # ...but exactly one resolves as truth
    rec = workdir.resolve_task_commit("map", tid)
    assert rec is not None and rec["task_id"] == tid
    # no double-registration in the streaming-shuffle feed
    for rt in sched.reduce_tasks:
        assert len(rt.task_files) == len(set(rt.task_files))
    # each blob of the raced task: two committed attempts, one winner
    for r in rec["parts"]:
        p = workdir.intermediate_path(tid, r)
        assert len(list(p.parent.glob(f"{p.name}.commit.*"))) == 2
        assert workdir.store.resolve(p) is not None
    # and the job's bytes equal an uninjected run's
    from distributed_grep_tpu.runtime.job import JobResult

    expected = clean_output(tmp_path, corpus, "nonatomic")
    got = [p.read_bytes() for p in workdir.list_outputs()]
    assert got == expected
    assert JobResult(output_files=workdir.list_outputs()).results


def test_duplicate_on_disk_commits_register_winning_record_parts(tmp_path):
    """map_finished registers the WINNING commit record's parts, not the
    RPC args — a late straggler RPC carrying a different parts list can
    never register blobs its winning attempt did not commit."""
    from distributed_grep_tpu.runtime import rpc

    workdir = WorkDir(tmp_path / "job", store=make_store("nonatomic"))
    sched = Scheduler(
        files=["f1"], n_reduce=3, sweep_interval_s=0.05,
        commit_resolver=workdir.resolve_task_commit,
    )
    a = sched.assign_task(rpc.AssignTaskArgs(), timeout=1.0)
    workdir.store.commit_task(workdir.commits_dir(), "map", a.task_id,
                              "aaaa", {"parts": [0, 1]})
    # straggler RPC lies about parts (e.g. raced re-execution under a
    # different app config); the record is the unit of truth
    sched.map_finished(rpc.TaskFinishedArgs(task_id=a.task_id,
                                            produced_parts=[0, 1, 2]))
    assert sched.reduce_tasks[0].task_files == [f"mr-{a.task_id}-0"]
    assert sched.reduce_tasks[1].task_files == [f"mr-{a.task_id}-1"]
    assert sched.reduce_tasks[2].task_files == []
    sched.stop()


def test_malformed_commit_record_degrades_to_rpc_parts(tmp_path):
    """The data plane accepts any small JSON body as a commit record; one
    missing "parts" must degrade to RPC-args registration, not wedge the
    task with a KeyError inside the scheduler lock."""
    from distributed_grep_tpu.runtime import rpc

    workdir = WorkDir(tmp_path / "job", store=make_store("nonatomic"))
    sched = Scheduler(
        files=["f1"], n_reduce=2, sweep_interval_s=0.05,
        commit_resolver=workdir.resolve_task_commit,
    )
    a = sched.assign_task(rpc.AssignTaskArgs(), timeout=1.0)
    workdir.store.commit_task(workdir.commits_dir(), "map", a.task_id, "aaaa", {})
    sched.map_finished(rpc.TaskFinishedArgs(task_id=a.task_id, produced_parts=[1]))
    assert sched.reduce_tasks[1].task_files == [f"mr-{a.task_id}-1"]
    assert sched.reduce_tasks[0].task_files == []
    sched.stop()


# ------------------------------------------------------- journal tearing

def test_journal_torn_tail_excluded_and_truncated(tmp_path):
    """Satellite: a torn tail is reported (not silently swallowed),
    excluded from replay, and truncated on reopen so the next append
    starts on a clean line."""
    path = tmp_path / "tasks.jsonl"
    j = TaskJournal(path)
    j.map_completed(0, "f", [0])
    j.map_completed(1, "g", [1])
    j.close()
    clean = path.read_bytes()
    # crash mid-append: half a record, no terminating newline
    path.write_bytes(clean + b'{"kind": "reduce_do')
    assert [e["task_id"] for e in TaskJournal.replay(path)] == [0, 1]
    j2 = TaskJournal(path)  # reopen truncates the torn tail
    assert path.stat().st_size == len(clean)
    j2.reduce_completed(2)
    j2.close()
    kinds = [e["kind"] for e in TaskJournal.replay(path)]
    assert kinds == ["map_done", "map_done", "reduce_done"]


def test_journal_unterminated_tail_is_torn_even_if_it_parses(tmp_path):
    """record() always newline-terminates, so an unterminated tail is a
    partial write BY DEFINITION — even when the prefix happens to parse
    (task_id 12 torn to 1 must not replay as task 1 done)."""
    path = tmp_path / "tasks.jsonl"
    j = TaskJournal(path)
    j.map_completed(0, "f", [0])
    j.close()
    clean = path.read_bytes()
    path.write_bytes(clean + b'{"kind": "reduce_done", "task_id": 1}')
    entries = TaskJournal.replay(path)
    assert [e["kind"] for e in entries] == ["map_done"]
    TaskJournal(path).close()
    assert path.read_bytes() == clean


def test_journal_append_crash_replay_idempotent(tmp_path, corpus):
    """Coordinator dies mid-journal-append (torn tail): the restarted run
    re-executes only the un-journaled work and the final output matches."""
    expected = clean_output(tmp_path, corpus, "posix")
    cfg = make_config(tmp_path, corpus, store="posix")
    run_job(cfg, n_workers=2)
    jpath = WorkDir(cfg.work_dir).journal_path()
    data = jpath.read_bytes()
    lines = data.splitlines(keepends=True)
    # tear the last entry in half — as if the fsync'd append died mid-write
    jpath.write_bytes(b"".join(lines[:-1]) + lines[-1][: len(lines[-1]) // 2])
    res = run_job(cfg, n_workers=2, resume=True)
    assert output_bytes(res) == expected
    # exactly the torn task re-ran; everything journaled was skipped
    total = len(corpus) + cfg.n_reduce
    redone = (res.metrics["counters"].get("map_assigned", 0)
              + res.metrics["counters"].get("reduce_assigned", 0))
    assert 1 <= redone < total


# ------------------------------------------------------------- http plane

def test_http_job_on_nonatomic_store(tmp_path, corpus):
    """The HTTP data plane routes PUTs and commit records through the
    coordinator's store: a full job on NonAtomicStore over real HTTP."""
    from distributed_grep_tpu.runtime.http_coordinator import CoordinatorServer
    from distributed_grep_tpu.runtime.http_transport import HttpTransport

    cfg = make_config(tmp_path, corpus, store="nonatomic",
                      coordinator_port=0, task_timeout_s=5.0)
    server = CoordinatorServer(cfg)
    server.start()
    app = load_application("distributed_grep_tpu.apps.grep", pattern="hello")
    addr = f"127.0.0.1:{server.port}"
    threads = [
        threading.Thread(
            target=WorkerLoop(HttpTransport(addr, rpc_timeout_s=10.0), app).run,
            daemon=True,
        )
        for _ in range(2)
    ]
    for t in threads:
        t.start()
    assert server.wait_done(timeout=30.0)
    for t in threads:
        t.join(timeout=10.0)
    outs = server.workdir.list_outputs()
    assert outs and all(".part." in p.name for p in outs)
    expected = clean_output(tmp_path, corpus, "nonatomic")
    assert [p.read_bytes() for p in outs] == expected
    # commit records made it through the data plane
    assert server.workdir.resolve_task_commit("map", 0) is not None
    server.shutdown(linger_s=0.1)


# ---------------------------------------------- posix behavior preserved

def test_posix_store_outputs_are_plain_files(tmp_path, corpus):
    """PosixStore keeps the exact on-disk shape the runtime always had:
    mr-out-<r> files, no part/marker decorations (behavior-preserving
    refactor guarantee)."""
    res = run_job(make_config(tmp_path, corpus, store="posix"), n_workers=2)
    assert all(p.name.startswith("mr-out-") and ".part." not in p.name
               for p in res.output_files)
    inter = WorkDir(make_config(tmp_path, corpus).work_dir).root / "intermediate"
    assert all(".commit." not in p.name for p in inter.iterdir())
