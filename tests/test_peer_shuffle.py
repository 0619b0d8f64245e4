"""Peer-to-peer shuffle tier (round 16, runtime/peer.py): reducers fetch
map output directly from the worker that produced it; the daemon moves
shuffle METADATA only.

Covers ISSUE 14's acceptance bars:

* a 2-worker HTTP service job with peer shuffle on completes
  byte-identical to the relay path while the daemon's measured shuffle
  data-plane bytes stay at ZERO (counter-proven);
* with peer shuffle off every wire payload keeps its pre-peer shape
  (the DGREP_SERVICE_FUSE=0 byte-identical contract);
* lost peer output (producer gone / checksum mismatch) re-enqueues the
  producing MAP task — the new COMPLETED -> UNASSIGNED transition —
  with quarantine attribution to the vanished producer and journal
  entries unique per (kind, task);
* the declared relay fallback: a dead peer endpoint with a daemon-held
  copy serves through the relay, no re-execution;
* the elastic scale signal (/status "scale") and the drainable local
  pool (scale_local_pool).

Standalone: ``python -m pytest tests/test_peer_shuffle.py -q`` (marker
``service`` — the daemon runtime suite).  CPU-only.
"""

from __future__ import annotations

import threading
import time
from pathlib import Path

import pytest

from distributed_grep_tpu.runtime import rpc
from distributed_grep_tpu.runtime.explain import summarize_events
from distributed_grep_tpu.runtime.http_transport import (
    ServiceHttpTransport,
    client_call,
)
from distributed_grep_tpu.runtime.journal import TaskJournal
from distributed_grep_tpu.runtime.peer import (
    PeerDataServer,
    checksum,
    env_peer_bind,
    env_peer_host,
    env_peer_port,
    env_peer_shuffle,
)
from distributed_grep_tpu.runtime.scheduler import Scheduler, WorkerHealth
from distributed_grep_tpu.runtime.service import GrepService, ServiceServer
from distributed_grep_tpu.runtime.types import TaskState
from distributed_grep_tpu.runtime.worker import WorkerLoop
from distributed_grep_tpu.utils.config import JobConfig

pytestmark = pytest.mark.service


def outputs_by_name(paths) -> dict[str, bytes]:
    out = {}
    for p in paths:
        name = Path(p).name.split(".part.")[0]
        out[name] = Path(p).read_bytes()
    return out


def grep_config(corpus, pattern="hello", **kw) -> JobConfig:
    defaults = dict(
        input_files=[str(p) for p in corpus.values()],
        application="distributed_grep_tpu.apps.grep_tpu",
        app_options={"pattern": pattern, "backend": "cpu"},
        n_reduce=2,
        work_dir="ignored",  # the service overrides its copy
    )
    defaults.update(kw)
    return JobConfig(**defaults)


# ------------------------------------------------------------- peer server

def test_peer_server_put_get_and_checksum(tmp_path):
    srv = PeerDataServer().start()
    try:
        size, crc = srv.put("job-1", "mr-0-1", b"hello shuffle\n")
        assert size == len(b"hello shuffle\n")
        assert crc == checksum(b"hello shuffle\n")
        assert srv.get_local("job-1", "mr-0-1") == b"hello shuffle\n"
        assert srv.spool_bytes() == size
        # overwrite (duplicate attempt) keeps accounting exact
        srv.put("job-1", "mr-0-1", b"shorter\n")
        assert srv.spool_bytes() == len(b"shorter\n")
        # the HTTP surface serves the spool
        from distributed_grep_tpu.runtime.http_transport import fetch_peer_data

        assert fetch_peer_data(srv.endpoint, "job-1", "mr-0-1") == b"shorter\n"
        with pytest.raises(RuntimeError):  # 404: honest absence, never a hang
            fetch_peer_data(srv.endpoint, "job-1", "mr-9-9")
    finally:
        srv.close()


def test_peer_server_rejects_traversal(tmp_path):
    srv = PeerDataServer()
    try:
        with pytest.raises(ValueError):
            srv.spool_path("../evil", "mr-0-0")
        with pytest.raises(ValueError):
            srv.spool_path("job-1", ".hidden")
    finally:
        srv.close()


def test_env_knob_accessors(monkeypatch):
    assert env_peer_shuffle() is True
    for off in ("0", "false", "no"):
        monkeypatch.setenv("DGREP_PEER_SHUFFLE", off)
        assert env_peer_shuffle() is False
    monkeypatch.setenv("DGREP_PEER_SHUFFLE", "1")
    assert env_peer_shuffle() is True
    monkeypatch.setenv("DGREP_PEER_PORT", "8125")
    assert env_peer_port() == 8125
    monkeypatch.setenv("DGREP_PEER_PORT", "bogus")
    assert env_peer_port() == 0
    monkeypatch.setenv("DGREP_PEER_PORT", "-1")
    assert env_peer_port() == 0
    monkeypatch.setenv("DGREP_PEER_HOST", "10.0.0.7")
    assert env_peer_host() == "10.0.0.7"


def test_bind_knob_cascade(monkeypatch):
    """Default bind is loopback; an advertised routable name implies the
    wildcard bind (a loopback-bound server can never honor it); an
    explicit DGREP_PEER_BIND wins over both."""
    assert env_peer_bind() == "127.0.0.1"
    monkeypatch.setenv("DGREP_PEER_HOST", "worker-7.cluster")
    assert env_peer_bind() == "0.0.0.0"
    monkeypatch.setenv("DGREP_PEER_BIND", "10.0.0.7")
    assert env_peer_bind() == "10.0.0.7"


def test_server_binds_wildcard_and_advertises_routable_host(monkeypatch):
    """Cross-host deployment shape: DGREP_PEER_HOST makes the server
    LISTEN on the wildcard while ADVERTISING the routable name — peers
    on other hosts can actually connect to what the endpoint says."""
    monkeypatch.setenv("DGREP_PEER_HOST", "127.0.0.1")  # routable-for-test
    srv = PeerDataServer().start()
    try:
        assert srv._httpd.server_address[0] == "0.0.0.0"
        assert srv.endpoint == f"http://127.0.0.1:{srv.port}"
        srv.put("j", "mr-0-0", b"cross-host\n")
        from distributed_grep_tpu.runtime.http_transport import (
            fetch_peer_data,
        )

        assert fetch_peer_data(srv.endpoint, "j", "mr-0-0") == b"cross-host\n"
    finally:
        srv.close()
    # explicit wildcard bind with NO advertise override never
    # advertises the undialable 0.0.0.0
    monkeypatch.delenv("DGREP_PEER_HOST")
    monkeypatch.setenv("DGREP_PEER_BIND", "0.0.0.0")
    srv = PeerDataServer()
    try:
        assert "0.0.0.0" not in srv.endpoint
    finally:
        srv.close()


# ------------------------------------------------------- wire no-op pins

def test_wire_shapes_unchanged_when_off():
    """With peer shuffle off (no peer server, no metadata) every payload
    keeps the exact pre-peer key set — the DGREP_SERVICE_FUSE=0
    byte-identical contract, applied to the new riders."""
    assert rpc.to_dict(rpc.AssignTaskArgs(worker_id=3)) == {"worker_id": 3}
    fin = rpc.to_dict(rpc.TaskFinishedArgs(task_id=1, produced_parts=[0]))
    assert set(fin) == {"task_id", "produced_parts"}
    nxt = rpc.to_dict(rpc.ReduceNextFileArgs(task_id=0, files_processed=2))
    assert set(nxt) == {"task_id", "files_processed"}
    reply = rpc.reply_to_dict(rpc.ReduceNextFileReply(next_file="mr-0-0"))
    # abort joined _REPLY_ELIDE (its docstring always promised "elided
    # when False"); the peer riders stay elided at their defaults too
    assert set(reply) == {"next_file", "done"}
    # ... and the peer riders DO travel when set
    assert rpc.to_dict(
        rpc.AssignTaskArgs(worker_id=3, peer_endpoint="http://h:1")
    )["peer_endpoint"] == "http://h:1"
    r2 = rpc.reply_to_dict(rpc.ReduceNextFileReply(
        next_file="mr-0-0", peer_endpoint="http://h:1", peer_size=4,
        peer_checksum="aa"))
    assert r2["peer_endpoint"] == "http://h:1"
    assert r2["peer_size"] == 4 and r2["peer_checksum"] == "aa"


def test_status_advertises_peer_capability(tmp_path, monkeypatch):
    """Workers gate their peer data plane on the daemon's /status "peer"
    key (run_http_worker): with the knob default-ON, a new worker
    attached to a PRE-peer daemon must not send the unknown
    AssignTaskArgs.peer_endpoint key — cls(**payload) there would
    TypeError on every poll.  Off keeps the pre-peer /status shape."""
    svc = GrepService(work_root=tmp_path / "svc", resume=False)
    try:
        assert svc.status()["peer"] is True
        monkeypatch.setenv("DGREP_PEER_SHUFFLE", "0")
        assert "peer" not in svc.status()
    finally:
        svc.stop()


# --------------------------------------------- service e2e: bytes receipt

def _spin_service(tmp_path, corpus, peer_on: bool, n_workers: int = 2):
    svc = GrepService(work_root=tmp_path / f"svc-{peer_on}", resume=False,
                      task_timeout_s=10.0, sweep_interval_s=0.2)
    server = ServiceServer(svc)
    server.start()
    addr = f"127.0.0.1:{server.port}"
    peers, loops, threads = [], [], []
    for _ in range(n_workers):
        peer = PeerDataServer().start() if peer_on else None
        peers.append(peer)
        loop = WorkerLoop(
            ServiceHttpTransport(addr, rpc_timeout_s=10.0), app=None,
            peer=peer,
        )
        loops.append(loop)
        t = threading.Thread(target=loop.run, daemon=True)
        t.start()
        threads.append(t)
    return svc, server, addr, peers, loops, threads


def _submit_and_wait(addr, cfg, timeout=60.0) -> dict:
    jid = client_call(addr, "POST", "/jobs", cfg.to_json().encode(),
                      timeout=10.0)["job_id"]
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        st = client_call(addr, "GET", f"/jobs/{jid}", timeout=10.0)
        if st["state"] in ("done", "failed", "cancelled"):
            assert st["state"] == "done", st
            return client_call(addr, "GET", f"/jobs/{jid}/result",
                               timeout=10.0)
        time.sleep(0.05)
    raise AssertionError("job did not finish")


def test_peer_job_byte_identical_with_daemon_bytes_zero(tmp_path, corpus):
    """THE acceptance receipt: peer and relay runs produce byte-identical
    outputs, and with peer shuffle on the daemon's shuffle data plane
    moves ZERO bytes (metadata only)."""
    results = {}
    for peer_on in (True, False):
        svc, server, addr, peers, loops, _threads = _spin_service(
            tmp_path, corpus, peer_on
        )
        try:
            res = _submit_and_wait(addr, grep_config(corpus))
            status = client_call(addr, "GET", "/status", timeout=10.0)
            results[peer_on] = (
                outputs_by_name(res["outputs"]),
                dict(svc._shuffle_stats),
                sum(lp.metrics.counters.get("peer_fetches", 0)
                    for lp in loops),
                status,
            )
        finally:
            svc.stop()
            server.shutdown()
            for p in peers:
                if p is not None:
                    p.close()
    outs_p, stats_p, fetches_p, status_p = results[True]
    outs_r, stats_r, fetches_r, status_r = results[False]
    assert outs_p == outs_r and outs_p  # byte-identical, non-trivial
    assert stats_p["daemon_shuffle_bytes"] == 0  # the P2P receipt
    assert fetches_p > 0
    assert stats_r["daemon_shuffle_bytes"] > 0 and fetches_r == 0
    # /status surfaces the counters (nonzero-only) + worker endpoints
    assert "shuffle" not in status_p  # all-zero: pre-peer shape kept
    assert status_r["shuffle"]["daemon_shuffle_bytes"] > 0
    endpoints = [row.get("data_endpoint")
                 for row in status_p["workers"].values()]
    assert all(e and e.startswith("http://") for e in endpoints)
    assert [r.get("data_endpoint")
            for r in status_r["workers"].values()] == [None, None]


# ------------------------------------------- lost output -> re-execution

def test_scheduler_lost_output_reexecutes_map(tmp_path):
    """The new MapTask transition: a lost-output report moves a COMPLETED
    peer-held map task back to UNASSIGNED (journal entry NOT duplicated),
    charges the vanished producer, gates the reducer's cursor on the
    re-execution, and serves the fresh attempt's metadata afterward."""
    files = [tmp_path / "a.txt", tmp_path / "b.txt"]
    for f in files:
        f.write_text("hello\n")
    journal = TaskJournal(tmp_path / "journal.jsonl")
    health = WorkerHealth(base_s=30.0)
    sched = Scheduler(files=[str(f) for f in files], n_reduce=1,
                      task_timeout_s=30.0, sweep_interval_s=5.0,
                      journal=journal, worker_health=health)
    try:
        # producer (worker 0) completes both maps with peer metadata
        for tid in range(2):
            a = sched.assign_task(rpc.AssignTaskArgs(worker_id=0),
                                  timeout=1.0)
            assert a.assignment == rpc.Assignment.MAP
            fin = rpc.TaskFinishedArgs(
                task_id=a.task_id, worker_id=0, produced_parts=[0],
                peer_endpoint="http://127.0.0.1:1",  # nothing listens here
                peer_parts={"0": [6, checksum(b"hello\n")]},
            )
            sched.map_finished(fin)
        # the reducer is served peer metadata
        r = sched.reduce_next_file(
            rpc.ReduceNextFileArgs(task_id=0, files_processed=0,
                                   epoch=sched.epoch, worker_id=1),
            timeout=0.2,
        )
        assert r.next_file == "mr-0-0"
        assert r.peer_endpoint == "http://127.0.0.1:1"
        assert r.peer_size == 6 and r.peer_checksum == checksum(b"hello\n")
        # the reducer must hold an assignment for the abort-and-requeue
        # half of the report (maps are done, so it gets reduce 0)
        ra = sched.assign_task(rpc.AssignTaskArgs(worker_id=1), timeout=1.0)
        assert ra.assignment == rpc.Assignment.REDUCE and ra.task_id == 0
        # lost-output report: map task 0 re-enqueues, the producer is
        # charged, and the REPORTING attempt is aborted (its worker must
        # be free to run the re-executed map — the small-pool deadlock
        # guard) with its reduce task immediately re-enqueued
        r = sched.reduce_next_file(
            rpc.ReduceNextFileArgs(task_id=0, files_processed=0,
                                   epoch=sched.epoch, worker_id=1,
                                   lost_file="mr-0-0"),
            timeout=0.2,
        )
        assert r.abort
        assert sched.map_tasks[0].state is TaskState.UNASSIGNED
        assert sched.map_tasks[0].peer is None
        assert not sched.map_phase_done()
        assert sched.reduce_tasks[0].state is TaskState.UNASSIGNED
        assert health._fails.get(0) == 1  # one attributed failure
        # a second report for the same task is a no-op (first wins)
        r2 = sched.reduce_next_file(
            rpc.ReduceNextFileArgs(task_id=0, files_processed=0,
                                   epoch=sched.epoch, worker_id=1,
                                   lost_file="mr-0-0"),
            timeout=0.2,
        )
        assert not r2.abort
        assert health._fails.get(0) == 1
        # a surviving worker re-executes; this time the commit is RELAY
        a = sched.assign_task(rpc.AssignTaskArgs(worker_id=2), timeout=1.0)
        assert a.assignment == rpc.Assignment.MAP and a.task_id == 0
        sched.map_finished(rpc.TaskFinishedArgs(
            task_id=0, worker_id=2, produced_parts=[0]))
        assert sched.map_phase_done()
        # the map phase completed TWICE (revocation re-crossed the
        # boundary) but the phase wall observed exactly once — a second
        # sample would include the elapsed reduce time
        from distributed_grep_tpu.runtime import scheduler as sched_mod

        assert sched_mod._H_MAP_PHASE.snapshot()[2] == 1
        r = sched.reduce_next_file(
            rpc.ReduceNextFileArgs(task_id=0, files_processed=0,
                                   epoch=sched.epoch, worker_id=1),
            timeout=0.2,
        )
        assert r.next_file == "mr-0-0" and not r.peer_endpoint
    finally:
        sched.stop()
        sched.close_journal()
    # journal: each (kind, task) at most once despite the re-completion
    entries = TaskJournal.replay(tmp_path / "journal.jsonl")
    seen = [(e["kind"], e["task_id"]) for e in entries]
    assert len(seen) == len(set(seen))
    assert ("map_done", 0) in seen


def test_lost_report_ignores_relay_and_bogus_names(tmp_path):
    """Only PEER-HELD completed outputs are revocable: a report against a
    relay-committed task (daemon holds the bytes — a 404 there is a
    store-layer bug, not a dead worker) or a malformed name is ignored."""
    f = tmp_path / "a.txt"
    f.write_text("hello\n")
    sched = Scheduler(files=[str(f)], n_reduce=1, task_timeout_s=30.0,
                      sweep_interval_s=5.0)
    try:
        a = sched.assign_task(rpc.AssignTaskArgs(worker_id=0), timeout=1.0)
        sched.map_finished(rpc.TaskFinishedArgs(
            task_id=a.task_id, worker_id=0, produced_parts=[0]))
        for bogus in ("mr-0-0", "not-a-name", "mr-99-0"):
            sched.reduce_next_file(
                rpc.ReduceNextFileArgs(task_id=0, files_processed=0,
                                       epoch=sched.epoch,
                                       lost_file=bogus),
                timeout=0.1,
            )
        assert sched.map_tasks[0].state is TaskState.COMPLETED
        assert sched.map_phase_done()
    finally:
        sched.stop()


def test_zombie_lost_report_fenced_by_epoch(tmp_path):
    """A stale-epoch zombie's lost-output report must abort the attempt
    WITHOUT re-enqueueing this incarnation's completed maps."""
    f = tmp_path / "a.txt"
    f.write_text("hello\n")
    sched = Scheduler(files=[str(f)], n_reduce=1, task_timeout_s=30.0,
                      sweep_interval_s=5.0)
    try:
        a = sched.assign_task(rpc.AssignTaskArgs(worker_id=0), timeout=1.0)
        sched.map_finished(rpc.TaskFinishedArgs(
            task_id=a.task_id, worker_id=0, produced_parts=[0],
            peer_endpoint="http://127.0.0.1:1", peer_parts={"0": [1, "x"]}))
        r = sched.reduce_next_file(
            rpc.ReduceNextFileArgs(task_id=0, files_processed=0,
                                   epoch="deadbeefcafe",
                                   lost_file="mr-0-0"),
            timeout=0.1,
        )
        assert r.abort
        assert sched.map_tasks[0].state is TaskState.COMPLETED
    finally:
        sched.stop()


# ---------------------------------------------------- relay fallback leg

class _RelayOnlyTransport:
    """A transport whose daemon holds a relay copy (mixed/migrating
    cluster): peer fetch fails, the declared fallback must serve it."""

    def __init__(self, blobs: dict[str, bytes]):
        self.blobs = blobs

    def read_intermediate(self, name: str) -> bytes:
        return self.blobs[name]


def test_relay_fallback_on_dead_peer(monkeypatch):
    monkeypatch.setenv("DGREP_RPC_RETRIES", "0")  # fail the dead dial fast
    data = b"relay copy\n"
    loop = WorkerLoop(_RelayOnlyTransport({"mr-0-0": data}), app=None)
    reply = rpc.ReduceNextFileReply(
        next_file="mr-0-0", peer_endpoint="http://127.0.0.1:1",
        peer_size=len(data), peer_checksum=checksum(data),
    )
    assert loop._fetch_shuffle(reply) == data
    assert loop.metrics.counters["peer_fetch_failures"] == 1
    assert loop.metrics.counters["relay_fallbacks"] == 1


def test_checksum_mismatch_is_a_declared_failure(monkeypatch):
    """A peer serving WRONG bytes (torn spool, bitrot) must never reach
    the reducer's sink: the crc gate fails the fetch and the relay
    fallback (here: also absent) turns it into a lost-output report."""
    srv = PeerDataServer().start()
    try:
        srv.put("j", "mr-0-0", b"corrupted bytes")

        class _NoRelay:
            def read_intermediate(self, name):
                raise RuntimeError("404")

        loop = WorkerLoop(_NoRelay(), app=None)
        loop._rpc_job_id = "j"
        reply = rpc.ReduceNextFileReply(
            next_file="mr-0-0", peer_endpoint=srv.endpoint,
            peer_size=5, peer_checksum="00000000",  # expect different bytes
        )
        assert loop._fetch_shuffle(reply) is None  # -> lost report
        assert loop.metrics.counters["peer_fetch_failures"] == 1
    finally:
        srv.close()


# ----------------------------------------------------- elastic scaling

def test_scale_advice_and_local_pool(tmp_path, corpus):
    svc = GrepService(work_root=tmp_path / "svc", resume=False,
                      rpc_timeout_s=0.5)
    try:
        # no workers, no jobs: idle with nothing attached -> no advice
        st = svc.status()
        assert "scale" not in st
        # demand with zero workers -> grow
        jid = svc.submit(grep_config(corpus))
        advice = svc.scale_advice()
        assert advice["advice"] == "grow" and advice["pending_tasks"] > 0
        assert svc.status()["scale"]["advice"] == "grow"
        # grow the pool; the job completes
        assert svc.scale_local_pool(2) == 2
        assert svc.local_pool_size() == 2
        assert svc.wait_job(jid, timeout=60)
        # idle with workers attached -> shrink
        deadline = time.monotonic() + 10
        while svc.scale_advice()["advice"] != "shrink":
            assert time.monotonic() < deadline
            time.sleep(0.05)
        # drain to zero: loops exit at their next idle poll
        assert svc.scale_local_pool(0) == -2
        assert svc.local_pool_size() == 0
        for t in svc._local_workers:
            t.join(timeout=10)
        assert not any(t.is_alive() for t in svc._local_workers)
        # drained loops + exited threads are PRUNED at the next scale
        # pass — grow/shrink cycles must not grow the lists (and their
        # retained transports) for the daemon's lifetime
        assert svc.scale_local_pool(1) == 1
        assert len(svc._local_loops) == 1 and len(svc._local_workers) == 1
        svc.scale_local_pool(0)
    finally:
        svc.stop()


def test_scale_advice_ignores_stale_worker_rows(tmp_path, corpus):
    """Worker rows linger for 1 h of silence, but only FRESH rows count
    as capacity: stale rows (drained loops, dead remotes) suppressing
    grow advice would stall recovery exactly when it needs workers."""
    svc = GrepService(work_root=tmp_path / "svc", resume=False,
                      rpc_timeout_s=0.5)
    try:
        svc.submit(grep_config(corpus))
        # six phantom workers, silent for 10 minutes
        with svc._lock:
            for wid in range(100, 106):
                svc.workers[wid] = {"job": None, "task": None,
                                    "seen": time.monotonic() - 600.0}
        advice = svc.scale_advice()
        assert advice["workers_attached"] == 0
        assert advice["advice"] == "grow"
    finally:
        svc.stop()


# ------------------------------------------------------------- explain

def test_explain_summarizes_shuffle_route():
    events = [
        {"t": "instant", "name": "shuffle:peer", "ts": 1.0,
         "args": {"bytes": 100}},
        {"t": "instant", "name": "shuffle:peer", "ts": 2.0,
         "args": {"bytes": 50}},
    ]
    agg = summarize_events(events)
    assert agg["shuffle"] == {
        "peer_fetches": 2, "peer_bytes": 150, "relay_fetches": 0,
        "relay_fallbacks": 0, "lost_outputs": 0, "route": "peer",
    }
    events += [
        {"t": "instant", "name": "shuffle:relay", "ts": 3.0,
         "args": {"fallback": True}},
        {"t": "instant", "name": "map_lost_output", "ts": 4.0},
    ]
    agg = summarize_events(events)
    assert agg["shuffle"]["route"] == "mixed"
    assert agg["shuffle"]["relay_fallbacks"] == 1
    assert agg["shuffle"]["lost_outputs"] == 1
    assert summarize_events([
        {"t": "instant", "name": "shuffle:relay", "ts": 1.0},
    ])["shuffle"]["route"] == "relay"
    assert "shuffle" not in summarize_events([])
