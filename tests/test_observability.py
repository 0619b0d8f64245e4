"""Observability suite: the span pipeline (utils/spans.py), Metrics under
concurrency, per-worker /status liveness, events.jsonl persistence, the
trace-export renderer, and the no-print/no-root-logger lint over runtime
modules.

Standalone-runnable (like the `faults` matrix):

    python -m pytest tests/ -q -m obs
"""

from __future__ import annotations

import contextlib
import json
import threading
import time
from pathlib import Path

import pytest

from distributed_grep_tpu.runtime import rpc
from distributed_grep_tpu.utils import spans
from distributed_grep_tpu.utils.config import JobConfig
from distributed_grep_tpu.utils.metrics import Metrics

pytestmark = pytest.mark.obs


# ------------------------------------------------------- Metrics concurrency

def test_metrics_concurrent_exact():
    """Parallel inc/observe/record_scan from worker-slot threads: snapshot
    totals are exact (no lost updates, no torn reads)."""
    m = Metrics()
    N_THREADS, N_OPS = 8, 500
    snapshots: list[dict] = []

    def pound(idx: int) -> None:
        for i in range(N_OPS):
            m.inc("ops")
            m.inc("weighted", 2.5)
            m.observe("lat", 0.001)
            m.record_scan(1000, 0.0001)
            if i % 100 == 0:  # concurrent readers must not corrupt state
                snapshots.append(m.snapshot())

    threads = [threading.Thread(target=pound, args=(i,)) for i in range(N_THREADS)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()

    snap = m.snapshot()
    total = N_THREADS * N_OPS
    assert snap["counters"]["ops"] == total
    assert snap["counters"]["weighted"] == pytest.approx(2.5 * total)
    assert snap["counters"]["bytes_scanned"] == 1000 * total
    assert snap["timings"]["lat"]["count"] == total
    assert snap["timings"]["lat"]["total_s"] == pytest.approx(0.001 * total)
    assert snap["throughput_GBps"] > 0
    assert snapshots  # the concurrent readers actually ran
    pb = m.piggyback()
    assert pb["ops"] == total and pb["gbps"] > 0


# --------------------------------------------------------------- span buffer

def test_span_buffer_bounded_and_drop_reporting():
    buf = spans.SpanBuffer(cap=4)
    # declared instant names (analysis/events.py) — the event-vocabulary
    # audit fixture validates everything that passes through the buffer
    names = ["cache:hit", "cache:miss", "cache:off", "corpus:hit",
             "corpus:miss", "index:prune", "index:maybe"]
    for i, n in enumerate(names):
        buf.add({"t": "instant", "name": n, "ts": float(i)})
    assert len(buf) == 4 and buf.dropped == 3
    first = buf.drain(limit=2)
    assert [r["name"] for r in first] == ["cache:hit", "cache:miss"]
    rest = buf.drain()
    # the drop count is reported once, when the buffer fully drains
    assert rest[-1]["name"] == "spans_dropped"
    assert rest[-1]["args"]["count"] == 3
    assert buf.dropped == 0 and buf.drain() == []


def test_span_parent_links():
    """Every span record names the innermost span open on its thread when
    it started; complete() records take the one open when they land."""
    buf = spans.SpanBuffer()
    with spans.task_context(buf, job="j", worker=0, task=1, attempt="a",
                            kind="map"):
        with spans.span("map:compute", cat="map"):
            with spans.span("map:emit", cat="map") as args:
                args["rows"] = 3
            spans.complete("map:read", 1.0, 0.5, cat="map")
        with spans.span("map:shuffle", cat="map"):
            pass
    by_name = {r["name"]: r for r in buf.drain()}
    assert by_name["map:emit"]["parent"] == "map:compute"
    assert by_name["map:emit"]["args"] == {"rows": 3}
    assert by_name["map:read"]["parent"] == "map:compute"
    assert by_name["map:compute"]["parent"] is None
    assert by_name["map:shuffle"]["parent"] is None


def test_explain_self_seconds_from_parent_links():
    """Explain's self seconds: a row's seconds minus those of the spans
    whose parent it is (direct children only)."""
    from distributed_grep_tpu.runtime import explain as explain_mod

    def span(name, dur, parent=None, **args):
        return {"t": "span", "name": name, "dur": dur, "parent": parent,
                "args": args}

    events = [
        span("map:compute", 1.0),
        span("map:compute", 1.0),
        span("engine:prep", 0.1, "map:compute"),
        span("scan:nfa", 1.5, "map:compute", bytes=10, compiles=2),
        span("engine:prep", 0.2, "scan:nfa"),
        span("engine:wait", 0.5, "scan:nfa"),
        span("engine:confirm", 0.6, "scan:nfa"),
        span("engine:stitch", 0.1, "engine:confirm"),  # grandchild
        span("map:emit", 0.3, "map:compute"),
        span("map:wait", 0.25),
    ]
    agg = explain_mod.summarize_events(events)
    st, nfa = agg["stages"], agg["modes"]["nfa"]
    assert st["map:compute"]["seconds"] == 2.0
    assert st["map:compute"]["self_seconds"] == pytest.approx(0.1)
    assert nfa["self_seconds"] == pytest.approx(0.2)
    assert nfa["compiles"] == 2
    assert st["engine:confirm"]["self_seconds"] == pytest.approx(0.5)
    assert st["engine:prep"] == {"count": 2, "seconds": 0.3,
                                 "self_seconds": 0.3}
    assert st["map:wait"]["self_seconds"] == 0.25


def test_span_context_tags_and_nesting():
    buf = spans.SpanBuffer()
    assert not spans.active()
    with spans.task_context(buf, job="j", worker=3, task=7, attempt="a1",
                            kind="map"):
        assert spans.active()
        with spans.span("map:read", cat="map", detail=1):
            pass
        spans.instant("index:maybe", cat="engine")
    assert not spans.active()
    recs = buf.drain()
    assert [r["name"] for r in recs] == ["map:read", "index:maybe"]
    for r in recs:
        assert (r["job"], r["worker"], r["task"], r["attempt"]) == ("j", 3, 7, "a1")
    assert recs[0]["t"] == "span" and "dur" in recs[0]
    assert recs[1]["t"] == "instant"


def test_span_emitters_noop_outside_context():
    """Disabled pipeline: emitters return immediately and buffer nothing."""
    spans.instant("nobody-home")
    with spans.scan_span("native") as rec:
        assert rec is None  # nothing to fill, nothing recorded
    with spans.span("nothing"):
        pass
    cm = spans.span("x")
    assert isinstance(cm, contextlib.AbstractContextManager)


# ------------------------------------------------------- engine scan records

def _scan_records(engine, data: bytes) -> list[dict]:
    buf = spans.SpanBuffer()
    with spans.task_context(buf, job="j", worker=0, task=0, attempt="a",
                            kind="map"):
        engine.scan(data)
    return [r for r in buf.drain() if r["name"].startswith("scan:")]


def test_engine_scan_record_host_path():
    from distributed_grep_tpu.ops.engine import GrepEngine

    eng = GrepEngine("needle", backend="cpu")
    recs = _scan_records(eng, b"hay\nneedle here\nhay\n" * 10)
    assert len(recs) == 1
    args = recs[0]["args"]
    assert args["mode"] == eng.mode
    assert args["bytes"] == len(b"hay\nneedle here\nhay\n" * 10)
    assert args["kernel"] == "host"  # route present on the host path
    assert args["interpret"] is False
    assert args["matches"] == 10
    assert recs[0]["cat"] == "engine" and recs[0]["dur"] >= 0


def test_engine_scan_record_device_path():
    from distributed_grep_tpu.ops.engine import GrepEngine

    eng = GrepEngine("needle", backend="device")
    data = b"hay\nneedle here\nhay\n" * 50
    recs = _scan_records(eng, data)
    assert len(recs) == 1
    args = recs[0]["args"]
    assert args["mode"] == eng.mode and eng.mode in ("shift_and", "nfa", "dfa")
    assert args["bytes"] == len(data)
    # the route is present on the device path too: XLA on the CPU backend
    assert args["kernel"] == "xla" and args["interpret"] is False
    assert args["matches"] == 50


def test_engine_scan_no_record_without_context():
    from distributed_grep_tpu.ops.engine import GrepEngine

    eng = GrepEngine("needle", backend="cpu")
    res = eng.scan(b"needle\n")  # must not raise, must not need a buffer
    assert res.n_matches == 1


ENGINE_SPANS = ("engine:prep", "engine:wait", "engine:decode",
                "engine:confirm", "engine:stitch")


def test_engine_spans_nest_under_the_scan():
    """An interpret-mode Pallas scan emits the five engine:* spans as
    children of its scan:<mode> span, which nests under the worker's
    map:compute; its compile counter reads the compiles of the first
    scan and none on the repeat."""
    import jax

    from distributed_grep_tpu.ops.engine import GrepEngine

    # The first scan must compile whatever tests ran before in this
    # process: drop jax's in-memory caches, and scan for a pattern no
    # other test compiles a kernel for.
    jax.clear_caches()
    eng = GrepEngine("spindle", backend="device", interpret=True)
    data = b"hay\nspindle here\nhay\n" * 200
    buf = spans.SpanBuffer()
    with spans.task_context(buf, job="j", worker=0, task=3, attempt="a",
                            kind="map"):
        with spans.span("map:compute", cat="map"):
            eng.scan(data)
        eng.scan(data)
    recs = buf.drain()
    scans = [r for r in recs if r["name"] == f"scan:{eng.mode}"]
    assert [s["parent"] for s in scans] == ["map:compute", None]
    for name in ENGINE_SPANS:
        got = [r for r in recs if r["name"] == name]
        assert len(got) >= 2, name  # each scan has its own
        assert {r["parent"] for r in got} == {f"scan:{eng.mode}"}, name
        assert all(r["task"] == 3 and r["cat"] == "engine" for r in got)
    first, repeat = (s["args"]["compiles"] for s in scans)
    assert first > 0 and repeat == 0


def test_scan_batch_prep_is_a_child_of_map_compute(tmp_path):
    """scan_batch's lookups and reads run before scan(): their
    engine:prep spans have the worker's map:compute as parent, while the
    scan's own prep nests under scan:<mode>."""
    from distributed_grep_tpu.ops.engine import GrepEngine

    p = tmp_path / "big.txt"
    p.write_bytes(b"hay\nneedle here\nhay\n" * 100)
    eng = GrepEngine("needle", backend="device", device_min_bytes=16)
    buf = spans.SpanBuffer()
    with spans.task_context(buf, job="j", worker=0, task=1, attempt="a",
                            kind="map"):
        with spans.span("map:compute", cat="map"):
            out = eng.scan_batch([("big.txt", str(p))])
    assert out[0][1].n_matches == 100
    parents = [r["parent"] for r in buf.drain()
               if r["name"] == "engine:prep"]
    assert "map:compute" in parents and f"scan:{eng.mode}" in parents


def test_collect_thread_spans_carry_the_task_tags():
    """A multi-segment scan collects on pool threads: their spans (no
    span is open on those threads, so no parent) keep the task's tags
    instead of being dropped."""
    from distributed_grep_tpu.ops.engine import GrepEngine

    eng = GrepEngine("needle", backend="device", segment_bytes=4096,
                     target_lanes=16)
    data = b"hay\nneedle here\nhay\n" * 500
    buf = spans.SpanBuffer()
    with spans.task_context(buf, job="j", worker=2, task=5, attempt="a",
                            kind="map"):
        res = eng.scan(data)
    assert res.n_matches == 500
    recs = buf.drain()
    pooled = [r for r in recs if r["name"] == "engine:decode"]
    n_segs = -(-len(data) // 4096)
    assert len(pooled) >= n_segs
    for r in pooled:
        assert r["parent"] is None
        assert (r["job"], r["worker"], r["task"]) == ("j", 2, 5)
    waits = [r["parent"] for r in recs if r["name"] == "engine:wait"]
    assert waits.count(None) >= n_segs  # the fetches, on the pool
    assert waits.count(f"scan:{eng.mode}") == n_segs  # the dispatches


@pytest.mark.service
def test_served_job_explain_has_wait_and_build_rows(tmp_path):
    """A served job's explain reports the scheduler's map:wait and
    reduce:wait (runnable to assigned, one per task) and the worker's
    shuffle:build inside map:shuffle."""
    from distributed_grep_tpu.runtime.service import GrepService

    files = []
    for i in range(3):
        p = tmp_path / f"f{i}.txt"
        p.write_bytes(b"needle %d\nhay\n" % i * 50)
        files.append(str(p))
    svc = GrepService(work_root=tmp_path / "root", spans=True)
    try:
        svc.start_local_workers(2)
        jid = svc.submit(JobConfig(
            input_files=files,
            application="distributed_grep_tpu.apps.grep_tpu",
            app_options={"pattern": "needle", "backend": "cpu"},
            n_reduce=2, spans=True, batch_bytes=0,
        ))
        assert svc.wait_job(jid, timeout=60)
        st = svc.job_explain(jid)["stages"]
    finally:
        svc.stop()
    assert st["map:wait"]["count"] == 3
    assert st["reduce:wait"]["count"] == 2
    assert st["map:wait"]["seconds"] >= 0 and st["reduce:wait"]["seconds"] >= 0
    assert st["shuffle:build"]["count"] == 3
    assert (st["map:shuffle"]["self_seconds"]
            <= st["map:shuffle"]["seconds"] - st["shuffle:build"]["seconds"]
            + 1e-5)


def test_span_batch_retry_dedup(tmp_path):
    """A transport-level RPC retry reships the same (worker, seq) batch;
    the scheduler persists it exactly once (events.jsonl must cover each
    attempt once, not once per retry)."""
    from distributed_grep_tpu.runtime.scheduler import Scheduler

    buf = spans.SpanBuffer()
    buf.add({"t": "instant", "name": "index:maybe", "ts": 1.0,
             "worker": 0})
    seq, batch = buf.drain_batch()
    assert seq == 1 and len(batch) == 1
    assert buf.drain_batch() == (-1, [])  # empty drain allocates no seq

    log_path = tmp_path / "events.jsonl"
    s = Scheduler(files=["a"], n_reduce=1, event_log=spans.EventLog(log_path))
    try:
        args = rpc.HeartbeatArgs(task_type="map", task_id=0, worker_id=0,
                                 spans=batch, spans_seq=seq, sent_at=1.0)
        s.heartbeat("map", 0, args=args)
        s.heartbeat("map", 0, args=args)  # the retry: identical batch
        events = [e for e in spans.EventLog.read(log_path)
                  if e.get("name") == "index:maybe"]
        assert len(events) == 1
    finally:
        s.stop()


# ------------------------------------------------------------ clock sync

def test_clock_sync_rtt_midpoint():
    cs = spans.ClockSync()
    # worker clock 5 s behind the coordinator; 200 ms round trip ->
    # the request transit is priced at rtt/2
    off = cs.observe(1, sent_at=100.0, recv_at=105.1, rtt_s=0.2)
    assert off == pytest.approx(5.0)
    # EWMA folds later observations in instead of jumping
    off2 = cs.observe(1, sent_at=200.0, recv_at=205.2, rtt_s=0.2)
    assert 5.0 < off2 < 5.1
    # no send timestamp (old worker / piggyback off): no estimate
    assert cs.observe(1, sent_at=0.0, recv_at=1.0, rtt_s=0.1) is None
    assert cs.observe(-1, sent_at=1.0, recv_at=1.0, rtt_s=0.1) is None


# --------------------------------------------------- disabled = true no-op

def test_disabled_rpc_payloads_unchanged():
    """Span-disabled runs put NOTHING extra on the wire: serialized args
    keep exactly the pre-span key set (old coordinators interop)."""
    hb = rpc.to_dict(rpc.HeartbeatArgs(task_type="map", task_id=1,
                                       worker_id=0, grace_s=2.0))
    assert set(hb) == {"task_type", "task_id", "worker_id", "grace_s"}
    fin = rpc.to_dict(rpc.TaskFinishedArgs(task_id=1, worker_id=0,
                                           produced_parts=[0, 1]))
    assert set(fin) == {"task_id", "worker_id", "produced_parts"}
    # and the piggybacked forms do serialize when populated
    hb2 = rpc.to_dict(rpc.HeartbeatArgs(
        task_type="map", task_id=1, spans=[{"t": "instant"}],
        metrics={"bytes_scanned": 5}, sent_at=1.0, rtt_s=0.1,
    ))
    assert {"spans", "metrics", "sent_at", "rtt_s"} <= set(hb2)
    # old-coordinator round trip: a default-shaped payload reconstructs
    assert rpc.from_dict("HeartbeatArgs", hb).grace_s == 2.0


def test_disabled_job_writes_no_event_log(tmp_path, monkeypatch):
    from distributed_grep_tpu.runtime.job import run_job

    monkeypatch.delenv("DGREP_SPANS", raising=False)
    monkeypatch.delenv("DGREP_TRACE_DIR", raising=False)
    (tmp_path / "in.txt").write_bytes(b"needle\nhay\n")
    cfg = JobConfig(
        input_files=[str(tmp_path / "in.txt")],
        n_reduce=2,
        work_dir=str(tmp_path / "work"),
        application="distributed_grep_tpu.apps.grep",
        app_options={"pattern": "needle"},
    )
    res = run_job(cfg, n_workers=2)
    assert res.results  # job actually ran
    assert not (tmp_path / "work" / "events.jsonl").exists()
    # trace.annotate stays a cheap nullcontext alongside (satellite #4)
    from distributed_grep_tpu.utils import trace

    assert isinstance(trace.annotate("x"), contextlib.nullcontext)


# ------------------------------------------------- local job, end to end

def test_local_job_spans_end_to_end(tmp_path):
    from distributed_grep_tpu.runtime.job import run_job

    (tmp_path / "in.txt").write_bytes(b"needle one\nhay\nneedle two\n" * 20)
    cfg = JobConfig(
        input_files=[str(tmp_path / "in.txt")],
        n_reduce=2,
        work_dir=str(tmp_path / "work"),
        application="distributed_grep_tpu.apps.grep_tpu",
        app_options={"pattern": "needle", "backend": "cpu"},
        spans=True,
        job_id="local-e2e",
    )
    res = run_job(cfg, n_workers=2)
    assert res.results
    log_path = tmp_path / "work" / "events.jsonl"
    assert log_path.exists()
    events = spans.EventLog.read(log_path)
    names = [e.get("name") for e in events]
    # coordinator decisions
    assert "assign_map" in names and "map_committed" in names
    assert "assign_reduce" in names and "reduce_committed" in names
    # worker task/phase spans, tagged with the causal ids
    task_spans = [e for e in events if e.get("name") == "map:task"]
    assert task_spans
    for e in task_spans:
        assert e["job"] == "local-e2e" and e["kind"] == "map"
        assert isinstance(e["worker"], int) and e["worker"] >= 0
        assert e["attempt"] and "dur" in e
    assert any(e.get("name") == "reduce:task" for e in events)
    # engine per-scan telemetry promoted from engine.stats
    scans = [e for e in events if str(e.get("name", "")).startswith("scan:")]
    assert scans and all("kernel" in s["args"] for s in scans)


# --------------------------------- HTTP job + killed worker (acceptance)

def _run_http_spans_job(tmp_path, corpus):
    """One HTTP job with the span pipeline on: worker 0 dies after reading
    its first split (the SIGKILL stand-in the suite uses, WorkerKilled);
    the surviving worker re-executes it after the timeout sweep."""
    from distributed_grep_tpu.apps.loader import load_application
    from distributed_grep_tpu.runtime.http_coordinator import CoordinatorServer
    from distributed_grep_tpu.runtime.http_transport import HttpTransport
    from distributed_grep_tpu.runtime.worker import WorkerKilled, WorkerLoop

    cfg = JobConfig(
        input_files=[str(p) for p in corpus.values()],
        application="distributed_grep_tpu.apps.grep",
        app_options={"pattern": "hello"},
        n_reduce=2,
        work_dir=str(tmp_path / "job"),
        coordinator_port=0,
        task_timeout_s=1.0,
        sweep_interval_s=0.1,
        spans=True,
        job_id="http-e2e",
    )
    server = CoordinatorServer(cfg)
    server.start()
    addr = f"127.0.0.1:{server.port}"
    app = load_application("distributed_grep_tpu.apps.grep", pattern="hello")

    def dying():
        loop = WorkerLoop(HttpTransport(addr), app, spans_enabled=True,
                          job_id="http-e2e",
                          fault_hooks={"after_map_read": _raise_killed})
        with contextlib.suppress(WorkerKilled):
            loop.run()

    t1 = threading.Thread(target=dying)
    t1.start()
    t1.join(timeout=10.0)
    assert not server.scheduler.done()

    survivor = WorkerLoop(HttpTransport(addr), app, spans_enabled=True,
                          job_id="http-e2e")
    t2 = threading.Thread(target=survivor.run)
    t2.start()
    assert server.wait_done(timeout=30.0)
    status = server.status()  # before shutdown: "during the run" surface
    t2.join(timeout=10.0)
    server.shutdown(linger_s=0.1)
    return server, status, survivor


def _raise_killed():
    from distributed_grep_tpu.runtime.worker import WorkerKilled

    raise WorkerKilled()


def test_http_job_spans_killed_worker_acceptance(tmp_path, corpus):
    server, status, survivor = _run_http_spans_job(tmp_path, corpus)
    events = spans.EventLog.read(Path(server.config.work_dir) / "events.jsonl")

    # every task attempt is covered: the coordinator's assign events carry
    # (task, worker, attempt) — including the killed attempt 1 and its
    # re-execution as attempt 2 after the timeout sweep
    assigns = [e for e in events if e.get("name") == "assign_map"]
    n_maps = len(server.scheduler.map_tasks)
    assert len(assigns) > n_maps  # more assignments than tasks = a retry
    retried = [e for e in assigns if e["args"]["attempt"] >= 2]
    assert retried
    assert any(e.get("name") == "task_timeout" for e in events)

    # the re-executed attempt's spans landed on the SURVIVING worker's row
    retried_task = retried[0]["args"]["task"]
    retask = [e for e in events if e.get("name") == "map:task"
              and e.get("task") == retried_task]
    assert retask and retask[-1]["worker"] == survivor.worker_id

    # per-worker aggregates shipped via heartbeat/finished piggyback
    w = status["workers"][str(survivor.worker_id)]
    assert w["metrics"]["bytes_scanned"] > 0
    assert w["metrics"]["gbps"] > 0
    assert w["last_heartbeat_age_s"] >= 0

    # trace-export: valid Chrome trace_event JSON, re-executed attempt on
    # the surviving worker's row
    from distributed_grep_tpu.__main__ import main

    out = tmp_path / "trace.json"
    rc = main(["trace-export", server.config.work_dir, "-o", str(out)])
    assert rc == 0
    doc = json.load(open(out))
    evs = doc["traceEvents"]
    assert evs
    for ev in evs:
        assert ev["ph"] in ("X", "i", "M")
        assert isinstance(ev["pid"], int) and isinstance(ev["tid"], int)
        if ev["ph"] != "M":
            assert isinstance(ev["ts"], float) and ev["ts"] > 0
    names = {(ev["name"], ev["tid"]) for ev in evs}
    # coordinator row (tid 0) holds the scheduling decisions
    assert ("assign_map", 0) in names and ("task_timeout", 0) in names
    # the re-executed map task renders on the survivor's row
    survivor_tid = survivor.worker_id + 1
    retask_evs = [ev for ev in evs if ev["name"] == "map:task"
                  and ev["args"].get("task") == retried_task]
    assert retask_evs and retask_evs[-1]["tid"] == survivor_tid
    # row names are declared via metadata events
    thread_names = {ev["args"]["name"] for ev in evs
                    if ev["ph"] == "M" and ev["name"] == "thread_name"}
    assert "coordinator" in thread_names
    assert f"worker {survivor.worker_id}" in thread_names


@pytest.mark.slow
def test_http_job_spans_sigkill_worker_subprocess(tmp_path):
    """The literal SIGKILL variant: a real worker subprocess is SIGKILLed
    mid-map; the surviving in-process worker re-executes after the timeout
    sweep, and events.jsonl + trace-export cover both attempts."""
    import os
    import signal
    import subprocess
    import sys

    from distributed_grep_tpu.apps.loader import load_application
    from distributed_grep_tpu.runtime.http_coordinator import CoordinatorServer
    from distributed_grep_tpu.runtime.http_transport import HttpTransport
    from distributed_grep_tpu.runtime.types import TaskState
    from distributed_grep_tpu.runtime.worker import WorkerLoop

    # task 0 is a wide-window split (a ~16 MB re-loop map runs ~100+ ms),
    # so the SIGKILL lands mid-task with high probability
    big = tmp_path / "big.txt"
    big.write_bytes((b"x" * 120 + b"\n") * 140_000 + b"hello tail\n")
    small = tmp_path / "small.txt"
    small.write_bytes(b"hello small\nnothing\n")
    cfg = JobConfig(
        input_files=[str(big), str(small)],
        application="distributed_grep_tpu.apps.grep",
        app_options={"pattern": "hello"},
        n_reduce=2,
        work_dir=str(tmp_path / "job"),
        coordinator_port=0,
        task_timeout_s=2.0,
        sweep_interval_s=0.1,
        spans=True,
        job_id="sigkill-e2e",
    )
    server = CoordinatorServer(cfg)
    server.start()
    addr = f"127.0.0.1:{server.port}"
    repo = str(Path(__file__).resolve().parents[1])
    env = {"PYTHONPATH": repo, "PATH": os.environ.get("PATH", "/usr/bin:/bin"),
           "DGREP_LOG": "WARNING", "JAX_PLATFORMS": "cpu"}
    w1 = subprocess.Popen(
        [sys.executable, "-m", "distributed_grep_tpu", "worker",
         "--addr", addr],
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL, env=env,
    )
    caught = False
    try:
        deadline = time.monotonic() + 90.0
        while time.monotonic() < deadline:
            with server.scheduler._lock:
                caught = any(t.state is TaskState.IN_PROGRESS
                             for t in server.scheduler.map_tasks)
                done = server.scheduler._done_locked()
            if caught or done:
                break
            time.sleep(0.005)
        if caught:
            os.kill(w1.pid, signal.SIGKILL)
    finally:
        if w1.poll() is None and not caught:
            w1.kill()
    if not caught:
        server.shutdown(linger_s=0.0)
        pytest.skip("never caught the worker subprocess mid-task")
    w1.wait(timeout=30)

    app = load_application("distributed_grep_tpu.apps.grep", pattern="hello")
    survivor = WorkerLoop(HttpTransport(addr), app, spans_enabled=True,
                          job_id="sigkill-e2e")
    t = threading.Thread(target=survivor.run)
    t.start()
    assert server.wait_done(timeout=180.0)
    t.join(timeout=15.0)
    if not server.metrics.counters.get("map_retries", 0):
        server.shutdown(linger_s=0.0)
        pytest.skip("SIGKILL landed after the map committed — no retry")
    server.shutdown(linger_s=0.1)

    events = spans.EventLog.read(Path(cfg.work_dir) / "events.jsonl")
    assigns = [e for e in events if e.get("name") == "assign_map"]
    retried = [e for e in assigns if e["args"]["attempt"] >= 2]
    assert retried and any(e.get("name") == "task_timeout" for e in events)
    retried_task = retried[0]["args"]["task"]
    retask = [e for e in events if e.get("name") == "map:task"
              and e.get("task") == retried_task]
    assert retask and retask[-1]["worker"] == survivor.worker_id
    doc = spans.export_chrome_trace(events)
    tids = {ev["tid"] for ev in doc["traceEvents"]
            if ev["ph"] == "X" and ev["name"] == "map:task"
            and ev["args"].get("task") == retried_task}
    assert (survivor.worker_id + 1) in tids


def test_trace_export_cli_missing_log(tmp_path):
    from distributed_grep_tpu.__main__ import main

    assert main(["trace-export", str(tmp_path)]) == 2


# --------------------------------------------------- /status liveness

def test_status_inflight_and_worker_liveness(tmp_path, corpus):
    """GET /status surfaces stragglers before the sweeper fires: heartbeat
    age and any declared grace window per in-flight task, plus per-worker
    last-heartbeat age."""
    from distributed_grep_tpu.runtime.http_coordinator import CoordinatorServer
    from distributed_grep_tpu.runtime.http_transport import HttpTransport

    cfg = JobConfig(
        input_files=[str(p) for p in corpus.values()],
        app_options={"pattern": "hello"},
        n_reduce=2,
        work_dir=str(tmp_path / "job"),
        coordinator_port=0,
        task_timeout_s=60.0,  # nothing must time out under us
    )
    server = CoordinatorServer(cfg)
    server.start()
    try:
        t = HttpTransport(f"127.0.0.1:{server.port}")
        a = t.assign_task(rpc.AssignTaskArgs())
        assert a.assignment == rpc.Assignment.MAP
        t.heartbeat(rpc.HeartbeatArgs(task_type="map", task_id=a.task_id,
                                      worker_id=a.worker_id, grace_s=30.0))
        time.sleep(0.05)
        status = t.fetch_status()
        inflight = status["in_flight"]
        assert len(inflight) == 1
        row = inflight[0]
        assert row["type"] == "map" and row["task_id"] == a.task_id
        assert row["attempts"] == 1 and row["heartbeat_age_s"] >= 0
        assert row["grace_s"] == 30.0 and row["grace_remaining_s"] > 0
        w = status["workers"][str(a.worker_id)]
        assert w["last_heartbeat_age_s"] >= 0
        assert w["task"] == f"map:{a.task_id}"
    finally:
        server.shutdown(linger_s=0.0)


# ------------------------------------------------------- logging lint

def test_runtime_modules_use_structured_logging():
    """One source of truth: the grep-based lint this test used to carry
    moved into the invariant checker (analysis/rules.py rule `logging`,
    AST-walked — prints inside nested expressions are caught too); this
    is now a thin `analyze --rule logging` invocation so the obs suite
    keeps failing loudly on control-plane print()/root-logger use."""
    from distributed_grep_tpu.analysis import run_analysis

    violations = run_analysis(rules=["logging"])
    assert not violations, "\n".join(v.render() for v in violations)
