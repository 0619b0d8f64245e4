"""Grep-as-a-service suite: the persistent multi-tenant coordinator
(runtime/service.py) and the cross-job compiled-model cache
(ops/engine.cached_engine).

Covers ISSUE 6's acceptance bars end to end:

* warm resubmit of an identical pattern registers compile_cache_hits and
  SKIPS engine reconstruction (GrepEngine.__init__ spy);
* two jobs submitted concurrently over SHARED workers produce outputs
  byte-identical to the same jobs run serially via run_job;
* a worker killed mid-job-A while job-B runs re-executes only A's attempt
  (B finishes with zero retries) — the faults-matrix pattern, multi-tenant;
* cancel leaves the other job's result intact;
* admission control (queue depth / running cap) rejects loudly;
* the one-shot serve_coordinator / cmd_coordinator stdout contract is
  unperturbed by the service layer (back-compat pin; bench.py's own
  one-JSON-line contract is pinned by tests/test_bench_contract.py).

Standalone: ``python -m pytest tests/test_service.py -q``.  CPU-only; the
grep engines run their native/host paths (backend "cpu").
"""

from __future__ import annotations

import json
import threading
import time
import urllib.error
import urllib.request
from pathlib import Path

import pytest

from distributed_grep_tpu.ops import engine as engine_mod
from distributed_grep_tpu.runtime.job import run_job
from distributed_grep_tpu.runtime.service import (
    AdmissionError,
    GrepService,
    JobState,
    ServiceServer,
)
from distributed_grep_tpu.utils.config import JobConfig

pytestmark = pytest.mark.service


@pytest.fixture(autouse=True)
def _fresh_cache():
    """Each test starts with an empty compiled-model cache and zeroed
    counters."""
    engine_mod.model_cache_clear()
    yield
    engine_mod.model_cache_clear()


@pytest.fixture
def service(tmp_path):
    svc = GrepService(
        work_root=tmp_path / "svc",
        task_timeout_s=5.0,
        sweep_interval_s=0.1,
    )
    yield svc
    svc.stop()


def grep_config(corpus, pattern="hello", **kw) -> JobConfig:
    defaults = dict(
        input_files=[str(p) for p in corpus.values()],
        application="distributed_grep_tpu.apps.grep_tpu",
        app_options={"pattern": pattern, "backend": "cpu"},
        n_reduce=3,
    )
    defaults.update(kw)
    return JobConfig(**defaults)


def outputs_by_name(paths) -> dict[str, bytes]:
    return {Path(p).name: Path(p).read_bytes() for p in paths}


# --------------------------------------------------------- model cache unit

def test_cached_engine_hit_returns_same_object():
    e1, v1 = engine_mod.cached_engine("needle", ignore_case=False, backend="cpu")
    e2, v2 = engine_mod.cached_engine("needle", ignore_case=False, backend="cpu")
    e3, v3 = engine_mod.cached_engine("other", ignore_case=False, backend="cpu")
    assert (v1, v2, v3) == ("miss", "hit", "miss")
    assert e1 is e2 and e1 is not e3
    c = engine_mod.model_cache_counters()
    assert c["compile_cache_hits"] == 1 and c["compile_cache_misses"] == 2


def test_cached_engine_disabled_by_env(monkeypatch):
    monkeypatch.setenv("DGREP_MODEL_CACHE", "0")
    e1, v1 = engine_mod.cached_engine("needle", backend="cpu")
    e2, v2 = engine_mod.cached_engine("needle", backend="cpu")
    assert v1 == v2 == "off" and e1 is not e2
    assert engine_mod.model_cache_counters() == {}  # untouched


def test_cached_engine_lru_eviction(monkeypatch):
    monkeypatch.setenv("DGREP_MODEL_CACHE", "2")
    engine_mod.cached_engine("p1", backend="cpu")
    engine_mod.cached_engine("p2", backend="cpu")
    engine_mod.cached_engine("p3", backend="cpu")  # evicts p1 (LRU)
    c = engine_mod.model_cache_counters()
    assert c["compile_cache_evictions"] == 1
    _, v = engine_mod.cached_engine("p3", backend="cpu")
    assert v == "hit"
    _, v = engine_mod.cached_engine("p1", backend="cpu")
    assert v == "miss"  # was evicted


def test_cached_engine_unhashable_args_bypass():
    class Opaque:  # an options object with no stable identity key
        __hash__ = None

    e, v = engine_mod.cached_engine("needle", backend="cpu",
                                    device_min_bytes=1 << 20)
    assert v == "miss"
    e2, v2 = engine_mod.cached_engine("needle", backend="cpu",
                                      devices=[Opaque()])
    assert v2 == "off" and e2 is not e


def test_cached_engine_mesh_and_device_list_bypass():
    """REAL meshes must bypass explicitly: jax.sharding.Mesh hashes by
    VALUE, so the unhashability guard alone would cache-share one
    tenant's mesh engine (mutated _accel_cached/demotion state and all)
    with the next — the off verdict must come from the mesh key itself.
    Explicit device LISTS likewise; symbolic devices='all' (the grep_tpu
    default) stays cacheable."""
    from distributed_grep_tpu.parallel.mesh import make_mesh

    mesh = make_mesh((2,), ("data",))
    e, v = engine_mod.cached_engine("needle", backend="device", mesh=mesh,
                                    interpret=True)
    assert v == "off"
    e2, v2 = engine_mod.cached_engine("needle", backend="device", mesh=mesh,
                                      interpret=True)
    assert v2 == "off" and e2 is not e
    assert engine_mod.model_cache_counters() == {}  # never touched
    import jax

    _, v3 = engine_mod.cached_engine("needle", backend="cpu",
                                     devices=jax.local_devices()[:1])
    assert v3 == "off"
    _, v4 = engine_mod.cached_engine("needle", backend="cpu", devices="all")
    assert v4 == "miss"  # the symbolic form is a stable key


def test_cache_counters_stamped_into_engine_stats():
    e, _ = engine_mod.cached_engine("needle", backend="cpu")
    engine_mod.cached_engine("needle", backend="cpu")  # a hit
    e.scan(b"a needle in a haystack\n")
    assert e.stats["compile_cache_hits"] == 1
    assert e.stats["compile_cache_misses"] == 1


# ------------------------------------------------------- service end to end

def test_late_reduce_attempt_on_terminal_job_aborts_not_done(
        tmp_path, corpus, service):
    """A duplicate reduce attempt that outlives its job's finalize must
    be ABORTED, never told done: done=True let a late attempt (timeout
    churn spawns several) treat its PARTIAL shuffle cursor as complete
    and rename a short output over the finalized job's committed file
    (posix rename-last-wins — caught by the chaos matrix as a rare
    byte-identity failure)."""
    from distributed_grep_tpu.runtime import rpc as rpc_mod

    service.start_local_workers(2)
    jid = service.submit(grep_config(corpus))
    assert service.wait_job(jid, timeout=60), service.job_status(jid)
    reply = service.reduce_next_file(
        rpc_mod.ReduceNextFileArgs(task_id=0, files_processed=1,
                                   job_id=jid, worker_id=99),
        timeout=0.1,
    )
    assert getattr(reply, "abort", False) and not reply.done
    # unknown/evicted job ids abort the attempt too
    reply2 = service.reduce_next_file(
        rpc_mod.ReduceNextFileArgs(task_id=0, files_processed=0,
                                   job_id="job-999", worker_id=99),
        timeout=0.1,
    )
    assert getattr(reply2, "abort", False) and not reply2.done


def test_service_single_job_matches_run_job(tmp_path, corpus, service):
    service.start_local_workers(2)
    jid = service.submit(grep_config(corpus))
    assert service.wait_job(jid, timeout=60), service.job_status(jid)
    res = service.job_result(jid)
    assert res["state"] == JobState.DONE

    oracle = run_job(
        grep_config(corpus, work_dir=str(tmp_path / "serial")), n_workers=2
    )
    assert outputs_by_name(res["outputs"]) == outputs_by_name(
        oracle.output_files
    )


def test_warm_resubmit_hits_cache_and_skips_rebuild(tmp_path, corpus,
                                                    service, monkeypatch):
    """ISSUE 6 acceptance: the SECOND submit of an identical pattern (after
    an intervening different pattern, so the app-level same-config
    short-circuit cannot answer) registers >= 1 compile_cache_hits and
    constructs NO new engine.  Result tier off: the round-20 result
    cache would answer the resubmit without any engine touch at all
    (its own pins live in tests/test_result_cache.py)."""
    service._result_store = None
    constructions = []
    orig_init = engine_mod.GrepEngine.__init__

    def spying_init(self, *a, **kw):
        constructions.append(a)
        return orig_init(self, *a, **kw)

    monkeypatch.setattr(engine_mod.GrepEngine, "__init__", spying_init)
    service.start_local_workers(1)  # ONE worker: no sibling warms the key
    j1 = service.submit(grep_config(corpus, pattern="hello"))
    assert service.wait_job(j1, timeout=60)
    j2 = service.submit(grep_config(corpus, pattern="fox"))
    assert service.wait_job(j2, timeout=60)
    built_before = len(constructions)
    hits_before = engine_mod.model_cache_counters().get(
        "compile_cache_hits", 0
    )
    # warm resubmit of the first pattern
    j3 = service.submit(grep_config(corpus, pattern="hello"))
    assert service.wait_job(j3, timeout=60)
    assert service.job_result(j3)["state"] == JobState.DONE
    assert len(constructions) == built_before  # model rebuild skipped
    hits = engine_mod.model_cache_counters()["compile_cache_hits"]
    assert hits >= hits_before + 1
    # identical outputs cold vs warm
    assert outputs_by_name(service.job_result(j1)["outputs"]) == \
        outputs_by_name(service.job_result(j3)["outputs"])


def test_concurrent_jobs_byte_identical_to_serial(tmp_path, corpus, service):
    """ISSUE 6 acceptance: two jobs submitted concurrently to one daemon
    over SHARED workers produce outputs byte-identical to the same jobs
    run serially via run_job."""
    service.start_local_workers(2)
    cfg_a = grep_config(corpus, pattern="hello")
    cfg_b = grep_config(corpus, pattern="fox", n_reduce=2)
    ja = service.submit(cfg_a)
    jb = service.submit(cfg_b)
    assert service.wait_job(ja, timeout=60), service.job_status(ja)
    assert service.wait_job(jb, timeout=60), service.job_status(jb)
    got_a = outputs_by_name(service.job_result(ja)["outputs"])
    got_b = outputs_by_name(service.job_result(jb)["outputs"])

    want_a = outputs_by_name(run_job(
        grep_config(corpus, pattern="hello",
                    work_dir=str(tmp_path / "sa")), n_workers=2
    ).output_files)
    want_b = outputs_by_name(run_job(
        grep_config(corpus, pattern="fox", n_reduce=2,
                    work_dir=str(tmp_path / "sb")), n_workers=2
    ).output_files)
    assert got_a == want_a
    assert got_b == want_b


def test_worker_kill_mid_job_a_reexecutes_only_a(tmp_path, corpus):
    """ISSUE 6 acceptance (faults-style, multi-tenant): SIGKILL-shaped
    worker death mid-job-A while job-B is running re-executes only A's
    attempt — B completes with zero retries and both outputs stay exact."""
    from distributed_grep_tpu.runtime.worker import WorkerKilled

    svc = GrepService(
        work_root=tmp_path / "svc",
        task_timeout_s=2.0,
        sweep_interval_s=0.1,
    )
    try:
        # the FIRST worker (whichever one) to read a map split of job A
        # (job ids are deterministic: job-1 = first submit) dies there —
        # keyed on the current THREAD's loop so the hook sees the job of
        # the worker actually running it
        from distributed_grep_tpu.runtime import worker as worker_mod

        loops_by_thread: dict[str, object] = {}
        kill_lock = threading.Lock()
        killed = {"n": 0}

        def die_on_job_a_map():
            loop = loops_by_thread.get(threading.current_thread().name)
            if loop is None or loop._rpc_job_id != "job-1":
                return
            with kill_lock:
                if killed["n"]:
                    return
                killed["n"] += 1
            raise WorkerKilled()

        orig_run = worker_mod.WorkerLoop.run

        def capturing_run(self):
            loops_by_thread[threading.current_thread().name] = self
            return orig_run(self)

        worker_mod.WorkerLoop.run, saved = capturing_run, orig_run
        try:
            svc.start_local_workers(
                2, fault_hooks_per_worker=[
                    {"after_map_read": die_on_job_a_map},
                    {"after_map_read": die_on_job_a_map},
                ]
            )
        finally:
            worker_mod.WorkerLoop.run = saved
        ja = svc.submit(grep_config(corpus, pattern="hello"))
        jb = svc.submit(grep_config(corpus, pattern="fox"))
        assert ja == "job-1"
        assert svc.wait_job(ja, timeout=60), svc.job_status(ja)
        assert svc.wait_job(jb, timeout=60), svc.job_status(jb)
        assert killed["n"] == 1  # the fault actually fired

        rec_a, rec_b = svc.record(ja), svc.record(jb)
        assert rec_a.metrics.counters.get("map_retries", 0) >= 1
        assert rec_b.metrics.counters.get("map_retries", 0) == 0
        assert rec_b.metrics.counters.get("reduce_retries", 0) == 0

        # both jobs' outputs byte-identical to serial runs
        for jid, pat, sub in ((ja, "hello", "sa"), (jb, "fox", "sb")):
            want = outputs_by_name(run_job(
                grep_config(corpus, pattern=pat,
                            work_dir=str(tmp_path / sub)), n_workers=2
            ).output_files)
            assert outputs_by_name(svc.job_result(jid)["outputs"]) == want
    finally:
        svc.stop()


def test_cancel_leaves_other_job_intact(tmp_path, corpus, service):
    # cancel job A before any worker exists (deterministically un-started
    # work), then attach workers: B must complete exactly, A stays
    # cancelled with no result.
    ja = service.submit(grep_config(corpus, pattern="hello"))
    jb = service.submit(grep_config(corpus, pattern="fox"))
    assert service.cancel(ja) == JobState.CANCELLED
    service.start_local_workers(2)
    assert service.wait_job(jb, timeout=60), service.job_status(jb)
    assert service.job_status(ja)["state"] == JobState.CANCELLED
    with pytest.raises(RuntimeError):
        service.job_result(ja)
    want = outputs_by_name(run_job(
        grep_config(corpus, pattern="fox",
                    work_dir=str(tmp_path / "sb")), n_workers=2
    ).output_files)
    assert outputs_by_name(service.job_result(jb)["outputs"]) == want


def test_admission_control_rejects_beyond_queue(tmp_path, corpus):
    svc = GrepService(work_root=tmp_path / "svc", max_jobs=1, queue_depth=1)
    try:
        # no workers attached: jobs stay running/queued
        svc.submit(grep_config(corpus))          # running slot
        svc.submit(grep_config(corpus))          # queued slot
        with pytest.raises(AdmissionError):
            svc.submit(grep_config(corpus))      # over the queue cap
    finally:
        svc.stop()


def test_submit_rejects_unreadable_inputs(tmp_path, corpus, service):
    cfg = grep_config(corpus)
    cfg.input_files = [str(tmp_path / "no-such-file.txt")]
    with pytest.raises(ValueError):
        service.submit(cfg)


def test_env_knob_accessors(monkeypatch):
    from distributed_grep_tpu.runtime.service import (
        env_service_max_jobs,
        env_service_queue,
    )

    monkeypatch.setenv("DGREP_SERVICE_MAX_JOBS", "7")
    monkeypatch.setenv("DGREP_SERVICE_QUEUE", "3")
    assert env_service_max_jobs() == 7
    assert env_service_queue() == 3
    monkeypatch.setenv("DGREP_SERVICE_MAX_JOBS", "bogus")
    assert env_service_max_jobs(5) == 5  # malformed keeps the default
    monkeypatch.setenv("DGREP_MODEL_CACHE", "bogus")
    assert engine_mod.env_model_cache_entries(9) == 9


# ------------------------------------------------------------- HTTP surface

def test_http_api_submit_status_result_and_telemetry(tmp_path, corpus,
                                                     monkeypatch):
    """The full HTTP surface: POST /jobs -> GET /jobs/<id> -> result;
    service /status exposes queue/jobs/workers with piggybacked
    compile_cache_* counters; per-job events.jsonl carries the
    cache:hit|miss instants and trace-export renders them.  Result tier
    off: the resubmit must SCAN for compile_cache_hits to register."""
    monkeypatch.setenv("DGREP_RESULT_CACHE", "0")
    svc = GrepService(
        work_root=tmp_path / "svc", spans=True,
        task_timeout_s=5.0, sweep_interval_s=0.1,
    )
    server = ServiceServer(svc)
    server.start()
    base = f"http://127.0.0.1:{server.port}"

    def call(method, path, body=None):
        req = urllib.request.Request(f"{base}{path}", data=body, method=method)
        if body is not None:
            req.add_header("Content-Type", "application/json")
        with urllib.request.urlopen(req, timeout=30) as r:
            return json.loads(r.read())

    try:
        svc.start_local_workers(1)
        cfg = grep_config(corpus, spans=True)
        jid = call("POST", "/jobs", cfg.to_json().encode())["job_id"]
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            st = call("GET", f"/jobs/{jid}")
            if st["state"] in (JobState.DONE, JobState.FAILED):
                break
            time.sleep(0.1)
        assert st["state"] == JobState.DONE, st
        assert st["map"]["completed"] == st["map"]["total"] == len(corpus)
        res = call("GET", f"/jobs/{jid}/result")
        assert res["outputs"]
        # warm resubmit over HTTP: different pattern in between, then the
        # original again -> >= 1 cache hit visible in /status
        j2 = call("POST", "/jobs",
                  grep_config(corpus, pattern="fox").to_json().encode())
        j3 = call("POST", "/jobs", cfg.to_json().encode())
        for j in (j2["job_id"], j3["job_id"]):
            assert svc.wait_job(j, timeout=60)
        status = call("GET", "/status")
        assert status["service"] is True
        assert status["compile_cache"]["compile_cache_hits"] >= 1
        rows = list(status["workers"].values())
        assert rows and any(
            "compile_cache_hits" in (r.get("metrics") or {}) for r in rows
        )
        # cache instants on the span pipeline, through trace-export
        ev_path = tmp_path / "svc" / jid / "events.jsonl"
        assert ev_path.exists()
        names = {
            json.loads(line).get("name")
            for line in ev_path.read_text().splitlines() if line.strip()
        }
        assert "cache:miss" in names or "cache:hit" in names
        from distributed_grep_tpu.utils.spans import (
            EventLog,
            export_chrome_trace,
        )

        doc = export_chrome_trace(EventLog.read(ev_path))
        assert any(
            e.get("name", "").startswith("cache:")
            for e in doc["traceEvents"]
        )
        # unknown job and not-done result answer 404/409
        with pytest.raises(urllib.error.HTTPError) as ei:
            call("GET", "/jobs/job-999")
        assert ei.value.code == 404
    finally:
        svc.stop()
        server.shutdown()


def test_http_admission_answers_429(tmp_path, corpus):
    svc = GrepService(work_root=tmp_path / "svc", max_jobs=1, queue_depth=0)
    server = ServiceServer(svc)
    server.start()
    base = f"http://127.0.0.1:{server.port}"
    try:
        body = grep_config(corpus).to_json().encode()

        def post():
            req = urllib.request.Request(f"{base}/jobs", data=body,
                                         method="POST")
            req.add_header("Content-Type", "application/json")
            with urllib.request.urlopen(req, timeout=30) as r:
                return json.loads(r.read())

        post()  # fills the single running slot (no workers attached)
        with pytest.raises(urllib.error.HTTPError) as ei:
            post()
        assert ei.value.code == 429
        # malformed config answers 400
        req = urllib.request.Request(f"{base}/jobs", data=b'{"n_reduce": 0}',
                                     method="POST")
        req.add_header("Content-Type", "application/json")
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(req, timeout=30)
        assert ei.value.code == 400
    finally:
        svc.stop()
        server.shutdown()


def test_http_worker_attach_serves_service_jobs(tmp_path, corpus):
    """A stock `dgrep worker`-shaped attach (run_http_worker) detects the
    service daemon, scopes its data plane per job, and completes jobs."""
    from distributed_grep_tpu.runtime.http_transport import run_http_worker

    svc = GrepService(
        work_root=tmp_path / "svc", task_timeout_s=5.0, sweep_interval_s=0.1
    )
    server = ServiceServer(svc)
    server.start()
    try:
        t = threading.Thread(
            target=lambda: run_http_worker(
                addr=f"127.0.0.1:{server.port}", n_parallel=1
            ),
            daemon=True,
        )
        t.start()
        jid = svc.submit(grep_config(corpus))
        assert svc.wait_job(jid, timeout=60), svc.job_status(jid)
        want = outputs_by_name(run_job(
            grep_config(corpus, work_dir=str(tmp_path / "serial")),
            n_workers=2,
        ).output_files)
        assert outputs_by_name(svc.job_result(jid)["outputs"]) == want
    finally:
        svc.stop()
        server.shutdown()
        t.join(timeout=15)


# --------------------------------------------- crash recovery (round 10)

def test_service_restart_preserves_history_and_id_counter(tmp_path, corpus):
    """A restarted daemon reloads terminal jobs from jobs.jsonl (results
    still answerable) and continues the job-id counter — old work dirs
    are never clobbered by a new incarnation's ids."""
    svc = GrepService(work_root=tmp_path / "svc", task_timeout_s=5.0,
                      sweep_interval_s=0.1)
    svc.start_local_workers(2)
    j1 = svc.submit(grep_config(corpus))
    assert svc.wait_job(j1, timeout=60), svc.job_status(j1)
    outputs = outputs_by_name(svc.job_result(j1)["outputs"])
    svc.stop()

    svc2 = GrepService(work_root=tmp_path / "svc", task_timeout_s=5.0,
                       sweep_interval_s=0.1)
    try:
        assert svc2.job_status(j1)["state"] == JobState.DONE
        assert outputs_by_name(svc2.job_result(j1)["outputs"]) == outputs
        j2 = svc2.submit(grep_config(corpus, pattern="fox"))
        assert j2 == "job-2"  # counter resumed past the registry's max
        svc2.start_local_workers(1)
        assert svc2.wait_job(j2, timeout=60), svc2.job_status(j2)
    finally:
        svc2.stop()


def test_service_restart_resumes_mid_job_from_journal(tmp_path, corpus):
    """Daemon death mid-job: a new service over the same work root
    resumes the RUNNING job from its journal — completed maps replay as
    done (not re-assigned), the rest run, outputs stay exact.  The first
    service is ABANDONED, not stopped: a SIGKILL runs no teardown, so
    neither does this test."""
    from distributed_grep_tpu.runtime.worker import WorkerKilled

    svc_a = GrepService(work_root=tmp_path / "svc", task_timeout_s=5.0,
                        sweep_interval_s=0.1)
    killed = {"n": 0}

    def die_on_third_read():
        killed["n"] += 1
        if killed["n"] >= 3:
            raise WorkerKilled()

    svc_a.start_local_workers(
        1, fault_hooks_per_worker=[{"after_map_read": die_on_third_read}]
    )
    j1 = svc_a.submit(grep_config(corpus))  # 3 files -> 3 map tasks
    rec_a = svc_a.record(j1)
    deadline = time.monotonic() + 30
    while rec_a.metrics.counters.get("map_completed", 0) < 2 \
            or killed["n"] < 3:
        assert time.monotonic() < deadline, rec_a.metrics.counters
        time.sleep(0.05)
    # svc_a is now abandoned mid-job (its only worker is dead)

    svc_b = GrepService(work_root=tmp_path / "svc", task_timeout_s=5.0,
                        sweep_interval_s=0.1)
    try:
        assert svc_b.job_status(j1)["state"] == JobState.RUNNING
        svc_b.start_local_workers(2)
        assert svc_b.wait_job(j1, timeout=60), svc_b.job_status(j1)
        # journal replay skipped the two committed maps
        assert svc_b.record(j1).metrics.counters.get("map_assigned", 0) <= 1
        want = outputs_by_name(run_job(
            grep_config(corpus, work_dir=str(tmp_path / "serial")),
            n_workers=2,
        ).output_files)
        assert outputs_by_name(svc_b.job_result(j1)["outputs"]) == want
    finally:
        svc_b.stop()


def test_service_restart_readmits_queued_jobs(tmp_path, corpus):
    """Queued (never-started) jobs survive a daemon death: the submit
    record alone re-admits them at restart."""
    svc_a = GrepService(work_root=tmp_path / "svc", max_jobs=1,
                        task_timeout_s=5.0, sweep_interval_s=0.1)
    j1 = svc_a.submit(grep_config(corpus))              # running slot
    j2 = svc_a.submit(grep_config(corpus, pattern="fox"))  # queued
    assert svc_a.job_status(j2)["state"] == JobState.QUEUED
    # abandon svc_a (no workers ever attached; nothing ran)

    svc_b = GrepService(work_root=tmp_path / "svc", task_timeout_s=5.0,
                        sweep_interval_s=0.1)
    try:
        assert svc_b.job_status(j1)["state"] == JobState.RUNNING
        # default max_jobs has free slots: the re-admitted job starts
        assert svc_b.job_status(j2)["state"] in (JobState.RUNNING,
                                                 JobState.QUEUED)
        svc_b.start_local_workers(2)
        for jid, pat, sub in ((j1, "hello", "sa"), (j2, "fox", "sb")):
            assert svc_b.wait_job(jid, timeout=60), svc_b.job_status(jid)
            want = outputs_by_name(run_job(
                grep_config(corpus, pattern=pat,
                            work_dir=str(tmp_path / sub)), n_workers=2
            ).output_files)
            assert outputs_by_name(svc_b.job_result(jid)["outputs"]) == want
    finally:
        svc_b.stop()


def test_service_resume_disabled_still_advances_ids(tmp_path, corpus):
    svc_a = GrepService(work_root=tmp_path / "svc")
    j1 = svc_a.submit(grep_config(corpus))
    svc_b = GrepService(work_root=tmp_path / "svc", resume=False)
    try:
        with pytest.raises(KeyError):
            svc_b.record(j1)  # not re-admitted
        j2 = svc_b.submit(grep_config(corpus, pattern="fox"))
        assert j2 == "job-2"  # but the id space is never reused
    finally:
        svc_b.stop()


def test_registry_compaction_bounds_history_and_retires_ids(tmp_path,
                                                            corpus):
    """The registry is append-only over an unbounded job stream: startup
    trims the reload to the newest terminal records, rewrites the file
    compacted, and the id_floor record keeps every dropped job's id
    retired — old work dirs are never re-minted."""
    from distributed_grep_tpu.runtime.service import (
        _MAX_TERMINAL_RECORDS,
        ServiceRegistry,
    )

    root = tmp_path / "svc"
    root.mkdir()
    reg = ServiceRegistry(root)
    cfg = grep_config(corpus)
    n_hist = _MAX_TERMINAL_RECORDS + 40
    for i in range(1, n_hist + 1):
        jid = f"job-{i}"
        reg.record_submit(jid, cfg)
        reg.record_state(jid, JobState.DONE, outputs=[])
    reg.close()
    size_before = (root / ServiceRegistry.FILENAME).stat().st_size

    svc = GrepService(work_root=root)
    try:
        # in-memory reload bounded like the live table
        terminal = [r for r in svc._jobs.values()
                    if r.state == JobState.DONE]
        assert len(terminal) == _MAX_TERMINAL_RECORDS
        # the file itself was compacted
        assert (root / ServiceRegistry.FILENAME).stat().st_size \
            < size_before
        # dropped ids stay retired: the next mint continues past ALL of
        # the history, including the trimmed-away jobs
        jid = svc.submit(grep_config(corpus))
        assert jid == f"job-{n_hist + 1}"
        jobs, floor = ServiceRegistry.replay(root)
        assert floor >= n_hist + 2
        assert "job-1" not in jobs  # trimmed out of the file
    finally:
        svc.stop()


def test_resume_fails_job_whose_inputs_vanished(tmp_path, corpus):
    """An input deleted during the outage must FAIL the resumed job, not
    re-enqueue its map forever (plan_map_splits shrugs stat failures off,
    so resume re-runs submit's readability validation)."""
    svc_a = GrepService(work_root=tmp_path / "svc")
    j1 = svc_a.submit(grep_config(corpus))
    Path(svc_a.record(j1).config.input_files[0]).unlink()
    # abandon svc_a (daemon crash); restart over the same root
    svc_b = GrepService(work_root=tmp_path / "svc")
    try:
        st = svc_b.job_status(j1)
        assert st["state"] == JobState.FAILED
        assert "unreadable" in st["error"]
    finally:
        svc_b.stop()


def test_resume_env_knob_accessor(monkeypatch):
    from distributed_grep_tpu.runtime.service import env_service_resume

    assert env_service_resume() is True
    monkeypatch.setenv("DGREP_SERVICE_RESUME", "0")
    assert env_service_resume() is False
    monkeypatch.setenv("DGREP_SERVICE_RESUME", "false")
    assert env_service_resume() is False
    monkeypatch.setenv("DGREP_SERVICE_RESUME", "1")
    assert env_service_resume() is True


# ------------------------------------------------------- back-compat pins

def test_one_shot_serve_coordinator_contract_unperturbed(tmp_path, corpus):
    """The single-job coordinator entry point still returns the status
    dict with committed "outputs" — the service layer must not perturb
    the one-shot path (run alongside an HTTP worker thread)."""
    import socket

    from distributed_grep_tpu.apps.loader import load_application
    from distributed_grep_tpu.runtime.http_coordinator import serve_coordinator
    from distributed_grep_tpu.runtime.http_transport import HttpTransport
    from distributed_grep_tpu.runtime.worker import WorkerLoop

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    cfg = JobConfig(
        input_files=[str(p) for p in corpus.values()],
        app_options={"pattern": "hello"},
        n_reduce=3,
        work_dir=str(tmp_path / "job"),
        coordinator_port=port,
    )
    app = load_application("distributed_grep_tpu.apps.grep", pattern="hello")
    result: dict = {}

    def serve():
        result.update(serve_coordinator(cfg))

    ct = threading.Thread(target=serve)
    ct.start()
    time.sleep(0.3)
    wt = threading.Thread(
        target=lambda: WorkerLoop(
            HttpTransport(f"127.0.0.1:{port}"), app
        ).run()
    )
    wt.start()
    ct.join(timeout=60)
    wt.join(timeout=15)
    assert not ct.is_alive()
    assert len(result["outputs"]) == 3
    assert result["done"] is True


def test_cmd_coordinator_stdout_one_json_line(tmp_path, corpus, capsys,
                                              monkeypatch):
    """cmd_coordinator prints EXACTLY one JSON line naming the outputs
    (scripts and the multi-process tests parse it)."""
    from distributed_grep_tpu import __main__ as cli
    from distributed_grep_tpu.runtime import http_coordinator as hc

    cfg_path = tmp_path / "job.json"
    cfg_path.write_text(JobConfig(
        input_files=[str(p) for p in corpus.values()],
        work_dir=str(tmp_path / "job"),
    ).to_json())
    monkeypatch.setattr(
        hc, "serve_coordinator",
        lambda config, resume=False: {"outputs": ["a", "b"], "done": True},
    )
    rc = cli.main(["coordinator", "--config", str(cfg_path)])
    assert rc == 0
    out = capsys.readouterr().out
    lines = [ln for ln in out.splitlines() if ln.strip()]
    assert len(lines) == 1
    assert json.loads(lines[0]) == {"outputs": ["a", "b"]}


def test_quarantine_expiry_reprobation_streak_resumes():
    """Satellite pin (round 18): quarantine EXPIRY is re-probation, not
    absolution — the failure streak resumes at threshold-1, so ONE more
    attributed timeout after a real wall-clock expiry re-quarantines the
    worker immediately, with the window doubled (episode 2); a committed
    task is what clears the whole record."""
    from distributed_grep_tpu.runtime.scheduler import (
        QUARANTINE_AFTER_FAILURES,
        WorkerHealth,
    )

    h = WorkerHealth(base_s=0.1)
    for i in range(QUARANTINE_AFTER_FAILURES - 1):
        assert h.record_failure(5) == 0.0, i  # probation: no window yet
    assert h.record_failure(5) == pytest.approx(0.1)  # episode 1
    assert h.quarantine_remaining(5) > 0
    time.sleep(0.15)  # REAL expiry — no by-hand state surgery
    assert h.quarantine_remaining(5) == 0.0  # assignable again
    # one more timeout: straight back in, doubled window — no second
    # run-up of QUARANTINE_AFTER_FAILURES consecutive failures needed
    assert h.record_failure(5) == pytest.approx(0.2)
    time.sleep(0.25)
    assert h.quarantine_remaining(5) == 0.0
    h.record_success(5)  # a committed task clears streak AND episodes
    for _ in range(QUARANTINE_AFTER_FAILURES - 1):
        assert h.record_failure(5) == 0.0
    assert h.record_failure(5) == pytest.approx(0.1)  # episode 1 again
