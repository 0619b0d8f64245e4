"""Service metrics tier (round 15): typed instruments, Prometheus
/metrics exposition, rolling-window cache rates, /status latency
summary, and the `dgrep explain` routing report.

Standalone-runnable:  python -m pytest tests/ -q -m metrics
"""

from __future__ import annotations

import json
import threading
import urllib.request

import pytest

from distributed_grep_tpu.utils import metrics as metrics_mod
from distributed_grep_tpu.utils.config import JobConfig

pytestmark = pytest.mark.metrics


# ------------------------------------------------------- histogram math

def test_histogram_bucket_math_and_render():
    h = metrics_mod.Histogram("dgrep_t_seconds", help="T.")
    for v in (0.002, 0.002, 0.01, 0.5, 3.0, 500.0):
        h.observe(v)
    counts, total, count = h.snapshot()
    assert count == 6 and total == pytest.approx(503.514)
    # raw (non-cumulative) landings: 0.002x2 -> le=0.004, 0.01 -> 0.016,
    # 0.5 -> 1.024, 3.0 -> 4.096, 500 -> +Inf
    by_edge = dict(zip(h.buckets, counts))
    assert by_edge[0.004] == 2 and by_edge[0.016] == 1
    assert by_edge[1.024] == 1 and by_edge[4.096] == 1
    assert counts[-1] == 1  # +Inf
    lines = h.render()
    # cumulative exposition contract + the exact terminal lines
    assert 'dgrep_t_seconds_bucket{le="0.004"} 2' in lines
    assert 'dgrep_t_seconds_bucket{le="0.016"} 3' in lines
    assert 'dgrep_t_seconds_bucket{le="+Inf"} 6' in lines
    assert lines[-1] == "dgrep_t_seconds_count 6"


def test_histogram_quantiles():
    h = metrics_mod.Histogram("dgrep_t_seconds")
    assert h.quantile(0.5) is None  # empty
    for _ in range(100):
        h.observe(0.01)  # all land in (0.004, 0.016]
    q = h.quantile(0.5)
    assert 0.004 < q <= 0.016
    # observations past the last finite edge clamp to it
    h2 = metrics_mod.Histogram("dgrep_t_seconds")
    h2.observe(1e9)
    assert h2.quantile(0.99) == h2.buckets[-1]


def test_untouched_instruments_answer_lock_free():
    """The CorpusCache `_touched` convention: instruments that were never
    recorded answer reads without taking their lock (the hot disabled
    path must not serialize on process-global mutexes)."""

    class Exploding:
        def __enter__(self):
            raise AssertionError("lock taken on the untouched path")

        def __exit__(self, *a):
            return False

    c = metrics_mod.MetricCounter("dgrep_x_total")
    c._lock = Exploding()
    assert c.value() == 0.0
    h = metrics_mod.Histogram("dgrep_x_seconds")
    h._lock = Exploding()
    assert h.snapshot()[2] == 0 and h.quantile(0.5) is None


# -------------------------------------------------- exposition (golden)

_GOLDEN_SERIES = {
    "dgrep_g": ("gauge", "A gauge."),
    "dgrep_h_seconds": ("histogram", "A histogram."),
    "dgrep_n_total": ("counter", "A counter."),
}

_GOLDEN = """\
# HELP dgrep_g A gauge.
# TYPE dgrep_g gauge
dgrep_g 2.5
# HELP dgrep_h_seconds A histogram.
# TYPE dgrep_h_seconds histogram
dgrep_h_seconds_bucket{le="0.001"} 0
dgrep_h_seconds_bucket{le="0.004"} 1
dgrep_h_seconds_bucket{le="0.016"} 1
dgrep_h_seconds_bucket{le="0.064"} 1
dgrep_h_seconds_bucket{le="0.256"} 1
dgrep_h_seconds_bucket{le="1.024"} 2
dgrep_h_seconds_bucket{le="4.096"} 2
dgrep_h_seconds_bucket{le="16.384"} 2
dgrep_h_seconds_bucket{le="65.536"} 2
dgrep_h_seconds_bucket{le="262.144"} 2
dgrep_h_seconds_bucket{le="+Inf"} 2
dgrep_h_seconds_sum 1.002
dgrep_h_seconds_count 2
# HELP dgrep_n_total A counter.
# TYPE dgrep_n_total counter
dgrep_n_total 3
"""


def test_prometheus_exposition_golden_and_byte_stable():
    reg = metrics_mod.MetricsRegistry(series=_GOLDEN_SERIES)
    reg.counter("dgrep_n_total").inc(3)
    reg.gauge("dgrep_g").set(2.5)
    h = reg.histogram("dgrep_h_seconds")
    h.observe(0.002)
    h.observe(1.0)
    first = reg.render()
    assert first == _GOLDEN
    assert reg.render() == first  # byte-stable


def test_registry_kind_mismatch_raises():
    reg = metrics_mod.MetricsRegistry(series=_GOLDEN_SERIES)
    reg.counter("dgrep_n_total")
    with pytest.raises(ValueError):
        reg.gauge("dgrep_n_total")
    with pytest.raises(ValueError):
        reg.histogram("dgrep_g")  # declared gauge


def test_reset_zeroes_in_place():
    """Module-level instrument references must survive a reset — the
    conftest isolation fixture zeroes values, never detaches them."""
    reg = metrics_mod.MetricsRegistry(series=_GOLDEN_SERIES)
    c = reg.counter("dgrep_n_total")
    c.inc(7)
    reg.reset()
    assert c.value() == 0.0
    c.inc(1)  # the SAME object still feeds the registry
    assert "dgrep_n_total 1" in reg.render()


def test_instrument_concurrency_stress():
    c = metrics_mod.MetricCounter("dgrep_s_total")
    h = metrics_mod.Histogram("dgrep_s_seconds")

    def work():
        for _ in range(2000):
            c.inc()
            h.observe(0.01)

    threads = [threading.Thread(target=work) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value() == 16000
    assert h.snapshot()[2] == 16000


# ------------------------------------------------- rolling-window rates

def test_rate_window_expiry():
    w = metrics_mod.RateWindow(window_s=100.0, granularity_s=10.0)
    w.add("hits", 5.0, now=0.0)
    w.add("hits", 3.0, now=50.0)
    assert w.total("hits", now=60.0) == 8.0
    assert w.total("hits", now=105.0) == 3.0  # first bucket aged out
    assert w.total("hits", now=500.0) == 0.0


def test_delta_tracker_baseline_and_deltas():
    t = metrics_mod.CounterDeltaTracker(("hits",), window_s=1000.0)
    t.observe("p", {"hits": 10}, now=0.0)  # first report = baseline
    assert t.window_totals(now=1.0)["hits"] == 0.0
    t.observe("p", {"hits": 16}, now=2.0)
    assert t.window_totals(now=3.0)["hits"] == 6.0
    # a LOWER reading is a stale/out-of-order snapshot (same-token
    # sources are same-process): ignored, baseline stays the running
    # max — lowering it would re-count the gap on the next report
    t.observe("p", {"hits": 2}, now=4.0)
    assert t.window_totals(now=5.0)["hits"] == 6.0
    t.observe("p", {"hits": 14}, now=6.0)  # still below the max: ignored
    assert t.window_totals(now=7.0)["hits"] == 6.0
    t.observe("p", {"hits": 20}, now=8.0)  # past the max: +4 only
    assert t.window_totals(now=9.0)["hits"] == 10.0


def test_delta_tracker_reconnect_same_process_no_double_count():
    """The satellite audit: a worker that reconnects after a daemon
    restart keeps its process-lifetime counters but gets a FRESH
    service-allocated id.  Keyed by the per-process token, the totals
    continue exactly; keyed by id (the no-token fallback), the new id
    re-baselines — either way nothing is double-counted or regressed."""
    t = metrics_mod.CounterDeltaTracker(("hits",), window_s=1000.0)
    # same process token reports under worker id 1, then id 7
    t.observe(42.0, {"hits": 10}, now=0.0)
    t.observe(42.0, {"hits": 15}, now=1.0)   # +5
    t.observe(42.0, {"hits": 18}, now=2.0)   # +3, now under a new id —
    # the SOURCE is the token, so the id change is invisible
    assert t.window_totals(now=3.0)["hits"] == 8.0
    # no-token fallback: id keys.  The reconnected id's first report
    # (full lifetime total 20) must BASELINE, not add 20
    t2 = metrics_mod.CounterDeltaTracker(("hits",), window_s=1000.0)
    t2.observe(1.0, {"hits": 10}, now=0.0)
    t2.observe(1.0, {"hits": 14}, now=1.0)   # +4
    t2.observe(7.0, {"hits": 20}, now=2.0)   # reconnect, fresh id
    assert t2.window_totals(now=3.0)["hits"] == 4.0


def test_service_worker_seen_feeds_rates_and_strips_proc(tmp_path):
    from distributed_grep_tpu.runtime.service import GrepService

    svc = GrepService(work_root=tmp_path / "root")
    try:
        svc._worker_seen(1, metrics={"proc": 42.0, "compile_cache_hits": 10})
        svc._worker_seen(1, metrics={"proc": 42.0, "compile_cache_hits": 15})
        # daemon reallocated the id; same process keeps reporting
        svc._worker_seen(7, metrics={"proc": 42.0, "compile_cache_hits": 18})
        totals = svc._cache_rates.window_totals()
        assert totals["compile_cache_hits"] == 8.0
        # the token is consumed, never stored into the /status rows
        st = svc.status()
        for row in st["workers"].values():
            assert "proc" not in (row.get("metrics") or {})
    finally:
        svc.stop()


def test_env_metrics_window_parser(monkeypatch):
    monkeypatch.delenv("DGREP_METRICS_WINDOW_S", raising=False)
    assert metrics_mod.env_metrics_window_s() == 300.0
    monkeypatch.setenv("DGREP_METRICS_WINDOW_S", "60")
    assert metrics_mod.env_metrics_window_s() == 60.0
    monkeypatch.setenv("DGREP_METRICS_WINDOW_S", "bogus")
    assert metrics_mod.env_metrics_window_s() == 300.0
    monkeypatch.setenv("DGREP_METRICS_WINDOW_S", "-5")
    assert metrics_mod.env_metrics_window_s() == 300.0


# ----------------------------------------- disabled-path no-op pinning

def test_spans_off_payloads_and_status_unchanged(tmp_path):
    """Metrics tier off the wire: spans-off workers piggyback nothing new
    (no 'proc' key can reach the wire), and a daemon that recorded
    nothing keeps the exact pre-metrics /status shape (no 'latency')."""
    from distributed_grep_tpu.runtime import rpc
    from distributed_grep_tpu.runtime.service import GrepService
    from distributed_grep_tpu.runtime.worker import WorkerLoop

    loop = WorkerLoop(transport=object(), app=None, spans_enabled=False)
    args = loop._finished_args(rpc.TaskFinishedArgs(task_id=0))
    assert args.metrics is None
    assert set(rpc.to_dict(args)) == {"task_id", "produced_parts"}
    # spans ON: the proc token rides INSIDE the metrics dict (no new
    # rpc field) and is stripped before any /status row stores it
    loop2 = WorkerLoop(transport=object(), app=None, spans_enabled=True)
    args2 = loop2._finished_args(rpc.TaskFinishedArgs(task_id=0))
    assert args2.metrics["proc"] == metrics_mod.PROC_TOKEN

    svc = GrepService(work_root=tmp_path / "root")
    try:
        st = svc.status()
        assert "latency" not in st
    finally:
        svc.stop()


def test_scheduler_worker_seen_strips_proc():
    from distributed_grep_tpu.runtime.scheduler import Scheduler

    s = Scheduler(files=[], n_reduce=0)
    s._worker_seen(0, metrics={"proc": 1.0, "bytes_scanned": 5})
    assert s.worker_status()["0"]["metrics"] == {"bytes_scanned": 5}
    s.stop()


# -------------------------------------------------- /metrics over HTTP

def _http_get(port: int, path: str):
    req = urllib.request.urlopen(
        f"http://127.0.0.1:{port}{path}", timeout=10
    )
    return req, req.read()


@pytest.mark.service
def test_service_metrics_endpoint_and_latency(tmp_path):
    from distributed_grep_tpu.runtime.service import GrepService, ServiceServer

    corpus = tmp_path / "corpus"
    corpus.mkdir()
    for i in range(3):
        (corpus / f"f{i}.txt").write_text("needle\nhay\n" * 20)
    svc = GrepService(work_root=tmp_path / "root")
    server = ServiceServer(svc)
    server.start()
    try:
        svc.start_local_workers(1)
        resp, body = _http_get(server.port, "/metrics")
        assert resp.status == 200
        assert resp.headers["Content-Type"].startswith("text/plain")
        text = body.decode("utf-8", "strict")
        assert "# TYPE dgrep_queue_wait_seconds histogram" in text
        assert "# TYPE dgrep_queue_depth gauge" in text
        assert "dgrep_jobs_done_total 0" in text
        # idle daemon: consecutive scrapes are byte-identical
        assert _http_get(server.port, "/metrics")[1] == body

        cfg = JobConfig(
            input_files=[str(p) for p in sorted(corpus.iterdir())],
            application="distributed_grep_tpu.apps.grep_tpu",
            app_options={"pattern": "needle", "backend": "cpu"},
            n_reduce=2,
        )
        jid = svc.submit(cfg)
        assert svc.wait_job(jid, timeout=60)
        text = _http_get(server.port, "/metrics")[1].decode("utf-8",
                                                            "strict")
        assert "dgrep_jobs_done_total 1" in text
        assert "dgrep_queue_wait_seconds_count 1" in text
        assert "dgrep_job_e2e_seconds_count 1" in text
        # /status gains the compact latency summary once data exists
        st = svc.status()
        assert st["latency"]["queue_wait_s"]["count"] == 1
        assert st["latency"]["job_e2e_s"]["p95"] >= (
            st["latency"]["job_e2e_s"]["p50"]
        )
    finally:
        server.shutdown()
        svc.stop()


def test_coordinator_metrics_endpoint(tmp_path):
    from distributed_grep_tpu.runtime.http_coordinator import CoordinatorServer

    p = tmp_path / "in.txt"
    p.write_text("needle\n")
    cfg = JobConfig(
        input_files=[str(p)], work_dir=str(tmp_path / "w"),
        application="distributed_grep_tpu.apps.grep",
        app_options={"pattern": "needle"}, n_reduce=1, coordinator_port=0,
    )
    server = CoordinatorServer(cfg)
    server.start()
    try:
        resp, body = _http_get(server.port, "/metrics")
        assert resp.status == 200
        text = body.decode("utf-8", "strict")
        assert "# TYPE dgrep_assign_poll_seconds histogram" in text
        assert "# TYPE dgrep_map_phase_seconds histogram" in text
    finally:
        server.scheduler.stop()
        server._httpd.shutdown()
        server._httpd.server_close()


# ----------------------------------------------------- explain reports

def test_summarize_events_unit():
    from distributed_grep_tpu.runtime import explain as explain_mod

    events = [
        {"t": "span", "name": "scan:fdr", "dur": 0.5,
         "args": {"bytes": 100, "matches": 3, "kernel": "pallas",
                  "interpret": False}},
        {"t": "span", "name": "scan:re", "dur": 0.1,
         "args": {"bytes": 10, "matches": 1, "kernel": "host",
                  "interpret": False}},
        {"t": "span", "name": "map:read", "dur": 0.2, "args": {}},
        {"t": "instant", "name": "cache:hit"},
        {"t": "instant", "name": "cache:hit"},
        {"t": "instant", "name": "corpus:miss"},
        {"t": "instant", "name": "index:prune", "args": {"bytes": 64}},
        {"t": "instant", "name": "fuse:plan", "args": {"queries": 3}},
        {"t": "instant", "name": "assign_map"},
        {"t": "instant", "name": "task_timeout"},
        {"t": "worker_clock", "worker": 0, "offset_s": 0.1},  # skipped
    ]
    agg = explain_mod.summarize_events(events)
    assert agg["modes"]["fdr"] == {
        "scans": 1, "bytes": 100, "seconds": 0.5, "matches": 3,
        "compiles": 0, "self_seconds": 0.5,
        "kernels": {"pallas": 1}, "kernel_bytes": {"pallas": 100}}
    assert agg["model_cache"]["hits"] == 2
    assert agg["corpus_cache"]["misses"] == 1
    assert agg["index"] == {"prunes": 1, "bytes_skipped": 64, "maybes": 0}
    assert agg["fusion"]["fused_plans"] == 1
    assert agg["fusion"]["max_queries"] == 3
    assert agg["stages"]["map:read"]["count"] == 1
    assert agg["tasks"]["map_assigns"] == 1
    assert agg["tasks"]["timeouts"] == 1
    # route verdict: host+device modes mixed
    assert explain_mod._route_verdict(agg["modes"]) == "mixed"
    assert explain_mod._route_verdict({"native": {"scans": 1}}) == "host"
    assert explain_mod._route_verdict({"fdr": {"scans": 1}}) == "device"
    # a device-mode engine that host-scanned a small input reads host
    assert explain_mod._route_verdict(
        {"fdr": {"scans": 2, "kernels": {"host": 1, "pallas": 1}}}) == "mixed"
    assert explain_mod._route_verdict({}) == "unknown"
    # scan:batch rows are envelopes (the inner engine span carries the
    # real mode): a pure-device batched job must read "device", not
    # "mixed", and batch-only evidence is "unknown"
    assert explain_mod._route_verdict(
        {"batch": {"scans": 2}, "shift_and": {"scans": 2}}) == "device"
    assert explain_mod._route_verdict({"batch": {"scans": 2}}) == "unknown"


@pytest.mark.service
def test_explain_e2e_index_pruned_cache_warm(tmp_path, capsys, monkeypatch):
    """Acceptance e2e: a real service job that was index-pruned and
    model-cache-warm; `dgrep explain` reports the kernel family, the
    host/device route, the prune, and the cache hits — and the /metrics
    rolling-window gauges move.  Result tier OFF: an identical resubmit
    would otherwise answer wholly from the round-20 result cache — no
    scan, nothing for this scan-path report to pin (that route has its
    own pins in tests/test_result_cache.py)."""
    from distributed_grep_tpu.__main__ import main
    from distributed_grep_tpu.runtime.service import GrepService, ServiceServer

    monkeypatch.setenv("DGREP_RESULT_CACHE", "0")
    corpus = tmp_path / "corpus"
    corpus.mkdir()
    files = []
    for i in range(4):
        p = corpus / f"f{i}.txt"
        text = "zebraquagga hit\n" if i == 0 else "plain line\n"
        p.write_text(text * 40)
        files.append(str(p))
    svc = GrepService(work_root=tmp_path / "root", spans=True)
    server = ServiceServer(svc)
    server.start()
    try:
        svc.start_local_workers(2)

        def submit(pattern: str) -> str:
            cfg = JobConfig(
                input_files=files,
                application="distributed_grep_tpu.apps.grep_tpu",
                app_options={"pattern": pattern, "backend": "cpu"},
                n_reduce=2, spans=True,
            )
            jid = svc.submit(cfg)
            assert svc.wait_job(jid, timeout=60)
            return jid

        submit("zebraquagga")   # cold: builds summaries + model
        submit("plain line")    # different model (A/B: defeats the
        # app-level same-config short-circuit on the next submit)
        jid = submit("zebraquagga")  # warm: model-cache hit, pruned plan

        doc = svc.job_explain(jid)
        assert doc["spans"] is True and doc["state"] == "done"
        assert doc["query"]["pattern"] == "zebraquagga"
        assert doc["routing"]["route"] == "host"  # cpu backend
        assert "native" in doc["routing"]["engine_modes"]
        idx = doc["routing"]["index"]
        assert idx["planner_shards_pruned"] == 3
        assert idx["planner_bytes_skipped"] > 0
        assert doc["routing"]["model_cache"]["hits"] >= 1
        assert doc["tasks"]["map_commits"] == 1  # pruned to one shard
        assert doc["timing"]["e2e_s"] > 0

        # rolling-window rates saw the warm hit
        text = svc.metrics_text()
        hits = [ln for ln in text.splitlines()
                if ln.startswith("dgrep_window_model_cache_hits ")]
        assert hits and float(hits[0].split()[1]) >= 1
        pruned = [ln for ln in text.splitlines()
                  if ln.startswith("dgrep_window_index_shards_pruned ")]
        assert pruned and float(pruned[0].split()[1]) >= 1

        # the CLI renders the same report through the HTTP surface
        addr = f"127.0.0.1:{server.port}"
        assert main(["explain", "--addr", addr, jid]) == 0
        cli_doc = json.loads(capsys.readouterr().out)
        assert cli_doc["job_id"] == jid
        assert cli_doc["routing"]["index"]["planner_shards_pruned"] == 3
    finally:
        server.shutdown()
        svc.stop()


def test_explain_local_workdir(tmp_path, capsys):
    from distributed_grep_tpu.__main__ import main
    from distributed_grep_tpu.runtime.job import run_job

    p = tmp_path / "in.txt"
    p.write_text("needle\nhay\n" * 10)
    cfg = JobConfig(
        input_files=[str(p)], work_dir=str(tmp_path / "w"),
        application="distributed_grep_tpu.apps.grep_tpu",
        app_options={"pattern": "needle", "backend": "cpu"},
        n_reduce=1, spans=True,
    )
    run_job(cfg, n_workers=1)
    assert main(["explain", str(tmp_path / "w")]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["routing"]["route"] == "host"
    assert doc["tasks"]["map_commits"] == 1
    # no event log, no --addr: a clean exit-2 diagnostic
    assert main(["explain", str(tmp_path / "nowhere")]) == 2
