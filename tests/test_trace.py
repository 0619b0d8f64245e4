"""utils/trace: jax.profiler integration (SURVEY.md §5 tracing subsystem)."""

import contextlib

from distributed_grep_tpu.utils import trace


def test_disabled_by_default(monkeypatch):
    monkeypatch.delenv("DGREP_TRACE_DIR", raising=False)
    assert not trace.enabled()
    # annotate must be a cheap nullcontext when off
    assert isinstance(trace.annotate("x"), contextlib.nullcontext)
    with trace.job_trace():
        pass


def test_job_trace_writes_profile(tmp_path, monkeypatch):
    d = tmp_path / "trace"
    monkeypatch.setenv("DGREP_TRACE_DIR", str(d))
    assert trace.enabled() and trace.trace_dir() == str(d)

    import jax.numpy as jnp

    with trace.job_trace():
        with trace.annotate("compute"):
            jnp.arange(8).sum().block_until_ready()
        with trace.annotate("scan"):
            jnp.arange(8).prod().block_until_ready()

    # jax.profiler.trace writes plugins/profile/<run>/... under the dir
    assert d.exists() and any(d.rglob("*.xplane.pb"))


def test_job_runs_traced(tmp_path, monkeypatch):
    """End-to-end: a tiny job under tracing produces identical output."""
    from distributed_grep_tpu.runtime.job import run_job
    from distributed_grep_tpu.utils.config import JobConfig

    (tmp_path / "in.txt").write_bytes(b"needle one\nhay\nneedle two\n")
    cfg = dict(
        input_files=[str(tmp_path / "in.txt")],
        n_reduce=2,
        work_dir=str(tmp_path / "work"),
        application="distributed_grep_tpu.apps.grep",
        app_options={"pattern": "needle"},
    )
    plain = run_job(JobConfig(**cfg), n_workers=2)

    monkeypatch.setenv("DGREP_TRACE_DIR", str(tmp_path / "trace"))
    traced = run_job(JobConfig(**cfg), n_workers=2)
    assert traced.results == plain.results
    assert (tmp_path / "trace").exists()


def test_span_and_annotation_share_one_clock(tmp_path, monkeypatch):
    """With DGREP_TRACE_DIR set a span is also a TraceAnnotation of the
    same name: in the profiler's .xplane.pb the region and the span
    record cover one interval, within 2 ms on the epoch clock."""
    import time
    import warnings

    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData

    from distributed_grep_tpu.utils import spans

    d = tmp_path / "trace"
    monkeypatch.setenv("DGREP_TRACE_DIR", str(d))
    buf = spans.SpanBuffer()
    jax.profiler.start_trace(str(d))
    try:
        with spans.task_context(buf, job="j", worker=0, task=0,
                                attempt="a", kind="map"):
            time.sleep(0.01)
            with spans.span("engine:wait", cat="engine"):
                jnp.arange(8).sum().block_until_ready()
                time.sleep(0.02)
    finally:
        jax.profiler.stop_trace()
    rec = next(r for r in buf.drain() if r["name"] == "engine:wait")
    pd = ProfileData.from_file(str(next(d.rglob("*.xplane.pb"))))
    regions = [(e.start_ns, e.duration_ns) for plane in pd.planes
               for line in plane.lines for e in line.events
               if e.name == "engine:wait"]
    assert len(regions) == 1
    start_ns, dur_ns = regions[0]
    # events are stamped in ns from the session's start, which the trace
    # records on the epoch clock (or, on some backends, from the epoch)
    if start_ns < 1e17:
        for plane in pd.planes:
            if plane.name == "Task Environment":
                with warnings.catch_warnings():
                    # jaxlib's stats binding warns that its type has no
                    # __module__; the suite makes that warning an error
                    warnings.simplefilter("ignore", DeprecationWarning)
                    start_ns += dict(plane.stats)["profile_start_time"]
    start = start_ns / 1e9
    assert abs(start - rec["ts"]) < 2e-3
    assert abs(start + dur_ns / 1e9 - (rec["ts"] + rec["dur"])) < 2e-3
