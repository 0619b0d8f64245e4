"""jax.profiler integration: device traces + named host/device regions.

The reference has no tracing at all — only fmt.Printf progress lines
(SURVEY.md §5; coordinator.go:45, worker.go:48 et al.).  Here tracing is
first-class and TPU-native: `job_trace` wraps a whole job in a
`jax.profiler.trace` (viewable in TensorBoard / Perfetto), and `annotate`
marks task phases (assign → data-ready → kernel → commit) as
`TraceAnnotation` regions so per-task spans line up with device activity
in the same timeline.

Everything is a no-op unless tracing is switched on — either by passing
`trace_dir` explicitly or via the DGREP_TRACE_DIR environment variable —
so the hot paths pay nothing in production.

This module covers the DEVICE side of the observability story; the
cross-process control-plane side is utils/spans.py (worker→coordinator
span shipping, events.jsonl, `dgrep trace-export`).  Both render into the
same Perfetto/TensorBoard viewers: a context-managed span
(`spans.span`, `spans.scan_span`) opens an `annotate` region over the same
interval, so the exported span rows line up with the jax.profiler device
rows when a run enables both DGREP_TRACE_DIR and the span pipeline.
"""

from __future__ import annotations

import os
from contextlib import contextmanager, nullcontext

from distributed_grep_tpu.utils.logging import get_logger

log = get_logger("trace")

_ENV_VAR = "DGREP_TRACE_DIR"


def trace_dir() -> str | None:
    """The active trace directory, or None when tracing is off."""
    return os.environ.get(_ENV_VAR) or None


def enabled() -> bool:
    return trace_dir() is not None


@contextmanager
def job_trace(out_dir: str | None = None):
    """Trace an entire job under `jax.profiler.trace(out_dir)`.

    No-op when tracing is off or jax.profiler is unavailable (e.g. a
    worker process that never touches a device).
    """
    d = out_dir or trace_dir()
    if d is None:
        yield
        return
    try:
        import jax
    except Exception:  # pragma: no cover - jax is a hard dep in practice
        yield
        return
    os.makedirs(d, exist_ok=True)
    log.info("profiler trace -> %s", d)
    with jax.profiler.trace(d):
        yield


def annotate(name: str):
    """Named region visible in the profiler timeline (host + device rows).

    Returns a context manager; a nullcontext when tracing is off so callers
    can annotate unconditionally.
    """
    if not enabled():
        return nullcontext()
    try:
        import jax
    except Exception:  # pragma: no cover
        return nullcontext()
    return jax.profiler.TraceAnnotation(name)
