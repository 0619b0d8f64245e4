"""Test configuration: force JAX onto a virtual 8-device CPU mesh.

Multi-chip TPU hardware is not available in CI; the standard JAX trick is to
fake an 8-device mesh on CPU via --xla_force_host_platform_device_count and
test pjit/shard_map logic there (SURVEY.md §4).  Must run before jax import.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

import pytest


@pytest.fixture
def coordinator_port_reader():
    """Returns port_from_stderr(proc, timeout): parse a coordinator
    subprocess's bound port from its stderr via a drain thread —
    readline() in the test thread could block past any deadline, and an
    undrained pipe can stall the coordinator once its ~64 KB buffer
    fills.  Lives in conftest so it needs no cross-test-module import
    (tests/ is not a package)."""
    import queue
    import re
    import threading
    import time

    def port_from_stderr(proc, timeout: float = 15.0):
        q: "queue.Queue[str]" = queue.Queue()

        def drain():
            for line in proc.stderr:  # runs to EOF: the pipe never fills
                q.put(line)

        threading.Thread(target=drain, daemon=True).start()
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            try:
                line = q.get(timeout=0.2)
            except queue.Empty:
                continue
            m = re.search(r"serving on .*:(\d+)", line)
            if m:
                return int(m.group(1))
        return None

    return port_from_stderr


@pytest.fixture
def workdir(tmp_path):
    from distributed_grep_tpu.utils.io import WorkDir

    return WorkDir(tmp_path / "job")


@pytest.fixture
def corpus(tmp_path):
    """A small multi-file text corpus with known grep-able content."""
    files = {}
    texts = {
        "a.txt": "hello world\nthe quick brown fox\nhello again\n",
        "b.txt": "nothing here\nfox says hello\n\ntrailing line",
        "c.txt": "HELLO uppercase\nhellohello twice on one line\nlast hello",
    }
    for name, text in texts.items():
        p = tmp_path / name
        p.write_text(text)
        files[name] = p
    return files


@pytest.fixture(autouse=True)
def _lockdep_audit(request):
    """The dynamic half of the concurrency-discipline layer (round 11):
    under the `service`, `chaos`, `soak_mini`, `follow`, and `result`
    suites every lock built
    through utils/lockdep.make_lock is instrumented — per-thread
    acquisition stacks, lock-order inversion detection, blocking-syscall-
    while-held detection — and the test FAILS if the run observed either.
    This is the runtime cross-check of the static `locked-blocking` /
    `lock-order` rules: the AST proves what it can see, the harness
    watches what the threads actually did.  Other tests skip activation
    (make_lock hands out raw Locks — zero overhead; suite-wide
    DGREP_LOCKDEP=1 was tried and blew the tier-1 time budget).  Locks
    the ops modules build at IMPORT time are outside this fixture's
    reach — the env-enabled path that covers them is pinned by a
    subprocess test in tests/test_lockdep.py."""
    markers = {m.name for m in request.node.iter_markers()}
    if not markers & {"service", "chaos", "soak_mini", "follow", "result"}:
        yield
        return
    from distributed_grep_tpu.utils import lockdep

    lockdep.activate()
    lockdep.reset()
    try:
        yield
    finally:
        report = lockdep.report()
        lockdep.deactivate()
        lockdep.reset()
    assert not report["inversions"], (
        "lockdep observed a lock-order inversion:\n"
        + "\n".join(str(i) for i in report["inversions"])
    )
    assert not report["blocking"], (
        "lockdep observed a blocking syscall while holding a lock:\n"
        + "\n".join(str(b) for b in report["blocking"])
    )


@pytest.fixture(autouse=True)
def _event_vocab_audit(request):
    """The dynamic half of the event-vocabulary contract (analyze rule
    `event-registry`): under the `service`/`obs`/`follow`/`fuse`/
    `result`/`chaos` suites every span/instant/daemon-event name emitted
    through SpanBuffer/EventLog/DaemonLog is validated against
    analysis/events.py EVENTS and the test FAILS on an undeclared name or
    a kind mismatch — catching dynamically-built names the static AST
    rule cannot resolve (helper pass-throughs, f-string members outside
    the declared family).  Other tests skip activation: the hooks cost
    one module-global bool read when off."""
    markers = {m.name for m in request.node.iter_markers()}
    if not markers & {"service", "obs", "follow", "fuse", "result",
                      "chaos"}:
        yield
        return
    from distributed_grep_tpu.utils import event_audit

    event_audit.activate()
    event_audit.reset()
    try:
        yield
    finally:
        found = event_audit.findings()
        event_audit.deactivate()
        event_audit.reset()
    assert not found, (
        "event audit observed names outside the analysis/events.py "
        "registry:\n" + "\n".join(found)
    )


@pytest.fixture(autouse=True)
def _fresh_model_cache():
    """The cross-job compiled-model cache (ops/engine.cached_engine) is
    process-global by design — in production a service process WANTS
    engines shared across jobs.  Across tests that sharing would leak
    mutated engine state (forced _accel_cached) from one test's engine
    into another's, so each test starts and ends with an empty cache."""
    from distributed_grep_tpu.ops import engine as _eng

    _eng.model_cache_clear()
    yield
    _eng.model_cache_clear()


@pytest.fixture(autouse=True)
def _fresh_fusion_counters():
    """The scan-fusion telemetry counters (ops/fuse.py) are
    process-global like the cache counters — zero them per test so one
    test's fused scans never satisfy another's counter assertions."""
    from distributed_grep_tpu.ops import fuse as _fuse

    _fuse.fusion_counters_clear()
    yield
    _fuse.fusion_counters_clear()


@pytest.fixture(autouse=True)
def _fresh_index():
    """The shard-index summary cache, attached store, and telemetry
    counters (distributed_grep_tpu/index) are process-global like the
    corpus cache — cleared per test so one test's summaries (or its
    attached persistence dir) never prune or pollute another's scans."""
    from distributed_grep_tpu.index import summary as _idx

    _idx.clear()
    yield
    _idx.clear()


@pytest.fixture(autouse=True)
def _fresh_follow():
    """The streaming-tier counters (runtime/follow.py) are process-global
    like the fusion counters — zero them per test (sys.modules-gated so
    tests that never touch the tier never import it)."""
    import sys as _sys

    fol = _sys.modules.get("distributed_grep_tpu.runtime.follow")
    if fol is not None:
        fol.follow_counters_clear()
        fol.follow_fused_counters_clear()
    yield
    fol = _sys.modules.get("distributed_grep_tpu.runtime.follow")
    if fol is not None:
        fol.follow_counters_clear()
        fol.follow_fused_counters_clear()


@pytest.fixture(autouse=True)
def _fresh_metrics():
    """The typed-instrument registry (utils/metrics.py round 15) is
    process-global by design — a daemon's /metrics aggregates across its
    whole life.  Across tests that would leak one test's latency
    histograms into another's /status "latency" summary and /metrics
    golden checks, so every instrument is zeroed IN PLACE per test
    (module-level instrument references stay valid)."""
    from distributed_grep_tpu.utils import metrics as _metrics

    _metrics.metrics_reset()
    yield
    _metrics.metrics_reset()


@pytest.fixture(autouse=True)
def _fresh_corpus_cache():
    """The device corpus cache (ops/layout.CorpusCache) is process-global
    by design — the service process WANTS shards shared across jobs.
    Across tests that sharing would serve one test's resident device
    arrays (and host bytes) to another scanning a same-named tmp file,
    so each test starts and ends with an empty cache."""
    from distributed_grep_tpu.ops import layout as _lay

    _lay.corpus_cache_clear()
    yield
    _lay.corpus_cache_clear()


def expand_records(records):
    """Flatten map output to per-record KeyValues: the built-in grep apps
    emit columnar LineBatch objects (round 5, runtime/columnar.py); tests
    asserting record shapes expand them through the semantic equivalence
    (LineBatch.to_keyvalues)."""
    from distributed_grep_tpu.runtime.columnar import LineBatch

    out = []
    for r in records:
        if isinstance(r, LineBatch):
            out.extend(r.to_keyvalues())
        else:
            out.append(r)
    return out
