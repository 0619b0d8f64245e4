"""Worker task loop — mirrors map_reduce/worker.go:126-178.

Loop: ask for work (long-poll AssignTask); on a map assignment read the
split, run the application's map, bucketize by FNV-32a partition, commit
intermediate files atomically, notify MapFinished; on a reduce assignment
stream intermediate files one at a time via ReduceNextFile (the pipelined
shuffle — reduce starts while maps still run), sort-merge group, run the
application's reduce per distinct key, commit the output atomically, notify
ReduceFinished.

Differences from the reference, on purpose:
* clean shutdown on an explicit JOB_DONE assignment instead of dying via
  log.Fatal when the coordinator closes connections (worker.go:223);
* app options (grep pattern) arrive with the assignment and are applied via
  the application's configure hook — the plumbing the reference never built;
* a fault-injection hook table for tests (SURVEY.md §5 calls for one);
* reduce output lines are sorted by key for deterministic output (the
  reference iterates a Go map — nondeterministic order, worker.go:163-168).
"""

from __future__ import annotations

import threading
import time
from typing import Callable, Optional

from distributed_grep_tpu.apps.loader import LoadedApplication
from distributed_grep_tpu.runtime import rpc, shuffle
from distributed_grep_tpu.runtime.extsort import ExternalReducer
from distributed_grep_tpu.runtime.transport import Transport
from distributed_grep_tpu.utils import metrics as metrics_mod
from distributed_grep_tpu.utils import spans as spans_mod
from distributed_grep_tpu.utils.logging import get_logger
from distributed_grep_tpu.utils.metrics import Metrics

log = get_logger("worker")

# Typed task-wall histograms (utils/metrics.py round 15): in-process
# workers land in the daemon's /metrics; remote workers in their own
# process's registry.
_H_MAP_TASK = metrics_mod.histogram("dgrep_map_task_seconds")
_H_REDUCE_TASK = metrics_mod.histogram("dgrep_reduce_task_seconds")


class WorkerKilled(Exception):
    """Raised by fault-injection hooks to simulate a worker crash."""


class TaskAborted(Exception):
    """The coordinator fenced this attempt off (stale scheduler epoch —
    the attempt outlived a coordinator/daemon restart): abandon it with
    NO commit and NO finished RPC, then go back to polling for work."""


def _engine_cache_counters() -> dict | None:
    """This process's cross-job engine-cache counters — compiled-model
    (compile_cache_hits/misses/evictions), device-corpus
    (corpus_cache_hits/misses/evictions/bytes_resident), AND scan-fusion
    (fused_queries/fused_dispatches/fusion_bytes_saved, ops/fuse.py) —
    or None when the owning modules were never imported or none was
    touched; piggybacked with the Metrics snapshot so the coordinator
    /status workers view shows cache/fusion effectiveness per worker.
    sys.modules-gated: a wordcount worker must not import the whole ops
    stack just to report nothing."""
    import sys as _sys

    counters: dict = {}
    eng = _sys.modules.get("distributed_grep_tpu.ops.engine")
    if eng is not None:
        counters.update(eng.model_cache_counters())
    lay = _sys.modules.get("distributed_grep_tpu.ops.layout")
    if lay is not None:
        counters.update(lay.corpus_cache_counters())
    fuse = _sys.modules.get("distributed_grep_tpu.ops.fuse")
    if fuse is not None:
        counters.update(fuse.fusion_counters())
    idx = _sys.modules.get("distributed_grep_tpu.index.summary")
    if idx is not None:
        # shard-index engine-side counters (index_shards_pruned/
        # bytes_skipped/maybe_scans/summaries_built), nonzero-only
        counters.update(idx.index_counters())
    fol = _sys.modules.get("distributed_grep_tpu.runtime.follow")
    if fol is not None:
        # streaming-tier counters (follow_wakes/suffix_bytes_scanned/
        # stream_dropped_records), nonzero-only — same contract
        counters.update(fol.follow_counters())
        # fused follow tier (round 21): follow_fused_* counters
        counters.update(fol.follow_fused_counters())
    return counters or None


class WorkerLoop:
    def __init__(
        self,
        transport: Transport,
        app: LoadedApplication | None = None,
        metrics: Optional[Metrics] = None,
        fault_hooks: Optional[dict[str, Callable[[], None]]] = None,
        reduce_memory_bytes: int = 128 << 20,
        spill_dir: Optional[str] = None,
        spans_enabled: Optional[bool] = None,
        job_id: str = "",
        peer=None,
    ):
        self.transport = transport
        # Peer-to-peer shuffle (round 16, runtime/peer.py): when a
        # PeerDataServer is attached, map commits spool their output
        # LOCALLY and register metadata instead of uploading bytes to the
        # daemon; one server is shared by every slot of the process.
        # None = relay shuffle, the pre-peer data plane exactly.
        self.peer = peer
        # Elastic shrink signal (dgrep serve --max-workers): set by the
        # service's local-pool scaler; the loop exits at the next idle
        # moment (never mid-task — attach/detach safety is the round-8
        # fresh-id/quarantine machinery).
        self.drain = threading.Event()
        # ``app`` may be None for workers attached to the service daemon
        # (runtime/service.py): there every assignment names its own
        # application module (AssignTaskReply.application) and the loop
        # resolves it per task (_bind_assignment) — one fresh module
        # instance per (loop, spec), cached, so two loops never share
        # app-module state and a loop reuses its instance across jobs.
        self.app = app
        self._job_apps: dict[str, LoadedApplication] = {}
        # The SERVICE job id of the current assignment, echoed on every
        # task RPC so the daemon can dispatch to the right scheduler.
        # Stays "" on single-job coordinators: the rpc fields elide and
        # the wire payload is byte-identical to the pre-service protocol.
        self._rpc_job_id = ""
        self.metrics = metrics or Metrics()
        self.fault_hooks = fault_hooks or {}
        self.reduce_memory_bytes = reduce_memory_bytes
        # Spills must land on real disk: the system temp dir is often a
        # RAM-backed tmpfs, which would defeat the reduce memory cap.
        self.spill_dir = spill_dir
        self.worker_id = -1
        # Span pipeline (utils/spans.py): None defers to the DGREP_SPANS
        # env var; run_job/run_http_worker pass JobConfig.spans explicitly.
        # Off means NO buffer exists — every emit site no-ops and RPC
        # payloads keep their pre-span shape (rpc._ELIDE_DEFAULTS).
        if spans_enabled is None:
            spans_enabled = spans_mod.env_enabled()
        self.spans = spans_mod.SpanBuffer() if spans_enabled else None
        self.job_id = job_id
        self._hb_rtt = -1.0  # last heartbeat round trip (ClockSync feed)
        self._assign_wait_s = 0.0

    def _fault(self, point: str) -> None:
        hook = self.fault_hooks.get(point)
        if hook:
            hook()

    def _attach_rpc_retries(self, args) -> None:
        """Piggyback the transport's transient-retry count — UNGATED by
        the span pipeline (an operator debugging a flaky fleet looks for
        rpc_retries in /status precisely when spans are off) but
        nonzero-only, so the zero-retry default keeps the wire payload
        byte-identical to the pre-span protocol."""
        retries = getattr(self.transport, "retry_count", 0)
        if retries:
            if args.metrics is None:
                args.metrics = {}
            args.metrics["rpc_retries"] = retries
        # Peer-shuffle telemetry rides the same ungated-but-nonzero-only
        # contract: an operator watching a fleet drain looks for spool
        # state and fetch-failure counts in /status with spans off, and
        # the zero defaults keep peer-free payloads byte-identical.
        stats: dict[str, float] = {}
        for k in ("peer_fetches", "peer_fetch_failures", "relay_fallbacks"):
            v = self.metrics.counters.get(k, 0)
            if v:
                stats[k] = v
        if self.peer is not None:
            sb = self.peer.spool_bytes()
            if sb:
                stats["peer_spool_bytes"] = float(sb)
        if stats:
            if args.metrics is None:
                args.metrics = {}
            args.metrics.update(stats)

    # --------------------------------------------------------------- liveness
    def _hb_interval(self, window_s: float) -> float:
        """Heartbeat cadence derived from the coordinator's declared
        detector window (AssignTaskReply.task_timeout_s): ~window/3 gives
        two chances to land a stamp per window, bounded to [50 ms, 5 s]."""
        return min(5.0, max(0.05, float(window_s) / 3.0))

    def _heartbeat(self, task_type: str, task_id: int,
                   grace_s: float = 0.0, job_id: str | None = None) -> None:
        """Advisory mid-task stamp (UpdateTimestamp, coordinator.go:176-182
        — exposed by the reference but never called mid-map; here it is
        what lets the sweeper run a tight window over long maps, VERDICT
        r3 item 3).  ``job_id`` overrides the current assignment's job
        for FUSED attempts (one scan holds K jobs' tasks; every
        participant's scheduler must see stamps).  Never raises: liveness
        is best-effort, the task's own RPCs surface real transport
        failure."""
        hb = getattr(self.transport, "heartbeat", None)
        if hb is None:
            return
        args = rpc.HeartbeatArgs(
            task_type=task_type, task_id=task_id,
            job_id=self._rpc_job_id if job_id is None else job_id,
            worker_id=self.worker_id, grace_s=grace_s,
        )
        if self.spans is not None:
            # Piggyback: buffered spans flush on the stamp the worker was
            # sending anyway (a failed stamp loses this batch — telemetry
            # is best-effort by the same contract as the stamp itself);
            # sent_at + the previous round trip feed the coordinator's
            # per-worker clock-offset estimate.
            args.spans_seq, args.spans = self.spans.drain_batch()
            args.metrics = self.metrics.piggyback()
            cc = _engine_cache_counters()
            if cc:
                args.metrics.update(cc)
            # source token for the service-side rolling-rate tracker:
            # same-process loops share module-global cache counters, and
            # a reconnect gets a fresh worker id — the token (not the id)
            # is what keeps deltas counted exactly once per process
            args.metrics["proc"] = metrics_mod.PROC_TOKEN
            args.sent_at = time.time()
            args.rtt_s = self._hb_rtt
        self._attach_rpc_retries(args)
        try:
            rtt = hb(args)
            # Transports that measure return the successful POST's round
            # trip as a float (retry sleeps excluded).  Anything else —
            # None from a stamp that exhausted its attempts, or a custom
            # transport without measurement — is NOT a valid sample: keep
            # the previous value rather than poison the clock sync with
            # timeout+retry wall time (a 16 s "RTT" would skew the
            # worker's whole trace row by seconds).
            if isinstance(rtt, float):
                self._hb_rtt = rtt
        except Exception:  # noqa: BLE001 — advisory by contract
            pass

    def _progress_fn(self, task_type: str, task_id: int,
                     window_s: float = 10.0) -> Callable:
        """A throttled progress callback for the application: plain calls
        stamp at most once per _hb_interval(window); grace calls (declaring
        a silent phase, e.g. a 20-40 s cold device compile) always go
        through."""
        last = [0.0]
        min_interval = self._hb_interval(window_s)

        def progress(grace_s: float = 0.0) -> None:
            now = time.monotonic()
            if not grace_s and now - last[0] < min_interval:
                return
            last[0] = now
            self._heartbeat(task_type, task_id, grace_s=grace_s)

        return progress

    def _pumping(self, task_type: str, task_id: int, interval_s: float = 2.0):
        """Context manager: stamp heartbeats from a side thread while the
        body runs — coarse process-alive liveness.  Two call sites:
        transport downloads (on non-local transports — the HTTP data
        plane has its own 15 s liveness budget, http_transport.py, so no
        app hang can hide there; the local transport resolves in
        microseconds and skips the pump, round 5), and the map COMPUTE
        leg of apps without set_progress
        support (there it genuinely cannot distinguish a slow map from a
        hung one — the accepted tradeoff, documented at the call site,
        because the alternative is spuriously re-executing every map
        longer than the sweep window; progress-capable apps keep
        fine-grained hang detection instead)."""
        import contextlib
        import threading

        @contextlib.contextmanager
        def ctx():
            stop = threading.Event()

            def pump() -> None:
                while not stop.wait(interval_s):
                    self._heartbeat(task_type, task_id)

            t = threading.Thread(target=pump, name="hb-pump", daemon=True)
            t.start()
            try:
                yield
            finally:
                stop.set()
                t.join(timeout=interval_s + 1.0)

        return ctx()

    def run(self) -> None:
        """The infinite task loop (worker.go:126-178), with a clean exit."""
        while True:
            if self.drain.is_set():
                # elastic shrink: exit at an idle loop top, never mid-task
                log.info("worker %d: drained (elastic shrink), exiting",
                         self.worker_id)
                return
            t_wait = time.monotonic()
            args = rpc.AssignTaskArgs(worker_id=self.worker_id)
            if self.peer is not None:
                # advertise the shuffle endpoint on every poll so /status
                # shows who holds spool state before an operator drains
                args.peer_endpoint = self.peer.endpoint
            reply = self.transport.assign_task(args)
            # idle wait for work — reported as an arg on the task span
            self._assign_wait_s = time.monotonic() - t_wait
            self.worker_id = reply.worker_id
            if reply.assignment in (rpc.Assignment.MAP, rpc.Assignment.REDUCE):
                self._bind_assignment(reply)
            if self.spans is not None:
                # buffer-synthesized records (drop reports) render on this
                # worker's row now that the coordinator named it
                self.spans.base_tags.update(
                    job=self.job_id, worker=self.worker_id
                )
            if reply.assignment == rpc.Assignment.JOB_DONE:
                log.info("worker %d: job done, exiting", self.worker_id)
                return
            if reply.assignment == rpc.Assignment.MAP:
                self._run_map(reply)
            elif reply.assignment == rpc.Assignment.REDUCE:
                self._run_reduce(reply)
            elif reply.retry_after_s > 0:
                # quarantined (scheduler.WorkerHealth): the coordinator
                # hinted how long until re-probation — sleep a bounded
                # slice of it instead of re-entering the long-poll hot
                # (capped so a shrunk window server-side is noticed)
                time.sleep(min(reply.retry_after_s, 5.0))
            # anything else ("retry"): long-poll window expired — loop again

    def _bind_assignment(self, reply: rpc.AssignTaskReply) -> None:
        """Adopt a (possibly multiplexed) assignment's job identity: span
        tags + data-plane scope follow the job, and the application module
        resolves from the assignment when the daemon names one (service
        workers serve many jobs through ONE attach).  Single-job replies
        carry neither field and this is a no-op."""
        if reply.job_id:
            self._rpc_job_id = reply.job_id
            self.job_id = reply.job_id
            bind = getattr(self.transport, "bind_job", None)
            if bind is not None:
                bind(reply.job_id)
        if reply.application:
            app = self._job_apps.get(reply.application)
            if app is None:
                from distributed_grep_tpu.apps.loader import load_application

                app = load_application(reply.application)
                self._job_apps[reply.application] = app
            self.app = app
        elif self.app is None:
            raise RuntimeError(
                "worker has no application: the assignment names none and "
                "no default app was given at construction"
            )

    def _publish_commit(self, kind: str, task_id: int, attempt: str,
                        payload: dict) -> None:
        """Publish the per-task commit record (runtime/store.py) — the
        durable commit on stores without atomic rename, published after
        every blob of the task is durable and BEFORE the finished RPC, so
        the record (not the RPC, not raw file existence) is the unit of
        truth the scheduler registers from.  Transports without the hook
        (custom test transports) keep RPC-args registration."""
        publish = getattr(self.transport, "publish_task_commit", None)
        if publish is not None:
            with spans_mod.span(f"{kind}:commit", cat=kind):
                publish(kind, task_id, attempt, payload)

    def _region(self, prefix: str, task_id: int) -> str:
        """A task phase's profiler region name: ``prefix`` (what the
        benchmark's trace reduction keys on), then job and task — task
        ids alone collide across a service's tenants."""
        return f"{prefix}:{self.job_id}:{task_id}"

    def _read_span(self, a: rpc.AssignTaskReply, **args):
        return spans_mod.span("map:read", cat="map",
                              annotation=self._region("map_read", a.task_id),
                              file=a.filename, **args)

    def _compute_span(self, task_id: int, **args):
        return spans_mod.span("map:compute", cat="map",
                              annotation=self._region("map_compute", task_id),
                              **args)

    def _task_ctx(self, kind: str, task_id: int, attempt: str):
        """The span pipeline's ambient task context for one attempt — a
        nullcontext when the pipeline is off, so every emit site below
        no-ops (utils/spans.active)."""
        if self.spans is None:
            import contextlib

            return contextlib.nullcontext()
        return spans_mod.task_context(
            self.spans, job=self.job_id, worker=self.worker_id,
            task=task_id, attempt=attempt, kind=kind,
        )

    def _finished_args(self, args: rpc.TaskFinishedArgs) -> rpc.TaskFinishedArgs:
        """Attach the final span flush + metrics snapshot to a finished
        RPC — the last chance to ship this attempt's telemetry (a worker
        may exit before any further heartbeat), so unlike the heartbeat's
        FLUSH_MAX batches this drains EVERYTHING (bounded by the buffer
        cap + one drop report)."""
        if self.spans is not None:
            args.spans_seq, args.spans = self.spans.drain_batch(
                limit=self.spans.cap + 1
            )
            args.metrics = self.metrics.piggyback()
            cc = _engine_cache_counters()
            if cc:
                args.metrics.update(cc)
            args.metrics["proc"] = metrics_mod.PROC_TOKEN  # see _heartbeat
        self._attach_rpc_retries(args)
        return args

    def _read_members(self, names: list[str], want_paths: bool
                      ) -> tuple[list, int]:
        """Resolve split members to (name, bytes-or-local-path) items +
        total bytes.  ``want_paths`` hands over resolved paths on local
        data planes (the device corpus cache then serves warm windows
        with zero reads); spooled temp copies honor the (path, is_temp)
        contract — read and unlinked, never handed over as a path (a
        transient realpath must not become a corpus content key).
        Shared by the batched map branch and the fused attempt."""
        import os as _os

        items: list = []
        n_bytes = 0
        if (want_paths
                and getattr(self.transport, "is_local", False)
                and hasattr(self.transport, "read_input_path")):
            for name in names:
                p, is_temp = self.transport.read_input_path(name)
                if is_temp:
                    with open(p, "rb") as _fh:
                        data_b = _fh.read()
                    _os.unlink(p)
                    items.append((name, data_b))
                    n_bytes += len(data_b)
                else:
                    items.append((name, str(p)))
                    n_bytes += _os.path.getsize(p)
        else:
            for name in names:
                b = self.transport.read_input(name)
                items.append((name, b))
                n_bytes += len(b)
        return items, n_bytes

    # ------------------------------------------------------------------- map
    def _run_map(self, a: rpc.AssignTaskReply) -> None:
        if a.fused:
            # cross-tenant scan fusion (runtime/fusion.py): this
            # assignment carries co-tenant tasks — one scan, K commits
            self._run_map_fused(a)
            return
        from distributed_grep_tpu.runtime.store import new_attempt_id

        t0 = time.perf_counter()
        t0_wall = time.time()
        attempt = new_attempt_id()
        with self._task_ctx("map", a.task_id, attempt):
            produced, peer_meta = self._map_attempt(a, attempt, t0)
            spans_mod.complete(
                "map:task", t0_wall, time.time() - t0_wall, cat="map",
                assign_wait_s=round(self._assign_wait_s, 6),
            )
            self._fault("before_map_finished")
            finished = rpc.TaskFinishedArgs(
                task_id=a.task_id, job_id=self._rpc_job_id,
                worker_id=self.worker_id,
                produced_parts=produced,
            )
            if peer_meta is not None:
                finished.peer_endpoint = peer_meta["endpoint"]
                finished.peer_parts = peer_meta["parts"]
            self.transport.map_finished(self._finished_args(finished))
        self.metrics.inc("map_tasks")
        self.metrics.observe("map_task_total", time.perf_counter() - t0)
        _H_MAP_TASK.observe(time.perf_counter() - t0)

    def _write_map_outputs(self, task_id: int, buckets: dict
                           ) -> tuple[list[int], dict | None]:
        """Commit one map attempt's partition files and return (produced
        partitions, peer metadata or None).  Peer shuffle active (a
        PeerDataServer attached and a service job bound): the bytes land
        on THIS worker's spool — atomic rename, crc32 self-checksum —
        and only the metadata travels; otherwise the pre-peer transport
        PUT (relay) runs unchanged."""
        produced: list[int] = []
        peer_active = self.peer is not None and bool(self._rpc_job_id)
        parts_meta: dict[str, list] = {}
        for r, kvs in sorted(buckets.items()):
            data = shuffle.encode_records(kvs)
            name = f"mr-{task_id}-{r}"
            if peer_active:
                size, crc = self.peer.put(self._rpc_job_id, name, data)
                parts_meta[str(r)] = [size, crc]
            else:
                # Atomic write == the temp-file + rename commit (worker.go:103).
                self.transport.write_intermediate(name, data)
            produced.append(r)
        if not peer_active:
            return produced, None
        return produced, {
            "endpoint": self.peer.endpoint,
            "worker": self.worker_id,
            "parts": parts_meta,
        }

    def _map_attempt(self, a: rpc.AssignTaskReply, attempt: str,
                     t0: float) -> tuple[list[int], dict | None]:
        self.app.configure(**a.app_options)
        # Streaming boundary: an app exposing map_path_fn receives a local
        # file path and reads it in bounded chunks (engine.scan_file) —
        # splits larger than worker RAM flow end-to-end.  Everyone else
        # gets the reference-shaped whole-bytes map_fn (worker.go:72-76).
        use_path = getattr(self.app, "map_path_fn", None) is not None and hasattr(
            self.transport, "read_input_path"
        )
        # Mid-task liveness (VERDICT r3 item 3): the app's progress callback
        # stamps the coordinator per chunk/segment (throttled), so the
        # failure detector keeps a tight window even over maps that
        # legitimately run long; downloads are covered by the pump thread
        # (they progress against the coordinator's own data plane).  Apps
        # WITHOUT progress support (wordcount over a big split) get the
        # pump over their compute leg too: coarse liveness (process alive)
        # beats the alternative — spurious re-execution of every map
        # longer than the window, forever.  Progress-capable apps rely on
        # their own stamps there, which unlike the pump also catch
        # app-level hangs.
        has_progress = self.app.set_progress(
            self._progress_fn("map", a.task_id, a.task_timeout_s)
        )
        pump_s = min(2.0, self._hb_interval(a.task_timeout_s))
        import contextlib

        def compute_guard():
            if has_progress:
                return contextlib.nullcontext()
            return self._pumping("map", a.task_id, pump_s)

        # Download-leg pumping only matters when the data plane can
        # actually take a while (HTTP pull): a local-filesystem transport
        # resolves the path in microseconds, and a pump thread per map
        # task is measurable overhead on a 2,000-file grep -r (round 5).
        def download_guard():
            if getattr(self.transport, "is_local", False):
                return contextlib.nullcontext()
            return self._pumping("map", a.task_id, pump_s)

        try:
            if a.filenames:
                # Batched multi-file split (cross-file device batching,
                # runtime/job.plan_map_splits): every member is below the
                # small-input threshold by construction, so whole-bytes
                # reads are bounded by the batch window.  Apps exposing
                # map_batch_fn amortize the scan across members (grep_tpu
                # packs them into shared device dispatches); others get
                # map_fn per member — still one task, one commit, one
                # journal entry instead of len(members) of each.
                batch_fn = self.app.map_batch_fn
                # Local data plane + a batch fn that accepts paths
                # (map_batch_paths, grep_tpu): hand over resolved member
                # paths instead of reading them here — the engine's
                # device corpus cache (round 7) then serves a warm
                # window with ZERO file reads, and cold members cost the
                # same whole-read scan_batch would have done anyway.
                batch_paths = (
                    batch_fn is not None
                    and getattr(self.app, "map_batch_paths", False)
                )
                with download_guard(), \
                        self._read_span(a, files=len(a.filenames)):
                    blobs, n_bytes = self._read_members(
                        a.filenames, want_paths=batch_paths
                    )
                self._fault("after_map_read")
                with self.metrics.timer("map_compute"), \
                        self._compute_span(a.task_id), compute_guard():
                    if batch_fn is not None:
                        records = batch_fn(blobs)
                    else:
                        records = [
                            r for name, b in blobs
                            for r in self.app.map_fn(name, b)
                        ]
                self.metrics.record_scan(n_bytes, time.perf_counter() - t0)
            elif use_path:
                import os

                with download_guard(), self._read_span(a):
                    path, is_temp = self.transport.read_input_path(a.filename)
                try:
                    self._fault("after_map_read")
                    n_bytes = os.path.getsize(path)
                    with self.metrics.timer("map_compute"), \
                            self._compute_span(a.task_id), compute_guard():
                        records = self.app.map_path_fn(a.filename, str(path))
                finally:
                    if is_temp:
                        os.unlink(path)
                self.metrics.record_scan(n_bytes, time.perf_counter() - t0)
            else:
                with download_guard(), self._read_span(a):
                    contents = self.transport.read_input(a.filename)
                self._fault("after_map_read")
                with self.metrics.timer("map_compute"), \
                        self._compute_span(a.task_id), compute_guard():
                    records = self.app.map_fn(a.filename, contents)
                self.metrics.record_scan(len(contents), time.perf_counter() - t0)
        finally:
            if has_progress:
                self.app.set_progress(None)
        # The shuffle leg (bucketize + intermediate writes) is worker-side
        # code with no app involvement, and on a match-dense map it can
        # run past the sweep window by itself (549k records measured ~8 s
        # on this host — observed swept mid-shuffle and re-executed; the
        # round-8 native record build runs HERE too — a DeferredBatch
        # partitions from its source bytes inside bucketize, so the
        # map:shuffle span now carries the one-pass build).  The
        # coarse pump is the right liveness here, same tradeoff as the
        # download legs: a hang in OUR shuffle is a worker bug, not an
        # app hang the detector needs to catch.  Small outputs skip the
        # pump: their shuffle leg is sub-millisecond ON THE LOCAL
        # TRANSPORT (bucketize scales with records; a remote transport's
        # intermediate PUSH can stall on the network at any size, so it
        # always keeps the pump), nowhere near any sweep window — and a
        # thread per map task costs real time on many-small-file jobs
        # (round 5).
        def shuffle_guard():
            if getattr(self.transport, "is_local", False):
                from distributed_grep_tpu.runtime.columnar import LineBatch

                n_records = sum(
                    len(r) if isinstance(r, LineBatch) else 1 for r in records
                )
                if n_records < 50_000:
                    return contextlib.nullcontext()
            return self._pumping("map", a.task_id, pump_s)

        with shuffle_guard(), spans_mod.span("map:shuffle", cat="map"):
            with spans_mod.span("shuffle:build", cat="map"):
                buckets = shuffle.bucketize(records, a.n_reduce)
            self._fault("before_map_commit")
            produced, peer_meta = self._write_map_outputs(a.task_id, buckets)
        payload: dict = {"parts": produced}
        if peer_meta is not None:
            # the commit record carries the peer metadata too — it is
            # the durable copy a restarted daemon re-registers from
            payload["peer"] = peer_meta
        self._publish_commit("map", a.task_id, attempt, payload)
        return produced, peer_meta

    # ------------------------------------------------------------ fused map
    def _run_map_fused(self, a: rpc.AssignTaskReply) -> None:
        """One worker scan serving K co-tenant map tasks (cross-tenant
        scan fusion — runtime/fusion.py planned it, ops/fuse.py runs it).
        The primary assignment's split is read ONCE (the planner matched
        the participants' splits by content identity); the app's
        map_fused_fn produces each participant's records from one union
        scan; each participant then commits through ITS OWN job's data
        plane, commit record, and finished RPC — per-job exactly-once,
        journals, attempt resolution, and the epoch fence are untouched.
        Any failure in the fused leg falls back to per-participant SOLO
        execution over the already-read items (fusion is a fast path,
        never a correctness dependency); a participant whose commit leg
        fails simply times out in its own scheduler and re-runs solo."""
        from distributed_grep_tpu.runtime.store import new_attempt_id

        t0_wall = time.time()
        participants: list[dict] = [{
            "job_id": a.job_id, "task_id": a.task_id,
            "filename": a.filename, "filenames": list(a.filenames),
            "n_reduce": a.n_reduce, "app_options": a.app_options,
            "epoch": a.epoch, "task_timeout_s": a.task_timeout_s,
        }]
        participants += [dict(p) for p in a.fused]
        part_ids = [(p["job_id"], p["task_id"]) for p in participants]

        # Fused liveness: EVERY participant's scheduler must see stamps,
        # or co-tenants' sweepers would re-enqueue tasks this worker is
        # actively scanning.  The throttled callback fans one stamp out
        # to K (job, task) pairs; grace declarations pass through.  The
        # cadence derives from the TIGHTEST participant's declared
        # detector window (fusion_key does not align task_timeout_s — a
        # co-tenant with a 2 s window must not be stamped on the
        # primary's 60 s cadence and swept mid-scan).
        window_s = min(
            float(p.get("task_timeout_s", a.task_timeout_s))
            for p in participants
        )
        min_interval = self._hb_interval(window_s)
        last = [0.0]

        def progress(grace_s: float = 0.0) -> None:
            now = time.monotonic()
            if not grace_s and now - last[0] < min_interval:
                return
            last[0] = now
            for jid_p, tid_p in part_ids:
                self._heartbeat("map", tid_p, grace_s=grace_s, job_id=jid_p)

        import contextlib
        import threading

        def fused_pump(force: bool = False):
            """Coarse liveness over legs with no app progress (download,
            shuffle/commit) — the solo path's download_guard/
            shuffle_guard, fanned out to every participant's (job, task)
            so no co-tenant's sweeper fires mid-leg.  Local transports
            skip it like the solo guards do (reads/writes resolve in
            microseconds there) unless ``force`` (match-dense local
            shuffle legs can outrun the sweep window by themselves —
            the solo shuffle_guard's 50k-record rule)."""
            if not force and getattr(self.transport, "is_local", False):
                return contextlib.nullcontext()

            @contextlib.contextmanager
            def ctx():
                stop = threading.Event()
                interval = min(2.0, min_interval)

                def pump() -> None:
                    while not stop.wait(interval):
                        for jid_p, tid_p in part_ids:
                            self._heartbeat("map", tid_p, job_id=jid_p)

                t = threading.Thread(target=pump, name="fused-hb-pump",
                                     daemon=True)
                t.start()
                try:
                    yield
                finally:
                    stop.set()
                    t.join(timeout=interval + 1.0)

            return ctx()

        names = list(a.filenames) or [a.filename]
        want_paths = bool(getattr(self.app, "map_batch_paths", False))
        attempt0 = new_attempt_id()
        committed = 0
        t0 = time.perf_counter()  # attempt start, like _run_map: the
        # record_scan/map_task_total telemetry must include the read leg
        # or fused gbps reads systematically higher than solo's
        with self._task_ctx("map", a.task_id, attempt0):
            with fused_pump(), self._read_span(a, files=len(names)):
                items, n_bytes = self._read_members(names, want_paths)
            self._fault("after_map_read")
            has_progress = self.app.set_progress(progress)
            records_per: list | None = None
            try:
                if self.app.map_fused_fn is not None:
                    with self.metrics.timer("map_compute"), \
                            self._compute_span(a.task_id,
                                               fused=len(participants)):
                        records_per = self.app.map_fused_fn(
                            items, participants
                        )
            except Exception:  # noqa: BLE001 — fusion is a fast path only
                log.exception(
                    "fused map attempt failed (%d queries); falling back "
                    "to solo per-participant execution", len(participants),
                )
                records_per = None
            finally:
                if has_progress:
                    self.app.set_progress(None)
            self.metrics.record_scan(n_bytes, time.perf_counter() - t0)

            def dense_records() -> bool:
                # the solo shuffle_guard's 50k-record rule, summed over
                # participants: a local match-dense commit loop can
                # outrun the sweep window with no RPC activity
                if records_per is None:
                    return False
                from distributed_grep_tpu.runtime.columnar import LineBatch

                n = sum(
                    len(r) if isinstance(r, LineBatch) else 1
                    for recs in records_per for r in recs
                )
                return n >= 50_000

            with fused_pump(force=dense_records()):
                for k, part in enumerate(participants):
                    try:
                        if records_per is not None:
                            records = records_per[k]
                        else:
                            records = self._solo_participant_records(
                                part, items, progress
                            )
                        self._commit_fused_participant(
                            part, records,
                            attempt0 if k == 0 else new_attempt_id(),
                            n_queries=len(participants),
                        )
                        committed += 1
                    except WorkerKilled:
                        raise  # fault injection: die like a real crash
                    except Exception:  # noqa: BLE001 — tenant re-runs solo
                        log.exception(
                            "fused participant %s task %d failed; its "
                            "scheduler will re-issue it",
                            part["job_id"], part["task_id"],
                        )
                    progress()  # stamp the still-pending participants
            spans_mod.complete(
                "map:task", t0_wall, time.time() - t0_wall, cat="map",
                assign_wait_s=round(self._assign_wait_s, 6),
                fused=len(participants),
            )
        self.metrics.inc("fused_map_attempts")
        self.metrics.observe("map_task_total", time.perf_counter() - t0)
        _H_MAP_TASK.observe(time.perf_counter() - t0)
        log.info(
            "fused map attempt served %d/%d co-tenant tasks (%s:%d + %d)",
            committed, len(participants), a.job_id, a.task_id,
            len(a.fused),
        )

    def _solo_participant_records(self, part: dict, items: list,
                                  progress) -> list:
        """The fused attempt's fallback: run ONE participant's ordinary
        map over the already-read items (its own configure + batch/plain
        map), exactly what a solo attempt of its task would compute."""
        self.app.configure(**part["app_options"])
        p_items = self._participant_items(items, part)
        has_progress = self.app.set_progress(progress)
        try:
            if self.app.map_batch_fn is not None:
                return self.app.map_batch_fn(p_items)
            out = []
            for name, data in p_items:
                if not isinstance(data, (bytes, bytearray, memoryview)):
                    with open(data, "rb") as f:
                        data = f.read()
                out.extend(self.app.map_fn(name, bytes(data)))
            return out
        finally:
            if has_progress:
                self.app.set_progress(None)

    @staticmethod
    def _participant_items(items: list, part: dict) -> list:
        """Re-label shared split items with THIS participant's member
        names (two tenants may address the same content through
        different paths — symlinks/hardlinks; record keys must carry
        each job's own names)."""
        p_names = list(part.get("filenames") or []) or [part.get("filename")]
        if len(p_names) != len(items):
            # fail safe, never key this tenant's records by the shared
            # split's (primary) names: the raise fails THIS participant's
            # fallback, its own scheduler re-issues the task solo
            raise RuntimeError(
                f"fused participant {part.get('job_id')!r} has "
                f"{len(p_names)} member names for a {len(items)}-item split"
            )
        return [(p_names[i], data) for i, (_nm, data) in enumerate(items)]

    def _commit_fused_participant(self, part: dict, records: list,
                                  attempt: str, n_queries: int) -> None:
        """One participant's commit leg: bind ITS job's data plane,
        bucketize with ITS n_reduce, write intermediates under ITS task
        id, publish ITS commit record, send ITS finished RPC — the exact
        solo-map commit protocol, replayed per tenant."""
        import contextlib

        jid, tid = part["job_id"], part["task_id"]
        self._rpc_job_id = jid
        self.job_id = jid
        bind = getattr(self.transport, "bind_job", None)
        if bind is not None:
            bind(jid)
        if self.spans is not None:
            # explicit job tag: split_by_job routes this record into the
            # PARTICIPANT's events.jsonl, not the primary's
            self.spans.add({
                "t": "instant", "name": "fuse:split", "cat": "fuse",
                "ts": time.time(), "job": jid, "worker": self.worker_id,
                "args": {"task": tid, "queries": n_queries},
            })
        # the commit leg's spans (map:shuffle, map:commit) carry THIS
        # participant's job/task tags — under the primary's ambient
        # context they would all route into the primary's events.jsonl
        # and its trace row would show K shuffle legs
        ctx = (
            spans_mod.task_context(
                self.spans, job=jid, worker=self.worker_id, task=tid,
                attempt=attempt, kind="map",
            )
            if self.spans is not None else contextlib.nullcontext()
        )
        with ctx:
            with spans_mod.span("map:shuffle", cat="map"):
                with spans_mod.span("shuffle:build", cat="map"):
                    buckets = shuffle.bucketize(records, part["n_reduce"])
                self._fault("before_map_commit")
                produced, peer_meta = self._write_map_outputs(tid, buckets)
            payload: dict = {"parts": produced}
            if peer_meta is not None:
                payload["peer"] = peer_meta
            self._publish_commit("map", tid, attempt, payload)
            self._fault("before_map_finished")
            finished = rpc.TaskFinishedArgs(
                task_id=tid, job_id=jid, worker_id=self.worker_id,
                produced_parts=produced,
            )
            if peer_meta is not None:
                finished.peer_endpoint = peer_meta["endpoint"]
                finished.peer_parts = peer_meta["parts"]
            self.transport.map_finished(self._finished_args(finished))
        self.metrics.inc("map_tasks")

    # ---------------------------------------------------------------- reduce
    def _run_reduce(self, a: rpc.AssignTaskReply) -> None:
        from distributed_grep_tpu.runtime.store import new_attempt_id

        t0 = time.perf_counter()
        t0_wall = time.time()
        attempt = new_attempt_id()
        with self._task_ctx("reduce", a.task_id, attempt):
            try:
                self._reduce_attempt(a, attempt)
            except TaskAborted:
                # fenced off by a newer scheduler incarnation: this
                # attempt's shuffle cursor is meaningless there — walk
                # away (the re-issued attempt owns the commit) and poll
                # for fresh work
                log.warning("reduce task %d attempt abandoned: stale "
                            "scheduler epoch", a.task_id)
                self.metrics.inc("reduce_aborted")
                return
            spans_mod.complete(
                "reduce:task", t0_wall, time.time() - t0_wall, cat="reduce",
                assign_wait_s=round(self._assign_wait_s, 6),
            )
            self.transport.reduce_finished(self._finished_args(
                rpc.TaskFinishedArgs(
                    task_id=a.task_id, job_id=self._rpc_job_id,
                    worker_id=self.worker_id,
                )
            ))
        self.metrics.inc("reduce_tasks")
        self.metrics.observe("reduce_task_total", time.perf_counter() - t0)
        _H_REDUCE_TASK.observe(time.perf_counter() - t0)

    def _reduce_attempt(self, a: rpc.AssignTaskReply, attempt: str) -> None:
        import os

        self.app.configure(**a.app_options)
        if self.spill_dir:
            os.makedirs(self.spill_dir, exist_ok=True)
        # Two record sinks behind one loop:
        # * generic apps — bounded-memory sort-merge grouping: records
        #   spill to sorted on-disk runs past the cap and group-reduce as
        #   a streaming merge (runtime/extsort.py; the reference
        #   materializes the whole partition, worker.go:161-162).
        #   Associative apps expose reduce_stream_fn to keep hot keys
        #   O(1) too.  Output: one "key<TAB>value\n" line per key (the
        #   reference writes "key value", worker.go:111-124, but grep
        #   keys contain spaces — a tab keeps the k/v split unambiguous),
        #   keys in sorted order for determinism.
        # * identity-reduce apps (the grep apps — ``reduce_is_identity``
        #   on the module) — columnar batches collate in (file, line)
        #   order (runtime/columnar.IdentityCollator): records never
        #   expand to per-line Python objects, output files come out in
        #   the CLI's display order, and collation downstream is a plain
        #   merge (the reference sorts once, worker.go:161-169 — so do
        #   we).
        if getattr(self.app.module, "reduce_is_identity", False):
            from distributed_grep_tpu.runtime.columnar import IdentityCollator

            sink = IdentityCollator(
                memory_limit_bytes=self.reduce_memory_bytes,
                spill_dir=self.spill_dir,
            )
            chunks = sink.iter_output_blocks  # bytes per batch, str per KV
            progress_stride = 64  # chunks are whole batches: coarse
        else:
            sink = ExternalReducer(
                memory_limit_bytes=self.reduce_memory_bytes,
                spill_dir=self.spill_dir,
            )
            stream_fn = getattr(self.app, "reduce_stream_fn", None)

            def chunks():
                for k, v in sink.reduce(self.app.reduce_fn, stream_fn):
                    yield f"{k}\t{v}\n"

            progress_stride = 4096
        try:
            files_processed = 0
            lost = ""
            t_shuffle = time.time()
            while True:
                r = self.transport.reduce_next_file(
                    rpc.ReduceNextFileArgs(
                        task_id=a.task_id, files_processed=files_processed,
                        job_id=self._rpc_job_id, epoch=a.epoch,
                        worker_id=self.worker_id, lost_file=lost,
                    )
                )
                lost = ""
                if getattr(r, "abort", False):
                    raise TaskAborted(a.task_id)
                if r.done:
                    break
                if not r.next_file:
                    continue  # long-poll window expired; re-poll (worker.go:153-160)
                data = self._fetch_shuffle(r)
                if data is None:
                    # unfetchable peer output (producer gone / checksum
                    # mismatch / no relay copy): report it on the next
                    # poll WITHOUT advancing the cursor — the scheduler
                    # re-executes the producing map and this cursor waits
                    # for the fresh attempt
                    lost = r.next_file
                    continue
                sink.add_many(shuffle.decode_records(data))
                files_processed += 1
                self._fault("after_reduce_file")
            # the streaming shuffle leg: long-poll waits included (reduce
            # runs concurrently with maps, so much of this is pipeline wait)
            spans_mod.complete(
                "reduce:shuffle", t_shuffle, time.time() - t_shuffle,
                cat="reduce", files=files_processed,
            )
            with spans_mod.span(
                    "reduce:compute", cat="reduce",
                    annotation=self._region("reduce_compute", a.task_id)):
                self._write_reduce_output(a, chunks(), progress_stride)
        finally:
            if sink.spill_count:
                self.metrics.inc("reduce_spills", sink.spill_count)
            sink.close()
        self._publish_commit(
            "reduce", a.task_id, attempt, {"output": f"mr-out-{a.task_id}"}
        )

    def _fetch_shuffle(self, r: rpc.ReduceNextFileReply) -> bytes | None:
        """Fetch one shuffle file.  No peer metadata on the reply: the
        pre-peer relay read, byte-identical behavior (errors propagate —
        the daemon answered wrong, not a vanished peer).  Peer-held:
        fetch directly from the producer through the transport retry
        helpers, verify size + crc32, fall back to the daemon relay on
        the DECLARED failures (peer gone after bounded retries, HTTP
        error, checksum mismatch — a mixed/migrating cluster may hold a
        relay copy), and return None when both fail — the caller reports
        the file lost and the producing map re-executes."""
        name = r.next_file
        endpoint = getattr(r, "peer_endpoint", "")
        if not endpoint:
            data = self.transport.read_intermediate(name)
            if self.peer is not None:
                # relay route in a peer-shuffle deployment (a local/relay
                # co-worker produced this one) — route telemetry only;
                # peer-free runs emit nothing
                spans_mod.instant("shuffle:relay", cat="reduce", file=name)
            return data
        try:
            if self.peer is not None and endpoint == self.peer.endpoint:
                # reducer IS the producer: serve from our own spool
                data = self.peer.get_local(self._rpc_job_id, name)
            else:
                fetch = getattr(self.transport, "fetch_peer", None)
                if fetch is not None:
                    data = fetch(endpoint, self._rpc_job_id, name)
                else:
                    from distributed_grep_tpu.runtime.http_transport import (
                        fetch_peer_data,
                    )

                    data = fetch_peer_data(endpoint, self._rpc_job_id, name)
            from distributed_grep_tpu.runtime.peer import checksum

            if (r.peer_size and len(data) != r.peer_size) or (
                r.peer_checksum and checksum(data) != r.peer_checksum
            ):
                raise OSError(
                    f"peer shuffle integrity failure for {name}: got "
                    f"{len(data)} bytes, crc {checksum(data)} (expected "
                    f"{r.peer_size}, {r.peer_checksum})"
                )
            self.metrics.inc("peer_fetches")
            spans_mod.instant("shuffle:peer", cat="reduce", file=name,
                              bytes=len(data))
            return data
        except (OSError, RuntimeError) as e:
            # CoordinatorGone (retry schedule dry) is an OSError; an HTTP
            # error status surfaces as RuntimeError — the declared
            # fallback set.  Anything else (a bug) propagates.
            self.metrics.inc("peer_fetch_failures")
            log.warning("peer fetch of %s from %s failed (%s); trying the "
                        "daemon relay", name, endpoint, e)
        try:
            data = self.transport.read_intermediate(name)
        except (OSError, RuntimeError):
            # no relay copy either (the common pure-P2P case: the bytes
            # died with the producer) — lost output
            return None
        self.metrics.inc("relay_fallbacks")
        spans_mod.instant("shuffle:relay", cat="reduce", file=name,
                          fallback=True)
        return data

    def _write_reduce_output(self, a: rpc.AssignTaskReply, chunks,
                             progress_stride: int) -> None:
        """Spool the output chunks locally, then commit atomically (the
        temp-file + rename commit, worker.go:103) — output size never
        bounds on worker memory.  Throttled progress stamps keep a long
        merge alive past the sweep window (it has no RPC activity)."""
        import os
        import tempfile

        fd, spool = tempfile.mkstemp(prefix="dgrep-redout-",
                                     dir=self.spill_dir or None)
        try:
            progress = self._progress_fn("reduce", a.task_id, a.task_timeout_s)
            # Binary spool: columnar sinks yield pre-encoded bytes blocks
            # (native formatter); str chunks encode utf-8/surrogateescape —
            # exactly what the old text-mode writer did per write.
            with self.metrics.timer("reduce_compute"), \
                    os.fdopen(fd, "wb") as out:
                for i, chunk in enumerate(chunks):
                    out.write(
                        chunk if isinstance(chunk, bytes)
                        else chunk.encode("utf-8", "surrogateescape")
                    )
                    if i % progress_stride == 0:
                        progress()
            self._fault("before_reduce_commit")
            wof = getattr(self.transport, "write_output_from_file", None)
            if wof is not None:
                wof(f"mr-out-{a.task_id}", spool)
            else:  # custom transports without the streaming commit
                with open(spool, "rb") as f:
                    self.transport.write_output(f"mr-out-{a.task_id}", f.read())
        finally:
            # the transport may have CONSUMED the spool (rename commit on
            # local data planes, runtime/store.put_from_file consume=True)
            if os.path.exists(spool):
                os.unlink(spool)
