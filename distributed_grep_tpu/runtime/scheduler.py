"""Coordinator task scheduler — the reference's semantics, without busy-polls.

Reproduces map_reduce/coordinator.go's behavior:

* one map task per input file, seeded up front (coordinator.go:329-333);
  reduce partitions 0..n_reduce-1 seeded alongside (coordinator.go:334-337);
* long-polling AssignTask: blocks until a map split is available; after the
  map phase completes, hands out reduce partitions (coordinator.go:43-95);
* file->task dedup so a re-enqueued file keeps its task id
  (coordinator.go:53-58);
* monotonically increasing worker ids allocated at assignment
  (coordinator.go:68,:86);
* streaming shuffle: ReduceNextFile blocks until the next intermediate file
  for that partition commits, or returns done once the map phase is over and
  the cursor is exhausted — so reducers run concurrently with maps
  (coordinator.go:159-174);
* heartbeats stamped at assignment and on every next-file fetch
  (coordinator.go:62,:82,:162); a background sweeper re-enqueues any
  in-progress task idle >= task_timeout_s (coordinator.go:97-124);
* idempotent completion: duplicate MapFinished/ReduceFinished short-circuit
  (coordinator.go:131-134);
* Done() when both phases complete (coordinator.go:276-299) — without the
  reference's side effect of tearing down connections inside the predicate.

Where the reference busy-polls (10 ms in AssignTask :89,:92, 50 ms in
ReduceNextFile :172, 1 s sweeper :122), this scheduler blocks on a single
condition variable and notifies on every state change.
"""

from __future__ import annotations

import os
import threading
import time
from collections import deque
from typing import Any, Optional

from distributed_grep_tpu.runtime import rpc
from distributed_grep_tpu.runtime.journal import TaskJournal
from distributed_grep_tpu.runtime.types import MapTask, ReduceTask, TaskState
from distributed_grep_tpu.utils import lockdep
from distributed_grep_tpu.utils import metrics as metrics_mod
from distributed_grep_tpu.utils.logging import get_logger
from distributed_grep_tpu.utils.metrics import Metrics
from distributed_grep_tpu.utils.spans import ClockSync, EventLog

log = get_logger("scheduler")

# Process-global typed instruments (utils/metrics.py round 15): scheduling
# latency + failure-detector activity, served at GET /metrics on both the
# one-shot coordinator and the service daemon.  Leaf locks — safe to
# touch under the scheduler lock.
_H_ASSIGN_POLL = metrics_mod.histogram("dgrep_assign_poll_seconds")
_H_MAP_PHASE = metrics_mod.histogram("dgrep_map_phase_seconds")
_H_REDUCE_PHASE = metrics_mod.histogram("dgrep_reduce_phase_seconds")
_C_REQUEUED = metrics_mod.counter("dgrep_tasks_requeued_total")
_C_QUARANTINED = metrics_mod.counter("dgrep_workers_quarantined_total")

# Consecutive attributed failures (task timeouts while holding the task)
# before a worker is quarantined.  One timeout is routine (a long GC pause,
# one slow disk); three in a row with no intervening success is a worker
# that keeps accepting work and keeps going dark — exactly the flaky-host
# pattern that otherwise captures a share of every job's tasks forever.
QUARANTINE_AFTER_FAILURES = 3
DEFAULT_QUARANTINE_S = 30.0
# Exponential backoff cap: repeated quarantine episodes double the window
# up to this many base windows (a worker flapping all day re-probations
# every ~8 windows instead of hourly-compounding to never).
_QUARANTINE_MAX_FACTOR = 8


def env_worker_quarantine_s(default: float = DEFAULT_QUARANTINE_S) -> float:
    """Base quarantine window for flaky workers — the ONE parser of
    DGREP_WORKER_QUARANTINE_S (malformed or <= 0 keeps the default,
    matching env_batch_bytes' shrug-off policy).  0 is deliberately not
    an off switch: quarantine is gated on attributed failures, and a
    deployment that wants it off sets the threshold unreachable by
    keeping workers healthy, not by a zero window that would re-admit a
    dark worker instantly."""
    raw = os.environ.get("DGREP_WORKER_QUARANTINE_S")
    if raw is None or raw == "":
        return default
    try:
        v = float(raw)
    except ValueError:
        return default
    return v if v > 0 else default


class WorkerHealth:
    """Per-worker consecutive-failure tracker with exponential-backoff
    quarantine — shared by every scheduler of a service daemon (one flaky
    worker must not be re-probationed per job) and owned privately by
    one-shot coordinators.  Thread-safe; all methods are O(1).

    A *failure* is an attributed task timeout: the sweeper re-enqueued a
    task while this worker held it.  A *success* is any committed task.
    After QUARANTINE_AFTER_FAILURES consecutive failures the worker is
    quarantined for base * 2^(episode-1) seconds (capped); while
    quarantined it receives no assignments — its polls park in the
    long-poll wait and return a retry with a retry_after_s hint.  Expiry
    is re-probation, not absolution: the failure streak resets to one
    step below the threshold, so one more timeout re-quarantines (with a
    doubled window) while one success clears the record."""

    # Bounded state over an unbounded worker-id stream (a service daemon
    # allocates a FRESH id per reconnect, and crashed workers leave their
    # records behind): past this many tracked workers the least-recently
    # touched non-quarantined records are dropped.
    MAX_TRACKED = 4096

    def __init__(self, base_s: float | None = None):
        self.base_s = (
            env_worker_quarantine_s() if base_s is None else float(base_s)
        )
        self._lock = lockdep.make_lock("worker-health")
        self._fails: dict[int, int] = {}  # consecutive attributed failures
        self._episodes: dict[int, int] = {}  # quarantine episodes so far
        self._until: dict[int, float] = {}  # monotonic expiry per worker
        self._touched: dict[int, float] = {}  # recency (prune order)
        self._polls: dict[int, float] = {}  # last assign poll per worker
        self.quarantined_total = 0  # counter: episodes ever entered
        # Optional fleet-timeline hook (round 19): the service points it
        # at DaemonLog staging so quarantine enter/expire/clear land on
        # daemon.jsonl exactly once per episode even with K per-job
        # schedulers sharing this tracker.  Called OUTSIDE self._lock
        # (the callback takes its own leaf lock); never raises upward.
        self.on_event = None

    def _emit(self, kind: str, **payload) -> None:
        cb = self.on_event
        if cb is not None:
            try:
                cb(kind, **payload)
            except Exception:  # noqa: BLE001 — telemetry, never fatal
                log.exception("worker-health event hook failed")

    def _prune_locked(self, now: float) -> None:
        if len(self._touched) <= self.MAX_TRACKED:
            return
        evictable = sorted(
            (wid for wid in self._touched
             if self._until.get(wid, 0.0) <= now),
            key=lambda wid: self._touched[wid],
        )
        for wid in evictable[: len(self._touched) - self.MAX_TRACKED]:
            for d in (self._fails, self._episodes, self._until,
                      self._touched, self._polls):
                d.pop(wid, None)

    def saw(self, worker_id: int) -> None:
        """Record an assign poll.  A worker loop is single-threaded: a
        poll AFTER an assignment proves it is NOT running that task — the
        evidence `record_failure` callers use to distinguish a lost
        assignment reply from a worker gone dark."""
        if worker_id < 0:
            return
        with self._lock:
            now = time.monotonic()
            self._polls[worker_id] = now
            self._touched[worker_id] = now
            self._prune_locked(now)

    def polled_since(self, worker_id: int, t: float) -> bool:
        """True when the worker has asked for work after monotonic ``t``."""
        with self._lock:
            return self._polls.get(worker_id, float("-inf")) > t

    def record_success(self, worker_id: int) -> None:
        if worker_id < 0:
            return
        with self._lock:
            had_episode = worker_id in self._episodes
            # drop the WHOLE record, _polls included: _prune_locked only
            # walks _touched, so an entry left in any sibling dict here
            # would leak for the daemon's lifetime
            self._fails.pop(worker_id, None)
            self._episodes.pop(worker_id, None)
            self._until.pop(worker_id, None)
            self._touched.pop(worker_id, None)
            self._polls.pop(worker_id, None)
        if had_episode:
            self._emit("quarantine_clear", worker=worker_id)

    def record_failure(self, worker_id: int) -> float:
        """Register an attributed failure; returns the quarantine window
        just entered (seconds), or 0.0 when the worker stays on
        probation."""
        if worker_id < 0:
            return 0.0
        episode = 0
        with self._lock:
            now = time.monotonic()
            self._touched[worker_id] = now
            self._prune_locked(now)
            if self._until.get(worker_id, 0.0) > now:
                return 0.0  # already quarantined: don't re-extend per sweep
            n = self._fails.get(worker_id, 0) + 1
            self._fails[worker_id] = n
            if n < QUARANTINE_AFTER_FAILURES:
                return 0.0
            ep = self._episodes.get(worker_id, 0) + 1
            self._episodes[worker_id] = ep
            episode = ep
            window = self.base_s * min(2 ** (ep - 1), _QUARANTINE_MAX_FACTOR)
            self._until[worker_id] = now + window
            # re-probation: one step below the threshold, so the next
            # failure after expiry re-quarantines immediately
            self._fails[worker_id] = QUARANTINE_AFTER_FAILURES - 1
            self.quarantined_total += 1
        self._emit("quarantine", worker=worker_id, episode=episode,
                   window_s=round(window, 3))
        return window

    def quarantine_remaining(self, worker_id: int) -> float:
        """Seconds of quarantine left for this worker (0.0 = assignable)."""
        with self._lock:
            until = self._until.get(worker_id)
            if until is None:
                return 0.0
            rem = until - time.monotonic()
            if rem > 0:
                return rem
            del self._until[worker_id]  # expired: re-probation
        self._emit("quarantine_expire", worker=worker_id)
        return 0.0

    def snapshot(self) -> dict:
        """Status view: active quarantines + the episode counter."""
        now = time.monotonic()
        with self._lock:
            active = {
                str(wid): round(until - now, 3)
                for wid, until in self._until.items() if until > now
            }
            return {
                "quarantined_total": self.quarantined_total,
                "active": active,
            }


def _split_label(members: tuple[str, ...]) -> str:
    """Display/journal label for a batched multi-file map split —
    deterministic for a given member list, so journal replay of the same
    job plan recognizes its own entries."""
    return f"{members[0]} (+{len(members) - 1} batched)"


def _producer_task_of(name: str) -> int | None:
    """The producing map task id of an intermediate file name
    ("mr-<tid>-<r>", the worker's own naming contract), or None for
    anything else-shaped.  Gates shuffle serves on the producer's
    COMPLETED state and resolves lost-output reports (peer shuffle,
    round 16) to the map task that must re-run."""
    parts = name.split("-")
    if len(parts) == 3 and parts[0] == "mr" and parts[1].isdigit():
        return int(parts[1])
    return None


class Scheduler:
    """Transport-agnostic coordinator state machine (thread-safe).

    ``files`` entries are either a single input path (one map task per
    file, the reference shape — coordinator.go:329-333) or a list of
    paths: a batched multi-file split (runtime/job.plan_map_splits packs
    the many-small-files regime so one map task — and one packed device
    dispatch — covers many sub-threshold files)."""

    def __init__(
        self,
        files: list[str],
        n_reduce: int,
        task_timeout_s: float = 10.0,
        sweep_interval_s: float = 1.0,
        app_options: Optional[dict[str, Any]] = None,
        journal: Optional[TaskJournal] = None,
        resume_entries: Optional[list[dict]] = None,
        metrics: Optional[Metrics] = None,
        commit_resolver: Optional[Any] = None,
        event_log: Optional[EventLog] = None,
        on_change: Optional[Any] = None,
        worker_health: Optional[WorkerHealth] = None,
        journal_gate: Optional[Any] = None,
        daemon_events: Optional[Any] = None,
    ):
        self.n_reduce = n_reduce
        self.task_timeout_s = task_timeout_s
        self.sweep_interval_s = sweep_interval_s
        self.app_options = dict(app_options or {})
        self.journal = journal
        self.metrics = metrics or Metrics()
        # commit_resolver(kind, task_id) -> winning task commit record
        # payload or None (WorkDir.resolve_task_commit, runtime/store.py).
        # When a record exists it — not the finished-RPC args — is the unit
        # of truth for what a completed task produced: a re-executed
        # straggler whose late RPC races the sweeper's re-issue can then
        # never register parts its winning attempt did not commit.
        self.commit_resolver = commit_resolver
        # Span pipeline (utils/spans.py): when an event log is wired in,
        # worker-shipped span records persist to events.jsonl and the
        # scheduler's own decisions (assignments, timeout re-enqueues,
        # commit registrations) are logged as coordinator-row events.
        # None = pipeline off: no file, no extra work on any RPC.
        self.event_log = event_log
        # Assignability callback for a MULTIPLEXING layer above (the
        # service daemon, runtime/service.py): its assign loop long-polls
        # across many schedulers on its own condition variable, which this
        # scheduler's internal notify cannot reach — called (outside the
        # lock) whenever work may have BECOME assignable here: a map-phase
        # completion (unlocks the reduce queue) or a timeout re-enqueue.
        # None (single-job coordinators) costs nothing.
        self.on_change = on_change
        # Flaky-worker quarantine (WorkerHealth above): the sweeper
        # attributes each timeout to the worker that held the task;
        # enough consecutive failures park that worker's assign polls
        # until an exponential-backoff window expires.  A service daemon
        # passes ONE shared instance to every job's scheduler (a flaky
        # worker is flaky for every tenant); one-shot coordinators get
        # their own.
        self.worker_health = worker_health or WorkerHealth()
        self._pending_events: list[dict] = []  # staged under the lock,
        # written by _flush_events after release
        # Journal completions are staged the same way (checked:
        # locked-blocking): TaskJournal fsyncs per record, and an fsync
        # inside the scheduler lock would stall every RPC handler behind
        # the disk on each commit.  The flush lock serializes write
        # batches end to end (the service registry-flush pattern);
        # durability-before-reply holds because map_finished /
        # reduce_finished flush in their `finally`, before the RPC reply
        # leaves the process.
        self._pending_journal: list[tuple] = []
        self._journal_flush_lock = lockdep.make_lock("journal-flush",
                                                     io_ok=True)
        # Daemon-scope write fence (round 18 HA failover): an optional
        # callable consulted by every journal flush batch before it
        # writes.  A False answer means this daemon lost the work-root
        # lease — the batch is DROPPED (the promoted daemon owns the
        # journal now; a stale interleaved line would poison its replay).
        # None (single-daemon, one-shot coordinators) skips the check
        # entirely.
        self.journal_gate = journal_gate
        # Fleet-timeline hook (round 19, runtime/daemon_log.py): a
        # callable(kind, **payload) the service points at DaemonLog
        # staging, called for daemon-consequential decisions (lost-output
        # revocations) — leaf-lock list append, safe under self._lock.
        # None (one-shot coordinators) costs nothing.
        self.daemon_events = daemon_events
        # (kind, task_id) pairs already journaled (staged or replayed):
        # a map task RE-COMPLETED after a lost-output re-execution (peer
        # shuffle, round 16) must not append a second map_done line —
        # the chaos matrix pins journal uniqueness per (kind, task), and
        # replay treats the first line as done anyway (re-execution is
        # deterministic, so the recorded parts still hold).
        self._journaled: set[tuple[str, int]] = set()
        self._span_seqs: dict[int, set[int]] = {}  # worker -> persisted
        # batch seqs (retry dedup, _persist_spans)
        self._span_seq_lock = lockdep.make_lock("span-seq")
        self._clock = ClockSync()
        # Per-worker liveness + shipped-metrics table (workers join
        # implicitly, so rows appear at first assignment/heartbeat):
        # worker_id -> {"seen": monotonic, "task": "map:3"|None,
        #               "metrics": last piggybacked counters snapshot,
        #               "clock_offset_s": ..., "rtt_s": ...}
        self.workers: dict[int, dict] = {}

        self._lock = lockdep.make_lock("scheduler")
        self._cond = threading.Condition(self._lock)

        # Task tables (MapData/ReduceData, helper_types.go:150-161).
        self.map_tasks: list[MapTask] = []
        for i, f in enumerate(files):
            if isinstance(f, (list, tuple)):
                members = tuple(str(m) for m in f)
                self.map_tasks.append(
                    MapTask(i, _split_label(members), files=members)
                )
            else:
                self.map_tasks.append(MapTask(i, f))
        self.reduce_tasks: list[ReduceTask] = [ReduceTask(i) for i in range(n_reduce)]
        self.file_to_task: dict[str, int] = {
            t.file: t.task_id for t in self.map_tasks
        }

        # Work queues (the buffered channels, coordinator.go:329-337).
        self._map_queue: deque[int] = deque(range(len(files)))
        self._reduce_queue: deque[int] = deque(range(n_reduce))

        self._next_worker_id = 0  # safeInt.get_and_increment (helper_types.go:45-79)
        # Incarnation epoch (rpc.AssignTaskReply.epoch): task_files
        # arrival order — and with it every reducer's files_processed
        # cursor — is only meaningful within ONE scheduler instance; a
        # restarted coordinator/daemon rebuilds the lists in replay
        # order, so shuffle fetches carrying another incarnation's epoch
        # are aborted (reduce_next_file), never served a file their
        # cursor would misindex.
        import uuid as _uuid

        self.epoch = _uuid.uuid4().hex[:12]
        self._stopped = False
        # Incremental completion counters: COMPLETED is terminal (the
        # sweeper only re-enqueues IN_PROGRESS tasks), so counting at the
        # transitions replaces the per-event O(n) sweeps over the task
        # tables that made a 2,000-file `grep -r` job quadratic (round 5).
        self._maps_completed = 0
        self._reduces_completed = 0
        # Phase-wall instrumentation anchors: construction -> last map
        # commit = map phase; that instant -> last reduce commit = reduce
        # phase.  Phases completed purely by journal replay observe
        # nothing (a resumed job's wall would misprice the live phase).
        self._phase_t0 = time.monotonic()
        self._reduce_t0: float | None = None
        # The map phase can complete MORE than once: a lost-output
        # revocation (peer shuffle) walks a COMPLETED map back to
        # UNASSIGNED and the re-execution re-crosses the phase boundary.
        # Observe the wall (and anchor _reduce_t0) at the FIRST crossing
        # only — a re-crossing's "map phase wall" would include the
        # elapsed reduce time.
        self._phase_observed = False

        if resume_entries:
            self._replay(resume_entries)
        # every map task is runnable from the start; reduces once the
        # map phase is done (here only by replay, or with no map task)
        now = time.time()
        for t in self.map_tasks:
            t.runnable_at = now
        if self._map_phase_done_locked():
            for rt in self.reduce_tasks:
                rt.runnable_at = now

        self._sweeper = threading.Thread(
            target=self._sweep_loop, name="failure-detector", daemon=True
        )
        self._sweeper.start()

    # ------------------------------------------------------------------ replay
    def _resolve_commit(self, kind: str, task_id: int):
        """The winning task commit record payload, or None (no resolver /
        no record).  Resolver failures count as 'no record' — the RPC-args
        path still works, so a broken commits dir degrades, not crashes."""
        if self.commit_resolver is None:
            return None
        try:
            return self.commit_resolver(kind, task_id)
        except Exception:  # noqa: BLE001 — degrade to RPC-args truth
            log.exception("commit record resolution failed for %s %d", kind, task_id)
            return None

    def _replay(self, entries: list[dict]) -> None:
        """Apply journal entries so a restarted coordinator skips done work."""
        for e in entries:
            if e.get("kind") == "map_done":
                tid = e["task_id"]
                if 0 <= tid < len(self.map_tasks):
                    t = self.map_tasks[tid]
                    files_e = e.get("files")
                    if t.file != e.get("file") or (
                        # batched split: the member list must match too (a
                        # re-planned batch with the same first file and
                        # count — e.g. member sizes changed between runs —
                        # is a DIFFERENT split and must re-run)
                        files_e is not None and tuple(files_e) != t.files
                    ):
                        # Input list changed/reordered since the journal was
                        # written: this entry describes a different split, so
                        # the task must run again.
                        log.warning(
                            "journal entry for map task %d names %r but task file "
                            "is %r; ignoring entry",
                            tid,
                            e.get("file"),
                            t.file,
                        )
                        continue
                    parts = e.get("parts", [])
                    peer = None
                    if e.get("has_record"):
                        # This completion was committed via a task commit
                        # record — re-resolve it as the unit of truth.  A
                        # journal entry whose record vanished is stale
                        # (someone swept the commits dir): re-run the task
                        # rather than trust unverifiable state.
                        record = self._resolve_commit("map", tid)
                        if record is None:
                            log.warning(
                                "journal says map task %d committed via record "
                                "but no valid record resolves; re-running", tid,
                            )
                            continue
                        # malformed record (no "parts"): keep the journal's
                        parts = record.get("parts", parts)
                        # peer-held output (round 16): the record's
                        # metadata survives a coordinator restart — if
                        # the producer also died, the first fetch fails
                        # and the lost-output path re-runs this task
                        if isinstance(record.get("peer"), dict):
                            peer = record["peer"]
                    if t.state is not TaskState.COMPLETED:
                        t.state = TaskState.COMPLETED
                        t.peer = peer
                        self._journaled.add(("map", tid))
                        self._register_map_outputs(tid, parts)
                        if tid in self._map_queue:
                            self._map_queue.remove(tid)
            elif e.get("kind") == "reduce_done":
                tid = e["task_id"]
                if 0 <= tid < len(self.reduce_tasks):
                    if e.get("has_record") and self._resolve_commit("reduce", tid) is None:
                        log.warning(
                            "journal says reduce task %d committed via record "
                            "but no valid record resolves; re-running", tid,
                        )
                        continue
                    t = self.reduce_tasks[tid]
                    t.state = TaskState.COMPLETED
                    self._journaled.add(("reduce", tid))
                    if tid in self._reduce_queue:
                        self._reduce_queue.remove(tid)
        # one-time O(n) resync of the incremental counters after replay
        self._maps_completed = sum(
            t.state is TaskState.COMPLETED for t in self.map_tasks
        )
        self._reduces_completed = sum(
            t.state is TaskState.COMPLETED for t in self.reduce_tasks
        )
        # A phase completed purely by replay observes nothing (the
        # round-15 contract) — and must not observe later either, when a
        # lost-output revocation makes a live commit re-cross it.
        if self.map_tasks and self._map_phase_done_locked():
            self._phase_observed = True
        log.info(
            "journal replay: %d map + %d reduce tasks already complete",
            self._maps_completed, self._reduces_completed,
        )

    # ----------------------------------------------------------- observability
    def _event(self, name: str, **args) -> None:
        """Coordinator-row event (no worker tag -> tid 0 in trace-export).
        No-op without an event log.  Call sites hold the scheduler lock, so
        the record is only STAGED here; `_flush_events` writes it to disk
        after the lock is released — a slow work-dir filesystem must not
        stall every RPC handler behind a flush inside the global lock."""
        if self.event_log is None:
            return
        self._pending_events.append({
            "t": "instant", "name": name, "cat": "sched",
            "ts": time.time(), **({"args": args} if args else {}),
        })

    def _stage_wait(self, kind: str, task) -> None:
        """Stage the task's ``<kind>:wait`` span — from runnable to this
        assignment, the wait for a worker slot — the way _event stages
        (call under the lock; written by _flush_events after release)."""
        if self.event_log is None or not task.runnable_at:
            return
        now = time.time()
        self._pending_events.append({
            "t": "span",
            "name": "map:wait" if kind == "map" else "reduce:wait",
            "cat": "sched", "ts": task.runnable_at,
            "dur": max(0.0, now - task.runnable_at), "parent": None,
            "args": {"task": task.task_id, "attempt": task.attempts},
        })
        task.runnable_at = 0.0

    def _flush_events(self) -> None:
        """Write staged coordinator events outside the scheduler lock.
        Never raises — telemetry must not take the control plane down."""
        if self.event_log is None:
            return
        with self._lock:
            if not self._pending_events:
                return
            pending, self._pending_events = self._pending_events, []
        self._persist_spans(pending)

    def _flush_journal(self) -> None:
        """Write staged journal completions outside the scheduler lock —
        TaskJournal fsyncs per record, exactly the filesystem work the
        scheduler lock must never hold (checked: locked-blocking).  The
        flush lock makes swap + append one ordered unit; a journal
        closed by a racing job teardown absorbs the write (the entry
        only re-runs an already-finished task after a restart).  Never
        raises — a full disk degrades crash-resume, not the control
        plane."""
        if self.journal is None:
            return
        with self._journal_flush_lock:
            self._write_staged_journal()

    def close_journal(self) -> None:
        """Flush staged completions, then close the journal — one ordered
        unit under the flush lock, so a job teardown can never close the
        file between a completion's staging and its write (a completion
        stages BEFORE it notifies done, so anything a finalizer could
        have observed is durable before the close)."""
        if self.journal is None:
            return
        with self._journal_flush_lock:
            self._write_staged_journal()
            self.journal.close()

    def _write_staged_journal(self) -> None:
        """The write half of _flush_journal; caller holds the flush lock."""
        with self._lock:
            if not self._pending_journal:
                return
            pending, self._pending_journal = self._pending_journal, []
        if self.journal_gate is not None and not self.journal_gate():
            # deposed (lease lost): drop the batch — commit records keep
            # the tasks' truth; the promoted daemon's replay re-resolves
            # them without ever seeing a stale interleaved line
            log.warning("journal flush fenced: lease lost, %d staged "
                        "entries dropped", len(pending))
            return
        for kind, task_id, file, parts, has_record, files in pending:
            try:
                if kind == "map":
                    self.journal.map_completed(
                        task_id, file, parts, has_record=has_record,
                        files=files,
                    )
                else:
                    self.journal.reduce_completed(
                        task_id, has_record=has_record
                    )
            except ValueError:
                # closed by job teardown racing a late completion: the
                # task is committed either way (commit records), the
                # journal line only skipped a restart's re-run
                log.warning(
                    "journal append after close dropped (%s task %d)",
                    kind, task_id,
                )
            except OSError:
                log.exception(
                    "journal append failed for %s task %d", kind, task_id
                )

    def _persist_spans(self, recs: list[dict], worker_id: int = -1,
                       seq: int = -1) -> None:
        """Persist a span batch.  (worker_id, seq) is the worker's batch
        counter: a transport-level RPC retry (grace-heartbeat re-POSTs,
        the finished RPC's 15 s retry loop) reships the SAME batch after
        the coordinator may already have processed it — dedup here keeps
        events.jsonl covering each attempt exactly once."""
        if self.event_log is None or not recs:
            return
        if seq >= 0 and worker_id >= 0:
            with self._span_seq_lock:
                seen = self._span_seqs.setdefault(worker_id, set())
                if seq in seen:
                    return
                seen.add(seq)
        try:
            self.event_log.write_many(recs)
        except Exception:  # noqa: BLE001
            log.exception("event log write failed")

    def _worker_seen(self, worker_id: int, task: str | None = ...,
                     metrics: dict | None = None) -> None:
        """Stamp a worker row (call under the lock).  `task` semantics:
        unspecified (Ellipsis) keeps the current in-flight marker."""
        if worker_id < 0:
            return
        info = self.workers.setdefault(worker_id, {"task": None})
        info["seen"] = time.monotonic()
        if task is not ...:
            info["task"] = task
        if metrics is not None:
            # the per-process source token rides only for the service's
            # delta tracker — it is not a counter, keep it out of the
            # /status worker rows (the dict is built fresh per RPC)
            metrics.pop("proc", None)
            info["metrics"] = metrics

    def _observe_clock(self, args: rpc.HeartbeatArgs,
                       recv_at: float) -> None:
        """Fold a heartbeat's clock observation in (recv_at = the wall
        clock at RPC arrival, captured by the caller before any span
        persistence or lock wait); persist a worker_clock record when the
        estimate moves >5 ms (trace-export reads the LAST record per
        worker)."""
        prev = self._clock.offsets.get(args.worker_id)
        off = self._clock.observe(
            args.worker_id, args.sent_at, recv_at, args.rtt_s
        )
        if off is None:
            return
        info = self.workers.get(args.worker_id)
        if info is not None:
            info["clock_offset_s"] = off
            info["rtt_s"] = self._clock.rtts.get(args.worker_id)
        if self.event_log is not None and (
            prev is None or abs(off - prev) > 0.005
        ):
            # staged like _event: callers hold the scheduler lock
            self._pending_events.append({
                "t": "worker_clock", "worker": args.worker_id,
                "offset_s": round(off, 6),
                "rtt_s": round(self._clock.rtts.get(args.worker_id, 0.0), 6),
                "ts": time.time(),
            })

    def worker_status(self) -> dict:
        """Per-worker liveness + shipped aggregates for GET /status: last
        heartbeat age, in-flight task, and the latest piggybacked Metrics
        counters (bytes_scanned / gbps / retries / spills)."""
        now = time.monotonic()
        with self._lock:
            out = {}
            for wid, info in sorted(self.workers.items()):
                row: dict = {
                    "last_heartbeat_age_s": round(now - info["seen"], 3),
                    "task": info.get("task"),
                }
                if info.get("metrics") is not None:
                    row["metrics"] = info["metrics"]
                if info.get("clock_offset_s") is not None:
                    row["clock_offset_s"] = round(info["clock_offset_s"], 6)
                q = self.worker_health.quarantine_remaining(wid)
                if q > 0:
                    row["quarantined_s"] = round(q, 3)
                out[str(wid)] = row
            return out

    def inflight_status(self) -> list[dict]:
        """Every IN_PROGRESS task with its heartbeat age and any active
        grace window — stragglers visible before the sweeper fires."""
        now = time.monotonic()
        out = []
        with self._lock:
            for kind, table in (("map", self.map_tasks),
                                ("reduce", self.reduce_tasks)):
                for t in table:
                    if t.state is TaskState.IN_PROGRESS:
                        age = now - t.timestamp
                        row = {
                            "type": kind, "task_id": t.task_id,
                            "attempts": t.attempts,
                            "heartbeat_age_s": round(age, 3),
                        }
                        if t.grace_s:
                            row["grace_s"] = t.grace_s
                            row["grace_remaining_s"] = round(
                                max(0.0, t.grace_s - age), 3
                            )
                        out.append(row)
        return out

    # ----------------------------------------------------------------- assign
    def assign_task(self, args: rpc.AssignTaskArgs, timeout: float = 30.0) -> rpc.AssignTaskReply:
        """Long-poll for work.  Blocks until a task is available, the job is
        done (reply JOB_DONE), or `timeout` elapses (reply JOB_DONE only if
        actually done; otherwise an empty retry reply with task_id == -2)."""
        deadline = _Deadline(timeout)
        t0 = time.monotonic()
        try:
            return self._assign_task_locked(args, deadline)
        finally:
            if timeout > 0:
                # real long-polls only: the service daemon sweeps every
                # running job's scheduler with timeout=0 per pass, and
                # those sub-millisecond probes would swamp the latency
                # signal (the daemon observes its own outer poll).
                _H_ASSIGN_POLL.observe(time.monotonic() - t0)
            self._flush_events()

    def _assign_task_locked(self, args: rpc.AssignTaskArgs,
                            deadline: "_Deadline") -> rpc.AssignTaskReply:
        with self._cond:
            worker_id = args.worker_id
            if worker_id < 0:
                worker_id = self._next_worker_id
                self._next_worker_id += 1
            # BEFORE any assignment stamp: a poll-then-assign in one call
            # must read as polled-before-held (lost-reply attribution)
            self.worker_health.saw(worker_id)
            while True:
                if self._stopped or self._done_locked():
                    return rpc.AssignTaskReply(
                        assignment=rpc.Assignment.JOB_DONE, worker_id=worker_id
                    )
                # Quarantined workers get no work: park in the long-poll
                # (waiting, not spinning — a tight retry loop against the
                # control plane is itself a failure mode) and answer a
                # retry with a client backoff hint at the window edge.
                quarantine_s = self.worker_health.quarantine_remaining(
                    worker_id
                )
                if quarantine_s > 0:
                    remaining = deadline.remaining()
                    if remaining <= 0:
                        return rpc.AssignTaskReply(
                            assignment="retry", task_id=-2,
                            worker_id=worker_id,
                            retry_after_s=round(quarantine_s, 3),
                        )
                    self._cond.wait(timeout=min(remaining, quarantine_s,
                                                self.sweep_interval_s))
                    continue
                while self._map_queue and (
                    self.map_tasks[self._map_queue[0]].state is not TaskState.UNASSIGNED
                ):
                    # Stale entry: the task timed out, was re-enqueued, and the
                    # original worker then completed it — never re-issue a
                    # COMPLETED (or already re-assigned) task.
                    self._map_queue.popleft()
                if self._map_queue:
                    tid = self._map_queue.popleft()
                    task = self.map_tasks[tid]
                    # file_to_task dedup keeps the same task id on re-issue
                    # (coordinator.go:53-58); queue entries are task ids here
                    # so the invariant holds by construction.
                    task.state = TaskState.IN_PROGRESS
                    task.heartbeat()
                    task.attempts += 1
                    task.worker = worker_id
                    task.stamped = False  # no worker-side evidence yet
                    task.fused_claim = False  # normal assign: chargeable
                    self.metrics.inc("map_assigned")
                    self._worker_seen(worker_id, task=f"map:{tid}")
                    self._stage_wait("map", task)
                    self._event("assign_map", task=tid, worker=worker_id,
                                attempt=task.attempts, file=task.file)
                    log.debug("assign map task %d (%s) -> worker %d", tid, task.file, worker_id)
                    return rpc.AssignTaskReply(
                        assignment=rpc.Assignment.MAP,
                        filename=task.file,
                        filenames=list(task.files),  # batched split members
                        task_id=tid,
                        n_reduce=self.n_reduce,
                        worker_id=worker_id,
                        app_options=self.app_options,
                        task_timeout_s=self.task_timeout_s,
                        epoch=self.epoch,
                    )
                while self._reduce_queue and (
                    self.reduce_tasks[self._reduce_queue[0]].state is not TaskState.UNASSIGNED
                ):
                    self._reduce_queue.popleft()  # stale entry (see map queue above)
                if self._map_phase_done_locked() and self._reduce_queue:
                    tid = self._reduce_queue.popleft()
                    task = self.reduce_tasks[tid]
                    task.state = TaskState.IN_PROGRESS
                    task.heartbeat()
                    task.attempts += 1
                    task.worker = worker_id
                    task.stamped = False  # see the map branch above
                    self.metrics.inc("reduce_assigned")
                    self._worker_seen(worker_id, task=f"reduce:{tid}")
                    self._stage_wait("reduce", task)
                    self._event("assign_reduce", task=tid, worker=worker_id,
                                attempt=task.attempts)
                    log.debug("assign reduce task %d -> worker %d", tid, worker_id)
                    return rpc.AssignTaskReply(
                        assignment=rpc.Assignment.REDUCE,
                        task_id=tid,
                        n_reduce=self.n_reduce,
                        worker_id=worker_id,
                        app_options=self.app_options,
                        task_timeout_s=self.task_timeout_s,
                        epoch=self.epoch,
                    )
                remaining = deadline.remaining()
                if remaining <= 0:
                    return rpc.AssignTaskReply(
                        assignment=rpc.Assignment.JOB_DONE if self._done_locked() else "retry",
                        task_id=-2,
                        worker_id=worker_id,
                    )
                self._cond.wait(timeout=min(remaining, self.sweep_interval_s))

    def claim_map_task(self, task_id: int, worker_id: int) -> dict | None:
        """Claim one SPECIFIC idle map task for a fused attempt (the
        service's cross-tenant scan fusion, runtime/fusion.py): the
        co-tenant's task joins another job's assignment, so this is the
        assign_task map branch minus the queue pop — the stale queue
        entry is skipped by the assign loop's UNASSIGNED check, exactly
        like a timeout re-enqueue's leftovers.  First attempts only: a
        task that already timed out once re-runs solo (fusion is a fast
        path; a fused-attempt-specific failure must not loop).  Returns
        the assignment fields for the fused reply entry, or None (not
        idle / retried / stopped — the planner then simply skips this
        tenant).  State moves only under the lock; events flush after
        release (checked: locked-blocking)."""
        try:
            with self._cond:
                if self._stopped or not 0 <= task_id < len(self.map_tasks):
                    return None
                task = self.map_tasks[task_id]
                if task.state is not TaskState.UNASSIGNED or task.attempts:
                    return None
                task.state = TaskState.IN_PROGRESS
                task.heartbeat()
                task.attempts += 1
                task.worker = worker_id
                task.stamped = False  # no worker-side evidence yet
                # Quarantine attribution: a fused EXTRA's timeout is never
                # charged (see the sweeper) — K participant schedulers
                # each sharing one WorkerHealth would otherwise count one
                # lost fused attempt as K consecutive failures and
                # insta-quarantine the worker; the PRIMARY assignment's
                # timeout carries the one charge for the shared event.
                task.fused_claim = True
                self.metrics.inc("map_assigned")
                self.metrics.inc("fused_assigned")
                self._worker_seen(worker_id, task=f"map:{task_id}")
                self._stage_wait("map", task)
                self._event("assign_map", task=task_id, worker=worker_id,
                            attempt=task.attempts, file=task.file,
                            fused=True)
                log.debug("fuse-claim map task %d (%s) -> worker %d",
                          task_id, task.file, worker_id)
                return {
                    "task_id": task_id,
                    "filename": task.file,
                    "filenames": list(task.files),
                    "n_reduce": self.n_reduce,
                    "app_options": self.app_options,
                    "task_timeout_s": self.task_timeout_s,
                    "epoch": self.epoch,
                }
        finally:
            self._flush_events()

    # ------------------------------------------------------------- completion
    def _notify_change(self) -> None:
        """Wake the multiplexing layer's assign loop (see on_change).
        Never raises — a broken callback must not fail a task commit."""
        cb = self.on_change
        if cb is None:
            return
        try:
            cb()
        except Exception:  # noqa: BLE001 — advisory wakeup only
            log.exception("scheduler on_change callback failed")

    def map_finished(self, args: rpc.TaskFinishedArgs) -> rpc.TaskFinishedReply:
        """Idempotent map commit (coordinator.go:126-148)."""
        record = self._resolve_commit("map", args.task_id)
        self._persist_spans(args.spans, args.worker_id, args.spans_seq)
        try:
            return self._map_finished_locked(args, record)
        finally:
            self._flush_journal()  # fsync BEFORE the reply leaves
            self._flush_events()
            self._notify_change()  # map-phase completion unlocks reduces

    def _map_finished_locked(self, args: rpc.TaskFinishedArgs,
                             record) -> rpc.TaskFinishedReply:
        with self._cond:
            self._worker_seen(args.worker_id, task=None, metrics=args.metrics)
            # any completed task — duplicates included — is a live,
            # functional worker: clear its failure streak
            self.worker_health.record_success(args.worker_id)
            task = self.map_tasks[args.task_id]
            if task.state is TaskState.COMPLETED:
                return rpc.TaskFinishedReply(ok=True)  # duplicate absorbed (:131-134)
            task.state = TaskState.COMPLETED
            self._maps_completed += 1
            # The task commit record (published before this RPC) is the
            # unit of truth for the produced partitions; the RPC args are
            # the fallback for transports without commit records — and for
            # a malformed record missing "parts" (the data plane accepts
            # any small JSON body; malformed degrades, never crashes).
            parts = args.produced_parts
            if record is not None and "parts" in record:
                parts = record["parts"]
            # Peer-held output metadata (round 16): the LIVE attempt's
            # args win over the resolved record — record resolution picks
            # the lexicographically-smallest attempt, which after a
            # lost-output re-execution can still be the DEAD producer's;
            # registering the freshly-finished attempt's endpoint is what
            # lets recovery converge (a wrong endpoint only ever costs
            # one more lost-output round, never serves wrong bytes — the
            # checksum gate).  Relay commits carry neither and clear it.
            peer = None
            if record is not None and isinstance(record.get("peer"), dict):
                peer = record["peer"]
            if args.peer_endpoint:
                peer = {"endpoint": args.peer_endpoint,
                        "worker": args.worker_id,
                        "parts": dict(args.peer_parts or {})}
            task.peer = peer
            self._register_map_outputs(args.task_id, parts)
            self.metrics.inc("map_completed")
            if self._map_phase_done_locked():
                # the map phase completes: the reduce tasks become runnable
                now = time.time()
                for rt in self.reduce_tasks:
                    if rt.state is TaskState.UNASSIGNED:
                        rt.runnable_at = now
            if self._map_phase_done_locked() and not self._phase_observed:
                self._phase_observed = True
                now = time.monotonic()
                self._reduce_t0 = now
                _H_MAP_PHASE.observe(now - self._phase_t0)
            if self.journal and ("map", args.task_id) not in self._journaled:
                # staged under the lock, at most once per task — the
                # COMPLETED transition gates duplicates within one
                # completion, the _journaled set gates RE-completions
                # after a lost-output re-execution (peer shuffle);
                # fsync'd by _flush_journal after release
                self._journaled.add(("map", args.task_id))
                self._pending_journal.append((
                    "map", args.task_id, task.file, parts,
                    record is not None, list(task.files) or None,
                ))
            self._event("map_committed", task=args.task_id,
                        worker=args.worker_id, parts=len(parts),
                        has_record=record is not None)
            log.info(
                "map task %d done (%d/%d)",
                args.task_id, self._maps_completed, len(self.map_tasks),
            )
            self._cond.notify_all()
            return rpc.TaskFinishedReply(ok=True)

    def _register_map_outputs(self, map_task_id: int, produced_parts: list[int]) -> None:
        """Register committed intermediate files with their reduce partitions —
        only partitions the map actually produced (coordinator.go:139-141)."""
        for r in produced_parts:
            if 0 <= r < self.n_reduce:
                name = f"mr-{map_task_id}-{r}"
                if name not in self.reduce_tasks[r].task_files:
                    self.reduce_tasks[r].task_files.append(name)

    def reduce_finished(self, args: rpc.TaskFinishedArgs) -> rpc.TaskFinishedReply:
        record = self._resolve_commit("reduce", args.task_id)
        self._persist_spans(args.spans, args.worker_id, args.spans_seq)
        try:
            return self._reduce_finished_locked(args, record)
        finally:
            self._flush_journal()  # fsync BEFORE the reply leaves
            self._flush_events()

    def _reduce_finished_locked(self, args: rpc.TaskFinishedArgs,
                                record) -> rpc.TaskFinishedReply:
        with self._cond:
            self._worker_seen(args.worker_id, task=None, metrics=args.metrics)
            self.worker_health.record_success(args.worker_id)
            task = self.reduce_tasks[args.task_id]
            if task.state is not TaskState.COMPLETED:
                task.state = TaskState.COMPLETED
                self._reduces_completed += 1
                self.metrics.inc("reduce_completed")
                if self._done_locked():
                    _H_REDUCE_PHASE.observe(
                        time.monotonic()
                        - (self._reduce_t0 or self._phase_t0)
                    )
                if self.journal and (
                    ("reduce", args.task_id) not in self._journaled
                ):
                    # staged like the map branch; see _flush_journal
                    self._journaled.add(("reduce", args.task_id))
                    self._pending_journal.append((
                        "reduce", args.task_id, None, None,
                        record is not None, None,
                    ))
                self._event("reduce_committed", task=args.task_id,
                            worker=args.worker_id,
                            has_record=record is not None)
                log.info(
                    "reduce task %d done (%d/%d)",
                    args.task_id, self._reduces_completed, self.n_reduce,
                )
            self._cond.notify_all()
            return rpc.TaskFinishedReply(ok=True)

    # ------------------------------------------------------ streaming shuffle
    def reduce_next_file(
        self, args: rpc.ReduceNextFileArgs, timeout: float = 30.0
    ) -> rpc.ReduceNextFileReply:
        """The pipelined shuffle feed (coordinator.go:159-174): block until the
        reducer's next intermediate file exists, or the map phase is done and
        the cursor is exhausted (done=True).  Doubles as a heartbeat (:162).

        Peer shuffle (round 16): a reply for a peer-held file carries the
        producing worker's endpoint + size + crc32 (wire-elided
        otherwise); an ``args.lost_file`` report re-enqueues the producing
        MAP task (``_report_lost_locked``) and this cursor then WAITS for
        the re-executed attempt — its file entry is gated on the
        producer's COMPLETED state, exactly like a file that has not
        arrived yet."""
        deadline = _Deadline(timeout)
        if args.epoch and args.epoch != self.epoch:
            # a reduce attempt from a PREVIOUS scheduler incarnation (it
            # outlived a daemon restart through its transport retries):
            # its files_processed cursor indexes the OLD task_files
            # arrival order — serving it from the rebuilt list would feed
            # it duplicate/missing shuffle files and its commit could WIN
            # attempt resolution with wrong bytes.  Abort the attempt;
            # the re-issued one owns this incarnation.  Checked BEFORE
            # any lost-output report is honored: a zombie must not
            # re-enqueue this incarnation's completed maps.
            log.warning(
                "aborting reduce attempt for task %d: stale scheduler "
                "epoch %s (current %s)", args.task_id, args.epoch,
                self.epoch,
            )
            return rpc.ReduceNextFileReply(abort=True)
        requeued = False
        try:
            with self._cond:
                if args.lost_file:
                    requeued = self._report_lost_locked(args)
                    if requeued:
                        # the reporter is ABORTED (and its task
                        # re-enqueued) along with the map re-enqueue: its
                        # cursor cannot advance until the map re-runs,
                        # and a worker parked in a gated long-poll is a
                        # worker that cannot run that map — with a small
                        # pool (every live worker holding a reduce) the
                        # job would deadlock.  Freed workers serve the
                        # map queue first, so progress is guaranteed
                        # with any one live worker; the re-issued reduce
                        # attempt re-fetches from the fresh metadata.
                        return rpc.ReduceNextFileReply(abort=True)
                task = self.reduce_tasks[args.task_id]
                while True:
                    task.heartbeat()
                    if args.worker_id < 0 or args.worker_id == task.worker:
                        # the CURRENT assignee demonstrably holds it; a
                        # same-life straggler's fetch must not plant the
                        # evidence that would charge the reassigned worker
                        task.stamped = True
                    if args.files_processed < len(task.task_files):
                        reply = self._serve_file_locked(
                            task.task_files[args.files_processed]
                        )
                        if reply is not None:
                            return reply
                        # producer re-executing (lost output): hold the
                        # cursor like a not-yet-arrived file — fall
                        # through to the wait
                    elif self._map_phase_done_locked():
                        return rpc.ReduceNextFileReply(done=True)
                    remaining = deadline.remaining()
                    if remaining <= 0:
                        # Not done — client should re-poll (long-poll
                        # window expired).
                        return rpc.ReduceNextFileReply(next_file="", done=False)
                    self._cond.wait(
                        timeout=min(remaining, self.sweep_interval_s)
                    )
        finally:
            self._flush_events()
            if requeued:
                self._notify_change()  # the map is assignable again

    def _serve_file_locked(self, name: str) -> rpc.ReduceNextFileReply | None:
        """One servable shuffle entry, or None while its producing map
        task is being re-executed (lost peer output — pre-peer this state
        was unreachable: files registered only at completion and were
        never revoked).  Peer-held entries carry the fetch metadata."""
        tid = _producer_task_of(name)
        mt = (self.map_tasks[tid]
              if tid is not None and 0 <= tid < len(self.map_tasks)
              else None)
        if mt is not None and mt.state is not TaskState.COMPLETED:
            return None
        reply = rpc.ReduceNextFileReply(next_file=name, done=False)
        if mt is not None and mt.peer:
            meta = mt.peer.get("parts", {}).get(name.rsplit("-", 1)[1])
            if meta:
                reply.peer_endpoint = str(mt.peer.get("endpoint", ""))
                reply.peer_size = int(meta[0])
                reply.peer_checksum = str(meta[1])
        return reply

    def _report_lost_locked(self, args: rpc.ReduceNextFileArgs) -> bool:
        """Handle a reducer's lost-output report (caller holds the lock):
        re-enqueue the producing map task — its output died with its
        worker, the load-bearing P2P fault path — and charge the vanished
        producer's health record.  Returns True when a task was actually
        re-enqueued (first report wins; later reporters of the same task
        see it already re-running and simply wait).  Only PEER-HELD
        completed outputs are revocable: a relay 404 is a data-plane bug
        the store layer owns, not a lost worker."""
        name = args.lost_file
        tid = _producer_task_of(name)
        if tid is None or not 0 <= tid < len(self.map_tasks):
            log.warning("ignoring lost-output report for %r: not an "
                        "intermediate file name", name)
            return False
        task = self.map_tasks[tid]
        if task.state is not TaskState.COMPLETED or not task.peer:
            return False
        producer = int(task.peer.get("worker", -1))
        log.warning(
            "map task %d output %s lost with its producer (worker %d, "
            "reported by worker %d); re-executing", tid, name, producer,
            args.worker_id,
        )
        task.state = TaskState.UNASSIGNED
        task.peer = None
        task.worker = -1
        task.stamped = False
        self._maps_completed -= 1
        self._map_queue.append(tid)
        task.runnable_at = time.time()
        self.metrics.inc("maps_lost_output")
        self.metrics.inc("map_retries")
        self.metrics.inc("tasks_requeued")
        _C_REQUEUED.inc()
        # SLO counter (round 19): created lazily at this event site so
        # deployments that never lose an output never render the series
        metrics_mod.counter("dgrep_maps_lost_output_total").inc()
        self._event("map_lost_output", task=tid, file=name,
                    producer=producer, reporter=args.worker_id)
        if self.daemon_events is not None:
            # lost-output revocation is a daemon-consequential decision:
            # put it on the fleet timeline too (leaf-lock stage)
            self.daemon_events("map_lost_output", task=tid,
                               producer=producer)
        # the producer demonstrably held committed state and vanished —
        # the direct analogue of the sweeper's attributed timeout
        # (WorkerHealth is a leaf lock, safe here like in the sweeper)
        if producer >= 0:
            window = self.worker_health.record_failure(producer)
            if window > 0:
                self.metrics.inc("workers_quarantined")
                _C_QUARANTINED.inc()
                self._event("quarantine", worker=producer,
                            window_s=round(window, 3))
        # free the REPORTING worker (the caller answers abort=True): its
        # reduce task re-enqueues now — NOT via a sweeper timeout later —
        # so the pool can run the re-executed map without dead time.  The
        # reporter takes no quarantine charge (it did nothing wrong).
        # Current-assignee reports only: a same-life straggler's report
        # re-enqueues the map above but must not yank the task from the
        # worker that legitimately holds it.
        rt = (self.reduce_tasks[args.task_id]
              if 0 <= args.task_id < len(self.reduce_tasks) else None)
        if rt is not None and rt.state is TaskState.IN_PROGRESS and (
            args.worker_id < 0 or rt.worker in (-1, args.worker_id)
        ):
            rt.state = TaskState.UNASSIGNED
            rt.worker = -1
            rt.stamped = False
            self._reduce_queue.append(args.task_id)
            rt.runnable_at = time.time()
            self.metrics.inc("reduce_retries")
            self.metrics.inc("tasks_requeued")
            _C_REQUEUED.inc()
        self._cond.notify_all()
        return True

    # -------------------------------------------------------------- liveness
    def heartbeat(self, task_type: str, task_id: int, grace_s: float = 0.0,
                  args: rpc.HeartbeatArgs | None = None) -> None:
        """UpdateTimestamp (coordinator.go:176-182), plus the grace rider:
        a nonzero grace_s declares a silent phase (cold device compile) so
        the sweeper allows max(task_timeout_s, grace_s) before re-enqueue;
        any later stamp clears it.  Only IN_PROGRESS tasks accept stamps —
        a straggler's late heartbeat must not resurrect a task the sweeper
        already re-enqueued (its eventual completion is still absorbed
        idempotently).

        ``args`` is the full HeartbeatArgs when the transport has one
        (span-pipeline piggyback: buffered spans persist to the event log,
        the metrics snapshot lands in the worker table, and sent_at/rtt_s
        feed the per-worker ClockSync).  The positional form stays for
        direct callers/tests."""
        # receive time FIRST: the offset estimate prices the request
        # transit at rtt/2, so recv_at must be the POST arrival, not
        # arrival + span-persist + lock-wait (a systematic late bias the
        # EWMA could never average away)
        recv_at = time.time()
        if args is not None:
            self._persist_spans(args.spans, args.worker_id, args.spans_seq)
        with self._cond:
            if args is not None:
                self._worker_seen(args.worker_id, metrics=args.metrics)
                self._observe_clock(args, recv_at)
            table = self.map_tasks if task_type == "map" else self.reduce_tasks
            if 0 <= task_id < len(table):
                task = table[task_id]
                if task.state is TaskState.IN_PROGRESS:
                    g = max(0.0, float(grace_s))
                    if args is not None and g > 0 and task.grace_s != g:
                        # only on the transition: a retried grace stamp
                        # (response lost, re-POST) re-declares the same
                        # window and must not duplicate the event
                        self._event("grace_declared", task=task_id,
                                    type=task_type, worker=args.worker_id,
                                    grace_s=g)
                    task.heartbeat(grace_s=g)
                    if args is None or args.worker_id < 0 \
                            or args.worker_id == task.worker:
                        # stamped only by the CURRENT assignee (see
                        # reduce_next_file) — a straggler's pump must not
                        # charge the reassigned worker
                        task.stamped = True
                    self.metrics.inc("heartbeats")
        self._flush_events()

    def _sweep_loop(self) -> None:
        """Failure detector (coordinator.go:97-124): re-enqueue stale tasks."""
        import time as _time

        while True:
            requeued = False
            failed_workers: list[int] = []
            with self._cond:
                if self._stopped or self._done_locked():
                    return
                now = _time.monotonic()
                for task in self.map_tasks:
                    if (
                        task.state is TaskState.IN_PROGRESS
                        and now - task.timestamp
                        >= max(self.task_timeout_s, task.grace_s)
                    ):
                        log.warning("map task %d timed out; re-enqueueing", task.task_id)
                        if (
                            task.stamped or not self.worker_health.polled_since(
                                task.worker, task.timestamp
                            )
                        ) and not getattr(task, "fused_claim", False):
                            # charge only with evidence the worker HELD the
                            # task (a stamp) or is gone (no poll since the
                            # assignment) — an unstamped timeout from a
                            # worker that kept polling is a LOST REPLY, the
                            # network's fault, not the worker's.  Fused
                            # EXTRAS (claim_map_task) are never charged:
                            # K participant schedulers share ONE
                            # WorkerHealth, so one lost fused attempt
                            # would otherwise count as K consecutive
                            # failures and insta-quarantine; the PRIMARY
                            # assignment's timeout carries the one charge.
                            failed_workers.append(task.worker)
                        task.state = TaskState.UNASSIGNED
                        self._map_queue.append(task.task_id)
                        task.runnable_at = time.time()
                        requeued = True
                        self.metrics.inc("map_retries")
                        self.metrics.inc("tasks_requeued")
                        _C_REQUEUED.inc()
                        self._event("task_timeout", type="map",
                                    task=task.task_id, attempt=task.attempts,
                                    worker=task.worker)
                        task.worker = -1
                        self._cond.notify_all()
                for task in self.reduce_tasks:
                    if (
                        task.state is TaskState.IN_PROGRESS
                        and now - task.timestamp
                        >= max(self.task_timeout_s, task.grace_s)
                    ):
                        log.warning("reduce task %d timed out; re-enqueueing", task.task_id)
                        if task.stamped or not self.worker_health.polled_since(
                            task.worker, task.timestamp
                        ):
                            failed_workers.append(task.worker)
                        task.state = TaskState.UNASSIGNED
                        self._reduce_queue.append(task.task_id)
                        task.runnable_at = time.time()
                        requeued = True
                        self.metrics.inc("reduce_retries")
                        self.metrics.inc("tasks_requeued")
                        _C_REQUEUED.inc()
                        self._event("task_timeout", type="reduce",
                                    task=task.task_id, attempt=task.attempts,
                                    worker=task.worker)
                        task.worker = -1
                        self._cond.notify_all()
                # Attribute each charged timeout to the worker that held
                # the task (WorkerHealth is a leaf lock — safe under the
                # scheduler lock, and the quarantine verdict must land
                # before the re-enqueued task is handed back to the same
                # dark worker on the very next poll).  DEDUPED per sweep:
                # one worker going dark is ONE event however many tasks
                # it held — a FUSED attempt (round 13) parks K tasks on
                # one worker, and counting its single death K times
                # would quarantine on the first lost attempt.
                for wid in sorted(set(failed_workers)):
                    window = self.worker_health.record_failure(wid)
                    if window > 0:
                        log.warning(
                            "worker %d quarantined for %.1fs after %d "
                            "consecutive task timeouts", wid, window,
                            QUARANTINE_AFTER_FAILURES,
                        )
                        self.metrics.inc("workers_quarantined")
                        _C_QUARANTINED.inc()
                        self._event("quarantine", worker=wid,
                                    window_s=round(window, 3))
            self._flush_events()
            if requeued:
                self._notify_change()  # re-enqueued work is assignable again
            _time.sleep(self.sweep_interval_s)

    # ------------------------------------------------------------- predicates
    def _map_phase_done_locked(self) -> bool:
        return self._maps_completed == len(self.map_tasks)

    def map_phase_done(self) -> bool:
        with self._lock:
            return self._map_phase_done_locked()

    def _done_locked(self) -> bool:
        return (
            self._map_phase_done_locked()
            and self._reduces_completed == self.n_reduce
        )

    def done(self) -> bool:
        """Pure predicate — no teardown side effects (unlike coordinator.go:291-296)."""
        with self._lock:
            return self._done_locked()

    def backlog(self) -> dict:
        """Live demand snapshot for the service's elastic scale advice
        (round 16): ASSIGNABLE unassigned tasks (reduce tasks count only
        once the map phase is done — they cannot be handed out earlier),
        in-flight tasks, and the oldest in-flight heartbeat age (a
        growing age with idle capacity means stalled recovery, the
        other grow signal)."""
        now = time.monotonic()
        with self._lock:
            unassigned = sum(
                t.state is TaskState.UNASSIGNED for t in self.map_tasks
            )
            if self._map_phase_done_locked():
                unassigned += sum(
                    t.state is TaskState.UNASSIGNED
                    for t in self.reduce_tasks
                )
            in_flight = 0
            oldest = 0.0
            for table in (self.map_tasks, self.reduce_tasks):
                for t in table:
                    if t.state is TaskState.IN_PROGRESS:
                        in_flight += 1
                        oldest = max(oldest, now - t.timestamp)
            return {
                "unassigned": unassigned,
                "in_flight": in_flight,
                "oldest_inflight_age_s": round(oldest, 3),
            }

    def wait_done(self, timeout: Optional[float] = None) -> bool:
        with self._cond:
            return self._cond.wait_for(self._done_locked, timeout=timeout)

    def stop(self) -> None:
        with self._cond:
            self._stopped = True
            self._cond.notify_all()


class _Deadline:
    def __init__(self, timeout: float):
        import time as _time

        self._t = _time.monotonic
        self._deadline = self._t() + timeout

    def remaining(self) -> float:
        return self._deadline - self._t()
