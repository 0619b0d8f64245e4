"""Task state tables — the coordinator's core bookkeeping.

Mirrors the reference's task model: state enum Unassigned/InProgress/
Completed (helper_types.go:144-148), per-map-task {state, timestamp, file}
(MapData, helper_types.go:150-154) and per-reduce-task {state, timestamp,
registered intermediate files} (ReduceData, helper_types.go:156-161).
Timestamps drive the 10s failure detector (coordinator.go:97-124).
Tasks — not workers — are the tracked entities; workers join implicitly by
asking for work (a genuine elasticity capability of the reference design).
"""

from __future__ import annotations

import enum
import time
from dataclasses import dataclass, field


class TaskType(enum.Enum):
    MAP = "map"
    REDUCE = "reduce"


class TaskState(enum.Enum):
    UNASSIGNED = "unassigned"
    IN_PROGRESS = "in_progress"
    COMPLETED = "completed"


@dataclass
class MapTask:
    task_id: int
    file: str  # single-file tasks: the input path; batched splits: the
    # split's display label (scheduler._split_label) — ``files`` then
    # carries the member paths
    state: TaskState = TaskState.UNASSIGNED
    timestamp: float = 0.0  # heartbeat; stamped at assignment + mid-task
    attempts: int = 0
    # One-shot extension of the sweep window, declared by a heartbeat ahead
    # of a known-long silent phase (a cold device compile blocks 20-40 s
    # with no observable progress).  Any later stamp resets it to 0, so
    # steady-state failure detection keeps the plain task_timeout_s — the
    # grace bounds only the declared window (VERDICT r3 item 3).
    grace_s: float = 0.0
    # Member files of a batched multi-file split (cross-file device
    # batching, runtime/job.plan_map_splits); () for ordinary tasks.
    files: tuple[str, ...] = ()
    # Worker holding the current attempt (-1 = none): lets the timeout
    # sweeper attribute the failure to the worker that went silent, the
    # input of the quarantine tracker (scheduler.WorkerHealth).
    worker: int = -1
    # True once the WORKER stamped this attempt (mid-task heartbeat /
    # shuffle fetch) — proof it actually received the assignment.  An
    # unstamped timeout might be a LOST ASSIGNMENT REPLY, not a dark
    # worker; the sweeper then charges the worker only if it also never
    # polled again (scheduler._sweep_loop).
    stamped: bool = False
    # True while the current attempt was claimed as a fused EXTRA
    # (Scheduler.claim_map_task, cross-tenant scan fusion): its timeout
    # is never charged to WorkerHealth — K participant schedulers share
    # one health tracker, and a single lost fused attempt must count as
    # ONE dark-worker event (the primary assignment's charge), not K.
    fused_claim: bool = False
    # Peer-to-peer shuffle (round 16, runtime/peer.py): where this map
    # task's committed output lives when it was spooled on the PRODUCING
    # worker instead of the coordinator — {"endpoint": "http://host:port",
    # "worker": service worker id, "parts": {partition: [size, crc32hex]}}.
    # None on relay commits (bytes on the coordinator, pre-peer behavior).
    # Cleared when a lost-output report re-enqueues the task.
    peer: dict | None = None
    # Wall clock at which the task last became runnable (enqueue or
    # re-enqueue); 0 once assigned — the start of its map:wait span.
    runnable_at: float = 0.0

    def heartbeat(self, grace_s: float = 0.0) -> None:
        self.timestamp = time.monotonic()
        self.grace_s = grace_s


@dataclass
class ReduceTask:
    task_id: int
    state: TaskState = TaskState.UNASSIGNED
    timestamp: float = 0.0
    attempts: int = 0
    grace_s: float = 0.0  # see MapTask.grace_s
    worker: int = -1  # see MapTask.worker (quarantine attribution)
    stamped: bool = False  # see MapTask.stamped
    # Intermediate files registered as map tasks commit; reducers stream these
    # in arrival order (the pipelined shuffle, coordinator.go:159-174).
    task_files: list[str] = field(default_factory=list)
    # Wall clock at which the task last became runnable (map-phase
    # completion or re-enqueue); 0 once assigned — see MapTask.
    runnable_at: float = 0.0

    def heartbeat(self, grace_s: float = 0.0) -> None:
        self.timestamp = time.monotonic()
        self.grace_s = grace_s
