"""Query-routing reports: why a grep was fast (or slow), from telemetry
already on disk.

The span pipeline persists everything an operator needs to answer "which
kernel family ran, was it pruned/fused/cache-warm, where did the time go"
— but only as a raw ``events.jsonl`` an operator had to replay in
Perfetto.  ``assemble()`` folds one job's event log (plus the job
record's planning tallies) into ONE JSON document: engine modes with
bytes/seconds/matches/compiles, host-vs-device routing, index prune
counts, fused attempts, model/corpus cache verdicts, per-stage walls with
their self seconds (a span's seconds less its children's, by the records'
``parent`` links), and task/attempt accounting.  Served as ``GET /jobs/<id>/explain`` by the service daemon
and rendered by ``dgrep explain`` (and ``dgrep submit --explain``).

Pure Python, no ops imports — the daemon control plane assembles reports
without touching the jax stack (the runtime/fusion.py rule).

Every event name this module matches on is declared in
``analysis/events.py EVENTS`` — the authoritative telemetry vocabulary
(``analyze --events`` renders it).  The ``event-registry`` rule audits
both sides: an emit of an undeclared name and a consumer match here that
no emitter produces are both violations, so emitters and this view
cannot drift apart silently.
"""

from __future__ import annotations

from typing import Any

# Engine modes that run on the host by construction; everything else is a
# device kernel family (shift_and / nfa / fdr / pairset / approx / ...).
_HOST_MODES = ("re", "native")

# Instant-event names folded into the routing verdicts.
_CACHE_INSTANTS = {
    "cache:hit": ("model_cache", "hits"),
    "cache:miss": ("model_cache", "misses"),
    "cache:off": ("model_cache", "bypassed"),
    "corpus:hit": ("corpus_cache", "hits"),
    "corpus:miss": ("corpus_cache", "misses"),
}


def _query_view(app_options: dict) -> dict:
    """The query half of the app options — what was asked, not how."""
    out: dict = {}
    if app_options.get("pattern") is not None:
        out["pattern"] = app_options["pattern"]
    pats = app_options.get("patterns")
    if pats:
        out["patterns"] = len(pats)
    for k in ("ignore_case", "invert", "word_regexp", "line_regexp",
              "max_errors", "count_only", "presence_only", "backend"):
        v = app_options.get(k)
        if v:
            out[k] = v
    return out


def summarize_events(events: list[dict]) -> dict:
    """Aggregate one job's span/event records into routing + stage
    views.  Unknown record shapes are skipped — the report must survive
    event logs written by newer/older peers."""
    modes: dict[str, dict] = {}
    stages: dict[str, dict] = {}
    routing: dict[str, dict] = {}
    fusion = {"fused_plans": 0, "fused_attempts": 0, "max_queries": 0}
    index = {"prunes": 0, "bytes_skipped": 0, "maybes": 0}
    result = {"hits": 0, "partial_hits": 0, "misses": 0,
              "splits_reused": 0, "bytes_unscanned": 0, "revalidations": 0}
    shuffle = {"peer_fetches": 0, "peer_bytes": 0, "relay_fetches": 0,
               "relay_fallbacks": 0, "lost_outputs": 0}
    tasks = {"map_assigns": 0, "reduce_assigns": 0, "timeouts": 0,
             "map_commits": 0, "reduce_commits": 0}
    follow = {"solo_wakes": 0, "fused_wakes": 0, "records": 0}
    # seconds of the spans whose parent is each span name: a row's self
    # seconds are its seconds minus those of its direct children
    child_seconds: dict[str, float] = {}
    for r in events:
        name = r.get("name", "")
        t = r.get("t")
        if t == "span":
            args = r.get("args") or {}
            parent = r.get("parent")
            if parent:
                child_seconds[parent] = (child_seconds.get(parent, 0.0)
                                         + float(r.get("dur", 0.0)))
            if name.startswith("scan:"):
                row = modes.setdefault(
                    name[len("scan:"):],
                    {"scans": 0, "bytes": 0, "seconds": 0.0, "matches": 0,
                     "compiles": 0},
                )
                row["scans"] += 1
                row["bytes"] += int(args.get("bytes", 0))
                row["seconds"] += float(r.get("dur", 0.0))
                row["matches"] += int(args.get("matches", 0))
                # backend compiles the scans waited on (scan_span)
                row["compiles"] += int(args.get("compiles", 0))
                # device scans: boundary lines re-matched on the host, the
                # host matcher passes of stitch and confirm together, and
                # the result fetch's nonzero blocks and whole-plane downloads
                for k in ("stitch_lines", "host_line_passes",
                          "fetch_blocks", "fetch_overflows"):
                    if k in args:
                        row[k] = row.get(k, 0) + int(args[k])
                # which engine ran the scan (utils/spans.scan_record):
                # pallas / xla on the device, host for the host scanners
                kernel = args.get("kernel")
                if kernel:
                    kernels = row.setdefault("kernels", {})
                    kernels[kernel] = kernels.get(kernel, 0) + 1
                    kbytes = row.setdefault("kernel_bytes", {})
                    kbytes[kernel] = (kbytes.get(kernel, 0)
                                      + int(args.get("bytes", 0)))
                if args.get("interpret"):
                    row["interpret"] = row.get("interpret", 0) + 1
            else:
                row = stages.setdefault(name, {"count": 0, "seconds": 0.0})
                row["count"] += 1
                row["seconds"] += float(r.get("dur", 0.0))
        elif t == "instant":
            hit = _CACHE_INSTANTS.get(name)
            if hit is not None:
                group, key = hit
                routing.setdefault(group, {})[key] = (
                    routing.get(group, {}).get(key, 0) + 1
                )
            elif name == "index:prune":
                index["prunes"] += 1
                index["bytes_skipped"] += int(
                    (r.get("args") or {}).get("bytes", 0)
                )
            elif name == "index:maybe":
                index["maybes"] += 1
            elif name in ("result:hit", "result:partial"):
                args = r.get("args") or {}
                key = "hits" if name == "result:hit" else "partial_hits"
                result[key] += 1
                result["splits_reused"] += int(args.get("splits_reused", 0))
                result["bytes_unscanned"] += int(
                    args.get("bytes_unscanned", 0)
                )
            elif name == "result:miss":
                result["misses"] += 1
            elif name == "result:revalidate":
                result["revalidations"] += 1
            elif name == "fuse:plan":
                fusion["fused_plans"] += 1
                fusion["max_queries"] = max(
                    fusion["max_queries"],
                    int((r.get("args") or {}).get("queries", 0)),
                )
            elif name == "fuse:split":
                fusion["fused_attempts"] += 1
            elif name in ("follow:wake", "fuse:wake"):
                # streaming tier: which wake loop served this standing
                # query — its own solo runner or a fused group (round 21)
                key = "solo_wakes" if name == "follow:wake" else "fused_wakes"
                follow[key] += 1
                follow["records"] += int(
                    (r.get("args") or {}).get("records", 0)
                )
            elif name == "shuffle:peer":
                shuffle["peer_fetches"] += 1
                shuffle["peer_bytes"] += int(
                    (r.get("args") or {}).get("bytes", 0)
                )
            elif name == "shuffle:relay":
                if (r.get("args") or {}).get("fallback"):
                    shuffle["relay_fallbacks"] += 1
                else:
                    shuffle["relay_fetches"] += 1
            elif name == "map_lost_output":
                shuffle["lost_outputs"] += 1
            elif name == "assign_map":
                tasks["map_assigns"] += 1
            elif name == "assign_reduce":
                tasks["reduce_assigns"] += 1
            elif name == "task_timeout":
                tasks["timeouts"] += 1
            elif name == "map_committed":
                tasks["map_commits"] += 1
            elif name == "reduce_committed":
                tasks["reduce_commits"] += 1
    for mode, row in modes.items():
        row["self_seconds"] = round(
            row["seconds"] - child_seconds.get(f"scan:{mode}", 0.0), 6)
        row["seconds"] = round(row["seconds"], 6)
    for name, row in stages.items():
        row["self_seconds"] = round(
            row["seconds"] - child_seconds.get(name, 0.0), 6)
        row["seconds"] = round(row["seconds"], 6)
    out: dict = {"modes": modes, "stages": stages, "tasks": tasks}
    out.update(routing)  # model_cache / corpus_cache, present when seen
    if any(fusion.values()):
        out["fusion"] = fusion
    if any(index.values()):
        out["index"] = index
    if any(result.values()):
        # query-result cache (round 20): was this job answered from
        # stored results, wholly or incrementally?  Nonzero-only — a
        # cache-free job's report keeps its pre-round-20 shape.
        out["result_cache"] = result
    if follow["solo_wakes"] or follow["fused_wakes"]:
        # standing-query route verdict: fused means every wake came from
        # a group's shared scan; mixed marks a catch-up/demotion mid-run
        follow["route"] = (
            "fused" if follow["fused_wakes"] and not follow["solo_wakes"]
            else "solo" if follow["solo_wakes"] and not follow["fused_wakes"]
            else "mixed"
        )
        out["follow"] = follow
    if any(shuffle.values()):
        # shuffle route verdict (peer-to-peer shuffle, round 16): which
        # data plane the job's reduce fetches actually rode
        peer_n = shuffle["peer_fetches"]
        relay_n = shuffle["relay_fetches"] + shuffle["relay_fallbacks"]
        shuffle["route"] = (
            "peer" if peer_n and not relay_n
            else "relay" if relay_n and not peer_n
            else "mixed"
        )
        out["shuffle"] = shuffle
    return out


def disruptions_view(daemon_events: list[dict], job_id: str,
                     submitted_at: float | None = None,
                     finished_at: float | None = None) -> dict:
    """Daemon-scope disruptions overlapping one job's lifetime, from the
    fleet timeline (runtime/daemon_log.py): quarantine episodes, this
    job's lost-output revocations, daemon restarts and failovers that
    happened while the job was live.  Nonzero-only — an undisturbed
    job's report keeps its pre-round-19 shape."""
    if not daemon_events:
        return {}
    lo = submitted_at or 0.0
    hi = finished_at if finished_at else float("inf")
    out = {"quarantines": 0, "lost_outputs": 0, "daemon_restarts": 0,
           "failovers": 0}
    max_failover = 0.0
    for r in daemon_events:
        kind = r.get("kind")
        payload = r.get("payload") or {}
        ts = float(r.get("ts", 0.0))
        if kind == "map_lost_output":
            # job-tagged: the revocation names its tenant directly
            if payload.get("job") == job_id:
                out["lost_outputs"] += 1
        elif kind == "quarantine":
            if lo <= ts <= hi:
                out["quarantines"] += 1
        elif kind in ("start", "resume"):
            # strictly after submit: the boot that ADMITTED the job is
            # not a disruption, a restart mid-job is
            if lo < ts <= hi:
                out["daemon_restarts"] += 1
        elif kind == "promoted":
            if lo < ts <= hi:
                out["failovers"] += 1
                max_failover = max(max_failover,
                                   float(payload.get("failover_s", 0.0)))
    view = {k: v for k, v in out.items() if v}
    if max_failover:
        view["max_failover_s"] = round(max_failover, 6)
    return view


def _route_verdict(modes: dict[str, dict]) -> str:
    """host / device / mixed / unknown — the one-word answer.
    ``scan:batch`` rows are EXCLUDED: a packed flush emits one batch span
    AND the inner engine's own ``scan:<mode>`` span, so the batch row is
    an envelope, not a route — counting it would report a pure-device
    batched job as "mixed"."""
    scored = {name: m for name, m in modes.items()
              if not name.startswith("batch")}
    if not scored:
        return "unknown"
    host = device = 0
    for name, m in scored.items():
        if name in _HOST_MODES:
            host += m["scans"]
            continue
        # a device-mode engine can still host-scan a small input
        on_host = (m.get("kernels") or {}).get("host", 0)
        host += on_host
        device += m["scans"] - on_host
    if host and device:
        return "mixed"
    return "device" if device else "host"


def assemble(
    job_id: str,
    config: Any,
    state: str,
    submitted_at: float | None,
    started_at: float | None,
    finished_at: float | None,
    metrics_counters: dict,
    events: list[dict],
    index_shards_pruned: int = 0,
    index_bytes_skipped: int = 0,
    result_splits_reused: int = 0,
    result_bytes_unscanned: int = 0,
    result_revalidations: int = 0,
    daemon_events: list[dict] | None = None,
) -> dict:
    """One job's routing report.  ``config`` is the JobConfig (only the
    application spec and app options are read); ``metrics_counters`` the
    job Metrics piggyback snapshot; planner-side index tallies come from
    the JobRecord (they fire at submit, before any worker span);
    ``daemon_events`` (the fleet timeline, when the daemon log is on)
    feeds the nonzero-only ``disruptions`` section."""
    agg = summarize_events(events)
    modes = agg.pop("modes")
    stages = agg.pop("stages")
    tasks = agg.pop("tasks")
    timing: dict = {}
    if submitted_at and started_at:
        timing["queue_wait_s"] = round(started_at - submitted_at, 6)
    if started_at and finished_at:
        timing["run_s"] = round(finished_at - started_at, 6)
    if submitted_at and finished_at:
        timing["e2e_s"] = round(finished_at - submitted_at, 6)

    routing: dict = {
        "route": _route_verdict(modes),
        "engine_modes": modes,
        **agg,  # model_cache/corpus_cache/fusion/index when seen
    }
    # planner-side prune tallies (fire before any worker span exists);
    # merge over the event view, which only sees engine-side prunes
    if index_shards_pruned:
        idx = routing.setdefault("index", {})
        idx["planner_shards_pruned"] = index_shards_pruned
        idx["planner_bytes_skipped"] = index_bytes_skipped
    # result-cache planner tallies (JobRecord fields — spans-off jobs
    # still report them; with spans on they merge over the instant view)
    if result_splits_reused or result_revalidations:
        res = routing.setdefault("result_cache", {})
        if result_splits_reused:
            res["planner_splits_reused"] = result_splits_reused
            res["planner_bytes_unscanned"] = result_bytes_unscanned
        if result_revalidations:
            res["planner_revalidations"] = result_revalidations

    counters = {
        k: v for k, v in sorted((metrics_counters or {}).items()) if v
    }
    disruptions = disruptions_view(
        daemon_events or [], job_id,
        submitted_at=submitted_at, finished_at=finished_at,
    )
    return {
        "job_id": job_id,
        "state": state,
        "application": getattr(config, "application", ""),
        "query": _query_view(getattr(config, "app_options", {}) or {}),
        "timing": timing,
        "routing": routing,
        "stages": stages,
        "tasks": tasks,
        "metrics": counters,
        # daemon-scope disruptions that overlapped this job (quarantine,
        # lost outputs, restarts/failovers) — nonzero-only, so a quiet
        # job's report keeps its pre-round-19 shape
        **({"disruptions": disruptions} if disruptions else {}),
        # spans off = a skeleton report; say so instead of reading empty
        "spans": bool(events),
    }
