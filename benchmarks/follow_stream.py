"""Standing-query streaming receipt (round 17, runtime/follow.py).

An appender thread grows a log file while a follow job stands over it on
a real daemon (ServiceServer HTTP API: POST /jobs with follow=true, then
GET /jobs/<id>/stream driven with a moving cursor).  Reports exactly ONE
JSON line: matched lines/s through the stream, and append-to-emit
latency p50/p95 (per appended batch: the wall from the append's flush to
the stream reply that carried its lines — poll cadence + suffix scan +
long-poll delivery, the whole wake path).

    python benchmarks/follow_stream.py [--lines 4000] [--batch 50]
        [--append-hz 40] [--poll-s 0.05] [--check]

``--check`` additionally pins the exactness contract: the streamed
(line, text) set must equal a one-shot engine scan over the FINAL file
bytes (the oracle every follow test pins — append boundaries, the
mid-line split carry, and the unterminated tail must all be invisible).

Fused-tier receipt (round 21): ``--tenants K`` stands K queries (one
follow job each, distinct per-tenant marker patterns) over ONE appended
log and A/B-interleaves the fused daemon (DGREP_FOLLOW_FUSE on — all K
ride one group wake: one stat + one union suffix scan per (file, wake))
against DGREP_FOLLOW_FUSE=0 (K solo wake loops, each re-reading the same
appended bytes), ``--reps`` rounds each, reporting per-leg medians in
the one JSON line.  ``--check`` then gates (a) per-tenant exactness:
every tenant's streamed set equals its own one-shot oracle over the
final bytes, both legs, zero drops; (b) counter flatness: the fused
leg's suffix_bytes_scanned stays within 1.25x of the final file size
(K=1's floor — each appended byte consumed ONCE for the whole group)
while the unfused leg pays ~K x.  Aggregate lines/s and p95
append-to-emit ratios are REPORTED, not gated (this box's load swings
2x — compare medians across runs, CLAUDE.md round 12).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import threading
import time
import urllib.request
from pathlib import Path

# Runnable as `python benchmarks/...` from anywhere: the repo root joins
# the FRONT of sys.path so the checkout being benchmarked always wins.
_root = Path(__file__).resolve().parent
if not (_root / "distributed_grep_tpu").is_dir():
    _root = _root.parent
if (_root / "distributed_grep_tpu").is_dir():
    sys.path.insert(0, str(_root))

# CPU-pinned (CLAUDE.md environment rules): ASSIGN, never setdefault.
os.environ["JAX_PLATFORMS"] = "cpu"
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")


def _pct(xs: list[float], q: float) -> float:
    if not xs:
        return 0.0
    xs = sorted(xs)
    i = min(len(xs) - 1, max(0, int(round(q * (len(xs) - 1)))))
    return xs[i]


def _tenant_pattern(k: int) -> str:
    return f"t{k:02d}x"


def _tenant_line(ln: int, tenants: int) -> bytes:
    mark = _tenant_pattern(ln % tenants).encode()
    return b"hello line %d %s payload\n" % (ln, mark)


def _oracle(pattern: str, final: bytes) -> dict[int, str]:
    """0-based line index -> text for a one-shot scan of the final bytes."""
    from distributed_grep_tpu.ops import lines as lines_mod
    from distributed_grep_tpu.ops.engine import GrepEngine

    eng = GrepEngine(pattern, backend="cpu")
    res = eng.scan(final)
    nl = lines_mod.newline_index(final)
    want = {}
    for ln in res.matched_lines.tolist():
        s, e = lines_mod.line_span(nl, int(ln), len(final))
        want[int(ln) - 1] = final[s:e].decode("utf-8", "surrogateescape")
    return want


def _run_multi_leg(args, fuse_on: bool):
    """One daemon lifecycle: K follow tenants over one appended log.
    Returns (wall_s, latency samples, per-tenant streamed dicts, final
    bytes, /status follow view, dropped)."""
    import importlib

    from distributed_grep_tpu.runtime import follow as follow_mod
    from distributed_grep_tpu.runtime.service import GrepService, ServiceServer
    from distributed_grep_tpu.utils.config import JobConfig

    importlib.invalidate_caches()
    os.environ["DGREP_FOLLOW_FUSE"] = "1" if fuse_on else "0"
    # one group must host every tenant (the registry cap defaults to 8)
    os.environ["DGREP_FUSE_MAX_QUERIES"] = str(max(2, args.tenants))
    # a follow job holds a running slot for its lifetime — K standing
    # tenants need K concurrent admissions (the daemon default is 4)
    os.environ["DGREP_SERVICE_MAX_JOBS"] = str(max(4, args.tenants))
    follow_mod.follow_counters_clear()
    follow_mod.follow_fused_counters_clear()

    root = Path(tempfile.mkdtemp(prefix="dgrep-follow-ab-"))
    log_path = root / "app.log"
    log_path.write_bytes(b"")

    service = GrepService(work_root=root / "svc")
    server = ServiceServer(service)
    server.start()
    base = f"http://127.0.0.1:{server.port}"

    def call(method: str, path: str, body: bytes | None = None,
             timeout: float = 30.0) -> dict:
        req = urllib.request.Request(f"{base}{path}", data=body,
                                     method=method)
        if body is not None:
            req.add_header("Content-Type", "application/json")
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return json.loads(r.read())

    jids = []
    for k in range(args.tenants):
        cfg = JobConfig(
            input_files=[str(log_path)],
            application="distributed_grep_tpu.apps.grep_tpu",
            app_options={"pattern": _tenant_pattern(k), "backend": "cpu"},
            work_dir="ignored",
            follow=True,
            follow_poll_s=args.poll_s,
        )
        jids.append(call("POST", "/jobs",
                         cfg.to_json().encode("utf-8"))["job_id"])

    total = max(args.tenants, args.lines)
    n_batches = max(1, total // args.batch)
    total = n_batches * args.batch
    flush_t: dict[int, float] = {}
    period = 1.0 / args.append_hz if args.append_hz > 0 else 0.0

    def appender() -> None:
        ln = 0
        with open(log_path, "ab") as f:
            for _b in range(n_batches):
                chunk = b"".join(
                    _tenant_line(ln + i, args.tenants)
                    for i in range(args.batch)
                )
                if _b % 2 == 0:  # mid-line split carry, as in the K=1 leg
                    f.write(chunk[:-9])
                    f.flush()
                    f.write(chunk[-9:])
                else:
                    f.write(chunk)
                f.flush()
                t = time.perf_counter()
                for i in range(args.batch):
                    flush_t[ln + i] = t
                ln += args.batch
                if period:
                    time.sleep(period)

    expected = [len([1 for ln in range(total) if ln % args.tenants == k])
                for k in range(args.tenants)]
    streamed: list[dict[int, str]] = [{} for _ in range(args.tenants)]
    latency: list[float] = []
    dropped = [0] * args.tenants
    lat_lock = threading.Lock()
    done_t = [0.0] * args.tenants

    def drain(k: int) -> None:
        cursor = 0
        deadline = time.monotonic() + 180.0
        while len(streamed[k]) < expected[k]:
            if time.monotonic() > deadline:
                raise TimeoutError(
                    f"tenant {k} stuck at {len(streamed[k])}/{expected[k]}")
            r = call("GET",
                     f"/jobs/{jids[k]}/stream?cursor={cursor}&timeout=5")
            now = time.perf_counter()
            cursor = int(r.get("next", cursor))
            dropped[k] += int(r.get("dropped", 0))
            for rec in r.get("records") or []:
                idx = rec["line"] - 1
                streamed[k][idx] = rec["text"]
                if idx in flush_t:
                    with lat_lock:
                        latency.append(now - flush_t[idx])
        done_t[k] = time.perf_counter()

    drains = [threading.Thread(target=drain, args=(k,))
              for k in range(args.tenants)]
    t_app = threading.Thread(target=appender)
    t0 = time.perf_counter()
    t_app.start()
    for t in drains:
        t.start()
    for t in drains:
        t.join()
    wall = max(done_t) - t0
    t_app.join()

    final = log_path.read_bytes()
    status = call("GET", "/status")
    for jid in jids:
        call("POST", f"/jobs/{jid}/cancel", b"")
    service.stop()
    server.shutdown()
    return wall, latency, streamed, final, status.get("follow", {}), sum(dropped)


def _median(xs: list[float]) -> float:
    xs = sorted(xs)
    return xs[len(xs) // 2] if xs else 0.0


def run_multi(args) -> int:
    """Interleaved A/B: fused daemon vs DGREP_FOLLOW_FUSE=0, K tenants."""
    legs = {"fused": [], "unfused": []}
    checks_ok = True
    flat_ok = True
    for _rep in range(args.reps):
        for name, fuse_on in (("fused", True), ("unfused", False)):
            wall, lat, streamed, final, fol, dropped = _run_multi_leg(
                args, fuse_on)
            n = sum(len(s) for s in streamed)
            legs[name].append({
                "wall": wall,
                "lines_per_s": n / wall if wall else 0.0,
                "p50": _pct(lat, 0.50), "p95": _pct(lat, 0.95),
                "wakes": int(fol.get("follow_wakes", 0)),
                "suffix_bytes": int(fol.get("suffix_bytes_scanned", 0)),
                "fused_wakes": int(fol.get("follow_fused_wakes", 0)),
                "bytes_saved": int(fol.get("follow_suffix_bytes_saved", 0)),
                "final_bytes": len(final),
            })
            if args.check:
                for k in range(args.tenants):
                    want = _oracle(_tenant_pattern(k), final)
                    if streamed[k] != want:
                        checks_ok = False
                if dropped:
                    checks_ok = False
                if fuse_on:
                    # flatness: the group consumed each appended byte ONCE
                    # regardless of K (K=1's floor is the file size)
                    suffix = int(fol.get("suffix_bytes_scanned", 0))
                    if suffix > 1.25 * len(final):
                        flat_ok = False

    fused = legs["fused"]
    unfused = legs["unfused"]
    lps_f = _median([leg["lines_per_s"] for leg in fused])
    lps_u = _median([leg["lines_per_s"] for leg in unfused])
    p95_f = _median([leg["p95"] for leg in fused])
    p95_u = _median([leg["p95"] for leg in unfused])
    ok = checks_ok and flat_ok
    rec = {
        "bench": "follow_stream_fused",
        "tenants": args.tenants,
        "lines": max(args.tenants, args.lines),
        "poll_s": args.poll_s,
        "reps": args.reps,
        "fused": {
            "lines_per_s": round(lps_f, 1),
            "latency_p50_ms": round(_median([leg["p50"] for leg in fused]) * 1e3, 2),
            "latency_p95_ms": round(p95_f * 1e3, 2),
            "follow_wakes": fused[-1]["wakes"],
            "suffix_bytes_scanned": fused[-1]["suffix_bytes"],
            "follow_fused_wakes": fused[-1]["fused_wakes"],
            "follow_suffix_bytes_saved": fused[-1]["bytes_saved"],
        },
        "unfused": {
            "lines_per_s": round(lps_u, 1),
            "latency_p50_ms": round(_median([leg["p50"] for leg in unfused]) * 1e3, 2),
            "latency_p95_ms": round(p95_u * 1e3, 2),
            "follow_wakes": unfused[-1]["wakes"],
            "suffix_bytes_scanned": unfused[-1]["suffix_bytes"],
        },
        "final_bytes": fused[-1]["final_bytes"],
        "suffix_bytes_ratio": round(
            unfused[-1]["suffix_bytes"] / max(1, fused[-1]["suffix_bytes"]), 2),
        "lines_per_s_ratio": round(lps_f / lps_u, 2) if lps_u else 0.0,
        "p95_ratio": round(p95_f / p95_u, 2) if p95_u else 0.0,
        **({"check": "ok" if ok else "FAIL"} if args.check else {}),
    }
    print(json.dumps(rec))  # exactly one JSON line
    return 0 if ok else 1


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--lines", type=int, default=4000,
                    help="matched lines to append in total")
    ap.add_argument("--batch", type=int, default=50,
                    help="lines per append flush (one latency sample each)")
    ap.add_argument("--append-hz", type=float, default=40.0,
                    help="append flushes per second (0 = as fast as possible)")
    ap.add_argument("--poll-s", type=float, default=0.05,
                    help="standing-query wake cadence (DGREP_FOLLOW_POLL_S)")
    ap.add_argument("--tenants", type=int, default=1,
                    help=">1 = fused-tier A/B: K standing queries over one "
                         "appender, fused vs DGREP_FOLLOW_FUSE=0")
    ap.add_argument("--reps", type=int, default=2,
                    help="A/B rounds per leg in --tenants mode (medians)")
    ap.add_argument("--check", action="store_true",
                    help="exit 1 unless the streamed set equals the "
                         "one-shot oracle over the final file bytes")
    args = ap.parse_args()

    os.environ["DGREP_FOLLOW_POLL_S"] = str(args.poll_s)
    if args.tenants > 1:
        return run_multi(args)

    from distributed_grep_tpu.runtime.service import GrepService, ServiceServer
    from distributed_grep_tpu.utils.config import JobConfig

    root = Path(tempfile.mkdtemp(prefix="dgrep-follow-"))
    log_path = root / "app.log"
    log_path.write_bytes(b"")

    service = GrepService(work_root=root / "svc")
    server = ServiceServer(service)
    server.start()
    base = f"http://127.0.0.1:{server.port}"

    def call(method: str, path: str, body: bytes | None = None,
             timeout: float = 30.0) -> dict:
        req = urllib.request.Request(f"{base}{path}", data=body,
                                     method=method)
        if body is not None:
            req.add_header("Content-Type", "application/json")
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return json.loads(r.read())

    cfg = JobConfig(
        input_files=[str(log_path)],
        application="distributed_grep_tpu.apps.grep_tpu",
        app_options={"pattern": "hello", "backend": "cpu"},
        work_dir="ignored",
        follow=True,
    )
    jid = call("POST", "/jobs", cfg.to_json().encode("utf-8"))["job_id"]

    n_batches = max(1, args.lines // args.batch)
    # per appended line: perf_counter at the flush that made it visible
    flush_t: dict[int, float] = {}
    period = 1.0 / args.append_hz if args.append_hz > 0 else 0.0

    def appender() -> None:
        ln = 0
        with open(log_path, "ab") as f:
            for _b in range(n_batches):
                chunk = b"".join(
                    b"hello line %d payload xyz\n" % (ln + i)
                    for i in range(args.batch)
                )
                # mid-line split carry exercised every other batch: the
                # next flush completes the torn line (the streamed set
                # must still equal the oracle — --check pins it)
                if _b % 2 == 0:
                    f.write(chunk[:-9])
                    f.flush()
                    f.write(chunk[-9:])
                else:
                    f.write(chunk)
                f.flush()
                t = time.perf_counter()
                for i in range(args.batch):
                    flush_t[ln + i] = t
                ln += args.batch
                if period:
                    time.sleep(period)

    t_app = threading.Thread(target=appender)
    t0 = time.perf_counter()
    t_app.start()

    streamed: dict[int, str] = {}  # 0-based appended index -> text
    latency: list[float] = []
    cursor = 0
    dropped = 0
    deadline = time.monotonic() + 120.0
    while len(streamed) < n_batches * args.batch:
        if time.monotonic() > deadline:
            raise TimeoutError(
                f"stream stuck at {len(streamed)}/{n_batches * args.batch}"
            )
        r = call("GET", f"/jobs/{jid}/stream?cursor={cursor}&timeout=5")
        now = time.perf_counter()
        cursor = int(r.get("next", cursor))
        dropped += int(r.get("dropped", 0))
        for rec in r.get("records") or []:
            idx = rec["line"] - 1
            streamed[idx] = rec["text"]
            if idx in flush_t:
                latency.append(now - flush_t[idx])
    wall = time.perf_counter() - t0
    t_app.join()

    final = log_path.read_bytes()
    status = call("GET", "/status")
    call("POST", f"/jobs/{jid}/cancel", b"")
    service.stop()
    server.shutdown()

    ok = True
    if args.check:
        # oracle: a one-shot engine scan of the final file state — the
        # streamed emissions across every wake must equal it exactly
        from distributed_grep_tpu.ops import lines as lines_mod
        from distributed_grep_tpu.ops.engine import GrepEngine

        eng = GrepEngine("hello", backend="cpu")
        res = eng.scan(final)
        nl = lines_mod.newline_index(final)
        want = {}
        for ln in res.matched_lines.tolist():
            s, e = lines_mod.line_span(nl, int(ln), len(final))
            # span end excludes the newline
            want[int(ln) - 1] = final[s:e].decode("utf-8", "surrogateescape")
        ok = streamed == want and dropped == 0

    fol = status.get("follow", {})
    rec = {
        "bench": "follow_stream",
        "lines": n_batches * args.batch,
        "batch": args.batch,
        "poll_s": args.poll_s,
        "wall_s": round(wall, 4),
        "lines_per_s": round(len(streamed) / wall, 1) if wall else 0.0,
        "latency_p50_ms": round(_pct(latency, 0.50) * 1e3, 2),
        "latency_p95_ms": round(_pct(latency, 0.95) * 1e3, 2),
        "follow_wakes": int(fol.get("follow_wakes", 0)),
        "suffix_bytes_scanned": int(fol.get("suffix_bytes_scanned", 0)),
        "dropped": dropped,
        **({"check": "ok" if ok else "FAIL"} if args.check else {}),
    }
    print(json.dumps(rec))  # exactly one JSON line
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
