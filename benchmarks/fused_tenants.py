"""K concurrent tenants over one warm corpus: fused vs unfused daemon.

ISSUE 11's acceptance receipt: with cross-tenant scan fusion ON
(DGREP_SERVICE_FUSE=1, the default) K=4 co-running grep jobs over the
same corpus share ONE scan per map split; with it OFF each tenant pays
its own full scan.  This benchmark drives the REAL surface end to end —
ServiceServer HTTP API (POST /jobs, GET /jobs/<id>), one in-process
worker — and reports interleaved A/B medians (this box's background
load swings ~2x; single draws lie):

    python benchmarks/fused_tenants.py [--tenants 4] [--files 4]
        [--file-kb 32768] [--patterns 0] [--reps 5] [--check]

``--check`` additionally asserts the fused legs' outputs are
byte-identical to the unfused legs' (same pattern sets, same corpus —
the unfused daemon is the solo oracle).  Prints exactly ONE JSON line.
"""

from __future__ import annotations

import argparse
import json
import os
import random
import string
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


def _needles(tenant: int, k: int = 4) -> list[str]:
    return [f"needle{tenant}mark{i}x" for i in range(k)]


def _pattern_set(n: int, seed: int, tenant: int) -> list[str]:
    """A tenant's literal set: SELECTIVE queries (8-14 char random
    strings essentially never occur in random text — the log/code-search
    shape, where a query matches a small fraction of the corpus) plus a
    few planted needles so every tenant's output is non-trivial.  Dense
    queries are the anti-regime by construction: fusion trades K full
    scans for one union scan + K confirms over CANDIDATE lines only, so
    its win scales with query selectivity."""
    rng = random.Random(seed)
    out = set(_needles(tenant))
    while len(out) < n:
        out.add("".join(
            rng.choice(string.ascii_lowercase)
            for _ in range(rng.randint(8, 14))
        ))
    return sorted(out)


def _make_corpus(root: Path, n_files: int, file_kb: float, n_tenants: int,
                 seed: int = 7) -> list[str]:
    rng = random.Random(seed)
    words = ["".join(rng.choice(string.ascii_lowercase)
                     for _ in range(rng.randint(3, 9))) for _ in range(400)]
    planted = [n for t in range(n_tenants) for n in _needles(t)]
    files = []
    lineno = 0
    for i in range(n_files):
        p = root / f"in{i:03d}.txt"
        target = int(file_kb * 1024)
        parts = []
        size = 0
        while size < target:
            line = " ".join(rng.choice(words)
                            for _ in range(rng.randint(6, 14)))
            if lineno % 211 == 0:  # ~0.5% of lines carry some needle
                line += " " + planted[(lineno // 211) % len(planted)]
            line += "\n"
            lineno += 1
            parts.append(line)
            size += len(line)
        p.write_text("".join(parts))
        files.append(str(p))
    return files


def _http(method: str, url: str, body: bytes | None = None,
          timeout: float = 30.0) -> dict:
    req = urllib.request.Request(url, data=body, method=method)
    if body is not None:
        req.add_header("Content-Type", "application/json")
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return json.loads(r.read())


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tenants", type=int, default=4)
    # scan-dominated splits by default: fusion removes SCANS, not task
    # commits (each participant still pays its own exactly-once commit
    # protocol — ~10 ms of fsync-bound work per task on this box), so
    # many tiny splits measure the commit floor, not the fusion lever
    ap.add_argument("--files", type=int, default=4)
    ap.add_argument("--file-kb", type=float, default=32768)
    ap.add_argument("--patterns", type=int, default=0,
                    help="literal-set size per tenant; 0 (default) = one "
                         "selective REGEX per tenant — the common tenant "
                         "shape, where solo and union automata are both "
                         "cache-resident and fusion's K-fold scan saving "
                         "shows whole.  Large literal sets still win, but "
                         "less: the union's K-fold-larger AC table falls "
                         "out of L2 and gives part of the saving back")
    ap.add_argument("--reps", type=int, default=5,
                    help="A/B rep pairs; medians reported")
    ap.add_argument("--check", action="store_true",
                    help="assert fused outputs byte-identical to the "
                         "unfused legs' and exit 1 on speedup < 2x")
    args = ap.parse_args()

    from distributed_grep_tpu.runtime.service import GrepService, ServiceServer
    from distributed_grep_tpu.utils.config import JobConfig

    tmp = Path(tempfile.mkdtemp(prefix="dgrep-fused-bench-"))
    corpus_dir = tmp / "corpus"
    corpus_dir.mkdir()
    files = _make_corpus(corpus_dir, args.files, args.file_kb, args.tenants)
    total_mb = sum(os.path.getsize(f) for f in files) / 1e6
    if args.patterns:
        queries = [
            {"patterns": _pattern_set(args.patterns, seed=100 + t, tenant=t)}
            for t in range(args.tenants)
        ]
    else:
        # one selective class-bearing regex per tenant (a pure literal
        # would ride the solo memmem fast path and measure memmem-vs-DFA,
        # not fusion); it matches exactly that tenant's planted needles
        queries = [
            {"pattern": f"needle{t}mark[0-3]x"} for t in range(args.tenants)
        ]

    service = GrepService(
        work_root=tmp / "svc",
        max_jobs=max(4, args.tenants),
    )
    server = ServiceServer(service)
    server.start()
    service.start_local_workers(1)
    base = f"http://127.0.0.1:{server.port}"

    def run_leg(fused: bool) -> tuple[float, list[list[str]]]:
        os.environ["DGREP_SERVICE_FUSE"] = "1" if fused else "0"
        t0 = time.perf_counter()
        jids: list[str] = []
        for t in range(args.tenants):
            cfg = JobConfig(
                input_files=files,
                application="distributed_grep_tpu.apps.grep_tpu",
                app_options={**queries[t], "backend": "cpu"},
                n_reduce=1,
            )
            jids.append(_http(
                "POST", f"{base}/jobs",
                cfg.to_json().encode("utf-8"),
            )["job_id"])
        outs: list[list[str]] = [[] for _ in jids]
        pending = set(range(len(jids)))
        while pending:
            for i in list(pending):
                st = _http("GET", f"{base}/jobs/{jids[i]}")
                state = st.get("state")
                if state == "done":
                    outs[i] = sorted(st["outputs"])
                    pending.discard(i)
                elif state in ("failed", "cancelled"):
                    raise RuntimeError(f"job {jids[i]}: {st}")
            if pending:
                # gentle poll: this box has ONE core — a hot client poll
                # loop steals cycles from the worker it is timing
                time.sleep(0.05)
        return time.perf_counter() - t0, outs

    def read_outputs(paths: list[str]) -> list[bytes]:
        return [Path(p).read_bytes() for p in paths]

    fused_s: list[float] = []
    unfused_s: list[float] = []
    check = "skipped"
    try:
        # one unmeasured warmup pair: model cache + page cache settle
        run_leg(True)
        run_leg(False)
        for rep in range(args.reps):
            fa, fused_outs = run_leg(True)
            fb, unfused_outs = run_leg(False)
            fused_s.append(fa)
            unfused_s.append(fb)
            if args.check and rep == 0:
                for t in range(args.tenants):
                    if read_outputs(fused_outs[t]) != read_outputs(
                        unfused_outs[t]
                    ):
                        print(json.dumps({
                            "bench": "fused_tenants", "error":
                            f"tenant {t} fused != unfused outputs",
                        }))
                        return 1
                check = "ok"
        status = _http("GET", f"{base}/status")
    finally:
        server.shutdown()
        service.stop()

    med = lambda xs: sorted(xs)[len(xs) // 2]  # noqa: E731
    fused_med, unfused_med = med(fused_s), med(unfused_s)
    speedup = unfused_med / fused_med if fused_med else 0.0
    out = {
        "bench": "fused_tenants",
        "tenants": args.tenants,
        "files": args.files,
        "corpus_mb": round(total_mb, 1),
        "patterns_per_tenant": args.patterns or "1 regex",
        "reps": args.reps,
        "fused_s": round(fused_med, 3),
        "unfused_s": round(unfused_med, 3),
        "aggregate_speedup": round(speedup, 2),
        "fused_s_all": [round(x, 3) for x in fused_s],
        "unfused_s_all": [round(x, 3) for x in unfused_s],
        "fusion": status.get("fusion", {}),
        "check": check,
    }
    print(json.dumps(out))
    if args.check and (check != "ok" or speedup < 2.0):
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
