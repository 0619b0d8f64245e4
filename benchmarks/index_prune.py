"""Shard-index routing against the grep service: warm selective queries
cost O(matching shards), not O(corpus).

ISSUE 12's acceptance bar: once shard summaries exist, a sparse-hit warm
query must beat the unindexed warm path by >= 5x (pruned shards are never
opened, never dispatched — the planner drops their map tasks), while a
dense-hit query (every shard a maybe) pays only the summary lookups.

    python benchmarks/index_prune.py [--files 48] [--file-mb 2]
        [--reps 3] [--check]

Drives the REAL surface end to end: ServiceServer HTTP API, one
in-process worker, indexed vs DGREP_INDEX=0 submits INTERLEAVED (this
box's background load swings single draws ±2x — medians over alternating
reps are the honest comparison; BASELINE.md (git history) round-8 note).  The sparse
query's needle lives in exactly one shard; the dense query's word is on
every line of every shard.  Prints exactly ONE JSON line.  ``--check``
exits 1 unless indexed and unindexed outputs are byte-identical for both
queries AND the sparse speedup clears 5x.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import tempfile
import time
import urllib.request
from pathlib import Path

_root = Path(__file__).resolve().parent
if not (_root / "distributed_grep_tpu").is_dir():
    _root = _root.parent
if (_root / "distributed_grep_tpu").is_dir():
    sys.path.insert(0, str(_root))

# CPU-pinned (CLAUDE.md environment rules): ASSIGN, never setdefault.
os.environ["JAX_PLATFORMS"] = "cpu"
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402

WORDS = (
    "the of and to in a is that for it as was with be by on not he this "
    "are at from or have an they which one you were all her she there "
    "would filler wikipedia philosophy"
).split()


def write_corpus(root: Path, n_files: int, file_bytes: int,
                 needle: bytes, seed: int = 9) -> list[Path]:
    """English-like shards; the needle lands in EXACTLY ONE (the sparse-
    hit shape: one shard matches, the rest are provably clean)."""
    rng = np.random.default_rng(seed)
    paths = []
    for i in range(n_files):
        lines, n = [], 0
        while n < file_bytes:
            k = int(rng.integers(3, 12))
            line = b" ".join(
                WORDS[int(rng.integers(0, len(WORDS)))].encode()
                for _ in range(k)
            )
            lines.append(line)
            n += len(line) + 1
        blob = b"\n".join(lines)[:file_bytes - 1] + b"\n"
        if i == n_files // 2:
            blob = needle + b"\n" + blob[len(needle) + 1:]
        p = root / f"f{i:05d}.txt"
        p.write_bytes(blob)
        paths.append(p)
    return paths


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--files", type=int, default=48)
    ap.add_argument("--file-mb", type=float, default=2.0)
    ap.add_argument("--sparse-pattern", default="zzyzxneedle")
    ap.add_argument("--dense-pattern", default="filler")
    ap.add_argument("--reps", type=int, default=3,
                    help="interleaved A/B reps per query; MEDIANS reported")
    ap.add_argument("--check", action="store_true",
                    help="exit 1 unless outputs identical and sparse "
                         "speedup >= 5x")
    args = ap.parse_args()

    from distributed_grep_tpu.runtime.service import (
        GrepService,
        ServiceServer,
    )
    from distributed_grep_tpu.utils.config import JobConfig

    root = Path(tempfile.mkdtemp(prefix="dgrep-index-prune-"))
    (root / "in").mkdir()
    file_bytes = int(args.file_mb * (1 << 20))
    paths = write_corpus(root / "in", args.files, file_bytes,
                         args.sparse_pattern.encode())
    total = sum(p.stat().st_size for p in paths)

    service = GrepService(work_root=root / "svc")
    server = ServiceServer(service)
    server.start()
    service.start_local_workers(1)
    base = f"http://127.0.0.1:{server.port}"

    def call(method: str, path: str, body: bytes | None = None) -> dict:
        req = urllib.request.Request(f"{base}{path}", data=body,
                                     method=method)
        if body is not None:
            req.add_header("Content-Type", "application/json")
        with urllib.request.urlopen(req, timeout=600) as r:
            return json.loads(r.read())

    def submit_and_wait(pattern: str) -> tuple[float, bytes]:
        cfg = JobConfig(
            input_files=[str(p) for p in paths],
            application="distributed_grep_tpu.apps.grep_tpu",
            app_options={"pattern": pattern, "backend": "cpu"},
            n_reduce=2,
            journal=False,
        )
        t0 = time.perf_counter()
        job_id = call("POST", "/jobs",
                      cfg.to_json().encode("utf-8"))["job_id"]
        while True:
            st = call("GET", f"/jobs/{job_id}")
            if st["state"] in ("done", "failed", "cancelled"):
                break
            time.sleep(0.01)
        dt = time.perf_counter() - t0
        if st["state"] != "done":
            raise RuntimeError(f"job {job_id} ended {st['state']}: {st}")
        res = call("GET", f"/jobs/{job_id}/result")
        out = b"".join(
            Path(p).read_bytes() for p in sorted(res.get("outputs", []))
        )
        return dt, out

    def timed_leg(pattern: str, indexed: bool) -> tuple[float, bytes]:
        if indexed:
            os.environ.pop("DGREP_INDEX", None)
        else:
            os.environ["DGREP_INDEX"] = "0"
        try:
            return submit_and_wait(pattern)
        finally:
            os.environ.pop("DGREP_INDEX", None)

    # warm-up: one indexed pass per query builds every shard's summary
    # (and the compiled-model cache), so the A/B below measures routing,
    # not first-compile or summary-build cost
    for pat in (args.sparse_pattern, args.dense_pattern):
        timed_leg(pat, indexed=True)

    times: dict[str, list[float]] = {
        "sparse_on": [], "sparse_off": [], "dense_on": [], "dense_off": [],
    }
    outs: dict[str, bytes] = {}
    for _ in range(max(1, args.reps)):
        for pat, key in ((args.sparse_pattern, "sparse"),
                         (args.dense_pattern, "dense")):
            for indexed, leg in ((True, "on"), (False, "off")):
                dt, out = timed_leg(pat, indexed)
                times[f"{key}_{leg}"].append(dt)
                outs[f"{key}_{leg}"] = out

    status = call("GET", "/status")
    med = {k: statistics.median(v) for k, v in times.items()}
    sparse_speedup = (
        med["sparse_off"] / med["sparse_on"] if med["sparse_on"] else 0.0
    )
    dense_overhead = (
        (med["dense_on"] - med["dense_off"]) / med["dense_off"]
        if med["dense_off"] else 0.0
    )
    out = {
        "bench": "index_prune",
        "files": args.files,
        "bytes": total,
        "backend": jax.default_backend(),
        "reps": args.reps,
        "sparse_indexed_s": round(med["sparse_on"], 4),
        "sparse_unindexed_s": round(med["sparse_off"], 4),
        "sparse_speedup": round(sparse_speedup, 3),
        "dense_indexed_s": round(med["dense_on"], 4),
        "dense_unindexed_s": round(med["dense_off"], 4),
        "dense_overhead_pct": round(100 * dense_overhead, 2),
        "index": status.get("index", {}),
    }

    identical = (
        outs["sparse_on"] == outs["sparse_off"]
        and outs["dense_on"] == outs["dense_off"]
    )
    if args.check:
        out["check"] = "ok" if identical else "MISMATCH"

    service.stop()
    server.shutdown()

    print(json.dumps(out), flush=True)  # exactly one JSON line
    ok = identical and (not args.check or sparse_speedup >= 5.0)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
