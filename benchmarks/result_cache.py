"""Query-result cache against the grep service: a repeated query over
unchanged inputs answers from stored per-split results in O(ms), and a
one-file append re-scans exactly one split.

ISSUE 18's acceptance bar: the warm full hit must beat the warm
UNCACHED scan (model cache hot, result tier off) by >= 10x, with
collated outputs byte-identical across hit / incremental / miss.

    python benchmarks/result_cache.py [--files 24] [--file-mb 1]
        [--reps 3] [--check]

Drives the REAL surface end to end: two ServiceServer HTTP daemons over
separate work roots — one with the result tier on, one with
DGREP_RESULT_CACHE=0 (the store is constructed at daemon start, so the
off leg needs its own daemon) — each with one in-process worker,
submits INTERLEAVED A/B (this box's background load swings single draws
±2x; medians over alternating reps are the honest comparison).  Output
comparison is COLLATED (sorted merged record lines): a cached job's
on-disk layout legitimately differs from a scanned job's.  Prints
exactly ONE JSON line.  ``--check`` exits 1 unless all legs are
byte-identical, the daemon reports the expected hits, AND the warm-hit
speedup clears 10x.
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
                 seed: int = 9) -> list[Path]:
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
        p = root / f"f{i:05d}.txt"
        p.write_bytes(blob)
        paths.append(p)
    return paths


def collate(paths: list[str]) -> bytes:
    """Layout-independent record comparison: merged, sorted lines."""
    lines: list[bytes] = []
    for p in sorted(paths):
        with open(p, "rb") as f:
            lines.extend(
                ln for ln in f.read().splitlines(keepends=True)
                if ln.strip()
            )
    lines.sort()
    return b"".join(lines)


class Daemon:
    def __init__(self, work_root: Path, cached: bool):
        from distributed_grep_tpu.runtime.service import (
            GrepService,
            ServiceServer,
        )

        prev = os.environ.pop("DGREP_RESULT_CACHE", None)
        if not cached:
            os.environ["DGREP_RESULT_CACHE"] = "0"
        try:
            self.service = GrepService(work_root=work_root)
        finally:
            os.environ.pop("DGREP_RESULT_CACHE", None)
            if prev is not None:
                os.environ["DGREP_RESULT_CACHE"] = prev
        self.server = ServiceServer(self.service)
        self.server.start()
        self.service.start_local_workers(1)
        self.base = f"http://127.0.0.1:{self.server.port}"

    def call(self, method: str, path: str, body: bytes | None = None):
        req = urllib.request.Request(f"{self.base}{path}", data=body,
                                     method=method)
        if body is not None:
            req.add_header("Content-Type", "application/json")
        with urllib.request.urlopen(req, timeout=600) as r:
            return json.loads(r.read())

    def submit_and_wait(self, cfg_json: bytes) -> tuple[float, bytes]:
        t0 = time.perf_counter()
        job_id = self.call("POST", "/jobs", cfg_json)["job_id"]
        while True:
            st = self.call("GET", f"/jobs/{job_id}")
            if st["state"] in ("done", "failed", "cancelled"):
                break
            time.sleep(0.005)
        dt = time.perf_counter() - t0
        if st["state"] != "done":
            raise RuntimeError(f"job {job_id} ended {st['state']}: {st}")
        res = self.call("GET", f"/jobs/{job_id}/result")
        return dt, collate(res.get("outputs", []))

    def stop(self):
        self.service.stop()
        self.server.shutdown()


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--files", type=int, default=32)
    ap.add_argument("--file-mb", type=float, default=1.0)
    ap.add_argument("--pattern", default="wikipedia philosophy",
                    help="selective phrase whose WORDS are in every "
                         "shard: the index tier prunes nothing (blooms "
                         "all say maybe) and the cached result blobs "
                         "stay small — the hit measures routing, not "
                         "match-dense materialization")
    ap.add_argument("--reps", type=int, default=3,
                    help="interleaved A/B reps; MEDIANS reported")
    ap.add_argument("--check", action="store_true",
                    help="exit 1 unless outputs identical, hits "
                         "reported, and warm-hit speedup >= 10x")
    args = ap.parse_args()

    from distributed_grep_tpu.utils.config import JobConfig

    root = Path(tempfile.mkdtemp(prefix="dgrep-result-cache-"))
    (root / "in").mkdir()
    file_bytes = int(args.file_mb * (1 << 20))
    paths = write_corpus(root / "in", args.files, file_bytes)
    total = sum(p.stat().st_size for p in paths)

    cfg_json = JobConfig(
        input_files=[str(p) for p in paths],
        application="distributed_grep_tpu.apps.grep_tpu",
        app_options={"pattern": args.pattern, "backend": "cpu"},
        n_reduce=2,
        journal=False,
    ).to_json().encode("utf-8")

    on = Daemon(root / "svc-on", cached=True)
    off = Daemon(root / "svc-off", cached=False)
    try:
        # warm-up: one pass each — seeds the result store on the cached
        # daemon and the compiled-model cache on both, so the A/B below
        # measures warm hit vs warm scan, not first-compile
        _, out_seed = on.submit_and_wait(cfg_json)
        off.submit_and_wait(cfg_json)

        hit_t: list[float] = []
        scan_t: list[float] = []
        outs: dict[str, bytes] = {}
        for _ in range(max(1, args.reps)):
            dt, out = on.submit_and_wait(cfg_json)
            hit_t.append(dt)
            outs["hit"] = out
            dt, out = off.submit_and_wait(cfg_json)
            scan_t.append(dt)
            outs["scan"] = out

        # incremental re-query: append ONE needle line to one file —
        # exactly one split drifts; the cached daemon re-scans only it
        needle = f"{args.pattern} zzyzxappended"
        with open(paths[0], "a") as f:
            f.write(needle + "\n")
        inc_t, out_inc = on.submit_and_wait(cfg_json)
        _, out_inc_oracle = off.submit_and_wait(cfg_json)

        status = on.call("GET", "/status")
    finally:
        on.stop()
        off.stop()

    med_hit = statistics.median(hit_t)
    med_scan = statistics.median(scan_t)
    speedup = med_scan / med_hit if med_hit else 0.0
    rc = status.get("result_cache", {})
    identical = (
        outs["hit"] == outs["scan"] == out_seed
        and out_inc == out_inc_oracle
        and needle.encode() in out_inc
    )
    out = {
        "bench": "result_cache",
        "files": args.files,
        "bytes": total,
        "backend": jax.default_backend(),
        "reps": args.reps,
        "warm_hit_s": round(med_hit, 4),
        "warm_scan_s": round(med_scan, 4),
        "hit_speedup": round(speedup, 3),
        "incremental_s": round(inc_t, 4),
        "result_cache": rc,
    }
    hits_ok = rc.get("result_hits", 0) >= max(1, args.reps)
    if args.check:
        out["check"] = "ok" if (identical and hits_ok) else "MISMATCH"

    print(json.dumps(out), flush=True)  # exactly one JSON line
    ok = identical and (not args.check or (hits_ok and speedup >= 10.0))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
