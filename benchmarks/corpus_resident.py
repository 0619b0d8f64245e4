"""Cold-vs-warm repeat-query throughput against the grep service with the
device corpus cache (round 7, ops/layout.CorpusCache) in force.

ISSUE 7's acceptance bar: a repeat query over the SAME inputs must skip
the host read, the stripe pack, and the HBM upload — the data path that
dominates a dense job's wall (BASELINE round 6: the scan kernel is ~12%).
Three warm legs separate the two caches' contributions:

* cold         — first submit: model miss + corpus miss (full data path)
* model_warm   — same pattern, corpus cache CLEARED first: the compiled-
                 model cache answers, the data path is paid again
* warm         — same pattern, both caches answer: the repeat-query
                 steady state (zero re-read / re-pack / re-upload)

    python benchmarks/corpus_resident.py [--files 64] [--file-kb 256]
        [--pattern volcano] [--warm-reps 3] [--timing e2e|slope] [--check]

Drives the REAL surface end to end: ServiceServer HTTP API, one in-process
worker (deterministic warm path), multi-file map splits handed to the
engine as PATHS (apps/grep_tpu.map_batch_paths) so the warm window is
recognized before any member is read.  ``--timing slope`` additionally
slope-times the device-resident rescan of the packed corpus
(utils/slope.py — the per-chip warm ceiling; on a CPU-only box it
reports the cpu number, re-run with ``--device`` through the chip tool for
the real-chip receipt).  Prints exactly ONE JSON line.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
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
# ``--device`` drops the pin for a run on the chip.
if "--device" not in sys.argv:
    os.environ["JAX_PLATFORMS"] = "cpu"
import jax  # noqa: E402

if "--device" not in sys.argv:
    jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402

WORDS = (
    "the of and to in a is that for it as was with be by on not he this are "
    "at from or have an they which one you were all her she there would "
    "fff needle volcano anarchism philosophy wikipedia"
).split()


def write_corpus(root: Path, n_files: int, file_bytes: int,
                 needle: bytes, seed: int = 9) -> list[Path]:
    """English-like filler files on disk; ~1 in 8 carries the needle (the
    log/code-search shape: most files miss, some hit)."""
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
        if i % 8 == 0:
            pos = int(rng.integers(0, max(1, len(blob) - len(needle) - 2)))
            blob = blob[:pos] + needle + blob[pos + len(needle):]
        p = root / f"f{i:05d}.txt"
        p.write_bytes(blob)
        paths.append(p)
    return paths


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--files", type=int, default=64)
    ap.add_argument("--file-kb", type=float, default=256)
    ap.add_argument("--pattern", default="volcano")
    ap.add_argument("--warm-reps", type=int, default=3,
                    help="warm submits; the MIN is reported")
    ap.add_argument("--batch-mb", type=float, default=32)
    ap.add_argument("--corpus-mb", type=float, default=1024,
                    help="DGREP_CORPUS_BYTES-equivalent budget (app option)")
    ap.add_argument("--timing", default="e2e", choices=["e2e", "slope"],
                    help="slope: additionally slope-time the device-"
                         "resident rescan of the packed corpus")
    ap.add_argument("--index", action="store_true",
                    help="add a shard-index leg (all three caches warm) "
                         "so model / corpus / index are attributable "
                         "separately; the base legs always run with "
                         "DGREP_INDEX=0 so their meaning is unchanged")
    ap.add_argument("--device", action="store_true",
                    help="do NOT pin JAX_PLATFORMS=cpu (a run on the chip)")
    ap.add_argument("--check", action="store_true",
                    help="exit 1 unless warm < cold and outputs identical")
    args = ap.parse_args()

    from distributed_grep_tpu.ops.layout import corpus_cache_clear
    from distributed_grep_tpu.runtime.service import GrepService, ServiceServer
    from distributed_grep_tpu.utils.config import JobConfig

    root = Path(tempfile.mkdtemp(prefix="dgrep-corpus-res-"))
    (root / "in").mkdir()
    file_bytes = int(args.file_kb * 1024)
    paths = write_corpus(root / "in", args.files, file_bytes,
                         args.pattern.encode())
    total = sum(p.stat().st_size for p in paths)

    service = GrepService(work_root=root / "svc")
    server = ServiceServer(service)
    server.start()
    service.start_local_workers(1)
    base = f"http://127.0.0.1:{server.port}"

    def call(method: str, path: str, body: bytes | None = None) -> dict:
        req = urllib.request.Request(f"{base}{path}", data=body, method=method)
        if body is not None:
            req.add_header("Content-Type", "application/json")
        with urllib.request.urlopen(req, timeout=600) as r:
            return json.loads(r.read())

    def submit_and_wait() -> tuple[float, dict]:
        cfg = JobConfig(
            input_files=[str(p) for p in paths],
            application="distributed_grep_tpu.apps.grep_tpu",
            app_options={
                "pattern": args.pattern,
                "backend": "device",
                "corpus_bytes": int(args.corpus_mb * (1 << 20)),
            },
            batch_bytes=int(args.batch_mb * (1 << 20)),
            n_reduce=2,
            journal=False,
        )
        t0 = time.perf_counter()
        job_id = call("POST", "/jobs", cfg.to_json().encode("utf-8"))["job_id"]
        while True:
            st = call("GET", f"/jobs/{job_id}")
            if st["state"] in ("done", "failed", "cancelled"):
                break
            time.sleep(0.01)
        dt = time.perf_counter() - t0
        if st["state"] != "done":
            raise RuntimeError(f"job {job_id} ended {st['state']}: {st}")
        return dt, call("GET", f"/jobs/{job_id}/result")

    # The base legs run with the shard index OFF: their round-7 meaning
    # (model vs corpus attribution) is unchanged by the index tier — the
    # --index leg below measures the third cache separately.
    os.environ["DGREP_INDEX"] = "0"
    cold_s, cold_res = submit_and_wait()
    # model-warm leg: the compiled-model cache answers, but the corpus
    # cache is emptied — the submit pays the full data path again
    corpus_cache_clear()
    model_warm_s, _ = submit_and_wait()
    # warm legs: both caches answer (the first repopulated the corpus)
    warm, warm_res = [], None
    for _ in range(max(1, args.warm_reps)):
        dt, warm_res = submit_and_wait()
        warm.append(dt)
    warm_s = min(warm)

    index_warm_s = None
    index_res = None
    if args.index:
        # shard-index leg: all THREE caches answer.  One untimed pass
        # builds + persists the summaries; the timed reps then route —
        # shards the query cannot match are pruned at the planner, so
        # warm cost falls from O(corpus) toward O(matching shards).
        os.environ.pop("DGREP_INDEX", None)
        submit_and_wait()  # summary-building pass
        idx = []
        for _ in range(max(1, args.warm_reps)):
            dt, index_res = submit_and_wait()
            idx.append(dt)
        index_warm_s = min(idx)
    os.environ.pop("DGREP_INDEX", None)
    status = call("GET", "/status")
    corpus = status.get("corpus_cache", {})

    out = {
        "bench": "corpus_resident",
        "files": args.files,
        "bytes": total,
        "pattern": args.pattern,
        "backend": jax.default_backend(),
        "cold_s": round(cold_s, 4),
        "model_warm_s": round(model_warm_s, 4),
        "warm_s": round(warm_s, 4),
        "cold_gbps": round(total / 1e9 / cold_s, 3),
        "warm_gbps": round(total / 1e9 / warm_s, 3),
        "speedup_vs_cold": round(cold_s / warm_s, 3) if warm_s else 0.0,
        "speedup_vs_model_warm": (
            round(model_warm_s / warm_s, 3) if warm_s else 0.0
        ),
        "corpus_cache_hits": int(corpus.get("corpus_cache_hits", 0)),
        "corpus_cache_misses": int(corpus.get("corpus_cache_misses", 0)),
        "bytes_resident": int(corpus.get("corpus_cache_bytes_resident", 0)),
    }
    if index_warm_s is not None:
        out["index_warm_s"] = round(index_warm_s, 4)
        out["index_speedup_vs_warm"] = (
            round(warm_s / index_warm_s, 3) if index_warm_s else 0.0
        )
        out["index"] = status.get("index", {})

    if args.check:
        def by_name(res: dict) -> dict:
            return {Path(p).name: Path(p).read_bytes()
                    for p in res.get("outputs", [])}

        identical = by_name(cold_res) == by_name(warm_res)
        if index_res is not None:
            identical = identical and by_name(index_res) == by_name(cold_res)
        out["check"] = "ok" if identical else "MISMATCH"

    service.stop()
    server.shutdown()

    if args.timing == "slope":
        # Device-resident warm-rescan ceiling: pack the whole corpus once
        # and slope-time chained kernel passes over the resident layout
        # (utils/slope.py via the baseline suite's per-mode setup) — what
        # a warm query costs once the upload is cached away.
        sys.path.insert(0, str(_root / "benchmarks"))
        from baseline_configs import slope_gbps

        from distributed_grep_tpu.ops.engine import GrepEngine
        from distributed_grep_tpu.ops.layout import BatchPacker

        eng = GrepEngine(args.pattern, backend="device")
        packer = BatchPacker(total + args.files + 1)
        for p in paths:
            packer.add(p.name, p.read_bytes())
        got = slope_gbps(eng, packer.pack().data)
        if got is None:
            out["slope_error"] = f"no device slope path for mode {eng.mode}"
        else:
            gbps, label = got
            out["resident_slope_gbps"] = round(gbps, 3)
            out["engine"] = label

    print(json.dumps(out), flush=True)  # exactly one JSON line
    ok = out.get("check", "ok") == "ok" and (
        not args.check or warm_s < cold_s
    )
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
