"""Cold-vs-warm submit latency against the grep-as-a-service daemon.

ISSUE 6's acceptance bar: a repeated pattern's second submit to a running
daemon must be strictly faster than the first on this CPU box, because the
cross-job compiled-model cache (ops/engine.cached_engine) skips engine
construction — off-chip, AC-bank/model compile for a large literal set
dominates a small job's wall, so the effect is CPU-measurable (on a real
chip the same cache additionally skips the ~20-40 s first XLA/Mosaic
compile per fresh shape key).

    python benchmarks/service_warm.py [--patterns 1500] [--warm-reps 3]
        [--check]
    python benchmarks/service_warm.py --corpus-warm [--files 32]
        [--file-kb 128] [--check]

Drives the REAL surface end to end: ServiceServer HTTP API (POST /jobs,
GET /jobs/<id>), one in-process worker (deterministic warm path: the one
worker's second configure must come from the cache, not a sibling's).
Submits alternate between two equal-sized pattern sets A/B so every warm
submit pays a real reconfigure THROUGH the cache (the app-level same-config
short-circuit cannot answer it).  Prints exactly ONE JSON line.

``--corpus-warm`` (round 7) separates the TWO caches' contributions over
a multi-file corpus on the device backend: cold (both miss), corpus-warm
only (a FRESH literal set per submit — the model cache cannot answer, the
resident shards do), model-warm only (a known set, the corpus cache
cleared before each submit — the data path is paid again), and both warm
(the repeat-query steady state).  The in-process worker shares this
process, so the per-leg cache clears reach the worker's engines directly.
"""

from __future__ import annotations

import argparse
import json
import os
import random
import string
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

# CPU-pinned (CLAUDE.md environment rules): ASSIGN, never setdefault — a
# TPU default backend would price the submit path with device dispatch
# latency, and this benchmark measures host-side model build.
os.environ["JAX_PLATFORMS"] = "cpu"
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")


def _pattern_set(n: int, seed: int) -> list[str]:
    rng = random.Random(seed)
    out = set()
    while len(out) < n:
        out.add("".join(
            rng.choice(string.ascii_lowercase)
            for _ in range(rng.randint(5, 12))
        ))
    return sorted(out)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--patterns", type=int, default=1500,
                    help="literal-set size per job (model build dominates)")
    ap.add_argument("--warm-reps", type=int, default=3,
                    help="warm submits per set; the MIN is reported")
    ap.add_argument("--check", action="store_true",
                    help="exit 1 unless warm < cold")
    ap.add_argument("--corpus-warm", action="store_true",
                    help="4-leg mode over a multi-file corpus: separate "
                         "the model cache's and the corpus cache's "
                         "contributions (cold / corpus-warm only / "
                         "model-warm only / both)")
    ap.add_argument("--files", type=int, default=32,
                    help="corpus files (--corpus-warm mode)")
    ap.add_argument("--file-kb", type=float, default=128,
                    help="KB per corpus file (--corpus-warm mode)")
    args = ap.parse_args()

    from distributed_grep_tpu.runtime.service import GrepService, ServiceServer
    from distributed_grep_tpu.utils.config import JobConfig

    root = Path(tempfile.mkdtemp(prefix="dgrep-svc-warm-"))
    corpus = root / "corpus.txt"
    corpus.write_bytes(b"".join(
        f"line {i} with some words in it\n".encode() for i in range(2000)
    ))

    service = GrepService(work_root=root / "svc")
    server = ServiceServer(service)
    server.start()
    service.start_local_workers(1)
    base = f"http://127.0.0.1:{server.port}"

    def call(method: str, path: str, body: bytes | None = None) -> dict:
        req = urllib.request.Request(f"{base}{path}", data=body, method=method)
        if body is not None:
            req.add_header("Content-Type", "application/json")
        with urllib.request.urlopen(req, timeout=120) as r:
            return json.loads(r.read())

    def _submit(cfg: JobConfig) -> float:
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
        return dt

    def submit_and_wait(patterns: list[str]) -> float:
        return _submit(JobConfig(
            input_files=[str(corpus)],
            application="distributed_grep_tpu.apps.grep_tpu",
            app_options={"patterns": patterns, "backend": "cpu"},
            n_reduce=2,
            journal=False,
        ))

    set_a = _pattern_set(args.patterns, seed=1)
    set_b = _pattern_set(args.patterns, seed=2)

    if args.corpus_warm:
        # 4-leg cache attribution (round 7): the device corpus cache
        # (ops/layout.CorpusCache) vs the compiled-model cache, over a
        # multi-file corpus on the device backend.  The in-process
        # worker's engines live in THIS process, so per-leg clears of
        # either cache reach them directly.
        from distributed_grep_tpu.ops.layout import corpus_cache_clear

        files_dir = root / "in"
        files_dir.mkdir()
        file_bytes = int(args.file_kb * 1024)
        paths = []
        for i in range(args.files):
            blob = b"".join(
                (b"a volcano erupts here\n" if j % 97 == 0
                 else b"filler line %d of file %d\n" % (j, i))
                for j in range(max(1, file_bytes // 24))
            )
            p = files_dir / f"f{i:04d}.txt"
            p.write_bytes(blob)
            paths.append(p)
        total = sum(p.stat().st_size for p in paths)

        def submit_corpus(patterns: list[str]) -> float:
            return _submit(JobConfig(
                input_files=[str(p) for p in paths],
                application="distributed_grep_tpu.apps.grep_tpu",
                # "volcano" guarantees matches; the literal set sizes the
                # model build (what the model-cache legs amortize)
                app_options={"patterns": patterns + ["volcano"],
                             "backend": "device",
                             "corpus_bytes": 1 << 30},
                batch_bytes=32 << 20,
                n_reduce=2,
                journal=False,
            ))

        reps = max(1, args.warm_reps)
        cold_s = submit_corpus(set_a)  # both caches miss
        # corpus-warm ONLY: a fresh literal set per submit — the model
        # cache cannot answer, the resident shards do
        corpus_warm = [
            submit_corpus(_pattern_set(args.patterns, seed=100 + i))
            for i in range(reps)
        ]
        # model-warm ONLY: a known set, the corpus evicted per submit —
        # the data path (read/pack/upload) is paid again every time
        model_warm = []
        for _ in range(reps):
            corpus_cache_clear()
            model_warm.append(submit_corpus(set_a))
        # both warm: the last model-warm submit left the shards resident
        both = [submit_corpus(set_a) for _ in range(reps)]

        status = call("GET", "/status")
        service.stop()
        server.shutdown()

        both_s = min(both)
        rec = {
            "bench": "service_warm",
            "mode": "corpus_warm",
            "patterns": args.patterns,
            "files": args.files,
            "bytes": total,
            "backend": jax.default_backend(),
            "cold_s": round(cold_s, 4),
            "corpus_warm_s": round(min(corpus_warm), 4),
            "model_warm_s": round(min(model_warm), 4),
            "both_warm_s": round(both_s, 4),
            "speedup_corpus_only": (
                round(cold_s / min(corpus_warm), 3) if min(corpus_warm) else 0.0
            ),
            "speedup_model_only": (
                round(cold_s / min(model_warm), 3) if min(model_warm) else 0.0
            ),
            "speedup_both": round(cold_s / both_s, 3) if both_s else 0.0,
            "compile_cache_hits": int(
                status["compile_cache"].get("compile_cache_hits", 0)
            ),
            "corpus_cache_hits": int(
                status["corpus_cache"].get("corpus_cache_hits", 0)
            ),
            "bytes_resident": int(
                status["corpus_cache"].get("corpus_cache_bytes_resident", 0)
            ),
        }
        print(json.dumps(rec))  # exactly one JSON line
        if args.check and not both_s < cold_s:
            return 1
        return 0

    # cold: first time each set is seen (engine constructed, cache miss)
    cold_a = submit_and_wait(set_a)
    cold_b = submit_and_wait(set_b)
    # warm: alternate A/B so every submit reconfigures through the cache
    warm = []
    for _ in range(args.warm_reps):
        warm.append(submit_and_wait(set_a))
        warm.append(submit_and_wait(set_b))
    cache = call("GET", "/status")["compile_cache"]
    service.stop()
    server.shutdown()

    cold_s = min(cold_a, cold_b)
    warm_s = min(warm)
    rec = {
        "bench": "service_warm",
        "patterns": args.patterns,
        "cold_s": round(cold_s, 4),
        "warm_s": round(warm_s, 4),
        "speedup": round(cold_s / warm_s, 3) if warm_s else 0.0,
        "compile_cache_hits": int(cache.get("compile_cache_hits", 0)),
        "compile_cache_misses": int(cache.get("compile_cache_misses", 0)),
    }
    print(json.dumps(rec))  # exactly one JSON line (driver contract shape)
    if args.check and not warm_s < cold_s:
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
