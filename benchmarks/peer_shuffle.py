"""Peer-to-peer shuffle receipt: daemon data-plane bytes flat at N workers.

ISSUE 14's acceptance bar: a match-dense multi-worker HTTP service job
with peer shuffle ON completes byte-identical to the relay path while the
daemon's measured shuffle data-plane bytes drop to ~0 (metadata only).
On this 1-core box N workers cannot show N-fold wall clock — every
"worker" shares one CPU — so the BYTES counter is the honest local proof:
it measures exactly the coordinator-NIC traffic the star topology forced
and P2P removes.  Wall times are reported as interleaved A/B medians for
context, not as the claim.

    python benchmarks/peer_shuffle.py [--files 8] [--file-kb 512]
        [--reps 3] [--check]

Drives the REAL surface end to end per run: a fresh GrepService +
ServiceServer, two HTTP workers (ServiceHttpTransport) each with its own
PeerDataServer in peer mode (none in relay mode), one submit through
POST /jobs, daemon shuffle bytes read from the service counters that
also feed GET /status "shuffle" and the dgrep_daemon_shuffle_bytes
gauge.  Prints exactly ONE JSON line.

Real-cluster recipe (the number this box cannot give): run `dgrep serve
--workers 0` on one host, `dgrep worker --addr` on N others
(DGREP_PEER_SHUFFLE=1 vs 0), a match-dense `dgrep submit`, and compare
job wall + the daemon's `/metrics` dgrep_daemon_shuffle_bytes — on a
TPU pod pair it with `--timing slope` engine receipts.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import tempfile
import threading
import time
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

from distributed_grep_tpu.runtime.http_transport import (  # noqa: E402
    ServiceHttpTransport,
    client_call,
)
from distributed_grep_tpu.runtime.peer import PeerDataServer  # noqa: E402
from distributed_grep_tpu.runtime.service import (  # noqa: E402
    GrepService,
    ServiceServer,
)
from distributed_grep_tpu.runtime.worker import WorkerLoop  # noqa: E402
from distributed_grep_tpu.utils.config import JobConfig  # noqa: E402


def _build_corpus(root: Path, files: int, file_kb: int) -> list[Path]:
    """Match-dense text: every third line hits the pattern, so the
    shuffle carries real volume (the regime the star topology chokes
    on)."""
    root.mkdir(parents=True, exist_ok=True)
    out = []
    for i in range(files):
        p = root / f"dense{i:02d}.txt"
        lines = []
        j = 0
        size = 0
        target = file_kb * 1024
        while size < target:
            line = (f"line {j} of file {i} "
                    + ("needle haystack match" if j % 3 == 0 else "plain"))
            lines.append(line)
            size += len(line) + 1
            j += 1
        p.write_text("\n".join(lines) + "\n")
        out.append(p)
    return out


def _run_once(corpus: list[Path], tmp: Path, peer_on: bool, rep: int
              ) -> tuple[float, dict, dict[str, bytes]]:
    """(wall seconds, daemon shuffle stats, outputs-by-name)."""
    svc = GrepService(work_root=tmp / f"svc-{peer_on}-{rep}", resume=False)
    server = ServiceServer(svc)
    server.start()
    addr = f"127.0.0.1:{server.port}"
    peers, threads = [], []
    for _ in range(2):
        peer = PeerDataServer().start() if peer_on else None
        peers.append(peer)
        loop = WorkerLoop(
            ServiceHttpTransport(addr, rpc_timeout_s=15.0), app=None,
            peer=peer,
        )
        t = threading.Thread(target=loop.run, daemon=True)
        t.start()
        threads.append(t)
    cfg = JobConfig(
        input_files=[str(p) for p in corpus],
        application="distributed_grep_tpu.apps.grep_tpu",
        app_options={"pattern": "needle", "backend": "cpu"},
        n_reduce=2,
        work_dir="ignored",
    )
    t0 = time.perf_counter()
    jid = client_call(addr, "POST", "/jobs", cfg.to_json().encode(),
                      timeout=30.0)["job_id"]
    while True:
        st = client_call(addr, "GET", f"/jobs/{jid}", timeout=30.0)
        if st["state"] in ("done", "failed", "cancelled"):
            break
        time.sleep(0.02)
    wall = time.perf_counter() - t0
    if st["state"] != "done":
        raise RuntimeError(f"job ended {st['state']}: {st}")
    res = client_call(addr, "GET", f"/jobs/{jid}/result", timeout=30.0)
    outs = {}
    for p in res["outputs"]:
        outs[Path(p).name.split(".part.")[0]] = Path(p).read_bytes()
    stats = dict(svc._shuffle_stats)
    svc.stop()
    server.shutdown()
    for p in peers:
        if p is not None:
            p.close()
    for t in threads:
        t.join(timeout=10)
    return wall, stats, outs


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--files", type=int, default=8)
    ap.add_argument("--file-kb", type=int, default=512)
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--check", action="store_true",
                    help="assert byte identity + peer-mode daemon "
                         "shuffle bytes == 0")
    args = ap.parse_args()

    tmp = Path(tempfile.mkdtemp(prefix="dgrep-peer-bench-"))
    corpus = _build_corpus(tmp / "corpus", args.files, args.file_kb)

    walls: dict[bool, list[float]] = {True: [], False: []}
    bytes_seen: dict[bool, list[int]] = {True: [], False: []}
    outs_ref: dict[bool, dict] = {}
    # interleaved A/B: this box's background load swings 2x, so modes
    # alternate within one window instead of running in blocks
    for rep in range(args.reps):
        for peer_on in (True, False):
            wall, stats, outs = _run_once(corpus, tmp, peer_on, rep)
            walls[peer_on].append(wall)
            bytes_seen[peer_on].append(stats["daemon_shuffle_bytes"])
            outs_ref.setdefault(peer_on, outs)

    identical = outs_ref[True] == outs_ref[False]
    result = {
        "bench": "peer_shuffle",
        "files": args.files,
        "file_kb": args.file_kb,
        "reps": args.reps,
        "workers": 2,
        "peer_wall_s_median": round(statistics.median(walls[True]), 4),
        "relay_wall_s_median": round(statistics.median(walls[False]), 4),
        "daemon_shuffle_bytes_peer": max(bytes_seen[True]),
        "daemon_shuffle_bytes_relay": min(bytes_seen[False]),
        "outputs_identical": identical,
        "note": ("1-core box: wall medians are context only — the "
                 "bytes-flat counter is the receipt; see the module "
                 "docstring for the real-cluster recipe"),
    }
    print(json.dumps(result, sort_keys=True))
    if args.check:
        assert identical, "peer vs relay outputs differ"
        assert max(bytes_seen[True]) == 0, \
            f"peer mode moved daemon shuffle bytes: {bytes_seen[True]}"
        assert min(bytes_seen[False]) > 0, "relay mode counted no bytes"
    return 0


if __name__ == "__main__":
    raise SystemExit(main())