"""Failover SLO receipt: SIGKILL the active daemon, time the takeover.

ISSUE 17's acceptance bar: an active+standby pair over one work root,
the active SIGKILLed mid-job, and the standby's promotion measured on
the REAL surface — the walls that feed the
``dgrep_daemon_failover_seconds`` histogram and the fleet timeline:

* ``failover_s``      — the promoted daemon's own detection→serving
                        clock, read back from daemon.jsonl's
                        ``promoted`` line (the histogram's sample);
* ``kill_to_active_s``— external wall from SIGKILL to the standby
                        answering /status role "active";
* ``active_to_first_progress_s`` — promotion to the first map-progress
                        advance the resumed job shows (assignment +
                        completion through the replayed scheduler).

Prints exactly ONE JSON line.  ``--check`` additionally gates: job
state "done" and ``failover_s`` > 0.  Pure control plane — the daemon
subprocesses own the jax stack; this driver only speaks HTTP and reads
daemon.jsonl.

    python benchmarks/failover_receipt.py [--files 6] [--file-kb 64]
        [--ttl-s 2.0] [--check]

Real-cluster recipe: same shape with the standby on a second host and
`dgrep worker --addr active,standby` fleets instead of --workers; the
histogram then aggregates over real failovers via `dgrep top` or any
Prometheus scrape of the promoted daemon's /metrics.
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import socket
import subprocess
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

from distributed_grep_tpu.runtime.daemon_log import DaemonLog  # noqa: E402
from distributed_grep_tpu.utils.config import JobConfig  # noqa: E402


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _http_json(method: str, url: str, body: bytes | None = None,
               timeout: float = 10.0) -> dict:
    req = urllib.request.Request(url, data=body, method=method)
    if body is not None:
        req.add_header("Content-Type", "application/json")
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return json.loads(r.read())


def _serve(work_root: Path, port: int, workers: int, ttl_s: float,
           standby: bool, log_path: Path) -> subprocess.Popen:
    env = {
        "PATH": os.environ.get("PATH", "/usr/bin:/bin"),
        "PYTHONPATH": str(_root),
        "JAX_PLATFORMS": "cpu",
        "DGREP_LOG": "WARNING",
        "DGREP_LEASE_TTL_S": str(ttl_s),
    }
    args = [sys.executable, "-m", "distributed_grep_tpu", "serve",
            "--host", "127.0.0.1", "--port", str(port),
            "--work-root", str(work_root), "--workers", str(workers)]
    if standby:
        args.append("--standby")
    return subprocess.Popen(args, stdout=subprocess.DEVNULL,
                            stderr=open(log_path, "wb"), env=env)


def _wait_status(port: int, deadline: float, want_role: str | None = None
                 ) -> dict:
    while time.monotonic() < deadline:
        try:
            st = _http_json("GET", f"http://127.0.0.1:{port}/status",
                            timeout=5.0)
            if st.get("service") and (want_role is None
                                      or st.get("role") == want_role):
                return st
        except OSError:
            pass
        time.sleep(0.05)
    raise TimeoutError(f"daemon on :{port} never answered"
                       + (f" role={want_role}" if want_role else ""))


def _build_corpus(root: Path, files: int, file_kb: int) -> list[str]:
    root.mkdir(parents=True, exist_ok=True)
    out = []
    for i in range(files):
        p = root / f"part{i:02d}.txt"
        line = f"alpha beta hello gamma {i} filler text line\n"
        miss = "nothing to see on this line at all\n"
        n = max(1, (file_kb * 1024) // len(line))
        p.write_text((line + miss * 3) * (n // 4 + 1))
        out.append(str(p))
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--files", type=int, default=6)
    ap.add_argument("--file-kb", type=int, default=64)
    ap.add_argument("--ttl-s", type=float, default=2.0)
    ap.add_argument("--check", action="store_true",
                    help="gate: job done and failover_s > 0")
    args = ap.parse_args()

    tmp = Path(tempfile.mkdtemp(prefix="dgrep-failover-"))
    work_root = tmp / "svc"
    work_root.mkdir()
    inputs = _build_corpus(tmp / "corpus", args.files, args.file_kb)

    a_port, b_port = _free_port(), _free_port()
    active = _serve(work_root, a_port, workers=1, ttl_s=args.ttl_s,
                    standby=False, log_path=tmp / "active.log")
    standby = _serve(work_root, b_port, workers=1, ttl_s=args.ttl_s,
                     standby=True, log_path=tmp / "standby.log")
    result: dict = {"benchmark": "failover_receipt", "files": args.files,
                    "file_kb": args.file_kb, "ttl_s": args.ttl_s}
    try:
        _wait_status(a_port, time.monotonic() + 60, "active")
        _wait_status(b_port, time.monotonic() + 60, "standby")

        cfg = JobConfig(
            input_files=inputs,
            application="distributed_grep_tpu.apps.grep_tpu",
            app_options={"pattern": "hello", "backend": "cpu"},
            n_reduce=2,
            task_timeout_s=5.0,
            work_dir=str(tmp / "sub"),
        )
        jid = _http_json("POST", f"http://127.0.0.1:{a_port}/jobs",
                         cfg.to_json().encode())["job_id"]
        # catch the kill mid-map so the promotion resumes real work
        deadline = time.monotonic() + 60
        progress_at_kill = 0
        while time.monotonic() < deadline:
            st = _http_json("GET",
                            f"http://127.0.0.1:{a_port}/jobs/{jid}")
            m = st.get("map", {})
            progress_at_kill = m.get("completed", 0)
            if progress_at_kill >= 1 or st.get("state") == "done":
                break
            time.sleep(0.02)

        kill_t = time.monotonic()
        active.send_signal(signal.SIGKILL)
        active.wait(timeout=30)
        _wait_status(b_port, time.monotonic() + 120, "active")
        kill_to_active = time.monotonic() - kill_t

        # first map-progress advance through the promoted daemon
        first_progress = None
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline:
            try:
                st = _http_json("GET",
                                f"http://127.0.0.1:{b_port}/jobs/{jid}")
            except OSError:
                time.sleep(0.05)
                continue
            m = st.get("map", {})
            if (st.get("state") == "done"
                    or m.get("completed", 0) > progress_at_kill):
                first_progress = time.monotonic() - kill_t - kill_to_active
                break
            time.sleep(0.05)

        # drain to terminal
        deadline = time.monotonic() + 180
        state = "unknown"
        while time.monotonic() < deadline:
            try:
                st = _http_json("GET",
                                f"http://127.0.0.1:{b_port}/jobs/{jid}")
            except OSError:
                time.sleep(0.1)
                continue
            state = st.get("state", "unknown")
            if state in ("done", "failed", "cancelled"):
                break
            time.sleep(0.1)

        events = DaemonLog.read(work_root)
        promoted = [e for e in events if e["kind"] == "promoted"]
        failover_s = (promoted[-1].get("payload", {}).get("failover_s")
                      if promoted else None)
        result.update({
            "job_state": state,
            "failover_s": failover_s,
            "kill_to_active_s": round(kill_to_active, 3),
            "active_to_first_progress_s": (
                round(first_progress, 3)
                if first_progress is not None else None),
            "lease_steals": sum(1 for e in events
                                if e["kind"] == "lease_steal"),
        })
    finally:
        for p in (active, standby):
            if p.poll() is None:
                p.kill()
                p.wait(timeout=30)

    if args.check:
        result["check"] = bool(
            result.get("job_state") == "done"
            and (result.get("failover_s") or 0) > 0
        )
    print(json.dumps(result))
    if args.check and not result["check"]:
        for name in ("active.log", "standby.log"):
            p = tmp / name
            if p.exists():
                sys.stderr.write(f"--- {name} ---\n")
                sys.stderr.write(
                    p.read_bytes()[-2000:].decode("utf-8", "replace"))
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
