"""Subprocess ``dgrep serve`` driver for the chaos/soak tiers.

A REAL daemon process (SIGKILL-able — the one death no in-process
simulation can model honestly: no finally blocks, no scheduler stop, no
flushes) plus the minimal HTTP client the tests need.  Lives outside the
test modules so tests/test_chaos.py and tests/test_soak.py share one
spawn recipe (pytest puts tests/ on sys.path; plain ``import
service_proc`` works from any test module).
"""

from __future__ import annotations

import json
import os
import signal
import socket
import subprocess
import sys
import time
import urllib.request
from pathlib import Path

REPO = str(Path(__file__).resolve().parents[1])


def free_port() -> int:
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


class ServiceProc:
    """One ``dgrep serve`` subprocess bound to a fixed (port, work_root)
    so a SIGKILL + ``start()`` models a daemon crash + restart: same
    address (attached workers' retry loops reconnect), same work root
    (the jobs.jsonl registry + per-job journals drive the resume)."""

    def __init__(self, work_root: Path, port: int | None = None,
                 workers: int = 0, env: dict | None = None,
                 extra_args: list[str] | None = None):
        self.work_root = Path(work_root)
        self.port = port or free_port()
        self.workers = workers
        # e.g. ["--standby"] for the HA tier; a parked standby still
        # answers /status {"service": true, "role": "standby"}, so the
        # start() readiness probe works unchanged
        self.extra_args = list(extra_args or [])
        self.base = f"http://127.0.0.1:{self.port}"
        self.env = {
            "PYTHONPATH": REPO, "PATH": os.environ.get("PATH", "/usr/bin:/bin"),
            "JAX_PLATFORMS": "cpu", "DGREP_LOG": "WARNING",
            **(env or {}),
        }
        self.proc: subprocess.Popen | None = None
        self._logs: list[Path] = []

    # ------------------------------------------------------------ lifecycle
    def start(self, timeout: float = 60.0) -> "ServiceProc":
        log_path = self.work_root.parent / (
            f"serve-{self.port}-{len(self._logs)}.log"
        )
        self._logs.append(log_path)
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "distributed_grep_tpu", "serve",
             "--host", "127.0.0.1", "--port", str(self.port),
             "--work-root", str(self.work_root), "--workers",
             str(self.workers), *self.extra_args],
            stdout=subprocess.DEVNULL,
            stderr=open(log_path, "wb"),
            env=self.env,
        )
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if self.proc.poll() is not None:
                raise RuntimeError(
                    f"serve died at startup: {self.tail_log()}"
                )
            try:
                if self.status().get("service"):
                    return self
            except OSError:
                time.sleep(0.1)
        raise TimeoutError(f"serve not ready on {self.base}: "
                           f"{self.tail_log()}")

    def sigkill(self) -> None:
        """The daemon crash: SIGKILL, no shutdown path of any kind runs."""
        assert self.proc is not None
        self.proc.send_signal(signal.SIGKILL)
        self.proc.wait(timeout=30)

    def terminate(self) -> None:
        if self.proc is not None and self.proc.poll() is None:
            self.proc.kill()
            self.proc.wait(timeout=30)

    def tail_log(self, n: int = 2000) -> str:
        out = []
        for p in self._logs:
            if p.exists():
                out.append(p.read_bytes()[-n:].decode("utf-8", "replace"))
        return "\n---\n".join(out)

    # --------------------------------------------------------------- client
    def status(self, timeout: float = 10.0) -> dict:
        return _http_json("GET", f"{self.base}/status", timeout=timeout)

    def submit(self, config) -> str:
        body = config.to_json().encode("utf-8", "strict")
        return _http_json("POST", f"{self.base}/jobs", body)["job_id"]

    def job_status(self, job_id: str) -> dict:
        return _http_json("GET", f"{self.base}/jobs/{job_id}")

    def job_result(self, job_id: str) -> dict:
        return _http_json("GET", f"{self.base}/jobs/{job_id}/result")

    def wait_job(self, job_id: str, timeout: float = 120.0,
                 poll_s: float = 0.2) -> dict:
        """Poll to a terminal state, riding out daemon-restart windows
        (connection errors while the daemon is down retry until the
        overall deadline)."""
        deadline = time.monotonic() + timeout
        last: dict = {}
        while time.monotonic() < deadline:
            try:
                last = self.job_status(job_id)
            except OSError:
                time.sleep(poll_s)
                continue
            if last.get("state") in ("done", "failed", "cancelled"):
                return last
            time.sleep(poll_s)
        raise TimeoutError(
            f"job {job_id} not terminal after {timeout}s: {last} "
            f"(daemon log: {self.tail_log()})"
        )
