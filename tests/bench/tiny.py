"""A tiny checkout for the harness tests: ``BENCHMARK.json`` and a copy of
``bench/`` with the configurations cut to a few hundred KiB, and the
program linked in beside them.  Runs on the CPU backend."""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
BENCH = ROOT / "bench"


def checkout(tmp: Path, program: bool = True) -> Path:
    """``tmp`` laid out as a checkout: the manifest and ``bench/``; with
    ``program`` the package is a link to this repository's."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp / "BENCHMARK.json")
    shutil.copytree(BENCH, tmp / "bench",
                    ignore=shutil.ignore_patterns(".run", "__pycache__"))
    if program:
        (tmp / "distributed_grep_tpu").symlink_to(ROOT / "distributed_grep_tpu")
    _cut(tmp / "bench" / "configs" / "subtitles-en.json",
         lambda c: (c["corpus"].update(file_bytes=256 << 10),
                    c["names"].update(count=64, per_file=4)))
    return tmp


def _cut(path: Path, edit) -> None:
    cfg = json.loads(path.read_text())
    edit(cfg)
    path.write_text(json.dumps(cfg))


def run(root: Path, workload: str, seed: int, seconds: float,
        control: int = 0, fault: str = "", timeout: float = 600):
    """``bench/run.py`` in a child process on the CPU, past the harness's
    look for a chip, with ``fault`` planted in the program (``faults.py``).
    Returns the CompletedProcess."""
    code = (
        "import sys; sys.path.insert(0, 'bench'); sys.path.insert(0, "
        f"{str(HERE)!r}); import faults, run; "
        f"faults.plant({fault!r}); "
        f"sys.exit(run.main(['--workload', {workload!r}, '--seed', "
        f"'{seed}', '--seconds', '{seconds}', '--trace', '0', "
        f"'--control', '{control}'], require_tpu=False))"
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu", DGREP_LOG="ERROR")
    return subprocess.run([sys.executable, "-c", code], cwd=root, env=env,
                          capture_output=True, text=True, timeout=timeout)


def result(proc) -> dict:
    """The run's last stdout line, parsed."""
    return json.loads(proc.stdout.strip().splitlines()[-1])


def line(proc, prefix: str) -> dict:
    """The earlier stdout line that starts with ``prefix``."""
    for ln in proc.stdout.splitlines():
        if ln.startswith(prefix + " "):
            return json.loads(ln[len(prefix) + 1 :])
    raise AssertionError(f"no {prefix!r} line in: {proc.stdout[-2000:]}")
