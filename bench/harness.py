"""The manifest and what it names, found by name (no jax, no program).

``BENCHMARK.json`` at the checkout's root lists configurations, cells and
metrics.  Each configuration is ``configs/<config>.json`` and its corpus
kind a module ``corpora/<kind>.py``, each traffic mix a data file
``traffic/<traffic>.json`` that ``gen.py`` reads, each per-layer metric a
module ``metrics/<metric>.py`` with ``read(ctx) -> float | None``, and the
peaks of each device kind are in ``peaks.json``.  A cell added as files
and manifest entries alone is picked up here with no edit.
"""

from __future__ import annotations

import importlib
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parent


class BenchError(Exception):
    """The manifest or a file it names is missing or malformed."""


def load_manifest(root: Path) -> dict:
    path = root / "BENCHMARK.json"
    try:
        return json.loads(path.read_text())
    except (OSError, ValueError) as e:
        raise BenchError(f"cannot read {path}: {e}") from e


def resolve(manifest: dict, workload: str, bench: Path = BENCH) -> dict:
    """The cell ``workload`` with its configuration, traffic mix, and the
    end-to-end and per-layer metrics it reports."""
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise BenchError(f"unknown workload {workload!r}; known: "
                         f"{sorted(cells)}")
    cell = cells[workload]
    configs = {c["name"]: c for c in manifest["configs"]}
    entry = configs[cell["config"]]
    cfg = _json(bench.parent / entry["file"])
    traffic = _json(bench / "traffic" / f"{cell['traffic']}.json")

    def applies(m: dict) -> bool:
        return "workloads" not in m or workload in m["workloads"]

    return {
        "cell": cell,
        "config": cfg,
        "traffic": traffic,
        "end_to_end": [m for m in manifest["end_to_end"] if applies(m)],
        "per_layer": [m for m in manifest["per_layer"] if applies(m)],
    }


def corpus_kind(kind: str):
    """The module ``corpora/<kind>.py`` that writes a corpus kind's files
    (``write_file``) and names its query vocabulary (``placeholders``)."""
    try:
        return importlib.import_module(f"corpora.{kind}")
    except ImportError as e:
        raise BenchError(f"no generator for corpus kind {kind!r}: {e}") from e


def metric_reader(name: str):
    """The ``read`` function of ``metrics/<name>.py``."""
    try:
        mod = importlib.import_module(f"metrics.{name}")
    except ImportError as e:
        raise BenchError(f"no reader for per-layer metric {name!r}: {e}") from e
    return mod.read


def peaks(device_kind: str, bench: Path = BENCH) -> dict:
    table = _json(bench / "peaks.json")
    if device_kind not in table:
        raise BenchError(f"device kind {device_kind!r} is not in peaks.json")
    return table[device_kind]


def _json(path: Path) -> dict:
    try:
        return json.loads(path.read_text())
    except (OSError, ValueError) as e:
        raise BenchError(f"cannot read {path}: {e}") from e
