"""The span readers of the benchmark against explain reports recorded from
a traced chip run of each cell.

    python3 -m pytest tests/bench/test_span_readers.py -q
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
BENCH = HERE.parents[1] / "bench"
sys.path.insert(0, str(BENCH))

import harness  # noqa: E402

# Each reader with the stages it sums (task_wait_mean_s: the mean of two
# stages).
SPAN_READERS = {
    "scan_prep_s_per_gb": ("engine:prep",),
    "device_wait_s_per_gb": ("engine:wait",),
    "scan_decode_s_per_gb": ("engine:decode",),
    "scan_confirm_s_per_gb": ("engine:confirm",),
    "scan_stitch_s_per_gb": ("engine:stitch",),
    "record_build_s_per_gb": ("shuffle:build",),
    "task_wait_mean_s": ("map:wait", "reduce:wait"),
}


@pytest.mark.parametrize("metric", sorted(SPAN_READERS))
def test_span_readers_on_recorded_explain(metric):
    recorded = json.loads((HERE / "explain_slice.json").read_text())
    stages = SPAN_READERS[metric]
    for cell, jobs in recorded.items():
        ctx = {"jobs": jobs, "trace": None, "peaks": None}
        rows = [j["explain"]["stages"][s] for j in jobs for s in stages
                if s in j["explain"]["stages"]]
        assert rows, (cell, metric)
        seconds = sum(r["seconds"] for r in rows)
        if metric == "task_wait_mean_s":
            want = seconds / sum(r["count"] for r in rows)
        else:
            want = seconds / (sum(j["bytes"] for j in jobs) / 1e9)
        got = harness.metric_reader(metric)(ctx)
        assert got == pytest.approx(want) and got > 0, (cell, metric)
