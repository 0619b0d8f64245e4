"""The harness on the CPU at tiny sizes: the manifest and what it names,
a cell of a new shape added as files alone, the trace reduction on a
recorded slice, the refusal without a TPU, and the window arithmetic.

    python3 -m pytest tests/bench -q
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
BENCH = ROOT / "bench"
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(HERE))

import devtrace  # noqa: E402
import gen  # noqa: E402
import harness  # noqa: E402
import run  # noqa: E402
import tiny  # noqa: E402

MANIFEST = harness.load_manifest(ROOT)


@pytest.mark.parametrize("cell", [w["name"] for w in MANIFEST["workloads"]])
def test_every_cell_resolves_by_name(cell):
    got = harness.resolve(MANIFEST, cell)
    assert got["config"]["name"] == got["cell"]["config"]
    assert {"families", "clients", "poll_s"} <= set(got["traffic"])
    assert got["end_to_end"] and got["per_layer"]
    for m in got["per_layer"]:
        assert callable(harness.metric_reader(m["name"]))


def test_manifest_keys_and_files():
    assert MANIFEST["command"] == ["python3", "bench/run.py"]
    for c in MANIFEST["configs"]:
        assert (ROOT / c["file"]).is_file()
    names = {m["name"] for m in MANIFEST["end_to_end"]}
    assert "setup_s" in names
    for m in MANIFEST["per_layer"]:
        assert m["moves"] in names
    assert harness.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(harness.BenchError):
        harness.peaks("TPU v9 imaginary")


# A corpus kind, a configuration, a traffic mix of another shape (files
# shared by the clients, queries repeated by a Zipf draw) and a per-layer
# metric, each a file of its own.
LINES_KIND = """
from pathlib import Path
import numpy as np

def placeholders(cfg):
    return {}

def write_file(cfg, seed, index, path):
    rng = np.random.default_rng([seed, index])
    w = cfg["words"]
    rows = [" ".join(w[int(i)] for i in rng.integers(len(w), size=3))
            for _ in range(cfg["corpus"]["lines"])]
    data = ("\\n".join(rows) + "\\n").encode()
    Path(path).write_bytes(data)
    return len(data)
"""
LINES_CONFIG = {"name": "lines", "words": ["alpha", "bravo", "charlie",
                                           "delta", "echo", "foxtrot"],
                "corpus": {"kind": "lines", "files": 4, "lines": 2000,
                           "prefix": "lines-", "suffix": ".txt"}}
REPEAT_MIX = {"clients": 2, "poll_s": 0.02, "n_reduce": 2,
              "why": "shared files, repeated queries",
              "files": {"per_query": 2, "shared": True},
              "repeats": {"zipf_s": 1.1, "queries_per_client": 40},
              "words": {"w": LINES_CONFIG["words"]},
              "families": [{"name": "literal", "pattern": "{w} {w}",
                            "fixed": True},
                           {"name": "alternate", "pattern": "{w}|{w}"}],
              "warmup": {}, "check_sample": 4, "pool_seed": 5,
              "pool_per_family": 2}
RESULT_HITS = """
def read(ctx):
    n = sum(j["explain"].get("routing", {}).get("result_cache", {})
            .get("hits", 0) for j in ctx["jobs"])
    return float(n) or None
"""


def test_cell_of_a_new_shape_added_as_files_alone(tmp_path):
    root = tiny.checkout(tmp_path)
    bench = root / "bench"
    (bench / "corpora" / "lines.py").write_text(LINES_KIND)
    (bench / "configs" / "lines.json").write_text(json.dumps(LINES_CONFIG))
    (bench / "traffic" / "repeat.json").write_text(json.dumps(REPEAT_MIX))
    (bench / "metrics" / "result_hits.py").write_text(RESULT_HITS)
    m = json.loads((root / "BENCHMARK.json").read_text())
    m["configs"].append({"name": "lines", "source": "x",
                         "file": "bench/configs/lines.json", "reduced": [],
                         "why": "x"})
    m["workloads"].append({"name": "lines.repeat", "config": "lines",
                           "traffic": "repeat", "chips": 1, "why": "x"})
    m["per_layer"].append({"name": "result_hits", "unit": "jobs",
                           "better": "higher", "source": "program_counter",
                           "layer": "x", "moves": "query_p50_s",
                           "workloads": ["lines.repeat"]})
    (root / "BENCHMARK.json").write_text(json.dumps(m))

    got = harness.resolve(m, "lines.repeat", bench)
    assert [x["name"] for x in got["per_layer"]][-1] == "result_hits"
    p = tiny.run(root, "lines.repeat", 2**31 + 77, 3)
    assert p.returncode == 0, p.stderr[-3000:]
    res = tiny.result(p)
    assert res["correct"] is True, res
    assert res["attempted"] > 4
    counts = tiny.line(p, "counts")
    assert counts["result_cache"]["hits"] > 0
    assert counts["route"]["device"] > 0  # the rest answered from the cache


def _busy_by_timeline(compact: dict, t0: int, t1: int) -> float:
    """Busy seconds on a 1 us grid: an independent check of the union."""
    grid = np.zeros((t1 - t0) // 1000 + 1, dtype=bool)
    for rows in compact["devices"].values():
        for _, s, e in rows:
            s, e = max(s, t0), min(e, t1)
            if e > s:
                grid[(s - t0) // 1000 : (e - t0 + 999) // 1000] = True
    return grid.sum() / 1e6


def test_trace_reduction_on_recorded_slice():
    compact = json.loads((HERE / "trace_slice.json").read_text())
    t0, t1 = 0, 3_000_000_000
    red = devtrace.reduce(compact, t0, t1)
    want = _busy_by_timeline(compact, t0, t1)
    n_ops = sum(len(r) for r in compact["devices"].values())
    assert abs(red["busy_s"] - want) <= 2e-6 * n_ops
    assert red["window_s"] == 3.0
    ctx = {"trace": red, "peaks": {"hbm_bytes_per_s": 819e9},
           "jobs": [{"bytes": 1 << 28, "explain": {"routing": {"engine_modes": {
               "shift_and": {"kernel_bytes": {"pallas": 1 << 28}},
               "batch": {"kernel_bytes": {"pallas": 1 << 28}}}}}}]}
    idle = harness.metric_reader("device_idle_share")(ctx)
    assert idle == pytest.approx(100 * (1 - want / 3.0), abs=1e-3)
    roof = harness.metric_reader("scan_roofline")(ctx)
    assert roof == pytest.approx(100 * (2**28 / 819e9) / red["busy_s"])
    assert 0 < roof < 100
    gaps = red["breakdown"]["idle_gaps"]
    assert len(gaps) <= 10 and gaps == sorted(gaps, key=lambda g: -g[1])
    assert {g[0] for g in gaps} <= {"map_read", "map_compute", "feed",
                                    "collect", "reduce_compute",
                                    "no_host_region"}
    assert red["breakdown"]["device_ops"][0][1] >= \
        red["breakdown"]["device_ops"][-1][1]


def test_readers_return_nothing_without_data():
    ctx = {"jobs": [], "trace": None, "peaks": None}
    for m in MANIFEST["per_layer"]:
        assert harness.metric_reader(m["name"])(ctx) is None


def test_window_metrics_over_all_queries():
    walls = [1.0 + i for i in range(20)]
    done = [{"t_post": 100.0, "t_done": 100.0 + w, "bytes": 1e9}
            for w in walls]
    got = run.window_metrics(done, 100.0, 150.0)
    assert got["served_gbps"] == pytest.approx(20 / 50)
    assert got["query_p50_s"] == pytest.approx(10.5)
    assert run.window_metrics([], 0.0, 1.0)["query_p50_s"] is None


@pytest.mark.parametrize("cell", ["subtitles.sparse", "subtitles.dense"])
def test_query_lists_keep_the_caches_honest(cell):
    got = harness.resolve(MANIFEST, cell)
    cfg, traffic = got["config"], got["traffic"]
    files = gen.corpus_files(cfg, Path("/data"))
    qs = gen.queries(cfg, traffic, 2**32 + 7, files)
    warm = gen.warm_queries(cfg, traffic, 2**32 + 7, files)
    pairs = [(q["pattern"], q["ignore_case"], f)
             for c in qs for q in c for f in q["files"]]
    assert len(pairs) == len(set(pairs)), "a window pair repeats"
    warmed = {(q["pattern"], q["ignore_case"], f) for q in warm
              for f in q["files"] if q["family"] != "fill"}
    assert not warmed & set(pairs), "a window pair was warmed"
    owners = [{f for q in c for f in q["files"]} for c in qs]
    for i, a in enumerate(owners):
        for b in owners[i + 1 :]:
            assert not a & b, "two clients share a file"
    fams = [f["name"] for f in traffic["families"]]
    for c in qs:
        head = [q["family"] for q in c[: len(fams)]]
        assert sorted(head) == sorted(fams)
    assert gen.queries(cfg, traffic, 2**32 + 7, files) == qs


def test_no_tpu_prints_no_result(tmp_path):
    root = tiny.checkout(tmp_path)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "subtitles.sparse", "--seed", str(2**31 + 5),
                        "--seconds", "1", "--trace", "0"], cwd=root, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert '"correct"' not in p.stdout


def test_benchmark_files_alone_print_no_result(tmp_path):
    root = tiny.checkout(tmp_path, program=False)
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "subtitles.sparse", "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=root, capture_output=True,
                       text=True, timeout=300)
    assert p.returncode != 0
    assert '"correct"' not in p.stdout
