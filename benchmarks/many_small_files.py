"""Many-small-files sweep: packed cross-file batching vs per-file host scan.

The regime the headline configs never touch (BASELINE.json scans big
splits): a `grep -r`-shaped corpus of thousands of sub-megabyte files,
where dispatch overhead — not bandwidth — prices the work.  This sweep
measures both sides of ISSUE 3's acceptance bar:

* ``host``   — per-file ``engine.scan`` on the cpu backend (native
  scanners), one dispatch per file: the pre-batching story.
* ``packed`` — ``engine.scan_batch`` on the device backend: small files
  pack into DGREP_BATCH_BYTES windows and each window is ONE kernel
  dispatch (ops/layout.BatchPacker).

    python benchmarks/many_small_files.py [--files 2000] [--file-kb 32]
        [--pattern volcano | --set N] [--timing e2e|slope] [--check]

``--timing slope`` packs the whole corpus into one buffer and slope-times
the device-resident kernel (utils/slope.py via baseline_configs.slope_gbps)
— the per-chip kernel number, where e2e wall time also measures the
host link.  Prints exactly one JSON line.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

# Runnable as `python benchmarks/...` from anywhere: the repo root joins
# the FRONT of sys.path so the checkout being benchmarked always wins.
_root = Path(__file__).resolve().parent
if not (_root / "distributed_grep_tpu").is_dir():
    _root = _root.parent
if (_root / "distributed_grep_tpu").is_dir():
    sys.path.insert(0, str(_root))

import numpy as np

from distributed_grep_tpu.ops.engine import GrepEngine

WORDS = (
    "the of and to in a is that for it as was with be by on not he this are "
    "at from or have an they which one you were all her she there would "
    "fff needle volcano anarchism philosophy wikipedia"
).split()


def synth_files(n_files: int, file_bytes: int, needles: list[bytes],
                seed: int = 9) -> list[tuple[str, bytes]]:
    """English-like filler files; ~1 in 8 carries an injected needle (the
    grep -r shape: most files miss, some hit)."""
    rng = np.random.default_rng(seed)
    out = []
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
        blob = b"\n".join(lines)[:file_bytes]
        if i % 8 == 0 and needles:
            nd = needles[int(rng.integers(0, len(needles)))]
            pos = int(rng.integers(0, max(1, len(blob) - len(nd) - 1)))
            blob = blob[:pos] + nd + blob[pos + len(nd):]
        out.append((f"f{i:05d}", blob))
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--files", type=int, default=2000)
    ap.add_argument("--file-kb", type=float, default=32)
    ap.add_argument("--pattern", default="volcano")
    ap.add_argument("--set", type=int, default=0, metavar="N",
                    help="use an N-literal pattern set (FDR path) instead "
                         "of the single pattern")
    ap.add_argument("--batch-mb", type=float, default=32)
    ap.add_argument("--timing", default="e2e", choices=["e2e", "slope"],
                    help="e2e: scan_batch wall incl. transfers; slope: "
                         "device-resident chained passes over the packed "
                         "layout (slow-link environments)")
    ap.add_argument("--check", action="store_true",
                    help="assert packed per-file lines == host per-file")
    args = ap.parse_args()

    file_bytes = int(args.file_kb * 1024)
    patterns = None
    pattern = args.pattern
    if args.set:
        rng = np.random.default_rng(5)
        pats = {args.pattern}
        while len(pats) < args.set:
            k = int(rng.integers(5, 10))
            pats.add("".join(chr(c) for c in rng.integers(97, 123, size=k)))
        patterns, pattern = sorted(pats), None
        needles = [p.encode() for p in patterns[:20]]
    else:
        needles = [pattern.encode()]
    files = synth_files(args.files, file_bytes, needles)
    total = sum(len(b) for _, b in files)
    out: dict = {
        "bench": "many_small_files",
        "files": args.files,
        "file_bytes": file_bytes,
        "bytes": total,
        "pattern": pattern or f"<set of {len(patterns)}>",
    }

    # --- host leg: per-file scans, one dispatch per file -------------------
    host = GrepEngine(pattern, patterns=patterns, backend="cpu")
    host_results = []
    t0 = time.perf_counter()
    for name, blob in files:
        host_results.append((name, host.scan(blob)))
    host_s = time.perf_counter() - t0
    out["host_gbps"] = round(total / 1e9 / host_s, 3)
    out["dispatches_host"] = args.files

    # --- packed leg: scan_batch on the device engine -----------------------
    eng = GrepEngine(
        pattern, patterns=patterns, backend="device",
        batch_bytes=int(args.batch_mb * (1 << 20)),
    )
    t0 = time.perf_counter()
    packed_results = eng.scan_batch(files)
    warm_s = time.perf_counter() - t0  # includes jit compiles
    st = dict(eng.stats)
    out["mode"] = eng.mode
    out["batched_files"] = st.get("batched_files", 0)
    out["dispatches_packed"] = (
        st.get("batch_dispatches", 0) + st.get("solo_dispatches", 0)
    )
    out["dispatches_saved"] = st.get("dispatches_saved", 0)
    out["batch_fill_ratio"] = st.get("batch_fill_ratio", 0.0)

    if args.timing == "slope":
        # Device-resident kernel throughput over the PACKED layout: pack
        # the whole corpus into one buffer and slope-time it (chained
        # i-dependent windows inside one jit — utils/slope.py via the
        # baseline suite's per-mode setup).
        sys.path.insert(0, str(_root / "benchmarks"))
        from baseline_configs import slope_gbps

        from distributed_grep_tpu.ops.layout import BatchPacker

        packer = BatchPacker(total + args.files + 1)
        for name, blob in files:
            packer.add(name, blob)
        packed_all = packer.pack().data
        got = slope_gbps(eng, packed_all)
        if got is None:
            out["error"] = f"no device slope path for mode {eng.mode}"
        else:
            gbps, label = got
            out["packed_gbps"] = round(gbps, 3)
            out["engine"] = label
            out["timing"] = "slope(device-resident,packed)"
    else:
        # warmed rescan: the jit specializations exist now, so this is the
        # steady-state number (the first pass is reported as compile_s)
        t0 = time.perf_counter()
        packed_results = eng.scan_batch(files)
        dt = time.perf_counter() - t0
        out["packed_gbps"] = round(total / 1e9 / dt, 3)
        out["timing"] = "e2e"
        out["compile_s"] = round(warm_s - dt, 2)
    if out.get("packed_gbps") and out.get("host_gbps"):
        out["speedup_vs_host"] = round(out["packed_gbps"] / out["host_gbps"], 2)

    if args.check:
        mism = []
        hr = dict(host_results)
        for name, res in packed_results:
            want = hr[name].matched_lines
            if not np.array_equal(res.matched_lines, want):
                mism.append(name)
        out["check"] = "ok" if not mism else f"MISMATCH {mism[:5]}"
        out["matched_lines"] = int(
            sum(r.n_matches for _, r in packed_results)
        )
    print(json.dumps(out), flush=True)
    return 0 if "error" not in out and "MISMATCH" not in str(out.get("check", "")) else 1


if __name__ == "__main__":
    sys.exit(main())
