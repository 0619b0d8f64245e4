"""Reduction of a profiler trace of the measured window to device busy
time, the idle share, the top device operations and the longest idle
gaps, each gap named by the host region that was open in it.

``load`` reads the ``.xplane.pb`` that ``jax.profiler.stop_trace`` wrote
(with ``jax.profiler.ProfileData``) into a compact form: per device plane
the intervals of its ``XLA Ops`` line, and the host's annotated regions
(the program's ``TraceAnnotation`` regions, on when ``DGREP_TRACE_DIR`` is
set).  ``reduce`` works on that compact form alone, so the tests check it
on a small recorded slice (``tests/bench/trace_slice.json``).
"""

from __future__ import annotations

import glob
import os

# Host regions the program annotates (utils/trace.annotate callers); a
# gap is named by the region prefix before the first ':'.
HOST_REGIONS = ("map_read:", "map_compute:", "reduce_compute:", "feed:",
                "collect:")
DEVICE_LINE = "XLA Ops"


def find_xspace(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {trace_dir}, "
                           f"found {len(paths)}")
    return paths[0]


def load(path: str) -> dict:
    """The compact form: {"devices": {plane: [[op, start_ns, end_ns]]},
    "host": [[region, start_ns, end_ns]]}."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices: dict[str, list] = {}
    host: list = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            rows = devices.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name == DEVICE_LINE:
                    rows.extend([e.name, int(e.start_ns), int(e.end_ns)]
                                for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend([e.name, int(e.start_ns), int(e.end_ns)]
                            for e in line.events
                            if e.name.startswith(HOST_REGIONS))
    return {"devices": devices, "host": host}


def op_name(hlo: str) -> str:
    """``%_fdr_pallas.1 = u32[...] custom-call(...)`` -> ``_fdr_pallas``:
    the instruction's name without its HLO text or numeric suffix."""
    name = hlo.split(" = ", 1)[0].lstrip("%")
    base, _, suffix = name.rpartition(".")
    return base if base and suffix.isdigit() else name


def _merge(iv: list[tuple[int, int]]) -> list[tuple[int, int]]:
    out: list[list[int]] = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def reduce(compact: dict, t0_ns: int, t1_ns: int, top: int = 10) -> dict:
    """Busy seconds (the union of op intervals inside [t0, t1], averaged
    over the device planes), the window's seconds, and the breakdown."""
    planes = [p for p, rows in compact["devices"].items() if rows]
    if not planes:
        raise RuntimeError("the trace holds no device operation")
    busy_ns = []
    per_op: dict[str, int] = {}
    merged0: list[tuple[int, int]] = []
    for i, p in enumerate(sorted(planes)):
        iv = []
        for name, s, e in compact["devices"][p]:
            s, e = max(s, t0_ns), min(e, t1_ns)
            if e > s:
                iv.append((s, e))
                op = op_name(name)
                per_op[op] = per_op.get(op, 0) + (e - s)
        merged = _merge(iv)
        busy_ns.append(sum(e - s for s, e in merged))
        if i == 0:
            merged0 = merged
    gaps = []
    prev = t0_ns
    for s, e in merged0 + [(t1_ns, t1_ns)]:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)
    host = [(n.split(":", 1)[0], s, e) for n, s, e in compact["host"]]
    named = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:top]:
        best, label = 0, "no_host_region"
        for n, hs, he in host:
            ov = min(e, he) - max(s, hs)
            if ov > best:
                best, label = ov, n
        named.append([label, (e - s) / 1e9])
    ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
    return {
        "busy_s": sum(busy_ns) / len(busy_ns) / 1e9,
        "window_s": (t1_ns - t0_ns) / 1e9,
        "chips": len(planes),
        "breakdown": {"device_ops": [[n, v / 1e9] for n, v in ops],
                      "idle_gaps": named},
    }


def window_ns(compact: dict, t_trace: float, t0: float,
              t1: float) -> tuple[int, int]:
    """The window [t0, t1] (epoch seconds) on the trace's clock.  The
    profiler stamps events in ns from the start of the trace session,
    begun at ``t_trace``; a trace stamped with the epoch is taken as is."""
    starts = [s for rows in compact["devices"].values() for _, s, _ in rows]
    if starts and min(starts) > 10**17:
        return int(t0 * 1e9), int(t1 * 1e9)
    return int((t0 - t_trace) * 1e9), int((t1 - t_trace) * 1e9)
