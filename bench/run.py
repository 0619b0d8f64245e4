#!/usr/bin/env python3
"""Benchmark of the served grep path: one cell, one run.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout.  The cell (``BENCHMARK.json``) names a
configuration (``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<traffic>.json``).  A run:

1. set-up: holds the chip, generates the cell's data from ``--seed`` in
   parallel (numpy only) under ``bench/.run/<cell>/``, starts the service
   in this process as ``dgrep serve`` does (``GrepService`` with spans on,
   ``ServiceServer``, 2 local workers), and warms up one query per query
   family over the cell's files;
2. window: ``clients`` closed-loop client processes (``client.py``, no
   jax) drive the HTTP API for ``--seconds``; with ``--trace 1`` the
   profiler traces this process over the window;
3. after the window: peak device memory, each completed job's explain
   report, the service stopped, a seeded sample of the completed queries
   compared with GNU grep (``check.py``).

Prints an earlier ``counts`` line (cache hits and misses, kernel bytes
per family, interpret flags, compiles in the window, set-up split), the
numbers compared with their limits as the last lines of stderr, and as
the last stdout line one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (end-to-end with ``--trace 0``, per-layer with
``--trace 1``), ``device`` and, traced, ``breakdown``.  Without a TPU, or
with fewer chips than the cell asks for, it exits 3 with no result.
"""

from __future__ import annotations

import time

T_PROCESS = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import multiprocessing  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

import check  # noqa: E402
import client  # noqa: E402
import devtrace  # noqa: E402
import gen  # noqa: E402
import harness  # noqa: E402

# The persistent compile cache: a fixed directory inside the checkout
# (its path is part of the cache key), and every program cached, so the
# second run of a cell compiles nothing.
CACHE_DIR = ROOT / ".jax_cache"
# An executable built (compiled, or loaded from the persistent cache),
# and a persistent-cache load.
_COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                   "/jax/compilation_cache/cache_hits")


# How long one warm-up query may take (a cold run compiles its pattern).
WARM_TIMEOUT_S = 600.0
# How long after the window's close a query in flight may still answer.
GRACE_S = 60.0


class RunFailed(Exception):
    """The run cannot produce a result; the message says why."""


def host_has_tpu() -> bool:
    """TPU chips on this host (v4: /dev/accel*, v5e: /dev/vfio/<n>),
    decided without importing jax."""
    import glob

    return bool(glob.glob("/dev/accel*") or glob.glob("/dev/vfio/[0-9]*"))


def _env_for_program(cfg: dict, run_dir: Path, trace: bool) -> None:
    """The deployment's settings (the configuration's ``service_env``),
    the compile cache, and with ``trace`` the program's annotations."""
    os.environ.update(cfg.get("service_env", {}))
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "-1"
    os.environ.setdefault("DGREP_LOG", "WARNING")
    if trace:
        # the program's TraceAnnotation regions (utils/trace.annotate)
        os.environ["DGREP_TRACE_DIR"] = str(run_dir / "trace")


def _claim_devices(chips: int, require_tpu: bool):
    """Hold the host's chips, as ``dgrep serve`` does at start-up."""
    if str(ROOT) not in sys.path:
        sys.path.insert(0, str(ROOT))
    from distributed_grep_tpu.__main__ import _device_entry

    if _device_entry(claim=True):
        raise RunFailed("the service could not hold the chips")
    import jax

    devs = jax.local_devices()
    if require_tpu and (devs[0].platform != "tpu" or len(devs) < chips):
        raise RunFailed(f"need {chips} tpu chip(s), jax found "
                        f"{len(devs)} {devs[0].platform} device(s)")
    return devs


def generate(cfg: dict, seed: int, data_dir: Path) -> list[Path]:
    """The configuration's corpus files, one spawned process per file."""
    files = gen.corpus_files(cfg, data_dir)
    ctx = multiprocessing.get_context("spawn")
    n = max(1, min(len(files), os.cpu_count() or 1))
    with ctx.Pool(n) as pool:
        pool.starmap(gen.write_corpus_file,
                     [(cfg, seed, i, str(p)) for i, p in enumerate(files)])
    return files


class Daemon:
    """The service in this process: ``dgrep serve``'s start-up."""

    def __init__(self, work_root: Path, workers: int):
        from distributed_grep_tpu.runtime.service import (
            GrepService,
            ServiceServer,
        )

        self.service = GrepService(work_root=work_root, spans=True)
        self.server = ServiceServer(self.service, host="127.0.0.1", port=0)
        self.server.start()
        self.service.start_local_workers(workers)
        self.addr = f"127.0.0.1:{self.server.port}"

    def stop(self) -> None:
        self.server.shutdown()
        self.service.stop()


def warm_up(d: Daemon, cfg: dict, traffic: dict, seed: int,
            files: list[Path]) -> None:
    """The fill queries (they fill the corpus cache), then every pool
    pattern: each pattern's programs are built here, not in the window.
    One at a time: an engine built while other jobs load the host can
    recalibrate under the model-cache lock and hang the service (PERF.md,
    Open questions)."""
    for q in gen.warm_queries(cfg, traffic, seed, files):
        body = json.dumps(gen.job_body(q, traffic["n_reduce"])).encode()
        rec = client.one_query(d.addr, body, traffic["poll_s"],
                               deadline=time.time() + WARM_TIMEOUT_S)
        if not rec["ok"]:
            raise RunFailed(f"warm-up query {q['family']} failed: "
                            f"{rec['error']}")


class CompileCounter:
    """Backend compiles and persistent-cache loads, by time stamp."""

    def __init__(self):
        import jax

        self.stamps: list[tuple[str, float]] = []

        def on_duration(event: str, _secs: float, **kw) -> None:
            if event in _COMPILE_EVENTS:
                self.stamps.append((event, time.time(), kw.get("fun_name")))

        def on_event(event: str, **kw) -> None:
            if event in _COMPILE_EVENTS:
                self.stamps.append((event, time.time(), None))

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def between(self, t0: float, t1: float) -> dict:
        """Counts by event, and by function for executables built."""
        out: dict = {}
        for ev, t, fun in self.stamps:
            if t0 <= t <= t1:
                key = ev.rsplit("/", 1)[-1]
                out[key] = out.get(key, 0) + 1
                if fun:
                    out.setdefault("functions", []).append(fun)
        return out


def run_window(d: Daemon, spec: dict, queries: list[list[dict]],
               seconds: float, run_dir: Path, trace_dir: Path | None):
    """Start the clients, release them together, trace the window if
    asked, and collect their records.  Returns (records, t0, t1,
    trace_bounds_ns, exhausted)."""
    ctx = multiprocessing.get_context("spawn")
    ready, go = ctx.Queue(), ctx.Queue()
    procs = []
    for c, qs in enumerate(queries):
        s = dict(spec, queries=qs, out=str(run_dir / f"client-{c}.json"))
        procs.append(ctx.Process(target=client.client_main,
                                 args=(s, ready, go), daemon=True))
    for p in procs:
        p.start()
    try:
        for _ in procs:
            ready.get(timeout=120)
        t_trace = None
        if trace_dir is not None:
            import jax

            # no Python tracer: it hooks every call of every thread and
            # would slow the host work the window measures
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            t_trace = time.time()
            jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
        t0 = time.time() + 0.05
        t1 = t0 + seconds
        for _ in procs:
            go.put((t0, t1))
        tb = None
        if trace_dir is not None:
            time.sleep(max(0.0, t1 - time.time()))
            jax.profiler.stop_trace()
            tb = (t_trace, t0, t1)
        for p in procs:
            p.join(timeout=max(1.0, t1 - time.time()) + GRACE_S + 30)

    finally:
        for p in procs:
            if p.is_alive():
                p.kill()
                p.join(10)
    recs, exhausted = [], 0
    for c in range(len(procs)):
        path = run_dir / f"client-{c}.json"
        if not path.exists():
            raise RunFailed(f"client {c} wrote no records "
                            f"(exit code {procs[c].exitcode})")
        got = json.loads(path.read_text())
        recs.extend(got["records"])
        exhausted += got["exhausted"]
    return recs, t0, t1, tb, exhausted


def window_metrics(done: list[dict], t0: float, t1: float) -> dict:
    """Served bytes per second over the whole window, and the median
    wall of every query completed in it."""
    walls = [r["t_done"] - r["t_post"] for r in done]
    return {
        "served_gbps": sum(r["bytes"] for r in done) / 1e9 / (t1 - t0),
        "query_p50_s": statistics.median(walls) if walls else None,
    }


def counts_of(done: list[dict]) -> dict:
    """What proves the traffic does what it was built for, from explain."""
    out: dict = {"jobs": len(done), "corpus_cache": {}, "result_cache": {},
                 "kernel_bytes": {}, "interpret": 0,
                 "max_host_bytes_per_query": 0, "route": {}}
    for r in done:
        rt = r["explain"].get("routing", {})
        for k, v in (rt.get("corpus_cache") or {}).items():
            out["corpus_cache"][k] = out["corpus_cache"].get(k, 0) + v
        res = rt.get("result_cache") or {}
        for k in ("hits", "partial_hits", "misses"):
            out["result_cache"][k] = out["result_cache"].get(k, 0) + res.get(k, 0)
        out["route"][rt.get("route")] = out["route"].get(rt.get("route"), 0) + 1
        host = 0
        for fam, m in (rt.get("engine_modes") or {}).items():
            if fam.startswith("batch"):
                continue
            row = out["kernel_bytes"].setdefault(fam, {})
            for k, v in (m.get("kernel_bytes") or {}).items():
                row[k] = row.get(k, 0) + v
            host += (m.get("kernel_bytes") or {}).get("host", 0)
            out["interpret"] += m.get("interpret", 0)
        out["max_host_bytes_per_query"] = max(out["max_host_bytes_per_query"],
                                              host)
    return out


def sample_for_check(done: list[dict], queries: list[list[dict]], n: int,
                     seed: int) -> list[tuple[dict, dict]]:
    """A seeded sample of the completed queries, the same number from
    each family as far as each has them."""
    rng = gen.np.random.default_rng([seed, 41])
    by_fam: dict[str, list[dict]] = {}
    for r in sorted(done, key=lambda r: (r["client"], r["k"])):
        by_fam.setdefault(r["family"], []).append(r)
    per = math.ceil(n / max(1, len(by_fam)))
    out = []
    for fam in sorted(by_fam):
        rows = by_fam[fam]
        for i in sorted(rng.choice(len(rows), size=min(per, len(rows)),
                                   replace=False)):
            r = rows[int(i)]
            out.append((queries[r["client"]][r["k"]], r))
    return out


def main(argv: list[str] | None = None, require_tpu: bool = True) -> int:
    """Entry point; ``require_tpu=False`` (tests only) skips the look for
    a chip and runs on whatever device jax has."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="compare the control's records (check.py) in the "
                         "program's place: not a benchmark run, its "
                         "'correct' must read false")
    args = ap.parse_args(argv)
    try:
        return _run(args, require_tpu)
    except (RunFailed, harness.BenchError) as e:
        print(f"bench: {e}", file=sys.stderr, flush=True)
        return 2


def _run(args, require_tpu: bool) -> int:
    if not (ROOT / "distributed_grep_tpu").is_dir():
        raise RunFailed(f"no program (distributed_grep_tpu) under {ROOT}")
    cell = harness.resolve(harness.load_manifest(ROOT), args.workload)
    cfg, traffic = cell["config"], cell["traffic"]
    if require_tpu and not host_has_tpu():
        print("bench: no TPU on this host", file=sys.stderr, flush=True)
        return 3
    run_dir = BENCH / ".run" / args.workload
    shutil.rmtree(run_dir, ignore_errors=True)
    (run_dir / "data").mkdir(parents=True)
    trace = bool(args.trace)
    _env_for_program(cfg, run_dir, trace)
    try:
        devs = _claim_devices(cell["cell"]["chips"], require_tpu)
    except RunFailed as e:
        print(f"bench: {e}", file=sys.stderr, flush=True)
        return 3
    pk = harness.peaks(devs[0].device_kind) if require_tpu else None
    compiles = CompileCounter()

    t_gen = time.time()
    files = generate(cfg, args.seed, run_dir / "data")
    t_daemon = time.time()
    d = Daemon(run_dir / "work", workers=2)
    try:
        t_warm = time.time()
        warm_up(d, cfg, traffic, args.seed, files)
        queries = gen.queries(cfg, traffic, args.seed, files)
        spec = {"addr": d.addr, "traffic": traffic, "grace_s": GRACE_S}
        t_clients = time.time()
        recs, t0, t1, tb, exhausted = run_window(
            d, spec, queries, args.seconds, run_dir,
            run_dir / "trace" if trace else None)
        memory_peak = max(int((x.memory_stats() or {}).get(
            "peak_bytes_in_use", 0)) for x in devs)
        done = [r for r in recs if r["ok"] and r["t_done"] <= t1]
        for r in done:  # the routing and stage report, after the window
            r["explain"] = client.call(d.addr, "GET",
                                       f"/jobs/{r['job_id']}/explain")
        if any(not r["ok"] for r in recs):
            # where the service stands when a query got no answer
            import faulthandler

            with open(run_dir / "stacks.txt", "w") as f:
                faulthandler.dump_traceback(file=f, all_threads=True)
    finally:
        d.stop()
    setup = {"generate_s": t_daemon - t_gen, "daemon_start_s": t_warm - t_daemon,
             "warm_up_s": t_clients - t_warm, "clients_start_s": t0 - t_clients,
             "process_s": t_gen - T_PROCESS}
    setup_s = t0 - T_PROCESS
    (run_dir / "records.json").write_text(json.dumps(recs))

    for r in done:
        r["bytes"] = sum(os.path.getsize(f)
                         for f in queries[r["client"]][r["k"]]["files"])
    unanswered = [r for r in recs if r.get("error") == client.NO_ANSWER]
    failed = [r for r in recs if not r["ok"] and r not in unanswered]
    counts = counts_of(done)
    counts.update(setup_split=setup, compiles_in_window=compiles.between(t0, t1),
                  clients_out_of_queries=exhausted,
                  queries_after_window=len([r for r in recs if r["ok"]
                                            and r["t_done"] > t1]),
                  walls_by_family={f: sorted(round(r["t_done"] - r["t_post"], 3)
                                             for r in done if r["family"] == f)
                                   for f in sorted({r["family"] for r in done})})
    print("counts " + json.dumps(counts, sort_keys=True), flush=True)

    sample = sample_for_check(done, queries, traffic["check_sample"],
                              args.seed)
    cmp = check.compare(sample, control=bool(args.control))
    compared = {
        "unanswered_queries": {"value": len(unanswered), "limit": 0},
        "mismatched_records": {"value": cmp["mismatched_records"], "limit": 0},
        "outputs_changed_after_read": {
            "value": cmp["outputs_changed_after_read"], "limit": 0},
        "sampled_queries": {"value": len(sample), "limit": 1},
    }
    correct = (not unanswered and cmp["mismatched_records"] == 0
               and cmp["outputs_changed_after_read"] == 0 and len(sample) >= 1)
    print("checked " + json.dumps(cmp["rows"]), flush=True)

    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": memory_peak}
    result: dict = {"correct": correct, "attempted": len(recs),
                    "failed": len(failed)}
    if trace:
        compact = devtrace.load(devtrace.find_xspace(str(run_dir / "trace")))
        w0, w1 = devtrace.window_ns(compact, *tb)
        red = devtrace.reduce(compact, w0, w1)
        (run_dir / "trace_reduced.json").write_text(json.dumps(red))
        ctx = {"jobs": done, "trace": red, "peaks": pk}
        metrics = {}
        for m in cell["per_layer"]:
            v = harness.metric_reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device.update(busy_s=red["busy_s"], window_s=red["window_s"])
        result["metrics"] = metrics
        result["device"] = device
        result["breakdown"] = red["breakdown"]
    else:
        e2e = dict(window_metrics(done, t0, t1), setup_s=setup_s)
        result["metrics"] = {m["name"]: {"value": e2e[m["name"]],
                                         "unit": m["unit"]}
                             for m in cell["end_to_end"]
                             if e2e.get(m["name"]) is not None}
        result["device"] = device
    result["compared"] = compared
    for k, v in compared.items():
        print(f"compared {k}: {v['value']} (limit {v['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
