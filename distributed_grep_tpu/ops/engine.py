"""GrepEngine: compiled pattern + device scan + host stitching, one object.

Engine selection, fastest first (the pluggable-backend story the north star
pins — CPU grep and TPU grep are interchangeable behind the application
interface):

1. ``shift_and`` — literal/class sequences <= 32 symbols: bit-parallel VPU
   scan (Pallas kernel on TPU, XLA scan elsewhere);
2. ``nfa``       — general regex (alternations, repeats, '^') <= 128
   Glushkov positions: bit-parallel position-automaton Pallas kernel
   (models/nfa.py, ops/pallas_nfa.py) — per-word bit-ops (range-compare
   or lane-gather B), so it keeps Pallas throughput where the DFA's
   per-byte table gather would fall off the cliff;
3. ``dfa``       — anything the subset compiler handles within the state
   cap ('$' accepts, big patterns, pattern-set banks): vectorized DFA
   table scan (XLA);
4. ``re``        — host fallback (Python re per line) for patterns outside
   the subset (e.g. newline-consuming) — the reference's own strategy
   (application/grep.go:20-30), kept as the escape hatch.

Orthogonal modes: ``fdr`` (large literal sets — Pallas bucket filter +
exact host confirm, models/fdr.py), ``pairset`` (all-1-2-byte sets —
exact row-partition pair kernel, no confirm, models/pairset.py), and
``approx`` (``max_errors=k`` agrep matching — k+1-row bit-parallel
recurrence, models/approx.py).

Large documents are scanned in segments (bounded device memory — the
reference instead reads whole files and cannot handle files larger than
RAM, worker.go:72-76); segment starts and stripe starts are boundary
positions whose lines get exact host re-scans (ops/lines.py).
"""

from __future__ import annotations

import os as _os
import re as _re
import time as _time_mod
from dataclasses import dataclass
from functools import partial

import numpy as np

from distributed_grep_tpu.models.aho import compile_aho_corasick_banks
from distributed_grep_tpu.models.fdr import (
    FP_CEILING_PER_BYTE,
    FdrError,
    FdrModel,
    compile_fdr,
)
from distributed_grep_tpu.models.dfa import (
    DfaTable,
    RegexError,
    build_stride_table,
    choose_stride,
    compile_dfa,
    reference_scan,
    enumerate_literal_set,
)
from distributed_grep_tpu.models.approx import (
    MAX_ERRORS,
    ApproxModel,
    line_matches as approx_line_matches,
    scan_reference as approx_scan_reference,
    try_compile_approx,
)
from distributed_grep_tpu.models.nfa import GlushkovModel, compile_scan_model
from distributed_grep_tpu.models.shift_and import (
    ShiftAndModel,
    filtered_for_device,
    try_compile_shift_and,
)
from distributed_grep_tpu.ops import lines as lines_mod
from distributed_grep_tpu.utils import spans as spans_mod
from distributed_grep_tpu.utils.logging import get_logger

# A cold XLA/Mosaic compile runs ~20-40 s with no observable progress;
# the scan declares it as a bounded grace window on its progress callback
# (per fresh layout shape) so a tight failure-detector window tolerates
# compiles without being blind to hangs.
COMPILE_GRACE_S = float(_os.environ.get("DGREP_COMPILE_GRACE_S", "90"))

import threading as _threading_mod

from distributed_grep_tpu.utils import lockdep as _lockdep


def _accepts_grace_kwarg(progress) -> bool:
    """Whether a progress callback can take ``grace_s=`` — decided from
    its SIGNATURE (once per scan), not by catching TypeError around the
    live call, which cannot distinguish 'callback lacks the kwarg' from a
    TypeError raised inside the callback body (round-4 ADVICE).  C
    callables without an introspectable signature are assumed modern: a
    TypeError from them is then a real bug and propagates."""
    import inspect

    try:
        inspect.signature(progress).bind(grace_s=COMPILE_GRACE_S)
        return True
    except TypeError:
        return False
    except ValueError:  # no introspectable signature (C callable)
        return True


log = get_logger("engine")

# Persistent read-ahead pools for scan_file's disk/scan overlap (round 6):
# ONE one-slot daemon pool per scanning thread, PROCESS-wide — shared by
# every engine, so neither constructing engines in a loop nor scanning
# thousands of files spawns threads (the old per-file ThreadPoolExecutor
# measured real overhead on a 2,000-file grep -r, round-5 note).  Entries
# for dead threads are pruned (pool shut down via its sentinel) on the
# next pool creation, so a process that churns worker threads does not
# accumulate idle daemon readers.
_reader_pools: dict = {}
_reader_pools_lock = _lockdep.make_lock("reader-pools")


def _thread_reader_pool():
    me = _threading_mod.get_ident()
    with _reader_pools_lock:
        pool = _reader_pools.get(me)
        if pool is None:
            live = {t.ident for t in _threading_mod.enumerate()}
            for ident in [k for k in _reader_pools if k not in live]:
                _reader_pools.pop(ident).shutdown(wait=False)
            from distributed_grep_tpu.ops.device_scan import _DaemonPool

            pool = _DaemonPool(1, thread_name_prefix="dgrep-read")
            _reader_pools[me] = pool
        return pool

# Coarse span path: above this many candidate lines per segment, per-line
# Python confirm would crawl — one native DFA pass over the whole segment
# (C, ~GB/s, vectorized line mapping) resolves everything instead.
SPAN_CONFIRM_LINE_LIMIT = 4096

# ------------------------------------------------ cross-job model cache
# The grep-as-a-service regime (runtime/service.py) reconfigures engines
# per task as jobs multiplex over shared workers; without a cache every
# pattern re-pays model compile (AC banks, FDR plans, Glushkov builds)
# and — on a real chip — the ~20-40 s first XLA/Mosaic compile per fresh
# (mode, mesh, shape) key.  cached_engine() memoizes whole engines
# by their construction arguments: a cache hit returns the SAME
# engine object, so its _compiled_keys / jit caches / uploaded device
# tables come along for free and the compile-grace path is skipped on
# the repeat submit.  Engines are scan-thread-safe by construction
# (thread-local stats/nl stash, per-thread reader pools — the same
# contract concurrent worker slots already rely on), so sharing one
# across jobs is the round-4 sharing story, widened.
DEFAULT_MODEL_CACHE_ENTRIES = 32


def env_model_cache_entries(default: int = DEFAULT_MODEL_CACHE_ENTRIES) -> int:
    """Entry cap for the cross-job compiled-model cache — the ONE parser
    of DGREP_MODEL_CACHE (0 disables caching; malformed keeps the
    default, matching env_batch_bytes' shrug-off policy)."""
    raw = _os.environ.get("DGREP_MODEL_CACHE")
    if raw is None or raw == "":
        return default
    try:
        return max(0, int(raw))
    except ValueError:
        return default


from collections import OrderedDict as _OrderedDict

# io_ok: holding the cache lock ACROSS engine construction is the design
# (same-pattern races collapse into one compile) — blocking under it is
# the lock's purpose, not an accident.
_model_cache_lock = _lockdep.make_lock("model-cache", io_ok=True)
_model_cache: "_OrderedDict[tuple, GrepEngine]" = _OrderedDict()
# Counters get their OWN lock: cached_engine holds _model_cache_lock across
# a whole engine construction (seconds for big literal sets), and every
# scan() stamps these counters into its stats — the stamp must never stall
# behind another thread's compile.
_model_cache_stats_lock = _lockdep.make_lock("model-cache-stats")
_model_cache_stats = {
    "compile_cache_hits": 0,
    "compile_cache_misses": 0,
    "compile_cache_evictions": 0,
}


def _count_cache(key: str, n: int = 1) -> None:
    with _model_cache_stats_lock:
        _model_cache_stats[key] += n


def model_cache_counters() -> dict:
    """Copy of the cache counters, or {} when the cache was never touched
    (so zero-activity processes never grow stats/piggyback keys)."""
    with _model_cache_stats_lock:
        if not any(_model_cache_stats.values()):
            return {}
        return dict(_model_cache_stats)


def model_cache_clear() -> None:
    """Drop every cached engine and zero the counters (tests)."""
    with _model_cache_lock:
        _model_cache.clear()
        with _model_cache_stats_lock:
            for k in _model_cache_stats:
                _model_cache_stats[k] = 0


def _hashable(v):
    if isinstance(v, (list, tuple)):
        return tuple(_hashable(x) for x in v)
    if isinstance(v, dict):
        return tuple(sorted((k, _hashable(x)) for k, x in v.items()))
    return v


def cached_engine(
    pattern: str | None = None,
    *,
    patterns: list[str] | None = None,
    **kw,
) -> tuple["GrepEngine", str]:
    """A (possibly shared) engine for these construction args, plus the
    cache verdict: "hit" (same object as a previous call — model compile
    AND the per-shape compile-grace bookkeeping are skipped), "miss"
    (constructed and cached), or "off" (cache disabled via
    DGREP_MODEL_CACHE=0, or the args are uncacheable — mesh engines are
    EXPLICITLY bypassed: jax.sharding.Mesh hashes by value, but equal-
    shaped meshes over different device sets would collide, and a mesh
    engine's sharded state is tied to ITS devices — always construct
    fresh; an explicit devices= LIST is bypassed for the same reason,
    while the symbolic devices="all" (the grep_tpu default) stays
    cacheable).

    The cache relies on one invariant: an engine never changes its
    plan, models or kernels after __init__ — they are a pure function of
    the construction args, so one object can answer every job that asks
    with the same key, concurrently.

    Construction runs UNDER the cache lock: two workers racing the same
    pattern serialize into one compile + one hit instead of two compiles
    (the whole point in the service regime); distinct-pattern
    constructions serialize too — the accepted cost, bounded by one
    model compile.  The constructor never calls back into the cache, so
    holding the (non-reentrant) lock across it cannot deadlock."""
    cap = env_model_cache_entries()
    key: tuple | None = (
        pattern,
        _hashable(patterns) if patterns is not None else None,
        _hashable(kw),
    )
    dev = kw.get("devices")
    if kw.get("mesh") is not None or not (dev is None or isinstance(dev, str)):
        key = None
    else:
        try:
            hash(key)
        except TypeError:
            key = None
    if cap <= 0 or key is None:
        return GrepEngine(pattern, patterns=patterns, **kw), "off"
    with _model_cache_lock:
        eng = _model_cache.get(key)
        if eng is not None:
            _model_cache.move_to_end(key)
            _count_cache("compile_cache_hits")
            return eng, "hit"
        eng = GrepEngine(pattern, patterns=patterns, **kw)
        _model_cache[key] = eng
        _count_cache("compile_cache_misses")
        evicted = 0
        while len(_model_cache) > cap:
            _model_cache.popitem(last=False)
            evicted += 1
        if evicted:
            _count_cache("compile_cache_evictions", evicted)
        return eng, "miss"


@dataclass
class ScanResult:
    matched_lines: np.ndarray  # sorted 1-based line numbers (always exact)
    # EXACT matched-line count — always equals matched_lines.size, on every
    # mode/backend (unified in round 3: it used to mean end offsets on
    # exact paths, pre-confirm candidates on the filter paths, making
    # cross-mode numbers non-comparable).  Kept as a field so scan_file can
    # sum it across chunks.  Telemetry lives in engine.stats instead:
    # "candidates" (pre-confirm filter hits), "end_offsets" (exact match
    # end offsets where a path computes them).
    n_matches: int
    bytes_scanned: int


class GrepEngine:
    """Scan documents for one compiled pattern (or literal pattern set)."""

    def __init__(
        self,
        pattern: str | None = None,
        *,
        patterns: list[str] | None = None,  # multi-literal set -> Aho-Corasick
        ignore_case: bool = False,
        backend: str = "device",  # "device" (jnp/pallas) | "cpu" (host re/native)
        max_errors: int = 0,  # agrep: match within <= k edit errors
        devices: object = None,  # None = default device; "all" = every local
        # chip (segments round-robin across them); or an explicit list
        mesh: object = None,  # jax.sharding.Mesh: each segment's lanes shard
        # across the mesh and the SAME Pallas kernels run per device under
        # shard_map with a psum'd candidate count (parallel/sharded_kernels)
        mesh_axis: object = "data",
        pattern_axis: object = None,  # FDR mode on a 2D mesh: shard
        # same-plan filter banks over this axis (EP — tables are the
        # sharded operand) while lanes shard over mesh_axis
        interpret: bool = False,  # force Pallas interpret mode (CI mesh tests)
        target_lanes: int = 1024,
        segment_bytes: int = 64 * 1024 * 1024,
        max_states: int = 4096,
        max_states_per_bank: int = 1 << 16,
        device_min_bytes: int | None = None,  # inputs smaller than this
        # scan on host even on a device engine: a device round-trip is
        # latency-bound (~ms per dispatch) while the
        # exact host scanners do sub-MB inputs in <= low ms — the grep -r
        # many-small-files regime.  None = DGREP_DEVICE_MIN_BYTES or 1 MB.
        batch_bytes: int | None = None,  # scan_batch packing window: small
        # inputs accumulate until the packed buffer reaches this size, then
        # flush as ONE dispatch (ops/layout.BatchPacker) — the cross-file
        # batching that puts the many-small-files regime back on the
        # kernels.  None = DGREP_BATCH_BYTES or 32 MB; 0 disables packing
        # (scan_batch then degrades to per-item scans).
        corpus_bytes: int | None = None,  # device corpus cache budget
        # (ops/layout.CorpusCache): scans with a content key keep their
        # packed/padded segments device-resident so a repeat query over
        # unchanged inputs skips the read/pack/upload path entirely.
        # None = DGREP_CORPUS_BYTES, else off (0) on CPU backends and
        # DEFAULT_CORPUS_BYTES_ACCEL on real accelerators; 0 disables.
    ):
        if (pattern is None) == (patterns is None):
            raise ValueError("exactly one of pattern / patterns is required")
        if max_errors and patterns is not None:
            raise ValueError("max_errors applies to a single pattern, not a set")
        self.backend = backend
        self.devices = devices
        self.mesh = mesh
        self.mesh_axis = mesh_axis
        self.pattern_axis = pattern_axis
        self._interpret = interpret
        if mesh is not None and devices is not None:
            raise ValueError("mesh and devices are mutually exclusive")
        if mesh is not None:
            # fail at construction, not inside the scan's kernel-failure
            # net (a bad axis name there would masquerade as a Mosaic
            # failure and silently demote the engine to its slow path)
            known = set(mesh.shape)
            lane_axes = (
                (mesh_axis,) if isinstance(mesh_axis, str) else tuple(mesh_axis)
            )
            if not lane_axes or not set(lane_axes) <= known:
                raise ValueError(
                    f"mesh_axis {mesh_axis!r} not in mesh axes {sorted(known)}"
                )
            if pattern_axis is not None and (
                pattern_axis not in known or pattern_axis in lane_axes
            ):
                raise ValueError(
                    f"pattern_axis {pattern_axis!r} must name a mesh axis "
                    f"outside mesh_axis {lane_axes}"
                )
        self.target_lanes = target_lanes
        self.segment_bytes = segment_bytes
        if device_min_bytes is not None:
            self.device_min_bytes = device_min_bytes
        else:
            # ONE parse (ops/layout.env_device_min_bytes), shared with the
            # map-split planner's "small file" bound — the two sides of
            # the batching contract can't drift on a malformed override
            from distributed_grep_tpu.ops.layout import env_device_min_bytes

            self.device_min_bytes = env_device_min_bytes()
        if batch_bytes is not None:
            self.batch_bytes = int(batch_bytes)
        else:
            # ONE parse for the env override, shared with the map-split
            # planner (JobConfig.effective_batch_bytes) — a stricter parse
            # here would crash worker engines on an env var the planner
            # already shrugged off
            from distributed_grep_tpu.ops.layout import env_batch_bytes

            self.batch_bytes = env_batch_bytes()
        # None = resolve lazily at scan time (_corpus_budget): the env
        # knob wins, else the backend decides — and probing the backend
        # here would import jax on CPU-only constructions
        self.corpus_bytes = (
            int(corpus_bytes) if corpus_bytes is not None else None
        )
        self.ignore_case = ignore_case
        # Shard-index inputs (distributed_grep_tpu/index): the ORIGINAL
        # query as constructed, captured BEFORE the literal-set routing
        # below rebinds pattern/patterns — requirements derive from the
        # query text via index.plan (the daemon-side planner derives from
        # the same inputs, so the two sides cannot disagree on
        # eligibility).  Resolved lazily in _index_requirements.
        self._index_query = (
            pattern,
            tuple(patterns) if patterns is not None else None,
            bool(ignore_case),
            int(max_errors),
        )
        self._index_req: object = False  # False = unresolved; None = ineligible

        self.shift_and: ShiftAndModel | None = None
        self._sa_filtered: ShiftAndModel | None = None  # rare-class device filter
        self.glushkov: GlushkovModel | None = None
        self.glushkov_exact: GlushkovModel | None = None
        self.table: DfaTable | None = None
        # Pattern sets beyond one automaton's uint16 state space compile to
        # several independent banks (Hyperscan-style ruleset sharding); each
        # bank is one extra device pass and matched lines are unioned.
        self.tables: list[DfaTable] = []
        self._dev_tables: dict | None = None  # device -> bank tables
        self._re_fallback: _re.Pattern[bytes] | None = None
        self.fdr: FdrModel | None = None
        self._fdr_dev_tables: dict | None = None  # device -> reach tables
        self._fdr_ep_dev_tables = None  # stacked pattern-axis-sharded tables
        self.pairset = None  # exact short-set model (models/pairset.py)
        self._fdr_pairset = None  # device engine for a mixed set's 1-byte
        # members (OR'd into the FDR candidate words)
        self._pairset_dev_tables: dict | None = None
        self._fdr_confirm = None  # utils/native.ConfirmSet (FDR mode only)
        # Compile-grace bookkeeping: every (kernel, layout shape) this
        # engine has COMPLETED a dispatch for.  A dispatch whose key is not
        # in here may block on a fresh XLA/Mosaic compile (~20-40 s on a
        # cold cache), so it declares a grace window on the progress
        # callback first — per SHAPE, not once per process: a job over
        # differently-sized files jit-specializes per distinct tail layout
        # (round-4 review finding).  Keys are added only after the kernel
        # call returns (compile done), so concurrent scans blocked on the
        # same compile each declare their own grace.
        self._compiled_keys: set = set()
        self._accel_cached: bool | None = None  # see _accel_backend
        # THREAD-LOCAL: one engine is scanned concurrently by worker slots
        # sharing the app module (grep_tpu), and a shared stash would let
        # thread A consume thread B's newline index whenever their splits
        # happen to be the same length — silently wrong nullable-at-$
        # results.  Each scan() call owns its thread's slot.
        import threading as _threading

        self._nl_local = _threading.local()
        self._nfa_filter = False  # Glushkov model is a candidate superset
        self.approx: ApproxModel | None = None
        self._approx_all_lines = False
        # Device-path observability (populated by _scan_device, empty for
        # the re/native modes): filter candidates, host confirm seconds,
        # scan wall seconds — the numbers behind the tuner's
        # max(scan, confirm) overlap model.  Read with .get().
        # Backed per-THREAD (the stats property below): concurrent worker
        # slots each see the counters of their own scan; a shared dict let
        # one slot's `self.stats = {}` reset land under another slot's
        # in-flight `+=` (crash or cross-scan telemetry bleed).  Within
        # one scan, _scan_device captures its dict once so collect-pool
        # threads mutate the owning scan's counters.
        self._stats_local = _threading.local()

        # Hyperscan-style literal decomposition: a regex that denotes a
        # finite literal set — alternations / small class products like
        # (volcano|anarchism|needle) — routes to the pattern-set engines
        # (AC banks + FDR device filter), which scan such sets faster than
        # the Glushkov NFA kernel compiled from the same regex.  Shift-and-
        # eligible patterns keep their (faster still) single-pass path, and
        # approximate matching keeps the regex form.
        self._literal_set_source: str | None = None
        sa_model = None  # compiled here, reused by the single-pattern branch
        routed_fdr: FdrModel | None = None  # probe model, reused when routed
        if pattern is not None and patterns is None and not max_errors:
            sa_model = try_compile_shift_and(pattern, ignore_case=ignore_case)
            if sa_model is None:
                lits = enumerate_literal_set(pattern, ignore_case=ignore_case)
                route = lits is not None and len(lits) >= 2
                if route and backend == "device":
                    # Only reroute when the FDR filter actually hosts the
                    # set (members >= 2 bytes, candidate rate under the
                    # ceiling): a set that falls back to the XLA DFA-bank
                    # device path would be far slower than the Glushkov
                    # NFA this regex otherwise compiles to.  The probe
                    # model is kept — the set branch reuses it.  Probe
                    # under the engine's chip-aware pricing: both the
                    # plan choice AND the round-5 native-crossover floor
                    # depend on n_chips, so a default-pricing probe
                    # would veto multi-chip-viable sets (and its model
                    # would need recompiling anyway).
                    try:
                        routed_fdr = compile_fdr(
                            lits, ignore_case=ignore_case,
                            pricing=self._fdr_base_pricing(),
                        )
                    except FdrError:
                        route = False
                if route:
                    self._literal_set_source = (
                        pattern if isinstance(pattern, str)
                        else pattern.decode("utf-8", "surrogateescape")
                    )
                    patterns = lits  # type: ignore[assignment]
                    pattern = None

        if max_errors:
            # agrep family (models/approx.py): literal/class-sequence
            # patterns only — the k-error recurrence rides the shift-and
            # symbol model.
            self.pattern = pattern
            if not 1 <= max_errors <= MAX_ERRORS:
                raise ValueError(f"max_errors must be 1..{MAX_ERRORS}")
            base = try_compile_shift_and(pattern, ignore_case=ignore_case)
            if base is None:
                raise ValueError(
                    "approximate matching needs a literal/class-sequence "
                    "pattern of <= 32 symbols (no anchors/alternation/repeats)"
                )
            if base.length <= max_errors:
                # deleting the whole pattern costs <= k edits: every line
                # (incl. empty ones) contains a match — like an empty regex
                self._approx_all_lines = True
            else:
                self.approx = try_compile_approx(
                    pattern, max_errors, ignore_case=ignore_case
                )
                assert self.approx is not None
            self.mode = "approx"
        elif patterns is not None:
            self.pattern = (
                self._literal_set_source or f"<set of {len(patterns)}>"
            )
            # Exact AC banks always exist: they are the CPU/native engine,
            # the DFA-bank device fallback, AND the host confirm oracle for
            # the FDR filter path.
            self.tables = compile_aho_corasick_banks(
                patterns, ignore_case=ignore_case,
                max_states_per_bank=max_states_per_bank,
            )
            self.table = self.tables[0]
            self.mode = "dfa"
            # Large literal sets: FDR bucketed filter (models/fdr.py) on the
            # Pallas path + exact per-line host confirm — the Hyperscan-style
            # architecture that keeps 1k..10k-pattern sets off the per-byte
            # table-gather cliff.  Literals shorter than 2 bytes can't form a
            # pair check and stay on the exact DFA banks (run additionally).
            if backend == "device":
                def _blen(p):
                    return len(p.encode("utf-8", "surrogateescape") if isinstance(p, str) else p)

                long_pats = [p for p in patterns if _blen(p) >= 2]
                short_pats = [p for p in patterns if _blen(p) < 2]
                # All members 1-2 bytes: the exact row-partition pair
                # kernel (models/pairset.py) beats BOTH alternatives —
                # FDR would filter 2-byte windows and pay a confirm
                # stream; the native route leaves the device idle.  Exact
                # on device, so it is tried first (round-4 closure of the
                # MXU question: the gather factorization wins the
                # shared-contraction formulation's ceiling).
                from distributed_grep_tpu.models.pairset import (
                    PairsetError,
                    compile_pairset,
                    expected_match_density,
                )

                if max(_blen(p) for p in patterns) <= 2:
                    # Exact kernel or not, matches are fetched O(matches)
                    # from the device: a set expected to match at ~0.1+/byte
                    # (a member like " " or "e") makes the sparse fetch the
                    # bottleneck and the device pass pointless — the same
                    # ceiling that keeps over-dense sets off the FDR filter
                    # routes these to the native host scanner.
                    dens = expected_match_density(
                        patterns, ignore_case=ignore_case
                    )
                    if dens > FP_CEILING_PER_BYTE:
                        log.warning(
                            "short set expected match density %.3g/byte is "
                            "over the device ceiling %.2g -> native host "
                            "scanner", dens, FP_CEILING_PER_BYTE,
                        )
                    else:
                        try:
                            self.pairset = compile_pairset(
                                patterns, ignore_case=ignore_case
                            )
                            self.mode = "pairset"
                        except PairsetError as e:
                            log.info(
                                "short set not pairset-representable: %s", e
                            )
                if self.mode != "pairset" and long_pats:
                    try:
                        # Chip-aware pricing (VERDICT r3 item 1): the host
                        # confirm threads are shared across every chip this
                        # engine drives, so the tuner prices the confirm leg
                        # at the per-chip share from the start (the routed
                        # decomposition probe above used the same pricing).
                        if short_pats:
                            # A dense 1-byte member ("e", " ") defeats the
                            # filter architecture outright: the pairset
                            # sidecar would turn every occurrence into a
                            # device-reported candidate, so the collect
                            # path's O(candidates) coordinate fetch +
                            # confirm stream swamps the scan it was meant
                            # to hide behind.  Same ceiling as the FDR
                            # plan's own candidate-rate gate; the whole set
                            # then routes loudly to the native scanner
                            # below.
                            short_dens = expected_match_density(
                                short_pats, ignore_case=ignore_case
                            )
                            if short_dens > FP_CEILING_PER_BYTE:
                                raise FdrError(
                                    f"mixed set's 1-byte members expect "
                                    f"{short_dens:.3g} matches/byte — over "
                                    f"the {FP_CEILING_PER_BYTE:.2g} device "
                                    f"candidate ceiling"
                                )
                        base_pricing = self._fdr_base_pricing()
                        # routed_fdr was probed under the same base
                        # pricing (chip count included) — reuse it as-is
                        self.fdr = routed_fdr or compile_fdr(
                            long_pats, ignore_case=ignore_case,
                            pricing=base_pricing,
                        )
                        confirm_pats = [
                            p for b in self.fdr.banks for p in b.patterns
                        ]
                        if short_pats:
                            # 1-byte members ride the exact pairset kernel
                            # ON DEVICE (a 1-byte set always factorizes:
                            # its columns are all-True, so rows collapse
                            # to <= 2 classes), OR'd into the FDR candidate
                            # words — the old per-segment host AC scan ran
                            # ~40x the device leg ON THE DISPATCH THREAD
                            # (0.2 s vs 5 ms per 64 MB segment); without
                            # a kernel backend the engine's DFA-bank/native
                            # fallback already covers the whole set.
                            self._fdr_pairset = compile_pairset(
                                short_pats, ignore_case=ignore_case
                            )
                            confirm_pats = (
                                confirm_pats + self._fdr_pairset.patterns
                            )
                        # Exact candidate confirm: bloom-filtered suffix
                        # probe + memcmp over the normalized members (native
                        # when available, ~4 ns/candidate —
                        # utils/native.ConfirmSet).  Runs
                        # per segment inside collect(), overlapped with the
                        # next segment's device scan — which is why the FDR
                        # tuner prices candidates at max(scan, confirm)
                        # rather than their sum (models/fdr.py).  Includes
                        # the short members, so the OR'd pairset matches
                        # confirm instead of being rejected.
                        from distributed_grep_tpu.utils.native import ConfirmSet

                        self._fdr_confirm = ConfirmSet(
                            confirm_pats, ignore_case=ignore_case,
                        )
                        self.mode = "fdr"
                    except FdrError as e:
                        log.info("pattern set FDR-ineligible: %s", e)
                # FDR-ineligible sets (density over the candidate ceiling,
                # short sets past the pairset class budget) must not
                # silently fall onto the XLA DFA-bank device path
                # (~0.1 GB/s — ~100x slower than the host's native MT
                # scanner).  Route to the native scanner loudly; keep the
                # device path only when the native lib is unavailable.
                if self.mode == "dfa":
                    self._route_native(
                        "pattern set ineligible for the FDR device filter"
                    )
        else:
            self.pattern = pattern
            try:
                self.table = compile_dfa(pattern, ignore_case=ignore_case, max_states=max_states)
                self.tables = [self.table]
                self.shift_and = sa_model
                if self.shift_and is not None:
                    self.mode = "shift_and"
                    # Rare-class device filter: check only the pattern's
                    # rarest byte-classes on device (fewer compares, the
                    # kernel's ALU bottleneck) — the span confirm pass
                    # already restores exact lines.  Disabled mid-scan if a
                    # corpus defeats the byte prior (see collect()).
                    self._sa_filtered = filtered_for_device(self.shift_and)
                else:
                    # compile_scan_model may return a bounded-repeat-relaxed
                    # FILTER automaton (fewer state words — models/nfa.py);
                    # its candidate lines then get the host confirm pass.
                    self.glushkov, self._nfa_filter = compile_scan_model(
                        pattern, ignore_case=ignore_case
                    )
                    if self._nfa_filter:
                        # exact automaton (may be None if over the position
                        # cap): the mid-scan fallback when a corpus defeats
                        # the relaxed filter's selectivity
                        from distributed_grep_tpu.models.nfa import (
                            try_compile_glushkov,
                        )

                        self.glushkov_exact = try_compile_glushkov(
                            pattern, ignore_case=ignore_case
                        )
                    else:
                        self.glushkov_exact = self.glushkov
                    self.mode = "nfa" if self.glushkov is not None else "dfa"
            except RegexError as e:
                # Outside the device subset (newline-consuming, state blowup,
                # unsupported syntax): host re fallback, like the reference.
                log.info("pattern %r -> host re fallback (%s)", pattern, e)
                flags = _re.IGNORECASE if ignore_case else 0
                from distributed_grep_tpu.models.dfa import (
                    expand_posix_classes,
                )

                # POSIX classes must expand before re sees them (re
                # misparses [[:digit:]]); matters for e.g. a \b pattern
                # whose body uses them — the rescue confirms candidate
                # lines with this matcher
                self._re_fallback = _re.compile(
                    expand_posix_classes(
                        pattern.encode("utf-8", "surrogateescape")
                        if isinstance(pattern, str) else bytes(pattern)
                    ), flags
                )
                self.mode = "re"
                if backend == "device":
                    # Rescue: a bounded repeat past the DFA expansion cap
                    # (e.g. {4,1000}) still compiles as a relaxed Glushkov
                    # FILTER (models/nfa.compile_scan_model widens {m,n}
                    # before building) — run it on device and confirm
                    # candidate lines with the exact re fallback (there is
                    # no DFA table to confirm with).  Without a Pallas
                    # backend the scan falls back to the per-line re loop.
                    try:
                        filt, is_filter = compile_scan_model(
                            pattern, ignore_case=ignore_case
                        )
                    except RegexError:
                        filt = None
                    if filt is None:
                        # \b/\B word boundaries (round 5): no exact
                        # automaton form at all, but the device filter
                        # strips zero-width assertions (language superset
                        # at the same end offsets) — '\berror\b' then
                        # scans as 'error' on the Pallas kernel and every
                        # candidate line is re-confirmed below, the same
                        # contract as the expansion-cap rescue.
                        from distributed_grep_tpu.models.nfa import (
                            compile_device_filter,
                        )

                        filt = compile_device_filter(
                            pattern, ignore_case=ignore_case
                        )
                    if filt is not None:
                        log.info(
                            "pattern %r rescued onto the device NFA filter "
                            "(%d positions, re-confirmed lines)",
                            pattern, filt.n_pos,
                        )
                        self.glushkov = filt
                        self.glushkov_exact = None
                        # always confirm: with no DFA oracle, even an
                        # "exact" Glushkov's stripe-boundary behavior is
                        # re-checked per line
                        self._nfa_filter = True
                        self.mode = "nfa"
        if self.mode == "dfa" and backend == "device" and self.tables:
            # Single patterns the bit-parallel kernels can't host exactly
            # ('$' accepts, > 128 Glushkov positions — e.g. a 200-char
            # literal) would otherwise run the per-byte XLA DFA device
            # path at ~0.1 GB/s.  First choice (round-5): a Glushkov
            # FILTER with the '$' dropped / body prefix-truncated
            # (models/nfa.compile_device_filter) — a candidate superset at
            # line granularity riding the same Pallas NFA kernel +
            # cand_words host-confirm contract as the relaxed-repeat
            # filters, which keeps everyday patterns like 'error$' on the
            # TPU.  Applies to mesh/interpret engines too (it IS the
            # device path — CI kernel coverage and the sharded step both
            # exercise it).  Only when no filter compiles: the native host
            # scanner (~3-25x the XLA DFA path) — same loud routing as
            # FDR-ineligible sets above, still excluding mesh/interpret
            # engines, which exist to run the device path.
            from distributed_grep_tpu.models.nfa import compile_device_filter

            filt = compile_device_filter(self.pattern, ignore_case=ignore_case)
            if filt is not None:
                log.info(
                    "pattern %r outside the exact device kernel subset -> "
                    "device NFA filter (%d positions; '$' dropped / prefix-"
                    "truncated), host-confirmed lines",
                    self.pattern, filt.n_pos,
                )
                self.glushkov = filt
                self.glushkov_exact = None  # no exact automaton exists here
                self._nfa_filter = True  # every candidate line is confirmed
                self.mode = "nfa"
            elif self.mesh is None and not self._interpret:
                self._route_native(
                    f"pattern {self.pattern!r} outside the device kernel "
                    f"subset"
                )
        if backend == "cpu" and self.mode != "re":
            self.mode = "native"  # host C scanner, same tables

    def _route_native(self, why: str) -> bool:
        """Loud device->host demotion (one policy, two callers: the
        FDR-ineligible set branch and the single-pattern device-subset
        branch): the native scanners give the exact same answers off the
        AC/DFA tables at ~3-100x the XLA DFA device path's ~0.1 GB/s.
        No-op when the native lib is missing — the device path, slow as
        it is, beats a Python table walk."""
        from distributed_grep_tpu.utils.native import native_available

        if not native_available():
            return False
        log.warning(
            "%s -> native host scanner (the XLA DFA device path would "
            "run ~100x slower)", why,
        )
        self.mode = "native"
        return True

    # ------------------------------------------------------- FDR pricing
    def _active_chip_count(self) -> int:
        """Chips whose scan streams share this host's confirm threads.

        Mesh mode: every device in the lane axes (plus the EP pattern axis
        when set — EP divides per-chip gather cost, so the scan leg scales
        with the full product) scans concurrently against ONE host confirm
        stream.  devices="all": every local chip round-robins segments.
        The reference's analogue is the per-worker fan-out cost model
        (coordinator.go:329-333) — one coordinator, many scanning workers."""
        if self.mesh is not None:
            axes = (
                (self.mesh_axis,) if isinstance(self.mesh_axis, str)
                else tuple(self.mesh_axis)
            )
            if self.pattern_axis is not None:
                axes = axes + (self.pattern_axis,)
            n = 1
            for a in axes:
                n *= int(self.mesh.shape[a])
            return n
        if self.devices == "all":
            import jax

            return max(1, len(jax.local_devices()))
        if self.devices:
            return max(1, len(list(self.devices)))
        return 1

    def _fdr_base_pricing(self):
        """default_pricing() with this engine's active chip count."""
        from dataclasses import replace as _replace

        from distributed_grep_tpu.models.fdr import default_pricing

        pricing = default_pricing()
        n_chips = self._active_chip_count()
        if n_chips > 1:
            pricing = _replace(pricing, n_chips=n_chips)
        return pricing

    # ------------------------------------------------------------------ scan
    @property
    def stats(self) -> dict:
        """Per-thread scan counters: the thread that ran scan() reads its
        own scan's numbers (bench/CLI read from the scanning thread), and
        concurrent worker slots cannot clobber each other."""
        d = getattr(self._stats_local, "d", None)
        if d is None:
            d = {}
            self._stats_local.d = d
        return d

    @stats.setter
    def stats(self, value: dict) -> None:
        self._stats_local.d = value

    def _kernel_backend_ok(self) -> bool:
        """One gate for "a Pallas kernel can actually run here": a real
        TPU backend, or interpret mode in CI.  Shared by every routing
        branch so the gates cannot silently diverge.  A kernel that fails
        at runtime raises (scan_device); it never flips this gate."""
        from distributed_grep_tpu.ops import pallas_scan

        return self._interpret or pallas_scan.available()

    def scan(self, data: bytes, progress=None, corpus_key=None) -> ScanResult:
        """Scan one in-memory document.  ``progress`` (optional callable,
        called as ``progress()`` at work milestones and
        ``progress(grace_s=N)`` ahead of a possible silent compile) is how
        a runtime failure detector keeps a tight liveness window over long
        scans (runtime/worker.py wires it to the heartbeat RPC).

        ``corpus_key`` (ops/layout.CorpusKey, derived from a FRESH stat of
        the input's backing file(s)) opts this scan into the device corpus
        cache: the packed/padded segments stay HBM-resident under the key
        and a repeat scan of the same content skips the pack + upload
        path.  The caller asserts ``data`` IS the bytes the key stats
        describe — scan_file/scan_batch derive key and bytes together."""
        self._nl_local.stash = None
        # Span-pipeline telemetry (utils/spans.py): each scan becomes one
        # structured per-scan record — mode, bytes, duration, and the
        # engine.stats counters (candidates, fallback flags) that
        # previously died with the process — open while the scan runs, so
        # the engine:* spans of the device path nest under it.  active()
        # is one thread-local read when the pipeline is off.
        with spans_mod.scan_span(self.mode) as rec:
            res = self._scan_body(data, progress, corpus_key)
            if rec is not None:
                # after the EOL fix-up: the record's match count must
                # equal the ScanResult the caller actually receives
                rec.update(bytes=len(data), stats=self.stats,
                           matches=res.n_matches)
        return res

    def _scan_body(self, data: bytes, progress, corpus_key) -> ScanResult:
        """scan()'s body: the engine route, the nullable-'$' EOL fix-up,
        and the cross-tier counters stamped into this thread's stats."""
        res = self._scan_impl(data, progress, corpus_key=corpus_key)
        # Nullable-at-'$' patterns (accept_eol at the line-start state,
        # e.g. '^$', '^ *$', 'x?$'): the empty match is valid at every
        # line's EOL — including EMPTY lines, which contain no byte for
        # the byte-level scanners to report on.  Scans attribute the
        # empty-line match to the '\n' PRECEDING the line, so they miss an
        # empty line at offset 0, and their padded trailing '\n'
        # symmetrically manufactures a match for a line that does not
        # exist when the data ends at a newline.  Post-processing owns
        # both edges for every backend: union in the empty lines, drop
        # anything past the last real line.  (Found by the round-4 wide
        # fuzz sweep, seed 3116.)
        if self.tables and any(bool(t.accept_eol[t.start]) for t in self.tables):
            with spans_mod.span("engine:stitch", cat="engine"):
                res = self._eol_fixup(data, res)
        cc = model_cache_counters()
        if cc:
            # cross-job model-cache telemetry rides engine.stats (and from
            # there the scan_span piggyback readers): stamped only when
            # the cache has ever been touched, so cache-free processes
            # keep their exact stats shape
            self.stats.update(cc)
        from distributed_grep_tpu.ops.layout import corpus_cache_counters

        ccorp = corpus_cache_counters()
        if ccorp:
            # same contract for the device corpus cache (hits/misses/
            # evictions + the bytes_resident gauge): nonzero-only
            self.stats.update(ccorp)
        import sys as _sys

        idx_mod = _sys.modules.get("distributed_grep_tpu.index.summary")
        if idx_mod is not None:
            # shard-index telemetry (index_shards_pruned/bytes_skipped/
            # maybe_scans/summaries_built), nonzero-only: sys.modules-
            # gated so index-free processes never import the tier just
            # to report nothing
            self.stats.update(idx_mod.index_counters())
        fol_mod = _sys.modules.get("distributed_grep_tpu.runtime.follow")
        if fol_mod is not None:
            # streaming-tier telemetry (follow_wakes/suffix_bytes_scanned/
            # stream_dropped_records), same nonzero-only sys.modules-gated
            # contract — rides engine.stats onto the heartbeat piggyback
            self.stats.update(fol_mod.follow_counters())
            # fused follow tier (round 21): follow_fused_queries/wakes/
            # suffix_bytes_saved — separate dict so the =0 no-op holds
            self.stats.update(fol_mod.follow_fused_counters())
        return res

    def _eol_fixup(self, data: bytes, res: ScanResult) -> ScanResult:
        """Nullable-at-'$' edges (see _scan_body): union in the empty
        lines, drop anything past the last real line."""
        stash = getattr(self._nl_local, "stash", None)
        nl = (
            stash[1] if stash is not None and stash[0] == len(data)
            # chunked scans stash per-piece indexes (wrong length) —
            # recompute over the full buffer then
            else lines_mod.newline_index(data)
        )
        n_lines = nl.size + (0 if not data or data.endswith(b"\n") else 1)
        ml = res.matched_lines[res.matched_lines <= n_lines]
        ml = np.union1d(ml, lines_mod.empty_line_numbers(data, nl))
        return ScanResult(ml.astype(np.int64), int(ml.size), res.bytes_scanned)

    def _scan_impl(self, data: bytes, progress=None, corpus_key=None) -> ScanResult:
        if self.mode == "re":
            return self._host_scan(self._scan_re, data, progress)
        if self._approx_all_lines or (
            self.tables and any(t.accept[t.start] for t in self.tables)
        ):
            # Pattern matches the empty string -> every line matches (grep
            # semantics); also sidesteps empty-match bookkeeping on device.
            n_lines = lines_mod.count_lines(data)
            return ScanResult(np.arange(1, n_lines + 1, dtype=np.int64), n_lines, len(data))
        if self.mode == "native":
            return self._host_scan(self._scan_native, data, progress)
        if self.mode == "pairset" and not self._kernel_backend_ok():
            # no kernel backend: the exact AC banks are the same
            # answer on host (native MT scanner when available)
            return self._host_scan(self._scan_native, data, progress)
        if self.mode == "nfa" and not self.tables:
            # DFA-less rescue (expansion-cap bounded repeats): the only
            # device engine is the Pallas NFA filter — without it (no TPU,
            # over budget, broken at runtime) there are no DFA banks to
            # fall back on, so the scan is the per-line re loop, like the
            # un-rescued mode.
            from distributed_grep_tpu.ops import pallas_nfa

            if not (
                self._kernel_backend_ok()
                and pallas_nfa.eligible(self.glushkov)
            ):
                return self._host_scan(self._scan_re, data, progress)
        if self._small_for_device(len(data)):
            # Host OR pending-batch (round 6): a sub-threshold input that
            # arrives through plain scan() takes the exact host engines —
            # round-trip-latency-bound on a real accelerator (~ms per
            # dispatch) while native memmem / AC-DFA
            # banks, or the re loop for the DFA-less NFA rescue, finish in
            # <= low ms.  The same input arriving through scan_batch()
            # instead JOINS a pending packed batch (ops/layout.BatchPacker)
            # and reaches the kernels as part of one amortized dispatch —
            # "host always" is no longer the only small-input story.
            # XLA-on-CPU "devices" are not gated (dispatch is ~µs there,
            # and the CI suite's device-path coverage runs on them).
            res = self._host_scan(self._host_scanner(), data, progress)
            self.stats["small_host_scan"] = True  # AFTER: scanners reset stats
            return res
        return self._scan_device(data, progress=progress, corpus_key=corpus_key)

    def _small_for_device(self, n_bytes: int) -> bool:
        """True when a PLAIN scan() of this size should reroute to the
        exact host engines rather than pay its own device dispatch.
        scan_batch's pack-vs-solo split uses the size threshold alone:
        packing amortizes dispatch overhead on every backend (interpret
        engines and XLA-on-CPU included), so it is not gated on
        _accel_backend the way the solo-host reroute is.  Probes the
        backend (resolving _accel_cached); jax-free callers use
        _small_route_cached."""
        return self._accel_backend() and self._small_route_cached(n_bytes)

    def _small_route_cached(self, n_bytes: int) -> bool:
        """_small_for_device's verdict WITHOUT the backend probe: reads
        the cached _accel_backend answer only, so it never touches jax
        (an unresolved flag reads False — for the
        corpus-cache opt-in that only costs one uncached scan, never a
        wrong answer or a hang)."""
        return (
            n_bytes < self.device_min_bytes
            and not self._interpret  # CI interpret engines exist to
            # exercise the kernels — never reroute them
            and self.mesh is None  # a mesh engine EXISTS to run the
            # sharded path (and dryrun_multichip asserts its psum
            # telemetry on tiny shapes — driver contract)
            and self.mode != "approx"  # the host approx oracle is a ~MB/s
            # Python recurrence; the device wins at any size
            and self._host_scanner() is not None
            and bool(self._accel_cached)
        )

    def _host_scanner(self):
        """The exact host engine for this pattern, or None if no host
        route exists (today every engine that reaches _scan_device has
        one — approx mode sets self.approx, sets compile AC banks,
        single patterns set tables or _re_fallback — but callers guard
        on this return rather than re-encoding that knowledge): the
        native scanners when tables exist (AC/DFA banks, memmem for
        literals) or the approx host recurrence; the re loop for the
        DFA-less NFA rescue."""
        if self.tables or self.approx is not None:
            return self._scan_native
        if self._re_fallback is not None:
            return self._scan_re
        return None

    def _accel_backend(self) -> bool:
        """True when jax's default backend is a real accelerator (tpu /
        gpu) — the regime where per-scan dispatch latency, not
        throughput, prices small inputs.  Cached: the answer cannot
        change within a process.  A backend that fails to initialise
        raises here."""
        cached = self._accel_cached
        if cached is None:
            import jax

            cached = jax.default_backend() != "cpu"
            self._accel_cached = cached
        return cached

    def _corpus_budget(self) -> int:
        """Effective device-corpus-cache byte budget for this engine's
        scans (0 = caching off).  Resolution order: the explicit
        ``corpus_bytes=`` construction arg, the DGREP_CORPUS_BYTES env
        knob (ONE parse, ops/layout.env_corpus_bytes), then the backend
        default — OFF on CPU (CI and plain host runs keep their exact
        pre-cache behavior), DEFAULT_CORPUS_BYTES_ACCEL on real
        accelerators (the service regime the cache exists for).  Mesh
        engines and explicit devices= LISTS always answer 0: resident
        segments are committed to specific devices, so sharing them
        across engines pinned to different sets would defeat the
        caller's pinning — the same bypass verdict as the model cache
        (the symbolic devices="all", the grep_tpu default, stays
        cacheable: every engine resolves it to the same local set and
        the round-robin device assignment is deterministic)."""
        if self.mesh is not None:
            return 0
        if self.devices is not None and not isinstance(self.devices, str):
            return 0
        if self.corpus_bytes is not None:
            return max(0, self.corpus_bytes)
        from distributed_grep_tpu.ops.layout import (
            DEFAULT_CORPUS_BYTES_ACCEL,
            env_corpus_bytes,
        )

        env = env_corpus_bytes()
        if env is not None:
            return env
        return DEFAULT_CORPUS_BYTES_ACCEL if self._accel_backend() else 0

    def _corpus_opt_in(self) -> bool:
        """Cheap, jax-FREE opt-in check for the corpus-cache paths that
        run at scan_file/scan_batch ENTRY — i.e. on engines (mode
        "re"/"native") that never touch jax at all.  The explicit arg
        and the env knob answer directly; the backend-default leg
        answers True only when a previous scan ALREADY probed the
        backend as an accelerator (_accel_cached) — it never probes
        itself, so host-only engines keep their zero-jax contract.  Cost: the
        first scan of an accelerator process runs uncached (the cache
        is empty then anyway); the second threads keys and populates.
        _corpus_budget() stays the authoritative resolution — called
        from ops/device_scan."""
        if self.mesh is not None or (
            self.devices is not None and not isinstance(self.devices, str)
        ):
            return False
        if self.corpus_bytes is not None:
            return self.corpus_bytes > 0
        from distributed_grep_tpu.ops.layout import env_corpus_bytes

        env = env_corpus_bytes()
        if env is not None:
            return env > 0
        return bool(self._accel_cached)

    # ------------------------------------------------------- shard index
    def _index_requirements(self):
        """This query's required-literal trigram requirements
        (index.plan.QueryRequirements), or None — index off
        (DGREP_INDEX=0) or ineligible (empty-match patterns, members
        under 3 bytes, approx mode, patterns outside the parser subset).
        The derivation is resolved once per engine; the env switch is
        re-read per call so the kill-switch works on cached engines.
        jax-free (index + models/dfa are numpy-only): safe at the
        scan_file/scan_batch entries, before any jax touch."""
        from distributed_grep_tpu.index import summary as index_summary

        if not index_summary.env_index_enabled():
            return None
        if self._index_req is False:
            from distributed_grep_tpu.index import plan as index_plan

            pat, pats, ic, me = self._index_query
            try:
                self._index_req = index_plan.requirements_for_query(
                    pattern=pat,
                    patterns=list(pats) if pats is not None else None,
                    ignore_case=ic, max_errors=me,
                )
            except Exception:  # noqa: BLE001 — derivation must never
                # break a scan: ineligible just means "scan everything"
                self._index_req = None
        return self._index_req

    def _index_publish_enabled(self) -> bool:
        """Whether this scan should BUILD summaries at all: only when a
        reuse surface exists — the persistent store is attached (the
        service threads <work_root>/index through the app) or the corpus
        cache is opted in (the in-process warm-query regime).  A one-shot
        CLI job has neither: building summaries its process will never
        consult would tax every cold local run for nothing.  Lookups and
        prunes stay ungated — they only fire when summaries already
        exist."""
        from distributed_grep_tpu.index import summary as index_summary

        return (
            index_summary.attached_store() is not None
            or self._corpus_opt_in()
        )

    def _index_pruned(self, key) -> "ScanResult":
        """Stamp one engine-side prune (counters + span instant + this
        thread's stats) and return the exact empty result — the summary
        proved no line of the shard can match, so "zero matched lines"
        is the true answer, for every caller semantics."""
        from distributed_grep_tpu.index import summary as index_summary

        index_summary.record_prune(key.n_bytes)
        spans_mod.instant("index:prune", cat="engine", bytes=key.n_bytes)
        self.stats = {}
        self.stats.update(index_summary.index_counters())
        return ScanResult(np.zeros(0, dtype=np.int64), 0, 0)

    def _index_publish(self, key, data: bytes) -> None:
        """Publish ``data``'s summary under ``key`` (memory + attached
        store) and mirror it onto the corpus-cache entry when one is
        resident.  Called AFTER the scan over ``data`` succeeded — the
        CorpusCache publish discipline — from the already-resident host
        bytes, so the build never sits on the cold read path."""
        from distributed_grep_tpu.index import summary as index_summary

        s = index_summary.publish_summary(key, data)
        if s is not None:
            from distributed_grep_tpu.ops.layout import corpus_cache

            corpus_cache().attach_summary(key, s)

    # A host-routed scan of a large in-memory split proceeds in
    # newline-aligned pieces with a progress stamp between pieces — the
    # same per-chunk exactness scan_file relies on (every engine mode is
    # exact over a chunk that starts at a line start), and what keeps a
    # tight failure-detector window honest over maps the device never
    # sees (native MT / re fallback routes, round-4 review finding: these
    # paths previously emitted no heartbeats at all, so a multi-GB
    # whole-bytes map was swept and re-executed forever).
    _HOST_CHUNK = 1 << 26

    def _host_scan(self, scanner, data: bytes, progress=None) -> ScanResult:
        if progress is None or len(data) <= int(1.5 * self._HOST_CHUNK):
            res = scanner(data)
            if progress is not None:
                progress()
            return res
        matched: list = []
        n_matches = 0
        end_offsets = 0
        lines_before = 0
        pos = 0
        while pos < len(data):
            end = min(pos + self._HOST_CHUNK, len(data))
            if end < len(data):
                cut = data.rfind(b"\n", pos, end)
                if cut >= pos:
                    end = cut + 1
                else:  # one line longer than the chunk: extend to its end
                    nxt = data.find(b"\n", end)
                    end = len(data) if nxt < 0 else nxt + 1
            piece = data[pos:end]
            res = scanner(piece)
            if res.matched_lines.size:
                matched.append(res.matched_lines + lines_before)
            n_matches += res.n_matches
            end_offsets += int(self.stats.get("end_offsets", 0))
            lines_before += lines_mod.count_lines(piece)
            pos = end
            progress()
        ml = (
            np.concatenate(matched) if matched else np.zeros(0, dtype=np.int64)
        )
        self.stats = {"end_offsets": end_offsets}
        return ScanResult(ml, n_matches, len(data))

    def _reader_pool(self):
        """The calling thread's persistent one-slot read-ahead pool —
        PROCESS-wide per scanning thread (see _thread_reader_pool), so
        constructing engines in a loop (fuzz sweeps, a worker
        reconfiguring per job) reuses one reader instead of accumulating
        pools, and concurrent worker slots never queue reads behind each
        other."""
        return _thread_reader_pool()

    def scan_file(self, path, chunk_bytes: int | None = None, emit=None,
                  progress=None, stop_after_match: bool = False,
                  stop=None, emit_chunk=None) -> ScanResult:
        """Stream a file of any size through the scanner: chunks are cut at
        newline boundaries (partial tail lines carry into the next chunk),
        so no line — and hence no grep match — ever spans a chunk, and host
        memory stays bounded by one chunk regardless of file size.  The
        reference reads whole files and cannot exceed worker RAM
        (worker.go:72-76); this is the end-to-end long-context path
        (SURVEY.md §5).

        ``emit(line_no, line_bytes)`` is called per matched line while the
        chunk is still in memory — collecting output costs O(matches), not
        a second pass.  Line numbers in the result are file-global.  A
        single line longer than chunk_bytes is accumulated whole (a line
        must fit in memory; grep semantics need the full line anyway).

        ``emit_chunk(lines_before, buf, matched_lines, nl_index)`` is the
        columnar alternative (round 5): called once per chunk that has
        matches, with the chunk-LOCAL 1-based matched line numbers and
        the chunk's newline index — the grep apps build one LineBatch per
        chunk from it (runtime/columnar.py) instead of paying a Python
        callback per matched line.

        Disk reads are pipelined (VERDICT r3 item 4): a one-slot reader
        thread fetches chunk i+1 while chunk i scans — the same shape as
        the device-feed double-buffer, one level up — so a disk-bound
        corpus pays max(read, scan) per chunk instead of their sum.
        Residual stall is recorded in stats["read_wait_seconds"] (~0 when
        the scan hides the read); host memory stays bounded by TWO chunks.

        ``stop_after_match=True`` stops reading after the first chunk that
        contains any matched line (GNU grep -q/-l stop at the first match;
        chunk granularity keeps the exactness machinery untouched).  The
        result then reports only the lines seen so far — presence, not a
        total count.  ``stop`` generalizes it: a zero-arg callable checked
        after each chunk's emits — return True to end the stream (callers
        whose emit applies a further filter, e.g. the -w/-x confirm,
        decide presence themselves).
        """
        import time as _time

        chunk_target = chunk_bytes or max(self.segment_bytes, 1 << 26)
        matched: list[int] = []
        n_matches = 0
        total = 0
        end_offsets = 0  # summed across chunks (per-chunk stats reset)
        read_wait = 0.0
        lines_before = 0
        carry = b""

        def scan_piece(buf: bytes, key=None) -> None:
            """One newline-bounded piece through scan(): match collection,
            per-line / columnar emit, file-global line accounting — shared
            by the streamed loop below and the corpus-cache warm path."""
            nonlocal n_matches, total, end_offsets, lines_before
            res = self.scan(buf, progress=progress, corpus_key=key)
            total += len(buf)
            n_matches += res.n_matches
            end_offsets += self.stats.get("end_offsets", 0)
            # scan() clears the thread's nl stash at entry and the host
            # scan modes re-stash this buffer's index — a length-matching
            # stash is therefore THIS scan's, never a stale collision;
            # reuse it instead of a second full newline pass over the
            # chunk (round 8: emit AND line accounting both need it)
            stash = getattr(self._nl_local, "stash", None)
            nl_idx = (
                stash[1] if stash is not None and stash[0] == len(buf)
                else None
            )
            if res.matched_lines.size:
                if emit is not None:
                    if nl_idx is None:
                        nl_idx = lines_mod.newline_index(buf)
                    for ln in res.matched_lines.tolist():
                        s, e = lines_mod.line_span(nl_idx, ln, len(buf))
                        emit(lines_before + ln, buf[s:e])
                elif emit_chunk is not None:
                    if nl_idx is None:
                        nl_idx = lines_mod.newline_index(buf)
                    emit_chunk(lines_before, buf, res.matched_lines, nl_idx)
                matched.extend((res.matched_lines + lines_before).tolist())
            if nl_idx is not None:
                # chunks are newline-terminated except possibly the final
                # one: reuse the index instead of re-counting
                lines_before += len(nl_idx) + (0 if buf.endswith(b"\n") else 1)
            else:
                lines_before += lines_mod.count_lines(buf)
            if progress is not None:
                progress()  # one work milestone per streamed chunk

        # Device corpus cache (round 7, ops/layout.CorpusCache): a
        # single-chunk file whose host bytes AND packed device segments
        # are already resident serves this scan with zero file reads and
        # zero uploads; a cold single-chunk scan threads its content key
        # so the NEXT query over unchanged bytes is warm.  Multi-chunk
        # files stream cold: their chunk cuts are content-dependent, and
        # the service regime this cache targets (log/code search) is many
        # files under the 64 MB chunk target, not one giant file.
        from distributed_grep_tpu.ops.layout import file_content_key

        # Shard index (distributed_grep_tpu/index): the query's required-
        # literal set vs this shard's trigram summary — "cannot match"
        # returns the exact empty result WITHOUT opening the file; a
        # maybe (or no summary yet) scans, and a successful whole-file
        # scan publishes the summary for the next query.  The lookup is
        # jax-free, like the corpus opt-in.  Both lookups are the scan's
        # host preparation (engine:prep).
        with spans_mod.span("engine:prep", cat="engine"):
            idx_req = self._index_requirements()
            idx_key = None
            idx_pub = False  # publish after a successful whole-file scan
            if idx_req is not None:
                from distributed_grep_tpu.index import (
                    summary as index_summary,
                )

                # lock-free routing gate: derive the key (realpath + stat)
                # only when a lookup could answer or a publish could land
                # — a summary-free one-shot process pays nothing per file
                if (index_summary.may_route()
                        or self._index_publish_enabled()):
                    idx_key = file_content_key(path)
                if idx_key is not None:
                    summ = index_summary.lookup_summary(idx_key)
                    if summ is not None:
                        if not idx_req.may_match(summ):
                            return self._index_pruned(idx_key)
                        index_summary.record_maybe()
                        spans_mod.instant("index:maybe", cat="engine")
                    else:
                        # single-chunk shards only — the corpus-cache
                        # regime: multi-chunk cuts are content-dependent
                        # and the target workload is many files under the
                        # chunk target; and only when a reuse surface
                        # exists (_index_publish_enabled — one-shot jobs
                        # skip the build entirely)
                        idx_pub = (
                            0 < idx_key.n_bytes <= chunk_target
                            and self._index_publish_enabled()
                        )

            corpus_k = None
            warm = None  # the corpus-cache entry that serves the whole file
            if self._corpus_opt_in():
                from distributed_grep_tpu.ops.layout import corpus_cache

                # one fresh stat serves both tiers when the index already
                # took it — key identity and validators must describe the
                # same snapshot for the publish below to be sound
                k = (idx_key if idx_key is not None
                     else file_content_key(path))
                # _small_route_cached: on a real accelerator a sub-
                # device_min_bytes solo file host-routes and can never
                # populate — skip the key/stat/lock work outright rather
                # than pay a guaranteed-miss lookup per query (reads the
                # CACHED backend flag only; jax-free)
                if (
                    k is not None and 0 < k.n_bytes <= chunk_target
                    and not self._small_route_cached(k.n_bytes)
                ):
                    corpus_k = k
                    ent = corpus_cache().lookup(k)
                    if ent is not None and len(ent.data) == k.n_bytes:
                        warm = ent

        idx_whole: bytes | None = None  # the whole keyed bytes, once in hand
        if warm is not None:
            # warm: the revalidated entry's host bytes stand in for the
            # disk read (stat drift would have evicted it) — the file is
            # never opened.  Counted at the cache (host-routed engines
            # never reach the resident_segments verdict in scan_device)
            corpus_cache().count_host_hit()
            scan_piece(warm.data, corpus_k)
            if idx_pub:
                # scan succeeded over the entry's (revalidated) bytes:
                # backfill the summary the index missed
                self._index_publish(corpus_k, warm.data)
            self.stats["end_offsets"] = end_offsets
            self.stats["read_wait_seconds"] = 0.0
            return ScanResult(
                np.asarray(matched, dtype=np.int64), n_matches, total
            )

        class _Ready:
            """Future-like wrapper for data already in hand (the first,
            synchronous read, and the EOF sentinel)."""

            def __init__(self, v: bytes):
                self._v = v

            def result(self) -> bytes:
                return self._v

        # The one-slot reader thread exists to overlap disk with scan —
        # pointless for files that fit in a single chunk.
        # BufferedReader.read(n) returns short only at EOF, so a full
        # block is the one case where more data may follow: the pool is
        # touched lazily at the first full block.  The pool itself is
        # PERSISTENT per scanning thread (round 6, _reader_pool): the old
        # per-file ThreadPoolExecutor paid a thread spawn + join per
        # multi-chunk file — measured real overhead on a 2,000-file
        # grep -r (round-5 note).
        pending = None  # the in-flight read future, if any

        def submit_read():
            nonlocal pending
            pending = self._reader_pool().submit(f.read, chunk_target)
            return pending

        key = None  # set by the whole-file unsplit branch only
        try:
            f = open(path, "rb")
            t0 = _time.perf_counter()
            with spans_mod.span("engine:prep", cat="engine"):
                nxt = _Ready(f.read(chunk_target))
            read_wait += _time.perf_counter() - t0  # the synchronous first
            # read is genuine stall: keep stats[read_wait_seconds] honest
            while True:
                t0 = _time.perf_counter()
                with spans_mod.span("engine:prep", cat="engine"):
                    block = nxt.result()  # the read-ahead's residual stall
                read_wait += _time.perf_counter() - t0
                if block:
                    # enqueue the NEXT read now; it overlaps this chunk's
                    # scan (short block = EOF: no read, no thread)
                    nxt = (
                        submit_read() if len(block) == chunk_target
                        else _Ready(b"")
                    )
                    buf = carry + block
                    whole_k = corpus_k if corpus_k is not None else (
                        idx_key if idx_pub else None
                    )
                    if (
                        whole_k is not None and total == 0
                        and len(buf) == whole_k.n_bytes
                        and file_content_key(path) == whole_k
                    ):
                        # The WHOLE single-chunk file is in hand and a
                        # fresh re-stat agrees: scan it UNSPLIT (the
                        # warm-serve path above proves whole-file-as-
                        # one-piece is exact).  The newline cut below
                        # would otherwise orphan an un-terminated tail
                        # into carry and leave the corpus key
                        # unthreaded on BOTH pieces — a no-trailing-
                        # newline file (common in code search) would
                        # never populate the cache.  The index-publish
                        # leg takes the same branch (same exactness
                        # argument) even when the corpus cache is off.
                        carry, final = b"", True
                        key = corpus_k  # the re-stat above just
                        # confirmed buf IS the keyed bytes; only the
                        # corpus key threads through scan() — the index
                        # summary publishes after the scan succeeds
                        if idx_pub:
                            idx_whole = buf
                    else:
                        cut = buf.rfind(b"\n")
                        if cut < 0:
                            carry = buf  # line longer than the chunk:
                            continue     # keep growing
                        carry, buf = buf[cut + 1 :], buf[: cut + 1]
                        final = False
                else:
                    buf, carry, final = carry, b"", True
                if buf:
                    # key is corpus_k ONLY when the unsplit branch above
                    # confirmed (fresh re-stat) that buf is the whole
                    # keyed file in one piece — every other piece,
                    # including a live-append tail that outgrew the
                    # stat, scans uncached
                    scan_piece(buf, key)
                    if idx_whole is not None:
                        # the scan over the whole keyed bytes SUCCEEDED:
                        # publish the shard summary (from the bytes
                        # already in hand — never an extra read)
                        self._index_publish(idx_key, idx_whole)
                        idx_whole = None
                    if (stop_after_match and n_matches) or (
                        stop is not None and stop()
                    ):
                        break  # presence settled: skip the rest of the file
                if final:
                    break
        finally:
            # The in-flight read must not outlive the file handle: cancel
            # a still-queued read, await one already running (bounded by a
            # single chunk read — what the old per-file pool shutdown also
            # waited for).  The pool itself stays alive for the next file.
            if pending is not None and not pending.cancel():
                try:
                    pending.result()
                except Exception:  # noqa: BLE001 — handle closes next
                    pass
            try:
                f.close()
            except NameError:
                pass  # open() itself failed
        self.stats["end_offsets"] = end_offsets
        self.stats["read_wait_seconds"] = read_wait
        return ScanResult(np.asarray(matched, dtype=np.int64), n_matches, total)

    # ------------------------------------------------- live-append suffix
    def scan_file_suffix(self, path, offset: int = 0, *, final: bool = False,
                         max_bytes: int | None = None, progress=None):
        """Scan the LIVE-APPEND suffix of ``path`` from ``offset`` — which
        MUST be a line start (the streaming tier's cursor invariant) — up
        to the last complete line.  Returns ``(res, consumed, data)``:
        the ScanResult over the suffix (matched_lines are suffix-local,
        1-based), the byte length actually consumed (the caller's cursor
        advance), and the scanned bytes (line text extraction happens
        while they are in hand).

        The partial tail line past the last newline is NOT consumed —
        the line-carry: the next wake re-reads it from the same offset,
        extended by whatever arrived since, so the emitted line set is
        byte-identical to a one-shot scan over the final file state.
        ``final=True`` (stream teardown / idle exit) includes an
        unterminated tail, matching the one-shot scanners' missing-
        trailing-newline behavior.  Exactness at every append boundary
        rides the DFA "'\\n' column == start state" invariant: the
        buffer begins at a line start and ends at a line boundary, so
        every kernel family scans it exactly like the same lines inside
        a whole-file scan (the same argument as cross-file batching).

        Live-append stat handling: the suffix NEVER threads a corpus key
        (appending content has no stable validator tuple — the cache's
        stale-never-served contract) and never consults the shard index
        (a stale trigram summary must not prune a standing query).
        ``max_bytes`` bounds one call's read (catch-up over a huge
        existing file proceeds in bounded steps; a capped read is cut at
        its last newline even under ``final``, and the caller simply
        continues from the advanced offset) — EXCEPT for a single line
        larger than the window: the read extends until a newline (or
        EOF) lands, because a newline-free full window would otherwise
        consume 0 bytes forever and permanently stall the cursor behind
        the giant line (memory is bounded by that one line, the same
        bound materializing it for emit needs anyway)."""
        cap = max_bytes or max(self.segment_bytes, 1 << 26)
        with open(path, "rb") as f:
            f.seek(offset)
            data = f.read(cap)
            # window_full: the last read filled its request, so the file
            # may extend beyond what we hold — the tail past the last
            # newline is then NEVER consumable, even under ``final``
            window_full = len(data) == cap
            if window_full and data.rfind(b"\n") < 0:
                while True:  # each chunk is newline-probed exactly once
                    more = f.read(cap)
                    if not more:
                        window_full = False
                        break
                    data += more
                    window_full = len(more) == cap
                    if not window_full or more.rfind(b"\n") >= 0:
                        break
        if not final or window_full:
            cut = data.rfind(b"\n")
            data = data[: cut + 1] if cut >= 0 else b""
        if not data:
            return (
                ScanResult(np.zeros(0, dtype=np.int64), 0, 0), 0, b""
            )
        res = self.scan(data, progress=progress)
        # per-scan suffix accounting (the module-level follow counters
        # aggregate across wakes; scan()'s tail merge may later overwrite
        # this key with the monotonic global — both are telemetry-only)
        self.stats["suffix_bytes_scanned"] = len(data)
        return res, len(data), data

    # ------------------------------------------------- cross-file batching
    def scan_batch(self, items, progress=None, emit=None,
                   index_prune: bool = False):
        """Scan many inputs, packing small ones into shared dispatches.

        ``items`` is an iterable of ``(name, data)`` where ``data`` is the
        input's bytes (or a filesystem path, read whole — callers with
        splits at or above device_min_bytes should stream those through
        scan_file themselves).  Inputs below device_min_bytes accumulate
        in a BatchPacker (ops/layout.py) and flush as ONE scan over the
        packed newline-terminated buffer whenever the next input would
        overflow ``batch_bytes``; larger inputs flush the pending batch
        (order is preserved) and scan solo.  Exactness at file granularity
        rides the two invariants the codebase already pins: every DFA
        '\\n' column is the start state (file boundaries are line starts,
        so every kernel family is exact there) and the host
        confirm/stitch pass owns stripe/segment boundaries — see the
        layout-module notes.

        Returns ``[(name, ScanResult)]`` in input order; matched_lines are
        per-file 1-based, bytes_scanned is the ORIGINAL blob length.
        ``emit(name, data, result)``, when given, is called per input
        while its blob is still in memory (the grep apps build their
        output records there).

        Telemetry lands in ``engine.stats`` after the call —
        ``batched_files``, ``batch_dispatches``, ``solo_dispatches``,
        ``dispatches_saved`` (= batched_files - batch_dispatches) and
        ``batch_fill_ratio`` (mean packed-buffer fill vs batch_bytes) —
        and each packed flush emits a ``scan:batch`` span on the span
        pipeline (utils/spans.py), so trace-export shows packed
        dispatches on the worker rows.

        Path items participate in the device corpus cache (round 7) when
        a byte budget is in force: solo files and packed windows thread
        content keys through scan(), and a repeat call over unchanged
        files serves host bytes AND device segments from the cache —
        zero reads, zero uploads.  A warm packed window is recognized
        BEFORE any member is read (the cache's window index maps a
        window's first member file to its stored member list; fresh
        stats of every member must match), so the whole window re-scans
        without touching the filesystem."""
        from distributed_grep_tpu.ops.layout import (
            BatchPacker,
            batch_content_key,
            corpus_cache,
            file_content_key,
            packed_size,
        )

        cap = max(0, int(self.batch_bytes))
        packer = BatchPacker(cap) if cap > 0 else None
        use_corpus = self._corpus_opt_in()  # jax-free entry
        cache = corpus_cache() if use_corpus else None
        # Shard index (distributed_grep_tpu/index): ``index_prune=True``
        # is the CALLER's assertion that per-item emits with empty data
        # and the (exact) empty result are equivalent to its real
        # semantics — true for print/count consumers, FALSE for invert
        # (the complement of nothing is nothing, not every line), so the
        # grep app passes ``not invert``.  Pruned path items are never
        # opened; cold-read members publish their summaries after the
        # flush's scan succeeds, and warm packed windows prune whole
        # (with their real cached member blobs — exact for every
        # consumer).
        idx_req = self._index_requirements()
        idx_on = idx_req is not None
        idx_pub_ok = idx_on and self._index_publish_enabled()
        if idx_on:
            from distributed_grep_tpu.index import summary as index_summary

            # lock-free routing gate (see scan_file): without a possible
            # lookup answer or a publish surface, skip all per-member
            # key/stat/lock work
            idx_on = index_summary.may_route() or idx_pub_ok
        pk_keys: list = []  # member content keys, parallel to the packer
        pk_pub: list = []  # (key, bytes) members to index-publish, ditto
        out: list = []
        read_wait = 0.0  # member-open stall; stamped like scan_file's so
        # path items (worker map_batch_paths handover — the read happens
        # HERE, inside map:compute, same shape as the map_path branch)
        # keep disk wait visible in engine stats / the span piggyback
        bstats = {
            "batched_files": 0, "batch_dispatches": 0,
            "solo_dispatches": 0, "fill_sum": 0.0,
        }

        def handle(name, data, res) -> None:
            if emit is not None:
                emit(name, data, res)
            out.append((name, res))

        def scan_packed(batch, names, win_key) -> None:
            """One packed window through scan() + demux + per-file emit —
            shared by the cold flush and the warm-window path (which
            reuses the CACHED PackedBatch: demux tables and member blobs
            come from the entry, not a re-read + re-pack)."""
            # the scan:batch span is open over the inner scan, whose own
            # scan:<mode> span nests under it
            with spans_mod.span(
                "scan:batch", cat="engine", mode=self.mode,
                files=len(batch), bytes=len(batch.data),
                fill_ratio=round(len(batch.data) / cap, 6),
            ) as span_args:
                res = self.scan(batch.data, progress=progress,
                                corpus_key=win_key)
                span_args["matches"] = res.n_matches
                if cache is not None and win_key is not None:
                    # record the demux tables + member blobs behind the
                    # entry this scan just published (no-op if it was not
                    # admitted) — what makes the next call's warm window
                    # possible without re-reading members
                    cache.attach_batch(win_key, batch)
                    if (idx_on and index_summary.lookup_summary(win_key)
                            is None):
                        # window-level summary (packed-window pruning on
                        # the warm path): built from batch.data, so
                        # boundary-spanning trigrams only ADD bits —
                        # over-approximate, never unsound.  Corpus-cache
                        # regimes only: without a resident window there
                        # is no warm-window scan to prune.
                        self._index_publish(win_key, batch.data)
                per_file = batch.demux(res.matched_lines)
            bstats["batched_files"] += len(batch)
            bstats["batch_dispatches"] += 1
            bstats["fill_sum"] += len(batch.data) / cap
            # member_blobs(): as-stored on a fresh pack, transient
            # slices of batch.data on a cache-slimmed warm window
            for name, blob, lines in zip(names, batch.member_blobs(),
                                         per_file):
                handle(name, blob, ScanResult(
                    lines.astype(np.int64), int(lines.size), len(blob)
                ))

        def flush() -> None:
            nonlocal pk_keys, pk_pub
            if packer is None:
                return
            keys, pk_keys = pk_keys, []
            pubs, pk_pub = pk_pub, []
            batch = packer.pack()
            if batch is None:
                return
            if len(batch) == 1:
                # nothing amortized: scan the original blob (no synthesized
                # terminator in bytes_scanned, no demux) and count it solo
                bstats["solo_dispatches"] += 1
                handle(batch.names[0], batch.blobs[0],
                       self.scan(batch.blobs[0], progress=progress,
                                 corpus_key=keys[0] if keys else None))
            else:
                win_key = batch_content_key(keys) if use_corpus else None
                scan_packed(batch, batch.names, win_key)
            # the members' scan succeeded: publish the summaries staged
            # at read time (per-member keys — what the service planner
            # prunes with)
            for ent in pubs:
                if ent is not None:
                    self._index_publish(*ent)

        def match_window(i, stored) -> list | None:
            """Fresh member keys when ``items[i:...]`` are path items for
            exactly the stored window's member files, in order — else
            None (the cold path then handles item i normally)."""
            ids = stored.identity[1]
            if i + len(ids) > len(items):
                return None
            keys = []
            for (_nm, d), ident in zip(items[i:i + len(ids)], ids):
                if isinstance(d, (bytes, bytearray, memoryview)):
                    return None
                k = file_content_key(d)
                if k is None or k.identity != ident:
                    return None
                keys.append(k)
            return keys

        items = list(items)  # the warm-window probe needs lookahead
        i = 0
        while i < len(items):
            name, data = items[i]
            is_blob = isinstance(data, (bytes, bytearray, memoryview))
            fk = keys = wk = ent = summ = None
            # the lookups (content key, warm window, shard index) are the
            # item's host preparation; scans and emits run outside
            with spans_mod.span("engine:prep", cat="engine"):
                if (use_corpus or idx_on) and not is_blob:
                    fk = file_content_key(data)
                if use_corpus and not is_blob and fk is not None \
                        and packer is not None:
                    stored = cache.window_for(fk)
                    keys = (
                        match_window(i, stored)
                        if stored is not None else None
                    )
                if keys is not None:
                    wk = batch_content_key(keys)
                    ent = cache.lookup(wk)
                    if not (
                        ent is not None and ent.batch is not None
                        # the ENGINE's cap governs warm content too:
                        # a window packed under a larger budget is
                        # not re-served once batch_bytes shrinks
                        # (per-dispatch memory bound; the cold path
                        # re-packs at the new granularity and the
                        # oversized entry ages out via LRU)
                        and len(ent.batch.data) <= cap
                    ):
                        ent = None
                if ent is not None and idx_on:
                    summ = (
                        ent.summary if ent.summary is not None
                        else index_summary.lookup_summary(wk)
                    )
                elif ent is None and idx_on and fk is not None:
                    summ = index_summary.lookup_summary(fk)
            if ent is not None:
                if summ is not None and not idx_req.may_match(summ):
                    # whole warm window pruned: members emit their
                    # REAL cached blobs with the (exact) empty
                    # result the summary proves — sound for every
                    # consumer incl. invert, and no union scan is
                    # dispatched
                    flush()
                    index_summary.record_prune(wk.n_bytes)
                    spans_mod.instant("index:prune", cat="engine",
                                      bytes=wk.n_bytes)
                    names_w = [nm for nm, _ in items[i:i + len(keys)]]
                    for nm, blob in zip(names_w, ent.batch.member_blobs()):
                        handle(nm, blob, ScanResult(
                            np.zeros(0, dtype=np.int64), 0, len(blob),
                        ))
                    i += len(keys)
                    continue
                if summ is not None:
                    # consulted and could not rule the query
                    # out: the warm-window scan is a maybe (the
                    # counter the dense-regime telemetry reads)
                    index_summary.record_maybe()
                flush()  # order-preserving, like a solo input
                cache.count_host_hit()
                scan_packed(
                    ent.batch,
                    [nm for nm, _ in items[i:i + len(keys)]],
                    wk,
                )
                if idx_pub_ok:
                    # backfill per-MEMBER summaries from the
                    # cached blobs (the warm path never iterates
                    # members, so a corpus-warm daemon would
                    # otherwise starve the planner of the
                    # per-file summaries it prunes with)
                    for mk, blob in zip(keys, ent.batch.member_blobs()):
                        if index_summary.lookup_summary(mk) is None:
                            self._index_publish(mk, blob)
                i += len(keys)
                continue
            i += 1
            idx_missing = False  # publish this member after its scan
            if idx_on and fk is not None:
                if summ is None:
                    idx_missing = idx_pub_ok
                elif not idx_req.may_match(summ):
                    if index_prune:
                        # "cannot match", and the caller declared empty-
                        # data emits exact: the file is never opened
                        flush()  # order-preserving, like a solo input
                        index_summary.record_prune(fk.n_bytes)
                        spans_mod.instant("index:prune", cat="engine",
                                          bytes=fk.n_bytes)
                        handle(name, b"", ScanResult(
                            np.zeros(0, dtype=np.int64), 0, 0
                        ))
                        continue
                    # caller needs the bytes (invert): scan as usual —
                    # still exact, the index just saves nothing here
                else:
                    index_summary.record_maybe()
            with spans_mod.span("engine:prep", cat="engine"):
                if not is_blob:
                    ent = (
                        cache.lookup(fk)
                        if cache is not None and fk is not None else None
                    )
                    if ent is not None and len(ent.data) == fk.n_bytes:
                        data = ent.data  # warm host bytes: no disk read
                        cache.count_host_hit()
                    else:
                        t_r = _time_mod.perf_counter()
                        with open(_os.fspath(data), "rb") as f:
                            data = f.read()
                        read_wait += _time_mod.perf_counter() - t_r
                        if fk is not None and (
                            len(data) != fk.n_bytes
                            or file_content_key(items[i - 1][1]) != fk
                        ):
                            fk = None  # changed between stat and read
                data = bytes(data)
            small = len(data) < self.device_min_bytes
            if packer is None or not small or packed_size(data) > cap:
                flush()  # order-preserving: pending smalls go first
                bstats["solo_dispatches"] += 1
                handle(name, data,
                       self.scan(data, progress=progress, corpus_key=fk))
                if idx_missing and fk is not None:
                    # the solo scan succeeded: publish this shard's summary
                    self._index_publish(fk, data)
                continue
            if not packer.fits(data):
                flush()
            packer.add(name, data)
            pk_keys.append(fk)
            pk_pub.append(
                (fk, data) if idx_missing and fk is not None else None
            )
        flush()
        # AFTER the last scan (each scan resets the thread's stats dict):
        # the batch counters describe the whole scan_batch call.
        st = self.stats
        st["batched_files"] = bstats["batched_files"]
        st["batch_dispatches"] = bstats["batch_dispatches"]
        st["solo_dispatches"] = bstats["solo_dispatches"]
        st["dispatches_saved"] = (
            bstats["batched_files"] - bstats["batch_dispatches"]
        )
        st["batch_fill_ratio"] = (
            round(bstats["fill_sum"] / bstats["batch_dispatches"], 6)
            if bstats["batch_dispatches"] else 0.0
        )
        st["read_wait_seconds"] = read_wait
        if idx_on:
            # re-stamp AFTER the last flush: members pruned after the
            # final dispatch would otherwise miss this call's stats (the
            # scan()-tail merge only sees counters as of its own scan)
            st.update(index_summary.index_counters())
        return out

    # ---------------------------------------------------------- host engines
    def _scan_re(self, data: bytes) -> ScanResult:
        self.stats = {}  # no device/telemetry legs on the re loop; also
        # clears a failed Pallas attempt's partial counters on rescan
        matched = []
        lines = data.split(b"\n")
        if lines and lines[-1] == b"":
            lines.pop()  # trailing '\n' closes the last line (grep -n);
            # also keeps scan_file's per-chunk line accounting exact
        for i, line in enumerate(lines, start=1):
            if self._re_fallback.search(line):
                matched.append(i)
        return ScanResult(np.asarray(matched, dtype=np.int64), len(matched), len(data))

    def _native_literal(self) -> bytes | None:
        """The pattern as one exact byte string, when it is one (every
        shift-and symbol a singleton class) — the memmem fast path."""
        if self.shift_and is None:
            return None
        out = []
        for ranges in self.shift_and.sym_ranges:
            if len(ranges) != 1 or ranges[0][0] != ranges[0][1]:
                return None
            out.append(ranges[0][0])
        return bytes(out)

    def _scan_native(self, data: bytes) -> ScanResult:
        lit = self._native_literal()
        if self.approx is not None:
            # host oracle (python recurrence) — correct, not a perf path;
            # the device XLA/Pallas cores are the fast approx engines
            offsets = approx_scan_reference(self.approx, data)
        elif lit is not None:
            # pure literal: native memmem scan (GB/s) instead of the
            # table-driven DFA walk (~0.3 GB/s single-thread)
            from distributed_grep_tpu.utils import native as native_mod

            offsets = native_mod.literal_scan(data, lit).astype(np.int64)
        elif self.tables:
            offsets = np.unique(np.concatenate(
                [reference_scan(t, data) for t in self.tables]
            ))
        else:
            offsets = np.zeros(0, dtype=np.int64)
        nl = lines_mod.newline_index(data)
        self._nl_local.stash = (len(data), nl)  # reused by scan()'s EOL leg
        # offsets are sorted on every branch above (literal_scan emits in
        # ascending order, np.unique sorts): one native linear merge
        lns = lines_mod.unique_match_lines(offsets, nl)
        self.stats = {"end_offsets": int(offsets.size)}
        return ScanResult(lns.astype(np.int64), int(lns.size), len(data))

    def _host_lines_batched(self) -> bool:
        """True when the host re-match is one native pass per DFA bank
        over the whole batch of lines; False for the approx model and
        the DFA-less re rescue, which match line by line."""
        return self.approx is None and bool(self.tables)

    def _host_lines_matching(
        self, data: bytes, nl: np.ndarray, line_nos: np.ndarray
    ) -> np.ndarray:
        """The subset of ``line_nos`` (sorted, unique, 1-based lines of
        ``data``) that match: the host verdict of the confirm and stitch
        legs of the device scan."""
        if self._host_lines_batched():
            return lines_mod.lines_matching(self.tables, data, nl, line_nos)
        if self.approx is not None:
            match = partial(approx_line_matches, self.approx)
        elif self._re_fallback is not None:
            # DFA-less NFA rescue (expansion-cap patterns): re is the oracle
            match = self._re_fallback.search
        else:
            return np.zeros(0, dtype=np.int64)
        keep = [
            ln for ln in np.asarray(line_nos, dtype=np.int64).tolist()
            if match(data[slice(*lines_mod.line_span(nl, ln, len(data)))])
        ]
        return np.asarray(keep, dtype=np.int64)

    def _host_line_passes(self, n_lines: int) -> int:
        """Host matcher passes one `_host_lines_matching` call over
        ``n_lines`` lines makes: one per bank, or one per line."""
        return len(self.tables) if self._host_lines_batched() else n_lines

    def _device_tables(self, dev=None) -> list[tuple]:
        """Per-bank device-resident scan tables, uploaded once per engine
        per device (multi-chip round-robin needs operands colocated with
        the compute device — call under jax.default_device(dev)).

        Each entry is ("stride", args) when the k-byte-stride composition
        pays (chunk/k scan steps, one gather each — see models/dfa
        StrideTable) or ("plain", args) for the per-byte core ('$' accepts,
        or class counts whose composed table would blow the budget)."""
        if self._dev_tables is None:
            self._dev_tables = {}
        if dev not in self._dev_tables:
            import jax.numpy as jnp

            tabs = []
            for t in self.tables:
                k = choose_stride(t)
                if k > 1:
                    st = build_stride_table(t, k)
                    tabs.append(("stride", (
                        jnp.asarray(st.trans_k.reshape(-1)),
                        jnp.asarray(st.byte_to_cls.astype(np.int32)),
                        jnp.int32(st.start),
                        st.k,
                        st.n_classes,
                    )))
                else:
                    tabs.append(("plain", (
                        jnp.asarray(t.trans.astype(np.int32).reshape(-1)),
                        jnp.asarray(t.byte_to_cls.astype(np.int32)),
                        jnp.asarray(t.accept),
                        jnp.asarray(t.accept_eol),
                        jnp.int32(t.start),
                        t.n_classes,
                    )))
            self._dev_tables[dev] = tabs
        return self._dev_tables[dev]

    def _fdr_device_tables(self, dev=None) -> list:
        """Per-bank FDR reach tables, uploaded once per engine per device
        (call under jax.default_device(dev))."""
        if self._fdr_dev_tables is None:
            self._fdr_dev_tables = {}
        if dev not in self._fdr_dev_tables:
            import jax.numpy as jnp

            from distributed_grep_tpu.ops import pallas_fdr

            self._fdr_dev_tables[dev] = [
                jnp.asarray(pallas_fdr.bank_device_tables(b)) for b in self.fdr.banks
            ]
        return self._fdr_dev_tables[dev]

    def _pairset_device_tables(self, dev=None):
        """Pairset scan tables, uploaded once per engine per device (an
        engine has at most one pairset model: the whole-set one in mode
        "pairset", or the short-member sidecar in mode "fdr")."""
        model = self.pairset if self.pairset is not None else self._fdr_pairset
        if self._pairset_dev_tables is None:
            self._pairset_dev_tables = {}
        if dev not in self._pairset_dev_tables:
            import jax.numpy as jnp

            from distributed_grep_tpu.ops import pallas_pairset

            self._pairset_dev_tables[dev] = jnp.asarray(
                pallas_pairset.device_tables(model)
            )
        return self._pairset_dev_tables[dev]

    def _fdr_ep_tables(self, pattern_axis):
        """Stacked pattern-axis-sharded FDR tables, built + uploaded once
        per engine — the EP analogue of _fdr_device_tables."""
        if self._fdr_ep_dev_tables is None:
            from distributed_grep_tpu.parallel import sharded_kernels as shk

            self._fdr_ep_dev_tables = shk.fdr_pattern_tables(
                self.fdr, self.mesh, pattern_axis
            )
        return self._fdr_ep_dev_tables

    # --------------------------------------------------------- device engine
    def _scan_device(self, data: bytes, progress=None,
                     corpus_key=None) -> ScanResult:
        """Per-segment device dispatch (ops/device_scan.py — split out
        round 5; the orchestration is the engine's, moved)."""
        from distributed_grep_tpu.ops.device_scan import scan_device

        return scan_device(self, data, progress=progress,
                           corpus_key=corpus_key)

def make_engine(
    pattern: str | None = None, patterns: list[str] | None = None, **kw
) -> GrepEngine:
    return GrepEngine(pattern, patterns=patterns, **kw)
