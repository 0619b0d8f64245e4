"""FDR-style bucketed literal-set filter model (Hyperscan's large-set idea,
re-derived for the TPU VPU's lane-gather primitive).

Large literal sets (BASELINE.json configs 3 and 5 — grep -f / Snort-style
rulesets) are the one workload where the reference's per-line regex loop
(/root/reference/application/grep.go:20-30) has no small automaton: an
Aho-Corasick DFA over 10k patterns has ~60k states, and a per-byte table
gather at that size is the XLA scan path's ~0.1 GB/s cliff.  Hyperscan's
answer is FDR: superimpose the set into a few *buckets*, filter the stream
with shift-AND over per-position reach tables, and confirm rare candidates
exactly.  This module is that idea rebuilt around what the TPU can do fast.

Design (v3 — round-2 final: per-check domains + cell-snapped clustering):

* 32 buckets — one uint32 per lane, the tile shape every kernel here uses.
* One *suffix window* per bank: every member is represented by its last
  ``m+1`` bytes (a true match always contains its suffix, so candidates
  stay a superset; the exact confirm restores precision).
* Reach tables indexed by a pair-domain hash ``h = ((b0*a) ^ (b1*b)) &
  (D-1)`` of two consecutive bytes.  **Each check chooses its own domain**
  (the kernel's lane-gather covers 128 entries per op, so a check costs
  D/128 gathers) — the unit of currency is the gather, and the information
  argument says a check's false-positive density depends only on
  ``n / (32 * D)``, i.e. on table bits, making the cost/density frontier
  flat in D.  What breaks the tie is the clustered check:
* **Cell-snapped clustered bucket assignment** — members are sorted by
  their final-pair hash at D=128 and buckets are runs of whole hash
  *cells* (a cell is never split across buckets).  Each bucket's density
  at the clustered check is then exactly its cell count / 128, and the
  *sum* over buckets is exactly 1 — independent of set size.  Because
  that property holds at ANY domain, the clustered check runs at the
  minimum D=128: **one gather buys a Σ-density-1 check** that would cost
  an unclustered plan ~log(32·d)/log(1/d) extra checks.  (v2 clustered at
  the filler domain and paid 4 gathers for it; that plus rank-straddled
  cells is where the 28-gather plan went.)
* A tunable **check plan**: ``(slot, family, domain)`` lookups.  Slot k
  covers the byte pair at depth m-1-k from the window end; two hash
  families (HASHES) give up to 2 checks per slot; checks sharing a slot
  AND together before entering the pipeline.  The tuner enumerates filler
  domain × lookup count × bank count and minimizes measured total cost
  (device gathers + expected confirm, overlapped), with expected candidate
  rates computed exactly from the built tables (``_fp_of_tables``).

For the 10k-pattern config-5 set this lands on clustered@128 + 3×D512 +
2×D256 = 17 gathers/byte at analytic fp ~2.7e-2 (measured ~12.2
GB/s/chip) — vs v2's 28 gathers at fp 9e-3 (7.8 GB/s) — because the
confirm side (native bloom-filtered suffix probe, utils/native.ConfirmSet)
got cheap enough to absorb the higher candidate rate while staying hidden
behind the device scan given the priced CONFIRM_THREADS host threads.
"""

from __future__ import annotations

import os
from dataclasses import dataclass

import numpy as np

NL = 0x0A
N_BUCKETS = 32
MAX_DEPTHS = 6  # pipeline slots; window = depths + 1 <= 7 bytes
DOMAINS = (128, 256, 512, 1024)  # kernel gathers per check = D / 128
CLUSTER_DOMAIN = 128  # the clustered check's domain: Σ-density 1 at 1 gather
# Two independent pair hash families; ANDing lookups of both families at
# one slot squares that slot's density (d -> d0*d1), which beats adding
# banks for dense full-alphabet sets.
HASHES = ((37, 101), (171, 59))
# Sets whose best achievable EXPECTED candidate rate (analytic x bias) is
# still above this are not worth filtering: beyond ~0.1/byte the host-side
# sparse decode + confirm legs stop hiding behind the device scan even
# with the full thread fan, and the exact DFA banks win.  compile_fdr
# raises and the engine keeps those instead.
FP_CEILING_PER_BYTE = 1e-1

# Total-cost model for the tuner, per scanned byte, calibrated on TPU v5e
# (2026-07-30, probe in ops/pallas_fdr.py docstring): the 128-entry lane
# gather issues at ~4.5 cycles per (8,128) vreg and is the kernel's
# bottleneck resource — ~4.7 ps per gather per byte at unroll=4.  One
# expected candidate costs ~4 ns of confirm (measured: the native
# bloom-filtered suffix probe, utils/native.ConfirmSet, ~3.8-4.3
# ns/candidate single-thread on the build host over sorted offsets at
# config-5 densities).  The engine overlaps the confirm of segment i with
# the device scan of segment i+1, so the steady-state per-byte cost is
# max(scan, confirm) plus a small non-overlapped share — the objective
# below — not their sum.
COST_PS_PER_GATHER = 4.7
# Measured on the real config-5 run (2026-07-30, engine.stats): FDR-biased
# candidates confirm at ~8.6 ns each single-thread — worse than the 4 ns
# random-offset microbench because filtered candidates pass the bloom and
# walk the probe path more often.  The engine's ConfirmSet fans the
# candidate array over min(8, cpu) threads; the tuner prices against
# CONFIRM_THREADS of them (default 8 — any real TPU host has that; set
# DGREP_CONFIRM_THREADS for constrained hosts, e.g. 1 on this 1-core
# build VM, which shifts the tuner toward more device gathers).
CONFIRM_PS_PER_CANDIDATE = 8_600.0


def _confirm_threads() -> int:
    """Confirm threads the tuner prices against.  Defaults to 8 — the
    runtime confirm fans candidates over min(8, cpu) threads
    (utils/native.ConfirmSet), and every real TPU host has >=8 cores, so
    the default prices exactly what will run in deployment.  Constrained
    workers should set DGREP_CONFIRM_THREADS to their core count (e.g. 1
    on the 1-core build VM), which shifts the tuner toward more device
    gathers / fewer candidates so a weak host's confirm still keeps up."""
    try:
        return max(1, int(os.environ.get("DGREP_CONFIRM_THREADS", "8")))
    except ValueError:
        return 8


CONFIRM_THREADS = _confirm_threads()
# The analytic fp model treats checks as independent; measured candidate
# rates run ~2.4x higher (same-pair cross-family checks are positively
# correlated through the shared pattern set — oracle-verified on the 10k
# config-5 set: model 0.019/byte vs 0.047 measured).  The tuner prices
# confirm with this bias; the analytic value still ranks plans.
EMPIRICAL_FP_BIAS = 2.5
OVERLAP_RESIDUE = 0.2  # fraction of the smaller leg that fails to overlap
# Kernel compile ceiling: lane-gathers per byte step.  Round-5 probe
# (benchmarks/probe_gather_ceiling.py, v5e 2026-08-01): 44/48/56/64-gather
# m=6 plans (fillers at D=1024) ALL compile and run bit-exact vs the
# NumPy reference at both production unrolls, with throughput tracking
# the ~4.7 ps/gather model (64 gathers -> 3.3-3.7 GB/s) — the old
# 40-gather cap (itself replacing an unroll-32-artifact 24) was
# conservative, not a hardware wall.  64 is the new probed bound.
MAX_GATHERS = 64
# The native MT host scanner is the engine's routing alternative for
# FDR-rejected sets: ~0.33 GB/s/core measured on this VM's AC/DFA table
# walk (BASELINE.md (git history) "native MT host scanner" row), scaling ~linearly
# with the confirm-thread fan.  With MAX_GATHERS=64 the plan menu now
# admits filters big enough to price BELOW that host fan, so
# eligibility must gate on scan cost too, not just candidate rate —
# a filter that scans slower than the host's exact scanner is not
# worth the device no matter how clean its candidate stream is.
NATIVE_SCAN_GBPS_PER_THREAD = 0.33


@dataclass(frozen=True)
class Pricing:
    """The tuner's cost constants as one value: the engine prices its plan
    once, at construction, with its active chip count
    (ops/engine._fdr_base_pricing)."""

    confirm_ps_per_candidate: float  # single-thread wall, ps
    confirm_threads: int
    fp_bias: float  # measured/analytic candidate-rate ratio
    overlap_residue: float
    # Active chips sharing this host's confirm thread fan (VERDICT r3 item
    # 1).  The scan leg scales with chips (each chip scans its own byte
    # stream / lane shard) while the confirm stream rides ONE host's
    # threads, so per scanned byte the confirm leg costs n_chips/threads —
    # on a 4-chip host a plan whose confirm hid behind the scan at 8
    # threads stops hiding at the 2-thread-per-chip share, and the tuner
    # should buy more device gathers instead.
    n_chips: int = 1

    def confirm_wall_ps(self, fp_per_byte: float) -> float:
        """Expected per-byte confirm wall given an analytic fp rate,
        relative to one chip's scan timeline (threads are shared across
        the host's active chips)."""
        return (
            fp_per_byte * self.fp_bias
            * self.confirm_ps_per_candidate
            * self.n_chips / self.confirm_threads
        )

    def total_ps(self, scan_ps: float, fp_per_byte: float) -> float:
        confirm = self.confirm_wall_ps(fp_per_byte)
        return max(scan_ps, confirm) + self.overlap_residue * min(scan_ps, confirm)


def default_pricing() -> Pricing:
    """Current module constants (reads globals at call time so tests can
    monkeypatch them)."""
    return Pricing(
        confirm_ps_per_candidate=CONFIRM_PS_PER_CANDIDATE,
        confirm_threads=CONFIRM_THREADS,
        fp_bias=EMPIRICAL_FP_BIAS,
        overlap_residue=OVERLAP_RESIDUE,
    )


def pair_hash(b0: np.ndarray | int, b1: np.ndarray | int, domain: int, which: int = 0):
    """The kernel's pair-domain hash — shared host/device definition.

    Domains are nested: ``pair_hash(..., D) == pair_hash(..., D') & (D-1)``
    for D <= D', which is what lets the kernel compute one hash per family
    and mask it down per check."""
    a, b = HASHES[which]
    return ((b0 * a) ^ (b1 * b)) & (domain - 1)


class FdrError(ValueError):
    pass


@dataclass(frozen=True)
class FdrBank:
    """One filter pass: a check plan over an m-slot pipeline.

    ``checks[i] = (slot, family, domain)``: lookup i probes ``tables[i]``
    (a (domain,) uint32 bucket-mask array) with hash family ``family`` of
    the byte pair at slot ``slot``; slot k is applied k steps after the
    oldest check, so it covers the pair at depth m-1-k from the window
    end.  Checks sharing a slot AND together before entering the
    pipeline."""

    m: int  # pipeline slots (window = m+1 bytes)
    checks: tuple[tuple[int, int, int], ...]  # (slot, family, domain)
    tables: tuple[np.ndarray, ...]  # per check: (domain,) uint32 bucket masks
    patterns: list[bytes]  # normalized suffix members (for debugging/repr)
    fp_per_byte: float  # expected candidate rate on uniform bytes

    @property
    def n_checks(self) -> int:
        return len(self.checks)

    @property
    def domain(self) -> int:
        """Largest check domain (kernel hash width)."""
        return max(d for _, _, d in self.checks)

    @property
    def families(self) -> tuple[int, ...]:
        return tuple(sorted({f for _, f, _ in self.checks}))

    @property
    def total_gathers(self) -> int:
        return sum(d // 128 for _, _, d in self.checks)

    def scan_cost_ps(self) -> float:
        """Modeled per-byte device cost (gathers dominate)."""
        return COST_PS_PER_GATHER * self.total_gathers


@dataclass(frozen=True)
class FdrModel:
    banks: list[FdrBank]
    ignore_case: bool
    n_patterns: int

    @property
    def fp_per_byte(self) -> float:
        return float(sum(b.fp_per_byte for b in self.banks))

    def scan_cost_ps(self) -> float:
        return sum(b.scan_cost_ps() for b in self.banks)

    @property
    def window(self) -> int:
        """Max filter window — candidate misses are confined to the first
        window-1 bytes of a stripe (the engine's boundary stitching)."""
        return max(b.m for b in self.banks) + 1


def _normalize(patterns: list[str | bytes], ignore_case: bool) -> list[bytes]:
    out: list[bytes] = []
    for p in patterns:
        b = p.encode("utf-8", "surrogateescape") if isinstance(p, str) else bytes(p)
        if not b:
            raise FdrError("empty literal in pattern set")
        if NL in b:
            raise FdrError("literal contains '\\n' — not representable per-line")
        out.append(b.lower() if ignore_case else b)
    return out


def _bucket_of(group: list[bytes]) -> np.ndarray:
    """Cell-snapped clustered bucket assignment.

    Sort members by their final-pair hash at CLUSTER_DOMAIN and pack whole
    hash cells into buckets targeting equal member counts.  Because no
    cell is split, bucket b's density at the clustered check is exactly
    cells(b)/CLUSTER_DOMAIN and Σ_b density_b == 1 (every cell belongs to
    exactly one bucket) — rank-range assignment (v2) leaked ~N_BUCKETS
    straddled cells, i.e. a 1.2x fp factor at D=128."""
    n = len(group)
    cells = [int(pair_hash(p[-2], p[-1], CLUSTER_DOMAIN)) for p in group]
    order = sorted(range(n), key=lambda i: (cells[i], group[i]))
    bucket = np.zeros(n, dtype=np.int64)
    b = 0
    for rank, i in enumerate(order):
        want = min(N_BUCKETS - 1, rank * N_BUCKETS // n)
        if want > b and cells[i] != cells[order[rank - 1]]:
            b = want
        bucket[i] = b
    return bucket


def _pair_arrays(group: list[bytes], m: int) -> tuple[np.ndarray, np.ndarray]:
    """(m, n) arrays of the byte pair at each depth d from the suffix end."""
    b0 = np.empty((m, len(group)), dtype=np.int64)
    b1 = np.empty((m, len(group)), dtype=np.int64)
    for d in range(m):
        for i, p in enumerate(group):
            b0[d, i] = p[len(p) - 2 - d]
            b1[d, i] = p[len(p) - 1 - d]
    return b0, b1


def _build_tables(
    group: list[bytes],
    bucket: np.ndarray,
    m: int,
    checks: tuple[tuple[int, int, int], ...],
    pair_cache: dict | None = None,
) -> tuple[np.ndarray, ...]:
    """Reach tables for one check plan (vectorized over members)."""
    if pair_cache is None or "pairs" not in pair_cache:
        pairs = _pair_arrays(group, m)
        if pair_cache is not None:
            pair_cache["pairs"] = pairs
    else:
        pairs = pair_cache["pairs"]
    b0, b1 = pairs
    bits = (np.uint32(1) << bucket.astype(np.uint32)).astype(np.uint32)
    out = []
    for slot, fam, domain in checks:
        key = (slot, fam, domain)
        if pair_cache is not None and key in pair_cache:
            out.append(pair_cache[key])
            continue
        d = m - 1 - slot
        idx = pair_hash(b0[d], b1[d], domain, which=fam)
        t = np.zeros(domain, dtype=np.uint32)
        np.bitwise_or.at(t, idx, bits)
        if pair_cache is not None:
            pair_cache[key] = t
        out.append(t)
    return tuple(out)


def _fp_of_tables(tables: tuple[np.ndarray, ...]) -> float:
    """Expected candidate probability per byte on uniform random pairs:
    sum over buckets of prod over checks of that bucket's density (checks
    are treated as independent — different pairs, or different hash
    families of one pair)."""
    prod = np.ones(N_BUCKETS, dtype=np.float64)
    for t in tables:
        bits = (t[:, None] >> np.arange(N_BUCKETS, dtype=np.uint32)) & 1
        prod *= bits.sum(axis=0) / t.shape[0]
    return float(prod.sum())


def _filler_slots(m: int) -> list[tuple[int, int]]:
    """Filler priority: family 0 from the deepest unused slot down, then
    family 1 (slot m-1 first: it shares the clustered pair and rides
    residual clustering)."""
    return [(k, 0) for k in range(m - 2, -1, -1)] + [
        (k, 1) for k in range(m - 1, -1, -1)
    ]


def _plans(m: int):
    """All candidate check plans: the cell-snapped clustered check (slot
    m-1, family 0) at CLUSTER_DOMAIN plus every multiset of filler domains
    (largest domains assigned to the highest-priority fillers).  Mixed
    domains matter: the gather is the unit of cost, and e.g. swapping one
    D=512 filler for D=256 drops 2 gathers for a ~1.5x fp factor — the
    right trade exactly when the confirm has slack."""
    from itertools import combinations_with_replacement

    slots = _filler_slots(m)
    for n_fill in range(1, len(slots) + 1):
        for doms in combinations_with_replacement(DOMAINS, n_fill):
            ds = sorted(doms, reverse=True)
            yield ((m - 1, 0, CLUSTER_DOMAIN),) + tuple(
                (k, f, d) for (k, f), d in zip(slots, ds)
            )


def _compress_banks(banks: list[FdrBank]) -> list[FdrBank]:
    """Drop pipeline slots no check probes (a small plan on a long window
    checks only shallow depths — e.g. the 8-word config-2 plan probes
    depths {0,1} but inherited m=6 from the members' length): slots with
    no check are pure shift-through (V_k(t) = V_{k-1}(t-1)), so remapping
    every check to slot m'-1-depth with m' = max depth + 1 yields a
    candidate stream identical except for LESS stripe-head over-report
    (the all-ones seed covers m' positions instead of m) while the kernel
    carries m' registers instead of m.  Tables are depth-keyed
    (d = m-1-slot) and therefore reused unchanged.  Probed on v5e
    (2026-07-30, config-2 A/B): throughput-neutral — Mosaic already
    sinks the dead shift-throughs — so this is kept for the smaller VMEM
    scratch, the shorter over-report window (fewer boundary confirms),
    and plan-shape honesty (a 2-depth plan now SAYS m=2)."""
    out = []
    for b in banks:
        depths = [b.m - 1 - slot for slot, _, _ in b.checks]
        m_eff = max(depths) + 1
        if m_eff == b.m:
            out.append(b)
            continue
        checks = tuple(
            (m_eff - 1 - d, fam, dom)
            for d, (_, fam, dom) in zip(depths, b.checks)
        )
        out.append(FdrBank(
            m=m_eff, checks=checks, tables=b.tables,
            patterns=b.patterns, fp_per_byte=b.fp_per_byte,
        ))
    return out


def _compile_group(
    group: list[bytes], m: int, fp_budget: float, max_banks: int = 4,
    pricing: Pricing | None = None,
) -> list[FdrBank]:
    """Pick (fill domain, n_lookups, n_banks) for one window group by
    minimizing the total-cost model (scan + expected confirm, overlapped),
    preferring budget-satisfying configurations when any exists."""
    pricing = pricing or default_pricing()
    total_ps = pricing.total_ps

    best: tuple[tuple, list[FdrBank]] | None = None
    for n_banks in (1, 2, 4):
        if n_banks > max_banks or (n_banks > 1 and len(group) < n_banks * N_BUCKETS):
            continue
        shards = [group[i::n_banks] for i in range(n_banks)]
        buckets = [_bucket_of(s) for s in shards]
        caches = [{} for _ in shards]
        for plan in _plans(m):
            gathers = sum(d // 128 for _, _, d in plan)
            if gathers > MAX_GATHERS:
                continue  # outside the kernel's probed compile ceiling
            # The scan leg alone lower-bounds total_ps (total = max(scan,
            # confirm) + residue*min >= scan), so once a within-budget best
            # exists, any plan whose gathers already cost more than that
            # best's TOTAL cannot win — skip building its tables (the
            # expensive step; ~halves the 10k-set tuner's compile time).
            if (
                best is not None
                and best[0][0] == 0
                and COST_PS_PER_GATHER * gathers * len(shards) > best[0][1]
            ):
                continue
            banks = []
            for shard, bucket, cache in zip(shards, buckets, caches):
                tabs = _build_tables(shard, bucket, m, plan, cache)
                banks.append(
                    FdrBank(
                        m=m,
                        checks=plan,
                        tables=tabs,
                        patterns=shard,
                        fp_per_byte=_fp_of_tables(tabs),
                    )
                )
            fp = sum(b.fp_per_byte for b in banks)
            cost = sum(b.scan_cost_ps() for b in banks)
            # prefer configurations within budget; among those, min
            # total cost; if none fits, min FP bounds the confirm.  The
            # budget bounds the EXPECTED rate (analytic x bias), the same
            # quantity the compile_fdr ceiling gates on.
            within = fp * pricing.fp_bias <= fp_budget
            key = (0, total_ps(cost, fp)) if within else (1, fp, cost)
            if best is None or key < best[0]:
                best = (key, banks)
    assert best is not None
    return _compress_banks(best[1])


def compile_fdr(
    patterns: list[str | bytes],
    *,
    ignore_case: bool = False,
    fp_budget_per_byte: float = FP_CEILING_PER_BYTE,
    max_banks: int = 4,
    pricing: Pricing | None = None,
) -> FdrModel:
    """Compile a literal set (every literal >= 2 bytes) into filter banks.

    The window is set by the shortest member (suffix truncation makes every
    longer member representable in it).  When the set's lengths are mixed
    enough that splitting pays — a long-window group gets more slots and a
    short group stops poisoning it — the tuner compares every two-group
    split against the single-bank compile by total cost.  Raises FdrError
    for sets this filter cannot host (the engine routes those to the exact
    DFA-bank path instead)."""
    pricing = pricing or default_pricing()
    norm = _normalize(patterns, ignore_case)
    if not norm:
        raise FdrError("empty pattern set")
    if any(len(p) < 2 for p in norm):
        raise FdrError("FDR needs literals >= 2 bytes")

    def window_of(subset: list[bytes]) -> int:
        return min(MAX_DEPTHS + 1, min(len(p) for p in subset))

    def group_cost(banks: list[FdrBank]) -> float:
        scan = sum(b.scan_cost_ps() for b in banks)
        return pricing.total_ps(scan, sum(b.fp_per_byte for b in banks))

    candidates: list[list[FdrBank]] = []
    single = _compile_group(
        norm, window_of(norm) - 1, fp_budget_per_byte, max_banks, pricing
    )
    candidates.append(single)
    lengths = sorted({min(len(p), MAX_DEPTHS + 1) for p in norm})
    for t in lengths[1:]:
        short = [p for p in norm if min(len(p), MAX_DEPTHS + 1) < t]
        long_ = [p for p in norm if min(len(p), MAX_DEPTHS + 1) >= t]
        if len(short) < N_BUCKETS or len(long_) < N_BUCKETS:
            continue
        candidates.append(
            _compile_group(short, window_of(short) - 1, fp_budget_per_byte / 2,
                           max_banks, pricing)
            + _compile_group(long_, window_of(long_) - 1, fp_budget_per_byte / 2,
                             max_banks, pricing)
        )
    banks = min(candidates, key=group_cost)
    from distributed_grep_tpu.utils.native import native_available

    scan_ps = sum(b.scan_cost_ps() for b in banks)
    device_gbps = pricing.n_chips * 1000.0 / scan_ps if scan_ps else float("inf")
    native_gbps = NATIVE_SCAN_GBPS_PER_THREAD * pricing.confirm_threads
    # Only cede to the host when the host scanner actually exists: on a
    # native-less install the engine's FdrError fallback is the ~0.1 GB/s
    # XLA DFA-bank path (_route_native no-ops there), and even a
    # 100-gather filter beats that by ~20x.
    if device_gbps < native_gbps and native_available():
        raise FdrError(
            f"cheapest filter plan scans at {device_gbps:.1f} GB/s "
            f"({sum(b.total_gathers for b in banks)} gathers x "
            f"{pricing.n_chips} chip(s)) — below the ~{native_gbps:.1f} GB/s "
            f"native host fan; the exact host scanner wins this set"
        )
    model = FdrModel(banks=banks, ignore_case=ignore_case, n_patterns=len(norm))
    # gate on the EXPECTED REAL rate (analytic x measured bias), like the
    # cost model — an analytic-only gate would admit sets whose true
    # candidate rate is in the confirm-dominates regime
    if model.fp_per_byte * pricing.fp_bias > FP_CEILING_PER_BYTE:
        raise FdrError(
            f"set too dense to filter: expected candidate rate "
            f"{model.fp_per_byte * pricing.fp_bias:.3g}/byte "
            f"(analytic x{pricing.fp_bias:g} bias) > {FP_CEILING_PER_BYTE:g}"
        )
    return model


# ------------------------------------------------------------------ reference

def reference_candidates(bank: FdrBank, data: bytes) -> np.ndarray:
    """NumPy oracle of the device filter for one bank: candidate end offsets
    (i+1 convention, like models/dfa.reference_scan) over a single stripe.

    Mirrors the kernel exactly, including the all-ones pipeline seed at the
    stripe start (conservative: early positions over-report rather than
    miss, and the engine confirms candidates exactly anyway).
    """
    arr = np.frombuffer(data, dtype=np.uint8).astype(np.int64)
    n = arr.size
    if n == 0:
        return np.zeros(0, dtype=np.int64)
    prev = np.concatenate([[0], arr[:-1]])
    ones = np.uint32(0xFFFFFFFF)
    slot_masks = np.full((bank.m, n), ones, dtype=np.uint32)
    for i, (slot, fam, domain) in enumerate(bank.checks):
        h = pair_hash(prev, arr, domain, which=fam)
        slot_masks[slot] &= bank.tables[i][h]
    # pipeline: V_0(t) = masks[0, t]; V_k(t) = V_{k-1}(t-1) & masks[k, t]
    Vs = np.empty((bank.m, n), dtype=np.uint32)
    Vs[0] = slot_masks[0]
    for k in range(1, bank.m):
        shifted = np.concatenate([[ones], Vs[k - 1][:-1]])
        Vs[k] = shifted & slot_masks[k]
    return np.nonzero(Vs[bank.m - 1] != 0)[0].astype(np.int64) + 1


def reference_candidates_model(model: FdrModel, data: bytes) -> np.ndarray:
    """Union of per-bank candidate end offsets."""
    if model.ignore_case:
        data = bytes(data).lower()
    outs = [reference_candidates(b, data) for b in model.banks]
    return np.unique(np.concatenate(outs)) if outs else np.zeros(0, dtype=np.int64)


def exact_match_lines(patterns: list[bytes], data: bytes, ignore_case: bool) -> set[int]:
    """Simple oracle for tests: 1-based lines containing any literal."""
    hay = data.lower() if ignore_case else data
    needles = [p.lower() if ignore_case else p for p in patterns]
    out = set()
    for i, line in enumerate(hay.split(b"\n"), 1):
        if any(nd in line for nd in needles):
            out.add(i)
    return out
