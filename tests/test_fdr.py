"""FDR bucketed literal-set filter: model oracle, Pallas kernel (interpret
mode), auto-tuning, and end-to-end exactness through the engine confirm
path.  The filter itself may over-report (bucket superimposition, all-ones
stripe seeds); exactness is asserted where the system promises it — at the
line level after host confirmation — while the model-level tests assert
the filter's contract: candidates are a SUPERSET of true match ends, with
a measured false-positive rate close to the model's prediction."""

from __future__ import annotations

import numpy as np
import pytest

from distributed_grep_tpu.models import fdr as fdr_mod
from distributed_grep_tpu.ops import layout as layout_mod
from distributed_grep_tpu.ops import pallas_fdr

from tests.test_ops import make_text


def _rand_literals(n, lo, hi, seed, alphabet=b"abcdefghijklmnopqrstuvwxyz"):
    rng = np.random.default_rng(seed)
    pats = set()
    while len(pats) < n:
        k = int(rng.integers(lo, hi + 1))
        pats.add(bytes(rng.choice(list(alphabet), size=k).tolist()))
    return sorted(pats)


def _true_ends(patterns, data: bytes, ignore_case=False) -> set[int]:
    hay = data.lower() if ignore_case else data
    ends = set()
    for p in patterns:
        nd = p.lower() if ignore_case else p
        start = 0
        while True:
            i = hay.find(nd, start)
            if i < 0:
                break
            ends.add(i + len(nd))  # i+1 convention: offset of last byte + 1
            start = i + 1
    return ends


# ------------------------------------------------------------------- model

def test_candidates_superset_of_matches():
    pats = _rand_literals(200, 4, 10, seed=1)
    model = fdr_mod.compile_fdr(pats)
    data = make_text(400, inject=[(7, b"xx " + pats[0] + b" yy"),
                                  (200, pats[10] + pats[50]),
                                  (399, b"ends with " + pats[99])])
    cands = set(fdr_mod.reference_candidates_model(model, data).tolist())
    assert _true_ends(pats, data) <= cands


def test_fp_rate_close_to_estimate():
    pats = _rand_literals(300, 5, 9, seed=2)
    model = fdr_mod.compile_fdr(pats, fp_budget_per_byte=1e-3)
    rng = np.random.default_rng(3)
    data = rng.integers(97, 123, size=1 << 20, dtype=np.uint8).tobytes()
    cands = fdr_mod.reference_candidates_model(model, data)
    true = _true_ends(pats, data)
    fp = len(set(cands.tolist()) - true) / len(data)
    # estimate assumes uniform pairs; lowercase text is close enough that
    # the empirical rate should be within ~30x (and under budget x30)
    assert fp <= max(model.fp_per_byte * 30, 3e-3), (fp, model.fp_per_byte)


def test_ignore_case_folding():
    pats = [b"NeedLe", b"VOLCANO"]
    model = fdr_mod.compile_fdr(pats, ignore_case=True)
    data = b"a nEEdle here\nand a volCANO there\n"
    cands = set(fdr_mod.reference_candidates_model(model, data).tolist())
    assert _true_ends(pats, data, ignore_case=True) <= cands


def test_length_stratification_and_short_patterns():
    pats = [b"ab", b"cd", b"needle", b"volcano", b"xy"] + _rand_literals(40, 6, 8, seed=4)
    model = fdr_mod.compile_fdr(pats)
    ms = sorted({b.m for b in model.banks})
    assert ms[0] == 1  # len-2 group got its own window
    data = b"ab here\nneedle there\nxy\n" + make_text(50)
    cands = set(fdr_mod.reference_candidates_model(model, data).tolist())
    assert _true_ends(pats, data) <= cands


def test_rejects_unusable_literals():
    with pytest.raises(fdr_mod.FdrError):
        fdr_mod.compile_fdr([b"a"])  # too short for a pair
    with pytest.raises(fdr_mod.FdrError):
        fdr_mod.compile_fdr([b"has\nnewline"])
    with pytest.raises(fdr_mod.FdrError):
        fdr_mod.compile_fdr([])


def test_big_set_banks_within_budget():
    pats = _rand_literals(2000, 5, 9, seed=5)
    model = fdr_mod.compile_fdr(pats, fp_budget_per_byte=2e-4)
    assert model.n_patterns == 2000
    for b in model.banks:
        assert 1 <= b.m <= fdr_mod.MAX_DEPTHS
        for _, _, d in b.checks:
            assert d in fdr_mod.DOMAINS
        assert b.total_gathers <= fdr_mod.MAX_GATHERS
    # cost search should prefer meeting the budget when feasible
    assert model.fp_per_byte <= 2e-3


def test_clustered_check_cells_never_split():
    """The cell-snapped clustered check assigns each hash cell to exactly
    one bucket, so its bucket densities sum to used_cells/domain <= 1 —
    the property that makes it worth one gather (models/fdr._bucket_of).
    Rank-range assignment (v2) would split ~N_BUCKETS cells and push the
    sum toward 1.25."""
    pats = _rand_literals(3000, 5, 9, seed=11)
    model = fdr_mod.compile_fdr(pats)
    for b in model.banks:
        slot, fam, domain = b.checks[0]
        assert (slot, fam, domain) == (b.m - 1, 0, fdr_mod.CLUSTER_DOMAIN)
        # no cell split: each table cell's mask is a single-bucket bit
        t = b.tables[0]
        nonzero = t[t != 0]
        assert np.all((nonzero & (nonzero - 1)) == 0)
        bits = (t[:, None] >> np.arange(32, dtype=np.uint32)) & 1
        assert float((bits.sum(axis=0) / t.shape[0]).sum()) <= 1.0 + 1e-9


# ------------------------------------------------------------------ kernel

def _kernel_vs_reference(pats, data, **compile_kw):
    model = fdr_mod.compile_fdr(pats, **compile_kw)
    if model.ignore_case:
        data_f = bytes(data).lower()
    else:
        data_f = data
    lay = layout_mod.choose_layout(
        len(data_f), target_lanes=4096, min_chunk=512,
        lane_multiple=4096, chunk_multiple=512,
    )
    arr = layout_mod.to_device_array(data_f, lay)
    for bank in model.banks:
        got = pallas_fdr.fdr_scan(arr, bank, interpret=True)
        # expected: reference per lane-stripe (each lane is its own stripe)
        want = np.zeros((lay.chunk, lay.lanes), dtype=bool)
        for lane in range(lay.lanes):
            stripe = bytes(arr[:, lane])
            ends = fdr_mod.reference_candidates(bank, stripe)
            want[(ends - 1).astype(np.int64), lane] = True
        np.testing.assert_array_equal(
            got, np.packbits(want, axis=1, bitorder="little")
        )


def test_pallas_fdr_interpret_matches_reference():
    pats = _rand_literals(60, 4, 9, seed=6)
    data = make_text(
        120,
        inject=[(3, b"xx " + pats[0] + b" yy"), (60, pats[1] + b" " + pats[2])],
    )
    _kernel_vs_reference(pats, data)


def test_pallas_fdr_interpret_multi_subtable():
    # force a multi-subtable domain (n_sub > 1) via a big enough set
    pats = _rand_literals(5000, 5, 9, seed=7)
    model = fdr_mod.compile_fdr(pats)
    assert any(b.domain >= 256 for b in model.banks)
    data = make_text(60, inject=[(5, pats[3] + b" mid " + pats[4])])
    _kernel_vs_reference(pats, data)


def test_pallas_fdr_short_window_bank():
    _kernel_vs_reference([b"ab", b"zq", b"needle"], make_text(60, inject=[(2, b"zq ab")]))


def test_device_tables_layout():
    pats = _rand_literals(100, 4, 8, seed=8)
    model = fdr_mod.compile_fdr(pats)
    bank = model.banks[0]
    tiles = pallas_fdr.bank_device_tables(bank)
    n_rows = sum(d // 128 for _, _, d in bank.checks)
    assert tiles.shape == (n_rows, 32, 128)
    # per-check subtables stack in plan order; any sublane row holds the
    # broadcast 128-entry slice
    row = 0
    for i, (_, _, d) in enumerate(bank.checks):
        for j in range(d // 128):
            np.testing.assert_array_equal(
                tiles[row, 5],
                bank.tables[i][j * 128 : (j + 1) * 128],
            )
            row += 1


# ----------------------------------------------------- engine (device path)

def test_engine_fdr_end_to_end_interpret(monkeypatch):
    """Full engine path: FDR candidates on the (interpreted) kernel, host
    confirm, boundary stitching — output must equal the oracle exactly."""
    from distributed_grep_tpu.ops import engine as engine_mod
    from distributed_grep_tpu.ops import pallas_scan

    pats = _rand_literals(150, 4, 9, seed=9)
    data = make_text(
        150,
        inject=[(2, b"xx " + pats[0] + b" yy"),
                (75, pats[1] + b" and " + pats[2]),
                (149, b"tail " + pats[3])],
    )
    monkeypatch.setattr(pallas_scan, "available", lambda: True)
    orig = pallas_fdr.fdr_scan_words
    monkeypatch.setattr(
        pallas_fdr, "fdr_scan_words",
        lambda arr, bank, interpret=None, **kw:
            orig(arr, bank, interpret=True, **kw),
    )
    eng = engine_mod.GrepEngine(patterns=[p.decode("latin-1") for p in pats])
    assert eng.mode == "fdr"
    res = eng.scan(data)
    want = fdr_mod.exact_match_lines(pats, data, ignore_case=False)
    assert set(res.matched_lines.tolist()) == want


def test_engine_fdr_ignore_case_interpret(monkeypatch):
    from distributed_grep_tpu.ops import engine as engine_mod
    from distributed_grep_tpu.ops import pallas_scan

    pats = [b"NEEDLE", b"VolCano", b"qq"]
    data = make_text(60, inject=[(5, b"a needle B"), (30, b"VOLCANO qQ")])
    monkeypatch.setattr(pallas_scan, "available", lambda: True)
    orig = pallas_fdr.fdr_scan_words
    monkeypatch.setattr(
        pallas_fdr, "fdr_scan_words",
        lambda arr, bank, interpret=None, **kw:
            orig(arr, bank, interpret=True, **kw),
    )
    eng = engine_mod.GrepEngine(
        patterns=[p.decode() for p in pats], ignore_case=True
    )
    assert eng.mode == "fdr"
    res = eng.scan(data)
    assert set(res.matched_lines.tolist()) == fdr_mod.exact_match_lines(
        pats, data, ignore_case=True
    )


def test_engine_fdr_kernel_failure_raises(monkeypatch):
    from distributed_grep_tpu.ops import engine as engine_mod
    from distributed_grep_tpu.ops import pallas_scan

    pats = _rand_literals(50, 4, 8, seed=10)
    data = make_text(60, inject=[(7, pats[0] + b" here")])
    monkeypatch.setattr(pallas_scan, "available", lambda: True)

    def boom(*a, **k):
        raise RuntimeError("mosaic says no")

    monkeypatch.setattr(pallas_fdr, "fdr_scan_words", boom)
    eng = engine_mod.GrepEngine(patterns=[p.decode("latin-1") for p in pats])
    assert eng.mode == "fdr"
    # raises naming the route: no rescan on the DFA banks or the host
    with pytest.raises(RuntimeError, match="mosaic says no") as ei:
        eng.scan(data)
    assert "device scan route: fdr/pallas" in ei.value.__notes__
    assert eng.mode == "fdr"


def test_engine_cpu_backend_ignores_fdr():
    from distributed_grep_tpu.ops import engine as engine_mod

    pats = _rand_literals(20, 4, 8, seed=11)
    data = make_text(40, inject=[(3, pats[0])])
    eng = engine_mod.GrepEngine(
        patterns=[p.decode("latin-1") for p in pats], backend="cpu"
    )
    assert eng.mode == "native" and eng.fdr is None
    res = eng.scan(data)
    assert set(res.matched_lines.tolist()) == fdr_mod.exact_match_lines(
        pats, data, ignore_case=False
    )


def test_too_dense_set_raises():
    # ~16k distinct full-alphabet 2-byte literals saturate every table and
    # hash combination: the model must refuse (engine then keeps the exact
    # DFA banks)
    rng = np.random.default_rng(12)
    pats = {bytes(p.tolist()) for p in rng.integers(1, 256, size=(30000, 2), dtype=np.uint8)}
    pats = sorted(p for p in pats if b"\n" not in p)[:16384]
    with pytest.raises(fdr_mod.FdrError):
        fdr_mod.compile_fdr(pats)


# ---------------------------------------------- literal-set decomposition

def test_alternation_routes_to_pattern_set_engines():
    """Hyperscan-style literal decomposition: a finite-literal-set regex
    compiles to the pattern-set engines (FDR on device), not the NFA."""
    from distributed_grep_tpu.ops.engine import GrepEngine

    eng = GrepEngine("(volcano|anarchism|needle)")
    assert eng.mode in ("fdr", "dfa") and len(eng.tables) >= 1
    assert eng.pattern == "(volcano|anarchism|needle)"
    got = set(eng.scan(b"a volcano\nx\nneedles\nanarchism!\n").matched_lines.tolist())
    assert got == {1, 3, 4}
    # 1-byte members are FDR-ineligible on device: regex paths keep them
    assert GrepEngine("(a|b)").mode == "nfa"
    # class-sequences keep the (faster) single-pass shift-and path
    assert GrepEngine("h[ae]llo").mode == "shift_and"
    # case-insensitive decomposition folds in the set engines, not by
    # enumerating case variants
    ci = GrepEngine("nee(dle|t)", ignore_case=True)
    assert ci.mode in ("fdr", "dfa")
    assert set(ci.scan(b"NEEDLE\nneet\nneat\n").matched_lines.tolist()) == {1, 2}


def test_literal_set_enumeration_caps_and_rejects():
    from distributed_grep_tpu.models.dfa import enumerate_literal_set

    assert enumerate_literal_set("(ab|cd)") == [b"ab", b"cd"]
    assert enumerate_literal_set("x[01][01]") == [b"x00", b"x01", b"x10", b"x11"]
    assert enumerate_literal_set("a+") is None          # unbounded
    assert enumerate_literal_set("^ab") is None         # anchored
    assert enumerate_literal_set("(a|)") is None        # empty member
    assert enumerate_literal_set("[0-9]{4}") is None    # 10^4 > cap
    assert enumerate_literal_set("volcano") == [b"volcano"]


# ------------------------------ FDR-ineligible device-cliff routing (round 3)

def test_fdr_ineligible_set_routes_to_native():
    """A set too dense for the FDR filter must route --backend device to the
    native MT host scanner (exact, ~GB/s) instead of the ~0.1 GB/s XLA
    DFA-bank device path (VERDICT r2 item 5)."""
    from distributed_grep_tpu.ops.engine import GrepEngine
    from distributed_grep_tpu.utils.native import native_available

    if not native_available():
        pytest.skip("native lib unavailable")
    rng = np.random.default_rng(9)
    raw = sorted({bytes(x) for x in rng.integers(0, 256, size=(25000, 3)).tolist()
                  if 10 not in x})
    eng = GrepEngine(patterns=raw, backend="device")
    assert eng.mode == "native"
    data = b"needle xyw\nno hit Q9w\n" + raw[17] + b" yes\nNOPE Q!\n"
    got = set(eng.scan(data).matched_lines.tolist())
    sp = set(raw)
    expected = {
        i for i, l in enumerate(data.split(b"\n")[:-1], 1)
        if any(q in l for q in sp)
    }
    assert got == expected


def test_all_short_pattern_set_routes_to_pairset():
    """1-2-byte sets never reach the FDR compiler; since round 4 the
    structured ones get the exact pairset device kernel (models/pairset)
    instead of the native-host consolation route."""
    from distributed_grep_tpu.ops.engine import GrepEngine

    eng = GrepEngine(patterns=["a", "b"], backend="device")
    assert eng.mode == "pairset"
    got = set(eng.scan(b"xyz\nqab\nccc\nBa\n").matched_lines.tolist())
    assert got == {2, 4}


def test_unfactorizable_short_set_still_routes_to_native():
    """A random dense pair set defeats both pairset orientations (> 32 row
    and column classes); it must keep the native MT route, never the
    device DFA cliff."""
    from distributed_grep_tpu.ops.engine import GrepEngine
    from distributed_grep_tpu.utils.native import native_available

    if not native_available():
        pytest.skip("native lib unavailable")
    rng = np.random.default_rng(8)
    pats = sorted({bytes(rng.integers(32, 127, size=2).tolist())
                   for _ in range(3000)} - {b"\n\n"})
    eng = GrepEngine(patterns=pats, backend="device")
    assert eng.mode == "native"


# ------------------------------------ plan fixed at construction

ALNUM = b"abcdefghijklmnopqrstuvwxyz0123456789"


def _plan(model):
    return [(b.m, b.checks) for b in model.banks]


def _slow_host_confirm(monkeypatch):
    """A host confirm ~100x slower than the priced constant: a plan that
    read this host's confirm speed would come out different."""
    import time

    from distributed_grep_tpu.utils.native import ConfirmSet

    real = ConfirmSet.confirm

    def slow(self, data, ends, n_threads=None):
        time.sleep(0.05)
        return real(self, data, ends, n_threads=n_threads)

    monkeypatch.setattr(ConfirmSet, "confirm", slow)


FIXED_PLAN_SETS = {
    "plain": ([p.decode() for p in _rand_literals(300, 5, 8, 31, ALNUM)],
              False),
    "ignore_case": ([p.decode().upper() if i % 2 else p.decode()
                     for i, p in enumerate(_rand_literals(300, 5, 8, 32))],
                    True),
    "mixed_short": ([p.decode() for p in _rand_literals(60, 4, 8, seed=14)]
                    + ["!", "~"], False),
}


@pytest.mark.parametrize("name", list(FIXED_PLAN_SETS))
def test_fdr_plan_does_not_depend_on_host_confirm_speed(monkeypatch, name):
    """The engine's FDR plan is the one its construction args price —
    base pricing at its chip count — however fast this host confirms."""
    from distributed_grep_tpu.ops.engine import GrepEngine

    pats, ignore_case = FIXED_PLAN_SETS[name]
    _slow_host_confirm(monkeypatch)
    eng = GrepEngine(patterns=pats, ignore_case=ignore_case)
    assert eng.mode == "fdr"
    assert (eng._fdr_pairset is not None) == (name == "mixed_short")
    direct = fdr_mod.compile_fdr(
        [p for p in pats if len(p) >= 2], ignore_case=ignore_case,
        pricing=fdr_mod.default_pricing(),
    )
    assert _plan(eng.fdr) == _plan(direct)


@pytest.mark.parametrize("distinct", [False, True],
                         ids=["same_set", "distinct_sets"])
def test_fdr_engines_build_concurrently_through_the_model_cache(
    monkeypatch, distinct
):
    """Four threads build FDR engines through the cross-job model cache
    at once, on a host whose confirm is far off the priced constant:
    every build returns (the constructor never calls back into the cache
    whose lock it runs under), and one key gives one object and one
    miss."""
    import threading

    from distributed_grep_tpu.ops import engine as engine_mod

    _slow_host_confirm(monkeypatch)
    sets = [
        [p.decode() for p in
         _rand_literals(200, 5, 8, 40 + (i if distinct else 0), ALNUM)]
        for i in range(4)
    ]
    got: list = [None] * 4

    def build(i):
        got[i] = engine_mod.cached_engine(patterns=sets[i])

    threads = [threading.Thread(target=build, args=(i,), daemon=True)
               for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
        assert not t.is_alive(), "an engine build never returned"
    engines = [e for e, _ in got]
    assert all(e.mode == "fdr" for e in engines)
    counters = engine_mod.model_cache_counters()
    if distinct:
        assert len({id(e) for e in engines}) == 4
        assert sorted(v for _, v in got) == ["miss"] * 4
        assert counters["compile_cache_misses"] == 4
        assert counters["compile_cache_hits"] == 0
    else:
        assert len({id(e) for e in engines}) == 1
        assert sorted(v for _, v in got) == ["hit", "hit", "hit", "miss"]
        assert counters["compile_cache_misses"] == 1
        assert counters["compile_cache_hits"] == 3


@pytest.mark.parametrize("n_chips", [1, 8])
def test_fdr_scan_exact_under_chip_count_pricing(n_chips):
    """The plan priced for one chip and the one priced for the 8-chip
    confirm share (an engine driving every local device) both scan
    exactly: the engine runs the plan its chip count prices, and its
    lines equal the reference."""
    from dataclasses import replace

    from distributed_grep_tpu.ops.engine import GrepEngine

    rng = np.random.default_rng(12)
    alphabet = list(ALNUM)
    pats = sorted({
        bytes(rng.choice(alphabet, size=int(rng.integers(5, 9))).tolist())
        for _ in range(2000)
    })
    spats = [p.decode() for p in pats]
    eng = GrepEngine(patterns=spats, interpret=True,
                     devices="all" if n_chips > 1 else None)
    assert eng.mode == "fdr"
    assert eng._fdr_base_pricing().n_chips == n_chips
    direct = fdr_mod.compile_fdr(
        spats, pricing=replace(fdr_mod.default_pricing(), n_chips=n_chips)
    )
    assert _plan(eng.fdr) == _plan(direct)

    lines = []
    for i in range(400):
        n = int(rng.integers(0, 50))
        lines.append(bytes(rng.choice(alphabet + [32], size=n).tolist()))
        if i % 37 == 3:
            lines[-1] = b"xx " + pats[int(rng.integers(0, len(pats)))] + b" yy"
    data = b"\n".join(lines) + b"\n"
    expected = {
        i for i, ln in enumerate(data.split(b"\n")[:-1], 1)
        if any(p in ln for p in pats)
    }
    assert expected
    assert set(eng.scan(data).matched_lines.tolist()) == expected


def test_chip_aware_pricing_buys_more_filtering():
    """VERDICT r3 item 1: the confirm threads are shared across a host's
    active chips, so Pricing.n_chips must shift the tuner toward more
    device gathers / lower candidate rates as the chip count grows —
    monotonically, and with a strict flip by 4 chips on a config-5-shaped
    set."""
    from dataclasses import replace

    pats = _rand_literals(
        3000, 5, 8, seed=21, alphabet=b"abcdefghijklmnopqrstuvwxyz0123456789"
    )
    base = replace(
        fdr_mod.default_pricing(),
        confirm_ps_per_candidate=8600.0, confirm_threads=8, n_chips=1,
    )
    models = {
        nc: fdr_mod.compile_fdr(pats, pricing=replace(base, n_chips=nc))
        for nc in (1, 4)
    }
    g1 = sum(b.total_gathers for b in models[1].banks)
    g4 = sum(b.total_gathers for b in models[4].banks)
    assert g4 > g1  # 4 chips -> confirm share quartered -> buy filtering
    assert models[4].fp_per_byte < models[1].fp_per_byte
    # and the wall model itself scales: same plan, 4x the confirm wall
    pr4 = replace(base, n_chips=4)
    assert pr4.confirm_wall_ps(0.01) == pytest.approx(
        4 * base.confirm_wall_ps(0.01)
    )


def test_engine_mesh_chip_count_pricing():
    """An engine driving an 8-device mesh must price the FDR confirm leg
    at the 8-chip share from construction."""
    from dataclasses import replace

    from distributed_grep_tpu.ops.engine import GrepEngine
    from distributed_grep_tpu.parallel.mesh import make_mesh

    pats = _rand_literals(
        3000, 5, 8, seed=21, alphabet=b"abcdefghijklmnopqrstuvwxyz0123456789"
    )
    spats = [p.decode() for p in pats]
    eng1 = GrepEngine(patterns=spats, interpret=True)
    mesh = make_mesh((8,), ("data",))
    eng8 = GrepEngine(patterns=spats, mesh=mesh, interpret=True)
    assert eng1._fdr_base_pricing().n_chips == 1
    assert eng8._fdr_base_pricing().n_chips == 8
    direct = fdr_mod.compile_fdr(
        spats, pricing=replace(fdr_mod.default_pricing(), n_chips=8)
    )
    assert [(b.m, b.checks) for b in eng8.fdr.banks] == \
        [(b.m, b.checks) for b in direct.banks]
    # EP on a 2D mesh: the pattern axis scans concurrently too
    mesh2 = make_mesh((4, 2), ("data", "seq"))
    eng_ep = GrepEngine(
        patterns=spats, mesh=mesh2, mesh_axis="data", pattern_axis="seq",
        interpret=True,
    )
    assert eng_ep._fdr_base_pricing().n_chips == 8


def test_pipeline_compression_preserves_candidates():
    """Round-4 m-compression: a plan probing only shallow depths drops its
    dead pipeline slots; the candidate stream is unchanged except for
    LESS stripe-head over-report (the all-ones seed shrinks)."""
    words = [b"volcano", b"anarchism", b"needleqq", b"breadth",
             b"journal", b"mineral", b"quantum", b"physics"]
    model = fdr_mod.compile_fdr(words)
    bank = model.banks[0]
    depths = [bank.m - 1 - s for s, _, _ in bank.checks]
    assert bank.m == max(depths) + 1  # compressed to the used depth range
    assert bank.m < min(len(w) for w in words) - 1  # actually shrank
    # reconstruct the uncompressed form and compare candidate streams
    m_old = min(len(w) for w in words) - 1
    checks_old = tuple(
        (m_old - 1 - d, fam, dom)
        for d, (_, fam, dom) in zip(depths, bank.checks)
    )
    b_old = fdr_mod.FdrBank(
        m=m_old, checks=checks_old, tables=bank.tables,
        patterns=bank.patterns, fp_per_byte=bank.fp_per_byte,
    )
    data = make_text(300, inject=[(4, b"xx volcano yy"),
                                  (150, b"physics anarchism"),
                                  (299, b"tail quantum")])
    got = set(fdr_mod.reference_candidates(bank, data).tolist())
    old = set(fdr_mod.reference_candidates(b_old, data).tolist())
    # identical beyond the old seed window; inside it only over-report may
    # differ (compressed seeds fewer positions) — never a lost candidate
    assert {e for e in got if e > m_old} == {e for e in old if e > m_old}
    assert got <= old
    assert _true_ends(words, data) <= got


def test_kernel_failure_mid_multisegment_scan_with_collect_pool(monkeypatch):
    """With collects on a pool, a kernel that fails on a LATER segment
    (first consumed inside a collect future) must still fail the scan —
    the failure surfaces via future.result() instead of an inline call —
    after the collect pool drained."""
    from distributed_grep_tpu.ops import engine as engine_mod
    from distributed_grep_tpu.ops import pallas_scan

    pats = _rand_literals(60, 4, 8, seed=13)
    data = make_text(
        3000,
        inject=[(5, pats[0] + b" head"), (1500, b"mid " + pats[1]),
                (2999, b"tail " + pats[2])],
    )
    monkeypatch.setattr(pallas_scan, "available", lambda: True)
    calls = {"n": 0}
    real = pallas_fdr.fdr_scan_words

    def flaky(arr, bank, **kw):
        calls["n"] += 1
        if calls["n"] >= 3:
            raise RuntimeError("mosaic says no, mid-scan")
        kw["interpret"] = True
        return real(arr, bank, **kw)

    monkeypatch.setattr(pallas_fdr, "fdr_scan_words", flaky)
    eng = engine_mod.GrepEngine(
        patterns=[p.decode("latin-1") for p in pats], segment_bytes=16 * 1024
    )
    assert eng.mode == "fdr"
    assert len(data) // (16 * 1024) >= 4
    with pytest.raises(RuntimeError, match="mid-scan") as ei:
        eng.scan(data)
    assert "device scan route: fdr/pallas" in ei.value.__notes__
    assert calls["n"] == 3  # no segment dispatched after the failure


def test_mixed_set_short_members_ride_the_device():
    """A set mixing long literals with 1-byte members: the shorts run the
    exact pairset kernel OR'd into the FDR candidate words (round 4 — the
    old host AC scan serialized the dispatch loop ~40x the device leg),
    and the extended ConfirmSet keeps the union exact."""
    from distributed_grep_tpu.ops import engine as engine_mod

    pats = _rand_literals(60, 4, 8, seed=14) + [b"!", b"~"]
    data = make_text(
        2500,
        inject=[(3, pats[0] + b" head"), (1200, b"bang ! mid"),
                (2499, b"tilde ~ tail " + pats[1])],
    )
    eng = engine_mod.GrepEngine(
        patterns=[p.decode("latin-1") for p in pats], interpret=True,
        segment_bytes=16 * 1024,
    )
    assert eng.mode == "fdr"
    assert eng._fdr_pairset is not None
    res = eng.scan(data)
    assert set(res.matched_lines.tolist()) == fdr_mod.exact_match_lines(
        pats, data, ignore_case=False
    )

    # and through the mesh path (lane-sharded FDR + lane-sharded pairset)
    from distributed_grep_tpu.parallel.mesh import make_mesh

    eng_m = engine_mod.GrepEngine(
        patterns=[p.decode("latin-1") for p in pats], interpret=True,
        mesh=make_mesh((8,), ("data",)),
    )
    assert eng_m.mode == "fdr" and eng_m._fdr_pairset is not None
    res_m = eng_m.scan(data)
    assert set(res_m.matched_lines.tolist()) == fdr_mod.exact_match_lines(
        pats, data, ignore_case=False
    )
    # the MESH kernels actually ran
    assert eng_m.stats.get("psum_candidates", 0) >= 1
    assert eng_m.stats["kernel"] == "pallas"


def test_mixed_set_dense_short_member_routes_to_native():
    """A mixed set whose 1-byte member is expected-dense (' ') must not
    attach the pairset sidecar: every occurrence would become a
    device-reported candidate and the collect path's O(candidates)
    coordinate fetch + confirm would swamp the scan (round-4 review
    finding).  The whole set keeps the loud native route, exact."""
    from distributed_grep_tpu.ops import engine as engine_mod

    pats = _rand_literals(40, 4, 8, seed=77) + [b" "]
    eng = engine_mod.GrepEngine(
        patterns=[p.decode("latin-1") for p in pats], interpret=True,
    )
    assert eng.mode in ("native", "dfa")
    assert eng._fdr_pairset is None and eng.fdr is None
    data = make_text(400, inject=[(3, pats[0] + b"-x"), (200, b"nospacehere")])
    res = eng.scan(data)
    import distributed_grep_tpu.models.fdr as fdr_mod
    assert set(res.matched_lines.tolist()) == fdr_mod.exact_match_lines(
        pats, data, ignore_case=False
    )
