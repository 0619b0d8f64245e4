"""Multi-chip tests on the virtual 8-device CPU mesh (SURVEY.md §4)."""

import re
from functools import partial

import numpy as np
import pytest

import jax

from distributed_grep_tpu.models.dfa import compile_dfa
from distributed_grep_tpu.ops import layout as layout_mod
from distributed_grep_tpu.ops import lines as lines_mod
from distributed_grep_tpu.parallel.mesh import make_mesh
from distributed_grep_tpu.parallel.sharded_scan import sharded_grep_step


@pytest.fixture(scope="module")
def mesh8():
    assert len(jax.devices()) == 8, "conftest must fake 8 CPU devices"
    return make_mesh((8,), ("data",))


def make_text(n_lines=400, seed=11, inject=()):
    rng = np.random.default_rng(seed)
    lines = []
    for _ in range(n_lines):
        n = int(rng.integers(0, 60))
        lines.append(bytes(rng.choice(list(b"abcdef gh"), size=n).tolist()))
    for pos, text in inject:
        lines[pos] = text
    return b"\n".join(lines) + b"\n"


def test_sharded_scan_matches_host_oracle(mesh8):
    data = make_text(inject=[(7, b"a needle here"), (390, b"needle again")])
    table = compile_dfa("needle")
    lay = layout_mod.choose_layout(len(data), target_lanes=64, min_chunk=8)
    arr = layout_mod.to_device_array(data, lay)
    packed, total, exits, neigh = sharded_grep_step(arr, table, mesh8)
    # Count: device total equals oracle count away from boundaries; boundary
    # misses are possible, so compare via full offsets with stitching below.
    packed_np = np.asarray(packed)
    offsets = lines_mod.match_offsets_from_packed(packed_np, lay)
    nl = lines_mod.newline_index(data)
    device_lines = np.unique(lines_mod.line_of_offsets(offsets, nl))
    stitched = lines_mod.stitch_lines(
        device_lines,
        data,
        nl,
        lay.stripe_starts().tolist(),
        partial(lines_mod.lines_matching, [table]),
    )
    expected = {
        i
        for i, line in enumerate(data.split(b"\n"), start=1)
        if re.search(b"needle", line)
    }
    assert set(stitched.tolist()) == expected
    assert int(total) == offsets.size


def test_sharded_scan_collectives_shapes(mesh8):
    data = make_text(100)
    table = compile_dfa("abc")
    lay = layout_mod.choose_layout(len(data), target_lanes=64, min_chunk=8)
    arr = layout_mod.to_device_array(data, lay)
    packed, total, exits, neigh = sharded_grep_step(arr, table, mesh8)
    assert np.asarray(exits).shape == (lay.lanes,)
    # ppermute ring: every device received exactly one neighbor state
    assert np.asarray(neigh).shape == (8,)
    assert np.asarray(packed).shape == (lay.chunk, lay.lanes // 8)


def test_mesh_helpers():
    m = make_mesh()
    assert m.devices.size == 8
    m2 = make_mesh((4, 2), ("data", "seq"))
    assert m2.shape == {"data": 4, "seq": 2}
    with pytest.raises(ValueError):
        make_mesh((16,), ("data",))


def test_two_axis_mesh_scan():
    """(data, seq) 2D mesh: lanes sharded over the flattened device order —
    scan over the seq axis composed with data axis still yields exact
    results after stitching."""
    mesh = make_mesh((4, 2), ("data", "seq"))
    data = make_text(200, inject=[(50, b"the needle sits here")])
    table = compile_dfa("needle")
    lay = layout_mod.choose_layout(len(data), target_lanes=64, min_chunk=8)
    arr = layout_mod.to_device_array(data, lay)
    # Shard lanes over 'seq' (stripes of one doc across chips), replicate
    # over 'data' — the long-context configuration.
    packed, total, exits, neigh = sharded_grep_step(arr, table, mesh, axis="seq")
    packed_np = np.asarray(packed)
    offsets = lines_mod.match_offsets_from_packed(packed_np, lay)
    nl = lines_mod.newline_index(data)
    device_lines = np.unique(lines_mod.line_of_offsets(offsets, nl))
    stitched = lines_mod.stitch_lines(
        device_lines, data, nl, lay.stripe_starts().tolist(),
        partial(lines_mod.lines_matching, [table]),
    )
    expected = {
        i for i, line in enumerate(data.split(b"\n"), start=1) if b"needle" in line
    }
    assert set(stitched.tolist()) == expected


def test_product_axis_sharding_uses_all_devices():
    """axis=("data","seq"): lanes shard over the 4x2 product — all 8 devices
    hold distinct stripes, psum spans both axes, ring rides 'seq'."""
    mesh = make_mesh((4, 2), ("data", "seq"))
    data = make_text(300, inject=[(13, b"needle one"), (250, b"two needle")])
    table = compile_dfa("needle")
    lay = layout_mod.choose_layout(len(data), target_lanes=64, min_chunk=8)
    arr = layout_mod.to_device_array(data, lay)
    packed, total, exits, neigh = sharded_grep_step(
        arr, table, mesh, axis=("data", "seq")
    )
    shard_shapes = {s.data.shape for s in packed.addressable_shards}
    assert shard_shapes == {(lay.chunk, lay.lanes // 8 // 8)}  # 8-way lane split
    # The ring must wrap over the LINEARIZED product order (data-major),
    # not within each seq group: device d receives device (d-1)%8's last
    # lane's exit state.
    exits_np = np.asarray(exits)
    local = lay.lanes // 8
    last_exit_per_dev = exits_np[local - 1 :: local]
    np.testing.assert_array_equal(np.asarray(neigh), np.roll(last_exit_per_dev, 1))
    offsets = lines_mod.match_offsets_from_packed(np.asarray(packed), lay)
    nl = lines_mod.newline_index(data)
    device_lines = np.unique(lines_mod.line_of_offsets(offsets, nl))
    stitched = lines_mod.stitch_lines(
        device_lines, data, nl, lay.stripe_starts().tolist(),
        partial(lines_mod.lines_matching, [table]),
    )
    expected = {
        i for i, line in enumerate(data.split(b"\n"), start=1) if b"needle" in line
    }
    assert set(stitched.tolist()) == expected
    assert int(total) == offsets.size


# ------------------------------------------------- pattern-parallel (EP) step

def test_pattern_set_step_matches_oracle():
    """Banks shard over 'seq' (pattern axis), lanes over 'data': the OR over
    the pattern axis must equal a single-automaton scan of the whole set."""
    from distributed_grep_tpu.models.aho import compile_aho_corasick_banks
    from distributed_grep_tpu.parallel.sharded_scan import sharded_pattern_set_step

    rng = np.random.default_rng(5)
    pats = sorted(
        {bytes(rng.choice(list(b"abcdefgh"), size=int(rng.integers(3, 7))).tolist())
         for _ in range(40)}
    )
    # one tiny bank per ~8 patterns -> several banks to shard
    tables = []
    for i in range(0, len(pats), 8):
        tables.extend(compile_aho_corasick_banks(pats[i : i + 8]))
    assert len(tables) >= 3
    data = make_text(300, inject=[(5, b"xx " + pats[0] + b" yy"), (250, pats[1])])
    mesh = make_mesh((4, 2), ("data", "seq"))
    lay = layout_mod.choose_layout(len(data), target_lanes=64, min_chunk=8)
    arr = layout_mod.to_device_array(data, lay)
    packed, total = sharded_pattern_set_step(arr, tables, mesh)
    offsets = lines_mod.match_offsets_from_packed(np.asarray(packed), lay)
    assert int(total) >= offsets.size  # total counts padded tail positions too
    nl = lines_mod.newline_index(data)
    device_lines = np.unique(lines_mod.line_of_offsets(offsets, nl))

    any_bank = partial(lines_mod.lines_matching, tables)
    stitched = lines_mod.stitch_lines(
        device_lines, data, nl, lay.stripe_starts().tolist(), any_bank
    )
    expected = {
        i for i, line in enumerate(data.split(b"\n"), 1)
        if any(p in line for p in pats)
    }
    assert set(stitched.tolist()) == expected


def test_pattern_set_step_bank_padding():
    """Bank count not divisible by the pattern axis: dead padding banks must
    contribute nothing."""
    from distributed_grep_tpu.models.aho import compile_aho_corasick
    from distributed_grep_tpu.parallel.sharded_scan import sharded_pattern_set_step

    tables = [compile_aho_corasick([b"needle"]), compile_aho_corasick([b"volcano"]),
              compile_aho_corasick([b"quartz"])]  # 3 banks over a 2-wide axis
    data = make_text(100, inject=[(3, b"a needle"), (50, b"quartz volcano")])
    mesh = make_mesh((4, 2), ("data", "seq"))
    lay = layout_mod.choose_layout(len(data), target_lanes=64, min_chunk=8)
    arr = layout_mod.to_device_array(data, lay)
    packed, total = sharded_pattern_set_step(arr, tables, mesh)
    offsets = lines_mod.match_offsets_from_packed(np.asarray(packed), lay)
    nl = lines_mod.newline_index(data)
    got = np.unique(lines_mod.line_of_offsets(offsets, nl))

    any_bank = partial(lines_mod.lines_matching, tables)
    got = lines_mod.stitch_lines(got, data, nl, lay.stripe_starts().tolist(), any_bank)
    expected = {
        i for i, line in enumerate(data.split(b"\n"), 1)
        if any(p in line for p in (b"needle", b"volcano", b"quartz"))
    }
    assert set(got.tolist()) == expected


# ----------------------------- production Pallas kernels under shard_map

def _mesh_layout(data, mesh, axis="data"):
    from distributed_grep_tpu.parallel import sharded_kernels as sk

    mult = sk.mesh_lane_multiple(mesh, axis)
    lay = layout_mod.choose_layout(
        len(data), target_lanes=mult, min_chunk=512,
        lane_multiple=mult, chunk_multiple=512,
    )
    return lay, layout_mod.to_device_array(data, lay)


def test_sharded_shift_and_bit_identical(mesh8):
    """The shift-and Pallas kernel under shard_map must produce the exact
    words a single-device run produces, with the psum count matching."""
    from distributed_grep_tpu.models.shift_and import try_compile_shift_and
    from distributed_grep_tpu.ops import pallas_scan
    from distributed_grep_tpu.parallel import sharded_kernels as sk

    data = make_text(500, inject=[(7, b"a needle here"), (420, b"needle!")])
    model = try_compile_shift_and("needle")
    lay, arr = _mesh_layout(data, mesh8)
    words, total = sk.sharded_shift_and_words(
        arr, model, mesh8, coarse=True, interpret=True
    )
    ref = pallas_scan.shift_and_scan_words(arr, model, interpret=True, coarse=True)
    np.testing.assert_array_equal(np.asarray(words), np.asarray(ref))
    assert int(total) == int(np.count_nonzero(np.asarray(ref)))
    # lanes really shard: every device holds 1/8 of the tile rows
    shard_shapes = {s.data.shape for s in words.addressable_shards}
    assert shard_shapes == {(lay.chunk // 32, lay.lanes // 128 // 8, 128)}


def test_sharded_nfa_bit_identical(mesh8):
    from distributed_grep_tpu.models.nfa import try_compile_glushkov
    from distributed_grep_tpu.ops import pallas_nfa
    from distributed_grep_tpu.parallel import sharded_kernels as sk

    data = make_text(400, inject=[(3, b"neeedle x"), (300, b"nedle")])
    model = try_compile_glushkov("ne+dle")
    assert model is not None
    lay, arr = _mesh_layout(data, mesh8)
    words, total = sk.sharded_nfa_words(arr, model, mesh8, interpret=True)
    ref = pallas_nfa.nfa_scan_words(arr, model, interpret=True)
    np.testing.assert_array_equal(np.asarray(words), np.asarray(ref))
    assert int(total) == int(np.count_nonzero(np.asarray(ref)))


def test_sharded_fdr_bit_identical(mesh8):
    from distributed_grep_tpu.models.fdr import compile_fdr
    from distributed_grep_tpu.ops import pallas_fdr
    from distributed_grep_tpu.parallel import sharded_kernels as sk

    rng = np.random.default_rng(17)
    pats = [b"needle", b"zebra", b"volcano"] + [
        bytes(rng.choice(list(b"abcdefgh"), size=6).tolist()) for _ in range(40)
    ]
    fdr = compile_fdr(pats)
    data = make_text(400, inject=[(11, b"xx needle"), (200, pats[5])])
    lay, arr = _mesh_layout(data, mesh8)
    words, total = sk.sharded_fdr_words(arr, fdr, mesh8, interpret=True)
    ref = None
    for bank in fdr.banks:
        w = pallas_fdr.fdr_scan_words(arr, bank, interpret=True)
        ref = w if ref is None else ref | w
    np.testing.assert_array_equal(np.asarray(words), np.asarray(ref))
    assert int(total) == int(np.count_nonzero(np.asarray(ref)))


def test_engine_mesh_mode_exact(mesh8):
    """GrepEngine(mesh=...) — the production multi-chip mode — must be exact
    vs the line oracle for all three kernel families and record the psum'd
    collective candidate count."""
    from distributed_grep_tpu.ops.engine import GrepEngine

    rng = np.random.default_rng(23)
    lines = []
    for i in range(700):
        n = int(rng.integers(0, 60))
        lines.append(bytes(rng.choice(list(b"abcdefg h"), size=n).tolist()))
        if i % 37 == 5:
            lines[-1] = b"xx needle yy"
        if i % 53 == 9:
            lines[-1] = b"neeeedle and needles"
    data = b"\n".join(lines) + b"\n"

    def oracle(rx):
        return {
            i for i, ln in enumerate(data.split(b"\n")[:-1], 1)
            if re.search(rx, ln)
        }

    engines = {
        "shift_and": GrepEngine("needle", mesh=mesh8, interpret=True),
        "nfa": GrepEngine("ne+dle", mesh=mesh8, interpret=True),
        "fdr": GrepEngine(
            patterns=["needle", "zebra", "volcano", "abcdef", "fedcba",
                      "gabhcd", "hhfgab", "deadbe"],
            mesh=mesh8, interpret=True,
        ),
    }
    rxs = {"shift_and": b"needle", "nfa": b"ne+dle",
           "fdr": b"needle|zebra|volcano|abcdef|fedcba|gabhcd|hhfgab|deadbe"}
    for want_mode, eng in engines.items():
        assert eng.mode == want_mode
        res = eng.scan(data)
        assert set(res.matched_lines.tolist()) == oracle(rxs[want_mode]), want_mode
        assert eng.stats.get("psum_candidates", 0) >= 1, want_mode


def test_engine_mesh_multi_segment(mesh8):
    """Several segments through the mesh path: per-segment shard_map scans
    with psum totals accumulated across segments."""
    from distributed_grep_tpu.ops.engine import GrepEngine

    data = make_text(2000, inject=[(5, b"needle a"), (1990, b"z needle")])
    eng = GrepEngine("needle", mesh=mesh8, interpret=True,
                     segment_bytes=16 * 1024)
    res = eng.scan(data)
    expected = {
        i for i, ln in enumerate(data.split(b"\n")[:-1], 1) if b"needle" in ln
    }
    assert set(res.matched_lines.tolist()) == expected
    assert eng.stats.get("psum_candidates", 0) >= 2


def test_sharded_fdr_pattern_parallel_bit_identical():
    """EP on the production kernel: same-plan FDR banks shard over the
    pattern axis (tables are the sharded operand), candidate words OR over
    ICI — output must be bit-identical to a single-device OR over all
    banks, including zero-table padding banks."""
    from distributed_grep_tpu.models.fdr import FdrModel, compile_fdr
    from distributed_grep_tpu.ops import pallas_fdr
    from distributed_grep_tpu.parallel import sharded_kernels as sk

    rng = np.random.default_rng(7)
    pats = sorted({
        bytes(rng.choice(list(b"abcdefghijklmnop"), size=6).tolist())
        for _ in range(600)
    })
    h1, h2 = pats[::2], pats[1::2]
    m1, m2 = compile_fdr(h1), compile_fdr(h2)
    plans = {(b.m, b.checks) for b in (*m1.banks, *m2.banks)}
    assert len(plans) == 1, "same-distribution halves should share a plan"
    model = FdrModel(banks=list(m1.banks) + list(m2.banks),
                     ignore_case=False, n_patterns=len(pats))

    data = make_text(500, inject=[(7, b"xx " + pats[3]), (420, pats[11])])
    mesh = make_mesh((4, 2), ("data", "seq"))
    lay, arr = _mesh_layout(data, mesh, axis="data")
    words, total = sk.sharded_fdr_pattern_step(
        arr, model, mesh, data_axis="data", pattern_axis="seq",
        interpret=True,
    )
    ref = None
    for bank in model.banks:
        w = pallas_fdr.fdr_scan_words(arr, bank, interpret=True)
        ref = w if ref is None else ref | w
    np.testing.assert_array_equal(np.asarray(words), np.asarray(ref))
    assert int(total) == int(np.count_nonzero(np.asarray(ref)))
    # lanes shard over data only; every device holds 1/4 of the tiles
    shard_shapes = {s.data.shape for s in words.addressable_shards}
    assert shard_shapes == {(lay.chunk // 32, lay.lanes // 128 // 4, 128)}



def test_sharded_approx_bit_identical_and_engine_mesh(mesh8):
    """The approx (agrep) kernel under shard_map: bit-identical words, and
    the engine's mesh mode is exact for max_errors scans."""
    from distributed_grep_tpu.models.approx import line_matches, try_compile_approx
    from distributed_grep_tpu.ops import pallas_approx
    from distributed_grep_tpu.ops.engine import GrepEngine
    from distributed_grep_tpu.parallel import sharded_kernels as sk

    model = try_compile_approx("needle", 1)
    assert model is not None
    data = make_text(400, inject=[(5, b"a needle"), (300, b"nedle x"),
                                  (350, b"nXedle")])
    lay, arr = _mesh_layout(data, mesh8)
    words, total = sk.sharded_approx_words(arr, model, mesh8, interpret=True)
    ref = pallas_approx.approx_scan_words(arr, model, interpret=True)
    np.testing.assert_array_equal(np.asarray(words), np.asarray(ref))
    assert int(total) == int(np.count_nonzero(np.asarray(ref)))

    eng = GrepEngine("needle", max_errors=1, mesh=mesh8, interpret=True)
    res = eng.scan(data)
    want = {
        i for i, ln in enumerate(data.split(b"\n")[:-1], 1)
        if line_matches(model, ln)
    }
    assert set(res.matched_lines.tolist()) == want
    assert eng.stats.get("psum_candidates", 0) >= 1


def test_engine_pattern_axis_ep_exact():
    """GrepEngine(mesh=2D, pattern_axis=...): same-plan FDR banks shard
    over the pattern axis inside the engine — exact output, psum recorded;
    mixed-plan models silently keep the lane-sharded step."""
    from distributed_grep_tpu.models.fdr import FdrModel, compile_fdr
    from distributed_grep_tpu.ops.engine import GrepEngine

    rng = np.random.default_rng(9)
    pats = sorted({
        bytes(rng.choice(list(b"abcdefghijklmnop"), size=6).tolist())
        for _ in range(400)
    })
    data = make_text(700, inject=[(11, b"xx " + pats[5]), (600, pats[17])])
    expected = {
        i for i, ln in enumerate(data.split(b"\n")[:-1], 1)
        if any(p in ln for p in pats)
    }
    mesh = make_mesh((4, 2), ("data", "seq"))
    eng = GrepEngine(
        patterns=[p.decode() for p in pats],
        mesh=mesh, mesh_axis="data", pattern_axis="seq", interpret=True,
    )
    assert eng.mode == "fdr"
    res = eng.scan(data)
    assert set(res.matched_lines.tolist()) == expected
    assert eng.stats.get("psum_candidates", 0) >= 1


def test_engine_mesh_axis_validation(mesh8):
    """Bad axis names fail at construction, not inside the scan's
    kernel-failure net (which would demote the engine silently)."""
    from distributed_grep_tpu.ops.engine import GrepEngine

    with pytest.raises(ValueError, match="mesh_axis"):
        GrepEngine("needle", mesh=mesh8, mesh_axis="bogus")
    mesh2d = make_mesh((4, 2), ("data", "seq"))
    with pytest.raises(ValueError, match="pattern_axis"):
        GrepEngine(patterns=["aa", "bb"], mesh=mesh2d, mesh_axis="data",
                   pattern_axis="typo")
    with pytest.raises(ValueError, match="pattern_axis"):
        GrepEngine(patterns=["aa", "bb"], mesh=mesh2d,
                   mesh_axis=("data", "seq"), pattern_axis="seq")


def test_confirms_overlap_across_device_segments(monkeypatch):
    """VERDICT r3 item 1 done-criterion: with several devices in flight,
    FDR confirms for different segments must run CONCURRENTLY (on the
    collect pool) instead of serializing on the dispatch thread — and the
    result must stay exact while they do."""
    import threading

    from distributed_grep_tpu.ops.engine import GrepEngine

    rng = np.random.default_rng(31)
    alphabet = list(b"abcdefghijklmnopqrstuvwxyz0123456789")
    pats = sorted({
        bytes(rng.choice(alphabet, size=int(rng.integers(5, 9))).tolist())
        for _ in range(200)
    })
    lines = []
    for i in range(4000):
        n = int(rng.integers(0, 50))
        lines.append(bytes(rng.choice(alphabet + [32], size=n).tolist()))
        if i % 41 == 3:
            lines[-1] = b"xx " + pats[int(rng.integers(0, len(pats)))] + b" yy"
    data = b"\n".join(lines) + b"\n"

    eng = GrepEngine(
        patterns=[p.decode() for p in pats], devices="all", interpret=True,
        segment_bytes=16 * 1024,
    )
    assert eng.mode == "fdr"
    assert len(data) // (16 * 1024) >= 4  # several segments in flight

    real = eng._fdr_confirm.confirm
    gate = threading.Event()
    lock = threading.Lock()
    calls = [0]

    def slow_confirm(buf, ends, **kw):
        with lock:
            calls[0] += 1
            first = calls[0] == 1
        if first:
            # hold the first confirm open until a second one ENTERS — only
            # possible if confirms run concurrently (the 10 s timeout keeps
            # a serializing regression failing fast instead of hanging)
            gate.wait(timeout=10)
        else:
            gate.set()
        return real(buf, ends, **kw)

    monkeypatch.setattr(eng._fdr_confirm, "confirm", slow_confirm)
    res = eng.scan(data)
    expected = {
        i for i, ln in enumerate(data.split(b"\n")[:-1], 1)
        if any(p in ln for p in pats)
    }
    assert set(res.matched_lines.tolist()) == expected
    assert eng.stats.get("confirm_concurrency_peak", 0) >= 2
