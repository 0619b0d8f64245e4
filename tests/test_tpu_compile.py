"""Ahead-of-time compiles of the Pallas kernels for a described v5e, with
no chip attached (on-chip-measurement guide, section 2).

Each test lowers one kernel at the engine's real 64 MiB segment layout and
compiles it with the TPU compiler, which refuses what the chip would
refuse (unaligned slices, VMEM over budget) where interpret mode accepts
it.  A compile that passes is not a chip run: nothing executes here.

The topology is described inside a module fixture, never at import: only
one process may load libtpu at a time, and every test worker imports
this file.
"""

import numpy as np
import pytest

SEGMENT = 64 * 1024 * 1024  # GrepEngine.segment_bytes default


@pytest.fixture(scope="module")
def topo():
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    from jax.experimental import topologies

    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


def _layout(lane_multiple: int):
    """The engine's Pallas layout for one full segment (device_scan's
    lay_kwargs with the default target_lanes)."""
    from distributed_grep_tpu.ops import layout as layout_mod

    return layout_mod.choose_layout(
        SEGMENT, target_lanes=max(1024, lane_multiple), min_chunk=512,
        lane_multiple=lane_multiple, chunk_multiple=512, quantize_chunk=True,
    )


def _tiles(sharding):
    import jax

    from distributed_grep_tpu.ops.pallas_scan import LANE_COLS, LANES_PER_BLOCK

    lay = _layout(LANES_PER_BLOCK)
    shape = (lay.chunk, lay.lanes // LANE_COLS, LANE_COLS)
    return lay, jax.ShapeDtypeStruct(shape, np.uint8, sharding=sharding)


def _const(arr, sharding):
    import jax

    return jax.ShapeDtypeStruct(arr.shape, arr.dtype, sharding=sharding)


def _assert_kernel(lowered):
    text = lowered.compile().as_text()
    assert "tpu_custom_call" in text


def _engine(*args, **kw):
    from distributed_grep_tpu.ops.engine import GrepEngine

    return GrepEngine(*args, backend="device", **kw)


def _config3():
    import sys
    from pathlib import Path

    sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "benchmarks"))
    import baseline_configs as bc

    return bc._rand_literals(1000, 6, 12, seed=3)


def _config5():
    import sys
    from pathlib import Path

    sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "benchmarks"))
    import baseline_configs as bc

    alphabet = np.arange(1, 256)
    alphabet = alphabet[alphabet != 0x0A]
    return [p.encode("latin-1").decode("utf-8", "surrogateescape")
            for p in bc._rand_literals(10_000, 5, 9, seed=5, alphabet=alphabet)]


@pytest.mark.parametrize("coarse", [False, True], ids=["exact", "coarse"])
def test_shift_and_compiles(one_chip, coarse):
    from distributed_grep_tpu.ops import pallas_scan

    model = _engine("volcano").shift_and
    lay, x = _tiles(one_chip)
    _assert_kernel(pallas_scan._shift_and_pallas.lower(
        x, sym_ranges=tuple(tuple(r) for r in model.sym_ranges),
        match_bit=int(model.match_bit), chunk=lay.chunk,
        lane_blocks=lay.lanes // pallas_scan.LANES_PER_BLOCK,
        interpret=False, coarse=coarse))


@pytest.mark.parametrize("gather_b", [False, True],
                         ids=["compare_b", "gather_b"])
def test_nfa_compiles(one_chip, gather_b):
    from distributed_grep_tpu.ops import pallas_nfa

    eng = _engine(r"get /[a-z0-9/.-]{4,24}\.gif", ignore_case=True)
    assert eng.mode == "nfa"
    model = eng.glushkov
    lay, x = _tiles(one_chip)
    b_tabs = (_const(pallas_nfa.build_b_tables(model), one_chip)
              if gather_b else None)
    _assert_kernel(pallas_nfa._nfa_pallas.lower(
        x, b_tabs, plan=model.kernel_plan(), chunk=lay.chunk,
        lane_blocks=lay.lanes // pallas_nfa.LANES_PER_BLOCK,
        gather_b=gather_b, interpret=False,
        unroll=pallas_nfa.unroll_for(model)))


@pytest.mark.parametrize("config", ["config3", "config5"])
def test_fdr_banks_compile(one_chip, config):
    from distributed_grep_tpu.ops import pallas_fdr

    pats = _config3() if config == "config3" else _config5()
    eng = _engine(patterns=pats)
    assert eng.mode == "fdr"
    lay, x = _tiles(one_chip)
    plans = {(b.m, pallas_fdr.kernel_plan(b)): b for b in eng.fdr.banks}
    for (m, plan), bank in plans.items():  # one compile per distinct plan
        _assert_kernel(pallas_fdr._fdr_pallas.lower(
            x, _const(pallas_fdr.bank_device_tables(bank), one_chip),
            m=m, plan=plan, chunk=lay.chunk,
            lane_blocks=lay.lanes // pallas_fdr.LANES_PER_BLOCK,
            interpret=False))


def test_pairset_compiles(one_chip):
    from distributed_grep_tpu.ops import pallas_pairset

    eng = _engine(patterns=["ne", "ee", "xx"])
    assert eng.mode == "pairset"
    model = eng.pairset
    lay, x = _tiles(one_chip)
    _assert_kernel(pallas_pairset._pairset_pallas.lower(
        x, _const(pallas_pairset.device_tables(model), one_chip),
        chunk=lay.chunk, lane_blocks=lay.lanes // pallas_pairset.LANES_PER_BLOCK,
        transposed=model.transposed, fold_case=model.ignore_case,
        interpret=False))


def test_approx_compiles(one_chip):
    from distributed_grep_tpu.ops import pallas_approx

    eng = _engine("needle", max_errors=1)
    assert eng.mode == "approx"
    model = eng.approx
    lay, x = _tiles(one_chip)
    _assert_kernel(pallas_approx._approx_pallas.lower(
        x, sym_ranges=tuple(tuple(r) for r in model.base.sym_ranges),
        match_bit=int(model.match_bit), k=model.k, chunk=lay.chunk,
        lane_blocks=lay.lanes // pallas_approx.LANES_PER_BLOCK,
        interpret=False))


@pytest.mark.parametrize("plane", ["words", "lane_bytes"])
def test_fetch_step_compiles(one_chip, plane):
    """The result fetch's device step over a full segment's plane: a
    Pallas word plane, and the XLA routes' packed lane bytes."""
    import jax

    from distributed_grep_tpu.ops import scan_jnp

    lay, _ = _tiles(one_chip)
    if plane == "words":
        shape, dtype = (lay.chunk // 32, lay.lanes // 128, 128), np.uint32
    else:
        shape, dtype = (lay.chunk, lay.lanes // 8), np.uint8
    x = jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    out = scan_jnp.fetch_nonzero_blocks.lower(x).compile().out_info
    _, cap = scan_jnp._fetch_shape(int(np.prod(shape)))
    words = cap * scan_jnp.FETCH_BLOCK * np.dtype(dtype).itemsize // 4
    assert out.shape == (1 + cap + words,) and out.dtype == np.uint32


@pytest.fixture(scope="module")
def mesh_2x2(topo):
    from jax.sharding import Mesh

    return Mesh(np.array(topo.devices).reshape(2, 2), ("data", "seq"))


def _mesh_tiles(mesh):
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from distributed_grep_tpu.parallel import sharded_kernels as shk

    axes = ("data", "seq")
    lay = _layout(shk.mesh_lane_multiple(mesh, axes))
    shape = (lay.chunk, lay.lanes // shk.LANE_COLS, shk.LANE_COLS)
    x = jax.ShapeDtypeStruct(shape, np.uint8,
                             sharding=NamedSharding(mesh, P(None, axes, None)))
    return lay, axes, x


def _assert_sharded(lowered):
    text = lowered.compile().as_text()
    assert "tpu_custom_call" in text
    assert "all-reduce" in text  # the psum'd candidate count


def test_sharded_shift_and_compiles(mesh_2x2):
    from distributed_grep_tpu.parallel import sharded_kernels as shk

    model = _engine("volcano").shift_and
    lay, axes, x = _mesh_tiles(mesh_2x2)
    _assert_sharded(shk._sharded_shift_and.lower(
        x, sym_ranges=tuple(tuple(r) for r in model.sym_ranges),
        match_bit=int(model.match_bit), chunk=lay.chunk, coarse=True,
        interpret=False, mesh=mesh_2x2, axes=axes))


def test_sharded_fdr_compiles(mesh_2x2):
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from distributed_grep_tpu.ops import pallas_fdr
    from distributed_grep_tpu.parallel import sharded_kernels as shk

    eng = _engine(patterns=_config3())
    banks = eng.fdr.banks
    lay, axes, x = _mesh_tiles(mesh_2x2)
    rep = NamedSharding(mesh_2x2, P())
    tabs = [jax.ShapeDtypeStruct(t.shape, t.dtype, sharding=rep)
            for t in (pallas_fdr.bank_device_tables(b) for b in banks)]
    _assert_sharded(shk._sharded_fdr.lower(
        x, *tabs, ms=tuple(b.m for b in banks),
        plans=tuple(pallas_fdr.kernel_plan(b) for b in banks),
        chunk=lay.chunk, interpret=False, mesh=mesh_2x2, axes=axes))


def test_sharded_fdr_pattern_parallel_compiles(mesh_2x2):
    """The config-5 mesh job's step: lanes over "data", same-plan FDR
    banks over "seq", candidate words OR'd across "seq"."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from distributed_grep_tpu.ops import pallas_fdr
    from distributed_grep_tpu.parallel import sharded_kernels as shk

    eng = _engine(patterns=_config5())
    plans = {(b.m, pallas_fdr.kernel_plan(b)) for b in eng.fdr.banks}
    if len(plans) != 1:
        pytest.skip("mixed-plan banks take the lane-sharded step")
    (m, plan), = plans
    n_banks = len(eng.fdr.banks) + len(eng.fdr.banks) % 2
    tab = pallas_fdr.bank_device_tables(eng.fdr.banks[0])
    tabs = jax.ShapeDtypeStruct(
        (n_banks,) + tab.shape, tab.dtype,
        sharding=NamedSharding(mesh_2x2, P(("seq",))))
    lay = _layout(shk.mesh_lane_multiple(mesh_2x2, ("data",)))
    x = jax.ShapeDtypeStruct(
        (lay.chunk, lay.lanes // shk.LANE_COLS, shk.LANE_COLS), np.uint8,
        sharding=NamedSharding(mesh_2x2, P(None, ("data",), None)))
    _assert_sharded(shk._sharded_fdr_pattern.lower(
        x, tabs, m=m, plan=plan, chunk=lay.chunk, interpret=False,
        mesh=mesh_2x2, data_axes=("data",), pattern_axes=("seq",)))
