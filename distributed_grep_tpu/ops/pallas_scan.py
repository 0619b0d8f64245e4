"""Pallas TPU kernel: Shift-And byte scan, gather-free, bit-packed output.

The hot loop the reference runs per line on a Raspberry Pi CPU
(application/grep.go:20-30) becomes a VPU-resident bit-parallel scan:

* Input bytes live in HBM as (chunk, 32, 128) uint8 — 4096 lanes per grid
  block, each lane a contiguous document stripe; blocks of rows are DMA'd
  to VMEM by pallas_call's grid machinery (double-buffered by the
  compiler).
* Per byte step the kernel computes the Shift-And B-mask from the byte
  value with **range compares** (the pattern's per-symbol byte sets as
  (lo, hi) ranges, baked into the kernel as compile-time constants) — no
  table gather, which Pallas TPU does not have — then performs
  ``s = ((s << 1) | 1) & B`` on a (32, 128) uint32 state tile.
* Match bits are packed on the fly, 32 byte-steps per uint32 word, so the
  HBM write traffic is input/32 and the host transfer is tiny.
* The lane state persists in VMEM scratch across sequential grid steps
  along the chunk axis (TPU grids execute sequentially, innermost last),
  so a stripe longer than one block carries its automaton state exactly.

Grid: (lane_blocks, chunk_blocks); chunk innermost.  The engine sizes the
layout so lanes % 4096 == 0 and chunk % (32 * CHUNK_BLOCK_WORDS) == 0.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from distributed_grep_tpu.models.shift_and import ShiftAndModel

SUBLANES = 32  # uint8 tile sublanes; 32*128 = 4096 lanes per grid block
LANE_COLS = 128
LANES_PER_BLOCK = SUBLANES * LANE_COLS
CHUNK_BLOCK_WORDS = 16  # byte-steps per grid block = 32 * this
MAX_TOTAL_RANGES = 48  # compare budget per byte step


def as_tiles(arr_cl, lane_blocks: int) -> jnp.ndarray:
    """(chunk, lanes) -> (chunk, lane_blocks*32, 128) kernel tiles.

    Accepts a host ndarray (copied contiguous, uploaded by the caller's
    jnp.asarray) OR an already-device-resident jnp array — the engine's
    double-buffered feed uploads segment i+1 while segment i scans, and the
    reshape then runs on device (_tile_reshape)."""
    chunk = arr_cl.shape[0]
    if isinstance(arr_cl, jnp.ndarray):
        return _tile_reshape(arr_cl, lane_blocks)
    return jnp.asarray(np.ascontiguousarray(
        arr_cl.reshape(chunk, lane_blocks * SUBLANES, LANE_COLS)
    ))


@functools.partial(jax.jit, static_argnames=("lane_blocks",))
def _tile_reshape(arr_cl, lane_blocks: int) -> jnp.ndarray:
    """as_tiles on device, as one named step, so a profiler trace shows
    what the reshape costs."""
    with jax.named_scope("as_tiles"):
        return arr_cl.reshape(
            arr_cl.shape[0], lane_blocks * SUBLANES, LANE_COLS
        )


def validate_unroll(unroll: int) -> None:
    """Kernels unroll byte steps in sub-blocks of a 32-step word; a factor
    that does not divide 32 would silently skip the tail bytes of every
    word (silent false negatives), so reject it at trace time."""
    if not (1 <= unroll <= 32 and 32 % unroll == 0):
        raise ValueError(f"unroll must divide 32: {unroll}")


def available() -> bool:
    """True when jax's default backend is a TPU (tests use interpret=True
    on the CPU backend).  A backend that fails to initialise raises: the
    kernels must not drop into interpret mode on a chip that is there
    but broken."""
    return jax.default_backend() == "tpu"


def eligible(model: ShiftAndModel) -> bool:
    return model.total_ranges <= MAX_TOTAL_RANGES


# unroll: byte steps per fori sub-block.  v5e sweep (2026-07-30): this
# kernel prefers FULL unroll (232/230 GB/s at 32 vs 218/207 at 8 on the
# 3-class filtered 'volcano') — its live state is one vreg pair, so the
# register pressure that pushes the FDR/NFA kernels to unroll 4-16 never
# materializes here.
def _kernel(data_ref, out_ref, state_ref, *, sym_ranges, match_bit, steps, coarse,
            unroll=32):
    """One grid step: scan `steps` bytes for 4096 lanes.

    Output per 32-byte word, two modes:

    * exact  — bit t set iff a match ends at byte t (the original packing);
      costs ~4 extra vector ops per byte for the per-position test+pack.
    * coarse — the word is nonzero iff ANY candidate match ends inside its
      32-byte span (the running state ORs into an accumulator; one mask
      per word).  For a full model spans are exact (no span-level false
      positives); for a rare-class filtered model (wildcard positions,
      models/shift_and.filtered_for_device) spans are a superset.  Either
      way the engine confirms the span's line(s) on host, overlapped with
      the next segment's scan — coarse words are candidates, never final
      output.
      Measured on v5e (2026-07-30): 139 -> ~290 GB/s for a 7-symbol
      literal; the exact per-byte pack was ~40% of the kernel's ALU work.
    """
    from jax.experimental import pallas as pl  # deferred: import cost

    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        state_ref[:] = jnp.zeros_like(state_ref)

    # Symbols sharing a byte-class share one compare: "needle" has 4
    # distinct classes across 6 positions, so its B-mask build costs 4
    # compares + 4 selects instead of 6 + 6 (repeated letters are the norm
    # in real patterns; the compare loop dominates the kernel's ALU work).
    # Positions with an EMPTY range list are wildcards (the rare-class
    # device filter, models/shift_and.filtered_for_device): their bits are
    # a compile-time constant OR — zero ALU cost per byte.
    groups: dict[tuple, int] = {}
    wildcard = 0
    for j, ranges in enumerate(sym_ranges):
        if not ranges:
            wildcard |= 1 << j
            continue
        groups[tuple(ranges)] = groups.get(tuple(ranges), 0) | (1 << j)
    range_groups = tuple(groups.items())

    n_inner = 32 // unroll

    def word_body(w, carry):
        def sub_body(sx, inner):
            word, s = inner
            for tt in range(unroll):
                b = data_ref[w * 32 + sx * unroll + tt].astype(jnp.int32)
                bmask = jnp.full((SUBLANES, LANE_COLS), jnp.uint32(wildcard))
                for ranges, mask in range_groups:
                    hit = None
                    for lo, hi in ranges:
                        r = (b >= lo) & (b <= hi) if lo != hi else (b == lo)
                        hit = r if hit is None else (hit | r)
                    bmask = bmask | jnp.where(hit, jnp.uint32(mask), jnp.uint32(0))
                s = ((s << jnp.uint32(1)) | jnp.uint32(1)) & bmask
                if coarse:
                    word = word | s
                else:
                    m = (s & jnp.uint32(match_bit)) != 0
                    bit = jnp.uint32(1 << tt) << (sx * jnp.uint32(unroll))
                    word = word | jnp.where(m, bit, jnp.uint32(0))
            return word, s

        word0 = jnp.zeros((SUBLANES, LANE_COLS), dtype=jnp.uint32)
        if n_inner == 1:
            word, s = sub_body(0, (word0, carry))
        else:
            word, s = jax.lax.fori_loop(0, n_inner, sub_body, (word0, carry))
        out_ref[w] = (word & jnp.uint32(match_bit)) if coarse else word
        return s

    final = jax.lax.fori_loop(0, steps // 32, word_body, state_ref[:])
    state_ref[:] = final


@functools.partial(
    jax.jit,
    static_argnames=(
        "sym_ranges", "match_bit", "chunk", "lane_blocks", "interpret", "coarse",
        "unroll",
    ),
)
def _shift_and_pallas(data, *, sym_ranges, match_bit, chunk, lane_blocks,
                      interpret=False, coarse=False, unroll=32):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    steps = 32 * CHUNK_BLOCK_WORDS
    chunk_blocks = chunk // steps
    validate_unroll(unroll)
    kernel = functools.partial(
        _kernel, sym_ranges=sym_ranges, match_bit=match_bit, steps=steps,
        coarse=coarse, unroll=unroll,
    )
    out = pl.pallas_call(
        kernel,
        grid=(lane_blocks, chunk_blocks),
        in_specs=[
            pl.BlockSpec(
                (steps, SUBLANES, LANE_COLS),
                lambda li, ci: (ci, li, 0),
                memory_space=pltpu.VMEM,
            )
        ],
        out_specs=pl.BlockSpec(
            (CHUNK_BLOCK_WORDS, SUBLANES, LANE_COLS),
            lambda li, ci: (ci, li, 0),
            memory_space=pltpu.VMEM,
        ),
        out_shape=jax.ShapeDtypeStruct(
            (chunk // 32, lane_blocks * SUBLANES, LANE_COLS), jnp.uint32
        ),
        scratch_shapes=[pltpu.VMEM((SUBLANES, LANE_COLS), jnp.uint32)],
        interpret=interpret,
    )(data)
    return out


def shift_and_scan_words(
    arr_cl: np.ndarray,
    model: ShiftAndModel,
    interpret: bool | None = None,
    coarse: bool = False,
) -> jnp.ndarray:
    """Run the kernel; returns packed words as a DEVICE array
    (chunk//32, lane_blocks*32, 128) uint32.

    ``coarse=False``: bit t of a word = match ends at that byte — decode
    via ops/sparse.offsets_from_sparse_words.  ``coarse=True``: a word is
    nonzero iff some candidate match ends in its 32-byte span (~2x kernel
    throughput; exact at span granularity for full models, a superset for
    rare-class filtered ones) — decode via
    ops/sparse.span_starts_from_sparse_words and CONFIRM the span's lines
    (mandatory for filtered models).

    Requires lanes % 4096 == 0 and chunk % 512 == 0 (the engine's layout
    guarantees this on the pallas path).
    """
    chunk, lanes = arr_cl.shape
    steps = 32 * CHUNK_BLOCK_WORDS
    if lanes % LANES_PER_BLOCK or chunk % steps:
        raise ValueError(f"pallas layout needs lanes%{LANES_PER_BLOCK}==0, chunk%{steps}==0")
    if not eligible(model):
        raise ValueError("pattern exceeds the pallas compare budget")
    lane_blocks = lanes // LANES_PER_BLOCK
    data = as_tiles(arr_cl, lane_blocks)
    if interpret is None:
        interpret = not available()
    return _shift_and_pallas(
        data,
        sym_ranges=tuple(tuple(r) for r in model.sym_ranges),
        match_bit=int(model.match_bit),
        chunk=chunk,
        lane_blocks=lane_blocks,
        interpret=interpret,
        coarse=coarse,
    )


def shift_and_scan(
    arr_cl: np.ndarray, model: ShiftAndModel, interpret: bool | None = None
) -> np.ndarray:
    """Dense-output wrapper (tests): packed bits in the scan_jnp convention."""
    chunk, lanes = arr_cl.shape
    words = shift_and_scan_words(arr_cl, model, interpret)
    return _unpack_words_to_lane_bits(np.asarray(words), chunk, lanes)


def _unpack_words_to_lane_bits(words: np.ndarray, chunk: int, lanes: int) -> np.ndarray:
    """Convert time-packed kernel words to the (chunk, lanes//8) lane-packed
    convention shared with scan_jnp (bit t of words[w, s, l] = match at
    chunk position w*32+t for lane (s // 32)*4096? — see reshape below)."""
    # words: (chunk//32, lane_blocks*32, 128) uint32; lane id of (S, l):
    # block = S // 32, sublane = S % 32 -> lane = block*4096 + sublane*128 + l
    n_words, S, L = words.shape
    lane_blocks = S // SUBLANES
    # bits along time: expand to (chunk, S, L) bool
    t = np.arange(32, dtype=np.uint32)
    bits = (words[:, None, :, :] >> t[None, :, None, None]) & 1  # (w, t, S, L)
    match = bits.reshape(chunk, S, L).astype(bool)
    # lane index mapping
    match = match.reshape(chunk, lane_blocks, SUBLANES, LANE_COLS)
    match = match.reshape(chunk, lanes)
    return np.packbits(match, axis=1, bitorder="little")
