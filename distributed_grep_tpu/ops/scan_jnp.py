"""XLA scan engines: DFA table scan and Shift-And, lane-parallel.

Both engines take the column-major stripe array (chunk, lanes) uint8 from
ops/layout.py and return packed match bits (chunk, lanes/8) uint8 — bit k of
out[c, g] is "a match ends at byte (c, lane g*8+k)".  Device->host transfer
is input/8; offset decoding happens on the host (ops/lines.py).

Design notes (TPU-first):

* The per-byte recurrence is sequential along a stripe but vectorized over
  lanes: one lax.scan over the chunk axis, each step doing O(lanes) VPU work.
* The byte->class and byte->B-mask table lookups are hoisted out of the scan
  as ONE whole-array gather (XLA lowers a 256-entry table gather fine on
  TPU); the in-loop DFA gather indexes the [n_states*n_classes] flat table.
* '$' accepts (accept_eol) are evaluated against a pre-shifted
  next-byte-is-newline plane, so anchors cost nothing in the loop.
* Everything is shapes-static, branch-free, jit-compiled once per
  (layout, model) signature.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from distributed_grep_tpu.models.dfa import DfaTable
from distributed_grep_tpu.models.shift_and import ShiftAndModel

NL = 0x0A


def _pack_lane_bits(match: jnp.ndarray) -> jnp.ndarray:
    """(chunk, lanes) bool -> (chunk, lanes//8) uint8, bit k = lane g*8+k."""
    c, l = match.shape
    assert l % 8 == 0, "lanes must be a multiple of 8 for bit packing"
    bits = match.reshape(c, l // 8, 8).astype(jnp.uint8)
    powers = jnp.asarray([1, 2, 4, 8, 16, 32, 64, 128], dtype=jnp.uint8)
    return (bits * powers).sum(axis=-1, dtype=jnp.uint8)


def dfa_scan_body(
    data_cl: jnp.ndarray,  # (chunk, lanes) uint8
    trans_flat: jnp.ndarray,  # (n_states * n_classes,) int32
    byte_to_cls: jnp.ndarray,  # (256,) int32
    accept: jnp.ndarray,  # (n_states,) bool
    accept_eol: jnp.ndarray,  # (n_states,) bool
    init: jnp.ndarray,  # (lanes,) int32 initial state per lane
    n_classes: int,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Shared DFA scan recurrence -> (final_states, match bool (chunk, lanes)).

    The single source of truth for scan semantics (the end-of-stripe
    next-byte-is-'\\n' rule, '$' accepts, the transition step) — both the
    single-chip core below and parallel/sharded_scan's shard_map body call
    this, so the two paths cannot drift.
    """
    chunk, lanes = data_cl.shape
    # Hoisted table lookups: one gather for the whole array.
    cls = byte_to_cls[data_cl.astype(jnp.int32)]  # (chunk, lanes) int32
    # next byte within the same stripe is the next row; the final row's
    # successor is the next stripe's first byte — treat it as '\n' (stripe
    # tails are re-checked by the host stitcher anyway, and real documents
    # are padded with '\n').
    nl_next = jnp.concatenate(
        [data_cl[1:] == NL, jnp.ones((1, lanes), dtype=bool)], axis=0
    )

    def step(states, inputs):
        cls_row, nl_row = inputs
        nxt = trans_flat[states * n_classes + cls_row]
        match = accept[nxt] | (accept_eol[nxt] & nl_row)
        return nxt, match

    return jax.lax.scan(step, init, (cls, nl_next))


@partial(jax.jit, static_argnames=("n_classes",))
def _dfa_scan_core(
    data_cl: jnp.ndarray,
    trans_flat: jnp.ndarray,
    byte_to_cls: jnp.ndarray,
    accept: jnp.ndarray,
    accept_eol: jnp.ndarray,
    start: jnp.ndarray,  # () int32
    n_classes: int,
) -> jnp.ndarray:
    lanes = data_cl.shape[1]
    init = jnp.full((lanes,), start, dtype=jnp.int32)
    _, match = dfa_scan_body(
        data_cl, trans_flat, byte_to_cls, accept, accept_eol, init, n_classes
    )
    return _pack_lane_bits(match)


@partial(jax.jit, static_argnames=("k", "n_classes"))
def _dfa_stride_core(
    data_cl: jnp.ndarray,  # (chunk, lanes) uint8, chunk % k == 0
    trans_k_flat: jnp.ndarray,  # (n_states * n_classes**k,) int32 packed
    byte_to_cls: jnp.ndarray,  # (256,) int32
    start: jnp.ndarray,  # () int32
    k: int,
    n_classes: int,
) -> jnp.ndarray:
    """k-byte-stride DFA scan (models/dfa.StrideTable): chunk/k lax.scan
    steps of one gather each; per-byte match positions recovered exactly
    from the packed accept bitmaps."""
    chunk, lanes = data_cl.shape
    cols = n_classes**k
    cls = byte_to_cls[data_cl.astype(jnp.int32)]  # (chunk, lanes)
    cls_k = cls.reshape(chunk // k, k, lanes)
    idx = cls_k[:, 0, :]
    for t in range(1, k):  # first byte of the stride is the most significant
        idx = idx * n_classes + cls_k[:, t, :]

    init = jnp.full((lanes,), start, dtype=jnp.int32)

    def step(states, idx_row):
        entry = trans_k_flat[states * cols + idx_row]
        return entry >> k, entry & ((1 << k) - 1)

    _, bitmaps = jax.lax.scan(step, init, idx)  # (chunk//k, lanes) int32
    t = jnp.arange(k, dtype=bitmaps.dtype)
    match = ((bitmaps[:, None, :] >> t[None, :, None]) & 1).astype(bool)
    return _pack_lane_bits(match.reshape(chunk, lanes))


def dfa_scan_stride(data_cl, stride_table) -> jnp.ndarray:
    """Run the stride engine; same packed-bit output convention as dfa_scan."""
    assert data_cl.shape[0] % stride_table.k == 0, "stride k must divide chunk"
    return _dfa_stride_core(
        jnp.asarray(data_cl),
        jnp.asarray(stride_table.trans_k.reshape(-1)),
        jnp.asarray(stride_table.byte_to_cls.astype(np.int32)),
        jnp.int32(stride_table.start),
        stride_table.k,
        stride_table.n_classes,
    )


def dfa_scan(data_cl: np.ndarray, table: DfaTable) -> jnp.ndarray:
    """Run the DFA engine; returns packed match bits as a device array
    (decode sparsely via sparse_nonzero + ops/sparse, or np.asarray for
    the dense path)."""
    return _dfa_scan_core(
        jnp.asarray(data_cl),
        jnp.asarray(table.trans.astype(np.int32).reshape(-1)),
        jnp.asarray(table.byte_to_cls.astype(np.int32)),
        jnp.asarray(table.accept),
        jnp.asarray(table.accept_eol),
        jnp.int32(table.start),
        table.n_classes,
    )


@jax.jit
def _shift_and_core(
    data_cl: jnp.ndarray,  # (chunk, lanes) uint8
    b_table: jnp.ndarray,  # (256,) uint32
    match_bit: jnp.ndarray,  # () uint32
) -> jnp.ndarray:
    # One whole-array gather for B[byte]; the scan is then pure VPU
    # shift/and/or — no gathers in the loop at all.
    b_all = b_table[data_cl.astype(jnp.int32)]  # (chunk, lanes) uint32
    lanes = data_cl.shape[1]
    init = jnp.zeros((lanes,), dtype=jnp.uint32)

    def step(s, b_row):
        s = ((s << jnp.uint32(1)) | jnp.uint32(1)) & b_row
        return s, (s & match_bit) != 0

    _, match = jax.lax.scan(step, init, b_all)
    return _pack_lane_bits(match)


def shift_and_scan(data_cl: np.ndarray, model: ShiftAndModel) -> jnp.ndarray:
    """Packed match bits as a device array (see dfa_scan)."""
    return _shift_and_core(
        jnp.asarray(data_cl),
        jnp.asarray(model.b_table),
        jnp.uint32(model.match_bit),
    )


# ----------------------------------------------------- sparse result fetch
# grep matches are sparse, and the host<->device link is the narrow pipe.
# Instead of downloading the dense packed-bit plane (input/8 bytes), the
# device views the flat plane as blocks of FETCH_BLOCK elements, flags the
# blocks that hold a nonzero element, and gathers up to FETCH_CAP of them:
# the count, their block ids and their elements come back in ONE transfer
# (no count first, no scatter over the plane).  A plane with more nonzero
# blocks than FETCH_CAP is downloaded whole instead; the host decodes
# either with one np.flatnonzero.

# Each XLA-side step of a scan is a jitted function of its own with a
# named scope, so a profiler trace names it (its XLA module and its ops'
# metadata) instead of an anonymous eager op.

FETCH_BLOCK = 128  # elements a block (a power of two)
FETCH_CAP = 1024  # nonzero blocks one fetch brings back


def _fetch_shape(size: int) -> tuple[int, int]:
    """(blocks in a plane of ``size`` elements, blocks a fetch returns)."""
    n_blocks = -(-size // FETCH_BLOCK)
    return n_blocks, min(FETCH_CAP, n_blocks)


@jax.jit
def fetch_nonzero_blocks(packed: jnp.ndarray) -> jnp.ndarray:
    """One uint32 vector: the plane's count of nonzero blocks, the ids of
    the first ``cap`` of them (ascending; past the count, ``n_blocks``),
    then those blocks' elements, ``4 // itemsize`` to a word,
    little-endian (``packed``: unsigned, 1, 2 or 4 bytes)."""
    with jax.named_scope("fetch_nonzero_blocks"):
        flat = packed.reshape(-1)
        n_blocks, cap = _fetch_shape(flat.size)
        blocks = jnp.pad(flat, (0, n_blocks * FETCH_BLOCK - flat.size))
        blocks = blocks.reshape(n_blocks, FETCH_BLOCK)
        # the j-th nonzero block is the first whose running count reaches j
        running = jnp.cumsum(jnp.any(blocks != 0, axis=1), dtype=jnp.int32)
        ids = jnp.searchsorted(
            running, jnp.arange(1, cap + 1, dtype=jnp.int32)
        ).astype(jnp.int32)
        got = blocks[ids].astype(jnp.uint32)  # ids past the count clamp
        bits = 8 * packed.dtype.itemsize
        per = 32 // bits
        got = got.reshape(cap, FETCH_BLOCK // per, per)
        words = got[..., 0]
        for j in range(1, per):
            words = words | (got[..., j] << jnp.uint32(bits * j))
        return jnp.concatenate([
            running[-1:].astype(jnp.uint32), ids.astype(jnp.uint32),
            words.reshape(-1),
        ])


@jax.jit
def or_words(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Two candidate-word planes OR'd on device (FDR banks, the pairset
    sidecar)."""
    with jax.named_scope("or_banks"):
        return a | b


def sparse_nonzero(
    packed_dev, counts: dict | None = None
) -> tuple[np.ndarray, np.ndarray]:
    """(flat indices ascending, int64; values) of the nonzero elements of
    a device packed-bit plane.  ``counts``, if given, gains the plane's
    nonzero blocks (``fetch_blocks``) and whether it was downloaded whole
    (``fetch_overflows``)."""
    out = np.asarray(fetch_nonzero_blocks(packed_dev))
    n = int(out[0])
    _, cap = _fetch_shape(packed_dev.size)
    overflow = n > cap
    if overflow:
        flat = np.asarray(packed_dev).reshape(-1)
        idx = np.flatnonzero(flat)
        vals = flat[idx]
    else:
        blocks = out[1 + cap:].view(packed_dev.dtype)[: n * FETCH_BLOCK]
        pos = np.flatnonzero(blocks)
        vals = blocks[pos]
        idx = out[1 + pos // FETCH_BLOCK].astype(np.int64) * FETCH_BLOCK
        idx += pos % FETCH_BLOCK
    if counts is not None:
        counts["fetch_blocks"] = counts.get("fetch_blocks", 0) + n
        counts["fetch_overflows"] = counts.get("fetch_overflows", 0) + overflow
    return idx.astype(np.int64, copy=False), vals


@partial(jax.jit, static_argnames=("k",))
def _approx_scan_core(
    data_cl: jnp.ndarray,  # (chunk, lanes) uint8
    b_table: jnp.ndarray,  # (256,) uint32
    match_bit: jnp.ndarray,  # () uint32
    k: int,
) -> jnp.ndarray:
    """agrep <= k-error scan (models/approx.py recurrence), lane-parallel:
    k+1 uint32 rows per lane, newline-reset before the match check so
    errorful matches never span lines."""
    b_all = b_table[data_cl.astype(jnp.int32)]  # (chunk, lanes) uint32
    is_nl = data_cl == NL
    lanes = data_cl.shape[1]
    seeds = [jnp.uint32((1 << j) - 1) for j in range(k + 1)]
    init = tuple(jnp.full((lanes,), s, dtype=jnp.uint32) for s in seeds)

    def step(R, inputs):
        b_row, nl_row = inputs
        new = [((R[0] << jnp.uint32(1)) | jnp.uint32(1)) & b_row]
        for j in range(1, k + 1):
            new.append(
                (((R[j] << jnp.uint32(1)) | jnp.uint32(1)) & b_row)
                | R[j - 1]
                | (R[j - 1] << jnp.uint32(1))
                | (new[j - 1] << jnp.uint32(1))
                | seeds[j]
            )
        new = [jnp.where(nl_row, seeds[j], new[j]) for j in range(k + 1)]
        return tuple(new), (new[k] & match_bit) != 0

    _, match = jax.lax.scan(step, init, (b_all, is_nl))
    return _pack_lane_bits(match)


def approx_scan(data_cl: np.ndarray, model) -> jnp.ndarray:
    """Packed match bits for the approximate model (see dfa_scan)."""
    return _approx_scan_core(
        jnp.asarray(data_cl),
        jnp.asarray(model.base.b_table),
        jnp.uint32(model.match_bit),
        model.k,
    )
