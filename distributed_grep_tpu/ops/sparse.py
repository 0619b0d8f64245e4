"""Sparse match-result decoding: device packed bits -> host byte offsets.

Companion to scan_jnp.sparse_nonzero: the device keeps the dense packed
match plane and sends back only its blocks that hold a nonzero element
(up to scan_jnp.FETCH_CAP of them, in one transfer; a plane with more is
downloaded whole).  The host turns them into (index, value) pairs for
the nonzero bytes/words, and this module decodes absolute match
end-offsets from the coordinates.  For sparse matches the host link
carries the matched blocks, not a dense plane the size of the shard.
"""

from __future__ import annotations

import numpy as np

from distributed_grep_tpu.ops.layout import Layout


# The bit index of a uint32 power of two from one multiply and one shift
# (a de Bruijn sequence): x * _DE_BRUIJN >> 27 is distinct for each bit.
_DE_BRUIJN = np.uint32(0x077CB531)
_LOW_BIT = np.zeros(32, dtype=np.int64)
_LOW_BIT[(np.uint32(1) << np.arange(32, dtype=np.uint32)) * _DE_BRUIJN
         >> np.uint32(27)] = np.arange(32)


def offsets_from_sparse_lane_bytes(
    idx: np.ndarray, vals: np.ndarray, layout: Layout
) -> np.ndarray:
    """Decode scan_jnp packing: packed (chunk, lanes//8) uint8, flat index
    = c*(lanes//8) + g, bit k = lane g*8+k.  Returns sorted end offsets."""
    if idx.size == 0:
        return np.zeros(0, dtype=np.int64)
    g8 = layout.lanes // 8
    c = idx // g8
    g = idx % g8
    out = []
    for k in range(8):
        sel = (vals >> k) & 1 != 0
        if sel.any():
            lane = g[sel] * 8 + k
            out.append(lane * layout.chunk + c[sel] + 1)
    offsets = np.concatenate(out) if out else np.zeros(0, dtype=np.int64)
    offsets = offsets[offsets <= layout.n_real]
    offsets.sort()
    return offsets


def _word_lanes(idx: np.ndarray, layout: Layout) -> tuple[np.ndarray, np.ndarray]:
    """(word row w, lane) of the flat indices of a Pallas word plane
    (chunk//32, S, 128): index (w*S + s)*128 + l is lane
    (s//32)*4096 + (s%32)*128 + l, which is s*128 + l, so one divmod by
    the lane count gives both."""
    return np.divmod(idx, layout.lanes)


def span_starts_from_sparse_words(
    idx: np.ndarray, layout: Layout
) -> np.ndarray:
    """Decode the COARSE Pallas packing (pallas_scan coarse=True): a nonzero
    word means "some match ends in this 32-byte stripe span"; values don't
    matter.  Returns sorted document offsets of span starts — each span is
    [start, min(start+32, stripe/document end)); the engine confirms the
    lines overlapping it."""
    if idx.size == 0:
        return np.zeros(0, dtype=np.int64)
    w, lane = _word_lanes(idx, layout)
    starts = lane * layout.chunk + w * 32
    starts = starts[starts < layout.n_real]
    starts.sort()
    return starts.astype(np.int64)


def offsets_from_sparse_words(
    idx: np.ndarray, vals: np.ndarray, layout: Layout
) -> np.ndarray:
    """Decode the Pallas kernel packing: words (chunk//32, S, 128) uint32,
    flat index = (w*S + s)*128 + l, bit t = chunk position w*32+t, lane
    = (s//32)*4096 + (s%32)*128 + l.  Returns sorted end offsets."""
    if idx.size == 0:
        return np.zeros(0, dtype=np.int64)
    w, lane = _word_lanes(idx, layout)
    base = lane * layout.chunk + w * 32 + 1  # the end offset of bit 0
    v = np.asarray(vals).astype(np.uint32, copy=False)
    out = []
    # one pass per set bit of the fullest word, each over the words that
    # still hold a bit: peel every word's lowest set bit
    while True:
        keep = v != 0
        v, base = v[keep], base[keep]
        if not v.size:
            break
        low = v & (~v + np.uint32(1))
        out.append(base + _LOW_BIT[(low * _DE_BRUIJN) >> np.uint32(27)])
        v = v ^ low
    offsets = np.concatenate(out) if out else np.zeros(0, dtype=np.int64)
    offsets = offsets[offsets <= layout.n_real]
    offsets.sort()
    return offsets
