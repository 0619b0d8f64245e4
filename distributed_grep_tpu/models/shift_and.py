"""Shift-And bit-parallel model for literals and short class sequences.

The fastest TPU scan path: the automaton state is one uint32 per lane, and a
byte step is ``s = ((s << 1) | 1) & B[byte]`` — pure VPU integer ops, no
table gathers (Pallas TPU has no vector gather; B[byte] is computed with
per-symbol compare/or, ops/shift_and_scan.py).  Bit j of ``s`` means "the
first j+1 symbols of the pattern match ending at this byte"; a match ends
where bit m-1 is set.

Eligible patterns: a plain concatenation of single-byte chars / classes
(after case folding), length <= 32, no anchors/alternation/repeats — i.e.
what a literal grep or a character-class literal like 'h[ae]llo' compiles
to.  Everything else uses the DFA model.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from distributed_grep_tpu.models import dfa as _dfa
from distributed_grep_tpu.models.dfa import NL, Char, Concat, RegexError

MAX_SYMBOLS = 32  # state fits a uint32 lane


@dataclass
class ShiftAndModel:
    """B-masks for the Shift-And scan.

    b_table    [256] uint32 — B[byte]: bit j set iff byte matches symbol j
    sym_ranges per symbol, the byte set as sorted disjoint (lo, hi) ranges —
               lets the Pallas kernel compute B[byte] with range compares
               instead of a table gather (Pallas TPU has no vector gather)
    length     number of symbols (match bit = length - 1)
    """

    b_table: np.ndarray
    sym_ranges: list[list[tuple[int, int]]]
    length: int
    pattern: str

    @property
    def match_bit(self) -> np.uint32:
        return np.uint32(1 << (self.length - 1))

    @property
    def total_ranges(self) -> int:
        return sum(len(r) for r in self.sym_ranges)


def try_compile_shift_and(
    pattern: str, ignore_case: bool = False
) -> ShiftAndModel | None:
    """Compile if the pattern is a Shift-And-eligible symbol sequence, else None."""
    try:
        ast = _dfa._Parser(pattern, ignore_case).parse()
    except RegexError:
        return None  # let compile_dfa surface the syntax error

    parts = ast.parts if isinstance(ast, Concat) else [ast]
    if not parts:
        return None
    sym_masks: list[int] = []
    for p in parts:
        if not isinstance(p, Char):
            return None  # repeats/alts/anchors -> DFA model
        if p.mask >> NL & 1:
            return None  # newline-consuming -> CPU fallback path decides
        sym_masks.append(p.mask)
    if len(sym_masks) > MAX_SYMBOLS:
        return None

    b = np.zeros(256, dtype=np.uint32)
    for j, mask in enumerate(sym_masks):
        bit = np.uint32(1 << j)
        for byte in range(256):
            if mask >> byte & 1:
                b[byte] |= bit
    return ShiftAndModel(
        b_table=b,
        sym_ranges=[_mask_to_ranges(m) for m in sym_masks],
        length=len(sym_masks),
        pattern=pattern,
    )


# ------------------------------------------------------- rare-class filter

# Byte-frequency prior for choosing which classes the device filter checks.
# English letter frequencies (upper+lower folded), whitespace/digits, and a
# uniform floor for everything else.  Exactness NEVER depends on this prior
# — it only tunes device work vs host confirm (the span-confirm pass in
# ops/engine.py restores exact lines either way), and the engine disables
# the filter for the rest of a scan if a segment's candidate rate shows
# the prior was badly wrong for the corpus.
_LETTER_FREQ = {
    "e": 0.127, "t": 0.091, "a": 0.082, "o": 0.075, "i": 0.070, "n": 0.067,
    "s": 0.063, "h": 0.061, "r": 0.060, "d": 0.043, "l": 0.040, "c": 0.028,
    "u": 0.028, "m": 0.024, "w": 0.024, "f": 0.022, "g": 0.020, "y": 0.020,
    "p": 0.019, "b": 0.015, "v": 0.0098, "k": 0.0077, "x": 0.0015,
    "q": 0.00095, "j": 0.00015, "z": 0.00007,
}


def _byte_prior() -> np.ndarray:
    prior = np.full(256, 1.0 / 256, dtype=np.float64)
    for ch, f in _LETTER_FREQ.items():
        prior[ord(ch)] = f
        prior[ord(ch.upper())] = f / 4  # uppercase much rarer in prose
    prior[ord(" ")] = 0.15
    for d in b"0123456789":
        prior[d] = 0.01
    return prior / prior.sum()


_PRIOR = _byte_prior()


def _text_prior() -> np.ndarray:
    """Prose-conditional byte prior: the `_byte_prior` weights renormalized
    over printable ASCII + whitespace only.

    `_byte_prior`'s uniform 1/256 floor over all 256 byte values divides
    its mass ~2.25x below real prose frequencies (' ' is ~15% of text
    bytes, but the normalized prior says 6.7%) — right for ranking classes
    by rarity (its original job), but an underestimate when a DENSITY gate
    needs an absolute matches-per-byte number for a text corpus
    (models/pairset.expected_match_density).  Gates take the max of the
    two priors' estimates: this one models text, the floored one models
    binary corpora.

    `_LETTER_FREQ` is conditioned on letters only (sums to ~1), so the
    weights here rescale it by the letter share of prose characters
    (~70% lowercase, ~1/15 of that uppercase) around space at ~17% —
    the standard all-character English distribution."""
    w = np.zeros(256, dtype=np.float64)
    w[9] = 0.002  # tab
    w[10] = 0.02  # newline (members never contain it; mass only)
    w[33:127] = 0.0015  # punctuation floor
    for ch, f in _LETTER_FREQ.items():
        w[ord(ch)] = f * 0.70
        w[ord(ch.upper())] = f * 0.70 / 15
    w[ord(" ")] = 0.17
    for d in b"0123456789":
        w[d] = 0.006
    return w / w.sum()

# Keep adding checked classes until the modeled false-candidate rate drops
# below this.  Economics: a span candidate costs ~1 us of host line confirm,
# the full-class device scan ~5 ps/byte — at 2e-6/byte the confirm is ~2 ps
# /byte, safely hidden, with ~2.5x margin for prior error.
FILTER_FP_TARGET = 2e-6


def filtered_for_device(
    model: ShiftAndModel, fp_target: float = FILTER_FP_TARGET
) -> ShiftAndModel | None:
    """A device-filter variant of ``model`` that checks only its rarest
    byte-classes (remaining positions become wildcards), or None when no
    class can be dropped.

    The per-class compare chain is the Pallas kernel's ALU bottleneck
    (ops/pallas_scan.py); every dropped class removes its compares while
    the kernel's span-candidate contract is preserved — candidates stay a
    superset, the engine's span line confirm restores exactness.  Classes
    are added rarest-first (every position of a chosen class is checked:
    repeated classes square their frequency for free) until the modeled
    false-candidate rate on the byte prior clears ``fp_target``."""
    classes: dict[tuple, list[int]] = {}
    for j, ranges in enumerate(model.sym_ranges):
        classes.setdefault(tuple(ranges), []).append(j)

    def freq(ranges: tuple) -> float:
        return float(sum(_PRIOR[lo : hi + 1].sum() for lo, hi in ranges))

    order = sorted(classes.items(), key=lambda kv: freq(kv[0]))
    fp = 1.0
    kept: set[int] = set()
    for ranges, positions in order:
        kept.update(positions)
        fp *= freq(ranges) ** len(positions)
        if fp <= fp_target:
            break
    if len(kept) == model.length:
        return None  # nothing dropped — use the full model
    b = model.b_table.copy()
    sym_ranges: list[list[tuple[int, int]]] = []
    for j in range(model.length):
        if j in kept:
            sym_ranges.append(model.sym_ranges[j])
        else:
            sym_ranges.append([])  # wildcard: every byte matches position j
            b |= np.uint32(1 << j)
    return ShiftAndModel(
        b_table=b, sym_ranges=sym_ranges, length=model.length,
        pattern=model.pattern,
    )


def _mask_to_ranges(mask: int) -> list[tuple[int, int]]:
    """256-bit membership mask -> sorted disjoint inclusive (lo, hi) ranges."""
    ranges: list[tuple[int, int]] = []
    b = 0
    while b < 256:
        if mask >> b & 1:
            lo = b
            while b < 256 and mask >> b & 1:
                b += 1
            ranges.append((lo, b - 1))
        else:
            b += 1
    return ranges


def scan_reference(model: ShiftAndModel, data: bytes) -> np.ndarray:
    """Host-side oracle: end offsets (index+1) of every match."""
    s = np.uint32(0)
    hits = []
    b = model.b_table
    mb = model.match_bit
    for i, byte in enumerate(data):
        s = np.uint32(((np.uint32(s) << np.uint32(1)) | np.uint32(1)) & b[byte])
        if s & mb:
            hits.append(i + 1)
    return np.asarray(hits, dtype=np.uint64)
