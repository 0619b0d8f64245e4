"""Corpus kind ``subtitles``: files of subtitle-like English lines with the
configuration's rare names written in.

A corpus kind is a module ``corpora/<kind>.py``, found by the ``kind`` of
a configuration's ``corpus``.  It gives ``write_file(cfg, seed, index,
path)`` (one corpus file, in a spawned numpy-only process) and
``placeholders(cfg)``: the ``{key}`` values of the kind's own vocabulary
that query patterns may name, as equal-length lists whose entry ``i`` is
one draw (a pattern never takes entry ``i`` twice).

The token join (``TokenTable``) does what ``_join_tokens`` in the
repository's ``benchmarks/baseline_configs.py`` does, copied so that a
change to the program cannot change the yardstick.  The line model is
new: a Zipf draw over the configuration's vocabulary, one capitalised
word at each line start, and the rare names written in as whole tokens.
"""

from __future__ import annotations

from pathlib import Path

import numpy as np


class TokenTable:
    """vocab[idx[i]] + seps[i] for every token, concatenated: one row
    gather and one mask select (the token join of ``_join_tokens``, laid
    out as rows so that no per-byte index arrays are built)."""

    def __init__(self, vocab: list[bytes]):
        self.lens = np.array([len(v) for v in vocab], dtype=np.int64)
        width = int(self.lens.max()) + 1
        self.table = np.zeros((len(vocab), width), dtype=np.uint8)
        for i, v in enumerate(vocab):
            self.table[i, : len(v)] = np.frombuffer(v, dtype=np.uint8)
        self.cols = np.arange(width)

    def join(self, idx: np.ndarray, seps: np.ndarray) -> np.ndarray:
        rows = self.table[idx]
        lens = self.lens[idx]
        rows[np.arange(idx.size), lens] = seps
        return rows[self.cols[None, :] <= lens[:, None]]


def names(cfg: dict) -> list[tuple[str, str]]:
    """The rare vocabulary: ``(first, surname)`` pairs.  First names come
    from the configuration's list; surnames are syllable words from the
    configuration's fixed seed, distinct from each other and from the
    vocabulary.  Fixed, so the query pools are the same in every run."""
    spec = cfg["names"]
    rng = np.random.default_rng([spec["seed"], 7])
    cons, vows = spec["consonants"], spec["vowels"]
    vocab = {w.lower() for w in cfg["vocabulary"]}
    firsts = spec["first_names"]
    out, seen = [], set()
    while len(out) < spec["count"]:
        n_syl = int(rng.integers(spec["syllables"][0], spec["syllables"][1] + 1))
        s = "".join(cons[int(rng.integers(len(cons)))]
                    + vows[int(rng.integers(len(vows)))] for _ in range(n_syl))
        s = s.capitalize()
        if s in seen or s.lower() in vocab:
            continue
        seen.add(s)
        out.append((firsts[len(out) % len(firsts)], s))
    return out


def placeholders(cfg: dict) -> dict[str, list[str]]:
    """``{name}``: a whole "First Surname"; ``{surname}``: a surname."""
    pool = names(cfg)
    return {"name": [f"{f} {s}" for f, s in pool],
            "surname": [s for _, s in pool]}


def subtitles_file(cfg: dict, seed: int, index: int, size: int) -> bytes:
    """One file of subtitle-like lines: Zipf words, a capitalised first
    word per line, each rare name written in ``per_file`` times (a share
    of them in capitals, as shouted lines are)."""
    rng = np.random.default_rng([seed, 11, index])
    words = cfg["vocabulary"]
    v = len(words)
    p = 1.0 / np.arange(1, v + 1) ** cfg["zipf_s"]
    p /= p.sum()
    # the Zipf draw through a 2**16-entry lookup table (each word at
    # least once): one integer draw and one gather per token
    lut = np.repeat(np.arange(v), np.maximum(1, np.round(p * 65536)).astype(int))
    pool = names(cfg)
    firsts = cfg["names"]["first_names"]
    surnames = [s for _, s in pool]
    table = ([w.encode() for w in words]
             + [(w[:1].upper() + w[1:]).encode() for w in words]
             + [f.encode() for f in firsts]
             + [f.upper().encode() for f in firsts]
             + [s.encode() for s in surnames]
             + [s.upper().encode() for s in surnames])
    base_first, base_sur = 2 * v, 2 * v + 2 * len(firsts)
    lo, hi = cfg["line_words"]
    avg_tok = float((np.array([len(w) + 1 for w in words]) * p).sum())
    n_tok = int(size / avg_tok * 1.02) + 1024
    k = rng.integers(lo, hi + 1, size=n_tok // lo + 1)
    k = k[: int(np.searchsorted(np.cumsum(k), n_tok)) + 1]
    n_tok = int(k.sum())
    idx = lut[rng.integers(0, lut.size, size=n_tok)]
    seps = np.full(n_tok, ord(" "), dtype=np.uint8)
    ends = np.cumsum(k) - 1
    seps[ends] = ord("\n")
    starts = np.concatenate([[0], ends[:-1] + 1])
    idx[starts] += v
    per = cfg["names"]["per_file"]
    n_inj = len(pool) * per
    # even positions whose next token is on the same line: two picks
    # never overlap, and first name and surname stay together
    cand = np.flatnonzero(seps[: n_tok - 1] == ord(" "))
    cand = cand[cand % 2 == 0]
    pos = rng.choice(cand, size=n_inj, replace=False)
    who = rng.permutation(np.repeat(np.arange(len(pool)), per))
    upper = rng.random(n_inj) < cfg["names"]["upper_share"]
    idx[pos] = base_first + (who % len(firsts)) + upper * len(firsts)
    idx[pos + 1] = base_sur + who + upper * len(pool)
    # joined in slices: the temporaries stay ~20 MB
    tt = TokenTable(table)
    step = 1 << 20
    out = np.concatenate([tt.join(idx[i : i + step], seps[i : i + step])
                          for i in range(0, n_tok, step)])
    if out.size < size:
        raise ValueError(f"generated {out.size} bytes, wanted {size}")
    out = out[:size]
    out[-1] = ord("\n")
    return out.tobytes()


def write_file(cfg: dict, seed: int, index: int, path: str) -> int:
    data = subtitles_file(cfg, seed, index, cfg["corpus"]["file_bytes"])
    Path(path).write_bytes(data)
    return len(data)
