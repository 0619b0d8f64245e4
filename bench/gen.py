"""Seeded data and query generation for the benchmark: the corpus files
and every client's query list, all made from ``--seed``.

One general generator reads every traffic mix, a data file
``traffic/<mix>.json``:

- ``clients``, ``poll_s``, ``n_reduce``: the closed-loop clients, their
  status poll period, and each job's reduce tasks;
- ``families``: each query family's ``pattern``, with ``{key}`` holes,
  and its flags (``fixed``: -F, ``ignore_case``: -i).  A hole takes a
  value of the corpus kind's ``placeholders`` that no other pool pattern
  took, or a word of the mix's ``words[key]``, distinct within one
  pattern;
- ``pool_seed``, ``pool_per_family``: the fixed pattern pool, the same
  in every run, so that its programs are compiled once per checkout;
- ``files``: ``per_query`` files make a query's file set; each client's
  sets are cut from its own share of the corpus, or with ``shared`` from
  all of it;
- ``repeats`` (optional): ``zipf_s`` and ``queries_per_client``: each
  client draws its queries with repeats, Zipf over a seeded ranking of
  its (pattern, file set) pairs.  Without it each pair is sent once;
- ``warmup``: ``fill_pattern``, a literal that matches nothing, is sent
  over each file set the window's queries name before anything else;
- ``check_sample``: how many completed queries a run compares.

The corpus is the configuration's: its ``corpus.kind`` names the module
``corpora/<kind>.py`` that writes the files (``harness.corpus_kind``).
Nothing here imports jax or the program: the generator processes of
set-up and the client processes of the window import this module.
"""

from __future__ import annotations

import re
from pathlib import Path

import numpy as np

import harness

_HOLE = re.compile(r"\{(\w+)\}")


def corpus_files(cfg: dict, data_dir: Path) -> list[Path]:
    c = cfg["corpus"]
    return [data_dir / f"{c['prefix']}{i:02d}{c['suffix']}"
            for i in range(c["files"])]


def write_corpus_file(cfg: dict, seed: int, index: int, path: str) -> int:
    """Pool task (spawned process, numpy only): one corpus file."""
    kind = harness.corpus_kind(cfg["corpus"]["kind"])
    return kind.write_file(cfg, seed, index, path)


def pattern_pool(cfg: dict, traffic: dict) -> dict[str, list[dict]]:
    """Each family's fixed pool of ``pool_per_family`` patterns, drawn
    from ``traffic['pool_seed']``.  Each entry: the pattern, the literals
    a candidate filter would look for (``required``), and the family's
    flags."""
    rng = np.random.default_rng(traffic["pool_seed"])
    own = harness.corpus_kind(cfg["corpus"]["kind"]).placeholders(cfg)
    n_own = min((len(v) for v in own.values()), default=0)
    order = [int(i) for i in rng.permutation(n_own)]
    words = traffic.get("words", {})
    out: dict[str, list[dict]] = {}
    for fam in traffic["families"]:
        rows = []
        for _ in range(traffic["pool_per_family"]):
            required: list[str] = []

            def one(m: re.Match) -> str:
                key = m.group(1)
                if key in own:
                    lit = own[key][order.pop()]
                elif key in words:
                    left = [w for w in words[key] if w not in required]
                    lit = left[int(rng.integers(len(left)))]
                else:
                    raise ValueError(f"unknown placeholder {m.group(0)!r}")
                required.append(lit)
                return lit

            rows.append({"family": fam["name"],
                         "pattern": _HOLE.sub(one, fam["pattern"]),
                         "required": required,
                         "fixed": bool(fam.get("fixed")),
                         "ignore_case": bool(fam.get("ignore_case"))})
        out[fam["name"]] = rows
    return out


def tenant_files(traffic: dict, files: list[Path], client: int) -> list[str]:
    """The files client ``client`` searches: its own consecutive share of
    the corpus (a tenant searching its own data, no two clients on one
    file), or with ``files.shared`` all of it."""
    if traffic["files"].get("shared"):
        return [str(p) for p in files]
    per = len(files) // traffic["clients"]
    return [str(p) for p in files[client * per : (client + 1) * per]]


def warm_file(traffic: dict, files: list[Path], i: int) -> str:
    """Where pattern ``i`` of the flat pool (pattern j of family f) is
    warmed: file j of client (j + f) mod clients, which never sends
    pattern ``i`` over that file.  The owners of one family's patterns,
    and of one pattern index's families, are all different clients."""
    n_fams = len(traffic["families"])
    j, f = divmod(i, n_fams)
    own = tenant_files(traffic, files, (j + f) % traffic["clients"])
    return own[j % len(own)]


def _flat_pool(cfg: dict, traffic: dict) -> list[dict]:
    """The pool as one list, families interleaved (pattern i of every
    family before pattern i + 1 of any)."""
    pool = list(pattern_pool(cfg, traffic).values())
    return [rows[j] for j in range(max(len(r) for r in pool))
            for rows in pool if j < len(rows)]


def _file_sets(traffic: dict, files: list[Path], client: int) -> list[list[str]]:
    n = traffic["files"]["per_query"]
    own = tenant_files(traffic, files, client)
    return [own[i : i + n] for i in range(0, len(own) - n + 1, n)]


def queries(cfg: dict, traffic: dict, seed: int,
            files: list[Path]) -> list[list[dict]]:
    """Every client's query list, in order, from the seed.  The pairs of
    a pool pattern and a file set of the client's, but never a pattern
    over the file it was warmed on; each pair once, in a seeded order
    that gives each family an equal share of every block of
    len(families) queries, or with ``repeats`` a Zipf draw over them."""
    pool = _flat_pool(cfg, traffic)
    fams = [f["name"] for f in traffic["families"]]
    rep = traffic.get("repeats")
    out: list[list[dict]] = []
    for c in range(traffic["clients"]):
        rng = np.random.default_rng([seed, 31, c])
        by_fam: dict[str, list[dict]] = {f: [] for f in fams}
        for i, p in enumerate(pool):
            w = warm_file(traffic, files, i)
            for fs in _file_sets(traffic, files, c):
                if w not in fs:
                    by_fam[p["family"]].append(dict(p, files=fs))
        for f in fams:
            by_fam[f] = [by_fam[f][int(j)]
                         for j in rng.permutation(len(by_fam[f]))]
        qs: list[dict] = []
        if rep:
            pairs = [q for f in fams for q in by_fam[f]]
            pairs = [pairs[int(j)] for j in rng.permutation(len(pairs))]
            weights = 1.0 / np.arange(1, len(pairs) + 1) ** rep["zipf_s"]
            qs = [pairs[int(j)] for j in rng.choice(
                len(pairs), size=rep["queries_per_client"],
                p=weights / weights.sum())]
        else:
            while all(by_fam.values()):
                for j in rng.permutation(len(fams)):
                    qs.append(by_fam[fams[int(j)]].pop())
        out.append([dict(q, client=c, k=k, seed=seed)
                    for k, q in enumerate(qs)])
    return out


def job_body(q: dict, n_reduce: int) -> dict:
    """The POST /jobs body ``dgrep submit --backend device`` sends for
    this query: -F turns the literal into an escaped pattern."""
    opts: dict = {"backend": "device"}
    if q["ignore_case"]:
        opts["ignore_case"] = True
    opts["pattern"] = re.escape(q["pattern"]) if q["fixed"] else q["pattern"]
    return {"input_files": q["files"],
            "application": "distributed_grep_tpu.apps.grep_tpu",
            "app_options": opts, "n_reduce": n_reduce}


def warm_queries(cfg: dict, traffic: dict, seed: int,
                 files: list[Path]) -> list[dict]:
    """The warm-up.  With ``fill_pattern``, that literal over each file
    set the window's queries name, first: each set is then read and in
    the device corpus cache under the key the window looks it up by (the
    first scan of a file, before its shard index exists to prune it).
    Then pool pattern i over ``warm_file(i)``, which builds its programs
    and leaves no result that a window query could find cached."""
    fill = traffic["warmup"].get("fill_pattern")
    out = []
    if fill:
        sets = {tuple(fs): None for c in range(traffic["clients"])
                for fs in _file_sets(traffic, files, c)}
        out += [{"family": "fill", "pattern": fill, "required": [fill],
                 "fixed": True, "ignore_case": False, "files": list(fs)}
                for fs in sets]
    out += [dict(p, files=[warm_file(traffic, files, i)])
            for i, p in enumerate(_flat_pool(cfg, traffic))]
    return [dict(q, client=traffic["clients"], k=i, seed=seed)
            for i, q in enumerate(out)]
