"""The comparison that decides ``correct``, and its control.

The plain reference is GNU grep (``LC_ALL=C grep -anH``), which shares
no code with the program.  Both sides are brought to ``path:N:line``
records and compared as multisets: the number compared is the count of
records that one side has and the other lacks, and its limit is 0.

The served output writes ``path (line number #N)<TAB>line`` and decodes
line bytes as UTF-8 with invalid bytes replaced; the reference's records
get the same decode.  (Normalisation copied from the repository's
``chip_smoke.py``: ``_SED_SERVED`` and ``_LOSSY``.)

The control is the reference with one stated guarantee broken: it
reports every line that a candidate filter passes, the first two bytes
of each required literal, and skips the confirmation that the match is
really there.  ``run.py --control 1`` compares its records in the
program's place, so that the run's ``correct`` reads false
(``tests/bench/test_bench_control.py``).
"""

from __future__ import annotations

import os
import re
import subprocess
import tempfile
from collections import Counter
from concurrent.futures import ThreadPoolExecutor

from client import read_outputs

_SERVED = re.compile(rb"^(.*?) \(line number #(\d+)\)\t(.*)$", re.S)


def served_records(outputs: list[str]) -> list[bytes]:
    out = []
    for p in outputs:
        with open(p, "rb") as f:
            for ln in f.read().split(b"\n"):
                if not ln:
                    continue
                m = _SERVED.match(ln)
                if m is None:
                    out.append(b"<unparsed>" + ln)
                else:
                    out.append(m.group(1) + b":" + m.group(2) + b":"
                               + m.group(3))
    return out


def _grep(args: list[str], files: list[str]) -> list[bytes]:
    r = subprocess.run(["grep", "-anH", *args, "--", *files],
                       capture_output=True, env={**os.environ, "LC_ALL": "C"},
                       timeout=600)
    if r.returncode > 1:
        raise RuntimeError(f"grep {args[:4]} failed: {r.stderr[:300]!r}")
    return [ln.decode("utf-8", "replace").encode()
            for ln in r.stdout.split(b"\n") if ln]


def _pattern_args(q: dict) -> list[str]:
    args = ["-i"] if q["ignore_case"] else []
    return args + (["-F"] if q["fixed"] else ["-E"]) + ["-e", q["pattern"]]


def _control_args(q: dict, tmp: str) -> list[str]:
    """The candidate filter: each required literal's first two bytes,
    with no confirmation."""
    prefixes = sorted({r.encode()[:2] for r in q["required"]})
    path = os.path.join(tmp, f"control-{q['client']}-{q['k']}.pat")
    with open(path, "wb") as f:
        f.write(b"\n".join(prefixes) + b"\n")
    return (["-i"] if q["ignore_case"] else []) + ["-F", "-f", path]


def diff_count(got: list[bytes], want: list[bytes]) -> int:
    a, b = Counter(got), Counter(want)
    return sum(((a - b) + (b - a)).values())


def compare(sample: list[tuple[dict, dict]], control: bool = False,
            workers: int = 8) -> dict:
    """Compare each (query, record) of the sample with the reference.
    Returns the numbers compared: mismatched records, and outputs whose
    bytes differ from what the client read.  With ``control`` the
    control's records stand in the program's place."""
    with tempfile.TemporaryDirectory(dir=os.environ.get("TMPDIR")) as tmp:

        def one(item):
            q, rec = item
            want = _grep(_pattern_args(q), q["files"])
            got = (_grep(_control_args(q, tmp), q["files"]) if control
                   else served_records(rec["outputs"]))
            _, digest = read_outputs(rec["outputs"])
            return {"family": q["family"], "job_id": rec["job_id"],
                    "records": len(want), "mismatched": diff_count(got, want),
                    "changed_after_read": int(digest != rec["digest"])}

        with ThreadPoolExecutor(workers) as ex:
            rows = list(ex.map(one, sample))
    return {"rows": rows,
            "mismatched_records": sum(r["mismatched"] for r in rows),
            "outputs_changed_after_read": sum(r["changed_after_read"]
                                              for r in rows)}
