"""The closed-loop client of the measured window (one process each, no
jax, no program import).

Each query makes the calls ``dgrep submit --wait`` makes, against the
daemon's HTTP API: ``POST /jobs``, ``GET /jobs/<id>`` polled at the
traffic's fixed ``poll_s``, ``GET /jobs/<id>/result``, and then every
output file is read to its last byte.  A query's wall runs from the POST
to that last byte.  The client stops sending at the window's end,
finishes the query in flight, and writes its records as JSON; the
harness fetches each job's explain report after the window.
"""

from __future__ import annotations

import hashlib
import json
import time
import urllib.request
from pathlib import Path

import gen

_TIMEOUT_S = 300.0
# The error of a query that got no answer by its deadline: it counts
# against ``correct``, not in ``failed``.
NO_ANSWER = "no answer by the deadline"


def call(addr: str, method: str, path: str, body: bytes | None = None):
    req = urllib.request.Request(f"http://{addr}{path}", data=body,
                                 method=method)
    if body is not None:
        req.add_header("Content-Type", "application/json")
    with urllib.request.urlopen(req, timeout=_TIMEOUT_S) as r:
        return json.loads(r.read())


def read_outputs(paths: list[str]) -> tuple[int, str]:
    """Read every output file in order; (bytes, blake2b hex digest)."""
    h = hashlib.blake2b(digest_size=16)
    n = 0
    for p in paths:
        with open(p, "rb") as f:
            while chunk := f.read(1 << 20):
                h.update(chunk)
                n += len(chunk)
    return n, h.hexdigest()


def one_query(addr: str, body: bytes, poll_s: float,
              deadline: float | None = None) -> dict:
    """One served query, timed from the POST to the last output byte; a
    query with no answer by ``deadline`` (epoch seconds) fails."""
    t0 = time.time()
    job = call(addr, "POST", "/jobs", body)["job_id"]
    deadline = deadline or t0 + _TIMEOUT_S
    while True:
        st = call(addr, "GET", f"/jobs/{job}")
        if st.get("state") in ("done", "failed", "cancelled"):
            break
        if time.time() > deadline:
            return {"job_id": job, "t_post": t0, "t_done": time.time(),
                    "ok": False, "error": NO_ANSWER}
        time.sleep(poll_s)
    if st["state"] != "done":
        return {"job_id": job, "t_post": t0, "t_done": time.time(),
                "ok": False, "error": f"{st['state']}: {st.get('error')}"}
    outputs = call(addr, "GET", f"/jobs/{job}/result")["outputs"]
    n, digest = read_outputs(outputs)
    return {"job_id": job, "t_post": t0, "t_done": time.time(), "ok": True,
            "outputs": outputs, "out_bytes": n, "digest": digest}


def client_main(spec: dict, ready, go) -> None:
    """Process entry: announce readiness on ``ready``, take the window's
    (start, end) epoch seconds from ``go``, run ``spec['queries']`` in a
    closed loop over it, then write the records to ``spec['out']``."""
    traffic = spec["traffic"]
    recs: list[dict] = []
    exhausted = True
    ready.put(True)
    t_start, t_end = go.get()
    time.sleep(max(0.0, t_start - time.time()))
    for q in spec["queries"]:
        if time.time() >= t_end:
            exhausted = False
            break
        body = json.dumps(gen.job_body(q, traffic["n_reduce"])).encode()
        try:
            rec = one_query(spec["addr"], body, traffic["poll_s"],
                            deadline=t_end + spec["grace_s"])
        except (OSError, ValueError, KeyError) as e:
            rec = {"t_post": None, "t_done": time.time(), "ok": False,
                   "error": f"{type(e).__name__}: {e}"}
        rec.update(k=q["k"], client=q["client"], family=q["family"])
        recs.append(rec)
    Path(spec["out"]).write_text(json.dumps(
        {"records": recs, "exhausted": exhausted}))
