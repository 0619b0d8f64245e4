"""Shared arithmetic of the span readers: a stage's summed span seconds
over the window's jobs, per GB of corpus those jobs covered."""

from __future__ import annotations


def seconds_per_gb(ctx: dict, stage: str) -> float | None:
    jobs = ctx["jobs"]
    gb = sum(j["bytes"] for j in jobs) / 1e9
    rows = [j["explain"].get("stages", {}).get(stage) for j in jobs]
    if not gb or not any(rows):
        return None
    return sum(r["seconds"] for r in rows if r) / gb
