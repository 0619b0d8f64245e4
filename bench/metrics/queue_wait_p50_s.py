"""Median of explain ``timing.queue_wait_s`` (submit to the job's start,
on the service's clock) over the jobs completed in the window."""

from __future__ import annotations

import statistics


def read(ctx: dict) -> float | None:
    waits = [j["explain"].get("timing", {}).get("queue_wait_s")
             for j in ctx["jobs"]]
    waits = [w for w in waits if w is not None]
    return statistics.median(waits) if waits else None
