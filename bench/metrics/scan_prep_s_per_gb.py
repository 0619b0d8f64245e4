"""Summed ``engine:prep`` span seconds of the jobs completed in the window,
per GB of corpus those jobs covered: the scan's host preparation
(lookups, reads, newline index, layout and upload)."""

from __future__ import annotations

from metrics._stages import seconds_per_gb


def read(ctx: dict) -> float | None:
    return seconds_per_gb(ctx, "engine:prep")
