"""Summed ``engine:stitch`` span seconds of the jobs completed in the
window, per GB of corpus those jobs covered: stripe and segment boundary
re-scans, the EOL fix-up and the final sort."""

from __future__ import annotations

from metrics._stages import seconds_per_gb


def read(ctx: dict) -> float | None:
    return seconds_per_gb(ctx, "engine:stitch")
