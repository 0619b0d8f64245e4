"""Summed ``engine:decode`` span seconds of the jobs completed in the
window, per GB of corpus those jobs covered: sparse device words decoded
to candidate or matched lines."""

from __future__ import annotations

from metrics._stages import seconds_per_gb


def read(ctx: dict) -> float | None:
    return seconds_per_gb(ctx, "engine:decode")
