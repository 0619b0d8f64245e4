"""Summed ``engine:confirm`` span seconds of the jobs completed in the
window, per GB of corpus those jobs covered: the host confirm of device
candidates."""

from __future__ import annotations

from metrics._stages import seconds_per_gb


def read(ctx: dict) -> float | None:
    return seconds_per_gb(ctx, "engine:confirm")
