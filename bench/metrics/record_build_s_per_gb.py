"""Summed ``shuffle:build`` span seconds of the jobs completed in the
window, per GB of corpus those jobs covered: the map's record build
(bucketize, where the deferred native build runs)."""

from __future__ import annotations

from metrics._stages import seconds_per_gb


def read(ctx: dict) -> float | None:
    return seconds_per_gb(ctx, "shuffle:build")
