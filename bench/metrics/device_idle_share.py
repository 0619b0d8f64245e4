"""100 x (1 - busy / window) of the traced window: busy is the union of
the device's op intervals (bench/devtrace.py), averaged over chips."""

from __future__ import annotations


def read(ctx: dict) -> float | None:
    tr = ctx.get("trace")
    if not tr or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
