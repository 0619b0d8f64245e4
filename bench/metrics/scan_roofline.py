"""The scan's share of its roofline, in %: the least time the chip could
take to read the bytes that the window's jobs scanned on the device
(``scan:<family>`` spans, ``kernel_bytes.pallas`` and ``.xla``), at the
peak HBM bandwidth of ``peaks.json``, over the device's busy seconds in
the traced window.  Every such job ran inside the window, so its device
work is inside the busy time; busy time also holds other work, so the
share is a lower bound of the kernels' own."""

from __future__ import annotations


def read(ctx: dict) -> float | None:
    tr = ctx.get("trace")
    if not tr or tr["busy_s"] <= 0:
        return None
    n = 0
    for j in ctx["jobs"]:
        modes = j["explain"].get("routing", {}).get("engine_modes", {})
        for name, mode in modes.items():
            if name.startswith("batch"):
                continue  # an envelope of the inner engine's own scan row
            kb = mode.get("kernel_bytes", {})
            n += kb.get("pallas", 0) + kb.get("xla", 0)
    if not n:
        return None
    return 100.0 * (n / ctx["peaks"]["hbm_bytes_per_s"]) / tr["busy_s"]
