"""Mean wait of a task for a worker slot (runnable to assigned): the
summed ``map:wait`` and ``reduce:wait`` span seconds of the jobs completed
in the window over their count."""

from __future__ import annotations


def read(ctx: dict) -> float | None:
    seconds = count = 0
    for j in ctx["jobs"]:
        stages = j["explain"].get("stages", {})
        for name in ("map:wait", "reduce:wait"):
            row = stages.get(name)
            if row:
                seconds += row["seconds"]
                count += row["count"]
    return seconds / count if count else None
