"""Faults planted in the program underneath a harness run, each one a way
the timed path could go wrong that ``correct`` has to catch:

- ``altered``: one answer changed where it is produced (the first matched
  line of each scan, or of each streamed chunk, reported as its
  neighbour);
- ``half``: half of the matched lines of each scan or chunk left out.

They are planted where matched lines become records: the whole-bytes
record build (``apps/grep_tpu._records_for_inner``) and the streamed
chunks of ``GrepEngine.scan_file``.  The harness must print
``correct: false`` for each (test_bench_control.py).
"""

from __future__ import annotations

import dataclasses


def _break(lines, fault: str):
    lines = lines.copy()
    if fault == "altered" and lines.size:
        lines[0] = lines[0] - 1 if lines[0] > 1 else lines[0] + 1
    elif fault == "half":
        lines = lines[::2]
    return lines


def plant(fault: str) -> None:
    if not fault:
        return
    if fault not in ("altered", "half"):
        raise ValueError(f"unknown fault {fault!r}")
    from distributed_grep_tpu.apps import grep_tpu
    from distributed_grep_tpu.ops.engine import GrepEngine

    inner = grep_tpu._records_for_inner

    def records(filename, contents, result, o, nl=None):
        res = dataclasses.replace(
            result, matched_lines=_break(result.matched_lines, fault))
        return inner(filename, contents, res, o, nl=nl)

    scan_file = GrepEngine.scan_file

    def streamed(self, path, *args, emit_chunk=None, **kw):
        if emit_chunk is not None:
            sink = emit_chunk

            def emit_chunk(lines_before, buf, mlines, nl_idx):
                sink(lines_before, buf, _break(mlines, fault), nl_idx)

        return scan_file(self, path, *args, emit_chunk=emit_chunk, **kw)

    grep_tpu._records_for_inner = records
    GrepEngine.scan_file = streamed
