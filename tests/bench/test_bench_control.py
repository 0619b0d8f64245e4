"""The comparison that decides ``correct``, shown to fail: the control
(the reference with its confirmation skipped, check.py) in the program's
place on every cell, and faults planted in the program underneath a
whole run.  Tiny sizes on
the CPU; the chip readings at the cells' own sizes are in PERF.md.

    python3 -m pytest tests/bench/test_bench_control.py -q
"""

from __future__ import annotations

import pytest

import tiny

CELLS = ["subtitles.sparse", "subtitles.dense"]


@pytest.mark.parametrize("control", [0, 1])
@pytest.mark.parametrize("cell", CELLS)
def test_control_fails_where_the_program_passes(tmp_path, cell, control):
    p = tiny.run(tiny.checkout(tmp_path), cell, 2**31 + 101, 3,
                 control=control)
    assert p.returncode == 0, p.stderr[-3000:]
    res = tiny.result(p)
    assert res["correct"] is (not control)
    assert (res["compared"]["mismatched_records"]["value"] > 0) is bool(control)
    assert list(res)[-1] == "compared"
    assert p.stderr.strip().splitlines()[-1].startswith("compared ")


@pytest.mark.parametrize("fault", ["altered", "half"])
@pytest.mark.parametrize("cell", CELLS)
def test_a_broken_timed_path_is_not_correct(tmp_path, cell, fault):
    p = tiny.run(tiny.checkout(tmp_path), cell, 2**31 + 103, 3, fault=fault)
    assert p.returncode == 0, p.stderr[-3000:]
    res = tiny.result(p)
    assert res["correct"] is False
    assert res["compared"]["mismatched_records"]["value"] > 0
