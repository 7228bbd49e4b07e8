"""The control of `mismatched_cells`: the configuration's reference in the
program's place with its state in int16 reads above the limit, where the
program reads 0. The same readings at the cells' own sizes come from
`bench/control.py` on the chip (PERF.md)."""
import os

import pytest
from _bench_helpers import small_root, stand_in  # noqa: F401

from bench import control, harness


@pytest.mark.parametrize("name,seed", [
    ("ddr3-1333-1ch1r.tiny_closed", 1), ("ddr3-1333-1ch1r.tiny_open", 2),
    ("ddr3-1333-2ch2r.tiny_multirank", 3)])
def test_control_fails_where_program_passes(small_root, name, seed):
    cell = harness.load_cell(name, str(small_root))
    assert cell.reference.__file__ == os.path.join(
        str(small_root), cell.config["reference"])
    # long enough that int16 latency sums wrap, as at the cells' sizes
    cell.mix["reqs"] = 2400 if cell.mix["mode"] == "closed" else 1600
    r = control.readings(cell, seed, stand_in())
    assert r["program"] == 0
    assert r["control"] > 0
