"""The DDR5 configuration with same-bank refresh and bank groups, and
its plain reference (`bench/reference_bg.py`): without bank groups the
reference gives `bench/reference.py`'s answers; a small copy of the
DDR5 cell reads `correct` true through the harness, and false on an
altered answer and under the int16 control."""
import contextlib
import dataclasses
import json
import os

import numpy as np
import pytest
from _bench_helpers import (REPO, SMALL, run_cell, small_mix,  # noqa: F401
                            small_root, stand_in)

from bench import control, harness, system
from bench.traffic import build

DDR5 = "ddr5-4800-2ch2r"
#: a small copy of the DDR5 cell: two 16-core mixes, 60 requests a core,
#: 16 cells, all of them checked
TINY_SB = dict(reqs=960, policies=["ref_ab", "ref_pb", "dsarp", "ideal"],
               densities=[8, 32], check_cells=16)


def _json(*path):
    with open(os.path.join(REPO, *path)) as f:
        return json.load(f)


@contextlib.contextmanager
def _ddr5_cell(root):
    """Cell `ddr5-4800-2ch2r.tiny_sb` of `root`'s manifest: the DDR5
    configuration under a small copy of its traffic."""
    with open(root / "bench" / "workloads" / "tiny_sb.json", "w") as f:
        json.dump(small_mix("sb_closed", TINY_SB, None), f)
    path = root / "BENCHMARK.json"
    saved = path.read_text()
    manifest = json.loads(saved)
    manifest["workloads"].append(dict(
        name=f"{DDR5}.tiny_sb", config=DDR5, traffic="tiny_sb", chips=1,
        why="t"))
    path.write_text(json.dumps(manifest))
    try:
        yield f"{DDR5}.tiny_sb"
    finally:
        path.write_text(saved)


@pytest.mark.parametrize("traffic", sorted(SMALL))
def test_reference_bg_without_groups_is_reference(traffic):
    config, base, overrides, scn = SMALL[traffic]
    cfg = _json("bench", "configs", f"{config}.json")
    plain = harness.load_reference(cfg)
    grouped = harness.load_reference(dict(cfg,
                                          reference="bench/reference_bg.py"))
    mix = build(small_mix(base, overrides, scn), cfg, 2 ** 31 + 11, plain)
    cells = mix.cells()
    assert grouped.simulate(mix, cfg, cells) == plain.simulate(mix, cfg,
                                                               cells)


@pytest.mark.parametrize("n_bank_groups", [2, 4, 8])
def test_reference_bg_agrees_with_the_device_path(n_bank_groups):
    """The DDR5 file with 2, 4 or 8 groups of its 32 banks a rank: the
    program's device path and the reference, every cell, bit for bit."""
    cfg = _json("bench", "configs", f"{DDR5}.json")
    cfg["layout"]["n_bank_groups"] = n_bank_groups
    mix = dict(small_mix("sb_closed", dict(TINY_SB, reqs=640), None),
               policies=["ref_ab", "ref_pb", "darp", "sarp_pb", "dsarp",
                         "ideal"], densities=[32])
    reference = harness.load_reference(cfg)
    traffic = build(mix, cfg, 2 ** 31 + 17, reference)
    cells = system.device_sweep(system.make_spec(traffic, cfg))
    assert all(c.refreshes_pb > 0 for c in cells if c.policy == "ref_pb")
    ref = reference.simulate(traffic, cfg, traffic.cells())
    assert harness.mismatched([cells], list(range(len(cells))), ref,
                              reference.FIELDS) == 0


def test_new_cells_load_with_all_their_metrics():
    for name, ref in ((f"{DDR5}.sb_closed", "reference_bg.py"),
                      ("ddr3-1333-2ch2r.fig_closed", "reference.py")):
        cell = harness.load_cell(name)
        assert os.path.basename(cell.reference.__file__) == ref
        assert {m["name"] for m in cell.end_to_end} == {"sim_ticks_per_s",
                                                         "setup_s"}
        assert {m["name"] for m in cell.per_layer} == {
            "host_ms_per_sweep", "tick_loop_us_per_tick",
            "tick_loop_roofline", "device_idle_share"}


def test_ddr5_file_reaches_the_program_whole():
    """The file's layout and timing: 128 banks in 16 same-bank sets a
    cell, and the quantized DDR5 timing at 32 Gb."""
    from repro.core.sweep import TickTiming

    cfg = _json("bench", "configs", f"{DDR5}.json")
    mix = build(dict(_json("bench", "workloads", "sb_closed.json"),
                     reqs=160), cfg, 1, harness.load_reference(cfg))
    spec = system.make_spec(mix, cfg)
    assert (spec.n_banks_total, spec.n_bank_groups) == (128, 8)
    assert len(spec.cells()) == 6 * 6 * 3
    tk = TickTiming.from_timing(spec.timing[32], spec.dt_ns)
    assert (tk.REFI, tk.U, tk.REFI_SB, tk.RFC_PB, tk.RFC_AB, tk.HIT,
            tk.MISS, tk.CCDL) == (585, 16, 36, 57, 66, 6, 16, 1)


def test_ddr5_small_cell_is_correct(small_root, capsys):
    with _ddr5_cell(small_root) as name:
        rc, res = run_cell(small_root, name, capsys, seed=2 ** 31 + 3)
    assert rc == 0
    assert res["correct"] is True and res["failed"] == 0
    assert res["check"] == {"mismatched_cells": {"value": 0, "limit": 0}}
    assert set(res["metrics"]) == {"sim_ticks_per_s", "setup_s"}


def _answer_altered(spec):
    cells = system.device_sweep(spec)
    g = len(cells) // 2
    cells[g] = dataclasses.replace(cells[g], energy=cells[g].energy + 1.0)
    return cells


def test_ddr5_altered_answer_is_not_correct(small_root, capsys):
    with _ddr5_cell(small_root) as name:
        rc, res = run_cell(small_root, name, capsys, sweep=_answer_altered)
    assert rc == 0 and res["correct"] is False
    assert res["check"]["mismatched_cells"]["value"] > 0


def test_ddr5_int16_control_is_wrong(small_root):
    with _ddr5_cell(small_root) as name:
        cell = harness.load_cell(name, str(small_root))
        row = control.readings(cell, 5, stand_in())
    assert row["cells"] == TINY_SB["check_cells"]
    assert row["program"] == 0
    assert row["control"] > 0
    assert np.int16 is control.CONTROL_ITYPE
