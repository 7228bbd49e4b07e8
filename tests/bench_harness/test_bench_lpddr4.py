"""The LPDDR4 configuration with controller-addressed per-bank refresh
under tpbR2pbR and refresh rounds, and its plain reference
(`bench/reference_lp.py`): without the rules the reference gives
`bench/reference_bg.py`'s answers; with them it agrees with the device
path; the file reaches the program whole; a small copy of the LPDDR4
cell reads `correct` true through the harness, and false on an altered
answer and under the int16 control. Grids without the rules trace none
of them."""
import contextlib
import dataclasses
import json
import os

import numpy as np
import pytest
from _bench_helpers import (REPO, run_cell, small_mix,  # noqa: F401
                            small_root, stand_in)

from bench import control, harness, system
from bench.traffic import build

LP = "lpddr4-3200-4ch2r"
#: a small copy of the LPDDR4 cell: two 8-core mixes, 60 requests a
#: core, 16 cells, all of them checked
TINY_PB = dict(reqs=480, policies=["ref_ab", "ref_pb", "darp", "ideal"],
               densities=[8, 32], check_cells=16)
#: every built-in per-bank-level policy the benchmark's grids name
PB_POLICIES = ["ref_pb", "darp", "sarp_pb", "dsarp", "elastic", "hira"]


def _json(*path):
    with open(os.path.join(REPO, *path)) as f:
        return json.load(f)


def _config(**rules):
    """The LPDDR4 file, its rules' values set from `rules`."""
    cfg = _json("bench", "configs", f"{LP}.json")
    cfg["timing_ns"].update(rules)
    return cfg


def _small(mix_overrides, cfg, seed):
    mix = small_mix("pb_closed", dict(TINY_PB, **mix_overrides), None)
    return build(mix, cfg, seed, harness.load_reference(cfg))


@contextlib.contextmanager
def _lp_cell(root):
    """Cell `lpddr4-3200-4ch2r.tiny_pb` of `root`'s manifest: the LPDDR4
    configuration under a small copy of its traffic."""
    with open(root / "bench" / "workloads" / "tiny_pb.json", "w") as f:
        json.dump(small_mix("pb_closed", TINY_PB, None), f)
    path = root / "BENCHMARK.json"
    saved = path.read_text()
    manifest = json.loads(saved)
    manifest["workloads"].append(dict(
        name=f"{LP}.tiny_pb", config=LP, traffic="tiny_pb", chips=1,
        why="t"))
    path.write_text(json.dumps(manifest))
    try:
        yield f"{LP}.tiny_pb"
    finally:
        path.write_text(saved)


@pytest.mark.parametrize("config", ["ddr3-1333-2ch2r", "ddr5-4800-2ch2r",
                                    LP])
def test_reference_lp_without_the_rules_is_reference_bg(config):
    """A file without the rules (the LPDDR4 file with both off): the
    reference gives `reference_bg.py`'s answers, every cell."""
    cfg = _json("bench", "configs", f"{config}.json")
    if config == LP:
        cfg["timing_ns"].update(tpbR2pbR=0.0, refpb_rounds=False)
    cfg = dict(cfg, reference="bench/reference_bg.py")
    mix = dict(small_mix("pb_closed", dict(TINY_PB, reqs=320), None),
               policies=["ref_ab"] + PB_POLICIES, densities=[32])
    grouped = harness.load_reference(cfg)
    lp = harness.load_reference(dict(cfg, reference="bench/reference_lp.py"))
    traffic = build(mix, cfg, 2 ** 31 + 5, grouped)
    cells = traffic.cells()
    assert lp.simulate(traffic, cfg, cells) == grouped.simulate(
        traffic, cfg, cells)


@pytest.mark.parametrize("rules", [
    dict(tpbR2pbR=90.0, refpb_rounds=True),
    dict(tpbR2pbR=90.0, refpb_rounds=False),
    dict(tpbR2pbR=0.0, refpb_rounds=True)], ids=["both", "spacing",
                                                 "rounds"])
def test_reference_lp_agrees_with_the_device_path(rules):
    """The LPDDR4 file with both rules, the spacing alone and the rounds
    alone: the program's device path and the reference, every cell of
    every built-in per-bank policy, bit for bit."""
    cfg = _config(**rules)
    reference = harness.load_reference(cfg)
    traffic = build(dict(small_mix("pb_closed", dict(TINY_PB, reqs=640),
                                   None),
                         policies=["ref_ab"] + PB_POLICIES + ["ideal"],
                         densities=[32]), cfg, 2 ** 31 + 17, reference)
    cells = system.device_sweep(system.make_spec(traffic, cfg))
    assert all(c.refreshes_pb > 0 for c in cells if c.policy == "ref_pb")
    ref = reference.simulate(traffic, cfg, traffic.cells())
    assert harness.mismatched([cells], list(range(len(cells))), ref,
                              reference.FIELDS) == 0


def test_the_rules_move_darp():
    """On the LPDDR4 file the rules decide: DARP's refreshes or its
    slowest core differ with them from without them, in some cell."""
    on, off = _config(), _config(tpbR2pbR=0.0, refpb_rounds=False)
    traffic = _small(dict(policies=["darp"], densities=[8, 32]), on, 9)

    def run(cfg):
        return system.device_sweep(system.make_spec(traffic, cfg))

    assert any((a.refreshes_pb, a.makespan) != (b.refreshes_pb, b.makespan)
               for a, b in zip(run(on), run(off)))


def test_new_cells_load_with_all_their_metrics():
    for name, ref in ((f"{LP}.pb_closed", "reference_lp.py"),
                      ("ddr3-1333-2ch2r.subarray_storm", "reference.py")):
        cell = harness.load_cell(name)
        assert os.path.basename(cell.reference.__file__) == ref
        assert {m["name"] for m in cell.end_to_end} == {"sim_ticks_per_s",
                                                         "setup_s"}
        assert {m["name"] for m in cell.per_layer} == {
            "host_ms_per_sweep", "tick_loop_us_per_tick",
            "tick_loop_roofline", "device_idle_share"}
        assert cell.workload["chips"] == 1


def test_subarray_storm_grid_is_the_issue_grid():
    cell = harness.load_cell("ddr3-1333-2ch2r.subarray_storm")
    traffic = build(dict(cell.mix, reqs=160), cell.config, 1,
                    cell.reference)
    assert len(traffic.cells()) == 8 * 6 * 3 and traffic.check_cells == 48
    assert cell.config["layout"]["n_subarrays"] == 8
    scn = traffic.scenarios[0]
    assert scn.params == dict(n_cores=8, mlp=4, think_ns=8.0,
                              row_hit_rate=0.05, write_ratio=0.2)


def test_lpddr4_file_reaches_the_program_whole():
    """The file's layout and timing: 64 banks, one unit each, and the
    quantized LPDDR4 timing and issue rules at 32 Gb."""
    from repro.core.sweep import TickTiming

    cfg = _json("bench", "configs", f"{LP}.json")
    mix = build(dict(_json("bench", "workloads", "pb_closed.json"),
                     reqs=160), cfg, 1, harness.load_reference(cfg))
    spec = system.make_spec(mix, cfg)
    assert (spec.n_banks_total, spec.n_bank_groups) == (64, 1)
    assert len(spec.cells()) == 6 * 6 * 3
    tk = TickTiming.from_timing(spec.timing[32], spec.dt_ns)
    assert (tk.REFI, tk.U, tk.REFI_SB, tk.RFC_PB, tk.RFC_AB, tk.HIT,
            tk.MISS, tk.TRP, tk.PB2PB, tk.PB_ROUNDS) == (
        781, 64, 12, 38, 76, 5, 12, 4, 18, True)


def test_rules_are_traced_only_where_a_dram_states_them():
    """A small grid of each configuration: the loop carry holds the
    spacing plane `pb_next` and the lowered loop the rules' operations
    (in `tick.refresh`) on the LPDDR4 file alone."""
    from repro.core.sweep import jaxbody
    from repro.core.sweep.engine import _Grid, _jax_arbiter

    for name in [c["name"] for c in _json("BENCHMARK.json")["configs"]]:
        cfg = _json("bench", "configs", f"{name}.json")
        traffic = build(dict(small_mix("pb_closed", dict(TINY_PB, reqs=160),
                                       None), densities=[32]),
                        cfg, 1, harness.load_reference(cfg))
        cfg_, cst, s0 = jaxbody.program(_Grid(system.make_spec(traffic,
                                                                cfg)))
        text = jaxbody.run_loop.lower(cfg_, cst, _jax_arbiter("jnp"),
                                      s0).as_text(debug_info=True)
        # the rules' operations, by the named scope in their locations
        ops = [ln for ln in text.splitlines() if "/refpb_rules/" in ln]
        rules = name == LP
        assert ("pb_next" in s0) is rules and bool(ops) is rules, name
        assert all("/tick.refresh/refpb_rules/" in ln for ln in ops), name


def test_lpddr4_small_cell_is_correct(small_root, capsys):
    with _lp_cell(small_root) as name:
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


def test_lpddr4_altered_answer_is_not_correct(small_root, capsys):
    with _lp_cell(small_root) as name:
        rc, res = run_cell(small_root, name, capsys, sweep=_answer_altered)
    assert rc == 0 and res["correct"] is False
    assert res["check"]["mismatched_cells"]["value"] > 0


def test_lpddr4_int16_control_is_wrong(small_root):
    with _lp_cell(small_root) as name:
        cell = harness.load_cell(name, str(small_root))
        row = control.readings(cell, 5, stand_in())
    assert row["cells"] == TINY_PB["check_cells"]
    assert row["program"] == 0
    assert row["control"] > 0
    assert np.int16 is control.CONTROL_ITYPE
