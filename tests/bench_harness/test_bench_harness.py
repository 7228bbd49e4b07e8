"""The benchmark's harness: it refuses without a chip, finds cells and
metrics by name, and decides `correct` by the plain reference: true on
sound runs, false on each fault the sweep cells can have."""
import dataclasses
import json
import os
import shutil
import subprocess
import sys

import pytest
from _bench_helpers import REPO, run_cell, small_root  # noqa: F401

from bench import harness, system

FIELDS = ("correct", "attempted", "failed", "metrics", "device", "check")


def _refused(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "ddr3-1333-1ch1r.fig_closed", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=120)
    return p.returncode != 0 and p.stdout.strip() == ""


def test_cpu_only_exits_nonzero_without_result():
    assert _refused(REPO)


def test_benchmark_files_alone_exit_nonzero_without_result(tmp_path):
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        for p in json.load(f)["paths"]:
            shutil.copytree(os.path.join(REPO, p), tmp_path / p,
                            ignore=shutil.ignore_patterns("__pycache__"))
    assert _refused(tmp_path)


@pytest.mark.parametrize("name", ["ddr3-1333-1ch1r.fig_closed",
                                  "ddr3-1333-2ch2r.dse_closed",
                                  "ddr3-1333-1ch1r.open_grid"])
def test_every_cell_loads_with_all_its_metrics(name):
    cell = harness.load_cell(name)
    assert {m["name"] for m in cell.end_to_end} == {"sim_ticks_per_s",
                                                     "setup_s"}
    assert {m["name"] for m in cell.per_layer} == {
        "host_ms_per_sweep", "tick_loop_us_per_tick", "tick_loop_roofline",
        "device_idle_share"}
    for m in cell.per_layer:
        assert callable(harness.metric_reader(m["name"]))


def test_new_cell_and_metric_files_need_no_code_edit(small_root, capsys):
    """A traffic file and a metric reader written beside the others, and
    named in the manifest, are found and reported."""
    with open(small_root / "bench" / "metrics" / "max_cell_ticks.py",
              "w") as f:
        f.write("def read(ctx):\n    return float(ctx.max_cell_ticks)\n")
    with open(small_root / "bench" / "workloads" / "tiny_closed.json") as f:
        mix = json.load(f)
    mix["policies"] = ["dsarp", "ideal"]
    with open(small_root / "bench" / "workloads" / "tiny_new.json",
              "w") as f:
        json.dump(mix, f)
    manifest_path = small_root / "BENCHMARK.json"
    with open(manifest_path) as f:
        manifest = json.load(f)
    saved = json.dumps(manifest)
    name = "ddr3-1333-1ch1r.tiny_new"
    manifest["workloads"].append(dict(name=name, config="ddr3-1333-1ch1r",
                                      traffic="tiny_new", chips=1, why="t"))
    manifest["per_layer"].append(dict(
        name="max_cell_ticks", unit="ticks", better="lower",
        source="program_counter", layer="tick loop", moves="sim_ticks_per_s",
        workloads=[name]))
    with open(manifest_path, "w") as f:
        json.dump(manifest, f)
    try:
        rc, res = run_cell(small_root, name, capsys, trace=1)
    finally:
        with open(manifest_path, "w") as f:
            f.write(saved)
    assert rc == 0 and res["correct"] is True
    assert res["metrics"]["max_cell_ticks"]["value"] > 0
    assert res["attempted"] == 2 * 2 * 2


@pytest.mark.parametrize("traffic", ["tiny_closed", "tiny_open",
                                     "tiny_multirank"])
def test_sound_run_is_correct(small_root, capsys, traffic):
    config = "ddr3-1333-2ch2r" if traffic == "tiny_multirank" \
        else "ddr3-1333-1ch1r"
    rc, res = run_cell(small_root, f"{config}.{traffic}", capsys,
                       seed=2 ** 31 + 9)
    assert rc == 0
    assert list(res) == list(FIELDS)
    assert res["correct"] is True and res["failed"] == 0
    assert res["check"] == {"mismatched_cells": {"value": 0, "limit": 0}}
    assert set(res["metrics"]) == {"sim_ticks_per_s", "setup_s"}
    assert res["metrics"]["sim_ticks_per_s"]["unit"] == "cell-ticks/s"


def test_traced_run_is_correct_and_has_breakdown(small_root, capsys):
    rc, res = run_cell(small_root, "ddr3-1333-1ch1r.tiny_closed", capsys,
                       trace=1)
    assert rc == 0 and res["correct"] is True
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
    assert res["device"]["window_s"] > 0
    # no device planes on the CPU: the readers find nothing to read
    assert res["metrics"] == {}


# ---------------------------------------------------------------- faults
def _unchanged_state(spec):
    """The tick loop's step returns its state unchanged: the loop stops
    where it started."""
    return system.device_sweep(dataclasses.replace(spec, horizon=1))


def _half_left_out(spec):
    """Half of the grid is left out, and its places are filled with the
    results of the half that ran."""
    half = dataclasses.replace(
        spec, policies=spec.policies[:len(spec.policies) // 2])
    cells = system.device_sweep(half)
    return cells + cells[:len(spec.cells()) - len(cells)]


def _answer_altered(spec):
    """One answer altered where it is produced."""
    cells = system.device_sweep(spec)
    cells[len(cells) // 2] = dataclasses.replace(
        cells[len(cells) // 2], row_hits=cells[len(cells) // 2].row_hits + 1)
    return cells


@pytest.mark.parametrize("fault", [_unchanged_state, _half_left_out,
                                   _answer_altered],
                         ids=["unchanged_state", "half_left_out",
                              "answer_altered"])
def test_fault_is_not_correct(small_root, capsys, fault):
    rc, res = run_cell(small_root, "ddr3-1333-1ch1r.tiny_closed", capsys,
                       sweep=fault)
    assert rc == 0
    assert res["correct"] is False
    assert res["check"]["mismatched_cells"]["value"] > 0
