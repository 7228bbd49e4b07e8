"""The benchmark's harness: it refuses without a chip, finds cells and
metrics by name, and decides `correct` by the plain reference: true on
sound runs, false on each fault the sweep cells can have."""
import contextlib
import dataclasses
import json
import os
import shutil
import subprocess
import sys

import pytest
from _bench_helpers import REPO, run_cell, small_root  # noqa: F401

from bench import harness, system
from bench.traffic import build

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


# ------------------------------------------------- configuration files
def _config(**timing_ns):
    """`ddr3-1333-1ch1r`'s file, renamed, with `timing_ns` keys replaced."""
    with open(os.path.join(REPO, "bench", "configs",
                           "ddr3-1333-1ch1r.json")) as f:
        config = json.load(f)
    config["name"] = "ddr3-test"
    config["timing_ns"].update(timing_ns)
    return config


#: DDR4 fine-granularity refresh at 2x: half the refresh interval, shorter
#: refreshes, where the program's own table has DDR3-1333's
FGR_2X = dict(tREFI=3906.25, tRFC_ab_pb={"8": [260.0, 110.0],
                                         "16": [530.0, 230.0],
                                         "32": [550.0, 235.0]})


@contextlib.contextmanager
def _cell_on(root, config):
    """Cell `ddr3-test.tiny_closed` of `root`'s manifest, on `config`
    written as a configuration file beside the others."""
    path = root / "bench" / "configs" / "ddr3-test.json"
    with open(path, "w") as f:
        json.dump(config, f)
    manifest_path = root / "BENCHMARK.json"
    with open(manifest_path) as f:
        saved = f.read()
    manifest = json.loads(saved)
    manifest["configs"].append(dict(
        manifest["configs"][0], name="ddr3-test",
        file="bench/configs/ddr3-test.json"))
    manifest["workloads"].append(dict(
        name="ddr3-test.tiny_closed", config="ddr3-test",
        traffic="tiny_closed", chips=1, why="t"))
    with open(manifest_path, "w") as f:
        json.dump(manifest, f)
    try:
        yield "ddr3-test.tiny_closed"
    finally:
        with open(manifest_path, "w") as f:
            f.write(saved)
        os.remove(path)


def test_other_timing_reaches_the_program_or_is_refused(small_root, capsys):
    """A configuration whose timing is not the program's own table is
    simulated by both sides where `SweepSpec` takes a timing; where it
    takes none, the run is refused at set-up with no result line, never
    checked against a DRAM the program did not simulate."""
    with _cell_on(small_root, _config(**FGR_2X)) as name:
        if system.takes_timing():
            rc, res = run_cell(small_root, name, capsys)
            assert rc == 0 and res["correct"] is True
            assert res["check"]["mismatched_cells"]["value"] == 0
        else:
            with pytest.raises(ValueError, match="program's own table"):
                run_cell(small_root, name, capsys)
            assert capsys.readouterr().out == ""


def test_make_spec_hands_the_file_timing_to_a_spec_that_takes_it(
        monkeypatch):
    @dataclasses.dataclass(frozen=True)
    class TimedSpec(system.SweepSpec):
        timing: object = None

    monkeypatch.setattr(system, "SweepSpec", TimedSpec)
    config = _config(**FGR_2X)
    with open(os.path.join(REPO, "bench", "workloads",
                           "fig_closed.json")) as f:
        mix = dict(json.load(f), reqs=40)
    spec = system.make_spec(
        build(mix, config, 1, harness.load_reference(config)), config)
    assert sorted(spec.timing) == [8, 16, 32]
    for d, T in spec.timing.items():
        assert T == system.dram_timing(config, d)
        assert T.tREFI == 3906.25 and T.n_subarrays == 8
        assert [T.tRFC_ab, T.tRFC_pb] == FGR_2X["tRFC_ab_pb"][str(d)]


@pytest.mark.parametrize("where,key", [("timing_ns", "tCCD_L"),
                                       ("layout", "n_bank_groups")],
                         ids=["timing", "layout"])
def test_unknown_config_key_fails_at_setup(small_root, capsys, where, key):
    config = _config()
    config[where][key] = 4
    with _cell_on(small_root, config) as name:
        with pytest.raises(TypeError, match=key):
            run_cell(small_root, name, capsys)
    assert capsys.readouterr().out == ""


def test_named_reference_decides_correct(small_root, capsys):
    """A stand-in reference, named by the configuration, that alters one
    field of one cell: the run reads `correct` false."""
    stand_in = small_root / "bench" / "reference_off_by_one.py"
    stand_in.write_text(
        "import importlib.util, os\n"
        "_s = importlib.util.spec_from_file_location('_plain', os.path.join("
        "os.path.dirname(__file__), 'reference.py'))\n"
        "_plain = importlib.util.module_from_spec(_s)\n"
        "_s.loader.exec_module(_plain)\n"
        "FIELDS = _plain.FIELDS\n\n"
        "def simulate(traffic, config, cells, itype=_plain.np.int32, "
        "record=False):\n"
        "    out = _plain.simulate(traffic, config, cells, itype, record)\n"
        "    if not record:\n"
        "        out[0]['row_hits'] += 1\n"
        "    return out\n")
    config = dict(_config(), reference="bench/reference_off_by_one.py")
    try:
        with _cell_on(small_root, config) as name:
            rc, res = run_cell(small_root, name, capsys)
    finally:
        os.remove(stand_in)
    assert rc == 0 and res["correct"] is False
    # the altered cell, in every sweep of the 16-cell grid
    assert res["check"]["mismatched_cells"]["value"] == res["attempted"] // 16


@pytest.mark.parametrize("reference", [None, "src/repro/__init__.py",
                                       "bench/../../reference.py"],
                         ids=["absent", "outside_bench", "leaves_root"])
def test_config_without_a_bench_reference_is_refused(small_root,
                                                     reference):
    config = _config()
    del config["reference"]
    if reference:
        config["reference"] = reference
    with _cell_on(small_root, config) as name:
        with pytest.raises(ValueError, match="reference"):
            harness.load_cell(name, str(small_root))


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
