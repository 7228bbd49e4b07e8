"""Shared pieces of the benchmark's own tests: small cells a test run can
hold, stand-ins for the system under test, and one harness run.

There is no `conftest.py` here: test modules of the suite import
`conftest` by name, and a second module of that name would shadow it."""
import json
import os
import shutil
import sys
import time
from types import SimpleNamespace

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [os.path.join(REPO, "src"), REPO]

from bench import harness, system  # noqa: E402

#: small cells: the real configurations and traffic kinds, cut in size
SMALL = {
    "tiny_closed": ("ddr3-1333-1ch1r", "fig_closed", dict(
        reqs=400, policies=["ref_ab", "darp", "hira", "ideal"],
        densities=[8, 32], check_cells=16), 2),
    "tiny_open": ("ddr3-1333-1ch1r", "open_grid", dict(
        reqs=240, policies=["ref_ab", "dsarp", "elastic"],
        densities=[32], check_cells=9), [1, 5, 6]),
    "tiny_multirank": ("ddr3-1333-2ch2r", "dse_closed", dict(
        reqs=320, policies=["ref_ab", "dsarp"], densities=[16],
        check_cells=4), None),
}


@pytest.fixture(scope="session")
def small_root(tmp_path_factory):
    """A checkout root holding the benchmark's files and a manifest of the
    small cells."""
    return make_root(tmp_path_factory.mktemp("bench_root"))


def make_root(root):
    """Write into `root` the benchmark's files and a manifest of the small
    cells, named `<config>.<traffic>`."""
    shutil.copytree(os.path.join(REPO, "bench"), root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cells = []
    for traffic, (config, base, overrides, scn) in SMALL.items():
        with open(root / "bench" / "workloads" / f"{traffic}.json",
                  "w") as f:
            json.dump(small_mix(base, overrides, scn), f)
        cells.append(dict(name=f"{config}.{traffic}", config=config,
                          traffic=traffic, chips=1, why="small test cell"))
    manifest["workloads"] = cells
    for m in manifest["per_layer"]:
        m["workloads"] = [c["name"] for c in cells]
    with open(root / "BENCHMARK.json", "w") as f:
        json.dump(manifest, f)
    return root


def small_mix(traffic: str, overrides: dict, scenarios) -> dict:
    with open(os.path.join(REPO, "bench", "workloads",
                           traffic + ".json")) as f:
        mix = json.load(f)
    mix.update(overrides)
    if isinstance(scenarios, int):
        mix["scenarios"] = mix["scenarios"][:scenarios]
    elif isinstance(scenarios, list):
        mix["scenarios"] = [mix["scenarios"][i] for i in scenarios]
    else:
        mix["scenarios"] = [dict(mix["scenarios"][0], mixes=2)]
    return mix


def stand_in(device_sweep=None):
    """The system module with the compile cache left off (a test must not
    turn it on for the rest of its process) and, optionally, another
    device path."""
    return SimpleNamespace(compile_cache=lambda: "off",
                           make_spec=system.make_spec,
                           device_sweep=device_sweep or system.device_sweep)


def run_cell(root, name, capsys, *, seed=3, trace=0, sweep=None):
    """One harness run of cell `name` without the look for a chip;
    returns (exit code, result line as a dict)."""
    args = harness.parse(["--workload", name, "--seed", str(seed),
                          "--seconds", "0.01", "--trace", str(trace)])
    rc = harness.run(args, t0=time.perf_counter(), root=str(root),
                     require_chip=False, system=stand_in(sweep))
    out = capsys.readouterr().out.strip().splitlines()
    return rc, json.loads(out[-1])
