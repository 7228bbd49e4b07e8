"""Readings that set the limit of `mismatched_cells`, the number a run's
`correct` is decided by.

    python3 bench/control.py --workload <cell> --seeds 1 2 3 ...

For each seed, at the cell's own size, on the cells a benchmark run with
that seed would check:

  * program: the device path's sweep against the reference (int32);
    sound runs read 0, and the largest over the seeds is the lower
    reading;
  * control: the configuration's reference itself put in the program's
    place, with every state plane and counter in int16, the next integer
    width below the contract's int32; its smallest reading over the seeds
    is the upper one.

The benchmark's runs never call this. Prints one line per seed and a
JSON summary as the last line.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

from bench import harness  # noqa: E402
from bench.traffic import build  # noqa: E402

#: the control's integer type: the width below the contract's int32
CONTROL_ITYPE = np.int16


def _diff(a: list, b: list, fields) -> int:
    return sum(any(x[f] != y[f] for f in fields) for x, y in zip(a, b))


def readings(cell: harness.Cell, seed: int, system) -> dict:
    """Program and control readings of `mismatched_cells` at `seed`."""
    reference = cell.reference
    traffic = build(cell.mix, cell.config, seed, reference)
    cells = system.device_sweep(system.make_spec(traffic, cell.config))
    dt = cell.config["dt_ns"]
    sample = harness.sample_cells(
        len(cells), traffic.check_cells,
        int(np.argmax(harness.cell_ticks(cells, dt))), seed)
    picked = [traffic.cells()[g] for g in sample]
    ref = reference.simulate(traffic, cell.config, picked)
    control = reference.simulate(traffic, cell.config, picked,
                                 itype=CONTROL_ITYPE)
    return {"seed": seed, "cells": len(sample),
            "program": harness.mismatched([cells], sample, ref,
                                          reference.FIELDS),
            "control": _diff(control, ref, reference.FIELDS)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    import jax

    from bench import system

    system.compile_cache()
    cell = harness.load_cell(args.workload)
    rows = []
    for seed in args.seeds:
        rows.append(readings(cell, seed, system))
        print(json.dumps(rows[-1]), flush=True)
    print(json.dumps({
        "workload": args.workload, "device": jax.devices()[0].device_kind,
        "lower": max(r["program"] for r in rows),
        "upper": min(r["control"] for r in rows), "rows": rows}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
