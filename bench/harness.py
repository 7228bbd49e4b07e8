"""One run of one benchmark cell.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell's entry in `BENCHMARK.json` names its configuration and its
traffic; both are data files the harness finds by name
(`bench/configs/`, `bench/workloads/<traffic>.json`), and so is each
per-layer metric's reader (`bench/metrics/<metric>.py`). The
configuration names its plain reference (``"reference"``, a module under
`bench/`). A new cell, a new metric or a new reference is a new file and
an entry in `BENCHMARK.json` or the configuration.

A run:

  1. refuses, with a non-zero exit and no result, where JAX finds no TPU
     or fewer chips than the cell asks for;
  2. turns JAX's persistent compilation cache on (`system.compile_cache`);
  3. draws the cell's traffic from `--seed` and builds its sweep spec;
  4. runs one sweep, which compiles or loads the loop from the cache and
     warms every shape. Steps 1-4 are `setup_s`;
  5. with `--trace 0`, calls the device path back to back on that one
     spec until `--seconds` have passed (the window); with `--trace 1`,
     profiles one sweep instead and reduces its trace;
  6. checks the sweeps of the window against the configuration's plain
     reference on cells drawn from the seed, and prints the result as the
     last line of standard output.
"""
from __future__ import annotations

import argparse
import importlib.util
import json
import os
import sys
import tempfile
import time
from dataclasses import dataclass
from types import ModuleType, SimpleNamespace

import numpy as np

from bench import roofline, trace
from bench.traffic import build

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: the event JAX records for each backend (XLA) compile
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
#: the event JAX records when the persistent cache serves such a compile
CACHE_HIT = "/jax/compilation_cache/cache_hits"
#: the harness's host span around the traced sweep
SPAN = "sweep"


@dataclass
class Cell:
    workload: dict              # the cell's entry in BENCHMARK.json
    config: dict                # its configuration file
    reference: ModuleType       # the plain reference the config names
    mix: dict                   # its traffic file
    end_to_end: list            # its end-to-end metric entries
    per_layer: list             # its per-layer metric entries


def load_cell(name: str, root: str = ROOT) -> Cell:
    """Cell `name` of `<root>/BENCHMARK.json`, with its files."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    by_name = {w["name"]: w for w in manifest["workloads"]}
    if name not in by_name:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json: "
                       f"{sorted(by_name)}")
    wl = by_name[name]
    cfg = {c["name"]: c for c in manifest["configs"]}[wl["config"]]
    with open(os.path.join(root, cfg["file"])) as f:
        config = json.load(f)
    with open(os.path.join(root, "bench", "workloads",
                           wl["traffic"] + ".json")) as f:
        mix = json.load(f)
    e2e = [m for m in manifest["end_to_end"]
           if name in m.get("workloads", [name])]
    moved = {m["name"] for m in e2e}
    per_layer = [m for m in manifest["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in moved)]
    return Cell(wl, config, load_reference(config, root), mix, e2e,
                per_layer)


def _load_module(name: str, path: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str, root: str = ROOT):
    """`read(ctx)` of `<root>/bench/metrics/<name>.py`."""
    return _load_module(f"bench_metric_{name}", os.path.join(
        root, "bench", "metrics", name + ".py")).read


def load_reference(config: dict, root: str = ROOT):
    """The plain reference `config` names: the module at
    ``<root>/<config["reference"]>``, under `bench/`, with
    ``simulate(traffic, config, cells, itype=np.int32, record=False)``
    and ``FIELDS``."""
    root = os.path.normpath(root)
    rel = config.get("reference")
    path = os.path.normpath(os.path.join(root, rel or ""))
    if not (rel and path.startswith(os.path.join(root, "bench", ""))
            and path.endswith(".py")):
        raise ValueError(f"{config.get('name')}: reference {rel!r} is no "
                         f"module under bench/")
    stem = os.path.splitext(os.path.relpath(path, root))[0]
    return _load_module("bench_reference_" + stem.replace(os.sep, "_"),
                        path)


class CompileCounter:
    """Counts backend compiles while open, and how many of them the
    persistent cache served."""

    def __enter__(self):
        import jax

        self.count = self.cache_hits = 0

        def listen(event, secs, **_):
            if event == BACKEND_COMPILE:
                self.count += 1

        def listen_hit(event, **_):
            if event == CACHE_HIT:
                self.cache_hits += 1

        self._listen, self._listen_hit = listen, listen_hit
        jax.monitoring.register_event_duration_secs_listener(listen)
        jax.monitoring.register_event_listener(listen_hit)
        return self

    def __exit__(self, *exc):
        import jax

        jax.monitoring.unregister_event_duration_listener(self._listen)
        jax.monitoring.unregister_event_listener(self._listen_hit)


def cell_ticks(cells, dt_ns: float) -> list[int]:
    return [round(c.makespan / dt_ns) for c in cells]


def sample_cells(n_cells: int, k: int, longest: int, seed: int) -> list:
    """`k` of `n_cells` grid cells drawn from `seed`, `longest` among
    them, in grid order."""
    k = min(k, n_cells)
    pick = [int(g) for g in np.random.default_rng(seed).choice(
        n_cells, size=k, replace=False)]
    if longest not in pick:
        pick[-1] = longest
    return sorted(pick)


def mismatched(sweeps: list, sample: list, ref: list, fields) -> int:
    """Sampled cells, over all `sweeps`, whose result differs from the
    reference in any of `fields`."""
    bad = 0
    for cells in sweeps:
        for g, r in zip(sample, ref):
            c = cells[g] if g < len(cells) else None
            if c is None or any(getattr(c, f, None) != r[f]
                                for f in fields):
                bad += 1
    return bad


def _window(system, spec, seconds: float):
    """Sweeps back to back until `seconds` have passed; returns them with
    the seconds from the window's start to the last sweep's return."""
    sweeps = []
    t0 = time.perf_counter()
    while True:
        sweeps.append(system.device_sweep(spec))
        elapsed = time.perf_counter() - t0
        if elapsed >= seconds:
            return sweeps, elapsed


def _traced(system, spec):
    """One profiled sweep inside the harness span `SPAN`."""
    import jax

    with tempfile.TemporaryDirectory() as logdir:
        with jax.profiler.trace(logdir):
            with jax.profiler.TraceAnnotation(SPAN):
                t0 = time.perf_counter()
                cells = system.device_sweep(spec)
                elapsed = time.perf_counter() - t0
        summary = trace.reduce(trace.load_xplane(trace.find_xplane(logdir)),
                               SPAN)
    return [cells], elapsed, summary


def run(args, *, t0: float, root: str = ROOT, require_chip: bool = True,
        system=None) -> int:
    """One run; returns the exit code. `require_chip=False` and a
    `system` stand-in exist for the benchmark's own tests."""
    cell = load_cell(args.workload, root)
    import jax

    devices = jax.devices()
    if require_chip and (devices[0].platform != "tpu"
                         or len(devices) < cell.workload["chips"]):
        print(f"bench: cell {args.workload} needs {cell.workload['chips']} "
              f"TPU chip(s); JAX finds {len(devices)} "
              f"{devices[0].platform} device(s). Nothing was run.",
              file=sys.stderr)
        return 2
    if system is None:
        from bench import system
    print(f"bench: compile cache {system.compile_cache()}", file=sys.stderr)
    config, reference = cell.config, cell.reference
    traffic = build(cell.mix, config, args.seed, reference)
    spec = system.make_spec(traffic, config)
    with CompileCounter() as compiles:
        first = system.device_sweep(spec)
        setup_s = time.perf_counter() - t0
        before, hits = compiles.count, compiles.cache_hits
        if args.trace:
            sweeps, window_s, summary = _traced(system, spec)
        else:
            sweeps, window_s = _window(system, spec, args.seconds)
        window_compiles = compiles.count - before
    stats = devices[0].memory_stats() or {}
    dt = config["dt_ns"]
    ticks = cell_ticks(sweeps[-1], dt)
    n_cells = len(first)
    print(f"bench: setup_s={setup_s} setup_compiles={before} "
          f"from_cache={hits} window_s={window_s} sweeps={len(sweeps)} "
          f"cells={n_cells} window_compiles={window_compiles}",
          file=sys.stderr)

    t_check = time.perf_counter()
    longest = int(np.argmax(cell_ticks(first, dt)))
    sample = sample_cells(n_cells, traffic.check_cells, longest, args.seed)
    ref = reference.simulate(traffic, config,
                             [traffic.cells()[g] for g in sample])
    bad = mismatched(sweeps, sample, ref, reference.FIELDS)
    print(f"bench: reference checked {len(sample)} cells x {len(sweeps)} "
          f"sweeps in {time.perf_counter() - t_check} s", file=sys.stderr)

    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind,
              "count": len(devices),
              "memory_peak_bytes": stats.get("peak_bytes_in_use")}
    result = {"correct": bad == 0,
              "attempted": sum(len(s) for s in sweeps),
              "failed": sum(not c.finished for s in sweeps for c in s)}
    if args.trace:
        ctx = SimpleNamespace(
            summary=summary, max_cell_ticks=max(ticks),
            least_bytes=roofline.least_bytes(traffic),
            peaks=(roofline.peaks(devices[0].device_kind)
                   if devices[0].platform == "tpu" else None))
        metrics = {}
        for m in cell.per_layer:
            value = metric_reader(m["name"], root)(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device.update(busy_s=summary.busy_ns / 1e9,
                      window_s=summary.span_ns / 1e9)
        ops = sorted(summary.op_ns.items(), key=lambda kv: -kv[1])[:10]
        result["breakdown"] = {
            "device_ops": [[n, ns / 1e9] for n, ns in ops],
            "idle_gaps": [[n, ns / 1e9] for n, ns in summary.gaps]}
    else:
        metrics = {"sim_ticks_per_s": sum(sum(cell_ticks(s, dt))
                                          for s in sweeps) / window_s,
                   "setup_s": setup_s}
        units = {m["name"]: m["unit"] for m in cell.end_to_end}
        metrics = {k: {"value": v, "unit": units[k]}
                   for k, v in metrics.items() if k in units}
    result.update(metrics=metrics, device=device,
                  check={"mismatched_cells": {"value": bad, "limit": 0}})
    # keys in the order the result line is read: the check comes last
    result = {k: result[k] for k in ("correct", "attempted", "failed",
                                     "metrics", "device", "breakdown",
                                     "check") if k in result}
    print(f"check mismatched_cells={bad} limit=0", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)
