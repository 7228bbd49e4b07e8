"""Where one sweep's time goes, by the program's own spans and scopes.

    python3 bench/profile_sweep.py --workload <cell> --seed <n>

A cell is set up as `bench/run.py` sets it up: compile cache, traffic
from the seed, one warm-up sweep. Then `UNTRACED` sweeps run with the
profiler off, and one sweep runs profiled inside the harness span
`sweep`. The run prints one JSON line:

  * ``metrics``: the cell's per-layer metrics of `BENCHMARK.json`, read
    from `bench.trace` as a `--trace 1` run reads them, and `READERS`,
    which read the program's spans, scopes and counters (`bench.spans`);
  * ``breakdown``: each program span's length and device-idle time, the
    device-idle time per span (``idle_by_span``, with ``(outside)``),
    the idle gaps of 1 ms or more with the span holding each, each tick
    scope's device time and the loop's device-busy time;
  * ``cost``: the untraced sweeps, the traced one, the seconds spent
    reading the trace, and five inactive spans, as the program enters
    them on every sweep, timed on the host.

Like the benchmark, it refuses with a non-zero exit where JAX finds no
TPU.
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import tempfile
import time
from types import SimpleNamespace

T0 = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

from bench import harness, roofline, spans, trace  # noqa: E402
from bench.traffic import build  # noqa: E402

#: the metrics that read the program's spans, scopes and counters
READERS = ("grid_build_ms", "stage_ms", "readback_ms", "finalize_ms",
           "tick_front_end_us_per_tick", "tick_refresh_us_per_tick",
           "tick_arbitrate_us_per_tick", "tick_serve_us_per_tick",
           "tick_loop_useful_share")
#: sweeps timed with the profiler off, against the traced one
UNTRACED = 3
#: repetitions of the five inactive spans a sweep enters
INACTIVE_REPS = 20000


def loop_op_names(spec) -> dict:
    """{HLO instruction: op_name} of the compiled loop `spec` runs; the
    persistent cache serves the compile."""
    from repro.core.sweep import jaxbody
    from repro.core.sweep.engine import _Grid, _jax_arbiter

    cfg, cst, s0 = jaxbody.program(_Grid(spec))
    return spans.hlo_op_names(jaxbody.run_loop.lower(
        cfg, cst, _jax_arbiter("jnp"), s0).compile().as_text())


def inactive_spans_s() -> float:
    """Host seconds of the five spans a sweep enters, with no profiler
    active."""
    from jax.profiler import TraceAnnotation

    t0 = time.perf_counter()
    for _ in range(INACTIVE_REPS):
        for name in spans.SPANS[:-1]:
            with TraceAnnotation(name):
                pass
        with TraceAnnotation(spans.SPANS[-1], cells=1, loop_iterations=1):
            pass
    return (time.perf_counter() - t0) / INACTIVE_REPS


def profile(workload: str, seed: int, root: str = ROOT,
            require_chip: bool = True, system=None):
    """The result dict of one profile run, or None where JAX finds no
    TPU (and `require_chip`)."""
    import jax

    cell = harness.load_cell(workload, root)
    devices = jax.devices()
    if require_chip and devices[0].platform != "tpu":
        print(f"profile_sweep: cell {workload} needs a TPU chip; JAX finds "
              f"{devices[0].platform}. Nothing was run.", file=sys.stderr)
        return None
    if system is None:
        from bench import system
    system.compile_cache()
    traffic = build(cell.mix, cell.config, seed, cell.reference)
    spec = system.make_spec(traffic, cell.config)
    first = system.device_sweep(spec)
    untraced = []
    for _ in range(UNTRACED):
        t0 = time.perf_counter()
        system.device_sweep(spec)
        untraced.append(time.perf_counter() - t0)
    op_names = loop_op_names(spec)
    with tempfile.TemporaryDirectory() as logdir:
        with jax.profiler.trace(logdir):
            with jax.profiler.TraceAnnotation(harness.SPAN):
                t0 = time.perf_counter()
                cells = system.device_sweep(spec)
                traced = time.perf_counter() - t0
        t_read = time.perf_counter()
        tr = spans.load(trace.find_xplane(logdir), op_names)
        summary = trace.reduce(tr.planes, harness.SPAN)
        sp = spans.reduce(tr, harness.SPAN)
        read_s = time.perf_counter() - t_read
    ticks = harness.cell_ticks(cells, cell.config["dt_ns"])
    ctx = SimpleNamespace(
        summary=summary, max_cell_ticks=max(ticks),
        least_bytes=roofline.least_bytes(traffic),
        peaks=(roofline.peaks(devices[0].device_kind)
               if devices[0].platform == "tpu" else None),
        spans=sp, sum_cell_ticks=sum(ticks))
    metrics = {}
    for name in [m["name"] for m in cell.per_layer] + list(READERS):
        value = harness.metric_reader(name, root)(ctx)
        if value is not None:
            metrics[name] = value
    breakdown = {}
    if sp is not None:
        breakdown = {
            "idle_by_span": {n: ns / 1e9
                             for n, ns in sp.idle_by_span.items()},
            "long_gaps": [[n, ns / 1e9] for n, ns in sp.long_gaps],
            "spans": {n: {"s": s.ns / 1e9, "idle_s": s.idle_ns / 1e9,
                          "stats": s.stats} for n, s in sp.spans.items()},
            "scopes": {n: ns / 1e9 for n, ns in sp.scope_ns.items()},
            "loop_busy_s": sp.loop_busy_ns / 1e9,
            "span_s": summary.span_ns / 1e9, "busy_s": summary.busy_ns / 1e9}
    return {"workload": workload, "seed": seed,
            "device": {"platform": devices[0].platform,
                       "kind": devices[0].device_kind,
                       "count": len(devices)},
            "same_as_warm": cells == first, "cells": len(cells),
            "sum_cell_ticks": sum(ticks), "max_cell_ticks": max(ticks),
            "metrics": metrics, "breakdown": breakdown,
            "cost": {"untraced_s": untraced, "traced_s": traced,
                     "traced_over_untraced": traced
                     / statistics.median(untraced),
                     "read_s": read_s,
                     "inactive_spans_s": inactive_spans_s(),
                     "run_s": time.perf_counter() - T0}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    args = ap.parse_args(argv)
    result = profile(args.workload, args.seed)
    if result is None:
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
