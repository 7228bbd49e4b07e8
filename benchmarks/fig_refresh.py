"""Paper-figure reproductions, driven by the batched sweep engine.

fig1: performance loss of REF_ab / REF_pb vs the no-refresh ideal across
      densities (paper Figure 1; claims C1, C2) — one *closed-loop*
      sweep-grid call reporting true weighted speedup.
fig2: service-timeline comparison — reads arriving during refreshes to
      other subarrays of the SAME bank (paper Figure 2; SARP mechanism),
      regenerated from the ACTUAL per-subarray refresh occupancy that
      `DramSim.run_ticks(record_timeline=True)` records, not a scripted
      timeline: the payload carries the first refresh window SARP
      parallelized serves into.
fig3: DSARP (and components) performance + energy vs baselines across
      densities (paper Figure 3; claims C3, C4), plus the post-paper
      registry policies (elastic, hira) — one *closed-loop* sweep-grid
      call; `ws` is `CellResult.weighted_speedup_vs`, the paper's metric.
sweep_grid: the engine's own benchmark — a timed 8x8x3 *open-loop*
      (policy x scenario x density) grid through the batched backend vs
      (a) the bit-identical scalar tick oracle and (b) the legacy
      workflow of looping the event-driven `DramSim` per cell.
closed_loop: the closed-loop analogue — a timed (policy x closed-scenario
      x density) grid through the batched backend vs looping
      `DramSim.run_ticks` per cell, plus the bit_identical conformance
      flag (the same cross-check `tests/test_conformance.py` enforces).
sweep_subarray: the [bank, subarray] hierarchy — the subarray-storm grid
      at n_subarrays in {1, 4, 8}, bit-identical per subarray count vs
      looping `DramSim.run_ticks`, per-count weighted speedup vs ideal.

`docs/figures.md` maps each emitted results/bench/*.json artifact to its
paper figure and regeneration command.
"""
from __future__ import annotations

import time

import numpy as np

from repro.core.refresh import (DramSim, make_closed_workload,
                                make_workload, run_policy)
from repro.core.refresh.timing import timing_for_density
from repro.core.sweep import SweepSpec, sweep

DENSITIES = (8, 16, 32)
#: scenario axis used for the open-loop engine benchmarks: low-contention,
#: mixed, write-drain, hot-bank contention, and the replay antagonist —
#: the last two sustain multi-bank refresh debt, which is what separates
#: policies like hira from sarp_pb (with a single owed bank every
#: selection rule picks it)
FIG_SCENARIOS = ("read_heavy", "mixed", "write_burst_draining",
                 "bank_camping", "trace_replay")
#: closed-loop scenario axis for the paper figures: the MLP spread is the
#: point — refresh hurts most where cores stall on every miss (low_mlp)
#: and least where deep MLP hides it (streaming)
CLOSED_FIG_SCENARIOS = ("closed_mixed", "closed_read_heavy",
                        "closed_write_heavy", "closed_low_mlp",
                        "closed_streaming")
#: every figure statistic averages these trace seeds
FIG_SEEDS = (1, 2)
#: the full default grid axes for sweep_grid (8 x 8 x 3)
GRID_POLICIES = ("ideal", "ref_ab", "ref_pb", "darp", "darp_ooo",
                 "sarp_pb", "dsarp", "elastic")
GRID_SCENARIOS = ("read_heavy", "write_burst_draining",
                  "row_buffer_friendly", "bank_camping",
                  "subarray_conflict_adversarial", "trace_replay",
                  "mixed", "streaming")
#: policy axis for the serving bench: the generic-engine spellings of the
#: grid baselines plus the registry extras (defined here so every
#: benchmark's policy axis lives next to the grid definitions)
SERVING_POLICIES = ("all_bank", "round_robin", "darp", "elastic", "hira")


#: fig3's policy axis; fig1's (ideal, ref_ab, ref_pb) is a subset, so one
#: `fig_grids` result can feed both figures without re-sweeping
FIG3_POLICIES = ("ref_ab", "ref_pb", "darp", "sarp_pb", "dsarp",
                 "elastic", "hira", "ideal")


def fig_grids(reqs: int = 2000) -> list:
    """One full closed-loop figure grid per seed — pass to fig1/fig3 via
    `runs=` to compute both figures from a single set of sweeps. The
    demand must span several tREFI intervals (reqs >= ~1500) or all-bank
    refresh barely fires and the Figure 1 ordering degenerates."""
    return [sweep(SweepSpec(policies=FIG3_POLICIES,
                            scenarios=CLOSED_FIG_SCENARIOS,
                            densities=DENSITIES, reqs=reqs, seed=s,
                            mode="closed"))
            for s in FIG_SEEDS]


def fig1(reqs: int = 2000, runs: list = None) -> dict:
    """Performance loss vs the no-refresh ideal: 1 - weighted speedup,
    the paper's closed-loop metric (was a latency proxy before the
    closed-loop sweep mode landed)."""
    if runs is None:
        runs = [sweep(SweepSpec(policies=("ideal", "ref_ab", "ref_pb"),
                                scenarios=CLOSED_FIG_SCENARIOS,
                                densities=DENSITIES, reqs=reqs, seed=s,
                                mode="closed"))
                for s in FIG_SEEDS]
    out = {}
    for d in DENSITIES:
        out[d] = {}
        for p in ("ref_ab", "ref_pb"):
            ws = [res.get(p, s, d).weighted_speedup_vs(
                      res.get("ideal", s, d))
                  for res in runs for s in CLOSED_FIG_SCENARIOS]
            out[d][p] = 1.0 - float(np.mean(ws))
    return out


def fig2() -> dict:
    """Reads arriving during a refresh to another subarray of the same
    bank: REF_pb marks every subarray and blocks them; SARP marks one and
    serves them concurrently. Regenerated from the recorded per-subarray
    occupancy timeline (deterministic: same seed, same timeline), with
    the first parallelized refresh window kept as the figure's excerpt."""
    out = {}
    T = timing_for_density(32, n_subarrays=8)
    wl = make_closed_workload("closed_subarray_storm", 240, 9)
    for pol in ("ref_pb", "sarp_pb"):
        r = DramSim(T, wl, pol).run_ticks(record_timeline=True)
        ref = r.timeline["refresh"]
        serves = r.timeline["serves"]
        sibling = sum(1 for (t, b, sub, row, isw, done, arr) in serves
                      if any(rb == b and rs not in (-1, sub) and s0 <= t < s1
                             for (rb, rs, s0, s1, k) in ref))
        excerpt = None
        for (rb, rs, s0, s1, k) in ref:
            inside = [s for s in serves if s[1] == rb and s0 <= s[0] < s1]
            if inside:
                excerpt = {"refresh_bank_sub_start_end": [rb, rs, s0, s1],
                           "serves_during": [list(s) for s in inside[:4]]}
                break
        out[pol] = {"avg_read_ns": r.avg_read_latency,
                    "p99_read_ns": r.p99_read_latency,
                    "refreshes_pb": r.refreshes_pb,
                    "serves_during_sibling_refresh": sibling,
                    "first_parallelized_refresh": excerpt}
    return out


def fig3(reqs: int = 2000, runs: list = None) -> dict:
    """DSARP + components vs baselines: `ws` is the true closed-loop
    weighted speedup vs the per-grid ideal (`weighted_speedup_vs`)."""
    policies = FIG3_POLICIES
    if runs is None:
        runs = fig_grids(reqs)
    out = {}
    for d in DENSITIES:
        row = {}
        for p in policies:
            ws, es = [], []
            for res in runs:
                for s in CLOSED_FIG_SCENARIOS:
                    cell = res.get(p, s, d)
                    ws.append(cell.weighted_speedup_vs(
                        res.get("ideal", s, d)))
                    es.append(cell.energy)
            row[p] = {"ws": float(np.mean(ws)), "energy": float(np.mean(es))}
        ref_ab_e = row["ref_ab"]["energy"]
        for p in row:
            row[p]["energy_vs_refab"] = row[p]["energy"] / ref_ab_e
            row[p]["improvement_vs_refab"] = \
                row[p]["ws"] / row["ref_ab"]["ws"] - 1
        out[d] = row
    return out


def sweep_grid(fast: bool = False) -> dict:
    """Timed grid sweep: batched backend vs the scalar tick oracle and vs
    the legacy `DramSim` event-loop workflow, plus bit-identity check."""
    reqs = 120 if fast else 400
    spec = SweepSpec(policies=GRID_POLICIES, scenarios=GRID_SCENARIOS,
                     densities=DENSITIES, reqs=reqs, seed=0)
    legacy_reqs_per_core = reqs // 4

    t0 = time.perf_counter()
    batched = sweep(spec, backend="batched")
    t_batched = time.perf_counter() - t0

    t0 = time.perf_counter()
    scalar = sweep(spec, backend="scalar")
    t_scalar = time.perf_counter() - t0
    identical = all(a == b for a, b in zip(batched.cells, scalar.cells))

    # the pre-sweep workflow: one event-driven DramSim run per grid cell
    # (closed-loop workload of comparable size; legacy preset cycled per
    # scenario since the event-loop sim predates the scenario library)
    legacy_presets = ("mixed", "read_heavy", "write_heavy", "low_mlp",
                      "streaming")
    t0 = time.perf_counter()
    for i, (p, s, d) in enumerate(spec.cells()):
        wl = make_workload(legacy_presets[i % len(legacy_presets)],
                           n_cores=4, reqs_per_core=legacy_reqs_per_core,
                           seed=0)
        run_policy(p, d, wl)
    t_legacy = time.perf_counter() - t0

    return {
        "grid": {"policies": len(spec.policies),
                 "scenarios": len(spec.scenarios),
                 "densities": len(spec.densities),
                 "cells": len(spec.cells()), "reqs_per_cell": spec.reqs},
        "batched_s": round(t_batched, 3),
        "scalar_tick_oracle_s": round(t_scalar, 3),
        "legacy_dramsim_loop_s": round(t_legacy, 3),
        "speedup_vs_scalar_tick": round(t_scalar / t_batched, 2),
        "speedup_vs_dramsim_loop": round(t_legacy / t_batched, 2),
        "bit_identical": identical,
    }


def _cell_matches_sim(cell, sim) -> bool:
    """Every stat a CellResult shares with a SimResult, bit-identical —
    ONE definition for every bench's bit_identical flag (the test-side
    twin is tests/test_conformance.py::_assert_cell_equals_sim)."""
    return (cell.makespan == sim.makespan
            and cell.reads_done == sim.reads_done
            and cell.writes_done == sim.writes_done
            and cell.avg_read_latency == sim.avg_read_latency
            and cell.p99_read_latency == sim.p99_read_latency
            and cell.refreshes_pb == sim.refreshes_pb
            and cell.refreshes_ab == sim.refreshes_ab
            and cell.row_hits == sim.row_hits
            and cell.row_misses == sim.row_misses
            and cell.energy == sim.energy
            and cell.max_abs_lag == sim.max_abs_lag
            and list(cell.core_finish) == list(sim.core_finish))


def closed_loop(fast: bool = False) -> dict:
    """Timed closed-loop grid: the batched backend advancing every
    (policy x closed-scenario x density) cell in lock-step vs the
    conformance workflow of looping `DramSim.run_ticks` per cell —
    including the bit_identical cross-check over every shared stat."""
    reqs = 120 if fast else 400
    seed = 0
    spec = SweepSpec(policies=GRID_POLICIES,
                     scenarios=CLOSED_FIG_SCENARIOS, densities=DENSITIES,
                     reqs=reqs, seed=seed, mode="closed")

    t0 = time.perf_counter()
    batched = sweep(spec, backend="batched")
    t_batched = time.perf_counter() - t0

    wls = {s: make_closed_workload(s, reqs, seed)
           for s in CLOSED_FIG_SCENARIOS}
    identical = True
    t0 = time.perf_counter()
    for p, s, d in spec.cells():
        sim = DramSim(timing_for_density(d), wls[s], p).run_ticks()
        identical &= _cell_matches_sim(batched.get(p, s, d), sim)
    t_ticks_loop = time.perf_counter() - t0

    return {
        "grid": {"policies": len(spec.policies),
                 "scenarios": len(spec.scenarios),
                 "densities": len(spec.densities),
                 "cells": len(spec.cells()), "reqs_per_cell": spec.reqs},
        "batched_s": round(t_batched, 3),
        "dramsim_ticks_loop_s": round(t_ticks_loop, 3),
        "speedup_vs_dramsim_ticks": round(t_ticks_loop / t_batched, 2),
        "bit_identical": identical,
    }


#: policy axis for the multirank hierarchy sweep: the flat baselines,
#: the paper's mechanism, and the two hierarchy-only registry policies
MULTIRANK_POLICIES = ("ideal", "ref_ab", "ref_pb", "darp", "dsarp",
                      "staggered_ab", "rank_aware_darp")


def sweep_multirank(fast: bool = False) -> dict:
    """The [channel, rank, bank] hierarchy sweep: the closed_multirank
    grid at n_ranks in {1, 2, 4} through the batched backend, each rank
    count cross-checked bit-identically against looping
    `DramSim.run_ticks` per cell (the conformance surface of
    tests/test_multirank.py), plus per-rank-count weighted speedup vs
    ideal — how much of each policy's refresh cost rank-level
    parallelism absorbs."""
    reqs = 120 if fast else 400
    seed = 0
    scen = "closed_multirank"
    wl = make_closed_workload(scen, reqs, seed)
    out = {"grid": {"policies": len(MULTIRANK_POLICIES), "scenario": scen,
                    "densities": list(DENSITIES), "reqs_per_cell": reqs},
           "per_rank_count": {}}
    identical = True
    for n_ranks in (1, 2, 4):
        spec = SweepSpec(policies=MULTIRANK_POLICIES, scenarios=(scen,),
                         densities=DENSITIES, reqs=reqs, seed=seed,
                         mode="closed", n_ranks=n_ranks)
        t0 = time.perf_counter()
        res = sweep(spec, backend="batched")
        t_batched = time.perf_counter() - t0
        t0 = time.perf_counter()
        for p, s, d in spec.cells():
            sim = DramSim(timing_for_density(d, n_ranks=n_ranks), wl,
                          p).run_ticks()
            identical &= _cell_matches_sim(res.get(p, s, d), sim)
        t_loop = time.perf_counter() - t0
        ws = {}
        for p in MULTIRANK_POLICIES:
            if p == "ideal":
                continue
            ws[p] = {d: round(res.get(p, scen, d).weighted_speedup_vs(
                res.get("ideal", scen, d)), 4) for d in DENSITIES}
        out["per_rank_count"][n_ranks] = {
            "batched_s": round(t_batched, 3),
            "dramsim_ticks_loop_s": round(t_loop, 3),
            "weighted_speedup_vs_ideal": ws,
        }
    out["bit_identical"] = identical
    return out


#: policy axis for the subarray hierarchy sweep: the flat baselines, the
#: paper's SARP family, and the hidden-row-activation extra
SUBARRAY_POLICIES = ("ideal", "ref_ab", "ref_pb", "sarp_ab", "sarp_pb",
                     "dsarp", "hira")


def sweep_subarray(fast: bool = False) -> dict:
    """The [bank, subarray] hierarchy sweep: the closed_subarray_storm
    grid at n_subarrays in {1, 4, 8} through the batched backend, each
    subarray count cross-checked bit-identically against looping
    `DramSim.run_ticks` per cell (the conformance surface of
    tests/test_subarray.py), plus per-subarray-count weighted speedup vs
    ideal — how much refresh cost subarray-level parallelism absorbs."""
    reqs = 120 if fast else 400
    seed = 0
    scen = "closed_subarray_storm"
    wl = make_closed_workload(scen, reqs, seed)
    out = {"grid": {"policies": len(SUBARRAY_POLICIES), "scenario": scen,
                    "densities": list(DENSITIES), "reqs_per_cell": reqs},
           "per_subarray_count": {}}
    identical = True
    for n_subarrays in (1, 4, 8):
        spec = SweepSpec(policies=SUBARRAY_POLICIES, scenarios=(scen,),
                         densities=DENSITIES, reqs=reqs, seed=seed,
                         mode="closed", n_subarrays=n_subarrays)
        t0 = time.perf_counter()
        res = sweep(spec, backend="batched")
        t_batched = time.perf_counter() - t0
        t0 = time.perf_counter()
        for p, s, d in spec.cells():
            sim = DramSim(timing_for_density(d, n_subarrays=n_subarrays),
                          wl, p).run_ticks()
            identical &= _cell_matches_sim(res.get(p, s, d), sim)
        t_loop = time.perf_counter() - t0
        ws = {}
        for p in SUBARRAY_POLICIES:
            if p == "ideal":
                continue
            ws[p] = {d: round(res.get(p, scen, d).weighted_speedup_vs(
                res.get("ideal", scen, d)), 4) for d in DENSITIES}
        out["per_subarray_count"][n_subarrays] = {
            "batched_s": round(t_batched, 3),
            "dramsim_ticks_loop_s": round(t_loop, 3),
            "weighted_speedup_vs_ideal": ws,
        }
    out["bit_identical"] = identical
    return out


def command_trace(fast: bool = False) -> dict:
    """The command layer's cost model: `DramSim.run_ticks` with
    `record_commands=True` vs disabled (emission must stay under ~10%
    slowdown and cost nothing when off), the JEDEC validator over the
    emitted trace (zero violations), and the emit -> replay round trip
    (`bit_identical`)."""
    from repro.core.commands import round_trip, validate_trace

    reqs = 300 if fast else 800
    reps = 3 if fast else 5
    T = timing_for_density(32, n_ranks=2, n_subarrays=4)
    wl = make_closed_workload("closed_mixed", reqs, 0)

    def timed(record):
        best = float("inf")
        res = None
        for _ in range(reps):
            t0 = time.perf_counter()
            res = DramSim(T, wl, "dsarp").run_ticks(record_commands=record)
            best = min(best, time.perf_counter() - t0)
        return best, res

    t_off, res_off = timed(False)
    t_on, res_on = timed(True)
    trace = res_on.commands
    violations = validate_trace(trace)
    _, bit_identical = round_trip(trace)
    return {
        "workload": {"scenario": "closed_mixed", "reqs": reqs,
                     "policy": "dsarp", "n_ranks": 2, "n_subarrays": 4},
        "commands": len(trace),
        "counts": trace.counts(),
        "disabled_s": round(t_off, 4),
        "enabled_s": round(t_on, 4),
        "overhead_pct": round(100.0 * (t_on - t_off) / t_off, 1),
        "disabled_emits_trace": res_off.commands is not None,
        "violations": len(violations),
        "bit_identical": bit_identical,
    }
