"""Randomized differential fuzz for the sweep engine's device path.

Property: for ANY point of the sweep space — policy x scenario x density
x n_ranks x n_channels x n_subarrays x n_bank_groups x mode x seed — the
jitted `jax` backend (the device path), the batched numpy oracle, and
the per-cell `DramSim.run_ticks` reference agree **bit-identically**:
every `CellResult` stat, the paper's `weighted_speedup_vs` metric, and
(closed mode) the batched backend's DFI-style command trace, command for
command.

Each case is drawn deterministically from `random.Random(case)`; the
layout axis cycles through `HIERARCHIES`, so every layout is drawn once
in each run of ``len(HIERARCHIES)`` cases. The case count scales with
the ``SWEEP_FUZZ_CASES`` env var (default 6 per mode).

Edge cases are pinned as golden fixtures under
``tests/fixtures/sweep_fuzz/`` and replayed by
`test_golden_fixture_cases_stay_bit_identical` — add any future
counterexample there.
"""
import json
import os
import random
from pathlib import Path

import pytest
from conftest import lpddr4

from repro.core.commands import validate_trace
from repro.core.refresh import DramSim, make_closed_workload
from repro.core.refresh.timing import timing_for_density
from repro.core.sweep import CellResult, SweepSpec, sweep

N_CASES = int(os.environ.get("SWEEP_FUZZ_CASES", "6"))
FIXTURES = Path(__file__).resolve().parent / "fixtures" / "sweep_fuzz"

POLICIES = ("ref_ab", "ref_pb", "darp", "dsarp", "sarp_pb", "elastic",
            "hira", "staggered_ab", "rank_aware_darp", "round_robin")
CLOSED_SCENARIOS = ("closed_mixed", "closed_read_heavy",
                    "closed_write_heavy", "closed_multirank",
                    "closed_subarray_storm")
OPEN_SCENARIOS = ("mixed", "read_heavy", "streaming",
                  "write_burst_draining", "bank_camping")
DENSITIES = (8, 16, 32)
#: closed-mode (n_ranks, n_channels, n_subarrays, n_bank_groups) draws,
#: bounded so repeated shapes hit the jit cache across cases
HIERARCHIES = ((1, 1, 1, 1), (2, 1, 1, 1), (1, 2, 1, 1), (1, 1, 4, 1),
               (2, 2, 4, 1), (2, 1, 1, 2))
#: open-mode n_ranks draws
OPEN_RANKS = (1, 2)
#: LPDDR4 (n_ranks, n_channels, n_subarrays) draws, and its per-bank
#: issue rules (tpbR2pbR ns, refresh rounds): both, the spacing alone,
#: the rounds alone
LP_LAYOUTS = ((2, 1, 1), (2, 2, 4), (1, 2, 8))
LP_RULES = ((90.0, True), (90.0, False), (0.0, True))


def _cells_equal(a, b, ctx=""):
    bad = [(x.policy, x.scenario, x.density_gb, f)
           for x, y in zip(a.cells, b.cells) if x != y
           for f in CellResult.__dataclass_fields__
           if getattr(x, f) != getattr(y, f)]
    assert not bad, f"{ctx} diverged: {bad[:8]}"


def _assert_cell_equals_sim(cell, sim):
    pairs = [(f, getattr(cell, f), getattr(sim, f)) for f in
             ("makespan", "reads_done", "writes_done", "avg_read_latency",
              "p99_read_latency", "refreshes_pb", "refreshes_ab",
              "row_hits", "row_misses", "energy", "max_abs_lag")]
    pairs.append(("core_finish", list(cell.core_finish),
                  list(sim.core_finish)))
    bad = [(n, a, b) for n, a, b in pairs if a != b]
    assert not bad, (cell.policy, cell.scenario, cell.density_gb, bad)


def _timing(density, n_ranks, n_channels, n_subarrays, n_bank_groups):
    """The program's DRAM at this layout; with bank groups, tCCD_L one
    tick above tCCD_S."""
    groups = (dict(n_bank_groups=n_bank_groups, tCCD_L=9.0, tCCD_S=6.0)
              if n_bank_groups > 1 else {})
    return timing_for_density(density, n_ranks=n_ranks,
                              n_channels=n_channels,
                              n_subarrays=n_subarrays, **groups)


def _check_closed_case(policy, scenario, density, hier, seed, reqs):
    n_ranks, n_channels, n_subarrays, n_bank_groups = hier
    T = _timing(density, *hier)
    spec = SweepSpec(policies=(policy, "ideal"), scenarios=(scenario,),
                     densities=(density,), reqs=reqs, seed=seed,
                     mode="closed", n_ranks=n_ranks,
                     n_channels=n_channels, n_subarrays=n_subarrays,
                     n_bank_groups=n_bank_groups, timing={density: T})
    dev = sweep(spec, "jax")
    batched = sweep(spec, "batched", record_commands=True)
    _cells_equal(dev, batched, f"jax/batched {policy}/{scenario}")

    wl = make_closed_workload(scenario, reqs, seed)
    d_ideal = dev.get("ideal", scenario, density)
    b_ideal = batched.get("ideal", scenario, density)
    for p in (policy, "ideal"):
        cell = dev.get(p, scenario, density)
        assert cell.finished, (p, scenario, density, hier, seed)
        sim = DramSim(T, wl, p).run_ticks(record_commands=True)
        _assert_cell_equals_sim(cell, sim)
        # the paper's metric, derived identically on both backends
        assert (cell.weighted_speedup_vs(d_ideal)
                == batched.get(p, scenario, density)
                .weighted_speedup_vs(b_ideal)), p
        # emitted command traces: batched sweep == per-cell sim
        tr = batched.commands_for(p, scenario, density)
        assert tr.cmds == sim.commands.cmds, (
            p, scenario, density, hier, seed,
            f"{len(tr.cmds)} vs {len(sim.commands.cmds)} cmds")


def _check_open_case(policy, scenario, density, n_ranks, seed, reqs):
    spec = SweepSpec(policies=(policy, "ideal"), scenarios=(scenario,),
                     densities=(density,), reqs=reqs, seed=seed,
                     n_ranks=n_ranks)
    dev = sweep(spec, "jax")
    batched = sweep(spec, "batched")
    _cells_equal(dev, batched, f"jax/batched {policy}/{scenario}")
    cell = dev.get(policy, scenario, density)
    ideal = dev.get("ideal", scenario, density)
    assert cell.latency_speedup_vs(ideal) == (
        batched.get(policy, scenario, density)
        .latency_speedup_vs(batched.get("ideal", scenario, density)))


# ------------------------------------------------------------ properties
@pytest.mark.parametrize("case", range(N_CASES))
def test_fuzz_closed_jax_equals_batched_equals_run_ticks(case):
    """Random closed-loop sweep points: jax == batched numpy ==
    `DramSim.run_ticks`, stats + weighted speedup + command traces."""
    rng = random.Random(case)
    _check_closed_case(policy=rng.choice(POLICIES),
                       scenario=rng.choice(CLOSED_SCENARIOS),
                       density=rng.choice(DENSITIES),
                       hier=HIERARCHIES[case % len(HIERARCHIES)],
                       seed=rng.randrange(2 ** 31),
                       reqs=rng.choice((24, 40)))


@pytest.mark.parametrize("case", range(N_CASES))
def test_fuzz_open_jax_equals_batched(case):
    """Random open-loop sweep points: jax == batched numpy on every
    CellResult field and the open-loop latency-speedup metric."""
    rng = random.Random(case)
    _check_open_case(policy=rng.choice(POLICIES),
                     scenario=rng.choice(OPEN_SCENARIOS),
                     density=rng.choice(DENSITIES),
                     n_ranks=OPEN_RANKS[case % len(OPEN_RANKS)],
                     seed=rng.randrange(2 ** 31), reqs=40)


@pytest.mark.parametrize("case", range(N_CASES))
def test_fuzz_lpddr4_rules_all_backends_agree(case):
    """Random LPDDR4 sweep points under the per-bank issue rules: closed,
    jax == batched == `DramSim.run_ticks` (stats and command traces, which
    validate clean); open, jax == batched == scalar."""
    rng = random.Random(10_000 + case)
    n_ranks, n_channels, S = LP_LAYOUTS[case % len(LP_LAYOUTS)]
    sp, rounds = LP_RULES[(case + case // 3) % len(LP_RULES)]
    policy = rng.choice(POLICIES)
    density = rng.choice(DENSITIES)
    seed, reqs = rng.randrange(2 ** 31), rng.choice((40, 64))
    # tREFI cut to a quarter, so that a short run refreshes every bank
    T = lpddr4(density, n_ranks=n_ranks, n_channels=n_channels,
               n_subarrays=S, tREFI=976.0, tpbR2pbR=sp, refpb_rounds=rounds)
    lay = dict(densities=(density,), reqs=reqs, seed=seed, dt_ns=5.0,
               n_ranks=n_ranks, n_channels=n_channels, n_subarrays=S,
               timing={density: T})
    scenario = rng.choice(CLOSED_SCENARIOS)
    spec = SweepSpec(policies=(policy, "ideal"), scenarios=(scenario,),
                     mode="closed", **lay)
    dev = sweep(spec, "jax")
    batched = sweep(spec, "batched", record_commands=True)
    _cells_equal(dev, batched, f"lpddr4 jax/batched {policy}/{scenario}")
    wl = make_closed_workload(scenario, reqs, seed)
    for p in (policy, "ideal"):
        cell = dev.get(p, scenario, density)
        assert cell.finished and cell.max_abs_lag <= T.refresh_budget, p
        sim = DramSim(T, wl, p).run_ticks(dt_ns=5.0, record_commands=True)
        _assert_cell_equals_sim(cell, sim)
        tr = batched.commands_for(p, scenario, density)
        assert tr.cmds == sim.commands.cmds, (p, scenario, density, case)
        assert validate_trace(tr) == [], (p, scenario, density, case)
    scenario = rng.choice(OPEN_SCENARIOS)
    spec = SweepSpec(policies=(policy, "ideal"), scenarios=(scenario,),
                     **lay)
    batched = sweep(spec, "batched")
    _cells_equal(sweep(spec, "jax"), batched, f"lpddr4 open {policy}")
    _cells_equal(sweep(spec, "scalar"), batched, f"lpddr4 open {policy}")


# -------------------------------------------------------- golden replays
def _fixture_cases():
    return sorted(FIXTURES.glob("*.json"))


@pytest.mark.parametrize("path", _fixture_cases(),
                         ids=lambda p: p.stem)
def test_golden_fixture_cases_stay_bit_identical(path):
    """Replay the pinned edge cases (a single-cell grid, a density-mixed
    open grid, the full closed hierarchy)."""
    case = json.loads(path.read_text())
    lay = {k: case.get(k, 1) for k in ("n_ranks", "n_channels",
                                        "n_subarrays")}
    # an LPDDR4 case states the values it changes from `conftest.lpddr4`
    dram = ({} if "lpddr4" not in case else dict(
        dt_ns=5.0, timing={d: lpddr4(d, **lay, **case["lpddr4"])
                           for d in case["densities"]}))
    spec = SweepSpec(policies=tuple(case["policies"]),
                     scenarios=tuple(case["scenarios"]),
                     densities=tuple(case["densities"]),
                     reqs=case["reqs"], seed=case["seed"],
                     mode=case["mode"], **lay, **dram)
    closed = case["mode"] == "closed"
    batched = sweep(spec, "batched", record_commands=closed)
    if closed:
        assert len(batched.commands) == len(batched.cells)
    _cells_equal(sweep(spec, "jax"), batched, path.stem)


def test_fixture_corpus_is_nonempty():
    assert len(_fixture_cases()) >= 3, (
        "the sweep fuzz golden corpus must keep its pinned cases; add "
        "counterexamples, never delete them")
