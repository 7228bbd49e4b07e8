"""Cross-engine differential conformance: the closed-loop sweep backends
(batched numpy, jitted jax, scalar oracle) vs `DramSim` run
tick-for-tick (`DramSim.run_ticks`) over every registered policy, the
closed scenario library, and all three densities.

Two independent implementations of the closed-loop tick contract exist on
purpose — the stacked-array sweep backends and the per-request
`DramSim.run_ticks` loop (which routes its lag accounting through the
shared `MaintenanceLedger`). Agreement is asserted **bit-identically**:
the state is all-integer and the derived-stat formulas are shared, so any
mismatch is a real contract violation, not float drift.

The one legitimate divergence — the event-heap float mode `DramSim.run()`
vs the tick contract (bus serialization point, FR-FCFS reordering within
a bank, asymmetric turnaround, quantization) — is *named and asserted* in
`test_event_mode_diverges_from_tick_contract_by_design`.

The normative statement of the contract both implementations follow —
state planes, issue order, refresh-debt accounting, and the
[channel, rank, bank] hierarchy — is docs/tick-contract.md. This module
pins the flat (single-rank) grid; `tests/test_multirank.py` runs the
same differential harness at n_ranks in {2, 4} and n_channels=2, and
`test_multirank_smoke_two_ranks` below keeps a compact rank-2 cross-check
inside the CI conformance job.
"""
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # deterministic fallback; see _hypothesis_shim
    from _hypothesis_shim import given, settings, strategies as st

from repro.core.policy import list_policies
from repro.core.refresh import DramSim, make_closed_workload
from repro.core.refresh.scenarios import list_closed_scenarios
from repro.core.refresh.timing import timing_for_density
from repro.core.sweep import CellResult, SweepSpec, sweep

DENSITIES = (8, 16, 32)
SCENARIOS = ("closed_mixed", "closed_read_heavy", "closed_write_heavy",
             "closed_low_mlp")
GRID_REQS, GRID_SEED = 96, 2


def _sim_ticks(policy: str, scenario: str, density: int, reqs: int,
               seed: int):
    wl = make_closed_workload(scenario, reqs, seed)
    return DramSim(timing_for_density(density), wl, policy).run_ticks()


def _assert_cell_equals_sim(cell, sim):
    """Every stat the two result types share must be bit-identical."""
    pairs = [
        ("makespan", cell.makespan, sim.makespan),
        ("reads_done", cell.reads_done, sim.reads_done),
        ("writes_done", cell.writes_done, sim.writes_done),
        ("avg_read_latency", cell.avg_read_latency, sim.avg_read_latency),
        ("p99_read_latency", cell.p99_read_latency, sim.p99_read_latency),
        ("refreshes_pb", cell.refreshes_pb, sim.refreshes_pb),
        ("refreshes_ab", cell.refreshes_ab, sim.refreshes_ab),
        ("row_hits", cell.row_hits, sim.row_hits),
        ("row_misses", cell.row_misses, sim.row_misses),
        ("energy", cell.energy, sim.energy),
        ("max_abs_lag", cell.max_abs_lag, sim.max_abs_lag),
        ("core_finish", list(cell.core_finish), list(sim.core_finish)),
    ]
    bad = [(n, a, b) for n, a, b in pairs if a != b]
    assert not bad, (cell.policy, cell.scenario, cell.density_gb, bad)


def _cells_equal(a, b, ctx=""):
    bad = [(x.policy, x.scenario, x.density_gb, f)
           for x, y in zip(a.cells, b.cells) if x != y
           for f in CellResult.__dataclass_fields__
           if getattr(x, f) != getattr(y, f)]
    assert not bad, f"{ctx} backends diverged: {bad[:8]}"


# ------------------------------------------------------ the full harness
@pytest.fixture(scope="module")
def grid_spec():
    return SweepSpec(policies=tuple(list_policies()), scenarios=SCENARIOS,
                     densities=DENSITIES, reqs=GRID_REQS, seed=GRID_SEED,
                     mode="closed")


@pytest.fixture(scope="module")
def grid_batched(grid_spec):
    return sweep(grid_spec, "batched")


def test_scenario_library_has_enough_closed_scenarios():
    names = list_closed_scenarios()
    assert len(names) >= 4
    for s in SCENARIOS:
        assert s in names, s


def test_closed_batched_matches_dramsim_ticks_full_grid(grid_spec,
                                                        grid_batched):
    """ALL registered policies x 4 closed scenarios x 3 densities:
    the batched grid is bit-identical to looping `DramSim.run_ticks`."""
    for p in grid_spec.policies:
        for s in SCENARIOS:
            for d in DENSITIES:
                cell = grid_batched.get(p, s, d)
                assert cell.finished, (p, s, d)
                _assert_cell_equals_sim(
                    cell, _sim_ticks(p, s, d, GRID_REQS, GRID_SEED))


def test_closed_jax_backend_matches_batched(grid_spec, grid_batched):
    _cells_equal(sweep(grid_spec, "jax"), grid_batched, "jax/batched")


def test_closed_scalar_oracle_matches_batched(grid_spec, grid_batched):
    _cells_equal(sweep(grid_spec, "scalar"), grid_batched,
                 "scalar/batched")


# --------------------------------------- non-trivial acceptance scenario
def test_all_policies_nontrivial_scenario_bit_identical():
    """Acceptance: every policy in `list_policies()` on a scenario long
    enough that refreshes, write drains, and MLP stalls all occur — stats
    bit-identical to `DramSim` tick-for-tick, and the run is provably
    non-trivial (refreshes issued, weighted speedup defined)."""
    reqs, seed, d = 400, 3, 32
    pols = tuple(list_policies())
    res = sweep(SweepSpec(policies=pols, scenarios=("closed_mixed",),
                          densities=(d,), reqs=reqs, seed=seed,
                          mode="closed"), "batched")
    ideal = res.get("ideal", "closed_mixed", d)
    some_refreshed = 0
    for p in pols:
        cell = res.get(p, "closed_mixed", d)
        assert cell.finished, p
        _assert_cell_equals_sim(cell,
                                _sim_ticks(p, "closed_mixed", d, reqs, seed))
        ws = cell.weighted_speedup_vs(ideal)
        assert 0.2 < ws < 2.0, (p, ws)
        assert cell.max_abs_lag <= 8, (p, cell.max_abs_lag)
        some_refreshed += cell.refreshes_pb + cell.refreshes_ab
    assert some_refreshed > 0


# --------------------------------------------------- hypothesis seeding
@settings(max_examples=8, deadline=None)
@given(seed=st.integers(0, 2 ** 31 - 1),
       scenario=st.sampled_from(SCENARIOS),
       density=st.sampled_from(DENSITIES))
def test_random_seeds_stay_bit_identical(seed, scenario, density):
    """Arbitrary (seed, scenario, density): batched closed sweep ==
    `DramSim.run_ticks`, per cell, bit for bit."""
    reqs = 64
    pols = ("ref_ab", "ref_pb", "darp", "dsarp", "hira")
    res = sweep(SweepSpec(policies=pols, scenarios=(scenario,),
                          densities=(density,), reqs=reqs, seed=seed,
                          mode="closed"), "batched")
    for p in pols:
        _assert_cell_equals_sim(res.get(p, scenario, density),
                                _sim_ticks(p, scenario, density, reqs,
                                           seed))


# ------------------------------------------------------- multirank smoke
def test_multirank_smoke_two_ranks():
    """Compact rank-2 conformance: all three backends bit-identical to
    `DramSim.run_ticks` on the
    closed_multirank scenario (the full rank/channel matrix lives in
    tests/test_multirank.py)."""
    pols = ("ideal", "ref_ab", "dsarp", "staggered_ab", "rank_aware_darp")
    spec = SweepSpec(policies=pols, scenarios=("closed_multirank",),
                     densities=(32,), reqs=GRID_REQS, seed=GRID_SEED,
                     mode="closed", n_ranks=2)
    batched = sweep(spec, "batched")
    _cells_equal(sweep(spec, "scalar"), batched, "scalar/batched R=2")
    _cells_equal(sweep(spec, "jax"), batched, "jax/batched R=2")
    wl = make_closed_workload("closed_multirank", GRID_REQS, GRID_SEED)
    T = timing_for_density(32, n_ranks=2)
    for p in pols:
        cell = batched.get(p, "closed_multirank", 32)
        assert cell.finished, p
        _assert_cell_equals_sim(cell, DramSim(T, wl, p).run_ticks())


# ------------------------------------------------ named, asserted gaps
def test_event_mode_diverges_from_tick_contract_by_design():
    """The event-heap float mode (`DramSim.run`) is NOT the tick contract:
    it models a separate bus serialization point, FR-FCFS reordering
    within a bank, and asymmetric read/write turnaround. The divergence is
    expected — assert it exists so nobody 'fixes' one side to silently
    track the other."""
    wl = make_closed_workload("closed_mixed", 200, 0)
    sim = DramSim(timing_for_density(32), wl, "dsarp")
    ticked = sim.run_ticks()
    event = sim.run()
    assert ticked.reads_done == event.reads_done          # same demand...
    assert ticked.makespan != event.makespan              # ...different clock
    # both clocks must still be sane (positive, finite, right order of
    # magnitude): within 2x of each other on this workload
    ratio = ticked.makespan / event.makespan
    assert 0.5 < ratio < 2.0, ratio


def test_open_loop_cell_refuses_weighted_speedup():
    """The PR-2 caveat, now enforced: open-loop cells raise when asked for
    the paper's closed-loop metric instead of silently returning a
    makespan ratio (docs/figures.md)."""
    res = sweep(SweepSpec(policies=("ideal", "ref_pb"),
                          scenarios=("mixed",), densities=(32,), reqs=60,
                          seed=0))
    cell = res.get("ref_pb", "mixed", 32)
    ideal = res.get("ideal", "mixed", 32)
    with pytest.raises(ValueError, match="closed-loop metric"):
        cell.weighted_speedup_vs(ideal)
    with pytest.raises(ValueError, match="closed-loop metric"):
        cell.per_core_slowdown_vs(ideal)
    assert cell.latency_speedup_vs(ideal) <= 1.01         # still available


def test_closed_cells_expose_per_core_slowdown():
    spec = SweepSpec(policies=("ideal", "ref_ab"),
                     scenarios=("closed_low_mlp",), densities=(32,),
                     reqs=400, seed=1, mode="closed")
    res = sweep(spec, "batched")
    cell = res.get("ref_ab", "closed_low_mlp", 32)
    ideal = res.get("ideal", "closed_low_mlp", 32)
    slow = cell.per_core_slowdown_vs(ideal)
    assert len(slow) == len(cell.core_finish) > 0
    assert all(s > 0 for s in slow)
    # stop-the-world refresh can't beat no-refresh on average
    assert cell.weighted_speedup_vs(ideal) <= 1.0 + 1e-9
