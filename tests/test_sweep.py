"""Sweep engine + scenario library: determinism, batched-vs-scalar
bit-identity for every registered policy, the jax fast path, and the
grid-vs-loop speed smoke."""
import dataclasses
import time

import numpy as np
import pytest

from repro.core.policy import PolicyBase, list_policies, register_policy
from repro.core.policy.registry import _REGISTRY
from repro.core.refresh import DramSim, make_closed_workload
from repro.core.refresh.scenarios import (Trace, list_scenarios, make_trace,
                                          register_scenario)
from repro.core.refresh.timing import timing_for_density
from repro.core.sweep import CellResult, SweepSpec, sweep

SMALL = dict(densities=(32,), reqs=120, seed=3)
BUILTIN_SCENARIOS = ("read_heavy", "write_burst_draining",
                     "row_buffer_friendly", "bank_camping",
                     "subarray_conflict_adversarial", "trace_replay",
                     "mixed", "streaming")


def _cells_equal(a, b):
    bad = [(x.policy, x.scenario, x.density_gb, f)
           for x, y in zip(a.cells, b.cells) if x != y
           for f in CellResult.__dataclass_fields__
           if getattr(x, f) != getattr(y, f)]
    assert not bad, f"backends diverged: {bad[:8]}"


# ------------------------------------------------------- scenario library
def test_scenario_registry_lists_builtins():
    names = list_scenarios()
    for s in BUILTIN_SCENARIOS:
        assert s in names, s


def test_unknown_scenario_error_lists_known_names():
    with pytest.raises(KeyError, match="unknown scenario"):
        make_trace("nope_not_a_scenario")
    with pytest.raises(KeyError, match="read_heavy"):
        make_trace("nope_not_a_scenario")


@pytest.mark.parametrize("name", BUILTIN_SCENARIOS)
def test_scenario_deterministic_under_fixed_seed(name):
    a = make_trace(name, reqs=300, seed=7)
    b = make_trace(name, reqs=300, seed=7)
    for f in ("arrive", "bank", "row", "sub", "is_write"):
        np.testing.assert_array_equal(getattr(a, f), getattr(b, f))
    # validate() ran inside make_trace; spot-check the invariants anyway
    assert (np.diff(a.arrive) >= 0).all()
    assert a.bank.max() < a.n_banks and a.sub.max() < a.n_subarrays


@pytest.mark.parametrize("name", [s for s in BUILTIN_SCENARIOS
                                  if s != "trace_replay"])
def test_scenario_seed_changes_trace(name):
    a = make_trace(name, reqs=300, seed=1)
    b = make_trace(name, reqs=300, seed=2)
    assert any(not np.array_equal(getattr(a, f), getattr(b, f))
               for f in ("arrive", "bank", "row", "is_write")), name


def test_scenarios_shared_across_grid_axes():
    """One trace per (scenario, seed): every policy/density cell of a
    scenario must see identical workloads (comparability)."""
    res = sweep(SweepSpec(policies=("ideal",), scenarios=("mixed",),
                          densities=(8, 32), reqs=100, seed=0))
    a, b = res.get("ideal", "mixed", 8), res.get("ideal", "mixed", 32)
    assert a.reads_done + a.writes_done == b.reads_done + b.writes_done


def test_trace_replay_accepts_explicit_trace():
    tr = make_trace("trace_replay", reqs=8, trace=dict(
        arrive=[0, 2, 4, 9], bank=[0, 1, 0, 1], row=[5, 6, 5, 6],
        is_write=[False, True, False, False]))
    assert isinstance(tr, Trace) and len(tr) == 4
    assert list(tr.sub) == [r % 8 for r in (5, 6, 5, 6)]


# -------------------------------------------- batched vs scalar identity
def test_batched_matches_scalar_3x3_grid():
    """The acceptance grid: 3 policies x 3 scenarios, bit-identical."""
    spec = SweepSpec(policies=("ref_pb", "darp", "dsarp"),
                     scenarios=("read_heavy", "bank_camping",
                                "write_burst_draining"), **SMALL)
    _cells_equal(sweep(spec, "batched"), sweep(spec, "scalar"))


def test_batched_matches_scalar_all_registered_policies():
    """Every registered policy (paper family, aliases, extras) must give
    bit-identical stats through the vectorized path and the real
    per-policy select()."""
    spec = SweepSpec(policies=tuple(list_policies()),
                     scenarios=("mixed", "write_burst_draining"), **SMALL)
    _cells_equal(sweep(spec, "batched"), sweep(spec, "scalar"))


def test_custom_policy_falls_back_and_stays_identical():
    @register_policy("_test_sweep_greedy")
    class _Greedy(PolicyBase):
        def select(self, view):
            from repro.core.policy import Decision
            lag = list(view.lag)
            picks = []
            self._forced(view, lag, picks)
            owed = sorted((b for b in range(view.n_banks)
                           if view.ready[b] and lag[b] > 0),
                          key=lambda b: -lag[b])
            for b in owed[:max(0, view.max_issues - len(picks))]:
                picks.append(Decision(b))
            return picks
    try:
        spec = SweepSpec(policies=("_test_sweep_greedy", "darp"),
                         scenarios=("mixed",), **SMALL)
        rb, rs = sweep(spec, "batched"), sweep(spec, "scalar")
        _cells_equal(rb, rs)
        assert rb.get("_test_sweep_greedy", "mixed", 32).refreshes_pb > 0
    finally:
        del _REGISTRY["_test_sweep_greedy"]


def test_budget_invariant_across_grid():
    spec = SweepSpec(policies=("ref_pb", "darp", "dsarp", "elastic",
                               "hira"),
                     scenarios=("streaming", "bank_camping"), **SMALL)
    for cell in sweep(spec):
        assert cell.finished, (cell.policy, cell.scenario)
        assert cell.max_abs_lag <= 8, (cell.policy, cell.scenario,
                                       cell.max_abs_lag)
        assert cell.refreshes_pb > 0, (cell.policy, cell.scenario)


def test_sweep_result_indexing():
    spec = SweepSpec(policies=("ideal", "ref_pb"),
                     scenarios=("mixed", "read_heavy"),
                     densities=(8, 32), reqs=80, seed=1)
    res = sweep(spec)
    assert res.stat("reads_done").shape == (2, 2, 2)
    cell = res.get("ref_pb", "read_heavy", 32)
    assert cell.policy == "ref_pb" and cell.density_gb == 32
    assert res.get("ideal", "mixed", 8).refreshes_pb == 0


def test_sarp_orderings_on_adversarial_scenario():
    """SARP pays on conflict-free traffic and loses its edge when accesses
    chase the refreshing subarray."""
    spec = SweepSpec(policies=("ref_pb", "sarp_pb"),
                     scenarios=("read_heavy",
                                "subarray_conflict_adversarial"),
                     densities=(32,), reqs=400, seed=0)
    res = sweep(spec)
    friendly = (res.get("sarp_pb", "read_heavy", 32).avg_read_latency
                / res.get("ref_pb", "read_heavy", 32).avg_read_latency)
    adv = (res.get("sarp_pb", "subarray_conflict_adversarial", 32)
           .avg_read_latency
           / res.get("ref_pb", "subarray_conflict_adversarial", 32)
           .avg_read_latency)
    assert friendly <= 1.01          # SARP never much worse when friendly
    assert adv >= friendly - 0.02    # adversarial erodes the advantage


# --------------------------------------------------------------- jax path
def test_jax_backend_bit_identical():
    spec = SweepSpec(policies=("ref_ab", "ref_pb", "darp", "dsarp",
                               "elastic", "hira", "ideal"),
                     scenarios=("mixed", "write_burst_draining"), **SMALL)
    _cells_equal(sweep(spec, "jax"), sweep(spec, "scalar"))


def test_jax_backend_rejects_custom_policies():
    @register_policy("_test_sweep_nojit")
    class _NoJit(PolicyBase):
        def select(self, view):
            return []
    try:
        spec = SweepSpec(policies=("_test_sweep_nojit",),
                         scenarios=("mixed",), **SMALL)
        with pytest.raises(ValueError, match="backend='batched'"):
            sweep(spec, "jax")
    finally:
        del _REGISTRY["_test_sweep_nojit"]


@pytest.mark.parametrize("backend,kw,error", [
    ("mega", {}, ValueError),
    ("jax", {"arbiter": "pallas"}, TypeError),
])
def test_sweep_rejects_removed_options(backend, kw, error):
    """`sweep` has one device path: a backend or keyword outside its
    API is refused, not silently mapped to another path."""
    spec = SweepSpec(policies=("darp",), scenarios=("mixed",), **SMALL)
    with pytest.raises(error):
        sweep(spec, backend, **kw)


def test_jax_scoring_is_one_cached_jnp_callable():
    """The traced body's scoring is the jnp definition only, and one
    object per name, so `run_loop`'s static argument reuses its
    compiled loop."""
    from repro.core.sweep.engine import _jax_arbiter
    assert _jax_arbiter("jnp") is _jax_arbiter("jnp")
    with pytest.raises(ValueError, match="pallas"):
        _jax_arbiter("pallas")


def test_empty_axis_spec_rejected_with_clear_error():
    with pytest.raises(ValueError, match="at least one policy"):
        sweep(SweepSpec(policies=("darp",), scenarios=()))
    with pytest.raises(ValueError, match="at least one policy"):
        sweep(SweepSpec(policies=(), scenarios=("mixed",)))


def test_masked_scores_match_shared():
    """The batched backend's mask-based fast scoring must stay in
    lock-step with the shared `arbiter_scores` definition."""
    from repro.core.sweep.arbiter import arbiter_scores, arbiter_scores_masked

    rs = np.random.RandomState(23)
    G, B = 64, 8
    for t in (0, 311, 5000):
        kw = dict(
            has_req=rs.rand(G, B) < 0.7,
            head_row=rs.randint(0, 4096, (G, B)).astype(np.int32),
            head_arrive=rs.randint(0, max(1, t + 1), (G, B)).astype(np.int32),
            head_is_write=rs.rand(G, B) < 0.3,
            bank_free=rs.randint(0, 700, (G, B)).astype(np.int32),
            # the head subarray's refresh-end tick + the bank-level
            # any-subarray-mid-refresh plane (gathered by the engine)
            head_ref_until=rs.randint(0, 700, (G, B)).astype(np.int32),
            bank_mid_ref=rs.rand(G, B) < 0.3,
            open_row=rs.randint(-1, 4096, (G, B)).astype(np.int32),
            drain=rs.rand(G) < 0.4,
            # per-bank rank-drain plane (each bank carries its rank's flag)
            rank_drain=np.repeat(rs.rand(G, 2) < 0.1, B // 2, axis=1),
            occ=rs.randint(0, 20, (G, B)).astype(np.int32),
        )
        expect = arbiter_scores(np, t, **kw)
        got = arbiter_scores_masked(
            t, has_req=kw["has_req"], idle=kw["bank_free"] <= t,
            head_ready=kw["head_ref_until"] <= t,
            bank_mid_ref=kw["bank_mid_ref"], head_row=kw["head_row"],
            head_arrive=kw["head_arrive"],
            head_is_write=kw["head_is_write"],
            open_row=kw["open_row"], drain=kw["drain"],
            rank_drain=np.asarray(kw["rank_drain"]),
            rank_can_drain=True, occ=kw["occ"])
        np.testing.assert_array_equal(np.asarray(got, np.int64),
                                      np.asarray(expect, np.int64), str(t))


# ------------------------------------------------------------ speed smoke
@pytest.mark.slow
def test_batched_grid_beats_scalar_loop():
    """Wall-clock smoke at a reduced grid; the full 8x8x3 acceptance
    numbers live in benchmarks/run.py -> results/bench/sweep_grid.json
    (batched is ~3x the tick oracle and >10x the legacy DramSim loop
    there). Threshold kept loose for CI noise."""
    spec = SweepSpec(policies=("ideal", "ref_ab", "ref_pb", "darp",
                               "darp_ooo", "sarp_pb", "dsarp", "elastic"),
                     scenarios=("read_heavy", "write_burst_draining",
                                "bank_camping", "streaming"),
                     densities=(8, 32), reqs=150, seed=0)
    t0 = time.perf_counter()
    rb = sweep(spec, "batched")
    t_b = time.perf_counter() - t0
    t0 = time.perf_counter()
    rs = sweep(spec, "scalar")
    t_s = time.perf_counter() - t0
    _cells_equal(rb, rs)
    assert t_b < t_s, (t_b, t_s)


# -------------------------------------------------- the spec's own DRAM
def _timed(spec, **timing_ns):
    """`spec` with its DRAM given as `SweepSpec.timing`: the program's
    table at each density, with `timing_ns` replaced."""
    lay = dict(n_banks=spec.n_banks, n_subarrays=spec.n_subarrays,
               n_ranks=spec.n_ranks, n_channels=spec.n_channels)
    return dataclasses.replace(spec, timing={
        d: dataclasses.replace(timing_for_density(d, **lay), **timing_ns)
        for d in spec.densities})


TIMED = {
    "open": SweepSpec(policies=("ref_ab", "ref_pb", "dsarp", "hira"),
                      scenarios=("mixed", "write_burst_draining"),
                      densities=(8, 32), reqs=160, seed=4),
    "closed": SweepSpec(policies=("ref_ab", "ref_pb", "darp", "dsarp",
                                  "staggered_ab"),
                        scenarios=("closed_multirank",), densities=(8, 32),
                        reqs=160, seed=4, mode="closed", n_ranks=2),
}


@pytest.mark.parametrize("backend", ["batched", "jax"])
@pytest.mark.parametrize("mode", sorted(TIMED))
def test_timing_equal_to_the_table_is_no_timing(mode, backend):
    spec = TIMED[mode]
    _cells_equal(sweep(_timed(spec), backend), sweep(spec, backend))


@pytest.mark.parametrize("mode", sorted(TIMED))
def test_other_timing_agrees_across_backends_and_with_run_ticks(mode):
    """Halved tREFI and other tRFC: every backend simulates the spec's
    DRAM, not the table's, and the closed grid equals
    `DramSim.run_ticks` on that DRAM cell for cell."""
    spec = _timed(TIMED[mode], tREFI=3906.25, tRFC_ab=260.0, tRFC_pb=110.0)
    batched = sweep(spec, "batched")
    _cells_equal(sweep(spec, "scalar"), batched)
    _cells_equal(sweep(spec, "jax"), batched)
    table = sweep(dataclasses.replace(spec, timing=None), "batched")
    assert any(a != b for a, b in zip(batched.cells, table.cells))
    if mode == "closed":
        for (p, s, d), cell in zip(spec.cells(), batched.cells):
            sim = DramSim(spec.timing[d], make_closed_workload(
                s, spec.reqs, spec.seed), p).run_ticks()
            assert (cell.makespan, cell.energy, cell.refreshes_pb,
                    cell.refreshes_ab, cell.max_abs_lag,
                    list(cell.core_finish)) == (
                sim.makespan, sim.energy, sim.refreshes_pb,
                sim.refreshes_ab, sim.max_abs_lag, list(sim.core_finish))


def test_timing_must_be_the_specs_dram():
    spec = TIMED["open"]
    with pytest.raises(ValueError, match="no 8 Gb"):
        dataclasses.replace(spec, timing={32: timing_for_density(32)})
    with pytest.raises(ValueError, match="not the spec's DRAM"):
        dataclasses.replace(spec, timing={
            d: timing_for_density(d, n_ranks=2) for d in (8, 32)})
    with pytest.raises(TypeError, match="tCCD_L"):
        SweepSpec(policies=("darp",), scenarios=("mixed",),
                  n_bank_groups=2)
