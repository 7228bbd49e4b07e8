"""The [channel, rank, bank] hierarchy: multirank conformance across all
sweep backends vs `DramSim.run_ticks`, the two hierarchy-only registry
policies (`staggered_ab`, `rank_aware_darp`), and the n_ranks=1
no-regression guarantees (flat grids bit-identical to the pre-hierarchy
engine's behavior; `rank_aware_darp` degrades to `darp` exactly).

The spec these tests enforce is docs/tick-contract.md; the flat-grid
harness lives in tests/test_conformance.py.
"""
import numpy as np
import pytest
from conftest import lpddr4

from repro.core.policy import (ALL_BANKS, Decision, MaintenanceView,
                               get_policy, list_policies, resolve_policy)
from repro.core.refresh import DramSim, make_closed_workload
from repro.core.refresh.timing import timing_for_density
from repro.core.sweep import CellResult, SweepSpec, sweep
from repro.core.sweep.engine import _Grid

REQS, SEED, DENSITY = 96, 2, 32
#: policy axis for the multirank grids: the paper family's representatives
#: plus both hierarchy policies and both post-paper extras
POLICIES = ("ideal", "ref_ab", "ref_pb", "darp", "dsarp", "elastic",
            "hira", "staggered_ab", "rank_aware_darp")


def _cells_equal(a, b, ctx=""):
    bad = [(x.policy, x.scenario, x.density_gb, f)
           for x, y in zip(a.cells, b.cells) if x != y
           for f in CellResult.__dataclass_fields__
           if getattr(x, f) != getattr(y, f)]
    assert not bad, f"{ctx} backends diverged: {bad[:8]}"


def _assert_cell_equals_sim(cell, sim):
    pairs = [(f, getattr(cell, f), getattr(sim, f)) for f in
             ("makespan", "reads_done", "writes_done", "avg_read_latency",
              "p99_read_latency", "refreshes_pb", "refreshes_ab",
              "row_hits", "row_misses", "energy", "max_abs_lag")]
    pairs.append(("core_finish", list(cell.core_finish),
                  list(sim.core_finish)))
    bad = [(n, a, b) for n, a, b in pairs if a != b]
    assert not bad, (cell.policy, cell.scenario, cell.density_gb, bad)


def _spec(n_ranks, n_channels=1, policies=POLICIES,
          scenario="closed_multirank"):
    return SweepSpec(policies=policies, scenarios=(scenario,),
                     densities=(DENSITY,), reqs=REQS, seed=SEED,
                     mode="closed", n_ranks=n_ranks, n_channels=n_channels)


# --------------------------------------------- multirank conformance grid
@pytest.mark.parametrize("n_ranks,n_channels", [(2, 1), (4, 1), (2, 2)])
def test_multirank_all_backends_bit_identical_to_run_ticks(n_ranks,
                                                           n_channels):
    """Every backend (batched numpy, jitted jax, scalar oracle) stays
    bit-identical to `DramSim.run_ticks` at every rank/channel count, for
    every policy on the multirank axis."""
    spec = _spec(n_ranks, n_channels)
    batched = sweep(spec, "batched")
    _cells_equal(sweep(spec, "scalar"), batched,
                 f"scalar/batched R={n_ranks} C={n_channels}")
    _cells_equal(sweep(spec, "jax"), batched,
                 f"jax/batched R={n_ranks} C={n_channels}")
    wl = make_closed_workload("closed_multirank", REQS, SEED)
    T = timing_for_density(DENSITY, n_ranks=n_ranks, n_channels=n_channels)
    for p in POLICIES:
        cell = batched.get(p, "closed_multirank", DENSITY)
        assert cell.finished, (p, n_ranks, n_channels)
        _assert_cell_equals_sim(cell, DramSim(T, wl, p).run_ticks())


def test_multirank_wrapped_ring_queues_bit_identical_to_run_ticks():
    """Two channels x two ranks, closed, long enough that some bank's ring
    queue takes more requests than it has slots, so its head slot wraps
    past the end of the ring: every backend stays bit-identical to
    `DramSim.run_ticks`."""
    reqs = 3600
    spec = SweepSpec(policies=POLICIES, scenarios=("closed_multirank",),
                     densities=(DENSITY,), reqs=reqs, seed=SEED,
                     mode="closed", n_ranks=2, n_channels=2)
    grid = _Grid(spec)
    per_bank = np.zeros((grid.G, grid.B), np.int64)
    for g, c in np.ndindex(grid.G, grid.C):
        np.add.at(per_bank[g], grid.s_bank[g, c, :grid.n_req_c[g, c]], 1)
    assert per_bank.max() > grid.LQ, (per_bank.max(), grid.LQ)
    batched = sweep(spec, "batched")
    for backend in ("scalar", "jax"):
        _cells_equal(sweep(spec, backend), batched,
                     f"{backend}/batched wrapped rings")
    wl = make_closed_workload("closed_multirank", reqs, SEED)
    T = timing_for_density(DENSITY, n_ranks=2, n_channels=2)
    for p in POLICIES:
        cell = batched.get(p, "closed_multirank", DENSITY)
        assert cell.finished, p
        _assert_cell_equals_sim(cell, DramSim(T, wl, p).run_ticks())


def test_every_registered_policy_conforms_at_two_ranks():
    """The full registry (aliases included) through the batched backend
    vs the scalar oracle at n_ranks=2 — custom select() paths and the
    vectorized paths must agree on the hierarchy too."""
    spec = _spec(2, policies=tuple(list_policies()),
                 scenario="closed_mixed")
    _cells_equal(sweep(spec, "batched"), sweep(spec, "scalar"),
                 "all-policies R=2")


# ------------------------------------------------- n_ranks=1 no-regression
def test_flat_grid_unchanged_by_hierarchy_default():
    """A SweepSpec without rank/channel arguments IS the flat engine:
    n_banks_total == n_banks and the conformance harness in
    tests/test_conformance.py pins its cells to DramSim.run_ticks. Here:
    explicit n_ranks=1, n_channels=1 is the same grid object cell-for-cell."""
    base = SweepSpec(policies=("ref_ab", "dsarp"),
                     scenarios=("closed_mixed",), densities=(DENSITY,),
                     reqs=REQS, seed=SEED, mode="closed")
    explicit = SweepSpec(policies=("ref_ab", "dsarp"),
                         scenarios=("closed_mixed",), densities=(DENSITY,),
                         reqs=REQS, seed=SEED, mode="closed",
                         n_ranks=1, n_channels=1)
    assert base.n_banks_total == base.n_banks == 8
    _cells_equal(sweep(base, "batched"), sweep(explicit, "batched"),
                 "default/explicit-1x1")


def test_rank_aware_darp_degrades_to_darp_at_one_rank():
    """At n_ranks=1 the rank-idle preference is a constant and
    `rank_aware_darp` must be bit-identical to `darp` — every stat, every
    scenario, both modes."""
    for mode, scens in (("closed", ("closed_mixed", "closed_write_heavy")),
                        ("open", ("mixed", "write_burst_draining",
                                  "bank_camping"))):
        spec = SweepSpec(policies=("darp", "rank_aware_darp"),
                         scenarios=scens, densities=(8, DENSITY),
                         reqs=200, seed=5, mode=mode)
        res = sweep(spec, "batched")
        for s in scens:
            for d in (8, DENSITY):
                a = res.get("darp", s, d)
                b = res.get("rank_aware_darp", s, d)
                bad = [f for f in CellResult.__dataclass_fields__
                       if f != "policy" and getattr(a, f) != getattr(b, f)]
                assert not bad, (mode, s, d, bad)


# ----------------------------------------------------- policy unit tests
def test_policy_registry_round_trip_multirank_pair():
    for name, level in (("staggered_ab", "ab"), ("rank_aware_darp", "pb")):
        pol = get_policy(name)
        assert pol.name == name and pol.level == level
        assert resolve_policy(name).select is not None
    a, b = get_policy("staggered_ab"), get_policy("staggered_ab")
    assert a is not b, "factories must return fresh instances (rr state)"


def _ab_view(t, ranks_due, ready, idle, n_ranks=2, n_channels=1):
    R = n_ranks * n_channels
    nb = 2                                   # 2 banks per rank
    B = R * nb
    return MaintenanceView(
        now=float(t), n_banks=B, budget=8, lag=[0] * B, demand=[0] * B,
        ready=list(ready), idle=list(idle), rank_due=sum(ranks_due),
        rank_quiet=all(ready) and all(idle), n_ranks=n_ranks,
        n_channels=n_channels,
        rank_of=tuple(b // nb for b in range(B)),
        channel_of=tuple(b // (n_ranks * nb) for b in range(B)),
        ranks_due=tuple(ranks_due))


def test_staggered_ab_walks_ranks_round_robin():
    pol = get_policy("staggered_ab")
    # both ranks due and quiet: only the pointer's rank starts
    v = _ab_view(0, [1, 1], [True] * 4, [True] * 4)
    decs = pol.select(v)
    assert [(d.bank, d.rank) for d in decs] == [(ALL_BANKS, 0)]
    decs = pol.select(_ab_view(1, [1, 1], [True] * 4, [True] * 4))
    assert [(d.bank, d.rank) for d in decs] == [(ALL_BANKS, 1)]
    # strict round-robin: pointer back at rank 0
    decs = pol.select(_ab_view(2, [1, 1], [True] * 4, [True] * 4))
    assert [(d.bank, d.rank) for d in decs] == [(ALL_BANKS, 0)]


def test_staggered_ab_never_overlaps_on_a_channel():
    """Drive the policy through an engine-shaped loop (2 ranks, 1
    channel): while one rank is mid-REF_ab its banks are not `ready`, so
    the channel is not clear and the policy must NOT start the sibling —
    unlike plain ref_ab, which starts every due+quiet rank at once."""
    RFC = 5
    pol = get_policy("staggered_ab")
    ref_until = [0, 0, 0, 0]
    due = [1, 1]
    in_flight = []                            # (rank, end)
    for t in range(40):
        ready = [ref_until[b] <= t for b in range(4)]
        decs = pol.select(_ab_view(t, due, ready, ready))
        for d in decs:
            assert d.bank == ALL_BANKS
            overlapping = [r for r, end in in_flight if end > t]
            assert not overlapping, \
                f"t={t}: started rank {d.rank} while {overlapping} mid-REFab"
            for b in (2 * d.rank, 2 * d.rank + 1):
                ref_until[b] = t + RFC
            due[d.rank] -= 1
            in_flight.append((d.rank, t + RFC))
        if sum(due) == 0 and all(end <= t for _, end in in_flight):
            break
    assert pol._rr == 2 and due == [0, 0]
    # contrast: plain ref_ab starts BOTH due+quiet ranks the same instant
    both = get_policy("ref_ab").select(
        _ab_view(0, [1, 1], [True] * 4, [True] * 4))
    assert sorted(d.rank for d in both) == [0, 1]


def test_staggered_ab_on_two_channels_allows_parallel_channels():
    """Ranks on DIFFERENT channels may refresh concurrently: with channel
    0's rank mid-refresh, the pointer still starts channel 1's rank."""
    pol = get_policy("staggered_ab")
    # 2 channels x 1 rank: rank 0 = channel 0, rank 1 = channel 1
    v = _ab_view(0, [1, 1], [True] * 4, [True] * 4, n_ranks=1,
                 n_channels=2)
    assert [d.rank for d in pol.select(v)] == [0]
    # rank 0 (channel 0) now mid-refresh: its banks not ready
    ready = [False, False, True, True]
    v = _ab_view(1, [0, 1], ready, ready, n_ranks=1, n_channels=2)
    assert [d.rank for d in pol.select(v)] == [1]


def test_rank_aware_darp_prefers_demand_idle_rank():
    """The most-owed candidate sits on a busy rank; a less-owed candidate
    sits on a demand-idle rank. darp takes the former, rank_aware_darp
    the latter (the refresh steals no bus slot)."""
    def view():
        return MaintenanceView(
            now=10.0, n_banks=8, budget=8,
            lag=[0, 3, 0, 0, 0, 2, 0, 0],
            demand=[4, 0, 0, 0, 0, 0, 0, 0],
            ready=[True] * 8,
            idle=[False] + [True] * 7,
            n_ranks=2, n_channels=1,
            rank_of=(0, 0, 0, 0, 1, 1, 1, 1), channel_of=(0,) * 8)
    assert [d.bank for d in get_policy("darp").select(view())] == [1]
    assert [d.bank for d in
            get_policy("rank_aware_darp").select(view())] == [5]


def test_rank_aware_darp_flat_view_falls_back_to_darp():
    """Generic engines (serving, checkpoint) pass no hierarchy: decisions
    must equal darp's exactly."""
    def view():
        return MaintenanceView(
            now=4.0, n_banks=6, budget=8, lag=[2, 0, 1, 0, 3, 0],
            demand=[0, 1, 0, 2, 0, 0], ready=[True] * 6,
            idle=[True, True, False, True, True, True])
    assert ([d.bank for d in get_policy("rank_aware_darp").select(view())]
            == [d.bank for d in get_policy("darp").select(view())])


# ----------------------------------------------- hierarchy sanity checks
def test_rank_staggering_splits_ab_debt_accrual():
    """At 2 ranks, REF_ab issues twice as many (one per rank per tREFI)
    and per-rank drains overlap demand on the sibling rank: the 2-rank
    makespan must stay well under 2x the 1-rank one."""
    wl = make_closed_workload("closed_low_mlp", 3200, 1)
    r1 = DramSim(timing_for_density(32, n_ranks=1), wl, "ref_ab").run_ticks()
    r2 = DramSim(timing_for_density(32, n_ranks=2), wl, "ref_ab").run_ticks()
    assert r1.refreshes_ab >= 3
    # one refresh per RANK per tREFI: the 2-rank run issues ~2x as many...
    assert r2.refreshes_ab > r1.refreshes_ab
    # ...yet each drain stalls only its own rank, so the makespan does not
    # double — staggering keeps the sibling rank serving
    assert r2.makespan < 1.25 * r1.makespan


def test_timing_hierarchy_indices():
    T = timing_for_density(8, n_banks=4, n_ranks=2, n_channels=2)
    assert T.n_ranks_total == 4 and T.n_banks_total == 16
    assert [T.rank_of(b) for b in (0, 3, 4, 12, 15)] == [0, 0, 1, 3, 3]
    assert [T.channel_of(b) for b in (0, 7, 8, 15)] == [0, 0, 1, 1]
    assert T.tREFI_pb == T.tREFI / 16


def test_energy_proxy_scales_background_with_ranks():
    from repro.core.refresh.sim import energy_proxy
    T1 = timing_for_density(32)
    T2 = timing_for_density(32, n_ranks=2)
    e1 = energy_proxy(T1, 1e6, 100, 50, 30, 10, 2)
    e2 = energy_proxy(T2, 1e6, 100, 50, 30, 10, 2)
    # only the background/standby term differs, by exactly one rank's worth
    assert e2 - e1 == pytest.approx(0.5 * 1e6)


def test_ledger_per_rank_budget_conservation():
    """MaintenanceLedger property, extended per-rank: grouping banks into
    ranks, every rank's aggregate lag stays within n_banks_in_rank *
    budget, and per-rank issue counts balance per-rank due counts within
    the same bound (budget conservation never leaks across ranks)."""
    from repro.core.policy.ledger import MaintenanceLedger
    rs = np.random.RandomState(7)
    NB, R, budget = 4, 3, 4
    B = NB * R
    rank_of = tuple(b // NB for b in range(B))
    led = MaintenanceLedger(B, interval=3.0, budget=budget, stagger=True)
    pol = resolve_policy("rank_aware_darp")
    t = 0.0
    for _ in range(120):
        t += float(rs.rand()) * 3.0
        ready = [bool(rs.rand() < 0.8) or led.lag(b, t) >= budget
                 for b in range(B)]
        view = led.view(t, demand=rs.randint(0, 3, B).tolist(),
                        write_window=bool(rs.rand() < 0.4), ready=ready,
                        idle=(rs.rand(B) < 0.8).tolist(),
                        n_ranks=R, rank_of=rank_of,
                        channel_of=(0,) * B)
        led.apply(pol.select(view), t)
        led.check_invariant(t)                # per-bank +-budget
        for gr in range(R):
            banks = [b for b in range(B) if rank_of[b] == gr]
            rank_lag = sum(led.lag(b, t) for b in banks)
            assert abs(rank_lag) <= NB * budget, (gr, t, rank_lag)
            rank_due = sum(led.due(b, t) for b in banks)
            rank_issued = sum(led.banks[b].issued for b in banks)
            assert abs(rank_due - rank_issued) <= NB * budget


# ------------------------------------------ bank groups, same-bank refresh
def _grouped(n_bank_groups, density=DENSITY, **layout):
    """The program's DRAM with `n_bank_groups` groups per rank, tCCD_L one
    tick above tCCD_S, and tREFI cut to a quarter so that a short run
    owes same-bank refreshes."""
    return timing_for_density(density, n_bank_groups=n_bank_groups,
                              tCCD_L=9.0, tCCD_S=6.0, tREFI=1953.125,
                              **layout)


@pytest.mark.parametrize("n_bank_groups,n_ranks,n_channels",
                         [(2, 2, 1), (4, 2, 2), (4, 1, 2), (8, 2, 1)])
def test_bank_groups_all_backends_bit_identical_to_run_ticks(
        n_bank_groups, n_ranks, n_channels):
    """Same-bank refresh units and the tCCD_L serve term: scalar, batched
    and jax stay bit-identical to `DramSim.run_ticks` at every group
    count, for every registered policy."""
    T = _grouped(n_bank_groups, n_ranks=n_ranks, n_channels=n_channels)
    policies = tuple(list_policies())
    spec = SweepSpec(policies=policies, scenarios=("closed_multirank",),
                     densities=(DENSITY,), reqs=240, seed=SEED,
                     mode="closed", n_ranks=n_ranks, n_channels=n_channels,
                     n_bank_groups=n_bank_groups, timing={DENSITY: T})
    batched = sweep(spec, "batched")
    _cells_equal(sweep(spec, "scalar"), batched, f"scalar G={n_bank_groups}")
    _cells_equal(sweep(spec, "jax"), batched, f"jax G={n_bank_groups}")
    wl = make_closed_workload("closed_multirank", 240, SEED)
    for p in policies:
        cell = batched.get(p, "closed_multirank", DENSITY)
        assert cell.finished, (p, n_bank_groups)
        _assert_cell_equals_sim(cell, DramSim(T, wl, p).run_ticks())
    # every per-bank-level refresh is one command over a whole set
    assert batched.get("ref_pb", "closed_multirank", DENSITY).refreshes_pb


def test_one_bank_group_is_the_flat_engine():
    """n_bank_groups=1 names no new mechanism: a spec and a `DramTiming`
    that say so are the default grid, cell for cell."""
    base = _spec(2, 2)
    explicit = SweepSpec(policies=POLICIES, scenarios=("closed_multirank",),
                         densities=(DENSITY,), reqs=REQS, seed=SEED,
                         mode="closed", n_ranks=2, n_channels=2,
                         n_bank_groups=1)
    _cells_equal(sweep(base, "batched"), sweep(explicit, "batched"),
                 "default/explicit-1-group")


def test_timing_bank_group_layout():
    T = _grouped(4, n_ranks=2)
    assert T.banks_per_group == 2 and T.n_refresh_units == 4
    with pytest.raises(ValueError, match="divide"):
        timing_for_density(32, n_bank_groups=3, tCCD_L=9.0, tCCD_S=6.0)
    with pytest.raises(TypeError, match="n_bank_groups"):
        timing_for_density(32, n_bank_groups=2)
    with pytest.raises(TypeError, match="tCCD_S"):
        timing_for_density(32, tCCD_S=6.0)


def test_energy_proxy_counts_every_bank_of_a_same_bank_refresh():
    from repro.core.refresh.sim import energy_proxy
    flat, grouped = timing_for_density(32), _grouped(4)
    e1 = energy_proxy(flat, 1e6, 100, 50, 30, 10, 2)
    e4 = energy_proxy(grouped, 1e6, 100, 50, 30, 10, 2)
    assert e4 - e1 == pytest.approx(3 * 0.15 * flat.tRFC_pb * 10)


def test_event_mode_refuses_bank_groups():
    """`DramSim.run` is the float event mode, not the tick contract, and
    models no bank groups; `run_ticks` does."""
    wl = make_closed_workload("closed_mixed", 40, SEED)
    with pytest.raises(ValueError, match="bank groups"):
        DramSim(_grouped(2), wl, "darp").run()
    assert DramSim(_grouped(2), wl, "darp").run_ticks().reads_done > 0


# ------------------------- LPDDR4: addressed per-bank refresh's issue rules
#: the per-bank issue rules, (tpbR2pbR ns, refresh rounds): both, the
#: spacing alone, the rounds alone
LP_RULES = [(90.0, True), (90.0, False), (0.0, True)]
#: every registered per-bank-level policy
PB_POLICIES = tuple(p for p in list_policies()
                    if not resolve_policy(p).ideal
                    and resolve_policy(p).level == "pb")


def _lp(tpbR2pbR=90.0, refpb_rounds=True, density=DENSITY, **layout):
    """LPDDR4 with the issue rules, tREFI cut to a quarter so that a
    short run refreshes every bank."""
    return lpddr4(density, tREFI=976.0, tpbR2pbR=tpbR2pbR,
                  refpb_rounds=refpb_rounds, **layout)


@pytest.mark.parametrize("rules", LP_RULES, ids=["both", "spacing",
                                                 "rounds"])
@pytest.mark.parametrize("n_ranks,n_channels", [(2, 1), (2, 2)])
def test_lpddr4_rules_all_backends_bit_identical_to_run_ticks(
        rules, n_ranks, n_channels):
    """The tpbR2pbR spacing and refresh rounds: scalar, batched and jax
    stay bit-identical to `DramSim.run_ticks` for every registered
    policy."""
    T = _lp(*rules, n_ranks=n_ranks, n_channels=n_channels)
    policies = tuple(list_policies())
    spec = SweepSpec(policies=policies, scenarios=("closed_multirank",),
                     densities=(DENSITY,), reqs=240, seed=SEED,
                     mode="closed", n_ranks=n_ranks, n_channels=n_channels,
                     dt_ns=5.0, timing={DENSITY: T})
    batched = sweep(spec, "batched")
    _cells_equal(sweep(spec, "scalar"), batched, f"scalar {rules}")
    _cells_equal(sweep(spec, "jax"), batched, f"jax {rules}")
    wl = make_closed_workload("closed_multirank", 240, SEED)
    for p in policies:
        cell = batched.get(p, "closed_multirank", DENSITY)
        assert cell.finished, (p, rules)
        _assert_cell_equals_sim(cell, DramSim(T, wl, p).run_ticks(dt_ns=5.0))


def _pb_starts(T, policy, reqs=400):
    """Each per-bank refresh start of a `run_ticks` run on `T`, in start
    order, as (tick, global rank, bank)."""
    wl = make_closed_workload("closed_multirank", reqs, SEED)
    tl = DramSim(T, wl, policy).run_ticks(dt_ns=5.0,
                                          record_timeline=True).timeline
    return sorted({(s0, b // T.n_banks, b) for b, _, s0, _, kind
                   in tl["refresh"] if kind == "pb"})


@pytest.mark.parametrize("policy", PB_POLICIES)
def test_lpddr4_spacing_binds(policy):
    """No two per-bank refreshes of a rank start closer than tpbR2pbR (18
    ticks of 5 ns); without the rule some do."""
    lay = dict(n_ranks=2, n_channels=2, n_subarrays=4)
    gaps = {}
    for sp in (90.0, 0.0):
        last, out = {}, []
        for t, gr, _ in _pb_starts(_lp(sp, False, **lay), policy):
            if gr in last:
                out.append(t - last[gr])
            last[gr] = t
        gaps[sp] = out
    assert gaps[90.0] and min(gaps[90.0]) >= 18, policy
    assert min(gaps[0.0]) < 18, policy


@pytest.mark.parametrize("policy", PB_POLICIES)
def test_lpddr4_rounds_bind(policy):
    """With refresh rounds, no bank of a rank starts a refresh while
    another bank of that rank has had fewer."""
    T = _lp(0.0, True, n_ranks=2, n_channels=2, n_subarrays=4)
    count = [0] * T.n_banks_total
    starts = _pb_starts(T, policy)
    assert starts, policy
    for _, gr, b in starts:
        rank = count[gr * T.n_banks:(gr + 1) * T.n_banks]
        assert count[b] == min(rank), (policy, b, rank)
        count[b] += 1


def test_lpddr4_lag_stays_in_budget_under_every_pb_policy():
    """Under both rules every built-in per-bank policy keeps |lag| within
    the budget of 8, at every density, over a run of some 15 tREFI in
    which in-order REFpb, which the spacing holds to one start every 18
    ticks where a rank's units fall due 6 apart, reaches the budget
    edge."""
    T = {d: _lp(density=d, n_ranks=2, n_channels=2) for d in (8, 16, 32)}
    spec = SweepSpec(policies=PB_POLICIES, scenarios=("closed_multirank",
                                                      "closed_write_heavy"),
                     densities=(8, 16, 32), reqs=3000, seed=SEED,
                     mode="closed", n_ranks=2, n_channels=2, dt_ns=5.0,
                     timing=T)
    res = sweep(spec, "batched")
    for cell in res:
        assert cell.finished, cell.policy
        assert cell.max_abs_lag <= 8, (cell.policy, cell.max_abs_lag)
        assert cell.refreshes_pb > 0, cell.policy
    assert max(c.max_abs_lag for c in res if c.policy == "ref_pb") >= 6


def test_lpddr4_timing_rules_validate():
    T = _lp()
    assert T.has_refpb_rules and not timing_for_density(32).has_refpb_rules
    with pytest.raises(ValueError, match="tpbR2pbR"):
        _lp(tpbR2pbR=-1.0)
    with pytest.raises(TypeError, match="refpb_rounds"):
        _lp(refpb_rounds=1)
    # 8 banks a rank, 90 ns apart, cannot all refresh within 600 ns
    with pytest.raises(ValueError, match="exceeds tREFI"):
        lpddr4(32, tREFI=600.0)
    from repro.core.sweep import TickTiming
    tk = TickTiming.from_timing(T, 5.0)
    assert (tk.PB2PB, tk.PB_ROUNDS) == (18, True)
    # rounded up: a spacing is a floor the commands keep
    assert TickTiming.from_timing(_lp(91.0), 5.0).PB2PB == 19
    assert TickTiming.from_timing(timing_for_density(32)).PB2PB == 0


def test_event_mode_refuses_refpb_rules():
    wl = make_closed_workload("closed_mixed", 40, SEED)
    with pytest.raises(ValueError, match="issue rules"):
        DramSim(_lp(), wl, "darp").run()
    assert DramSim(_lp(), wl, "darp").run_ticks(dt_ns=5.0).reads_done > 0
