"""The [bank, subarray] hierarchy: subarray conformance across all sweep
backends vs `DramSim.run_ticks` for every registered policy, the directed
SARP semantics (serving an idle subarray during a sibling subarray's
refresh), the n_subarrays=1 no-regression pin against the pre-subarray
golden fixture, refresh-timeline determinism, and the load-bearing-ness
of the packed no-conflict score bit.

The spec these tests enforce is docs/tick-contract.md §2-§4; the flat
harness lives in tests/test_conformance.py and the rank/channel matrix in
tests/test_multirank.py.
"""
import json
from pathlib import Path

import numpy as np
import pytest

from repro.core.policy import list_policies
from repro.core.refresh import DramSim, make_closed_workload
from repro.core.refresh.timing import timing_for_density
from repro.core.sweep import CellResult, SweepSpec, sweep
from repro.core.sweep.arbiter import arbiter_scores
from repro.core.sweep.fields import W_NOCONF

REQS, SEED, DENSITY = 96, 2, 32
SCENARIOS = ("closed_subarray_storm", "closed_subarray_locality")
SUBARRAYS = (1, 4, 8)
GOLDEN = Path(__file__).resolve().parent / "fixtures" / "sweep_s1_golden.json"


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


def _spec(n_subarrays, policies=None, scenarios=SCENARIOS):
    return SweepSpec(policies=policies or tuple(list_policies()),
                     scenarios=scenarios, densities=(DENSITY,),
                     reqs=REQS, seed=SEED, mode="closed",
                     n_subarrays=n_subarrays)


# --------------------------------------------- subarray conformance grid
@pytest.mark.parametrize("n_subarrays", SUBARRAYS)
def test_subarray_all_backends_bit_identical_to_run_ticks(n_subarrays):
    """Every backend (batched numpy, jitted jax, scalar oracle) stays
    bit-identical to `DramSim.run_ticks` at every subarray count, for
    EVERY registered policy on both subarray scenarios."""
    spec = _spec(n_subarrays)
    batched = sweep(spec, "batched")
    _cells_equal(sweep(spec, "scalar"), batched,
                 f"scalar/batched S={n_subarrays}")
    _cells_equal(sweep(spec, "jax"), batched,
                 f"jax/batched S={n_subarrays}")
    for scen in SCENARIOS:
        wl = make_closed_workload(scen, REQS, SEED)
        T = timing_for_density(DENSITY, n_subarrays=n_subarrays)
        for p in list_policies():
            cell = batched.get(p, scen, DENSITY)
            assert cell.finished, (p, scen, n_subarrays)
            _assert_cell_equals_sim(cell, DramSim(T, wl, p).run_ticks())


# ------------------------------------------ directed SARP/HiRA semantics
def _overlapped_serves(sim):
    """Serves that landed while ANOTHER subarray of the same bank was
    mid-refresh, and serves inside their OWN subarray's refresh window."""
    sibling = own = 0
    for (t, b, sub, row, isw, done, arr) in sim.timeline["serves"]:
        for (rb, rs, s0, s1, kind) in sim.timeline["refresh"]:
            if rb != b or not (s0 <= t < s1):
                continue
            if rs == -1 or rs == sub:
                own += 1
            else:
                sibling += 1
    return sibling, own


def _timeline_sim(policy, n_subarrays=8, reqs=400):
    T = timing_for_density(DENSITY, n_subarrays=n_subarrays)
    wl = make_closed_workload("closed_subarray_storm", reqs, SEED)
    return DramSim(T, wl, policy).run_ticks(record_timeline=True)


def test_sarp_serves_idle_subarray_during_sibling_refresh():
    """The tentpole semantics, directly: a SARP policy serves requests to
    idle subarrays WHILE a sibling subarray of the same bank refreshes;
    a non-SARP policy (whole-bank refresh occupancy) never overlaps a
    serve with any refresh of that bank. Nobody ever serves into their
    own subarray's refresh window."""
    sarp = _timeline_sim("sarp_pb")
    sibling, own = _overlapped_serves(sarp)
    assert sarp.refreshes_pb > 0
    assert sibling > 0, "sarp_pb never exploited an idle subarray"
    assert own == 0

    base = _timeline_sim("ref_pb")
    sibling, own = _overlapped_serves(base)
    assert base.refreshes_pb > 0
    assert sibling == 0, "ref_pb marks ALL subarrays; overlap impossible"
    assert own == 0


def test_hira_hidden_refresh_starts_under_inflight_access():
    """The hra trait (HiRA): a pb refresh aimed at a subarray other than
    the bank's open one may start while the bank is still mid-access —
    hira's timeline must contain refresh starts strictly inside a serve's
    bank-busy window, which plain sarp_pb (no hra) never produces."""
    def hidden_starts(sim):
        busy = {}                 # bank -> list of (start, bank_free_end)
        for (t, b, sub, row, isw, done, arr) in sim.timeline["serves"]:
            busy.setdefault(b, []).append((t, done))
        return sum(1 for (b, rs, s0, s1, kind) in sim.timeline["refresh"]
                   if kind == "pb"
                   and any(t0 < s0 < t1 for t0, t1 in busy.get(b, ())))

    assert hidden_starts(_timeline_sim("hira")) > 0
    assert hidden_starts(_timeline_sim("sarp_pb")) == 0


def test_hira_is_plain_sarp_at_one_subarray():
    """At S=1 the refresh target always equals the open subarray, so the
    hidden-start branch is inert: hira == sarp_pb decision-for-decision
    would be too strong (their select() orders differ), but hira at S=1
    must equal ITSELF without the hra trait — pinned by the S=1 golden
    cells — and its hidden-start count must be zero."""
    sim = _timeline_sim("hira", n_subarrays=1, reqs=200)
    for (b, rs, s0, s1, kind) in sim.timeline["refresh"]:
        if kind == "pb":
            assert rs in (0, -1)
    sibling, own = _overlapped_serves(sim)
    assert sibling == 0 and own == 0


# --------------------------------------------- n_subarrays=1 golden pin
def test_s1_sweep_bit_identical_to_pre_subarray_golden():
    """n_subarrays=1 reproduces the pre-subarray [grid, global_bank]
    engine bit-for-bit: every stat of every (policy, scenario, density)
    cell equals the golden fixture captured before the subarray plane
    landed."""
    golden = json.loads(GOLDEN.read_text())
    gspec = golden["spec"]
    spec = SweepSpec(policies=tuple(gspec["policies"]),
                     scenarios=tuple(gspec["scenarios"]),
                     densities=tuple(gspec["densities"]),
                     reqs=gspec["reqs"], seed=gspec["seed"],
                     mode=gspec["mode"],
                     n_subarrays=gspec["n_subarrays"])
    res = sweep(spec, "batched")
    bad = []
    for key, want in golden["cells"].items():
        pol, scen, dens = key.split("|")
        cell = res.get(pol, scen, int(dens))
        for f, w in want.items():
            got = getattr(cell, f)
            got = list(got) if f == "core_finish" else got
            if got != w:
                bad.append((key, f, got, w))
    assert len(golden["cells"]) == (len(gspec["policies"])
                                    * len(gspec["scenarios"])
                                    * len(gspec["densities"]))
    assert not bad, bad[:8]


# ---------------------------------------------- timeline determinism
def test_refresh_timeline_deterministic_and_complete():
    """Same seed -> identical occupancy timeline (fig2 regenerates from
    this, so figure determinism reduces to it), and the recorded refresh
    events account for every counted refresh."""
    a = _timeline_sim("sarp_pb", reqs=200)
    b = _timeline_sim("sarp_pb", reqs=200)
    assert a.timeline == b.timeline
    assert a.timeline["refresh"] and a.timeline["serves"]
    n_pb = sum(1 for e in a.timeline["refresh"] if e[4] == "pb")
    assert n_pb == a.refreshes_pb
    # off by default: the stats path records nothing
    assert _timeline_sim("sarp_pb", reqs=64).timeline is not None
    plain = DramSim(timing_for_density(DENSITY),
                    make_closed_workload("closed_mixed", 64, SEED),
                    "sarp_pb").run_ticks()
    assert plain.timeline is None


def test_fig2_regenerates_deterministically_from_occupancy():
    """fig2 is now derived from the recorded per-subarray occupancy, not
    a scripted timeline: two regenerations are identical payload-for-
    payload, SARP's excerpt shows serves inside a sibling refresh window,
    and REF_pb (whole-bank occupancy) has no such window to show."""
    import sys
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
    try:
        from benchmarks import fig_refresh as FR
    finally:
        sys.path.pop(0)
    a, b = FR.fig2(), FR.fig2()
    assert a == b
    assert a["sarp_pb"]["serves_during_sibling_refresh"] > 0
    assert a["ref_pb"]["serves_during_sibling_refresh"] == 0
    assert a["sarp_pb"]["first_parallelized_refresh"] is not None
    assert a["ref_pb"]["first_parallelized_refresh"] is None
    assert a["sarp_pb"]["p99_read_ns"] < a["ref_pb"]["p99_read_ns"]


# ------------------------------------- packed no-conflict bit semantics
def test_noconf_bit_steers_arbiter_away_from_refreshing_banks():
    """Mutation sensitivity for the new packed field: two eligible heads,
    equal but for bank 0 having a sibling subarray mid-refresh. With
    W_NOCONF the conflict-free bank wins despite a slightly older rival;
    zeroing the bit flips the winner — the bit is load-bearing, not
    decorative."""
    kw = dict(
        has_req=np.array([[True, True]]),
        head_row=np.array([[7, 9]], dtype=np.int32),
        head_arrive=np.array([[0, 2]], dtype=np.int32),
        head_is_write=np.array([[False, False]]),
        bank_free=np.zeros((1, 2), dtype=np.int32),
        head_ref_until=np.zeros((1, 2), dtype=np.int32),
        bank_mid_ref=np.array([[True, False]]),
        open_row=np.full((1, 2), -1, dtype=np.int32),
        drain=np.array([False]),
        rank_drain=np.array([[False, False]]),
    )
    score = arbiter_scores(np, np.int32(10), **kw)
    assert int(np.argmax(score[0])) == 1, "noconf must beat 2 ticks of age"
    assert score[0, 1] - score[0, 0] == W_NOCONF - 2
    # and when both banks are clear the bit is a constant offset: the
    # older head wins, exactly the S=1 / non-SARP degeneration
    kw["bank_mid_ref"] = np.array([[False, False]])
    score = arbiter_scores(np, np.int32(10), **kw)
    assert int(np.argmax(score[0])) == 0


# ------------------------------------------------- view plumbing sanity
def test_run_ticks_exposes_subarray_view_fields():
    """DramSim.run_ticks hands policies a MaintenanceView carrying the
    subarray plane; spot-check via a recording policy at S=4."""
    from repro.core.policy.base import PolicyBase

    seen = {}

    class Probe(PolicyBase):
        name = "probe"
        level = "pb"

        def select(self, view):
            seen["n_subarrays"] = view.n_subarrays
            seen.setdefault("next_ref_sub", view.next_ref_sub)
            seen["lens"] = (len(view.next_ref_sub),
                            len(view.refreshing_sub), len(view.active_sub))
            return []

    T = timing_for_density(DENSITY, n_subarrays=4)
    wl = make_closed_workload("closed_subarray_locality", 48, SEED)
    DramSim(T, wl, Probe()).run_ticks()
    assert seen["n_subarrays"] == 4
    assert seen["lens"] == (T.n_banks,) * 3
    assert all(0 <= s < 4 for s in seen["next_ref_sub"])


# ------------------------------------------ bank groups, same-bank refresh
def _grouped(n_bank_groups, n_subarrays, **layout):
    """The program's DRAM with `n_bank_groups` groups per rank, tCCD_L one
    tick above tCCD_S, and tREFI cut to a quarter so that a short run
    owes same-bank refreshes."""
    return timing_for_density(DENSITY, n_subarrays=n_subarrays,
                              n_bank_groups=n_bank_groups, tCCD_L=9.0,
                              tCCD_S=6.0, tREFI=1953.125, **layout)


@pytest.mark.parametrize("n_bank_groups,n_subarrays",
                         [(2, 1), (2, 8), (4, 4)])
def test_bank_groups_subarray_all_backends_bit_identical_to_run_ticks(
        n_bank_groups, n_subarrays):
    """Every registered policy on both subarray scenarios with same-bank
    refresh: scalar, batched and jax bit-identical to `run_ticks`."""
    T = _grouped(n_bank_groups, n_subarrays)
    spec = SweepSpec(policies=tuple(list_policies()), scenarios=SCENARIOS,
                     densities=(DENSITY,), reqs=REQS * 2, seed=SEED,
                     mode="closed", n_subarrays=n_subarrays,
                     n_bank_groups=n_bank_groups, timing={DENSITY: T})
    batched = sweep(spec, "batched")
    _cells_equal(sweep(spec, "scalar"), batched,
                 f"scalar G={n_bank_groups} S={n_subarrays}")
    _cells_equal(sweep(spec, "jax"), batched,
                 f"jax G={n_bank_groups} S={n_subarrays}")
    for scen in SCENARIOS:
        wl = make_closed_workload(scen, REQS * 2, SEED)
        for p in list_policies():
            cell = batched.get(p, scen, DENSITY)
            assert cell.finished, (p, scen)
            _assert_cell_equals_sim(cell, DramSim(T, wl, p).run_ticks())


@pytest.mark.parametrize("policy", ["ref_pb", "darp", "sarp_pb", "hira"])
def test_same_bank_refresh_blocks_every_bank_of_its_set(policy):
    """A recorded timeline: every same-bank refresh occupies each bank of
    its set over one window; no serve lands inside its own subarray's
    window (a non-SARP REFsb's is the whole bank), while SARP ones serve
    a sibling subarray; and the lag of every set stays within the
    budget."""
    T = _grouped(4, 4, n_ranks=2)
    wl = make_closed_workload("closed_multirank", 600, SEED)
    sim = DramSim(T, wl, policy).run_ticks(record_timeline=True)
    sets = {}
    for (b, rs, s0, s1, kind) in sim.timeline["refresh"]:
        assert kind == "pb"
        gr, k = b // T.n_banks, b % T.banks_per_group
        sets.setdefault((gr, k, s0, s1), []).append((b, rs))
    assert sim.refreshes_pb == len(sets) > 0
    for (gr, k, s0, s1), banks in sets.items():
        assert sorted(b for b, _ in banks) == [
            gr * T.n_banks + g * T.banks_per_group + k
            for g in range(T.n_bank_groups)]
        assert len({rs for _, rs in banks}) == 1
    sibling, own = _overlapped_serves(sim)
    assert own == 0
    assert (sibling > 0) == DramSim(T, wl, policy).policy.sarp
    assert sim.max_abs_lag <= T.refresh_budget
