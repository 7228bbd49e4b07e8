"""The command layer: DFI-style emission, JEDEC validation, replay.

Four pins (docs/tick-contract.md section 7 is the normative spec):

* emission — `record_commands=True` on `DramSim.run_ticks` / `run` and
  the batched closed-loop sweep produce canonically-ordered `CmdTrace`s
  whose counts reconcile with the run's stats; disabled runs carry no
  trace (and pay nothing — `benchmarks/run.py::command_trace` measures
  the overhead);
* validation — golden fixtures under tests/fixtures/commands/: the
  captured trace is violation-free, and each `bad_*.json` (one planted
  sequencing break per rule) fires exactly its named rule first;
* replay — emit -> validate -> replay is a bit-identical round trip
  (`round_trip`), from fresh runs and from the on-disk fixture;
* the property — every registered policy x closed scenario x
  n_ranks in {1, 2} x n_subarrays in {1, 4} emits a violation-free
  trace (full matrix deterministically, random seeds via hypothesis).
"""
import json
from pathlib import Path

import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # deterministic fallback; see _hypothesis_shim
    from _hypothesis_shim import given, settings, strategies as st

from conftest import lpddr4

from repro.core.commands import (MNEMONICS, TIMING_FIELDS, Cmd, CmdTrace,
                                 round_trip, traces_equal, validate_trace)
from repro.core.commands.trace import REFPB_RULE_FIELDS, _key, tick_meta
from repro.core.commands.validator import RULES
from repro.core.policy import list_policies, resolve_policy
from repro.core.refresh import DramSim, make_closed_workload
from repro.core.refresh.scenarios import list_closed_scenarios
from repro.core.refresh.timing import timing_for_density
from repro.core.refresh.workload import make_workload
from repro.core.sweep import SweepSpec, sweep

FIXTURES = Path(__file__).resolve().parent / "fixtures" / "commands"


def _run(policy="dsarp", density=32, n_ranks=2, n_subarrays=4, reqs=48,
         seed=3, record=True):
    T = timing_for_density(density, n_ranks=n_ranks,
                           n_subarrays=n_subarrays)
    wl = make_workload(n_cores=2, reqs_per_core=reqs, seed=seed)
    return DramSim(T, wl, policy).run_ticks(record_commands=record)


# ------------------------------------------------------------- emission

def test_disabled_by_default_and_zero_cost():
    res = _run(record=False)
    assert res.commands is None


def test_trace_counts_reconcile_with_stats():
    res = _run()
    tr = res.commands
    assert len(tr) > 0
    counts = tr.counts()
    assert set(counts) <= set(MNEMONICS)
    assert counts["RD"] == res.reads_done
    assert counts["WR"] == res.writes_done
    assert counts["REF_PB"] == res.refreshes_pb
    assert counts["REF_AB"] == res.refreshes_ab == 0  # dsarp is pb-level
    assert counts["PRE"] >= counts["REF_PB"]  # every refresh has a preamble
    # canonical order: sorted by (tick, op-class, address)
    assert tr.cmds == sorted(tr.cmds, key=_key)


def test_ab_policy_emits_rank_level_commands():
    res = _run(policy="ref_ab", reqs=400)  # long enough to owe a REF_AB
    counts = res.commands.counts()
    assert counts["REF_AB"] == res.refreshes_ab > 0
    assert counts["PREA"] == counts["REF_AB"]
    for c in res.commands.cmds:
        if c.op in ("PREA", "REF_AB"):
            assert c.bank == -1 and c.sub == -1


def test_meta_carries_every_timing_field():
    tr = _run().commands
    for f in TIMING_FIELDS:
        assert f in tr.meta, f
    assert tr.meta["clock"] == "tick"
    assert tr.meta["TRP"] == 2 and tr.meta["BUDGET"] == 8
    assert tr.meta["end"] >= max(c.tick for c in tr.cmds)


def test_event_mode_emits_ns_trace():
    T = timing_for_density(32, n_subarrays=4)
    wl = make_workload(n_cores=2, reqs_per_core=48, seed=3)
    res = DramSim(T, wl, "dsarp").run(record_commands=True)
    tr = res.commands
    assert tr.meta["clock"] == "ns" and tr.meta["dt_ns"] is None
    assert len(tr) > 0
    assert validate_trace(tr) == []


def test_json_round_trip():
    tr = _run().commands
    back = CmdTrace.from_json(json.loads(json.dumps(tr.to_json())))
    assert traces_equal(tr, back)
    assert back.demand is not None  # captured traces keep their streams


# ----------------------------------------------------- golden fixtures

def _load(name):
    return CmdTrace.from_json(json.loads((FIXTURES / name).read_text()))


def test_golden_valid_fixture_is_clean_and_replays():
    tr = _load("valid.json")
    assert validate_trace(tr) == []
    res, bit_identical = round_trip(tr)
    assert bit_identical
    assert res.commands.meta["end"] == tr.meta["end"]


@pytest.mark.parametrize("rule", RULES)
def test_golden_fixture_fires_exactly_its_rule(rule):
    bad = _load("bad_" + rule.replace("-", "_") + ".json")
    fired = validate_trace(bad)
    assert fired, rule
    assert fired[0].rule == rule, fired[:3]


# --------------------------------------------------------------- replay

@pytest.mark.parametrize("policy", ("dsarp", "ref_ab", "hira", "elastic"))
def test_round_trip_is_bit_identical(policy):
    res = _run(policy=policy)
    replayed, bit_identical = round_trip(res.commands)
    assert bit_identical
    assert replayed.makespan == res.makespan
    assert replayed.avg_read_latency == res.avg_read_latency


def test_replay_under_a_different_policy_is_counterfactual():
    from repro.core.commands import replay_trace

    tr = _run(policy="ref_pb").commands
    other = replay_trace(tr, policy="dsarp")
    assert other.commands.meta["policy"] == "dsarp"
    assert validate_trace(other.commands) == []


def test_external_trace_replays_through_demand_synthesis():
    # strip the captured demand: replay must go through
    # demand_from_commands, stay JEDEC-clean, and be deterministic
    tr = _run().commands
    external = CmdTrace(meta=dict(tr.meta), cmds=list(tr.cmds))  # no demand
    res, _ = round_trip(external)
    assert validate_trace(res.commands) == []
    again, _ = round_trip(CmdTrace(meta=dict(tr.meta), cmds=list(tr.cmds)))
    assert res.makespan == again.makespan
    assert traces_equal(res.commands, again.commands)


# ------------------------------------------------- batched sweep parity

def test_batched_sweep_emission_matches_run_ticks():
    reqs, seed = 96, 2
    spec = SweepSpec(policies=("dsarp", "ref_ab", "darp"),
                     scenarios=("closed_mixed",), densities=(8, 32),
                     reqs=reqs, seed=seed, n_ranks=2, mode="closed")
    res = sweep(spec, "batched", record_commands=True)
    for p in spec.policies:
        for d in spec.densities:
            tr = res.commands_for(p, "closed_mixed", d)
            assert validate_trace(tr) == [], (p, d)
            wl = make_closed_workload("closed_mixed", reqs, seed)
            sim = DramSim(timing_for_density(d, n_ranks=2), wl, p)
            ref = sim.run_ticks(record_commands=True).commands
            assert traces_equal(tr, ref), (p, d)


def test_sweep_refuses_recording_off_the_fast_path():
    spec = SweepSpec(policies=("dsarp",), scenarios=("closed_mixed",),
                     densities=(32,), reqs=8, mode="closed")
    with pytest.raises(ValueError):
        sweep(spec, "scalar", record_commands=True)


# ----------------------------------------------- the clean-trace matrix

def test_every_policy_matrix_is_violation_free():
    """Full matrix: 14+ policies x closed scenarios x R{1,2} x S{1,4}."""
    failures = []
    for policy in list_policies():
        for scenario in list_closed_scenarios():
            for n_ranks in (1, 2):
                for n_subarrays in (1, 4):
                    T = timing_for_density(32, n_ranks=n_ranks,
                                           n_subarrays=n_subarrays)
                    wl = make_closed_workload(scenario, 32, 1)
                    res = DramSim(T, wl, policy).run_ticks(
                        record_commands=True)
                    vio = validate_trace(res.commands, limit=1)
                    if vio:
                        failures.append(
                            (policy, scenario, n_ranks, n_subarrays,
                             str(vio[0])))
    assert not failures, failures[:5]


@settings(max_examples=20, deadline=None)
@given(policy=st.sampled_from(sorted(list_policies())),
       scenario=st.sampled_from(sorted(list_closed_scenarios())),
       n_ranks=st.sampled_from((1, 2)),
       n_subarrays=st.sampled_from((1, 4)),
       seed=st.integers(min_value=0, max_value=2**16))
def test_property_every_emitted_trace_is_jedec_clean(
        policy, scenario, n_ranks, n_subarrays, seed):
    T = timing_for_density(32, n_ranks=n_ranks, n_subarrays=n_subarrays)
    wl = make_closed_workload(scenario, 48, seed)
    res = DramSim(T, wl, policy).run_ticks(record_commands=True)
    vio = validate_trace(res.commands, limit=3)
    assert vio == [], (policy, scenario, n_ranks, n_subarrays, seed,
                       [str(v) for v in vio])


# ---------------------------------------- same-bank refresh (bank groups)

def _grouped_run(policy="ref_pb", reqs=400, record=True):
    """2 ranks x 4 bank groups of 2 banks, tREFI cut to a quarter so the
    run owes same-bank refreshes."""
    T = timing_for_density(32, n_ranks=2, n_subarrays=4, n_bank_groups=4,
                           tCCD_L=9.0, tCCD_S=6.0, tREFI=1953.125)
    wl = make_closed_workload("closed_multirank", reqs, 3)
    return T, DramSim(T, wl, policy).run_ticks(record_commands=record)


@pytest.mark.parametrize("policy", ("ref_pb", "dsarp", "ref_ab", "hira"))
def test_same_bank_refresh_emits_ref_sb_validates_and_replays(policy):
    T, res = _grouped_run(policy)
    tr = res.commands
    counts = tr.counts()
    assert counts["REF_PB"] == 0
    assert counts["REF_SB"] == res.refreshes_pb
    assert (counts["REF_SB"] > 0) == (policy != "ref_ab")
    for c in tr.cmds:
        if c.op == "REF_SB":
            assert 0 <= c.bank < T.banks_per_group
    assert tr.meta["n_bank_groups"] == 4 and tr.meta["CCDL"] == 1
    assert tr.meta["REFI_SB"] == tr.meta["REFI"] // T.n_refresh_units
    assert validate_trace(tr) == []
    replayed, bit_identical = round_trip(tr)
    assert bit_identical and replayed.makespan == res.makespan
    back = CmdTrace.from_json(json.loads(json.dumps(tr.to_json())))
    assert traces_equal(tr, back) and round_trip(back)[1]


def _planted(tr, change):
    """`tr` with `change(cmds)` applied to a copy of its commands."""
    cmds = list(tr.cmds)
    change(cmds)
    return CmdTrace(meta=dict(tr.meta), cmds=cmds, demand=tr.demand)


def test_ref_sb_faults_fire_their_rules():
    T, res = _grouped_run("ref_pb")
    tr = res.commands
    i = next(k for k, c in enumerate(tr.cmds) if c.op == "REF_SB")
    ref = tr.cmds[i]
    # a set bank's preamble left out
    j = next(k for k, c in enumerate(tr.cmds)
             if c.op == "PRE" and c.tick == ref.tick - tr.meta["TRP"]
             and (c.ch, c.rank) == (ref.ch, ref.rank)
             and c.bank % T.banks_per_group == ref.bank
             and c.bank != ref.bank)
    fired = validate_trace(_planted(tr, lambda cmds: cmds.pop(j)))
    assert fired and fired[0].rule == "missing-prea"
    # an ACT to another bank of the set inside the refresh window
    other = ref.bank + T.banks_per_group
    fired = validate_trace(_planted(tr, lambda cmds: cmds.append(
        ref._replace(tick=ref.tick + 1, op="ACT", bank=other, row=5,
                     data=-1))))
    assert "short-trfc" in {v.rule for v in fired}
    # REF_PB where the part refreshes same-bank sets
    fired = validate_trace(_planted(tr, lambda cmds: cmds.__setitem__(
        i, ref._replace(op="REF_PB"))))
    assert "bad-sequence" in {v.rule for v in fired}
    # a serve that skips tCCD_L after a same-group column command
    bad = CmdTrace(meta=dict(tr.meta, CCDL=tr.meta["CCDL"] + 4),
                   cmds=list(tr.cmds))
    assert "trtr-min-latency" in {v.rule for v in validate_trace(bad)}


def test_batched_sweep_ref_sb_emission_matches_run_ticks():
    T, _ = _grouped_run(record=False)
    spec = SweepSpec(policies=("dsarp", "ref_ab", "ref_pb"),
                     scenarios=("closed_multirank",), densities=(32,),
                     reqs=400, seed=3, n_ranks=2, n_subarrays=4,
                     n_bank_groups=4, mode="closed", timing={32: T})
    res = sweep(spec, "batched", record_commands=True)
    for p in spec.policies:
        tr = res.commands_for(p, "closed_multirank", 32)
        assert validate_trace(tr) == [], p
        assert traces_equal(tr, _grouped_run(p)[1].commands), p


# ------------------------------- per-bank refresh issue rules (LPDDR4)

def _lp_trace(starts, **rules):
    """A hand-built LPDDR4 trace (2 ranks, darp) of per-bank refreshes,
    one at each ``(decision tick, rank, bank)`` of `starts`: PRE, then
    REF_PB tRP later."""
    T = lpddr4(32, n_ranks=2, tREFI=976.0, **rules)
    meta = tick_meta(T, resolve_policy("darp"), 5.0)
    cmds = []
    for t, rank, bank in starts:
        cmds.append(Cmd(t, "PRE", 0, rank, bank, -1, -1, -1))
        cmds.append(Cmd(t + meta["TRP"], "REF_PB", 0, rank, bank, -1, -1,
                        t))
    return CmdTrace(meta=meta, cmds=sorted(cmds, key=_key))


def test_refpb_rules_ride_in_the_meta():
    assert set(REFPB_RULE_FIELDS) == {"PB2PB", "REFPB_ROUNDS"}
    meta = _lp_trace([]).meta
    assert (meta["PB2PB"], meta["REFPB_ROUNDS"]) == (18, True)
    assert not set(REFPB_RULE_FIELDS) & set(_run(record=True)
                                            .commands.meta)


def test_planted_refpb_too_soon_fires_refpb_spacing():
    # rank 0: banks 0 and 1 five ticks apart; rank 1 on its own clock
    fired = validate_trace(_lp_trace([(0, 0, 0), (5, 0, 1), (5, 1, 0)]))
    assert [v.rule for v in fired] == ["refpb-spacing"]
    assert fired[0].addr == "ch0.r0.b1"
    assert validate_trace(_lp_trace([(0, 0, 0), (18, 0, 1)])) == []
    # without the spacing the same trace is clean
    assert validate_trace(_lp_trace([(0, 0, 0), (5, 0, 1)],
                                    tpbR2pbR=0.0)) == []


def test_planted_refpb_out_of_round_fires_refpb_round():
    # bank 0 of rank 0 again before banks 1-7 of it have had one
    fired = validate_trace(_lp_trace([(0, 0, 0), (60, 0, 0)]))
    assert [v.rule for v in fired] == ["refpb-round"]
    assert fired[0].addr == "ch0.r0.b0"
    rounds = [(18 * k, 0, k) for k in range(8)] + [(144, 0, 0)]
    assert validate_trace(_lp_trace(rounds)) == []
    assert validate_trace(_lp_trace([(0, 0, 0), (60, 0, 0)],
                                    refpb_rounds=False)) == []


@pytest.mark.parametrize("policy", ("ref_pb", "darp", "dsarp", "hira",
                                    "elastic", "ref_ab"))
def test_recorded_lpddr4_trace_validates_and_replays(policy):
    """The batched backend's recorded LPDDR4 traces under both rules are
    JEDEC-clean, the run_ticks trace command for command, and replay
    bit-identically."""
    T = lpddr4(32, n_ranks=2, n_channels=2, n_subarrays=4, tREFI=976.0)
    spec = SweepSpec(policies=(policy,), scenarios=("closed_multirank",),
                     densities=(32,), reqs=400, seed=3, n_ranks=2,
                     n_channels=2, n_subarrays=4, mode="closed",
                     dt_ns=5.0, timing={32: T})
    tr = sweep(spec, "batched", record_commands=True).commands_for(
        policy, "closed_multirank", 32)
    assert tr.meta["PB2PB"] == 18 and tr.meta["REFPB_ROUNDS"] is True
    assert validate_trace(tr) == [], policy
    assert (tr.counts()["REF_PB"] > 0) == (policy != "ref_ab")
    wl = make_closed_workload("closed_multirank", 400, 3)
    res = DramSim(T, wl, policy).run_ticks(dt_ns=5.0, record_commands=True)
    assert traces_equal(tr, res.commands)
    replayed, bit_identical = round_trip(res.commands)
    assert bit_identical and replayed.makespan == res.makespan
