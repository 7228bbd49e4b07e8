#!/usr/bin/env python3
"""Regenerate the golden command-trace fixtures.

    PYTHONPATH=src python tests/fixtures/commands/regen.py

`valid.json` is a captured dsarp run (2 ranks, 4 subarrays) that
validates clean and round-trips bit-identically; each `bad_*.json` is
the same trace with ONE planted sequencing violation, named after the
rule it must fire (see tests/test_commands.py::test_golden_fixture).

The per-bank refresh issue rules' fixtures (`bad_refpb_*.json`) start
from a captured darp run on an LPDDR4-like part that keeps neither rule,
which validates clean; each states one rule the run did not keep:

    PYTHONPATH=src python tests/fixtures/commands/regen.py --refpb
"""
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(
    os.path.abspath(__file__)), "..", "..", "..", "src"))

from repro.core.commands import Cmd, validate_trace  # noqa: E402
from repro.core.commands.trace import CmdTrace  # noqa: E402
from repro.core.refresh.sim import DramSim  # noqa: E402
from repro.core.refresh.timing import timing_for_density  # noqa: E402
from repro.core.refresh.workload import make_workload  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))


def base_trace() -> CmdTrace:
    T = timing_for_density(32, n_subarrays=4, n_ranks=2)
    wl = make_workload(n_cores=2, reqs_per_core=48, seed=3)
    res = DramSim(T, wl, "dsarp").run_ticks(record_commands=True)
    return res.commands


def clone(trace: CmdTrace, cmds) -> CmdTrace:
    return CmdTrace(meta=dict(trace.meta), cmds=list(cmds), demand=None)


def mutate(trace: CmdTrace, rule: str) -> CmdTrace:
    cmds = list(trace.cmds)
    m = trace.meta
    NB, NR = m["n_banks"], m["n_ranks"]
    refs = [(i, c) for i, c in enumerate(cmds) if c.op == "REF_PB"]
    assert refs, "base trace has no per-bank refresh to mutate"
    i, ref = refs[len(refs) // 2]

    if rule == "missing-prea":
        # drop the PRE preamble of one REF_PB
        pre = [k for k, c in enumerate(cmds)
               if c.op == "PRE" and c.tick == ref.tick - m["TRP"]
               and (c.ch, c.rank, c.bank, c.sub) ==
               (ref.ch, ref.rank, ref.bank, ref.sub)]
        del cmds[pre[0]]
    elif rule == "short-trp":
        # slide the REF_PB one tick early: gap TRP-1 < TRP
        cmds[i] = ref._replace(tick=ref.tick - 1)
    elif rule == "short-trfc":
        # an ACT landing on the refreshing subarray inside its window
        gb = (ref.ch * NR + ref.rank) * NB + ref.bank
        cmds.append(Cmd(ref.tick + 1, "ACT", ref.ch, ref.rank, ref.bank,
                        ref.sub, 123, -1))
    elif rule == "postpone-budget":
        # corrupt the decision tick: a huge due count at that instant
        cmds[i] = ref._replace(data=ref.data + 100 * m["REFI"])
    elif rule == "trtr-min-latency":
        # a burst whose data completes the tick it starts
        k, c = next((k, c) for k, c in enumerate(cmds)
                    if c.op in ("RD", "WR"))
        cmds[k] = c._replace(data=c.tick)
    elif rule == "bad-sequence":
        # a read from a closed row with no same-tick ACT, injected before
        # any command touches the machine (same rank as the first serve,
        # so no downstream turnaround drift)
        c = next(c for c in cmds if c.op in ("RD", "WR"))
        t0 = cmds[0].tick - 1
        cmds.append(Cmd(t0, "RD", c.ch, c.rank, c.bank, c.sub, 999,
                        t0 + 50))
    else:
        raise ValueError(rule)
    return clone(trace, cmds)


def refpb_base_trace() -> CmdTrace:
    """A darp run on ticks of 5 ns without the per-bank issue rules, on 2
    ranks of 8 banks, with tREFI cut to an eighth of LPDDR4's so that a
    short run refreshes a bank twice."""
    from repro.core.refresh.timing import DramTiming
    T = DramTiming(density_gb=32, n_ranks=2, n_subarrays=4, tRCD=18.0,
                   tRP=18.0, tCL=17.5, tBL=5.0, tWR=18.0, tWTR=10.0,
                   tREFI=488.0, tRFC_ab=380.0, tRFC_pb=190.0)
    wl = make_workload(n_cores=2, reqs_per_core=96, seed=3)
    return DramSim(T, wl, "darp").run_ticks(dt_ns=5.0,
                                           record_commands=True).commands


def refpb_mutate(trace: CmdTrace, rule: str) -> CmdTrace:
    meta = dict(trace.meta)
    if rule == "refpb-spacing":
        # a tpbR2pbR spacing one tick above the closest two refreshes of
        # a rank
        last, gaps = {}, []
        for c in trace.cmds:
            if c.op == "REF_PB":
                if (c.ch, c.rank) in last:
                    gaps.append(c.tick - last[(c.ch, c.rank)])
                last[(c.ch, c.rank)] = c.tick
        meta["PB2PB"] = min(gaps) + 1
    elif rule == "refpb-round":
        # the out-of-order run held to refresh rounds
        meta["REFPB_ROUNDS"] = True
    else:
        raise ValueError(rule)
    return CmdTrace(meta=meta, cmds=list(trace.cmds), demand=None)


def refpb_main():
    trace = refpb_base_trace()
    vio = validate_trace(trace)
    assert vio == [], vio
    for rule in ("refpb-spacing", "refpb-round"):
        bad = refpb_mutate(trace, rule)
        fired = validate_trace(bad)
        assert fired and fired[0].rule == rule, (rule, fired[:3])
        name = "bad_" + rule.replace("-", "_") + ".json"
        with open(os.path.join(HERE, name), "w") as f:
            json.dump(bad.to_json(), f, indent=1, sort_keys=True)
        print(f"{name}: fires {rule} ({len(fired)} violation(s))")


def main():
    trace = base_trace()
    vio = validate_trace(trace)
    assert vio == [], vio
    with open(os.path.join(HERE, "valid.json"), "w") as f:
        json.dump(trace.to_json(), f, indent=1, sort_keys=True)
    print(f"valid.json: {len(trace)} cmds, clean")
    for rule in ("missing-prea", "short-trp", "short-trfc",
                 "postpone-budget", "trtr-min-latency", "bad-sequence"):
        bad = mutate(trace, rule)
        fired = validate_trace(bad)
        assert fired and fired[0].rule == rule, (rule, fired[:3])
        name = "bad_" + rule.replace("-", "_") + ".json"
        with open(os.path.join(HERE, name), "w") as f:
            json.dump(bad.to_json(), f, indent=1, sort_keys=True)
        print(f"{name}: fires {rule} ({len(fired)} violation(s))")


if __name__ == "__main__":
    refpb_main() if "--refpb" in sys.argv[1:] else main()
