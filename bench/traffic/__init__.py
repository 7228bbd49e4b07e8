"""Traffic of a benchmark cell: one general generator, `build`, reads a
traffic file (`bench/workloads/<traffic>.json`) and draws its demand
from the run's seed.

A traffic file names the grid's axes (policies, densities, request
count) and its scenarios, each a set of generator parameters:

  * closed loop (``"mode": "closed"``): every scenario is a workload of
    MLP-limited cores (n_cores, mlp, think_ns, row_hit_rate,
    write_ratio). ``"mixes": n`` makes n scenarios of those parameters,
    ``<name>.m000`` on, each drawn from its own seed.
  * open loop (``"mode": "open"``): every scenario names an open-loop
    kind of `generators.OPEN_KINDS` and its parameters.

The streams are drawn once from the file's ``base_seed``; the run's seed
then relabels rows (a permutation within each subarray's rows) and,
where ``relabel`` names them, banks (a permutation). So every seed
offers the same requests, think gaps and arrivals, and the same per-bank
queue lengths, which fix the program's padded shapes, in another
placement: no seed compiles anew. Relabelled banks meet other refresh
phases, which moves a closed grid's slowest cell, and with it the
sweep's tick count, by a few per cent from seed to seed; closed mixes
therefore relabel rows alone.

``check_cells`` is how many of the grid's cells the reference checks in
every run (drawn from the seed; the longest cell is always among them).
"""
from __future__ import annotations

from dataclasses import dataclass, replace

import numpy as np

from bench.traffic.generators import (N_ROWS, OPEN_KINDS, ClosedStreams,
                                      OpenTrace, closed_seed,
                                      closed_streams, open_rs)


@dataclass(frozen=True)
class Traffic:
    mode: str                   # 'open' | 'closed'
    policies: tuple
    densities: tuple
    reqs: int
    scenarios: tuple            # OpenTrace | ClosedStreams, grid order
    check_cells: int

    def cells(self) -> list[tuple[str, int, int]]:
        """(policy, scenario index, density) in the grid's order:
        policies outermost, densities innermost."""
        return [(p, s, d) for p in self.policies
                for s in range(len(self.scenarios)) for d in self.densities]


def _layout(config: dict) -> tuple[int, int]:
    lay = config["layout"]
    B = lay["n_channels"] * lay["n_ranks"] * lay["n_banks"]
    return B, lay["n_subarrays"]


def _relabel(scn, seed: int, what):
    """Rows through a permutation that keeps each row's subarray
    (``row % n_subarrays``), and banks through a permutation where
    `what` names them."""
    rs = open_rs(f"relabel:{scn.name}", seed)
    S = scn.n_subarrays
    bank_perm = rs.permutation(scn.n_banks).astype(np.int32)
    row_perm = rs.permutation(N_ROWS // S).astype(np.int32)
    bank = bank_perm[scn.bank] if "bank" in what else scn.bank
    return replace(scn, bank=bank,
                   row=row_perm[scn.row // S] * S + scn.row % S).validate()


def _capture(config: dict, reference):
    """The source run of a replay kind: one closed-loop cell of the
    configuration's plain reference, recording its serves."""
    B, S = _layout(config)

    def capture(source: dict, reqs: int, seed: int):
        streams = closed_streams(source["scenario"], source, B, S, reqs,
                                 seed, config["dt_ns"])
        one = Traffic(mode="closed", policies=(source["policy"],),
                      densities=(source["density"],), reqs=reqs,
                      scenarios=(streams,), check_cells=1)
        _, (serves,) = reference.simulate(one, config, one.cells(),
                                          record=True)
        return serves
    return capture


def build(mix: dict, config: dict, seed: int, reference) -> Traffic:
    """The traffic `mix` (a parsed traffic file) for `config` at `seed`;
    a replay kind's source run is a cell of `reference`, the module
    `config` names (`harness.load_reference`)."""
    B, S = _layout(config)
    reqs = int(mix["reqs"])
    scenarios = []
    if mix["mode"] == "closed":
        for sc in mix["scenarios"]:
            params = {k: sc[k] for k in ("n_cores", "mlp", "think_ns",
                                         "row_hit_rate", "write_ratio")}
            names = ([f"{sc['name']}.m{i:03d}" for i in range(sc["mixes"])]
                     if "mixes" in sc else [sc["name"]])
            for name in names:
                base = closed_streams(name, params, B, S, reqs,
                                      closed_seed(name, mix["base_seed"]),
                                      config["dt_ns"])
                scenarios.append(_relabel(base, seed, mix["relabel"]))
    elif mix["mode"] == "open":
        for sc in mix["scenarios"]:
            kw = {k: v for k, v in sc.items() if k not in ("name", "kind")}
            if sc["kind"] == "replay_capture":
                kw["capture"] = _capture(config, reference)
            base = OPEN_KINDS[sc["kind"]](
                sc["name"], B, S, reqs, open_rs(sc["name"], mix["base_seed"]),
                **kw).validate()
            scenarios.append(_relabel(base, seed, mix["relabel"]))
    else:
        raise ValueError(f"unknown traffic mode {mix['mode']!r}")
    return Traffic(mode=mix["mode"], policies=tuple(mix["policies"]),
                   densities=tuple(mix["densities"]), reqs=reqs,
                   scenarios=tuple(scenarios),
                   check_cells=int(mix["check_cells"]))


__all__ = ["Traffic", "build", "ClosedStreams", "OpenTrace"]
