"""Plain reference of the integer tick contract (`docs/tick-contract.md`)
for a DRAM whose controller addresses each per-bank refresh under the
two issue rules of LPDDR4 REFpb, which decides a run's `correct` on such
a configuration.

It imports nothing of the program. It is `bench/reference_bg.py` with
the two rules, which bind every per-bank-level start, forced ones too,
over the refresh units of one rank:

  * spacing (``tpbR2pbR`` in the file's timing, ns): ``PB2PB``, that
    time rounded up to whole ticks. A start on rank ``gr`` at tick
    ``start`` keeps the rank's units from starting before ``start +
    PB2PB`` (``pb_next``); of the units picked on one rank in one tick,
    only the lowest starts, and the others are not issued.
  * rounds (``refpb_rounds``): a unit may start only while it has had
    no more refreshes than any other unit of its rank.

The policies see both rules through the units' readiness. Without them
the answers are `bench/reference_bg.py`'s, bit for bit. The tick loops
are that file's: this module loads its own copy of it (a module object
of its own, so the benchmark's `reference_bg` stays as it is) and puts
its per-cell state and its decision phase into that copy. Every integer
plane and counter is of the integer type `itype`: int32 is the
contract; a narrower type is the benchmark's control.
"""
from __future__ import annotations

import importlib.util
import math
import os

import numpy as np


def _load(name: str, file: str):
    """The module `file` from beside this one, as a module object of its
    own named `name`."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), file)
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_bg = _load("bench_reference_bg_for_lp", "reference_bg.py")
MAX_LAT_TICKS, IDEAL, AB = _bg.MAX_LAT_TICKS, _bg.IDEAL, _bg.AB
POLICIES, FIELDS = _bg.POLICIES, _bg.FIELDS
ns_timing = _bg.ns_timing
_bg_pb_refresh = _bg._pb_refresh


# ------------------------------------------------------------- timing
def tick_timing(config: dict, density: int) -> dict:
    """`bench/reference_bg.py`'s quantized timing, with the spacing
    ``PB2PB`` (``ceil(tpbR2pbR / dt)``, 0 for none) and ``ROUNDS``."""
    out = _bg.tick_timing(config, density)
    T = ns_timing(config, density)
    sp = float(T.get("tpbR2pbR", 0.0))
    spacing = max(1, math.ceil(sp / config["dt_ns"] - 1e-9)) if sp > 0 else 0
    return dict(out, PB2PB=spacing, ROUNDS=bool(T.get("refpb_rounds", False)))


class _Cells(_bg._Cells):
    """`bench/reference_bg.py`'s per-cell constants, with each cell's
    spacing and round rule."""

    def __init__(self, traffic, config, cells, I):
        super().__init__(traffic, config, cells, I)
        tick = {d: tick_timing(config, d) for d in traffic.densities}
        self.PB2PB = np.array([tick[d]["PB2PB"] for _, _, d in cells], I)
        self.ROUNDS = np.array([tick[d]["ROUNDS"] for _, _, d in cells])
        self.rules = bool((self.PB2PB > 0).any() or self.ROUNDS.any())


# -------------------------------------------------------------- rules
def _rules_ready(gc, t, st):
    """[G, U]: the units the spacing and the rounds let start at t."""
    G, R, U = gc.G, gc.R, gc.U
    iss = st["issued"].reshape(G, R, U // R)
    in_round = iss == iss.min(axis=2, keepdims=True)
    in_round |= ~gc.ROUNDS[:, None, None]
    spaced = (t >= st["pb_next"]) | (gc.PB2PB == 0)[:, None]
    return (in_round & spaced[:, :, None]).reshape(G, U)


def _lowest_a_rank(gc, picks):
    """`picks` with one unit a rank, its lowest, in the cells whose
    spacing is on."""
    G, R, U = gc.G, gc.R, gc.U
    p = picks.reshape(G, R, U // R)
    lowest = p.argmax(axis=2)
    one = ((np.arange(U // R)[None, None, :] == lowest[:, :, None])
           & p.any(axis=2)[:, :, None])
    return np.where((gc.PB2PB > 0)[:, None, None], one, p).reshape(G, U)


def _pb_refresh(gc, t, picks, due, st, I):
    """`bench/reference_bg.py`'s per-unit refresh starts; a rank that
    starts a unit at ``start`` opens again at ``start + PB2PB``."""
    if (gc.PB2PB > 0).any():
        G, R, U = gc.G, gc.R, gc.U
        new_sub = st["ctr"] % gc.S
        start = np.maximum(t, st["bank_free"])
        start = np.where(gc.hra[:, None] & (new_sub != st["open_sub"]), t,
                         start)
        start_u = _bg._units(start, "max", R, gc.NBG).reshape(G, R, U // R)
        p = picks.reshape(G, R, U // R)
        last = np.where(p, start_u, 0).max(axis=2)
        st["pb_next"] = np.where(
            p.any(axis=2) & (gc.PB2PB > 0)[:, None],
            last + gc.PB2PB[:, None], st["pb_next"]).astype(I)
    _bg_pb_refresh(gc, t, picks, due, st, I)


# ------------------------------------------- state and decision phase
def _state(gc, I):
    st = _bg._plain._state(gc, I)
    st["issued"] = np.zeros((gc.G, gc.U), I)
    st["last_bg"] = np.full((gc.G, gc.NC), -1, I)
    st["pb_next"] = np.zeros((gc.G, gc.R), I)
    return st


def _decide(gc, t, st, active, kind_active, demand, I):
    """`bench/reference_bg.py`'s phases B and C, with the issue rules.
    Returns the banks' ``idle`` for arbitration."""
    G, B, S, R, NB, NBG = gc.G, gc.B, gc.S, gc.R, gc.NB, gc.NBG
    if gc.level_ab.any():
        acc = ((active & gc.level_ab)[:, None] & (t > gc.rank_phase)
               & ((t - gc.rank_phase) % gc.REFI[:, None] == 0))
        if acc.any():
            st["ab_pending"] = (st["ab_pending"] + acc).astype(I)
            st["rank_drain"] |= acc
    due = np.maximum((t - gc.phase) // gc.REFI[:, None] + 1, 0).astype(I)
    lag = (due - st["issued"]).astype(I)
    ready = (st["ref_until_s"].reshape(G, B, S) <= t).all(axis=2)
    idle = st["bank_free"] <= t
    demand_u = _bg._units(demand, "sum", R, NBG)
    ready_u = _bg._units(ready, "all", R, NBG)
    if gc.rules:
        ready_u = ready_u & _rules_ready(gc, t, st)
    idle_u = _bg._units(idle, "all", R, NBG)
    need = _bg._plain._could_pick(
        kind=kind_active, lag=lag, demand=demand_u,
        write_window=st["drain"], budget=gc.budget, wrp=gc.wrp)
    picks = None
    if need.any():
        picks, rr = _bg._plain._select(
            kind=np.where(need, kind_active, IDEAL), lag=lag, ready=ready_u,
            idle=idle_u, demand=demand_u, write_window=st["drain"],
            budget=gc.budget, wrp=gc.wrp, urgent_at=gc.urgent_at,
            rr=st["rr"])
        st["rr"] = rr.astype(I)
        if not picks.any():
            picks = None
    if gc.level_ab.any():
        quiet_r = (idle.reshape(G, R, NB).all(axis=2)
                   & ready.reshape(G, R, NB).all(axis=2))
        pend = (active & (gc.kind == AB))[:, None] & (st["ab_pending"] > 0)
        if pend.any():
            start_ab_r = pend & quiet_r
            if start_ab_r.any():
                _bg._plain._ab_refresh(gc, t, start_ab_r, st, I)
    if picks is not None:
        _pb_refresh(gc, t, _lowest_a_rank(gc, picks), due, st, I)
    return idle


_bg._state, _bg._decide = _state, _decide


# ------------------------------------------------------ the tick loops
def simulate(traffic, config, cells, itype=np.int32, record=False):
    """Results of `cells` ((policy, scenario index, density) triples of
    `traffic`'s grid) as dicts of `FIELDS`. With `record` (closed loop)
    also each cell's serves as (tick, bank, row, is_write) arrays."""
    gc = _Cells(traffic, config, cells, itype)
    if traffic.mode == "closed":
        return _bg._run_closed(gc, traffic, config, itype, record)
    return _bg._run_open(gc, traffic, config, itype)
