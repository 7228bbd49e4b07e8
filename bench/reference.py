"""Plain reference of the sweep engine's integer tick contract
(`docs/tick-contract.md`), which decides a run's `correct`.

It imports nothing of the program. It is a copy, cut down to the
built-in policy classes the traffic files name, of the program's batched
numpy backend (`repro.core.sweep.engine._run_batched` and
`_run_batched_closed`, with `sweep.policies.select_batch` and
`could_pick`, `sweep.arbiter.arbiter_scores_masked`, the score fields of
`sweep.fields`, `engine._finalize`, `refresh.sim.energy_proxy` and the
tick quantization of `engine.TickTiming`). The program's own tests pin
that backend bit for bit to the scalar oracle and to
`DramSim.run_ticks`. The DRAM timing comes from the configuration file,
not from the program.

`simulate` runs any subset of a grid's cells, in lock-step over stacked
``[G, B]`` planes; a cell's result depends on nothing but its own policy,
scenario and density and on the grid's horizon, which is computed from
the whole traffic as the program computes it. Every integer plane and
counter is of the integer type `itype`: int32 is the contract; a
narrower type is the benchmark's control (the arbitration score, a
packed 26-bit field, stays int32).
"""
from __future__ import annotations

import math

import numpy as np

#: read-latency histogram width (ticks); larger waits clip into the top bin
MAX_LAT_TICKS = 4095

# packed arbitration score (descending priority): drain-mode write,
# closed-loop queue occupancy, row hit, no sibling-subarray refresh, age
AGE_CAP = (1 << 20) - 1
W_NOCONF = 1 << 20
W_HIT = 1 << 21
W_OCC = 1 << 22
OCC_CAP = 7
W_WRITE = 1 << 25

IDEAL, AB, RR, DARP, ELASTIC, HIRA = range(6)

#: the built-in policy classes by registry name, with their traits
POLICIES = {
    "ideal": dict(kind=IDEAL),
    "ref_ab": dict(kind=AB),
    "ref_pb": dict(kind=RR),
    "sarp_pb": dict(kind=RR, sarp=True),
    "darp": dict(kind=DARP, wrp=True),
    "darp_ooo": dict(kind=DARP),
    "dsarp": dict(kind=DARP, wrp=True, sarp=True),
    "elastic": dict(kind=ELASTIC, urgency=0.75),
    "hira": dict(kind=HIRA, sarp=True, hra=True),
}

_NEG = -(10 ** 9)
_KD = 64          # hira's (demand, lag) key: demand * _KD + (lag + budget)

#: the result fields of one cell, as the program's `CellResult` names them
FIELDS = ("policy", "scenario", "density_gb", "makespan", "reads_done",
          "writes_done", "avg_read_latency", "p99_read_latency",
          "refreshes_pb", "refreshes_ab", "row_hits", "row_misses",
          "energy", "max_abs_lag", "finished", "mode", "core_finish")


# ------------------------------------------------------------- timing
def ns_timing(config: dict, density: int) -> dict:
    """The configuration's DRAM timing at one density, in ns."""
    tm = config["timing_ns"]
    ab, pb = tm["tRFC_ab_pb"][str(density)]
    return dict(tm, tRFC_ab=ab, tRFC_pb=pb)


def tick_timing(config: dict, density: int) -> dict:
    """`ns_timing` quantized to ticks: ``max(1, int(ns / dt + 0.5))``."""
    T = ns_timing(config, density)
    dt = config["dt_ns"]
    lay = config["layout"]

    def tk(ns):
        return max(1, int(ns / dt + 0.5))

    refi = tk(T["tREFI"])
    n_banks_total = lay["n_channels"] * lay["n_ranks"] * lay["n_banks"]
    return dict(REFI=refi, REFI_PB=max(1, refi // n_banks_total),
                RFC_PB=tk(T["tRFC_pb"]), RFC_AB=tk(T["tRFC_ab"]),
                HIT=tk(T["tCL"] + T["tBL"]),
                MISS=tk(T["tRP"] + T["tRCD"] + T["tCL"] + T["tBL"]),
                WR=tk(T["tWR"]), TURN=tk(T["tWTR"]), RTR=tk(T["tRTR"]),
                SARP_PEN=tk(T["sarp_penalty"]),
                budget=int(T["refresh_budget"]))


def energy_proxy(T: dict, n_ranks_total: int, n_banks: int,
                 makespan_ns: float, reads: int, writes: int, misses: int,
                 ref_pb: int, ref_ab: int) -> float:
    """The simulator's energy proxy (arbitrary units)."""
    return (0.5 * makespan_ns * n_ranks_total
            + 12.0 * misses
            + 6.0 * (reads + writes)
            + 0.15 * T["tRFC_pb"] * ref_pb
            + 0.15 * T["tRFC_ab"] * ref_ab * n_banks / 2)


# ------------------------------------------------------------- policies
def _could_pick(*, kind, lag, demand, write_window, budget, wrp):
    bud = budget[:, None]
    owed = (lag > 0).any(axis=1)
    pullable = (lag > -bud).any(axis=1)
    quiet_cell = demand.sum(axis=1) == 0
    return (owed
            | ((kind == ELASTIC) & quiet_cell & pullable)
            | (write_window & pullable
               & (((kind == DARP) & wrp) | (kind == HIRA))))


def _pick_one(cand, key, allow):
    G, B = cand.shape
    kmax = np.where(cand, key, _NEG)
    b = np.argmax(kmax, axis=1)
    ok = allow & cand[np.arange(G), b]
    return (np.arange(B)[None, :] == b[:, None]) & ok[:, None]


def _select(*, kind, lag, ready, idle, demand, write_window, budget, wrp,
            urgent_at, rr):
    """Per-bank refresh picks of the per-bank policy classes."""
    G, B = lag.shape
    vec = kind >= RR
    bud = budget[:, None]
    forced = vec[:, None] & (lag >= bud) & ready
    lag2 = lag - forced
    can = vec & ~forced.any(axis=1)
    picks = forced
    rr_new = rr
    is_rr = can & (kind == RR)
    if is_rr.any():
        idx = rr % B
        ar = np.arange(G)
        rr_elig = is_rr & (lag2[ar, idx] > 0) & ready[ar, idx]
        picks = picks | ((np.arange(B)[None, :] == idx[:, None])
                         & rr_elig[:, None])
        rr_new = rr + rr_elig
    is_darp = can & (kind == DARP)
    if is_darp.any():
        ww = write_window & wrp
        cand = (ready & idle & (demand == 0)
                & np.where(ww[:, None], lag2 > -bud, lag2 > 0))
        picks = picks | _pick_one(cand, lag2, is_darp)
    is_el = can & (kind == ELASTIC)
    if is_el.any():
        pressure = demand.sum(axis=1)
        cand_rg = ready & idle & (demand == 0) & (lag2 > 0)
        c_quiet = ready & idle & (lag2 > -bud)
        c_high = ready & (lag2 >= urgent_at[:, None])
        cand_e = np.where((pressure == 0)[:, None], c_quiet,
                          np.where((pressure <= B)[:, None], cand_rg,
                                   c_high))
        picks = picks | _pick_one(cand_e, lag2, is_el)
    is_hira = can & (kind == HIRA)
    if is_hira.any():
        key_dl = demand * _KD + (lag2 + bud)
        hot = ready & (lag2 > 0) & (demand > 0)
        cold = ready & idle & (lag2 > 0) & (demand == 0)
        has_hot, has_cold = hot.any(axis=1), cold.any(axis=1)
        picks = picks | _pick_one(hot, key_dl, is_hira)
        picks = picks | _pick_one(cold, lag2, is_hira & ~has_hot)
        extra = ready & (lag2 > -bud)
        picks = picks | _pick_one(extra, key_dl,
                                  is_hira & ~has_hot & ~has_cold
                                  & write_window)
    return picks, rr_new


def _scores(t, *, has_req, idle, head_ready, bank_mid_ref, head_row,
            head_arrive, head_is_write, open_row, drain, rank_drain,
            rank_can_drain, occ=None):
    elig = has_req & idle & head_ready
    if rank_can_drain:
        elig &= ~rank_drain
    base = (np.minimum(t - head_arrive.astype(np.int32), AGE_CAP)
            + np.where(head_row == open_row, W_HIT, 0)
            + np.where(bank_mid_ref, 0, W_NOCONF))
    if occ is not None:
        base += W_OCC * np.minimum(occ.astype(np.int32), OCC_CAP)
    if drain.any():
        base += np.where(drain[:, None] & head_is_write, W_WRITE, 0)
    return np.where(elig, base, -1)


# ----------------------------------------------------------------- grid
class _Cells:
    """Per-cell constants of the cells to simulate, of integer type I."""

    def __init__(self, traffic, config, cells, I):
        lay = config["layout"]
        self.NB, self.NR, self.NC = (lay["n_banks"], lay["n_ranks"],
                                     lay["n_channels"])
        self.R = self.NR * self.NC
        self.B = self.R * self.NB
        self.S = lay["n_subarrays"]
        self.G = G = len(cells)
        self.cells = cells
        B, R = self.B, self.R
        tick = {d: tick_timing(config, d) for d in traffic.densities}
        self.tick = tick
        col = lambda f: np.array([tick[d][f] for _, _, d in cells], I)
        for f in ("REFI", "RFC_PB", "RFC_AB", "HIT", "MISS", "WR", "TURN",
                  "RTR", "SARP_PEN", "budget"):
            setattr(self, f, col(f))
        pol = [POLICIES[p] for p, _, _ in cells]
        self.kind = np.array([p["kind"] for p in pol], I)
        self.level_ab = self.kind == AB
        self.sarp = np.array([p.get("sarp", False) for p in pol])
        self.hra = np.array([p.get("hra", False) for p in pol])
        self.wrp = np.array([p.get("wrp", False) for p in pol])
        self.urgent_at = np.array(
            [max(1, int(p["urgency"] * tick[d]["budget"]))
             if "urgency" in p else 1 for p, (_, _, d) in zip(pol, cells)],
            I)
        self.phase = (np.arange(B)[None, :]
                      * np.array([tick[d]["REFI_PB"] for _, _, d in cells]
                                 )[:, None]).astype(I)
        self.rank_phase = (np.arange(R)[None, :]
                           * (self.REFI.astype(np.int64) // R)[:, None]
                           ).astype(I)
        self.scn = np.array([s for _, s, _ in cells], np.int64)
        svc = max(tick[d]["MISS"] for d in tick) \
            + max(tick[d]["WR"] for d in tick) \
            + max(tick[d]["TURN"] for d in tick) + 2
        rfc_ab = max(tick[d]["RFC_AB"] for d in tick)
        scns = traffic.scenarios
        if traffic.mode == "closed":
            span = max(int(s.think.sum(axis=1).max()) for s in scns)
            n_tot = max(int(s.is_write.size) for s in scns)
        else:
            span = max(int(s.arrive[-1]) for s in scns)
            n_tot = max(len(s) for s in scns)
        self.horizon = min(span + 4 * n_tot * svc + 8 * rfc_ab + 64, 1 << 28)


def _p99_ticks(hist_row, n_reads: int) -> int:
    if n_reads <= 0:
        return 0
    target = math.ceil(0.99 * n_reads)
    return int(np.searchsorted(np.cumsum(hist_row), target, side="left"))


def _finalize(gc, traffic, config, g, *, reads, writes, hits, misses, refpb,
              refab, lat_sum, hist, maxlag, last_done, finished,
              core_finish=None) -> dict:
    p, s, d = gc.cells[g]
    scn = traffic.scenarios[s]
    dt = config["dt_ns"]
    if core_finish is None:
        mode, cf = "open", ()
        makespan = float(last_done) * dt
    else:
        mode = "closed"
        nc = scn.n_cores
        cf = tuple(float(int(f)) * dt for f in list(core_finish)[:nc])
        makespan = float(max((int(f) for f in list(core_finish)[:nc]),
                             default=0)) * dt
    reads, writes = int(reads), int(writes)
    return dict(
        policy=p, scenario=scn.name, density_gb=d, makespan=makespan,
        reads_done=reads, writes_done=writes,
        avg_read_latency=(dt * int(lat_sum) / reads) if reads else 0.0,
        p99_read_latency=dt * _p99_ticks(hist, reads),
        refreshes_pb=int(refpb), refreshes_ab=int(refab),
        row_hits=int(hits), row_misses=int(misses),
        energy=energy_proxy(ns_timing(config, d), gc.R, gc.NB, makespan,
                            reads, writes, int(misses), int(refpb),
                            int(refab)),
        max_abs_lag=int(maxlag), finished=bool(finished), mode=mode,
        core_finish=cf)


# ------------------------------------------------------ the tick loops
def simulate(traffic, config, cells, itype=np.int32, record=False):
    """Results of `cells` ((policy, scenario index, density) triples of
    `traffic`'s grid) as dicts of `FIELDS`. With `record` (closed loop)
    also each cell's serves as (tick, bank, row, is_write) arrays."""
    gc = _Cells(traffic, config, cells, itype)
    if traffic.mode == "closed":
        return _run_closed(gc, traffic, config, itype, record)
    return _run_open(gc, traffic, config, itype)


def _ab_refresh(gc, t, start_ab_r, st, I):
    """All-bank refresh starts: SARP marks (and closes) only the target
    subarray ctr % S; a non-SARP refresh occupies the whole bank."""
    NB, S = gc.NB, gc.S
    m = np.repeat(start_ab_r, NB, axis=1)
    new_sub = st["ctr"] % S
    mark = (np.repeat(m, S, axis=1)
            & np.where(gc.sarp[:, None], np.repeat(new_sub, S, axis=1)
                       == st["sub_of_col"], True))
    st["ref_until_s"] = np.where(mark, (t + gc.RFC_AB)[:, None],
                                 st["ref_until_s"]).astype(I)
    st["open_row_s"] = np.where(mark, -1, st["open_row_s"]).astype(I)
    st["ctr"] = (st["ctr"] + (m & gc.sarp[:, None])).astype(I)
    st["ab_pending"] = (st["ab_pending"] - start_ab_r).astype(I)
    st["rank_drain"] = np.where(start_ab_r, st["ab_pending"] > 0,
                                st["rank_drain"])
    st["refab"] += start_ab_r.sum(axis=1).astype(I)


def _pb_refresh(gc, t, picks, due, st, I):
    """Per-bank refresh starts; an hra policy starts a refresh of a
    subarray the in-flight access is not using at t."""
    S = gc.S
    new_sub = st["ctr"] % S
    start = np.maximum(t, st["bank_free"])
    start = np.where(gc.hra[:, None] & (new_sub != st["open_sub"]), t, start)
    mark = (np.repeat(picks, S, axis=1)
            & np.where(gc.sarp[:, None], np.repeat(new_sub, S, axis=1)
                       == st["sub_of_col"], True))
    st["ref_until_s"] = np.where(
        mark, np.repeat(start + gc.RFC_PB[:, None], S, axis=1),
        st["ref_until_s"]).astype(I)
    st["open_row_s"] = np.where(mark, -1, st["open_row_s"]).astype(I)
    st["ctr"] = (st["ctr"] + picks).astype(I)
    st["issued"] = (st["issued"] + picks).astype(I)
    st["refpb"] += picks.sum(axis=1).astype(I)
    lag_after = due - st["issued"]
    st["maxlag"] = np.maximum(
        st["maxlag"], np.where(picks, np.abs(lag_after), 0).max(axis=1)
    ).astype(I)


def _state(gc, I):
    G, B, S, R, NC = gc.G, gc.B, gc.S, gc.R, gc.NC
    z = lambda *shape: np.zeros(shape, I)
    return dict(
        bank_free=z(G, B), ref_until_s=z(G, B * S),
        open_row_s=np.full((G, B * S), -1, I), open_sub=np.full((G, B), -1, I),
        ctr=z(G, B), issued=z(G, B), rr=z(G), wpend=z(G),
        drain=np.zeros(G, bool), last_op=np.zeros((G, NC), bool),
        last_rank=np.full((G, NC), -1, I), ab_pending=z(G, R),
        rank_drain=np.zeros((G, R), bool),
        reads=z(G), writes=z(G), hits=z(G), misses=z(G), refpb=z(G),
        refab=z(G), lat_sum=z(G), hist=z(G, MAX_LAT_TICKS + 1),
        maxlag=z(G), last_done=z(G),
        sub_of_col=np.tile(np.arange(S, dtype=I), B)[None, :])


def _decide(gc, t, st, active, kind_active, demand, I):
    """Phases B and C: per-rank all-bank debt, then refresh decisions.
    Returns ``due`` for the per-bank lag bookkeeping."""
    G, B, S, R, NB = gc.G, gc.B, gc.S, gc.R, gc.NB
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
    need = _could_pick(kind=kind_active, lag=lag, demand=demand,
                       write_window=st["drain"], budget=gc.budget,
                       wrp=gc.wrp)
    picks = None
    if need.any():
        picks, rr = _select(
            kind=np.where(need, kind_active, IDEAL), lag=lag, ready=ready,
            idle=idle, demand=demand, write_window=st["drain"],
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
                _ab_refresh(gc, t, start_ab_r, st, I)
    if picks is not None:
        _pb_refresh(gc, t, picks, due, st, I)
    return idle


def _serve(gc, t, ch, st, score, head, bank_mid, I):
    """Phase D for one channel: the best-scored eligible head request
    starts. Returns (cells, banks, is_write, done ticks) of the starts."""
    G, NB, S = gc.G, gc.NB, gc.S
    RBC = gc.NR * NB
    arG = np.arange(G)
    sc_ch = score[:, ch * RBC:(ch + 1) * RBC]
    bs_loc = sc_ch.argmax(axis=1)
    ok = sc_ch[arG, bs_loc] >= 0
    if not ok.any():
        return None
    gs = np.nonzero(ok)[0]
    bs = bs_loc[gs] + ch * RBC
    h_arr, h_row, h_sub, h_w, head_or = head
    row, sub = h_row[gs, bs], h_sub[gs, bs]
    arr, isw = h_arr[gs, bs], h_w[gs, bs]
    hit = row == head_or[gs, bs]
    lat = np.where(hit, gc.HIT[gs], gc.MISS[gs])
    lat = lat + np.where(gc.sarp[gs] & bank_mid[gs, bs], gc.SARP_PEN[gs], 0)
    lat = lat + np.where(isw != st["last_op"][gs, ch], gc.TURN[gs], 0)
    gr_b = bs // NB
    lr = st["last_rank"][gs, ch]
    lat = lat + np.where((lr >= 0) & (lr != gr_b), gc.RTR[gs], 0)
    done = (t + lat).astype(I)
    st["bank_free"][gs, bs] = done + np.where(isw, gc.WR[gs], 0)
    st["last_op"][gs, ch] = isw
    st["last_rank"][gs, ch] = gr_b
    st["open_row_s"][gs, bs * S + sub] = row
    st["open_sub"][gs, bs] = sub
    st["hits"][gs] += hit
    st["misses"][gs] += ~hit
    st["writes"][gs] += isw
    st["reads"][gs] += ~isw
    st["wpend"][gs] -= isw
    st["drain"][gs] &= ~(isw & (st["wpend"][gs] <= gc.LO))
    rmask = ~isw
    lrec = np.minimum(done - arr, MAX_LAT_TICKS).astype(I)
    st["lat_sum"][gs] += np.where(rmask, lrec, 0).astype(I)
    np.add.at(st["hist"], (gs[rmask], lrec[rmask]), 1)
    st["last_done"][gs] = np.maximum(st["last_done"][gs], done)
    return gs, bs, row, isw, done, rmask


def _run_open(gc, traffic, config, I):
    G, B, S, R, NB = gc.G, gc.B, gc.S, gc.R, gc.NB
    gc.HI, gc.LO = config["wbuf"]["hi"], config["wbuf"]["lo"]
    PAD = np.iinfo(I).max
    # per-(scenario, bank) FIFOs, padded to the longest
    split = []
    L = 1
    for tr in traffic.scenarios:
        per_bank = []
        for b in range(B):
            m = tr.bank == b
            per_bank.append((tr.arrive[m], tr.row[m], tr.sub[m],
                             tr.is_write[m]))
            L = max(L, int(m.sum()))
        split.append(per_bank)
    qa = np.full((G, B, L), PAD, I)
    qr, qs = np.zeros((G, B, L), I), np.zeros((G, B, L), I)
    qw = np.zeros((G, B, L), bool)
    n_pb = np.zeros((G, B), I)
    for g, s in enumerate(gc.scn):
        for b, (arr, row, sub, isw) in enumerate(split[s]):
            n = len(arr)
            n_pb[g, b] = n
            qa[g, b, :n], qr[g, b, :n] = arr, row
            qs[g, b, :n], qw[g, b, :n] = sub, isw
    n_tot = n_pb.sum(axis=1)
    next_arrive, next_w = qa[:, :, 0].copy(), qw[:, :, 0].copy()
    h_arr, h_row = qa[:, :, 0].copy(), qr[:, :, 0].copy()
    h_sub, h_w = qs[:, :, 0].copy(), qw[:, :, 0].copy()
    qa, qr = qa.reshape(G * B, L), qr.reshape(G * B, L)
    qs, qw = qs.reshape(G * B, L), qw.reshape(G * B, L)
    n_pb_flat = n_pb.reshape(G * B)
    st = _state(gc, I)
    n_arrived, n_served = np.zeros((G, B), I), np.zeros((G, B), I)
    active = n_tot > 0
    n_left = n_tot.astype(np.int64)
    kind_active = np.where(active, gc.kind, IDEAL)
    rank_can_drain = bool(gc.level_ab.any())
    nav, nwv = next_arrive.ravel(), next_w.ravel()
    t = 0
    alive = int(active.sum())
    while alive and t < gc.horizon:
        # ---- A: arrivals (one queue slot per pass handles bursts)
        while True:
            can = next_arrive <= t
            if not can.any():
                break
            st["wpend"] += (can & next_w).sum(axis=1).astype(I)
            n_arrived += can.astype(I)
            gf = np.nonzero(can.ravel())[0]
            slot = n_arrived.ravel()[gf]
            sl = np.minimum(slot, L - 1)
            nav[gf] = np.where(slot >= n_pb_flat[gf], PAD, qa[gf, sl])
            nwv[gf] = qw[gf, sl]
        st["drain"] |= st["wpend"] >= gc.HI
        demand = (n_arrived - n_served).astype(I)
        idle = _decide(gc, t, st, active, kind_active, demand, I)
        # ---- D: arbitration, one start per channel
        has_req = demand > 0
        if not has_req.any():
            t += 1
            continue
        ru3 = st["ref_until_s"].reshape(G, B, S)
        head_ru = np.take_along_axis(ru3, h_sub[:, :, None], 2)[:, :, 0]
        head_or = np.take_along_axis(st["open_row_s"].reshape(G, B, S),
                                     h_sub[:, :, None], 2)[:, :, 0]
        bank_mid = (ru3 > t).any(axis=2)
        score = _scores(
            t, has_req=has_req, idle=idle, head_ready=head_ru <= t,
            bank_mid_ref=bank_mid, head_row=h_row, head_arrive=h_arr,
            head_is_write=h_w, open_row=head_or, drain=st["drain"],
            rank_drain=np.repeat(st["rank_drain"], NB, axis=1),
            rank_can_drain=rank_can_drain)
        head = (h_arr, h_row, h_sub, h_w, head_or)
        for ch in range(gc.NC):
            out = _serve(gc, t, ch, st, score, head, bank_mid, I)
            if out is None:
                continue
            gs, bs = out[0], out[1]
            n_served[gs, bs] += 1
            gf = gs * B + bs
            sl = np.minimum(n_served[gs, bs], L - 1)
            h_arr[gs, bs], h_row[gs, bs] = qa[gf, sl], qr[gf, sl]
            h_sub[gs, bs], h_w[gs, bs] = qs[gf, sl], qw[gf, sl]
            # ---- E: retire finished cells
            n_left[gs] -= 1
            if (n_left[gs] == 0).any():
                done_cells = gs[n_left[gs] == 0]
                active[done_cells] = False
                kind_active[done_cells] = IDEAL
                alive = int(active.sum())
        t += 1
    return [_finalize(gc, traffic, config, g, reads=st["reads"][g],
                      writes=st["writes"][g], hits=st["hits"][g],
                      misses=st["misses"][g], refpb=st["refpb"][g],
                      refab=st["refab"][g], lat_sum=st["lat_sum"][g],
                      hist=st["hist"][g], maxlag=st["maxlag"][g],
                      last_done=st["last_done"][g], finished=not active[g])
            for g in range(G)]


def _run_closed(gc, traffic, config, I, record):
    G, B, S, R, NB = gc.G, gc.B, gc.S, gc.R, gc.NB
    wb = config["wbuf"]
    gc.HI, gc.LO, CAP = wb["hi"], wb["lo"], wb["cap"]
    PAD = np.iinfo(I).max
    scns = traffic.scenarios
    C = max(s.n_cores for s in scns)
    N = max(int(s.is_write.shape[1]) for s in scns)
    K = max(s.mlp for s in scns)
    LQ = 1 << max(1, (C * K + CAP + 1 - 1).bit_length())
    QM = LQ - 1
    sw = np.zeros((G, C, N), bool)
    sb, sr = np.zeros((G, C, N), I), np.zeros((G, C, N), I)
    ssub, sth = np.zeros((G, C, N), I), np.zeros((G, C, N), I)
    n_req = np.zeros((G, C), I)
    mlp_col = np.zeros((G, 1), I)
    for g, s in enumerate(gc.scn):
        dem = scns[s]
        c, n = dem.is_write.shape
        sw[g, :c, :n], sb[g, :c, :n], sr[g, :c, :n] = (dem.is_write,
                                                       dem.bank, dem.row)
        ssub[g, :c, :n], sth[g, :c, :n] = dem.sub, dem.think
        n_req[g, :c] = n
        mlp_col[g] = dem.mlp
    sw, sb, sr = (a.reshape(G * C, N) for a in (sw, sb, sr))
    ssub, sth = ssub.reshape(G * C, N), sth.reshape(G * C, N)
    # ring bank queues, flat [G*B, LQ]
    qa, qr = np.zeros((G * B, LQ), I), np.zeros((G * B, LQ), I)
    qs, qc = np.zeros((G * B, LQ), I), np.zeros((G * B, LQ), I)
    qw = np.zeros((G * B, LQ), bool)
    q_head, q_tail = np.zeros((G, B), I), np.zeros((G, B), I)
    next_idx, next_issue = np.zeros((G, C), I), np.zeros((G, C), I)
    out_reads = np.zeros((G, C), I)
    remaining = n_req.copy()
    finish = np.where(remaining == 0, 0, -1).astype(I)
    comp_t = np.full((G, C, K), PAD, I)
    st = _state(gc, I)
    active = (remaining > 0).any(axis=1)
    kind_active = np.where(active, gc.kind, IDEAL)
    rank_can_drain = bool(gc.level_ab.any())
    arG = np.arange(G, dtype=np.int64)
    arB = np.arange(B, dtype=np.int64)
    flat_gc = arG[:, None] * C + np.arange(C, dtype=np.int64)[None, :]
    flat_gb = arG[:, None] * B + arB[None, :]
    serves = [[] for _ in range(G)] if record else None
    t = 0
    alive = int(active.sum())
    while alive and t < gc.horizon:
        # ---- 0: outstanding-read completions
        exp = comp_t <= t
        if exp.any():
            n_exp = exp.sum(axis=2).astype(I)
            out_reads -= n_exp
            remaining -= n_exp
            comp_t[exp] = PAD
        # ---- 1: core issue (at most one per core per tick, core order)
        sl = np.minimum(next_idx, N - 1)
        can = (next_idx < n_req) & (next_issue <= t)
        if can.any():
            head_w = sw[flat_gc, sl]
            want_w = can & head_w
            want_r = can & ~head_w & (out_reads < mlp_col)
            # write-buffer backpressure, first-come in core order
            rank_w = np.cumsum(want_w, axis=1) - want_w
            ok_w = want_w & (rank_w < (CAP - st["wpend"])[:, None])
            issue = ok_w | want_r
            if issue.any():
                hb = sb[flat_gc, sl]
                oh = issue[:, :, None] & (hb[:, :, None] == arB[None, None, :])
                pref = np.cumsum(oh, axis=1) - oh
                gi, ci = np.nonzero(issue)
                bk = hb[gi, ci]
                slot = (q_tail[gi, bk] + pref[gi, ci, bk]) & QM
                gf = gi * B + bk
                fgc = gi * C + ci
                idx2 = sl[gi, ci]
                qa[gf, slot] = t
                qr[gf, slot] = sr[fgc, idx2]
                qs[gf, slot] = ssub[fgc, idx2]
                qw[gf, slot] = sw[fgc, idx2]
                qc[gf, slot] = ci
                q_tail += oh.sum(axis=1).astype(I)
                st["wpend"] += ok_w.sum(axis=1).astype(I)
                out_reads += want_r.astype(I)
                remaining -= ok_w.astype(I)     # writes retire at issue
                next_issue[issue] = t + sth[fgc, idx2]
                next_idx[issue] += 1
        newly = (remaining == 0) & (finish < 0)
        if newly.any():
            finish[newly] = t
            done_cells = active & ~(remaining > 0).any(axis=1)
            if done_cells.any():
                active &= ~done_cells
                kind_active[done_cells] = IDEAL
                alive = int(active.sum())
                if not alive:
                    break
        # ---- 2: write-drain watermark
        st["drain"] |= st["wpend"] >= gc.HI
        demand = (q_tail - q_head).astype(I)
        idle = _decide(gc, t, st, active, kind_active, demand, I)
        # ---- 5: occupancy-aware arbitration, one start per channel
        has_req = (demand > 0) & active[:, None]
        if not has_req.any():
            t += 1
            continue
        hslot = q_head & QM
        h_arr, h_row = qa[flat_gb, hslot], qr[flat_gb, hslot]
        h_sub, h_w = qs[flat_gb, hslot], qw[flat_gb, hslot]
        ru3 = st["ref_until_s"].reshape(G, B, S)
        head_ru = np.take_along_axis(ru3, h_sub[:, :, None], 2)[:, :, 0]
        head_or = np.take_along_axis(st["open_row_s"].reshape(G, B, S),
                                     h_sub[:, :, None], 2)[:, :, 0]
        bank_mid = (ru3 > t).any(axis=2)
        score = _scores(
            t, has_req=has_req, idle=idle, head_ready=head_ru <= t,
            bank_mid_ref=bank_mid, head_row=h_row, head_arrive=h_arr,
            head_is_write=h_w, open_row=head_or, drain=st["drain"],
            rank_drain=np.repeat(st["rank_drain"], NB, axis=1),
            rank_can_drain=rank_can_drain, occ=demand)
        head = (h_arr, h_row, h_sub, h_w, head_or)
        for ch in range(gc.NC):
            out = _serve(gc, t, ch, st, score, head, bank_mid, I)
            if out is None:
                continue
            gs, bs, row, isw, done, rmask = out
            core = qc[gs * B + bs, hslot[gs, bs]]
            q_head[gs, bs] += 1
            if record:
                for k in range(len(gs)):
                    serves[gs[k]].append((t, bs[k], row[k], isw[k]))
            # reads: park the data return in the core's MLP window slot
            if rmask.any():
                gr, cr = gs[rmask], core[rmask]
                k = np.argmax(comp_t[gr, cr] == PAD, axis=1)
                comp_t[gr, cr, k] = done[rmask]
        t += 1
    fin = np.where(finish < 0, t, finish)
    results = [_finalize(gc, traffic, config, g, reads=st["reads"][g],
                         writes=st["writes"][g], hits=st["hits"][g],
                         misses=st["misses"][g], refpb=st["refpb"][g],
                         refab=st["refab"][g], lat_sum=st["lat_sum"][g],
                         hist=st["hist"][g], maxlag=st["maxlag"][g],
                         last_done=st["last_done"][g],
                         finished=not active[g], core_finish=fin[g])
               for g in range(G)]
    if record:
        return results, [tuple(np.array(c) for c in zip(*sv))
                         for sv in serves]
    return results
