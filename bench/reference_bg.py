"""Plain reference of the integer tick contract (`docs/tick-contract.md`)
for a DRAM with bank groups and same-bank refresh (DDR5 REFsb), which
decides a run's `correct` on such a configuration.

It imports nothing of the program. It is `bench/reference.py` (a copy,
cut down to the built-in policy classes the traffic files name, of the
program's batched numpy backend) with the two mechanisms bank groups
add, and takes that module's unchanged pieces from it: the policy
classes and their vectorised picks, the arbitration score, the
all-bank refresh start and the result fields.

  * The per-bank refresh unit. A per-bank-level policy refreshes a
    same-bank set: bank k of every group of a rank, global banks
    ``gr * n_banks + g * K + k`` (K banks per group), unit
    ``u = gr * K + k``. Debt (due, issued, lag) is per unit, phased
    ``u * (REFI // U)`` apart. The policies see units: a unit is ready
    and idle when every bank of it is, and its demand is theirs summed.
    A refresh starts once every bank of the unit can, and marks every
    bank of it with the one-subarray (SARP) or all-subarray rule.
  * The bank-group serve term. A start in the bank group of its
    channel's previous start (same rank, same group) takes ``CCDL =
    ticks(tCCD_L) - ticks(tCCD_S)`` more.

Without bank groups a unit is a bank and ``CCDL`` is 0: the answers are
`bench/reference.py`'s, bit for bit. The DRAM timing and layout come
from the configuration file. Every integer plane and counter is of the
integer type `itype`: int32 is the contract; a narrower type is the
benchmark's control.
"""
from __future__ import annotations

import importlib.util
import os

import numpy as np


def _plain_reference():
    """`bench/reference.py`, loaded from beside this file."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "reference.py")
    spec = importlib.util.spec_from_file_location("bench_reference_plain",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_plain = _plain_reference()
MAX_LAT_TICKS, IDEAL, AB = _plain.MAX_LAT_TICKS, _plain.IDEAL, _plain.AB
POLICIES, FIELDS = _plain.POLICIES, _plain.FIELDS
ns_timing = _plain.ns_timing


# ------------------------------------------------------------- timing
def _groups(config: dict) -> int:
    return config["layout"].get("n_bank_groups", 1)


def tick_timing(config: dict, density: int) -> dict:
    """`bench/reference.py`'s quantized timing, with the refresh units
    ``U``, their interval ``REFI_SB = REFI // U`` and the serve term
    ``CCDL``."""
    out = _plain.tick_timing(config, density)
    lay, T = config["layout"], ns_timing(config, density)
    nbg = _groups(config)
    U = lay["n_channels"] * lay["n_ranks"] * (lay["n_banks"] // nbg)

    def tk(ns):
        return max(1, int(ns / config["dt_ns"] + 0.5))

    return dict(out, U=U, REFI_SB=max(1, out["REFI"] // U),
                CCDL=tk(T["tCCD_L"]) - tk(T["tCCD_S"]) if nbg > 1 else 0)


def energy_proxy(T: dict, n_ranks_total: int, n_banks: int, n_groups: int,
                 makespan_ns: float, reads: int, writes: int, misses: int,
                 ref_pb: int, ref_ab: int) -> float:
    """The simulator's energy proxy (arbitrary units); a per-bank-level
    refresh covers `n_groups` banks."""
    return (0.5 * makespan_ns * n_ranks_total
            + 12.0 * misses
            + 6.0 * (reads + writes)
            + 0.15 * T["tRFC_pb"] * ref_pb * n_groups
            + 0.15 * T["tRFC_ab"] * ref_ab * n_banks / 2)


# ------------------------------------------------------------- units
def _units(x, how, R, NBG):
    """[G, B] per bank -> [G, U] per unit: `how` over each unit's banks."""
    G, B = x.shape
    K = B // (R * NBG)
    return getattr(x.reshape(G, R, NBG, K), how)(axis=2).reshape(G, R * K)


def _banks(u, R, NBG):
    """[G, U] per unit -> [G, B]: each unit's value on every bank of it."""
    G, U = u.shape
    K = U // R
    return np.broadcast_to(u.reshape(G, R, 1, K),
                           (G, R, NBG, K)).reshape(G, U * NBG)


class _Cells(_plain._Cells):
    """`bench/reference.py`'s per-cell constants, with the refresh units'
    phases and the serve term."""

    def __init__(self, traffic, config, cells, I):
        super().__init__(traffic, config, cells, I)
        self.NBG = _groups(config)
        self.BPG = self.NB // self.NBG       # banks per group
        self.U = self.R * self.BPG
        tick = {d: tick_timing(config, d) for d in traffic.densities}
        self.CCDL = np.array([tick[d]["CCDL"] for _, _, d in cells], I)
        self.phase = (np.arange(self.U)[None, :]
                      * np.array([tick[d]["REFI_SB"] for _, _, d in cells]
                                 )[:, None]).astype(I)


def _finalize(gc, traffic, config, g, **stats) -> dict:
    out = _plain._finalize(gc, traffic, config, g, **stats)
    out["energy"] = energy_proxy(
        ns_timing(config, out["density_gb"]), gc.R, gc.NB, gc.NBG,
        out["makespan"], out["reads_done"], out["writes_done"],
        out["row_misses"], out["refreshes_pb"], out["refreshes_ab"])
    return out


# ------------------------------------------------------ the tick loops
def simulate(traffic, config, cells, itype=np.int32, record=False):
    """Results of `cells` ((policy, scenario index, density) triples of
    `traffic`'s grid) as dicts of `FIELDS`. With `record` (closed loop)
    also each cell's serves as (tick, bank, row, is_write) arrays."""
    gc = _Cells(traffic, config, cells, itype)
    if traffic.mode == "closed":
        return _run_closed(gc, traffic, config, itype, record)
    return _run_open(gc, traffic, config, itype)


def _pb_refresh(gc, t, picks, due, st, I):
    """Per-unit refresh starts: each picked unit starts once every bank
    of it can (an hra policy: a bank whose refresh subarray is not the
    one its in-flight access uses can at t), and marks every bank."""
    S, R, NBG = gc.S, gc.R, gc.NBG
    picks_b = _banks(picks, R, NBG)
    new_sub = st["ctr"] % S
    start = np.maximum(t, st["bank_free"])
    start = np.where(gc.hra[:, None] & (new_sub != st["open_sub"]), t, start)
    start = _banks(_units(start, "max", R, NBG), R, NBG)
    mark = (np.repeat(picks_b, S, axis=1)
            & np.where(gc.sarp[:, None], np.repeat(new_sub, S, axis=1)
                       == st["sub_of_col"], True))
    st["ref_until_s"] = np.where(
        mark, np.repeat(start + gc.RFC_PB[:, None], S, axis=1),
        st["ref_until_s"]).astype(I)
    st["open_row_s"] = np.where(mark, -1, st["open_row_s"]).astype(I)
    st["ctr"] = (st["ctr"] + picks_b).astype(I)
    st["issued"] = (st["issued"] + picks).astype(I)
    st["refpb"] += picks.sum(axis=1).astype(I)
    lag_after = due - st["issued"]
    st["maxlag"] = np.maximum(
        st["maxlag"], np.where(picks, np.abs(lag_after), 0).max(axis=1)
    ).astype(I)


def _state(gc, I):
    st = _plain._state(gc, I)
    st["issued"] = np.zeros((gc.G, gc.U), I)
    st["last_bg"] = np.full((gc.G, gc.NC), -1, I)
    return st


def _decide(gc, t, st, active, kind_active, demand, I):
    """Phases B and C: per-rank all-bank debt, then refresh decisions on
    the units' view. Returns the banks' ``idle`` for arbitration."""
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
    demand_u = _units(demand, "sum", R, NBG)
    ready_u = _units(ready, "all", R, NBG)
    idle_u = _units(idle, "all", R, NBG)
    need = _plain._could_pick(kind=kind_active, lag=lag, demand=demand_u,
                              write_window=st["drain"], budget=gc.budget,
                              wrp=gc.wrp)
    picks = None
    if need.any():
        picks, rr = _plain._select(
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
                _plain._ab_refresh(gc, t, start_ab_r, st, I)
    if picks is not None:
        _pb_refresh(gc, t, picks, due, st, I)
    return idle


def _serve(gc, t, ch, st, score, head, bank_mid, I):
    """Phase D for one channel: the best-scored eligible head request
    starts. Returns (cells, banks, rows, is_write, done ticks, reads)."""
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
    bg_b = bs // gc.BPG                     # bank group, over all ranks
    lat = lat + np.where(st["last_bg"][gs, ch] == bg_b, gc.CCDL[gs], 0)
    done = (t + lat).astype(I)
    st["bank_free"][gs, bs] = done + np.where(isw, gc.WR[gs], 0)
    st["last_op"][gs, ch] = isw
    st["last_rank"][gs, ch] = gr_b
    st["last_bg"][gs, ch] = bg_b
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


def _heads(gc, t, st, h_sub):
    """Each bank's head request's own subarray's refresh and open-row
    state, and whether any subarray of the bank is mid-refresh."""
    G, B, S = gc.G, gc.B, gc.S
    ru3 = st["ref_until_s"].reshape(G, B, S)
    head_ru = np.take_along_axis(ru3, h_sub[:, :, None], 2)[:, :, 0]
    head_or = np.take_along_axis(st["open_row_s"].reshape(G, B, S),
                                 h_sub[:, :, None], 2)[:, :, 0]
    return head_ru, head_or, (ru3 > t).any(axis=2)


def _run_open(gc, traffic, config, I):
    G, B, NB = gc.G, gc.B, gc.NB
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
        head_ru, head_or, bank_mid = _heads(gc, t, st, h_sub)
        score = _plain._scores(
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
    G, B, NB = gc.G, gc.B, gc.NB
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
        head_ru, head_or, bank_mid = _heads(gc, t, st, h_sub)
        score = _plain._scores(
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
