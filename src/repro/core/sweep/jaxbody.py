"""jaxbody — the traced tick loop of the sweep engine's device path.

The open- and closed-loop tick bodies (tick-contract phases A-E / 0-5)
are pure functions of three ingredients:

  * ``TickCfg``  — static shape/config facts (frozen dataclass, hashable,
                   usable as a jit static argument),
  * ``cst``      — per-grid constant planes (jnp arrays, traced so one
                   compiled loop serves many grids of the same shape),
  * ``s``        — the per-tick state dict.

`loop` runs either body to completion as one `lax.while_loop`. The engine's
jax backend calls it jitted (`run_loop`, on the inputs `program` builds);
integer arithmetic keeps it bit-identical to the batched numpy backend
and the scalar oracle.

Everything is int32/bool (tick-contract section 3). The ``*_state0``
functions build the canonical initial state and each ``*_body`` returns a
dict with exactly the same keys; the `pallas-lint` analysis pass (PL505)
checks the key sets statically, because a key dropped from the body's
return dict would silently freeze that state plane.

Both bodies name their phases with `jax.named_scope`, one set of names
for both modes, so a profiler trace or the compiled HLO's ``op_name``
says which phase an operation belongs to: ``tick.front_end`` (closed
phases 0-2, open phase A), ``tick.refresh`` (3-4, B-C),
``tick.arbitrate`` (the head reads through `scores`) and
``tick.serve`` (the per-channel serve loop with the histogram update).
Scopes are metadata only: the traced program runs the same operations.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.sweep.engine import MAX_LAT_TICKS, _PAD_ARRIVE
from repro.core.sweep.policies import (KIND_AB, KIND_IDEAL, KIND_STAG,
                                       per_bank, per_unit, refpb_rules_first,
                                       refpb_rules_next, refpb_rules_ready,
                                       select_batch)


# ------------------------------------------------------------------ config
@dataclass(frozen=True)
class TickCfg:
    """Static facts of one grid's tick loop (hashable for jit).

    ``closed`` selects the closed-loop body; the open-loop fields (``L``)
    and closed-loop fields (``C``/``N``/``K``/``LQ``/``CAP``) are only
    meaningful for their mode and default to 0 in the other."""
    closed: bool
    B: int                  # global banks per cell (NC * NR * NB)
    S: int                  # subarrays per bank
    NB: int                 # banks per rank
    NR: int                 # ranks per channel
    R: int                  # global ranks (NC * NR)
    NC: int                 # channels
    HI: int                 # write-drain high watermark
    LO: int                 # write-drain low watermark
    has_stag: bool          # any staggered_ab cell in the grid
    has_hra: bool           # any HiRA-trait cell in the grid
    L: int = 0              # open: padded per-bank FIFO length
    C: int = 0              # closed: padded core count
    N: int = 0              # closed: padded per-core stream length
    K: int = 0              # closed: MLP window slots
    LQ: int = 0             # closed: ring-queue capacity (power of two)
    CAP: int = 0            # closed: shared write-buffer capacity
    NBG: int = 1            # bank groups per rank
    pb_rules: bool = False  # any cell with a per-bank issue rule

    @property
    def BPG(self) -> int:
        """Banks per bank group."""
        return self.NB // self.NBG

    @property
    def U(self) -> int:
        """Refresh units per cell: banks, or same-bank sets with bank
        groups (`policies.per_unit`)."""
        return self.R * self.BPG


def open_cfg(grid) -> TickCfg:
    spec = grid.spec
    return TickCfg(closed=False, B=grid.B, S=grid.S, NB=grid.NB,
                   NR=grid.NR, R=grid.R, NC=grid.NC, HI=spec.wbuf_hi,
                   LO=spec.wbuf_lo, has_stag=grid.has_stag,
                   has_hra=grid.has_hra, L=grid.L, NBG=grid.NBG,
                   pb_rules=grid.has_pb_rules)


def closed_cfg(grid) -> TickCfg:
    spec = grid.spec
    return TickCfg(closed=True, B=grid.B, S=grid.S, NB=grid.NB,
                   NR=grid.NR, R=grid.R, NC=grid.NC, HI=spec.wbuf_hi,
                   LO=spec.wbuf_lo, has_stag=grid.has_stag,
                   has_hra=grid.has_hra, C=grid.C, N=grid.N, K=grid.K,
                   LQ=grid.LQ, CAP=spec.wbuf_cap, NBG=grid.NBG,
                   pb_rules=grid.has_pb_rules)


# ------------------------------------------------------------------ consts
def _j32(x):
    return jnp.asarray(x, jnp.int32)


def _shared_consts(grid) -> dict:
    """Per-cell constant planes common to both modes (all [G] int32/bool
    except the staggered refresh phases, [G, R] and [G, U], and the
    shared scalar horizon); the tCCD_L term only with bank groups, the
    per-bank issue rules' spacing and round flag only where some cell
    has one."""
    return dict(
        phase=_j32(grid.phase), rank_phase=_j32(grid.rank_phase),
        kind=_j32(grid.kind), level_ab=jnp.asarray(grid.level_ab),
        sarp=jnp.asarray(grid.sarp), hra=jnp.asarray(grid.hra),
        wrp=jnp.asarray(grid.wrp), urgent_at=_j32(grid.urgent_at),
        budget=_j32(grid.budget),
        REFI=_j32(grid.REFI), RFC_PB=_j32(grid.RFC_PB),
        RFC_AB=_j32(grid.RFC_AB), HIT=_j32(grid.HIT),
        MISS=_j32(grid.MISS), WR=_j32(grid.WR), TURN=_j32(grid.TURN),
        RTR=_j32(grid.RTR), SARP_PEN=_j32(grid.SARP_PEN),
        horizon=jnp.int32(grid.horizon),
        **({"CCDL": _j32(grid.CCDL)} if grid.NBG > 1 else {}),
        **({"PB2PB": _j32(grid.PB2PB),
            "pb_rounds": jnp.asarray(grid.pb_rounds)}
           if grid.has_pb_rules else {}))


def open_consts(grid) -> dict:
    G, B, L = grid.G, grid.B, grid.L
    return dict(
        qa=_j32(grid.q_arrive.reshape(G * B, L)),
        qr=_j32(grid.q_row.reshape(G * B, L)),
        qs=_j32(grid.q_sub.reshape(G * B, L)),
        qw=jnp.asarray(grid.q_write.reshape(G * B, L)),
        n_pb=_j32(grid.n_per_bank),
        n_tot=_j32(grid.n_tot),
        **_shared_consts(grid))


def closed_consts(grid) -> dict:
    """The closed body's constant planes. The per-core request streams
    are three [G*C, N] int32 planes: `srec`, the packed record
    (`stream_record`: global subarray and kind), `sr` (row) and `sth`
    (think ticks)."""
    G, C, N = grid.G, grid.C, grid.N
    srec = stream_record(grid.s_bank, grid.s_sub, grid.s_write, grid.S)
    return dict(
        srec=_j32(srec.reshape(G * C, N)),
        sr=_j32(grid.s_row.reshape(G * C, N)),
        sth=_j32(grid.s_think.reshape(G * C, N)),
        n_req=_j32(grid.n_req_c),
        mlp=_j32(grid.mlp_g),
        **_shared_consts(grid))


def stream_record(bank, sub, is_write, S: int):
    """A closed stream request's packed record, ``gsub << 1 | is_write``
    with ``gsub = bank * S + sub`` the request's global subarray (the
    column of the [G, B*S] planes). ``B * S`` stays far below 2**30 in
    any layout, so the record fits an int32."""
    return ((bank * S + sub) << 1) | is_write


# ------------------------------------------------------------ lane picks
def pick(plane, idx):
    """``plane[..., idx]``: for each index of `idx`, the one entry of
    `plane`'s last axis it names, as a one-hot compare and a reduction
    along that axis. On a TPU this is a dense pass over `plane`, where a
    gather serialises its indices. Exactly one lane matches an index in
    ``[0, plane.shape[-1])``, so the result equals the gather bit for bit;
    an index out of that range reads 0 (False)."""
    hot = (lax.broadcasted_iota(jnp.int32, plane.shape, plane.ndim - 1)
           == idx[..., None])
    if plane.dtype == jnp.bool_:
        return (hot & plane).any(axis=-1)
    return jnp.where(hot, plane, 0).sum(axis=-1, dtype=plane.dtype)


def next_requests(cfg: TickCfg, cst: dict, next_idx):
    """Each core's request at its stream position ``next_idx`` [G, C]
    (clamped to the stream's last entry), unpacked from the [G*C, N]
    stream planes: ``(bank, sub, is_write, row, think)``, each [G, C].
    Each plane is read by a one-hot `pick` over its N lanes; the clamped
    index is in range, so it reads what a gather would, bit for bit."""
    sl = jnp.minimum(next_idx, cfg.N - 1).ravel()

    def read(plane):
        return pick(plane, sl).reshape(next_idx.shape)

    rec, row, think = read(cst["srec"]), read(cst["sr"]), read(cst["sth"])
    gsub = rec >> 1
    return gsub // cfg.S, gsub % cfg.S, (rec & 1) == 1, row, think


# ------------------------------------------------------------- state zero
def open_state0(cfg: TickCfg, cst: dict) -> dict:
    """Canonical open-loop t=0 state. The next-arrival mirror is masked by
    ``n_pb > 0`` so banks with no requests never fire an arrival; for the
    engine's stacked queues this is the identity, because empty queue
    slots are pre-filled with `_PAD_ARRIVE`."""
    G, B, S = cst["n_pb"].shape[0], cfg.B, cfg.S
    live = cst["n_pb"] > 0
    qa0 = cst["qa"][:, 0].reshape(G, B)
    qw0 = cst["qw"][:, 0].reshape(G, B)
    return dict(
        t=jnp.int32(0),
        bank_free=jnp.zeros((G, B), jnp.int32),
        ref_until_s=jnp.zeros((G, B * S), jnp.int32),
        open_row_s=jnp.full((G, B * S), -1, jnp.int32),
        open_sub=jnp.full((G, B), -1, jnp.int32),
        ctr=jnp.zeros((G, B), jnp.int32),
        issued=jnp.zeros((G, cfg.U), jnp.int32),
        n_arrived=jnp.zeros((G, B), jnp.int32),
        n_served=jnp.zeros((G, B), jnp.int32),
        rr=jnp.zeros(G, jnp.int32),
        ab_rr=jnp.zeros(G, jnp.int32),
        wpend=jnp.zeros(G, jnp.int32),
        drain=jnp.zeros(G, bool),
        last_op=jnp.zeros((G, cfg.NC), bool),
        # bank group (gb // BPG, over all ranks) of each channel's last
        # start: its rank is last_bg // NBG, so without groups it is the
        # last start's rank
        last_bg=jnp.full((G, cfg.NC), -1, jnp.int32),
        ab_pending=jnp.zeros((G, cfg.R), jnp.int32),
        rank_drain=jnp.zeros((G, cfg.R), bool),
        # per rank: the tick the tpbR2pbR spacing lets a pb start again
        # (only grids with a per-bank issue rule carry it)
        **({"pb_next": jnp.zeros((G, cfg.R), jnp.int32)}
           if cfg.pb_rules else {}),
        next_arrive=jnp.where(live, qa0, _PAD_ARRIVE),
        next_w=jnp.where(live, qw0, False),
        h_arr=qa0,
        h_row=cst["qr"][:, 0].reshape(G, B),
        h_sub=cst["qs"][:, 0].reshape(G, B),
        h_w=qw0,
        reads=jnp.zeros(G, jnp.int32),
        writes=jnp.zeros(G, jnp.int32),
        hits=jnp.zeros(G, jnp.int32),
        misses=jnp.zeros(G, jnp.int32),
        refpb=jnp.zeros(G, jnp.int32),
        refab=jnp.zeros(G, jnp.int32),
        lat_sum=jnp.zeros(G, jnp.int32),     # exact: clipped lats, guarded
        hist=jnp.zeros((G, MAX_LAT_TICKS + 1), jnp.int32),
        maxlag=jnp.zeros(G, jnp.int32),
        last_done=jnp.zeros(G, jnp.int32),
    )


def closed_state0(cfg: TickCfg, cst: dict) -> dict:
    """Canonical closed-loop t=0 state. Cells with no requests at all
    start with ``remaining == 0`` and are finished at t=0."""
    G, B, S = cst["n_req"].shape[0], cfg.B, cfg.S
    C, K, LQ = cfg.C, cfg.K, cfg.LQ
    return dict(
        t=jnp.int32(0),
        # ring bank queues (flat [G*B*LQ] so appends are one scatter);
        # qc holds core << 1 | is_write, so no bool plane is scattered
        qa=jnp.zeros(G * B * LQ, jnp.int32),
        qr=jnp.zeros(G * B * LQ, jnp.int32),
        qs=jnp.zeros(G * B * LQ, jnp.int32),
        qc=jnp.zeros(G * B * LQ, jnp.int32),
        q_head=jnp.zeros((G, B), jnp.int32),
        q_tail=jnp.zeros((G, B), jnp.int32),
        # core state
        next_idx=jnp.zeros((G, C), jnp.int32),
        next_issue=jnp.zeros((G, C), jnp.int32),
        out_reads=jnp.zeros((G, C), jnp.int32),
        remaining=cst["n_req"],
        finish=jnp.where(cst["n_req"] == 0, 0, -1).astype(jnp.int32),
        comp_t=jnp.full((G, C, K), _PAD_ARRIVE, jnp.int32),
        # machine state
        bank_free=jnp.zeros((G, B), jnp.int32),
        ref_until_s=jnp.zeros((G, B * S), jnp.int32),
        open_row_s=jnp.full((G, B * S), -1, jnp.int32),
        open_sub=jnp.full((G, B), -1, jnp.int32),
        ctr=jnp.zeros((G, B), jnp.int32),
        issued=jnp.zeros((G, cfg.U), jnp.int32),
        rr=jnp.zeros(G, jnp.int32),
        ab_rr=jnp.zeros(G, jnp.int32),
        wpend=jnp.zeros(G, jnp.int32),
        drain=jnp.zeros(G, bool),
        last_op=jnp.zeros((G, cfg.NC), bool),
        # bank group (gb // BPG, over all ranks) of each channel's last
        # start: its rank is last_bg // NBG, so without groups it is the
        # last start's rank
        last_bg=jnp.full((G, cfg.NC), -1, jnp.int32),
        ab_pending=jnp.zeros((G, cfg.R), jnp.int32),
        rank_drain=jnp.zeros((G, cfg.R), bool),
        # per rank: the tick the tpbR2pbR spacing lets a pb start again
        # (only grids with a per-bank issue rule carry it)
        **({"pb_next": jnp.zeros((G, cfg.R), jnp.int32)}
           if cfg.pb_rules else {}),
        # stats
        reads=jnp.zeros(G, jnp.int32),
        writes=jnp.zeros(G, jnp.int32),
        hits=jnp.zeros(G, jnp.int32),
        misses=jnp.zeros(G, jnp.int32),
        refpb=jnp.zeros(G, jnp.int32),
        refab=jnp.zeros(G, jnp.int32),
        lat_sum=jnp.zeros(G, jnp.int32),
        hist=jnp.zeros((G, MAX_LAT_TICKS + 1), jnp.int32),
        maxlag=jnp.zeros(G, jnp.int32),
        last_done=jnp.zeros(G, jnp.int32),
    )


# ------------------------------------------------------------- conditions
def open_cond(cst: dict, s: dict):
    return ((s["t"] < cst["horizon"])
            & (s["n_served"].sum() < cst["n_tot"].sum()))


def closed_cond(cst: dict, s: dict):
    return (s["t"] < cst["horizon"]) & (s["remaining"].sum() > 0)


# ------------------------------------------------------ per-bank issue rules
def refpb_ready(cfg: TickCfg, cst: dict, s: dict, t, issued, ready_u):
    """The refresh units' readiness for the policies, ``[G, U]``: with a
    per-bank issue rule in the grid (static at trace time — other grids
    trace none of it), narrowed to the units the tpbR2pbR spacing and
    the refresh rounds let start at `t`."""
    if not cfg.pb_rules:
        return ready_u
    with jax.named_scope("refpb_rules"):
        return ready_u & refpb_rules_ready(
            t=t, issued=issued, pb_next=s["pb_next"],
            spacing=cst["PB2PB"], rounds=cst["pb_rounds"],
            n_ranks_total=cfg.R)


# ------------------------------------------------------- open-loop body
def open_body(cfg: TickCfg, cst: dict, scores, s: dict) -> dict:
    """One open-loop tick (phases A-E) for every cell at once. `scores`
    is the arbitration callable ``scores(t, **planes) -> [G, B] int32``
    (the jnp scoring definitions of `sweep.arbiter`)."""
    B, L, S = cfg.B, cfg.L, cfg.S
    NB, R, NC = cfg.NB, cfg.R, cfg.NC
    RBC = cfg.NR * cfg.NB            # banks per channel
    HI, LO = cfg.HI, cfg.LO
    qa, qr, qs, qw = cst["qa"], cst["qr"], cst["qs"], cst["qw"]
    n_pb, n_tot = cst["n_pb"], cst["n_tot"]
    phase, rank_phase = cst["phase"], cst["rank_phase"]
    kind, level_ab = cst["kind"], cst["level_ab"]
    sarp, hra, wrp = cst["sarp"], cst["hra"], cst["wrp"]
    urgent_at, budget = cst["urgent_at"], cst["budget"]
    REFI, RFC_PB, RFC_AB = cst["REFI"], cst["RFC_PB"], cst["RFC_AB"]
    HIT, MISS, WR = cst["HIT"], cst["MISS"], cst["WR"]
    TURN, RTR, SARP_PEN = cst["TURN"], cst["RTR"], cst["SARP_PEN"]
    G = kind.shape[0]
    arG = jnp.arange(G)
    flat_gb = (arG[:, None] * B + jnp.arange(B)[None, :])
    sub_of_col = jnp.tile(jnp.arange(S, dtype=jnp.int32), B)[None, :]

    t = s["t"]

    with jax.named_scope("tick.front_end"):
        # ---- A: arrivals
        def acond(a):
            return (a["next_arrive"] <= t).any()

        def abody(a):
            can = a["next_arrive"] <= t
            n_arrived = a["n_arrived"] + can
            sl = jnp.minimum(n_arrived, L - 1)
            na = qa[flat_gb, sl]
            exhausted = n_arrived >= n_pb
            return dict(
                n_arrived=n_arrived,
                wpend=a["wpend"] + (can & a["next_w"]).sum(axis=1),
                next_arrive=jnp.where(
                    can, jnp.where(exhausted, _PAD_ARRIVE, na),
                    a["next_arrive"]),
                next_w=jnp.where(can, qw[flat_gb, sl], a["next_w"]))

        sub = lax.while_loop(acond, abody, dict(
            n_arrived=s["n_arrived"], wpend=s["wpend"],
            next_arrive=s["next_arrive"], next_w=s["next_w"]))
        n_arrived, wpend = sub["n_arrived"], sub["wpend"]
        drain = s["drain"] | (wpend >= HI)
        n_served = s["n_served"]
        active = n_served.sum(axis=1) < n_tot

    with jax.named_scope("tick.refresh"):
        # ---- B: per-rank refresh debt (staggered tREFI/R apart)
        acc = ((active & level_ab)[:, None] & (t > rank_phase)
               & ((t - rank_phase) % REFI[:, None] == 0))
        ab_pending = s["ab_pending"] + acc
        rank_drain = s["rank_drain"] | acc

        # ---- C: decisions
        due = jnp.where(t >= phase, (t - phase) // REFI[:, None] + 1, 0)
        issued = s["issued"]
        lag = due - issued
        bank_free, ref_until_s = s["bank_free"], s["ref_until_s"]
        ready = (ref_until_s.reshape(G, B, S) <= t).all(axis=2)
        idle = bank_free <= t
        demand = n_arrived - n_served
        picks, rr = select_batch(
            jnp, kind=jnp.where(active, kind, KIND_IDEAL), lag=lag,
            ready=refpb_ready(cfg, cst, s, t, issued,
                              per_unit(ready, "all", R, cfg.NBG)),
            idle=per_unit(idle, "all", R, cfg.NBG),
            demand=per_unit(demand, "sum", R, cfg.NBG), write_window=drain,
            budget=budget, wrp=wrp, urgent_at=urgent_at, rr=s["rr"],
            nb=cfg.BPG)

        quiet_r = (idle.reshape(G, R, NB).all(axis=2)
                   & ready.reshape(G, R, NB).all(axis=2))
        start_ab_r = ((active & (kind == KIND_AB))[:, None]
                      & (ab_pending > 0) & quiet_r)
        # staggered_ab: strict rank round-robin, channel-overlap-free
        # (cfg.has_stag is static at trace time — grids without the policy
        # keep this block out of the traced graph entirely)
        if cfg.has_stag:
            idx = s["ab_rr"] % R
            chan_ready = ready.reshape(G, NC, RBC).all(axis=2)
            st_elig = (active & (kind == KIND_STAG)
                       & (ab_pending[arG, idx] > 0) & quiet_r[arG, idx]
                       & chan_ready[arG, idx // cfg.NR])
            start_ab_r = start_ab_r.at[arG, idx].set(
                start_ab_r[arG, idx] | st_elig)
            ab_rr = s["ab_rr"] + st_elig
        else:
            ab_rr = s["ab_rr"]
        ctr = s["ctr"]
        open_row_s, open_sub = s["open_row_s"], s["open_sub"]
        sarp_c = sarp[:, None]

        # SARP marks (and closes) only the target subarray ctr % S; a
        # non-SARP refresh occupies every subarray of the bank
        m = jnp.repeat(start_ab_r, NB, axis=1)
        new_sub = ctr % S
        mark = (jnp.repeat(m, S, axis=1)
                & jnp.where(sarp_c, jnp.repeat(new_sub, S, axis=1)
                            == sub_of_col, True))
        ref_until_s = jnp.where(mark, (t + RFC_AB)[:, None], ref_until_s)
        open_row_s = jnp.where(mark, -1, open_row_s)
        ctr = ctr + (m & sarp_c)
        ab_pending = ab_pending - start_ab_r
        rank_drain = jnp.where(start_ab_r, ab_pending > 0, rank_drain)
        refab = s["refab"] + start_ab_r.sum(axis=1)

        if cfg.pb_rules:
            with jax.named_scope("refpb_rules"):
                picks = refpb_rules_first(jnp, picks, cst["PB2PB"], R)
        new_sub = ctr % S
        start = jnp.maximum(t, bank_free)
        if cfg.has_hra:
            # HiRA hidden row activation: refresh a subarray the in-flight
            # access is NOT using starting at t (static at trace time —
            # grids without the trait keep this out of the traced graph)
            start = jnp.where(hra[:, None] & (new_sub != open_sub), t,
                              start)
        # a same-bank set starts once every bank of it can (per_unit and
        # per_bank are the identity without bank groups)
        start_u = per_unit(start, "max", R, cfg.NBG)
        if cfg.pb_rules:
            with jax.named_scope("refpb_rules"):
                pb_next = refpb_rules_next(jnp, picks, start_u,
                                           s["pb_next"], cst["PB2PB"], R)
        start = per_bank(jnp, start_u, R, cfg.NBG)
        picks_b = per_bank(jnp, picks, R, cfg.NBG)
        mark = (jnp.repeat(picks_b, S, axis=1)
                & jnp.where(sarp_c, jnp.repeat(new_sub, S, axis=1)
                            == sub_of_col, True))
        ref_until_s = jnp.where(
            mark, jnp.repeat(start + RFC_PB[:, None], S, axis=1),
            ref_until_s)
        open_row_s = jnp.where(mark, -1, open_row_s)
        ctr = ctr + picks_b
        issued = issued + picks
        refpb = s["refpb"] + picks.sum(axis=1)
        maxlag = jnp.maximum(
            s["maxlag"],
            jnp.where(picks, jnp.abs(due - issued), 0).max(axis=1))

    with jax.named_scope("tick.arbitrate"):
        # ---- D: arbitration + serve, one start per channel (scores —
        # incl. the drain flag — snapshotted before any serve; the head
        # request's own subarray's state is picked from [G, B*S] planes)
        ru3 = ref_until_s.reshape(G, B, S)
        head_ru = pick(ru3, s["h_sub"])
        head_or = pick(open_row_s.reshape(G, B, S), s["h_sub"])
        bank_mid = (ru3 > t).any(axis=2)
        score = scores(t, has_req=demand > 0, head_row=s["h_row"],
                       head_arrive=s["h_arr"], head_is_write=s["h_w"],
                       bank_free=bank_free, head_ref_until=head_ru,
                       bank_mid_ref=bank_mid, open_row=head_or,
                       drain=drain,
                       rank_drain=jnp.repeat(rank_drain, NB, axis=1))
    with jax.named_scope("tick.serve"):
        h_arr_s, h_row_s = s["h_arr"], s["h_row"]
        h_sub_s, h_w_s = s["h_sub"], s["h_w"]
        last_op, last_bg = s["last_op"], s["last_bg"]
        reads, writes = s["reads"], s["writes"]
        hits_s, misses_s = s["hits"], s["misses"]
        lat_sum, hist = s["lat_sum"], s["hist"]
        last_done = s["last_done"]
        for ch in range(NC):
            sc_ch = score[:, ch * RBC:(ch + 1) * RBC]
            bs = jnp.argmax(sc_ch, axis=1) + ch * RBC
            ok = score[arG, bs] >= 0
            row, sub_ = h_row_s[arG, bs], h_sub_s[arG, bs]
            arr, isw = h_arr_s[arG, bs], h_w_s[arG, bs]
            hit = row == head_or[arG, bs]
            gr_b = bs // NB
            bg_b = gr_b if cfg.NBG == 1 else bs // cfg.BPG
            lbg = last_bg[:, ch]
            lr = lbg if cfg.NBG == 1 else lbg // cfg.NBG
            lat = (jnp.where(hit, HIT, MISS)
                   + jnp.where(sarp & bank_mid[arG, bs],
                               SARP_PEN, 0)
                   + jnp.where(isw != last_op[:, ch], TURN, 0)
                   + jnp.where((lr >= 0) & (lr != gr_b), RTR, 0))
            if cfg.NBG > 1:
                # a start in its channel's last bank group: tCCD_L
                lat = lat + jnp.where(lbg == bg_b, cst["CCDL"], 0)
            done = t + lat
            bank_free = bank_free.at[arG, bs].set(
                jnp.where(ok, done + jnp.where(isw, WR, 0),
                          bank_free[arG, bs]))
            last_op = last_op.at[:, ch].set(
                jnp.where(ok, isw, last_op[:, ch]))
            last_bg = last_bg.at[:, ch].set(jnp.where(ok, bg_b, lbg))
            gsub = bs * S + sub_
            open_row_s = open_row_s.at[arG, gsub].set(
                jnp.where(ok, row, open_row_s[arG, gsub]))
            open_sub = open_sub.at[arG, bs].set(
                jnp.where(ok, sub_, open_sub[arG, bs]))
            n_served = n_served.at[arG, bs].add(ok)
            served_w = ok & isw
            wpend = wpend - served_w
            drain = drain & ~(served_w & (wpend <= LO))
            rmask = ok & ~isw
            lrec = jnp.minimum(done - arr, MAX_LAT_TICKS)
            hist = hist.at[arG, lrec].add(rmask)
            lat_sum = lat_sum + jnp.where(rmask, lrec, 0)
            reads = reads + rmask
            writes = writes + served_w
            hits_s = hits_s + (ok & hit)
            misses_s = misses_s + (ok & ~hit)
            last_done = jnp.where(ok, jnp.maximum(last_done, done),
                                  last_done)
            flat = arG * B + bs
            sl = jnp.minimum(n_served[arG, bs], L - 1)
            h_arr_s = h_arr_s.at[arG, bs].set(
                jnp.where(ok, qa[flat, sl], h_arr_s[arG, bs]))
            h_row_s = h_row_s.at[arG, bs].set(
                jnp.where(ok, qr[flat, sl], h_row_s[arG, bs]))
            h_sub_s = h_sub_s.at[arG, bs].set(
                jnp.where(ok, qs[flat, sl], h_sub_s[arG, bs]))
            h_w_s = h_w_s.at[arG, bs].set(
                jnp.where(ok, qw[flat, sl], h_w_s[arG, bs]))

    return dict(
        t=t + 1, bank_free=bank_free, ref_until_s=ref_until_s,
        open_row_s=open_row_s, open_sub=open_sub,
        ctr=ctr, issued=issued, n_arrived=n_arrived,
        n_served=n_served, rr=rr, ab_rr=ab_rr, wpend=wpend,
        drain=drain, last_op=last_op, last_bg=last_bg,
        ab_pending=ab_pending, rank_drain=rank_drain,
        **({"pb_next": pb_next} if cfg.pb_rules else {}),
        next_arrive=sub["next_arrive"], next_w=sub["next_w"],
        h_arr=h_arr_s, h_row=h_row_s, h_sub=h_sub_s, h_w=h_w_s,
        reads=reads, writes=writes,
        hits=hits_s, misses=misses_s,
        refpb=refpb, refab=refab,
        lat_sum=lat_sum,
        hist=hist, maxlag=maxlag,
        last_done=last_done,
    )


# ----------------------------------------------------- closed-loop body
def closed_body(cfg: TickCfg, cst: dict, scores, s: dict) -> dict:
    """One closed-loop tick (phases 0-5): the open-loop phases plus
    per-core MLP-window state and core-fed ring bank queues."""
    B, S = cfg.B, cfg.S
    NB, R, NC = cfg.NB, cfg.R, cfg.NC
    RBC = cfg.NR * cfg.NB            # banks per channel
    C = cfg.C
    LQ = cfg.LQ
    QM = LQ - 1
    HI, LO, CAP = cfg.HI, cfg.LO, cfg.CAP
    n_req, mlp_col = cst["n_req"], cst["mlp"][:, None]
    phase, rank_phase = cst["phase"], cst["rank_phase"]
    kind, level_ab = cst["kind"], cst["level_ab"]
    sarp, hra, wrp = cst["sarp"], cst["hra"], cst["wrp"]
    urgent_at, budget = cst["urgent_at"], cst["budget"]
    REFI, RFC_PB, RFC_AB = cst["REFI"], cst["RFC_PB"], cst["RFC_AB"]
    HIT, MISS, WR = cst["HIT"], cst["MISS"], cst["WR"]
    TURN, RTR, SARP_PEN = cst["TURN"], cst["RTR"], cst["SARP_PEN"]
    G = kind.shape[0]
    arG = jnp.arange(G)
    arB = jnp.arange(B)
    arC = jnp.arange(C)
    sub_of_col = jnp.tile(jnp.arange(S, dtype=jnp.int32), B)[None, :]
    OOB = G * B * LQ                       # scatter target for non-issues

    t = s["t"]

    with jax.named_scope("tick.front_end"):
        # ---- 0: outstanding-read completions
        exp = s["comp_t"] <= t
        n_exp = exp.sum(axis=2).astype(jnp.int32)
        out_reads = s["out_reads"] - n_exp
        remaining = s["remaining"] - n_exp
        comp_t = jnp.where(exp, _PAD_ARRIVE, s["comp_t"])

        # ---- 1: core issue (at most one per core per tick, core order)
        next_idx = s["next_idx"]
        hb, req_sub, head_w, req_row, req_think = next_requests(
            cfg, cst, next_idx)
        can = (next_idx < n_req) & (s["next_issue"] <= t)
        want_w = can & head_w
        want_r = can & ~head_w & (out_reads < mlp_col)
        rank_w = jnp.cumsum(want_w, axis=1) - want_w
        ok_w = want_w & (rank_w < (CAP - s["wpend"])[:, None])
        issue = ok_w | want_r
        oh = issue[:, :, None] & (hb[:, :, None] == arB[None, None, :])
        pref = jnp.cumsum(oh, axis=1) - oh
        # each issue's place among its bank's appends this tick, and the
        # bank's tail, by one-hot selects over the B lanes
        pos_in = pick(pref, hb)
        tail_b = pick(jnp.broadcast_to(s["q_tail"][:, None, :], (G, C, B)),
                      hb)
        slot = (tail_b + pos_in) & QM
        tgt = jnp.where(issue, (arG[:, None] * B + hb) * LQ + slot, OOB)
        tgtf = tgt.ravel()
        qa = s["qa"].at[tgtf].set(jnp.full(G * C, t, jnp.int32),
                                  mode="drop")
        qr = s["qr"].at[tgtf].set(req_row.ravel(), mode="drop")
        qs_ = s["qs"].at[tgtf].set(req_sub.ravel(), mode="drop")
        qc = s["qc"].at[tgtf].set(
            ((arC[None, :] << 1) | head_w).ravel(), mode="drop")
        q_tail = s["q_tail"] + oh.sum(axis=1)
        wpend = s["wpend"] + ok_w.sum(axis=1)
        out_reads = out_reads + want_r
        remaining = remaining - ok_w          # writes retire at issue
        next_issue = jnp.where(issue, t + req_think, s["next_issue"])
        next_idx = next_idx + issue
        finish = jnp.where((remaining == 0) & (s["finish"] < 0), t,
                           s["finish"])
        active = (remaining > 0).any(axis=1)

        # ---- 2: write-drain watermark
        drain = s["drain"] | (wpend >= HI)

    with jax.named_scope("tick.refresh"):
        # ---- 3: per-rank refresh debt (staggered tREFI/R apart)
        acc = ((active & level_ab)[:, None] & (t > rank_phase)
               & ((t - rank_phase) % REFI[:, None] == 0))
        ab_pending = s["ab_pending"] + acc
        rank_drain = s["rank_drain"] | acc

        # ---- 4: decisions
        due = jnp.where(t >= phase, (t - phase) // REFI[:, None] + 1, 0)
        issued = s["issued"]
        lag = due - issued
        bank_free, ref_until_s = s["bank_free"], s["ref_until_s"]
        ready = (ref_until_s.reshape(G, B, S) <= t).all(axis=2)
        idle = bank_free <= t
        demand = q_tail - s["q_head"]
        picks, rr = select_batch(
            jnp, kind=jnp.where(active, kind, KIND_IDEAL), lag=lag,
            ready=refpb_ready(cfg, cst, s, t, issued,
                              per_unit(ready, "all", R, cfg.NBG)),
            idle=per_unit(idle, "all", R, cfg.NBG),
            demand=per_unit(demand, "sum", R, cfg.NBG), write_window=drain,
            budget=budget, wrp=wrp, urgent_at=urgent_at, rr=s["rr"],
            nb=cfg.BPG)

        quiet_r = (idle.reshape(G, R, NB).all(axis=2)
                   & ready.reshape(G, R, NB).all(axis=2))
        start_ab_r = ((active & (kind == KIND_AB))[:, None]
                      & (ab_pending > 0) & quiet_r)
        # staggered_ab: strict rank round-robin, channel-overlap-free
        # (cfg.has_stag is static at trace time — grids without the policy
        # keep this block out of the traced graph entirely)
        if cfg.has_stag:
            idx = s["ab_rr"] % R
            chan_ready = ready.reshape(G, NC, RBC).all(axis=2)
            st_elig = (active & (kind == KIND_STAG)
                       & (ab_pending[arG, idx] > 0) & quiet_r[arG, idx]
                       & chan_ready[arG, idx // cfg.NR])
            start_ab_r = start_ab_r.at[arG, idx].set(
                start_ab_r[arG, idx] | st_elig)
            ab_rr = s["ab_rr"] + st_elig
        else:
            ab_rr = s["ab_rr"]
        ctr = s["ctr"]
        open_row_s, open_sub = s["open_row_s"], s["open_sub"]
        sarp_c = sarp[:, None]

        # SARP marks (and closes) only the target subarray ctr % S; a
        # non-SARP refresh occupies every subarray of the bank
        m = jnp.repeat(start_ab_r, NB, axis=1)
        new_sub = ctr % S
        mark = (jnp.repeat(m, S, axis=1)
                & jnp.where(sarp_c, jnp.repeat(new_sub, S, axis=1)
                            == sub_of_col, True))
        ref_until_s = jnp.where(mark, (t + RFC_AB)[:, None], ref_until_s)
        open_row_s = jnp.where(mark, -1, open_row_s)
        ctr = ctr + (m & sarp_c)
        ab_pending = ab_pending - start_ab_r
        rank_drain = jnp.where(start_ab_r, ab_pending > 0, rank_drain)
        refab = s["refab"] + start_ab_r.sum(axis=1)

        if cfg.pb_rules:
            with jax.named_scope("refpb_rules"):
                picks = refpb_rules_first(jnp, picks, cst["PB2PB"], R)
        new_sub = ctr % S
        start = jnp.maximum(t, bank_free)
        if cfg.has_hra:
            # HiRA hidden row activation: refresh a subarray the in-flight
            # access is NOT using starting at t (static at trace time —
            # grids without the trait keep this out of the traced graph)
            start = jnp.where(hra[:, None] & (new_sub != open_sub), t,
                              start)
        # a same-bank set starts once every bank of it can (per_unit and
        # per_bank are the identity without bank groups)
        start_u = per_unit(start, "max", R, cfg.NBG)
        if cfg.pb_rules:
            with jax.named_scope("refpb_rules"):
                pb_next = refpb_rules_next(jnp, picks, start_u,
                                           s["pb_next"], cst["PB2PB"], R)
        start = per_bank(jnp, start_u, R, cfg.NBG)
        picks_b = per_bank(jnp, picks, R, cfg.NBG)
        mark = (jnp.repeat(picks_b, S, axis=1)
                & jnp.where(sarp_c, jnp.repeat(new_sub, S, axis=1)
                            == sub_of_col, True))
        ref_until_s = jnp.where(
            mark, jnp.repeat(start + RFC_PB[:, None], S, axis=1),
            ref_until_s)
        open_row_s = jnp.where(mark, -1, open_row_s)
        ctr = ctr + picks_b
        issued = issued + picks
        refpb = s["refpb"] + picks.sum(axis=1)
        maxlag = jnp.maximum(
            s["maxlag"],
            jnp.where(picks, jnp.abs(due - issued), 0).max(axis=1))

    with jax.named_scope("tick.arbitrate"):
        # ---- 5: occupancy-aware arbitration + serve, one start per
        # channel (scores — incl. drain — snapshotted before any serve)
        hslot = s["q_head"] & QM

        def head(q):                       # each bank's ring-queue head
            return pick(q.reshape(G, B, LQ), hslot)

        h_row, h_sub, h_arr, hc = head(qr), head(qs_), head(qa), head(qc)
        h_core, h_w = hc >> 1, (hc & 1) == 1
        has_req = (demand > 0) & active[:, None]
        ru3 = ref_until_s.reshape(G, B, S)
        head_ru = pick(ru3, h_sub)
        head_or = pick(open_row_s.reshape(G, B, S), h_sub)
        bank_mid = (ru3 > t).any(axis=2)
        score = scores(t, has_req=has_req, head_row=h_row,
                       head_arrive=h_arr, head_is_write=h_w,
                       bank_free=bank_free, head_ref_until=head_ru,
                       bank_mid_ref=bank_mid, open_row=head_or,
                       drain=drain, occ=demand,
                       rank_drain=jnp.repeat(rank_drain, NB, axis=1))
    with jax.named_scope("tick.serve"):
        last_op, last_bg = s["last_op"], s["last_bg"]
        q_head = s["q_head"]
        reads, writes = s["reads"], s["writes"]
        hits_s, misses_s = s["hits"], s["misses"]
        lat_sum, hist = s["lat_sum"], s["hist"]
        last_done = s["last_done"]
        for ch in range(NC):
            sc_ch = score[:, ch * RBC:(ch + 1) * RBC]
            bs = jnp.argmax(sc_ch, axis=1) + ch * RBC
            ok = score[arG, bs] >= 0
            row, sub_ = h_row[arG, bs], h_sub[arG, bs]
            arr, isw = h_arr[arG, bs], h_w[arG, bs]
            core = h_core[arG, bs]
            hit = row == head_or[arG, bs]
            gr_b = bs // NB
            bg_b = gr_b if cfg.NBG == 1 else bs // cfg.BPG
            lbg = last_bg[:, ch]
            lr = lbg if cfg.NBG == 1 else lbg // cfg.NBG
            lat = (jnp.where(hit, HIT, MISS)
                   + jnp.where(sarp & bank_mid[arG, bs],
                               SARP_PEN, 0)
                   + jnp.where(isw != last_op[:, ch], TURN, 0)
                   + jnp.where((lr >= 0) & (lr != gr_b), RTR, 0))
            if cfg.NBG > 1:
                # a start in its channel's last bank group: tCCD_L
                lat = lat + jnp.where(lbg == bg_b, cst["CCDL"], 0)
            done = t + lat
            bank_free = bank_free.at[arG, bs].set(
                jnp.where(ok, done + jnp.where(isw, WR, 0),
                          bank_free[arG, bs]))
            last_op = last_op.at[:, ch].set(
                jnp.where(ok, isw, last_op[:, ch]))
            last_bg = last_bg.at[:, ch].set(jnp.where(ok, bg_b, lbg))
            gsub = bs * S + sub_
            open_row_s = open_row_s.at[arG, gsub].set(
                jnp.where(ok, row, open_row_s[arG, gsub]))
            open_sub = open_sub.at[arG, bs].set(
                jnp.where(ok, sub_, open_sub[arG, bs]))
            q_head = q_head.at[arG, bs].add(ok)
            served_w = ok & isw
            wpend = wpend - served_w
            drain = drain & ~(served_w & (wpend <= LO))
            rmask = ok & ~isw
            lrec = jnp.minimum(done - arr, MAX_LAT_TICKS)
            hist = hist.at[arG, lrec].add(rmask)
            lat_sum = lat_sum + jnp.where(rmask, lrec, 0)
            reads = reads + rmask
            writes = writes + served_w
            hits_s = hits_s + (ok & hit)
            misses_s = misses_s + (ok & ~hit)
            last_done = jnp.where(ok, jnp.maximum(last_done, done),
                                  last_done)
            # reads: park the data return in the core's MLP window slot
            free_k = jnp.argmax(comp_t[arG, core] == _PAD_ARRIVE, axis=1)
            comp_t = comp_t.at[arG, core, free_k].set(
                jnp.where(rmask, done, comp_t[arG, core, free_k]))

    return dict(
        t=t + 1, qa=qa, qr=qr, qs=qs_, qc=qc,
        q_head=q_head, q_tail=q_tail,
        next_idx=next_idx, next_issue=next_issue, out_reads=out_reads,
        remaining=remaining, finish=finish, comp_t=comp_t,
        bank_free=bank_free, ref_until_s=ref_until_s,
        open_row_s=open_row_s, open_sub=open_sub, ctr=ctr,
        issued=issued,
        rr=rr, ab_rr=ab_rr, wpend=wpend, drain=drain, last_op=last_op,
        last_bg=last_bg,
        ab_pending=ab_pending, rank_drain=rank_drain,
        **({"pb_next": pb_next} if cfg.pb_rules else {}),
        reads=reads, writes=writes,
        hits=hits_s, misses=misses_s,
        refpb=refpb, refab=refab,
        lat_sum=lat_sum,
        hist=hist, maxlag=maxlag,
        last_done=last_done,
    )


# -------------------------------------------------------------- the loop
def loop(cfg: TickCfg, cst: dict, scores, s0: dict) -> dict:
    """Run the tick body of `cfg`'s mode from `s0` until its condition
    fails; returns the final state."""
    cond, body = ((closed_cond, closed_body) if cfg.closed
                  else (open_cond, open_body))
    return lax.while_loop(lambda s: cond(cst, s),
                          lambda s: body(cfg, cst, scores, s), s0)


#: the jax backend's program: `loop` jitted, with the config and the
#: arbitration callable static (pass the same `scores` object to reuse
#: the compiled loop)
run_loop = jax.jit(loop, static_argnums=(0, 2))


def program(grid) -> tuple:
    """The inputs of `run_loop` for one `engine._Grid`: ``(cfg, cst,
    s0)``."""
    if grid.closed:
        cfg, cst = closed_cfg(grid), closed_consts(grid)
        return cfg, cst, closed_state0(cfg, cst)
    cfg, cst = open_cfg(grid), open_consts(grid)
    return cfg, cst, open_state0(cfg, cst)
