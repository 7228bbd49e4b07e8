"""Fused tick-loop megakernel — the whole simulator as one Pallas call.

`kernels/sweep_arbiter.py` accelerates one phase (arbitration scoring);
the host still drives a `lax.while_loop` around it, so every tick pays a
full-grid HBM round-trip and the grid axis cannot shard. This module
fuses the *entire* tick loop — arrivals/core issue, refresh debt and
±postpone budget, SARP/HiRA subarray marking, packed-score arbitration,
per-channel serve with tRTR, closed-loop `comp_t` parking and wbuf
backpressure — into a cell-tiled kernel that runs each tile of cells to
completion in one invocation and ships home only the `[tile,
MEGA_NSTAT]` integer stat block (plus per-core finish ticks for closed
grids). The traced tick body is *shared* with the engine's jax backend
(`repro.core.sweep.jaxbody`), so bit-identity with the batched numpy
backend and `DramSim.run_ticks` holds by construction.

Layout (see `docs/tick-contract.md`, "fused kernel"):

  * cells are sorted scenario-major (then density, then policy kind) and
    cut into scenario-pure tiles; a tile's demand stream is gathered
    once via scalar prefetch (`tile_scn[i]` indexes the `[NS, ...]`
    per-scenario planes), so a 10^5-cell grid carries `n_scenarios`
    stream copies instead of 10^5;
  * per-cell constants travel as one int32 row of the `[G, MEGA_NPARAM]`
    params block (column table in `sweep/fields.py`; the `pallas-lint`
    PL504 rule pins kernel shapes to those names);
  * pad cells (tile remainders) carry `MP_PAD=1`: the kernel masks their
    request counts to zero and `jaxbody` starts them finished, so they
    run zero ticks and cannot perturb the tile's early-exit condition;
  * tiles are dispatched in fixed-shape chunks (`chunk_tiles` per shard)
    so one compiled program serves giga-grids and per-chunk stats stream
    back without materializing full stacked state; `n_shards > 1`
    splits each chunk's tile axis across devices with `shard_map`
    (logical axis ``cells`` in `repro/parallel/sharding.py`).

Mosaic, the TPU's Pallas compiler, refuses this kernel (see
`MegakernelRefused`), so it runs only in interpret mode (same traced
graph, plain XLA ops), which keeps CI and the conformance tier green on
CPU. On a TPU the sweep's device path is `sweep(..., backend="jax")`,
the same tick body under a plain XLA `lax.while_loop`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.sweep import jaxbody
from repro.core.sweep.arbiter import arbiter_scores
from repro.core.sweep.fields import (MEGA_NPARAM, MEGA_NSTAT, MP_BUDGET,
                                     MP_HIT, MP_HORIZON, MP_HRA, MP_KIND,
                                     MP_LEVEL_AB, MP_MISS, MP_MLP, MP_PAD,
                                     MP_REFI, MP_REFI_PB, MP_RFC_AB,
                                     MP_RFC_PB, MP_RTR, MP_SARP,
                                     MP_SARP_PEN, MP_TURN, MP_URGENT,
                                     MP_WR, MP_WRP, MS_FINISHED, MS_HITS,
                                     MS_LASTDONE, MS_LATSUM, MS_MAXLAG,
                                     MS_MISSES, MS_P99, MS_READS,
                                     MS_REFAB, MS_REFPB, MS_WRITES)
from repro.core.sweep.policies import KIND_IDEAL
from repro.parallel.sharding import (LOGICAL_RULES_SINGLE_POD,
                                     logical_to_spec, sharding_context)

#: default cell-tile height (cells that run one fused loop together; a
#: tile early-exits as soon as *its* cells are done, so homogeneous
#: tiles — same scenario/density — finish fastest)
TILE = 64

#: tiles per `pallas_call` per shard: bounds the dispatched program and
#: result-buffer size so giga-grids stream through one compiled call
CHUNK_TILES = 32

#: why the compiled kernel is refused (checked ahead of time against a
#: described v5e chip by tests/test_tpu_aot.py, which fails the day
#: Mosaic accepts the kernel)
REFUSAL = (
    "Mosaic cannot lower the sweep megakernel for a TPU: the per-scenario "
    "request-count block (1, C) is not (8, 128)-aligned, and the shared "
    "tick body (repro.core.sweep.jaxbody) gathers with 2-D advanced "
    "indexing and take_along_axis (first at `sw[flat_gc, sl]` in "
    "closed_body and `qa[flat_gb, sl]` in open_body), which Pallas's "
    "Mosaic gather lowering rejects. Use sweep(..., backend='jax') on a "
    "TPU: the same tick body under an XLA lax.while_loop.")


class MegakernelRefused(NotImplementedError):
    """The megakernel was asked for on a TPU, where Mosaic does not
    lower it (`REFUSAL`)."""


# ------------------------------------------------------------ host layout
def _pack_params(grid) -> np.ndarray:
    """One int32 row per cell (canonical cell order), MP_* columns."""
    p = np.zeros((grid.G, MEGA_NPARAM), np.int32)
    p[:, MP_KIND] = grid.kind
    p[:, MP_LEVEL_AB] = grid.level_ab
    p[:, MP_SARP] = grid.sarp
    p[:, MP_HRA] = grid.hra
    p[:, MP_WRP] = grid.wrp
    p[:, MP_URGENT] = grid.urgent_at
    p[:, MP_BUDGET] = grid.budget
    p[:, MP_REFI] = grid.REFI
    p[:, MP_REFI_PB] = np.array(
        [grid.timing[d].REFI_PB for _, _, d in grid.cells], np.int32)
    p[:, MP_RFC_PB] = grid.RFC_PB
    p[:, MP_RFC_AB] = grid.RFC_AB
    p[:, MP_HIT] = grid.HIT
    p[:, MP_MISS] = grid.MISS
    p[:, MP_WR] = grid.WR
    p[:, MP_TURN] = grid.TURN
    p[:, MP_RTR] = grid.RTR
    p[:, MP_SARP_PEN] = grid.SARP_PEN
    if grid.closed:
        p[:, MP_MLP] = grid.mlp_g
    p[:, MP_HORIZON] = grid.horizon
    return p


def _pad_row() -> np.ndarray:
    """Params row for a pad cell: picks nothing (KIND_IDEAL), zero
    requests (the kernel masks counts on MP_PAD), unit timings so the
    refresh-debt modulus is well defined, and zero horizon so an all-pad
    tile exits at t=0."""
    r = np.zeros(MEGA_NPARAM, np.int32)
    r[MP_KIND] = KIND_IDEAL
    for j in (MP_URGENT, MP_REFI, MP_REFI_PB, MP_RFC_PB, MP_RFC_AB,
              MP_HIT, MP_MISS, MP_WR, MP_TURN, MP_RTR, MP_SARP_PEN,
              MP_MLP):
        r[j] = 1
    r[MP_PAD] = 1
    return r


def _layout(grid, tile):
    """Sort cells scenario-major and cut into scenario-pure tiles.

    Returns ``(rows, tile_scn, tile)``: `rows` maps each padded kernel
    row to its original cell index (-1 for pad rows), `tile_scn` gives
    each tile's scenario index (the scalar-prefetch operand)."""
    d_index = {d: i for i, d in enumerate(grid.spec.densities)}
    d_of = np.array([d_index[d] for _, _, d in grid.cells], np.int32)
    order = np.lexsort((grid.kind, d_of, grid.scn_of_cell))
    scn_sorted = grid.scn_of_cell[order]
    n_scn = int(scn_sorted.max()) + 1
    if tile is None:
        group = max(1, grid.G // n_scn)      # cells per scenario
        tile = min(TILE, group)
    rows, tile_scn = [], []
    for scn in range(n_scn):
        gs = order[scn_sorted == scn]
        for i0 in range(0, len(gs), tile):
            part = gs[i0:i0 + tile]
            rows.extend(int(g) for g in part)
            rows.extend([-1] * (tile - len(part)))
            tile_scn.append(scn)
    return (np.asarray(rows, np.int32), np.asarray(tile_scn, np.int32),
            tile)


# ------------------------------------------------------------ kernel body
def _scores_jnp(t, **planes):
    """The jnp scoring definitions — a kernel cannot nest the Pallas
    arbiter, so the megakernel inlines the packed-score reference."""
    return arbiter_scores(jnp, t, **planes)


def _param_consts(p, cfg) -> dict:
    """Expand one tile's packed [T, MEGA_NPARAM] rows into the jaxbody
    constant planes (the traced analogue of `_Grid`'s per-cell
    constants; `horizon` is the tile max — pad rows carry 0)."""
    col = lambda j: p[:, j]
    return dict(
        phase=jnp.arange(cfg.B, dtype=jnp.int32)[None, :]
        * col(MP_REFI_PB)[:, None],
        rank_phase=jnp.arange(cfg.R, dtype=jnp.int32)[None, :]
        * (col(MP_REFI) // cfg.R)[:, None],
        kind=col(MP_KIND), level_ab=col(MP_LEVEL_AB) != 0,
        sarp=col(MP_SARP) != 0, hra=col(MP_HRA) != 0,
        wrp=col(MP_WRP) != 0, urgent_at=col(MP_URGENT),
        budget=col(MP_BUDGET), REFI=col(MP_REFI), RFC_PB=col(MP_RFC_PB),
        RFC_AB=col(MP_RFC_AB), HIT=col(MP_HIT), MISS=col(MP_MISS),
        WR=col(MP_WR), TURN=col(MP_TURN), RTR=col(MP_RTR),
        SARP_PEN=col(MP_SARP_PEN), horizon=col(MP_HORIZON).max())


def _pack_stats(out, finished):
    """Final state planes -> the [T, MEGA_NSTAT] int32 block (MS_*
    column order). p99 is reduced in-kernel so the [MAX_LAT_TICKS+1]
    histogram rows never ship home: for int32 read counts,
    ceil(0.99 * reads) == (99 * reads + 99) // 100 exactly, and
    searchsorted(cumsum, target, 'left') == argmax(cumsum >= target)
    for target >= 1 (reads == 0 makes both sides 0)."""
    reads = out["reads"]
    target = (99 * reads + 99) // 100
    p99 = jnp.argmax(jnp.cumsum(out["hist"], axis=1)
                     >= target[:, None], axis=1)
    cols = [reads, out["writes"], out["hits"], out["misses"],
            out["refpb"], out["refab"], out["lat_sum"], out["maxlag"],
            out["last_done"], p99, finished]
    return jnp.stack([c.astype(jnp.int32) for c in cols], axis=1)


def _mega_closed_kernel(scn_ref, params_ref, sw_ref, sb_ref, sr_ref,
                        ssub_ref, sth_ref, nreq_ref, stats_ref, cf_ref,
                        *, cfg):
    """Closed-loop tick loop (contract phases 0-5) for one tile."""
    del scn_ref  # consumed by the BlockSpec index maps (stream gather)
    p = params_ref[...]
    tile = p.shape[0]
    live = p[:, MP_PAD] == 0

    def stream(ref):
        return jnp.broadcast_to(
            ref[...], (tile, cfg.C, cfg.N)).reshape(tile * cfg.C, cfg.N)

    n_req = jnp.where(live[:, None],
                      jnp.broadcast_to(nreq_ref[...], (tile, cfg.C)), 0)
    cst = dict(sw=stream(sw_ref) != 0, sb=stream(sb_ref),
               sr=stream(sr_ref), ssub=stream(ssub_ref),
               sth=stream(sth_ref), n_req=n_req, mlp=p[:, MP_MLP],
               **_param_consts(p, cfg))
    out = jaxbody.loop(cfg, cst, _scores_jnp,
                       jaxbody.closed_state0(cfg, cst))
    stats_ref[...] = _pack_stats(out, (out["remaining"] <= 0).all(axis=1))
    cf_ref[...] = jnp.where(out["finish"] < 0, out["t"], out["finish"])


def _mega_open_kernel(scn_ref, params_ref, qa_ref, qr_ref, qs_ref,
                      qw_ref, npb_ref, stats_ref, *, cfg):
    """Open-loop tick loop (contract phases A-E) for one tile."""
    del scn_ref  # consumed by the BlockSpec index maps (stream gather)
    p = params_ref[...]
    tile = p.shape[0]
    live = p[:, MP_PAD] == 0

    def stream(ref):
        return jnp.broadcast_to(
            ref[...], (tile, cfg.B, cfg.L)).reshape(tile * cfg.B, cfg.L)

    n_pb = jnp.where(live[:, None],
                     jnp.broadcast_to(npb_ref[...], (tile, cfg.B)), 0)
    cst = dict(qa=stream(qa_ref), qr=stream(qr_ref), qs=stream(qs_ref),
               qw=stream(qw_ref) != 0, n_pb=n_pb,
               n_tot=n_pb.sum(axis=1), **_param_consts(p, cfg))
    out = jaxbody.loop(cfg, cst, _scores_jnp, jaxbody.open_state0(cfg, cst))
    stats_ref[...] = _pack_stats(
        out, out["n_served"].sum(axis=1) >= cst["n_tot"])


# ------------------------------------------------------------- dispatch
def _closed_call(tile_scn, params, sw, sb, sr, ssub, sth, nreq, *, cfg,
                 n_tiles, tile, interpret):
    blk_p = pl.BlockSpec((tile, MEGA_NPARAM), lambda i, scn: (i, 0))
    blk_s = pl.BlockSpec((1, cfg.C, cfg.N), lambda i, scn: (scn[i], 0, 0))
    blk_n = pl.BlockSpec((1, cfg.C), lambda i, scn: (scn[i], 0))
    rows = n_tiles * tile
    return pl.pallas_call(
        functools.partial(_mega_closed_kernel, cfg=cfg),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(n_tiles,),
            in_specs=[blk_p, blk_s, blk_s, blk_s, blk_s, blk_s, blk_n],
            out_specs=[pl.BlockSpec((tile, MEGA_NSTAT),
                                    lambda i, scn: (i, 0)),
                       pl.BlockSpec((tile, cfg.C),
                                    lambda i, scn: (i, 0))]),
        out_shape=[jax.ShapeDtypeStruct((rows, MEGA_NSTAT), jnp.int32),
                   jax.ShapeDtypeStruct((rows, cfg.C), jnp.int32)],
        interpret=interpret,
    )(tile_scn, params, sw, sb, sr, ssub, sth, nreq)


def _open_call(tile_scn, params, qa, qr, qs, qw, npb, *, cfg, n_tiles,
               tile, interpret):
    blk_p = pl.BlockSpec((tile, MEGA_NPARAM), lambda i, scn: (i, 0))
    blk_q = pl.BlockSpec((1, cfg.B, cfg.L), lambda i, scn: (scn[i], 0, 0))
    blk_n = pl.BlockSpec((1, cfg.B), lambda i, scn: (scn[i], 0))
    rows = n_tiles * tile
    return pl.pallas_call(
        functools.partial(_mega_open_kernel, cfg=cfg),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(n_tiles,),
            in_specs=[blk_p, blk_q, blk_q, blk_q, blk_q, blk_n],
            out_specs=[pl.BlockSpec((tile, MEGA_NSTAT),
                                    lambda i, scn: (i, 0))]),
        out_shape=[jax.ShapeDtypeStruct((rows, MEGA_NSTAT), jnp.int32)],
        interpret=interpret,
    )(tile_scn, params, qa, qr, qs, qw, npb)


_jit = functools.partial(
    jax.jit, static_argnames=("cfg", "n_tiles", "tile", "interpret"))
_closed_call_jit = _jit(_closed_call)
_open_call_jit = _jit(_open_call)


def run_mega(grid, *, n_shards=1, tile=None, chunk_tiles=CHUNK_TILES):
    """Run every cell of `grid` (an `engine._Grid` built with
    ``stack_streams=False``) through the fused tick-loop kernel.

    Returns a dict of canonical-cell-order [G] integer arrays (keys
    ``reads writes hits misses refpb refab lat_sum maxlag last_done p99
    finished``, plus ``core_finish`` [G, C] for closed grids) — exactly
    the inputs `engine._finalize` needs, so the engine never touches
    MS_* columns.

    The kernel runs in interpret mode; on a TPU, where that would hide
    the device behind the interpreter and Mosaic refuses the compiled
    kernel, this raises `MegakernelRefused` before lowering."""
    if jax.default_backend() == "tpu":
        raise MegakernelRefused(REFUSAL)
    if n_shards < 1:
        raise ValueError(f"n_shards must be >= 1, got {n_shards}")
    closed = grid.closed
    cfg = jaxbody.closed_cfg(grid) if closed else jaxbody.open_cfg(grid)
    params = _pack_params(grid)
    rows, tile_scn, tile = _layout(grid, tile)
    n_tiles = int(tile_scn.shape[0])
    # fixed-shape chunks: one compiled program, streamed results
    chunk = max(1, min(int(chunk_tiles), -(-n_tiles // n_shards)))
    per_call = chunk * n_shards
    pad_t = -n_tiles % per_call
    if pad_t:
        tile_scn = np.concatenate(
            [tile_scn, np.zeros(pad_t, np.int32)])
        rows = np.concatenate([rows, np.full(pad_t * tile, -1, np.int32)])
    real = rows >= 0
    pp = np.zeros((rows.shape[0], MEGA_NPARAM), np.int32)
    pp[real] = params[rows[real]]
    pp[~real] = _pad_row()

    j32 = lambda a: jnp.asarray(a, jnp.int32)
    if closed:
        streams = tuple(j32(a) for a in (
            grid.scn_write, grid.scn_bank, grid.scn_row, grid.scn_sub,
            grid.scn_think, grid.scn_nreq))
    else:
        streams = tuple(j32(a) for a in (
            grid.scn_qa, grid.scn_qr, grid.scn_qs, grid.scn_qw,
            grid.scn_npb))
    raw = _closed_call if closed else _open_call

    if n_shards > 1:
        devs = jax.devices()
        if len(devs) < n_shards:
            raise ValueError(
                f"n_shards={n_shards} but only {len(devs)} devices are "
                "visible; on CPU set XLA_FLAGS="
                "--xla_force_host_platform_device_count before jax "
                "imports")
        mesh = Mesh(np.asarray(devs[:n_shards]), ("data",))
        with sharding_context(mesh, LOGICAL_RULES_SINGLE_POD):
            tiles_p = logical_to_spec(("cells",))
            row_p = logical_to_spec(("cells", None))
        rep = [P(*([None] * a.ndim)) for a in streams]
        fn = jax.jit(jax.shard_map(
            functools.partial(raw, cfg=cfg, n_tiles=chunk, tile=tile,
                              interpret=True),
            mesh=mesh, in_specs=(tiles_p, row_p, *rep),
            out_specs=(row_p, row_p) if closed else (row_p,),
            check_vma=False))
    else:
        fn = functools.partial(
            _closed_call_jit if closed else _open_call_jit,
            cfg=cfg, n_tiles=per_call, tile=tile, interpret=True)

    n_chunks = -(-int(tile_scn.shape[0]) // per_call)
    stat_parts, cf_parts = [], []
    for c in range(n_chunks):
        ts = jnp.asarray(tile_scn[c * per_call:(c + 1) * per_call])
        ppc = jnp.asarray(
            pp[c * per_call * tile:(c + 1) * per_call * tile])
        out = fn(ts, ppc, *streams)
        stat_parts.append(np.asarray(out[0]))
        if closed:
            cf_parts.append(np.asarray(out[1]))
    stats = np.concatenate(stat_parts, axis=0)
    idx = rows[real]
    res = np.zeros((grid.G, MEGA_NSTAT), np.int32)
    res[idx] = stats[real]
    out_d = dict(reads=res[:, MS_READS], writes=res[:, MS_WRITES],
                 hits=res[:, MS_HITS], misses=res[:, MS_MISSES],
                 refpb=res[:, MS_REFPB], refab=res[:, MS_REFAB],
                 lat_sum=res[:, MS_LATSUM], maxlag=res[:, MS_MAXLAG],
                 last_done=res[:, MS_LASTDONE], p99=res[:, MS_P99],
                 finished=res[:, MS_FINISHED] != 0)
    if closed:
        cf = np.concatenate(cf_parts, axis=0)
        cf_g = np.zeros((grid.G, cfg.C), np.int32)
        cf_g[idx] = cf[real]
        out_d["core_finish"] = cf_g
    return out_d
