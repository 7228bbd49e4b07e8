"""`jaxbody.pick`, the one-hot lane select the tick bodies read each bank's
queue head and head subarray with, against the gathers it replaced:
equal bit for bit on int32 planes holding the padding values the bodies
store (-1, 0, `_PAD_ARRIVE`), on bool planes, at every axis length a
ring queue or a bank's subarrays can have, and on a plane broadcast over
the middle axis (the closed front end's bank tails), eager and jitted.
And each core's next request from the packed stream record
(`jaxbody.next_requests`) against the five gathers it replaced."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.sweep.engine import _PAD_ARRIVE
from repro.core.sweep.jaxbody import (TickCfg, next_requests, pick,
                                      stream_record)

LENGTHS = (2, 8, 128, 512)
G, B = 3, 5


def _plane(rng, n, dtype):
    """A [G, B, n] plane: int32 drawn from the values the bodies hold
    (-1 for a closed row, 0, `_PAD_ARRIVE`, and ordinary ticks), or
    bool."""
    if dtype == jnp.bool_:
        return rng.random((G, B, n)) < 0.5
    vals = np.array([-1, 0, int(_PAD_ARRIVE), 1, 7, 4095, -(1 << 30)],
                    np.int32)
    return np.where(rng.random((G, B, n)) < 0.6,
                    rng.choice(vals, (G, B, n)),
                    rng.integers(-(1 << 31), (1 << 31) - 1, (G, B, n),
                                 dtype=np.int32)).astype(np.int32)


def _indices(rng, n):
    """[G, B] indices into an axis of length `n`, with the first and last
    lane among them."""
    idx = rng.integers(0, n, (G, B)).astype(np.int32)
    idx[0, 0], idx[-1, -1] = 0, n - 1
    return idx


@pytest.mark.parametrize("dtype", [jnp.int32, jnp.bool_],
                         ids=["int32", "bool"])
@pytest.mark.parametrize("n", LENGTHS)
def test_pick_equals_take_along_axis(n, dtype):
    rng = np.random.default_rng(n)
    plane, idx = _plane(rng, n, dtype), _indices(rng, n)
    want = jnp.take_along_axis(jnp.asarray(plane), jnp.asarray(idx)[..., None],
                               axis=2)[..., 0]
    for got in (pick(jnp.asarray(plane), jnp.asarray(idx)),
                jax.jit(pick)(plane, idx)):
        assert got.dtype == want.dtype and got.shape == (G, B)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        np.testing.assert_array_equal(
            np.asarray(got), np.take_along_axis(plane, idx[..., None],
                                                axis=2)[..., 0])
    # a [G, n] plane broadcast over the middle axis (the closed front
    # end's `tail_b`: `q_tail` over the cores) reads `row[idx]`
    row = plane[:, 0, :]
    want = np.take_along_axis(row, idx, axis=1)
    for got in (pick(jnp.broadcast_to(jnp.asarray(row)[:, None, :],
                                      (G, B, n)), jnp.asarray(idx)),
                jax.jit(lambda r, i: pick(
                    jnp.broadcast_to(r[:, None, :], (G, B, n)), i))(row,
                                                                    idx)):
        assert got.dtype == want.dtype
        np.testing.assert_array_equal(np.asarray(got), want)


@pytest.mark.parametrize("dtype", [jnp.int32, jnp.bool_],
                         ids=["int32", "bool"])
@pytest.mark.parametrize("lq", LENGTHS)
def test_pick_reads_wrapped_ring_heads_like_the_flat_gather(lq, dtype):
    """The closed body's ring queues: flat [G*B*LQ] planes whose head slot
    is ``q_head & (LQ - 1)``. Every tail has gone round the ring once,
    and some rings have wrapped (head slot past tail slot), the first
    with its head in the last slot; the pick over the [G, B, LQ] view
    reads what the flat gather ``q[(g*B + b)*LQ + slot]`` read."""
    rng = np.random.default_rng(1000 + lq)
    flat = _plane(rng, lq, dtype).reshape(G * B * lq)
    occ = rng.integers(1, lq, (G, B))            # occupancy, 1..LQ-1
    tail = lq + rng.integers(0, lq, (G, B))      # tails past one lap
    occ[0, 0], tail[0, 0] = 1, lq                # head in the last slot
    head = tail - occ
    hslot = (head & (lq - 1)).astype(np.int32)
    # a wrapped ring: the tail slot has come round behind the head slot
    assert (hslot > (tail & (lq - 1))).any()
    flat_gb = np.arange(G)[:, None] * B + np.arange(B)[None, :]
    want = jnp.asarray(flat)[flat_gb * lq + hslot]
    got = jax.jit(lambda q, s: pick(q.reshape(G, B, lq), s))(flat, hslot)
    assert got.dtype == want.dtype
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_pick_of_a_constant_plane_is_that_constant():
    """`_PAD_ARRIVE` everywhere (an empty ring's arrival plane) and -1
    everywhere (all rows closed) read back unchanged at every index: the
    lanes the mask drops add nothing."""
    idx = jnp.asarray(_indices(np.random.default_rng(0), 8))
    for v in (int(_PAD_ARRIVE), -1, 0):
        got = pick(jnp.full((G, B, 8), v, jnp.int32), idx)
        np.testing.assert_array_equal(np.asarray(got), np.full((G, B), v))


# ------------------------------------------------- closed front end reads
#: banks and subarrays per cell, and cores per cell, of the streams below
SB, SS, SC = 16, 8, 4


def _stream_cfg(n):
    return TickCfg(closed=True, B=SB, S=SS, NB=SB, NR=1, R=1, NC=1, HI=0,
                   LO=0, has_stag=False, has_hra=False, C=SC, N=n)


def _streams(rng, n):
    """Random [G, SC, n] per-core streams: (is_write, bank, sub, row,
    think), rows over the whole int32 range."""
    shape = (G, SC, n)
    return (rng.random(shape) < 0.3,
            rng.integers(0, SB, shape, dtype=np.int32),
            rng.integers(0, SS, shape, dtype=np.int32),
            rng.integers(-(1 << 31), (1 << 31) - 1, shape, dtype=np.int32),
            rng.integers(0, 1 << 20, shape, dtype=np.int32))


@pytest.mark.parametrize("n", (1, 500, 1000, 2048))
def test_next_requests_equal_the_five_stream_gathers(n):
    """`next_requests` over the packed record, `sr` and `sth` gives back
    bit for bit the ``(bank, sub, is_write, row, think)`` that the five
    gathers ``sb/ssub/sw/sr/sth[flat_gc, sl]`` from [G*C, N] planes
    gave, at the first and last entry and past the end (clamped), with
    no gather left in the traced program."""
    rng = np.random.default_rng(n)
    sw, sb, ssub, sr, sth = _streams(rng, n)
    cfg = _stream_cfg(n)
    cst = {k: jnp.asarray(p.reshape(G * SC, n)) for k, p in (
        ("srec", stream_record(sb, ssub, sw, SS)), ("sr", sr), ("sth", sth))}
    next_idx = rng.integers(0, n + 3, (G, SC)).astype(np.int32)
    next_idx[0, :3] = 0, n - 1, n               # first, last, one past
    next_idx[-1, -1] = n + 1000                 # far past the end
    flat_gc = np.arange(G)[:, None] * SC + np.arange(SC)[None, :]
    sl = jnp.minimum(jnp.asarray(next_idx), n - 1)
    want = [jnp.asarray(p.reshape(G * SC, n))[flat_gc, sl]
            for p in (sb, ssub, sw, sr, sth)]
    jitted = jax.jit(next_requests, static_argnums=0)
    for got in (next_requests(cfg, cst, jnp.asarray(next_idx)),
                jitted(cfg, cst, next_idx)):
        for g, w in zip(got, want, strict=True):
            assert g.dtype == w.dtype and g.shape == (G, SC)
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    assert "gather" not in str(jax.make_jaxpr(
        lambda c, i: next_requests(cfg, c, i))(cst, next_idx))
