"""`jaxbody.pick`, the one-hot lane select the tick bodies read each bank's
queue head and head subarray with, against the gathers it replaced:
equal bit for bit on int32 planes holding the padding values the bodies
store (-1, 0, `_PAD_ARRIVE`), on bool planes, at every axis length a
ring queue or a bank's subarrays can have, eager and jitted."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.sweep.engine import _PAD_ARRIVE
from repro.core.sweep.jaxbody import pick

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
