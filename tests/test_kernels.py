"""Pallas kernels vs ref.py oracles: shape/dtype sweeps in interpret mode."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.kernels import ops, ref

RS = np.random.RandomState(42)


# ------------------------------------------------------------------- flash
@pytest.mark.parametrize("bh,s,d", [(2, 64, 16), (1, 128, 32), (3, 256, 64)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention(bh, s, d, dtype, causal):
    q = jnp.asarray(RS.randn(bh, s, d), dtype)
    k = jnp.asarray(RS.randn(bh, s, d), dtype)
    v = jnp.asarray(RS.randn(bh, s, d), dtype)
    out = ops.flash_attention(q, k, v, causal=causal)
    expect = ref.flash_attention(q, k, v, causal=causal)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32),
                               atol=tol, rtol=tol)


def test_flash_attention_trainable_grads():
    q = jnp.asarray(RS.randn(2, 64, 16), jnp.float32)
    k = jnp.asarray(RS.randn(2, 64, 16), jnp.float32)
    v = jnp.asarray(RS.randn(2, 64, 16), jnp.float32)

    def f_kern(q, k, v):
        return (ops.flash_attention_trainable(q, k, v, True) ** 2).sum()

    def f_ref(q, k, v):
        return (ref.flash_attention(q, k, v, causal=True)
                .astype(jnp.float32) ** 2).sum()

    gk = jax.grad(f_kern, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-4, rtol=1e-4)


# ---------------------------------------------------------------- kv_quant
@pytest.mark.parametrize("p,t,h,d", [(4, 8, 2, 16), (2, 16, 4, 32), (1, 64, 1, 64)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_kv_quant(p, t, h, d, dtype):
    pages = jnp.asarray(RS.randn(p, t, h, d) * 3, dtype)
    q8, sc = ops.kv_quant(pages)
    q8r, scr = ref.kv_quant(pages)
    np.testing.assert_allclose(np.asarray(sc), np.asarray(scr), rtol=1e-4)
    assert np.abs(np.asarray(q8, np.int32) - np.asarray(q8r, np.int32)).max() <= 1
    # roundtrip error bound: |x - q*s| <= s/2 per element
    deq = np.asarray(q8, np.float32) * np.asarray(sc)[:, None, :, None]
    err = np.abs(deq - np.asarray(pages, np.float32))
    bound = np.asarray(sc)[:, None, :, None] * 0.51 + 1e-6
    assert (err <= bound).all()


# ------------------------------------------------------------ paged (SARP)
@pytest.mark.parametrize("b,h,hkv,d,t,maxp", [
    (2, 4, 2, 16, 8, 3), (1, 8, 8, 32, 16, 2), (3, 6, 2, 64, 8, 4)])
def test_refresh_paged_attention(b, h, hkv, d, t, maxp):
    p_total = maxp * b + 2
    kp = jnp.asarray(RS.randn(p_total, t, hkv, d), jnp.float32)
    vp = jnp.asarray(RS.randn(p_total, t, hkv, d), jnp.float32)
    k8, ks = ref.kv_quant(kp)
    v8, vs = ref.kv_quant(vp)
    perm = RS.permutation(p_total)[:b * maxp].reshape(b, maxp)
    table = jnp.asarray(perm, jnp.int32)
    lens = jnp.asarray(RS.randint(1, maxp * t + 1, b), jnp.int32)
    q = jnp.asarray(RS.randn(b, h, d), jnp.float32)
    out = ops.refresh_paged_attention(q, k8, v8, ks, vs, table, lens,
                                      page_size=t)
    expect = ref.paged_decode_attention(q, k8, v8, ks, vs, table, lens,
                                        page_size=t)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               atol=5e-5, rtol=1e-4)


def test_serial_baseline_matches():
    b, h, hkv, d, t, maxp = 2, 4, 2, 16, 8, 3
    p_total = 8
    kp = jnp.asarray(RS.randn(p_total, t, hkv, d), jnp.float32)
    vp = jnp.asarray(RS.randn(p_total, t, hkv, d), jnp.float32)
    k8, ks = ref.kv_quant(kp)
    v8, vs = ref.kv_quant(vp)
    table = jnp.asarray([[0, 1, 2], [3, 4, 5]], jnp.int32)
    lens = jnp.asarray([17, 24], jnp.int32)
    q = jnp.asarray(RS.randn(b, h, d), jnp.float32)
    fused = ops.refresh_paged_attention(q, k8, v8, ks, vs, table, lens,
                                        page_size=t)
    serial = ops.paged_attention_serial(q, k8, v8, ks, vs, table, lens,
                                        page_size=t)
    np.testing.assert_allclose(np.asarray(fused), np.asarray(serial),
                               atol=2e-2, rtol=2e-2)


# --------------------------------------------------------------------- ssd
@pytest.mark.parametrize("b,s,h,p,n,chunk", [
    (2, 64, 3, 8, 16, 16), (1, 128, 2, 16, 32, 32), (2, 32, 1, 64, 8, 8)])
def test_mamba2_ssd(b, s, h, p, n, chunk):
    x = jnp.asarray(RS.randn(b, s, h, p), jnp.float32)
    dt = jnp.asarray(np.abs(RS.randn(b, s, h)) * 0.1 + 0.01, jnp.float32)
    A = jnp.asarray(-np.abs(RS.randn(h)) - 0.1, jnp.float32)
    Bi = jnp.asarray(RS.randn(b, s, n), jnp.float32)
    Ci = jnp.asarray(RS.randn(b, s, n), jnp.float32)
    y = ops.mamba2_ssd(x, dt, A, Bi, Ci, chunk=chunk)
    yr = ref.mamba2_ssd(x, dt, A, Bi, Ci, chunk=chunk)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               atol=5e-4, rtol=2e-3)


def test_ssd_matches_naive_recurrence():
    """The chunked oracle itself must equal the O(S) recurrence."""
    b, s, h, p, n = 1, 32, 2, 4, 8
    x = jnp.asarray(RS.randn(b, s, h, p), jnp.float32)
    dt = jnp.asarray(np.abs(RS.randn(b, s, h)) * 0.1 + 0.01, jnp.float32)
    A = jnp.asarray(-np.abs(RS.randn(h)) - 0.1, jnp.float32)
    Bi = jnp.asarray(RS.randn(b, s, n), jnp.float32)
    Ci = jnp.asarray(RS.randn(b, s, n), jnp.float32)
    yr = np.asarray(ref.mamba2_ssd(x, dt, A, Bi, Ci, chunk=8))
    # naive
    state = np.zeros((b, h, p, n))
    ys = []
    for t in range(s):
        da = np.exp(np.asarray(dt[:, t]) * np.asarray(A)[None])
        state = state * da[:, :, None, None] + np.einsum(
            "bh,bn,bhp->bhpn", np.asarray(dt[:, t]), np.asarray(Bi[:, t]),
            np.asarray(x[:, t]))
        ys.append(np.einsum("bn,bhpn->bhp", np.asarray(Ci[:, t]), state))
    naive = np.stack(ys, 1)
    np.testing.assert_allclose(yr, naive, atol=1e-4, rtol=1e-3)
