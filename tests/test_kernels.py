"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret mode)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.quant_matmul import quant_matmul as pl_quant_matmul
from repro.kernels.ssd_scan import ssd_scan as pl_ssd_scan
from repro.kernels.window_attn import window_attn as pl_window_attn


# -- quant_matmul ---------------------------------------------------------------

@pytest.mark.parametrize("m,k,n", [(128, 128, 128), (256, 384, 128),
                                   (128, 256, 256)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_quant_matmul_sweep(m, k, n, dtype):
    key = jax.random.PRNGKey(m + k + n)
    x = jax.random.normal(key, (m, k), jnp.float32).astype(dtype).astype(jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), (k, n)) * 0.05
    w_scale = jnp.abs(w).max(axis=0) / 127.0
    w_q = jnp.clip(jnp.round(w / w_scale[None, :]), -128, 127).astype(jnp.int8)
    x_scale = jnp.abs(x).max() / 127.0
    y_ref = ref.quant_matmul(x, w_q, w_scale, x_scale)
    y_pl = pl_quant_matmul(x, w_q, w_scale, x_scale)
    np.testing.assert_allclose(np.asarray(y_pl), np.asarray(y_ref),
                               rtol=1e-5, atol=1e-5)


def test_quant_matmul_blocks():
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (256, 256))
    w = jax.random.normal(jax.random.PRNGKey(1), (256, 256)) * 0.03
    w_scale = jnp.abs(w).max(axis=0) / 127.0
    w_q = jnp.clip(jnp.round(w / w_scale[None, :]), -128, 127).astype(jnp.int8)
    x_scale = jnp.abs(x).max() / 127.0
    y_ref = ref.quant_matmul(x, w_q, w_scale, x_scale)
    for bm, bn, bk in [(128, 128, 128), (256, 128, 128), (128, 256, 256)]:
        y = pl_quant_matmul(x, w_q, w_scale, x_scale, bm=bm, bn=bn, bk=bk)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                   rtol=1e-5, atol=1e-5)


def test_quant_matmul_ops_fallback():
    # an off-grid shape never falls back to the oracle silently: the pallas
    # request raises, and the reference runs only when asked for
    x = jnp.ones((100, 96))
    w_q = jnp.ones((96, 50), jnp.int8)
    with pytest.raises(ValueError, match="multiples of 128"):
        ops.quant_matmul(x, w_q, jnp.ones((50,)), jnp.asarray(0.1))
    y = ops.quant_matmul(x, w_q, jnp.ones((50,)), jnp.asarray(0.1),
                         impl="ref")
    assert y.shape == (100, 50)


def test_window_attn_ops_off_grid_raises():
    q = jnp.ones((1, 96, 4, 32))
    k = v = jnp.ones((1, 96, 2, 32))
    with pytest.raises(ValueError, match="multiples of 128"):
        ops.window_attn(q, k, v, window=64)
    assert ops.window_attn(q, k, v, window=64, impl="ref").shape == q.shape


# -- ssd_scan --------------------------------------------------------------------

@pytest.mark.parametrize("t,chunk", [(128, 32), (256, 64), (192, 64)])
@pytest.mark.parametrize("h,p,n", [(2, 16, 8), (3, 32, 16)])
def test_ssd_scan_sweep(t, chunk, h, p, n):
    if t % chunk:
        pytest.skip("t must be divisible by chunk")
    key = jax.random.PRNGKey(t + h)
    ks = jax.random.split(key, 5)
    b = 2
    x = jax.random.normal(ks[0], (b, t, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, t, h)) - 1)
    A = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.3)
    B = jax.random.normal(ks[3], (b, t, n)) * 0.5
    C = jax.random.normal(ks[4], (b, t, n)) * 0.5
    y_ref, st_ref = ref.ssd_scan(x, dt, A, B, C, chunk)
    y_pl, st_pl = pl_ssd_scan(x, dt, A, B, C, chunk=chunk)
    np.testing.assert_allclose(np.asarray(y_pl), np.asarray(y_ref),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(st_pl), np.asarray(st_ref),
                               rtol=2e-4, atol=2e-4)


def test_ssd_scan_matches_sequential_recurrence():
    """Chunked SSD == naive token-by-token recurrence."""
    from repro.nn.ssm import ssd_step
    key = jax.random.PRNGKey(9)
    ks = jax.random.split(key, 5)
    b, t, h, p, n = 1, 64, 2, 8, 4
    x = jax.random.normal(ks[0], (b, t, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, t, h)))
    A = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.3)
    B = jax.random.normal(ks[3], (b, t, n)) * 0.5
    C = jax.random.normal(ks[4], (b, t, n)) * 0.5
    y_k, st_k = pl_ssd_scan(x, dt, A, B, C, chunk=16)
    state = jnp.zeros((b, h, p, n))
    ys = []
    for i in range(t):
        y_i, state = ssd_step(state, x[:, i], dt[:, i], A, B[:, i], C[:, i])
        ys.append(y_i)
    y_seq = jnp.stack(ys, axis=1)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_seq),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(st_k), np.asarray(state),
                               rtol=1e-4, atol=1e-4)


# -- window_attn ------------------------------------------------------------------

@pytest.mark.parametrize("t,w,bq", [(256, 128, 64), (256, 64, 64),
                                    (512, 256, 128)])
@pytest.mark.parametrize("h,kv,hd", [(4, 2, 64), (4, 4, 32)])
def test_window_attn_sweep(t, w, bq, h, kv, hd):
    key = jax.random.PRNGKey(t + w + h)
    ks = jax.random.split(key, 3)
    b = 2
    q = jax.random.normal(ks[0], (b, t, h, hd))
    k = jax.random.normal(ks[1], (b, t, kv, hd))
    v = jax.random.normal(ks[2], (b, t, kv, hd))
    y_ref = ref.window_attn(q, jnp.repeat(k, h // kv, 2),
                            jnp.repeat(v, h // kv, 2), w)
    y_pl = pl_window_attn(q, k, v, window=w, bq=bq, bk=bq)
    np.testing.assert_allclose(np.asarray(y_pl), np.asarray(y_ref),
                               rtol=2e-5, atol=2e-5)


def test_window_attn_matches_chunked_sdpa():
    from repro.nn.attention import chunked_sdpa
    key = jax.random.PRNGKey(3)
    ks = jax.random.split(key, 3)
    b, t, h, kv, hd, w = 1, 256, 4, 2, 32, 128
    q = jax.random.normal(ks[0], (b, t, h, hd))
    k = jax.random.normal(ks[1], (b, t, kv, hd))
    v = jax.random.normal(ks[2], (b, t, kv, hd))
    y1 = chunked_sdpa(q, k, v, window=w, chunk_q=64)
    y2 = pl_window_attn(q, k, v, window=w, bq=64, bk=64)
    np.testing.assert_allclose(np.asarray(y2), np.asarray(y1),
                               rtol=2e-4, atol=2e-4)
