"""Pallas TPU kernel: sliding-window flash attention (prefill).

Enables the dense architectures to run the ``long_500k`` shape: position i
attends to (i-window, i], so compute and KV memory are O(T·W), not O(T²).

Grid: (B, H, T/bq, W/bk + 1) — the last (kv) axis is sequential; online
softmax stats (m, l) and the output accumulator live in VMEM scratch across
it.  The k/v block index is derived from (query block, kv step) in the
BlockSpec index map (clamped at 0; out-of-range positions are masked).
GQA is handled by mapping query head h to kv head h // group in the k/v
index maps — no materialized head broadcast.  The wrapper moves heads ahead
of time, (B,T,H,hd) -> (B,H,T,hd), so every block ends in a (rows, hd)
tile that Mosaic can lay out.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            bq: int, bk: int, window: int):
    qi = pl.program_id(2)
    j = pl.program_id(3)
    nj = pl.num_programs(3)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0]                          # (bq, hd)
    k = k_ref[0, 0]                          # (bk, hd)
    v = v_ref[0, 0]                          # (bk, hd)
    hd = q.shape[-1]

    q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    kv_blk = qi + j - (nj - 1)               # may be negative (clamped in map)
    k_pos = (jnp.maximum(kv_blk, 0) * bk
             + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1))
    valid = (kv_blk >= 0) & (k_pos <= q_pos) & (k_pos > q_pos - window)

    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) / jnp.sqrt(
        jnp.float32(hd))
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_scr[...]                      # (bq, 1)
    m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    l_scr[...] = l_scr[...] * alpha + p.sum(axis=1, keepdims=True)
    acc_scr[...] = (acc_scr[...] * alpha
                    + jnp.dot(p, v.astype(jnp.float32),
                              preferred_element_type=jnp.float32))
    m_scr[...] = m_new

    @pl.when(j == nj - 1)
    def _emit():
        denom = jnp.maximum(l_scr[...], 1e-20)
        o_ref[0, 0] = (acc_scr[...] / denom).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("window", "bq", "bk", "interpret"))
def window_attn(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                window: int, bq: int = 128, bk: int = 128,
                interpret: bool = True) -> jnp.ndarray:
    """q: (B,T,H,hd); k/v: (B,T,Kv,hd) with H % Kv == 0. Causal + window."""
    b, t, h, hd = q.shape
    kv = k.shape[2]
    assert h % kv == 0 and t % bq == 0 and t % bk == 0, (q.shape, k.shape)
    assert window % bk == 0, (window, bk)
    group = h // kv
    nj = window // bk + 1
    grid = (b, h, t // bq, nj)

    def kv_map(bi, hi, qi, j):
        return bi, hi // group, jnp.maximum(qi + j - (nj - 1), 0), 0
    scratch = [pltpu.VMEM((bq, 1), jnp.float32),
               pltpu.VMEM((bq, 1), jnp.float32),
               pltpu.VMEM((bq, hd), jnp.float32)]
    kern = functools.partial(_kernel, bq=bq, bk=bk, window=window)
    heads_first = lambda a: a.transpose(0, 2, 1, 3)  # noqa: E731
    out = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda bi, hi, qi, j: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, bk, hd), kv_map),
            pl.BlockSpec((1, 1, bk, hd), kv_map),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, hd),
                               lambda bi, hi, qi, j: (bi, hi, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, t, hd), q.dtype),
        scratch_shapes=scratch,
        interpret=interpret,
    )(heads_first(q), heads_first(k), heads_first(v))
    return heads_first(out)
