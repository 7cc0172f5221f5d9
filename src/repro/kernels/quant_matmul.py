"""Pallas TPU kernel: fused fake-quant int8 matmul.

Used by the quantized-inference path that the accuracy-exploration stage
evaluates (§IV-C): activations are quantized on the fly (symmetric int8),
weights arrive pre-quantized (int8 + per-channel scales), accumulation is
f32 in VMEM, and the dequant epilogue is fused.

Blocking: (bm, bk) x (bk, bn) -> (bm, bn), all MXU-aligned multiples of 128.
Grid (M/bm, N/bn, K/bk) with K innermost: the output block is revisited
across the K steps and accumulated in place (standard Pallas matmul
pattern); quant/dequant happen per tile so the working set stays in VMEM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, wq_ref, wscale_ref, xscale_ref, o_ref):
    k_idx = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(k_idx == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    x = x_ref[...]
    x_scale = xscale_ref[0]
    xq = jnp.clip(jnp.round(x / x_scale), -128, 127).astype(jnp.float32)
    wq = wq_ref[...].astype(jnp.float32)
    acc = jnp.dot(xq, wq, preferred_element_type=jnp.float32)
    o_ref[...] += acc

    @pl.when(k_idx == nk - 1)
    def _epilogue():
        o_ref[...] = o_ref[...] * x_scale * wscale_ref[...]


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def quant_matmul(x: jnp.ndarray, w_q: jnp.ndarray, w_scale: jnp.ndarray,
                 x_scale: jnp.ndarray, *, bm: int = 128, bn: int = 128,
                 bk: int = 128, interpret: bool = True) -> jnp.ndarray:
    """x: (M, K) f32; w_q: (K, N) int8; w_scale: (N,); x_scale: scalar."""
    m, k = x.shape
    k2, n = w_q.shape
    assert k == k2 and m % bm == 0 and n % bn == 0 and k % bk == 0, \
        (x.shape, w_q.shape, (bm, bn, bk))
    grid = (m // bm, n // bn, k // bk)
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
            # the scalar activation scale lives in scalar memory
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        interpret=interpret,
    )(x, w_q, w_scale.reshape(1, n),
      jnp.reshape(x_scale, (1,)).astype(jnp.float32))
