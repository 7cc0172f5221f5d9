"""Pallas TPU kernel: Mamba2 SSD chunked scan.

TPU adaptation of the SSD algorithm (DESIGN.md §6): the intra-chunk term is
a (C×C)·(C×P) matmul chain (MXU work — this is exactly the "duality" the
paper exploits), the inter-chunk recurrence is carried in a VMEM scratch
state that persists across the sequential chunk axis of the grid.

Grid: (B, H, NC) — NC (chunks) is the innermost, sequential dimension, so
the (P, N) state scratch is a true running carry per (batch, head).
The wrapper moves heads ahead of time so every block ends in a whole tile:
x (1, 1, C, P); dt both as a column (1, 1, C, 1) and a row (1, 1, 1, C), so
cumulative sums come from masked reductions in either orientation without
a transpose; B/C (1, C, N); the per-head decay A in scalar memory; state
scratch (P, N) f32.  On a TPU the chunk is a multiple of 128.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, dtc_ref, dtr_ref, a_ref, b_ref, c_ref, y_ref, st_ref,
            state):
    nc = pl.program_id(2)
    n_chunks = pl.num_programs(2)

    @pl.when(nc == 0)
    def _init():
        state[...] = jnp.zeros_like(state)

    x = x_ref[0, 0]                             # (C, P)
    a = a_ref[pl.program_id(1)]                 # scalar (negative)
    dA_col = dtc_ref[0, 0] * a                  # (C, 1) log-decay
    dA_row = dtr_ref[0, 0] * a                  # (1, C)
    bm = b_ref[0]                               # (C, N)
    cm = c_ref[0]                               # (C, N)

    chunk = x.shape[0]
    row = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    mask = col <= row                           # causal, incl. diagonal
    # inclusive cumsum of dA, as a column and as a row
    cs_col = jnp.sum(jnp.where(mask, dA_row, 0.0), axis=1, keepdims=True)
    cs_row = jnp.sum(jnp.where(row <= col, dA_col, 0.0), axis=0,
                     keepdims=True)
    total = jnp.sum(dA_row, axis=1, keepdims=True)            # (1, 1)

    # intra-chunk: (C B^T ⊙ L) (dt x)
    L = jnp.where(mask, jnp.exp(cs_col - cs_row), 0.0)
    cb = jax.lax.dot_general(cm, bm, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    xdt = x * dtc_ref[0, 0]                     # (C, P)
    y_intra = jnp.dot(cb * L, xdt, preferred_element_type=jnp.float32)

    # inter-chunk: carried state contribution
    y_inter = jax.lax.dot_general(
        cm, state[...], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * jnp.exp(cs_col)
    y_ref[0, 0] = (y_intra + y_inter).astype(y_ref.dtype)

    # state update: H <- exp(Σ dA) H + Σ_i decay_i B_i (dt x)_i
    decay_to_end = jnp.exp(total - cs_col)      # (C, 1)
    s_new = jax.lax.dot_general(xdt, bm * decay_to_end,
                                (((0,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)  # (P, N)
    state[...] = jnp.exp(total) * state[...] + s_new

    @pl.when(nc == n_chunks - 1)
    def _emit_state():
        st_ref[0, 0] = state[...].astype(st_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x: jnp.ndarray, dt: jnp.ndarray, A: jnp.ndarray,
             B: jnp.ndarray, C: jnp.ndarray, *, chunk: int = 128,
             interpret: bool = True):
    """x: (b,T,h,p); dt: (b,T,h); A: (h,); B,C: (b,T,n).

    Returns (y (b,T,h,p), final_state (b,h,p,n)).  D-skip is applied by the
    caller (ops.ssd_scan)."""
    b, t, h, p = x.shape
    n = B.shape[-1]
    assert t % chunk == 0, (t, chunk)
    ncs = t // chunk
    grid = (b, h, ncs)
    scratch = [pltpu.VMEM((p, n), jnp.float32)]
    dt_h = dt.transpose(0, 2, 1)                # (b, h, T)
    y, st = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, chunk, p), lambda bi, hi, ci: (bi, hi, ci, 0)),
            pl.BlockSpec((1, 1, chunk, 1), lambda bi, hi, ci: (bi, hi, ci, 0)),
            pl.BlockSpec((1, 1, 1, chunk), lambda bi, hi, ci: (bi, hi, 0, ci)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, chunk, n), lambda bi, hi, ci: (bi, ci, 0)),
            pl.BlockSpec((1, chunk, n), lambda bi, hi, ci: (bi, ci, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, chunk, p), lambda bi, hi, ci: (bi, hi, ci, 0)),
            pl.BlockSpec((1, 1, p, n), lambda bi, hi, ci: (bi, hi, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, t, p), x.dtype),
            jax.ShapeDtypeStruct((b, h, p, n), jnp.float32),
        ],
        scratch_shapes=scratch,
        interpret=interpret,
    )(x.transpose(0, 2, 1, 3), dt_h[..., None], dt_h[:, :, None, :],
      A.astype(jnp.float32), B, C)
    return y.transpose(0, 2, 1, 3), st
