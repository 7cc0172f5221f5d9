"""Pure-jnp oracles for every Pallas kernel (the allclose ground truth)."""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp


# -- quant_matmul -------------------------------------------------------------

def quant_matmul(x: jnp.ndarray, w_q: jnp.ndarray, w_scale: jnp.ndarray,
                 x_scale: jnp.ndarray) -> jnp.ndarray:
    """Fake-quant matmul: y = q(x) @ (w_q * w_scale).

    x: (M, K) float; w_q: (K, N) int8; w_scale: (N,); x_scale: scalar.
    x is quantized symmetric-8bit on the fly with the given scale.
    """
    xq = jnp.clip(jnp.round(x / x_scale), -128, 127)
    acc = (xq.astype(jnp.float32) @ w_q.astype(jnp.float32))
    return acc * x_scale * w_scale[None, :]


# -- ssd_scan ------------------------------------------------------------------

def ssd_scan(x: jnp.ndarray, dt: jnp.ndarray, A: jnp.ndarray,
             B: jnp.ndarray, C: jnp.ndarray, chunk: int,
             init_state: Optional[jnp.ndarray] = None
             ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Chunked SSD without the D skip term (the op adds it outside).

    Shapes as in repro.nn.ssm.ssd_chunked.  Returns (y, final_state)."""
    from repro.nn.ssm import ssd_chunked
    return ssd_chunked(x, dt, A, B, C, chunk, D=None, init_state=init_state)


# -- pareto_rank ---------------------------------------------------------------

def dominates_tile(Fp: jnp.ndarray, cvp: jnp.ndarray,
                   FqT: jnp.ndarray, cvq: jnp.ndarray) -> jnp.ndarray:
    """Deb constrained-domination tile: out[i, j] = (Fp[i], cvp[i, 0])
    dominates (FqT[:, j], cvq[0, j]).

    Rows arrive as columns — Fp (rows, m), cvp (rows, 1) — and columns as
    rows — FqT (m, cols), cvq (1, cols) — so every operand is a plain static
    slice that broadcasts to (rows, cols): no gather and no relayout, which
    is what Mosaic needs to compile it.  The objective loop is unrolled over
    the (static, small) objective count so no (rows, cols, m) temporary is
    ever materialized — the building block every blocked/tiled Pareto
    primitive shares."""
    all_le = any_lt = None
    for j in range(Fp.shape[1]):
        a, b = Fp[:, j:j + 1], FqT[j:j + 1, :]
        le, lt = a <= b, a < b
        all_le = le if all_le is None else all_le & le
        any_lt = lt if any_lt is None else any_lt | lt
    feas_p, feas_q = cvp <= 0, cvq <= 0
    # feasible beats infeasible; two infeasible compare by violation; two
    # feasible by Pareto domination
    return ((feas_p & ~feas_q) | (~feas_p & ~feas_q & (cvp < cvq))
            | (feas_p & feas_q & all_le & any_lt))


def _pack_rows(B: jnp.ndarray) -> jnp.ndarray:
    """Pack a (rows, n) bool tile into (rows // 32, n) uint32 words (bit j of
    word w = B[32w + j] — the ``nsga2_jax._pack_bits`` layout)."""
    rows, n = B.shape
    W = B.reshape(rows // 32, 32, n).astype(jnp.uint32)
    weights = jnp.uint32(1) << jnp.arange(32, dtype=jnp.uint32)
    return (W * weights[None, :, None]).sum(axis=1, dtype=jnp.uint32)


def _pad_rows(Fr, cvr, rows):
    pad = (-Fr.shape[0]) % rows
    if pad:
        # +inf violation: padding rows dominate nothing, so their bits are 0
        Fr = jnp.pad(Fr, ((0, pad), (0, 0)))
        cvr = jnp.pad(cvr, (0, pad), constant_values=jnp.inf)
    return Fr, cvr


def packed_domination(Fr: jnp.ndarray, cvr: jnp.ndarray,
                      Fq: jnp.ndarray, cvq: jnp.ndarray,
                      block: int = 1024) -> jnp.ndarray:
    """Bit-packed constrained-domination rows, built tile-by-tile.

    Returns (ceil(len(Fr)/32), len(Fq)) uint32 — bit-for-bit the packing of
    the dense ``domination_matrix`` rows, but peak working memory is
    O(len(Fq) * block) instead of O(rows * cols * m): a ``lax.map`` walks
    row tiles of dominators against the full column set.
    """
    r = Fr.shape[0]
    rows = max(32, min(block, r + (-r) % 32) // 32 * 32)
    Fr, cvr = _pad_rows(Fr, cvr, rows)
    FqT, cvq = Fq.T, cvq[None, :]
    def tile(args):
        fp, cp = args
        return _pack_rows(dominates_tile(fp, cp, FqT, cvq))
    words = jax.lax.map(tile, (Fr.reshape(-1, rows, Fr.shape[1]),
                               cvr.reshape(-1, rows, 1)))
    return words.reshape(-1, Fq.shape[0])[: (r + 31) // 32]


def domination_counts(F: jnp.ndarray, CV: jnp.ndarray,
                      alive: Optional[jnp.ndarray] = None,
                      block: int = 1024) -> jnp.ndarray:
    """Per-individual count of (alive) constrained dominators, accumulated
    tile-by-tile over dominator row blocks — O(n * block) peak memory, the
    streaming twin of ``domination_matrix(...).sum(axis=0)``."""
    n = F.shape[0]
    if alive is None:
        alive = jnp.ones(n, dtype=bool)
    rows = max(32, min(block, n + (-n) % 32) // 32 * 32)
    Fp, cvp = _pad_rows(F, CV, rows)
    ap = jnp.pad(alive, (0, Fp.shape[0] - n))
    FT, cvq = F.T, CV[None, :]
    def step(acc, args):
        fp, cp, al = args
        d = dominates_tile(fp, cp, FT, cvq) & al[:, None]
        return acc + jnp.sum(d, axis=0, dtype=jnp.int32), None
    acc, _ = jax.lax.scan(
        step, jnp.zeros(n, dtype=jnp.int32),
        (Fp.reshape(-1, rows, F.shape[1]), cvp.reshape(-1, rows, 1),
         ap.reshape(-1, rows)))
    return acc


# -- window_attn ----------------------------------------------------------------

def window_attn(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                window: int) -> jnp.ndarray:
    """Sliding-window causal attention.

    q, k, v: (B, T, H, hd) (same head count — GQA expansion happens in the
    caller).  Position i attends to j in (i-window, i].  Returns (B,T,H,hd).
    """
    b, t, h, hd = q.shape
    pos = jnp.arange(t)
    mask = (pos[None, :] <= pos[:, None]) & (pos[None, :] > pos[:, None] - window)
    scores = jnp.einsum("bihd,bjhd->bhij", q, k) / jnp.sqrt(hd)
    scores = jnp.where(mask[None, None], scores, -jnp.inf)
    p = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(q.dtype)
    return jnp.einsum("bhij,bjhd->bihd", p, v)
