"""Jit'd kernel wrappers with implementation dispatch.

``impl`` resolution: 'pallas' uses the Pallas kernel (interpret=True on CPU
— a correctness harness; compiled Mosaic on real TPU), 'ref' uses the
pure-jnp oracle, 'auto' picks ref on CPU backends and pallas on TPU.
Dry-run lowering always uses 'ref' (DESIGN.md §6).

A 'pallas' request for a shape the kernel cannot tile raises: a wrapper
never swaps in the reference behind the caller's back, so a run that asked
for the kernel either ran it or failed.
"""

from __future__ import annotations

import os
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import ref as _ref


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


_IMPLS = ("auto", "ref", "pallas")


def resolve_impl(impl: str) -> str:
    if impl not in _IMPLS:
        raise ValueError(f"unknown impl {impl!r}; valid choices: "
                         f"{', '.join(_IMPLS)}")
    if impl == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "ref"
    return impl


def resolve_rank_impl(impl: str) -> str:
    """Like :func:`resolve_impl`, with an env override for 'auto': the CI
    kernel-interpret leg sets ``REPRO_RANK_IMPL=pallas`` so every 'auto'
    caller exercises the Pallas branch (interpret=True) on CPU."""
    if impl not in _IMPLS:
        raise ValueError(f"unknown rank impl {impl!r}; valid choices: "
                         f"{', '.join(_IMPLS)}")
    if impl == "auto":
        env = os.environ.get("REPRO_RANK_IMPL", "auto")
        if env not in _IMPLS:
            raise ValueError(
                f"invalid REPRO_RANK_IMPL={env!r}; valid choices: "
                f"{', '.join(_IMPLS)} (unset the variable for backend "
                "auto-detection)")
        impl = env
    return resolve_impl(impl)


# -- pareto_rank ----------------------------------------------------------------

# fixed column tile for the Pallas branch: rows follow the caller's block
# (the knob trades tile-loop overhead against working-set size) while the
# column width stays VMEM-friendly at any row count
_PALLAS_COL_TILE = 256


def _row_tile(block: int) -> int:
    # the packed output block is (rows // 32, cols): rows must give it a
    # whole number of 8-sublane tiles on TPU, so the row tile is a multiple
    # of 256 whatever smaller block the caller asks for
    return max(256, block // 256 * 256)


def _packed_rows(Fr, cvr, Fq, cvq, block: int, impl: str) -> jnp.ndarray:
    """(ceil(r/32), n) packed domination rows, shape-legalizing pads."""
    r, n = Fr.shape[0], Fq.shape[0]
    if impl == "ref":
        return _ref.packed_domination(Fr, cvr, Fq, cvq, block)
    from repro.kernels.pareto_rank import packed_domination as k
    bp, bq = _row_tile(block), _PALLAS_COL_TILE
    Fr, cvr = _ref._pad_rows(Fr, cvr, bp)
    Fq, cvq = _ref._pad_rows(Fq, cvq, bq)
    out = k(Fr, cvr, Fq, cvq, bp=bp, bq=bq, interpret=_interpret())
    return out[: (r + 31) // 32, :n]


def packed_domination(F, CV, *, block: int = 1024, impl: str = "auto",
                      mesh=None) -> jnp.ndarray:
    """Bit-packed constrained-domination matrix, built tile-by-tile.

    Returns (ceil(n/32), n) uint32 in the ``nsga2_jax._pack_bits`` layout —
    bit-identical to packing the dense ``domination_matrix``, but the dense
    (n, n[, m]) boolean temporaries never exist: peak working memory is the
    packed words plus one (block, n) tile.  With a 1-D ``mesh`` the
    dominator row-tiles are sharded across its devices with
    ``jax.shard_map``.
    """
    impl = resolve_rank_impl(impl)
    F = jnp.asarray(F, jnp.float32)
    CV = jnp.asarray(CV, jnp.float32)
    n = F.shape[0]
    W = (n + 31) // 32
    if mesh is not None and mesh.size > 1:
        from jax.sharding import PartitionSpec as P
        ax = mesh.axis_names[0]
        Fr, cvr = _ref._pad_rows(F, CV, 32 * mesh.size)
        fn = jax.shard_map(
            lambda fr, cr, fq, cq: _packed_rows(fr, cr, fq, cq, block, impl),
            mesh=mesh, in_specs=(P(ax, None), P(ax), P(None, None), P(None)),
            out_specs=P(ax, None), check_vma=False)
        return fn(Fr, cvr, F, CV)[:W]
    return _packed_rows(F, CV, F, CV, block, impl)[:W]


def domination_counts(F, CV, alive: Optional[jnp.ndarray] = None, *,
                      block: int = 1024, impl: str = "auto") -> jnp.ndarray:
    """(n,) int32 count of alive constrained dominators per individual,
    accumulated tile-by-tile — O(n · block) peak memory.  ``counts == 0``
    is the first constrained front (used to merge restart fronts without a
    dense host-side sort)."""
    impl = resolve_rank_impl(impl)
    F = jnp.asarray(F, jnp.float32)
    CV = jnp.asarray(CV, jnp.float32)
    n = F.shape[0]
    if alive is None:
        alive = jnp.ones(n, dtype=bool)
    if impl == "ref":
        return _ref.domination_counts(F, CV, alive, block)
    from repro.kernels.pareto_rank import domination_counts as k
    bp, bq = _row_tile(block), _PALLAS_COL_TILE
    Fp, cvp = _ref._pad_rows(F, CV, bp)
    ap = jnp.pad(alive, (0, Fp.shape[0] - n))
    Fq, cvq = _ref._pad_rows(F, CV, bq)
    return k(Fp, cvp, ap, Fq, cvq, bp=bp, bq=bq, interpret=_interpret())[:n]


def quant_matmul(x, w_q, w_scale, x_scale, impl: str = "pallas"):
    if resolve_impl(impl) == "ref":
        return _ref.quant_matmul(x, w_q, w_scale, x_scale)
    from repro.kernels.quant_matmul import quant_matmul as k
    m, kk = x.shape
    n = w_q.shape[1]
    if m % 128 or n % 128 or kk % 128:
        raise ValueError(
            f"quant_matmul: pallas needs M, K, N multiples of 128, got "
            f"{(m, kk, n)}; pad the operands or pass impl='ref'")
    return k(x, w_q, w_scale, x_scale, interpret=_interpret())


def ssd_scan(x, dt, A, B, C, chunk: int = 128, impl: str = "pallas"
             ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    if resolve_impl(impl) == "ref":
        return _ref.ssd_scan(x, dt, A, B, C, chunk)
    from repro.kernels.ssd_scan import ssd_scan as k
    return k(x, dt, A, B, C, chunk=chunk, interpret=_interpret())


def window_attn(q, k, v, window: int, impl: str = "pallas"):
    if resolve_impl(impl) == "ref":
        group = q.shape[2] // k.shape[2]
        k_e = jnp.repeat(k, group, axis=2)
        v_e = jnp.repeat(v, group, axis=2)
        return _ref.window_attn(q, k_e, v_e, window)
    from repro.kernels.window_attn import window_attn as kern
    t = q.shape[1]
    if t % 128 or window % 128:
        raise ValueError(
            f"window_attn: pallas needs T and window multiples of 128, got "
            f"T={t}, window={window}; pass impl='ref' for other shapes")
    return kern(q, k, v, window=window, bq=128, bk=128,
                interpret=_interpret())
