"""Pallas TPU kernel: tiled constrained Pareto-domination primitive.

The NSGA-II ranking hot path needs, for every individual q, which
individuals p Deb-dominate it.  Materializing that as a dense
(pop, pop) matrix — as the original ``nsga2_jax`` path did — costs
O(pop² · m) bytes of broadcast temporaries and caps populations around 2k.
This kernel walks the pair space in (row-tile × column-tile) blocks so the
dense relation never exists in memory:

* :func:`packed_domination` — each grid step compares a (bp, bq) tile
  and writes it bit-packed (32 dominators per uint32 word, the layout
  ``nsga2_jax._pack_bits`` produces), straight into the (ceil(r/32), n)
  output.  Peak live memory is the packed words plus one tile.

The ``pallas_call`` carries its function's name, which a device trace
shows as the kernel's operation name.  It takes the dominator rows and
the column population separately so the row space can be sharded across
devices (``shard_map`` over row tiles in ``kernels.ops``).  The pure-jnp
blocked twin lives in ``kernels.ref``; ground truth for both is the dense
``nsga2_jax.domination_matrix``.  Objectives/violations are compared in
float32; ``interpret=True`` runs the same grid on CPU (the correctness
harness; compiled Mosaic on real TPU).

Layout: row-side operands are (bp, m) / (bp, 1) column blocks and the
column population enters transposed as (m, bq) / (1, bq) row blocks, so
the tile is built from static slices that broadcast without a gather or a
sublane-to-lane relayout (both of which Mosaic refuses).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# one Deb constrained-domination tile definition for both impls: plain jnp
# ops, so it traces identically inside pallas_call and in the blocked twins
from repro.kernels.ref import dominates_tile as _dom_tile


def _packed_kernel(fp_ref, cvp_ref, fqt_ref, cvq_ref, o_ref):
    dom = _dom_tile(fp_ref[...], cvp_ref[...], fqt_ref[...], cvq_ref[...])
    bp, bq = dom.shape
    # bit j of word w is row 32w+j; bits are disjoint, so the int32 sum is
    # their OR (bit 31 lands as the sign bit, reinterpreted as uint32)
    words = dom.astype(jnp.int32).reshape(bp // 32, 32, bq)
    bits = jax.lax.broadcasted_iota(jnp.int32, (1, 32, 1), 1)
    o_ref[...] = jax.lax.bitcast_convert_type(
        jnp.sum(words << bits, axis=1), jnp.uint32)


def _col_major(f_cols, cv_cols):
    """Column population as lane-major rows: (m, n) objectives, (1, n)
    violations — the layout ``dominates_tile`` broadcasts against."""
    return (f_cols.astype(jnp.float32).T,
            cv_cols.astype(jnp.float32).reshape(1, -1))


@functools.partial(jax.jit, static_argnames=("bp", "bq", "interpret"))
def packed_domination(f_rows: jnp.ndarray, cv_rows: jnp.ndarray,
                      f_cols: jnp.ndarray, cv_cols: jnp.ndarray, *,
                      bp: int = 256, bq: int = 256,
                      interpret: bool = True) -> jnp.ndarray:
    """Bit-packed domination rows: out word (w, q) bit j = row 32w+j of
    (f_rows, cv_rows) Deb-dominates column q of (f_cols, cv_cols).

    f_rows: (r, m); f_cols: (n, m); r % bp == 0, n % bq == 0, bp % 256 == 0
    and bq % 128 == 0 (TPU tiling: the (bp // 32, bq) output block needs 8
    sublanes; the ops wrapper pads with +inf violations, which dominate
    nothing).  Returns (r // 32, n) uint32.
    """
    r, m = f_rows.shape
    n = f_cols.shape[0]
    assert r % bp == 0 and n % bq == 0 and bp % 32 == 0, (r, n, bp, bq)
    fqt, cvq = _col_major(f_cols, cv_cols)
    return pl.pallas_call(
        _packed_kernel,
        grid=(r // bp, n // bq),
        in_specs=[
            pl.BlockSpec((bp, m), lambda i, j: (i, 0)),
            pl.BlockSpec((bp, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((m, bq), lambda i, j: (0, j)),
            pl.BlockSpec((1, bq), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((bp // 32, bq), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((r // 32, n), jnp.uint32),
        interpret=interpret,
        name="packed_domination",
    )(f_rows.astype(jnp.float32),
      cv_rows.astype(jnp.float32).reshape(-1, 1), fqt, cvq)
