"""Pipeline parallelism over the ``pod`` mesh axis — the paper's partitioning
executed on the production mesh.

The explorer (``repro.core``) picks the stage boundary; for a homogeneous
transformer stack on identical pods the latency-balanced Def.-2 optimum is
the equal split (the explorer confirms this — see benchmarks), which lets us
use a stacked-stage ``shard_map``: stage parameters (S, L/S, ...) are sharded
over 'pod', microbatches circulate stage-to-stage with ``lax.ppermute``
(GPipe schedule).  Cross-pod traffic per microbatch is exactly the paper's
link tensor: (b_mb, T, d_model).

``pipelined_apply`` matches the monolithic model's logits (tested), modulo
the embed/final-norm/head which run replicated outside the pipelined stack.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models.decoder import DecoderLM, _scan_blocks
from repro.nn.layers import rms_norm


def stack_stages(params: Dict[str, Any], n_stages: int) -> Dict[str, Any]:
    """Reshape scan-stacked blocks (L, ...) -> (S, L/S, ...)."""
    out = dict(params)
    blocks = params["blocks_dense"]
    def rs(x):
        l = x.shape[0]
        assert l % n_stages == 0, (l, n_stages)
        return x.reshape(n_stages, l // n_stages, *x.shape[1:])
    out["blocks_dense"] = jax.tree_util.tree_map(rs, blocks)
    return out


def pipelined_apply(model: DecoderLM, params: Dict[str, Any], batch: Dict,
                    mesh: Mesh, n_microbatches: int,
                    stage_axis: str = "pod") -> jnp.ndarray:
    """Forward pass with the layer stack pipelined over ``stage_axis``.

    params must already be stage-stacked (see ``stack_stages``).  Embedding,
    final norm and head run outside the pipelined region (replicated over
    the stage axis, sharded over data/model as usual).
    """
    n_stages = mesh.shape[stage_axis]
    x, positions = model._embed(params, batch)
    b, t, d = x.shape
    assert b % n_microbatches == 0, (b, n_microbatches)
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(t)[None], (b, t))
    mb = b // n_microbatches
    xs = x.reshape(n_microbatches, mb, t, d)
    pos_mb = positions.reshape(n_microbatches, mb, t) \
        if positions.ndim == 2 else None

    blocks = params["blocks_dense"]

    # everything except the stage axis stays as-is (data/model sharding of
    # microbatches is handled by the outer jit); inside shard_map we only
    # split the stage axis.
    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(P(stage_axis), P(), P()),
        out_specs=P(),
        check_vma=False)
    def run(blocks_stage, xs_all, pos_all):
        # blocks_stage leaves: (1, L/S, ...) — this pod's slice
        blocks_local = jax.tree_util.tree_map(lambda a: a[0], blocks_stage)
        stage = jax.lax.axis_index(stage_axis)
        n_steps = n_microbatches + n_stages - 1

        def stage_fn(x_mb, pos_):
            y, _, _ = _scan_blocks(model.dense_block, blocks_local, x_mb,
                                   pos_)
            return y

        def body(carry, step):
            buf, outputs = carry
            mb_idx = jnp.clip(step, 0, n_microbatches - 1)
            x_in = jax.lax.dynamic_index_in_dim(xs_all, mb_idx, 0,
                                                keepdims=False)
            p_in = jax.lax.dynamic_index_in_dim(pos_all, mb_idx, 0,
                                                keepdims=False)
            inp = jnp.where(stage == 0, x_in, buf)
            out = stage_fn(inp, p_in)
            # hand off to the next stage
            nxt = jax.lax.ppermute(
                out, stage_axis,
                [(i, (i + 1) % n_stages) for i in range(n_stages)])
            # last stage emits: microbatch (step - (S-1)) completes at step
            emit_idx = jnp.clip(step - (n_stages - 1), 0, n_microbatches - 1)
            do_emit = step >= (n_stages - 1)
            outputs = jax.lax.cond(
                do_emit,
                lambda o: jax.lax.dynamic_update_index_in_dim(
                    o, out, emit_idx, 0),
                lambda o: o, outputs)
            return (nxt, outputs), None

        buf0 = jnp.zeros_like(xs_all[0])
        outs0 = jnp.zeros_like(xs_all)
        (_, outputs), _ = jax.lax.scan(body, (buf0, outs0),
                                       jnp.arange(n_steps))
        # only the LAST stage's outputs are real: zero elsewhere + psum
        last = n_stages - 1
        outputs = jnp.where(stage == last, outputs, 0)
        outputs = jax.lax.psum(outputs, stage_axis)
        return outputs

    pos_in = pos_mb if pos_mb is not None else jnp.zeros(
        (n_microbatches, mb, t), jnp.int32)
    outs = run(blocks, xs, pos_in)
    x = outs.reshape(b, t, d)
    x = rms_norm(x, params["final_norm"])
    return model._head(params, x)


def explorer_stage_boundary(cfg: ModelConfig, seq: int, n_stages: int,
                            link: str = "dci") -> Tuple[list, object]:
    """Use the paper's explorer to choose the pipeline cut on TPU pods.

    Returns (cut layer indices, ExplorationResult).  For identical pods the
    Pareto-selected cut is the balanced split; heterogeneous pod mixes move
    it — both come from the same machinery (DESIGN.md §5).
    """
    from repro.core import Platform, QuantSpec, SystemConfig, get_link
    from repro.core.hwmodel.arch import TPU_V5E
    from repro.explore import SearchSettings, explore_graph
    from repro.models.registry import build_model
    import dataclasses as dc

    model = build_model(cfg)
    graph = model.to_graph(seq)
    pod = Platform("pod", dc.replace(TPU_V5E, mem_bytes=256 * 16 * 2 ** 30),
                   QuantSpec(bits=16))
    system = SystemConfig([pod] * n_stages,
                          [get_link(link)] * (n_stages - 1))
    res = explore_graph(graph, system, objectives=("latency", "throughput"),
                        schedule_policy="insertion",
                        search=SearchSettings(seed=0))
    # map graph cut positions back to block indices (2 nodes per block:
    # attention + ffn, plus embed at 0)
    if res.selected is None:          # no feasible partition: balanced split
        step = max(1, cfg.n_layers // n_stages)
        return [min(cfg.n_layers - 1, (k + 1) * step - 1)
                for k in range(n_stages - 1)], res
    cuts = []
    for c in res.selected.cuts:
        layer = max(0, min(cfg.n_layers - 1, c // 2))
        cuts.append(layer)
    return cuts, res
