"""Mixture-of-Experts FFN — capacity-based scatter dispatch.

Supports DeepSeek-style fine-grained experts: ``n_shared`` always-on shared
experts plus ``n_experts`` routed experts with top-k (softmax or sigmoid
gating).  Dispatch is scatter/gather based (GShard capacity semantics
without the O(T·E·C) one-hot dispatch tensor, which is memory-infeasible at
DeepSeek-V3 scale):

  1. route: top-k experts per token, position-in-expert via cumsum;
  2. scatter tokens into a (groups, E·C, d) buffer (overflow → dropped);
  3. batched expert matmuls on (groups, E, C, d) — experts shard over the
     ``experts``/model axis, groups over ``batch``/data ⇒ the all-to-all
     happens at this boundary;
  4. gather back and combine with router weights.

Aux metrics (Switch load-balance loss, router z-loss, drop fraction) are
returned for the training loop.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.nn.module import Module, normal_init
from repro.nn.sharding import shard


def _gated_ffn(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


# -- batch-local dispatch machinery -------------------------------------------
#
# Scatter/gather with leading batch dims makes GSPMD fall back to full
# replication (measured: one DeepSeek-V3 MoE layer -> 700+ GiB/device).  The
# dispatch is batch-local by construction, so on a mesh we run it inside
# shard_map over the batch axes and GSPMD never sees the scatter.

def _route_positions(idx, cap: int, e: int, k: int):
    """idx: (b, t, k) expert choices -> (slot (b, t·k), keep (b, t, k)).

    Sort-based position-in-expert ranking: O(tk log tk) time, O(tk) memory
    (a one-hot cumsum would materialize (b, t·k, E) — infeasible at 256
    experts × 1M tokens)."""
    b, t, _ = idx.shape
    tk = t * k
    flat = idx.reshape(b, tk)
    order = jnp.argsort(flat, axis=1, stable=True)
    sorted_ids = jnp.take_along_axis(flat, order, axis=1)
    first = jax.vmap(lambda s: jnp.searchsorted(s, s, side="left"))(sorted_ids)
    ranks = jnp.arange(tk)[None, :] - first
    pos = jnp.zeros((b, tk), jnp.int32)
    pos = pos.at[jnp.arange(b)[:, None], order].set(ranks.astype(jnp.int32))
    keep = pos.reshape(b, t, k) < cap
    slot = jnp.where(keep, idx * cap + pos.reshape(b, t, k), e * cap)
    return slot.reshape(b, tk), keep


def _batch_axes_size():
    from repro.nn.sharding import current_mesh, current_rules
    mesh = current_mesh()
    if mesh is None:
        return None, None
    bax = current_rules().get("batch")
    if bax is None:
        return None, None
    axes = (bax,) if isinstance(bax, str) else tuple(bax)
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return (bax if isinstance(bax, str) else tuple(axes)), n


def _maybe_batch_local(fn, args, n_out: int, axes_override=None):
    """Run fn inside shard_map over the batch axes when a mesh is active.

    axes_override: explicit (axis-name-or-tuple, total-size) for the group
    axis — used by the fine-grained (batch × seq-shard) grouping."""
    from jax.sharding import PartitionSpec as P
    from repro.nn.sharding import current_mesh
    mesh = current_mesh()
    if axes_override is not None:
        bax, n = axes_override
    else:
        bax, n = _batch_axes_size()
    b = args[0].shape[0]
    if mesh is None or bax is None or b % n != 0:
        return fn(*args)
    in_specs = tuple(P(bax, *([None] * (a.ndim - 1))) for a in args)
    # fn outputs all carry batch on axis 0
    def spec_for(shape):
        return P(bax, *([None] * (len(shape) - 1)))
    out_shapes = jax.eval_shape(fn, *args)
    flat, treedef = jax.tree_util.tree_flatten(out_shapes)
    out_specs = jax.tree_util.tree_unflatten(
        treedef, [spec_for(s.shape) for s in flat])
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)(*args)


def _dispatch(x, idx, cap: int, e: int, k: int, axes_override=None):
    """(x (b,t,d), idx (b,t,k)) -> (x_e (b,e,cap,d), slot (b,tk), keep)."""

    def local(x, idx):
        b, t, d = x.shape
        slot, keep = _route_positions(idx, cap, e, k)
        buf = jnp.zeros((b, e * cap + 1, d), x.dtype)
        tok = jnp.repeat(x, k, axis=1).reshape(b, t * k, d)
        buf = buf.at[jnp.arange(b)[:, None], slot].set(tok, mode="drop")
        return buf[:, :-1].reshape(b, e, cap, d), slot, keep

    return _maybe_batch_local(local, (x, idx), 3, axes_override)


def _combine(y_e, slot, wk, axes_override=None):
    """(y_e (b,e,cap,d), slot (b,tk), wk (b,t,k)) -> y (b,t,d)."""

    def local(y_e, slot, wk):
        b, e, cap, d = y_e.shape
        t, k = wk.shape[1], wk.shape[2]
        y_flat = jnp.concatenate(
            [y_e.reshape(b, e * cap, d), jnp.zeros((b, 1, d), y_e.dtype)],
            axis=1)
        y_tok = jnp.take_along_axis(y_flat, slot[..., None], axis=1)
        y_tok = y_tok.reshape(b, t, k, d)
        return (y_tok * wk[..., None]).sum(axis=2)

    return _maybe_batch_local(local, (y_e, slot, wk), 1, axes_override)


class MoEFFN(Module):
    def __init__(self, d_model: int, d_ff: int, n_experts: int, top_k: int,
                 n_shared: int = 0, capacity_factor: float = 1.25,
                 router_scale: float = 1.0, sigmoid_gate: bool = False,
                 dtype=jnp.float32):
        self.d, self.ff = d_model, d_ff
        self.e, self.k, self.sh = n_experts, top_k, n_shared
        self.cap_f = capacity_factor
        self.router_scale = router_scale
        self.sigmoid_gate = sigmoid_gate
        self.dtype = dtype

    def init(self, key):
        ks = jax.random.split(key, 7)
        d, ff, e = self.d, self.ff, self.e
        std = d ** -0.5
        p = {
            "router": normal_init(ks[0], (d, e), std, self.dtype),
            "w_gate": normal_init(ks[1], (e, d, ff), std, self.dtype),
            "w_up": normal_init(ks[2], (e, d, ff), std, self.dtype),
            "w_down": normal_init(ks[3], (e, ff, d), ff ** -0.5, self.dtype),
        }
        if self.sh:
            p["sh_gate"] = normal_init(ks[4], (d, self.sh * ff), std, self.dtype)
            p["sh_up"] = normal_init(ks[5], (d, self.sh * ff), std, self.dtype)
            p["sh_down"] = normal_init(ks[6], (self.sh * ff, d),
                                       (self.sh * ff) ** -0.5, self.dtype)
        return p, {}

    def apply(self, params, state, x, **kw) -> Tuple[jnp.ndarray, dict]:
        b, t, d = x.shape
        xg = shard(x, ("batch", "seq", "act_embed"))
        logits = (xg @ params["router"]).astype(jnp.float32)   # (b,t,E)
        scores = (jax.nn.sigmoid(logits) if self.sigmoid_gate
                  else jax.nn.softmax(logits, axis=-1))
        wk, idx = jax.lax.top_k(scores, self.k)                # (b,t,k)
        wk = (wk / jnp.maximum(wk.sum(-1, keepdims=True), 1e-9)
              * self.router_scale).astype(x.dtype)

        # decode (t == 1): one GLOBAL token group — per-batch-row groups
        # would need capacity ≥ 1 slot per (row, expert), a 256× dispatch
        # blow-up for 1 token; tensors are tiny so the plain path is fine.
        from repro.nn.sharding import axis_size, current_rules
        axes_override = None
        n_seq = axis_size("seq")
        if t == 1 and b > 1:
            g, tg = 1, b * t
            xg_d = xg.reshape(g, tg, d)
            idx_d = idx.reshape(g, tg, self.k)
            wk_d = wk.reshape(g, tg, self.k)
        elif n_seq > 1 and t % n_seq == 0:
            # §Perf D3: sequence-parallel residual — dispatch in finer
            # (batch × seq-shard) groups so the shard_map stays fully local
            # (no per-layer all-gather of the seq-sharded activations)
            g, tg = b * n_seq, t // n_seq
            xg_d = xg.reshape(g, tg, d)
            idx_d = idx.reshape(g, tg, self.k)
            wk_d = wk.reshape(g, tg, self.k)
            bax, nb = _batch_axes_size()
            if bax is not None and b % nb == 0:
                seq_ax = current_rules().get("seq")
                baxes = (bax,) if isinstance(bax, str) else tuple(bax)
                saxes = (seq_ax,) if isinstance(seq_ax, str) else tuple(seq_ax)
                axes_override = (baxes + saxes, nb * n_seq)
        else:
            g, tg = b, t
            xg_d, idx_d, wk_d = xg, idx, wk
        cap = max(int(tg * self.k * self.cap_f / self.e), 4)
        x_e, slot, keep = _dispatch(xg_d, idx_d, cap, self.e, self.k,
                                    axes_override)
        # §Perf "expert_ep": experts sharded over BOTH mesh axes (1/chip) —
        # the batch axis must yield 'data' to the expert axis here, so the
        # all-to-all moves (tiny) tokens instead of gathering (huge) weights
        from repro.nn.sharding import current_rules
        ep_both = isinstance(current_rules().get("experts"), (tuple, list))
        e_axes = (None, "experts", "expert_cap", "act_embed") if ep_both \
            else ("batch", "experts", "expert_cap", "act_embed")
        x_e = shard(x_e, e_axes)

        h = jnp.einsum("becd,edf->becf", x_e, params["w_gate"])
        u = jnp.einsum("becd,edf->becf", x_e, params["w_up"])
        y_e = jnp.einsum("becf,efd->becd", jax.nn.silu(h) * u,
                         params["w_down"])
        y_e = shard(y_e, e_axes)

        y = _combine(y_e, slot, wk_d, axes_override).reshape(b, t, d)
        y = shard(y, ("batch", "seq", "act_embed"))

        if self.sh:
            y = y + _gated_ffn(xg, params["sh_gate"], params["sh_up"],
                               params["sh_down"])

        me = scores.reshape(-1, self.e).mean(0)                # (E,)
        counts = jnp.zeros((self.e,), jnp.float32).at[idx.reshape(-1)].add(1.0)
        ce = counts / (b * t)                                   # tokens/expert
        aux = {"lb_loss": self.e * jnp.sum(me * ce / self.k),
               "z_loss": jnp.mean(jax.nn.logsumexp(logits, -1) ** 2),
               "dropped": 1.0 - keep.mean()}
        return y, aux
