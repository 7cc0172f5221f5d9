"""Logical-axis sharding hints (MaxText-style).

Models annotate tensors with *logical* axis names; the launcher installs a
mesh + rules mapping logical names to mesh axes.  Without an active mesh the
hints are no-ops, so the same model code runs on one CPU device and on the
512-chip production mesh.

Canonical logical axes:
  batch        — global batch            -> ('pod', 'data') / 'data'
  seq          — sequence                -> None (or 'data' for long-context)
  act_embed    — activation d_model      -> None
  heads        — attention heads         -> 'model'
  kv_heads     — kv heads                -> 'model'
  embed        — weight d_model (FSDP)   -> 'data'
  mlp          — FFN width               -> 'model'
  experts      — MoE experts             -> 'model'
  expert_cap   — dispatch slots          -> 'model'
  vocab        — vocabulary              -> 'model'
  layers       — stacked scan layers     -> None
  kv_seq       — KV-cache sequence       -> None
  state        — SSM state dim           -> None
  ssm_heads    — SSM heads               -> 'model'
"""

from __future__ import annotations

import threading
from contextlib import contextmanager
from typing import Dict, Optional, Sequence, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

Axis = Union[str, Tuple[str, ...], None]

_ctx = threading.local()

DEFAULT_RULES: Dict[str, Axis] = {
    "batch": "data",
    "seq": None,
    "act_embed": None,
    "heads": "model",
    "kv_heads": "model",
    "embed": "data",
    "mlp": "model",
    "experts": "model",
    "expert_cap": None,
    "vocab": "model",
    "layers": None,
    "kv_seq": None,
    "state": None,
    "ssm_heads": "model",
    "codebooks": None,
    # §Perf optimizations (None = baseline behaviour)
    "attn_kv": None,        # attention-local kv-head sharding (+ kv dup)
    "mla_latent": None,     # MLA: shard the compressed latent dim
}


def axis_size(logical_name: str) -> int:
    """Mesh size of the axis a logical name maps to (1 when unmapped)."""
    mesh = current_mesh()
    if mesh is None:
        return 1
    ax = current_rules().get(logical_name)
    if ax is None:
        return 1
    axes = (ax,) if isinstance(ax, str) else tuple(ax)
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n

MULTIPOD_RULES = dict(DEFAULT_RULES, batch=("pod", "data"))


def set_mesh(mesh: Optional[Mesh], rules: Optional[Dict[str, Axis]] = None):
    _ctx.mesh = mesh
    _ctx.rules = dict(rules) if rules is not None else dict(DEFAULT_RULES)


def current_mesh() -> Optional[Mesh]:
    return getattr(_ctx, "mesh", None)


def current_rules() -> Dict[str, Axis]:
    return getattr(_ctx, "rules", dict(DEFAULT_RULES))


@contextmanager
def mesh_context(mesh: Mesh, rules: Optional[Dict[str, Axis]] = None):
    prev_mesh, prev_rules = current_mesh(), current_rules()
    set_mesh(mesh, rules)
    try:
        with mesh:
            yield mesh
    finally:
        set_mesh(prev_mesh, prev_rules)


def logical_to_spec(logical_axes: Sequence[Optional[str]],
                    rules: Optional[Dict[str, Axis]] = None) -> P:
    rules = rules if rules is not None else current_rules()
    used = set()
    out = []
    for name in logical_axes:
        ax = rules.get(name) if name else None
        # an axis may appear only once in a spec
        if ax is None:
            out.append(None)
            continue
        axes = (ax,) if isinstance(ax, str) else tuple(ax)
        axes = tuple(a for a in axes if a not in used)
        used.update(axes)
        out.append(axes if len(axes) > 1 else (axes[0] if axes else None))
    return P(*out)


def shard(x: jax.Array, logical_axes: Sequence[Optional[str]]) -> jax.Array:
    """Apply a sharding constraint if a mesh is active; identity otherwise."""
    mesh = current_mesh()
    if mesh is None:
        return x
    assert len(logical_axes) == x.ndim, (logical_axes, x.shape)
    spec = logical_to_spec(logical_axes)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, spec))


def named_sharding(logical_axes: Sequence[Optional[str]]) -> Optional[NamedSharding]:
    mesh = current_mesh()
    if mesh is None:
        return None
    return NamedSharding(mesh, logical_to_spec(logical_axes))
