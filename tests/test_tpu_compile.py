"""Compile-only checks of the main-path kernels and programs for a TPU v5e.

Nothing runs: each program is lowered and compiled for a described (not
attached) ``v5e:2x2`` topology, which is what the chip's own compiler would
accept or refuse — tiling and alignment, fast-memory limits, programs that
do not fit in HBM.  The topology and everything built from it come from the
module-scoped fixtures below, so only the worker that runs this file loads
the TPU compiler, and only once a test has started.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,  # noqa: E402
                          SingleDeviceSharding)

from repro.kernels import ops, pareto_rank  # noqa: E402

POP = 32768
ROWS = 2 * POP            # environmental selection ranks parents + offspring
N_OBJ = 3
V5E_HBM_BYTES = 16 * 2 ** 30


@pytest.fixture(scope="module")
def topo():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def quiet_cache():
    """A compile for a described chip cannot be read back from the
    persistent cache; keep it out of the cache for these tests."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _total_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)


@pytest.mark.parametrize("rank_block", [256, 1024])
def test_pareto_kernel_compiles(topo, one_chip, quiet_cache, rank_block):
    """The ranking kernel at pop 32768 with the row tiles the ops wrapper
    picks for ``rank_block`` 256 and 1024."""
    bp, bq = ops._row_tile(rank_block), ops._PALLAS_COL_TILE
    F = _spec((ROWS, N_OBJ), jnp.float32, one_chip)
    cv = _spec((ROWS,), jnp.float32, one_chip)
    fn = jax.jit(lambda f, c: pareto_rank.packed_domination(
        f, c, f, c, bp=bp, bq=bq, interpret=False))
    compiled = fn.lower(F, cv).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert _total_bytes(compiled) < V5E_HBM_BYTES


def test_sharded_packed_domination_compiles(topo, quiet_cache, monkeypatch):
    """The ``rank_devices`` path: packed domination with its tile rows
    sharded over a four-chip mesh."""
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    mesh = Mesh(np.asarray(topo.devices), ("rank",))
    repl = NamedSharding(mesh, P())
    fn = jax.jit(lambda f, c: ops.packed_domination(
        f, c, block=1024, impl="pallas", mesh=mesh))
    compiled = fn.lower(_spec((ROWS, N_OBJ), jnp.float32, repl),
                        _spec((ROWS,), jnp.float32, repl)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    # each chip holds a quarter of the (ROWS/32, ROWS) packed words
    words = ROWS // 32 * ROWS * 4
    assert compiled.memory_analysis().output_size_in_bytes <= words // 4 + 4096


@pytest.mark.parametrize("stage", [0, 1])
def test_smollm_stage_decode_step_compiles(topo, one_chip, quiet_cache,
                                           stage):
    """One wave decode step of each stage of smollm-360m at its published
    widths, cut in two as the serve path cuts it, fits one chip's HBM."""
    from repro.models.registry import build_model, get_config
    from repro.serving.pipeline import PartitionedLMRunner
    cfg = get_config("smollm-360m")
    model = build_model(cfg)
    lanes, capacity = 2, 64
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))[0]
    cuts = [cfg.n_layers // 2 - 1]
    weights = jax.eval_shape(
        lambda p: PartitionedLMRunner(model, p, cuts).stage_weights(stage),
        params)
    runner = PartitionedLMRunner(model, params, cuts)
    caches = jax.eval_shape(lambda: jax.tree_util.tree_map(
        lambda x: jnp.stack([x] * lanes),
        runner.init_stage_caches(stage, 1, capacity)))
    if stage == 0:
        x = _spec((lanes, 1, 1), jnp.int32, one_chip)
    else:
        x = _spec((lanes, 1, 1, cfg.d_model), jnp.float32, one_chip)
    place = lambda t: jax.tree_util.tree_map(  # noqa: E731
        lambda s: _spec(s.shape, s.dtype, one_chip), t)
    step = jax.jit(jax.vmap(runner.stage_step_fn(stage),
                            in_axes=(None, 0, 0)))
    compiled = step.lower(place(weights), place(caches), x).compile()
    need = _total_bytes(compiled)
    assert need < V5E_HBM_BYTES, need
    # the stage holds its half of the 32 blocks' weights
    assert compiled.memory_analysis().argument_size_in_bytes > 2 ** 29


def test_window_attn_compiles(topo, one_chip, quiet_cache):
    """Sliding-window attention at smollm-360m's heads (15 query, 5 kv,
    head dim 64) over its 4096-token window."""
    from repro.kernels.window_attn import window_attn
    t = 4096
    q = _spec((1, t, 15, 64), jnp.float32, one_chip)
    kv = _spec((1, t, 5, 64), jnp.float32, one_chip)
    fn = jax.jit(lambda q, k, v: window_attn(q, k, v, window=t, bq=128,
                                             bk=128, interpret=False))
    compiled = fn.lower(q, kv, kv).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert _total_bytes(compiled) < V5E_HBM_BYTES


def test_ssd_scan_compiles(topo, one_chip, quiet_cache):
    """The SSD chunked scan at mamba2-370m's widths (32 heads of 64, state
    128, chunk 128)."""
    from repro.configs.mamba2_370m import CONFIG as cfg
    from repro.kernels.ssd_scan import ssd_scan
    t, n = 1024, cfg.ssm_state
    h = cfg.ssm_expand * cfg.d_model // cfg.ssm_headdim
    fn = jax.jit(lambda x, dt, A, B, C: ssd_scan(
        x, dt, A, B, C, chunk=cfg.ssm_chunk, interpret=False))
    compiled = fn.lower(
        _spec((1, t, h, cfg.ssm_headdim), jnp.float32, one_chip),
        _spec((1, t, h), jnp.float32, one_chip),
        _spec((h,), jnp.float32, one_chip),
        _spec((1, t, n), jnp.float32, one_chip),
        _spec((1, t, n), jnp.float32, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_quant_matmul_compiles(topo, one_chip, quiet_cache):
    """The int8 fake-quant matmul on MXU-aligned (512x1024) @ (1024x2560)
    operands."""
    from repro.kernels.quant_matmul import quant_matmul
    fn = jax.jit(lambda x, w, ws, xs: quant_matmul(x, w, ws, xs,
                                                   interpret=False))
    compiled = fn.lower(_spec((512, 1024), jnp.float32, one_chip),
                        _spec((1024, 2560), jnp.int8, one_chip),
                        _spec((2560,), jnp.float32, one_chip),
                        _spec((), jnp.float32, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()
