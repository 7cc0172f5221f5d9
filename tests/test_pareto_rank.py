"""Tiled Pareto-ranking primitives (``repro.kernels.pareto_rank`` /
``kernels.ops``) vs the dense ``nsga2_jax.domination_matrix`` oracle, the
blocked ``nondominated_rank`` path vs the dense peel (bit-exact, incl. caps,
ragged sizes, all-infeasible rows, duplicated objective vectors), the
vmapped multi-restart runner vs per-seed sequential runs, and the
``shard_map``-sharded tile grid on a forced multi-device host."""

import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro.core import nsga2_jax  # noqa: E402
from repro.core.nsga2 import pareto_indices  # noqa: E402
from repro.kernels import ops  # noqa: E402

IMPLS = ("ref", "pallas")
# deliberately ragged vs every tile: the ref twins tile by the 32/64 blocks
# passed below, the Pallas kernels by 256 rows x 256 columns whatever the
# block (kernels.ops), so 600 and 777 give them 3x3 and 4x4 grids: the
# packed-output index map and the counts' accumulation across row steps
SIZES = (33, 97, 130, 600, 777)


def population(n, m=3, infeas=0.3, dup=False, seed=0):
    rng = np.random.default_rng(seed)
    F = rng.random((n, m)).astype(np.float32)
    if dup:                      # duplicated objective vectors share fronts
        F[n // 2:] = F[rng.integers(0, n // 2, n - n // 2)]
    CV = np.where(rng.random(n) < infeas, (rng.random(n) * 3).round(1),
                  0.0).astype(np.float32)
    return jnp.asarray(F), jnp.asarray(CV)


def dense_packed(F, CV):
    return np.asarray(nsga2_jax._pack_bits(
        nsga2_jax.domination_matrix(F, CV)))


# -- packed words / counts vs the dense oracle --------------------------------

@pytest.mark.parametrize("impl", IMPLS)
@pytest.mark.parametrize("n", SIZES)
def test_packed_domination_matches_dense(impl, n):
    F, CV = population(n, dup=True, seed=n)
    want = dense_packed(F, CV)
    got = np.asarray(ops.packed_domination(F, CV, block=32, impl=impl))
    assert got.shape == want.shape
    assert (got == want).all()


@pytest.mark.parametrize("impl", IMPLS)
def test_packed_domination_all_infeasible(impl):
    rng = np.random.default_rng(9)
    CV = jnp.asarray((rng.random(97) * 2 + 0.1).round(1), jnp.float32)
    F = jnp.asarray(rng.random((97, 2)), jnp.float32)
    want = dense_packed(F, CV)
    got = np.asarray(ops.packed_domination(F, CV, block=64, impl=impl))
    assert (got == want).all()


@pytest.mark.parametrize("impl", IMPLS)
@pytest.mark.parametrize("n", SIZES)
def test_domination_counts_match_dense(impl, n):
    F, CV = population(n, seed=n + 1)
    D = np.asarray(nsga2_jax.domination_matrix(F, CV))
    got = np.asarray(ops.domination_counts(F, CV, block=32, impl=impl))
    assert (got == D.sum(axis=0)).all()
    alive = jnp.asarray(np.random.default_rng(n).random(n) < 0.5)
    got_alive = np.asarray(
        ops.domination_counts(F, CV, alive, block=32, impl=impl))
    assert (got_alive == D[np.asarray(alive)].sum(axis=0)).all()


# -- blocked rank vs the dense peel -------------------------------------------

@pytest.mark.parametrize("impl", IMPLS)
@pytest.mark.parametrize("n", SIZES)
@pytest.mark.parametrize("infeas", (0.0, 0.5, 1.0))
def test_blocked_rank_bit_exact(impl, n, infeas):
    F, CV = population(n, infeas=infeas, dup=True, seed=n)
    for cap in (None, n // 3, n):
        want = np.asarray(nsga2_jax.nondominated_rank(F, CV, cap=cap))
        got = np.asarray(nsga2_jax.nondominated_rank(
            F, CV, cap=cap, rank_block=64, rank_impl=impl))
        assert (got == want).all(), (impl, n, infeas, cap)


def test_blocked_rank_duplicate_cv_groups():
    """Equal-CV infeasible individuals must land in one shared front (the
    closed-form group ranking), exactly as the dense peel assigns them."""
    F = jnp.asarray(np.random.default_rng(0).random((40, 2)), jnp.float32)
    CV = jnp.asarray(np.tile([0.0, 0.5, 0.5, 1.5], 10), jnp.float32)
    want = np.asarray(nsga2_jax.nondominated_rank(F, CV))
    got = np.asarray(nsga2_jax.nondominated_rank(F, CV, rank_block=32))
    assert (got == want).all()


def test_blocked_runner_equals_dense_runner():
    """The whole compiled generation loop is bit-identical whichever
    ranking primitive it consumes."""
    def eval_fn(X):
        f1 = jnp.sum(X, axis=1).astype(jnp.float32)
        f2 = jnp.sum((X - 12) ** 2, axis=1).astype(jnp.float32)
        cv = jnp.maximum(0.0, 9.0 - X[:, 0]).astype(jnp.float32)
        return jnp.stack([f1, f2], axis=1), cv

    args = dict(n_var=3, lower=0, upper=30, pop_size=48, n_gen=8, seed=3)
    dense = nsga2_jax.jit_nsga2(
        eval_fn, runner=nsga2_jax.make_jit_runner(
            eval_fn, 3, 0, 30, 48, rank_block=0), **args)
    blocked = nsga2_jax.jit_nsga2(
        eval_fn, runner=nsga2_jax.make_jit_runner(
            eval_fn, 3, 0, 30, 48, rank_block=32), **args)
    for a, b in zip(dense, blocked):
        assert (a == b).all()


def test_pareto_indices_blocked_matches_dense():
    rng = np.random.default_rng(4)
    X = rng.integers(0, 6, size=(200, 3))
    F = rng.random((200, 2))
    F[50:100] = F[:50]                       # duplicate decision ties
    CV = np.where(rng.random(200) < 0.4, rng.random(200), 0.0)
    want = pareto_indices(X, F, CV)
    got = nsga2_jax.pareto_indices_blocked(X, F, CV, block=64)
    assert (got == want).all()


# -- the runner's first-front mask --------------------------------------------

def _mask_eval(X, t):
    """Integer-valued objectives (ties on F for distinct X) and a violation
    of ``v - t`` over v in 0..9: t 10 leaves every row feasible, t 5.5 about
    40% infeasible, t -1 none feasible (with ties in the violation)."""
    Xf = X.astype(jnp.float32)
    f1 = Xf[:, 0] + Xf[:, 1]
    f2 = (Xf[:, 1] - 9.0) ** 2 + Xf[:, 0]
    v = ((X[:, 0] * 7 + X[:, 1] * 3) % 10).astype(jnp.float32)
    return jnp.stack([f1, f2], axis=1), jnp.maximum(v - t, 0.0)


@pytest.fixture(scope="module")
def mask_runners():
    """One compiled runner per ranking path, shared by every case."""
    return {rb: nsga2_jax.make_jit_runner(
        _mask_eval, n_var=2, lower=0, upper=15, pop_size=48, rank_block=rb,
        rank_impl="ref") for rb in (0, 32)}


@pytest.mark.parametrize("dup", [False, True], ids=["distinct", "dup_rows"])
@pytest.mark.parametrize("feasible", [10.0, 5.5, -1.0],
                         ids=["all_feasible", "some_feasible", "none_feasible"])
@pytest.mark.parametrize("n_gen", [0, 3])
@pytest.mark.parametrize("rank_block", [0, 32], ids=["dense", "tiled"])
def test_runner_front_mask_is_first_front(mask_runners, rank_block, n_gen,
                                          feasible, dup):
    """The mask the runner returns is the first constrained front of the
    population it returns, so the selection built on it equals
    ``pareto_indices`` on the returned arrays; ``front_rows`` counts it."""
    from repro.core.nsga2 import fast_non_dominated_sort, front_selection
    pop = 48
    X0 = np.random.default_rng(2).integers(0, 16, size=(pop, 2))
    if dup:
        X0[pop // 2:] = X0[:pop // 2]
    counts, mask = {}, np.zeros(pop, dtype=bool)
    X, F, CV = nsga2_jax.jit_nsga2(
        None, n_var=2, lower=0, upper=15, pop_size=pop, n_gen=n_gen, seed=4,
        runner=mask_runners[rank_block], X0=X0,
        eval_args=(jnp.float32(feasible),), counts=counts, front=mask)
    feas = CV <= 0
    assert {10.0: feas.all(), -1.0: not feas.any()}.get(feasible, True)
    if dup:
        assert len(np.unique(X, axis=0)) < pop
    first = np.flatnonzero(mask)
    assert (first == fast_non_dominated_sort(F, CV)[0]).all()
    assert (front_selection(X, CV, first) == pareto_indices(X, F, CV)).all()
    assert counts["front_rows"] == mask.sum() > 0


# -- env-forced dispatch (the CI kernel-interpret leg) ------------------------

def test_rank_impl_env_override(monkeypatch):
    monkeypatch.setenv("REPRO_RANK_IMPL", "pallas")
    assert ops.resolve_rank_impl("auto") == "pallas"
    # explicit impls are never overridden
    assert ops.resolve_rank_impl("ref") == "ref"
    monkeypatch.delenv("REPRO_RANK_IMPL")
    assert ops.resolve_rank_impl("ref") == "ref"
    with pytest.raises(ValueError, match="rank impl"):
        ops.resolve_rank_impl("mosaic")


def test_rank_impl_invalid_env_raises(monkeypatch):
    """A typo'd REPRO_RANK_IMPL must fail loudly at dispatch, naming the
    variable and the valid choices — not silently fall through to some
    branch (the CI kernel-interpret leg depends on the env actually
    taking effect)."""
    monkeypatch.setenv("REPRO_RANK_IMPL", "palas")
    with pytest.raises(ValueError) as ei:
        ops.resolve_rank_impl("auto")
    msg = str(ei.value)
    assert "REPRO_RANK_IMPL" in msg and "'palas'" in msg
    for choice in ("auto", "ref", "pallas"):
        assert choice in msg
    # explicit non-auto impls bypass the env entirely, even a broken one
    assert ops.resolve_rank_impl("ref") == "ref"


def test_resolve_impl_rejects_unknown():
    """resolve_impl used to return unknown impl strings unchanged, sending
    e.g. quant_matmul(impl='bogus') down the Pallas branch; it must raise
    and list the valid choices instead."""
    with pytest.raises(ValueError, match="valid choices"):
        ops.resolve_impl("bogus")
    assert ops.resolve_impl("ref") == "ref"
    assert ops.resolve_impl("pallas") == "pallas"
    assert ops.resolve_impl("auto") in ("ref", "pallas")


# -- multi-restart runner -----------------------------------------------------

def _toy_eval(X):
    f1 = jnp.sum(X, axis=1).astype(jnp.float32)
    f2 = jnp.sum((X - 20) ** 2, axis=1).astype(jnp.float32)
    cv = jnp.maximum(0.0, 15.0 - X[:, 0]).astype(jnp.float32)
    return jnp.stack([f1, f2], axis=1), cv


def test_restarts_bit_identical_to_sequential_seeds():
    R, pop, n_gen, seed = 3, 48, 10, 7
    Xr, Fr, CVr = nsga2_jax.jit_nsga2_restarts(
        _toy_eval, 3, 0, 40, pop, n_gen, R, seed=seed)
    assert Xr.shape == (R * pop, 3)
    for i in range(R):
        Xi, Fi, CVi = nsga2_jax.jit_nsga2(
            _toy_eval, 3, 0, 40, pop, n_gen, seed=seed + i)
        sl = slice(i * pop, (i + 1) * pop)
        assert (Xr[sl] == Xi).all()
        assert (Fr[sl] == Fi).all()
        assert (CVr[sl] == CVi).all()


def test_restart_front_equals_union_of_seed_fronts():
    """Non-dominated filtering of the merged restart output == filtering
    the union of the per-seed sequential fronts."""
    R, pop, n_gen, seed = 3, 48, 10, 7
    Xr, Fr, CVr = nsga2_jax.jit_nsga2_restarts(
        _toy_eval, 3, 0, 40, pop, n_gen, R, seed=seed)
    merged = Xr[pareto_indices(Xr, Fr, CVr)]

    union_X, union_F, union_CV = [], [], []
    for i in range(R):
        Xi, Fi, CVi = nsga2_jax.jit_nsga2(
            _toy_eval, 3, 0, 40, pop, n_gen, seed=seed + i)
        idx = pareto_indices(Xi, Fi, CVi)
        union_X.append(Xi[idx])
        union_F.append(Fi[idx])
        union_CV.append(CVi[idx])
    uX = np.concatenate(union_X)
    uF = np.concatenate(union_F)
    uCV = np.concatenate(union_CV)
    want = uX[pareto_indices(uX, uF, uCV)]
    assert ({tuple(r) for r in merged} == {tuple(r) for r in want})


def test_restart_candidate_seeding_matches_single():
    cands = [[1, 2, 3], [4, 5, 6], [0, 9, 9]]
    Xr, _, _ = nsga2_jax.jit_nsga2_restarts(
        _toy_eval, 3, 0, 40, 32, 4, 2, seed=1, candidates=cands)
    X0, _, _ = nsga2_jax.jit_nsga2(
        _toy_eval, 3, 0, 40, 32, 4, seed=1, candidates=cands)
    assert (Xr[:32] == X0).all()


# -- sharded tile grid (forced multi-device host) -----------------------------

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def test_sharded_rank_matches_dense_multidev():
    """packed_domination sharded over 4 forced host devices — and the full
    blocked rank consuming it under jit — agree with the dense path."""
    code = """
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import Mesh
        from repro.core import nsga2_jax as J
        from repro.kernels import ops

        assert len(jax.devices()) == 4
        mesh = Mesh(np.asarray(jax.devices()), ("rank",))
        rng = np.random.default_rng(3)
        for n in (97, 130):
            F = jnp.asarray(rng.random((n, 3)), jnp.float32)
            CV = jnp.asarray(np.where(rng.random(n) < 0.3,
                                      rng.random(n), 0.0), jnp.float32)
            dense = np.asarray(J._pack_bits(J.domination_matrix(F, CV)))
            got = np.asarray(ops.packed_domination(F, CV, block=32,
                                                   impl="ref", mesh=mesh))
            assert (got == dense).all(), n
            fn = jax.jit(lambda f, c: J.nondominated_rank(
                f, c, rank_block=32, rank_impl="ref", mesh=mesh))
            assert (np.asarray(fn(F, CV))
                    == np.asarray(J.nondominated_rank(F, CV))).all(), n
        print("MULTIDEV_OK")
    """
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = SRC
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, timeout=520,
                         env=env)
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    assert "MULTIDEV_OK" in out.stdout
