"""JIT-compiled twins of the NSGA-II operators (``repro.core.nsga2``).

Everything here is shape-static and traceable, so the *entire* generation
loop — non-dominated ranking, crowding, binary tournaments, crossover,
mutation, repair and the batched metric evaluation — runs as one compiled
XLA program over fixed-shape population arrays (:func:`jit_nsga2`), which
carries the search past the NumPy path's O(pop²) host sort to
accelerator-rate populations of 10k+.

Differences from the NumPy implementation, by construction:

* randomness comes from ``jax.random`` (different stream than
  ``np.random.default_rng``), so runs are seeded/reproducible but not
  bit-identical to the NumPy search — equivalence is at the Pareto-front
  level (tested);
* front peeling stops once ``pop_size`` individuals are ranked (the only
  ranks environmental selection can consume); the tail keeps rank ``n``;
* crowding is computed per rank group over the combined parent+offspring
  population and carried into the next generation's tournaments instead of
  being recomputed on the survivors.
"""

from __future__ import annotations

from typing import Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

Array = jax.Array
EvalFn = Callable[[Array], Tuple[Array, Array]]


# -- jittable domination / ranking / crowding ---------------------------------

def constrained_dominates(Fa: Array, cva: Array,
                          Fb: Array, cvb: Array) -> Array:
    """Broadcasting Deb constraint-domination (twin of the NumPy version)."""
    feas_a, feas_b = cva <= 0, cvb <= 0
    dom = jnp.all(Fa <= Fb, axis=-1) & jnp.any(Fa < Fb, axis=-1)
    return jnp.where(feas_a & ~feas_b, True,
                     jnp.where(feas_b & ~feas_a, False,
                               jnp.where(~feas_a & ~feas_b, cva < cvb, dom)))


def domination_matrix(F: Array, CV: Array) -> Array:
    """D[p, q] = p constraint-dominates q, diagonal cleared."""
    n = F.shape[0]
    D = constrained_dominates(F[:, None, :], CV[:, None],
                              F[None, :, :], CV[None, :])
    return D & ~jnp.eye(n, dtype=bool)


def _pack_bits(B: Array) -> Array:
    """Pack a boolean (n, m) matrix into (ceil(n/32), m) uint32 words along
    axis 0 (bit j of word w, column q = B[32w + j, q])."""
    n, m = B.shape
    pad = (-n) % 32
    Bp = jnp.pad(B, ((0, pad), (0, 0)))
    W = Bp.reshape(-1, 32, m).astype(jnp.uint32)
    weights = (jnp.uint32(1) << jnp.arange(32, dtype=jnp.uint32))
    return (W * weights[None, :, None]).sum(axis=1, dtype=jnp.uint32)


def nondominated_rank(F: Array, CV: Array,
                      cap: Optional[int] = None, *,
                      rank_block: Optional[int] = None,
                      rank_impl: str = "auto",
                      mesh=None) -> Array:
    """Front index per individual (0 = first front), peeled until at least
    ``cap`` individuals are ranked (default: all).  The unpeeled tail keeps
    rank ``n`` — environmental selection never reaches it.

    With ``rank_block`` unset/0 the dense path runs: the full domination
    matrix is built in one broadcast, bit-packed (32 individuals per uint32
    word), and each peel step counts surviving dominators with
    ``population_count`` over a (n/32, n) word matrix — ~n²/8 bytes of
    traffic per front instead of the 4n² a float mat-vec would read.

    ``rank_block > 0`` switches to the tiled primitive
    (``repro.kernels.ops.packed_domination``): the packed words are built
    (rank_block, n)-tile by tile so the dense (n, n[, m]) booleans never
    exist, and only *feasible* Pareto layers are peeled — Deb domination
    totally orders infeasible individuals by violation, so their ranks (the
    equal-CV groups, appended after the feasible layers) come in closed
    form instead of one O(n²/8) popcount pass per (often singleton) front.
    Ranks are bit-identical to the dense path; ``mesh`` (1-D) shards the
    tile rows across devices.

    Inside a program the phases carry the scopes ``pack`` (the domination
    words), ``peel`` (the popcount loop) and ``tail`` (the tiled path's
    infeasible ranks).
    """
    return _rank_and_passes(F, CV, cap, rank_block, rank_impl, mesh)[0]


def _rank_and_passes(F: Array, CV: Array, cap: Optional[int],
                     rank_block: Optional[int], rank_impl: str,
                     mesh) -> Tuple[Array, Array]:
    """:func:`nondominated_rank` and the number of peeling passes it ran
    (int32: iterations of the popcount loop)."""
    n = F.shape[0]
    cap = n if cap is None else min(cap, n)
    with jax.named_scope("pack"):
        if rank_block:
            from repro.kernels import ops
            Dp = ops.packed_domination(F, CV, block=rank_block,
                                       impl=rank_impl, mesh=mesh)
            # only feasible layers are peeled; the infeasible tail follows
            alive = CV <= 0
        else:
            Dp = _pack_bits(domination_matrix(F, CV))   # (W, n) uint32
            alive = jnp.ones(n, dtype=bool)
    with jax.named_scope("peel"):
        rank, passes, done = _peel(Dp, alive, cap)
    if not rank_block:
        return rank, passes
    with jax.named_scope("tail"):
        return _infeasible_tail(CV, rank, passes, done, cap), passes


def _peel(Dp: Array, alive: Array, cap: int) -> Tuple[Array, Array, Array]:
    """Peel fronts of the ``alive`` individuals off the packed domination
    words ``Dp`` until ``cap`` are ranked; returns (rank, passes, ranked
    count), unranked individuals keeping rank ``n``."""
    n = Dp.shape[1]
    state = (jnp.full(n, n, dtype=jnp.int32), alive,
             jnp.int32(0), jnp.int32(0))    # rank, alive, front idx, ranked

    def cond(s):
        _, alive, _, done = s
        return alive.any() & (done < cap)

    def body(s):
        rank, alive, r, done = s
        alive_p = _pack_bits(alive[:, None])[:, 0]  # (W,)
        n_dom = lax.population_count(Dp & alive_p[:, None]).sum(axis=0)
        front = alive & (n_dom == 0)                # no alive dominator
        front = jnp.where(front.any(), front, alive)   # numerical safety
        rank = jnp.where(front, r, rank)
        return (rank, alive & ~front, r + 1,
                done + front.sum(dtype=jnp.int32))

    rank, _, passes, done = lax.while_loop(cond, body, state)
    return rank, passes, done


def _infeasible_tail(CV: Array, rank: Array, n_feas_fronts: Array,
                     done: Array, cap: int) -> Array:
    """Ranks of the tiled path's infeasible individuals after its feasible
    layers (see :func:`nondominated_rank`)."""
    n = CV.shape[0]
    feas = CV <= 0
    # every feasible individual dominates every infeasible one and
    # infeasible pairs compare by violation alone, so the remaining fronts
    # are the equal-CV groups in ascending order.  A group is peeled iff
    # the count ranked before it is still under the cap — exactly the
    # dense loop's stopping rule.
    cvs = jnp.where(feas, jnp.inf, CV)
    order = jnp.argsort(cvs)
    scv = cvs[order]
    new_grp = jnp.concatenate([jnp.zeros(1, dtype=bool),
                               scv[1:] != scv[:-1]])
    grp_sorted = jnp.cumsum(new_grp.astype(jnp.int32))
    grp = jnp.zeros(n, jnp.int32).at[order].set(grp_sorted)
    first_idx = jax.ops.segment_min(jnp.arange(n, dtype=jnp.int32),
                                    grp_sorted, num_segments=n)
    before = done + first_idx[grp]                  # ranked before my group
    include = ~feas & (before < cap)
    return jnp.where(include, n_feas_fronts + grp, rank)


def crowding_by_rank(F: Array, rank: Array) -> Array:
    """Crowding distance within each rank group (twin of
    ``crowding_distance`` applied per front, without materializing fronts).

    Per objective: lexsort by (rank, value); interior points accumulate the
    neighbour gap normalized by their group's value span (segment min/max),
    group boundaries get ``inf`` — exactly the NumPy accounting.
    """
    n, m = F.shape
    crowd = jnp.zeros(n)
    for j in range(m):                               # m static, unrolled
        f = F[:, j]
        order = jnp.lexsort((f, rank))
        sr, sf = rank[order], f[order]
        span = (jax.ops.segment_max(f, rank, num_segments=n + 1)
                - jax.ops.segment_min(f, rank, num_segments=n + 1))[sr]
        same = sr[1:] == sr[:-1]
        false1 = jnp.zeros(1, dtype=bool)
        interior = (jnp.concatenate([false1, same])
                    & jnp.concatenate([same, false1]))
        gap = (jnp.concatenate([sf[1:], sf[-1:]])
               - jnp.concatenate([sf[:1], sf[:-1]]))
        contrib = jnp.where(
            interior,
            jnp.where(span > 0, gap / jnp.where(span > 0, span, 1.0), 0.0),
            jnp.inf)
        crowd = crowd.at[order].add(contrib)
    return crowd


# -- jittable GA operators ----------------------------------------------------

def tournament(key: Array, F: Array, CV: Array, crowd: Array,
               n: int) -> Array:
    """n independent binary tournaments → winner indices."""
    ka, kb = jax.random.split(key)
    a = jax.random.randint(ka, (n,), 0, F.shape[0])
    b = jax.random.randint(kb, (n,), 0, F.shape[0])
    a_dom = constrained_dominates(F[a], CV[a], F[b], CV[b])
    b_dom = constrained_dominates(F[b], CV[b], F[a], CV[a])
    return jnp.where(a_dom | (~b_dom & (crowd[a] >= crowd[b])), a, b)


def repair(X: Array, lo: int, hi: int) -> Array:
    """Clip/sort/de-duplicate cut vectors — twin of ``_repair_batch`` (the
    scans run over the short static n_var axis, unrolled)."""
    X = jnp.clip(jnp.sort(X, axis=1), lo, hi)
    n_var = X.shape[1]
    for i in range(1, n_var):
        X = X.at[:, i].set(jnp.where(X[:, i] <= X[:, i - 1],
                                     jnp.minimum(hi, X[:, i - 1] + 1),
                                     X[:, i]))
    for i in range(n_var - 2, -1, -1):     # if saturated at hi, push left
        X = X.at[:, i].set(jnp.where(X[:, i] >= X[:, i + 1],
                                     jnp.maximum(lo, X[:, i + 1] - 1),
                                     X[:, i]))
    return X


def make_offspring(key: Array, X: Array, F: Array, CV: Array, crowd: Array,
                   lo: int, hi: int) -> Array:
    """Tournaments → uniform crossover → blend step → reset/local-step
    mutation → repair, mirroring the NumPy brood construction."""
    pop, n_var = X.shape
    half = (pop + 1) // 2
    k1, k2, k3, k4, k5, k6, k7, k8 = jax.random.split(key, 8)
    P1 = X[tournament(k1, F, CV, crowd, half)]
    P2 = X[tournament(k2, F, CV, crowd, half)]
    mask = jax.random.uniform(k3, (half, n_var)) < 0.5
    Xc = jnp.concatenate([jnp.where(mask, P1, P2),
                          jnp.where(mask, P2, P1)])[:pop]
    if n_var > 0:
        par1 = jnp.concatenate([P1, P1])[:pop]
        par2 = jnp.concatenate([P2, P2])[:pop]
        blend = jax.random.uniform(k4, (pop,)) < 0.3
        j = jax.random.randint(k5, (pop,), 0, n_var)
        rows = jnp.arange(pop)
        mid = (par1[rows, j] + par2[rows, j]) // 2
        Xc = Xc.at[rows, j].set(jnp.where(blend, mid, Xc[rows, j]))
    nv = max(n_var, 1)
    r = jax.random.uniform(k6, (pop, n_var))
    reset = r < 0.5 / nv
    step = ~reset & (r < 2.0 / nv)
    Xc = jnp.where(reset, jax.random.randint(k7, Xc.shape, lo, hi + 1), Xc)
    Xc = jnp.where(step, Xc + jax.random.randint(k8, Xc.shape, -3, 4), Xc)
    return repair(Xc, lo, hi)


# -- the compiled generation loop ---------------------------------------------

# what the compiled loop counts: generations executed, iterations of the
# ``rank/peel`` popcount loop summed over them, and the final population's
# rows on its first front
COUNTS = ("generations", "peel_passes", "front_rows")

# auto rank_block policy: combined (2·pop) populations at/below the
# threshold keep the dense packed path (fastest there, memory irrelevant);
# beyond it the tiled path runs with the default tile rows
_AUTO_DENSE_MAX = 4096
_AUTO_RANK_BLOCK = 2048


def _resolve_rank_block(rank_block: Optional[int], pop_size: int) -> int:
    """None → auto (dense ≤ ``_AUTO_DENSE_MAX`` combined, else 2048-row
    tiles); 0 forces dense; a positive int is the tile row count."""
    if rank_block is None:
        return 0 if 2 * pop_size <= _AUTO_DENSE_MAX else _AUTO_RANK_BLOCK
    return rank_block


def _make_run(eval_fn: EvalFn, lo: int, hi: int, pop_size: int,
              rank_block: int, rank_impl: str, mesh):
    """The whole-search program (unjitted) shared by the single-seed and
    vmapped multi-restart runners.

    ``run(key, X0, n_gen, *eval_args)`` forwards any trailing arguments to
    every ``eval_fn(X, *eval_args)`` call — that is how runtime-valued
    evaluation tables (gene table, :class:`~repro.core.partition_jax
    .EvalTables`) flow through the compiled program without being baked
    into the trace.

    Each phase runs under a ``jax.named_scope`` (``init``, then per
    generation ``offspring``, ``evaluate``, ``rank/pack``, ``rank/peel``,
    ``rank/tail``, ``crowding``, ``select``), which a device trace shows as
    the path of every operation.  Beside (X, F, CV) the program returns
    the final population's first-front mask and its :data:`counts
    <COUNTS>`.

    The mask is the survivors' rank 0, carried from the ranking that
    selected them: survivors are kept whole front by whole front, so a
    survivor of rank k > 0 is dominated by a survivor of rank k - 1, and a
    rank-0 one by none."""

    def gen_step(carry, eval_args):
        key, X, F, CV, crowd, _, gens, peels = carry
        key, k_off = jax.random.split(key)
        with jax.named_scope("offspring"):
            Xc = make_offspring(k_off, X, F, CV, crowd, lo, hi)
        with jax.named_scope("evaluate"):
            Fc, CVc = eval_fn(Xc, *eval_args)
        Xall = jnp.concatenate([X, Xc])
        Fall = jnp.concatenate([F, Fc])
        CVall = jnp.concatenate([CV, CVc])
        # elitist environmental selection: whole fronts in rank order, the
        # boundary front tie-broken by crowding == lexsort by (rank, -crowd)
        with jax.named_scope("rank"):
            rank, passes = _rank_and_passes(Fall, CVall, pop_size,
                                            rank_block, rank_impl, mesh)
        with jax.named_scope("crowding"):
            crowd_all = crowding_by_rank(Fall, rank)
        with jax.named_scope("select"):
            keep = jnp.lexsort((-crowd_all, rank))[:pop_size]
            X, F, CV = Xall[keep], Fall[keep], CVall[keep]
        return (key, X, F, CV, crowd_all[keep], rank[keep], gens + 1,
                peels + passes)

    def run(key: Array, X0: Array, n_gen, *eval_args):
        with jax.named_scope("init"):
            X0 = repair(X0, lo, hi)
            F0, CV0 = eval_fn(X0, *eval_args)
            rank0 = nondominated_rank(F0, CV0, rank_block=rank_block,
                                      rank_impl=rank_impl, mesh=mesh)
            crowd0 = crowding_by_rank(F0, rank0)
        carry = (key, X0, F0, CV0, crowd0, rank0, jnp.int32(0),
                 jnp.int32(0))
        _, X, F, CV, _, rank, gens, peels = lax.fori_loop(
            0, n_gen, lambda _, c: gen_step(c, eval_args), carry)
        front = rank == 0
        counts = dict(zip(COUNTS, (gens, peels,
                                   front.sum(dtype=jnp.int32))))
        return X, F, CV, front, counts

    return run


def make_jit_runner(eval_fn: EvalFn, n_var: int, lower: int, upper: int,
                    pop_size: int, rank_block: Optional[int] = None,
                    rank_impl: str = "auto", mesh=None):
    """Compile the whole NSGA-II run into one XLA program.

    Returns ``run(key, X0, n_gen, *eval_args) -> (X, F, CV, front,
    counts)``, where ``front`` is the (pop_size,) bool mask of the final
    population's first front and ``counts`` maps each name of
    :data:`COUNTS` to an int32 scalar.
    ``n_gen`` is a traced loop bound, so one compilation serves any
    generation budget at a given (pop_size, n_var) shape.  ``X0`` is donated — the population
    buffers live in place across the generation loop.  Trailing
    ``eval_args`` are forwarded to ``eval_fn(X, *eval_args)`` as ordinary
    (non-donated) runtime arguments: pass value-bearing tables (gene table,
    ``EvalTables``) here and the same compilation serves every same-shape
    perturbation of them without retracing.

    ``rank_block``/``rank_impl``/``mesh`` select the ranking primitive (see
    :func:`nondominated_rank`): the auto policy keeps the dense packed
    matrix for combined populations ≤ 4096 and tiles beyond, which is what
    lets pop 32768+ run in O(pop · rank_block) working memory.
    """
    run = _make_run(eval_fn, lower, upper, pop_size,
                    _resolve_rank_block(rank_block, pop_size), rank_impl,
                    mesh)
    return jax.jit(run, donate_argnums=(1,))


def make_jit_restart_runner(eval_fn: EvalFn, n_var: int, lower: int,
                            upper: int, pop_size: int,
                            rank_block: Optional[int] = None,
                            rank_impl: str = "auto", mesh=None,
                            n_eval_args: int = 0):
    """The ``vmap``-over-seeds twin of :func:`make_jit_runner`.

    Returns ``run(keys, X0s, n_gen, *eval_args)`` over arrays with a
    leading restart axis (the counts too: one per restart) — one compilation covers every generation budget
    at a given (n_restarts, pop_size, n_var) shape, and all restarts
    advance in lockstep inside a single XLA program.  ``n_eval_args``
    declares how many trailing runtime arguments ``eval_fn`` takes; they
    are broadcast (not mapped) across restarts.
    """
    run = _make_run(eval_fn, lower, upper, pop_size,
                    _resolve_rank_block(rank_block, pop_size), rank_impl,
                    mesh)
    axes = (0, 0, None) + (None,) * n_eval_args
    return jax.jit(jax.vmap(run, in_axes=axes), donate_argnums=(1,))


def init_population(rng: np.random.Generator, pop_size: int, n_var: int,
                    lower: int, upper: int,
                    candidates: Optional[Sequence[Sequence[int]]]
                    ) -> np.ndarray:
    """Host-side population init — matches the NumPy
    :func:`repro.core.nsga2.nsga2` draw-for-draw."""
    X0 = rng.integers(lower, upper + 1, size=(pop_size, n_var))
    if candidates is not None and len(candidates):
        cand = np.asarray(list(candidates), dtype=int)
        k = min(len(cand), pop_size // 2)
        X0[:k] = cand[rng.permutation(len(cand))[:k]]
    return X0


def warm_population(rng: np.random.Generator, pop_size: int, n_var: int,
                    lower: int, upper: int,
                    warm: Optional[np.ndarray]) -> np.ndarray:
    """Host-side warm-started population: previous-front rows verbatim,
    then jitter-mutated copies, then a random tail.

    Layout (all counts deterministic given ``pop_size`` and ``len(warm)``):

    * up to ``pop_size // 2`` rows are ``warm`` rows copied verbatim — the
      elites the re-search refines;
    * up to ``pop_size // 4`` rows are elites plus a small integer jitter
      (uniform in [-2, 2] per gene, clipped to bounds) — local exploration
      around the previous optimum, where a drifted system's new optimum
      usually lives;
    * the remainder is uniform random in [lower, upper] — global escape
      hatch so a warm start can never trap the search.

    An empty (or ``None``) ``warm`` degenerates to the cold uniform init.
    """
    if warm is None:
        warm = np.empty((0, n_var), dtype=int)
    warm = np.asarray(warm, dtype=int).reshape(-1, n_var)
    if len(warm) == 0:
        return rng.integers(lower, upper + 1, size=(pop_size, n_var))
    n_elite = min(len(warm), max(pop_size // 2, 1))
    elite = np.clip(warm[:n_elite], lower, upper)
    n_jit = min(pop_size - n_elite, pop_size // 4)
    base = elite[rng.integers(0, n_elite, size=n_jit)]
    jittered = np.clip(base + rng.integers(-2, 3, size=base.shape),
                       lower, upper)
    n_rand = pop_size - n_elite - n_jit
    rand = rng.integers(lower, upper + 1, size=(n_rand, n_var))
    return np.concatenate([elite, jittered, rand])[:pop_size]


def jit_nsga2(eval_fn: EvalFn, n_var: int, lower: int, upper: int,
              pop_size: int, n_gen: int, seed: int = 0,
              candidates: Optional[Sequence[Sequence[int]]] = None,
              runner=None, X0: Optional[np.ndarray] = None,
              eval_args: Tuple = (), counts: Optional[dict] = None,
              front: Optional[np.ndarray] = None
              ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Run the compiled NSGA-II loop; returns host (X, F, CV) arrays.

    Population init (including ``candidates`` seeding) matches the NumPy
    :func:`repro.core.nsga2.nsga2` exactly and stays host-side; everything
    after the first device transfer is one XLA program.  Pass a prebuilt
    ``runner`` (from :func:`make_jit_runner`) to reuse a compilation, an
    explicit ``X0`` (pop_size, n_var) to override the uniform init (warm
    starts — see :func:`warm_population`), and ``eval_args`` to forward
    runtime table values to ``eval_fn``.  A ``counts`` dict is filled with
    the program's :data:`COUNTS` as ints, and a ``front`` bool array of
    shape (pop_size,) with the final population's first-front mask.
    """
    if X0 is None:
        X0 = init_population(np.random.default_rng(seed), pop_size, n_var,
                             lower, upper, candidates)
    if runner is None:
        runner = make_jit_runner(eval_fn, n_var, lower, upper, pop_size)
    X, F, CV, first, c = runner(jax.random.PRNGKey(seed),
                                jnp.asarray(X0, dtype=jnp.int32), n_gen,
                                *eval_args)
    if counts is not None:
        counts.update({k: int(v) for k, v in c.items()})
    if front is not None:
        front[:] = np.asarray(first)
    return (np.asarray(X, dtype=np.int64), np.asarray(F, dtype=np.float64),
            np.asarray(CV, dtype=np.float64))


def jit_nsga2_restarts(eval_fn: EvalFn, n_var: int, lower: int, upper: int,
                       pop_size: int, n_gen: int, n_restarts: int,
                       seed: int = 0,
                       candidates: Optional[Sequence[Sequence[int]]] = None,
                       runner=None, X0s: Optional[np.ndarray] = None,
                       eval_args: Tuple = (), counts: Optional[dict] = None,
                       front: Optional[np.ndarray] = None
                       ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Multi-restart search: ``n_restarts`` independently seeded runs as one
    vmapped XLA program, compiled once.

    Restart ``i`` reproduces ``jit_nsga2(..., seed=seed + i)`` bit-for-bit
    (same host init stream, same PRNG key), so the merged output's
    non-dominated front equals the union of the per-seed sequential fronts
    after one final non-dominated filter.  Returns host (X, F, CV) with the
    restart axis flattened to ``n_restarts * pop_size`` rows.  ``X0s``
    overrides the per-restart init (shape (n_restarts, pop_size, n_var));
    ``eval_args`` are broadcast to every restart (the runner must have been
    built with a matching ``n_eval_args``).  A ``counts`` dict is filled
    with the program's :data:`COUNTS`, each a list of one int per restart,
    and a ``front`` bool array of shape (n_restarts * pop_size,) with each
    restart's first-front mask, flattened like X.
    """
    if X0s is None:
        X0s = np.stack([
            init_population(np.random.default_rng(seed + i), pop_size,
                            n_var, lower, upper, candidates)
            for i in range(n_restarts)])
    keys = jnp.stack([jax.random.PRNGKey(seed + i)
                      for i in range(n_restarts)])
    if runner is None:
        runner = make_jit_restart_runner(eval_fn, n_var, lower, upper,
                                         pop_size,
                                         n_eval_args=len(eval_args))
    X, F, CV, first, c = runner(keys, jnp.asarray(X0s, dtype=jnp.int32),
                                n_gen, *eval_args)
    if counts is not None:
        counts.update({k: np.asarray(v).tolist() for k, v in c.items()})
    flat = n_restarts * pop_size
    if front is not None:
        front[:] = np.asarray(first).reshape(flat)
    return (np.asarray(X, dtype=np.int64).reshape(flat, n_var),
            np.asarray(F, dtype=np.float64).reshape(flat, -1),
            np.asarray(CV, dtype=np.float64).reshape(flat))

