"""The ``jax.jit``-compiled search path: jittable ``evaluate_batch``
fast-path vs the NumPy evaluator, the NSGA-II operator twins
(rank/crowding/repair) vs ``repro.core.nsga2``, seeded Pareto-front
equivalence of ``JitNSGA2Search`` vs ``NSGA2Search`` on the
EfficientNet-style test schedule, spec plumbing, and the strategy-registry
collision semantics."""

import dataclasses
import warnings

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro.core import nsga2_jax  # noqa: E402
from repro.core.accuracy import MeasuredAccuracy, ProxyAccuracy  # noqa: E402
from repro.core.graph import linearize  # noqa: E402
from repro.core.nsga2 import (crowding_distance,  # noqa: E402
                              fast_non_dominated_sort)
from repro.core.partition import Constraints, PartitionEvaluator  # noqa: E402
from repro.core.partition_jax import make_batch_eval_fn  # noqa: E402
from repro.explore import (ExplorationSpec, JitNSGA2Search,  # noqa: E402
                           ModelRef, NSGA2Search, PlatformSpec,
                           SearchSettings, SystemSpec, register_strategy,
                           run_spec)
from repro.explore.strategies import STRATEGIES  # noqa: E402
from repro.models.cnn.zoo import build_cnn  # noqa: E402

FOUR_PLATFORM = SystemSpec(
    platforms=(PlatformSpec("A0", "eyr", bits=16),
               PlatformSpec("A1", "eyr", bits=16),
               PlatformSpec("B0", "smb", bits=8),
               PlatformSpec("B1", "smb", bits=8)),
    links=("gige", "gige", "gige"))

ALL_OBJECTIVES = ("latency", "energy", "throughput", "bandwidth",
                  "memory", "accuracy")


@pytest.fixture(scope="module")
def evaluator():
    graph = build_cnn("efficientnet_b0", in_hw=64).to_graph()
    system = FOUR_PLATFORM.build()
    schedule = linearize(graph, "min_memory")
    return PartitionEvaluator(graph, schedule, system,
                              accuracy_fn=ProxyAccuracy(schedule, system))


def random_cuts(evaluator, n, seed=0):
    rng = np.random.default_rng(seed)
    L = len(evaluator.schedule)
    return np.sort(rng.integers(-1, L, size=(n, evaluator.system.n_cuts)),
                   axis=1)


# -- jittable evaluator fast-path ---------------------------------------------

def test_jit_eval_matches_numpy_evaluate_batch(evaluator):
    """Every objective column and the violation vector agree with the NumPy
    evaluator to float32 tolerance, constraints active."""
    C = random_cuts(evaluator, 256)
    mem_cap = int(np.median(
        evaluator.evaluate_batch(C).memory_bytes.max(axis=1)))
    cons = Constraints(max_link_bytes=200_000, min_accuracy=0.9,
                       max_latency_s=0.05, max_energy_j=0.05,
                       min_throughput=10.0)
    be = evaluator.evaluate_batch(C, cons)
    F_np, CV_np = be.as_objectives(ALL_OBJECTIVES), be.violation
    fn = jax.jit(make_batch_eval_fn(evaluator.jax_tables(),
                                    ALL_OBJECTIVES, cons))
    F_j, CV_j = (np.asarray(x) for x in fn(jnp.asarray(C)))
    assert CV_np.max() > 0, "constraints must actually bite in this test"
    np.testing.assert_allclose(F_j, F_np, rtol=2e-5, atol=1e-6)
    np.testing.assert_allclose(CV_j, CV_np, rtol=2e-5, atol=1e-5)
    assert mem_cap > 0


def test_jit_eval_memory_capacity_violation(evaluator):
    """Platform memory-capacity violations (no explicit constraints)
    agree — exercises the Def.-3 segment-memory twin under pressure."""
    sys_small = SystemSpec(
        platforms=tuple(dataclasses.replace(p, mem_capacity=300_000)
                        for p in FOUR_PLATFORM.platforms),
        links=FOUR_PLATFORM.links).build()
    schedule = evaluator.schedule
    ev = PartitionEvaluator(evaluator.graph, schedule, sys_small,
                            accuracy_fn=ProxyAccuracy(schedule, sys_small))
    C = random_cuts(ev, 256, seed=3)
    be = ev.evaluate_batch(C)
    fn = jax.jit(make_batch_eval_fn(ev.jax_tables(), ("latency", "memory")))
    F_j, CV_j = (np.asarray(x) for x in fn(jnp.asarray(C)))
    assert be.violation.max() > 0
    np.testing.assert_allclose(CV_j, be.violation, rtol=2e-5, atol=1e-5)
    np.testing.assert_allclose(F_j[:, 1], be.memory_bytes.max(axis=1),
                               rtol=2e-5)


def test_jit_eval_requires_proxy_for_accuracy(evaluator):
    ev = PartitionEvaluator(evaluator.graph, evaluator.schedule,
                            evaluator.system,
                            accuracy_fn=MeasuredAccuracy(lambda c: 0.5))
    with pytest.raises(ValueError, match="proxy"):
        make_batch_eval_fn(ev.jax_tables(), ("latency", "accuracy"))


# -- operator twins -----------------------------------------------------------

def test_rank_and_crowding_twins_match_numpy():
    rng = np.random.default_rng(7)
    n = 300
    F = rng.random((n, 3))
    CV = np.where(rng.random(n) < 0.3, rng.random(n), 0.0)
    fronts = fast_non_dominated_sort(F, CV)
    rank_np = np.empty(n, dtype=int)
    for r, fr in enumerate(fronts):
        rank_np[fr] = r
    rank_j = np.asarray(nsga2_jax.nondominated_rank(
        jnp.asarray(F, jnp.float32), jnp.asarray(CV, jnp.float32)))
    assert (rank_j == rank_np).all()
    crowd_np = np.zeros(n)
    for fr in fronts:
        crowd_np[fr] = crowding_distance(F[fr])
    crowd_j = np.asarray(nsga2_jax.crowding_by_rank(
        jnp.asarray(F, jnp.float32), jnp.asarray(rank_j)))
    finite = np.isfinite(crowd_np)
    assert (np.isfinite(crowd_j) == finite).all()
    np.testing.assert_allclose(crowd_j[finite], crowd_np[finite], atol=1e-5)


def test_rank_cap_covers_selection_prefix():
    """Capped peeling must rank at least `cap` individuals and agree with
    the full sort on every rank it assigned."""
    rng = np.random.default_rng(1)
    F = rng.random((128, 2))
    CV = np.zeros(128)
    rank_full = np.asarray(nsga2_jax.nondominated_rank(
        jnp.asarray(F, jnp.float32), jnp.asarray(CV, jnp.float32)))
    rank_cap = np.asarray(nsga2_jax.nondominated_rank(
        jnp.asarray(F, jnp.float32), jnp.asarray(CV, jnp.float32), cap=64))
    ranked = rank_cap < 128
    assert ranked.sum() >= 64
    assert (rank_cap[ranked] == rank_full[ranked]).all()


def test_repair_twin_matches_numpy():
    from repro.core.nsga2 import _repair_batch
    rng = np.random.default_rng(2)
    X = rng.integers(-5, 40, size=(64, 4))
    want = _repair_batch(X.copy(), 0, 30)
    got = np.asarray(nsga2_jax.repair(jnp.asarray(X, jnp.int32), 0, 30))
    assert (want == got).all()


# -- seeded front equivalence -------------------------------------------------

def _no_clear_domination(Fa, Fb, scale, tol=0.02):
    """No point of Fa dominates any point of Fb by more than tol of the
    per-objective range (both GA fronts approximate the same true front)."""
    for f in Fa:
        margin_dom = np.all(f <= Fb - tol * scale, axis=1)
        assert not margin_dom.any(), (
            f"front point {f} clearly dominates {Fb[margin_dom][0]}")


def test_jit_front_equivalent_to_numpy_front(evaluator):
    """Seeded JIT and NumPy searches on the EfficientNet-style schedule
    converge to equivalent Pareto fronts (neither clearly dominates the
    other anywhere, same ideal point within tolerance)."""
    objectives = ("latency", "energy", "throughput")
    # budget chosen so both stochastic runs converge to the true front
    # (margins go to 0 here); at pop 192 / n_gen 50 the 1-ulp float32
    # difference between baked-constant and runtime-argument tables is
    # enough to send the two trajectories to different front samples
    settings = SearchSettings(strategy="nsga2", seed=0, pop_size=256,
                              n_gen=100)
    from repro.explore import run_search
    res_np = run_search(evaluator, objectives=objectives, settings=settings)
    res_jit = run_search(
        evaluator, objectives=objectives,
        settings=dataclasses.replace(settings, strategy="jit_nsga2"))
    assert res_np.nsga is not None and res_jit.nsga is not None
    assert len(res_jit.pareto) >= 1
    Fn = np.array([e.as_objectives(objectives) for e in res_np.pareto])
    Fj = np.array([e.as_objectives(objectives) for e in res_jit.pareto])
    scale = np.ptp(np.concatenate([Fn, Fj]), axis=0) + 1e-12
    _no_clear_domination(Fn, Fj, scale)
    _no_clear_domination(Fj, Fn, scale)
    # ideal points agree to 8% of each objective's range across both fronts
    # (different arithmetic streams; at this budget seed 0 hits 0% gap)
    assert (np.abs(Fj.min(axis=0) - Fn.min(axis=0)) <= 0.08 * scale).all()


def test_jit_front_points_are_exactly_scored(evaluator):
    """Returned PartitionEvals come from the exact NumPy evaluator (no
    float32 drift in reported metrics)."""
    from repro.explore import run_search
    res = run_search(evaluator, settings=SearchSettings(
        strategy="jit_nsga2", seed=1, pop_size=64, n_gen=10))
    for ev in res.pareto:
        exact = evaluator.evaluate(ev.cuts)
        assert ev.latency_s == exact.latency_s
        assert ev.memory_bytes == exact.memory_bytes


def test_jit_fallback_on_measured_accuracy(evaluator):
    """Accuracy objective + non-proxy oracle falls back to the NumPy
    strategy with a warning instead of mis-searching."""
    ev = PartitionEvaluator(evaluator.graph, evaluator.schedule,
                            evaluator.system,
                            accuracy_fn=MeasuredAccuracy(lambda c: 0.75))
    from repro.explore import run_search
    with pytest.warns(UserWarning, match="falling back"):
        res = run_search(ev, objectives=("latency", "accuracy"),
                         settings=SearchSettings(strategy="jit_nsga2",
                                                 seed=0, pop_size=32,
                                                 n_gen=5))
    assert len(res.pareto) >= 1


# -- spec plumbing ------------------------------------------------------------

def test_spec_json_roundtrip_selects_jit_strategy():
    spec = ExplorationSpec(
        model=ModelRef("cnn", "squeezenet11", {"in_hw": 64}),
        system=FOUR_PLATFORM,
        objectives=("latency", "energy"),
        search=SearchSettings(strategy="jit_nsga2", seed=0, pop_size=64,
                              n_gen=8))
    spec2 = ExplorationSpec.from_json(spec.to_json())
    assert spec2 == spec
    assert spec2.search.strategy == "jit_nsga2"
    res = run_spec(spec2)
    assert res.strategy == "jit_nsga2"
    assert res.nsga is not None
    assert len(res.pareto) >= 1
    assert res.n_evaluated == 64 * 9


def test_spec_roundtrip_scaling_knobs():
    """rank_block / rank_impl / n_restarts / rank_devices survive the JSON
    round-trip and are validated at construction."""
    spec = ExplorationSpec(
        model=ModelRef("cnn", "squeezenet11", {"in_hw": 64}),
        system=FOUR_PLATFORM,
        search=SearchSettings(strategy="jit_nsga2", pop_size=64, n_gen=4,
                              rank_block=512, rank_impl="ref",
                              n_restarts=3, rank_devices=2))
    spec2 = ExplorationSpec.from_json(spec.to_json())
    assert spec2 == spec
    assert spec2.search.rank_block == 512
    assert spec2.search.n_restarts == 3
    with pytest.raises(ValueError, match="rank_impl"):
        SearchSettings(rank_impl="mosaic")
    with pytest.raises(ValueError, match="n_restarts"):
        SearchSettings(n_restarts=0)


def test_rank_devices_beyond_visible_raises():
    """A search asked to shard its ranking over more devices than exist
    fails instead of quietly running on fewer."""
    import jax

    from repro.explore.strategies import _rank_mesh
    assert _rank_mesh(None) is None and _rank_mesh(1) is None
    with pytest.raises(ValueError, match="rank_devices"):
        _rank_mesh(len(jax.devices()) + 1)


def test_jit_strategy_restarts_front_superset(evaluator):
    """n_restarts=2 merges both seeds' fronts: every single-seed front
    point is matched or dominated, and n_evaluated counts both runs."""
    from repro.explore import run_search
    base = SearchSettings(strategy="jit_nsga2", seed=5, pop_size=64,
                          n_gen=8, rank_block=64)
    res1 = run_search(evaluator, settings=base)
    res2 = run_search(evaluator,
                      settings=dataclasses.replace(base, n_restarts=2))
    assert res2.n_evaluated == 2 * 64 * 9
    # seed 5 is restart 0 of the merged run, so its front can only be
    # equalled or improved by the union
    F1 = np.array([e.as_objectives(("latency", "energy")) for e in res1.pareto])
    F2 = np.array([e.as_objectives(("latency", "energy")) for e in res2.pareto])
    for f in F2:
        assert not (F1 < f - 1e-12).all(axis=1).any(), \
            "merged front point dominated by a single-seed point"


@pytest.mark.parametrize("n_restarts,dense_max", [(1, None), (2, None),
                                                  (2, 0)],
                         ids=["one", "restarts_dense", "restarts_blocked"])
def test_jit_strategy_pareto_idx_matches_host_front(evaluator, monkeypatch,
                                                    n_restarts, dense_max):
    """The front built from the runner's mask is ``pareto_indices`` of the
    returned population, for one search and for merged restarts on both
    sides of the dense/blocked size switch."""
    from repro.core.nsga2 import pareto_indices
    from repro.explore import run_search
    blocked = []
    if dense_max is not None:
        monkeypatch.setattr(JitNSGA2Search, "_DENSE_PARETO_MAX", dense_max)
        orig = nsga2_jax.pareto_indices_blocked

        def spy(*a, **kw):
            blocked.append(len(a[0]))
            return orig(*a, **kw)

        monkeypatch.setattr(nsga2_jax, "pareto_indices_blocked", spy)
    res = run_search(evaluator, settings=SearchSettings(
        strategy="jit_nsga2", seed=5, pop_size=64, n_gen=8, rank_block=64,
        n_restarts=n_restarts))
    nsga = res.nsga
    want = pareto_indices(nsga.X, nsga.F, nsga.CV)
    assert len(want) and (nsga.pareto_idx == want).all()
    assert res.counts["front_rows"] >= len(want)
    # the blocked extraction ran, over the restarts' front rows only
    assert blocked == ([] if dense_max is None else [res.counts["front_rows"]])


# -- strategy registry --------------------------------------------------------

def test_register_strategy_collision_and_override():
    class Custom:
        name = "jit_nsga2"

        def search(self, ctx):
            raise NotImplementedError

    with pytest.raises(ValueError, match="already registered"):
        register_strategy("jit_nsga2", Custom)
    original = STRATEGIES["jit_nsga2"]
    assert original is JitNSGA2Search
    try:
        register_strategy("jit_nsga2", Custom, override=True)
        assert STRATEGIES["jit_nsga2"] is Custom
    finally:
        register_strategy("jit_nsga2", original, override=True)
    # fresh names register without override and are selectable from
    # SearchSettings / resolved to instances (the registry's whole point)
    class Stub:
        name = "my_custom_search"

        def search(self, ctx):
            raise NotImplementedError

    try:
        register_strategy("my_custom_search", Stub)
        assert STRATEGIES["my_custom_search"] is Stub
        settings = SearchSettings(strategy="my_custom_search")
        from repro.explore.strategies import resolve_strategies
        (strat,) = resolve_strategies(settings, n_cuts=3, n_candidates=10)
        assert isinstance(strat, Stub)
    finally:
        STRATEGIES.pop("my_custom_search", None)
    with pytest.raises(ValueError, match="unknown strategy"):
        SearchSettings(strategy="my_custom_search")


def test_lazy_jit_twins_via_nsga2_module():
    """core.nsga2 exposes the twins under jit_* without importing JAX at
    module import time."""
    from repro.core import nsga2
    assert nsga2.jit_repair is nsga2_jax.repair
    assert nsga2.jit_nsga2 is nsga2_jax.jit_nsga2
    with pytest.raises(AttributeError):
        nsga2.jit_does_not_exist


# -- campaign end-to-end ------------------------------------------------------

def test_campaign_runs_jit_strategy():
    from repro.explore import Campaign
    spec = ExplorationSpec(
        model=ModelRef("cnn", "squeezenet11", {"in_hw": 64}),
        system=FOUR_PLATFORM,
        objectives=("latency", "energy"),
        search=SearchSettings(strategy="jit_nsga2", seed=0, pop_size=64,
                              n_gen=6))
    models = [ModelRef("cnn", n, {"in_hw": 64})
              for n in ("squeezenet11", "regnetx_400mf")]
    with warnings.catch_warnings():
        warnings.simplefilter("error")        # no fallback warnings allowed
        cr = Campaign(spec, models=models).run()
    assert len(cr.entries) == 2
    for e in cr.entries:
        assert len(e.result.pareto) >= 1
        assert e.result.selected is not None
    rep = cr.report.to_dict()
    assert rep["template"]["search"]["strategy"] == "jit_nsga2"
