"""Exploration engine: resolve a spec, run its strategies, finish with the
final non-dominated filtering and the paper's Def.-2 weighted-sum selection.

Three entry points, from most to least declarative:

* :func:`run_spec`      — resolve an :class:`ExplorationSpec` end-to-end.
* :func:`explore_graph` — run over a live ``LayerGraph``/``SystemConfig``
  (for callers that already hold model objects, e.g. the serving driver).
* :func:`run_search`    — run over a prebuilt ``PartitionEvaluator``
  (campaigns inject shared cost tables here).

All strategies — including the ``jax.jit``-compiled ``jit_nsga2``, which
reads the evaluator's tables as device arrays via
``PartitionEvaluator.jax_tables()`` (built lazily, cached per evaluator) —
consume the same evaluator, so campaign-level cost-table sharing benefits
the JIT path too.
"""

from __future__ import annotations

from typing import Callable, Dict, List, Optional, Sequence

import numpy as np

from repro.core.accuracy import ProxyAccuracy
from repro.core.graph import LayerGraph, linearize
from repro.core.layers import LayerInfo
from repro.core.memory import SegmentMemoryTable
from repro.core.nsga2 import fast_non_dominated_sort
from repro.core.partition import (Constraints, PartitionEval,
                                  PartitionEvaluator, SystemConfig,
                                  single_platform_eval)
from repro.explore.filters import candidate_positions, link_feasibility
from repro.explore.result import ExplorationResult
from repro.explore.spec import AccuracySpec, ExplorationSpec, SearchSettings
from repro.explore.strategies import (SearchContext, resolve_strategies)
from repro.obs.handle import Obs, phase

DEFAULT_OBJECTIVES = ("latency", "energy")


def select_weighted(pareto: Sequence[PartitionEval],
                    objectives: Sequence[str],
                    weights: Sequence[float]) -> Optional[PartitionEval]:
    """Def. 2: min-max-normalized weighted sum over the front; ``None`` for
    an empty front."""
    if not pareto:
        return None
    F = np.array([ev.as_objectives(objectives) for ev in pareto], dtype=float)
    lo, hi = F.min(axis=0), F.max(axis=0)
    span = np.where(hi - lo > 0, hi - lo, 1.0)
    score = ((F - lo) / span) @ np.asarray(weights)
    return pareto[int(np.argmin(score))]


def run_search(evaluator: PartitionEvaluator, *,
               constraints: Optional[Constraints] = None,
               objectives: Sequence[str] = DEFAULT_OBJECTIVES,
               weights: Optional[Sequence[float]] = None,
               settings: Optional[SearchSettings] = None,
               candidates: Optional[Sequence[int]] = None,
               warm_cuts: Optional[Sequence[Sequence[int]]] = None,
               obs: Optional[Obs] = None
               ) -> ExplorationResult:
    """Run the configured strategies over a prebuilt evaluator and finish:
    union pool → final non-dominated filter → Def.-2 selection.

    ``candidates`` overrides the filtered candidate positions — the online
    re-partitioner pins them to the *baseline* system's list so the gene
    table (and hence the compiled-runner shape) stays identical across
    drifted systems; feasibility shifts are then absorbed by constraint
    domination instead of by re-filtering.  ``warm_cuts`` feeds a previous
    Pareto front's cut rows to warm-startable strategies (honored when
    ``settings.warm_start`` is on).  Its host phases are named spans
    (:func:`repro.obs.phase`), also recorded on ``obs`` when it is live.
    """
    constraints = constraints or Constraints()
    settings = settings or SearchSettings()
    objectives = tuple(objectives)
    weights = (tuple(weights) if weights
               else tuple(1.0 for _ in objectives))
    with phase("search/candidates", obs):
        if candidates is None:
            cands = candidate_positions(evaluator, constraints,
                                        settings.allow_multi_tensor_cuts)
        else:
            cands = list(candidates)
        link_feas = link_feasibility(evaluator, constraints.max_link_bytes)
    ctx = SearchContext(
        evaluator=evaluator, candidates=cands, constraints=constraints,
        objectives=objectives, settings=settings, link_feas=link_feas,
        warm_cuts=(np.asarray(warm_cuts, dtype=int)
                   if warm_cuts is not None and len(warm_cuts) else None),
        obs=obs or Obs.off())

    with phase("search/baselines", obs):
        baselines = [single_platform_eval(evaluator, i, constraints)
                     for i in range(len(evaluator.system.platforms))]

    scan_pool: List[PartitionEval] = []
    search_pool: List[PartitionEval] = []
    all_evals: List[PartitionEval] = []
    nsga = None
    n_evaluated = 0
    counts: Dict[str, int] = {}
    used: List[str] = []
    for strategy in resolve_strategies(settings, ctx.n_cuts, len(cands)):
        out = strategy.search(ctx)
        (scan_pool if out.exhaustive else search_pool).extend(out.evals)
        if not all_evals and out.all_evals:
            all_evals = out.all_evals
        nsga = out.nsga or nsga
        n_evaluated += out.n_evaluated
        for k, v in out.counts.items():
            counts[k] = counts.get(k, 0) + v
        used.append(out.strategy_used or strategy.name)

    # pool order mirrors the legacy Explorer: exact scans, then feasible
    # baselines, then heuristic-search points (first-seen wins dedupe ties)
    pool = scan_pool + [b for b in baselines if b.violation <= 0] + search_pool
    if not pool:
        pool = baselines[:]

    pareto: List[PartitionEval] = []
    with phase("search/select", obs):
        if pool:
            F = np.array([ev.as_objectives(objectives) for ev in pool])
            CV = np.array([ev.violation for ev in pool])
            fronts = fast_non_dominated_sort(F, CV)
            seen = set()
            for i in fronts[0]:
                if pool[i].cuts not in seen:
                    seen.add(pool[i].cuts)
                    pareto.append(pool[i])
        selected = select_weighted(pareto, objectives, weights)
    return ExplorationResult(
        schedule=list(evaluator.schedule), candidates=cands,
        all_evals=all_evals, pareto=pareto, selected=selected,
        baselines=baselines, objectives=objectives, nsga=nsga,
        strategy=settings.strategy, n_evaluated=n_evaluated,
        strategy_used="+".join(dict.fromkeys(used)) or settings.strategy,
        counts=counts)


def explore_graph(graph: LayerGraph, system: SystemConfig, *,
                  objectives: Sequence[str] = DEFAULT_OBJECTIVES,
                  weights: Optional[Sequence[float]] = None,
                  constraints: Optional[Constraints] = None,
                  search: Optional[SearchSettings] = None,
                  schedule_policy: str = "min_memory",
                  batch: int = 1,
                  accuracy_fn: Optional[Callable] = None,
                  accuracy: Optional[AccuracySpec] = None,
                  shared_groups: Optional[Dict[str, str]] = None,
                  schedule: Optional[Sequence[LayerInfo]] = None,
                  cost_cache: Optional[Dict] = None,
                  memtable: Optional[SegmentMemoryTable] = None
                  ) -> ExplorationResult:
    """Run one exploration over live graph/system objects.

    ``schedule`` / ``cost_cache`` / ``memtable`` let campaign runners share
    per-model scheduling and per-arch cost tables across systems.  The
    accuracy oracle resolves in precedence order: a live ``accuracy_fn``
    object, then a declarative ``accuracy`` :class:`AccuracySpec` (proxy
    knobs or a registered measured oracle), then the default
    :class:`ProxyAccuracy`.  The whole call is the span ``search/entry``
    (:func:`repro.obs.phase`), its evaluator build ``search/evaluator``.
    """
    with phase("search/entry"):
        with phase("search/evaluator"):
            if schedule is None:
                schedule = linearize(graph, schedule_policy)
            acc = accuracy_fn
            if acc is None and accuracy is not None:
                acc = accuracy.build(graph, schedule, system)
            if acc is None:
                acc = ProxyAccuracy(schedule, system)
            evaluator = PartitionEvaluator(
                graph, schedule, system, accuracy_fn=acc, batch=batch,
                shared_groups=shared_groups, cost_cache=cost_cache,
                memtable=memtable)
        return run_search(evaluator, constraints=constraints,
                          objectives=objectives, weights=weights,
                          settings=search)


def run_spec(spec: ExplorationSpec) -> ExplorationResult:
    """Resolve a declarative spec (model + system refs) and run it."""
    graph, shared = spec.model.build()
    system = spec.system.build()
    return explore_graph(
        graph, system, objectives=spec.objectives, weights=spec.weights,
        constraints=spec.constraints, search=spec.search,
        schedule_policy=spec.schedule_policy, batch=spec.batch,
        accuracy=spec.accuracy, shared_groups=shared)
