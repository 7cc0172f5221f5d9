"""Online re-partitioning under live system drift.

The paper's deployment scenarios (automotive, robotics) have links that
degrade and nodes that drop out mid-mission; a cold
:func:`~repro.explore.runner.run_spec` reacts in *seconds* because every
perturbed system re-traces and re-compiles the ``jit_nsga2`` program.
:class:`OnlineRepartitioner` turns the search into a service that reacts in
*milliseconds* by exploiting three invariants of drift:

1. **Shapes are static.**  Link degradation changes ``rate_bps`` values and
   node dropout shrinks a ``mem_capacity`` — neither changes any table
   shape, so the compiled runner (whose evaluation tables are runtime
   pytree arguments — :func:`repro.core.partition_jax.make_runtime_eval_fn`)
   is reused across every perturbation via the shared shape-keyed runner
   cache.  Zero recompilation after the first search.
2. **The candidate list is pinned** to the baseline system's filtered cut
   positions, keeping the gene table (and hence the compiled shape)
   identical across drifted systems; feasibility shifts are absorbed by
   Deb constraint domination inside the search, exactly how the paper's
   NSGA-II handles infeasible rows.
3. **Optima move slowly.**  Each re-search warm-starts from the previous
   Pareto front (:func:`repro.core.nsga2_jax.warm_population`), so a small
   generation budget re-converges.

Perturbation helpers (:func:`degrade_link`, :func:`drop_node`) produce
same-shape :class:`~repro.explore.spec.SystemSpec` variants; decisions are
consumed by the serving runtime by swapping
:func:`~repro.explore.deploy.lm_block_cuts` on the replicas when
:attr:`RepartitionDecision.changed` (see ``launch/drift.py`` for the
end-to-end loop and ``benchmarks/drift_bench.py`` for the ≥ 20× warm-vs-cold
gate).
"""

from __future__ import annotations

import dataclasses
import time
from typing import Iterable, Iterator, List, Optional, Tuple, Union

import numpy as np

from repro.core.accuracy import ProxyAccuracy
from repro.core.graph import linearize
from repro.core.nsga2 import crowding_distance
from repro.core.partition import PartitionEvaluator, SystemConfig
from repro.explore.deploy import lm_block_cuts
from repro.explore.filters import candidate_positions
from repro.explore.result import ExplorationResult
from repro.explore.runner import run_search
from repro.explore.spec import ExplorationSpec, SearchSettings, SystemSpec
from repro.obs.handle import NOOP_OBS, Obs, phase

SystemLike = Union[SystemSpec, SystemConfig]

# a "dropped" node keeps its table slot (shapes must not change) but gets a
# 1-byte memory capacity: every placement that assigns it layers violates
# Def. 3 maximally, so constraint domination routes the search around it
_DROPPED_CAPACITY = 1


def degrade_link(system: SystemSpec, link: int,
                 factor: float) -> SystemSpec:
    """A same-shape copy of ``system`` with ``links[link]`` slowed down.

    The link's effective ``rate_bps`` (registry base plus any existing
    override) is divided by ``factor`` (> 1 degrades, < 1 upgrades).  Only
    a value changes, so the perturbed spec shares the baseline's compiled
    runner.
    """
    if not 0 <= link < len(system.links):
        raise IndexError(f"link {link} out of range "
                         f"(system has {len(system.links)})")
    if factor <= 0:
        raise ValueError(f"factor must be > 0, got {factor}")
    links = list(system.links)
    rate = links[link].build().rate_bps / factor
    links[link] = dataclasses.replace(links[link], rate_bps=rate)
    return dataclasses.replace(
        system, links=tuple(links),
        name=f"{system.label}~link{link}/{factor:g}")


def drop_node(system: SystemSpec, node: int) -> SystemSpec:
    """A same-shape copy of ``system`` with platform ``node`` marked dead.

    The platform keeps its slot in every table (shapes are sacred) but its
    memory capacity collapses to 1 byte, so any placement routing layers
    onto it is maximally infeasible and the re-search steers every stage
    around the node — the paper's node-dropout scenario without a single
    recompilation.
    """
    if not 0 <= node < len(system.platforms):
        raise IndexError(f"node {node} out of range "
                         f"(system has {len(system.platforms)})")
    plats = list(system.platforms)
    plats[node] = dataclasses.replace(plats[node],
                                      mem_capacity=_DROPPED_CAPACITY)
    return dataclasses.replace(
        system, platforms=tuple(plats),
        name=f"{system.label}~drop{node}")


@dataclasses.dataclass
class RepartitionDecision:
    """One re-deployment decision emitted by :class:`OnlineRepartitioner`.

    ``cuts`` is the Def.-2 selected cut vector (``None`` when the front
    came up empty), ``changed`` flags whether deployment must act (the cut
    vector differs from the previous decision's), ``repartition_ms`` is the
    wall-clock of the whole update (evaluator build + warm re-search +
    selection), and ``feasible`` reports whether the selected placement
    satisfies every constraint on the *drifted* system.
    """

    step: int                       # 0-based update counter
    label: str                      # system label at this step
    cuts: Optional[Tuple[int, ...]]
    changed: bool
    repartition_ms: float
    feasible: bool
    pareto_size: int
    strategy_used: str
    result: ExplorationResult = dataclasses.field(repr=False)
    trigger: str = "event"          # 'event' (told) | 'measured' (observed)

    def block_cuts(self, n_layers: int) -> List[int]:
        """Decoder-block cut indices for ``PartitionedLMRunner`` — the
        serve-side form of this decision (falls back to a middle split
        when ``cuts`` is None, so deployment always has a target)."""
        return lm_block_cuts(self.cuts or (), n_layers)


class OnlineRepartitioner:
    """Millisecond re-partitioning service over a stream of drifted systems.

    Construction resolves the spec's model once (graph, schedule, Def.-3
    memory table, per-arch cost cache are all shared across updates) and
    pins the candidate cut positions from the spec's *baseline* system.
    Each :meth:`update` then builds a cheap evaluator for the drifted
    system, re-searches warm from the previous Pareto front on the shared
    compiled runner, and emits a :class:`RepartitionDecision`.

    The search strategy is forced to ``jit_nsga2`` (the only strategy whose
    compilation is reusable across systems); every other knob of
    ``spec.search`` — or of an explicit ``settings`` override — is honored,
    including ``warm_start=False`` for A/B comparisons.
    """

    def __init__(self, spec: ExplorationSpec, *,
                 settings: Optional[SearchSettings] = None,
                 max_warm_front: int = 64,
                 obs: Optional[Obs] = None):
        if max_warm_front < 1:
            raise ValueError(
                f"max_warm_front must be >= 1, got {max_warm_front}")
        self.max_warm_front = max_warm_front
        # repartition decisions land on the "health/repartition" track
        self.obs = obs if obs is not None else NOOP_OBS
        self.spec = spec
        settings = settings or spec.search
        if settings.strategy != "jit_nsga2":
            settings = dataclasses.replace(settings, strategy="jit_nsga2")
        self.settings = settings
        graph, shared = spec.model.build()
        self.graph = graph
        self.shared_groups = shared
        self.schedule = linearize(graph, spec.schedule_policy)
        self._cost_cache: dict = {}
        base_eval = self._evaluator(spec.system.build())
        self._memtable = base_eval._memtable
        # pinned gene space: the baseline system's filtered candidates
        self.candidates: List[int] = candidate_positions(
            base_eval, spec.constraints, settings.allow_multi_tensor_cuts)
        self.decisions: List[RepartitionDecision] = []
        self._front_cuts: Optional[np.ndarray] = None
        self._last_cuts: Optional[Tuple[int, ...]] = None

    def _evaluator(self, system: SystemConfig) -> PartitionEvaluator:
        spec = self.spec
        if spec.accuracy is not None:
            acc = spec.accuracy.build(self.graph, self.schedule, system)
        else:
            acc = ProxyAccuracy(self.schedule, system)
        return PartitionEvaluator(
            self.graph, self.schedule, system, accuracy_fn=acc,
            batch=spec.batch, shared_groups=self.shared_groups,
            cost_cache=self._cost_cache,
            memtable=getattr(self, "_memtable", None))

    def update(self, system: SystemLike, label: Optional[str] = None,
               trigger: str = "event") -> RepartitionDecision:
        """Re-partition for one (possibly drifted) system snapshot.

        ``system`` may be a declarative :class:`SystemSpec` (typically from
        :func:`degrade_link` / :func:`drop_node`, or a
        ``DivergenceMonitor.drifted_system()`` snapshot — in that case pass
        ``trigger='measured'``) or an already-built :class:`SystemConfig`.
        It must be same-shape with the baseline (same platform/link
        counts); a different shape still works but pays one fresh XLA
        compilation.

        The whole call is the span ``search/entry`` (:func:`repro.obs
        .phase`): the evaluator build ``search/evaluator``, the search's
        own phases (:func:`~repro.explore.runner.run_search`), then the
        carried warm front ``search/warm_carry``.
        """
        with phase("search/entry", self.obs):
            return self._update(system, label, trigger)

    def _update(self, system: SystemLike, label: Optional[str],
                trigger: str) -> RepartitionDecision:
        t0 = time.perf_counter()
        with phase("search/evaluator", self.obs):
            if isinstance(system, SystemSpec):
                label = label or system.label
                system = system.build()
            evaluator = self._evaluator(system)
        label = label or f"step{len(self.decisions)}"
        res = run_search(
            evaluator, constraints=self.spec.constraints,
            objectives=self.spec.objectives, weights=self.spec.weights,
            settings=self.settings, candidates=self.candidates,
            warm_cuts=self._front_cuts, obs=self.obs)
        ms = (time.perf_counter() - t0) * 1e3
        cuts = res.selected.cuts if res.selected is not None else None
        feasible = res.selected is not None and res.selected.violation <= 0
        decision = RepartitionDecision(
            step=len(self.decisions), label=label, cuts=cuts,
            changed=cuts != self._last_cuts, repartition_ms=ms,
            feasible=feasible, pareto_size=len(res.pareto),
            strategy_used=res.strategy_used, result=res, trigger=trigger)
        self._last_cuts = cuts
        if self.obs.enabled:
            self.obs.tracer.instant(
                "repartition", cat="health", track="health/repartition",
                args={"label": label, "trigger": trigger,
                      "changed": decision.changed,
                      "feasible": feasible, "ms": round(ms, 3)})
            self.obs.metrics.counter("repartition_decisions").inc()
            if decision.changed:
                self.obs.metrics.counter("repartition_changes").inc()
            self.obs.metrics.histogram("repartition_ms").observe(ms)
        with phase("search/warm_carry", self.obs):
            if res.pareto:
                front = res.pareto
                if len(front) > self.max_warm_front:
                    # bound the carried warm seed: long drift histories must
                    # not grow it without limit, and crowding distance keeps
                    # the most diversity-preserving top-k of the front
                    F = np.asarray([e.as_objectives(self.spec.objectives)
                                    for e in front], dtype=float)
                    cd = crowding_distance(F)
                    keep = sorted(np.argsort(-cd, kind="stable")
                                  [:self.max_warm_front])
                    front = [front[int(i)] for i in keep]
                self._front_cuts = np.asarray([e.cuts for e in front],
                                              dtype=int)
        self.decisions.append(decision)
        return decision

    def watch(self, systems: Iterable[SystemLike]
              ) -> Iterator[RepartitionDecision]:
        """Drive :meth:`update` over a stream of system snapshots, yielding
        each decision as it is made (generator — lazy, so a live producer
        can feed it)."""
        for system in systems:
            yield self.update(system)
