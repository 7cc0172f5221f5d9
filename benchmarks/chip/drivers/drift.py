"""Driver for online re-partitioning cells: back-to-back
``OnlineRepartitioner.update`` calls over a seeded stream of drifted systems.

Set-up builds the repartitioner once (graph, schedule, memory table, cost
tables, the pinned candidate positions) and makes its first decision on the
baseline system, which compiles the shared runner.  The window then feeds
events until its time is up, each a same-shape copy of the baseline, so the
runner compiles once and every search starts warm from the last front:

* a link degradation, round-robin over the chain's links, by a factor from a
  fixed geometric spread over ``factor`` (each seed gets the same factors in
  its own order);
* every ``drop_every``-th event, a node dropped (its memory collapses to one
  byte), the node drawn from the seed.

The event stream is built the way ``benchmarks/drift_bench.py`` builds it,
with factors bounded so that a long window cannot overflow them.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Dict, List

import numpy as np

from benchmarks.chip import traffic as T
from benchmarks.chip.drivers import search as S


@dataclasses.dataclass
class Decision:
    """One update of the window: its host wall, the program's own
    ``search_wall_s`` observation, what it returned and the system it
    answered for."""
    start_s: float
    wall_s: float
    program_wall_s: float
    result: object
    system: object


@dataclasses.dataclass
class State:
    cfg: Dict
    work: Dict
    seed: int
    spec: object
    rep: object
    events: List
    cost_rows: Dict


def _spec(cfg: Dict, work: Dict, seed: int):
    from repro.explore import (ExplorationSpec, ModelRef, PlatformSpec,
                               SearchSettings, SystemSpec)
    t = work["traffic"]
    return ExplorationSpec(
        model=ModelRef("cnn", cfg["model"], {"in_hw": int(cfg["in_hw"])}),
        system=SystemSpec(platforms=tuple(
            PlatformSpec(p["name"], p["arch"], bits=int(p["bits"]))
            for p in cfg["platforms"]), links=tuple(cfg["links"])),
        objectives=tuple(cfg["objectives"]),
        schedule_policy=cfg["schedule_policy"], batch=int(cfg["batch"]),
        search=SearchSettings(strategy="jit_nsga2", seed=T.sub_seed(seed, 0),
                              pop_size=int(t["pop"]), n_gen=int(t["n_gen"]),
                              warm_start=True))


def event_stream(base, traffic: Dict, seed: int) -> List:
    """The pool of drifted systems, in the seed's order."""
    from repro.explore import degrade_link, drop_node
    n = int(traffic["pool"])
    lo, hi = (float(v) for v in traffic["factor"])
    factors = lo * (hi / lo) ** ((np.arange(n) + 0.5) / n)
    factors = T.rng_for(seed, 21).permutation(factors)
    nodes = T.rng_for(seed, 22).integers(0, len(base.platforms), size=n)
    every = int(traffic["drop_every"])
    out = []
    for i in range(n):
        if (i + 1) % every == 0:
            out.append(drop_node(base, int(nodes[i])))
        else:
            out.append(degrade_link(base, i % len(base.links),
                                    float(factors[i])))
    return out


def build(cfg: Dict, work: Dict, seed: int, ref) -> State:
    from repro.core.hwmodel.mapper import layer_cost_table
    from repro.explore import OnlineRepartitioner
    spec = _spec(cfg, work, seed)
    rep = OnlineRepartitioner(spec)
    first = rep.update(spec.system)
    if first.strategy_used != "jit_nsga2":
        raise RuntimeError(f"search ran {first.strategy_used!r}")
    base = spec.system.build()
    rows = {p.arch.name: layer_cost_table(rep.schedule, p.arch,
                                          int(cfg["batch"]))
            for p in base.platforms}
    return State(cfg, work, seed, spec, rep,
                 event_stream(spec.system, work["traffic"], seed), rows)


def instrument(st: State) -> None:
    """Nothing to switch on: the search records into the program's default
    metrics registry whether traced or not."""


def window(st: State, seconds: float, annotate) -> Dict:
    from repro.obs.metrics import default_registry
    hist = default_registry().histogram("search_wall_s")
    out: List[Decision] = []
    t0 = time.perf_counter()
    i = 0
    while time.perf_counter() - t0 < seconds:
        system = st.events[i % len(st.events)].build()
        before = hist.total
        a = time.perf_counter()
        with annotate("bench/update"):
            d = st.rep.update(system)
        b = time.perf_counter()
        out.append(Decision(a - t0, b - a, hist.total - before, d.result,
                            system))
        i += 1
    return {"searches": out, "t_end": time.perf_counter() - t0}


e2e = S.e2e
counts = S.counts
release = S.release


def readings(st: State, res: Dict, ref, control: bool = False
             ) -> Dict[str, float]:
    """As for search cells (:func:`search.compare_searches`), each decision
    against the reference on the system it answered for; the populations of
    ``quality_checks`` decisions drawn from the seed are scored against the
    whole space on their system."""
    rep = st.rep
    items = [(d.result, S.reference_data(rep.graph, rep.schedule, d.system,
                                         st.cost_rows, int(st.cfg["batch"])))
             for d in res["searches"]]
    k = min(int(st.work["traffic"]["quality_checks"]), len(items))
    picks = T.rng_for(st.seed, 12).choice(len(items), size=k, replace=False)
    return S.compare_searches(items, len(rep.schedule), ref, st.seed,
                              int(st.work["traffic"]["sample_rows"]),
                              picks.tolist(),
                              int(st.work["traffic"]["share_rows"]), control)


def layer_inputs(st: State, res: Dict) -> Dict:
    t = st.work["traffic"]
    return {"searches": res["searches"], "pop": int(t["pop"]),
            "n_gen": int(t["n_gen"]), "m": len(st.cfg["objectives"]),
            "platforms": len(st.cfg["platforms"]),
            "links": len(st.cfg["links"])}
