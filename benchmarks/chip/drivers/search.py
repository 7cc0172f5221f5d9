"""Driver for partition-search cells: back-to-back ``explore_graph`` calls.

Set-up builds the graph, the system, the schedule, the per-architecture
cost tables and the memory table once, as ``Campaign`` and the fleet worker
share them, and warms the compiled search with one short search at the
cell's population.  The window then runs whole searches back to back, each
with its own seed drawn from the run's seed, until the window's time is
up; the search running at that moment finishes and counts.

The answers checked after the window are each search's final population
and reported front as the timed path produced them (see
:func:`compare_searches`).
"""

from __future__ import annotations

import dataclasses
import time
from typing import Dict, List

import numpy as np

from benchmarks.chip import traffic as T


@dataclasses.dataclass
class Search:
    """One search of the window: its seed, host wall, the program's own
    ``search_wall_s`` observation, and what it returned."""
    seed: int
    start_s: float
    wall_s: float
    program_wall_s: float
    result: object


@dataclasses.dataclass
class State:
    cfg: Dict
    work: Dict
    seed: int
    graph: object
    shared: object
    system: object
    schedule: list
    cost_cache: dict
    memtable: object
    data: Dict = dataclasses.field(default_factory=dict)


def _settings(work: Dict, seed: int, n_gen: int):
    from repro.explore import SearchSettings
    t = work["traffic"]
    return SearchSettings(strategy="jit_nsga2", seed=seed,
                          pop_size=int(t["pop"]), n_gen=n_gen,
                          rank_block=int(t["rank_block"]))


def _search(st: State, seed: int, n_gen: int):
    from repro.explore.runner import explore_graph
    return explore_graph(
        st.graph, st.system, objectives=tuple(st.cfg["objectives"]),
        search=_settings(st.work, seed, n_gen), batch=int(st.cfg["batch"]),
        schedule=st.schedule, cost_cache=st.cost_cache, memtable=st.memtable,
        shared_groups=st.shared)


def build(cfg: Dict, work: Dict, seed: int, ref) -> State:
    from repro.core.graph import linearize
    from repro.core.memory import SegmentMemoryTable
    from repro.explore import ModelRef, PlatformSpec, SystemSpec
    graph, shared = ModelRef("cnn", cfg["model"],
                             {"in_hw": int(cfg["in_hw"])}).build()
    system = SystemSpec(
        platforms=tuple(PlatformSpec(p["name"], p["arch"], bits=int(p["bits"]))
                        for p in cfg["platforms"]),
        links=tuple(cfg["links"])).build()
    schedule = linearize(graph, cfg["schedule_policy"])
    st = State(cfg, work, seed, graph, shared, system, schedule, {},
               SegmentMemoryTable(schedule, shared))
    # one generation compiles the same runner the window drives (the
    # generation count is a traced loop bound) and fills the cost tables
    res = _search(st, T.sub_seed(seed, 1 << 20), 1)
    if res.strategy_used != "jit_nsga2":
        raise RuntimeError(f"search ran {res.strategy_used!r}, not jit_nsga2")
    st.data = reference_data(st.graph, st.schedule, st.system,
                             {k: v[0] for k, v in st.cost_cache.items()},
                             int(cfg["batch"]))
    return st


def reference_data(graph, schedule, system, cost_rows: Dict,
                   batch: int) -> Dict:
    """The deployment's per-layer rows for the plain reference
    (``cost_rows``: the hardware model's per-layer costs by architecture)."""
    pos = {layer.name: i for i, layer in enumerate(schedule)}
    plats = system.platforms
    rows = [cost_rows[p.arch.name] for p in plats]
    return {
        "lat": [[c.latency_s for c in tab] for tab in rows],
        "energy": [[c.energy_j for c in tab] for tab in rows],
        "params": [layer.params for layer in schedule],
        "act": [layer.activation_footprint for layer in schedule],
        "out_elems": [layer.fmap_out for layer in schedule],
        "edges": sorted((pos[u], pos[v]) for u, v in graph.edges),
        "bits": [p.quant.bits for p in plats],
        "capacity": [p.capacity for p in plats],
        "batch": batch,
        "links": [{"rate_bps": lk.rate_bps, "t_setup_s": lk.t_setup_s,
                   "payload_bytes": lk.payload_bytes,
                   "header_bytes": lk.header_bytes, "p_tx_w": lk.p_tx_w,
                   "p_rx_w": lk.p_rx_w, "e_per_byte_j": lk.e_per_byte_j}
                  for lk in system.links],
    }


def instrument(st: State) -> None:
    """Nothing to switch on: the search records into the program's default
    metrics registry whether traced or not."""


def window(st: State, seconds: float, annotate) -> Dict:
    """Searches back to back for ``seconds``; returns every search."""
    from repro.obs.metrics import default_registry
    hist = default_registry().histogram("search_wall_s")
    n_gen = int(st.work["traffic"]["n_gen"])
    out: List[Search] = []
    t0 = time.perf_counter()
    i = 0
    while time.perf_counter() - t0 < seconds:
        s = T.sub_seed(st.seed, i)
        before = hist.total
        a = time.perf_counter()
        with annotate("bench/search"):
            res = _search(st, s, n_gen)
        b = time.perf_counter()
        out.append(Search(s, a - t0, b - a, hist.total - before, res))
        i += 1
    end = time.perf_counter() - t0
    return {"searches": out, "t_end": end}


def e2e(st: State, res: Dict) -> Dict[str, float]:
    evals = sum(s.result.n_evaluated for s in res["searches"])
    return {"search_evals_per_s": evals / res["t_end"]}


def counts(res: Dict) -> Dict[str, int]:
    searches = res["searches"]
    return {"attempted": len(searches),
            "failed": sum(1 for s in searches
                          if s.result.strategy_used != "jit_nsga2"
                          or s.result.nsga is None)}


def release(st: State) -> None:
    from repro.explore.strategies import clear_jit_runner_cache
    clear_jit_runner_cache()


def readings(st: State, res: Dict, ref, control: bool = False
             ) -> Dict[str, float]:
    """The numbers compared, worst over every search of the window (see
    :func:`compare_searches`); the populations of ``quality_checks``
    searches drawn from the seed are scored against the whole space."""
    searches = res["searches"]
    t = st.work["traffic"]
    k = min(int(t["quality_checks"]), len(searches))
    picks = T.rng_for(st.seed, 12).choice(len(searches), size=k, replace=False)
    return compare_searches([(s.result, st.data) for s in searches],
                            len(st.schedule), ref, st.seed,
                            int(t["sample_rows"]), picks.tolist(),
                            int(t["share_rows"]), control)


def compare_searches(items, n_layers: int, ref, seed: int, n_rows: int,
                     picks, share_rows: int, control: bool = False
                     ) -> Dict[str, float]:
    """Worst readings over ``(ExplorationResult, reference data)`` pairs.

    * ``pop_dominated_pct``, for the items listed in ``picks``: the median,
      over ``share_rows`` rows of the final population drawn from the seed,
      of the share of the whole space (the reference's ``all_cut_vectors``)
      that dominates the row.  Only the generation loop's selection brings
      the population near the front: a population left as it was drawn
      reads far higher;
    * ``front_mismatches``: rows by which the program's first front of its
      final population differs from the reference's (the host selection);
    * ``device_eval_gap``: widest gap between the objectives and violation
      the compiled generation loop computed for its final population and the
      reference's, on ``n_rows`` rows drawn from the seed plus the front;
    * ``host_rescore_gap``: widest gap between the reported front's
      float64 re-scored objectives and the reference's.

    With ``control`` the reference at the next lower precision takes the
    program's place: bfloat16 for the float32 device evaluation (and for the
    dominance count, over the same rows), float32 for the float64 re-score.
    """
    import ml_dtypes
    worst = {"pop_dominated_pct": 0.0, "front_mismatches": 0.0,
             "device_eval_gap": 0.0, "host_rescore_gap": 0.0}
    picks = set(picks)
    space = {}
    low = ml_dtypes.bfloat16 if control else np.float64
    for k, (r, data) in enumerate(items):
        data = dict(data, cut_elems=ref.cut_elements(
            n_layers, data["edges"], data["out_elems"]))
        nsga = r.nsga
        table = np.array([-1] + list(r.candidates) + [n_layers - 1])
        cuts = np.sort(table[nsga.X], axis=1)
        rng = T.rng_for(seed, 11, k)
        sample = rng.choice(len(cuts), size=min(n_rows, len(cuts)),
                            replace=False)
        if k in picks:
            key = (id(items[k][1]), tuple(table))
            if key not in space:
                F_all, CV_all = ref.evaluate(
                    ref.all_cut_vectors(table, cuts.shape[1]), data, low)
                space[key] = (F_all.astype(np.float64),
                              CV_all.astype(np.float64))
            F_s, CV_s = ref.evaluate(cuts[sample[:share_rows]], data, low)
            share = ref.dominated_share(F_s.astype(np.float64),
                                        CV_s.astype(np.float64), *space[key])
            worst["pop_dominated_pct"] = max(worst["pop_dominated_pct"],
                                             float(np.median(share)))
        mism = ref.front_mismatches(nsga.X, nsga.F, nsga.CV, nsga.pareto_idx)
        rows = np.union1d(sample, nsga.pareto_idx)
        F_ref, CV_ref = ref.evaluate(cuts[rows], data)
        if control:
            F_got, CV_got = ref.evaluate(cuts[rows], data, ml_dtypes.bfloat16)
        else:
            F_got, CV_got = nsga.F[rows], nsga.CV[rows]
        dev = ref.evaluation_gap(F_got, CV_got, F_ref, CV_ref)
        pc = np.array([e.cuts for e in r.pareto], dtype=np.int64)
        F_ref, CV_ref = ref.evaluate(pc, data)
        if control:
            F_got, CV_got = ref.evaluate(pc, data, np.float32)
        else:
            F_got = np.array([[e.latency_s, e.energy_j, -e.throughput]
                              for e in r.pareto])
            CV_got = np.array([e.violation for e in r.pareto])
        host = ref.evaluation_gap(F_got, CV_got, F_ref, CV_ref)
        worst["front_mismatches"] = max(worst["front_mismatches"], mism)
        worst["device_eval_gap"] = max(worst["device_eval_gap"], dev)
        worst["host_rescore_gap"] = max(worst["host_rescore_gap"], host)
    return worst


def layer_inputs(st: State, res: Dict) -> Dict:
    """What the per-layer readers of this kind read besides the trace."""
    t = st.work["traffic"]
    return {"searches": res["searches"], "pop": int(t["pop"]),
            "n_gen": int(t["n_gen"]), "m": len(st.cfg["objectives"]),
            "platforms": len(st.cfg["platforms"]),
            "links": len(st.cfg["links"])}
