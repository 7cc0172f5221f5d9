"""The search's own instrumentation: host phases on the profiler's clock,
named device scopes and kernels, and the compiled loop's counts.

* ``explore_graph`` and ``OnlineRepartitioner.update`` record their host
  phases as ``search/...`` spans (``repro.obs.phase``) that a profiler
  trace shows, nested and in order, covering the entry;
* the runner's peeling-pass count equals the fronts a NumPy reference
  peels, on the dense and the tiled path, and its generation count is the
  budget it ran;
* the compiled runner's HLO names every generation phase and both kernels.
"""

import glob
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import nsga2_jax
from repro.core.nsga2 import fast_non_dominated_sort
from repro.explore import (ExplorationSpec, ModelRef, OnlineRepartitioner,
                           PlatformSpec, SearchSettings, SystemSpec,
                           degrade_link)
from repro.explore.runner import explore_graph
from repro.obs import NOOP_OBS, Obs, phase

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.chip import progtrace  # noqa: E402

SYSTEM = SystemSpec(platforms=(PlatformSpec("EYR0", "eyr", bits=16),
                               PlatformSpec("SMB0", "smb", bits=8)),
                    links=("gige",))
SETTINGS = SearchSettings(strategy="jit_nsga2", seed=0, pop_size=64, n_gen=2)
SPEC = ExplorationSpec(model=ModelRef("cnn", "squeezenet11", {"in_hw": 64}),
                       system=SYSTEM, objectives=("latency", "energy"),
                       search=SETTINGS)

SEARCH_CHILDREN = ["search/evaluator", "search/candidates",
                   "search/baselines", "search/tables", "search/init",
                   "search/device", "search/front", "search/rescore",
                   "search/select"]
UPDATE_CHILDREN = SEARCH_CHILDREN + ["search/warm_carry"]


# -- host phases on the profiler's clock --------------------------------------

@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """One explore_graph and two warm updates under the profiler, after a
    warm-up that compiles the shared runner; -> the spans in the trace."""
    graph, _ = SPEC.model.build()
    system = SYSTEM.build()
    rep = OnlineRepartitioner(SPEC)
    explore_graph(graph, system, search=SETTINGS)
    rep.update(SYSTEM)
    d = str(tmp_path_factory.mktemp("trace"))
    jax.profiler.start_trace(d)
    try:
        res = explore_graph(graph, system, search=SETTINGS)
        decisions = [rep.update(degrade_link(SYSTEM, 0, f))
                     for f in (2.0, 4.0)]
    finally:
        jax.profiler.stop_trace()
    files = glob.glob(os.path.join(d, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    assert len(files) == 1
    pt = progtrace.load(files[0], (float("-inf"), float("inf")))
    return pt, res, decisions


def _entries(pt):
    return [s for s in pt.spans if s.name == progtrace.ENTRY]


def _children(pt, entry):
    return [s for s in pt.spans if s.name != progtrace.ENTRY
            and s.start >= entry.start and s.end <= entry.end]


def test_each_call_is_one_entry_with_its_device_span(traced):
    pt, _, _ = traced
    entries = _entries(pt)
    assert len(entries) == 3
    assert len(pt.entries()) == 3
    for (e, d), want in zip(pt.entries(), entries):
        assert e is want and d.name == progtrace.DEVICE
    # no span outside an entry
    assert all(any(e.start <= s.start and s.end <= e.end for e in entries)
               for s in pt.spans)


def test_children_in_order_and_disjoint(traced):
    pt, _, _ = traced
    for e, want in zip(_entries(pt), [SEARCH_CHILDREN] + [UPDATE_CHILDREN] * 2):
        kids = _children(pt, e)
        assert [k.name for k in kids] == want
        for a, b in zip(kids, kids[1:]):
            assert a.end <= b.start


def test_children_cover_the_entry(traced):
    pt, _, _ = traced
    for e in _entries(pt):
        covered = sum(k.dur for k in _children(pt, e))
        # what is left: the runner-cache lookup and the decision's
        # bookkeeping, well under a few ms
        assert e.dur - covered < max(0.005, 0.05 * e.dur), (e.dur, covered)


def test_results_carry_the_loops_counts(traced):
    _, res, decisions = traced
    for r in [res] + [d.result for d in decisions]:
        assert r.counts["generations"] == SETTINGS.n_gen
        assert r.counts["peel_passes"] >= SETTINGS.n_gen
        assert r.n_evaluated == SETTINGS.pop_size * (SETTINGS.n_gen + 1)


def test_phase_records_on_a_live_handle_only():
    obs = Obs.on()
    with phase("search/x", obs):
        with phase("search/y"):
            pass
    with phase("search/z", NOOP_OBS):
        pass
    spans = obs.tracer.spans()
    assert [(s.name, s.track) for s in spans] == [("search/x", "search/host")]


def test_phase_records_even_when_the_body_raises():
    obs = Obs.on()
    with pytest.raises(ValueError):
        with phase("search/x", obs):
            raise ValueError("boom")
    assert [s.name for s in obs.tracer.spans()] == ["search/x"]


# -- the compiled loop's counts ------------------------------------------------

LO, HI, POP = 0, 15, 64
# a tight sum limit: most of a population is infeasible, so the tiled path
# runs out of feasible fronts before the cap and the dense path does not
LIMIT = 10.0


def _eval_np(X):
    X = np.asarray(X, dtype=np.float64)
    F = np.stack([X[:, 0], HI - X[:, 1] + X[:, 0] % 3], axis=1)
    return F, np.maximum(X.sum(axis=1) - LIMIT, 0.0)


def _recording_eval(seen):
    """A jittable evaluation with integer-valued objectives (exact in
    float32) that hands every population it scores to ``seen``."""
    def eval_fn(X):
        jax.debug.callback(lambda x: seen.append(np.asarray(x)), X,
                           ordered=True)
        Xf = X.astype(jnp.float32)
        F = jnp.stack([Xf[:, 0], HI - Xf[:, 1] + X[:, 0] % 3], axis=1)
        return F.astype(jnp.float32), jnp.maximum(Xf.sum(axis=1) - LIMIT, 0.0)
    return eval_fn


def ref_passes(X, cap, feasible_only):
    """Fronts the peeling loop runs through, from the NumPy sort: fronts in
    order until ``cap`` individuals are ranked (the tiled path peels only
    the feasible ones, which come first)."""
    F, CV = _eval_np(X)
    done = passes = 0
    for front in fast_non_dominated_sort(F, CV):
        if done >= cap or (feasible_only and (CV[front] > 0).all()):
            break
        passes += 1
        done += len(front)
    return passes


@pytest.mark.parametrize("rank_block", [0, 32], ids=["dense", "tiled_ref"])
def test_peel_passes_match_numpy_fronts(rank_block):
    seen = []
    run = nsga2_jax.make_jit_runner(_recording_eval(seen), n_var=2, lower=LO,
                                    upper=HI, pop_size=POP,
                                    rank_block=rank_block, rank_impl="ref")
    X0 = np.random.default_rng(5).integers(LO, HI + 1, size=(POP, 2))
    key = jax.random.PRNGKey(11)
    X1, _, _, _, c1 = run(key, jnp.asarray(X0, jnp.int32), 1)
    jax.effects_barrier()
    parents0, offspring1 = seen[0], seen[1]
    seen.clear()
    _, _, _, _, c2 = run(key, jnp.asarray(X0, jnp.int32), 2)
    jax.effects_barrier()
    assert (seen[1] == offspring1).all()
    offspring2 = seen[2]
    tiled = rank_block > 0
    want1 = ref_passes(np.concatenate([parents0, offspring1]), POP, tiled)
    want2 = want1 + ref_passes(np.concatenate([np.asarray(X1), offspring2]),
                               POP, tiled)
    assert want1 >= 1
    assert (int(c1["generations"]), int(c1["peel_passes"])) == (1, want1)
    assert (int(c2["generations"]), int(c2["peel_passes"])) == (2, want2)


def test_jit_nsga2_fills_counts_and_restarts_count_each():
    def eval_fn(X):
        Xf = X.astype(jnp.float32)
        return (jnp.stack([Xf[:, 0], -Xf[:, 1]], axis=1),
                jnp.zeros(X.shape[0], jnp.float32))

    args = dict(n_var=2, lower=LO, upper=HI, pop_size=32, n_gen=3)
    counts = {}
    X, _, _ = nsga2_jax.jit_nsga2(eval_fn, seed=1, counts=counts, **args)
    assert counts["generations"] == 3 and counts["peel_passes"] >= 3
    assert X.shape == (32, 2)
    rc = {}
    nsga2_jax.jit_nsga2_restarts(eval_fn, n_restarts=2, seed=1, counts=rc,
                                 **args)
    assert rc["generations"] == [3, 3]
    # restart 0 is the single run with the same seed
    assert rc["peel_passes"][0] == counts["peel_passes"]


# -- names in the compiled program ----------------------------------------------

def _op_names(text):
    return set(re.findall(r'op_name="([^"]*)"', text))


def test_runner_hlo_names_every_phase_and_kernel():
    def eval_fn(X):
        Xf = X.astype(jnp.float32)
        return (jnp.stack([Xf[:, 0], HI - Xf[:, 1]], axis=1),
                jnp.maximum(Xf.sum(axis=1) - 20.0, 0.0))

    run = nsga2_jax.make_jit_runner(eval_fn, n_var=2, lower=LO, upper=HI,
                                    pop_size=256, rank_block=256,
                                    rank_impl="pallas")
    names = _op_names(run.lower(jax.random.PRNGKey(0),
                                jnp.zeros((256, 2), jnp.int32), 2)
                      .compile().as_text())
    in_loop = {progtrace.phase_of(n) for n in names} - {None}
    assert in_loop == set(progtrace.PHASES)
    assert any(n.startswith("jit(run)/init/") for n in names)
    assert any("packed_domination" in n.split("/") for n in names)

