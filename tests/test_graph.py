"""Graph IR: topo sort, clean cuts, live sets, branch regions."""

import functools
import math
import time

import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core import layers as L
from repro.core.graph import GraphError, LayerGraph, linearize
from repro.core.partition import PartitionEvaluator
from repro.explore import PlatformSpec, SystemSpec
from repro.models.cnn.zoo import CNN_ZOO, build_cnn


def chain_graph(n=5):
    g = LayerGraph(name="chain")
    layers = [L.elementwise_layer(f"l{i}", L.RELU, (4, 8, 8)) for i in range(n)]
    g.chain(layers)
    return g


def diamond_graph():
    g = LayerGraph(name="diamond")
    g.add(L.conv_layer("a", 3, 8, (8, 8), 3))
    g.add(L.conv_layer("b1", 8, 8, (8, 8), 3), after=["a"])
    g.add(L.conv_layer("b2", 8, 16, (8, 8), 3), after=["a"])
    g.add(L.concat_layer("c", [(8, 8, 8), (16, 8, 8)]), after=["b1", "b2"])
    g.add(L.elementwise_layer("d", L.RELU, (24, 8, 8)), after=["c"])
    return g


def test_topo_sort_chain():
    g = chain_graph()
    order = [l.name for l in g.topo_sort()]
    assert order == [f"l{i}" for i in range(5)]


def test_topo_sort_detects_cycle():
    g = chain_graph(3)
    g.edges.append(("l2", "l0"))
    with pytest.raises(GraphError):
        g.topo_sort()


def test_clean_cuts_chain():
    g = chain_graph(5)
    sched = g.topo_sort()
    assert g.clean_cuts(sched) == [0, 1, 2, 3]


def test_clean_cuts_diamond():
    g = diamond_graph()
    sched = g.topo_sort()
    cuts = g.clean_cuts(sched)
    names = {sched[p].name for p in cuts}
    # inside the parallel branches there is no single-tensor cut
    assert names == {"a", "c"}
    # multi-tensor cuts exist inside the diamond
    all_cuts = dict(g.all_cuts(sched))
    assert any(len(v) == 2 for v in all_cuts.values())


def test_live_set_and_cut_bytes():
    g = diamond_graph()
    sched = g.topo_sort()
    pos_a = [i for i, l in enumerate(sched) if l.name == "a"][0]
    assert g.live_set(sched, pos_a) == ["a"]
    nbytes = g.cut_bytes(sched, pos_a, bytes_per_elem=2)
    assert nbytes == 8 * 8 * 8 * 2


def test_min_memory_policy_valid():
    g = diamond_graph()
    sched = linearize(g, "min_memory")
    assert g.validate_schedule(sched)


def test_random_policy_valid_and_seeded():
    g = diamond_graph()
    s1 = linearize(g, "random", seed=3)
    s2 = linearize(g, "random", seed=3)
    assert [l.name for l in s1] == [l.name for l in s2]
    assert g.validate_schedule(s1)


# -- property tests ------------------------------------------------------------

@st.composite
def random_dag(draw):
    n = draw(st.integers(3, 12))
    g = LayerGraph(name="rand")
    for i in range(n):
        preds = []
        if i > 0:
            k = draw(st.integers(1, min(3, i)))
            preds = sorted({draw(st.integers(0, i - 1)) for _ in range(k)})
        g.add(L.elementwise_layer(f"n{i}", L.RELU, (2, 4, 4)),
              after=[f"n{p}" for p in preds] or None)
    return g


@given(random_dag(), st.integers(0, 10_000))
@settings(max_examples=40, deadline=None)
def test_topo_sort_respects_edges(g, seed):
    sched = g.topo_sort(seed=seed)
    assert g.validate_schedule(sched)


@given(random_dag())
@settings(max_examples=40, deadline=None)
def test_clean_cut_live_sets_are_singletons(g):
    sched = g.topo_sort()
    for p in g.clean_cuts(sched):
        live = g.live_set(sched, p)
        assert live == [sched[p].name]


@given(random_dag())
@settings(max_examples=30, deadline=None)
def test_cut_bytes_nonnegative_and_zero_only_at_sinks(g):
    sched = g.topo_sort()
    for p in range(len(sched) - 1):
        assert g.cut_bytes(sched, p, 1.0) >= 0


# -- the linear cut sweep against the prefix-set definition -------------------
#
# The reference is the definition itself: the prefix schedule[:p+1] as a set,
# and a prefix producer is live when an edge of the edge list leads from it
# out of the prefix.  It costs O(L * E) over all positions; the graph's sweep
# must give the same answers in O(L + E).

BPES = (0.5, 1.0, 2.0)          # 4-, 8- and 16-bit links


def ref_live_set(g, schedule, p):
    prefix = {l.name for l in schedule[: p + 1]}
    return sorted({u for u, v in g.edges if u in prefix and v not in prefix})


def ref_cut_bytes(g, schedule, p, bpe):
    total = sum(g.nodes[n].fmap_out for n in ref_live_set(g, schedule, p))
    return int(math.ceil(total * bpe))


def assert_cuts_match_reference(g, schedule):
    n = len(schedule)
    lives = [ref_live_set(g, schedule, p) for p in range(n)]
    assert g.clean_cuts(schedule) == [
        p for p in range(n - 1) if lives[p] == [schedule[p].name]]
    for max_live in (1, 4, n):
        assert g.all_cuts(schedule, max_live) == [
            (p, lives[p]) for p in range(n - 1) if 0 < len(lives[p]) <= max_live]
    assert g.cut_elements(schedule) == [
        ref_cut_bytes(g, schedule, p, 1.0) for p in range(n - 1)]
    for p in range(n):
        assert g.live_set(schedule, p) == lives[p]
        for bpe in BPES:
            assert g.cut_bytes(schedule, p, bpe) == ref_cut_bytes(g, schedule, p, bpe)


@functools.lru_cache(maxsize=None)
def zoo_graph(name):
    return build_cnn(name).to_graph()


@pytest.mark.parametrize("policy", ["insertion", "min_memory", "random"])
@pytest.mark.parametrize("name", sorted(CNN_ZOO))
def test_cut_sweep_matches_prefix_definition_on_zoo(name, policy):
    g = zoo_graph(name)
    assert_cuts_match_reference(g, linearize(g, policy, seed=11))


@pytest.mark.parametrize("name", sorted(CNN_ZOO))
def test_evaluator_cut_elements_match_prefix_definition(name):
    g = zoo_graph(name)
    schedule = linearize(g, "min_memory")
    system = SystemSpec(platforms=(PlatformSpec("A0", "eyr", bits=16),
                                   PlatformSpec("B0", "smb", bits=4)),
                        links=("gige",)).build()
    ev = PartitionEvaluator(g, schedule, system)
    elems = ev.cut_elements()
    assert elems.dtype == np.int64
    assert elems.tolist() == [ref_cut_bytes(g, schedule, p, 1.0)
                              for p in range(len(schedule) - 1)]
    for p in range(len(schedule) - 1):
        for bpe in BPES:
            assert ev._cut_bytes(p, bpe) == ref_cut_bytes(g, schedule, p, bpe)


@st.composite
def random_fanout_dag(draw):
    """Several sources, sinks anywhere (nodes nobody consumes) and fan-out
    to many consumers; feature-map sizes differ so element sums do too."""
    n = draw(st.integers(2, 16))
    g = LayerGraph(name="fanout")
    for i in range(n):
        preds = draw(st.sets(st.integers(0, i - 1), max_size=4)) if i else set()
        size = draw(st.integers(1, 9))
        g.add(L.elementwise_layer(f"n{i}", L.RELU, (size, 3)),
              after=[f"n{p}" for p in sorted(preds)] or None)
    return g


@given(random_fanout_dag(), st.integers(0, 10_000))
@settings(max_examples=60, deadline=None)
def test_cut_sweep_matches_prefix_definition_on_random_dags(g, seed):
    for policy in ("insertion", "min_memory", "random"):
        assert_cuts_match_reference(g, linearize(g, policy, seed=seed))


def test_cut_sweep_on_a_partial_schedule():
    """A consumer left off the schedule never runs, so its producer stays
    live to the end, also past the schedule's last position; a name that
    repeats joins the prefix at its first position."""
    g = diamond_graph()
    schedule = [g.nodes[n] for n in ("a", "b1", "c")]
    assert_cuts_match_reference(g, schedule)
    assert g.live_set(schedule, 2) == ["a", "c"]
    assert g.live_set(schedule, -1) == []
    assert_cuts_match_reference(g, [g.nodes[n] for n in ("a", "b1", "a", "b2", "c")])


def test_cut_sweep_is_linear_on_a_long_chain():
    """20,000 layers: the sweep takes a fraction of a second, a per-position
    rescan of the prefix (quadratic or worse) takes minutes."""
    n = 20_000
    g = chain_graph(n)
    schedule = g.topo_sort()
    t0 = time.perf_counter()
    clean = g.clean_cuts(schedule)
    cuts = g.all_cuts(schedule)
    elems = g.cut_elements(schedule)
    live = g.live_set(schedule, n // 2)
    wall = time.perf_counter() - t0
    assert clean == list(range(n - 1))
    assert [p for p, _ in cuts] == clean
    assert elems == [4 * 8 * 8] * (n - 1)
    assert live == [schedule[n // 2].name]
    assert wall < 5.0, f"cut sweep of {n} layers took {wall:.2f} s"
