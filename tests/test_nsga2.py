"""NSGA-II invariants + convergence on a known discrete front."""

import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core.nsga2 import (crowding_distance, dominates,
                              fast_non_dominated_sort, front_of_rows, nsga2,
                              pareto_indices)


def test_dominates():
    assert dominates(np.array([1, 1]), np.array([2, 2]))
    assert dominates(np.array([1, 2]), np.array([2, 2]))
    assert not dominates(np.array([1, 3]), np.array([2, 2]))
    assert not dominates(np.array([2, 2]), np.array([2, 2]))


@given(st.lists(st.tuples(st.floats(0, 10), st.floats(0, 10)),
                min_size=2, max_size=40))
@settings(max_examples=50, deadline=None)
def test_front0_mutually_nondominating(pts):
    F = np.array(pts)
    fronts = fast_non_dominated_sort(F)
    f0 = fronts[0]
    for i in f0:
        for j in f0:
            assert not dominates(F[i], F[j])


@given(st.lists(st.tuples(st.floats(0, 10), st.floats(0, 10)),
                min_size=3, max_size=30))
@settings(max_examples=40, deadline=None)
def test_domination_implies_earlier_front(pts):
    F = np.array(pts)
    fronts = fast_non_dominated_sort(F)
    rank = {}
    for r, fr in enumerate(fronts):
        for i in fr:
            rank[int(i)] = r
    n = len(F)
    for i in range(n):
        for j in range(n):
            if dominates(F[i], F[j]):
                assert rank[i] < rank[j]


def test_crowding_boundary_infinite():
    F = np.array([[0.0, 3.0], [1.0, 2.0], [2.0, 1.0], [3.0, 0.0]])
    d = crowding_distance(F)
    assert np.isinf(d[0]) and np.isinf(d[3])
    assert np.isfinite(d[1]) and np.isfinite(d[2])


def test_nsga2_converges_discrete_front():
    """min (x/50, (50-x)/50) over integers: whole range is the true front;
    NSGA-II must find a spread of non-dominated points + respect constraint
    x >= 10."""
    def evaluate(X):
        x = X[:, 0].astype(float)
        F = np.stack([x / 50.0, (50.0 - x) / 50.0], axis=1)
        CV = np.maximum(0.0, 10.0 - x) / 10.0
        return F, CV

    res = nsga2(evaluate, n_var=1, lower=0, upper=50, pop_size=24,
                n_gen=30, seed=1)
    xs = res.pareto_X[:, 0]
    assert (xs >= 10).all()
    assert len(np.unique(xs)) >= 5       # decent spread
    # all returned points feasible & mutually non-dominating
    F, CV = evaluate(res.pareto_X)
    assert (CV <= 0).all()


def test_nsga2_multi_cut_sorted():
    def evaluate(X):
        F = np.stack([X.sum(1).astype(float), (X.max(1) - X.min(1)).astype(float)],
                     axis=1)
        return F, np.zeros(len(X))
    res = nsga2(evaluate, n_var=3, lower=0, upper=20, pop_size=16, n_gen=10,
                seed=0)
    for x in res.X:
        assert list(x) == sorted(x)


# -- merged restart fronts ----------------------------------------------------

def _restart_rows(n_restarts, per_restart, dup, threshold, seed):
    """A flattened restart population of 2 * per_restart rows a restart and
    ``per_restart`` ascending rows picked from each.  Three genes over
    0..39; integer objectives (ties on F for distinct vectors) and a
    violation ``v - threshold`` over v in 0..9 that depend on the vector
    alone, so copies share F and CV as in a search.  ``dup`` draws every
    restart from one pool of per_restart // 8 vectors."""
    rng = np.random.default_rng(seed)
    n = 2 * n_restarts * per_restart
    if dup:
        pool = rng.integers(0, 40, size=(per_restart // 8, 3))
        X = pool[rng.integers(0, len(pool), n)]
    else:
        X = np.stack(np.unravel_index(
            rng.choice(40 ** 3, n, replace=False), (40, 40, 40)), axis=1)
    F = np.stack([X[:, 0] + X[:, 1], 39 - X[:, 0] + X[:, 2],
                  78 - X[:, 1] - X[:, 2] + X[:, 0] * X[:, 2] % 7],
                 axis=1).astype(float)
    CV = np.maximum(X @ np.array([7, 3, 5]) % 10 - threshold, 0.0)
    rows = np.concatenate([
        2 * per_restart * r
        + np.sort(rng.choice(2 * per_restart, per_restart, replace=False))
        for r in range(n_restarts)])
    return X, F, CV, rows


@pytest.mark.parametrize("per_restart", [64, 600])
@pytest.mark.parametrize("n_restarts", [2, 3])
@pytest.mark.parametrize("threshold", [10.0, 4.5, -1.0],
                         ids=["all_feasible", "some_feasible", "none_feasible"])
@pytest.mark.parametrize("dup", [False, True], ids=["distinct", "copies"])
def test_front_of_rows_equals_front_of_all_rows(dup, threshold, n_restarts,
                                                per_restart):
    """Sorting only the distinct vectors among ``rows`` gives exactly the
    indices, in order, of ``pareto_indices`` over every row."""
    X, F, CV, rows = _restart_rows(n_restarts, per_restart, dup, threshold,
                                   seed=n_restarts * 1000 + per_restart)
    feas = CV[rows] <= 0
    assert {10.0: feas.all(), -1.0: not feas.any()}.get(
        threshold, 0 < feas.sum() < len(rows))
    n_distinct = len(np.unique(X[rows], axis=0))
    assert (n_distinct <= per_restart // 8) if dup else \
        (n_distinct == len(rows))
    want = rows[pareto_indices(X[rows], F[rows], CV[rows])]
    got = front_of_rows(X, F, CV, rows)
    assert got.dtype == want.dtype and np.array_equal(got, want)
