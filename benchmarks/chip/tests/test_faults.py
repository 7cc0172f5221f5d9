"""A run whose timed path is broken underneath reads ``correct: false``.

Each test skips the harness's look for a chip and drives the rest of a run
on the CPU at a small size, with the cell's own limits, and plants one fault
in the program where it produces its answer.  A sound run at the same size
reads ``correct: true``.
"""

import pytest

from benchmarks.chip import layout
from benchmarks.chip import run as bench

SEED = 2 ** 33 + 17
SEARCH = "search-effb0-pop32k"
DRIFT = "drift-effb0-pop2k"


def small_search():
    cfg = layout.config("effb0-chain4")
    cfg["in_hw"] = 64
    work = layout.workload(SEARCH)
    work["traffic"].update(pop=512, n_gen=10, rank_block=256, sample_rows=256,
                           quality_checks=2)
    return cfg, work


def small_drift():
    cfg = layout.config("effb0-chain4")
    cfg["in_hw"] = 64
    work = layout.workload(DRIFT)
    work["traffic"].update(pop=256, n_gen=12, sample_rows=128, pool=12,
                           quality_checks=2)
    return cfg, work


def run_small(cell, make, seconds=2.0):
    cfg, work = make()
    result, compared, _ = bench.run_cell(cell, SEED, seconds, False,
                                         require_tpu=False, cfg=cfg, work=work)
    return result


# -- search -------------------------------------------------------------------

def test_search_sound_run_is_correct():
    assert run_small(SEARCH, small_search)["correct"] is True


def _wrap_runner_output(monkeypatch, alter):
    import repro.core.nsga2_jax as nj
    orig = nj.jit_nsga2

    def broken(*a, **kw):
        X, F, CV = orig(*a, **kw)
        return alter(X, F.copy(), CV.copy())

    monkeypatch.setattr(nj, "jit_nsga2", broken)


def test_drift_sound_run_is_correct():
    assert run_small(DRIFT, small_drift)["correct"] is True


def test_drift_objectives_altered(monkeypatch):
    def alter(X, F, CV):
        F[:, 1] *= 0.99
        return X, F, CV
    _wrap_runner_output(monkeypatch, alter)
    assert run_small(DRIFT, small_drift)["correct"] is False


def test_search_objectives_altered(monkeypatch):
    def alter(X, F, CV):
        F[:, 0] *= 1.01
        return X, F, CV
    _wrap_runner_output(monkeypatch, alter)
    r = run_small(SEARCH, small_search)
    assert r["correct"] is False
    assert r["checks"]["device_eval_gap"]["value"] > 0.005


def test_search_half_the_population_left_out(monkeypatch):
    def alter(X, F, CV):
        F[len(F) // 2:] = F[: len(F) - len(F) // 2]
        return X, F, CV
    _wrap_runner_output(monkeypatch, alter)
    assert run_small(SEARCH, small_search)["correct"] is False


def test_search_front_member_dropped(monkeypatch):
    import repro.explore.strategies as strat
    orig = strat.pareto_indices

    def broken(X, F, CV):
        return orig(X, F, CV)[1:]

    monkeypatch.setattr(strat, "pareto_indices", broken)
    r = run_small(SEARCH, small_search)
    assert r["correct"] is False
    assert r["checks"]["front_mismatches"]["value"] >= 1


# -- the generation loop -------------------------------------------------------

def _invert(f):
    def broken(*a, **kw):
        return ~f(*a, **kw)
    return broken


def _fails_on_population(r):
    """Only the population's dominated share sees these faults."""
    check = r["checks"]["pop_dominated_pct"]
    return r["correct"] is False and check["value"] > check["limit"]


@pytest.mark.parametrize("cell,make", [(SEARCH, small_search),
                                       (DRIFT, small_drift)])
def test_search_state_left_unchanged(cell, make):
    """The compiled search returns its initial population."""
    from benchmarks.chip import control
    with control.state_unchanged():
        assert _fails_on_population(run_small(cell, make))


def test_search_packed_domination_altered(monkeypatch):
    """The tiled ranking kernel's bits come out inverted."""
    import repro.kernels.ops as ops
    monkeypatch.setattr(ops, "packed_domination",
                        _invert(ops.packed_domination))
    assert _fails_on_population(run_small(SEARCH, small_search))


def test_drift_domination_matrix_altered(monkeypatch):
    """The dense ranking's domination matrix comes out inverted."""
    import repro.core.nsga2_jax as nj
    monkeypatch.setattr(nj, "domination_matrix",
                        _invert(nj.domination_matrix))
    assert _fails_on_population(run_small(DRIFT, small_drift))


@pytest.mark.parametrize("cell,make", [(SEARCH, small_search),
                                       (DRIFT, small_drift)])
def test_control_reads_not_correct(cell, make):
    """The reference at the next lower precision, in the program's place,
    and the search left unchanged each fail at least one of the cell's
    limits, where the program keeps to all."""
    from benchmarks.chip import control
    cfg, work = make()
    r = control.readings(cell, SEED, 2.0, require_tpu=False, cfg=cfg,
                         work=work)
    limits = work["limits"]
    assert all(v <= limits[k] for k, v in r["program"].items()), r
    for bad in ("control", "state_unchanged"):
        assert any(v > limits[k] for k, v in r[bad].items()), (bad, r)
