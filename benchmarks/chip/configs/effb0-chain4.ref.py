"""Plain reference of the partition search's answers (effb0-chain4).

Imports nothing of the program: NumPy and the standard library only.  Its
data are the deployment's per-layer rows, taken once in set-up: each
layer's parameters, activation footprint and output size, the graph's
edges, the platforms' bit widths and memory capacities, the links'
parameters, and the latency and energy of each layer on each accelerator.
Those last rows, and the gene values (the candidate cut positions), are
inputs shared with the program: they come from its hardware model and its
candidate filter, so this reference checks the search and its evaluation,
not the hardware model.  None of the program's prefix sums, tables or
compiled functions is used.

Semantics, from the paper (Defs. 1-4) as the configuration states them:

* a cut vector ``c`` (sorted, ``-1`` = platform skipped, ``L-1`` = nothing
  after) puts schedule positions ``c[k-1]+1 .. c[k]`` on platform ``k``;
* a stage's latency and energy are the sums over its layers;
* a link between stage ``k`` and ``k+1`` carries the outputs of layers at or
  before the cut that a later layer consumes, at the producer's bit width,
  when both sides run something: ``t = setup + (bytes + packets * header) *
  8 / rate``, ``e = (p_tx + p_rx) * t + e_byte * bytes``;
* latency = stages + links; throughput = 1 / slowest active module;
* memory of a stage (Def. 3) = (parameters + largest activation footprint *
  batch) * bits / 8, floored; the violation is the summed relative excess
  over each platform's capacity;
* objectives (latency, energy, -throughput), minimised; constrained
  domination as Deb's.

The search is held to its whole space: every sorted cut vector over the
search's gene values (``all_cut_vectors``), evaluated here in float64; a
row the search returns is scored by the share of the space that dominates
it (``dominated_share``).
"""

from __future__ import annotations

import itertools

import numpy as np

OBJECTIVES = ("latency", "energy", "throughput")


def cut_elements(n_layers, edges, out_elems):
    """Elements crossing a cut after each position ``p < L - 1``: outputs of
    layers at or before ``p`` that a layer after ``p`` consumes (each
    producer once)."""
    out = []
    for p in range(n_layers - 1):
        live = {u for u, v in edges if u <= p < v}
        out.append(sum(out_elems[u] for u in live))
    return out


def evaluate(cuts, data, dtype=np.float64):
    """Objectives ``(R, 3)`` and violation ``(R,)`` of ``R`` cut vectors,
    every quantity computed in ``dtype``."""
    C = np.asarray(cuts, dtype=np.int64)
    R, K = C.shape
    L = len(data["params"])
    P = K + 1
    B = np.concatenate([np.full((R, 1), -1), C, np.full((R, 1), L - 1)],
                       axis=1)

    def t(x):
        return np.asarray(x, dtype=dtype)

    stage_lat = np.zeros((R, P), dtype=dtype)
    energy = np.zeros(R, dtype=dtype)
    par = np.zeros((R, P), dtype=dtype)
    peak = np.zeros((R, P), dtype=dtype)
    for k in range(P):
        a, b = B[:, k] + 1, B[:, k + 1]
        for layer in range(L):
            on = (a <= layer) & (layer <= b)
            stage_lat[:, k] = np.where(on, stage_lat[:, k]
                                       + t(data["lat"][k][layer]),
                                       stage_lat[:, k])
            energy = np.where(on, energy + t(data["energy"][k][layer]), energy)
            par[:, k] = np.where(on, par[:, k] + t(data["params"][layer]),
                                 par[:, k])
            peak[:, k] = np.where(on, np.maximum(peak[:, k],
                                                 t(data["act"][layer])),
                                  peak[:, k])

    link_lat = np.zeros((R, K), dtype=dtype)
    elems = data["cut_elems"]
    for k in range(K):
        lk = data["links"][k]
        p = C[:, k]
        sent = B[:, k + 1] > B[:, k]
        remaining = B[:, -1] > B[:, k + 1]
        active = (p >= 0) & (p < L - 1) & sent & remaining
        e = t([elems[int(min(max(q, 0), L - 2))] for q in p])
        nbytes = np.where(active, np.ceil(e * t(data["bits"][k] / 8.0))
                          * t(data["batch"]), t(0.0))
        packets = np.ceil(nbytes / t(lk["payload_bytes"]))
        wire = (nbytes + packets * t(lk["header_bytes"])) * t(8.0)
        lat = np.where(nbytes > 0, t(lk["t_setup_s"]) + wire
                       / t(lk["rate_bps"]), t(0.0))
        link_lat[:, k] = lat
        energy = energy + np.where(
            nbytes > 0, t(lk["p_tx_w"] + lk["p_rx_w"]) * lat
            + t(lk["e_per_byte_j"]) * nbytes, t(0.0))

    latency = stage_lat.sum(axis=1) + link_lat.sum(axis=1)
    mods = np.concatenate([stage_lat, link_lat], axis=1)
    slowest = np.where(mods > 0, mods, t(0.0)).max(axis=1)
    throughput = np.where(slowest > 0, t(1.0) / np.where(slowest > 0, slowest,
                                                         t(1.0)), t(0.0))

    cv = np.zeros(R, dtype=dtype)
    for k in range(P):
        bpe = t(data["bits"][k] / 8.0)
        mem = np.floor(par[:, k] * bpe + peak[:, k] * t(data["batch"]) * bpe)
        cap = t(data["capacity"][k])
        cv = cv + np.where(mem > cap, (mem - cap) / cap, t(0.0))
    F = np.stack([latency, energy, -throughput], axis=1)
    return F, cv


def dominates(Fa, cva, Fb, cvb):
    """Deb's constrained domination of row(s) ``a`` over row(s) ``b``."""
    feas_a, feas_b = cva <= 0, cvb <= 0
    dom = np.all(Fa <= Fb, axis=-1) & np.any(Fa < Fb, axis=-1)
    return np.where(feas_a & ~feas_b, True,
                    np.where(feas_b & ~feas_a, False,
                             np.where(~feas_a & ~feas_b, cva < cvb, dom)))


def all_cut_vectors(values, k):
    """Every sorted vector of ``k`` cuts drawn, with repeats, from the gene
    values ``values`` (ascending): the whole space the search explores."""
    idx = np.array(list(itertools.combinations_with_replacement(
        range(len(values)), k)), dtype=np.int64)
    return np.asarray(values, dtype=np.int64)[idx]


def dominated_share(F, CV, F_all, CV_all, chunk=64):
    """For each row of ``(F, CV)``, the share of the rows of ``(F_all,
    CV_all)`` (the whole space) that dominate it, in %."""
    F, CV = np.asarray(F), np.asarray(CV)
    out = []
    for i in range(0, len(F), chunk):
        d = dominates(F_all[None, :, :], CV_all[None, :],
                      F[i:i + chunk, None, :], CV[i:i + chunk, None])
        out.append(100.0 * d.mean(axis=1))
    return np.concatenate(out) if out else np.zeros(0)


def front_mismatches(X, F, CV, front):
    """Rows by which a claimed first front of the population ``(X, F, CV)``
    differs from the true one: claimed rows that some row dominates, plus
    rows outside the claim (by decision vector) that no claimed row
    dominates.  0 iff the claim is the whole non-dominated set."""
    X = np.asarray(X)
    F = np.asarray(F, np.float64)
    CV = np.asarray(CV, np.float64)
    front = np.asarray(front, np.int64)
    bad = 0
    for i in front:
        if dominates(F, CV, F[i][None, :], np.asarray([CV[i]])).any():
            bad += 1
    claimed = {tuple(x) for x in X[front]}
    covered = np.zeros(len(F), dtype=bool)
    for i in front:
        covered |= dominates(F[i][None, :], np.asarray([CV[i]]), F, CV)
    for r in np.flatnonzero(~covered):
        if tuple(X[r]) not in claimed:
            bad += 1
    return bad


def relative_gap(got, want):
    """Largest ``|got - want| / |want|`` over all entries (``|got - want|``
    where ``want`` is 0)."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    scale = np.where(want == 0, 1.0, np.abs(want))
    gap = np.abs(got - want) / scale
    return float(gap.max()) if gap.size else 0.0


def evaluation_gap(F, CV, F_ref, CV_ref):
    """The widest gap of an evaluation: relative over the objectives, and
    absolute over the violation, which is already a share of capacity."""
    cv = np.abs(np.asarray(CV, np.float64) - np.asarray(CV_ref, np.float64))
    return max(relative_gap(F, F_ref), float(cv.max()) if cv.size else 0.0)
