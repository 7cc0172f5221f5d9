"""Whole-generation share of the chip's peak, in %: the least time the
generation's counted work needs (``costs.search_generation``: the offspring's
evaluation and the packed domination of the combined population, read once
by the peeling), as the larger of operations over the bf16 peak and bytes
over HBM bandwidth, over the measured device time of a generation.  The
work is compares, popcounts and gathers on the vector unit, not matrix
products, so against the matrix peak the share reads low (see
``rank_roofline``)."""

from benchmarks.chip import costs
from benchmarks.chip.searchtrace import runner_executions


def read(run):
    runs = runner_executions(run)
    if not runs:
        return None
    work = costs.search_generation(run["pop"], run["m"], run["platforms"],
                                   run["links"])
    need = costs.roofline_s(work, run["peaks"]) * run["n_gen"] * len(runs)
    return 100.0 * need / sum(e.dur for e in runs)
