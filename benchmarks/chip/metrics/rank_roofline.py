"""Roofline share of the Pallas ranking kernels, in %.

Device time: every ``packed_domination`` and ``domination_counts`` kernel
call in the traced window.  Work (``costs``): per search, one packed
domination of the initial population and one per generation of the combined
population, and one dominator count of the final population.  The roofline
time is the larger of operations over ``peaks.json``'s bf16 matrix peak and
bytes over HBM bandwidth.  The kernels' compares and popcounts run on the
vector unit, whose peak is far lower and has no public figure in the table:
the operation term is therefore taken too low, and the share reads low (it
is a lower bound), never above what a vector-unit peak would give."""

from benchmarks.chip import costs
from benchmarks.chip.searchtrace import KERNELS, runner_executions


def read(run):
    tr = run["trace"]
    runs = runner_executions(run)
    if not runs:
        return None
    t = tr.op_seconds(lambda n: any(k in n.split(" = ", 1)[0]
                                    for k in KERNELS))
    if t <= 0:
        return None
    pop, m, n_gen = run["pop"], run["m"], run["n_gen"]
    per_search = (costs.packed_domination(pop, m)
                  + costs.packed_domination(2 * pop, m) * n_gen
                  + costs.domination_counts(pop, m))
    return 100.0 * costs.roofline_s(per_search, run["peaks"]) * len(runs) / t
