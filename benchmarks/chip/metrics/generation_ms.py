"""Device time of one generation of the compiled search, in ms: the
executions of the runner program (``jit_run``) in the traced window, over
the generations they ran (each execution also ranks its initial
population once)."""

from benchmarks.chip.searchtrace import runner_executions


def read(run):
    runs = runner_executions(run)
    if not runs:
        return None
    return 1e3 * sum(e.dur for e in runs) / (len(runs) * run["n_gen"])
