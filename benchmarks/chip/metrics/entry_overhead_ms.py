"""Search entry overhead, in ms: mean over the window's searches of the
``explore_graph`` call's wall on the benchmark's clock minus that call's own
``search_wall_s`` observation in the program's default metrics registry
(the compiled search and its ``jit_nsga2`` bookkeeping).  What is left is
the entry's host work: evaluator and table export, candidate filtering,
baselines, the final front's re-score and the Def.-2 selection."""


def read(run):
    searches = run.get("searches")
    if not searches:
        return None
    return 1e3 * sum(s.wall_s - s.program_wall_s
                     for s in searches) / len(searches)
