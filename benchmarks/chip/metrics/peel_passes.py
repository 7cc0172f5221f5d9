"""Peeling passes a generation: the compiled loop's own count of iterations
of its ``rank/peel`` while-loop, summed over the window's searches, over
the generations it counted (``ExplorationResult.counts``).  A count, the
same for the same seed."""

from benchmarks.chip import progtrace


def read(run):
    gens = progtrace.generations(run)
    if not gens:
        return None
    return sum(int(s.result.counts["peel_passes"])
               for s in run["searches"]) / gens
