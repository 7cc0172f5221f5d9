"""Device time of a generation's offspring evaluation, in ms: the time in
which an operation under the compiled loop's ``evaluate`` scope ran on the
device inside the runner's executions in the traced window, over the
generations the program counted it ran (``ExplorationResult.counts``)."""

from benchmarks.chip import progtrace


def read(run):
    return progtrace.phase_ms_per_generation(run, "evaluate")
