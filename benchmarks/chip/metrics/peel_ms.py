"""Device time of a generation's front peeling, in ms: the time in which an
operation under the compiled loop's ``rank/peel`` scope (the popcount
while-loop) ran on the device inside the runner's executions in the traced
window, over the generations the program counted it ran."""

from benchmarks.chip import progtrace


def read(run):
    return progtrace.phase_ms_per_generation(run, "rank/peel")
