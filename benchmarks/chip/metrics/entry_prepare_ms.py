"""Search entry before the device, in ms: mean over the window's searches of
the time from the start of the program's ``search/entry`` span to the start
of its ``search/device`` span (evaluator build, candidate filter, baselines,
table export, initial population), on the device trace's clock."""

from benchmarks.chip import progtrace


def read(run):
    pt = progtrace.view(run)
    pairs = pt.entries() if pt is not None else []
    if not pairs:
        return None
    return 1e3 * sum(d.start - e.start for e, d in pairs) / len(pairs)
