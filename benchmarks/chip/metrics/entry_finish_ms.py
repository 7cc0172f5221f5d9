"""Search entry after the device, in ms: mean over the window's searches of
the time from the end of the program's ``search/device`` span to the end of
its ``search/entry`` span (front mask, float64 re-score, pool selection and,
for a re-partition, the warm-front carry), on the device trace's clock."""

from benchmarks.chip import progtrace


def read(run):
    pt = progtrace.view(run)
    pairs = pt.entries() if pt is not None else []
    if not pairs:
        return None
    return 1e3 * sum(e.end - d.end for e, d in pairs) / len(pairs)
