"""Record one traced run of a cell and print where its time went, by the
program's own spans and scopes.

    python3 benchmarks/chip/record_progtrace.py --workload <cell> \\
        --seed <n> --seconds <s> [--save <file>]

Runs the cell once traced, as ``run.py --trace 1`` does, and prints one
JSON object on the last line of standard output: ``correct``, set-up time
and compiles inside the window, the per-layer
metrics and the breakdown, the window's candidate evaluations a second (as
``run.py`` counts them, to set against an untraced run), each generation
phase's device ms a generation, the window's
device idle seconds by the innermost ``search/`` span over them (and the
share under a span below ``search/entry``), and the longest idle gaps
named the same way.  With ``--save``, the program trace, the searches'
counts and the readings of the metrics that read them go to a gzipped JSON
file; ``data/<cell>.progtrace.json.gz`` is one, which the reader tests
reduce again.
The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
for _p in (ROOT, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from benchmarks.chip import progtrace  # noqa: E402
from benchmarks.chip import run as bench  # noqa: E402

# the per-layer metrics that read the program's spans, scopes and counts
PROGRAM_METRICS = ("entry_prepare_ms", "entry_finish_ms", "evaluate_ms",
                   "peel_ms", "peel_passes")


def record(name: str, seed: int, seconds: float) -> dict:
    """One traced run; returns the breakdown and the record to save."""
    seen = {}
    per_layer = bench.per_layer

    def keep(cell, view, inputs, kind):
        # the readers run while the trace is still on disk
        out = per_layer(cell, view, inputs, kind)
        seen.update(trace=view, searches=inputs["searches"],
                    prog=progtrace.view(dict(inputs, trace=view)))
        return out

    bench.per_layer = keep
    try:
        result, _, info = bench.run_cell(name, seed, seconds, True)
    finally:
        bench.per_layer = per_layer
    trace, pt, searches = seen["trace"], seen["prog"], seen["searches"]
    counts = [s.result.counts for s in searches]
    gens = sum(c["generations"] for c in counts)
    idle = progtrace.idle_by_span(trace, pt)
    below = sum(v for k, v in idle.items()
                if k not in (progtrace.ENTRY, "none"))
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    return {
        "summary": {
            "cell": name, "seed": seed, "correct": result["correct"],
            "setup_s": info["setup_s"],
            "compiles_in_window": info["compiles_in_window"],
            "metrics": metrics, "breakdown": result["breakdown"],
            "search_evals_per_s": (
                sum(s.result.n_evaluated for s in searches)
                / max(s.start_s + s.wall_s for s in searches)),
            "phase_ms": {p: 1e3 * pt.phase_s(p) / gens
                         for p in progtrace.PHASES},
            "idle_s": idle,
            "idle_below_entry_share": below / max(sum(idle.values()), 1e-12),
            "named_gaps": progtrace.named_gaps(trace, pt),
            "counts": counts},
        "record": {
            "cell": name, "seed": seed, "window": list(trace.window),
            "progtrace": pt.to_json(), "counts": counts,
            "metrics": {k: metrics[k] for k in PROGRAM_METRICS
                        if k in metrics}},
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--save", default=None, metavar="FILE")
    args = ap.parse_args(argv)
    try:
        out = record(args.workload, args.seed, args.seconds)
    except bench.NoChip as e:
        print(f"record_progtrace.py: {e}", file=sys.stderr)
        return 2
    if args.save:
        progtrace.save(args.save, out["record"])
    print(json.dumps(out["summary"]))
    return 0


if __name__ == "__main__":
    sys.exit(main())
