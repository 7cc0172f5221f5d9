"""Readings from which a cell's correctness limits are set, on the chip.

    python3 benchmarks/chip/control.py --workload <cell> --seconds <s> \\
        --seeds 1 2 3 ...

In one process, for each seed: the cell's set-up and a window of
``--seconds`` through the timed path, then the numbers compared, once for
the program (the lower readings) and once with the configuration's plain
reference at the next lower precision in the program's place (the control,
which sets the upper readings).  Then a second window with a fault planted
(:func:`state_unchanged`) and its readings.  One JSON line per seed on
standard output.  The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
for _p in (ROOT, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from benchmarks.chip import layout  # noqa: E402
from benchmarks.chip import run as bench  # noqa: E402


@contextlib.contextmanager
def state_unchanged():
    """A fault in the timed path: the compiled search runs no generation
    and returns its initial population, as a step that returns its state
    unchanged would (the rest of the search runs as ever)."""
    import repro.core.nsga2_jax as nj
    orig = nj.jit_nsga2

    def broken(*a, **kw):
        return orig(*a, **dict(kw, n_gen=0))

    nj.jit_nsga2 = broken
    try:
        yield
    finally:
        nj.jit_nsga2 = orig


def readings(name: str, seed: int, seconds: float, *,
             require_tpu: bool = True, cfg=None, work=None) -> dict:
    """Program, control and planted-fault readings of one seed."""
    cell = layout.cell(name)
    if require_tpu:
        bench.check_devices(int(cell["chips"]))
    bench.enable_cache()
    work = work or layout.workload(name)
    cfg = cfg or layout.config(cell["config"])
    ref = layout.reference(cell["config"])
    drv = layout.driver(work["kind"])
    st = drv.build(cfg, work, seed, ref)
    res = drv.window(st, seconds, bench.annotator(False))
    with state_unchanged():
        bad = drv.window(st, seconds, bench.annotator(False))
    drv.release(st)
    return {"seed": seed, "counts": drv.counts(res),
            "program": drv.readings(st, res, ref),
            "control": drv.readings(st, res, ref, control=True),
            "state_unchanged": drv.readings(st, bad, ref)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    try:
        for seed in args.seeds:
            print(json.dumps(readings(args.workload, seed, args.seconds)),
                  flush=True)
    except bench.NoChip as e:
        print(f"control.py: {e}", file=sys.stderr)
        return 2
    return 0


if __name__ == "__main__":
    sys.exit(main())
