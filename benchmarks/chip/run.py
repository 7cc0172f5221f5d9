"""Run one benchmark cell once on a TPU and print its result line.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``; its files are
found by name (``layout.py``).  A run:

1. refuses, with a non-zero exit and no result, when JAX finds no TPU or
   fewer chips than the cell asks for;
2. keeps JAX's persistent compilation cache at ``<checkout>/.jax_cache``;
3. set-up (``setup_s``, from process start): the driver builds the work
   from the seed and warms every shape the cell's traffic uses;
4. the window: ``--seconds`` of traffic (``--trace 1``: the cell's shorter
   ``trace_seconds``, under the profiler and the program's span recorder);
5. reads the device's peak memory, frees the program's state, and compares
   what the window produced with the configuration's plain reference;
6. prints the compile-cache counts on standard error, then each number
   compared beside its limit as the last lines there, and the result as one
   JSON object on the last line of standard output.

With ``--trace 0`` the result's metrics are the cell's end-to-end metrics;
with ``--trace 1`` its per-layer metrics, read by ``metrics/<name>.py``.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from typing import Dict, List, Optional, Tuple  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
for _p in (ROOT, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)
# the TPU runtime would otherwise write its logs to a fixed /tmp path
os.environ.setdefault("TPU_LOG_DIR", "disabled")

from benchmarks.chip import layout  # noqa: E402

CACHE_DIR = os.path.join(ROOT, ".jax_cache")
TRACE_DIR = os.path.join(ROOT, ".bench_trace")


class NoChip(RuntimeError):
    """JAX sees no TPU, or fewer chips than the cell needs."""


class CompileEvents:
    """Counts compilations (every lowering to an XLA module, which precedes
    a backend compile or a persistent-cache read) and the persistent
    cache's requests and hits, from JAX's monitoring events."""

    def __init__(self):
        import jax.monitoring
        self.n = {"lowerings": 0, "backend_compiles": 0, "cache_requests": 0,
                  "cache_hits": 0}
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, event: str, **kw) -> None:
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.n["cache_requests"] += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.n["cache_hits"] += 1

    def _duration(self, event: str, duration: float, **kw) -> None:
        if event == "/jax/core/compile/jaxpr_to_mlir_module_duration":
            self.n["lowerings"] += 1
        elif event == "/jax/core/compile/backend_compile_duration":
            self.n["backend_compiles"] += 1

    def snapshot(self) -> Dict[str, int]:
        return dict(self.n)


def check_devices(chips: int):
    """The devices, or NoChip unless JAX finds at least ``chips`` TPUs."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"needs a TPU, JAX found {devs[0].platform}")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chip(s), JAX sees {len(devs)}")
    return devs


def enable_cache() -> None:
    import jax
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def annotator(traced: bool):
    """``annotate(name)``: a profiler span on the trace's clock when traced,
    else nothing."""
    if not traced:
        return lambda name: contextlib.nullcontext()
    import jax
    return jax.profiler.TraceAnnotation


def run_cell(name: str, seed: int, seconds: float, traced: bool, *,
             require_tpu: bool = True, cfg: Optional[Dict] = None,
             work: Optional[Dict] = None
             ) -> Tuple[Dict, List[Tuple[str, float, float]], Dict]:
    """Run one cell once; returns ``(result, compared, info)``.

    ``require_tpu``, ``cfg`` and ``work`` let the tests drive a run on the
    CPU at a small size."""
    import jax
    cell = layout.cell(name)
    devs = check_devices(int(cell["chips"])) if require_tpu else jax.devices()
    enable_cache()
    events = CompileEvents()
    work = work or layout.workload(name)
    if work["config"] != cell["config"] or \
            work["traffic_name"] != cell["traffic"]:
        raise ValueError(f"workloads/{name}.json does not match BENCHMARK.json")
    cfg = cfg or layout.config(cell["config"])
    ref = layout.reference(cell["config"])
    drv = layout.driver(work["kind"])
    annotate = annotator(traced)

    st = drv.build(cfg, work, seed, ref)
    if traced:
        drv.instrument(st)
    setup_s = time.perf_counter() - T_START
    before = events.snapshot()
    window_s = min(seconds, float(work["trace_seconds"])) if traced else seconds
    if traced:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        jax.profiler.start_trace(TRACE_DIR)
    try:
        with annotate("bench/window"):
            res = drv.window(st, window_s, annotate)
    finally:
        if traced:
            jax.profiler.stop_trace()
    after = events.snapshot()
    in_window = {k: after[k] - before[k] for k in after}
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devs[:int(cell["chips"])])
    drv.release(st)

    readings = drv.readings(st, res, ref)
    limits = work["limits"]
    compared = [(k, float(v), float(limits[k])) for k, v in readings.items()]
    cnt = drv.counts(res)
    correct = cnt["failed"] == 0 and all(v <= lim for _, v, lim in compared)

    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": int(cell["chips"]), "memory_peak_bytes": peak}
    info = {"setup_s": setup_s, "compiles_in_window": in_window,
            "compiles_total": after, "counts": cnt}
    if traced:
        from benchmarks.chip import devtrace
        view = devtrace.load(TRACE_DIR)
        device["busy_s"] = view.busy_s()
        device["window_s"] = view.window_s
        metrics = per_layer(name, view, drv.layer_inputs(st, res),
                            devs[0].device_kind)
        breakdown = {"device_ops": [list(x) for x in view.top_ops(10)],
                     "idle_gaps": [list(x) for x in view.idle_gaps(10)]}
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    else:
        values = dict(drv.e2e(st, res), setup_s=setup_s)
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in layout.metrics_for(name, "end_to_end")}
        breakdown = None
    result = {"correct": correct, "attempted": cnt["attempted"],
              "failed": cnt["failed"], "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = {k: {"value": v, "limit": lim} for k, v, lim in compared}
    return result, compared, info


def per_layer(name: str, view, inputs: Dict, device_kind: str) -> Dict:
    """Every per-layer metric of the cell whose reader finds something."""
    run = dict(inputs, trace=view, peaks=layout.peaks(device_kind))
    out = {}
    for m in layout.metrics_for(name, "per_layer"):
        value = layout.metric_reader(m["name"]).read(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result, compared, info = run_cell(args.workload, args.seed,
                                          args.seconds, bool(args.trace))
    except NoChip as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 2
    bad = [k for k, v in result["metrics"].items()
           if not math.isfinite(v["value"])]
    print(f"[bench] {args.workload} seed={args.seed} "
          f"setup_s={info['setup_s']} counts={info['counts']}",
          file=sys.stderr)
    print(f"[bench] compile cache over the run: {info['compiles_total']}; "
          f"inside the window: {info['compiles_in_window']}", file=sys.stderr)
    if bad:
        print(f"run.py: metrics without a finite value: {bad}",
              file=sys.stderr)
    for k, v, lim in compared:
        print(f"check {k}: {v!r} limit {lim!r}", file=sys.stderr)
    if bad:
        return 1
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
