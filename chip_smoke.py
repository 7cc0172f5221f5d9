"""Chip smoke: drive both halves of the system once on a TPU and check them.

    python chip_smoke.py            # one chip: search phase, then serve phase
    python chip_smoke.py --chips 4  # four chips: sharded-ranking search only

Search phase: ``repro.explore.run_spec`` with ``strategy="jit_nsga2"`` on
EfficientNet-B0 (224x224) over a four-platform embedded chain at pop 32768
with 1024-row rank tiles, so the tiled Pallas Pareto ranking runs compiled
through Mosaic.  The runner's lowered IR must hold the kernel
(``tpu_custom_call``), and its front must equal that of the same spec run
with ``rank_impl="ref"``.

Serve phase: ``repro.launch.serve`` at smollm-360m's published widths
(``--full-width``, random weights): the explorer picks a two-stage cut,
``PartitionedLMRunner`` splits the model and one async and one serial
``PipelineServeEngine`` replica answer the same requests behind the router.
Checks: no request dropped, async tokens equal serial tokens, and the
partitioned forward's logits agree with a float32 monolithic forward under
``jax.default_matmul_precision("highest")`` (tolerances in
:data:`LOGIT_TOL`).  Reported without gating: whether the served cut is the
explorer's choice, and how many persistent compilation-cache hits one more
engine's re-jits of the already compiled stage programs get.

``--chips 4`` runs the same search at ``rank_devices=4`` (the ranking tile
rows sharded over a four-device mesh) and at ``rank_devices=1``, and
requires identical fronts.

Everything runs in this one process.  Without a TPU the script exits
non-zero before any phase and prints no result.  The last line of standard
output is ``{"ok": true, "device": {...}}``.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys
import tempfile

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.monitoring  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.explore import (ExplorationSpec, ModelRef, PlatformSpec,  # noqa: E402
                           SearchSettings, SystemSpec, run_spec)
from repro.explore.strategies import (_rank_mesh,  # noqa: E402
                                      clear_jit_runner_cache)
from repro.core import get_link  # noqa: E402
from repro.launch import serve  # noqa: E402
from repro.serve import PipelineServeEngine, ServeLink  # noqa: E402
from repro.utils.compile_cache import enable_compile_cache  # noqa: E402

SEARCH_POP = 32768
SEARCH_RANK_BLOCK = 1024
SEARCH_GENS = 4

SERVE_ARGV = ["--arch", "smollm-360m", "--requests", "8", "--prompt-len",
              "32", "--max-new", "16", "--replicas", "1", "--warm-steps", "0"]

# Relative Frobenius error ||logits - ref|| / ||ref|| of the partitioned
# forward against the float32 monolithic reference.
#  * "highest": both sides in float32.  They differ only in summation order
#    (unit roundoff 6e-8), far below 1e-4; a single bf16 pass anywhere
#    (roundoff 2e-3) breaks it.
#  * "default": the partitioned forward as the program runs it.  A TPU runs
#    a float32 matmul at default precision as one bfloat16 pass (operands
#    rounded to 8 significant bits, ~3e-3 relative per matmul), compounded
#    over 32 residual layers.
LOGIT_TOL = {"highest": 1e-4, "default": 3e-2}


def log(msg: str) -> None:
    print(msg, flush=True)


# -- search -------------------------------------------------------------------

def search_spec(pop: int, n_gen: int, rank_block: int, rank_impl: str,
                rank_devices=None, in_hw: int = 224):
    """EfficientNet-B0 over the README's chain extended to four platforms."""
    return ExplorationSpec(
        model=ModelRef("cnn", "efficientnet_b0", {"in_hw": in_hw}),
        system=SystemSpec(
            platforms=(PlatformSpec("EYR0", "eyr", bits=16),
                       PlatformSpec("EYR1", "eyr", bits=16),
                       PlatformSpec("SMB0", "smb", bits=8),
                       PlatformSpec("SMB1", "smb", bits=8)),
            links=("gige",) * 3),
        objectives=("latency", "energy", "throughput"),
        search=SearchSettings(strategy="jit_nsga2", seed=0, pop_size=pop,
                              n_gen=n_gen, rank_block=rank_block,
                              rank_impl=rank_impl, rank_devices=rank_devices))


def front(result) -> list:
    """The Pareto front as sorted (cuts, objective values) rows."""
    return sorted((tuple(e.cuts), e.latency_s, e.energy_j, e.throughput)
                  for e in result.pareto)


def run_search(spec, ir_dir=None):
    """``run_spec`` on a fresh runner cache; with ``ir_dir`` the lowered
    IR of every program is dumped there."""
    clear_jit_runner_cache()
    if ir_dir is not None:
        jax.config.update("jax_dump_ir_to", ir_dir)
    try:
        res = run_spec(spec)
    finally:
        jax.config.update("jax_dump_ir_to", "")
    if res.strategy_used != "jit_nsga2":
        raise RuntimeError(f"search ran {res.strategy_used!r}, not jit_nsga2")
    return res


def runner_has_kernel(ir_dir: str) -> bool:
    """Whether the compiled search runner's lowered IR calls a Mosaic
    kernel."""
    files = glob.glob(os.path.join(ir_dir, "*jit_run*.mlir"))
    if not files:
        raise RuntimeError(f"no search runner IR was dumped to {ir_dir}")
    for path in files:
        with open(path) as f:
            if "tpu_custom_call" in f.read():
                return True
    return False


def search_phase(pop: int = SEARCH_POP, n_gen: int = SEARCH_GENS,
                 rank_block: int = SEARCH_RANK_BLOCK,
                 rank_impl: str = "auto", in_hw: int = 224) -> dict:
    """The tiled-kernel search against the same search on the reference
    ranking; returns what was checked."""
    with tempfile.TemporaryDirectory() as ir_dir:
        res = run_search(search_spec(pop, n_gen, rank_block, rank_impl,
                                     in_hw=in_hw), ir_dir)
        kernel = runner_has_kernel(ir_dir)
    ref = run_search(search_spec(pop, n_gen, rank_block, "ref", in_hw=in_hw))
    got, want = front(res), front(ref)
    log(f"[search] pop={pop} rank_block={rank_block} gens={n_gen}: "
        f"runner IR tpu_custom_call={kernel}; front {len(got)} point(s), "
        f"ref front {len(want)}, identical={got == want}")
    return {"kernel": kernel, "identical": got == want, "front": len(got)}


def sharded_search_phase(n_devices: int, pop: int = SEARCH_POP,
                         n_gen: int = SEARCH_GENS,
                         rank_block: int = SEARCH_RANK_BLOCK,
                         rank_impl: str = "auto", in_hw: int = 224) -> dict:
    """The search with ranking sharded over ``n_devices`` against the same
    search on one device."""
    mesh = _rank_mesh(n_devices)
    if mesh is None or mesh.size != n_devices:
        raise RuntimeError(f"rank mesh has {0 if mesh is None else mesh.size}"
                           f" device(s), wanted {n_devices}")
    sharded = run_search(search_spec(pop, n_gen, rank_block, rank_impl,
                                     n_devices, in_hw))
    single = run_search(search_spec(pop, n_gen, rank_block, rank_impl, 1,
                                    in_hw))
    got, want = front(sharded), front(single)
    log(f"[search x{n_devices}] pop={pop} rank_block={rank_block} "
        f"gens={n_gen}: mesh {mesh.size} device(s) "
        f"{[d.id for d in mesh.devices.flat]} of {len(jax.devices())}; "
        f"front {len(got)} point(s) at rank_devices={n_devices}, "
        f"{len(want)} at rank_devices=1, identical={got == want}")
    return {"mesh": mesh.size, "identical": got == want}


# -- serve --------------------------------------------------------------------

def logit_errors(runner, batch) -> dict:
    """Relative error of the partitioned forward against the float32
    monolithic forward, with the partitioned side at each precision."""
    with jax.default_matmul_precision("highest"):
        ref, _ = runner.model.apply(runner.params, {}, batch, train=False)
        ref = np.asarray(ref, np.float64)
    errs = {}
    for prec in LOGIT_TOL:
        if prec == "highest":
            with jax.default_matmul_precision("highest"):
                got, _ = runner.forward(batch)
        else:
            got, _ = runner.forward(batch)
        diff = np.asarray(got, np.float64) - ref
        errs[prec] = float(np.linalg.norm(diff) / np.linalg.norm(ref))
    return errs


def rejit_probe(runner, args, events: CompileEvents) -> dict:
    """Build and warm one more serial engine, with ``repro.launch.serve``'s
    engine settings, over the served runner.  Its stage programs equal
    those the served engines compiled, but each engine jits its own, so
    every one goes to the backend again; returns the compile requests that
    consulted the persistent cache and the hits among them."""
    before = events.snapshot()
    links = [ServeLink(model=get_link(args.link))
             for _ in range(runner.n_stages - 1)]
    eng = PipelineServeEngine(runner, n_slots=8, n_groups=4, eos=None,
                              mode="serial", capacity=64, links=links,
                              name="probe")
    eng.warmup(prompt_len=args.prompt_len)
    del eng
    after = events.snapshot()
    return {k: after[k] - before[k] for k in after}


def serve_phase(events: CompileEvents, full_width: bool = True) -> dict:
    """``repro.launch.serve`` through the partitioned pipeline, then the
    three checks; returns what was checked and what is only reported."""
    argv = SERVE_ARGV + (["--full-width"] if full_width else [])
    args = serve.parse_args(argv)
    run = serve.serve(args)
    cfg = run.runner.model.cfg
    n = len(run.requests)
    log(f"[serve] {cfg.arch_id}: {cfg.n_layers}L d{cfg.d_model} "
        f"{cfg.n_heads}H kv{cfg.n_kv} ffn{cfg.d_ff} vocab {cfg.vocab}; "
        f"stages {run.runner.ranges}")
    dropped = {mode: n - rep.n_done for mode, rep in
               (("async", run.rep_async), ("serial", run.rep_serial))}
    toks = {mode: {r.rid: list(r.tokens) for r in rep.records}
            for mode, rep in (("async", run.rep_async),
                              ("serial", run.rep_serial))}
    same = toks["async"] == toks["serial"]
    log(f"[serve] {n} request(s): dropped async={dropped['async']} "
        f"serial={dropped['serial']}; async tokens == serial tokens: {same}")
    batch = {"tokens": jnp.asarray(np.stack([r.prompt for r in
                                             run.requests[:2]]))}
    errs = logit_errors(run.runner, batch)
    for prec, err in errs.items():
        log(f"[serve] logits vs float32 reference, partitioned forward at "
            f"{prec} precision: rel err {err:.3e} (tol {LOGIT_TOL[prec]:g})")
    stats = jax.devices()[0].memory_stats() or {}
    log(f"[serve] device 0 peak_bytes_in_use={stats.get('peak_bytes_in_use')} "
        f"(process peak so far)")
    rejit = rejit_probe(run.runner, args, events)
    log(f"[serve] one more engine's stage-program re-jits: "
        f"{rejit['requests']} compile request(s) through the persistent "
        f"cache, {rejit['hits']} hit(s)")
    return {"dropped": sum(dropped.values()), "tokens_equal": same,
            "logits_ok": all(errs[p] <= LOGIT_TOL[p] for p in errs),
            "explorer_cut": run.explorer_cut, "rejit": rejit}


# -- main ---------------------------------------------------------------------

class CompileEvents:
    """Counts, from JAX monitoring events, the backend compile requests that
    consulted the persistent compilation cache and the hits among them."""

    EVENTS = {"/jax/compilation_cache/compile_requests_use_cache": "requests",
              "/jax/compilation_cache/cache_hits": "hits"}

    def __init__(self):
        self.n = {"requests": 0, "hits": 0}
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **kw) -> None:
        name = self.EVENTS.get(event)
        if name is not None:
            self.n[name] += 1

    def snapshot(self) -> dict:
        return dict(self.n)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded-ranking search on four "
                         "chips")
    args = ap.parse_args(argv)

    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform}",
              file=sys.stderr)
        return 2
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees {len(devs)} "
              "device(s)", file=sys.stderr)
        return 2
    cache = enable_compile_cache()
    events = CompileEvents()
    log(f"[device] {dev.platform} {dev.device_kind} x{len(devs)}; "
        f"compile cache {cache}")

    checks, reported = {}, {}
    if args.chips == 4:
        r = sharded_search_phase(4)
        checks["sharded_front_identical"] = r["identical"]
        checks["mesh_has_4_devices"] = r["mesh"] == 4
    else:
        r = search_phase()
        checks["search_kernel_compiled"] = r["kernel"]
        checks["search_front_identical_to_ref"] = r["identical"]
        r = serve_phase(events)
        checks["serve_zero_dropped"] = r["dropped"] == 0
        checks["serve_async_tokens_equal_serial"] = r["tokens_equal"]
        checks["serve_logits_within_tol"] = r["logits_ok"]
        reported["serve_cut_from_explorer"] = r["explorer_cut"]
        reported["serve_rejit_cache_hits"] = r["rejit"]["hits"]
    n = events.snapshot()
    log(f"[cache] whole run: {n['requests']} compile request(s) through the "
        f"persistent cache, {n['hits']} hit(s)")
    failed = [k for k, ok in checks.items() if not ok]
    log(f"[checks] {checks}")
    if reported:
        log(f"[reported, not gating] {reported}")
    if failed:
        print(f"chip_smoke: failed {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
