"""Serving driver — the paper-kind end-to-end example, now on the
``repro.serve`` runtime.

Trains (briefly) a reduced model — or, with ``--full-width``, takes the
config at its published widths — lets the explorer pick the Def.-2 cut
for an embedded two-platform system, then serves a synthetic Poisson
traffic stream over partitioned stages with continuous batching:

  1. the explorer's schedule cut is snapped onto a decoder-block boundary
     (``repro.explore.lm_block_cuts``) and feeds the serving config;
  2. N replicas of the async stage pipeline (thread-per-stage workers,
     emulated link wire time overlapped with compute) serve the stream
     behind a least-outstanding-slots router;
  3. the same burst through the lockstep serial-handoff baseline shows
     what pipelining buys (Def. 4), with per-request TTFT/latency
     percentiles from the router's merged report.

  PYTHONPATH=src python -m repro.launch.serve --arch smollm-360m \
      --requests 16 --prompt-len 8 --max-new 12 --replicas 2
"""

from __future__ import annotations

import argparse
import dataclasses

import jax
import jax.numpy as jnp

from repro.core import Platform, QuantSpec, SystemConfig, get_link
from repro.core.hwmodel.arch import EYERISS_LIKE, SIMBA_LIKE
from repro.data.synthetic import make_batch_for
from repro.explore import SearchSettings, explore_graph, lm_block_cuts
from repro.models.registry import ARCH_IDS, build_model, get_config
from repro.obs import NOOP_OBS, Obs, write_chrome_trace
from repro.optim.optimizers import get_optimizer
from repro.serve import (PipelineServeEngine, ReplicaRouter, ServeLink,
                         ServeReport, poisson_traffic)
from repro.serving.pipeline import PartitionedLMRunner
from repro.training.train_lib import make_train_step
from repro.utils.compile_cache import enable_compile_cache


def parse_args(argv=None) -> argparse.Namespace:
    """The serve entry point's command line (``argv`` None reads ``sys.argv``)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-360m", choices=ARCH_IDS)
    ap.add_argument("--full-width", action="store_true",
                    help="serve the config at its published widths instead "
                         "of ModelConfig.reduced()")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=12)
    ap.add_argument("--rate-rps", type=float, default=200.0)
    ap.add_argument("--replicas", type=int, default=2)
    ap.add_argument("--link", default="eth10",
                    help="emulated inter-stage link (see repro.core.link)")
    ap.add_argument("--warm-steps", type=int, default=30)
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="write a Chrome trace-event JSON of the async run "
                         "(open in Perfetto, or `python -m repro.obs PATH`)")
    ap.add_argument("--metrics", default=None, metavar="PATH",
                    help="write a JSON metrics snapshot after the run")
    return ap.parse_args(argv)


@dataclasses.dataclass
class ServeRun:
    """What one :func:`serve` call built and measured: the explorer-cut
    runner (which holds the model and its weights), whether that cut is the
    explorer's choice, the traffic, and the routed async and serial-handoff
    reports over that traffic."""
    runner: PartitionedLMRunner
    explorer_cut: bool
    requests: list
    rep_async: ServeReport
    rep_serial: ServeReport


def serve(args: argparse.Namespace, obs: Obs = NOOP_OBS) -> ServeRun:
    """Build the model, let the explorer pick the cut, and serve the same
    Poisson traffic through async and serial replicas behind the router."""
    cfg = get_config(args.arch)
    if not args.full_width:
        cfg = cfg.reduced()
    if cfg.family not in ("dense",):
        raise SystemExit(f"--arch {args.arch}: partitioned serving needs a "
                         "dense decoder (block-boundary stage cuts)")
    model = build_model(cfg)
    key = jax.random.PRNGKey(0)
    params, state = model.init(key)

    if args.warm_steps > 0:
        # brief warm training so generations aren't pure noise
        opt = get_optimizer("adamw", 1e-3)
        opt_state = opt.init(params)
        step_fn = jax.jit(make_train_step(model, cfg, opt))
        for i in range(args.warm_steps):
            b = make_batch_for(cfg, 8, 64, seed=i)
            b = {k: jnp.asarray(v) for k, v in b.items()}
            params, opt_state, state, metrics = step_fn(params, opt_state,
                                                        state, b)
        del opt_state
        print(f"[serve] warm-trained {cfg.arch_id} to "
              f"loss={float(metrics['loss']):.3f}")

    # 1. the explorer picks the cut for a two-platform embedded system
    graph = model.to_graph(args.prompt_len)
    system = SystemConfig(
        [Platform("A", EYERISS_LIKE, QuantSpec(bits=16)),
         Platform("B", SIMBA_LIKE, QuantSpec(bits=8))],
        [get_link(args.link)])
    er = explore_graph(graph, system,
                       objectives=("latency", "energy", "throughput"),
                       search=SearchSettings(seed=0))
    sel = er.selected.cuts if er.selected is not None else (1,)
    cuts = lm_block_cuts(sel, cfg.n_layers)
    explorer_cut = (er.selected is not None
                    and not all(c < 0 for c in sel))
    # lm_block_cuts splits in the middle when the explorer chose no cut,
    # e.g. when no cut of the model fits the platforms' memory
    fallback = (f" (explorer chose no cut among {len(er.candidates)} "
                "feasible position(s): middle split)"
                if all(c < 0 for c in sel) else "")
    print(f"[serve] explorer selected schedule cuts {tuple(sel)} "
          f"-> block cuts {cuts}{fallback}")

    # 2. traffic + N async replicas behind the least-outstanding router
    runner = PartitionedLMRunner(model, params, cuts=cuts)
    reqs = poisson_traffic(args.requests, rate_rps=args.rate_rps,
                           vocab=cfg.vocab, prompt_len=args.prompt_len,
                           max_new=args.max_new, seed=123)

    def make_replicas(mode, obs=NOOP_OBS):
        reps = []
        for i in range(args.replicas):
            links = [ServeLink(model=get_link(args.link))
                     for _ in range(runner.n_stages - 1)]
            eng = PipelineServeEngine(runner, n_slots=8, n_groups=4,
                                      eos=None, mode=mode, capacity=64,
                                      links=links, name=f"replica{i}",
                                      obs=obs)
            eng.warmup(prompt_len=args.prompt_len)
            reps.append(eng)
        return reps

    # traced run: spans from every replica's stages/links plus the router
    rep_async = ReplicaRouter(make_replicas("async", obs),
                              obs=obs).serve(list(reqs), realtime=False)
    rep_serial = ReplicaRouter(make_replicas("serial")).serve(
        list(reqs), realtime=False)
    return ServeRun(runner, explorer_cut, reqs, rep_async, rep_serial)


def main(argv=None) -> int:
    enable_compile_cache()
    args = parse_args(argv)
    obs = Obs.on() if (args.trace or args.metrics) else NOOP_OBS
    run = serve(args, obs)
    rep_async, rep_serial = run.rep_async, run.rep_serial

    # 3. the report: throughput, Def.-4 context, per-request percentiles
    a, s = rep_async.summary(), rep_serial.summary()
    print(f"[serve] serial handoff: {s['tokens_per_s']:.0f} tok/s; "
          f"async pipeline: {a['tokens_per_s']:.0f} tok/s "
          f"(x{a['tokens_per_s'] / max(s['tokens_per_s'], 1e-9):.2f}) over "
          f"{args.replicas} replica(s), {rep_async.n_done} request(s)")
    for k in ("ttft_p50_ms", "ttft_p95_ms", "latency_p50_ms",
              "latency_p95_ms"):
        if k in a:
            print(f"[serve]   async {k} = {a[k]}")
    routed = rep_async.extra.get("routed_per_replica")
    if routed:
        print(f"[serve]   routed per replica: {routed}")
    if args.trace:
        write_chrome_trace(args.trace, obs.tracer)
        print(f"[serve] wrote Chrome trace -> {args.trace} "
              f"(python -m repro.obs {args.trace})")
    if args.metrics:
        obs.metrics.write_snapshot(args.metrics)
        print(f"[serve] wrote metrics snapshot -> {args.metrics}")
    if rep_async.n_done != args.requests or rep_serial.n_done != args.requests:
        print("[serve] ERROR: dropped requests")
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
