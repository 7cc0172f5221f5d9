"""Quickstart: declarative exploration with ``repro.explore``.

One :class:`ExplorationSpec` describes the whole run — model, system,
objectives, constraints, search strategy — and is JSON-round-trippable, so
the same spec that runs here can be stored in a config repo or shipped to a
fleet runner.  The setup is the paper's §V-A: SqueezeNet on a 16-bit
Eyeriss-like sensor node + 8-bit Simba-like central unit over GigE.

  PYTHONPATH=src python examples/quickstart.py
"""

from repro.explore import (Campaign, ExplorationSpec, ModelRef, PlatformSpec,
                           SystemSpec, run_spec)
from repro.core.partition import Constraints

# 1. the whole exploration as one declarative, serializable spec
spec = ExplorationSpec(
    model=ModelRef("cnn", "squeezenet11"),
    system=SystemSpec(
        platforms=(PlatformSpec("sensor-node", "eyr", bits=16),
                   PlatformSpec("central-unit", "smb", bits=8)),
        links=("gige",)),
    objectives=("latency", "energy", "throughput"),
    constraints=Constraints(max_link_bytes=2_000_000))
print("spec:", spec.to_json())
assert ExplorationSpec.from_json(spec.to_json()) == spec  # JSON round-trip

# 2. run it: schedule -> candidate filtering -> metric evaluation ->
#    search strategy -> Pareto front -> Def.-2 selection (Fig. 1)
result = run_spec(spec)
print(result.summary())

print("\nPareto front:")
for ev in sorted(result.pareto, key=lambda e: e.latency_s):
    name = (result.layer_name(ev.cuts[0]) if ev.cuts[0] >= 0
            else "all-on-central-unit")
    print(f"  cut after {name:24s} lat={ev.latency_s*1e3:7.3f} ms  "
          f"E={ev.energy_j*1e3:7.3f} mJ  th={ev.throughput:8.1f}/s")

# 3. fleet mode: fan the same spec template across the CNN zoo in one
#    Campaign (shared per-arch cost tables) and get a serializable report
fleet = Campaign(spec, models=[ModelRef("cnn", n)
                               for n in ("squeezenet11", "resnet50",
                                         "efficientnet_b0")])
report = fleet.run().report
print("\n" + report.summary())

# 4. big populations: strategy="jit_nsga2" compiles the whole NSGA-II
#    generation loop (ranking, crowding, variation, metric evaluation over
#    the precomputed cost tables) into one jax.jit program — pick it when
#    pop_size climbs into the thousands (~10x the NumPy strategy at pop
#    2048; CI's benchmarks/explorer_bench.py + compare_bench.py gate keeps
#    both paths from regressing >20% run-over-run)
import dataclasses  # noqa: E402

from repro.explore import SearchSettings  # noqa: E402

jit_spec = dataclasses.replace(
    spec, search=SearchSettings(strategy="jit_nsga2", pop_size=4096,
                                n_gen=40))
print("\njit_nsga2:", run_spec(jit_spec).summary())

# 5. scaling the jit search to very large populations — the knobs
#    (worked example: EfficientNet-B0 across a 4-node chain, 3 cuts)
#
#    * rank_block   — row-tile size of the blocked Pareto-ranking kernel
#      (repro.kernels.pareto_rank).  None auto-selects: dense ranking for
#      combined populations <= 4096, 2048-row tiles beyond, so peak memory
#      is O(pop * rank_block) instead of the dense O(pop^2) that capped
#      populations around 2k.  Set it explicitly to trade tile-loop
#      overhead against working-set size.
#    * rank_impl    — 'auto' (blocked jnp on CPU, the Pallas kernel on TPU),
#      'ref', or 'pallas' to pin a branch.
#    * n_restarts   — >1 vmaps that many independently seeded searches into
#      ONE compiled program (seeds seed..seed+n-1) and merges their fronts:
#      restart diversity at roughly the cost of one larger batch.
#    * rank_devices — shards the ranking tile grid across that many local
#      devices via shard_map on multi-device hosts.
#
#    With these, pop 32768 completes on a CPU host where the dense path
#    OOMs, and accelerators stay busy at 100k+ (see
#    benchmarks/explorer_bench.py, which records jit_nsga_pop_max).
scale_spec = ExplorationSpec(
    model=ModelRef("cnn", "efficientnet_b0", {"in_hw": 64}),
    system=SystemSpec(
        platforms=(PlatformSpec("cam0", "eyr", bits=16),
                   PlatformSpec("cam1", "eyr", bits=16),
                   PlatformSpec("edge", "smb", bits=8),
                   PlatformSpec("central", "smb", bits=8)),
        links=("gige", "gige", "gige")),
    objectives=("latency", "energy"),
    search=SearchSettings(strategy="jit_nsga2", pop_size=2048, n_gen=12,
                          rank_block=512,      # force the tiled ranking
                          rank_impl="auto",
                          n_restarts=2))       # 2 seeds, one compile
print("\njit_nsga2 scaled:", run_spec(scale_spec).summary())

# 6. fleet mode at zoo scale: the same Campaign, distributed.  A sweep
#    materializes as a durable work manifest (one JSON cell per
#    model x system, states driven by atomic claim/shard files), any number
#    of worker processes -- on this host or on many hosts sharing the
#    directory -- claim cells and publish report shards, and the merge is
#    report-identical to the serial Campaign.run above (same seeds, same
#    entries, serial entry order).  Equivalent shell workflow:
#
#      python -m repro.fleet init --spec spec.json --manifest sweep.manifest
#      python -m repro.fleet run  --manifest sweep.manifest --workers 2
#
#    Fault tolerance is the point: kill a worker mid-cell (or the whole
#    host) and re-run the SAME command -- done cells are never recomputed,
#    the dead worker's claim is reclaimed automatically, and only pending
#    work executes.  `python -m repro.fleet status --manifest ...` shows
#    per-cell state; `... hosts --hosts a,b,c` prints the per-host commands
#    for a multi-host run.  Failed cells retry within a bounded budget and
#    can be merged as placeholders with --allow-failed.
import tempfile  # noqa: E402

from repro.fleet import run_fleet  # noqa: E402

with tempfile.TemporaryDirectory() as mdir:
    fleet.to_manifest(mdir)                   # the Campaign from step 3
    fleet_report = run_fleet(mdir, workers=2, verbose=True)
print("\nfleet sweep (2 workers):")
print(fleet_report.summary())

from repro.fleet import report_fingerprint  # noqa: E402

assert report_fingerprint(fleet_report) == report_fingerprint(report), \
    "fleet merge must be report-identical to the serial Campaign"
print("fleet merged report == serial campaign report (modulo wall-clock)")

# 7. keep it correct: the repo's own static analyzer.  Three rule families
#    guard the contracts everything above depends on -- RPR1xx trace-safety
#    (no Python branches/host syncs on traced values inside the jitted
#    search path), RPR2xx Pallas kernel call contracts (block/grid
#    divisibility, index_map arity, no hardcoded interpret= flags), RPR3xx
#    fleet atomicity (no plain open(...,'w') bypassing the atomic-publish
#    helpers that make the fleet runtime crash-safe).  CI gates on it; run
#    it locally before pushing:
#
#      PYTHONPATH=src python -m repro.analysis src benchmarks
#      PYTHONPATH=src python -m repro.analysis --list-rules
#      PYTHONPATH=src python -m repro.analysis src --select RPR3 --format json
#
#    Suppressions live in .analysis-baseline.json and every entry must
#    carry a written justification (see CONTRIBUTING.md).
import os  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402

repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
env = dict(os.environ, PYTHONPATH=os.path.join(repo, "src"))
gate = subprocess.run(
    [sys.executable, "-m", "repro.analysis", "src", "benchmarks"],
    cwd=repo, env=env, capture_output=True, text=True)
print("\nstatic analysis gate:")
print(gate.stdout.strip())
assert gate.returncode == 0, gate.stdout + gate.stderr

# 8. serve the partition for real: repro.serve.  Everything above picks
#    cuts from *models* of latency/energy; the serving runtime executes
#    them — continuous batching over partitioned LM stages with per-slot
#    admission/eviction (no lockstep waves), thread-per-stage async
#    workers that overlap emulated link wire time with compute (Def. 4:
#    steady-state throughput ~ 1/max(stage, link)), and a
#    least-outstanding-slots router over N replicas.  The walkthrough:
#    pick a cut with explore_graph on the reduced LM's graph, snap it to
#    a decoder-block boundary with lm_block_cuts, launch 2 async
#    replicas, read the merged TTFT/throughput report.  (CI's
#    benchmarks/serve_smoke.py asserts byte-identical greedy tokens vs
#    the monolithic engine; benchmarks/serve_bench.py gates the
#    async-vs-serial speedup and the Def.-4 prediction gap.)
import jax  # noqa: E402

from repro.core import Platform, QuantSpec, SystemConfig, get_link  # noqa: E402
from repro.core.hwmodel import EYERISS_LIKE, SIMBA_LIKE  # noqa: E402
from repro.explore import explore_graph, lm_block_cuts  # noqa: E402
from repro.models.registry import build_model, get_config  # noqa: E402
from repro.serve import (PipelineServeEngine, ReplicaRouter,  # noqa: E402
                         ServeLink, poisson_traffic)
from repro.serving.pipeline import PartitionedLMRunner  # noqa: E402

lm_cfg = get_config("smollm-360m").reduced()
lm = build_model(lm_cfg)
lm_params, _ = lm.init(jax.random.PRNGKey(0))

lm_system = SystemConfig(
    [Platform("A", EYERISS_LIKE, QuantSpec(bits=16)),
     Platform("B", SIMBA_LIKE, QuantSpec(bits=8))],
    [get_link("eth10")])                       # embedded 10 Mbit/s Ethernet
lm_result = explore_graph(lm.to_graph(8), lm_system,
                          objectives=("latency", "energy", "throughput"))
sel = lm_result.selected.cuts if lm_result.selected is not None else (1,)
cuts = lm_block_cuts(sel, lm_cfg.n_layers)     # schedule cut -> block cut
print(f"\nserve: explorer cuts {tuple(sel)} -> block cuts {cuts}")

lm_runner = PartitionedLMRunner(lm, lm_params, cuts=cuts)
replicas = []
for i in range(2):
    eng = PipelineServeEngine(
        lm_runner, n_slots=8, n_groups=4, mode="async", capacity=32,
        links=[ServeLink(model=get_link("eth10"))
               for _ in range(lm_runner.n_stages - 1)],
        name=f"replica{i}")
    eng.warmup(prompt_len=8)
    replicas.append(eng)

traffic = poisson_traffic(8, rate_rps=200.0, vocab=lm_cfg.vocab,
                          prompt_len=8, max_new=6, seed=0)
served = ReplicaRouter(replicas).serve(list(traffic), realtime=False)
summary = served.summary()
print(f"serve: {served.n_done} request(s), "
      f"{summary['tokens_per_s']:.0f} tok/s over 2 replicas, "
      f"TTFT p95 {summary.get('ttft_p95_ms', 0):.0f} ms, "
      f"routed {served.extra['routed_per_replica']}")
assert served.n_done == len(traffic), "serve dropped requests"
