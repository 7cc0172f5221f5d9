"""Fleet launchers: N local worker processes, or the per-host commands for
a multi-host run over a shared manifest directory.

Local workers are plain subprocesses of ``python -m repro.fleet worker``;
the same command is what a remote host runs (the manifest directory is the
only coordination channel, so "multi-host" just means the directory lives
on a shared filesystem).  :func:`run_fleet` is the one-call path: reclaim
stale claims, start workers, wait, merge — and because every step is
manifest-driven, running it again after a crash (or Ctrl-C) resumes instead
of recomputing.

A JAX process takes every accelerator chip of its host, and a second
process that asks for them fails or hangs.  So where the workers' JAX
backend is an accelerator, one local worker runs per host; any number run
when it is the CPU.  The parent never imports JAX itself: it asks a
short-lived child which backend the workers will get.
"""

from __future__ import annotations

import os
import subprocess
import sys
import time
import warnings
from typing import Dict, List, Optional, Sequence

from repro.explore.campaign import CampaignReport
from repro.fleet.manifest import Manifest
from repro.fleet.merge import merge_manifest


def _worker_env() -> Dict[str, str]:
    """Child env with ``repro`` importable even when the parent got it via
    ``sys.path`` manipulation rather than an installed package."""
    env = dict(os.environ)
    src = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    parts = [p for p in env.get("PYTHONPATH", "").split(os.pathsep) if p]
    if src not in parts:
        env["PYTHONPATH"] = os.pathsep.join([src] + parts)
    return env


def worker_command(manifest_dir: str, worker_id: Optional[str] = None,
                   verbose: bool = False) -> List[str]:
    cmd = [sys.executable, "-m", "repro.fleet", "worker",
           "--manifest", os.path.abspath(manifest_dir)]
    if worker_id:
        cmd += ["--worker-id", worker_id]
    if verbose:
        cmd.append("--verbose")
    return cmd


def worker_backend(env: Dict[str, str]) -> str:
    """JAX backend a worker started with ``env`` gets ('cpu', 'tpu', ...),
    asked of a child process that exits — and so frees any chip — before
    the workers start."""
    if env.get("JAX_PLATFORMS", "").strip() == "cpu":
        return "cpu"
    probe = subprocess.run(
        [sys.executable, "-c", "import jax; print(jax.default_backend())"],
        env=env, capture_output=True, text=True, timeout=300, check=True)
    return probe.stdout.split()[-1]


def start_workers(manifest_dir: str, n: int, verbose: bool = False
                  ) -> List[subprocess.Popen]:
    """Spawn ``n`` local worker processes against ``manifest_dir`` — one
    only when the workers' backend is an accelerator (see module
    docstring)."""
    env = _worker_env()
    if n > 1:
        backend = worker_backend(env)
        if backend != "cpu":
            warnings.warn(
                f"fleet: starting 1 local worker instead of {n}: a JAX "
                f"process holds every {backend} chip of this host",
                stacklevel=2)
            n = 1
    return [subprocess.Popen(worker_command(manifest_dir, verbose=verbose),
                             env=env) for _ in range(n)]


def wait_workers(procs: Sequence[subprocess.Popen]) -> List[int]:
    return [p.wait() for p in procs]


def host_commands(manifest_dir: str, hosts: Sequence[str],
                  workers_per_host: int = 1) -> str:
    """The copy-pasteable per-host commands for a multi-host run; the
    manifest directory must be on a filesystem all hosts share."""
    path = os.path.abspath(manifest_dir)
    lines = [f"# manifest: {path} (must be shared across hosts)"]
    for h in hosts:
        if workers_per_host > 1:
            cmd = (f"python -m repro.fleet run --manifest {path} "
                   f"--workers {workers_per_host} --no-merge")
        else:
            cmd = f"python -m repro.fleet worker --manifest {path}"
        lines.append(f"ssh {h} 'cd <repo>; PYTHONPATH=src {cmd}'")
    lines.append(f"# then, anywhere: python -m repro.fleet merge "
                 f"--manifest {path} --out report.json")
    return "\n".join(lines)


def run_fleet(manifest_dir: str, workers: int = 2,
              reclaim: str = "stale", allow_failed: bool = False,
              merge: bool = True,
              verbose: bool = False) -> Optional[CampaignReport]:
    """Run (or resume) a sweep with ``workers`` local processes and merge.

    ``reclaim``: ``'stale'`` (default) clears claims whose owner died on
    this host — the resume-after-crash path; ``'all'`` force-clears every
    claim (only when no worker anywhere is live); ``'none'`` leaves claims
    untouched.  Done cells are never recomputed — resuming an interrupted
    manifest only runs what is still pending.
    """
    manifest = Manifest.load(manifest_dir)
    if reclaim not in ("stale", "all", "none"):
        raise ValueError(f"reclaim must be 'stale', 'all' or 'none', "
                         f"got {reclaim!r}")
    if reclaim != "none":
        got = manifest.reclaim_stale(force=(reclaim == "all"))
        if got and verbose:
            print(f"[fleet] reclaimed {len(got)} stale claim(s)")
    t0 = time.perf_counter()
    if not manifest.complete():
        procs = start_workers(manifest_dir, workers, verbose=verbose)
        try:
            codes = wait_workers(procs)
        except KeyboardInterrupt:
            for p in procs:
                p.terminate()
            raise
        bad = [c for c in codes if c != 0]
        if bad and not manifest.complete():
            raise RuntimeError(
                f"{len(bad)} worker(s) exited non-zero and the manifest is "
                f"incomplete; inspect {manifest_dir}/failed and re-run")
    if not merge:
        return None
    report = merge_manifest(manifest, allow_failed=allow_failed)
    if verbose:
        print(f"[fleet] merged {len(report.entries)} cell(s) in "
              f"{time.perf_counter() - t0:.1f}s wall "
              f"({report.wall_s:.1f}s aggregate compute)")
    return report
