"""Public-API documentation: generated reference + docstring gate.

``PUBLIC_API`` names the repo's supported surface — the classes and
functions the docs/ walkthroughs and external callers are allowed to rely
on.  From that single list this package derives both artifacts:

* ``python -m repro.docs``          regenerates ``docs/api.md`` (stdlib
  ``inspect`` only — no doc toolchain to install);
* ``python -m repro.docs --check``  exits non-zero when any listed object,
  or any public method of a listed class, is missing a docstring.  CI runs
  this in the analysis job, so the surface cannot silently grow
  undocumented entries.

Adding a name here is the act of making it public: it must be importable
from the stated module and documented, or CI fails.
"""

from __future__ import annotations

import importlib
import inspect
from typing import Iterator, List, Tuple

# (module, names) — the supported public surface, grouped by layer in the
# order docs/architecture.md presents them.
PUBLIC_API: Tuple[Tuple[str, Tuple[str, ...]], ...] = (
    ("repro.core", (
        "LayerGraph", "LayerInfo", "SystemConfig", "Platform", "QuantSpec",
        "LinkModel", "get_link", "Constraints", "PartitionEvaluator",
        "ProxyAccuracy", "MeasuredAccuracy",
    )),
    ("repro.core.partition_jax", (
        "EvalTables", "build_eval_tables", "make_batch_eval_fn",
        "make_runtime_eval_fn",
    )),
    ("repro.core.nsga2_jax", (
        "jit_nsga2", "jit_nsga2_restarts", "make_jit_runner",
        "make_jit_restart_runner", "warm_population", "nondominated_rank",
    )),
    ("repro.explore", (
        "ExplorationSpec", "SearchSettings", "ModelRef", "PlatformSpec",
        "LinkSpec", "SystemSpec", "AccuracySpec", "SweepSpec", "Campaign",
        "ExplorationResult", "run_search", "run_spec", "explore_graph",
        "lm_block_cuts", "candidate_positions", "register_strategy",
        "SearchStrategy", "NSGA2Search", "JitNSGA2Search",
        "OnlineRepartitioner", "RepartitionDecision", "degrade_link",
        "drop_node", "jit_runner_cache_size", "clear_jit_runner_cache",
    )),
    ("repro.serve", (
        "SlotScheduler", "PipelineServeEngine", "ReplicaRouter", "Request",
        "RequestRecord", "ServeReport", "ServeLink", "RequestStream",
        "poisson_traffic", "stream_of",
        "FaultPlan", "LinkDegrade", "StageStall", "ReplicaCrash",
        "ReplicaCrashError", "FaultTrace",
        "HealthMonitor", "Ewma", "FailureDetector", "DivergenceMonitor",
        "DriftSignal",
    )),
    ("repro.obs", (
        "Obs", "phase", "Tracer", "NullTracer", "Span", "MetricsRegistry",
        "Counter", "Gauge", "Histogram", "default_registry",
        "to_chrome_trace", "write_chrome_trace", "load_chrome_trace",
        "validate_chrome_trace", "percentile", "latency_summary",
        "mean_tail",
    )),
)


def iter_api() -> Iterator[Tuple[str, str, object]]:
    """Yield ``(module_name, attr_name, object)`` for every PUBLIC_API
    entry; raises if a listed name fails to import (the list *is* the
    contract, so a stale entry is an error, not a skip)."""
    for mod_name, names in PUBLIC_API:
        mod = importlib.import_module(mod_name)
        for name in names:
            obj = getattr(mod, name, None)
            if obj is None:
                raise AttributeError(
                    f"PUBLIC_API lists {mod_name}.{name} but the module "
                    "does not export it")
            yield mod_name, name, obj


def _public_methods(cls) -> List[Tuple[str, object]]:
    out = []
    for name, member in vars(cls).items():
        if name.startswith("_"):
            continue
        fn = member
        if isinstance(member, (staticmethod, classmethod)):
            fn = member.__func__
        elif isinstance(member, property):
            fn = member.fget
        if not callable(fn):
            continue
        out.append((name, fn))
    return out


def missing_docstrings() -> List[str]:
    """Dotted paths of public objects (and public methods of public
    classes) whose ``__doc__`` is empty — the CI gate's finding list."""
    missing = []
    for mod_name, name, obj in iter_api():
        path = f"{mod_name}.{name}"
        if not inspect.getdoc(obj):
            missing.append(path)
        if inspect.isclass(obj):
            for mname, fn in _public_methods(obj):
                if not (fn.__doc__ or "").strip():
                    missing.append(f"{path}.{mname}")
    return missing


def _signature(obj) -> str:
    try:
        return str(inspect.signature(obj))
    except (ValueError, TypeError):
        return "(...)"


def _doc_block(obj, indent: str = "") -> str:
    doc = inspect.getdoc(obj) or ""
    return "\n".join(indent + line if line else ""
                     for line in doc.splitlines())


def render_api_md() -> str:
    """The full ``docs/api.md`` markdown, rendered from live docstrings."""
    lines = [
        "# API reference",
        "",
        "Generated by `PYTHONPATH=src python -m repro.docs` from the",
        "`repro.docs.PUBLIC_API` surface — edit docstrings, not this file.",
        "",
    ]
    for mod_name, names in PUBLIC_API:
        mod = importlib.import_module(mod_name)
        lines += [f"## `{mod_name}`", ""]
        head = (inspect.getdoc(mod) or "").split("\n\n")[0]
        if head:
            lines += [head, ""]
        for name in names:
            obj = getattr(mod, name)
            if inspect.isclass(obj):
                init_sig = _signature(obj.__init__).replace("(self, ", "(") \
                    .replace("(self)", "()")
                lines += [f"### class `{name}{init_sig}`", "",
                          _doc_block(obj), ""]
                for mname, fn in sorted(_public_methods(obj)):
                    first = (inspect.getdoc(fn) or "").split("\n")[0]
                    lines.append(f"- **`{mname}{_signature(fn)}`** — {first}")
                if _public_methods(obj):
                    lines.append("")
            else:
                lines += [f"### `{name}{_signature(obj)}`", "",
                          _doc_block(obj), ""]
    return "\n".join(lines).rstrip() + "\n"
