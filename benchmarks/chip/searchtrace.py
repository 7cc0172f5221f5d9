"""Names of the compiled search's programs and kernels in a device trace."""

# the search runner (``make_jit_runner``'s ``run``) and the Pallas ranking
# kernels (``pareto_rank.packed_domination`` / ``domination_counts``)
RUNNER = "jit_run("
KERNELS = ("packed_domination", "domination_counts")


def runner_executions(run):
    """The runner program's executions inside the traced window, when this
    is a search cell's run."""
    if not run.get("searches"):
        return []
    return run["trace"].module_events(lambda n: n.startswith(RUNNER))
