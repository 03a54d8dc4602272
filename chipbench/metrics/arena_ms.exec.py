"""Device time of the arena kernels per executed step, from the trace."""

from chipbench.bench.readers import arena_kernels


def read(run):
    k = arena_kernels(run)
    if k is None or not run.get("traced_steps"):
        return None
    return 1e3 * k[1] / run["traced_steps"]
