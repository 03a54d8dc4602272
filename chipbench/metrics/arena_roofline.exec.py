"""The arena kernels' share of their roofline: the bytes their slices need
(read + written, the slices' own sizes, chipbench.bench.counts) over the
chip's HBM bandwidth times their device time, in percent.  Read only when
the trace holds exactly the launches that count describes."""

from chipbench.bench.readers import arena_kernels


def read(run):
    k = arena_kernels(run)
    steps = run.get("traced_steps")
    if k is None or not steps or k[0] != steps * run["slice_calls"]:
        return None
    need = steps * run["slice_bytes"] / run["peaks"]["hbm_bytes_per_s"]
    return 100.0 * need / k[1]
