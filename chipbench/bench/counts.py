"""Operations and bytes of the benchmarked work, from shapes alone.

These functions are the yardstick behind every roofline share and ``mfu``:
they count the work a cell asks for from the configuration's shapes and the
plan's sizes, so they read the same whatever implements it.  Nothing here
imports the program.
"""

from __future__ import annotations

import dataclasses

def least_seconds(flops: int, nbytes: int, peaks: dict) -> float:
    """The roofline's least time: the larger of the compute and the
    memory bound."""
    return max(flops / peaks["bf16_flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])


def graph_least_bytes(sizes) -> int:
    """Least HBM traffic of one execution: every tensor written once and
    read once."""
    return 2 * sum(sizes)


@dataclasses.dataclass(frozen=True)
class SliceWork:
    calls: int            # arena-kernel launches one execution makes
    nbytes: int           # bytes those launches need: slices read + written


def slice_work(nodes, exits) -> SliceWork:
    """Arena-kernel calls and slice bytes of one slice-per-node execution.

    ``nodes`` are ``(op, size_bytes, pred_sizes, aliased_pred_sizes)`` in
    schedule order.  The executor writes every input and every computed
    node's value into its slice (an in-place partial conv accumulates:
    read the slice, read the value, write the slice), reads each
    predecessor's slice once per consumer, fills the uncovered tail of a
    concat view, and reads each graph output once at the end.  Bytes are
    the slices' own sizes, never the kernels' padded blocks.
    """
    calls = nbytes = 0
    for op, size, preds, aliased in nodes:
        if op == "concat_view" and aliased:
            tail = size - sum(preds)
            if tail > 0:
                calls += 1
                nbytes += 2 * tail
            continue
        if op == "input":
            calls += 1
            nbytes += 2 * size
            continue
        calls += len(preds)
        nbytes += 2 * sum(preds)
        calls += 1
        nbytes += (3 if op == "partial_conv" and aliased else 2) * size
    calls += len(exits)
    nbytes += 2 * sum(exits)
    return SliceWork(calls, nbytes)
