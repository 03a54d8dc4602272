"""The operation and byte counts against hand counts and the executor."""

import json
from pathlib import Path

import pytest

from chipbench.bench import counts

BENCH = Path(__file__).resolve().parents[1]


def test_randwire_slice_work_matches_the_executor(monkeypatch):
    """The slice calls and bytes counted from the graph equal what the
    slice-per-node executor asks of its arena ops, call by call."""
    import jax.numpy as jnp

    import repro.core.executor as ex
    from repro.core import PlanConfig, execute, plan
    from repro.graphs import randwire_network

    g = randwire_network(n_cells=8, n=32)
    res = plan(g, PlanConfig(rewrite=True), cache=False)
    seen = {"calls": 0, "bytes": 0}

    def counting(name, per_elem):
        real = getattr(ex, name)

        def op(arena, *a, **kw):
            n = a[1] if name == "arena_read" else a[0].shape[-1]
            seen["calls"] += 1
            seen["bytes"] += per_elem * 4 * n
            return real(arena, *a, **kw)
        return op

    monkeypatch.setattr(ex, "arena_read", counting("arena_read", 2))
    monkeypatch.setattr(ex, "arena_write", counting("arena_write", 2))
    monkeypatch.setattr(ex, "arena_accum", counting("arena_accum", 3))
    G = res.graph
    x = jnp.zeros(G.sizes[0] // 4)
    execute(G, [x], res.arena, order=res.order, impl="xla")
    nodes = [(G.nodes[u].op, G.sizes[u], [G.sizes[p] for p in G.nodes[u].preds],
              [G.sizes[p] for p in G.nodes[u].alias_preds]) for u in res.order]
    exits = [G.sizes[u] for u in range(len(G.nodes)) if not G.succs[u]]
    work = counts.slice_work(nodes, exits)
    assert (work.calls, work.nbytes) == (seen["calls"], seen["bytes"])
    # 274 nodes, each written once, every edge read once, one output read
    assert work.calls == len(G.nodes) + G.n_edges + len(exits)
    assert counts.graph_least_bytes(g.sizes) == 2 * 21_805_368
