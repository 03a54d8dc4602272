"""A planned graph run in its arena (``kind: graph_exec``).

Set-up plans the configuration's graph cold (``plan()``; no plan cache is
configured, so every run plans), makes the traffic's seeded inputs on the
device, and runs each once so the arena program compiles.  The window
calls the public ``repro.core.execute`` back to back, one step in flight,
cycling those inputs, each step ending in ``block_until_ready``.  The
check compares a seeded sample of the window's outputs with the plain
host reference (:mod:`graph_ref`) and the realized arena bytes with the
planned ones.
"""

from __future__ import annotations

import time

import numpy as np

from chipbench.bench import counts, graph_ref
from chipbench.bench.harness import Check
from chipbench.bench.traffic import rng_for


class ExecCell:
    def __init__(self, cell, seed, spans, peaks):
        self.cell = cell
        self.seed = seed
        self.spans = spans
        self.peaks = peaks

    def setup(self) -> None:
        import jax.numpy as jnp

        import repro.graphs
        from repro.core import PlanConfig, execute, plan

        self.execute = execute
        cfg = self.cell.config
        g = getattr(repro.graphs, cfg["graph"]["generator"])(
            **cfg["graph"]["args"])
        with self.spans("plan"):
            t = time.perf_counter()
            res = plan(g, PlanConfig(**cfg["plan"]), cache=False)
            self.plan_s = time.perf_counter() - t
        self.res = res
        G = res.graph
        self.input_ids = [nd.id for nd in G.nodes if nd.op == "input"]
        rng = rng_for(self.seed, 3)
        self.host_inputs = [
            [rng.standard_normal(G.sizes[u] // 4).astype(np.float32)
             for u in self.input_ids]
            for _ in range(int(self.cell.mix["inputs"]))]
        self.inputs = [[jnp.asarray(x) for x in xs]
                       for xs in self.host_inputs]
        with self.spans("warm-up"):
            for xs in self.inputs:
                self._step(xs)
        # the work of one step, for the rooflines
        pos = {u: i for i, u in enumerate(res.order)}
        nodes = [(G.nodes[u].op, G.sizes[u],
                  [G.sizes[p] for p in G.nodes[u].preds],
                  [G.sizes[p] for p in G.nodes[u].alias_preds])
                 for u in sorted(res.order, key=pos.get)]
        exits = [G.sizes[u] for u in range(len(G.nodes))
                 if not G.succs[u]]
        self.slices = counts.slice_work(nodes, exits)
        self.least_s = counts.least_seconds(
            0, counts.graph_least_bytes(g.sizes), self.peaks)

    def _step(self, xs):
        import jax

        res = self.res
        ex = self.execute(res.graph, xs, res.arena, order=res.order,
                          jit=True)
        jax.block_until_ready(ex.outputs)
        return ex

    def window(self, seconds: float, tracer) -> dict:
        keep = int(self.cell.params["check_steps"])
        rng = rng_for(self.seed, 4)
        sample: list = []                 # (step, input index, outputs)
        n_in = len(self.inputs)
        if tracer is not None:
            tracer.start()
        t0 = time.perf_counter()
        paused = 0.0
        i = 0
        while True:
            with self.spans("execute"):
                ex = self._step(self.inputs[i % n_in])
            # reservoir sample of the window's answers, drawn from the seed
            if len(sample) < keep:
                sample.append((i, i % n_in, ex))
            else:
                j = int(rng.integers(0, i + 1))
                if j < keep:
                    sample[j] = (i, i % n_in, ex)
            i += 1
            if tracer is not None:
                paused += tracer.poll(i)
            if time.perf_counter() - t0 - paused >= seconds:
                break
        window_s = time.perf_counter() - t0 - paused
        self.sample = sample
        self.last = ex
        return {"steps": i, "attempted": i, "window_s": window_s,
                "plan_s": self.plan_s, "least_s": i * self.least_s,
                "slice_calls": self.slices.calls,
                "slice_bytes": self.slices.nbytes}

    def free(self) -> None:
        self.sample = [(i, k, {n: np.asarray(v) for n, v in
                               ex.outputs.items()})
                       for i, k, ex in self.sample]
        self.inputs = None

    def check(self, control: bool = False) -> list[Check]:
        G = self.res.graph
        arena = self.res.arena
        names = {nd.name: nd.id for nd in G.nodes}
        nodes = [graph_ref.RefNode(
            nd.id, nd.op, nd.size_bytes, tuple(nd.preds),
            frozenset(nd.alias_preds),
            int(dict(nd.meta).get("recompute_sig", nd.id)))
            for nd in G.nodes]
        refs = {}
        worst = 0.0
        failed = 0
        limit = float(self.cell.params["limits"]["max_abs_err"])
        for _, k, outs in self.sample:
            if k not in refs:
                refs[k] = graph_ref.run(
                    nodes, dict(zip(self.input_ids, self.host_inputs[k])))
            want = refs[k]
            if set(outs) != {G.nodes[u].name for u in want}:
                err = float("inf")
            else:
                err = max(float(np.max(np.abs(outs[n] - want[names[n]])))
                          for n in outs)
            worst = max(worst, err)
            failed += not err <= limit
        self.failed = failed
        ex = self.last
        checks = [
            Check("max_abs_err", worst, limit),
            Check("peak_bytes_gap",
                  float(abs(ex.realized_peak_bytes - arena.peak_bytes)), 0.0),
            Check("extent_bytes_gap",
                  float(abs(ex.realized_arena_bytes - arena.arena_bytes)),
                  0.0),
        ]
        if control:
            ctrl = 0.0
            for k in refs:
                low = graph_ref.run(
                    nodes, dict(zip(self.input_ids, self.host_inputs[k])),
                    dtype=graph_ref.BF16)
                ctrl = max(ctrl, max(float(np.max(np.abs(low[u] - v)))
                                     for u, v in refs[k].items()))
            checks.append(Check("control_max_abs_err", ctrl, limit))
        return checks
