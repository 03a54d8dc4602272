"""The published DARTS_V2 ImageNet network (``darts_imagenet_network``).

At full size the generator builds in milliseconds, so its structure is
checked there: node count, weights against the published 4.7M, where the
reductions fall, the feature-map sizes, the two inputs of every cell, the
logits, and a rewritten plan that is exact and lower than the plain one.
Execution runs at a small size that keeps both reductions: the rewritten
plan's outputs are compared with the plain host reference of the
benchmark (``chipbench/bench/graph_ref.py``), which evaluates the
surrogate node by node with no arena and no schedule, and the realized
arena bytes with the planned ones.
"""

import time
from pathlib import Path

import numpy as np
import pytest

from repro.core import PlanConfig, plan
from repro.graphs import darts_imagenet_network

ROOT = Path(__file__).resolve().parents[1]
SMALL = dict(layers=5, channels=8, image_hw=32, classes=10)
# float32 against float32: the surrogate's tanh/cos and the blend's
# division round differently in XLA and numpy, ~1e-7 at these magnitudes
# on the CPU; 1e-5 leaves room and still catches any misplaced slice
# (errors of order 0.1-1)
F32_TOL = 1e-5
# (spatial size, channels) of each published cell's intermediate nodes
CELL_SHAPES = [(28, 48)] * 4 + [(14, 96)] * 5 + [(7, 192)] * 5


@pytest.fixture(scope="module")
def net():
    return darts_imagenet_network()


@pytest.fixture(scope="module")
def benchmark_path():
    """The checkout root on the import path, for ``chipbench``."""
    with pytest.MonkeyPatch.context() as mp:
        mp.syspath_prepend(str(ROOT))
        yield


def _by_name(g):
    return {nd.name: nd for nd in g.nodes}


def test_published_size(net):
    assert len(net.nodes) == 709
    params = sum(nd.weight_bytes for nd in net.nodes) // 4
    assert abs(params - 4.7e6) / 4.7e6 < 0.01
    assert sum(nd.op == "concat" for nd in net.nodes) == 14 + 3


def test_stem_feature_maps(net):
    nodes = _by_name(net)
    assert nodes["image"].size_bytes == 224 * 224 * 3 * 4
    assert nodes["stem0.conv0"].size_bytes == 112 * 112 * 24 * 4
    assert nodes["stem0.bn1"].size_bytes == 56 * 56 * 48 * 4
    assert nodes["stem1.bn"].size_bytes == 28 * 28 * 48 * 4


@pytest.mark.parametrize("ci", range(14))
def test_cell_shape_and_inputs(net, ci):
    nodes = _by_name(net)
    hw, c = CELL_SHAPES[ci]
    reduction = ci in (4, 9)
    assert nodes[f"c{ci}.n2.add"].size_bytes == hw * hw * c * 4
    assert nodes[f"c{ci}.concat"].size_bytes == 4 * hw * hw * c * 4
    pools = [nd for nd in net.nodes
             if nd.name.startswith(f"c{ci}.") and nd.op == "pool"]
    assert len(pools) == (5 if reduction else 0)
    # c_{k-2} and c_{k-1}: the two cells before, or the two stems
    outs = ["stem0.bn1", "stem1.bn"] + [f"c{k}.concat" for k in range(14)]
    want = [nodes[outs[ci]].id, nodes[outs[ci + 1]].id]
    got = [list(nodes[f"c{ci}.pre{j}.relu"].preds) for j in (0, 1)]
    assert got == [[want[0]], [want[1]]]
    assert want[0] != want[1]
    # a FactorizedReduce where the cell before reduced (the stem counts)
    assert (f"c{ci}.pre0.concat" in nodes) == (ci in (0, 5, 10))


def test_single_output_is_the_logits(net):
    exits = [nd for nd in net.nodes if not net.succs[nd.id]]
    assert [nd.name for nd in exits] == ["head.fc"]
    assert exits[0].size_bytes == 1000 * 4
    assert _by_name(net)["head.pool"].size_bytes == 768 * 4


def test_rewritten_plan_is_exact_and_lower(net):
    plain = plan(net, PlanConfig(rewrite=False), cache=False)
    rw = plan(net, PlanConfig(rewrite=True), cache=False)
    assert rw.exact
    assert rw.peak_bytes < plain.peak_bytes
    assert any(nd.alias_preds for nd in rw.graph.nodes)


@pytest.fixture(scope="module")
def small_plan():
    res = plan(darts_imagenet_network(**SMALL), PlanConfig(rewrite=True),
               cache=False)
    assert sum(bool(nd.alias_preds) for nd in res.graph.nodes) > 50
    return res


@pytest.mark.parametrize(
    "jit,impl",
    [(True, "xla"), (False, "xla"), (True, "pallas")],
    ids=["jit-xla", "eager-xla", "jit-pallas-interpret"])
def test_rewritten_plan_executes_like_the_reference(small_plan,
                                                    benchmark_path, jit,
                                                    impl):
    import jax

    from chipbench.bench import graph_ref
    from repro.core import execute

    res = small_plan
    G = res.graph
    ids = [nd.id for nd in G.nodes if nd.op == "input"]
    rng = np.random.default_rng(17)
    xs = [rng.standard_normal(G.sizes[u] // 4).astype(np.float32)
          for u in ids]
    ex = execute(G, xs, res.arena, order=res.order, jit=jit, impl=impl,
                 interpret=impl == "pallas")
    jax.block_until_ready(ex.outputs)
    nodes = [graph_ref.RefNode(nd.id, nd.op, nd.size_bytes, tuple(nd.preds),
                               frozenset(nd.alias_preds), nd.id)
             for nd in G.nodes]
    want = graph_ref.run(nodes, dict(zip(ids, xs)))
    assert set(ex.outputs) == {G.nodes[u].name for u in want} == {"head.fc"}
    err = max(float(np.max(np.abs(np.asarray(ex.outputs[G.nodes[u].name])
                                  - v))) for u, v in want.items())
    assert err <= F32_TOL
    assert ex.realized_peak_bytes == res.arena.peak_bytes
    assert ex.realized_arena_bytes == res.arena.arena_bytes


def test_benchmark_cell_checks_pass_and_bf16_control_fails(benchmark_path):
    from chipbench.bench import harness

    cell = harness.load_cell("darts-imagenet-exec")
    assert cell.config["graph"] == {"generator": "darts_imagenet_network",
                                    "args": {}}
    assert cell.config["plan"] == {"rewrite": True}
    cell.config["graph"]["args"] = dict(SMALL)
    cell.params["check_steps"] = 8
    res = harness.run_cell(cell, seed=2**31 + 12345, seconds=1.0,
                           trace=False, t_start=time.perf_counter(),
                           require_tpu=False, control=True)
    checks = res["checks"]
    assert res["correct"], checks
    assert checks["peak_bytes_gap"]["value"] == 0
    assert checks["extent_bytes_gap"]["value"] == 0
    limit = checks["max_abs_err"]["limit"]
    assert checks["max_abs_err"]["value"] <= limit
    assert checks["control_max_abs_err"]["value"] > limit
    assert "exec_ms" in res["metrics"]
