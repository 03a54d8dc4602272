"""Arena-backed executor: numeric transparency + realized-vs-planned bytes.

The contract under test (DESIGN.md §6): executing a schedule through the
planned arena must (a) reproduce the plain interpreter's outputs exactly,
and (b) realize — measured from executed alloc/free events, not estimated —
a live-byte high-water equal to ``ArenaPlan.peak_bytes`` and a byte extent
equal to ``ArenaPlan.arena_bytes``.
"""

import re

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro import obs  # noqa: E402
from repro.core import (  # noqa: E402
    ExecutorError,
    Graph,
    compile_plan,
    execute,
    execute_plan,
    executor,
    plan_arena_best,
    run_reference,
    schedule,
)
from repro.graphs import BENCHMARK_GRAPHS  # noqa: E402
from repro.kernels.arena import arena_accum, arena_read, arena_write  # noqa: E402
from repro.kernels.arena.ref import (  # noqa: E402
    arena_accum_ref,
    arena_read_ref,
    arena_write_ref,
)

PAPER_GRAPHS = ["darts_imagenet_cell", "swiftnet_cell_c", "randwire_cifar10"]


def _inputs(g, seed=0):
    rng = np.random.default_rng(seed)
    return {
        g.nodes[i].name: rng.standard_normal(g.sizes[i] // 4)
        .astype(np.float32)
        for i in g.entries() if g.nodes[i].op == "input"
    }


def _max_err(ref, outs):
    assert set(ref) == set(outs)
    return max(float(jnp.max(jnp.abs(ref[k] - outs[k]))) for k in ref)


# ---------------------------------------------------------------- acceptance

@pytest.mark.parametrize("name", PAPER_GRAPHS)
@pytest.mark.parametrize("rewrite", [False, True], ids=["plain", "rewritten"])
def test_execute_matches_reference_and_realizes_plan(name, rewrite):
    res = schedule(BENCHMARK_GRAPHS[name](), rewrite=rewrite,
                   inplace=rewrite, compute_baselines=False)
    g = res.graph
    inputs = _inputs(g)
    ref = run_reference(g, inputs)
    ex = execute_plan(g, res.order, res.arena, inputs)
    assert _max_err(ref, ex.outputs) <= 1e-5
    # realized == planned, exactly (strict=True above already asserted it)
    assert ex.realized_peak_bytes == res.arena.peak_bytes
    assert ex.realized_arena_bytes == res.arena.arena_bytes
    assert ex.realized_matches_plan


def test_execute_convenience_schedules_when_no_plan():
    g = BENCHMARK_GRAPHS["swiftnet_cell_c"]()
    ex = execute(g, _inputs(g))
    assert ex.realized_matches_plan
    with pytest.raises(ExecutorError, match="order"):
        res = schedule(g, compute_baselines=False)
        execute(res.graph, _inputs(res.graph), res.arena)


# ------------------------------------------------------- rewritten aliasing

def _concat_depthconv_graph():
    return Graph.build([
        dict(name="i", op="input", size_bytes=64),
        dict(name="a", op="conv", size_bytes=64, preds=[0]),
        dict(name="b", op="conv", size_bytes=128, preds=[0]),
        dict(name="cc", op="concat", size_bytes=192, preds=[1, 2]),
        dict(name="dw", op="depthconv", size_bytes=192, preds=[3]),
        dict(name="out", op="op", size_bytes=32, preds=[4]),
    ])


def test_concat_view_executes_without_materializing():
    res = schedule(_concat_depthconv_graph(), compute_baselines=False,
                   cache=False)
    g = res.graph
    assert any(nd.op == "concat_view" for nd in g.nodes)
    x = {"i": np.linspace(-1.0, 1.0, 16, dtype=np.float32)}
    ref = run_reference(g, x)
    ex = execute_plan(g, res.order, res.arena, x)
    assert _max_err(ref, ex.outputs) == 0.0
    assert ex.realized_matches_plan
    # the parts sit back-to-back inside the view's buffer
    view = next(nd for nd in g.nodes if nd.op == "concat_view")
    offs = sorted(res.arena.offset_of(p) for p in view.preds)
    assert offs[0] == res.arena.offset_of(view.id)
    sizes = sorted((res.arena.offset_of(p), g.sizes[p]) for p in view.preds)
    assert sizes[0][0] + sizes[0][1] == sizes[1][0]


def test_mixed_alias_concat_view_is_refused():
    # a concat_view aliasing only SOME preds has no arena layout for the
    # rest: the executor must refuse instead of silently zero-filling
    g = Graph.build([
        dict(name="i", op="input", size_bytes=32),
        dict(name="a", op="conv", size_bytes=32, preds=[0]),
        dict(name="b", op="conv", size_bytes=32, preds=[0]),
        dict(name="v", op="concat_view", size_bytes=64, preds=[1, 2],
             alias_preds=[1]),
    ])
    from repro.core import kahn_schedule
    order = kahn_schedule(g).order
    plan = plan_arena_best(g, order)
    with pytest.raises(ExecutorError, match="not all aliased"):
        execute_plan(g, order, plan, inputs=None)
    # the reference interpreter still defines its semantics
    assert "v" in run_reference(g, None)


def test_pallas_interpret_path_matches_xla_path():
    res = schedule(_concat_depthconv_graph(), compute_baselines=False,
                   cache=False)
    x = {"i": np.linspace(-1.0, 1.0, 16, dtype=np.float32)}
    a = execute_plan(res.graph, res.order, res.arena, x, impl="xla")
    b = execute_plan(res.graph, res.order, res.arena, x, impl="pallas",
                     interpret=True)
    assert _max_err(a.outputs, b.outputs) == 0.0


@pytest.mark.parametrize("name", ["swiftnet_cell_c"])
def test_pallas_interpret_on_rewritten_cell(name):
    # covers the in-place accumulate kernel on real partial-conv chains
    res = schedule(BENCHMARK_GRAPHS[name](), compute_baselines=False)
    ref = run_reference(res.graph, _inputs(res.graph))
    ex = execute_plan(res.graph, res.order, res.arena, _inputs(res.graph),
                      impl="pallas", interpret=True)
    assert _max_err(ref, ex.outputs) == 0.0
    assert ex.realized_matches_plan


def test_jit_and_donated_arena():
    res = schedule(_concat_depthconv_graph(), compute_baselines=False,
                   cache=False)
    x = {"i": np.linspace(-1.0, 1.0, 16, dtype=np.float32)}
    ref = run_reference(res.graph, x)
    arena = jnp.zeros(-(-res.arena.arena_bytes // 4), jnp.float32)
    ex = execute_plan(res.graph, res.order, res.arena, x, arena=arena,
                      jit=True)
    assert _max_err(ref, ex.outputs) <= 1e-5
    # an undersized donated arena is rejected up front
    with pytest.raises(ExecutorError, match="donated arena"):
        execute_plan(res.graph, res.order, res.arena, x,
                     arena=jnp.zeros(3, jnp.float32))


def test_strict_catches_plan_schedule_mismatch():
    g = BENCHMARK_GRAPHS["randwire_cifar10"]()
    res = schedule(g, rewrite=False, compute_baselines=False)
    # a different (valid) schedule does not realize this plan's lifetimes
    other = g.topo_order()
    if other == res.order:
        pytest.skip("topo order equals DP order on this seed")
    with pytest.raises(ExecutorError, match="realized arena diverges"):
        execute_plan(res.graph, other, res.arena, _inputs(res.graph))


# --------------------------------------------------------- input resolution

def _two_input_plan():
    """Two inputs of different sizes, scheduled in reverse id order: a
    sequence of inputs that followed the schedule would swap them."""
    g = Graph.build([
        dict(name="a", op="input", size_bytes=64),
        dict(name="b", op="input", size_bytes=96),
        dict(name="c", op="conv", size_bytes=64, preds=[0, 1]),
        dict(name="out", op="op", size_bytes=32, preds=[2]),
    ])
    order = [1, 0, 2, 3]
    return g, order, plan_arena_best(g, order)


def _two_inputs(g):
    return [jnp.linspace(-1.0, 1.0, g.sizes[u] // 4) for u in (0, 1)]


def test_input_sequence_follows_input_node_id_order():
    g, order, plan = _two_input_plan()
    xa, xb = _two_inputs(g)
    ref = run_reference(g, {"a": xa, "b": xb})
    assert _max_err(ref, run_reference(g, [xa, xb])) == 0.0
    ex = execute_plan(g, order, plan, [xa, xb])
    assert _max_err(ref, ex.outputs) == 0.0


@pytest.mark.parametrize("form", ["sequence", "names", "ids"])
def test_given_inputs_skip_the_default(form, monkeypatch):
    g, order, plan = _two_input_plan()
    xa, xb = _two_inputs(g)
    inputs = {"sequence": [xa, xb], "names": {"a": xa, "b": xb},
              "ids": {0: xa, 1: xb}}[form]
    ref = run_reference(g, {"a": xa, "b": xb})
    before = obs.counters()
    # the first call traces the program, whose node ops use the ramp too
    ex = execute(g, inputs, plan, order=order, jit=True)
    jax.block_until_ready(ex.outputs)

    def no_ramp(uid, n):
        raise AssertionError(f"default ramp computed for given input {uid}")

    monkeypatch.setattr(executor, "_ramp", no_ramp)
    for _ in range(10):
        ex = execute(g, inputs, plan, order=order, jit=True)
        jax.block_until_ready(ex.outputs)
    after = obs.counters()
    assert _max_err(ref, ex.outputs) <= 1e-5
    assert after.get("execute.default_input", 0) == \
        before.get("execute.default_input", 0)
    assert after.get("program.trace", 0) - \
        before.get("program.trace", 0) == 1


@pytest.mark.parametrize("given", [None, "b"], ids=["none", "partial"])
def test_defaulted_inputs_are_unchanged(given):
    g, order, plan = _two_input_plan()
    xb = _two_inputs(g)[1]
    inputs = None if given is None else {given: xb}
    prog = compile_plan(g, order, plan)
    before = obs.counters()
    ext = executor._resolve_inputs(g, inputs)
    prog_ext = dict(zip(order[:2], prog.resolve_ext(inputs)))
    after = obs.counters()
    defaulted = [0, 1] if given is None else [0]
    for u in defaulted:
        want = np.asarray(executor._ramp(u, g.sizes[u] // 4) / 0.05 * 0.3)
        assert want.dtype == np.float32
        for got in (ext[u], prog_ext[u]):
            got = np.asarray(got)
            assert got.dtype == np.float32
            assert np.array_equal(got.view(np.uint32), want.view(np.uint32))
    if given is not None:
        assert np.array_equal(np.asarray(ext[1]), np.asarray(xb))
        assert np.array_equal(np.asarray(prog_ext[1]), np.asarray(xb))
    # two resolutions above, each defaulting the same inputs
    assert after.get("execute.default_input", 0) - \
        before.get("execute.default_input", 0) == 2 * len(defaulted)


@pytest.mark.parametrize("inputs, msg", [
    ({"zz": np.zeros(16, np.float32)}, "unknown input 'zz'"),
    ({2: np.zeros(16, np.float32)}, "unknown input 2"),
    ([np.zeros(16, np.float32)], "graph has 2 inputs, got 1"),
    ([np.zeros(16, np.float32)] * 3, "graph has 2 inputs, got 3"),
], ids=["unknown-name", "non-input-id", "too-few", "too-many"])
def test_input_errors_are_unchanged(inputs, msg):
    g, order, plan = _two_input_plan()
    with pytest.raises(ExecutorError, match=f"^{re.escape(msg)}$"):
        run_reference(g, inputs)
    with pytest.raises(ExecutorError, match=f"^{re.escape(msg)}$"):
        execute_plan(g, order, plan, inputs)


# ------------------------------------------------------------ arena kernels

@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_arena_ops_match_ref_oracle(impl):
    rng = np.random.default_rng(3)
    arena = jnp.asarray(rng.standard_normal(32).astype(np.float32))
    x = jnp.asarray(rng.standard_normal(5).astype(np.float32))
    kw = dict(impl=impl, interpret=True)
    np.testing.assert_array_equal(
        arena_write(arena, x, 7, **kw), arena_write_ref(arena, x, 7))
    np.testing.assert_allclose(
        arena_accum(arena, x, 7, **kw), arena_accum_ref(arena, x, 7),
        rtol=1e-6)
    np.testing.assert_array_equal(
        arena_read(arena, 7, 5, **kw), arena_read_ref(arena, 7, 5))


# ------------------------------------------------------------- real tensors

def test_pack_unpack_roundtrip_mixed_dtypes():
    from repro.core.executor import pack_buffers, unpack_buffer
    from repro.core import kahn_schedule

    arrays = {
        0: jnp.asarray(np.arange(12, dtype=np.float32).reshape(3, 4)),
        1: jnp.asarray(np.arange(8, dtype=np.int32)),
        2: jnp.asarray(np.linspace(0, 1, 16, dtype=np.float32)
                       .astype(jnp.bfloat16)),
    }
    specs = [dict(name=f"b{i}", op="cache",
                  size_bytes=int(np.prod(a.shape)) * a.dtype.itemsize,
                  preds=[]) for i, a in arrays.items()]
    specs.append(dict(name="sink", op="act", size_bytes=8,
                      preds=[0, 1, 2]))
    g = Graph.build(specs)
    plan = plan_arena_best(g, kahn_schedule(g).order)
    arena = pack_buffers(plan, arrays)
    assert arena.dtype == jnp.uint8 and arena.shape[0] == plan.arena_bytes
    for nid, a in arrays.items():
        back = unpack_buffer(arena, plan, nid, a.shape, a.dtype)
        np.testing.assert_array_equal(np.asarray(back), np.asarray(a))


# -------------------------------------------------------------- jaxpr path

def test_compile_scheduled_nas_like():
    from repro.core.jax_bridge import compile_scheduled

    def nas_like(x):
        branches = []
        for i in range(4):
            h = jnp.tanh(x * (i + 1.0))
            h = h @ jnp.ones((x.shape[-1], 2 * x.shape[-1]), x.dtype)
            h = jax.nn.relu(h) @ jnp.ones((2 * x.shape[-1], 8), x.dtype)
            branches.append(h)
        return jnp.sum(jnp.concatenate(branches, -1) ** 2)

    x = jnp.ones((16, 32), jnp.float32)
    fn = compile_scheduled(nas_like, cache=False)
    y = fn(x)                      # asserts equivalence internally too
    assert jnp.allclose(y, nas_like(x), atol=1e-5)
    r = fn.report
    assert r.realized_bytes == r.optimal_peak > 0
    assert r.realized_matches_plan
    assert r.arena_bytes >= r.optimal_peak


def test_compile_scheduled_mixed_dtypes_and_pytree():
    from repro.core.jax_bridge import compile_scheduled

    def mixed(a, b):
        c = (a * 2).astype(jnp.bfloat16)
        d = jnp.sum(c.astype(jnp.float32)) + b
        return {"c": c, "d": d, "count": (a > 0).sum()}

    fn = compile_scheduled(mixed, cache=False)
    a = jnp.linspace(-1, 1, 40).reshape(5, 8)
    out = fn(a, jnp.float32(3.0))
    assert out["c"].dtype == jnp.bfloat16
    assert fn.report.realized_matches_plan
    assert fn.report.n_env_bypassed >= 1          # the bool intermediate
