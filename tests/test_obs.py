"""The program's spans and counters (``repro.obs``) and where the executor
records them."""

import threading

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from repro import obs  # noqa: E402
from repro.core import PlanConfig, execute, plan  # noqa: E402
from repro.graphs import BENCHMARK_GRAPHS  # noqa: E402


def test_spans_nest_and_share_a_call_id():
    with obs.recording() as rec:
        with obs.span("a"):
            with obs.span("a.b"):
                with obs.span("a.b.c"):
                    pass
            with obs.span("a.d"):
                pass
        with obs.span("e"):
            pass
    # closed innermost first
    assert [s.name for s in rec.spans] == ["a.b.c", "a.b", "a.d", "a", "e"]
    by = {s.name: s for s in rec.spans}
    assert [by[n].parent for n in ("a", "a.b", "a.b.c", "a.d", "e")] == \
        [None, "a", "a.b", "a", None]
    assert len({by[n].call_id for n in ("a", "a.b", "a.b.c", "a.d")}) == 1
    assert by["e"].call_id != by["a"].call_id
    for s in rec.spans:
        assert s.t0_ns <= s.t1_ns
    assert by["a"].t0_ns <= by["a.b"].t0_ns <= by["a.b.c"].t0_ns
    assert by["a.b.c"].t1_ns <= by["a.b"].t1_ns <= by["a.d"].t0_ns
    assert by["a.d"].t1_ns <= by["a"].t1_ns


def test_nothing_is_recorded_when_recording_is_off():
    with obs.recording() as rec:
        pass
    with obs.span("outside"):
        pass
    assert rec.spans == []
    # off, a span is only the profiler's annotation
    assert isinstance(obs.span("x"), jax.profiler.TraceAnnotation)
    with obs.recording():
        assert not isinstance(obs.span("x"), jax.profiler.TraceAnnotation)


def test_a_span_closes_when_its_block_raises():
    with obs.recording() as rec:
        with pytest.raises(KeyError):
            with obs.span("outer"):
                with obs.span("outer.inner"):
                    raise KeyError("x")
        with obs.span("next"):
            pass
    assert [(s.name, s.parent) for s in rec.spans] == \
        [("outer.inner", "outer"), ("outer", None), ("next", None)]


def test_threads_keep_their_own_nesting():
    barrier = threading.Barrier(2)

    def work(tag):
        with obs.span(tag):
            barrier.wait(timeout=10)
            with obs.span(tag + ".in"):
                barrier.wait(timeout=10)

    with obs.recording() as rec:
        ts = [threading.Thread(target=work, args=(t,)) for t in "xy"]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=10)
    assert not any(t.is_alive() for t in ts)
    by = {s.name: s for s in rec.spans}
    assert by["x.in"].parent == "x" and by["y.in"].parent == "y"
    assert by["x.in"].call_id == by["x"].call_id != by["y"].call_id


def test_counters_add_and_snapshot():
    before = obs.counters()
    obs.count("test.obs.counter")
    obs.count("test.obs.counter", 4)
    after = obs.counters()
    assert after["test.obs.counter"] - before.get("test.obs.counter", 0) == 5
    after["test.obs.counter"] = -1          # a copy, not the live table
    assert obs.counters()["test.obs.counter"] != -1


def _planned():
    # a fresh plan: its program cache starts empty
    return plan(BENCHMARK_GRAPHS["randwire_cifar10"](), PlanConfig(),
                cache=False)


def test_execute_records_its_phases_in_order():
    res = _planned()
    x = [np.ones(res.graph.sizes[u] // 4, np.float32)
         for u in range(len(res.graph.nodes))
         if res.graph.nodes[u].op == "input"]
    with obs.recording() as rec:
        ex = execute(res.graph, x, res.arena, order=res.order, jit=True)
    jax.block_until_ready(ex.outputs)
    top = [s for s in rec.spans if s.name == "execute"]
    assert len(top) == 1
    (root,) = top
    inside = sorted((s for s in rec.spans if s.parent == "execute"
                     and s.name.startswith("execute.")),
                    key=lambda s: s.t0_ns)
    assert [s.name for s in inside] == [
        "execute.resolve_inputs", "execute.alloc_arena", "execute.dispatch"]
    for s in inside:
        assert s.call_id == root.call_id
        assert root.t0_ns <= s.t0_ns <= s.t1_ns <= root.t1_ns
    # a plan's first execution builds its program, inside the call
    (build,) = [s for s in rec.spans if s.name == "program.build"]
    assert build.parent == "execute" and build.call_id == root.call_id


def test_one_build_and_one_trace_over_repeated_calls():
    res = _planned()
    before = obs.counters()
    for i in range(10):
        ex = execute(res.graph, None, res.arena, order=res.order, jit=True)
        jax.block_until_ready(ex.outputs)
    after = obs.counters()
    for name in ("program.build", "program.trace"):
        assert after.get(name, 0) - before.get(name, 0) == 1, name
