"""Arena-backed execution of SERENITY schedules (DESIGN.md §6).

The scheduler/allocator stack plans *where* every intermediate tensor lives
(`ScheduleResult.order` + `ArenaPlan` byte offsets); this module closes the
loop by actually *running* a graph against that plan: one donated linear
arena buffer holds every intermediate, each node reads its predecessors as
slices at their planned offsets and writes its output at its own offset
(``repro.kernels.arena``: XLA ``dynamic_slice``/``dynamic_update_slice`` on
CPU/GPU, Pallas slice kernels on TPU).  Alias chains from the rewriter
execute without copies: in-place nodes overwrite their predecessor's slice,
``concat_view`` parts slice-write back-to-back into the view's buffer, so
the rewritten concat is never materialized.

Because benchmark graphs carry only byte costs (not tensor semantics),
node computation uses a *surrogate numerics* registry: every tensor is a
flat float32 vector of ``size_bytes / 4`` elements and every op is a
deterministic, value- and position-sensitive function of its inputs.  The
executor's correctness contract is *schedule/arena transparency*: for any
graph and any valid (order, plan), ``execute_plan`` must produce bit-for-bit
the values of the plain dict-storage interpreter ``run_reference`` — a wrong
offset, a premature overwrite, or a mis-laid concat part shows up as a
numeric mismatch.

Alongside values, execution *measures* the arena (realized, not estimated):

  ``realized_peak_bytes``  -- high-water of live bytes resident in the arena,
                              tracked from executed alloc/free events; must
                              equal ``ArenaPlan.peak_bytes`` exactly.
  ``realized_arena_bytes`` -- high-water byte extent (max live offset+size);
                              must equal ``ArenaPlan.arena_bytes`` exactly.

``strict=True`` (default) asserts both equalities — the realized-vs-planned
invariant of DESIGN.md §6.

Execution has two granularities (DESIGN.md §11): the default
*slice-per-node* path issues one arena read per predecessor and one write
per node — maximally transparent, every dataflow edge round-trips through
the arena — and the *fused* path (``fuse=True``) executes each in-place
alias chain (:func:`repro.core.rewriter.fuse_alias_chains`) as one region:
the running value is forwarded in registers between chain members and the
chain's shared slice is written once (a single Pallas launch /
``dynamic_update_slice`` for pure-elementwise tails).  Both paths are
bit-equal to ``run_reference`` and realize the same planned footprint.

Public entry points
-------------------
run_reference(g, inputs)                   -> {output name: value}
reference_fn(g)                            -> jit-able unscheduled baseline
execute_plan(g, order, plan, inputs, ...)  -> ExecutionResult
compile_plan(g, order, plan, ...)          -> PlanProgram (precompiled,
                                              memoized on the plan)
RealizedTracker                            -- the measurement machinery
pack_buffers / unpack_buffer               -- move real (shaped, dtyped)
                                              tensors in/out of a planned
                                              uint8 arena (serving state)
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Mapping, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.allocator import ArenaPlan
from repro.core.graph import Graph, Node
from repro.core.rewriter import FusedRegion, fuse_alias_chains
from repro.kernels.arena import (
    arena_accum,
    arena_chain_write,
    arena_read,
    arena_write,
)
from repro.kernels.arena.elemwise import ELEMWISE_FNS


class ExecutorError(ValueError):
    pass


# ---------------------------------------------------------------------------
# Surrogate numerics: deterministic per-op value functions on flat float32
# ---------------------------------------------------------------------------

# unary elementwise ops (the in-place-eligible set plus synonyms); the
# canonical table lives in repro.kernels.arena.elemwise so the fused chain
# kernels apply the exact same jnp callables (bit-equality by construction)
_ELEMWISE: dict[str, Callable] = ELEMWISE_FNS

OpFn = Callable[[Node, list, int], "jnp.ndarray"]


def _fit(x, n: int):
    """Resize a flat vector to ``n`` elements (truncate or tile)."""
    m = x.shape[0]
    if m == n:
        return x
    if m > n or m == 0:
        return jnp.zeros(n, x.dtype) if m == 0 else x[:n]
    reps = -(-n // m)
    return jnp.tile(x, reps)[:n]


def _concat_pad(xs, n: int):
    """Concatenate then zero-pad/truncate to ``n`` elements.

    This is the reference semantics of ``concat``/``concat_view``: the arena
    path realizes it as back-to-back slice-writes plus a zeroed tail, so the
    reference must pad with zeros (never tile)."""
    if not xs:
        return jnp.zeros(n, jnp.float32)
    cc = jnp.concatenate(xs) if len(xs) > 1 else xs[0]
    if cc.shape[0] >= n:
        return cc[:n]
    return jnp.concatenate([cc, jnp.zeros(n - cc.shape[0], cc.dtype)])


def _ramp(uid: int, n: int):
    # per-node positional signature: makes off-by-one-slice bugs visible
    return 0.05 * jnp.cos(jnp.arange(n, dtype=jnp.float32)
                          * (0.37 + 0.013 * (uid % 29)))


def _blend(xs, n: int):
    if not xs:
        return jnp.zeros(n, jnp.float32)
    acc = _fit(xs[0], n)
    for x in xs[1:]:
        acc = acc + _fit(x, n)
    return acc / len(xs)


def _sig(nd: Node) -> int:
    """The node id keying the positional signature.

    Recompute clones (``repro.core.rewriter.rematerialize``) carry their
    original's id as ``recompute_sig`` metadata; using it here makes a
    clone compute bit-for-bit the same value as the node it rematerializes,
    for every op — the executor-side half of the recompute contract.
    """
    for k, v in nd.meta:
        if k == "recompute_sig":
            return int(v)
    return nd.id


def _default_op(nd: Node, xs, n: int):
    acc = _blend(xs, n)
    acc = jnp.tanh(acc + 0.25 * jnp.roll(acc, 1))
    return 0.9 * acc + _ramp(_sig(nd), n)


def _partial_conv_contrib(nd: Node, branch_xs, n: int):
    """The per-branch accumulation step of a rewritten partial conv."""
    t = _blend(branch_xs, n)
    return 0.4 * jnp.tanh(t + 0.25 * jnp.roll(t, 1)) + 0.1 * _ramp(_sig(nd), n)


def _split_accum(nd: Node, invals):
    """(accumulator value or None, branch values) for an accumulating node."""
    acc, branches = None, []
    for p, v in zip(nd.preds, invals):
        if p in nd.alias_preds and acc is None:
            acc = v
        else:
            branches.append(v)
    return acc, branches


def node_value(nd: Node, invals, n: int,
               registry: Mapping[str, OpFn] | None = None):
    """Reference output of ``nd`` given predecessor values (``(n,)`` f32).

    ``registry`` overrides/extends the built-in op table; entries are called
    as ``fn(node, raw_pred_values, n_elements)``.
    """
    if registry is not None and nd.op in registry:
        return registry[nd.op](nd, invals, n)
    if nd.op in ("concat", "concat_view"):
        return _concat_pad(invals, n)
    if nd.op == "partial_conv":
        acc, branches = _split_accum(nd, invals)
        contrib = _partial_conv_contrib(nd, branches, n)
        return contrib if acc is None else acc + contrib
    if nd.op == "add":
        return _blend(invals, n)
    if nd.op in _ELEMWISE and len(invals) == 1:
        return _ELEMWISE[nd.op](_fit(invals[0], n))
    return _default_op(nd, invals, n)


# ---------------------------------------------------------------------------
# Input / output plumbing
# ---------------------------------------------------------------------------


def _elems(nbytes: int, what: str) -> int:
    if nbytes % 4:
        raise ExecutorError(
            f"{what}: size {nbytes} bytes is not float32-aligned (the "
            f"surrogate executor models tensors as 4-byte elements)"
        )
    return nbytes // 4


def input_nodes(g: Graph) -> list[int]:
    return [nd.id for nd in g.nodes if nd.op == "input"]


def _resolve_inputs(g: Graph, inputs) -> dict[int, "jnp.ndarray"]:
    """Accept {name: array}, {node_id: array}, or a sequence in input-node
    id order; returns flat float32 arrays keyed by node id.  Only inputs
    the caller did not give get the default ramp (counter
    ``execute.default_input``)."""
    ids = input_nodes(g)
    by_name = {g.nodes[i].name: i for i in ids}
    out: dict[int, jnp.ndarray] = {}
    if inputs is None:
        inputs = {}
    if isinstance(inputs, Mapping):
        for k, v in inputs.items():
            nid = by_name.get(k, k if isinstance(k, int) else None)
            if nid is None or nid not in ids:
                raise ExecutorError(f"unknown input {k!r}")
            out[nid] = jnp.asarray(v, jnp.float32).reshape(-1)
    else:
        vals = list(inputs)
        if len(vals) != len(ids):
            raise ExecutorError(
                f"graph has {len(ids)} inputs, got {len(vals)}")
        for nid, v in zip(ids, vals):
            out[nid] = jnp.asarray(v, jnp.float32).reshape(-1)
    missing = [nid for nid in ids if nid not in out]
    for nid in missing:
        out[nid] = (_ramp(nid, _elems(g.sizes[nid], g.nodes[nid].name))
                    / 0.05 * 0.3)
    if missing:
        obs.count("execute.default_input", len(missing))
    return out


# ---------------------------------------------------------------------------
# Realized-footprint measurement
# ---------------------------------------------------------------------------


class RealizedTracker:
    """Measure the arena from executed events (DESIGN.md §6).

    Feed it each node as it executes (`step(u)`); it activates the node's
    allocation on first touch (the whole chain buffer is reserved from its
    first write) and retires an allocation one step after its last consumer
    executed — exactly the allocator's free-before-alloc event order.  Bytes
    of graph outputs stay resident to the end.

    ``peak_bytes`` is the high-water of summed live allocation sizes;
    ``extent_bytes`` the high-water of ``offset + size`` over live
    allocations.  Both are in bytes and must reproduce the plan's
    ``peak_bytes`` / ``arena_bytes`` when execution follows the planned
    order — the realized-vs-planned invariant.
    """

    def __init__(self, g: Graph, order: Sequence[int], plan: ArenaPlan,
                 steps: Sequence[Sequence[int]] | None = None):
        self._g = g
        sched = set(order)
        horizon = len(order) if steps is None else len(steps)
        self._alloc = {u: plan.allocation_of(u) for u in order}
        self._uses: dict[int, int] = {}
        self._output: dict[int, bool] = {}
        for a in {id(a): a for a in self._alloc.values()}.values():
            uses = 0
            is_out = False
            for m in a.node_ids:
                consumers = [s for s in g.succs[m] if s in sched]
                uses += len(consumers)
                is_out |= not consumers
            self._uses[id(a)] = uses
            # a plan may hold buffers past their last consumer (pinned
            # latency-class plans set t_free beyond the horizon): honor the
            # plan's lifetime, not just graph-output-ness
            self._output[id(a)] = is_out or a.t_free > horizon
        self._active: set[int] = set()
        self._pending_retire: list = []
        self._live = 0
        self.peak_bytes = 0
        self.extent_bytes = 0

    def step(self, u: int) -> None:
        self.step_group((u,))

    def step_group(self, units: Sequence[int]) -> None:
        """One time slot: all of ``units`` execute concurrently.

        Every member's allocation is activated before the slot's peak is
        sampled (co-issued outputs are live together — the step-model
        transient of ``simulate_steps``), and predecessors fully consumed by
        the slot retire at its end, landing before the next slot's allocs.
        """
        # frees scheduled from the previous step land before this alloc
        for a in self._pending_retire:
            self._active.discard(id(a))
            self._live -= a.size
        self._pending_retire = []
        for u in units:
            a = self._alloc[u]
            if id(a) not in self._active:
                self._active.add(id(a))
                self._live += a.size
                self.extent_bytes = max(self.extent_bytes, a.offset + a.size)
        self.peak_bytes = max(self.peak_bytes, self._live)
        for u in units:
            for p in self._g.nodes[u].preds:
                pa = self._alloc.get(p)
                if pa is None:
                    continue
                self._uses[id(pa)] -= 1
                if self._uses[id(pa)] == 0 and not self._output[id(pa)] \
                        and id(pa) in self._active:
                    self._pending_retire.append(pa)


# ---------------------------------------------------------------------------
# Interpreters
# ---------------------------------------------------------------------------


def reference_fn(g: Graph,
                 registry: Mapping[str, OpFn] | None = None) -> Callable:
    """A jit-able closure computing ``g``'s reference outputs.

    Returns ``fn(ext_vals) -> tuple`` mapping a tuple of input-node values
    (input-node id order, flat float32) to the tuple of exit-node values,
    with every intermediate held as its own array — no arena, XLA plans the
    memory.  This is the *unscheduled jit* baseline of
    ``benchmarks/bench_executor.py``; :func:`run_reference` wraps it.
    """
    order = list(g.topo_order())
    nds = g.nodes
    elems = {u: _elems(g.sizes[u], nds[u].name) for u in order}

    def fn(ext_vals):
        env: dict[int, jnp.ndarray] = {}
        it = iter(ext_vals)
        for u in order:
            nd = nds[u]
            if nd.op == "input":
                env[u] = _fit(next(it), elems[u])
            else:
                env[u] = node_value(nd, [env[p] for p in nd.preds],
                                    elems[u], registry)
        return tuple(env[u] for u in g.exits())

    return fn


def run_reference(g: Graph, inputs=None, *,
                  registry: Mapping[str, OpFn] | None = None
                  ) -> dict[str, "jnp.ndarray"]:
    """Plain dict-storage interpreter: the executor's numeric ground truth.

    Runs ``g`` in topological order with every intermediate held as its own
    array (no arena).  Returns ``{node name: flat f32 value}`` for the graph
    outputs (nodes with no consumers).
    """
    ext = _resolve_inputs(g, inputs)
    vals = tuple(ext[u] for u in input_nodes(g))
    outs = reference_fn(g, registry)(vals)
    return {g.nodes[u].name: v for u, v in zip(g.exits(), outs)}


@dataclasses.dataclass
class ExecutionResult:
    """What ``execute_plan`` produced and measured.

    ``outputs`` maps output-node names to their flat float32 values (read
    back from the final arena).  All ``*_bytes`` fields are bytes;
    ``realized_*`` are measured from execution, ``planned_*`` copied from
    the plan.
    """

    outputs: dict[str, "jnp.ndarray"]
    realized_peak_bytes: int
    realized_arena_bytes: int
    planned_peak_bytes: int
    planned_arena_bytes: int
    order: list[int]
    impl: str
    fused: bool = False
    n_regions: int = 0

    @property
    def realized_matches_plan(self) -> bool:
        return (self.realized_peak_bytes == self.planned_peak_bytes
                and self.realized_arena_bytes == self.planned_arena_bytes)


class PlanProgram:
    """A precompiled executable for one ``(graph, order, plan)`` triple.

    Everything derivable from the plan alone is computed once at
    construction — float32 element counts, per-node element offsets, the
    realized peak/extent (the :class:`RealizedTracker` replay is a pure
    function of the schedule), the fused-region decomposition and each
    region's elementwise tail — so calling :meth:`run` only feeds values
    through the arena program.  ``execute_plan`` used to re-derive all of
    this on every call, which dominated on the 274-node full networks; it
    now routes through :func:`compile_plan`, which memoizes instances on
    the plan itself.  The whole-program jit (``jit=True``) is traced once
    per program and reused, arena donated.

    With ``fuse=False`` the program replays the slice-per-node path
    bit-for-bit (one read per predecessor, one write/accumulate per node).
    With ``fuse=True`` each :class:`~repro.core.rewriter.FusedRegion` runs
    as one unit: the running chain value is forwarded in registers from
    member to member (legal because an aliased predecessor has exactly one
    consumer — nothing else ever reads the interior values) and only the
    final member's value is stored, through
    :func:`~repro.kernels.arena.arena_chain_write` when the region tail is
    pure unregistered elementwise (one launch), else a single
    ``arena_write``.  Cross-region edges still round-trip through the
    arena, so the fused path realizes the identical footprint and stays
    bit-equal to ``run_reference`` (DESIGN.md §11).
    """

    def __init__(self, g: Graph, order: Sequence[int], plan: ArenaPlan, *,
                 fuse: bool = False,
                 registry: Mapping[str, OpFn] | None = None,
                 impl: str = "auto", interpret: bool = False,
                 steps: Sequence[Sequence[int]] | None = None):
        self.graph = g
        self.order = list(order)
        self.plan = plan
        self.steps = None if steps is None else tuple(
            tuple(s) for s in steps)
        self.fuse = bool(fuse)
        self.registry = registry
        self.impl = impl
        self.interpret = interpret
        nds = g.nodes
        self._elems = {u: _elems(g.sizes[u], nds[u].name)
                       for u in self.order}
        off = {}
        for u in self.order:
            b = plan.offset_of(u)
            if b % 4:
                raise ExecutorError(
                    f"node {nds[u].name}: planned byte offset {b} is not "
                    f"float32-aligned")
            off[u] = b // 4
        self._off = off
        self.arena_elems = -(-plan.arena_bytes // 4)
        self._input_ids = [u for u in self.order if nds[u].op == "input"]
        self._exit_ids = list(g.exits())

        # rewriter-produced views alias every predecessor; a mixed view has
        # no arena layout for the non-aliased parts — refuse rather than
        # silently diverge from run_reference
        for u in self.order:
            nd = nds[u]
            if nd.op == "concat_view" and nd.alias_preds and \
                    any(p not in nd.alias_preds for p in nd.preds):
                raise ExecutorError(
                    f"concat_view {nd.name}: preds {nd.preds} are not "
                    f"all aliased ({sorted(nd.alias_preds)}); mixed "
                    f"views are not executable")

        # a width-W step schedule executes member ops of one slot against
        # simultaneously-live storage: the plan must place every co-issued
        # slot disjointly (the steps were the plan's lifetime positions)
        if self.steps is not None:
            if [u for s in self.steps for u in s] != self.order:
                raise ExecutorError("steps do not flatten to order")
            for st in self.steps:
                if len(st) < 2:
                    continue
                in_step = set(st)
                spans = []
                for u in st:
                    if set(nds[u].preds) & in_step:
                        raise ExecutorError(
                            f"step {st} is not an antichain: {nds[u].name} "
                            f"reads a co-issued node")
                    a = plan.allocation_of(u)
                    spans.append((a.offset, a.offset + a.size, u, id(a)))
                spans.sort()
                for s0, s1 in zip(spans, spans[1:]):
                    if s1[0] < s0[1] and s1[3] != s0[3]:
                        raise ExecutorError(
                            f"co-issued nodes {nds[s0[2]].name} and "
                            f"{nds[s1[2]].name} overlap in the arena "
                            f"([{s0[0]}, {s0[1]}) vs [{s1[0]}, {s1[1]})); "
                            f"plan the arena with steps= to keep them "
                            f"disjoint")

        # realized footprint is a pure function of (g, order, plan): replay
        # it once here instead of on every execution
        tracker = RealizedTracker(g, self.order, plan, steps=self.steps)
        if self.steps is not None:
            for st in self.steps:
                tracker.step_group(st)
        else:
            for u in self.order:
                tracker.step(u)
        self.realized_peak_bytes = tracker.peak_bytes
        self.realized_arena_bytes = tracker.extent_bytes

        if self.fuse:
            self.regions = fuse_alias_chains(g, self.order, plan)
        else:
            self.regions = [FusedRegion((u,)) for u in self.order]
        # interior members forward their value in registers (no arena write)
        self._interior = {u for r in self.regions for u in r.node_ids[:-1]}
        # collapse schedule-contiguous pure-elementwise chain runs ending at
        # a region tail into one arena_chain_write launch:
        #   {schedule position of run head: (members consumed, ops, tail id)}
        link_next: dict[int, int] = {}
        for r in self.regions:
            for a, b in zip(r.node_ids, r.node_ids[1:]):
                link_next[a] = b
        self._groups: dict[int, tuple[int, tuple[str, ...], int]] = {}
        consumed: set[int] = set()
        for i, u in enumerate(self.order):
            if i in consumed:
                continue
            j, ops = i, []
            while j + 1 < len(self.order):
                nxt = link_next.get(self.order[j])
                if nxt is None or self.order[j + 1] != nxt:
                    break
                nd = nds[nxt]
                if (nd.op not in ELEMWISE_FNS or len(nd.preds) != 1
                        or (registry is not None and nd.op in registry)):
                    break
                ops.append(nd.op)
                j += 1
            if ops and self.order[j] not in self._interior:
                self._groups[i] = (j - i, tuple(ops), self.order[j])
                consumed.update(range(i + 1, j + 1))
        self._jitted = None

    @property
    def n_regions(self) -> int:
        return len(self.regions)

    @property
    def n_fused_nodes(self) -> int:
        """Chain members executed without their own arena write."""
        return sum(len(r) - 1 for r in self.regions)

    # -- program body ------------------------------------------------------

    def _zero_view_tail(self, arena, u):
        # concat_view parts already sit back-to-back inside this buffer: the
        # concat never materializes.  Zero any tail the parts do not cover
        # so the view equals the reference's zero-pad.
        n, covered = self._elems[u], sum(self._elems[p]
                                         for p in self.graph.nodes[u].preds)
        if covered < n:
            arena = arena_write(
                arena, jnp.zeros(n - covered, jnp.float32),
                self._off[u] + covered, impl=self.impl,
                interpret=self.interpret)
        return arena

    def _body_slice(self, arena, ext_it):
        """Slice-per-node: one read per predecessor, one store per node."""
        nds = self.graph.nodes
        elems, off = self._elems, self._off
        impl, interpret, registry = self.impl, self.interpret, self.registry
        for u in self.order:
            nd = nds[u]
            if nd.op == "concat_view" and nd.alias_preds:
                arena = self._zero_view_tail(arena, u)
                continue
            if nd.op == "input":
                arena = arena_write(arena, next(ext_it), off[u], impl=impl,
                                    interpret=interpret)
                continue
            invals = [arena_read(arena, off[p], elems[p], impl=impl,
                                 interpret=interpret) for p in nd.preds]
            if nd.op == "partial_conv" and nd.alias_preds and \
                    (registry is None or nd.op not in registry):
                # in-place accumulation into the (aliased) running output —
                # a true read-modify-write of the shared slice
                branches = [v for p, v in zip(nd.preds, invals)
                            if p not in nd.alias_preds]
                contrib = _partial_conv_contrib(nd, branches, elems[u])
                arena = arena_accum(arena, contrib, off[u], impl=impl,
                                    interpret=interpret)
                continue
            arena = arena_write(arena, node_value(nd, invals, elems[u],
                                                  registry),
                                off[u], impl=impl, interpret=interpret)
        return arena

    def _body_fused(self, arena, ext_it):
        """Fused: chain members forward their value in registers; only the
        region tail stores.  Legal because an aliased predecessor has
        exactly one consumer — the next chain member — so nothing an
        interleaved node does can observe (or clobber: the chain's
        allocation is live throughout) the skipped interior stores.
        Schedule-contiguous pure-elementwise runs ending at a tail execute
        as one ``arena_chain_write`` launch."""
        nds = self.graph.nodes
        elems, off = self._elems, self._off
        impl, interpret, registry = self.impl, self.interpret, self.registry
        order = self.order
        fwd: dict = {}
        i = 0
        while i < len(order):
            u = order[i]
            nd = nds[u]
            if nd.op == "concat_view" and nd.alias_preds:
                arena = self._zero_view_tail(arena, u)
                i += 1
                continue
            if nd.op == "input":
                val = next(ext_it)
            else:
                invals = [fwd[p] if p in fwd
                          else arena_read(arena, off[p], elems[p], impl=impl,
                                          interpret=interpret)
                          for p in nd.preds]
                val = node_value(nd, invals, elems[u], registry)
                for p in nd.preds:
                    fwd.pop(p, None)  # single consumer: value is dead now
            grp = self._groups.get(i)
            if grp is not None:
                m, ops, out = grp
                arena = arena_chain_write(arena, val, off[out], ops,
                                          impl=impl, interpret=interpret)
                i += m + 1
                continue
            if u in self._interior:
                fwd[u] = val
            else:
                arena = arena_write(arena, val, off[u], impl=impl,
                                    interpret=interpret)
            i += 1
        return arena

    def _program(self, arena, ext_flat):
        # the body runs once per jit trace (or per eager call)
        obs.count("program.trace")
        body = self._body_fused if self.fuse else self._body_slice
        arena = body(arena, iter(ext_flat))
        outs = tuple(arena_read(arena, self._off[u], self._elems[u],
                                impl=self.impl, interpret=self.interpret)
                     for u in self._exit_ids)
        return outs, arena

    # -- entry point -------------------------------------------------------

    def resolve_ext(self, inputs) -> tuple:
        """Flatten/resize user inputs to the program's input tuple."""
        ext = _resolve_inputs(self.graph, inputs)
        return tuple(_fit(ext[u], self._elems[u]) for u in self._input_ids)

    def run(self, inputs=None, *, arena=None, jit: bool = False,
            strict: bool = True) -> ExecutionResult:
        """Execute the program; see :func:`execute_plan` for semantics."""
        plan = self.plan
        with obs.span("execute.resolve_inputs"):
            ext_vals = self.resolve_ext(inputs)
        if arena is None:
            with obs.span("execute.alloc_arena"):
                arena = jnp.zeros(self.arena_elems, jnp.float32)
        elif strict and arena.shape[0] < self.arena_elems:
            raise ExecutorError(
                f"donated arena has {arena.shape[0]} elements "
                f"({arena.shape[0] * 4} bytes) < planned arena_bytes "
                f"{plan.arena_bytes}")
        if strict and (self.realized_peak_bytes != plan.peak_bytes
                       or self.realized_arena_bytes != plan.arena_bytes):
            raise ExecutorError(
                f"realized arena diverges from plan: peak "
                f"{self.realized_peak_bytes} vs planned {plan.peak_bytes}, "
                f"extent {self.realized_arena_bytes} vs planned "
                f"{plan.arena_bytes}")

        with obs.span("execute.dispatch"):
            if jit:
                if self._jitted is None:
                    self._jitted = jax.jit(self._program,
                                           donate_argnums=(0,))
                outs, _ = self._jitted(arena, ext_vals)
            else:
                outs, _ = self._program(arena, ext_vals)

        nds = self.graph.nodes
        return ExecutionResult(
            outputs={nds[u].name: v for u, v in zip(self._exit_ids, outs)},
            realized_peak_bytes=self.realized_peak_bytes,
            realized_arena_bytes=self.realized_arena_bytes,
            planned_peak_bytes=plan.peak_bytes,
            planned_arena_bytes=plan.arena_bytes,
            order=list(self.order),
            impl=self.impl,
            fused=self.fuse,
            n_regions=self.n_regions,
        )


_PROGRAM_CACHE_CAP = 8


def compile_plan(
    g: Graph,
    order: Sequence[int],
    plan: ArenaPlan,
    *,
    fuse: bool = False,
    registry: Mapping[str, OpFn] | None = None,
    impl: str = "auto",
    interpret: bool = False,
    steps: Sequence[Sequence[int]] | None = None,
) -> PlanProgram:
    """Build (or fetch) the :class:`PlanProgram` for this plan.

    Programs are memoized on the plan object itself (like its offset
    index), keyed by the schedule and execution options, so repeat
    executions — the decode tick loop, benchmark steady state — skip the
    per-plan precomputation and reuse the cached jit trace.  The cache is
    dropped on pickling (``ArenaPlan.__getstate__``) and capped per plan.
    """
    steps_key = None if steps is None else tuple(tuple(s) for s in steps)
    key = (id(g), tuple(order), bool(fuse), impl, bool(interpret),
           None if registry is None else id(registry), steps_key)
    cache = plan.__dict__.setdefault("_programs", {})
    prog = cache.get(key)
    # ids can be recycled after gc: accept a hit only if it still points at
    # the same live objects
    if prog is not None and prog.graph is g and \
            (registry is None or prog.registry is registry):
        return prog
    with obs.span("program.build"):
        prog = PlanProgram(g, order, plan, fuse=fuse, registry=registry,
                           impl=impl, interpret=interpret, steps=steps)
    obs.count("program.build")
    cache[key] = prog
    while len(cache) > _PROGRAM_CACHE_CAP:
        cache.pop(next(iter(cache)))
    return prog


def execute_plan(
    g: Graph,
    order: Sequence[int],
    plan: ArenaPlan,
    inputs=None,
    *,
    registry: Mapping[str, OpFn] | None = None,
    impl: str = "auto",
    interpret: bool = False,
    arena=None,
    jit: bool = False,
    strict: bool = True,
    fuse: bool = False,
    steps: Sequence[Sequence[int]] | None = None,
) -> ExecutionResult:
    """Run schedule ``order`` of ``g`` against the planned arena.

    Args:
      g: the graph to execute (typically ``SerenityResult.graph`` — i.e.
        post-rewrite, so alias chains are present).
      order: the schedule to execute; must be the order ``plan`` was built
        from (the realized-vs-planned invariant is asserted against it).
      plan: the :class:`ArenaPlan` whose byte offsets place every tensor.
      inputs: input-node values ({name: array}, {node_id: array}, or a
        sequence in input-node order); missing inputs get a deterministic
        per-node default.  Values are flattened to float32.
      registry: optional op-function overrides (see :func:`node_value`).
      impl: arena slice op dispatch — 'auto' (Pallas on TPU, XLA elsewhere;
        ``$REPRO_ARENA_IMPL`` overrides), 'pallas', 'xla', or 'ref'.
      interpret: run Pallas kernels in interpret mode (CPU validation).
      arena: optional donated float32 buffer of at least
        ``plan.arena_bytes / 4`` elements to execute in (reused storage,
        e.g. across decode steps); allocated fresh when ``None``.
      jit: trace the whole arena program into one jitted function with the
        arena buffer donated to XLA (trace cached per plan/options).
      strict: assert the realized-vs-planned invariant and that the arena
        buffer is large enough.
      fuse: execute in-place alias chains as fused regions — value
        forwarding between members, one write (or one chain-kernel launch)
        per region instead of per node (DESIGN.md §11).  Bit-equal to the
        default slice-per-node path.
      steps: optional width-W step schedule (must flatten to ``order``, and
        ``plan`` must have been packed with the same ``steps``).  Values
        still stream through the arena one op at a time — co-issued ops'
        outputs are bit-identical because the plan places them disjointly
        (asserted) — but the realized footprint is replayed in step groups,
        so the realized-vs-planned invariant checks the *concurrent* peak
        (DESIGN.md §12).

    Returns:
      :class:`ExecutionResult` with output values and the measured
      realized peak/extent bytes.
    """
    return compile_plan(g, order, plan, fuse=fuse, registry=registry,
                        impl=impl, interpret=interpret, steps=steps).run(
        inputs, arena=arena, jit=jit, strict=strict)


# ---------------------------------------------------------------------------
# Real-tensor arena packing (serving state)
# ---------------------------------------------------------------------------


def _to_bytes(x) -> "jnp.ndarray":
    """Flatten any (non-bool) array to its raw little-endian uint8 bytes."""
    x = jnp.asarray(x)
    if x.dtype == jnp.bool_:
        raise ExecutorError("bool tensors cannot be arena-packed")
    # bitcast appends an itemsize axis for multi-byte dtypes (none for u8)
    return jax.lax.bitcast_convert_type(x.reshape(-1),
                                        jnp.uint8).reshape(-1)


def _from_bytes(b, shape, dtype) -> "jnp.ndarray":
    """Rebuild an array of ``shape``/``dtype`` from its raw bytes."""
    dtype = jnp.dtype(dtype)
    if dtype.itemsize == 1:
        return jax.lax.bitcast_convert_type(b, dtype).reshape(shape)
    return jax.lax.bitcast_convert_type(
        b.reshape(-1, dtype.itemsize), dtype).reshape(shape)


def pack_buffers(plan: ArenaPlan, arrays: Mapping[int, "jnp.ndarray"], *,
                 arena=None, impl: str = "auto",
                 jit: bool = True) -> "jnp.ndarray":
    """Pack real tensors into one uint8 arena at their planned byte offsets.

    ``arrays`` maps node ids (of the graph the plan was built from) to
    arbitrarily shaped/dtyped tensors; each must fit the node's planned
    span in bytes.  Returns the (donatable) uint8 arena of
    ``plan.arena_bytes`` bytes.  The pack loop is jitted with the arena
    donated by default, so XLA fuses it into one in-place pack instead of
    copying the whole arena once per tensor.  Used by the serving driver to
    realize the decode-state plan (DESIGN.md §1/§6).
    """
    items = sorted(arrays.items())
    for nid, x in items:
        a = plan.allocation_of(nid)
        span = a.size - a.intra.get(nid, 0)
        nbytes = int(np.prod(jnp.shape(x))) * jnp.dtype(
            jnp.result_type(x)).itemsize
        if nbytes > span:
            raise ExecutorError(
                f"node {nid}: {nbytes} bytes exceed planned span {span}")

    def _pack(arena, vals):
        for (nid, _), x in zip(items, vals):
            arena = arena_write(arena, _to_bytes(x), plan.offset_of(nid),
                                impl=impl)
        return arena

    if arena is None:
        arena = jnp.zeros(plan.arena_bytes, jnp.uint8)
    vals = tuple(x for _, x in items)
    if jit:
        return jax.jit(_pack, donate_argnums=(0,))(arena, vals)
    return _pack(arena, vals)


def unpack_buffer(arena, plan: ArenaPlan, node_id: int, shape, dtype, *,
                  impl: str = "auto") -> "jnp.ndarray":
    """Read one planned tensor back out of a uint8 arena."""
    nbytes = int(np.prod(shape)) * jnp.dtype(dtype).itemsize
    b = arena_read(arena, plan.offset_of(node_id), nbytes, impl=impl)
    return _from_bytes(b, shape, dtype)
