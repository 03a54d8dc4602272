"""Multi-tenant serving: request queue + budgeted arena pool + batched decode.

    PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-1b --smoke \
        --requests 8 --prompt-len 32 --gen 16 --budget-mb 4

SERENITY integration (DESIGN.md §1/§9): every request's decode state is
arena-planned by the paper's machinery — KV caches pinned resident at the
bottom of the plan (:func:`repro.core.allocator.plan_arena_regions`), the
per-step transients (embed/attn/MLP activations, logits) stacked above —
and the request then *leases* that plan from a budgeted
:class:`repro.runtime.pool.ArenaPool`.  Admission charges the joint
co-residency extent (:func:`repro.core.allocator.plan_shared_arena`):
requests are admitted, queued FIFO, or rejected against one global device
byte budget, and the admitted set's transient slack is shared, so the pool
sustains far more concurrency than one-arena-per-request under the same
budget (``benchmarks/bench_serving.py`` measures both).

The decode loop is continuously batched: each server step advances every
admitted request by one token, the batch composition re-forms as requests
finish and queued requests take their bytes, and each request's KV state
lives *packed in its leased arena buffer at the planned byte offsets*
between steps (``pack_buffers``/``unpack_buffer``).  Two step modes:

  ``serial``  (default) one jitted bsz=1 decode reused for every active
              request, executed back-to-back — transients of distinct
              requests are never live together, matching the pool's
              ``overlap='serial'`` admission accounting.
  ``vmap``    all active requests advance in ONE jitted arena->arena
              program: the active arenas are stacked into a
              ``(bucket, extent)`` uint8 matrix (donated), each row
              unpacked at the planned byte offsets, decoded and packed
              back entirely inside the vmapped XLA program — no Python
              loop over leases.  Programs are cached per power-of-two
              batch bucket; padding rows beyond the live batch are charged
              to the pool budget (``ArenaPool.reserve_scratch``) for the
              step, falling back to an exact-size bucket when they do not
              fit.  All members' transients materialize at once, so
              admission must use ``overlap='none'`` accounting.
"""

from __future__ import annotations

import argparse
import dataclasses
import time
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

import repro.configs as configs
from repro.core import Graph, PlanConfig, pin_transients, plan
from repro.core.allocator import resident_bytes
from repro.core.executor import pack_buffers, unpack_buffer
from repro.core.plancache import default_cache
from repro.launch.compile_cache import configure_compile_cache
from repro.launch.mesh import make_production_mesh, rules_for_mesh
from repro.launch.steps import make_decode_step, make_prefill_step
from repro.models.params import ParamDef
from repro.models.zoo import build_model
from repro.runtime.chaos import ChaosController, TransientExecutorError
from repro.runtime.fleet import Fleet, PlannerService, bucket_key_for
from repro.runtime.loadgen import OpenLoopLoadGen, workload_summary
from repro.runtime.pool import ArenaPool, PoolError

#: Pareto request classes decode admission serves (DESIGN.md §12): a
#: ``memory`` request leases the tight regions plan (transients time-share
#: their bytes — maximum co-residency under the budget), a ``latency``
#: request the same layout with every transient pinned always-live
#: (:func:`~repro.core.allocator.pin_transients`) — it pays more bytes so
#: its step never waits on buffer reuse inside a shared arena.
REQUEST_CLASSES = ("memory", "latency")


def _align4(n: int) -> int:
    return -(-int(n) // 4) * 4


def decode_state_graph(model, bsz: int, smax: int) -> tuple[Graph, int]:
    """The serve-schedule dataflow graph for one request's decode step.

    Nodes 0..C-1 are the persistent KV-cache buffers (graph outputs: state
    that survives between steps); above them the per-step transient chain —
    embedding activation, per-layer attention + MLP activations, logits,
    sampled token — each consumed by the next, so the arena planner can
    time-share their bytes.  Returns ``(graph, n_cache_leaves)``; cache
    node ids equal the ``jax.tree`` leaf order of ``make_cache_defs``,
    which is what ``pack_decode_state`` relies on.
    """
    defs = model.make_cache_defs(bsz, smax)
    leaves = jax.tree.leaves(defs, is_leaf=lambda x: isinstance(x, ParamDef))
    specs = []
    for i, d in enumerate(leaves):
        nbytes = _align4(int(np.prod(d.shape)) * np.dtype(d.dtype).itemsize)
        specs.append(dict(name=f"cache{i}", op="cache", size_bytes=nbytes,
                          preds=[]))
    cfg = model.cfg
    D, F, V = cfg.d_model, cfg.d_ff, cfg.vocab_size
    prev = None

    def chain(name, op, nbytes):
        nonlocal prev
        specs.append(dict(name=name, op=op, size_bytes=_align4(nbytes),
                          preds=[] if prev is None else [prev]))
        prev = len(specs) - 1

    chain("embed_out", "act", bsz * D * 4)
    for li in range(cfg.n_layers):
        chain(f"l{li}.attn", "act", bsz * D * 4)
        chain(f"l{li}.mlp", "act", bsz * F * 4)
        chain(f"l{li}.out", "act", bsz * D * 4)
    chain("logits", "act", bsz * V * 4)
    chain("token", "act", bsz * 4)
    return Graph.build(specs, name="decode_state"), len(leaves)


def plan_decode_arena(model, bsz: int, smax: int) -> dict:
    """Arena-plan one request's decode state with the SERENITY allocator.

    The KV caches are pinned resident at the bottom of the arena (they
    persist between steps, so their bytes can never be time-shared) and the
    per-step transients are planned above them
    (:func:`~repro.core.allocator.plan_arena_regions`).  The plan is
    memoized in the content-addressed plan cache: every replica serving the
    same (arch, batch, seq) shape — and every later request for it in this
    process — reuses the first plan in O(graph hash).
    """
    g, n_cache = decode_state_graph(model, bsz, smax)
    pc = default_cache()
    cache_opts = ("serve.plan_decode_arena", 3)   # 3: PlanConfig-planned
    out = pc.get(g, cache_opts)
    if out is None:
        # resident: the KV caches and the sampled token — everything the
        # request carries between steps (the token node also keeps the
        # logits buffer transient: it is the logits' consumer).  The Kahn
        # scheduler is deliberate: decode state is dozens of *isolated*
        # persistent buffers, which the exact DP models as an exponential
        # bitmask space with nothing to gain over the greedy order.
        cfg = PlanConfig(
            rewrite=False, inplace=False, scheduler="kahn",
            resident=(*range(n_cache), len(g) - 1),
            compute_baselines=False)
        res = plan(g, cfg, cache=pc)
        apl = res.arena
        naive = sum(g.sizes)
        pers, extent = resident_bytes(apl)
        out = {"arena_bytes": apl.arena_bytes, "naive_bytes": naive,
               "peak_bytes": apl.peak_bytes, "policy": apl.policy,
               "frag_ratio": apl.frag_ratio,
               "persistent_bytes": pers, "resident_extent": extent,
               "transient_bytes": apl.arena_bytes - extent,
               "n_buffers": len(g), "n_cache": n_cache, "plan": apl,
               "graph": g, "order": res.order}
        pc.put(g, cache_opts, out)
    return out


def pack_decode_state(plan: dict, cache, arena=None):
    """Pack a decode-state pytree into (the resident region of) an arena.

    The cache leaves land at their planned byte offsets; the returned uint8
    buffer covers the plan's resident extent (the persistent region — the
    transient region above it exists only during a step and is never
    materialized per request).  Pass ``arena`` to reuse a leased buffer
    (donated to the jitted pack).
    """
    leaves, _ = jax.tree.flatten(cache)
    if arena is None:
        arena = jnp.zeros(plan["resident_extent"], jnp.uint8)
    return pack_buffers(plan["plan"], dict(enumerate(leaves)), arena=arena)


def unpack_decode_state(plan: dict, arena, defs_like):
    """Rebuild the decode-state pytree from its planned arena offsets."""
    leaves, treedef = jax.tree.flatten(defs_like)
    apl = plan["plan"]
    rebuilt = [unpack_buffer(arena, apl, i, leaf.shape, leaf.dtype)
               for i, leaf in enumerate(leaves)]
    return jax.tree.unflatten(treedef, rebuilt)


def realize_decode_state(plan: dict, cache):
    """Initialize the decode state through the planned arena.

    Packs the initial cache leaves into one uint8 arena buffer at their
    planned byte offsets (jitted, arena donated) and rebuilds the cache
    pytree from slices of it, so the state the decode loop starts from is
    materialized at the plan's offsets rather than ad-hoc per-buffer
    allocations.  Returns (arena, rebuilt_cache).
    """
    arena = pack_decode_state(plan, cache)
    return arena, unpack_decode_state(plan, arena, cache)


# ---------------------------------------------------------------------------
# Request-queue server with continuous batching
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Request:
    """One generation request moving through submit -> admit -> decode."""

    rid: int
    prompt: np.ndarray               # (P,) int32 token ids
    max_new: int
    klass: str | None = None         # Pareto request class (REQUEST_CLASSES;
                                     # None = classless base-plan admission)
    priority: int = 0                # higher = preempted later
    tenant: str | None = None        # quota bucket (ArenaPool.tenant_quotas)
    submit_s: float = 0.0
    admit_s: float = 0.0
    done_s: float = 0.0
    tokens: list = dataclasses.field(default_factory=list)
    rejected: bool = False
    reject_code: str = ""            # machine-readable cause (Ticket.reason_code)
    reject_reason: str = ""
    preemptions: int = 0             # times this request was spilled
    # runtime state while admitted
    lease: object = None
    arena: object = None             # leased uint8 buffer holding the KV state
    spill: object = None             # SpilledLease while preempted
    t: int = 0                       # decode position (cache_len)
    last_tok: int = 0

    @property
    def latency_s(self) -> float:
        return self.done_s - self.submit_s


@dataclasses.dataclass
class TickWatchdog:
    """Per-tick deadline + stall escalation for the serving loop.

    Two concerns (DESIGN.md §13): a *deadline* — ticks slower than
    ``step_deadline_s`` are counted (``deadline_misses``) — and a *stall* —
    ``stall_ticks`` consecutive ticks with no observable progress (no
    token, no admission, no release, no queue movement) escalate instead
    of silently spinning: :meth:`observe` returns ``True`` and the server
    raises :class:`ServingStallError` carrying the structured queue
    diagnostics.
    """

    step_deadline_s: float | None = None
    stall_ticks: int = 64            # > the max readmit backoff (2^5 ticks)
    ticks: int = 0
    deadline_misses: int = 0
    slowest_tick_s: float = 0.0
    stagnant_ticks: int = 0          # consecutive no-progress ticks
    escalations: int = 0

    def observe(self, dt: float, progressed: bool) -> bool:
        """Record one tick; True when stall escalation is due."""
        self.ticks += 1
        self.slowest_tick_s = max(self.slowest_tick_s, dt)
        if self.step_deadline_s is not None and dt > self.step_deadline_s:
            self.deadline_misses += 1
        self.stagnant_ticks = 0 if progressed else self.stagnant_ticks + 1
        if self.stagnant_ticks >= self.stall_ticks:
            self.escalations += 1
            self.stagnant_ticks = 0
            return True
        return False

    def as_dict(self) -> dict:
        return {"ticks": self.ticks,
                "deadline_misses": self.deadline_misses,
                "slowest_tick_s": self.slowest_tick_s,
                "escalations": self.escalations}


class ServingStallError(RuntimeError):
    """The decode loop provably cannot make progress.

    ``report`` is the structured diagnostics dict: every queued request's
    rid/class/priority/tenant and its per-request ``_fits`` failure
    reason, plus the pool's reserved/budget bytes at escalation time.
    """

    def __init__(self, message: str, report: dict):
        super().__init__(message)
        self.report = report


class DecodeServer:
    """Continuous-batching decode server over a budgeted arena pool.

    Each :meth:`step` (one scheduler tick):

      1. admits queued requests the pool now has bytes for (prefill fills
         their KV cache, which is packed into the leased arena),
      2. advances every admitted request by one decode token — the *batch*
         is the admitted set, re-formed every tick as requests finish,
      3. releases finished requests' leases (their warm buffers go to the
         pool LRU; the freed bytes admit the queue head).

    Between ticks every request's KV state lives packed in its leased
    arena buffer at the planned byte offsets.

    Robustness layer (DESIGN.md §13): a mid-run :meth:`set_budget` shrink
    (or an injected admission fault) triggers the graceful-degradation
    ladder — (1) re-plan a ``latency``-class request at its
    memory-optimal Pareto point, (2) shrink vmap buckets to the exact
    batch / drop padding scratch, (3) preempt the lowest-priority lease
    (spill its packed KV state to host, re-admit later with bounded
    retry + exponential backoff).  A :class:`TickWatchdog` escalates
    stalls with structured queue diagnostics, and a ``chaos=``
    :class:`~repro.runtime.chaos.ChaosController` drives deterministic
    fault injection through the hooks.
    """

    def __init__(self, model, params, pool: ArenaPool, *, smax: int,
                 rules=None, step_mode: str = "serial",
                 chaos: ChaosController | None = None,
                 step_deadline_s: float | None = None,
                 stall_ticks: int = 64,
                 max_readmit_attempts: int = 5,
                 max_transient_retries: int = 3):
        if step_mode not in ("serial", "vmap"):
            raise ValueError(f"unknown step_mode {step_mode!r}")
        if step_mode == "vmap" and pool.overlap == "serial":
            raise ValueError(
                "step_mode='vmap' materializes every active request's "
                "transients at once; the pool must use overlap='none' "
                "admission accounting")
        self.model = model
        self.params = params
        self.pool = pool
        self.smax = smax
        self.step_mode = step_mode
        self.rules = rules
        self._prefill = jax.jit(make_prefill_step(model, rules))
        self._decode = jax.jit(make_decode_step(model, rules))
        self._batched: dict[int, object] = {}   # bucket -> jitted step
        self._plan = plan_decode_arena(model, 1, smax)
        # register our regions plan with the pool once; submits reuse the
        # key (no per-request graph re-fingerprinting)
        self._key, _ = pool.plan(self._plan["graph"], self._plan["order"],
                                 plan=self._plan["plan"])
        # the decode state's Pareto request classes (DESIGN.md §12): both
        # keep the regions layout (identical offsets, so pack/unpack and
        # the jitted steps are class-agnostic) but charge admission
        # differently — 'latency' pins its transients always-live
        pool.register_pareto(self._key, {
            "memory": self._plan["plan"],
            "latency": pin_transients(self._plan["plan"]),
        })
        self._tickets: dict[int, Request] = {}
        self.active: list[Request] = []
        self.done: list[Request] = []
        # robustness state (DESIGN.md §13)
        self.chaos = chaos
        if chaos is not None:
            if pool.admission_hook is not None:
                raise ValueError(
                    "chaos= takes ownership of pool.admission_hook, but "
                    "the pool already has one installed; construct the "
                    "pool without admission_hook= or inject admission "
                    "faults through the chaos FaultPlan instead")
            pool.admission_hook = chaos.admission_should_fail
        self.max_readmit_attempts = max_readmit_attempts
        self.max_transient_retries = max_transient_retries
        self.watchdog = TickWatchdog(step_deadline_s=step_deadline_s,
                                     stall_ticks=stall_ticks)
        self._tick = 0
        self._spilled: list[Request] = []       # preempted, awaiting readmit
        self._exact_buckets = False             # ladder rung 2 latch
        self._scratch_token = None              # vmap padding reservation
        self.ladder = {"replan": 0, "shrink_buckets": 0, "preempt": 0}
        self.transient_errors = 0
        self._transient_streak = 0
        self._last_tick_s = 0.0
        self.min_budget_bytes = pool.budget_bytes
        self.max_over_budget_bytes = 0
        self.last_stall: dict | None = None

    # -- admission ---------------------------------------------------------

    def warm(self, n_buffers: int = 1) -> None:
        """Startup warming: pre-plan + pre-allocate arenas for this shape."""
        for _ in range(n_buffers):
            self.pool.warm(self._plan["graph"], key=self._key)

    def submit(self, req: Request) -> None:
        req.submit_s = time.perf_counter()
        # the pool holds *our* regions plan under self._key, so lease
        # buffers, admission accounting and the state pack/unpack all
        # address one set of offsets; a classed request leases its
        # registered Pareto-point plan instead (same offsets, different
        # admission charge)
        ticket = self.pool.submit(self._plan["graph"], key=self._key,
                                  klass=req.klass, priority=req.priority,
                                  tenant=req.tenant)
        if ticket.rejected:
            self._finish_rejected(req, ticket)
            return
        self._tickets[ticket.rid] = req

    def _finish_rejected(self, req: Request, ticket) -> None:
        req.rejected = True
        req.reject_code = ticket.reason_code
        req.reject_reason = ticket.reason
        req.done_s = time.perf_counter()
        req.spill = None
        self.done.append(req)

    def _collect_rejected(self) -> None:
        """Retire queued requests a budget-shrink sweep rejected."""
        for ticket in self.pool.poll_rejected():
            req = self._tickets.pop(ticket.rid, None)
            if req is not None:
                self._finish_rejected(req, ticket)

    def _start(self, ticket) -> None:
        req = self._tickets.pop(ticket.rid)
        req.admit_s = time.perf_counter()
        req.lease = ticket.lease
        if req.spill is not None:
            # re-admission of a preempted request: its packed KV state is
            # self-contained (plan offsets are buffer-relative), so the
            # restore is one host->device byte copy — no re-prefill, and
            # req.t / tokens continue exactly where the spill left off
            sp, req.spill = req.spill, None
            ticket.lease.buffer = None
            req.arena = jnp.asarray(np.asarray(sp.host_state))
            req.klass = sp.klass or req.klass   # a downgrade sticks
            self.active.append(req)
            return
        P = len(req.prompt)
        cache = self.model.init_cache(1, self.smax)
        batch = {"tokens": jnp.asarray(req.prompt, jnp.int32)[None]}
        if self.model.cfg.is_encoder_decoder:
            batch["frames"] = jax.random.normal(
                jax.random.PRNGKey(req.rid), (1, P, self.model.cfg.d_model),
                jnp.float32)
        logits, cache = self._prefill(self.params, cache, batch)
        req.last_tok = int(jnp.argmax(logits, -1)[0])
        req.tokens.append(req.last_tok)
        req.t = P
        req.arena = pack_decode_state(self._plan, cache,
                                      arena=ticket.lease.buffer)
        ticket.lease.buffer = None    # ownership moved to the request
        self.active.append(req)

    # -- degradation ladder (DESIGN.md §13) ---------------------------------

    def set_budget(self, nbytes: int) -> None:
        """Shrink/grow the pool budget mid-run and enforce it.

        A shrink that leaves the admitted set over budget walks the
        degradation ladder (:meth:`_degrade_once`) until the members fit
        again — the pool itself never evicts, so this is where preemption
        happens.
        """
        over = self.pool.set_budget(nbytes)
        self.min_budget_bytes = min(self.min_budget_bytes,
                                    self.pool.budget_bytes)
        while over > 0:
            if not self._degrade_once():
                break                 # nothing left to shed (no members)
            over = self.pool.reserved_bytes - self.pool.budget_bytes

    def _preempt_request(self, req: Request,
                         downgrade_to: str | None = None) -> None:
        """Spill an active request's lease; it rejoins via readmit."""
        sp = self.pool.preempt(req.lease, state=req.arena)
        req.lease = None
        req.arena = None
        req.preemptions += 1
        if downgrade_to is not None and sp.klass != downgrade_to:
            self.pool.downgrade(sp, downgrade_to)
            req.klass = downgrade_to
        sp.next_tick = self._tick + 1   # first readmit try next tick
        req.spill = sp
        self.active.remove(req)
        self._spilled.append(req)

    def _degrade_once(self) -> bool:
        """One ladder rung; True when it shed bytes (or scratch).

        Rung 1: re-plan a ``latency``-class request at its memory-optimal
        Pareto point (preempt + downgrade + readmit — the PR 8 classes
        share offsets, so only the admission charge changes).  Rung 2:
        pin vmap decode to exact-size batch buckets and drop any padding
        scratch.  Rung 3: preempt the lowest-priority lease outright.
        """
        # admitted-but-unpolled tickets (an external set_budget between
        # poll and _start) hold leases none of the rungs below can see:
        # absorb them into the active set first so their bytes are
        # sheddable rather than silently left over budget
        for ticket in self.pool.poll():
            self._start(ticket)
        lat = [r for r in self.active if r.klass == "latency"
               and r.lease is not None]
        if lat and "memory" in self.pool.pareto_classes(self._key):
            victim = min(lat, key=lambda r: (r.priority, -r.rid))
            self._preempt_request(victim, downgrade_to="memory")
            self.ladder["replan"] += 1
            return True
        if not self._exact_buckets:
            self._exact_buckets = True
            self.ladder["shrink_buckets"] += 1
            # drop the server's own padding-scratch reservation (token-
            # scoped: other reservers' scratch is theirs to release)
            token, self._scratch_token = self._scratch_token, None
            if token is not None:
                token.release()
            return True
        owned = [r for r in self.active if r.lease is not None]
        if not owned:
            return False
        # same ordering as ArenaPool.preempt_candidate: lowest priority
        # first, youngest lease among ties
        victim = min(owned, key=lambda r: (r.priority, -r.lease.rid))
        self._preempt_request(victim)
        self.ladder["preempt"] += 1
        return True

    def _retry_spilled(self) -> None:
        """Drive due re-admissions: bounded retry, exponential backoff."""
        still = []
        for req in self._spilled:
            sp = req.spill
            if not sp.due(self._tick):
                still.append(req)
                continue
            ticket = self.pool.readmit(sp)
            if ticket.rejected:
                self._finish_rejected(req, ticket)
            elif ticket.admitted:
                self._tickets[ticket.rid] = req   # restored by _start
            else:
                sp.backoff(self._tick)
                if sp.attempts >= self.max_readmit_attempts:
                    ticket.reason_code = "readmit_exhausted"
                    ticket.reason = (
                        f"re-admission failed after {sp.attempts} attempts "
                        f"(pool reserved {self.pool.reserved_bytes} of "
                        f"{self.pool.budget_bytes} budget bytes)")
                    ticket.rejected = True
                    self._finish_rejected(req, ticket)
                else:
                    still.append(req)
        self._spilled = still

    # -- decode ------------------------------------------------------------

    def _cache_defs(self):
        return self.model.make_cache_defs(1, self.smax)

    def _step_serial(self) -> None:
        for req in self.active:
            cache = unpack_decode_state(self._plan, req.arena,
                                        self._cache_defs())
            tok = jnp.full((1, 1), req.last_tok, jnp.int32)
            logits, cache = self._decode(self.params, cache, tok,
                                         jnp.int32(req.t))
            req.last_tok = int(jnp.argmax(logits, -1)[0])
            req.tokens.append(req.last_tok)
            req.t += 1
            req.arena = pack_decode_state(self._plan, cache, arena=req.arena)

    def _build_batched(self, bucket: int):
        """One jitted arena->arena decode program for this batch bucket.

        The program's input is the stacked ``(bucket, resident_extent)``
        uint8 arena matrix (donated): each row is unpacked at the *planned
        byte offsets* — a layout fixed at trace time, not a Python loop
        over leases — decoded one token, and the new KV state packed back
        into the row, all inside one ``jax.vmap``-ed XLA program.
        """
        decode = make_decode_step(self.model, self.rules)
        defs = self._cache_defs()
        dplan = self._plan

        def one(arena, tok, t, params):
            cache = unpack_decode_state(dplan, arena, defs)
            logits, new = decode(params, cache, tok, t)
            leaves = jax.tree.leaves(new)
            arena = pack_buffers(dplan["plan"], dict(enumerate(leaves)),
                                 arena=arena, jit=False)
            return jnp.argmax(logits, -1).reshape(()), arena

        def step(params, arenas, toks, ts):
            return jax.vmap(one, in_axes=(0, 0, 0, None))(
                arenas, toks, ts, params)

        return jax.jit(step, donate_argnums=(1,))

    @staticmethod
    def _bucket(n: int) -> int:
        """Next power-of-two batch bucket (bounds trace count to log2)."""
        return 1 << max(0, n - 1).bit_length()

    def _step_vmap(self) -> None:
        B = len(self.active)
        # ladder rung 2: exact-size buckets trade extra traces for zero
        # padding rows (no scratch charged against the shrunk budget)
        bucket = B if self._exact_buckets else self._bucket(B)
        pad = bucket - B
        if pad:
            # padding rows materialize real state + transients beyond the
            # admitted set: charge them to the pool budget for the duration
            # of the step (a handle-based reservation released in the
            # finally below), or shrink the bucket to the exact batch
            try:
                self._scratch_token = self.pool.reserve_scratch(
                    pad * self._plan["arena_bytes"])
            except PoolError:
                bucket, pad = B, 0
        try:
            fn = self._batched.get(bucket)
            if fn is None:
                fn = self._batched[bucket] = self._build_batched(bucket)
            r0 = self.active[0]
            arenas = jnp.stack([r.arena for r in self.active]
                               + [r0.arena] * pad)
            toks = jnp.asarray([[[r.last_tok]] for r in self.active]
                               + [[[r0.last_tok]]] * pad, jnp.int32)
            ts = jnp.asarray([r.t for r in self.active] + [r0.t] * pad,
                             jnp.int32)
            next_toks, arenas = fn(self.params, arenas, toks, ts)
            next_toks = np.asarray(next_toks).reshape(-1)[:B]
            for i, req in enumerate(self.active):
                req.last_tok = int(next_toks[i])
                req.tokens.append(req.last_tok)
                req.t += 1
                req.arena = arenas[i]
        finally:
            token, self._scratch_token = self._scratch_token, None
            if token is not None:
                token.release()

    def step(self) -> int:
        """One scheduler tick; returns the number of active requests.

        Tick order: arm this tick's chaos faults, admit (poll + start),
        apply injected budget shrinks (which may walk the ladder), retry
        spilled re-admissions, then decode — guarded by the transient-
        error bounded retry — and finally retire finished requests and
        record the budget-invariant trace.
        """
        self._tick += 1
        t_tick = time.perf_counter()
        shrinks = ()
        if self.chaos is not None:
            shrinks = self.chaos.begin_tick(self._tick)
        self.pool.kick()              # retry after transient faults
        self._collect_rejected()
        for ticket in self.pool.poll():
            self._start(ticket)
        for spec in shrinks:
            if spec.kind == "budget_shrink":
                self.set_budget(max(1, int(self.pool.budget_bytes
                                           * spec.factor)))
        self._collect_rejected()
        self._retry_spilled()
        for ticket in self.pool.poll():
            self._start(ticket)
        if self.active:
            try:
                if self.chaos is not None:
                    self.chaos.maybe_executor_error()
                if self.step_mode == "serial":
                    self._step_serial()
                else:
                    self._step_vmap()
                self._transient_streak = 0
            except TransientExecutorError:
                # request state untouched: skip the decode phase this tick
                # and retry next tick, up to the bounded retry limit
                self.transient_errors += 1
                self._transient_streak += 1
                if self._transient_streak > self.max_transient_retries:
                    raise
        still = []
        for req in self.active:
            if len(req.tokens) >= req.max_new:
                req.done_s = time.perf_counter()
                req.lease.buffer = req.arena   # warm buffer back to the pool
                req.arena = None
                self.pool.release(req.lease)
                self.done.append(req)
            else:
                still.append(req)
        self.active = still
        # budget-invariant trace: realized arena bytes vs the instantaneous
        # (possibly shrunk) budget — the chaos suite asserts this never
        # goes positive once the ladder has run
        self.max_over_budget_bytes = max(
            self.max_over_budget_bytes,
            self.pool.reserved_bytes - self.pool.budget_bytes)
        self._last_tick_s = time.perf_counter() - t_tick
        return len(self.active)

    # -- stall diagnostics (DESIGN.md §13) ----------------------------------

    def _progress_sig(self) -> tuple:
        """Observable state; two equal signatures = a tick did nothing.

        Spill backoff state is part of the signature: a failed readmit
        attempt re-arms the backoff (``attempts``/``next_tick`` move), and
        that is observable work even when nothing else changed.
        """
        return (len(self.done),
                sum(len(r.tokens) for r in self.active),
                len(self.active), len(self._spilled), len(self._tickets),
                self.pool.queue_len, self.pool.stats.admitted,
                self.pool.budget_bytes,
                tuple(sorted((r.rid, r.spill.attempts, r.spill.next_tick)
                             for r in self._spilled)))

    def _backoff_pending(self) -> bool:
        """True while a spilled re-admission is waiting out its exponential
        backoff window — that wait is scheduled future work, not
        stagnation, so it must not count toward watchdog escalation."""
        return any(r.spill is not None and r.spill.next_tick > self._tick
                   for r in self._spilled)

    def _stall_report(self) -> dict:
        """Structured queue diagnostics: every waiting request's identity
        and its current ``_fits`` failure reason."""
        return {
            "tick": self._tick,
            "queued": self.pool.queue_report(),
            "waiting_rids": sorted(self._tickets),
            "spilled": [{"rid": r.rid, "attempts": r.spill.attempts,
                         "next_tick": r.spill.next_tick,
                         "klass": r.spill.klass}
                        for r in self._spilled],
            "reserved_bytes": self.pool.reserved_bytes,
            "budget_bytes": self.pool.budget_bytes,
            "scratch_bytes": self.pool.scratch_bytes,
            "watchdog": self.watchdog.as_dict(),
        }

    def _raise_stall(self) -> None:
        report = self._stall_report()
        self.last_stall = report
        queued = ", ".join(
            f"rid={q['rid']} klass={q['klass']} prio={q['priority']} "
            f"({q['why']})" for q in report["queued"]) or "none"
        raise ServingStallError(
            f"serving stalled at tick {report['tick']}: "
            f"{len(report['waiting_rids'])} request(s) waiting, "
            f"{len(report['spilled'])} spilled, none active; pool reserved "
            f"{report['reserved_bytes']} of {report['budget_bytes']} budget "
            f"bytes; queued: [{queued}]", report)

    def run(self, requests: Sequence[Request], *,
            max_steps: int = 100_000) -> dict:
        """Drive all ``requests`` to completion; returns serving metrics."""
        t0 = time.perf_counter()
        for r in requests:
            self.submit(r)
        steps = 0
        while (self.active or self._tickets or self._spilled) \
                and steps < max_steps:
            sig = self._progress_sig()
            self.step()
            steps += 1
            progressed = self._progress_sig() != sig \
                or self._backoff_pending()
            if self.watchdog.observe(self._last_tick_s, progressed):
                self._raise_stall()
            if not progressed and not self.active and self._tickets \
                    and not self._spilled and not self.pool.leases \
                    and not self.pool.pending_admissions \
                    and self.chaos is None:
                # nothing active, nothing held, pending or spilled, no
                # fault injection that could explain it, and the queue did
                # not move: it can never drain (an admission bug) — fail
                # loudly now instead of waiting out the watchdog
                self._raise_stall()
        jax.block_until_ready(self.params)
        wall = time.perf_counter() - t0
        served = [r for r in self.done if not r.rejected]
        lat = sorted(r.latency_s for r in served)
        if lat:
            p50_ms = 1e3 * float(np.percentile(lat, 50))
            p99_ms = 1e3 * float(np.percentile(lat, 99))
        else:
            # an all-rejected run has no latencies: report NaN, never a
            # vacuous 0.0 that would pass any latency SLO silently
            p50_ms = p99_ms = float("nan")
        n_tok = sum(len(r.tokens) for r in served)
        st = self.pool.stats
        ps = self.pool.preemption_stats
        reject_codes: dict[str, int] = {}
        for r in self.done:
            if r.rejected:
                code = r.reject_code or "submit"
                reject_codes[code] = reject_codes.get(code, 0) + 1
        return {
            "n_requests": len(requests),
            "n_served": len(served),
            "n_rejected": sum(r.rejected for r in self.done),
            "n_tokens": n_tok,
            "wall_s": wall,
            "tok_per_s": n_tok / max(wall, 1e-9),
            "p50_ms": p50_ms,
            "p99_ms": p99_ms,
            "steps": steps,
            "max_concurrent": st.max_concurrent,
            "peak_reserved_bytes": st.peak_reserved_bytes,
            "budget_bytes": self.pool.budget_bytes,
            "warm_hits": st.warm_hits,
            "plan_hits": st.plan_hits,
            "arena_bytes": self._plan["arena_bytes"],
            "persistent_bytes": self._plan["persistent_bytes"],
            "transient_bytes": self._plan["transient_bytes"],
            "admitted_by_class": dict(st.admitted_by_class),
            # robustness block (DESIGN.md §13)
            "reject_codes": reject_codes,
            "n_preempted": ps.preemptions,
            "spill_bytes": ps.spilled_bytes,
            "n_readmitted": ps.readmitted,
            "readmit_attempts": ps.readmit_attempts,
            "admission_faults": ps.admission_faults,
            "budget_shrinks": ps.budget_shrinks,
            "min_budget_bytes": self.min_budget_bytes,
            "max_over_budget_bytes": self.max_over_budget_bytes,
            "transient_errors": self.transient_errors,
            "ladder": dict(self.ladder),
            "watchdog": self.watchdog.as_dict(),
            "stall": self.last_stall,
        }


def make_pool(budget_bytes: int, *, step_mode: str = "serial",
              pooled: bool = True, max_warm: int = 4,
              tenant_quotas: dict[str, int] | None = None) -> ArenaPool:
    """Pool whose admission accounting matches the server's step mode."""
    overlap = "serial" if (pooled and step_mode == "serial") else "none"
    return ArenaPool(
        budget_bytes,
        overlap=overlap,
        max_warm=max_warm,
        alloc_fn=lambda n: jnp.zeros(n, jnp.uint8),
        tenant_quotas=tenant_quotas,
    )


def run_server(model, params, requests, *, smax: int, budget_bytes: int,
               step_mode: str = "serial", pooled: bool = True,
               rules=None, warm: int = 0,
               chaos: ChaosController | None = None,
               tenant_quotas: dict[str, int] | None = None,
               **server_kwargs) -> dict:
    """Build a pool + server, serve ``requests``, return metrics."""
    pool = make_pool(budget_bytes, step_mode=step_mode, pooled=pooled,
                     tenant_quotas=tenant_quotas)
    server = DecodeServer(model, params, pool, smax=smax, rules=rules,
                          step_mode=step_mode, chaos=chaos, **server_kwargs)
    if warm:
        server.warm(warm)
    return server.run(requests)


def synth_requests(n: int, prompt_len: int, gen: int, vocab: int,
                   seed: int = 0,
                   latency_frac: float = 0.0,
                   priorities: Sequence[int] | None = None,
                   tenants: Sequence[str] | None = None) -> list[Request]:
    """Synthesize ``n`` requests; ``latency_frac`` > 0 tags that fraction
    as the ``latency`` Pareto class and the rest ``memory`` (0.0 keeps
    every request classless — base-plan admission, the pre-§12 behavior).
    ``priorities`` / ``tenants`` are cycled over the requests when given.
    """
    if not 0.0 <= latency_frac <= 1.0:
        raise ValueError(f"latency_frac must be in [0, 1], got {latency_frac}")
    rng = np.random.default_rng(seed)
    n_lat = round(n * latency_frac)
    reqs = []
    for i in range(n):
        klass = None if latency_frac == 0.0 else \
            ("latency" if i < n_lat else "memory")
        reqs.append(Request(
            rid=i,
            prompt=rng.integers(0, vocab, prompt_len).astype(np.int32),
            max_new=gen, klass=klass,
            priority=priorities[i % len(priorities)] if priorities else 0,
            tenant=tenants[i % len(tenants)] if tenants else None))
    return reqs


# ---------------------------------------------------------------------------
# Sharded fleet top layer (DESIGN.md §14)
# ---------------------------------------------------------------------------


def fleet_planner_for_model(model, buckets: Sequence[int]) \
        -> tuple[PlannerService, dict]:
    """A :class:`PlannerService` loaded with this model's real decode
    plans, one per sequence bucket.

    Each bucket's regions-layout decode plan (KV caches pinned resident,
    transients above — :func:`plan_decode_arena`) is registered together
    with its two Pareto class plans, all backed by the shared
    content-addressed plan cache — so fleet workers lease exactly the
    plans the single-device server serves, fetched by fingerprint, never
    planned locally.  Returns ``(planner, {bucket: PlanRecord})``.
    """
    planner = PlannerService(cache=default_cache())
    records = {}
    for b in sorted(set(int(b) for b in buckets)):
        d = plan_decode_arena(model, 1, b)
        records[b] = planner.register(
            d["graph"], plan=d["plan"],
            classes={"memory": d["plan"],
                     "latency": pin_transients(d["plan"])})
    return planner, records


def run_fleet(model, arrivals, *, buckets: Sequence[int],
              n_decode: int = 4, n_prefill: int = 1,
              shard_budget_bytes: int | None = None,
              prefill_budget_bytes: int | None = None,
              max_batch: int = 8, prefill_chunk: int = 32,
              tenant_quotas: dict[str, int] | None = None,
              fault_plans: dict | None = None,
              max_ticks: int | None = None) -> dict:
    """Serve an open-loop workload on a sharded fleet of this model's
    decode plans (simulated device workers — scheduling fidelity, not
    kernels; see ``runtime/fleet.py``).

    ``shard_budget_bytes`` defaults to ``max_batch`` times the largest
    non-oversize bucket's arena — each decode shard can hold a full
    batch of the biggest routable request.
    """
    planner, records = fleet_planner_for_model(model, buckets)
    if shard_budget_bytes is None:
        fitted = sorted(records)[:-1] or sorted(records)
        shard_budget_bytes = max_batch * records[fitted[-1]].alone_bytes
    fleet = Fleet(planner, key_for=bucket_key_for(records),
                  n_decode=n_decode, n_prefill=n_prefill,
                  shard_budget_bytes=shard_budget_bytes,
                  prefill_budget_bytes=prefill_budget_bytes,
                  max_batch=max_batch, prefill_chunk=prefill_chunk,
                  tenant_quotas=tenant_quotas, fault_plans=fault_plans)
    metrics = fleet.run_arrivals(arrivals, max_ticks=max_ticks)
    metrics["shard_budget_bytes"] = shard_budget_bytes
    metrics["buckets"] = sorted(records)
    return metrics


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--budget-mb", type=float, default=0.0,
                    help="global arena budget; 0 = 4x one request's arena")
    ap.add_argument("--step-mode", choices=("serial", "vmap"),
                    default="serial")
    ap.add_argument("--no-pool", action="store_true",
                    help="naive one-arena-per-request admission baseline")
    ap.add_argument("--warm", type=int, default=2,
                    help="arenas to pre-plan/pre-allocate at startup")
    ap.add_argument("--latency-frac", type=float, default=0.0,
                    help="fraction of requests admitted as the "
                         "latency-sensitive Pareto class (pinned "
                         "transients); the rest memory-starved")
    ap.add_argument("--mesh", choices=("none", "single", "multi"),
                    default="none")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--fleet", type=int, default=0, metavar="N",
                    help="serve on a sharded fleet of N decode shards "
                         "(simulated workers over the real decode plans) "
                         "instead of the single in-process server")
    ap.add_argument("--prefill-shards", type=int, default=1,
                    help="dedicated prefill-lane shards (fleet mode; 0 "
                         "prefills inline on decode shards)")
    ap.add_argument("--rate", type=float, default=2.0,
                    help="open-loop Poisson arrival rate, requests/tick "
                         "(fleet mode)")
    args = ap.parse_args()
    configure_compile_cache()

    cfg = configs.smoke(args.arch) if args.smoke else configs.get(args.arch)
    model = build_model(cfg)
    smax = args.prompt_len + args.gen

    plan = plan_decode_arena(model, 1, smax)
    pc_stats = default_cache().stats
    print(f"[serve] decode-state arena/request: "
          f"{plan['arena_bytes']/1e6:.2f} MB "
          f"({plan['persistent_bytes']/1e6:.2f} MB KV state + "
          f"{plan['transient_bytes']/1e6:.2f} MB step transients, "
          f"policy={plan['policy']}, naive sum "
          f"{plan['naive_bytes']/1e6:.2f} MB; plan cache "
          f"hits={pc_stats.hits} misses={pc_stats.misses})")

    budget = int(args.budget_mb * 1e6) if args.budget_mb else \
        4 * plan["arena_bytes"]

    if args.fleet > 0:
        # sharded fleet: open-loop load over per-bucket decode plans;
        # simulated workers exercise routing/admission, not kernels
        gen = OpenLoopLoadGen(
            seed=args.seed, rate=args.rate,
            prompt_mean=args.prompt_len, prompt_max=4 * smax,
            gen_mean=args.gen, gen_max=2 * args.gen, latency_frac=0.25)
        arrivals = gen.arrivals(args.requests)
        print(f"[fleet] workload: {workload_summary(arrivals)}")
        m = run_fleet(model, arrivals,
                      buckets=(smax, 2 * smax, 8 * smax),
                      n_decode=args.fleet, n_prefill=args.prefill_shards)
        print(f"[fleet] {m['n_served']}/{m['n_requests']} served "
              f"({m['n_rejected']} rejected, rate {m['rejection_rate']}), "
              f"{m['tokens']} tokens over {m['ticks']} ticks on "
              f"{args.fleet}+{args.prefill_shards} shards "
              f"({m['tok_per_tick']} tok/tick)")
        print(f"[fleet] latency p50 {m['p50_ticks']} / p99 {m['p99_ticks']} "
              f"ticks; {m['handoffs']} prefill handoffs, "
              f"{m['migrations']} migrations, {m['preemptions']} "
              f"preemptions; shard budget "
              f"{m['shard_budget_bytes']/1e6:.2f} MB")
        return

    mesh = rules = None
    if args.mesh != "none":
        mesh = make_production_mesh(multi_pod=args.mesh == "multi")
        rules = rules_for_mesh(mesh)

    params = model.init(jax.random.PRNGKey(args.seed))
    reqs = synth_requests(args.requests, args.prompt_len, args.gen,
                          cfg.vocab_size, args.seed + 1,
                          latency_frac=args.latency_frac)
    metrics = run_server(model, params, reqs, smax=smax,
                         budget_bytes=budget, step_mode=args.step_mode,
                         pooled=not args.no_pool, rules=rules,
                         warm=args.warm)
    print(f"[serve] {metrics['n_served']}/{metrics['n_requests']} requests "
          f"({metrics['n_rejected']} rejected), {metrics['n_tokens']} tokens "
          f"in {metrics['wall_s']:.2f} s "
          f"({metrics['tok_per_s']:.1f} tok/s)")
    print(f"[serve] latency p50 {metrics['p50_ms']:.0f} ms / "
          f"p99 {metrics['p99_ms']:.0f} ms; concurrency "
          f"{metrics['max_concurrent']} under "
          f"{metrics['budget_bytes']/1e6:.2f} MB budget "
          f"(peak reserved {metrics['peak_reserved_bytes']/1e6:.2f} MB; "
          f"warm hits {metrics['warm_hits']})")
    if metrics["admitted_by_class"]:
        by = metrics["admitted_by_class"]
        print("[serve] admitted by Pareto class: "
              + ", ".join(f"{k}={by[k]}" for k in sorted(by)))


if __name__ == "__main__":
    main()
