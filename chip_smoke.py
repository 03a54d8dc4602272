"""Bring-up smoke run of the main path on one TPU chip.

    python chip_smoke.py

Runs three phases in this one process, through the entry points a user
calls:

  device   the first JAX device is a TPU, ``$REPRO_ARENA_IMPL`` is unset and
           the arena dispatch resolves to compiled Pallas kernels (no
           interpret mode, no XLA slices, no CPU fallback)
  paper    SwiftNet cell A, the DARTS ImageNet cell and the 274-node RandWire
           network: ``plan(g, PlanConfig(rewrite=True))``, then a jitted
           ``execute`` unfused and fused; realized peak and extent must equal
           the planned bytes and every output must be allclose to
           ``run_reference``
  serving  full-width llama3.2-1b with seeded random weights: ``run_server``
           serves 4 requests (256-token prompts, 32 new tokens) under the
           default budget (4x one request's arena); all must be served, their
           tokens must equal a plain greedy decode that keeps the KV cache
           outside any arena, and a second run under ``step_mode='vmap'``
           must give the same tokens

It prints arena bytes, peak device memory, compile seconds and tokens
served, and ends with one JSON line naming the device.  A failed check
raises, so the process exits non-zero without that line.
"""

from __future__ import annotations

import contextlib
import json
import os
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ALLCLOSE = dict(rtol=1e-4, atol=1e-4)


class SmokeFailure(AssertionError):
    """A phase produced a wrong result."""


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def device_check() -> dict:
    """Refuse to run anywhere but on a TPU with compiled Pallas arena ops."""
    import jax

    from repro.kernels.arena.ops import ENV_IMPL, _resolve

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU, JAX found "
                         f"{dev.platform!r} ({dev.device_kind})")
    if os.environ.get(ENV_IMPL):
        raise SystemExit(f"chip_smoke: ${ENV_IMPL} is set "
                         f"({os.environ[ENV_IMPL]!r}); unset it")
    impl = _resolve("auto", False)
    if impl != ("pallas", False):
        raise SystemExit(f"chip_smoke: arena dispatch resolves to {impl}, "
                         f"not compiled pallas")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}


def paper_graphs() -> dict:
    from repro.graphs import darts_normal_cell, randwire_network, swiftnet_cell

    return {
        "swiftnet_cell_a": lambda: swiftnet_cell("A"),
        "darts_imagenet_cell": darts_normal_cell,
        "randwire_net_32x8": lambda: randwire_network(n_cells=8, n=32),
    }


def paper_phase(graphs: dict | None = None) -> list[dict]:
    """Plan each graph with rewriting and run it in its arena, unfused and
    fused; returns one row per (graph, fuse)."""
    import numpy as np

    from repro.core import PlanConfig, execute, plan, run_reference

    rows = []
    for name, build in (graphs or paper_graphs()).items():
        res = plan(build(), PlanConfig(rewrite=True))
        ref = run_reference(res.graph)
        for fuse in (False, True):
            ex = execute(res.graph, None, res.arena, order=res.order,
                         jit=True, fuse=fuse)
            _check(ex.realized_peak_bytes == res.arena.peak_bytes
                   and ex.realized_arena_bytes == res.arena.arena_bytes,
                   f"{name} fuse={fuse}: realized peak/extent "
                   f"{ex.realized_peak_bytes}/{ex.realized_arena_bytes} != "
                   f"planned {res.arena.peak_bytes}/{res.arena.arena_bytes}")
            _check(set(ex.outputs) == set(ref),
                   f"{name} fuse={fuse}: outputs {sorted(ex.outputs)} != "
                   f"reference {sorted(ref)}")
            err = 0.0
            for k, want in ref.items():
                got, want = np.asarray(ex.outputs[k]), np.asarray(want)
                e = float(np.max(np.abs(got - want)))
                _check(np.allclose(got, want, **ALLCLOSE),
                       f"{name} fuse={fuse}: output {k!r} differs from "
                       f"run_reference (max abs err {e})")
                err = max(err, e)
            rows.append({"graph": name, "nodes": len(res.graph),
                         "fuse": fuse,
                         "arena_bytes": res.arena.arena_bytes,
                         "peak_bytes": res.arena.peak_bytes,
                         "max_abs_err": err})
    return rows


def _greedy_reference(model, params, prompt, gen: int, smax: int) -> list:
    """Greedy tokens with the KV cache kept as a plain pytree: the serving
    path's answer without any arena pack/unpack."""
    import jax
    import jax.numpy as jnp

    from repro.launch.steps import make_decode_step, make_prefill_step

    prefill = jax.jit(make_prefill_step(model, None))
    decode = jax.jit(make_decode_step(model, None))
    cache = model.init_cache(1, smax)
    logits, cache = prefill(params, cache,
                            {"tokens": jnp.asarray(prompt, jnp.int32)[None]})
    toks = [int(jnp.argmax(logits, -1)[0])]
    for t in range(len(prompt), len(prompt) + gen - 1):
        logits, cache = decode(params, cache,
                               jnp.full((1, 1), toks[-1], jnp.int32),
                               jnp.int32(t))
        toks.append(int(jnp.argmax(logits, -1)[0]))
    return toks


def serving_phase(cfg=None, *, n_requests: int = 4, prompt_len: int = 256,
                  gen: int = 32, seed: int = 0) -> dict:
    """Serve ``n_requests`` through ``run_server``, serial then vmap."""
    import jax

    import repro.configs as configs
    from repro.launch.serve import plan_decode_arena, run_server, synth_requests
    from repro.models.zoo import build_model

    cfg = cfg or configs.get("llama3.2-1b")
    model = build_model(cfg)
    smax = prompt_len + gen
    dplan = plan_decode_arena(model, 1, smax)
    budget = 4 * dplan["arena_bytes"]     # serve.main's default budget
    params = model.init(jax.random.PRNGKey(seed))
    out = {"arch": cfg.name, "arena_bytes": dplan["arena_bytes"],
           "kv_bytes": dplan["persistent_bytes"], "budget_bytes": budget}
    tokens = {}
    for mode in ("serial", "vmap"):
        reqs = synth_requests(n_requests, prompt_len, gen, cfg.vocab_size,
                              seed + 1)
        m = run_server(model, params, reqs, smax=smax, budget_bytes=budget,
                       step_mode=mode)
        _check(m["n_served"] == n_requests and m["n_rejected"] == 0,
               f"{mode}: served {m['n_served']}/{n_requests}, "
               f"rejected {m['n_rejected']}")
        tokens[mode] = [list(r.tokens) for r in reqs]
        _check(all(len(t) == gen for t in tokens[mode]),
               f"{mode}: token counts {[len(t) for t in tokens[mode]]} != "
               f"{gen}")
        out[mode] = {"n_served": m["n_served"], "n_tokens": m["n_tokens"],
                     "max_concurrent": m["max_concurrent"],
                     "peak_reserved_bytes": m["peak_reserved_bytes"]}
        if mode == "serial":
            want = [_greedy_reference(model, params, r.prompt, gen, smax)
                    for r in reqs]
            _check(tokens["serial"] == want,
                   "serial tokens differ from the arena-free greedy decode")
    _check(tokens["serial"] == tokens["vmap"],
           "vmap tokens differ from serial tokens")
    return out


@contextlib.contextmanager
def compile_clock():
    """Sum JAX's backend-compile seconds (persistent-cache reads included)
    and count persistent-cache hits while the block runs."""
    from jax import monitoring

    tally = {"compile_s": 0.0, "compiles": 0, "cache_hits": 0}

    def on_duration(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            tally["compile_s"] += duration
            tally["compiles"] += 1

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            tally["cache_hits"] += 1

    monitoring.register_event_duration_secs_listener(on_duration)
    monitoring.register_event_listener(on_event)
    try:
        yield tally
    finally:
        monitoring.unregister_event_duration_listener(on_duration)
        monitoring.unregister_event_listener(on_event)


def _peak_bytes_in_use() -> int | None:
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def main() -> int:
    device = device_check()
    from repro.launch.compile_cache import configure_compile_cache

    print(f"[chip_smoke] device {device}; compile cache "
          f"{configure_compile_cache()}", flush=True)

    t0 = time.perf_counter()
    with compile_clock() as clock:
        rows = paper_phase()
    for r in rows:
        print(f"[paper] {r['graph']} ({r['nodes']} nodes) fuse={r['fuse']}: "
              f"arena {r['arena_bytes']} B, peak {r['peak_bytes']} B "
              f"(realized == planned), max abs err vs run_reference "
              f"{r['max_abs_err']}", flush=True)
    print(f"[paper] wall {time.perf_counter() - t0:.1f} s, compile "
          f"{clock['compile_s']:.1f} s over {clock['compiles']} programs "
          f"({clock['cache_hits']} persistent-cache hits); peak_bytes_in_use "
          f"{_peak_bytes_in_use()}", flush=True)

    t0 = time.perf_counter()
    with compile_clock() as clock:
        s = serving_phase()
    print(f"[serving] {s['arch']}: arena {s['arena_bytes']} B/request "
          f"({s['kv_bytes']} B KV), budget {s['budget_bytes']} B", flush=True)
    for mode in ("serial", "vmap"):
        m = s[mode]
        print(f"[serving] {mode}: {m['n_served']} served, {m['n_tokens']} "
              f"tokens, max concurrent {m['max_concurrent']}, peak reserved "
              f"{m['peak_reserved_bytes']} B", flush=True)
    print(f"[serving] serial tokens == arena-free greedy decode == vmap "
          f"tokens; wall {time.perf_counter() - t0:.1f} s, compile "
          f"{clock['compile_s']:.1f} s over {clock['compiles']} programs "
          f"({clock['cache_hits']} persistent-cache hits); peak_bytes_in_use "
          f"{_peak_bytes_in_use()}", flush=True)

    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
