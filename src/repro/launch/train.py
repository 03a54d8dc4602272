"""Training driver.

    PYTHONPATH=src python -m repro.launch.train --arch llama3.2-1b --smoke \
        --steps 200 --batch 8 --seq 256 --ckpt-dir /tmp/ckpt

Wires together: config -> model -> optimizer -> (optional mesh+sharding) ->
data pipeline -> fault-tolerant loop with async checkpointing.  On this
container it runs reduced configs on CPU; on a TPU slice the same driver
shards over the production mesh (--mesh single|multi).
"""

from __future__ import annotations

import argparse
import logging
import time

import jax
import jax.numpy as jnp

import repro.configs as configs
from repro.checkpoint import CheckpointManager, latest_step, restore
from repro.configs.base import ShapeConfig
from repro.data import DataPipeline
from repro.launch.compile_cache import configure_compile_cache
from repro.launch.mesh import make_production_mesh, rules_for_mesh
from repro.launch.steps import make_optimizer, make_train_step
from repro.models.zoo import build_model
from repro.runtime import FaultTolerantLoop

log = logging.getLogger("repro.train")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced same-family config (CPU-sized)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--mesh", choices=("none", "single", "multi"),
                    default="none")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    logging.basicConfig(level=logging.INFO,
                        format="%(asctime)s %(name)s %(message)s")
    configure_compile_cache()

    cfg = configs.smoke(args.arch) if args.smoke else configs.get(args.arch)
    model = build_model(cfg)
    opt = make_optimizer(cfg, lr=args.lr)

    mesh = rules = None
    if args.mesh != "none":
        mesh = make_production_mesh(multi_pod=args.mesh == "multi")
        rules = rules_for_mesh(mesh)

    step_fn = make_train_step(model, opt, rules, peak_lr=args.lr,
                              warmup=max(args.steps // 20, 10),
                              total_steps=args.steps)
    jit_step = jax.jit(step_fn, donate_argnums=(0,))

    pipe = DataPipeline(cfg=cfg, seq_len=args.seq, global_batch=args.batch,
                        seed=args.seed)

    params = model.init(jax.random.PRNGKey(args.seed))
    n_params = sum(x.size for x in jax.tree.leaves(params))
    log.info("arch=%s params=%.2fM devices=%d", cfg.name, n_params / 1e6,
             jax.device_count())
    state = {"params": params, "opt": opt.init(params)}

    ckpt = CheckpointManager(args.ckpt_dir, keep=3)
    start = latest_step(args.ckpt_dir) or 0
    if start:
        log.info("resuming from checkpoint step %d", start)
        state = restore(args.ckpt_dir, start, state)

    losses = []
    t_last = time.perf_counter()

    def on_metrics(step, metrics):
        nonlocal t_last
        losses.append(float(metrics["loss"]))
        if step % args.log_every == 0:
            dt = time.perf_counter() - t_last
            t_last = time.perf_counter()
            tok_s = args.batch * args.seq * args.log_every / dt
            log.info("step %5d loss=%.4f  %.1f tok/s", step,
                     float(metrics["loss"]), tok_s)

    def run_step(state, batch):
        batch = {k: jnp.asarray(v) for k, v in batch.items()}
        return jit_step(state, batch)

    loop = FaultTolerantLoop(
        step_fn=run_step,
        ckpt_manager=ckpt,
        batch_iter_factory=pipe.iter_from,
        ckpt_every=args.ckpt_every,
    )
    ctx = mesh if mesh is not None else _nullcontext()
    with ctx:
        state, end_step = loop.run(state, start, args.steps,
                                   on_metrics=on_metrics)
    first = sum(losses[:10]) / max(len(losses[:10]), 1)
    last = sum(losses[-10:]) / max(len(losses[-10:]), 1)
    log.info("done at step %d: loss %.4f -> %.4f (stragglers=%d)",
             end_step, first, last, loop.timer.stragglers)


class _nullcontext:
    def __enter__(self):
        return None

    def __exit__(self, *a):
        return False


if __name__ == "__main__":
    main()
