"""End-to-end training driver.

On a production pod this runs under ``jax.distributed`` with the 16x16 (or
2x16x16) mesh; on this CPU container it runs real training of reduced
configs (``--reduced``) over the host mesh — same code path, same
fault-tolerance machinery.

Example (trains a ~small dense model for 50 steps with checkpoints):
  PYTHONPATH=src python -m repro.launch.train --arch qwen2-1.5b --reduced \
      --steps 50 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt --psum-mode ina
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp

from repro.configs import ARCHS
from repro.configs.base import ShapeConfig
from repro.data.pipeline import DataConfig, TokenPipeline
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.models.api import get_model
from repro.optim.adamw import adamw_init
from repro.core.collectives import CLI_PSUM_MODES
from repro.parallel.steps import build_train_step
from repro.parallel.tp import ParallelCtx
from repro.plan import add_plan_cli_args, plan_for_launch
from repro.runtime.fault_tolerance import FTConfig, run_training


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=sorted(ARCHS))
    ap.add_argument("--reduced", action="store_true",
                    help="tiny same-family config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--psum-mode", default="ina", choices=CLI_PSUM_MODES)
    add_plan_cli_args(ap)
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument("--production-mesh", action="store_true",
                    help="16x16 mesh (requires 256 devices)")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = ARCHS[args.arch]
    if args.reduced:
        cfg = cfg.reduced()
    model = get_model(cfg)

    mesh = (make_production_mesh() if args.production_mesh
            else make_host_mesh(args.model_parallel))
    shape = ShapeConfig("cli", args.seq, args.batch, "train")
    plan, _ = plan_for_launch(cfg, mesh, shape, args.psum_mode,
                              plan_dir=args.plan_dir,
                              enabled=not args.no_plan)
    pctx = ParallelCtx(mesh=mesh, psum_mode=args.psum_mode, plan=plan)
    ts = build_train_step(model, mesh, shape, pctx, base_lr=args.lr,
                          warmup=min(20, args.steps // 5 + 1),
                          total_steps=args.steps, donate=False)

    print(f"[train] {cfg.name} ({'reduced' if args.reduced else 'full'}) "
          f"mesh={dict(mesh.shape)} psum={args.psum_mode}")
    params = jax.device_put(model.init(jax.random.PRNGKey(0)),
                            ts.param_sharding)
    opt = jax.device_put(adamw_init(params), ts.opt_sharding)
    n_params = sum(p.size for p in jax.tree.leaves(params))
    print(f"[train] {n_params/1e6:.1f}M params")

    pipe = TokenPipeline(DataConfig(vocab=cfg.vocab, seq_len=args.seq,
                                    global_batch=args.batch))

    def step_fn(state, batch):
        params, opt = state
        batch = {k: jax.device_put(v, ts.batch_sharding[k])
                 for k, v in batch.items()}
        params, opt, stats = ts.fn(params, opt, batch)
        return (params, opt), stats

    losses = []

    def on_metrics(step, metrics, dt):
        loss = float(metrics["loss"])
        losses.append(loss)
        if step % 5 == 0 or step == args.steps - 1:
            print(f"  step {step:4d} loss {loss:7.4f} "
                  f"gnorm {float(metrics['grad_norm']):8.3f} "
                  f"lr {float(metrics['lr']):.2e}  {dt*1e3:6.0f} ms")

    ft = FTConfig(ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every)
    state = (params, opt)
    state, last, stragglers = run_training(
        step_fn, state, pipe.batch, ft=ft, num_steps=args.steps,
        on_metrics=on_metrics)
    print(f"[train] done at step {last}; loss {losses[0]:.4f} -> "
          f"{losses[-1]:.4f}; stragglers={len(stragglers)}")
    assert losses[-1] < losses[0], "loss did not improve"


if __name__ == "__main__":
    main()
