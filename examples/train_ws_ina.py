"""End-to-end driver: train a ~100M-param dense model for a few hundred
steps with INA psum accumulation on the host mesh.

This is the deliverable (b) end-to-end example: real data pipeline, real
AdamW, checkpointing, INA-mode tensor parallelism over the model axis of an
8-device host mesh.

Run:  PYTHONPATH=src python examples/train_ws_ina.py [--steps 200]
(CPU: ~100M params trains slowly; --small switches to a 10M config.)
"""
import os
if not os.environ.get("XLA_FLAGS"):
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import argparse
import dataclasses

import jax

from repro.configs.base import ModelConfig, ShapeConfig
from repro.data.pipeline import DataConfig, TokenPipeline
from repro.launch.mesh import make_host_mesh
from repro.models.api import get_model
from repro.optim.adamw import adamw_init
from repro.parallel.steps import build_train_step
from repro.parallel.tp import ParallelCtx
from repro.runtime.fault_tolerance import FTConfig, run_training


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--small", action="store_true")
    ap.add_argument("--psum-mode", default="ina",
                    choices=["xla_spmd", "ina", "ina_ring", "eject_inject"])
    ap.add_argument("--ckpt-dir", default="/tmp/ws_ina_ckpt")
    args = ap.parse_args()

    if args.small:
        cfg = ModelConfig(name="demo-10m", family="dense", n_layers=4,
                          d_model=256, n_heads=8, n_kv_heads=4, d_ff=1024,
                          vocab=8192, attn_chunk=256, dtype="float32")
        batch, seq = 8, 128
    else:
        # ~100M params: 12L x 768 x GQA + 32k vocab
        cfg = ModelConfig(name="demo-100m", family="dense", n_layers=12,
                          d_model=768, n_heads=12, n_kv_heads=4, d_ff=3072,
                          vocab=32768, attn_chunk=512, dtype="float32")
        batch, seq = 8, 512

    model = get_model(cfg)
    mesh = make_host_mesh(4)            # (data 2, model 4) on 8 devices
    pctx = ParallelCtx(mesh=mesh, psum_mode=args.psum_mode)
    shape = ShapeConfig("train", seq, batch, "train")
    ts = build_train_step(model, mesh, shape, pctx, base_lr=3e-4,
                          warmup=20, total_steps=args.steps, donate=False)

    params = jax.device_put(model.init(jax.random.PRNGKey(0)),
                            ts.param_sharding)
    opt = jax.device_put(adamw_init(params), ts.opt_sharding)
    n = sum(p.size for p in jax.tree.leaves(params))
    print(f"[ws_ina] {cfg.name}: {n/1e6:.1f}M params, mesh {dict(mesh.shape)}, "
          f"psum={args.psum_mode}")

    pipe = TokenPipeline(DataConfig(vocab=cfg.vocab, seq_len=seq,
                                    global_batch=batch))

    def step_fn(state, batch_host):
        p, o = state
        b = {k: jax.device_put(v, ts.batch_sharding[k])
             for k, v in batch_host.items()}
        p, o, stats = ts.fn(p, o, b)
        return (p, o), stats

    losses = []

    def on_metrics(step, m, dt):
        losses.append(float(m["loss"]))
        if step % 20 == 0:
            print(f"  step {step:4d} loss {losses[-1]:.4f} ({dt*1e3:.0f} ms)")

    state, last, _ = run_training(
        step_fn, (params, opt), pipe.batch,
        ft=FTConfig(ckpt_dir=args.ckpt_dir, ckpt_every=50),
        num_steps=args.steps, on_metrics=on_metrics)
    print(f"[ws_ina] loss {losses[0]:.4f} -> {losses[-1]:.4f} over {last} steps")
    assert losses[-1] < losses[0]
    print("train_ws_ina OK")


if __name__ == "__main__":
    main()
