"""Serving driver: continuous batching + paged KV over the ServingEngine.

The default path seats every prompt through the engine
(:mod:`repro.serve.engine`): chunked **batched** prefill under a
prefill-phase ExecutionPlan, then vmapped per-slot decode under a
decode-phase plan — prefill/decode disaggregation with one plan per phase
via :func:`~repro.plan.plan_for_launch`.

``--legacy-loop`` keeps the pre-serving-engine behaviour (one batch, one
serve_step per prompt token) as an escape hatch and as the reference the
token-equivalence test pins against: the engine must produce exactly the
tokens the legacy loop does, request by request.

Example:
  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-1.5b --reduced \\
      --batch 4 --prompt-len 16 --gen 16 --slots 2 --psum-mode ina
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs import ARCHS
from repro.configs.base import ShapeConfig
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.core.collectives import CLI_PSUM_MODES
from repro.models.api import get_model
from repro.parallel.steps import build_serve_step
from repro.parallel.tp import ParallelCtx
from repro.plan import add_plan_cli_args, plan_for_launch


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=sorted(ARCHS))
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4,
                    help="number of requests (legacy: batch rows)")
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--psum-mode", default="ina", choices=CLI_PSUM_MODES)
    add_plan_cli_args(ap)
    ap.add_argument("--model-parallel", type=int, default=1)
    # engine path
    ap.add_argument("--slots", type=int, default=None,
                    help="continuous-batching slots (default: --batch)")
    ap.add_argument("--block-size", type=int, default=16)
    ap.add_argument("--prefill-chunk", type=int, default=8)
    ap.add_argument("--no-batched-prefill", action="store_true",
                    help="prefill via the per-token decode loop")
    ap.add_argument("--check", action="store_true",
                    help="verify paged==monolithic cache on every retire")
    ap.add_argument("--legacy-loop", action="store_true",
                    help="pre-engine path: one batch, per-token prefill")
    return ap


def make_prompts(cfg, batch: int, prompt_len: int):
    """The seeded prompt block both paths share (key 7, legacy-compatible)."""
    return jax.random.randint(jax.random.PRNGKey(7), (batch, prompt_len), 3,
                              cfg.vocab)


def run_engine(args, cfg, requests=None):
    """Serve ``requests`` (default: ``--batch`` seeded prompts of
    ``--prompt-len`` tokens, ``--gen`` + 1 new tokens each) through the
    engine; returns ``(engine, EngineReport)``.  The cache length is the
    longest request's footprint, rounded up to whole blocks."""
    from repro.serve.batching import Request
    from repro.serve.engine import ServingEngine

    if requests is None:
        prompts = make_prompts(cfg, args.batch, args.prompt_len)
        requests = [
            Request(rid=f"req{i}", prompt_len=args.prompt_len,
                    max_new=args.gen + 1,
                    prompt=tuple(int(t) for t in prompts[i]))
            for i in range(args.batch)]
    mesh = make_host_mesh(args.model_parallel)
    block = args.block_size
    max_seq = -(-max(r.total_positions for r in requests) // block) * block
    slots = args.slots or len(requests)
    # one plan per phase: prefill and decode disaggregate
    dshape = ShapeConfig("cli", max_seq, slots, "decode")
    pshape = ShapeConfig("cli", max_seq, slots, "prefill")
    decode_plan, _ = plan_for_launch(cfg, mesh, dshape, args.psum_mode,
                                     plan_dir=args.plan_dir,
                                     enabled=not args.no_plan)
    prefill_plan, _ = plan_for_launch(cfg, mesh, pshape, args.psum_mode,
                                      plan_dir=args.plan_dir,
                                      enabled=not args.no_plan)
    engine = ServingEngine(
        cfg, slots=slots, max_seq=max_seq, block_size=block,
        prefill_chunk=args.prefill_chunk, psum_mode=args.psum_mode,
        prefill_plan=prefill_plan, decode_plan=decode_plan,
        batched_prefill=not args.no_batched_prefill, check=args.check,
        model_parallel=args.model_parallel)
    print("[serve] engine set-up (trace, compile + run): "
          + ", ".join(f"{k} {v:.1f}s" for k, v in engine.setup_s.items()))
    compile_s = engine.warmup()
    print("[serve] first call per step (compile + run): "
          + ", ".join(f"{k} {v:.1f}s" for k, v in compile_s.items()))

    rec = engine.recorder
    t0, paged = rec.clock(), rec.counters.get("page_out_bytes", 0)
    report = engine.run(requests)
    wall = rec.clock() - t0
    paged = rec.counters.get("page_out_bytes", 0) - paged
    total = sum(len(r["tokens"]) for r in report.requests)
    print(f"[serve] engine: {len(requests)} requests on {slots} slots, "
          f"max_seq {max_seq}, {report.iterations} iterations "
          f"({report.prefill_chunks} prefill chunks, {report.decode_steps} "
          f"decode steps), {total} tokens in {wall:.3f} s host wall time")
    stalls = [s.end - s.start for s in rec.spans() if s.name == "admit"
              and s.start >= t0 and (s.counts or {}).get("admitted")]
    print(f"[serve] page-out "
          f"{paged / max(1, report.decode_steps) / 1e6:.3f} MB per decode "
          f"step; admission stall {1e3 * sum(stalls) / max(1, len(stalls)):.1f}"
          f" ms mean over {len(stalls)} admissions")
    by_rid = report.tokens()
    print(f"[serve] sample {requests[0].rid}: {by_rid[requests[0].rid]}")
    for rid, toks in by_rid.items():
        if not all(0 <= t < cfg.vocab for t in toks):
            raise RuntimeError(f"{rid}: token outside the vocabulary")
    return engine, report


def run_legacy(args, cfg) -> None:
    """The pre-engine loop: one fixed batch, per-token prefill steps."""
    model = get_model(cfg)
    mesh = make_host_mesh(args.model_parallel)

    max_seq = args.prompt_len + args.gen
    shape = ShapeConfig("cli", max_seq, args.batch, "decode")
    plan, _ = plan_for_launch(cfg, mesh, shape, args.psum_mode,
                              plan_dir=args.plan_dir,
                              enabled=not args.no_plan)
    pctx = ParallelCtx(mesh=mesh, psum_mode=args.psum_mode, plan=plan)
    ss = build_serve_step(model, mesh, shape, pctx, donate_cache=True)

    params = jax.device_put(model.init(jax.random.PRNGKey(0)),
                            ss.param_sharding)
    cache = jax.device_put(model.init_cache(args.batch, max_seq),
                           ss.cache_sharding)

    prompts = make_prompts(cfg, args.batch, args.prompt_len)
    media = None
    if cfg.family in ("encdec", "vlm") and cfg.num_media_tokens:
        media = jnp.ones((args.batch, cfg.num_media_tokens, cfg.d_model),
                         jnp.dtype(cfg.dtype))
    if cfg.family == "vlm":
        from repro.models import vision
        cache = vision.prefill_media_kv(params, cfg, media, cache, pctx)
        cache = jax.device_put(cache, ss.cache_sharding)

    # prefill token-by-token through the serve step (keeps one artifact)
    t0 = time.time()
    for pos in range(args.prompt_len):
        batch = {"tokens": prompts[:, pos:pos + 1],
                 "pos": jnp.asarray(pos, jnp.int32)}
        if media is not None:
            batch["media"] = media
        nxt, cache = ss.fn(params, batch, cache)
    print(f"[serve] prefill {args.prompt_len} steps "
          f"{(time.time()-t0)*1e3:.0f} ms")

    generated = []
    tok = nxt[:, None]
    t0 = time.time()
    for i in range(args.gen):
        batch = {"tokens": tok, "pos": jnp.asarray(args.prompt_len + i,
                                                   jnp.int32)}
        if media is not None:
            batch["media"] = media
        nxt, cache = ss.fn(params, batch, cache)
        generated.append(nxt)
        tok = nxt[:, None]
    dt = time.time() - t0
    out = jnp.stack(generated, axis=1)
    print(f"[serve] generated {args.gen} x {args.batch} tokens in "
          f"{dt*1e3:.0f} ms ({args.gen*args.batch/dt:.1f} tok/s)")
    print(f"[serve] sample row: {out[0].tolist()}")
    assert out.shape == (args.batch, args.gen)
    assert bool((out >= 0).all()) and bool((out < cfg.vocab).all())


def main(argv=None) -> None:
    args = build_parser().parse_args(argv)
    enable_compile_cache()
    cfg = ARCHS[args.arch]
    if args.reduced:
        cfg = cfg.reduced()
    if args.legacy_loop or cfg.family in ("encdec", "vlm"):
        if not args.legacy_loop:
            print(f"[serve] family {cfg.family!r} needs media plumbing; "
                  "running the legacy loop")
        run_legacy(args, cfg)
    else:
        run_engine(args, cfg)


if __name__ == "__main__":
    main()
