"""Bring-up smoke run on a TPU: serve qwen2-1.5b at its published widths.

    python chip_smoke.py             # one chip: serve + check (default)
    python chip_smoke.py --chips 4   # four chips: TP decode per psum mode

Default phase (one chip): seeded random weights, 8 seeded requests with
prompts of 32-256 tokens on 4 slots, 32 new tokens each, through the same
engine path as ``python -m repro.launch.serve``.  Checks that every request
returns exactly its tokens, that the paged KV pool matches the monolithic
cache on every retire, and that the engine's prefill logits agree with a
plain float32 forward at ``highest`` matmul precision.

``--chips 4`` runs only the tensor-parallel phase: the same engine on a
(data 1, model 4) mesh under ``xla_spmd`` (the reference), ``ina``,
``eject_inject`` and ``auto`` (planned from the real mesh).  At float32
activations it compares each mode with ``xla_spmd`` on one prefill chunk's
logits, on the logits of one decode step after that prefill, and on the
generated tokens; at the config's own activation dtype (bfloat16) it
serves the same requests under each mode and checks they come back whole.

Everything runs in this one process.  The last line of standard output is
one JSON object naming the device; it is printed only when every check
passed.  Off a TPU the script exits non-zero without it.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

ARCH = "qwen2-1.5b"
#: Seed of the prompts; the weights come from the engine's own seed.
SEED = 0


class SmokeFailure(RuntimeError):
    """A check of the smoke run failed."""


def _require(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def _rel_l2(got, want) -> float:
    import numpy as np
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def _engine_args(*extra: str):
    from repro.launch.serve import build_parser
    return build_parser().parse_args(["--arch", ARCH, *extra])


def _print_config(cfg) -> None:
    print(f"[smoke] config {cfg.name}: {cfg.n_layers} layers, d_model "
          f"{cfg.d_model}, {cfg.n_heads} heads / {cfg.n_kv_heads} KV heads x "
          f"{cfg.resolved_head_dim}, d_ff {cfg.d_ff}, vocab {cfg.vocab}, "
          f"dtype {cfg.dtype}, params {cfg.param_dtype}")


def _check_served(report, requests, vocab: int) -> dict:
    """Every request came back with exactly its tokens, all in the
    vocabulary, and every retire passed the paged==monolithic check."""
    tokens = report.tokens()
    _require(set(tokens) == {r.rid for r in requests},
             f"served {sorted(tokens)}, expected every request")
    for r in requests:
        toks = tokens[r.rid]
        _require(len(toks) == r.max_new,
                 f"{r.rid}: {len(toks)} tokens, expected {r.max_new}")
        _require(all(0 <= t < vocab for t in toks),
                 f"{r.rid}: token outside the vocabulary")
    _require(report.checks == len(requests),
             f"{report.checks} paged==monolithic checks passed, expected "
             f"{len(requests)}")
    return tokens


def reference_last_logits(cfg, params, prompt):
    """Last-position logits ``[V]`` of a plain float32 ``model.forward``
    at ``highest`` matmul precision: the reference for the engine."""
    import jax
    import jax.numpy as jnp

    from repro.models.api import get_model

    model = get_model(dataclasses.replace(cfg, dtype="float32"))
    with jax.default_matmul_precision("highest"):
        fwd = jax.jit(lambda p, t: model.forward(p, {"tokens": t})[0, -1])
        return fwd(params, jnp.asarray(prompt, jnp.int32)[None])


def serve_phase(cfg, *, n_requests: int = 8, slots: int = 4,
                prompt_lens: tuple[int, int] = (32, 256), gen: int = 32,
                chunk: int = 64, logits_tol: float = 5e-2) -> dict:
    """Serve seeded requests through the engine and check the results."""
    import jax
    import numpy as np

    from repro.launch.serve import run_engine
    from repro.serve.traffic import make_workload

    _print_config(cfg)
    requests = make_workload(
        n_requests, qps=0.0, prompt_dist="uniform:%d:%d" % prompt_lens,
        gen_dist=f"fixed:{gen}", seed=SEED, vocab=cfg.vocab, prefix="req")
    print(f"[smoke] {n_requests} requests on {slots} slots, prompt lengths "
          f"{[r.prompt_len for r in requests]}, {gen} new tokens each, "
          f"prefill chunk {chunk}")
    args = _engine_args("--slots", str(slots), "--prefill-chunk", str(chunk),
                        "--check")
    t0 = time.perf_counter()
    engine, report = run_engine(args, cfg, requests)
    wall = time.perf_counter() - t0

    tokens = _check_served(report, requests, cfg.vocab)
    engine.kv.check()
    served = sum(len(t) for t in tokens.values())
    print(f"[smoke] served {served} tokens; {report.checks} paged==monolithic "
          f"checks passed; {report.decode_steps} decode steps, "
          f"{report.prefill_chunks} prefill chunks")
    print(f"[smoke] wall {wall:.3f} s for set-up, compile and serving "
          "(bring-up observation, not a metric)")

    errors, agree = [], []
    for r in requests[:2]:
        last = (r.prompt_len - 1) % chunk
        got = np.asarray(engine.prefill_logits(r.prompt)[last], np.float32)
        want = np.asarray(reference_last_logits(cfg, engine.params, r.prompt))
        errors.append(_rel_l2(got, want))
        agree.append(int(got.argmax()) == int(want.argmax()))
        print(f"[smoke] {r.rid} (prompt {r.prompt_len}): prefill logits vs "
              f"float32 reference rel L2 {errors[-1]!r}, argmax "
              f"{'agrees' if agree[-1] else 'differs'}")
    _require(max(errors) <= logits_tol,
             f"prefill logits rel L2 {max(errors)!r} > {logits_tol}")
    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    print(f"[smoke] peak_bytes_in_use {peak if peak is not None else 'not reported'}")
    return {"tokens": served, "logits_rel_l2": errors,
            "argmax_agree": agree, "peak_bytes_in_use": peak}


def tp_phase(cfg, *, tol: float = 1e-2) -> dict:
    """Tensor-parallel serving under each psum mode on a (1, 4) mesh.

    At float32 activations each mode is compared with ``xla_spmd`` on one
    prefill chunk's logits, on one decode step's logits after that
    prefill, and on the generated tokens.  The modes sum the row-parallel
    partials in different orders; in bfloat16 that rounding alone moves
    the logits of 28 random layers by about 2% (rel L2), which would hide a
    wrong collective.  In float32 a correct mode stays well inside ``tol``
    (on a TPU, default-precision matmuls still round their inputs to
    bfloat16, which leaves about 0.5%) and a wrong one is off by order one.
    Each mode then serves the same requests at the config's own activation
    dtype, the path users run, and must return them whole.
    """
    import jax
    import numpy as np

    from repro.launch.serve import run_engine
    from repro.serve.traffic import make_workload

    model_parallel, chunk, gen = 4, 64, 8
    modes = ("xla_spmd", "ina", "eject_inject", "auto")   # reference first
    n = len(jax.devices())
    _require(n >= model_parallel,
             f"{n} device(s); the TP phase needs {model_parallel}")
    f32 = dataclasses.replace(cfg, dtype="float32")
    _print_config(f32)
    requests = make_workload(
        2, qps=0.0, prompt_dist=f"uniform:{chunk}:{2 * chunk}",
        gen_dist=f"fixed:{gen}", seed=SEED, vocab=cfg.vocab, prefix="tp")
    probe = requests[0].prompt[:chunk]      # exactly one prefill chunk
    chunk_logits, step_logits, tokens = {}, {}, {}
    for mode in modes:
        args = _engine_args("--model-parallel", str(model_parallel),
                            "--psum-mode", mode, "--prefill-chunk",
                            str(chunk), "--slots", str(len(requests)),
                            "--check")
        print(f"[smoke] psum mode {mode}, {f32.dtype} activations")
        engine, report = run_engine(args, f32, requests)
        tokens[mode] = _check_served(report, requests, cfg.vocab)
        chunk_logits[mode] = np.asarray(engine.prefill_logits(probe),
                                        np.float32)
        step_logits[mode] = np.asarray(engine.decode_logits(probe),
                                       np.float32)
        del engine                          # free its params before the next
        print(f"[smoke] psum mode {mode}, {cfg.dtype} activations")
        engine, report = run_engine(args, cfg, requests)
        _check_served(report, requests, cfg.vocab)
        print(f"[smoke] {mode} at {cfg.dtype}: {len(requests)} requests "
              f"served whole, {report.checks} paged==monolithic checks passed")
        del engine
    ref = modes[0]
    result = {}
    for mode in modes[1:]:
        r = {"chunk_rel_l2": _rel_l2(chunk_logits[mode], chunk_logits[ref]),
             "step_rel_l2": _rel_l2(step_logits[mode], step_logits[ref]),
             "argmax_agree": float(np.mean(chunk_logits[mode].argmax(-1)
                                           == chunk_logits[ref].argmax(-1))),
             "tokens_equal": sum(a == b for rid in tokens[ref] for a, b in
                                 zip(tokens[mode][rid], tokens[ref][rid])),
             "tokens": sum(len(t) for t in tokens[ref].values())}
        print(f"[smoke] {mode} vs {ref} at {f32.dtype}: chunk logits rel L2 "
              f"{r['chunk_rel_l2']!r}, decode-step logits rel L2 "
              f"{r['step_rel_l2']!r}, argmax agreement {r['argmax_agree']!r} "
              f"of {chunk} rows, generated tokens {r['tokens_equal']}/"
              f"{r['tokens']} equal")
        result[mode] = r
    for mode, r in result.items():
        worst = max(r["chunk_rel_l2"], r["step_rel_l2"])
        _require(worst <= tol, f"{mode}: logits rel L2 {worst!r} > {tol}")
        _require(r["tokens_equal"] == r["tokens"],
                 f"{mode}: {r['tokens_equal']}/{r['tokens']} generated "
                 f"tokens equal to {ref}")
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: serve and check on one chip; 4: only the "
                         "tensor-parallel psum-mode comparison")
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    dev = devices[0]
    print(f"[smoke] devices: {len(devices)} x {dev.device_kind} "
          f"({dev.platform})")
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, found {dev.platform!r}",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, found {len(devices)}", file=sys.stderr)
        return 1

    from repro.configs import ARCHS
    from repro.launch.compile_cache import enable_compile_cache

    print(f"[smoke] compile cache: {enable_compile_cache()}")
    cfg = ARCHS[ARCH]
    if args.chips == 4:
        tp_phase(cfg)
    else:
        serve_phase(cfg)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
