"""Build the program's serving engine for a cell and drive its window.

The engine is built as ``repro.launch.serve.run_engine`` builds it: the
CLI's defaults (prefill chunk, psum mode, block size), one
``plan_for_launch`` per phase, ``check=False``, and the weights made on
the device from the seed.  The window drives ``ServingEngine.run`` itself;
``bench.hooks`` times it from the outside.
"""
from __future__ import annotations

import time

from bench.hooks import Hooks, Served, Tracer, WindowClosed
from bench.traffic import make_traffic

#: The program's registry keys checked against a configuration file.
PROGRAM_KEYS = {
    "n_layers": "num_hidden_layers", "d_model": "hidden_size",
    "n_heads": "num_attention_heads", "n_kv_heads": "num_key_value_heads",
    "d_ff": "intermediate_size", "vocab": "vocab_size",
    "resolved_head_dim": "head_dim", "qkv_bias": "qkv_bias",
    "rope_theta": "rope_theta", "norm_eps": "rms_norm_eps",
    "tie_embeddings": "tie_word_embeddings", "param_dtype": None,
    "dtype": None,
}


def program_config(config: dict, overrides: dict | None = None):
    """The program's own ModelConfig for a configuration file; refuses one
    whose sizes or numerics differ from the file.  The file's
    ``program_overrides`` set what the registry's entry leaves at another
    value than the source (a norm's epsilon); ``overrides`` (tests only)
    shrink the entry."""
    import dataclasses

    from repro.configs import ARCHS

    cfg = dataclasses.replace(ARCHS[config["program_arch"]],
                              **config.get("program_overrides", {}),
                              **(overrides or {}))
    want = {k: config[v] for k, v in PROGRAM_KEYS.items() if v}
    want["param_dtype"] = config["numerics"]["param_dtype"]
    want["dtype"] = config["numerics"]["dtype"]
    got = {k: getattr(cfg, k) for k in PROGRAM_KEYS}
    diff = {k: (got[k], want[k]) for k in want if got[k] != want[k]}
    if cfg.family != "dense" or cfg.qk_norm or diff:
        raise ValueError(f"{cfg.name}: the program's config differs from "
                         f"{config['name']}.json (program, file): {diff}")
    return cfg


def build_engine(cfg, cell: dict, param_seed: int):
    """A ServingEngine as ``run_engine`` builds one, with the cell's slots,
    cache length and chips."""
    from repro.configs.base import ShapeConfig
    from repro.launch.mesh import make_host_mesh
    from repro.launch.serve import build_parser
    from repro.plan import plan_for_launch
    from repro.serve.engine import ServingEngine

    mp = cell.get("model_parallel", 1)
    args = build_parser().parse_args(["--arch", cfg.name])
    mesh = make_host_mesh(mp)
    slots, max_seq = cell["slots"], cell["max_seq"]
    plans = [plan_for_launch(cfg, mesh, ShapeConfig("cli", max_seq, slots, kind),
                             args.psum_mode, plan_dir=args.plan_dir,
                             enabled=not args.no_plan)[0]
             for kind in ("decode", "prefill")]
    return ServingEngine(
        cfg, slots=slots, max_seq=max_seq, block_size=args.block_size,
        prefill_chunk=args.prefill_chunk, psum_mode=args.psum_mode,
        prefill_plan=plans[1], decode_plan=plans[0],
        batched_prefill=not args.no_batched_prefill, check=False,
        model_parallel=mp, param_seed=param_seed)


def with_free_params(params: dict, param_seed: int, config: dict) -> dict:
    """``params`` with the norm scales and QKV biases that
    ``bench.reference.free_params`` draws from the seed, made on the
    device in one jitted call, each in the sharding of the leaf it
    replaces.  The other leaves are the same arrays."""
    import jax

    from bench.counts import sizes
    from bench.reference import free_params

    layers = params["layers"]
    place = {"ln1": ("layers", "ln1"), "ln2": ("layers", "ln2"),
             "ln_f": ("ln_f",)}
    if "bq" in layers["attn"]:
        place.update({b: ("layers", "attn", b) for b in ("bq", "bk", "bv")})

    def leaf(path):
        node = params
        for k in path:
            node = node[k]
        return node

    shardings = {k: leaf(p).sharding for k, p in place.items()}
    dtypes = {k: leaf(p).dtype for k, p in place.items()}
    free = jax.jit(lambda key: {
        k: v.astype(dtypes[k]) for k, v in
        free_params(key, sizes(config), "bq" in place).items()},
        out_shardings=shardings)(jax.random.PRNGKey(param_seed))
    for k, p in place.items():
        if free[k].shape != leaf(p).shape:
            raise ValueError(f"{'/'.join(p)}: the program holds "
                             f"{leaf(p).shape}, the draw is {free[k].shape}")
    out = dict(params, layers=dict(layers, attn=dict(layers["attn"])))
    for k, p in place.items():
        node = out
        for key in p[:-1]:
            node = node[key]
        node[p[-1]] = free[k]
    return out


def warm_up(engine, vocab: int) -> None:
    """Serve one request per prompt length up to the prefill chunk, so
    every eager operation of the serving loop (the index of a prompt's
    last row in its chunk, the slot take, the seat) has run once before
    the window.  Same programs, same shapes as the window's."""
    from repro.serve.batching import Request

    chunk = getattr(engine, "prefill_chunk", 8)
    engine.run([Request(rid=f"warm{n}", prompt_len=n, max_new=2,
                        prompt=tuple(3 + (7 * n + i) % (vocab - 3)
                                     for i in range(n)))
                for n in range(1, chunk + 1)])


def request_of(served: Served):
    from repro.serve.batching import Request
    # Due times are the harness's: the engine admits at once.
    return Request(rid=served.rid, prompt_len=len(served.prompt),
                   max_new=served.max_new, arrival=0.0, prompt=served.prompt)


def drive(engine, mix: dict, cell: dict, seed: int, seconds: float,
          vocab: int, tracer: Tracer | None = None,
          clock=time.perf_counter) -> Hooks:
    """Run the window; returns the hooks with every time they took.

    Open loop: the window opens now; each request is submitted when it
    falls due, by the admission wrapper, or by this loop when the engine
    has drained and sleeps until the next one is due.  Backlog: every
    request is submitted first; the window opens at the first decode step
    with every slot full."""
    items = make_traffic(mix, cell, seed, vocab, seconds)
    hooks = Hooks(engine, clock=clock, tracer=tracer)
    hooks.install(request_of)
    if mix["arrivals"] == "backlog":
        hooks.seconds = seconds
        for it in items:
            hooks.add(Served(it.rid, it.prompt, it.max_new, float("nan")),
                      submitted=True)
        try:
            engine.run([request_of(hooks.requests[it.rid]) for it in items])
        except WindowClosed:
            pass
        for r in hooks.requests.values():
            r.due_at = hooks.t0
    else:
        t0 = clock()
        hooks.open_window(t0, seconds)
        for it in items:
            hooks.add(Served(it.rid, it.prompt, it.max_new, t0 + it.due),
                      submitted=False)
        try:
            while True:
                t = hooks.enter("wait")
                due = hooks.submit_due(t)
                if due or engine.sched.has_work:
                    engine.run([request_of(r) for r in due])
                    continue
                nxt = hooks.pending[0].due_at if hooks.pending else hooks.t1
                hooks.begin_gap("wait", t)
                time.sleep(max(0.0, min(nxt, hooks.t1) - t))
        except WindowClosed:
            pass
    hooks.end_gap(clock())
    if hooks.tracer is not None:
        hooks.tracer.close(clock)
    if hooks.t0 is None:
        raise RuntimeError("the window never opened")
    return hooks
