"""train_step / serve_step builders with full sharding plumbing.

``build_train_step``: loss -> grads -> AdamW, params+optimizer FSDP/TP
sharded, batch sharded over (pod, data).  ``build_serve_step``: one-token
decode against a sharded KV cache.  Both return (jitted_fn, shardings) so
the dry-run can ``.lower().compile()`` them with ShapeDtypeStructs only.

Every builder accepts ``plan`` (a :class:`repro.plan.ExecutionPlan`): it is
attached to the ``ParallelCtx`` the step traces under, so ``mode="auto"``
psum sites read their precomputed strategy instead of re-consulting the NoC
cost model per call site (DESIGN.md S11).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, ShapeConfig
from repro.models.api import Model, cache_specs, param_specs
from repro.optim.adamw import AdamWState, adamw_init, adamw_update, cosine_schedule
from repro.parallel.sharding import fit_specs, shardings_for
from repro.parallel.tp import ParallelCtx


def _data_axes(mesh: Mesh):
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def cache_placement(model: Model, mesh: Mesh, batch: int, seq: int):
    """(shapes, shardings) of ``model``'s cache for ``batch`` rows of
    ``seq`` positions, placed as every step here places it."""
    cshapes = jax.eval_shape(partial(model.init_cache, batch, seq))
    cspecs = fit_specs(cache_specs(model.cfg, _data_axes(mesh)), cshapes,
                       mesh)
    return cshapes, jax.tree.map(lambda s: NamedSharding(mesh, s), cspecs,
                                 is_leaf=lambda x: isinstance(x, P))


def _with_plan(pctx: Optional[ParallelCtx], mesh: Mesh,
               plan) -> ParallelCtx:
    """The step's ParallelCtx, carrying ``plan`` when one was supplied.

    An explicit ``pctx.plan`` wins (the caller already decided); otherwise
    the plan handle is attached so auto psum sites resolve through it.
    """
    pctx = pctx if pctx is not None else ParallelCtx(mesh=mesh)
    if plan is not None and pctx.plan is None:
        pctx = dataclasses.replace(pctx, plan=plan)
    return pctx


@dataclasses.dataclass
class TrainStep:
    fn: object                    # jitted (params, opt, batch) -> ...
    param_sharding: dict
    opt_sharding: object
    batch_sharding: dict
    param_shapes: dict


def build_train_step(model: Model, mesh: Mesh, shape: ShapeConfig,
                     pctx: Optional[ParallelCtx] = None,
                     base_lr: float = 3e-4, warmup: int = 200,
                     total_steps: int = 10_000,
                     donate: bool = True, plan=None) -> TrainStep:
    cfg = model.cfg
    pctx = _with_plan(pctx, mesh, plan)
    lr = cosine_schedule(base_lr, warmup, total_steps)

    # Shapes without allocation; sharding intents fitted to real dims.
    pshapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    pspecs = fit_specs(param_specs(pshapes, mesh), pshapes, mesh)
    psh = jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs,
                       is_leaf=lambda x: isinstance(x, P))
    oshapes = jax.eval_shape(adamw_init, pshapes)
    osh = AdamWState(
        step=NamedSharding(mesh, P()),
        m=jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs,
                       is_leaf=lambda x: isinstance(x, P)),
        v=jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs,
                       is_leaf=lambda x: isinstance(x, P)))

    baxes = _data_axes(mesh)
    bspecs = model.batch_specs(shape, data_axes=baxes)
    _ishapes = model.input_specs(shape)
    bspecs = {k: fit_specs(v, _ishapes[k], mesh) for k, v in bspecs.items()}
    bsh = {k: NamedSharding(mesh, v) for k, v in bspecs.items()}

    def step(params, opt: AdamWState, batch):
        loss, grads = jax.value_and_grad(
            lambda p: model.loss(p, batch, pctx))(params)
        new_params, new_opt, stats = adamw_update(params, grads, opt, lr)
        stats["loss"] = loss
        return new_params, new_opt, stats

    jitted = jax.jit(
        step,
        in_shardings=(psh, osh, bsh),
        out_shardings=(psh, osh, None),
        donate_argnums=(0, 1) if donate else ())
    return TrainStep(fn=jitted, param_sharding=psh, opt_sharding=osh,
                     batch_sharding=bsh, param_shapes=pshapes)


@dataclasses.dataclass
class ServeStep:
    fn: object                    # jitted (params, batch, cache) -> ...
    param_sharding: dict
    cache_sharding: dict
    batch_sharding: dict
    param_shapes: dict
    cache_shapes: dict


def build_serve_step(model: Model, mesh: Mesh, shape: ShapeConfig,
                     pctx: Optional[ParallelCtx] = None,
                     donate_cache: bool = True, plan=None) -> ServeStep:
    cfg = model.cfg
    pctx = _with_plan(pctx, mesh, plan)
    baxes = _data_axes(mesh)

    pshapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    pspecs = fit_specs(param_specs(pshapes, mesh), pshapes, mesh)
    if pctx.serve_replicated_params:
        # Serving layout: drop FSDP axes so decode never gathers params
        # (params replicated over data/pod, sharded over model only).
        def _strip(spec):
            return P(*[tuple(a for a in (e if isinstance(e, tuple) else (e,))
                             if a == "model") or None
                       if e is not None else None for e in spec])
        pspecs = jax.tree.map(_strip, pspecs,
                              is_leaf=lambda x: isinstance(x, P))
    psh = jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs,
                       is_leaf=lambda x: isinstance(x, P))

    cshapes, csh = cache_placement(model, mesh, shape.global_batch,
                                   shape.seq_len)

    bspecs = model.batch_specs(shape, data_axes=baxes)
    _ishapes = model.input_specs(shape)
    bspecs = {k: fit_specs(v, _ishapes[k], mesh) for k, v in bspecs.items()}
    bsh = {k: NamedSharding(mesh, v) for k, v in bspecs.items()}

    def step(params, batch, cache):
        logits, new_cache = model.decode_step(params, batch, cache, pctx)
        # greedy next-token (serving semantics)
        next_tok = jnp.argmax(logits[:, -1, :], axis=-1)
        return next_tok, new_cache

    from repro.parallel.sharding import fit_spec
    tok_spec = fit_spec(P(baxes), (shape.global_batch,), mesh)
    jitted = jax.jit(
        step,
        in_shardings=(psh, bsh, csh),
        out_shardings=(NamedSharding(mesh, tok_spec), csh),
        donate_argnums=(2,) if donate_cache else ())
    return ServeStep(fn=jitted, param_sharding=psh, cache_sharding=csh,
                     batch_sharding=bsh, param_shapes=pshapes,
                     cache_shapes=cshapes)


@dataclasses.dataclass
class PagedServeStep:
    """Continuous-batching decode artifact (repro.serve engine).

    ``fn(params, batch, cache) -> (next_tok [B], cache)`` where
    ``batch = {"tokens": [B, 1], "pos": [B]}`` carries a **per-slot**
    position — each cache slot advances independently, which is what lets
    requests join/leave the batch at token boundaries.  Semantically slot
    ``i`` computes exactly what a ``B=1`` ``decode_step`` at ``pos[i]``
    would (the step is a vmap of the per-request decode), so continuous
    batching cannot change any request's output.  Built with
    ``logits=True`` the step returns each slot's logits ``[B, V]`` in place
    of ``next_tok``.
    """

    fn: object
    param_sharding: dict
    cache_sharding: dict
    param_shapes: dict
    cache_shapes: dict
    cache_batch_axes: dict        # per-leaf batch-axis index (slot plumbing)


def build_paged_serve_step(model: Model, mesh: Mesh, shape: ShapeConfig,
                           pctx: Optional[ParallelCtx] = None,
                           donate_cache: bool = True,
                           plan=None, logits: bool = False) -> PagedServeStep:
    """Per-slot-position decode step for the paged-KV serving engine.

    ``shape.global_batch`` is the slot count (the continuous-batching
    capacity), ``shape.seq_len`` the per-slot cache length.  Inactive slots
    simply decode garbage that the engine ignores; their cache writes land
    at positions the scheduler will overwrite before they become visible
    (decode attention is masked to ``<= pos``).
    """
    from repro.models.api import cache_batch_axes

    cfg = model.cfg
    pctx = _with_plan(pctx, mesh, plan)
    baxis = cache_batch_axes(cfg)

    pshapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    pspecs = fit_specs(param_specs(pshapes, mesh), pshapes, mesh)
    psh = jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs,
                       is_leaf=lambda x: isinstance(x, P))
    cshapes, csh = cache_placement(model, mesh, shape.global_batch,
                                   shape.seq_len)

    has_media = cfg.family in ("encdec", "vlm") and cfg.num_media_tokens

    def _row(params, tok, pos, cache_row, media_row=None):
        # One request's decode: vmap stripped the batch axis from every
        # cache leaf; reinsert a singleton so the family decode_step sees
        # its usual batched layout.
        cache_b = jax.tree.map(lambda l, a: jnp.expand_dims(l, a),
                               cache_row, baxis)
        batch = {"tokens": tok[None, :], "pos": pos}
        if media_row is not None:
            batch["media"] = media_row[None]
        out, new_cache = model.decode_step(params, batch, cache_b, pctx)
        out = out[0, -1, :]
        if not logits:
            out = jnp.argmax(out, axis=-1)
        return out, jax.tree.map(lambda l, a: jnp.squeeze(l, axis=a),
                                 new_cache, baxis)

    if has_media:
        vmapped = jax.vmap(_row, in_axes=(None, 0, 0, baxis, 0),
                           out_axes=(0, baxis))

        def step(params, batch, cache):
            return vmapped(params, batch["tokens"], batch["pos"], cache,
                           batch["media"])
    else:
        vmapped = jax.vmap(_row, in_axes=(None, 0, 0, baxis),
                           out_axes=(0, baxis))

        def step(params, batch, cache):
            return vmapped(params, batch["tokens"], batch["pos"], cache)

    # Per-slot tokens and positions are tiny: replicate them.
    rep = NamedSharding(mesh, P())
    jitted = jax.jit(step, in_shardings=(psh, rep, csh),
                     out_shardings=(rep, csh),
                     donate_argnums=(2,) if donate_cache else ())
    return PagedServeStep(fn=jitted, param_sharding=psh, cache_sharding=csh,
                          param_shapes=pshapes, cache_shapes=cshapes,
                          cache_batch_axes=baxis)


@dataclasses.dataclass
class PrefillStep:
    """Chunked cache-populating prefill artifact (repro.serve engine).

    ``fn(params, batch, cache) -> (logits [B, C, V], cache)`` with
    ``batch = {"tokens": [B, C], "pos0": scalar}``: one compile serves
    every chunk of a chunked prefill (``pos0`` is traced).
    """

    fn: object
    chunk: int
    param_sharding: dict
    cache_sharding: dict


def build_prefill_step(model: Model, mesh: Mesh, shape: ShapeConfig,
                       chunk: int, pctx: Optional[ParallelCtx] = None,
                       donate_cache: bool = True, plan=None) -> PrefillStep:
    """Batched prefill into a KV cache, ``chunk`` tokens per call.

    Requires the family to implement ``prefill`` (``model.has_prefill``);
    the serving engine falls back to a decode-step loop otherwise.
    ``shape`` fixes the cache geometry (slots x max_seq) like
    :func:`build_paged_serve_step`.
    """
    if not model.has_prefill:
        raise NotImplementedError(
            f"family {model.cfg.family!r} has no batched prefill")
    pctx = _with_plan(pctx, mesh, plan)

    pshapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    pspecs = fit_specs(param_specs(pshapes, mesh), pshapes, mesh)
    psh = jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs,
                       is_leaf=lambda x: isinstance(x, P))
    _, csh = cache_placement(model, mesh, shape.global_batch, shape.seq_len)

    def step(params, batch, cache):
        return model.prefill(params, {"tokens": batch["tokens"]}, cache,
                             pctx, pos_offset=batch["pos0"])

    # One chunk of tokens in, its logits out (read on the host): replicated.
    rep = NamedSharding(mesh, P())
    jitted = jax.jit(step, in_shardings=(psh, rep, csh),
                     out_shardings=(rep, csh),
                     donate_argnums=(2,) if donate_cache else ())
    return PrefillStep(fn=jitted, chunk=chunk, param_sharding=psh,
                       cache_sharding=csh)


def build_prefill(model: Model, mesh: Mesh, shape: ShapeConfig,
                  pctx: Optional[ParallelCtx] = None, plan=None):
    """Forward-only full-sequence pass (the prefill_32k cells)."""
    pctx = _with_plan(pctx, mesh, plan)
    pshapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    pspecs = fit_specs(param_specs(pshapes, mesh), pshapes, mesh)
    psh = jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs,
                       is_leaf=lambda x: isinstance(x, P))
    baxes = _data_axes(mesh)
    bspecs = model.batch_specs(shape, data_axes=baxes)
    _ishapes = model.input_specs(shape)
    bspecs = {k: fit_specs(v, _ishapes[k], mesh) for k, v in bspecs.items()}
    bsh = {k: NamedSharding(mesh, v) for k, v in bspecs.items()}

    def fwd(params, batch):
        return model.forward(params, batch, pctx)

    jitted = jax.jit(fwd, in_shardings=(psh, bsh))
    return jitted, psh, bsh, pshapes
