"""Mixture-of-Experts decoder (llama4-scout family; MLA variant in mla.py).

Routing is Switch-style top-k with a fixed per-expert capacity so the
dispatch/combine are dense einsums (dry-run friendly, no ragged ops) and the
compiled FLOPs scale with *activated* parameters (tokens x top_k), not with
the total expert count.  Experts are sharded over the ``model`` axis (EP);
the combine contraction over experts is the paper's INA accumulation site
(see parallel/tp.py::combine_experts).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.models.transformer import _dtype, remat_policy
from repro.parallel.tp import ParallelCtx, constrain_acts


# --------------------------------------------------------------------------- #
# params
# --------------------------------------------------------------------------- #
def init_moe_mlp(key, cfg: ModelConfig) -> dict:
    m = cfg.moe
    ks = jax.random.split(key, 5)
    e, d, f = m.num_experts, cfg.d_model, m.d_ff_expert
    p = {
        "router": L.dense_init(ks[0], (d, e)),
        "w_gate": L.dense_init(ks[1], (e, d, f), in_dim=d),
        "w_up": L.dense_init(ks[2], (e, d, f), in_dim=d),
        "w_down": L.dense_init(ks[3], (e, f, d), in_dim=f),
    }
    if m.num_shared:
        p["shared"] = L.init_mlp(ks[4], d, m.d_ff_expert * m.num_shared)
    return p


def init_layer(key, cfg: ModelConfig, dense: bool = False) -> dict:
    k1, k2 = jax.random.split(key)
    hd = cfg.resolved_head_dim
    return {
        "ln1": jnp.ones((cfg.d_model,)),
        "attn": L.init_attn(k1, cfg.d_model, cfg.n_heads, cfg.n_kv_heads, hd,
                            cfg.qk_norm, cfg.qkv_bias),
        "ln2": jnp.ones((cfg.d_model,)),
        "mlp": (L.init_mlp(k2, cfg.d_model, cfg.d_ff) if dense
                else init_moe_mlp(k2, cfg)),
    }


def init(cfg: ModelConfig, key) -> dict:
    nd = cfg.moe.first_dense_layers
    keys = jax.random.split(key, cfg.n_layers + 2)
    dense_layers = [init_layer(keys[i], cfg, dense=True) for i in range(nd)]
    moe_layers = [init_layer(keys[i], cfg) for i in range(nd, cfg.n_layers)]
    params = {
        "embed": L.dense_init(keys[-2], (cfg.vocab, cfg.d_model)),
        "layers": jax.tree.map(lambda *xs: jnp.stack(xs), *moe_layers),
        "ln_f": jnp.ones((cfg.d_model,)),
        "lm_head": L.dense_init(keys[-1], (cfg.d_model, cfg.vocab),
                                in_dim=cfg.d_model),
    }
    if dense_layers:
        params["dense_layers"] = jax.tree.map(lambda *xs: jnp.stack(xs),
                                              *dense_layers)
    return params


# --------------------------------------------------------------------------- #
# MoE forward
# --------------------------------------------------------------------------- #
def _expert_partial(xt, gate_idx, pos, keep, gate_vals, wg, wu, wd,
                    e0, e_local: int, cap: int):
    """Dispatch -> FFN -> locally-combined partial output for experts
    [e0, e0+e_local).  Returns [T, D] partial sums (zero where no local
    expert contributed) — the WS psum that INA accumulates.
    """
    t, d = xt.shape
    k = gate_idx.shape[1]
    rel = gate_idx - e0
    local = (rel >= 0) & (rel < e_local) & keep
    rel_safe = jnp.where(local, rel, e_local)          # OOB -> dropped scatter

    # slot_token[e, c] = token index occupying capacity slot c of expert e.
    tids = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32)[:, None], (t, k))
    st = jnp.full((e_local, cap), t, jnp.int32)
    st = st.at[rel_safe, pos].set(tids, mode="drop")

    xt_pad = jnp.concatenate([xt, jnp.zeros((1, d), xt.dtype)], axis=0)
    xe = xt_pad[st]                                    # [e_local, C, D]

    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, wg.astype(xe.dtype)))
    h = h * jnp.einsum("ecd,edf->ecf", xe, wu.astype(xe.dtype))
    ye = jnp.einsum("ecf,efd->ecd", h, wd.astype(xe.dtype))   # [e_local, C, D]

    contrib = ye[jnp.clip(rel_safe, 0, e_local - 1), pos]     # [T, k, D]
    w = (gate_vals * local.astype(gate_vals.dtype)).astype(xt.dtype)
    return jnp.einsum("tkd,tk->td", contrib, w)


def moe_mlp(p: dict, x: jax.Array, cfg: ModelConfig,
            pctx: Optional[ParallelCtx] = None) -> tuple[jax.Array, jax.Array]:
    """Returns (output, aux_loss). x: [B, S, D].

    Experts are sharded over the model axis (EP).  Each device computes the
    partial combine owned by its local experts; the cross-device psum of
    those partials is the paper's INA accumulation site.
    """
    m = cfg.moe
    b, s, d = x.shape
    e, k = m.num_experts, m.top_k
    n_tok = b * s
    cap = min(max(8, int(n_tok * k * m.capacity_factor / e)), n_tok)

    logits32 = jnp.einsum("bsd,de->bse", x, p["router"].astype(x.dtype)
                          ).astype(jnp.float32)
    probs = jax.nn.softmax(logits32, axis=-1)                    # [B,S,E]
    gate_vals, gate_idx = jax.lax.top_k(probs, k)                # [B,S,k]
    gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)

    gate_idx = gate_idx.reshape(n_tok, k)
    gate_vals = gate_vals.reshape(n_tok, k)
    onehot = jax.nn.one_hot(gate_idx, e, dtype=jnp.float32)      # [T,k,E]
    pos_in_expert = (jnp.cumsum(onehot.reshape(n_tok * k, e), axis=0)
                     .reshape(n_tok, k, e) - 1.0)
    pos = (pos_in_expert * onehot).sum(-1)                       # [T,k]
    keep = (pos < cap) & (gate_vals > 0)
    pos = jnp.where(keep, pos, 0).astype(jnp.int32)
    xt = x.reshape(n_tok, d)

    if pctx is not None and pctx.manual:
        n_shards = pctx.mesh.shape[pctx.axis]
        e_local = e // n_shards
        from jax.sharding import PartitionSpec as P

        from jax import shard_map
        from repro.core.collectives import psum_with_mode

        def body(xt, gi, po, ke, gv, wg, wu, wd):
            i = jax.lax.axis_index(pctx.axis)
            dt_in = xt.dtype
            if jax.default_backend() == "cpu" and xt.dtype == jnp.bfloat16:
                # CPU-only: keep region tensors f32 so autodiff-generated
                # psums are f32 (XLA CPU AllReducePromotion crashes on bf16
                # all-reduce; see core/collectives._needs_f32_workaround)
                xt = xt.astype(jnp.float32)
            partial = _expert_partial(xt, gi, po, ke, gv, wg, wu, wd,
                                      i * e_local, e_local, cap)
            return psum_with_mode(partial, pctx.axis, pctx.psum_mode,
                                  scatter_axis=partial.ndim - 1,
                                  plan=pctx.plan).astype(dt_in)

        rep2 = P(None, None)
        out_flat = shard_map(
            body, mesh=pctx.mesh,
            in_specs=(rep2, rep2, rep2, rep2, rep2,
                      P(pctx.axis, None, None), P(pctx.axis, None, None),
                      P(pctx.axis, None, None)),
            out_specs=rep2, axis_names={pctx.axis}, check_vma=False,
        )(xt, gate_idx, pos, keep, gate_vals,
          p["w_gate"], p["w_up"], p["w_down"])
    else:
        out_flat = _expert_partial(xt, gate_idx, pos, keep, gate_vals,
                                   p["w_gate"], p["w_up"], p["w_down"],
                                   0, e, cap)

    out = out_flat.reshape(b, s, d)
    if "shared" in p:
        out = out + L.mlp_block(p["shared"], x, pctx)

    # Switch aux losses: load balance + router z-loss.
    me = probs.reshape(n_tok, e).mean(0)
    ce = (onehot * keep[..., None].astype(jnp.float32)).sum(1).mean(0)
    aux = m.aux_loss_coef * e * jnp.sum(me * ce) \
        + m.router_z_coef * jnp.mean(
            jnp.square(jax.nn.logsumexp(logits32, axis=-1)))
    return out, aux.astype(jnp.float32)


def layer_fwd(lp: dict, x: jax.Array, cfg: ModelConfig, cos, sin,
              pctx, dense: bool = False):
    hd = cfg.resolved_head_dim
    x = x + L.attn_block(lp["attn"], L.rms_norm(x, lp["ln1"], cfg.norm_eps),
                         n_heads=cfg.n_heads, n_kv=cfg.n_kv_heads, head_dim=hd,
                         cos=cos, sin=sin, causal=True, chunk=cfg.attn_chunk,
                         eps=cfg.norm_eps, pctx=pctx, unroll=cfg.scan_unroll)
    h = L.rms_norm(x, lp["ln2"], cfg.norm_eps)
    if dense:
        return constrain_acts(x + L.mlp_block(lp["mlp"], h, pctx), pctx), \
            jnp.float32(0)
    y, aux = moe_mlp(lp["mlp"], h, cfg, pctx)
    return constrain_acts(x + y, pctx), aux


def hidden_states(params: dict, cfg: ModelConfig, tokens: jax.Array,
                  pctx: Optional[ParallelCtx] = None):
    dt = _dtype(cfg)
    x = L.embed(params["embed"], tokens, dt)
    pos = jnp.arange(tokens.shape[1])
    cos, sin = L.rope_cos_sin(pos, cfg.resolved_head_dim, cfg.rope_theta)
    aux_total = jnp.float32(0)

    if "dense_layers" in params:
        def dbody(carry, lp):
            x, aux = carry
            x, a = layer_fwd(lp, x, cfg, cos, sin, pctx, dense=True)
            return (x, aux + a), None
        (x, aux_total), _ = jax.lax.scan(
            jax.checkpoint(dbody, policy=remat_policy(cfg)),
            (x, aux_total), params["dense_layers"],
            unroll=True if cfg.scan_unroll else 1)

    def body(carry, lp):
        x, aux = carry
        x, a = layer_fwd(lp, x, cfg, cos, sin, pctx)
        return (x, aux + a), None

    (x, aux_total), _ = jax.lax.scan(
        jax.checkpoint(body, policy=remat_policy(cfg)),
        (x, aux_total), params["layers"],
        unroll=True if cfg.scan_unroll else 1)
    return L.rms_norm(x, params["ln_f"], cfg.norm_eps), aux_total


def forward(params: dict, cfg: ModelConfig, batch: dict,
            pctx: Optional[ParallelCtx] = None) -> jax.Array:
    x, _ = hidden_states(params, cfg, batch["tokens"], pctx)
    return L.logits_head(x, params["lm_head"], pctx)


def loss(params: dict, cfg: ModelConfig, batch: dict,
         pctx: Optional[ParallelCtx] = None) -> jax.Array:
    x, aux = hidden_states(params, cfg, batch["tokens"], pctx)
    logits = L.logits_head(x, params["lm_head"], pctx)
    return L.xent_loss(logits, batch["labels"]) + aux


# --------------------------------------------------------------------------- #
# decode
# --------------------------------------------------------------------------- #
def init_cache(cfg: ModelConfig, batch: int, max_seq: int) -> dict:
    hd = cfg.resolved_head_dim
    dt = _dtype(cfg)
    nd = cfg.moe.first_dense_layers
    cache = {
        "k": jnp.zeros((cfg.n_layers - nd, batch, max_seq, cfg.n_kv_heads, hd), dt),
        "v": jnp.zeros((cfg.n_layers - nd, batch, max_seq, cfg.n_kv_heads, hd), dt),
    }
    if nd:
        cache["dk"] = jnp.zeros((nd, batch, max_seq, cfg.n_kv_heads, hd), dt)
        cache["dv"] = jnp.zeros((nd, batch, max_seq, cfg.n_kv_heads, hd), dt)
    return cache


def decode_step(params: dict, cfg: ModelConfig, batch: dict, cache: dict,
                pctx: Optional[ParallelCtx] = None):
    dt = _dtype(cfg)
    tokens, pos = batch["tokens"], batch["pos"]
    hd = cfg.resolved_head_dim
    x = L.embed(params["embed"], tokens, dt)
    cos, sin = L.rope_cos_sin(pos[None], hd, cfg.rope_theta)

    def make_body(dense):
        def body(x, lp_ck_cv):
            lp, ck, cv = lp_ck_cv
            h = L.rms_norm(x, lp["ln1"], cfg.norm_eps)
            y, ck, cv = L.attn_block_decode(
                lp["attn"], h, ck, cv, pos, n_heads=cfg.n_heads,
                n_kv=cfg.n_kv_heads, head_dim=hd, cos=cos, sin=sin,
                eps=cfg.norm_eps, pctx=pctx)
            x = x + y
            h = L.rms_norm(x, lp["ln2"], cfg.norm_eps)
            if dense:
                x = x + L.mlp_block(lp["mlp"], h, pctx)
            else:
                y, _ = moe_mlp(lp["mlp"], h, cfg, pctx)
                x = x + y
            return x, (ck, cv)
        return body

    new_cache = dict(cache)
    if "dk" in cache:
        x, kv = jax.lax.scan(make_body(True), x,
                             (params["dense_layers"], cache["dk"], cache["dv"]),
                             unroll=True if cfg.scan_unroll else 1)
        new_cache["dk"], new_cache["dv"] = kv
    x, kv = jax.lax.scan(make_body(False), x,
                         (params["layers"], cache["k"], cache["v"]),
                         unroll=True if cfg.scan_unroll else 1)
    new_cache["k"], new_cache["v"] = kv
    x = L.rms_norm(x, params["ln_f"], cfg.norm_eps)
    return L.logits_head(x, params["lm_head"], pctx), new_cache
