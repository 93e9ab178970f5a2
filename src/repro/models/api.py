"""Uniform model API: every family exposes init/forward/loss/cache/decode.

``input_specs`` builds ShapeDtypeStruct stand-ins for the dry-run (no
allocation); media-frontend archs get precomputed embeddings per the stub
rule.  ``param_specs`` derives FSDP+TP PartitionSpecs from parameter names.
"""
from __future__ import annotations

import dataclasses
from types import ModuleType

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig, ShapeConfig
from repro.models import encdec, hybrid, mla, moe, rwkv, transformer, vision

_FAMILIES: dict[str, ModuleType] = {
    "dense": transformer,
    "moe": moe,
    "mla_moe": mla,
    "ssm": rwkv,
    "hybrid": hybrid,
    "encdec": encdec,
    "vlm": vision,
}


@dataclasses.dataclass(frozen=True)
class Model:
    cfg: ModelConfig
    mod: ModuleType

    def init(self, key):
        params = self.mod.init(self.cfg, key)
        pd = jnp.dtype(self.cfg.param_dtype)
        if pd != jnp.float32:
            # store matrix weights in the compute dtype (halves FSDP
            # all-gather traffic); norms/scalars stay fp32
            params = jax.tree.map(
                lambda p: p.astype(pd) if p.ndim >= 2 else p, params)
        return params

    def init_served(self, key):
        """:meth:`init`'s params as the serving engine holds them: each
        leaf that :func:`served_dtypes` puts at ``cfg.dtype`` cast there,
        in the same program, the rest as :meth:`init` makes them."""
        params = self.init(key)
        return jax.tree.map(lambda p, dt: p.astype(dt), params,
                            served_dtypes(self.cfg, params))

    def forward(self, params, batch, pctx=None):
        return self.mod.forward(params, self.cfg, batch, pctx)

    def loss(self, params, batch, pctx=None):
        return self.mod.loss(params, self.cfg, batch, pctx)

    def init_cache(self, batch: int, max_seq: int):
        return self.mod.init_cache(self.cfg, batch, max_seq)

    def decode_step(self, params, batch, cache, pctx=None):
        return self.mod.decode_step(params, self.cfg, batch, cache, pctx)

    @property
    def has_prefill(self) -> bool:
        """Does this family implement a batched cache-populating prefill?
        Families without one fall back to a per-token decode loop in the
        serving engine (repro.serve.engine)."""
        return hasattr(self.mod, "prefill")

    def prefill(self, params, batch, cache, pctx=None, pos_offset=0):
        """Batched causal forward over a chunk that writes into ``cache``
        at absolute positions ``pos_offset..pos_offset+C-1``; returns
        (logits [B, C, V], new cache)."""
        if not self.has_prefill:
            raise NotImplementedError(
                f"family {self.cfg.family!r} has no batched prefill; "
                "use a decode-step loop")
        return self.mod.prefill(params, self.cfg, batch, cache, pctx,
                                pos_offset)

    def gemm_layers(self, tokens: int = 256):
        """One decoder block's GEMMs (:func:`repro.core.ops.transformer_gemms`)
        — the unit the plan builder's mapper search and pallas tile planning
        operate on.  Whole-model totals scale linearly in depth, so
        per-block verdicts are depth-invariant."""
        from repro.core.ops import transformer_gemms
        return transformer_gemms(self.cfg, tokens)

    # ------------------------------------------------------------------ #
    def input_specs(self, shape: ShapeConfig) -> dict:
        """ShapeDtypeStruct stand-ins for one (arch x shape) cell."""
        cfg = self.cfg
        b, s = shape.global_batch, shape.seq_len
        tok = jnp.int32
        if shape.kind in ("train", "prefill"):
            specs = {
                "tokens": jax.ShapeDtypeStruct((b, s), tok),
            }
            if shape.kind == "train":
                specs["labels"] = jax.ShapeDtypeStruct((b, s), tok)
        else:   # decode: one new token against a cache of length s
            specs = {
                "tokens": jax.ShapeDtypeStruct((b, 1), tok),
                "pos": jax.ShapeDtypeStruct((), jnp.int32),
            }
        if cfg.family in ("encdec", "vlm") and cfg.num_media_tokens:
            specs["media"] = jax.ShapeDtypeStruct(
                (b, cfg.num_media_tokens, cfg.d_model), jnp.dtype(cfg.dtype))
        return specs

    def batch_specs(self, shape: ShapeConfig, data_axes=("pod", "data"),
                    ) -> dict:
        """PartitionSpecs matching input_specs (batch over data/pod axes)."""
        bspec = P(data_axes)
        specs = {"tokens": bspec}
        if shape.kind == "train":
            specs["labels"] = bspec
        if shape.kind == "decode":
            specs["pos"] = P()
        if self.cfg.family in ("encdec", "vlm") and self.cfg.num_media_tokens:
            specs["media"] = bspec
        return specs


def get_model(cfg: ModelConfig) -> Model:
    if cfg.family not in _FAMILIES:
        raise KeyError(f"unknown family {cfg.family!r}; "
                       f"have {sorted(_FAMILIES)}")
    return Model(cfg, _FAMILIES[cfg.family])


# --------------------------------------------------------------------------- #
# parameter sharding rules (FSDP over 'data', TP over 'model')
# --------------------------------------------------------------------------- #
_COL_NAMES = ("wq", "wk", "wv", "w_up", "w_gate", "w_in", "wr", "wg",
              "lm_head", "w_uk", "w_uv", "w_dkv")
_ROW_NAMES = ("wo", "w_down", "w_out")


def _leaf_spec(path: tuple, leaf, mesh_shape: dict | None) -> P:
    names = [getattr(p, "key", getattr(p, "name", "")) for p in path]
    name = names[-1] if names else ""
    parent = names[-2] if len(names) > 1 else ""
    # stacked-layer leading dims stay unsharded
    lead = 0
    for n in names:
        if n in ("layers", "dense_layers", "enc_layers", "dec_layers",
                 "xlayers"):
            lead += 1
        elif n == "groups":
            lead += 2
    pre = (None,) * lead
    nd = leaf.ndim - lead

    def guard(spec_tail: tuple) -> P:
        """Drop axes that do not evenly divide the dimension."""
        dims = leaf.shape[lead:]
        out = []
        for size, ax in zip(dims, spec_tail):
            if ax is None or mesh_shape is None:
                out.append(ax)
            else:
                span = mesh_shape.get(ax, 1)
                out.append(ax if size % span == 0 and size >= span else None)
        return P(*pre, *out)

    if nd < 2:
        return P(*pre)                                     # norms, biases, ...
    if name == "embed":
        return guard(("model", "data"))                    # [V, D]
    if name in ("w_gate", "w_up", "w_down") and nd == 3:
        # MoE experts [E, D, F] / [E, F, D]: EP over model, FSDP inner
        return guard(("model", "data", None))
    if parent == "cmix" and name == "wv":
        return guard(("model", "data"))                    # [F, D] row-parallel
    if name in _ROW_NAMES:
        return guard(("model", "data"))
    if name in _COL_NAMES or nd == 2:
        return guard(("data", "model"))                    # [D, F] col-parallel
    return P(*pre)


def param_specs(params, mesh=None) -> dict:
    """PartitionSpec pytree mirroring ``params`` (name-rule based).

    With ``mesh``, axes that do not evenly divide a dimension are dropped
    (GQA KV projections narrower than the TP span, odd vocab sizes, ...).
    """
    mesh_shape = dict(mesh.shape) if mesh is not None else None
    return jax.tree_util.tree_map_with_path(
        lambda p, l: _leaf_spec(p, l, mesh_shape), params)


# --------------------------------------------------------------------------- #
# served dtypes: which leaves serving holds at the compute dtype
# --------------------------------------------------------------------------- #
_NORMS = (("ln1",), ("ln2",), ("ln_f",), ("q_norm",), ("k_norm",))
# Experts: the CPU expert-parallel path computes them in float32.
_EXPERTS = (("layers", "mlp", "w_gate"), ("layers", "mlp", "w_up"),
            ("layers", "mlp", "w_down"))
#: Per family served by the engine, the leaves (as the tail of their path)
#: that some use reads in float32: norm scales, the recurrences' decay and
#: bonus parameters, MoE experts.  Every other leaf of these families is
#: read only through a cast to ``cfg.dtype``.
_FLOAT32_USES = {
    "dense": _NORMS,
    "moe": _NORMS + _EXPERTS,
    "mla_moe": _NORMS + (("kv_norm",),) + _EXPERTS,
    "ssm": _NORMS + (("ln_in",), ("ln_x",), ("w0",), ("u",)),
    "hybrid": _NORMS + (("ln",), ("inv_norms",), ("gate_norm",),
                        ("A_log",), ("dt_bias",)),
}


def served_dtypes(cfg: ModelConfig, params) -> dict:
    """Pytree (mirroring ``params``) of the dtype serving holds each leaf
    at: ``cfg.dtype`` for a leaf that every use casts to it, so the held
    copy has the values each step would make; the leaf's own dtype for
    one read in float32 somewhere, and for every leaf of a family not
    listed in ``_FLOAT32_USES``."""
    keep = _FLOAT32_USES.get(cfg.family)
    dt = jnp.dtype(cfg.dtype)

    def dtype(path, leaf):
        names = tuple(getattr(p, "key", getattr(p, "name", "")) for p in path)
        if keep is None or any(names[-len(t):] == t for t in keep):
            return leaf.dtype
        return dt

    return jax.tree_util.tree_map_with_path(dtype, params)


def cache_batch_axes(cfg: ModelConfig) -> dict:
    """Pytree (mirroring the decode cache) of each leaf's batch-axis index.

    Derived from :func:`cache_specs` by planting a sentinel where the batch
    axes go, so per-family axis knowledge lives in exactly one place.  The
    serving engine uses this to vmap a per-request decode over cache slots
    and to slice single requests out of a batched cache
    (``repro.serve.kvcache`` / ``parallel.steps.build_paged_serve_step``).
    """
    marker = "__batch__"    # a one-name entry is stored as the bare name

    def axis_of(spec: P) -> int:
        for i, e in enumerate(spec):
            if e == marker:
                return i
        raise ValueError(f"cache spec {spec} has no batch axis")

    return jax.tree.map(axis_of, cache_specs(cfg, batch_axes=marker),
                        is_leaf=lambda x: isinstance(x, P))


# --------------------------------------------------------------------------- #
# decode-cache sharding intents (repaired against shapes by fit_specs)
# --------------------------------------------------------------------------- #
def cache_specs(cfg: ModelConfig, batch_axes=("pod", "data")) -> dict:
    """PartitionSpec intents matching init_cache's structure per family."""
    B = batch_axes
    kv5 = P(None, B, None, "model", None)          # [L, B, S, K, hd]
    if cfg.family in ("dense",):
        return {"k": kv5, "v": kv5}
    if cfg.family == "moe":
        out = {"k": kv5, "v": kv5}
        if cfg.moe.first_dense_layers:
            out["dk"] = kv5
            out["dv"] = kv5
        return out
    if cfg.family == "mla_moe":
        lat = {"latent": P(None, B, None, None),
               "k_rope": P(None, B, None, None)}
        out = {"moe": dict(lat)}
        if cfg.moe.first_dense_layers:
            out["dense"] = dict(lat)
        return out
    if cfg.family == "ssm":
        return {
            "state": P(None, B, "model", None, None),
            "tprev": P(None, B, None, "model"),
            "cprev": P(None, B, None, "model"),
        }
    if cfg.family == "hybrid":
        return {
            "ssm": P(None, None, B, "model", None, None),
            "conv": P(None, None, B, None, "model"),
            "k": P(None, B, None, "model", None),
            "v": P(None, B, None, "model", None),
        }
    if cfg.family == "encdec":
        return {"k": kv5, "v": kv5}
    if cfg.family == "vlm":
        kv6 = P(None, None, B, None, "model", None)
        mkv = P(None, B, None, "model", None)
        return {"k": kv6, "v": kv6, "mk": mkv, "mv": mkv}
    raise KeyError(cfg.family)
