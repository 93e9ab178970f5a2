"""Dispatching wrappers: Pallas kernel on TPU, jnp reference elsewhere.

The framework's model code is pure JAX so the 512-device CPU dry-run can
compile it; these ops are the drop-in accelerated paths for real TPU runs
(``use_pallas=True``) and are validated against ref.py in interpret mode.
The interpreter runs only when the caller asks for it (``interpret=True``):
``use_pallas=True`` off a TPU raises rather than quietly interpreting.
"""
from __future__ import annotations

import jax

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.ina_matmul import ina_matmul
from repro.kernels.wkv6 import wkv6


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _use_pallas(use_pallas: bool | None, interpret: bool) -> bool:
    """Run the Pallas kernel?  ``None`` means "where a TPU is present"."""
    if interpret:
        return True
    on_tpu = _on_tpu()
    if use_pallas and not on_tpu:
        raise RuntimeError(
            f"use_pallas=True needs a TPU backend, not "
            f"{jax.default_backend()!r}; pass interpret=True to run the "
            "kernel in the Pallas interpreter")
    return on_tpu if use_pallas is None else use_pallas


def matmul(x: jax.Array, w: jax.Array, *, use_pallas: bool | None = None,
           interpret: bool = False, plan=None, **blocks) -> jax.Array:
    """``plan`` (a :class:`repro.plan.ExecutionPlan`) supplies the pallas
    block sizes for this problem shape when it planned one; explicit
    ``blocks`` kwargs always win (the caller measured something)."""
    if _use_pallas(use_pallas, interpret):
        if plan is not None and not blocks:
            tiles = plan.tile_for(x.shape[0], x.shape[1], w.shape[1],
                                  str(x.dtype))
            if tiles is not None:
                blocks = dict(zip(("bm", "bn", "bk"), tiles))
        return ina_matmul(x, w, interpret=interpret, **blocks)
    return ref.matmul_ref(x, w)


def attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
              causal: bool = True, use_pallas: bool | None = None,
              interpret: bool = False, **blocks) -> jax.Array:
    if _use_pallas(use_pallas, interpret):
        return flash_attention(q, k, v, causal=causal, interpret=interpret,
                               **blocks)
    return ref.attention_ref(q, k, v, causal=causal)


def wkv(r, k, v, logw, u, *, use_pallas: bool | None = None,
        interpret: bool = False, **kw) -> jax.Array:
    if _use_pallas(use_pallas, interpret):
        return wkv6(r, k, v, logw, u, interpret=interpret, **kw)
    return ref.wkv6_ref(r, k, v, logw, u)
