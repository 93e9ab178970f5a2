"""Blocked (flash) causal attention kernel — the prefill hot-spot.

Online-softmax over KV blocks with m/l/acc scratch resident in VMEM; the
[Sq, Sk] score matrix never exists.  Grid: (batch*heads, q-blocks, kv-blocks)
with the kv dim sequential ("arbitrary") so scratch carries across kv steps.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            nkv: int, bq: int, bkv: int, scale: float, causal: bool):
    kb = pl.program_id(2)
    qb = pl.program_id(1)

    @pl.when(kb == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    run = True
    if causal:
        # skip fully-masked blocks (block-sparsity of the causal mask)
        run = (kb * bkv) <= (qb * bq + bq - 1)

    @pl.when(run if causal else True)
    def _step():
        q = q_ref[0]                                   # [bq, d]
        k = k_ref[0]                                   # [bkv, d]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if causal:
            qpos = qb * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 0)
            kpos = kb * bkv + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 1)
            s = jnp.where(qpos >= kpos, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_ref[...] = l_ref[...] * alpha + p.sum(-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(kb == nkv - 1)
    def _flush():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("bq", "bkv", "causal", "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    bq: int = 512, bkv: int = 512, causal: bool = True,
                    interpret: bool = False) -> jax.Array:
    """q/k/v: [BH, S, D] (batch*heads flattened, KV already GQA-expanded)."""
    bh, sq, d = q.shape
    sk = k.shape[1]
    bq, bkv = min(bq, sq), min(bkv, sk)
    assert sq % bq == 0 and sk % bkv == 0
    nkv = sk // bkv
    scale = 1.0 / math.sqrt(d)

    return pl.pallas_call(
        functools.partial(_kernel, nkv=nkv, bq=bq, bkv=bkv, scale=scale,
                          causal=causal),
        grid=(bh, sq // bq, nkv),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bkv, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bkv, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)
