"""RWKV6 WKV kernel: chunked data-dependent-decay recurrence.

Grid: (batch*heads, seq-chunks); the chunk dim is sequential so the [hd, hd]
(k x v) state lives in VMEM scratch across chunks — the recurrent state never
leaves the chip, the in-network-accumulation idea applied to a recurrence.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(r_ref, k_ref, v_ref, w_ref, u_ref, o_ref, state_ref, *,
            chunk: int):
    c = pl.program_id(1)

    @pl.when(c == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    r = r_ref[0].astype(jnp.float32)          # [C, hd]
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    logw = w_ref[0].astype(jnp.float32)       # [C, hd] (negative)
    u = u_ref[0].astype(jnp.float32)          # [1, hd]

    tpos = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    spos = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    # Inclusive prefix sum over the chunk as a lower-triangular matmul:
    # Mosaic has no cumsum lowering.  HIGHEST keeps the f32 sum exact.
    tril = (tpos >= spos).astype(jnp.float32)
    cum = jax.lax.dot_general(tril, logw, (((1,), (0,)), ((), ())),
                              precision=jax.lax.Precision.HIGHEST,
                              preferred_element_type=jnp.float32)  # [C, hd]
    cum_prev = cum - logw
    re = r * jnp.exp(cum_prev)
    # Factorized intra-chunk decay: exact while the per-chunk cumulative
    # decay stays <= 80 nats (clamp keeps saturated-decay regimes finite;
    # use a smaller chunk for exactness there).
    kf = k * jnp.exp(-jnp.maximum(cum, -80.0))
    scores = jax.lax.dot_general(re, kf, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    scores = jnp.where(tpos > spos, scores, 0.0)      # strictly causal
    diag = jnp.sum(r * u * k, axis=-1, keepdims=True)  # u-bonus (s == t)

    y = jax.lax.dot_general(scores, v, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    y = y + diag * v
    y = y + jax.lax.dot_general(re, state_ref[...], (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
    o_ref[0] = y.astype(o_ref.dtype)

    last = jax.lax.slice_in_dim(cum, chunk - 1, chunk, axis=0)   # [1, hd]
    tail = jnp.exp(last - cum)                # [C, hd]
    # The chunk's total decay as a column [hd, 1], summed by a transposed
    # matmul (Mosaic cannot turn the row ``last`` into a column).
    decay = jax.lax.dot_general(logw, jnp.ones((chunk, 1), jnp.float32),
                                (((0,), (0,)), ((), ())),
                                precision=jax.lax.Precision.HIGHEST,
                                preferred_element_type=jnp.float32)
    state_ref[...] = state_ref[...] * jnp.exp(decay) + \
        jax.lax.dot_general((k * tail), v, (((0,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def wkv6(r: jax.Array, k: jax.Array, v: jax.Array, logw: jax.Array,
         u: jax.Array, *, chunk: int = 128,
         interpret: bool = False) -> jax.Array:
    """r/k/v/logw: [BH, S, hd]; u: [BH, hd].  Returns [BH, S, hd]."""
    bh, s, hd = r.shape
    chunk = min(chunk, s)
    assert s % chunk == 0, (s, chunk)
    u3 = u[:, None, :]                        # [BH, 1, hd]

    return pl.pallas_call(
        functools.partial(_kernel, chunk=chunk),
        grid=(bh, s // chunk),
        in_specs=[
            pl.BlockSpec((1, chunk, hd), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, hd), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, hd), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, hd), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, 1, hd), lambda b, c: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, hd), lambda b, c: (b, c, 0)),
        scratch_shapes=[pltpu.VMEM((hd, hd), jnp.float32)],
        out_shape=jax.ShapeDtypeStruct((bh, s, hd), r.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(r, k, v, logw, u3)
