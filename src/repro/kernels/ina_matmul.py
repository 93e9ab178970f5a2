"""INA matmul kernel: K-blocked matmul with a VMEM-resident accumulator.

The chip-level analogue of the paper's In-Network Accumulation (DESIGN.md
S2.2): when the contraction dim is blocked (the PE's "weights split across
multiple memory-limited units"), partial sums either
  (a) bounce through HBM per K-block — eject/inject (kernels/ref.py), or
  (b) stay resident in VMEM across the K grid and only the finished tile is
      written — in-network accumulation (this kernel).
The MXU sees hardware-aligned (multiples of 128) tiles via BlockSpecs.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, w_ref, o_ref, acc_ref, *, nk: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(x_ref[...], w_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def ina_matmul(x: jax.Array, w: jax.Array, *, bm: int = 256, bn: int = 256,
               bk: int = 512, interpret: bool = False) -> jax.Array:
    """[M, K] @ [K, N] with in-VMEM psum accumulation over K blocks.

    The static defaults suit MXU-aligned shapes; planned per-shape blocks
    (an :class:`repro.plan.ExecutionPlan`'s ``tile_for``) arrive as
    ``bm``/``bn``/``bk`` via :func:`repro.kernels.ops.matmul`.  Blocks
    must divide the problem dims exactly (the plan's chooser guarantees
    this; hand-picked blocks are asserted below).
    """
    m, k = x.shape
    k2, n = w.shape
    assert k == k2, (x.shape, w.shape)
    bm, bn, bk = min(bm, m), min(bn, n), min(bk, k)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, \
        f"dims {(m, n, k)} not divisible by blocks {(bm, bn, bk)}"
    nk = k // bk

    return pl.pallas_call(
        functools.partial(_kernel, nk=nk),
        grid=(m // bm, n // bn, nk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, w)
