"""Tensor parallelism with the paper's INA toggle.

Column-parallel projections shard the *output* feature dim over the ``model``
axis and need no communication.  Row-parallel projections shard the
*contraction* dim — each device produces a full-shape **partial sum**, the
exact WS-dataflow situation of the paper (weights split across PEs), and the
accumulation strategy is selectable:

  * ``mode="ina"``        — XLA psum (lowers to in-network reduce on the ICI
                            ring; the INA fast path)
  * ``mode="ina_ring"``   — explicit chunked ring with in-flight accumulation
                            (the paper's algorithm, visible in HLO)
  * ``mode="eject_inject"`` — full-tensor relay ring with endpoint adds
                            (the paper's Fig. 4(a) baseline)
  * ``mode="auto"``       — resolved per call site: from the attached
                            ``plan`` (a repro.plan.ExecutionPlan, decided
                            once per (config, mesh, phase, dtype) and
                            persisted) when one is carried, else at trace
                            time by the NoC collective cost model (simulated
                            mesh latency of each strategy for this tensor
                            size / axis span; repro.core.noc.collective.cost)
  * ``mode="xla_spmd"``   — no shard_map at all: plain einsum, GSPMD chooses

The shard_map regions are *partial*: only the ``model`` axis is manual; the
``data``/``pod`` axes stay auto (GSPMD handles batch/FSDP sharding through
the region transparently).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map

from repro.core.collectives import psum_with_mode


@dataclass(frozen=True)
class ParallelCtx:
    """How model-axis parallelism is executed inside the forward pass."""
    mesh: Optional[Mesh] = None
    psum_mode: str = "xla_spmd"   # xla_spmd | ina | ina_ring | eject_inject
                                  # | auto (NoC-simulated cost picks per site)
    axis: str = "model"
    plan: Optional[object] = None  # repro.plan.ExecutionPlan: precomputed
                                  # per-site strategies consulted by
                                  # mode="auto" (None -> trace-time fallback)
    seq_shard: bool = True        # Megatron-style sequence-sharded activations
    rs_seq: bool = False          # row-parallel psum -> reduce-scatter(seq):
                                  # the INA output stays scattered (SP fusion)
    sp_entry: bool = False        # rs_seq via explicit bf16 ppermute ring
    serve_replicated_params: bool = False   # serving layout: params TP-only
                                  # (no FSDP) — kills per-token param gathers

    @property
    def manual(self) -> bool:
        return self.mesh is not None and self.psum_mode != "xla_spmd" \
            and self.axis in self.mesh.axis_names and \
            self.mesh.shape[self.axis] > 1


def col_linear(x: jax.Array, w: jax.Array, pctx: Optional[ParallelCtx] = None,
               b: Optional[jax.Array] = None) -> jax.Array:
    """Column-parallel matmul: w sharded on its last dim; no communication."""
    out = jnp.einsum("...d,df->...f", x, w.astype(x.dtype))
    if b is not None:
        out = out + b.astype(x.dtype)
    return out


def row_linear(x: jax.Array, w: jax.Array, pctx: Optional[ParallelCtx] = None,
               b: Optional[jax.Array] = None) -> jax.Array:
    """Row-parallel matmul + psum: the paper's INA site.

    ``x``: [..., F] activations sharded on F over the model axis;
    ``w``: [F, D] sharded on F.  Every device computes a partial [..., D]
    and partials are accumulated per ``pctx.psum_mode``.
    """
    if pctx is None or not pctx.manual:
        out = jnp.einsum("...f,fd->...d", x, w.astype(x.dtype))
    else:
        nd = x.ndim
        xs = P(*([None] * (nd - 1)), pctx.axis)
        ws = P(pctx.axis, None)
        span = pctx.mesh.shape[pctx.axis]
        rs_seq = (pctx.rs_seq and nd == 3 and x.shape[1] % span == 0
                  and x.shape[1] >= span)
        if rs_seq:
            # In-network accumulation straight into the sequence-parallel
            # layout: each hop accumulates and keeps only its seq shard —
            # half the wire bytes of RS+AG and no re-gather before the
            # residual add (the carry is seq-sharded anyway).
            os_ = P(None, pctx.axis, None)

            def local(xl, wl):
                partial = jnp.einsum("...f,fd->...d", xl,
                                     wl.astype(xl.dtype))
                if pctx.sp_entry:
                    # bf16-safe in-flight ring (ppermute-based; avoids the
                    # f32-wire CPU workaround of psum_scatter)
                    from repro.core.collectives import ring_reduce_scatter_ina
                    return ring_reduce_scatter_ina(partial, pctx.axis,
                                                   scatter_axis=1)
                from repro.core.collectives import reduce_scatter_with_mode
                return reduce_scatter_with_mode(partial, pctx.axis,
                                                pctx.psum_mode,
                                                scatter_axis=1,
                                                plan=pctx.plan)
        else:
            os_ = P(*([None] * nd))

            def local(xl, wl):
                partial = jnp.einsum("...f,fd->...d", xl,
                                     wl.astype(xl.dtype))
                return psum_with_mode(partial, pctx.axis, pctx.psum_mode,
                                      scatter_axis=partial.ndim - 1,
                                      plan=pctx.plan)

        out = shard_map(local, mesh=pctx.mesh, in_specs=(xs, ws),
                        out_specs=os_, axis_names={pctx.axis},
                        check_vma=False)(x, w)
    if b is not None:
        out = out + b.astype(x.dtype)
    return out


def combine_experts(combine: jax.Array, expert_out: jax.Array,
                    pctx: Optional[ParallelCtx] = None) -> jax.Array:
    """Combine expert-parallel outputs: the MoE INA site.

    ``combine``: [B, S, E, C] combine weights; ``expert_out``: [E, C, D]
    per-expert outputs, both sharded on the expert dim E over the model axis
    (EP).  The contraction over E produces per-device partial sums that are
    accumulated per ``pctx.psum_mode`` — the same WS psum situation as
    row-parallel linears, with experts in place of weight slices.
    """
    if pctx is None or not pctx.manual:
        return jnp.einsum("bsec,ecd->bsd", combine,
                          expert_out.astype(combine.dtype))

    def local(cl, el):
        partial = jnp.einsum("bsec,ecd->bsd", cl, el.astype(cl.dtype))
        return psum_with_mode(partial, pctx.axis, pctx.psum_mode,
                              scatter_axis=partial.ndim - 1,
                              plan=pctx.plan)

    return shard_map(
        local, mesh=pctx.mesh,
        in_specs=(P(None, None, pctx.axis, None), P(pctx.axis, None, None)),
        out_specs=P(None, None, None), axis_names={pctx.axis},
        check_vma=False)(combine, expert_out)


def constrain_acts(x: jax.Array, pctx: Optional[ParallelCtx],
                   seq_dim: int = 1) -> jax.Array:
    """Sequence-parallel activation constraint between layers.

    Shards [B, S, D] activations: batch over (pod, data), sequence over the
    model axis (Megatron SP) — this bounds the per-device residual-carry
    memory of the layer scan.  No-op when the dims do not divide (decode
    S=1) or there is no mesh.
    """
    if pctx is None or pctx.mesh is None:
        return x
    from jax.sharding import NamedSharding
    mesh = pctx.mesh
    baxes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    bspan = 1
    for a in baxes:
        bspan *= mesh.shape[a]
    spec = [None] * x.ndim
    if baxes and x.shape[0] % bspan == 0 and x.shape[0] >= bspan:
        spec[0] = baxes if len(baxes) > 1 else baxes[0]
    mspan = mesh.shape.get(pctx.axis, 1)
    if pctx.seq_shard and mspan > 1 and x.ndim > seq_dim and             x.shape[seq_dim] % mspan == 0 and x.shape[seq_dim] >= mspan:
        spec[seq_dim] = pctx.axis
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(*spec)))
