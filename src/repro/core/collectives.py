"""INA as pod-scale collectives: accumulate-while-routing vs eject/inject.

The paper's dichotomy (Fig. 4) maps exactly onto how a partial-sum
all-reduce can be scheduled on a TPU ICI ring (DESIGN.md S2.1):

* ``ring_psum_eject_inject``  — Fig. 4(a).  The *full* psum tensor is relayed
  around the ring; at every stop it is "ejected" into the endpoint (added to
  the local accumulator) and the received tensor is "re-injected" for the
  next hop.  P-1 steps, each moving ``|x|`` bytes per link: per-link traffic
  ``(P-1) * |x|``.

* ``ring_reduce_scatter_ina`` — Fig. 4(b).  The tensor is chunked 1/P; each
  hop *accumulates the local contribution into the moving chunk and forwards
  it* — the add happens "in the network" (inside the step, fused with the
  permute), never bouncing through an endpoint buffer.  P-1 steps, each
  moving ``|x|/P``: per-link traffic ``(P-1)/P * |x|`` — a ~P x reduction,
  the datacenter-scale version of the paper's result.

* ``psum_ina``                — reduce-scatter + all-gather when the full
  reduced tensor is needed (2(P-1)/P * |x| per link).

``*_xla`` variants use XLA's native collectives (``psum_scatter`` /
``psum``), which lower to the same in-network schedule but let the compiler
fuse/overlap; the explicit ring variants keep the paper's algorithm visible
in the HLO (collective-permute chains) for the roofline analysis.

All functions must be called inside ``shard_map`` with ``axis_name`` bound.
"""
from __future__ import annotations

import functools
from contextlib import contextmanager
from dataclasses import dataclass
from typing import Literal, Optional

import jax
import jax.numpy as jnp

from jax.lax import axis_size

PsumMode = Literal["ina", "ina_ring", "eject_inject", "xla", "auto"]

#: The ``--psum-mode`` choices every launch CLI offers (one source of
#: truth for train/serve/dryrun argparse).
CLI_PSUM_MODES = ("xla_spmd", "ina", "ina_ring", "eject_inject", "auto")


# --------------------------------------------------------------------------- #
# Fig. 4(a): eject -> local add -> inject, hop by hop (full tensor each hop).
# --------------------------------------------------------------------------- #
def ring_psum_eject_inject(x: jax.Array, axis_name: str) -> jax.Array:
    """Unchunked ring all-reduce: P-1 full-tensor hops with endpoint adds."""
    p = axis_size(axis_name)
    if p == 1:
        return x
    perm = [(i, (i + 1) % p) for i in range(p)]
    acc = x
    send = x
    for _ in range(p - 1):
        send = jax.lax.ppermute(send, axis_name, perm)   # inject -> next hop
        acc = acc + send                                 # eject -> local add
    return acc


# --------------------------------------------------------------------------- #
# Fig. 4(b): chunked ring reduce-scatter with in-flight accumulation.
# --------------------------------------------------------------------------- #
def ring_reduce_scatter_ina(x: jax.Array, axis_name: str,
                            scatter_axis: int = 0) -> jax.Array:
    """In-network accumulation: each hop adds its contribution to the moving
    1/P chunk and forwards it.  Device ``i`` returns fully-reduced chunk ``i``.
    """
    p = axis_size(axis_name)
    if p == 1:
        return x
    if x.shape[scatter_axis] % p != 0:
        raise ValueError(
            f"scatter axis {scatter_axis} ({x.shape[scatter_axis]}) "
            f"not divisible by axis size {p}")
    i = jax.lax.axis_index(axis_name)
    c = x.shape[scatter_axis] // p
    perm = [(j, (j + 1) % p) for j in range(p)]

    def chunk(k):
        k = jnp.mod(k, p)
        return jax.lax.dynamic_slice_in_dim(x, k * c, c, axis=scatter_axis)

    # Each step the moving chunk arrives from the ring predecessor, our local
    # contribution is added (the INA add), and it is forwarded.  Seeded with
    # chunk (i-1) so that after p-1 steps device i holds chunk i summed over
    # every device (the moving chunk index decreases by one per hop).
    carry = chunk(i - 1)
    for s in range(p - 1):
        carry = jax.lax.ppermute(carry, axis_name, perm)
        carry = carry + chunk(i - 2 - s)   # in-network accumulation
    return carry


def ring_all_gather(x: jax.Array, axis_name: str, gather_axis: int = 0,
                    ) -> jax.Array:
    """Ring all-gather (P-1 hops of |x| each); inverse of the scatter."""
    p = axis_size(axis_name)
    if p == 1:
        return x
    i = jax.lax.axis_index(axis_name)
    perm = [(j, (j + 1) % p) for j in range(p)]
    c = x.shape[gather_axis]
    out_shape = list(x.shape)
    out_shape[gather_axis] = c * p
    out = jnp.zeros(out_shape, x.dtype)

    send = x
    out = jax.lax.dynamic_update_slice_in_dim(
        out, send, jnp.mod(i, p) * c, axis=gather_axis)
    for s in range(p - 1):
        send = jax.lax.ppermute(send, axis_name, perm)
        # After s+1 forwards we are holding the chunk owned by (i - s - 1).
        out = jax.lax.dynamic_update_slice_in_dim(
            out, send, jnp.mod(i - s - 1, p) * c, axis=gather_axis)
    return out


def psum_ina(x: jax.Array, axis_name: str, scatter_axis: int = 0) -> jax.Array:
    """Full all-reduce via INA: reduce-scatter (in-flight adds) + all-gather."""
    rs = ring_reduce_scatter_ina(x, axis_name, scatter_axis)
    return ring_all_gather(rs, axis_name, scatter_axis)


# --------------------------------------------------------------------------- #
# XLA-native fast paths (same in-network schedule, compiler-optimized).
# --------------------------------------------------------------------------- #
def _needs_f32_workaround(x: jax.Array) -> bool:
    """XLA CPU's AllReducePromotion pass crashes on bf16 all-reduce/
    reduce-scatter inside manual shard_map regions (``Invalid binary
    instruction opcode copy``).  Upcast around the collective on CPU only;
    TPU keeps bf16 on the wire.  The dry-run's measured collective bytes for
    these sites are therefore f32 (2x the TPU bf16 bytes) — noted in
    EXPERIMENTS.md."""
    return x.dtype == jnp.bfloat16 and jax.default_backend() == "cpu"


def psum_scatter_xla(x: jax.Array, axis_name: str, scatter_axis: int = 0,
                     ) -> jax.Array:
    if _needs_f32_workaround(x):
        return jax.lax.psum_scatter(
            x.astype(jnp.float32), axis_name, scatter_dimension=scatter_axis,
            tiled=True).astype(x.dtype)
    return jax.lax.psum_scatter(x, axis_name, scatter_dimension=scatter_axis,
                                tiled=True)


def psum_xla(x: jax.Array, axis_name: str) -> jax.Array:
    if _needs_f32_workaround(x):
        return jax.lax.psum(x.astype(jnp.float32), axis_name).astype(x.dtype)
    return jax.lax.psum(x, axis_name)


# --------------------------------------------------------------------------- #
# Simulated-mesh cost bridge: PsumMode selection driven by the NoC subsystem
# (repro.core.noc.collective.cost) instead of the per-link formulas below.
# --------------------------------------------------------------------------- #
def mesh_psum_costs(p: int, nbytes: int):
    """Simulated mesh allreduce cost per PsumMode (latency cycles, pJ)."""
    from repro.core.noc.collective.cost import psum_mode_costs
    return psum_mode_costs(p, nbytes)


def choose_psum_mode(p: int, nbytes: int,
                     objective: str = "latency") -> PsumMode:
    """Best PsumMode for a ``p``-device axis by simulated mesh cost."""
    from repro.core.noc.collective.cost import choose_psum_mode as _choose
    return _choose(p, nbytes, objective=objective)


# --------------------------------------------------------------------------- #
# ExecutionPlan bridge: how ``mode="auto"`` call sites resolve.
#
# Three regimes, in priority order (DESIGN.md S11):
#   1. *Recording* — inside :func:`record_psum_sites` the site's shape is
#      appended to the active trace and a shape-preserving stand-in mode is
#      returned without touching the simulator; the plan builder resolves
#      the deduplicated sites afterwards, once each.
#   2. *Plan-driven* — a :class:`repro.plan.ExecutionPlan` handed down from
#      ``ParallelCtx`` answers from its precomputed per-site table.
#   3. *Planless fallback* — the original trace-time path: the NoC
#      collective cost model simulates the candidate strategies for this
#      (span, payload), hoisted behind a process-wide memo so one site
#      shape costs one resolution per process no matter how many identical
#      call sites a model traces.
# --------------------------------------------------------------------------- #
@dataclass(frozen=True)
class PsumSite:
    """One ``mode="auto"`` call site, as seen at trace time."""

    op: str                 # "psum" | "reduce_scatter"
    p: int                  # axis span
    nbytes: int             # per-device partial-sum payload


_TRACE_SITES: Optional[list] = None


@contextmanager
def record_psum_sites():
    """Collect ``mode="auto"`` sites instead of resolving them.

    Inside the context every auto site appends a :class:`PsumSite` to the
    yielded list and traces under a fixed stand-in strategy (``"ina"``;
    every strategy is shape-preserving, so recording traces are exact).
    Used by the plan builder's abstract trace; reentrant.
    """
    global _TRACE_SITES
    prev, sites = _TRACE_SITES, []
    _TRACE_SITES = sites
    try:
        yield sites
    finally:
        _TRACE_SITES = prev


@functools.lru_cache(maxsize=None)
def _fallback_choice(p: int, nbytes: int,
                     objective: str = "latency") -> str:
    """Per-process memo of the planless resolution (one simulation set per
    distinct site shape per trace, however many sites share it)."""
    return choose_psum_mode(p, nbytes, objective=objective)


def resolve_auto_mode(op: str, p: int, nbytes: int,
                      plan: Optional[object] = None) -> str:
    """Resolve one ``mode="auto"`` site (see the regime table above).

    ``plan`` is duck-typed: anything with a ``psum_mode(p, nbytes) ->
    Optional[str]`` method (an :class:`repro.plan.ExecutionPlan`).  A plan
    miss — a site the plan never saw, e.g. after a shape change — falls
    back to the trace-time path rather than erroring, resolved under the
    *plan's* objective so one trace never mixes decision criteria.
    Known limit: the fallback costs under the default :class:`NocConfig`;
    a plan built with a custom ``noc_cfg`` (no CLI does this) should
    cover its sites or accept default-costed misses.
    """
    if _TRACE_SITES is not None:
        _TRACE_SITES.append(PsumSite(op=op, p=p, nbytes=int(nbytes)))
        return "ina"
    if plan is not None:
        mode = plan.psum_mode(p, int(nbytes))
        if mode is not None:
            return mode
        return _fallback_choice(p, int(nbytes),
                                getattr(plan, "objective", "latency"))
    return _fallback_choice(p, int(nbytes))


# --------------------------------------------------------------------------- #
# Mode dispatch used by the tensor-parallel layers.
# --------------------------------------------------------------------------- #
def psum_with_mode(x: jax.Array, axis_name: str, mode: PsumMode,
                   scatter_axis: int = 0,
                   plan: Optional[object] = None) -> jax.Array:
    """Fully-reduced psum under the selected accumulation strategy.

    ``mode="auto"`` resolves at trace time: from ``plan`` (an
    :class:`repro.plan.ExecutionPlan` carried by ``ParallelCtx``) when one
    is attached, else from the NoC collective cost model for this tensor
    size and axis span (the sizes are static under jit, so the simulation
    runs once per distinct shape — see :func:`resolve_auto_mode`).
    """
    if mode == "auto":
        p = axis_size(axis_name)
        mode = resolve_auto_mode("psum", p, x.nbytes, plan)
        if mode == "ina_ring" and x.shape[scatter_axis] % p != 0:
            # The chunked ring needs the scatter axis to divide; fall back
            # to the compiler-scheduled in-network reduce, which doesn't.
            mode = "ina"
    if mode == "eject_inject":
        return ring_psum_eject_inject(x, axis_name)
    if mode == "ina_ring":
        return psum_ina(x, axis_name, scatter_axis)
    if mode in ("ina", "xla"):
        return psum_xla(x, axis_name)
    raise ValueError(f"unknown psum mode: {mode}")


def reduce_scatter_with_mode(x: jax.Array, axis_name: str, mode: PsumMode,
                             scatter_axis: int = 0,
                             plan: Optional[object] = None) -> jax.Array:
    """Reduce-scattered psum (output stays sharded on ``scatter_axis``)."""
    if mode == "auto":
        mode = resolve_auto_mode("reduce_scatter", axis_size(axis_name),
                                 x.nbytes, plan)
    if mode == "eject_inject":
        # The baseline has no in-network reduction: full all-reduce, then the
        # caller's shard is sliced out locally (the ejected copy).
        full = ring_psum_eject_inject(x, axis_name)
        p = axis_size(axis_name)
        i = jax.lax.axis_index(axis_name)
        c = x.shape[scatter_axis] // p
        return jax.lax.dynamic_slice_in_dim(full, i * c, c, axis=scatter_axis)
    if mode == "ina_ring":
        return ring_reduce_scatter_ina(x, axis_name, scatter_axis)
    if mode in ("ina", "xla"):
        return psum_scatter_xla(x, axis_name, scatter_axis)
    raise ValueError(f"unknown psum mode: {mode}")


# --------------------------------------------------------------------------- #
# Analytic per-link traffic (bytes) — used by the roofline cross-check.
# --------------------------------------------------------------------------- #
def per_link_bytes(mode: PsumMode, p: int, nbytes: int,
                   need_full: bool = True) -> float:
    """Bytes crossing each ring link per psum of an ``nbytes`` tensor."""
    if p == 1:
        return 0.0
    if mode == "eject_inject":
        return (p - 1) * nbytes
    if mode in ("ina", "ina_ring", "xla", "auto"):
        rs = (p - 1) / p * nbytes
        return rs * 2 if need_full else rs
    raise ValueError(mode)


# --------------------------------------------------------------------------- #
# Bytes a traced program puts into its collectives.
# --------------------------------------------------------------------------- #
#: Primitives of the collectives the modes above emit inside ``shard_map``.
COLLECTIVE_PRIMITIVES = ("psum", "reduce_scatter", "ppermute", "all_gather")


def _sub_jaxprs(params: dict) -> list:
    out = []
    for v in params.values():
        for x in v if isinstance(v, (tuple, list)) else (v,):
            j = getattr(x, "jaxpr", x)          # ClosedJaxpr -> Jaxpr
            if hasattr(j, "eqns"):
                out.append(j)
    return out


def collective_bytes(jaxpr) -> int:
    """Bytes one device puts into the collectives of ``jaxpr`` (a traced
    program, e.g. from ``jax.make_jaxpr``) in one run: the operands of
    every :data:`COLLECTIVE_PRIMITIVES` equation, times the trip count of
    the scans around it.  Under ``ina`` that is the row-parallel partial
    sums' bytes; under the ring modes, every hop's.  The operands are as
    the program holds them, so on the CPU a bfloat16 psum counts at float32
    (:func:`_needs_f32_workaround`).  Collectives the compiler inserts
    (``xla_spmd``) are not in the trace and count 0."""
    total = 0
    for eqn in getattr(jaxpr, "jaxpr", jaxpr).eqns:
        name = eqn.primitive.name
        if name in COLLECTIVE_PRIMITIVES:
            total += sum(v.aval.size * v.aval.dtype.itemsize
                         for v in eqn.invars)
        inner = [collective_bytes(j) for j in _sub_jaxprs(eqn.params)]
        if name == "cond":                      # one branch runs
            total += max(inner, default=0)
        elif name == "while" and any(inner):
            raise ValueError("a collective inside a while loop runs an "
                             "unknown number of times")
        elif name == "scan":
            total += eqn.params["length"] * sum(inner)
        else:
            total += sum(inner)
    return total
