"""The planning pass: one abstract trace + one resolution sweep per plan.

:func:`build_plan` runs **once per (model config, mesh shape, phase,
dtype)** and produces an :class:`~.plan.ExecutionPlan` in three steps:

1. *Site collection* — the real model code is traced abstractly
   (``jax.eval_shape``; zero FLOPs, zero devices) with
   :func:`repro.core.collectives.record_psum_sites` active, so every
   ``mode="auto"`` psum site reports its (axis span, payload) instead of
   resolving itself.  Meshes of any shape trace on a single-CPU container
   via ``jax.sharding.AbstractMesh`` — the spans are what matter, not the
   devices.
2. *Resolution* — the deduplicated site shapes are costed once each
   through the NoC collective cost model (riding the persistent
   ``SIM_CACHE``, so a warm store resolves with zero engine runs) and the
   winning strategy recorded alongside the full candidate comparison.
3. *Mapper + tiles* — the config's decoder-block GEMMs get a PR-3 mapping
   search verdict (through the same sim cache) and a pallas tile choice
   (:mod:`.tiles`, pure arithmetic).

The builder imports jax lazily: the experiments CLI only pays for it when
the plan section actually runs.
"""
from __future__ import annotations

from typing import Optional, Sequence

from repro.configs.base import ModelConfig, ShapeConfig, SHAPES
from repro.core.noc import NocConfig

from .plan import (ExecutionPlan, GemmVerdict, PsumDecision, TileChoice,
                   config_digest, plan_schema_hash)
from .tiles import choose_tiles

#: Phase -> the canonical ShapeConfig traced for it.
PHASES = ("train", "prefill", "decode")
PHASE_SHAPES = {"train": "train_4k", "prefill": "prefill_32k",
                "decode": "decode_32k"}


def normalize_mesh(mesh_shape) -> tuple[tuple[str, int], ...]:
    """((axis, span), ...) from a Mesh/AbstractMesh, dict, or pair list."""
    shape = getattr(mesh_shape, "shape", mesh_shape)
    if hasattr(shape, "items"):
        return tuple((str(a), int(s)) for a, s in shape.items())
    return tuple((str(a), int(s)) for a, s in shape)


def trace_mesh(mesh_shape):
    """A mesh to trace over: real meshes pass through, shapes become
    ``AbstractMesh`` (no devices needed — only axis spans drive planning)."""
    import jax
    if isinstance(mesh_shape, jax.sharding.Mesh):
        return mesh_shape
    names, sizes = zip(*normalize_mesh(mesh_shape))
    return jax.sharding.AbstractMesh(sizes, names)


def phase_shape(phase: str, shape: Optional[ShapeConfig] = None,
                ) -> ShapeConfig:
    if shape is not None:
        return shape
    if phase not in PHASE_SHAPES:
        raise ValueError(f"unknown phase {phase!r}; pick from {PHASES}")
    return SHAPES[PHASE_SHAPES[phase]]


def collect_psum_sites(cfg: ModelConfig, mesh, shape: ShapeConfig,
                       pctx=None) -> list:
    """Abstract-trace one phase and return its recorded ``PsumSite`` list."""
    import jax
    from repro.core.collectives import record_psum_sites
    from repro.models.api import get_model
    from repro.parallel.tp import ParallelCtx

    model = get_model(cfg)
    if pctx is None:
        pctx = ParallelCtx(mesh=mesh, psum_mode="auto")
    pshapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    batch = model.input_specs(shape)
    with record_psum_sites() as sites:
        if shape.kind == "train":
            jax.eval_shape(lambda p, b: model.loss(p, b, pctx),
                           pshapes, batch)
        elif shape.kind == "prefill":
            jax.eval_shape(lambda p, b: model.forward(p, b, pctx),
                           pshapes, batch)
        else:
            cshapes = jax.eval_shape(
                lambda: model.init_cache(shape.global_batch, shape.seq_len))
            jax.eval_shape(
                lambda p, b, c: model.decode_step(p, b, c, pctx),
                pshapes, batch, cshapes)
    return sites


def resolve_sites(sites: Sequence, objective: str = "latency",
                  noc_cfg: NocConfig = NocConfig(), *,
                  chips: int = 1, package: str = "mesh",
                  ) -> tuple[PsumDecision, ...]:
    """Dedup recorded sites and cost each distinct shape exactly once.

    Resolution calls the same ``choose_psum_mode`` the planless fallback
    uses (same defaults, same tie-breaks), so a plan-driven run picks
    bit-identical strategies to today's per-call-site auto path.  With
    ``chips`` > 1 the TP axis spans chips and every site is priced
    through the hierarchical facade (DESIGN.md S14): intra-chip rows plus
    a package-level allreduce, same candidate set and tie-breaks.
    """
    from repro.core.noc.collective.cost import AUTO_CANDIDATES
    if chips > 1:
        from repro.core.noc.hierarchy import (choose_hier_psum_mode,
                                              hier_psum_mode_costs)

        def _costs(p, nbytes):
            return hier_psum_mode_costs(p, nbytes, noc_cfg, chips=chips,
                                        package=package)

        def _choose(p, nbytes):
            return choose_hier_psum_mode(p, nbytes, noc_cfg, chips=chips,
                                         package=package,
                                         objective=objective)
    else:
        from repro.core.noc.collective.cost import (choose_psum_mode,
                                                    psum_mode_costs)

        def _costs(p, nbytes):
            return psum_mode_costs(p, nbytes, noc_cfg)

        def _choose(p, nbytes):
            return choose_psum_mode(p, nbytes, noc_cfg, objective=objective)

    groups: dict[tuple[int, int], dict] = {}
    for s in sites:
        g = groups.setdefault((s.p, s.nbytes), {"count": 0, "ops": set()})
        g["count"] += 1
        g["ops"].add(s.op)
    out = []
    for (p, nbytes), g in sorted(groups.items()):
        costs = _costs(p, nbytes)
        mode = _choose(p, nbytes)
        out.append(PsumDecision(
            p=p, nbytes=nbytes, mode=mode,
            ops=tuple(sorted(g["ops"])), count=g["count"],
            costs=tuple((m, costs[m].latency_cycles, costs[m].energy_pj)
                        for m in AUTO_CANDIDATES)))
    return tuple(out)


#: (cfg, tokens, mapper_space) -> gemm_verdicts result.  Verdicts are a
#: pure function of those three (deterministic search; ``jobs`` only
#: parallelizes, PR-4's jobs-identity test), and train/prefill phases
#: share tokens=256 — without the memo every full plan sweep would run
#: the same search once per phase.
_GEMM_MEMO: dict = {}


def gemm_verdicts(cfg: ModelConfig, tokens: int, mapper_space: str = "quick",
                  jobs: int = 1,
                  ) -> tuple[tuple[GemmVerdict, ...],
                             Optional[tuple[int, int, int]]]:
    """Mapper search over the config's decoder-block GEMMs (PR-3 path)."""
    from repro.mapper import MapperConfig, QUICK_MAPPER, search_network
    from repro.models.api import get_model

    memo_key = (cfg, tokens, mapper_space)
    hit = _GEMM_MEMO.get(memo_key)
    if hit is not None:
        return hit
    layers = get_model(cfg).gemm_layers(tokens)
    mcfg = QUICK_MAPPER if mapper_space == "quick" else MapperConfig()
    out = search_network(f"{cfg.name}:gemm", layers, mcfg, jobs=jobs)
    by_name = {l.name: l for l in layers}
    verdicts = []
    for a, b in zip(out.best.assignments, out.baseline.assignments):
        layer = by_name[a.layer]
        verdicts.append(GemmVerdict(
            layer=a.layer, M=layer.M, K=layer.K, N=layer.N,
            mapping=a.mapping.label(), dataflow=a.mapping.dataflow,
            semantics=a.mapping.semantics,
            latency_cycles=a.latency_cycles, energy_pj=a.total_energy_pj,
            baseline_latency_cycles=b.latency_cycles,
            baseline_energy_pj=b.total_energy_pj))
    _GEMM_MEMO[memo_key] = (tuple(verdicts), out.best.hardware)
    return _GEMM_MEMO[memo_key]


def tile_choices(cfg: ModelConfig, tokens: int,
                 dtype: str) -> tuple[TileChoice, ...]:
    """Deduplicated pallas tile plan over the config's GEMM shapes."""
    from repro.models.api import get_model
    out, seen = [], set()
    for layer in get_model(cfg).gemm_layers(tokens):
        key = (layer.M, layer.K, layer.N, dtype)
        if key in seen:
            continue
        seen.add(key)
        bm, bn, bk = choose_tiles(layer.M, layer.K, layer.N, dtype)
        out.append(TileChoice(m=layer.M, k=layer.K, n=layer.N, dtype=dtype,
                              bm=bm, bn=bn, bk=bk))
    return tuple(sorted(out, key=lambda t: (t.m, t.k, t.n)))


def build_plan(cfg: ModelConfig, mesh_shape, phase: str, *,
               objective: str = "latency",
               mapper_space: str = "quick",
               gemm_search: bool = True,
               tokens: Optional[int] = None,
               shape: Optional[ShapeConfig] = None,
               noc_cfg: NocConfig = NocConfig(),
               jobs: int = 1,
               chips: int = 1,
               package: str = "mesh",
               pctx=None) -> ExecutionPlan:
    """One planning pass -> a frozen, serializable :class:`ExecutionPlan`.

    ``mesh_shape`` is a Mesh, AbstractMesh, dict, or (axis, span) pairs;
    ``tokens`` defaults to the mapper's 256-token M tile for train/prefill
    and the batch width for decode (a decode GEMM runs one token per
    sequence).  ``gemm_search=False`` skips the mapper verdicts (tile and
    psum planning keep working) for callers that only consume the runtime
    half.  ``chips`` > 1 prices every psum site on a mesh-of-meshes
    (``package`` selects the cross-chip fabric, DESIGN.md S14) and stamps
    the chip topology into the plan identity.
    """
    shape = phase_shape(phase, shape)
    mesh = normalize_mesh(mesh_shape)
    if tokens is None:
        tokens = shape.global_batch if shape.kind == "decode" else 256
    dtype = str(cfg.dtype)

    sites = collect_psum_sites(cfg, trace_mesh(mesh_shape), shape, pctx=pctx)
    psum = resolve_sites(sites, objective=objective, noc_cfg=noc_cfg,
                         chips=chips, package=package)
    if gemm_search:
        gemms, hardware = gemm_verdicts(cfg, tokens, mapper_space, jobs=jobs)
    else:
        gemms, hardware = (), None
    tiles = tile_choices(cfg, tokens, dtype)

    return ExecutionPlan(
        model=cfg.name, mesh=mesh, phase=phase, dtype=dtype,
        schema=plan_schema_hash(), objective=objective,
        psum=psum, gemms=gemms, tiles=tiles,
        mapper_hardware=hardware, mapper_space=mapper_space, tokens=tokens,
        noc=repr(noc_cfg), config=config_digest(cfg),
        chips=chips, package=package)
