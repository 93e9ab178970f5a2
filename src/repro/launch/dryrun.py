import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this lowers the real train/serve/prefill step with
ShapeDtypeStruct inputs (no allocation), compiles it for the production
mesh, and records memory_analysis / cost_analysis / collective byte counts
parsed from the HLO — the inputs to EXPERIMENTS.md SS Dry-run/Roofline.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch llama3-8b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--psum-mode ina]
  PYTHONPATH=src python -m repro.launch.dryrun --all --out results/dryrun.json

``--psum-mode auto`` plans through the persistent ExecutionPlan store
(DESIGN.md S11): the first run builds and persists each cell's plan (plus
its collective-simulation rows), the second run plans entirely from the
warm store — 0 collective simulations, identical step artifacts.
"""
import argparse
import json
import re
import sys
import time
import traceback

import jax

from repro.configs import ARCHS, SHAPES, shape_applicable
from repro.launch.mesh import make_production_mesh
from repro.models.api import get_model
from repro.parallel.steps import build_prefill, build_serve_step, build_train_step
from repro.parallel.tp import ParallelCtx

# bytes of every collective op parsed out of the per-device HLO
_COLLECTIVE_RE = re.compile(
    r"(\w[\w.\-]*)\s*=\s*(?:\([^)]*\)|\S+)\s*"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)\b")

_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "u8": 1, "s8": 1,
                "u16": 2, "s16": 2, "u32": 4, "s32": 4, "u64": 8, "s64": 8,
                "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1}


def _shape_bytes(type_str: str) -> int:
    """Bytes of one HLO shape string like 'bf16[128,1024]{1,0}'."""
    total = 0
    for m in re.finditer(r"(\w+)\[([\d,]*)\]", type_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo: str) -> dict:
    """Sum output-shape bytes per collective kind from HLO text."""
    out: dict[str, float] = {}
    for line in hlo.splitlines():
        m = re.search(
            r"=\s*((?:\w+\[[^\]]*\][^ ]*|\([^)]*\)))\s*"
            r"(all-gather|all-reduce|reduce-scatter|all-to-all|"
            r"collective-permute)", line)
        if not m:
            continue
        kind = m.group(2)
        out[kind] = out.get(kind, 0) + _shape_bytes(m.group(1))
    out["total"] = sum(v for k, v in out.items() if k != "total")
    return out


def _lower_step(cfg, shape, mesh, pctx):
    model = get_model(cfg)
    if shape.kind == "train":
        from repro.optim.adamw import adamw_init
        ts = build_train_step(model, mesh, shape, pctx)
        opt_shapes = jax.eval_shape(adamw_init, ts.param_shapes)
        return ts.fn.lower(ts.param_shapes, opt_shapes,
                           model.input_specs(shape))
    if shape.kind == "prefill":
        fn, psh, bsh, pshapes = build_prefill(model, mesh, shape, pctx)
        return fn.lower(pshapes, model.input_specs(shape))
    ss = build_serve_step(model, mesh, shape, pctx)
    return ss.fn.lower(ss.param_shapes, model.input_specs(shape),
                       ss.cache_shapes)


def _cost_point(cfg, shape, mesh, pctx) -> dict:
    """flops/bytes/collective-bytes of one compiled (per-device) program."""
    compiled = _lower_step(cfg, shape, mesh, pctx).compile()
    cost = compiled.cost_analysis()
    coll = collective_bytes(compiled.as_text())
    return {"flops": cost.get("flops", 0.0),
            "bytes": cost.get("bytes accessed", 0.0),
            "coll": coll.get("total", 0.0), "coll_by_kind": coll}


def roofline_costs(cfg, shape, mesh, pctx, fast: bool = False) -> dict:
    """Per-unit marginal HLO costs via fully-unrolled shallow compiles,
    extrapolated to full depth (XLA cost_analysis counts scan bodies once —
    DESIGN.md S6).  ``fast``: single-compile variant (1 unit, fixed costs
    folded into the marginal -> <~5% overestimate of embed/logits terms);
    used for the chunk-heavy ssm/hybrid train/prefill cells where the
    two-point compile is prohibitive on this container.
    """
    from repro.configs.base import depth_scaled, depth_units
    units = depth_units(cfg)
    m1 = _cost_point(depth_scaled(cfg, 1), shape, mesh, pctx)
    out = {}
    if fast:
        for key in ("flops", "bytes", "coll"):
            out[key] = m1[key] * units
            out[f"{key}_per_unit"] = m1[key]
            out[f"{key}_fixed"] = 0.0
        out["units"] = units
        out["fast"] = True
        out["coll_by_kind_u2"] = m1["coll_by_kind"]
        return out
    m2 = _cost_point(depth_scaled(cfg, 2), shape, mesh, pctx)
    for key in ("flops", "bytes", "coll"):
        marginal = max(m2[key] - m1[key], 0.0)
        fixed = max(m1[key] - marginal, 0.0)
        out[key] = fixed + marginal * units
        out[f"{key}_per_unit"] = marginal
        out[f"{key}_fixed"] = fixed
    out["units"] = units
    out["coll_by_kind_u2"] = m2["coll_by_kind"]
    return out


def run_cell(arch: str, shape_name: str, mesh, psum_mode: str = "xla_spmd",
             verbose: bool = True, roofline: bool = True,
             plan_dir=None, use_plan: bool = True) -> dict:
    cfg = ARCHS[arch]
    shape = SHAPES[shape_name]
    model = get_model(cfg)
    # One plan per cell through the shared launch helper (same store keys
    # as train/serve); ``info`` carries the warm-store evidence — a warm
    # second dry-run plans every cell with 0 collective simulations.
    from repro.plan import plan_for_launch
    plan, plan_info = plan_for_launch(cfg, mesh, shape, psum_mode,
                                      plan_dir=plan_dir, enabled=use_plan,
                                      verbose=False)
    pctx = ParallelCtx(mesh=mesh, psum_mode=psum_mode, plan=plan)

    t0 = time.time()
    lowered = _lower_step(cfg, shape, mesh, pctx)
    t_lower = time.time() - t0

    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    coll = collective_bytes(hlo)

    n_dev = mesh.devices.size
    result = {
        "arch": arch, "shape": shape_name, "kind": shape.kind,
        "mesh": dict(mesh.shape), "devices": n_dev,
        "psum_mode": psum_mode,
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "flops_per_device": cost.get("flops", 0.0),
        "bytes_per_device": cost.get("bytes accessed", 0.0),
        "collective_bytes_per_device": coll,
        "memory": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", 0),
            "output_bytes": getattr(mem, "output_size_in_bytes", 0),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", 0),
            "peak_bytes": getattr(mem, "peak_memory_in_bytes", 0),
        },
    }
    if plan_info is not None:
        result["plan"] = plan_info
    if roofline:
        fast = cfg.family in ("ssm", "hybrid") and \
            shape.kind in ("train", "prefill")
        result["roofline"] = roofline_costs(cfg, shape, mesh, pctx,
                                            fast=fast)
    if verbose:
        print(f"[dryrun] {arch} x {shape_name} x {n_dev}dev "
              f"({psum_mode}): lower {t_lower:.1f}s compile {t_compile:.1f}s")
        if plan_info is not None:
            src = "warm store" if plan_info["from_store"] else "built"
            print(f"  plan: {plan_info['key']} ({src}, "
                  f"{plan_info['collective_sims']} collective sims, "
                  f"{plan_info['plan_s']}s) "
                  f"modes={plan_info['psum']['modes']}")
        print(f"  memory: args={result['memory']['argument_bytes']:.3e} "
              f"temp={result['memory']['temp_bytes']:.3e} "
              f"peak={result['memory']['peak_bytes']:.3e}")
        if roofline:
            r = result["roofline"]
            print(f"  roofline/dev: flops={r['flops']:.3e} "
                  f"bytes={r['bytes']:.3e} coll={r['coll']:.3e} "
                  f"(units={r['units']})")
    return result


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    from repro.core.collectives import CLI_PSUM_MODES
    from repro.plan import add_plan_cli_args
    ap.add_argument("--psum-mode", default="xla_spmd",
                    choices=CLI_PSUM_MODES)
    add_plan_cli_args(ap)
    ap.add_argument("--no-roofline", action="store_true",
                    help="skip the unrolled costing compiles")
    ap.add_argument("--out", default=None)
    ap.add_argument("--resume", action="store_true",
                    help="skip cells already present in --out")
    args = ap.parse_args()

    meshes = []
    if args.both_meshes:
        meshes = [make_production_mesh(multi_pod=False),
                  make_production_mesh(multi_pod=True)]
    else:
        meshes = [make_production_mesh(multi_pod=args.multi_pod)]

    cells = []
    if args.all:
        for arch, cfg in ARCHS.items():
            for sname, shp in SHAPES.items():
                if shape_applicable(cfg, shp):
                    cells.append((arch, sname))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        cells = [(args.arch, args.shape)]

    results, failures = [], []
    done = set()
    if args.out and args.resume:
        try:
            with open(args.out) as f:
                prev = json.load(f)
            results = prev.get("results", [])
            done = {(r["arch"], r["shape"], tuple(sorted(r["mesh"].items())))
                    for r in results}
            print(f"[dryrun] resuming: {len(done)} cells already done")
        except FileNotFoundError:
            pass

    def flush():
        if args.out:
            with open(args.out, "w") as f:
                json.dump({"results": results, "failures": failures}, f,
                          indent=1)

    for mesh in meshes:
        for arch, sname in cells:
            key = (arch, sname, tuple(sorted(dict(mesh.shape).items())))
            if key in done:
                continue
            try:
                multi = "pod" in mesh.axis_names
                results.append(run_cell(arch, sname, mesh, args.psum_mode,
                                        roofline=not (args.no_roofline or multi),
                                        plan_dir=args.plan_dir,
                                        use_plan=not args.no_plan))
            except Exception as e:               # noqa: BLE001
                traceback.print_exc()
                failures.append({"arch": arch, "shape": sname,
                                 "mesh": dict(mesh.shape), "error": str(e)})
            flush()

    if args.out:
        print(f"wrote {args.out}")
    print(f"\n{len(results)} cells OK, {len(failures)} failed")
    for f in failures:
        print(f"  FAIL {f['arch']} x {f['shape']} x {f['mesh']}: "
              f"{f['error'][:200]}")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
