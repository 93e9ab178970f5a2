"""ExecutionPlan layer (DESIGN.md S11): determinism, persistence,
planless-fallback equivalence, and the collective-simulation counters.

Coverage map (ISSUE 5):

* plan JSON is byte-deterministic; the store round-trips plans and a
  schema-hash mismatch invalidates (rebuild, never stale reads);
* plan-driven ``psum_with_mode`` is numerically identical to the planless
  ``mode="auto"`` path (resolution-level equality here, device-level
  equality in the slow 8-device subprocess test);
* one site shape costs one simulation set per trace, rides the persistent
  sim cache (``COST_STATS`` deltas — the ROUTE_STATS-style regression),
  and the ``xla``/``ina`` lowering alias + auto candidate set are pinned;
* every registry config plans the decode phase (the ``--section plan``
  smoke unit).
"""
import json
import os
import subprocess
import sys

import pytest

from repro.configs import ARCHS
from repro.core.noc.collective.cost import (AUTO_CANDIDATES, COST_STATS,
                                            PSUM_MODE_LOWERING, _simulate,
                                            choose_psum_mode, psum_mode_costs)
from repro.core.noc.simcache import SIM_CACHE, fresh_sim_cache
from repro.plan import (ExecutionPlan, PlanStore, PsumDecision, build_plan,
                        choose_tiles, plan_schema_hash)

MESH = (("data", 16), ("model", 16))


def _decode_plan(arch="qwen2-1.5b", **kw):
    kw.setdefault("gemm_search", False)
    return build_plan(ARCHS[arch], MESH, "decode", **kw)


# --------------------------------------------------------------------------- #
# 1. Determinism + persistence
# --------------------------------------------------------------------------- #
def test_plan_json_byte_deterministic():
    a = build_plan(ARCHS["qwen2-1.5b"], MESH, "decode", gemm_search=True,
                   mapper_space="quick")
    b = build_plan(ARCHS["qwen2-1.5b"], MESH, "decode", gemm_search=True,
                   mapper_space="quick")
    assert a.to_json() == b.to_json()
    assert a == b and hash(a) == hash(b)


def test_plan_store_roundtrip(tmp_path):
    plan = _decode_plan()
    store = PlanStore(tmp_path)
    path = store.save(plan)
    assert path.name == f"{plan.key}.json"
    loaded = store.load(plan.key)
    assert loaded == plan
    assert loaded.to_json() == plan.to_json()
    # lookups survive the round trip
    d = plan.psum[0]
    assert loaded.psum_mode(d.p, d.nbytes) == d.mode


def test_plan_store_schema_invalidation(tmp_path):
    plan = _decode_plan()
    store = PlanStore(tmp_path)
    path = store.save(plan)
    doc = json.loads(path.read_text())
    doc["schema"] = "stale0000stale00"
    path.write_text(json.dumps(doc))
    assert store.load(plan.key) is None
    # get_or_build treats the stale file as cold and rebuilds in place
    rebuilt, built = store.get_or_build(ARCHS["qwen2-1.5b"], MESH, "decode",
                                        gemm_search=False)
    assert built and rebuilt.schema == plan_schema_hash()
    assert store.load(plan.key) == rebuilt


def test_plan_store_corrupt_file_is_cold(tmp_path):
    store = PlanStore(tmp_path)
    plan = _decode_plan()
    store.save(plan)
    store.path_for(plan.key).write_text("{not json")
    assert store.load(plan.key) is None


def test_store_rebuilds_on_build_param_mismatch(tmp_path):
    """The key covers (model, mesh, phase, dtype) only; explicit build
    parameters are checked against the stored plan — a quick-space store
    must never answer a full-space request as warm."""
    store = PlanStore(tmp_path)
    p1, built1 = store.get_or_build(ARCHS["qwen2-1.5b"], MESH, "decode",
                                    gemm_search=True, mapper_space="quick")
    assert built1 and p1.mapper_space == "quick"
    p2, built2 = store.get_or_build(ARCHS["qwen2-1.5b"], MESH, "decode",
                                    gemm_search=True, mapper_space="full")
    assert built2 and p2.mapper_space == "full"       # mismatch = rebuild
    _, built3 = store.get_or_build(ARCHS["qwen2-1.5b"], MESH, "decode",
                                   gemm_search=True, mapper_space="full")
    assert not built3                                 # now genuinely warm
    # a gemm-less plan cannot satisfy a caller that wants verdicts
    _, built4 = store.get_or_build(ARCHS["qwen2-1.5b"], MESH, "decode",
                                   gemm_search=False)
    assert not built4                # superset plan serves the plain request
    store2 = PlanStore(tmp_path / "bare")
    store2.get_or_build(ARCHS["qwen2-1.5b"], MESH, "decode",
                        gemm_search=False)
    _, rebuilt = store2.get_or_build(ARCHS["qwen2-1.5b"], MESH, "decode",
                                     gemm_search=True, mapper_space="quick")
    assert rebuilt


def test_store_rebuilds_on_config_edit(tmp_path):
    """A registry-config edit keeps the name/dtype (same key) but must go
    cold — the plan records a config-content digest."""
    import dataclasses
    store = PlanStore(tmp_path)
    cfg = ARCHS["qwen2-1.5b"]
    p1, _ = store.get_or_build(cfg, MESH, "decode", gemm_search=False)
    cfg2 = dataclasses.replace(cfg, d_ff=cfg.d_ff * 2)
    p2, built = store.get_or_build(cfg2, MESH, "decode", gemm_search=False)
    assert built and p2.config != p1.config
    assert p2.key == p1.key                   # same file, new content


def test_plan_miss_fallback_honors_plan_objective():
    from repro.core.collectives import resolve_auto_mode
    p, nbytes = 9, 77_777                     # unique; plan never saw it
    plan = ExecutionPlan(model="t", mesh=(("model", p),), phase="decode",
                         dtype="float32", objective="energy")
    assert resolve_auto_mode("psum", p, nbytes, plan) \
        == choose_psum_mode(p, nbytes, objective="energy")


def test_gemm_verdicts_memoized_across_phases():
    from repro.plan.builder import _GEMM_MEMO, gemm_verdicts
    cfg = ARCHS["qwen2-1.5b"]
    first = gemm_verdicts(cfg, 256, "quick")
    assert (cfg, 256, "quick") in _GEMM_MEMO
    assert gemm_verdicts(cfg, 256, "quick") is first   # shared, not re-run


def test_launch_phase_distinguishes_cli_shapes():
    from repro.configs.base import SHAPES, ShapeConfig
    from repro.plan import launch_phase
    a = ShapeConfig("cli", 16, 2, "decode")
    b = ShapeConfig("cli", 512, 32, "decode")
    assert launch_phase(a) != launch_phase(b)         # no plan-file collision
    assert launch_phase(SHAPES["decode_32k"]) == "decode"
    assert launch_phase(SHAPES["train_4k"]) == "train"
    assert launch_phase(SHAPES["long_500k"]) not in ("decode", "long_500k")


def test_get_or_build_warm_store_zero_sims(tmp_path):
    store = PlanStore(tmp_path)
    plan, built = store.get_or_build(ARCHS["qwen2-1.5b"], MESH, "decode",
                                     gemm_search=False)
    assert built
    runs0 = COST_STATS["engine_runs"]
    again, built2 = store.get_or_build(ARCHS["qwen2-1.5b"], MESH, "decode",
                                       gemm_search=False)
    assert not built2 and again == plan
    assert COST_STATS["engine_runs"] == runs0     # warm: zero simulations


# --------------------------------------------------------------------------- #
# 2. Planless-fallback equivalence (resolution level)
# --------------------------------------------------------------------------- #
def test_plan_decisions_match_planless_auto():
    """Every planned strategy equals what today's per-call-site auto path
    would pick — the mechanism behind bit-identical plan-driven steps."""
    plan = _decode_plan("llama3-8b")
    assert plan.psum, "decode trace found no auto psum sites"
    for d in plan.psum:
        assert plan.psum_mode(d.p, d.nbytes) == d.mode
        assert d.mode == choose_psum_mode(d.p, d.nbytes)
        assert d.mode in AUTO_CANDIDATES
    # unplanned site shapes miss (callers fall back, never error)
    assert plan.psum_mode(3, 999) is None


def test_resolve_auto_mode_regimes():
    from repro.core.collectives import record_psum_sites, resolve_auto_mode
    p, nbytes = 16, 1 << 20
    # recording: sites captured, nothing simulated
    runs0 = COST_STATS["engine_runs"] + COST_STATS["store_hits"]
    with record_psum_sites() as sites:
        stand_in = resolve_auto_mode("psum", p, nbytes)
    assert stand_in == "ina"
    assert [(s.op, s.p, s.nbytes) for s in sites] == [("psum", p, nbytes)]
    assert COST_STATS["engine_runs"] + COST_STATS["store_hits"] == runs0
    # plan-driven: the plan's answer wins
    plan = ExecutionPlan(model="t", mesh=(("model", p),), phase="decode",
                         dtype="float32",
                         psum=(PsumDecision(p=p, nbytes=nbytes,
                                            mode="eject_inject",
                                            ops=("psum",), count=1),))
    assert resolve_auto_mode("psum", p, nbytes, plan) == "eject_inject"
    # plan miss: trace-time fallback
    assert resolve_auto_mode("psum", p, 12345, plan) \
        == choose_psum_mode(p, 12345)


# --------------------------------------------------------------------------- #
# 3. Simulation counters (satellite: one sim set per site shape per trace,
#    persistent across processes via the window store)
# --------------------------------------------------------------------------- #
def test_auto_resolution_simulates_each_shape_once():
    p, nbytes = 6, 54_321                      # unique to this test
    with fresh_sim_cache():
        _simulate.cache_clear()
        runs0 = COST_STATS["engine_runs"]
        choose_psum_mode(p, nbytes)
        delta = COST_STATS["engine_runs"] - runs0
        # 4 modes, 3 distinct lowerings (xla aliases ina) -> 3 engine runs
        assert delta == 3
        choose_psum_mode(p, nbytes)
        psum_mode_costs(p, nbytes)
        assert COST_STATS["engine_runs"] - runs0 == 3    # memoized


def test_collective_sims_ride_persistent_store(tmp_path):
    p, nbytes = 7, 98_765                      # unique to this test
    with fresh_sim_cache():
        _simulate.cache_clear()
        choose_psum_mode(p, nbytes)
        SIM_CACHE.save(tmp_path)
    with fresh_sim_cache():
        _simulate.cache_clear()
        loaded = SIM_CACHE.load(tmp_path)
        assert loaded > 0
        runs0 = COST_STATS["engine_runs"]
        hits0 = COST_STATS["store_hits"]
        mode = choose_psum_mode(p, nbytes)
        assert COST_STATS["engine_runs"] == runs0        # zero engine runs
        assert COST_STATS["store_hits"] - hits0 == 3
    with fresh_sim_cache():
        _simulate.cache_clear()
        assert choose_psum_mode(p, nbytes) == mode       # ground truth agrees


def test_store_hit_costs_bit_identical(tmp_path):
    """Costs served from the persistent store equal engine ground truth."""
    from repro.core.noc.collective.cost import collective_cost
    kw = dict(payload_bits=4096.0)
    with fresh_sim_cache():
        _simulate.cache_clear()
        truth = collective_cost("allreduce", **kw)
        SIM_CACHE.save(tmp_path)
    with fresh_sim_cache():
        _simulate.cache_clear()
        SIM_CACHE.load(tmp_path)
        warm = collective_cost("allreduce", **kw)
    assert warm == truth
    assert warm.ledger.as_tuple() == truth.ledger.as_tuple()


# --------------------------------------------------------------------------- #
# 4. The xla/ina lowering alias + auto candidate set (satellite pin)
# --------------------------------------------------------------------------- #
def test_auto_candidate_set_pinned():
    assert AUTO_CANDIDATES == ("ina", "ina_ring", "eject_inject")
    assert "xla" not in AUTO_CANDIDATES
    # the alias auto's exclusion rests on: xla lowers exactly like ina
    assert PSUM_MODE_LOWERING["xla"] == PSUM_MODE_LOWERING["ina"] \
        == ("reduce_bcast", "ina")
    assert set(PSUM_MODE_LOWERING) == {"ina", "ina_ring", "eject_inject",
                                       "xla"}
    costs = psum_mode_costs(8, 2048)
    assert costs["xla"].latency_cycles == costs["ina"].latency_cycles
    assert costs["xla"].energy_pj == costs["ina"].energy_pj


# --------------------------------------------------------------------------- #
# 5. Tiles
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("m,k,n", [(256, 4096, 1024), (128, 14336, 4096),
                                   (1, 4096, 1000), (384, 768, 96)])
def test_choose_tiles_divide_and_fit(m, k, n):
    for dtype in ("float32", "bfloat16"):
        bm, bn, bk = choose_tiles(m, k, n, dtype)
        assert m % bm == 0 and n % bn == 0 and k % bk == 0
        import jax.numpy as jnp
        item = jnp.dtype(dtype).itemsize
        ws = (bm * bk + bk * bn) * item * 2 + bm * bn * (4 + item)
        from repro.plan.tiles import VMEM_BUDGET_BYTES
        assert ws <= VMEM_BUDGET_BYTES


def test_plan_tiles_drive_ina_matmul():
    import jax
    import jax.numpy as jnp
    from repro.kernels.ops import matmul
    plan = _decode_plan("qwen2-1.5b")
    t = plan.tiles[0]
    assert plan.tile_for(t.m, t.k, t.n, t.dtype) == t.tiles
    assert t.m % t.bm == 0 and t.n % t.bn == 0 and t.k % t.bk == 0
    # planned tiles produce the same numbers as the default blocks
    x = jax.random.normal(jax.random.PRNGKey(0), (64, 256), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), (256, 128), jnp.float32)
    tiny = ExecutionPlan(
        model="t", mesh=(("model", 1),), phase="decode", dtype="float32",
        tiles=(type(t)(m=64, k=256, n=128, dtype="float32",
                       bm=32, bn=64, bk=128),))
    got = matmul(x, w, interpret=True, plan=tiny)
    ref = matmul(x, w, interpret=True)
    assert jnp.allclose(got, ref, atol=1e-5)


# --------------------------------------------------------------------------- #
# 6. Per-config smoke: all registry configs plan the decode phase
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_all_configs_plan_decode(arch):
    plan = _decode_plan(arch)
    assert plan.model == arch and plan.phase == "decode"
    assert plan.schema == plan_schema_hash()
    assert plan.psum, f"{arch}: no auto psum sites traced"
    for d in plan.psum:
        assert d.mode in AUTO_CANDIDATES and d.count >= 1
        assert len(d.costs) == len(AUTO_CANDIDATES)
    assert plan.tiles
    s = plan.psum_summary()
    assert s["sites"] >= s["distinct"] >= 1
    assert s["latency_delta_x"] >= 1.0     # never worse than all-eject/inject
    # round-trips through JSON
    assert ExecutionPlan.from_json(plan.to_json()) == plan


def test_three_phases_distinct_keys():
    keys = set()
    for phase in ("train", "prefill", "decode"):
        plan = build_plan(ARCHS["qwen2-1.5b"], MESH, phase,
                          gemm_search=False)
        assert plan.phase == phase and plan.psum
        keys.add(plan.key)
    assert len(keys) == 3


# --------------------------------------------------------------------------- #
# 7. The --section plan sweep, its CSV/markdown emitters, and the launch
#    helper (the surfaces the CI plan-smoke job rides)
# --------------------------------------------------------------------------- #
def test_run_plan_section_cold_then_warm(tmp_path):
    import dataclasses
    from repro.experiments.sweeps import QUICK_SWEEP, _plan_csv, run_plan
    sweep = dataclasses.replace(QUICK_SWEEP, plan_dir=str(tmp_path))
    fig = run_plan(sweep)
    assert len(fig["rows"]) == len(ARCHS)
    assert not any("plan_error" in r for r in fig["rows"])
    assert set(fig["plans"]) == {r["key"] for r in fig["rows"]}
    warm = run_plan(sweep)
    assert all(r["warm"] and r["collective_engine_runs"] == 0
               for r in warm["rows"])
    lines = _plan_csv(fig)
    assert all(l.startswith("plan_") for l in lines)
    assert all("\n" not in l and l.count(",") == 2 for l in lines)


def test_plan_error_rows_stay_parseable():
    from repro.experiments.report import _plan_table
    from repro.experiments.sweeps import _plan_csv
    rows = [{"workload": "x", "phase": "decode",
             "plan_error": "Boom, with, commas\nand | pipes",
             "elapsed_us": 1.0}]
    (line,) = _plan_csv({"rows": rows})
    assert line.startswith("plan_error_x_decode,")     # the CI grep prefix
    assert "\n" not in line and line.count(",") == 2
    table = _plan_table(rows)
    assert "|" == table.splitlines()[-1][0]            # one well-formed row
    assert len(table.splitlines()) == 3                # head + rule + row


def test_plan_for_launch_warm_roundtrip(tmp_path, monkeypatch):
    from repro.configs.base import SHAPES
    from repro.plan import plan_for_launch
    # Keep the helper's window-store wiring inside the sandbox: with a
    # persist dir already set it must not retarget to results/.simcache.
    monkeypatch.setattr(SIM_CACHE, "_persist_dir", tmp_path)
    cfg = ARCHS["qwen2-1.5b"]
    shape = SHAPES["decode_32k"]
    assert plan_for_launch(cfg, MESH, shape, "ina") == (None, None)
    plan, info = plan_for_launch(cfg, MESH, shape, "auto",
                                 plan_dir=tmp_path, verbose=False,
                                 gemm_search=False)
    assert plan is not None and not info["from_store"]
    plan2, info2 = plan_for_launch(cfg, MESH, shape, "auto",
                                   plan_dir=tmp_path, verbose=False,
                                   gemm_search=False)
    assert plan2 == plan
    assert info2["from_store"] and info2["collective_sims"] == 0
    assert plan.phase == "decode"          # canonical shape -> bare phase


# --------------------------------------------------------------------------- #
# 8. Device-level equivalence: plan-driven == planless auto, and the plan
#    really drives the lowering (8 host devices, subprocess isolation)
# --------------------------------------------------------------------------- #
SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map
from repro.core.collectives import choose_psum_mode, psum_with_mode
from repro.plan import ExecutionPlan, PsumDecision

devs = jax.devices()
mesh = Mesh(np.array(devs), ("model",))
x = jax.random.normal(jax.random.PRNGKey(0), (8, 16, 32), jnp.float32)

def run(plan):
    f = shard_map(lambda xs: psum_with_mode(xs[0], "model", "auto",
                                            plan=plan)[None],
                  mesh=mesh, in_specs=P("model"), out_specs=P("model"))
    return jax.jit(f)(x)

nbytes = 16 * 32 * 4                      # the local partial inside the region
auto_mode = choose_psum_mode(8, nbytes)
plan = ExecutionPlan(model="t", mesh=(("model", 8),), phase="decode",
                     dtype="float32",
                     psum=(PsumDecision(p=8, nbytes=nbytes, mode=auto_mode,
                                        ops=("psum",), count=1),))
planless = run(None)
planned = run(plan)
assert np.array_equal(np.asarray(planless), np.asarray(planned)), \
    "plan-driven psum not bit-identical to planless auto"

# A plan forcing the Fig. 4(a) baseline must change the lowering (proof the
# plan is consulted) while staying numerically equivalent.
forced = ExecutionPlan(model="t", mesh=(("model", 8),), phase="decode",
                       dtype="float32",
                       psum=(PsumDecision(p=8, nbytes=nbytes,
                                          mode="eject_inject",
                                          ops=("psum",), count=1),))
f = shard_map(lambda xs: psum_with_mode(xs[0], "model", "auto",
                                        plan=forced)[None],
              mesh=mesh, in_specs=P("model"), out_specs=P("model"),
              check_vma=False)
txt = jax.jit(f).lower(x).as_text()
n_cp = txt.count("collective_permute") + txt.count("collective-permute")
assert n_cp >= 7, f"plan-forced eject_inject not in HLO ({n_cp} permutes)"
np.testing.assert_allclose(np.asarray(jax.jit(f)(x)), np.asarray(planless),
                           rtol=1e-4, atol=1e-4)
print("PLAN_EQUIV_OK")
"""


@pytest.mark.slow
def test_plan_driven_psum_bit_identical_on_8_devices():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    proc = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, \
        f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr}"
    assert "PLAN_EQUIV_OK" in proc.stdout
