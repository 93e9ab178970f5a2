"""The Pallas kernels compile for a described TPU v5e at model widths.

Interpret mode accepts kernels that the chip's compiler (Mosaic) refuses,
so each kernel is compiled here for a v5e that is described, not attached:
``ina_matmul`` and ``flash_attention`` at qwen2-1.5b widths, ``wkv6`` at
rwkv6-7b widths.  The serving engine's column gather is compiled the same
way at qwen2-1.5b's cache, for the bytes it moves, and its decode and
prefill steps with the params as it holds them, for the weights they
convert.  Only shapes are needed; nothing runs.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process may load the TPU library, and under
pytest-xdist every worker imports every test file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_attention import flash_attention
from repro.kernels.ina_matmul import ina_matmul
from repro.kernels.wkv6 import wkv6

BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        # keep the TPU compiler's logs out of the temp directory
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        # a compile for a described chip can be written to the persistent
        # cache but never read back: keep the cache out of it
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        try:
            try:
                yield topologies.get_topology_desc(platform="tpu",
                                                   topology_name="v5e:2x2")
            except Exception as e:                          # noqa: BLE001
                pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        finally:
            jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _kernel_case(name):
    """(kernel call, argument shapes) at the widths the models use."""
    if name == "ina_matmul":      # qwen2-1.5b MLP up-projection, 2048 tokens
        return (lambda x, w: ina_matmul(x, w),
                [((2048, 1536), BF16), ((1536, 8960), BF16)])
    if name == "flash_attention":  # qwen2-1.5b: 12 heads x 4096 x 128
        return (lambda q, k, v: flash_attention(q, k, v),
                [((12, 4096, 128), BF16)] * 3)
    if name == "wkv6":            # rwkv6-7b: 64 heads x 4096 x 64
        return (lambda r, k, v, w, u: wkv6(r, k, v, w, u),
                [((64, 4096, 64), BF16)] * 4 + [((64, 64), BF16)])
    raise KeyError(name)


@pytest.mark.parametrize("name", ["ina_matmul", "flash_attention", "wkv6"])
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, shapes = _kernel_case(name)
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in shapes]
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in hlo, f"{name} did not lower to a Mosaic kernel"


def test_column_gather_reads_only_the_columns(one_chip):
    """The decode page-out's gather, at qwen2-1.5b's cache with 32 slots
    and max_seq 1024 (0.94 GB), moves the 0.92 MB of columns and not the
    whole cache: a vmapped gather compiled to 2.02 GB of traffic and
    537 MB of temp."""
    from repro.configs import ARCHS
    from repro.models.api import get_model
    from repro.serve.engine import gather_columns
    from repro.serve.kvcache import PagedKVCache

    cfg, slots, max_seq = ARCHS["qwen2-1.5b"], 32, 1024
    leaves = PagedKVCache(cfg, max_seq, 16, 1).leaves
    cache = jax.eval_shape(lambda: get_model(cfg).init_cache(slots, max_seq))
    cache = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        cache)
    pos = jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=one_chip)
    compiled = jax.jit(
        lambda w, p: gather_columns(w, p, leaves)).lower(cache, pos).compile()
    out = compiled.memory_analysis().output_size_in_bytes
    assert out == slots * 2 * 28 * 2 * 128 * 2       # K and V columns, bf16
    cost = compiled.cost_analysis()
    cost = cost[0] if isinstance(cost, list) else cost
    assert cost["bytes accessed"] < 16 * out
    assert compiled.memory_analysis().temp_size_in_bytes < 16 * out


def _whole_weight_ops(text: str, weights) -> list:
    """Converts, copies and transposes in compiled HLO ``text`` whose
    result has the shape of a whole weight, in any order of its axes."""
    import re

    shapes = {tuple(sorted(w.shape)) for w in weights}
    ops = re.findall(r"= \w+\[([0-9,]+)\]\{[^}]*\} (convert|copy|transpose)\(",
                     text)
    return [(shape, op) for shape, op in ops
            if tuple(sorted(int(n) for n in shape.split(","))) in shapes]


def test_served_steps_read_the_held_weights_as_held(one_chip):
    """qwen2-1.5b's decode step (32 slots, max_seq 1024) and prefill step
    (8-token chunks), compiled with the params as the serving engine holds
    them, convert, copy or transpose no whole weight: the per-step casts
    of the float32 weights are gone, and no relayout (of the tied head's
    table, say) takes their place.  Compiled with float32 params, the
    decode step converts every matrix."""
    import numpy as np
    from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

    from repro.configs import ARCHS
    from repro.configs.base import ShapeConfig
    from repro.models.api import get_model
    from repro.parallel.steps import (build_paged_serve_step,
                                      build_prefill_step, cache_placement)
    from repro.parallel.tp import ParallelCtx

    model = get_model(ARCHS["qwen2-1.5b"])
    mesh = Mesh(np.array([one_chip.device_set.pop()]).reshape(1, 1),
                ("data", "model"), axis_types=(AxisType.Auto,) * 2)
    pctx = ParallelCtx(mesh=mesh, psum_mode="ina")
    step = build_paged_serve_step(model, mesh,
                                  ShapeConfig("serve", 1024, 32, "decode"),
                                  pctx)
    prefill = build_prefill_step(model, mesh,
                                 ShapeConfig("serve", 1024, 1, "prefill"), 8,
                                 pctx)
    rshapes, rsharding = cache_placement(model, mesh, 1, 1024)

    def placed(shapes, shardings):
        return jax.tree.map(lambda s, sh: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=sh), shapes, shardings)

    key = jax.random.PRNGKey(0)
    held = placed(jax.eval_shape(model.init_served, key), step.param_sharding)
    f32 = placed(jax.eval_shape(model.init, key), step.param_sharding)
    weights = [w for w in jax.tree.leaves(held) if w.dtype == BF16]
    assert len(weights) == 11             # embed, 7 attention, 3 MLP
    rep = NamedSharding(mesh, P())

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=rep)

    decode = ({"tokens": i32(32, 1), "pos": i32(32)},
              placed(step.cache_shapes, step.cache_sharding))
    chunk = ({"tokens": i32(1, 8), "pos0": i32()}, placed(rshapes, rsharding))

    def ops(fn, params, args):
        text = fn.lower(params, *args).compile().as_text()
        return _whole_weight_ops(text, weights)

    assert ops(step.fn, held, decode) == []
    assert ops(prefill.fn, held, chunk) == []
    assert {op for _, op in ops(step.fn, f32, decode)} == {"convert"}
    assert len(ops(step.fn, f32, decode)) >= len(weights)
