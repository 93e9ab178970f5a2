"""Serving engine + paged KV cache (DESIGN.md S12).

Coverage map (ISSUE 6):

* the engine's tokens match the legacy one-batch loop exactly — per
  request, with fewer slots than requests (continuous batching cannot
  change what any request computes);
* per-token loop prefill and chunked batched prefill agree;
* paged==monolithic: property tests on the :class:`BlockAllocator`
  (no aliasing, no leaks under random alloc/extend/free) and on
  :class:`PagedKVCache` round-trips (interleaved writes, bit-identical
  gathers, zeros past the covered length);
* scheduler admission: head-of-line blocking, priority order, slot and
  block release on finish;
* request validation (engine needs prompts; footprint must fit).
"""
import dataclasses

import numpy as np
import pytest

from _hypothesis_compat import given, settings, strategies as st

from repro.configs import ARCHS
from repro.serve import (BlockAllocator, PagedKVCache, Request, Scheduler,
                         ServingEngine)
from repro.serve.cluster import SimKV
from repro.serve.engine import prompt_lengths

ARCH = ARCHS["qwen2-1.5b"].reduced()
PROMPT_LEN, GEN, BATCH = 6, 5, 3
MAX_SEQ = PROMPT_LEN + GEN + 1      # engine feeds one token past the prompt


def _prompts():
    import jax
    return np.asarray(jax.random.randint(
        jax.random.PRNGKey(7), (BATCH, PROMPT_LEN), 3, ARCH.vocab))


def legacy_tokens(cfg, model_parallel: int = 1):
    """The legacy launch/serve.py loop: one fixed batch, per-token
    prefill through the monolithic serve step, on ``model_parallel``
    devices.  Returns the prompts and [B, GEN+1] — the first generated
    token plus GEN greedy continuations."""
    import jax
    import jax.numpy as jnp

    from repro.configs.base import ShapeConfig
    from repro.launch.mesh import make_host_mesh
    from repro.models.api import get_model
    from repro.parallel.steps import build_serve_step
    from repro.parallel.tp import ParallelCtx

    model = get_model(cfg)
    mesh = make_host_mesh(model_parallel)
    shape = ShapeConfig("test", MAX_SEQ, BATCH, "decode")
    pctx = ParallelCtx(mesh=mesh, psum_mode="ina")
    ss = build_serve_step(model, mesh, shape, pctx, donate_cache=True)
    params = jax.device_put(model.init(jax.random.PRNGKey(0)),
                            ss.param_sharding)
    cache = jax.device_put(model.init_cache(BATCH, MAX_SEQ),
                           ss.cache_sharding)
    prompts = _prompts()
    for pos in range(PROMPT_LEN):
        nxt, cache = ss.fn(
            params, {"tokens": jnp.asarray(prompts[:, pos:pos + 1]),
                     "pos": jnp.asarray(pos, jnp.int32)}, cache)
    out = [np.asarray(nxt)]
    tok = nxt[:, None]
    for i in range(GEN):
        nxt, cache = ss.fn(
            params, {"tokens": tok,
                     "pos": jnp.asarray(PROMPT_LEN + i, jnp.int32)}, cache)
        out.append(np.asarray(nxt))
        tok = nxt[:, None]
    return prompts, np.stack(out, axis=1)


@pytest.fixture(scope="module")
def reference_tokens():
    return legacy_tokens(ARCH)


def _requests(prompts):
    return [Request(rid=f"r{i}", prompt_len=PROMPT_LEN, max_new=GEN + 1,
                    prompt=tuple(int(t) for t in prompts[i]))
            for i in range(BATCH)]


@pytest.fixture(scope="module")
def engine_report(reference_tokens):
    prompts, _ = reference_tokens
    eng = ServingEngine(ARCH, slots=2, max_seq=MAX_SEQ, block_size=4,
                        prefill_chunk=4, check=True)
    return eng.run(_requests(prompts))


# --------------------------------------------------------------------------- #
# Engine == legacy loop
# --------------------------------------------------------------------------- #
def test_engine_matches_legacy_loop(reference_tokens, engine_report):
    """Continuous batching on 2 slots (< 3 requests) reproduces the
    one-batch loop token-for-token — the serving-engine contract."""
    _, ref = reference_tokens
    tokens = engine_report.tokens()
    assert set(tokens) == {f"r{i}" for i in range(BATCH)}
    for i in range(BATCH):
        assert tokens[f"r{i}"] == ref[i].tolist(), f"r{i} diverged"


def test_engine_report_shape(engine_report):
    rep = engine_report
    assert rep.checks == BATCH               # every retire verified paged KV
    assert rep.decode_steps >= GEN           # slots < requests => extra iters
    assert {r["slot"] for r in rep.requests} <= {0, 1}
    # 2 slots run concurrently; the third request waits for a retirement
    admits = sorted(r["admit_iter"] for r in rep.requests)
    assert admits[0] == admits[1] == 0 and admits[2] > 0


def test_loop_prefill_matches_batched(reference_tokens):
    """batched_prefill=False (per-token decode loop) produces the same
    tokens as the chunked batched prefill path."""
    prompts, ref = reference_tokens
    eng = ServingEngine(ARCH, slots=1, max_seq=MAX_SEQ, block_size=4,
                        prefill_chunk=4, batched_prefill=False, check=True)
    rep = eng.run(_requests(prompts)[:1])
    assert rep.tokens()["r0"] == ref[0].tolist()


# one config per kind of cache leaf the decode page-out gathers: paged
# K/V; paged K/V beside unpaged conv/SSM state; all unpaged; paged MLA
# latents and rope keys
LEAF_KINDS = {"qwen2-1.5b": {True}, "zamba2-2.7b": {True, False},
              "rwkv6-7b": {False}, "deepseek-v2-lite-16b": {True}}


def _mixed_requests(cfg, lens):
    rng = np.random.default_rng(11)
    return [Request(rid=f"m{i}", prompt_len=p, max_new=n,
                    prompt=tuple(int(t) for t in rng.integers(3, cfg.vocab, p)))
            for i, (p, n) in enumerate(lens)]


@pytest.mark.parametrize("arch,dtype",
                         [(a, "float32") for a in sorted(LEAF_KINDS)]
                         + [("zamba2-2.7b", "bfloat16")])
def test_engine_pool_matches_working_cache(arch, dtype):
    """check=True: at every retirement the request's pooled row (prefill
    row, then one decode column a step) is bit-identical to its slot of
    the working cache, for every kind of cache leaf, and for a cache of
    two dtypes (bf16 K/V beside f32 state, as zamba2 serves)."""
    cfg = dataclasses.replace(ARCHS[arch].reduced(), dtype=dtype)
    eng = ServingEngine(cfg, slots=2, max_seq=24, block_size=4,
                        prefill_chunk=4, check=True)
    assert {m.paged for m in eng.kv.leaves} == LEAF_KINDS[arch]
    assert len({m.dtype for m in eng.kv.leaves}) == (
        2 if dtype == "bfloat16" else 1)
    reqs = _mixed_requests(cfg, [(5, 6), (9, 4), (3, 7)])
    rep = eng.run(reqs)
    assert rep.checks == len(reqs)
    assert rep.decode_steps >= max(r.max_new for r in reqs)


def test_warmup_compiles_the_column_gather():
    """After warm-up, serving adds no entry to the gather's jit cache:
    no compile falls inside a timed window."""
    eng = ServingEngine(ARCH, slots=2, max_seq=24, block_size=4,
                        prefill_chunk=4)
    eng.warmup()
    compiled = eng._columns._cache_size()
    assert compiled == 1
    eng.run(_mixed_requests(ARCH, [(5, 3), (7, 4), (2, 2)]))
    assert eng._columns._cache_size() == compiled


def test_warmup_compiles_every_prompt_length():
    """The prefill row's page-out is cut to one of a few lengths, each
    compiled by warm-up; the seat is one program."""
    eng = ServingEngine(ARCH, slots=2, max_seq=24, block_size=4,
                        prefill_chunk=4)
    assert eng._prompt_lengths == prompt_lengths(4, 24) == (4, 8, 16, 24)
    assert prompt_lengths(8, 1024) == (8, 16, 32, 64, 128, 256, 512, 1024)
    eng.warmup()
    compiled = (eng._prompt_row._cache_size(), eng._seat_fn._cache_size())
    assert compiled == (4, 1)
    eng.run(_mixed_requests(ARCH, [(5, 3), (17, 4), (2, 2), (23, 1)]))
    assert (eng._prompt_row._cache_size(),
            eng._seat_fn._cache_size()) == compiled


def test_stale_positions_past_a_prompt_are_never_read():
    """A slot seated from the prefill cache after a longer prompt holds
    that prompt's entries past its own; the short request's tokens are
    those of a fresh engine, whose caches hold zeros there."""
    long_, short = _mixed_requests(ARCH, [(19, 2), (3, 6)])

    def served(reqs):
        eng = ServingEngine(ARCH, slots=1, max_seq=24, block_size=4,
                            prefill_chunk=4, check=True)
        return eng.run(reqs).tokens()

    assert served([long_, short])[short.rid] == served([short])[short.rid]


# --------------------------------------------------------------------------- #
# Params held at the compute dtype
# --------------------------------------------------------------------------- #
# one registry entry per family the engine serves
SERVED_FAMILIES = {"dense": "qwen2-1.5b", "moe": "llama4-scout-17b-16e",
                   "mla_moe": "deepseek-v2-lite-16b", "ssm": "rwkv6-7b",
                   "hybrid": "zamba2-2.7b"}


def _bf16_engine(arch, **kw):
    cfg = dataclasses.replace(ARCHS[arch].reduced(), dtype="bfloat16")
    return ServingEngine(cfg, slots=2, max_seq=24, block_size=4,
                         prefill_chunk=4, **kw)


def _float32_params(eng):
    """The engine's params as :meth:`Model.init` makes them: all float32,
    in the steps' layout."""
    import jax
    return jax.jit(eng.model.init, out_shardings=eng.step.param_sharding)(
        jax.random.PRNGKey(0))


@pytest.mark.parametrize("family", sorted(SERVED_FAMILIES))
def test_held_params_serve_what_float32_params_serve(family):
    """At a bfloat16 compute dtype, the params the engine holds (the
    leaves every use casts, cast once at build) give bit for bit the
    per-slot decode logits, prefill logits and tokens of float32 params
    that each step casts."""
    import jax
    import jax.numpy as jnp

    from repro.parallel.steps import build_paged_serve_step

    eng = _bf16_engine(SERVED_FAMILIES[family])
    cfg, held, f32 = eng.cfg, eng.params, _float32_params(eng)
    assert {leaf.dtype for leaf in jax.tree.leaves(f32)} == {np.dtype("float32")}
    assert jnp.dtype("bfloat16") in {leaf.dtype
                                     for leaf in jax.tree.leaves(held)}

    lens = [(5, 6), (9, 4), (3, 7)]
    tokens = {}
    for name, params in (("held", held), ("f32", f32)):
        eng.params = params
        tokens[name] = eng.run(_mixed_requests(cfg, lens)).tokens()
    assert tokens["held"] == tokens["f32"]

    step = build_paged_serve_step(eng.model, eng.mesh, eng._dshape,
                                  eng._pctx_d, donate_cache=False, logits=True)
    batch = {"tokens": jnp.asarray([[5], [9]], jnp.int32),
             "pos": jnp.asarray([7, 12], jnp.int32)}
    decode = [np.asarray(step.fn(p, batch, eng.working)[0])
              for p in (held, f32)]
    np.testing.assert_array_equal(*decode)
    if eng.prefill_step is not None:
        prefill = []
        for p in (held, f32):
            eng.params = p
            prefill.append(np.asarray(eng.prefill_logits(np.arange(3, 10))))
        np.testing.assert_array_equal(*prefill)


def _weight_converts(fn, held, params, *args) -> list:
    """Shapes that a convert in ``fn``'s program, lowered with ``params``,
    takes from a float32 operand shaped like a bfloat16 matrix of the
    held params ``held``: whole, one layer of it, or transposed."""
    import re

    import jax
    import jax.numpy as jnp

    shapes = set()
    for leaf in jax.tree.leaves(held):
        if leaf.dtype == jnp.bfloat16 and leaf.ndim >= 2:
            for s in (leaf.shape, leaf.shape[1:]):
                if len(s) >= 2:
                    shapes |= {tuple(s), tuple(s[::-1])}
    text = fn.lower(params, *args).as_text()
    found = re.findall(r"stablehlo\.convert[^\n]*\(tensor<([0-9x]+)xf32>\) "
                       r"-> tensor<[0-9x]+xbf16>", text)
    return [s for s in found
            if tuple(int(n) for n in s.split("x")) in shapes]


def test_held_programs_cast_no_weight():
    """The tiny dense config's decode and prefill programs, lowered with
    the held params, convert no float32 weight to bfloat16; with float32
    params they convert at least five."""
    import jax.numpy as jnp

    eng = _bf16_engine("qwen2-1.5b")
    decode = ({"tokens": jnp.zeros((2, 1), jnp.int32),
               "pos": jnp.zeros((2,), jnp.int32)}, eng.working)
    prefill = ({"tokens": jnp.zeros((1, 4), jnp.int32),
                "pos0": jnp.asarray(0, jnp.int32)}, eng._pcache)
    f32 = _float32_params(eng)
    for fn, args in ((eng.step.fn, decode), (eng.prefill_step.fn, prefill)):
        assert _weight_converts(fn, eng.params, eng.params, *args) == []
        assert len(_weight_converts(fn, eng.params, f32, *args)) >= 5


def test_held_param_bytes_split_by_dtype():
    """``held_param_bytes`` is the bytes the harness counts per chip,
    split by dtype: the norm scales in float32, the rest in bfloat16."""
    import jax

    from bench.run import param_bytes_per_chip

    eng = _bf16_engine("qwen2-1.5b")
    held, params = eng.held_param_bytes, eng.params
    assert sum(held.values()) == param_bytes_per_chip(params)
    norms = [params["ln_f"], params["layers"]["ln1"], params["layers"]["ln2"]]
    assert held["float32"] == sum(n.nbytes for n in norms)
    assert held["bfloat16"] == sum(
        leaf.nbytes for leaf in jax.tree.leaves(params)) - held["float32"]


# --------------------------------------------------------------------------- #
# The engine at TP 4, on four virtual devices (a child process, so the
# device count is set before JAX starts)
# --------------------------------------------------------------------------- #
TP4_SCRIPT = """
import dataclasses, json, math, sys
sys.path.insert(0, {tests!r})
import test_serve as t
from repro.configs import ARCHS
from repro.serve import ServingEngine

# phi3-mini's registry entry shrunk, MHA as phi3 is: 4 heads, one a device
cfg = dataclasses.replace(ARCHS["phi3-mini-3.8b"].reduced(), n_kv_heads=4)
prompts, ref = t.legacy_tokens(cfg, model_parallel=4)
eng = ServingEngine(cfg, slots=2, max_seq=t.MAX_SEQ, block_size=4,
                    prefill_chunk=4, check=True, model_parallel=4)
rep = eng.run(t._requests(prompts))
spans = eng.recorder.spans()
by_id = {{s.id: s for s in spans}}
column = sum(math.prod(m.row_shape) // eng.max_seq * m.dtype.itemsize
             for m in eng.kv.leaves if m.paged)
print("RESULT", json.dumps({{
    "tokens": rep.tokens(), "ref": ref.tolist(), "checks": rep.checks,
    "prefill_page_out": [s.counts["page_out_bytes"] for s in spans
                         if s.name == "page_out"
                         and by_id[s.parent].name == "prefill"],
    "column_bytes": column, "psum_bytes": eng._psum_bytes,
    "charged": eng.recorder.counters["psum_bytes"],
    "decode_steps": rep.decode_steps, "prefill_chunks": rep.prefill_chunks,
    "sizes": [cfg.n_layers, cfg.d_model, eng.slots, eng.prefill_chunk],
    "shards": [len(leaf.sharding.device_set)
               for leaf in eng.working.values()]}}))
"""


@pytest.fixture(scope="module")
def tp4(tmp_path_factory):
    import json
    import os
    import subprocess
    import sys
    from pathlib import Path

    tmp = tmp_path_factory.mktemp("tp4")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               REPRO_PLAN_DIR=str(tmp / "plans"),
               REPRO_SIMCACHE_DIR=str(tmp / "simcache"))
    code = TP4_SCRIPT.format(tests=str(Path(__file__).resolve().parent))
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=600)
    line = [ln for ln in p.stdout.splitlines() if ln.startswith("RESULT")]
    assert line, p.stderr[-3000:]
    return json.loads(line[0][len("RESULT"):])


def test_tp4_engine_matches_legacy_loop(tp4):
    assert tp4["shards"] == [4, 4]               # K and V split by heads
    for i in range(BATCH):
        assert tp4["tokens"][f"r{i}"] == tp4["ref"][i], f"r{i} diverged"


def test_tp4_pool_matches_working_cache(tp4):
    """check=True: the row seated on the device from the prefill cache,
    and the columns decode wrote, equal the pool at every retirement."""
    assert tp4["checks"] == BATCH


def test_tp4_admission_pages_out_the_bucketed_prompt(tp4):
    cut = next(n for n in prompt_lengths(4, MAX_SEQ) if n >= PROMPT_LEN)
    assert cut == 8
    assert tp4["prefill_page_out"] == [cut * tp4["column_bytes"]] * BATCH


def test_tp4_psum_bytes_are_the_row_parallel_partials(tp4):
    """Two row-parallel psums a layer (attention out, MLP down), each of
    a ``[tokens, d_model]`` float32 partial: the decode step's tokens are
    its slots, a prefill chunk's its chunk."""
    layers, d, slots, chunk = tp4["sizes"]
    assert tp4["psum_bytes"] == {"decode": 2 * layers * slots * d * 4,
                                 "prefill": 2 * layers * chunk * d * 4}
    assert tp4["charged"] == \
        tp4["decode_steps"] * tp4["psum_bytes"]["decode"] + \
        tp4["prefill_chunks"] * tp4["psum_bytes"]["prefill"]


def test_engine_rejects_promptless_and_oversized():
    eng = ServingEngine(ARCH, slots=1, max_seq=MAX_SEQ, block_size=4,
                        prefill_chunk=4)
    with pytest.raises(ValueError, match="need tokens"):
        eng.run([Request(rid="x", prompt_len=4, max_new=2)])
    with pytest.raises(ValueError, match="max_seq"):
        eng.run([Request(rid="y", prompt_len=MAX_SEQ, max_new=2,
                         prompt=tuple(range(3, 3 + MAX_SEQ)))])


# --------------------------------------------------------------------------- #
# BlockAllocator properties
# --------------------------------------------------------------------------- #
@given(st.lists(st.tuples(st.sampled_from(["alloc", "extend", "free"]),
                          st.integers(0, 5), st.integers(0, 4)),
                max_size=60))
@settings(max_examples=60, deadline=None)
def test_allocator_never_aliases_or_leaks(ops):
    """Random alloc/extend/free interleavings: every block is free or
    owned by exactly one request, and free + live == total, always."""
    alloc = BlockAllocator(12)
    owned: dict[int, int] = {}
    for op, rid, n in ops:
        try:
            if op == "alloc":
                blocks = alloc.alloc(rid, n)
                assert len(blocks) == n
                owned[rid] = n
            elif op == "extend":
                alloc.extend(rid, n)
                owned[rid] += n
            else:
                freed = alloc.free(rid)
                assert freed == owned.pop(rid)
        except (KeyError, MemoryError):
            pass                              # rejected ops must not mutate
        alloc.check()
        assert alloc.live_blocks == sum(owned.values())
        assert alloc.free_blocks == 12 - alloc.live_blocks
    for rid in list(owned):
        alloc.free(rid)
    assert alloc.free_blocks == 12


def test_allocator_deterministic_order():
    a = BlockAllocator(6)
    assert a.alloc("a", 2) == [0, 1]
    assert a.alloc("b", 2) == [2, 3]
    a.free("a")
    assert a.alloc("c", 3) == [0, 1, 4]       # reuses lowest ids first


# --------------------------------------------------------------------------- #
# PagedKVCache round-trips
# --------------------------------------------------------------------------- #
@pytest.fixture(scope="module")
def kv():
    return PagedKVCache(ARCH, max_seq=16, block_size=4, num_blocks=12)


def _random_row(kv, rng):
    leaves = []
    for meta in kv.leaves:
        if np.issubdtype(meta.dtype, np.integer):
            leaves.append(rng.integers(0, 7, size=meta.row_shape)
                          .astype(meta.dtype))
        else:
            leaves.append(rng.standard_normal(size=meta.row_shape)
                          .astype(meta.dtype))
    return kv._treedef.unflatten(leaves)


@given(st.integers(0, 2 ** 16), st.integers(1, 16), st.integers(1, 16))
@settings(max_examples=25, deadline=None)
def test_paged_roundtrip_bit_identical(kv, seed, len_a, len_b):
    """Two requests' rows written interleaved, chunk by chunk: each
    gathers back bit-identical to its source, zeros past its length,
    and releasing one leaves the other untouched."""
    rng = np.random.default_rng(seed)
    kv.admit("a", len_a)
    kv.admit("b", len_b)
    try:
        row_a, row_b = _random_row(kv, rng), _random_row(kv, rng)
        pos_a = pos_b = 0
        while pos_a < len_a or pos_b < len_b:
            if pos_a < len_a:
                n = min(int(rng.integers(1, 5)), len_a - pos_a)
                kv.write_range("a", pos_a, row_a, n)
                pos_a += n
            if pos_b < len_b:
                n = min(int(rng.integers(1, 5)), len_b - pos_b)
                kv.write_range("b", pos_b, row_b, n)
                pos_b += n
        kv.assert_matches("a", row_a, len_a)
        kv.assert_matches("b", row_b, len_b)
        kv.check()
        # zeros past the covered length on every paged leaf
        got = kv._treedef.flatten_up_to(kv.gather_row("a", len_a))
        for meta, leaf in zip(kv.leaves, got):
            if not meta.paged:
                continue
            tail = np.moveaxis(leaf, meta.batch_axis, 0)[len_a:]
            assert not np.any(tail.astype(np.float32))
        kv.release("b")
        kv.check()
        kv.assert_matches("a", row_a, len_a)
    finally:
        for rid in list(kv.allocator.tables):
            kv.release(rid)
    assert kv.allocator.free_blocks == 12


def test_column_writes_equal_one_range_write():
    """Writing a row's columns position by position (as decode does)
    gives the same pool as one write_range of the whole row, on paged
    and unpaged leaves alike."""
    cfg = ARCHS["zamba2-2.7b"].reduced()
    by_range, by_column = (PagedKVCache(cfg, max_seq=16, block_size=4,
                                        num_blocks=8) for _ in range(2))
    assert {m.paged for m in by_range.leaves} == {True, False}
    rng = np.random.default_rng(4)
    by_range.admit("x", 2)          # another request's blocks come first
    by_column.admit("x", 2)
    row = _random_row(by_range, rng)
    length = 11
    by_range.admit("a", length)
    by_column.admit("a", length)
    by_range.write_range("a", 0, row, length)
    rows = by_column._treedef.flatten_up_to(row)
    for pos in range(length):
        col = [np.take(leaf, pos, axis=m.batch_axis) if m.paged else leaf
               for m, leaf in zip(by_column.leaves, rows)]
        by_column.write_column("a", pos, by_column._treedef.unflatten(col))
    assert by_column.length("a") == by_range.length("a") == length
    for mine, theirs in zip(by_column._pools, by_range._pools):
        if mine is not None:
            np.testing.assert_array_equal(mine, theirs)
    for mine, theirs in zip(by_column._state["a"], by_range._state["a"]):
        np.testing.assert_array_equal(mine, theirs)
    by_column.assert_matches("a", row, length)
    by_column.check()


def test_kvcache_block_size_must_divide():
    with pytest.raises(ValueError, match="divide"):
        PagedKVCache(ARCH, max_seq=10, block_size=4, num_blocks=4)


def test_kvcache_admission_accounting(kv):
    assert kv.blocks_for(1) == 1 and kv.blocks_for(5) == 2
    kv.admit("x", 16)                         # 4 blocks
    kv.admit("y", 16)
    kv.admit("z", 16)
    assert not kv.can_admit(1)                # 12 blocks all reserved
    assert kv.release("y") == 4
    assert kv.can_admit(16)
    kv.release("x")
    kv.release("z")


# --------------------------------------------------------------------------- #
# Scheduler admission
# --------------------------------------------------------------------------- #
def test_scheduler_head_of_line_blocking():
    """A too-big head request must not be overtaken by smaller ones."""
    sched = Scheduler(4, SimKV(block_size=4, num_blocks=4))
    sched.submit(Request(rid="big", prompt_len=12, max_new=4, arrival=0.0))
    sched.submit(Request(rid="small", prompt_len=2, max_new=2, arrival=1.0))
    assert [st.req.rid for st in sched.admit(now=2.0)] == ["big"]
    assert sched.admit(now=2.0) == []         # small waits for blocks
    sched.finish(0, now=3.0)
    assert [st.req.rid for st in sched.admit(now=3.0)] == ["small"]


def test_scheduler_priority_policy():
    sched = Scheduler(1, SimKV(block_size=4, num_blocks=64),
                      policy="priority")
    sched.submit(Request(rid="late-hi", prompt_len=2, max_new=1,
                         arrival=0.0, priority=0))
    sched.submit(Request(rid="early-lo", prompt_len=2, max_new=1,
                         arrival=0.0, priority=5))
    assert sched.admit(now=0.0)[0].req.rid == "late-hi"


def test_scheduler_releases_slot_and_blocks():
    kv = SimKV(block_size=4, num_blocks=8)
    sched = Scheduler(2, kv)
    sched.submit(Request(rid="a", prompt_len=8, max_new=8))   # 4 blocks
    sched.submit(Request(rid="b", prompt_len=8, max_new=8))
    assert len(sched.admit()) == 2 and kv.allocator.free_blocks == 0
    st = sched.finish(0, now=1.0)
    assert st.req.rid == "a" and st.finish_time == 1.0
    assert kv.allocator.free_blocks == 4
    assert sched.n_active == 1 and sched.has_work


def test_request_validation():
    with pytest.raises(ValueError, match="positive"):
        Request(rid="r", prompt_len=0, max_new=1)
    with pytest.raises(ValueError, match="mismatch"):
        Request(rid="r", prompt_len=3, max_new=1, prompt=(1, 2))
    assert Request(rid="r", prompt_len=3, max_new=2).total_positions == 5
