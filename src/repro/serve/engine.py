"""ServingEngine: continuous batching + paged KV over real jax decode.

The engine is the *execution* half of the serving subsystem (the
request-level :mod:`~repro.serve.cluster` simulator is the capacity half;
they share :mod:`~repro.serve.batching` and the block-accounting rules of
:mod:`~repro.serve.kvcache`).  Per iteration it

1. admits queued requests into free cache slots (token boundary only),
2. prefills each admitted prompt — batched chunked prefill
   (:func:`~repro.parallel.steps.build_prefill_step`) when the family
   implements ``prefill``, a per-token decode loop otherwise — into a
   one-row device cache, emitting the first token; one program seats that
   row in the request's working-cache slot on the device (at TP each chip
   copies its own heads), and only the prompt's positions, cut to the next
   of :func:`prompt_lengths`, come to the host for the paged pool,
3. runs one vmapped per-slot-position decode step
   (:func:`~repro.parallel.steps.build_paged_serve_step`) over the whole
   slot batch, appends one token per active request, and pages out the
   cache column the step wrote: one gather program
   (:func:`gather_columns`) takes each slot's entry at its
   position (whole state for leaves without a sequence axis) into one
   ``[slots, n]`` array per dtype, which comes to the host in one
   transfer; each active slot's column is written into the pool at its
   position,
4. retires finished requests, releasing their blocks and slot.

Each of these is a span of the engine's :class:`~repro.exec.trace.Recorder`
(``engine.recorder``), with counters at the same boundaries:

* ``decode`` (one per iteration) holds ``decode.step`` (the step program,
  with the column gather dispatched behind it, up to the tokens on the
  host), one ``page_out`` (no rid: the wait on the columns and their
  transfer), a ``pool_write`` per active row, and ``retire``;
* ``admit`` (one per iteration; the ``admitted`` count where it admits)
  holds a ``prefill`` per request: ``prefill.chunks`` (up to the first
  token on the host), then ``page_out`` and ``pool_write`` of the prompt's
  positions, and ``seat``;
* counters ``page_out_bytes`` (the host arrays' ``nbytes``: every slot's
  column in decode, the prompt's bucketed positions in prefill),
  ``page_out_rows`` (active rows in decode, one per prefill),
  ``prefill_chunks``, ``decode_steps``, ``active_slot_steps``,
  ``admitted``, and ``psum_bytes``: what each device puts into the
  collectives of the decode step or the prefill program
  (:func:`~repro.core.collectives.collective_bytes`, counted once from the
  traced program), charged with every decode step and prefill chunk; 0 at
  TP 1.

The engine calls ``self.sched.admit``, ``self.prefill_step.fn`` and
``self.step.fn`` by attribute at call time, so wrappers put there sit
inside these spans.

Each slot computes exactly what the request would compute running alone
(the decode step is a vmap of the B=1 decode; decode attention masks
positions ``> pos``), so joining or leaving the batch can never change a
request's tokens — the property tests/test_serve.py pins against the
legacy one-batch loop.

The params are made on the device at build, each weight that every use
casts to the compute dtype already cast there
(:meth:`~repro.models.api.Model.init_served`): one copy, read by every
step with no per-call convert.  ``held_param_bytes`` records its bytes
per dtype on the fullest device.

Prefill/decode are disaggregated: each phase carries its own
``ParallelCtx`` (and, under ``--psum-mode auto``, its own
:class:`~repro.plan.ExecutionPlan` via ``plan_for_launch`` — see
``launch/serve.py``).

Media-conditioned families (encdec/vlm) need a per-request media tensor
threaded through admission; the engine rejects them — the legacy batch
loop in ``launch/serve.py`` still serves those.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Optional

import numpy as np

from repro.configs.base import ModelConfig, ShapeConfig
from repro.serve.batching import Request, RequestState, Scheduler
from repro.serve.kvcache import PagedKVCache

_NO_ENGINE_FAMILIES = ("encdec", "vlm")


@dataclasses.dataclass
class EngineReport:
    """What one :meth:`ServingEngine.run` did (deterministic content)."""

    requests: list                 # per-request dicts, finish order
    iterations: int
    prefill_chunks: int
    decode_steps: int
    checks: int                    # paged==monolithic verifications passed

    def tokens(self) -> dict:
        return {r["rid"]: r["tokens"] for r in self.requests}


def prompt_lengths(chunk: int, max_seq: int) -> tuple[int, ...]:
    """The lengths a prefill row's page-out is cut to: ``chunk`` times the
    powers of two below ``max_seq``, then ``max_seq``.  One compile each,
    and a prompt pages out less than twice its length."""
    out, n = [], chunk
    while n < max_seq:
        out.append(n)
        n *= 2
    return tuple(out) + (max_seq,)


def prompt_row(rows, length: int, leaves):
    """Positions ``[0, length)`` of a one-row cache, batch axis removed;
    whole state for leaves without a sequence axis.  ``leaves`` is the
    pool's leaf layout (:attr:`PagedKVCache.leaves`)."""
    import jax

    flat, treedef = jax.tree.flatten(rows)
    out = []
    for leaf, m in zip(flat, leaves):
        row = jax.lax.index_in_dim(leaf, 0, m.batch_axis, keepdims=False)
        if m.paged:
            row = jax.lax.slice_in_dim(row, 0, length, axis=m.batch_axis)
        out.append(row)
    return treedef.unflatten(out)


def gather_columns(working, pos, leaves):
    """The cache column each decode step wrote, for every slot.

    ``leaves`` is the pool's leaf layout (:attr:`PagedKVCache.leaves`). A
    leaf with a sequence axis (which follows the batch axis) gives each
    slot's entry at its ``pos``, one slice per slot: a vmapped gather
    would relayout the whole cache.  One without gives each slot's whole
    state.  The columns of one dtype come out as one ``[slots, n]`` array,
    so one transfer brings them to the host.
    """
    import jax
    import jax.numpy as jnp

    def column(leaf, a, slot, p):
        start, size = [0] * leaf.ndim, list(leaf.shape)
        start[a], start[a + 1] = slot, p
        size[a] = size[a + 1] = 1
        return jax.lax.dynamic_slice(leaf, start, size).reshape(-1)

    slots = pos.shape[0]
    packed = {}
    for leaf, m in zip(jax.tree.leaves(working), leaves):
        a = m.batch_axis
        if m.paged:
            col = jnp.stack([column(leaf, a, i, pos[i]) for i in range(slots)])
        else:
            col = jnp.moveaxis(leaf, a, 0).reshape(slots, -1)
        packed.setdefault(m.dtype.name, []).append(col)
    return {d: jnp.concatenate(cols, axis=1) for d, cols in packed.items()}


def held_bytes(params) -> dict:
    """Bytes of ``params`` on the device that holds the most of them, per
    dtype name."""
    import jax

    per = {}
    for leaf in jax.tree.leaves(params):
        for sh in leaf.addressable_shards:
            dev = per.setdefault(sh.device, {})
            dev[leaf.dtype.name] = dev.get(leaf.dtype.name, 0) + sh.data.nbytes
    return max(per.values(), key=lambda d: sum(d.values()))


class ServingEngine:
    def __init__(self, cfg: ModelConfig, *, slots: int = 4,
                 max_seq: Optional[int] = None, block_size: int = 16,
                 num_blocks: Optional[int] = None, prefill_chunk: int = 8,
                 psum_mode: str = "ina", prefill_plan=None, decode_plan=None,
                 batched_prefill: bool = True, policy: str = "fcfs",
                 model_parallel: int = 1, check: bool = False,
                 param_seed: int = 0) -> None:
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from repro.core.collectives import collective_bytes
        from repro.exec.timing import Stopwatch
        from repro.exec.trace import Recorder
        from repro.launch.mesh import make_host_mesh
        from repro.models.api import get_model
        from repro.parallel.steps import (build_paged_serve_step,
                                          build_prefill_step, cache_placement)
        from repro.parallel.tp import ParallelCtx

        if cfg.family in _NO_ENGINE_FAMILIES:
            raise ValueError(
                f"family {cfg.family!r} needs per-request media plumbing; "
                "use launch/serve.py --legacy-loop")
        self.cfg = cfg
        self.model = get_model(cfg)
        self.slots = slots
        self.max_seq = max_seq or cfg.max_seq
        self.prefill_chunk = prefill_chunk
        self.check = check
        if num_blocks is None:
            # enough for every slot to hold a full-length request
            num_blocks = slots * math.ceil(self.max_seq / block_size)
        self.kv = PagedKVCache(cfg, self.max_seq, block_size, num_blocks)
        self.sched = Scheduler(slots, self.kv, policy)
        #: seconds each part of construction took (tracing, compile, run)
        self.setup_s = {}
        #: spans and counters of serving (not of construction or warm-up)
        self.recorder = Recorder()

        watch = Stopwatch()
        self.mesh = make_host_mesh(model_parallel)
        self._pctx_d = ParallelCtx(mesh=self.mesh, psum_mode=psum_mode,
                                   plan=decode_plan)
        pctx_p = ParallelCtx(mesh=self.mesh, psum_mode=psum_mode,
                             plan=prefill_plan)
        self._dshape = ShapeConfig("serve", self.max_seq, slots, "decode")
        self.step = build_paged_serve_step(self.model, self.mesh,
                                           self._dshape, self._pctx_d,
                                           donate_cache=True)
        self._logits_step = None
        self.baxis = self.step.cache_batch_axes

        self.prefill_step = None
        if batched_prefill and self.model.has_prefill:
            pshape = ShapeConfig("serve", self.max_seq, 1, "prefill")
            self.prefill_step = build_prefill_step(
                self.model, self.mesh, pshape, prefill_chunk, pctx_p,
                donate_cache=True)
        else:
            # per-token fallback: B=1 decode loop doubles as prefill
            self._loop_step = jax.jit(
                lambda p, t, pos, c: self.model.decode_step(
                    p, {"tokens": t, "pos": pos}, c, pctx_p))
        # The one-row cache prefill writes, placed as the steps place it.
        rshapes, self._row_sharding = cache_placement(self.model, self.mesh,
                                                      1, self.max_seq)

        def i32(*shape):
            return jax.ShapeDtypeStruct(shape, np.int32)

        def traced_bytes(fn, *args):
            return collective_bytes(jax.make_jaxpr(fn)(*args))

        # The params as held (:meth:`~repro.models.api.Model.init_served`)
        pshapes = jax.eval_shape(self.model.init_served,
                                 jax.random.PRNGKey(param_seed))
        self._psum_bytes = {"decode": traced_bytes(
            self.step.fn, pshapes, {"tokens": i32(slots, 1), "pos": i32(slots)},
            self.step.cache_shapes)}
        if self.prefill_step is not None:
            self._psum_bytes["prefill"] = traced_bytes(
                self.prefill_step.fn, pshapes,
                {"tokens": i32(1, prefill_chunk), "pos0": i32()}, rshapes)
        else:
            self._psum_bytes["prefill"] = traced_bytes(
                self._loop_step, pshapes, i32(1, 1), i32(), rshapes)
        self.setup_s["steps"] = watch.seconds

        # Params and caches are made on the device, in the steps' layout:
        # no host copy, and no staging of the whole model on one device
        # when the params are sharded.  The leaves every use casts to the
        # compute dtype are held there, cast in the same program: the steps
        # read the values they would make, without a convert per call.
        watch = Stopwatch()
        self.params = jax.block_until_ready(jax.jit(
            self.model.init_served, out_shardings=self.step.param_sharding)(
                jax.random.PRNGKey(param_seed)))
        self.setup_s["params"] = watch.seconds
        #: bytes of params the fullest device holds, per dtype name
        self.held_param_bytes = held_bytes(self.params)
        watch = Stopwatch()
        csh = self.step.cache_sharding
        self.working = jax.jit(
            lambda: self.model.init_cache(slots, self.max_seq),
            out_shardings=csh)()
        self._pcache = jax.jit(
            lambda: self.model.init_cache(1, self.max_seq),
            out_shardings=self._row_sharding)()
        jax.block_until_ready((self.working, self._pcache))
        self.setup_s["caches"] = watch.seconds

        def seat(working, rows, slot):
            return jax.tree.map(
                lambda leaf, r, a: jax.lax.dynamic_update_slice_in_dim(
                    leaf, r, slot, a),
                working, rows, self.baxis)

        rep = NamedSharding(self.mesh, P())
        self._seat_fn = jax.jit(
            seat, in_shardings=(csh, self._row_sharding, rep),
            out_shardings=csh, donate_argnums=0)
        self._prompt_lengths = prompt_lengths(prefill_chunk, self.max_seq)
        self._prompt_row = jax.jit(
            functools.partial(prompt_row, leaves=self.kv.leaves),
            static_argnums=1)

        # fixed at ``slots`` (inactive slots gather position 0): one compile
        self._columns = jax.jit(
            functools.partial(gather_columns, leaves=self.kv.leaves),
            in_shardings=(csh, rep))
        self._cache_def = jax.tree.structure(self.baxis)
        self._jnp = jax.numpy
        self._jax = jax

    # ------------------------------------------------------------------ #
    def _extract_row(self, cache, slot: int):
        """One slot's cache row (host numpy, batch axis removed)."""
        jnp = self._jnp
        return self._jax.tree.map(
            lambda leaf, a: np.asarray(jnp.take(leaf, slot, axis=a)),
            cache, self.baxis)

    def _page_out(self, rows, plen: int, rid):
        """The one-row cache ``rows``' first ``plen`` positions, cut to the
        next of :attr:`_prompt_lengths`, on the host, as a ``page_out``
        span, counted."""
        n = next(n for n in self._prompt_lengths if n >= plen)
        rec = self.recorder
        with rec.span("page_out", rid=rid):
            row = self._jax.device_get(self._prompt_row(rows, n))
            rec.count("page_out_rows")
            rec.count("page_out_bytes",
                      sum(leaf.nbytes for leaf in self._jax.tree.leaves(row)))
        return row

    def _pool_write(self, rid, pos0: int, row, length: int) -> None:
        with self.recorder.span("pool_write", rid=rid):
            self.kv.write_range(rid, pos0, row, length)

    def _unpack_columns(self, packed) -> list:
        """:func:`gather_columns`' host arrays as one ``[slots, ...]``
        array per cache leaf, in the pool's leaf order.  Inactive slots
        hold position 0's entry, which nothing writes."""
        out, offset = [], dict.fromkeys(packed, 0)
        for m in self.kv.leaves:
            o, n = offset[m.dtype.name], math.prod(m.column_shape)
            out.append(packed[m.dtype.name][:, o:o + n].reshape(
                (self.slots,) + m.column_shape))
            offset[m.dtype.name] = o + n
        return out

    def _seat(self, st: RequestState, rows) -> None:
        """Copy the one-row device cache ``rows`` the prompt was prefilled
        into to the request's working-cache slot, in one program on the
        device (at TP each chip copies its own heads).  Positions past the
        prompt hold what an earlier, longer prompt left there: decode
        writes a position before it attends to it, so none is read."""
        with self.recorder.span("seat", rid=st.req.rid):
            self.working = self._seat_fn(self.working, rows,
                                         np.int32(st.slot))

    def prefill_logits(self, prompt):
        """Prefill ``prompt`` (token ids) chunk by chunk into the prefill
        cache; returns the last chunk's logits ``[chunk, V]`` (rows past
        the prompt's end are padding).  Needs the batched prefill step."""
        jnp = self._jnp
        prompt = np.asarray(prompt, np.int32)
        chunk = self.prefill_chunk
        logits = None
        for c0 in range(0, len(prompt), chunk):
            part = prompt[c0:c0 + chunk]
            toks = np.zeros((1, chunk), np.int32)
            toks[0, :len(part)] = part         # pad tail: causally masked
            logits, self._pcache = self.prefill_step.fn(
                self.params,
                {"tokens": jnp.asarray(toks),
                 "pos0": jnp.asarray(c0, jnp.int32)},
                self._pcache)
        return logits[0]

    def decode_logits(self, prompt):
        """Prefill ``prompt`` into slot 0, then run one decode step on the
        token the prefill picked; returns slot 0's logits ``[V]``.  The
        step is the engine's decode program (same shapes, same psum plan)
        with the final argmax left off.  Slot 0 is reseated before any
        request reads it."""
        from repro.parallel.steps import build_paged_serve_step

        jnp = self._jnp
        if self._logits_step is None:
            self._logits_step = build_paged_serve_step(
                self.model, self.mesh, self._dshape, self._pctx_d,
                donate_cache=False, logits=True)
        plen = len(prompt)
        last = self.prefill_logits(prompt)[(plen - 1) % self.prefill_chunk]
        self.working = self._seat_fn(self.working, self._pcache, np.int32(0))
        toks = np.zeros((self.slots, 1), np.int32)
        toks[0, 0] = int(jnp.argmax(last))
        pos = np.zeros((self.slots,), np.int32)
        pos[0] = plen
        logits, _ = self._logits_step.fn(
            self.params, {"tokens": jnp.asarray(toks), "pos": jnp.asarray(pos)},
            self.working)
        return logits[0]

    def warmup(self) -> dict:
        """Compile every step the engine runs; returns the seconds each
        first call took (compile + one execution).  Leaves no state that
        serving can see: slots are reseated and prefill rows rewritten
        before they are read."""
        from repro.exec.timing import Stopwatch

        jax, jnp = self._jax, self._jnp
        out = {}
        if self.prefill_step is not None:
            watch = Stopwatch()
            jax.block_until_ready(
                self.prefill_logits(np.zeros(self.prefill_chunk, np.int32)))
            out["prefill"] = watch.seconds
        watch = Stopwatch()
        _, self.working = jax.block_until_ready(self.step.fn(
            self.params, {"tokens": jnp.zeros((self.slots, 1), jnp.int32),
                          "pos": jnp.zeros((self.slots,), jnp.int32)},
            self.working))
        out["decode"] = watch.seconds
        watch = Stopwatch()
        jax.device_get(self._columns(
            self.working, jnp.zeros((self.slots,), jnp.int32)))
        out["columns"] = watch.seconds
        watch = Stopwatch()
        for n in self._prompt_lengths:
            jax.block_until_ready(self._prompt_row(self._pcache, n))
        self.working = jax.block_until_ready(
            self._seat_fn(self.working, self._pcache, np.int32(0)))
        out["page_out+seat"] = watch.seconds
        return out

    def _prefill(self, st: RequestState) -> None:
        """Run the prompt into a one-row device cache, write its positions
        into the pool, seat the row on the device, and give the request
        its first generated token."""
        jnp = self._jnp
        rec = self.recorder
        req = st.req
        prompt = np.asarray(req.prompt, np.int32)
        plen = req.prompt_len
        with rec.span("prefill.chunks", rid=req.rid):
            if self.prefill_step is not None:
                chunk = self.prefill_chunk
                logits = self.prefill_logits(prompt)
                runs = -(-plen // chunk)
                first = int(jnp.argmax(logits[(plen - 1) % chunk]))
                cache = self._pcache
            else:
                cache = self.model.init_cache(1, self.max_seq)
                for pos in range(plen):
                    lg, cache = self._loop_step(
                        self.params, jnp.asarray(prompt[None, pos:pos + 1]),
                        jnp.asarray(pos, jnp.int32), cache)
                runs = plen
                first = int(jnp.argmax(lg[0, -1]))
                cache = self._jax.device_put(cache, self._row_sharding)
            rec.count("prefill_chunks", runs)
            rec.count("psum_bytes", runs * self._psum_bytes["prefill"])
        row = self._page_out(cache, plen, req.rid)
        self._pool_write(req.rid, 0, row, plen)
        self._seat(st, cache)
        st.generated.append(first)

    # ------------------------------------------------------------------ #
    def run(self, requests: list[Request], max_iters: int = 100_000,
            ) -> EngineReport:
        jnp = self._jnp
        for req in requests:
            if req.prompt is None:
                raise ValueError(f"{req.rid}: engine requests need tokens")
            if req.total_positions > self.max_seq:
                raise ValueError(f"{req.rid}: prompt+max_new "
                                 f"{req.total_positions} > max_seq "
                                 f"{self.max_seq}")
            self.sched.submit(req)

        rec = self.recorder
        before = dict(rec.counters)
        finished, it, checks = [], 0, 0
        while self.sched.has_work:
            if it >= max_iters:
                raise RuntimeError(f"engine exceeded {max_iters} iterations")
            with rec.span("admit"):
                admitted = self.sched.admit(now=it)
                if admitted:
                    rec.count("admitted", len(admitted))
                for st in admitted:
                    with rec.span("prefill", rid=st.req.rid):
                        self._prefill(st)
                    st.first_token_time = it
            if not self.sched.active:
                if len(self.sched.queue):
                    head = self.sched.queue.peek()
                    raise RuntimeError(
                        f"request {head.rid!r} can never be admitted "
                        f"(needs {self.kv.blocks_for(head.total_positions)} "
                        f"blocks of {self.kv.allocator.num_blocks})")
                break
            checks += self._retire(it, finished)
            if not self.sched.active:
                it += 1
                continue

            with rec.span("decode"):
                toks = np.zeros((self.slots, 1), np.int32)
                pos = np.zeros((self.slots,), np.int32)
                active = list(self.sched.active.items())
                for slot, st in active:
                    toks[slot, 0] = st.generated[-1]
                    pos[slot] = st.pos - 1       # feed token at its position
                with rec.span("decode.step"):
                    pos = jnp.asarray(pos)
                    nxt, self.working = self.step.fn(
                        self.params, {"tokens": jnp.asarray(toks), "pos": pos},
                        self.working)
                    packed = self._columns(self.working, pos)
                    nxt = np.asarray(nxt)
                    rec.count("decode_steps")
                    rec.count("active_slot_steps", len(active))
                    rec.count("psum_bytes", self._psum_bytes["decode"])
                with rec.span("page_out"):
                    packed = self._jax.device_get(packed)
                    rec.count("page_out_rows", len(active))
                    rec.count("page_out_bytes",
                              sum(a.nbytes for a in packed.values()))
                    cols = self._unpack_columns(packed)
                for slot, st in active:
                    with rec.span("pool_write", rid=st.req.rid):
                        self.kv.write_column(
                            st.req.rid, st.pos - 1,
                            self._cache_def.unflatten(
                                [c[slot] for c in cols]))
                    st.generated.append(int(nxt[slot]))
                it += 1
                checks += self._retire(it, finished)
        self.kv.check()
        done = {k: v - before.get(k, 0) for k, v in rec.counters.items()}
        return EngineReport(requests=finished, iterations=it,
                            prefill_chunks=done.get("prefill_chunks", 0),
                            decode_steps=done.get("decode_steps", 0),
                            checks=checks)

    def _retire(self, it: int, finished: list) -> int:
        checks = 0
        with self.recorder.span("retire"):
            for slot in sorted(self.sched.active):
                st = self.sched.active[slot]
                if not st.done:
                    continue
                if self.check:
                    # every position actually fed is pooled bit-identically
                    covered = st.req.prompt_len + len(st.generated) - 1
                    self.kv.assert_matches(
                        st.req.rid, self._extract_row(self.working, slot),
                        min(covered, self.max_seq))
                    self.kv.check()
                    checks += 1
                self.sched.finish(slot, now=it)
                finished.append({
                    "rid": st.req.rid, "slot": slot,
                    "prompt_len": st.req.prompt_len,
                    "tokens": list(st.generated),
                    "admit_iter": int(st.admit_time),
                    "first_token_iter": int(st.first_token_time),
                    "finish_iter": it,
                })
        return checks
