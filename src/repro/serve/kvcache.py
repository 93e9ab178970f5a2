"""Paged KV cache: fixed-size blocks, a free-list allocator, block tables.

The serving engine keeps two views of decode state:

* a **monolithic working cache** on device (``model.init_cache(slots,
  max_seq)``) that the jitted decode/prefill steps read and write — jax
  wants dense rectangular arrays;
* this **paged pool** on host, the authoritative per-request store.  Leaves
  with a sequence axis (K/V, MLA latents) are chopped into fixed-size
  position blocks owned by a free-list :class:`BlockAllocator`; leaves
  without one (SSM states, conv tails) are stored whole per request.

A request's cache row round-trips bit-identically: positions copied from
the device go into blocks verbatim, and :meth:`PagedKVCache.gather_row`
reassembles exactly what the monolithic cache holds below the request's
length (zeros past it, where the working slot may hold stale entries that
decode attention masks out).  That makes "paged == monolithic" a
checkable invariant rather than a hope — see ``ServingEngine(check=True)``
and tests/test_serve.py.

Block accounting is the admission-control currency shared with the
request-level cluster simulator (:mod:`repro.serve.cluster`): an instance
admits a request only when enough free blocks exist for its worst-case
length (prompt + max_new, reserved up front — simple and safe; growing
on demand is a possible refinement noted in DESIGN.md S12).
"""
from __future__ import annotations

import dataclasses
import math

import numpy as np


class BlockAllocator:
    """Free-list allocator over ``num_blocks`` fixed-size blocks.

    Invariants (checked by :meth:`check`): every block is either free or
    owned by exactly one request (no aliasing), and ``free + live ==
    total`` (no leaks).  Allocation order is deterministic (lowest block
    id first) so simulations replay identically.
    """

    def __init__(self, num_blocks: int) -> None:
        if num_blocks <= 0:
            raise ValueError(f"num_blocks must be positive, got {num_blocks}")
        self.num_blocks = num_blocks
        self._free = list(range(num_blocks - 1, -1, -1))   # pop() -> lowest id
        self.tables: dict[object, list[int]] = {}

    @property
    def free_blocks(self) -> int:
        return len(self._free)

    @property
    def live_blocks(self) -> int:
        return sum(len(t) for t in self.tables.values())

    def can_alloc(self, n: int) -> bool:
        return len(self._free) >= n

    def alloc(self, rid, n: int) -> list[int]:
        """Reserve ``n`` blocks for ``rid`` (must not already own any)."""
        if rid in self.tables:
            raise KeyError(f"request {rid!r} already has a block table")
        if n < 0 or not self.can_alloc(n):
            raise MemoryError(
                f"need {n} blocks, {len(self._free)} free "
                f"(of {self.num_blocks})")
        blocks = [self._free.pop() for _ in range(n)]
        self.tables[rid] = blocks
        return blocks

    def extend(self, rid, n: int) -> list[int]:
        """Append ``n`` more blocks to an existing table."""
        if rid not in self.tables:      # check before popping: a failed
            raise KeyError(             # extend must not leak free blocks
                f"request {rid!r} has no block table to extend")
        if n < 0 or not self.can_alloc(n):
            raise MemoryError(
                f"need {n} more blocks, {len(self._free)} free")
        new = [self._free.pop() for _ in range(n)]
        self.tables[rid].extend(new)
        return new

    def free(self, rid) -> int:
        """Release every block ``rid`` owns; returns how many."""
        blocks = self.tables.pop(rid)
        self._free.extend(reversed(blocks))
        self._free.sort(reverse=True)    # keep pop() order deterministic
        return len(blocks)

    def check(self) -> None:
        """Raise on any no-alias / no-leak violation.

        Delegates to the static verifier (``repro.analysis``) so the CLI
        and this runtime guard agree on one invariant set; raises
        ``AssertionError`` (explicitly — not a bare ``assert``, so the
        check survives ``python -O``)."""
        from repro.analysis.verify import verify_allocator
        findings = verify_allocator(self)
        if findings:
            raise AssertionError("; ".join(str(f) for f in findings))


@dataclasses.dataclass(frozen=True)
class _LeafMeta:
    """Layout of one cache leaf, batch axis removed (a 'row')."""

    name: str              # '/'-joined tree path, for debugging
    batch_axis: int        # axis index in the *batched* leaf
    paged: bool            # has a max_seq axis right after the batch axis
    row_shape: tuple       # shape with the batch axis removed
    dtype: object

    @property
    def column_shape(self) -> tuple:
        """One position of a paged row (sequence axis removed); the whole
        row of an unpaged one."""
        if not self.paged:
            return self.row_shape
        a = self.batch_axis
        return self.row_shape[:a] + self.row_shape[a + 1:]


def _flatten_with_names(tree):
    import jax
    leaves, treedef = jax.tree_util.tree_flatten_with_path(tree)
    names = ["/".join(str(getattr(k, "key", k)) for k in path)
             for path, _ in leaves]
    return names, [leaf for _, leaf in leaves], treedef


class PagedKVCache:
    """Host-side paged store for one engine's (or one simulated
    instance's) decode state.

    ``row`` trees below always mean a single request's cache with the
    batch axis removed (what ``jnp.take(leaf, slot, axis=batch_axis)``
    yields); paged leaves keep their native axis order, with the sequence
    axis sitting where the batch axis used to be.
    """

    def __init__(self, cfg, max_seq: int, block_size: int,
                 num_blocks: int) -> None:
        import jax

        from repro.models.api import cache_batch_axes, get_model
        if max_seq % block_size:
            raise ValueError(f"block_size {block_size} must divide "
                             f"max_seq {max_seq}")
        self.cfg = cfg
        self.max_seq = max_seq
        self.block_size = block_size
        self.allocator = BlockAllocator(num_blocks)

        model = get_model(cfg)
        shapes = jax.eval_shape(lambda: model.init_cache(1, max_seq))
        baxes = cache_batch_axes(cfg)
        names, leaves, self._treedef = _flatten_with_names(shapes)
        _, axes, _ = _flatten_with_names(baxes)
        self.leaves: list[_LeafMeta] = []
        self._pools: list = []           # aligned; None for unpaged leaves
        for name, leaf, a in zip(names, leaves, axes):
            row = leaf.shape[:a] + leaf.shape[a + 1:]
            # After removing the batch axis the sequence axis (if any) is
            # at index ``a``; identified by its extent == max_seq.  Small
            # leaf dims never collide with a serving-scale max_seq.
            paged = a < len(row) and row[a] == max_seq
            self.leaves.append(_LeafMeta(name, a, paged, row,
                                         np.dtype(leaf.dtype)))
            if paged:
                self._pools.append(np.zeros(
                    (num_blocks, block_size) + self.leaves[-1].column_shape,
                    dtype=np.dtype(leaf.dtype)))
            else:
                self._pools.append(None)
        # per-request store for unpaged leaves (whole rows, latest value)
        self._state: dict[object, list] = {}
        self._length: dict[object, int] = {}

    # ------------------------------------------------------------------ #
    def blocks_for(self, positions: int) -> int:
        return math.ceil(positions / self.block_size)

    def can_admit(self, positions: int) -> bool:
        return self.allocator.can_alloc(self.blocks_for(positions))

    def admit(self, rid, positions: int) -> None:
        """Reserve blocks for ``positions`` cache slots (prompt + max
        new tokens — worst case up front)."""
        self.allocator.alloc(rid, self.blocks_for(positions))
        self._state[rid] = [None] * len(self.leaves)
        self._length[rid] = 0

    def release(self, rid) -> int:
        self._state.pop(rid)
        self._length.pop(rid)
        return self.allocator.free(rid)

    def length(self, rid) -> int:
        return self._length[rid]

    # ------------------------------------------------------------------ #
    def _seq_front(self, meta: _LeafMeta, row):
        """Move a row leaf's sequence axis to the front."""
        return np.moveaxis(row, meta.batch_axis, 0)

    def write_range(self, rid, pos0: int, row_tree, length: int) -> None:
        """Store positions ``[pos0, pos0+length)`` of ``row_tree`` (a full
        or partial row whose paged leaves carry >= pos0+length positions)
        and refresh the unpaged per-request state."""
        table = self.allocator.tables[rid]
        rows = self._treedef.flatten_up_to(row_tree)
        for i, (meta, row) in enumerate(zip(self.leaves, rows)):
            row = np.asarray(row)
            if not meta.paged:
                self._state[rid][i] = row.copy()
                continue
            sf = self._seq_front(meta, row)
            for pos in range(pos0, pos0 + length):
                blk, off = divmod(pos, self.block_size)
                self._pools[i][table[blk], off] = sf[pos]
        self._length[rid] = max(self._length[rid], pos0 + length)

    def write_column(self, rid, pos: int, column_tree) -> None:
        """Store position ``pos`` from ``column_tree``: a row's entry at
        that position on paged leaves (sequence axis removed), the whole
        per-request state on unpaged ones (as :meth:`write_range`)."""
        table = self.allocator.tables[rid]
        blk, off = divmod(pos, self.block_size)
        cols = self._treedef.flatten_up_to(column_tree)
        for i, (meta, col) in enumerate(zip(self.leaves, cols)):
            if meta.paged:
                self._pools[i][table[blk], off] = col
            else:
                self._state[rid][i] = np.array(col)
        self._length[rid] = max(self._length[rid], pos + 1)

    def gather_row(self, rid, length: int | None = None):
        """Reassemble ``rid``'s row (native layout): block contents for
        positions < length, zeros beyond (exactly the monolithic slot)."""
        table = self.allocator.tables[rid]
        length = self._length[rid] if length is None else length
        out = []
        for i, meta in enumerate(self.leaves):
            if not meta.paged:
                st = self._state[rid][i]
                out.append(np.zeros(meta.row_shape, meta.dtype)
                           if st is None else st.copy())
                continue
            sf = np.zeros((self.max_seq,) + meta.column_shape, meta.dtype)
            for pos in range(length):
                blk, off = divmod(pos, self.block_size)
                sf[pos] = self._pools[i][table[blk], off]
            out.append(np.moveaxis(sf, 0, meta.batch_axis))
        return self._treedef.unflatten(out)

    def assert_matches(self, rid, row_tree, length: int) -> None:
        """Bitwise: pooled content == ``row_tree`` on positions < length
        (the paged==monolithic invariant)."""
        rows = self._treedef.flatten_up_to(row_tree)
        mine = self._treedef.flatten_up_to(self.gather_row(rid, length))
        for meta, theirs, ours in zip(self.leaves, rows, mine):
            theirs = np.asarray(theirs)
            if meta.paged:
                sl = [slice(None)] * theirs.ndim
                sl[meta.batch_axis] = slice(0, length)
                theirs, ours = theirs[tuple(sl)], ours[tuple(sl)]
            if not np.array_equal(theirs, ours):
                raise AssertionError(
                    f"paged/monolithic mismatch on leaf {meta.name} "
                    f"for request {rid!r}")

    def check(self) -> None:
        """Allocator invariants plus the paged bookkeeping (state/length
        keys match block tables, lengths covered); see kvcache check()."""
        from repro.analysis.verify import verify_kvcache
        findings = verify_kvcache(self)
        if findings:
            raise AssertionError("; ".join(str(f) for f in findings))
