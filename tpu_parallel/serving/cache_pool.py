"""Slot-based KV-cache pool for continuous batching.

The pool is ONE cache pytree in the exact per-layer layout the model's
:class:`~tpu_parallel.models.layers.Attention` creates (stacked
``[n_layers, n_slots, seq_len, kv_heads, head_dim]`` payloads under
``nn.scan``, per-slot position tables, int8 scales under
``kv_cache_dtype="int8"``; for a recurrent layer its ``ssm_state``
``[n_slots, heads, head_dim, d_state]`` and ``conv_state``, of one size
whatever the context length) — the batch axis IS the slot axis.  Requests
own slots for their lifetime: admission prefills the request alone
(batch 1) and row-inserts the fresh cache into the freed slot; retirement
just returns the slot index to the free list (the row is dead weight until
the next insert overwrites all of it, including the position table whose
``-1`` entries keep unwritten slots out of every attention read).

Memory model — two layouts share this module:

- **Fixed-slot** (:class:`CachePool`, ``kv_block_tokens == 0``): pool
  bytes are fixed at construction — ``n_slots x seq_len`` K/V entries per
  layer regardless of how many requests are in flight.  Slots are
  whole-sequence rows: the simplest correct layout, but a 9-token request
  pays for the full context window and every prefix-cache hit copies
  O(prefix_len) rows.
- **Block-paged** (:class:`PagedCachePool` + :class:`BlockAllocator`,
  ``kv_block_tokens > 0``): K/V live in a flat pool of fixed-size blocks;
  each slot owns a block-table row mapping logical block indices to
  physical blocks, filled on demand as the sequence grows.  Slot count
  decouples from ``seq_len`` (short requests hold only the blocks they
  use), prefix-cache hits are O(1) refcounted table writes instead of row
  copies, and the first write into a shared block copy-on-writes that ONE
  block (docs/10_serving_engine.md has the full memory-model story).

``kv_cache_dtype="int8"`` halves the payload exactly as on the static
path under either layout.

Donation invariant: every WRITE op on the pool (insert / scatter / clear
/ copy_prefix) and every engine decode tick — per-step, verify, and the
fused multi-step tick — DONATES the pool operand, so exactly ONE pool's
worth of device memory is ever live and XLA recycles it in place.  The
flip side is an ownership contract: ``pool.cache`` is the only valid
handle, and a reference to the tree held across any tick or write op
points at deleted buffers (reads raise; pinned in
``tests/test_serving.py::test_fused_tick_donation_invalidates_old_buffers``).
Read-side ops (``extract``, ``stack_prefix``) copy and may be held.
"""

from __future__ import annotations

import functools
import heapq
import zlib
from typing import List, Optional

import jax
import jax.numpy as jnp
from jax import lax

from tpu_parallel.models.generate import beam_cache_batch_axis


class KVIntegrityError(ValueError):
    """Exported KV block bytes failed their checksum — the payload was
    corrupted somewhere between ``export_blocks`` and the import (host
    RAM rot, a truncated spill, a mangled wire transfer).  Serving such
    blocks would be silently wrong attention for EVERY request sharing
    the prefix; callers catch this, count a typed refusal, and fall
    back to the bitwise recompute path."""


def block_checksums(rows, count: int):
    """Per-block CRC32 over exported host rows (the
    :meth:`PagedCachePool.export_blocks` layout: one array per
    block-axis leaf, block dim at axis 0).  Block ``b``'s checksum
    chains every leaf's row bytes in flatten order, so any flipped bit
    in any payload, position table or int8 scale changes it.  Computed
    at EXPORT time (spill / migration capture) and verified at IMPORT
    time (:meth:`PagedCachePool.import_stored`) — the
    verify-or-recompute rule's cheap half."""
    import numpy as np

    out = []
    for b in range(count):
        crc = 0
        for leaf in rows:
            crc = zlib.crc32(
                np.ascontiguousarray(leaf[b]).tobytes(), crc
            )
        out.append(crc)
    return tuple(out)


def _leaf_name(path) -> str:
    return path[-1].key if hasattr(path[-1], "key") else str(path[-1])


def insert_rows(pool_cache, fresh_cache, slot):
    """Write a batch-1 prefill cache into row ``slot`` of the pool.

    Pure tree op (traceable; the engine jits it with ``slot`` traced so one
    compile serves every slot).  Batch axes are located by the shared
    name registry (:func:`~tpu_parallel.models.generate.beam_cache_batch_axis`
    — K/V payloads and int8 scales at ndim-4, position tables at ndim-2);
    scalar counters keep the POOL's value: the engine drives decode with
    explicit per-slot positions and ``write_index``, so the shared scalar
    ``cache_index`` is never read on this path.
    """

    def ins(path, pool_leaf, fresh_leaf):
        ax = beam_cache_batch_axis(path, pool_leaf)
        if ax is None:
            return pool_leaf
        return lax.dynamic_update_slice_in_dim(
            pool_leaf, fresh_leaf.astype(pool_leaf.dtype), slot, axis=ax
        )

    return jax.tree_util.tree_map_with_path(ins, pool_cache, fresh_cache)


def scatter_rows(pool_cache, fresh_cache, slots):
    """Write the rows of a batch-N prefill cache into pool rows ``slots``
    [N] — the batched-prefill generalization of :func:`insert_rows` (one
    scatter per leaf instead of N dynamic-slice programs).

    Traceable with ``slots`` traced.  Rows whose slot is OUT OF RANGE
    (the engine passes ``n_slots`` for a padded prefill batch's dummy
    rows) are DROPPED by JAX's default scatter semantics — the pool leaf
    keeps its value, which is exactly the discard the padding wants.
    """

    def ins(path, pool_leaf, fresh_leaf):
        ax = beam_cache_batch_axis(path, pool_leaf)
        if ax is None:
            return pool_leaf
        idx = (slice(None),) * ax + (slots,)
        return pool_leaf.at[idx].set(fresh_leaf.astype(pool_leaf.dtype))

    return jax.tree_util.tree_map_with_path(ins, pool_cache, fresh_cache)


def extract_rows(pool_cache, slot, n: int = 1):
    """Slice ``n`` consecutive rows starting at ``slot`` out of the pool —
    a batch-``n`` cache tree in the model's own layout (scalar counters
    pass through unchanged; the engine never reads them).  The chunked
    prefill's read side: extract the slot's row, extend it one chunk
    (:func:`~tpu_parallel.models.generate.prefill_extend_step`), scatter
    it back."""

    def ext(path, leaf):
        ax = beam_cache_batch_axis(path, leaf)
        if ax is None:
            return leaf
        return lax.dynamic_slice_in_dim(leaf, slot, n, axis=ax)

    return jax.tree_util.tree_map_with_path(ext, pool_cache)


# a recurrent layer's leaves (models/ssm.py): a summary of the row's past
# with no position table over it, so a stale one is masked by nothing
STATE_LEAVES = ("ssm_state", "conv_state")


def clear_rows(pool_cache, slot):
    """Invalidate pool row ``slot``: every position-table entry to -1, so
    no query ever attends the row's (stale) K/V again, and a recurrent
    layer's state leaves to ZERO (a chunked prompt starts from ``S = 0``;
    nothing masks a stale state).  The K/V payloads
    are left untouched — dead bytes until overwritten.  Used before a
    chunked prefill starts writing a freed slot incrementally (a whole-row
    insert is not available until the LAST chunk; the stale occupant must
    not leak into the chunks' attention reads meanwhile)."""

    def clr(path, leaf):
        name = _leaf_name(path)
        if not name.startswith(("cached_pos", "cross_mask") + STATE_LEAVES):
            return leaf
        ax = beam_cache_batch_axis(path, leaf)
        if ax is None:
            return leaf
        row_shape = leaf.shape[:ax] + (1,) + leaf.shape[ax + 1:]
        fill = 0 if name.startswith(STATE_LEAVES) else -1
        return lax.dynamic_update_slice_in_dim(
            leaf, jnp.full(row_shape, fill, leaf.dtype), slot, axis=ax
        )

    return jax.tree_util.tree_map_with_path(clr, pool_cache)


def copy_prefix_rows(pool_cache, prefix_cache, slot, length):
    """Copy a stored prefix row into pool row ``slot``, trimming validity
    to the first ``length`` positions: K/V payloads copy whole (slots
    beyond ``length`` are dead bytes), the position table copies masked to
    -1 beyond ``length`` so ONLY the prefix is attendable.  The whole-row
    copy doubles as the slot's invalidation of its previous occupant.

    Exactness: cached K/V is a pure function of (token, position, params)
    — including the int8 path's per-(position, kv-head) quantization — so
    a copied prefix row is bit-identical to recomputing the prefill.
    """

    def ins(path, pool_leaf, fresh_leaf):
        ax = beam_cache_batch_axis(path, pool_leaf)
        if ax is None:
            return pool_leaf
        fresh_leaf = fresh_leaf.astype(pool_leaf.dtype)
        if _leaf_name(path).startswith(("cached_pos", "cross_mask")):
            valid = jnp.arange(fresh_leaf.shape[-1]) < length
            fresh_leaf = jnp.where(valid, fresh_leaf, -1)
        return lax.dynamic_update_slice_in_dim(
            pool_leaf, fresh_leaf, slot, axis=ax
        )

    return jax.tree_util.tree_map_with_path(ins, pool_cache, prefix_cache)


def _pool_cache_shapes(model, params, n_slots: int):
    """abstract shapes of the model's decode cache at batch ``n_slots``,
    via ``jax.eval_shape`` — no forward pass runs."""

    def probe():
        tok = jnp.zeros((n_slots, 1), jnp.int32)
        pos = jnp.zeros((n_slots, 1), jnp.int32)
        kwargs = {}
        bt = getattr(model.config, "kv_block_tokens", 0)
        if bt > 0:
            # paged models refuse decode without a table; the probe's dummy
            # one never runs (eval_shape), it only shapes the cache tree
            kwargs["block_table"] = jnp.zeros(
                (n_slots, model.config.seq_len // bt), jnp.int32
            )
            kwargs["write_index"] = jnp.zeros((n_slots,), jnp.int32)
        _, variables = model.apply(
            {"params": params},
            tok,
            positions=pos,
            train=False,
            decode=True,
            hidden_only=True,
            mutable=["cache"],
            **kwargs,
        )
        return variables["cache"]

    return jax.eval_shape(probe)


def empty_pool(model, params, n_slots: int):
    """Allocate the pool cache: the model's own decode-cache structure at
    batch ``n_slots``, zero-filled, with every position-table entry at -1
    (no slot attends until a request's prefill row is inserted).

    Only the cache STRUCTURE comes from the model, so any config (GQA
    widths, int8 scales, unrolled vs scanned stacks) produces its
    matching pool.
    """

    def alloc(path, leaf):
        if _leaf_name(path).startswith("cached_pos"):
            return jnp.full(leaf.shape, -1, leaf.dtype)
        return jnp.zeros(leaf.shape, leaf.dtype)

    return jax.tree_util.tree_map_with_path(
        alloc, _pool_cache_shapes(model, params, n_slots)
    )


def stack_prefix_rows(rows, length):
    """Stack batch-1 prefix rows into one batch-N cache tree, position
    tables trimmed to the first ``length`` entries (-1 beyond) — the
    BATCHED prefix-hit landing: N same-length hits extend as one padded
    model call instead of N single-row round-trips.

    ``rows`` is a tuple of stored prefix rows (NOT donated — they stay
    live in the prefix cache; the concatenate copies).  Scalar leaves take
    the first row's value (unread).
    """

    def stk(path, *leaves):
        ax = beam_cache_batch_axis(path, leaves[0])
        if ax is None:
            return leaves[0]
        out = jnp.concatenate(leaves, axis=ax)
        if _leaf_name(path).startswith(("cached_pos", "cross_mask")):
            out = jnp.where(jnp.arange(out.shape[-1]) < length, out, -1)
        return out

    return jax.tree_util.tree_map_with_path(stk, *rows)


class CachePool:
    """Host-side slot bookkeeping + the device cache pytree.

    ``acquire()``/``release()`` manage the free list; ``insert()`` commits
    a prefilled request into its slot.  The device tree lives at
    ``self.cache`` and is REPLACED (functionally) by every insert and by
    every engine decode tick.
    """

    def __init__(self, model, params, n_slots: int):
        if n_slots < 1:
            raise ValueError(f"n_slots={n_slots} < 1")
        self.n_slots = n_slots
        self.cache = empty_pool(model, params, n_slots)
        self._free: List[int] = list(range(n_slots))
        (self._insert, self._scatter, self._extract, self._clear,
         self._copy_prefix, self.stack_prefix) = default_row_fns()

    @property
    def n_free(self) -> int:
        return len(self._free)

    @property
    def occupancy(self) -> float:
        return 1.0 - len(self._free) / self.n_slots

    def acquire(self) -> Optional[int]:
        """Claim a free slot index (lowest-first, deterministic), or None."""
        if not self._free:
            return None
        return self._free.pop(0)

    def release(self, slot: int) -> None:
        if slot in self._free or not (0 <= slot < self.n_slots):
            raise ValueError(f"bad release of slot {slot}")
        self._free.append(slot)
        self._free.sort()

    def insert(self, fresh_cache, slot: int) -> None:
        """Row-insert a batch-1 prefill cache into ``slot``."""
        self.cache = self._insert(self.cache, fresh_cache, jnp.int32(slot))

    def scatter(self, fresh_cache, slots) -> None:
        """Scatter a batch-N prefill cache's rows into ``slots`` [N]; pass
        ``n_slots`` for dummy rows (dropped — see :func:`scatter_rows`)."""
        self.cache = self._scatter(
            self.cache, fresh_cache, jnp.asarray(slots, jnp.int32)
        )

    def extract(self, slot: int):
        """Pull one slot's row out as a batch-1 cache tree (chunked-prefill
        read side; also the prefix cache's capture path)."""
        return self._extract(self.cache, jnp.int32(slot))

    def clear(self, slot: int) -> None:
        """Invalidate a slot's position table before incremental writes."""
        self.cache = self._clear(self.cache, jnp.int32(slot))

    def copy_prefix(self, prefix_cache, slot: int, length: int) -> None:
        """Land a stored prefix row (first ``length`` positions valid)
        into ``slot`` — the prefix-reuse admission skips recomputing those
        tokens entirely."""
        self.cache = self._copy_prefix(
            self.cache, prefix_cache, jnp.int32(slot), jnp.int32(length)
        )

    def assert_slot_aligned(self, slot: int) -> None:
        """Assert the ALIGNED-layout invariant speculative decoding's
        no-rollback story rests on: every valid entry of ``slot``'s
        position table stores exactly its own column index
        (``pos[col] in {-1, col}``).

        Why this is THE invariant: the engine always writes position p at
        column p (prefill from 0, decode/verify at ``write_index == pos``),
        so a REJECTED draft's stale K/V at column c holds position c — and
        c necessarily exceeds the slot's accepted frontier.  Any later
        forward writes its tokens (columns L..L+T-1) before its attention
        read, so surviving stale columns satisfy c >= L+T > every query
        position and the ``kp <= qp`` mask keeps them invisible; -1
        entries (pads, cleared rows) never attend at all.  If alignment
        ever broke — a stale column holding a SMALLER position — stale
        K/V could silently enter attention, which is why this is an
        assert, not a repair.  Debug/test aid (one small device->host
        fetch per call): the engine runs it per verify tick under
        ``spec_check_invariants=True``.
        """
        import numpy as np

        def check(path, leaf):
            if not _leaf_name(path).startswith("cached_pos"):
                return leaf
            ax = beam_cache_batch_axis(path, leaf)
            row = np.asarray(
                lax.dynamic_slice_in_dim(leaf, slot, 1, axis=ax)
            ).reshape(-1, leaf.shape[-1])
            cols = np.arange(leaf.shape[-1])[None, :]
            bad = (row != -1) & (row != cols)
            assert not bad.any(), (
                f"slot {slot} position table misaligned at "
                f"(layer, col) {np.argwhere(bad)[:4].tolist()}: stale "
                f"columns would enter attention (pos != col)"
            )
            return leaf

        jax.tree_util.tree_map_with_path(check, self.cache)


@functools.lru_cache(maxsize=None)
def default_row_fns():
    """Jitted (insert, scatter, extract, clear, copy_prefix, stack_prefix),
    one set a process (every pool shares the traces), with the pool
    operand donated on every WRITE op (the old pool tree is dead the
    moment the call returns, and without donation XLA keeps a full second
    pool copy alive; extract reads only, and stack_prefix's inputs stay
    live in the prefix cache — neither donates)."""
    return (
        jax.jit(insert_rows, donate_argnums=0),
        jax.jit(scatter_rows, donate_argnums=0),
        jax.jit(extract_rows, static_argnums=2),
        jax.jit(clear_rows, donate_argnums=0),
        jax.jit(copy_prefix_rows, donate_argnums=0),
        jax.jit(stack_prefix_rows),
    )


# --- block-paged layout ------------------------------------------------------


def free_block_pos(pool_cache, blocks):
    """Invalidate physical ``blocks`` ([k] int32): every position entry to
    -1, so a recycled block's stale positions can never re-enter attention
    under its next owner's table (the paged analog of :func:`clear_rows`).
    Pad ``blocks`` with the pool size — out-of-range scatters DROP, so one
    compiled shape serves any free count.  K/V payloads stay as dead bytes
    until overwritten, exactly as on the fixed-slot path."""

    def clr(path, leaf):
        if not _leaf_name(path).startswith(("cached_pos", "cross_mask")):
            return leaf
        ax = beam_cache_batch_axis(path, leaf)
        if ax is None:
            return leaf
        idx = (slice(None),) * ax + (blocks,)
        return leaf.at[idx].set(-1)

    return jax.tree_util.tree_map_with_path(clr, pool_cache)


def copy_block(pool_cache, src, dst):
    """Copy physical block ``src`` onto ``dst`` across every cache leaf —
    the device half of copy-on-write: a slot about to write into a SHARED
    block gets its own copy of that ONE block (O(block_tokens), not
    O(prefix_len) rows).  Positions copy verbatim: sharing maps the same
    LOGICAL block index into every sharer's table, so the stored global
    positions are already correct for the copy."""

    def cp(path, leaf):
        ax = beam_cache_batch_axis(path, leaf)
        if ax is None:
            return leaf
        row = lax.dynamic_slice_in_dim(leaf, src, 1, axis=ax)
        return lax.dynamic_update_slice_in_dim(leaf, row, dst, axis=ax)

    return jax.tree_util.tree_map_with_path(cp, pool_cache)


def gather_block_rows(pool_cache, blocks):
    """Gather physical ``blocks`` ([k] int32) out of every block-axis
    cache leaf, the block dim moved to axis 0 — a READ op (no donation;
    the pool stays live).  The device half of :meth:`PagedCachePool.
    export_blocks`: one gathered tree fetches to the host in a single
    ``device_get``, so spilling a warm prefix to the host tier or
    shipping it to another replica is one batched transfer, not one
    round-trip per leaf per block."""
    out = []
    for path, leaf in jax.tree_util.tree_flatten_with_path(pool_cache)[0]:
        ax = beam_cache_batch_axis(path, leaf)
        if ax is None:
            continue
        rows = jnp.take(leaf, blocks, axis=ax)
        out.append(jnp.moveaxis(rows, ax, 0))
    return out


def scatter_block_rows(pool_cache, rows, blocks):
    """Write gathered block rows (the :func:`gather_block_rows` layout —
    block dim at axis 0, one array per block-axis leaf in flatten order)
    into physical ``blocks`` across every leaf — a WRITE op (pool
    donated).  Out-of-range indices DROP (pad with the pool size), so one
    compiled shape serves any restore/import count.  Positions copy
    verbatim: block payloads always land at the same LOGICAL index they
    were exported from, so the stored global positions stay correct."""
    it = iter(rows)

    def scat(path, leaf):
        ax = beam_cache_batch_axis(path, leaf)
        if ax is None:
            return leaf
        row = jnp.moveaxis(next(it), 0, ax).astype(leaf.dtype)
        idx = (slice(None),) * ax + (blocks,)
        return leaf.at[idx].set(row)

    return jax.tree_util.tree_map_with_path(scat, pool_cache)


@functools.lru_cache(maxsize=None)
def default_block_fns():
    """Jitted (free_block_pos, copy_block, gather_block_rows,
    scatter_block_rows), one set a process — the write ops donate the
    pool operand under the module's donation contract; the gather is a
    read and never does."""
    return (
        jax.jit(free_block_pos, donate_argnums=0),
        jax.jit(copy_block, donate_argnums=0),
        jax.jit(gather_block_rows),
        jax.jit(scatter_block_rows, donate_argnums=0),
    )


class BlockAllocator:
    """Host-side free list + refcounts over the physical block pool.

    THE single mutation authority for block ownership (the
    ``scripts/check_blocks.py`` gate enforces that no code outside this
    module writes a block table directly): ``alloc`` hands out the
    lowest-numbered free block with refcount 1, ``share`` bumps a live
    block's refcount (prefix-cache entries and every additional slot
    mapping hold one reference each), ``free`` drops one reference and
    returns the block to the free list when the count hits zero.
    Refcounts can never go negative — freeing an unreferenced block
    raises (the double-free guard), as does sharing one.
    """

    def __init__(self, n_blocks: int):
        if n_blocks < 1:
            raise ValueError(f"n_blocks={n_blocks} < 1")
        import numpy as np

        self.n_blocks = n_blocks
        self._free: List[int] = list(range(n_blocks))
        self._ref = np.zeros(n_blocks, np.int32)

    @property
    def n_free(self) -> int:
        return len(self._free)

    @property
    def in_use(self) -> int:
        return self.n_blocks - len(self._free)

    def refcount(self, block: int) -> int:
        return int(self._ref[block])

    def alloc(self) -> int:
        """Claim the lowest free block (deterministic), refcount 1."""
        if not self._free:
            raise RuntimeError(
                "block pool exhausted — admission control must reserve "
                "blocks before the engine writes (estimated-blocks gate)"
            )
        block = heapq.heappop(self._free)
        self._ref[block] = 1
        return block

    def share(self, block: int) -> None:
        """One more reference to a LIVE block (a slot mapping or a
        prefix-cache entry)."""
        if not (0 <= block < self.n_blocks) or self._ref[block] < 1:
            raise ValueError(
                f"share of unallocated block {block} "
                f"(refcount {self.refcount(block) if 0 <= block < self.n_blocks else 'n/a'})"
            )
        self._ref[block] += 1

    def free(self, block: int) -> bool:
        """Drop one reference; True when the block actually returned to
        the free list (refcount hit zero — the caller must invalidate its
        device positions via :func:`free_block_pos` before reuse)."""
        if not (0 <= block < self.n_blocks) or self._ref[block] < 1:
            raise ValueError(
                f"double free of block {block} (refcount "
                f"{self.refcount(block) if 0 <= block < self.n_blocks else 'n/a'})"
            )
        self._ref[block] -= 1
        if self._ref[block] == 0:
            heapq.heappush(self._free, block)
            return True
        return False

    def check(self) -> None:
        """Invariant audit (tests / debug): refcounts non-negative, the
        free list holds exactly the zero-refcount blocks, no duplicates."""
        import numpy as np

        assert (self._ref >= 0).all(), "negative refcount"
        free = sorted(self._free)
        assert free == sorted(set(free)), "duplicate free-list entry"
        zero = np.nonzero(self._ref == 0)[0].tolist()
        assert free == zero, f"free list {free} != zero-ref blocks {zero}"
        assert self.in_use + self.n_free == self.n_blocks


class PagedCachePool:
    """Block-paged pool: device cache tree + per-slot block tables +
    host bookkeeping (slot free list, :class:`BlockAllocator`, per-slot
    block targets for admission accounting).

    The device tree at ``self.cache`` holds every layer's K/V in
    ``kv_pool_blocks`` blocks of ``kv_block_tokens`` positions; the HOST
    mirror ``self.block_table`` [n_slots, max_blocks] is authoritative
    (device uploads are per-call copies), with -1 = unmapped.  All table
    mutation goes through this class — allocation (``ensure_writable``),
    refcounted prefix sharing (``map_prefix`` / ``snapshot_blocks``), and
    release — so the allocator's refcounts can never drift from the
    tables (``scripts/check_blocks.py`` gates raw writes).

    Same donation-and-ownership contract as :class:`CachePool`: every
    write op (extend/decode/verify ticks, COW copies, free-list
    invalidation) DONATES the pool operand, so ``self.cache`` is the only
    valid handle and stale references point at deleted buffers.
    """

    def __init__(self, model, params, n_slots: int):
        import numpy as np

        cfg = model.config
        bt = cfg.kv_block_tokens
        if bt < 1:
            raise ValueError(
                "PagedCachePool needs a model built with kv_block_tokens "
                f"> 0 (got {bt})"
            )
        if cfg.seq_len % bt != 0:
            raise ValueError(
                f"kv_block_tokens={bt} must divide seq_len={cfg.seq_len}"
            )
        if n_slots < 1:
            raise ValueError(f"n_slots={n_slots} < 1")
        self.n_slots = n_slots
        self.block_tokens = bt
        self.max_blocks = cfg.seq_len // bt
        self.n_blocks = cfg.kv_pool_blocks
        self.cache = empty_pool(model, params, n_slots)
        self.allocator = BlockAllocator(self.n_blocks)
        self.block_table = np.full(
            (n_slots, self.max_blocks), -1, np.int32
        )
        # bumped on EVERY host-mirror mutation so the engine re-uploads
        # the device copy lazily (the fused tick's table rides its inputs)
        self.table_version = 0
        self._free_slots: List[int] = list(range(n_slots))
        # blocks each occupied slot is still entitled to allocate
        # (admission reserved them); available = free - outstanding
        self._target_blocks = np.zeros(n_slots, np.int32)
        # cumulative tallies (ServingMetrics delta-syncs these)
        self.cow_copies = 0
        self.shared_block_maps = 0
        (self._free_pos, self._copy_block, self._gather_rows,
         self._scatter_rows) = default_block_fns()
        # bytes of ONE block across every payload leaf (all layers) — the
        # capacity denominator behind kv_bytes_per_active_token
        self.bytes_per_block = sum(
            leaf.size * leaf.dtype.itemsize // self.n_blocks
            for path, leaf in jax.tree_util.tree_flatten_with_path(
                self.cache
            )[0]
            if beam_cache_batch_axis(path, leaf) is not None
        )

    # -- slot lifecycle ----------------------------------------------------

    @property
    def n_free(self) -> int:
        return len(self._free_slots)

    @property
    def occupancy(self) -> float:
        return 1.0 - len(self._free_slots) / self.n_slots

    @property
    def blocks_in_use(self) -> int:
        return self.allocator.in_use

    @property
    def blocks_free(self) -> int:
        return self.allocator.n_free

    def acquire(self) -> Optional[int]:
        if not self._free_slots:
            return None
        return self._free_slots.pop(0)

    def release(self, slot: int) -> None:
        """Return ``slot`` to the free list AND drop its block references:
        exclusively-owned blocks go back to the allocator (positions
        device-invalidated so the next owner never attends stale entries);
        shared blocks just decrement — prefix-cache entries and co-sharers
        keep them alive."""
        if slot in self._free_slots or not (0 <= slot < self.n_slots):
            raise ValueError(f"bad release of slot {slot}")
        freed = []
        for j in range(self.max_blocks):
            blk = int(self.block_table[slot, j])
            if blk >= 0 and self.allocator.free(blk):
                freed.append(blk)
        self.block_table[slot, :] = -1
        self.table_version += 1
        self._target_blocks[slot] = 0
        self._invalidate(freed)
        self._free_slots.append(slot)
        self._free_slots.sort()

    def _invalidate(self, blocks) -> None:
        """Device-side -1 of freed blocks' position entries, in padded
        fixed-width calls (one compiled shape)."""
        if not blocks:
            return
        import numpy as np

        for i in range(0, len(blocks), self.max_blocks):
            chunk = blocks[i : i + self.max_blocks]
            idx = np.full(self.max_blocks, self.n_blocks, np.int32)
            idx[: len(chunk)] = chunk
            self.cache = self._free_pos(self.cache, jnp.asarray(idx))

    # -- admission accounting ----------------------------------------------

    def blocks_needed(self, total_tokens: int) -> int:
        """Blocks a request of ``total_tokens`` (prompt + budget) needs,
        ignoring prefix sharing (the conservative admission estimate)."""
        return -(-int(total_tokens) // self.block_tokens)

    def outstanding_blocks(self) -> int:
        """Blocks occupied slots are still entitled to allocate.  One
        vectorized pass — the admission gate calls this per queued
        candidate per tick (idle slots have target 0, so they contribute
        ``max(0, -mapped) == 0``)."""
        import numpy as np

        mapped = (self.block_table >= 0).sum(axis=1)
        return int(np.maximum(self._target_blocks - mapped, 0).sum())

    def blocks_available(self) -> int:
        """Free blocks NOT spoken for by in-flight slots' entitlements —
        the admission gate's budget."""
        return self.allocator.n_free - self.outstanding_blocks()

    def begin_slot(
        self, slot: int, total_tokens: int, cow_reserve: int = 0
    ) -> None:
        """Record the slot's block entitlement at admission (ceil of its
        worst-case token footprint) — lazy allocation draws against it.
        ``cow_reserve`` is extra headroom for copy-on-write allocations
        when prefix sharing can land MID-block (buckets not aligned to
        ``block_tokens``): a COW keeps the original alive under its other
        referents AND claims a fresh block, so it is real demand the
        plain ceil cannot see — the engine reserves one block per
        non-aligned bucket (plus one for a mid-block hit tail), which
        upper-bounds the slot's possible COW events."""
        self._target_blocks[slot] = (
            min(self.max_blocks, self.blocks_needed(total_tokens))
            + int(cow_reserve)
        )

    # -- the write path ----------------------------------------------------

    def ensure_writable(self, slot: int, start_col: int, end_col: int) -> None:
        """Make logical columns ``[start_col, end_col)`` of ``slot``
        writable: allocate unmapped blocks in range, and COPY-ON-WRITE any
        block in range whose refcount exceeds one (someone else — a
        prefix-cache entry or a co-sharing slot — still reads the
        original).  Runs before every engine write (prefill extend, decode
        tick, verify tick), so shared blocks are never scribbled on."""
        if end_col <= start_col:
            return
        first = start_col // self.block_tokens
        last = min(self.max_blocks, self.blocks_needed(end_col))
        dirty = False
        for j in range(first, last):
            blk = int(self.block_table[slot, j])
            if blk < 0:
                self.block_table[slot, j] = self.allocator.alloc()
                dirty = True
            elif self.allocator.refcount(blk) > 1:
                new = self.allocator.alloc()
                self.cache = self._copy_block(
                    self.cache, jnp.int32(blk), jnp.int32(new)
                )
                self.allocator.free(blk)  # refcount was > 1: stays alive
                self.block_table[slot, j] = new
                self.cow_copies += 1
                dirty = True
        if dirty:
            self.table_version += 1

    # -- prefix sharing ----------------------------------------------------

    def map_prefix(self, slot: int, blocks, length: int) -> None:
        """Land a stored prefix into ``slot`` as TABLE POINTER WRITES — one
        refcount bump per block, zero K/V copies (the fixed-slot layout's
        ``copy_prefix`` was O(prefix_len) rows).  A later write into any
        shared block copy-on-writes through :meth:`ensure_writable`.
        Positions need no trimming: entries beyond ``length`` in the tail
        block hold their own column index (the aligned-layout invariant),
        which every query masks out until the slot overwrites them."""
        need = self.blocks_needed(length)
        if len(blocks) < need:
            raise ValueError(
                f"prefix of {length} tokens needs {need} blocks, got "
                f"{len(blocks)}"
            )
        for j in range(need):
            blk = int(blocks[j])
            self.allocator.share(blk)
            self.block_table[slot, j] = blk
        self.shared_block_maps += need
        self.table_version += 1

    def snapshot_blocks(self, slot: int, length: int):
        """Freeze the slot's first ``ceil(length / block_tokens)`` blocks
        as a prefix-cache entry: one refcount bump each, NO copies.  The
        owner's next write into a snapshotted block copy-on-writes away
        from it, so the stored prefix is immutable from this moment."""
        need = self.blocks_needed(length)
        blocks = tuple(int(b) for b in self.block_table[slot, :need])
        if any(b < 0 for b in blocks):
            raise ValueError(
                f"slot {slot} has only "
                f"{int((self.block_table[slot] >= 0).sum())} mapped blocks; "
                f"cannot snapshot {need}"
            )
        for b in blocks:
            self.allocator.share(b)
        return blocks

    def pin_blocks(self, blocks) -> None:
        """Take a temporary reference on a stored prefix entry's blocks so
        the entry can outlive its LRU slot: a same-tick eviction (another
        admission group's ``store_one`` overflowing the cache calls
        :meth:`free_stored` on the entry) must not free blocks a later
        group looked up but has not mapped yet.  Pair every pin with one
        :meth:`free_stored` once the blocks are mapped."""
        for b in blocks:
            self.allocator.share(int(b))

    def free_stored(self, blocks) -> None:
        """Drop a prefix-cache entry's block references (LRU eviction or a
        lost store race); blocks whose refcount hits zero return to the
        free list and are device-invalidated."""
        freed = [b for b in blocks if self.allocator.free(int(b))]
        self._invalidate(freed)

    # -- block export / import (host offload tier + cross-replica migration)

    @property
    def export_meta(self):
        """Shape signature of one exported block: ``(leaf name, per-block
        shape, dtype)`` per block-axis cache leaf in flatten order — what
        :meth:`import_stored` callers compare before landing foreign
        payloads (a different model config must refuse, not scribble)."""
        out = []
        for path, leaf in jax.tree_util.tree_flatten_with_path(
            self.cache
        )[0]:
            ax = beam_cache_batch_axis(path, leaf)
            if ax is None:
                continue
            shape = leaf.shape[:ax] + leaf.shape[ax + 1:]
            out.append((_leaf_name(path), shape, str(leaf.dtype)))
        return tuple(out)

    def export_blocks(self, blocks):
        """Copy physical ``blocks``' K/V (payloads, positions, int8
        scales) to HOST memory: one jitted gather + one batched
        ``device_get`` per fixed-width chunk.  Returns one numpy array
        per block-axis leaf (flatten order, block dim at axis 0, length
        ``len(blocks)``) — the exchange payload the host offload tier
        spills and cross-replica migration ships.  A read op: the blocks
        stay live under their existing references, and refcounted
        immutability (any sharer's write copy-on-writes away) means the
        exported bytes can never be scribbled mid-copy."""
        import numpy as np

        if not blocks:
            return []
        chunks = []
        for i in range(0, len(blocks), self.max_blocks):
            chunk = blocks[i : i + self.max_blocks]
            idx = np.zeros(self.max_blocks, np.int32)  # pad: block 0 rows
            idx[: len(chunk)] = chunk
            gathered = self._gather_rows(self.cache, jnp.asarray(idx))
            host = jax.device_get(gathered)  # host-sync: offload/migration cold path, one batched fetch per chunk
            chunks.append([leaf[: len(chunk)] for leaf in host])
        if len(chunks) == 1:
            return list(chunks[0])
        return [
            np.concatenate([c[i] for c in chunks], axis=0)
            for i in range(len(chunks[0]))
        ]

    def _write_blocks(self, rows, blocks) -> None:
        """Scatter host block rows (the :meth:`export_blocks` layout)
        into physical ``blocks`` — padded fixed-width jitted calls, the
        pool donated per the module contract."""
        import numpy as np

        for i in range(0, len(blocks), self.max_blocks):
            chunk = blocks[i : i + self.max_blocks]
            idx = np.full(self.max_blocks, self.n_blocks, np.int32)
            idx[: len(chunk)] = chunk
            pad = self.max_blocks - len(chunk)
            payload = [
                np.concatenate(
                    [leaf[i : i + len(chunk)]]
                    + ([np.zeros((pad,) + leaf.shape[1:], leaf.dtype)]
                       if pad else []),
                    axis=0,
                )
                for leaf in rows
            ]
            self.cache = self._scatter_rows(
                self.cache,
                [jnp.asarray(p) for p in payload],
                jnp.asarray(idx),
            )

    def import_stored(self, rows, count: int, checksums=None):
        """Allocate ``count`` fresh blocks — each with refcount 1, the
        STORE's reference, exactly like :meth:`snapshot_blocks`'s bumps —
        and land exported host rows in them via one batched upload +
        scatter.  Returns the block-id tuple, or None when fewer than
        ``count`` blocks are available beyond in-flight slots'
        entitlements (the caller counts a typed restore/migration
        fallback instead of stealing blocks admission already promised).

        ``checksums`` (per-block CRC32s recorded at export time,
        :func:`block_checksums`) are verified BEFORE any allocation or
        device write: a mismatch raises :class:`KVIntegrityError` —
        never lands unverified bytes — and the caller counts a typed
        ``restore_failure``/``integrity`` refusal and recomputes.  The
        imported entry participates in normal sharing from here:
        ``map_prefix`` bumps it per hit, ``free_stored`` releases it."""
        if count < 1:
            return ()
        if checksums is not None:
            got = block_checksums(rows, count)
            want = tuple(int(c) for c in checksums[:count])
            if len(want) < count or got != want:
                bad = [
                    i for i, (g, w) in enumerate(zip(got, want))
                    if g != w
                ] or list(range(len(want), count))
                raise KVIntegrityError(
                    f"KV import refused: block(s) {bad} of {count} fail "
                    "their export checksum — corrupted bytes must "
                    "recompute, never serve"
                )
        if self.blocks_available() < count:
            return None
        blocks = tuple(self.allocator.alloc() for _ in range(count))
        self._write_blocks(rows, blocks)
        return blocks

    # -- invariants --------------------------------------------------------

    def assert_slot_aligned(self, slot: int) -> None:
        """The block-paged generalization of
        :meth:`CachePool.assert_slot_aligned`: gathered through the slot's
        table, every valid LOGICAL position entry stores exactly its own
        column (``pos[c] in {-1, c}``) — the no-rollback invariant that
        keeps stale speculative columns and shared-tail surplus invisible.
        """
        import numpy as np

        tbl = self.block_table[slot]
        mapped = np.repeat(tbl >= 0, self.block_tokens)

        def check(path, leaf):
            if not _leaf_name(path).startswith("cached_pos"):
                return leaf
            ax = beam_cache_batch_axis(path, leaf)
            arr = np.asarray(leaf)
            pages = np.take(arr, np.maximum(tbl, 0), axis=ax)
            flat = pages.reshape(*arr.shape[:ax], -1)
            row = np.where(mapped, flat, -1).reshape(-1, flat.shape[-1])
            cols = np.arange(flat.shape[-1])[None, :]
            bad = (row != -1) & (row != cols)
            assert not bad.any(), (
                f"slot {slot} paged position table misaligned at "
                f"(layer, col) {np.argwhere(bad)[:4].tolist()}: stale "
                f"columns would enter attention (pos != col)"
            )
            return leaf

        jax.tree_util.tree_map_with_path(check, self.cache)
