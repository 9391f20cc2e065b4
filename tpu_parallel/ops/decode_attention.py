"""Attention of new query rows against a slot's stored K/V, as one Pallas TPU kernel.

A decode step attends a handful of new rows a slot (one token, or the 2L rows
of a block step) against that slot's stripe of the K/V pool.  Written as
``jax.numpy`` (``models.layers.decode_attention_xla``) the step writes every
layer's ``[slots, kv_heads, group, rows, positions]`` scores to HBM, twice,
and reads every slot's WHOLE stripe whatever the slot holds.  This kernel

* keeps the scores on the chip: for a slot and a tile of stored positions the
  ``q k^T`` product, the mask, a running maximum and sum in float32 and the
  ``p v`` product all happen in VMEM (the flash recurrence).  Operands go into
  the matrix unit in the type they come in with float32 accumulation, and the
  probabilities are rounded to that type before ``p v`` as the ``jax.numpy``
  path rounds them: the same precision, not a lower one;
* reads only the tiles a slot holds: each slot's tile range goes in as
  prefetched scalars, a grid step outside it names a block that is already in
  VMEM (no copy) and computes nothing.  The range is computed from the STORED
  position table (:func:`tile_ranges`), so it is right for the engine's
  aligned table, a left-padded one, and one with holes; inside a tile the
  mask is exact, from the stored positions;
* reads the pool as it is stored: ``[slots, positions * kv_heads, head_dim]``
  is a view of the stripe (a row a position and K/V head; the compiler makes
  it a bitcast, where ``[slots, positions, kv_heads * head_dim]`` would be a
  re-tiled copy of the pool a layer), a block is whole rows of it, a head's
  rows are picked out of the block in VMEM (:func:`_head`), and all the
  query rows that share a K/V head (``new_len x group``) go through the
  matrix unit together;
* gives a row that sees nothing zeros (a parked slot, a pad row at position
  -1), where the ``jax.numpy`` path gives the mean of V.

Grid ``(slots, tiles a stripe)``, the tiles innermost.  A slot's live tiles are
walked in its LAST grid steps: the steps before them name the first live tile,
which the pipeline fetched while the previous slot's last tile was computed,
so no slot waits for its first copy.

``decode_attention_plan`` is the rule that sends a shape here (it sees shapes
and operand kinds only); every other shape runs the ``jax.numpy`` path.  As
with the flash and grouped-FFN kernels there is no fallback by backend: off
the TPU the same kernel runs in Pallas interpret mode.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu  # importable on CPU too

from tpu_parallel.ops.flash_attention import NEG_INF, _NT, _padded_bytes, _sds
from tpu_parallel.ops.grouped_ffn import _round_up

# the name the device trace shows the kernel under
KERNEL_NAME = "attn.decode_stripes"
# stored positions a grid step: on a TPU v5e at the block-diffusion cell's
# shape 1024 and 512 are within 4% of each other and three times faster than
# 128 (PERF.md section 6, PR 44); the largest that leaves at least two tiles
TILES = (1024, 512, 256, 128)
# query rows a K/V head (``new_len x group``) that one VMEM block may hold
MAX_ROWS = 256
# rows of a packed bf16 tile: the row block is padded to a multiple
ROW_ALIGN = 16


def decode_attention_plan(
    q_shape: Tuple[int, ...],
    kv_shape: Tuple[int, ...],
    dtype,
    kv_dtype=None,
    *,
    bias: bool = False,
    scales: bool = False,
    paged: bool = False,
) -> Optional[dict]:
    """What the kernel does with queries ``[slots, new_len, heads, head_dim]``
    against stripes ``[slots, positions, kv_heads, head_dim]``: the tile, the
    tiles a stripe, the grid, the rows a call and the VMEM limit it asks for.
    None where the ``jax.numpy`` path runs instead.

    The rule sees shapes and operand kinds: ``head_dim`` a multiple of 128 (a
    K/V head is whole lanes of the stored row); no score bias, no int8 scales
    and not the paged gather (each folds something into the scores or copies
    the stripe, which the ``jax.numpy`` path already does); queries and
    stripes both bfloat16 (the served type; in float32 the scores are the
    reference's own and there is no cell to measure); the stored positions a
    multiple of a tile of ``TILES`` and at least two of them (one tile has no
    range to walk); ``new_len x group`` at most ``MAX_ROWS``; one K/V head or
    an even number (two stored heads share a 32-bit word, :func:`_head`).
    """
    if len(q_shape) != 4 or len(kv_shape) != 4:
        return None
    slots, new_len, heads, head_dim = q_shape
    positions, kv_heads = kv_shape[1], kv_shape[2]
    kv_dtype = dtype if kv_dtype is None else kv_dtype
    if bias or scales or paged:
        return None
    if jnp.dtype(dtype) != jnp.bfloat16 or jnp.dtype(kv_dtype) != jnp.bfloat16:
        return None
    if head_dim % 128 or heads % kv_heads or (kv_heads > 1 and kv_heads % 2):
        return None
    rows = new_len * (heads // kv_heads)
    if rows > MAX_ROWS:
        return None
    tile = next(
        (t for t in TILES if positions % t == 0 and positions // t >= 2), None
    )
    if tile is None:
        return None
    tiles = positions // tile
    padded = _round_up(rows, ROW_ALIGN)
    need = (
        # K and V blocks double-buffered, the stored positions beside them
        2 * 2 * _padded_bytes(tile * kv_heads, head_dim, kv_dtype)
        + 2 * _padded_bytes(1, tile, jnp.int32)
        # the query and output rows of every K/V head, double-buffered
        + 2 * 2 * kv_heads * _padded_bytes(padded, head_dim, dtype)
        + 2 * 2 * _padded_bytes(padded, 1, jnp.int32)
        # accumulator, maximum and sum a K/V head
        + kv_heads * _padded_bytes(padded, head_dim, jnp.float32)
        + 2 * kv_heads * _padded_bytes(padded, 1, jnp.float32)
        # a head's K and V picked out of the blocks, its scores, their
        # exponentials and the mask on their way
        + 2 * _padded_bytes(tile, head_dim, jnp.float32)
        + 4 * _padded_bytes(padded, tile, jnp.float32)
    )
    return {
        "tile": tile, "tiles": tiles, "grid": [slots, tiles], "rows": rows,
        "vmem_limit_bytes": min(max(need * 5 // 4, 32 << 20), 100 << 20),
    }


def visible_bounds(positions: jax.Array, window: int = 0, block_len: int = 0):
    """``(lo, hi)``, each shaped as ``positions``: a query at position ``p``
    sees the stored positions ``kp`` with ``lo < kp <= hi``.  ``hi`` is ``p``,
    or under the block rule the end of ``p``'s block, ``p // L * L + L - 1``
    (floor division: a pad query at -1 keeps -1); ``lo`` is ``hi - window``
    under a window and never under -1, so that no negative (pad) position is
    seen.  The one definition of the mask ``models.layers.decode_attention_xla``
    spells out as ``kp >= 0``, ``kp <= hi``, ``hi - kp < window``."""
    hi = positions.astype(jnp.int32)
    if block_len:
        hi = hi // block_len * block_len + (block_len - 1)
    lo = jnp.full_like(hi, -1)
    if window:
        lo = jnp.maximum(hi - window, lo)
    return lo, hi


def tile_ranges(k_pos: jax.Array, lo: jax.Array, hi: jax.Array, tile: int):
    """``(first, count)``, each ``[slots]`` int32: the tiles ``[first, first +
    count)`` of a slot's stripe hold every stored position that any of the
    slot's query rows can see.  From the stored positions themselves
    (``k_pos`` ``[slots, positions]``; ``lo`` / ``hi`` ``[slots, rows]`` from
    :func:`visible_bounds`): a tile counts if one of its positions lies under
    the largest ``hi`` and above the smallest ``lo`` of the rows that can see
    anything.  A slot none of whose rows sees a stored position has count 0."""
    slots, positions = k_pos.shape
    tiles = positions // tile
    top = jnp.max(hi, axis=1, keepdims=True)
    sees = hi >= 0
    floor = jnp.min(
        jnp.where(sees, lo, jnp.iinfo(jnp.int32).max), axis=1, keepdims=True
    )
    held = jnp.any(
        ((k_pos > floor) & (k_pos <= top)).reshape(slots, tiles, tile), axis=2
    )
    index = jnp.arange(tiles, dtype=jnp.int32)[None, :]
    first = jnp.min(jnp.where(held, index, tiles), axis=1)
    last = jnp.max(jnp.where(held, index, -1), axis=1)
    count = jnp.maximum(last - first + 1, 0)
    return jnp.minimum(first, tiles - 1).astype(jnp.int32), count.astype(jnp.int32)


def _head(ref, n: int, kv_heads: int, tile: int):
    """K/V head ``n``'s ``[tile, head_dim]`` out of a block of stored rows
    ``[1, tile * kv_heads, head_dim]``, row ``position * kv_heads + head``.
    The pool stores a position's heads side by side, and in a 16-bit type
    two neighbouring rows share each 32-bit word: the block is read as words
    (a row of words is one position's pair of heads), a strided load takes
    the pair's row of every position, and the half that is head ``n`` is
    widened to float32 in place and rounded back, which changes no bit."""
    if kv_heads == 1:
        return ref[0]
    dtype = ref.dtype
    if dtype.itemsize == 4:  # a row a word already (the tests' float32)
        return ref[0, pl.ds(n, tile, stride=kv_heads), :]
    words = ref.bitcast(jnp.uint32)
    pair = words[0, pl.ds(n // 2, tile, stride=kv_heads // 2), :]
    half = pair << 16 if n % 2 == 0 else pair & jnp.uint32(0xFFFF0000)
    return pltpu.bitcast(half, jnp.float32).astype(dtype)


def _kernel(first_ref, count_ref, q_ref, lo_ref, hi_ref, kp_ref, k_ref, v_ref,
            o_ref, acc_ref, m_ref, l_ref, *, tile: int, tiles: int,
            kv_heads: int):
    """One grid step ``(slot, step)``: the slot's rows against one tile of its
    stripe, every K/V head in turn, into the float32 running state; the
    slot's last step normalises and stores."""
    del first_ref
    slot, step = pl.program_id(0), pl.program_id(1)

    @pl.when(step == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(step >= tiles - count_ref[slot])
    def _tile():
        kp = kp_ref[0]  # [1, tile]
        mask = (kp > lo_ref[0]) & (kp <= hi_ref[0])  # [rows, tile]
        for n in range(kv_heads):
            k = _head(k_ref, n, kv_heads, tile)
            v = _head(v_ref, n, kv_heads, tile)
            s = lax.dot_general(
                q_ref[0, n], k, _NT, preferred_element_type=jnp.float32
            )
            s = jnp.where(mask, s, NEG_INF)
            m_prev = m_ref[n]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            # a row that has seen nothing yet weighs its masked keys 1; the
            # first key it sees wipes that (alpha 0), the last step the rest
            p = jnp.exp(s - m_new)
            l_ref[n] = l_ref[n] * alpha + jnp.sum(p, axis=1, keepdims=True)
            acc_ref[n] = acc_ref[n] * alpha + jnp.dot(
                p.astype(v.dtype), v, preferred_element_type=jnp.float32
            )
            m_ref[n] = m_new

    @pl.when(step == tiles - 1)
    def _store():
        # a row that saw no key (its maximum never moved) returns zeros
        seen = m_ref[...] > NEG_INF
        out = acc_ref[...] / jnp.where(seen, l_ref[...], 1.0)
        o_ref[0] = jnp.where(seen, out, 0.0).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("tile", "vmem_limit_bytes", "interpret")
)
def _stripes(q, k_all, v_all, lo, hi, k_pos, *, tile, vmem_limit_bytes,
             interpret):
    """:func:`decode_stripes` under a tile.  Jitted so that the layers of a
    model (and the calls of a test that runs eagerly) share one trace."""
    slots, new_len, heads, head_dim = q.shape
    positions, kv_heads = k_all.shape[1], k_all.shape[2]
    group = heads // kv_heads
    tiles = positions // tile
    rows = new_len * group
    padded = _round_up(rows, ROW_ALIGN)
    # a K/V head's rows together, ordered (query, member of the group)
    qg = q.reshape(slots, new_len, kv_heads, group, head_dim)
    qg = qg.transpose(0, 2, 1, 3, 4).reshape(slots, kv_heads, rows, head_dim)
    row_lo = jnp.repeat(lo.astype(jnp.int32), group, axis=1)
    row_hi = jnp.repeat(hi.astype(jnp.int32), group, axis=1)
    if padded != rows:  # pad rows sit at position -1 and see nothing
        pad = padded - rows
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, pad), (0, 0)))
        row_lo = jnp.pad(row_lo, ((0, 0), (0, pad)), constant_values=-1)
        row_hi = jnp.pad(row_hi, ((0, 0), (0, pad)), constant_values=-1)
    k_pos = k_pos.astype(jnp.int32)
    first, count = tile_ranges(k_pos, lo, hi, tile)
    stored = tile * kv_heads  # rows of the pool a tile of positions is

    def stripe_map(slot, step, first, count):
        # the live tiles are the slot's last steps; the steps before them
        # stay on the first live tile (fetched once, ahead of time)
        at = first[slot] + jnp.maximum(step - (tiles - count[slot]), 0)
        return slot, at, 0

    def position_map(slot, step, first, count):
        return slot, 0, stripe_map(slot, step, first, count)[1]

    def rows_map(slot, step, *_):
        return slot, 0, 0

    def heads_map(slot, step, *_):
        return slot, 0, 0, 0

    params = {} if interpret else {
        "compiler_params": pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=vmem_limit_bytes,
        )
    }
    out = pl.pallas_call(
        functools.partial(
            _kernel, tile=tile, tiles=tiles, kv_heads=kv_heads
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(slots, tiles),
            in_specs=[
                pl.BlockSpec((1, kv_heads, padded, head_dim), heads_map),
                pl.BlockSpec((1, padded, 1), rows_map),
                pl.BlockSpec((1, padded, 1), rows_map),
                pl.BlockSpec((1, 1, tile), position_map),
                pl.BlockSpec((1, stored, head_dim), stripe_map),
                pl.BlockSpec((1, stored, head_dim), stripe_map),
            ],
            out_specs=pl.BlockSpec((1, kv_heads, padded, head_dim), heads_map),
            scratch_shapes=[
                pltpu.VMEM((kv_heads, padded, head_dim), jnp.float32),
                pltpu.VMEM((kv_heads, padded, 1), jnp.float32),
                pltpu.VMEM((kv_heads, padded, 1), jnp.float32),
            ],
        ),
        out_shape=_sds((slots, kv_heads, padded, head_dim), q.dtype, q),
        name=KERNEL_NAME,
        interpret=interpret,
        **params,
    )(
        first, count, qg, row_lo[..., None], row_hi[..., None],
        k_pos[:, None, :],
        # the stripes as they are stored, a row a (position, K/V head): a
        # view, where ``[slots, positions, kv_heads * head_dim]`` is a
        # re-tiled copy of the pool (PERF.md section 6, PR 44)
        k_all.reshape(slots, positions * kv_heads, head_dim),
        v_all.reshape(slots, positions * kv_heads, head_dim),
    )
    out = out[:, :, :rows].reshape(slots, kv_heads, new_len, group, head_dim)
    return out.transpose(0, 2, 1, 3, 4).reshape(slots, new_len, heads, head_dim)


def decode_stripes(
    q: jax.Array,
    k_all: jax.Array,
    v_all: jax.Array,
    lo: jax.Array,
    hi: jax.Array,
    k_pos: jax.Array,
    *,
    tile: int,
    vmem_limit_bytes: int = 32 << 20,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """The kernel over operands as the model holds them: ``q`` ``[slots,
    new_len, heads, head_dim]`` ALREADY scaled, the stripes ``[slots,
    positions, kv_heads, head_dim]``, ``lo`` / ``hi`` ``[slots, new_len]``
    (:func:`visible_bounds`) and the stored positions ``[slots, positions]``.
    Returns ``[slots, new_len, heads, head_dim]`` in ``q``'s type.  ``tile``
    divides the positions; any dtype of 16 or 32 bits runs (the rule of
    :func:`decode_attention_plan` is the caller's)."""
    positions, kv_heads = k_all.shape[1], k_all.shape[2]
    if positions % tile or q.shape[2] % kv_heads:
        raise ValueError(
            f"tile {tile} over {positions} stored positions, {q.shape[2]} "
            f"heads over {kv_heads} K/V heads"
        )
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _stripes(
        q, k_all, v_all, lo, hi, k_pos, tile=tile,
        vmem_limit_bytes=vmem_limit_bytes, interpret=interpret,
    )
