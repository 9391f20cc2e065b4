"""Causal flash attention as Pallas TPU kernels (fwd + bwd), with custom VJP.

No reference capability exists (the reference has no attention at all —
SURVEY.md §5 long-context row); this kernel serves the transformer configs and
the ≥40% MFU target: O(seq) memory instead of O(seq^2), fp32 online softmax,
bf16 MXU matmuls, block sizes aligned to the 128-lane MXU.

Layout convention: [batch, heads, seq, head_dim] operands (the public API
accepts [batch, seq, heads, head_dim] and transposes).  The causal structure
is exploited twice: tiles beyond the band are skipped (not masked — skipped),
and only the tiles the diagonal (or a window edge) crosses are masked at all.

Grouped-query attention is native: K/V may carry ``n_kv < n_heads`` heads and
are NEVER expanded — the BlockSpec index maps route each query head to its
K/V head's blocks, so GQA pays 1/group of MHA's K/V HBM traffic (the whole
point of GQA; a pre-kernel ``jnp.repeat`` would materialize full-MHA K/V
because Pallas operands are real buffers, not fusible broadcasts).

Two kernel variants share the band geometry (``_band_bounds``):

- **resident**: one grid step holds a whole (batch, head) row in VMEM and
  walks the tiles of the band in a loop that is static at trace time, so
  each tile is classified before it is emitted (``_tile_kind``): tiles
  outside the band emit nothing, tiles strictly inside it emit no iota,
  compare or select, and only the tiles an edge of the band crosses are
  masked.  Score tiles are computed TRANSPOSED (``[block_k, block_q]``, keys
  on sublanes, queries on lanes): the running max / sum, ``lse`` and
  ``delta`` are lane-dense ``[1, block_q]`` rows and the output accumulator
  is ``[head_dim, block_q]``.  The backward is ONE kernel per (batch, kv
  head) row: from one score tile, one ``exp`` and one ``dp`` it forms
  ``dv``, ``dk`` and ``dq`` (5 matmuls); ``dq`` accumulates in an fp32 VMEM
  scratch and is written once.
- **streamed**: the K/V walk is a grid dimension; VMEM holds one
  [block_k, d] tile plus fp32 online-softmax scratch carried across grid
  steps, so residency is O(block) and any row fits v5e VMEM.  Out-of-band
  grid steps clamp their index map to the previous block — Pallas skips the
  DMA when the mapped block is unchanged — so causal still halves the
  traffic, not just the FLOPs; every tile it computes is masked and every
  grid step is paid.  Its backward is the dq / dkv pair.

``flash_plan`` maps the shape to the tiles of each pass and says which
variant runs.  The FORWARD is resident while the row's blocks and its live
score tiles fit ``RESIDENT_VMEM_BUDGET`` and its walk has at most
``MAX_STATIC_TILES`` bodies (``_fwd_streams``: bytes, not a row count; an
8192-long causal row at a tile of 512 is 136 bodies), the BACKWARD while
``group * seq`` and ``seq_kv`` are at most ``STREAM_SEQ_THRESHOLD`` rows
and its walk is as short.  Chip numbers behind both rules are in PERF.md
(section 6: PR 27 for the backward and heads of 64, PR 48 for the forward
at heads of 128 and more and rows to 8192).

Packed sequences: ``segment_ids`` [batch, seq] adds a same-segment condition
to every tile in all kernels (each query can always see itself, so no row
of a causal self-attention is ever fully masked).

There is no jnp fallback by backend: off-TPU the SAME kernels run in Pallas
interpret mode (``interpret=None`` resolves from ``jax.default_backend()`` —
what the CPU tests exercise), and on a TPU they compile to ``tpu_custom_call``s.
A program that must be on the chip proves it by finding that custom call in
its lowered text (``chip_smoke.py`` does).  The one dense escape hatch is by
SHAPE, not backend: a sequence no tile divides warns and takes the O(seq^2)
``causal_attention`` path.
"""

from __future__ import annotations

import functools
import warnings
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu  # importable on CPU too

# above this row length (K/V, or the group's queries) the streamed BACKWARD
# kernels take over (no cell runs a backward past 1024 rows: PR 27's rule)
STREAM_SEQ_THRESHOLD = 4096
# the resident FORWARD holds a row's q, k, v and output blocks in VMEM,
# double-buffered, beside its live fp32 score tiles (``_resident_need``):
# past this many bytes the streamed kernel runs (an 8192-long row of 192-wide
# heads at a tile of 512 needs 44 MiB and compiles for a v5e; a 16k row at
# the tile of 1024 it derives needs 80 MiB at 128 columns, 112 at 192)
RESIDENT_VMEM_BUDGET = 64 << 20
# the resident kernels unroll their tile walk at trace time: past this many
# tile bodies in one kernel the streamed kernels (a grid, not an unroll) run
MAX_STATIC_TILES = 160
# a derived tile never cuts a row into more pieces than this
MAX_TILES_PER_SIDE = 16
NEG_INF = -1e30
# dot_general dimension numbers: a @ b.T and a.T @ b, no transpose op
_NT = (((1,), (1,)), ((), ()))
_TN = (((0,), (0,)), ((), ()))


def reference_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, segment_ids: Optional[jax.Array] = None
) -> jax.Array:
    """jnp causal attention on [B, H, S, D] (fp32 softmax) — ground truth."""
    scale = 1.0 / jnp.sqrt(q.shape[-1])
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * scale
    s = q.shape[2]
    mask = jnp.tril(jnp.ones((s, s), bool))
    if segment_ids is not None:
        same = segment_ids[:, None, :, None] == segment_ids[:, None, None, :]
        mask = jnp.logical_and(mask, same)
    scores = jnp.where(mask, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", probs.astype(q.dtype), v)


def _sds(shape, dtype, like):
    """ShapeDtypeStruct for pallas out_shape, inheriting ``like``'s varying
    axes — under shard_map's replication checker (check_vma=True) pallas
    outputs must declare their vma explicitly."""
    from tpu_parallel.core.metrics import vma_of

    vma = vma_of(like)
    if vma:
        return jax.ShapeDtypeStruct(shape, dtype, vma=frozenset(vma))
    return jax.ShapeDtypeStruct(shape, dtype)


def _kv_row_map(h: int, h_kv: int):
    """Block-row index map routing query-head row ``bh`` of a [B*H, ...] grid
    to its K/V head's row in the [B*H_KV, ...] K/V array — the native-GQA
    mechanism (no K/V expansion anywhere)."""
    if h == h_kv:
        return lambda bh_: bh_
    group = h // h_kv
    return lambda bh_: (bh_ // h) * h_kv + (bh_ % h) // group


def _window_first_k_block(qi, block_q: int, block_k: int, window: int,
                          q_offset: int = 0):
    """First key block that can intersect the sliding window of query block
    ``qi`` (tracer-safe: ``qi`` is a pallas program_id)."""
    return jnp.maximum(0, q_offset + qi * block_q - window + 1) // block_k


def _band_bounds(causal: int, window: int):
    """The band, once: key ``k`` is visible to query ``q`` iff
    ``lo <= q_pos - k_pos <= hi`` (``None`` = unbounded).  Causal bounds it
    below at 0; a window bounds it above at ``window - 1`` and, when not
    causal (encoder local attention), symmetrically below.

    ``causal`` is a length: 1 (``True``) is the causal rule, ``L > 1`` the
    BLOCK rule (the sequence cut into blocks of ``L`` from position 0, a
    query sees keys up to the end of its own block: causal is the block rule
    at ``L = 1``).  The block rule is not a function of the difference alone:
    its lower bound is ``q_pos % L - (L - 1)``, and ``lo`` here is the
    LOOSEST one, ``-(L - 1)``, which the first query of a block has;
    :func:`_band_mask` adds each query's own ``q_pos % L`` and
    :func:`_tile_kind` knows that tiles are whole blocks."""
    lo = 1 - int(causal) if causal else (-(window - 1) if window else None)
    hi = window - 1 if window else None
    return lo, hi


def _band_mask(qi, ki, shape, block_q: int, block_k: int, causal: bool,
               window: int, q_offset: int = 0, transposed: bool = False):
    """Causal and/or sliding-window mask for one score tile, or None when
    neither applies — the ONE definition all kernels (forward and backward,
    resident and streamed) share, so forward and backward can never
    desynchronize on the band geometry.  ``shape`` is ``[block_q, block_k]``,
    or ``[block_k, block_q]`` with ``transposed`` (the resident kernels).

    ``q_offset`` (static) shifts query positions relative to key positions:
    in ring attention the q chunk starts ``j * local_seq`` tokens after the
    K/V chunk it is attending, so the sliding-window band between them is
    the same geometry translated by that constant.

    ``q_pos - k_pos`` is a loop-invariant iota difference plus one scalar
    per tile, so a masked tile costs a compare and a select per bound.  Under
    the block rule (``causal = L > 1``; tiles and ``q_offset`` are multiples
    of ``L``, so a query's place in its block is its row's) the lower bound
    moves with the query: ``q_pos - k_pos - q_pos % L >= -(L - 1)``.
    """
    lo, hi = _band_bounds(causal, window)
    if lo is None and hi is None:
        return None
    q_axis, k_axis = (1, 0) if transposed else (0, 1)
    q_row = lax.broadcasted_iota(jnp.int32, shape, q_axis)
    rel = q_row - lax.broadcasted_iota(jnp.int32, shape, k_axis)
    shift = q_offset + qi * block_q - ki * block_k
    mask = None
    if lo is not None:
        mask = (rel - q_row % int(causal) if causal > 1 else rel) >= lo - shift
    if hi is not None:
        near = rel <= hi - shift
        mask = near if mask is None else jnp.logical_and(mask, near)
    return mask


SKIP, MASKED, INTERIOR = "skip", "masked", "interior"


def _tile_kind(qi: int, ki: int, block_q: int, block_k: int, causal: bool,
               window: int, q_offset: int = 0) -> str:
    """Where tile ``(qi, ki)`` lies against the band, on Python ints (the
    resident kernels walk their tiles at trace time): ``SKIP`` if no key of
    it is visible to any query of it, ``INTERIOR`` if every key is visible
    to every query (no mask needed), ``MASKED`` if an edge of the band
    crosses it.  Interval arithmetic on ``q_pos - k_pos`` against
    :func:`_band_bounds` — the same inequality :func:`_band_mask` evaluates
    per element.  Under the block rule a tile is whole blocks, so its last
    query ends a block and sees no key past itself (SKIP is at 0 as for
    causal), while its first query starts one and sees ``L - 1`` keys ahead
    (INTERIOR from ``lo``): only the tiles the diagonal crosses differ."""
    lo, hi = _band_bounds(causal, window)
    q_lo = q_offset + qi * block_q
    d_min = q_lo - ((ki + 1) * block_k - 1)
    d_max = q_lo + block_q - 1 - ki * block_k
    none_below = 0 if causal else lo
    if (lo is not None and d_max < none_below) or (
        hi is not None and d_min > hi
    ):
        return SKIP
    if (lo is None or d_min >= lo) and (hi is None or d_max <= hi):
        return INTERIOR
    return MASKED


def _count_tiles(n_q: int, n_k: int, block_q: int, block_k: int, causal: bool,
                 window: int, q_offset: int = 0):
    """(tiles computed, tiles masked) of one row's walk."""
    kinds = [
        _tile_kind(qi, ki, block_q, block_k, causal, window, q_offset)
        for qi in range(n_q) for ki in range(n_k)
    ]
    return len(kinds) - kinds.count(SKIP), kinds.count(MASKED)


def _stream_k_range(qi, block_q, block_k, causal, window, num_ki, q_offset=0):
    """[first, last] K-block range query block ``qi`` actually needs.  Used
    by both the streamed kernels (compute predicate) and their index maps
    (DMA clamp) — they MUST agree, so it is one function.  The range may be
    empty (first > last) for offset chunks whose window misses every key
    block; callers must clamp before using it as an index."""
    if causal:
        last = ((qi + 1) * block_q - 1) // block_k
    elif window:
        # symmetric band: the largest visible key is q_max + window - 1
        last = jnp.minimum(
            num_ki - 1,
            (q_offset + (qi + 1) * block_q - 1 + window - 1) // block_k,
        )
    else:
        last = num_ki - 1
    first = (
        _window_first_k_block(qi, block_q, block_k, window, q_offset)
        if window
        else 0
    )
    return first, last


def _stream_q_range(ki, block_q, block_k, causal, window, num_qi, q_offset=0):
    """[first, last] Q-block range that sees key block ``ki`` — the q-side
    mirror of :func:`_stream_k_range`, shared by the streamed dkv kernel's
    compute predicate and its index maps for the same must-agree reason.
    May be empty (last < first) — see _stream_k_range."""
    if causal:
        first = ki * block_k // block_q
    elif window:
        # symmetric band: the smallest query seeing key block ki is
        # k_min - window + 1 (in q-local coordinates: minus q_offset)
        first = jnp.maximum(0, ki * block_k - window + 1 - q_offset) // block_q
    else:
        first = 0
    if window:
        # queries beyond (k_block_end + window - 1) see none of this block
        # (-(-x // y) is a tracer-safe ceil); q_offset shifts the band
        last = jnp.minimum(
            num_qi - 1,
            -(-((ki + 1) * block_k + window - q_offset - 1) // block_q) - 1,
        )
    else:
        last = num_qi - 1
    return first, last


def _use_stream(rows: int, tiles: int, stream: Optional[bool]) -> bool:
    """Streamed BACKWARD kernels for a row too long to hold in VMEM, or a
    walk of more tile bodies than a kernel should unroll; an explicit
    ``stream`` wins."""
    if stream is not None:
        return bool(stream)
    return rows > STREAM_SEQ_THRESHOLD or tiles > MAX_STATIC_TILES


def _fwd_streams(seq: int, seq_kv: int, width: int, dtype, block_q: int,
                 block_k: int, tiles: int, stream: Optional[bool]) -> bool:
    """The FORWARD's variant, for :func:`flash_plan` and :func:`_flash_fwd`
    alike: streamed where the row's blocks at ``width`` columns and the live
    score tiles need more VMEM than ``RESIDENT_VMEM_BUDGET``, or the walk
    more tile bodies than a kernel should unroll; an explicit ``stream``
    wins."""
    if stream is not None:
        return bool(stream)
    need = _resident_need(
        _fwd_row_bytes(seq, seq_kv, width, dtype), 0, block_q, block_k
    )
    return need > RESIDENT_VMEM_BUDGET or tiles > MAX_STATIC_TILES


def _rows_can_be_empty(causal: bool, q_offset: int) -> bool:
    """Static: can a query row have no visible key?  Not in causal
    self-attention (a row sees itself, in any segment and any window); in a
    non-causal or offset chunk a window or a segment can miss every key."""
    return not causal or q_offset != 0


def _derive_tile(seq: int, preferred: int) -> Optional[int]:
    """Largest tile <= ``preferred`` that is a multiple of 128 and divides
    ``seq`` (grown again while it would cut the row into more than
    ``MAX_TILES_PER_SIDE`` pieces); a row shorter than 128 is one tile.
    None when nothing divides: the caller takes the dense path, loudly."""
    if seq < 128:
        return seq
    tile = preferred
    while tile > 128 and seq % tile:
        tile //= 2
    if seq % tile:
        return None
    while seq // tile > MAX_TILES_PER_SIDE and seq % (2 * tile) == 0:
        tile *= 2
    return tile


def _preferred_tiles(head_dim: int, rows: int) -> Tuple[int, int]:
    """(forward, backward) square tile of the resident kernels, from two
    sweeps on a TPU v5e (bf16; PERF.md section 6).  The backward takes 256
    at every width (PR 27: rows of 1024 and 2048, tiles 128-1024 each way).
    The forward (PR 48: heads of 64, 128, 192 and 256, rows of 128 to 8192,
    causal, windowed and under the block rule, one K/V head a group or one a
    head) takes 128 at a head width of exactly 128 on rows past 512 and 512
    everywhere else.  At 128 columns the score matmul is one full pass of the
    matrix unit and the kernel is bound by it at any tile, so the tile that
    computes least beyond the diagonal wins (1024 to 3072 rows: 128 reads
    55-87% of the least time, 512 16-35% more milliseconds).  At every other
    width the walk pays by the tile BODY (64 columns leave the matrix unit
    half empty, 192 cost it a second, half-empty pass and lay no operand on
    the 128 lanes): 512 reads 48-67% where 128 reads 29-34% (192 columns,
    whatever the group), and the same at 64 (4096 rows: 40% against 20% at
    256).  A row of at most 512 is ONE tile at every width (one body beats
    three or ten of a smaller tile by 10-20%).  The group, the rule and a
    window move nothing: the key is the width and the row."""
    return (128 if head_dim == 128 and rows > 512 else 512), 256


def flash_plan(
    seq: int,
    head_dim: int,
    group: int = 1,
    dtype=jnp.bfloat16,
    *,
    seq_kv: Optional[int] = None,
    causal: bool = True,
    window: int = 0,
    q_offset: int = 0,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    stream: Optional[bool] = None,
) -> Optional[dict]:
    """What the kernels will do for this shape: the tile of each pass, which
    variant runs it, and how many tiles of one row's walk are computed and
    how many of those are masked.  ``None`` when no tile divides the row.

    Derived tiles (``block_q`` / ``block_k`` None) are
    :func:`_preferred_tiles` cut to what divides the row; an explicit tile is
    used for both passes.  The forward's variant follows the bytes of the
    row's blocks at ``head_dim`` columns of ``dtype`` (:func:`_fwd_streams`;
    a caller with two widths gives the larger), the backward's the row count
    (:func:`_use_stream`); the tiles were measured in bf16 and fp32 operands
    take the same.
    """
    seq_kv = seq if seq_kv is None else seq_kv
    fwd_pref, bwd_pref = _preferred_tiles(head_dim, max(seq, seq_kv))
    block = int(causal) if causal > 1 else 0  # the block rule's length
    if block and (window or q_offset % block):
        raise ValueError(
            f"the block rule (blocks of {block}) takes no window and an "
            f"offset of whole blocks (window={window}, q_offset={q_offset})"
        )
    plan = {"rule": "block" if block else "causal" if causal else "full"}
    if block:
        plan["block_len"] = block
    bwd_rows = max(seq_kv, group * seq)
    # the streamed kernels mask every tile and pay every grid step: the
    # larger tile they always ran with (the forward streamed for its bytes
    # or its bodies has a tile of 512 or more already)
    if stream:
        fwd_pref = 512
    if _use_stream(bwd_rows, 0, stream):
        bwd_pref = 512
    for name, pref in (("fwd", fwd_pref), ("bwd", bwd_pref)):
        bq = _derive_tile(seq, pref) if block_q is None else min(block_q, seq)
        bk = _derive_tile(seq_kv, pref) if block_k is None else min(block_k, seq_kv)
        if bq is None or bk is None or seq % bq or seq_kv % bk:
            return None
        if block and (bq % block or bk % block):
            return None  # a tile has to be whole blocks
        computed, masked = _count_tiles(
            seq // bq, seq_kv // bk, bq, bk, causal, window, q_offset
        )
        if name == "fwd":
            streamed = _fwd_streams(
                seq, seq_kv, head_dim, dtype, bq, bk, computed, stream
            )
        else:
            streamed = _use_stream(bwd_rows, group * computed, stream)
        plan[name] = {
            "block_q": bq,
            "block_k": bk,
            "variant": "streamed" if streamed else "resident",
            "tiles_computed": computed,
            # the streamed kernels mask every tile they compute
            "tiles_masked": computed if streamed and (causal or window) else masked,
        }
    plan["fused_bwd"] = plan["bwd"]["variant"] == "resident"
    return plan


def _padded_bytes(rows: int, cols: int, dtype) -> int:
    """VMEM bytes of a [rows, cols] block: lanes pad to 128, sublanes to a
    32-byte word's worth of rows."""
    itemsize = jnp.dtype(dtype).itemsize
    sub = 8 * max(1, 4 // itemsize)
    return -(-rows // sub) * sub * -(-cols // 128) * 128 * itemsize


def _fwd_row_bytes(seq: int, seq_kv: int, width: int, dtype) -> int:
    """VMEM bytes of the resident forward's four blocks of one row: q and
    the output, k and v, each counted at ``width`` columns."""
    return 2 * _padded_bytes(seq, width, dtype) + 2 * _padded_bytes(
        seq_kv, width, dtype
    )


def _resident_need(block_bytes: int, scratch_bytes: int, block_q: int,
                   block_k: int) -> int:
    """VMEM bytes a resident kernel needs: its blocks (double-buffered), its
    scratch and a handful of live fp32 score tiles."""
    return 2 * block_bytes + scratch_bytes + 12 * block_q * block_k * 4


def _resident_params(interpret: bool, block_bytes: int, scratch_bytes: int,
                     block_q: int, block_k: int):
    """Compiler parameters of a resident kernel: rows are independent, and
    the VMEM limit follows :func:`_resident_need` instead of the 16 MiB
    default that a 4096-long row outgrows."""
    if interpret:
        return {}
    need = _resident_need(block_bytes, scratch_bytes, block_q, block_k)
    limit = min(max(need * 5 // 4, 32 << 20), 100 << 20)
    return {
        "compiler_params": pltpu.CompilerParams(
            dimension_semantics=("parallel",), vmem_limit_bytes=limit
        )
    }


def _stream_kv_map(kv_row, block_q, block_k, causal, window, num_ki, q_offset):
    """Index map for streamed K/V (and seg-k) BlockSpecs on a (bh, qi, ki)
    grid: clamps ki into the needed range so out-of-band grid steps re-map
    to an already-fetched block (no DMA).  ONE builder shared by the forward
    and dq kernels' pallas_calls — their fetch patterns must agree with the
    kernels' _stream_k_range compute predicate."""

    def kv_map(bh_, qi, ki):
        first, last = _stream_k_range(
            qi, block_q, block_k, causal, window, num_ki, q_offset
        )
        # negative q_offset (ahead ring chunks) can drive `last` below 0 for
        # early q blocks; the index map must stay in bounds — compute is
        # predicated off for those steps anyway
        last = jnp.clip(last, 0, num_ki - 1)
        return (kv_row(bh_), jnp.clip(ki, jnp.minimum(first, last), last), 0)

    return kv_map


# --- forward kernels ----------------------------------------------------------


def _finalize_rows(acc, m, l, o_ref, lse_ref, causal):
    """Write out/lse from online-softmax state.  Causal rows always see at
    least themselves (l > 0); an offset-window ring chunk can leave rows
    with NO visible keys — those must emit the empty-partial contract
    (out = 0, lse = NEG_INF) instead of 0/0 = nan."""
    if causal:
        o_ref[0] = (acc / l).astype(o_ref.dtype)
        # log-sum-exp per query row, needed by the backward pass.  Kept as a
        # trailing length-1 lane dim: TPU blocks need the last two dims to be
        # (8k, 128k) or full — [block_q, 1] against a [bh, s, 1] array is
        # legal, [1, block_q] against [bh, s] is not.
        lse_ref[0] = m + jnp.log(l)
    else:
        empty = l <= 0.0
        o_ref[0] = jnp.where(
            empty, 0.0, acc / jnp.where(empty, 1.0, l)
        ).astype(o_ref.dtype)
        lse_ref[0] = jnp.where(
            empty, NEG_INF, m + jnp.log(jnp.where(empty, 1.0, l))
        )


def _tile_mask(kind, qi, ki, seg_q, seg_k, block_q, block_k, causal, window,
               q_offset):
    """Mask of one TRANSPOSED ``[block_k, block_q]`` tile of a resident
    kernel, or None: the band only where an edge crosses the tile, the
    same-segment test on every tile of a packed batch."""
    mask = None
    if kind == MASKED:
        mask = _band_mask(qi, ki, (block_k, block_q), block_q, block_k, causal,
                          window, q_offset, transposed=True)
    if seg_q is not None:
        same = seg_k == seg_q  # [bk, 1] == [1, bq]
        mask = same if mask is None else jnp.logical_and(mask, same)
    return mask


def _fwd_kernel(
    q_ref, k_ref, v_ref, *rest, block_q, block_k, scale, has_segments,
    causal=True, window=0, q_offset=0, can_be_empty=False,
):
    """Resident forward: one grid step is one (batch, head) row.  The tile
    walk is static; per q tile the online softmax runs on transposed score
    tiles ``s_t = k q^T`` so its state is lane-dense (``m``, ``l``:
    ``[1, block_q]``; ``acc_t``: ``[head_dim, block_q]``)."""
    if has_segments:
        # separate q- and k-side segment refs: for self-attention both view
        # the same ids; ring chunks pass the local chunk's ids (as rows,
        # [n_q, 1, block_q]) vs the rotating chunk's ([s_kv, 1])
        seg_q_ref, seg_k_ref, o_ref, lse_ref = rest
    else:
        o_ref, lse_ref = rest
    d = v_ref.shape[-1]  # the value width (the keys' may differ)
    for qi in range(q_ref.shape[1] // block_q):
        rows = slice(qi * block_q, (qi + 1) * block_q)
        # keep MXU operands in the input dtype (bf16 on TPU: full MXU rate)
        # and accumulate fp32 via preferred_element_type; fp32 operands
        # would run the systolic array at a fraction of peak
        q = (q_ref[0, rows, :] * jnp.asarray(scale, q_ref.dtype)).astype(q_ref.dtype)
        seg_q = seg_q_ref[0, qi] if has_segments else None  # [1, bq]
        acc_t = jnp.zeros((d, block_q), jnp.float32)
        m = jnp.full((1, block_q), NEG_INF, jnp.float32)
        l = jnp.zeros((1, block_q), jnp.float32)
        for ki in range(k_ref.shape[1] // block_k):
            kind = _tile_kind(qi, ki, block_q, block_k, causal, window, q_offset)
            if kind == SKIP:
                continue
            cols = slice(ki * block_k, (ki + 1) * block_k)
            k = k_ref[0, cols, :]
            v = v_ref[0, cols, :]
            s_t = lax.dot_general(k, q, _NT, preferred_element_type=jnp.float32)
            mask = _tile_mask(
                kind, qi, ki, seg_q, seg_k_ref[0, cols, :] if has_segments else None,
                block_q, block_k, causal, window, q_offset,
            )
            if mask is not None:
                s_t = jnp.where(mask, s_t, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s_t, axis=0, keepdims=True))
            alpha = jnp.exp(m - m_new)
            p_t = jnp.exp(s_t - m_new)
            l = l * alpha + jnp.sum(p_t, axis=0, keepdims=True)
            acc_t = acc_t * alpha + lax.dot_general(
                v, p_t.astype(v.dtype), _TN, preferred_element_type=jnp.float32
            )  # v^T p^T: [d, bq]
            m = m_new
        # Causal self-attention rows always see themselves (l > 0); an
        # offset-window or cross-segment chunk can leave rows with NO visible
        # key — those emit the empty-partial contract (out = 0, lse =
        # NEG_INF) instead of 0/0 = nan.  The guard is static.
        if can_be_empty:
            empty = l <= 0.0
            safe_l = jnp.where(empty, 1.0, l)
            out_t = jnp.where(empty, 0.0, acc_t / safe_l)
            lse = jnp.where(empty, NEG_INF, m + jnp.log(safe_l))
        else:
            out_t = acc_t / l
            lse = m + jnp.log(l)
        o_ref[0, rows, :] = out_t.T.astype(o_ref.dtype)
        # log-sum-exp per query, needed by the backward pass; stored as
        # lane-dense rows [bh, n_q, 1, block_q] ([bh, s, 1] would pad every
        # value to a 128-lane word in VMEM and in HBM)
        lse_ref[0, qi] = lse


def _fwd_kernel_stream(
    q_ref, k_ref, v_ref, *rest, block_q, block_k, scale, has_segments,
    causal, window, num_ki, q_offset=0,
):
    """Streamed forward: grid (bh, qi, ki); online-softmax state lives in
    fp32 VMEM scratch carried across the ki grid dimension."""
    if has_segments:
        seg_q_ref, seg_k_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref = rest
    else:
        o_ref, lse_ref, acc_ref, m_ref, l_ref = rest
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    first, last = _stream_k_range(
        qi, block_q, block_k, causal, window, num_ki, q_offset
    )
    # the block the index map actually fetched (clamped copy of ki; the
    # range can be empty — min keeps the fetch index in bounds, the
    # compute predicate below keeps the empty range compute-free)
    kf = jnp.clip(ki, jnp.minimum(first, last), last)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when((ki >= first) & (ki <= last))
    def _compute():
        q = (q_ref[0] * jnp.asarray(scale, q_ref.dtype)).astype(q_ref.dtype)
        k = k_ref[0]  # [block_k, d] — block kf
        v = v_ref[0]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)
        mask = _band_mask(qi, kf, s.shape, block_q, block_k, causal, window,
                          q_offset)
        if has_segments:
            same = seg_q_ref[0] == seg_k_ref[0].T  # [bq, bk]
            mask = same if mask is None else jnp.logical_and(mask, same)
        if mask is not None:
            s = jnp.where(mask, s, NEG_INF)
        m_prev, l_prev = m_ref[...], l_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_ref[...] = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32
        )
        m_ref[...] = m_new

    @pl.when(ki == num_ki - 1)
    def _finalize():
        _finalize_rows(acc_ref[...], m_ref[...], l_ref[...], o_ref, lse_ref,
                       causal)


def _flash_fwd(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    seg_q: Optional[jax.Array],
    seg_k: Optional[jax.Array],
    *,
    block_q: int,
    block_k: int,
    interpret: bool,
    causal: bool = True,
    window: int = 0,
    stream: Optional[bool] = None,
    q_offset: int = 0,
) -> Tuple[jax.Array, jax.Array]:
    (b, h, s, d), e = q.shape, v.shape[-1]  # e: the width of a value
    h_kv, s_kv = k.shape[1], k.shape[2]
    scale = 1.0 / (d**0.5)
    bh = b * h
    qf = q.reshape(bh, s, d)
    kf = k.reshape(b * h_kv, s_kv, d)
    vf = v.reshape(b * h_kv, s_kv, e)
    kv_row = _kv_row_map(h, h_kv)
    kernel_kwargs = dict(
        block_q=block_q,
        block_k=block_k,
        scale=scale,
        has_segments=seg_q is not None,
        causal=causal,
        window=window,
        q_offset=q_offset,
    )
    n_q = s // block_q
    tiles, _ = _count_tiles(
        n_q, s_kv // block_k, block_q, block_k, causal, window, q_offset
    )
    if _fwd_streams(s, s_kv, max(d, e), q.dtype, block_q, block_k, tiles, stream):
        num_ki = s_kv // block_k
        kv_map = _stream_kv_map(
            kv_row, block_q, block_k, causal, window, num_ki, q_offset
        )

        in_specs = [
            pl.BlockSpec((1, block_q, d), lambda bh_, qi, ki: (bh_, qi, 0)),
            pl.BlockSpec((1, block_k, d), kv_map),
            pl.BlockSpec((1, block_k, e), kv_map),
        ]
        args = [qf, kf, vf]
        if seg_q is not None:
            # [B, S_q, 1] q-block view and [B, S_kv, 1] (clamped) k-block
            # view; for self-attention both are the same array
            in_specs.append(
                pl.BlockSpec((1, block_q, 1), lambda bh_, qi, ki: (bh_ // h, qi, 0))
            )
            in_specs.append(
                pl.BlockSpec(
                    (1, block_k, 1),
                    lambda bh_, qi, ki: (bh_ // h,)
                    + kv_map(bh_, qi, ki)[1:],
                )
            )
            args += [seg_q, seg_k]
        out, lse = pl.pallas_call(
            functools.partial(_fwd_kernel_stream, num_ki=num_ki, **kernel_kwargs),
            grid=(bh, s // block_q, num_ki),
            in_specs=in_specs,
            out_specs=[
                pl.BlockSpec((1, block_q, e), lambda bh_, qi, ki: (bh_, qi, 0)),
                pl.BlockSpec((1, block_q, 1), lambda bh_, qi, ki: (bh_, qi, 0)),
            ],
            out_shape=[
                _sds((bh, s, e), q.dtype, qf),
                _sds((bh, s, 1), jnp.float32, qf),
            ],
            scratch_shapes=[
                pltpu.VMEM((block_q, e), jnp.float32),
                pltpu.VMEM((block_q, 1), jnp.float32),
                pltpu.VMEM((block_q, 1), jnp.float32),
            ],
            interpret=interpret,
        )(*args)
        return out.reshape(b, h, s, e), lse.reshape(b, h, s)

    # resident: one grid step per (batch, head) row; consecutive heads of a
    # GQA group map to the same K/V block, which Pallas does not fetch twice
    in_specs = [
        pl.BlockSpec((1, s, d), lambda bh_: (bh_, 0, 0)),
        pl.BlockSpec((1, s_kv, d), lambda bh_: (kv_row(bh_), 0, 0)),
        pl.BlockSpec((1, s_kv, e), lambda bh_: (kv_row(bh_), 0, 0)),
    ]
    args = [qf, kf, vf]
    block_bytes = _fwd_row_bytes(s, s_kv, max(d, e), q.dtype)
    if seg_q is not None:
        # all H heads of batch row b read the same ids: the q side as
        # lane-dense rows, one per q tile, the k side as a [S_kv, 1] column
        in_specs.append(
            pl.BlockSpec((1, n_q, 1, block_q), lambda bh_: (bh_ // h, 0, 0, 0))
        )
        in_specs.append(pl.BlockSpec((1, s_kv, 1), lambda bh_: (bh_ // h, 0, 0)))
        args += [seg_q.reshape(b, n_q, 1, block_q), seg_k]
        block_bytes += _padded_bytes(s_kv, 1, jnp.int32)
    out, lse = pl.pallas_call(
        functools.partial(
            _fwd_kernel, can_be_empty=_rows_can_be_empty(causal, q_offset),
            **kernel_kwargs,
        ),
        grid=(bh,),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, s, e), lambda bh_: (bh_, 0, 0)),
            pl.BlockSpec((1, n_q, 1, block_q), lambda bh_: (bh_, 0, 0, 0)),
        ],
        out_shape=[
            _sds((bh, s, e), q.dtype, qf),
            _sds((bh, n_q, 1, block_q), jnp.float32, qf),
        ],
        interpret=interpret,
        **_resident_params(interpret, block_bytes, 0, block_q, block_k),
    )(*args)
    return out.reshape(b, h, s, e), lse.reshape(b, h, s)


# --- backward kernels ---------------------------------------------------------


def _bwd_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rest,
    block_q, block_k, scale, seq_len, group, has_segments, causal=True,
    window=0, q_offset=0, can_be_empty=False,
):
    """Resident backward, ONE pass: grid (b*h_kv,), one step per K/V head's
    row.  Each tile of the band is visited once and from one transposed score
    tile ``s_t = k q^T``, one ``p_t = exp(s_t - lse)`` and one ``dp_t = v
    do^T`` come ``dv += p_t do``, ``dk += ds_t q`` and ``dq_t += k^T ds_t``
    (5 matmuls; ``lse`` / ``delta`` are lane-dense ``[1, block_q]`` rows).
    ``dk`` / ``dv`` of a key tile accumulate as values over its q tiles;
    ``dq`` accumulates transposed in the fp32 scratch ``dq_acc``
    ``[group * n_q, head_dim, block_q]`` and is written once at the end.

    Under GQA (group > 1) the q/do/lse/delta operands arrive reshaped to
    ``[b*h_kv, group*seq, ...]`` and the walk repeats per query head of the
    group, summing into the same ``dk`` / ``dv`` — the reduction over the
    group happens here, not via an expanded K/V."""
    if has_segments:
        seg_q_ref, seg_k_ref, dq_ref, dk_ref, dv_ref, dq_acc = rest
    else:
        dq_ref, dk_ref, dv_ref, dq_acc = rest
    d = k_ref.shape[-1]
    n_q = seq_len // block_q
    started = set()  # dq tiles that hold a partial sum already (static)
    for ki in range(k_ref.shape[1] // block_k):
        cols = slice(ki * block_k, (ki + 1) * block_k)
        k = k_ref[0, cols, :]
        v = v_ref[0, cols, :]
        k_t = k.T  # [d, bk], once per key tile
        seg_k = seg_k_ref[0, cols, :] if has_segments else None
        dk = jnp.zeros((block_k, d), jnp.float32)
        dv = jnp.zeros((block_k, d), jnp.float32)
        for g in range(group):  # one walk per query head in the group
            for qi in range(n_q):
                kind = _tile_kind(qi, ki, block_q, block_k, causal, window,
                                  q_offset)
                if kind == SKIP:
                    continue
                t = g * n_q + qi
                rows = slice(t * block_q, (t + 1) * block_q)
                q = (
                    q_ref[0, rows, :] * jnp.asarray(scale, q_ref.dtype)
                ).astype(q_ref.dtype)
                do = do_ref[0, rows, :]
                lse = lse_ref[0, t]  # [1, bq]
                s_t = lax.dot_general(
                    k, q, _NT, preferred_element_type=jnp.float32
                )  # [bk, bq]
                mask = _tile_mask(
                    kind, qi, ki, seg_q_ref[0, qi] if has_segments else None,
                    seg_k, block_q, block_k, causal, window, q_offset,
                )
                if mask is not None:
                    s_t = jnp.where(mask, s_t, NEG_INF)
                p_t = jnp.exp(s_t - lse)
                if can_be_empty and mask is not None:
                    # empty rows (lse == NEG_INF) must contribute zero:
                    # exp(s - lse) is exp(0) = 1 on their masked entries.
                    # A row of an unmasked tile sees keys, so is not empty.
                    p_t = jnp.where(lse <= NEG_INF / 2, 0.0, p_t)
                dv = dv + jnp.dot(
                    p_t.astype(do.dtype), do, preferred_element_type=jnp.float32
                )
                dp_t = lax.dot_general(
                    v, do, _NT, preferred_element_type=jnp.float32
                )
                ds_t = (p_t * (dp_t - delta_ref[0, t])).astype(q.dtype)
                dk = dk + jnp.dot(ds_t, q, preferred_element_type=jnp.float32)
                dq_t = jnp.dot(k_t, ds_t, preferred_element_type=jnp.float32)
                dq_acc[t] = dq_acc[t] + dq_t if t in started else dq_t
                started.add(t)
        # q was pre-scaled, so dk already carries one factor of `scale`
        dk_ref[0, cols, :] = dk.astype(dk_ref.dtype)
        dv_ref[0, cols, :] = dv.astype(dv_ref.dtype)
    for t in range(group * n_q):
        rows = slice(t * block_q, (t + 1) * block_q)
        if t in started:
            dq_ref[0, rows, :] = (dq_acc[t].T * scale).astype(dq_ref.dtype)
        else:  # a q tile whose window misses the whole chunk
            dq_ref[0, rows, :] = jnp.zeros((block_q, d), dq_ref.dtype)


def _bwd_dq_kernel_stream(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rest,
    block_q, block_k, scale, has_segments, causal, window, num_ki, q_offset=0,
):
    """Streamed dq: grid (bh, qi, ki); fp32 dq accumulator in scratch."""
    if has_segments:
        seg_q_ref, seg_k_ref, dq_ref, dq_acc_ref = rest
    else:
        dq_ref, dq_acc_ref = rest
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    first, last = _stream_k_range(
        qi, block_q, block_k, causal, window, num_ki, q_offset
    )
    kf = jnp.clip(ki, jnp.minimum(first, last), last)

    @pl.when(ki == 0)
    def _init():
        dq_acc_ref[...] = jnp.zeros_like(dq_acc_ref)

    @pl.when((ki >= first) & (ki <= last))
    def _compute():
        q = (q_ref[0] * jnp.asarray(scale, q_ref.dtype)).astype(q_ref.dtype)
        do = do_ref[0]
        lse = lse_ref[0]
        delta = delta_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)
        mask = _band_mask(qi, kf, s.shape, block_q, block_k, causal, window,
                          q_offset)
        if has_segments:
            same = seg_q_ref[0] == seg_k_ref[0].T
            mask = same if mask is None else jnp.logical_and(mask, same)
        if mask is not None:
            s = jnp.where(mask, s, NEG_INF)
        # empty rows (lse == NEG_INF, only in offset-window chunk mode)
        # must contribute zero: exp(s - lse) would be exp(0) = 1 on
        # their masked entries
        p = jnp.where(lse <= NEG_INF / 2, 0.0, jnp.exp(s - lse))
        dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
        ds = (p * (dp - delta)).astype(k.dtype)
        dq_acc_ref[...] = dq_acc_ref[...] + jnp.dot(
            ds, k, preferred_element_type=jnp.float32
        )

    @pl.when(ki == num_ki - 1)
    def _finalize():
        dq_ref[0] = (dq_acc_ref[...] * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel_stream(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rest,
    block_q, block_k, scale, has_segments, causal, window, group, num_qi,
    q_offset=0,
):
    """Streamed dk/dv: grid (b*h_kv, ki, g, qi).  The index maps feed the
    (g, qi) walk one [block_q, ...] tile at a time; dk/dv accumulate in fp32
    scratch across the two inner grid dims and flush once per (bkv, ki)."""
    if has_segments:
        seg_q_ref, seg_k_ref, dk_ref, dv_ref, dk_acc_ref, dv_acc_ref = rest
    else:
        dk_ref, dv_ref, dk_acc_ref, dv_acc_ref = rest
    ki = pl.program_id(1)
    g = pl.program_id(2)
    qi = pl.program_id(3)
    first_q, last_q = _stream_q_range(
        ki, block_q, block_k, causal, window, num_qi, q_offset
    )
    qf = jnp.clip(qi, first_q, jnp.maximum(last_q, first_q))

    @pl.when((g == 0) & (qi == 0))
    def _init():
        dk_acc_ref[...] = jnp.zeros_like(dk_acc_ref)
        dv_acc_ref[...] = jnp.zeros_like(dv_acc_ref)

    @pl.when((qi >= first_q) & (qi <= last_q))
    def _compute():
        k = k_ref[0]
        v = v_ref[0]
        q = (q_ref[0] * jnp.asarray(scale, q_ref.dtype)).astype(q_ref.dtype)
        do = do_ref[0]
        lse = lse_ref[0]
        delta = delta_ref[0]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)  # [bq, bk]
        mask = _band_mask(qf, ki, s.shape, block_q, block_k, causal, window,
                          q_offset)
        if has_segments:
            same = seg_q_ref[0] == seg_k_ref[0].T
            mask = same if mask is None else jnp.logical_and(mask, same)
        if mask is not None:
            s = jnp.where(mask, s, NEG_INF)
        # empty rows (lse == NEG_INF, only in offset-window chunk mode)
        # must contribute zero: exp(s - lse) would be exp(0) = 1 on
        # their masked entries
        p = jnp.where(lse <= NEG_INF / 2, 0.0, jnp.exp(s - lse))
        dv_acc_ref[...] = dv_acc_ref[...] + jnp.dot(
            p.astype(do.dtype).T, do, preferred_element_type=jnp.float32
        )
        dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
        ds = (p * (dp - delta)).astype(q.dtype)
        dk_acc_ref[...] = dk_acc_ref[...] + jnp.dot(
            ds.T, q, preferred_element_type=jnp.float32
        )

    @pl.when((g == pl.num_programs(2) - 1) & (qi == num_qi - 1))
    def _finalize():
        # q was pre-scaled, so dk already carries one factor of `scale`
        dk_ref[0] = dk_acc_ref[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc_ref[...].astype(dv_ref.dtype)


def _flash_bwd(
    q, k, v, seg_q, seg_k, out, lse, do, *, block_q, block_k, interpret,
    causal=True, window=0, dlse=None, stream: Optional[bool] = None,
    q_offset: int = 0,
):
    b, h, s, d = q.shape
    h_kv, s_kv = k.shape[1], k.shape[2]
    group = h // h_kv
    scale = 1.0 / (d**0.5)
    bh = b * h
    b_kv = b * h_kv
    delta = jnp.sum(out.astype(jnp.float32) * do.astype(jnp.float32), axis=-1)
    if dlse is not None:
        # chunked/ring combine: a nonzero cotangent on lse folds into the
        # same per-row correction the probs already use —
        # ds = p * (dp - (delta - dlse))
        delta = delta - dlse
    qf = q.reshape(bh, s, d)
    kf, vf = (x.reshape(b_kv, s_kv, d) for x in (k, v))
    has_segments = seg_q is not None
    kernel_kwargs = dict(
        block_q=block_q,
        block_k=block_k,
        scale=scale,
        has_segments=has_segments,
        causal=causal,
        window=window,
        q_offset=q_offset,
    )
    n_q = s // block_q
    tiles, _ = _count_tiles(
        n_q, s_kv // block_k, block_q, block_k, causal, window, q_offset
    )
    # the resident kernel holds [group*s, d] q/do/dq rows in VMEM and unrolls
    # group * tiles bodies, so under GQA the decision budgets for group*s,
    # not just s_kv — e.g. group=8 at s=4096 is an 8MB bf16 q row
    if not _use_stream(max(s_kv, group * s), group * tiles, stream):
        # group the query-head operands by K/V head: [b*h_kv, group*s, ...];
        # lse / delta as lane-dense rows, one per q tile
        rows = group * s
        qg = q.reshape(b_kv, rows, d)
        dog = do.reshape(b_kv, rows, d)
        lseg = lse.reshape(b_kv, group * n_q, 1, block_q)
        deltag = delta.reshape(b_kv, group * n_q, 1, block_q)
        q_spec = pl.BlockSpec((1, rows, d), lambda bkv_: (bkv_, 0, 0))
        kv_spec = pl.BlockSpec((1, s_kv, d), lambda bkv_: (bkv_, 0, 0))
        stat_spec = pl.BlockSpec(
            (1, group * n_q, 1, block_q), lambda bkv_: (bkv_, 0, 0, 0)
        )
        in_specs = [q_spec, kv_spec, kv_spec, q_spec, stat_spec, stat_spec]
        args = [qg, kf, vf, dog, lseg, deltag]
        block_bytes = 3 * _padded_bytes(rows, d, q.dtype) + 4 * _padded_bytes(
            s_kv, d, q.dtype
        )
        if has_segments:
            in_specs.append(
                pl.BlockSpec(
                    (1, n_q, 1, block_q), lambda bkv_: (bkv_ // h_kv, 0, 0, 0)
                )
            )
            in_specs.append(
                pl.BlockSpec((1, s_kv, 1), lambda bkv_: (bkv_ // h_kv, 0, 0))
            )
            args += [seg_q.reshape(b, n_q, 1, block_q), seg_k]
            block_bytes += _padded_bytes(s_kv, 1, jnp.int32)
        scratch_bytes = group * n_q * _padded_bytes(d, block_q, jnp.float32)
        dq, dk, dv = pl.pallas_call(
            functools.partial(
                _bwd_kernel, seq_len=s, group=group,
                can_be_empty=_rows_can_be_empty(causal, q_offset),
                **kernel_kwargs,
            ),
            grid=(b_kv,),
            in_specs=in_specs,
            out_specs=[q_spec, kv_spec, kv_spec],
            out_shape=[
                _sds((b_kv, rows, d), q.dtype, qf),
                _sds((b_kv, s_kv, d), q.dtype, qf),
                _sds((b_kv, s_kv, d), q.dtype, qf),
            ],
            scratch_shapes=[
                pltpu.VMEM((group * n_q, d, block_q), jnp.float32)
            ],
            interpret=interpret,
            **_resident_params(
                interpret, block_bytes, scratch_bytes, block_q, block_k
            ),
        )(*args)
        return (
            dq.reshape(b, h, s, d),
            dk.reshape(b, h_kv, s_kv, d),
            dv.reshape(b, h_kv, s_kv, d),
        )

    # ---- streamed: the dq / dkv pair ----
    dof = do.reshape(bh, s, d)
    lsef = lse.reshape(bh, s, 1)
    deltaf = delta.reshape(bh, s, 1)
    kv_row = _kv_row_map(h, h_kv)
    num_ki = s_kv // block_k
    kv_map = _stream_kv_map(
        kv_row, block_q, block_k, causal, window, num_ki, q_offset
    )

    in_specs = [
        pl.BlockSpec((1, block_q, d), lambda bh_, qi, ki: (bh_, qi, 0)),
        pl.BlockSpec((1, block_k, d), kv_map),
        pl.BlockSpec((1, block_k, d), kv_map),
        pl.BlockSpec((1, block_q, d), lambda bh_, qi, ki: (bh_, qi, 0)),
        pl.BlockSpec((1, block_q, 1), lambda bh_, qi, ki: (bh_, qi, 0)),
        pl.BlockSpec((1, block_q, 1), lambda bh_, qi, ki: (bh_, qi, 0)),
    ]
    args = [qf, kf, vf, dof, lsef, deltaf]
    if has_segments:
        in_specs.append(
            pl.BlockSpec((1, block_q, 1), lambda bh_, qi, ki: (bh_ // h, qi, 0))
        )
        in_specs.append(
            pl.BlockSpec(
                (1, block_k, 1),
                lambda bh_, qi, ki: (bh_ // h,) + kv_map(bh_, qi, ki)[1:],
            )
        )
        args += [seg_q, seg_k]
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel_stream, num_ki=num_ki, **kernel_kwargs),
        grid=(bh, s // block_q, num_ki),
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            (1, block_q, d), lambda bh_, qi, ki: (bh_, qi, 0)
        ),
        out_shape=_sds((bh, s, d), q.dtype, qf),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret,
    )(*args)

    dkv_out_specs = [
        pl.BlockSpec((1, block_k, d), lambda bh_, ki, *_: (bh_, ki, 0)),
        pl.BlockSpec((1, block_k, d), lambda bh_, ki, *_: (bh_, ki, 0)),
    ]
    dkv_out_shape = [
        _sds((b_kv, s_kv, d), q.dtype, qf),
        _sds((b_kv, s_kv, d), q.dtype, qf),
    ]
    num_qi = s // block_q

    def q_row(bkv_, g):
        if group == 1:
            return bkv_
        return (bkv_ // h_kv) * h + (bkv_ % h_kv) * group + g

    def qi_clip(ki, qi):
        first_q, last_q = _stream_q_range(
            ki, block_q, block_k, causal, window, num_qi, q_offset
        )
        # negative q_offset (ahead ring chunks) can push first_q past
        # the last block for late k blocks; keep the index in bounds —
        # those grid steps are compute-predicated off
        first_q = jnp.clip(first_q, 0, num_qi - 1)
        return jnp.clip(qi, first_q, jnp.maximum(last_q, first_q))

    def q_map(bkv_, ki, g, qi):
        return (q_row(bkv_, g), qi_clip(ki, qi), 0)

    in_specs = [
        pl.BlockSpec((1, block_q, d), q_map),
        pl.BlockSpec((1, block_k, d), lambda bkv_, ki, g, qi: (bkv_, ki, 0)),
        pl.BlockSpec((1, block_k, d), lambda bkv_, ki, g, qi: (bkv_, ki, 0)),
        pl.BlockSpec((1, block_q, d), q_map),
        pl.BlockSpec((1, block_q, 1), q_map),
        pl.BlockSpec((1, block_q, 1), q_map),
    ]
    args = [qf, kf, vf, dof, lsef, deltaf]
    if has_segments:
        in_specs.append(
            pl.BlockSpec(
                (1, block_q, 1),
                lambda bkv_, ki, g, qi: (bkv_ // h_kv, qi_clip(ki, qi), 0),
            )
        )
        in_specs.append(
            pl.BlockSpec(
                (1, block_k, 1),
                lambda bkv_, ki, g, qi: (bkv_ // h_kv, ki, 0),
            )
        )
        args += [seg_q, seg_k]
    dk, dv = pl.pallas_call(
        functools.partial(
            _bwd_dkv_kernel_stream, group=group, num_qi=num_qi, **kernel_kwargs
        ),
        grid=(b_kv, s_kv // block_k, group, num_qi),
        in_specs=in_specs,
        out_specs=dkv_out_specs,
        out_shape=dkv_out_shape,
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        interpret=interpret,
    )(*args)

    return (
        dq.reshape(b, h, s, d),
        dk.reshape(b, h_kv, s_kv, d),
        dv.reshape(b, h_kv, s_kv, d),
    )


# --- public API with custom VJP ----------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9, 10, 11))
def _flash_finalize(
    q, k, v, seg_q, seg_k, out, lse, block_q, block_k, interpret, window, stream
):
    """Identity on ``out``; exists to attach the backward kernels
    (``block_q`` / ``block_k`` are the BACKWARD's tiles).

    The forward kernel runs *outside* this custom_vjp (see
    ``_flash_attention_bhsd``) so its outputs are ordinary named values in
    the surrounding jaxpr: a ``save_only_these_names(..., "attn")`` remat
    policy can then keep them, and the backward never re-runs the forward
    kernel.  Residuals hidden inside a custom_vjp are invisible to remat
    policies: the forward kernel would then run again for every layer
    in the backward pass.
    """
    del q, k, v, seg_q, seg_k, lse
    return out


def _finalize_fwd(q, k, v, seg_q, seg_k, out, lse, block_q, block_k, interpret,
                  window, stream):
    return out, (q, k, v, seg_q, seg_k, out, lse)


def _finalize_bwd(block_q, block_k, interpret, window, stream, residuals, do):
    q, k, v, seg_q, seg_k, out, lse = residuals
    dq, dk, dv = _flash_bwd(
        q, k, v, seg_q, seg_k, out, lse, do,
        block_q=block_q, block_k=block_k, interpret=interpret, window=window,
        stream=stream,
    )
    # segment ids (int) carry no gradient; out/lse arrive behind
    # stop_gradient, so their zero cotangents are discarded by the caller
    return dq, dk, dv, None, None, jnp.zeros_like(out), jnp.zeros_like(lse)


_flash_finalize.defvjp(_finalize_fwd, _finalize_bwd)


def _flash_attention_bhsd(q, k, v, seg, fwd_tile, bwd_tile, interpret,
                          window=0, stream=None):
    from jax.ad_checkpoint import checkpoint_name

    # self-attention: q and k index the same positions, so one segment
    # array serves both sides of the kernels' (seg_q, seg_k) contract
    # stop_gradient on the *inputs*: the forward kernel then sees all-zero
    # tangents and AD bypasses it entirely (all q/k/v gradient flows through
    # _flash_finalize's backward kernels).  Stopping only the outputs is too
    # late — JVP would still trace into the pallas forward kernel.
    out, lse = _flash_fwd(
        lax.stop_gradient(q),
        lax.stop_gradient(k),
        lax.stop_gradient(v),
        seg,
        seg,
        block_q=fwd_tile[0],
        block_k=fwd_tile[1],
        interpret=interpret,
        window=window,
        stream=stream,
    )
    out = checkpoint_name(out, "attn")
    lse = checkpoint_name(lse, "attn")
    return _flash_finalize(
        q, k, v, seg, seg, out, lse, bwd_tile[0], bwd_tile[1], interpret,
        window, stream
    )


# --- chunk attention for ring/sequence parallelism ---------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9, 10, 11, 12, 13))
def _chunk_finalize(
    q, k, v, seg_q, seg_k, out, lse, causal, block_q, block_k, interpret,
    stream, window, q_offset
):
    """Identity on ``(out, lse)``; attaches the chunk backward kernels.

    Same layout as :func:`_flash_finalize`: the forward kernel runs OUTSIDE
    this custom_vjp (on stop_gradient inputs) so its outputs are ordinary
    named jaxpr values — a ``save_only_these_names(..., "attn")`` remat
    policy keeps them and the backward (ring steps, bidirectional encoders)
    never re-runs the forward kernel.  Unlike _flash_finalize, ``lse`` stays
    a differentiable output: ring's combine_chunks needs its cotangent.
    """
    del q, k, v, seg_q, seg_k
    return out, lse


def _chunk_finalize_fwd(q, k, v, seg_q, seg_k, out, lse, causal, block_q,
                        block_k, interpret, stream, window, q_offset):
    return (out, lse), (q, k, v, seg_q, seg_k, out, lse)


def _chunk_finalize_bwd(causal, block_q, block_k, interpret, stream, window,
                        q_offset, residuals, cotangents):
    q, k, v, seg_q, seg_k, out, lse = residuals
    do, dlse = cotangents
    dq, dk, dv = _flash_bwd(
        q, k, v, seg_q, seg_k, out, lse, do,
        block_q=block_q, block_k=block_k, interpret=interpret,
        causal=causal, dlse=dlse, stream=stream,
        window=window, q_offset=q_offset,
    )
    # seg ids carry no gradient; out/lse arrive behind stop_gradient
    return dq, dk, dv, None, None, jnp.zeros_like(out), jnp.zeros_like(lse)


_chunk_finalize.defvjp(_chunk_finalize_fwd, _chunk_finalize_bwd)


def _chunk_attention_bhsd(
    q, k, v, seg_q, seg_k, causal, fwd_tile, bwd_tile, interpret, stream,
    window, q_offset
):
    from jax.ad_checkpoint import checkpoint_name

    out, lse = _flash_fwd(
        lax.stop_gradient(q),
        lax.stop_gradient(k),
        lax.stop_gradient(v),
        seg_q, seg_k,
        block_q=fwd_tile[0], block_k=fwd_tile[1],
        interpret=interpret, causal=causal, stream=stream,
        window=window, q_offset=q_offset,
    )
    out = checkpoint_name(out, "attn")
    lse = checkpoint_name(lse, "attn")
    return _chunk_finalize(
        q, k, v, seg_q, seg_k, out, lse, causal, bwd_tile[0], bwd_tile[1],
        interpret, stream, window, q_offset
    )


def flash_chunk_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    interpret: Optional[bool] = None,
    stream: Optional[bool] = None,
    window: int = 0,
    q_offset: int = 0,
    segment_ids_q: Optional[jax.Array] = None,
    segment_ids_kv: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array]:
    """One flash-attention partial over a K/V chunk, for ring combining.

    ``q, k, v``: [batch, seq_q, heads, head_dim] / [batch, seq_kv, ...].
    Returns ``(out, lse)`` with ``out`` [batch, seq_q, heads, head_dim]
    normalized *within the chunk* and ``lse`` [batch, heads, seq_q] its
    log-sum-exp; partials from different chunks combine exactly via
    :func:`tpu_parallel.ops.ring_attention.combine_chunks`.  Differentiable
    in both outputs — the lse cotangent folds into the backward kernel's
    delta correction, which is what makes the combine's gradient exact.

    Same kernels as :func:`flash_attention`: the resident pair (static tile
    walk, ONE backward pass with ``dq`` in an fp32 VMEM scratch) while the
    forward's blocks fit VMEM and the backward's rows are at most
    ``STREAM_SEQ_THRESHOLD``, the streamed ones beyond.  ``block_q`` /
    ``block_k`` None derives each pass's tiles from the chunk lengths
    (:func:`_preferred_tiles`).  The ``lse <= NEG_INF / 2`` guard for rows with no
    visible key is compiled in exactly where such rows can exist
    (``causal=False`` or ``q_offset != 0``): a static condition.

    ``causal=True`` is the diagonal chunk of a sequence-sharded causal
    attention (q and k index the same positions); ``causal=False`` is a
    fully-visible (strictly-past) chunk.

    ``window``/``q_offset`` (both static) add a banded mask over global
    positions (query i sits at ``q_offset + i`` relative to the chunk's
    keys).  With ``causal=True`` the band is one-sided (key j visible iff
    ``q_offset + i - j < window``, Mistral semantics); with
    ``causal=False`` it is SYMMETRIC — ``|q_offset + i - j| < window`` —
    the encoder local-attention form.  Ring attention passes SIGNED
    ``q_offset = j * local_seq``: positive for chunks behind the queries
    (the symmetric upper side is vacuous there), NEGATIVE for chunks ahead
    (bidirectional rings — the upper side binds).  Rows whose window misses
    the whole chunk come back as empty partials (out 0, lse NEG_INF),
    which :func:`combine_chunks` weights to zero.

    ``segment_ids_q``/``segment_ids_kv`` ([batch, seq_q] / [batch, seq_kv],
    both or neither) mask packed sequences across chunks: queries attend
    only same-segment keys.  Ring attention passes the local chunk's ids as
    the q side and the currently-held (rotated) chunk's ids as the kv side.
    A row whose segment matches nothing in the chunk is an empty partial,
    handled as above.
    """
    if (segment_ids_q is None) != (segment_ids_kv is None):
        raise ValueError(
            "segment_ids_q and segment_ids_kv must be passed together"
        )
    if q.shape[2] % k.shape[2] != 0:
        raise ValueError(
            f"q heads {q.shape[2]} not a multiple of k/v heads {k.shape[2]}"
        )
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    # exact-divisor tiles: a grid of s // bq with s % bq != 0 would leave
    # query rows unwritten and key rows unattended — silent corruption, not
    # an error.  gcd shrinks to the largest legal tile; warn when it bites.
    import math

    s_q, s_kv, d = q.shape[1], k.shape[1], q.shape[3]
    tiles = []
    for preferred in _preferred_tiles(d, max(s_q, s_kv)):  # forward, backward
        want_q = block_q or _derive_tile(s_q, preferred) or preferred
        want_k = block_k or _derive_tile(s_kv, preferred) or preferred
        bq = math.gcd(s_q, min(want_q, s_q))
        bk = math.gcd(s_kv, min(want_k, s_kv))
        if causal:
            bk = math.gcd(bq, bk)  # the diagonal chunk: block_q % block_k == 0
        if bq < min(want_q, s_q) or bk < min(want_k, s_kv):
            warnings.warn(
                f"flash_chunk_attention shrank tiles to {bq}x{bk}: chunk "
                f"lengths q={s_q}/kv={s_kv} are not divisible by the "
                f"requested {want_q}x{want_k}",
                stacklevel=2,
            )
        tiles.append((bq, bk))
    qt, kt, vt = (x.transpose(0, 2, 1, 3) for x in (q, k, v))
    seg_q = seg_k = None
    if segment_ids_q is not None:
        seg_q = segment_ids_q.astype(jnp.int32)[:, :, None]
        seg_k = segment_ids_kv.astype(jnp.int32)[:, :, None]
    out, lse = _chunk_attention_bhsd(
        qt, kt, vt, seg_q, seg_k, causal, tiles[0], tiles[1], interpret, stream,
        window, q_offset
    )
    return out.transpose(0, 2, 1, 3), lse


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    segment_ids: Optional[jax.Array] = None,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    window: int = 0,
    interpret: Optional[bool] = None,
    stream: Optional[bool] = None,
    block_len: int = 0,
) -> jax.Array:
    """Causal flash attention on [batch, seq, heads, head_dim] inputs.

    ``block_len`` L > 0 is the block rule in causal's place: query ``t`` sees
    keys up to the end of its own block of L, ``(t // L) * L + L - 1``; tiles
    are whole blocks, so only the tiles the diagonal crosses are masked
    otherwise than under causal.  No window beside it.

    ``k``/``v`` may carry fewer heads than ``q`` (grouped-query attention:
    ``n_heads % n_kv_heads == 0``); the kernels route each query head to its
    K/V head via BlockSpec index maps — K/V are never expanded, so GQA keeps
    its 1/group HBM saving on the Pallas path.

    ``window > 0`` adds sliding-window masking: query t sees keys in
    (t - window, t] only, and whole key blocks outside the window are
    skipped, not masked — O(seq * window) compute at long sequence.

    ``block_q`` / ``block_k`` None (the default) derives the tiles of the
    forward and of the backward from the shape (:func:`flash_plan`; they need
    not be equal); an explicit value is used for both passes.

    ``stream`` selects the long-sequence kernels (K/V walked as a grid
    dimension, O(block_k) VMEM residency); ``None`` auto-selects them where
    :func:`flash_plan` says so: the forward where a row's blocks outgrow
    ``RESIDENT_VMEM_BUDGET``, the backward above ``STREAM_SEQ_THRESHOLD``
    rows, either past ``MAX_STATIC_TILES`` tile bodies.
    Below that the resident kernels run: a tile walk that is static at trace
    time (tiles outside the band emit nothing, tiles inside it no mask), and
    a backward that is ONE pass — per (batch, kv head) row every tile is
    visited once and yields ``dv``, ``dk`` and ``dq`` from one score tile,
    ``dq`` accumulating in an fp32 VMEM scratch ``[group * seq / block_q,
    head_dim, block_q]`` that is written once.  The streamed backward is
    the dq / dkv pair.  No ``lse`` guard for empty rows is compiled into
    causal self-attention: a row always sees itself.

    Drop-in replacement for
    :func:`tpu_parallel.models.layers.causal_attention` (the ``attn_fn``
    hook).  ``segment_ids`` [batch, seq] masks attention to same-segment
    prefixes (packed sequences) inside the kernel.  ``interpret`` defaults
    to True off-TPU so tests exercise the same kernel code on CPU.
    """
    b, s, h, d = q.shape
    h_kv = k.shape[2]
    if h % h_kv != 0:
        raise ValueError(f"q heads {h} not a multiple of k/v heads {h_kv}")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    rule = block_len if block_len > 1 else True  # L = 1 is causal
    plan = flash_plan(
        s, d, h // h_kv, q.dtype, causal=rule, window=window, block_q=block_q,
        block_k=block_k, stream=stream,
    )
    if plan is None or any(
        plan[p]["block_q"] % plan[p]["block_k"] for p in ("fwd", "bwd")
    ):
        # O(seq^2) escape hatch for shapes the kernel can't tile — loud, not
        # silent: this is a memory/perf cliff the caller should know about
        warnings.warn(
            f"flash_attention falling back to the O(seq^2) reference path: "
            f"seq_len={s} not divisible by block_q={block_q}/block_k={block_k}"
            + (" (no multiple of 128 divides it)" if block_q is None else ""),
            stacklevel=2,
        )
        from tpu_parallel.models.layers import causal_attention

        if h_kv != h:  # the dense path has no head routing — expand
            k = jnp.repeat(k, h // h_kv, axis=2)
            v = jnp.repeat(v, h // h_kv, axis=2)
        return causal_attention(
            q, k, v, segment_ids=segment_ids, window=window, block_len=block_len
        )
    seg = None
    if segment_ids is not None:
        # one int32 lane per batch row ([B, S, 1]); the kernels' BlockSpec
        # index maps route all H heads of row b to the same block
        seg = segment_ids.astype(jnp.int32)[:, :, None]
    qt, kt, vt = (x.transpose(0, 2, 1, 3) for x in (q, k, v))
    fwd_tile = (plan["fwd"]["block_q"], plan["fwd"]["block_k"])
    bwd_tile = (plan["bwd"]["block_q"], plan["bwd"]["block_k"])
    if rule is not True:
        # the kernels that carry their rule as an argument, forward and
        # backward: the same ones, the block length in ``causal``'s place
        out, _ = _chunk_attention_bhsd(
            qt, kt, vt, seg, seg, rule, fwd_tile, bwd_tile, interpret, stream,
            0, 0,
        )
        return out.transpose(0, 2, 1, 3)
    out = _flash_attention_bhsd(
        qt, kt, vt, seg, fwd_tile, bwd_tile, interpret, window, stream,
    )
    return out.transpose(0, 2, 1, 3)


def flash_attention_fwd_bhsd(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    interpret: Optional[bool] = None,
    stream: Optional[bool] = None,
) -> jax.Array:
    """Causal flash attention, FORWARD ONLY, in the kernels' own layout, for a
    query-key width that differs from the value width (latent attention
    scores at 192 and sums values at 128): ``q`` ``[batch, heads, seq, dk]``,
    ``k`` ``[batch, kv_heads, seq, dk]``, ``v`` ``[batch, kv_heads, seq, dv]``
    give ``[batch, heads, seq, dv]``; scores are scaled by ``dk ** -0.5``.

    The forward kernels of :func:`flash_attention` at the tiles and in the
    variant :func:`flash_plan` gives the row and the query-key width, as every
    caller's: a ``dk`` that is no multiple of the 128 lanes (192) is a
    block's FULL last axis, which the chip's compiler lays out itself
    (``tests/test_chip_compile.py`` compiles it).  No backward: the dq / dkv
    kernels take one width, so this is the serving prefill's call and
    differentiating it raises.  (At the end of the file: a kernel's bytes
    carry the line numbers of the frames above it.)"""
    (b, h, s, d), h_kv = q.shape, k.shape[1]
    if h % h_kv != 0:
        raise ValueError(f"q heads {h} not a multiple of k/v heads {h_kv}")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    plan = flash_plan(
        s, max(d, v.shape[-1]), h // h_kv, q.dtype, block_q=block_q,
        block_k=block_k, stream=stream,
    )
    if plan is None or plan["fwd"]["block_q"] % plan["fwd"]["block_k"]:
        raise ValueError(
            f"no tile of the flash kernels divides a row of {s} positions "
            f"(block_q={block_q}, block_k={block_k}): pad the row to a "
            "multiple of 128"
        )
    out, _ = _flash_fwd(
        q, k, v, None, None, block_q=plan["fwd"]["block_q"],
        block_k=plan["fwd"]["block_k"], interpret=interpret, stream=stream,
    )
    return out
