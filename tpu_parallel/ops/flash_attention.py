"""Causal flash attention as Pallas TPU kernels (fwd + bwd), with custom VJP.

No reference capability exists (the reference has no attention at all —
SURVEY.md §5 long-context row); this kernel serves the transformer configs and
the ≥40% MFU target: O(seq) memory instead of O(seq^2), fp32 online softmax,
bf16 MXU matmuls, block sizes aligned to the 128-lane MXU.

Layout convention: [batch, heads, seq, head_dim] inside the kernels (the
public API accepts [batch, seq, heads, head_dim] and transposes).  The causal
structure is exploited twice: key blocks beyond the query block are skipped
(not masked — skipped), and the backward kernels iterate only the triangle
they need.

Grouped-query attention is native: K/V may carry ``n_kv < n_heads`` heads and
are NEVER expanded — the BlockSpec index maps route each query head to its
K/V head's blocks, so GQA pays 1/group of MHA's K/V HBM traffic (the whole
point of GQA; a pre-kernel ``jnp.repeat`` would materialize full-MHA K/V
because Pallas operands are real buffers, not fusible broadcasts).

Two kernel variants share the masking/band geometry:

- **resident** (seq <= ``STREAM_SEQ_THRESHOLD``): one (batch, head) row's
  whole K/V lives in VMEM; the K loop runs inside the kernel and skips
  out-of-band blocks entirely.  This is the measured-fastest path at the
  bench config (512x512 tiles, seq 1024).
- **streamed** (longer seq): the K/V walk is a grid dimension; VMEM holds one
  [block_k, d] tile plus fp32 online-softmax scratch carried across grid
  steps, so residency is O(block) and seq 8k-32k fits v5e VMEM.  Out-of-band
  grid steps clamp their index map to the previous block — Pallas skips the
  DMA when the mapped block is unchanged — so causal still halves the
  traffic, not just the FLOPs.

Packed sequences: ``segment_ids`` [batch, seq] adds a same-segment condition
to the causal mask in all kernels (each query can always see itself, so no
row is ever fully masked).

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

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
# above this K/V length the streamed kernels take over (resident K/V at
# 4096 x 64 x bf16 is ~0.5MB/operand — comfortable; 16k+ overflows v5e VMEM
# once pipelining double-buffers the operands)
STREAM_SEQ_THRESHOLD = 4096
NEG_INF = -1e30


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


def _band_mask(qi, ki, shape, block_q: int, block_k: int, causal: bool,
               window: int, q_offset: int = 0):
    """Causal and/or sliding-window mask for one [block_q, block_k] score
    tile, or None when neither applies — the ONE definition all kernels
    (fwd, dq, dkv; resident and streamed) share, so forward and backward can
    never desynchronize on the band geometry.

    ``q_offset`` (static) shifts query positions relative to key positions:
    in ring attention the q chunk starts ``j * local_seq`` tokens after the
    K/V chunk it is attending, so the sliding-window band between them is
    the same geometry translated by that constant.
    """
    if not (causal or window):
        return None
    q_pos = q_offset + qi * block_q + lax.broadcasted_iota(jnp.int32, shape, 0)
    k_pos = ki * block_k + lax.broadcasted_iota(jnp.int32, shape, 1)
    mask = None
    if causal:
        mask = q_pos >= k_pos
    if window:
        # causal: one-sided band (keys at most window-1 behind the query);
        # non-causal (encoder local attention): symmetric |q - k| < window
        near = q_pos - k_pos < window
        if not causal:
            near = jnp.logical_and(near, k_pos - q_pos < window)
        mask = near if mask is None else jnp.logical_and(mask, near)
    return mask


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


def _use_stream(s_kv: int, stream: Optional[bool]) -> bool:
    return s_kv > STREAM_SEQ_THRESHOLD if stream is None else bool(stream)


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


def _fwd_kernel(
    q_ref, k_ref, v_ref, *rest, block_q, block_k, scale, has_segments,
    causal=True, window=0, q_offset=0,
):
    if has_segments:
        # separate q- and k-side segment refs: for self-attention both view
        # the same array; ring chunks pass the local chunk's ids vs the
        # rotating chunk's ids
        seg_q_ref, seg_k_ref, o_ref, lse_ref = rest
    else:
        o_ref, lse_ref = rest
    qi = pl.program_id(1)
    # keep MXU operands in the input dtype (bf16 on TPU: full MXU rate) and
    # accumulate fp32 via preferred_element_type; fp32 operands would run
    # the systolic array at a fraction of peak
    q = (q_ref[0] * jnp.asarray(scale, q_ref.dtype)).astype(q_ref.dtype)
    if has_segments:
        seg_q = seg_q_ref[0]  # [bq, 1] — block qi via the index map
    # band range from the ONE shared helper (causal: blocks <= qi; full
    # mode: every block, or the symmetric window band for encoders)
    first_k_block, last_k_block = _stream_k_range(
        qi, block_q, block_k, causal, window,
        k_ref.shape[1] // block_k, q_offset,
    )
    num_k_blocks = last_k_block + 1

    def body(ki, carry):
        acc, m_prev, l_prev = carry
        k = k_ref[0, pl.ds(ki * block_k, block_k), :]
        v = v_ref[0, pl.ds(ki * block_k, block_k), :]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)  # [bq, bk]
        mask = _band_mask(qi, ki, s.shape, block_q, block_k, causal, window,
                          q_offset)
        if has_segments:
            seg_k = seg_k_ref[0, pl.ds(ki * block_k, block_k), :]  # [bk, 1]
            same = seg_q == seg_k.T
            mask = same if mask is None else jnp.logical_and(mask, same)
        if mask is not None:
            s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc = acc * alpha + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32
        )
        return acc, m_new, l_new

    d = q_ref.shape[-1]
    acc = jnp.zeros((block_q, d), jnp.float32)
    m0 = jnp.full((block_q, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q, 1), jnp.float32)
    acc, m, l = lax.fori_loop(first_k_block, num_k_blocks, body, (acc, m0, l0))
    _finalize_rows(acc, m, l, o_ref, lse_ref, causal)


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
    b, h, s, d = q.shape
    h_kv, s_kv = k.shape[1], k.shape[2]
    scale = 1.0 / (d**0.5)
    bh = b * h
    qf = q.reshape(bh, s, d)
    kf = k.reshape(b * h_kv, s_kv, d)
    vf = v.reshape(b * h_kv, s_kv, d)
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
    out_shape = [
        _sds((bh, s, d), q.dtype, qf),
        _sds((bh, s, 1), jnp.float32, qf),
    ]
    if _use_stream(s_kv, stream):
        num_ki = s_kv // block_k
        kv_map = _stream_kv_map(
            kv_row, block_q, block_k, causal, window, num_ki, q_offset
        )

        in_specs = [
            pl.BlockSpec((1, block_q, d), lambda bh_, qi, ki: (bh_, qi, 0)),
            pl.BlockSpec((1, block_k, d), kv_map),
            pl.BlockSpec((1, block_k, d), kv_map),
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
                pl.BlockSpec((1, block_q, d), lambda bh_, qi, ki: (bh_, qi, 0)),
                pl.BlockSpec((1, block_q, 1), lambda bh_, qi, ki: (bh_, qi, 0)),
            ],
            out_shape=out_shape,
            scratch_shapes=[
                pltpu.VMEM((block_q, d), jnp.float32),
                pltpu.VMEM((block_q, 1), jnp.float32),
                pltpu.VMEM((block_q, 1), jnp.float32),
            ],
            interpret=interpret,
        )(*args)
        return out.reshape(b, h, s, d), lse.reshape(b, h, s)

    in_specs = [
        pl.BlockSpec((1, block_q, d), lambda bh_, qi: (bh_, qi, 0)),
        pl.BlockSpec((1, s_kv, d), lambda bh_, qi: (kv_row(bh_), 0, 0)),
        pl.BlockSpec((1, s_kv, d), lambda bh_, qi: (kv_row(bh_), 0, 0)),
    ]
    args = [qf, kf, vf]
    if seg_q is not None:
        # all H heads of batch row b read the same blocks: the q side one
        # [block_q, 1] tile per grid step, the k side its full [S_kv, 1] lane
        in_specs.append(
            pl.BlockSpec((1, block_q, 1), lambda bh_, qi: (bh_ // h, qi, 0))
        )
        in_specs.append(
            pl.BlockSpec((1, s_kv, 1), lambda bh_, qi: (bh_ // h, 0, 0))
        )
        args += [seg_q, seg_k]
    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, **kernel_kwargs),
        grid=(bh, s // block_q),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh_, qi: (bh_, qi, 0)),
            pl.BlockSpec((1, block_q, 1), lambda bh_, qi: (bh_, qi, 0)),
        ],
        out_shape=out_shape,
        interpret=interpret,
    )(*args)
    return out.reshape(b, h, s, d), lse.reshape(b, h, s)


# --- backward kernels ---------------------------------------------------------


def _bwd_dq_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rest,
    block_q, block_k, scale, has_segments, causal=True, window=0, q_offset=0,
):
    if has_segments:
        seg_q_ref, seg_k_ref, dq_ref = rest
    else:
        (dq_ref,) = rest
    qi = pl.program_id(1)
    q = (q_ref[0] * jnp.asarray(scale, q_ref.dtype)).astype(q_ref.dtype)
    do = do_ref[0]  # [bq, D]
    lse = lse_ref[0]  # [bq, 1]
    delta = delta_ref[0]  # [bq, 1]
    if has_segments:
        seg_q = seg_q_ref[0]  # [bq, 1] — block qi via the index map
    first_k_block, last_k_block = _stream_k_range(
        qi, block_q, block_k, causal, window,
        k_ref.shape[1] // block_k, q_offset,
    )
    num_k_blocks = last_k_block + 1

    def body(ki, dq):
        k = k_ref[0, pl.ds(ki * block_k, block_k), :]
        v = v_ref[0, pl.ds(ki * block_k, block_k), :]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)
        mask = _band_mask(qi, ki, s.shape, block_q, block_k, causal, window,
                          q_offset)
        if has_segments:
            seg_k = seg_k_ref[0, pl.ds(ki * block_k, block_k), :]
            same = seg_q == seg_k.T
            mask = same if mask is None else jnp.logical_and(mask, same)
        if mask is not None:
            s = jnp.where(mask, s, NEG_INF)
        # empty rows (lse == NEG_INF, only in offset-window chunk mode)
        # must contribute zero: exp(s - lse) would be exp(0) = 1 on
        # their masked entries
        p = jnp.where(lse <= NEG_INF / 2, 0.0, jnp.exp(s - lse))
        dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
        ds = (p * (dp - delta)).astype(k.dtype)
        return dq + jnp.dot(ds, k, preferred_element_type=jnp.float32)

    d = q_ref.shape[-1]
    dq = lax.fori_loop(
        first_k_block, num_k_blocks, body, jnp.zeros((block_q, d), jnp.float32)
    )
    dq_ref[0] = (dq * scale).astype(dq_ref.dtype)


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


def _bwd_dkv_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rest,
    block_q, block_k, scale, seq_len, has_segments, causal=True, window=0,
    group=1, q_offset=0,
):
    """Resident dk/dv: grid (b*h_kv, ki).  Under GQA (group > 1) the
    q/do/lse/delta operands arrive reshaped to [b*h_kv, group*seq, ...] and
    the kernel statically unrolls over the group's query heads, summing their
    contributions — the reduction over the group happens here, not via an
    expanded K/V."""
    if has_segments:
        seg_q_ref, seg_k_ref, dk_ref, dv_ref = rest
    else:
        dk_ref, dv_ref = rest
    ki = pl.program_id(1)
    k = k_ref[0]  # [block_k, D]
    v = v_ref[0]
    if has_segments:
        seg_k = seg_k_ref[0]  # [bk, 1] — block ki via the index map
    # shared q-range helper: [first, last] may be empty; fori_loop with
    # lower >= upper simply runs zero iterations
    first_q_block, last_q_block = _stream_q_range(
        ki, block_q, block_k, causal, window, seq_len // block_q, q_offset
    )
    num_q_blocks = last_q_block + 1

    def make_body(g):
        base = g * seq_len

        def body(qi, carry):
            dk, dv = carry
            q = (
                q_ref[0, pl.ds(base + qi * block_q, block_q), :]
                * jnp.asarray(scale, q_ref.dtype)
            ).astype(q_ref.dtype)
            do = do_ref[0, pl.ds(base + qi * block_q, block_q), :]
            lse = lse_ref[0, pl.ds(base + qi * block_q, block_q), :]
            delta = delta_ref[0, pl.ds(base + qi * block_q, block_q), :]
            s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)  # [bq, bk]
            mask = _band_mask(qi, ki, s.shape, block_q, block_k, causal, window,
                              q_offset)
            if has_segments:
                seg_q = seg_q_ref[0, pl.ds(qi * block_q, block_q), :]
                same = seg_q == seg_k.T
                mask = same if mask is None else jnp.logical_and(mask, same)
            if mask is not None:
                s = jnp.where(mask, s, NEG_INF)
            # empty rows (lse == NEG_INF, only in offset-window chunk mode)
            # must contribute zero: exp(s - lse) would be exp(0) = 1 on
            # their masked entries
            p = jnp.where(lse <= NEG_INF / 2, 0.0, jnp.exp(s - lse))
            dv = dv + jnp.dot(
                p.astype(do.dtype).T, do, preferred_element_type=jnp.float32
            )
            dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
            ds = (p * (dp - delta)).astype(q.dtype)
            dk = dk + jnp.dot(ds.T, q, preferred_element_type=jnp.float32)
            return dk, dv

        return body

    d = k_ref.shape[-1]
    zeros = jnp.zeros((block_k, d), jnp.float32)
    carry = (zeros, zeros)
    for g in range(group):  # static unroll: one pass per query head in group
        carry = lax.fori_loop(first_q_block, num_q_blocks, make_body(g), carry)
    dk, dv = carry
    # q was pre-scaled, so dk already carries one factor of `scale`
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


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
    dof = do.reshape(bh, s, d)
    lsef = lse.reshape(bh, s, 1)
    deltaf = delta.reshape(bh, s, 1)
    has_segments = seg_q is not None
    kv_row = _kv_row_map(h, h_kv)
    # the resident dkv kernel holds [group*s, d] q/do operands in VMEM, so
    # under GQA the stream decision must budget for group*s, not just s_kv —
    # e.g. group=8 at s=4096 is an 8MB bf16 q tile, past v5e VMEM
    streamed = _use_stream(max(s_kv, group * s), stream)

    # ---- dq ----
    if streamed:
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
            functools.partial(
                _bwd_dq_kernel_stream,
                block_q=block_q,
                block_k=block_k,
                scale=scale,
                has_segments=has_segments,
                causal=causal,
                window=window,
                num_ki=num_ki,
                q_offset=q_offset,
            ),
            grid=(bh, s // block_q, num_ki),
            in_specs=in_specs,
            out_specs=pl.BlockSpec(
                (1, block_q, d), lambda bh_, qi, ki: (bh_, qi, 0)
            ),
            out_shape=_sds((bh, s, d), q.dtype, qf),
            scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
            interpret=interpret,
        )(*args)
    else:
        in_specs = [
            pl.BlockSpec((1, block_q, d), lambda bh_, qi: (bh_, qi, 0)),
            pl.BlockSpec((1, s_kv, d), lambda bh_, qi: (kv_row(bh_), 0, 0)),
            pl.BlockSpec((1, s_kv, d), lambda bh_, qi: (kv_row(bh_), 0, 0)),
            pl.BlockSpec((1, block_q, d), lambda bh_, qi: (bh_, qi, 0)),
            pl.BlockSpec((1, block_q, 1), lambda bh_, qi: (bh_, qi, 0)),
            pl.BlockSpec((1, block_q, 1), lambda bh_, qi: (bh_, qi, 0)),
        ]
        args = [qf, kf, vf, dof, lsef, deltaf]
        if has_segments:
            in_specs.append(
                pl.BlockSpec((1, block_q, 1), lambda bh_, qi: (bh_ // h, qi, 0))
            )
            in_specs.append(
                pl.BlockSpec((1, s_kv, 1), lambda bh_, qi: (bh_ // h, 0, 0))
            )
            args += [seg_q, seg_k]
        dq = pl.pallas_call(
            functools.partial(
                _bwd_dq_kernel,
                block_q=block_q,
                block_k=block_k,
                scale=scale,
                has_segments=has_segments,
                causal=causal,
                window=window,
                q_offset=q_offset,
            ),
            grid=(bh, s // block_q),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, block_q, d), lambda bh_, qi: (bh_, qi, 0)),
            out_shape=_sds((bh, s, d), q.dtype, qf),
            interpret=interpret,
        )(*args)

    # ---- dk/dv ----
    dkv_out_specs = [
        pl.BlockSpec((1, block_k, d), lambda bh_, ki, *_: (bh_, ki, 0)),
        pl.BlockSpec((1, block_k, d), lambda bh_, ki, *_: (bh_, ki, 0)),
    ]
    dkv_out_shape = [
        _sds((b_kv, s_kv, d), q.dtype, qf),
        _sds((b_kv, s_kv, d), q.dtype, qf),
    ]
    if streamed:
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
                _bwd_dkv_kernel_stream,
                block_q=block_q,
                block_k=block_k,
                scale=scale,
                has_segments=has_segments,
                causal=causal,
                window=window,
                group=group,
                num_qi=num_qi,
                q_offset=q_offset,
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
    else:
        # group the query-head operands by K/V head: [b*h_kv, group*s, ...]
        qg = q.reshape(b_kv, group * s, d)
        dog = do.reshape(b_kv, group * s, d)
        lseg = lse.reshape(b_kv, group * s, 1)
        deltag = delta.reshape(b_kv, group * s, 1)
        in_specs = [
            pl.BlockSpec((1, group * s, d), lambda bh_, ki: (bh_, 0, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh_, ki: (bh_, ki, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh_, ki: (bh_, ki, 0)),
            pl.BlockSpec((1, group * s, d), lambda bh_, ki: (bh_, 0, 0)),
            pl.BlockSpec((1, group * s, 1), lambda bh_, ki: (bh_, 0, 0)),
            pl.BlockSpec((1, group * s, 1), lambda bh_, ki: (bh_, 0, 0)),
        ]
        args = [qg, kf, vf, dog, lseg, deltag]
        if has_segments:
            in_specs.append(
                pl.BlockSpec((1, s, 1), lambda bh_, ki: (bh_ // h_kv, 0, 0))
            )
            in_specs.append(
                pl.BlockSpec((1, block_k, 1), lambda bh_, ki: (bh_ // h_kv, ki, 0))
            )
            args += [seg_q, seg_k]
        dk, dv = pl.pallas_call(
            functools.partial(
                _bwd_dkv_kernel,
                block_q=block_q,
                block_k=block_k,
                scale=scale,
                seq_len=s,
                has_segments=has_segments,
                causal=causal,
                window=window,
                group=group,
                q_offset=q_offset,
            ),
            grid=(b_kv, s_kv // block_k),
            in_specs=in_specs,
            out_specs=dkv_out_specs,
            out_shape=dkv_out_shape,
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
    """Identity on ``out``; exists to attach the backward kernels.

    The forward kernel runs *outside* this custom_vjp (see
    ``_flash_attention_bhsd``) so its outputs are ordinary named values in
    the surrounding jaxpr: a ``save_only_these_names(..., "attn")`` remat
    policy can then keep them, and the backward never re-runs the forward
    kernel.  Residuals hidden inside a custom_vjp are invisible to remat
    policies — measured as a full forward-kernel re-run per layer
    (scripts/attn_wrap_bisect.py).
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


def _flash_attention_bhsd(q, k, v, seg, block_q, block_k, interpret, window=0,
                          stream=None):
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
        block_q=block_q,
        block_k=block_k,
        interpret=interpret,
        window=window,
        stream=stream,
    )
    out = checkpoint_name(out, "attn")
    lse = checkpoint_name(lse, "attn")
    return _flash_finalize(
        q, k, v, seg, seg, out, lse, block_q, block_k, interpret, window, stream
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
    q, k, v, seg_q, seg_k, causal, block_q, block_k, interpret, stream, window,
    q_offset
):
    from jax.ad_checkpoint import checkpoint_name

    out, lse = _flash_fwd(
        lax.stop_gradient(q),
        lax.stop_gradient(k),
        lax.stop_gradient(v),
        seg_q, seg_k,
        block_q=block_q, block_k=block_k,
        interpret=interpret, causal=causal, stream=stream,
        window=window, q_offset=q_offset,
    )
    out = checkpoint_name(out, "attn")
    lse = checkpoint_name(lse, "attn")
    return _chunk_finalize(
        q, k, v, seg_q, seg_k, out, lse, causal, block_q, block_k, interpret,
        stream, window, q_offset
    )


def flash_chunk_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
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
    in both outputs — the lse cotangent folds into the backward kernels'
    delta correction, which is what makes the combine's gradient exact.

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

    bq = math.gcd(q.shape[1], min(block_q, q.shape[1]))
    bk = math.gcd(k.shape[1], min(block_k, k.shape[1]))
    if causal:
        bk = math.gcd(bq, bk)  # causal num_k_blocks needs block_q % block_k == 0
    if bq < min(block_q, q.shape[1]) or bk < min(block_k, k.shape[1]):
        warnings.warn(
            f"flash_chunk_attention shrank tiles to {bq}x{bk}: chunk lengths "
            f"q={q.shape[1]}/kv={k.shape[1]} are not divisible by the "
            f"requested {block_q}x{block_k}",
            stacklevel=2,
        )
    qt, kt, vt = (x.transpose(0, 2, 1, 3) for x in (q, k, v))
    seg_q = seg_k = None
    if segment_ids_q is not None:
        seg_q = segment_ids_q.astype(jnp.int32)[:, :, None]
        seg_k = segment_ids_kv.astype(jnp.int32)[:, :, None]
    out, lse = _chunk_attention_bhsd(
        qt, kt, vt, seg_q, seg_k, causal, bq, bk, interpret, stream, window,
        q_offset
    )
    return out.transpose(0, 2, 1, 3), lse


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    segment_ids: Optional[jax.Array] = None,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
    window: int = 0,
    interpret: Optional[bool] = None,
    stream: Optional[bool] = None,
) -> jax.Array:
    """Causal flash attention on [batch, seq, heads, head_dim] inputs.

    ``k``/``v`` may carry fewer heads than ``q`` (grouped-query attention:
    ``n_heads % n_kv_heads == 0``); the kernels route each query head to its
    K/V head via BlockSpec index maps — K/V are never expanded, so GQA keeps
    its 1/group HBM saving on the Pallas path.

    ``window > 0`` adds sliding-window masking: query t sees keys in
    (t - window, t] only, and whole key blocks outside the window are
    skipped, not masked — O(seq * window) compute at long sequence.

    ``stream`` selects the long-sequence kernels (K/V walked as a grid
    dimension, O(block_k) VMEM residency); ``None`` auto-selects them above
    ``STREAM_SEQ_THRESHOLD`` tokens.

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
    block_q = min(block_q, s)
    block_k = min(block_k, s)
    if s % block_q != 0 or s % block_k != 0 or block_q % block_k != 0:
        # O(seq^2) escape hatch for shapes the kernel can't tile — loud, not
        # silent: this is a memory/perf cliff the caller should know about
        warnings.warn(
            f"flash_attention falling back to the O(seq^2) reference path: "
            f"seq_len={s} not divisible by block_q={block_q}/block_k={block_k}",
            stacklevel=2,
        )
        from tpu_parallel.models.layers import causal_attention

        if h_kv != h:  # the dense path has no head routing — expand
            k = jnp.repeat(k, h // h_kv, axis=2)
            v = jnp.repeat(v, h // h_kv, axis=2)
        return causal_attention(q, k, v, segment_ids=segment_ids, window=window)
    seg = None
    if segment_ids is not None:
        # one int32 lane per batch row ([B, S, 1]); the kernels' BlockSpec
        # index maps route all H heads of row b to the same block
        seg = segment_ids.astype(jnp.int32)[:, :, None]
    qt, kt, vt = (x.transpose(0, 2, 1, 3) for x in (q, k, v))
    out = _flash_attention_bhsd(
        qt, kt, vt, seg, block_q, block_k, interpret, window, stream
    )
    return out.transpose(0, 2, 1, 3)
