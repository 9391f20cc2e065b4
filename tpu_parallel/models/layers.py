"""Shared transformer building blocks, parallelism-aware.

No reference capability exists for any of this (the reference's models are
2-layer MLPs — SURVEY.md §2.4); these layers serve the BASELINE.json
transformer configs (GPT-2 125M/350M, Llama-style 1B).  TPU-first choices:

- bf16 activations / fp32 params and fp32 LayerNorm+softmax accumulation
  (MXU-friendly, numerically safe).
- Tensor parallelism is *structural*, not conditional: attention and MLP
  projections are :class:`~tpu_parallel.parallel.tp.TPDense` over the
  ``model`` axis.  On a mesh where that axis has size 1 the collectives are
  identity — one model definition serves every mesh shape.
- ``nn.remat`` + ``nn.scan`` over layers keep compile time and HBM in check
  at 125M+ scale.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
from typing import Any, Callable, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from tpu_parallel.parallel import fsdp
from tpu_parallel.parallel.tp import TPDense, axis_size_or_none


@dataclasses.dataclass(frozen=True)
class ExpertsSpec:
    """A layer's routed experts (``models/moe.py::RoutedExperts``): dropless
    top-k routing over ``n_experts``, of which this program holds ``held =
    (first, count)`` (None = all of them).  The router always scores all
    ``n_experts`` and normalises over its true top-k; the layer adds only
    what its held experts give."""

    n_experts: int
    top_k: int
    width: int  # hidden width of one routed expert (a shared one: shared_width)
    score: str = "softmax"  # "softmax" | "sigmoid" over the router's logits
    shared: int = 0  # shared experts beside the routed ones
    held: Optional[Tuple[int, int]] = None
    # > 0: the routed experts live in a latent of this width, between ONE
    # down-projection and ONE up-projection a layer around the routed sum
    # (router and shared experts read the full-width input)
    latent: int = 0
    # an expert: "swiglu" W_down(silu(W_gate x) * (W_up x)), three matrices;
    # "relu2" W_2 relu(W_1 x)^2, two
    ffn: str = "swiglu"
    shared_width: int = 0  # a shared expert's own hidden width (0: ``width``)
    shared_sum: bool = False  # shared outputs added (True) or averaged
    # a learned bias added to the scores in the CHOICE of the top-k only
    select_bias: bool = False
    route_scale: float = 1.0  # times the renormalised weights

    @property
    def held_range(self) -> Tuple[int, int]:
        return self.held if self.held is not None else (0, self.n_experts)


@dataclasses.dataclass(frozen=True)
class SSMSpec:
    """A recurrent (Mamba-2) mixer's sizes (``models/ssm.py::SSMMixer``)."""

    n_heads: int
    head_dim: int
    d_state: int  # columns of a head's state ``[head_dim, d_state]``
    n_groups: int = 1  # B and C are shared by the heads of a group
    d_conv: int = 4  # width of the depthwise causal conv
    chunk: int = 256  # tokens a block of the chunked scan


@dataclasses.dataclass(frozen=True)
class LayerSpec:
    """One block's kind.  ``TransformerConfig.layer_pattern`` holds one period
    of these, repeated over the depth; a model whose blocks are all alike is
    the one-entry case (:meth:`TransformerConfig.layer_specs` derives it).
    ``mixer="none"`` or ``mlp="none"``: ONE sublayer behind ONE norm."""

    attn: str = "full"  # "full" | "window" | "latent" (sizes in ``latent``)
    window: int = 0  # keys a query sees, itself included ("window" only)
    positions: str = "model"  # "model" (config.positional) | "rope" | "none"
    mlp: str = "dense"  # "dense" | "experts" | "none" (the mixer alone)
    # None with mlp="experts": the config's capacity-routed moe_* experts
    experts: Optional[ExpertsSpec] = None
    mixer: str = "attention"  # "attention" | "ssm" | "none" (the MLP alone)
    ssm: Optional[SSMSpec] = None  # the recurrent mixer's sizes
    latent: Optional[LatentSpec] = None  # attn="latent": its five sizes


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    """Architecture + parallelism knobs for the transformer family."""

    vocab_size: int = 50304  # GPT-2's 50257 padded up to a multiple of 128 (MXU lanes)
    d_model: int = 768
    n_layers: int = 12
    n_heads: int = 12
    # grouped-query attention: number of K/V heads (None = MHA; 1 = MQA).
    # Q heads are grouped onto the K/V heads after RoPE — natively (no K/V
    # expansion) on the flash and decode paths, by repetition elsewhere.
    n_kv_heads: Optional[int] = None
    # width of one head; None = d_model // n_heads (a share of a wider model
    # holds fewer heads than d_model / head_dim, so the size is stated)
    head_dim: Optional[int] = None
    seq_len: int = 1024
    mlp_ratio: int = 4
    # hidden width of the dense MLP (and of a capacity-routed expert);
    # None = mlp_ratio * d_model
    mlp_dim: Optional[int] = None
    dropout_rate: float = 0.0
    dtype: Any = jnp.bfloat16
    # positional encoding: "learned" (GPT-2), "rope" (Llama), or "relative"
    # (T5: no embedding-level positions — a bucketed per-head bias added to
    # the attention scores, shared across the stack's layers; xla attention
    # path only)
    positional: str = "learned"
    rope_theta: float = 10000.0
    # T5 relative-bias shape knobs (used when positional="relative")
    rel_num_buckets: int = 32
    rel_max_distance: int = 128
    # norm: "layernorm" (GPT-2), "layernorm_nobias" (scale only) or
    # "rmsnorm" (Llama)
    norm: str = "layernorm"
    # norm placement: True = pre-norm (GPT/Llama/T5: x + f(norm(x)), final
    # norm after the stack); False = post-norm (original BERT:
    # norm(x + f(x)), embedding-sum norm instead of a final norm — set
    # embed_norm=True to match).  Post-norm exists for checkpoint interop
    # (models/hf.py BERT import); pre-norm remains the default for
    # from-scratch training (stabler at depth).
    prenorm: bool = True
    # LayerNorm over the embedding sum (token + positional) before the
    # stack — the BERT embeddings.LayerNorm
    embed_norm: bool = False
    # canonical GPT-2/Llama epsilon (flax's default is 1e-6; 1e-5 matches
    # the reference implementations bit-for-bit — models/hf.py interop)
    norm_eps: float = 1e-5
    # mlp: "gelu" (GPT-2's tanh approximation), "gelu_exact" (BERT's erf
    # form — interop-exact against torch), "relu" (original T5), "swiglu"
    # (Llama), or "geglu" (T5 v1.1: gelu-gated, two up projections)
    mlp: str = "gelu"
    # biases on the attention/MLP projections (False for Llama-style and T5
    # checkpoints, True for GPT-2/BERT)
    dense_bias: bool = True
    # parallelism
    model_axis: str = "model"
    data_axis: str = "data"
    pipe_axis: str = "pipe"
    seq_axis: str = "seq"
    num_microbatches: int = 4  # pipeline schedule depth (used when pipe > 1)
    remat: bool = True
    # remat granularity: "full" recomputes everything in the backward pass;
    # "proj" saves only the named projection outputs (qkv/out/up/down) so the
    # backward recomputes just norms, elementwise ops, and attention probs —
    # most of full-remat's memory win without re-running the big matmuls;
    # "proj_attn" additionally saves the attention context and the flash
    # kernel's logsumexp ("attn" names), so the backward never re-runs the
    # attention forward — the fastest policy with attn_impl="flash" (the
    # saved tensors are O(seq), not O(seq^2));
    # "dots" saves every matmul output (includes O(seq^2) attention scores —
    # only viable at short sequence or small batch)
    remat_policy: str = "full"
    scan_layers: bool = True
    # layers per unrolled step of the layer scan (nn.scan's ``unroll``): the
    # scan's cost is the per-tick carry round-trips, which unrolling the
    # body does not remove (on the chip: not measured); the knob stays
    scan_unroll: int = 1
    # blocks per scanned BODY (scan length becomes n_layers / scan_group):
    # the residual carry is materialized at tick boundaries only.  Param
    # layout changes to [n_layers/g] stacks of g named blocks ("block0"..);
    # g=1 keeps the historical layout.  Must divide n_layers and hold whole
    # periods of layer kinds.  What was measured: docs/05, "The scan tax"
    scan_group: int = 1
    # lax.scan's _split_transpose: lowers the layer scan's BACKWARD as two
    # loops (residual regeneration + gradient accumulation) that XLA can
    # overlap.  The scan tax an earlier round bisected lives in the
    # backward, which is exactly the pass this targets.
    scan_split_transpose: bool = False
    fsdp: bool = False  # shard big params over the data axis (ZeRO-3)
    fsdp_min_size: int = 2**18
    attn_impl: str = "xla"  # "xla" | "flash" | "ring" | "ulysses"
    # flash kernel tile sizes; None derives the forward's and the backward's
    # tiles from the shape (ops.flash_attention.flash_plan, whose table is
    # the v5e sweeps in PERF.md section 6, PRs 27 and 48); an int for both
    flash_block_q: Optional[int] = None
    flash_block_k: Optional[int] = None
    # sliding-window attention: 0 = full causal; >0 = each query sees only
    # the last `attn_window` positions (Mistral-style).  Applies to every
    # attention impl: xla, flash (whole out-of-window key blocks skipped
    # in-kernel), ring (out-of-window chunks skip their kernels entirely),
    # ulysses (band applied on the gathered sequence), and decode.
    attn_window: int = 0
    # decode KV-cache storage: "bf16" (= cfg.dtype) or "int8" — int8 halves
    # the cache HBM (the decode-memory hog) with one fp32 scale per
    # (position, kv-head); the attention read is int8-NATIVE (scales fold
    # into the score/value matmuls inside decode_attention — no
    # dequantized cache copy), except the lazy-beam path which still
    # dequantizes transiently per layer per step
    kv_cache_dtype: str = "bf16"
    # paged decode KV cache (the serving engine's block-table layout):
    # kv_block_tokens > 0 stores decode K/V in a flat pool of
    # ``kv_pool_blocks`` fixed-size blocks of ``kv_block_tokens`` positions
    # each instead of per-row ``seq_len`` stripes.  Every decode call must
    # then pass ``block_table`` [batch, seq_len // kv_block_tokens] mapping
    # each row's logical block index to a physical pool block (-1 =
    # unmapped: reads masked out, writes dropped) plus ``write_index`` —
    # the engine owns the tables through
    # :class:`~tpu_parallel.serving.cache_pool.BlockAllocator`.  0 = the
    # classic contiguous per-row cache.  Set ONLY by the serving engine
    # (it rebuilds its model with these fields); training and the static
    # generate() paths never page.
    kv_block_tokens: int = 0
    kv_pool_blocks: int = 0
    # lazy beam-search decode: >1 switches the decode attention to the
    # cross-beam form (beam j of prompt i = row i*k+j) that follows beam
    # ancestry through a per-slot source-row table instead of physically
    # re-gathering every layer's KV cache every step.  Set ONLY by the beam
    # loops (models/generate.py builds a beam_width=k model for the decode
    # scan); 0 everywhere else.
    beam_width: int = 0
    # bidirectional (encoder / BERT-style) attention: every position sees
    # every same-segment position — with attn_window > 0, those in the
    # symmetric band |q - k| < window (encoder local attention).  Composes
    # with the xla and flash paths, GQA, packing, TP/FSDP/PP, ulysses SP
    # (band applied on the gathered sequence), and ring SP (the band spans
    # chunks via signed static offsets; out-of-band chunks skip their
    # kernels); refuses decode (encoders don't autoregress)
    bidirectional: bool = False
    # mixture-of-experts: 0 = dense MLP; >0 replaces every block's MLP with
    # routed experts, expert-parallel over the model axis
    moe_experts: int = 0
    # routing family: "topk" (tokens choose experts; see moe_top_k) or
    # "expert_choice" (experts choose their top-capacity tokens — perfectly
    # balanced by construction, no aux loss; NOT causal: a token's routing
    # depends on the whole batch, including later positions, so use for
    # encoders/non-AR objectives or accept the leak knowingly)
    moe_router: str = "topk"
    # experts per token: 1 = Switch (gate = router prob), >1 = GShard-style
    # (gates renormalized over the chosen experts)
    moe_top_k: int = 1
    moe_capacity_factor: float = 1.25
    moe_balance_weight: float = 0.01
    # EP dispatch mechanics: "dense" replicates the token set over the EP
    # ranks and builds [T, E, C] one-hot dispatch/combine masks (zero
    # communication on dispatch, one psum on combine — fine on small
    # meshes, but per-rank mask memory and dispatch-einsum cost grow with
    # the FULL token count).  "alltoall" shards the token set over the EP
    # axis: each rank routes its T/ep tokens locally ([T/ep, E, C/ep]
    # masks — ep^2 smaller), exchanges expert payloads with one
    # all_to_all each way, and closes with an all_gather of the combined
    # tokens.  Capacity becomes a per-(sender, expert) quota of C/ep
    # (GShard's formulation): identical results while nothing overflows,
    # different drop choices under pressure.  topk router only
    # (expert_choice needs global top-capacity; it stays dense).
    moe_dispatch: str = "dense"
    # one period of block kinds, repeated over the depth (n_layers is a
    # whole number of periods); None = every block alike, its kind read off
    # attn_window / positional / moe_experts
    layer_pattern: Optional[Tuple[LayerSpec, ...]] = None
    # parallel block: attention and MLP read ONE norm of the residual and
    # are added to it together (x + attn(h) + mlp(h))
    parallel_block: bool = False
    # output head tied to the token embedding (logits = h E^T * logit_scale)
    tie_embeddings: bool = False
    logit_scale: float = 1.0
    # fresh-cache prefill attends within the call through the flash kernels
    # instead of reading the whole cache stripe back.  Right-padded aligned
    # rows only (token j at position j): the serving engine's bucketed
    # prefill; left-padded ragged generate() refuses it.
    prefill_flash: bool = False
    # stated scalars (published configs give them): the attention score scale
    # (None = head_dim ** -0.5), a multiplier on the token embedding, and one
    # on what each mixer and MLP adds to the residual
    attn_scale: Optional[float] = None
    embed_scale: float = 1.0
    residual_scale: float = 1.0
    # per-head RMSNorm on queries and keys before the rotary embedding, each
    # with its own learned scale over head_dim (Qwen3)
    qk_norm: bool = False
    # which two numbers of a head one rotation turns: "interleaved" pairs
    # (2i, 2i + 1), "half" pairs (i, i + head_dim / 2) (rotate-half, Qwen/Llama
    # checkpoints)
    rope_pairing: str = "interleaved"
    # block-causal visibility (generation by diffusion over blocks): 0 =
    # causal; L > 0 = the sequence is cut into blocks of L from position 0 and
    # a query at p sees every key j <= (p // L) * L + L - 1, its own block
    # whole and all earlier blocks.  Not a function of p - j alone, so it is a
    # third rule beside causal and window (which it refuses to combine with)
    block_len: int = 0
    # the id a position of a block holds until the denoising fills it (the
    # serving engine's block step feeds it; no layer reads this field)
    mask_token_id: Optional[int] = None
    # leading layers BEFORE the repeated period (a dense layer ahead of the
    # expert layers): n_layers = len(layer_head) + whole periods of
    # layer_pattern (None: of the one kind every other block has); unrolled
    # stacks only (a scanned body is one period)
    layer_head: Tuple[LayerSpec, ...] = ()
    # sandwich norms: a norm on the OUTPUT of each sublayer too, before it is
    # added to the residual (four norms a layer: norm_attn, norm_post_attn,
    # norm_mlp, norm_post_mlp); a pre-norm block with both sublayers
    sandwich_norm: bool = False

    def __post_init__(self):
        if self.head_dim is None:
            object.__setattr__(self, "head_dim", self.d_model // self.n_heads)
        if self.mlp_dim is None:
            object.__setattr__(self, "mlp_dim", self.mlp_ratio * self.d_model)
        if self.rope_pairing not in ("interleaved", "half"):
            raise ValueError(
                f"rope_pairing={self.rope_pairing!r} (interleaved | half)"
            )
        # the depth is leading layers and whole periods; what a block rule,
        # a latent layer, a head of layers or sandwich norms do not combine
        # with is refused here, at construction (down at the end of the file,
        # where lines may be added: a kernel's bytes carry the line numbers
        # of the frames that reach it, BlockStack's and Block's among them)
        check_layer_kinds(self)

    @property
    def layer_specs(self) -> Tuple[LayerSpec, ...]:
        """One period of block kinds (one entry for a uniform model)."""
        if self.layer_pattern is not None:
            return self.layer_pattern
        return (
            LayerSpec(
                attn="window" if self.attn_window else "full",
                window=self.attn_window,
                mlp="experts" if self.moe_experts > 0 else "dense",
            ),
        )

    @property
    def routed_layers(self) -> int:
        """Layers whose MLP is a dropless :class:`ExpertsSpec` layer (over the
        whole depth, leading layers included: :func:`depth_specs`)."""
        return sum(1 for s in depth_specs(self) if s.experts is not None)


    @property
    def recurrent_layers(self) -> int:
        """Layers whose mixer carries a recurrent state (``mixer="ssm"``)."""
        # over the whole depth (a leading layer may be one)
        return sum(1 for s in depth_specs(self) if s.mixer == "ssm")

    @property
    def drops_tokens(self) -> bool:
        """Whether some layer routes with a capacity (a token's output then
        depends on its batch-mates: no serving comparison can accept it)."""
        return any(
            s.mlp == "experts" and s.experts is None for s in depth_specs(self)
        )


def seq_parallel_active(config: TransformerConfig) -> bool:
    """True when attention shards the token axis: a seq-parallel impl is
    selected AND the seq mesh axis is actually bound (shard_map region)."""
    return config.attn_impl in ("ring", "ulysses") and bool(
        axis_size_or_none(config.seq_axis)
    )


def make_norm(config: TransformerConfig, name: str):
    """fp32 norm (LayerNorm or RMSNorm) — small, precision-critical."""
    if config.norm == "rmsnorm":
        return nn.RMSNorm(epsilon=config.norm_eps, dtype=jnp.float32, name=name)
    return nn.LayerNorm(
        epsilon=config.norm_eps, dtype=jnp.float32, name=name,
        use_bias=config.norm != "layernorm_nobias",
    )


def apply_rope(
    x: jax.Array, positions: jax.Array, theta: float = 10000.0,
    pairing: str = "interleaved",
) -> jax.Array:
    """Rotary position embedding over the last (head_dim) axis.

    ``x``: [batch, seq, heads, head_dim]; ``positions``: [batch, seq].
    ``pairing``: pair ``i`` turns by ``pos * theta ** (-2i / head_dim)`` and
    is ``(x[2i], x[2i + 1])`` ("interleaved") or ``(x[i], x[i + head_dim /
    2])`` ("half", the rotate-half form).
    """
    head_dim = x.shape[-1]
    freq_exponents = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    inv_freq = 1.0 / (theta**freq_exponents)  # [head_dim/2]
    angles = positions[..., None].astype(jnp.float32) * inv_freq  # [B, S, hd/2]
    angles = angles[:, :, None, :]  # broadcast over heads
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    if pairing == "half":
        x1, x2 = x[..., : head_dim // 2], x[..., head_dim // 2 :]
        return jnp.concatenate(
            [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
        ).astype(x.dtype)
    x1, x2 = x[..., ::2], x[..., 1::2]
    rotated = jnp.stack(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1
    ).reshape(x.shape)
    return rotated.astype(x.dtype)


def _score_scale(scale: Optional[float], head_dim: int, dtype):
    """The factor on attention scores: a stated one, else ``head_dim ** -0.5``."""
    if scale is not None:
        return jnp.asarray(scale, dtype)
    return 1.0 / jnp.sqrt(head_dim).astype(dtype)


def causal_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    segment_ids: Optional[jax.Array] = None,
    window: int = 0,
    causal: bool = True,
    bias: Optional[jax.Array] = None,
    scale: Optional[float] = None,
    block_len: int = 0,
) -> jax.Array:
    """Reference attention: fp32 softmax, bf16 matmuls on the MXU.

    ``q, k, v``: [batch, seq, heads, head_dim].  O(seq^2) memory — the
    Pallas flash kernel (``ops.flash_attention``) replaces this on TPU for
    long sequences.  ``causal=False`` is the bidirectional (encoder) form:
    every position attends every (same-segment) position — with ``window``,
    those within the symmetric band |q - k| < window.  ``block_len`` L > 0
    is the block rule: a query sees keys up to the end of its own block of L.
    """
    head_dim = q.shape[-1]
    scale = _score_scale(scale, head_dim, q.dtype)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q * scale, k)
    scores = scores.astype(jnp.float32)
    if bias is not None:
        # additive position bias [1|B, h, q, k] (T5 relative bias)
        scores = scores + bias.astype(jnp.float32)
    q_pos = lax.broadcasted_iota(jnp.int32, scores.shape, 2)
    k_pos = lax.broadcasted_iota(jnp.int32, scores.shape, 3)
    mask = q_pos >= k_pos if causal else None
    if block_len:
        mask = k_pos <= q_pos // block_len * block_len + (block_len - 1)
    if window:
        # causal: query t attends keys in (t - window, t]; bidirectional
        # (encoder local attention): the symmetric band |q - k| < window
        near = q_pos - k_pos < window
        if not causal:
            near = jnp.logical_and(near, k_pos - q_pos < window)
        mask = near if mask is None else jnp.logical_and(mask, near)
    if segment_ids is not None:
        same_seg = segment_ids[:, None, :, None] == segment_ids[:, None, None, :]
        mask = same_seg if mask is None else jnp.logical_and(mask, same_seg)
    if mask is not None:
        scores = jnp.where(mask, scores, jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def decode_attention_xla(
    q: jax.Array, k_all: jax.Array, v_all: jax.Array, positions: jax.Array,
    window: int = 0, bias: Optional[jax.Array] = None,
    k_positions: Optional[jax.Array] = None,
    k_scale: Optional[jax.Array] = None,
    v_scale: Optional[jax.Array] = None,
    scale: Optional[float] = None,
    block_len: int = 0,
) -> jax.Array:
    """Attention of new queries against a full KV cache, GQA-native.

    ``q``: [batch, new_len, heads, head_dim] at global ``positions``
    [batch, new_len]; ``k_all``/``v_all``: [batch, cache_len, kv_heads,
    head_dim] where ``heads % kv_heads == 0`` (grouped queries contract
    against their group's K/V directly — no repeated-K/V materialization).

    ``k_positions``: the global position each cache slot holds.  Default
    (None) is the aligned layout — slot j holds position j, entries beyond
    the write index masked out by the position comparison.  Ragged batches
    (left-padded prompts) pass the per-row table ``[batch, cache_len]``
    where pad slots hold -1: negative slots never attend, and the causal
    comparison keys off the STORED positions, not slot indices.

    ``k_scale``/``v_scale`` [batch, cache_len, kv_heads, 1] switch to the
    INT8-NATIVE read: ``k_all``/``v_all`` are the raw int8 payloads and
    the per-(position, kv-head) scales fold into the surrounding matmuls
    — K scales multiply the scores AFTER the q·k contraction (a scale is
    constant over head_dim, so ``q·(kq*ks) == (q·kq)*ks`` exactly), and
    V scales fold into the probability weights (``(w*vs)·vq``).  The int8
    payload feeds the dot directly (the int8→compute-dtype cast is
    elementwise, fused into the dot's operand read); no dequantized
    cache-sized copy is ever materialized — the transient bf16 K+V copies
    per layer per step were the whole int8 decode cliff (int8 fell
    behind bf16 at batch 32 while it copied them).

    ``block_len`` L > 0 is the block rule: a query at ``p`` sees the stored
    positions up to the end of its own block, ``(p // L) * L + L - 1``,
    instead of up to ``p`` (a pad query at -1 still sees nothing).
    """
    b, nq, h, head_dim = q.shape
    h_kv = k_all.shape[2]
    group = h // h_kv
    scale = _score_scale(scale, head_dim, q.dtype)
    qg = (q * scale).reshape(b, nq, h_kv, group, head_dim)
    k_in = k_all if k_scale is None else k_all.astype(q.dtype)
    scores = jnp.einsum("bqngd,bknd->bngqk", qg, k_in).astype(jnp.float32)
    if k_scale is not None:
        # fold K scales post-matmul: [b, S, n, 1] -> [b, n, 1, 1, S]
        ks = k_scale[..., 0].transpose(0, 2, 1)[:, :, None, None, :]
        scores = scores * ks
    if bias is not None:
        # [1|B, h, q, k] -> grouped [1|B, h_kv, group, q, k]
        bb = bias.reshape(bias.shape[0], h_kv, group, *bias.shape[2:])
        scores = scores + bb.astype(jnp.float32)
    if k_positions is None:
        k_pos = jnp.broadcast_to(jnp.arange(k_all.shape[1]), (b, k_all.shape[1]))
    else:
        k_pos = k_positions
    kp = k_pos[:, None, None, None, :]
    qp = positions[:, None, None, :, None]
    if block_len:  # floor division: -1 // L * L + L - 1 == -1
        qp = qp // block_len * block_len + (block_len - 1)
    mask = jnp.logical_and(kp >= 0, kp <= qp)
    if window:
        mask = jnp.logical_and(mask, qp - kp < window)
    scores = jnp.where(mask, scores, jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    if v_scale is None:
        out = jnp.einsum("bngqk,bknd->bqngd", probs, v_all)
    else:
        # fold V scales into the probability weights (fp32 multiply, one
        # round back to the compute dtype) so the int8 V payload feeds
        # the value contraction directly
        vs = v_scale[..., 0].transpose(0, 2, 1)[:, :, None, None, :]
        w = (probs.astype(jnp.float32) * vs).astype(q.dtype)
        out = jnp.einsum("bngqk,bknd->bqngd", w, v_all.astype(q.dtype))
    return out.reshape(b, nq, h, head_dim)


def beam_decode_attention(
    q: jax.Array, k_all: jax.Array, v_all: jax.Array, positions: jax.Array,
    beam_src: jax.Array, num_beams: int, window: int = 0,
    bias: Optional[jax.Array] = None,
    k_positions: Optional[jax.Array] = None,
    scale: Optional[float] = None,
) -> jax.Array:
    """Decode attention against an UN-reordered beam-search KV cache.

    Rows are beam-major: beam j of prompt i is row ``i*num_beams + j``.
    ``beam_src`` [rows, cache_len] names, per row and cache slot, the row
    (within the same prompt's beam group) whose cache physically holds that
    slot of this beam's history — the beam loop maintains it (each written
    slot maps to the writing row; a row-gather by winner parents follows
    every top-k).  Mathematically identical to physically gathering cache
    rows by beam ancestry, but the cache is read once and never rewritten:
    scores/values are computed all-pairs over the ``num_beams`` group rows
    (k x the attention FLOPs — noise in bandwidth-bound decode, where the
    eager reorder's full cache read+write per layer per step dominates)
    and the right pair is selected per slot from the table.
    """
    rows, nq, h, head_dim = q.shape
    kb = num_beams
    b = rows // kb
    if b * kb != rows:
        raise ValueError(f"rows={rows} not divisible by num_beams={kb}")
    cache_len = k_all.shape[1]
    h_kv = k_all.shape[2]
    group = h // h_kv
    scale = _score_scale(scale, head_dim, q.dtype)
    qg = (q * scale).reshape(b, kb, nq, h_kv, group, head_dim)
    kg = k_all.reshape(b, kb, cache_len, h_kv, head_dim)
    # all-pairs scores over the beam group: [b, j, j', h_kv, group, q, slot]
    scores = jnp.einsum("bjqngd,bpsnd->bjpngqs", qg, kg).astype(jnp.float32)
    # per (row, slot) select the source beam's score
    src_local = (beam_src.reshape(b, kb, cache_len) % kb).astype(jnp.int32)
    idx = src_local[:, :, None, None, None, None, :]  # [b, j, 1, 1, 1, 1, s]
    sel = jnp.take_along_axis(scores, idx, axis=2)[:, :, 0]  # [b,j,n,g,q,s]
    sel = sel.reshape(rows, h_kv, group, nq, cache_len)
    if bias is not None:
        bb = bias.reshape(bias.shape[0], h_kv, group, *bias.shape[2:])
        sel = sel + bb.astype(jnp.float32)
    if k_positions is None:
        k_pos = jnp.broadcast_to(jnp.arange(cache_len), (rows, cache_len))
    else:
        k_pos = k_positions
    kp = k_pos[:, None, None, None, :]
    qp = positions[:, None, None, :, None]
    mask = jnp.logical_and(kp >= 0, kp <= qp)
    if window:
        mask = jnp.logical_and(mask, qp - kp < window)
    sel = jnp.where(mask, sel, jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(sel, axis=-1).astype(q.dtype)
    # value side: bucket each row's probs by source beam (one-hot over j')
    # and contract all-pairs — V is read once, never gathered
    pg = probs.reshape(b, kb, h_kv, group, nq, cache_len)
    onehot = jax.nn.one_hot(src_local, kb, axis=2, dtype=q.dtype)
    # onehot: [b, j, j', s]; pm: [b, j, j', n, g, q, s]
    pm = pg[:, :, None] * onehot[:, :, :, None, None, None, :]
    vg = v_all.reshape(b, kb, cache_len, h_kv, head_dim)
    out = jnp.einsum("bjpngqs,bpsnd->bjqngd", pm, vg)
    return out.reshape(rows, nq, h, head_dim)


def t5_relative_bucket(
    relative_position: jax.Array,
    bidirectional: bool,
    num_buckets: int,
    max_distance: int,
) -> jax.Array:
    """T5's relative-position bucketing (log-spaced beyond ``max_exact``).

    ``relative_position`` is ``k_pos - q_pos``.  Bidirectional stacks split
    the buckets between past and future; causal stacks bucket only the past
    (future positions land in bucket 0 and are masked out by the causal
    mask anyway).  Mirrors ``_relative_position_bucket`` in the canonical
    implementation so imported tables index identically.
    """
    rp = relative_position
    bucket = jnp.zeros_like(rp)
    if bidirectional:
        num_buckets = num_buckets // 2
        bucket = bucket + (rp > 0).astype(jnp.int32) * num_buckets
        rp = jnp.abs(rp)
    else:
        rp = -jnp.minimum(rp, 0)
    max_exact = num_buckets // 2
    is_small = rp < max_exact
    scaled = max_exact + (
        jnp.log(jnp.maximum(rp, 1).astype(jnp.float32) / max_exact)
        / jnp.log(max_distance / max_exact)
        * (num_buckets - max_exact)
    ).astype(jnp.int32)
    scaled = jnp.minimum(scaled, num_buckets - 1)
    return bucket + jnp.where(is_small, rp, scaled)


class RelativePositionBias(nn.Module):
    """T5-style bucketed per-head position bias, shared across a stack.

    ``(q_positions [Q], k_positions [K]) -> bias [1, n_heads, Q, K]``
    (fp32).  The bucket table is a tiny replicated param
    ``[num_buckets, n_heads]``; under TP the caller's Attention slices its
    local heads off the full-width bias.
    """

    config: TransformerConfig
    bidirectional: bool

    @nn.compact
    def __call__(self, q_positions: jax.Array, k_positions: jax.Array):
        cfg = self.config
        rel = k_positions[None, :] - q_positions[:, None]  # [Q, K]
        bucket = t5_relative_bucket(
            rel, self.bidirectional, cfg.rel_num_buckets, cfg.rel_max_distance
        )
        table = self.param(
            "rel_embedding",
            nn.initializers.normal(stddev=1.0),
            (cfg.rel_num_buckets, cfg.n_heads),
        )
        bias = jnp.asarray(table, jnp.float32)[bucket]  # [Q, K, H]
        return bias.transpose(2, 0, 1)[None]  # [1, H, Q, K]

    def for_step(
        self,
        positions: Optional[jax.Array],
        q_len: int,
        cache_len: int,
        decode: bool,
    ) -> jax.Array:
        """The positions-to-bias recipe shared by GPTLM and the seq2seq
        decoder: queries at ``positions`` (row 0 — every current caller
        broadcasts uniform positions; packed/ragged rows are refused
        upstream) against themselves (training) or every cache slot
        (``decode``)."""
        q_pos = positions[0] if positions is not None else jnp.arange(q_len)
        k_pos = jnp.arange(cache_len) if decode else q_pos
        return self(q_pos, k_pos)


def bidirectional_flash_attention(q, k, v, segment_ids=None, *, block_q,
                                  block_k, window=0):
    """Full-visibility flash attention: ONE non-causal "chunk" spanning the
    whole sequence (native GQA + in-kernel segment masking; lse discarded).
    ``window`` restricts to the symmetric band |q - k| < window (encoder
    local attention) with out-of-band key blocks skipped in-kernel.
    Shared by the encoder's flash path and its Ulysses inner attention."""
    from tpu_parallel.ops.flash_attention import flash_chunk_attention

    out, _ = flash_chunk_attention(
        q, k, v, causal=False, block_q=block_q, block_k=block_k, window=window,
        segment_ids_q=segment_ids, segment_ids_kv=segment_ids,
    )
    return out


class Attention(nn.Module):
    """Multi-head causal self-attention, heads sharded over the model axis.

    QKV is one fused column-parallel projection (each model rank owns
    ``n_heads / tp`` heads); the output projection is row-parallel, closing
    the Megatron f/g pair with a single psum.

    ``decode=True`` switches to incremental decoding: K/V are appended to a
    ``cache`` collection of length ``seq_len`` (created on first mutable
    apply), and queries attend to the full cache prefix.  The same path
    serves prefill (multi-token write at index 0) and per-token decode.

    ``write_index`` [batch] enables SLOT-INDEXED cache writes for the
    continuous-batching engine (``tpu_parallel.serving``): each row's
    K/V lands at its OWN cache slots (``write_index + [0..tokens)``)
    instead of the shared scalar ``cache_index`` — rows in the same step
    may sit at different depths of their generations, and a multi-token
    step extends a row's cache by one prompt chunk (the engine's chunked
    prefill).  A row whose ``write_index`` is parked at ``seq_len``
    drops its ENTIRE multi-token write (every target out of range /
    unmapped — the scatter-discard contract), which is what lets the
    engine's unified ragged tick run one fixed-shape chunk pass over
    the whole slot pool with only the prefilling rows landing writes.
    The attention read is unchanged (it already keys off the stored
    per-slot position table, not slot indices), so aligned and
    slot-indexed layouts read identically.
    """

    config: TransformerConfig
    # injected attention implementation; defaults resolved in __call__
    attn_fn: Optional[Callable] = None
    # this layer's kind (window, positions); None = the uniform model's
    spec: Optional[LayerSpec] = None

    @property
    def window(self) -> int:
        spec = self.spec or self.config.layer_specs[0]
        return spec.window if spec.attn == "window" else 0

    def _scope(self):
        """A model of several layer kinds names each kind's attention for
        the device trace (``attn.window`` / ``attn.full``); a uniform
        model's ops keep the names they had."""
        if self.config.block_len:
            return jax.named_scope("attn.block")
        if self.spec is None:
            return contextlib.nullcontext()
        return jax.named_scope(f"attn.{self.spec.attn}")

    @property
    def rotary(self) -> bool:
        positions = (self.spec or self.config.layer_specs[0]).positions
        if positions == "model":
            return self.config.positional == "rope"
        return positions == "rope"

    @nn.compact
    def __call__(
        self,
        x: jax.Array,
        positions: Optional[jax.Array] = None,
        segment_ids: Optional[jax.Array] = None,
        train: bool = True,
        decode: bool = False,
        cache_valid: Optional[jax.Array] = None,
        attn_bias: Optional[jax.Array] = None,
        write_index: Optional[jax.Array] = None,
        block_table: Optional[jax.Array] = None,
    ) -> jax.Array:
        cfg = self.config
        tp_size = axis_size_or_none(cfg.model_axis) or 1
        if attn_bias is not None and tp_size > 1:
            # the model-level bias covers all heads; keep this rank's slice
            lh = attn_bias.shape[1] // tp_size
            attn_bias = lax.dynamic_slice_in_dim(
                attn_bias, lax.axis_index(cfg.model_axis) * lh, lh, axis=1
            )
        n_kv = cfg.n_kv_heads or cfg.n_heads
        if cfg.n_heads % tp_size != 0:
            raise ValueError(f"n_heads={cfg.n_heads} not divisible by tp={tp_size}")
        if n_kv % tp_size != 0 or cfg.n_heads % n_kv != 0:
            raise ValueError(
                f"n_kv_heads={n_kv} must divide n_heads={cfg.n_heads} and be "
                f"divisible by tp={tp_size}"
            )
        local_heads = cfg.n_heads // tp_size
        local_kv = n_kv // tp_size
        if cfg.bidirectional:
            if decode:
                raise NotImplementedError(
                    "incremental decoding with bidirectional attention "
                    "(encoders do not autoregress)"
                )
        if n_kv == cfg.n_heads:
            qkv = TPDense(
                features=3 * cfg.n_heads * cfg.head_dim,
                axis_name=cfg.model_axis,
                style="column",
                use_bias=cfg.dense_bias,
                dtype=cfg.dtype,
                name="qkv",
            )(x)
            qkv = checkpoint_name(qkv, "proj")
            qkv = qkv.reshape(*x.shape[:-1], local_heads, 3 * cfg.head_dim)
            q, k, v = jnp.split(qkv, 3, axis=-1)
        else:
            # GQA: separate projections (Q is n_heads wide, KV n_kv wide)
            q = TPDense(
                features=cfg.n_heads * cfg.head_dim,
                axis_name=cfg.model_axis,
                style="column",
                use_bias=cfg.dense_bias,
                dtype=cfg.dtype,
                name="q",
            )(x)
            q = checkpoint_name(q, "proj").reshape(
                *x.shape[:-1], local_heads, cfg.head_dim
            )
            kv = TPDense(
                features=2 * n_kv * cfg.head_dim,
                axis_name=cfg.model_axis,
                style="column",
                use_bias=cfg.dense_bias,
                dtype=cfg.dtype,
                name="kv",
            )(x)
            kv = checkpoint_name(kv, "proj").reshape(
                *x.shape[:-1], local_kv, 2 * cfg.head_dim
            )
            k, v = jnp.split(kv, 2, axis=-1)
        if cfg.qk_norm:
            q, k = (
                nn.RMSNorm(epsilon=cfg.norm_eps, dtype=jnp.float32, name=name)(
                    t
                ).astype(cfg.dtype)
                for name, t in (("q_norm", q), ("k_norm", k))
            )
        if decode:
            if seq_parallel_active(cfg):
                raise NotImplementedError(
                    "incremental decoding under sequence parallelism"
                )
            if segment_ids is not None:
                raise NotImplementedError(
                    "incremental decoding with packed sequences (segment_ids)"
                )
            if cfg.block_len and cfg.beam_width > 1:
                raise NotImplementedError("the block rule under beam search")
            b = x.shape[0]
            if cfg.kv_cache_dtype not in ("bf16", "int8"):
                raise ValueError(
                    f"kv_cache_dtype={cfg.kv_cache_dtype!r} (bf16 | int8)"
                )
            quant_cache = cfg.kv_cache_dtype == "int8"
            cache_store_dtype = jnp.int8 if quant_cache else cfg.dtype
            paged = cfg.kv_block_tokens > 0
            if paged:
                # block-paged layout: K/V live in a FLAT pool of
                # kv_pool_blocks blocks of kv_block_tokens positions each,
                # shared by every row; rows address it through their
                # block_table entries.  The pool is row-count-free — slot
                # capacity decouples from seq_len.
                if cfg.kv_pool_blocks < 1:
                    raise ValueError(
                        f"kv_block_tokens={cfg.kv_block_tokens} needs "
                        f"kv_pool_blocks >= 1 (got {cfg.kv_pool_blocks})"
                    )
                if block_table is None or write_index is None:
                    raise ValueError(
                        "paged KV cache (kv_block_tokens > 0) requires "
                        "block_table AND write_index — the serving "
                        "engine's block-allocator path is the only caller"
                    )
                if cfg.beam_width > 1:
                    raise NotImplementedError(
                        "paged KV cache under lazy beam search (beam_src "
                        "bookkeeping assumes contiguous per-row caches)"
                    )
                kv_store = (
                    cfg.kv_pool_blocks, cfg.kv_block_tokens, local_kv,
                    cfg.head_dim,
                )
                scale_store = (
                    cfg.kv_pool_blocks, cfg.kv_block_tokens, local_kv, 1
                )
                pos_store = (cfg.kv_pool_blocks, cfg.kv_block_tokens)
            else:
                kv_store = (b, cfg.seq_len, local_kv, cfg.head_dim)
                scale_store = (b, cfg.seq_len, local_kv, 1)
                pos_store = (b, cfg.seq_len)
            # a prefill that CREATES its cache holds every key it may read in
            # this call: with prefill_flash it attends through the kernels
            # and never reads the seq_len-long stripe back
            fresh_prefill = (
                cfg.prefill_flash
                and x.shape[1] > 1
                and write_index is None
                and not self.has_variable("cache", "cached_key")
            )
            # cache at K/V-head width (local_kv): under GQA this is the whole
            # point — n_heads/n_kv less cache HBM; decode_attention contracts
            # grouped queries against it directly (no expansion)
            cached_k = self.variable(
                "cache",
                "cached_key",
                jnp.zeros,
                kv_store,
                cache_store_dtype,
            )
            cached_v = self.variable(
                "cache",
                "cached_value",
                jnp.zeros,
                kv_store,
                cache_store_dtype,
            )
            if quant_cache:
                # one fp32 scale per (position, kv-head): int8 payload + a
                # head_dim-th of fp32 ≈ half the bf16 cache HBM
                cached_k_scale = self.variable(
                    "cache",
                    "cached_key_scale",
                    jnp.zeros,
                    scale_store,
                    jnp.float32,
                )
                cached_v_scale = self.variable(
                    "cache",
                    "cached_value_scale",
                    jnp.zeros,
                    scale_store,
                    jnp.float32,
                )
            # per-slot global positions (int32) — the decode mask keys off
            # STORED positions, so ragged (left-padded) batches work: pad
            # slots hold -1 and never attend.  Aligned batches write j at
            # slot j, reproducing the classic layout.  Paged mode stores
            # the table per (block, offset); freed blocks are re-invalidated
            # to -1 by the allocator before reuse.
            cached_p = self.variable(
                "cache",
                "cached_pos",
                lambda: jnp.full(pos_store, -1, jnp.int32),
            )
            cache_index = self.variable(
                "cache", "cache_index", lambda: jnp.zeros((), jnp.int32)
            )
            idx = cache_index.value
            if positions is None:
                positions = jnp.broadcast_to(
                    idx + jnp.arange(x.shape[1])[None, :], x.shape[:2]
                )
        if self.rotary:
            if positions is None:
                local = jnp.arange(x.shape[1])
                if seq_parallel_active(cfg):
                    # seq-sharded: offset local positions to global ones
                    local = local + lax.axis_index(cfg.seq_axis) * x.shape[1]
                positions = jnp.broadcast_to(local, x.shape[:2])
            q = apply_rope(q, positions, cfg.rope_theta, cfg.rope_pairing)
            k = apply_rope(k, positions, cfg.rope_theta, cfg.rope_pairing)
        if decode:
            # cache_valid gates persistence (pipeline decode: only the rank
            # whose tick this is may commit writes — other ranks run the
            # same program on garbage activations and must leave their cache
            # untouched).  The attention read uses the fresh buffers either
            # way; invalid ticks' outputs are discarded downstream.
            if cache_valid is None:
                keep = lambda new, old: new
            else:
                keep = lambda new, old: jnp.where(cache_valid, new, old)
            if write_index is not None:
                # per-row slot writes (continuous batching): the update is a
                # batched scatter starting at each row's own index, not one
                # contiguous dynamic-slice.  Multi-token steps write each
                # row's tokens at write_index + [0..T) — the chunked-prefill
                # path (serving engine) extends a slot's cache one prompt
                # chunk at a time between decode ticks.
                if cfg.beam_width > 1:
                    raise NotImplementedError(
                        "write_index under lazy beam search (beam_src slot "
                        "bookkeeping assumes the shared scalar cache_index)"
                    )
                # [b, T] (a block step) gives every token a column of its own
                wi = write_index.astype(jnp.int32)
                if wi.ndim == 1:
                    wi = wi[:, None] + jnp.arange(x.shape[1])[None, :]
                if paged:
                    # logical column -> (physical block, offset) through the
                    # row's block table: table[row, col // bt] * bt +
                    # col % bt.  Unmapped (-1) table entries and logical
                    # blocks beyond the table width redirect to pool index
                    # kv_pool_blocks — out of range, DROPPED by scatter
                    # semantics, the same discard the contiguous layout's
                    # column-seq_len park relies on.
                    bt = cfg.kv_block_tokens
                    lblk = wi // bt
                    ok = lblk < block_table.shape[1]
                    phys = jnp.take_along_axis(
                        block_table, jnp.where(ok, lblk, 0), axis=1
                    )
                    phys = jnp.where(
                        ok & (phys >= 0), phys, cfg.kv_pool_blocks
                    )
                    off = wi % bt
                    upd = lambda buf, new: buf.at[phys, off].set(
                        new.astype(buf.dtype)
                    )
                else:
                    rows = jnp.arange(b)[:, None]
                    # out-of-range targets (a pool's free slots, a padded
                    # chunk's tail beyond seq_len) fall under JAX's default
                    # scatter semantics: the update is DROPPED, leaving the
                    # cache intact — deliberately not clamped, which would
                    # overwrite a valid boundary entry instead
                    upd = lambda buf, new: buf.at[rows, wi].set(
                        new.astype(buf.dtype)
                    )
            else:
                upd = lambda buf, new: lax.dynamic_update_slice_in_dim(
                    buf, new, idx, axis=1
                )
            k_scale = v_scale = None
            if quant_cache:
                from tpu_parallel.models.quantize import absmax_int8

                kq, ks = absmax_int8(k, axis=-1)
                vq, vs = absmax_int8(v, axis=-1)
                new_k = upd(cached_k.value, kq)
                new_v = upd(cached_v.value, vq)
                new_ks = upd(cached_k_scale.value, ks)
                new_vs = upd(cached_v_scale.value, vs)
                cached_k.value = keep(new_k, cached_k.value)
                cached_v.value = keep(new_v, cached_v.value)
                cached_k_scale.value = keep(new_ks, cached_k_scale.value)
                cached_v_scale.value = keep(new_vs, cached_v_scale.value)
                if cfg.beam_width > 1:
                    # the cross-beam all-pairs read has no scale fold yet:
                    # keep the transient dequantized copy on this path only
                    k_all = (
                        new_k.astype(jnp.float32) * new_ks
                    ).astype(cfg.dtype)
                    v_all = (
                        new_v.astype(jnp.float32) * new_vs
                    ).astype(cfg.dtype)
                else:
                    # int8-native read: the payloads go to decode_attention
                    # raw, scales fold into the score/value matmuls — no
                    # dequantized cache copy is materialized
                    k_all, v_all = new_k, new_v
                    k_scale, v_scale = new_ks, new_vs
            else:
                k_all = upd(cached_k.value, k)
                v_all = upd(cached_v.value, v)
                cached_k.value = keep(k_all, cached_k.value)
                cached_v.value = keep(v_all, cached_v.value)
            new_p = upd(cached_p.value, positions.astype(jnp.int32))
            cached_p.value = keep(new_p, cached_p.value)
            cache_index.value = keep(idx + x.shape[1], idx)
            if cfg.beam_width > 1:
                # lazy beam search: the cache rows are never re-gathered;
                # a per-slot source-row table follows beam ancestry instead.
                # This layer's contract: every slot IT writes maps to the
                # writing row (the beam loop row-gathers the table by winner
                # parents after each top-k).
                own_row = jnp.arange(b, dtype=jnp.int32)[:, None]
                beam_src = self.variable(
                    "cache",
                    "beam_src",
                    lambda: own_row + jnp.zeros((b, cfg.seq_len), jnp.int32),
                )
                new_src = lax.dynamic_update_slice_in_dim(
                    beam_src.value,
                    own_row + jnp.zeros((b, x.shape[1]), jnp.int32),
                    idx,
                    axis=1,
                )
                beam_src.value = keep(new_src, beam_src.value)
                out = beam_decode_attention(
                    q, k_all, v_all, positions, new_src, cfg.beam_width,
                    window=self.window, bias=attn_bias, k_positions=new_p,
                    scale=cfg.attn_scale,
                )
            elif fresh_prefill:
                if quant_cache or paged or attn_bias is not None:
                    raise NotImplementedError(
                        "prefill_flash with an int8 / paged cache or a "
                        "score bias"
                    )
                out = self._attend(q, k, v, None, impl="flash")
            else:
                k_pos = new_p
                if paged:
                    # assemble each row's LOGICAL K/V view by gathering its
                    # blocks out of the flat pool (one gather per payload;
                    # logical column c = pool[table[c // bt], c % bt]), so
                    # the attention math below is untouched and paged greedy
                    # output is bitwise identical to the contiguous layout
                    bt = cfg.kv_block_tokens
                    tbl = jnp.maximum(block_table, 0)

                    def pages(buf):
                        g = jnp.take(buf, tbl, axis=0)
                        return g.reshape(
                            b, tbl.shape[1] * bt, *buf.shape[2:]
                        )

                    k_all, v_all = pages(k_all), pages(v_all)
                    if k_scale is not None:
                        k_scale, v_scale = pages(k_scale), pages(v_scale)
                    # unmapped (-1) table entries gathered block 0's
                    # contents above — mask them out through the stored
                    # positions (-1 never attends)
                    mapped = jnp.repeat(block_table >= 0, bt, axis=1)
                    k_pos = jnp.where(mapped, pages(new_p), -1)
                # grouped queries contract against the kv-width cache directly
                attend = decode_attention_xla if paged else decode_attention
                with self._scope():
                    out = attend(
                        q, k_all, v_all, positions, window=self.window,
                        bias=attn_bias, k_positions=k_pos,
                        k_scale=k_scale, v_scale=v_scale,
                        scale=cfg.attn_scale, block_len=cfg.block_len,
                    )
        else:
            out = self._attend(q, k, v, segment_ids, attn_bias)
        if cfg.attn_impl != "flash":
            # let the "proj_attn" remat policy keep the attention context so
            # the backward never recomputes it — an O(seq) residual.  The
            # flash path already names its kernel-layout out+lse inside
            # ops/flash_attention.py; naming this transpose too would save
            # the same tensor twice.
            out = checkpoint_name(out, "attn")
        out = out.reshape(*x.shape[:-1], local_heads * cfg.head_dim)
        out = TPDense(
            features=cfg.d_model,
            axis_name=cfg.model_axis,
            style="row",
            use_bias=cfg.dense_bias,
            dtype=cfg.dtype,
            name="out",
        )(out)
        out = checkpoint_name(out, "proj")
        if cfg.dropout_rate > 0.0:
            out = nn.Dropout(rate=cfg.dropout_rate, deterministic=not train)(out)
        return out

    def _attend(self, q, k, v, segment_ids, attn_bias=None, impl=None):
        cfg = self.config
        if impl is not None and impl != cfg.attn_impl:
            cfg = dataclasses.replace(cfg, attn_impl=impl)
        if cfg.block_len and (
            self.attn_fn is not None or cfg.attn_impl not in ("xla", "flash")
        ):
            raise NotImplementedError(
                "block_len > 0 (the block rule) runs on the xla and flash "
                f"attention paths, not attn_impl={cfg.attn_impl!r} or an "
                "injected attn_fn"
            )
        if attn_bias is not None and cfg.attn_impl != "xla":
            # the Pallas/ring/ulysses kernels take no additive score bias;
            # T5-style models must run the xla attention path
            raise NotImplementedError(
                f"attention score bias (positional='relative') under "
                f"attn_impl={cfg.attn_impl!r} — use attn_impl='xla'"
            )
        group = q.shape[-2] // k.shape[-2]
        native_group = (
            cfg.attn_impl in ("flash", "ring", "ulysses")
            and self.attn_fn is None
        )
        if group != 1 and not native_group:
            # GQA head expansion for the paths without native group routing
            # (xla einsum, injected hooks).  XLA fuses this broadcast into
            # the einsum contractions.  The Pallas flash path must NOT take
            # it — kernel operands are materialized buffers, so it routes
            # groups via BlockSpec index maps; ring keeps K/V at kv-head
            # width because THEY ride the ppermute ring (group x less ring
            # traffic; the jnp ring contracts grouped queries natively,
            # like decode_attention); ulysses reshards kv heads at kv width
            # (group x less all_to_all volume) or expands internally when
            # h_kv doesn't divide the axis.
            k = jnp.repeat(k, group, axis=2)
            v = jnp.repeat(v, group, axis=2)
        if cfg.attn_scale is not None and (self.attn_fn or cfg.attn_impl != "xla"):
            # these paths scale by head_dim ** -0.5 themselves: fold the rest in
            q = q * jnp.asarray(cfg.attn_scale * q.shape[-1] ** 0.5, q.dtype)
        attn_fn = self.attn_fn
        if attn_fn is None:
            if cfg.attn_impl == "flash" and cfg.bidirectional:
                attn_fn = functools.partial(
                    bidirectional_flash_attention,
                    block_q=cfg.flash_block_q, block_k=cfg.flash_block_k,
                    window=self.window,
                )
            elif cfg.attn_impl == "flash":
                from tpu_parallel.ops.flash_attention import flash_attention

                attn_fn = functools.partial(
                    flash_attention,
                    block_q=cfg.flash_block_q,
                    block_k=cfg.flash_block_k,
                    window=self.window,
                    block_len=cfg.block_len,
                )
            elif cfg.attn_impl == "ring":
                from tpu_parallel.ops.ring_attention import (
                    ring_attention,
                    ring_flash_attention,
                )

                # flash-composed ring on TPU; the jnp path elsewhere (the
                # interpret-mode kernels can't declare vma for the trainer's
                # replication checker, and CPU gains nothing from them).
                # segment_ids (packed sequences) are the LOCAL chunk's ids —
                # both impls rotate them around the ring with their K/V.
                if jax.default_backend() == "tpu":

                    def attn_fn(q, k, v, segment_ids=None):
                        return ring_flash_attention(
                            q, k, v, axis_name=cfg.seq_axis,
                            block_q=cfg.flash_block_q,
                            block_k=cfg.flash_block_k,
                            window=self.window,
                            segment_ids=segment_ids,
                            causal=not cfg.bidirectional,
                        )

                else:

                    def attn_fn(q, k, v, segment_ids=None):
                        return ring_attention(
                            q, k, v, axis_name=cfg.seq_axis,
                            window=self.window,
                            segment_ids=segment_ids,
                            causal=not cfg.bidirectional,
                        )

            elif cfg.attn_impl == "ulysses":
                from tpu_parallel.ops.flash_attention import flash_attention
                from tpu_parallel.ops.ulysses import ulysses_attention

                # the inner attention sees the full gathered sequence, so the
                # window band (causal) or full visibility (bidirectional)
                # applies directly
                if cfg.bidirectional:
                    inner = functools.partial(
                        bidirectional_flash_attention,
                        block_q=cfg.flash_block_q,
                        block_k=cfg.flash_block_k,
                        window=self.window,
                    )
                else:
                    inner = functools.partial(
                        flash_attention,
                        block_q=cfg.flash_block_q,
                        block_k=cfg.flash_block_k,
                        window=self.window,
                    )

                def attn_fn(q, k, v, segment_ids=None):
                    if segment_ids is not None:
                        # packed sequences: the inner attention needs the
                        # whole sequence's ids — a tiny int32 all_gather
                        # (the activations already pay two all_to_alls)
                        segment_ids = lax.all_gather(
                            segment_ids, cfg.seq_axis, axis=1, tiled=True
                        )
                    return ulysses_attention(
                        q, k, v, axis_name=cfg.seq_axis, attn_fn=inner,
                        segment_ids=segment_ids,
                    )

            else:
                attn_fn = functools.partial(
                    causal_attention, window=self.window,
                    causal=not cfg.bidirectional, bias=attn_bias,
                    scale=cfg.attn_scale, block_len=cfg.block_len,
                )
        with self._scope():
            return attn_fn(q, k, v, segment_ids=segment_ids)


class MLP(nn.Module):
    """Transformer MLP: column-up / row-down (Megatron pair).

    Activations: gelu (GPT-2 tanh form), gelu_exact (BERT erf form), relu
    (original T5), swiglu (Llama, silu-gated), geglu (T5 v1.1 — gated by
    the TANH-approximate gelu, what HF's "gated-gelu" resolves to).  Gated
    variants use two column projections (gate/up), bias-free (no gated
    checkpoint family carries them).
    """

    config: TransformerConfig

    @nn.compact
    def __call__(self, x: jax.Array, train: bool = True) -> jax.Array:
        cfg = self.config
        hidden = cfg.mlp_dim
        gated = cfg.mlp in ("swiglu", "geglu")
        if gated:
            gate = TPDense(
                features=hidden, axis_name=cfg.model_axis, style="column",
                dtype=cfg.dtype, use_bias=False, name="gate",
            )(x)
            up = TPDense(
                features=hidden, axis_name=cfg.model_axis, style="column",
                dtype=cfg.dtype, use_bias=False, name="up",
            )(x)
            # geglu's gate is gelu_new (the tanh approximation) — what T5
            # v1.1's "gated-gelu" resolves to in the canonical implementation
            act = (
                nn.silu
                if cfg.mlp == "swiglu"
                else functools.partial(nn.gelu, approximate=True)
            )
            h = act(checkpoint_name(gate, "proj")) * checkpoint_name(up, "proj")
        else:
            h = TPDense(
                features=hidden, axis_name=cfg.model_axis, style="column",
                use_bias=cfg.dense_bias, dtype=cfg.dtype, name="up",
            )(x)
            h = checkpoint_name(h, "proj")
            if cfg.mlp == "relu":
                h = nn.relu(h)
            else:
                h = nn.gelu(h, approximate=cfg.mlp != "gelu_exact")
        y = TPDense(
            features=cfg.d_model, axis_name=cfg.model_axis, style="row",
            dtype=cfg.dtype, use_bias=not gated and cfg.dense_bias, name="down",
        )(h)
        y = checkpoint_name(y, "proj")
        if cfg.dropout_rate > 0.0:
            y = nn.Dropout(rate=cfg.dropout_rate, deterministic=not train)(y)
        return y


def _scaled(fn, scale: float, *args, **kwargs):
    """``fn``'s output times the residual multiplier, in its own type."""
    y = fn(*args, **kwargs)
    return y * jnp.asarray(scale, y.dtype)


def make_mixer(config: TransformerConfig, spec: Optional[LayerSpec]):
    """A block's token mixer by the layer's kind: :class:`Attention`
    (``"attn"``), the recurrent :class:`~tpu_parallel.models.ssm.SSMMixer`
    (``"ssm"``) or None (``"none"``), its output times the residual
    multiplier the config states.  Called inside the block's compact method."""
    kind = spec or config.layer_specs[0]
    if kind.mixer == "ssm":
        from tpu_parallel.models.ssm import SSMMixer

        mixer = SSMMixer(config, kind.ssm, name="ssm")
    elif kind.mixer == "attention":
        mixer = attention_module(config, spec, kind)
    else:
        return _no_mixer(kind)
    if config.residual_scale == 1.0:
        return mixer
    return functools.partial(_scaled, mixer, config.residual_scale)


class Block(nn.Module):
    """Pre-norm transformer block: x + attn(norm(x)); x + mlp(norm(x)).
    ``config.parallel_block``: x + attn(h) + mlp(h), both from h = norm(x).
    ``spec``: this layer's kind; one without a mixer or MLP: x + f(norm(x))."""

    config: TransformerConfig
    spec: Optional[LayerSpec] = None

    @nn.compact
    def __call__(
        self,
        x: jax.Array,
        positions: Optional[jax.Array] = None,
        segment_ids: Optional[jax.Array] = None,
        train: bool = True,
        decode: bool = False,
        aux_scale: Optional[jax.Array] = None,
        cache_valid: Optional[jax.Array] = None,
        attn_bias: Optional[jax.Array] = None,
        write_index: Optional[jax.Array] = None,
        block_table: Optional[jax.Array] = None,
    ) -> jax.Array:
        cfg = self.config
        spec = self.spec or cfg.layer_specs[0]
        capacity_experts = spec.mlp == "experts" and spec.experts is None
        if decode and capacity_experts and cfg.moe_router == "expert_choice":
            # EC routes over the whole token pool; a single-token decode
            # step degenerates to a dense all-expert mixture that resembles
            # nothing the model trained on — refuse loudly
            raise NotImplementedError(
                "incremental decoding with expert-choice routing "
                "(the routing pool collapses to one token per row)"
            )
        attn = make_mixer(cfg, self.spec)
        mlp_fn = (
            lambda h: MLP(cfg, name="mlp")(h, train=train)
        )
        if capacity_experts:
            from tpu_parallel.models.moe import MoEMLP

            mlp_fn = lambda h: MoEMLP(cfg, name="moe")(
                h, train=train, aux_scale=aux_scale
            )
        elif spec.mlp == "experts":
            from tpu_parallel.models.moe import RoutedExperts

            mlp_fn = lambda h: RoutedExperts(cfg, spec.experts, name="moe")(
                h, valid=None if positions is None else positions >= 0
            )
        if cfg.residual_scale != 1.0:
            mlp_fn = functools.partial(_scaled, mlp_fn, cfg.residual_scale)
        attn_kwargs = dict(
            positions=positions, segment_ids=segment_ids, train=train,
            decode=decode, cache_valid=cache_valid, attn_bias=attn_bias,
            write_index=write_index, block_table=block_table)
        if cfg.sandwich_norm:  # a norm on each sublayer's output too
            return sandwich_block(cfg, x, attn, attn_kwargs, mlp_fn)
        if "none" in (spec.mixer, spec.mlp):  # ONE sublayer behind ONE norm
            return one_sublayer(
                cfg, spec, x, attn, attn_kwargs, mlp_fn
            )
        if cfg.parallel_block:
            if not cfg.prenorm:
                raise ValueError("parallel_block is a pre-norm block")
            h = make_norm(cfg, "norm")(x).astype(cfg.dtype)
            x = x + attn(h, **attn_kwargs) + mlp_fn(h)
        elif cfg.prenorm:
            h = make_norm(cfg, "norm_attn")(x).astype(cfg.dtype)
            x = x + attn(h, **attn_kwargs)
            h = make_norm(cfg, "norm_mlp")(x).astype(cfg.dtype)
            x = x + mlp_fn(h)
        else:
            # post-norm (original BERT): normalize the residual SUM
            x = make_norm(cfg, "norm_attn")(x + attn(x, **attn_kwargs)).astype(
                cfg.dtype
            )
            x = make_norm(cfg, "norm_mlp")(x + mlp_fn(x)).astype(cfg.dtype)
        return x


class _ScanBlock(nn.Module):
    """nn.scan target: ``group`` Block(s) per tick, carrying (x, positions,
    segment_ids, aux_scale, cache_valid).  ``block_cls`` lets BlockStack
    substitute the FSDP-wrapped Block (static metadata — both classes produce
    the same variable tree shape, the wrapped one with data-sharded leaves).

    ``group > 1`` (``config.scan_group``) applies that many consecutive
    blocks per scan tick: the carry (the [B, S, d] residual stream) is
    materialized at tick boundaries only, so grouping divides the per-tick
    HBM round-trips by ``group`` while keeping compile size at
    ``n_layers / group`` of the unrolled cost.  Distinct from
    ``scan_unroll`` (which unrolls the LOOP but keeps one block per carry
    round-trip — measured slower, see TransformerConfig.scan_unroll).
    Group 1 keeps the historical single-block param naming ("block")."""

    config: TransformerConfig
    train: bool
    decode: bool = False
    block_cls: Any = Block
    group: int = 1

    @nn.compact
    def __call__(self, carry, _):
        (
            x, positions, segment_ids, aux_scale, cache_valid, attn_bias,
            write_index, block_table,
        ) = carry
        period = self.config.layer_specs
        for j in range(self.group):
            name = "block" if self.group == 1 else f"block{j}"
            spec = period[j % len(period)] if len(period) > 1 else None
            x = self.block_cls(self.config, spec, name=name)(
                x,
                positions=positions,
                segment_ids=segment_ids,
                train=self.train,
                decode=self.decode,
                aux_scale=aux_scale,
                cache_valid=cache_valid,
                attn_bias=attn_bias,
                write_index=write_index,
                block_table=block_table,
            )
        return (
            (
                x, positions, segment_ids, aux_scale, cache_valid, attn_bias,
                write_index, block_table,
            ),
            None,
        )


def remat_kwargs_for(config: TransformerConfig) -> dict:
    """``nn.remat`` kwargs for a layer stack under ``config.remat_policy``.

    prevent_cse=False is safe (and fastest) under scan for plain remat, but
    with a save-policy XLA can CSE the "recompute" against the forward and
    hoist per-layer score tensors out of the scan — 9G+ of stacked
    [layers, B, H, S, S] buffers.  Keep CSE prevention on when a policy
    narrows the saveable set.
    """
    remat_kwargs = dict(prevent_cse=config.remat_policy != "full")
    if config.remat_policy == "dots":
        remat_kwargs["policy"] = (
            jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        )
    elif config.remat_policy == "proj":
        remat_kwargs["policy"] = jax.checkpoint_policies.save_only_these_names(
            "proj"
        )
    elif config.remat_policy == "proj_attn":
        remat_kwargs["policy"] = jax.checkpoint_policies.save_only_these_names(
            "proj", "attn"
        )
    return remat_kwargs


class BlockStack(nn.Module):
    """``n_layers`` blocks, optionally remat'd and scanned.

    ``nn.scan`` stacks per-layer params along a leading axis
    (``PARTITION_NAME=None`` keeps flax's Partitioned metadata consistent);
    compile time is then constant in depth.  ``nn.remat`` trades recompute
    for HBM — the standard TPU recipe for 125M+ models.
    """

    config: TransformerConfig
    n_layers: int

    @nn.compact
    def __call__(
        self,
        x: jax.Array,
        positions: Optional[jax.Array] = None,
        segment_ids: Optional[jax.Array] = None,
        train: bool = True,
        decode: bool = False,
        aux_scale: Optional[jax.Array] = None,
        cache_valid: Optional[jax.Array] = None,
        attn_bias: Optional[jax.Array] = None,
        write_index: Optional[jax.Array] = None,
        block_table: Optional[jax.Array] = None,
    ) -> jax.Array:
        cfg = self.config
        period = cfg.layer_specs
        if (self.n_layers - len(cfg.layer_head)) % len(period) != 0:
            raise ValueError(
                f"a stack of {self.n_layers} layers is not a whole number "
                f"of periods of {len(period)}"
            )
        remat_kwargs = remat_kwargs_for(cfg)
        # ZeRO-3 over the layers themselves: each tick (scan) or layer
        # (unrolled) gathers ITS params just-in-time and the backward
        # re-gathers under remat, so peak HBM holds one layer's full weights
        # — without this wrap `fsdp=True` sharded only the embeddings/lm_head
        # and the block stack (the bulk of the model) stayed replicated over
        # the data axis.  The wrap sits INSIDE nn.remat: the all_gather is
        # recomputed, never saved.
        base_block: Any = fsdp.maybe_shard(Block, cfg)
        if cfg.scan_layers:
            if seq_parallel_active(cfg):
                # seq-parallel attention output is seq-varying (axis_index /
                # all_to_all inside), so the layer-scan carry must enter
                # seq-varying too — otherwise a size-1 seq axis trips the
                # replication checker (inputs replicated, body output varying)
                from tpu_parallel.core.metrics import pvary_missing, vma_of

                x = pvary_missing(
                    x, vma_of(jax.lax.axis_index(cfg.seq_axis))
                )
            if (
                cfg.moe_experts > 0
                and cfg.moe_dispatch == "alltoall"
                and axis_size_or_none(cfg.model_axis) is not None
            ):
                # same carry-typing rule for the a2a MoE: its closing
                # all_gather leaves the block output model-VARYING (the
                # values are identical across ranks, but the checker can't
                # prove it), so the carry must enter model-varying too
                from tpu_parallel.core.metrics import pvary_missing

                x = pvary_missing(x, (cfg.model_axis,))
            group = max(1, cfg.scan_group)
            if group % len(period) != 0:
                raise ValueError(
                    f"scan_group={group} must hold whole periods of "
                    f"{len(period)} layer kinds (the scanned body is one "
                    "period or several)"
                )
            if self.n_layers % group != 0:
                raise ValueError(
                    f"scan_group={group} must divide n_layers={self.n_layers}"
                )
            scan_target = _ScanBlock
            if cfg.remat and not decode:
                scan_target = nn.remat(_ScanBlock, **remat_kwargs)
            # no divisibility requirement: lax.scan peels a remainder step
            stacked = nn.scan(
                scan_target,
                variable_axes={"params": 0, "cache": 0, "losses": 0},
                variable_broadcast=False,
                split_rngs={"params": True, "dropout": True},
                length=self.n_layers // group,
                unroll=cfg.scan_unroll,
                _split_transpose=cfg.scan_split_transpose,
                metadata_params={nn.PARTITION_NAME: None},
            )(cfg, train, decode, base_block, group, name="layers")
            (x, _, _, _, _, _, _, _), _ = stacked(
                (
                    x, positions, segment_ids, aux_scale, cache_valid,
                    attn_bias, write_index, block_table,
                ),
                None,
            )
        else:
            # static_argnums: train/decode are Python bools branching the
            # trace (self=0, x=1, positions=2, segment_ids=3, train=4,
            # decode=5) — without it nn.remat traces them as jnp bools and
            # every `if train` raises TracerBoolConversionError
            block_cls = (
                nn.remat(base_block, static_argnums=(4, 5), **remat_kwargs)
                if cfg.remat and not decode
                else base_block
            )
            for i in range(self.n_layers):
                spec = spec_at(cfg, i)  # None: the uniform model's one kind
                x = block_cls(cfg, spec, name=f"layer_{i}")(
                    x, positions, segment_ids, train, decode, aux_scale,
                    cache_valid, attn_bias, write_index, block_table,
                )
        return x


def tied_logits(config: TransformerConfig, table: jax.Array, hidden):
    """``hidden E^T * logit_scale`` against the token embedding ``table``
    ``[vocab, d_model]``: the output head of ``tie_embeddings`` models."""
    if axis_size_or_none(config.model_axis) is not None:
        raise NotImplementedError(
            "tie_embeddings under a bound model axis (the tied table is not "
            "vocabulary-sharded)"
        )
    logits = jnp.einsum(
        "...d,vd->...v", hidden.astype(config.dtype),
        jnp.asarray(table, config.dtype),
    )
    if config.logit_scale != 1.0:
        logits = logits * config.logit_scale
    return logits


class Embedding(nn.Module):
    """Token (+ learned positional) embedding, bf16 output; with
    ``attend=True`` the tied output head over a hidden state."""

    config: TransformerConfig

    @nn.compact
    def __call__(
        self,
        tokens: jax.Array,
        positions: Optional[jax.Array] = None,
        attend: bool = False,
    ) -> jax.Array:
        cfg = self.config
        tok = nn.Embed(
            num_embeddings=cfg.vocab_size,
            features=cfg.d_model,
            dtype=cfg.dtype,
            name="tok",
        )
        if attend:
            # the tied output head: ``tokens`` is the final hidden state
            return tied_logits(cfg, tok.embedding, tokens)
        emb = tok(tokens)
        if cfg.embed_scale != 1.0:
            emb = emb * jnp.asarray(cfg.embed_scale, emb.dtype)
        if cfg.positional == "learned":
            if positions is None:
                local = jnp.arange(tokens.shape[1])
                if seq_parallel_active(cfg):
                    # seq-sharded tokens: offset local positions to global
                    # ones so each shard embeds ITS rows of the table (the
                    # rope analog lives inside Attention)
                    local = local + lax.axis_index(cfg.seq_axis) * tokens.shape[1]
                positions = jnp.broadcast_to(local, tokens.shape)
            pos_emb = nn.Embed(
                num_embeddings=cfg.seq_len,
                features=cfg.d_model,
                dtype=cfg.dtype,
                name="pos",
            )(positions)
            emb = emb + pos_emb
        if cfg.embed_norm:
            # BERT's embeddings.LayerNorm over the summed embedding
            emb = make_norm(cfg, "norm")(emb).astype(cfg.dtype)
        return emb


def decode_attention(
    q: jax.Array, k_all: jax.Array, v_all: jax.Array, positions: jax.Array,
    window: int = 0, bias: Optional[jax.Array] = None,
    k_positions: Optional[jax.Array] = None,
    k_scale: Optional[jax.Array] = None,
    v_scale: Optional[jax.Array] = None,
    scale: Optional[float] = None,
    block_len: int = 0,
) -> jax.Array:
    """Attention of new queries against a full KV cache: the operands and the
    result of :func:`decode_attention_xla`, which runs every shape but those
    that ``ops.decode_attention.decode_attention_plan`` sends to the Pallas
    kernel over the stored stripes (bfloat16 heads of a multiple of 128, no
    bias, no int8 scales, at least two tiles of stored positions).  The rule
    sees shapes and operand kinds only; both paths apply one mask
    (``ops.decode_attention.visible_bounds``), the kernel to float32 scores
    that never leave the chip.  A row that sees no key (a pad at -1, a parked
    slot) gets zeros from the kernel and the mean of V from the ``jax.numpy``
    body: it is discarded downstream either way.  The paged gather has copied
    the stripe already and calls the ``jax.numpy`` body itself.

    (Down here, below every frame that reaches a ``pallas_call``: a kernel's
    bytes carry the line numbers of those frames, and a function added above
    them would recompile every kernel program of every cell once a machine.)
    """
    from tpu_parallel.ops import decode_attention as stripes

    plan = stripes.decode_attention_plan(
        q.shape, k_all.shape, q.dtype, k_all.dtype,
        bias=bias is not None, scales=k_scale is not None,
    )
    if plan is None:
        return decode_attention_xla(
            q, k_all, v_all, positions, window=window, bias=bias,
            k_positions=k_positions, k_scale=k_scale, v_scale=v_scale,
            scale=scale, block_len=block_len,
        )
    if k_positions is None:  # the aligned layout: slot j holds position j
        k_positions = jnp.broadcast_to(
            jnp.arange(k_all.shape[1]), k_all.shape[:2]
        )
    lo, hi = stripes.visible_bounds(positions, window, block_len)
    return stripes.decode_stripes(
        q * _score_scale(scale, q.shape[-1], q.dtype), k_all, v_all, lo, hi,
        k_positions, tile=plan["tile"],
        vmem_limit_bytes=plan["vmem_limit_bytes"],
    )


def _no_mixer(kind: LayerSpec) -> None:
    """``make_mixer`` for a layer that says it has none."""
    if kind.mixer != "none":
        raise ValueError(
            f"LayerSpec.mixer={kind.mixer!r} (attention | ssm | none)"
        )
    return None


def one_sublayer(
    config: TransformerConfig, spec: LayerSpec, x, mixer, mixer_kwargs, mlp_fn
):
    """A block that is ONE sublayer behind ONE norm, ``x + f(norm(x))`` with
    ``f`` the layer's mixer (``spec.mlp == "none"``) or its feed-forward part
    (``spec.mixer == "none"``).  The absent half creates no parameter and no
    cache leaf.  Called inside :class:`Block`'s compact method (down here for
    the reason :func:`decode_attention` gives)."""
    if spec.mixer == "none" and spec.mlp == "none":
        raise ValueError("a layer with neither a mixer nor a feed-forward part")
    if config.parallel_block or not config.prenorm:
        raise ValueError(
            "a one-sublayer block is pre-norm and has nothing to run in "
            "parallel with"
        )
    h = make_norm(config, "norm")(x).astype(config.dtype)
    if spec.mixer == "none":
        return x + mlp_fn(h)
    return x + mixer(h, **mixer_kwargs)


def layer_kinds(config: TransformerConfig) -> dict:
    """Layers by kind over the whole depth: ``{"ssm", "attention", "experts",
    "dense"}`` count sublayers (a two-sublayer block counts in two of them),
    ``"layers"`` the depth."""
    out = {"layers": config.n_layers}
    for s in depth_specs(config):
        for kind in (s.mixer, s.mlp):
            if kind != "none":
                out[kind] = out.get(kind, 0) + 1
    return out


# --- leading layers, sandwich norms, latent attention --------------------------
# (every line below was added under the last frame that reaches a kernel)


@dataclasses.dataclass(frozen=True)
class LatentSpec:
    """A latent attention layer's five sizes (``LayerSpec.attn="latent"``,
    ``models/latent_attention.py``): queries go through a latent of
    ``q_rank``, keys and values through ONE of ``kv_rank`` a position that all
    heads share; a head scores at ``nope_dim + rope_dim`` (the rotary part is
    ``rope_dim`` columns and ONE key for all heads) and sums values at
    ``v_dim``.  The cache row is ``kv_rank + rope_dim`` wide."""

    q_rank: int
    kv_rank: int
    nope_dim: int
    rope_dim: int
    v_dim: int

    @property
    def row(self) -> int:
        """Numbers a position stores: the normed latent and the rotary key."""
        return self.kv_rank + self.rope_dim


def depth_specs(config: TransformerConfig) -> Tuple[LayerSpec, ...]:
    """Every layer's kind over the whole depth: the leading layers, then the
    period repeated."""
    period = config.layer_specs
    repeats = (config.n_layers - len(config.layer_head)) // len(period)
    return tuple(config.layer_head) + tuple(period) * repeats


def spec_at(config: TransformerConfig, i: int) -> Optional[LayerSpec]:
    """Layer ``i``'s kind for its :class:`Block`; None for a uniform model
    (one kind, no leading layers), whose ops keep the names they had."""
    if not config.layer_head and len(config.layer_specs) == 1:
        return None
    return depth_specs(config)[i]


def check_layer_kinds(config: TransformerConfig) -> None:
    """``TransformerConfig.__post_init__``'s checks of what the layer kinds
    combine with: raises ``ValueError`` for what is not written."""
    head, pattern = config.layer_head, config.layer_pattern
    if pattern is not None or head:
        body = config.n_layers - len(head)
        if pattern is not None and not pattern or body < 0 or body % len(
            config.layer_specs
        ):
            raise ValueError(
                f"n_layers={config.n_layers} is not {len(head)} leading "
                f"layer(s) and a whole number of periods of "
                f"{len(pattern or (None,))} layers"
            )
    if head and config.scan_layers:
        raise ValueError(
            "layer_head (leading layers before the period) runs on unrolled "
            "stacks: set scan_layers=False (a scanned body is one period)"
        )
    specs = depth_specs(config)
    if config.block_len < 0 or (config.block_len and (
        config.bidirectional or config.recurrent_layers
        or any(s.attn != "full" for s in specs)
    )):
        raise ValueError(
            f"block_len={config.block_len}: the block rule is causal across "
            "blocks and full inside one; it does not combine with a "
            "window, a bidirectional stack, a latent layer or a recurrent "
            "layer (whose state cannot take a block back)"
        )
    if config.sandwich_norm and (
        config.parallel_block or not config.prenorm
        or config.residual_scale != 1.0
        or any("none" in (s.mixer, s.mlp) for s in specs)
    ):
        raise ValueError(
            "sandwich_norm is a pre-norm block of two sublayers, each between "
            "two norms: no parallel_block, post-norm, residual multiplier or "
            "one-sublayer layer beside it"
        )
    for s in specs:
        if s.attn != "latent" or s.mixer != "attention":
            continue
        refused = {
            "no LatentSpec (LayerSpec.latent)": s.latent is None,
            "a window": bool(s.window or config.attn_window),
            "int8 K/V (kv_cache_dtype)": config.kv_cache_dtype != "bf16",
            "the paged pool (kv_block_tokens)": config.kv_block_tokens > 0,
            "a bidirectional stack": config.bidirectional,
            "lazy beam search (beam_width)": config.beam_width > 1,
            "a relative score bias": config.positional == "relative",
            "q/k norms over a head (its latents have their own)": config.qk_norm,
            "sequence parallelism": config.attn_impl in ("ring", "ulysses"),
            "positions other than rotary": not (
                s.positions == "rope"
                or s.positions == "model" and config.positional == "rope"
            ),
        }
        for what, asked in refused.items():
            if asked:
                raise ValueError(
                    f"a latent attention layer does not run with {what}: its "
                    "cache is one row a position that all heads share, read "
                    "by the absorbed form (models/latent_attention.py)"
                )


def attention_module(config: TransformerConfig, spec, kind: LayerSpec):
    """``make_mixer``'s attention by the layer's kind: :class:`Attention`, or
    the latent sibling behind the same call."""
    if kind.attn == "latent":
        from tpu_parallel.models.latent_attention import LatentAttention

        return LatentAttention(config, kind.latent, name="attn")
    return Attention(config, spec=spec, name="attn")


def sandwich_block(config: TransformerConfig, x, mixer, mixer_kwargs, mlp_fn):
    """A block with sandwich norms: ``a = x + N(mixer(N(x)))``, ``y = a +
    N(mlp(N(a)))``, four norms a layer.  Called inside :class:`Block`'s
    compact method (its combinations are checked at construction)."""
    norm = lambda name, y: make_norm(config, name)(y).astype(config.dtype)
    x = x + norm("norm_post_attn", mixer(norm("norm_attn", x), **mixer_kwargs))
    return x + norm("norm_post_mlp", mlp_fn(norm("norm_mlp", x)))
