"""Decoder-only transformer LM, composable over DP x FSDP x TP x PP meshes.

The flagship model family for the BASELINE.json matrix: GPT-2 125M/350M
(learned positions, LayerNorm, gelu) and Llama-style (RoPE, RMSNorm, SwiGLU)
via :class:`~tpu_parallel.models.layers.TransformerConfig` switches.  No
reference model exists to mirror (the reference trains 2-layer MLPs only);
the parallelism semantics follow the framework's strategy modules:

- TP: structural (TPDense everywhere; identity on tp=1 meshes).
- FSDP: ``config.fsdp`` wraps each Block / embedding in
  ``fsdp.shard_module_params`` over the data axis — gathers are per-block,
  so peak HBM holds one block's full weights, not the model's.
- PP: ``pipe_size > 1`` runs the block stack as GPipe stages over the pipe
  axis.  Logits are then valid on the **last** pipe rank only — train with
  :func:`make_gpt_loss`, which masks by :func:`pp.last_stage_mask`.
  ``positions``/``segment_ids`` (packed sequences) ride as pipeline extras:
  each rank indexes its current microbatch's slice of the replicated
  arrays — no extra ring traffic.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from tpu_parallel.core.losses import token_ce_and_argmax
from tpu_parallel.core.metrics import Metrics, pvary_missing, vma_of
from tpu_parallel.core.rng import fold_rng_over_axis
from tpu_parallel.models.layers import (
    Attention,
    Block,
    BlockStack,
    Embedding,
    ExpertsSpec,
    LayerSpec,
    RelativePositionBias,
    TransformerConfig,
    make_norm,
    tied_logits,
)
from tpu_parallel.parallel import fsdp, pp
from tpu_parallel.parallel.tp import TPDense


@dataclasses.dataclass(frozen=True)
class GPTConfig(TransformerConfig):
    """TransformerConfig plus pipeline degree (static model knobs only)."""

    pipe_size: int = 1  # number of pipeline stages the block stack is cut into
    # virtual stages per pipe rank (circular schedule).  >1 cuts the GPipe
    # bubble ~interleave-fold: rank r holds layer chunks r, r+pipe,
    # r+2*pipe, ... and activations lap the ring `interleave` times.  Not
    # yet composable with MoE (nn.switch requires identical variable
    # writes across branches; each chunk sows its own balance loss).
    pipe_interleave: int = 1
    # pipeline TRAINING schedule: "gpipe" differentiates through the full
    # microbatch schedule (activation memory grows with num_microbatches);
    # "1f1b" computes gradients inside a one-forward-one-backward schedule
    # that bounds in-flight microbatches at pipe_size per rank (see
    # parallel/pp.py pipeline_1f1b_grads) at the cost of ~pipe_size extra
    # bubble ticks.  Same math (grad-parity pinned in tests/test_pp.py);
    # forward/eval/serving always run the GPipe/ring paths.  Not yet
    # composable with pipe_interleave > 1 or MoE.
    pipe_schedule: str = "gpipe"
    # chunked lm_head + CE: compute logits ``loss_chunk`` sequence positions
    # at a time inside the loss (rematerialized in the backward), so the full
    # [B, S, vocab] logits tensor never exists in HBM.  0 = off.  The
    # dominant-memory fix for large batches at GPT-2 vocab (50304): full
    # logits are ~3 GB bf16 per 32x1024 batch, twice that with their
    # gradient.  Costs one extra lm_head matmul in the backward (~9% of
    # model FLOPs) — a win whenever it unlocks a larger batch.
    loss_chunk: int = 0


def _make_lm_head(
    cfg: "GPTConfig",
    name: Optional[str] = "lm_head",
    gather: bool = True,
    fsdp_wrap: bool = True,
):
    """The vocab projection — one definition for the in-model call and the
    standalone apply in :func:`make_gpt_loss` (``name=None``; the loss binds
    it directly to ``params["lm_head"]``).  The loss path passes
    ``gather=False``: logits stay column-sharded over the model axis and CE
    runs vocab-parallel (``core.losses.vocab_parallel_cross_entropy``) —
    the public model surface keeps full-vocab logits for generation/interop.
    The parameter tree is identical either way.

    Under ``cfg.fsdp`` the head is FSDP-wrapped like the blocks (the vocab
    kernel is among the largest single params in the model).  Callers that
    apply the head repeatedly in a scan (chunked CE, the decode loop) pass
    ``fsdp_wrap=False`` and pre-gather via :func:`_lm_head_params` ONCE
    outside the loop — the wrapped module would re-all_gather the kernel
    every iteration (jax.checkpoint pins the gather inside the scan body, so
    XLA cannot hoist it)."""
    cls = fsdp.maybe_shard(TPDense, cfg) if fsdp_wrap else TPDense
    return cls(
        features=cfg.vocab_size,
        axis_name=cfg.model_axis,
        style="column",
        gather_output=gather,
        use_bias=False,
        dtype=cfg.dtype,
        name=name,
    )


def _apply_lm_head(cfg: "GPTConfig", lm_params, hidden, gather: bool = False):
    """The output head over ``hidden`` from :func:`_lm_head_params`' tree:
    the tied token embedding where ``cfg.tie_embeddings``, else the
    ``lm_head`` projection (``gather`` all-gathers a model-sharded
    vocabulary)."""
    if cfg.tie_embeddings:
        return tied_logits(cfg, lm_params["embedding"], hidden)
    head = _make_lm_head(cfg, name=None, gather=gather, fsdp_wrap=False)
    return head.apply({"params": lm_params}, hidden)


def lm_logits(cfg: "GPTConfig", params, hidden, gather: bool = False):
    """The output head applied from ``params`` outside the model (the
    serving engine's one-position reads)."""
    return _apply_lm_head(cfg, _lm_head_params(cfg, params), hidden, gather)


def _lm_head_params(cfg: "GPTConfig", params):
    """The lm_head param subtree, FSDP-gathered ONCE when sharded.

    Pairs with ``_make_lm_head(..., fsdp_wrap=False)``: the returned tree is
    the full (per-TP-rank) weight, safe to close over in a chunk/decode scan
    without re-gathering per iteration.  The gather's custom backward still
    psum_scatters the accumulated cotangent, so gradients are identical to
    the per-iteration-gather form.  No-op when the data axis is unbound
    (plain ``generate`` on exported params) or ``cfg.fsdp`` is off."""
    from tpu_parallel.parallel.tp import axis_size_or_none

    if cfg.tie_embeddings:
        if cfg.fsdp:
            raise NotImplementedError("tie_embeddings with fsdp")
        return params["embed"]["tok"]
    lm = params["lm_head"]
    if cfg.fsdp and axis_size_or_none(cfg.data_axis) is not None:
        lm = fsdp.gather_params(lm, cfg.data_axis)
    return lm


class GPTLM(nn.Module):
    """tokens [B, S] -> logits [B, S, vocab].

    ``positions`` contract under ``positional="relative"``: every row must
    hold the SAME position vector (the bias table is computed once from row
    0 — ragged/packed per-row positions are refused by the framework entry
    points, and a direct ``apply`` with genuinely per-row positions would
    silently get row-0 bias for all rows).  Learned/rope positional modes
    accept per-row positions.
    """

    config: GPTConfig

    @nn.compact
    def __call__(
        self,
        tokens: jax.Array,
        positions: Optional[jax.Array] = None,
        segment_ids: Optional[jax.Array] = None,
        train: bool = True,
        decode: bool = False,
        hidden_only: bool = False,
        write_index: Optional[jax.Array] = None,
        block_table: Optional[jax.Array] = None,
    ) -> jax.Array:
        cfg = self.config
        if write_index is not None and not decode:
            raise ValueError(
                "write_index (slot-indexed cache writes) requires decode=True"
            )
        if block_table is not None and cfg.kv_block_tokens < 1:
            raise ValueError(
                "block_table passed but kv_block_tokens == 0 — paged KV "
                "serving requires a model built with kv_block_tokens/"
                "kv_pool_blocks (the serving engine constructs one)"
            )
        if write_index is not None and cfg.positional == "relative":
            # the shared T5 bias table is computed from ROW 0's positions
            # (for_step below); a slot pool holds rows at different depths,
            # so every other row would silently get row-0's bias — refuse
            # loudly instead (serve relative-bias models through generate())
            raise NotImplementedError(
                "slot-indexed cache writes with relative position bias "
                "(the shared bias table assumes row-uniform positions; "
                "slot-pool rows sit at different depths)"
            )
        if decode and positions is None:
            # default decode positions from a model-level step counter, so
            # learned positional embeddings see global positions (Attention
            # keeps its own per-layer cache index for the K/V mask — both
            # advance by the same token count and stay consistent)
            counter = self.variable(
                "cache", "decode_pos", lambda: jnp.zeros((), jnp.int32)
            )
            positions = jnp.broadcast_to(
                counter.value + jnp.arange(tokens.shape[1])[None, :], tokens.shape
            )
            counter.value = counter.value + tokens.shape[1]
        embed = fsdp.maybe_shard(Embedding, cfg)(cfg, name="embed")
        x = embed(tokens, positions=positions)

        attn_bias = None
        if cfg.positional == "relative":
            # T5-style bucketed score bias, ONE table shared by every layer
            # (hence computed here, above the stack) — xla attention path
            # only; PP would need the bias as a pipeline extra and packing
            # per-row position tables, neither wired yet
            if cfg.pipe_size > 1:
                raise NotImplementedError(
                    "relative position bias under pipeline parallelism"
                )
            if cfg.attn_impl != "xla":
                raise NotImplementedError(
                    "relative position bias needs attn_impl='xla' (the "
                    "flash/ring/ulysses kernels take no additive score bias)"
                )
            if segment_ids is not None:
                raise NotImplementedError(
                    "relative position bias with packed sequences"
                )
            attn_bias = RelativePositionBias(
                cfg, bidirectional=cfg.bidirectional, name="rel_bias"
            ).for_step(positions, tokens.shape[1], cfg.seq_len, decode)

        if cfg.pipe_interleave > 1 and cfg.pipe_size <= 1:
            raise ValueError(
                "pipe_interleave > 1 requires pipe_size > 1 (a pipe mesh "
                "axis); on a pipe=1 mesh the knob would be silently ignored"
            )
        if cfg.pipe_size > 1:
            if write_index is not None or block_table is not None:
                raise NotImplementedError(
                    "slot-indexed cache writes under pipeline parallelism "
                    "(the decode ring's per-stage caches would need the "
                    "write-slot table as a ring extra — serve pipe meshes "
                    "through generate_sharded, not the serving engine)"
                )
            chunks = cfg.pipe_size * cfg.pipe_interleave
            if cfg.n_layers % chunks != 0:
                raise ValueError(
                    f"n_layers={cfg.n_layers} not divisible by pipe_size*"
                    f"pipe_interleave={chunks}"
                )
            if cfg.pipe_interleave > 1 and cfg.moe_experts > 0:
                raise NotImplementedError(
                    "MoE under the interleaved pipeline schedule (chunk "
                    "branches would sow mismatched loss collections)"
                )
            layers_per_chunk = cfg.n_layers // chunks
            if cfg.moe_experts > 0 and cfg.moe_dispatch == "alltoall":
                from tpu_parallel.core.metrics import pvary_missing
                from tpu_parallel.parallel.tp import axis_size_or_none

                if axis_size_or_none(cfg.model_axis) is not None:
                    # the a2a MoE's closing all_gather makes stage outputs
                    # model-VARYING; the pipeline scan's activation carry
                    # must enter that way or the carry types disagree
                    # (same rule as BlockStack's inner scan)
                    x = pvary_missing(x, (cfg.model_axis,))
            pipeline = pp.PipelineModule(
                stage_fn=functools.partial(BlockStack, cfg, layers_per_chunk),
                num_microbatches=cfg.num_microbatches,
                axis_name=cfg.pipe_axis,
                # BlockStack accepts aux_scale: bubble ticks contribute
                # exactly zero to sown losses (MoE balance)
                pass_validity=True,
                interleave=cfg.pipe_interleave,
                name="pipeline",
            )
            if decode:
                from tpu_parallel.parallel.tp import axis_size_or_none

                if segment_ids is not None:
                    # mirror the non-PP decode refusal (Attention raises) —
                    # silently dropping them would attend across documents
                    raise NotImplementedError(
                        "incremental decoding with packed sequences "
                        "(segment_ids)"
                    )
                if axis_size_or_none(cfg.pipe_axis) is None:
                    # fail clearly here — otherwise the ring's collectives
                    # die on an unbound-axis error deep in JAX
                    raise ValueError(
                        f"pipe_size={cfg.pipe_size} decoding needs the "
                        f"{cfg.pipe_axis!r} mesh axis bound: serve through "
                        "generate_sharded under the training mesh (plain "
                        "generate()/generate_beam() run without a mesh)"
                    )
                # ring decode (pp.execute_pipeline_decode): positions ride
                # through directly — no scan, so traced kwargs are fine
                x = pipeline(x, train=train, decode=True, positions=positions)
            else:
                # packed sequences / explicit positions ride as pipeline
                # extras: every rank holds them replicated and indexes its
                # current microbatch locally (pp.execute_pipeline_step)
                extras = {}
                if segment_ids is not None:
                    extras["segment_ids"] = segment_ids
                if positions is not None:
                    extras["positions"] = positions
                x = pipeline(x, train=train, extras=extras or None)
        else:
            x = BlockStack(cfg, cfg.n_layers, name="blocks")(
                x,
                positions=positions,
                segment_ids=segment_ids,
                train=train,
                decode=decode,
                attn_bias=attn_bias,
                write_index=write_index,
                block_table=block_table,
            )

        if cfg.prenorm:
            # post-norm stacks (BERT interop) leave the trunk already
            # normalized by the last block's norm_mlp — an extra final norm
            # has no HF counterpart and would break checkpoint parity
            x = make_norm(cfg, "norm_final")(x).astype(cfg.dtype)
        if hidden_only:
            # for chunked-loss training (make_gpt_loss applies the lm_head
            # itself, loss_chunk positions at a time)
            return x
        # Logits stay in cfg.dtype: the bf16 matmul already rounded them, so
        # an fp32 cast here would only double the largest tensor in the
        # program (see token_cross_entropy, which upcasts inside the
        # reductions instead).
        if cfg.tie_embeddings:
            if cfg.fsdp:
                raise NotImplementedError("tie_embeddings with fsdp")
            return embed(x, attend=True)
        return _make_lm_head(cfg)(x)


def ce_form(model_axis_size: Optional[int]) -> str:
    """Which cross-entropy a step runs, from what it can observe: the size
    of the bound model axis (None outside a mesh).  ``"fused"`` where the
    vocabulary is whole on the chip, ``"vocab_parallel"`` where the head's
    columns are sharded and the row statistics cross chips."""
    return "vocab_parallel" if (model_axis_size or 1) > 1 else "fused"


def loss_plan(config, rows: int, tokens: int, model_axis_size: Optional[int]) -> dict:
    """What the head and the loss of one pass over ``rows`` sequences of
    ``tokens`` positions will do, said once (static for a compiled step, as
    ``flash_plan`` is): the form, the logits' shape and type, and the bytes
    of ``[rows, vocab]``-sized residuals the loss keeps for its backward —
    the logits as the head wrote them plus a float32 log-sum-exp a row for
    ``fused``; the float32 exponentials of the shard, which autodiff saves,
    for ``vocab_parallel``.  Under ``loss_chunk`` the backward holds one
    chunk's at a time."""
    form = ce_form(model_axis_size)
    vocab = config.vocab_size // (model_axis_size or 1)
    dtype = jnp.dtype(config.dtype)
    live = rows * (config.loss_chunk or tokens)
    per_logit = dtype.itemsize if form == "fused" else 4
    return {
        "form": form,
        "rows_per_pass": rows,
        "vocab": vocab,
        "logits_dtype": dtype.name,
        "residual_bytes_per_pass": live * (vocab * per_logit + 4),
        "chunk": config.loss_chunk,
    }


def make_ce_fn(config: GPTConfig):
    """``(lm_params, hidden, targets, mask) -> (loss_sum, correct_sum)``:
    the shared CE machinery of every token-prediction objective (causal LM,
    MLM, seq2seq) — vocab-parallel under TP, sequence-chunked under
    ``config.loss_chunk``.

    ``lm_params`` must be pre-gathered when FSDP-sharded
    (:func:`_lm_head_params`): the head applied here is unwrapped, so the
    chunk scan never re-all_gathers the vocab kernel per iteration."""
    from tpu_parallel.core.losses import vocab_parallel_cross_entropy

    chunk = config.loss_chunk

    def ce_block(lm_params, h, targets, mask):
        """lm_head + CE + accuracy on one block of hidden states; returns
        (loss_sum, correct_sum).  Vocab-parallel when the vocabulary is
        sharded over the model axis, the one-pass unit of
        ``core.losses.token_ce_and_argmax`` where it is whole on the chip
        (no mesh, or a model axis of one)."""
        from tpu_parallel.parallel.tp import axis_size_or_none

        tp = axis_size_or_none(config.model_axis)
        # stable names for the step's second bottleneck: this head is
        # unnamed (applied outside the model), so it gets no module scope
        with jax.named_scope("lm_head"):
            logits = _apply_lm_head(config, lm_params, h)
        if ce_form(tp) == "vocab_parallel":
            with jax.named_scope("cross_entropy"):
                ce, pred = vocab_parallel_cross_entropy(
                    logits, targets, config.model_axis
                )
        else:
            ce, pred = token_ce_and_argmax(logits, targets)
        with jax.named_scope("cross_entropy"):
            loss_sum = (ce * mask).sum()
            correct = ((pred == targets) * mask).sum()
            if tp == 1:
                # the head's shard is typed as varying over the model axis
                # even when that axis is one chip: the sum over it (no
                # collective once compiled) closes the type, as the
                # vocab-parallel psums do
                loss_sum, correct = (
                    lax.psum(x, config.model_axis)
                    if config.model_axis in vma_of(x) else x
                    for x in (loss_sum, correct)
                )
        return loss_sum, correct

    def chunked_ce(lm_params, h, targets, mask):
        """scan ce_block over sequence chunks; logits exist only
        [B, loss_chunk, vocab/tp] at a time."""
        b, s = targets.shape
        if s % chunk != 0:
            raise ValueError(f"seq_len={s} not divisible by loss_chunk={chunk}")
        n = s // chunk
        hs = h.reshape(b, n, chunk, h.shape[-1]).transpose(1, 0, 2, 3)
        ts = targets.reshape(b, n, chunk).transpose(1, 0, 2)
        ms = mask.reshape(b, n, chunk).transpose(1, 0, 2)

        def body(carry, xs):
            loss_sum, correct = ce_block(lm_params, *xs)
            return (carry[0] + loss_sum, carry[1] + correct), None

        # promote the zero carry to the body outputs' varying-axes type (the
        # hidden states' axes plus the model axis, which the CE's psums over
        # the sharded vocab introduce) so the scan type-checks under
        # shard_map's replication checker
        vma = vma_of(h)
        if vma and config.model_axis not in vma:
            vma = vma + (config.model_axis,)
        init = (
            pvary_missing(jnp.float32(0.0), vma),
            pvary_missing(jnp.float32(0.0), vma),
        )
        (loss_sum, correct), _ = lax.scan(jax.checkpoint(body), init, (hs, ts, ms))
        return loss_sum, correct

    return chunked_ce if chunk else ce_block


def make_gpt_1f1b_grad_fn(config: GPTConfig, train: bool = True):
    """``(params, batch, rng) -> (grads, metrics)`` via the memory-bounded
    1F1B pipeline schedule (:func:`tpu_parallel.parallel.pp.pipeline_1f1b_grads`).

    Replaces the ``jax.grad``-through-GPipe path inside the train step when
    ``config.pipe_schedule == "1f1b"``: in-flight microbatch activations are
    bounded at ``pipe_size`` per rank instead of ``num_microbatches``.  The
    forward/eval/serving paths (``GPTLM.__call__``) are untouched — the
    schedule only changes HOW gradients are computed, not the math: grads
    and loss match the GPipe step (tests/test_pp.py pins parity).

    The per-rank composite mirrors ``GPTLM``'s pipe path module-by-module
    and BY NAME (embed / pipeline.stage / norm_final / lm_head), so the
    params tree initialized through the standard path serves unchanged.
    """
    # pipe_size == 1 is the legitimate degenerate: every tick forwards and
    # immediately backwards one microbatch — per-microbatch vjp
    # accumulation, the n=1 baseline of the scaling harness
    if config.pipe_interleave > 1:
        raise NotImplementedError(
            "1F1B with interleaved virtual stages (the circular schedule's "
            "chunk walk and the 1F1B buffer discipline do not compose yet)"
        )
    if config.moe_experts > 0:
        raise NotImplementedError(
            "MoE under 1F1B (sown balance losses need per-tick replay "
            "bookkeeping the schedule does not carry)"
        )
    if config.positional == "relative":
        raise NotImplementedError("relative position bias under pipelines")
    layers_per_stage = config.n_layers // config.pipe_size
    if config.n_layers % config.pipe_size:
        raise ValueError(
            f"n_layers={config.n_layers} not divisible by "
            f"pipe_size={config.pipe_size}"
        )

    from tpu_parallel.parallel.tp import ModuleShard

    ce_fn = make_ce_fn(config)
    embed_mod = fsdp.maybe_shard(Embedding, config)(config)
    if config.pipe_size > 1:
        stage_mod = ModuleShard(
            module_fn=functools.partial(BlockStack, config, layers_per_stage),
            axis_name=config.pipe_axis,
        )
        stage_params = lambda p: p["pipeline"]["stage"]  # noqa: E731
    else:
        # degenerate single-stage: GPTLM builds a plain BlockStack named
        # "blocks" at pipe_size=1 — mirror that tree
        stage_mod = BlockStack(config, config.n_layers)
        stage_params = lambda p: p["blocks"]  # noqa: E731
    norm_mod = make_norm(config, None) if config.prenorm else None
    fold_axes = (
        config.data_axis, config.model_axis, config.pipe_axis, config.seq_axis
    )

    def fwd_fn(params, x_in, mb, rng_mb):
        dropout_rng = fold_rng_over_axis(rng_mb, fold_axes)
        x0 = embed_mod.apply(
            {"params": params["embed"]}, mb.tokens, positions=mb.positions
        )
        stage_idx = lax.axis_index(config.pipe_axis)
        x = jnp.where(stage_idx == 0, x0, x_in)
        y = stage_mod.apply(
            {"params": stage_params(params)},
            x,
            positions=mb.positions,
            segment_ids=mb.segment_ids,
            train=train,
            rngs={"dropout": dropout_rng},
        )
        h = y
        if norm_mod is not None:
            h = norm_mod.apply({"params": params["norm_final"]}, y).astype(
                config.dtype
            )
        mask = (
            mb.loss_mask
            if mb.loss_mask is not None
            else jnp.ones(mb.targets.shape, jnp.float32)
        )
        mask = mask * pp.last_stage_mask(config.pipe_axis)
        n_tok = mask.sum()
        loss_sum, correct = ce_fn(
            _lm_head_params(config, params), h, mb.targets, mask
        )
        metrics: Metrics = {
            "loss": (loss_sum, n_tok),
            "accuracy": (correct.astype(jnp.float32), n_tok),
        }
        return y, loss_sum, metrics

    def grad_fn(params, batch, rng):
        mb_rows = batch.tokens.shape[0] // config.num_microbatches
        return pp.pipeline_1f1b_grads(
            fwd_fn,
            params,
            batch,
            rng,
            num_microbatches=config.num_microbatches,
            axis_name=config.pipe_axis,
            act_shape=(mb_rows, batch.tokens.shape[1], config.d_model),
            act_dtype=config.dtype,
        )

    return grad_fn


def make_gpt_loss(config: GPTConfig, train: bool = True):
    """Next-token CE in the accumulate_gradients loss shape, PP/TP-aware.

    Dropout RNG folds over every parallel axis; under PP the loss and metric
    counts are masked to the last pipe rank (the only rank with real logits).
    ``train=False`` builds the evaluation variant (dropout off).

    The lm_head is applied here, not in the model: logits stay column-
    sharded over the model axis and CE runs vocab-parallel — under TP the
    full-vocab [B, S, vocab] logits tensor never materializes and the
    per-microbatch all_gather (the largest TP collective) disappears;
    the softmax statistics cost three O(B*S) scalar collectives instead.

    With ``config.loss_chunk > 0`` the lm_head + CE additionally run
    ``loss_chunk`` sequence positions at a time under a rematerialized
    ``lax.scan`` — even the vocab-*sharded* logits never exist at full
    sequence length (see ``GPTConfig.loss_chunk``).
    """
    fold_axes = (
        config.data_axis, config.model_axis, config.pipe_axis, config.seq_axis
    )
    ce_fn = make_ce_fn(config)

    def loss_fn(params, apply_fn, batch, rng):
        dropout_rng = fold_rng_over_axis(rng, fold_axes)
        apply_kwargs = dict(
            positions=batch.positions,
            segment_ids=batch.segment_ids,
            train=train,
            rngs={"dropout": dropout_rng},
            hidden_only=True,
        )
        aux_loss = 0.0
        if config.moe_experts > 0:
            hidden, mods = apply_fn(
                {"params": params}, batch.tokens, mutable=["losses"], **apply_kwargs
            )
            sown = jax.tree_util.tree_leaves(mods.get("losses", {}))
            if sown:
                # Normalize the tick/layer-stacked sum so the aux gradient per
                # router matches the no-PP case regardless of pipe degree.
                # Without PP each of the n_layers blocks sows once.  Under PP
                # each rank's layers_per_stage blocks sow once per REAL tick
                # (bubble ticks zeroed via aux_scale — pp.py), i.e.
                # num_microbatches times — and every rank adds its own
                # aux term to its local total, so the denominator must count
                # ALL layers (n_layers, not layers_per_stage): summed across
                # ranks the aux terms then reconstruct exactly the per-layer
                # mean-over-microbatches, and each router's gradient carries
                # the same 1/n_layers weight as at pipe_size=1
                # (tests/test_moe.py::test_pp_aux_gradient_invariance).
                if config.pipe_size > 1:
                    denom = config.n_layers * config.num_microbatches
                else:
                    denom = config.n_layers
                aux_loss = sum(jnp.sum(leaf) for leaf in sown) / denom
        else:
            hidden = apply_fn({"params": params}, batch.tokens, **apply_kwargs)
        mask = (
            batch.loss_mask
            if batch.loss_mask is not None
            else jnp.ones(batch.targets.shape, jnp.float32)
        )
        if config.pipe_size > 1:
            mask = mask * pp.last_stage_mask(config.pipe_axis)
        n_tok = mask.sum()
        loss_sum, correct = ce_fn(
            _lm_head_params(config, params), hidden, batch.targets, mask
        )
        metrics: Metrics = {
            "loss": (loss_sum, n_tok),
            "accuracy": (correct.astype(jnp.float32), n_tok),
        }
        total = loss_sum / jnp.maximum(n_tok, 1.0)
        if config.moe_experts > 0:
            # Metric: the full-model per-layer balance mean.  Under PP each
            # rank holds only its stage's share (aux_loss sums to the full
            # mean across ranks) and n_tok is nonzero on the last rank only —
            # psum the shares so the reported value covers every layer.
            aux_metric = aux_loss
            if config.pipe_size > 1:
                aux_metric = lax.psum(aux_loss, config.pipe_axis)
            metrics["moe_balance"] = (aux_metric * n_tok, n_tok)
            total = total + config.moe_balance_weight * aux_loss
        return total, metrics

    return loss_fn


class EncoderClassifier(nn.Module):
    """Sequence classification head over the (bidirectional) trunk.

    The BERT fine-tune shape: encoder hidden states -> pooled vector
    (``"first"`` = CLS-style first token through a tanh pooler, ``"mean"``
    = mean over the row's FIRST segment when ``segment_ids`` are given —
    padding/foreign segments excluded — else over every position) -> class
    logits.  Works with
    :func:`~tpu_parallel.core.losses.make_classification_loss` unchanged
    (``apply_fn(tokens)`` -> ``[batch, num_classes]``); the trunk composes
    with TP/FSDP exactly as the LM does.  Requires ``bidirectional=True``:
    under a causal mask the CLS position attends to nothing but itself.
    """

    config: GPTConfig
    num_classes: int
    pool: str = "first"  # "first" (CLS) | "mean"

    @nn.compact
    def __call__(
        self,
        tokens: jax.Array,
        positions: Optional[jax.Array] = None,
        segment_ids: Optional[jax.Array] = None,
        train: bool = True,
    ) -> jax.Array:
        cfg = self.config
        if not cfg.bidirectional:
            raise ValueError(
                "EncoderClassifier requires bidirectional=True — under a "
                "causal mask the pooled position cannot see the sequence"
            )
        h = GPTLM(cfg, name="encoder")(
            tokens,
            positions=positions,
            segment_ids=segment_ids,
            train=train,
            hidden_only=True,
        )
        if self.pool == "mean":
            if segment_ids is not None:
                # pool only the row's first segment: pad tokens (and any
                # packed neighbours) must not shift the pooled vector
                w = (segment_ids == segment_ids[:, :1]).astype(h.dtype)[..., None]
                pooled = (h * w).sum(axis=1) / jnp.maximum(w.sum(axis=1), 1.0)
            else:
                pooled = h.mean(axis=1)
        elif self.pool == "first":
            pooled = h[:, 0]
        else:
            raise ValueError(f"pool={self.pool!r} (first | mean)")
        pooled = jnp.tanh(
            nn.Dense(cfg.d_model, dtype=cfg.dtype, name="pooler")(pooled)
        )
        if cfg.dropout_rate > 0.0:
            pooled = nn.Dropout(
                rate=cfg.dropout_rate, deterministic=not train
            )(pooled)
        # fp32 class logits: tiny tensor, and the CE upcast costs nothing
        return nn.Dense(
            self.num_classes, dtype=jnp.float32, name="classifier"
        )(pooled)


def make_mlm_loss(
    config: GPTConfig,
    mask_rate: float = 0.15,
    mask_token_id: Optional[int] = None,
    train: bool = True,
):
    """Masked-LM objective for bidirectional (encoder) configs.

    Wraps :func:`make_gpt_loss`'s CE machinery (vocab-parallel under TP,
    chunked under ``loss_chunk``, PP-masked): each step corrupts
    ``mask_rate`` of the input tokens to ``mask_token_id`` (default: the
    last vocab id, by convention reserved for [MASK]) and scores the model
    on recovering the originals at exactly those positions.

    RNG discipline: the corruption pattern folds over the data and seq axes
    only — model/pipe ranks hold replicated copies of the same tokens and
    MUST corrupt them identically, while data/seq shards draw independent
    masks.  (Dropout keeps its own all-axes fold inside the inner loss.)
    """
    from tpu_parallel.core.state import TextBatch

    inner = make_gpt_loss(config, train=train)
    mask_id = (
        mask_token_id if mask_token_id is not None else config.vocab_size - 1
    )
    corrupt_axes = (config.data_axis, config.seq_axis)

    def loss_fn(params, apply_fn, batch, rng):
        mask_rng = fold_rng_over_axis(jax.random.fold_in(rng, 17), corrupt_axes)
        masked = jax.random.bernoulli(mask_rng, mask_rate, batch.tokens.shape)
        corrupted = jnp.where(masked, mask_id, batch.tokens)
        loss_mask = masked.astype(jnp.float32)
        if batch.loss_mask is not None:
            loss_mask = loss_mask * batch.loss_mask
        mlm_batch = TextBatch(
            tokens=corrupted,
            targets=batch.tokens,
            loss_mask=loss_mask,
            positions=batch.positions,
            segment_ids=batch.segment_ids,
        )
        return inner(params, apply_fn, mlm_batch, rng)

    return loss_fn


# --- Named configurations (BASELINE.md matrix) --------------------------------


def gpt2_125m(**overrides) -> GPTConfig:
    return GPTConfig(
        **{
            **dict(
                vocab_size=50304, d_model=768, n_layers=12, n_heads=12, seq_len=1024
            ),
            **overrides,
        }
    )


def gpt2_350m(**overrides) -> GPTConfig:
    return GPTConfig(
        **{
            **dict(
                vocab_size=50304, d_model=1024, n_layers=24, n_heads=16, seq_len=1024
            ),
            **overrides,
        }
    )


def llama_1b(**overrides) -> GPTConfig:
    return GPTConfig(
        **{
            **dict(
                vocab_size=32000,
                d_model=2048,
                n_layers=16,
                n_heads=16,
                seq_len=2048,
                positional="rope",
                norm="rmsnorm",
                mlp="swiglu",
            ),
            **overrides,
        }
    )


def bert_base(**overrides) -> GPTConfig:
    """BERT-base-shaped bidirectional encoder (MLM via make_mlm_loss).

    vocab 30522 padded to 30592 (multiple of 128 for MXU lanes; the last id
    doubles as [MASK] by make_mlm_loss's default).
    """
    return GPTConfig(
        **{
            **dict(
                vocab_size=30592,
                d_model=768,
                n_layers=12,
                n_heads=12,
                seq_len=512,
                bidirectional=True,
            ),
            **overrides,
        }
    )


def bert_base_hf(**overrides) -> GPTConfig:
    """BERT-base in its ORIGINAL (HF-checkpoint-faithful) form: post-norm
    residuals, embeddings.LayerNorm, erf gelu, vocab 30522 unpadded —
    the config :func:`~tpu_parallel.models.hf.from_hf_bert` imports into.
    For from-scratch pretraining prefer :func:`bert_base` (pre-norm,
    MXU-padded vocab)."""
    return GPTConfig(
        **{
            **dict(
                vocab_size=30522,
                d_model=768,
                n_layers=12,
                n_heads=12,
                seq_len=512,
                bidirectional=True,
                prenorm=False,
                embed_norm=True,
                mlp="gelu_exact",
                scan_layers=False,
                # BERT's LayerNorm epsilon (GPT-2/Llama use 1e-5; with the
                # wrong eps all 25 norms silently drift from torch)
                norm_eps=1e-12,
            ),
            **overrides,
        }
    )


def parallel_experts_decoder(
    *,
    window: int,
    experts: ExpertsSpec,
    window_layers: int = 3,
    **overrides,
) -> GPTConfig:
    """A decoder of parallel blocks (attention and experts from one
    bias-free LayerNorm, added to the residual together) whose period is
    ``window_layers`` sliding-window layers with rotary positions and then
    one full-attention layer with no positions at all; every MLP is the
    dropless ``experts`` layer; the head is the token embedding.  Sizes
    (``d_model``, ``n_heads``, ``n_kv_heads``, ``head_dim``, ``n_layers``,
    ``vocab_size``, ``seq_len``, ``rope_theta``) come as ``overrides``."""
    local = LayerSpec("window", window, "rope", "experts", experts)
    full = LayerSpec("full", 0, "none", "experts", experts)
    return GPTConfig(
        **{
            **dict(
                positional="rope",
                norm="layernorm_nobias",
                dense_bias=False,
                parallel_block=True,
                tie_embeddings=True,
                scan_layers=False,
                layer_pattern=(local,) * window_layers + (full,),
            ),
            **overrides,
        }
    )


def hybrid_ssm_decoder(
    *,
    pattern: Tuple[str, ...],
    ssm: "SSMSpec",
    **overrides,
) -> GPTConfig:
    """A pre-norm decoder whose token mixers are mixed by layer: ``pattern``
    is one period of ``"ssm"`` (the recurrent Mamba-2 mixer of
    ``models/ssm.py``, sized by ``ssm``) and ``"attention"`` (causal,
    grouped-query, with no positional encoding at all); every layer has the
    dense SwiGLU MLP, RMSNorm and no bias; the head is the token embedding.
    Sizes and the stated scalars (``attn_scale``, ``embed_scale``,
    ``residual_scale``, ``logit_scale``) come as ``overrides``."""
    kinds = {
        "ssm": LayerSpec(positions="none", mixer="ssm", ssm=ssm),
        "attention": LayerSpec(positions="none"),
    }
    return GPTConfig(
        **{
            **dict(
                positional="rope",  # no learned table; every layer says "none"
                norm="rmsnorm",
                mlp="swiglu",
                dense_bias=False,
                tie_embeddings=True,
                scan_layers=False,
                layer_pattern=tuple(kinds[k] for k in pattern),
            ),
            **overrides,
        }
    )


def block_diffusion_decoder(
    *, experts: ExpertsSpec, block_len: int, mask_token_id: int, **overrides
) -> GPTConfig:
    """A pre-norm decoder generated by masked diffusion inside blocks of
    ``block_len`` positions and autoregressively across them (Qwen3-MoE's
    block under the block rule): RMSNorm, no bias, grouped-query attention
    with a per-head RMSNorm on queries and keys before rotate-half rotary
    positions, every MLP the dropless softmax-routed ``experts`` layer, the
    head untied.  A position sees its own block whole and every earlier
    block (``TransformerConfig.block_len``); a masked position holds
    ``mask_token_id`` and predicts its own token.  Sizes come as
    ``overrides``."""
    return GPTConfig(
        **{
            **dict(
                positional="rope",
                rope_pairing="half",
                qk_norm=True,
                norm="rmsnorm",
                norm_eps=1e-6,
                dense_bias=False,
                scan_layers=False,
                block_len=block_len,
                mask_token_id=mask_token_id,
                layer_pattern=(
                    LayerSpec("full", 0, "model", "experts", experts),
                ),
            ),
            **overrides,
        }
    )


def tiny_block_diffusion(**overrides) -> GPTConfig:
    """``block_diffusion_decoder`` at CPU-test size: 3 layers, 4 heads of 16
    on 2 K/V heads, 8 softmax-routed experts top-2, blocks of 4, the last id
    the mask."""
    experts = overrides.pop(
        "experts", ExpertsSpec(n_experts=8, top_k=2, width=48)
    )
    return block_diffusion_decoder(
        experts=experts,
        block_len=overrides.pop("block_len", 4),
        mask_token_id=overrides.pop("mask_token_id", 255),
        **{
            **dict(
                vocab_size=256, d_model=64, n_layers=3, n_heads=4,
                n_kv_heads=2, head_dim=16, seq_len=64, rope_theta=1e6,
                dtype=jnp.float32, remat=False,
            ),
            **overrides,
        },
    )


def tiny_hybrid_ssm(**overrides) -> GPTConfig:
    """``hybrid_ssm_decoder`` at CPU-test size: two periods of ``ssm, ssm,
    attention, ssm``, 4 heads of 16, a state of 16, chunks of 8."""
    from tpu_parallel.models.layers import SSMSpec

    return hybrid_ssm_decoder(
        pattern=overrides.pop("pattern", ("ssm", "ssm", "attention", "ssm")),
        ssm=overrides.pop(
            "ssm", SSMSpec(n_heads=8, head_dim=16, d_state=16, chunk=8)
        ),
        **{
            **dict(
                vocab_size=256, d_model=64, n_layers=8, n_heads=4,
                n_kv_heads=2, head_dim=16, mlp_dim=128, seq_len=48,
                attn_scale=0.09, embed_scale=3.0, residual_scale=0.5,
                logit_scale=16.0, dtype=jnp.float32, remat=False,
            ),
            **overrides,
        },
    )


def tiny_parallel_experts(**overrides) -> GPTConfig:
    """``parallel_experts_decoder`` at CPU-test size: two periods, window 8,
    16 sigmoid-routed experts top-4 of which 4 are held, 2 shared."""
    experts = overrides.pop(
        "experts",
        ExpertsSpec(
            n_experts=16, top_k=4, width=48, score="sigmoid", shared=2,
            held=(4, 4),
        ),
    )
    return parallel_experts_decoder(
        window=overrides.pop("window", 8),
        experts=experts,
        **{
            **dict(
                vocab_size=256, d_model=32, n_layers=8, n_heads=4,
                n_kv_heads=1, head_dim=16, seq_len=40, rope_theta=50000.0,
                dtype=jnp.float32, remat=False,
            ),
            **overrides,
        },
    )


def tiny_test(**overrides) -> GPTConfig:
    """Small config for CPU-mesh tests: real structure, toy sizes."""
    return GPTConfig(
        **{
            **dict(
                vocab_size=256,
                d_model=32,
                n_layers=4,
                n_heads=4,
                seq_len=32,
                dtype=jnp.float32,
                num_microbatches=2,
            ),
            **overrides,
        }
    )


def one_sublayer_decoder(
    *, pattern: str, ssm: "SSMSpec", experts: ExpertsSpec, **overrides
) -> GPTConfig:
    """A pre-norm decoder whose layers are ONE sublayer each behind ONE
    RMSNorm: ``pattern`` is one period, a letter a layer, of ``"M"`` (the
    recurrent Mamba-2 mixer of ``models/ssm.py``, sized by ``ssm``), ``"*"``
    (causal grouped-query attention with no positional encoding at all) and
    ``"E"`` (the dropless ``experts`` layer); no bias, the head untied.
    Sizes come as ``overrides``."""
    kinds = {
        "M": LayerSpec(positions="none", mlp="none", mixer="ssm", ssm=ssm),
        "*": LayerSpec(positions="none", mlp="none"),
        "E": LayerSpec(mlp="experts", experts=experts, mixer="none"),
    }
    return GPTConfig(
        **{
            **dict(
                positional="rope",  # no learned table; every layer says "none"
                norm="rmsnorm",
                dense_bias=False,
                scan_layers=False,
                layer_pattern=tuple(kinds[k] for k in pattern),
            ),
            **overrides,
        }
    )


def tiny_one_sublayer(**overrides) -> GPTConfig:
    """``one_sublayer_decoder`` at CPU-test size: one period ``MEMEMEM*EME``,
    8 recurrent heads of 16 in 2 groups with a state of 16, 4 query heads of
    16 on 2 K/V heads, 16 sigmoid-routed relu2 experts of width 24 in a latent
    of 32, top-4 with a selection bias and a scale of 2.5, of which 8 are
    held, one shared expert of width 48."""
    from tpu_parallel.models.layers import SSMSpec

    pattern = overrides.pop("pattern", "MEMEMEM*EME")
    return one_sublayer_decoder(
        pattern=pattern,
        ssm=overrides.pop(
            "ssm",
            SSMSpec(n_heads=8, head_dim=16, d_state=16, n_groups=2, chunk=8),
        ),
        experts=overrides.pop(
            "experts",
            ExpertsSpec(
                n_experts=16, top_k=4, width=24, score="sigmoid", shared=1,
                held=(0, 8), latent=32, ffn="relu2", shared_width=48,
                shared_sum=True, select_bias=True, route_scale=2.5,
            ),
        ),
        **{
            **dict(
                vocab_size=256, d_model=64, n_layers=len(pattern), n_heads=4,
                n_kv_heads=2, head_dim=16, seq_len=48, dtype=jnp.float32,
                remat=False,
            ),
            **overrides,
        },
    )


def latent_experts_decoder(
    *, latent: "LatentSpec", experts: ExpertsSpec, dense_layers: int = 1,
    **overrides,
) -> GPTConfig:
    """A pre-norm decoder of latent attention layers (``models/
    latent_attention.py``, sized by ``latent``) with sandwich norms (four
    RMSNorms a layer): ``dense_layers`` leading layers with a dense SwiGLU MLP
    of ``mlp_dim``, then layers of the dropless ``experts``; rotary positions
    on the latent layer's rotary columns in rotate-half pairing, no bias, the
    head untied.  Sizes come as ``overrides`` (``n_layers`` counts the leading
    layers too)."""
    attn = dict(attn="latent", latent=latent)
    return GPTConfig(
        **{
            **dict(
                positional="rope",
                rope_pairing="half",
                norm="rmsnorm",
                mlp="swiglu",
                dense_bias=False,
                sandwich_norm=True,
                scan_layers=False,
                layer_head=(LayerSpec(**attn),) * dense_layers,
                layer_pattern=(
                    LayerSpec(mlp="experts", experts=experts, **attn),
                ),
            ),
            **overrides,
        }
    )


def tiny_latent_experts(**overrides) -> GPTConfig:
    """``latent_experts_decoder`` at CPU-test size: one leading dense layer of
    width 96 and three expert layers; 4 heads that score at 16 + 8 and sum
    values at 16 over a latent of 32 (queries through 48), a cache row of 40;
    16 sigmoid-routed experts of width 24, top-4 with a scale of 2.5, of which
    4 are held, one shared expert."""
    from tpu_parallel.models.layers import LatentSpec

    return latent_experts_decoder(
        latent=overrides.pop(
            "latent",
            LatentSpec(q_rank=48, kv_rank=32, nope_dim=16, rope_dim=8, v_dim=16),
        ),
        experts=overrides.pop(
            "experts",
            ExpertsSpec(
                n_experts=16, top_k=4, width=24, score="sigmoid", shared=1,
                held=(0, 4), shared_sum=True, route_scale=2.5,
            ),
        ),
        **{
            **dict(
                vocab_size=256, d_model=64, n_layers=4, n_heads=4,
                mlp_dim=96, seq_len=48, rope_theta=25600.0,
                dtype=jnp.float32, remat=False,
            ),
            **overrides,
        },
    )
