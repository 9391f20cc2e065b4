"""Autoregressive generation with a KV cache, fully jitted.

No reference capability exists (the reference is training-only tutorial
scripts — SURVEY.md §0); this provides the inference path users expect of a
framework.  The decode loop is a ``lax.scan`` over single-token steps: each
step appends K/V to the per-layer ``cache`` collection
(:class:`~tpu_parallel.models.layers.Attention` decode mode) and attends
against the cached prefix only — O(seq) per generated token instead of the
O(seq^2) of re-running the full forward.

Works for MHA and GQA, learned and RoPE positions, scan and unrolled layer
stacks.  Mesh serving goes through :func:`generate_sharded`: TP shards the
cache over heads exactly as activations; pipeline meshes decode via the
ring pass in :func:`tpu_parallel.parallel.pp.execute_pipeline_decode`
(per-stage KV caches, writes gated to the owning tick).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from tpu_parallel.models.gpt import GPTLM
from tpu_parallel.parallel.tp import export_single_device_params  # noqa: F401  (re-export: mesh-trained state -> generate-able params)


def _sample(
    logits: jax.Array, rng: jax.Array, temperature: float, top_k: int,
    top_p: float = 0.0,
):
    """One token per row from [batch, vocab] logits.

    ``top_k`` keeps the k highest logits; ``top_p`` in (0, 1) keeps the
    smallest prefix of the sorted distribution whose mass reaches p
    (nucleus sampling; the argmax token always survives).  Both filters
    compose (intersection) and apply after the temperature scale.
    """
    # models emit cfg.dtype (bf16) logits; sample in fp32 so the temperature
    # scale and the categorical's gumbel trick don't round at bf16
    logits = logits.astype(jnp.float32)
    if temperature == 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    logits = logits / temperature
    if top_k > 0:
        kth = jnp.sort(logits, axis=-1)[:, -top_k][:, None]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    if 0.0 < top_p < 1.0:
        desc = jnp.sort(logits, axis=-1)[:, ::-1]
        cum = jnp.cumsum(jax.nn.softmax(desc, axis=-1), axis=-1)
        # keep tokens whose mass BEFORE them is < p (so top-1 always stays)
        keep = cum - jax.nn.softmax(desc, axis=-1) < top_p
        cutoff = jnp.min(
            jnp.where(keep, desc, jnp.inf), axis=-1, keepdims=True
        )
        logits = jnp.where(logits < cutoff, -jnp.inf, logits)
    return jax.random.categorical(rng, logits, axis=-1).astype(jnp.int32)


def _sample_sharded(
    logits: jax.Array, rng: jax.Array, temperature: float, top_k: int,
    top_p: float, axis_name: str,
):
    """One token per row from vocab-SHARDED [batch, vocab/tp] logits, no
    full-vocab gather.

    - greedy: the two-collective global-argmax trick
      (:func:`~tpu_parallel.core.losses.vocab_parallel_argmax`).
    - temperature: Gumbel-max — each shard perturbs its slice with its own
      Gumbel noise (rng folded over the model axis) and the global argmax
      of ``logits/T + G`` is an exact softmax sample.
    - top_k: each shard's local top-k is a superset contributor to the
      global top-k; all_gather the ``tp * k`` candidates (tiny) and finish
      there.
    - top_p: needs the full sorted distribution — gathers the row
      (one [batch, vocab] all_gather per step, still far below the old
      every-step full-logits gather at [batch, seq, vocab] prefill).

    Every rank returns the SAME token (all decisions go through
    collectives), which TP decoding requires.
    """
    from tpu_parallel.core.losses import vocab_parallel_argmax
    from tpu_parallel.core.rng import fold_rng_over_axis

    if 0.0 < top_p < 1.0:
        full = lax.all_gather(logits, axis_name, axis=-1, tiled=True)
        # identical rng on every rank -> identical sample
        return _sample(full, rng, temperature, top_k, top_p)
    lf = logits.astype(jnp.float32)
    if temperature == 0.0:
        return vocab_parallel_argmax(lf, axis_name)
    lf = lf / temperature
    vs = lf.shape[-1]
    offset = lax.axis_index(axis_name) * vs
    if top_k > 0:
        k = min(top_k, vs)
        vals, idx = jax.lax.top_k(lf, k)  # [b, k] local
        cand_vals = lax.all_gather(vals, axis_name, axis=-1, tiled=True)
        cand_ids = lax.all_gather(
            idx.astype(jnp.int32) + offset, axis_name, axis=-1, tiled=True
        )
        # global top-k lives inside the tp*k candidates; mask the rest and
        # sample among candidates (identical rng/result on every rank)
        kth = jnp.sort(cand_vals, axis=-1)[:, -top_k][:, None]
        masked = jnp.where(cand_vals < kth, -jnp.inf, cand_vals)
        choice = jax.random.categorical(rng, masked, axis=-1)
        return jnp.take_along_axis(cand_ids, choice[:, None], axis=1)[:, 0]
    # pure temperature: Gumbel-max over the shards
    g = jax.random.gumbel(fold_rng_over_axis(rng, axis_name), lf.shape)
    return vocab_parallel_argmax(lf + g, axis_name)


def _cached_apply(model: GPTLM, variables, tokens, with_rows: bool, **kwargs):
    """``model.apply`` in decode mode: ``(hidden, cache)`` or, ``with_rows``,
    ``(hidden, cache, rows)`` with ``rows`` the ``[layers, held + 1]`` count
    of rows this call routed to each held expert (None for a model without
    a dropless expert layer: :func:`tpu_parallel.models.moe.expert_rows`)."""
    from tpu_parallel.models.moe import MOE_STATS, expert_rows

    counted = with_rows and model.config.routed_layers > 0
    hidden, updated = model.apply(
        variables,
        tokens,
        train=False,
        decode=True,
        hidden_only=True,
        mutable=["cache", MOE_STATS] if counted else ["cache"],
        **kwargs,
    )
    if not with_rows:
        return hidden, updated["cache"]
    return hidden, updated["cache"], expert_rows(updated) if counted else None


def decode_step(
    model: GPTLM,
    params,
    cache,
    tok: jax.Array,
    positions: jax.Array,
    write_index: Optional[jax.Array] = None,
    block_table: Optional[jax.Array] = None,
    with_rows: bool = False,
):
    """One single-token decode tick — THE reusable core of every decode loop.

    ``tok``/``positions``: [batch] current tokens and their global positions.
    Returns ``(hidden [batch, 1, d_model], new_cache)``.  Shared by the
    :func:`_generate_core` scan body (aligned batches, ``write_index=None``)
    and the continuous-batching engine (``tpu_parallel.serving.engine``,
    which passes per-row ``write_index`` so each slot's K/V lands at its own
    cache depth — on its per-step tick, as the scan body of its FUSED
    multi-step tick, and as the decode phase of its UNIFIED ragged tick
    right after a :func:`prefill_extend_step` chunk phase in the same
    dispatch; sharing this one core is what makes every tick family's
    greedy output bitwise identical by construction).  ``with_rows`` adds
    the expert layers' row counts (:func:`_cached_apply`).
    """
    return _cached_apply(
        model,
        {"params": params, "cache": cache},
        tok[:, None],
        with_rows,
        positions=positions[:, None],
        write_index=write_index,
        block_table=block_table,
    )


def padded_prefill_inputs(lengths, width: int):
    """RIGHT-padded prefill positions for prompts of ``lengths`` in a
    ``width``-wide bucket: real tokens get 0..len-1, pad slots -1.

    The pad contract mirrors the ragged decode layout everywhere: -1
    positions are never attended (``decode_attention`` masks ``kp >= 0``),
    their nn.Embed/RoPE lookups are harmless garbage, and the cache slots
    they occupy carry position -1 until real tokens (the request's decode
    steps) overwrite them — so bucket padding costs ZERO cache capacity.
    Returns ``(positions [b, width] int32, last_idx [b] int32)`` where
    ``last_idx`` is each row's final REAL token index (the hidden state the
    lm_head must read — right padding means it is NOT row -1).
    """
    lengths = jnp.asarray(lengths, jnp.int32)
    iota = jnp.arange(width, dtype=jnp.int32)[None, :]
    positions = jnp.where(iota < lengths[:, None], iota, -1)
    return positions, lengths - 1


def prefill_step(model: GPTLM, params, tokens: jax.Array,
                 positions: jax.Array, with_rows: bool = False):
    """Fresh-cache prefill over ``tokens`` [b, P] at explicit ``positions``
    [b, P] — THE pad-aware prefill core of the serving engine's fast path.

    With ``positions`` from :func:`padded_prefill_inputs`, a batch of
    different-length prompts padded to one bucket width prefills as ONE
    call compiled per BUCKET shape, not per distinct length: pad slots
    write position -1 into the per-slot cache table and are never
    attended, so every real token's K/V (including int8-quantized caches —
    quantization is per (position, kv-head), invisible to batch
    composition) is bit-identical to an exact-length prefill.  Returns
    ``(hidden [b, P, d_model], cache)``.
    """
    return _cached_apply(
        model, {"params": params}, tokens, with_rows, positions=positions
    )


def prefill_extend_step(model: GPTLM, params, cache, tokens: jax.Array,
                        positions: jax.Array, write_start: jax.Array,
                        block_table: Optional[jax.Array] = None,
                        with_rows: bool = False):
    """Continue a prefill INTO an existing cache: ``tokens`` [b, T] at
    global ``positions`` [b, T] (pads -1), K/V written at cache slots
    ``write_start + [0..T)`` per row (the multi-token ``write_index`` path
    in ``models/layers.py``).

    The chunked-prefill core: a long prompt splits into budget-sized
    chunks that interleave with the engine's decode ticks — each chunk
    attends the already-cached prefix plus itself causally, which is
    mathematically identical to one monolithic prefill (scores depend only
    on stored positions).  Also the prefix-cache completion core: after
    ``CachePool.copy_prefix`` lands a cached prefix, the prompt remainder
    runs through here at ``write_start = prefix_len``.  Returns
    ``(hidden [b, T, d_model], cache)``.

    RAGGED MULTI-PHASE batches (the engine's unified tick): ``b`` is the
    whole slot pool and only SOME rows are prefilling — non-prefill rows
    ride as all-pad (every position -1) with ``write_start`` parked at
    ``seq_len``, so their writes drop whole-row and their outputs are
    never read.  Per-row ``write_start`` plus per-row pad raggedness is
    exactly the bucketed-prefill discipline, so mixing phases in one
    call changes no row's math (row-parallel ops — the same argument
    that makes batch composition invisible everywhere else).
    """
    return _cached_apply(
        model,
        {"params": params, "cache": cache},
        tokens,
        with_rows,
        positions=positions,
        write_index=write_start,
        block_table=block_table,
    )


def verify_step(model: GPTLM, params, cache, tokens: jax.Array,
                positions: jax.Array, write_index: jax.Array,
                block_table: Optional[jax.Array] = None):
    """Score T tokens per row in ONE forward — the speculative-decoding
    verify core.  ``tokens`` [b, T] is each row's current token followed by
    its draft tokens, at global ``positions`` [b, T] (pads -1); K/V land at
    cache slots ``write_index + [0..T)`` per row (the same multi-token
    ``write_index`` scatter chunked prefill uses).

    Exactness: each token's attention reads the post-write cache and masks
    by STORED positions, so position ``p + i`` attends the prefix plus the
    drafts before it — token-for-token identical to ``i`` sequential
    :func:`decode_step` calls (the chunked-prefill argument: scores depend
    only on stored positions, and every op is row/position-parallel).  The
    returned ``hidden`` [b, T, d_model] therefore yields EXACT next-token
    distributions at every draft offset in one pass.

    Rejected drafts need NO cache rollback: their K/V sit at columns
    beyond the accepted frontier, and in the engine's aligned layout
    (column == stored position, :meth:`CachePool.assert_slot_aligned`)
    every stale column holds a position strictly greater than any query
    position that can occur before the column is overwritten — the mask
    ``kp <= qp`` keeps them invisible.  (Under the block rule a query sees
    past itself, to its block's end, and the argument is made again in
    :func:`block_step`; the serving engine refuses drafts on a block model.)  Pad offsets (positions -1) write
    -1 into the position table, invalidating their columns outright.
    """
    return _cached_apply(
        model,
        {"params": params, "cache": cache},
        tokens,
        False,
        positions=positions,
        write_index=write_index,
        block_table=block_table,
    )


def block_step(model: GPTLM, params, cache, prev: Optional[jax.Array],
               tokens: jax.Array, start: jax.Array, live: jax.Array,
               pend: jax.Array, block_table: Optional[jax.Array] = None,
               with_rows: bool = False):
    """One forward of a block model's CURRENT blocks: ``tokens`` [b, L] are
    row ``i``'s block as it stands (filled ids and mask ids), at positions
    ``start[i] + [0..L)``; its K/V are written at those columns EVERY call, so
    a block's keys are rewritten until the block is final.  Rows that are not
    ``live`` ride as pads (positions -1, writes parked at ``seq_len``).

    NARROW (``prev`` None): those L rows alone; returns ``(hidden [b, L,
    d_model], cache)``.  WIDE (``prev`` [b, L]): 2L rows, the completed block
    before the current one riding along where it still awaits its final K/V
    (``pend[i]``): ``prev[i]`` clean at ``start[i] - L + [0..L)``, written by
    this one call and final from then on; pads elsewhere.  A FINAL COLUMN IS
    WRITTEN ONCE: every token has a column of its own (``write_index`` [b,
    2L]) and a pad's is parked at ``seq_len``, so a row with nothing pending
    leaves the columns before its block as they are.  Returns ``(hidden [b,
    2L, d_model], cache)``: the current block is ``[:, L:]``.

    Why the two halves are one forward.  Under the block rule
    (``TransformerConfig.block_len``) a query of the block that starts at
    ``s`` sees the stored positions ``<= s + L - 1`` and no other, and a layer
    writes the call's K/V before it reads.  So the completed block's queries
    see their own clean rows and nothing of the block behind them, which is
    what a forward of their own would show them, and the current block's
    queries read the completed block's FINAL keys, written by this call.

    Why no stale column can be seen.  The block's own columns hold THIS
    call's rows.  Every column before ``s`` was written by this same occupant:
    by the prefill of its prompt's whole blocks, or by the call that carried
    an earlier block clean, at the latest this one - a block is always
    written whole before a later block reads it (the engine feeds no block
    whose predecessor is still pending through a narrow call).  Every column
    from ``s + L`` on holds -1 (a prefill's padding) or, in the aligned layout
    (column == stored position), a position ``>= s + L`` left by a longer
    earlier occupant of the slot or by this occupant's own prefill padding:
    past the block's end, invisible.  So a slot needs no clearing between
    occupants, and the half-filled keys of a block in progress are
    overwritten before any later block can see them."""
    width = tokens.shape[1]
    first = 0 if prev is None else -width
    offs = jnp.arange(first, width, dtype=jnp.int32)[None, :]
    real = live[:, None] & (pend[:, None] | (offs >= 0))
    columns = start[:, None] + offs
    return _cached_apply(
        model,
        {"params": params, "cache": cache},
        tokens if prev is None else jnp.concatenate([prev, tokens], axis=1),
        with_rows,
        positions=jnp.where(real, columns, -1),
        write_index=jnp.where(real, columns, model.config.seq_len),
        block_table=block_table,
    )


def _generate_core(
    model: GPTLM,
    params,
    prompt: jax.Array,
    rng: jax.Array,
    max_new_tokens: int,
    temperature: float,
    top_k: int,
    top_p: float = 0.0,
    prompt_mask: Optional[jax.Array] = None,
) -> jax.Array:
    """The traceable prefill + decode-scan body shared by :func:`generate`
    (jit, one device) and :func:`generate_sharded` (shard_map, any mesh).

    The lm_head applies only to the LAST position's hidden state (the only
    logits sampling reads — full-prompt prefill logits were pure waste),
    column-sharded under TP: sampling then runs vocab-parallel
    (:func:`_sample_sharded`) and the per-step full-vocab all_gather
    disappears for greedy/temperature/top-k decoding.

    ``prompt_mask`` [b, P] enables RAGGED batches: rows LEFT-padded (False
    at the left, so the last slot is each row's final real token — the one
    the head reads).  Pad slots write position -1 into the per-slot cache
    position table and are never attended; each row continues from its own
    length.  None = all rows full length (the aligned fast path).
    """
    from tpu_parallel.models.gpt import _apply_lm_head, _lm_head_params
    from tpu_parallel.parallel.tp import axis_size_or_none

    cfg = model.config
    b, prompt_len = prompt.shape
    if prompt_mask is not None and cfg.prefill_flash:
        raise NotImplementedError(
            "ragged (left-padded) prompts with prefill_flash: the kernels "
            "mask by index, and a left pad would be attended"
        )
    if prompt_len + max_new_tokens > cfg.seq_len:
        raise ValueError(
            f"prompt ({prompt_len}) + max_new_tokens ({max_new_tokens}) "
            f"exceeds seq_len ({cfg.seq_len})"
        )
    if prompt_mask is not None and cfg.positional == "relative":
        raise NotImplementedError(
            "ragged prompts with relative position bias (the shared bias "
            "table assumes row-uniform query positions)"
        )
    # unwrapped head + one up-front FSDP gather: the wrapped head would
    # re-all_gather the vocab kernel every decode step inside the scan
    lm_params = _lm_head_params(cfg, params)

    def next_token(h, rng):
        # h: [b, t, d] hidden states; head only the final position
        logits = _apply_lm_head(cfg, lm_params, h[:, -1:])[:, 0]
        if axis_size_or_none(cfg.model_axis) is not None:
            return _sample_sharded(
                logits, rng, temperature, top_k, top_p, cfg.model_axis
            )
        return _sample(logits, rng, temperature, top_k, top_p)

    # Prefill: one batched forward over the prompt creates and fills the
    # cache ('cache' is created on the fly because it is marked mutable).
    if prompt_mask is None:
        positions = jnp.broadcast_to(jnp.arange(prompt_len), (b, prompt_len))
        lengths = jnp.full((b,), prompt_len, jnp.int32)
    else:
        m = prompt_mask.astype(jnp.int32)
        if m.shape != prompt.shape:
            raise ValueError(
                f"prompt_mask shape {m.shape} != prompt shape {prompt.shape}"
            )
        # real tokens get 0..len-1; pads get -1 (never attended; their
        # nn.Embed lookup clamps harmlessly — the outputs are unread)
        positions = jnp.cumsum(m, axis=1) - 1
        positions = jnp.where(m > 0, positions, -1)
        lengths = m.sum(axis=1).astype(jnp.int32)
    hidden, variables = model.apply(
        {"params": params},
        prompt,
        positions=positions,
        train=False,
        decode=True,
        hidden_only=True,
        mutable=["cache"],
    )
    rng, sub = jax.random.split(rng)
    first = next_token(hidden, sub)

    def step(carry, _):
        cache, tok, pos, rng = carry
        hidden, cache = decode_step(model, params, cache, tok, pos)
        rng, sub = jax.random.split(rng)
        nxt = next_token(hidden, sub)
        return (cache, nxt, pos + 1, rng), tok

    init = (variables["cache"], first, lengths, rng)
    (_, last, _, _), toks = lax.scan(step, init, None, length=max_new_tokens - 1)
    # scan emits the *input* token of each step; append the final sample
    return jnp.concatenate([toks.T, last[:, None]], axis=1)


@functools.partial(
    jax.jit, static_argnums=(0,),
    static_argnames=("max_new_tokens", "temperature", "top_k", "top_p"),
)
def generate(
    model: GPTLM,
    params,
    prompt: jax.Array,
    rng: Optional[jax.Array] = None,
    *,
    max_new_tokens: int = 32,
    temperature: float = 0.0,
    top_k: int = 0,
    top_p: float = 0.0,
    prompt_mask: Optional[jax.Array] = None,
) -> jax.Array:
    """Generate ``max_new_tokens`` continuations of ``prompt`` [batch, P].

    Returns [batch, max_new_tokens] of sampled tokens (greedy when
    ``temperature == 0``).  The prompt must fit the model's ``seq_len``
    together with the new tokens (the cache is allocated at ``seq_len``).
    ``prompt_mask`` serves RAGGED batches — rows LEFT-padded to a common
    length, each continuing from its own last real token (see
    :func:`_generate_core`).  Single-device params layout — for
    mesh-sharded states use :func:`generate_sharded` (or
    ``export_single_device_params`` when the weights aren't split over
    tp/pipe).
    """
    if rng is None:
        rng = jax.random.PRNGKey(0)
    return _generate_core(
        model, params, prompt, rng, max_new_tokens, temperature, top_k, top_p,
        prompt_mask=prompt_mask,
    )


def generate_sharded(
    model: GPTLM,
    params,
    prompt: jax.Array,
    mesh,
    rng: Optional[jax.Array] = None,
    *,
    max_new_tokens: int = 32,
    temperature: float = 0.0,
    top_k: int = 0,
    top_p: float = 0.0,
    prompt_mask: Optional[jax.Array] = None,
    param_specs=None,
    batch_spec=None,
) -> jax.Array:
    """Generate under a mesh: TP-split weights stay split, batch shards DP.
    ``prompt_mask`` serves ragged (left-padded) batches, sharded like the
    prompt rows.

    The serving path for states whose weights live on multiple devices
    (``export_single_device_params`` refuses tp/pipe degree > 1 by design).
    Runs the same prefill + decode scan inside one ``shard_map``: the KV
    cache shards over heads exactly as activations do, TP collectives run
    per decode step, each data shard generates its rows, and pipe meshes
    run each forward as a ring pass over the stages (interleaved-schedule
    models excepted — the model raises).

    ``params`` is the (possibly ``nn.Partitioned``-boxed) params tree from a
    mesh init/training state; ``param_specs`` defaults to its partition
    spec.  Sampling RNG folds over the data axis so shards draw independent
    noise; it must NOT fold over the model axis (TP ranks must sample the
    same token).
    """
    import flax.linen as nn
    from jax.sharding import PartitionSpec as P

    if param_specs is None:
        param_specs = nn.get_partition_spec(params)
    if batch_spec is None:
        batch_spec = P(model.config.data_axis)
    if rng is None:
        rng = jax.random.PRNGKey(0)
    # the shard_map arity is fixed, so a placeholder all-ones mask always
    # rides along; has_mask keeps the no-mask call IDENTICAL to the aligned
    # path inside the core (an all-ones mask is semantically aligned, but
    # must not trip the ragged-vs-relative refusal)
    has_mask = prompt_mask is not None
    if prompt_mask is None:
        prompt_mask = jnp.ones(prompt.shape, jnp.bool_)
    fn = _sharded_generate_fn(
        model,
        mesh,
        _HashableTree.of(param_specs),
        batch_spec,
        max_new_tokens,
        temperature,
        top_k,
        top_p,
        has_mask,
    )
    return fn(params, prompt, prompt_mask, rng)


class _HashableTree:
    """Hashable wrapper for a pytree of hashable leaves (PartitionSpecs) —
    lets the compiled sharded-generate closures live in an lru_cache, so a
    serving loop pays trace + XLA compile once, not per call."""

    __slots__ = ("treedef", "leaves")

    def __init__(self, treedef, leaves):
        self.treedef = treedef
        self.leaves = leaves

    @classmethod
    def of(cls, tree):
        leaves, treedef = jax.tree_util.tree_flatten(tree)
        return cls(treedef, tuple(leaves))

    def tree(self):
        return jax.tree_util.tree_unflatten(self.treedef, list(self.leaves))

    def __hash__(self):
        return hash((self.treedef, self.leaves))

    def __eq__(self, other):
        return (
            isinstance(other, _HashableTree)
            and self.treedef == other.treedef
            and self.leaves == other.leaves
        )


def build_sharded_serving(
    model, mesh, param_specs, batch_specs, out_spec, core, fold_axes=None,
):
    """The one shard_map serving harness, shared by every family.

    ``core(model, params, *batch_args, rng)`` is the traceable decode body
    (:func:`_generate_core`, seq2seq's ``_seq2seq_core``, ...).  The harness
    contributes the invariants both paths must share: sampling RNG folds
    over the DATA axis only (TP ranks must draw the same sample), and
    ``check_vma=False`` — sampled tokens are replicated over the model and
    pipe axes by construction (every TP rank's decision flows through the
    vocab-parallel collectives in :func:`_sample_sharded` — or an
    identical-rng gathered sample on the top_p path; the decode ring
    psum-broadcasts over pipe), which the checker cannot prove.

    ``fold_axes`` overrides the RNG fold: the default ``None`` folds over
    the data axis (batch rows are data-sharded, shards must draw
    independent noise); the serving engine passes ``()`` — its slot arrays
    ride REPLICATED over the data axis, so every rank must draw the SAME
    noise or the replicated outputs silently diverge across ranks.
    """
    from jax.sharding import PartitionSpec as P

    from tpu_parallel.core.rng import fold_rng_over_axis

    if fold_axes is None:
        fold_axes = (model.config.data_axis,)

    def body(params, *args):
        *batch_args, rng = args
        if fold_axes:
            rng = fold_rng_over_axis(rng, tuple(fold_axes))
        return core(model, params, *batch_args, rng)

    return jax.jit(
        jax.shard_map(
            body,
            mesh=mesh,
            in_specs=(param_specs, *batch_specs, P()),
            out_specs=out_spec,
            check_vma=False,
        )
    )


@functools.lru_cache(maxsize=32)
def _sharded_generate_fn(
    model, mesh, specs: _HashableTree, batch_spec, max_new_tokens, temperature,
    top_k, top_p=0.0, has_mask=False,
):
    def core(model_, params, prompt, prompt_mask, rng):
        return _generate_core(
            model_, params, prompt, rng, max_new_tokens, temperature, top_k,
            top_p, prompt_mask=prompt_mask if has_mask else None,
        )

    return build_sharded_serving(
        model, mesh, specs.tree(), (batch_spec, batch_spec), batch_spec, core
    )


# --- beam search --------------------------------------------------------------


def beam_cache_batch_axis(path, x):
    """Batch axis of a KV-cache leaf, by name — ONE registry for every
    family's beam search (a new cache leaf added here reorders correctly
    in both).  K/V payloads (self and cross) and a recurrent layer's
    ``ssm_state`` carry batch at ndim-4; its ``conv_state`` at ndim-3; the
    per-slot position table and the cross padding mask at ndim-2; scalar
    counters return None (pass through)."""
    name = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
    if name.startswith(
        ("cached_key", "cached_value", "cross_key", "cross_value", "ssm_state")
    ):
        return x.ndim - 4
    if name.startswith(("conv_state", "cached_latent")):  # [.., b, rows, width]
        return x.ndim - 3
    if name.startswith(("cached_pos", "cross_mask")):
        return x.ndim - 2
    return None


def beam_expand_cache(cache, k):
    """Replicate every batch row ``k`` ways (beam j of row i = row i*k+j)."""

    def expand(path, x):
        ax = beam_cache_batch_axis(path, x)
        return x if ax is None else jnp.repeat(x, k, axis=ax)

    return jax.tree_util.tree_map_with_path(expand, cache)


def beam_seed_src(cache, num_beams):
    """Insert an identity ``beam_src`` table beside every self-attention
    cache (lazy beam search): ``beam_src[row, slot]`` names the row whose
    cache physically holds that slot of this row's beam history.  Identity
    is correct post-prefill — every beam of a prompt holds identical
    replicated prefill slots.  Seeding happens HERE (not lazily inside the
    layer) so the decode scan's carry structure is fixed from step one."""

    def walk(d):
        if not isinstance(d, dict):
            return d
        out = {key: walk(val) for key, val in d.items()}
        if "cached_key" in d:
            ck = d["cached_key"]
            batch_ax = ck.ndim - 4  # stacked layer dims (nn.scan) lead
            rows, cache_len = ck.shape[batch_ax], ck.shape[batch_ax + 1]
            ident = jnp.arange(rows, dtype=jnp.int32)[:, None] + jnp.zeros(
                (rows, cache_len), jnp.int32
            )
            out["beam_src"] = jnp.broadcast_to(
                ident, (*ck.shape[:batch_ax], rows, cache_len)
            ) + jnp.zeros((), jnp.int32)
        return out

    return walk(cache)


def beam_advance_src(cache, row_idx):
    """Lazy-beam step update: row-gather every ``beam_src`` table by the
    winning beams' parent rows (``new[r'] = old[parent(r')]``).  The K/V
    payloads are NOT touched — that is the point: the eager alternative
    (:func:`beam_reorder_cache`) moves every layer's full cache every step.
    The slot written this step already maps to the writing row (the layer
    maintains that invariant), so the gather alone keeps the table exact."""

    def advance(path, x):
        name = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
        if name == "beam_src":
            return jnp.take(x, row_idx, axis=x.ndim - 2)
        return x

    return jax.tree_util.tree_map_with_path(advance, cache)


def beam_reorder_cache(cache, row_idx, skip_prefixes=()):
    """Gather cache rows to follow their winning beams.  ``skip_prefixes``
    names beam-INVARIANT leaves (e.g. the cross-attention memory caches,
    identical across a row's beams by construction) whose per-step gather
    would be a provable no-op — skipping saves the HBM traffic."""

    def reorder(path, x):
        name = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
        if name.startswith(tuple(skip_prefixes)):
            return x
        ax = beam_cache_batch_axis(path, x)
        return x if ax is None else jnp.take(x, row_idx, axis=ax)

    return jax.tree_util.tree_map_with_path(reorder, cache)


def beam_backtrack(first, toks, src_beams, scores):
    """Follow each row's best final beam back through the per-step
    (token, source-beam) records; returns [batch, T] token ids."""
    def backtrack(carry, xs):
        beam = carry
        step_toks, step_src = xs
        tok_here = jnp.take_along_axis(step_toks, beam[:, None], axis=1)[:, 0]
        beam = jnp.take_along_axis(step_src, beam[:, None], axis=1)[:, 0]
        return beam, tok_here

    best = jnp.argmax(scores, axis=-1)
    beam0, rev_toks = lax.scan(backtrack, best, (toks[::-1], src_beams[::-1]))
    first_tok = jnp.take_along_axis(first, beam0[:, None], axis=1)[:, 0]
    return jnp.concatenate([first_tok[:, None], rev_toks[::-1].T], axis=1)


@functools.partial(
    jax.jit, static_argnums=(0,),
    static_argnames=("max_new_tokens", "num_beams", "length_penalty", "lazy"),
)
def generate_beam(
    model: GPTLM,
    params,
    prompt: jax.Array,
    *,
    max_new_tokens: int = 32,
    num_beams: int = 4,
    length_penalty: float = 0.0,
    lazy: bool = True,
) -> Tuple[jax.Array, jax.Array]:
    """Beam-search decoding: the highest-scoring continuation per prompt row.

    Returns ``(tokens [batch, max_new_tokens], scores [batch])`` where
    ``scores`` is the winning beam's total log-probability divided by
    ``len**length_penalty`` (0 = pure log-prob, 1 = per-token mean).

    Beams ride as extra batch rows through the same prefill + decode scan
    as :func:`generate`; each step takes the top ``num_beams`` of the
    ``num_beams * vocab`` joint continuations per prompt.  ``lazy=True``
    (default) follows beam ancestry through per-slot source-row tables and
    the cross-beam decode attention
    (:func:`~tpu_parallel.models.layers.beam_decode_attention`) — the KV
    cache is never re-gathered; ``lazy=False`` is the eager form that
    physically reorders every layer's cache rows each step (same tokens,
    ~2x the per-step HBM traffic — kept as the reference implementation).
    No early-termination/EOS handling — fixed-length decoding, the same
    contract as :func:`generate`.
    """
    import dataclasses

    cfg = model.config
    b, prompt_len = prompt.shape
    if prompt_len + max_new_tokens > cfg.seq_len:
        raise ValueError(
            f"prompt ({prompt_len}) + max_new_tokens ({max_new_tokens}) "
            f"exceeds seq_len ({cfg.seq_len})"
        )
    k = num_beams
    vocab = cfg.vocab_size

    # prefill ONCE per prompt row, then replicate the cache k ways (beam j
    # of prompt i is row i*k + j) — beams are identical until the first
    # expansion, so prefilling b*k rows would waste (k-1)/k of the FLOPs.
    # Prefill always runs the plain (beam_width=0) model: rows are still
    # un-expanded prompt rows.
    plain = (
        model
        if cfg.beam_width == 0
        else type(model)(dataclasses.replace(cfg, beam_width=0))
    )
    positions = jnp.broadcast_to(jnp.arange(prompt_len), (b, prompt_len))
    logits, variables = plain.apply(
        {"params": params},
        prompt,
        positions=positions,
        train=False,
        decode=True,
        mutable=["cache"],
    )

    cache0 = beam_expand_cache(variables["cache"], k)
    first_logp = jax.nn.log_softmax(logits[:, -1].astype(jnp.float32))  # [b, V]
    scores, first = jax.lax.top_k(first_logp, k)  # [b, k] each
    tok = first.reshape(b * k).astype(jnp.int32)

    if lazy:
        stepper = type(model)(dataclasses.replace(cfg, beam_width=k))
        cache0 = beam_seed_src(cache0, k)
    else:
        stepper = plain

    def step(carry, _):
        cache, tok, scores, pos = carry
        logits, updated = stepper.apply(
            {"params": params, "cache": cache},
            tok[:, None],
            positions=jnp.full((b * k, 1), pos, jnp.int32),
            train=False,
            decode=True,
            mutable=["cache"],
        )
        logp = jax.nn.log_softmax(logits[:, -1].astype(jnp.float32))
        # joint scores over (beam, next-token) per prompt row
        joint = scores[:, :, None] + logp.reshape(b, k, vocab)  # [b, k, V]
        new_scores, flat_idx = jax.lax.top_k(joint.reshape(b, k * vocab), k)
        src_beam = flat_idx // vocab  # [b, k] originating beam per winner
        next_tok = (flat_idx % vocab).astype(jnp.int32)
        row_idx = (src_beam + jnp.arange(b)[:, None] * k).reshape(b * k)
        if lazy:
            # follow ancestry in the tiny int32 tables only
            cache = beam_advance_src(updated["cache"], row_idx)
        else:
            # reorder cache rows to follow winning beams (shared helper: K/V
            # payloads + the position table; scalar counters pass through)
            cache = beam_reorder_cache(updated["cache"], row_idx)
        return (
            (cache, next_tok.reshape(b * k), new_scores, pos + 1),
            (next_tok, src_beam),
        )

    init = (cache0, tok, scores, jnp.int32(prompt_len))
    (cache, tok, scores, _), (toks, src_beams) = lax.scan(
        step, init, None, length=max_new_tokens - 1
    )

    # backtrack: follow each final beam to its token at every step
    # (toks/src_beams: [T-1, b, k]; the first token table is `first` [b, k])
    out = beam_backtrack(first, toks, src_beams, scores)
    best_scores = jnp.max(scores, axis=-1)
    if length_penalty:
        total_len = jnp.float32(max_new_tokens)
        best_scores = best_scores / (total_len**length_penalty)
    return out.astype(jnp.int32), best_scores
