"""Continuous-batching inference engine: iteration-level scheduling over a
fixed pool of KV-cache slots, with a fast-path prefill.

The static path (``models/generate.py``) decodes a batch run-to-completion:
every request starts together and the whole batch waits for the longest
generation.  This engine decodes the SLOT POOL instead — by default one
jitted FUSED tick of ``decode_steps_per_tick`` (8) masked single-token
steps in a ``lax.scan`` over all ``n_slots`` rows, with the KV cache and
the per-slot serving state (device-resident between ticks) donated, so
the host pays one dispatch + one sync per 8 tokens instead of per token —
and lets requests join (prefill into a freed slot) and leave (EOS /
length retirement) between ticks:

- tick = [admissions] + [ONE unified ragged dispatch: every in-flight
  chunked prefill consumes its next prompt chunk (in-device final-chunk
  activation) while decode slots run ``decode_steps_per_tick`` fused
  scan steps, each masked per slot] + [retirements].  The per-phase
  form (``unified_tick=False``, or T=1) advances chunks as separate
  per-slot extend dispatches before the decode dispatch — the parity
  baseline the unified tick is pinned bitwise against.  With
  ``draft_tokens > 0`` the decode step becomes a SPECULATIVE verify
  tick (``serving/spec_decode.py``): a drafter proposes up to K tokens
  per slot, one multi-token forward scores them all, and each slot
  advances by its accepted prefix + one bonus token — output provably
  identical to one-token ticks (greedy: bitwise; sampled: in
  distribution via the Leviathan rejection rule).  An explicit T > 1
  fuses T whole draft-verify-accept blocks per dispatch, drafting
  in-scan from a device-resident token history via the traceable NGram
  twin.  ``step()`` is ``launch()`` then ``collect()``, one tick deep:
  on the fused and unified ticks it launches tick N+1 before it reads
  tick N, so N's host sync and delivery, and the caller's work between
  two steps, overlap N+1's device compute.
- the decode step threads per-slot positions and per-slot cache write
  indices (``write_index`` — the slot-indexed write path in
  ``models/layers.py``) because rows sit at different depths of their
  generations; the attention mask already keys off stored per-slot
  positions, so mixed-depth rows read correctly.
- sampling knobs are per-REQUEST traced arrays (temperature / top_k /
  top_p per slot, :func:`sample_tokens`): two requests with different
  knobs share a tick without recompiling.
- inactive (free) slots still run through the step — their sampled tokens
  are ignored and their cache writes are aimed at column ``seq_len``
  (out of range, dropped by scatter semantics) so an idle or
  mid-chunked-prefill row is never touched; masking work out of a
  fixed-shape jitted step is the standard slot-pool trade.

Prefill fast path — three cooperating mechanisms (all EXACT: greedy
outputs are token-identical to batch-1 exact-length prefill, pinned in
``tests/test_serving.py``):

1. **Length bucketing** (``prefill_buckets``): prompts pad RIGHT up to a
   small geometric bucket set, so ``_prefill_core`` compiles O(#buckets)
   shapes instead of O(#distinct lengths).  Pad slots carry position -1
   (never attended) and are overwritten by the request's own decode
   tokens — zero cache-capacity cost.  Same-bucket admissions run as ONE
   batched prefill (the scheduler groups them; the batch pads to
   ``prefill_batch`` rows so batch size never adds compile shapes) and
   the fresh rows scatter into their slots in one call.
2. **Chunked prefill** (``prefill_chunk_tokens``): prompts above the
   budget split into budget-sized chunks that interleave with decode
   ticks — one chunk per tick continues INTO the already-assigned slot's
   cache via the multi-token ``write_index`` path
   (:func:`~tpu_parallel.models.generate.prefill_extend_step`), bounding
   how long any prefill can stall in-flight decodes.
3. **Prefix reuse** (``prefix_cache_size``): an LRU cache over
   bucket-aligned prompt prefixes (system prompts, few-shot headers);
   hits COPY the stored K/V row into the fresh slot
   (:meth:`CachePool.copy_prefix`) and only the prompt remainder runs the
   model.  Hit/miss/eviction counters surface in
   :class:`~tpu_parallel.serving.metrics.ServingMetrics`.

Block-paged KV cache (``kv_block_tokens`` > 0 or ``"auto"``): swaps the
fixed ``n_slots x seq_len`` pool for a flat pool of fixed-size blocks
addressed through per-slot block tables
(:class:`~tpu_parallel.serving.cache_pool.PagedCachePool`) — slot count
decouples from ``seq_len`` (admission reserves estimated blocks, with
transient exhaustion queuing head-of-line and impossible requests
rejecting with the typed ``capacity`` reason), and prefix reuse becomes
refcounted block SHARING with copy-on-write instead of row copies.  All
serving paths (per-step / fused / speculative / chunked / int8 /
crash-replay) stay greedy-bitwise-identical to the fixed layout
(``tests/test_paged_kv.py``; memory-model story in
``docs/10_serving_engine.md``).  Both pools run ONE family of device
programs: the block table is an operand of each (``None`` on the
fixed-slot pool, where it adds no parameter to the compiled program).
Not yet paged: lazy beam search.

Hierarchical KV memory (``kv_radix_cache`` / ``kv_host_blocks``, paged
only — ``serving/kv_hierarchy.py``): the aligned-LRU prefix cache swaps
for a token-level RADIX TREE over the block pool (any shared prefix
hits at block granularity, frequency-aware eviction) with a host-RAM
offload tier below it (evicted-but-warm blocks spill via batched
``device_get`` and restore via batched ``device_put`` instead of a
re-prefill), plus ``export_prefix``/``import_prefix`` — the
cross-replica KV migration primitive the cluster's forced-prefix
relocation paths use to ship a moved request's blocks instead of
recomputing them.

Greedy equivalence: for requests submitted together, per-request outputs
are token-identical to static ``generate()`` on the same prompts (pinned
in ``tests/test_serving.py``) — row-parallel ops make batch composition
invisible to each row, and both paths share
:func:`~tpu_parallel.models.generate.decode_step`.

One chip: the engine's programs are plain ``jit``s over unsharded
params.  Weights split over a mesh are served statically through
``generate_sharded``.
"""

from __future__ import annotations

import dataclasses
import functools
import json
import logging
import math
import time
from typing import (
    Callable,
    Dict,
    List,
    Optional,
    Sequence,
    Tuple,
    Union,
)

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from tpu_parallel.models.generate import (
    block_step,
    decode_step,
    padded_prefill_inputs,
    prefill_extend_step,
    prefill_step,
    verify_step,
)
from tpu_parallel.obs.device_clock import DeviceClock
from tpu_parallel.obs.phases import ENGINE_PREFIX, SPAN_PREFIX, phase
from tpu_parallel.obs.registry import MetricRegistry
from tpu_parallel.obs.tracer import NULL_TRACER, Tracer
from tpu_parallel.serving.cache_pool import (
    CachePool,
    KVIntegrityError,
    PagedCachePool,
    block_checksums,
)
from tpu_parallel.serving.metrics import (
    STALL_NONE,
    STALL_PREFILL,
    STALL_QUEUE_EMPTY,
    STALL_SPEC_VERIFY,
    ServingMetrics,
)
from tpu_parallel.serving.kv_hierarchy import (
    MIGRATE_ALREADY_CACHED,
    MIGRATE_IMPORTED,
    MIGRATE_INCOMPATIBLE,
    MIGRATE_INTEGRITY,
    MIGRATE_NO_BLOCKS,
    MIGRATE_NO_KEY,
    MIGRATE_NO_PREFIX_CACHE,
    MIGRATE_NOT_PAGED,
    MIGRATE_WEIGHTS_VERSION,
    KVPrefixExport,
    RadixPrefixCache,
)
from tpu_parallel.serving.prefix_cache import PrefixCache
from tpu_parallel.serving.request import (
    CANCELLED,
    FAIL_INTEGRITY,
    FAILED,
    FINISHED,
    REJECT_CAPACITY,
    REJECT_UNSUPPORTED,
    REJECTED,
    RUNNING,
    Request,
    RequestOutput,
    StreamEvent,
)
from tpu_parallel.serving.scheduler import FIFOScheduler, SchedulerConfig
from tpu_parallel.serving.spec_decode import (
    Drafter,
    NGramDrafter,
    adapt_draft_len,
    adapt_draft_len_traced,
    draft_for_row,
    filter_logits,
    ngram_draft_tokens,
    verify_tokens,
)
from tpu_parallel.utils.stack_room import stack_room


def validate_same_shapes(old, new) -> None:
    """Raise ``ValueError`` unless ``new`` matches ``old`` leaf for leaf
    in structure, shape and dtype — the precondition for a
    recompile-free weight rebind.  THE one check both
    :meth:`ServingEngine.rebind_params` and the cluster's
    ``begin_swap`` typed up-front refusal run, so they can never drift
    apart."""
    old_leaves, old_def = jax.tree_util.tree_flatten_with_path(old)
    new_leaves, new_def = jax.tree_util.tree_flatten_with_path(new)
    if old_def != new_def:
        raise ValueError(
            f"weight tree structure differs: {new_def} != {old_def}"
        )
    for (path, a), (_, b) in zip(old_leaves, new_leaves):
        if (
            getattr(a, "shape", None) != getattr(b, "shape", None)
            or getattr(a, "dtype", None) != getattr(b, "dtype", None)
        ):
            raise ValueError(
                f"leaf {jax.tree_util.keystr(path)}: "
                f"{getattr(b, 'shape', None)}/{getattr(b, 'dtype', None)}"
                f" != served {getattr(a, 'shape', None)}/"
                f"{getattr(a, 'dtype', None)}"
            )


# device-side integrity sentinel: a sampled "token" of this value means
# the row's logits contained NaN/Inf — the host fails the request typed
# (``FAIL_INTEGRITY``) instead of streaming garbage.  Rides the existing
# tick outputs at zero extra transfer cost (an int is an int); -2 can
# never collide with real ids (>= 0) or the parked/pad value (-1).
NON_FINITE_TOKEN = -2


def sample_tokens(
    logits: jax.Array,
    rng: jax.Array,
    temperature: jax.Array,
    top_k: jax.Array,
    top_p: jax.Array,
    rows: Optional[jax.Array] = None,
) -> jax.Array:
    """Per-ROW sampling from [batch, vocab] logits with per-row knobs.

    The vectorized counterpart of ``models.generate._sample``: the knobs
    are traced [batch] arrays, so one compiled program serves every knob
    combination in the pool.  Same semantics per row — ``temperature == 0``
    is exact argmax; ``top_k``/``top_p`` compose by intersection after the
    temperature scale; ``top_k <= 0`` / ``top_p`` outside (0, 1) disable
    that filter; the argmax token always survives the nucleus cut.  The
    filter math lives in ``spec_decode.filter_logits`` — the speculative
    rejection rule needs the SAME target distribution this sampler draws
    from, or spec-vs-nonspec would silently drift.

    The sampler computes only what a row whose token will be READ asks
    for.  ``rows`` is the bool mask of those rows (None: every row): a
    slot's knobs outlive its request, so the callers pass their live
    rows.  Where none of them is sampled, which the device decides a call
    (``lax.cond``, both branches in the one program), the token is the
    argmax and neither the filters' two full-vocabulary sorts nor the
    Gumbel draw run; ``filter_logits`` applies the same rule to each sort.
    A branch that runs, runs on all rows: a row's token does not depend
    on who shares its batch.

    Integrity sentinel: a row whose logits contain ANY non-finite value
    returns ``NON_FINITE_TOKEN`` instead of a sample — ``argmax`` over
    NaN logits would otherwise return an arbitrary-but-valid token id
    and the stream would continue as confident garbage.  One
    ``isfinite`` reduce per row is noise next to the lm_head matmul
    that produced the logits; finite rows are bitwise unchanged."""
    lf = logits.astype(jnp.float32)
    greedy = jnp.argmax(lf, axis=-1).astype(jnp.int32)
    sampled_rows = temperature > 0.0
    counts = sampled_rows if rows is None else sampled_rows & rows

    def draw(_):
        # greedy rows take the argmax branch of the where, so their
        # filtered (guard-divided) logits are never read
        x = filter_logits(lf, temperature, top_k, top_p, rows)
        sampled = jax.random.categorical(rng, x, axis=-1).astype(jnp.int32)
        return jnp.where(sampled_rows, sampled, greedy)

    out = lax.cond(jnp.any(counts), draw, lambda _: greedy, None)
    finite = jnp.isfinite(lf).all(axis=-1)
    return jnp.where(finite, out, jnp.int32(NON_FINITE_TOKEN))


def _full_last_logits(cfg, params, hidden, last_idx=None):
    """lm_head over ONE position per row (the per-row knob sampler needs
    the whole vocab row; batch is n_slots, not tokens).

    ``last_idx`` [batch] selects each row's position (the bucketed
    prefill's per-row LAST REAL token — right padding means it is not
    uniformly -1); None reads the final position (decode steps, exact
    prefill)."""
    from tpu_parallel.models.gpt import lm_logits

    if last_idx is None:
        hidden = hidden[:, -1:]
    else:
        idx = jnp.broadcast_to(
            last_idx.astype(jnp.int32)[:, None, None],
            (hidden.shape[0], 1, hidden.shape[2]),
        )
        hidden = jnp.take_along_axis(hidden, idx, axis=1)
    return lm_logits(cfg, params, hidden)[:, 0]


def _full_logits(cfg, params, hidden):
    """lm_head over EVERY position of [batch, T, d_model] hidden — the
    speculative verify needs all T target distributions, not just the
    last (T = draft_tokens + 1, batch = n_slots — still tiny)."""
    from tpu_parallel.models.gpt import lm_logits

    return lm_logits(cfg, params, hidden)


def _pad_parked(cfg, pos, widx):
    """Positions of a decode step with the parked rows (``widx`` at
    ``seq_len``: free slots, finished ones, slots in the middle of a chunked
    prompt) at -1.  Attention masks such a row by itself, its write falls
    out of range; a recurrent layer has to be TOLD that the row is a pad,
    and a negative position is how every caller tells it
    (``models/ssm.py``).  A model without one gets ``pos`` as it is."""
    if not cfg.recurrent_layers:
        return pos
    return jnp.where(widx < cfg.seq_len, pos, -1)


def _calls(rows):
    """One apply's expert row counts ``[layers, held + 1]`` as a one-call
    ``[1, layers, held + 1]`` block (the fused tick's are one a step)."""
    return None if rows is None else rows[None]


def _prefill_core(model, params, prompt, positions, last_idx, rng):
    """Batch-N pad-aware prefill: fills fresh caches, returns each row's
    last REAL position's full-vocab logits + the cache.  ``positions``
    carry -1 at pad slots (:func:`padded_prefill_inputs`); with uniform
    ``arange`` positions this is the exact-length prefill.  ``rng`` unused
    (sampling happens outside so the prefill compiles per SHAPE only, not
    per knob set)."""
    del rng
    hidden, cache, rows = prefill_step(
        model, params, prompt, positions, with_rows=True
    )
    logits = _full_last_logits(model.config, params, hidden, last_idx)
    return logits, cache, _calls(rows)


def _extend_core(
    model, params, tokens, positions, last_idx, write_start, cache, rng,
    table=None,
):
    """Continue a prefill into existing cache rows (chunked prefill /
    prefix-reuse remainder / every paged admission): tokens at global
    ``positions`` (pads -1) write K/V at columns ``write_start + [0..T)``.
    Returns the chunk's last real position's logits (read only for the
    FINAL chunk) + the extended cache.

    ``table`` is the block table of the rows in ``cache``: None on the
    fixed-slot pool, whose ``cache`` is the rows themselves; on the paged
    pool ``cache`` is the whole block pool and each row's K/V lands
    DIRECTLY in it (column ``c`` at ``table[row, c // bt] * bt + c % bt``;
    a dummy row's all--1 table drops every write), so there is no fresh
    per-request cache to insert or scatter."""
    del rng
    hidden, cache, rows = prefill_extend_step(
        model, params, cache, tokens, positions, write_start,
        block_table=table, with_rows=True,
    )
    logits = _full_last_logits(model.config, params, hidden, last_idx)
    return logits, cache, _calls(rows)


def _decode_core(
    model, params, tok, pos, widx, temperature, top_k, top_p, cache, rng,
    table=None,
):
    """One engine tick over the slot pool: slot-indexed cache writes,
    per-slot sampling; with a block ``table`` the reads and writes go
    through it and the math is the same (greedy output bitwise identical
    across the pools).  Returns (next_tokens [n_slots], new cache)."""
    hidden, cache, rows = decode_step(
        model, params, cache, tok, _pad_parked(model.config, pos, widx),
        write_index=widx, block_table=table, with_rows=True,
    )
    logits = _full_last_logits(model.config, params, hidden)
    nxt = sample_tokens(
        logits, rng, temperature, top_k, top_p,
        rows=widx < model.config.seq_len,
    )
    return nxt, cache, _calls(rows)


def _fused_decode_core(
    model, params, steps, tok, pos, widx, live, budget, eos, temp, topk,
    topp, cache, rng, table=None,
):
    """``steps`` masked single-token decode ticks in ONE jitted
    ``lax.scan`` — the fused engine tick's device body.  Per-slot serving
    state rides the scan carry as device arrays (current token, cache
    position, write index, live mask, remaining token budget); the host
    uploads it only after admissions/releases and otherwise re-donates
    the returned arrays, so a steady-state decode pays ONE dispatch +
    ONE sync per ``steps`` tokens instead of per token.

    Each scan step is bit-identical to one per-step ``_decode_core``
    tick: same ``decode_step``, same last-position lm_head, same per-slot
    sampler (greedy output is therefore bitwise identical; sampled rows
    draw from the same per-knob distributions under a per-step folded
    rng).  A slot that finishes MID-SCAN — EOS sampled, or its budget
    decremented to zero — drops out of the ``live`` mask: subsequent
    steps park its cache writes at column ``seq_len`` exactly as
    inactive slots do on the per-step tick, its state stops advancing,
    and its emitted positions carry -1.  ``eos`` is -1 for requests
    without an EOS id (sampled tokens are nonnegative, so -1 never
    matches).

    Returns ``(block [steps, n_slots], counts [n_slots], state, cache,
    rows)`` where ``block`` holds each step's emitted token per slot (-1
    where the slot was not live) and ``counts`` is each slot's progress
    this tick — live steps form a PREFIX of the scan, so the host delivers
    ``block[:counts[s], s]`` through the existing StreamEvent path.
    ``rows`` ``[steps, layers, held + 1]`` counts the rows each step routed
    to each held expert (None for a model without a dropless expert layer:
    the program is then the one it was).
    """
    cfg = model.config
    seq_len = cfg.seq_len

    def body(carry, step_rng):
        tok, pos, widx, live, budget, cache = carry
        widx_eff = jnp.where(live, widx, seq_len)
        hidden, cache, rows = decode_step(
            model, params, cache, tok, _pad_parked(cfg, pos, widx_eff),
            write_index=widx_eff, block_table=table, with_rows=True,
        )
        logits = _full_last_logits(cfg, params, hidden)
        nxt = sample_tokens(logits, step_rng, temp, topk, topp, rows=live)
        emitted = jnp.where(live, nxt, -1)
        budget = budget - live.astype(budget.dtype)
        # the NaN/Inf sentinel stops the slot exactly like EOS: steps
        # past non-finite logits are garbage, so the slot parks and the
        # emitted sentinel (counted below) lets the host fail it typed
        done = live & (
            (nxt == eos) | (budget <= 0) | (nxt == NON_FINITE_TOKEN)
        )
        adv = live.astype(pos.dtype)
        pos = pos + adv
        widx = widx + adv
        tok = jnp.where(live, nxt, tok)
        live = live & ~done
        return (tok, pos, widx, live, budget, cache), (emitted, rows)

    (tok, pos, widx, live, budget, cache), (block, rows) = lax.scan(
        body, (tok, pos, widx, live, budget, cache),
        jax.random.split(rng, steps),
    )
    # every live-emitted position counts — including the sentinel (-2),
    # which is a PROGRESS signal (the typed failure) even though it is
    # not a token; parked steps emit -1 and live steps never do
    counts = (block != -1).sum(axis=0).astype(jnp.int32)
    return block, counts, (tok, pos, widx, live, budget), cache, rows


def _verify_core(
    model, params, tok, drafts, draft_len, pos, widx, temperature, top_k,
    top_p, cache, rng, table=None,
):
    """One SPECULATIVE engine tick over the slot pool: each row feeds its
    current token plus its (padded) draft block through one multi-token
    forward (:func:`~tpu_parallel.models.generate.verify_step`), scores
    every offset, and the per-row acceptance rule
    (:func:`~tpu_parallel.serving.spec_decode.verify_tokens`) keeps the
    longest exact prefix + one bonus token.

    Padding discipline: offsets beyond a row's ``draft_len`` carry
    position -1 — their cache writes land -1 in the position table
    (column invalidated outright, never attended) and their logits are
    garbage the acceptance rule cannot reach (``accepted <= draft_len``).
    Inactive/parked rows (``widx == seq_len``) drop every write out of
    range exactly as on the plain decode tick.  Returns
    ``(tokens [n, K+1], accepted [n], new cache)``; the host delivers
    ``accepted + 1`` tokens per active row.
    """
    k = drafts.shape[1]
    tokens = jnp.concatenate([tok[:, None], drafts], axis=1)
    offs = jnp.arange(k + 1, dtype=jnp.int32)[None, :]
    positions = jnp.where(
        offs <= draft_len[:, None], pos[:, None] + offs, -1
    )
    hidden, cache = verify_step(
        model, params, cache, tokens, positions, widx, block_table=table
    )
    logits = _full_logits(model.config, params, hidden)
    out_tokens, accepted = verify_tokens(
        drafts, draft_len, logits, rng, temperature, top_k, top_p
    )
    # integrity sentinel, spec edition: argmax over NaN logits returns
    # an arbitrary-but-valid token — screen every offset the acceptance
    # rule can reach (<= draft_len; pad offsets carry garbage BY DESIGN
    # and must not trip it) and emit the sentinel row instead
    finite = jnp.where(
        offs <= draft_len[:, None],
        jnp.isfinite(logits).all(axis=-1),
        True,
    ).all(axis=1)
    out_tokens = jnp.where(
        finite[:, None], out_tokens, jnp.int32(NON_FINITE_TOKEN)
    )
    return out_tokens, accepted, cache


def _ragged_chunk_phase(
    model, params, tok, pos, widx, live, budget, eos, temp, topk, topp,
    ctoks, clen, cstart, cfinal, cbudget, cache, rng, table=None,
):
    """The unified tick's PREFILL PHASE: one multi-token forward over the
    fixed ``[n_slots, chunk_tokens]`` input block advances every
    mid-chunked-prefill slot by its next prompt chunk while every other
    row rides along as padding (positions -1, writes parked at column
    ``seq_len`` — the standard ragged discard).  Rows whose chunk
    COMPLETES their prompt (``cfinal``) activate IN-DEVICE: their first
    token samples from the chunk's last real position's logits with the
    slot's own knobs and the slot state flips to decode-live — so a
    prompt can finish prefilling and start decoding inside the SAME
    dispatch, exactly as the per-phase engine's advance-then-decode tick
    ordering, minus its extra dispatch + sync per chunk slot.

    ``ctoks`` [n, C] right-padded chunk tokens, ``clen`` [n] real tokens
    this tick (0 = row not prefilling), ``cstart`` [n] the slot's prefill
    depth (write offset), ``cfinal`` [n] whether this chunk is the
    prompt's last, ``cbudget`` [n] ``max_new_tokens`` for activating
    rows.  Returns ``(act_emit [n], new slot state, cache)`` where
    ``act_emit`` carries each activating row's sampled first token (-1
    elsewhere) — delivered by the host BEFORE the tick's decode tokens,
    mirroring the per-phase activation order.
    """
    cfg = model.config
    seq_len = cfg.seq_len
    chunk = ctoks.shape[1]
    iota = jnp.arange(chunk, dtype=jnp.int32)[None, :]
    positions = jnp.where(
        iota < clen[:, None], cstart[:, None] + iota, -1
    )
    wstart = jnp.where(clen > 0, cstart, seq_len)
    hidden, cache, rows = prefill_extend_step(
        model, params, cache, ctoks, positions, wstart, block_table=table,
        with_rows=True,
    )
    logits = _full_last_logits(
        cfg, params, hidden, jnp.maximum(clen - 1, 0)
    )
    act = cfinal & (clen > 0)
    tok0 = sample_tokens(logits, rng, temp, topk, topp, rows=act)
    nb = cbudget - 1
    done0 = (tok0 == eos) | (nb <= 0)
    new_pos = cstart + clen
    tok = jnp.where(act, tok0, tok)
    pos = jnp.where(act, new_pos, pos)
    widx = jnp.where(act, new_pos, widx)
    budget = jnp.where(act, nb, budget)
    live = jnp.where(act, ~done0, live)
    act_emit = jnp.where(act, tok0, -1)
    return act_emit, (tok, pos, widx, live, budget), cache, _calls(rows)


def _unified_tick_core(
    model, params, steps, tok, pos, widx, live, budget, eos, temp, topk,
    topp, ctoks, clen, cstart, cfinal, cbudget, cache, rng, table=None,
):
    """THE unified ragged engine tick: prefill-chunk slots consume their
    next prompt chunk (with in-device final-chunk activation,
    :func:`_ragged_chunk_phase`) and decode slots run ``steps`` masked
    decode scan steps (:func:`_fused_decode_core`) in ONE jitted
    dispatch — a tick that previously cost one extend dispatch PER chunk
    slot plus the decode dispatch now costs exactly one, and a prefill
    chunk no longer stalls in-flight decodes for a dispatch of its own.
    Greedy output is bitwise identical to the per-phase engine by the
    same row-parallel argument as the batched bucketed prefill (batch
    composition is invisible to each row; every op is row/position
    parallel).  Returns ``(act_emit [n], block [steps, n], counts [n],
    state, cache, rows)`` (``rows``: the chunk phase's expert row counts,
    then each decode step's).
    """
    rng_act, rng_scan = jax.random.split(rng)
    act_emit, (tok, pos, widx, live, budget), cache, chunk_rows = (
        _ragged_chunk_phase(
            model, params, tok, pos, widx, live, budget, eos, temp, topk,
            topp, ctoks, clen, cstart, cfinal, cbudget, cache, rng_act,
            table=table,
        )
    )
    block, counts, state, cache, rows = _fused_decode_core(
        model, params, steps, tok, pos, widx, live, budget, eos, temp,
        topk, topp, cache, rng_scan, table=table,
    )
    if rows is not None:
        rows = jnp.concatenate([chunk_rows, rows])
    return act_emit, block, counts, state, cache, rows


def _fused_spec_core(
    model, params, steps, k, max_ngram, min_ngram, adaptive, tok, pos,
    widx, live, budget, keff, hist, eos, temp, topk, topp, kmax,
    cache, rng, table=None,
):
    """``steps`` speculative draft-verify-accept blocks in ONE jitted
    ``lax.scan`` — the fused treatment of the verify tick, which before
    this paid one dispatch + one sync per block.  Each scan step is the
    per-step :func:`_verify_core` tick verbatim (same
    :func:`~tpu_parallel.models.generate.verify_step`, same
    :func:`~tpu_parallel.serving.spec_decode.verify_tokens` sharing
    ``filter_logits`` with the sampler), with the two host-side jobs
    folded on device:

    - DRAFTING: block ``t+1``'s context contains block ``t``'s accepted
      tokens, so the scan carries the per-slot token ``hist`` [n,
      seq_len] and drafts via
      :func:`~tpu_parallel.serving.spec_decode.ngram_draft_tokens` — the
      traceable twin of the host ``NGramDrafter`` (token-identical, so
      fused-vs-per-step greedy output stays bitwise; the engine refuses
      to fuse any OTHER drafter, whose host state the scan cannot see).
    - ADAPTATION: ``keff`` rides the carry and grows/shrinks per block
      by the shared :func:`adapt_draft_len` law (``adaptive`` static).

    Budget/EOS discipline matches the per-step tick token-for-token: a
    block delivers ``accepted + 1`` tokens truncated at the first EOS,
    the slot drops out of ``live`` (writes parked at ``seq_len``), and
    surplus verify K/V beyond the finish is dead weight masked by the
    aligned layout.  Returns ``(blocks [steps, n, K+1], counts
    [steps, n], drafted [steps, n], accepted [steps, n], state, cache)``
    where ``counts`` is each block's DELIVERED token count per slot
    (0 = slot not live that block).
    """
    cfg = model.config
    seq_len = cfg.seq_len
    offs = jnp.arange(k + 1, dtype=jnp.int32)[None, :]
    rows = jnp.arange(tok.shape[0])

    def body(carry, step_rng):
        tok, pos, widx, live, budget, keff, hist, cache = carry
        hlen = pos + 1
        cap = jnp.minimum(
            jnp.minimum(keff, seq_len - 1 - widx), budget - 1
        )
        cap = jnp.where(live, jnp.maximum(cap, 0), 0)
        drafts, dlen = ngram_draft_tokens(
            hist, hlen, cap, k, max_ngram, min_ngram
        )
        dlen = jnp.where(live, dlen, 0)
        widx_eff = jnp.where(live, widx, seq_len)
        tokens = jnp.concatenate([tok[:, None], drafts], axis=1)
        positions = jnp.where(
            offs <= dlen[:, None], pos[:, None] + offs, -1
        )
        hidden, cache = verify_step(
            model, params, cache, tokens, positions, widx_eff,
            block_table=table,
        )
        logits = _full_logits(cfg, params, hidden)
        out_tokens, accepted = verify_tokens(
            drafts, dlen, logits, step_rng, temp, topk, topp
        )
        # integrity sentinel (same screen as _verify_core): a row whose
        # reachable verify logits went non-finite emits the sentinel and
        # stops — blocks after NaN are garbage by definition
        finite = jnp.where(
            offs <= dlen[:, None],
            jnp.isfinite(logits).all(axis=-1),
            True,
        ).all(axis=1)
        out_tokens = jnp.where(
            finite[:, None], out_tokens, jnp.int32(NON_FINITE_TOKEN)
        )
        # delivery truncation, the per-step host loop's law: accepted + 1
        # tokens, cut at the first EOS; a length finish only ever lands
        # on the block's last token (draft_for_row's budget clamp)
        in_block = offs <= accepted[:, None]
        is_eos = (out_tokens == eos[:, None]) & in_block
        eos_at = jnp.where(
            is_eos.any(axis=1),
            jnp.argmax(is_eos, axis=1).astype(jnp.int32),
            k + 1,
        )
        e = jnp.minimum(accepted + 1, eos_at + 1)
        e = jnp.where(live, e, 0)
        emitted = jnp.where(offs < e[:, None], out_tokens, -1)
        new_budget = budget - e
        done = live & (is_eos.any(axis=1) | (new_budget <= 0) | ~finite)
        # history gains the block's accepted + bonus tokens at columns
        # pos + 1 + j (out-of-range targets for dead rows drop)
        for j in range(k + 1):
            col = jnp.where(
                live & (j <= accepted), pos + 1 + j, seq_len
            )
            hist = hist.at[rows, col].set(out_tokens[:, j])
        adv = jnp.where(live, accepted + 1, 0)
        pos = pos + adv
        widx = widx + adv
        tok = jnp.where(
            live,
            jnp.take_along_axis(
                out_tokens, accepted[:, None], axis=1
            )[:, 0],
            tok,
        )
        budget = jnp.where(live, new_budget, budget)
        new_live = live & ~done
        if adaptive:
            keff = jnp.where(
                new_live & (kmax > 0),
                adapt_draft_len_traced(keff, dlen, accepted, kmax),
                keff,
            )
        return (
            (tok, pos, widx, new_live, budget, keff, hist, cache),
            (emitted, e, dlen, accepted),
        )

    (tok, pos, widx, live, budget, keff, hist, cache), outs = lax.scan(
        body,
        (tok, pos, widx, live, budget, keff, hist, cache),
        jax.random.split(rng, steps),
    )
    blocks, counts, drafted, accepted = outs
    return (
        blocks, counts, drafted, accepted,
        (tok, pos, widx, live, budget, keff, hist), cache,
    )


def _unified_spec_core(
    model, params, steps, k, max_ngram, min_ngram, adaptive, tok, pos,
    widx, live, budget, keff, hist, eos, temp, topk, topp, kmax, ctoks,
    clen, cstart, cfinal, cbudget, cache, rng, table=None,
):
    """The unified ragged tick's SPECULATIVE form: the same chunk-phase
    prologue as :func:`_unified_tick_core` (a freshly-activated row's
    first token lands in ``hist`` so the first verify block can draft
    from it), then ``steps`` fused draft-verify blocks
    (:func:`_fused_spec_core`) — prefill chunks, activation, drafting,
    verify and acceptance all inside one dispatch."""
    seq_len = model.config.seq_len
    rng_act, rng_scan = jax.random.split(rng)
    act_emit, (tok, pos, widx, live, budget), cache, _ = _ragged_chunk_phase(
        model, params, tok, pos, widx, live, budget, eos, temp, topk,
        topp, ctoks, clen, cstart, cfinal, cbudget, cache, rng_act,
        table=table,
    )
    act = act_emit >= 0
    # an activating row's context = prompt (uploaded with the state) +
    # its first token; its draft length starts at the slot cap
    rows = jnp.arange(tok.shape[0])
    col = jnp.where(act, cstart + clen, seq_len)
    hist = hist.at[rows, col].set(jnp.maximum(act_emit, 0))
    keff = jnp.where(act, kmax, keff)
    blocks, counts, drafted, accepted, state, cache = _fused_spec_core(
        model, params, steps, k, max_ngram, min_ngram, adaptive, tok,
        pos, widx, live, budget, keff, hist, eos, temp, topk, topp,
        kmax, cache, rng_scan, table=table,
    )
    return act_emit, blocks, counts, drafted, accepted, state, cache


def sample_block(logits, rng, temperature, rows=None):
    """A block model's pick and its CONFIDENCE, a position: ``(x0, conf)``
    ``[n, L]`` from logits ``[n, L, vocab]`` with per-ROW temperature ``[n]``.

    ``x0`` is the argmax (``temperature == 0``) or a draw from
    ``softmax(logits / temperature)``; ``conf`` is that distribution's
    probability of ``x0``.  Greedy needs the row's maximum, where it lies
    and the sum of ``exp(l - max)``, reductions over the vocabulary that one
    pass gives: ``conf = 1 / sum(exp(l - max))``.  No sort: the filters a
    nucleus or a top-k would need are refused on a block model.  As in
    :func:`sample_tokens`, the draw runs only where a row that will be READ
    (``rows``) is sampled, chosen on the device a call (``lax.cond``).  A
    position whose logits hold a NaN or an infinity has a non-finite
    ``conf``: that is the integrity screen, at no pass of its own."""
    lf = logits.astype(jnp.float32)
    top = jnp.max(lf, axis=-1)
    greedy = jnp.argmax(lf, axis=-1).astype(jnp.int32)
    conf = 1.0 / jnp.sum(jnp.exp(lf - top[..., None]), axis=-1)
    sampled_rows = temperature > 0.0
    counts = sampled_rows if rows is None else sampled_rows & rows

    def draw(_):
        t = jnp.where(sampled_rows, temperature, 1.0)[:, None, None]
        scaled = lf / t
        x = jax.random.categorical(rng, scaled, axis=-1).astype(jnp.int32)
        peak = jnp.max(scaled, axis=-1)
        at = jnp.take_along_axis(scaled, x[..., None], axis=-1)[..., 0]
        p = jnp.exp(at - peak) / jnp.sum(
            jnp.exp(scaled - peak[..., None]), axis=-1
        )
        keep = sampled_rows[:, None]
        return jnp.where(keep, x, greedy), jnp.where(keep, p, conf)

    return lax.cond(jnp.any(counts), draw, lambda _: (greedy, conf), None)


def unmask_choice(conf, masked, nstep, dsteps, threshold):
    """Which masked positions of each row's block a denoising step fills:
    ``[n, L]`` bool from the confidences ``conf`` ``[n, L]``, the mask
    ``masked``, the steps the block has had (``nstep`` ``[n]``), the
    request's steps a block ``dsteps`` and its ``threshold``.

    Static rule: the ``L // T`` (one more in the first ``L % T`` steps) most
    confident masked positions, the lower index first among equals, at most
    what is masked.  Dynamic rule (``threshold > 0``): every masked position
    over the threshold where those are at least that many.  A rank is a
    count over the block's ``L`` positions (``[n, L, L]`` compares), never a
    sort, and never anything over the vocabulary."""
    width = conf.shape[1]
    conf = jnp.where(masked, conf, -jnp.inf)
    count = width // dsteps + (nstep < width % dsteps).astype(jnp.int32)
    count = jnp.minimum(count, masked.sum(axis=1, dtype=jnp.int32))
    idx = jnp.arange(width)
    ahead = (conf[:, None, :] > conf[:, :, None]) | (
        (conf[:, None, :] == conf[:, :, None])
        & (idx[None, None, :] < idx[None, :, None])
    )  # [n, i, j]: j goes before i
    rank = ahead.sum(axis=2, dtype=jnp.int32)
    static = masked & (rank < count[:, None])
    over = masked & (conf > threshold[:, None])
    dynamic = (threshold > 0.0) & (
        over.sum(axis=1, dtype=jnp.int32) >= count
    )
    return jnp.where(dynamic[:, None], over, static)


def _block_prefill_core(model, params, prompt, positions):
    """A block model's prefill: the prompt's WHOLE blocks under the block
    rule (through the flash kernels where ``prefill_flash``), their K/V
    final.  No logits are read and no token is sampled: the first generated
    block shares the prompt's tail and is denoised like every other."""
    _, cache, rows = prefill_step(
        model, params, prompt, positions, with_rows=True
    )
    return cache, _calls(rows)


# what a slot did in one step of a block tick (``kinds``; 0: parked): it
# denoised its block, it denoised and carried the commit of the block before,
# or it was live and not fed, its commit pending at a narrow step
BLOCK_DENOISED, BLOCK_CARRIED, BLOCK_WAITED = 1, 2, 3


def _block_decode_core(
    model, params, steps, blk, msk, fstep, start, ptail, nstep, live,
    budget, prev, pend, eos, temp, dsteps, thr, cache, rng, table=None,
):
    """``steps`` forwards of every slot's CURRENT block in ONE jitted
    ``lax.scan``: generation by diffusion over blocks (a model with
    ``block_len`` L > 0).  The carry holds, a slot, its block (``blk`` [n, L]
    ids, ``msk`` which are still masked, ``fstep`` the step at which each
    was filled, -1 for a prompt's tail), the block's ``start``, the prompt
    tail's length ``ptail`` (the first block only), the denoising steps the
    block has had (``nstep``), ``live`` / ``budget`` as the fused tick has
    them, and the completed block that still awaits its final K/V (``prev``
    [n, L] ids, ``pend`` [n] whether there is one); ``dsteps`` and ``thr``
    are the request's steps a block and confidence threshold.

    A step feeds each slot its block as it stands
    (:func:`~tpu_parallel.models.generate.block_step`) and every slot it
    feeds DENOISES (a live slot always has masked positions):
    :func:`sample_block` picks and weighs each position of the block,
    :func:`unmask_choice` fills the most confident; a filled position never
    changes.  The step that fills a block's last position EMITS the block
    (its generated positions in order, cut at the budget and after an EOS)
    and ends the slot there, or moves it on at once: the completed block
    becomes the pending one and the next block is all mask ids.  No forward
    is spent on a COMMIT alone: the pending block rides, clean, the next
    block's first denoising forward, which makes its K/V final.

    Rows are not free (the decode attention's scores grow with them: on the
    chip a step of 2L rows a slot costs 1.45 steps of L), so the steps of a
    tick alternate: the even ones are WIDE (2L rows a slot, the half before
    the block pads where nothing is pending) and carry the commits, the odd
    ones NARROW (the block's L rows).  A slot whose commit is pending at a
    narrow step WAITS that step (live, not fed, it fills nothing); waiting
    moves it onto the wide steps, where a request of an EVEN number of steps
    a block then stays: one wait a request.  A request of an odd number (or
    under the threshold rule, whose blocks end where they end) waits once a
    block, so it gets the forwards it got when a commit had one of its own,
    in a tick whose steps cost more: the gain needs even steps a block.  So
    a step emits 0 or up to L tokens a slot, and every block that a later one
    reads gets exactly one clean forward.

    Returns ``(blocks [steps, n, L] (-1: nothing), counts [steps, n],
    fsteps [steps, n, L], kinds [steps, n] (0 parked, else ``BLOCK_DENOISED``
    / ``BLOCK_CARRIED`` / ``BLOCK_WAITED``), fills [steps, n], state, cache,
    rows)``."""
    cfg = model.config
    width, mask_id = cfg.block_len, cfg.mask_token_id
    offs = jnp.arange(width, dtype=jnp.int32)[None, :]

    def step(carry, step_rng, wide):
        blk, msk, fstep, start, ptail, nstep, live, budget = carry[:8]
        prev, pend, cache = carry[8:]
        fed = live if wide else live & ~pend
        hidden, cache, rows = block_step(
            model, params, cache, prev if wide else None, blk, start, fed,
            pend, block_table=table, with_rows=True,
        )
        logits = _full_logits(cfg, params, hidden[:, -width:])
        with jax.named_scope("diffusion.unmask"):
            x0, conf = sample_block(logits, step_rng, temp, rows=fed)
            broken = fed & ~jnp.where(
                msk, jnp.isfinite(conf), True
            ).all(axis=1)
            fill = unmask_choice(conf, msk, nstep, dsteps, thr)
            fill = fill & fed[:, None]
        blk = jnp.where(fill, x0, blk)
        msk = msk & ~fill
        fstep = jnp.where(fill, nstep[:, None], fstep)
        nstep = nstep + fed.astype(nstep.dtype)
        complete = fed & ~msk.any(axis=1) & ~broken
        # the block's generated positions, in order, up to the budget and
        # to its first EOS (delivered with it)
        room = jnp.minimum(width - ptail, budget)
        inside = (offs >= ptail[:, None]) & (offs < (ptail + room)[:, None])
        is_eos = inside & (blk == eos[:, None])
        ended = is_eos.any(axis=1)
        cut = jnp.where(
            ended, jnp.argmax(is_eos, axis=1).astype(jnp.int32) + 1,
            ptail + room,
        )
        emitted = jnp.where(
            complete[:, None] & inside & (offs < cut[:, None]), blk, -1
        )
        # non-finite logits: the sentinel alone, and the slot stops
        emitted = jnp.where(
            broken[:, None], jnp.where(offs == 0, NON_FINITE_TOKEN, -1),
            emitted,
        )
        count = jnp.where(complete, cut - ptail, 0) + broken.astype(jnp.int32)
        budget = budget - jnp.where(complete, cut - ptail, 0)
        done = (complete & (ended | (budget <= 0))) | broken
        kind = jnp.where(
            fed, jnp.where(pend, BLOCK_CARRIED, BLOCK_DENOISED),
            jnp.where(live, BLOCK_WAITED, 0),
        )
        out = (
            emitted, count, jnp.where(emitted >= 0, fstep, -1),
            kind, fill.sum(axis=1, dtype=jnp.int32), rows,
        )
        live = live & ~done
        # a completed block whose request goes on awaits its final K/V, which
        # the next wide forward writes; the next block starts at once
        move = complete & ~done
        prev = jnp.where(move[:, None], blk, prev)
        pend = jnp.where(fed, move, pend)
        blk = jnp.where(move[:, None], mask_id, blk)
        msk = msk | move[:, None]
        fstep = jnp.where(move[:, None], -1, fstep)
        start = start + jnp.where(move, width, 0)
        ptail = jnp.where(move, 0, ptail)
        nstep = jnp.where(move, 0, nstep)
        carry = (
            blk, msk, fstep, start, ptail, nstep, live, budget, prev, pend,
            cache,
        )
        return carry, out

    def pair(carry, rngs):
        carry, wide = step(carry, rngs[0], True)
        carry, narrow = step(carry, rngs[1], False)
        return carry, jax.tree.map(lambda a, b: jnp.stack([a, b]), wide, narrow)

    carry = (blk, msk, fstep, start, ptail, nstep, live, budget, prev, pend, cache)
    rngs = jax.random.split(rng, steps)
    pairs, outs = steps // 2, []
    if pairs:
        carry, both = lax.scan(
            pair, carry, rngs[: 2 * pairs].reshape(pairs, 2, *rngs.shape[1:])
        )
        outs.append(jax.tree.map(
            lambda x: x.reshape(2 * pairs, *x.shape[2:]), both
        ))
    if steps % 2:
        carry, last = step(carry, rngs[-1], True)
        outs.append(jax.tree.map(lambda x: x[None], last))
    blocks, counts, fsteps, kinds, fills, rows = jax.tree.map(
        lambda *parts: jnp.concatenate(parts), *outs
    )
    return blocks, counts, fsteps, kinds, fills, carry[:-1], carry[-1], rows


@functools.lru_cache(maxsize=16)
def _block_engine_fns(model, steps: int):
    """A block model's two jitted programs ``(prefill, tick)``, cached per
    (model, steps): the prefill of a prompt's whole blocks (no logits) and
    the tick of ``steps`` block forwards (:func:`_block_decode_core`).  As
    in :func:`_fused_engine_fn` the slot state (argnum 1) and the cache pool
    (argnum 3) of the tick are donated and the block table is the last
    operand (None: this family runs on the fixed-slot pool)."""
    prefill = jax.jit(
        lambda params, prompt, positions: _block_prefill_core(
            model, params, prompt, positions
        )
    )
    tick = jax.jit(
        lambda params, state, knobs, cache, rng, table=None: (
            _block_decode_core(
                model, params, steps, *state, *knobs, cache, rng, table
            )
        ),
        donate_argnums=(1, 3),
    )
    return prefill, tick


@functools.partial(jax.jit, donate_argnums=0)
def _seat_block_rows(
    state, knobs, slots, blk, ptail, start, budget, eos, temp, dsteps, thr
):
    """Write admitted requests' rows into a block model's device-resident
    slot state (:func:`_seat_rows`' twin): ``slots`` [nb] (a dummy row
    carries ``n_slots`` and is dropped), ``blk`` [nb, L] the first block
    (the prompt's tail, then mask ids), ``ptail`` the tail's length,
    ``start`` the block's first position.  Every seated row goes live, with
    no block pending: the columns before its block are the prefill's."""

    def put(rows, values):
        return rows.at[slots].set(values.astype(rows.dtype), mode="drop")

    width = blk.shape[1]
    masked = jnp.arange(width)[None, :] >= ptail[:, None]
    one = jnp.ones_like(ptail)
    values = (
        blk, masked, jnp.full_like(blk, -1), start, ptail, 0 * one,
        one.astype(bool), budget, jnp.zeros_like(blk), one < 0,
    )
    state = tuple(put(rows, v) for rows, v in zip(state, values))
    knobs = tuple(
        put(rows, v) for rows, v in zip(knobs, (eos, temp, dsteps, thr))
    )
    return state, knobs


@jax.jit
def _block_state_alive(state, alive):
    """A block model's slot state with the slots the host removed (a
    cancel, an integrity trip) dead: nothing is in flight when this runs,
    so the device's state is whole and only lacks the removal."""
    return state[:6] + (state[6] & alive,) + state[7:]


@functools.lru_cache(maxsize=16)
def _engine_fns(model):
    """Jitted per-step engine programs ``(prefill, extend, decode, verify,
    sample)``, cached per model so every engine instance (tests build
    many) shares traces.  A paged engine's model carries
    ``kv_block_tokens``, so each pool has its own entry.

    The cache-pool operand is DONATED in the extend, the decode step and
    the verify: the old tree is dead the moment the call returns, and
    without donation XLA holds a second full pool (the engine's dominant
    HBM) at every tick.  The block table is every program's LAST operand:
    None on the fixed-slot pool (an empty pytree: no parameter of the
    compiled program), the per-slot table on the paged pool, never
    donated — it is a small upload of the host-authoritative mirror, and
    donation would only buy an ownership hazard."""
    prefill = jax.jit(
        lambda params, prompt, positions, last_idx, rng: _prefill_core(
            model, params, prompt, positions, last_idx, rng
        )
    )
    extend = jax.jit(
        lambda params, tokens, positions, last_idx, wstart, cache, rng, \
            table=None: _extend_core(
                model, params, tokens, positions, last_idx, wstart, cache,
                rng, table,
            ),
        donate_argnums=5,
    )
    decode = jax.jit(
        lambda params, tok, pos, widx, temp, tk, tp, cache, rng, \
            table=None: _decode_core(
                model, params, tok, pos, widx, temp, tk, tp, cache, rng,
                table,
            ),
        donate_argnums=7,
    )
    verify = jax.jit(
        lambda params, tok, drafts, dlen, pos, widx, temp, tk, tp, cache, \
            rng, table=None: _verify_core(
                model, params, tok, drafts, dlen, pos, widx, temp, tk, tp,
                cache, rng, table,
            ),
        donate_argnums=9,
    )
    return prefill, extend, decode, verify, jax.jit(sample_tokens)


@functools.lru_cache(maxsize=16)
def _fused_engine_fn(model, steps: int):
    """The jitted fused decode tick at compiled width ``steps``, cached
    per (model, steps) so engines sharing a model share the trace.  The
    slot-state tuple (argnum 1) and the cache pool (argnum 3) are both
    DONATED: the engine re-donates the state arrays the previous tick
    returned, so steady-state decode recycles every buffer in place; the
    knob tuple (eos/temperature/top_k/top_p, argnum 2) is NOT donated —
    it only changes on admission, when the host re-uploads anyway.  The
    block table (last, None on the fixed-slot pool) is not donated
    either: it is loop-invariant through the scan and the host re-uploads
    it only when the allocator moved a mapping, so steady-state decode
    re-dispatches the same device table and the compile count stays
    pinned per (model, steps)."""
    return jax.jit(
        lambda params, state, knobs, cache, rng, table=None: (
            _fused_decode_core(
                model, params, steps, *state, *knobs, cache, rng, table
            )
        ),
        donate_argnums=(1, 3),
    )


@functools.lru_cache(maxsize=16)
def _unified_engine_fn(model, steps: int, chunk: int):
    """The jitted UNIFIED ragged tick (chunk phase + decode scan) at
    compiled widths ``(steps, chunk)`` — exactly ONE program per engine
    configuration, so the compile-shape family stays O(#buckets + 1):
    the bucketed prefill/extend shapes plus this.  Donation contract
    matches :func:`_fused_engine_fn` (slot state + cache donated; knobs,
    the per-tick chunk operands and the trailing block table are never
    donated), and the state tuples are structurally identical, so
    pure-decode ticks chain the SAME donated carry through ``_fused_fn``
    without a re-upload."""
    return jax.jit(
        lambda params, state, knobs, chunk_ops, cache, rng, table=None: (
            _unified_tick_core(
                model, params, steps, *state, *knobs, *chunk_ops, cache,
                rng, table,
            )
        ),
        donate_argnums=(1, 4),
    )


@functools.lru_cache(maxsize=16)
def _fused_spec_engine_fn(
    model, steps: int, chunk: int, k: int, max_ngram: int, min_ngram: int,
    adaptive: bool,
):
    """The jitted FUSED speculative tick: ``steps`` draft-verify-accept
    blocks per dispatch (``chunk`` > 0 additionally folds the ragged
    chunk phase in front — the unified spec tick).  The spec slot state
    (the fused 5-tuple + per-slot draft length + the token-history
    carry) and the cache are donated; knobs, chunk operands and the
    trailing block table are not."""
    if chunk > 0:
        return jax.jit(
            lambda params, state, knobs, chunk_ops, cache, rng, table=None: (
                _unified_spec_core(
                    model, params, steps, k, max_ngram, min_ngram,
                    adaptive, *state, *knobs, *chunk_ops, cache, rng,
                    table,
                )
            ),
            donate_argnums=(1, 4),
        )
    return jax.jit(
        lambda params, state, knobs, cache, rng, table=None: _fused_spec_core(
            model, params, steps, k, max_ngram, min_ngram, adaptive,
            *state, *knobs, cache, rng, table,
        ),
        donate_argnums=(1, 3),
    )


@jax.jit
def _own_arrays(tree):
    """ONE dispatch that turns a host-array upload into XLA-OWNED
    buffers — THE ownership laundering every donated upload must pass
    through.  ``jnp.asarray`` of a numpy array can be a zero-copy VIEW
    of host memory on CPU, and the fused/unified ticks DONATE the
    slot-state tree — donating a borrowed buffer lets XLA recycle
    memory it does not own, so the returned state would alias freed
    numpy storage and later host allocations scribble over the live
    slot state (observed as flaky mid-run corruption under heap
    churn).  Routing every array through an actual computation defeats
    jax's input->output forwarding, so the results are always
    device-allocated; one jitted call per tree structure keeps the
    upload at one dispatch instead of one per leaf."""

    def own(x):
        if x.dtype == jnp.bool_:
            return jnp.logical_and(x, True)
        return x + jnp.zeros((), x.dtype)

    return jax.tree_util.tree_map(own, tree)


@functools.partial(jax.jit, donate_argnums=0)
def _seat_rows(
    state, knobs, slots, first, plen, budget, eos, temp, topk, topp, act
):
    """Write admitted requests' rows into the device-resident slot state,
    as the unified tick's final chunk does in-device: ``slots`` [nb] (a
    dummy row carries ``n_slots`` and is dropped), ``first`` the sampled
    first tokens still on the device, ``plen`` / ``budget`` / ``eos`` and
    the sampling knobs per row.  ``act`` rows go live unless the first
    token already ends them (EOS, a budget of one, the non-finite
    sentinel); the others (a chunked prompt's start) get their knobs and
    stay dead until the tick's chunk phase activates them.  The state is
    donated and chains between two ticks; the knobs are small and are
    not."""
    left = budget - 1
    done = (first == eos) | (left <= 0) | (first == NON_FINITE_TOKEN)

    def put(rows, values):
        return rows.at[slots].set(values.astype(rows.dtype), mode="drop")

    tok, pos, widx, live, bud = state
    state = (
        put(tok, first), put(pos, plen), put(widx, plen),
        put(live, act & ~done), put(bud, left),
    )
    knobs = tuple(
        put(rows, values)
        for rows, values in zip(knobs, (eos, temp, topk, topp))
    )
    return state, knobs


def default_prefill_buckets(seq_len: int, start: int = 32) -> Tuple[int, ...]:
    """Geometric bucket set ``(32, 64, ..., seq_len)`` — prompt lengths
    collapse onto O(log seq_len) compile shapes.  ``seq_len`` is always
    the last bucket so every admissible prompt fits one."""
    buckets, b = [], min(start, seq_len)
    while b < seq_len:
        buckets.append(b)
        b *= 2
    buckets.append(seq_len)
    return tuple(buckets)


class _ChunkState:
    """An in-flight chunked prefill: the request owns its slot, the prompt
    extends one chunk per tick, activation happens on the final chunk."""

    __slots__ = ("out", "offset")

    def __init__(self, out: RequestOutput, offset: int):
        self.out = out
        self.offset = offset


class _WithoutExpertRows:
    """A jitted engine program minus its last output, the dropless expert
    layers' row counts (None for a model without one): those go, unread,
    onto ``sink`` for the tick's collect, and no call site changes.
    Everything else (``_cache_size``, ``lower``) is the program's own."""

    def __init__(self, fn, sink: list):
        self._fn, self._sink = fn, sink

    def __call__(self, *args):
        *out, rows = self._fn(*args)
        if rows is not None:
            self._sink.append(rows)
        return tuple(out)

    def __getattr__(self, name):
        return getattr(self._fn, name)


class _PendingTick:
    """One engine tick in flight between :meth:`ServingEngine.launch` and
    :meth:`ServingEngine.collect`: the dispatch's UNSYNCED device result
    handles (``payload``) plus the host bookkeeping collect needs.  The
    donation-ownership contract rides here — every buffer the launch's
    dispatch returned (slot state, cache pool) is device-owned and must
    not be read until this tick's collect."""

    __slots__ = (
        "kind", "start", "t0", "t1", "tick_span", "events", "admitted",
        "chunks_advanced", "chunk_tokens", "chunk_spans", "active_tokens",
        "entering", "finals", "payload", "overlapped", "phases", "between",
        "expert_rows", "firsts", "owners", "tiles", "latent_rows",
    )

    def __init__(self):
        self.kind = "idle"
        self.start = 0.0  # launch entry: the first phase's start
        # the decode dispatch's window on the engine's clock, from the
        # phase reads: enqueued at t0, synced at t1
        self.t0 = 0.0
        self.t1 = 0.0
        self.tick_span = None
        self.events: List[StreamEvent] = []
        self.admitted: List[RequestOutput] = []
        self.chunks_advanced = 0
        self.chunk_tokens = 0
        self.chunk_spans: List[tuple] = []
        self.active_tokens = 0
        self.entering: Tuple[int, ...] = ()
        self.finals: List[tuple] = []
        self.payload = None
        # launched while its predecessor was still uncollected
        self.overlapped = False
        # admissions seated on the device by this launch: (first tokens
        # [nb], still on the device until collect, [(row, slot, out)]);
        # and who held each slot when the tick was dispatched: a later
        # tenant of a slot never receives this tick's tokens
        self.firsts: List[tuple] = []
        self.owners: List[Optional[RequestOutput]] = []
        # expert row counts this tick's programs returned (device arrays
        # until collect reads them)
        self.expert_rows: list = []
        # the phase clock: seconds by leaf phase (a phase entered twice
        # in one tick adds up), and the gap since the previous busy
        # tick's collect (None: there was none, or it was not busy)
        self.phases: Dict[str, float] = {}
        self.between: Optional[float] = None
        # (walked, held) stripe tiles of the decode kernel over the slots
        # this tick entered with (None: no program uses the kernel)
        self.tiles: Optional[Tuple[int, int]] = None
        # stored rows the tick's decode steps read, over the latent layers
        # (None: the model has none)
        self.latent_rows: Optional[int] = None

    def add_phase(self, name: str, seconds: float) -> None:
        self.phases[name] = self.phases.get(name, 0.0) + seconds


class ServingEngine:
    """In-process continuous-batching engine over one model + params.

    ``step()`` runs one scheduling + decode tick and returns the tick's
    :class:`StreamEvent`s (incremental delivery); ``run()`` loops until
    idle.  ``add_request`` is non-blocking: the returned
    :class:`RequestOutput` fills in as ticks run.

    ``n_slots`` fixes the pool (HBM = ``n_slots x seq_len`` K/V per layer
    — ``kv_cache_dtype="int8"`` halves it); ``scheduler`` takes a
    :class:`SchedulerConfig` (or a ready scheduler) for admission policy;
    ``clock`` is injectable for deterministic timeout tests.

    Prefill fast-path knobs (see the module docstring; all exact):

    - ``prefill_buckets``: ``"auto"`` (default — geometric 32..seq_len),
      an explicit ascending tuple, or None/() for the legacy batch-1
      exact-length prefill (compiles per distinct prompt length).
    - ``prefill_batch``: row count every batched prefill call pads to
      (default: the scheduler's ``max_prefills_per_tick``), so batch size
      never adds compile shapes.  Dummy rows scatter out of range and
      vanish; a tick's same-bucket admissions beyond it run as further
      calls of the same shape.
    - ``prefill_chunk_tokens``: prompts longer than this split into
      chunks interleaving with decode ticks (None = monolithic prefill).
    - ``prefix_cache_size``: LRU entries of bucket-aligned prefix K/V
      rows (0 = off; each entry is a full seq_len row of HBM).  Requires
      bucketing.  Under ``kv_radix_cache`` the same knob bounds the
      radix tree's resident DEVICE BLOCKS instead of whole entries.
    - ``kv_radix_cache`` (paged only): replace the aligned-LRU prefix
      cache with the token-level radix hierarchy
      (:class:`~tpu_parallel.serving.kv_hierarchy.RadixPrefixCache`) —
      ANY shared prefix hits at block granularity with frequency-aware
      eviction, and hits are always block-aligned so the copy-on-write
      admission reserve drops to zero.
    - ``kv_host_blocks`` (implies ``kv_radix_cache``): host-RAM offload
      tier capacity in blocks — evicted-but-warm prefix blocks spill to
      host arrays via one batched ``device_get`` and restore via one
      batched ``device_put`` instead of a re-prefill.

    Fused decode tick (exact — greedy output bitwise identical to the
    per-step engine, pinned in ``tests/test_serving.py``):

    - ``decode_steps_per_tick``: T > 1 runs T masked decode steps in ONE
      jitted ``lax.scan`` with the KV cache and per-slot state buffers
      donated — one host dispatch + one device sync per T tokens instead
      of per token (the per-step tick's dominant cost at small batch).
      Slot state (current token, cache position, write index, live mask,
      remaining budget) lives in device arrays between ticks; admissions
      write their rows into it, and the host re-uploads only after a
      cancel (paged and speculative engines: after every admission and
      release).  Slots finishing
      mid-scan (EOS, budget) park their writes at column ``seq_len`` for
      the remaining steps.  Streaming granularity becomes per-tick
      (bounded by T).  ``"auto"`` (default) = 8; spec engines
      (``draft_tokens > 0``) resolve to 1 under "auto" but an EXPLICIT
      T > 1 fuses T draft-verify blocks per dispatch (below).  1 = the
      per-step engine.
    - ``unified_tick``: the UNIFIED RAGGED tick — prefill-chunk slots
      consume their next prompt chunk (fixed ``[n_slots, chunk_tokens]``
      input block, right-padded, pad positions -1) while decode slots
      run their T masked scan steps, in ONE jitted dispatch per engine
      tick, with final-chunk activation (first-token sampling) done
      in-device.  A tick that used to pay one extend dispatch PER chunk
      slot plus the decode dispatch pays exactly one, so prefill chunks
      stop stalling in-flight decodes (Sarathi-Serve's stall-free
      coalesced batching over this engine's bucket quantum).  ``"auto"``
      (default) = on whenever the fused tick is; ``False`` keeps the
      per-phase advance-then-decode tick (the parity baseline — greedy
      output is bitwise identical either way, pinned in tests).  With
      ``draft_tokens > 0`` and an explicit T > 1 the same treatment
      fuses SPECULATIVE ticks: T draft-verify-accept blocks per
      dispatch, drafting in-scan via the traceable NGram twin
      (:func:`~tpu_parallel.serving.spec_decode.ngram_draft_tokens`) —
      custom drafters refuse (their host state is invisible mid-scan).

    One tick queued on the device (:meth:`step`, or the :meth:`launch` /
    :meth:`collect` halves directly): tick N's device->host sync and
    delivery, and the caller's work between two steps, overlap tick
    N+1's device compute — :meth:`launch` dispatches without syncing,
    admissions and whole-prompt prefills included, and :meth:`collect`
    syncs and delivers, one tick deep on the fused and unified ticks
    over the fixed-slot pool (their slot state stays on the device as
    the authority; the other engines' launches read host mirrors and
    keep ``collect(launch())``).  The donation-ownership contract from
    the fused tick is the invariant: buffers a launch's dispatch
    returned belong to the device until that tick's collect
    (``scripts/check_host_sync.py`` gates what launch reaches against
    syncs).

    Speculative decode knobs (exact for every drafter — see the module
    docstring and ``docs/10_serving_engine.md``):

    - ``draft_tokens``: max drafts per slot per tick; the verify program
      compiles ONCE at width ``draft_tokens + 1`` (0 = off, the plain
      single-token tick).  Per-request override: ``Request.draft_tokens``.
    - ``drafter``: a :class:`~tpu_parallel.serving.spec_decode.Drafter`
      (default: model-free prompt-lookup
      :class:`~tpu_parallel.serving.spec_decode.NGramDrafter`).
    - ``spec_adaptive``: acceptance-adaptive per-slot draft lengths
      (grow after full acceptance, shrink to the cut otherwise).
    - ``spec_check_invariants``: assert the aligned-layout no-rollback
      invariant (:meth:`CachePool.assert_slot_aligned`) every verify
      tick — debug aid, one device fetch per slot per tick.

    Models with recurrent (state-space) layers (``LayerSpec.mixer ==
    "ssm"``, ``models/ssm.py``; docs/10_serving_engine.md): a slot holds a
    float32 state of one size beside its K/V stripes, in the same
    fixed-slot pool; whole-prompt, bucketed and chunked prefill and the
    per-step, fused and unified ticks all run them (parked rows ride as
    pads: position -1).  What cuts, shares or rolls a cache back by
    position is refused at construction: ``prefix_cache_size > 0``,
    ``kv_radix_cache``, ``kv_block_tokens``, the host and disk tiers,
    ``draft_tokens > 0``.  ``ssm_plan`` says what a slot holds.

    Telemetry (docs/11_observability.md):

    - ``tracer``: a :class:`~tpu_parallel.obs.tracer.Tracer` records each
      request's lifecycle as spans (``queue -> prefill[chunk i] ->
      decode/verify -> finish``) on one track per slot plus a scheduler
      track — export with
      :func:`~tpu_parallel.obs.exporters.write_chrome_trace` and open in
      Perfetto.  Default is the no-op ``NULL_TRACER`` (near-zero cost:
      no timestamps, no allocation).
    - ``registry``: the :class:`~tpu_parallel.obs.registry.MetricRegistry`
      backing every counter/gauge/histogram (``ServingMetrics`` owns one
      by default; pass a shared registry to co-locate serving + trainer
      series for one Prometheus export).  Per-tick the engine
      publishes queue depth, occupancy, and a stall-cause counter
      (``queue_empty`` / ``prefill`` / ``spec_verify`` / ``none``); the
      scheduler adds the queue-age gauge.
    - the phase clock, always on: every tick is cut into leaf phases
      (``schedule`` / ``prefill`` / ``dispatch`` / ``device_wait`` /
      ``deliver`` / ``record``, and ``between`` two busy ticks), each
      written by :class:`~tpu_parallel.obs.phases.phase` to
      ``serving_tick_phase_seconds``, to a ``tick.<phase>`` span when
      tracing, and to an ``engine.tick.<phase>`` profiler annotation —
      on ``clock``, which a tracer should share.
    - the completion clock, always on: every device program is launched
      through :meth:`_run`, and one thread an engine
      (:class:`~tpu_parallel.obs.device_clock.DeviceClock`, started at
      a dispatch, ended when the engine drains) stamps each as it
      completes:
      ``serving_device_seconds{program, shape}`` (``tick`` /
      ``tick_chunk`` / ``prefill`` / ``extend``),
      ``serving_device_idle_seconds_total``, a ``device.<program>`` span
      on the ``device`` track when tracing, a ``device.run.<program>``
      profiler annotation; ``summary()["device_*"]``.
    """

    def __init__(
        self,
        model,
        params,
        n_slots: int = 8,
        scheduler: Union[SchedulerConfig, FIFOScheduler, None] = None,
        rng: Optional[jax.Array] = None,
        metrics: Optional[ServingMetrics] = None,
        tracer: Optional[Tracer] = None,
        registry: Optional[MetricRegistry] = None,
        clock: Callable[[], float] = time.monotonic,
        prefill_buckets: Union[str, Sequence[int], None] = "auto",
        prefill_batch: Optional[int] = None,
        prefill_chunk_tokens: Optional[int] = None,
        prefix_cache_size: int = 0,
        kv_block_tokens: Union[int, str, None] = None,
        kv_pool_blocks: Optional[int] = None,
        kv_radix_cache: bool = False,
        kv_host_blocks: int = 0,
        kv_disk_dir: Optional[str] = None,
        kv_disk_blocks: int = 0,
        decode_steps_per_tick: Union[int, str] = "auto",
        unified_tick: Union[str, bool] = "auto",
        draft_tokens: int = 0,
        drafter: Optional[Drafter] = None,
        spec_adaptive: bool = True,
        spec_check_invariants: bool = False,
    ):
        cfg = model.config
        if getattr(cfg, "pipe_size", 1) > 1:
            raise NotImplementedError(
                "the serving engine does not run pipeline meshes — serve "
                "pipe-split models through generate_sharded"
            )
        if cfg.positional == "relative":
            # the shared T5 bias table assumes row-uniform positions; a
            # slot pool's rows sit at different depths, so the engine's
            # decode (and the fast path's padded prefill rows) would get
            # row-0 bias — PR 1 accepted these configs and was silently
            # wrong; the model now refuses write_index + relative, and the
            # engine refuses up front with the pointer
            raise NotImplementedError(
                "the serving engine does not run positional='relative' "
                "models (per-row slot depths break the shared bias "
                "table) — serve those through generate()"
            )
        if cfg.drops_tokens:
            raise NotImplementedError(
                "the serving engine does not run capacity-routed experts: a "
                "dropped token makes a request's output depend on its "
                "batch-mates — give the layers an ExpertsSpec (the dropless "
                "RoutedExperts layer)"
            )
        if cfg.recurrent_layers:
            # a recurrent state is a summary of the row's whole past: it
            # cannot be cut at a position, shared by position or rolled back
            refused = {
                "prefix_cache_size > 0": (
                    prefix_cache_size > 0,
                    "a stored prefix is K/V rows trimmed by position "
                    "(serving/prefix_cache.py); reuse needs a snapshot of the "
                    "state AT the prefix boundary",
                ),
                "kv_radix_cache": (
                    bool(kv_radix_cache),
                    "the radix tree (serving/kv_hierarchy.py) shares blocks of "
                    "positions; a state has none",
                ),
                "kv_block_tokens": (
                    kv_block_tokens not in (None, 0),
                    "the block-paged pool (serving/cache_pool.py) pages "
                    "positions; a state of one size a slot has no blocks",
                ),
                "kv_host_blocks / kv_disk_dir": (
                    kv_host_blocks > 0 or kv_disk_dir is not None,
                    "the host and disk tiers spill the paged pool's blocks",
                ),
                "draft_tokens > 0": (
                    draft_tokens > 0,
                    "a rejected draft is left behind a position mask in the "
                    "K/V stripe; a state that has absorbed it cannot be "
                    "rolled back by the verify tick",
                ),
            }
            for option, (asked, why) in refused.items():
                if asked:
                    raise NotImplementedError(
                        f"the serving engine does not run a model with "
                        f"recurrent (state-space) layers under {option}: "
                        f"{why} - serve it on the fixed-slot pool with "
                        "bucketed or chunked prefill"
                    )
        from tpu_parallel.models.layers import depth_specs

        self._latent = [
            s.latent for s in depth_specs(cfg)
            if s.mixer == "attention" and s.attn == "latent"
        ]
        if self._latent:
            # a latent layer stores one row a position that all heads share
            # and reads it through the absorbed form: what extends, shares,
            # pages, exports or verifies K/V rows is not written for it
            refused = {
                "prefill_chunk_tokens": (
                    prefill_chunk_tokens is not None,
                    "chunk extension (and the unified tick's chunk phase) "
                    "attends a chunk's queries against up-projected stored "
                    "latents, which models/generate.py::prefill_extend_step "
                    "and ops/flash_attention.py::flash_chunk_attention do "
                    "not do",
                ),
                "prefix_cache_size > 0": (
                    prefix_cache_size > 0,
                    "a prefix hit lands stored rows and EXTENDS them with "
                    "the rest of the prompt (serving/prefix_cache.py): chunk "
                    "extension over a latent cache",
                ),
                "kv_block_tokens": (
                    kv_block_tokens not in (None, 0),
                    "the block-paged pool (serving/cache_pool.py) gathers "
                    "K/V heads through a block table; the latent leaf has "
                    "no paged layout",
                ),
                "kv_radix_cache / kv_host_blocks / kv_disk_dir": (
                    bool(kv_radix_cache) or kv_host_blocks > 0
                    or kv_disk_dir is not None,
                    "the radix tree and the host and disk tiers live on the "
                    "paged pool (and with them K/V export and import, "
                    "serving/kv_wire.py)",
                ),
                "draft_tokens > 0": (
                    draft_tokens > 0,
                    "the verify tick scores draft_tokens + 1 rows a slot "
                    "(serving/spec_decode.py); the absorbed form and its "
                    "row count are written for a step of one (multi-token "
                    "prediction needs the same tick)",
                ),
            }
            for option, (asked, why) in refused.items():
                if asked:
                    raise NotImplementedError(
                        f"the serving engine does not run a model with "
                        f"latent attention layers under {option}: {why} - "
                        "serve it on the fixed-slot pool with whole-prompt "
                        "bucketed prefill"
                    )
        self._block_len = int(cfg.block_len)
        if self._block_len:
            # generation by diffusion over blocks: a step feeds a slot's
            # whole block and rewrites its keys until the block is final
            if cfg.mask_token_id is None or cfg.seq_len % self._block_len:
                raise ValueError(
                    f"a block model (block_len={self._block_len}) states its "
                    f"mask_token_id (got {cfg.mask_token_id}) and a seq_len "
                    f"of whole blocks (got {cfg.seq_len})"
                )
            refused = {
                "draft_tokens > 0": (
                    draft_tokens > 0,
                    "the verify tick keeps a rejected draft's column "
                    "invisible by kp <= qp, and a block's query sees past "
                    "itself to its block's end; a draft has no confidence "
                    "to be chosen by either",
                ),
                "prefill_chunk_tokens": (
                    prefill_chunk_tokens is not None,
                    "the unified tick's chunk phase samples a first token "
                    "from the chunk's last position, and a chunk that ends "
                    "inside a block would leave half a block's keys final",
                ),
                "prefix_cache_size > 0": (
                    prefix_cache_size > 0,
                    "a stored prefix is cut at a bucket, not at a block "
                    "boundary that the hit's first block could start from",
                ),
                "kv_block_tokens": (
                    kv_block_tokens not in (None, 0),
                    "the block step rewrites L columns a forward through "
                    "write_index; the paged pool's copy-on-write window is "
                    "sized for one column a step",
                ),
                "kv_radix_cache / kv_host_blocks / kv_disk_dir": (
                    bool(kv_radix_cache) or kv_host_blocks > 0
                    or kv_disk_dir is not None,
                    "the radix tree and the host and disk tiers live on the "
                    "paged pool (and with them K/V export and import)",
                ),
            }
            for option, (asked, why) in refused.items():
                if asked:
                    raise NotImplementedError(
                        f"the serving engine does not run a block-diffusion "
                        f"model (block_len={self._block_len}) under "
                        f"{option}: {why} - serve it on the fixed-slot pool "
                        "with whole-prompt bucketed prefill"
                    )
        self.model = model
        self.params = params
        # the served weight set's identity — rebind_params() updates it;
        # the cluster's rolling hot-swap reports it per replica
        self.weights_version = "initial"
        self.clock = clock
        # telemetry: the tracer records lifecycle spans (one track per
        # slot + a scheduler track; NULL_TRACER = disabled, near-zero
        # cost), the registry backs every counter/gauge/histogram.
        # Metrics own the registry so `registry` is only consulted when
        # metrics are engine-built; the scheduler publishes its queue-age
        # gauge into the same store.
        self.tracer = tracer if tracer is not None else NULL_TRACER
        if metrics is not None:
            self.metrics = metrics
        else:
            self.metrics = ServingMetrics(registry=registry)
        self.registry = self.metrics.registry
        # NaN/Inf sentinel trips (typed per-request integrity failures);
        # the cluster's ReplicaHandle watches this for DEGRADED health
        self.integrity_trips = 0
        if isinstance(scheduler, FIFOScheduler):
            self.scheduler = scheduler
            if self.scheduler.registry is None:
                self.scheduler.registry = self.registry
        else:
            self.scheduler = FIFOScheduler(
                scheduler, clock=clock, registry=self.registry
            )
        self._queue_spans: Dict[str, object] = {}
        # the one-tick pipeline: the tick step() left on the device, and
        # why the next launch may not go ahead of a collect (a host-side
        # change the device state has to be rebuilt for)
        self._pending: Optional[_PendingTick] = None
        self._flush_cause: Optional[str] = None
        # the phase clock's memory across ticks: the newest launched
        # tick (a collect that finds another one in flight beside its
        # own ran beside device work), the end of the last collect (a
        # pipelined tick's period starts there) and of the last busy
        # tick's collect — what `between` is measured from
        self._newest_tick: Optional[_PendingTick] = None
        self._collect_end = float("-inf")
        self._busy_end: Optional[float] = None
        # the completion clock: what _run dispatched, stamped as it
        # completes; its thread starts with a dispatch and ends at a drain
        self._device_clock = DeviceClock(
            self.clock, self.tracer, self._device_ran, self._device_lost
        )
        self._rng = rng if rng is not None else jax.random.PRNGKey(0)

        if prefill_buckets == "auto":
            self._buckets: Optional[Tuple[int, ...]] = (
                default_prefill_buckets(cfg.seq_len)
            )
        elif prefill_buckets:
            bs = tuple(sorted(int(b) for b in prefill_buckets))
            if bs[0] < 1 or bs[-1] > cfg.seq_len:
                raise ValueError(
                    f"prefill_buckets={bs} outside [1, seq_len={cfg.seq_len}]"
                )
            if bs[-1] < cfg.seq_len:
                bs = bs + (cfg.seq_len,)  # every admissible prompt must fit
            self._buckets = bs
        else:
            self._buckets = None
        if self._block_len:
            if self._buckets is None or any(
                b % self._block_len for b in self._buckets
            ):
                raise ValueError(
                    f"a block model prefills its prompt's whole blocks: "
                    f"prefill_buckets={self._buckets} have to be multiples "
                    f"of block_len={self._block_len}"
                )
        # block-paged KV cache: kv_block_tokens > 0 (or "auto") swaps the
        # fixed n_slots x seq_len pool for a flat pool of kv_pool_blocks
        # blocks addressed through per-slot block tables — slot count
        # decouples from seq_len and prefix hits become O(1) refcounted
        # pointer writes.  None/0 keeps the fixed-slot layout.
        if kv_block_tokens in (None, 0):
            self._paged = False
            self._block_tokens = 0
        else:
            if kv_block_tokens == "auto":
                # the bucket quantum: the largest size dividing seq_len,
                # every prefill bucket, and 32 — so bucket-aligned prefix
                # keys (the router/prefix-cache alignment) always land on
                # block boundaries and shared blocks need no trimming
                bt = math.gcd(cfg.seq_len, 32)
                for b in self._buckets or ():
                    bt = math.gcd(bt, int(b))
            else:
                bt = int(kv_block_tokens)
                if bt < 1:
                    raise ValueError(f"kv_block_tokens={bt} < 1")
                if cfg.seq_len % bt != 0:
                    raise ValueError(
                        f"kv_block_tokens={bt} must divide "
                        f"seq_len={cfg.seq_len}"
                    )
            n_blocks = (
                int(kv_pool_blocks)
                if kv_pool_blocks is not None
                else n_slots * (cfg.seq_len // bt)
            )
            if n_blocks < 1:
                raise ValueError(f"kv_pool_blocks={n_blocks} < 1")
            self._paged = True
            self._block_tokens = bt
            # the engine's jitted fns and pool key off the PAGED model
            # variant (cache shapes are config-driven); params are
            # layout-agnostic and shared
            model = type(model)(
                dataclasses.replace(
                    cfg, kv_block_tokens=bt, kv_pool_blocks=n_blocks
                )
            )
            cfg = model.config
            self.model = model
        if prefill_chunk_tokens is not None and prefill_chunk_tokens < 1:
            raise ValueError(
                f"prefill_chunk_tokens={prefill_chunk_tokens} < 1"
            )
        self._chunk_tokens = prefill_chunk_tokens
        if prefix_cache_size > 0 and self._buckets is None:
            raise ValueError(
                "prefix_cache_size > 0 requires prefill bucketing (prefix "
                "keys are bucket-aligned)"
            )
        # radix hierarchy (kv_hierarchy.py): swaps the aligned-LRU cache
        # for the token-level radix tree + optional host offload tier on
        # the paged path; built AFTER the pool below (it holds pool
        # references), so only validate here
        if kv_host_blocks < 0:
            raise ValueError(f"kv_host_blocks={kv_host_blocks} < 0")
        # SSD tier (kv_disk.py) UNDER the host tier: both knobs travel
        # together, and the host tier must exist — disk spills are COLD
        # host evictions, so a diskful hierarchy without a host tier
        # would never populate it
        if kv_disk_blocks < 0:
            raise ValueError(f"kv_disk_blocks={kv_disk_blocks} < 0")
        if (kv_disk_dir is None) != (kv_disk_blocks == 0):
            raise ValueError(
                "kv_disk_dir and kv_disk_blocks > 0 travel together"
            )
        if kv_disk_dir is not None and kv_host_blocks < 1:
            raise ValueError(
                "kv_disk_dir needs kv_host_blocks > 0 (the disk tier "
                "sits UNDER the host offload tier)"
            )
        self._kv_disk_dir = kv_disk_dir
        self._kv_disk_blocks = int(kv_disk_blocks)
        self._radix_requested = bool(kv_radix_cache) or kv_host_blocks > 0
        self._kv_host_blocks = int(kv_host_blocks)
        self._radix: Optional[RadixPrefixCache] = None
        if self._radix_requested:
            if not self._paged:
                raise ValueError(
                    "kv_radix_cache / kv_host_blocks need the block-paged "
                    "pool (kv_block_tokens > 0) — the radix tree indexes "
                    "physical blocks"
                )
            if prefix_cache_size < 1:
                raise ValueError(
                    "kv_radix_cache needs prefix_cache_size > 0 (the "
                    "radix tree's resident device-block budget)"
                )
        self._prefix = (
            PrefixCache(
                prefix_cache_size,
                # paged entries hold refcounted block ids: eviction must
                # hand the references back to the allocator
                on_evict=(
                    self._release_prefix_entry if self._paged else None
                ),
            )
            if prefix_cache_size > 0
            else None
        )
        # copy-on-write admission headroom: with buckets NOT aligned to
        # the block size, stored prefixes end mid-block, so sharing puts
        # live write columns inside shared blocks and the sharers'
        # writes COW — each COW claims a fresh block the plain
        # ceil(total/bt) estimate cannot see (the original stays alive
        # under its other referents).  Reserve one block per non-aligned
        # bucket plus one for a mid-block hit tail — the upper bound on
        # one slot's COW events — so the block gate can never admit a
        # set whose COWs exhaust the pool mid-tick.  Zero under the
        # "auto" quantum (aligned buckets never COW).
        self._cow_reserve = 0
        if self._paged and self._prefix is not None:
            unaligned = sum(
                1 for b in self._buckets if b % self._block_tokens != 0
            )
            self._cow_reserve = (1 + unaligned) if unaligned else 0
        if self._radix_requested:
            # radix matches and stores are FULL blocks: every hit's
            # remainder starts on a block boundary, so prefix sharing can
            # never put a live write column inside a shared block and the
            # unaligned-bucket COW reserve is provably unnecessary
            self._cow_reserve = 0
        self._prefill_batch = (
            prefill_batch
            if prefill_batch is not None
            else self.scheduler.config.max_prefills_per_tick
        )
        if self._prefill_batch < 1:
            raise ValueError(f"prefill_batch={self._prefill_batch} < 1")
        self._chunking: Dict[int, _ChunkState] = {}
        self._prefill_shapes: set = set()

        # speculative decode: draft_tokens > 0 switches the decode tick to
        # draft-verify blocks of COMPILED width draft_tokens + 1 (per-slot
        # draft lengths vary underneath via -1-position padding; the
        # program shape never changes)
        if draft_tokens < 0:
            raise ValueError(f"draft_tokens={draft_tokens} < 0")
        self._spec_width = draft_tokens
        self._drafter: Drafter = (
            drafter if drafter is not None else NGramDrafter()
        )
        self._spec_adaptive = spec_adaptive
        self._spec_check = spec_check_invariants

        # fused multi-step decode tick: T > 1 runs T masked decode steps
        # in one jitted lax.scan with the cache AND the per-slot state
        # donated — one host dispatch + one sync per T tokens ("auto" =
        # 8 plain; speculative engines resolve to 1 under "auto" — an
        # EXPLICIT T > 1 with draft_tokens > 0 instead fuses T
        # draft-verify blocks per dispatch, drafting ON DEVICE via the
        # traceable NGram twin, so it refuses custom drafters whose
        # host state the scan cannot see)
        if decode_steps_per_tick == "auto":
            fused = 1 if draft_tokens > 0 else 8
        else:
            fused = int(decode_steps_per_tick)
            if fused < 1:
                raise ValueError(
                    f"decode_steps_per_tick={decode_steps_per_tick} < 1"
                )
            if fused > 1 and draft_tokens > 0 and (
                type(self._drafter) is not NGramDrafter
            ):
                raise NotImplementedError(
                    "decode_steps_per_tick > 1 with draft_tokens > 0 "
                    "fuses T draft-verify blocks in one scan, drafting "
                    "on device via the traceable NGram drafter — a "
                    "custom Drafter's host state is invisible to the "
                    "scan; keep decode_steps_per_tick=1 for it"
                )
        self._fused_steps = fused
        self._spec_fused = fused > 1 and draft_tokens > 0
        # the UNIFIED ragged tick: prefill-chunk slots and decode slots
        # advance in ONE dispatch per tick (phase mask + per-slot token
        # raggedness; in-device final-chunk activation).  "auto" turns
        # it on whenever the fused tick is ("False" keeps the per-phase
        # chunk-advance-then-decode tick — the parity baseline).
        if unified_tick not in ("auto", True, False):
            raise ValueError(f"unified_tick={unified_tick!r}")
        if unified_tick is True and fused < 2:
            raise ValueError(
                "unified_tick=True needs decode_steps_per_tick > 1 (the "
                "unified tick IS the fused tick with the ragged chunk "
                "phase folded in)"
            )
        self._unified = (
            fused > 1 if unified_tick == "auto" else bool(unified_tick)
        )
        chunkw = int(prefill_chunk_tokens or 0) if self._unified else 0
        # one family of programs for both pools, cached by model (the
        # paged model above carries kv_block_tokens): the block table is
        # their last operand, None on the fixed-slot pool
        (self._prefill_fn, self._extend_fn, self._decode_fn,
         self._verify_fn, self._sample_fn) = _engine_fns(model)
        self._fused_fn = None
        self._unified_fn = None
        self._block_fn = None
        self._block_prefill_fn = None
        self._spec_fused_fn = None
        self._spec_unified_fn = None
        if self._spec_fused:
            spec_sig = (
                draft_tokens, self._drafter.max_ngram,
                self._drafter.min_ngram, bool(spec_adaptive),
            )
            # two programs when chunking is configured: the pure-decode
            # fused verify scan and the unified (chunk-phase) variant;
            # their state tuples match, so the donated carry chains
            self._spec_fused_fn = _fused_spec_engine_fn(
                model, fused, 0, *spec_sig
            )
            if chunkw > 0:
                self._spec_unified_fn = _fused_spec_engine_fn(
                    model, fused, chunkw, *spec_sig
                )
        elif self._block_len:
            # ONE tick program whatever decode_steps_per_tick is (1 runs the
            # same scan at one step), and a prefill without a head
            self._block_prefill_fn, self._block_fn = _block_engine_fns(
                model, fused
            )
        elif fused > 1:
            self._fused_fn = _fused_engine_fn(model, fused)
            if chunkw > 0:
                self._unified_fn = _unified_engine_fn(model, fused, chunkw)
        # device-resident slot state (fused path): the previous tick's
        # returned arrays are re-donated, so steady-state decode never
        # re-uploads.  On the engines that chain ticks (fused or unified,
        # fixed-slot pool) it is the authority: admissions write their
        # own rows into it (_seat_rows), retirements are already dead in
        # its live mask, and the host mirrors follow at collect; only a
        # host-side removal (cancel, an integrity trip) rebuilds it from
        # the mirrors, once nothing is in flight.  The other fused
        # engines (paged, speculative) re-upload after every admission
        # and release, as they did.
        self._dev_state = None
        self._dev_knobs = None
        self._state_dirty = True
        self._chains = (
            fused > 1 or bool(self._block_len)
        ) and not self._spec_fused and not self._paged
        # device copy of the paged block-table mirror, re-uploaded only
        # when the allocator bumped table_version
        self._dev_table = None
        self._table_version = -1

        if self._paged:
            self._prefill_fn = None  # paged prefill IS the extend path
            self.pool: Union[CachePool, PagedCachePool] = PagedCachePool(
                model, params, n_slots
            )
            if self._radix_requested:
                # the radix hierarchy replaces the aligned-LRU cache: the
                # same self._prefix slot serves both (identical lookup/
                # store/evict/counter surface), so every downstream
                # consumer — admission, block-pressure valve, metrics,
                # the cluster's hit-rate aggregation — is layout-blind
                disk_store = None
                if self._kv_disk_dir is not None:
                    from tpu_parallel.serving.kv_disk import KVDiskStore

                    disk_store = KVDiskStore(
                        self._kv_disk_dir,
                        self.clock,
                        capacity_blocks=self._kv_disk_blocks,
                    )
                self._radix = RadixPrefixCache(
                    self.pool,
                    max_device_blocks=prefix_cache_size,
                    host_capacity_blocks=self._kv_host_blocks,
                    disk_store=disk_store,
                    weights_version=self.weights_version,
                )
                self._prefix = self._radix
        else:
            self.pool = CachePool(model, params, n_slots)

        # dropless expert layers: every program returns its row counts as
        # one more output; strip it here so that no call site changes, and
        # keep the device arrays until the tick's collect reads them
        self._expert_rows: list = []
        for name in ("_prefill_fn", "_extend_fn", "_decode_fn", "_fused_fn",
                     "_unified_fn", "_block_fn", "_block_prefill_fn"):
            fn = getattr(self, name)
            if fn is not None:
                setattr(self, name, _WithoutExpertRows(fn, self._expert_rows))

        from tpu_parallel.models.layers import layer_kinds

        # sublayers by kind over the depth ("ssm", "attention", "experts",
        # "dense"; "layers" the depth): a layer may be ONE of them alone, so
        # each plan's log line and tracer instant says of how many
        self.layer_kinds = layer_kinds(cfg)
        self.moe_plan = self._plan_experts(n_slots)
        self.ssm_plan = self._plan_state(n_slots)
        self.sampler_plan = self._plan_sampler(n_slots)
        self.block_plan = self._plan_blocks(n_slots)
        self.attn_plan = self._plan_attention(n_slots)
        self.prefill_attn_plan = self._plan_prefill_attention()
        self.latent_plan = self._plan_latent(n_slots)

        n = n_slots
        self._tok = np.zeros(n, np.int32)
        self._pos = np.zeros(n, np.int32)
        # inactive rows aim their decode-tick cache writes at column
        # seq_len — out of range, DROPPED — so a freed or mid-chunked-
        # prefill row is never dirtied by the shared decode step
        self._widx = np.full(n, cfg.seq_len, np.int32)
        self._temp = np.zeros(n, np.float32)
        self._topk = np.zeros(n, np.int32)
        self._topp = np.zeros(n, np.float32)
        self._active = np.zeros(n, bool)
        self._slot_out: List[Optional[RequestOutput]] = [None] * n
        # per-slot speculative state: the request's draft cap and the
        # acceptance-adaptive effective draft length (<= cap)
        self._spec_max = np.zeros(n, np.int32)
        self._spec_k = np.zeros(n, np.int32)

    @staticmethod
    def _flat_plan(plan: Dict[str, dict]) -> Dict[str, object]:
        """A plan's entries as one flat dict, ``<shape>_<key>``: a tracer
        instant's attributes."""
        return {f"{name}_{k}": v for name, p in plan.items() for k, v in p.items()}

    def _plan_experts(self, n_slots: int) -> Optional[Dict[str, dict]]:
        """What the dropless expert layers do in each program shape this
        engine runs (:func:`~tpu_parallel.models.moe.moe_plan`), logged
        and put on the tracer once at build; None without such a layer."""
        from tpu_parallel.models.moe import moe_plan

        cfg = self.model.config
        spec = next(
            (s.experts for s in cfg.layer_specs if s.experts is not None),
            None,
        )
        if spec is None:
            return None
        shapes = {"decode": n_slots * max(1, 2 * self._block_len)}
        if self._block_len and self._fused_steps > 1:
            shapes["decode_narrow"] = n_slots * self._block_len
        if self._chunk_tokens and self._unified:
            shapes["chunk"] = n_slots * self._chunk_tokens
        for b in self._buckets or ():
            shapes[f"prefill_{b}"] = self._prefill_batch * b
        plan = {
            name: moe_plan(spec, t, cfg.d_model, cfg.dtype)
            for name, t in shapes.items()
        }
        logging.getLogger(__name__).info(
            "moe_plan %s layers %s", json.dumps(plan),
            json.dumps(self.layer_kinds),
        )
        if self.tracer.enabled:
            self.tracer.instant(
                "moe_plan", track="scheduler", layers=cfg.routed_layers,
                of_layers=cfg.n_layers,
                **self._flat_plan(plan),
            )
        return plan

    def _plan_attention(self, n_slots: int) -> Dict[str, dict]:
        """What runs the attention of new rows against the stored stripes in
        each decode program shape (``ops.decode_attention.
        decode_attention_plan``, the rule ``layers.decode_attention`` applies
        to the same shapes): ``kernel`` with its tile, tiles a stripe and rows
        a call, or ``xla``; logged and put on the tracer once at build.  The
        choice is static a shape; what moves at run time is how much of a
        stripe is walked, which :meth:`_walked_tiles` counts a tick."""
        from tpu_parallel.ops.decode_attention import decode_attention_plan

        cfg = self.model.config
        kv_heads, head_dim = cfg.n_kv_heads or cfg.n_heads, cfg.head_dim
        if self._latent:
            # the absorbed form: every head against ONE stored head of a row
            kv_heads, head_dim = 1, self._latent[0].row
        shapes = {"decode": max(1, 2 * self._block_len)}
        if self._block_len and self._fused_steps > 1:
            shapes["decode_narrow"] = self._block_len
        if self._chunk_tokens and self._unified:
            shapes["chunk"] = self._chunk_tokens
        dtype = jnp.dtype(cfg.dtype)
        plan = {}
        for name, new_len in shapes.items():
            found = decode_attention_plan(
                (n_slots, new_len, cfg.n_heads, head_dim),
                (n_slots, cfg.seq_len, kv_heads, head_dim),
                dtype, jnp.int8 if cfg.kv_cache_dtype == "int8" else dtype,
                scales=cfg.kv_cache_dtype == "int8", paged=self._paged,
                bias=cfg.positional == "relative",
            )
            plan[name] = {"path": "xla"} if found is None else {
                "path": "kernel", "tile": found["tile"],
                "tiles": found["tiles"], "rows": found["rows"],
            }
        # the windows of the attention layers, by how many layers have each
        windows: Dict[int, int] = {}
        for spec in cfg.layer_specs:
            if spec.mixer == "attention" and spec.attn != "latent":
                window = spec.window if spec.attn == "window" else 0
                windows[window] = windows.get(window, 0) + 1
        self._attn_walk = None
        if plan["decode"]["path"] == "kernel":
            self._attn_walk = (
                plan["decode"]["tile"], plan["decode"]["tiles"],
                max(1, self._block_len), sorted(windows.items()),
            )
        logging.getLogger(__name__).info(
            "attn_plan %s layers %s", json.dumps(plan),
            json.dumps(self.layer_kinds),
        )
        if self.tracer.enabled:
            self.tracer.instant(
                "attn_plan", track="scheduler",
                layers=self.layer_kinds.get("attention", 0),
                of_layers=cfg.n_layers,
                **self._flat_plan(plan),
            )
        return plan

    def _plan_prefill_attention(self) -> Optional[Dict[str, dict]]:
        """What the flash kernels' FORWARD does in each whole-prompt prefill
        shape (``ops.flash_attention.flash_plan`` at the bucket's row and the
        layers' head width, group, rule and window, as the kernels' callers
        ask it): the tile, ``resident`` or ``streamed``, and the tiles of one
        row's walk computed and masked; a model whose attention layers differ
        in their window has an entry a window (``prefill_<bucket>_window<w>``).
        Logged and put on the tracer once at build; None where no prefill
        attends through the kernels (``prefill_flash`` off, no buckets, no
        attention layer).  Static a shape, as ``moe_plan`` and ``attn_plan``."""
        from tpu_parallel.models.layers import depth_specs
        from tpu_parallel.ops.flash_attention import flash_plan

        cfg = self.model.config
        kinds = set()  # (head width, group, window) of the attention layers
        for spec in depth_specs(cfg):
            if spec.mixer != "attention":
                continue
            if spec.attn == "latent":  # every head its own key, two widths
                width = max(
                    spec.latent.nope_dim + spec.latent.rope_dim,
                    spec.latent.v_dim,
                )
                kinds.add((width, 1, 0))
            else:
                kinds.add((
                    cfg.head_dim, cfg.n_heads // (cfg.n_kv_heads or cfg.n_heads),
                    spec.window if spec.attn == "window" else 0,
                ))
        if not (cfg.prefill_flash and self._buckets and kinds):
            return None
        plan = {}
        for bucket in self._buckets:
            for width, group, window in sorted(kinds):
                found = flash_plan(
                    bucket, width, group, cfg.dtype,
                    causal=cfg.block_len if cfg.block_len > 1 else True,
                    window=window, block_q=cfg.flash_block_q,
                    block_k=cfg.flash_block_k,
                )
                name = f"prefill_{bucket}" + (f"_window{window}" if window else "")
                plan[name] = {"path": "dense"} if found is None else {
                    "tile": found["fwd"]["block_q"],
                    "variant": found["fwd"]["variant"],
                    "tiles_computed": found["fwd"]["tiles_computed"],
                    "tiles_masked": found["fwd"]["tiles_masked"],
                }
        logging.getLogger(__name__).info(
            "prefill_attn_plan %s layers %s", json.dumps(plan),
            json.dumps(self.layer_kinds),
        )
        if self.tracer.enabled:
            self.tracer.instant(
                "prefill_attn_plan", track="scheduler",
                layers=self.layer_kinds.get("attention", 0),
                of_layers=cfg.n_layers,
                **self._flat_plan(plan),
            )
        return plan

    def _plan_latent(self, n_slots: int) -> Optional[Dict[str, object]]:
        """What a slot holds and which form runs for a model with latent
        attention layers (``models/latent_attention.py``): the heads and the
        five sizes, the bytes a position stores a layer and over the latent
        layers, the form of each program shape (``absorbed``: the stored
        rows are read as they lie; ``expanded``: the call's own latents are
        up-projected once and go through the flash kernels) and, last, what
        the kernels do in each expanded shape (``prefill_attn_plan``); logged
        and put on the tracer once at build; None without such a layer.
        ``latent_bytes_per_position`` goes to the metrics either way."""
        cfg = self.model.config
        if not self._latent:
            self.metrics.set_latent_bytes_per_position(0)
            return None
        spec = self._latent[0]
        row_bytes = spec.row * jnp.dtype(cfg.dtype).itemsize
        plan = {
            "layers": len(self._latent), "of_layers": cfg.n_layers,
            "heads": cfg.n_heads, "q_rank": spec.q_rank,
            "kv_rank": spec.kv_rank, "nope_dim": spec.nope_dim,
            "rope_dim": spec.rope_dim, "v_dim": spec.v_dim, "row": spec.row,
            "bytes_per_position_per_layer": row_bytes,
            "bytes_per_position": row_bytes * len(self._latent),
            "pool_bytes": row_bytes * len(self._latent) * n_slots * cfg.seq_len,
            "decode": "absorbed",
        }
        prefill = "expanded" if cfg.prefill_flash else "absorbed"
        for b in self._buckets or ():
            plan[f"prefill_{b}"] = prefill
        if self._buckets is None:
            plan["prefill"] = prefill
        if self.prefill_attn_plan:
            plan["prefill_attn"] = self.prefill_attn_plan
        self.metrics.set_latent_bytes_per_position(plan["bytes_per_position"])
        logging.getLogger(__name__).info("latent_plan %s", json.dumps(plan))
        if self.tracer.enabled:
            self.tracer.instant("latent_plan", track="scheduler", **plan)
        return plan

    def _latent_rows_read(self, slots) -> Optional[int]:
        """Stored rows the tick's decode steps read over the live ``slots``,
        summed over the latent layers: a step reads what the slot holds once
        its own row is in, ``_pos + j + 1`` at step ``j``.  From the host's
        mirrors at launch, as :meth:`_walked_tiles` (a slot that ends inside
        the tick is counted to the tick's end); None without a latent layer."""
        if not self._latent or not len(slots):
            return None
        pos = self._pos[list(slots)].astype(np.int64)
        steps = np.arange(1, self._fused_steps + 1)
        rows = np.minimum(pos[:, None] + steps[None, :], self.model.config.seq_len)
        return len(self._latent) * int(rows.sum())

    def _walked_tiles(self, slots) -> Optional[Tuple[int, int]]:
        """``(walked, held)`` for one tick over the live ``slots``: the
        tiles the decode kernel walks a layer-call, summed over the
        attention layers, and ``live slots x tiles a stripe x layers``.
        From the host's mirrors at launch (a slot's rows go in at
        ``_pos``; a window layer starts at the first tile a row can see):
        no device read.  None where no decode program uses the kernel."""
        if self._attn_walk is None or not len(slots):
            return None
        tile, tiles, new_len, windows = self._attn_walk
        pos = self._pos[list(slots)].astype(np.int64)
        last = np.minimum((pos + new_len - 1) // tile, tiles - 1)
        walked = held = 0
        for window, layers in windows:
            first = np.maximum(pos - window + 1, 0) // tile if window else 0
            walked += layers * int((last - first + 1).sum())
            held += layers * len(pos) * tiles
        return walked, held

    def _plan_sampler(self, n_slots: int) -> Dict[str, object]:
        """What :func:`sample_tokens` is compiled for: rows x vocabulary
        a decode step (and a prefill call's first tokens), and that what
        it runs of that block is chosen on the device, a step, from the
        live rows' knobs; logged and put on the tracer once at build."""
        plan = {
            "rows": n_slots, "vocab": self.model.config.vocab_size,
            "steps_per_tick": self._fused_steps,
            "first_token_rows": self._prefill_batch,
            "chosen": "on_device_per_step",
        }
        logging.getLogger(__name__).info("sampler_plan %s", json.dumps(plan))
        if self.tracer.enabled:
            self.tracer.instant("sampler_plan", track="scheduler", **plan)
        return plan

    def _plan_blocks(self, n_slots: int) -> Optional[Dict[str, object]]:
        """What a tick does for a block-diffusion model: the block's length
        and mask id, the rows a forward feeds (a wide step slots x 2L: the
        block and the completed one before it; a narrow step slots x L), the
        forwards a tick and which of them are wide, what makes a completed
        block's K/V final, and where the fill is chosen; logged and put on the
        tracer once at build; None for any other model."""
        if not self._block_len:
            return None
        cfg = self.model.config
        plan = {
            "block_len": self._block_len, "mask_token_id": cfg.mask_token_id,
            "rows_per_step": n_slots * 2 * self._block_len,
            "rows_per_narrow_step": n_slots * self._block_len,
            "steps_per_tick": self._fused_steps,
            "wide_steps_per_tick": (self._fused_steps + 1) // 2,
            "commit": "rides_next_block_first_step",
            "chosen": "top_k_over_block_on_device",
        }
        logging.getLogger(__name__).info("block_plan %s", json.dumps(plan))
        if self.tracer.enabled:
            self.tracer.instant("block_plan", track="scheduler", **plan)
        return plan

    def _plan_state(self, n_slots: int) -> Optional[Dict[str, object]]:
        """What a slot holds for a model with recurrent layers: layers of
        each kind, the state's shape and type, the scan's chunk, bytes of
        state and of K/V a slot (read off the pool's own leaves), logged
        and put on the tracer once at build; None without such a layer.
        ``state_bytes_per_slot`` goes to the metrics either way."""
        from tpu_parallel.serving.cache_pool import STATE_LEAVES, _leaf_name

        cfg = self.model.config
        state = kv = 0
        dtypes = set()
        for path, leaf in jax.tree_util.tree_flatten_with_path(
            self.pool.cache
        )[0]:
            name = _leaf_name(path)
            if name.startswith(STATE_LEAVES):
                state += leaf.nbytes
                if name.startswith("ssm_state"):
                    dtypes.add(str(leaf.dtype))
            elif name.startswith("cached_"):
                kv += leaf.nbytes
        self._state_bytes_per_slot = state // n_slots
        self.metrics.set_state_bytes_per_slot(self._state_bytes_per_slot)
        if not cfg.recurrent_layers:
            return None
        spec = next(s.ssm for s in cfg.layer_specs if s.mixer == "ssm")
        plan = {
            "ssm_layers": cfg.recurrent_layers,
            "attention_layers": self.layer_kinds.get("attention", 0),
            "expert_layers": cfg.routed_layers, "layers": cfg.n_layers,
            "heads": spec.n_heads, "head_dim": spec.head_dim,
            "d_state": spec.d_state, "groups": spec.n_groups,
            "conv_width": spec.d_conv, "chunk": spec.chunk,
            "state_dtype": "/".join(sorted(dtypes)),
            "state_bytes_per_slot": self._state_bytes_per_slot,
            "kv_bytes_per_slot": kv // n_slots,
            "slots": n_slots,
        }
        logging.getLogger(__name__).info("ssm_plan %s", json.dumps(plan))
        if self.tracer.enabled:
            self.tracer.instant("ssm_plan", track="scheduler", **plan)
        return plan

    # -- submission --------------------------------------------------------

    def add_request(
        self,
        request: Request,
        requeue: bool = False,
        arrival_time: Optional[float] = None,
    ) -> RequestOutput:
        """Submit; returns the live output record (status REJECTED with a
        TYPED ``finish_reason`` — ``capacity`` / ``queue_full`` /
        ``draining`` — when the prompt cannot fit or admission refuses;
        human detail in ``out.detail``).

        ``requeue=True`` marks accepted work being relocated by the
        cluster frontend (bypasses the drain gate, not the queue bound);
        ``arrival_time`` preserves the ORIGINAL arrival across replica
        retries so queue-wait telemetry stays cumulative — a retried
        request's wait is everything since the client submitted, not
        since the failover."""
        out = RequestOutput(
            request,
            arrival_time=(
                arrival_time if arrival_time is not None else self.clock()
            ),
        )
        unsupported = self.unsupported(request)
        if unsupported is not None:
            out.status = REJECTED
            out.finish_reason = REJECT_UNSUPPORTED
            out.detail = unsupported
            self.metrics.record_rejected()
            return out
        total = len(request.prompt) + request.max_new_tokens
        if total > self.model.config.seq_len:
            out.status = REJECTED
            out.finish_reason = REJECT_CAPACITY
            out.detail = (
                f"prompt ({len(request.prompt)}) + max_new_tokens "
                f"({request.max_new_tokens}) exceeds seq_len "
                f"({self.model.config.seq_len})"
            )
            self.metrics.record_rejected()
            return out
        if self._paged:
            # a pool smaller than one request's worst case could never
            # admit it — the typed reject the cluster frontend already
            # understands (transient exhaustion instead queues: the
            # per-tick block gate holds the head until blocks free up)
            need = self.pool.blocks_needed(total) + self._cow_reserve
            if need > self.pool.n_blocks:
                out.status = REJECTED
                out.finish_reason = REJECT_CAPACITY
                out.detail = (
                    f"request needs {need} KV blocks "
                    f"({total} tokens at {self.pool.block_tokens}/block"
                    + (
                        f" + {self._cow_reserve} copy-on-write reserve"
                        if self._cow_reserve
                        else ""
                    )
                    + f") but the pool holds {self.pool.n_blocks}"
                )
                self.metrics.record_rejected()
                return out
        verdict = self.scheduler.submit(out, requeue=requeue)
        if not verdict:
            out.status = REJECTED
            out.finish_reason = verdict.reason
            self.metrics.record_rejected()
            return out
        if self.tracer.enabled:
            # async span (queue waits of concurrent requests overlap on
            # the scheduler track); closed at admission or expiry
            rid = request.request_id
            self._queue_spans[rid] = self.tracer.start_async(
                "queue", track="scheduler", async_id=rid, request_id=rid
            )
        return out

    def unsupported(self, request: Request) -> Optional[str]:
        """What ``request`` asks for that the served model's decoding rule
        does not do (the typed ``unsupported`` rejection), or None."""
        size = self._block_len
        sp = request.sampling
        if not size:
            if request.denoising_steps is not None or (
                request.confidence_threshold > 0.0
            ):
                return (
                    "denoising_steps / confidence_threshold on a model that "
                    "does not generate by diffusion over blocks"
                )
            return None
        if request.denoising_steps is not None and not (
            1 <= request.denoising_steps <= size
        ):
            return (
                f"denoising_steps={request.denoising_steps} outside 1.."
                f"block_len={size}"
            )
        if sp.top_k > 0 or 0.0 < sp.top_p < 1.0:
            return (
                "top_k / top_p on a block-diffusion model: a position's pick "
                "and its confidence come from one pass over the logits, no "
                "sort over the vocabulary"
            )
        if request.draft_tokens:
            return "draft_tokens on a block-diffusion model"
        return None

    # -- lifecycle control (cancellation / drain) --------------------------

    def cancel(self, request_id: str, reason: str = "cancelled") -> bool:
        """Cancel a request wherever it is — queued (pulled from the
        scheduler) or in-engine (its slot released, mid-chunked-prefill
        included).  Terminal tokenless StreamEvent to the stream, status
        CANCELLED, cache slot returned to the free list.  False when the
        request is unknown or already terminal (nothing to cancel)."""
        out = self.scheduler.remove(request_id)
        slot: Optional[int] = None
        if out is None:
            for i, candidate in enumerate(self._slot_out):
                if (
                    candidate is not None
                    and candidate.request.request_id == request_id
                ):
                    slot, out = i, candidate
                    break
            if out is None:
                return False
            self.release_slot(slot)
            self._flush("cancel")
        now = self.clock()
        span = self._queue_spans.pop(request_id, None)
        if span is not None:
            span.finish(cancelled=True)
        out.status = CANCELLED
        out.finish_reason = reason
        out.finish_time = now
        self.metrics.record_cancelled()
        if self.tracer.enabled:
            track = "scheduler" if slot is None else f"slot {slot}"
            self.tracer.instant(
                "cancel", track=track, request_id=request_id, reason=reason
            )
        event = StreamEvent(
            request_id=request_id,
            token=-1,
            index=-1,
            finished=True,
            finish_reason=reason,
        )
        if out.request.on_token is not None:
            out.request.on_token(event)
        return True

    def release_slot(self, slot: int) -> None:
        """Free ``slot`` without delivering anything: drop any in-flight
        chunked prefill, park the row's decode writes out of range (column
        seq_len — dropped by scatter semantics, see ``__init__``), return
        the slot to the pool.  The retirement AND cancellation path."""
        self._chunking.pop(slot, None)
        self._active[slot] = False
        self._slot_out[slot] = None
        self._widx[slot] = self.model.config.seq_len
        if not self._chains:
            # re-upload before the next tick; where ticks chain, a slot
            # retired by length or EOS is already dead in the live mask
            # the device carries, and only a removal the device cannot
            # know of rebuilds its state (_flush)
            self._state_dirty = True
        self.pool.release(slot)

    def _flush(self, cause: str) -> None:
        """The host removed a seated request behind the device's back:
        the next launch waits for the tick in flight and rebuilds the
        device's slot state from the host mirrors, which are whole again
        once nothing is in flight."""
        self._state_dirty = True
        self._flush_cause = cause

    def begin_drain(self) -> None:
        """Graceful-drain admission gate: new ``add_request`` submissions
        reject with the typed ``draining`` reason; queued and in-flight
        work runs to completion (the cluster frontend additionally
        re-routes the queued remainder across live replicas)."""
        self.scheduler.begin_drain()

    @property
    def draining(self) -> bool:
        return self.scheduler.draining

    @property
    def in_flight(self) -> int:
        """Requests holding a cache slot right now — decoding slots plus
        mid-chunked-prefill slots (the router's active-slot load term)."""
        return int(self._active.sum()) + len(self._chunking)

    @property
    def pending_prefill_tokens(self) -> int:
        """Estimated prompt tokens still to prefill: queued prompts plus
        the unwritten remainders of in-flight chunked prefills."""
        chunk_rest = sum(
            len(st.out.request.prompt) - st.offset
            for st in self._chunking.values()
        )
        return self.scheduler.pending_prefill_tokens + chunk_rest

    # -- the tick ----------------------------------------------------------

    def step(self) -> List[StreamEvent]:
        """One engine tick: expire stale queue entries, advance in-flight
        chunked prefills (one unified-dispatch phase, or one per-slot
        chunk extend each on the per-phase engine), admit into free slots
        (bounded by the scheduler's prefill budget, same-bucket
        admissions as one batched prefill), one decode tick over the
        pool (``decode_steps_per_tick`` fused scan steps — or one
        per-step / speculative-verify step), retire finished slots.
        Returns the events of ONE tick.

        The two halves are :meth:`launch` (dispatch, no sync) and
        :meth:`collect` (one sync, delivery), and ``step()`` keeps one
        tick queued on the device between them: with tick N pending it
        launches tick N+1 FIRST and then collects N, so N's sync and
        delivery, and whatever the caller does before its next
        ``step()``, run beside device work.  N+1 admits into the slots
        that were free at the last collect; a slot N frees is refilled
        by N+2.  Whether a launch may go ahead is read off the engine
        (:meth:`_ahead_refusal`); where it may not, this is
        ``collect(launch())``."""
        p, self._pending = self._pending, None
        if p is None:
            p = self.launch()
        refusal = self._ahead_refusal(p)
        if refusal is None:
            self._pending = self.launch(ahead=True)
        elif p.kind != "idle" and self._chains:
            self.metrics.record_flush(refusal)
        return self.collect(p)

    def _ahead_refusal(self, p: _PendingTick) -> Optional[str]:
        """Why the next tick may NOT be dispatched before ``p``, the tick
        in flight, is collected; None when it may.  Ticks chain on the
        engines whose launch reads nothing the tick in flight will
        change: the fused and unified ticks over the fixed-slot pool,
        whose slot state stays on the device (the per-step, speculative
        and paged engines draft, grow block tables or upload from host
        mirrors that lag a tick in flight).  A host-side removal
        (``_flush``) waits for the collect; and a launch goes ahead only
        for work that is certain: a slot with budget beyond the tick in
        flight, a chunked prompt mid-way, or a queued request and a free
        slot — else the tick after a drain would run over dead slots."""
        if not self._chains or p.kind == "idle":
            return "tick_kind"
        if self._flush_cause is not None:
            return self._flush_cause
        if self._chunking or (self.scheduler.depth and self.pool.n_free):
            return None
        # the tick in flight and a first token; a block step may emit L
        reach = self._fused_steps * max(1, self._block_len) + 1
        for slot in np.nonzero(self._active)[0]:
            out = self._slot_out[slot]
            if out.request.max_new_tokens - len(out.tokens) > reach:
                return None
        return "draining"

    def launch(self, ahead: bool = False) -> _PendingTick:
        """The tick's HOST->DEVICE half: expire, fold/advance chunked
        prefills, admit, and DISPATCH the tick's decode work WITHOUT
        syncing.  Returns the pending handle :meth:`collect` finishes.

        ``ahead=True`` marks a pipelined launch (tick N+1 dispatched
        while tick N is still uncollected, :meth:`_ahead_refusal`): the
        host mirrors then lag the device by the tick in flight, and what
        this launch dispatches takes its slot state from the device.
        Between launch and collect every donated buffer belongs to the
        device: nothing launch reaches may read device results (the
        launch rule in ``scripts/check_host_sync.py``)."""
        p = self._newest_tick = _PendingTick()
        p.overlapped = ahead
        if self.tracer.enabled:
            p.tick_span = self.tracer.span(
                "tick", track="scheduler", tick=self.metrics.ticks
            )
        unified = self._unified and self._fused_steps > 1
        # per-phase: chunked prefills run BEFORE scheduling, one extend
        # dispatch per slot — a chunk finishing this tick decodes this
        # tick (so such a tick enters `schedule` and `prefill` twice)
        chunks_first = not unified and bool(self._chunking)
        with self._phase(p, "schedule") as first:
            now = p.start = first.start
            if self._busy_end is not None:
                p.between, self._busy_end = now - self._busy_end, None
            if self._chains and (
                self._state_dirty or self._dev_state is None
            ):
                # before any admission writes its rows: the mirrors hold
                # no first token of a request seated by this launch
                self._upload_slot_state()
            self._expire_queue(now, p.events)
            if not chunks_first:
                p.admitted = self._schedule(now)
        if chunks_first:
            with self._phase(p, "prefill"):
                p.chunks_advanced = len(self._chunking)
                for slot in sorted(self._chunking):
                    p.events.extend(self._advance_chunk(slot))
            with self._phase(p, "schedule"):
                p.admitted = self._schedule(now)
        with self._phase(p, "prefill"):
            p.events.extend(self._admit_batch(p.admitted))
        if unified:
            # chunk slots (newly started ones included) ride THIS tick's
            # unified dispatch — same chunk-per-tick cadence as the
            # per-phase engine, minus its per-slot dispatches
            p.chunks_advanced = len(self._chunking)
        with self._phase(p, "dispatch") as dispatch:
            self._launch_decode(p)
            p.tiles = self._walked_tiles(p.entering)
            p.latent_rows = self._latent_rows_read(p.entering)
            # active tokens RESIDENT during this tick's decode = slots'
            # written depths + chunked prefills' post-advance offsets,
            # captured BEFORE delivery retires finished slots — the
            # capacity denominator behind kv_bytes_per_active_token
            p.active_tokens = int(self._pos[self._active].sum()) + sum(
                st.offset for st in self._chunking.values()
            )
        p.owners = list(self._slot_out)
        p.expert_rows = self._expert_rows[:]
        del self._expert_rows[:]
        p.t0 = dispatch.end
        return p

    def _phase(self, p: _PendingTick, name: str) -> phase:
        """One leaf phase of tick ``p`` (:mod:`tpu_parallel.obs.phases`):
        its seconds on the engine's clock go to ``p`` (observed at the
        tick's end if it was busy), a span to the ``scheduler`` track
        when tracing, and ``engine.tick.<name>`` to the profiler."""
        return phase(
            p.add_phase, self.tracer, "scheduler", name, self.clock,
            annotation=ENGINE_PREFIX + name,
        )

    @stack_room
    def _run(self, kind: Optional[str], shape: str, fn, *args):
        """THE dispatch point: every jitted program of the engine is
        launched here and nowhere else.  Calls ``fn(*args)`` and returns
        what it returned; for a watched ``kind`` (``tick``: a decode tick
        that carried no prompt tokens, ``tick_chunk``, ``prefill``,
        ``extend``) it hands the completion clock
        (:mod:`tpu_parallel.obs.device_clock`) ``shape`` (the compiled
        shape in words), the engine's clock read now that the call has
        returned, and the smallest array of the program's FIRST output:
        a tick's token block, a prefill's logits, a block prefill's fresh
        rows - what no later program donates, so the clock's thread may
        wait for it.  ``kind=None`` (the first-token sampler) is not
        watched: its device time falls to the watched program that
        follows, as that of the row scatters, the seat programs and the
        uploads, which have no output that survives.

        A program's first call traces it, millions of Python calls deep
        in this frame: :func:`stack_room` keeps them clear of the edges
        of the interpreter's stack chunks, where this frame, one more
        than a direct call had, had pushed them (the block-diffusion
        cell's prefill programs traced 2.6 times as long)."""
        out = fn(*args)
        if kind is not None:
            leaf = min(
                jax.tree_util.tree_leaves(out[0]), key=lambda x: x.size
            )
            self._device_clock.watch(kind, shape, self.clock(), leaf)
        return out

    def _device_ran(self, *interval) -> None:
        """The completion clock's sink, on its thread: the record that is
        current when the program completes takes it."""
        self.metrics.record_device(*interval)

    def _device_lost(self, dropped: bool) -> None:
        if dropped:
            self.metrics.record_device_dropped()
        else:
            self.metrics.record_device_fault()

    def _schedule(self, now: float) -> List[RequestOutput]:
        bucket_key = (
            self._admission_key
            if (self._buckets is not None or self._chunk_tokens is not None)
            else None
        )
        return self.scheduler.schedule(
            self.pool.n_free, now, bucket_key=bucket_key,
            can_admit=self._block_gate() if self._paged else None,
        )

    def _expire_queue(self, now: float, events: List[StreamEvent]) -> None:
        for out in self.scheduler.expire(now):
            # terminal notification with no token (token/index = -1):
            # expiry is asynchronous — unlike REJECTED, which the caller
            # sees synchronously on add_request — so stream consumers need
            # the event or they wait forever
            out.finish_reason = "max_wait"
            out.finish_time = now
            rid = out.request.request_id
            span = self._queue_spans.pop(rid, None)
            if span is not None:
                span.finish(expired=True)
            event = StreamEvent(
                request_id=rid,
                token=-1,
                index=-1,
                finished=True,
                finish_reason="max_wait",
            )
            if out.request.on_token is not None:
                out.request.on_token(event)
            events.append(event)
            self.metrics.record_expired()

    def _launch_decode(self, p: _PendingTick) -> None:
        """Dispatch the tick's decode-phase device work (no sync)."""
        unified_chunks = bool(
            self._unified and self._fused_steps > 1 and self._chunking
        )
        if not self._active.any() and not unified_chunks:
            return
        p.entering = tuple(int(s) for s in np.nonzero(self._active)[0])
        if self._block_len:
            self._launch_block(p)
        elif self._spec_fused:
            self._launch_spec_fused(p, unified_chunks)
        elif self._spec_width > 0:
            self._launch_spec_step(p)
        elif self._fused_steps > 1:
            if unified_chunks:
                self._launch_unified(p)
            else:
                self._launch_fused(p)
        else:
            self._launch_per_step(p)

    def collect(self, p: _PendingTick) -> List[StreamEvent]:
        """The tick's DEVICE->HOST half: ONE sync on the launch's result
        handles, then delivery (the first tokens of the requests the
        launch seated, then the tick's block), retirement, metric syncs
        and the tick record.  Pure host work apart from the sync — from
        ``step()`` all of it runs while the NEXT tick's device dispatch
        is already computing."""
        events = p.events
        decoded = p.kind != "idle"
        if decoded:
            with self._phase(p, "device_wait") as wait:
                self._sync_payload(p)
            p.t1 = wait.end
            with self._phase(p, "deliver"):
                for tokens, rows in p.firsts:
                    events.extend(self._deliver_firsts(tokens, rows))
                if p.kind == "block":
                    events.extend(self._collect_block(p))
                elif p.kind == "fused":
                    events.extend(self._collect_fused(p))
                elif p.kind == "unified":
                    events.extend(self._collect_unified(p))
                elif p.kind == "spec_fused":
                    events.extend(self._collect_spec_fused(p))
                elif p.kind == "spec":
                    events.extend(self._collect_spec_step(p))
                else:
                    events.extend(self._collect_per_step(p))
        admitted = p.admitted
        chunks_advanced = p.chunks_advanced
        active_tokens = p.active_tokens
        with self._phase(p, "record") as record:
            for rows in p.expert_rows:  # small counts of programs synced above
                rows = np.asarray(rows)  # host-sync: a tick's expert counts
                self.metrics.record_expert_rows(rows)
            if self._prefix is not None:
                entry_bytes = None
                if self._radix is not None:
                    entry_bytes = self._radix.device_bytes
                elif self._paged:
                    entry_bytes = self.pool.bytes_per_block * sum(
                        len(blocks) for blocks, _ in self._prefix.values()
                    )
                self.metrics.sync_prefix_cache(
                    self._prefix, entry_bytes=entry_bytes
                )
                if self._radix is not None:
                    self.metrics.sync_host_tier(self._radix)
                    if self._radix.disk is not None:
                        self.metrics.sync_disk_tier(self._radix)
            if self._paged:
                self.metrics.sync_block_pool(
                    self.pool, active_tokens=active_tokens
                )
            # stall attribution, most-specific first: any prefill work
            # this tick stalled the pool's decode; a speculative tick
            # spent its decode slot verifying; an undecoded tick with
            # nothing admitted was starved by an empty queue; else a
            # clean decode tick
            if admitted or chunks_advanced:
                stall = STALL_PREFILL
            elif decoded and self._spec_width > 0:
                stall = STALL_SPEC_VERIFY
            elif not decoded:
                stall = STALL_QUEUE_EMPTY
            else:
                stall = STALL_NONE
        end = record.end
        # the tick's period: from its predecessor's collect where it was
        # launched ahead of that, else from its own launch
        period = end - max(p.start, self._collect_end)
        self._collect_end = end
        self.metrics.record_tick(
            now=end,
            queue_depth=self.scheduler.depth,
            occupancy=self.pool.occupancy,
            # expiry notifications carry token=-1 — not generated tokens
            new_tokens=sum(1 for ev in events if ev.token >= 0),
            prefills=len(admitted),
            decoded=decoded,
            stall=stall,
            host_ms=period * 1000.0,
        )
        if decoded:
            # what ran beside device work is not host-exposed: this
            # tick's launch and the gap before it where its predecessor
            # was in flight, its collect where its successor already is
            hidden = set()
            if p.overlapped:
                hidden.update(("between", "schedule", "prefill", "dispatch"))
            if self._newest_tick is not p:
                hidden.update(("device_wait", "deliver", "record"))
            self.metrics.record_busy_tick(
                period, p.phases, prefill=stall == STALL_PREFILL,
                between=p.between, ahead=p.overlapped, hidden=hidden,
                # an upper bound of what the device chose: a slot's
                # owner may have finished before the tick's last step
                sampled=any(
                    out is not None
                    and out.request.sampling.temperature > 0.0
                    for out in p.owners
                ),
                tiles=p.tiles, latent_rows=p.latent_rows,
            )
            self._busy_end = end
            if p.between is not None and self.tracer.enabled:
                self.tracer.record(
                    SPAN_PREFIX + "between", "scheduler",
                    p.start - p.between, p.start,
                )
        if p.tick_span is not None:
            p.tick_span.finish(
                stall=stall,
                queue_depth=self.scheduler.depth,
                admitted=len(admitted),
                decoded=decoded,
                **{f"{k}_ms": 1e3 * v for k, v in p.phases.items()},
            )
        if not self.has_work():
            # drained: every program has completed, and the completion
            # clock lets go of the device (its thread ends)
            self._device_clock.rest()
        return events

    def _sync_payload(self, p: _PendingTick) -> None:
        """The tick's ONE device sync: the launch's result handles (all
        of one dispatch) become host arrays on ``p.payload``; a host-side
        entry (the spec tick's draft lengths) passes through.  The first
        tokens of the requests the launch seated are read in the same
        wait: they were sampled before the tick ran."""
        p.firsts = [
            (np.asarray(tokens), rows)  # host-sync: with the tick's block
            for tokens, rows in p.firsts
        ]
        if p.kind == "step":
            p.payload = np.asarray(p.payload)
        else:
            p.payload = tuple(
                x if x is None else np.asarray(x)  # host-sync: once a tick
                for x in p.payload
            )

    def has_work(self) -> bool:
        return (
            self._pending is not None
            or self.scheduler.depth > 0
            or bool(self._active.any())
            or bool(self._chunking)
        )

    def run(self, max_ticks: Optional[int] = None) -> List[StreamEvent]:
        """``step()`` until idle (or ``max_ticks`` steps, which may leave
        a tick in flight for the next call); returns all events."""
        events: List[StreamEvent] = []
        ticks = 0
        while self.has_work() and (max_ticks is None or ticks < max_ticks):
            events.extend(self.step())
            ticks += 1
        return events

    def reset_metrics(
        self, metrics: Optional[ServingMetrics] = None
    ) -> ServingMetrics:
        """Swap in a fresh metrics record and rewire the scheduler's
        telemetry to it — the bench's measure-after-warmup reset.

        The default replacement keeps the old record's ``logger`` /
        ``log_every`` streaming config but owns a NEW registry: registry
        instruments are monotone (a shared one cannot be zeroed without
        lying to its other writers), so a reset always starts new series
        — re-share explicitly by passing ``metrics`` built on the
        registry you want.  Returns the new metrics."""
        if metrics is None:
            metrics = ServingMetrics(
                logger=self.metrics.logger, log_every=self.metrics.log_every
            )
        # what the device finishes from here on is the new record's, from
        # here on: a program in flight now is clipped to this instant
        metrics.open_device_window(self.clock())
        self.metrics = metrics
        self.registry = self.metrics.registry
        self.scheduler.registry = self.registry
        if self._paged:
            # the pool's COW/share tallies are cumulative; watermark them
            # so the fresh record's delta-synced counters start at zero
            self.metrics.seed_block_pool(self.pool)
        self.metrics.set_state_bytes_per_slot(self._state_bytes_per_slot)
        self.metrics.set_latent_bytes_per_position(
            (self.latent_plan or {}).get("bytes_per_position", 0)
        )
        return self.metrics

    def rebind_params(self, params, version: Optional[str] = None) -> None:
        """Swap the engine's served weights IN PLACE — the zero-downtime
        hot-swap entry point (``cluster/swap.py`` drives it per replica).

        The new tree must match the current one exactly in structure,
        leaf shapes and dtypes (:func:`validate_same_shapes` — the ONE
        check ``begin_swap``'s typed up-front refusal also uses): every
        jitted engine fn takes ``params`` as a plain traced operand, so
        a same-shape rebind reuses every compiled program (no retrace,
        no recompile — the swap tests pin the jit cache sizes).  A
        mismatched tree raises ``ValueError`` with the first offending
        leaf path; an engine with work in flight raises ``RuntimeError``
        — callers must drain or relocate first, because requests
        mid-generation would silently continue under different weights
        (the cluster controller guarantees the idle window; direct users
        get the loud error).
        """
        if self.has_work():
            raise RuntimeError(
                f"rebind_params with work in flight ({self.in_flight} "
                f"slots, {self.scheduler.depth} queued) — drain or "
                "relocate before swapping weights"
            )
        try:
            validate_same_shapes(self.params, params)
        except ValueError as exc:
            raise ValueError(
                f"rebind_params: {exc} — same-shape swaps only (a "
                "reshape needs a new engine)"
            ) from None
        self.params = params
        if version is not None:
            self.weights_version = version
            if self._radix is not None:
                # future disk spills stamp the new version; persisted
                # chains from the old weights refuse typed at hydrate
                self._radix.weights_version = version

    # -- KV block export / import (cross-replica migration) ----------------

    def export_prefix(self, request_id: str) -> Optional[KVPrefixExport]:
        """Export a live request's written KV prefix as host bytes — the
        source half of cross-replica migration (``cluster/migration.py``
        calls this right before a relocation cancels the slot, so the
        forced-prefix replay can ship blocks instead of recomputing).

        Covers the FULL blocks of the columns actually written so far: a
        decoding slot has written ``prompt + delivered[:-1]`` (the
        current token lands next tick), a mid-chunked-prefill slot its
        chunk offset — both strictly shorter than the replay's
        forced-prefix prompt, so the import is always a usable lookup
        key.  Stale speculative columns sit at/beyond the accepted
        frontier and therefore outside every exported block.  None when
        there is nothing exportable (fixed-slot engine, unknown request,
        less than one full block written)."""
        if not self._paged:
            return None
        slot = None
        for i, out in enumerate(self._slot_out):
            if (
                out is not None
                and out.request.request_id == request_id
            ):
                slot = i
                break
        if slot is None:
            return None
        out = self._slot_out[slot]
        if slot in self._chunking:
            written = self._chunking[slot].offset
            ctx = tuple(int(t) for t in out.request.prompt)
        elif self._active[slot]:
            written = int(self._pos[slot])
            ctx = tuple(int(t) for t in out.request.prompt) + tuple(
                int(t) for t in out.tokens
            )
        else:
            return None
        bt = self.pool.block_tokens
        n = min(written, len(ctx)) // bt
        if n <= 0:
            return None
        blocks = [int(self.pool.block_table[slot, j]) for j in range(n)]
        if any(b < 0 for b in blocks):
            return None  # belt and braces: written columns are mapped
        leaves = tuple(self.pool.export_blocks(blocks))
        return KVPrefixExport(
            tokens=ctx[: n * bt],
            length=n * bt,
            block_tokens=bt,
            weights_version=self.weights_version,
            meta=self.pool.export_meta,
            leaves=leaves,
            checksums=block_checksums(list(leaves), n),
        )

    def export_hot_prefixes(
        self, max_blocks: int = 16
    ) -> List[KVPrefixExport]:
        """Export the radix tree's hottest resident chains (up to
        ``max_blocks`` blocks total) — the donor half of the autopilot
        scale-up warm start.  Empty without a radix cache."""
        if self._radix is None:
            return []
        out = []
        meta = self.pool.export_meta
        for tokens, blocks in self._radix.hottest_chains(max_blocks):
            leaves = tuple(self.pool.export_blocks(list(blocks)))
            out.append(
                KVPrefixExport(
                    tokens=tokens,
                    length=len(tokens),
                    block_tokens=self.pool.block_tokens,
                    weights_version=self.weights_version,
                    meta=meta,
                    leaves=leaves,
                    checksums=block_checksums(list(leaves), len(blocks)),
                )
            )
        return out

    def import_prefix(self, export: KVPrefixExport) -> str:
        """Land an exported KV prefix in THIS engine's prefix cache so
        the next admission of a matching prompt HITS instead of
        re-prefilling — the target half of migration.  Returns a typed
        verdict (``kv_hierarchy.MIGRATION_STATUSES``): everything except
        ``imported`` / ``already_cached`` is a counted fallback and the
        caller's forced-prefix replay recomputes exactly as before.
        Refuses typed on block-size/shape mismatch and — critically — on
        a ``weights_version`` mismatch: cached K/V is a function of the
        params, and importing across versions would continue the stream
        with silently wrong attention reads."""
        if not self._paged:
            return MIGRATE_NOT_PAGED
        if self._prefix is None:
            return MIGRATE_NO_PREFIX_CACHE
        if export.weights_version != self.weights_version:
            return MIGRATE_WEIGHTS_VERSION
        if (
            export.block_tokens != self.pool.block_tokens
            or export.meta != self.pool.export_meta
        ):
            return MIGRATE_INCOMPATIBLE
        tokens = tuple(int(t) for t in export.tokens)
        if self._radix is not None:
            if self._radix.covers(tokens, export.length):
                return MIGRATE_ALREADY_CACHED
            try:
                blocks = self.pool.import_stored(
                    list(export.leaves), export.n_blocks,
                    checksums=export.checksums or None,
                )
            except KVIntegrityError:
                # the export's bytes rotted in transit/at rest: typed
                # refusal — the replay recomputes bitwise instead of
                # serving corrupted attention to every sharer
                return MIGRATE_INTEGRITY
            if blocks is None:
                return MIGRATE_NO_BLOCKS
            dupes = self._radix.insert(tokens, blocks)
            if dupes:
                self.pool.free_stored(dupes)
            return MIGRATE_IMPORTED
        # aligned-LRU target: store under the largest bucket key the
        # export covers (lookups probe bucket-aligned keys only)
        width = max(
            (b for b in self._buckets or () if b <= export.length),
            default=0,
        )
        if width <= 0:
            return MIGRATE_NO_KEY
        key = tokens[:width]
        if key in self._prefix:
            return MIGRATE_ALREADY_CACHED
        need = self.pool.blocks_needed(width)
        try:
            blocks = self.pool.import_stored(
                [leaf[:need] for leaf in export.leaves], need,
                checksums=(
                    export.checksums[:need] if export.checksums else None
                ),
            )
        except KVIntegrityError:
            return MIGRATE_INTEGRITY
        if blocks is None:
            return MIGRATE_NO_BLOCKS
        if not self._prefix.store_one(key, width, blocks):
            self.pool.free_stored(blocks)  # lost the store race
            return MIGRATE_ALREADY_CACHED
        return MIGRATE_IMPORTED

    @property
    def decode_steps_per_tick(self) -> int:
        """Decode steps per fused tick (1 = the per-step engine — spec
        "auto" resolves here; plain ``"auto"`` resolves to 8)."""
        return self._fused_steps

    @property
    def unified_tick(self) -> bool:
        """True when chunked-prefill and decode slots advance in ONE
        dispatch per tick (the unified ragged tick; "auto" = on whenever
        the fused tick is)."""
        return self._unified and self._fused_steps > 1

    @property
    def prefill_buckets(self) -> Optional[Tuple[int, ...]]:
        """The engine's prefill bucket set (None in legacy exact mode) —
        the alignment the prefix cache AND the cluster's prefix-affinity
        router key off."""
        return self._buckets

    @property
    def prefill_compiles(self) -> int:
        """Distinct prefill/extend call SHAPES this engine has issued —
        the host-side mirror of jit compile count (the jitted fns are
        shared across engines of the same model via an lru_cache, so
        their ``_cache_size()`` counts the whole process)."""
        return len(self._prefill_shapes)

    # -- internals ---------------------------------------------------------

    def _next_rng(self) -> jax.Array:
        self._rng, sub = jax.random.split(self._rng)
        return sub

    def _device_table(self) -> Optional[jax.Array]:
        """Every tick program's block-table operand: None on the
        fixed-slot pool; on the paged pool the device copy of the pool's
        host-authoritative block-table mirror, re-uploaded ONLY when the
        allocator moved a mapping (``table_version``) — steady-state
        decode re-dispatches the same device array, so the fused tick's
        inputs are loop-invariant and its compile count stays pinned."""
        if not self._paged:
            return None
        if (
            self._dev_table is None
            or self._table_version != self.pool.table_version
        ):
            self._dev_table = jnp.asarray(self.pool.block_table)
            self._table_version = self.pool.table_version
        return self._dev_table

    def _block_gate(self) -> Callable[[RequestOutput], bool]:
        """Admission gate closure for ONE scheduling pass: a candidate
        must fit its WORST-CASE block footprint (``ceil((prompt +
        max_new_tokens) / block_tokens)``, ignoring prefix sharing —
        conservative, plus the copy-on-write reserve for non-aligned
        buckets) inside the free blocks not already spoken for by
        in-flight slots' entitlements.  The closure tracks its own
        running reservation so one tick's multiple admissions cannot
        jointly overcommit.  A refused candidate first EVICTS
        least-recently-used prefix-cache entries (stored entries hold
        refcounted blocks indefinitely — without the pressure valve a
        head whose worst case exceeds the un-stored remainder would
        starve forever); only then does it wait (head-of-line,
        FIFO-fair) for running requests to retire blocks."""
        pool = self.pool
        reserved = 0

        def gate(out: RequestOutput) -> bool:
            nonlocal reserved
            need = (
                pool.blocks_needed(
                    len(out.request.prompt) + out.request.max_new_tokens
                )
                + self._cow_reserve
            )
            avail = pool.blocks_available() - reserved
            while (
                need > avail
                and self._prefix is not None
                and self._prefix.pop_lru()
            ):
                # an evicted entry frees its blocks only if no live slot
                # still maps them — recompute rather than assume
                avail = pool.blocks_available() - reserved
            if need > avail:
                return False
            reserved += need
            return True

        return gate

    def _release_prefix_entry(self, entry) -> None:
        """PrefixCache eviction hook (paged mode): hand the evicted
        entry's block references back to the allocator; blocks nobody
        else holds return to the free list device-invalidated."""
        blocks, _length = entry
        self.pool.free_stored(blocks)

    def _bucket_for(self, length: int) -> int:
        for b in self._buckets:
            if b >= length:
                return b
        raise AssertionError(
            f"no bucket >= {length} (buckets {self._buckets})"
        )  # unreachable: seq_len is always the last bucket

    def _admission_key(self, out: RequestOutput):
        """Scheduler grouping key: same-bucket requests batch into one
        prefill call; chunked prompts get a unique key (they admit alone
        and proceed chunk-by-chunk).  With bucketing off every short
        prompt shares one key — the legacy path prefills batch-1 per
        request regardless, so splitting by length would only serialize
        admissions across ticks."""
        length = len(out.request.prompt)
        if self._block_len:
            # the prefill covers the prompt's whole blocks (none: no call)
            whole = length // self._block_len * self._block_len
            return ("bucket", self._bucket_for(whole) if whole else 0)
        if self._chunk_tokens is not None and length > self._chunk_tokens:
            if self._unified and self._fused_steps > 1:
                # unified tick: chunk starts BATCH — every one admitted
                # this tick claims its slot and rides the same fixed
                # [n_slots, chunk_tokens] dispatch, so long prompts no
                # longer serialize one admission per tick
                return ("chunk",)
            return ("chunk", id(out))
        if self._buckets is None:
            return ("exact",)
        return ("bucket", self._bucket_for(length))

    def _admit_batch(self, admitted: List[RequestOutput]) -> List[StreamEvent]:
        """Route one tick's admissions: chunked prompts start their slot,
        prefix-cache hits run as batched remainder extends (grouped by
        prefix length and remainder bucket), the rest as one padded
        batched prefill (or batch-1 exact calls in legacy mode)."""
        events: List[StreamEvent] = []
        batch: List[RequestOutput] = []
        hit_groups: Dict[Tuple[int, int], list] = {}
        if self.tracer.enabled:
            for out in admitted:
                span = self._queue_spans.pop(out.request.request_id, None)
                if span is not None:
                    span.finish()
        if self._paged:
            return self._admit_batch_paged(admitted)
        if self._block_len:
            self._admit_blocks(admitted)
            return events
        for out in admitted:
            length = len(out.request.prompt)
            if self._chunk_tokens is not None and length > self._chunk_tokens:
                events.extend(self._start_chunked(out))
                continue
            if self._prefix is not None:
                hit = self._prefix.lookup(out.request.prompt, self._buckets)
                if hit is not None:
                    row, plen = hit
                    key = (plen, self._bucket_for(length - plen))
                    hit_groups.setdefault(key, []).append((out, row))
                    continue
            batch.append(out)
        for (plen, width), group in hit_groups.items():
            events.extend(self._admit_prefix_batch(group, plen, width))
        if not batch:
            return events
        if self._buckets is None:
            # legacy exact-length path: batch-1 prefill per request,
            # compiled per distinct prompt length (the PR 1 behavior)
            for out in batch:
                events.extend(self._admit_exact(out))
            return events
        events.extend(self._admit_bucketed(batch))
        return events

    def _admit_exact(self, out: RequestOutput) -> List[StreamEvent]:
        req = out.request
        slot = self.pool.acquire()
        assert slot is not None, "scheduler admitted beyond free slots"
        t0 = self.tracer.now()
        length = len(req.prompt)
        prompt = jnp.asarray(req.prompt, jnp.int32)[None, :]
        positions = jnp.broadcast_to(
            jnp.arange(length, dtype=jnp.int32), (1, length)
        )
        logits, fresh = self._run(
            "prefill", f"{length}x1", self._prefill_fn,
            self.params, prompt, positions,
            jnp.asarray([length - 1], jnp.int32), self._next_rng(),
        )
        self._prefill_shapes.add(("prefill", 1, length))
        self.metrics.record_prefill_call(real=length)
        self.pool.insert(fresh, slot)
        first = self._sample_first(logits, [out])
        if self.tracer.enabled:
            self.tracer.record(
                "prefill", f"slot {slot}", t0, self.tracer.now(),
                request_id=req.request_id, slot=slot, bucket=length,
                cache_hit=False,
            )
        return self._seat([slot], [out], first)

    def _admit_bucketed(
        self, outs: List[RequestOutput]
    ) -> List[StreamEvent]:
        """ONE padded batched prefill for a same-bucket admission group:
        rows pad right to the bucket width, the batch pads to
        ``prefill_batch`` dummy rows (scattered out of range, dropped),
        and every real row's fresh cache scatters into its slot in one
        call.  A group larger than ``prefill_batch`` runs as several such
        calls, so that the group's size never adds a compile shape."""
        nb = self._prefill_batch
        if len(outs) > nb:
            return [
                ev for i in range(0, len(outs), nb)
                for ev in self._admit_bucketed(outs[i:i + nb])
            ]
        t0 = self.tracer.now()
        width = self._bucket_for(max(len(o.request.prompt) for o in outs))
        tokens = np.zeros((nb, width), np.int32)
        lengths = np.ones(nb, np.int32)  # dummy rows: 1 real token
        slots = np.full(nb, self.pool.n_slots, np.int32)  # dummies drop
        for i, out in enumerate(outs):
            prompt = out.request.prompt
            tokens[i, : len(prompt)] = prompt
            lengths[i] = len(prompt)
            slot = self.pool.acquire()
            assert slot is not None, "scheduler admitted beyond free slots"
            slots[i] = slot
        positions, last_idx = padded_prefill_inputs(lengths, width)
        logits, fresh = self._run(
            "prefill", f"{width}x{nb}", self._prefill_fn,
            self.params, jnp.asarray(tokens), positions, last_idx,
            self._next_rng(),
        )
        self._prefill_shapes.add(("prefill", nb, width))
        real = sum(len(o.request.prompt) for o in outs)
        self.metrics.record_prefill_call(real=real, padded=nb * width - real)
        self.pool.scatter(fresh, slots)
        firsts = self._sample_first(logits, outs)
        if self.tracer.enabled:
            # one batched device call fans out to a span per admitted
            # slot (same measured window), plus the batch-level span on
            # the scheduler track
            t1 = self.tracer.now()
            self.tracer.record(
                "prefill_batch", "scheduler", t0, t1, bucket=width, rows=nb,
                requests=len(outs),
            )
            for i, out in enumerate(outs):
                self.tracer.record(
                    "prefill", f"slot {int(slots[i])}", t0, t1,
                    request_id=out.request.request_id, slot=int(slots[i]),
                    bucket=width, cache_hit=False,
                )
        for i, out in enumerate(outs):
            # store BEFORE activating (uniform with the paged path, where
            # immediate retirement wipes the slot's block table)
            self._maybe_store_prefix(out, int(slots[i]))
        return self._seat(slots, outs, firsts)

    def _admit_prefix_batch(
        self, group: List[tuple], prefix_len: int, width: int
    ) -> List[StreamEvent]:
        """Prefix-cache hits sharing (prefix length, remainder bucket):
        stack the stored K/V rows into ONE batch-N cache (positions
        trimmed to the prefix), run every remainder as one padded extend
        call, scatter the completed rows into their slots.  Skips
        recomputing ``prefix_len`` tokens per request AND keeps hits
        batched like cold prefills."""
        t0 = self.tracer.now()
        nb = max(self._prefill_batch, len(group))
        rows = [row for (_, row) in group]
        rows += [rows[0]] * (nb - len(rows))  # dummy rows: dropped slots
        stacked = self.pool.stack_prefix(
            tuple(rows), jnp.int32(prefix_len)
        )
        tokens = np.zeros((nb, width), np.int32)
        rems = np.ones(nb, np.int32)
        slots = np.full(nb, self.pool.n_slots, np.int32)
        for i, (out, _) in enumerate(group):
            rem = out.request.prompt[prefix_len:]
            tokens[i, : len(rem)] = rem
            rems[i] = len(rem)
            slot = self.pool.acquire()
            assert slot is not None, "scheduler admitted beyond free slots"
            slots[i] = slot
        base, last_idx = padded_prefill_inputs(rems, width)
        positions = jnp.where(base >= 0, base + prefix_len, -1)
        logits, ext = self._run(
            "extend", f"{width}x{nb}", self._extend_fn,
            self.params, jnp.asarray(tokens), positions, last_idx,
            jnp.full((nb,), prefix_len, jnp.int32), stacked,
            self._next_rng(),
        )
        self._prefill_shapes.add(("extend", nb, width))
        real = int(rems[: len(group)].sum())
        self.metrics.record_prefill_call(real=real, padded=nb * width - real)
        self.pool.scatter(ext, slots)
        outs = [out for (out, _) in group]
        firsts = self._sample_first(logits, outs)
        if self.tracer.enabled:
            t1 = self.tracer.now()
            self.tracer.record(
                "prefill_batch", "scheduler", t0, t1, bucket=width, rows=nb,
                requests=len(outs), prefix_len=prefix_len,
            )
            for i, out in enumerate(outs):
                self.tracer.record(
                    "prefill", f"slot {int(slots[i])}", t0, t1,
                    request_id=out.request.request_id, slot=int(slots[i]),
                    bucket=width, cache_hit=True, prefix_len=prefix_len,
                )
        for i, out in enumerate(outs):
            # a request hitting on a SHORT prefix may carry a longer
            # bucket-aligned prefix that was LRU-evicted — re-seed it
            # (no-op unless some key is actually new); store BEFORE
            # activating (uniform with the paged path, where immediate
            # retirement wipes the slot's block table)
            self._maybe_store_prefix(out, int(slots[i]))
        return self._seat(slots, outs, firsts)

    def _admit_batch_paged(
        self, admitted: List[RequestOutput]
    ) -> List[StreamEvent]:
        """Paged admission routing: there is no whole-row prefill — every
        prompt (cold or prefix hit) lands DIRECTLY in the shared block
        pool through one batched extend call, grouped by (prefix length,
        remainder width) so one compiled shape serves the group.  A
        prefix hit costs a table pointer write plus refcount bump per
        shared block — ZERO K/V row copies (the fixed-slot layout's
        ``stack_prefix_rows``/``copy_prefix`` economy is gone)."""
        events: List[StreamEvent] = []
        groups: Dict[Tuple[int, int], list] = {}
        # free blocks this tick's admissions are owed (worst case + COW
        # reserve, what the block gate reserved): a radix lookup
        # restoring warm host blocks must leave this much headroom, or a
        # restore could exhaust the pool mid-tick under the seats the
        # gate already promised.  The reserve shrinks as requests are
        # SEATED (begin_slot moves their need into the pool's own
        # entitlement accounting, which blocks_available() already
        # subtracts) — keeping a seated request's need here would
        # double-count it and refuse restores with real headroom.
        def _need(o):
            return (
                self.pool.blocks_needed(
                    len(o.request.prompt) + o.request.max_new_tokens
                )
                + self._cow_reserve
            )

        reserve = sum(_need(o) for o in admitted)
        for out in admitted:
            length = len(out.request.prompt)
            if self._chunk_tokens is not None and length > self._chunk_tokens:
                events.extend(self._start_chunked(out, reserve=reserve))
                # seated inside _start_chunked: its entitlement is now
                # the pool's to account
                reserve -= _need(out)
                continue
            plen, blocks = 0, None
            if self._prefix is not None:
                hit = self._lookup_prefix(
                    out.request.prompt, reserve=reserve
                )
                if hit is not None:
                    blocks, plen = hit
                    # pin: an earlier-processed group's prefix store can
                    # LRU-evict this entry (free_stored -> refcount 0 ->
                    # block reused) before OUR group maps it; the pin is
                    # dropped right after map_prefix
                    self.pool.pin_blocks(blocks)
            width = (
                self._bucket_for(length - plen)
                if self._buckets is not None
                else length - plen  # legacy exact widths, compiled per len
            )
            groups.setdefault((plen, width), []).append((out, blocks))
        for (plen, width), group in groups.items():
            events.extend(self._admit_extend_paged(group, plen, width))
        return events

    def _admit_extend_paged(
        self, group: List[tuple], plen: int, width: int
    ) -> List[StreamEvent]:
        """ONE batched extend for a same-(prefix, width) paged admission
        group: each row maps its shared prefix blocks (refcount bumps, no
        copies), allocates writable blocks for its remainder, and writes
        its remainder K/V straight into the pool through its block-table
        row.  Dummy batch rows pass an all--1 table — every write
        dropped."""
        t0 = self.tracer.now()
        nb = max(self._prefill_batch, len(group))
        tokens = np.zeros((nb, width), np.int32)
        rems = np.ones(nb, np.int32)
        table = np.full((nb, self.pool.max_blocks), -1, np.int32)
        slots: List[int] = []
        for i, (out, blocks) in enumerate(group):
            req = out.request
            rem = req.prompt[plen:]
            tokens[i, : len(rem)] = rem
            rems[i] = len(rem)
            slot = self.pool.acquire()
            assert slot is not None, "scheduler admitted beyond free slots"
            slots.append(slot)
            self.pool.begin_slot(
                slot, len(req.prompt) + req.max_new_tokens,
                cow_reserve=self._cow_reserve,
            )
            if blocks is not None:
                self.pool.map_prefix(slot, blocks, plen)
                self.pool.free_stored(blocks)  # drop the admission pin
            self.pool.ensure_writable(slot, plen, len(req.prompt))
            table[i] = self.pool.block_table[slot]
        base, last_idx = padded_prefill_inputs(rems, width)
        positions = jnp.where(base >= 0, base + plen, -1)
        logits, self.pool.cache = self._run(
            "extend", f"{width}x{nb}", self._extend_fn,
            self.params, jnp.asarray(tokens), positions, last_idx,
            jnp.full((nb,), plen, jnp.int32), self.pool.cache,
            self._next_rng(), jnp.asarray(table),
        )
        self._prefill_shapes.add(("extend", nb, width))
        real = int(rems[: len(group)].sum())
        self.metrics.record_prefill_call(real=real, padded=nb * width - real)
        outs = [out for (out, _) in group]
        firsts = self._sample_first(logits, outs)
        if self.tracer.enabled:
            t1 = self.tracer.now()
            self.tracer.record(
                "prefill_batch", "scheduler", t0, t1, bucket=width, rows=nb,
                requests=len(outs), prefix_len=plen,
            )
            for i, out in enumerate(outs):
                self.tracer.record(
                    "prefill", f"slot {slots[i]}", t0, t1,
                    request_id=out.request.request_id, slot=slots[i],
                    bucket=width, cache_hit=plen > 0, prefix_len=plen,
                )
        for i, out in enumerate(outs):
            # store BEFORE activating: a request finishing on its first
            # token (max_new_tokens=1 / immediate EOS) releases its slot
            # inside _activate's delivery, wiping the block table the
            # snapshot needs
            self._maybe_store_prefix(out, slots[i])
        return self._seat(slots, outs, firsts)

    def _extend_slot(
        self, slot: int, tokens_seq, offset: int, width: int
    ):
        """Extend the slot's row with ``tokens_seq`` (padded right to
        ``width``) writing at cache columns ``offset + [0..)``; returns
        the extension's last real logits.  The fixed-slot pool extracts
        the row and inserts it back; the paged chunk writes straight
        into the shared pool through the slot's table row."""
        take = len(tokens_seq)
        tokens = np.zeros((1, width), np.int32)
        tokens[0, :take] = tokens_seq
        base, last_idx = padded_prefill_inputs([take], width)
        positions = jnp.where(base >= 0, base + offset, -1)
        if self._paged:
            self.pool.ensure_writable(slot, offset, offset + take)
            rows = self.pool.cache
            table = jnp.asarray(self.pool.block_table[slot : slot + 1])
        else:
            rows, table = self.pool.extract(slot), None
        logits, rows = self._run(
            "extend", f"{width}x1", self._extend_fn,
            self.params, jnp.asarray(tokens), positions, last_idx,
            jnp.asarray([offset], jnp.int32), rows, self._next_rng(), table,
        )
        self._prefill_shapes.add(("extend", 1, width))
        if table is None:
            self.pool.insert(rows, slot)
        else:
            self.pool.cache = rows
        return logits

    def _lookup_prefix(self, prompt, reserve: int = 0):
        """Hierarchy-aware prefix probe: the radix tree matches at block
        granularity (restoring warm host-tier blocks only within the
        ``reserve`` headroom the admission gate has not promised away);
        the aligned-LRU cache probes its bucket keys.  Same counted
        hit/miss contract either way."""
        if self._radix is not None:
            return self._radix.lookup(prompt, reserve=reserve)
        return self._prefix.lookup(prompt, self._buckets)

    def _start_chunked(
        self, out: RequestOutput, reserve: int = 0
    ) -> List[StreamEvent]:
        """Claim a slot for a long prompt and run its first chunk (the
        remaining chunks advance one per tick).  A prefix-cache hit seeds
        the slot and the chunking starts at the prefix boundary."""
        slot = self.pool.acquire()
        assert slot is not None, "scheduler admitted beyond free slots"
        offset = 0
        if self._paged:
            self.pool.begin_slot(
                slot,
                len(out.request.prompt) + out.request.max_new_tokens,
                cow_reserve=self._cow_reserve,
            )
            # begin_slot just moved THIS request's need into the pool's
            # entitlement accounting — drop it from the caller's reserve
            # or the lookup's restore headroom double-counts it
            reserve = max(
                0,
                reserve
                - self.pool.blocks_needed(
                    len(out.request.prompt) + out.request.max_new_tokens
                )
                - self._cow_reserve,
            )
        if self._prefix is not None:
            hit = self._lookup_prefix(out.request.prompt, reserve=reserve)
            if hit is not None:
                row, offset = hit
                if self._paged:
                    # O(1) pointer writes; the first chunk's writes into a
                    # shared tail block copy-on-write through
                    # ensure_writable — never O(prefix) row copies
                    self.pool.map_prefix(slot, row, offset)
                else:
                    self.pool.copy_prefix(row, slot, offset)
        if offset == 0 and not self._paged:
            # incremental writes only from here on: invalidate the slot's
            # previous occupant NOW (a whole-row insert never happens);
            # a paged slot needs no clear — release() already
            # device-invalidated its freed blocks' positions
            self.pool.clear(slot)
        out.status = RUNNING
        self._slot_out[slot] = out
        self._chunking[slot] = _ChunkState(out, offset)
        if self._unified and self._fused_steps > 1:
            # the unified tick runs this slot's first chunk inside THIS
            # tick's one dispatch; activation may happen in-device, so
            # the slot's sampling knobs, EOS (and spec caps) must reach
            # the device state before then: as rows of their own where
            # ticks chain, else through the mirrors and a re-upload
            self._mirror_knobs(slot, out)
            if self._chains:
                self._write_rows(
                    [(0, slot, out)], jnp.asarray(np.zeros(1, np.int32)),
                    live=False,
                )
            else:
                self._state_dirty = True
            return []
        return self._advance_chunk(slot)

    def _advance_chunk(self, slot: int) -> List[StreamEvent]:
        """Run ONE chunk of the slot's in-flight prefill; on the final
        chunk, sample the request's first token and activate the slot for
        decode."""
        st = self._chunking[slot]
        prompt = st.out.request.prompt
        take = min(self._chunk_tokens, len(prompt) - st.offset)
        t0 = self.tracer.now()
        chunk_index = st.offset // self._chunk_tokens
        logits = self._extend_slot(
            slot, prompt[st.offset : st.offset + take],
            offset=st.offset, width=self._chunk_tokens,
        )
        st.offset += take
        self.metrics.record_prefill_call(
            chunks=1, real=take, padded=self._chunk_tokens - take
        )
        if self.tracer.enabled:
            self.tracer.record(
                "prefill_chunk", f"slot {slot}", t0, self.tracer.now(),
                request_id=st.out.request.request_id, slot=slot,
                chunk=chunk_index, offset=st.offset,
                final=st.offset >= len(prompt),
            )
        if st.offset < len(prompt):
            return []
        del self._chunking[slot]
        first = self._sample_first(logits, [st.out])
        # store BEFORE activating: immediate retirement inside _activate
        # releases the slot (paged: wipes the table the snapshot needs)
        self._maybe_store_prefix(st.out, slot)
        return self._seat([slot], [st.out], first)

    def _maybe_store_prefix(self, out: RequestOutput, slot: int) -> None:
        """Seed the prefix cache from a freshly prefilled slot row (every
        bucket-aligned proper prefix of the prompt, first writer wins).
        The extract only runs when at least one key would be new."""
        if self._prefix is None:
            return
        prompt = tuple(int(t) for t in out.request.prompt)
        if self._radix is not None:
            # radix store: index the prompt's FULL blocks — every block
            # boundary becomes a shareable match point (any-prefix hits),
            # and full-blocks-only keeps sharers' writes off shared
            # blocks entirely (no COW reserve).  snapshot_blocks hands
            # one reference per block; the tree keeps refs for NEW nodes
            # and returns the duplicates for release.
            bt = self.pool.block_tokens
            full = (len(prompt) // bt) * bt
            if full <= 0 or self._radix.covers(prompt, full):
                return
            blocks = self.pool.snapshot_blocks(slot, full)
            dupes = self._radix.insert(prompt[:full], blocks)
            if dupes:
                self.pool.free_stored(dupes)
            return
        if all(
            b >= len(prompt) or prompt[:b] in self._prefix
            for b in self._buckets
        ):
            return
        if self._paged:
            # per-key refcounted block snapshots — NO K/V copies: the
            # owner's next write into a snapshotted block copy-on-writes
            # away, so stored prefixes are immutable from this moment
            for b in self._buckets:
                if b >= len(prompt) or prompt[:b] in self._prefix:
                    continue
                blocks = self.pool.snapshot_blocks(slot, b)
                if not self._prefix.store_one(prompt[:b], b, blocks):
                    self.pool.free_stored(blocks)  # lost the store race
            return
        self._prefix.store(prompt, self._buckets, self.pool.extract(slot))

    def _sample_first(self, logits, outs: List[RequestOutput]) -> jax.Array:
        """Sample each admitted request's FIRST token from its prefill
        logits, on the device and left there (rows beyond ``outs`` are a
        padded batch's dummies — sampled greedily and discarded)."""
        nb = logits.shape[0]
        temp = np.zeros(nb, np.float32)
        topk = np.zeros(nb, np.int32)
        topp = np.zeros(nb, np.float32)
        for i, out in enumerate(outs):
            sp = out.request.sampling
            temp[i], topk[i], topp[i] = sp.temperature, sp.top_k, sp.top_p
        return self._run(
            None, "", self._sample_fn,
            logits,
            self._next_rng(),
            jnp.asarray(temp),
            jnp.asarray(topk),
            jnp.asarray(topp),
        )

    def _seat(
        self, slots, outs: List[RequestOutput], first: jax.Array
    ) -> List[StreamEvent]:
        """Commit admitted requests to their slots (``slots[i]`` takes
        ``outs[i]``, its first token ``first[i]`` still on the device).
        Where ticks chain, the rows go into the device's slot state
        (:func:`_seat_rows`) and the first tokens travel with this
        launch's tick, to be delivered at its collect: no event yet.  The
        other engines read them back now and deliver."""
        if not self._chains:
            first = np.asarray(  # host-sync: these engines do not chain
                first
            )
            return [
                self._activate(int(slot), out, int(first[i]))
                for i, (slot, out) in enumerate(zip(slots, outs))
            ]
        # any sequence of token ids will do (tests/benchmarks doctors them)
        first = jnp.asarray(first, jnp.int32)
        rows = [
            (i, int(slot), out)
            for i, (slot, out) in enumerate(zip(slots, outs))
        ]
        for _, slot, out in rows:
            self._occupy(slot, out)
        self._write_rows(rows, first, live=True)
        self._newest_tick.firsts.append((first, rows))
        return []

    def _write_rows(self, rows, first: jax.Array, live: bool) -> None:
        """One :func:`_seat_rows` call: row ``i`` of ``first`` and its
        request's budget, EOS and knobs go to ``slot`` for each ``(i,
        slot, out)``; ``live`` rows decode from their prompt's end, the
        others (a chunked prompt's start) only get their knobs."""
        nb, n = first.shape[0], self.pool.n_slots
        where = np.full(nb, n, np.int32)  # a batch's dummy rows drop
        ints = np.zeros((3, nb), np.int32)  # prompt length, budget, EOS
        knobs = np.zeros((3, nb), np.float32)  # temperature, top-k, top-p
        for i, slot, out in rows:
            req, sp = out.request, out.request.sampling
            where[i] = slot
            eos = -1 if req.eos_token_id is None else req.eos_token_id
            ints[:, i] = len(req.prompt), req.max_new_tokens, eos
            knobs[:, i] = sp.temperature, sp.top_k, sp.top_p
        self._dev_state, self._dev_knobs = _seat_rows(
            self._dev_state, self._dev_knobs, jnp.asarray(where), first,
            *(jnp.asarray(x) for x in ints),
            jnp.asarray(knobs[0]), jnp.asarray(knobs[1].astype(np.int32)),
            jnp.asarray(knobs[2]),
            jnp.asarray((where < n) & live),
        )

    def _occupy(self, slot: int, out: RequestOutput) -> None:
        """The host mirrors of a slot whose request decodes from its
        prompt's end: everything but its current token, which the device
        may still hold."""
        self._pos[slot] = self._widx[slot] = len(out.request.prompt)
        self._mirror_knobs(slot, out)
        self._active[slot] = True
        self._slot_out[slot] = out
        out.status = RUNNING

    def _mirror_knobs(self, slot: int, out: RequestOutput) -> None:
        sp = out.request.sampling
        self._temp[slot] = sp.temperature
        self._topk[slot] = sp.top_k
        self._topp[slot] = sp.top_p
        # per-request speculative cap: None inherits the engine's
        # draft_tokens; an explicit value clamps to it (the verify
        # program is compiled at the engine width — a larger request
        # ask cannot widen it)
        req_k = out.request.draft_tokens
        cap = self._spec_width if req_k is None else min(
            req_k, self._spec_width
        )
        self._spec_max[slot] = cap
        self._spec_k[slot] = cap

    def _activate(
        self, slot: int, out: RequestOutput, tok0: int
    ) -> StreamEvent:
        """Commit an admitted request to its slot on the host: mirrors,
        first-token delivery, and a re-upload before the next tick."""
        self._occupy(slot, out)
        self._state_dirty = True
        return self._first_token(slot, tok0)

    def _first_token(self, slot: int, tok0: int) -> StreamEvent:
        self._tok[slot] = tok0
        self._slot_out[slot].first_token_time = self.clock()
        return self._deliver(slot, tok0)

    def _deliver_firsts(self, tokens, rows) -> List[StreamEvent]:
        """The first tokens of requests a launch seated on the device,
        now on the host; a request cancelled since gets nothing."""
        return [
            self._first_token(slot, int(tokens[i]))
            for i, slot, out in rows
            if self._slot_out[slot] is out
        ]

    def _launch_per_step(self, p: _PendingTick) -> None:
        if self._paged:
            seq_len = self.model.config.seq_len
            for slot in p.entering:
                w = int(self._widx[slot])
                if w < seq_len:
                    self.pool.ensure_writable(slot, w, w + 1)
        nxt, self.pool.cache = self._run(
            "tick", "1", self._decode_fn,
            self.params,
            jnp.asarray(self._tok),
            jnp.asarray(self._pos),
            jnp.asarray(self._widx),
            jnp.asarray(self._temp),
            jnp.asarray(self._topk),
            jnp.asarray(self._topp),
            self.pool.cache,
            self._next_rng(),
            self._device_table(),
        )
        p.kind = "step"
        p.payload = nxt

    def _collect_per_step(self, p: _PendingTick) -> List[StreamEvent]:
        nxt = p.payload  # synced by collect(); t1 is real device time
        events = []
        trace = self.tracer.enabled
        t1 = p.t1
        if trace:
            self.tracer.record("decode_tick", "scheduler", p.t0, t1)
        # every slot's current token was just written into the cache;
        # advance even the slots that retire on this token's delivery
        for slot in p.entering:
            if not self._active[slot]:
                # an earlier slot's on_token callback cancel()ed this one
                # mid-loop: its slot is released, nothing to deliver
                continue
            if trace:
                out = self._slot_out[slot]
                self.tracer.record(
                    "decode", f"slot {slot}", p.t0, t1,
                    request_id=out.request.request_id, slot=slot,
                    token_index=len(out.tokens),
                )
            self._pos[slot] += 1
            self._widx[slot] += 1
            self._tok[slot] = int(nxt[slot])
            events.append(self._deliver(slot, int(nxt[slot])))
        # DELIVERED tokens (== the spec tick's numerator): a slot
        # cancelled mid-loop by a stream callback contributes nothing
        self.metrics.record_dispatch(tokens=len(events))
        return events

    def _upload_slot_state(self) -> None:
        """Rebuild the device-resident slot-state arrays from the host
        mirrors, with nothing in flight.  Where ticks chain it runs at
        the head of a launch, after a removal the device could not know
        of (cancel, an integrity trip) and on the first tick; on the
        paged and speculative engines after every admission and release.
        Otherwise the fused tick re-donates the arrays the previous tick
        returned.  Budget and EOS derive from the live request
        records (budget = remaining new tokens; EOS -1 = no stop id);
        mid-chunked-prefill slots contribute their EOS too — the unified
        tick's in-device activation checks it before the host ever sees
        the first token.  Spec-fused engines additionally carry each
        slot's adaptive draft length and its token HISTORY row (prompt +
        delivered tokens — the in-scan drafter's context)."""
        n = self.pool.n_slots
        if self._block_len:
            self._upload_block_state()
            return
        budget = np.zeros(n, np.int32)
        eos = np.full(n, -1, np.int32)
        for slot in np.nonzero(self._active)[0]:
            out = self._slot_out[slot]
            budget[slot] = out.request.max_new_tokens - len(out.tokens)
            if out.request.eos_token_id is not None:
                eos[slot] = int(out.request.eos_token_id)
        for slot, st in self._chunking.items():
            if st.out.request.eos_token_id is not None:
                eos[slot] = int(st.out.request.eos_token_id)

        if self._spec_fused:
            seq_len = self.model.config.seq_len
            hist = np.zeros((n, seq_len), np.int32)
            for slot, out in enumerate(self._slot_out):
                if out is None:
                    continue
                ctx = list(out.request.prompt)
                if self._active[slot]:
                    ctx = ctx + out.tokens
                ctx = ctx[:seq_len]
                hist[slot, : len(ctx)] = ctx
            self._dev_state, self._dev_knobs = _own_arrays((
                (
                    self._tok, self._pos, self._widx, self._active,
                    budget, self._spec_k, hist,
                ),
                (
                    eos, self._temp, self._topk, self._topp,
                    self._spec_max,
                ),
            ))
            self._state_dirty, self._flush_cause = False, None
            return
        # one jitted call producing XLA-OWNED buffers (never zero-copy
        # views of the host mirrors — see _own_arrays for why donating
        # a borrowed buffer corrupts live state)
        self._dev_state, self._dev_knobs = _own_arrays((
            (self._tok, self._pos, self._widx, self._active, budget),
            (eos, self._temp, self._topk, self._topp),
        ))
        self._state_dirty, self._flush_cause = False, None

    def _ensure_decode_writable(self, p: _PendingTick, width: int) -> None:
        """Paged launches (nothing to do on the fixed-slot pool, whose
        columns are the slot's own): make every column this tick CAN
        write writable up front (budget-clamped so a finishing slot
        never draws blocks beyond its admission entitlement); the table
        then rides the
        scan's inputs loop-invariant — steady-state ticks re-upload
        nothing and the compile count stays pinned.  ``width`` is the
        tick's worst-case per-slot column advance (T decode steps, or
        T * (K + 1) verify columns).  Paged ticks never launch ahead:
        the window is read off host mirrors that a tick in flight would
        leave a width behind."""
        if not self._paged:
            return
        seq_len = self.model.config.seq_len
        for slot in p.entering:
            out = self._slot_out[slot]
            if out is None:
                continue
            w = int(self._widx[slot])
            rem = out.request.max_new_tokens - len(out.tokens)
            end = min(w + min(width, max(rem, 0)), seq_len)
            self.pool.ensure_writable(slot, w, end)
        for slot, out in p.finals:
            # a chunk completing this tick activates in-device and
            # decodes from its prompt length immediately
            plen = len(out.request.prompt)
            end = min(
                plen + min(width, out.request.max_new_tokens), seq_len
            )
            self.pool.ensure_writable(slot, plen, end)

    def _launch_fused(self, p: _PendingTick) -> None:
        """Dispatch one FUSED decode tick: ``_fused_steps`` masked decode
        steps in one jitted lax.scan with the cache and slot-state
        buffers donated (:func:`_fused_decode_core`)."""
        if self._state_dirty or self._dev_state is None:
            self._upload_slot_state()
        self._ensure_decode_writable(p, self._fused_steps)
        block, counts, self._dev_state, self.pool.cache = self._run(
            "tick", str(self._fused_steps), self._fused_fn,
            self.params, self._dev_state, self._dev_knobs, self.pool.cache,
            self._next_rng(), self._device_table(),
        )
        p.kind = "fused"
        p.payload = (block, counts)

    def _build_chunk_block(self, p: _PendingTick):
        """Fold every in-flight chunked prefill into this tick's unified
        dispatch: build the fixed ``[n_slots, chunk_tokens]`` right-padded
        input block (pad positions -1 via ``clen``), advance each slot's
        offset, and mark the slots whose chunk COMPLETES the prompt —
        their activation happens in-device and the host finishes the
        bookkeeping at collect.  Paged slots make their chunk's write
        range writable here (launch side, before the dispatch)."""
        cfg = self.model.config
        n, width = self.pool.n_slots, self._chunk_tokens
        ctoks = np.zeros((n, width), np.int32)
        clen = np.zeros(n, np.int32)
        cstart = np.full(n, cfg.seq_len, np.int32)
        cfinal = np.zeros(n, bool)
        cbudget = np.ones(n, np.int32)
        consumed = 0
        for slot in sorted(self._chunking):
            st = self._chunking[slot]
            prompt = st.out.request.prompt
            take = min(width, len(prompt) - st.offset)
            ctoks[slot, :take] = prompt[st.offset : st.offset + take]
            clen[slot] = take
            cstart[slot] = st.offset
            if self._paged:
                self.pool.ensure_writable(slot, st.offset, st.offset + take)
            p.chunk_spans.append((
                slot, st.out.request.request_id,
                st.offset // width, st.offset + take,
                st.offset + take >= len(prompt),
            ))
            st.offset += take
            consumed += take
            if st.offset >= len(prompt):
                cfinal[slot] = True
                cbudget[slot] = st.out.request.max_new_tokens
                p.finals.append((slot, st.out))
        for slot, out in p.finals:
            # activated in-device by this dispatch: the mirrors follow
            # now, but for the first token, which collect brings
            del self._chunking[slot]
            self._occupy(slot, out)
        p.chunk_tokens = consumed
        # chunk operands are per-tick uploads, never donated — plain
        # device puts are safe (no ownership hazard to launder)
        return (
            jnp.asarray(ctoks), jnp.asarray(clen), jnp.asarray(cstart),
            jnp.asarray(cfinal), jnp.asarray(cbudget),
        )

    def _launch_unified(self, p: _PendingTick) -> None:
        """Dispatch one UNIFIED ragged tick: the chunk phase (every
        mid-prefill slot's next chunk, in-device final-chunk activation)
        plus the fused decode scan, as ONE jitted call
        (:func:`_unified_tick_core`) — the tick that used to cost one
        extend dispatch per chunk slot plus the decode dispatch."""
        if self._state_dirty or self._dev_state is None:
            self._upload_slot_state()
        chunk_ops = self._build_chunk_block(p)
        self._ensure_decode_writable(p, self._fused_steps)
        out = self._run(
            "tick_chunk", f"{self._fused_steps}+{self._chunk_tokens}",
            self._unified_fn,
            self.params, self._dev_state, self._dev_knobs, chunk_ops,
            self.pool.cache, self._next_rng(), self._device_table(),
        )
        act_emit, block, counts, self._dev_state, self.pool.cache = out
        p.kind = "unified"
        p.payload = (act_emit, block, counts)

    def _collect_chunks(self, p: _PendingTick, t1: float) -> None:
        """Collect-side chunk bookkeeping shared by the unified tick
        kinds: tracer spans and the chunk-continuation tally — counting
        only chunks FOLDED into this tick's dispatch (``chunk_spans``),
        so a per-phase spec-fused tick, whose chunks already counted
        through ``_advance_chunk``'s ``record_prefill_call``, never
        double-tallies."""
        if p.chunk_spans and self.tracer.enabled:
            for slot, rid, idx, offset, final in p.chunk_spans:
                self.tracer.record(
                    "prefill_chunk", f"slot {slot}", p.t0, t1,
                    request_id=rid, slot=slot, chunk=idx, offset=offset,
                    final=final,
                )
        if p.chunk_spans:
            self.metrics.record_chunks(len(p.chunk_spans))
            # the chunk block is [n_slots, chunk]: every slot rides along
            self.metrics.record_prefill_tokens(
                p.chunk_tokens,
                self.pool.n_slots * self._chunk_tokens - p.chunk_tokens,
            )

    def _check_progress(self, p: _PendingTick, counts) -> None:
        """The no-progress desync guard: a slot that was decode-live at
        LAUNCH always enters the scan live with budget >= 1, so zero
        progress means the device state desynced from the host mirrors —
        fail loudly instead of spinning run() forever.  Scoped to
        ``p.entering`` (decode-live AT LAUNCH, still active now): a tick
        holding only mid-chunk prefill rows has no entering slots, so
        pure chunk advancement counts as progress instead of tripping
        the guard (the unified tick's chunk-only regression), and a
        pipelined tick's stale mirror of a slot that finished in flight
        is skipped via the activity re-check, or the owner's where the
        slot has a new tenant by now."""
        stuck = [
            s for s in p.entering
            if counts[s] == 0 and self._active[s]
            and self._slot_out[s] is p.owners[s]
        ]
        if stuck:
            raise RuntimeError(
                f"fused tick made no progress on active slots {stuck} "
                f"(device live={np.asarray(self._dev_state[3])}, "
                f"budget={np.asarray(self._dev_state[4])}, "
                f"chunk tokens advanced={p.chunk_tokens}) — slot state "
                "desynced from host mirrors"
            )

    def _deliver_block(self, p: _PendingTick, block, counts, t1):
        """Deliver a fused/unified tick's ``[T, n_slots]`` token block
        through the per-token delivery path."""
        events: List[StreamEvent] = []
        trace = self.tracer.enabled
        for slot in np.nonzero(self._active)[0]:
            c = int(counts[slot])
            # re-check liveness: a stream callback may have cancel()ed
            # this slot (releasing it, _slot_out -> None) while an
            # earlier slot's tokens were being delivered; and a tenant
            # seated after this tick's dispatch has nothing in it
            if (
                c == 0 or not self._active[slot]
                or self._slot_out[slot] is not p.owners[slot]
            ):
                continue
            if trace:
                out = self._slot_out[slot]
                self.tracer.record(
                    "decode", f"slot {int(slot)}", p.t0, t1,
                    request_id=out.request.request_id, slot=int(slot),
                    token_index=len(out.tokens), tokens=c,
                )
            # host mirrors advance by the slot's full progress BEFORE
            # delivery (delivery may finish the request and release the
            # slot, which parks the mirror at seq_len again)
            self._pos[slot] += c
            self._widx[slot] += c
            self._tok[slot] = int(block[c - 1, slot])
            for t in range(c):
                event = self._deliver(int(slot), int(block[t, slot]))
                events.append(event)
                if event.finish_reason == FAIL_INTEGRITY:
                    # the sentinel tripped mid-block: the scan kept
                    # running (liveness is in-carry), but everything
                    # after non-finite logits is garbage by definition
                    break
                if event.finished and t != c - 1:
                    # the scan stopped emitting AT the finish: the
                    # device's EOS/budget logic and _deliver's must agree
                    # token-for-token, or tokens would silently vanish
                    raise AssertionError(
                        f"slot {slot}: host finished at token {t + 1} of "
                        f"a {c}-token device block"
                    )
                if not self._active[slot]:
                    # finished naturally, or the on_token callback
                    # cancelled the request mid-block: the surplus
                    # device tokens die with the released slot
                    break
        return events

    def _collect_fused(self, p: _PendingTick) -> List[StreamEvent]:
        """Collect one fused decode tick: ONE device->host sync per T
        decode steps — the whole point — then the per-token delivery
        path.  Greedy output is bitwise identical to the per-step tick;
        streaming granularity becomes per-tick (at most
        ``decode_steps_per_tick`` tokens per event flush)."""
        block, counts = p.payload
        self._check_progress(p, counts)
        trace = self.tracer.enabled
        t1 = p.t1
        if trace:
            self.tracer.record(
                "decode_tick", "scheduler", p.t0, t1,
                steps=self._fused_steps, tokens=int(counts.sum()),
            )
        events = self._deliver_block(p, block, counts, t1)
        # DELIVERED tokens, not counts.sum(): cancelled slots' surplus
        # device tokens are dropped above, and every tick type keeps
        # the same amortization numerator (see record_dispatch docstring)
        self.metrics.record_dispatch(tokens=len(events))
        return events

    def _deliver_finals(self, p: _PendingTick, act_emit) -> List[StreamEvent]:
        """Finish the unified tick's in-device activations on the host:
        the device sampled each first token, flipped the slot live and
        advanced its state, and the launch moved the mirrors
        (:meth:`_build_chunk_block`) — deliver the first token, but to no
        request cancelled since."""
        return [
            self._first_token(slot, int(act_emit[slot]))
            for slot, out in p.finals
            if self._slot_out[slot] is out
        ]

    def _collect_unified(self, p: _PendingTick) -> List[StreamEvent]:
        """Collect one unified ragged tick: sync the activation row and
        the decode block together (still ONE sync), deliver activations
        first (the per-phase engine's chunk-advance-then-decode order),
        then the decode block."""
        act_emit, block, counts = p.payload
        events: List[StreamEvent] = []
        trace = self.tracer.enabled
        t1 = p.t1
        self._collect_chunks(p, t1)
        events.extend(self._deliver_finals(p, act_emit))
        self._check_progress(p, counts)
        if trace:
            self.tracer.record(
                "decode_tick", "scheduler", p.t0, t1,
                steps=self._fused_steps, tokens=int(counts.sum()),
                chunk_tokens=p.chunk_tokens,
            )
        events.extend(self._deliver_block(p, block, counts, t1))
        delivered = sum(1 for ev in events if ev.token >= 0)
        self.metrics.record_dispatch(tokens=delivered)
        # unified-tick amortization: prompt chunk tokens consumed +
        # tokens this ONE dispatch delivered (activations included;
        # admission-prefill events live outside this local list)
        self.metrics.record_unified_tick(p.chunk_tokens + delivered)
        return events

    def _launch_spec_step(self, p: _PendingTick) -> None:
        """Dispatch one speculative verify tick: draft per active slot
        (host-side, capped by the adaptive length, the slot's remaining
        token budget, and seq_len), then ONE multi-token verify forward
        over every slot's block.

        Per-slot variable acceptance rides the FIXED compiled width: short
        drafts pad with -1 positions (columns invalidated, never
        attended), inactive and mid-chunked-prefill slots park their whole
        block at column seq_len exactly as on the plain decode tick.  A
        request whose budget or EOS lands mid-block truncates delivery
        there — the surplus accepted K/V beyond the finish is dead weight
        in a slot that is being released anyway.
        """
        cfg = self.model.config
        k = self._spec_width
        n = self.pool.n_slots
        drafts = np.zeros((n, k), np.int32)
        dlen = np.zeros(n, np.int32)
        active = p.entering
        for slot in active:
            out = self._slot_out[slot]
            # rem >= 1 for an active slot; draft_for_row clamps so a
            # block never overshoots the budget or writes out of range
            d = draft_for_row(
                self._drafter,
                list(out.request.prompt) + out.tokens,
                int(self._spec_k[slot]),
                int(self._widx[slot]),
                cfg.seq_len,
                out.request.max_new_tokens - len(out.tokens),
            )
            dlen[slot] = len(d)
            drafts[slot, : len(d)] = d
        if self._paged:
            # the verify writes current token + dlen draft columns;
            # draft_for_row already clamped dlen inside the budget, so
            # the range never overdraws the slot's block entitlement
            for slot in active:
                w = int(self._widx[slot])
                self.pool.ensure_writable(
                    int(slot),
                    w,
                    min(w + int(dlen[slot]) + 1, cfg.seq_len),
                )
        block, accepted, self.pool.cache = self._run(
            "tick", f"1x{k + 1}", self._verify_fn,
            self.params,
            jnp.asarray(self._tok),
            jnp.asarray(drafts),
            jnp.asarray(dlen),
            jnp.asarray(self._pos),
            jnp.asarray(self._widx),
            jnp.asarray(self._temp),
            jnp.asarray(self._topk),
            jnp.asarray(self._topp),
            self.pool.cache,
            self._next_rng(),
            self._device_table(),
        )
        p.kind = "spec"
        p.payload = (block, accepted, dlen)

    def _collect_spec_step(self, p: _PendingTick) -> List[StreamEvent]:
        """Collect one speculative verify tick: one sync, then deliver
        each slot's accepted prefix + bonus token (truncated at a
        mid-block EOS/length finish)."""
        k = self._spec_width
        block, accepted, dlen = p.payload
        events = []
        trace = self.tracer.enabled
        t1 = p.t1
        if trace:
            self.tracer.record(
                "verify_tick", "scheduler", p.t0, t1, width=k
            )
        for slot in p.entering:
            if not self._active[slot]:
                # an earlier slot's on_token callback cancel()ed this one
                # mid-loop: slot released, its accepted block dies with it
                continue
            a = int(accepted[slot])
            drafted = int(dlen[slot])
            if trace:
                out = self._slot_out[slot]
                self.tracer.record(
                    "verify", f"slot {int(slot)}", p.t0, t1,
                    request_id=out.request.request_id, slot=int(slot),
                    draft_k=drafted, accepted=a,
                    token_index=len(out.tokens),
                )
            # current token + a accepted drafts entered the cache; the
            # bonus (block[a]) is the new current token, written next tick
            self._pos[slot] += a + 1
            self._widx[slot] += a + 1
            self._tok[slot] = int(block[slot, a])
            delivered = 0
            for tok in block[slot, : a + 1]:
                event = self._deliver(int(slot), int(tok))
                events.append(event)
                delivered += 1
                if event.finished:
                    break  # EOS/length mid-block: drop the surplus
            self.metrics.record_spec(
                drafted=drafted,
                accepted=a,
                wasted=(k + 1) - delivered,
            )
            if (
                self._spec_adaptive
                and self._active[slot]
                and self._spec_max[slot] > 0
            ):
                self._spec_k[slot] = adapt_draft_len(
                    int(self._spec_k[slot]), drafted, a,
                    int(self._spec_max[slot]),
                )
            if self._spec_check:
                self.pool.assert_slot_aligned(int(slot))
        self.metrics.record_dispatch(tokens=len(events))
        return events

    def _launch_spec_fused(
        self, p: _PendingTick, unified_chunks: bool
    ) -> None:
        """Dispatch one FUSED speculative tick: ``_fused_steps``
        draft-verify-accept blocks in one jitted lax.scan, drafting
        in-scan from the device-resident token history
        (:func:`_fused_spec_core`); with chunk work this tick, the
        unified chunk phase rides in front (:func:`_unified_spec_core`)
        — chunks, activation, drafting, verify and acceptance in ONE
        dispatch."""
        if self._state_dirty or self._dev_state is None:
            self._upload_slot_state()
        chunk_ops = (
            self._build_chunk_block(p) if unified_chunks else None
        )
        self._ensure_decode_writable(
            p, self._fused_steps * (self._spec_width + 1)
        )
        # the unified program takes the chunk operands after the knobs
        # and returns the activation row first; else they are one call
        steps = f"{self._fused_steps}x{self._spec_width + 1}"
        if chunk_ops is None:
            fn, chunk, first = self._spec_fused_fn, (), (None,)
            kind = "tick"
        else:
            fn, chunk, first = self._spec_unified_fn, (chunk_ops,), ()
            kind, steps = "tick_chunk", f"{steps}+{self._chunk_tokens}"
        *out, self._dev_state, self.pool.cache = self._run(
            kind, steps, fn,
            self.params, self._dev_state, self._dev_knobs, *chunk,
            self.pool.cache, self._next_rng(), self._device_table(),
        )
        p.kind = "spec_fused"
        # (act_emit, blocks, counts, drafted, accepted)
        p.payload = (*first, *out)

    def _collect_spec_fused(self, p: _PendingTick) -> List[StreamEvent]:
        """Collect one fused speculative tick: ONE sync per T verify
        blocks, then per-block delivery — each block's accepted prefix +
        bonus, truncated at a mid-block finish, with the host replaying
        the same adaptation law the scan applied so the ``_spec_k``
        mirrors stay exact."""
        k = self._spec_width
        act_emit, blocks, counts, drafted, accepted = p.payload
        events: List[StreamEvent] = []
        trace = self.tracer.enabled
        t1 = p.t1
        self._collect_chunks(p, t1)
        events.extend(self._deliver_finals(p, act_emit))
        # the per-slot DELIVERED totals play the fused tick's counts role
        self._check_progress(p, counts.sum(axis=0))
        if trace:
            self.tracer.record(
                "verify_tick", "scheduler", p.t0, t1, width=k,
                steps=self._fused_steps, tokens=int(counts.sum()),
                chunk_tokens=p.chunk_tokens,
            )
        for t in range(blocks.shape[0]):
            for slot in range(self.pool.n_slots):
                e = int(counts[t, slot])
                if e == 0 or not self._active[slot]:
                    continue
                a = int(accepted[t, slot])
                d = int(drafted[t, slot])
                if trace:
                    out = self._slot_out[slot]
                    self.tracer.record(
                        "verify", f"slot {slot}", p.t0, t1,
                        request_id=out.request.request_id, slot=slot,
                        draft_k=d, accepted=a,
                        token_index=len(out.tokens),
                    )
                self._pos[slot] += a + 1
                self._widx[slot] += a + 1
                self._tok[slot] = int(blocks[t, slot, a])
                delivered = 0
                for tok in blocks[t, slot, :e]:
                    event = self._deliver(slot, int(tok))
                    events.append(event)
                    delivered += 1
                    if event.finish_reason == FAIL_INTEGRITY:
                        break  # the sentinel: surplus is garbage
                    if event.finished:
                        if delivered != e:
                            # the scan truncated AT the finish: its
                            # EOS/budget law and _deliver's must agree
                            raise AssertionError(
                                f"slot {slot}: host finished at token "
                                f"{delivered} of a {e}-token spec block"
                            )
                        break
                    if not self._active[slot]:
                        break  # cancelled mid-block by a stream callback
                self.metrics.record_spec(
                    drafted=d, accepted=a, wasted=(k + 1) - delivered,
                )
                if (
                    self._spec_adaptive
                    and self._active[slot]
                    and self._spec_max[slot] > 0
                ):
                    # replay the scan's adaptation law on the mirrors
                    self._spec_k[slot] = adapt_draft_len(
                        int(self._spec_k[slot]), d, a,
                        int(self._spec_max[slot]),
                    )
                if self._spec_check:
                    self.pool.assert_slot_aligned(slot)
        delivered = sum(1 for ev in events if ev.token >= 0)
        self.metrics.record_dispatch(tokens=delivered)
        if act_emit is not None:
            # the UNIFIED spec dispatch (chunk phase folded in) — the
            # pure fused verify scan is dispatch-amortized but not a
            # unified ragged tick, so it keeps no series here
            self.metrics.record_unified_tick(p.chunk_tokens + delivered)
        return events

    # -- generation by diffusion over blocks --------------------------------

    def _upload_block_state(self) -> None:
        """A block model's device-resident slot state: made once, all
        slots dead; after a host-side removal (cancel, an integrity trip,
        nothing in flight) the device's own state with the removed slots
        dead.  The block in progress exists only on the device, so there
        is no rebuilding it from host mirrors, and no need."""
        n, width = self.pool.n_slots, self._block_len
        if self._dev_state is None:
            ints = np.zeros(n, np.int32)
            self._dev_state, self._dev_knobs = _own_arrays((
                (
                    np.zeros((n, width), np.int32),
                    np.zeros((n, width), bool),
                    np.full((n, width), -1, np.int32),
                    ints, ints, ints, np.zeros(n, bool), ints,
                    np.zeros((n, width), np.int32), np.zeros(n, bool),
                ),
                (
                    np.full(n, -1, np.int32), np.zeros(n, np.float32),
                    np.full(n, width, np.int32), np.zeros(n, np.float32),
                ),
            ))
        else:
            self._dev_state = _block_state_alive(
                self._dev_state, jnp.asarray(self._active)
            )
        self._state_dirty, self._flush_cause = False, None

    def _admit_blocks(self, outs: List[RequestOutput]) -> None:
        """Seat one tick's admissions of a block model (one bucket's group,
        as the scheduler grouped them): ONE padded prefill call a
        ``prefill_batch`` rows over each prompt's WHOLE blocks (none for a
        prompt shorter than a block: its slot needs no clearing, see
        :func:`~tpu_parallel.models.generate.block_step`), no logits and no
        first token, then the rows into the device's slot state with the
        prompt's tail seated in the first block."""
        size, nb = self._block_len, self._prefill_batch
        for i in range(0, len(outs), nb):
            group = outs[i:i + nb]
            t0 = self.tracer.now()
            whole = [len(o.request.prompt) // size * size for o in group]
            slots = np.full(nb, self.pool.n_slots, np.int32)  # dummies drop
            for j in range(len(group)):
                slot = self.pool.acquire()
                assert slot is not None, "scheduler admitted beyond free slots"
                slots[j] = slot
            width = self._bucket_for(max(whole)) if max(whole) else 0
            if width:
                tokens = np.zeros((nb, width), np.int32)
                lengths = np.full(nb, size, np.int32)  # dummy rows: a block
                for j, out in enumerate(group):
                    tokens[j, : whole[j]] = out.request.prompt[: whole[j]]
                    lengths[j] = whole[j]
                positions, _ = padded_prefill_inputs(lengths, width)
                fresh = self._run(
                    "prefill", f"{width}x{nb}", self._block_prefill_fn,
                    self.params, jnp.asarray(tokens), positions,
                )[0]
                self._prefill_shapes.add(("prefill", nb, width))
                self.metrics.record_prefill_call(
                    real=sum(whole), padded=nb * width - sum(whole)
                )
                self.pool.scatter(fresh, slots)
            rows = [
                (j, int(slots[j]), out) for j, out in enumerate(group)
            ]
            self._seat_blocks(rows, whole)
            if self.tracer.enabled:
                t1 = self.tracer.now()
                for j, slot, out in rows:
                    self.tracer.record(
                        "prefill", f"slot {slot}", t0, t1,
                        request_id=out.request.request_id, slot=slot,
                        bucket=width, cache_hit=False,
                    )

    def _seat_blocks(self, rows, whole) -> None:
        """One :func:`_seat_block_rows` call for ``(i, slot, out)`` rows
        whose prefill covered ``whole[i]`` positions."""
        size, nb, n = self._block_len, self._prefill_batch, self.pool.n_slots
        where = np.full(nb, n, np.int32)
        blk = np.full((nb, size), self.model.config.mask_token_id, np.int32)
        ints = np.zeros((5, nb), np.int32)  # tail, start, budget, EOS, steps
        ints[4] = size
        reals = np.zeros((2, nb), np.float32)  # temperature, threshold
        for i, slot, out in rows:
            req = out.request
            tail = list(req.prompt[whole[i]:])
            where[i] = slot
            blk[i, : len(tail)] = tail
            eos = -1 if req.eos_token_id is None else req.eos_token_id
            steps = size if req.denoising_steps is None else req.denoising_steps
            ints[:, i] = len(tail), whole[i], req.max_new_tokens, eos, steps
            reals[:, i] = req.sampling.temperature, req.confidence_threshold
            self._occupy(slot, out)
        self._dev_state, self._dev_knobs = _seat_block_rows(
            self._dev_state, self._dev_knobs, jnp.asarray(where),
            jnp.asarray(blk), jnp.asarray(ints[0]), jnp.asarray(ints[1]),
            jnp.asarray(ints[2]), jnp.asarray(ints[3]), jnp.asarray(reals[0]),
            jnp.asarray(ints[4]), jnp.asarray(reals[1]),
        )

    def _launch_block(self, p: _PendingTick) -> None:
        """Dispatch one tick of a block model: ``_fused_steps`` forwards of
        every slot's current block in one jitted scan
        (:func:`_block_decode_core`), cache and slot state donated."""
        if self._state_dirty or self._dev_state is None:
            self._upload_slot_state()
        out = self._run(
            "tick", str(self._fused_steps), self._block_fn,
            self.params, self._dev_state, self._dev_knobs, self.pool.cache,
            self._next_rng(), self._device_table(),
        )
        *payload, self._dev_state, self.pool.cache = out
        p.kind = "block"
        p.payload = tuple(payload)

    def _collect_block(self, p: _PendingTick) -> List[StreamEvent]:
        """Collect one tick of a block model: a block's tokens go to the
        stream in order at the collect of the tick that completed it, one
        event a token, and arrive TOGETHER (one timestamp a block).  A slot
        may have filled positions all tick and completed no block: no
        progress guard reads token counts here."""
        blocks, counts, fsteps, kinds, fills = p.payload
        events: List[StreamEvent] = []
        trace = self.tracer.enabled
        mine = np.array([
            self._active[s] and self._slot_out[s] is p.owners[s]
            for s in range(self.pool.n_slots)
        ])
        # the window's work by what the device did, for the slots whose
        # owner launched this tick (a later tenant has nothing in it); a
        # slot that waited was live, and its pad rows rode the forward
        self.metrics.record_block_steps(
            forwards=int((kinds[:, mine] > 0).sum()),
            commits=int((kinds[:, mine] == BLOCK_CARRIED).sum()),
            waits=int((kinds[:, mine] == BLOCK_WAITED).sum()),
            filled=int(fills[:, mine].sum()),
            completed=int((counts[:, mine] > 0).sum()),
        )
        if trace:
            self.tracer.record(
                "decode_tick", "scheduler", p.t0, p.t1,
                steps=self._fused_steps, tokens=int(counts.sum()),
            )
        for slot in np.nonzero(mine)[0]:
            slot = int(slot)
            for t in np.nonzero(counts[:, slot])[0]:
                if not self._active[slot]:
                    break  # finished, or a stream callback cancelled it
                keep = blocks[t, slot] != -1
                tokens = blocks[t, slot][keep]
                steps = fsteps[t, slot][keep]
                out = self._slot_out[slot]
                if trace:
                    self.tracer.record(
                        "decode", f"slot {slot}", p.t0, p.t1,
                        request_id=out.request.request_id, slot=slot,
                        token_index=len(out.tokens), tokens=len(tokens),
                    )
                now = self.clock()
                out.token_groups.append(len(out.tokens))
                if out.first_token_time is None:
                    out.first_token_time = now
                self._pos[slot] += len(tokens)
                self._widx[slot] = self._pos[slot]
                for token, step in zip(tokens, steps):
                    if token != NON_FINITE_TOKEN:
                        out.fill_steps.append(int(step))
                    event = self._deliver(slot, int(token), now=now)
                    events.append(event)
                    if not self._active[slot]:
                        break
        self.metrics.record_dispatch(tokens=len(events))
        return events

    def _fail_integrity(self, slot: int) -> StreamEvent:
        """The device sampled the NaN/Inf sentinel for this slot: fail
        the request TYPED (``FAIL_INTEGRITY``) and release the slot —
        the one thing this path must never do is deliver a token.  The
        cluster's :class:`ReplicaHandle` escalates the replica to
        DEGRADED health on the trip, so routers deprioritize an engine
        producing non-finite logits while its in-flight peers finish."""
        out = self._slot_out[slot]
        req = out.request
        self.release_slot(slot)
        self._flush("integrity")
        out.status = FAILED
        out.finish_reason = FAIL_INTEGRITY
        out.detail = (
            "non-finite logits (NaN/Inf) at sampling — refusing to "
            "stream garbage tokens"
        )
        out.finish_time = self.clock()
        self.integrity_trips += 1
        self.metrics.record_integrity_trip()
        if self.tracer.enabled:
            self.tracer.instant(
                "integrity_trip", track=f"slot {slot}",
                request_id=req.request_id,
            )
        event = StreamEvent(
            request_id=req.request_id,
            token=-1,
            index=-1,
            finished=True,
            finish_reason=FAIL_INTEGRITY,
        )
        if req.on_token is not None:
            req.on_token(event)
        return event

    def _deliver(
        self, slot: int, token: int, now: Optional[float] = None
    ) -> StreamEvent:
        """Record one generated token for the request in ``slot``; retire
        the slot when the token finishes the request (EOS or length).
        The device-side sentinel (``NON_FINITE_TOKEN``) never counts as
        a token: it reroutes to the typed integrity failure.  ``now``: the
        arrival time of a group of tokens delivered together (a block)."""
        if token == NON_FINITE_TOKEN:
            return self._fail_integrity(slot)
        out = self._slot_out[slot]
        req = out.request
        now = self.clock() if now is None else now
        out.tokens.append(token)
        out.token_times.append(now)
        finish_reason = None
        if req.eos_token_id is not None and token == req.eos_token_id:
            finish_reason = "eos"
        elif len(out.tokens) >= req.max_new_tokens:
            finish_reason = "length"
        event = StreamEvent(
            request_id=req.request_id,
            token=token,
            index=len(out.tokens) - 1,
            finished=finish_reason is not None,
            finish_reason=finish_reason,
        )
        if finish_reason is not None:
            if self.tracer.enabled:
                self.tracer.instant(
                    "finish", track=f"slot {slot}",
                    request_id=req.request_id, reason=finish_reason,
                    tokens=len(out.tokens),
                )
            out.status = FINISHED
            out.finish_reason = finish_reason
            out.finish_time = now
            self.release_slot(slot)
            self.metrics.record_finished(out)
        if req.on_token is not None:
            req.on_token(event)
        return event
