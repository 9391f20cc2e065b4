"""Speculative decoding: draft-verify multi-token decode, exactly.

Decode is the serving hot path (nearly all of a request's time after the
prefill fast path) and a single-token step is memory-bound — the whole model's
weights stream through HBM to produce ONE token per row.  Speculative
decoding (Leviathan et al. 2023) buys back that bandwidth: a cheap DRAFTER
proposes K tokens per row, one forward scores all K (+1 bonus position)
through the multi-token ``write_index`` scatter the chunked prefill
already built (:func:`~tpu_parallel.models.generate.verify_step`), and an
acceptance rule keeps the longest exact prefix — the output token stream
is PROVABLY identical to non-speculative decoding:

- greedy: accept drafts while they equal the verify argmax chain, then
  append the argmax at the first mismatch (the "bonus" token).  Every
  emitted token is the argmax the sequential loop would have produced —
  bitwise parity (:func:`greedy_verify`).
- sampled: the Leviathan rejection rule (:func:`rejection_verify`).  The
  drafter here is DETERMINISTIC (a point mass q), so draft ``d`` is
  accepted with probability ``p(d)`` under the target distribution ``p``
  (temperature / top-k / top-p filtered), and a rejection resamples from
  the residual ``p`` with ``d`` zeroed out, renormalized — the marginal
  of every emitted token is exactly ``p`` (unit-pinned in
  ``tests/test_spec_decode.py``), though the realized sequence differs
  from the non-spec engine's (different RNG consumption).

Rejection needs NO cache rollback: rejected drafts' K/V sit at columns
beyond the accepted frontier, where the engine's aligned layout
(column == stored position; ``CachePool.assert_slot_aligned``) keeps them
masked until the next verify overwrites them.

The drafter is pluggable (:class:`Drafter`); the default
:class:`NGramDrafter` is MODEL-FREE prompt-lookup drafting (Saxena 2023):
propose the continuation of the most recent earlier occurrence of the
context's longest matching suffix n-gram.  Zero extra FLOPs/HBM, exact by
construction (a bad draft only wastes verify positions), and strongest
exactly where decode is longest — repetitive/structured continuations
(code, extraction, summaries quoting the prompt, greedy cycles).

:func:`generate_speculative` is the standalone batch loop (host-side
drafting around jitted verify ticks) so ``scripts/decode_bench.py`` can
measure the path without the serving engine; the engine's spec tick
(``ServingEngine`` with ``draft_tokens > 0``) shares every device
function with it.
"""

from __future__ import annotations

from typing import List, Optional, Protocol, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax


class Drafter(Protocol):
    """Anything that proposes draft tokens for one request.

    ``draft(context, k)`` sees the request's full token history (prompt +
    everything generated so far, INCLUDING the current token whose K/V is
    not yet written) and returns up to ``k`` proposed continuation tokens
    (possibly none).  Host-side and per-slot — drafters may be stateful.
    A wrong draft can never corrupt output (the verify rule rejects it);
    it only wastes verify positions.
    """

    def draft(self, context: Sequence[int], k: int) -> List[int]:
        ...


class NGramDrafter:
    """Model-free prompt-lookup drafting: find the most recent earlier
    occurrence of the context's suffix n-gram (longest n first, down to
    ``min_ngram``) and propose the tokens that followed it.

    Deterministic and CPU-only — no second model, no device work.  On
    repetitive continuations (greedy cycles, code, quote-heavy answers)
    acceptance approaches 1 and decode emits ~K+1 tokens per forward; on
    novel text it proposes nothing (or garbage that verify rejects) and
    decode degenerates gracefully to the single-token path.
    """

    def __init__(self, max_ngram: int = 3, min_ngram: int = 1):
        if not 1 <= min_ngram <= max_ngram:
            raise ValueError(
                f"need 1 <= min_ngram ({min_ngram}) <= max_ngram ({max_ngram})"
            )
        self.max_ngram = max_ngram
        self.min_ngram = min_ngram

    def draft(self, context: Sequence[int], k: int) -> List[int]:
        if k <= 0:
            return []
        ctx = list(context)
        length = len(ctx)
        for n in range(min(self.max_ngram, length - 1), self.min_ngram - 1, -1):
            pattern = ctx[length - n:]
            # most recent earlier occurrence wins (locality: recent
            # repetition predicts the continuation better than old)
            for s in range(length - n - 1, -1, -1):
                if ctx[s:s + n] == pattern:
                    cont = ctx[s + n: s + n + k]
                    if cont:
                        return cont
        return []


def filter_logits(
    logits: jax.Array,
    temperature: jax.Array,
    top_k: jax.Array,
    top_p: jax.Array,
    rows: Optional[jax.Array] = None,
) -> jax.Array:
    """Per-row sampling filters over [rows, vocab] fp32 logits with traced
    per-row knobs — the shared filter core of ``engine.sample_tokens`` and
    the rejection rule's target distribution.  Semantics identical to the
    static ``models.generate._sample``: temperature scale first, top-k and
    top-p compose by intersection, the argmax always survives the nucleus
    cut; ``top_k <= 0`` / ``top_p`` outside (0, 1) disable that filter.
    Greedy rows (``temperature <= 0``) get a guarded divide — callers take
    the argmax branch and never read their filtered values.

    Each filter costs a sort of the whole [rows, vocab] block, and runs
    only where some row that COUNTS asks for it: a row counts when its
    filtered values will be read, i.e. it is sampled and in ``rows`` (a
    bool mask; None: every row).  The choice is made on the device, a
    call (``lax.cond``); a filter that runs, runs on all rows, and one
    that is skipped was the identity on every row that counts."""
    lf = logits.astype(jnp.float32)
    t = jnp.where(temperature > 0.0, temperature, 1.0)[:, None]
    x = lf / t
    vocab = x.shape[-1]
    counts = temperature > 0.0
    if rows is not None:
        counts = counts & rows
    k = jnp.clip(top_k.astype(jnp.int32), 0, vocab)
    use_p = (top_p > 0.0) & (top_p < 1.0)

    def top_k_cut(x):
        # per-row top-k with traced k: the kth-largest value via one sort
        asc = jnp.sort(x, axis=-1)
        kth = jnp.take_along_axis(
            asc, jnp.clip(vocab - k, 0, vocab - 1)[:, None], axis=-1
        )
        return jnp.where((k > 0)[:, None] & (x < kth), -jnp.inf, x)

    def nucleus_cut(x):
        # per-row nucleus on the (already top-k-filtered) distribution
        desc = jnp.sort(x, axis=-1)[:, ::-1]
        probs = jax.nn.softmax(desc, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        keep = cum - probs < top_p[:, None]  # mass BEFORE the token < p
        cutoff = jnp.min(
            jnp.where(keep, desc, jnp.inf), axis=-1, keepdims=True
        )
        return jnp.where(use_p[:, None] & (x < cutoff), -jnp.inf, x)

    x = lax.cond(jnp.any(counts & (k > 0)), top_k_cut, lambda x: x, x)
    return lax.cond(jnp.any(counts & use_p), nucleus_cut, lambda x: x, x)


def target_probs(
    logits: jax.Array,
    temperature: jax.Array,
    top_k: jax.Array,
    top_p: jax.Array,
) -> jax.Array:
    """The verify target distribution p at every offset: [n, T, vocab]
    logits + per-ROW knobs -> filtered, normalized probabilities (fp32).
    Row knobs broadcast over the row's T offsets (one request, one knob
    set, K+1 scored positions)."""
    n, t, vocab = logits.shape
    flat = filter_logits(
        logits.astype(jnp.float32).reshape(n * t, vocab),
        jnp.repeat(temperature, t),
        jnp.repeat(top_k, t),
        jnp.repeat(top_p, t),
    )
    return jax.nn.softmax(flat, axis=-1).reshape(n, t, vocab)


def _leading_accepts(ok: jax.Array) -> jax.Array:
    """Length of the leading all-True prefix per row of a [n, K] bool
    mask — the accepted-draft count (acceptance stops at the first
    rejection; later lucky matches must not count)."""
    return jnp.sum(jnp.cumprod(ok.astype(jnp.int32), axis=1), axis=1)


def _emit(drafts: jax.Array, accepted: jax.Array, bonus: jax.Array):
    """Assemble the emitted-token block [n, K+1]: offsets < accepted carry
    the accepted drafts, offset ``accepted`` the bonus token; later
    offsets repeat the bonus (unread — callers deliver accepted+1)."""
    n, k = drafts.shape
    ext = jnp.concatenate(
        [drafts, jnp.zeros((n, 1), drafts.dtype)], axis=1
    )
    iota = jnp.arange(k + 1, dtype=jnp.int32)[None, :]
    return jnp.where(iota < accepted[:, None], ext, bonus[:, None])


def greedy_verify(
    drafts: jax.Array, draft_len: jax.Array, targets: jax.Array
) -> Tuple[jax.Array, jax.Array]:
    """Greedy acceptance: longest draft prefix matching the verify argmax
    chain, plus the argmax at the first mismatch as the bonus token.

    ``drafts`` [n, K] (pads beyond ``draft_len`` ignored), ``targets``
    [n, K+1] = argmax of the verify logits at each offset (``targets[:,i]``
    is the token that FOLLOWS offset ``i``'s input token).  Returns
    ``(tokens [n, K+1], accepted [n])`` — ``accepted + 1`` tokens emit per
    row, every one bitwise equal to what sequential greedy decode would
    have produced (accepted drafts equal their targets by construction;
    the bonus IS the target at the cut).
    """
    n, k = drafts.shape
    iota = jnp.arange(k, dtype=jnp.int32)[None, :]
    ok = (drafts == targets[:, :k]) & (iota < draft_len[:, None])
    accepted = _leading_accepts(ok)
    bonus = jnp.take_along_axis(targets, accepted[:, None], axis=1)[:, 0]
    return _emit(drafts, accepted, bonus), accepted


def rejection_verify(
    drafts: jax.Array,
    draft_len: jax.Array,
    probs: jax.Array,
    rng: jax.Array,
) -> Tuple[jax.Array, jax.Array]:
    """Leviathan rejection sampling specialized to a DETERMINISTIC drafter
    (q = point mass on the draft): accept ``d_i`` with probability
    ``p_i(d_i)``; at the first rejection sample the bonus from the
    residual ``p_i`` with ``d_i`` zeroed, renormalized; with every draft
    accepted, sample the bonus from the next distribution unmodified.

    ``probs`` [n, K+1, vocab] are the filtered target distributions
    (:func:`target_probs`).  Marginal of each emitted token is exactly the
    target distribution — speculative sampling changes WHEN tokens are
    produced, never their law.  Returns ``(tokens [n, K+1], accepted [n])``.
    """
    n, k = drafts.shape
    r_accept, r_bonus = jax.random.split(rng)
    if k > 0:
        u = jax.random.uniform(r_accept, (n, k))
        p_draft = jnp.take_along_axis(
            probs[:, :k, :], drafts[..., None], axis=-1
        )[..., 0]
        iota = jnp.arange(k, dtype=jnp.int32)[None, :]
        ok = (u < p_draft) & (iota < draft_len[:, None])
        accepted = _leading_accepts(ok)
    else:
        accepted = jnp.zeros((n,), jnp.int32)
    row_p = jnp.take_along_axis(
        probs, accepted[:, None, None], axis=1
    )[:, 0]  # [n, vocab]: the distribution at the cut
    if k > 0:
        # zero the rejected draft out of the residual — only when the cut
        # IS a rejection (accepted < draft_len), not a fully-accepted
        # block whose bonus draws from the next distribution whole
        rejected = jnp.take_along_axis(
            drafts, jnp.clip(accepted, 0, k - 1)[:, None], axis=1
        )[:, 0]
        cut_is_rejection = accepted < draft_len
        mask = jax.nn.one_hot(rejected, probs.shape[-1], dtype=row_p.dtype)
        resid = row_p * (1.0 - mask * cut_is_rejection[:, None])
        norm = resid.sum(axis=-1, keepdims=True)
        # p(d) ~ 1 makes rejection near-impossible; if fp still lands here
        # with an empty residual, falling back to row_p keeps the sample
        # valid (measure-zero event)
        row_p = jnp.where(norm > 0, resid / jnp.maximum(norm, 1e-30), row_p)
    bonus_logits = jnp.where(row_p > 0, jnp.log(jnp.maximum(row_p, 1e-30)),
                             -jnp.inf)
    bonus = jax.random.categorical(r_bonus, bonus_logits, axis=-1).astype(
        jnp.int32
    )
    return _emit(drafts, accepted, bonus), accepted


def verify_tokens(
    drafts: jax.Array,
    draft_len: jax.Array,
    logits: jax.Array,
    rng: jax.Array,
    temperature: jax.Array,
    top_k: jax.Array,
    top_p: jax.Array,
) -> Tuple[jax.Array, jax.Array]:
    """Per-row acceptance over one verify forward's logits [n, K+1, vocab]
    with per-row sampling knobs: greedy rows (``temperature <= 0``) take
    :func:`greedy_verify` on the raw argmax chain (bitwise parity with
    sequential decode), sampled rows :func:`rejection_verify` on the
    filtered target distributions.  Returns ``(tokens [n, K+1],
    accepted [n])``; callers emit ``accepted + 1`` tokens per row.
    """
    lf = logits.astype(jnp.float32)
    g_tokens, g_acc = greedy_verify(drafts, draft_len,
                                    jnp.argmax(lf, axis=-1).astype(jnp.int32))
    greedy = temperature <= 0.0

    def sampled(_):
        probs = target_probs(lf, temperature, top_k, top_p)
        s_tokens, s_acc = rejection_verify(drafts, draft_len, probs, rng)
        return (
            jnp.where(greedy[:, None], g_tokens, s_tokens),
            jnp.where(greedy, g_acc, s_acc),
        )

    # an all-greedy pool (the common serving case) skips the rejection
    # path's [n*(K+1), vocab] sorts entirely at runtime — on CPU they cost
    # more than the verify forward itself
    tokens, accepted = lax.cond(
        jnp.any(~greedy), sampled, lambda _: (g_tokens, g_acc), None
    )
    return tokens.astype(jnp.int32), accepted


def ngram_draft_tokens(
    history: jax.Array,
    length: jax.Array,
    cap: jax.Array,
    k: int,
    max_ngram: int = 3,
    min_ngram: int = 1,
) -> Tuple[jax.Array, jax.Array]:
    """DEVICE prompt-lookup drafting — the traceable twin of
    :class:`NGramDrafter`, token-for-token identical by construction
    (pinned in ``tests/test_spec_decode.py``): longest suffix n-gram
    first (``max_ngram`` down to ``min_ngram``), most recent earlier
    occurrence wins, propose up to ``cap`` following tokens.

    This is what lets the FUSED speculative tick run ``T`` draft-verify
    blocks inside one ``lax.scan``: block ``t+1``'s context includes
    block ``t``'s accepted tokens, which live only on device mid-scan —
    a host drafter would force one dispatch + one sync per block, the
    exact per-step tax the fused tick exists to kill.  The token
    ``history`` [rows, L] rides the scan carry (the engine re-uploads it
    only on admission, like the rest of the slot state).

    ``length`` [rows] is each row's live context length (prompt +
    generated, INCLUDING the current unwritten token — the same context
    :meth:`NGramDrafter.draft` sees); ``cap`` [rows] the per-row draft
    budget (:func:`draft_for_row`'s clamp, computed by the caller;
    ``<= 0`` drafts nothing).  Entries of ``history`` at or beyond
    ``length`` are never read.  Returns ``(drafts [rows, k], dlen
    [rows])`` with drafts zero-padded beyond ``dlen`` — byte-identical
    to the engine's host-side draft block layout.
    """
    if k < 1:
        raise ValueError(f"k={k} < 1")
    if not 1 <= min_ngram <= max_ngram:
        raise ValueError(
            f"need 1 <= min_ngram ({min_ngram}) <= max_ngram ({max_ngram})"
        )
    L = history.shape[-1]

    def one_row(hist, hlen, kcap):
        iota = jnp.arange(L, dtype=jnp.int32)
        drafts = jnp.zeros((k,), jnp.int32)
        dlen = jnp.zeros((), jnp.int32)
        found = jnp.zeros((), bool)
        # static unroll over the (tiny) n-gram size ladder: largest g
        # with any match wins, exactly like the host drafter's outer loop
        for g in range(max_ngram, min_ngram - 1, -1):
            ok_g = (kcap > 0) & (g <= hlen - 1)
            sfx = hist[jnp.clip(hlen - g + jnp.arange(g), 0, L - 1)]
            match = jnp.ones((L,), bool)
            for j in range(g):
                at = jnp.clip(iota + j, 0, L - 1)
                match = match & (hist[at] == sfx[j]) & (iota + j < L)
            # s <= hlen - g - 1 keeps the continuation nonempty (the host
            # drafter's `if cont` can only be empty at s == hlen - g,
            # which its range already excludes)
            match = match & (iota <= hlen - g - 1) & ok_g
            s = jnp.max(jnp.where(match, iota, -1))
            hit = s >= 0
            cont = hist[jnp.clip(s + g + jnp.arange(k), 0, L - 1)]
            take = jnp.where(hit, jnp.minimum(kcap, hlen - (s + g)), 0)
            use = hit & ~found
            drafts = jnp.where(use, cont, drafts)
            dlen = jnp.where(use, take, dlen)
            found = found | hit
        # zero-pad beyond dlen — the host block layout (np.zeros + fill)
        drafts = jnp.where(jnp.arange(k) < dlen, drafts, 0)
        return drafts, dlen

    return jax.vmap(one_row)(
        history,
        jnp.asarray(length, jnp.int32),
        jnp.asarray(cap, jnp.int32),
    )


def adapt_draft_len_traced(
    k: jax.Array, drafted: jax.Array, accepted: jax.Array, k_max: jax.Array,
) -> jax.Array:
    """Traceable :func:`adapt_draft_len` (k_min fixed at 1) — the fused
    spec tick's in-scan per-slot adaptation, same grow/shrink law so the
    fused and per-step engines ride identical draft-length trajectories."""
    grown = jnp.minimum(k + 1, k_max)
    shrunk = jnp.maximum(1, accepted + 1)
    adapted = jnp.where(accepted >= drafted, grown, shrunk)
    return jnp.where(drafted <= 0, k, adapted)


def draft_for_row(
    drafter: Drafter,
    context: Sequence[int],
    k_eff: int,
    write_index: int,
    seq_len: int,
    remaining: int,
) -> List[int]:
    """One row's draft block, safety-capped — THE shared clamp of the
    engine's spec tick and :func:`generate_speculative` (two hand-synced
    copies would let the paths silently diverge).

    The cap is correctness-critical on two sides: ``seq_len - 1 - widx``
    keeps every REAL draft's cache write in range (a dropped write would
    silently lose a scored position), and ``remaining - 1`` keeps a block
    (accepted + bonus) from overshooting the request's token budget.
    Returns at most ``k_eff`` drafted tokens, possibly none.
    """
    cap = min(int(k_eff), seq_len - 1 - int(write_index), remaining - 1)
    if cap <= 0:
        return []
    return list(drafter.draft(context, cap))[:cap]


def adapt_draft_len(k: int, drafted: int, accepted: int, k_max: int,
                    k_min: int = 1) -> int:
    """Acceptance-adaptive draft length: grow by one after a fully-accepted
    block, shrink to just past the acceptance point otherwise.  Bounded in
    [k_min, k_max]; a tick that drafted nothing teaches nothing.  The
    VERIFY program shape never changes (the engine pads every block to its
    compiled K_max width) — adaptation only trims how many real drafts
    ride it, trading wasted verify positions against capture of long runs.
    """
    if drafted <= 0:
        return k
    if accepted >= drafted:
        return min(k + 1, k_max)
    return max(k_min, accepted + 1)


def generate_speculative(
    model,
    params,
    prompt: jax.Array,
    rng: Optional[jax.Array] = None,
    *,
    max_new_tokens: int = 32,
    draft_tokens: int = 4,
    drafter: Optional[Drafter] = None,
    adaptive: bool = True,
    temperature: float = 0.0,
    top_k: int = 0,
    top_p: float = 0.0,
    return_stats: bool = False,
    registry=None,
):
    """Standalone batch speculative decoding — ``generate()``'s contract
    (returns [batch, max_new_tokens]; greedy output is token-identical,
    pinned in tests) through draft-verify ticks instead of a single-token
    scan.

    The loop is HOST-side (the drafter reads token histories Python-side),
    one jitted :func:`~tpu_parallel.models.generate.verify_step` + accept
    per tick, sharing the engine's compiled functions
    (``serving.engine._engine_fns``) — so ``scripts/decode_bench.py`` can
    measure speculative decode without standing up the engine, and
    ``draft_tokens=0`` degenerates to the engine-style per-token host loop
    (the honest non-spec baseline: the engine cannot use ``generate()``'s
    fused scan, requests join and leave between ticks).  Rows finish at
    their own tick (variable acceptance); finished rows park their cache
    writes out of range exactly like the engine's freed slots.

    With ``return_stats`` also returns ``{"ticks", "drafted", "accepted",
    "acceptance_rate", "tokens_per_tick"}``.  ``registry`` (a
    :class:`~tpu_parallel.obs.registry.MetricRegistry`) additionally
    observes each row-tick's acceptance fraction into the SAME
    ``serving_spec_acceptance_ratio`` histogram the engine's spec tick
    feeds, so standalone decode-bench runs and engine runs export
    comparable acceptance distributions.
    """
    from tpu_parallel.serving.engine import _engine_fns

    cfg = model.config
    b, prompt_len = prompt.shape
    if prompt_len + max_new_tokens > cfg.seq_len:
        raise ValueError(
            f"prompt ({prompt_len}) + max_new_tokens ({max_new_tokens}) "
            f"exceeds seq_len ({cfg.seq_len})"
        )
    if draft_tokens < 0:
        raise ValueError(f"draft_tokens={draft_tokens} < 0")
    if rng is None:
        rng = jax.random.PRNGKey(0)
    if drafter is None:
        drafter = NGramDrafter()
    prefill_fn, _, _, verify_fn, sample_fn = _engine_fns(model)

    def split():
        nonlocal rng
        rng, sub = jax.random.split(rng)
        return sub

    temp = jnp.full((b,), temperature, jnp.float32)
    tk = jnp.full((b,), top_k, jnp.int32)
    tp = jnp.full((b,), top_p, jnp.float32)
    positions = jnp.broadcast_to(
        jnp.arange(prompt_len, dtype=jnp.int32), (b, prompt_len)
    )
    logits, cache, _ = prefill_fn(
        params, prompt.astype(jnp.int32), positions,
        jnp.full((b,), prompt_len - 1, jnp.int32), split(),
    )
    first = np.asarray(sample_fn(logits, split(), temp, tk, tp))
    prompts_host = [
        [int(t) for t in row] for row in np.asarray(prompt)
    ]  # plain int lists ONCE — not np-scalar conversion per tick
    out: List[List[int]] = [[int(first[r])] for r in range(b)]
    tok = first.astype(np.int32)
    pos = np.full(b, prompt_len, np.int32)
    widx = np.full(b, prompt_len, np.int32)
    kmax = draft_tokens
    k_eff = np.full(b, max(kmax, 0), np.int32)
    ticks = drafted_total = accepted_total = 0
    acceptance_hist = (
        registry.histogram("serving_spec_acceptance_ratio")
        if registry is not None
        else None
    )

    while any(len(t) < max_new_tokens for t in out):
        drafts = np.zeros((b, kmax), np.int32)
        dlen = np.zeros(b, np.int32)
        for r in range(b):
            rem = max_new_tokens - len(out[r])
            if rem <= 0:
                widx[r] = cfg.seq_len  # park: finished rows write nothing
                continue
            d = draft_for_row(
                drafter, prompts_host[r] + out[r], int(k_eff[r]),
                int(widx[r]), cfg.seq_len, rem,
            )
            dlen[r] = len(d)
            drafts[r, : len(d)] = d
        block, accepted, cache = verify_fn(
            params, jnp.asarray(tok), jnp.asarray(drafts),
            jnp.asarray(dlen), jnp.asarray(pos), jnp.asarray(widx),
            temp, tk, tp, cache, split(),
        )
        # one sync per verify tick — the tick boundary, not per slot
        block, accepted = np.asarray(block), np.asarray(accepted)  # host-sync: tick-boundary
        ticks += 1
        for r in range(b):
            if len(out[r]) >= max_new_tokens:
                continue
            a = int(accepted[r])
            out[r].extend(int(t) for t in block[r, : a + 1])
            tok[r] = int(block[r, a])
            pos[r] += a + 1
            widx[r] += a + 1
            drafted_total += int(dlen[r])
            accepted_total += a
            if acceptance_hist is not None and int(dlen[r]) > 0:
                acceptance_hist.observe(a / int(dlen[r]))
            if adaptive and kmax > 0:
                k_eff[r] = adapt_draft_len(
                    int(k_eff[r]), int(dlen[r]), a, kmax
                )
    tokens = jnp.asarray(
        [row[:max_new_tokens] for row in out], jnp.int32
    )
    if not return_stats:
        return tokens
    # tokens emitted BY verify ticks (each row's first token came from the
    # prefill sample, not a tick)
    emitted = int(sum(len(row[:max_new_tokens]) for row in out)) - b
    stats = {
        "ticks": ticks,
        "drafted": drafted_total,
        "accepted": accepted_total,
        "acceptance_rate": (
            round(accepted_total / drafted_total, 4) if drafted_total else None
        ),
        "tokens_per_tick": round(emitted / max(ticks, 1), 3),
    }
    return tokens, stats
