"""The plain reference of the ``sdar_moe`` decoder: Qwen3-MoE's block,
generated by masked diffusion inside blocks of ``L`` positions and
autoregressively across blocks.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: no kernel, no cache, no
batching, and nothing imported from ``tpu_parallel``.  The equations, with
``x`` the residual (no bias anywhere, ``eps`` 1e-6):

- block ``l``: ``h = x + Attn(RMSNorm(x))``; ``y = h + MoE(RMSNorm(h))``;
  ``RMSNorm(x) = x / sqrt(mean(x^2) + eps) * g``;
- ``Attn``: ``q = W_q x`` as ``H`` heads of ``hd``, ``k``, ``v`` as ``KV``
  heads of ``hd``; ``q <- RMSNorm_hd(q)``, ``k <- RMSNorm_hd(k)`` a head,
  each with its own learned scale; rotary positions on ``q`` and ``k``,
  ROTATE-HALF pairing: pair ``i`` is ``(x[i], x[i + hd / 2])`` and turns by
  ``pos * theta ** (-2 i / hd)``; scores times ``hd ** -0.5``; fp32 softmax;
  query head ``i`` reads K/V head ``i // (H / KV)``; output ``concat(heads)
  W_o``.  **The block rule**: the query at position ``p`` sees the key at
  ``j`` iff ``0 <= j <= (p // L) * L + L - 1``: its own block whole, and
  every earlier block;
- ``MoE(h) = sum_{e in top-k} w_e W_down[e] (silu(W_gate[e] h) * (W_up[e]
  h))``: ``s = softmax(W_r h)`` over ALL experts in float32, ``top-k`` the
  largest, ``w_e = s_e / sum_{top-k} s`` (``norm_topk_prob``); no shared
  expert;
- head: ``logits = W_head RMSNorm(x)``, untied.  A masked position predicts
  ITS OWN token (no shift).

**Decoding** (the release's ``generate.py``, block diffusion; :func:`generate`).
The sequence is cut into blocks of ``L`` from position 0.  The prompt's first
``(P // L) * L`` tokens are final as they stand; the prompt's tail shares the
first generated block and is never masked.  Block by block: the block holds
its known tokens and mask ids.  Step ``t``: one forward of the block's ``L``
rows against all earlier positions and the block's own rows; ``x0 = argmax``
(or a draw at ``temperature``), ``c = softmax(logits)[x0]`` at masked
positions, ``-inf`` elsewhere; fill the ``L // T`` (``+ 1`` in the first ``L %
T`` steps) most confident masked positions, ``T`` the request's denoising
steps a block; with a ``confidence_threshold > 0``, every masked position
over it where those are at least that many (``low_confidence_dynamic``).  A
filled position never changes again.  When nothing is masked the block is
final and the next one starts (the program pays one more forward there, to
write the block's final K/V; a reference without a cache has nothing to
write).  Generation ends at the budget, the last block denoised whole and
delivered up to it, or at an EOS, whose block is delivered up to it.

Departures from the release, each on purpose: (1) where fewer positions are
masked than a step would fill (a first block that holds a prompt tail), the
release's ``topk`` reaches into the ``-inf`` entries and OVERWRITES prompt
tokens; here a step fills at most what is masked.  (2) The release checks
for EOS after every block over the whole output; here a block's tokens are
delivered in order up to its first EOS, which is the same text.  (3) The
release recomputes a partial first block from its start with the prompt's
tail as given tokens; so does this, and that is no departure: it is said
because the program shares it.

Weights, in this file's own layout (``lib/sdar_weights.py`` re-lays the
program's tree out so):

    {"embed": [V, d], "lnf_g": [d], "head": [d, V],
     "layers": iterable of {"ln1_g": [d], "ln2_g": [d], "wq": [d, H, hd],
       "wk": [d, KV, hd], "wv": [d, KV, hd], "wo": [H, hd, d],
       "q_norm_g": [hd], "k_norm_g": [hd], "router": [d, E],
       "w_gate": [E, d, w], "w_up": [E, d, w], "w_down": [E, w, d]}}

``shape``: ``block_len``, ``rope_theta``, ``num_experts_per_tok``, ``eps``.
``precision`` rounds the operands of every matmul (``"float32"``: nothing;
``"bfloat16"``; ``"float8"``, e4m3 with a per-tensor scale) before an
fp32-accumulated product: the lower ones exist only as the controls that
``correct`` has to fail.

:func:`replay` is what ``correct`` runs: for streams the timed path served,
with the step at which each position was filled, it forwards the clean
sequence once (keeping each layer's keys and values) and then, for each step
index, the generated blocks as they stood at that step (a block sees the
clean blocks before it and itself), so the reference reads the logits the
program's forward of that step should have produced.

**What it holds on the device.**  :func:`replay` and :func:`forward` wait for
every call before they make the next (nothing is in flight when weights are
drawn) and drop a layer's weights before they ask ``weights["layers"]`` for
the next, so whatever the streams and however fast the host: the top-level
weights, ONE layer, the streams' own rows, and either a layer in the making
or one call's outputs and temporaries.  :func:`replay_bytes_bound` is that
sum as a function of the shapes.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

QUERY_BLOCK = 1024  # attention is computed for this many queries at a time


def _round(x, precision):
    if precision == "float32":
        return x
    if precision == "bfloat16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    if precision == "float8":
        scale = 448.0 / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
        return (x * scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) / scale
    raise ValueError(f"unknown precision {precision!r}")


def _mm(spec, a, b, precision):
    return jnp.einsum(
        spec, _round(a, precision), _round(b, precision),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )


def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * g


def rotate_half(x, positions, theta):
    """Rotary positions over the last axis of ``x`` ``[T, heads, hd]`` at
    ``positions`` ``[T]``: pair ``(x[i], x[i + hd / 2])`` turns by ``pos *
    theta ** (-2i / hd)``."""
    hd = x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    angle = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    lo, hi = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([lo * cos - hi * sin, hi * cos + lo * sin], axis=-1)


def keys_values(h, positions, lw, shape, precision):
    """A layer's keys (normed, rotated) and values of ``h`` ``[T, d]``."""
    k = _mm("td,dhk->thk", h, lw["wk"], precision)
    v = _mm("td,dhk->thk", h, lw["wv"], precision)
    k = rotate_half(
        rms_norm(k, lw["k_norm_g"], shape["eps"]), positions, shape["rope_theta"]
    )
    return k, v


def attend(h, positions, k, v, visible, lw, shape, precision):
    """One layer's attention of the queries of ``h`` ``[T, d]`` over keys and
    values ``[S, KV, hd]``; ``visible(q_rows)`` gives the ``[rows, S]`` mask
    of a block of queries."""
    q = _mm("td,dhk->thk", h, lw["wq"], precision)
    q = rotate_half(
        rms_norm(q, lw["q_norm_g"], shape["eps"]), positions, shape["rope_theta"]
    )
    group = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    out = []
    for lo in range(0, q.shape[0], QUERY_BLOCK):
        qb = q[lo:lo + QUERY_BLOCK]
        scores = _mm("qhk,shk->hqs", qb, k, precision) / math.sqrt(q.shape[-1])
        seen = visible(jnp.arange(lo, lo + qb.shape[0]))
        scores = jnp.where(seen[None], scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        out.append(_mm("hqs,shk->qhk", probs, v, precision))
    return _mm("qhk,hkd->qd", jnp.concatenate(out), lw["wo"], precision)


def experts(h, lw, shape, precision):
    scores = jax.nn.softmax(_mm("td,de->te", h, lw["router"], precision), axis=-1)
    kth = jnp.sort(scores, axis=-1)[:, -shape["num_experts_per_tok"]][:, None]
    top = jnp.where(scores >= kth, scores, 0.0)
    weights = top / jnp.sum(top, axis=-1, keepdims=True)

    def add(y, one):  # one expert over every token, then weighed
        weight, w_gate, w_up, w_down = one
        mid = jax.nn.silu(_mm("td,dw->tw", h, w_gate, precision)) * _mm(
            "td,dw->tw", h, w_up, precision
        )
        return y + weight[:, None] * _mm("tw,wd->td", mid, w_down, precision), None

    # a loop over the experts (lax.scan: one expert's body is compiled)
    y, _ = jax.lax.scan(
        add, jnp.zeros_like(h), (weights.T, lw["w_gate"], lw["w_up"], lw["w_down"])
    )
    return y


def block_rule(block_len, q_positions, k_positions):
    """``[Q, K]``: the key at ``j`` is visible to the query at ``p`` iff ``0
    <= j <= (p // L) * L + L - 1``."""
    last = q_positions // block_len * block_len + (block_len - 1)
    return (k_positions[None, :] >= 0) & (k_positions[None, :] <= last[:, None])


def layer(x, lw, shape, precision="float32"):
    """One block over the whole sequence ``x`` ``[T, d]`` at positions
    ``0..T-1`` under the block rule: ``(x', k, v)``."""
    pos = jnp.arange(x.shape[0])
    h = rms_norm(x, lw["ln1_g"], shape["eps"])
    k, v = keys_values(h, pos, lw, shape, precision)
    seen = lambda rows: block_rule(shape["block_len"], rows, pos)
    x = x + attend(h, pos, k, v, seen, lw, shape, precision)
    x = x + experts(rms_norm(x, lw["ln2_g"], shape["eps"]), lw, shape, precision)
    return x, k, v


def layer_over(x, positions, k_clean, v_clean, lw, shape, precision="float32"):
    """One block over the rows ``x`` ``[R, d]``, whole blocks at
    ``positions`` ``[R]``, each block seeing the CLEAN keys of the blocks
    before it and its own rows (the blocks as they stood at one step)."""
    size = shape["block_len"]
    h = rms_norm(x, lw["ln1_g"], shape["eps"])
    k, v = keys_values(h, positions, lw, shape, precision)
    clean_pos = jnp.arange(k_clean.shape[0])

    def seen(rows):
        mine = positions[rows] // size
        return jnp.concatenate([
            clean_pos[None, :] < (mine * size)[:, None],
            (positions // size)[None, :] == mine[:, None],
        ], axis=1)

    x = x + attend(
        h, positions, jnp.concatenate([k_clean, k]),
        jnp.concatenate([v_clean, v]), seen, lw, shape, precision,
    )
    return x + experts(rms_norm(x, lw["ln2_g"], shape["eps"]), lw, shape, precision)


def _frozen(shape):
    return tuple(sorted(shape.items()))


@functools.partial(jax.jit, static_argnames=("shape", "precision"))
def _layer_jit(x, lw, shape, precision):
    return layer(x, lw, dict(shape), precision)


@functools.partial(jax.jit, static_argnames=("shape", "precision"))
def _layer_over_jit(x, positions, k_clean, v_clean, lw, shape, precision):
    return layer_over(x, positions, k_clean, v_clean, lw, dict(shape), precision)


@functools.partial(jax.jit, static_argnames=("eps", "precision"))
def _head_jit(x, g, head, eps, precision):
    return _mm("td,dv->tv", rms_norm(x, g, eps), head, precision)


@functools.partial(jax.jit, static_argnames=("eps", "precision"))
def _head_read_jit(x, g, head, pick, eps, precision):
    """What a comparison needs of the logits ``[R, V]`` of the rows ``x``,
    without keeping them: the best id, its logit, the logit of ``pick``
    ``[R]``, and the confidence ``softmax(logits)[best]``."""
    logits = _mm("td,dv->tv", rms_norm(x, g, eps), head, precision)
    best = jnp.argmax(logits, axis=-1)
    top = jnp.max(logits, axis=-1)
    at = jnp.take_along_axis(logits, pick[:, None], axis=-1)[:, 0]
    confidence = 1.0 / jnp.sum(jnp.exp(logits - top[:, None]), axis=-1)
    return best, top, at, confidence


def forward(weights, tokens, shape, precision="float32", rows=None):
    """Logits ``[rows, V]`` in float32 for one sequence ``tokens`` ``[T]`` at
    positions ``0..T-1`` under the block rule (``rows``: a slice of
    positions, default all).  ``weights["layers"]`` may be any iterable."""
    frozen = _frozen(shape)
    with jax.default_matmul_precision("highest"):
        x = weights["embed"][jnp.asarray(tokens)]
        layers = iter(weights["layers"])
        lw = next(layers, None)
        while lw is not None:
            x, _, _ = jax.block_until_ready(
                _layer_jit(x, lw, shape=frozen, precision=precision)
            )
            del lw  # gone before the next is made
            lw = next(layers, None)
        if rows is not None:
            x = x[rows]
        return _head_jit(
            x, weights["lnf_g"], weights["head"], eps=shape["eps"],
            precision=precision,
        )


def transfer_counts(block_len: int, steps: int) -> list:
    """Positions a step fills, by step: ``L // T``, one more in the first
    ``L % T`` steps (the release's ``get_num_transfer_tokens``)."""
    return [block_len // steps + (t < block_len % steps) for t in range(steps)]


def choose(confidence, masked, count: int, threshold: float = 0.0):
    """Which masked positions a step fills (numpy, one block): the ``count``
    most confident (the lower index first among equals, as ``top_k`` has
    it), at most what is masked; with a ``threshold > 0``, every masked
    position over it where those are at least ``count``."""
    confidence = np.where(masked, confidence, -np.inf)
    count = min(count, int(masked.sum()))
    over = masked & (confidence > threshold)
    if threshold > 0.0 and over.sum() >= count:
        return over
    order = np.argsort(-confidence, kind="stable")[:count]
    fill = np.zeros_like(masked)
    fill[order] = True
    return fill


def generate(weights, prompt, shape, mask_id: int, max_new_tokens: int,
             denoising_steps=None, confidence_threshold: float = 0.0,
             eos_token_id=None, precision="float32"):
    """The decoding rule above for one prompt, greedy: ``(tokens,
    fill_steps)``, the delivered tokens and the step of its block at which
    each was filled.  Every forward is over the whole sequence so far (no
    cache); ``weights["layers"]`` has to be a list."""
    size = shape["block_len"]
    steps = size if denoising_steps is None else denoising_steps
    counts = transfer_counts(size, steps)
    prompt = [int(t) for t in prompt]
    whole = len(prompt) // size * size
    seq, tail = prompt[:whole], prompt[whole:]
    out, out_steps = [], []
    while len(out) < max_new_tokens:
        block = np.array(tail + [mask_id] * (size - len(tail)), np.int64)
        masked = np.arange(size) >= len(tail)
        filled_at = np.full(size, -1, np.int64)
        step = 0
        while masked.any():
            logits = np.asarray(forward(
                weights, seq + block.tolist(), shape, precision,
                rows=slice(len(seq), len(seq) + size),
            ), np.float64)
            x0 = logits.argmax(-1)
            top = logits.max(-1, keepdims=True)
            confidence = 1.0 / np.exp(logits - top).sum(-1)
            fill = choose(
                confidence, masked, counts[min(step, steps - 1)],
                confidence_threshold,
            )
            block[fill], filled_at[fill] = x0[fill], step
            masked &= ~fill
            step += 1
        for i in range(len(tail), size):
            if len(out) == max_new_tokens:
                break
            out.append(int(block[i]))
            out_steps.append(int(filled_at[i]))
            if eos_token_id is not None and out[-1] == eos_token_id:
                return out, out_steps
        seq, tail = seq + block.tolist(), []
    return out, out_steps


def replay(weights, streams, shape, mask_id: int, precision="float32", pad=256,
           watch=None):
    """The final hidden rows of what the program's forwards should have seen.

    ``streams``: ``[{"prompt": [...], "tokens": [...], "fill_steps": [...]}]``
    as the timed path served them.  For each stream the clean sequence
    (prompt + tokens, the last block dropped where it is not whole: what the
    program held in its undelivered positions is not known) goes through the
    layers once, and for each step index ``t`` the generated blocks as they
    stood at step ``t`` (a position filled at a step ``< t``, or a prompt's
    tail, holds its token, every other the mask id) go through them seeing
    the clean keys before their block and themselves.  With the layers
    outermost: ``weights["layers"]`` is walked once, one layer held at a
    time, every call waited for (the module's docstring); ``watch(where)``,
    if given, is called after each draw of a layer and at each layer's end,
    with nothing in flight.

    Returns, a stream, ``{"positions": [R] (the rows' positions), "tokens":
    [R] served ids, "fill_steps": [R] (-1: a prompt's tail), "hidden": {t:
    [R, d]}}``; :func:`read` turns ``hidden`` into logits' numbers."""
    size = shape["block_len"]
    frozen = _frozen(shape)
    work = []
    for s in streams:
        prompt, tokens = list(s["prompt"]), list(s["tokens"])
        total = (len(prompt) + len(tokens)) // size * size
        first = len(prompt) // size * size
        seq = (prompt + tokens)[:total]
        steps = [-1] * (len(prompt) - first) + list(s["fill_steps"])
        steps = np.array(steps[:total - first])
        padded = -(-total // pad) * pad
        clean = jnp.asarray(seq + [0] * (padded - total), jnp.int32)
        positions = jnp.arange(first, total)
        noisy = {}
        for t in range(int(steps.max()) + 1 if len(steps) else 0):
            ids = np.where(steps < t, np.array(seq[first:]), mask_id)
            noisy[t] = jnp.asarray(ids, jnp.int32)
        work.append({
            "clean": clean, "positions": positions, "noisy": noisy,
            "tokens": np.array(seq[first:]), "fill_steps": steps,
        })
    with jax.default_matmul_precision("highest"):
        xs = [weights["embed"][w["clean"]] for w in work]
        hs = [{t: weights["embed"][ids] for t, ids in w["noisy"].items()}
              for w in work]
        watch = watch or (lambda where: None)
        layers = iter(weights["layers"])
        lw, depth = next(layers, None), 0
        while lw is not None:
            watch(f"layer {depth} drawn")
            for i, w in enumerate(work):
                xs[i], k, v = jax.block_until_ready(_layer_jit(
                    xs[i], lw, shape=frozen, precision=precision
                ))
                for t in hs[i]:
                    hs[i][t] = jax.block_until_ready(_layer_over_jit(
                        hs[i][t], w["positions"], k, v, lw, shape=frozen,
                        precision=precision,
                    ))
            watch(f"layer {depth} done")
            del lw  # gone before the next is made
            lw, depth = next(layers, None), depth + 1
    return [
        {"positions": np.asarray(w["positions"]), "tokens": w["tokens"],
         "fill_steps": w["fill_steps"], "hidden": hs[i]}
        for i, w in enumerate(work)
    ]


def replay_bytes_bound(sizes, longest: int, generated: int, streams: int,
                       steps: int, pad: int = 256, served_bytes: int = 2,
                       passes: int = 1) -> int:
    """The most device memory :func:`replay` holds at once, in bytes, from
    shapes alone: ``streams`` streams of at most ``longest`` positions of
    which at most ``generated`` were generated, ``steps`` step indices a
    block, layers made in a type of ``served_bytes`` a value.  Not a
    function of the number of layers: one is held at a time.

    ``sizes``: ``d_model``, ``n_heads``, ``n_kv_heads``, ``head_dim``,
    ``experts``, ``expert_width``, ``vocab_size``, ``block_len`` (the
    configuration file's ``model`` group).  Everything float32 but a layer in
    the making:

    - the top-level weights: embedding, head, final norm;
    - one layer;
    - the streams' rows: a stream's clean residual ``[T, d]`` (``T`` is
      ``longest`` padded) and ``steps`` noisy ones ``[R, d]`` (``R``: the
      generated positions and a prompt's tail, under a block), one stream's
      clean keys and values, and with ``passes`` > 1 (the control reads a
      second pass against the first) the earlier passes' noisy rows;
    - and the larger of a layer IN THE MAKING (the tree as it was made, let
      go leaf by leaf while the float32 copy, counted above, grows) and ONE
      CALL's outputs and temporaries: scores and probabilities of one query
      block over all keys, the keys and values repeated to every query head
      with their rounded copies, eight arrays of the rows' size.  A
      described v5e's compiler gives the calls of the cell's worst sample
      (``layer`` over 4096 rows, ``layer_over`` of 512 over 4608 keys) 0.82
      and 0.31 GB in float32 and 1.34 GB as the float8 control (sandbox
      AOT, PR 43); this counts 1.93 and 0.98.

    The reads that follow (:func:`read`: ``[R, V]`` logits, a rounded copy of
    the head in the control) hold no layer and stay under the last term.
    """
    d, heads, kv, hd = (sizes[k] for k in
                        ("d_model", "n_heads", "n_kv_heads", "head_dim"))
    experts, width, vocab = (sizes[k] for k in
                             ("experts", "expert_width", "vocab_size"))
    f32 = 4
    top = f32 * (2 * vocab * d + d)
    layer_values = (2 * d * heads * hd + 2 * d * kv * hd + 2 * hd + 2 * d
                    + d * experts + 3 * experts * d * width)
    padded = -(-longest // pad) * pad
    rows = generated + sizes["block_len"]
    held = f32 * (
        streams * d * (padded + steps * rows) + 2 * padded * kv * hd
        + (passes - 1) * streams * steps * rows * d
    )

    def call(rows, keys):
        return f32 * (
            rows * d + 2 * rows * kv * hd
            + 2 * heads * min(rows, QUERY_BLOCK) * keys
            + 4 * keys * heads * hd + 8 * rows * max(d, heads * hd)
        )

    return top + f32 * layer_values + held + max(
        served_bytes * layer_values, call(padded, padded),
        call(rows, padded + rows),
    )


def read(weights, hidden, pick, shape, precision="float32"):
    """``(best id, best logit, logit of pick, confidence of best)`` ``[R]``
    each, of the logits of the rows ``hidden`` ``[R, d]``."""
    with jax.default_matmul_precision("highest"):
        out = _head_read_jit(
            hidden, weights["lnf_g"], weights["head"],
            jnp.asarray(pick, jnp.int32), eps=shape["eps"], precision=precision,
        )
    return tuple(np.asarray(x) for x in out)
