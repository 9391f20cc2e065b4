"""The plain reference of the ``cohere2_moe`` decoder: parallel blocks, window
and full attention mixed by layer, sigmoid-routed experts beside averaged
shared experts, the head tied to the embedding.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: no kernel, no cache, no
batching, and nothing imported from ``tpu_parallel``.  The equations, with
``x`` the residual and ``h = LN(x)`` (subtract the mean, divide by
``sqrt(var + eps)``, multiply by a learned scale; no bias anywhere):

- block ``l``: ``x <- x + Attn_l(h) + MoE(h)``, both from the same ``h``;
- ``Attn_l``: ``q = h Wq``, ``k = h Wk``, ``v = h Wv``; query head ``i``
  reads K/V head ``i // (H / KV)``; scores ``q k^T / sqrt(head_dim)``, fp32
  softmax, output ``concat(heads) Wo``.  ``sliding_attention``: rotary
  positions on q and k over the whole head, interleaved pairs ``(x0, x1),
  (x2, x3), ...``, and query ``i`` sees keys ``i - window < j <= i``.
  ``full_attention``: NO positional encoding at all, every key ``j <= i``;
- ``MoE(h) = sum_{e in top-k} w_e E_e(h) + (1 / S) sum_i S_i(h)``: ``s =
  sigmoid(h Wr)`` over all experts, ``top-k`` the largest, ``w_e = s_e /
  sum_{top-k} s``; every expert, routed or shared, is ``W_down(silu(W_gate
  h) * (W_up h))``;
- ``logits = LN_f(x) E^T * logit_scale`` with ``E`` the token embedding.

**The share.**  ``shape["held"] = (first, count)`` says which routed experts
are here, the weights carry that many experts, the heads held and the
vocabulary rows held: the router still scores all ``num_experts`` and
normalises over its true top-k, and what the absent experts and heads would
have added is left out.  With ``held = (0, num_experts)`` and every head it
is the uncut model.

Weights, in this file's own layout (``lib/cohere2_weights.py`` re-lays the
program's tree out so):

    {"embed": [V, d], "lnf_g": [d],
     "layers": iterable of {"ln_g": [d], "wq": [d, H, hd], "wk": [d, KV, hd],
       "wv": [d, KV, hd], "wo": [H, hd, d], "router": [d, E],
       "w_gate": [held, d, w], "w_up": [held, d, w], "w_down": [held, w, d],
       "s_gate": [S, d, w], "s_up": [S, d, w], "s_down": [S, w, d]}}

``precision`` rounds the operands of every matmul (``"float32"``: nothing;
``"bfloat16"``; ``"float8"``, e4m3 with a per-tensor scale) before an
fp32-accumulated product: the lower ones exist only as the controls that
``correct`` has to fail.  ``route_precision`` does the same to the router's
matmul alone: with everything else in float32 it shows what a changed expert
set by itself does to the logits.
"""

import functools
import math

import jax
import jax.numpy as jnp

QUERY_BLOCK = 2048  # attention is computed for this many queries at a time


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


def layer_norm(x, g, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * g


def rotate(x, theta):
    """Rotary positions over the last axis of ``x`` ``[T, heads, hd]`` at
    positions ``0..T-1``: pair ``(x[2i], x[2i+1])`` turns by ``pos *
    theta ** (-2i / hd)``."""
    t, _, hd = x.shape
    inv_freq = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack(
        [even * cos - odd * sin, even * sin + odd * cos], axis=-1
    ).reshape(x.shape)


def attention(h, lw, kind, shape, precision):
    """One layer's attention over ``h`` ``[T, d]``, in blocks of queries."""
    t = h.shape[0]
    q = _mm("td,dhk->thk", h, lw["wq"], precision)
    k = _mm("td,dhk->thk", h, lw["wk"], precision)
    v = _mm("td,dhk->thk", h, lw["wv"], precision)
    if kind == "sliding_attention":
        q, k = rotate(q, shape["rope_theta"]), rotate(k, shape["rope_theta"])
    elif kind != "full_attention":
        raise ValueError(f"unknown layer type {kind!r}")
    group = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    key_pos = jnp.arange(t)[None, :]
    out = []
    for lo in range(0, t, QUERY_BLOCK):
        qb = q[lo:lo + QUERY_BLOCK]
        scores = _mm("qhk,shk->hqs", qb, k, precision) / math.sqrt(q.shape[-1])
        q_pos = (lo + jnp.arange(qb.shape[0]))[:, None]
        seen = key_pos <= q_pos
        if kind == "sliding_attention":
            seen = seen & (q_pos - key_pos < shape["sliding_window"])
        scores = jnp.where(seen[None], scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        out.append(_mm("hqs,shk->qhk", probs, v, precision))
    return _mm("qhk,hkd->qd", jnp.concatenate(out), lw["wo"], precision)


def expert(h, w_gate, w_up, w_down, precision):
    mid = jax.nn.silu(_mm("td,dw->tw", h, w_gate, precision)) * _mm(
        "td,dw->tw", h, w_up, precision
    )
    return _mm("tw,wd->td", mid, w_down, precision)


def route(h, lw, shape, precision):
    """``(weights [T, E], chosen [T, E] bool)``: each token's normalised
    weight on each of its top-k experts, zero elsewhere."""
    scores = jax.nn.sigmoid(_mm("td,de->te", h, lw["router"], precision))
    kth = jnp.sort(scores, axis=-1)[:, -shape["num_experts_per_tok"]][:, None]
    chosen = scores >= kth
    top = jnp.where(chosen, scores, 0.0)
    return top / jnp.sum(top, axis=-1, keepdims=True), chosen


def experts(h, lw, shape, precision, route_precision):
    weights, chosen = route(h, lw, shape, route_precision or precision)
    first, count = shape["held"]
    n_shared = lw["s_gate"].shape[0]

    def add(y, one):  # one expert over every token, then weighed
        weight, w_gate, w_up, w_down = one
        return y + weight[:, None] * expert(h, w_gate, w_up, w_down, precision), None

    # a loop over the held experts, then over the shared ones (lax.scan, so
    # that the block compiles one expert's body and not twenty)
    y, _ = jax.lax.scan(add, jnp.zeros_like(h), (
        weights[:, first:first + count].T,
        lw["w_gate"], lw["w_up"], lw["w_down"],
    ))
    y, _ = jax.lax.scan(add, y, (
        jnp.full((n_shared, h.shape[0]), 1.0 / n_shared),
        lw["s_gate"], lw["s_up"], lw["s_down"],
    ))
    return y, chosen


def block(x, lw, kind, shape, precision="float32", route_precision=None):
    """One parallel block over ``x`` ``[T, d]``: ``(x', chosen [T, E])``."""
    h = layer_norm(x, lw["ln_g"], shape["eps"])
    moe, chosen = experts(h, lw, shape, precision, route_precision)
    return x + attention(h, lw, kind, shape, precision) + moe, chosen


def _frozen(shape):
    return tuple(sorted(
        (k, tuple(v) if isinstance(v, (list, tuple)) else v)
        for k, v in shape.items() if k != "layer_types"
    ))


@functools.partial(
    jax.jit, static_argnames=("kind", "shape", "precision", "route_precision")
)
def _block_jit(x, lw, kind, shape, precision, route_precision):
    return block(x, lw, kind, dict(shape), precision, route_precision)


@functools.partial(jax.jit, static_argnames=("eps", "scale", "precision"))
def _head_jit(x, g, embed, eps, scale, precision):
    return _mm("td,vd->tv", layer_norm(x, g, eps), embed, precision) * scale


def forward_each(weights, sequences, shape, precision="float32",
                 route_precision=None, rows=None, with_routing=False):
    """``forward`` for several sequences, each by itself (nothing is
    batched), with the layers outermost: ``weights["layers"]`` is walked
    once, so a generator that makes a layer when it is asked for makes each
    layer once for all of them.  ``rows`` is one slice a sequence.  Returns
    a list of logits, or of ``(logits, routing)``."""
    frozen = _frozen(shape)
    with jax.default_matmul_precision("highest"):
        xs = [weights["embed"][tokens] for tokens in sequences]
        routing = [[] for _ in sequences]
        for kind, lw in zip(shape["layer_types"], weights["layers"]):
            for i, x in enumerate(xs):
                xs[i], chosen = _block_jit(
                    x, lw, kind=kind, shape=frozen, precision=precision,
                    route_precision=route_precision,
                )
                if with_routing:
                    routing[i].append(chosen)
        out = []
        for i, x in enumerate(xs):
            if rows is not None:
                x = x[rows[i]]
            out.append(_head_jit(
                x, weights["lnf_g"], weights["embed"], eps=shape["eps"],
                scale=float(shape["logit_scale"]), precision=precision,
            ))
    return list(zip(out, routing)) if with_routing else out


def forward(weights, tokens, shape, precision="float32", route_precision=None,
            rows=None, with_routing=False):
    """Logits ``[rows, V]`` in float32 for one sequence ``tokens`` ``[T]`` at
    positions ``0..T-1`` (``rows``: a slice of positions, default all).

    ``shape``: ``layer_types`` (one name a layer), ``sliding_window``,
    ``rope_theta``, ``num_experts_per_tok``, ``held``, ``eps``,
    ``logit_scale``.  ``weights["layers"]`` may be any iterable: a generator
    that makes a layer when it is asked for keeps one layer in memory at a
    time.  One compiled block per layer kind and length of ``tokens``.
    ``with_routing`` also returns each layer's ``chosen`` ``[T, E]``."""
    return forward_each(
        weights, [tokens], shape, precision, route_precision,
        None if rows is None else [rows], with_routing,
    )[0]
