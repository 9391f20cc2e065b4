"""The plain reference of the ``pangu_ultra_moe`` decoder: latent attention
(MLA) in its EXPANDED form only, sandwich norms, leading dense layers before
sigmoid-routed expert layers with one shared expert, an untied head.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: no kernel, no cache, no absorbed
form, no batching, and nothing imported from ``tpu_parallel``.  The equations,
with ``x`` a row of the residual and ``N`` an RMSNorm (divide by
``sqrt(mean(x^2) + eps)``, multiply by a learned scale; no bias anywhere)::

    block:  a = x + N_post_attn( MLA( N_in(x) ) )
            y = a + N_post_mlp( F( N_pre_mlp(a) ) )       # four norms a layer
    F:      a dense layer: W_down( silu(W_gate h) * (W_up h) )
            an expert layer: shared(h) + scale * sum_{e in top-k(s)}
              ( s_e / sum_top-k s ) * expert_e(h),  s = sigmoid(h W_r) over
              ALL experts; experts and the shared one SwiGLU
    MLA:    cq = N_q(h W_dq)
            [q_nope_i ; q_rope_i] = cq W_uq,i
            [ckv ; k_rope] = h W_dkv ;  c = N_kv(ckv)
            [k_nope_i ; v_i] = c W_ukv,i
            score_i(t, s) = ( q_nope_i(t) . k_nope_i(s)
                              + rope(q_rope_i(t)) . rope(k_rope(s)) )
                            / sqrt(nope + rope),   s <= t, softmax over s
            out = [ sum_s p_i(t, s) v_i(s) ]_i W_o
    rope:   rotate-half pairing: pair j of a rotary part of width r is
            (x[j], x[j + r / 2]) and turns by pos * theta ** (-2j / r); ONE
            rotary key a position serves every head
    head:   logits = N_f(x) W_head over the rows held

**Departures from the release, each the configuration file's ``assumed``:**
the router scores by sigmoid with no group limit and no selection bias; the
four norms sit on the input and the output of each sublayer; the inner norms
use the model's eps; no rope scaling.  The multi-token-prediction module is
not here (the served share loads none).

**The share.**  ``shape["held"] = (first, count)`` says which routed experts
are here and the weights carry that many, and the vocabulary rows held: the
router still scores all its experts and normalises over its true top-k, what
the absent experts would have added is left out, and the post-MLP norm is
taken over the partial sum that is here (it is not linear).  With ``held =
(0, n_experts)`` it is the uncut layer.  :func:`mlp_parts` gives the two
parts apart, BEFORE the norm, for the test that ties the shares to the model.

Weights, in this file's own layout (``lib/pangu_weights.py`` re-lays the
program's tree out so)::

    {"embed": [V, d], "lnf_g": [d], "head": [d, V],
     "layers": iterable of {"n_in": [d], "n_post_attn": [d], "n_pre_mlp": [d],
       "n_post_mlp": [d], "w_dq": [d, rq], "n_q": [rq], "w_uq": [rq, H, n + r],
       "w_dkv": [d, rk + r], "n_kv": [rk], "w_ukv": [rk, H, n + v],
       "w_o": [H, v, d],
       and a dense layer's "w_gate": [d, w], "w_up": [d, w], "w_down": [w, d]
       or an expert layer's "router": [d, E], "e_gate": [held, d, w],
       "e_up": [held, d, w], "e_down": [held, w, d], "s_gate": [d, w],
       "s_up": [d, w], "s_down": [w, d]}}

``precision`` rounds the operands of every matmul (``"float32"``: nothing;
``"bfloat16"``; ``"float8"``, e4m3 with a per-tensor scale) before an
fp32-accumulated product: the lower ones exist only as the controls that
``correct`` has to fail.
"""

import functools
import math

import jax
import jax.numpy as jnp

QUERY_BLOCK = 512  # attention is computed for this many queries at a time


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


def rotate_half(x, theta):
    """Rotary positions over the last axis of ``x`` ``[T, ..., r]`` at
    positions ``0..T-1``: pair ``(x[j], x[j + r/2])`` turns by ``pos *
    theta ** (-2j / r)``."""
    t, r = x.shape[0], x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    angle = angle.reshape(t, *([1] * (x.ndim - 2)), r // 2)
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    lo, hi = x[..., : r // 2], x[..., r // 2 :]
    return jnp.concatenate([lo * cos - hi * sin, hi * cos + lo * sin], axis=-1)


def latent_attention(h, lw, shape, precision):
    """One layer's attention over ``h`` ``[T, d]``: keys and values expanded
    from the latent for every head, scores in blocks of queries."""
    t = h.shape[0]
    nope, rope_dim, eps = shape["nope_dim"], shape["rope_dim"], shape["eps"]
    cq = rms_norm(_mm("td,dr->tr", h, lw["w_dq"], precision), lw["n_q"], eps)
    q = _mm("tr,rhk->thk", cq, lw["w_uq"], precision)
    q_nope, q_rope = q[..., :nope], rotate_half(q[..., nope:], shape["rope_theta"])
    ckv = _mm("td,dr->tr", h, lw["w_dkv"], precision)
    rank = ckv.shape[-1] - rope_dim
    c = rms_norm(ckv[:, :rank], lw["n_kv"], eps)
    k_rope = rotate_half(ckv[:, rank:], shape["rope_theta"])  # one for all heads
    kv = _mm("tr,rhk->thk", c, lw["w_ukv"], precision)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    key_pos = jnp.arange(t)[None, :]
    out = []
    for lo in range(0, t, QUERY_BLOCK):
        rows = slice(lo, lo + QUERY_BLOCK)
        scores = (
            _mm("qhk,shk->hqs", q_nope[rows], k_nope, precision)
            + _mm("qhk,sk->hqs", q_rope[rows], k_rope, precision)
        ) / math.sqrt(nope + rope_dim)
        q_pos = (lo + jnp.arange(scores.shape[1]))[:, None]
        scores = jnp.where((key_pos <= q_pos)[None], scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        out.append(_mm("hqs,shk->qhk", probs, v, precision))
    return _mm("qhk,hkd->qd", jnp.concatenate(out), lw["w_o"], precision)


def swiglu(h, w_gate, w_up, w_down, precision):
    mid = jax.nn.silu(_mm("td,dw->tw", h, w_gate, precision)) * _mm(
        "td,dw->tw", h, w_up, precision
    )
    return _mm("tw,wd->td", mid, w_down, precision)


def route(h, lw, shape, precision):
    """``[T, E]``: each token's weight on each of its top-k experts
    (renormalised over the true top-k, times the routing scale), zero
    elsewhere."""
    scores = jax.nn.sigmoid(_mm("td,de->te", h, lw["router"], precision))
    kth = jnp.sort(scores, axis=-1)[:, -shape["num_experts_per_tok"]][:, None]
    top = jnp.where(scores >= kth, scores, 0.0)
    return shape["routed_scaling_factor"] * top / jnp.sum(top, axis=-1, keepdims=True)


def mlp_parts(h, lw, shape, precision="float32"):
    """An expert layer's MLP over ``h`` ``[T, d]`` BEFORE the post-MLP norm,
    as ``(shared, routed)``: what the one shared expert gives (every chip
    computes it alike) and what the held experts give."""
    weights = route(h, lw, shape, precision)
    first, count = shape["held"]

    def add(y, one):  # one expert over every token, then weighed
        weight, w_gate, w_up, w_down = one
        return y + weight[:, None] * swiglu(h, w_gate, w_up, w_down, precision), None

    routed, _ = jax.lax.scan(add, jnp.zeros_like(h), (
        weights[:, first:first + count].T,
        lw["e_gate"], lw["e_up"], lw["e_down"],
    ))
    shared = swiglu(h, lw["s_gate"], lw["s_up"], lw["s_down"], precision)
    return shared, routed


def block(x, lw, shape, precision="float32"):
    """One layer over ``x`` ``[T, d]``; dense or experts by the weights."""
    eps = shape["eps"]
    h = rms_norm(x, lw["n_in"], eps)
    a = x + rms_norm(
        latent_attention(h, lw, shape, precision), lw["n_post_attn"], eps
    )
    h = rms_norm(a, lw["n_pre_mlp"], eps)
    if "router" in lw:
        f = sum(mlp_parts(h, lw, shape, precision))
    else:
        f = swiglu(h, lw["w_gate"], lw["w_up"], lw["w_down"], precision)
    return a + rms_norm(f, lw["n_post_mlp"], eps)


def _frozen(shape):
    return tuple(sorted(
        (k, tuple(v) if isinstance(v, (list, tuple)) else v)
        for k, v in shape.items()
    ))


@functools.partial(jax.jit, static_argnames=("shape", "precision"))
def _block_jit(x, lw, shape, precision):
    return block(x, lw, dict(shape), precision)


@functools.partial(jax.jit, static_argnames=("eps", "precision"))
def _head_jit(x, g, head, eps, precision):
    return _mm("td,dv->tv", rms_norm(x, g, eps), head, precision)


def forward_each(weights, sequences, shape, precision="float32", rows=None,
                 watch=None):
    """``forward`` for several sequences, each by itself (nothing is
    batched), with the layers outermost: ``weights["layers"]`` is walked
    once, so a generator that makes a layer when it is asked for makes each
    layer once for all of them and holds one at a time.  ``rows`` is one
    slice a sequence; ``watch(where)`` is called after each layer (the
    caller's memory watch).  Returns a list of logits."""
    frozen = _frozen(shape)
    with jax.default_matmul_precision("highest"):
        xs = [weights["embed"][tokens] for tokens in sequences]
        for i, lw in enumerate(weights["layers"]):
            for j, x in enumerate(xs):
                xs[j] = _block_jit(x, lw, shape=frozen, precision=precision)
            if watch is not None:
                jax.block_until_ready(xs)
                watch(f"layer {i}")
            del lw
        out = []
        for j, x in enumerate(xs):
            if rows is not None:
                x = x[rows[j]]
            out.append(_head_jit(
                x, weights["lnf_g"], weights["head"], eps=shape["eps"],
                precision=precision,
            ))
    return out


def forward(weights, tokens, shape, precision="float32", rows=None):
    """Logits ``[rows, V]`` in float32 for one sequence ``tokens`` ``[T]`` at
    positions ``0..T-1`` (``rows``: a slice of positions, default all).

    ``shape``: ``nope_dim``, ``rope_dim``, ``rope_theta``, ``eps``,
    ``num_experts_per_tok``, ``routed_scaling_factor``, ``held``.
    ``weights["layers"]`` may be any iterable: a generator that makes a layer
    when it is asked for keeps one layer in memory at a time.  One compiled
    block per kind of layer and length of ``tokens``."""
    return forward_each(
        weights, [tokens], shape, precision, None if rows is None else [rows]
    )[0]
