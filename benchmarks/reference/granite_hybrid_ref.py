"""The plain reference of the ``granitemoehybrid`` decoder without experts:
Mamba-2 layers and attention layers mixed by ``layer_types``, a shared SwiGLU
MLP in every layer, the head tied to the embedding.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: no kernel, no cache, no
batching, no padding, and nothing imported from ``tpu_parallel``.  The
equations, with ``rms(x) = x / sqrt(mean(x^2) + eps) * g``:

- ``x_0 = embedding_multiplier * E[ids]``;
- layer ``l``: ``h = x + residual_multiplier * mixer_l(rms(x))``, then ``x' = h
  + residual_multiplier * W_out(silu(g) * u)`` with ``[g | u] = W_in rms(h)``;
- ``logits = rms(x_L) E^T / logits_scaling``;
- **attention**: ``q = u Wq``, ``k = u Wk``, ``v = u Wv``; query head ``i``
  reads K/V head ``i // (H / KV)``; NO positional encoding; scores ``q k^T *
  attention_multiplier``, causal, fp32 softmax; output ``concat(heads) Wo``;
- **mamba** (``d_inner = heads * d_head``, ``G`` groups, state ``N``, conv
  width ``K``): ``[z | xBC | dt] = u W_in`` (``d_inner | d_inner + 2 G N |
  heads``); ``xBC_t = silu(b + sum_{j<K} w[:, j] * xBC_{t-(K-1)+j})`` with zeros
  before the start, written as an explicit shifted sum; ``[x | B | C] = xBC``;
  ``dt_t = softplus(dt_t + dt_bias)``, ``A = -exp(A_log)``; the recurrence
  ``S_t[h] = exp(dt_t[h] A[h]) S_{t-1}[h] + dt_t[h] x_t[h] (outer) B_t``, ``S_0 =
  0``, ``y_t[h] = S_t[h] C_t + D[h] x_t[h]`` as a SEQUENTIAL ``lax.scan`` over
  time (the definition, not the chunked form); ``y = rms_{d_inner}(y *
  silu(z))`` (the gate BEFORE the norm, one group over all of ``d_inner``);
  output ``y W_out``.

Weights, in this file's own layout (``lib/granite_weights.py`` re-lays the
program's tree out so)::

    {"embed": [V, d], "lnf_g": [d],
     "layers": iterable of {"ln1_g": [d], "ln2_g": [d],
       "mlp_in": [d, 2 I] (g | u), "mlp_out": [I, d],
       and for "attention": "wq": [d, H, hd], "wk": [d, KV, hd], "wv": [d, KV,
         hd], "wo": [H, hd, d];
       for "mamba": "w_in": [d, 2 d_inner + 2 G N + heads], "conv_w": [C, K],
         "conv_b": [C], "dt_bias": [heads], "A_log": [heads], "D": [heads],
         "norm_g": [d_inner], "w_out": [d_inner, d]}}

``precision`` rounds the operands of every matmul, and ``x``, ``B`` and ``C``
where they enter the recurrence (``"float32"``: nothing; ``"bfloat16"``;
``"float8"``, e4m3 with a per-tensor scale), before an fp32 product.
``state_precision="bfloat16"`` rounds the recurrent state after every step
and leaves everything else as ``precision`` says.  Both exist only as the
controls that ``correct`` has to tell from the configuration's own types.
"""

import functools

import jax
import jax.numpy as jnp


def _round(x, precision):
    if precision == "float32":
        return x
    if precision == "bfloat16":
        # not a cast there and back: the TPU compiler may keep the excess
        # precision of such a pair, and the control would round nothing
        return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
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


def attention(u, lw, shape, precision):
    """One layer's attention over ``u`` ``[T, d]``: no positions at all."""
    t = u.shape[0]
    q = _mm("td,dhk->thk", u, lw["wq"], precision)
    k = _mm("td,dhk->thk", u, lw["wk"], precision)
    v = _mm("td,dhk->thk", u, lw["wv"], precision)
    group = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    scores = _mm("qhk,shk->hqs", q, k, precision) * shape["attention_multiplier"]
    seen = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
    probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), axis=-1)
    out = _mm("hqs,shk->qhk", probs, v, precision)
    return _mm("qhk,hkd->qd", out, lw["wo"], precision)


def recurrence(x, dt, a, b, c, d, state_precision=None):
    """``y [T, H, P]`` of the selective recurrence, one step a token: ``x``
    ``[T, H, P]``, ``dt`` ``[T, H]``, ``a`` / ``d`` ``[H]``, ``b`` / ``c``
    ``[T, H, N]``.  Also returns the last state ``[H, P, N]``."""

    def step(state, inputs):
        x_t, dt_t, b_t, c_t = inputs
        state = (
            jnp.exp(dt_t * a)[:, None, None] * state
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        )
        if state_precision is not None:
            state = _round(state, state_precision)
        y_t = jnp.sum(state * c_t[:, None, :], axis=-1) + d[:, None] * x_t
        return state, y_t

    zero = jnp.zeros((x.shape[1], x.shape[2], b.shape[-1]), jnp.float32)
    last, y = jax.lax.scan(step, zero, (x, dt, b, c))
    return y, last


def mamba(u, lw, shape, precision, state_precision=None, keep=None):
    """One layer's Mamba-2 mixer over ``u`` ``[T, d]``, and the recurrent
    state that token ``keep`` left (None: the last).  With ``keep`` the
    recurrence STOPS there: the tokens after it get a step of ``dt = 0``
    (decay 1, nothing added: the state stays, bit for bit, what ``keep``
    left), so the scan's last state is the one asked for.  Rows after
    ``keep`` are then not the model's; they are padding nobody reads."""
    t = u.shape[0]
    heads, p = shape["mamba_n_heads"], shape["mamba_d_head"]
    groups, n = shape["mamba_n_groups"], shape["mamba_d_state"]
    width = shape["mamba_d_conv"]
    d_inner = heads * p
    zxbcdt = _mm("td,de->te", u, lw["w_in"], precision)
    z = zxbcdt[:, :d_inner]
    xbc = zxbcdt[:, d_inner:2 * d_inner + 2 * groups * n]
    dt = zxbcdt[:, 2 * d_inner + 2 * groups * n:]
    # depthwise causal conv as an explicit shifted sum, zeros before the start
    padded = jnp.concatenate([jnp.zeros((width - 1, xbc.shape[1])), xbc])
    conv = lw["conv_b"][None, :]
    for j in range(width):
        conv = conv + lw["conv_w"][:, j][None, :] * padded[j:j + t]
    xbc = jax.nn.silu(conv)
    x = xbc[:, :d_inner].reshape(t, heads, p)
    b = xbc[:, d_inner:d_inner + groups * n].reshape(t, groups, n)
    c = xbc[:, d_inner + groups * n:].reshape(t, groups, n)
    b = jnp.repeat(b, heads // groups, axis=1)
    c = jnp.repeat(c, heads // groups, axis=1)
    dt = jax.nn.softplus(dt + lw["dt_bias"][None, :])
    if keep is not None:
        dt = jnp.where(jnp.arange(t)[:, None] <= keep, dt, 0.0)
    y, state = recurrence(
        _round(x, precision), dt, -jnp.exp(lw["A_log"]), _round(b, precision),
        _round(c, precision), lw["D"], state_precision,
    )
    y = rms_norm(y.reshape(t, d_inner) * jax.nn.silu(z), lw["norm_g"], shape["eps"])
    return _mm("te,ed->td", y, lw["w_out"], precision), state


def block(x, lw, kind, shape, precision="float32", state_precision=None,
          keep=None):
    """One layer over ``x`` ``[T, d]``; with it the recurrent state that
    token ``keep`` left (None for an attention layer)."""
    u = rms_norm(x, lw["ln1_g"], shape["eps"])
    state = None
    if kind == "mamba":
        mixed, state = mamba(u, lw, shape, precision, state_precision, keep)
    elif kind == "attention":
        mixed = attention(u, lw, shape, precision)
    else:
        raise ValueError(f"unknown layer type {kind!r}")
    h = x + shape["residual_multiplier"] * mixed
    gu = _mm("td,de->te", rms_norm(h, lw["ln2_g"], shape["eps"]), lw["mlp_in"], precision)
    g, up = jnp.split(gu, 2, axis=-1)
    mlp = _mm("te,ed->td", jax.nn.silu(g) * up, lw["mlp_out"], precision)
    return h + shape["residual_multiplier"] * mlp, state


def _frozen(shape):
    return tuple(sorted((k, v) for k, v in shape.items() if k != "layer_types"))


@functools.partial(
    jax.jit, static_argnames=("kind", "shape", "precision", "state_precision")
)
def _block_jit(x, lw, kind, shape, precision, state_precision, keep):
    return block(x, lw, kind, dict(shape), precision, state_precision, keep)


@functools.partial(jax.jit, static_argnames=("eps", "scale", "precision"))
def _head_jit(x, g, embed, eps, scale, precision):
    return _mm("td,vd->tv", rms_norm(x, g, eps), embed, precision) / scale


def forward_each(weights, sequences, shape, precision="float32",
                 state_precision=None, rows=None, keep=None):
    """``forward`` for several sequences, each by itself (nothing is batched),
    with the layers outermost: ``weights["layers"]`` is walked once, so a
    generator that makes a layer when it is asked for makes each layer once
    for all of them.  ``rows`` is one slice a sequence.  Returns a list of
    logits; with ``keep`` (one position a sequence; rows after it are not to
    be read, see ``mamba``) also, a sequence, the recurrent states ``[H, P,
    N]`` that position left, one a mamba layer in the order of the layers."""
    frozen = _frozen(shape)
    states = [[] for _ in sequences]
    with jax.default_matmul_precision("highest"):
        xs = [
            shape["embedding_multiplier"] * weights["embed"][tokens]
            for tokens in sequences
        ]
        for kind, lw in zip(shape["layer_types"], weights["layers"]):
            for i, x in enumerate(xs):
                xs[i], state = _block_jit(
                    x, lw, kind=kind, shape=frozen, precision=precision,
                    state_precision=state_precision,
                    keep=None if keep is None else jnp.int32(keep[i]),
                )
                if state is not None:
                    states[i].append(state)
        out = []
        for i, x in enumerate(xs):
            if rows is not None:
                x = x[rows[i]]
            out.append(_head_jit(
                x, weights["lnf_g"], weights["embed"], eps=shape["eps"],
                scale=float(shape["logits_scaling"]), precision=precision,
            ))
    return out if keep is None else (out, states)


def forward(weights, tokens, shape, precision="float32", state_precision=None,
            rows=None):
    """Logits ``[rows, V]`` in float32 for one sequence ``tokens`` ``[T]`` at
    positions ``0..T-1`` (``rows``: a slice of positions, default all).

    ``shape``: ``layer_types`` (one name a layer), ``eps``,
    ``embedding_multiplier``, ``residual_multiplier``,
    ``attention_multiplier``, ``logits_scaling``, ``mamba_n_heads``,
    ``mamba_d_head``, ``mamba_d_state``, ``mamba_n_groups``,
    ``mamba_d_conv``.  ``weights["layers"]`` may be any iterable.  One
    compiled block per layer kind and length of ``tokens``."""
    return forward_each(
        weights, [tokens], shape, precision, state_precision,
        None if rows is None else [rows],
    )[0]
