"""The plain reference of the ``nemotron_h`` decoder: layers that are ONE
sublayer each behind ONE RMSNorm, mixed by ``hybrid_override_pattern``: ``M``
a Mamba-2 mixer with grouped B/C and a gate norm a group, ``*`` attention
without positions, ``E`` a LatentMoE (sigmoid router with a selection bias,
two-matrix ``relu^2`` experts in a latent between one down- and one
up-projection a layer, one shared expert at full width); untied head.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: no kernel, no cache, no
batching, no padding, the recurrence one step a token, and nothing imported
from ``tpu_parallel``.  The equations, with ``rms(x) = x / sqrt(mean(x^2) +
eps) * g`` and no bias in any linear map (the conv has one):

- ``x_0 = E[ids]``; layer ``l`` of kind ``c``: ``x_{l+1} = x_l +
  F_c(rms_l(x_l))``; ``logits = W_head rms_f(x_L)``;
- **M** (``d_inner = heads * d_head``, ``G`` groups, state ``N``, conv width
  ``K``): ``[z | xBC | dt] = u W_in`` (``d_inner | d_inner + 2 G N | heads``);
  ``xBC_t = silu(b + sum_{j<K} w[:, j] * xBC_{t-(K-1)+j})`` with zeros before
  the start, written as an explicit shifted sum; ``[x | B | C] = xBC``, ``x``
  as ``heads`` of ``d_head``, ``B`` and ``C`` as ``G`` groups of ``N``, head
  ``h`` reads group ``h // (heads / G)``; ``dt_t = softplus(dt_t + dt_bias)``,
  NOT clamped; ``A = -exp(A_log)``; ``S_t[h] = exp(dt_t[h] A[h]) S_{t-1}[h] +
  dt_t[h] x_t[h] (outer) B_t``, ``S_0 = 0``, ``y_t[h] = S_t[h] C_t + D[h]
  x_t[h]`` as a SEQUENTIAL ``lax.scan`` over time; ``y = rms_G(y * silu(z))
  * g``: the gate BEFORE the norm, the mean square taken over each group of
  ``d_inner / G`` channels apart; output ``y W_out``;
- ``*``: ``q = u Wq``, ``k = u Wk``, ``v = u Wv``; query head ``i`` reads K/V
  head ``i // (H / KV)``; NO positional encoding; scores ``q k^T * head_dim
  ** -0.5``, causal, fp32 softmax; output ``concat(heads) Wo``;
- **E**: ``s = sigmoid(u W_r)`` over ALL ``n_routed`` experts; the ``top_k``
  largest of ``s + b`` (the bias moves the CHOICE and no weight); ``w = scale
  * s_top / sum(s_top)``; ``v = u W_dn`` (to the latent); ``r = sum_{e in
  top-k, e held} w_e W2_e relu(v W1_e)^2``; ``y = r W_up + relu(u S1)^2 S2``.

**The share.**  ``shape["held"] = (first, count)`` says which routed experts
are here and the weights carry that many; the router still scores all
``n_routed`` and normalises over its true top-k, and what the absent experts
would have added is left out.  The vocabulary rows are whatever ``embed`` and
``head`` hold.  With ``held = (0, n_routed)`` it is the uncut layer.
:func:`latent_moe` returns the layer's three parts apart (routed sum in the
latent, its up-projection, the shared expert), so that shares can be added up
with router, projections and shared expert counted once.

Weights, in this file's own layout (``lib/nemotron_weights.py`` re-lays the
program's tree out so)::

    {"embed": [V, d], "lnf_g": [d], "head": [d, V],
     "layers": iterable of {"ln_g": [d], and for
       "M": "w_in": [d, 2 d_inner + 2 G N + heads], "conv_w": [C, K],
         "conv_b": [C], "dt_bias": [heads], "A_log": [heads], "D": [heads],
         "norm_g": [d_inner], "w_out": [d_inner, d];
       "*": "wq": [d, H, hd], "wk": [d, KV, hd], "wv": [d, KV, hd],
         "wo": [H, hd, d];
       "E": "router": [d, n_routed], "router_bias": [n_routed],
         "w_dn": [d, latent], "w_up": [latent, d], "w1": [held, latent, w],
         "w2": [held, w, latent], "s1": [d, ws], "s2": [ws, d]}}

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

QUERY_BLOCK = 2048  # attention is computed for this many queries at a time


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


def rms_norm(x, g, eps, groups: int = 1):
    """RMSNorm over the last axis, the mean square taken over each of
    ``groups`` equal runs of it apart; one scale ``g`` over the whole axis."""
    xg = x.reshape(*x.shape[:-1], groups, x.shape[-1] // groups)
    xg = xg * jax.lax.rsqrt(jnp.mean(jnp.square(xg), axis=-1, keepdims=True) + eps)
    return xg.reshape(x.shape) * g


def relu2(x):
    return jnp.square(jnp.maximum(x, 0.0))


def attention(u, lw, shape, precision):
    """One layer's attention over ``u`` ``[T, d]``: no positions at all."""
    t = u.shape[0]
    q = _mm("td,dhk->thk", u, lw["wq"], precision)
    k = _mm("td,dhk->thk", u, lw["wk"], precision)
    v = _mm("td,dhk->thk", u, lw["wv"], precision)
    group = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    key_pos = jnp.arange(t)[None, :]
    out = []
    for lo in range(0, t, QUERY_BLOCK):
        qb = q[lo:lo + QUERY_BLOCK]
        scores = _mm("qhk,shk->hqs", qb, k, precision) * q.shape[-1] ** -0.5
        seen = key_pos <= (lo + jnp.arange(qb.shape[0]))[:, None]
        probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), axis=-1)
        out.append(_mm("hqs,shk->qhk", probs, v, precision))
    return _mm("qhk,hkd->qd", jnp.concatenate(out), lw["wo"], precision)


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
    heads, p = shape["mamba_num_heads"], shape["mamba_head_dim"]
    groups, n = shape["n_groups"], shape["ssm_state_size"]
    width = shape["conv_kernel"]
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
    b = jnp.repeat(b, heads // groups, axis=1)  # head h reads group h // (H/G)
    c = jnp.repeat(c, heads // groups, axis=1)
    dt = jax.nn.softplus(dt + lw["dt_bias"][None, :])
    if keep is not None:
        dt = jnp.where(jnp.arange(t)[:, None] <= keep, dt, 0.0)
    y, state = recurrence(
        _round(x, precision), dt, -jnp.exp(lw["A_log"]), _round(b, precision),
        _round(c, precision), lw["D"], state_precision,
    )
    y = rms_norm(
        y.reshape(t, d_inner) * jax.nn.silu(z), lw["norm_g"], shape["eps"], groups
    )
    return _mm("te,ed->td", y, lw["w_out"], precision), state


def route(u, lw, shape, precision):
    """``weights [T, n_routed]``: each token's weight on each of its top-k
    experts (chosen by score plus bias, weighed by score alone, renormalised
    over the top-k, times the scaling factor), zero elsewhere."""
    scores = jax.nn.sigmoid(_mm("td,de->te", u, lw["router"], precision))
    biased = scores + lw["router_bias"][None, :]
    kth = jnp.sort(biased, axis=-1)[:, -shape["num_experts_per_tok"]][:, None]
    top = jnp.where(biased >= kth, scores, 0.0)
    return shape["routed_scaling_factor"] * top / jnp.sum(
        top, axis=-1, keepdims=True
    )


def latent_moe(u, lw, shape, precision):
    """One LatentMoE layer over ``u`` ``[T, d]`` as its three parts:
    ``(routed [T, latent], up [T, d], shared [T, d])``, the held experts'
    weighted sum in the latent, its up-projection, and the shared expert;
    the layer adds ``up + shared``."""
    weights = route(u, lw, shape, precision)
    first, count = shape["held"]
    v = _mm("td,dl->tl", u, lw["w_dn"], precision)

    def add(r, one):  # one expert over every token, then weighed
        weight, w1, w2 = one
        out = _mm("tw,wl->tl", relu2(_mm("tl,lw->tw", v, w1, precision)), w2, precision)
        return r + weight[:, None] * out, None

    # a loop over the held experts (lax.scan: one expert's body is compiled)
    routed, _ = jax.lax.scan(add, jnp.zeros_like(v), (
        weights[:, first:first + count].T, lw["w1"], lw["w2"],
    ))
    up = _mm("tl,ld->td", routed, lw["w_up"], precision)
    shared = _mm(
        "tw,wd->td", relu2(_mm("td,dw->tw", u, lw["s1"], precision)), lw["s2"],
        precision,
    )
    return routed, up, shared


def block(x, lw, kind, shape, precision="float32", state_precision=None,
          keep=None):
    """One layer over ``x`` ``[T, d]``; with it the recurrent state that
    token ``keep`` left (None for any layer but ``M``)."""
    u = rms_norm(x, lw["ln_g"], shape["eps"])
    state = None
    if kind == "M":
        out, state = mamba(u, lw, shape, precision, state_precision, keep)
    elif kind == "*":
        out = attention(u, lw, shape, precision)
    elif kind == "E":
        _, up, shared = latent_moe(u, lw, shape, precision)
        out = up + shared
    else:
        raise ValueError(f"unknown layer kind {kind!r}")
    return x + out, state


def _frozen(shape):
    return tuple(sorted(
        (k, tuple(v) if isinstance(v, (list, tuple)) else v)
        for k, v in shape.items() if k != "pattern"
    ))


@functools.partial(
    jax.jit, static_argnames=("kind", "shape", "precision", "state_precision")
)
def _block_jit(x, lw, kind, shape, precision, state_precision, keep):
    return block(x, lw, kind, dict(shape), precision, state_precision, keep)


@functools.partial(jax.jit, static_argnames=("eps", "precision"))
def _head_jit(x, g, head, eps, precision):
    return _mm("td,dv->tv", rms_norm(x, g, eps), head, precision)


def forward_each(weights, sequences, shape, precision="float32",
                 state_precision=None, rows=None, keep=None, watch=None):
    """``forward`` for several sequences, each by itself (nothing is batched),
    with the layers outermost: ``weights["layers"]`` is walked once, so a
    generator that makes a layer when it is asked for makes each layer once
    for all of them and holds ONE layer's float32 weights at a time (the
    layer before is waited for and dropped first).  ``rows`` is one slice a
    sequence.  Returns a list of logits; with ``keep`` (one position a
    sequence; rows after it are not to be read, see ``mamba``) also, a
    sequence, the recurrent states ``[H, P, N]`` that position left, one an
    ``M`` layer in the order of the layers.  ``watch(where)`` is called with
    nothing in flight after each layer."""
    frozen = _frozen(shape)
    states = [[] for _ in sequences]
    with jax.default_matmul_precision("highest"):
        xs = [weights["embed"][tokens] for tokens in sequences]
        layers = iter(weights["layers"])
        for index, kind in enumerate(shape["pattern"]):
            lw = next(layers)
            for i, x in enumerate(xs):
                xs[i], state = _block_jit(
                    x, lw, kind=kind, shape=frozen, precision=precision,
                    state_precision=state_precision,
                    keep=None if keep is None else jnp.int32(keep[i]),
                )
                if state is not None:
                    states[i].append(state)
            # the next layer's weights are made only once this one's work is
            # done and its weights can go
            jax.block_until_ready(xs)
            del lw
            if watch is not None:
                watch(f"layer {index} {kind}")
        out = []
        for i, x in enumerate(xs):
            if rows is not None:
                x = x[rows[i]]
            out.append(_head_jit(
                x, weights["lnf_g"], weights["head"], eps=shape["eps"],
                precision=precision,
            ))
    return out if keep is None else (out, states)


def forward(weights, tokens, shape, precision="float32", state_precision=None,
            rows=None):
    """Logits ``[rows, V]`` in float32 for one sequence ``tokens`` ``[T]`` at
    positions ``0..T-1`` (``rows``: a slice of positions, default all).

    ``shape``: ``pattern`` (one letter a layer), ``eps``, ``mamba_num_heads``,
    ``mamba_head_dim``, ``ssm_state_size``, ``n_groups``, ``conv_kernel``,
    ``num_experts_per_tok``, ``routed_scaling_factor``, ``held``.
    ``weights["layers"]`` may be any iterable.  One compiled block per layer
    kind and length of ``tokens``."""
    return forward_each(
        weights, [tokens], shape, precision, state_precision,
        None if rows is None else [rows],
    )[0]
