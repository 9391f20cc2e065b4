"""The plain reference: GPT-2's forward pass, its loss, gradients and AdamW.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: no kernels, no cache, no
batching tricks, and nothing imported from ``tpu_parallel``.  It takes the
benchmark's own weights (``benchmarks/lib/weights.py`` makes them from the
seed), in this file's own layout:

    {"wte": [V, d], "wpe": [S, d], "lnf_g": [d], "lnf_b": [d], "head": [d, V],
     "layers": [{"ln1_g", "ln1_b", "wq": [d, H, hd], "wk", "wv",
                 "bq": [H, hd], "bk", "bv", "wo": [H, hd, d], "bo": [d],
                 "ln2_g", "ln2_b", "w_up": [d, 4d], "b_up", "w_down", "b_down"}]}

Departures from the published GPT-2, all of them the served configuration's
(listed under ``assumed`` in the configuration files): the output head is a
matrix of its own and not the transposed token embedding, and the vocabulary
is held as 50304 rows.  Pre-norm blocks, learned positions, LayerNorm with
eps 1e-5 and the tanh form of GELU are as published.

``precision`` selects what the matmul operands are rounded to before an
fp32-accumulated product: ``"float32"`` (nothing; the reference itself),
``"bfloat16"``, or ``"float8"`` (e4m3 with a per-tensor scale).  The lower
precisions exist only as the controls that ``correct`` has to fail.
"""

import functools
import math

import jax
import jax.numpy as jnp

LN_EPS = 1e-5


def _round(x, precision):
    if precision == "float32":
        return x
    if precision == "bfloat16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    if precision == "float8":
        # per-tensor scale to e4m3's largest finite value, as an fp8 matmul
        # path would hold its operands
        scale = 448.0 / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
        return (x * scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) / scale
    raise ValueError(f"unknown precision {precision!r}")


def _mm(spec, a, b, precision):
    return jnp.einsum(
        spec, _round(a, precision), _round(b, precision),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )


def layer_norm(x, g, b):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + LN_EPS) * g + b


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)
    ))


def block(x, lw, precision="float32"):
    """One pre-norm transformer block over ``x`` ``[B, T, d]``, causal."""
    t = x.shape[1]
    h = layer_norm(x, lw["ln1_g"], lw["ln1_b"])
    q = _mm("btd,dhk->bthk", h, lw["wq"], precision) + lw["bq"]
    k = _mm("btd,dhk->bthk", h, lw["wk"], precision) + lw["bk"]
    v = _mm("btd,dhk->bthk", h, lw["wv"], precision) + lw["bv"]
    scores = _mm("bqhk,bshk->bhqs", q, k, precision) / math.sqrt(q.shape[-1])
    causal = jnp.tril(jnp.ones((t, t), bool))
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    ctx = _mm("bhqs,bshk->bqhk", probs, v, precision)
    x = x + _mm("bqhk,hkd->bqd", ctx, lw["wo"], precision) + lw["bo"]
    h = layer_norm(x, lw["ln2_g"], lw["ln2_b"])
    up = gelu_tanh(_mm("btd,df->btf", h, lw["w_up"], precision) + lw["b_up"])
    return x + _mm("btf,fd->btd", up, lw["w_down"], precision) + lw["b_down"]


def forward(weights, tokens, precision="float32", remat=False):
    """Logits ``[B, T, V]`` in float32 for ``tokens`` ``[B, T]`` at positions
    ``0..T-1``."""
    with jax.default_matmul_precision("highest"):
        t = tokens.shape[1]
        x = weights["wte"][tokens] + weights["wpe"][:t][None]
        step = functools.partial(block, precision=precision)
        if remat:
            step = jax.checkpoint(step)
        for lw in weights["layers"]:
            x = step(x, lw)
        x = layer_norm(x, weights["lnf_g"], weights["lnf_b"])
        return _mm("btd,dv->btv", x, weights["head"], precision)


_block_jit = jax.jit(block, static_argnames=("precision",))


@functools.partial(jax.jit, static_argnames=("precision",))
def _head_jit(x, g, b, head, precision):
    return _mm("btd,dv->btv", layer_norm(x, g, b), head, precision)


def forward_layerwise(weights, tokens, precision="float32"):
    """``forward`` with one compiled block reused by every layer, so a deep
    model costs one block's compilation for each shape of ``tokens``.
    ``weights["layers"]`` may be any iterable (a generator that makes a
    layer when it is asked for keeps one layer in memory at a time)."""
    with jax.default_matmul_precision("highest"):
        t = tokens.shape[1]
        x = weights["wte"][tokens] + weights["wpe"][:t][None]
        for lw in weights["layers"]:
            x = _block_jit(x, lw, precision=precision)
        return _head_jit(
            x, weights["lnf_g"], weights["lnf_b"], weights["head"],
            precision=precision,
        )


def loss_sum(weights, tokens, targets, precision="float32"):
    """Summed next-token cross-entropy over every position of the rows."""
    logits = forward(weights, tokens, precision, remat=True)
    logz = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return jnp.sum(logz - picked)


def loss_and_grads(weights, tokens, targets, block_rows, precision="float32"):
    """Mean loss over all tokens and its gradients, accumulated over blocks of
    ``block_rows`` rows so that the fp32 logits of one block fit."""
    fn = jax.jit(
        jax.value_and_grad(functools.partial(loss_sum, precision=precision))
    )
    n = tokens.shape[0]
    total, grads = 0.0, None
    for lo in range(0, n, block_rows):
        val, g = fn(weights, tokens[lo:lo + block_rows],
                    targets[lo:lo + block_rows])
        total = total + val
        grads = g if grads is None else jax.tree.map(jnp.add, grads, g)
    count = tokens.size
    return total / count, jax.tree.map(lambda g: g / count, grads)


def warmup_cosine(step, peak, warmup_steps, decay_steps, end_frac=0.1):
    """Linear warm-up from 0 to ``peak``, then a cosine down to
    ``end_frac * peak`` at ``decay_steps``."""
    if step < warmup_steps:
        return peak * step / warmup_steps
    frac = min((step - warmup_steps) / max(decay_steps - warmup_steps, 1), 1.0)
    return peak * (end_frac + (1 - end_frac) * 0.5 * (1 + math.cos(math.pi * frac)))


@functools.partial(jax.jit, static_argnames=("b1", "b2", "eps"))
def _adamw_apply(params, mu, nu, grads, lr, wd, count, b1=0.9, b2=0.999,
                 eps=1e-8):
    mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, mu, grads)
    nu = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, nu, grads)
    c1, c2 = 1 - b1 ** count, 1 - b2 ** count
    params = jax.tree.map(
        lambda p, m, v: p - lr * ((m / c1) / (jnp.sqrt(v / c2) + eps) + wd * p),
        params, mu, nu,
    )
    return params, mu, nu


def global_norm(tree):
    return jnp.sqrt(sum(jnp.sum(jnp.square(x)) for x in jax.tree.leaves(tree)))


def clip_by_global_norm(grads, max_norm):
    norm = global_norm(grads)
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(norm, 1e-30))
    return jax.tree.map(lambda g: g * scale, grads)


class AdamW:
    """Global-norm clipping, then AdamW (decay on every parameter) under a
    warm-up/cosine schedule: the optimizer the training cells state."""

    def __init__(self, params, opt: dict):
        self.opt = opt
        self.mu = jax.tree.map(jnp.zeros_like, params)
        self.nu = jax.tree.map(jnp.zeros_like, params)
        self.count = 0

    def step(self, params, grads):
        """Returns ``(new params, the gradient as AdamW got it)``."""
        o = self.opt
        clipped = clip_by_global_norm(grads, o["grad_clip"])
        lr = warmup_cosine(
            self.count, o["learning_rate"], o["warmup_steps"],
            max(o["steps"], o["warmup_steps"] + 1),
        )
        self.count += 1
        params, self.mu, self.nu = _adamw_apply(
            params, self.mu, self.nu, clipped, lr, o["weight_decay"],
            float(self.count),
        )
        return params, clipped
