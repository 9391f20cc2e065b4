"""Loss functions in the (params, apply_fn, batch, rng) -> (loss, metrics) shape.

Capability parity: the reference's two near-identical ``loss_fn``s
(``data_paral.py:171-189``, ``param_sharding.py:325-340``) — softmax CE with
``(sum, count)`` metrics and dropout RNG folded over the mesh so replicas
decorrelate.  Generalized with an LM variant for the transformer configs.
"""

from __future__ import annotations

from typing import Callable, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import optax

from tpu_parallel.core.metrics import Metrics
from tpu_parallel.core.rng import fold_rng_over_axis
from tpu_parallel.core.state import Batch, TextBatch

AxisNames = Union[str, Sequence[str]]


def _row_statistics(logits: jax.Array, targets: jax.Array):
    """ONE pass over the vocabulary: per row, in fp32, the maximum, the sum
    of ``exp(x - maximum)``, the target's logit and the first index of the
    maximum — a variadic reduce whose combiner carries a running maximum
    (the recurrence the flash kernels use for a row of scores)."""
    x = logits.astype(jnp.float32)
    axis = x.ndim - 1
    idx = jax.lax.broadcasted_iota(jnp.int32, x.shape, axis)
    hit = jnp.where(idx == targets[..., None], x, 0.0)

    def combine(a, b):
        (m1, s1, i1, t1), (m2, s2, i2, t2) = a, b
        m = jnp.maximum(m1, m2)
        s = s1 * jnp.exp(m1 - m) + s2 * jnp.exp(m2 - m)
        i = jnp.where(m1 > m2, i1, jnp.where(m2 > m1, i2, jnp.minimum(i1, i2)))
        return m, s, i, t1 + t2

    # a finite floor, not -inf: the reduction may combine two inits, and
    # exp(-inf - -inf) is not a number
    init = (
        jnp.float32(jnp.finfo(jnp.float32).min), jnp.float32(0.0),
        jnp.int32(jnp.iinfo(jnp.int32).max), jnp.float32(0.0),
    )
    return jax.lax.reduce((x, jnp.ones_like(x), idx, hit), init, combine, (axis,))


@jax.custom_vjp
def token_ce_and_argmax(
    logits: jax.Array, targets: jax.Array
) -> Tuple[jax.Array, jax.Array]:
    """``(ce, pred)`` per token from logits of any dtype whose vocabulary
    is whole on the chip: fp32 loss and the ``argmax`` token (first index on
    ties), both from the four row statistics of :func:`_row_statistics`.

    Forward and backward are written by hand so that what crosses HBM is
    decided here and not by what autodiff saves: the residuals are the
    logits AS GIVEN (bf16 from a bf16 head) and the fp32 ``[rows]``
    log-sum-exp; ``d logits = (exp(logits - lse) - onehot) * g`` is formed
    in fp32 from those, rounded to the logits' dtype once (the cotangent of
    the upcast) and written once for the head's two backward matmuls.  No
    fp32 ``[rows, vocab]`` tensor is saved, written or returned.
    """
    return _token_ce_fwd(logits, targets)[0]


def _token_ce_fwd(logits, targets):
    with jax.named_scope("cross_entropy"):
        row_max, sum_exp, pred, target_logit = _row_statistics(logits, targets)
        lse = row_max + jnp.log(sum_exp)
        return (lse - target_logit, pred), (logits, lse, targets)


def _token_ce_bwd(residuals, cotangents):
    logits, lse, targets = residuals
    g = cotangents[0]
    with jax.named_scope("cross_entropy"):
        x = logits.astype(jnp.float32)
        idx = jax.lax.broadcasted_iota(jnp.int32, x.shape, x.ndim - 1)
        onehot = (idx == targets[..., None]).astype(jnp.float32)
        d = ((jnp.exp(x - lse[..., None]) - onehot) * g[..., None]).astype(
            logits.dtype
        )
        # Written ONCE, into a buffer of its own, and read by the head's two
        # backward matmuls as plain operands.  Without the barrier XLA forms
        # d logits twice, inside each matmul as its producer (cheaper by
        # itself: 26.2 against 28.3 ms a pass of 16 x 1024 x 50304 on a
        # v5e); held beside the logits rather than over them because XLA
        # plans the train step by its peak memory (PERF.md section 6, PR
        # 46: under about 9.2 GB the rest of the step runs 16 ms longer).
        d, _ = jax.lax.optimization_barrier((d, logits))
        return d, None


token_ce_and_argmax.defvjp(_token_ce_fwd, _token_ce_bwd)


def token_cross_entropy(logits: jax.Array, targets: jax.Array) -> jax.Array:
    """Per-token CE with fp32 math from logits of any dtype.

    Models emit bf16 logits (their matmuls already round to bf16 — a model-
    side fp32 cast would only double the [B, S, vocab] HBM footprint, the
    dominant buffer at GPT-2 vocab sizes).  The upcast happens inside
    :func:`token_ce_and_argmax`'s one reduction and, in the backward, inside
    the fusion that forms ``d logits``: no fp32 logits tensor materializes,
    and none is saved for the backward (autodiff through a library softmax
    saved the fp32 ``exp(logits - max)`` of every row).

    Runs under ``jax.named_scope("cross_entropy")``: XLA's fusion names
    change with every compile, the scope in their metadata does not
    (docs/05_performance.md).
    """
    return token_ce_and_argmax(logits, targets)[0]


def vocab_parallel_argmax(logits: jax.Array, axis_name: str) -> jax.Array:
    """Global argmax over vocab-sharded logits [..., vocab/tp]: each shard
    nominates its local winner; the shard(s) holding the global max win,
    lowest id on ties (matching ``argmax``'s first-occurrence convention on
    gathered logits).  Two scalar-per-row collectives, no gather."""
    vs = logits.shape[-1]
    offset = jax.lax.axis_index(axis_name) * vs
    lf = logits.astype(jnp.float32)
    local_max = lf.max(axis=-1)
    global_max = jax.lax.pmax(jax.lax.stop_gradient(local_max), axis_name)
    local_arg = lf.argmax(axis=-1).astype(jnp.int32) + offset
    nominee = jnp.where(local_max == global_max, local_arg, jnp.int32(2**31 - 1))
    return jax.lax.pmin(nominee, axis_name)


def vocab_parallel_cross_entropy(
    logits: jax.Array, targets: jax.Array, axis_name: str
) -> Tuple[jax.Array, jax.Array]:
    """Per-token CE on vocab-sharded logits — no full-vocab gather, ever.

    ``logits`` [..., vocab/tp] is this rank's column-parallel lm_head shard
    (shard i owns the contiguous vocab range [i*vs, (i+1)*vs)); ``targets``
    [...] are global token ids.  Megatron-style: the softmax statistics are
    assembled from three scalar-per-token collectives over ``axis_name``
    (pmax of the row max, psum of the shifted sum-of-exp, psum of the
    owning shard's target logit) — O(batch*seq) communication instead of
    the O(batch*seq*vocab) all_gather a gathered lm_head needs.

    Returns ``(ce, pred)``: fp32 per-token loss and the global argmax token
    id (ties across shards break to the lowest id, matching ``argmax``'s
    first-occurrence convention on gathered logits).
    """
    vs = logits.shape[-1]
    offset = jax.lax.axis_index(axis_name) * vs
    lf = logits.astype(jnp.float32)  # fuses into the reductions on TPU
    local_max = lf.max(axis=-1)
    # stability shift only — lse is invariant to it in exact arithmetic, so
    # a zero derivative is correct; stopping the *input* keeps AD from ever
    # tracing pmax (which has no differentiation rule)
    global_max = jax.lax.pmax(jax.lax.stop_gradient(local_max), axis_name)
    sum_exp = jnp.exp(lf - global_max[..., None]).sum(axis=-1)
    lse = global_max + jnp.log(jax.lax.psum(sum_exp, axis_name))
    # the correct-class logit lives on exactly one shard; fetch via psum
    t_local = targets - offset
    owns = (t_local >= 0) & (t_local < vs)
    safe_idx = jnp.clip(t_local, 0, vs - 1)
    own_logit = jnp.take_along_axis(lf, safe_idx[..., None], axis=-1)[..., 0]
    target_logit = jax.lax.psum(jnp.where(owns, own_logit, 0.0), axis_name)
    ce = lse - target_logit
    pred = vocab_parallel_argmax(logits, axis_name)
    return ce, pred


def make_classification_loss(fold_axes: AxisNames = "data") -> Callable:
    """Softmax-CE loss for ``Batch``; dropout rng folded over ``fold_axes``."""

    def loss_fn(params, apply_fn, batch: Batch, rng: jax.Array):
        dropout_rng = fold_rng_over_axis(rng, fold_axes)
        logits = apply_fn(
            {"params": params}, batch.inputs, train=True, rngs={"dropout": dropout_rng}
        )
        loss = optax.softmax_cross_entropy_with_integer_labels(logits, batch.labels)
        correct = (logits.argmax(-1) == batch.labels).sum()
        bs = batch.labels.size
        metrics: Metrics = {
            "loss": (loss.sum(), jnp.float32(bs)),
            "accuracy": (correct.astype(jnp.float32), jnp.float32(bs)),
        }
        return loss.mean(), metrics

    return loss_fn


def make_lm_loss(fold_axes: AxisNames = "data") -> Callable:
    """Next-token cross-entropy for ``TextBatch`` with loss masking."""

    def loss_fn(params, apply_fn, batch: TextBatch, rng: jax.Array):
        dropout_rng = fold_rng_over_axis(rng, fold_axes)
        logits = apply_fn(
            {"params": params},
            batch.tokens,
            positions=batch.positions,
            train=True,
            rngs={"dropout": dropout_rng},
        )
        loss, pred = token_ce_and_argmax(logits, batch.targets)
        mask = (
            batch.loss_mask
            if batch.loss_mask is not None
            else jnp.ones_like(loss, jnp.float32)
        )
        with jax.named_scope("cross_entropy"):
            loss = loss * mask
            n_tok = mask.sum()
            correct = ((pred == batch.targets) * mask).sum()
        metrics: Metrics = {
            "loss": (loss.sum(), n_tok),
            "accuracy": (correct.astype(jnp.float32), n_tok),
        }
        return loss.sum() / jnp.maximum(n_tok, 1.0), metrics

    return loss_fn
