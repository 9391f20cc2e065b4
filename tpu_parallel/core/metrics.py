"""Collective-synced (sum, count) metrics.

Capability parity: the reference's metric convention (``util.py:18``,
``print_metrics`` at ``util.py:170-181``, psum sync at ``data_paral.py:220-228``)
— metrics are pytrees of ``(sum, count)`` pairs, so syncing is one ``psum`` and
accumulation across steps is a tree-add.  The reference's ``metics`` typo bug
(``data_paral.py:231``) is, naturally, not reproduced.
"""

from __future__ import annotations

from typing import Dict, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
from jax import lax

Metrics = Dict[str, Tuple[jax.Array, jax.Array]]


def vma_of(x) -> Tuple[str, ...]:
    """The mesh axes ``x`` is varying over (empty outside shard_map).

    Works on traced arrays and on ``jax.eval_shape`` results.
    """
    # sorted: .vma is a frozenset, and hash-randomized iteration order would
    # vary the axes tuples baked into jaxprs run-to-run (compile-cache poison)
    return tuple(sorted(jax.typeof(x).vma))


def pvary_missing(x: jax.Array, axis_names: Sequence[str]) -> jax.Array:
    """Promote ``x`` to "varying" over any of ``axis_names`` it isn't yet.

    Under shard_map's replication checker (check_vma=True) a collective may
    only reduce over axes its operand varies on; a metric computed from
    replicated inputs (e.g. an eval loss on a broadcast batch) is *invarying*
    over the data axis and a bare ``psum(x, "data")`` is rejected.  The
    promotion is semantically free — the per-device values are identical, so
    the sum simply multiplies by the axis size exactly as it did with the
    checker off.  Outside shard_map (no vma tracking) this is a no-op.
    """
    vma = jax.typeof(x).vma
    missing = tuple(a for a in axis_names if a not in vma)
    return lax.pcast(x, missing, to="varying") if missing else x


def metric(value: jax.Array, count: Union[int, jax.Array] = 1) -> Tuple[jax.Array, jax.Array]:
    """Build one (sum, count) entry. ``value`` should already be a sum."""
    return (jnp.asarray(value, jnp.float32), jnp.asarray(count, jnp.float32))


def sync_metrics(
    metrics: Metrics,
    axis_names: Union[str, Sequence[str]],
    mean_axes: Union[str, Sequence[str]] = (),
) -> Metrics:
    """All-reduce metric sums and counts over the given mesh axes.

    ``axis_names``: axes whose ranks hold *disjoint* tokens (data, seq, and
    pipe under last-stage masking) — summed.  ``mean_axes``: axes whose ranks
    compute *replicated* metrics (the tensor-parallel axis) — averaged, so
    token counts stay exact instead of multiplying by the axis size.
    """
    if isinstance(axis_names, str):
        axis_names = (axis_names,)
    if isinstance(mean_axes, str):
        mean_axes = (mean_axes,)

    def _sync(x):
        if axis_names:
            x = lax.psum(pvary_missing(x, axis_names), axis_names)
        if mean_axes:
            x = lax.pmean(pvary_missing(x, mean_axes), mean_axes)
        return x

    with jax.named_scope("sync_metrics"):
        return jax.tree_util.tree_map(_sync, metrics)


def accumulate_metrics(running: Optional[Metrics], step: Metrics) -> Metrics:
    """Tree-add a step's metrics into the running totals."""
    if running is None:
        return step
    return jax.tree_util.tree_map(jnp.add, running, step)


def zeros_like_metrics(shapes) -> Metrics:
    """Zero-initialized pytree matching an ``eval_shape`` result.

    Works for any pytree of ``ShapeDtypeStruct``s (metrics, gradient
    accumulators, scan carries).
    """
    return jax.tree_util.tree_map(lambda s: jnp.zeros(s.shape, s.dtype), shapes)


def compute(metrics: Metrics) -> Dict[str, float]:
    """Device-get and reduce each (sum, count) to a host-side mean."""
    host = jax.device_get(metrics)
    return {k: float(s) / max(float(c), 1e-8) for k, (s, c) in host.items()}


def format_metrics(metrics: Metrics, title: Optional[str] = None) -> str:
    vals = compute(metrics)
    lines = []
    if title:
        lines.append(f" {title} ".center(32, "="))
    for k in sorted(vals):
        lines.append(f"{k}: {vals[k]:.6f}")
    return "\n".join(lines)


def print_metrics(metrics: Metrics, title: Optional[str] = None) -> None:
    print(format_metrics(metrics, title))
