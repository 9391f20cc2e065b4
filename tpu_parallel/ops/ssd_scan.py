"""The selective state-space recurrence of a Mamba-2 layer, chunked.

The definition, per head ``h`` (state ``S`` is ``[head_dim, d_state]``)::

    S_t = exp(dt_t A) S_{t-1} + dt_t * x_t (outer) B_t
    y_t = S_t C_t + D x_t

:func:`ssd_scan` computes it for a whole call in the block-decomposed
("state-space dual") form: the sequence is cut into chunks of ``chunk``
tokens; INSIDE a chunk the outputs are one masked matrix product (``C B^T``
weighed by the decays between the two positions, times ``x``), BETWEEN
chunks the state is carried by a short recurrence over the chunks.  The work
is matrix products and no ``[T, heads, head_dim, d_state]`` tensor is ever
formed.  Decays, their cumulative sums and the state are float32 whatever the
type of ``x``; the products take ``x``'s type with float32 accumulation.

:func:`ssd_step` is the same recurrence for one token a row: the decode step,
one read and one write of the state.

**Pads.**  ``valid`` ``[batch, T]`` marks the real tokens.  A token that is
not valid has ``dt := 0``: the state passes it unchanged (decay 1, nothing
added) and no valid token's output depends on it.  Its own output is
garbage nobody reads.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax


def _heads(bc: jax.Array, n_heads: int) -> jax.Array:
    """``[..., groups, d_state]`` -> ``[..., heads, d_state]``: every head of
    a group shares the group's B (or C)."""
    groups = bc.shape[-2]
    if groups == n_heads:
        return bc
    return jnp.repeat(bc, n_heads // groups, axis=-2)


def ssd_step(
    x: jax.Array, dt: jax.Array, A: jax.Array, B: jax.Array, C: jax.Array,
    D: jax.Array, state: jax.Array, valid: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array]:
    """One token a row.  ``x`` ``[b, heads, head_dim]``, ``dt`` ``[b, heads]``
    (after the softplus), ``A`` / ``D`` ``[heads]``, ``B`` / ``C`` ``[b,
    groups, d_state]``, ``state`` ``[b, heads, head_dim, d_state]`` float32,
    ``valid`` ``[b]``.  Returns ``(y [b, heads, head_dim] in x's type, state')``;
    a row that is not valid keeps its state."""
    h = x.shape[1]
    xf = x.astype(jnp.float32)
    dt = dt.astype(jnp.float32)
    Bh = _heads(B, h).astype(jnp.float32)
    Ch = _heads(C, h).astype(jnp.float32)
    decay = jnp.exp(dt * A.astype(jnp.float32))
    new = (
        decay[:, :, None, None] * state
        + (dt[:, :, None] * xf)[..., None] * Bh[:, :, None, :]
    )
    if valid is not None:
        new = jnp.where(valid[:, None, None, None], new, state)
    # a float32 multiply-and-sum, not a dot: the matrix unit would round the
    # state to bfloat16, and this way the read fuses with the update
    y = (new * Ch[:, :, None, :]).sum(-1) + D.astype(jnp.float32)[:, None] * xf
    return y.astype(x.dtype), new


def ssd_scan(
    x: jax.Array, dt: jax.Array, A: jax.Array, B: jax.Array, C: jax.Array,
    D: jax.Array, init_state: Optional[jax.Array] = None,
    valid: Optional[jax.Array] = None, chunk: int = 256,
) -> Tuple[jax.Array, jax.Array]:
    """A whole call.  ``x`` ``[b, T, heads, head_dim]``, ``dt`` ``[b, T,
    heads]`` (after the softplus), ``A`` / ``D`` ``[heads]``, ``B`` / ``C``
    ``[b, T, groups, d_state]``, ``init_state`` ``[b, heads, head_dim,
    d_state]`` (None: zeros; a fresh prefill), ``valid`` ``[b, T]`` (None:
    every token).  Returns ``(y [b, T, heads, head_dim] in x's type,
    final_state float32)``.  ``T`` need not be a multiple of ``chunk``: the
    tail is padded with tokens that are not valid."""
    b, t, h, p = x.shape
    n = B.shape[-1]
    f32 = jnp.float32
    dt = dt.astype(f32)
    if valid is not None:
        dt = jnp.where(valid[:, :, None], dt, 0.0)
    size = min(chunk, t)
    pad = -t % size
    if pad:
        widths = lambda a: ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2)
        x, dt, B, C = (jnp.pad(a, widths(a)) for a in (x, dt, B, C))
    nc = (t + pad) // size
    cut = lambda a: a.reshape(b, nc, size, *a.shape[2:])
    xc, dtc = cut(x), cut(dt)
    Bc, Cc = cut(_heads(B, h)), cut(_heads(C, h))
    # log-decay from the chunk's start up to and including each token
    acs = jnp.cumsum(dtc * A.astype(f32), axis=2)  # [b, nc, L, h]
    total = acs[:, :, -1]  # [b, nc, h]: the whole chunk's
    xdt = (xc.astype(f32) * dtc[..., None]).astype(x.dtype)  # dt_j x_j

    # inside a chunk: y_i = sum_{j<=i} exp(acs_i - acs_j) (C_i . B_j) dt_j x_j
    scores = jnp.einsum(
        "bclhn,bcshn->bchls", Cc, Bc, preferred_element_type=f32
    )
    gap = acs.transpose(0, 1, 3, 2)  # [b, nc, h, L]
    gap = gap[..., :, None] - gap[..., None, :]
    causal = jnp.tril(jnp.ones((size, size), bool))
    weights = jnp.where(causal, jnp.exp(jnp.where(causal, gap, 0.0)), 0.0)
    y = jnp.einsum(
        "bchls,bcshp->bclhp", (scores * weights).astype(x.dtype), xdt,
        preferred_element_type=f32,
    )

    # what each chunk adds to the state by its end, from a zero start
    to_end = jnp.exp(total[:, :, None] - acs)  # [b, nc, L, h]
    added = jnp.einsum(
        "bclhp,bclhn->bchpn",
        (xdt.astype(f32) * to_end[..., None]).astype(x.dtype), Bc,
        preferred_element_type=f32,
    )

    # between chunks: the state at each chunk's start
    if init_state is None:
        init_state = jnp.zeros((b, h, p, n), f32)

    def carry(state, inputs):
        decay, add = inputs
        return decay[:, :, None, None] * state + add, state

    final, starts = lax.scan(
        carry, init_state.astype(f32),
        (jnp.exp(total).swapaxes(0, 1), added.swapaxes(0, 1)),
    )
    starts = starts.swapaxes(0, 1)  # [b, nc, h, p, n]
    # the carried state is read in float32 (six passes of the matrix unit
    # over a product a hundredth of the chunk's own)
    y = y + jnp.exp(acs)[..., None] * jnp.einsum(
        "bclhn,bchpn->bclhp", Cc.astype(f32), starts,
        precision=lax.Precision.HIGHEST,
    )
    y = y + D.astype(f32)[:, None] * xc.astype(f32)
    return y.reshape(b, nc * size, h, p)[:, :t].astype(x.dtype), final
