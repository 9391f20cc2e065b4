"""The Mamba-2 mixer: a block's token mixer that carries a recurrent state
where attention carries keys and values.

For one layer, with ``u`` the normed residual ``[batch, T, d_model]`` and
``d_inner = heads * head_dim``::

    [z | xBC | dt] = W_in u                       # d_inner | d_inner + 2 G N | heads
    xBC_t = silu(b + sum_j w[j] * xBC_{t-(K-1)+j})   # depthwise, causal, width K
    [x | B | C] = xBC                             # [heads, head_dim] | [G, N] | [G, N]
    dt_t = softplus(dt_t + dt_bias);  A = -exp(A_log)
    S_t = exp(dt_t A) S_{t-1} + dt_t * x_t (outer) B_t;  y_t = S_t C_t + D x_t
    y = rmsnorm_G(y * silu(z)) * g                # the gate BEFORE the norm; the mean
                                                  # square over each of G groups apart
    out = W_out y

The recurrence runs in :mod:`tpu_parallel.ops.ssd_scan`: the chunked scan for
a call of several tokens (prefill, a prompt chunk), the one-token update for
decode.

**Cache** (``decode=True``): ``ssm_state`` ``[batch, heads, head_dim, N]``
float32 and ``conv_state`` ``[batch, K - 1, d_inner + 2 G N]``, the last
``K - 1`` conv inputs.  A call starts from what they hold (zeros when the
call creates them: a fresh prefill) and leaves them at its last real token.
There is no position table: the state is a summary, nothing in it can be cut
or rolled back by position.

**The pad rule** (one, for every caller).  A token whose position is
negative is a pad: it changes neither ``ssm_state`` nor ``conv_state``, and
no real token's output depends on it (``dt := 0``, its conv input counts as
zero; the conv window a call leaves is its last ``K - 1`` REAL inputs, older
ones taken from the window it started with).  That is the bucketed prefill's
right padding, ``generate()``'s left padding, the unified tick's padded
chunk block and the fused tick's parked slots alike.  The real tokens of a
row are one run; a call that continues a state pads on the right (a
left-padded row starts from zeros, where pads and "before the start" are the
same thing).
"""

from __future__ import annotations

from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from tpu_parallel.models.layers import SSMSpec, TransformerConfig
from tpu_parallel.ops.ssd_scan import ssd_scan, ssd_step
from tpu_parallel.parallel.tp import axis_size_or_none


def dt_bias_init(lo: float = 1e-3, hi: float = 1e-1):
    """The inverse softplus of a step drawn log-uniformly in ``[lo, hi]``."""

    def init(key, shape, dtype=jnp.float32):
        dt = jnp.exp(jax.random.uniform(
            key, shape, jnp.float32, jnp.log(lo), jnp.log(hi)
        ))
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)

    return init


def a_log_init(key, shape, dtype=jnp.float32):
    """``log`` of a decay rate drawn uniformly in ``[1, 16]``."""
    return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0)).astype(dtype)


def conv_init(key, shape, dtype=jnp.float32):
    return jax.random.uniform(key, shape, dtype, -0.5, 0.5)


def last_inputs(window: jax.Array, inputs: jax.Array, valid: jax.Array):
    """The conv window a call leaves: the last ``K - 1`` entries of ``window``
    ``[b, K - 1, c]`` followed by the call's REAL ``inputs`` ``[b, T, c]``
    (``valid`` ``[b, T]``, one run a row)."""
    keep = window.shape[1]
    if inputs.shape[1] == 1:  # decode: shift one in, or stay
        moved = jnp.concatenate(
            [window[:, 1:], inputs.astype(window.dtype)], axis=1
        )
        return jnp.where(valid[:, :, None], moved, window)
    count = valid.sum(axis=1).astype(jnp.int32)  # real tokens a row
    start = jnp.argmax(valid, axis=1).astype(jnp.int32)  # the run's first
    j = jnp.arange(keep, dtype=jnp.int32)[None, :] + count[:, None]
    # entry j of [window ; real inputs]: the window below `keep`, else the run
    idx = jnp.where(j < keep, j, start[:, None] + j)
    both = jnp.concatenate([window, inputs.astype(window.dtype)], axis=1)
    return jnp.take_along_axis(both, idx[:, :, None], axis=1)


class GroupRMSNorm(nn.Module):
    """RMSNorm whose mean square is taken over each of ``groups`` equal runs
    of the last axis apart (Mamba-2's gate norm with ``n_groups > 1``: a
    group's channels are normalised among themselves), one ``scale`` over
    the whole axis; float32."""

    groups: int
    epsilon: float

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        width = x.shape[-1]
        scale = self.param("scale", nn.initializers.ones, (width,))
        xg = x.astype(jnp.float32).reshape(
            *x.shape[:-1], self.groups, width // self.groups
        )
        xg = xg * jax.lax.rsqrt(
            jnp.mean(jnp.square(xg), axis=-1, keepdims=True) + self.epsilon
        )
        return xg.reshape(x.shape) * scale.astype(jnp.float32)


class SSMMixer(nn.Module):
    """One layer's Mamba-2 mixer (the module docstring has the equations,
    the cache and the pad rule).  Takes the keyword arguments
    :class:`~tpu_parallel.models.layers.Attention` takes, so that a block
    calls either; not tensor-parallel."""

    config: TransformerConfig
    spec: SSMSpec

    @nn.compact
    def __call__(
        self,
        u: jax.Array,
        positions: Optional[jax.Array] = None,
        segment_ids: Optional[jax.Array] = None,
        train: bool = True,
        decode: bool = False,
        cache_valid: Optional[jax.Array] = None,
        attn_bias: Optional[jax.Array] = None,
        write_index: Optional[jax.Array] = None,
        block_table: Optional[jax.Array] = None,
    ) -> jax.Array:
        cfg, s = self.config, self.spec
        del train, attn_bias, write_index  # a state row is the batch row
        if (axis_size_or_none(cfg.model_axis) or 1) > 1:
            raise NotImplementedError("a recurrent mixer under a model axis")
        if segment_ids is not None or cache_valid is not None:
            raise NotImplementedError(
                "a recurrent mixer with packed sequences or under the "
                "pipeline's decode ring"
            )
        if block_table is not None:
            raise NotImplementedError(
                "a recurrent state in the block-paged pool (a state has no "
                "positions to page)"
            )
        b, t, _ = u.shape
        d_inner = s.n_heads * s.head_dim
        bc = s.n_groups * s.d_state
        conv_dim = d_inner + 2 * bc
        keep = s.d_conv - 1
        valid = (
            jnp.ones((b, t), bool) if positions is None else positions >= 0
        )

        with jax.named_scope("ssm.in_proj"):
            zxbcdt = nn.Dense(
                2 * d_inner + 2 * bc + s.n_heads, use_bias=False,
                dtype=cfg.dtype, name="in_proj",
            )(u)
        z, xbc, dt = jnp.split(zxbcdt, [d_inner, d_inner + conv_dim], axis=-1)
        xbc = jnp.where(valid[:, :, None], xbc, 0)  # a pad's input is zero

        window = state = None
        if decode:
            conv_state = self.variable(
                "cache", "conv_state", jnp.zeros, (b, keep, conv_dim), cfg.dtype
            )
            ssm_state = self.variable(
                "cache", "ssm_state", jnp.zeros,
                (b, s.n_heads, s.head_dim, s.d_state), jnp.float32,
            )
            window, state = conv_state.value, ssm_state.value
        with jax.named_scope("ssm.conv"):
            w = self.param("conv_weight", conv_init, (s.d_conv, conv_dim))
            bias = self.param("conv_bias", nn.initializers.zeros, (conv_dim,))
            before = (
                jnp.zeros((b, keep, conv_dim), xbc.dtype) if window is None
                else window
            )
            taps = jnp.concatenate([before, xbc], axis=1).astype(jnp.float32)
            acc = bias.astype(jnp.float32)
            for j in range(s.d_conv):  # an explicit shifted sum
                acc = acc + w[j].astype(jnp.float32) * taps[:, j:j + t]
            xbc_out = nn.silu(acc).astype(cfg.dtype)
            if decode:
                conv_state.value = last_inputs(window, xbc, valid)

        x, B, C = jnp.split(xbc_out, [d_inner, d_inner + bc], axis=-1)
        x = x.reshape(b, t, s.n_heads, s.head_dim)
        B = B.reshape(b, t, s.n_groups, s.d_state)
        C = C.reshape(b, t, s.n_groups, s.d_state)
        dt_bias = self.param("dt_bias", dt_bias_init(), (s.n_heads,))
        A = -jnp.exp(self.param("A_log", a_log_init, (s.n_heads,)).astype(jnp.float32))
        D = self.param("D", nn.initializers.ones, (s.n_heads,))
        dt = jax.nn.softplus(dt.astype(jnp.float32) + dt_bias.astype(jnp.float32))
        if t == 1 and decode:
            with jax.named_scope("ssm.step"):
                y, final = ssd_step(
                    x[:, 0], dt[:, 0], A, B[:, 0], C[:, 0], D, state,
                    valid[:, 0],
                )
                y = y[:, None]
        else:
            # the call's rows x tokens ride in the scope: a trace tells the
            # prefill programs of a bucket ladder apart by it
            with jax.named_scope("ssm.scan"), jax.named_scope(f"call{b}x{t}"):
                y, final = ssd_scan(
                    x, dt, A, B, C, D, state, valid, chunk=s.chunk
                )
        if decode:
            ssm_state.value = final

        with jax.named_scope("ssm.gate_norm"):
            gated = y.reshape(b, t, d_inner).astype(jnp.float32) * nn.silu(
                z.astype(jnp.float32)
            )
            if s.n_groups == 1:
                norm = nn.RMSNorm(
                    epsilon=cfg.norm_eps, dtype=jnp.float32, name="gate_norm"
                )
            else:
                norm = GroupRMSNorm(s.n_groups, cfg.norm_eps, name="gate_norm")
            y = norm(gated).astype(cfg.dtype)
        with jax.named_scope("ssm.out_proj"):
            return nn.Dense(
                cfg.d_model, use_bias=False, dtype=cfg.dtype, name="out_proj"
            )(y)
