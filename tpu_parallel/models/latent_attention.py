"""Latent attention (MLA): keys and values of ALL heads from one stored latent.

A layer stores ``kv_rank + rope_dim`` numbers a position (``LatentSpec.row``:
the RMS-normed latent ``c`` and ONE rotary key ``k_rope`` that every head
shares) where :class:`~tpu_parallel.models.layers.Attention` stores ``2 x
kv_heads x head_dim``.  With ``h`` the block's normed input::

    cq = RMSNorm(h W_dq)                        # q_rank
    [q_nope_i ; q_rope_i] = cq W_uq,i           # nope_dim + rope_dim a head
    [ckv ; k_rope] = h W_dkv                    # kv_rank + rope_dim
    c = RMSNorm(ckv)                            # THE ROW IS [c ; rope(k_rope)]
    k_nope_i = c W_uk,i        v_i = c W_uv,i   # nope_dim, v_dim a head
    score_i(t, s) = (q_nope_i(t) . k_nope_i(s) + rope(q_rope_i(t)) . rope(k_rope(s)))
                    / sqrt(nope_dim + rope_dim),   s <= t, softmax over s
    out = [sum_s p_i(t, s) v_i(s)]_i W_o

ONE set of parameters, TWO forms that give the same numbers in another order:

- **expanded** (a forward without a cache; a prefill that creates its cache
  under ``prefill_flash``): the prompt's latents are up-projected once to
  ``k_i = [k_nope_i ; k_rope]`` and ``v_i`` and attention runs over them, under
  ``prefill_flash`` / ``attn_impl="flash"`` through the flash kernels at a
  query-key width (192) that differs from the value width (128):
  ``ops.flash_attention.flash_attention_fwd_bhsd``;
- **absorbed** (every other cached call: a decode step, a prefill without
  ``prefill_flash``): ``W_uk`` goes into the query (``q'_i = W_uk,i^T
  q_nope_i``, ``kv_rank`` wide) and ``W_uv`` into the output (``v-part =
  W_uv,i o'_i`` with ``o'_i = sum_s p_i c(s)``), so the stored rows are read
  as they lie and never expanded: one stored head of ``row`` columns scored,
  its first ``kv_rank`` columns summed.  The product against the stored rows
  is :func:`~tpu_parallel.models.layers.decode_attention_xla` with one K/V
  head (``ops.decode_attention.decode_attention_plan`` says ``xla`` for a head
  of 576; a kernel over the latent rows is not written).

The row is stored in ``config.dtype``: the expanded form up-projects the SAME
rounded ``c`` that it stores, so both forms start from one row.

Cache leaves (``decode=True``): ``cached_latent`` ``[batch, seq_len, row]``,
the position table ``cached_pos`` ``[batch, seq_len]`` (-1: nothing stored)
and the scalar ``cache_index``; writes follow :class:`Attention`'s contract
(``write_index`` per row, out-of-range targets dropped).  What a latent layer
does not run with is refused at construction
(``layers.check_layer_kinds``); a model axis larger than 1 here.

Trace scopes: ``attn.latent`` around the layer, and inside it ``mla.q_proj``,
``mla.kv_down``, ``mla.kv_up`` (the expansion), ``mla.absorb`` (the two
per-head products of the absorbed form), ``mla.scores`` (the products against
the stored rows, under ``mla.scores/stored``; in the expanded form the
attention itself, the flash kernel under ``mla.scores/flash<rows>x<seq>``) and
``mla.out_proj``.
"""

from __future__ import annotations

from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from tpu_parallel.models.layers import (
    LatentSpec,
    TransformerConfig,
    apply_rope,
    causal_attention,
    decode_attention_xla,
)
from tpu_parallel.parallel.tp import axis_size_or_none


class _Matrix(nn.Module):
    """One ``[rows, cols]`` matrix under ``<name>/kernel`` (variance
    ``1 / rows``), handed out in ``dtype``: the two forms contract the same
    matrices along different axes, so they are parameters and not layers."""

    rows: int
    cols: int
    dtype: jnp.dtype

    @nn.compact
    def __call__(self) -> jax.Array:
        kernel = self.param(
            "kernel", nn.initializers.lecun_normal(), (self.rows, self.cols),
            jnp.float32,
        )
        return jnp.asarray(kernel, self.dtype)


class LatentAttention(nn.Module):
    """Causal multi-head attention over a shared latent (module docstring);
    :class:`~tpu_parallel.models.layers.Attention`'s call signature."""

    config: TransformerConfig
    spec: LatentSpec

    @nn.compact
    def __call__(
        self,
        x: jax.Array,
        positions: Optional[jax.Array] = None,
        segment_ids: Optional[jax.Array] = None,
        train: bool = True,
        decode: bool = False,
        cache_valid: Optional[jax.Array] = None,
        attn_bias: Optional[jax.Array] = None,
        write_index: Optional[jax.Array] = None,
        block_table: Optional[jax.Array] = None,
    ) -> jax.Array:
        cfg, sp = self.config, self.spec
        if (axis_size_or_none(cfg.model_axis) or 1) > 1:
            raise NotImplementedError(
                "latent attention under a model axis larger than 1: the "
                "latent cache is shared by all heads, so splitting heads "
                "over chips would copy it to each"
            )
        if attn_bias is not None or block_table is not None:
            raise NotImplementedError(
                "latent attention takes no score bias and no block table"
            )
        if decode and segment_ids is not None:
            raise NotImplementedError(
                "incremental decoding with packed sequences (segment_ids)"
            )
        b, t = x.shape[:2]
        heads, dtype = cfg.n_heads, cfg.dtype
        qk_dim = sp.nope_dim + sp.rope_dim
        matrix = lambda name, rows, cols: _Matrix(rows, cols, dtype, name=name)()
        rms = lambda name, y: nn.RMSNorm(
            epsilon=cfg.norm_eps, dtype=jnp.float32, name=name
        )(y).astype(dtype)
        cached = idx = None
        if decode:
            cached = self.variable(
                "cache", "cached_latent", jnp.zeros,
                (b, cfg.seq_len, sp.row), dtype,
            )
            fresh_prefill = (
                cfg.prefill_flash and t > 1 and write_index is None
                and not self.has_variable("cache", "cached_pos")
            )
            cached_p = self.variable(
                "cache", "cached_pos",
                lambda: jnp.full((b, cfg.seq_len), -1, jnp.int32),
            )
            cache_index = self.variable(
                "cache", "cache_index", lambda: jnp.zeros((), jnp.int32)
            )
            idx = cache_index.value
            if positions is None:
                positions = jnp.broadcast_to(idx + jnp.arange(t)[None, :], (b, t))
        elif positions is None:
            positions = jnp.broadcast_to(jnp.arange(t), (b, t))
        rope = lambda y: apply_rope(y, positions, cfg.rope_theta, cfg.rope_pairing)
        with jax.named_scope("attn.latent"):
            with jax.named_scope("mla.q_proj"):
                cq = rms("q_norm", x @ matrix("q_down", cfg.d_model, sp.q_rank))
                q = (cq @ matrix("q_up", sp.q_rank, heads * qk_dim)).reshape(
                    b, t, heads, qk_dim
                )
                q_nope, q_rope = q[..., :sp.nope_dim], rope(q[..., sp.nope_dim:])
            with jax.named_scope("mla.kv_down"):
                ckv = x @ matrix("kv_down", cfg.d_model, sp.row)
                c = rms("kv_norm", ckv[..., :sp.kv_rank])
                k_rope = rope(ckv[..., None, sp.kv_rank:])[:, :, 0]
                row = jnp.concatenate([c, k_rope], axis=-1)  # what is stored
            w_uk = matrix("k_up", sp.kv_rank, heads * sp.nope_dim).reshape(
                sp.kv_rank, heads, sp.nope_dim
            )
            w_uv = matrix("v_up", sp.kv_rank, heads * sp.v_dim).reshape(
                sp.kv_rank, heads, sp.v_dim
            )
            scale = cfg.attn_scale if cfg.attn_scale is not None else qk_dim ** -0.5
            rows_all = pos_all = None
            if decode:
                rows_all, pos_all = self._store(
                    cached, cached_p, cache_index, row, positions, idx,
                    write_index, cache_valid,
                )
            if decode and not fresh_prefill:
                out = self._absorbed(
                    q_nope, q_rope, w_uk, w_uv, rows_all, pos_all, positions,
                    scale,
                )
            else:
                flash = fresh_prefill if decode else cfg.attn_impl == "flash"
                out = self._expanded(
                    q_nope, q_rope, c, k_rope, w_uk, w_uv, segment_ids, scale,
                    flash,
                )
            with jax.named_scope("mla.out_proj"):
                w_o = matrix("out", heads * sp.v_dim, cfg.d_model)
                out = jnp.einsum(
                    "bthv,hvd->btd", out, w_o.reshape(heads, sp.v_dim, cfg.d_model)
                )
        if cfg.dropout_rate > 0.0:
            out = nn.Dropout(rate=cfg.dropout_rate, deterministic=not train)(out)
        return out

    def _store(self, cached, cached_p, cache_index, row, positions, idx,
               write_index, cache_valid):
        """Write the new rows and their positions (:class:`Attention`'s
        contract: per-row ``write_index`` with out-of-range targets dropped,
        else the shared scalar index; ``cache_valid`` gates persistence) and
        return the stripe and table the read uses."""
        b, t = row.shape[:2]
        if write_index is not None:
            wi = write_index.astype(jnp.int32)
            if wi.ndim == 1:
                wi = wi[:, None] + jnp.arange(t)[None, :]
            at = jnp.arange(b)[:, None]
            upd = lambda buf, new: buf.at[at, wi].set(new.astype(buf.dtype))
        else:
            upd = lambda buf, new: lax.dynamic_update_slice_in_dim(
                buf, new.astype(buf.dtype), idx, axis=1
            )
        keep = (lambda new, old: new) if cache_valid is None else (
            lambda new, old: jnp.where(cache_valid, new, old)
        )
        rows_all = upd(cached.value, row)
        pos_all = upd(cached_p.value, positions.astype(jnp.int32))
        cached.value = keep(rows_all, cached.value)
        cached_p.value = keep(pos_all, cached_p.value)
        cache_index.value = keep(idx + t, idx)
        return rows_all, pos_all

    def _absorbed(self, q_nope, q_rope, w_uk, w_uv, rows_all, pos_all,
                  positions, scale):
        """New queries against the stored rows, which are never expanded:
        ``[batch, t, heads, v_dim]``."""
        sp = self.spec
        with jax.named_scope("mla.absorb"):
            q_lat = jnp.einsum("bthn,rhn->bthr", q_nope, w_uk)
        with jax.named_scope("mla.scores"), jax.named_scope("stored"):
            # one stored head of ``row`` columns scored and summed; the rotary
            # columns of the sum are dropped (a twelfth more work than the
            # first kv_rank columns alone, and no copy of the stripe)
            o_lat = decode_attention_xla(
                jnp.concatenate([q_lat, q_rope], axis=-1),
                rows_all[:, :, None, :], rows_all[:, :, None, :], positions,
                k_positions=pos_all, scale=scale,
            )[..., :sp.kv_rank]
        with jax.named_scope("mla.absorb"):
            return jnp.einsum("bthr,rhv->bthv", o_lat, w_uv)

    def _expanded(self, q_nope, q_rope, c, k_rope, w_uk, w_uv, segment_ids,
                  scale, flash):
        """Attention within the call over up-projected keys and values:
        ``[batch, t, heads, v_dim]``.  The flash kernels take (and the
        expansion writes) ``[batch, heads, seq, width]``, XLA's attention
        ``[batch, seq, heads, width]``."""
        if flash and segment_ids is not None:
            raise NotImplementedError(
                "packed sequences through the latent layer's flash path"
            )
        out_axes = "bhs" if flash else "bsh"
        with jax.named_scope("mla.kv_up"):
            k_nope = jnp.einsum(f"bsr,rhn->{out_axes}n", c, w_uk)
            v = jnp.einsum(f"bsr,rhv->{out_axes}v", c, w_uv)
            one_key = k_rope[:, None] if flash else k_rope[:, :, None]
            k = jnp.concatenate([
                k_nope,  # the ONE rotary key beside every head's own part
                jnp.broadcast_to(one_key, (*k_nope.shape[:3], k_rope.shape[-1])),
            ], axis=-1)
        q = jnp.concatenate([q_nope, q_rope], axis=-1)
        if not flash:
            with jax.named_scope("mla.scores"):
                return causal_attention(
                    q, k, v, segment_ids=segment_ids, scale=scale
                )
        from tpu_parallel.ops.flash_attention import flash_attention_fwd_bhsd

        # the kernels scale by width ** -0.5 themselves: fold the rest in
        q = q.transpose(0, 2, 1, 3) * jnp.asarray(
            scale * q.shape[-1] ** 0.5, q.dtype
        )
        call = f"flash{q.shape[0]}x{q.shape[2]}"  # the call's shape, for a trace
        with jax.named_scope("mla.scores"), jax.named_scope(call):
            out = flash_attention_fwd_bhsd(
                q, k, v, block_q=self.config.flash_block_q,
                block_k=self.config.flash_block_k,
            )
        return out.transpose(0, 2, 1, 3)
