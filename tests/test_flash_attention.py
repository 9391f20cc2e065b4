"""Flash-attention kernel tests (interpret mode on CPU; same code as TPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import make_packed_segments as _packed_segments
from tpu_parallel.ops.flash_attention import (
    flash_attention,
    reference_attention,
)


def _make_qkv(rng, b=2, s=256, h=2, d=64, dtype=jnp.float32):
    ks = jax.random.split(rng, 3)
    shape = (b, s, h, d)
    return tuple(jax.random.normal(k, shape, dtype) for k in ks)


def _ref_bshd(q, k, v):
    out = reference_attention(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)
    )
    return out.transpose(0, 2, 1, 3)


def test_forward_matches_reference(rng):
    q, k, v = _make_qkv(rng)
    out = flash_attention(q, k, v, block_q=64, block_k=64, interpret=True)
    ref = _ref_bshd(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-3, atol=2e-3)


def test_forward_rectangular_blocks(rng):
    q, k, v = _make_qkv(rng, s=256)
    out = flash_attention(q, k, v, block_q=128, block_k=64, interpret=True)
    ref = _ref_bshd(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-3, atol=2e-3)


def test_gradients_match_reference(rng):
    q, k, v = _make_qkv(rng, b=1, s=128, h=2, d=32)

    def loss_flash(q, k, v):
        return (flash_attention(q, k, v, block_q=64, block_k=64, interpret=True) ** 2).sum()

    def loss_ref(q, k, v):
        return (_ref_bshd(q, k, v) ** 2).sum()

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=5e-3, atol=5e-3,
            err_msg=f"d{name} mismatch",
        )


def test_causality(rng):
    """Future tokens must not influence earlier outputs."""
    q, k, v = _make_qkv(rng, b=1, s=128, h=1, d=32)
    out1 = flash_attention(q, k, v, block_q=64, block_k=64, interpret=True)
    # perturb the last 64 positions of k/v: first 64 outputs must be unchanged
    k2 = k.at[:, 64:].add(1.0)
    v2 = v.at[:, 64:].add(1.0)
    out2 = flash_attention(q, k2, v2, block_q=64, block_k=64, interpret=True)
    np.testing.assert_allclose(
        np.asarray(out1[:, :64]), np.asarray(out2[:, :64]), rtol=1e-5, atol=1e-5
    )
    assert not np.allclose(np.asarray(out1[:, 64:]), np.asarray(out2[:, 64:]))


def test_bf16_runs(rng):
    q, k, v = _make_qkv(rng, dtype=jnp.bfloat16, s=128)
    out = flash_attention(q, k, v, block_q=64, block_k=64, interpret=True)
    ref = _ref_bshd(q, k, v)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), rtol=5e-2, atol=5e-2
    )


def test_fallback_on_odd_shapes(rng):
    """Indivisible seq falls back to the reference path, still correct."""
    q, k, v = _make_qkv(rng, s=100)
    out = flash_attention(q, k, v, block_q=64, block_k=64, interpret=True)
    ref = _ref_bshd(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-3, atol=2e-3)


def test_attention_hook_in_model(rng):
    """flash_attention plugs into the model's attn_fn hook (bshd contract)."""
    from tpu_parallel.models.layers import causal_attention

    q, k, v = _make_qkv(rng, s=128)
    # model layers call attn_fn(q, k, v, segment_ids=...) in [B,S,H,D]
    out_hook = flash_attention(q, k, v, segment_ids=None, interpret=True)
    out_model = causal_attention(q, k, v, segment_ids=None)
    np.testing.assert_allclose(
        np.asarray(out_hook), np.asarray(out_model), rtol=2e-3, atol=2e-3
    )





def test_packed_forward_matches_reference(rng):
    """segment_ids run in-kernel (no fallback) and match the masked reference."""
    q, k, v = _make_qkv(rng, b=2, s=256)
    seg = _packed_segments(jax.random.PRNGKey(9), 2, 256)
    out = flash_attention(
        q, k, v, segment_ids=seg, block_q=64, block_k=64, interpret=True
    )
    ref = reference_attention(
        q.transpose(0, 2, 1, 3),
        k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3),
        segment_ids=seg,
    ).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-3, atol=2e-3)


def test_packed_no_cross_segment_leakage(rng):
    """Perturbing segment 0's K/V must not change segment 1+ outputs."""
    q, k, v = _make_qkv(rng, b=1, s=128, h=1, d=32)
    seg = jnp.concatenate(
        [jnp.zeros((1, 64), jnp.int32), jnp.ones((1, 64), jnp.int32)], axis=1
    )
    out1 = flash_attention(q, k, v, segment_ids=seg, block_q=64, block_k=64, interpret=True)
    k2 = k.at[:, :64].add(1.0)
    v2 = v.at[:, :64].add(1.0)
    out2 = flash_attention(q, k2, v2, segment_ids=seg, block_q=64, block_k=64, interpret=True)
    np.testing.assert_allclose(
        np.asarray(out1[:, 64:]), np.asarray(out2[:, 64:]), rtol=1e-5, atol=1e-5
    )
    assert not np.allclose(np.asarray(out1[:, :64]), np.asarray(out2[:, :64]))


def test_packed_gradients_match_reference(rng):
    q, k, v = _make_qkv(rng, b=1, s=128, h=2, d=32)
    seg = _packed_segments(jax.random.PRNGKey(4), 1, 128)

    def loss_flash(q, k, v):
        return (
            flash_attention(
                q, k, v, segment_ids=seg, block_q=64, block_k=64, interpret=True
            )
            ** 2
        ).sum()

    def loss_ref(q, k, v):
        out = reference_attention(
            q.transpose(0, 2, 1, 3),
            k.transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3),
            segment_ids=seg,
        ).transpose(0, 2, 1, 3)
        return (out**2).sum()

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=5e-3, atol=5e-3,
            err_msg=f"d{name} mismatch",
        )


def test_packed_model_trains_with_flash(rng):
    """End-to-end: a GPT with attn_impl='flash' accepts packed batches."""
    import optax
    from jax.sharding import PartitionSpec as P

    from tpu_parallel.core import compute
    from tpu_parallel.core.state import TextBatch, TrainState
    from tpu_parallel.data import lm_batch
    from tpu_parallel.models import GPTLM, make_gpt_loss, tiny_test
    from tpu_parallel.parallel.spmd import build_train_functions
    from tpu_parallel.runtime import MeshConfig, make_mesh

    mesh = make_mesh(MeshConfig(data=8))
    cfg = tiny_test(attn_impl="flash", seq_len=64)
    base = lm_batch(jax.random.PRNGKey(0), 16, cfg.seq_len, cfg.vocab_size)
    seg = np.asarray(_packed_segments(jax.random.PRNGKey(2), 16, cfg.seq_len))
    batch = TextBatch(
        tokens=base.tokens, targets=base.targets, loss_mask=base.loss_mask,
        positions=base.positions, segment_ids=seg,
    )
    model = GPTLM(cfg)
    tx = optax.adamw(3e-3)

    def init(rng_, b):
        v = model.init({"params": rng_}, b.tokens, train=False)["params"]
        return TrainState.create(apply_fn=model.apply, params=v, tx=tx, rng=rng_)

    funcs = build_train_functions(
        init, make_gpt_loss(cfg), mesh, batch, batch_spec=P("data"), donate=False,
        # interpret-mode pallas inside the step: JAX vma limitation (see spmd)
        check_vma=False,
    )
    state = funcs.init_fn(rng, batch)
    state, m0 = funcs.step_fn(state, None, batch)
    first = compute(m0)["loss"]
    for _ in range(5):
        state, m = funcs.step_fn(state, None, batch)
    assert compute(m)["loss"] < first


# --- sliding window -----------------------------------------------------------


@pytest.mark.fast
def test_window_matches_masked_reference(rng):
    """Flash sliding window == dense attention with an explicit band mask."""
    import jax.numpy as jnp

    from tpu_parallel.models.layers import causal_attention

    b, s, h, d = 1, 256, 2, 32
    ks = jax.random.split(rng, 3)
    q, k, v = (jax.random.normal(kk, (b, s, h, d)) for kk in ks)
    for window in (32, 64, 100):
        out = flash_attention(
            q, k, v, block_q=64, block_k=64, window=window, interpret=True
        )
        ref = causal_attention(q, k, v, window=window)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=2e-3, atol=2e-3,
            err_msg=f"window={window}",
        )


@pytest.mark.fast
def test_window_gradients_match(rng):
    from tpu_parallel.models.layers import causal_attention

    b, s, h, d = 1, 128, 2, 16
    ks = jax.random.split(rng, 3)
    q, k, v = (jax.random.normal(kk, (b, s, h, d)) for kk in ks)

    def loss_flash(q, k, v):
        return (
            flash_attention(
                q, k, v, block_q=32, block_k=32, window=48, interpret=True
            )
            ** 2
        ).sum()

    def loss_ref(q, k, v):
        return (causal_attention(q, k, v, window=48) ** 2).sum()

    g_f = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_r = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, bb, name in zip(g_f, g_r, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(bb), rtol=5e-3, atol=5e-3,
            err_msg=f"d{name}",
        )


def test_window_decode_matches_train_forward(rng):
    """A windowed model decodes with the same logits its training forward
    produces (the decode mask must apply the same band)."""
    from tpu_parallel.models import GPTLM, tiny_test

    cfg = tiny_test(dtype=jnp.float32, remat=False, attn_window=8, seq_len=32)
    model = GPTLM(cfg)
    prompt = jax.random.randint(rng, (2, 20), 0, cfg.vocab_size)
    params = model.init({"params": jax.random.PRNGKey(1)}, prompt, train=False)[
        "params"
    ]
    full = model.apply({"params": params}, prompt, train=False)
    decoded, _ = model.apply(
        {"params": params}, prompt, train=False, decode=True, mutable=["cache"]
    )
    np.testing.assert_allclose(
        np.asarray(decoded), np.asarray(full), rtol=1e-4, atol=1e-4
    )


# --- grouped-query attention (native: K/V never expanded) ---------------------


def _gqa_ref(q, k, v, segment_ids=None):
    """Expand K/V heads and run the dense reference — GQA ground truth."""
    group = q.shape[2] // k.shape[2]
    ke = jnp.repeat(k, group, axis=2)
    ve = jnp.repeat(v, group, axis=2)
    return reference_attention(
        q.transpose(0, 2, 1, 3),
        ke.transpose(0, 2, 1, 3),
        ve.transpose(0, 2, 1, 3),
        segment_ids=segment_ids,
    ).transpose(0, 2, 1, 3)


def _make_gqa(rng, b=2, s=256, h=4, h_kv=2, d=32, dtype=jnp.float32):
    ks = jax.random.split(rng, 3)
    q = jax.random.normal(ks[0], (b, s, h, d), dtype)
    k = jax.random.normal(ks[1], (b, s, h_kv, d), dtype)
    v = jax.random.normal(ks[2], (b, s, h_kv, d), dtype)
    return q, k, v


@pytest.mark.parametrize("stream", [False, True])
def test_gqa_forward_matches_expanded_reference(rng, stream):
    for h, h_kv in ((4, 2), (4, 1), (6, 3)):
        q, k, v = _make_gqa(rng, h=h, h_kv=h_kv)
        out = flash_attention(
            q, k, v, block_q=64, block_k=64, interpret=True, stream=stream
        )
        ref = _gqa_ref(q, k, v)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=2e-3, atol=2e-3,
            err_msg=f"h={h} h_kv={h_kv} stream={stream}",
        )


@pytest.mark.parametrize("stream", [False, True])
def test_gqa_gradients_match_expanded_reference(rng, stream):
    q, k, v = _make_gqa(rng, b=1, s=128, h=4, h_kv=2, d=32)

    def loss_flash(q, k, v):
        return (
            flash_attention(
                q, k, v, block_q=64, block_k=64, interpret=True, stream=stream
            )
            ** 2
        ).sum()

    def loss_ref(q, k, v):
        return (_gqa_ref(q, k, v) ** 2).sum()

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=5e-3, atol=5e-3,
            err_msg=f"d{name} mismatch (stream={stream})",
        )


def test_gqa_packed_window_matches_reference(rng):
    """GQA composes with segment ids and sliding window in-kernel."""
    from tpu_parallel.models.layers import causal_attention

    q, k, v = _make_gqa(rng, b=2, s=128, h=4, h_kv=2, d=32)
    seg = _packed_segments(jax.random.PRNGKey(7), 2, 128)
    out = flash_attention(
        q, k, v, segment_ids=seg, block_q=64, block_k=64, interpret=True
    )
    ref = _gqa_ref(q, k, v, segment_ids=seg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-3, atol=2e-3)
    # window (no segments)
    out_w = flash_attention(
        q, k, v, block_q=32, block_k=32, window=48, interpret=True
    )
    group = 2
    ref_w = causal_attention(
        q, jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2), window=48
    )
    np.testing.assert_allclose(
        np.asarray(out_w), np.asarray(ref_w), rtol=2e-3, atol=2e-3
    )


def test_gqa_model_flash_matches_xla(rng):
    """A GQA model forward agrees between attn_impl='flash' and 'xla'."""
    from tpu_parallel.models import GPTLM, tiny_test

    cfg_x = tiny_test(
        n_kv_heads=2, dtype=jnp.float32, remat=False, scan_layers=False,
        seq_len=64, attn_impl="xla",
    )
    cfg_f = tiny_test(
        n_kv_heads=2, dtype=jnp.float32, remat=False, scan_layers=False,
        seq_len=64, attn_impl="flash", flash_block_q=32, flash_block_k=32,
    )
    tokens = jax.random.randint(rng, (2, 64), 0, cfg_x.vocab_size)
    params = GPTLM(cfg_x).init({"params": jax.random.PRNGKey(0)}, tokens, train=False)[
        "params"
    ]
    lx = GPTLM(cfg_x).apply({"params": params}, tokens, train=False)
    lf = GPTLM(cfg_f).apply({"params": params}, tokens, train=False)
    np.testing.assert_allclose(np.asarray(lf), np.asarray(lx), rtol=2e-3, atol=2e-3)


def test_gqa_decode_matches_train_forward(rng):
    """GQA prefill-decode (kv-width cache, grouped einsum) == train forward."""
    from tpu_parallel.models import GPTLM, tiny_test

    cfg = tiny_test(n_kv_heads=2, dtype=jnp.float32, remat=False, seq_len=32)
    model = GPTLM(cfg)
    prompt = jax.random.randint(rng, (2, 20), 0, cfg.vocab_size)
    params = model.init({"params": jax.random.PRNGKey(1)}, prompt, train=False)[
        "params"
    ]
    full = model.apply({"params": params}, prompt, train=False)
    decoded, _ = model.apply(
        {"params": params}, prompt, train=False, decode=True, mutable=["cache"]
    )
    np.testing.assert_allclose(
        np.asarray(decoded), np.asarray(full), rtol=1e-4, atol=1e-4
    )


# --- streamed (long-sequence) kernels ----------------------------------------


@pytest.mark.parametrize("window", [0, 100])
def test_stream_forward_matches_resident(rng, window):
    q, k, v = _make_qkv(rng, b=1, s=256, h=2, d=32)
    out_r = flash_attention(
        q, k, v, block_q=64, block_k=64, window=window, interpret=True,
        stream=False,
    )
    out_s = flash_attention(
        q, k, v, block_q=64, block_k=64, window=window, interpret=True,
        stream=True,
    )
    np.testing.assert_allclose(
        np.asarray(out_s), np.asarray(out_r), rtol=1e-5, atol=1e-5
    )


def test_stream_packed_matches_reference(rng):
    q, k, v = _make_qkv(rng, b=2, s=256)
    seg = _packed_segments(jax.random.PRNGKey(9), 2, 256)
    out = flash_attention(
        q, k, v, segment_ids=seg, block_q=64, block_k=64, interpret=True,
        stream=True,
    )
    ref = reference_attention(
        q.transpose(0, 2, 1, 3),
        k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3),
        segment_ids=seg,
    ).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("window", [0, 48])
def test_stream_gradients_match_resident(rng, window):
    q, k, v = _make_qkv(rng, b=1, s=128, h=2, d=32)

    def loss(stream):
        def f(q, k, v):
            return (
                flash_attention(
                    q, k, v, block_q=32, block_k=32, window=window,
                    interpret=True, stream=stream,
                )
                ** 2
            ).sum()

        return f

    g_s = jax.grad(loss(True), argnums=(0, 1, 2))(q, k, v)
    g_r = jax.grad(loss(False), argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g_s, g_r, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-5,
            err_msg=f"d{name} (window={window})",
        )


def test_stream_chunk_attention_combines(rng):
    """flash_chunk_attention's streamed path (non-causal full chunks)."""
    from tpu_parallel.ops.flash_attention import flash_chunk_attention

    q, k, v = _make_qkv(rng, b=1, s=128, h=2, d=32)
    out_r, lse_r = flash_chunk_attention(
        q, k, v, causal=False, block_q=64, block_k=64, interpret=True,
        stream=False,
    )
    out_s, lse_s = flash_chunk_attention(
        q, k, v, causal=False, block_q=64, block_k=64, interpret=True,
        stream=True,
    )
    np.testing.assert_allclose(np.asarray(out_s), np.asarray(out_r), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(lse_s), np.asarray(lse_r), rtol=1e-5, atol=1e-5)


def test_stream_auto_dispatch_long_seq(rng):
    """The streamed kernels at seq 8192 stay correct (``stream=True``: since
    the forward's variant follows the bytes of a row, an 8192 x 64 row is
    resident by itself; a dense reference is impractical at 8k, so the check
    is the online softmax's self-consistency: output rows equal a direct jnp
    computation on a few sampled query positions)."""
    b, s, h, d = 1, 8192, 1, 64
    ks = jax.random.split(rng, 3)
    q, k, v = (
        jax.random.normal(kk, (b, s, h, d), jnp.float32) * 0.1 for kk in ks
    )
    out = flash_attention(
        q, k, v, block_q=512, block_k=512, interpret=True, stream=True
    )

    # dense ground truth at a handful of query positions
    for pos in (0, 511, 4096, 8191):
        qi = q[:, pos, 0]  # [b, d]
        scores = jnp.einsum("bd,bkd->bk", qi, k[:, : pos + 1, 0]) / jnp.sqrt(d)
        probs = jax.nn.softmax(scores, axis=-1)
        ref = jnp.einsum("bk,bkd->bd", probs, v[:, : pos + 1, 0])
        np.testing.assert_allclose(
            np.asarray(out[:, pos, 0]), np.asarray(ref), rtol=2e-3, atol=2e-3,
            err_msg=f"pos={pos}",
        )


def test_stream_long_seq_backward_runs(rng):
    """fwd+bwd at seq 8192 through the streamed kernels (grads finite)."""
    b, s, h, d = 1, 8192, 1, 64
    ks = jax.random.split(rng, 3)
    q, k, v = (
        jax.random.normal(kk, (b, s, h, d), jnp.float32) * 0.1 for kk in ks
    )

    def loss(q, k, v):
        return (
            flash_attention(
                q, k, v, block_q=512, block_k=512, interpret=True, stream=True
            ) ** 2
        ).sum()

    gq, gk, gv = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    for g, name in ((gq, "dq"), (gk, "dk"), (gv, "dv")):
        arr = np.asarray(g)
        assert np.isfinite(arr).all(), f"{name} has non-finite entries"
        assert np.abs(arr).max() > 0, f"{name} is all zero"


@pytest.mark.parametrize("q_offset", [32, 100, 140, -32, -100, -140])
def test_stream_offset_chunk_matches_resident(rng, q_offset):
    """Streamed kernels with a window q_offset (ring partial chunks) agree
    with the resident kernels — including empty rows (at q_offset=140 with
    window=40, rows past local index 26 see no keys at all: their partials
    must come back (0, NEG_INF) with exactly-zero gradients).  NEGATIVE
    offsets are the bidirectional ring's ahead chunks: the in-bounds
    clamps in the streamed index maps must hold there too (early q blocks
    see no keys; late k blocks see no queries)."""
    from tpu_parallel.ops.flash_attention import flash_chunk_attention

    b, s, h, d = 1, 128, 2, 32
    ks = jax.random.split(rng, 3)
    q, k, v = (jax.random.normal(kk, (b, s, h, d)) for kk in ks)
    window = 40

    def run(stream):
        def f(q, k, v):
            out, lse = flash_chunk_attention(
                q, k, v, causal=False, window=window, q_offset=q_offset,
                block_q=32, block_k=32, interpret=True, stream=stream,
            )
            return out, lse

        (out, lse), vjp = jax.vjp(f, q, k, v)
        grads = vjp((jnp.ones_like(out), jnp.ones_like(lse) * 0.1))
        return out, lse, grads

    out_r, lse_r, g_r = run(False)
    out_s, lse_s, g_s = run(True)
    np.testing.assert_allclose(np.asarray(out_s), np.asarray(out_r), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(lse_s), np.asarray(lse_r), rtol=1e-5, atol=1e-5)
    for a, b_, name in zip(g_s, g_r, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b_), rtol=1e-5, atol=1e-5,
            err_msg=f"d{name} (q_offset={q_offset})",
        )


@pytest.mark.fast
def test_remat_policy_sees_kernel_outputs(rng):
    """The finalize-pattern contract: the fwd kernels' out/lse are ordinary
    named jaxpr values, so a save_only_these_names("attn") remat policy
    keeps them and the backward graph contains NO forward-kernel re-run —
    2 pallas calls (fwd + the one resident backward), not 3.  Guards against re-hiding the
    forward inside the custom_vjp or dropping the checkpoint_name calls,
    for both the self-attention path and the chunk (ring/encoder) path."""
    from tpu_parallel.ops.flash_attention import flash_chunk_attention

    q, k, v = _make_qkv(rng, b=1, s=64, h=1, d=16)
    pol_save = jax.checkpoint_policies.save_only_these_names("attn")
    pol_none = jax.checkpoint_policies.save_only_these_names("nothing-matches")

    def chunk_block(q, k, v):
        out, lse = flash_chunk_attention(
            q, k, v, causal=True, block_q=32, block_k=32, interpret=True
        )
        return (out * 2).sum() + (lse * 0.1).sum()

    def self_block(q, k, v):
        out = flash_attention(q, k, v, block_q=32, block_k=32, interpret=True)
        return (out * 2).sum()

    for name, block in (("chunk", chunk_block), ("self", self_block)):
        counts = {}
        for pname, pol in (("saved", pol_save), ("unsaved", pol_none)):
            f = jax.checkpoint(block, policy=pol, prevent_cse=True)
            text = str(jax.make_jaxpr(jax.grad(f))(q, k, v))
            counts[pname] = text.count("pallas_call")
        assert counts["saved"] == 2, (name, counts)
        assert counts["unsaved"] == 3, (name, counts)


# --- the resident tile walk: every tile once, masked only at the band's edges --


def _dense_ref(q, k, v, *, causal=True, window=0, q_offset=0, seg_q=None,
               seg_k=None):
    """Dense fp32 attention on [b, s, h, d] with the kernels' band
    (``lo <= q_pos - k_pos <= hi``), GQA by expansion, segments, and the
    empty-partial contract: a row with no visible key is ``out = 0``,
    ``lse = NEG_INF`` and a constant.  Returns (out, lse [b, h, s], empty)."""
    from tpu_parallel.ops.flash_attention import NEG_INF

    group = q.shape[2] // k.shape[2]
    k, v = (jnp.repeat(x, group, axis=2) for x in (k, v))
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32)
    scores = scores / jnp.sqrt(q.shape[-1]).astype(jnp.float32)
    rel = (q_offset + jnp.arange(q.shape[1]))[:, None] - jnp.arange(k.shape[1])[None]
    mask = jnp.ones(rel.shape, bool)
    if causal:
        mask &= rel >= 0
    if window:
        mask &= rel < window
        if not causal:
            mask &= -rel < window
    mask = jnp.broadcast_to(mask, scores.shape)
    if seg_q is not None:
        mask &= (seg_q[:, :, None] == seg_k[:, None, :])[:, None]
    empty = ~mask.any(-1)
    masked = jnp.where(mask, scores, -1e30)
    lse = jnp.where(empty, NEG_INF, jax.nn.logsumexp(masked, axis=-1))
    p = jnp.where(mask, jnp.exp(masked - lse[..., None]), 0.0)
    p = jnp.where(empty[..., None], 0.0, p)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v), lse, empty


# (seq, block_q, block_k): rows of 1, 2, 4 and 8 tiles, and block_q != block_k
_WALKS = [(8, 8, 8), (16, 8, 8), (32, 8, 8), (64, 8, 8), (32, 16, 8)]


_HEADS = {"mha": (2, 2), "gqa2": (4, 2), "gqa4": (4, 1)}
_WINDOWS = {"full": 0, "win<tile": 5, "win>tile": 20}


def _walk_is_run(hn, packed, wn, walk):
    """The full product on the rows of 1 and 2 tiles and the rectangular
    walk.  Interpret mode traces every tile body of the static walk (group x
    tiles of them in the backward), so the long rows are thinned where they
    add no new band: the 4-tile row takes its windows with MHA only, the
    8-tile row is MHA on three band / segment combinations."""
    if walk == (64, 8, 8):
        return hn == "mha" and (packed, wn) in {
            (False, "full"), (False, "win<tile"), (True, "win>tile")
        }
    if walk == (32, 8, 8):
        return hn == "mha" or wn == "full"
    return True


_WALK_CASES = [
    pytest.param(*_HEADS[hn], packed, _WINDOWS[wn], *walk,
                 id=f"{hn}-{'packed' if packed else 'dense'}-{wn}-{walk[0]}-{walk[1]}x{walk[2]}")
    for hn in _HEADS for packed in (False, True) for wn in _WINDOWS
    for walk in _WALKS if _walk_is_run(hn, packed, wn, walk)
]


@pytest.mark.parametrize("h,h_kv,segments,window,seq,block_q,block_k", _WALK_CASES)
def test_resident_walk_matches_reference(rng, h, h_kv, segments, window, seq,
                                         block_q, block_k):
    """Forward and all three gradients of the resident kernels against the
    dense reference, over rows with zero, one and several unmasked tiles,
    the diagonal tile, windows inside one tile and across tiles, packed
    segments and GQA groups."""
    b, d = 1, 8
    ks = jax.random.split(jax.random.fold_in(rng, seq * 7 + window), 4)
    q = jax.random.normal(ks[0], (b, seq, h, d))
    k, v = (jax.random.normal(kk, (b, seq, h_kv, d)) for kk in ks[1:3])
    w = jax.random.normal(ks[3], (b, seq, h, d))
    seg = _packed_segments(jax.random.PRNGKey(seq), b, seq) if segments else None

    def flash(q, k, v):
        return flash_attention(
            q, k, v, segment_ids=seg, window=window, block_q=block_q,
            block_k=block_k, interpret=True, stream=False,
        )

    def ref(q, k, v):
        return _dense_ref(q, k, v, window=window, seg_q=seg, seg_k=seg)[0]

    out, vjp = jax.vjp(flash, q, k, v)
    out_ref, vjp_ref = jax.vjp(ref, q, k, v)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(out_ref), rtol=2e-3, atol=2e-3
    )
    for a, bb, name in zip(vjp(w), vjp_ref(w), "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(bb), rtol=5e-3, atol=5e-3,
            err_msg=f"d{name}",
        )


@pytest.mark.parametrize(
    "window,q_offset,segments,tile",
    [(12, 16, False, 16), (20, 40, False, 8), (12, -16, False, 16),
     (0, 0, True, 16), (24, 32, True, 16)],
    ids=["off16", "off40-some-tiles-skipped", "ahead16", "foreign-segments",
         "off32-packed"],
)
def test_chunk_guard_rows_without_a_visible_key(rng, window, q_offset, segments,
                                                tile):
    """The only users of the ``lse <= NEG_INF / 2`` guard: non-causal chunks
    whose window (at a static ``q_offset``) or whose segments leave rows
    with NO visible key.  With a nonzero cotangent on ``out`` AND on ``lse``
    for every row, empty rows must come back as empty partials and add
    nothing to any gradient."""
    from tpu_parallel.ops.flash_attention import NEG_INF, flash_chunk_attention

    b, s, h, h_kv, d = 2, 32, 4, 2, 8
    ks = jax.random.split(jax.random.fold_in(rng, 100 + q_offset), 5)
    q = jax.random.normal(ks[0], (b, s, h, d))
    k, v = (jax.random.normal(kk, (b, s, h_kv, d)) for kk in ks[1:3])
    w_out = jax.random.normal(ks[3], (b, s, h, d))
    w_lse = jax.random.normal(ks[4], (b, h, s))
    seg_q = seg_k = None
    if segments:
        # the kv chunk holds segments 0-1, the q chunk 1-2: segment 2's
        # queries match nothing
        seg_k = (jnp.arange(s)[None] >= s // 2).astype(jnp.int32) + jnp.zeros((b, 1), jnp.int32)
        seg_q = seg_k + 1
    kw = dict(causal=False, window=window, q_offset=q_offset)

    def flash(q, k, v):
        return flash_chunk_attention(
            q, k, v, block_q=tile, block_k=tile, interpret=True,
            segment_ids_q=seg_q, segment_ids_kv=seg_k, **kw,
        )

    (out, lse), vjp = jax.vjp(flash, q, k, v)
    (ref_out, ref_lse, empty), vjp_ref = jax.vjp(
        lambda *a: _dense_ref(*a, seg_q=seg_q, seg_k=seg_k, **kw), q, k, v
    )
    assert bool(empty.any()) and not bool(empty.all()), "the case must mix both"
    seen = ~np.asarray(empty)
    np.testing.assert_allclose(
        np.asarray(lse)[seen], np.asarray(ref_lse)[seen], rtol=2e-3, atol=2e-3
    )
    assert (np.asarray(lse)[~seen] <= NEG_INF / 2).all()
    seen_o = seen.transpose(0, 2, 1)[..., None]  # [b, s, h, 1]
    np.testing.assert_allclose(
        np.asarray(out) * seen_o, np.asarray(ref_out) * seen_o, rtol=2e-3, atol=2e-3
    )
    # dout != 0 and dlse != 0 on every row, the empty ones included
    g_flash = vjp((w_out, w_lse))
    g_ref = vjp_ref((w_out, w_lse, np.zeros(empty.shape, jax.dtypes.float0)))
    for a, bb, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(bb), rtol=5e-3, atol=5e-3,
            err_msg=f"d{name}",
        )


_BANDS = [
    # causal, window, q_offset, block_q, block_k, n_q, n_k
    (True, 0, 0, 8, 8, 4, 4),
    (True, 0, 0, 16, 8, 2, 4),
    (True, 5, 0, 8, 8, 4, 4),
    (True, 20, 0, 8, 8, 8, 8),
    (True, 20, 0, 8, 16, 8, 4),
    (False, 0, 0, 8, 8, 3, 3),
    (False, 12, 0, 8, 8, 4, 4),
    (False, 12, 16, 8, 8, 4, 4),
    (False, 20, -24, 8, 8, 4, 4),
    (False, 7, 40, 8, 8, 4, 4),
]


@pytest.mark.parametrize("causal,window,q_offset,block_q,block_k,n_q,n_k", _BANDS)
def test_tile_kind_is_the_band_mask_summarised(causal, window, q_offset, block_q,
                                               block_k, n_q, n_k):
    """``_tile_kind`` (what the static walk emits), ``_band_mask`` (what a
    masked tile applies, in both orientations) and the streamed kernels'
    block ranges are one band: SKIP = nothing visible, INTERIOR = everything
    visible, MASKED = the rest."""
    import importlib

    # the module: ``tpu_parallel.ops`` re-exports the function under its name
    fa = importlib.import_module("tpu_parallel.ops.flash_attention")
    for qi in range(n_q):
        first, last = fa._stream_k_range(
            qi, block_q, block_k, causal, window, n_k, q_offset
        )
        for ki in range(n_k):
            mask = fa._band_mask(
                qi, ki, (block_q, block_k), block_q, block_k, causal, window,
                q_offset,
            )
            mask = np.ones((block_q, block_k), bool) if mask is None else np.asarray(mask)
            mask_t = fa._band_mask(
                qi, ki, (block_k, block_q), block_q, block_k, causal, window,
                q_offset, transposed=True,
            )
            if mask_t is not None:
                np.testing.assert_array_equal(np.asarray(mask_t), mask.T)
            want = (
                fa.SKIP if not mask.any()
                else fa.INTERIOR if mask.all() else fa.MASKED
            )
            kind = fa._tile_kind(qi, ki, block_q, block_k, causal, window, q_offset)
            assert kind == want, (qi, ki)
            if kind != fa.SKIP:  # the streamed range may only be wider
                assert int(first) <= ki <= int(last), (qi, ki, first, last)


_PLAN_SHAPES = [
    (seq, d, group)
    for seq in (64, 128, 256, 512, 1024, 2048, 4096, 8192, 32768)
    for d in (64, 128)
    for group in (1, 4)
]


def _holds_the_variant_rule(plan, seq, head_dim, group):
    """A resident pass fits what its rule allows: the forward the VMEM budget
    by the bytes of the row's blocks and its live score tiles, the backward
    the row count; both a walk short enough to unroll."""
    from tpu_parallel.ops.flash_attention import (
        MAX_STATIC_TILES, RESIDENT_VMEM_BUDGET, STREAM_SEQ_THRESHOLD,
        _fwd_row_bytes, _resident_need,
    )

    for name, bodies in (("fwd", 1), ("bwd", group)):
        p = plan[name]
        assert seq % p["block_q"] == 0 and seq % p["block_k"] == 0
        assert p["block_q"] % p["block_k"] == 0
        assert 0 < p["tiles_masked"] <= p["tiles_computed"]
        if p["variant"] != "resident":
            continue
        assert bodies * p["tiles_computed"] <= MAX_STATIC_TILES
        if name == "fwd":
            need = _resident_need(
                _fwd_row_bytes(seq, seq, head_dim, jnp.bfloat16), 0,
                p["block_q"], p["block_k"],
            )
            assert need <= RESIDENT_VMEM_BUDGET
        else:
            assert group * seq <= STREAM_SEQ_THRESHOLD
    assert plan["fused_bwd"] == (plan["bwd"]["variant"] == "resident")


@pytest.mark.parametrize("seq,head_dim,group", _PLAN_SHAPES)
def test_derived_tiles_divide_the_row(seq, head_dim, group):
    """Every shape of the table gets tiles that divide it, a resident pass
    only where its rule allows one (the forward by bytes, the backward by
    rows) or the streamed kernels, and an explicit tile wins."""
    from tpu_parallel.ops.flash_attention import flash_plan

    plan = flash_plan(seq, head_dim, group)
    _holds_the_variant_rule(plan, seq, head_dim, group)
    # the forward of a row that fits is resident whatever its length: a
    # causal 8192 row at a tile of 512 is 136 bodies; 32768 rows do not fit
    assert plan["fwd"]["variant"] == ("resident" if seq <= 8192 else "streamed")
    explicit = flash_plan(seq, head_dim, group, block_q=64, block_k=32)
    for name in ("fwd", "bwd"):
        assert (explicit[name]["block_q"], explicit[name]["block_k"]) == (64, 32)


# the backward's plan of every shape above as PR 47 left it (tile, variant,
# tiles computed, tiles masked): PR 48 changed the forward's rule alone
_BACKWARD_PLANS = {
    64: (64, "resident", 1, 1), 128: (128, "resident", 1, 1),
    256: (256, "resident", 1, 1), 512: (256, "resident", 3, 2),
    1024: (256, "resident", 10, 4), 2048: (256, "resident", 36, 8),
    4096: (256, "resident", 136, 16), 8192: (512, "streamed", 136, 136),
    32768: (2048, "streamed", 136, 136),
}
_GROUPED_BACKWARD_PLANS = {  # a group of 4: streamed past 4096 / 4 rows
    **_BACKWARD_PLANS, 2048: (512, "streamed", 10, 10),
    4096: (512, "streamed", 36, 36),
}


@pytest.mark.parametrize("seq,head_dim,group", _PLAN_SHAPES)
def test_the_backwards_plan_is_the_one_it_had(seq, head_dim, group):
    from tpu_parallel.ops.flash_attention import flash_plan

    tile, variant, computed, masked = (
        _GROUPED_BACKWARD_PLANS if group == 4 else _BACKWARD_PLANS
    )[seq]
    assert flash_plan(seq, head_dim, group)["bwd"] == {
        "block_q": tile, "block_k": tile, "variant": variant,
        "tiles_computed": computed, "tiles_masked": masked,
    }
    if head_dim < 128 and seq <= 4096:  # the forward too: tiles of 512
        fwd = flash_plan(seq, head_dim, group)["fwd"]
        assert (fwd["block_q"], fwd["variant"]) == (min(seq, 512), "resident")


# the prefill shapes of the serving cells with heads of 128 and more (PERF.md
# section 6, PR 48): head width, group, rule, window, and each row with the
# forward tile that flash_plan derives (512 at 192 columns; at 128 columns
# one tile to 512 rows, then 128 grown to at most 16 tiles a side); all
# resident, cell 3's 6144 and 8192 rows under its window too
_CELL_FORWARD_PLANS = [
    (cell, seq, width, group, rule, window, tile)
    for cell, width, group, rule, windows, rows in (
        ("longdoc", 192, 1, True, (0,), {
            1024: 512, 2048: 512, 3072: 512, 4096: 512, 6144: 512, 8192: 512}),
        ("longshort", 128, 16, True, (0, 4096), {
            512: 512, 1024: 128, 1536: 128, 2048: 128, 3072: 256, 4096: 256,
            6144: 512, 8192: 512}),
        ("blockgen", 128, 8, 4, (0,), {
            256: 256, 512: 512, 1024: 128, 2048: 128, 3072: 256}),
        ("reasoning", 128, 16, True, (0,), {
            128: 128, 256: 256, 512: 512, 1024: 128, 2048: 128}),
    )
    for window in windows
    for seq, tile in rows.items()
]


@pytest.mark.parametrize(
    "cell,seq,width,group,rule,window,tile", _CELL_FORWARD_PLANS
)
def test_forward_plan_of_the_serving_cells(cell, seq, width, group, rule,
                                           window, tile):
    """One rule for every caller, keyed on shape alone (the head's width and
    the row): the forward tile the sweep found and the resident kernel while
    the row's blocks fit VMEM."""
    from tpu_parallel.ops.flash_attention import _count_tiles, flash_plan

    plan = flash_plan(seq, width, group, causal=rule, window=window)
    computed, masked = _count_tiles(
        seq // tile, seq // tile, tile, tile, rule, window
    )
    assert plan["fwd"] == {
        "block_q": tile, "block_k": tile, "variant": "resident",
        "tiles_computed": computed, "tiles_masked": masked,
    }
    # under a window the resident walk classifies its tiles at trace time:
    # the diagonal's and the window edge's are masked, not every one
    assert masked < computed or computed == 1
    _holds_the_variant_rule(plan, seq, width, group)


@pytest.mark.parametrize("seq,head_dim,causal,tile,computed", [
    (16384, 128, True, 1024, 136),   # the blocks of a 16k row: 112 MiB
    (16384, 64, True, 1024, 136),    # lanes pad 64 columns to 128: the same
    (8192, 128, False, 512, 256),    # a full walk: more bodies than unroll
])
def test_forward_streams_past_the_budget_or_the_unroll(seq, head_dim, causal,
                                                       tile, computed):
    from tpu_parallel.ops.flash_attention import flash_plan

    fwd = flash_plan(seq, head_dim, causal=causal)["fwd"]
    assert (fwd["block_q"], fwd["variant"], fwd["tiles_computed"]) == (
        tile, "streamed", computed
    )
    # an explicit variant wins either way
    assert flash_plan(seq, head_dim, causal=causal, stream=False)["fwd"][
        "variant"] == "resident"
    assert flash_plan(1024, head_dim, stream=True)["fwd"]["variant"] == "streamed"


def test_resident_forward_past_4096_rows_at_two_widths(rng):
    """The forward a latent prefill calls, on a row past the 4096 that used to
    send it to the streamed kernel: scores at 192, values at 128, one head;
    resident at the derived tile of 512 (45 tile bodies at 4608 rows),
    against a direct computation at sampled positions, as
    ``test_stream_auto_dispatch_long_seq`` checks the streamed one."""
    from tpu_parallel.ops.flash_attention import (
        flash_attention_fwd_bhsd, flash_plan,
    )

    s, dk, dv = 4608, 192, 128
    plan = flash_plan(s, dk)["fwd"]
    assert (plan["variant"], plan["block_q"], plan["tiles_computed"]) == (
        "resident", 512, 45
    )
    ks = jax.random.split(rng, 3)
    q = jax.random.normal(ks[0], (1, 1, s, dk), jnp.float32) * 0.1
    k = jax.random.normal(ks[1], (1, 1, s, dk), jnp.float32) * 0.1
    v = jax.random.normal(ks[2], (1, 1, s, dv), jnp.float32) * 0.1
    out = flash_attention_fwd_bhsd(q, k, v, interpret=True)
    assert out.shape == (1, 1, s, dv)
    for pos in (0, 511, 512, 4095, 4096, 4607):
        scores = jnp.einsum("d,kd->k", q[0, 0, pos], k[0, 0, : pos + 1]) / jnp.sqrt(dk)
        ref = jax.nn.softmax(scores) @ v[0, 0, : pos + 1]
        np.testing.assert_allclose(
            np.asarray(out[0, 0, pos]), np.asarray(ref), rtol=2e-3, atol=2e-3,
            err_msg=f"pos={pos}",
        )


def test_plan_of_the_train_cell_is_one_backward_pass_and_a_masked_diagonal():
    """gpt2_125m's attention, 1024 x 64, group 1: both passes resident, ONE
    backward kernel, and the only masked tiles are the ones the diagonal
    crosses (seq / tile of them)."""
    from tpu_parallel.ops.flash_attention import flash_plan

    plan = flash_plan(1024, 64, 1, jnp.bfloat16)
    assert plan["fused_bwd"] is True
    assert plan["fwd"] == {
        "block_q": 512, "block_k": 512, "variant": "resident",
        "tiles_computed": 3, "tiles_masked": 1024 // 512,
    }
    assert plan["bwd"] == {
        "block_q": 256, "block_k": 256, "variant": "resident",
        "tiles_computed": 10, "tiles_masked": 1024 // 256,
    }
    assert flash_plan(1000, 64, 1) is None  # no multiple of 128 divides it


def test_derived_tiles_run_the_same_numbers(rng):
    """block_q / block_k None (the default) = tiles from the shape: same
    output and gradients as the reference, different tiles per pass."""
    q, k, v = _make_qkv(rng, b=1, s=256, h=2, d=16)
    out = flash_attention(q, k, v, interpret=True)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(_ref_bshd(q, k, v)), rtol=2e-3, atol=2e-3
    )
    g = jax.grad(lambda *a: (flash_attention(*a, interpret=True) ** 2).sum(), argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(lambda *a: (_ref_bshd(*a) ** 2).sum(), argnums=(0, 1, 2))(q, k, v)
    for a, bb in zip(g, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(bb), rtol=5e-3, atol=5e-3)


def test_trainer_says_its_flash_plan_once(rng):
    """The counter that says the change engaged: ``Trainer.flash_plan`` at
    build, and a ``flash_plan`` instant on the tracer when tracing is on."""
    from tpu_parallel.obs.tracer import Tracer
    from tpu_parallel.runtime import MeshConfig
    from tpu_parallel.train_lib import Trainer, TrainerConfig

    config = TrainerConfig(
        model="tiny", model_overrides=dict(attn_impl="flash"),
        mesh=MeshConfig(data=8), global_batch_size=8, steps=1, log_every=10,
    )
    tracer = Tracer()
    trainer = Trainer(config, tracer=tracer)
    assert trainer.flash_plan["fused_bwd"] is True
    trainer.init()
    trainer.train(steps=1)
    (instant,) = [i for i in tracer.instants if i["name"] == "flash_plan"]
    assert instant["attrs"]["fused_bwd"] is True
    assert instant["attrs"]["bwd_variant"] == "resident"
    assert instant["attrs"]["fwd_tiles_masked"] >= 1
