"""Layers that are ONE sublayer each behind ONE norm (a Mamba-2 mixer with
grouped B/C and a gate norm a group, attention without positions, or a
LatentMoE): the program against the plain reference
(``benchmarks/reference/nemotron_h_ref.py``) on LOGITS, at a small size,
seeded random weights, float32.

One period ``MEMEMEM*EME``; ``d_model`` 64, 8 state heads of 16 in 2 groups
with a state of 16, 4 query heads of 16 over 2 K/V heads, 16 sigmoid-routed
``relu2`` experts of width 24 in a latent of 32, top-4 with a selection bias
and a scale of 2.5, 8 held, one shared expert of width 48; chunks of 8,
lengths that are no multiple of the chunk.
"""

import dataclasses
import logging
import os
import sys

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmarks"))

from lib import nemotron_weights  # noqa: E402
from reference import nemotron_h_ref as ref  # noqa: E402

from tpu_parallel.models import generate as gen  # noqa: E402
from tpu_parallel.models import moe  # noqa: E402
from tpu_parallel.models.gpt import GPTLM, lm_logits, tiny_one_sublayer  # noqa: E402
from tpu_parallel.models.layers import ExpertsSpec, LayerSpec, layer_kinds  # noqa: E402
from tpu_parallel.models.ssm import GroupRMSNorm  # noqa: E402
from tpu_parallel.ops import grouped_ffn as gffn  # noqa: E402
from tpu_parallel.serving import ServingEngine, cache_pool  # noqa: E402
from tpu_parallel.serving.request import Request  # noqa: E402

SEED = 2 ** 31 + 45
TOL = 5e-5  # float32 against float32: summation order only
PATTERN = "MEMEMEM*EME"


def build(**overrides):
    cfg = tiny_one_sublayer(**overrides)
    model = GPTLM(cfg)
    abstract = jax.eval_shape(
        lambda: model.init(
            {"params": jax.random.PRNGKey(0)}, jnp.zeros((1, 8), jnp.int32),
            train=False,
        )
    )["params"]
    return cfg, model, abstract, nemotron_weights.make_params(SEED, abstract)


def experts_of(cfg) -> ExpertsSpec:
    return next(s.experts for s in cfg.layer_specs if s.experts is not None)


def shape_of(cfg) -> dict:
    ssm = next(s.ssm for s in cfg.layer_specs if s.mixer == "ssm")
    es = experts_of(cfg)
    return {
        "pattern": "".join(
            "M" if s.mixer == "ssm" else "E" if s.mixer == "none" else "*"
            for s in cfg.layer_specs
        ),
        "eps": cfg.norm_eps, "held": es.held_range,
        "routed_scaling_factor": es.route_scale,
        "num_experts_per_tok": es.top_k,
        "mamba_num_heads": ssm.n_heads, "mamba_head_dim": ssm.head_dim,
        "ssm_state_size": ssm.d_state, "n_groups": ssm.n_groups,
        "conv_kernel": ssm.d_conv,
    }


def reference_weights(cfg, abstract):
    return nemotron_weights.to_reference(
        SEED, abstract, cfg.n_heads, cfg.n_kv_heads
    )


def reference_logits(cfg, abstract, tokens):
    return ref.forward(reference_weights(cfg, abstract), tokens, shape_of(cfg))


def draw_tokens(n, seed=1):
    return np.asarray(
        jax.random.randint(jax.random.PRNGKey(seed), (n,), 1, 256), np.int32
    )


# -- the stack ------------------------------------------------------------------


def test_a_layer_is_one_norm_and_one_sublayer():
    cfg, _, abstract, _ = build()
    assert shape_of(cfg)["pattern"] == PATTERN
    blocks = abstract["blocks"]
    for i, kind in enumerate(PATTERN):
        part = {"M": "ssm", "E": "moe", "*": "attn"}[kind]
        assert set(blocks[f"layer_{i}"]) == {"norm", part}, (i, kind)
    assert layer_kinds(cfg) == {
        "layers": 11, "ssm": 5, "experts": 5, "attention": 1,
    }
    assert cfg.recurrent_layers == 5 and cfg.routed_layers == 5
    assert "lm_head" in abstract  # untied
    moe_leaves = set(blocks["layer_1"]["moe"])
    assert moe_leaves == {"router", "select_bias", "latent_down", "latent_up",
                          "experts", "shared_up", "shared_down"}
    es = experts_of(cfg)
    held = blocks["layer_1"]["moe"]["experts"]["sharded"]
    assert held["up"]["kernel"].shape == (8, es.latent, es.width)
    assert held["down"]["kernel"].shape == (8, es.width, es.latent)
    assert blocks["layer_1"]["moe"]["shared_up"]["kernel"].shape == (1, 64, 48)


def test_the_scanned_stack_runs_one_sublayer_a_layer_too():
    """Two periods under ``nn.scan`` (one period a tick): each named block of
    the body holds one norm and one sublayer, stacked over the two ticks, and
    the cache has leaves for the mixers alone."""
    cfg = tiny_one_sublayer(scan_layers=True, scan_group=11, n_layers=22)
    model = GPTLM(cfg)
    toks = jnp.asarray(draw_tokens(9)[None])
    variables = model.init({"params": jax.random.PRNGKey(0)}, toks, train=False)
    body = variables["params"]["blocks"]["layers"]
    for j, kind in enumerate(PATTERN):
        part = {"M": "ssm", "E": "moe", "*": "attn"}[kind]
        assert set(body[f"block{j}"]) == {"norm", part}, (j, kind)
    assert body["block0"]["norm"]["scale"].shape == (2, 64)
    out, state = model.apply(
        {"params": variables["params"]}, toks, train=False, decode=True,
        positions=jnp.arange(9)[None], mutable=["cache", "moe_stats"],
    )
    assert np.isfinite(np.asarray(out)).all() and float(jnp.std(out)) > 0.1
    cached = state["cache"]["blocks"]["layers"]
    assert set(cached) == {f"block{j}" for j, k in enumerate(PATTERN) if k != "E"}
    assert layer_kinds(cfg) == {"layers": 22, "ssm": 10, "experts": 10,
                                "attention": 2}


def test_a_layer_with_neither_half_is_refused():
    cfg = tiny_one_sublayer()
    empty = LayerSpec(mixer="none", mlp="none")
    model = GPTLM(tiny_one_sublayer(layer_pattern=cfg.layer_pattern[:10] + (empty,)))
    with pytest.raises(ValueError, match="neither"):
        model.init({"params": jax.random.PRNGKey(0)}, jnp.zeros((1, 8), jnp.int32),
                   train=False)
    parallel = GPTLM(tiny_one_sublayer(parallel_block=True))
    with pytest.raises(ValueError, match="one-sublayer"):
        parallel.init({"params": jax.random.PRNGKey(0)},
                      jnp.zeros((1, 8), jnp.int32), train=False)


@pytest.mark.parametrize("tokens", [21, 8, 3])
def test_full_forward_matches_reference(tokens):
    cfg, model, abstract, params = build()
    toks = draw_tokens(tokens)
    logits = model.apply({"params": params}, toks[None], train=False)[0]
    want = reference_logits(cfg, abstract, toks)
    assert float(jnp.std(want)) > 0.1  # the comparison has something to see
    np.testing.assert_allclose(logits, want, atol=TOL, rtol=TOL)


def test_every_kind_of_layer_carries_weight():
    """The toy's logits move when a layer of any kind is zeroed: the
    comparisons would see a sublayer that did nothing."""
    _, model, _, params = build()
    toks = draw_tokens(21)[None]
    base = model.apply({"params": params}, toks, train=False)
    for layer, part in (("layer_0", "ssm"), ("layer_1", "moe"), ("layer_7", "attn")):
        broken = jax.tree_util.tree_map(lambda x: x, params)
        broken["blocks"][layer][part] = jax.tree_util.tree_map(
            jnp.zeros_like, broken["blocks"][layer][part]
        )
        other = model.apply({"params": broken}, toks, train=False)
        assert float(jnp.max(jnp.abs(other - base))) > 1e-2, (layer, part)


@pytest.mark.parametrize("change", [
    dict(select_bias=False), dict(route_scale=1.0), dict(score="softmax"),
    dict(top_k=3), dict(held=(8, 8)),
], ids=lambda c: next(iter(c)))
def test_each_stated_size_of_the_router_fails_when_dropped(change):
    """The program with one field of its ``ExpertsSpec`` at another value,
    on the same parameters, is no longer the reference's model."""
    cfg, _, abstract, params = build()
    toks = draw_tokens(21)
    want = reference_logits(cfg, abstract, toks)
    other = GPTLM(tiny_one_sublayer(
        experts=dataclasses.replace(experts_of(cfg), **change)
    ))
    got = other.apply({"params": params}, toks[None], train=False)[0]
    assert float(jnp.max(jnp.abs(got - want))) > 1e-2, change


def test_the_latent_projections_fail_when_dropped():
    """At a latent as wide as the model the experts' matrices fit either
    program: the one without the two projections is not the reference's."""
    wide = dataclasses.replace(experts_of(tiny_one_sublayer()), latent=64)
    cfg, model, abstract, params = build(experts=wide)
    toks = draw_tokens(21)
    want = reference_logits(cfg, abstract, toks)
    got = model.apply({"params": params}, toks[None], train=False)[0]
    np.testing.assert_allclose(got, want, atol=TOL, rtol=TOL)
    bare = GPTLM(tiny_one_sublayer(experts=dataclasses.replace(wide, latent=0)))
    got = bare.apply({"params": params}, toks[None], train=False)[0]
    assert float(jnp.max(jnp.abs(got - want))) > 1e-2


@pytest.fixture
def fresh_kernels():
    """``ops.grouped_ffn._planned`` is jitted: a test that patches what it
    traces needs it traced anew, and must not leave its trace behind."""
    gffn._planned.clear_cache()
    yield
    gffn._planned.clear_cache()


def test_the_square_fails_when_dropped(monkeypatch, fresh_kernels):
    cfg, model, abstract, params = build()
    toks = draw_tokens(21)
    want = reference_logits(cfg, abstract, toks)
    assert moe.moe_plan(experts_of(cfg), 21, 64, jnp.float32)["grouped"] == "streamed"
    monkeypatch.setattr(gffn, "_relu2", lambda y: jnp.maximum(y, 0.0))
    got = model.apply({"params": params}, toks[None], train=False)[0]
    assert float(jnp.max(jnp.abs(got - want))) > 1e-2


def test_shared_experts_are_summed_or_averaged_at_their_own_width():
    es = ExpertsSpec(n_experts=4, top_k=2, width=8, shared=2, shared_width=12,
                     ffn="relu2", shared_sum=True)
    cfg = tiny_one_sublayer()
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 5, 64), jnp.float32)
    layer = moe.RoutedExperts(cfg, es)
    params = layer.init({"params": jax.random.PRNGKey(4)}, x)["params"]
    assert params["shared_up"]["kernel"].shape == (2, 64, 12)
    assert "shared_gate" not in params and "latent_down" not in params
    summed = layer.apply({"params": params}, x)
    mean = moe.RoutedExperts(
        cfg, dataclasses.replace(es, shared_sum=False)
    ).apply({"params": params}, x)
    none = moe.RoutedExperts(
        cfg, dataclasses.replace(es, shared=0)
    ).apply({"params": params}, x)
    shared = sum(
        ref.relu2(x[0] @ params["shared_up"]["kernel"][i])
        @ params["shared_down"]["kernel"][i] for i in range(2)
    )
    np.testing.assert_allclose(summed[0] - none[0], shared, atol=TOL, rtol=TOL)
    np.testing.assert_allclose(mean[0] - none[0], shared / 2, atol=TOL, rtol=TOL)
    with pytest.raises(ValueError, match="ffn"):
        moe.RoutedExperts(cfg, dataclasses.replace(es, ffn="gelu")).init(
            {"params": jax.random.PRNGKey(4)}, x
        )


# -- the gate norm a group ------------------------------------------------------


def test_gate_norm_takes_its_mean_square_a_group():
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 3, 32), jnp.float32) * (
        1.0 + jnp.arange(32) // 8
    )
    grouped = GroupRMSNorm(4, 1e-5)
    params = grouped.init(jax.random.PRNGKey(0), x)
    assert params["params"]["scale"].shape == (32,)
    got = grouped.apply(params, x)
    want = ref.rms_norm(x, jnp.ones((32,)), 1e-5, groups=4)
    np.testing.assert_allclose(got, want, atol=1e-6, rtol=1e-6)
    for g in range(4):  # each group of 8 channels has unit mean square
        part = np.asarray(got[..., 8 * g:8 * g + 8])
        np.testing.assert_allclose((part ** 2).mean(-1), 1.0, rtol=1e-3)
    one = nn.RMSNorm(epsilon=1e-5)
    whole = one.apply(one.init(jax.random.PRNGKey(0), x), x)
    assert float(jnp.max(jnp.abs(whole - got))) > 0.1
    np.testing.assert_allclose(
        whole, ref.rms_norm(x, jnp.ones((32,)), 1e-5, groups=1),
        atol=1e-6, rtol=1e-6,
    )


def test_one_norm_over_the_groups_is_not_the_reference(monkeypatch):
    """The program with ONE norm over all of ``d_inner`` where the model has
    a norm a group, on the same parameters, is not the reference's model."""
    from tpu_parallel.models import ssm

    cfg, model, abstract, params = build()
    toks = draw_tokens(21)
    want = reference_logits(cfg, abstract, toks)
    monkeypatch.setattr(
        ssm, "GroupRMSNorm",
        lambda groups, eps, name: nn.RMSNorm(
            epsilon=eps, dtype=jnp.float32, name=name
        ),
    )
    got = model.apply({"params": params}, toks[None], train=False)[0]
    assert float(jnp.max(jnp.abs(got - want))) > 1e-2


# -- the share ------------------------------------------------------------------


def test_the_four_shares_add_up_to_the_uncut_layer():
    """The four shares ``[0, 4) ... [12, 16)`` of one ``E`` layer's routed
    sum, with router, latent projections and shared expert counted once, add
    up to the uncut reference's layer; each share's program is its
    reference."""
    base = experts_of(tiny_one_sublayer())
    cfg = tiny_one_sublayer()
    whole = dataclasses.replace(base, held=None)
    x = jax.random.normal(jax.random.PRNGKey(7), (1, 13, 64), jnp.float32)
    layer = moe.RoutedExperts(cfg, whole)
    tree = jax.eval_shape(lambda: layer.init({"params": jax.random.PRNGKey(0)}, x))
    params = nemotron_weights.make_params(SEED, tree["params"])
    flat = nemotron_weights._flat(params)
    lw = {
        "router": flat["router/kernel"], "router_bias": flat["select_bias"],
        "w_dn": flat["latent_down/kernel"], "w_up": flat["latent_up/kernel"],
        "w1": flat["experts/up/kernel"], "w2": flat["experts/down/kernel"],
        "s1": flat["shared_up/kernel"][0], "s2": flat["shared_down/kernel"][0],
    }
    assert float(jnp.std(lw["router_bias"])) > 0.01  # the bias is not zero
    shape = {"num_experts_per_tok": base.top_k, "held": (0, 16),
             "routed_scaling_factor": base.route_scale}
    with jax.default_matmul_precision("highest"):
        routed, up, shared = ref.latent_moe(x[0], lw, shape, "float32")
        uncut = up + shared
        got = layer.apply({"params": params}, x)[0]
        np.testing.assert_allclose(got, uncut, atol=TOL, rtol=TOL)
        total_latent, total_out = 0.0, 0.0
        for first in (0, 4, 8, 12):
            part = dict(lw, w1=lw["w1"][first:first + 4], w2=lw["w2"][first:first + 4])
            r, u, s = ref.latent_moe(x[0], part, dict(shape, held=(first, 4)), "float32")
            np.testing.assert_allclose(s, shared, atol=1e-6)
            mine = jax.tree_util.tree_map(lambda v: v, params)
            mine["experts"] = jax.tree_util.tree_map(
                lambda v: v[first:first + 4], params["experts"]
            )
            out = moe.RoutedExperts(
                cfg, dataclasses.replace(base, held=(first, 4))
            ).apply({"params": mine}, x)[0]
            np.testing.assert_allclose(out, u + s, atol=TOL, rtol=TOL)
            assert float(jnp.std(u)) > 0.01  # every share adds something
            total_latent, total_out = total_latent + r, total_out + out
    np.testing.assert_allclose(total_latent, routed, atol=TOL, rtol=TOL)
    # four programs each added the shared expert: counted once, three go
    np.testing.assert_allclose(
        total_out - 3 * shared, uncut, atol=4 * TOL, rtol=4 * TOL
    )


# -- the streamed kernel's one-weight relu2 call --------------------------------


@pytest.mark.parametrize("sizes", [
    [3, 0, 5, 1, 0, 0, 7, 2], [0, 0, 0, 0, 0, 0, 0, 40], [0] * 8,
])
def test_streamed_relu2_matches_ragged_dot(sizes):
    key = jax.random.split(jax.random.PRNGKey(11), 3)
    rows = jax.random.normal(key[0], (48, 128), jnp.float32)
    w_up = jax.random.normal(key[1], (8, 128, 384), jnp.float32) / 11.3
    w_down = jax.random.normal(key[2], (8, 384, 128), jnp.float32) / 19.6
    group_sizes = jnp.asarray(sizes, jnp.int32)
    assert gffn.grouped_ffn_plan(48, 8, 128, 384, jnp.float32, 2) is not None
    got = gffn.grouped_ffn(rows, (w_up, w_down), group_sizes, interpret=True)
    mid = jnp.square(jax.nn.relu(lax.ragged_dot(rows, w_up, group_sizes)))
    want = lax.ragged_dot(mid, w_down, group_sizes)
    n = sum(sizes)
    np.testing.assert_allclose(got[:n], want[:n], atol=2e-4, rtol=2e-4)
    assert not np.asarray(got[n:]).any()  # rows past the groups come back zero
    if n:
        assert float(jnp.std(want[:n])) > 0.1
    # the three-weight call is what it was
    w_gate = jax.random.normal(key[0], (8, 128, 384), jnp.float32) / 11.3
    gated = gffn.grouped_ffn(rows, (w_gate, w_up, w_down), group_sizes, interpret=True)
    mid = jax.nn.silu(lax.ragged_dot(rows, w_gate, group_sizes)) * lax.ragged_dot(
        rows, w_up, group_sizes
    )
    np.testing.assert_allclose(
        gated[:n], lax.ragged_dot(mid, w_down, group_sizes)[:n],
        atol=2e-4, rtol=2e-4,
    )


def test_plan_for_two_matrices_counts_one_weight_in_the_first_call():
    three = gffn.grouped_ffn_plan(2816, 128, 1024, 2688, jnp.bfloat16, 3)
    two = gffn.grouped_ffn_plan(2816, 128, 1024, 2688, jnp.bfloat16, 2)
    assert two is not None and three is not None
    assert two["block_in"] == 512 and two["block_mid"] == 896
    assert two["slots"] == 128 + (2816 + 15 * 128) // 128
    assert two["vmem_limit_bytes"] <= three["vmem_limit_bytes"]
    # the real decode shape: 128 slots x top-22 on 128 held of 512
    es = ExpertsSpec(n_experts=512, top_k=22, width=2688, score="sigmoid",
                     shared=1, held=(0, 128), latent=1024, ffn="relu2",
                     shared_width=5376, shared_sum=True, select_bias=True,
                     route_scale=5.0)
    plan = moe.moe_plan(es, 128, 4096, jnp.bfloat16)
    assert plan["buffer_rows"] == 2816 and plan["grouped"] == "streamed"
    assert (plan["latent"], plan["matrices"], plan["ffn"]) == (1024, 2, "relu2")
    prefill = moe.moe_plan(es, 2048, 4096, jnp.bfloat16)
    assert prefill["buffer_rows"] == 45056 and prefill["grouped"] == "ragged_dot"
    # defaults: the plan of a three-matrix expert at the model's width
    old = moe.moe_plan(ExpertsSpec(128, 8, 768), 256, 2048, jnp.bfloat16)
    assert (old["latent"], old["matrices"], old["ffn"]) == (0, 3, "swiglu")


def test_relu2_ffn_takes_ragged_dot_where_no_plan_fits(monkeypatch):
    key = jax.random.split(jax.random.PRNGKey(12), 3)
    rows = jax.random.normal(key[0], (2048, 32), jnp.float32)
    w_up = jax.random.normal(key[1], (4, 32, 24), jnp.float32) / 5.6
    w_down = jax.random.normal(key[2], (4, 24, 32), jnp.float32) / 4.9
    sizes = jnp.asarray([100, 0, 900, 48], jnp.int32)
    assert gffn.grouped_ffn_plan(2048, 4, 32, 24, jnp.float32, 2) is None
    called = []
    monkeypatch.setattr(moe, "grouped_ffn", lambda *a, **k: called.append(a))
    got = moe._grouped_ffn(rows, (w_up, w_down), sizes)
    assert not called
    want = jnp.concatenate([
        ref.relu2(rows[lo:hi] @ w_up[e]) @ w_down[e]
        for e, (lo, hi) in enumerate([(0, 100), (100, 100), (100, 1000), (1000, 1048)])
    ])
    np.testing.assert_allclose(got[:1048], want, atol=2e-4, rtol=2e-4)


# -- prefill, then decode through the pool -------------------------------------


def test_bucket_padded_prefill_then_decode_beside_parked_rows():
    """Three rows: a prompt of 11 right-padded to 16, a dummy row of pads, a
    prompt of 16; then decode steps in which the middle row is parked
    (position -1).  Rows 0 and 2 give the reference's logits throughout."""
    cfg, model, abstract, params = build()
    a, b = draw_tokens(19, seed=4), draw_tokens(24, seed=5)
    na, nb = 11, 16
    prompt = np.zeros((3, 16), np.int32)
    prompt[0, :na], prompt[2, :nb] = a[:na], b[:nb]
    positions = np.full((3, 16), -1, np.int32)
    positions[0, :na], positions[2, :nb] = np.arange(na), np.arange(nb)
    hidden, cache = gen.prefill_step(
        model, params, jnp.asarray(prompt), jnp.asarray(positions)
    )
    got_a = [lm_logits(cfg, params, hidden)[0, :na]]
    got_b = [lm_logits(cfg, params, hidden)[2, :nb]]
    for step in range(8):
        tok = jnp.asarray([a[na + step], 0, b[nb + step]])
        pos = jnp.asarray([na + step, -1, nb + step])
        h, cache = gen.decode_step(model, params, cache, tok, pos)
        logits = lm_logits(cfg, params, h)
        got_a.append(logits[0])
        got_b.append(logits[2])
    want_a = reference_logits(cfg, abstract, a)
    want_b = reference_logits(cfg, abstract, b)
    np.testing.assert_allclose(jnp.concatenate(got_a), want_a, atol=TOL, rtol=TOL)
    np.testing.assert_allclose(jnp.concatenate(got_b), want_b, atol=TOL, rtol=TOL)


def test_pool_holds_five_states_five_windows_and_one_stripe():
    cfg, model, _, params = build()
    pool = cache_pool.empty_pool(model, params, 3)
    names = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(pool)[0]:
        name = cache_pool._leaf_name(path)
        names.setdefault(name, []).append(leaf)
    assert len(names["ssm_state"]) == 5 and len(names["conv_state"]) == 5
    assert len(names["cached_key"]) == len(names["cached_value"]) == 1
    assert set(pool["blocks"]) == {f"layer_{i}" for i, k in enumerate(PATTERN)
                                   if k != "E"}
    assert all(x.dtype == jnp.float32 and x.shape == (3, 8, 16, 16)
               for x in names["ssm_state"])
    dirty = jax.tree_util.tree_map(lambda x: x + 1, pool)
    cleared = cache_pool.clear_rows(dirty, jnp.int32(1))
    for path, leaf in jax.tree_util.tree_flatten_with_path(cleared)[0]:
        name = cache_pool._leaf_name(path)
        if name.startswith(cache_pool.STATE_LEAVES):
            assert not np.asarray(leaf[1]).any(), name
            assert np.asarray(leaf[0] == 1).all() and np.asarray(leaf[2] == 1).all()
        elif name.startswith("cached_pos"):
            assert np.asarray(leaf[1] == -1).all()
    row = cache_pool.extract_rows(dirty, jnp.int32(2))
    states = nemotron_weights.slot_states(row)
    assert len(states) == 5 and states[0].shape == (1, 8, 16, 16)


# -- the engine -------------------------------------------------------------------


def outputs(engine, prompts, new=6):
    outs = [
        engine.add_request(Request(prompt=list(map(int, p)), max_new_tokens=new))
        for p in prompts
    ]
    engine.run()
    return [list(out.tokens) for out in outs]


def generated(model, params, prompt, new=6):
    return list(map(int, gen.generate(
        model, params, jnp.asarray(prompt[None]), max_new_tokens=new
    )[0]))


ENGINES = {
    "per_step": dict(decode_steps_per_tick=1, prefill_buckets=None),
    # the cell's: whole-prompt prefill one row a call, the fused tick of 8
    "fused_bucketed": dict(prefill_buckets=(8, 16), prefill_batch=1),
}


@pytest.mark.parametrize("name", sorted(ENGINES))
def test_engine_output_equals_generate(name):
    """Greedy serving equals ``generate()``, with more requests than slots so
    that slots are reused and ticks run with free and finished slots beside
    live ones; the expert counters come out over the 5 expert layers."""
    cfg, model, _, params = build()
    prompts = [draw_tokens(n, seed=10 + n) for n in (5, 11, 3, 14, 9)]
    engine = ServingEngine(model, params, n_slots=3, **ENGINES[name])
    got = outputs(engine, prompts, new=7)
    for prompt, tokens in zip(prompts, got):
        assert tokens == generated(model, params, prompt, new=7)
    summary = engine.metrics.summary()
    assert summary["prefill_tokens_real"] == sum(len(p) for p in prompts)
    assert summary["state_bytes_per_slot"] == 5 * (8 * 16 * 16 * 4 + 3 * 192 * 4)
    # one count a layer's pass: 5 expert layers a forward
    assert summary["moe_calls"] > 0 and summary["moe_calls"] % 5 == 0
    assert 0 < summary["moe_experts_touched_mean"] <= 8
    if name == "fused_bucketed":
        assert summary["launch_ahead_share"] > 0.5


def test_a_reused_slot_equals_a_fresh_engine():
    _, model, _, params = build()
    kw = dict(prefill_buckets=(8, 16))
    first, second = draw_tokens(13, seed=31), draw_tokens(10, seed=32)
    used = ServingEngine(model, params, n_slots=1, **kw)
    assert outputs(used, [first])[0] == generated(model, params, first)
    fresh = ServingEngine(model, params, n_slots=1, **kw)
    assert outputs(used, [second]) == outputs(fresh, [second])


def test_plans_say_layers_by_kind(caplog):
    from tpu_parallel.obs import Tracer

    _, model, _, params = build()
    tracer = Tracer()
    with caplog.at_level(logging.INFO, logger="tpu_parallel.serving.engine"):
        engine = ServingEngine(
            model, params, n_slots=2, prefill_buckets=(8, 16), tracer=tracer
        )
    assert engine.layer_kinds == {"layers": 11, "ssm": 5, "experts": 5,
                                  "attention": 1}
    plan = engine.ssm_plan
    assert (plan["ssm_layers"], plan["attention_layers"], plan["expert_layers"],
            plan["layers"], plan["groups"]) == (5, 1, 5, 11, 2)
    assert plan["kv_bytes_per_slot"] == 2 * 48 * 2 * 16 * 4 + 48 * 4
    decode = engine.moe_plan["decode"]
    assert (decode["latent"], decode["matrices"], decode["ffn"]) == (32, 2, "relu2")
    assert decode["buffer_rows"] == 2 * 4 and decode["held"] == 8
    said = [r.getMessage() for r in caplog.records]
    for name in ("moe_plan", "ssm_plan", "attn_plan"):
        assert any(m.startswith(name) for m in said), name
    assert any(m.startswith("moe_plan") and '"experts": 5' in m for m in said)
    assert any(m.startswith("attn_plan") and '"attention": 1' in m for m in said)
    instants = {e["name"]: e for e in tracer.instants}
    assert instants["moe_plan"]["attrs"]["layers"] == 5
    assert instants["moe_plan"]["attrs"]["of_layers"] == 11
    assert instants["attn_plan"]["attrs"]["layers"] == 1
    assert instants["ssm_plan"]["attrs"]["expert_layers"] == 5


REFUSED = {
    "prefix_cache": dict(prefix_cache_size=4),
    "radix": dict(kv_radix_cache=True, kv_block_tokens=4, prefix_cache_size=4),
    "paged": dict(kv_block_tokens=4),
    "host_tier": dict(kv_host_blocks=8, kv_block_tokens=4, prefix_cache_size=4),
    "speculative": dict(draft_tokens=3),
}


@pytest.mark.parametrize("name", sorted(REFUSED))
def test_engine_refuses_what_a_state_cannot_do(name):
    _, model, _, params = build()
    with pytest.raises(NotImplementedError, match="recurrent"):
        ServingEngine(model, params, n_slots=2, **REFUSED[name])
