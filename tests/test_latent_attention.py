"""Latent attention (MLA), sandwich norms and leading layers, on the CPU at a
toy size on seeded weights: the program against the plain reference
(``benchmarks/reference/pangu_ultra_moe_ref.py``, the expanded form only, no
cache); prefill through the flash kernel (interpret mode) then decode through
the latent cache against the reference's full forward, logits compared; the
absorbed form against the expanded form on the same parameters; the share
test (all 16 shares' routed parts and ONE shared expert add up to the uncut
layer's MLP before the post-MLP norm); the engine's plans, counters and every
typed refusal.

Tolerances: the toy computes in float32, so program and reference differ by
the order of float32 sums only: logits of unit spread agree to a few 1e-6
(``TOL`` 5e-5 leaves ten times that); nothing here is compared in bfloat16.
"""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmarks"))

from lib import pangu_weights  # noqa: E402
from reference import pangu_ultra_moe_ref as ref  # noqa: E402

from tpu_parallel.models import GPTLM, ExpertsSpec, LatentSpec, LayerSpec  # noqa: E402
from tpu_parallel.models.generate import decode_step, generate, prefill_step  # noqa: E402
from tpu_parallel.models.gpt import (  # noqa: E402
    GPTConfig,
    lm_logits,
    tiny_latent_experts,
    tiny_test,
)
from tpu_parallel.models.layers import depth_specs, layer_kinds, spec_at  # noqa: E402
from tpu_parallel.serving import Request, ServingEngine  # noqa: E402

TOL = 5e-5  # float32 sums in another order, at logits of unit spread


def shape_of(cfg, held):
    spec = cfg.layer_pattern[0]
    return dict(
        nope_dim=spec.latent.nope_dim, rope_dim=spec.latent.rope_dim,
        rope_theta=cfg.rope_theta, eps=cfg.norm_eps,
        num_experts_per_tok=spec.experts.top_k,
        routed_scaling_factor=spec.experts.route_scale, held=held,
    )


def seeded(cfg, seed=0, tokens=24):
    """Weights with norm scales that are not 1 (a norm left out or put in the
    wrong place then moves every logit)."""
    model = GPTLM(cfg)
    toks = jax.random.randint(jax.random.PRNGKey(seed + 1), (2, tokens), 1, 256)
    params = model.init({"params": jax.random.PRNGKey(seed)}, toks, train=False)[
        "params"
    ]

    def scale(path, x):
        if path[-1].key != "scale":
            return x
        key = jax.random.fold_in(jax.random.PRNGKey(7), hash(str(path)) % 2**31)
        return x * (1.0 + 0.2 * jax.random.normal(key, x.shape))

    return model, jax.tree_util.tree_map_with_path(scale, params), toks


@pytest.fixture(scope="module")
def toy():
    cfg = tiny_latent_experts()
    model, params, toks = seeded(cfg)
    weights = pangu_weights.tree_to_reference(params, cfg.n_heads)
    want = jnp.stack([
        ref.forward(weights, t, shape_of(cfg, (0, 4))) for t in toks
    ])
    return cfg, model, params, toks, want


def test_full_forward_matches_the_reference(toy):
    cfg, model, params, toks, want = toy
    got = model.apply({"params": params}, toks, train=False)
    assert float(jnp.std(want)) > 0.5
    assert float(jnp.max(jnp.abs(got - want))) < TOL


def test_flash_forward_matches_the_reference(toy):
    """``attn_impl="flash"``: the expanded form through the kernel at a
    query-key width (24) that differs from the value width (16)."""
    cfg, _, params, toks, want = toy
    model = GPTLM(dataclasses.replace(cfg, attn_impl="flash", seq_len=128))
    pad = jnp.pad(toks, ((0, 0), (0, 128 - toks.shape[1])))
    got = model.apply({"params": params}, pad, train=False)[:, :toks.shape[1]]
    assert float(jnp.max(jnp.abs(got - want))) < TOL


@pytest.mark.parametrize("flash", [False, True])
def test_prefill_then_decode_through_the_cache(toy, flash):
    """Prefill (absorbed, or expanded through the flash kernel in interpret
    mode under ``prefill_flash``) writes the latent rows; every later token is
    a decode step of the absorbed form against them."""
    cfg, _, params, toks, want = toy
    model = GPTLM(dataclasses.replace(cfg, prefill_flash=flash, seq_len=128))
    n = 16 if flash else 9
    pos = jnp.broadcast_to(jnp.arange(n), (2, n))
    hidden, cache = prefill_step(model, params, toks[:, :n], pos)
    leaves = cache["blocks"]["layer_1"]["attn"]
    assert set(leaves) == {"cached_latent", "cached_pos", "cache_index"}
    assert leaves["cached_latent"].shape == (2, 128, 40)  # no K/V heads
    outs = [lm_logits(cfg, params, hidden)]
    step = jax.jit(lambda cache, tok, pos: decode_step(model, params, cache, tok, pos))
    for i in range(n, toks.shape[1]):
        hidden, cache = step(cache, toks[:, i], jnp.full((2,), i))
        outs.append(lm_logits(cfg, params, hidden))
    got = jnp.concatenate(outs, axis=1)
    assert float(jnp.max(jnp.abs(got - want))) < TOL


def test_a_prompt_padded_to_a_bucket_and_a_slot_write(toy):
    """A right-padded prompt (pads at position -1) through the flash prefill:
    the real rows' logits are the reference's, the pads' rows store -1; then a
    decode step with a per-row ``write_index``, one row parked out of range."""
    cfg, _, params, toks, want = toy
    model = GPTLM(dataclasses.replace(cfg, prefill_flash=True, seq_len=128))
    n, bucket = 11, 16
    pos = jnp.where(jnp.arange(bucket) < n, jnp.arange(bucket), -1)
    pos = jnp.broadcast_to(pos, (2, bucket))
    padded = jnp.where(pos >= 0, toks[:, :bucket], 0)
    hidden, cache = prefill_step(model, params, padded, pos)
    got = lm_logits(cfg, params, hidden)[:, :n]
    assert float(jnp.max(jnp.abs(got - want[:, :n]))) < TOL
    table = cache["blocks"]["layer_0"]["attn"]["cached_pos"]
    assert table[0, :bucket].tolist() == list(range(n)) + [-1] * (bucket - n)
    before = cache["blocks"]["layer_2"]["attn"]["cached_latent"]
    hidden, cache = decode_step(
        model, params, cache, toks[:, n], jnp.asarray([n, n]),
        write_index=jnp.asarray([n, 128]),  # row 1 is parked: write dropped
    )
    got = lm_logits(cfg, params, hidden)[0, 0]
    assert float(jnp.max(jnp.abs(got - want[0, n]))) < TOL
    after = cache["blocks"]["layer_2"]["attn"]["cached_latent"]
    assert bool(jnp.all(after[1] == before[1]))
    assert not bool(jnp.all(after[0] == before[0]))


def test_absorbed_form_equals_expanded_form():
    """One layer, the same parameters: the cached call (absorbed: the stored
    rows are never expanded) against the call without a cache (expanded)."""
    from tpu_parallel.models.latent_attention import LatentAttention

    cfg = tiny_latent_experts()
    layer = LatentAttention(cfg, cfg.layer_pattern[0].latent)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 12, cfg.d_model))
    variables = layer.init(jax.random.PRNGKey(4), x, train=False)
    expanded = layer.apply(variables, x, train=False)
    absorbed, state = layer.apply(
        variables, x, train=False, decode=True, mutable=["cache"]
    )
    assert float(jnp.max(jnp.abs(absorbed - expanded))) < 1e-5
    step, _ = layer.apply(
        {**variables, **state}, x[:, :1] * 0.5, train=False, decode=True,
        mutable=["cache"],
    )
    longer = jnp.concatenate([x, x[:, :1] * 0.5], axis=1)
    want = layer.apply(variables, longer, train=False)[:, -1:]
    assert float(jnp.max(jnp.abs(step - want))) < 1e-5


def test_generate_runs_the_latent_model(toy):
    """The static ``generate()`` path (left-padded ragged prompts, absorbed
    prefill): greedy tokens are the full forward's argmax."""
    cfg, model, params, toks, _ = toy
    prompt = toks[:1, :7]
    out = generate(model, params, prompt, max_new_tokens=5)
    full = model.apply({"params": params}, out[:, :-1], train=False)
    assert out[0, 7:].tolist() == jnp.argmax(full[0, 6:], -1).tolist()


def test_sandwich_norms_are_four_and_each_counts(toy):
    cfg, model, params, toks, want = toy
    block = params["blocks"]["layer_1"]
    assert {k for k in block if k.startswith("norm")} == {
        "norm_attn", "norm_post_attn", "norm_mlp", "norm_post_mlp",
    }
    for name in ("norm_post_attn", "norm_post_mlp"):
        broken = jax.tree_util.tree_map_with_path(
            lambda p, x: jnp.ones_like(x)
            if [k.key for k in p][-2:] == [name, "scale"] else x, params,
        )
        got = model.apply({"params": broken}, toks, train=False)
        assert float(jnp.max(jnp.abs(got - want))) > 0.05, name
    # the same stack without them is the plain pre-norm block: two norms
    plain = GPTLM(dataclasses.replace(cfg, sandwich_norm=False))
    two = plain.init({"params": jax.random.PRNGKey(0)}, toks, train=False)
    assert {k for k in two["params"]["blocks"]["layer_1"] if "norm" in k} == {
        "norm_attn", "norm_mlp",
    }


def test_a_leading_dense_layer_then_expert_layers(toy):
    cfg, _, params, _, _ = toy
    kinds = [s.mlp for s in depth_specs(cfg)]
    assert kinds == ["dense", "experts", "experts", "experts"]
    assert layer_kinds(cfg) == {
        "layers": 4, "attention": 4, "dense": 1, "experts": 3,
    }
    assert cfg.routed_layers == 3
    assert "mlp" in params["blocks"]["layer_0"]
    assert params["blocks"]["layer_0"]["mlp"]["gate"]["shard"]["kernel"].shape == (64, 96)
    assert all("moe" in params["blocks"][f"layer_{i}"] for i in (1, 2, 3))
    assert spec_at(cfg, 0) is cfg.layer_head[0]
    assert spec_at(tiny_test(), 2) is None  # a uniform model's ops keep their names
    # one mechanism, not a flag of this model: a window layer ahead of full ones
    mixed = tiny_test(
        scan_layers=False, n_layers=3, positional="rope",
        layer_head=(LayerSpec(attn="window", window=4),),
    )
    assert [s.attn for s in depth_specs(mixed)] == ["window", "full", "full"]
    model = GPTLM(mixed)
    toks = jnp.ones((1, 8), jnp.int32)
    params = model.init({"params": jax.random.PRNGKey(0)}, toks, train=False)
    assert model.apply(params, toks, train=False).shape == (1, 8, 256)


def test_all_sixteen_shares_add_up_to_the_uncut_layer():
    """THE SHARE TEST: the routed parts of all shares (4 here, of 4 experts
    each), with the shared expert counted once, add up to the uncut layer's
    MLP output BEFORE the post-MLP norm, and the norm of that sum is the uncut
    layer's; the norm of ONE share's partial sum is not (it is not linear)."""
    cfg = tiny_latent_experts(
        experts=ExpertsSpec(
            n_experts=16, top_k=4, width=24, score="sigmoid", shared=1,
            held=None, shared_sum=True, route_scale=2.5,
        ),
    )
    _, params, toks = seeded(cfg, seed=3)
    whole = pangu_weights.tree_to_reference(params, cfg.n_heads)["layers"][1]
    shape = shape_of(cfg, (0, 16))
    h = jax.random.normal(jax.random.PRNGKey(5), (8, cfg.d_model))
    with jax.default_matmul_precision("highest"):
        shared, routed = ref.mlp_parts(h, whole, shape)
        parts = []
        for first in range(0, 16, 4):
            one = dict(whole, **{
                k: whole[k][first:first + 4] for k in ("e_gate", "e_up", "e_down")
            })
            s, r = ref.mlp_parts(h, one, dict(shape, held=(first, 4)))
            assert float(jnp.max(jnp.abs(s - shared))) == 0.0
            parts.append(r)
        total = shared + sum(parts)
        assert float(jnp.max(jnp.abs(total - (shared + routed)))) < 1e-5
        normed = ref.rms_norm(total, whole["n_post_mlp"], shape["eps"])
        uncut = ref.rms_norm(shared + routed, whole["n_post_mlp"], shape["eps"])
        assert float(jnp.max(jnp.abs(normed - uncut))) < 1e-5
        partial = ref.rms_norm(shared + parts[0], whole["n_post_mlp"], shape["eps"])
        assert float(jnp.max(jnp.abs(partial - uncut))) > 0.1
    # and the program's layer with every expert held is the uncut reference
    model = GPTLM(cfg)
    weights = pangu_weights.tree_to_reference(params, cfg.n_heads)
    want = ref.forward(weights, toks[0], shape)
    got = model.apply({"params": params}, toks[:1], train=False)[0]
    assert float(jnp.max(jnp.abs(got - want))) < TOL


LATENT = LatentSpec(q_rank=48, kv_rank=32, nope_dim=16, rope_dim=8, v_dim=16)


@pytest.mark.parametrize("fields, said", [
    (dict(block_len=4, mask_token_id=0), "block_len=4"),
    (dict(kv_cache_dtype="int8"), "int8 K/V"),
    (dict(kv_block_tokens=8, kv_pool_blocks=4), "paged pool"),
    (dict(attn_window=8), "a window"),
    (dict(bidirectional=True), "bidirectional"),
    (dict(beam_width=2), "beam"),
    (dict(qk_norm=True), "q/k norms"),
    (dict(attn_impl="ring"), "sequence parallelism"),
    (dict(positional="learned"), "rotary"),
    (dict(scan_layers=True), "layer_head"),
    (dict(parallel_block=True), "sandwich_norm"),
    (dict(residual_scale=0.5), "sandwich_norm"),
    (dict(n_layers=0), "leading"),
])
def test_construction_refuses_what_is_not_written(fields, said):
    with pytest.raises(ValueError, match=said):
        tiny_latent_experts(**fields)


def test_construction_refuses_a_latent_layer_without_its_sizes():
    with pytest.raises(ValueError, match="LatentSpec"):
        GPTConfig(
            positional="rope", scan_layers=False, n_layers=1,
            layer_pattern=(LayerSpec(attn="latent"),),
        )
    with pytest.raises(ValueError, match="a window"):
        tiny_latent_experts(layer_head=(
            LayerSpec(attn="latent", latent=LATENT, window=4),
        ))


def test_a_model_axis_larger_than_one_is_refused():
    from jax.sharding import Mesh, PartitionSpec as P

    from tpu_parallel.models.latent_attention import LatentAttention

    cfg = tiny_latent_experts()
    layer = LatentAttention(cfg, LATENT)
    x = jnp.zeros((1, 4, cfg.d_model))
    mesh = Mesh(np.array(jax.devices()[:2]), ("model",))
    init = jax.shard_map(
        lambda x: layer.init(jax.random.PRNGKey(0), x, train=False),
        mesh=mesh, in_specs=P(), out_specs=P(), check_vma=False,
    )
    with pytest.raises(NotImplementedError, match="model axis larger than 1"):
        init(x)


@pytest.fixture(scope="module")
def engine_toy():
    cfg = tiny_latent_experts(prefill_flash=True, seq_len=32)
    model, params, toks = seeded(cfg, seed=5, tokens=24)
    return cfg, model, params, toks


def test_engine_serves_it_and_counts_the_rows_it_reads(engine_toy):
    from tpu_parallel.obs import Tracer

    cfg, model, params, toks = engine_toy
    tracer = Tracer()
    eng = ServingEngine(
        model, params, n_slots=3, prefill_buckets=(16,),
        decode_steps_per_tick=4, tracer=tracer,
    )
    plan = eng.latent_plan
    assert plan["layers"] == plan["of_layers"] == 4 and plan["heads"] == 4
    assert (plan["q_rank"], plan["kv_rank"], plan["nope_dim"],
            plan["rope_dim"], plan["v_dim"], plan["row"]) == (48, 32, 16, 8, 16, 40)
    assert plan["bytes_per_position_per_layer"] == 160  # 40 float32 numbers
    assert plan["bytes_per_position"] == 640
    assert plan["decode"] == "absorbed"
    assert plan["prefill_16"] == plan["prefill_32"] == "expanded"
    assert eng.attn_plan == {"decode": {"path": "xla"}}
    # what the flash kernels do in each expanded shape: by itself and, for
    # the drivers that print latent_plan, at its END (a row under 128
    # positions is one tile; the head's width is its scores', 16 + 8)
    one_tile = lambda n: {
        "tile": n, "variant": "resident", "tiles_computed": 1, "tiles_masked": 1,
    }
    assert eng.prefill_attn_plan == {
        "prefill_16": one_tile(16), "prefill_32": one_tile(32),
    }
    assert list(plan)[-1] == "prefill_attn"
    assert plan["prefill_attn"] == eng.prefill_attn_plan
    instants = {e["name"]: e for e in tracer.instants if e["name"].endswith("_plan")}
    assert instants["latent_plan"]["attrs"]["row"] == 40
    assert "attn_plan" in instants and "moe_plan" in instants
    assert instants["prefill_attn_plan"]["attrs"]["prefill_32_tile"] == 32
    # the pool: one latent row a position and layer, no K/V leaf
    names = {p[-1].key for p, _ in jax.tree_util.tree_flatten_with_path(eng.pool.cache)[0]}
    assert names == {"cached_latent", "cached_pos", "cache_index"}
    prompt = [int(t) for t in toks[0, :11]]
    outs = [
        eng.add_request(Request(prompt=prompt, max_new_tokens=9)),
        eng.add_request(Request(prompt=prompt[:5], max_new_tokens=6)),
    ]
    while eng.has_work():
        eng.step()
    for out in outs:
        seq = jnp.asarray([list(out.request.prompt) + out.tokens])
        full = model.apply({"params": params}, seq, train=False)[0]
        first = len(out.request.prompt) - 1
        assert out.tokens == jnp.argmax(full[first:-1], -1).tolist()
    summary = eng.metrics.summary()
    assert summary["latent_bytes_per_position"] == 640
    # a step reads what the slot holds once its own row is in: at least the
    # two prompts' rows over 4 layers a step, at most every slot full
    steps = summary["decode_ticks"] * 4
    assert 4 * (11 + 5) <= summary["latent_positions_read"] <= 4 * 3 * 32 * steps
    assert eng.registry.counter("serving_latent_positions_read_total").value == (
        summary["latent_positions_read"]
    )
    # a fixed-slot engine exports and imports no K/V (the typed answers)
    assert eng.export_prefix("nobody") is None
    fresh = eng.reset_metrics().summary()
    assert fresh["latent_positions_read"] == 0
    assert fresh["latent_bytes_per_position"] == 640


def test_rows_read_by_a_tick_from_the_slot_mirrors(engine_toy):
    _, model, params, _ = engine_toy
    eng = ServingEngine(
        model, params, n_slots=3, prefill_buckets=(16,), decode_steps_per_tick=4,
    )
    eng._pos[:] = [10, 0, 30]
    # slot 0: 11 + 12 + 13 + 14; slot 2: 31 + 32 + 32 + 32 (a slot's end)
    assert eng._latent_rows_read([0, 2]) == 4 * (50 + 127)
    assert eng._latent_rows_read([]) is None
    plain = GPTLM(tiny_test(dtype=jnp.float32))
    other = ServingEngine(
        plain,
        plain.init({"params": jax.random.PRNGKey(0)}, jnp.zeros((1, 8), jnp.int32),
                   train=False)["params"],
        n_slots=2,
    )
    assert other.latent_plan is None and other._latent_rows_read([0]) is None
    assert other.metrics.summary()["latent_positions_read"] == 0


@pytest.mark.parametrize("options, said", [
    (dict(prefill_chunk_tokens=8), "prefill_chunk_tokens"),
    (dict(prefill_chunk_tokens=8, unified_tick=True), "prefill_chunk_tokens"),
    (dict(prefix_cache_size=2), "prefix_cache_size"),
    (dict(kv_block_tokens=8), "kv_block_tokens"),
    (dict(kv_block_tokens="auto", kv_radix_cache=True), "kv_block_tokens"),
    (dict(kv_radix_cache=True), "kv_radix_cache"),
    (dict(kv_host_blocks=4), "kv_radix_cache / kv_host_blocks"),
    (dict(draft_tokens=2), "draft_tokens"),
])
def test_engine_refuses_by_type(engine_toy, options, said):
    _, model, params, _ = engine_toy
    with pytest.raises(NotImplementedError, match="latent attention layers under") as err:
        ServingEngine(model, params, n_slots=2, prefill_buckets=(16,), **options)
    assert said in str(err.value)
