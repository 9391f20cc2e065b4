"""Recurrent (Mamba-2) layers beside attention layers: the program against the
plain reference (``benchmarks/reference/granite_hybrid_ref.py``) on LOGITS, at
a small size, seeded random weights, float32.

Two periods of ``ssm, ssm, attention, ssm``; ``d_model`` 64, 4 query heads of
16 over 2 K/V heads, 8 state heads of 16 with a state of 16, chunks of 8,
lengths that are no multiple of the chunk; the stated scalars (score scale,
embedding, residual and logit multipliers) all away from their defaults.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmarks"))

from lib import granite_weights  # noqa: E402
from reference import granite_hybrid_ref as ref  # noqa: E402

from tpu_parallel.models import generate as gen  # noqa: E402
from tpu_parallel.models.generate import beam_cache_batch_axis  # noqa: E402
from tpu_parallel.models.gpt import GPTLM, lm_logits, tiny_hybrid_ssm  # noqa: E402
from tpu_parallel.models.layers import SSMSpec  # noqa: E402
from tpu_parallel.models.ssm import last_inputs  # noqa: E402
from tpu_parallel.ops.ssd_scan import ssd_scan, ssd_step  # noqa: E402
from tpu_parallel.serving import (  # noqa: E402
    SchedulerConfig,
    ServingEngine,
    cache_pool,
)
from tpu_parallel.serving.request import Request  # noqa: E402

SEED = 2 ** 31 + 91
TOL = 5e-5  # float32 against float32: summation order only


def build(**overrides):
    cfg = tiny_hybrid_ssm(**overrides)
    model = GPTLM(cfg)
    abstract = jax.eval_shape(
        lambda: model.init(
            {"params": jax.random.PRNGKey(0)}, jnp.zeros((1, 8), jnp.int32),
            train=False,
        )
    )["params"]
    return cfg, model, abstract, granite_weights.make_params(SEED, abstract)


def shape_of(cfg):
    spec = next(s.ssm for s in cfg.layer_specs if s.mixer == "ssm")
    return {
        "layer_types": [
            "mamba" if s.mixer == "ssm" else "attention" for s in cfg.layer_specs
        ] * (cfg.n_layers // len(cfg.layer_specs)),
        "eps": cfg.norm_eps,
        "embedding_multiplier": cfg.embed_scale,
        "residual_multiplier": cfg.residual_scale,
        "attention_multiplier": cfg.attn_scale,
        "logits_scaling": 1.0 / cfg.logit_scale,
        "mamba_n_heads": spec.n_heads, "mamba_d_head": spec.head_dim,
        "mamba_d_state": spec.d_state, "mamba_n_groups": spec.n_groups,
        "mamba_d_conv": spec.d_conv,
    }


def reference_logits(cfg, abstract, tokens):
    w = granite_weights.to_reference(SEED, abstract, cfg.n_heads, cfg.n_kv_heads)
    return ref.forward(w, jnp.asarray(tokens, jnp.int32), shape_of(cfg))


def draw_tokens(n, seed=1):
    return np.asarray(
        jax.random.randint(jax.random.PRNGKey(seed), (n,), 1, 256), np.int32
    )


def scan_inputs(b, t, h=4, p=8, g=1, n=16, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    return dict(
        x=jax.random.normal(ks[0], (b, t, h, p)),
        dt=jax.nn.softplus(jax.random.normal(ks[1], (b, t, h)) - 2.0),
        A=-jnp.exp(jax.random.uniform(ks[2], (h,), minval=0.0, maxval=2.5)),
        B=jax.random.normal(ks[3], (b, t, g, n)),
        C=jax.random.normal(ks[4], (b, t, g, n)),
        D=jax.random.normal(ks[5], (h,)),
        init=jax.random.normal(ks[6], (b, h, p, n)),
    )


def sequential(i, row, t=None, init=None):
    """The definition, one step a token, for one row of ``scan_inputs``."""
    h = i["x"].shape[2]
    rep = lambda a: jnp.repeat(a[row, :t], h // a.shape[2], axis=1)

    def step(state, inputs):
        x_t, dt_t, b_t, c_t = inputs
        state = (
            jnp.exp(dt_t * i["A"])[:, None, None] * state
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        )
        return state, jnp.sum(state * c_t[:, None, :], -1) + i["D"][:, None] * x_t

    start = jnp.zeros_like(i["init"][row]) if init is None else init
    last, y = jax.lax.scan(
        step, start, (i["x"][row, :t], i["dt"][row, :t], rep(i["B"]), rep(i["C"]))
    )
    return y, last


# -- the scan and the step against the sequential recurrence -----------------


@pytest.mark.parametrize("t,chunk,groups", [
    (21, 8, 1), (8, 8, 1), (5, 8, 1), (33, 16, 2), (1, 8, 1),
])
def test_chunked_scan_matches_sequential_recurrence(t, chunk, groups):
    i = scan_inputs(2, t, g=groups)
    y, final = ssd_scan(
        i["x"], i["dt"], i["A"], i["B"], i["C"], i["D"], chunk=chunk
    )
    for row in range(2):
        want_y, want_s = sequential(i, row)
        np.testing.assert_allclose(y[row], want_y, atol=TOL, rtol=TOL)
        np.testing.assert_allclose(final[row], want_s, atol=TOL, rtol=TOL)
    # the reference's own recurrence is the same definition
    ref_y, _ = ref.recurrence(
        i["x"][0], i["dt"][0], i["A"],
        jnp.repeat(i["B"][0], 4 // groups, 1), jnp.repeat(i["C"][0], 4 // groups, 1),
        i["D"],
    )
    np.testing.assert_allclose(y[0], ref_y, atol=TOL, rtol=TOL)


def test_scan_from_a_state_with_pads_matches_sequential():
    """An initial state is continued; tokens that are not valid change
    neither the state nor any valid token's output, wherever they sit."""
    i = scan_inputs(3, 13, seed=3)
    lengths = [13, 6, 0]
    valid = jnp.arange(13)[None, :] < jnp.asarray(lengths)[:, None]
    y, final = ssd_scan(
        i["x"], i["dt"], i["A"], i["B"], i["C"], i["D"], i["init"], valid,
        chunk=4,
    )
    for row, n in enumerate(lengths):
        want_y, want_s = sequential(i, row, t=n, init=i["init"][row])
        np.testing.assert_allclose(y[row, :n], want_y, atol=TOL, rtol=TOL)
        np.testing.assert_allclose(final[row], want_s, atol=TOL, rtol=TOL)
    np.testing.assert_array_equal(final[2], i["init"][2])


def test_step_is_the_scan_at_one_token_and_parks_a_pad():
    i = scan_inputs(2, 1, seed=5)
    args = (i["x"][:, 0], i["dt"][:, 0], i["A"], i["B"][:, 0], i["C"][:, 0],
            i["D"], i["init"])
    y, new = ssd_step(*args, valid=jnp.asarray([True, False]))
    want_y, want_s = sequential(i, 0, init=i["init"][0])
    np.testing.assert_allclose(y[0], want_y[0], atol=TOL, rtol=TOL)
    np.testing.assert_allclose(new[0], want_s, atol=TOL, rtol=TOL)
    np.testing.assert_array_equal(new[1], i["init"][1])
    ys, finals = ssd_scan(
        i["x"], i["dt"], i["A"], i["B"], i["C"], i["D"], i["init"]
    )
    np.testing.assert_allclose(ys[0, 0], y[0], atol=TOL, rtol=TOL)
    np.testing.assert_allclose(finals[0], new[0], atol=TOL, rtol=TOL)


def test_conv_window_keeps_the_last_real_inputs():
    window = jnp.arange(6.0).reshape(1, 3, 2).repeat(4, 0)
    inputs = 10 + jnp.arange(10.0).reshape(1, 5, 2).repeat(4, 0)
    valid = jnp.asarray([
        [1, 1, 1, 1, 1], [1, 1, 0, 0, 0], [0, 0, 0, 0, 0], [0, 0, 0, 1, 1],
    ], bool)
    got = last_inputs(window, inputs, valid)
    np.testing.assert_array_equal(got[0], inputs[0, 2:])
    np.testing.assert_array_equal(
        got[1], jnp.concatenate([window[1, 2:], inputs[1, :2]])
    )
    np.testing.assert_array_equal(got[2], window[2])
    np.testing.assert_array_equal(
        got[3], jnp.concatenate([window[3, 2:], inputs[3, 3:]])
    )
    one = last_inputs(window[:2], inputs[:2, :1], jnp.asarray([[True], [False]]))
    np.testing.assert_array_equal(
        one[0], jnp.concatenate([window[0, 1:], inputs[0, :1]])
    )
    np.testing.assert_array_equal(one[1], window[1])


# -- the model against the reference -----------------------------------------


@pytest.mark.parametrize("tokens", [21, 8, 3])
def test_full_forward_matches_reference(tokens):
    cfg, model, abstract, params = build()
    toks = draw_tokens(tokens)
    logits = model.apply({"params": params}, toks[None], train=False)[0]
    want = reference_logits(cfg, abstract, toks)
    assert float(jnp.std(want)) > 0.1  # the comparison has something to see
    np.testing.assert_allclose(logits, want, atol=TOL, rtol=TOL)


def test_every_stated_scalar_and_both_mixers_carry_weight():
    """The toy's logits move when a scalar or a mixer is changed: the
    comparisons above would see a layer that did nothing."""
    cfg, model, abstract, params = build()
    toks = draw_tokens(21)[None]
    base = model.apply({"params": params}, toks, train=False)
    for change in (dict(attn_scale=None), dict(embed_scale=1.0),
                   dict(residual_scale=1.0), dict(logit_scale=1.0)):
        other = GPTLM(tiny_hybrid_ssm(**change)).apply(
            {"params": params}, toks, train=False
        )
        assert float(jnp.max(jnp.abs(other - base))) > 1e-2, change
    for layer, part in (("layer_0", "ssm"), ("layer_2", "attn")):
        broken = jax.tree_util.tree_map(lambda x: x, params)
        broken["blocks"][layer][part] = jax.tree_util.tree_map(
            jnp.zeros_like, broken["blocks"][layer][part]
        )
        other = model.apply({"params": broken}, toks, train=False)
        assert float(jnp.max(jnp.abs(other - base))) > 1e-2, (layer, part)


def decode_logits(cfg, model, params, cache, toks, start):
    out = []
    for i in range(start, len(toks)):
        h, cache = gen.decode_step(
            model, params, cache, jnp.asarray(toks[i:i + 1]), jnp.asarray([i])
        )
        out.append(lm_logits(cfg, params, h)[0])
    return jnp.concatenate(out)


def test_prefill_then_decode_logits_match_reference():
    cfg, model, abstract, params = build()
    toks = draw_tokens(29)
    want = reference_logits(cfg, abstract, toks)
    hidden, cache = gen.prefill_step(
        model, params, jnp.asarray(toks[None, :13]), jnp.arange(13)[None]
    )
    got = jnp.concatenate([
        lm_logits(cfg, params, hidden)[0],
        decode_logits(cfg, model, params, cache, toks, 13),
    ])
    np.testing.assert_allclose(got, want, atol=TOL, rtol=TOL)


@pytest.mark.parametrize("layout", ["right_bucket", "left", "two_chunks"])
def test_pads_change_nothing(layout):
    """The pad rule: a prompt of 11 right-padded to a bucket of 16,
    left-padded to 16, or fed as chunks of 7 + 4 (the second padded to 7,
    the state carried) leaves the cache of the exact-length single pass, and
    decoding from it gives the reference's logits."""
    cfg, model, abstract, params = build()
    toks = draw_tokens(19, seed=4)
    n = 11
    want = reference_logits(cfg, abstract, toks)
    _, exact = gen.prefill_step(
        model, params, jnp.asarray(toks[None, :n]), jnp.arange(n)[None]
    )
    pad = np.zeros(16 - n, np.int32)
    if layout == "right_bucket":
        positions, _ = gen.padded_prefill_inputs([n], 16)
        hidden, cache = gen.prefill_step(
            model, params, jnp.asarray(np.concatenate([toks[:n], pad]))[None],
            positions,
        )
        got = lm_logits(cfg, params, hidden)[0, :n]
    elif layout == "left":
        positions = jnp.asarray(
            np.concatenate([np.full(16 - n, -1), np.arange(n)])
        )[None]
        hidden, cache = gen.prefill_step(
            model, params, jnp.asarray(np.concatenate([pad, toks[:n]]))[None],
            positions,
        )
        got = lm_logits(cfg, params, hidden)[0, 16 - n:]
    else:
        hidden1, cache = gen.prefill_step(
            model, params, jnp.asarray(toks[None, :7]), jnp.arange(7)[None]
        )
        second = np.concatenate([toks[7:n], np.zeros(3, np.int32)])
        positions = jnp.asarray(np.concatenate([np.arange(7, n), [-1] * 3]))[None]
        hidden2, cache = gen.prefill_extend_step(
            model, params, cache, jnp.asarray(second)[None], positions,
            jnp.asarray([7]),
        )
        got = jnp.concatenate([
            lm_logits(cfg, params, hidden1)[0],
            lm_logits(cfg, params, hidden2)[0, :n - 7],
        ])
    np.testing.assert_allclose(got, want[:n], atol=TOL, rtol=TOL)
    for (path, a), b in zip(
        jax.tree_util.tree_flatten_with_path(cache)[0],
        jax.tree_util.tree_leaves(exact),
    ):
        name = cache_pool._leaf_name(path)
        if name.startswith(cache_pool.STATE_LEAVES):
            np.testing.assert_allclose(a, b, atol=TOL, rtol=TOL, err_msg=name)
    if layout != "left":  # a left-padded stripe decodes through generate()
        np.testing.assert_allclose(
            decode_logits(cfg, model, params, cache, toks, n), want[n:],
            atol=TOL, rtol=TOL,
        )


def test_generate_left_padded_rows_match_each_alone():
    cfg, model, _, params = build()
    long, short = draw_tokens(12, seed=6), draw_tokens(5, seed=7)
    batch = np.stack([long, np.concatenate([np.zeros(7, np.int32), short])])
    mask = np.stack([np.ones(12, bool), np.arange(12) >= 7])
    both = gen.generate(
        model, params, jnp.asarray(batch), max_new_tokens=6,
        prompt_mask=jnp.asarray(mask),
    )
    for row, prompt in enumerate((long, short)):
        alone = gen.generate(model, params, jnp.asarray(prompt[None]), max_new_tokens=6)
        np.testing.assert_array_equal(both[row], alone[0])


# -- the cache registry and the pool -----------------------------------------


def test_cache_registry_knows_the_state_leaves():
    cfg, model, _, params = build()
    pool = cache_pool.empty_pool(model, params, 3)
    seen = set()
    for path, leaf in jax.tree_util.tree_flatten_with_path(pool)[0]:
        name = cache_pool._leaf_name(path)
        ax = beam_cache_batch_axis(path, leaf)
        if name in ("ssm_state", "conv_state"):
            seen.add(name)
            assert ax == 0 and leaf.shape[0] == 3
            assert leaf.dtype == (jnp.float32 if name == "ssm_state" else cfg.dtype)
            assert not np.asarray(leaf).any()
    assert seen == {"ssm_state", "conv_state"}
    stacked = jnp.zeros((2, 3, 8, 16, 16))  # under a layer scan
    key = jax.tree_util.DictKey("ssm_state")
    assert beam_cache_batch_axis((key,), stacked) == 1
    conv = jax.tree_util.DictKey("conv_state")
    assert beam_cache_batch_axis((conv,), jnp.zeros((2, 3, 3, 160))) == 1


def test_clear_rows_zeroes_a_slots_state_and_only_its():
    cfg, model, _, params = build()
    pool = cache_pool.empty_pool(model, params, 3)
    dirty = jax.tree_util.tree_map(lambda x: x + 1, pool)
    cleared = cache_pool.clear_rows(dirty, jnp.int32(1))
    for path, leaf in jax.tree_util.tree_flatten_with_path(cleared)[0]:
        name = cache_pool._leaf_name(path)
        if name.startswith(cache_pool.STATE_LEAVES):
            assert not np.asarray(leaf[1]).any(), name
            assert np.asarray(leaf[0] == 1).all() and np.asarray(leaf[2] == 1).all()
        elif name.startswith("cached_pos"):
            assert np.asarray(leaf[1] == -1).all()
        elif name.startswith("cached_"):
            assert np.asarray(leaf == 1).all()  # payloads stay: dead bytes


def test_int8_kv_leaves_the_state_float32():
    _, model, _, params = build(kv_cache_dtype="int8")
    pool = cache_pool.empty_pool(model, params, 2)
    kinds = {
        cache_pool._leaf_name(p): leaf.dtype
        for p, leaf in jax.tree_util.tree_flatten_with_path(pool)[0]
    }
    assert kinds["ssm_state"] == jnp.float32
    assert kinds["cached_key"] == jnp.int8


# -- the engine ----------------------------------------------------------------


def outputs(engine, prompts, new=6):
    outs = [
        engine.add_request(Request(
            prompt=list(map(int, p)), max_new_tokens=new,
        )) for p in prompts
    ]
    engine.run()
    return [list(out.tokens) for out in outs]


def generated(model, params, prompt, new=6):
    return list(map(int, gen.generate(
        model, params, jnp.asarray(prompt[None]), max_new_tokens=new
    )[0]))


ENGINES = {
    "per_step": dict(decode_steps_per_tick=1, prefill_buckets=None),
    "fused_bucketed": dict(prefill_buckets=(8, 16)),
    "fused_bucketed_batch": dict(prefill_buckets=(8, 16), prefill_batch=2),
    "per_step_chunked": dict(
        decode_steps_per_tick=1, prefill_buckets=(8, 16), prefill_chunk_tokens=6
    ),
    "unified_chunked": dict(prefill_buckets=(8, 16), prefill_chunk_tokens=6),
    # the cell's: one row a call, several same-bucket admissions a tick
    "fused_batch1_of4": dict(
        prefill_buckets=(8, 16), prefill_batch=1,
        scheduler=SchedulerConfig(max_prefills_per_tick=4),
    ),
}


@pytest.mark.parametrize("name", sorted(ENGINES))
def test_engine_output_equals_generate(name):
    """Greedy serving equals ``generate()`` on every path the model runs:
    exact and bucketed whole-prompt prefill (a dummy row beside the real
    one), chunked prefill by extension and inside the unified tick, the
    per-step and the fused tick, with more requests than slots so that slots
    are reused and ticks run with free and finished slots beside live ones."""
    _, model, _, params = build()
    prompts = [draw_tokens(n, seed=10 + n) for n in (5, 11, 3, 14, 9)]
    engine = ServingEngine(model, params, n_slots=3, **ENGINES[name])
    got = outputs(engine, prompts, new=7)
    for prompt, tokens in zip(prompts, got):
        assert tokens == generated(model, params, prompt, new=7)
    summary = engine.metrics.summary()
    assert summary["prefill_tokens_real"] == sum(len(p) for p in prompts)
    assert summary["state_bytes_per_slot"] == engine.ssm_plan["state_bytes_per_slot"]
    if name == "fused_batch1_of4":
        assert {rows for _, rows, _ in engine._prefill_shapes} == {1}
        assert summary["prefill_calls"] == len(prompts)
    if name == "per_step":
        assert summary["prefill_tokens_padded"] == 0
    else:
        assert summary["prefill_tokens_padded"] > 0


@pytest.mark.parametrize("chunked", [False, True])
def test_a_reused_slot_equals_a_fresh_engine(chunked):
    """A slot's next occupant starts from S = 0: the stale state of the
    request before it leaks into nothing (whole-prompt insert overwrites the
    row; a chunked start clears it)."""
    _, model, _, params = build()
    kw = dict(prefill_buckets=(8, 16))
    if chunked:
        kw["prefill_chunk_tokens"] = 6
    first, second = draw_tokens(13, seed=31), draw_tokens(10, seed=32)
    used = ServingEngine(model, params, n_slots=1, **kw)
    assert outputs(used, [first])[0] == generated(model, params, first)
    fresh = ServingEngine(model, params, n_slots=1, **kw)
    assert outputs(used, [second]) == outputs(fresh, [second])
    assert outputs(fresh, [second])[0] == generated(model, params, second)


def test_reference_returns_the_state_a_position_left():
    """``keep``: the state after that token, whatever follows it; the logits
    up to it are those of the pass without it."""
    cfg, _, abstract, _ = build()
    tokens = draw_tokens(19, seed=5)
    weights = lambda: granite_weights.to_reference(
        SEED, abstract, cfg.n_heads, cfg.n_kv_heads
    )
    (logits,), (states,) = ref.forward_each(
        weights(), [jnp.asarray(tokens)], shape_of(cfg), keep=[11]
    )
    (cut_logits,), (cut,) = ref.forward_each(
        weights(), [jnp.asarray(tokens[:12])], shape_of(cfg), keep=[11]
    )
    assert len(states) == len(cut) == cfg.recurrent_layers
    for a, b in zip(states, cut):
        np.testing.assert_allclose(a, b, atol=TOL, rtol=TOL)
        assert float(jnp.abs(a).max()) > 0
    np.testing.assert_allclose(logits[:12], cut_logits, atol=TOL, rtol=TOL)
    np.testing.assert_allclose(
        logits[:12], reference_logits(cfg, abstract, tokens)[:12], atol=TOL, rtol=TOL
    )


@pytest.mark.parametrize("name", ["fused_batch1_of4", "unified_chunked"])
def test_a_retired_slot_holds_the_state_its_last_fed_token_left(name):
    """What the benchmark's probe reads: when the engine retires a stream, the
    slot's row still holds the state after ``prompt + tokens[:-1]`` (the last
    token is sampled and never fed; the tick in flight runs the finished slot
    as a pad), equal to the reference's after the same tokens; read through
    ``pool.extract`` before the slot is seated again."""
    cfg, model, abstract, params = build()
    prompts = [draw_tokens(n, seed=40 + n) for n in (5, 11, 3, 14, 9)]
    engine = ServingEngine(model, params, n_slots=2, **ENGINES[name])
    read = {}
    inner = engine.release_slot

    def release_slot(slot):
        out = engine._slot_out[slot]
        read[tuple(out.request.prompt)] = granite_weights.slot_states(
            engine.pool.extract(slot)
        )
        inner(slot)

    engine.release_slot = release_slot
    got = outputs(engine, prompts, new=7)
    assert len(read) == len(prompts)
    for prompt, tokens in zip(prompts, got):
        fed = list(map(int, prompt)) + tokens[:-1]
        _, (want,) = ref.forward_each(
            granite_weights.to_reference(SEED, abstract, cfg.n_heads, cfg.n_kv_heads),
            [jnp.asarray(fed + [0, 0, 0], jnp.int32)], shape_of(cfg),
            keep=[len(fed) - 1],
        )
        have = read[tuple(map(int, prompt))]
        assert len(have) == len(want) == cfg.recurrent_layers
        for a, b in zip(have, want):
            np.testing.assert_allclose(a[0], b, atol=TOL, rtol=TOL)


def test_a_stale_state_would_show():
    """The comparison above can fail: with the clear left out, a chunked
    prompt in a used slot continues the previous occupant's state."""
    _, model, _, params = build()
    kw = dict(prefill_buckets=(8, 16), prefill_chunk_tokens=6,
              decode_steps_per_tick=1)
    first, second = draw_tokens(13, seed=31), draw_tokens(10, seed=32)
    used = ServingEngine(model, params, n_slots=1, **kw)
    outputs(used, [first])
    used.pool._clear = lambda cache, slot: cache
    assert outputs(used, [second])[0] != generated(model, params, second)


def test_a_parked_slot_not_told_would_show(monkeypatch):
    """With parked rows left at their old positions, a slot in the middle of
    a chunked prompt is stepped by every decode step of the ticks between its
    chunks.  (A model of another ``seq_len``: the engine caches its programs
    by model, and these broken ones must serve nobody else.)"""
    from tpu_parallel.serving import engine as engine_module

    monkeypatch.setattr(engine_module, "_pad_parked", lambda cfg, pos, widx: pos)
    _, model, _, params = build(seq_len=44)
    prompts = [draw_tokens(n, seed=10 + n) for n in (5, 14, 17)]
    engine = ServingEngine(
        model, params, n_slots=3, prefill_buckets=(8, 16),
        prefill_chunk_tokens=6,
    )
    got = outputs(engine, prompts, new=7)
    assert got[0] == generated(model, params, prompts[0], new=7)  # one chunk
    assert got[1:] != [generated(model, params, p, new=7) for p in prompts[1:]]


def test_ssm_plan_is_logged_and_traced(caplog):
    import logging

    from tpu_parallel.obs import Tracer

    cfg, model, _, params = build()
    tracer = Tracer()
    with caplog.at_level(logging.INFO, logger="tpu_parallel.serving.engine"):
        engine = ServingEngine(model, params, n_slots=2, tracer=tracer)
    plan = engine.ssm_plan
    assert plan["ssm_layers"] == 6 and plan["attention_layers"] == 2
    assert plan["state_dtype"] == "float32"
    # 6 layers x (8 x 16 x 16 fp32 + 3 x 160 fp32 conv inputs)
    assert plan["state_bytes_per_slot"] == 6 * (8 * 16 * 16 * 4 + 3 * 160 * 4)
    # 2 layers x (K and V: 48 x 2 x 16 fp32, 48 positions int32)
    assert plan["kv_bytes_per_slot"] == 2 * (2 * 48 * 2 * 16 * 4 + 48 * 4)
    assert any("ssm_plan" in r.getMessage() for r in caplog.records)
    assert any(e["name"] == "ssm_plan" for e in tracer.instants)
    from tpu_parallel.models.gpt import tiny_test

    plain = GPTLM(tiny_test())
    p = plain.init({"params": jax.random.PRNGKey(0)}, jnp.zeros((1, 8), jnp.int32), train=False)["params"]
    other = ServingEngine(plain, p, n_slots=2)
    assert other.ssm_plan is None
    assert other.metrics.summary()["state_bytes_per_slot"] == 0


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


def test_mixer_kind_is_checked():
    from tpu_parallel.models.layers import LayerSpec

    cfg = tiny_hybrid_ssm()
    bad = cfg.layer_pattern[:3] + (LayerSpec(mixer="conv"),)
    model = GPTLM(tiny_hybrid_ssm(layer_pattern=bad))
    with pytest.raises(ValueError, match="mixer"):
        model.init({"params": jax.random.PRNGKey(0)}, jnp.zeros((1, 8), jnp.int32), train=False)
    assert cfg.recurrent_layers == 6
    assert SSMSpec(8, 16, 16).chunk == 256
