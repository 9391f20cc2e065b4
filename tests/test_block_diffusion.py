"""Generation by diffusion over blocks (a model with ``block_len`` L > 0):
the layer (per-head q/k norms, rotate-half rotary pairs, the block rule in
every attention path), the engine's block step against the plain reference's
own generation loop token for token, the per-request knob through HTTP, the
journal and a recovery, every refusal, the counters, and that the programs
the other serving cells run lower to the text they had."""

import hashlib
import json
import logging
import os
import sys
import urllib.error
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO_ROOT, "benchmarks"))

from lib import sdar_weights  # noqa: E402
from reference import sdar_moe_ref as ref  # noqa: E402

from tpu_parallel.cluster import Frontend, FrontendConfig  # noqa: E402
from tpu_parallel.daemon import (  # noqa: E402
    DaemonConfig,
    DaemonHTTPServer,
    ServingDaemon,
    load_state,
    read_journal,
)
from tpu_parallel.daemon.http import build_request  # noqa: E402
from tpu_parallel.models import GPTLM, tiny_test  # noqa: E402
from tpu_parallel.models.gpt import (  # noqa: E402
    tiny_block_diffusion,
    tiny_hybrid_ssm,
    tiny_parallel_experts,
)
from tpu_parallel.models.layers import (  # noqa: E402
    apply_rope,
    causal_attention,
    decode_attention,
)
from tpu_parallel.obs.registry import MetricRegistry  # noqa: E402
from tpu_parallel.obs.tracer import Tracer  # noqa: E402
from tpu_parallel.ops.flash_attention import (  # noqa: E402
    flash_attention,
    flash_plan,
)
from tpu_parallel.serving.engine import (  # noqa: E402
    BLOCK_CARRIED as CARRIED,
    BLOCK_DENOISED as DENOISED,
    BLOCK_WAITED as WAITED,
)
from tpu_parallel.serving import (  # noqa: E402
    REJECT_UNSUPPORTED,
    REJECTED,
    Request,
    SamplingParams,
    SchedulerConfig,
    ServingEngine,
    engine as engine_mod,
)

SEED = 7


def build(block_len=4, head_scale=1.0, **overrides):
    """A tiny block model, its seeded weights, and the same weights in the
    reference's layout (``head_scale`` multiplies the head: sharper
    logits, so that some confidences pass a threshold)."""
    cfg = tiny_block_diffusion(block_len=block_len, **overrides)
    model = GPTLM(cfg)
    abstract = jax.eval_shape(
        lambda: model.init(
            {"params": jax.random.PRNGKey(0)}, jnp.zeros((1, 16), jnp.int32),
            train=False,
        )
    )["params"]
    params = sdar_weights.make_params(SEED, abstract)
    weights = sdar_weights.to_reference(
        SEED, abstract, cfg.n_heads, cfg.n_kv_heads
    )
    weights["layers"] = list(weights["layers"])
    if head_scale != 1.0:
        params["lm_head"]["shard"]["kernel"] = (
            params["lm_head"]["shard"]["kernel"] * head_scale
        )
        weights["head"] = weights["head"] * head_scale
    shape = {
        "block_len": block_len, "rope_theta": cfg.rope_theta,
        "num_experts_per_tok": 2, "eps": cfg.norm_eps,
    }
    return cfg, model, params, weights, shape


@pytest.fixture(scope="module", params=[4, 16])
def env(request):
    return build(request.param)


def prompt_of(n, seed):
    return [int(t) for t in np.random.RandomState(seed).randint(0, 250, n)]


# -- the layer -----------------------------------------------------------------


def test_forward_matches_the_reference(env):
    cfg, model, params, weights, shape = env
    tokens = prompt_of(32, 0)
    with jax.default_matmul_precision("highest"):
        ours = model.apply(
            {"params": params}, jnp.asarray(tokens)[None], train=False
        )[0]
    theirs = ref.forward(weights, tokens, shape)
    assert float(jnp.std(theirs)) > 0.5  # the logits carry weight
    np.testing.assert_allclose(ours, theirs, atol=2e-5)


def test_rotate_half_pairs_and_norm_scales_carry_weight():
    """The two stated sizes are read: another pairing, or q/k norms left
    out, is another model."""
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 6, 2, 8))
    pos = jnp.arange(6)[None]
    half = apply_rope(x, pos, 1e4, "half")
    inter = apply_rope(x, pos, 1e4)
    assert float(jnp.abs(half - inter).max()) > 0.1
    # pair i of "half" is (x[i], x[i + 4]): the interleaved rotation of the
    # same pairs laid side by side
    perm = jnp.array([0, 4, 1, 5, 2, 6, 3, 7])
    np.testing.assert_allclose(
        half[..., perm], apply_rope(x[..., perm], pos, 1e4), atol=1e-6
    )
    cfg, model, params, _, _ = build(4)
    tokens = jnp.asarray(prompt_of(16, 1))[None]
    base = model.apply({"params": params}, tokens, train=False)
    plain = GPTLM(tiny_block_diffusion(qk_norm=False))
    bare = jax.tree.map(lambda x: x, params)
    for layer in bare["blocks"].values():
        del layer["attn"]["q_norm"], layer["attn"]["k_norm"]
    other = plain.apply({"params": bare}, tokens, train=False)
    assert float(jnp.abs(base - other).max()) > 1e-2


@pytest.mark.parametrize("block_len", [4, 16])
@pytest.mark.parametrize("seq,tiles", [(64, (32, 16)), (128, (None, None))])
def test_block_rule_in_every_attention_path(block_len, seq, tiles):
    """``causal_attention``, ``decode_attention`` and the flash kernels
    (resident and streamed, interpret mode, grouped queries) against the
    rule written out, forward and backward."""
    key = jax.random.PRNGKey(block_len + seq)
    q = jax.random.normal(key, (2, seq, 4, 16))
    k = jax.random.normal(jax.random.fold_in(key, 1), (2, seq, 2, 16))
    v = jax.random.normal(jax.random.fold_in(key, 2), (2, seq, 2, 16))
    kr, vr = jnp.repeat(k, 2, 2), jnp.repeat(v, 2, 2)
    qp, kp = jnp.arange(seq)[:, None], jnp.arange(seq)[None, :]
    seen = kp <= qp // block_len * block_len + block_len - 1
    assert bool(seen[0, block_len - 1]) and not bool(seen[0, block_len])
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, kr) / 4.0
    want = jnp.einsum(
        "bhqk,bkhd->bqhd",
        jax.nn.softmax(jnp.where(seen, scores, -1e30), -1), vr,
    )
    dense = lambda q: causal_attention(q, kr, vr, block_len=block_len)
    np.testing.assert_allclose(dense(q), want, atol=2e-6)
    pos = jnp.broadcast_to(jnp.arange(seq), (2, seq))
    np.testing.assert_allclose(
        decode_attention(q, k, v, pos, block_len=block_len), want, atol=2e-6
    )
    bq, bk = tiles
    for stream in (False, True):
        flash = lambda q: flash_attention(
            q, k, v, block_q=bq, block_k=bk, block_len=block_len, stream=stream
        )
        np.testing.assert_allclose(flash(q), want, atol=3e-6)
    grad = lambda f: jax.grad(lambda q: jnp.sum(f(q) ** 2))(q)
    np.testing.assert_allclose(grad(flash), grad(dense), atol=2e-5)


def test_flash_plan_says_which_rule_it_planned():
    assert flash_plan(256, 128)["rule"] == "causal"
    assert flash_plan(256, 128, causal=False)["rule"] == "full"
    plan = flash_plan(256, 128, causal=4)
    assert plan["rule"] == "block" and plan["block_len"] == 4
    causal = flash_plan(256, 128)
    # tiles are whole blocks: only the tiles the diagonal crosses are masked
    assert plan["fwd"] == causal["fwd"]
    assert flash_plan(64, 16, causal=16, block_q=32, block_k=16)["fwd"][
        "tiles_masked"
    ] < flash_plan(64, 16, causal=4, block_q=32, block_k=16)["fwd"][
        "tiles_masked"
    ]
    assert flash_plan(96, 16, causal=64) is None  # no tile of whole blocks
    with pytest.raises(ValueError, match="no window"):
        flash_plan(256, 128, causal=4, window=64)


def test_a_pad_query_sees_nothing_and_a_block_sees_its_end():
    q = jnp.ones((1, 3, 2, 8))
    kv = jax.random.normal(jax.random.PRNGKey(3), (1, 8, 2, 8))
    stored = jnp.array([[0, 1, 2, 3, 4, 5, -1, 7]])
    pos = jnp.array([[1, 5, -1]])
    out = decode_attention(
        q, kv, kv, pos, k_positions=stored, block_len=4
    )
    first = decode_attention(
        q[:, :1], kv[:, :4], kv[:, :4], pos[:, :1], block_len=4
    )
    np.testing.assert_allclose(out[:, :1], first, atol=1e-6)  # keys 0..3
    keys = jnp.array([0, 1, 2, 3, 4, 5, 7])  # position 7 ends block 1
    second = decode_attention(
        q[:, 1:2], kv[:, keys], kv[:, keys], pos[:, 1:2],
        k_positions=stored[:, keys], block_len=4,
    )
    np.testing.assert_allclose(out[:, 1:2], second, atol=1e-6)


# -- the engine against the reference's own generation loop --------------------


def serve(env, requests, n_slots=3, steps=4, **engine_kw):
    cfg, model, params, _, _ = env
    with jax.default_matmul_precision("highest"):
        eng = ServingEngine(
            model, params, n_slots=n_slots, prefill_buckets=(16, 32),
            scheduler=SchedulerConfig(max_prefills_per_tick=2),
            decode_steps_per_tick=steps, **engine_kw,
        )
        outs = [eng.add_request(r) for r in requests]
        eng.run()
    return eng, outs


def reference(env, request):
    cfg, _, _, weights, shape = env
    return ref.generate(
        weights, request.prompt, shape, cfg.mask_token_id,
        request.max_new_tokens, request.denoising_steps,
        request.confidence_threshold, request.eos_token_id,
    )


def test_engine_equals_the_reference_token_for_token(env):
    """A batch that mixes steps a block (L, L/2, 2, 1, default), prompts
    with every tail ``P mod L`` (shorter than a block among them), budgets
    that are no multiple of L, through 3 slots (so slots are seated again)
    and ticks of 4 forwards: tokens AND the step each was filled at."""
    size = env[0].block_len
    lengths = [size + t for t in range(size)] + [3, 31, 2 * size]
    steps = [size, size // 2, 2, 1, None]
    requests = [
        Request(
            prompt=prompt_of(p, i), max_new_tokens=7 + 3 * (i % 4),
            denoising_steps=steps[i % len(steps)],
        )
        for i, p in enumerate(lengths)
    ]
    eng, outs = serve(env, requests)
    for request, out in zip(requests, outs):
        tokens, fill_steps = reference(env, request)
        assert out.status == "finished" and out.finish_reason == "length"
        assert out.tokens == tokens, (len(request.prompt), out.tokens, tokens)
        assert out.fill_steps == fill_steps
    summary = eng.metrics.summary()
    assert summary["tokens_out"] == sum(r.max_new_tokens for r in requests)
    assert summary["block_forwards"] > summary["block_commit_forwards"] > 0
    # a live slot-step carried a commit, or waited for a wide step, or neither
    assert (
        summary["block_commit_forwards"] + summary["block_commit_waits"]
        < summary["block_forwards"]
    )
    assert summary["blocks_completed"] >= len(requests)
    assert summary["tokens_per_forward"] == round(
        summary["block_tokens_filled"] / summary["block_forwards"], 4
    )
    assert 0 < summary["commit_forward_share"] < 0.5
    pool = eng.pool
    assert pool.n_free == pool.n_slots


def test_per_step_engine_runs_the_same_core(env):
    request = Request(prompt=prompt_of(9, 3), max_new_tokens=9,
                      denoising_steps=2)
    _, (out,) = serve(env, [request], steps=1)
    assert out.tokens == reference(env, request)[0]


def test_an_eos_inside_a_block_ends_the_stream_there(env):
    size = env[0].block_len
    budget = 2 * size + 3
    base = Request(prompt=prompt_of(size + 1, 5), max_new_tokens=budget)
    tokens, _ = reference(env, base)
    # an id that first appears inside a block, not at its end
    at = next(
        i for i, t in enumerate(tokens)
        if (size + 1 + i) % size != size - 1 and t not in tokens[:i]
    )
    request = Request(prompt=base.prompt, max_new_tokens=budget,
                      eos_token_id=tokens[at])
    _, (out,) = serve(env, [request])
    assert out.finish_reason == "eos"
    assert out.tokens == tokens[: at + 1] == reference(env, request)[0]


def test_a_slot_seated_after_a_longer_occupant(env):
    """One slot.  The second prompt is shorter than a block, so no prefill
    runs for it and NOTHING clears the first occupant's columns: they hold
    positions past every block the newcomer reads (``block_step`` says
    why), and its output equals the reference's."""
    long = Request(prompt=prompt_of(30, 8), max_new_tokens=20)
    short = Request(prompt=prompt_of(3, 9), max_new_tokens=17,
                    denoising_steps=2)
    eng, outs = serve(env, [long, short], n_slots=1)
    assert eng.metrics.summary()["prefill_calls"] == 1
    for request, out in zip((long, short), outs):
        assert out.tokens == reference(env, request)[0]


@pytest.mark.parametrize("block_len", [4, 16])
def test_the_dynamic_rule_fills_what_passes_the_threshold(block_len):
    """Logits scaled (the head times 8) until some confidences pass 0.6:
    such a step fills more than ``L // T`` positions, and the engine and
    the reference agree on which."""
    env = build(block_len, head_scale=8.0)
    requests = [
        Request(prompt=prompt_of(5 + i, 20 + i), max_new_tokens=2 * block_len,
                confidence_threshold=0.6)
        for i in range(3)
    ]
    eng, outs = serve(env, requests)
    more = 0
    for request, out in zip(requests, outs):
        tokens, fill_steps = reference(env, request)
        assert out.tokens == tokens and out.fill_steps == fill_steps
        static, _ = reference(env, Request(
            prompt=request.prompt, max_new_tokens=request.max_new_tokens
        ))
        more += max(fill_steps) < block_len - 1 or tokens != static
    assert more  # some block took fewer forwards than one a position
    assert eng.metrics.summary()["tokens_per_forward"] > 1.0


def test_a_sampled_request_draws_and_a_greedy_neighbour_does_not_move(env):
    prompt = prompt_of(9, 11)
    greedy = Request(prompt=prompt, max_new_tokens=8)
    sampled = Request(prompt=prompt, max_new_tokens=8,
                      sampling=SamplingParams(temperature=1.5))
    _, (a, b) = serve(env, [greedy, sampled])
    assert a.tokens == reference(env, greedy)[0]
    assert len(b.tokens) == 8 and b.tokens != a.tokens
    assert all(0 <= t < env[0].vocab_size for t in b.tokens)


def test_tokens_that_arrive_together_are_one_gap(env):
    """A block is delivered whole: its tokens share a timestamp and the
    ITL histogram gets one gap a block, not ``L - 1`` gaps of zero."""
    size = env[0].block_len
    request = Request(prompt=prompt_of(size, 12), max_new_tokens=3 * size)
    eng, (out,) = serve(env, [request])
    assert out.token_groups == [0, size, 2 * size]
    assert len(set(out.token_times)) == 3
    gaps = out.inter_token_latencies()
    assert len(gaps) == 2 and all(g > 0 for g in gaps)
    assert eng.metrics._itl.count == 2


def test_block_plan_is_logged_and_traced(env, caplog):
    cfg, model, params, _, _ = env
    tracer = Tracer()
    with caplog.at_level(logging.INFO, logger="tpu_parallel.serving.engine"):
        eng = ServingEngine(model, params, n_slots=2, prefill_buckets=(16,),
                            tracer=tracer)
    plan = eng.block_plan
    assert plan["block_len"] == cfg.block_len
    # a slot feeds its block behind the completed one before it: 2L rows
    assert plan["rows_per_step"] == 2 * 2 * cfg.block_len
    assert plan["commit"] == "rides_next_block_first_step"
    # the even steps of a tick are wide, the odd ones feed the block alone
    assert plan["rows_per_narrow_step"] == 2 * cfg.block_len
    assert plan["wide_steps_per_tick"] == 4
    assert eng.moe_plan["decode_narrow"]["tokens"] == 2 * cfg.block_len
    assert plan["steps_per_tick"] == 8 and plan["mask_token_id"] == 255
    assert any("block_plan" in r.getMessage() for r in caplog.records)
    (instant,) = [i for i in tracer.instants if i["name"] == "block_plan"]
    assert instant["attrs"]["rows_per_step"] == plan["rows_per_step"]
    assert instant["attrs"]["commit"] == plan["commit"]
    assert eng.moe_plan["decode"]["tokens"] == plan["rows_per_step"]
    plain = GPTLM(tiny_test(dtype=jnp.float32))
    weights = plain.init(
        {"params": jax.random.PRNGKey(0)}, jnp.zeros((1, 8), jnp.int32),
        train=False,
    )["params"]
    assert ServingEngine(plain, weights, n_slots=2).block_plan is None


def test_scopes_and_expert_rows_come_out_of_the_block_core(env):
    cfg, model, params, _, _ = env
    eng, _ = serve(env, [Request(prompt=prompt_of(9, 13), max_new_tokens=5)])
    text = eng._block_fn.lower(
        params, eng._dev_state, eng._dev_knobs, eng.pool.cache,
        jax.random.PRNGKey(0),
    ).as_text(debug_info=True)
    for scope in ("attn.block", "diffusion.unmask", "moe.router", "moe.experts"):
        assert scope in text, scope
    summary = eng.metrics.summary()
    assert summary["moe_calls"] > 0
    assert 0 < summary["moe_experts_touched_mean"] <= 8


# -- a completed block's commit rides the next block's first forward -----------


def columns(cache, seq_len, lo=None, hi=None):
    """Every leaf of a cache that has positions, cut to columns [lo, hi)."""
    out = []
    for leaf in jax.tree.leaves(cache):
        if seq_len in leaf.shape:
            axis = leaf.shape.index(seq_len)
            out.append(np.asarray(leaf).take(range(lo or 0, hi or seq_len), axis))
    assert len(out) >= 3  # keys, values, stored positions
    return out


def test_a_folded_commit_writes_what_a_forward_of_its_own_writes(env):
    """The completed block's K/V after the step that carried it beside the
    next block equal those a stand-alone clean forward of that block writes
    over the same prefix, and the next block's rows see the same thing."""
    from tpu_parallel.models.generate import block_step, prefill_step

    cfg, model, params, _, _ = env
    size, seq = cfg.block_len, cfg.seq_len
    prompt = jnp.asarray(prompt_of(2 * size, 40))[None]
    clean = jnp.asarray(prompt_of(size, 41))[None]
    masks = jnp.full((1, size), cfg.mask_token_id, jnp.int32)
    half = jnp.where(jnp.arange(size) % 2 == 0, clean, masks)
    start, yes = jnp.array([2 * size]), jnp.array([True])
    with jax.default_matmul_precision("highest"):
        _, cache = prefill_step(
            model, params, prompt, jnp.arange(2 * size)[None]
        )
        # the block in progress leaves half-filled keys in its columns
        _, dirty = block_step(model, params, cache, masks, half, start, yes, ~yes)
        _, alone = block_step(model, params, dirty, masks, clean, start, yes, ~yes)
        after, _ = block_step(
            model, params, alone, clean, masks, start + size, yes, ~yes
        )
        both, folded = block_step(
            model, params, dirty, clean, masks, start + size, yes, yes
        )
    lo, hi = 2 * size, 3 * size
    moved = False
    for was, want, got in zip(
        columns(dirty, seq, lo, hi), columns(alone, seq, lo, hi),
        columns(folded, seq, lo, hi),
    ):
        np.testing.assert_allclose(got, want, atol=2e-5)
        moved |= bool(np.abs(want - was).max() > 1e-3)
    assert moved  # the half-filled keys were another thing
    for want, got in zip(columns(dirty, seq, 0, lo), columns(folded, seq, 0, lo)):
        np.testing.assert_array_equal(got, want)
    np.testing.assert_allclose(both[:, size:], after[:, size:], atol=2e-5)


def watched(env, request, n_slots=2, steps=1):
    """One request through ticks of ``steps`` forwards (one: every step is a
    wide one), with every call of the tick program recorded: the slot state
    and the pool before it, the pool after it, and what the steps were
    (``kinds``)."""
    cfg, model, params, _, _ = env
    calls = []
    with jax.default_matmul_precision("highest"):
        eng = ServingEngine(
            model, params, n_slots=n_slots, prefill_buckets=(16, 32),
            decode_steps_per_tick=steps,
        )
        inner = eng._block_fn

        def spy(params, state, knobs, cache, rng, table):
            before = jax.device_get((state, cache))
            out = inner(params, state, knobs, cache, rng, table)
            calls.append((*before, jax.device_get(out[6]), np.asarray(out[3])))
            return out

        eng._block_fn = spy
        out = eng.add_request(request)
        eng.run()
    return eng, out, calls


@pytest.mark.parametrize("prompt,steps", [
    ("a_block", None), ("a_block_and_a_tail", 2), ("shorter_than_a_block", 2),
    ("a_block", 1),
])
def test_a_final_column_is_written_once(env, prompt, steps):
    """Step by step, a slot writes its block's L columns, and the L before
    them only in the step that carries the completed block: with nothing
    pending (the first block after a prefill, with or without a tail; a
    prompt shorter than a block, which has no prefill; every step inside a
    block) the columns before its block stay bit for bit, as does the rest
    of the pool."""
    cfg = env[0]
    size, seq = cfg.block_len, cfg.seq_len
    length = {"a_block": size, "a_block_and_a_tail": size + 2,
              "shorter_than_a_block": 3}[prompt]
    request = Request(prompt=prompt_of(length, 50), max_new_tokens=2 * size + 3,
                      denoising_steps=steps)
    eng, out, calls = watched(env, request)
    assert out.tokens == reference(env, request)[0]
    slot = next(i for i in range(2) if calls[0][0][6][i])
    carried = 0
    for state, before, after, kinds in calls:
        start, live, pend = int(state[3][slot]), state[6][slot], state[9][slot]
        assert live and kinds[0, slot] == (CARRIED if pend else DENOISED)
        assert not (pend and start < size)
        lo = start - size if pend else start
        for was, now in zip(columns(before, seq), columns(after, seq)):
            axis = was.shape.index(seq)
            rows = [slice(None)] * was.ndim
            rows[axis - 1] = slot
            mine_was, mine_now = was[tuple(rows)], now[tuple(rows)]
            keep = np.ones(seq, bool)
            keep[lo:start + size] = False
            np.testing.assert_array_equal(
                mine_now.compress(keep, axis - 1), mine_was.compress(keep, axis - 1)
            )
            rows[axis - 1] = 1 - slot  # the idle slot: nothing at all
            np.testing.assert_array_equal(now[tuple(rows)], was[tuple(rows)])
        carried += bool(pend)
    first = calls[0][0]
    assert not first[9][slot] and int(first[3][slot]) == length // size * size
    blocks = -(-(length % size + request.max_new_tokens) // size)
    assert carried == blocks - 1  # one commit a completed block that goes on
    summary = eng.metrics.summary()
    assert summary["block_commit_forwards"] == carried
    assert summary["block_commit_waits"] == 0  # a tick of one step is wide


def test_a_request_that_ends_with_its_block_makes_no_commit(env):
    size = env[0].block_len
    request = Request(prompt=prompt_of(size, 60), max_new_tokens=size)
    eng, (out,) = serve(env, [request])
    assert out.tokens == reference(env, request)[0]
    summary = eng.metrics.summary()
    assert summary["block_forwards"] == size and summary["blocks_completed"] == 1
    assert summary["block_commit_forwards"] == 0
    assert summary["block_commit_waits"] == 0
    assert summary["commit_forward_share"] == 0.0


def test_a_one_step_request_carries_a_commit_in_every_step_after_its_first(env):
    size = env[0].block_len
    more = 40 // size  # blocks after the one that holds the prompt's tail
    request = Request(prompt=prompt_of(size + 1, 61), max_new_tokens=more * size,
                      denoising_steps=1)
    eng, out, calls = watched(env, request)
    tokens, fill_steps = reference(env, request)
    assert out.tokens == tokens and out.fill_steps == fill_steps
    slot = next(i for i in range(2) if calls[0][0][6][i])
    assert [int(k[0, slot]) for *_, k in calls] == [DENOISED] + [CARRIED] * more
    summary = eng.metrics.summary()
    assert summary["block_forwards"] == more + 1
    assert summary["block_commit_forwards"] == more
    assert summary["commit_forward_share"] == round(more / (more + 1), 4)


@pytest.mark.parametrize("tail,steps", [
    (0, 2), (2, 2), (0, None), (1, None), (0, 1), (0, 3),
])
def test_a_commit_waits_for_a_wide_step_and_the_slot_stays_on_them(
    env, tail, steps
):
    """The steps of a tick alternate, wide and narrow, and only a wide one
    carries a commit.  A block completed by a wide step leaves its slot
    waiting through the narrow one, which moves the slot: from then on a
    request of an even number of steps a block completes its blocks in
    narrow steps, so it waits once at most.  A request of one step a block
    completes one in every step it is fed and waits in every narrow one; one
    of three steps a block waits once a block."""
    size = env[0].block_len
    request = Request(prompt=prompt_of(size + tail, 65),
                      max_new_tokens=40 - tail, denoising_steps=steps)
    eng, out, calls = watched(env, request, steps=4)
    tokens, fill_steps = reference(env, request)
    assert out.tokens == tokens and out.fill_steps == fill_steps
    slot = next(i for i in range(2) if calls[0][0][6][i])
    kinds = np.concatenate([k[:, slot] for *_, k in calls])
    kinds = kinds[: np.nonzero(kinds)[0][-1] + 1]
    # no commit rides a narrow step
    assert set(kinds[1::2]) <= {DENOISED, WAITED}
    summary = eng.metrics.summary()
    assert summary["block_commit_waits"] == (kinds == WAITED).sum()
    # a slot that waits is live: its step counts, and fills nothing
    assert summary["block_forwards"] == len(kinds)
    blocks = -(-(tail + len(tokens)) // size)
    assert summary["block_commit_forwards"] == blocks - 1
    if steps == 1:
        every = [DENOISED, WAITED] + [CARRIED, WAITED] * len(kinds)
        assert list(kinds) == every[: len(kinds)]
    elif steps == 3:
        # an odd number of steps a block: every block is completed by a wide
        # step and waits through the narrow one, so the request gets the
        # slot-steps it got when a commit had a forward of its own
        assert (kinds == WAITED).sum() == blocks - 1
        assert len(kinds) == (steps + 1) * blocks - 1
    else:
        a_step = size // (steps or size)  # positions a step, the static rule
        first = -(-(size - tail) // a_step)  # forwards of the first block
        assert (kinds == WAITED).sum() == first % 2  # completed by a wide step
        assert (kinds == CARRIED).sum() == blocks - 1


def test_a_lone_request_of_many_blocks_fills_a_position_a_forward(env):
    """One step a position: a block of L took L + 1 forwards (0.8 tokens a
    forward at L 4) and takes L."""
    size = env[0].block_len
    blocks = 40 // size
    request = Request(prompt=prompt_of(size, 62), max_new_tokens=blocks * size,
                      denoising_steps=size)
    eng, (out,) = serve(env, [request], n_slots=1)
    assert out.tokens == reference(env, request)[0]
    summary = eng.metrics.summary()
    assert summary["block_forwards"] == blocks * size
    assert abs(summary["tokens_per_forward"] - 1.0) < 0.02
    assert summary["block_commit_forwards"] == blocks - 1
    assert summary["block_commit_waits"] == 0


def test_a_slot_seated_after_a_cancel_with_a_commit_pending(env):
    """One slot.  The first occupant is cancelled while a completed block
    awaits its final K/V; the newcomer (a prompt shorter than a block: no
    prefill, nothing clears anything) is seated with nothing pending, writes
    nothing before its block and reads nothing stale."""
    size = env[0].block_len
    first = Request(prompt=prompt_of(size, 63), max_new_tokens=3 * size,
                    denoising_steps=1)
    short = Request(prompt=prompt_of(3, 64), max_new_tokens=2 * size + 1,
                    denoising_steps=2)
    cfg, model, params, _, _ = env
    with jax.default_matmul_precision("highest"):
        eng = ServingEngine(
            model, params, n_slots=1, prefill_buckets=(16, 32),
            decode_steps_per_tick=2,
        )
        gone = eng.add_request(first)
        eng.collect(eng.launch())
        assert bool(jax.device_get(eng._dev_state[9])[0])  # a commit pending
        assert eng.cancel(first.request_id)
        out = eng.add_request(short)
        eng.run()
    # a tick of a wide and a narrow step: the first completes a block, the
    # second is no step for a slot whose commit is pending
    assert gone.status == "cancelled" and len(gone.tokens) == size
    assert out.tokens == reference(env, short)[0]
    assert eng.metrics.summary()["prefill_calls"] == 1


# -- the knob: HTTP, the journal, a recovery -----------------------------------


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def sleep(self, seconds):
        self.t += seconds


def daemon_on(env, path, steps=2):
    cfg, model, params, _, _ = env

    def frontend_factory(clock):
        engine = ServingEngine(
            model, params, n_slots=2, prefill_buckets=(16, 32),
            scheduler=SchedulerConfig(max_prefills_per_tick=2),
            decode_steps_per_tick=steps,
        )
        return Frontend(
            [engine], router="least", config=FrontendConfig(restart=None),
            clock=clock, registry=MetricRegistry(),
        )

    return ServingDaemon(
        frontend_factory, str(path), clock=FakeClock(),
        config=DaemonConfig(fsync_batch=4),
    )


def post(port, body):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/v1/submit", data=json.dumps(body).encode(),
        method="POST", headers={"Content-Type": "application/json"},
    )
    try:
        with urllib.request.urlopen(req, timeout=60) as resp:
            return resp.status, json.loads(resp.read())
    except urllib.error.HTTPError as exc:
        return exc.code, json.loads(exc.read())


def test_the_knob_through_http_the_journal_and_a_recovery(tmp_path):
    env = build(4)
    request = build_request({
        "prompt": [1, 2, 3], "denoising_steps": 2,
        "confidence_threshold": 0.25,
    })
    assert request.denoising_steps == 2
    assert request.confidence_threshold == 0.25
    assert build_request({"prompt": [1]}).denoising_steps is None
    with pytest.raises(ValueError, match="denoising_steps"):
        build_request({"prompt": [1], "denoising_steps": 0})

    path = tmp_path / "j.jsonl"
    prompt = prompt_of(10, 30)
    want = Request(prompt=prompt, max_new_tokens=14, denoising_steps=2)
    tokens, _ = reference(env, want)
    with jax.default_matmul_precision("highest"):
        d1 = daemon_on(env, path)
        server = DaemonHTTPServer(d1).start()
        try:
            code, rec = post(server.port, {
                "prompt": prompt, "max_new_tokens": 14, "denoising_steps": 2,
                "dedupe_token": "k-0",
            })
            assert code == 200, rec
            code, bad = post(server.port, {
                "prompt": prompt, "max_new_tokens": 4, "denoising_steps": 9,
            })
            assert code == 400 and bad["finish_reason"] == REJECT_UNSUPPORTED
            assert "denoising_steps=9" in bad["detail"]
        finally:
            server.stop()
        rid = rec["request_id"]
        submits = [r for r in read_journal(str(path))[0]
                   if r["record"] == "submit"]
        assert [s["denoising_steps"] for s in submits] == [2]
        assert submits[0]["confidence_threshold"] == 0.0
        for _ in range(3):  # a tick of 2 forwards completes a block
            d1.tick()
        partial = len(d1.result(rid)["tokens"])
        assert 0 < partial < 14  # the kill lands mid-stream, at a block's end
        d1.journal.abort()

        d2 = daemon_on(env, path)
        assert load_state(str(path)).recoveries == 1
        seated = d2.frontend._pending[0].out.request
        assert seated.denoising_steps == 2  # the journal carried the knob
        for _ in range(40):
            if d2.result(rid)["status"] == "finished":
                break
            d2.tick()
        got = d2.result(rid)
    assert got["status"] == "finished" and len(got["tokens"]) == 14
    assert got["tokens"][:partial] == tokens[:partial]
    # the replay prefills prompt + delivered and denoises on at two steps a
    # block: what an uninterrupted engine gives from that forced prefix
    forced = Request(prompt=prompt + tokens[:partial],
                     max_new_tokens=14 - partial, denoising_steps=2)
    assert got["tokens"][partial:] == reference(env, forced)[0]


# -- refusals ------------------------------------------------------------------


@pytest.mark.parametrize("kwargs,match", [
    (dict(draft_tokens=2), "draft_tokens > 0"),
    (dict(prefill_chunk_tokens=8), "prefill_chunk_tokens"),
    (dict(prefix_cache_size=2), "prefix_cache_size > 0"),
    (dict(kv_block_tokens=4), "kv_block_tokens"),
    (dict(kv_block_tokens=4, kv_radix_cache=True, prefix_cache_size=2),
     "draft_tokens|prefix_cache_size|kv_block_tokens"),
    (dict(kv_host_blocks=4), "kv_radix_cache / kv_host_blocks / kv_disk_dir"),
])
def test_engine_refuses_what_a_block_model_cannot_do(kwargs, match):
    cfg, model, params, _, _ = build(4)
    with pytest.raises(NotImplementedError, match=match):
        ServingEngine(model, params, n_slots=2, prefill_buckets=(16,), **kwargs)


def test_engine_refuses_sizes_that_are_not_whole_blocks():
    cfg, model, params, _, _ = build(4)
    with pytest.raises(ValueError, match="multiples of block_len"):
        ServingEngine(model, params, n_slots=2, prefill_buckets=(10,))
    odd = GPTLM(tiny_block_diffusion(seq_len=62))
    with pytest.raises(ValueError, match="whole blocks"):
        ServingEngine(odd, params, n_slots=2, prefill_buckets=(16,))
    maskless = GPTLM(tiny_block_diffusion(mask_token_id=None))
    with pytest.raises(ValueError, match="mask_token_id"):
        ServingEngine(maskless, params, n_slots=2, prefill_buckets=(16,))
    eng = ServingEngine(model, params, n_slots=2, prefill_buckets=(16,))
    assert eng.export_prefix("nobody") is None  # K/V export: the paged pool's


@pytest.mark.parametrize("config,match", [
    (dict(attn_window=8), "window"),
    (dict(bidirectional=True), "bidirectional"),
])
def test_config_refuses_the_block_rule_beside_another(config, match):
    with pytest.raises(ValueError, match=match):
        tiny_test(block_len=4, **config)
    with pytest.raises(ValueError, match="recurrent"):
        tiny_hybrid_ssm(block_len=4)


@pytest.mark.parametrize("fields,detail", [
    (dict(denoising_steps=5), "outside 1..block_len=4"),
    (dict(sampling=SamplingParams(temperature=1.0, top_k=5)), "top_k / top_p"),
    (dict(sampling=SamplingParams(temperature=1.0, top_p=0.9)), "top_k / top_p"),
    (dict(draft_tokens=2), "draft_tokens"),
])
def test_submission_refuses_typed_on_a_block_model(fields, detail):
    cfg, model, params, _, _ = build(4)
    eng = ServingEngine(model, params, n_slots=2, prefill_buckets=(16,))
    out = eng.add_request(Request(prompt=[1, 2, 3], **fields))
    assert out.status == REJECTED and out.finish_reason == REJECT_UNSUPPORTED
    assert detail in out.detail
    front = Frontend([eng], config=FrontendConfig(restart=None))
    seen = front.submit(Request(prompt=[1, 2, 3], **fields))
    assert seen.status == REJECTED and seen.finish_reason == REJECT_UNSUPPORTED


@pytest.mark.parametrize("fields", [
    dict(denoising_steps=2), dict(confidence_threshold=0.5),
])
def test_any_other_model_refuses_the_denoising_knobs(fields):
    model = GPTLM(tiny_test(dtype=jnp.float32))
    params = model.init(
        {"params": jax.random.PRNGKey(0)}, jnp.zeros((1, 8), jnp.int32),
        train=False,
    )["params"]
    eng = ServingEngine(model, params, n_slots=2)
    out = eng.add_request(Request(prompt=[1, 2, 3], **fields))
    assert out.status == REJECTED and out.finish_reason == REJECT_UNSUPPORTED
    assert "does not generate by diffusion" in out.detail
    with pytest.raises(ValueError):
        Request(prompt=[1], confidence_threshold=1.0)


# -- today's programs -----------------------------------------------------------

# sha256 of ``Lowered.as_text()`` (no source locations) of the serving
# programs of three toy models that stand for cells 2, 3 and 4 and of one
# train step's forward and backward, taken on the commit BEFORE the block
# fields existed (PR 33, 8df3bbc), and of the block-diffusion toy that stands
# for cell 5, taken on the commit before the one-sublayer and latent-expert
# fields existed (PR 44, 868692f): with the new fields at their defaults
# every one of them lowers to the same text.
GOLDEN = json.load(open(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "golden_lowered_programs.json"
)))


def lowered_programs():
    """``{name: sha256}`` of the toy programs (``scripts/lowered_programs.py``
    does the same for the cells' own sizes, for a described chip)."""
    out = {}

    def note(name, lowered):
        out[name] = hashlib.sha256(lowered.as_text().encode()).hexdigest()

    families = {
        "gpt": tiny_test(dtype=jnp.float32, remat=False),
        "experts": tiny_parallel_experts(),
        "hybrid": tiny_hybrid_ssm(),
    }
    for family, cfg in families.items():
        model = GPTLM(cfg)
        params = model.init(
            {"params": jax.random.PRNGKey(0)}, jnp.zeros((1, 8), jnp.int32),
            train=False,
        )["params"]
        chunk = 8 if family == "gpt" else None
        eng = ServingEngine(
            model, params, n_slots=2, prefill_buckets=(16,),
            prefill_chunk_tokens=chunk,
        )
        eng._upload_slot_state()
        state, knobs, key = eng._dev_state, eng._dev_knobs, jax.random.PRNGKey(0)
        note(f"{family}.fused", eng._fused_fn.lower(
            params, state, knobs, eng.pool.cache, key
        ))
        ints = lambda *s: jnp.zeros(s, jnp.int32)
        if chunk:
            ops = (ints(2, chunk), ints(2), ints(2), jnp.zeros(2, bool), ints(2))
            note(f"{family}.unified", eng._unified_fn.lower(
                params, state, knobs, ops, eng.pool.cache, key
            ))
        prefill = eng._prefill_fn.lower(
            params, ints(2, 16), ints(2, 16), ints(2), key
        )
        note(f"{family}.prefill", prefill)
        fresh = jax.eval_shape(
            lambda p: engine_mod._prefill_core(
                model, p, ints(2, 16), ints(2, 16), ints(2), key
            )[1], params,
        )
        note(f"{family}.extend", eng._extend_fn.lower(
            params, ints(2, 16), ints(2, 16), ints(2), ints(2), fresh, key
        ))
    # the block-diffusion toy's own two programs (added by the PR after the
    # one that brought them: hashes taken on ITS parent, 868692f)
    cfg = tiny_block_diffusion()
    model = GPTLM(cfg)
    params = model.init(
        {"params": jax.random.PRNGKey(0)}, jnp.zeros((1, 8), jnp.int32),
        train=False,
    )["params"]
    ints = lambda *s: jnp.zeros(s, jnp.int32)
    flags = lambda *s: jnp.zeros(s, bool)
    floats = lambda *s: jnp.zeros(s, jnp.float32)
    block_prefill, block_tick = engine_mod._block_engine_fns(model, 8)
    note("blockgen.prefill", block_prefill.lower(params, ints(1, 16), ints(1, 16)))
    pool = jax.eval_shape(
        lambda p: engine_mod._block_prefill_core(
            model, p, ints(2, 16), ints(2, 16)
        )[0], params,
    )
    size = cfg.block_len
    state = (ints(2, size), flags(2, size), ints(2, size), ints(2), ints(2),
             ints(2), flags(2), ints(2), ints(2, size), flags(2))
    knobs = (ints(2), floats(2), ints(2), floats(2))
    note("blockgen.tick", block_tick.lower(
        params, state, knobs, pool, jax.random.PRNGKey(0), None
    ))
    cfg = tiny_test(dtype=jnp.float32)
    model = GPTLM(cfg)
    params = jax.eval_shape(lambda: model.init(
        {"params": jax.random.PRNGKey(0)}, jnp.zeros((1, 8), jnp.int32),
        train=False,
    ))["params"]
    loss = lambda p, t: jnp.sum(
        model.apply({"params": p}, t, train=False).astype(jnp.float32)
    )
    note("gpt.train_grad", jax.jit(jax.grad(loss)).lower(
        params, jnp.zeros((2, 16), jnp.int32)
    ))
    return out


def test_todays_programs_lower_to_the_text_they_had():
    got = lowered_programs()
    assert set(got) == set(GOLDEN)
    moved = [name for name in GOLDEN if got[name] != GOLDEN[name]]
    assert not moved, moved


if __name__ == "__main__":  # python tests/test_block_diffusion.py > golden
    print(json.dumps(lowered_programs(), indent=1, sort_keys=True))
