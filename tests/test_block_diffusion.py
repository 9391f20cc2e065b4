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
    assert plan["rows_per_step"] == 2 * cfg.block_len
    assert plan["steps_per_tick"] == 8 and plan["mask_token_id"] == 255
    assert any("block_plan" in r.getMessage() for r in caplog.records)
    (instant,) = [i for i in tracer.instants if i["name"] == "block_plan"]
    assert instant["attrs"]["rows_per_step"] == plan["rows_per_step"]
    assert eng.moe_plan["decode"]["tokens"] == 2 * cfg.block_len
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
        for _ in range(4):
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
# fields existed (PR 33, 8df3bbc): with the new fields at their defaults
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
