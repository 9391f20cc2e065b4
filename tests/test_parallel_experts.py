"""Parallel blocks with window and full attention mixed by layer, dropless
sigmoid-routed experts beside shared ones, a chip's share of the experts: the
program against the plain reference (``benchmarks/reference/
cohere2_moe_ref.py``) on LOGITS, at a small size, seeded random weights,
float32.

Two periods (window, window, window, full), window 8 in contexts of 24-40, 16
experts top-4 with 2 shared, 4 held.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmarks"))

from lib import cohere2_weights, weights  # noqa: E402
from reference import cohere2_moe_ref as ref  # noqa: E402

from tpu_parallel.models import generate as gen  # noqa: E402
from tpu_parallel.models.gpt import (  # noqa: E402
    GPTLM,
    lm_logits,
    tiny_parallel_experts,
    tiny_test,
)
from tpu_parallel.models.layers import (  # noqa: E402
    Attention,
    ExpertsSpec,
    LayerSpec,
)
from tpu_parallel.models.moe import MOE_STATS, RoutedExperts, moe_plan  # noqa: E402
from tpu_parallel.serving import ServingEngine  # noqa: E402
from tpu_parallel.serving.request import Request  # noqa: E402

SEED = 2 ** 31 + 77
TOL = 5e-5  # float32 against float32: summation order only


def experts_spec(held=(4, 4), **kw):
    return ExpertsSpec(**{**dict(
        n_experts=16, top_k=4, width=48, score="sigmoid", shared=2, held=held,
    ), **kw})


def build(held=(4, 4), **overrides):
    cfg = tiny_parallel_experts(experts=experts_spec(held), **overrides)
    model = GPTLM(cfg)
    abstract = jax.eval_shape(
        lambda: model.init(
            {"params": jax.random.PRNGKey(0)}, jnp.zeros((1, 8), jnp.int32),
            train=False,
        )
    )["params"]
    return cfg, model, abstract, weights.make_params(SEED, abstract)


def shape_of(cfg):
    es = cfg.layer_specs[0].experts
    return {
        "layer_types": [
            "sliding_attention" if s.attn == "window" else "full_attention"
            for s in cfg.layer_specs
        ] * (cfg.n_layers // len(cfg.layer_specs)),
        "sliding_window": cfg.layer_specs[0].window,
        "rope_theta": cfg.rope_theta, "num_experts_per_tok": es.top_k,
        "held": es.held_range, "eps": cfg.norm_eps,
        "logit_scale": cfg.logit_scale,
    }


def reference_logits(cfg, abstract, tokens, rows=None):
    n_kv = cfg.n_kv_heads or cfg.n_heads
    rw = cohere2_weights.to_reference(SEED, abstract, cfg.n_heads, n_kv)
    return ref.forward(rw, jnp.asarray(tokens), shape_of(cfg), rows=rows)


def tokens_of(n, seed=0):
    return [int(t) for t in np.random.RandomState(seed).randint(1, 250, n)]


def grouped_path(cfg, tokens):
    """What ``moe_plan`` says runs the grouped matmuls of ``tokens`` rows."""
    es = cfg.layer_specs[0].experts
    return moe_plan(es, tokens, cfg.d_model, cfg.dtype)["grouped"]


@pytest.mark.parametrize("held,tokens,path", [
    ((4, 4), 36, "streamed"), ((0, 16), 36, "streamed"),
    ((12, 4), 36, "streamed"), ((0, 1), 36, "streamed"),
    ((4, 4), 136, "ragged_dot"), ((12, 4), 136, "ragged_dot"),
])
def test_full_forward_matches_reference(held, tokens, path):
    """On both sides of the rule that picks the grouped matmuls: 36 tokens
    (the buffer's rows an expert fit one window of the streamed kernel) and
    136 on 4 held experts (they do not: ``lax.ragged_dot``)."""
    cfg, model, abstract, params = build(held, seq_len=max(40, tokens))
    assert grouped_path(cfg, tokens) == path
    toks = tokens_of(tokens)
    got = model.apply({"params": params}, jnp.asarray([toks]), train=False)[0]
    want = reference_logits(cfg, abstract, toks)
    assert float(jnp.abs(got - want).max()) < TOL


def test_scanned_periods_match_unrolled():
    """The same layers scanned by whole periods (``scan_group`` 4)."""
    cfg, model, abstract, params = build()
    scanned = GPTLM(tiny_parallel_experts(
        experts=experts_spec(), scan_layers=True, scan_group=4,
    ))
    toks = jnp.asarray([tokens_of(30)])
    init = scanned.init({"params": jax.random.PRNGKey(0)}, toks, train=False)
    blocks = params["blocks"]
    stacked = {
        f"block{j}": jax.tree.map(
            lambda *xs: jnp.stack(xs), blocks[f"layer_{j}"], blocks[f"layer_{4 + j}"]
        ) for j in range(4)
    }
    tree = dict(params, blocks={"layers": stacked})
    assert jax.tree.structure(tree) == jax.tree.structure(init["params"])
    got = scanned.apply({"params": tree}, toks, train=False)
    want = model.apply({"params": params}, toks, train=False)
    assert float(jnp.abs(got - want).max()) < TOL
    with pytest.raises(ValueError, match="whole periods"):
        GPTLM(tiny_parallel_experts(scan_layers=True)).init(
            {"params": jax.random.PRNGKey(0)}, toks, train=False
        )


@pytest.mark.parametrize("mode", ["cache", "flash", "chunks"])
def test_prefill_then_decode_logits_match_reference(mode):
    """Prefill (read back from the cache, through the flash kernels, or in
    chunks written at a slot's depth), then one-token decode steps with
    slot-indexed writes: every position's logits against the reference's
    full forward."""
    cfg, model, abstract, params = build(prefill_flash=mode == "flash")
    toks = tokens_of(38)
    n_prompt, width = 21, 24
    prompt = jnp.asarray([toks[:n_prompt] + [0] * (width - n_prompt)])
    positions, _ = gen.padded_prefill_inputs([n_prompt], width)
    if mode == "chunks":
        _, cache = gen.prefill_step(
            model, params, prompt[:, :8], positions[:, :8]
        )
        hidden = [None]
        for lo in (8, 16):
            h, cache = gen.prefill_extend_step(
                model, params, cache, prompt[:, lo:lo + 8],
                positions[:, lo:lo + 8], jnp.asarray([lo]),
            )
            hidden.append(h)
        last = hidden[-1][:, n_prompt - 1 - 16]
    else:
        h, cache = gen.prefill_step(model, params, prompt, positions)
        last = h[:, n_prompt - 1]
    got = [lm_logits(cfg, params, last[:, None])[0, 0]]
    for i in range(n_prompt, len(toks) - 1):
        h, cache = gen.decode_step(
            model, params, cache, jnp.asarray([toks[i]]), jnp.asarray([i]),
            write_index=jnp.asarray([i]),
        )
        got.append(lm_logits(cfg, params, h)[0, 0])
    want = reference_logits(
        cfg, abstract, toks, rows=slice(n_prompt - 1, len(toks) - 1)
    )
    assert float(jnp.abs(jnp.stack(got) - want).max()) < TOL


ENGINES = {
    "fixed_slot": dict(prefill_buckets=(16, 32)),
    "fixed_slot_flash_prefill": dict(prefill_buckets=(16, 32)),
    "chunked_unified": dict(prefill_buckets=(16, 32), prefill_chunk_tokens=8),
    "chunked_per_phase": dict(
        prefill_buckets=(16, 32), prefill_chunk_tokens=8, unified_tick=False,
    ),
    "per_step": dict(prefill_buckets=(16, 32), decode_steps_per_tick=1),
    "paged": dict(prefill_buckets=(16, 32), kv_block_tokens=8),
}


@pytest.mark.parametrize("name", sorted(ENGINES))
def test_engine_serves_the_references_best_token(name):
    """Five requests over four slots (queueing, slot reuse, contexts of
    17-39): every served token is the reference's best at its position, by
    the reference's own logits, and the expert counters add up."""
    cfg, model, abstract, params = build(prefill_flash="flash" in name)
    engine = ServingEngine(model, params, n_slots=4, **ENGINES[name])
    outs = [
        engine.add_request(Request(
            request_id=f"r{i}", prompt=tokens_of(n, seed=i), max_new_tokens=12,
        )) for i, n in enumerate((5, 13, 22, 27, 9))
    ]
    engine.run()
    for out in outs:
        prompt = list(out.request.prompt)
        seq = prompt + list(out.tokens)
        logits = reference_logits(
            cfg, abstract, seq, rows=slice(len(prompt) - 1, len(seq) - 1)
        )
        picked = jnp.take_along_axis(
            logits, jnp.asarray(out.tokens)[:, None], axis=-1
        )[:, 0]
        assert len(out.tokens) == 12
        assert float((logits.max(-1) - picked).max()) < TOL, out.request.request_id
    summary = engine.metrics.summary()
    es = cfg.layer_specs[0].experts
    assert summary["moe_calls"] > 0
    assert summary["moe_assignments_total"] % es.top_k == 0
    assert 0 < summary["moe_assignments_held"] < summary["moe_assignments_total"]
    assert 0 < summary["moe_experts_touched_mean"] <= es.held_range[1]
    assert summary["moe_rows_per_expert_max_over_mean"] >= 1.0
    assert engine.moe_plan["decode"]["buffer_rows"] == 4 * es.top_k


@pytest.mark.parametrize("flash", [True, False])
def test_engine_says_what_the_flash_kernels_do_in_each_prefill_shape(flash):
    """``prefill_attn_plan``: for each bucket (the slot's length is the last
    rung) and each window the layers have, the forward's tile and variant and
    the tiles of a row's walk, from ``flash_plan`` at the layers' head width,
    group and rule; logged and on the tracer once at build, ``attn_plan`` as
    it was; None where the prefill does not attend through the kernels."""
    from tpu_parallel.obs import Tracer
    from tpu_parallel.ops.flash_attention import flash_plan

    cfg, model, _, params = build(prefill_flash=flash)
    tracer = Tracer()
    engine = ServingEngine(
        model, params, n_slots=2, prefill_buckets=(16, 32), tracer=tracer
    )
    instants = {e["name"]: e["attrs"] for e in tracer.instants}
    assert engine.attn_plan == {"decode": {"path": "xla"}}
    if not flash:
        assert engine.prefill_attn_plan is None
        assert "prefill_attn_plan" not in instants
        return
    plan = engine.prefill_attn_plan
    assert list(plan) == [
        f"prefill_{b}{w}" for b in (16, 32, 40) for w in ("", "_window8")
    ]
    for bucket in (16, 32, 40):
        for window in (0, 8):
            want = flash_plan(bucket, 16, 4, cfg.dtype, window=window)["fwd"]
            name = f"prefill_{bucket}" + (f"_window{window}" if window else "")
            assert plan[name] == {
                "tile": want["block_q"], "variant": "resident",
                "tiles_computed": want["tiles_computed"],
                "tiles_masked": want["tiles_masked"],
            }
    attrs = instants["prefill_attn_plan"]
    assert (attrs["layers"], attrs["of_layers"]) == (8, 8)
    assert attrs["prefill_40_window8_tile"] == 40
    assert attrs["prefill_32_variant"] == "resident"


def test_engine_refuses_a_model_that_drops():
    cfg = tiny_test(moe_experts=4, moe_top_k=2)
    assert cfg.drops_tokens
    with pytest.raises(NotImplementedError, match="capacity-routed"):
        ServingEngine(GPTLM(cfg), {}, n_slots=2)


def _layer(spec, d=32, seed=3):
    cfg = tiny_parallel_experts()
    module = RoutedExperts(cfg, spec)
    x = jax.random.normal(jax.random.PRNGKey(seed), (1, 24, d), jnp.float32)
    params = weights.make_params(SEED, jax.eval_shape(
        lambda: module.init({"params": jax.random.PRNGKey(0)}, x)
    )["params"])
    return cfg, module, params, x


def _share(params, first, count):
    cut = jax.tree.map(lambda w: w[first:first + count], params["experts"])
    return dict(params, experts=cut)


def _ref_layer(params, shape_held, top_k=4):
    f = lambda x: jnp.asarray(x, jnp.float32)
    e = params["experts"]["sharded"]
    return {
        "router": f(params["router"]["kernel"]),
        "w_gate": f(e["gate"]["kernel"]), "w_up": f(e["up"]["kernel"]),
        "w_down": f(e["down"]["kernel"]),
        "s_gate": f(params["shared_gate"]["kernel"]),
        "s_up": f(params["shared_up"]["kernel"]),
        "s_down": f(params["shared_down"]["kernel"]),
    }, {"num_experts_per_tok": top_k, "held": shape_held}


def test_the_four_shares_add_up_to_the_uncut_layer():
    """Guide section 4: the parts that all four shares give, with what every
    chip computes alike (the shared experts) counted once, add up to what the
    uncut layer gives; each share agrees with the reference given that share."""
    cfg, whole, params, x = _layer(experts_spec(None))
    y_whole = whole.apply({"params": params}, x)
    s_gate, s_up, s_down = (
        params[f"shared_{n}"]["kernel"] for n in ("gate", "up", "down")
    )
    mid = jax.nn.silu(jnp.einsum("btd,edw->btew", x, s_gate)) * (
        jnp.einsum("btd,edw->btew", x, s_up)
    )
    shared = jnp.einsum("btew,ewd->btd", mid, s_down) / 2
    assert float(jnp.abs(shared).mean()) > 0.05  # the experts carry weight
    total = -3 * shared
    for first in (0, 4, 8, 12):
        part = RoutedExperts(cfg, experts_spec((first, 4))).apply(
            {"params": _share(params, first, 4)}, x
        )
        lw, shape = _ref_layer(_share(params, first, 4), (first, 4))
        with jax.default_matmul_precision("highest"):
            want, _ = ref.experts(x[0], lw, shape, "float32", None)
        assert float(jnp.abs(part[0] - want).max()) < TOL
        total = total + part
    assert float(jnp.abs(total - y_whole).max()) < TOL


@pytest.mark.parametrize("tokens,case,path", [
    (24, "natural", "streamed"), (24, "all_on_one_expert", "streamed"),
    (24, "all_on_the_held", "streamed"),
    (128, "all_on_one_expert", "streamed"), (128, "all_on_the_held", "streamed"),
    (136, "all_on_one_expert", "ragged_dot"), (136, "all_on_the_held", "ragged_dot"),
    (640, "natural", "ragged_dot"), (640, "all_on_the_held", "ragged_dot"),
])
def test_no_imbalance_drops_a_token(tokens, case, path):
    """Every token forced onto held experts (one of them, or all four of
    its choices): the worst-case buffer takes every assignment, none is
    dropped, and the layer still is the reference's.  640 tokens is past the
    size at which the small buffer and the worst-case one are two programs.
    Held on both sides of the rule that picks the grouped matmuls: 128 tokens
    is the most the streamed kernel takes on 4 held experts (one expert with
    every row then runs over two of its windows), 136 the first that
    ``lax.ragged_dot`` takes."""
    spec = experts_spec((4, 4), n_experts=32)
    cfg, module, params, _ = _layer(spec)
    assert moe_plan(spec, tokens, 32, jnp.float32)["grouped"] == path
    x = jnp.abs(
        jax.random.normal(jax.random.PRNGKey(5), (1, tokens, 32), jnp.float32)
    ) + 0.1
    router = np.array(params["router"]["kernel"])
    if case == "all_on_one_expert":
        router[:, 5] = 4.0  # every token's first choice: held expert 5
    elif case == "all_on_the_held":
        router[:, 4:8] = 4.0 + 0.25 * np.arange(4)  # all four choices held
    params = dict(params, router={"kernel": jnp.asarray(router)})
    got, stats = module.apply({"params": params}, x, mutable=[MOE_STATS])
    rows = np.asarray(jax.tree.leaves(stats)[0])
    assert rows.sum() == tokens * spec.top_k
    if case == "all_on_one_expert":
        assert rows[1] == tokens
    if case == "all_on_the_held":
        assert rows[:4].tolist() == [tokens] * 4 and rows[4] == 0
    lw, shape = _ref_layer(params, (4, 4))
    with jax.default_matmul_precision("highest"):
        want, _ = ref.experts(x[0], lw, shape, "float32", None)
    assert float(jnp.abs(got[0] - want).max()) < 2e-4


def _attention(spec, decode):
    cfg = tiny_parallel_experts()
    module = Attention(cfg, spec=spec)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 30, 32), jnp.float32)
    params = module.init({"params": jax.random.PRNGKey(2)}, x, train=False)["params"]

    def run(x, positions):
        if not decode:
            return module.apply(
                {"params": params}, x, positions=positions, train=False
            )
        # through the cache: 22 positions prefilled, then one at a time
        out, state = module.apply(
            {"params": params}, x[:, :22], positions=positions[:, :22],
            train=False, decode=True, mutable=["cache"],
        )
        outs = [out]
        for i in range(22, x.shape[1]):
            out, state = module.apply(
                {"params": params, "cache": state["cache"]}, x[:, i:i + 1],
                positions=positions[:, i:i + 1], train=False, decode=True,
                mutable=["cache"], write_index=jnp.asarray([i]),
            )
            outs.append(out)
        return jnp.concatenate(outs, axis=1)

    return x, run


@pytest.mark.parametrize("decode", [False, True], ids=["forward", "cached"])
@pytest.mark.parametrize("kind", ["window", "full"])
def test_a_window_layer_never_reads_past_its_window(kind, decode):
    """Perturb the input at position 5: a window-8 layer's outputs move at
    positions 5..12 and nowhere else; a full layer's at every position
    from 5 on."""
    spec = LayerSpec("window", 8, "rope") if kind == "window" else (
        LayerSpec("full", 0, "none")
    )
    x, run = _attention(spec, decode)
    positions = jnp.arange(30)[None]
    base = run(x, positions)
    moved = run(x.at[0, 5].add(1.0), positions)
    changed = np.asarray(jnp.abs(moved - base).max(axis=-1)[0] > 1e-6)
    want = np.zeros(30, bool)
    want[5:13 if kind == "window" else 30] = True
    assert changed.tolist() == want.tolist()


@pytest.mark.parametrize("decode", [False, True], ids=["forward", "cached"])
@pytest.mark.parametrize("kind", ["window", "full"])
def test_a_full_layer_is_never_rotated(kind, decode):
    """Stretch the positions (0, 1, 2, ... -> 0, 2, 4, ...; the order, and
    so the causal mask, stays): a full layer with no positional encoding
    gives the same output, a rotary window layer another."""
    spec = LayerSpec("window", 64, "rope") if kind == "window" else (
        LayerSpec("full", 0, "none")
    )
    x, run = _attention(spec, decode)
    # the cached path masks by stored positions: keep them inside seq_len 40
    near = run(x, jnp.arange(30)[None])
    far = run(x, (jnp.arange(30) + jnp.arange(30) // 3)[None])
    same = float(jnp.abs(near - far).max()) < 1e-6
    assert same == (kind == "full")


def test_expert_axis_closes_with_the_psum(mesh_data4_model2):
    """Under a bound model axis each rank holds half of the held range and
    the routed sum closes with a psum; router and shared experts are on every
    rank alike and counted once: the same output as the layer on one device."""
    import flax.linen as nn
    from jax.sharding import PartitionSpec as P

    cfg, module, params, x = _layer(experts_spec((4, 4)))
    want = module.apply({"params": params}, x)
    split = dict(params, experts={"sharded": jax.tree.map(
        lambda w: nn.Partitioned(
            w.reshape(2, 2, *w.shape[1:]), names=("model",) + (None,) * w.ndim
        ), params["experts"]["sharded"],
    )})
    got = jax.jit(jax.shard_map(
        lambda x, p: module.apply({"params": p}, x),
        mesh=mesh_data4_model2,
        in_specs=(P("data"), nn.get_partition_spec(split)),
        out_specs=P("data"),
        check_vma=False,
    ))(jnp.tile(x, (4, 1, 1)), split)[:1]
    assert float(jnp.abs(got - want).max()) < TOL
