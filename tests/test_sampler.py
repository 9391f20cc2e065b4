"""The per-slot sampler's contract (``engine.sample_tokens``): it computes
only what a row whose token will be read asks for (the argmax alone where
every such row is greedy; a sort only where such a row asks for that
filter) and returns, bit for bit, what the unconditional arithmetic kept
below as the plain reference returns."""

import logging
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_parallel.models import GPTLM, tiny_test
from tpu_parallel.obs import Tracer
from tpu_parallel.serving import (
    Request,
    SamplingParams,
    SchedulerConfig,
    ServingEngine,
    sample_tokens,
)
from tpu_parallel.serving import engine as engine_mod
from tpu_parallel.serving.engine import NON_FINITE_TOKEN

ROWS, VOCAB = 6, 64


def reference_sample_tokens(logits, rng, temperature, top_k, top_p):
    """The sampler as it was before it chose its work: both filters and
    the draw on every row of every call, a greedy row's share thrown away
    by the last ``where``.  Kept as the plain reference."""
    lf = logits.astype(jnp.float32)
    greedy = jnp.argmax(lf, axis=-1).astype(jnp.int32)
    t = jnp.where(temperature > 0.0, temperature, 1.0)[:, None]
    x = lf / t
    vocab = x.shape[-1]
    k = jnp.clip(top_k.astype(jnp.int32), 0, vocab)
    asc = jnp.sort(x, axis=-1)
    kth = jnp.take_along_axis(
        asc, jnp.clip(vocab - k, 0, vocab - 1)[:, None], axis=-1
    )
    x = jnp.where((k > 0)[:, None] & (x < kth), -jnp.inf, x)
    desc = jnp.sort(x, axis=-1)[:, ::-1]
    probs = jax.nn.softmax(desc, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    keep = cum - probs < top_p[:, None]
    cutoff = jnp.min(jnp.where(keep, desc, jnp.inf), axis=-1, keepdims=True)
    use_p = ((top_p > 0.0) & (top_p < 1.0))[:, None]
    x = jnp.where(use_p & (x < cutoff), -jnp.inf, x)
    sampled = jax.random.categorical(rng, x, axis=-1).astype(jnp.int32)
    out = jnp.where(temperature > 0.0, sampled, greedy)
    finite = jnp.isfinite(lf).all(axis=-1)
    return jnp.where(finite, out, jnp.int32(NON_FINITE_TOKEN))


# (temperature, top_k, top_p) a row
GREEDY = (0.0, 0, 0.0)
TEMPERATURE = (0.9, 0, 1.0)
TOP_K = (1.3, 5, 0.0)
TOP_P = (0.7, 0, 0.8)
BOTH = (1.1, 7, 0.6)
MIXES = {
    "all_greedy": [GREEDY] * ROWS,
    # a greedy row that carries filter knobs asks for nothing
    "greedy_with_filter_knobs": [(0.0, 4, 0.5)] * ROWS,
    "temperature_alone": [GREEDY, TEMPERATURE, GREEDY, TEMPERATURE,
                          TEMPERATURE, GREEDY],
    "top_k_alone": [TOP_K, GREEDY, TOP_K, GREEDY, GREEDY, TOP_K],
    "top_p_alone": [GREEDY, GREEDY, TOP_P, TOP_P, GREEDY, TOP_P],
    "both": [BOTH, GREEDY, GREEDY, BOTH, GREEDY, BOTH],
    "all_five": [GREEDY, TEMPERATURE, TOP_K, TOP_P, BOTH, GREEDY],
}


def _knobs(mix):
    temperature, top_k, top_p = zip(*MIXES[mix])
    return (jnp.asarray(temperature, jnp.float32),
            jnp.asarray(top_k, jnp.int32), jnp.asarray(top_p, jnp.float32))


def _mask(kind, temperature):
    """The rows whose token the caller reads."""
    if kind == "all_rows":
        return None
    sampled = np.asarray(temperature) > 0.0
    if kind == "some_rows":
        # every other row, whatever it asks for
        return np.arange(ROWS) % 2 == 1
    # every sampled row is masked out (the stale knobs of a slot whose
    # request left); an all-greedy mix loses its first row
    return ~sampled if sampled.any() else np.arange(ROWS) > 0


@pytest.mark.parametrize("non_finite", [False, True],
                         ids=["finite", "non_finite_row"])
@pytest.mark.parametrize("mask", ["all_rows", "some_rows", "sampled_rows_out"])
@pytest.mark.parametrize("mix", sorted(MIXES))
def test_sampler_matches_unconditional_reference(mix, mask, non_finite):
    """Every row that is read gets the reference's token under the same
    ``rng``: a greedy row the argmax, a sampled row the same draw from the
    same filtered logits whoever shares its batch, a row of non-finite
    logits the sentinel."""
    temperature, top_k, top_p = _knobs(mix)
    rows = _mask(mask, temperature)
    for seed in range(3):
        key = jax.random.PRNGKey(100 + seed)
        logits = 3.0 * jax.random.normal(
            jax.random.fold_in(key, 1), (ROWS, VOCAB), jnp.float32
        )
        if non_finite:
            logits = logits.at[3, 7].set(jnp.nan).at[4, 0].set(jnp.inf)
        want = np.asarray(jax.jit(reference_sample_tokens)(
            logits, key, temperature, top_k, top_p
        ))
        got = np.asarray(jax.jit(sample_tokens)(
            logits, key, temperature, top_k, top_p,
            None if rows is None else jnp.asarray(rows),
        ))
        read = np.ones(ROWS, bool) if rows is None else rows
        np.testing.assert_array_equal(got[read], want[read])
        if non_finite:
            assert got[3] == got[4] == NON_FINITE_TOKEN
        # a row nobody reads still holds a token of the vocabulary
        assert ((got >= 0) & (got < VOCAB) | (got == NON_FINITE_TOKEN)).all()


def _prims(jaxpr, inside_cond=False):
    """(primitive name, whether some enclosing equation is a ``cond``) for
    every equation of ``jaxpr``, sub-jaxprs included."""
    for eqn in jaxpr.eqns:
        yield eqn.primitive.name, inside_cond
        nested = inside_cond or eqn.primitive.name == "cond"
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _prims(sub, nested)


DRAW_PRIMS = {"sort", "random_bits", "threefry2x32", "cumsum"}


def test_all_greedy_branch_holds_no_sort_and_no_draw():
    """The jaxpr of the sampler: outside its ``cond`` no sort and no
    random bits, and the branch an all-greedy step takes is empty of
    them."""
    temperature, top_k, top_p = _knobs("all_five")
    jaxpr = jax.make_jaxpr(sample_tokens)(
        jnp.zeros((ROWS, VOCAB)), jax.random.PRNGKey(0), temperature, top_k,
        top_p, jnp.ones(ROWS, bool),
    ).jaxpr
    outside = {name for name, inside in _prims(jaxpr) if not inside}
    assert not outside & DRAW_PRIMS, outside
    assert "argmax" in outside and "is_finite" in outside
    conds = [e for e in jaxpr.eqns if e.primitive.name == "cond"]
    assert len(conds) == 1
    greedy_branch, draw_branch = conds[0].params["branches"]
    assert not {n for n, _ in _prims(greedy_branch.jaxpr)} & DRAW_PRIMS
    drawn = {n for n, _ in _prims(draw_branch.jaxpr)}
    assert {"sort", "random_bits", "cond"} <= drawn
    # inside the draw each sort sits under a cond of its own
    sorts = [inside for name, inside in _prims(draw_branch.jaxpr)
             if name == "sort"]
    assert len(sorts) == 2 and all(sorts)


def _build(n_rows=3, prompt_len=5):
    cfg = tiny_test(dtype=jnp.float32, remat=False, seq_len=96)
    model = GPTLM(cfg)
    prompt = jax.random.randint(
        jax.random.PRNGKey(0), (n_rows, prompt_len), 1, cfg.vocab_size
    )
    params = model.init(
        {"params": jax.random.PRNGKey(1)}, prompt, train=False
    )["params"]
    return cfg, model, np.asarray(prompt), params


def _computations(hlo):
    """name -> body lines of every computation of an HLO module's text."""
    out, name = {}, None
    for line in hlo.splitlines():
        head = re.match(r"^(ENTRY\s+)?%?([\w.\-]+)\s.*\{$", line)
        if head and not line.startswith(" "):
            name = "ENTRY" if head.group(1) else head.group(2)
            out[name] = []
        elif line.startswith("}"):
            name = None
        elif name is not None:
            out[name].append(line)
    return out


CALLS = re.compile(r"\b(?:to_apply|body|condition|calls)=%?([\w.\-]+)")
BRANCHES = re.compile(
    r"\b(?:branch_computations=\{([^}]*)\}"
    r"|(?:true|false)_computation=%?([\w.\-]+))"
)


@pytest.mark.parametrize("program", ["fused", "unified"])
def test_lowered_tick_holds_its_sorts_inside_a_conditional(program):
    """The tick as lowered: a ``sort`` is reached from the entry only
    through a ``conditional``'s branch, on the decode steps of the fused
    tick and on the unified tick's chunk phase alike."""
    cfg, model, _, params = _build()
    n = 4
    eng = ServingEngine(
        model, params, n_slots=n, prefill_buckets=(8, 16),
        prefill_chunk_tokens=6 if program == "unified" else None,
    )
    state = (
        jnp.zeros(n, jnp.int32), jnp.zeros(n, jnp.int32),
        jnp.zeros(n, jnp.int32), jnp.ones(n, bool), jnp.ones(n, jnp.int32),
    )
    knobs = (
        jnp.full(n, -1, jnp.int32), jnp.zeros(n, jnp.float32),
        jnp.zeros(n, jnp.int32), jnp.zeros(n, jnp.float32),
    )
    rng = jax.random.PRNGKey(0)
    if program == "fused":
        fn = engine_mod._fused_engine_fn(model, 8)
        lowered = fn.lower(params, state, knobs, eng.pool.cache, rng)
    else:
        fn = engine_mod._unified_engine_fn(model, 8, 6)
        chunk_ops = (
            jnp.zeros((n, 6), jnp.int32), jnp.zeros(n, jnp.int32),
            jnp.zeros(n, jnp.int32), jnp.zeros(n, bool),
            jnp.zeros(n, jnp.int32),
        )
        lowered = fn.lower(
            params, state, knobs, chunk_ops, eng.pool.cache, rng
        )
    comps = _computations(lowered.as_text(dialect="hlo"))
    has_sort = {
        name for name, lines in comps.items()
        if any(re.search(r"\bsort\(", line) for line in lines)
    }
    assert has_sort, "the program holds no sort at all"
    # what the entry reaches without entering a conditional's branch
    reached, todo, conditionals = set(), ["ENTRY"], 0
    while todo:
        name = todo.pop()
        if name in reached:
            continue
        reached.add(name)
        for line in comps[name]:
            todo.extend(CALLS.findall(line))
            conditionals += bool(BRANCHES.search(line))
    assert conditionals >= 1
    assert not reached & has_sort, sorted(reached & has_sort)


def _req(prompt_row, n_new, **kwargs):
    return Request(
        prompt=[int(t) for t in prompt_row], max_new_tokens=n_new, **kwargs
    )


@pytest.mark.parametrize("steps", [1, 8], ids=["per_step", "fused"])
def test_finished_sampled_request_leaves_skip_share_at_one(steps):
    """A slot's knobs outlive its request: once the sampled request has
    left, the ticks that follow count as skipped again, through
    ``step()``; while it ran they did not."""
    cfg, model, prompt, params = _build(n_rows=3)
    eng = ServingEngine(
        model, params, n_slots=2, decode_steps_per_tick=steps,
        scheduler=SchedulerConfig(max_prefills_per_tick=2),
        rng=jax.random.PRNGKey(3),
    )
    assert eng.metrics.summary()["sampler_skip_share"] is None
    hot = eng.add_request(_req(
        prompt[0], 4, sampling=SamplingParams(temperature=2.0, top_p=0.9)
    ))
    long_greedy = eng.add_request(_req(prompt[1], 60))
    while hot.finish_reason is None:
        eng.step()
    with_hot = eng.metrics.summary()
    assert with_hot["sampler_draw_ticks"] >= 1
    assert with_hot["sampler_skip_share"] < 1.0
    # the tick in flight was launched with the request in its slot
    eng.step()
    eng.reset_metrics()
    assert eng._temp.max() > 0.0  # the mirror still holds the old knob
    for _ in range(3):
        eng.step()
    after = eng.metrics.summary()
    assert after["busy_ticks"] == 3
    assert after["sampler_draw_ticks"] == 0
    assert after["sampler_skip_share"] == 1.0
    eng.run()
    assert len(hot.tokens) == 4 and len(long_greedy.tokens) == 60


def test_greedy_stream_does_not_see_its_sampled_neighbour():
    """A greedy request's tokens beside a sampled one (both filters), and
    beside the stale knobs it leaves behind, are the tokens it gets alone."""
    cfg, model, prompt, params = _build(n_rows=2)

    def serve(with_neighbour):
        eng = ServingEngine(
            model, params, n_slots=2, rng=jax.random.PRNGKey(5),
            scheduler=SchedulerConfig(max_prefills_per_tick=2),
        )
        if with_neighbour:
            eng.add_request(_req(prompt[1], 3, sampling=SamplingParams(
                temperature=1.5, top_k=9, top_p=0.7
            )))
        out = eng.add_request(_req(prompt[0], 24))
        eng.run()
        return out.tokens

    assert serve(True) == serve(False)


def test_sampler_plan_is_logged_and_traced(caplog):
    cfg, model, _, params = _build()
    tracer = Tracer()
    with caplog.at_level(logging.INFO, logger="tpu_parallel.serving.engine"):
        eng = ServingEngine(model, params, n_slots=4, tracer=tracer)
    plan = eng.sampler_plan
    assert plan["rows"] == 4 and plan["vocab"] == cfg.vocab_size
    assert plan["steps_per_tick"] == 8
    assert plan["chosen"] == "on_device_per_step"
    assert any("sampler_plan" in r.getMessage() for r in caplog.records)
    assert any(e["name"] == "sampler_plan" for e in tracer.instants)
