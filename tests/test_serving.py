"""Continuous-batching engine tests: greedy parity with the static path,
slot reuse across staggered arrivals, scheduler policies, per-request
sampling isolation, and the prefill fast path (bucketing / batching /
chunking / prefix reuse — all pinned token-identical to the exact
batch-1-prefill engine)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _spec_drafters import AntiOracleDrafter, OracleDrafter
from _spec_drafters import ref_map as _ref_map

from tpu_parallel.models import GPTLM, tiny_test
from tpu_parallel.models.generate import generate, padded_prefill_inputs
from tpu_parallel.serving import (
    EXPIRED,
    FINISHED,
    REJECTED,
    FIFOScheduler,
    PrefixCache,
    Request,
    RequestOutput,
    SamplingParams,
    SchedulerConfig,
    ServingEngine,
    ServingMetrics,
    default_prefill_buckets,
    percentile,
)


def _build(rng, n_rows=3, prompt_len=5, **overrides):
    cfg = tiny_test(dtype=jnp.float32, remat=False, **overrides)
    model = GPTLM(cfg)
    prompt = jax.random.randint(rng, (n_rows, prompt_len), 1, cfg.vocab_size)
    params = model.init(
        {"params": jax.random.PRNGKey(1)}, prompt, train=False
    )["params"]
    return cfg, model, prompt, params


def _req(prompt_row, n_new, **kwargs):
    return Request(
        prompt=[int(t) for t in np.asarray(prompt_row)],
        max_new_tokens=n_new,
        **kwargs,
    )


@pytest.mark.parametrize("variant", ["gpt", "rope"])
def test_engine_greedy_parity_simultaneous(rng, variant):
    """Acceptance: N simultaneously-arriving greedy requests through the
    engine are token-identical to static generate() on the same prompts —
    learned-pos (GPT-2) and RoPE variants."""
    overrides = dict(
        gpt={}, llama={}, rope=dict(positional="rope", norm="rmsnorm")
    )[variant]
    cfg, model, prompt, params = _build(rng, n_rows=3, **overrides)
    want = np.asarray(generate(model, params, prompt, max_new_tokens=8))
    eng = ServingEngine(
        model, params, n_slots=4,
        scheduler=SchedulerConfig(max_prefills_per_tick=3),
    )
    outs = [eng.add_request(_req(prompt[i], 8)) for i in range(3)]
    eng.run()
    for i, out in enumerate(outs):
        assert out.status == FINISHED and out.finish_reason == "length"
        np.testing.assert_array_equal(
            np.asarray(out.tokens), want[i], err_msg=f"request {i}"
        )


def test_engine_staggered_arrivals_match_reference(rng):
    """Acceptance: requests joining mid-flight into freed slots (pool of 2,
    4 requests of different prompt lengths and budgets, arrivals spread
    over ticks) each match a one-request-at-a-time reference decode."""
    cfg, model, _, params = _build(rng)
    lens, budgets = [3, 5, 4, 6], [6, 4, 8, 5]
    rows = [
        jax.random.randint(
            jax.random.fold_in(rng, i), (1, L), 1, cfg.vocab_size
        )
        for i, L in enumerate(lens)
    ]
    refs = [
        np.asarray(generate(model, params, r, max_new_tokens=n))
        for r, n in zip(rows, budgets)
    ]
    eng = ServingEngine(model, params, n_slots=2)
    outs = [eng.add_request(_req(rows[0][0], budgets[0]))]
    outs.append(eng.add_request(_req(rows[1][0], budgets[1])))
    eng.step(), eng.step()
    outs.append(eng.add_request(_req(rows[2][0], budgets[2])))
    eng.step(), eng.step()
    outs.append(eng.add_request(_req(rows[3][0], budgets[3])))
    eng.run()
    for i, (out, ref) in enumerate(zip(outs, refs)):
        assert out.status == FINISHED, f"request {i}: {out.status}"
        np.testing.assert_array_equal(
            np.asarray(out.tokens), ref[0], err_msg=f"request {i}"
        )
    # four requests through two slots => slots were reused
    assert eng.metrics.finished == 4 and eng.pool.n_free == 2


def test_slot_reuse_after_completion(rng):
    """A single-slot pool serves requests strictly in sequence: the second
    runs only after the first retires and reuses its slot, with outputs
    unpolluted by the slot's previous occupant.  Per-step tick: the
    admitted-but-not-finished checkpoint below needs one-token ticks."""
    cfg, model, prompt, params = _build(rng, n_rows=2)
    refs = [
        np.asarray(generate(model, params, prompt[i : i + 1], max_new_tokens=5))
        for i in range(2)
    ]
    eng = ServingEngine(model, params, n_slots=1, decode_steps_per_tick=1)
    a = eng.add_request(_req(prompt[0], 5))
    b = eng.add_request(_req(prompt[1], 5))
    # first tick admits only request a (one slot)
    eng.step()
    assert a.status == "running" and b.status == "queued"
    eng.run()
    np.testing.assert_array_equal(np.asarray(a.tokens), refs[0][0])
    np.testing.assert_array_equal(np.asarray(b.tokens), refs[1][0])
    assert eng.pool.n_free == 1


def test_eos_retires_before_max_new_tokens(rng):
    """EOS stop: the engine retires the slot at the first EOS (included in
    the output) instead of decoding to the length budget."""
    cfg, model, prompt, params = _build(rng, n_rows=1)
    ref = list(
        np.asarray(generate(model, params, prompt, max_new_tokens=8))[0]
    )
    eos = int(ref[2])
    stop = ref.index(eos)  # first occurrence (<= 2, well before 8)
    eng = ServingEngine(model, params, n_slots=2)
    out = eng.add_request(_req(prompt[0], 8, eos_token_id=eos))
    eng.run()
    assert out.finish_reason == "eos"
    assert out.tokens == ref[: stop + 1]
    assert eng.pool.n_free == 2  # slot returned


def test_admission_control_rejects_when_full(rng):
    """max_queue admission control: submissions beyond the queue bound are
    REJECTED at submit time while the pool is busy."""
    cfg, model, prompt, params = _build(rng, n_rows=3)
    eng = ServingEngine(
        model, params, n_slots=1,
        scheduler=SchedulerConfig(max_queue=1),
    )
    a = eng.add_request(_req(prompt[0], 6))
    eng.step()  # a occupies the only slot; queue is empty again
    b = eng.add_request(_req(prompt[1], 6))
    c = eng.add_request(_req(prompt[2], 6))
    assert b.status == "queued"
    assert c.status == REJECTED and c.finish_reason == "queue_full"
    eng.run()
    assert a.status == FINISHED and b.status == FINISHED
    assert c.tokens == []


def test_queue_timeout_expires_requests(rng):
    """max_wait: a queued request whose wait exceeds the budget EXPIRES
    instead of serving a long-abandoned client (deterministic via an
    injected clock)."""
    cfg, model, prompt, params = _build(rng, n_rows=2)
    t = [0.0]
    eng = ServingEngine(
        model, params, n_slots=1,
        scheduler=SchedulerConfig(max_wait=10.0),
        clock=lambda: t[0],
    )
    seen = []
    a = eng.add_request(_req(prompt[0], 6))
    b = eng.add_request(
        _req(prompt[1], 6, on_token=lambda ev: seen.append(ev))
    )
    eng.step()  # a takes the slot, b queued at t=0
    t[0] = 11.0
    events = eng.run()
    assert a.status == FINISHED
    assert b.status == EXPIRED and b.tokens == []
    assert b.finish_reason == "max_wait"
    # expiry is asynchronous: the stream gets a tokenless terminal event
    assert len(seen) == 1 and seen[0].finished and seen[0].token == -1
    assert seen[0].finish_reason == "max_wait"
    assert any(
        ev.request_id == b.request.request_id and ev.finished
        for ev in events
    )
    assert eng.metrics.expired == 1
    assert eng.metrics.tokens_out == 6  # a's tokens only, not the notification


def test_per_request_sampling_isolation(rng):
    """Per-slot sampling knobs: a greedy request, a temp-with-top_k=1
    request (deterministically argmax — proves the per-row filter applies
    to ITS row), and a hot-temperature request share ticks; the two
    deterministic rows must match the static greedy reference exactly."""
    cfg, model, prompt, params = _build(rng, n_rows=1)
    ref = np.asarray(generate(model, params, prompt, max_new_tokens=6))[0]
    eng = ServingEngine(
        model, params, n_slots=4,
        scheduler=SchedulerConfig(max_prefills_per_tick=4),
        rng=jax.random.PRNGKey(3),
    )
    greedy = eng.add_request(_req(prompt[0], 6))
    topk1 = eng.add_request(
        _req(prompt[0], 6, sampling=SamplingParams(temperature=1.0, top_k=1))
    )
    hot = eng.add_request(
        _req(prompt[0], 6, sampling=SamplingParams(temperature=4.0))
    )
    eng.run()
    np.testing.assert_array_equal(np.asarray(greedy.tokens), ref)
    np.testing.assert_array_equal(np.asarray(topk1.tokens), ref)
    assert len(hot.tokens) == 6
    assert all(0 <= tok < cfg.vocab_size for tok in hot.tokens)


def test_engine_int8_cache_matches_static_int8(rng):
    """The engine's slot pool composes with kv_cache_dtype="int8": both
    paths quantize identically, so engine greedy tokens equal static
    generate() on the same int8-cache model."""
    cfg, model, prompt, params = _build(rng, n_rows=2, kv_cache_dtype="int8")
    want = np.asarray(generate(model, params, prompt, max_new_tokens=6))
    eng = ServingEngine(
        model, params, n_slots=2,
        scheduler=SchedulerConfig(max_prefills_per_tick=2),
    )
    outs = [eng.add_request(_req(prompt[i], 6)) for i in range(2)]
    eng.run()
    for i, out in enumerate(outs):
        np.testing.assert_array_equal(np.asarray(out.tokens), want[i])


def test_streaming_events_and_metrics(rng):
    """Incremental delivery + observability: on_token fires once per token
    in order, and the summary's counters/latency stats are coherent."""
    cfg, model, prompt, params = _build(rng, n_rows=2)
    seen = []
    # per-step tick: the occupancy-mean assertion needs ticks where the
    # request is still in its slot at tick end (a fused tick would
    # finish it within the first decode tick)
    eng = ServingEngine(model, params, n_slots=2, decode_steps_per_tick=1)
    out = eng.add_request(
        _req(prompt[0], 5, on_token=lambda ev: seen.append(ev))
    )
    eng.run()
    assert [ev.token for ev in seen] == out.tokens
    assert [ev.index for ev in seen] == list(range(5))
    assert seen[-1].finished and seen[-1].finish_reason == "length"
    s = eng.metrics.summary()
    assert s["finished"] == 1 and s["tokens_out"] == 5
    assert s["ttft_ms_p50"] is not None and s["ttft_ms_p50"] >= 0
    assert 0.0 < s["slot_occupancy_mean"] <= 1.0
    assert s["tokens_per_sec"] is None or s["tokens_per_sec"] > 0


def test_capacity_rejected_at_submit(rng):
    cfg, model, prompt, params = _build(rng, n_rows=1)
    eng = ServingEngine(model, params, n_slots=1)
    out = eng.add_request(_req(prompt[0], cfg.seq_len))
    assert out.status == REJECTED and out.finish_reason == "capacity"
    assert "seq_len" in out.detail


def test_scheduler_policies_host_only():
    """Pure host-side scheduler behavior: FIFO order, prefill budget,
    expiry — no device work."""
    sched = FIFOScheduler(SchedulerConfig(max_prefills_per_tick=2))
    outs = [
        RequestOutput(Request(prompt=[1]), arrival_time=float(i))
        for i in range(5)
    ]
    for out in outs:
        assert sched.submit(out)
    assert sched.depth == 5
    first = sched.schedule(n_free=4, now=10.0)
    assert first == outs[:2]  # prefill budget caps below free slots
    second = sched.schedule(n_free=1, now=10.0)
    assert second == outs[2:3]  # free slots cap below the budget
    timed = FIFOScheduler(SchedulerConfig(max_wait=5.0))
    old = RequestOutput(Request(prompt=[1]), arrival_time=0.0)
    new = RequestOutput(Request(prompt=[1]), arrival_time=8.0)
    timed.submit(old), timed.submit(new)
    dropped = timed.expire(now=9.0)
    assert dropped == [old] and old.status == EXPIRED
    assert timed.schedule(4, 9.0) == [new]


def test_percentile_helper():
    assert percentile([], 50) is None
    assert percentile([3.0], 95) == 3.0
    assert percentile([1.0, 2.0, 3.0, 4.0], 50) == 2.5
    assert percentile([1.0, 2.0, 3.0, 4.0], 100) == 4.0


# -- prefill fast path ------------------------------------------------------


def _shared_prefix_prompts(rng, cfg, prefix_len, suffix_lens):
    """Prompts sharing one random ``prefix_len``-token header, with random
    suffixes of the given lengths — the system-prompt workload shape."""
    prefix = [
        int(t)
        for t in np.asarray(
            jax.random.randint(rng, (prefix_len,), 1, cfg.vocab_size)
        )
    ]
    prompts = []
    for i, n in enumerate(suffix_lens):
        sfx = np.asarray(
            jax.random.randint(
                jax.random.fold_in(rng, 100 + i), (n,), 1, cfg.vocab_size
            )
        )
        prompts.append(prefix + [int(t) for t in sfx])
    return prompts


def _greedy_refs(model, params, prompts, n_new):
    return [
        np.asarray(
            generate(
                model, params, jnp.asarray(p, jnp.int32)[None, :],
                max_new_tokens=n_new,
            )
        )[0]
        for p in prompts
    ]


def test_padded_prefill_inputs_helper():
    pos, last = padded_prefill_inputs([3, 5, 1], 5)
    np.testing.assert_array_equal(
        np.asarray(pos),
        [[0, 1, 2, -1, -1], [0, 1, 2, 3, 4], [0, -1, -1, -1, -1]],
    )
    np.testing.assert_array_equal(np.asarray(last), [2, 4, 0])


def test_default_prefill_buckets():
    assert default_prefill_buckets(1024) == (32, 64, 128, 256, 512, 1024)
    assert default_prefill_buckets(32) == (32,)
    assert default_prefill_buckets(100) == (32, 64, 100)


def test_bucketed_prefill_parity_staggered(rng):
    """Acceptance: bucketed + batched prefill is token-identical to exact
    prefill, INCLUDING staggered arrivals into reused slots — mixed prompt
    lengths through a 2-slot pool, every request vs its own static greedy
    reference."""
    cfg, model, _, params = _build(rng)
    lens, budgets = [3, 9, 6, 14, 11], [6, 4, 8, 5, 6]
    rows = [
        jax.random.randint(
            jax.random.fold_in(rng, i), (1, L), 1, cfg.vocab_size
        )
        for i, L in enumerate(lens)
    ]
    prompts = [[int(t) for t in np.asarray(r)[0]] for r in rows]
    refs = [
        np.asarray(
            generate(model, params, r, max_new_tokens=n)
        )[0]
        for r, n in zip(rows, budgets)
    ]
    eng = ServingEngine(
        model, params, n_slots=2,
        scheduler=SchedulerConfig(max_prefills_per_tick=2),
        prefill_buckets=(4, 8, 16),
    )
    outs = [eng.add_request(_req(prompts[0], budgets[0]))]
    outs.append(eng.add_request(_req(prompts[1], budgets[1])))
    eng.step(), eng.step()
    outs.append(eng.add_request(_req(prompts[2], budgets[2])))
    eng.step()
    outs.append(eng.add_request(_req(prompts[3], budgets[3])))
    outs.append(eng.add_request(_req(prompts[4], budgets[4])))
    eng.run()
    for i, (out, ref) in enumerate(zip(outs, refs)):
        assert out.status == FINISHED, f"request {i}: {out.status}"
        np.testing.assert_array_equal(
            np.asarray(out.tokens), ref, err_msg=f"request {i}"
        )
    assert eng.metrics.finished == 5 and eng.pool.n_free == 2
    # 5 distinct lengths collapsed onto <= 4 call shapes (3 buckets +
    # seq_len appended)
    assert eng.prefill_compiles <= 4


@pytest.mark.parametrize("chunk", [3, 5])
def test_chunked_prefill_parity(rng, chunk):
    """Acceptance: chunked prefill (prompts split across decode ticks,
    continuing into the slot's cache via multi-token write_index) is
    token-identical to exact monolithic prefill for every chunk budget."""
    cfg, model, _, params = _build(rng)
    lens = [9, 13, 4]
    rows = [
        jax.random.randint(
            jax.random.fold_in(rng, 10 + i), (1, L), 1, cfg.vocab_size
        )
        for i, L in enumerate(lens)
    ]
    prompts = [[int(t) for t in np.asarray(r)[0]] for r in rows]
    refs = [
        np.asarray(generate(model, params, r, max_new_tokens=6))[0]
        for r in rows
    ]
    eng = ServingEngine(
        model, params, n_slots=2,
        scheduler=SchedulerConfig(max_prefills_per_tick=2),
        prefill_buckets=(4, 8, 16),
        prefill_chunk_tokens=chunk,
    )
    outs = [eng.add_request(_req(p, 6)) for p in prompts]
    eng.run()
    for i, (out, ref) in enumerate(zip(outs, refs)):
        assert out.status == FINISHED, f"request {i}: {out.status}"
        np.testing.assert_array_equal(
            np.asarray(out.tokens), ref, err_msg=f"request {i}"
        )
    # the long prompts really went through chunk continuations
    assert eng.metrics.prefill_chunks >= sum(
        -(-L // chunk) for L in lens if L > chunk
    )


def test_chunked_prefill_interleaves_decode(rng):
    """A long prompt's chunks ride separate ticks, and already-running
    requests keep producing tokens on those ticks (the head-of-line fix)."""
    cfg, model, _, params = _build(rng)
    short = [int(t) for t in np.asarray(
        jax.random.randint(rng, (3,), 1, cfg.vocab_size)
    )]
    long = [int(t) for t in np.asarray(
        jax.random.randint(jax.random.fold_in(rng, 1), (12,), 1,
                           cfg.vocab_size)
    )]
    eng = ServingEngine(
        model, params, n_slots=2,
        prefill_buckets=(4, 8, 16), prefill_chunk_tokens=4,
        decode_steps_per_tick=1,  # per-tick progress accounting below
    )
    a = eng.add_request(_req(short, 10))
    eng.step()  # a running
    b = eng.add_request(_req(long, 4))
    n_before = len(a.tokens)
    eng.step()  # b's first chunk + a's decode tick
    eng.step()  # b's second chunk + a's decode tick
    assert len(b.tokens) == 0  # still prefilling (12 tokens / 4-chunks)
    assert len(a.tokens) >= n_before + 2  # decode never stalled
    eng.run()
    ref_b = np.asarray(
        generate(model, params, jnp.asarray(long, jnp.int32)[None, :],
                 max_new_tokens=4)
    )[0]
    np.testing.assert_array_equal(np.asarray(b.tokens), ref_b)


def test_prefix_cache_unit():
    """PrefixCache mechanics: bucket-aligned lookup, every-prefix store,
    LRU eviction, hit/miss counters."""
    pc = PrefixCache(max_entries=2)
    buckets = (4, 8)
    assert pc.lookup([1, 2, 3, 4, 5], buckets) is None  # miss, empty
    stored = pc.store([1, 2, 3, 4, 5], buckets, "rowA")
    assert stored == [4]  # 8 >= len-? only the 4-prefix is proper
    hit = pc.lookup([1, 2, 3, 4, 9], buckets)
    assert hit == ("rowA", 4)
    assert (pc.hits, pc.misses) == (1, 1)
    # identical full prompt: the 4-prefix still serves (strictly shorter)
    assert pc.lookup([1, 2, 3, 4, 5], buckets) == ("rowA", 4)
    # a long prompt stores BOTH aligned prefixes, evicting LRU beyond 2
    pc.store(list(range(10, 19)), buckets, "rowB")
    assert len(pc) == 2 and pc.evictions == 1
    assert pc.lookup([1, 2, 3, 4, 9], buckets) is None  # evicted
    assert pc.lookup(list(range(10, 19)), buckets) == ("rowB", 8)
    with pytest.raises(ValueError):
        PrefixCache(0)


def test_prefix_reuse_exact_output(rng):
    """Acceptance: prefix-cache hits (copied K/V rows + remainder-only
    prefill) produce token-identical greedy output, across staggered
    arrivals into REUSED slots; counters and eviction behave."""
    cfg, model, _, params = _build(rng)
    prompts = _shared_prefix_prompts(
        rng, cfg, prefix_len=8, suffix_lens=[3, 6, 2, 9, 5]
    )
    refs = _greedy_refs(model, params, prompts, 6)
    eng = ServingEngine(
        model, params, n_slots=2,
        scheduler=SchedulerConfig(max_prefills_per_tick=2),
        prefill_buckets=(8, 16), prefix_cache_size=4,
    )
    outs = [eng.add_request(_req(prompts[0], 6))]
    outs.append(eng.add_request(_req(prompts[1], 6)))
    eng.step(), eng.step()
    for p in prompts[2:]:
        outs.append(eng.add_request(_req(p, 6)))
    eng.run()
    for i, (out, ref) in enumerate(zip(outs, refs)):
        assert out.status == FINISHED, f"request {i}: {out.status}"
        np.testing.assert_array_equal(
            np.asarray(out.tokens), ref, err_msg=f"request {i}"
        )
    s = eng.metrics.summary()
    # every request after the first shares the 8-token header
    assert s["prefix_hits"] >= 3 and s["prefix_hit_rate"] > 0.5


def test_prefix_reuse_int8_cache_exact(rng):
    """Acceptance: prefix reuse + bucketing over an int8 KV cache —
    copied quantized rows are bit-identical, greedy output matches the
    static int8 reference."""
    cfg, model, _, params = _build(rng, kv_cache_dtype="int8")
    prompts = _shared_prefix_prompts(
        rng, cfg, prefix_len=8, suffix_lens=[3, 5, 4, 6]
    )
    refs = _greedy_refs(model, params, prompts, 6)
    eng = ServingEngine(
        model, params, n_slots=2,
        scheduler=SchedulerConfig(max_prefills_per_tick=2),
        prefill_buckets=(8, 16), prefix_cache_size=2,
    )
    outs = [eng.add_request(_req(p, 6)) for p in prompts]
    eng.run()
    for i, (out, ref) in enumerate(zip(outs, refs)):
        np.testing.assert_array_equal(
            np.asarray(out.tokens), ref, err_msg=f"request {i}"
        )
    assert eng.metrics.prefix_hits >= 2


def test_prefill_compile_count(rng):
    """Acceptance: with bucketing, the prefill jit compiles at most one
    program per bucket regardless of how many distinct prompt lengths
    arrive — inspected via the jitted function's lowering cache."""
    from tpu_parallel.serving import engine as engine_mod

    engine_mod._engine_fns.cache_clear()  # fresh jit fns for this model
    cfg, model, _, params = _build(rng)
    eng = ServingEngine(
        model, params, n_slots=4,
        scheduler=SchedulerConfig(max_prefills_per_tick=2),
        prefill_buckets=(4, 8, 16),
    )
    if not hasattr(eng._prefill_fn, "_cache_size"):
        pytest.skip("jax.jit cache inspection unavailable")
    lengths = [3, 4, 5, 6, 7, 9, 11, 13, 15, 17]  # 10 distinct lengths
    for i, L in enumerate(lengths):
        p = jax.random.randint(
            jax.random.fold_in(rng, i), (L,), 1, cfg.vocab_size
        )
        eng.add_request(_req(np.asarray(p), 2))
    eng.run()
    n_buckets = 4  # (4, 8, 16) + seq_len=32 appended
    assert eng._prefill_fn._cache_size() <= n_buckets
    assert eng.prefill_compiles <= n_buckets
    assert eng.metrics.finished == len(lengths)
    # same-bucket admissions batched: fewer device calls than requests
    assert eng.metrics.prefill_calls < len(lengths)
    # the legacy exact path really does compile per distinct length
    engine_mod._engine_fns.cache_clear()
    exact = ServingEngine(
        model, params, n_slots=4, prefill_buckets=None,
    )
    for i, L in enumerate([3, 5, 7, 9]):
        p = jax.random.randint(
            jax.random.fold_in(rng, 50 + i), (L,), 1, cfg.vocab_size
        )
        exact.add_request(_req(np.asarray(p), 2))
    exact.run()
    assert exact._prefill_fn._cache_size() == 4


def test_engine_refuses_relative_positional(rng):
    """The shared T5 bias table assumes row-uniform positions — a slot
    pool's mixed-depth rows (and padded prefill rows) break it, so the
    engine refuses loudly instead of serving row-0 bias to every slot."""
    cfg, model, _, params = _build(rng, positional="relative")
    with pytest.raises(NotImplementedError, match="relative"):
        ServingEngine(model, params, n_slots=2)


def test_scheduler_injectable_clock():
    """Satellite: the scheduler's own clock drives expire()/schedule()
    when ``now`` is omitted — timeout tests advance a fake clock instead
    of sleeping."""
    t = [0.0]
    sched = FIFOScheduler(SchedulerConfig(max_wait=5.0), clock=lambda: t[0])
    old = RequestOutput(Request(prompt=[1]), arrival_time=0.0)
    new = RequestOutput(Request(prompt=[1]), arrival_time=4.0)
    sched.submit(old), sched.submit(new)
    assert sched.expire() == []  # t=0: nothing stale
    t[0] = 6.0
    dropped = sched.expire()  # no `now` argument, no sleep
    assert dropped == [old] and old.status == EXPIRED
    assert sched.schedule(4) == [new]


def test_scheduler_bucket_grouping():
    """bucket_key constrains a tick's admissions to the FIFO head's
    group; other buckets keep their order for the next tick."""
    sched = FIFOScheduler(SchedulerConfig(max_prefills_per_tick=3))
    outs = [
        RequestOutput(Request(prompt=[1] * n), arrival_time=0.0)
        for n in [3, 9, 4, 2, 11]
    ]
    for out in outs:
        sched.submit(out)
    key = lambda o: len(o.request.prompt) <= 4  # two buckets
    first = sched.schedule(8, 0.0, bucket_key=key)
    assert first == [outs[0], outs[2], outs[3]]  # head's bucket, FIFO
    second = sched.schedule(8, 0.0, bucket_key=key)
    assert second == [outs[1], outs[4]]
    assert sched.depth == 0


def test_metrics_empty_run_summary():
    """Satellite: a run with ZERO finished requests still summarizes to
    serializable values (no IndexError/NaN in the JSONL sink)."""
    import json

    m = ServingMetrics()
    s = m.summary()
    assert s["finished"] == 0 and s["ttft_ms_p95"] is None
    assert s["prefix_hit_rate"] is None and s["tokens_per_sec"] is None
    json.dumps(s)  # must not raise
    m.record_tick(now=1.0, queue_depth=0, occupancy=0.0, new_tokens=0,
                  prefills=0, decoded=False)
    json.dumps(m.summary())
    assert percentile([None, None], 50) is None  # degenerate samples
    assert percentile([1.0], 200.0) == 1.0  # p clamped into [0, 100]


@pytest.mark.slow
def test_burst_ttft_improves_with_fast_path(rng):
    """Perf (wall-clock, >5s — slow lane): under an all-at-once burst of
    mixed-length shared-prefix prompts, the fast path (bucketed batched
    prefill + prefix reuse) cuts TTFT p95 vs the exact batch-1 engine.
    Timing-based: asserts direction with generous margin, not a ratio."""
    import time as _time

    cfg, model, _, params = _build(rng)
    prompts = _shared_prefix_prompts(
        rng, cfg, prefix_len=8,
        suffix_lens=[(i * 7) % 13 + 1 for i in range(24)],
    )

    def drive(**kw):
        eng = ServingEngine(
            model, params, n_slots=8,
            scheduler=SchedulerConfig(max_prefills_per_tick=4), **kw,
        )
        for p in prompts:  # warm compiles
            eng.add_request(_req(p, 2))
        eng.run()
        eng.reset_metrics()
        t0 = _time.perf_counter()
        outs = [eng.add_request(_req(p, 8)) for p in prompts]
        eng.run()
        assert all(out.status == FINISHED for out in outs)
        return eng.metrics.summary()

    slow = drive(prefill_buckets=None)
    fast = drive(prefill_buckets=(8, 16), prefix_cache_size=8)
    assert fast["prefix_hits"] > 0  # the prefix cache really engaged
    assert fast["ttft_ms_p95"] < slow["ttft_ms_p95"]


# -- speculative decoding ---------------------------------------------------


def test_spec_engine_greedy_parity_staggered(rng):
    """Acceptance: the speculative engine (n-gram drafter, adaptive K,
    bucketed prefill) is token-identical to the NON-spec engine and the
    static reference across staggered arrivals into reused slots."""
    cfg, model, _, params = _build(rng)
    lens, budgets = [3, 9, 6, 12, 5], [8, 6, 8, 5, 7]
    prompts = [
        [int(t) for t in np.asarray(
            jax.random.randint(
                jax.random.fold_in(rng, i), (L,), 1, cfg.vocab_size
            )
        )]
        for i, L in enumerate(lens)
    ]
    refs = [
        np.asarray(generate(
            model, params, jnp.asarray(p, jnp.int32)[None, :],
            max_new_tokens=n,
        ))[0]
        for p, n in zip(prompts, budgets)
    ]

    def drive(**kw):
        eng = ServingEngine(
            model, params, n_slots=2,
            scheduler=SchedulerConfig(max_prefills_per_tick=2),
            prefill_buckets=(4, 8, 16), **kw,
        )
        outs = [eng.add_request(_req(prompts[0], budgets[0]))]
        outs.append(eng.add_request(_req(prompts[1], budgets[1])))
        eng.step(), eng.step()
        outs.append(eng.add_request(_req(prompts[2], budgets[2])))
        eng.step()
        for p, n in zip(prompts[3:], budgets[3:]):
            outs.append(eng.add_request(_req(p, n)))
        eng.run()
        return eng, outs

    plain_eng, plain = drive()
    spec_eng, spec = drive(draft_tokens=3, spec_check_invariants=True)
    for i, (a, b, ref) in enumerate(zip(plain, spec, refs)):
        assert a.status == FINISHED and b.status == FINISHED
        np.testing.assert_array_equal(
            np.asarray(a.tokens), ref, err_msg=f"plain request {i}"
        )
        np.testing.assert_array_equal(
            np.asarray(b.tokens), ref, err_msg=f"spec request {i}"
        )
    s = spec_eng.metrics.summary()
    assert s["tokens_drafted"] > 0
    assert s["spec_acceptance_rate"] is not None


def test_spec_engine_int8_cache_parity(rng):
    """Speculative verify + int8 KV cache: quantization is per
    (position, kv-head), invisible to block width — spec greedy tokens
    equal the static int8 reference."""
    cfg, model, prompt, params = _build(rng, n_rows=2,
                                        kv_cache_dtype="int8")
    want = np.asarray(generate(model, params, prompt, max_new_tokens=8))
    eng = ServingEngine(
        model, params, n_slots=2,
        scheduler=SchedulerConfig(max_prefills_per_tick=2),
        draft_tokens=3,
    )
    outs = [eng.add_request(_req(prompt[i], 8)) for i in range(2)]
    eng.run()
    for i, out in enumerate(outs):
        np.testing.assert_array_equal(np.asarray(out.tokens), want[i])


def test_spec_engine_adversarial_drafter_exact(rng):
    """Acceptance: a drafter returning garbage every tick must cost only
    wasted verify positions — token-exact output, acceptance rate 0."""
    cfg, model, prompt, params = _build(rng, n_rows=2)
    want = np.asarray(generate(model, params, prompt, max_new_tokens=8))
    prompts = [[int(t) for t in np.asarray(prompt[i])] for i in range(2)]
    eng = ServingEngine(
        model, params, n_slots=2,
        scheduler=SchedulerConfig(max_prefills_per_tick=2),
        draft_tokens=3, spec_adaptive=False,
        drafter=AntiOracleDrafter(_ref_map(prompts, want), cfg.vocab_size),
        spec_check_invariants=True,
    )
    outs = [eng.add_request(_req(p, 8)) for p in prompts]
    eng.run()
    for i, out in enumerate(outs):
        np.testing.assert_array_equal(np.asarray(out.tokens), want[i])
    s = eng.metrics.summary()
    assert s["tokens_drafted"] > 0 and s["tokens_accepted"] == 0
    assert s["spec_acceptance_rate"] == 0.0
    assert s["spec_wasted_positions"] > 0


def test_spec_engine_eos_mid_verify_block(rng):
    """Acceptance: EOS landing INSIDE an accepted verify block truncates
    delivery at the EOS token and finishes with finish_reason="eos" —
    matching the non-spec engine on the same request."""
    cfg, model, prompt, params = _build(rng, n_rows=1, prompt_len=4)
    ref = list(np.asarray(
        generate(model, params, prompt, max_new_tokens=10)
    )[0])
    # an EOS value whose FIRST occurrence is deep enough that an oracle
    # K=6 block (emitted as ref[1..7] on the first verify tick) spans it
    eos_idx = next(
        i for i in range(2, 7) if ref[i] not in ref[:i]
    )
    eos = int(ref[eos_idx])
    prompts = [[int(t) for t in np.asarray(prompt[0])]]

    def drive(**kw):
        eng = ServingEngine(model, params, n_slots=1, **kw)
        out = eng.add_request(_req(prompts[0], 10, eos_token_id=eos))
        eng.run()
        return eng, out

    _, plain = drive()
    eng, spec = drive(
        draft_tokens=6, drafter=OracleDrafter(_ref_map(prompts, [ref])),
        spec_check_invariants=True,
    )
    assert plain.finish_reason == "eos" and spec.finish_reason == "eos"
    assert spec.tokens == ref[: eos_idx + 1] == plain.tokens
    # the oracle block really did span the EOS (some surplus discarded)
    assert eng.metrics.spec_wasted_positions > 0
    assert eng.pool.n_free == 1


def test_spec_engine_oracle_fewer_decode_ticks(rng):
    """The deterministic form of the speedup claim: with a perfect
    drafter the engine finishes the same workload in far fewer decode
    ticks than one-token-per-tick (no wall-clock in tier-1)."""
    cfg, model, prompt, params = _build(rng, n_rows=2)
    n_new = 12
    want = np.asarray(generate(model, params, prompt, max_new_tokens=n_new))
    prompts = [[int(t) for t in np.asarray(prompt[i])] for i in range(2)]

    def drive(**kw):
        eng = ServingEngine(
            model, params, n_slots=2,
            scheduler=SchedulerConfig(max_prefills_per_tick=2), **kw,
        )
        outs = [eng.add_request(_req(p, n_new)) for p in prompts]
        eng.run()
        for i, out in enumerate(outs):
            np.testing.assert_array_equal(np.asarray(out.tokens), want[i])
        return eng.metrics

    plain = drive(decode_steps_per_tick=1)  # the per-step baseline
    spec = drive(
        draft_tokens=4, drafter=OracleDrafter(_ref_map(prompts, want)),
    )
    assert plain.decode_ticks == n_new - 1  # one token per tick
    assert spec.decode_ticks <= 3  # ~5 tokens per verify tick
    assert spec.tokens_accepted > 0
    s = spec.summary()
    assert s["tokens_per_decode_tick"] > plain.summary()[
        "tokens_per_decode_tick"
    ]


def test_spec_engine_per_request_knobs(rng):
    """Per-request draft_tokens: 0 opts a request out of drafting (it
    still shares verify ticks) while its neighbour speculates; both stay
    exact, and a hot-temperature request rides along unharmed."""
    cfg, model, prompt, params = _build(rng, n_rows=1)
    ref = np.asarray(generate(model, params, prompt, max_new_tokens=6))[0]
    eng = ServingEngine(
        model, params, n_slots=4,
        scheduler=SchedulerConfig(max_prefills_per_tick=4),
        draft_tokens=3, rng=jax.random.PRNGKey(3),
    )
    on = eng.add_request(_req(prompt[0], 6))
    off = eng.add_request(_req(prompt[0], 6, draft_tokens=0))
    hot = eng.add_request(
        _req(prompt[0], 6, sampling=SamplingParams(temperature=4.0))
    )
    eng.run()
    np.testing.assert_array_equal(np.asarray(on.tokens), ref)
    np.testing.assert_array_equal(np.asarray(off.tokens), ref)
    assert len(hot.tokens) == 6
    assert all(0 <= tok < cfg.vocab_size for tok in hot.tokens)
    with pytest.raises(ValueError, match="draft_tokens"):
        Request(prompt=[1], draft_tokens=-1)


def test_spec_engine_chunked_prefill_interleaves(rng):
    """Speculative ticks and chunked prefill coexist: a long prompt's
    chunks still ride separate ticks while running requests keep
    producing (multi-token) output, and everything stays exact."""
    cfg, model, _, params = _build(rng)
    short = [int(t) for t in np.asarray(
        jax.random.randint(rng, (3,), 1, cfg.vocab_size)
    )]
    long = [int(t) for t in np.asarray(
        jax.random.randint(jax.random.fold_in(rng, 1), (12,), 1,
                           cfg.vocab_size)
    )]
    refs = [
        np.asarray(generate(
            model, params, jnp.asarray(p, jnp.int32)[None, :],
            max_new_tokens=n,
        ))[0]
        for p, n in ((short, 10), (long, 4))
    ]
    eng = ServingEngine(
        model, params, n_slots=2,
        prefill_buckets=(4, 8, 16), prefill_chunk_tokens=4,
        draft_tokens=3,
    )
    a = eng.add_request(_req(short, 10))
    eng.step()
    b = eng.add_request(_req(long, 4))
    n_before = len(a.tokens)
    eng.step(), eng.step()
    assert len(b.tokens) == 0  # still prefilling
    assert len(a.tokens) >= n_before + 2  # decode never stalled
    eng.run()
    np.testing.assert_array_equal(np.asarray(a.tokens), refs[0])
    np.testing.assert_array_equal(np.asarray(b.tokens), refs[1])


def test_cache_pool_slot_aligned_guard(rng):
    """The no-rollback invariant guard: aligned slots pass; a table made
    deliberately misaligned trips the assert."""
    cfg, model, prompt, params = _build(rng, n_rows=2)
    eng = ServingEngine(model, params, n_slots=2, draft_tokens=2)
    out = eng.add_request(_req(prompt[0], 4))
    eng.run()
    assert out.status == FINISHED
    eng.pool.assert_slot_aligned(0)
    eng.pool.assert_slot_aligned(1)

    def corrupt(path, leaf):
        name = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
        if name.startswith("cached_pos"):
            return leaf.at[..., 0, 3].set(7)  # slot 0, column 3 -> pos 7
        return leaf

    eng.pool.cache = jax.tree_util.tree_map_with_path(corrupt, eng.pool.cache)
    with pytest.raises(AssertionError, match="misaligned"):
        eng.pool.assert_slot_aligned(0)


# -- fused multi-step decode tick -------------------------------------------


def _drive_engine(model, params, prompts, budgets, staggered=False, **kw):
    """Submit ``prompts`` (optionally staggered across ticks) and run to
    idle; returns (engine, outputs)."""
    eng = ServingEngine(
        model, params,
        scheduler=SchedulerConfig(max_prefills_per_tick=2), **kw,
    )
    outs = []
    if staggered:
        outs.append(eng.add_request(_req(prompts[0], budgets[0])))
        outs.append(eng.add_request(_req(prompts[1], budgets[1])))
        eng.step(), eng.step()
        outs.append(eng.add_request(_req(prompts[2], budgets[2])))
        eng.step()
        for p, n in zip(prompts[3:], budgets[3:]):
            outs.append(eng.add_request(_req(p, n)))
    else:
        outs = [
            eng.add_request(_req(p, n)) for p, n in zip(prompts, budgets)
        ]
    eng.run()
    return eng, outs


def test_fused_tick_greedy_parity_staggered(rng):
    """Acceptance: the fused tick (T=4) is BITWISE identical to the
    per-step engine across staggered arrivals into reused slots, with
    budgets deliberately not multiples of T so every request exhausts
    its budget MID-scan-block."""
    cfg, model, _, params = _build(rng)
    lens, budgets = [3, 9, 6, 12, 5], [6, 5, 9, 3, 7]
    prompts = [
        [int(t) for t in np.asarray(
            jax.random.randint(
                jax.random.fold_in(rng, i), (L,), 1, cfg.vocab_size
            )
        )]
        for i, L in enumerate(lens)
    ]
    kw = dict(n_slots=2, prefill_buckets=(4, 8, 16))
    plain_eng, plain = _drive_engine(
        model, params, prompts, budgets, staggered=True,
        decode_steps_per_tick=1, **kw,
    )
    fused_eng, fused = _drive_engine(
        model, params, prompts, budgets, staggered=True,
        decode_steps_per_tick=4, **kw,
    )
    for i, (a, b) in enumerate(zip(plain, fused)):
        assert a.status == FINISHED and b.status == FINISHED
        assert a.finish_reason == b.finish_reason == "length"
        np.testing.assert_array_equal(
            np.asarray(b.tokens), np.asarray(a.tokens),
            err_msg=f"request {i}",
        )
    # the fused engine really amortized: far fewer decode ticks
    assert fused_eng.metrics.decode_ticks < plain_eng.metrics.decode_ticks
    assert fused_eng.pool.n_free == 2


def test_fused_tick_eos_mid_block(rng):
    """EOS sampled MID-scan-block: delivery truncates at the EOS token,
    the surplus scan steps park their writes, and the retired slot is
    clean for its next occupant — bitwise equal to the per-step engine."""
    cfg, model, prompt, params = _build(rng, n_rows=2, prompt_len=4)
    ref = list(np.asarray(
        generate(model, params, prompt[:1], max_new_tokens=12)
    )[0])
    # an EOS whose first occurrence is deep enough that a T=8 block
    # spans it mid-scan
    eos_idx = next(i for i in range(2, 7) if ref[i] not in ref[:i])
    eos = int(ref[eos_idx])
    prompts = [[int(t) for t in np.asarray(prompt[0])]]

    def drive(**kw):
        eng = ServingEngine(model, params, n_slots=1, **kw)
        out = eng.add_request(_req(prompts[0], 12, eos_token_id=eos))
        eng.run()
        # the slot is reusable and unpolluted after the mid-block retire
        nxt = eng.add_request(_req(prompts[0], 4))
        eng.run()
        return eng, out, nxt

    _, plain, plain_next = drive(decode_steps_per_tick=1)
    eng, fused, fused_next = drive(decode_steps_per_tick=8)
    assert plain.finish_reason == fused.finish_reason == "eos"
    assert fused.tokens == ref[: eos_idx + 1] == plain.tokens
    assert fused_next.tokens == plain_next.tokens
    assert eng.pool.n_free == 1
    eng.pool.assert_slot_aligned(0)


def test_fused_tick_int8_parity(rng):
    """Fused tick over an int8 KV cache (the int8-native attention read):
    bitwise equal to the per-step int8 engine and the static int8
    reference."""
    cfg, model, prompt, params = _build(rng, n_rows=2, kv_cache_dtype="int8")
    want = np.asarray(generate(model, params, prompt, max_new_tokens=9))
    prompts = [[int(t) for t in np.asarray(prompt[i])] for i in range(2)]
    _, plain = _drive_engine(
        model, params, prompts, [9, 9], n_slots=2, decode_steps_per_tick=1,
    )
    _, fused = _drive_engine(
        model, params, prompts, [9, 9], n_slots=2, decode_steps_per_tick=4,
    )
    for i in range(2):
        np.testing.assert_array_equal(np.asarray(plain[i].tokens), want[i])
        np.testing.assert_array_equal(np.asarray(fused[i].tokens), want[i])


def test_fused_tick_chunked_prefill_interleave_parity(rng):
    """Fused decode ticks compose with chunked prefill: a long prompt's
    chunks keep riding one-per-tick while fused blocks advance running
    requests; both requests stay bitwise exact."""
    cfg, model, _, params = _build(rng)
    short = [int(t) for t in np.asarray(
        jax.random.randint(rng, (3,), 1, cfg.vocab_size)
    )]
    long = [int(t) for t in np.asarray(
        jax.random.randint(jax.random.fold_in(rng, 1), (12,), 1,
                           cfg.vocab_size)
    )]
    refs = [
        np.asarray(generate(
            model, params, jnp.asarray(p, jnp.int32)[None, :],
            max_new_tokens=n,
        ))[0]
        for p, n in ((short, 11), (long, 5))
    ]
    eng = ServingEngine(
        model, params, n_slots=2,
        prefill_buckets=(4, 8, 16), prefill_chunk_tokens=4,
        decode_steps_per_tick=4,
    )
    a = eng.add_request(_req(short, 11))
    eng.step()
    b = eng.add_request(_req(long, 5))
    eng.run()
    np.testing.assert_array_equal(np.asarray(a.tokens), refs[0])
    np.testing.assert_array_equal(np.asarray(b.tokens), refs[1])
    assert eng.metrics.prefill_chunks >= 3  # 12 tokens / 4-chunks


def test_fused_tick_donation_invalidates_old_buffers(rng):
    """Satellite (buffer-donation audit): the cache pool AND the device
    slot-state operands are DONATED — after a tick the previous tick's
    buffers are deleted, so no second pool copy can exist.  Pinned for
    the fused tick and the per-step ``_decode_fn`` alike; a stale
    reference held across a tick raises on use."""
    cfg, model, prompt, params = _build(rng, n_rows=1)
    for steps in (1, 4):
        eng = ServingEngine(
            model, params, n_slots=2, decode_steps_per_tick=steps,
        )
        out = eng.add_request(_req(prompt[0], 12))
        eng.step()  # admit + first decode tick
        old_cache = jax.tree_util.tree_leaves(eng.pool.cache)
        old_state = (
            jax.tree_util.tree_leaves(eng._dev_state) if steps > 1 else []
        )
        eng.step()  # decode-only tick: donates cache (and fused state)
        assert all(leaf.is_deleted() for leaf in old_cache), (
            f"T={steps}: old pool buffers survived the tick (donation "
            "regressed — a second full pool copy is alive)"
        )
        assert all(leaf.is_deleted() for leaf in old_state)
        eng.run()
        assert out.status == FINISHED and len(out.tokens) == 12


@pytest.mark.parametrize("pool", ["fixed", "paged"])
def test_fused_tick_compile_count_pin(rng, pool):
    """The fused tick compiles ONCE on either pool: its state/cache shapes
    are fixed by (n_slots, seq_len) and the paged block table rides its
    inputs at a fixed [n_slots, max_blocks] shape, so a mixed workload —
    staggered arrivals, EOS, varying budgets, prefix hits, table growth —
    adds prefill shapes only, bounded by the bucket set (+1 extend shape
    per distinct hit group width; the paged pool prefills through the
    extend alone)."""
    from tpu_parallel.serving import engine as engine_mod

    engine_mod._engine_fns.cache_clear()
    engine_mod._fused_engine_fn.cache_clear()
    cfg, model, _, params = _build(rng)
    eng = ServingEngine(
        model, params, n_slots=4,
        scheduler=SchedulerConfig(max_prefills_per_tick=2),
        prefill_buckets=(4, 8, 16), prefix_cache_size=2,
        decode_steps_per_tick=8,
        kv_block_tokens=4 if pool == "paged" else None,
    )
    if not hasattr(eng._fused_fn, "_cache_size"):
        pytest.skip("jax.jit cache inspection unavailable")
    shared = [7, 3, 5, 2]
    lengths = [3, 4, 5, 6, 9, 11, 15]
    for i, L in enumerate(lengths):
        sfx = jax.random.randint(
            jax.random.fold_in(rng, i), (max(1, L - 4),), 1, cfg.vocab_size
        )
        p = shared + [int(t) for t in np.asarray(sfx)]
        eng.add_request(_req(p, 2 + (i % 5)))
        if i % 2:
            eng.step()
    eng.run()
    assert eng.metrics.finished == len(lengths)
    n_buckets = 4  # (4, 8, 16) + seq_len appended
    # ONE fused program, ever (paged: the table upload is loop-invariant)
    assert eng._fused_fn._cache_size() == 1
    prefills = 0 if pool == "paged" else eng._prefill_fn._cache_size()
    assert prefills <= n_buckets
    # total jitted decode+prefill+extend shapes stay <= #buckets + 2
    assert (
        eng._fused_fn._cache_size() + prefills
        + eng._extend_fn._cache_size()
    ) <= n_buckets + 2


def test_fused_tick_dispatch_metrics(rng):
    """Satellite (dispatch observability): host_dispatches /
    tokens_per_dispatch / host_ms_per_tick flow registry -> summary ->
    Prometheus text, and the fused tick's amortization is visible —
    tokens per dispatch strictly above the per-step engine's."""
    from tpu_parallel.obs import write_prometheus

    cfg, model, prompt, params = _build(rng, n_rows=1)
    prompts = [[int(t) for t in np.asarray(prompt[0])]]

    def drive(steps):
        eng, _ = _drive_engine(
            model, params, prompts, [12], n_slots=1,
            decode_steps_per_tick=steps,
        )
        return eng

    plain, fused = drive(1), drive(8)
    for eng in (plain, fused):
        s = eng.metrics.summary()
        assert s["host_dispatches"] == eng.metrics.host_dispatches > 0
        assert s["tokens_per_dispatch_mean"] > 0
        assert s["host_ms_per_tick_p95"] is not None
    assert (
        fused.metrics.summary()["tokens_per_dispatch_mean"]
        > plain.metrics.summary()["tokens_per_dispatch_mean"]
    )
    # far fewer host round-trips for the same 12 tokens
    assert fused.metrics.host_dispatches < plain.metrics.host_dispatches
    text = write_prometheus(fused.registry, "/tmp/test_dispatch_prom.txt")
    exposition = open(text).read()
    for name in (
        "serving_host_dispatches_total",
        "serving_tokens_per_dispatch",
        "serving_host_ms_per_tick",
    ):
        assert name in exposition, name


def test_fused_tick_cancel_from_stream_callback(rng):
    """Regression: cancel() issued from inside an on_token stream
    callback (the client-disconnect pattern) mid-fused-block must drop
    the slot's surplus device tokens and leave neighbours delivering —
    not crash the tick on the released slot's None record."""
    cfg, model, prompt, params = _build(rng, n_rows=2)
    ref = np.asarray(generate(model, params, prompt[1:2], max_new_tokens=12))
    eng = ServingEngine(
        model, params, n_slots=2,
        scheduler=SchedulerConfig(max_prefills_per_tick=2),
        decode_steps_per_tick=8,
    )
    got = []

    def disconnect(ev):
        got.append(ev.token)
        if len(got) == 3:  # mid-block: 8-token device blocks
            assert eng.cancel(victim.request.request_id)

    victim = eng.add_request(
        _req(prompt[0], 12, on_token=disconnect)
    )
    neighbour = eng.add_request(_req(prompt[1], 12))
    eng.run()
    assert victim.status == "cancelled"
    assert len(victim.tokens) == 3  # surplus block tokens dropped
    assert neighbour.status == FINISHED
    np.testing.assert_array_equal(np.asarray(neighbour.tokens), ref[0])
    assert eng.pool.n_free == 2
    # a callback cancelling a DIFFERENT slot mid-loop is survived too
    eng2 = ServingEngine(
        model, params, n_slots=2,
        scheduler=SchedulerConfig(max_prefills_per_tick=2),
        decode_steps_per_tick=8,
    )
    outs = {}

    def shoot_other(ev):
        other = outs.get("b")
        if other is not None and not other.done:
            eng2.cancel(other.request.request_id)

    outs["a"] = eng2.add_request(_req(prompt[0], 12, on_token=shoot_other))
    outs["b"] = eng2.add_request(_req(prompt[1], 12))
    eng2.run()
    assert outs["a"].status == FINISHED
    assert outs["b"].status == "cancelled"
    assert eng2.pool.n_free == 2
    # ... and on the SPECULATIVE per-step tick (same cancel-mid-loop class)
    eng3 = ServingEngine(
        model, params, n_slots=2,
        scheduler=SchedulerConfig(max_prefills_per_tick=2), draft_tokens=3,
    )
    souts = {}

    def spec_shoot(ev):
        other = souts.get("b")
        if other is not None and not other.done:
            eng3.cancel(other.request.request_id)

    souts["a"] = eng3.add_request(_req(prompt[0], 12, on_token=spec_shoot))
    souts["b"] = eng3.add_request(_req(prompt[1], 12))
    eng3.run()
    assert souts["a"].status == FINISHED
    assert souts["b"].status == "cancelled"
    assert eng3.pool.n_free == 2


def test_fused_tick_knob_validation(rng):
    """decode_steps_per_tick < 1 refuses; explicit T > 1 with a CUSTOM
    drafter refuses (in-scan drafting can only mirror the traceable
    NGram drafter), while the default drafter fuses T verify blocks per
    dispatch; 'auto' resolves to 8 plain and 1 speculative; unified_tick
    needs a fused tick."""
    cfg, model, _, params = _build(rng)
    with pytest.raises(ValueError, match="decode_steps_per_tick"):
        ServingEngine(model, params, n_slots=1, decode_steps_per_tick=0)
    with pytest.raises(NotImplementedError, match="drafter"):
        ServingEngine(
            model, params, n_slots=1, decode_steps_per_tick=4,
            draft_tokens=2, drafter=OracleDrafter({}),
        )
    spec_fused = ServingEngine(
        model, params, n_slots=1, decode_steps_per_tick=4, draft_tokens=2,
    )
    assert spec_fused.decode_steps_per_tick == 4
    assert spec_fused._spec_fused_fn is not None
    assert ServingEngine(model, params, n_slots=1).decode_steps_per_tick == 8
    assert (
        ServingEngine(
            model, params, n_slots=1, draft_tokens=2
        ).decode_steps_per_tick
        == 1
    )
    with pytest.raises(ValueError, match="unified_tick"):
        ServingEngine(
            model, params, n_slots=1, decode_steps_per_tick=1,
            unified_tick=True,
        )
    assert ServingEngine(model, params, n_slots=1).unified_tick
    assert not ServingEngine(
        model, params, n_slots=1, unified_tick=False
    ).unified_tick


# -- the unified ragged tick (prefill+decode in one dispatch) ---------------


def _drive_interleaved(model, params, prompts, budgets, **kw):
    """Submit prompts staggered so chunked prefills interleave running
    decodes, run to idle; returns (engine, outputs)."""
    eng = ServingEngine(
        model, params,
        scheduler=SchedulerConfig(max_prefills_per_tick=2), **kw,
    )
    outs = [eng.add_request(_req(prompts[0], budgets[0]))]
    eng.step()
    for p, n in zip(prompts[1:], budgets[1:]):
        outs.append(eng.add_request(_req(p, n)))
        eng.step()
    eng.run()
    return eng, outs


@pytest.mark.parametrize(
    "variant", ["plain", "int8", "paged", "paged_prefix"]
)
def test_unified_tick_bitwise_vs_per_phase(rng, variant):
    """Acceptance (tentpole): the unified ragged tick — chunked prefills
    and fused decode in ONE dispatch per tick, with in-device
    final-chunk activation — is BITWISE identical to the per-phase
    engine (unified_tick=False: per-slot chunk extends, then the decode
    dispatch) across staggered arrivals, chunk+decode interleave and
    slot reuse; per layout (fixed / int8 / paged / paged+prefix-cache)."""
    overrides = {"int8": dict(kv_cache_dtype="int8")}.get(variant, {})
    cfg, model, _, params = _build(rng, **overrides)
    layout = {
        "paged": dict(kv_block_tokens="auto"),
        "paged_prefix": dict(kv_block_tokens="auto", prefix_cache_size=2),
    }.get(variant, {})
    lens, budgets = [3, 12, 9, 14, 5], [9, 5, 7, 4, 6]
    prompts = [
        [int(t) for t in np.asarray(
            jax.random.randint(
                jax.random.fold_in(rng, 20 + i), (L,), 1, cfg.vocab_size
            )
        )]
        for i, L in enumerate(lens)
    ]
    kw = dict(
        n_slots=2, prefill_buckets=(4, 8, 16), prefill_chunk_tokens=4,
        decode_steps_per_tick=4, **layout,
    )
    phase_eng, phased = _drive_interleaved(
        model, params, prompts, budgets, unified_tick=False, **kw
    )
    uni_eng, unified = _drive_interleaved(
        model, params, prompts, budgets, unified_tick=True, **kw
    )
    assert uni_eng.unified_tick and not phase_eng.unified_tick
    for i, (a, b) in enumerate(zip(phased, unified)):
        assert a.status == FINISHED and b.status == FINISHED, (
            f"request {i}: {a.status} / {b.status}"
        )
        np.testing.assert_array_equal(
            np.asarray(b.tokens), np.asarray(a.tokens),
            err_msg=f"request {i} ({variant})",
        )
    # both really chunked; the unified engine paid FEWER device
    # dispatches for the same tokens (chunk extends rode the decode
    # dispatch) — the tick's raison d'etre
    assert uni_eng.metrics.prefill_chunks >= 3
    assert phase_eng.metrics.prefill_chunks == uni_eng.metrics.prefill_chunks
    assert uni_eng.metrics.host_dispatches < phase_eng.metrics.host_dispatches
    assert uni_eng.pool.n_free == 2


def test_unified_tick_chunk_only_progress_regression(rng):
    """Satellite bugfix: a tick holding ONLY mid-chunk prefill rows (no
    decode-live slots) makes progress by chunk advancement alone — the
    no-progress RuntimeError guard must not fire on it.  Pinned by
    stepping a single long chunked prompt through an otherwise-idle
    unified engine, tick by tick."""
    cfg, model, _, params = _build(rng)
    long = [int(t) for t in np.asarray(
        jax.random.randint(rng, (14,), 1, cfg.vocab_size)
    )]
    ref = np.asarray(generate(
        model, params, jnp.asarray(long, jnp.int32)[None, :],
        max_new_tokens=4,
    ))[0]
    eng = ServingEngine(
        model, params, n_slots=1, prefill_buckets=(4, 8, 16),
        prefill_chunk_tokens=4, decode_steps_per_tick=8,
    )
    assert eng.unified_tick
    out = eng.add_request(_req(long, 4))
    # ticks 1..3 hold only the mid-chunk prefill row: every one must
    # advance the chunk (not raise, not spin) and deliver nothing
    for tick in range(3):
        events = eng.step()
        assert events == [], f"tick {tick} delivered early: {events}"
        assert len(out.tokens) == 0
    assert eng.metrics.prefill_chunks == 3
    eng.run()
    assert out.status == FINISHED
    np.testing.assert_array_equal(np.asarray(out.tokens), ref)


def test_unified_tick_eos_at_activation_and_mid_block(rng):
    """EOS discipline through the unified tick: an EOS that IS the
    in-device-sampled first token retires the slot before it ever
    decodes, and an EOS mid-decode-block truncates delivery — both
    bitwise equal to the per-phase engine, slot clean for reuse."""
    cfg, model, _, params = _build(rng)
    long = [int(t) for t in np.asarray(
        jax.random.randint(rng, (11,), 1, cfg.vocab_size)
    )]
    ref = list(np.asarray(generate(
        model, params, jnp.asarray(long, jnp.int32)[None, :],
        max_new_tokens=12,
    ))[0])
    kw = dict(
        n_slots=1, prefill_buckets=(4, 8, 16), prefill_chunk_tokens=4,
        decode_steps_per_tick=8,
    )

    def drive(eos, unified):
        eng = ServingEngine(model, params, unified_tick=unified, **kw)
        out = eng.add_request(_req(long, 12, eos_token_id=eos))
        eng.run()
        nxt = eng.add_request(_req(long, 3))
        eng.run()
        assert eng.pool.n_free == 1
        return out, nxt

    # eos_idx 0: the EOS IS the in-device-sampled activation token (the
    # request retires without ever decoding); eos_idx 3: EOS lands
    # mid-decode-block (both engines stop at that token's FIRST greedy
    # occurrence — wherever it is, they must agree bitwise)
    for eos_idx in (0, 3):
        eos = int(ref[eos_idx])
        a, a_next = drive(eos, unified=False)
        b, b_next = drive(eos, unified=True)
        assert a.finish_reason == b.finish_reason == "eos"
        assert b.tokens == a.tokens and b.tokens[-1] == eos
        assert b_next.tokens == a_next.tokens


def test_unified_tick_chunk_starts_batch(rng):
    """Scheduler satellite: under the unified tick, chunked prompts
    share ONE admission group — two long prompts admit the SAME tick
    (each claiming a slot, both riding the one [n_slots, chunk_tokens]
    dispatch) instead of serializing one admission per tick; outputs
    stay bitwise."""
    cfg, model, _, params = _build(rng)
    longs = [
        [int(t) for t in np.asarray(
            jax.random.randint(
                jax.random.fold_in(rng, 40 + i), (11 + i,), 1,
                cfg.vocab_size
            )
        )]
        for i in range(2)
    ]
    refs = [
        np.asarray(generate(
            model, params, jnp.asarray(p, jnp.int32)[None, :],
            max_new_tokens=5,
        ))[0]
        for p in longs
    ]
    eng = ServingEngine(
        model, params, n_slots=2,
        scheduler=SchedulerConfig(max_prefills_per_tick=2),
        prefill_buckets=(4, 8, 16), prefill_chunk_tokens=4,
        decode_steps_per_tick=4,
    )
    outs = [eng.add_request(_req(p, 5)) for p in longs]
    eng.step()
    # both admitted (and mid-chunk) after ONE tick
    assert eng.in_flight == 2 and eng.scheduler.depth == 0
    eng.run()
    for out, ref in zip(outs, refs):
        assert out.status == FINISHED
        np.testing.assert_array_equal(np.asarray(out.tokens), ref)


@pytest.mark.parametrize("variant", ["plain", "int8", "paged"])
def test_spec_fused_tick_bitwise(rng, variant):
    """Fused speculative verify: T draft-verify-accept blocks per
    dispatch with in-scan NGram drafting — bitwise identical to the
    per-step spec engine AND the static reference across staggered
    arrivals, budgets exhausting mid-block, per layout."""
    overrides = {"int8": dict(kv_cache_dtype="int8")}.get(variant, {})
    cfg, model, _, params = _build(rng, **overrides)
    layout = (
        dict(kv_block_tokens="auto") if variant == "paged" else {}
    )
    lens, budgets = [3, 9, 6, 12, 5], [6, 5, 9, 3, 7]
    prompts = [
        [int(t) for t in np.asarray(
            jax.random.randint(
                jax.random.fold_in(rng, 60 + i), (L,), 1, cfg.vocab_size
            )
        )]
        for i, L in enumerate(lens)
    ]
    kw = dict(
        n_slots=2, prefill_buckets=(4, 8, 16), draft_tokens=3, **layout
    )
    step_eng, stepped = _drive_engine(
        model, params, prompts, budgets, staggered=True,
        decode_steps_per_tick=1, **kw,
    )
    fused_eng, fused = _drive_engine(
        model, params, prompts, budgets, staggered=True,
        decode_steps_per_tick=4, **kw,
    )
    for i, (a, b) in enumerate(zip(stepped, fused)):
        assert a.status == FINISHED and b.status == FINISHED
        np.testing.assert_array_equal(
            np.asarray(b.tokens), np.asarray(a.tokens),
            err_msg=f"request {i} ({variant})",
        )
    # the fused spec engine really amortized its verify dispatches
    assert fused_eng.metrics.host_dispatches < step_eng.metrics.host_dispatches
    # and both drafted (the drafter twin really ran in-scan)
    assert fused_eng.metrics.tokens_drafted > 0
    assert fused_eng.pool.n_free == 2


def test_spec_fused_eos_mid_block_and_chunked(rng):
    """Fused spec composes with chunked prefill (the unified spec tick)
    and truncates at EOS mid-verify-block — bitwise vs the per-step
    spec engine."""
    cfg, model, prompt, params = _build(rng, n_rows=1, prompt_len=4)
    ref = list(np.asarray(
        generate(model, params, prompt[:1], max_new_tokens=12)
    )[0])
    eos_idx = next(i for i in range(2, 7) if ref[i] not in ref[:i])
    eos = int(ref[eos_idx])
    short = [int(t) for t in np.asarray(prompt[0])]
    long = [int(t) for t in np.asarray(
        jax.random.randint(jax.random.fold_in(rng, 3), (12,), 1,
                           cfg.vocab_size)
    )]

    def drive(steps):
        eng = ServingEngine(
            model, params, n_slots=2,
            scheduler=SchedulerConfig(max_prefills_per_tick=2),
            prefill_buckets=(4, 8, 16), prefill_chunk_tokens=4,
            draft_tokens=3, decode_steps_per_tick=steps,
        )
        a = eng.add_request(_req(short, 12, eos_token_id=eos))
        eng.step()
        b = eng.add_request(_req(long, 5))
        eng.run()
        return a, b

    a1, b1 = drive(1)
    a4, b4 = drive(4)
    assert a1.finish_reason == a4.finish_reason == "eos"
    assert a4.tokens == a1.tokens == ref[: eos_idx + 1]
    assert b4.tokens == b1.tokens and b1.status == FINISHED


def test_unified_tick_compile_count_pin(rng):
    """Jit compile-count pin: the unified fn compiles ONCE (its chunk
    and state shapes are fixed by (n_slots, chunk_tokens, seq_len)), so
    a mixed chunked workload adds the ONE unified program on top of the
    fused-tick family — the compile-shape family stays O(#buckets + 1)."""
    from tpu_parallel.serving import engine as engine_mod

    engine_mod._engine_fns.cache_clear()
    engine_mod._fused_engine_fn.cache_clear()
    engine_mod._unified_engine_fn.cache_clear()
    cfg, model, _, params = _build(rng)
    eng = ServingEngine(
        model, params, n_slots=4,
        scheduler=SchedulerConfig(max_prefills_per_tick=2),
        prefill_buckets=(4, 8, 16), prefill_chunk_tokens=4,
        decode_steps_per_tick=8,
    )
    if not hasattr(eng._unified_fn, "_cache_size"):
        pytest.skip("jax.jit cache inspection unavailable")
    lengths = [3, 5, 9, 11, 14, 6, 13]
    for i, L in enumerate(lengths):
        p = [int(t) for t in np.asarray(
            jax.random.randint(
                jax.random.fold_in(rng, 80 + i), (L,), 1, cfg.vocab_size
            )
        )]
        eng.add_request(_req(p, 2 + (i % 5)))
        if i % 2:
            eng.step()
    eng.run()
    assert eng.metrics.finished == len(lengths)
    assert eng._unified_fn._cache_size() == 1  # ONE unified program, ever
    assert eng._fused_fn._cache_size() == 1


def _sequential(eng):
    """The loop ``step()`` replaced: every tick collected before the next
    is launched."""
    events = []
    while eng.has_work():
        events.extend(eng.collect(eng.launch()))
    return events


PIPELINE_KINDS = dict(
    # the fused tick, whole-prompt prefill in buckets, one prompt a call
    fused=dict(
        prefill_buckets=(8, 16), prefill_batch=1,
        scheduler=SchedulerConfig(max_prefills_per_tick=1),
        decode_steps_per_tick=2,
    ),
    # the unified tick: prompts past the chunk budget ride it in chunks,
    # the short ones prefill in their bucket
    unified=dict(
        prefill_buckets=(4, 8), prefill_chunk_tokens=6,
        scheduler=SchedulerConfig(max_prefills_per_tick=2),
        decode_steps_per_tick=2,
    ),
    per_step=dict(decode_steps_per_tick=1),
)


@pytest.mark.parametrize("kind", list(PIPELINE_KINDS))
def test_step_pipeline_bitwise_and_donation_audit(rng, kind):
    """``step()`` keeps one tick queued on the device: with a queue deeper
    than the slots (admissions, whole-prompt and chunked prefills,
    retirements every few ticks) it launches tick N+1 before it reads
    tick N on nine busy ticks of ten, and every request's greedy tokens
    arrive in the order a ``collect(launch())`` loop gives them; a
    per-step engine never launches ahead.  The donation audit: a launch
    ahead donates the pending tick's state and cache, and nothing reads
    them before collect (a read would raise on the deleted buffer)."""
    cfg, model, _, params = _build(rng)
    lens = [3, 5, 9, 4, 12, 7, 10, 6, 11, 8]
    budgets = [14, 9, 17, 12, 15, 18, 10, 16, 13, 11]
    prompts = [
        [int(t) for t in np.asarray(
            jax.random.randint(
                jax.random.fold_in(rng, 90 + i), (L,), 1, cfg.vocab_size
            )
        )]
        for i, L in enumerate(lens)
    ]

    def serve(drain):
        eng = ServingEngine(model, params, n_slots=2, **PIPELINE_KINDS[kind])
        order = []
        outs = [
            eng.add_request(_req(
                p, n, request_id=f"r{i}",
                on_token=lambda ev: order.append((ev.request_id, ev.index)),
            ))
            for i, (p, n) in enumerate(zip(prompts, budgets))
        ]
        drain(eng)
        assert eng.pool.n_free == 2 and not eng.has_work()
        return eng, outs, order

    seq_eng, seq, seq_order = serve(_sequential)
    eng, piped, order = serve(lambda e: e.run())
    for i, (a, b) in enumerate(zip(seq, piped)):
        assert a.status == FINISHED and b.status == FINISHED
        assert b.tokens == a.tokens, f"request {i}"
    by_request = lambda pairs, rid: [x for x in pairs if x[0] == rid]
    for i in range(len(prompts)):
        assert by_request(order, f"r{i}") == by_request(seq_order, f"r{i}")
    s = eng.metrics.summary()
    assert seq_eng.metrics.summary()["launch_ahead_share"] == 0.0
    if kind == "per_step":
        assert s["launch_ahead_share"] == 0.0
        assert s["launch_ahead_flushes"] == {}
        return
    assert s["launch_ahead_share"] >= 0.9, s
    assert s["overlapped_dispatches"] >= 0.9 * s["busy_ticks"]
    if kind == "unified":
        assert s["prefill_chunks"] > 0 and s["unified_tick_tokens_mean"] > 0
    # the only launches that waited are the last ticks': nothing was
    # certain to be left for a successor
    assert set(s["launch_ahead_flushes"]) == {"draining"}
    assert s["launch_ahead_flushes"]["draining"] <= 2
    # donation audit on a pipelined pair
    eng = ServingEngine(model, params, n_slots=1)
    out = eng.add_request(_req(prompts[0], 28))
    p1 = eng.launch()
    old_state = jax.tree_util.tree_leaves(eng._dev_state)
    old_cache = jax.tree_util.tree_leaves(eng.pool.cache)
    assert eng._ahead_refusal(p1) is None
    p2 = eng.launch(ahead=True)  # donates p1's returned buffers
    assert all(leaf.is_deleted() for leaf in old_state), (
        "launch-ahead did not donate the pending tick's state buffers"
    )
    assert all(leaf.is_deleted() for leaf in old_cache)
    ev1 = eng.collect(p1)  # the first token rides its tick's collect
    ev2 = eng.collect(p2)
    assert len(ev1) - 1 == len(ev2) == eng.decode_steps_per_tick
    eng.run()
    assert out.status == FINISHED and len(out.tokens) == 28


@pytest.mark.parametrize(
    "case", ["length_in_flight", "first_token", "eos", "cancel", "max_wait"]
)
def test_step_pipeline_edges_end_clean(rng, case):
    """What ends a request while a tick is in flight: its budget (the
    surplus tick parks on the device's live mask and the host retires at
    collect), its first token, an EOS inside the tick in flight, a
    ``cancel()`` and a ``max_wait`` expiry.  Each ends with the tokens of
    the sequential loop, every slot free, nothing in flight, and the
    launch that had to wait counted under its cause."""
    cfg, model, prompt, params = _build(rng, n_rows=3)
    refs = [
        [int(t) for t in np.asarray(generate(
            model, params, prompt[i : i + 1], max_new_tokens=12
        ))[0]]
        for i in range(3)
    ]
    now = [0.0]
    eng = ServingEngine(
        model, params, n_slots=2, decode_steps_per_tick=4,
        clock=lambda: now[0],
        scheduler=SchedulerConfig(max_prefills_per_tick=2, max_wait=5.0),
    )
    want = {0: refs[0], 1: refs[1], 2: refs[2]}
    knobs = {0: {}, 1: {}, 2: {}}
    if case == "length_in_flight":
        # 9-token budgets on 4-step ticks: the finish lands mid-pipeline
        want = {i: refs[i][:9] for i in range(3)}
    elif case == "first_token":
        want[1] = refs[1][:1]
    elif case == "eos":
        # stops at the sixth token: inside the second tick, in flight
        # while the third is launched
        knobs[1] = dict(eos_token_id=refs[1][5])
        want[1] = refs[1][: refs[1].index(refs[1][5]) + 1]
    outs = [
        eng.add_request(_req(
            prompt[i], len(want[i]) if i != 1 or case != "eos" else 12,
            request_id=f"r{i}", **knobs[i],
        ))
        for i in range(3)
    ]
    events = eng.step()
    assert eng._pending is not None  # a tick is in flight from here on
    if case == "cancel":
        assert eng.cancel("r0")
        want[0] = list(outs[0].tokens)
    elif case == "max_wait":
        now[0] += 10.0  # r2 still queues behind the two seated
        want[2] = []
    while eng.has_work():
        events.extend(eng.step())
    for i, out in enumerate(outs):
        assert out.tokens == want[i], (case, i)
    assert outs[1].status == FINISHED
    assert outs[1].finish_reason == ("eos" if case == "eos" else "length")
    if case == "cancel":
        assert outs[0].status == "cancelled" and outs[2].status == FINISHED
    if case == "max_wait":
        assert outs[2].status == EXPIRED
        assert [ev.finish_reason for ev in events if ev.token < 0] == [
            "max_wait"
        ]
    assert sum(1 for ev in events if ev.token >= 0) == sum(
        len(out.tokens) for out in outs
    )
    assert eng.pool.n_free == 2 and eng._pending is None
    assert not eng._active.any() and not eng.has_work()
    assert all(out is None for out in eng._slot_out)
    flushes = eng.metrics.summary()["launch_ahead_flushes"]
    assert flushes.get("cancel" if case == "cancel" else "draining", 0) >= 1
    # and the engine serves on, its device state whole
    again = eng.add_request(_req(prompt[2], 12))
    eng.run()
    assert again.tokens == refs[2]


@pytest.mark.slow
def test_spec_engine_wall_clock_with_oracle(rng):
    """Perf (wall-clock — slow lane): with a high-acceptance drafter the
    speculative engine drains the same greedy workload faster than
    one-token-per-tick.  Direction only, generous margin."""
    import time as _time

    cfg, model, _, params = _build(rng, n_rows=8, prompt_len=5)
    n_new = 12
    prompts = [
        [int(t) for t in np.asarray(
            jax.random.randint(
                jax.random.fold_in(rng, i), (5,), 1, cfg.vocab_size
            )
        )]
        for i in range(8)
    ]
    refs = [
        np.asarray(generate(
            model, params, jnp.asarray(p, jnp.int32)[None, :],
            max_new_tokens=n_new,
        ))[0]
        for p in prompts
    ]

    def drive(**kw):
        eng = ServingEngine(
            model, params, n_slots=8,
            scheduler=SchedulerConfig(max_prefills_per_tick=8), **kw,
        )
        for p in prompts:  # warm compiles
            eng.add_request(_req(p, 2))
        eng.run()
        t0 = _time.perf_counter()
        outs = [eng.add_request(_req(p, n_new)) for p in prompts]
        eng.run()
        for out, ref in zip(outs, refs):
            np.testing.assert_array_equal(np.asarray(out.tokens), ref)
        return _time.perf_counter() - t0

    dt_plain = drive()
    dt_spec = drive(
        draft_tokens=4, drafter=OracleDrafter(_ref_map(prompts, refs)),
    )
    assert dt_spec < dt_plain


# -- unified telemetry: lifecycle tracing through the engine ---------------


def test_engine_trace_complete_span_chain_per_request(rng):
    """Acceptance: a mixed burst (bucketed + chunked + speculative) under
    a Tracer yields ONE complete span chain per request — queue ->
    prefill[/chunk] -> decode/verify -> finish — on one track per slot
    plus the scheduler track, and the Chrome export round-trips."""
    import json

    from tpu_parallel.obs import Tracer, write_chrome_trace

    cfg, model, _, params = _build(rng)
    tracer = Tracer()
    eng = ServingEngine(
        model, params, n_slots=2,
        scheduler=SchedulerConfig(max_prefills_per_tick=2),
        tracer=tracer, prefill_chunk_tokens=4, draft_tokens=3,
    )
    prompts = [
        [1, 2, 3, 4, 5, 6, 7, 8, 9, 10],  # > chunk budget: chunked path
        [3, 4, 5],  # bucketed path
        [5, 6, 7, 8],  # joins after a slot frees
    ]
    outs = [eng.add_request(_req(p, 5)) for p in prompts]
    eng.run()
    assert all(out.status == FINISHED for out in outs)

    # the completion clock's spans (device.<kind>) have a track of their own
    assert tracer.tracks() == ["scheduler", "device", "slot 0", "slot 1"]
    for out in outs:
        rid = out.request.request_id
        chain = [
            s.name for s in tracer.spans if s.attrs.get("request_id") == rid
        ]
        assert chain[0] == "queue", chain
        assert any(name.startswith("prefill") for name in chain), chain
        assert any(name in ("decode", "verify") for name in chain), chain
        finishes = [
            ev for ev in tracer.instants
            if ev["attrs"].get("request_id") == rid
        ]
        assert len(finishes) == 1 and finishes[0]["name"] == "finish"
        # span chain is time-ordered within the request
        starts = [
            s.start for s in tracer.spans
            if s.attrs.get("request_id") == rid
        ]
        assert starts == sorted(starts)
    # chunked request: one prefill_chunk span per chunk, indexed
    chunked = [
        s for s in tracer.spans
        if s.name == "prefill_chunk"
        and s.attrs["request_id"] == outs[0].request.request_id
    ]
    assert [s.attrs["chunk"] for s in chunked] == list(range(len(chunked)))
    assert len(chunked) == 3  # 10 tokens / chunk 4 -> 3 chunks
    assert chunked[-1].attrs["final"] is True
    # verify spans carry draft K + acceptance attrs
    verifies = [s for s in tracer.spans if s.name == "verify"]
    assert verifies and all(
        "draft_k" in s.attrs and "accepted" in s.attrs for s in verifies
    )
    # export round-trips (field-level contract pinned in test_obs.py)
    path = write_chrome_trace(tracer, "/tmp/test_engine_trace.json")
    events = json.load(open(path))["traceEvents"]
    assert {e["ph"] for e in events} >= {"M", "X", "i", "b", "e"}


def test_engine_prefix_hit_trace_attrs_and_queue_span(rng):
    """Prefix-cache hits mark their prefill spans cache_hit=True, and a
    request that waits in the queue records a queue span covering the
    wait (fake clock: deterministic widths)."""
    from tpu_parallel.obs import Tracer

    cfg, model, _, params = _build(rng)
    clock = [0.0]

    def fake_clock():
        clock[0] += 0.25
        return clock[0]

    tracer = Tracer(clock=fake_clock)
    # per-step tick: the stall-cause assertions below need pure decode
    # ticks ("none") to exist, which a fused tick folds away
    eng = ServingEngine(
        model, params, n_slots=1, clock=fake_clock,
        prefill_buckets=(8, 16), prefix_cache_size=2, tracer=tracer,
        decode_steps_per_tick=1,
    )
    shared = [7, 3, 5, 2, 9, 4, 6, 1]  # one full bucket: a storable prefix
    outs = [
        eng.add_request(_req(shared + [5, 6], 4)),
        eng.add_request(_req(shared + [8, 2], 4)),
    ]
    eng.run()
    assert all(out.status == FINISHED for out in outs)
    assert eng.metrics.prefix_hits >= 1
    prefills = {
        s.attrs["request_id"]: s for s in tracer.spans if s.name == "prefill"
    }
    assert prefills[outs[0].request.request_id].attrs["cache_hit"] is False
    hit_span = prefills[outs[1].request.request_id]
    assert hit_span.attrs["cache_hit"] is True
    assert hit_span.attrs["prefix_len"] == len(shared)
    # the second request queued behind a 1-slot pool: its queue span is
    # wider than the first's and closed before its prefill began
    queues = {
        s.attrs["request_id"]: s for s in tracer.spans if s.name == "queue"
    }
    q0 = queues[outs[0].request.request_id]
    q1 = queues[outs[1].request.request_id]
    assert q1.end - q1.start > q0.end - q0.start
    assert q1.end <= hit_span.start
    # stall-cause counters cover the run: prefill ticks + decode ticks
    stalls = {
        row["labels"]["cause"]: row["value"]
        for row in eng.registry.snapshot()["counters"]
        if row["name"] == "serving_tick_stall_total"
    }
    assert stalls["prefill"] >= 2 and stalls["none"] >= 1
    # scheduler published queue telemetry into the engine registry
    waits = [
        row for row in eng.registry.snapshot()["histograms"]
        if row["name"] == "serving_queue_wait_seconds"
    ]
    assert waits and waits[0]["count"] == 2


def test_engine_reset_metrics_rewires_scheduler_registry(rng):
    cfg, model, prompt, params = _build(rng, n_rows=1)
    eng = ServingEngine(model, params, n_slots=1)
    assert eng.scheduler.registry is eng.registry
    old_registry = eng.registry
    eng.add_request(_req(prompt[0], 3))
    eng.run()
    fresh = eng.reset_metrics()
    assert fresh is eng.metrics
    assert eng.registry is fresh.registry is not old_registry
    assert eng.scheduler.registry is eng.registry
    assert eng.metrics.ticks == 0
    # the engine still serves correctly after the swap
    out = eng.add_request(_req(prompt[0], 3))
    eng.run()
    assert out.status == FINISHED and eng.metrics.finished == 1


# -- device-side NaN/Inf integrity sentinel ----------------------------------


def _poison(params):
    """Every floating leaf becomes NaN — the corrupted-weights shape
    that would otherwise stream confident garbage."""
    return jax.tree_util.tree_map(
        lambda x: (
            jnp.full_like(x, jnp.nan)
            if jnp.issubdtype(x.dtype, jnp.floating) else x
        ),
        params,
    )


def test_nan_sentinel_fails_request_typed_at_prefill(rng):
    """Non-finite logits at the FIRST sampled token: the request FAILS
    typed ``integrity`` with zero tokens streamed, the slot releases,
    and the trip is counted — never a garbage stream."""
    from tpu_parallel.serving import FAIL_INTEGRITY, FAILED

    cfg, model, prompt, params = _build(rng)
    eng = ServingEngine(
        model, _poison(params), n_slots=2, decode_steps_per_tick=1
    )
    events = []
    out = eng.add_request(_req(prompt[0], 6, on_token=events.append))
    eng.run(max_ticks=20)
    assert out.status == FAILED
    assert out.finish_reason == FAIL_INTEGRITY
    assert out.tokens == []
    assert eng.integrity_trips == 1
    assert eng.metrics.summary()["integrity_trips"] == 1
    assert eng.pool.n_free == eng.pool.n_slots  # slot released
    assert len(events) == 1 and events[0].finished
    assert events[0].finish_reason == FAIL_INTEGRITY
    assert events[0].token == -1  # the sentinel never streams
    assert not eng.has_work()


def test_nan_sentinel_mid_stream_fused_tick(rng):
    """Weights rot AFTER tokens already streamed, under the fused
    multi-step tick: delivery stops at the trip (already-delivered
    tokens stand), the request fails typed, and the pool stays clean."""
    from tpu_parallel.serving import FAIL_INTEGRITY, FAILED

    cfg, model, prompt, params = _build(rng)
    eng = ServingEngine(
        model, params, n_slots=2, decode_steps_per_tick=4
    )
    out = eng.add_request(_req(prompt[0], 12))
    eng.step()
    assert out.status == "running" and len(out.tokens) >= 1
    delivered = list(out.tokens)
    eng.params = _poison(params)  # the rot lands mid-flight
    eng.run(max_ticks=10)
    assert out.status == FAILED
    assert out.finish_reason == FAIL_INTEGRITY
    # nothing after the trip streamed: what stands is what was delivered
    # and the tick that was already on the device with sound weights
    assert out.tokens[: len(delivered)] == delivered
    assert len(out.tokens) == len(delivered) + 4
    assert eng.integrity_trips == 1
    assert eng.pool.n_free == eng.pool.n_slots
    assert not eng.has_work()


def test_nan_sentinel_escalates_replica_to_degraded(rng):
    """The cluster view: a sentinel trip flips the replica HEALTHY ->
    DEGRADED (routers deprioritize it) without killing it — an
    escalation, not a death."""
    from tpu_parallel.cluster.replica import DEGRADED, ReplicaHandle

    cfg, model, prompt, params = _build(rng)
    eng = ServingEngine(
        model, _poison(params), n_slots=2, decode_steps_per_tick=1
    )
    handle = ReplicaHandle(0, eng)
    handle.submit(_req(prompt[0], 4))
    for _ in range(10):
        handle.step()
        if handle.health == DEGRADED:
            break
    assert handle.health == DEGRADED
    assert eng.integrity_trips == 1
    assert handle.open_requests == 0  # the failed request left the ledger


@pytest.mark.parametrize("spec_steps", [1, 2])
def test_nan_sentinel_spec_verify_path(rng, spec_steps):
    """The sentinel covers speculative decoding too — per-step verify
    AND the fused verify scan: weights rotting mid-stream under
    draft-verify ticks fail the request typed instead of delivering an
    argmax-over-NaN token chain."""
    from tpu_parallel.serving import FAIL_INTEGRITY, FAILED

    cfg, model, prompt, params = _build(rng)
    eng = ServingEngine(
        model, params, n_slots=2, draft_tokens=3,
        decode_steps_per_tick=spec_steps,
    )
    out = eng.add_request(_req(prompt[0], 12))
    eng.step()
    assert out.status == "running" and len(out.tokens) >= 1
    delivered = list(out.tokens)
    eng.params = _poison(params)
    eng.run(max_ticks=10)
    assert out.status == FAILED
    assert out.finish_reason == FAIL_INTEGRITY
    assert out.tokens == delivered
    assert eng.integrity_trips == 1
    assert eng.pool.n_free == eng.pool.n_slots
    assert not eng.has_work()


# -- the phase clock ---------------------------------------------------------

PHASES = ("schedule", "prefill", "dispatch", "device_wait", "deliver", "record")
OLD_SUMMARY_KEYS = {
    "cancelled", "decode_ticks", "expired", "finished", "host_dispatches",
    "host_ms_per_tick_p50", "host_ms_per_tick_p95", "launch_ahead_share",
    "launch_ahead_flushes",
    "integrity_trips", "itl_ms_p50", "itl_ms_p95", "kv_block_cow_copies",
    "kv_blocks_free", "kv_blocks_in_use", "kv_bytes_per_active_token",
    "kv_host_blocks_in_use", "kv_host_breaker_state",
    "kv_host_breaker_trips", "kv_host_evictions", "kv_host_offloads",
    "kv_host_restore_failures", "kv_host_restored_blocks",
    "kv_integrity_failures", "overlapped_dispatches", "prefill_calls",
    "prefill_chunks", "prefills", "prefix_entries", "prefix_entry_bytes",
    "prefix_evictions", "prefix_hit_rate", "prefix_hits", "prefix_misses",
    "prefix_shared_blocks", "queue_depth_max", "queue_depth_mean",
    "rejected", "slot_occupancy_mean", "spec_acceptance_rate",
    "spec_wasted_positions", "ticks", "tokens_accepted", "tokens_drafted",
    "tokens_out", "tokens_per_decode_tick", "tokens_per_dispatch_mean",
    "tokens_per_sec", "ttft_ms_p50", "ttft_ms_p95",
    "unified_tick_tokens_mean",
}
NEW_SUMMARY_KEYS = {
    "busy_ticks", "busy_tick_ms_mean", "decode_only_tick_ms_mean",
    "prefill_tick_ms_mean", "host_exposed_share",
    *(f"tick_{name}_ms_mean" for name in PHASES + ("between",)),
}
# what a prefill computed beside the prompt tokens, and a slot's bytes of
# state of one size (PR 32): counters, 0 until something is recorded
PREFILL_SUMMARY_KEYS = {
    "prefill_tokens_real", "prefill_tokens_padded", "state_bytes_per_slot",
}
# the busy ticks whose sampler could draw, and the share that could not
# (PR 33): a counter, and a share that is None until a tick was busy
SAMPLER_SUMMARY_KEYS = {"sampler_draw_ticks", "sampler_skip_share"}
# the decode kernel over the stored stripes (PR 44): None where no program
# uses it (tests/test_ops_decode_attention.py has the engines that do)
ATTENTION_SUMMARY_KEYS = {"decode_tiles_walked_share"}
# the latent attention layers' rows a decode step reads and bytes a position
# (both 0 for a model without one)
LATENT_SUMMARY_KEYS = {"latent_positions_read", "latent_bytes_per_position"}
# the completion clock (PR 41): device time by program, from inside; two
# counts that start at 0 and the split by shape that starts empty, the
# rest None until a program has completed
DEVICE_SUMMARY_KEYS = {
    "device_tick_ms_mean", "device_tick_chunk_ms_mean",
    "device_prefill_ms_mean", "device_prefill_share",
    "device_prefill_ms_per_ktok", "device_idle_share", "device_programs",
    "device_clock_dropped", "device_by_shape",
}


class _SetClock:
    """A clock that moves only when told to (so all of a tick's time falls
    inside the code the test makes slow) and counts its reads: the thread's
    that made it (the pump's), and apart from them every other thread's
    (the completion clock's, one a program, whenever it gets to them)."""

    def __init__(self):
        import threading

        self.t = 0.0
        self.reads = 0
        self.reads_elsewhere = 0
        self._thread, self._me = threading.current_thread, threading.get_ident()

    def __call__(self):
        if self._thread().ident == self._me:
            self.reads += 1
        else:
            self.reads_elsewhere += 1
        return self.t


def _slow(obj, attr, clock, seconds):
    """Make ``obj.attr`` take ``seconds`` on ``clock``."""
    inner = getattr(obj, attr)

    def slowed(*args, **kwargs):
        clock.t += seconds
        return inner(*args, **kwargs)

    setattr(obj, attr, slowed)


def _phase_hists(eng):
    sums, counts = {}, {}
    busy_sum, busy_count = 0.0, 0
    for row in eng.registry.snapshot()["histograms"]:
        if row["name"] == "serving_tick_phase_seconds":
            sums[row["labels"]["phase"]] = row["sum"]
            counts[row["labels"]["phase"]] = row["count"]
        elif row["name"] == "serving_busy_tick_seconds":
            busy_sum += row["sum"]
            busy_count += row["count"]
    return sums, counts, busy_sum, busy_count


@pytest.mark.parametrize("kind", ["fused", "unified", "per_step"])
def test_phases_partition_busy_ticks_and_skip_idle_ones(rng, kind):
    """Every busy tick enters the phase series once, pipelined or not: its
    six phases with what they cost, its period in `busy_tick`, the gap
    before its launch in `between`.  On the per-step engine, where every
    tick is collected before the next is launched, the six phases add up
    to the busy ticks' wall time; on the engines that keep a tick in
    flight the periods tile the run, gaps and all, and what ran beside
    device work is not host-exposed.  Idle ticks and the sleep before a
    burst enter no histogram; a step reads the clock at most 12 times
    beside its per-token stamps and once a program it dispatched (the
    completion clock's second read a program is on its own thread)."""
    cfg, model, prompt, params = _build(rng, n_rows=2, prompt_len=7)
    clock = _SetClock()
    knobs = dict(
        fused={}, unified=dict(prefill_chunk_tokens=4),
        per_step=dict(decode_steps_per_tick=1),
    )[kind]
    eng = ServingEngine(model, params, n_slots=2, clock=clock, **knobs)
    cost = dict(schedule=0.002, prefill=0.003, dispatch=0.005,
                device_wait=0.1, deliver=0.001)
    _slow(eng.scheduler, "schedule", clock, cost["schedule"])
    _slow(eng, "_admit_batch", clock, cost["prefill"])
    _slow(eng, "_launch_decode", clock, cost["dispatch"])
    _slow(eng, "_sync_payload", clock, cost["device_wait"])

    def on_token(event):
        clock.t += cost["deliver"]

    def submit(row):
        eng.add_request(_req(prompt[row], 20, on_token=on_token))

    submit(0)
    submit(1)
    ticks, tokens = 0, 0
    while eng.has_work():
        before = clock.reads
        events = eng.step()
        ticks += 1
        tokens += len(events)
        if events and all(ev.index > 0 for ev in events):  # steady
            assert clock.reads - before - len(events) <= 12 + 1
        clock.t += 0.5  # whatever the engine's owner does between ticks
    assert ticks >= 3
    assert clock.reads_elsewhere <= eng.metrics.host_dispatches
    if kind == "unified":
        assert eng.metrics.summary()["unified_tick_tokens_mean"] is not None

    sums, counts, busy_sum, busy_count = _phase_hists(eng)
    assert busy_count == ticks
    assert {counts[name] for name in PHASES} == {ticks}
    for name in ("schedule", "dispatch", "device_wait"):
        assert sums[name] == pytest.approx(cost[name] * ticks)
    # a first token is delivered by the prefill that made it only where
    # the engine reads it back there; where ticks chain it rides the
    # collect of the tick it was dispatched before
    first = 2 if kind == "per_step" else 0
    assert sums["prefill"] == pytest.approx(
        cost["prefill"] * ticks + cost["deliver"] * first
    )
    assert sums["deliver"] == pytest.approx(
        cost["deliver"] * (tokens - first)
    )
    six = sum(sums[name] for name in PHASES)
    s = eng.metrics.summary()
    exposed = sum(
        s[f"tick_{n}_ms_mean"] for n in ("schedule", "deliver", "record",
                                         "between")
    )
    everything = sum(s[f"tick_{n}_ms_mean"] for n in PHASES + ("between",))
    if kind == "per_step":
        assert s["launch_ahead_share"] == 0.0
        assert six == pytest.approx(busy_sum)
        assert counts["between"] == ticks - 1
        assert sums["between"] == pytest.approx(0.5 * (ticks - 1))
        assert s["host_exposed_share"] == pytest.approx(
            100.0 * exposed / everything, abs=1e-3
        )
    else:
        assert s["overlapped_dispatches"] == ticks - 1
        # a period runs from the previous collect: it holds the owner's
        # gap, which `between` reads at the launch that follows it (the
        # first step launches two ticks, the last one none)
        assert counts["between"] == ticks - 2
        assert sums["between"] == pytest.approx(0.5 * (ticks - 2))
        assert busy_sum == pytest.approx(six + 0.5 * (ticks - 1))
        # exposed: the first tick's launch, the last one's collect
        assert 0.0 < s["host_exposed_share"] < 5.0
        assert s["host_exposed_share"] < 100.0 * exposed / everything

    # idle ticks, then the sleep before the next burst: observed nowhere
    clock.t += 100.0
    for _ in range(3):
        eng.step()
    clock.t += 100.0
    assert _phase_hists(eng) == (sums, counts, busy_sum, busy_count)
    submit(0)
    eng.step()
    sums2, counts2, _, busy_count2 = _phase_hists(eng)
    assert busy_count2 == ticks + 1
    assert counts2["between"] == counts["between"]  # the tick before was idle
    assert sums2["between"] == pytest.approx(sums["between"])
    assert eng.metrics.summary()["busy_ticks"] == ticks + 1
    assert ticks + 1 < eng.metrics.summary()["ticks"]


def test_summary_keeps_every_old_key_and_has_the_phase_clock(rng):
    cfg, model, prompt, params = _build(rng, n_rows=1)
    eng = ServingEngine(model, params, n_slots=1)
    empty = eng.metrics.summary()
    every = (OLD_SUMMARY_KEYS | NEW_SUMMARY_KEYS | PREFILL_SUMMARY_KEYS
             | SAMPLER_SUMMARY_KEYS | DEVICE_SUMMARY_KEYS
             | ATTENTION_SUMMARY_KEYS | LATENT_SUMMARY_KEYS)
    assert set(empty) == every
    assert empty["decode_tiles_walked_share"] is None
    assert all(empty[k] == 0 for k in LATENT_SUMMARY_KEYS)
    counts = {"device_programs", "device_clock_dropped"}
    assert all(empty[k] == 0 for k in counts)
    assert empty["device_by_shape"] == {}
    assert all(
        empty[k] is None
        for k in DEVICE_SUMMARY_KEYS - counts - {"device_by_shape"}
    )
    assert all(empty[k] == 0 for k in PREFILL_SUMMARY_KEYS)
    assert empty["sampler_draw_ticks"] == 0
    assert empty["sampler_skip_share"] is None
    assert empty["busy_ticks"] == 0
    assert all(empty[k] is None for k in NEW_SUMMARY_KEYS - {"busy_ticks"})
    eng.add_request(_req(prompt[0], 12))
    eng.run()
    s = eng.metrics.summary()
    assert set(s) == every
    assert s["prefill_tokens_real"] == len(prompt[0])
    assert s["busy_ticks"] == s["decode_ticks"] >= 2
    # the one admission made the first tick a prefill tick
    assert s["prefill_tick_ms_mean"] > 0 and s["decode_only_tick_ms_mean"] > 0
    assert 0.0 < s["host_exposed_share"] < 100.0
    assert s["tick_between_ms_mean"] is not None


BENCHMARK_READS = (
    # what benchmarks/metrics/engine.*.py read off summary()
    "busy_tick_ms_mean", "tick_device_wait_ms_mean", "tick_deliver_ms_mean",
    "tick_between_ms_mean", "host_exposed_share", "slot_occupancy_mean",
    "tokens_out", "prefill_tick_ms_mean", "decode_only_tick_ms_mean",
    "host_ms_per_tick_p50", "launch_ahead_share", "sampler_skip_share",
)


def test_pipelined_ticks_enter_the_phase_histograms(rng):
    """`step()` collects tick N with tick N+1 queued on the device: such
    ticks count in `overlapped_dispatches` AND in every phase series (a
    pipelined run that left them out would turn the benchmark's readers
    to null), with `busy_tick` as the tick's period, and what ran beside
    device work kept out of `host_exposed_share`."""
    cfg, model, prompt, params = _build(rng, n_rows=3)

    def serve(drain):
        clock = _SetClock()
        eng = ServingEngine(
            model, params, n_slots=2, clock=clock,
            scheduler=SchedulerConfig(max_prefills_per_tick=1),
            decode_steps_per_tick=2,
        )
        _slow(eng, "_sync_payload", clock, 0.1)
        _slow(eng.scheduler, "schedule", clock, 0.01)
        for i in range(3):  # a queue deeper than the slots
            eng.add_request(_req(prompt[i], 20))
        drain(eng)
        return eng.metrics.summary()

    s = serve(lambda eng: eng.run())
    assert s["overlapped_dispatches"] >= 2
    assert s["busy_ticks"] == s["decode_ticks"]
    assert s["launch_ahead_share"] >= 0.8
    for key in BENCHMARK_READS:
        assert s[key] is not None, key
    # a period is a sync and a schedule long; the sync is device time
    assert s["busy_tick_ms_mean"] == pytest.approx(110.0, rel=0.05)
    assert s["tick_device_wait_ms_mean"] == pytest.approx(100.0)
    assert s["host_ms_per_tick_p50"] == pytest.approx(110.0, rel=0.2)
    # the same work, every tick collected before the next launch: the
    # schedule phase is then exposed, and it was hidden above
    seq = serve(_sequential)
    assert seq["launch_ahead_share"] == 0.0
    assert seq["host_exposed_share"] == pytest.approx(100 * 0.01 / 0.11, rel=0.05)
    assert s["host_exposed_share"] < 0.2 * seq["host_exposed_share"]


def test_tick_span_carries_its_phases(rng):
    """One click on a `tick` span answers where the tick went; the phase
    spans nest inside it on the scheduler track under their own names."""
    from tpu_parallel.obs import Tracer

    cfg, model, prompt, params = _build(rng, n_rows=1)
    clock = [0.0]

    def fake_clock():
        clock[0] += 0.25
        return clock[0]

    tracer = Tracer(clock=fake_clock)
    eng = ServingEngine(
        model, params, n_slots=1, clock=fake_clock, tracer=tracer,
    )
    eng.add_request(_req(prompt[0], 26))
    eng.run()
    ticks = [s for s in tracer.spans if s.name == "tick"]
    assert len(ticks) >= 2
    leaves = sorted(
        (s for s in tracer.spans
         if s.track == "scheduler" and s.name.startswith("tick.")
         and s.name != "tick.between"),
        key=lambda s: s.start,
    )
    # leaves: sequential, never overlapping one another, though a tick
    # span now holds its successor's launch between its own two halves
    assert all(a.end <= b.start for a, b in zip(leaves, leaves[1:]))
    assert len(leaves) == len(PHASES) * len(ticks)
    for tick in ticks:
        assert {f"{n}_ms" for n in PHASES} <= set(tick.attrs)
        inside = [
            s for s in leaves if tick.start <= s.start and s.end <= tick.end
        ]
        assert {s.name for s in inside} == {f"tick.{n}" for n in PHASES}
        # its own launch opens it, its own collect closes it
        assert [s.name for s in inside[:3]] == [
            "tick.schedule", "tick.prefill", "tick.dispatch"
        ]
        assert [s.name for s in inside[-3:]] == [
            "tick.device_wait", "tick.deliver", "tick.record"
        ]
    # the decode dispatch's window holds exactly the wait for the device
    decode = [s for s in tracer.spans if s.name == "decode_tick"]
    waits = [s for s in tracer.spans if s.name == "tick.device_wait"]
    assert len(decode) == len(waits) == len(ticks)
    for d, w in zip(decode, waits):
        assert d.start < w.start and d.end == w.end
    # no request-level span name was taken by a phase
    assert not [s for s in tracer.spans
                if s.name in PHASES and s.track == "scheduler"]
    assert [s for s in tracer.spans if s.name == "tick.between"]
