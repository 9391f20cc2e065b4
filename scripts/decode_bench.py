"""Serving throughput: tokens/sec for KV-cache decoding.

Prints one JSON line per (batch, new_tokens) point.  Not part of the driver
contract — perf evidence for the generation path (prefill + lax.scan decode,
last-position lm_head, int8-cache variant, speculative draft-verify).
``--model`` names what is served (the tiny test config by default,
``gpt2_125m`` for a chip) — the backend never picks it — and every record
names where it ran (``platform``, ``device_kind``, ``device_count``).

Usage:
  python scripts/decode_bench.py [--reps N] [--warmup N]
      [batch,prompt,new[,kv_cache_dtype]] ...
  python scripts/decode_bench.py --spec [--draft-k K1,K2,...] [combos ...]
  python scripts/decode_bench.py --engine [--fused-tick T1,T2,...] [combos]
  python scripts/decode_bench.py beam [batch prompt new num_beams]
  (every form takes --model tiny|gpt2_125m)

``--engine`` measures the SERVING ENGINE's decode hot loop across the
``--fused-tick`` sweep (decode_steps_per_tick 1,4,8,16 by default): T=1
is the per-step tick paying one host dispatch + one sync per token, T>1
the fused lax.scan tick paying them once per T tokens.  Greedy output is
parity-asserted against static generate() for every T; records carry the
engine's dispatch metrics (tokens_per_dispatch, host_ms_per_tick).  The
default engine combos sweep batch 1 (the 14x dispatch-tax case) and the
batch-32 int8-vs-bf16 pair (the int8-native attention read's crossover).

Defaults exercise batch 8/32 at prompt 512, 128 new tokens, bf16 + int8
cache.  ``--reps``/``--warmup`` control the timing loop (previously
hard-coded at 3 reps / 1 warmup call).

``--spec`` measures speculative decoding (``serving/spec_decode.py``) on a
REPETITIVE prompt (a short pattern tiled to the prompt length — the
workload shape prompt-lookup drafting wins on): for each combo it times
the engine-style per-token host loop (``draft_tokens=0`` — the honest
non-spec baseline: the serving engine dispatches per tick and cannot use
``generate()``'s fused scan), then the draft-verify loop across the
``--draft-k`` sweep, asserting greedy token parity between every pair and
reporting acceptance rate, tokens/tick, and the speedup.  The fused-scan
``generate()`` time rides along for reference.

Beam mode: times lazy vs eager beam search against the aligned-greedy
floor at the same effective rows (defaults 2 x 512 + 128, 4 beams).
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from tpu_parallel.utils.profiling import run_identity


def _build(model_name, kv_dtype="bf16", **tiny_overrides):
    """The model the caller named: full-width GPT-2 125M, or the tiny test
    config with the workload's own window/positional overrides."""
    from tpu_parallel.models import GPTLM, gpt2_125m, tiny_test

    if model_name == "gpt2_125m":
        cfg = gpt2_125m(
            dropout_rate=0.0, remat=False, scan_layers=True,
            kv_cache_dtype=kv_dtype,
        )
    else:
        cfg = tiny_test(kv_cache_dtype=kv_dtype, **tiny_overrides)
    return GPTLM(cfg), cfg


def run_one(batch, prompt_len, new_tokens, kv_dtype="bf16", reps=3, warmup=1,
            model_name="tiny"):
    from tpu_parallel.models.generate import generate

    model, cfg = _build(model_name, kv_dtype)
    # clamp BOTH knobs to the model's window (the tiny model has seq_len
    # 32, far below the default combos)
    new_tokens = min(new_tokens, cfg.seq_len // 2)
    prompt_len = max(1, min(prompt_len, cfg.seq_len - new_tokens))
    prompt = jax.random.randint(
        jax.random.PRNGKey(0), (batch, prompt_len), 0, cfg.vocab_size
    )
    params = model.init({"params": jax.random.PRNGKey(1)}, prompt, train=False)[
        "params"
    ]

    def timed(n_new):
        # warmup (compile + extra reps), then time; finish on a
        # device->host read, so the region ends on a value the host holds
        for _ in range(max(warmup, 1)):
            out = generate(model, params, prompt, max_new_tokens=n_new)
        jax.device_get(out[0, -1])
        t0 = time.perf_counter()
        for _ in range(reps):
            out = generate(model, params, prompt, max_new_tokens=n_new)
        out.block_until_ready()
        jax.device_get(out[0, -1])
        return (time.perf_counter() - t0) / reps

    dt_full = timed(new_tokens)
    dt_prefill = timed(1)  # prefill + a single sample
    decode_dt = max(dt_full - dt_prefill, 1e-9)  # the scan's share
    return dict(
        batch=batch,
        prompt=prompt_len,
        new_tokens=new_tokens,
        kv_cache=kv_dtype,
        **run_identity(cfg),
        e2e_tokens_per_sec=round(batch * new_tokens / dt_full, 1),
        decode_tokens_per_sec=round(batch * (new_tokens - 1) / decode_dt, 1),
        decode_ms_per_step=round(decode_dt / (new_tokens - 1) * 1000, 3),
        prefill_ms=round(dt_prefill * 1000, 2),
    )


def run_spec(batch, prompt_len, new_tokens, kv_dtype="bf16", ks=(2, 4, 8),
             reps=3, warmup=1, model_name="tiny"):
    """Speculative vs per-token host-loop decode on a repetitive prompt;
    one JSON line per point.  Parity-asserted: every variant must produce
    the same greedy tokens."""
    import numpy as np

    from tpu_parallel.models.generate import generate
    from tpu_parallel.serving.spec_decode import generate_speculative

    # the tiny stand-in is tuned for the workload under test: a longer
    # window than the 32-token test default (cycles need decode length to
    # form and amortize) and the RoPE/RMSNorm variant, whose untrained
    # greedy continuations actually lock onto the prompt's repetition (the
    # learned-positions tiny model wanders chaotically — ~0.35 acceptance
    # vs ~0.8 here — which starves any drafter)
    model, cfg = _build(
        model_name, kv_dtype, seq_len=256, positional="rope", norm="rmsnorm"
    )
    new_tokens = min(new_tokens, cfg.seq_len // 2)
    prompt_len = max(1, min(prompt_len, cfg.seq_len - new_tokens))
    # repetitive prompt: a short random pattern tiled to length — the
    # prompt-lookup drafter's home turf (greedy continuations of a cycle)
    period = 16 if model_name == "gpt2_125m" else 4
    pattern = jax.random.randint(
        jax.random.PRNGKey(0), (batch, period), 0, cfg.vocab_size
    )
    prompt = jnp.tile(pattern, (1, prompt_len // period + 1))[:, :prompt_len]
    params = model.init({"params": jax.random.PRNGKey(1)}, prompt, train=False)[
        "params"
    ]

    def timed(fn):
        for _ in range(max(warmup, 1)):
            out = fn()
        jax.device_get(jax.tree_util.tree_leaves(out)[0].ravel()[-1])
        t0 = time.perf_counter()
        for _ in range(reps):
            out = fn()
        jax.device_get(jax.tree_util.tree_leaves(out)[0].ravel()[-1])
        return (time.perf_counter() - t0) / reps, out

    # fused-scan generate: the static-batch reference (no host dispatch
    # at all — the engine can't use it, but it bounds what decode costs)
    dt_scan, ref = timed(
        lambda: generate(model, params, prompt, max_new_tokens=new_tokens)
    )
    ref = np.asarray(ref)

    def spec(k):
        return generate_speculative(
            model, params, prompt, max_new_tokens=new_tokens, draft_tokens=k,
        )

    # prefill share of the host loop (prefill + first sample, zero ticks)
    dt_pre, _ = timed(
        lambda: generate_speculative(
            model, params, prompt, max_new_tokens=1, draft_tokens=0,
        )
    )
    dt_step, base = timed(lambda: spec(0))
    assert np.array_equal(np.asarray(base), ref), "stepwise != scan tokens"
    base_decode = max(dt_step - dt_pre, 1e-9)
    record = dict(
        bench="spec_decode",
        batch=batch,
        prompt=prompt_len,
        new_tokens=new_tokens,
        kv_cache=kv_dtype,
        **run_identity(cfg),
        pattern_period=period,
        scan_decode_tokens_per_sec=round(
            batch * (new_tokens - 1) / max(dt_scan - dt_pre, 1e-9), 1
        ),
        stepwise_decode_tokens_per_sec=round(
            batch * (new_tokens - 1) / base_decode, 1
        ),
    )
    for k in ks:
        # stats come from the FIRST (untimed, compiling) call — the loop
        # is deterministic, so re-running purely for stats would double
        # the sweep's wall-clock for nothing
        toks, stats = generate_speculative(
            model, params, prompt, max_new_tokens=new_tokens, draft_tokens=k,
            return_stats=True,
        )
        assert np.array_equal(np.asarray(toks), ref), f"spec K={k} mismatch"
        dt_k, _ = timed(lambda k=k: spec(k))
        k_decode = max(dt_k - dt_pre, 1e-9)
        record[f"spec_k{k}_decode_tokens_per_sec"] = round(
            batch * (new_tokens - 1) / k_decode, 1
        )
        record[f"spec_k{k}_speedup_vs_stepwise"] = round(
            base_decode / k_decode, 3
        )
        record[f"spec_k{k}_acceptance_rate"] = stats["acceptance_rate"]
        record[f"spec_k{k}_tokens_per_tick"] = stats["tokens_per_tick"]
    return record


def run_engine(batch, prompt_len, new_tokens, kv_dtype="bf16",
               ticks=(1, 4, 8, 16), reps=3, warmup=1, chunk=0,
               model_name="tiny"):
    """ENGINE-mode decode throughput: the ServingEngine's decode hot loop
    across the ``--fused-tick`` sweep — T=1 is the per-step tick (one
    host dispatch + sync per token), T>1 the fused lax.scan tick with donated cache +
    slot state.  One JSON record per T, parity-asserted against static
    ``generate()`` (greedy bitwise), carrying the engine's own dispatch
    metrics (tokens_per_dispatch, dispatches_per_tick, host_ms_per_tick)
    so the record shows WHERE the speedup comes from, not just that it
    happened.

    ``chunk`` > 0 runs chunked prefill, which at T>1 rides the UNIFIED
    ragged tick (chunk advance + decode in one dispatch — T=1 keeps the
    per-phase alternating engine as the comparison row); the record's
    ``launch_ahead_share`` is the share of busy ticks that ``step()``
    launched with their predecessor still on the device (0 at T=1)."""
    import numpy as np

    from tpu_parallel.models.generate import generate
    from tpu_parallel.serving import Request, SchedulerConfig, ServingEngine

    # the tiny stand-in gets a real decode window: seq 256 gives the
    # cache-read side enough weight that the int8-native read's bandwidth
    # story is visible (the 32-token test default is all fixed overhead)
    model, cfg = _build(model_name, kv_dtype, seq_len=256)
    new_tokens = min(new_tokens, cfg.seq_len // 2)
    prompt_len = max(1, min(prompt_len, cfg.seq_len - new_tokens))
    prompt = jax.random.randint(
        jax.random.PRNGKey(0), (batch, prompt_len), 0, cfg.vocab_size
    )
    params = model.init({"params": jax.random.PRNGKey(1)}, prompt, train=False)[
        "params"
    ]
    refs = np.asarray(
        generate(model, params, prompt, max_new_tokens=new_tokens)
    )
    prompts = [[int(t) for t in np.asarray(prompt[i])] for i in range(batch)]

    for steps in ticks:
        # ONE long-lived engine per T (a server doesn't rebuild its pool
        # per request): the timed window is submission + drain only
        eng = ServingEngine(
            model, params, n_slots=batch,
            scheduler=SchedulerConfig(max_prefills_per_tick=batch),
            decode_steps_per_tick=steps,
            prefill_chunk_tokens=chunk or None,
        )

        def run_once(n_new):
            outs = [
                eng.add_request(Request(prompt=p, max_new_tokens=n_new))
                for p in prompts
            ]
            eng.run()
            return outs

        for _ in range(max(warmup, 1)):
            outs = run_once(new_tokens)
        for i, out in enumerate(outs):
            assert out.status == "finished" and list(out.tokens) == [
                int(t) for t in refs[i]
            ], f"engine T={steps} greedy mismatch on row {i}"
        eng.reset_metrics()
        t0 = time.perf_counter()
        for _ in range(reps):
            run_once(new_tokens)
        dt_full = (time.perf_counter() - t0) / reps
        s = eng.metrics.summary()
        run_once(1)  # warm the prefill-only shape set
        t0 = time.perf_counter()
        for _ in range(reps):
            run_once(1)
        dt_pre = (time.perf_counter() - t0) / reps
        decode_dt = max(dt_full - dt_pre, 1e-9)
        print(json.dumps(dict(
            bench="engine_decode",
            batch=batch,
            prompt=prompt_len,
            new_tokens=new_tokens,
            kv_cache=kv_dtype,
            **run_identity(cfg),
            decode_steps_per_tick=steps,
            prefill_chunk_tokens=chunk or None,
            unified_tick=eng.unified_tick,
            engine_decode_tokens_per_sec=round(
                batch * (new_tokens - 1) / decode_dt, 1
            ),
            tokens_per_decode_tick=s["tokens_per_decode_tick"],
            tokens_per_dispatch=s["tokens_per_dispatch_mean"],
            # metrics accumulate over the `reps` timed runs; report the
            # PER-RUN dispatch count so records compare across --reps
            host_dispatches=round(s["host_dispatches"] / reps),
            dispatches_per_tick=round(
                s["host_dispatches"] / max(s["ticks"], 1), 3
            ),
            dispatches_per_token=round(
                s["host_dispatches"] / max(s["tokens_out"], 1), 4
            ),
            launch_ahead_share=s["launch_ahead_share"],
            unified_tick_tokens_mean=s["unified_tick_tokens_mean"],
            host_ms_per_tick_p50=s["host_ms_per_tick_p50"],
        )), flush=True)


def run_beam(batch=2, prompt_len=512, new_tokens=128, num_beams=4,
             model_name="tiny"):
    """Lazy vs eager beam search vs the aligned-greedy floor at the same
    effective rows (batch * num_beams) — one JSON line per variant."""
    from tpu_parallel.models.generate import generate, generate_beam

    model, cfg = _build(model_name)
    new_tokens = min(new_tokens, cfg.seq_len // 2)
    prompt_len = max(1, min(prompt_len, cfg.seq_len - new_tokens))
    prompt = jax.random.randint(
        jax.random.PRNGKey(0), (batch, prompt_len), 0, cfg.vocab_size
    )
    params = model.init({"params": jax.random.PRNGKey(1)}, prompt, train=False)[
        "params"
    ]
    rows = batch * num_beams
    flat_prompt = jnp.repeat(prompt, num_beams, axis=0)

    def timed(fn, reps=3):
        out = fn()
        jax.device_get(jax.tree_util.tree_leaves(out)[0].ravel()[-1])
        t0 = time.perf_counter()
        for _ in range(reps):
            out = fn()
        jax.device_get(jax.tree_util.tree_leaves(out)[0].ravel()[-1])
        return (time.perf_counter() - t0) / reps

    dt_greedy = timed(
        lambda: generate(model, params, flat_prompt, max_new_tokens=new_tokens)
    )
    results = dict(greedy_rows_ms=round(dt_greedy * 1000, 1))
    for name, lazy in (("lazy", True), ("eager", False)):
        dt = timed(
            lambda lazy=lazy: generate_beam(
                model, params, prompt, max_new_tokens=new_tokens,
                num_beams=num_beams, lazy=lazy,
            )
        )
        results[f"beam_{name}_ms"] = round(dt * 1000, 1)
        results[f"beam_{name}_vs_greedy_per_row"] = round(dt / dt_greedy, 3)
    results.update(
        batch=batch, num_beams=num_beams, rows=rows, prompt=prompt_len,
        new_tokens=new_tokens, **run_identity(cfg),
    )
    print(json.dumps(results), flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("combos", nargs="*",
                    help="batch,prompt,new[,kv_cache_dtype] points (after "
                         "a leading 'beam': batch prompt new num_beams)")
    ap.add_argument("--model", choices=("tiny", "gpt2_125m"),
                    default="tiny",
                    help="the model served: the tiny test config or "
                         "full-width GPT-2 125M (a chip)")
    ap.add_argument("--reps", type=int, default=3,
                    help="timed repetitions per point")
    ap.add_argument("--warmup", type=int, default=1,
                    help="untimed warmup calls per point (>=1: the first "
                         "call compiles)")
    ap.add_argument("--spec", action="store_true",
                    help="speculative-decode sweep on repetitive prompts")
    ap.add_argument("--draft-k", type=str, default="2,4,8",
                    help="draft lengths the --spec sweep measures")
    ap.add_argument("--engine", action="store_true",
                    help="ServingEngine decode hot loop across the "
                         "--fused-tick sweep (parity-asserted; records "
                         "dispatch-amortization metrics)")
    ap.add_argument("--fused-tick", type=str, default="1,4,8,16",
                    help="decode_steps_per_tick values the --engine "
                         "sweep measures (1 = the per-step tick)")
    ap.add_argument("--chunk", type=int, default=0,
                    help="--engine: prefill_chunk_tokens (0 = off); at "
                         "T>1 chunked prompts ride the UNIFIED ragged "
                         "tick, at T=1 the per-phase alternating engine")
    args = ap.parse_args()
    if args.combos[:1] == ["beam"]:
        run_beam(*(int(a) for a in args.combos[1:]), model_name=args.model)
        return

    combos = []
    for arg in args.combos:
        parts = arg.split(",")
        combos.append(
            (int(parts[0]), int(parts[1]), int(parts[2]),
             parts[3] if len(parts) > 3 else "bf16")
        )
    if not combos:
        if args.spec:
            combos = [(8, 512, 128, "bf16")]
        elif args.engine:
            # the batch-1 dispatch-amortization curve + the batch-32
            # int8-vs-bf16 crossover the int8-native read closes
            combos = [
                (1, 32, 64, "bf16"),
                (8, 32, 64, "bf16"),
                (32, 32, 64, "bf16"),
                (32, 32, 64, "int8"),
            ]
        else:
            combos = [
                (8, 512, 128, "bf16"),
                (32, 512, 128, "bf16"),
                (32, 512, 128, "int8"),
            ]
    ks = tuple(int(k) for k in args.draft_k.split(","))
    fused_ticks = tuple(int(t) for t in args.fused_tick.split(","))
    for combo in combos:
        try:
            if args.spec:
                record = run_spec(*combo, ks=ks, reps=args.reps,
                                  warmup=args.warmup, model_name=args.model)
            elif args.engine:
                run_engine(*combo, ticks=fused_ticks, reps=args.reps,
                           warmup=args.warmup, chunk=args.chunk,
                           model_name=args.model)
                continue  # run_engine prints one record per T itself
            else:
                record = run_one(*combo, reps=args.reps, warmup=args.warmup,
                                 model_name=args.model)
            print(json.dumps(record), flush=True)
        except Exception as e:  # OOM etc — report and continue
            print(
                json.dumps(dict(combo=list(combo), error=repr(e)[:200])),
                flush=True,
            )


if __name__ == "__main__":
    main()
