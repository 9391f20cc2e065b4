"""Driver of the serving cells of parallel-block expert decoders: HTTP/SSE
traffic through the daemon, as ``drivers/serve.py`` does for GPT-2, with this
family's model and this family's reference.

The same path: a ``ServingEngine`` (fixed-slot pool, fused tick of 8) behind
``Frontend`` -> ``ServingDaemon`` -> ``DaemonHTTPServer`` on loopback, weights
made on the device from ``--seed`` in the type they are served in, load from a
child process (``lib/loadgen.py``) that never imports JAX.  What differs:

- the model is built from the configuration file's own keys (the published
  ``config.json`` keys, as cut to this chip's share): layer kinds from
  ``layer_types``, experts held ``[0, num_experts)`` of the published count;
- prompts are prefilled WHOLE, one a call, padded to a bucket of the cell's
  ladder, with attention through the flash kernels (``prefill_flash``): the
  unified tick pads every slot to the chunk width, which at 32 slots times a
  useful chunk is most of a second, and reading a prompt's scores back from
  an 8192-long stripe needs a ``[16, T, 8192]`` fp32 block;
- the pool's size is logged from K/V heads and ``head_dim``;
- the reference is ``reference/cohere2_moe_ref.py``, given the same share,
  its layers made one at a time.

``correct``: after the engine and its weights are freed, the longest stream
that ended in the window (it has to pass the window layers' window, so that
window and full layers differ) and a seeded sample of the rest go through the
reference.  Two numbers, each with its limit: ``served_off_best_share``, the
share (%) of served tokens that are not the reference's best, and
``served_logit_gap``, the widest gap of a served token's fp32 logit under the
fp32 best over the vocabulary slice.  The share is what a loss of precision
moves; the widest gap is set by the rare token whose top-k expert set differs
between the bfloat16 path and the float32 reference (a router score near the
k-th place), so its limit catches a broken layer and not a rounding.
``--control 1`` also
reads the float8 control and what a changed expert set does by itself (the
reference with its router alone in bfloat16): how often the top-k set flips,
and how far the logits move when it does.
"""

import gc
import os
import random
import shutil
import tempfile
import threading
import time

import jax
import jax.numpy as jnp

from drivers.serve import (
    ATTACH_MARGIN_S,
    WARMUP_NEW_TOKENS,
    annotate,
    finish,
    report_failures,
    start_load,
    wait_attached,
)
from lib import cohere2_weights, moe_cost
from lib import traffic as traffic_lib
from lib import weights, xplane, xplane_scopes
from reference import cohere2_moe_ref

REFERENCE_PAD = 2048  # reference sequences pad to a multiple: four shapes
# device time is read by scope; the grouped matmuls' custom calls carry no
# scope and are found by their op name
MOE_OPS = r"moe\.|ragged-dot"
SCOPES = (MOE_OPS, r"moe\.router", r"moe\.experts", r"moe\.shared",
          r"attn\.window", r"attn\.full", r"ragged-dot")


def model_config(config: dict, engine: dict):
    """The program's ``GPTConfig`` for a configuration file of this family
    (its top level holds the published keys as run here)."""
    from tpu_parallel.models.gpt import parallel_experts_decoder
    from tpu_parallel.models.layers import ExpertsSpec

    depth = config["num_hidden_layers"]
    kinds = config["layer_types"][:depth]
    period = kinds.index("full_attention") + 1
    if kinds != (["sliding_attention"] * (period - 1) + ["full_attention"]) * (
        depth // period
    ):
        raise ValueError(f"layer_types {kinds} is not a repeated period")
    experts = ExpertsSpec(
        n_experts=config["published"]["num_experts"],
        top_k=config["num_experts_per_tok"],
        width=config["intermediate_size"],
        score=config["expert_selection_fn"],
        shared=config["num_shared_experts"],
        held=(0, config["num_experts"]),
    )
    return parallel_experts_decoder(
        window=config["sliding_window"],
        experts=experts,
        window_layers=period - 1,
        vocab_size=config["vocab_size"],
        d_model=config["hidden_size"],
        n_layers=depth,
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        seq_len=engine["slot_positions"],
        rope_theta=float(config["rope_theta"]),
        norm_eps=config["layer_norm_eps"],
        logit_scale=float(config["logit_scale"]),
        dtype=getattr(jnp, config["precision"]["compute"]),
        remat=False,
        prefill_flash=True,
        **engine.get("model_overrides", {}),
    )


def reference_shape(config: dict) -> dict:
    return {
        "layer_types": config["layer_types"][:config["num_hidden_layers"]],
        "sliding_window": config["sliding_window"],
        "rope_theta": float(config["rope_theta"]),
        "num_experts_per_tok": config["num_experts_per_tok"],
        "held": (0, config["num_experts"]),
        "eps": config["layer_norm_eps"],
        "logit_scale": float(config["logit_scale"]),
    }


def run(run) -> None:
    from tpu_parallel.cluster import Frontend, FrontendConfig
    from tpu_parallel.daemon import (
        EXIT_CLEAN,
        DaemonConfig,
        DaemonHTTPServer,
        ServingDaemon,
    )
    from tpu_parallel.models import GPTLM
    from tpu_parallel.obs.registry import MetricRegistry
    from tpu_parallel.serving import SchedulerConfig, ServingEngine

    cell, config, mix = run.cell, run.config, run.traffic
    eng = cell["engine"]
    cfg = model_config(config, eng)
    model = GPTLM(cfg)
    abstract = jax.eval_shape(
        lambda: model.init(
            {"params": jax.random.PRNGKey(0)}, jnp.zeros((1, 16), jnp.int32),
            train=False,
        )
    )["params"]
    served = getattr(jnp, eng["served_parameters"])
    params = weights.make_params(run.seed, abstract, dtype=served)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    vocab = config["vocab_size"]  # ids are drawn from the rows held here
    lo, hi = mix["prompt_tokens"]["min"], mix["prompt_tokens"]["max"]
    ladder = [b for b in eng["prefill_buckets"] if b < cfg.seq_len]
    ladder.append(cfg.seq_len)  # the engine's own last bucket
    buckets = sorted({
        min(b for b in ladder if b >= n) for n in range(lo, hi + 1)
    })
    n_kv = cfg.n_kv_heads or cfg.n_heads
    kv_token = 2 * cfg.n_layers * n_kv * cfg.head_dim * jnp.dtype(cfg.dtype).itemsize
    run.log(f"engine: {n_params / 1e6:.1f}M parameters served as "
            f"{eng['served_parameters']}, {eng['n_slots']} slots x "
            f"{cfg.seq_len} positions x {kv_token} B = "
            f"{eng['n_slots'] * cfg.seq_len * kv_token / 1e9:.2f} GB of pool "
            f"({n_kv} K/V head(s) of {cfg.head_dim}, {cfg.n_layers} layers), "
            f"whole-prompt prefill in buckets {buckets}")

    engines = []

    def frontend_factory(clock):
        engine = ServingEngine(
            model, params, n_slots=eng["n_slots"],
            scheduler=SchedulerConfig(
                max_prefills_per_tick=eng["max_prefills_per_tick"]
            ),
            prefill_buckets=tuple(buckets),
            prefill_batch=1,
        )
        engines.append(engine)
        return Frontend(
            [engine], router="least", config=FrontendConfig(restart=None),
            clock=clock, registry=MetricRegistry(),
        )

    workdir = tempfile.mkdtemp(prefix="bench_serve_moe_")
    daemon = ServingDaemon(
        frontend_factory, os.path.join(workdir, "journal.jsonl"),
        config=DaemonConfig(grace_seconds=600.0),
    )
    engine = engines[0]
    run.log(f"moe_plan: {engine.moe_plan}")
    report_failures(engine, "step", run.log)
    stats = jax.local_devices()[0].memory_stats() or {}
    run.log(f"engine built: {stats.get('bytes_in_use', 0) / 1e9:.2f} GB in use "
            f"of {stats.get('bytes_limit', 0) / 1e9:.2f} GB")
    if run.trace:
        annotate(engine, "launch", "engine.launch")
        annotate(engine, "collect", "engine.collect")
        annotate(daemon, "submit", "daemon.submit")
    server = DaemonHTTPServer(daemon).start()
    exit_codes = []
    pump = threading.Thread(
        target=lambda: exit_codes.append(daemon.run()), daemon=True
    )
    pump.start()
    arrivals = mix["arrivals"]
    children = []
    scopes = None
    try:
        # -- set-up: every program the traffic can reach runs once ---------
        rng = random.Random(run.seed ^ 0x5EED)
        lengths = [min(b, hi) for b in buckets]
        warm = [{
            "max_new_tokens": WARMUP_NEW_TOKENS,
            "prompt": [rng.randrange(1, vocab) for _ in range(n)],
        } for n in lengths]
        t_warm = time.perf_counter()
        proc, path = start_load({
            "port": server.port, "t0": time.monotonic(), "requests": warm,
            "clients": 2, "drain_timeout_s": 1500,
            "io_timeout_s": 1500, "tag": f"warm{run.seed}",
        }, workdir, "warm")
        children.append(proc)
        records = finish(proc, path, 1600)
        bad = [r["error"] for r in records if not r["ok"]]
        if bad:
            raise RuntimeError(f"warm-up requests failed: {bad}")
        run.log(f"warm-up: {len(warm)} requests (prompts {lengths}) in "
                f"{time.perf_counter() - t_warm:.1f}s; prefill shapes "
                f"{sorted(engine._prefill_shapes)}")

        requests = traffic_lib.make_requests(mix, run.seed, vocab, cfg.seq_len)
        t0 = time.monotonic() + 0.3
        proc, path = start_load({
            "port": server.port, "t0": t0, "requests": requests,
            "clients": arrivals["clients"], "tag": f"w{run.seed}",
            "drain_timeout_s": cell["drain_timeout_s"],
        }, workdir, "window")
        children.append(proc)
        attached = wait_attached(proc, arrivals["ramp_max_s"])
        lo_t = max(arrivals["ramp_s"], attached + ATTACH_MARGIN_S)
        hi_t = lo_t + run.seconds
        time.sleep(max(0.0, t0 + lo_t - time.monotonic()))

        # -- the window -----------------------------------------------------
        run.values["setup_s"] = time.perf_counter() - run.t_process
        engine.reset_metrics()
        run.compiles.active = True
        run.log(f"window open {lo_t:.2f}s after the clients started (last "
                f"first stream attached at {attached:.2f}s): set-up took "
                f"{run.values['setup_s']:.1f}s")
        tracer_thread = None
        if run.trace and run.seconds > 0:
            logdir = os.path.join(run.root, ".bench_trace", run.name)
            shutil.rmtree(logdir, ignore_errors=True)

            def traced():
                time.sleep(max(0.0, run.seconds / 2 - cell["trace_seconds"] / 2))
                jax.profiler.start_trace(logdir)
                before = engine.metrics.summary()
                with jax.profiler.TraceAnnotation("bench_window"):
                    time.sleep(cell["trace_seconds"])
                after = engine.metrics.summary()
                jax.profiler.stop_trace()
                # what the expert counters gained while the trace ran
                run.facts["traced_experts"] = moe_cost.counters_between(
                    before, after
                )

            tracer_thread = threading.Thread(target=traced, daemon=True)
            tracer_thread.start()
        time.sleep(max(0.0, t0 + hi_t - time.monotonic()))
        run.counters = dict(engine.metrics.summary())
        run.compiles.active = False
        run.log("window closed")
        try:
            proc.stdin.write("stop\n")
            proc.stdin.flush()
        except OSError:
            raise RuntimeError(
                "the load generator ended before the window closed: the "
                "pool of requests ran out (raise pool_per_client) or it failed"
            )
        records = finish(proc, path, cell["drain_timeout_s"] + 60)
        if tracer_thread is not None:
            tracer_thread.join()
            t_red = time.perf_counter()
            trace_file = xplane.find_trace(logdir)
            run.device_trace = xplane.reduce_trace(
                trace_file, annotations=("engine.", "daemon."),
                window_annotation="bench_window",
            )
            scopes = xplane_scopes.by_pattern(trace_file, SCOPES)
            shutil.rmtree(logdir, ignore_errors=True)
            run.log(f"trace reduced in {time.perf_counter() - t_red:.1f}s; "
                    f"device time by scope: {scopes}")
        daemon.request_drain()
        pump.join(timeout=600)
    finally:
        for child in children:  # no process outlives the run
            if child.poll() is None:
                child.kill()
                child.wait()
        server.stop()
    run.read_memory()
    run.log(f"runtime memory counters: {jax.local_devices()[0].memory_stats()}")
    if exit_codes != [EXIT_CLEAN]:
        run.log(f"daemon exit codes {exit_codes} (clean is {EXIT_CLEAN})")
    shutil.rmtree(workdir, ignore_errors=True)
    run.facts["scopes"] = scopes
    run.facts["experts"] = {
        "d_model": cfg.d_model, "width": config["intermediate_size"],
        "bytes_per_value": jnp.dtype(served).itemsize,
    }

    # -- what the clients saw ------------------------------------------------
    for r in records:
        want = requests[r["idx"]]["max_new_tokens"]
        if r["ok"] and len(r["tokens"]) != want:
            r["ok"], r["error"] = False, f"{len(r['tokens'])} of {want} tokens"
    failures = [r for r in records if not r["ok"] and not r["cancelled"]]
    run.attempted, run.failed = len(records), len(failures)
    for r in failures[:5]:
        run.log(f"request {r['idx']} failed: {r['error']}")
    in_window = sum(
        1 for r in records for t in r["token_s"] if lo_t <= t < hi_t
    )
    inside = [r for r in records if r["ok"] and r["sent_s"] >= lo_t
              and r["token_s"][-1] < hi_t]
    ended = [r for r in records if r["ok"] and lo_t <= r["token_s"][-1] < hi_t]
    run.samples["submit_s"] = [
        r["submit_s"] for r in records
        if "submit_s" in r and lo_t <= r["sent_s"] < hi_t
    ]
    run.samples["ttft_s"] = [r["token_s"][0] - r["sent_s"] for r in inside]
    run.samples["tpot_s"] = [
        (r["token_s"][-1] - r["token_s"][0]) / (len(r["tokens"]) - 1)
        for r in inside if len(r["tokens"]) > 1
    ]
    pct = traffic_lib.percentile
    stats = {"out_tok_s": in_window / run.seconds if run.seconds > 0 else 0.0}
    for name in ("ttft", "tpot", "submit"):
        for q in (50, 95):
            if run.samples[f"{name}_s"]:
                stats[f"{name}_p{q}_ms"] = 1e3 * pct(run.samples[f"{name}_s"], q)
    for metric, statistic in cell["statistics"].items():
        run.values[metric] = stats[statistic]
    run.log(f"closed loop: {len(records)} sent, "
            f"{sum(r['ok'] for r in records)} finished ({len(ended)} of them "
            f"in the window, {len(inside)} sent and finished in it), "
            f"{sum(r['cancelled'] for r in records)} cancelled at the end, "
            f"{run.failed} failed; {in_window} tokens reached the clients in "
            f"the window, the engine counted {run.counters.get('tokens_out')}")
    run.log("client side: " + ", ".join(
        f"{k} {v:.2f}" for k, v in sorted(stats.items())
    ))
    keys = ("ticks", "decode_ticks", "prefill_calls", "slot_occupancy_mean",
            "queue_depth_mean", "busy_tick_ms_mean",
            "tick_device_wait_ms_mean", "tick_prefill_ms_mean", "tokens_out",
            "moe_calls", "moe_assignments_total", "moe_assignments_held",
            "moe_experts_touched_mean", "moe_rows_per_expert_max_over_mean")
    run.log("engine counters: " + ", ".join(
        f"{k} {run.counters.get(k)}" for k in keys
    ))
    run.check("failed_requests", run.failed, 0)

    # -- the reference, once the engine and its weights are freed -----------
    engines.clear()
    del engine, daemon, server, params, frontend_factory
    gc.collect()
    t_ref = time.perf_counter()
    compare(run, ended, requests, abstract, cfg, served)
    run.log(f"reference and comparison: {time.perf_counter() - t_ref:.1f}s")


def compare(run, done, requests, abstract, cfg, served) -> None:
    if not done:
        run.check("streams_compared", 1, 0)
        return
    shape = reference_shape(run.config)
    n_kv = cfg.n_kv_heads or cfg.n_heads
    rng = random.Random(run.seed ^ 0xC0FFEE)
    size = lambda r: len(requests[r["idx"]]["prompt"]) + len(r["tokens"])
    longest = max(done, key=size)
    rest = [r for r in done if r is not longest]
    sample = [longest] + rng.sample(
        rest, min(len(rest), run.cell["reference_streams"] - 1)
    )

    sample.sort(key=size)
    sequences, rows, served_tokens = [], [], []
    for r in sample:
        prompt = requests[r["idx"]]["prompt"]
        seq = prompt + r["tokens"]
        padded = min(-(-len(seq) // REFERENCE_PAD) * REFERENCE_PAD, cfg.seq_len)
        sequences.append(jnp.asarray(seq + [0] * (padded - len(seq)), jnp.int32))
        rows.append(slice(len(prompt) - 1, len(seq) - 1))
        served_tokens.append(jnp.asarray(r["tokens"], jnp.int32))

    def reference(**kw):
        """Every sampled stream through the reference, each layer's weights
        made once (from the seed, in the served type, upcast) for all."""
        ref_weights = cohere2_weights.to_reference(
            run.seed, abstract, cfg.n_heads, n_kv, dtype=served
        )
        return cohere2_moe_ref.forward_each(
            ref_weights, sequences, shape, rows=rows, **kw
        )

    def gap_of(full, pick):
        return jnp.max(full, axis=-1) - jnp.take_along_axis(
            full, pick[:, None], axis=-1
        )[:, 0]

    t0 = time.perf_counter()
    results = reference(with_routing=run.control)
    jax.block_until_ready(results)
    run.log(f"reference: {len(sequences)} sequences of "
            f"{[len(s) for s in sequences]} positions (padded) in "
            f"{time.perf_counter() - t0:.1f}s")
    logits = [r[0] for r in results] if run.control else results
    gaps = [gap_of(l, t) for l, t in zip(logits, served_tokens)]
    worst = max(float(jnp.max(g)) for g in gaps)
    count = sum(len(t) for t in served_tokens)
    off_best = sum(int(jnp.sum(g > 0)) for g in gaps)
    off_share = 100.0 * off_best / count
    ctl_worst, ctl_share, flip_worst, flips, routings = 0.0, 0.0, 0.0, 0, 0
    if run.control:
        low = reference(precision=run.cell["control_precision"])
        ctl_gaps = [
            gap_of(l, jnp.argmax(c, axis=-1)) for l, c in zip(logits, low)
        ]
        ctl_worst = max(float(jnp.max(g)) for g in ctl_gaps)
        ctl_share = 100.0 * sum(int(jnp.sum(g > 0)) for g in ctl_gaps) / count
        # a changed expert set by itself: the router alone in bfloat16
        rerouted = reference(route_precision="bfloat16", with_routing=True)
        for (full, chosen), (moved, other), seq_rows in zip(
            results, rerouted, rows
        ):
            flip_worst = max(flip_worst, float(jnp.max(jnp.abs(moved - full))))
            n = seq_rows.stop + 1  # the sequence's real length
            for a, b in zip(chosen, other):
                flips += int(jnp.sum(jnp.any(a[:n] != b[:n], axis=-1)))
                routings += n
    run.log(f"reference: {len(sample)} streams, {count} served tokens "
            f"(longest {size(longest)} positions); {off_best} tokens are "
            f"not the fp32 best; widest gap {worst:.6g}")
    run.check("longest_stream_passes_window",
              shape["sliding_window"] + 1, size(longest))
    limits = run.cell["limits"]
    run.check("served_logit_gap", worst, limits["served_logit_gap"])
    run.check("served_off_best_share", off_share, limits["served_off_best_share"])
    if run.control:
        run.log(f"control {run.cell['control_precision']}: served_logit_gap="
                f"{ctl_worst:.6g} served_off_best_share={ctl_share:.6g}")
        run.log(f"router alone in bfloat16: {flips} of {routings} routings "
                f"({100.0 * flips / max(routings, 1):.3f}%) change their "
                f"top-{shape['num_experts_per_tok']} set; the logits of the "
                f"served rows move by at most {flip_worst:.6g}")
        run.facts["control"] = {
            "served_logit_gap": ctl_worst, "served_off_best_share": ctl_share,
            "flip_share": flips / max(routings, 1),
            "flip_logit_move": flip_worst,
        }
