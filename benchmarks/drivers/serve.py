"""Driver of the serving cells: HTTP/SSE traffic through the daemon.

The process that holds the chip builds what ``chip_smoke.py``'s serve phase
builds: a ``ServingEngine`` in the configuration the daemon child uses
(fixed-slot pool, fused tick, prefill buckets, a chunk budget so the unified
tick runs) behind ``Frontend`` -> ``ServingDaemon`` -> ``DaemonHTTPServer`` on
loopback.  Weights are the benchmark's, made on the device from ``--seed`` in
the type they are served in.  The load comes from a child process
(``lib/loadgen.py``) that never imports JAX.

Set-up ends when every program the traffic can reach has run once (one
request for each prefill bucket in use, one chunked prompt, so that bucketed
prefill, the unified tick and the fused decode tick are all compiled) and
the closed loop's clients have filled the slots: the window opens
``ramp_s`` after the clients start, or when the last client's first stream
is attached, whichever is later.  (A stream that attaches late is handed,
in one burst, every token made while it waited; inside the window that
burst would count tokens made before it.)  The window is ``--seconds`` of
the clients' steady state; the cell's file names, under ``statistics``,
which of the client-side statistics each of its end-to-end metrics is.

After the window the daemon drains, the engine and its weights are freed,
and the plain reference reads a seeded sample of the finished streams (the
longest among them): the widest gap by which a served token's fp32 logit
lies below the fp32 best.
"""

import gc
import json
import os
import random
import select
import shutil
import subprocess
import sys
import tempfile
import threading
import time

import jax
import jax.numpy as jnp

from lib import traffic as traffic_lib
from lib import weights, xplane
from reference import gpt2_ref

LOADGEN = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "lib", "loadgen.py",
)
WARMUP_NEW_TOKENS = 12  # more than one fused tick of 8
REFERENCE_PAD = 128  # reference sequences pad to a multiple: few shapes
ATTACH_MARGIN_S = 0.5  # a late stream's backlog arrives at once, before this


def start_load(plan: dict, workdir: str, name: str):
    """Start the load generator on ``plan``: ``(process, result path)``."""
    plan_path = os.path.join(workdir, f"{name}.plan.json")
    result_path = os.path.join(workdir, f"{name}.result.json")
    with open(plan_path, "w") as f:
        json.dump(plan, f)
    env = {k: v for k, v in os.environ.items() if not k.startswith("JAX_")}
    proc = subprocess.Popen(
        [sys.executable, LOADGEN, plan_path, result_path], env=env,
        stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True,
    )
    return proc, result_path


def wait_attached(proc, timeout: float) -> float:
    """The offset at which the last client's first stream was attached."""
    ready, _, _ = select.select([proc.stdout], [], [], timeout)
    line = proc.stdout.readline() if ready else ""
    if not line.startswith("attached "):
        raise RuntimeError(
            f"the clients' first streams were not attached within {timeout:.0f}s"
        )
    return float(line.split()[1])


def finish(proc, result_path, timeout: float) -> list:
    """Wait for the load generator and read its records."""
    try:
        rc = proc.wait(timeout=timeout)
    except subprocess.TimeoutExpired:
        proc.kill()
        proc.wait()
        raise RuntimeError("the load generator outlived its deadline")
    if rc != 0:
        raise RuntimeError(f"the load generator exited with {rc}")
    with open(result_path) as f:
        return json.load(f)["records"]


def annotate(obj, attr: str, label: str) -> None:
    """Wrap ``obj.attr`` in a profiler annotation, from outside the
    program: idle gaps of the device get the host's doing as their name."""
    inner = getattr(obj, attr)

    def wrapped(*args, **kwargs):
        with jax.profiler.TraceAnnotation(label):
            return inner(*args, **kwargs)

    setattr(obj, attr, wrapped)


def report_failures(obj, attr: str, log) -> None:
    """Log what ``obj.attr`` raises before the cluster absorbs it (a dead
    replica otherwise shows only as requests that finished as failed)."""
    import traceback

    inner = getattr(obj, attr)

    def wrapped(*args, **kwargs):
        try:
            return inner(*args, **kwargs)
        except Exception:
            text = traceback.format_exc()
            log(f"{attr} raised:\n{text[:3000]}\n...\n{text[-1500:]}")
            raise

    setattr(obj, attr, wrapped)


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
    from tpu_parallel.serving.engine import default_prefill_buckets
    from tpu_parallel.train_lib import MODEL_REGISTRY

    cell, config, mix = run.cell, run.config, run.traffic
    eng = cell["engine"]
    cfg = MODEL_REGISTRY[config["registry"]](**weights.model_overrides(
        config, remat=False, **eng.get("model_overrides", {})
    ))
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
    vocab = config["model"]["vocab_real"]
    chunk = eng["prefill_chunk_tokens"]
    lo, hi = mix["prompt_tokens"]["min"], mix["prompt_tokens"]["max"]
    ladder = default_prefill_buckets(cfg.seq_len)  # the engine's own ladder
    buckets = sorted({
        min(b for b in ladder if b >= n)
        for n in range(lo, min(hi, chunk) + 1)
    })
    run.log(f"engine: {n_params / 1e6:.1f}M parameters served as "
            f"{eng['served_parameters']}, {eng['n_slots']} slots x "
            f"{cfg.seq_len} positions, prefill buckets {buckets}, chunk "
            f"budget {chunk}")

    engines = []

    def frontend_factory(clock):
        engine = ServingEngine(
            model, params, n_slots=eng["n_slots"],
            scheduler=SchedulerConfig(
                max_prefills_per_tick=eng["max_prefills_per_tick"]
            ),
            prefill_buckets=tuple(buckets),
            prefill_chunk_tokens=chunk,
        )
        engines.append(engine)
        return Frontend(
            [engine], router="least", config=FrontendConfig(restart=None),
            clock=clock, registry=MetricRegistry(),
        )

    workdir = tempfile.mkdtemp(prefix="bench_serve_")
    daemon = ServingDaemon(
        frontend_factory, os.path.join(workdir, "journal.jsonl"),
        config=DaemonConfig(grace_seconds=600.0),
    )
    engine = engines[0]
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
    try:
        # -- set-up: every program the traffic can reach runs once ---------
        rng = random.Random(run.seed ^ 0x5EED)
        lengths = list(buckets) + ([min(hi, 2 * chunk + 1)] if hi > chunk else [])
        warm = [{
            "max_new_tokens": WARMUP_NEW_TOKENS,
            "prompt": [rng.randrange(1, vocab) for _ in range(n)],
        } for n in lengths]
        t_warm = time.perf_counter()
        proc, path = start_load({
            "port": server.port, "t0": time.monotonic(), "requests": warm,
            "clients": len(warm), "drain_timeout_s": 1500,
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
                with jax.profiler.TraceAnnotation("bench_window"):
                    time.sleep(cell["trace_seconds"])
                jax.profiler.stop_trace()

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
            run.device_trace = xplane.reduce_trace(
                xplane.find_trace(logdir),
                annotations=("engine.", "daemon."),
                window_annotation="bench_window",
            )
            shutil.rmtree(logdir, ignore_errors=True)
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
    # latencies: of the requests the window saw from their sending to
    # their last token
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
    kv_token = 2 * cfg.n_layers * cfg.d_model * jnp.dtype(served).itemsize
    work = sum(len(r["tokens"]) for r in ended)
    if work:
        held = sum(
            len(r["tokens"])
            * (len(requests[r["idx"]]["prompt"]) + len(r["tokens"]) / 2)
            for r in ended
        ) / work
        run.log(f"pool: {eng['n_slots']} slots x {cfg.seq_len} positions x "
                f"{kv_token / 1e3:.0f} KB = "
                f"{eng['n_slots'] * cfg.seq_len * kv_token / 1e9:.2f} GB "
                f"reserved; a running stream held {held:.0f} positions on "
                f"average, {100 * held / cfg.seq_len:.0f}% of its slot")
    keys = ("ticks", "decode_ticks", "prefill_calls", "prefill_chunks",
            "slot_occupancy_mean", "queue_depth_mean", "queue_depth_max",
            "host_ms_per_tick_p50", "host_ms_per_tick_p95", "tokens_out")
    run.log("engine counters: " + ", ".join(
        f"{k} {run.counters.get(k)}" for k in keys
    ))
    run.check("failed_requests", run.failed, 0)

    # -- the reference, once the engine and its weights are freed -----------
    engines.clear()
    del engine, daemon, server, params, frontend_factory
    gc.collect()
    t_ref = time.perf_counter()
    compare(run, ended, requests, abstract, cfg.n_heads, served)
    run.log(f"reference and comparison: {time.perf_counter() - t_ref:.1f}s")


def compare(run, done, requests, abstract, n_heads, served) -> None:
    if not done:
        run.check("streams_compared", 1, 0)
        return
    rng = random.Random(run.seed ^ 0xC0FFEE)
    size = lambda r: len(requests[r["idx"]]["prompt"]) + len(r["tokens"])
    longest = max(done, key=size)
    rest = [r for r in done if r is not longest]
    sample = [longest] + rng.sample(
        rest, min(len(rest), run.cell["reference_streams"] - 1)
    )
    ref_weights = weights.to_reference(
        weights.make_params(run.seed, abstract, dtype=served), n_heads
    )
    worst, near_ties, count = 0.0, 0, 0
    ctl_worst = 0.0
    for r in sorted(sample, key=size):
        prompt = requests[r["idx"]]["prompt"]
        seq = prompt + r["tokens"]
        padded = min(
            -(-len(seq) // REFERENCE_PAD) * REFERENCE_PAD,
            ref_weights["wpe"].shape[0],
        )
        toks = jnp.asarray([seq + [0] * (padded - len(seq))], jnp.int32)
        rows = slice(len(prompt) - 1, len(seq) - 1)
        logits = gpt2_ref.forward_layerwise(ref_weights, toks)[0, rows]
        got = jnp.asarray(r["tokens"], jnp.int32)
        best = jnp.max(logits, axis=-1)
        gaps = best - jnp.take_along_axis(logits, got[:, None], axis=-1)[:, 0]
        worst = max(worst, float(jnp.max(gaps)))
        near_ties += int(jnp.sum(gaps > 0))
        count += len(r["tokens"])
        if run.control:
            low = gpt2_ref.forward_layerwise(
                ref_weights, toks, run.cell["control_precision"]
            )[0, rows]
            pick = jnp.argmax(low, axis=-1)
            cgaps = best - jnp.take_along_axis(logits, pick[:, None], axis=-1)[:, 0]
            ctl_worst = max(ctl_worst, float(jnp.max(cgaps)))
    run.log(f"reference: {len(sample)} streams, {count} served tokens "
            f"(longest {size(longest)} positions); {near_ties} tokens are "
            f"not the fp32 best; widest gap {worst:.6g}")
    run.check("served_logit_gap", worst, run.cell["limits"]["served_logit_gap"])
    if run.control:
        run.log(f"control {run.cell['control_precision']}: served_logit_gap="
                f"{ctl_worst:.6g}")
        run.facts["control"] = {"served_logit_gap": ctl_worst}
