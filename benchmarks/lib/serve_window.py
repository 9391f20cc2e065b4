"""The window of a serving cell, for any family of model: what a driver of
HTTP/SSE traffic through the daemon does whatever it serves.

``run(run, family)`` builds a ``ServingEngine`` (fixed-slot pool, fused tick,
whole-prompt prefill in the cell's buckets) behind ``Frontend`` ->
``ServingDaemon`` -> ``DaemonHTTPServer`` on loopback, warms every program the
traffic can reach (one request a prefill bucket in use), starts the closed
loop of clients in a child process (``lib/loadgen.py``) that never imports
JAX, opens the window ``ramp_s`` after the clients start or once the last
client's first stream is attached, whichever is later, traces
``trace_seconds`` in its middle under ``--trace 1``, closes it, drains, reads
what the clients saw and, once the engine and its weights are freed, hands the
streams that ended in the window to the family's comparison.

What belongs to a family of model is ``family``, an object with

- ``name``: a word for the working directory;
- ``build(run) -> built``: the model and its weights, as a namespace with
  ``model``, ``params``, ``cfg`` (its ``seq_len`` is a slot's positions) and
  ``vocab`` (ids are drawn under it), and whatever its ``compare`` needs;
- ``engine_built(run, engine)``: log what a slot holds; put its own probes on
  the engine, from outside;
- ``window(run, opened)``: told when the window opens (True) and closes;
- ``traced(run, trace_file)``: read what its metrics need from the raw trace,
  before the file goes;
- ``closed(run, engine, built)``: the facts its readers need from the engine,
  before the engine is freed;
- ``counter_keys``: the counters of ``summary()`` worth a line in the log;
- ``compare(run, ended, requests, built)``: ``correct``, from the records of
  the streams that ended in the window.

``drivers/serve.py`` and ``drivers/serve_moe.py`` hold older copies of this
window; folding them onto it is a ``benchmark`` PR's (they are files that are
there).
"""

import gc
import os
import random
import shutil
import tempfile
import threading
import time
import weakref

import jax

from drivers.serve import (
    ATTACH_MARGIN_S,
    WARMUP_NEW_TOKENS,
    annotate,
    finish,
    report_failures,
    start_load,
    wait_attached,
)
from lib import traffic as traffic_lib
from lib import xplane


def run(run, family) -> None:
    from tpu_parallel.cluster import Frontend, FrontendConfig
    from tpu_parallel.daemon import (
        EXIT_CLEAN,
        DaemonConfig,
        DaemonHTTPServer,
        ServingDaemon,
    )
    from tpu_parallel.obs.registry import MetricRegistry
    from tpu_parallel.serving import SchedulerConfig, ServingEngine

    cell, mix = run.cell, run.traffic
    eng = cell["engine"]
    built = family.build(run)
    cfg, vocab = built.cfg, built.vocab
    n_params = sum(x.size for x in jax.tree.leaves(built.params))
    n_bytes = sum(x.nbytes for x in jax.tree.leaves(built.params))
    lo, hi = mix["prompt_tokens"]["min"], mix["prompt_tokens"]["max"]
    ladder = [b for b in eng["prefill_buckets"] if b < cfg.seq_len]
    ladder.append(cfg.seq_len)  # the engine's own last bucket
    buckets = sorted({
        min(b for b in ladder if b >= n) for n in range(lo, hi + 1)
    })
    run.log(f"engine: {n_params} parameters ({n_bytes / 1e9:.2f} GB) "
            f"served as {eng['served_parameters']}, {eng['n_slots']} slots x "
            f"{cfg.seq_len} positions, whole-prompt prefill in buckets "
            f"{buckets}, {eng['prefill_batch']} row(s) a call, at most "
            f"{eng['max_prefills_per_tick']} admissions a tick")

    engines = []

    def frontend_factory(clock):
        engine = ServingEngine(
            built.model, built.params, n_slots=eng["n_slots"],
            scheduler=SchedulerConfig(
                max_prefills_per_tick=eng["max_prefills_per_tick"]
            ),
            prefill_buckets=tuple(buckets),
            prefill_batch=eng["prefill_batch"],
        )
        engines.append(engine)
        return Frontend(
            [engine], router="least", config=FrontendConfig(restart=None),
            clock=clock, registry=MetricRegistry(),
        )

    workdir = tempfile.mkdtemp(prefix=f"bench_{family.name}_")
    daemon = ServingDaemon(
        frontend_factory, os.path.join(workdir, "journal.jsonl"),
        config=DaemonConfig(grace_seconds=600.0),
    )
    engine = engines[0]
    family.engine_built(run, engine)
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
        family.window(run, True)
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
        family.window(run, False)
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
            said = family.traced(run, trace_file)
            shutil.rmtree(logdir, ignore_errors=True)
            run.log(f"trace reduced in {time.perf_counter() - t_red:.1f}s; {said}")
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
    family.closed(run, engine, built)

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
    run.log("engine counters: " + ", ".join(
        f"{k} {run.counters.get(k)}" for k in family.counter_keys
    ))
    run.check("failed_requests", run.failed, 0)

    # -- the reference, once the engine and its weights are freed -----------
    engines.clear()
    built.params = None
    gone = weakref.ref(engine)
    del engine, daemon, server, frontend_factory
    device = jax.local_devices()[0]
    wait_freed(run, gone, device)
    before = device.memory_stats() or {}
    t_ref = time.perf_counter()
    family.compare(run, ended, requests, built)
    run.log(f"reference and comparison: {time.perf_counter() - t_ref:.1f}s")
    # what the comparison held: the runtime's peak is a process's lifetime
    # peak and the engine's stands in it, so a family that samples its own
    # (and states a bound) leaves both under run.facts["comparison_memory"]
    after = device.memory_stats() or {}
    memory = run.facts.setdefault("comparison_memory", {})
    memory.update(
        bytes_limit=after.get("bytes_limit", 0),
        in_use_before=before.get("bytes_in_use", 0),
        lifetime_peak_before=before.get("peak_bytes_in_use", 0),
        lifetime_peak_after=after.get("peak_bytes_in_use", 0),
    )
    gb = lambda key: (f"{memory[key] / 1e9:.2f} GB" if memory.get(key)
                      else "not read")
    run.log(f"comparison memory: sampled peak {gb('sampled_peak_bytes')}, "
            f"bound {gb('bound_bytes')}, limit {gb('bytes_limit')}; "
            f"{gb('in_use_before')} in use when it began; the process's "
            f"peak {gb('lifetime_peak_before')} before it and "
            f"{gb('lifetime_peak_after')} after")


def wait_freed(run, gone, device, patience_s: float = 60.0) -> None:
    """Collect until the engine (``gone``, a weak reference) is no more, and
    say what the device still holds.  One collection is not always enough: a
    handler thread of the HTTP server that is still parked on its stream's
    queue (a keep-alive period, 2 s) holds the daemon and with it the engine,
    weights and pool, and the comparison then starts on a chip that is three
    quarters full and runs out of memory (PR 43)."""
    t0 = time.perf_counter()
    rounds, holders = 0, []
    while True:
        gc.collect()
        rounds += 1
        if gone() is None or time.perf_counter() - t0 > patience_s:
            break
        if not holders:
            holders = sorted(
                t.name for t in threading.enumerate()
                if t is not threading.current_thread()
            )
        time.sleep(0.25)
    stats = device.memory_stats() or {}
    run.log(f"engine {'freed' if gone() is None else 'STILL REFERENCED'} after "
            f"{rounds} collection(s) in {time.perf_counter() - t0:.2f}s: "
            f"{stats.get('bytes_in_use', 0) / 1e9:.2f} GB in use"
            + (f"; threads alive after the first: {holders}" if holders else ""))
