"""The quickest proof that the system still starts on the chip.

    python chip_smoke.py [--seed N]          one TPU chip: train + serve
    python chip_smoke.py --multichip         four chips: the mesh legs only

Drives both main paths once through their normal entry points at the full
published width of ``gpt2_125m`` (vocab 50304, d_model 768, 12 heads, seq
1024, bf16; full depth on one chip), with random weights and tokens made
from ``--seed`` — no network, no checkpoint, no data file:

- **train**: the ``Trainer`` that ``train.py`` builds from
  ``configs/gpt2_125m_dp.py`` (flash attention, ``proj_attn`` remat), a few
  steps of the plain ``train()`` loop on a repeated batch.  Checked: first
  loss near ln(vocab), loss finite and falling, the Pallas kernel is IN the
  step (``tpu_custom_call`` in its lowered text — not interpreted), and the
  first-step loss agrees with ``attn_impl="xla"`` on the same batch and
  parameters.
- **serve**: a ``ServingEngine`` behind ``Frontend`` -> ``ServingDaemon`` ->
  ``DaemonHTTPServer`` on loopback in THIS process; HTTP submits across
  several prefill buckets with more requests than slots, one SSE stream
  read to its terminal event, then drain.  Checked: every request's tokens
  equal ``generate()``'s greedy continuation on the same chip (or, at a
  divergence, the near-tie rule below), zero replica deaths / restarts /
  retries / failed requests, exit code 0, journal closed clean.
- **--multichip** (four chips, nothing else runs): DP-4, FSDP x TP 2x2,
  PP x DP 2x2 and ring-SP x DP 2x2 against the same model on ONE chip of
  the four, at full width with depth cut to 4 scanned layers.  Checked:
  per-step loss against the reference, four distinct devices in the mesh,
  every chip holding a shard of the parameters and of the batch.

Any failed check or exception exits non-zero; ``"ok": true`` is printed only
as the last line of a run in which every phase passed, with the device as
JAX reports it.  One process: nothing here starts a child.
"""

import argparse
import dataclasses
import itertools
import json
import math
import os
import sys
import tempfile
import threading
import time
import urllib.request

# 64 rows in one pass (the shipped eight-chip shape, 8 rows a chip) does not
# fit one 16 GB v5e; 4 accumulation passes of 16 rows do (CHANGES.md, PR 22)
TRAIN_MINIBATCHES = 4
TRAIN_STEPS = 5
# random-init cross-entropy sits above ln(vocab) by about half the logit
# variance: the lecun-normal head over a layer-normed stream gives logits of
# unit variance, so ln(50304) + 0.5 = 11.3 is expected (11.35 on the CPU)
FIRST_LOSS_TOL = 0.7
# flash vs xla attention, same batch and parameters, bf16 activations: the
# loss is an fp32 mean over 65k tokens, so the kernels' rounding averages out
FLASH_XLA_LOSS_TOL = 0.01

SERVE_SLOTS = 2
SERVE_NEW_TOKENS = 16
SERVE_CHUNK_TOKENS = 64
# prompt lengths: 12 and 40 prefill in buckets 32 and 64 of the engine's
# default ladder; the 100-token prompts exceed the chunk budget, so their
# prefill rides the unified tick in two chunks.  Five requests over two slots
# forces queueing and slot reuse.
SERVE_PROMPT_LENS = (12, 40, 100, 12, 100)
# Two differently fused bf16 programs may round a near-tie apart.  A token
# that differs from generate()'s is accepted only if the fp32 model, fed the
# same prefix, puts the two candidates within this many logit units: two
# ulps of bf16 at the magnitude of a winning logit (4..8, ulp 2^-5) — the
# model emits bf16 logits, so closer candidates are ties it cannot order.
NEAR_TIE_LOGIT_GAP = 0.0625

MULTICHIP_LAYERS = 4
MULTICHIP_BATCH = 16
MULTICHIP_STEPS = 4
# legs whose parameters are replicated draw the reference's exact init, so
# only bf16 reduction order (and Adam's sign sensitivity to it) separates the
# losses: 0.005 at the fourth step on four v5e (CHANGES.md, PR 22)
TIGHT_LOSS_TOL = 0.02
# TP and PP fold the init rng over their mesh axis (each shard / stage draws
# its own slice), so "same seed" is a second draw of the same initialiser,
# not the same weights: the trajectories agree statistically, not bitwise
# (0.018 and 0.007 measured)
LOOSE_LOSS_TOL = 0.05


class SmokeFailure(AssertionError):
    pass


def log(msg):
    print(msg, flush=True)


def check(ok, msg):
    if not ok:
        raise SmokeFailure(msg)
    log(f"  ok: {msg}")


class CacheCounter:
    """Counts persistent-compile-cache hits and misses via jax.monitoring."""

    def __init__(self):
        import jax

        self.hits = self.misses = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


def peak_hbm():
    """The runtime's own high-water mark on chip 0, next to its limit."""
    import jax

    stats = jax.devices()[0].memory_stats()
    return (f"peak_bytes_in_use {stats['peak_bytes_in_use'] / 1e9:.2f} GB of "
            f"bytes_limit {stats['bytes_limit'] / 1e9:.2f} GB")


def per_step(running_means):
    """Per-step values from the running means ``Trainer.train`` logs (its
    metrics accumulate over the run; every step counts the same tokens)."""
    out, prev = [], 0.0
    for k, mean in enumerate(running_means, 1):
        out.append(k * mean - prev)
        prev = k * mean
    return out


def run_trainer(trainer, batch, steps):
    """``steps`` of the plain ``train()`` loop on a repeated batch; returns
    the per-step losses."""
    means = []
    trainer.train(
        batch_iter=itertools.repeat(batch), steps=steps,
        log_fn=lambda step, m: means.append(m["loss"]),
    )
    losses = per_step(means)
    check(
        len(losses) == steps and all(math.isfinite(x) for x in losses),
        f"{steps} finite losses: {[round(x, 4) for x in losses]}",
    )
    return losses


# -- train --------------------------------------------------------------------


def smoke_config(seed, attn_impl):
    from configs.gpt2_125m_dp import get_config

    cd = get_config()
    cd.seed = seed
    cd.steps = TRAIN_STEPS
    cd.log_every = 1
    cd.num_minibatches = TRAIN_MINIBATCHES
    cd.model_overrides.attn_impl = attn_impl
    return cd


def train_phase(seed):
    import jax
    import optax

    from tpu_parallel.core import compute
    from tpu_parallel.data import lm_batch
    from train import build_trainer

    log("== train: Trainer(configs/gpt2_125m_dp.py), flash + proj_attn, bf16")
    trainer, _ = build_trainer(smoke_config(seed, "flash"))
    cfg = trainer.model_config
    check(
        (cfg.vocab_size, cfg.d_model, cfg.n_layers, cfg.n_heads, cfg.seq_len)
        == (50304, 768, 12, 12, 1024)
        and cfg.attn_impl == "flash" and cfg.remat_policy == "proj_attn",
        f"full gpt2_125m shape, {trainer.num_params / 1e6:.1f}M parameters",
    )
    rows = trainer.config.global_batch_size
    log(f"  global batch {rows} rows = {TRAIN_MINIBATCHES} passes of "
        f"{rows // TRAIN_MINIBATCHES}")
    batch = lm_batch(
        jax.random.PRNGKey(seed), rows, cfg.seq_len, cfg.vocab_size
    )
    trainer.init()
    param_norm = float(optax.global_norm(trainer.state.params))

    t0 = time.perf_counter()
    lowered = trainer.funcs.step_fn.lower(trainer.state, None, batch)
    kernels = lowered.as_text().count("tpu_custom_call")
    check(
        kernels > 0,
        f"{kernels} tpu_custom_call sites in the lowered step (the flash "
        "kernels are compiled, not interpreted)",
    )
    mem = lowered.compile().memory_analysis()
    log(f"  step compiled in {time.perf_counter() - t0:.1f}s; memory_analysis: "
        f"temp {mem.temp_size_in_bytes / 1e9:.2f} GB, arguments "
        f"{mem.argument_size_in_bytes / 1e9:.2f} GB, output "
        f"{mem.output_size_in_bytes / 1e9:.2f} GB")

    t0 = time.perf_counter()
    losses = run_trainer(trainer, batch, TRAIN_STEPS)
    log(f"  {TRAIN_STEPS} steps (both step programs compiled) in "
        f"{time.perf_counter() - t0:.1f}s; {peak_hbm()}")
    check(
        abs(losses[0] - math.log(cfg.vocab_size)) < FIRST_LOSS_TOL,
        f"first loss {losses[0]:.4f} within {FIRST_LOSS_TOL} of ln(vocab) "
        f"{math.log(cfg.vocab_size):.4f}",
    )
    check(
        losses[-1] < losses[0] - 0.01 and max(losses) <= losses[0] + 0.01,
        f"loss falls on the repeated batch: {losses[0]:.4f} -> {losses[-1]:.4f}",
    )
    trainer.state = None  # free the chip for the comparison trainer
    del trainer

    log("== train: the same first step with attn_impl='xla'")
    t0 = time.perf_counter()
    ref, _ = build_trainer(smoke_config(seed, "xla"))
    ref.init()
    check(
        float(optax.global_norm(ref.state.params)) == param_norm,
        f"same initial parameters (global norm {param_norm:.6f})",
    )
    _, metrics = ref.funcs.step_fn(ref.state, None, batch)
    xla_loss = compute(metrics)["loss"]
    log(f"  xla step compiled and run in {time.perf_counter() - t0:.1f}s")
    check(
        abs(xla_loss - losses[0]) < FLASH_XLA_LOSS_TOL,
        f"first-step loss flash {losses[0]:.5f} vs xla {xla_loss:.5f} "
        f"(tolerance {FLASH_XLA_LOSS_TOL})",
    )
    ref.state = None


# -- serve --------------------------------------------------------------------


def http_json(port, method, path, body=None):
    data = None if body is None else json.dumps(body).encode()
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}", data=data, method=method
    )
    if data is not None:
        req.add_header("Content-Type", "application/json")
    with urllib.request.urlopen(req, timeout=600) as resp:
        return json.loads(resp.read() or b"{}")


def read_sse(port, rid):
    """Read one request's event stream to its terminal event."""
    with urllib.request.urlopen(
        f"http://127.0.0.1:{port}/v1/stream/{rid}", timeout=600
    ) as resp:
        payload = resp.read()
    return [
        json.loads(line[len(b"data: "):])
        for line in payload.split(b"\n")
        if line.startswith(b"data: ")
    ]


def fp32_logits(model, params, tokens):
    """Teacher-forced logits of the fp32 twin of ``model`` over ``tokens``."""
    import jax
    import jax.numpy as jnp

    twin = type(model)(dataclasses.replace(model.config, dtype=jnp.float32))
    with jax.default_matmul_precision("float32"):
        logits = jax.jit(
            lambda p, t: twin.apply({"params": p}, t, train=False)
        )(params, jnp.asarray(tokens, jnp.int32)[None, :])
    return jax.device_get(logits[0].astype(jnp.float32))


def compare_tokens(name, model, params, prompt, got, want):
    """The serving invariant for one request: ``got`` (the engine's tokens)
    equals ``want`` (``generate()``'s), or the first difference is a near-tie
    of the fp32 model and the engine's remaining tokens stay within the same
    bound of the fp32 argmax along the engine's own prefix."""
    logits = fp32_logits(model, params, list(prompt) + list(got))
    # logits[p] scores the token at position p + 1
    rows = logits[len(prompt) - 1: len(prompt) - 1 + len(got)]
    gaps = [float(row.max() - row[tok]) for row, tok in zip(rows, got)]
    log(f"  {name}: prompt {len(prompt)} tokens, {len(got)} new; largest fp32 "
        f"gap between the fp32 argmax and the served token {max(gaps):.4f}")
    if list(got) == list(want):
        return True
    step = next(i for i, (a, b) in enumerate(zip(got, want)) if a != b)
    gap = abs(float(rows[step][got[step]] - rows[step][want[step]]))
    log(f"  {name}: DIFFERS from generate() at step {step}: engine "
        f"{got[step]} vs generate {want[step]}, fp32 logit gap {gap:.5f}; "
        f"later gaps {[round(g, 4) for g in gaps[step + 1:]]}")
    check(
        gap < NEAR_TIE_LOGIT_GAP
        and all(g < NEAR_TIE_LOGIT_GAP for g in gaps[step + 1:]),
        f"{name}: the difference is a bf16 near-tie (fp32 gaps under "
        f"{NEAR_TIE_LOGIT_GAP})",
    )
    return False


def serve_phase(seed):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpu_parallel.cluster import Frontend, FrontendConfig
    from tpu_parallel.daemon import (
        EXIT_CLEAN,
        DaemonConfig,
        DaemonHTTPServer,
        ServingDaemon,
        load_state,
    )
    from tpu_parallel.models import GPTLM, gpt2_125m
    from tpu_parallel.models.generate import generate
    from tpu_parallel.obs.registry import MetricRegistry
    from tpu_parallel.serving import SchedulerConfig, ServingEngine

    log("== serve: ServingEngine(gpt2_125m) behind Frontend -> ServingDaemon "
        "-> HTTP, in this process")
    cfg = gpt2_125m(remat=False)
    model = GPTLM(cfg)
    rng = jax.random.PRNGKey(seed)
    params = model.init(
        {"params": rng}, jnp.zeros((1, 16), jnp.int32), train=False
    )["params"]
    prompts = [
        [int(t) for t in np.asarray(jax.random.randint(
            jax.random.fold_in(rng, i), (n,), 1, cfg.vocab_size
        ))]
        for i, n in enumerate(SERVE_PROMPT_LENS)
    ]

    t0 = time.perf_counter()
    refs = [
        [int(t) for t in np.asarray(generate(
            model, params, jnp.asarray(p, jnp.int32)[None, :],
            max_new_tokens=SERVE_NEW_TOKENS,
        ))[0]]
        for p in prompts
    ]
    log(f"  generate() references for {len(prompts)} prompts "
        f"({len(set(SERVE_PROMPT_LENS))} compiles) in "
        f"{time.perf_counter() - t0:.1f}s")

    engines = []

    def frontend_factory(clock):
        # the engine scripts/daemon_bench.py's serve child builds (default
        # buckets, fused tick), plus a chunk budget so the unified tick runs
        engine = ServingEngine(
            model, params, n_slots=SERVE_SLOTS,
            scheduler=SchedulerConfig(max_prefills_per_tick=2),
            prefill_chunk_tokens=SERVE_CHUNK_TOKENS,
        )
        engines.append(engine)  # kept for the engine-side counters below
        return Frontend(
            [engine], router="least", config=FrontendConfig(restart=None),
            clock=clock, registry=MetricRegistry(),
        )

    with tempfile.TemporaryDirectory() as tmp:
        journal = os.path.join(tmp, "journal.jsonl")
        daemon = ServingDaemon(
            frontend_factory, journal,
            config=DaemonConfig(grace_seconds=600.0),
        )
        server = DaemonHTTPServer(daemon).start()
        exit_codes = []
        pump = threading.Thread(
            target=lambda: exit_codes.append(daemon.run()), daemon=True
        )
        pump.start()
        try:
            t0 = time.perf_counter()
            rids = [
                http_json(server.port, "POST", "/v1/submit", {
                    "prompt": p, "max_new_tokens": SERVE_NEW_TOKENS,
                    "dedupe_token": f"smoke-{seed}-{i}",
                })["request_id"]
                for i, p in enumerate(prompts)
            ]
            events = read_sse(server.port, rids[0])
            streamed = [e["token"] for e in events if "token" in e]
            check(
                events[-1].get("finished")
                and events[-1]["finish_reason"] == "length",
                f"SSE stream of {rids[0]} read to its terminal event "
                f"({len(streamed)} tokens)",
            )
            records = []
            for rid in rids:
                while True:
                    rec = http_json(server.port, "GET", f"/v1/result/{rid}")
                    if rec["status"] not in ("queued", "running"):
                        break
                    check(pump.is_alive(), "daemon pump still running")
                    time.sleep(0.05)
                records.append(rec)
            log(f"  {len(rids)} requests over {SERVE_SLOTS} slots served in "
                f"{time.perf_counter() - t0:.1f}s (compiles included); "
                f"{peak_hbm()}")
            cluster = http_json(server.port, "GET", "/statez")["cluster"]
            daemon.request_drain()
            pump.join(timeout=600)
        finally:
            server.stop()

        check(
            all(r["status"] == "finished" and r["finish_reason"] == "length"
                for r in records),
            "every request finished with its full token budget",
        )
        check(streamed == records[0]["tokens"], "SSE tokens equal the record's")
        exact = sum(
            compare_tokens(f"request {i}", model, params, p, r["tokens"], ref)
            for i, (p, r, ref) in enumerate(zip(prompts, records, refs))
        )
        log(f"  {exact}/{len(prompts)} requests token-equal to generate(); "
            "any other differs only at an fp32 near-tie (checked above)")
        counters = {
            k: cluster[k] for k in (
                "replica_deaths", "restarts", "restart_failures", "retries",
                "requeued", "failed", "cancelled", "deadline_sheds",
                "watchdog_kills",
            )
        }
        check(
            not any(counters.values())
            and cluster["finished"] == cluster["submitted"] == len(prompts),
            f"no failure was absorbed: {counters}, finished "
            f"{cluster['finished']}/{cluster['submitted']}",
        )
        summary = engines[0].metrics.summary()
        log(f"  engine: prefill shapes {sorted(engines[0]._prefill_shapes)}, "
            f"prefill_chunks {summary.get('prefill_chunks')}, "
            f"host_dispatches {summary.get('host_dispatches')}")
        check(
            summary.get("prefill_chunks", 0) >= 1,
            "a prompt longer than the chunk budget went through the unified "
            "tick",
        )
        check(
            exit_codes == [EXIT_CLEAN] and not pump.is_alive(),
            "daemon drained to exit code 0",
        )
        check(load_state(journal).clean_shutdown, "journal closed clean")


# -- multichip ----------------------------------------------------------------


def describe_mesh(mesh, mesh_config):
    """Which branch of ``runtime.make_mesh`` laid the devices out: the
    topology-aware ``create_device_mesh``, or the plain reshape it falls back
    to when that raises."""
    import jax
    import numpy as np
    from jax.experimental import mesh_utils

    from tpu_parallel.runtime import AXIS_ORDER

    sizes = mesh_config.resolved(mesh.size).axis_sizes()
    shape = tuple(sizes[a] for a in AXIS_ORDER)
    try:
        want = mesh_utils.create_device_mesh(
            shape, devices=jax.devices(), allow_split_physical_axes=True
        )
        same = np.array_equal(
            np.vectorize(lambda d: d.id)(want),
            np.vectorize(lambda d: d.id)(mesh.devices),
        )
        branch = f"create_device_mesh (layout matches: {same})"
    except (ValueError, AssertionError, NotImplementedError) as exc:
        branch = f"plain reshape — create_device_mesh raised {exc!r}"
    ids = [d.id for d in mesh.devices.flat]
    log(f"  mesh {dict(mesh.shape)} device ids {ids}; built by {branch}")


def check_placement(name, trainer, batch, n_devices, expect_split):
    """Every chip holds its part: of the parameters after init, and of the
    batch the steps consumed."""
    import jax

    check(
        len({d.id for d in trainer.mesh.devices.flat}) == n_devices,
        f"{name}: mesh holds {n_devices} distinct devices",
    )

    def on_every_chip(x):
        return (
            len(x.sharding.device_set) == n_devices
            and len({s.device.id for s in x.addressable_shards}) == n_devices
        )

    leaves = jax.tree_util.tree_leaves(trainer.state.params)
    split = sum(
        1 for x in leaves if x.addressable_shards[0].data.shape != x.shape
    )
    check(
        all(on_every_chip(x) for x in leaves) and (split > 0) == expect_split,
        f"{name}: every chip holds its part of all {len(leaves)} parameter "
        f"arrays ({split} split across chips, the rest replicated)",
    )
    want = tuple(
        dim // (trainer.mesh.shape[axis] if axis else 1)
        for dim, axis in itertools.zip_longest(
            batch.tokens.shape, trainer.batch_spec
        )
    )
    shard_shapes = {s.data.shape for s in batch.tokens.addressable_shards}
    check(
        on_every_chip(batch.tokens) and shard_shapes == {want},
        f"{name}: batch {batch.tokens.shape} sits as {want} shards "
        f"({trainer.batch_spec}) on {n_devices} chips",
    )


def multichip_phase(seed):
    import jax
    from jax.sharding import NamedSharding

    from tpu_parallel.data import lm_batch
    from tpu_parallel.runtime import MeshConfig, make_mesh
    from tpu_parallel.train_lib import Trainer, TrainerConfig

    devices = jax.devices()
    check(len(devices) == 4, f"four chips visible ({len(devices)})")
    log(f"== multichip: gpt2_125m width, depth cut to {MULTICHIP_LAYERS} "
        f"scanned layers (scan_layers=True), global batch {MULTICHIP_BATCH}, "
        f"{MULTICHIP_STEPS} steps a leg, seed {seed}")

    def build(mesh_config, mesh=None, **overrides):
        return Trainer(
            TrainerConfig(
                model="gpt2_125m",
                model_overrides=dict(
                    n_layers=MULTICHIP_LAYERS, scan_layers=True,
                    remat_policy="proj_attn", **overrides,
                ),
                mesh=mesh_config,
                global_batch_size=MULTICHIP_BATCH,
                steps=MULTICHIP_STEPS,
                warmup_steps=2,
                log_every=1,
                seed=seed,
            ),
            mesh=mesh,
        )

    def run(name, trainer, n_devices, expect_split):
        """Init, place the batch in the step's layout, train; returns the
        per-step losses."""
        t0 = time.perf_counter()
        trainer.init()
        batch = jax.device_put(
            host_batch, NamedSharding(trainer.mesh, trainer.batch_spec)
        )
        kernels = trainer.funcs.step_fn.lower(
            trainer.state, None, batch
        ).as_text().count("tpu_custom_call")
        check(kernels > 0, f"{name}: {kernels} tpu_custom_call sites in the step")
        losses = run_trainer(trainer, batch, MULTICHIP_STEPS)
        log(f"  {name}: {time.perf_counter() - t0:.1f}s with compiles; "
            f"chip 0 {peak_hbm()}")
        check_placement(name, trainer, batch, n_devices, expect_split)
        trainer.state = None
        return losses

    log("-- reference: one chip of the four, attn_impl='flash'")
    one = MeshConfig(data=1)
    ref = build(one, mesh=make_mesh(one, devices=devices[:1]), attn_impl="flash")
    cfg = ref.model_config
    host_batch = lm_batch(
        jax.random.PRNGKey(seed), MULTICHIP_BATCH, cfg.seq_len, cfg.vocab_size
    )
    check(
        [d.id for d in ref.mesh.devices.flat] == [devices[0].id],
        f"reference mesh is confined to device {devices[0].id}",
    )
    ref_losses = run("reference", ref, 1, False)

    legs = (
        ("DP data=4", MeshConfig(data=4),
         dict(attn_impl="flash"), TIGHT_LOSS_TOL, False),
        ("FSDPxTP data=2 model=2", MeshConfig(data=2, model=2),
         dict(attn_impl="flash", fsdp=True), LOOSE_LOSS_TOL, True),
        ("PPxDP pipe=2 data=2", MeshConfig(pipe=2, data=2),
         dict(attn_impl="flash"), LOOSE_LOSS_TOL, True),
        ("ring-SPxDP seq=2 data=2", MeshConfig(seq=2, data=2),
         dict(attn_impl="ring"), TIGHT_LOSS_TOL, False),
    )
    for name, mesh_config, overrides, tol, expect_split in legs:
        log(f"-- {name}: {overrides}")
        trainer = build(mesh_config, **overrides)
        describe_mesh(trainer.mesh, mesh_config)
        losses = run(name, trainer, 4, expect_split)
        worst = max(abs(a - b) for a, b in zip(losses, ref_losses))
        check(
            worst < tol,
            f"{name}: per-step loss within {tol} of the one-chip reference "
            f"(largest difference {worst:.5f})",
        )


# -- entry --------------------------------------------------------------------


def main():
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--seed", type=int, default=0,
                        help="seeds every weight and token (default 0)")
    parser.add_argument("--multichip", action="store_true",
                        help="run ONLY the four-chip mesh legs and their "
                             "one-chip reference (needs four chips)")
    args = parser.parse_args()

    from tpu_parallel.runtime import enable_compilation_cache, require_tpu

    t_start = time.perf_counter()
    device = require_tpu()  # before anything is built: no chip, no run
    cache_dir = enable_compilation_cache()
    cache = CacheCounter()
    log(f"device: {device}; compile cache: {cache_dir}")

    if args.multichip:
        multichip_phase(args.seed)
    else:
        train_phase(args.seed)
        serve_phase(args.seed)
    log(f"compile cache {cache_dir}: {cache.hits} hits, {cache.misses} misses; "
        f"total {time.perf_counter() - t_start:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": device["platform"],
        "kind": device["device_kind"],
        "count": device["device_count"],
    }}), flush=True)


if __name__ == "__main__":
    sys.exit(main())
