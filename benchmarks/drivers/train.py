"""Driver of the training cells: ``Trainer.train()`` under a seeded feed.

Set-up builds ONE ``Trainer`` the way ``train.build_trainer`` does
(``TrainerConfig.from_config_dict`` of the cell's trainer fields), on a mesh
over the first ``chips`` devices, puts the benchmark's own weights into its
state, and drives it through its first steps with the window's own call
(``Trainer.train(batch_iter=..., steps=...)``) and the window's own feed (a
seeded synthetic batch a step, every row different).  The same object then
runs the window: calls of ``steps_per_call`` steps until ``--seconds`` have
passed, the clock read after ``Trainer.train`` has waited for the state.

After the window the state is freed and the plain reference follows the
same first steps in float32: each step's loss, the norm of the first
gradient as the optimizer got it (from Adam's first moment after one step:
``mu = 0.1 * g``), and the norm of the parameters' change after them,
the last two by the worst leaf.
"""

import os
import shutil
import time

import jax
import jax.numpy as jnp

from lib import weights, xplane
from reference import gpt2_ref

REFERENCE_STEPS = 2
TRACED_STEPS = 3
ADAM_B1 = 0.9


def make_batch(key, step: int, rows: int, seq_len: int, vocab: int):
    """One seeded next-token batch: tokens ``[rows, seq_len + 1]`` drawn
    uniformly from the real vocabulary, every row its own draw."""
    return jax.random.randint(
        jax.random.fold_in(key, step), (rows, seq_len + 1), 0, vocab
    )


class Feed:
    """The input pipeline: batch ``i`` is a function of the seed and ``i``,
    made on the device, placed in the step's layout."""

    def __init__(self, trainer, seed: int, vocab: int):
        from jax.sharding import NamedSharding

        from tpu_parallel.core.state import TextBatch

        cfg = trainer.model_config
        rows = trainer.config.global_batch_size
        sharding = NamedSharding(trainer.mesh, trainer.batch_spec)
        key = weights.seed_key(seed)

        def build(step):
            toks = make_batch(key, step, rows, cfg.seq_len, vocab)
            return TextBatch(
                tokens=toks[:, :-1], targets=toks[:, 1:],
                loss_mask=jnp.ones((rows, cfg.seq_len), jnp.float32),
                positions=jnp.broadcast_to(
                    jnp.arange(cfg.seq_len), (rows, cfg.seq_len)
                ),
            )

        self._build = jax.jit(build, out_shardings=sharding)
        self.step = 0

    def __iter__(self):
        return self

    def __next__(self):
        batch = self._build(jnp.int32(self.step))
        self.step += 1
        return batch


def per_step(running_means):
    """Per-step losses from the running means ``Trainer.train`` logs."""
    out, prev = [], 0.0
    for k, mean in enumerate(running_means, 1):
        out.append(k * mean - prev)
        prev = k * mean
    return out


def adam_mu(opt_state):
    """The first-moment tree inside an optax chain's state."""
    found = []

    def walk(node):
        if hasattr(node, "mu") and hasattr(node, "nu"):
            found.append(node.mu)
        elif isinstance(node, (tuple, list)):
            for child in node:
                walk(child)

    walk(opt_state)
    if len(found) != 1:
        raise RuntimeError(f"expected one Adam state, found {len(found)}")
    return found[0]


def worst_leaf_gap(prog: dict, ref: dict):
    """The widest gap between the program's and the reference's norm of a
    leaf, against the reference's norm of that leaf or of the median leaf,
    whichever is larger.  Returns ``(gap, leaf name)``."""
    names = sorted(ref)
    floor = sorted(ref[n] for n in names)[len(names) // 2]
    gaps = {
        n: abs(prog[n] - ref[n]) / max(ref[n], floor, 1e-30) for n in names
    }
    worst = max(gaps, key=gaps.get)
    return gaps[worst], worst


def run(run) -> None:
    from tpu_parallel.obs import Tracer
    from tpu_parallel.obs.tracer import NULL_TRACER
    from tpu_parallel.runtime import make_mesh
    from tpu_parallel.train_lib import Trainer, TrainerConfig

    cell, config = run.cell, run.config
    chips = run.entry["chips"]
    cd = dict(cell["trainer"])
    cd["model"] = config["registry"]
    cd["model_overrides"] = weights.model_overrides(
        config, **cd.get("model_overrides", {})
    )
    cd["global_batch_size"] = run.traffic["rows_per_step"]
    cd["seed"] = run.seed & 0x7FFFFFFF
    cd["log_every"] = 1 << 30  # the loop's own log points fence the device
    trainer_config = TrainerConfig.from_config_dict(cd)
    trainer = Trainer(trainer_config, mesh=make_mesh(
        trainer_config.mesh, devices=jax.devices()[:chips]
    ))
    cfg = trainer.model_config
    rows, seq = trainer.config.global_batch_size, cfg.seq_len
    if seq != run.traffic["seq_len"]:
        raise SystemExit(
            f"traffic states sequence {run.traffic['seq_len']}, the model {seq}"
        )
    if trainer.mesh.size != chips:
        raise SystemExit(
            f"the cell asks for {chips} chips, the mesh holds {trainer.mesh.size}"
        )
    vocab = config["model"]["vocab_real"]
    run.facts.update(
        rows=rows, seq_len=seq, chips=chips,
        passes=trainer.config.num_minibatches, model=config["model"],
    )
    run.log(f"trainer: {trainer.num_params / 1e6:.1f}M parameters, mesh "
            f"{dict(trainer.mesh.shape)}, {rows} rows a step in "
            f"{trainer.config.num_minibatches} passes, sequence {seq}")

    # -- set-up: the program's state, the benchmark's weights ---------------
    t_phase = [time.perf_counter()]

    def phase(what):
        now = time.perf_counter()
        run.log(f"set-up: {what} {now - t_phase[0]:.1f}s "
                f"({now - run.t_process:.1f}s since the process began)")
        t_phase[0] = now

    phase("imports and Trainer built")
    trainer.init()
    shardings = jax.tree.map(lambda x: x.sharding, trainer.state.params)
    abstract = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), trainer.state.params
    )
    trainer.state = trainer.state.replace(
        params=weights.make_params(run.seed, abstract, out_shardings=shardings)
    )
    feed = Feed(trainer, run.seed, vocab)
    jax.block_until_ready(trainer.state.params)
    phase("state initialised, weights made")

    step_fn = trainer.funcs.step_fn
    if hasattr(step_fn, "lower"):
        first = next(feed)
        mem = step_fn.lower(trainer.state, None, first).compile().memory_analysis()
        run.program_bytes = int(
            mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes
        )
        run.log(f"step program by memory_analysis: arguments "
                f"{mem.argument_size_in_bytes / 1e9:.2f} GB, temporaries "
                f"{mem.temp_size_in_bytes / 1e9:.2f} GB, aliased "
                f"{mem.alias_size_in_bytes / 1e9:.2f} GB")
        feed.step = 0
        phase("step lowered, compiled, sized")

    ref_view = jax.jit(lambda t: weights.leaf_norms_device(
        weights.to_reference(t, cfg.n_heads)
    ))
    means = []
    log_fn = lambda step, m: means.append(m["loss"])
    trainer.config.log_every = 1
    trainer.train(batch_iter=feed, steps=1, log_fn=log_fn)
    losses = [means[-1]]
    phase("first step (metrics None)")
    mu_norms = ref_view(adam_mu(trainer.state.opt_state))
    means.clear()
    trainer.train(batch_iter=feed, steps=REFERENCE_STEPS - 1, log_fn=log_fn)
    losses += per_step(means)
    w0 = weights.make_params(run.seed, abstract, out_shardings=shardings)
    delta_norms = ref_view(
        jax.tree.map(lambda a, b: a - b, trainer.state.params, w0)
    )
    del w0
    prog = {
        "losses": [float(x) for x in losses],
        "grad": {k: float(v) / (1 - ADAM_B1) for k, v in mu_norms.items()},
        "delta": {k: float(v) for k, v in delta_norms.items()},
    }
    phase("further steps and the numbers compared")
    run.log(f"first {REFERENCE_STEPS} losses: {prog['losses']}")
    trainer.config.log_every = 1 << 30
    # one call of the window's length, so that nothing is new inside it
    trainer.train(batch_iter=feed, steps=cell["steps_per_call"])

    phase(f"one call of {cell['steps_per_call']} steps")

    # -- the window ----------------------------------------------------------
    run.values["setup_s"] = time.perf_counter() - run.t_process
    run.log(f"window open: set-up took {run.values['setup_s']:.1f}s")
    run.compiles.active = True
    t0 = time.perf_counter()
    steps = 0
    while time.perf_counter() - t0 < run.seconds:
        trainer.train(batch_iter=feed, steps=cell["steps_per_call"])
        steps += cell["steps_per_call"]
    elapsed = time.perf_counter() - t0
    run.compiles.active = False
    run.attempted, run.failed = steps, 0
    if steps:
        run.values["train_tok_s_chip"] = steps * rows * seq / elapsed / chips
        run.log(f"window: {steps} steps in {elapsed:.3f}s, "
                f"{elapsed / steps * 1e3:.2f} ms a step, "
                f"{run.values['train_tok_s_chip']:.1f} tokens/s/chip")

    if run.trace and steps:
        logdir = os.path.join(run.root, ".bench_trace", run.name)
        shutil.rmtree(logdir, ignore_errors=True)
        jax.profiler.start_trace(logdir)
        with jax.profiler.TraceAnnotation("bench_window"):
            trainer.train(batch_iter=feed, steps=TRACED_STEPS)
        jax.profiler.stop_trace()
        run.device_trace = xplane.reduce_trace(
            xplane.find_trace(logdir), window_annotation="bench_window"
        )
        run.facts["traced_steps"] = TRACED_STEPS
        shutil.rmtree(logdir, ignore_errors=True)
        for n, chip in run.device_trace["chips"].items():
            if chip["ops"]:
                run.log(f"chip {n}: busy {chip['busy_s']:.4f}s of "
                        f"{chip['window_s']:.4f}s, collectives "
                        f"{chip['collective_s']:.4f}s, exposed "
                        f"{chip['collective_exposed_s']:.4f}s")
        tracer = Tracer()
        trainer.tracer = tracer
        trainer.train(batch_iter=feed, steps=TRACED_STEPS)
        trainer.tracer = NULL_TRACER
        run.spans = [
            (s.name, s.start, s.end, dict(s.attrs)) for s in tracer.spans if s.end is not None
        ]
    run.read_memory()

    # -- the reference, once the program's state is freed -------------------
    trainer.state = None
    del trainer, feed
    t_ref = time.perf_counter()
    compare(run, prog, abstract, cfg.n_heads, rows, seq, vocab, cd)
    run.log(f"reference and comparison: {time.perf_counter() - t_ref:.1f}s")


def reference_steps(run, abstract, n_heads, rows, seq, vocab, cd, precision):
    """The first steps, followed by the plain reference in ``precision``."""
    params = weights.to_reference(
        weights.make_params(run.seed, abstract, dtype=jnp.float32), n_heads
    )
    start = params
    key = weights.seed_key(run.seed)
    adam = gpt2_ref.AdamW(params, {
        "grad_clip": cd["grad_clip"], "learning_rate": cd["learning_rate"],
        "warmup_steps": cd["warmup_steps"], "steps": cd["steps"],
        "weight_decay": cd["weight_decay"],
    })
    out = {"losses": []}
    for step in range(REFERENCE_STEPS):
        t_step = time.perf_counter()
        toks = make_batch(key, step, rows, seq, vocab)
        loss, grads = gpt2_ref.loss_and_grads(
            params, toks[:, :-1], toks[:, 1:],
            run.cell["reference_block_rows"], precision,
        )
        params, clipped = adam.step(params, grads)
        out["losses"].append(float(loss))
        run.log(f"reference ({precision}) step {step}: "
                f"{time.perf_counter() - t_step:.1f}s")
        if step == 0:
            out["grad"] = weights.leaf_norms(clipped)
    out["delta"] = weights.leaf_norms(
        jax.tree.map(lambda a, b: a - b, params, start)
    )
    return out


def compare(run, prog, abstract, n_heads, rows, seq, vocab, cd) -> None:
    limits = run.cell["limits"]
    ref = reference_steps(
        run, abstract, n_heads, rows, seq, vocab, cd, "float32"
    )
    run.log(f"reference losses: {ref['losses']}")

    # A key bias shifts every score of a row alike, so its true gradient is
    # zero and Adam turns the rounding noise there into a full-sized update:
    # leaves whose reference gradient is under a hundredth of the median
    # leaf's are left out of the update comparison (and named).
    norms = sorted(ref["grad"].values())
    live = [n for n, g in ref["grad"].items() if g >= 0.01 * norms[len(norms) // 2]]
    dead = sorted(set(ref["grad"]) - set(live))
    run.log(f"leaves without a gradient, left out of update_norm_gap: {dead}")

    def numbers(side):
        loss_gap = max(
            abs(a - b) for a, b in zip(side["losses"], ref["losses"])
        )
        grad_gap, grad_leaf = worst_leaf_gap(side["grad"], ref["grad"])
        delta_gap, delta_leaf = worst_leaf_gap(
            {n: side["delta"][n] for n in live},
            {n: ref["delta"][n] for n in live},
        )
        return {
            "loss_gap": loss_gap, "grad_norm_gap": grad_gap,
            "update_norm_gap": delta_gap,
        }, (grad_leaf, delta_leaf)

    got, leaves = numbers(prog)
    run.log(f"worst leaves: gradient {leaves[0]}, update {leaves[1]}")
    for name, value in got.items():
        run.check(name, value, limits[name])
    if run.control:
        low = reference_steps(
            run, abstract, n_heads, rows, seq, vocab, cd,
            run.cell["control_precision"],
        )
        ctl, _ = numbers(low)
        run.log("control " + run.cell["control_precision"] + ": "
                + " ".join(f"{k}={v:.6g}" for k, v in ctl.items()))
        run.facts["control"] = ctl
