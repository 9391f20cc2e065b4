"""Benchmark: GPT-2 125M training throughput on a TPU.

One process that measures on the TPU JAX finds, or fails: no CPU fallback,
no retry, no older result.  Prints ONE JSON line:

    {"metric": "tokens/sec/chip", "value": N, "unit": "tokens/sec/chip",
     "vs_baseline": M, "mfu": U, "platform": "tpu", "device_kind": "...",
     "device_count": C, ...}

``vs_baseline`` is measured MFU divided by the 0.40 north-star target from
BASELINE.json (the reference publishes no numbers of its own — BASELINE.md).
The peak comes from ``utils.profiling.PEAK_FLOPS_BY_KIND``; a device that is
not in that table is an error, not an assumed 197e12.
"""

import json
import time


def main():
    from tpu_parallel.runtime import (
        MeshConfig,
        enable_compilation_cache,
        require_tpu,
    )

    device = require_tpu()
    enable_compilation_cache()

    import jax

    from tpu_parallel.core import compute as compute_metrics
    from tpu_parallel.train_lib import Trainer, TrainerConfig
    from tpu_parallel.utils.profiling import (
        peak_flops,
        sync,
        transformer_flops_per_token,
    )

    peak = peak_flops(jax.devices()[0])
    n_chips = device["device_count"]
    # The shape earlier rounds tuned on a v5e (not measured on the current
    # machine — PERF.md): flash kernels (tiles from the shape), "proj_attn" remat, layers
    # unrolled, 256 rows a chip accumulated over 16 passes of 16 rows.  16
    # rows a pass is also what fits: the compiler refuses 32 (16.6 GB of the
    # chip's 15.75 GB) and 64 (24.9 GB) for this step (CHANGES.md, PR 22).
    batch, steps, minib = 256 * n_chips, 12, 16
    config = TrainerConfig(
        model="gpt2_125m",
        model_overrides=dict(
            dropout_rate=0.0,
            remat=True,
            remat_policy="proj_attn",
            attn_impl="flash",
            scan_layers=False,
        ),
        mesh=MeshConfig(data=-1),
        global_batch_size=batch,
        num_minibatches=minib,
        steps=steps,
        log_every=10_000,  # no intermediate logging inside the timed loop
        donate=True,
    )
    trainer = Trainer(config)
    trainer.init()

    tokens_per_step = batch * trainer.model_config.seq_len

    # warmup: both step programs (metrics None / carried) compile here
    state, metrics = trainer.state, None
    for _ in range(3):
        state, metrics = trainer.funcs.step_fn(state, metrics, trainer.example_batch)
    sync((state, metrics))

    metrics = None  # drop warmup-step sums so final_loss covers timed steps only
    t0 = time.perf_counter()
    for _ in range(steps):
        state, metrics = trainer.funcs.step_fn(state, metrics, trainer.example_batch)
    sync((state, metrics))
    dt = time.perf_counter() - t0
    final_loss = compute_metrics(metrics)["loss"]

    tokens_per_sec_chip = tokens_per_step * steps / dt / n_chips
    mfu = tokens_per_sec_chip * transformer_flops_per_token(trainer.model_config) / peak

    print(
        json.dumps(
            {
                "metric": "tokens/sec/chip",
                "value": round(tokens_per_sec_chip, 1),
                "unit": "tokens/sec/chip",
                "vs_baseline": round(mfu / 0.40, 4),
                "mfu": round(mfu, 4),
                "model": config.model,
                "params_m": round(trainer.num_params / 1e6, 1),
                **device,
                "global_batch": batch,
                "seq_len": trainer.model_config.seq_len,
                "steps_timed": steps,
                "final_loss": round(final_loss, 4),
            }
        ),
        flush=True,
    )


if __name__ == "__main__":
    main()
