"""Perf sweep for GPT-2 125M on a TPU: batch x remat x attn.

Prints one JSON line per config with tokens/sec/chip and MFU (and the device
it ran on); used to pick bench.py defaults.  Fails without a TPU.  Not part
of the driver contract — a tuning tool.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax


def run_one(
    batch, remat, attn_impl, steps=12, minib=1, scan_layers=True, chunk=0,
    extra=None,
):
    from tpu_parallel.core import compute as compute_metrics
    from tpu_parallel.runtime import MeshConfig
    from tpu_parallel.train_lib import Trainer, TrainerConfig
    from tpu_parallel.utils.profiling import mfu, sync

    overrides = dict(
        dropout_rate=0.0, attn_impl=attn_impl, scan_layers=scan_layers,
        loss_chunk=chunk, **(extra or {}),
    )
    # remat spec: "0" = off, "1"/"full" = full remat, "proj"/"dots" = that policy
    if remat in ("dots", "proj", "proj_attn"):
        overrides.update(remat=True, remat_policy=remat)
    else:
        overrides.update(remat=remat in ("1", "full"))
    config = TrainerConfig(
        model="gpt2_125m",
        model_overrides=overrides,
        mesh=MeshConfig(data=-1),
        global_batch_size=batch,
        num_minibatches=minib,
        steps=steps,
        log_every=10_000,
        donate=True,
    )
    trainer = Trainer(config)
    trainer.init()
    state, metrics = trainer.state, None
    for _ in range(3):
        state, metrics = trainer.funcs.step_fn(state, metrics, trainer.example_batch)
    sync((state, metrics))
    metrics = None
    t0 = time.perf_counter()
    for _ in range(steps):
        state, metrics = trainer.funcs.step_fn(state, metrics, trainer.example_batch)
    sync((state, metrics))
    dt = time.perf_counter() - t0

    tokens_per_sec_chip = (
        batch * trainer.model_config.seq_len * steps / dt / jax.device_count()
    )
    return dict(
        batch=batch,
        remat=remat,
        attn=attn_impl,
        tokens_per_sec_chip=round(tokens_per_sec_chip, 1),
        mfu=round(mfu(tokens_per_sec_chip, trainer.model_config), 4),
        final_loss=round(compute_metrics(metrics)["loss"], 3),
    )


def main():
    from tpu_parallel.runtime import require_tpu

    device = require_tpu()
    combos = []
    for arg in sys.argv[1:]:
        parts = arg.split(",")
        b, r, a = parts[:3]
        minib = int(parts[3]) if len(parts) > 3 else 1
        scan = parts[4] != "0" if len(parts) > 4 else True
        chunk = int(parts[5]) if len(parts) > 5 else 0
        # trailing key=value pairs become raw model-config overrides,
        # e.g. 24,proj_attn,flash,1,1,0,flash_block_q=1024
        extra = {}
        for kv in parts[6:]:
            key, val = kv.split("=", 1)
            try:
                val = int(val)
            except ValueError:
                pass
            extra[key] = val
        combos.append((int(b), r, a, minib, scan, chunk, extra))
    if not combos:
        combos = [(16, "1", "xla", 1, True, 0, {}), (32, "1", "xla", 1, True, 0, {})]
    for batch, remat, attn, minib, scan, chunk, extra in combos:
        try:
            result = run_one(
                batch, remat, attn, minib=minib, scan_layers=scan, chunk=chunk,
                extra=extra,
            )
            result["minib"], result["scan"], result["chunk"] = minib, scan, chunk
            result.update(device)
            if extra:
                result["extra"] = extra
            print(json.dumps(result), flush=True)
        except Exception as e:  # OOM etc — report and keep sweeping
            print(
                json.dumps(
                    dict(
                        batch=batch, remat=remat, attn=attn, minib=minib,
                        scan=scan, chunk=chunk, error=repr(e)[:300],
                    )
                ),
                flush=True,
            )


if __name__ == "__main__":
    main()
