"""Attribute full-step time across components by substitution.

Times the full train step with attention swapped between {xla, flash, none}
(``none`` passes V through, keeping every shape and the surrounding
projections identical), which yields the *in-model* cost of each attention
implementation by subtraction.

This was written when the profiler returned no per-op device events; on the
v5e it now does (``scripts/profile_step.py``, CHANGES.md PR 22), so per-op
attribution should come from the trace and that reason for this script is
gone.  Substitution still answers a different question — what the step costs
WITHOUT a component, including what XLA re-fuses around the hole.

Usage: python scripts/bisect_step.py [batch] [remat] [variants...]
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax


def run_one(batch, remat, attn_variant, steps=12):
    import tpu_parallel.models.layers as layers
    from tpu_parallel.runtime import MeshConfig
    from tpu_parallel.train_lib import Trainer, TrainerConfig
    from tpu_parallel.utils.profiling import sync

    orig = layers.causal_attention
    attn_impl = "xla"
    if attn_variant == "flash":
        attn_impl = "flash"
    elif attn_variant == "none":
        layers.causal_attention = lambda q, k, v, segment_ids=None, window=0: v
    elif attn_variant != "xla":
        raise ValueError(f"unknown attention variant: {attn_variant!r}")

    overrides = dict(dropout_rate=0.0, attn_impl=attn_impl)
    if remat in ("dots", "proj", "proj_attn"):
        overrides.update(remat=True, remat_policy=remat)
    else:
        overrides.update(remat=remat in ("1", "full"))
    try:
        config = TrainerConfig(
            model="gpt2_125m",
            model_overrides=overrides,
            mesh=MeshConfig(data=-1),
            global_batch_size=batch,
            steps=steps,
            log_every=10_000,
            donate=True,
        )
        trainer = Trainer(config)
        trainer.init()
        state, metrics = trainer.state, None
        for _ in range(3):
            state, metrics = trainer.funcs.step_fn(
                state, metrics, trainer.example_batch
            )
        sync((state, metrics))
        t0 = time.perf_counter()
        for _ in range(steps):
            state, metrics = trainer.funcs.step_fn(
                state, metrics, trainer.example_batch
            )
        sync((state, metrics))
        dt = (time.perf_counter() - t0) / steps
        print(
            json.dumps(
                {
                    "batch": batch,
                    "remat": remat,
                    "attn": attn_variant,
                    "step_ms": round(dt * 1e3, 2),
                }
            ),
            flush=True,
        )
    except Exception as e:
        print(
            json.dumps(
                {"batch": batch, "remat": remat, "attn": attn_variant,
                 "error": repr(e)[:140]}
            ),
            flush=True,
        )
    finally:
        layers.causal_attention = orig


def main():
    batch = int(sys.argv[1]) if len(sys.argv) > 1 else 16
    remat = sys.argv[2] if len(sys.argv) > 2 else "proj"
    variants = sys.argv[3:] or ["xla", "flash", "none"]
    for v in variants:
        run_one(batch, remat, v)


if __name__ == "__main__":
    main()
