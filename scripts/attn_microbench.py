"""Microbenchmark attention implementations at bench shapes on the real chip.

Times fwd+bwd of the XLA reference path vs the Pallas flash kernel across
block sizes, standalone (outside the full model), to locate the attention
share of the MFU gap.  Prints one JSON line per variant.

Usage: python scripts/attn_microbench.py [batch] [seq] [heads] [head_dim]
"""

import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp


def main():
    b = int(sys.argv[1]) if len(sys.argv) > 1 else 16
    s = int(sys.argv[2]) if len(sys.argv) > 2 else 1024
    h = int(sys.argv[3]) if len(sys.argv) > 3 else 12
    d = int(sys.argv[4]) if len(sys.argv) > 4 else 64

    from tpu_parallel.models.layers import causal_attention
    from tpu_parallel.ops.flash_attention import flash_attention

    key = jax.random.PRNGKey(0)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, s, h, d), jnp.bfloat16)
    k = jax.random.normal(kk, (b, s, h, d), jnp.bfloat16)
    v = jax.random.normal(kv, (b, s, h, d), jnp.bfloat16)

    # causal FLOPs: 2 matmuls (QK^T, AV) x 2*s*s*d x 0.5 (triangle), x3.5 bwd
    flops = 3.5 * b * h * (2 * 2 * s * s * d * 0.5)

    def bench(name, fn, **kw):
        def loss(q, k, v):
            return jnp.sum(fn(q, k, v).astype(jnp.float32))

        step = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
        try:
            out = step(q, k, v)
            jax.block_until_ready(out)
            n = 20
            t0 = time.perf_counter()
            for _ in range(n):
                out = step(q, k, v)
            jax.block_until_ready(out)
            # end the timed region on a value the host has actually read
            float(jnp.sum(out[0].astype(jnp.float32)))
            dt = (time.perf_counter() - t0) / n
            print(
                json.dumps(
                    {
                        "impl": name,
                        **kw,
                        "ms": round(dt * 1e3, 3),
                        "tflops": round(flops / dt / 1e12, 1),
                    }
                ),
                flush=True,
            )
        except Exception as e:  # compile failures shouldn't kill the sweep
            print(json.dumps({"impl": name, **kw, "error": repr(e)[:120]}), flush=True)

    bench("xla", causal_attention)
    for bq, bk in [(128, 128), (256, 128), (256, 256), (512, 256), (512, 512), (1024, 512), (512, 1024), (1024, 1024)]:
        if bq > s or bk > s:
            continue
        bench(
            "flash",
            functools.partial(flash_attention, block_q=bq, block_k=bk),
            bq=bq,
            bk=bk,
        )


if __name__ == "__main__":
    main()
