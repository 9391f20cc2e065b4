"""Time the flash-attention kernels alone, on the chip, forward and backward apart.

The tool of the tile sweep behind ``ops.flash_attention.flash_plan`` (PERF.md
section 6, PR 27).  For each tile it runs the forward kernel and the backward
kernel(s) of ``tpu_parallel.ops.flash_attention`` at one shape under the
profiler and reads each kernel's DEVICE time off the trace (the ops are
named by a ``jax.named_scope`` per variant), so a host that dispatches slowly
cannot pass for a slow kernel.  Beside the milliseconds it prints their
share of the least time the chip could take: ``benchmarks/lib/flops.py``'s
count for causal attention (2 of its 7 matmuls forward, 5 backward) over
``benchmarks/lib/peaks.py``'s peak for the device.

It refuses to run off the TPU: a CPU time says nothing about a kernel.

Usage:
    python scripts/attn_microbench.py                     # the train cell's shape, derived tiles
    python scripts/attn_microbench.py --sweep             # + every tile 128-1024 each way
    python scripts/attn_microbench.py --seq 2048 --heads 6 --head-dim 128 --sweep
"""

import argparse
import collections
import functools
import importlib
import json
import os
import shutil
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "benchmarks"))

import jax
import jax.numpy as jnp

SWEEP_TILES = (128, 256, 512, 1024)
REPEATS = 5
INTERPRET = False  # a rehearsal off the chip flips this; a measurement never


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--seq", type=int, default=1024)
    ap.add_argument("--heads", type=int, default=12)
    ap.add_argument("--kv-heads", type=int, default=None)
    ap.add_argument("--head-dim", type=int, default=64)
    ap.add_argument("--sweep", action="store_true",
                    help="also every tile of 128-1024 each way that divides seq")
    args = ap.parse_args()

    from lib import flops, xplane
    from lib.peaks import peaks
    from tpu_parallel.runtime import require_tpu

    # the module, not the function that ``tpu_parallel.ops`` re-exports
    fa = importlib.import_module("tpu_parallel.ops.flash_attention")
    require_tpu()
    b, s, h, d = args.batch, args.seq, args.heads, args.head_dim
    h_kv = args.kv_heads or h
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    q, do = (jax.random.normal(k, (b, h, s, d), jnp.bfloat16) for k in keys[:2])
    k, v = (jax.random.normal(kk, (b, h_kv, s, d), jnp.bfloat16) for kk in keys[2:])

    plan = fa.flash_plan(s, d, h // h_kv)
    print(json.dumps({"shape": [b, s, h, h_kv, d], "plan": plan}), flush=True)
    variants = [("derived", "fwd", plan["fwd"]["block_q"], plan["fwd"]["block_k"]),
                ("derived", "bwd", plan["bwd"]["block_q"], plan["bwd"]["block_k"])]
    if args.sweep:
        tiles = [t for t in SWEEP_TILES if s % t == 0]
        # not the derived tile again: two programs that differ in their op
        # names alone share one compile-cache entry, and the trace then
        # shows both under the first one's name
        variants += [("sweep", p, bq, bk) for bq in tiles for bk in tiles
                     if bq % bk == 0 for p in ("fwd", "bwd")
                     if (bq, bk) != (plan[p]["block_q"], plan[p]["block_k"])]

    def scoped(tag, fn):
        def run(*a):
            with jax.named_scope(tag):
                return fn(*a)
        return jax.jit(run)

    # residuals of one forward, shared by every backward variant
    out, lse = jax.jit(functools.partial(
        fa._flash_fwd, seg_q=None, seg_k=None, block_q=plan["fwd"]["block_q"],
        block_k=plan["fwd"]["block_k"], interpret=INTERPRET))(q, k, v)
    jobs = []
    for kind, which, bq, bk in variants:
        tag = f"mb_{which}_{kind}_{bq}x{bk}"
        if which == "fwd":
            fn = functools.partial(fa._flash_fwd, seg_q=None, seg_k=None,
                                   block_q=bq, block_k=bk, interpret=INTERPRET)
            call = (scoped(tag, fn), (q, k, v))
        else:
            fn = lambda q, k, v, out, lse, do, bq=bq, bk=bk: fa._flash_bwd(  # noqa: E731
                q, k, v, None, None, out, lse, do, block_q=bq, block_k=bk,
                interpret=INTERPRET)
            call = (scoped(tag, fn), (q, k, v, out, lse, do))
        try:
            jax.block_until_ready(call[0](*call[1]))  # compile + warm up
        except Exception as exc:  # noqa: BLE001 — a tile the chip refuses
            print(json.dumps({"tag": tag, "error": repr(exc)[:160]}), flush=True)
            continue
        jobs.append((tag, which, kind, bq, bk) + call)

    logdir = tempfile.mkdtemp(prefix="attn_microbench_")
    jax.profiler.start_trace(logdir)
    for tag, *_, fn, fn_args in jobs:
        for _ in range(REPEATS):
            res = fn(*fn_args)
        jax.block_until_ready(res)
    jax.profiler.stop_trace()
    trace = xplane.load(xplane.find_trace(logdir))
    shutil.rmtree(logdir, ignore_errors=True)
    seconds = collections.Counter()
    for name, start, end in trace["devices"][min(trace["devices"])]["ops"]:
        for tag, *_ in jobs:
            if name == tag or name.startswith(tag + "."):
                seconds[tag] += end - start

    model = {"seq_len": s, "d_model": h * d}
    least_all, bound = flops.roofline_seconds(
        flops.causal_attention_train_cost(b, model),
        peaks(jax.devices()[0].device_kind),
    )
    least = {"fwd": least_all * 2 / 7, "bwd": least_all * 5 / 7}
    for tag, which, kind, bq, bk, *_ in jobs:
        ms = seconds[tag] / REPEATS * 1e3
        print(json.dumps({
            "pass": which, "tiles": kind, "block_q": bq, "block_k": bk,
            "kernel_ms": round(ms, 4),
            "least_ms": round(least[which] * 1e3, 4), "bound": bound,
            "roofline_pct": round(100 * least[which] * 1e3 / ms, 2) if ms else None,
        }), flush=True)


if __name__ == "__main__":
    main()
