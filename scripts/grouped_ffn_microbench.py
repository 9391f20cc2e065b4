"""Time one expert layer's grouped FFN alone, on the chip: ``lax.ragged_dot`` x 3 against the streamed kernel.

The tool behind ``ops.grouped_ffn``'s constants (PERF.md section 6, PR 31).
One layer's decode shape by default (a ``[256, 4096]`` rows buffer, 16 held
experts of width 4096, group sizes as the expert cell's counters say: 14 of
16 touched, 32 rows in all, max over mean 1.3), each variant under the
profiler in a trace of its own; it reads the DEVICE time off the trace (all
ops of the program, and the ``ragged-dot`` ops alone, which is what the
benchmark's roofline reader sums), so a host that dispatches slowly cannot
pass for a slow kernel.  Beside the milliseconds: their share of the least
time by ``benchmarks/lib/moe_cost.py``'s bytes and FLOPs, and how far the
streamed output lies from the ``ragged_dot`` one.

It refuses to run off the TPU: a CPU time says nothing about a kernel.

Usage:
    python scripts/grouped_ffn_microbench.py            # the cell's decode shape
    python scripts/grouped_ffn_microbench.py --sweep    # + weight blocks of 1-16 MiB, windows of 32 and 64 rows
    python scripts/grouped_ffn_microbench.py --rows 1024 --sizes 40,25,31,48,20,0,36,28,44,30,33,0,52,38,41,46   # a 512-token prefill's small buffer
"""

import argparse
import json
import os
import re
import shutil
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "benchmarks"))

import jax
import jax.numpy as jnp
from jax import lax

# 14 of 16 touched, 32 rows, max over mean 3 / (32 / 14) = 1.31
CELL_SIZES = (3, 2, 2, 3, 2, 0, 2, 2, 3, 2, 2, 0, 3, 2, 2, 2)
SWEEP_BLOCK_MIB = (1, 2, 8, 16)
SWEEP_WINDOW_ROWS = (32, 64)
REPEATS = 10
KERNELS = re.compile("ragged-dot")


def ragged_dot_ffn(rows, weights, group_sizes):
    w_gate, w_up, w_down = weights
    gate = lax.ragged_dot(rows, w_gate, group_sizes)
    up = lax.ragged_dot(rows, w_up, group_sizes)
    return lax.ragged_dot(jax.nn.silu(gate) * up, w_down, group_sizes)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--d-model", type=int, default=4096)
    ap.add_argument("--width", type=int, default=4096)
    ap.add_argument("--rows", type=int, default=256, help="buffer rows")
    ap.add_argument("--sizes", type=str, default=",".join(map(str, CELL_SIZES)),
                    help="rows of each held expert")
    ap.add_argument("--sweep", action="store_true")
    args = ap.parse_args()

    from lib import flops, moe_cost, xplane
    from lib.peaks import peaks
    from tpu_parallel.ops import grouped_ffn as gf
    from tpu_parallel.runtime import require_tpu

    require_tpu()
    sizes = [int(s) for s in args.sizes.split(",")]
    n, d, w = len(sizes), args.d_model, args.width
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    rows = jax.random.normal(keys[0], (args.rows, d), jnp.bfloat16)
    weights = tuple(
        (jax.random.normal(k, shape, jnp.float32) / shape[1] ** 0.5).astype(jnp.bfloat16)
        for k, shape in zip(keys[1:], ((n, d, w), (n, d, w), (n, w, d)))
    )
    group_sizes = jnp.asarray(sizes, jnp.int32)
    held_rows, touched = sum(sizes), sum(s > 0 for s in sizes)
    least, bound = flops.roofline_seconds(
        moe_cost.routed_experts_cost(
            held_rows, touched, {"d_model": d, "width": w, "bytes_per_value": 2}
        ),
        peaks(jax.devices()[0].device_kind),
    )
    print(json.dumps({"shape": [args.rows, d, w, n], "sizes": sizes,
                      "least_ms": round(least * 1e3, 4), "bound": bound}), flush=True)

    # (variant, the module's constants while it traces: rows of a window,
    # bytes of a weight block); each streamed variant is a function of its
    # own, so that no trace is shared
    derived = (gf.WINDOW_ROWS, gf.WEIGHT_BLOCK_BYTES)
    variants = [("ragged_dot", None), ("streamed", derived)]
    if args.sweep:
        variants += [("streamed", (derived[0], mib << 20)) for mib in SWEEP_BLOCK_MIB]
        variants += [("streamed", (rows_, derived[1])) for rows_ in SWEEP_WINDOW_ROWS]

    want = None
    for name, knobs in variants:
        record = {"variant": name}
        fn = ragged_dot_ffn
        if knobs:
            gf.WINDOW_ROWS, gf.WEIGHT_BLOCK_BYTES = knobs
            record["plan"] = gf.grouped_ffn_plan(args.rows, n, d, w)
            fn = lambda *a: gf.grouped_ffn(*a)  # noqa: E731
        jitted = jax.jit(fn)
        try:
            out = jax.block_until_ready(jitted(rows, weights, group_sizes))
        except Exception as exc:  # noqa: BLE001 — a block the chip refuses
            print(json.dumps({**record, "error": repr(exc)[:300]}), flush=True)
            continue
        finally:
            gf.WINDOW_ROWS, gf.WEIGHT_BLOCK_BYTES = derived
        out = out[:held_rows].astype(jnp.float32)
        if want is None:
            want = out
        record["max_abs_diff_from_ragged_dot"] = float(jnp.abs(out - want).max())
        record["max_abs"] = float(jnp.abs(want).max())
        logdir = tempfile.mkdtemp(prefix="grouped_ffn_microbench_")
        jax.profiler.start_trace(logdir)
        for _ in range(REPEATS):
            res = jitted(rows, weights, group_sizes)
        jax.block_until_ready(res)
        jax.profiler.stop_trace()
        trace = xplane.load(xplane.find_trace(logdir))
        shutil.rmtree(logdir, ignore_errors=True)
        ops = [e for e in trace["devices"][min(trace["devices"])]["ops"]
               if not xplane.CONTAINER.match(e[0])]
        program_ms = xplane.total(xplane.union([(s, e) for _, s, e in ops])) / REPEATS * 1e3
        kernels = [e - s for name_, s, e in ops if KERNELS.search(name_)]
        kernel_ms = sum(kernels) / REPEATS * 1e3
        record.update({
            "program_ms": round(program_ms, 4), "kernels_ms": round(kernel_ms, 4),
            "kernels_a_call": len(kernels) / REPEATS,
            "kernels_roofline_pct": round(100 * least * 1e3 / kernel_ms, 2) if kernel_ms else None,
            "program_roofline_pct": round(100 * least * 1e3 / program_ms, 2),
            "top_ops_ms": sorted(
                ((name_, round((e - s) * 1e3, 4)) for name_, s, e in ops[-len(ops) // REPEATS:]),
                key=lambda t: -t[1])[:6],
        })
        print(json.dumps(record), flush=True)


if __name__ == "__main__":
    main()
