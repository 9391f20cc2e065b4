"""Time the attention kernels alone, on the chip: the flash kernels forward and
backward apart, and (``--decode``) the decode kernel over the stored stripes.

The tool of the tile sweep behind ``ops.flash_attention.flash_plan`` (PERF.md
section 6, PR 27).  For each tile it runs the forward kernel and the backward
kernel(s) of ``tpu_parallel.ops.flash_attention`` at one shape under the
profiler and reads each kernel's DEVICE time off the trace (the ops are
named by a ``jax.named_scope`` per variant), so a host that dispatches slowly
cannot pass for a slow kernel.  Beside the milliseconds it prints their
share of the least time the chip could take: ``benchmarks/lib/flops.py``'s
count for causal attention (2 of its 7 matmuls forward, 5 backward) over
``benchmarks/lib/peaks.py``'s peak for the device.

``--decode`` times ``ops.decode_attention.decode_stripes`` beside XLA's
program of ``models.layers.decode_attention_xla`` at the decode shapes of the
two serving cells whose heads are 128 wide (``DECODE_SHAPES``: the
block-diffusion cell's step at 4 and 8 rows a slot, the expert cell's one row
under its window and without), at tiles 128 / 512 / 1024, with stream lengths
drawn as the cell's traffic draws them and with every stripe full.  A line a
variant: milliseconds a layer-call on the device (all that ran there under
the variant's own trace: the kernel with the few XLA ops around it), the share of the least
time the walked tiles' bytes allow (K and V tiles inside the slots' ranges,
the query and output rows, over the chip's HBM peak), the share of a stripe's
tiles walked, and the largest difference from XLA's output over the rows
that see a key.  It times nothing a cell runs.

It refuses to run off the TPU: a CPU time says nothing about a kernel.

Usage:
    python scripts/attn_microbench.py                     # the train cell's shape, derived tiles
    python scripts/attn_microbench.py --sweep             # + every tile 128-1024 each way
    python scripts/attn_microbench.py --seq 2048 --heads 6 --head-dim 128 --sweep
    python scripts/attn_microbench.py --decode            # the decode kernel beside XLA's program
    python scripts/attn_microbench.py --latent            # the forward kernel at latent attention's widths
    python scripts/attn_microbench.py --fwd-sweep         # the forward kernel over the serving cells' prefill rows

``--fwd-sweep`` is the sweep behind ``flash_plan``'s FORWARD rule (PERF.md
section 6, PR 48): the forward kernel alone at square tiles of 128 / 256 /
512 / 1024, resident and streamed, over the rows that the serving cells with
heads of 128 and more prefill (``FWD_SWEEP_SHAPES``: one prompt a call, each
cell's heads, K/V heads, widths, rule and windows, its buckets and its
slot's last rung).  A line a variant: milliseconds a layer-call, the share of
the least time of the pairs the rule lets a query see, the tiles computed
and masked, and ``derived`` where the variant is the one ``flash_plan``
gives that shape today; the lines also go to
``chiprun_out/attn_fwd_sweep_<unix time>.jsonl``.  ``--cells a,b`` keeps some of the table's rows.

``--latent`` times the FORWARD kernel at latent attention's shape (one prompt,
128 heads, scores at 192 and values at 128; ``LATENT_SEQS``) two ways: the
widths as they are (192 is a block's full last axis) and both padded with
zero columns to 256 (the layout the one-width kernels would need), at the
call's own tile and variant, at other tiles, and through the streamed
kernel (``LATENT_VARIANTS``).  The share is of the least time of the
PUBLISHED widths, the causal half of ``2 x 128 x (192 + 128) x S^2`` FLOPs
(``benchmarks/lib/mla_cost.py``), so padding reads as lost share.
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
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "benchmarks"))

import jax
import jax.numpy as jnp

SWEEP_TILES = (128, 256, 512, 1024)
REPEATS = 5
INTERPRET = False  # a rehearsal off the chip flips this; a measurement never

DECODE_TILES = (128, 512, 1024)
# name: slots, rows a slot, heads, K/V heads, stored positions, block rule,
# windows, and the cell's stream lengths (a lognormal prompt, clipped, plus a
# uniform share of the answer: ``benchmarks/traffic/{blockgen,longshort}.json``)
DECODE_SHAPES = {
    "blockgen_q4": dict(slots=64, new_len=4, heads=32, kv_heads=4,
                        positions=4096, block_len=4, windows=(0,),
                        prompt=(768, 0.8, 64, 3072), answer=512),
    "blockgen_q8": dict(slots=64, new_len=8, heads=32, kv_heads=4,
                        positions=4096, block_len=4, windows=(0,),
                        prompt=(768, 0.8, 64, 3072), answer=512),
    "longshort_q1": dict(slots=32, new_len=1, heads=16, kv_heads=1,
                         positions=8192, block_len=0, windows=(0, 4096),
                         prompt=(1536, 0.9, 128, 7680), answer=512),
}


def decode_main():
    """The decode kernel beside XLA's program, a line a variant."""
    import numpy as np

    from lib.peaks import peaks
    from tpu_parallel.runtime import require_tpu

    require_tpu()
    hbm = peaks(jax.devices()[0].device_kind)["hbm_bytes_per_s"]
    rng = np.random.default_rng(0)
    for name, shape in DECODE_SHAPES.items():
        b, nq, h, h_kv = (shape[k] for k in ("slots", "new_len", "heads", "kv_heads"))
        s, size, d = shape["positions"], shape["block_len"], 128
        keys = jax.random.split(jax.random.PRNGKey(0), 3)
        q = jax.random.normal(keys[0], (b, nq, h, d), jnp.bfloat16)
        k = jax.random.normal(keys[1], (b, s, h_kv, d), jnp.bfloat16)
        v = jax.random.normal(keys[2], (b, s, h_kv, d), jnp.bfloat16)
        median, sigma, low, high = shape["prompt"]
        drawn = np.clip(rng.lognormal(np.log(median), sigma, b), low, high)
        drawn = drawn + rng.uniform(0, shape["answer"], b)
        for lengths_name, lengths in (
            ("traffic", np.minimum(drawn.astype(np.int64), s - nq)),
            ("full", np.full(b, s - nq)),
        ):
            if size:  # a block step's rows start on a block
                lengths = lengths // size * size
            # the aligned table: column j holds position j up to the rows
            # the step has just written
            cols = np.arange(s)[None, :]
            k_pos = jnp.asarray(
                np.where(cols < (lengths + nq)[:, None], cols, -1), jnp.int32
            )
            pos = jnp.asarray(lengths[:, None] + np.arange(nq)[None, :], jnp.int32)
            for window in shape["windows"]:
                run_decode_variants(
                    name, lengths_name, window, (q, k, v, pos, k_pos),
                    size, lengths, hbm,
                )


def run_decode_variants(name, lengths_name, window, operands, size, lengths,
                        hbm):
    """XLA's program and the kernel at every tile over ``operands``, each
    under a trace of its own."""
    import numpy as np

    from lib import xplane, xplane_scopes
    from tpu_parallel.models.layers import _score_scale, decode_attention_xla
    from tpu_parallel.ops import decode_attention as da

    q, k, v, pos, k_pos = operands
    (b, nq, h, d), (s, h_kv) = q.shape, k.shape[1:3]

    def scoped(tag, fn):
        def run(*a):
            with jax.named_scope(tag):
                return fn(*a)
        return jax.jit(run)

    def xla(q, k, v, pos, k_pos):
        return decode_attention_xla(
            q, k, v, pos, window=window, k_positions=k_pos, block_len=size
        )

    def kernel(q, k, v, pos, k_pos, tile):
        lo, hi = da.visible_bounds(pos, window, size)
        return da.decode_stripes(
            q * _score_scale(None, d, q.dtype), k, v, lo, hi, k_pos, tile=tile,
            interpret=INTERPRET,
        )

    jobs = [("mb_dec_xla", None, scoped("mb_dec_xla", xla))]
    jobs += [
        (f"mb_dec_kernel_{tile}", tile,
         scoped(f"mb_dec_kernel_{tile}", functools.partial(kernel, tile=tile)))
        for tile in DECODE_TILES
    ]
    outs = {}
    for tag, _, fn in jobs:
        outs[tag] = np.asarray(
            jax.block_until_ready(fn(*operands)).astype(jnp.float32)
        )  # compile + warm up
    # a trace a job, and ALL the device's busy time in it: some of XLA's ops
    # (the expert cell's shape: half its program) carry no scope to find
    # them by
    seconds = {}
    for tag, _, fn in jobs:
        logdir = tempfile.mkdtemp(prefix="attn_microbench_")
        jax.profiler.start_trace(logdir)
        for _ in range(REPEATS):
            res = fn(*operands)
        jax.block_until_ready(res)
        jax.profiler.stop_trace()
        seconds[tag] = xplane_scopes.by_pattern(
            xplane.find_trace(logdir), [tag]
        )["busy_s"]
        shutil.rmtree(logdir, ignore_errors=True)
    hi = lengths + nq - 1  # the last position a row of the slot sees
    lo = np.maximum(lengths - window + 1, 0) if window else np.zeros_like(hi)
    rows_bytes = 2 * q.size * q.dtype.itemsize  # the rows in, the rows out
    for tag, tile, _ in jobs:
        ms = seconds[tag] / REPEATS * 1e3
        line = {
            "shape": name, "lengths": lengths_name, "window": window,
            "mean_length": round(float(lengths.mean()), 1),
            "path": "kernel" if tile else "xla", "tile": tile,
            "ms_per_layer_call": round(ms, 4),
        }
        if tile:
            walked = int((hi // tile - lo // tile + 1).sum())
            least = (
                walked * tile * h_kv * d * k.dtype.itemsize * 2 + rows_bytes
            ) / hbm * 1e3
            line.update(
                tiles_walked_share=round(walked / (b * (s // tile)), 4),
                floor_ms=round(least, 4),
                floor_pct=round(100 * least / ms, 2) if ms else None,
                max_abs_diff_vs_xla=float(
                    np.abs(outs[tag] - outs["mb_dec_xla"]).max()
                ),
            )
        print(json.dumps(line), flush=True)


LATENT_SEQS = (4096, 8192)
# (label, tile, stream): the call's own choice (what flash_plan derives for
# the row, as for every caller), other tiles, and the streamed kernel
LATENT_VARIANTS = (("own", None, None), ("t256", 256, None),
                   ("t1024", 1024, None), ("streamed512", 512, True))


def latent_main():
    from lib import flops, mla_cost
    from lib.peaks import peaks
    from tpu_parallel.runtime import require_tpu

    fa = importlib.import_module("tpu_parallel.ops.flash_attention")
    require_tpu()
    heads, qk, dv = 128, 192, 128
    mla = {"heads": heads, "qk": qk, "v": dv, "bytes_per_value": 2}
    jobs = []
    for s in LATENT_SEQS:
        keys = jax.random.split(jax.random.PRNGKey(s), 3)
        q, k = (jax.random.normal(kk, (1, heads, s, qk), jnp.bfloat16) for kk in keys[:2])
        v = jax.random.normal(keys[2], (1, heads, s, dv), jnp.bfloat16)
        pad = lambda x: jnp.pad(x, ((0, 0),) * 3 + ((0, 256 - x.shape[-1]),))
        # zero key columns add nothing to a score; the kernel scales by
        # width ** -0.5, so the padded queries carry the rest of the factor
        padded = (pad(q) * jnp.asarray((256 / qk) ** 0.5, q.dtype), pad(k), pad(v))
        ran = set()
        for label, tile, stream in LATENT_VARIANTS:
            # not one program under two labels: programs that differ in their
            # op names alone share one compile-cache entry, and the trace then
            # shows both under the first one's name (PR 47 read its ``own``
            # rows twice over that way, 16.47 and 83.19 ms for 8.23 and 41.60)
            plan = fa.flash_plan(s, qk, block_q=tile, block_k=tile, stream=stream)["fwd"]
            if (plan["block_q"], plan["variant"]) in ran:
                continue
            ran.add((plan["block_q"], plan["variant"]))
            for widths, operands in (("192_128", (q, k, v)), ("256_256", padded)):
                tag = f"mb_latent_{s}_{widths}_{label}"

                def run(q, k, v, tag=tag, tile=tile, stream=stream):
                    with jax.named_scope(tag):
                        return fa.flash_attention_fwd_bhsd(
                            q, k, v, block_q=tile, block_k=tile,
                            stream=stream, interpret=INTERPRET,
                        )

                fn = jax.jit(run)
                try:
                    out = jax.block_until_ready(fn(*operands))
                except Exception as exc:  # noqa: BLE001 - a tile the chip refuses
                    print(json.dumps({"tag": tag, "error": repr(exc)[:160]}), flush=True)
                    continue
                jobs.append((tag, s, widths, label, fn, operands, out))
    logdir = tempfile.mkdtemp(prefix="attn_microbench_")
    jax.profiler.start_trace(logdir)
    for tag, *_, fn, operands, _ in jobs:
        for _ in range(REPEATS):
            res = fn(*operands)
        jax.block_until_ready(res)
    jax.profiler.stop_trace()
    seconds = device_seconds_by_tag(logdir, {tag for tag, *_ in jobs})
    first = {}
    for tag, s, widths, label, _, _, out in jobs:
        least, bound = flops.roofline_seconds(
            mla_cost.prefill_attention_cost(float(s) ** 2, mla),
            peaks(jax.devices()[0].device_kind),
        )
        ms = seconds[tag] / REPEATS * 1e3
        same = first.setdefault(s, out)[..., :dv].astype(jnp.float32)
        print(json.dumps({
            "seq": s, "widths": widths, "variant": label,
            "kernel_ms": round(ms, 4), "least_ms": round(least * 1e3, 4),
            "bound": bound,
            "roofline_pct": round(100 * least * 1e3 / ms, 2) if ms else None,
            "max_diff_from_first": float(jnp.max(jnp.abs(
                out[..., :dv].astype(jnp.float32) - same))),
        }), flush=True)


# the forward sweep's rows (PERF.md section 6, PR 48): one prompt a call,
# name: heads, K/V heads, score and value widths, the rule as the kernels
# take it (1 causal, L > 1 blocks of L), windows, the cell's prefill rows
FWD_SWEEP_SHAPES = {
    "longdoc": dict(heads=128, kv_heads=128, qk=192, v=128, rule=1,
                    windows=(0,), rows=(1024, 2048, 3072, 4096, 6144, 8192)),
    "longshort": dict(heads=16, kv_heads=1, qk=128, v=128, rule=1,
                      windows=(0, 4096),
                      rows=(512, 1024, 1536, 2048, 3072, 4096, 6144, 8192)),
    "blockgen": dict(heads=32, kv_heads=4, qk=128, v=128, rule=4,
                     windows=(0,), rows=(256, 512, 1024, 2048, 3072)),
    "reasoning": dict(heads=32, kv_heads=2, qk=128, v=128, rule=1,
                      windows=(0,), rows=(128, 256, 512, 1024, 2048)),
    # heads of 64 past the 4096 rows no cell prefills (configs/gpt2_125m_long)
    "long64": dict(heads=12, kv_heads=12, qk=64, v=64, rule=1,
                   windows=(0,), rows=(4096, 8192)),
    # no cell's shapes: what tells the head's width from the group as the
    # key of the tile (every head its own K/V at 128; one K/V head at 192;
    # a width of two full lane tiles)
    "mha128": dict(heads=32, kv_heads=32, qk=128, v=128, rule=1,
                   windows=(0,), rows=(1024, 2048, 4096)),
    "gqa192": dict(heads=16, kv_heads=1, qk=192, v=128, rule=1,
                   windows=(0,), rows=(2048, 4096)),
    "mha256": dict(heads=16, kv_heads=16, qk=256, v=256, rule=1,
                   windows=(0,), rows=(2048, 4096)),
}
# a resident variant unrolls its walk: the sweep compiles none past this
FWD_SWEEP_MAX_BODIES = 320


def visible_pairs(rows: int, rule: int, window: int) -> int:
    """(query, key) pairs the rule lets see each other in a row of ``rows``:
    what the two matmuls of the forward have to compute."""
    if window:
        return sum(min(q + 1, window) for q in range(rows))
    if rule > 1:  # a query sees to the end of its block of ``rule``
        return sum((q // rule + 1) * rule for q in range(rows))
    return rows * (rows + 1) // 2


def fwd_sweep_jobs(fa, cells):
    """``(tag, line, cost, fn, operands)`` for every variant of the table's
    rows: the line holds what is known before anything runs, ``cost`` the
    FLOPs and bytes of the pairs the rule lets see each other."""
    for cell in cells:
        shape = FWD_SWEEP_SHAPES[cell]
        h, h_kv, qk, dv, rule = (
            shape[k] for k in ("heads", "kv_heads", "qk", "v", "rule")
        )
        for s in shape["rows"]:
            keys = jax.random.split(jax.random.PRNGKey(s), 3)
            q = jax.random.normal(keys[0], (1, h, s, qk), jnp.bfloat16)
            k = jax.random.normal(keys[1], (1, h_kv, s, qk), jnp.bfloat16)
            v = jax.random.normal(keys[2], (1, h_kv, s, dv), jnp.bfloat16)
            for window in shape["windows"]:
                if window >= s:
                    continue  # the window binds nothing: the causal program
                own = fa.flash_plan(
                    s, max(qk, dv), h // h_kv, causal=rule, window=window
                )["fwd"]
                for tile in SWEEP_TILES:
                    if s % tile or tile % rule:
                        continue
                    for stream in (False, True):
                        plan = fa.flash_plan(
                            s, max(qk, dv), h // h_kv, causal=rule,
                            window=window, block_q=tile, block_k=tile,
                            stream=stream,
                        )["fwd"]
                        if not stream and plan["tiles_computed"] > FWD_SWEEP_MAX_BODIES:
                            continue
                        tag = (f"mb_fs_{cell}_{s}_w{window}_"
                               f"{'s' if stream else 'r'}{tile}")

                        def run(q, k, v, tag=tag, tile=tile, stream=stream,
                                window=window):
                            with jax.named_scope(tag):
                                return fa._flash_fwd(
                                    q, k, v, None, None, block_q=tile,
                                    block_k=tile, interpret=INTERPRET,
                                    causal=rule, window=window, stream=stream,
                                )[0]

                        line = {
                            "cell": cell, "rows": s, "window": window,
                            "tile": tile, "variant": plan["variant"],
                            "tiles_computed": plan["tiles_computed"],
                            "tiles_masked": plan["tiles_masked"],
                            "derived": plan == own,
                        }
                        cost = {
                            "flops": 2 * h * (qk + dv) * visible_pairs(s, rule, window),
                            "bytes": 2 * s * (h + h_kv) * (qk + dv),
                        }
                        yield tag, line, cost, jax.jit(run), (q, k, v)


def device_seconds_by_tag(logdir, tags):
    """Seconds of the device's ops named by each tag in the trace under
    ``logdir``, which is removed (an op carries the innermost
    ``jax.named_scope``'s name, ``<tag>`` or ``<tag>.<n>``)."""
    from lib import xplane

    trace = xplane.load(xplane.find_trace(logdir))
    shutil.rmtree(logdir, ignore_errors=True)
    seconds = collections.Counter()
    for name, start, end in trace["devices"][min(trace["devices"])]["ops"]:
        tag = name.split(".", 1)[0]
        if tag in tags:
            seconds[tag] += end - start
    return seconds


def fwd_sweep_main(cells):
    from lib import flops
    from lib.peaks import peaks
    from tpu_parallel.runtime import require_tpu

    fa = importlib.import_module("tpu_parallel.ops.flash_attention")
    require_tpu()
    peak = peaks(jax.devices()[0].device_kind)
    os.makedirs(os.path.join(REPO, "chiprun_out"), exist_ok=True)
    # a name of its own a call: the chip tool merges a call's files over
    # what an earlier call brought back
    out_path = os.path.join(
        REPO, "chiprun_out", f"attn_fwd_sweep_{int(time.time())}.jsonl"
    )
    for cell in cells:  # a trace a cell: its operands go when it is read
        jobs = []
        for job in fwd_sweep_jobs(fa, [cell]):
            tag, line, _, fn, operands = job
            try:
                jax.block_until_ready(fn(*operands))  # compile + warm up
            except Exception as exc:  # noqa: BLE001 - a tile the chip refuses
                print(json.dumps({**line, "error": repr(exc)[:160]}), flush=True)
                continue
            jobs.append(job)
        logdir = tempfile.mkdtemp(prefix="attn_microbench_")
        jax.profiler.start_trace(logdir)
        for *_, fn, operands in jobs:
            for _ in range(REPEATS):
                res = fn(*operands)
            jax.block_until_ready(res)
        jax.profiler.stop_trace()
        seconds = device_seconds_by_tag(logdir, {tag for tag, *_ in jobs})
        with open(out_path, "a") as out:
            for tag, line, cost, *_ in jobs:
                least, bound = flops.roofline_seconds(cost, peak)
                ms = seconds[tag] / REPEATS * 1e3
                line.update(
                    kernel_ms=round(ms, 4), least_ms=round(least * 1e3, 4),
                    bound=bound,
                    roofline_pct=round(100 * least * 1e3 / ms, 2) if ms else None,
                )
                print(json.dumps(line), flush=True)
                out.write(json.dumps(line) + "\n")


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--seq", type=int, default=1024)
    ap.add_argument("--heads", type=int, default=12)
    ap.add_argument("--kv-heads", type=int, default=None)
    ap.add_argument("--head-dim", type=int, default=64)
    ap.add_argument("--sweep", action="store_true",
                    help="also every tile of 128-1024 each way that divides seq")
    ap.add_argument("--decode", action="store_true",
                    help="the decode kernel over the stored stripes beside "
                         "XLA's program, at the serving cells' decode shapes")
    ap.add_argument("--latent", action="store_true",
                    help="the forward kernel at latent attention's widths "
                         "(192 / 128) beside both padded to 256")
    ap.add_argument("--fwd-sweep", action="store_true",
                    help="the forward kernel at tiles 128-1024, resident and "
                         "streamed, over the serving cells' prefill rows "
                         "(FWD_SWEEP_SHAPES)")
    ap.add_argument("--cells", default=",".join(FWD_SWEEP_SHAPES),
                    help="--fwd-sweep: the table's rows to run, by name")
    args = ap.parse_args()
    if args.decode:
        return decode_main()
    if args.fwd_sweep:
        return fwd_sweep_main(args.cells.split(","))
    if args.latent:
        return latent_main()

    from lib import flops
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
    seconds = device_seconds_by_tag(logdir, {tag for tag, *_ in jobs})

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
