"""The block-diffusion cell's comparison ALONE: no engine, no daemon, no
window.  It times nothing and is no cell.

    python3 benchmarks/compare_blockgen.py --seed <n> [--streams 8]
        [--positions 3584] [--steps 4] [--repeats 1] [--control 0|1]
        [--workload serve-sdar_30b_a3b_depth6-blockgen] [--out <file.npz>]

Streams are made from ``--seed`` in the shape the timed path would hand over
(``--streams`` streams of ``--positions`` positions, the mix's longest answer
generated at ``--steps`` denoising steps a block, each block's positions
filled in a seeded order; the ids are random, not a model's, so the four
compared numbers say nothing of a program: they are there to be EQUAL between
two versions of the reference on the same streams) and handed to
``drivers/serve_blockgen.py::compare``, the function the cell's run calls,
with the cell's own files.  The defaults are the worst sample the mix allows.
A repeat prints one JSON line: the four numbers, the seconds, the sampled
peak, the bound, and the runtime's counters; no engine was ever built in this
process, so ``peak_bytes_in_use`` is the comparison's true peak.  ``--out``
keeps what the first pass's numbers were read from (each stream's and step's
best logit, served id's logit and confidence, the rows' norms, and the first
stream's hidden rows whole) for holding two versions against each other.

It is how PR 43 found what held the memory, how the margin is checked when a
``model_config`` PR changes the shapes, and what to run first when the
comparison next runs out of memory.
"""

import argparse
import json
import random
import sys
import time
import types

import run as bench  # benchmarks/run.py: the repo and benchmarks/ on sys.path

CELL = "serve-sdar_30b_a3b_depth6-blockgen"


def make_streams(seed: int, streams: int, positions: int, generated: int,
                 steps: int, block_len: int, vocab: int) -> list:
    """What ``StreamProbe.held()`` gives, made from the seed."""
    from reference import sdar_moe_ref

    rng = random.Random(seed ^ 0xA10E)
    counts = sdar_moe_ref.transfer_counts(block_len, steps)
    at_step = [t for t, n in enumerate(counts) for _ in range(n)]
    prompt = positions - generated
    out = []
    for _ in range(streams):
        fill_steps = []
        for lo in range(0, generated, block_len):
            # a first block that holds a prompt's tail fills what is masked
            order = at_step[:block_len - (prompt % block_len if lo == 0 else 0)]
            rng.shuffle(order)
            fill_steps += order
        fill_steps = fill_steps[:generated]
        out.append(types.SimpleNamespace(
            prompt=tuple(rng.randrange(1, vocab) for _ in range(prompt)),
            tokens=[rng.randrange(1, vocab) for _ in fill_steps],
            fill_steps=fill_steps, denoising_steps=steps,
        ))
    return out


def digest(weights, replayed, shape) -> dict:
    """What a pass's numbers are read from, as host arrays."""
    import numpy as np

    from reference import sdar_moe_ref

    kept = {}
    for s, stream in enumerate(replayed):
        for t, hidden in stream["hidden"].items():
            reads = sdar_moe_ref.read(weights, hidden, stream["tokens"], shape)
            for name, value in zip(("best", "top", "at", "conf"), reads):
                kept[f"s{s}t{t}_{name}"] = value
            rows = np.asarray(hidden)
            kept[f"s{s}t{t}_norm"] = np.linalg.norm(rows, axis=-1)
            if s == 0:
                kept[f"s0t{t}_hidden"] = rows
    return kept


def alone(seed: int, streams=None, positions=None, steps=None, repeats=1,
          control=False, workload=CELL, out=None, root=bench.ROOT,
          bench_dir=bench.BENCH_DIR) -> list:
    """Run the comparison ``repeats`` times on one set of streams; returns
    (and prints) a record a repeat."""
    import jax

    from drivers import serve_blockgen
    from reference import sdar_moe_ref

    manifest = bench.read_json(f"{root}/BENCHMARK.json")
    entry = next(w for w in manifest["workloads"] if w["name"] == workload)
    run = bench.Run(root, bench_dir, manifest, entry, seed, 0.0, 0, control)
    mix = run.traffic
    generated = mix["output_tokens"]["max"]
    streams = streams or run.cell["reference_streams"]
    positions = positions or mix["prompt_tokens"]["max"] + generated
    steps = steps or max(mix["request_knobs"]["denoising_steps"]["values"])
    built = serve_blockgen.describe(run)
    held = make_streams(seed, streams, positions, generated, steps,
                        built.cfg.block_len, built.vocab)
    requests = [{"prompt": list(s.prompt)} for s in held]
    ended = [{"idx": i, "tokens": s.tokens} for i, s in enumerate(held)]
    device = jax.local_devices()[0]
    run.log(f"comparison alone: {workload}, seed {seed}, {streams} streams of "
            f"{positions} positions ({generated} generated at {steps} steps a "
            f"block), {repeats} repeat(s), control {int(control)}; device "
            f"{device.device_kind}")

    kept = {}
    inner = sdar_moe_ref.replay

    def replay(weights, streams, shape, *rest, **kw):
        replayed = inner(weights, streams, shape, *rest, **kw)
        if out and not kept:  # the first pass of the first repeat
            kept.update(digest(weights, replayed, shape))
        return replayed

    records = []
    sdar_moe_ref.replay = replay
    try:
        for repeat in range(repeats):
            run.checks, run.facts = [], {}
            t0 = time.perf_counter()
            serve_blockgen.compare(run, held, ended, requests, built)
            seconds = time.perf_counter() - t0
            stats = device.memory_stats() or {}
            records.append({
                "repeat": repeat, "seconds": seconds,
                "numbers": {c["name"]: c["value"] for c in run.checks},
                "control": run.facts.get("control"),
                **run.facts.get("comparison_memory", {}),
                **{k: stats.get(k) for k in
                   ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")},
            })
            print(json.dumps(records[-1]), flush=True)
    finally:
        sdar_moe_ref.replay = inner
    if out:
        import numpy as np

        np.savez_compressed(out, **kept)
    return records


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--streams", type=int, default=None)
    parser.add_argument("--positions", type=int, default=None)
    parser.add_argument("--steps", type=int, default=None)
    parser.add_argument("--repeats", type=int, default=1)
    parser.add_argument("--control", type=int, choices=(0, 1), default=0)
    parser.add_argument("--workload", default=CELL)
    parser.add_argument("--out", default=None)
    args = parser.parse_args(argv)
    from tpu_parallel.runtime import enable_compilation_cache

    enable_compilation_cache()
    alone(args.seed, args.streams, args.positions, args.steps, args.repeats,
          bool(args.control), args.workload, args.out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
